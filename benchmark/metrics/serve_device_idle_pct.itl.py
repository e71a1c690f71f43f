from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("serve_device_idle_pct")

METRIC = {
    **_BASE.METRIC,
    "name": "serve_device_idle_pct.itl",
    "moves": "itl_p95_ms",
    "why": "serve_device_idle_pct for a cell held end to end by itl_p95_ms: the share of the gap between tokens in which the device waits for the host.",
}

read = _BASE.read
