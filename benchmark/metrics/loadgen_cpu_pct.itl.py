from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("loadgen_cpu_pct")

METRIC = {
    **_BASE.METRIC,
    "name": "loadgen_cpu_pct.itl",
    "moves": "itl_p95_ms",
    "why": "loadgen_cpu_pct for a cell held end to end by itl_p95_ms: a saturated client would stretch the gaps it measures; at 650 frames a second this one idles.",
}

read = _BASE.read
