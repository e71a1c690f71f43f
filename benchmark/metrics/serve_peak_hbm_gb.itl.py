from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("serve_peak_hbm_gb")

METRIC = {
    **_BASE.METRIC,
    "name": "serve_peak_hbm_gb.itl",
    "moves": "itl_p95_ms",
    "why": "serve_peak_hbm_gb for a cell held end to end by itl_p95_ms: the memory the cell holds (weights, slot cache of every kind, the making of the weights), against the floor a cell has to fill.",
}

read = _BASE.read
