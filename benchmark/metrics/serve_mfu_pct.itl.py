from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("serve_mfu_pct")

METRIC = {
    **_BASE.METRIC,
    "name": "serve_mfu_pct.itl",
    "moves": "itl_p95_ms",
    "why": "serve_mfu_pct for a cell held end to end by itl_p95_ms: where an iteration carries one prefill chunk and one decode step (19 in 20 here) the gap between tokens IS the iteration, so the useful FLOPs of the window over its length times the bf16 peak are the whole iteration's share of the peak, which bounds any kernel's claim on the gap.",
}

read = _BASE.read
