from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("ttft_mean_ms")

METRIC = {
    **_BASE.METRIC,
    "name": "ttft_mean_ms.itl",
    "moves": "itl_p95_ms",
    "why": "ttft_mean_ms for a cell held end to end by itl_p95_ms: a prompt of 16 chunks shares the one chunk an iteration with every other slot's, so the first token waits slots x chunks iterations; what a long-document user waits for, too few requests a window to hold to a bound.",
}

read = _BASE.read
