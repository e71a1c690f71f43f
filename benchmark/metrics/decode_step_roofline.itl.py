from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("decode_step_roofline")

METRIC = {
    **_BASE.METRIC,
    "name": "decode_step_roofline.itl",
    "moves": "itl_p95_ms",
    "why": "decode_step_roofline for a cell held end to end by itl_p95_ms: the step is the part of every gap that the chunk does not take.",
}

read = _BASE.read
