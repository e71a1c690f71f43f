from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("decode_steps_per_s")

METRIC = {
    **_BASE.METRIC,
    "name": "decode_steps_per_s.itl",
    "moves": "itl_p95_ms",
    "why": "decode_steps_per_s for a cell held end to end by itl_p95_ms: the loop's iterations a second, whose inverse is the mean gap between tokens of a decoding slot.",
}

read = _BASE.read
