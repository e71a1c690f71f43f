from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("prefill_dev_ms")

METRIC = {
    **_BASE.METRIC,
    "name": "prefill_dev_ms.itl",
    "moves": "itl_p95_ms",
    "why": "prefill_dev_ms for a cell held end to end by itl_p95_ms: every other iteration carries a prefill chunk, and the gap between tokens of such an iteration is the step plus the chunk; the chunk's device time grows with its position in the prompt (the keys it expands and attends), and a prompt's last chunk is what itl_p95_ms reads.",
}

read = _BASE.read
