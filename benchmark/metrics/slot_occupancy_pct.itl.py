from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("slot_occupancy_pct")

METRIC = {
    **_BASE.METRIC,
    "name": "slot_occupancy_pct.itl",
    "moves": "itl_p95_ms",
    "why": "slot_occupancy_pct for a cell held end to end by itl_p95_ms: with one chunk an iteration the slots that are not decoding wait their turn at prefill; a scheduler that admits less shows here while the gap itself stays as it was.",
}

read = _BASE.read
