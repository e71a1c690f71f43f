from benchmark.harness.lookup import metric_reader

_BASE = metric_reader("out_tokens_per_s")

METRIC = {
    **_BASE.METRIC,
    "name": "out_tokens_per_s.layer",
    "layer": "generation scheduler and slot cache",
    "moves": "itl_p95_ms",
    "why": "out_tokens_per_s as a per-layer reading, for a cell where it holds no bound: a 51 s window admits some 30 requests of 128-256 tokens, whose count alone moves it by more than half its bound between seeds.",
}

read = _BASE.read
