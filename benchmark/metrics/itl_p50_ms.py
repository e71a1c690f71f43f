from benchmark.harness.stats import percentile

METRIC = {
    "name": "itl_p50_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "host_clock",
    "why": "Median gap between consecutive tokens of one stream in the window: one scheduler iteration.",
    "moves": "itl_p95_ms",
}


def read(run):
    if run.client is None:
        return None
    v = percentile(run.client.gaps_s, 50)
    return None if v is None else v * 1e3
