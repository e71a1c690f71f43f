from benchmark.harness.stats import percentile

METRIC = {
    "name": "itl_p95_ms",
    "unit": "ms",
    "layer": "service",
    "source": "host_clock",
    "why": "95th percentile over every gap between consecutive tokens of one stream in the window.",
}


def read(run):
    if run.client is None:
        return None
    v = percentile(run.client.gaps_s, 95)
    return None if v is None else v * 1e3
