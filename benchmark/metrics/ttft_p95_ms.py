from benchmark.harness.stats import percentile

METRIC = {
    "name": "ttft_p95_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "host_clock",
    "why": "Send to first token at the client, 95th percentile over every request sent in the window; end to end only once an open-loop cell builds a queue.",
    "moves": "ttft_p50_ms",
}


def read(run):
    if run.client is None:
        return None
    v = percentile(run.client.ttft_s, 95)
    return None if v is None else v * 1e3
