from benchmark.harness.stats import percentile

METRIC = {
    "name": "ttft_p50_ms",
    "unit": "ms",
    "layer": "service",
    "source": "host_clock",
    "why": "Send to first streamed token at the client, median over every request sent in the window.",
}


def read(run):
    if run.client is None:
        return None
    v = percentile(run.client.ttft_s, 50)
    return None if v is None else v * 1e3
