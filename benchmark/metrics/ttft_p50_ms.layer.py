from benchmark.harness.stats import percentile

METRIC = {
    "name": "ttft_p50_ms.layer",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "host_clock",
    "why": "ttft_p50_ms as a per-layer reading, for the cells where it holds no bound: with one admission an iteration and the prefill queue loaded to 0.75 the median sits on the edge between one and two iterations of wait and steps by a whole iteration between runs. A metric's name is its own, so the same reading takes this one where it is not end to end.",
    "moves": "out_tokens_per_s",
}


def read(run):
    if run.client is None:
        return None
    v = percentile(run.client.ttft_s, 50)
    return None if v is None else v * 1e3
