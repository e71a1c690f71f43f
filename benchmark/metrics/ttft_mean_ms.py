METRIC = {
    "name": "ttft_mean_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "host_clock",
    "why": "Mean send-to-first-token time over every request sent in the window: the first token waits whole iterations, so the median steps where the mean moves smoothly. While a request waits in the prefill queue its slot idles, so the wait comes out of the tokens streamed.",
    "moves": "out_tokens_per_s",
}


def read(run):
    if run.client is None or not run.client.ttft_s:
        return None
    return 1e3 * sum(run.client.ttft_s) / len(run.client.ttft_s)
