METRIC = {
    "name": "out_tokens_per_s",
    "unit": "tokens/s",
    "layer": "service",
    "source": "host_clock",
    "why": "Every token streamed to a client inside the window, over the window's length: what a batch of waiting callers pays for.",
}


def read(run):
    if run.client is None or not run.client.tokens:
        return None
    return run.client.tokens_per_s()
