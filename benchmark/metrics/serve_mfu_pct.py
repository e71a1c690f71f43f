from benchmark.harness.readers import served_flops

METRIC = {
    "name": "serve_mfu_pct",
    "unit": "%",
    "layer": "model step",
    "source": "host_clock",
    "why": "Useful FLOPs of every prompt and output token processed in the window (live keys only) over the window times the chip's bf16 peak: the whole step's share, which bounds any kernel's claim.",
    "moves": "out_tokens_per_s",
}


def read(run):
    if run.client is None or run.peaks is None or not run.window_s:
        return None
    flops = served_flops(run)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"]) if flops else None
