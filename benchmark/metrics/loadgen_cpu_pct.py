METRIC = {
    "name": "loadgen_cpu_pct",
    "unit": "%",
    "layer": "load generator",
    "source": "host_clock",
    "why": "CPU time of the generator child over the window, in percent of one core: a generator short of CPU reads its tokens late and sends its next request late, and must not be read as a slow or a fast server.",
    "moves": "out_tokens_per_s",
}


def read(run):
    if run.loadgen_cpu_s is None or not run.window_s:
        return None
    return 100.0 * run.loadgen_cpu_s / run.window_s
