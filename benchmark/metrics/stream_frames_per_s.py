from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "stream_frames_per_s",
    "unit": "1/s",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Token frames the handler threads took from their streams a second of the window, first to last iteration record (ring column stream_frames): the rate the server's stream plane runs at, a frame a token unless a handler finds two.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "stream_frames")
    if d is None or not d["t_end"]:
        return None
    return d["stream_frames"] / d["t_end"]
