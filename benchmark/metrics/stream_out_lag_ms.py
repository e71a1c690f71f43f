from benchmark.harness.sched_ring import per_event_ms

METRIC = {
    "name": "stream_out_lag_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Scheduler's publish of a token to the gRPC handler thread taking it, mean over the frames taken in the window (stream_lag_ns over stream_frames; serving/stream.py).",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_event_ms(run, "stream_lag_ns", "stream_frames")
