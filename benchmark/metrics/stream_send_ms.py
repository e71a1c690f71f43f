from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "stream_send_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "A handler thread away with a frame, mean over the sends that ended in the window: from TokenStream.next_event handing it a batch to its next call of next_event (encoding, gRPC's write, the wait for its completion; ring columns stream_send_ns over stream_sends; tdn_gen_stream_send_*; span tdn.stream.send). With stream_out_lag_ms a frame's whole life on the server.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "stream_send_ns", "stream_sends")
    if d is None or not d["stream_sends"]:
        return None
    return d["stream_send_ns"] / d["stream_sends"] / 1e6
