from benchmark.harness.sched_ring import per_event_ms

METRIC = {
    "name": "queue_wait_mean_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Submit to slot bind, mean over the requests bound in the window (queue_wait_ns over binds; tdn_gen_queue_wait_seconds_total): the wait for a free slot.",
    "moves": "out_tokens_per_s",
}


def read(run):
    return per_event_ms(run, "queue_wait_ns", "binds")
