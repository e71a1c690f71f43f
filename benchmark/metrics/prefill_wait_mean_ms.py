from benchmark.harness.sched_ring import per_event_ms

METRIC = {
    "name": "prefill_wait_mean_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Slot bind to first token, mean over the first tokens of the window (prefill_wait_ns over first_tokens): the loop prefills one slot an iteration, so bound requests stand here while their slot idles.",
    "moves": "out_tokens_per_s",
}


def read(run):
    return per_event_ms(run, "prefill_wait_ns", "first_tokens")
