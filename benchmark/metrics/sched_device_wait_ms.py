from benchmark.harness.sched_ring import per_iteration_ms

METRIC = {
    "name": "sched_device_wait_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Time one iteration's loop thread stood blocked on the device: phases prefill.fetch + step.fetch (int(tok), np.asarray(toks)), mean over the window's iterations.",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_iteration_ms(run, "prefill.fetch", "step.fetch")
