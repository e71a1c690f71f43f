from benchmark.harness.sched_ring import per_iteration_ms

METRIC = {
    "name": "sched_dispatch_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Phases prefill.dispatch + step.dispatch: hook, key and the call into the jitted program until it returns, mean over the window's iterations.",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_iteration_ms(run, "prefill.dispatch", "step.dispatch")
