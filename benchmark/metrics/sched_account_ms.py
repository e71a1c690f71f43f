from benchmark.harness.sched_ring import per_iteration_ms

METRIC = {
    "name": "sched_account_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Phases step.account + prefill.post: guard, counters, goodput tally, spans, first-token publish, mean over the window's iterations.",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_iteration_ms(run, "step.account", "prefill.post")
