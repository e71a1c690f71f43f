from benchmark.harness.sched_ring import per_iteration_ms

METRIC = {
    "name": "sched_admit_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Phases reap + admit + bind: cancelled streams freed, the pending queue popped under its lock, rows bound to slots, mean over the window's iterations.",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_iteration_ms(run, "reap", "admit", "bind")
