from benchmark.harness.sched_ring import per_iteration_ms

METRIC = {
    "name": "sched_publish_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Phase step.publish: the per-slot token append, stream publish and retire check after a decode step, mean over the window's iterations.",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_iteration_ms(run, "step.publish")
