from benchmark.harness.sched_ring import HOST_PHASES, per_iteration_ms

METRIC = {
    "name": "sched_host_ms",
    "unit": "ms",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Host time of one scheduler iteration: every loop phase but idle and the two fetches (tdn.gen.* spans; tdn_gen_loop_seconds_total), mean over the window's iterations.",
    "moves": "itl_p95_ms",
}


def read(run):
    return per_iteration_ms(run, *HOST_PHASES)
