from benchmark.harness.sched_ring import HOST_PHASES, window_delta

METRIC = {
    "name": "sched_offcpu_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "One minus the loop thread's CPU time over the wall time of its host phases in the window (the thread burns no CPU in its waits): the share of host time the loop wanted to run and could not (GIL, a lock, a blocking call, descheduled).",
    "moves": "itl_p95_ms",
}


def read(run):
    d = window_delta(run)
    if d is None:
        return None
    wall = sum(d[p] for p in HOST_PHASES)
    return 100.0 * (1.0 - d["cpu_ns"] / wall) if wall else None
