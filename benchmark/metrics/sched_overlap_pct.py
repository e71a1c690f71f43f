from benchmark.harness.sched_ring import window_delta

METRIC = {
    "name": "sched_overlap_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Share of the window's loop iterations whose decode step was launched while the step before it was still unread (iteration ring column steps_ahead; tdn_gen_steps_ahead_total): the device had its next launch queued while the host published the last.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = window_delta(run)
    if d is None or "steps_ahead" not in d or not d["iterations"]:
        return None
    return 100.0 * d["steps_ahead"] / d["iterations"]
