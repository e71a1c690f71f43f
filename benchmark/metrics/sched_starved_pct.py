from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "sched_starved_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Share of the window's wall time (first to last iteration record) in which nothing was queued on the device and the loop knew it: from the end of its wait for the device to the return of the next iteration's first dispatch, idle for want of work apart (ring column starved_ns; tdn_gen_device_starved_seconds_total). What the loop owns up to of the device's idle share, without a capture: it leaves out the wake after the wait and counts the whole dispatch call.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "starved_ns")
    if d is None or not d["t_end"]:
        return None
    return 100.0 * d["starved_ns"] / 1e9 / d["t_end"]
