from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "sched_cpu_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "The loop thread's CPU seconds over the window's wall time, first to last iteration record (ring column cpu_ns; 100 is one core). A part of server_cpu_pct.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "cpu_ns")
    if d is None or not d["t_end"]:
        return None
    return 100.0 * d["cpu_ns"] / 1e9 / d["t_end"]
