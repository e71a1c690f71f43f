from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "server_cpu_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "CPU seconds of every thread of the serving process (loop, handlers, gRPC's and the runtime's) over the window's wall time, first to last iteration record (ring column proc_cpu_ns, time.process_time_ns at an iteration's end every tenth of a second; tdn_gen_process_cpu_seconds_total; 100 is one core): how near the server's one interpreter is to full.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "proc_cpu_ns")
    if d is None or not d["t_end"]:
        return None
    return 100.0 * d["proc_cpu_ns"] / 1e9 / d["t_end"]
