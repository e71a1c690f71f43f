from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "sched_publish_offcpu_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "One minus the loop thread's CPU time inside step.publish over that phase's wall time in the window (ring column cpu.publish; tdn_gen_loop_publish_cpu_seconds_total): the share of the publish the loop wanted the interpreter and did not have it.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "cpu.publish")
    if d is None or not d["step.publish"]:
        return None
    return 100.0 * (1.0 - d["cpu.publish"] / d["step.publish"])
