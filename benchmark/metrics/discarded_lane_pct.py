from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "discarded_lane_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Of the lanes the window's decode steps computed for an occupant, those whose occupant had gone by the time the step was read (EOS, a cancel, the numeric guard: found one launch late): discarded_lanes over it plus slot_steps (ring columns; tdn_gen_discarded_lanes_total).",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "discarded_lanes", "slot_steps")
    lanes = d["discarded_lanes"] + d["slot_steps"] if d else 0
    if not lanes:
        return None
    return 100.0 * d["discarded_lanes"] / lanes
