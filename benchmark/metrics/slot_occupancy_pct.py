METRIC = {
    "name": "slot_occupancy_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "slot_steps_total over steps_total times slots, deltas across the window: the share of slot lanes that decoded.",
    "moves": "out_tokens_per_s",
}


def read(run):
    c = run.counters
    if not c.get("steps_total"):
        return None
    return 100.0 * c["slot_steps_total"] / (c["steps_total"] * c["slots"])
