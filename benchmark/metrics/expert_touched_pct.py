METRIC = {
    "name": "expert_touched_pct",
    "unit": "%",
    "layer": "model step",
    "source": "program_counter",
    "why": "Of the (decode step, expert layer, held expert) triples of the window, the share in which the expert got at least one (token, expert) pair (tdn_gen_expert_touched_total over tdn_gen_expert_visits_total, counted on the device): 1 - (1 - k/E)^slots with even routing, 63.5 at 48 slots. A step that skips untouched experts reads that share of the held matrices, a deployment's step (32 times the tokens an expert) all of them.",
    "moves": "itl_p95_ms",
}


def read(run):
    visits = run.counters.get("expert_visits")
    touched = run.counters.get("expert_touched")
    if not visits or touched is None:
        return None
    return 100.0 * touched / visits
