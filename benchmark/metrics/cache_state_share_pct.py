METRIC = {
    "name": "cache_state_share_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Recurrent-state bytes over all bytes of the slot cache (tdn_gen_cache_bytes by kind): what a slot copy, a prefix tier or a preemption snapshot moves that does not grow with position.",
    "moves": "itl_p95_ms",
}


def read(run):
    kinds = run.counters.get("cache_bytes") or {}
    total = sum(kinds.values())
    if not total or "state" not in kinds:
        return None
    return 100.0 * kinds["state"] / total
