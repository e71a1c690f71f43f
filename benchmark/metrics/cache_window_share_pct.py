METRIC = {
    "name": "cache_window_share_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Window-ring bytes over all bytes of the slot cache (tdn_gen_cache_bytes by kind): what the window layers hold a slot whatever its length, beside the one K/V cache that grows with position and the scan states.",
    "moves": "itl_p95_ms",
}


def read(run):
    kinds = run.counters.get("cache_bytes") or {}
    total = sum(kinds.values())
    if not total or "window" not in kinds:
        return None
    return 100.0 * kinds["window"] / total
