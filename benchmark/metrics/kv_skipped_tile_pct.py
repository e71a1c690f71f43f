from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "kv_skipped_tile_pct",
    "unit": "%",
    "layer": "kernels",
    "source": "program_counter",
    "why": "Of the 128-lane position tiles of the shared K/V extent over the slots of the window's decode steps, those the step left in HBM past a slot's frontier (ring columns kv_tiles_skipped over it plus kv_tiles_visited; tdn_gen_step_kv_tiles_total{state}); only a model whose step stops at the frontier counts any.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "kv_tiles_visited", "kv_tiles_skipped")
    tiles = d["kv_tiles_visited"] + d["kv_tiles_skipped"] if d else 0
    if not tiles:
        return None
    return 100.0 * d["kv_tiles_skipped"] / tiles
