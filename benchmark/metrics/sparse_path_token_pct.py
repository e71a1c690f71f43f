METRIC = {
    "name": "sparse_path_token_pct",
    "unit": "%",
    "layer": "model step",
    "source": "program_counter",
    "why": "Share of the window's prefilled and decoded positions that lie where the model serves them by its block selection and not by the dense fallback (tdn_gen_sparse_positions_total over it plus tdn_gen_dense_positions_total, summed on the host from positions): it reads the traffic, not the executed path, so 0 means the cell's lengths no longer reach the mechanism; that the selection ran and chose well is served_logit_gap_mean's to hold.",
    "moves": "itl_p95_ms",
}


def read(run):
    sparse = run.counters.get("sparse_positions_total")
    dense = run.counters.get("dense_positions_total")
    if sparse is None or dense is None or not sparse + dense:
        return None
    return 100.0 * sparse / (sparse + dense)
