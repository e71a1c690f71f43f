METRIC = {
    "name": "train_mfu_pct",
    "unit": "%",
    "layer": "trainer",
    "source": "host_clock",
    "why": "Forward and backward FLOPs a token (causal attention counted once, recomputation not counted) times tokens a second over the chip's bf16 peak.",
    "moves": "train_tokens_per_s",
}


def read(run):
    t = run.train
    if not t or not t["steps"] or run.peaks is None:
        return None
    rate = t["steps"] * t["tokens_per_step"] / t["seconds"]
    return 100.0 * rate * run.counts.train_token_flops(t["seq_len"]) / run.peaks["bf16_flops"]
