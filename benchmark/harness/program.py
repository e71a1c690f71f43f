"""The configuration file in the program's own terms."""

from __future__ import annotations


def transformer_config(cfg: dict, remat: bool = False):
    from tpu_dist_nn.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["n_embd"]),
        n_heads=int(cfg["n_head"]), n_layers=int(cfg["n_layer"]),
        d_ff=int(cfg.get("n_inner") or 4 * cfg["n_embd"]),
        max_seq_len=int(cfg["n_positions"]), causal=True,
        compute_dtype=cfg["compute_dtype"], remat=remat)
