"""How often the program and the reference select different blocks.

    python3 benchmark/tools/selection_agreement.py <cell> [--seed N] [--queries Q]

The first layer of `minicpm-sala`'s cut is a `minicpm4` layer, so its
input is the embedding alone: one random prompt of the cell's length
goes through that layer's q/k projections in the program's arithmetic
(bfloat16, `tpu_dist_nn.models.sala`) and in the reference's (float32,
`highest`), the compressed keys are made on each side, and the last Q
query positions select their blocks.  Prints, over (query, K/V group)
pairs: the share whose selected sets differ, the mean count of blocks
that differ among the top-k, and the reference's margin at the k-th
block where they differ and where they agree.  Needs no server; on the
chip it takes about a minute.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=2048)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import lookup
    from tpu_dist_nn.models import sala

    cell = lookup.Cell(args.cell)
    cfgd, ref = cell.config, cell.reference
    cfg = sala.load_model_config(cell.driver().config_path(cell))
    s = ref.sizes(cfgd)
    if s["mixers"][0] != "minicpm4":
        raise SystemExit("the configuration's first layer is not minicpm4")
    T, Q = int(cell.params["prompt_len"]), args.queries
    T -= T % s["blk"]
    Q = min(Q, T)
    tokens = np.random.default_rng(args.seed).integers(0, s["V"], T)
    embed = ref.top_weight(cfgd, args.seed, "embed", "float32")
    x = embed[jnp.asarray(tokens)] * s["scale_emb"]
    del embed
    w = ref.layer_weights(cfgd, args.seed, 0, "float32")
    t = jnp.arange(T - Q, T)

    with jax.default_matmul_precision("highest"):
        q, k, _, _ = ref._projections(x, w, s, None, s["Hq"], s["G"], s["Dh"])
        want, want_scores = jax.jit(
            lambda q, k: ref.select(q, ref.compressed_keys(k, s), t, s,
                                    T // s["blk"]))(q[T - Q:], k)
    want, want_scores = np.asarray(want), np.asarray(want_scores)
    del q, k

    low = jnp.dtype(cfg.param_dtype)
    blk = jax.tree.map(lambda a: a.astype(low), w)

    @jax.jit
    def program(x, blk):
        q, k, _, _ = sala._sparse_qkv(x.astype(low), blk, cfg)
        ck, fresh = sala._new_compressed(
            jnp.zeros((s["G"], s["Dh"], T), low), k, 0, cfg, T // s["stride"])
        ck = jnp.where(fresh[None, None, :], ck, jnp.zeros_like(ck))
        scores = sala._einsum32("cghd,gdj->cghj", q[T - Q:], ck) \
            / np.sqrt(s["Dh"])
        return sala.select_blocks(scores, t, cfg, T)

    got = np.asarray(program(x, blk))
    pairs = want.shape[0] * want.shape[1]
    differ = (got != want).any(-1)
    swapped = (got & ~want).sum(-1)
    ranked = np.sort(np.where(np.isfinite(want_scores), want_scores, -np.inf),
                     -1)[..., ::-1]
    k_th = min(s["topk"], ranked.shape[-1] - 1)
    margin = ranked[..., k_th - 1] - ranked[..., k_th]
    margin = np.where(np.isfinite(margin), margin, np.nan)
    print(json.dumps({
        "pairs": int(pairs), "queries": [int(T - Q), int(T)],
        "sets_differ_share": float(differ.mean()),
        "blocks_swapped_mean": float(swapped.mean()),
        "blocks_swapped_max": int(swapped.max()),
        "selected_blocks": int(want[-1, 0].sum()),
        "margin_at_kth_where_differ": float(np.nanmedian(margin[differ]))
        if differ.any() else None,
        "margin_at_kth_where_agree": float(np.nanmedian(margin[~differ]))
        if (~differ).any() else None,
        "device": jax.devices()[0].device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
