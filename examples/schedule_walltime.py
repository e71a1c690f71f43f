"""Wall-clock the pipeline-schedule family against the tick model.

VERDICT r4 weak item 2: the zero-bubble family's superiority rested
only on tick accounting + symbolic replay — "no wall-clock measurement
on any backend confirms ticks translate to time (per-branch cost
asymmetry, switch overhead, recompute could eat the margin)". This
experiment supplies the measurement, honestly scoped to what a 1-core
virtual-device box can show:

* The table executors dispatch per-device branches with ``lax.switch``
  (parallel/interleaved.py:381), so on ONE physical core a step's wall
  time is the SUM of taken-branch costs plus per-tick overhead — idle
  ticks are nearly free. A serialized wall-clock therefore CANNOT show
  the bubble advantage directly (that is a property of parallel
  hardware); what it CAN do is validate a measured per-branch cost
  model, which then prices the tick tables into a hardware-honest
  makespan prediction.

* **Branch microbench**: the four executor branch bodies are mirrored
  as standalone jitted programs at the exact chunk widths the
  schedules use — FWD (chunk forward), BWD (forward recompute + full
  vjp, interleaved.py `bwd`), BWD_B (recompute + input grad only,
  `bwd_b` — weight grads DCE'd), BWD_W (recompute + weight grads only,
  `bwd_w`). Measured min-of-R with value-fetch barriers. This exposes
  the asymmetry the tick model ignores: the zero-bubble split pays the
  forward RECOMPUTE twice (once in B, once in W).

* **Tick-table pricing**: for each schedule's real ``ScheduleTables``
  the parallel makespan is ``sum_t max_s c(op[s,t])`` and the
  serialized cost is ``sum_t sum_s c(op[s,t])``.

* **Validation**: the REAL train step (make_pipeline_lm_train_step —
  the same programs `tdn lm --schedule ...` runs) is wall-clocked on
  the 8-virtual-device mesh and compared against the serialized
  prediction; the residual per tick is the measured switch/dispatch +
  collective overhead, reported and folded into the parallel
  prediction.

Matched-granularity pairs (S=4, L=8): {1f1b(v=1), zb(v=1)} at 2
blocks/chunk and {interleaved(v=2), zb-v} at 1 block/chunk.

Prints the whole record as JSON (`--out FILE` writes it there instead
and prints the matched pairs only). Run:
    python examples/schedule_walltime.py [--fast]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from tpu_dist_nn.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_transformer,
)
from tpu_dist_nn.parallel import schedule_table as st  # noqa: E402
from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh  # noqa: E402
from tpu_dist_nn.train.lm_trainer import (  # noqa: E402
    make_pipeline_lm_train_step,
)

S = 4           # pipeline stages
L = 8           # transformer blocks
D_MODEL, N_HEADS, D_FF = 128, 4, 512
SEQ = 128
MICRO_B = 4     # rows per microbatch


def _cfg() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=64, d_model=D_MODEL, n_heads=N_HEADS, n_layers=L,
        d_ff=D_FF, max_seq_len=SEQ,
    )


def _time(fn, *args, reps: int = 5) -> float:
    """min-of-reps seconds; a value fetch is the barrier (repo rule)."""
    out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
        best = min(best, time.monotonic() - t0)
    return best


def _chunk_apply(blocks, x, cfg):
    """Forward through a chunk's block stack (the executor's per-tick
    compute, minus wire/buffer bookkeeping)."""
    from tpu_dist_nn.models.transformer import block_apply

    def body(carry, blk):
        return block_apply(blk, carry, cfg), None

    y, _ = jax.lax.scan(body, x, blocks)
    return y


def branch_costs(cfg, n_blocks: int, reps: int) -> dict:
    """Measured seconds for the four executor branch bodies at this
    chunk width (see module docstring for the mirrored structure)."""
    key = jax.random.key(0)
    params = init_transformer(key, cfg)
    blocks = jax.tree.map(lambda a: a[:n_blocks], params["blocks"])
    x = jax.random.normal(
        jax.random.key(1), (MICRO_B, SEQ, D_MODEL), jnp.float32
    )
    dy = jax.random.normal(jax.random.key(2), x.shape, jnp.float32)

    fwd = jax.jit(lambda b, xx: _chunk_apply(b, xx, cfg))

    def bwd_full(b, xx, cot):       # recompute fwd + full vjp
        y, vjp = jax.vjp(lambda bb, xi: _chunk_apply(bb, xi, cfg), b, xx)
        db, dx = vjp(cot)
        return dx, db

    def bwd_b(b, xx, cot):          # recompute fwd + input grad only
        y, vjp = jax.vjp(lambda xi: _chunk_apply(b, xi, cfg), xx)
        (dx,) = vjp(cot)
        return dx

    def bwd_w(b, xx, cot):          # recompute fwd + weight grads only
        y, vjp = jax.vjp(lambda bb: _chunk_apply(bb, xx, cfg), b)
        (db,) = vjp(cot)
        return db

    # The round-5 cotangent-stash split (parallel/split_backward.py):
    # B = one forward + backbone + dx GEMMs, stashing (act, cot) pairs;
    # W = pure dW GEMMs, no recompute — the executor-side fix the
    # recompute finding motivates, measured here at the same widths.
    from tpu_dist_nn.parallel.split_backward import (
        chunk_backward_split,
        chunk_weight_grads,
    )

    stash_b = jax.jit(
        lambda b, xx, cot: chunk_backward_split(b, xx, cot, cfg)
    )
    _, _, wstash = stash_b(blocks, x, dy)

    return {
        "F": _time(jax.jit(fwd), blocks, x, reps=reps),
        "B": _time(jax.jit(bwd_full), blocks, x, dy, reps=reps),
        "B_split_dx": _time(jax.jit(bwd_b), blocks, x, dy, reps=reps),
        "B_split_dw": _time(jax.jit(bwd_w), blocks, x, dy, reps=reps),
        "B_stash": _time(stash_b, blocks, x, dy, reps=reps),
        "W_gemm": _time(jax.jit(chunk_weight_grads), wstash, reps=reps),
    }


def price_tables(tb: st.ScheduleTables, c: dict) -> dict:
    """Tick-table pricing under measured branch costs."""
    cost = np.zeros_like(tb.op, dtype=np.float64)
    cost[tb.op == st.FWD] = c["F"]
    cost[tb.op == st.BWD] = c["B"]
    cost[tb.op == st.BWD_B] = c["B_split_dx"]
    cost[tb.op == st.BWD_W] = c["B_split_dw"]
    per_tick_max = cost.max(axis=0)
    return {
        "ticks": int(tb.ticks),
        "bubble_ticks": int(tb.bubble_ticks),
        "parallel_makespan_s": float(per_tick_max.sum()),
        "serialized_work_s": float(cost.sum()),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer reps / one M (CI smoke)")
    ap.add_argument("--out", default=None,
                    help="write the record here, not to stdout")
    args = ap.parse_args()
    reps = 2 if args.fast else 5
    ms = (8,) if args.fast else (8, 16)
    cfg = _cfg()

    record = {
        "task": "schedule family wall-clock vs tick model "
                "(VERDICT r4 weak item 2)",
        "config": {
            "S": S, "L": L, "d_model": D_MODEL, "d_ff": D_FF, "seq": SEQ,
            "micro_batch": MICRO_B, "Ms": list(ms),
            "backend": "8-virtual-device CPU mesh (1 physical core): "
                       "serialized wall validates the branch-cost "
                       "model; the parallel makespan column is that "
                       "model priced over the real tick tables",
        },
        "branch_costs_s": {},
        "schedules": {},
    }

    # Branch costs at both chunk widths used below.
    for width in (2, 1):
        record["branch_costs_s"][f"{width}_blocks"] = branch_costs(
            cfg, width, reps
        )
    bc = record["branch_costs_s"]
    # The asymmetries the tick model ignores, stated explicitly:
    b2 = bc["2_blocks"]
    record["asymmetry"] = {
        "split_overhead_2blocks":
            (b2["B_split_dx"] + b2["B_split_dw"]) / b2["B"],
        "note": "B_split_dx + B_split_dw vs combined B: >1 means the "
                "zero-bubble split pays real extra compute (the "
                "forward recompute happens in BOTH halves)",
    }

    mesh = build_mesh(MeshSpec(stage=S))
    opt = optax.sgd(1e-3)

    # (name, schedule, v, table builder, branch-cost overrides): the
    # zb-stash arm prices BWD_B/BWD_W with the cotangent-stash costs —
    # and is also MEASURED, since make_pipeline_lm_train_step runs the
    # real stash executor for schedule="zb-stash".
    arms = [
        ("1f1b", "1f1b", 1,
         lambda M: st.build_interleaved_1f1b(S, 1, M), None),
        ("interleaved", "interleaved", 2,
         lambda M: st.build_interleaved_1f1b(S, 2, M), None),
        ("zb", "zb", 1, lambda M: st.build_zero_bubble(S, 1, M), None),
        ("zb-v", "zb-v", 2, lambda M: st.build_zb_v(S, M), None),
        ("zb-stash", "zb-stash", 1,
         lambda M: st.build_zero_bubble(S, 1, M),
         {"B_split_dx": "B_stash", "B_split_dw": "W_gemm"}),
    ]
    for name, sched, v, build, cost_overrides in arms:
        chunk_w = L // (S * v)
        c = dict(record["branch_costs_s"][f"{chunk_w}_blocks"])
        if cost_overrides:
            for dst, src in cost_overrides.items():
                c[dst] = c[src]
        per_m = {}
        for M in ms:
            tb = build(M)
            pricing = price_tables(tb, c)
            step = make_pipeline_lm_train_step(
                mesh, cfg, S, M, opt, schedule=sched, num_virtual=v,
            )
            params = init_transformer(jax.random.key(3), cfg)
            from tpu_dist_nn.parallel.transformer_pipeline import (
                shard_blocks,
                shard_blocks_interleaved,
                shard_blocks_vshape,
            )

            if sched == "zb-v":
                p = dict(params,
                         blocks=shard_blocks_vshape(params["blocks"], S))
            elif sched in ("interleaved", "zb", "zb-stash"):
                p = dict(params, blocks=shard_blocks_interleaved(
                    params["blocks"], S, v))
            else:
                p = dict(params, blocks=shard_blocks(params["blocks"], S))
            tokens = jnp.asarray(
                np.random.default_rng(M).integers(
                    0, 64, (MICRO_B * M, SEQ + 1)
                ),
                jnp.int32,
            )
            o = opt.init(p)
            measured = _time(
                lambda pp, oo, tt: step(pp, oo, tt)[2], p, o, tokens,
                reps=reps,
            )
            overhead_per_tick = (
                (measured - pricing["serialized_work_s"]) / pricing["ticks"]
            )
            per_m[f"M{M}"] = {
                **pricing,
                "measured_serialized_s": round(measured, 4),
                "serialized_model_error":
                    round(measured / pricing["serialized_work_s"] - 1, 3),
                "overhead_per_tick_s": round(overhead_per_tick, 6),
                "parallel_makespan_with_overhead_s": round(
                    pricing["parallel_makespan_s"]
                    + max(overhead_per_tick, 0.0) * pricing["ticks"], 4
                ),
            }
        record["schedules"][name] = {
            "num_virtual": v, "blocks_per_chunk": chunk_w, **per_m,
        }

    # Ratios at the largest M, within MATCHED-GRANULARITY pairs only —
    # raw tick counts across different chunk widths are incomparable.
    # "canonical" prices ticks with the ZB paper's idealized weights
    # (F=1, combined B=2, split B=1, W=1 — no recompute); "measured"
    # prices them with this box's branch costs (split halves each pay
    # the forward recompute). The gap between the two columns IS the
    # answer to "does the tick model translate to time".
    Mk = f"M{ms[-1]}"
    canon = {"F": 1.0, "B": 2.0, "B_split_dx": 1.0, "B_split_dw": 1.0}

    def canon_makespan(name):
        _, sched, v, build, _ov = next(a for a in arms if a[0] == name)
        tb = build(ms[-1])
        return price_tables(tb, canon)["parallel_makespan_s"]

    record["matched_pairs"] = {}
    for a, b in (("1f1b", "zb"), ("interleaved", "zb-v"),
                 ("1f1b", "zb-stash")):
        chunk_w = record["schedules"][a]["blocks_per_chunk"]
        c = record["branch_costs_s"][f"{chunk_w}_blocks"]
        # Price the split schedule's tables with the COTANGENT-STASH
        # branch costs (split_backward.py: B_stash carries the one
        # forward + backbone + dx, W_gemm is pure dW GEMMs) — the
        # executor-side fix this experiment motivates, priced before
        # it is wired into the executor.
        _, _, _, build, _ov2 = next(x for x in arms if x[0] == b)
        tb = build(ms[-1])
        stash_costs = dict(c)
        stash_costs["B_split_dx"] = c["B_stash"]
        stash_costs["B_split_dw"] = c["W_gemm"]
        stash_pricing = price_tables(tb, stash_costs)
        base = record["schedules"][a][Mk]
        record["matched_pairs"][f"{b}_vs_{a}"] = {
            "canonical_tick_model": round(
                canon_makespan(b) / canon_makespan(a), 4
            ),
            "measured_cost_parallel_makespan": round(
                record["schedules"][b][Mk]
                ["parallel_makespan_with_overhead_s"]
                / base["parallel_makespan_with_overhead_s"], 4
            ),
            "stash_split_parallel_makespan": round(
                stash_pricing["parallel_makespan_s"]
                / base["parallel_makespan_s"], 4
            ),
            "granularity_blocks_per_chunk": chunk_w,
        }
    if args.out is None:
        print(json.dumps(record, indent=2))
        return 0
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(json.dumps(record["matched_pairs"], indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
