"""Time the chunk's routed experts on the chip, alone.

One process: for each cell ``models/experts.py:routed`` on one layer of
held experts at that cell's chunk shapes, with the choices drawn from
``--seed`` as the cell's router spreads them (a top-k over Gumbel noise,
the held experts' weight set so that a held expert gets ``pairs`` of the
chunk's choices on average), milliseconds a layer (mean of ``--reps``
calls closed by one ``block_until_ready``) and GB/s over the held
matrices, for each tile height of ``--tiles`` (``PAIR_TILE``, read at
trace time).  Needs a TPU; one JSON line a cell and tile height.

    laguna  A 2048, k 10 of 256, 128 held, D 3072, F 1024, ~80 pairs
    kimi    A 1024, k 8 of 384, 12 held, D 7168, F 2048, ~27 pairs

It calls the layer with the arguments its module's ``routed`` takes
(the chosen ids and weights, or the masked grid of an older tree), so
one copy of it times two trees; run both from one path (compile-cache
keys hold paths):

    python3 tools/experts_sweep.py --tiles 128,256      # on a TPU host
    python3 tools/experts_sweep.py --platform cpu --cells tiny
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (tokens, chosen a token, router width, held, D, F, pairs a held
# expert in a chunk)
CELLS = {"laguna": (2048, 10, 256, 128, 3072, 1024, 80),
         "kimi": (1024, 8, 384, 12, 7168, 2048, 27),
         "tiny": (256, 4, 32, 8, 64, 32, 24)}


def draw(rng, A, k, E, held, pairs):
    """``chosen (A, k)``: a top-k over Gumbel noise with the held
    experts' log-weight bisected until a held expert gets ``pairs``."""
    import numpy as np

    noise = rng.gumbel(size=(A, E))
    on = np.isin(np.arange(E), held)

    def chosen(b):
        return np.argsort(-(noise + b * on), -1)[:, :k]

    lo, hi = -20.0, 20.0
    for _ in range(40):
        mid = (lo + hi) / 2
        if np.isin(chosen(mid), held).sum() / len(held) < pairs:
            lo = mid
        else:
            hi = mid
    return chosen((lo + hi) / 2).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="laguna,kimi")
    ap.add_argument("--tiles", default="128", help="tile heights")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--trace", help="also trace the calls under this "
                    "directory and print their longest operations")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist_nn.models import experts
    from tpu_dist_nn.utils.backend import require_platform

    require_platform(args.platform)  # chip numbers or nothing
    takes_ids = "held" in inspect.signature(experts.routed).parameters
    rng = np.random.default_rng(args.seed)
    for cell in args.cells.split(","):
        A, k, E, N, D, F, pairs = CELLS[cell]
        held = tuple(int(e) for e in np.sort(rng.choice(E, N, False)))
        chosen = jnp.asarray(draw(rng, A, k, E, np.asarray(held), pairs))
        ks = jax.random.split(jax.random.key(args.seed), 4)
        u = jax.random.normal(ks[0], (A, D), jnp.bfloat16)
        w = jax.random.uniform(ks[1], (A, k), jnp.float32, 0.05, 0.3)
        gu = (jax.random.normal(ks[2], (1, N, D, 2 * F), jnp.bfloat16)
              / np.sqrt(D)).astype(jnp.bfloat16)
        dn = (jax.random.normal(ks[3], (1, N, F, D), jnp.bfloat16)
              / np.sqrt(F)).astype(jnp.bfloat16)
        every = jnp.ones((A,), bool)
        held_bytes = N * 3 * D * F * 2
        count = np.bincount(np.searchsorted(held, np.asarray(chosen)).ravel()
                            [np.isin(np.asarray(chosen), held).ravel()],
                            minlength=N)
        for R in (int(x) for x in args.tiles.split(",")):
            experts.PAIR_TILE = R
            if takes_ids:
                fn = jax.jit(lambda u, c, w, gu, dn: experts.routed(
                    u, c, w, held, gu, dn, 0, every, "experts")[0])
            else:
                fn = jax.jit(lambda u, c, w, gu, dn: experts.routed(
                    u, *experts.held_gates(c, w, held), gu, dn, 0, every,
                    "experts")[0])
            out = fn(u, chosen, w, gu, dn).block_until_ready()  # compiles
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = fn(u, chosen, w, gu, dn)
            out.block_until_ready()
            ms = 1e3 * (time.perf_counter() - t0) / args.reps
            if args.trace:
                from benchmark.harness.xplane import reduce_trace

                where = os.path.join(args.trace, f"{cell}-{R}")
                with jax.profiler.trace(where):
                    for _ in range(args.reps):
                        out = fn(u, chosen, w, gu, dn)
                    out.block_until_ready()
                ops = (reduce_trace(where) or {}).get("ops", [])
                print(json.dumps({"cell": cell, "tile": R, "ops_ms": [
                    [n, round(1e3 * t / args.reps, 4)] for n, t in ops[:24]]
                    + [["the rest", round(1e3 * sum(t for _, t in ops[24:])
                                          / args.reps, 4)]]}), flush=True)
            print(json.dumps({
                "cell": cell, "tile": R, "ms": round(ms, 4),
                "gb_per_s": round(held_bytes / ms / 1e6, 1),
                "pairs_mean": round(float(count.mean()), 1),
                "tiles": int(np.sum(-(-count // R))),
                "form": "ids" if takes_ids else "grid",
                "finite": bool(jnp.isfinite(out).all())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
