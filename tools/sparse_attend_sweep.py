"""Time the chunk's causal attention on the chip, kernel and loop.

One process, the benchmark cell's shapes unless told otherwise: for
each ``--start`` the XLA loop and the Pallas kernel
``kernels/sparse_attend.py`` under each ``--tiles``, milliseconds a call
(mean of ``--reps`` calls closed by one ``block_until_ready``), the
kernel's share of the MXU's peak over the tiles it visits, and the
largest difference between the two outputs.  Needs a TPU; one JSON line
a configuration.

``--mode sparse`` (the long-document cell's block-masked attention: C
2048, 2 K/V groups of 16 heads of 128, extent 33 024, blocks of 64)
times ``models/sala.py:_attend_chunk`` against tilings ``tq x KT x
unroll``; ``--mode dense`` (a full layer of the 16k repository cell: C
2048, 8 K/V groups of 6 heads of 128, extent 17 408, no selection)
times ``models/laguna.py:_full_chunk_loop`` against tilings ``tq x KT``.

    chiprun -- python3 tools/sparse_attend_sweep.py --tiles 512x768x4,256x384x4
    python3 tools/sparse_attend_sweep.py --mode dense --tiles 512x1024,512x512
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("sparse", "dense"), default="sparse")
    ap.add_argument("--shape", help="C,G,group,Dh,M[,block_size]; "
                    "default the mode's cell")
    ap.add_argument("--start", help="default 0,14336,30720 (sparse), "
                    "0,6144,14336 (dense)")
    ap.add_argument("--tiles", help="tq x KT [x unroll]; default 512x768x4 "
                    "(sparse), 512x1024 (dense)")
    ap.add_argument("--density", type=float, default=0.2,
                    help="share of blocks selected, at random")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.device import PEAKS
    from tpu_dist_nn.kernels import sparse_attend as sa
    from tpu_dist_nn.models import laguna, sala
    from tpu_dist_nn.utils.backend import require_platform

    require_platform(args.platform)  # chip numbers or nothing
    # The published peak of the device the calls run on; none off the chip.
    peak = PEAKS.get(jax.devices()[0].device_kind, {}).get("bf16_flops")
    dense = args.mode == "dense"
    C, G, g, Dh, M, *blk = (int(x) for x in (args.shape or (
        "2048,8,6,128,17408" if dense else "2048,2,16,128,33024,64")
    ).split(","))
    starts = args.start or ("0,6144,14336" if dense else "0,14336,30720")
    specs = args.tiles or ("512x1024" if dense else "512x768x4")
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (C, G, g, Dh), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (G, Dh, M), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (G, Dh, M), jnp.float32).astype(jnp.bfloat16)
    if not dense:
        blk = blk[0]
        picked = jax.random.bernoulli(ks[3], args.density, (C, G, M // blk))
        cfg = types.SimpleNamespace(block_size=blk)

    def timed(fn, *a):
        out = fn(*a).block_until_ready()  # compiles
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        out.block_until_ready()
        return out, 1e3 * (time.perf_counter() - t0) / args.reps

    if dense:
        loop = jax.jit(lambda q, k, v, sel, s: laguna._full_chunk_loop(
            q, k, v, s))
    else:
        loop = jax.jit(lambda q, k, v, sel, s: sala._attend_chunk(
            q, k, v, sel, s + jnp.arange(C), cfg))
    for start in (int(x) for x in starts.split(",")):
        sel = None
        if not dense:
            t = start + np.arange(C)
            own = np.arange(M // blk)[None, :] == (t // blk)[:, None]
            sel = picked | jnp.asarray(own)[:, None, :]
        ref, ms = timed(loop, q, k, v, sel, jnp.int32(start))
        ref = np.asarray(ref, np.float32).reshape(C, -1)
        print(json.dumps({"path": "xla_loop", "start": start,
                          "ms": round(ms, 3)}), flush=True)
        for spec in specs.split(","):
            tq, kt, *unroll = (int(x) for x in spec.split("x"))
            fn = jax.jit(lambda q, k, v, sel, s: sa.attend_chunk(
                q, k, v, sel, s, None if dense else blk, tile=(tq, kt),
                **({"unroll": unroll[0]} if unroll else {})))
            try:
                out, ms = timed(fn, q, k, v, sel, jnp.int32(start))
            except Exception as e:  # noqa: BLE001: a tiling the compiler refuses
                print(json.dumps({"path": spec, "start": start,
                                  "error": str(e)[:300]}), flush=True)
                continue
            visited = sum((start + (i + 1) * tq - 1) // kt + 1
                          for i in range(C // tq)) * tq * kt
            flops = 4 * G * g * Dh * visited
            diff = np.abs(np.asarray(out, np.float32).reshape(C, -1) - ref)
            print(json.dumps({
                "path": spec, "start": start, "ms": round(ms, 3),
                "mxu_pct": peak and round(100 * flops / peak / (ms / 1e3), 1),
                "max_diff": float(diff.max()),
                "nan": bool(np.isnan(diff).any())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
