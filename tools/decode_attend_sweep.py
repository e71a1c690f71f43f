"""Time the SambaY step's shared-K/V attention on the chip, kernel and XLA.

One process, the reasoning cell's shapes (96 slots, 20 K/V heads of 64,
extent 3072, bfloat16) unless told otherwise, ``pos`` evenly spread over
``--pos lo:hi`` as the cell's traffic holds it: the XLA path
``models/sambay.py:_attend_rows`` (the whole extent, masked) and the
Pallas kernel ``kernels/decode_attend.py`` under each ``--tiles`` width,
milliseconds a call (mean of ``--reps`` calls closed by one
``block_until_ready``), GB/s over the LIVE bytes (K and V up to each
slot's ``pos``, whatever was read), the share of the extent the kernel
copies, and the largest difference between the two outputs.
Needs a TPU; one JSON line a configuration.

    chiprun -- python3 tools/decode_attend_sweep.py --tiles 128,256,512
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="96,20,64,3072", help="S,G,d,M")
    ap.add_argument("--pos", default="2048:3071")
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist_nn.kernels import decode_attend as da
    from tpu_dist_nn.models import sambay
    from tpu_dist_nn.utils.backend import require_platform

    require_platform(args.platform)  # chip numbers or nothing
    S, G, d, M = (int(x) for x in args.shape.split(","))
    lo, hi = (int(x) for x in args.pos.split(":"))
    dtype = jnp.dtype(args.dtype)
    ks = jax.random.split(jax.random.key(0), 5)
    draw = lambda k, shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    q = draw(ks[0], (S, G // 2, 2, 2, d))
    K, V = draw(ks[1], (1, S, G, d, M)), draw(ks[2], (1, S, G, d, M))
    k_own, v_own = draw(ks[3], (S, G, d)), draw(ks[4], (S, G, d))
    pos = np.linspace(lo, hi, S).astype(np.int32)
    lam = jnp.float32(0.4)
    live = 2 * G * d * int(pos.sum()) * dtype.itemsize

    def timed(fn, *a):
        out = fn(*a).block_until_ready()  # compiles
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        out.block_until_ready()
        return out, 1e3 * (time.perf_counter() - t0) / args.reps

    def line(path, ms, **more):
        print(json.dumps({"path": path, "ms": round(ms, 4), "live_gb_s":
                          round(live / ms / 1e6, 1), **more}), flush=True)

    xla = jax.jit(lambda q, K, V, ko, vo, pos, lam: sambay._attend_rows(
        q, K[0, :S], V[0, :S], ko, vo,
        jnp.arange(M)[None, :] < pos[:, None], lam))
    ref, ms = timed(xla, q, K, V, k_own, v_own, jnp.asarray(pos), lam)
    ref = np.asarray(ref)
    line("xla", ms)
    for tile in (int(x) for x in args.tiles.split(",")):
        fn = jax.jit(lambda q, K, V, ko, vo, pos, lam: da.attend_rows(
            q, K, V, ko, vo, pos, lam, tile=tile))
        try:
            out, ms = timed(fn, q, K, V, k_own, v_own, jnp.asarray(pos), lam)
        except Exception as e:  # noqa: BLE001: a width the compiler refuses
            print(json.dumps({"path": tile, "error": str(e)[:300]}),
                  flush=True)
            continue
        diff = np.abs(np.asarray(out) - ref)
        line(tile, ms,
             fetched_pct=round(100 * float(
                 da.fetched_tiles(pos).sum()) * 128 / (S * M), 1),
             max_diff=float(diff.max()), nan=bool(np.isnan(diff).any()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
