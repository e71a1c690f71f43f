"""Time a chunk's expanded latent attention on the chip, kernel and loop.

One process, the repository cell's shapes (C 1024, 64 heads, latent rows
of 512 + 64, heads of 128 + 64 and 128, extent 9216) unless told
otherwise: for each ``--start`` the XLA loop
``models/mla_moe.py:_expanded_loop`` and the Pallas kernel
``kernels/expand_attend.py`` under each ``--tiles`` ``heads a group x
query tile x key tile``, milliseconds a call (mean of ``--reps`` calls
closed by one ``block_until_ready``), the kernel's share of the MXU's
peak over the key tiles it visits (expansion, scores and values of every
visited tile whole: the mask's skipped pairs count as done), and the
largest difference between the two outputs.  Needs a TPU; one JSON line
a configuration.

    chiprun -- python3 tools/expand_attend_sweep.py --tiles 8x256x512,4x512x512
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1024,64,512,128,64,128,9216",
                    help="C,H,r_kv,d_n,d_r,d_v,M")
    ap.add_argument("--start", default="0,3072,7168")
    ap.add_argument("--tiles", default="8x256x512")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.device import PEAKS
    from tpu_dist_nn.kernels import expand_attend as ea
    from tpu_dist_nn.models import mla_moe
    from tpu_dist_nn.utils.backend import require_platform

    require_platform(args.platform)  # chip numbers or nothing
    # The published peak of the device the calls run on; none off the chip.
    peak = PEAKS.get(jax.devices()[0].device_kind, {}).get("bf16_flops")
    C, H, rkv, dn, dr, dv, M = (int(x) for x in args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    ks = jax.random.split(jax.random.key(0), 5)
    q_n = jax.random.normal(ks[0], (C, H, dn), jnp.float32).astype(dtype)
    q_r = jax.random.normal(ks[1], (C, H, dr), jnp.float32).astype(dtype)
    rows = jax.random.normal(ks[2], (1, rkv + dr, M), jnp.float32).astype(dtype)
    wk = (jax.random.normal(ks[3], (rkv, H, dn), jnp.float32)
          / np.sqrt(rkv)).astype(dtype)
    wv = (jax.random.normal(ks[4], (rkv, H, dv), jnp.float32)
          / np.sqrt(rkv)).astype(dtype)
    scale = 1.0 / np.sqrt(dn + dr)

    def timed(fn, *a):
        out = fn(*a).block_until_ready()  # compiles
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        out.block_until_ready()
        return out, 1e3 * (time.perf_counter() - t0) / args.reps

    loop = jax.jit(lambda q_n, q_r, rows, s: mla_moe._expanded_loop(
        q_n, q_r, rows, s + jnp.arange(C), wk, wv, scale))
    kernels = {spec: jax.jit(functools.partial(
        lambda tile, q_n, q_r, rows, s: ea.attend_chunk(
            q_n, q_r, rows, wk, wv, s, scale, tile=tile),
        tuple(int(x) for x in spec.split("x"))))
        for spec in args.tiles.split(",")}
    for start in (int(x) for x in args.start.split(",")):
        ref, ms = timed(loop, q_n, q_r, rows, jnp.int32(start))
        ref = np.asarray(ref, np.float32)
        print(json.dumps({"path": "xla_loop", "start": start,
                          "ms": round(ms, 3)}), flush=True)
        for spec, fn in kernels.items():
            kt = int(spec.split("x")[2])
            try:
                out, ms = timed(fn, q_n, q_r, rows, jnp.int32(start))
            except Exception as e:  # noqa: BLE001: a tiling the compiler refuses
                print(json.dumps({"path": spec, "start": start,
                                  "error": str(e)[:300]}), flush=True)
                continue
            visited = ((start + C - 1) // kt + 1) * kt
            flops = 2 * H * visited * (rkv * (dn + dv) + C * (dn + dr + dv))
            diff = np.abs(np.asarray(out, np.float32) - ref)
            print(json.dumps({
                "path": spec, "start": start, "ms": round(ms, 3),
                "ms_a_tile": round(ms * kt / visited, 4),
                "mxu_pct": peak and round(100 * flops / peak / (ms / 1e3), 1),
                "max_diff": float(diff.max()),
                "nan": bool(np.isnan(diff).any())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
