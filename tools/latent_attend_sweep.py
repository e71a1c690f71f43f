"""Time the Kimi-K2 step's latent attention on the chip, kernel and XLA.

One process, the repository cell's shapes (48 slots of a cache of 50 and
six layers, 64 heads, rows of 576 of which 512 are values, extent 9216,
bfloat16) unless told otherwise, ``pos`` evenly spread over ``--pos
lo:hi`` as the cell's traffic holds it, one layer a call: the XLA path
``models/mla_moe.py:_latent_einsums`` (the whole extent, masked), a
plain read of as many bytes as are live (a reduction over one array),
and the Pallas kernel ``kernels/latent_attend.py`` under each
``--tiles`` width and ``--in-flight`` depth, whole and with its products
taken out (``copies``: the same DMAs, nothing computed), so that a
kernel the HBM bounds (whole = copies) is told from one the MXU bounds.
Milliseconds a call (mean of ``--reps`` calls closed by one
``block_until_ready``), GB/s over the LIVE bytes (each slot's rows up to
its ``pos``, whatever was read), the share of the extent the kernel
copies, and the largest difference between kernel and XLA.  Needs a
TPU; one JSON line a configuration.

    chiprun -- python3 tools/latent_attend_sweep.py --tiles 512,1024
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_LANES = 128


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="48,64,576,512,9216", help="S,H,r,r_kv,M")
    ap.add_argument("--cache", default="6,50", help="layers,slots of the cache")
    ap.add_argument("--pos", default="8192:9215")
    ap.add_argument("--tiles", default="512,1024")
    ap.add_argument("--in-flight", default="2,3,4")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist_nn.kernels import latent_attend as la
    from tpu_dist_nn.models import mla_moe
    from tpu_dist_nn.utils.backend import require_platform

    require_platform(args.platform)  # chip numbers or nothing
    S, H, r, rkv, M = (int(x) for x in args.shape.split(","))
    L, slots = (int(x) for x in args.cache.split(","))
    lo, hi = (int(x) for x in args.pos.split(":"))
    dtype = jnp.dtype(args.dtype)
    scale = 0.1
    ks = jax.random.split(jax.random.key(0), 3)
    draw = lambda k, shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    q, own = draw(ks[0], (S, H, r)), draw(ks[1], (S, r))
    lat = draw(ks[2], (L, slots, 1, r, M))
    pos = np.linspace(lo, hi, S).astype(np.int32)
    layer = jnp.int32(L - 1)
    live = r * int(pos.sum()) * dtype.itemsize

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

    xla = jax.jit(lambda q, lat, layer, own, pos: mla_moe._latent_einsums(
        q, jax.lax.dynamic_slice(
            lat, (layer, 0, 0, 0, 0), (1, S) + lat.shape[2:])[0],
        own, pos, scale)[..., :rkv])
    ref, ms = timed(xla, q, lat, layer, own, jnp.asarray(pos))
    ref = np.asarray(ref)
    line("xla", ms)
    plain = draw(ks[2], (live // dtype.itemsize // _LANES, _LANES))
    _, ms = timed(jax.jit(lambda a: jnp.max(a)), plain)
    line("plain_read", ms)

    whole = la._attend_tile
    for tile in (int(x) for x in args.tiles.split(",")):
        for depth in (int(x) for x in args.in_flight.split(",")):
            for what in ("kernel", "copies"):
                la._attend_tile = whole if what == "kernel" \
                    else (lambda *a, **kw: None)
                la._call.cache_clear()  # the kernel traced anew
                fn = jax.jit(lambda q, lat, layer, own, pos: la.attend_rows(
                    q, lat, layer, own, pos, rkv, scale, tile=tile,
                    in_flight=depth))
                try:
                    out, ms = timed(fn, q, lat, layer, own, jnp.asarray(pos))
                except Exception as e:  # noqa: BLE001: a plan the compiler refuses
                    print(json.dumps({"path": what, "tile": tile, "in_flight":
                                      depth, "error": str(e)[:300]}),
                          flush=True)
                    continue
                more = {}
                if what == "kernel":
                    diff = np.abs(np.asarray(out) - ref)
                    more = {"max_diff": float(diff.max()),
                            "nan": bool(np.isnan(diff).any())}
                line(what, ms, tile=tile, in_flight=depth, fetched_pct=round(
                    100 * float(la.fetched_tiles(pos).sum()) * _LANES
                    / (S * M), 1), **more)
    la._attend_tile = whole
    la._call.cache_clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
