"""Device-resident throughput: chained-in-jit + fetch-barrier.

Methodology (shared with bench.py's ``_time_resident``):

* ``--iters`` data-dependent passes inside ONE jit — the loop carry
  perturbs the next input, so XLA cannot hoist, overlap, or elide
  iterations;
* every timed call carries a distinct ``seed`` input (numerically an
  exact identity: ``+ seed * 1e-30`` rounds away in f32), so no two
  timed executions are byte-identical;
* every sample is closed by ``np.asarray`` of a scalar output, and the
  dispatch+fetch RTT floor (timed on a trivial seeded program) is
  subtracted.

Compares, per pass over the flagship FCNN (784-128-64-10):

  f32 XLA chain | f32 fused Pallas chain | int8 jnp | int8 fused Pallas

Emits one JSON line. Needs a TPU; fails without one.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=60000)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax

    from tpu_dist_nn.utils.backend import require_platform

    require_platform("tpu")  # these are chip numbers or nothing
    devices = jax.devices()
    backend = jax.default_backend()

    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpu_dist_nn.kernels.fused_dense import _fcnn_fused_call
    from tpu_dist_nn.kernels.quantized import (
        fcnn_quantized_forward,
        forward_quantized,
        quantize_fcnn,
    )
    from tpu_dist_nn.models.fcnn import forward, init_fcnn

    params = init_fcnn(jax.random.key(0), [784, 128, 64, 10])
    qp = quantize_fcnn(params)
    acts = ("relu", "relu", "softmax")
    shapes = tuple((p["w"].shape, p["b"].shape) for p in params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.uniform(0.0, 1.0, (args.n, 784)).astype(np.float32)
    )
    x = jax.device_put(x)

    paths = {
        "f32_xla": lambda bx: forward(params, bx),
        "f32_fused": lambda bx: _fcnn_fused_call(
            shapes, acts, 512, None, bx,
            *[t for q in params for t in (q["w"], q["b"])],
        ),
        "int8_jnp": lambda bx: forward_quantized(qp, bx, acts),
        "int8_fused": lambda bx: fcnn_quantized_forward(
            qp, bx, activations=acts
        ),
    }

    # RTT floor: dispatch + scalar fetch of a trivial seeded program.
    @jax.jit
    def _trivial(seed):
        return seed * jnp.float32(2.0) + jnp.float32(1.0)

    np.asarray(_trivial(jnp.float32(0.5)))  # compile
    floor_times = []
    for i in range(5):
        t0 = time.monotonic()
        np.asarray(_trivial(jnp.float32(1000.0 + i)))
        floor_times.append(time.monotonic() - t0)
    rtt_floor = min(floor_times)

    seed_counter = [float(np.random.default_rng().integers(1 << 20))]

    def chained(fn, iters):
        @jax.jit
        def run(bx, seed):
            def body(_, carry):
                eps, acc = carry
                out = fn(bx + eps)
                s = out.reshape(-1)[0]
                return s * jnp.float32(1e-30), acc + s

            out0 = fn(bx + seed * jnp.float32(1e-30))
            s0 = out0.reshape(-1)[0]
            _, acc = lax.fori_loop(
                0, iters, body, (s0 * jnp.float32(1e-30), s0)
            )
            return acc

        return run

    results = {}
    for name, fn in paths.items():
        try:
            run = chained(fn, args.iters)

            def timed():
                seed_counter[0] += 1.0
                s = jnp.float32(seed_counter[0])
                t0 = time.monotonic()
                np.asarray(run(x, s))  # value fetch = true barrier
                return time.monotonic() - t0

            timed()  # compile
            best = min(timed() for _ in range(args.reps))
            per_iter = max(
                (best - rtt_floor) / (args.iters + 1), 1e-12
            )
            results[name] = {
                "per_pass_s": round(per_iter, 9),
                "samples_per_sec": round(args.n / per_iter, 1),
            }
        except Exception as e:  # pragma: no cover - backend-specific
            print(f"# {name} failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            results[name] = None

    def ratio(a, b):
        if results.get(a) and results.get(b):
            return round(
                results[a]["samples_per_sec"] / results[b]["samples_per_sec"],
                4,
            )
        return None

    print(json.dumps({
        "backend": backend,
        "device_kind": devices[0].device_kind,
        "n": args.n,
        "iters_chained": args.iters,
        "rtt_floor_s": round(rtt_floor, 6),
        "method": ("fori_loop chained in one jit, distinct seed per "
                   "call, closed by value fetch, RTT floor subtracted"),
        "paths": results,
        "fused_vs_xla": ratio("f32_fused", "f32_xla"),
        "int8_fused_vs_f32_fused": ratio("int8_fused", "f32_fused"),
        "int8_jnp_vs_f32_xla": ratio("int8_jnp", "f32_xla"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
