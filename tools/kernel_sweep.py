"""Shape sweep on live hardware: where do the Pallas kernels win?

The first chip measurements (round 4; their records and the probe
that took them are gone) showed XLA's own fusion beating the
hand-written fused/int8 Pallas chains at the flagship's tiny widths
(784-128-64-10). This sweep maps the crossover: dense chains at
growing widths (f32 XLA vs fused Pallas vs int8 jnp vs int8 Pallas)
and attention at growing sequence lengths (XLA dot-product attention
vs the flash kernel, forward and forward+grad) — so kernel selection
can be gated on measured wins, not assumptions.

Timing: chained passes inside one jit, a distinct seed per call, a
value fetch to close it, the dispatch+fetch floor subtracted (built
for a remote chip: ROADMAP D4). Needs a TPU; fails without one.

Emits one JSON line per configuration plus a trailing summary line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--target-s", type=float, default=0.4,
                    help="target chained-compute seconds per timed call")
    ap.add_argument("--only", choices=("dense", "attn"), default=None)
    args = ap.parse_args()

    import jax

    from tpu_dist_nn.utils.backend import require_platform

    require_platform("tpu")  # these are chip numbers or nothing
    devices = jax.devices()
    backend = jax.default_backend()
    kind = devices[0].device_kind

    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpu_dist_nn.kernels.fused_dense import _fcnn_fused_call
    from tpu_dist_nn.kernels.flash_attention import flash_attention
    from tpu_dist_nn.kernels.quantized import (
        fcnn_quantized_forward,
        forward_quantized,
        quantize_fcnn,
    )
    from tpu_dist_nn.models.fcnn import forward, init_fcnn

    # The dispatch+fetch floor: a trivial jitted call, fetched.
    @jax.jit
    def _trivial(seed):
        return seed * jnp.float32(2.0) + jnp.float32(1.0)

    np.asarray(_trivial(jnp.float32(0.5)))
    floor = min(
        _timeit(lambda: np.asarray(_trivial(jnp.float32(1000.0 + i))))
        for i in range(5)
    )
    seed_counter = [float(np.random.default_rng().integers(1 << 20))]

    def measure(fn, x, iters):
        """Per-pass seconds for fn(x) via seeded chained fori_loop.

        Auto-calibrates: if the chained signal lands under 0.1 s above
        the RTT floor, scales ``iters`` up (recompiling) until it
        clears, so fast paths at small shapes aren't refused and slow
        paths don't over-run.
        """
        for _attempt in range(4):
            @jax.jit
            def run(bx, seed, _k=iters):
                def body(_, carry):
                    eps, acc = carry
                    out = fn(bx + eps)
                    s = out.reshape(-1)[0].astype(jnp.float32)
                    return (s * jnp.float32(1e-30)).astype(bx.dtype), acc + s

                out0 = fn(bx + (seed * jnp.float32(1e-30)).astype(bx.dtype))
                s0 = out0.reshape(-1)[0].astype(jnp.float32)
                _, acc = lax.fori_loop(
                    0, _k, body,
                    ((s0 * jnp.float32(1e-30)).astype(bx.dtype), s0),
                )
                return acc

            def timed():
                seed_counter[0] += 1.0
                s = jnp.float32(seed_counter[0])
                t0 = time.monotonic()
                np.asarray(run(x, s))
                return time.monotonic() - t0

            timed()  # compile
            best = min(timed() for _ in range(args.reps))
            signal = best - floor
            if signal >= 0.1:
                return signal / (iters + 1), iters
            # Estimate per-pass from what we saw (floor jitter makes
            # tiny signals unreliable: assume at least 2 ms of signal)
            per = max(signal, 0.002) / (iters + 1)
            iters = min(int(0.25 / per), iters * 20)
        return None, iters

    records = []

    # ---- dense chains: width sweep, depth 3, batch 8192 ----
    batch = 8192
    widths = (512, 1024, 2048, 4096) if args.only in (None, "dense") else ()
    for width in widths:
        dims = [width, width, width, width]
        params = init_fcnn(jax.random.key(0), dims)
        qp = quantize_fcnn(params)
        acts = ("relu", "relu", "softmax")
        shapes = tuple((p["w"].shape, p["b"].shape) for p in params)
        x = jax.device_put(jnp.asarray(
            np.random.default_rng(1).uniform(0, 1, (batch, width)),
            jnp.float32))

        flops = 2 * batch * sum(
            a * b for a, b in ((width, width),) * 3
        )
        # iters sized so chained compute ~ target_s, assuming >=10 TFLOPS
        guess = max(8, min(400, int(args.target_s / (flops / 10e12))))

        paths = {
            "f32_xla": lambda bx, p=params: forward(p, bx),
            "f32_fused": lambda bx, s=shapes, p=params: _fcnn_fused_call(
                s, acts, 512, None, bx,
                *[t for q in p for t in (q["w"], q["b"])]),
            "int8_jnp": lambda bx, q=qp: forward_quantized(q, bx, acts),
            "int8_fused": lambda bx, q=qp: fcnn_quantized_forward(
                q, bx, activations=acts),
        }
        rec = {"kind": "dense", "width": width, "depth": 3, "batch": batch}
        for name, fn in paths.items():
            try:
                t, used = measure(fn, x, guess)
            except Exception as e:
                print(f"# dense w={width} {name}: {type(e).__name__}: {e}",
                      file=sys.stderr)
                t, used = None, guess
            rec[name] = (
                {"per_pass_s": round(t, 9), "iters": used,
                 "tflops": round(flops / t / 1e12, 2)}
                if t else None
            )
        records.append(rec)
        print(json.dumps(rec), flush=True)

    # ---- attention: seq sweep, bf16, (B, T, H, Dh) ----
    B, H, Dh = 4, 8, 64
    seqs = (1024, 2048, 4096) if args.only in (None, "attn") else ()
    for T in seqs:
        q = jax.random.normal(jax.random.key(3), (B, T, H, Dh), jnp.bfloat16)
        k = jax.random.normal(jax.random.key(4), (B, T, H, Dh), jnp.bfloat16)
        v = jax.random.normal(jax.random.key(5), (B, T, H, Dh), jnp.bfloat16)
        scale = 1.0 / float(np.sqrt(Dh))

        def xla_attn(qq, kk, vv):
            # (B, T, H, Dh) -> heads-major einsum attention, causal
            logits = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) * scale
            mask = jnp.tril(jnp.ones((T, T), bool))
            logits = jnp.where(mask[None, None], logits.astype(jnp.float32),
                               -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1).astype(qq.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)

        # attention FLOPs: 2*B*H*T^2*Dh (QK) * 2 (PV), causal halves
        flops = 2 * 2 * B * H * T * T * Dh // 2
        guess = max(4, min(200, int(args.target_s / (flops / 20e12))))

        paths = {
            "attn_xla": lambda qq: xla_attn(qq, k, v),
            "attn_flash": lambda qq: flash_attention(qq, k, v, causal=True),
            "attn_xla_grad": lambda qq: jax.grad(
                lambda z: xla_attn(z, k, v).astype(jnp.float32).sum()
            )(qq),
            "attn_flash_grad": lambda qq: jax.grad(
                lambda z: flash_attention(
                    z, k, v, causal=True).astype(jnp.float32).sum()
            )(qq),
        }
        rec = {"kind": "attention", "B": B, "T": T, "H": H, "Dh": Dh,
               "causal": True}
        for name, fn in paths.items():
            try:
                t, used = measure(fn, q, guess)
            except Exception as e:
                print(f"# attn T={T} {name}: {type(e).__name__}: {e}",
                      file=sys.stderr)
                t, used = None, guess
            fl = flops * (2.5 if "grad" in name else 1.0)  # bwd ~ 2.5x fwd
            rec[name] = (
                {"per_pass_s": round(t, 9), "iters": used,
                 "tflops": round(fl / t / 1e12, 2)}
                if t else None
            )
        records.append(rec)
        print(json.dumps(rec), flush=True)

    def _ratio(rec, a, b):
        if rec.get(a) and rec.get(b):
            return round(rec[b]["per_pass_s"] / rec[a]["per_pass_s"], 3)
        return None

    summary = {
        "backend": backend, "device_kind": kind,
        "rtt_floor_s": round(floor, 6),
        "dense_fused_speedup_vs_xla": {
            str(r["width"]): _ratio(r, "f32_fused", "f32_xla")
            for r in records if r["kind"] == "dense"},
        "dense_int8jnp_speedup_vs_xla": {
            str(r["width"]): _ratio(r, "int8_jnp", "f32_xla")
            for r in records if r["kind"] == "dense"},
        "attn_flash_speedup_vs_xla": {
            str(r["T"]): _ratio(r, "attn_flash", "attn_xla")
            for r in records if r["kind"] == "attention"},
        "attn_flash_grad_speedup_vs_xla": {
            str(r["T"]): _ratio(r, "attn_flash_grad", "attn_xla_grad")
            for r in records if r["kind"] == "attention"},
    }
    print(json.dumps(summary), flush=True)
    return 0


def _timeit(fn):
    t0 = time.monotonic()
    fn()
    return time.monotonic() - t0


if __name__ == "__main__":
    sys.exit(main())
