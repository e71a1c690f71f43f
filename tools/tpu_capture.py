"""Capture real-TPU evidence: pipelined step latency + profiler trace.

Run on a TPU host (it fails without one). Emits ONE JSON line on stdout:

    {"backend", "device_kind", "n_devices", "pipeline": {p50_s, ...},
     "stage_compute": {p50_s, ...}, "trace_dir"}

``pipeline`` is the BASELINE.md metric — p50 per-stage pipeline step
latency — measured as the wall-clock of one full pipelined forward
(GPipe schedule, ``parallel/pipeline.py``) divided by its step count
T = M + S - 1; ``stage_compute`` is the single-stage dense-chain step
on its own. On a single-chip host the mesh is (data=1, stage=n_devices)
so the schedule, ppermute hops and all, is exactly what a pod slice
runs — with n_devices=1 the hop is a no-op but the schedule/trace
structure is identical.

A ``jax.profiler`` trace of one pipelined step lands in ``--trace-dir``
(TensorBoard/Perfetto format) with the per-stage ``named_scope`` labels
from parallel/gpipe.py:58-61 — the trace-level analogue of the
reference's per-hop RPC timers (run_grpc_inference.py:139-148).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default="artifacts/trace")
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args()

    import jax

    from tpu_dist_nn.utils.backend import require_platform

    require_platform("tpu")  # these are chip numbers or nothing
    devices = jax.devices()
    backend = jax.default_backend()
    kind = devices[0].device_kind

    import jax.numpy as jnp
    import numpy as np

    from tpu_dist_nn.core.schema import partition_model
    from tpu_dist_nn.models.fcnn import init_fcnn, spec_from_params
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.pipeline import (
        build_pipeline_params,
        compiled_pipeline,
        pad_batch,
    )
    from tpu_dist_nn.utils.profiling import LatencyStats, capture_trace

    # The flagship model at the reference's torch shape
    # (generate_mnist_pytorch.py:25-27), pipelined over every local
    # device: 3 stages when 3+ devices exist, else what fits.
    n_dev = len(devices)
    n_stages = min(3, n_dev)
    params = init_fcnn(jax.random.key(0), [784, 128, 64, 10])
    model = spec_from_params(params, ["relu", "relu", "softmax"])
    dist = {1: [3], 2: [2, 1], 3: [1, 1, 1]}[n_stages]
    stages = partition_model(model, dist)
    pp = build_pipeline_params(stages)
    mesh = build_mesh(MeshSpec(stage=n_stages))

    M = args.microbatches
    xs, _ = pad_batch(pp.meta, jnp.asarray(
        np.random.default_rng(0).uniform(0, 1, (args.batch, 784)),
        jnp.float32), M, 1, jnp.float32)
    run = compiled_pipeline(mesh, pp.meta, M, False, jnp.float32)
    jax.block_until_ready(run(pp.weights, xs))  # compile

    T = M + pp.meta.num_stages - 1  # schedule steps per forward
    full = LatencyStats("pipelined_forward")
    per_step = LatencyStats("pipeline_step")
    for _ in range(args.reps):
        t0 = time.monotonic()
        jax.block_until_ready(run(pp.weights, xs))
        dt = time.monotonic() - t0
        full.record(dt)
        per_step.record(dt / T)

    # Single-stage compute on its own (no schedule): the per-stage
    # cost floor the p50 step latency is judged against.
    from tpu_dist_nn.models.fcnn import forward

    bx = jnp.asarray(
        np.random.default_rng(1).uniform(0, 1, (args.batch // M, 784)),
        jnp.float32,
    )
    fwd = jax.jit(forward)
    jax.block_until_ready(fwd(params, bx))
    stage = LatencyStats("stage_compute")
    for _ in range(args.reps):
        with stage.time():
            jax.block_until_ready(fwd(params, bx))

    os.makedirs(args.trace_dir, exist_ok=True)
    with capture_trace(args.trace_dir):
        jax.block_until_ready(run(pp.weights, xs))

    print(json.dumps({
        "backend": backend,
        "device_kind": kind,
        "n_devices": n_dev,
        "n_stages": n_stages,
        "num_microbatches": M,
        "batch": args.batch,
        "schedule_steps": T,
        "pipelined_forward": full.summary(),
        "pipeline_step": per_step.summary(),
        "stage_compute": stage.summary(),
        "trace_dir": args.trace_dir,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
