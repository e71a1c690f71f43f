"""Heterogeneous pipeline: mixed-layer (conv/pool/dense) models across
devices with non-uniform inter-stage shapes.

The SPMD GPipe executor (:mod:`tpu_dist_nn.parallel.pipeline`) requires
uniform per-stage programs (one shard_map body), which rules out conv
models whose feature-map shapes shrink stage to stage. This executor is
the single-controller alternative, closest in spirit to the reference's
container-per-stage chain (``run_grpc_fcnn.py:83-155``) but with the
Docker/gRPC substrate replaced by device placement + async dispatch:

* each stage is its own jitted program with its params committed to its
  device (stage i -> ``devices[i]``);
* the hand-off is ``jax.device_put`` of the flat activation batch —
  a device-to-device copy, no serialization (SURVEY.md §2.4);
* microbatches are dispatched eagerly: JAX's async dispatch lets
  microbatch m+1 run stage i while microbatch m runs stage i+1 — the
  GPipe overlap without an SPMD schedule.

Training (round 2; the reference's pipeline is inference-only,
SURVEY.md §2.3): the same placement runs a hand-rolled GPipe
forward/backward — each stage's VJP is a per-stage jitted program with
activation recompute, so only the stage-BOUNDARY activations live
across the schedule (O(M·S) boundary tensors — GPipe memory; the
per-stage internals rematerialize inside the VJP), cotangents hand off
device-to-device mirroring the forward, gradients accumulate per stage
ON that stage's device, and each stage applies its own optax update
locally. Adam & friends are elementwise, so per-stage updates on
microbatch-mean gradients are numerically the single-program update —
asserted to tolerance by tests/test_hetero_pipeline.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpu_dist_nn.core.schema import ModelSpec, validate_distribution
from tpu_dist_nn.models.network import (
    build_network,
    jitted_network_forward,
    network_forward_lax,
    network_logits,
)


class HeteroPipeline:
    """Per-stage placement of a mixed-layer model.

    ``distribution[i]`` layers are pinned to ``devices[i]``; activations
    hand off as committed device arrays between consecutive stages.
    """

    def __init__(self, model: ModelSpec, distribution, devices=None,
                 dtype=jnp.float32):
        validate_distribution(distribution, len(model.layers))
        if devices is None:
            devices = jax.devices()
        if len(distribution) > len(devices):
            raise ValueError(
                f"{len(distribution)} stages need as many devices; "
                f"only {len(devices)} available"
            )
        self.distribution = list(distribution)
        self.devices = list(devices[: len(distribution)])
        self.out_dim = model.output_dim
        self._dtype = dtype
        self.stages = []
        idx = 0
        for n, dev in zip(distribution, self.devices):
            sub = ModelSpec(model.layers[idx : idx + n])
            plan, params = build_network(sub, dtype)
            self.stages.append(
                {
                    "plan": plan,
                    "params": jax.device_put(params, dev),
                    "device": dev,
                }
            )
            idx += n

    def _dispatch_chunks(self, chunks, *, block_each: bool = False) -> list:
        """Issue every chunk's stage calls; return unawaited results.

        THE pipelined dispatch loop — ``forward`` and the overlap
        instrumentation (:func:`measure_dispatch_overlap`) both run
        exactly this code, so the measured path cannot drift from the
        served one. ``block_each=True`` is the instrumentation's
        control arm: await every stage call (serialized dispatch).
        """
        outs = []
        for chunk in chunks:
            # One host->device transfer, then cast to the serving dtype
            # on the first stage's device.
            h = jax.device_put(chunk, self.stages[0]["device"]).astype(self._dtype)
            for stage in self.stages:
                h = jax.device_put(h, stage["device"])
                h = jitted_network_forward(stage["plan"])(stage["params"], h)
                if block_each:
                    # The control arm serializes on a fetched value,
                    # the same barrier the async arm closes with, so
                    # the two arms pay the same kind of wait.
                    np.asarray(h[:1, :1])
            outs.append(h)  # don't block: let later chunks overlap
        return outs

    def forward(self, x, *, microbatch_size: int | None = None) -> np.ndarray:
        """``x (B, in_dim)`` -> ``(B, out_dim)`` through the chain.

        With ``microbatch_size`` the batch is split and every chunk's
        stage calls are dispatched before any result is awaited, so
        chunks overlap across stages (measured:
        :func:`measure_dispatch_overlap`, docs/PERF.md).
        """
        x = np.asarray(x, np.float32)
        if len(x) == 0:
            return np.zeros((0, self.out_dim), np.float32)
        chunks = (
            [x]
            if microbatch_size is None
            else [
                x[i : i + microbatch_size]
                for i in range(0, len(x), microbatch_size)
            ]
        )
        outs = self._dispatch_chunks(chunks)
        return np.concatenate([np.asarray(o) for o in outs])

    def placement_summary(self) -> dict:
        return {
            "num_stages": len(self.stages),
            "stage_devices": [str(s["device"]) for s in self.stages],
            "stage_layers": self.distribution,
            "stage_kinds": [
                [p.kind for p in s["plan"]] for s in self.stages
            ],
        }

    def set_stage_params(self, params_list) -> None:
        """Install trained per-stage params (committed to each stage's
        device) — the training loop's write-back."""
        for stage, p in zip(self.stages, params_list):
            stage["params"] = jax.device_put(p, stage["device"])


def measure_dispatch_overlap(hp: HeteroPipeline, x, microbatch_size: int,
                             reps: int = 3) -> dict:
    """Quantify cross-stage overlap of the microbatched forward.

    The claimed mechanism (module docstring) is JAX async dispatch:
    the host issues chunk ``m+1``'s stage-``i`` program while chunk
    ``m``'s stage-``i+1`` still runs, so on independent devices the
    programs execute concurrently. The host-side observable — valid
    even on a single-core virtual-device mesh where wall-clock overlap
    cannot show — is that the FULL dispatch loop returns long before
    the results are ready. Returns (all min-of-``reps`` seconds):

    - ``dispatch_s``: issue every chunk x stage call, await nothing —
      the window in which later chunks' programs are already enqueued
      behind earlier chunks' downstream stages;
    - ``total_s``: dispatch + block on all results;
    - ``blocked_s``: the control arm — the same loop awaiting every
      stage call (what a synchronously-dispatching host would cost);
    - ``dispatch_ratio``: ``dispatch_s / blocked_s``; well below 1
      means the host never serializes on per-stage completion, i.e.
      the overlap window is real. On real multi-device hardware
      ``total_s < blocked_s`` additionally shows the wall-clock win.
    - ``fetch_rtt_s``: measured per-value-fetch round-trip, already
      subtracted from ``total_s``/``blocked_s`` in proportion to each
      arm's fetch count — the barriers are value fetches, and without
      this correction the control arm's per-stage fetches would
      manufacture a low ratio out of fetch latency.
    """
    import time

    x = np.asarray(x, np.float32)
    chunks = [
        x[i: i + microbatch_size] for i in range(0, len(x), microbatch_size)
    ]
    # Warm compiles with a value fetch per output: an un-drained
    # warm-up would pollute rep 1.
    for o in hp._dispatch_chunks(chunks):
        np.asarray(o[:1, :1])

    # Per-fetch RTT floor: every barrier below is a value fetch, which
    # costs a device->host round-trip of its own. The control arm
    # fetches per STAGE and the async arm per CHUNK, so without
    # correction a slow fetch path would manufacture a low
    # dispatch_ratio out of pure fetch latency. The
    # probe output is DRAINED first (its own value fetched) so the
    # timed fetches measure fetch cost alone, not the chunk's compute.
    probe = hp._dispatch_chunks(chunks[:1])[0]
    np.asarray(probe[:1, :1])  # drain: compute finishes here
    t0 = time.monotonic()
    for _ in range(3):
        np.asarray(probe[:1, :1])
    rtt = (time.monotonic() - t0) / 3

    rng = np.random.default_rng()  # OS entropy: two calls must differ too
    dispatch_s, total_s, blocked_s = [], [], []
    n_stage_fetches = len(chunks) * len(hp.stages)
    for _ in range(reps):
        # Perturb one element per rep so no two reps upload or run
        # byte-identical work. chunks[0] views x, and _dispatch_chunks
        # re-device_puts per call.
        chunks[0][0, 0] = np.float32(rng.uniform(0.0, 1.0))
        t0 = time.monotonic()
        outs = hp._dispatch_chunks(chunks)
        dispatch_s.append(time.monotonic() - t0)
        # One element per chunk output suffices — a buffer's values
        # exist only after its program ran.
        for o in outs:
            np.asarray(o[:1, :1])
        total_s.append(max(time.monotonic() - t0 - rtt * len(chunks), 0.0))

        chunks[0][0, 0] = np.float32(rng.uniform(0.0, 1.0))
        t0 = time.monotonic()
        hp._dispatch_chunks(chunks, block_each=True)
        blocked_s.append(
            max(time.monotonic() - t0 - rtt * n_stage_fetches, 0.0)
        )
    out = {
        "num_chunks": len(chunks),
        "num_stages": len(hp.stages),
        "dispatch_s": min(dispatch_s),
        "total_s": min(total_s),
        "blocked_s": min(blocked_s),
        "fetch_rtt_s": rtt,
    }
    if out["blocked_s"] <= 0.0:
        raise RuntimeError(
            "overlap measurement invalid: serialized arm vanished under "
            f"the RTT correction (rtt {rtt:.4f}s x {n_stage_fetches} "
            "fetches) — raise the workload size"
        )
    out["dispatch_ratio"] = out["dispatch_s"] / out["blocked_s"]
    return out


# ---------------------------------------------------------------- training

@functools.lru_cache(maxsize=32)
def _stage_fwd(plan):
    """Training-time stage forward: pure lax (see network_forward_lax)."""
    return jax.jit(functools.partial(network_forward_lax, plan))


@functools.lru_cache(maxsize=32)
def _stage_bwd(plan):
    """(params, x, g_out) -> (g_params, g_x) with activation recompute:
    the VJP is rebuilt inside jit from the saved stage INPUT, so the
    schedule only ever stores boundary activations."""

    def bwd(params, x, g):
        _, pull = jax.vjp(
            lambda p, xx: network_forward_lax(plan, p, xx), params, x
        )
        return pull(g)

    return jax.jit(bwd)


@functools.lru_cache(maxsize=32)
def _last_stage_loss_bwd(plan):
    """(params, x, y) -> (loss, g_params, g_x): CE on the sub-chain's
    logits (final activation skipped — train_network's convention)."""
    from tpu_dist_nn.train.trainer import cross_entropy

    def f(params, x, y):
        def loss_f(p, xx):
            return cross_entropy(network_logits(plan, p, xx), y)

        loss, (gp, gx) = jax.value_and_grad(loss_f, argnums=(0, 1))(params, x)
        return loss, gp, gx

    return jax.jit(f)


# One process-wide jit each (retraces per pytree structure); inputs are
# committed arrays, so each call runs on its stage's device.
_tree_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
_tree_scale = jax.jit(lambda t, s: jax.tree.map(lambda l: l * s, t))
_tree_sqsum = jax.jit(
    lambda t: sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(t))
)


def make_hetero_train_step(hp: HeteroPipeline, optimizer, num_microbatches: int,
                           clip_norm: float | None = None):
    """Build ``step(params_list, opt_states, x, y)`` running the GPipe
    schedule over the per-stage device placement.

    The host drives the schedule; every per-stage program (forward, VJP,
    gradient accumulate, optimizer update) is jitted and committed to
    its stage's device, and JAX's async dispatch overlaps microbatch
    ``m+1``'s stage ``i`` with microbatch ``m``'s stage ``i+1`` exactly
    as in :meth:`HeteroPipeline.forward`. Microbatches are equal-sized
    (mean-of-means == full-batch mean for the CE loss), so the update
    equals the single-program one for elementwise optimizers.
    """
    stages = hp.stages
    S = len(stages)

    @jax.jit  # one wrapper; jit retraces per pytree structure + device
    def _apply_update(params, opt_state, grads):
        import optax

        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def step(params_list, opt_states, x, y):
        if len(x) % num_microbatches:
            raise ValueError(
                f"batch of {len(x)} rows does not split into "
                f"{num_microbatches} equal microbatches"
            )
        mb = len(x) // num_microbatches
        xs = [x[m * mb:(m + 1) * mb] for m in range(num_microbatches)]
        ys = [y[m * mb:(m + 1) * mb] for m in range(num_microbatches)]

        # Forward wave: stage inputs (boundary activations) are the only
        # saved state; dispatch everything before awaiting anything.
        inputs = [[None] * S for _ in range(num_microbatches)]
        for m, xm in enumerate(xs):
            h = jax.device_put(jnp.asarray(xm), stages[0]["device"])
            for i, stage in enumerate(stages):
                h = jax.device_put(h, stage["device"])
                inputs[m][i] = h
                if i + 1 < S:
                    h = _stage_fwd(stage["plan"])(params_list[i], h)

        # Backward wave: per-microbatch cotangent flows tail -> head,
        # gradients accumulate on each stage's device.
        grads = [None] * S
        losses = []
        for m in range(num_microbatches):
            loss, gp, gx = _last_stage_loss_bwd(stages[-1]["plan"])(
                params_list[-1], inputs[m][-1], jnp.asarray(ys[m])
            )
            losses.append(loss)
            grads[-1] = gp if grads[-1] is None else _tree_add(grads[-1], gp)
            for i in reversed(range(S - 1)):
                gx = jax.device_put(gx, stages[i]["device"])
                gp, gx = _stage_bwd(stages[i]["plan"])(
                    params_list[i], inputs[m][i], gx
                )
                grads[i] = gp if grads[i] is None else _tree_add(grads[i], gp)

        # Per-stage update on microbatch-mean gradients, local to the
        # stage's device.
        inv = 1.0 / num_microbatches
        mean_grads = [_tree_scale(g, inv) for g in grads]
        if clip_norm is not None:
            # GLOBAL-norm clipping spans the stages: per-stage squared
            # sums (each on its device) combine on the host into the
            # full-model norm — optax.clip_by_global_norm's exact
            # semantics, which `optimizer` therefore must NOT also
            # apply (train_hetero builds it clip-free).
            gnorm = float(
                np.sqrt(sum(float(_tree_sqsum(g)) for g in mean_grads))
            )
            if gnorm > clip_norm:
                mean_grads = [
                    _tree_scale(g, clip_norm / gnorm) for g in mean_grads
                ]
        new_params, new_opt = [], []
        for i in range(S):
            p, o = _apply_update(params_list[i], opt_states[i], mean_grads[i])
            new_params.append(p)
            new_opt.append(o)
        loss = jnp.stack(losses).mean()
        return new_params, new_opt, loss

    return step


def train_hetero(
    hp: HeteroPipeline,
    train_data,
    config=None,
    eval_data=None,
    checkpoints=None,
    num_microbatches: int = 2,
):
    """Train a heterogeneous (conv/pool/dense) model THROUGH the
    pipeline placement; returns ``(params_list, history)`` and installs
    the trained params back into ``hp``.

    Matches :func:`tpu_dist_nn.train.trainer.train_network` numerically
    (same loop, loss, optimizer recipe) — the difference is WHERE the
    compute runs: one jitted program per stage on that stage's device
    instead of one whole-model program.
    """
    from tpu_dist_nn.train.trainer import (
        TrainConfig,
        optimizer_for,
        run_training_loop,
    )

    import dataclasses as _dc

    config = config or TrainConfig()
    if config.clip_norm is not None and config.grad_accum > 1:
        # MultiSteps accumulates RAW gradients and clips the
        # accumulated mean at the real update; this step clips each
        # batch's mean on the host BEFORE MultiSteps sees it —
        # mean-of-clipped != clip-of-mean, so the combination would
        # silently diverge from the single-program trainer.
        raise ValueError(
            "clip_norm with grad_accum > 1 is not supported through the "
            "hetero pipeline (clipping would apply per micro-step, not "
            "to the accumulated gradient); drop one of the two or train "
            "with the single-program executor"
        )
    if config.batch_size % num_microbatches:
        raise ValueError(
            f"batch_size {config.batch_size} must be a multiple of "
            f"num_microbatches {num_microbatches}"
        )
    # Global-norm clipping is applied ACROSS stages by the step itself
    # (see make_hetero_train_step); the per-stage optimizers must be
    # built clip-free or clipping would apply twice with per-stage
    # norms.
    opt_config = (
        _dc.replace(config, clip_norm=None)
        if config.clip_norm is not None else config
    )
    optimizer = optimizer_for(opt_config, train_data)
    params_list = [s["params"] for s in hp.stages]
    opt_states = [
        jax.device_put(optimizer.init(p), s["device"])
        for p, s in zip(params_list, hp.stages)
    ]
    step = make_hetero_train_step(
        hp, optimizer, num_microbatches, clip_norm=config.clip_norm
    )

    eval_fn = None
    if eval_data is not None:
        def eval_fn(params_list_):
            hp.set_stage_params(params_list_)
            from tpu_dist_nn.train.metrics import classification_metrics

            preds = hp.forward(eval_data.x).argmax(-1)
            return classification_metrics(
                preds, eval_data.y, eval_data.num_classes
            )

    params_list, history = run_training_loop(
        step, params_list, opt_states, train_data, config, eval_fn,
        checkpoints=checkpoints,
    )
    hp.set_stage_params(params_list)
    return params_list, history
