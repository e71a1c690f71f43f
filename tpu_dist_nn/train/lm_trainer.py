"""Language-model training: single-chip and pipelined Tiny-Transformer.

The native-training analogue of the reference's centralized recipes
(Adam + CE, generate_mnist_pytorch.py:37-52) applied to the
BASELINE.json configs[4] LM workload: next-token cross-entropy, Adam,
jit-compiled steps; the pipelined variant differentiates straight
through the GPipe schedule.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpu_dist_nn.checkpoint.store import flush
from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    dot_product_attention,
    lm_loss,
)
from tpu_dist_nn.obs.registry import REGISTRY
from tpu_dist_nn.parallel.transformer_pipeline import (
    make_pipeline_lm_loss,
    shard_blocks,
    unshard_blocks,
)

# LM training metric families (docs/OBSERVABILITY.md). Updated ONLY at
# log/checkpoint boundaries — the points that already pay a host sync
# (float(loss)) — so instrumentation adds zero fetch barriers to the
# step loop (the r4 honest-timing rule).
_LM_STEPS = REGISTRY.counter(
    "tdn_train_steps_total", "optimizer steps completed", labels=("trainer",),
)
_LM_TOKENS = REGISTRY.counter(
    "tdn_train_tokens_total", "training tokens consumed (targets)",
    labels=("trainer",),
)
_LM_LOSS = REGISTRY.gauge(
    "tdn_train_loss", "latest recorded training loss", labels=("trainer",),
)
_LM_TOKENS_PER_S = REGISTRY.gauge(
    "tdn_train_tokens_per_second",
    "training throughput between the last two log boundaries",
    labels=("trainer",),
)
_LM_STEP_SECONDS = REGISTRY.histogram(
    "tdn_train_step_seconds",
    "mean wall time per optimizer step over a logging interval",
    labels=("trainer",),
)
_LM_CHECKPOINTS = REGISTRY.counter(
    "tdn_checkpoint_saves_total", "checkpoint save events",
    labels=("trainer",),
)


@dataclasses.dataclass(frozen=True)
class LMTrainConfig:
    learning_rate: float = 1e-3
    steps: int = 200
    batch_size: int = 16
    seq_len: int = 128
    log_every: int = 50
    clip_norm: float | None = None
    warmup_steps: int = 0
    lr_schedule: str = "constant"
    weight_decay: float = 0.0
    grad_accum: int = 1
    # K training steps per device call (one lax.scan over a (K, B, T+1)
    # superbatch): removes the per-step Python dispatch + host
    # round-trip, the suspect named for round 4's ~0.21 real-workload
    # MFU (ROADMAP.md S5; not measured on today's code). Built-in
    # single-chip path only.
    steps_per_call: int = 1


def _resolve_attn_fn(attn_fn):
    if attn_fn is not None:
        return attn_fn
    from tpu_dist_nn.kernels.flash_attention import default_attn_fn

    return default_attn_fn()


def make_step_body(loss_fn, optimizer, value_and_grad=None):
    """The one training-step body every LM variant jits:
    value_and_grad over ``loss_fn(params, tokens)``, optimizer update,
    apply. Single definition so baseline / pipelined / MoE / ZeRO steps
    cannot drift apart (a change like grad clipping lands everywhere).

    ``value_and_grad`` overrides the AD-derived gradient with a
    hand-scheduled ``(params, tokens) -> (loss, grads)`` (the 1F1B
    pipeline schedule); the optimizer half stays shared either way.
    """
    vag = value_and_grad if value_and_grad is not None else jax.value_and_grad(loss_fn)

    def step(params, opt_state, tokens):
        loss, grads = vag(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def make_lm_train_step(cfg: TransformerConfig, optimizer, attn_fn=None, *,
                       donate: bool = False, steps_per_call: int = 1):
    """jitted ``step(params, opt_state, tokens) -> (params, opt_state, loss)``.

    ``attn_fn=None`` picks the backend default (the Pallas flash kernel
    on TPU, the jnp reference elsewhere).

    ``donate=True`` donates the (params, opt_state) input buffers to
    XLA so the update aliases them in place instead of allocating a
    fresh copy of every parameter and moment each step — at 85M params
    that is ~1 GB of HBM writes per step saved. The caller's input
    arrays are INVALIDATED by each call (rebind to the results, as
    :func:`train_lm` does); default False so ad-hoc callers that reuse
    a params pytree across step functions keep working.

    ``steps_per_call=K > 1`` returns a superstep
    ``(params, opt_state, tokens_k (K, B, T+1)) -> (..., losses (K,))``
    running K optimizer steps in ONE ``lax.scan``-ed device program:
    no Python dispatch, no host sync, no loss fetch between the K
    steps — the input-pipeline shape the TPU wants. Losses come back
    as a K-vector (one fetch per superstep when the caller logs).
    """
    attn_fn = _resolve_attn_fn(attn_fn)
    body = make_step_body(lambda p, t: lm_loss(p, t, cfg, attn_fn), optimizer)
    donate_kw = {"donate_argnums": (0, 1)} if donate else {}
    if steps_per_call == 1:
        return jax.jit(body, **donate_kw)
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")

    def superstep(params, opt_state, tokens_k):
        def scan_body(carry, toks):
            p, o = carry
            p, o, loss = body(p, o, toks)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            scan_body, (params, opt_state), tokens_k
        )
        return params, opt_state, losses

    return jax.jit(superstep, **donate_kw)


def make_pipeline_lm_train_step(mesh, cfg: TransformerConfig, num_stages: int,
                                num_microbatches: int, optimizer,
                                attn_fn=None, schedule: str = "gpipe",
                                num_virtual: int = 1,
                                tensor_parallel: int = 1,
                                donate: bool = False):
    """Pipelined train step.

    ``schedule``: "gpipe" (AD through the forward schedule; blocks in
    :func:`~tpu_dist_nn.parallel.transformer_pipeline.shard_blocks`
    layout), "1f1b" (hand-rolled one-forward-one-backward with
    activation recompute, O(num_stages) live activations; same layout),
    or "interleaved" (virtual-stage Megatron 1F1B, ``num_virtual``
    chunks per device, blocks in
    :func:`~tpu_dist_nn.parallel.transformer_pipeline.shard_blocks_interleaved`
    layout — bubble cut to 2(S-1) chunk-ticks).

    ``tensor_parallel > 1`` Megatron-shards each stage's blocks over the
    mesh's ``model`` axis and composes with ALL three schedules — the
    scheduled executors tolerate the block psums because their tick
    predicates are model-invariant (one_f_one_b.make_1f1b docstring;
    for the table executor the [device, tick] tables never consult the
    model axis). Layouts: "gpipe"/"1f1b" expect
    :func:`~tpu_dist_nn.parallel.transformer_pipeline.shard_blocks_pp_tp`,
    "interleaved" expects
    :func:`~tpu_dist_nn.parallel.transformer_pipeline.shard_blocks_interleaved_tp`.
    """
    from tpu_dist_nn.parallel.mesh import AXIS_MODEL
    from tpu_dist_nn.parallel.one_f_one_b import validate_schedule

    validate_schedule(schedule)
    # Same donation contract as make_lm_train_step: opt-in in-place
    # (params, opt_state) update; each call invalidates its inputs so
    # callers must rebind (train_lm does).
    _jit = functools.partial(
        jax.jit, **({"donate_argnums": (0, 1)} if donate else {})
    )
    attn = _resolve_attn_fn(attn_fn)
    if tensor_parallel > 1 and mesh.shape.get(AXIS_MODEL, 1) != tensor_parallel:
        raise ValueError(
            f"tensor_parallel={tensor_parallel} but the mesh '{AXIS_MODEL}' "
            f"axis has size {mesh.shape.get(AXIS_MODEL, 1)}"
        )
    if schedule == "zb-v":
        # Zero-bubble on the V-shape placement: v=2 fixed by the
        # placement; blocks in shard_blocks_vshape (or _tp) layout.
        from tpu_dist_nn.parallel import transformer_pipeline as tpl

        make = (
            tpl.make_pipeline_tp_lm_zb_v_grad
            if tensor_parallel > 1 else tpl.make_pipeline_lm_zb_v_grad
        )
        vag = make(mesh, cfg, num_microbatches, attn)
        return _jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule == "zb-stash":
        # TRUE zero-bubble: the ZB-H1 tables with the cotangent-stash
        # split backward — W ticks are pure dW GEMMs
        # (parallel/split_backward.py; dense LM only). Same
        # shard_blocks_interleaved layout as zb.
        from tpu_dist_nn.parallel import transformer_pipeline as tpl

        if tensor_parallel > 1:
            raise ValueError(
                "zb-stash is dense-LM only (the stash split knows the "
                "dense block structure); use schedule='zb' with "
                "tensor_parallel"
            )
        vag = tpl.make_pipeline_lm_zb_stash_grad(
            mesh, cfg, num_virtual, num_microbatches, attn
        )
        return _jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule in ("interleaved", "zb"):
        # Both ride the table executor on the shard_blocks_interleaved
        # (or _tp) layout; "zb" swaps in the split-backward zero-bubble
        # tables. schedule="zb" defaults to the classic contiguous
        # placement unless num_virtual > 1 is requested explicitly.
        from tpu_dist_nn.parallel import transformer_pipeline as tpl

        make = {
            ("interleaved", False): tpl.make_pipeline_lm_interleaved_grad,
            ("interleaved", True): tpl.make_pipeline_tp_lm_interleaved_grad,
            ("zb", False): tpl.make_pipeline_lm_zb_grad,
            ("zb", True): tpl.make_pipeline_tp_lm_zb_grad,
        }[(schedule, tensor_parallel > 1)]
        vag = make(mesh, cfg, num_virtual, num_microbatches, attn)
        return _jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule == "1f1b":
        if tensor_parallel > 1:
            from tpu_dist_nn.parallel.transformer_pipeline import (
                make_pipeline_tp_lm_1f1b_grad,
            )

            vag = make_pipeline_tp_lm_1f1b_grad(
                mesh, cfg, num_stages, num_microbatches, attn
            )
        else:
            from tpu_dist_nn.parallel.transformer_pipeline import (
                make_pipeline_lm_1f1b_grad,
            )

            vag = make_pipeline_lm_1f1b_grad(
                mesh, cfg, num_stages, num_microbatches, attn
            )
        return _jit(make_step_body(None, optimizer, value_and_grad=vag))
    if tensor_parallel > 1:
        from tpu_dist_nn.parallel.transformer_pipeline import (
            make_pipeline_tp_lm_loss,
        )

        loss_fn = make_pipeline_tp_lm_loss(
            mesh, cfg, num_stages, num_microbatches, attn
        )
        return _jit(make_step_body(loss_fn, optimizer))
    loss_fn = make_pipeline_lm_loss(mesh, cfg, num_stages, num_microbatches, attn)
    return _jit(make_step_body(loss_fn, optimizer))


def lm_block_layout(sched: str, stages: int, num_virtual: int, *,
                    cfg=None, tp: int = 1, ep: int = 0):
    """-> ``(shard_blocks_fn, unshard_blocks_fn)`` for the pipelined-LM
    param layout implied by (schedule, sharding) — ONE dispatch shared
    by the CLI's MoE / pp x sp / pp x tp branches and the examples, so
    a new schedule cannot land in one site and silently mis-lay the
    others. ``ep > 0`` selects the expert-sharded family (``cfg``
    unused), ``tp > 1`` the Megatron family (needs ``cfg``), else the
    dense family."""
    if ep:
        from tpu_dist_nn.parallel import expert_parallel as m

        if sched == "zb-v":
            return (
                lambda b: m.shard_blocks_vshape_ep(b, stages, ep),
                m.unshard_blocks_vshape_ep,
            )
        if sched in ("interleaved", "zb"):
            return (
                lambda b: m.shard_blocks_interleaved_ep(
                    b, stages, num_virtual, ep
                ),
                m.unshard_blocks_interleaved_ep,
            )
        return (
            lambda b: m.shard_blocks_pp_ep(b, stages, ep),
            m.unshard_blocks_pp_ep,
        )
    from tpu_dist_nn.parallel import transformer_pipeline as m

    if tp > 1:
        if sched == "zb-v":
            return (
                lambda b: m.shard_blocks_vshape_tp(b, cfg, stages, tp),
                lambda b: m.unshard_blocks_vshape_tp(b, cfg),
            )
        if sched in ("interleaved", "zb"):
            return (
                lambda b: m.shard_blocks_interleaved_tp(
                    b, cfg, stages, num_virtual, tp
                ),
                lambda b: m.unshard_blocks_interleaved_tp(b, cfg),
            )
        return (
            lambda b: m.shard_blocks_pp_tp(b, cfg, stages, tp),
            lambda b: m.unshard_blocks_pp_tp(b, cfg),
        )
    if sched == "zb-v":
        return (
            lambda b: m.shard_blocks_vshape(b, stages),
            m.unshard_blocks_vshape,
        )
    if sched in ("interleaved", "zb", "zb-stash"):
        return (
            lambda b: m.shard_blocks_interleaved(b, stages, num_virtual),
            m.unshard_blocks_interleaved,
        )
    return (lambda b: m.shard_blocks(b, stages), m.unshard_blocks)


def make_pipeline_moe_lm_train_step(mesh, cfg, num_stages: int,
                                    num_microbatches: int, optimizer,
                                    attn_fn=None, schedule: str = "gpipe",
                                    num_virtual: int = 1,
                                    sp_mode: str | None = None):
    """Pipeline x expert-parallel MoE train step: blocks pipelined over
    ``stage``, experts sharded over ``expert`` inside each stage, batch
    over ``(data, expert)``. Blocks in
    :func:`~tpu_dist_nn.parallel.expert_parallel.shard_blocks_pp_ep`
    layout.

    ``schedule="gpipe"`` (default): AD through the forward schedule.
    ``schedule="1f1b"``: the memory-flat hand-rolled schedule.
    ``schedule="interleaved"/"zb"``: the table executors with
    ``num_virtual`` chunks per device
    (:func:`~tpu_dist_nn.parallel.expert_parallel.shard_blocks_interleaved_ep`
    layout). On every hand schedule the router aux losses ride the
    executor's ``with_aux`` channel (pre-scaled contract)."""
    from tpu_dist_nn.parallel.expert_parallel import (
        make_pipeline_ep_lm_1f1b_grad,
        make_pipeline_ep_lm_interleaved_grad,
        make_pipeline_ep_lm_loss,
        make_pipeline_ep_lm_zb_grad,
    )
    from tpu_dist_nn.parallel.one_f_one_b import validate_schedule

    validate_schedule(schedule)
    if schedule == "zb-stash":
        raise ValueError(
            "zb-stash is dense-LM only (the stash split knows the "
            "dense block structure); use schedule='zb' with --experts"
        )
    if sp_mode is not None:
        # THREE-AXIS MoE (pp x sp x ep): gpipe only — tokens follow the
        # sp convention (full rows, masked CE), so the scheduled
        # executors' shifted-target tails don't apply; see
        # make_pipeline_sp_ep_lm_loss's docstring for the boundary.
        from tpu_dist_nn.parallel.expert_parallel import (
            make_pipeline_sp_ep_lm_loss,
        )

        if schedule != "gpipe":
            raise ValueError(
                f"--experts x --seq-parallel x --stages supports the "
                f"gpipe schedule only (got {schedule!r}): the scheduled "
                "executors' three-axis product (aux channel + "
                "in-schedule ring + expert all_to_all per tick branch) "
                "is out of scope; the gpipe cell carries the "
                "three-axis parity evidence"
            )
        return jax.jit(
            make_step_body(
                make_pipeline_sp_ep_lm_loss(
                    mesh, cfg, num_stages, num_microbatches, sp_mode
                ),
                optimizer,
            )
        )
    attn_fn = _resolve_attn_fn(attn_fn)
    if schedule == "zb-v":
        from tpu_dist_nn.parallel.expert_parallel import (
            make_pipeline_ep_lm_zb_v_grad,
        )

        vag = make_pipeline_ep_lm_zb_v_grad(
            mesh, cfg, num_microbatches, attn_fn
        )
        return jax.jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule in ("interleaved", "zb"):
        make = (
            make_pipeline_ep_lm_interleaved_grad
            if schedule == "interleaved" else make_pipeline_ep_lm_zb_grad
        )
        vag = make(mesh, cfg, num_virtual, num_microbatches, attn_fn)
        return jax.jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule == "1f1b":
        vag = make_pipeline_ep_lm_1f1b_grad(
            mesh, cfg, num_stages, num_microbatches, attn_fn
        )
        return jax.jit(make_step_body(None, optimizer, value_and_grad=vag))
    return jax.jit(
        make_step_body(
            make_pipeline_ep_lm_loss(
                mesh, cfg, num_stages, num_microbatches, attn_fn
            ),
            optimizer,
        )
    )


def make_pipeline_sp_lm_train_step(mesh, cfg: TransformerConfig,
                                   num_stages: int, num_microbatches: int,
                                   optimizer, mode: str = "ring",
                                   schedule: str = "gpipe",
                                   num_virtual: int = 1,
                                   tensor_parallel: int = 1):
    """Pipeline x sequence-parallel train step: blocks pipelined over
    ``stage``, each microbatch's sequence dim sharded over ``seq``,
    batch over ``data``. Tokens are full (input+target) rows (the sp
    loss masks position 0 — ring_attention.py).

    ``schedule="gpipe"`` (default): AD through the forward schedule,
    ring or Ulysses attention; blocks in ``shard_blocks`` layout.
    ``schedule="1f1b"``: the memory-flat hand-rolled schedule —
    O(stages) live activations, the combination long context needs
    most — ring or Ulysses; in-schedule the ring rotates K/V with the
    branch-safe group-local collective (see
    transformer_pipeline.make_pipeline_sp_lm_1f1b_grad).
    ``schedule="interleaved"/"zb"``: the table executors with
    ``num_virtual`` chunks per device (``shard_blocks_interleaved``
    layout; ``_tp`` variants with TP). ``schedule="zb-v"``: the
    V-placement zero-bubble tables (``shard_blocks_vshape[_tp]``
    layout, v=2 fixed by the placement).

    ``tensor_parallel > 1`` additionally Megatron-shards each stage's
    blocks over the mesh's ``model`` axis — PP x TP x SP (x DP), the
    full Megatron-LM long-context deployment shape, on every schedule
    (gpipe: AD through make_pipeline_tp_sp_lm_loss; hand schedules:
    transformer_pipeline.make_pipeline_tp_sp_lm_1f1b_grad etc.)."""
    from tpu_dist_nn.parallel import transformer_pipeline as tpl
    from tpu_dist_nn.parallel.mesh import AXIS_MODEL
    from tpu_dist_nn.parallel.one_f_one_b import validate_schedule

    validate_schedule(schedule)
    if schedule == "zb-stash":
        raise ValueError(
            "zb-stash is dense-LM only (the stash split knows the "
            "dense block structure); use schedule='zb' with "
            "seq-parallel"
        )
    if tensor_parallel > 1 and mesh.shape.get(AXIS_MODEL, 1) != tensor_parallel:
        raise ValueError(
            f"tensor_parallel={tensor_parallel} but the mesh '{AXIS_MODEL}' "
            f"axis has size {mesh.shape.get(AXIS_MODEL, 1)}"
        )
    if schedule == "zb-v":
        make = (
            tpl.make_pipeline_tp_sp_lm_zb_v_grad
            if tensor_parallel > 1 else tpl.make_pipeline_sp_lm_zb_v_grad
        )
        vag = make(mesh, cfg, num_microbatches, mode)
        return jax.jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule in ("interleaved", "zb"):
        make = {
            ("interleaved", False): tpl.make_pipeline_sp_lm_interleaved_grad,
            ("interleaved", True): tpl.make_pipeline_tp_sp_lm_interleaved_grad,
            ("zb", False): tpl.make_pipeline_sp_lm_zb_grad,
            ("zb", True): tpl.make_pipeline_tp_sp_lm_zb_grad,
        }[(schedule, tensor_parallel > 1)]
        vag = make(mesh, cfg, num_virtual, num_microbatches, mode)
        return jax.jit(make_step_body(None, optimizer, value_and_grad=vag))
    if schedule == "1f1b":
        make = (
            tpl.make_pipeline_tp_sp_lm_1f1b_grad
            if tensor_parallel > 1 else tpl.make_pipeline_sp_lm_1f1b_grad
        )
        vag = make(mesh, cfg, num_stages, num_microbatches, mode)
        return jax.jit(make_step_body(None, optimizer, value_and_grad=vag))
    loss_fn = (
        tpl.make_pipeline_tp_sp_lm_loss(
            mesh, cfg, num_stages, num_microbatches, mode
        )
        if tensor_parallel > 1
        else tpl.make_pipeline_sp_lm_loss(
            mesh, cfg, num_stages, num_microbatches, mode
        )
    )
    return jax.jit(make_step_body(loss_fn, optimizer))


def make_seq_parallel_lm_train_step(mesh, cfg: TransformerConfig, optimizer,
                                    mode: str = "ring"):
    """Sequence-parallel train step over the mesh's ``seq`` axis —
    ``mode="ring"`` (K/V rotation, O(T/N) memory) or ``"ulysses"``
    (head-scatter all_to_all, full local attention per head slice);
    tokens arrive as full (inputs+target) rows — the sp loss masks
    position 0 instead of slicing (ring_attention.py)."""
    from tpu_dist_nn.parallel.ring_attention import make_seq_parallel_lm_loss

    return jax.jit(
        make_step_body(make_seq_parallel_lm_loss(mesh, cfg, mode), optimizer)
    )


def make_moe_lm_train_step(cfg, optimizer, mesh=None, attn_fn=None):
    """MoE train step: single-chip (``mesh=None``, grouped oracle) or
    expert-parallel over the mesh's ``expert`` axis (all_to_all
    dispatch). ``cfg`` is a
    :class:`~tpu_dist_nn.parallel.expert_parallel.MoEConfig`.
    ``attn_fn=None`` resolves the backend default (flash on TPU), same
    as the dense train step."""
    from tpu_dist_nn.parallel.expert_parallel import (
        make_ep_lm_forward,
        moe_lm_loss,
    )

    attn_fn = _resolve_attn_fn(attn_fn)
    if mesh is None:
        def loss_fn(p, t):
            return moe_lm_loss(p, t, cfg, attn_fn=attn_fn)
    else:
        loss_fn = make_ep_lm_forward(mesh, cfg, attn_fn, with_loss=True)
    return jax.jit(make_step_body(loss_fn, optimizer))


def make_sp_moe_lm_train_step(mesh, cfg, optimizer, mode: str = "ring"):
    """Long-context MoE train step: sequence parallelism (ring/Ulysses
    attention over ``seq``) × expert parallelism (all_to_all dispatch
    over ``expert``), batch over ``(data, expert)`` — tokens are full
    (input+target) rows (the sp masking convention).
    ``params["blocks"]`` in ep_shard_blocks layout."""
    from tpu_dist_nn.parallel.expert_parallel import make_sp_ep_lm_loss

    return jax.jit(
        make_step_body(make_sp_ep_lm_loss(mesh, cfg, mode), optimizer)
    )


def make_ep_tp_moe_lm_train_step(mesh, cfg, optimizer,
                                 attn_fn=dot_product_attention):
    """TP-inside-experts train step: experts over ``expert`` AND each
    expert's FFN Megatron-split over ``model`` (the cell previously
    rejected as "expert banks are already sharded").
    ``params["blocks"]`` in ep_shard_blocks layout — the model axis is
    a sharding annotation, not a host relayout."""
    from tpu_dist_nn.parallel.expert_parallel import make_ep_tp_lm_loss

    return jax.jit(
        make_step_body(make_ep_tp_lm_loss(mesh, cfg, attn_fn), optimizer)
    )


def evaluate_moe_lm(params, cfg, rows: np.ndarray,
                    batch_size: int = 16,
                    max_batches: int | None = None) -> dict:
    """MoE eval: CE only (router aux excluded) so perplexity/bits-per-
    byte are comparable with the dense model's numbers."""
    return _evaluate_ce(
        _jitted_moe_ce(cfg), params, rows, batch_size, max_batches
    )


def train_lm(params, cfg: TransformerConfig, batches: Iterable[np.ndarray],
             train_cfg: LMTrainConfig, *, mesh=None, num_stages: int = 1,
             num_microbatches: int = 1, checkpoints=None,
             checkpoint_every: int | None = None, step_fn=None,
             schedule: str = "gpipe", globalize=None, num_virtual: int = 1):
    """Run the training loop; pipelined when ``mesh``+``num_stages>1``.

    ``checkpoints`` (a CheckpointManager) enables step-level save +
    resume of (params, opt_state): the checkpoint index counts
    completed steps, and on resume the batch stream is consumed up to
    that step so a deterministic stream (``lm_batches`` with a fixed
    seed) stays aligned. Saves every ``checkpoint_every`` steps
    (default: ``log_every``; with ``steps_per_call=K > 1`` it must be
    a multiple of K — mid-group steps could only save group-end
    state). Returns ``(params, history)`` with params in standard
    (unstaged) layout either way.

    ``step_fn``: ``optimizer -> step`` factory overriding the built-in
    step (used by the MoE family via :func:`make_moe_lm_train_step`);
    the caller then owns any param-layout shard/unshard.

    ``globalize``: ``host_batch -> jax.Array`` assembling each process's
    stripe into one globally-sharded batch (multi-host;
    ``data/feed.global_batch``). Without it in a multi-process job the
    batches stay process-local and every host trains its own divergent
    model — so that case warns and requires the caller to feed IDENTICAL
    data on every host (replicated training).

    Device-residency (VERDICT r4 item 1 — the 0.21-MFU suspects): the
    built-in steps run with donated (params, opt_state) buffers — the
    incoming pytrees are copied ONCE so the caller's arrays survive,
    then every update aliases in place. With
    ``train_cfg.steps_per_call=K > 1`` (single-chip path only) the loop
    feeds K-step superbatches through one ``lax.scan``-ed device
    program: no per-step Python dispatch, loss fetched at most once
    per group (checkpoint saves then land on group boundaries).
    """
    from tpu_dist_nn.checkpoint.store import resume_or_init

    from tpu_dist_nn.train.optimizers import build_optimizer

    optimizer = build_optimizer(
        train_cfg.learning_rate,
        schedule=train_cfg.lr_schedule,
        warmup_steps=train_cfg.warmup_steps,
        total_steps=train_cfg.steps,
        clip_norm=train_cfg.clip_norm,
        weight_decay=train_cfg.weight_decay,
        grad_accum=train_cfg.grad_accum,
    )
    from tpu_dist_nn.parallel.one_f_one_b import validate_schedule

    validate_schedule(schedule)
    pipelined = step_fn is None and mesh is not None and num_stages > 1
    if schedule != "gpipe" and not pipelined:
        raise ValueError(
            f"schedule={schedule!r} requires the pipelined dense LM path "
            "(mesh + num_stages > 1, no custom step_fn)"
        )
    if jax.process_count() > 1 and globalize is None:
        import logging

        logging.getLogger(__name__).warning(
            "multi-host job without a batch globalizer: training runs "
            "replicated per host (identical data required on every host); "
            "no cross-host parallelism"
        )
    k = train_cfg.steps_per_call
    if k < 1:
        # Same contract as make_lm_train_step: reject, don't clamp — a
        # silently-ignored 0 would make an A/B harness believe it
        # measured an arm that never ran.
        raise ValueError(f"steps_per_call must be >= 1, got {k}")
    if k > 1 and train_cfg.log_every % k != 0:
        # Mid-group history entries would all be stamped at the group's
        # single device call, so their `seconds` deltas are not
        # value-fetch barriers — the dishonest-timing failure the r4
        # forensics rule exists to prevent. Requiring log boundaries to
        # land on group ends keeps every logged timestamp a true fetch.
        raise ValueError(
            f"log_every ({train_cfg.log_every}) must be a multiple of "
            f"steps_per_call ({k}): per-step timestamps inside one "
            "grouped device call are not fetch barriers"
        )
    if k > 1 and checkpoint_every and checkpoint_every % k != 0:
        # Same contract as log_every: a mid-group matching step can
        # only save the GROUP-END state, so a misaligned cadence would
        # silently thin the requested checkpoints to group boundaries
        # (fewer saves than asked, each at a different step than asked).
        raise ValueError(
            f"checkpoint_every ({checkpoint_every}) must be a multiple "
            f"of steps_per_call ({k}): checkpoints inside one grouped "
            "device call can only capture group-end state"
        )
    if k > 1 and (step_fn is not None or pipelined):
        raise ValueError(
            "steps_per_call > 1 is the built-in single-chip path only "
            "(custom step_fn and pipelined schedules run one step per "
            "call)"
        )
    if k > 1 and globalize is not None:
        raise ValueError(
            "steps_per_call > 1 does not compose with a multi-host "
            "batch globalizer; set steps_per_call=1 for multi-host runs"
        )
    multi = None
    if step_fn is not None:
        step = step_fn(optimizer)
    elif pipelined and schedule == "zb-v":
        from tpu_dist_nn.parallel.transformer_pipeline import (
            shard_blocks_vshape,
        )

        params = dict(
            params, blocks=shard_blocks_vshape(params["blocks"], num_stages)
        )
        step = make_pipeline_lm_train_step(
            mesh, cfg, num_stages, num_microbatches, optimizer,
            schedule=schedule, donate=True,
        )
    elif pipelined and schedule in ("interleaved", "zb", "zb-stash"):
        from tpu_dist_nn.parallel.transformer_pipeline import (
            shard_blocks_interleaved,
        )

        params = dict(
            params,
            blocks=shard_blocks_interleaved(
                params["blocks"], num_stages, num_virtual
            ),
        )
        step = make_pipeline_lm_train_step(
            mesh, cfg, num_stages, num_microbatches, optimizer,
            schedule=schedule, num_virtual=num_virtual, donate=True,
        )
    elif pipelined:
        params = dict(params, blocks=shard_blocks(params["blocks"], num_stages))
        step = make_pipeline_lm_train_step(
            mesh, cfg, num_stages, num_microbatches, optimizer,
            schedule=schedule, donate=True,
        )
    else:
        step = make_lm_train_step(cfg, optimizer, donate=True)
        if k > 1:
            multi = make_lm_train_step(
                cfg, optimizer, donate=True, steps_per_call=k
            )
    # A step may carry its own (e.g. sharded, ZeRO-1) state init —
    # eager optimizer.init would materialize full replicated moments.
    opt_state = getattr(step, "init_opt_state", optimizer.init)(params)
    start_step, state = resume_or_init(
        checkpoints, {"params": params, "opt_state": opt_state}
    )
    params, opt_state = state["params"], state["opt_state"]
    if step_fn is None:
        # The built-in steps donate their (params, opt_state) inputs:
        # copy once so the CALLER's pytree (and a restore template a
        # test may reuse) is never invalidated — every later input is
        # loop-internal and safely consumed in place.
        params = jax.tree.map(jnp.copy, params)
        opt_state = jax.tree.map(jnp.copy, opt_state)
    every = checkpoint_every or train_cfg.log_every

    history = []
    t0 = time.monotonic()
    # Throughput bookkeeping between log boundaries (the existing host
    # syncs): tokens/steps since the last logged entry.
    obs = {"tokens": 0, "steps": 0, "t_last": t0}
    # One trace per run, log-interval spans hanging off the root —
    # recorded retroactively AT the log boundary, where float(loss)
    # already paid the host sync (the r4 honest-timing rule: tracing
    # adds zero fetch barriers to the step loop).
    from tpu_dist_nn.obs import trace as _trace

    run_span = _trace.TRACER.start(
        "train.lm", attrs={"steps": train_cfg.steps,
                           "batch_size": train_cfg.batch_size},
    )

    def _flush_group(group):
        """Run the buffered (index, batch) group as ONE device call."""
        nonlocal params, opt_state
        n_history_before = len(history)
        obs["steps"] += len(group)
        obs["tokens"] += sum(
            int(b.shape[0]) * max(int(b.shape[1]) - 1, 0) for _, b in group
        )
        if len(group) == 1 and multi is None:
            i, batch = group[0]
            gb = (
                globalize(batch) if globalize is not None
                else jnp.asarray(batch)
            )
            params, opt_state, loss = step(params, opt_state, gb)
            losses = [loss]
        else:
            # (K, B, T+1) superbatch; a shorter FINAL group re-traces
            # once for its length (the scan program is length-static).
            stack = jnp.asarray(np.stack([b for _, b in group]))
            params, opt_state, losses_v = multi(params, opt_state, stack)
            losses = [losses_v[j] for j in range(len(group))]
        for j, (i, _) in enumerate(group):
            if (i + 1) % train_cfg.log_every == 0 or i == train_cfg.steps - 1:
                # float() is the only host sync — one fetch per logged
                # step, at most one per group.
                history.append(
                    {"step": i + 1, "loss": float(losses[j]),
                     "seconds": time.monotonic() - t0}
                )
        if len(history) > n_history_before:
            # A log boundary: the float(loss) above was a true fetch,
            # so wall time here measures completed device work. Publish
            # the interval's throughput, then reset the window.
            now = time.monotonic()
            dt = max(now - obs["t_last"], 1e-9)
            if run_span.sampled:
                _trace.TRACER.record_span(
                    "log_interval", run_span.ctx, obs["t_last"], dt,
                    attrs={"step": history[-1]["step"],
                           "steps": obs["steps"], "tokens": obs["tokens"],
                           "loss": history[-1]["loss"]},
                )
            _LM_LOSS.labels(trainer="lm").set(history[-1]["loss"])
            _LM_STEPS.labels(trainer="lm").inc(obs["steps"])
            _LM_TOKENS.labels(trainer="lm").inc(obs["tokens"])
            _LM_TOKENS_PER_S.labels(trainer="lm").set(obs["tokens"] / dt)
            _LM_STEP_SECONDS.labels(trainer="lm").observe(dt / obs["steps"])
            obs.update(tokens=0, steps=0, t_last=now)
        if checkpoints is not None and any(
            (i + 1) % every == 0 or i == train_cfg.steps - 1
            for i, _ in group
        ):
            i_last = group[-1][0]
            checkpoints.save(
                i_last + 1, {"params": params, "opt_state": opt_state},
                metadata={"step": i_last + 1, "loss": float(losses[-1])},
            )
            _LM_CHECKPOINTS.labels(trainer="lm").inc()

    try:
        group = []
        for i, batch in enumerate(batches):
            if i >= train_cfg.steps:
                break
            if i < start_step:
                continue  # replay-skip: keeps a seeded stream aligned
            group.append((i, batch))
            # Flush on the GLOBAL step grid, not group length: a resume
            # from a checkpoint at start_step % k != 0 would otherwise
            # shift every later group off the log_every boundaries and
            # stamp mid-group (non-fetch-barrier) timestamps — the
            # first post-resume group is simply shorter instead.
            if (i + 1) % k == 0 or i == train_cfg.steps - 1:
                _flush_group(group)
                group = []
        if group:
            _flush_group(group)
    except BaseException:
        # Enqueued async saves become durable even when the loop
        # raises — the crash-resume guarantee is the point. On this
        # path peers may still be mid-step, so the flush must stay
        # collective-free (store.flush docstring). An
        # exc_info check inside a finally would misfire under a
        # caller's active except handler; the explicit re-raise cannot.
        flush(checkpoints, unwinding=True)
        raise
    else:
        flush(checkpoints)
    finally:
        run_span.end()
    if pipelined:
        if schedule == "zb-v":
            from tpu_dist_nn.parallel.transformer_pipeline import (
                unshard_blocks_vshape,
            )

            params = dict(
                params, blocks=unshard_blocks_vshape(params["blocks"])
            )
        elif schedule in ("interleaved", "zb", "zb-stash"):
            from tpu_dist_nn.parallel.transformer_pipeline import (
                unshard_blocks_interleaved,
            )

            params = dict(
                params, blocks=unshard_blocks_interleaved(params["blocks"])
            )
        else:
            params = dict(params, blocks=unshard_blocks(params["blocks"]))
    return params, history


@functools.lru_cache(maxsize=32)
def _jitted_lm_loss(cfg: TransformerConfig):
    """Process-wide cached jitted loss per config (configs are hashable) —
    a fresh jax.jit per eval call would recompile every time."""
    return jax.jit(functools.partial(lm_loss, cfg=cfg))


@functools.lru_cache(maxsize=32)
def _jitted_moe_ce(cfg):
    from tpu_dist_nn.models.transformer import next_token_ce
    from tpu_dist_nn.parallel.expert_parallel import moe_forward

    attn_fn = _resolve_attn_fn(None)

    @jax.jit
    def ce(p, tokens):
        logits, _ = moe_forward(p, tokens[:, :-1], cfg, attn_fn=attn_fn)
        return next_token_ce(logits, tokens[:, 1:])

    return ce


def _evaluate_ce(loss_fn, params, rows: np.ndarray, batch_size: int,
                 max_batches: int | None = None) -> dict:
    # Per-batch losses accumulate in ONE on-device running sum (full
    # batches are equal-weight, so the mean of batch means is the
    # weighted mean); the single float() at the end is the only host
    # sync — per-batch float() would block the host once per eval
    # batch. A running scalar, not a list: the
    # 8 MB corpus can mean thousands of eval batches, and stacking
    # thousands of unsynced device values aborted XLA:CPU (round 5).
    total, n = None, 0
    for i in range(0, len(rows) - batch_size + 1, batch_size):
        if max_batches is not None and n >= max_batches:
            break
        batch = jnp.asarray(rows[i : i + batch_size])
        loss_b = loss_fn(params, batch)
        total = loss_b if total is None else total + loss_b
        n += 1
    if n == 0:
        raise ValueError("not enough rows for one eval batch")
    loss = float(total) / n
    return {
        "loss_nats_per_token": loss,
        "perplexity": float(np.exp(loss)),
        "bits_per_byte": loss / np.log(2),
        # The count this loop ACTUALLY consumed — callers report it
        # instead of re-deriving the batching arithmetic.
        "eval_rows_used": n * batch_size,
    }


def evaluate_lm(params, cfg: TransformerConfig, rows: np.ndarray,
                batch_size: int = 16,
                max_batches: int | None = None) -> dict:
    """Mean next-token CE + perplexity + bits/byte over ``(N, T+1)`` rows."""
    return _evaluate_ce(
        _jitted_lm_loss(cfg), params, rows, batch_size, max_batches
    )
