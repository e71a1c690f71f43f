"""1F1B (one-forward-one-backward) pipelined training schedule.

The GPipe path (:mod:`tpu_dist_nn.train.pipeline_trainer`) differentiates
straight through the forward schedule, which makes XLA stash every
scan-step activation: live memory grows with the microbatch count M.
This module hand-rolls the standard 1F1B schedule instead: each stage
interleaves one backward between forwards as soon as the first gradient
arrives, so at most ``S - s`` microbatches are ever in flight at stage
``s`` — the activation stash is a ring buffer of ``min(S, M)`` slots,
independent of M. Combined with activation recomputation (the backward
tick re-runs the stage forward from the stashed *input* instead of
keeping per-layer intermediates), live memory per stage is O(S·|mb|)
instead of O(M·|mb|) — the reason 1F1B is the production schedule for
deep pipelines.

Timing: forward of microbatch ``f`` at stage ``s`` runs at tick
``a(s,f) = s + 2f``; backward at ``b(s,f) = 2S-1-s + 2f``.  Forward and
backward ticks of one stage fall on opposite parities, so every tick a
stage does exactly one of {forward, backward, idle} — selected with
``lax.switch`` on a device-local predicate so only the taken branch
executes — while both hand-off wires (activations down, gradients up)
ride a single unconditional ``lax.ppermute`` pair per tick over ICI.
Total ticks ``T = 2(M + S - 1)``, the same bubble fraction as GPipe.

The reference never trains across stages at all (SURVEY.md §3.5: its
training is centralized Keras/torch); both schedules are part of the
capability the build adds on top of the reference's inference-only
pipeline (``grpc_node.py:120-147``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_dist_nn.parallel.mesh import AXIS_DATA, AXIS_STAGE
from tpu_dist_nn.parallel.pipeline import PipelineMeta, PipelineWeights, _stage_apply

#: The pipeline training schedules the framework implements.
#: "interleaved" = virtual-stage (Megatron) 1F1B — see
#: parallel/interleaved.py; LM family only for now.
SCHEDULES = ("gpipe", "1f1b", "interleaved", "zb", "zb-v", "zb-stash")


def validate_schedule(schedule: str) -> str:
    """The single validation point for schedule names (CLI choices lists
    aside) — every trainer/engine entry path funnels through here."""
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}: use "
            + " or ".join(repr(s) for s in SCHEDULES)
        )
    return schedule


def microbatch_axes(microbatch_spec) -> tuple[str, ...]:
    """``(data, *extra)``: every mesh axis the MICROBATCH is sharded
    over (e.g. ``seq`` in the pipeline x sequence-parallel
    composition) — the scheduled executors' wires and accumulators are
    varying over these, and stage/chunk grads reduce over them exactly
    like ``data`` (params are replicated over them while each shard saw
    different positions). Axes that shard PARAMS but not activations,
    like Megatron's ``model``, are deliberately NOT here: their grads
    stay per-shard. One definition shared by make_1f1b and the table
    executor (interleaved/zb)."""
    extra = tuple(
        ax
        for part in microbatch_spec
        if part is not None
        for ax in ((part,) if isinstance(part, str) else tuple(part))
        if ax != AXIS_DATA
    )
    return (AXIS_DATA, *extra)


def make_1f1b(
    mesh,
    stage_fn,
    tail_fn,
    num_stages: int,
    num_microbatches: int,
    *,
    microbatch_spec=None,
    stage_params_spec=None,
    stage_static_spec=None,
    aux_spec=None,
    want_dx0: bool = True,
    with_aux: bool = False,
):
    """Generic 1F1B executor over the ``(stage, data)`` mesh axes.

    ``with_aux=True`` changes the stage contract to
    ``stage_fn(params, static, x) -> (y, aux_contribution)`` (e.g. an
    MoE stage's router load-balancing loss): the executor adds each
    backward tick's recomputed ``aux_contribution`` into the returned
    loss and backpropagates cotangent 1.0 through it, so contributions
    must arrive PRE-SCALED (fold the aux weight and any
    1/(stages*microbatches*shards) normalization in before returning —
    the same pre-scaled convention as ``tail_fn``). The forward tick
    discards the aux value (the backward recomputes it), and the
    summed contributions ride the same end-of-scan loss psum.

    Model-agnostic counterpart of :func:`tpu_dist_nn.parallel.gpipe.make_gpipe`
    for the backward pass:

    * ``stage_fn(stage_params, stage_static, x) -> y`` — one stage's
      compute on a microbatch; ``y.shape == x.shape`` uniform across
      stages. ``stage_params`` (differentiated) and ``stage_static``
      (not differentiated — integer tables etc.) are pytrees whose
      leaves carry a leading length-1 stage-shard axis already stripped
      by this wrapper.
    * ``tail_fn(tail_params, y, *aux_f) -> scalar`` — the per-microbatch
      loss applied to the LAST stage's output (e.g. unembed + CE). It
      must return this microbatch's *contribution* to the total loss
      (pre-scaled: fold any 1/num_microbatches or mask normalization in
      before calling). ``aux_f`` are the microbatch-f slices of the
      ``aux`` operand arrays (labels, masks, targets, ...).

    Returns ``f(xs, stage_params, stage_static, tail_params, aux) ->
    (loss, stage_grads, tail_grads, dx0)`` where ``stage_grads`` keeps
    the leading stage-shard axis (like the weights), ``tail_grads`` is
    replicated, and ``dx0: (M, *microbatch_shape)`` is the loss gradient
    w.r.t. each input microbatch — backpropagate it through whatever
    produced ``xs`` (e.g. the embedding) outside the schedule. When
    ``xs`` is raw data with nothing upstream, pass ``want_dx0=False``:
    the M-sized cotangent buffer (which would scale live memory with M
    again) and its end-of-scan psum are skipped entirely and the dx0
    slot returns a scalar zero.

    Collectives inside ``stage_fn``/``tail_fn``: allowed over mesh axes
    on which the tick predicate is INVARIANT — the predicate depends
    only on ``(t, stage index)``, so every participant of a collective
    over a disjoint axis (``model``, ``seq``, ``expert``) takes the same
    branch at the same tick — AND whose lowering has GROUP-LOCAL
    participation: ``psum``/``all_gather``/``all_to_all`` lower to ops
    whose rendezvous involves only their replica group, so peers in
    other branches are irrelevant. Megatron tensor parallelism (psums
    over ``model``) and Ulysses sequence parallelism (all_to_all over
    ``seq``) therefore compose with this schedule.

    ``lax.ppermute`` does NOT, even over a disjoint axis: it lowers to
    collective-permute, whose rendezvous expects EVERY partition in the
    program to execute the instruction — devices in a different branch
    never reach it, so the op deadlocks (proven by the minimal
    reproducer in ``tools/repro_ring_1f1b.py``: "Expected 4 threads to
    join the rendezvous, but only 2 arrived") or, in larger programs,
    silently mis-pairs with a later execution and computes wrong
    values. That is why ring attention inside the scheduled executors
    replaces its ppermute K/V rotation with a group-local
    reduce-scatter rotation
    (``ring_attention._rotate_one_hop_group_local`` — exact,
    branch-safe, ~N× the hop bandwidth) while Ulysses needs no change,
    and why this executor's own stage wires ride ONE UNCONDITIONAL
    ppermute pair per tick outside the ``lax.switch``. Also still
    banned: collectives over ``stage`` or ``data`` inside the bodies
    (the predicate varies over ``stage``, and the executor owns the
    ``data``-axis reduction itself, once, after the scan).
    """
    S, M = num_stages, num_microbatches
    K = min(S, M)
    T = 2 * (M + S - 1)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]
    if microbatch_spec is None:
        microbatch_spec = P(AXIS_DATA)
    data_like = microbatch_axes(microbatch_spec)
    vary = (AXIS_STAGE, *data_like)
    if stage_params_spec is None:
        stage_params_spec = P(AXIS_STAGE)
    if stage_static_spec is None:
        # A plain per-leaf default, NOT stage_params_spec: that may be a
        # pytree of specs (e.g. the Megatron per-leaf dict) whose
        # structure the static operand does not share.
        stage_static_spec = P(AXIS_STAGE)
    if aux_spec is None:
        aux_spec = P(None, *microbatch_spec)
    xs_spec = P(None, *microbatch_spec)

    def device_fn(xs, stage_params, stage_static, tail_params, aux):
        def mark_varying(z, axes):
            # Idempotent "mark varying over `axes`": zeros_like of an
            # already-varying tracer is itself varying, and pcast
            # rejects re-adding axes.
            have = jax.typeof(z).vma
            need = tuple(a for a in axes if a not in have)
            return lax.pcast(z, need, to="varying") if need else z

        def vcast(z):
            return mark_varying(z, vary)

        # Strip the length-1 stage-shard axis; mark all differentiated
        # params varying over the microbatch axes (and tail over
        # `stage` too): see compiled_1f1b_grad's note — otherwise
        # jax.vjp inserts an implicit psum per backward tick (a
        # collective, which inside the lax.switch branch would also
        # break SPMD). Marking must be idempotent: a leaf can already
        # be VARYING over a microbatch axis when that axis shards the
        # params too (expert parallelism's (data, expert) batch with
        # expert-sharded FFN banks) — and such a leaf's grads must NOT
        # be reduced over that axis at the end (each shard owns its
        # slice), so remember every leaf's own pre-mark sharding.
        sp0 = jax.tree.map(lambda a: a[0], stage_params)
        sp_shard_axes = jax.tree.map(
            lambda a: jax.typeof(a).vma, sp0
        )
        sp = jax.tree.map(lambda a: mark_varying(a, data_like), sp0)
        st = jax.tree.map(lambda a: a[0], stage_static)
        tp = jax.tree.map(lambda a: mark_varying(a, vary), tail_params)
        s_idx = lax.axis_index(AXIS_STAGE)
        mb_shape = xs.shape[1:]
        dt = xs.dtype

        def fwd_only(p, x):
            return stage_fn(p, st, x)

        def zeros_like_vma(ref):
            # Grad accumulators must carry the PRIMAL leaf's varying
            # axes: a model-sharded Megatron leaf (varying over `model`)
            # accumulates per-shard cotangents, so an accumulator left
            # invariant over `model` would fail the vma check at the
            # first add.
            return mark_varying(
                jnp.zeros(ref.shape, ref.dtype),
                jax.typeof(ref).vma,
            )

        zeros_wire = vcast(jnp.zeros(mb_shape, dt))
        carry0 = (
            zeros_wire,                                  # activations from s-1
            zeros_wire,                                  # grads from s+1
            vcast(jnp.zeros((K, *mb_shape), dt)),        # input stash
            jax.tree.map(zeros_like_vma, sp),
            jax.tree.map(zeros_like_vma, tp),
            # dx cotangents at stage 0 (skipped when not wanted: the
            # M-sized buffer would re-couple live memory to M).
            vcast(jnp.zeros((M if want_dx0 else 1, *mb_shape), dt)),
            vcast(jnp.zeros((), jnp.float32)),           # loss accumulator
        )

        def tick(carry, t):
            fwd_wire, bwd_wire, stash, g_sp, g_tp, dx0, loss_acc = carry
            tf = t - s_idx
            tb = t - (2 * S - 1 - s_idx)
            is_f = (tf >= 0) & (tf < 2 * M) & (tf % 2 == 0)
            is_b = (tb >= 0) & (tb < 2 * M) & (tb % 2 == 0)
            f_f = jnp.clip(tf // 2, 0, M - 1)
            f_b = jnp.clip(tb // 2, 0, M - 1)
            is_last = s_idx == S - 1

            def idle(_):
                return zeros_wire, zeros_wire, stash, g_sp, g_tp, dx0, loss_acc

            def fwd(_):
                inp = lax.dynamic_index_in_dim(xs, f_f, 0, keepdims=False)
                x_in = jnp.where(s_idx == 0, inp, fwd_wire)
                new_stash = lax.dynamic_update_index_in_dim(
                    stash, x_in, f_f % K, 0
                )
                out = fwd_only(sp, x_in)
                # with_aux: the aux value is discarded here — the
                # backward tick recomputes it (and its gradient).
                y = out[0] if with_aux else out
                return y, zeros_wire, new_stash, g_sp, g_tp, dx0, loss_acc

            def bwd(_):
                x_in = lax.dynamic_index_in_dim(stash, f_b % K, 0, keepdims=False)
                if with_aux:
                    (y, aux_v), svjp = jax.vjp(fwd_only, sp, x_in)
                else:
                    y, svjp = jax.vjp(fwd_only, sp, x_in)
                aux_f = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, f_b, 0, keepdims=False),
                    aux,
                )

                def tail_live(_):
                    loss_f, tvjp = jax.vjp(
                        lambda tpar, yy: tail_fn(tpar, yy, *aux_f), tp, y
                    )
                    d_tp, dy = tvjp(vcast(jnp.ones((), loss_f.dtype)))
                    return loss_f.astype(jnp.float32), dy, d_tp

                def tail_skip(_):
                    return (
                        vcast(jnp.zeros((), jnp.float32)),
                        zeros_wire,
                        jax.tree.map(lambda a: vcast(jnp.zeros_like(a)), tp),
                    )

                # Only the last stage pays the tail (head/loss) FLOPs.
                loss_f, dy_tail, d_tp = lax.cond(is_last, tail_live, tail_skip, 0)
                dy = jnp.where(is_last, dy_tail, bwd_wire)
                if with_aux:
                    # Pre-scaled aux contract: cotangent 1.0, value
                    # summed into the loss.
                    d_sp, dx = svjp((dy, vcast(jnp.ones((), aux_v.dtype))))
                    loss_f = loss_f + aux_v.astype(jnp.float32)
                else:
                    d_sp, dx = svjp(dy)
                if want_dx0:
                    new_dx0 = jnp.where(
                        s_idx == 0,
                        lax.dynamic_update_index_in_dim(dx0, dx, f_b, 0),
                        dx0,
                    )
                else:
                    new_dx0 = dx0
                return (
                    zeros_wire,
                    dx,
                    stash,
                    jax.tree.map(jnp.add, g_sp, d_sp),
                    jax.tree.map(jnp.add, g_tp, d_tp),
                    new_dx0,
                    loss_acc + loss_f,
                )

            branch = is_f.astype(jnp.int32) + 2 * is_b.astype(jnp.int32)
            send_y, send_dx, stash, g_sp, g_tp, dx0, loss_acc = lax.switch(
                branch, [idle, fwd, bwd], 0
            )
            with jax.named_scope("f1b_ppermute_hop"):
                nxt_fwd = (
                    lax.ppermute(send_y, AXIS_STAGE, fwd_perm)
                    if fwd_perm
                    else send_y
                )
                nxt_bwd = (
                    lax.ppermute(send_dx, AXIS_STAGE, bwd_perm)
                    if bwd_perm
                    else send_dx
                )
            return (nxt_fwd, nxt_bwd, stash, g_sp, g_tp, dx0, loss_acc), None

        (_aw, _gw, _st, g_sp, g_tp, dx0, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T)
        )
        # Cross-shard reductions happen ONCE here, not per tick: data
        # shards each saw a slice of the rows; tail grads and loss live
        # only on the last stage; dx0 only on stage 0. Per leaf, reduce
        # only over microbatch axes the PRIMAL leaf was replicated on —
        # a leaf sharded over one of them (EP's expert-sharded banks)
        # keeps per-shard grads there.
        g_sp = jax.tree.map(
            lambda a, sh: (
                lax.psum(a, axes)[None]
                if (axes := tuple(ax for ax in data_like if ax not in sh))
                else a[None]
            ),
            g_sp, sp_shard_axes,
        )
        g_tp = jax.tree.map(lambda a: lax.psum(a, vary), g_tp)
        if want_dx0:
            dx0 = lax.psum(dx0, AXIS_STAGE)
        else:
            dx0 = jnp.zeros((), jnp.float32)  # invariant placeholder
        loss = lax.psum(loss_acc, vary)
        return loss, g_sp, g_tp, dx0

    return jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(
            xs_spec,
            stage_params_spec,
            stage_static_spec,
            P(),
            aux_spec,
        ),
        out_specs=(P(), stage_params_spec, P(), xs_spec if want_dx0 else P()),
    )


def _dense_stage_fn(sp, st, x):
    """The padded dense-chain chunk compute, shared by every hand-rolled
    schedule (1F1B and interleaved) so the numerics cannot drift."""
    return _stage_apply(sp["w"], sp["b"], st["act"], st["width"], x)


def _dense_masked_ce_tail(final_dim: int):
    """Masked softmax-CE over the first ``final_dim`` columns; padding
    columns are excluded from the normalizer with -inf (matching
    pipeline._masked_activation's softmax semantics). The mask must
    arrive pre-scaled by the global normalizer."""

    def tail_fn(_tail_params, logits, lbl, msk_scaled):
        col = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logp = jax.nn.log_softmax(
            jnp.where(col < final_dim, logits, -jnp.inf), axis=-1
        )
        ll = jnp.take_along_axis(logp, lbl[:, None], axis=-1)[:, 0]
        return -(ll * msk_scaled).sum()

    return tail_fn


@functools.lru_cache(maxsize=64)
def compiled_1f1b_grad(mesh, meta: PipelineMeta, num_microbatches: int, dtype):
    """Build + jit the 1F1B loss-and-grad executor for the dense chain.

    Returns ``f(weights, xs, labels, mask) -> (loss, grads)`` with the
    same semantics as ``jax.value_and_grad`` over the GPipe trainer's
    ``loss_fn`` — masked mean CE over real rows — so the two schedules
    are drop-in interchangeable (and tested for numerical parity).
    """
    stage_fn = _dense_stage_fn
    tail_fn = _dense_masked_ce_tail(meta.final_dim)

    mapped = make_1f1b(
        mesh,
        stage_fn,
        tail_fn,
        meta.num_stages,
        num_microbatches,
        microbatch_spec=P(AXIS_DATA, None),
        aux_spec=P(None, AXIS_DATA),
        want_dx0=False,  # xs is raw data; nothing upstream to backprop
    )
    act = jnp.asarray(meta.act_array(logits=True))
    width = jnp.asarray(meta.width_array())

    @jax.jit
    def run(weights: PipelineWeights, xs, labels, mask):
        # labels/mask arrive (M, B) microbatch-major (the layout
        # prepare_pipeline_batch produces). Fold the global
        # mean-normalizer into the mask so tail_fn needs no
        # cross-microbatch state.
        mask = mask.astype(dtype)
        mask = mask / mask.sum()
        sp = {"w": weights.w, "b": weights.b}
        st = {"act": act, "width": width}
        loss, g_sp, _g_tail, _dx0 = mapped(xs, sp, st, {}, (labels, mask))
        return loss, PipelineWeights(w=g_sp["w"], b=g_sp["b"])

    return run


@functools.lru_cache(maxsize=64)
def compiled_interleaved_dense_grad(mesh, meta: PipelineMeta, num_virtual: int,
                                    num_microbatches: int, dtype):
    """Interleaved (virtual-stage) loss-and-grad for the dense chain.

    ``meta`` must describe ``S * num_virtual`` pipeline chunks (build the
    params with a distribution of that length); chunk ``c`` runs on
    device ``c % S``, so the padded weight blocks regroup
    ``(V, L, D, D) -> (S, v, L, D, D)``. Same numerical contract as the
    other schedules (masked mean CE; parity-tested).
    """
    from tpu_dist_nn.parallel.interleaved import make_interleaved_1f1b
    from tpu_dist_nn.parallel.mesh import AXIS_STAGE

    S = mesh.shape[AXIS_STAGE]
    v = num_virtual
    V = meta.num_stages
    if V != S * v:
        raise ValueError(
            f"meta has {V} chunks but mesh stage axis {S} x virtual {v} "
            f"= {S * v}; build the pipeline params with a {S * v}-entry "
            "distribution"
        )
    stage_fn = _dense_stage_fn
    tail_fn = _dense_masked_ce_tail(meta.final_dim)

    mapped = make_interleaved_1f1b(
        mesh, stage_fn, tail_fn, v, num_microbatches,
        microbatch_spec=P(AXIS_DATA, None),
        aux_spec=P(None, AXIS_DATA),
        want_dx0=False,
    )

    from tpu_dist_nn.parallel.pipeline import regroup_chunks

    def regroup(a):
        return regroup_chunks(a, S, v)

    def ungroup(a):  # inverse
        return jnp.swapaxes(a, 0, 1).reshape(V, *a.shape[2:])

    act = jnp.asarray(meta.act_array(logits=True))
    width = jnp.asarray(meta.width_array())
    st = {"act": regroup(act), "width": regroup(width)}

    @jax.jit
    def run(weights: PipelineWeights, xs, labels, mask):
        mask = mask.astype(dtype)
        mask = mask / mask.sum()
        sp = {"w": regroup(weights.w), "b": regroup(weights.b)}
        loss, g_sp, _g_tail, _dx0 = mapped(xs, sp, st, {}, (labels, mask))
        return loss, PipelineWeights(w=ungroup(g_sp["w"]), b=ungroup(g_sp["b"]))

    return run
