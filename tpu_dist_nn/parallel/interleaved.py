"""Table-driven interleaved (virtual-stage) 1F1B pipeline executor.

Runs the schedules compiled by
:mod:`tpu_dist_nn.parallel.schedule_table`: device ``s`` holds ``v``
model chunks (global chunk ``c`` at local slot ``c // S``, ``c % S ==
s``), and each scan tick plays back one table entry — idle, one chunk's
forward, or one chunk's backward (with activation recompute, as in
:mod:`tpu_dist_nn.parallel.one_f_one_b`). Forward activations ride a
``ppermute`` ring ``s -> s+1 (mod S)`` — the wrap link carries chunk
``kS-1 -> kS`` hand-offs — and cotangents ride the reverse ring;
receive buffers (slot-allocated by the host scheduler, verified
clobber-free) decouple arrival from consumption, which is what lets the
Megatron-interleaved order cut the pipeline bubble to ``2(S-1)``
chunk-ticks, ``v``x less than contiguous-chunk 1F1B.

The executor is schedule-agnostic: any
:class:`~tpu_dist_nn.parallel.schedule_table.ScheduleTables` with the
same wire model plays back unchanged — proven by the zero-bubble
(ZB-H1) schedule, which arrives as just another table
(:func:`~tpu_dist_nn.parallel.schedule_table.build_zero_bubble`): its
SPLIT backward ops play back as two extra ``lax.switch`` branches —
``BWD_B`` recomputes the chunk forward and emits only the input
cotangent (the critical-path op, sent downstream immediately), parking
the consumed ``dy`` in a cotangent stash; ``BWD_W`` recomputes again
and emits only the weight gradient from the parked ``(x, dy)`` pair in
what would otherwise be a bubble tick. (Two recomputes per microbatch
instead of one — the extra forward is the price of the bubble halving;
XLA's DCE trims the unused cotangent from each branch.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_dist_nn.parallel.mesh import AXIS_DATA, AXIS_STAGE
from tpu_dist_nn.parallel.schedule_table import ScheduleTables, build_interleaved_1f1b


def make_interleaved_1f1b(
    mesh,
    stage_fn,
    tail_fn,
    num_virtual: int,
    num_microbatches: int,
    *,
    microbatch_spec=None,
    chunk_params_spec=None,
    chunk_static_spec=None,
    aux_spec=None,
    want_dx0: bool = True,
    tables: ScheduleTables | None = None,
    with_aux: bool = False,
    split_fns=None,
):
    """Interleaved counterpart of
    :func:`tpu_dist_nn.parallel.one_f_one_b.make_1f1b`.

    ``with_aux=True``: same contract as make_1f1b's —
    ``stage_fn -> (y, aux_contribution)`` with contributions
    PRE-SCALED; the backward recomputation adds the value to the loss
    and backpropagates cotangent 1.0. Under the zero-bubble split the
    aux's input gradient rides BWD_B and its weight gradient BWD_W
    (both phases pass the unit cotangent through their shared vjp);
    the value is counted once, in BWD_B.

    * ``stage_fn(chunk_params, chunk_static, x) -> y`` — ONE chunk's
      compute; ``chunk_params``/``chunk_static`` pytrees arrive with
      leaves ``(v, ...)`` per device (global layout ``(S, v, ...)``,
      spec ``P(stage)``) and this wrapper indexes out the scheduled
      chunk's slice per tick.
    * ``tail_fn(tail_params, y, *aux_f)`` — per-microbatch loss on the
      LAST chunk's output (pre-scaled), exactly as in ``make_1f1b``.

    Returns ``f(xs, chunk_params, chunk_static, tail_params, aux) ->
    (loss, chunk_grads, tail_grads, dx0)`` with ``chunk_grads`` in the
    ``(S, v, ...)`` layout of the params.

    ``split_fns=(fwd_collect, bwd_from_inputs, weight_grads)`` swaps
    the split-backward branches for the COTANGENT-STASH split
    (parallel/split_backward.py): ``BWD_B`` runs ``fwd_collect(pc, x)
    -> (y, inner)`` once, then ``bwd_from_inputs(pc, inner, dy) ->
    (dx, d_partial, wstash)`` — the backbone + dx GEMMs, stashing the
    per-op (activation, cotangent) pairs — and ``BWD_W`` runs
    ``weight_grads(wstash) -> d_partial``: PURE dW GEMMs, no forward
    recompute (the round-5 wall-clock measurement's fix: the recompute
    split priced zb at 1.39-1.92x of its combined-backward rivals; the
    stash split restores the canonical tick ratios at ~16x the
    split-bridge stash memory). ``d_partial`` pytrees must together
    cover the chunk grads (zeros in the other half). Requires
    ``with_aux=False`` (aux channels ride the recompute split).
    """
    if split_fns is not None and with_aux:
        raise ValueError(
            "split_fns (cotangent-stash split) does not compose with "
            "with_aux: aux channels ride the recompute split"
        )
    S = mesh.shape[AXIS_STAGE]
    v, M = num_virtual, num_microbatches
    if tables is None:
        tables = build_interleaved_1f1b(S, v, M)
    if (tables.num_devices, tables.num_chunks, tables.num_microbatches) != (S, S * v, M):
        raise ValueError("tables do not match (S, v, M)")
    T, A, G, K = tables.ticks, tables.abuf_slots, tables.gbuf_slots, tables.stash_slots
    D = tables.dybuf_slots
    # Split-backward (zero-bubble) branches are traced only when the
    # tables actually contain BWD_B/BWD_W ops — combined-backward
    # schedules pay no extra compile cost.
    from tpu_dist_nn.parallel.schedule_table import BWD_B

    has_split = bool((tables.op >= BWD_B).any())
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    if microbatch_spec is None:
        microbatch_spec = P(AXIS_DATA)
    # Microbatch-sharding axes beyond `data` (e.g. `seq`) make the
    # wires/accumulators varying and the chunk grads reduce over them
    # like `data` (one shared derivation: one_f_one_b.microbatch_axes).
    from tpu_dist_nn.parallel.one_f_one_b import microbatch_axes

    data_like = microbatch_axes(microbatch_spec)
    vary = (AXIS_STAGE, *data_like)
    if chunk_params_spec is None:
        chunk_params_spec = P(AXIS_STAGE)
    if chunk_static_spec is None:
        # A plain per-leaf default, NOT chunk_params_spec: that may be a
        # pytree of specs (e.g. the Megatron per-leaf dict) whose
        # structure the static operand does not share (make_1f1b's
        # stage_static_spec note).
        chunk_static_spec = P(AXIS_STAGE)
    if aux_spec is None:
        aux_spec = P(None, *microbatch_spec)
    xs_spec = P(None, *microbatch_spec)
    tb = {
        name: jnp.asarray(getattr(tables, name))
        for name in (
            "op", "chunk", "mb", "stash",
            "abuf_read", "gbuf_read", "is_c0",
        )
    }
    tb["dy_stash"] = jnp.asarray(tables.dy_stash_or_empty())
    # Routing: sender-side ring choice + channel-major receives (a
    # device can receive up to three payloads per tick — fwd ring, bwd
    # ring, self loopback — on non-monotone placements like ZB-V's
    # V-shape; classic schedules derive the fwd→abuf / bwd→gbuf
    # defaults).
    tb["send_rev"] = jnp.asarray(tables.send_rev_or_default())
    for name, arr in tables.channel_tables().items():
        tb[name] = jnp.asarray(arr)

    def device_fn(xs, chunk_params, chunk_static, tail_params, aux):
        def mark_varying(z, axes):
            # Idempotent "mark varying over `axes`" (one_f_one_b.py).
            have = jax.typeof(z).vma
            need = tuple(a for a in axes if a not in have)
            return lax.pcast(z, need, to="varying") if need else z

        # Strip the length-1 stage-shard axis -> (v, ...) leaves; mark
        # params data-varying so jax.vjp stays collective-free (see
        # one_f_one_b's note), tail params (stage, data)-varying.
        # Marking is idempotent and each leaf's own pre-mark sharding
        # is remembered for the end-of-scan grad reduction (a leaf can
        # be sharded over a batch axis — EP's expert-sharded banks;
        # one_f_one_b.py's note).
        sp0 = jax.tree.map(lambda a: a[0], chunk_params)
        sp_shard_axes = jax.tree.map(
            lambda a: jax.typeof(a).vma, sp0
        )
        sp = jax.tree.map(lambda a: mark_varying(a, data_like), sp0)
        st = jax.tree.map(lambda a: a[0], chunk_static)
        s_idx = lax.axis_index(AXIS_STAGE)
        mb_shape = xs.shape[1:]
        dt = xs.dtype

        def vcast(z):
            return mark_varying(z, vary)

        def zeros_like_vma(ref):
            # Grad accumulators must carry the PRIMAL leaf's varying
            # axes: a model-sharded Megatron chunk leaf (varying over
            # `model`) accumulates per-shard cotangents, so an
            # accumulator left invariant over `model` would fail the
            # lax.switch branch-type check at the first bwd tick.
            return mark_varying(
                jnp.zeros(ref.shape, ref.dtype),
                jax.typeof(ref).vma,
            )

        tp = jax.tree.map(lambda a: vcast(jnp.asarray(a)), tail_params)

        # This device's schedule rows: (T,) each.
        row = {
            k: lax.dynamic_index_in_dim(val, s_idx, 0, keepdims=False)
            for k, val in tb.items()
        }

        zeros_wire = vcast(jnp.zeros(mb_shape, dt))
        if split_fns is None or not has_split:
            # Cotangent stash bridging split BWD_B -> BWD_W (1 dummy
            # slot for combined schedules).
            dybuf0 = vcast(jnp.zeros((D, *mb_shape), dt))
        else:
            # Stash-split mode: the bridge carries the per-op
            # (activation, cotangent) PYTREE instead of the bare dy —
            # shapes inferred once from the split fns at this chunk/
            # microbatch shape (every chunk is shape-identical).
            # Shapes only — strip vma so eval_shape traces clean.
            pc0_sd = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), sp
            )
            x_sd = jax.ShapeDtypeStruct(mb_shape, dt)
            _, inner_sd = jax.eval_shape(split_fns[0], pc0_sd, x_sd)
            _, _, wst_sd = jax.eval_shape(
                split_fns[1], pc0_sd, inner_sd, x_sd
            )
            dybuf0 = jax.tree.map(
                lambda sd: vcast(jnp.zeros((D, *sd.shape), sd.dtype)),
                wst_sd,
            )
        carry0 = (
            zeros_wire,                                  # fwd ring payload
            zeros_wire,                                  # bwd ring payload
            zeros_wire,                                  # self loopback
            vcast(jnp.zeros((A, *mb_shape), dt)),        # activation recv buf
            vcast(jnp.zeros((G, *mb_shape), dt)),        # cotangent recv buf
            vcast(jnp.zeros((K, *mb_shape), dt)),        # input stash
            dybuf0,                                      # split bridge
            jax.tree.map(zeros_like_vma, sp),
            jax.tree.map(zeros_like_vma, tp),
            vcast(jnp.zeros((M if want_dx0 else 1, *mb_shape), dt)),
            vcast(jnp.zeros((), jnp.float32)),           # loss accumulator
        )

        def tick(carry, t):
            (fwd_wire, bwd_wire, self_wire, abuf, gbuf, stash, dybuf,
             g_sp, g_tp, dx0, loss_acc) = carry
            # Receive phase, channel-major: each physical channel (fwd
            # ring, bwd ring, self loopback) can carry one payload per
            # tick, stored into abuf (dst 0) or gbuf (dst 1) at its
            # scheduled slot (-1 = nothing on that channel).
            for name, wire in (
                ("fwdch", fwd_wire), ("bwdch", bwd_wire),
                ("selfch", self_wire),
            ):
                dst = row[f"{name}_dst"][t]
                slot = row[f"{name}_slot"][t]
                abuf = jnp.where(
                    dst == 0,
                    lax.dynamic_update_index_in_dim(
                        abuf, wire, jnp.clip(slot, 0, A - 1), 0
                    ),
                    abuf,
                )
                gbuf = jnp.where(
                    dst == 1,
                    lax.dynamic_update_index_in_dim(
                        gbuf, wire, jnp.clip(slot, 0, G - 1), 0
                    ),
                    gbuf,
                )
            g_slot = row["chunk"][t]
            f = row["mb"][t]
            k_slot = row["stash"][t]
            pc = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, g_slot, 0, keepdims=False),
                sp,
            )
            stc = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, g_slot, 0, keepdims=False),
                st,
            )

            def chunk_fwd_g(p, x):
                return stage_fn(p, stc, x)

            def idle(_):
                return (zeros_wire, zeros_wire, stash, dybuf, g_sp, g_tp,
                        dx0, loss_acc)

            def fwd(_):
                ar = row["abuf_read"][t]
                feed = lax.dynamic_index_in_dim(xs, f, 0, keepdims=False)
                buf = lax.dynamic_index_in_dim(
                    abuf, jnp.clip(ar, 0, A - 1), 0, keepdims=False
                )
                x_in = jnp.where(ar < 0, feed, buf)
                new_stash = lax.dynamic_update_index_in_dim(stash, x_in, k_slot, 0)
                out = chunk_fwd_g(pc, x_in)
                y = out[0] if with_aux else out  # bwd recomputes the aux
                return (y, zeros_wire, new_stash, dybuf, g_sp, g_tp,
                        dx0, loss_acc)

            def split_vjp(x_in):
                """vjp of the chunk; with_aux folds the unit aux
                cotangent in so both backward phases see it."""
                if with_aux:
                    (y, aux_v), svjp = jax.vjp(chunk_fwd_g, pc, x_in)
                    return y, aux_v.astype(jnp.float32), (
                        lambda dy: svjp((dy, vcast(jnp.ones((), aux_v.dtype))))
                    )
                y, svjp = jax.vjp(chunk_fwd_g, pc, x_in)
                return y, vcast(jnp.zeros((), jnp.float32)), svjp

            def resolve_dy(y):
                """This op's cotangent: the loss tail (last chunk) or
                the received upstream grad — plus the tail's loss and
                tail-param grads (zeros off the last chunk)."""
                gr = row["gbuf_read"][t]
                aux_f = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, f, 0, keepdims=False),
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

                loss_f, dy_tail, d_tp = lax.cond(gr < 0, tail_live, tail_skip, 0)
                grad_in = lax.dynamic_index_in_dim(
                    gbuf, jnp.clip(gr, 0, G - 1), 0, keepdims=False
                )
                return jnp.where(gr < 0, dy_tail, grad_in), loss_f, d_tp

            def accumulate_g_sp(d_pc):
                return jax.tree.map(
                    lambda acc, d: lax.dynamic_update_index_in_dim(
                        acc,
                        lax.dynamic_index_in_dim(acc, g_slot, 0, keepdims=False) + d,
                        g_slot,
                        0,
                    ),
                    g_sp,
                    d_pc,
                )

            def record_dx0(dx):
                if not want_dx0:
                    return dx0
                return jnp.where(
                    row["is_c0"][t] > 0,
                    lax.dynamic_update_index_in_dim(dx0, dx, f, 0),
                    dx0,
                )

            def bwd(_):
                x_in = lax.dynamic_index_in_dim(stash, k_slot, 0, keepdims=False)
                y, aux_v, svjp = split_vjp(x_in)
                dy, loss_f, d_tp = resolve_dy(y)
                d_pc, dx = svjp(dy)
                return (
                    zeros_wire,
                    dx,
                    stash,
                    dybuf,
                    accumulate_g_sp(d_pc),
                    jax.tree.map(jnp.add, g_tp, d_tp),
                    record_dx0(dx),
                    loss_acc + loss_f + aux_v,
                )

            def bwd_b(_):
                # Zero-bubble split: input grad ONLY (critical path).
                # The consumed dy is parked in the cotangent stash for
                # the matching BWD_W tick; d_pc is unused, so XLA's DCE
                # trims the weight-grad computation from this branch.
                # The aux value is counted HERE (once); its weight
                # grads ride the matching BWD_W's shared vjp.
                x_in = lax.dynamic_index_in_dim(stash, k_slot, 0, keepdims=False)
                y, aux_v, svjp = split_vjp(x_in)
                dy, loss_f, d_tp = resolve_dy(y)
                _d_pc, dx = svjp(dy)
                dslot = jnp.clip(row["dy_stash"][t], 0, D - 1)
                new_dybuf = lax.dynamic_update_index_in_dim(dybuf, dy, dslot, 0)
                return (
                    zeros_wire,
                    dx,
                    stash,
                    new_dybuf,
                    g_sp,
                    jax.tree.map(jnp.add, g_tp, d_tp),
                    record_dx0(dx),
                    loss_acc + loss_f + aux_v,
                )

            def bwd_w(_):
                # Zero-bubble split: weight grad from the parked
                # (x, dy) pair; no wire traffic, so the scheduler can
                # park this op in any bubble tick.
                x_in = lax.dynamic_index_in_dim(stash, k_slot, 0, keepdims=False)
                dy = lax.dynamic_index_in_dim(
                    dybuf, jnp.clip(row["dy_stash"][t], 0, D - 1), 0,
                    keepdims=False,
                )
                _y, _aux_v, svjp = split_vjp(x_in)
                d_pc, _dx = svjp(dy)
                return (
                    zeros_wire,
                    zeros_wire,
                    stash,
                    dybuf,
                    accumulate_g_sp(d_pc),
                    g_tp,
                    dx0,
                    loss_acc,
                )

            def bwd_b_stash(_):
                # Cotangent-stash split B: one forward (collecting the
                # per-block inputs), backbone + dx GEMMs, and the
                # per-op (act, cot) pairs parked in the bridge — the
                # partial (bias/LN) grads accumulate HERE, the dW GEMMs
                # moved wholesale to BWD_W.
                x_in = lax.dynamic_index_in_dim(stash, k_slot, 0, keepdims=False)
                y, inner = split_fns[0](pc, x_in)
                dy, loss_f, d_tp = resolve_dy(y)
                dx, d_part, wst = split_fns[1](pc, inner, dy)
                dslot = jnp.clip(row["dy_stash"][t], 0, D - 1)
                new_dybuf = jax.tree.map(
                    lambda buf, w: lax.dynamic_update_index_in_dim(
                        buf, w, dslot, 0
                    ),
                    dybuf, wst,
                )
                return (
                    zeros_wire,
                    dx,
                    stash,
                    new_dybuf,
                    accumulate_g_sp(d_part),
                    jax.tree.map(jnp.add, g_tp, d_tp),
                    record_dx0(dx),
                    loss_acc + loss_f,
                )

            def bwd_w_stash(_):
                # The canonical ZB W tick: pure dW GEMMs from the
                # bridged (act, cot) pairs — no forward recompute, no
                # backward backbone (asserted by
                # tests/test_split_backward.py's jaxpr contract).
                dslot = jnp.clip(row["dy_stash"][t], 0, D - 1)
                wst = jax.tree.map(
                    lambda buf: lax.dynamic_index_in_dim(
                        buf, dslot, 0, keepdims=False
                    ),
                    dybuf,
                )
                d_big = split_fns[2](wst)
                return (
                    zeros_wire,
                    zeros_wire,
                    stash,
                    dybuf,
                    accumulate_g_sp(d_big),
                    g_tp,
                    dx0,
                    loss_acc,
                )

            split_branches = (
                [bwd_b_stash, bwd_w_stash]
                if split_fns is not None else [bwd_b, bwd_w]
            )
            branches = [idle, fwd, bwd] + (split_branches if has_split else [])
            (send_y, send_dx, stash, dybuf, g_sp, g_tp, dx0,
             loss_acc) = lax.switch(row["op"][t], branches, 0)
            # Sender-side routing: 0 = natural ring (fwd op -> fwd
            # ring, bwd op -> bwd ring), 1 = the opposite ring (the
            # V placement's second leg), 2 = self loopback (the V's
            # apex — no wire at all). Only one of send_y/send_dx is
            # non-zero per tick, so swapping both is the clean "ride
            # the other ring".
            sr = row["send_rev"][t]
            ring_y = lax.select_n(sr, send_y, send_dx, zeros_wire)
            ring_dx = lax.select_n(sr, send_dx, send_y, zeros_wire)
            nxt_self = send_y + send_dx  # one is zeros; read iff sr==2
            with jax.named_scope("interleaved_ring_hop"):
                nxt_fwd = (
                    lax.ppermute(ring_y, AXIS_STAGE, fwd_perm) if S > 1 else ring_y
                )
                nxt_bwd = (
                    lax.ppermute(ring_dx, AXIS_STAGE, bwd_perm) if S > 1 else ring_dx
                )
            return (
                nxt_fwd, nxt_bwd, nxt_self, abuf, gbuf, stash, dybuf,
                g_sp, g_tp, dx0, loss_acc
            ), None

        (_f, _b, _sf, _a, _g, _s, _dy, g_sp, g_tp, dx0, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T)
        )
        # Per-leaf reduction: only over microbatch axes the primal leaf
        # was replicated on (one_f_one_b.py's note — EP's
        # expert-sharded banks keep per-shard grads).
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
            dx0 = jnp.zeros((), jnp.float32)
        loss = lax.psum(loss_acc, vary)
        return loss, g_sp, g_tp, dx0

    return jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(
            xs_spec,
            chunk_params_spec,
            chunk_static_spec,
            P(),
            aux_spec,
        ),
        out_specs=(P(), chunk_params_spec, P(), xs_spec if want_dx0 else P()),
    )


def make_interleaved_forward(
    mesh,
    stage_fn,
    num_virtual: int,
    num_microbatches: int,
    *,
    microbatch_spec=None,
    chunk_params_spec=None,
    chunk_static_spec=None,
    tables: ScheduleTables | None = None,
):
    """Forward-only (inference) interleaved executor.

    The inference leg of :func:`make_interleaved_1f1b`: plays back a
    :func:`~tpu_dist_nn.parallel.schedule_table.build_interleaved_forward`
    table — FWD/IDLE ticks only, activations on the ``s -> s+1 (mod S)``
    ring, no stash/cotangents — and collects the LAST chunk's output
    per microbatch. Same ``stage_fn(chunk_params, chunk_static, x)``
    contract and ``(S, v, ...)`` chunk layout as the training executor.

    Returns ``f(xs, chunk_params, chunk_static) -> (M, *microbatch_shape)``.
    """
    from tpu_dist_nn.parallel.schedule_table import build_interleaved_forward

    S = mesh.shape[AXIS_STAGE]
    v, M = num_virtual, num_microbatches
    V = S * v
    if tables is None:
        tables = build_interleaved_forward(S, v, M)
    if (tables.num_devices, tables.num_chunks, tables.num_microbatches) != (S, V, M):
        raise ValueError("tables do not match (S, v, M)")
    T, A = tables.ticks, tables.abuf_slots
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    vary = (AXIS_STAGE, AXIS_DATA)
    if microbatch_spec is None:
        microbatch_spec = P(AXIS_DATA)
    if chunk_params_spec is None:
        chunk_params_spec = P(AXIS_STAGE)
    if chunk_static_spec is None:
        # Same asymmetry guard as the training executor: the params
        # spec may be a per-leaf pytree the static operand doesn't share.
        chunk_static_spec = P(AXIS_STAGE)
    xs_spec = P(None, *microbatch_spec)
    tb = {
        name: jnp.asarray(getattr(tables, name))
        for name in ("op", "chunk", "mb", "abuf_read")
    }
    # Channel-major receives: forward-only schedules use the fwd ring
    # and, at S=1 (where every hop is device-local), the self loopback.
    # A reverse-ring forward hop (send_rev == 1) would need the bwd
    # wire this executor does not carry — no forward-only builder
    # emits one; fail loudly if that changes.
    import numpy as _np

    send_rev_np = tables.send_rev_or_default()
    if (_np.asarray(send_rev_np) == 1).any():
        raise ValueError(
            "forward-only executor has no reverse ring: tables contain "
            "send_rev == 1 hops (use the training executor's wire model)"
        )
    tb["send_rev"] = jnp.asarray(send_rev_np)
    for name, arr in tables.channel_tables().items():
        if name.startswith(("fwdch", "selfch")):
            tb[name] = jnp.asarray(arr)

    def device_fn(xs, chunk_params, chunk_static):
        sp = jax.tree.map(lambda a: a[0], chunk_params)
        st = jax.tree.map(lambda a: a[0], chunk_static)
        s_idx = lax.axis_index(AXIS_STAGE)
        mb_shape = xs.shape[1:]
        dt = xs.dtype

        def vcast(z):
            have = jax.typeof(z).vma
            need = tuple(a for a in vary if a not in have)
            return lax.pcast(z, need, to="varying") if need else z

        row = {
            k: lax.dynamic_index_in_dim(val, s_idx, 0, keepdims=False)
            for k, val in tb.items()
        }
        zeros_wire = vcast(jnp.zeros(mb_shape, dt))
        carry0 = (
            zeros_wire,                            # fwd ring payload
            zeros_wire,                            # self loopback
            vcast(jnp.zeros((A, *mb_shape), dt)),  # activation recv buf
            vcast(jnp.zeros((M, *mb_shape), dt)),  # per-mb outputs
        )

        def tick(carry, t):
            fwd_wire, self_wire, abuf, outs = carry
            for name, wire in (("fwdch", fwd_wire), ("selfch", self_wire)):
                dst = row[f"{name}_dst"][t]
                slot = row[f"{name}_slot"][t]
                abuf = jnp.where(
                    dst == 0,
                    lax.dynamic_update_index_in_dim(
                        abuf, wire, jnp.clip(slot, 0, A - 1), 0
                    ),
                    abuf,
                )
            g_slot = row["chunk"][t]
            f = row["mb"][t]
            c_global = g_slot * S + s_idx
            pc = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, g_slot, 0, keepdims=False),
                sp,
            )
            stc = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, g_slot, 0, keepdims=False),
                st,
            )

            def idle(_):
                return zeros_wire, outs

            def fwd(_):
                ar = row["abuf_read"][t]
                feed = lax.dynamic_index_in_dim(xs, f, 0, keepdims=False)
                buf = lax.dynamic_index_in_dim(
                    abuf, jnp.clip(ar, 0, A - 1), 0, keepdims=False
                )
                x_in = jnp.where(ar < 0, feed, buf)
                y = stage_fn(pc, stc, x_in)
                is_last = c_global == V - 1
                new_outs = jnp.where(
                    is_last,
                    lax.dynamic_update_index_in_dim(outs, y, f, 0),
                    outs,
                )
                return jnp.where(is_last, zeros_wire, y), new_outs

            send_y, outs = lax.switch(row["op"][t], [idle, fwd], 0)
            sr = row["send_rev"][t]
            ring_y = jnp.where(sr == 2, zeros_wire, send_y)
            with jax.named_scope("interleaved_fwd_ring_hop"):
                nxt = (
                    lax.ppermute(ring_y, AXIS_STAGE, fwd_perm)
                    if S > 1 else ring_y
                )
            return (nxt, send_y, abuf, outs), None

        (_w, _sf, _a, outs), _ = lax.scan(tick, carry0, jnp.arange(T))
        # Outputs live only on the last chunk's device (S-1): replicate.
        return lax.psum(outs, AXIS_STAGE)

    return jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(xs_spec, chunk_params_spec, chunk_static_spec),
        out_specs=xs_spec,
    )
