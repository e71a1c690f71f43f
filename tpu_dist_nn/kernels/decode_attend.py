"""Differential attention of one decode query a slot over the shared K/V
cache: each live byte read once, the scores kept on the chip.

The contract of :func:`tpu_dist_nn.models.sambay._attend_rows`, which
stays as the path of shapes that do not tile and as this kernel's
oracle: ``q (S, R, 2, 2, d)`` (K/V pair ``r``, the query pair on it,
first or second softmax) over the first ``pos[s]`` positions of slot
``s`` of ``K``, ``V (1, slots, G, d, M)`` (positions in the lanes, as
the slot cache stores them) and over the position's own ``k_own``,
``v_own (S, G, d)``, which no cache holds yet.  Returns ``(S, R, 2, 2
d)`` float32.

XLA scores every slot's whole extent and masks: the float32 scores ``(S,
G, 2, M)`` go to HBM, come back for the softmax, go out as
probabilities and come back for the values (PERF.md section 5).  Here
the whole cache arrays go to the call and stay in HBM; ``pos`` is
scalar-prefetched and the kernel copies by hand what is live: a slot's
position tiles of every head up to the one that holds ``pos - 1``, a
tile all of whose lanes are live as one DMA, the frontier tile by the
128-lane pieces that are (:func:`fetched_tiles`).  Slots behind the
first ``S`` (a prefix pool's) are never named.

One grid row a slot, and one more: row ``u`` walks the K tiles of slot
``u`` beside the V tiles of slot ``u - 1``, a pair a job, so that both
arrays are in flight at once and nothing waits for a softmax; the jobs
of all rows are one sequence, ``_IN_FLIGHT`` of them copied ahead of the
one computed, across rows.  Measured on a v5e, the copies alone and the
whole kernel take the same time, 98 % of what the chip's HBM gives a
plain read (PERF.md section 6, PR 32).

A K/V head has two query rows, too few for the MXU's 128: both products
run on the VPU.  K lies ``(d, positions)``: a query element is spread
over the lanes, multiplied in and added down the ``d`` sublanes.  A
slot's scores ``(2, 2 R, M)`` float32 stay in VMEM with the running
maximum and sum of both softmaxes (the frontier masked by lane); after
the last K tile the own key joins as one more column.  A row later ``a
= p_0 - lam p_1`` is formed a tile at a time, spread over the sublanes,
multiplied into V and added tile onto tile in float32, with one lane
reduction at the end.  The oracle's arithmetic or wider: float32
scores, softmax and accumulation, and ``a`` is not rounded to the
values' type.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Position tiles of a job, widest first.  Timed on a v5e at the
# reasoning cell's shapes: 512 and 1024 lanes 1.76 ms a layer (the
# copies' own time), 256 1.92, 128 2.78: a job's fixed work, the
# softmax's share and the loop's, is paid a tile (PERF.md section 6).
_TILES = (512, 256, 128)
# Jobs whose copies are started ahead of the one computed, its own
# among them: 2 left the HBM idle 2 % of the time, 4 and 6 read as 3.
_IN_FLIGHT = 3
# What the kernel may hold in VMEM: the jobs in flight, a slot's
# scores twice (the slot scored and the slot before it), the queries
# spread and the accumulators.
_RESIDENT_BYTES = 48 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",),
    vmem_limit_bytes=64 * 1024 * 1024,
)


def _score_rows(G: int) -> int:
    """A softmax's ``2 R = G`` score rows, in whole sublane tiles."""
    return -(-G // 8) * 8


def _resident(G: int, d: int, M: int, tile: int, itemsize: int) -> int:
    return (2 * _IN_FLIGHT * G * d * tile * itemsize
            + 2 * 2 * _score_rows(G) * M * 4 + (G // 2) * 8 * tile * 4
            + 2 * 2 * G * d * _LANES * 4)


def tiles(S: int, G: int, d: int, M: int, dtype):
    """The position tile the kernel walks these shapes with, or ``None``
    where they do not tile: the caller keeps ``_attend_rows``.  ``S``
    decides nothing (a slot is a row of the grid)."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or G % 2 or d % (32 // itemsize) \
            or 2 * G > _LANES:
        # K/V heads in pairs; whole sublane tiles of d; a slot's 2 G
        # query rows and output columns in the lanes of one tile.
        return None
    return next((t for t in _TILES if M % t == 0
                 and _resident(G, d, M, t, itemsize) <= _RESIDENT_BYTES),
                None)


def _reach(pos):
    """Lanes of a slot's rows that are read for a query at ``pos``: the
    positions before it, one where nothing is cached yet.  Traced in
    the kernel, numpy on the scheduler's host path."""
    return pos + (pos < 1)


def fetched_tiles(pos):
    """128-lane position tiles of a slot's K (and of its V) the kernel
    copies for a query at ``pos`` (an int array)."""
    return (_reach(np.asarray(pos, np.int64)) - 1) // _LANES + 1


def _own_scores(qg, k_own):
    """``qg (S, G, 2, d)`` float32 on the position's own key ``(S, G,
    d)``: the column no cache holds, ``(S, G, 2)``."""
    return jnp.sum(qg * k_own[:, :, None, :].astype(jnp.float32), -1) \
        / np.sqrt(qg.shape[-1])


def _kernel(pos_ref, lam_ref, q_ref, own_ref, k_hbm, v_hbm, o_ref, aown_ref,
            kbuf, vbuf, sem, cur, qb_ref, s_ref, m_ref, l_ref, a_ref,
            acc_ref, *, S):
    u = pl.program_id(0)
    NB, G, d, TM = kbuf.shape
    pieces = TM // _LANES
    sublane = lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
    chunks = [slice(c * _LANES, (c + 1) * _LANES) for c in range(pieces)]
    scale = 1.0 / np.sqrt(d)
    lam = lam_ref[0]

    def reach(slot):
        return _reach(pos_ref[slot])

    def visits(slot):
        return (reach(slot) - 1) // TM + 1

    def n_jobs(row):
        """K tiles of slot ``row`` beside V tiles of slot ``row - 1``."""
        nk = jnp.where(row < S, visits(jnp.minimum(row, S - 1)), 0)
        nv = jnp.where(row >= 1, visits(jnp.maximum(row - 1, 0)), 0)
        return jnp.maximum(nk, nv)

    def copies(row, t, buf, act):
        """Start, or wait for, the copies of job ``(row, t)`` into buffer
        ``buf``: the same conditions name the same copies both times."""
        for which, (hbm, vmem, slot, on) in enumerate((
                (k_hbm, kbuf, jnp.minimum(row, S - 1), row < S),
                (v_hbm, vbuf, jnp.maximum(row - 1, 0), row >= 1))):
            tile0 = t * TM
            # 128-lane pieces of the tile that hold a position read.
            live = jnp.where(on, jnp.clip(
                (reach(slot) - tile0 + _LANES - 1) // _LANES, 0, pieces), 0)

            def go(lane0, width, c):
                cp = pltpu.make_async_copy(
                    hbm.at[0, slot, :, :, pl.ds(
                        pl.multiple_of(tile0 + lane0, _LANES), width)],
                    vmem.at[buf, :, :, pl.ds(lane0, width)],
                    sem.at[which, buf, c])
                cp.start() if act == "start" else cp.wait()

            @pl.when(live == pieces)
            def _():
                go(0, TM, 0)

            def piece(c, carry):
                go(pl.multiple_of(c * _LANES, _LANES), _LANES, c)
                return carry

            lax.fori_loop(0, jnp.where(live == pieces, 0, live), piece, 0)

    def start_next():
        # cur: the next job to start (row, t), how many were started,
        # how many were waited for.
        row, t = cur[0], cur[1]

        @pl.when(row <= S)
        def _():
            copies(row, t, cur[2] % NB, "start")
            cur[2] = cur[2] + 1
            last = t + 1 == n_jobs(row)
            cur[0] = jnp.where(last, row + 1, row)
            cur[1] = jnp.where(last, 0, t + 1)

    @pl.when(u == 0)
    def _():
        # A frontier tile's dead lanes keep what the buffer held, and are
        # multiplied by zeros: what it held must be finite.
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        for i in range(4):
            cur[i] = 0
        lax.fori_loop(0, NB - 1, lambda i, carry: start_next(), None)

    ks, vs = jnp.minimum(u, S - 1), jnp.maximum(u - 1, 0)
    kpar, vpar = u % 2, (u + 1) % 2  # where slot u's scores lie, slot u - 1's

    @pl.when(u < S)
    def _():
        # A head's two query rows, each element spread over the lanes.
        qT = q_ref[0]  # (d, 2 G): lane i = 2 g + p
        for i in range(2 * G):  # a lane picked by number: not a loop
            qb_ref[i] = jnp.broadcast_to(qT[:, i:i + 1], (d, _LANES))
        m_ref[kpar] = jnp.full(m_ref.shape[1:], -jnp.inf, jnp.float32)
        l_ref[kpar] = jnp.zeros(l_ref.shape[1:], jnp.float32)

    @pl.when(u >= 1)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def job(t, carry):
        start_next()
        buf = cur[3] % NB
        copies(u, t, buf, "wait")
        cur[3] = cur[3] + 1

        @pl.when((u < S) & (t < visits(ks)))
        def _():
            # Head g = 2 r + c is softmax c of pair r: row 2 r + p of
            # plane c.  A row of a sublane tile at an offset known only
            # in the loop is written as the tile under a mask.
            def pair(r, carry):
                tile8 = pl.ds(pl.multiple_of(2 * r // 8 * 8, 8), 8)
                for c in range(2):
                    g = 2 * r + c
                    spread = [qb_ref[2 * g + p] for p in range(2)]
                    for lanes in chunks:
                        kf = kbuf[buf, g, :, lanes].astype(jnp.float32)
                        for p in range(2):
                            row = jnp.sum(kf * spread[p], axis=0,
                                          keepdims=True) * scale
                            pltpu.store(
                                s_ref.at[kpar, t, c, tile8, lanes],
                                jnp.broadcast_to(row, sublane.shape),
                                mask=sublane == (2 * r + p) % 8)
                return carry

            lax.fori_loop(0, G // 2, pair, 0)
            at = t * TM + lax.broadcasted_iota(jnp.int32, s_ref.shape[2:], 2)
            sc = jnp.where(at < pos_ref[ks], s_ref[kpar, t], -jnp.inf)
            s_ref[kpar, t] = sc
            m_old = m_ref[kpar]
            m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1, keepdims=True))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            l_ref[kpar] = l_ref[kpar] * jnp.exp(m_old - m_safe) + jnp.sum(
                jnp.exp(sc - m_safe), axis=-1, keepdims=True)
            m_ref[kpar] = m_new

        @pl.when((u >= 1) & (t < visits(vs)))
        def _():
            # m and l are the slot's final maximum and 1 / sum by now.
            e, inv = jnp.exp(s_ref[vpar, t] - m_ref[vpar]), l_ref[vpar]
            a = e[0] * inv[0] - lam * (e[1] * inv[1])
            for i in range(G):  # row 2 r + p to where a loop finds it
                a_ref[i // 2, i % 2:i % 2 + 1] = a[i:i + 1]

            def pair(r, carry):
                for c in range(2):
                    g = 2 * r + c
                    acc = [acc_ref[2 * g + p] for p in range(2)]
                    for lanes in chunks:
                        vf = vbuf[buf, g, :, lanes].astype(jnp.float32)
                        for p in range(2):
                            acc[p] = acc[p] + vf * a_ref[r, p:p + 1, lanes]
                    for p in range(2):
                        acc_ref[2 * g + p] = acc[p]
                return carry

            lax.fori_loop(0, G // 2, pair, 0)

        return carry

    lax.fori_loop(0, n_jobs(u), job, 0)

    @pl.when(u < S)
    def _():
        # The own key as one more column of both softmaxes.
        own, m = own_ref[0], m_ref[kpar]  # (2, rows, 1)
        m_own = jnp.maximum(m, own)
        e_own = jnp.exp(own - m_own)
        inv = 1.0 / (l_ref[kpar] * jnp.exp(m - m_own) + e_own)
        m_ref[kpar], l_ref[kpar] = m_own, inv
        aown_ref[0] = e_own[0] * inv[0] - lam * (e_own[1] * inv[1])

    @pl.when(u >= 1)
    def _():
        # Columns by number, not in a loop: 40 lane reductions in a row,
        # each waiting for the one before, cost 0.3 ms a layer.
        lane = lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
        out = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for i in range(2 * G):
            out = jnp.where(
                lane == i, jnp.sum(acc_ref[i], axis=1, keepdims=True), out)
        o_ref[0] = out


@functools.lru_cache(maxsize=None)
def _call(S, G, d, M, TM, dtype, interpret: bool):
    """The ``pallas_call`` of these shapes.  One object a shape: the
    layers of a program that share it (the full layer, the cross layers'
    scan) trace the kernel once, which is seconds of a server's start
    on the chip's host."""
    R, rows = G // 2, _score_rows(G)

    def row_of(slot_of_row, *dims):
        return pl.BlockSpec((1,) + dims, lambda u, pos_ref: (
            slot_of_row(u),) + (0,) * len(dims))

    scored = lambda u: jnp.minimum(u, S - 1)   # noqa: E731: row u's K slot
    valued = lambda u: jnp.maximum(u - 1, 0)   # noqa: E731: row u's V slot
    return pl.pallas_call(
        functools.partial(_kernel, S=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S + 1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      row_of(scored, d, 2 * G),
                      row_of(scored, 2, rows, 1),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row_of(valued, d, _LANES),
                       row_of(scored, rows, 1)],
            scratch_shapes=[
                pltpu.VMEM((_IN_FLIGHT, G, d, TM), dtype),
                pltpu.VMEM((_IN_FLIGHT, G, d, TM), dtype),
                pltpu.SemaphoreType.DMA((2, _IN_FLIGHT, TM // _LANES)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((2 * G, d, _LANES), jnp.float32),
                pltpu.VMEM((2, M // TM, 2, rows, TM), jnp.float32),
                pltpu.VMEM((2, 2, rows, 1), jnp.float32),
                pltpu.VMEM((2, 2, rows, 1), jnp.float32),
                pltpu.VMEM((R, 2, TM), jnp.float32),
                pltpu.VMEM((2 * G, d, _LANES), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((S, d, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((S, rows, 1), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="decode_attend",
    )


def attend_rows(q, K, V, k_own, v_own, pos, lam, *, tile=None):
    """``q (S, R, 2, 2, d)`` at ``pos (S,)`` over slots ``[0, S)`` of the
    whole cache arrays ``K``, ``V (1, slots, G, d, M)`` and the own
    ``k_own``, ``v_own (S, G, d)``; ``lam`` a traced scalar.  Returns
    ``(S, R, 2, 2 d)`` float32.  The shapes must tile (:func:`tiles`).
    ``tile`` is for timing other widths (tools/decode_attend_sweep.py):
    the program passes none."""
    S, R, _, _, d = q.shape
    G, M = K.shape[2], K.shape[-1]
    TM = tile or tiles(S, G, d, M, K.dtype)
    pos = jnp.clip(pos.astype(jnp.int32), 0, M - 1)
    qg = q.astype(jnp.float32).transpose(0, 1, 3, 2, 4).reshape(S, G, 2, d)
    # Plane c, row 2 r + p, as the kernel lays a slot's scores out, in
    # whole sublane tiles (rows past 2 R are nobody's).
    rows = _score_rows(G)
    own = jnp.pad(_own_scores(qg, k_own).reshape(S, R, 2, 2).transpose(
        0, 2, 1, 3).reshape(S, 2, 2 * R), ((0, 0), (0, 0), (0, rows - 2 * R))
    )[..., None]

    # Chosen by the platform the program is LOWERED for (kv_write.py).
    # The interpreted twin is traced only where something can run it:
    # a process whose backend is the TPU lowers for nothing else, and
    # tracing the twin as well was a third of the seconds this kernel
    # adds to a server's start on the chip's host (PERF.md section 6).
    shape = (S, G, d, M, TM, jnp.dtype(K.dtype))
    twin = {} if jax.default_backend() == "tpu" else {
        "default": _call(*shape, True)}
    o, a_own = lax.platform_dependent(
        pos, jnp.asarray(lam, jnp.float32).reshape(1),
        qg.reshape(S, 2 * G, d).transpose(0, 2, 1), own, K, V,
        tpu=_call(*shape, False), **twin,
    )
    o = o[:, :, :2 * G].transpose(0, 2, 1).reshape(S, G, 2, d) \
        + jnp.repeat(a_own[:, :2 * R].reshape(S, R, 2), 2, axis=1)[..., None] \
        * v_own[:, :, None, :].astype(jnp.float32)
    return o.reshape(S, R, 2, 2, d).transpose(0, 1, 3, 2, 4).reshape(
        S, R, 2, 2 * d)
