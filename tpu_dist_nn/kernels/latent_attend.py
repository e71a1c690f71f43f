"""Absorbed latent attention of one decode query a slot: each live
latent row read once, the scores kept on the chip, both products on the
MXU.

The contract of :func:`tpu_dist_nn.models.mla_moe._attend_latent`, whose
XLA einsums stay as the path of shapes that do not tile and as this
kernel's oracle: the folded query ``q (S, H, r)`` (``[q_n wk ; q_r]``,
in the rows' type) over the first ``pos[s]`` positions of slot ``s`` of
layer ``layer`` of the whole cache ``lat (L, slots, 1, r, M)`` (positions
in the lanes, as the slot cache stores them) and over the position's
own row ``own (S, r)``, which no cache holds yet.  Returns ``(S, H,
r_kv)`` float32: the probabilities over the rows' first ``r_kv`` numbers
(the rotated key's ``r - r_kv`` are keys, not values).

XLA scores every slot's whole extent and masks: the float32 scores ``(S,
1, H, M + 1)`` go to HBM, come back for the softmax, go out as
probabilities and come back for the second product, which reads every
column of every slot again (PERF.md section 5).  Here the whole cache
goes to the call and stays in HBM; ``pos`` and the layer's index (traced:
the step scans over its layers, and a slice of a layer handed over as an
operand would be a copy of it) are scalar-prefetched and the kernel
copies by hand what is live: a slot's position tiles ``(r, TM)`` up to
the one that holds ``pos - 1``, a tile all of whose lanes are live as
one DMA, the frontier tile by the 128-lane pieces that are
(:func:`fetched_tiles`).  Slots behind the first ``S`` (a prefix pool's)
and other layers are never named.

One grid row a slot; the tiles of all rows are one sequence of jobs,
``_IN_FLIGHT`` of them copied ahead of the one computed, across rows
(kernels/decode_attend.py's plan).  A job is one pass: the scores ``(H,
r) x (r, TM)`` in float32, the mask ``position < pos``, the running
maximum, sum and accumulator in float32, the probabilities rounded to
the rows' type as the XLA path rounds them, and the second product ``(H,
TM) x (TM, r_kv)``, which contracts both operands' last axis over the
tile's first ``r_kv`` rows: no tile is transposed or sliced in HBM.  The
own row opens a slot's softmax (its score the first maximum, its values
the first accumulator), so no maximum is ever ``-inf``, also where a
slot has nothing cached or is not decoded (``pos`` 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dist_nn.kernels.decode_attend import _reach, fetched_tiles

__all__ = ["attend_rows", "fetched_tiles", "tiles"]

_LANES = 128
# Position tiles of a job, widest first, and the jobs whose copies are
# started ahead of the one computed, its own among them
# (tools/latent_attend_sweep.py; PERF.md section 6, PR 36).
_TILES = (1024, 512, 256, 128)
_IN_FLIGHT = 3
# What the kernel may hold in VMEM: the jobs in flight, a tile's scores
# and probabilities, the query and the output block twice.
_RESIDENT_BYTES = 48 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",),
    vmem_limit_bytes=64 * 1024 * 1024,
)


def _resident(H: int, r: int, rkv: int, tile: int, itemsize: int) -> int:
    """Bytes the kernel keeps in VMEM at a tile width."""
    return (_IN_FLIGHT * r * tile * itemsize + 3 * H * tile * 4
            + 2 * H * r * itemsize + 2 * H * rkv * 4 + 2 * H * _LANES * 4)


def tiles(S: int, H: int, r: int, rkv: int, M: int, dtype):
    """The position tile the kernel walks these shapes with, or ``None``
    where they do not tile: the caller keeps its XLA einsums.  ``S``
    decides nothing (a slot is a row of the grid)."""
    itemsize = jnp.dtype(dtype).itemsize
    pack = 32 // itemsize if itemsize in (2, 4) else 0
    if not pack or rkv % _LANES or not rkv < r or r % pack or H % pack:
        # The values in whole lane tiles of the output; the rows, which
        # the first product contracts, and the heads, its rows, in whole
        # sublane tiles.
        return None
    return next((t for t in _TILES if M % t == 0
                 and _resident(H, r, rkv, t, itemsize) <= _RESIDENT_BYTES),
                None)


def _attend_tile(q, rows_ref, seen, m_ref, l_ref, o_ref, scale, wide):
    """One tile of a slot's rows ``(r, TM)`` into the slot's running
    softmax, product, softmax, product laid out in line.  All of the
    kernel that is not a copy: tools/latent_attend_sweep.py puts nothing
    in its place to time the copies alone."""
    rkv = o_ref.shape[-1]
    s = jnp.dot(q, wide(rows_ref[...]),
                preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen, s, -jnp.inf)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    fade = jnp.exp(m - m_new)
    l_ref[...] = fade * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    o_ref[...] = fade * o_ref[...] + lax.dot_general(
        wide(p.astype(rows_ref.dtype)), wide(rows_ref[:rkv, :]),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(pos_ref, layer_ref, q_ref, own_ref, lat_hbm, o_ref, buf, sem,
            cur, m_ref, l_ref, *, S, scale, widen):
    u = pl.program_id(0)
    NB, _, TM = buf.shape
    H, rkv = o_ref.shape[1:]
    pieces = TM // _LANES
    layer = layer_ref[0]
    wide = (lambda a: a.astype(jnp.float32)) if widen else (lambda a: a)

    def reach(slot):
        return _reach(pos_ref[slot])

    def visits(slot):
        return (reach(slot) - 1) // TM + 1

    def copies(slot, t, b, act):
        """Start, or wait for, the copies of tile ``t`` of ``slot`` into
        buffer ``b``: the same conditions name the same copies both
        times."""
        tile0 = t * TM
        # 128-lane pieces of the tile that hold a position read.
        live = jnp.clip((reach(slot) - tile0 + _LANES - 1) // _LANES,
                        0, pieces)

        def go(lane0, width, c):
            cp = pltpu.make_async_copy(
                lat_hbm.at[layer, slot, 0, :, pl.ds(
                    pl.multiple_of(tile0 + lane0, _LANES), width)],
                buf.at[b, :, pl.ds(lane0, width)],
                sem.at[b, c])
            cp.start() if act == "start" else cp.wait()

        @pl.when(live == pieces)
        def _():
            go(0, TM, 0)

        def piece(c, carry):
            go(pl.multiple_of(c * _LANES, _LANES), _LANES, c)
            return carry

        lax.fori_loop(0, jnp.where(live == pieces, 0, live), piece, 0)

    def start_next():
        # cur: the next job to start (slot, t), how many were started,
        # how many were waited for.
        slot, t = cur[0], cur[1]

        @pl.when(slot < S)
        def _():
            copies(slot, t, cur[2] % NB, "start")
            cur[2] = cur[2] + 1
            last = t + 1 == visits(slot)
            cur[0] = jnp.where(last, slot + 1, slot)
            cur[1] = jnp.where(last, 0, t + 1)

    @pl.when(u == 0)
    def _():
        # A frontier tile's dead lanes keep what the buffer held, and are
        # multiplied by zeros: what it held must be finite.
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        for i in range(4):
            cur[i] = 0
        lax.fori_loop(0, NB - 1, lambda i, carry: start_next(), None)

    # The own row opens the softmax: its score, a sum of one, its values.
    q = q_ref[0]
    own = own_ref[0].astype(jnp.float32)  # (1, r)
    m_ref[...] = jnp.sum(q.astype(jnp.float32) * own, axis=1,
                         keepdims=True) * scale
    l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
    o_ref[0] = jnp.broadcast_to(own[:, :rkv], (H, rkv))
    lane = lax.broadcasted_iota(jnp.int32, (H, TM), 1)

    def job(t, carry):
        start_next()
        b = cur[3] % NB
        copies(u, t, b, "wait")
        cur[3] = cur[3] + 1
        _attend_tile(wide(q), buf.at[b], lane < pos_ref[u] - t * TM,
                     m_ref, l_ref, o_ref.at[0], scale, wide)
        return carry

    lax.fori_loop(0, visits(u), job, 0)
    o_ref[0] = o_ref[0] / l_ref[...]


@functools.lru_cache(maxsize=None)
def _call(S, H, r, rkv, TM, NB, dtype, scale, interpret: bool):
    """The ``pallas_call`` of these shapes.  One object a shape: the
    dense layers' scan and the expert layers' scan trace the kernel
    once, which is seconds of a server's start on the chip's host
    (decode_attend.py)."""

    def row(*dims):
        return pl.BlockSpec((1,) + dims, lambda u, pos_ref, layer_ref: (
            u,) + (0,) * len(dims))

    return pl.pallas_call(
        functools.partial(_kernel, S=S, scale=scale, widen=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[row(H, r), row(1, r),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(H, rkv),
            scratch_shapes=[
                pltpu.VMEM((NB, r, TM), dtype),
                pltpu.SemaphoreType.DMA((NB, TM // _LANES)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, rkv), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="latent_attend",
    )


def attend_rows(q, lat, layer, own, pos, rkv: int, scale: float, *,
                tile=None, in_flight=_IN_FLIGHT):
    """``q (S, H, r)`` at ``pos (S,)`` over slots ``[0, S)`` of layer
    ``layer`` (traced) of the whole cache ``lat (L, slots, 1, r, M)`` and
    the own rows ``own (S, r)``; returns ``(S, H, rkv)`` float32.  The
    shapes must tile (:func:`tiles`).  ``tile`` and ``in_flight`` are for
    timing other plans (tools/latent_attend_sweep.py): the program passes
    neither."""
    S, H, r = q.shape
    M = lat.shape[-1]
    dtype = jnp.dtype(lat.dtype)
    TM = tile or tiles(S, H, r, rkv, M, dtype)
    shape = (S, H, r, rkv, TM, in_flight, dtype, float(scale))
    # Chosen by the platform the program is LOWERED for (kv_write.py);
    # the interpreted twin is traced only where something can run it
    # (decode_attend.py).
    twin = {} if jax.default_backend() == "tpu" else {
        "default": _call(*shape, True)}
    return lax.platform_dependent(
        jnp.clip(pos.astype(jnp.int32), 0, M - 1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.astype(dtype), own[:, None].astype(dtype), lat,
        tpu=_call(*shape, False), **twin,
    )
