"""Latent attention of one prefill chunk in its expanded form, the
expanded keys, values and scores kept on the chip.

The contract of :func:`tpu_dist_nn.models.mla_moe._attend_expanded`,
whose ``fori_loop`` stays as the path of shapes no TPU tile fits and as
this kernel's oracle: the chunk's queries ``q_n (C, H, d_n)`` and ``q_r
(C, H, d_r)`` (rotated) at positions ``start .. start + C - 1`` attend
the slot's latent rows ``(1, r_kv + d_r, M)`` (positions in the lanes,
as the slot cache stores them; the chunk's own rows already written),
each row expanded to a head's key and value by ``w_kvb``.  Returns
``(C, H, d_v)`` float32.

The loop forms each key tile's float32 scores ``(H, C, KT)`` in HBM,
134 MB a tile at the published widths, and crosses them three to four
times (PERF.md section 5).  Here the grid is ``(head groups, key
tiles)`` with the key tiles innermost and a group's WHOLE chunk of
queries resident: one step takes a ``(r_kv + d_r, KT)`` tile of latent
rows and, a head at a time, expands it once (``[wk_h ; wv_h]^T c``:
keys ``(d_n, KT)`` over the shared rotated key ``(d_r, KT)``, values
``(d_v, KT)``, rounded to the rows' type as the loop rounds them), then
walks the query tiles that see any of it: one product of depth ``d_n +
d_r``, the mask ``key position <= query position``, the running
maximum, sum and accumulator in float32 (the accumulator is the output
block, which stays in VMEM over the key tiles), the probabilities
rounded to the values' type for the second product, which contracts
both operands' last axis: no tile is transposed.  HBM sees q once, the
latent tile once a head group, ``w_kvb`` once, and the output.  Where
every query sees the whole key tile (all but one or two tiles of a late
chunk) the head's query tiles are laid out in line without a mask, so
that one tile's softmax overlaps the next one's products; elsewhere a
loop walks them under the mask.

``start`` is scalar-prefetched: the key tiles visited are ``(start + C
- 1) // KT + 1``, the loop's trip count; later grid steps clamp their
block index to the last one visited (no new DMA) and skip the body.  A
``(query tile, key tile)`` pair is skipped only where the mask hides
all of it.  The first key tile holds position 0, which every query
sees, so no running maximum stays ``-inf``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Key tiles, query tiles and heads of a group, first fit first: the ones
# timed on a v5e (tools/expand_attend_sweep.py; PERF.md section 6, PR
# 34).  A key tile divides the extent (a ragged last tile would hand the
# products whatever lies past the edge).
_KEY_TILES = (1024, 512)
_QUERY_TILES = (512, 256, 128)
_HEAD_GROUPS = (8, 4, 2, 1)
# What a head group may hold in VMEM (:func:`_resident`); the scores of
# the query tile in flight come on top, so the compiler's 16 MiB
# default is raised.
_RESIDENT_BYTES = 48 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=96 * 1024 * 1024,
)


def _resident(hg, C, rkv, dn, dr, dv, kt, itemsize) -> int:
    """Bytes a head group keeps in VMEM: q, ``w_kvb``'s share and the
    latent tile double-buffered, the float32 output block twice, the two
    statistics (a lane-padded column each) and a head's expanded tile."""
    lanes = -(-(dn + dr) // _LANES) * _LANES
    return (2 * hg * C * lanes * itemsize
            + 2 * hg * (dn + dv) * rkv * itemsize
            + 2 * (rkv + dr) * kt * itemsize
            + 2 * hg * C * dv * 4 + 2 * hg * C * _LANES * 4
            + (dn + dr + dv) * kt * itemsize)


def tiles(C, H, rkv, dn, dr, dv, M, dtype):
    """``(heads a group, query tile, key tile)`` the kernel runs these
    shapes with, or ``None`` where they do not tile: the caller keeps
    its XLA loop."""
    itemsize = jnp.dtype(dtype).itemsize
    pack = 32 // itemsize if itemsize in (2, 4) else 0
    if not pack or rkv % _LANES or dv % _LANES or dn % pack or dr % pack:
        # Whole lane tiles of the contraction over the latent row and of
        # the output; whole sublane tiles where the expanded keys meet
        # the rotated one.
        return None
    kt = next((k for k in _KEY_TILES if M % k == 0), None)
    tq = next((t for t in _QUERY_TILES if C % t == 0), None)
    if kt is None or tq is None:
        return None
    hg = next((g for g in _HEAD_GROUPS if H % g == 0 and _resident(
        g, C, rkv, dn, dr, dv, kt, itemsize) <= _RESIDENT_BYTES), None)
    return None if hg is None else (hg, tq, kt)


def _kernel(start_ref, q_ref, w_ref, rows_ref, o_ref, m_ref, l_ref, k_ref,
            v_ref, *, tq, dn, scale, widen):
    j = pl.program_id(1)
    hg, C, _ = q_ref.shape
    rkv = w_ref.shape[-1]
    KT = rows_ref.shape[-1]
    start = start_ref[0]
    visits = jnp.minimum((start + C - 1) // KT + 1, pl.num_programs(1))
    wide = (lambda a: a.astype(jnp.float32)) if widen else (lambda a: a)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    def expand(h):
        """Head ``h``'s keys over the shared rotated one, and values."""
        kv = jnp.dot(wide(w_ref[h]), wide(rows_ref[0, :rkv, :]),
                     preferred_element_type=jnp.float32)
        k_ref[:dn, :] = kv[:dn].astype(k_ref.dtype)
        k_ref[dn:, :] = rows_ref[0, rkv:, :]
        v_ref[...] = kv[dn:].astype(v_ref.dtype)

    def attend(h, at, seen):
        """Query tile ``at`` of head ``h`` on the expanded key tile,
        under the mask ``seen``, or under none where all of it is."""
        s = jnp.dot(wide(q_ref[h, at, :]), wide(k_ref[...]),
                    preferred_element_type=jnp.float32) * scale
        if seen is not None:
            s = jnp.where(seen, s, -jnp.inf)
        m = m_ref[h, at, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m - m_new)
        l_ref[h, at, :] = fade * l_ref[h, at, :] \
            + jnp.sum(p, axis=1, keepdims=True)
        o_ref[h, at, :] = fade * o_ref[h, at, :] + lax.dot_general(
            wide(p.astype(v_ref.dtype)), wide(v_ref[...]),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h, at, :] = m_new

    # Every query sees the whole key tile: most tiles of a late chunk.
    clear = j * KT + KT - 1 <= start

    @pl.when((j < visits) & clear)
    def _():
        def head(h, carry):
            expand(h)
            # The head's query tiles laid out in line, no mask: one
            # tile's softmax can overlap the next one's products.
            for i in range(C // tq):
                attend(h, pl.ds(i * tq, tq), None)
            return carry

        lax.fori_loop(0, hg, head, None)

    @pl.when((j < visits) & jnp.logical_not(clear))
    def _():
        # Key position minus query position of a (query tile, key tile)
        # pair, less what the pair's own offset adds: seen where <= it.
        ahead = lax.broadcasted_iota(jnp.int32, (tq, KT), 1) \
            - lax.broadcasted_iota(jnp.int32, (tq, KT), 0)
        # Query tiles before this one see none of the key tile.
        first = jnp.maximum(j * KT - start, 0) // tq

        def head(h, carry):
            expand(h)

            def queries(i, carry):
                attend(h, pl.ds(pl.multiple_of(i * tq, tq), tq),
                       ahead <= start + i * tq - j * KT)
                return carry

            return lax.fori_loop(first, C // tq, queries, carry)

        lax.fori_loop(0, hg, head, None)

    @pl.when(j == visits - 1)
    def _():
        def head(h, carry):
            o_ref[h] = o_ref[h] / l_ref[h]
            return carry

        lax.fori_loop(0, hg, head, None)


@functools.lru_cache(maxsize=None)
def _call(C, H, rkv, dn, dr, dv, M, dtype, tiling, scale, interpret: bool):
    """The ``pallas_call`` of these shapes.  One object a shape: the
    layers of a program that share it trace the kernel once, which is
    seconds of a server's start on the chip's host (decode_attend.py)."""
    hg, tq, KT = tiling

    def rows_map(g, j, start_ref):
        visits = jnp.minimum((start_ref[0] + C - 1) // KT + 1, M // KT)
        return 0, 0, jnp.minimum(j, visits - 1)

    def group(*dims):
        return pl.BlockSpec((hg,) + dims, lambda g, j, start_ref: (
            g,) + (0,) * len(dims))

    return pl.pallas_call(
        functools.partial(_kernel, tq=tq, dn=dn, scale=scale,
                          widen=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hg, M // KT),
            in_specs=[group(C, dn + dr), group(dn + dv, rkv),
                      pl.BlockSpec((1, rkv + dr, KT), rows_map)],
            out_specs=group(C, dv),
            scratch_shapes=[
                pltpu.VMEM((hg, C, 1), jnp.float32),
                pltpu.VMEM((hg, C, 1), jnp.float32),
                pltpu.VMEM((dn + dr, KT), dtype),
                pltpu.VMEM((dv, KT), dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, C, dv), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="expand_attend",
    )


def attend_chunk(q_n, q_r, rows, wk, wv, start, scale: float, *, tile=None):
    """``q_n (C, H, d_n)``, ``q_r (C, H, d_r)`` at positions ``start ..
    start + C - 1`` (``start`` traced) over the latent rows ``(1, r_kv +
    d_r, M)`` expanded by ``wk (r_kv, H, d_n)`` and ``wv (r_kv, H,
    d_v)``; returns ``(C, H, d_v)`` float32.  The shapes must tile
    (:func:`tiles`).  ``tile`` ``(heads a group, query tile, key tile)``
    is for timing other tilings (tools/expand_attend_sweep.py): the
    program passes none."""
    C, H, dn = q_n.shape
    dr, dv = q_r.shape[-1], wv.shape[-1]
    rkv, M = wk.shape[0], rows.shape[-1]
    dtype = jnp.dtype(rows.dtype)
    tiling = tuple(tile or tiles(C, H, rkv, dn, dr, dv, M, dtype))
    shape = (C, H, rkv, dn, dr, dv, M, dtype, tiling, float(scale))
    # Chosen by the platform the program is LOWERED for (kv_write.py);
    # the interpreted twin is traced only where something can run it
    # (decode_attend.py).
    twin = {} if jax.default_backend() == "tpu" else {
        "default": _call(*shape, True)}
    o = lax.platform_dependent(
        jnp.asarray(start, jnp.int32).reshape(1),
        jnp.concatenate([q_n, q_r], -1).transpose(1, 0, 2).astype(dtype),
        jnp.concatenate([wk, wv], -1).transpose(1, 2, 0).astype(dtype),
        rows,
        tpu=_call(*shape, False), **twin,
    )
    return o.transpose(1, 0, 2)
