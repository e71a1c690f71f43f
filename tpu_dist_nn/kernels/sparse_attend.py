"""Block-masked causal attention of one prefill chunk, scores kept on the chip.

The chunk's queries ``q (C, G, group, Dh)`` at positions ``start ..
start + C - 1`` attend the slot's keys and values ``(G, Dh, M)``
(positions in the lanes, as the slot cache stores them) where their
K/V group's block selection ``sel (C, G, NB)`` and causality allow:
the contract of :func:`tpu_dist_nn.models.sala._attend_chunk`, whose
loop stays for shapes no TPU tile fits and as the oracle.  Without a
selection causality alone decides (the dense kernel, end of the file).

That loop forms each key tile's float32 scores ``(G, group, C, KT)``
in HBM, 268 MB a tile at the published widths, and crosses them three
times (PERF.md section 5).  Here the grid is ``(G, query tiles, key
tiles)`` with the key tiles innermost: one step takes a ``(Dh, KT)``
tile of K and of V, which the group's heads share, and runs every head's
``(tq, Dh) x (Dh, KT)`` product, masked online softmax and ``(tq, KT) x
(KT, Dh)`` product against it with the statistics and the accumulator
in VMEM scratch.  HBM sees q once, the visible K/V once a query tile,
the selection as one bit a block, and the output.

``start`` is scalar-prefetched: a query tile visits the key tiles up to
its own last position, later grid steps clamp their block index to the
last one visited (no new DMA) and skip the body.  The mask is the
group's, ``selected block & key_pos <= t``: the selection arrives as
one int32 word a (query, key tile) with bit ``b`` the tile's block
``b``, and is spread over the lanes by a shift, never laid out at the
scores' size outside VMEM.

Same arithmetic as the loop: bf16 operands, float32 scores, maximum,
sum and accumulator, probabilities cast to the values' type for the
second product.  A row with nothing to attend comes back zero.
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
# Key and query tiles, largest first: the ones timed on a v5e and found
# faster than the XLA loop (PERF.md section 6, PR 28; the loop 36.8 ms a
# layer at the benchmark cell's last chunk, 512 x 768 8.73, 256 x 768
# 10.0, 128 x 768 13.6, 256 x 384 18.3).  An extent neither divides
# keeps the loop: a smaller key tile rewrites the statistics and the
# accumulator more often and was never shown to win.  A key tile divides
# the extent (a ragged last tile would hand the second product whatever
# lies past the edge) and holds at most 32 blocks, one bit each.
_KEY_TILES = (768, 384)
_QUERY_TILES = (512, 256, 128)
_WORD_BITS = 32
# Heads of the group's loop laid out in line, so that one head's
# softmax can overlap the next one's products.
_HEAD_UNROLL = 4
# What a query tile holds in VMEM for the whole group: the float32
# accumulator and the two statistics (a lane-padded column each), q and
# the output double-buffered.  20 MiB at (group 16, tq 512, Dh 128); the
# scores of the heads in flight, the K/V tiles and the mask come on top,
# so the compiler's 16 MiB default is raised.
_RESIDENT_BYTES = 24 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024,
)


def _resident(group: int, tq: int, Dh: int) -> int:
    return group * tq * ((Dh + 2 * _LANES) * 4 + 2 * 2 * Dh * 2)


def tiles(C: int, group: int, Dh: int, M: int, block_size=None):
    """``(query tile, key tile)`` the kernel runs these shapes with (no
    selection where ``block_size`` is None), or None: the XLA loop."""
    if Dh % _LANES:
        return None
    kt = _dense_key_tile(M) if block_size is None else next(
        (k for k in _KEY_TILES if M % k == 0 and k % block_size == 0
         and k // block_size <= _WORD_BITS), None)
    tq = next((t for t in _QUERY_TILES
               if C % t == 0 and _resident(group, t, Dh) <= _RESIDENT_BYTES),
              None)
    return None if kt is None or tq is None else (tq, kt)


def _kernel(start_ref, bits_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, bias_ref, *, block_size, unroll, widen):
    qi, j = pl.program_id(1), pl.program_id(2)
    _, group, tq, Dh = q_ref.shape
    KT = k_ref.shape[-1]
    first = start_ref[0] + qi * tq  # the tile's first query position
    visits = (first + tq - 1) // KT + 1
    scale = 1.0 / np.sqrt(Dh)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < visits)
    def _():
        # The group's mask for this (query tile, key tile), as what it
        # adds to a score: 0 where the key's block is selected and the
        # key is not in the query's future, -inf elsewhere.
        words = bits_ref[0]  # (tq, key tiles)
        mine = lax.broadcasted_iota(jnp.int32, words.shape, 1) == j
        word = jnp.sum(jnp.where(mine, words, 0), axis=1, keepdims=True)
        lane = lax.broadcasted_iota(jnp.int32, (tq, KT), 1)
        picked = lax.shift_right_logical(
            jnp.broadcast_to(word, (tq, KT)), lane // block_size) & 1
        row = lax.broadcasted_iota(jnp.int32, (tq, KT), 0)
        seen = j * KT + lane <= first + row
        bias_ref[...] = jnp.where((picked != 0) & seen, 0.0, -jnp.inf)
        k, v = k_ref[0], v_ref[0]  # (Dh, KT)
        if widen:  # the CPU has no bf16 x bf16 = f32 product
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)

        def head(h):
            q = q_ref[0, h]  # (tq, Dh)
            s = jnp.dot(q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) * scale
            s = s + bias_ref[...]
            m = m_ref[h]  # (tq, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(s - m_safe)
            alpha = jnp.exp(m - m_safe)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p.astype(v_ref.dtype).astype(v.dtype), v,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[h] = alpha * acc_ref[h] + pv
            m_ref[h] = m_new

        def heads(i, carry):
            for u in range(unroll):
                head(i * unroll + u)
            return carry

        lax.fori_loop(0, group // unroll, heads, None)

    @pl.when(j == visits - 1)
    def _():
        def head(h, carry):
            l = l_ref[h]
            o_ref[0, h] = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)
                           ).astype(o_ref.dtype)
            return carry

        lax.fori_loop(0, group, head, None)


def _selection_words(sel, KT, block_size):
    """``sel (C, G, NB)`` bool -> ``(G, C, NB * block_size / KT)`` int32,
    bit ``b`` of a word the selection of its key tile's block ``b``."""
    C, G, NB = sel.shape
    per = KT // block_size
    bits = sel.reshape(C, G, NB // per, per).astype(jnp.int32)
    words = jnp.sum(bits << jnp.arange(per, dtype=jnp.int32), axis=-1)
    return words.transpose(1, 0, 2)


def attend_chunk(q, k_rows, v_rows, sel, start, block_size=None, *,
                 tile=None, unroll: int = _HEAD_UNROLL):
    """``q (C, G, group, Dh)`` at ``start ..`` (traced) over ``k_rows``,
    ``v_rows (G, Dh, M)`` where ``sel (C, G, M / block_size)`` allows:
    ``(C, G * group * Dh)`` in q's type.  ``sel`` None: :func:`_attend_dense`.
    Shapes that tile (:func:`tiles`); ``tile``, ``unroll``: the sweep's."""
    if sel is None:
        return _attend_dense(q, k_rows, v_rows, start, tile)
    C, G, group, Dh = q.shape
    M = k_rows.shape[-1]
    tq, KT = tile or tiles(C, group, Dh, M, block_size)
    n_key = M // KT
    if group % unroll:
        unroll = 1

    def kv_map(g, qi, j, start_ref):
        visits = (start_ref[0] + (qi + 1) * tq - 1) // KT + 1
        return g, 0, jnp.minimum(j, visits - 1)

    q_spec = pl.BlockSpec((1, group, tq, Dh),
                          lambda g, qi, j, start_ref: (g, 0, qi, 0))
    kv_spec = pl.BlockSpec((1, Dh, KT), kv_map)
    bits_spec = pl.BlockSpec((1, tq, n_key),
                             lambda g, qi, j, start_ref: (g, qi, 0))

    def call(interpret: bool):
        return pl.pallas_call(
            functools.partial(_kernel, block_size=block_size,
                              unroll=unroll, widen=interpret),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(G, C // tq, n_key),
                in_specs=[bits_spec, q_spec, kv_spec, kv_spec],
                out_specs=q_spec,
                scratch_shapes=[
                    pltpu.VMEM((group, tq, 1), jnp.float32),
                    pltpu.VMEM((group, tq, 1), jnp.float32),
                    pltpu.VMEM((group, tq, Dh), jnp.float32),
                    pltpu.VMEM((tq, KT), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((G, group, C, Dh), q.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
            name="sparse_attend",
        )

    # Chosen by the platform the program is LOWERED for (kv_write.py).
    o = lax.platform_dependent(
        jnp.asarray(start, jnp.int32).reshape(1),
        _selection_words(sel, KT, block_size),
        q.transpose(1, 2, 0, 3), k_rows, v_rows,
        tpu=call(False), default=call(True),
    )
    return o.transpose(2, 0, 1, 3).reshape(C, G * group * Dh)


# ------------------------------------------------------------------ dense
# Without a selection (a full layer of grouped-query attention,
# models/laguna.py) the mask is ``key_pos <= t`` alone.  A key tile that
# a query tile sees whole (all but the one or two on a late chunk's
# diagonal) runs the group's heads in line and unmasked, so that one
# head's softmax can overlap the next one's products.  q and the output
# keep the caller's ``(C, G * group * Dh)`` layout, a head a 128-lane
# slice, so neither is transposed in HBM; the float32 output block is
# the accumulator, resident over the key tiles.  The first key tile
# holds position 0, which every query sees: no maximum stays -inf.
#
# Key tiles, largest first, and the query tile `tiles` picks, as timed
# on a v5e at the 16k repository cell's full layer (PERF.md section 6;
# ms a layer at `start` 14 336): 512 x 1024 6.09, 256 x 1024 6.61,
# 512 x 512 10.4, 1024 x 1024 10.7, the XLA loop 27.4.
_DENSE_KEY_TILES = (1024, 512)


def _dense_key_tile(M: int):
    return next((k for k in _DENSE_KEY_TILES if M % k == 0), None)


def _dense_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  Dh, widen):
    qi, j = pl.program_id(1), pl.program_id(2)
    tq, group = q_ref.shape[0], m_ref.shape[0]
    KT = k_ref.shape[-1]
    first = start_ref[0] + qi * tq  # the tile's first query position
    visits = (first + tq - 1) // KT + 1
    scale = 1.0 / np.sqrt(Dh)
    wide = (lambda a: a.astype(jnp.float32)) if widen else (lambda a: a)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    def head(h, seen):
        """Head ``h`` on the key tile, under the mask ``seen`` or, where
        the tile is seen whole, under none."""
        lanes = slice(h * Dh, (h + 1) * Dh)
        s = jnp.dot(wide(q_ref[:, lanes]), wide(k_ref[0]),
                    preferred_element_type=jnp.float32) * scale
        if seen is not None:
            s = jnp.where(seen, s, -jnp.inf)
        m = m_ref[h]  # (tq, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m - m_new)
        l_ref[h] = fade * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        o_ref[:, lanes] = fade * o_ref[:, lanes] + lax.dot_general(
            wide(p.astype(v_ref.dtype)), wide(v_ref[0]),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    clear = j * KT + KT - 1 <= first

    @pl.when((j < visits) & clear)
    def _():
        for h in range(group):
            head(h, None)

    @pl.when((j < visits) & jnp.logical_not(clear))
    def _():
        seen = j * KT + lax.broadcasted_iota(jnp.int32, (tq, KT), 1) \
            <= first + lax.broadcasted_iota(jnp.int32, (tq, KT), 0)
        for h in range(group):
            head(h, seen)

    @pl.when(j == visits - 1)
    def _():
        for h in range(group):
            lanes = slice(h * Dh, (h + 1) * Dh)
            o_ref[:, lanes] = o_ref[:, lanes] / l_ref[h]


@functools.lru_cache(maxsize=None)
def _dense_call(C, G, group, Dh, M, tiling, interpret: bool):
    """The dense ``pallas_call`` of these shapes.  One object a shape:
    the layers of a program that share it trace the kernel once
    (expand_attend.py)."""
    tq, KT = tiling

    def kv_map(g, qi, j, start_ref):
        visits = (start_ref[0] + (qi + 1) * tq - 1) // KT + 1
        return g, 0, jnp.minimum(j, visits - 1)

    heads = pl.BlockSpec((tq, group * Dh),
                         lambda g, qi, j, start_ref: (qi, g))
    kv_spec = pl.BlockSpec((1, Dh, KT), kv_map)
    return pl.pallas_call(
        functools.partial(_dense_kernel, Dh=Dh, widen=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, C // tq, M // KT),
            in_specs=[heads, kv_spec, kv_spec],
            out_specs=heads,
            scratch_shapes=[
                pltpu.VMEM((group, tq, 1), jnp.float32),
                pltpu.VMEM((group, tq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C, G * group * Dh), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="sparse_attend",
    )


def _attend_dense(q, k_rows, v_rows, start, tile=None):
    """``q (C, G, group, Dh)`` at positions ``start ..`` (traced) over
    every key of ``k_rows``, ``v_rows (G, Dh, M)`` up to its own;
    ``(C, G, group, Dh)`` float32.  The shapes must tile (:func:`tiles`
    with no ``block_size``).  ``tile`` ``(query tile, key tile)``, as
    :func:`attend_chunk`'s ``tile`` and ``unroll``, is for timing other
    tilings (tools/sparse_attend_sweep.py): the program passes none."""
    C, G, group, Dh = q.shape
    M = k_rows.shape[-1]
    shape = (C, G, group, Dh, M, tuple(tile or tiles(C, group, Dh, M)))
    # Chosen by the platform the program is LOWERED for (kv_write.py);
    # the interpreted twin is traced only where something can run it
    # (decode_attend.py).
    twin = {} if jax.default_backend() == "tpu" else {
        "default": _dense_call(*shape, True)}
    o = lax.platform_dependent(
        jnp.asarray(start, jnp.int32).reshape(1),
        q.reshape(C, G * group * Dh), k_rows, v_rows,
        tpu=_dense_call(*shape, False), **twin)
    return o.reshape(C, G, group, Dh)
