"""Flash attention as Pallas TPU kernels, with a custom VJP.

The reference has no attention at all (SURVEY.md §5 "long-context:
entirely absent"); the transformer family exists for BASELINE configs[4]
and this kernel is its throughput lever. Design:

* **Online-softmax forward** — the score matrix is never materialized
  in HBM. Each program owns one ``(batch*heads, q-block)`` tile, keeps
  the K/V rows for its head resident in VMEM, and streams k-blocks
  through the classic running ``(max, sum, acc)`` recurrence. Scores
  accumulate in f32 on the MXU regardless of input dtype.
* **Custom VJP** — two backward kernels recompute probabilities
  blockwise from the saved logsumexp (the flash-attention backward):
  one gridded over q-blocks producing ``dq``, one over k-blocks
  producing ``dk``/``dv``. No ``(T, T)`` tensor exists in any pass.
* **Causal masking + padding** are handled with in-kernel iota masks;
  ragged sequence lengths pad up to the block size and slice back.
* **Sequence ceiling** — the whole-sequence operands (K/V in the
  forward and ``dq`` kernels; Q, dO and the ``(T, 1)`` lse/delta
  columns in the ``dk``/``dv`` kernel) are VMEM-resident, lane-padded
  to 128 and double-buffered, so T is bounded by VMEM, not HBM. The
  kernels raise Mosaic's scoped-VMEM limit from its 16 MiB default to
  :data:`VMEM_LIMIT_BYTES` (a v5e core has 128 MiB), and a length
  that still would not fit raises a ``ValueError`` naming the ceiling
  (:func:`max_seq_len`) before anything is traced. At head dim 64 in
  bf16 that is 94 208 rows forward and 31 360 through the backward
  pass. Past it the K/V stream has to be blocked over the grid — not
  built.

Runs in interpreter mode off-TPU (the CPU test mesh), compiles to
Mosaic on TPU. Swaps into any ``attn_fn`` hook
(``models.transformer.block_apply``, the MoE block, the trainers):
signature matches :func:`~tpu_dist_nn.models.transformer.dot_product_attention`
— ``q, k, v: (..., T, H, Dh) -> (..., T, H, Dh)``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Scoped-VMEM limit handed to Mosaic for every kernel here, and the room
# kept free in it for the streamed blocks, outputs and temporaries.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
_VMEM_SLACK_BYTES = 4 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def max_seq_len(head_dim: int, dtype, *, backward: bool = False) -> int:
    """Longest (block-padded) sequence whose resident operands fit
    :data:`VMEM_LIMIT_BYTES`: forward only, or forward and backward.

    Per row a program holds two ``head_dim``-wide operands in ``dtype``
    (K and V; in the ``dk``/``dv`` kernel Q and dO) and, backward only,
    the two f32 lse/delta columns — each padded to 128 lanes, each
    double-buffered. The v5e compiler's refusals fall where this
    predicts (tests/test_tpu_compile.py)."""
    lanes = -(-head_dim // 128) * 128
    row = 2 * lanes * jnp.dtype(dtype).itemsize
    if backward:
        row += 2 * 128 * 4
    rows = (VMEM_LIMIT_BYTES - _VMEM_SLACK_BYTES) // (2 * row)
    return rows // 128 * 128


def _check_seq_len(T: int, Tp: int, head_dim: int, dtype, *, backward: bool):
    limit = max_seq_len(head_dim, dtype, backward=backward)
    if Tp > limit:
        raise ValueError(
            f"flash_attention keeps whole-sequence operands in VMEM: "
            f"T={T} (head_dim {head_dim}, {jnp.dtype(dtype).name}) is past "
            f"the {limit} rows that fit its "
            f"{VMEM_LIMIT_BYTES // 2**20} MiB limit "
            f"{'through the backward pass' if backward else 'forward'}"
        )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, seq_len):
    """One (bh, q-block) tile: online softmax over streamed k-blocks."""
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (bq, Dh)
    bq, d = q.shape
    n_kb = k_ref.shape[1] // block_k
    if causal:
        # Skip k-blocks entirely above the diagonal: only blocks with
        # jk*bk <= iq*bq + bq - 1 can contain unmasked entries.
        n_kb = jnp.minimum(n_kb, (iq * bq + bq + block_k - 1) // block_k)

    q_ids = iq * bq + _iota((bq, block_k), 0)
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    def body(jk, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        s = q @ kb.T  # (bq, bk)
        k_ids = jk * block_k + _iota((bq, block_k), 1)
        mask = k_ids < seq_len
        if causal:
            mask &= k_ids <= q_ids
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + p @ vb
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)  # (bq, 1)


def _flash_fwd(q, k, v, *, scale, causal, block_q, block_k, seq_len):
    """``q,k,v: (BH, Tp, Dh)`` padded -> ``(o (BH, Tp, Dh), lse (BH, Tp))``."""
    BH, Tp, d = q.shape
    grid = (BH, Tp // block_q)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_k=block_k,
        seq_len=seq_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tp, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tp, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            # lse rides as (BH, Tp, 1): Mosaic wants the last two block
            # dims (8, 128)-aligned or equal to the array dims.
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, d), q.dtype),
            jax.ShapeDtypeStruct((BH, Tp, 1), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, block_k, seq_len):
    """dq for one (bh, q-block): stream k-blocks, recompute p from lse."""
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (bq, Dh)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]  # (bq, 1)
    delta = delta_ref[0]
    bq, d = q.shape
    n_kb = k_ref.shape[1] // block_k
    if causal:
        n_kb = jnp.minimum(n_kb, (iq * bq + bq + block_k - 1) // block_k)
    q_ids = iq * bq + _iota((bq, block_k), 0)

    def body(jk, dq):
        kb = k_ref[0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        s = (q @ kb.T) * scale
        k_ids = jk * block_k + _iota((bq, block_k), 1)
        mask = k_ids < seq_len
        if causal:
            mask &= k_ids <= q_ids
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = do @ vb.T  # (bq, bk)
        ds = p * (dp - delta)
        return dq + (ds @ kb) * scale

    dq = lax.fori_loop(0, n_kb, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, seq_len):
    """dk/dv for one (bh, k-block): stream q-blocks."""
    jk = pl.program_id(1)
    kb = k_ref[0].astype(jnp.float32)  # (bk, Dh)
    vb = v_ref[0].astype(jnp.float32)
    bk, d = kb.shape
    n_qb = q_ref.shape[1] // block_q
    # Causal: q-blocks strictly above this k-block's diagonal see it
    # fully masked — start the stream at the first intersecting block.
    lo = (jk * bk) // block_q if causal else 0
    k_ids = jk * bk + _iota((block_q, bk), 1)

    def body(iq, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(iq * block_q, block_q), :]  # (bq, 1)
        delta = delta_ref[0, pl.ds(iq * block_q, block_q), :]
        s = (qb @ kb.T) * scale  # (bq, bk)
        q_ids = iq * block_q + _iota((block_q, bk), 0)
        mask = k_ids < seq_len
        if causal:
            mask &= k_ids <= q_ids
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_new = dv + p.T @ dob
        dp = dob @ vb.T
        ds = p * (dp - delta)
        dk_new = dk + (ds.T @ qb) * scale
        return dk_new, dv_new

    zero = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, n_qb, body, (zero, zero))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(res, g, *, scale, causal, block_q, block_k, seq_len):
    q, k, v, o, lse = res
    do = g.astype(jnp.float32)
    BH, Tp, d = q.shape
    _check_seq_len(seq_len, Tp, d, q.dtype, backward=True)
    # delta_i = Σ_d dO_id · O_id — the softmax-jacobian diagonal term.
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, block_k=block_k,
            seq_len=seq_len,
        ),
        grid=(BH, Tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tp, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tp, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tp, d), q.dtype),
        interpret=_interpret(),
        compiler_params=_PARAMS,
    )(q, k, v, g.astype(q.dtype), lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            seq_len=seq_len,
        ),
        grid=(BH, Tp // block_k),
        in_specs=[
            pl.BlockSpec((1, Tp, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Tp, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Tp, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Tp, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, d), q.dtype),
            jax.ShapeDtypeStruct((BH, Tp, d), q.dtype),
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
    )(q, k, v, g.astype(q.dtype), lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool, block_q: int = 128,
                    block_k: int = 128):
    """Drop-in for ``dot_product_attention``: ``(..., T, H, Dh)`` in/out.

    Pads T up to the block size (padded keys are masked via the in-kernel
    ``seq_len`` guard, padded queries sliced off), flattens ``(..., H)``
    into the grid's batch dim, and runs the online-softmax kernels.
    Differentiable via the custom flash VJP. Raises ``ValueError`` for
    a length past :func:`max_seq_len` (forward here, the lower backward
    ceiling when the VJP is traced).
    """
    *batch, T, H, Dh = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"q/k/v shapes must match: {q.shape} {k.shape} {v.shape}"
        )
    bq = min(block_q, max(T, 8))
    bk = min(block_k, max(T, 8))
    # Pad to a common multiple of both block sizes: the grid strides by
    # bq and the in-kernel k loop by bk, so each must divide Tp exactly.
    step = int(np.lcm(bq, bk))
    Tp = int(np.ceil(T / step) * step)
    _check_seq_len(T, Tp, Dh, q.dtype, backward=False)

    def to_bh(a):
        a = jnp.moveaxis(a, -2, -3)  # (..., H, T, Dh)
        a = a.reshape(-1, T, Dh)
        if Tp != T:
            a = jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0)))
        return a

    scale = 1.0 / float(np.sqrt(Dh))
    o = _flash_call(to_bh(q), to_bh(k), to_bh(v), scale, causal, bq, bk, T)
    o = o[:, :T]
    o = o.reshape(*batch, H, T, Dh)
    return jnp.moveaxis(o, -3, -2)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_call(q, k, v, scale, causal, block_q, block_k, seq_len):
    o, _ = _flash_fwd(
        q, k, v, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=seq_len,
    )
    return o


def _flash_call_fwd(q, k, v, scale, causal, block_q, block_k, seq_len):
    o, lse = _flash_fwd(
        q, k, v, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=seq_len,
    )
    return o, (q, k, v, o, lse)


def _flash_call_bwd(scale, causal, block_q, block_k, seq_len, res, g):
    return _flash_bwd(
        res, g, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=seq_len,
    )


_flash_call.defvjp(_flash_call_fwd, _flash_call_bwd)


# Crossover below which XLA's materialized attention is used instead.
# Not measured on today's code: the figure comes from one round-4 run
# (B=4 H=8 Dh=64 causal bf16: flash behind XLA at T=1024/2048, ahead at
# T=4096, where the T^2 f32 logits go HBM-bound). Shapes are static
# under jit, so the dispatch resolves at trace time.
# ``TDN_FLASH_MIN_SEQ`` overrides for on-chip re-verification.
try:
    FLASH_MIN_SEQ = int(os.environ.get("TDN_FLASH_MIN_SEQ", "") or 3072)
except ValueError:
    FLASH_MIN_SEQ = 3072  # malformed override must not break import


def select_attention(q, k, v, *, causal: bool):
    """Shape-aware attention dispatch, resolved at trace time: the
    flash kernel from ``FLASH_MIN_SEQ`` up to its VMEM ceiling
    (:func:`max_seq_len`; past it :func:`flash_attention` raises), the
    jnp reference below that."""
    from tpu_dist_nn.models.transformer import dot_product_attention

    if q.shape[-3] >= FLASH_MIN_SEQ:
        return flash_attention(q, k, v, causal=causal)
    return dot_product_attention(q, k, v, causal=causal)


def default_attn_fn():
    """The attention to use on this backend: measured shape-aware
    dispatch on TPU (:func:`select_attention` — XLA attention at short
    sequences, flash from ``FLASH_MIN_SEQ``), the jnp reference
    elsewhere (interpret-mode Pallas on CPU is correct but slow —
    tests opt in explicitly)."""
    from tpu_dist_nn.models.transformer import dot_product_attention

    return select_attention if jax.default_backend() == "tpu" else dot_product_attention
