"""Tiny-Transformer LM: the BASELINE configs[4] model family.

The reference has no attention anywhere (SURVEY.md §5 "long-context:
entirely absent"); this family exists because BASELINE.json configs[4]
names "Tiny-Transformer encoder on WikiText-2 (per-block pipeline stage
over ICI)" as a target workload. Design is TPU-first:

* Blocks are **stacked**: every parameter leaf carries a leading
  ``(n_layers, ...)`` axis, so the single-chip forward is a
  ``lax.scan`` over one traced block (one compile, MXU-shaped matmuls)
  and the pipelined forward shards the same axis over the ``stage``
  mesh axis and rides the generic GPipe schedule
  (:mod:`tpu_dist_nn.parallel.gpipe`) unchanged — one block group per
  stage, hand-off = ``ppermute`` of the ``(batch, seq, d_model)``
  activation over ICI.
* Pre-LayerNorm residual blocks (attn then MLP), GELU MLP, learned
  positional embeddings, tied LM head — the standard small-LM recipe.
* Causality is a static flag: the mask is built at trace time, no
  dynamic shapes.

Attention is factored out (:func:`dot_product_attention`) so the
sequence-parallel ring executor (:mod:`tpu_dist_nn.parallel.ring_attention`)
can swap in blockwise attention while reusing everything else.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Static architecture description (hashable; closed over by jit).

    ``compute_dtype`` selects the forward-pass precision as a string
    (hashable): params stay float32 master copies; under ``"bfloat16"``
    the loss path casts them (and activations) to bf16 for the MXU and
    keeps softmax/CE accumulation in f32 — the standard TPU mixed-
    precision recipe. Gradients flow back to the f32 masters through
    the cast.
    """

    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 512
    max_seq_len: int = 256
    causal: bool = True
    compute_dtype: str = "float32"
    remat: bool = False

    def __post_init__(self):
        if self.d_model % self.n_heads:
            raise ValueError(
                f"d_model={self.d_model} not divisible by n_heads={self.n_heads}"
            )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def cast_params(self, params):
        """Params in the compute dtype (identity for float32)."""
        if self.compute_dtype == "float32":
            return params
        dtype = jnp.dtype(self.compute_dtype)
        return jax.tree.map(lambda a: a.astype(dtype), params)

    def slot_model(self):
        """This block's functions for the continuous scheduler (the
        protocol of :mod:`tpu_dist_nn.models.slot_model`)."""
        from tpu_dist_nn.models import generate
        from tpu_dist_nn.models.slot_model import SlotModel
        from tpu_dist_nn.obs.goodput import LMFlopModel

        return SlotModel(
            init_slot_cache=generate.init_slot_cache,
            prefill_chunk_into_cache=generate.prefill_chunk_into_cache,
            decode_step_slots=generate.decode_step_slots,
            copy_cache_slot=generate.copy_cache_slot,
            flop_model=LMFlopModel.from_config,
            cache_bytes=generate.cache_bytes,
        )


def init_transformer(key: jax.Array, cfg: TransformerConfig, dtype=jnp.float32):
    """Params pytree; block leaves are stacked on a leading n_layers axis."""
    k_tok, k_pos, k_blocks = jax.random.split(key, 3)
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    s_embed = 1.0 / np.sqrt(D)

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dtype)

    bk = jax.random.split(k_blocks, 6 * L).reshape(L, 6)
    blocks = {
        "ln1_g": jnp.ones((L, D), dtype),
        "ln1_b": jnp.zeros((L, D), dtype),
        # qkv fused: one (D, 3D) matmul feeds the MXU better than three
        # (D, D) ones.
        "w_qkv": jnp.stack([dense(bk[i, 0], (D, 3 * D), s_embed) for i in range(L)]),
        "b_qkv": jnp.zeros((L, 3 * D), dtype),
        "w_o": jnp.stack([dense(bk[i, 1], (D, D), s_embed / np.sqrt(2 * L)) for i in range(L)]),
        "b_o": jnp.zeros((L, D), dtype),
        "ln2_g": jnp.ones((L, D), dtype),
        "ln2_b": jnp.zeros((L, D), dtype),
        "w_up": jnp.stack([dense(bk[i, 2], (D, F), s_embed) for i in range(L)]),
        "b_up": jnp.zeros((L, F), dtype),
        "w_down": jnp.stack(
            [dense(bk[i, 3], (F, D), (1.0 / np.sqrt(F)) / np.sqrt(2 * L)) for i in range(L)]
        ),
        "b_down": jnp.zeros((L, D), dtype),
    }
    return {
        "tok_embed": dense(k_tok, (cfg.vocab_size, D), s_embed),
        "pos_embed": dense(k_pos, (cfg.max_seq_len, D), 0.01),
        "blocks": blocks,
        "lnf_g": jnp.ones((D,), dtype),
        "lnf_b": jnp.zeros((D,), dtype),
        # LM head tied to tok_embed (logits = x @ tok_embed.T).
    }


def layer_norm(x, g, b, eps=1e-5):
    """Stats accumulate in f32 regardless of input dtype (bf16-safe)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = ((xf - mu) * lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * g + b


def dot_product_attention(q, k, v, *, causal: bool):
    """Standard softmax attention.

    ``q,k,v: (..., T, H, Dh)`` -> ``(..., T, H, Dh)``. Scores accumulate
    in f32 regardless of input dtype (bf16-safe on the MXU).
    """
    dtype = q.dtype
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def attn_sublayer(block: dict, x: jnp.ndarray, cfg: TransformerConfig,
                  attn_fn=dot_product_attention, *, return_kv: bool = False):
    """Pre-LN attention sublayer with residual: ``(B, T, D) -> (B, T, D)``.

    Shared by the dense block and the MoE block
    (:mod:`tpu_dist_nn.parallel.expert_parallel`), which differ only in
    their FFN sublayer. ``return_kv`` additionally returns this
    sublayer's ``(k, v)`` ``(B, T, H, Dh)`` tensors — the KV-cache fill
    for autoregressive decoding (:mod:`tpu_dist_nn.models.generate`).
    """
    B, T, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    h = layer_norm(x, block["ln1_g"], block["ln1_b"])
    qkv = h @ block["w_qkv"] + block["b_qkv"]
    q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, Dh), 3, axis=2)
    o = attn_fn(q, k, v, causal=cfg.causal).reshape(B, T, D)
    y = x + o @ block["w_o"] + block["b_o"]
    return (y, k, v) if return_kv else y


def ffn_sublayer(block: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Pre-LN GELU MLP sublayer with residual — shared by the batched
    block and the KV-cached decode step (``models.generate``)."""
    h = layer_norm(x, block["ln2_g"], block["ln2_b"])
    h = jax.nn.gelu(h @ block["w_up"] + block["b_up"])
    return x + h @ block["w_down"] + block["b_down"]


def block_apply(block: dict, x: jnp.ndarray, cfg: TransformerConfig,
                attn_fn=dot_product_attention) -> jnp.ndarray:
    """One pre-LN residual block: ``x: (batch, T, D) -> (batch, T, D)``.

    ``block`` holds *unstacked* leaves (no leading layer axis) — a scan
    carry slice single-chip, or one stage's shard in the pipeline.
    """
    return ffn_sublayer(block, attn_sublayer(block, x, cfg, attn_fn))


def maybe_remat(cfg: TransformerConfig, apply=block_apply):
    """``apply`` wrapped in per-block rematerialization when
    ``cfg.remat`` — the one definition of the trade for every scan body
    (single-chip, pipelined, ring, tensor-parallel): drop each block's
    internal activations after the forward, recompute them in the
    backward. HBM residency falls from O(n_layers * per-block) to one
    block's worth, bought with ~1/3 more FLOPs (MXU FLOPs are the cheap
    resource; HBM is the bottleneck). Trailing args of ``apply`` beyond
    (block, x) must be static (hashable)."""
    if not cfg.remat:
        return apply
    import inspect

    n_args = len(inspect.signature(apply).parameters)
    return jax.checkpoint(
        apply, static_argnums=tuple(range(2, n_args)), prevent_cse=False
    )


def embed(params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    """``tokens: (batch, T) int32 -> (batch, T, D)`` activations."""
    T = tokens.shape[-1]
    return params["tok_embed"][tokens] + params["pos_embed"][:T]


def unembed(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final LN + tied LM head: ``(batch, T, D) -> (batch, T, V)``."""
    x = layer_norm(x, params["lnf_g"], params["lnf_b"])
    return x @ params["tok_embed"].T


def forward(params: dict, tokens: jnp.ndarray, cfg: TransformerConfig,
            attn_fn=dot_product_attention) -> jnp.ndarray:
    """Full LM forward: ``(batch, T) tokens -> (batch, T, vocab) logits``.

    The block stack runs as ``lax.scan`` over the stacked layer axis —
    one traced block body regardless of depth. Runs in
    ``cfg.compute_dtype`` (params cast per :meth:`cast_params`).
    """
    params = cfg.cast_params(params)
    x = embed(params, tokens)

    apply = maybe_remat(cfg)

    def body(carry, block):
        return apply(block, carry, cfg, attn_fn), None

    x, _ = lax.scan(body, x, params["blocks"])
    return unembed(params, x)


def next_token_ce(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean cross-entropy (nats/token): ``logits (..., T, V)``,
    ``targets (..., T) int``. The single definition of the LM loss
    numerics, shared by the dense, MoE, and sharded loss paths."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def masked_next_token_ce(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token CE on FULL (input+target) rows: score positions
    ``0..T-2`` against targets ``1..T-1`` instead of slicing the input
    (the shifted slice would break seq-axis divisibility). The single
    definition of the sequence-parallel loss convention — shared by the
    sp-only path (ring_attention) and pipeline x sp, which are
    documented as numerically comparable BECAUSE they call this."""
    return next_token_ce(logits[:, :-1], tokens[:, 1:])


def lm_loss(params: dict, tokens: jnp.ndarray, cfg: TransformerConfig,
            attn_fn=dot_product_attention) -> jnp.ndarray:
    """Next-token cross-entropy (mean nats/token) on ``(batch, T)`` tokens."""
    logits = forward(params, tokens[:, :-1], cfg, attn_fn)
    return next_token_ce(logits, tokens[:, 1:])


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
