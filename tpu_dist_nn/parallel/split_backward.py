"""Cotangent-stash split backward: the missing piece for a TRUE
zero-bubble W tick.

The round-5 wall-clock measurement (docs/PERF.md "Do ticks translate
to time?", `examples/schedule_walltime.py`) showed the executor's
recompute-based split backward pays the chunk FORWARD in both halves
(BWD_B and BWD_W each rebuild the vjp from the stashed input), so the
zero-bubble schedules' tick-level advantage does not survive measured
branch costs. The canonical ZB accounting (B ≈ W ≈ F) assumes a W tick
that is PURE weight-gradient GEMMs — ``dW = actᵀ @ cot`` per weighted
op — which requires the B tick to stash every (activation, cotangent)
pair at the weight-application points. jax's ``vjp`` does not expose
interior cotangents, so this module hand-chains the block backward at
SUB-OP granularity:

* the risky math (softmax attention core, GELU, LayerNorm) stays
  inside ``jax.vjp`` of weight-free subfunctions — nothing numerical
  is re-derived by hand;
* only the weight applications are split: the dx half
  (``cot @ Wᵀ``) happens in B, the dW half (``actᵀ @ cot``) is
  DEFERRED — B stashes the four (act, cot) pairs per block
  (w_qkv, w_o, w_up, w_down; bias and LayerNorm grads are tiny and
  computed in B);
* W (:func:`chunk_weight_grads`) is then exactly the canonical W tick:
  four GEMMs per block, NO forward recompute, no backward backbone.

Cost model (the triangle PERF.md describes, now with all three
corners): B = one forward recompute + backbone + dx GEMMs (the
combined backward minus the dW GEMMs); W = dW GEMMs only. Memory: the
stash is ~(2F + 8D)/D ≈ 16× a block input per block — the price the
canonical accounting always implied. Parity:
:func:`chunk_backward_split` + :func:`chunk_weight_grads` equal
``jax.vjp`` of the chunk forward exactly (tested to AD tolerances with
the jnp reference attention; any ``attn_fn`` — flash included — rides
``jax.vjp`` of the weight-free core, but only the reference core is
parity-tested in CI).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    dot_product_attention,
    layer_norm,
)


def block_backward_split(block: dict, x: jnp.ndarray, dy: jnp.ndarray,
                         cfg: TransformerConfig,
                         attn_fn=dot_product_attention):
    """One block's backward with the four dW GEMMs DEFERRED.

    -> ``(dx, d_small, wstash)`` where ``d_small`` holds the bias and
    LayerNorm grads (computed here — they are reductions, not GEMMs)
    and ``wstash`` holds the four (activation, cotangent) pairs from
    which :func:`block_weight_grads` later computes
    ``d_{w_qkv, w_o, w_up, w_down}`` as pure GEMMs.

    The forward runs ONCE, capturing the sub-op vjps as it goes (their
    primal outputs ARE the interior activations) — same math as
    ``models.transformer.block_apply``, de-composed at the weight
    applications.
    """
    B, T, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim

    # ---- Forward, vjps captured at the weight-free sub-ops.
    h1, ln1_vjp = jax.vjp(
        lambda xx, g, b: layer_norm(xx, g, b), x, block["ln1_g"],
        block["ln1_b"],
    )
    qkv = h1 @ block["w_qkv"] + block["b_qkv"]
    q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, Dh), 3, axis=2)
    o, attn_vjp = jax.vjp(
        lambda qq, kk, vv: attn_fn(qq, kk, vv, causal=cfg.causal), q, k, v
    )
    o_flat = o.reshape(B, T, D)
    y1 = x + o_flat @ block["w_o"] + block["b_o"]
    h2, ln2_vjp = jax.vjp(
        lambda xx, g, b: layer_norm(xx, g, b), y1, block["ln2_g"],
        block["ln2_b"],
    )
    pre = h2 @ block["w_up"] + block["b_up"]
    u, gelu_vjp = jax.vjp(jax.nn.gelu, pre)

    # ---- FFN sublayer backward: y2 = y1 + gelu(LN2(y1)@Wup+bup)@Wdown
    du = dy @ block["w_down"].T                      # dx half of w_down
    d_bdown = jnp.sum(dy, axis=(0, 1))
    (d_pre,) = gelu_vjp(du)
    dh2 = d_pre @ block["w_up"].T                    # dx half of w_up
    d_bup = jnp.sum(d_pre, axis=(0, 1))
    d_y1_ln, d_g2, d_b2 = ln2_vjp(dh2)
    d_y1 = dy + d_y1_ln                              # + residual

    # ---- Attention sublayer backward: y1 = x + attn(LN1(x))@Wo + bo
    d_o_flat = d_y1 @ block["w_o"].T                 # dx half of w_o
    d_bo = jnp.sum(d_y1, axis=(0, 1))
    d_o = d_o_flat.reshape(B, T, H, Dh)
    dq, dk, dv = attn_vjp(d_o)
    d_qkv = jnp.concatenate([dq, dk, dv], axis=2).reshape(B, T, 3 * D)
    dh1 = d_qkv @ block["w_qkv"].T                   # dx half of w_qkv
    d_bqkv = jnp.sum(d_qkv, axis=(0, 1))
    dx_ln, d_g1, d_b1 = ln1_vjp(dh1)
    dx = d_y1 + dx_ln                                # + residual

    d_small = {
        "b_qkv": d_bqkv, "b_o": d_bo, "b_up": d_bup, "b_down": d_bdown,
        "ln1_g": d_g1, "ln1_b": d_b1, "ln2_g": d_g2, "ln2_b": d_b2,
    }
    wstash = {
        "h1": h1, "d_qkv": d_qkv,          # -> d_w_qkv
        "o_flat": o_flat, "d_y1": d_y1,    # -> d_w_o
        "h2": h2, "d_pre": d_pre,          # -> d_w_up
        "u": u, "dy": dy,                  # -> d_w_down
    }
    return dx, d_small, wstash


def block_weight_grads(wstash: dict) -> dict:
    """The canonical ZB W tick for one block: four GEMMs, nothing else.

    ``d_W = actᵀ @ cot`` with the (act, cot) pairs
    :func:`block_backward_split` stashed — no forward recompute, no
    backward backbone.
    """
    def gemm(act, cot):
        return jnp.einsum("btd,btf->df", act, cot)

    return {
        "w_qkv": gemm(wstash["h1"], wstash["d_qkv"]),
        "w_o": gemm(wstash["o_flat"], wstash["d_y1"]),
        "w_up": gemm(wstash["h2"], wstash["d_pre"]),
        "w_down": gemm(wstash["u"], wstash["dy"]),
    }


def chunk_backward_split(blocks: dict, x: jnp.ndarray, dy: jnp.ndarray,
                         cfg: TransformerConfig,
                         attn_fn=dot_product_attention):
    """Split backward through a CHUNK (stacked ``(L_c, ...)`` blocks).

    Recomputes the forward ONCE from the chunk input (storing each
    block's input — the memory-flat property the executors rely on),
    then walks blocks in reverse with :func:`block_backward_split`.

    -> ``(dx, d_small (L_c-stacked), wstash (L_c-stacked))``.
    """
    def fwd_body(carry, block):
        from tpu_dist_nn.models.transformer import block_apply

        return block_apply(block, carry, cfg, attn_fn), carry

    _, xs = jax.lax.scan(fwd_body, x, blocks)  # xs: per-block INPUTS

    def bwd_body(cot, inputs):
        block, x_in = inputs
        dx, d_small, wstash = block_backward_split(
            block, x_in, cot, cfg, attn_fn
        )
        return dx, (d_small, wstash)

    dx, (d_smalls, wstashes) = jax.lax.scan(
        bwd_body, dy, (blocks, xs), reverse=True
    )
    return dx, d_smalls, wstashes


def chunk_weight_grads(wstashes: dict) -> dict:
    """W over a chunk's stacked stash: ``(L_c, ...)`` GEMMs via vmap —
    one fused launch, still nothing but GEMMs."""
    return jax.vmap(block_weight_grads)(wstashes)
