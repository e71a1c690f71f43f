"""Flash-attention kernel parity vs the jnp reference attention —
forward and custom-VJP backward, causal and bidirectional, ragged
lengths, and as an attn_fn swapped into the transformer block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.kernels.flash_attention import flash_attention
from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    dot_product_attention,
    forward,
    init_transformer,
    lm_loss,
)


def _qkv(B, T, H, Dh, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T", [32, 48])  # 48: ragged (pads to block)
def test_forward_matches_reference(causal, T):
    q, k, v = _qkv(2, T, 2, 16)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_reference(causal):
    q, k, v = _qkv(2, 32, 2, 8, seed=1)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=16, block_k=16) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_grads_match_reference_ragged_causal():
    # T=24 with block 16 -> padded to 32; padded keys must not leak
    # into outputs or gradients.
    q, k, v = _qkv(1, 24, 2, 8, seed=2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16) ** 2
        )

    np.testing.assert_allclose(
        float(loss_ref(q, k, v)), float(loss_flash(q, k, v)), rtol=1e-5
    )
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_swaps_into_transformer_forward_and_loss():
    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_seq_len=32,
    )
    params = init_transformer(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, (2, 32)), jnp.int32
    )

    def flash_fn(q, k, v, *, causal):
        return flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)

    ref_logits = forward(params, tokens, cfg)
    out_logits = forward(params, tokens, cfg, attn_fn=flash_fn)
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(out_logits), rtol=2e-4, atol=2e-4
    )

    # jitted: eager grad-through-interpret-mode-pallas is the suite's
    # slowest single test otherwise (and never hits the compile cache).
    g_ref = jax.jit(jax.grad(lm_loss), static_argnums=2)(params, tokens, cfg)
    g_out = jax.jit(
        jax.grad(lambda p, t: lm_loss(p, t, cfg, attn_fn=flash_fn))
    )(params, tokens)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_out)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


@pytest.mark.parametrize("bq,bk", [(16, 8), (8, 16), (32, 8)])
def test_mismatched_block_sizes_with_ragged_length(bq, bk):
    # T=40 doesn't divide either block size; padding must extend to a
    # common multiple of both or keys/rows are silently dropped.
    q, k, v = _qkv(1, 40, 2, 8, seed=3)
    ref = dot_product_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=bq, block_k=bk)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=2e-5, atol=2e-5
    )


def test_rejects_mismatched_shapes():
    q, k, v = _qkv(1, 16, 2, 8)
    with pytest.raises(ValueError, match="must match"):
        flash_attention(q, k[:, :8], v, causal=True)


@pytest.mark.parametrize("head_dim,dtype,fwd,bwd", [
    (64, jnp.bfloat16, 94208, 31360),
    (64, jnp.float32, 47104, 23552),
    (128, jnp.bfloat16, 94208, 31360),   # lanes pad to 128 either way
    (256, jnp.float32, 23552, 15616),
])
def test_sequence_ceiling_follows_the_resident_vmem_model(
        head_dim, dtype, fwd, bwd):
    from tpu_dist_nn.kernels.flash_attention import max_seq_len

    assert max_seq_len(head_dim, dtype) == fwd
    assert max_seq_len(head_dim, dtype, backward=True) == bwd


def test_length_past_the_ceiling_raises_before_tracing_a_kernel():
    """The whole-sequence operands are VMEM-resident, so a length the
    compiler would refuse is refused here first, by name — forward at
    the forward ceiling, and at the lower backward ceiling once the VJP
    is traced. Shapes only: nothing this long is allocated."""
    from tpu_dist_nn.kernels.flash_attention import max_seq_len

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def spec(t):
        return (jax.ShapeDtypeStruct((1, t, 1, 64), jnp.bfloat16),) * 3

    fwd, bwd = max_seq_len(64, jnp.bfloat16), max_seq_len(
        64, jnp.bfloat16, backward=True
    )
    with pytest.raises(ValueError, match=f"T={fwd + 1} .* {fwd} rows"):
        jax.eval_shape(attn, *spec(fwd + 1))
    grad = jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum())
    assert jax.eval_shape(attn, *spec(bwd + 1)).shape == (1, bwd + 1, 1, 64)
    with pytest.raises(ValueError, match="backward pass"):
        jax.eval_shape(grad, *spec(bwd + 1))
    jax.eval_shape(grad, *spec(bwd))
