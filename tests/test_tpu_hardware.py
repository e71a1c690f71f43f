"""Real-hardware parity gates — skipped on the CPU test mesh.

The CPU suite pins exact tolerances under
``JAX_DEFAULT_MATMUL_PRECISION=highest``; on a real TPU the default f32
matmul precision differs from the float64 oracle by ~1e-3 relative
(bf16-accumulated MXU passes). These tests encode that documented
tolerance policy (SURVEY.md §7 hard part 3) against the actual chip,
plus compile/parity checks for the Pallas kernels that only lower via
Mosaic there. Run on a TPU host:
``TDN_TEST_TPU=1 python -m pytest tests/test_tpu_hardware.py``
(without the env var the conftest pins the CPU backend and every test
here skips). ``chip_smoke.py`` runs exactly that as its kernels phase
and reads the recorded properties from ``--junitxml``.

Every kernel test also asserts that the compiled program holds a Mosaic
kernel (``tpu_custom_call``): several entry points dispatch quietly to
their jnp/lax reference by shape, and a kernel that did would otherwise
be compared with itself.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend"
)

from tpu_dist_nn.models.fcnn import forward, init_fcnn, spec_from_params  # noqa: E402
from tpu_dist_nn.testing.oracle import oracle_forward_batch  # noqa: E402

TPU_RTOL = 2e-3  # default-precision f32 MXU vs float64 oracle
TPU_ATOL = 2e-3


def _holds_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _max_err(got, want) -> float:
    return float(np.max(np.abs(
        np.asarray(got, np.float64) - np.asarray(want, np.float64)
    )))


def test_device_is_a_tpu(record_property):
    d = jax.devices()[0]
    record_property("platform", d.platform)
    record_property("kind", d.device_kind)
    record_property("count", len(jax.devices()))
    record_property("compile_cache_dir", jax.config.jax_compilation_cache_dir)
    assert d.platform == "tpu"


def test_block_until_ready_waits_for_the_device():
    """Timings in this repo end in block_until_ready or a value fetch;
    the former has to be a barrier for them to mean anything."""
    import time

    x = jnp.ones((4096, 4096), jnp.bfloat16)
    chain = jax.jit(lambda a: jax.lax.fori_loop(
        0, 400, lambda _, c: (c @ a) * jnp.bfloat16(1e-4), a
    )[:8, :128])
    chain(x).block_until_ready()  # compile
    t0 = time.monotonic()
    y = chain(x)
    dispatched = time.monotonic() - t0
    y.block_until_ready()
    blocked = time.monotonic() - t0
    np.asarray(y)
    fetched = time.monotonic() - t0
    # 400 4096^3 matmuls are >0.25 s of MXU time at the chip's peak: the
    # call returns at once, the wait takes the compute, and the fetch
    # after it has nothing left to wait for.
    assert dispatched < 0.05 < blocked
    assert fetched - blocked < 0.05


def test_forward_parity_vs_oracle_on_device(record_property):
    params = init_fcnn(jax.random.key(0), [784, 128, 64, 10])
    model = spec_from_params(params, ["relu", "relu", "softmax"])
    x = np.random.default_rng(0).uniform(0, 1, (64, 784)).astype(np.float32)
    got = np.asarray(jax.jit(forward)(params, jnp.asarray(x)))
    want = oracle_forward_batch(model, x)
    record_property("max_abs_err", _max_err(got, want))
    np.testing.assert_allclose(got, want, rtol=TPU_RTOL, atol=TPU_ATOL)


@pytest.mark.parametrize("m,k,n,activation", [
    (256, 784, 128, "relu"), (256, 64, 10, "softmax"), (100, 784, 128, "relu"),
])
def test_fused_dense_matches_jnp_on_device(record_property, m, k, n, activation):
    from tpu_dist_nn.kernels.fused_dense import (
        _apply_named_activation,
        fused_dense,
    )

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.uniform(0, 1, (m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)
    fn = lambda x, w, b: fused_dense(x, w, b, activation=activation)  # noqa: E731
    _holds_kernel(fn, x, w, b)
    got = fn(x, w, b)
    want = _apply_named_activation(x @ w + b, activation)
    record_property("max_abs_err", _max_err(got, want))
    np.testing.assert_allclose(got, want, rtol=TPU_RTOL, atol=TPU_ATOL)


def test_fused_chain_matches_jnp_on_device(record_property):
    from tpu_dist_nn.kernels.fused_dense import fcnn_fused_forward

    params = init_fcnn(jax.random.key(1), [784, 128, 64, 10])
    x = jnp.asarray(
        np.random.default_rng(1).uniform(0, 1, (256, 784)), jnp.float32
    )
    acts = ("relu", "relu", "softmax")
    fn = lambda p, x: fcnn_fused_forward(p, x, activations=acts)  # noqa: E731
    _holds_kernel(fn, params, x)
    got = np.asarray(fn(params, x))
    want = np.asarray(forward(params, x))
    record_property("max_abs_err", _max_err(got, want))
    np.testing.assert_allclose(got, want, rtol=TPU_RTOL, atol=TPU_ATOL)


def test_flash_attention_matches_reference_on_device(record_property):
    from tpu_dist_nn.kernels.flash_attention import flash_attention
    from tpu_dist_nn.models.transformer import dot_product_attention

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 32)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 4, 32)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 4, 32)) * 0.5, jnp.float32)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    _holds_kernel(fn, q, k, v)
    got = np.asarray(fn(q, k, v))
    want = np.asarray(dot_product_attention(q, k, v, causal=True))
    record_property("max_abs_err", _max_err(got, want))
    # The MXU path rounds through bf16 (8 mantissa bits ≈ 4e-3 rel);
    # observed worst case is 1 element in 32k just over 2e-3.
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)


def test_flash_attention_long_bf16_forward_and_backward_on_device(
        record_property):
    """The shape flash is selected at (T >= FLASH_MIN_SEQ) and the LM's
    head layout: T=4096, 12 heads of 64, bf16, forward and all three
    gradients against f32 attention on the same bf16-rounded inputs.
    Tolerances are bf16's: one rounding of the output (2^-8 relative)
    forward, two chained rounded products in the gradients."""
    from tpu_dist_nn.kernels.flash_attention import flash_attention
    from tpu_dist_nn.models.transformer import dot_product_attention

    rng = np.random.default_rng(6)
    shape = (1, 4096, 12, 64)
    q, k, v, g = (
        jnp.asarray(rng.normal(size=shape) * 0.5, jnp.bfloat16)
        for _ in range(4)
    )

    def loss(attn, q, k, v):
        out = attn(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    flash = jax.jit(jax.value_and_grad(
        lambda q, k, v: loss(flash_attention, q, k, v),
        argnums=(0, 1, 2), has_aux=True,
    ))
    _holds_kernel(flash, q, k, v)
    (_, out), grads = flash(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(dot_product_attention, q, k, v),
            argnums=(0, 1, 2), has_aux=True,
        ))(*(a.astype(jnp.float32) for a in (q, k, v)))

    def rel(got, want):
        want = np.asarray(want, np.float64)
        return _max_err(got, want) / float(np.max(np.abs(want)))

    assert np.isfinite(np.asarray(out, np.float32)).all()
    fwd = rel(out, ref_out)
    bwd = max(rel(a, b) for a, b in zip(grads, ref_grads))
    record_property("max_abs_err", max(fwd, bwd))
    assert fwd < 2e-2, fwd
    assert bwd < 4e-2, bwd


def test_conv_kernel_matches_lax_on_device(record_property):
    from jax import lax

    from tpu_dist_nn.kernels.conv2d import fused_conv2d

    rng = np.random.default_rng(3)
    imgs = jnp.asarray(rng.normal(size=(64, 16, 16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 32, 64)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    fn = lambda imgs, w, b: fused_conv2d(  # noqa: E731
        imgs, w, b, padding="same", activation="relu", pool_window=(2, 2)
    )
    _holds_kernel(fn, imgs, w, b)
    got = fn(imgs, w, b)
    conv = lax.conv_general_dilated(
        imgs, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + b
    want = lax.reduce_window(
        jnp.maximum(conv, 0.0), -jnp.inf, lax.max,
        window_dimensions=(1, 2, 2, 1), window_strides=(1, 2, 2, 1),
        padding="VALID",
    )
    record_property("max_abs_err", _max_err(got, want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=TPU_RTOL, atol=TPU_ATOL
    )


def test_int8_chain_accuracy_preserving_on_device(record_property):
    from tpu_dist_nn.kernels.quantized import (
        fcnn_quantized_forward,
        forward_quantized,
        quantize_fcnn,
    )

    params = init_fcnn(jax.random.key(2), [784, 128, 64, 10])
    x = jnp.asarray(
        np.random.default_rng(4).uniform(0, 1, (512, 784)), jnp.float32
    )
    qp = quantize_fcnn(params)
    acts = ("relu", "relu", "softmax")
    # prefer_kernel=True: this gate exists to prove the Pallas int8
    # chain on hardware; the shape-based dispatch would route the
    # flagship's tiny layers to the jnp chain.
    fn = lambda qp, x: fcnn_quantized_forward(  # noqa: E731
        qp, x, activations=acts, prefer_kernel=True
    )
    _holds_kernel(fn, qp, x)
    got = np.asarray(fn(qp, x))
    # Same arithmetic in jnp: the kernel's own reference.
    record_property(
        "max_abs_err", _max_err(got, forward_quantized(qp, x, acts))
    )
    want = np.asarray(forward(params, x)).argmax(-1)
    # Int8 is lossy; the serving gate is argmax agreement, not values.
    assert (got.argmax(-1) == want).mean() > 0.97
