"""Fused dense Pallas kernels.

Two kernels:

* :func:`fused_dense` — one layer, ``act(x @ W + b)``, tiled over an
  ``(M/bm, N/bn)`` grid with the K dim resident: each program computes
  one ``(bm, bn)`` output tile on the MXU with f32 accumulation and
  applies bias+activation on the VPU before the tile leaves VMEM.
* :func:`fcnn_fused_forward` — a whole FCNN chain in ONE kernel per
  batch tile: every layer's weights sit in VMEM and the inter-layer
  activations never touch HBM. For reference-scale MLPs
  (784-128-64-10 ≈ 0.4 MB of f32 weights, far under the ~16 MB VMEM
  budget) this removes every intermediate HBM round-trip — the fusion
  XLA cannot do (it fuses elementwise into a matmul, not
  matmul→matmul). Falls back to the jnp chain when the weights would
  not fit.

Both run in interpreter mode automatically off-TPU (CPU tests), and
compile to Mosaic on TPU. Activation handling is static (Python-level
dispatch on the name — no lax.switch inside the kernel).

Not measured on today's code. The one round-4 run on a v5e had the
f32 whole-chain kernel at parity at best with XLA's own fusion — behind
it at the flagship's tiny widths, close at widths 512-1024, and failing
to compile past the VMEM budget at 2048+. Nothing in the framework
routes f32 inference through this kernel by default; it remains for the
int8 variant (kernels/quantized.py) and as the VMEM-residency pattern
the quantized chain builds on. The hardware parity gate is
tests/test_tpu_hardware.py::test_fused_chain_matches_jnp_on_device.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tpu_dist_nn.core.activations import ACTIVATION_NAMES

# Weight budget for the whole-chain kernel: stay well under ~16 MB VMEM
# (weights + biases + two activation buffers + padding slack).
_VMEM_WEIGHT_BUDGET_BYTES = 8 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _apply_named_activation(z: jnp.ndarray, name: str) -> jnp.ndarray:
    if name == "linear":
        return z
    if name == "relu":
        return jnp.maximum(z, 0.0)
    if name == "sigmoid":
        return jax.nn.sigmoid(z)
    if name == "tanh":
        return jnp.tanh(z)
    if name == "gelu":
        return jax.nn.gelu(z)
    if name == "softmax":
        return jax.nn.softmax(z, axis=-1)
    raise ValueError(f"unknown activation for fused kernel: {name}")


# ---------------------------------------------------------------------------
# Single fused layer
# ---------------------------------------------------------------------------

def _dense_kernel(x_ref, w_ref, b_ref, o_ref, *, activation: str):
    z = (
        jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
        + b_ref[:].astype(jnp.float32)
    )
    o_ref[:] = _apply_named_activation(z, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "block_m", "block_n"))
def fused_dense(x, w, b, *, activation: str = "linear", block_m: int = 256,
                block_n: int = 256):
    """``act(x @ W + b)`` as one Pallas kernel.

    ``x: (M, K)``, ``w: (K, N)``, ``b: (N,)``. Tiles the output over an
    ``(⌈M/bm⌉, ⌈N/bn⌉)`` grid with K resident per program (reference
    layer widths keep K small; blocked-K is not needed at this scale).
    Softmax needs the whole row: it forces ``block_n >= N``.
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2 or b.shape != (N,):
        raise ValueError(f"shape mismatch: x{x.shape} @ w{w.shape} + b{b.shape}")
    bm = min(block_m, M)
    bn = N if activation == "softmax" else min(block_n, N)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn))
    return pl.pallas_call(
        functools.partial(_dense_kernel, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bn,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=_interpret(),
    )(x, w, b)


# ---------------------------------------------------------------------------
# Whole-chain kernel
# ---------------------------------------------------------------------------

def _chain_kernel(x_ref, *refs, activations: Sequence[str],
                  input_scale: float | None):
    *wb_refs, o_ref = refs
    h = x_ref[:]
    if input_scale is not None:
        # Integer wire format: normalize on-device (e.g. uint8 pixels
        # scaled by 1/255) — 4x less host->device traffic than f32.
        h = h.astype(jnp.float32) * input_scale
    compute_dtype = o_ref.dtype
    h = h.astype(compute_dtype)
    for li, act in enumerate(activations):
        w_ref, b_ref = wb_refs[2 * li], wb_refs[2 * li + 1]
        z = (
            jnp.dot(h, w_ref[:], preferred_element_type=jnp.float32)
            + b_ref[:].astype(jnp.float32)
        )
        h = _apply_named_activation(z, act).astype(compute_dtype)
    o_ref[:] = h


def chain_fits_vmem(params) -> bool:
    weight_bytes = sum(
        int(np.prod(p["w"].shape)) * p["w"].dtype.itemsize
        + int(np.prod(p["b"].shape)) * p["b"].dtype.itemsize
        for p in params
    )
    return weight_bytes <= _VMEM_WEIGHT_BUDGET_BYTES


def fcnn_fused_forward(params, x, *, activations: Sequence[str] | None = None,
                       block_b: int = 512, input_scale: float | None = None):
    """Whole FCNN chain in one Pallas kernel per batch tile.

    ``params``: the :mod:`tpu_dist_nn.models.fcnn` pytree. Every
    layer's weights are resident in VMEM; the grid covers only the
    batch dim, so inter-layer activations stay on-chip. Falls back to
    the plain jnp chain when the weights exceed the VMEM budget.

    Pass ``activations`` explicitly on hot paths: recovering the names
    from the params' ``act`` ids forces a device->host scalar read per
    layer on every call.

    ``input_scale``: accept an integer-typed ``x`` (e.g. uint8 pixels)
    and normalize on device — the wire format then carries 1 byte per
    feature instead of 4.
    """
    if activations is None:
        activations = tuple(ACTIVATION_NAMES[int(p["act"])] for p in params)
    else:
        activations = tuple(activations)

    if not chain_fits_vmem(params):
        from tpu_dist_nn.models.fcnn import forward

        xf = x.astype(jnp.float32) * input_scale if input_scale is not None else x
        return forward(params, xf)

    return _fcnn_fused_call(
        tuple((p["w"].shape, p["b"].shape) for p in params),
        activations,
        min(block_b, x.shape[0]),
        input_scale,
        x,
        *[t for p in params for t in (p["w"], p["b"])],
    )


@functools.partial(
    jax.jit,
    static_argnames=("wb_shapes", "activations", "block_b", "input_scale"),
)
def _fcnn_fused_call(wb_shapes, activations, block_b, input_scale, x, *wbs):
    M = x.shape[0]
    out_dim = wb_shapes[-1][0][1]
    out_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    grid = (pl.cdiv(M, block_b),)
    in_specs = [pl.BlockSpec((block_b, x.shape[1]), lambda i: (i, 0))]
    for w_shape, b_shape in wb_shapes:
        in_specs.append(pl.BlockSpec(w_shape, lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec(b_shape, lambda i: (0,)))
    return pl.pallas_call(
        functools.partial(
            _chain_kernel, activations=activations, input_scale=input_scale
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, out_dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, out_dim), out_dtype),
        interpret=_interpret(),
    )(x, *wbs)
