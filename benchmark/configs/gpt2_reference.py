"""Plain reference for the GPT-2 configurations, and their seeded weights.

GPT-2 as published (Radford et al. 2019; `modeling_gpt2.py`): learned
positions, pre-LayerNorm blocks, fused c_attn (q | k | v), causal
softmax attention scaled by 1/sqrt(head size), `gelu_new` (the tanh
form), a final LayerNorm and a head tied to the token embedding.
Departure: no dropout (see each configuration's `assumed`).

Everything is straightforward `jax.numpy` in float32 at matmul
precision `highest`: no kernels, no cache, no batching tricks. It
imports nothing of the program. The weights are made here from the seed
in the pytree layout that the program's entry points take as input:
they are the benchmark's input to the program, like the prompts.

The weights are random, and NOT GPT-2's initialisation: with that one
(matrices N(0, 0.02), residual projections damped by 1/sqrt(2 n_layer))
a tied head makes the model repeat its last token with a margin of
several units, and a comparison of served tokens with the reference's
best could then tell no precision from another (my chip run 1, PR 23:
the widest gap read 0.0017 and 0.0).  Here every matrix has unit gain
(N(0, 1/fan_in)), the embeddings keep `initializer_range`, gains are
1 + N(0, 0.02) and biases N(0, 0.02), so that logits spread by about
0.6 and every leaf takes part.

`quant` computes the same mathematics in a lower precision, put in the
program's place as the control of "How `correct` is decided".  Every
activation is then held in bfloat16, as a program of these
configurations holds it, and every matmul operand is rounded:
`"fp8"` to float8 e4m3 with one scale a tensor, THE control, which the
cells' limits fail on every seed read; `"int8"` to 8-bit integers
(weights per output channel, activations per token, attention operands
per head and token), which the limits do NOT fail (its readings lie
within a factor of the program's own that the seed's weights move:
each cell file says so under `not_guarded`); `"bf16"` to bfloat16,
the program's own precision emulated, read beside the others so that
a later limit normalised within the run has its readings.  Gradients
pass the rounding straight through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_LEAVES = ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_o", "b_o",
                "ln2_g", "ln2_b", "w_up", "b_up", "w_down", "b_down")


def sizes(cfg: dict) -> dict:
    """The sizes the mathematics needs, from a configuration file."""
    d = int(cfg["n_embd"])
    return {
        "D": d, "L": int(cfg["n_layer"]), "H": int(cfg["n_head"]),
        "F": int(cfg.get("n_inner") or 4 * d),
        "P": int(cfg["n_positions"]), "V": int(cfg["vocab_size"]),
        "eps": float(cfg["layer_norm_epsilon"]),
        "std": float(cfg["initializer_range"]),
    }


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def weight_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    D, L, F = s["D"], s["L"], s["F"]
    return {
        "tok_embed": (s["V"], D), "pos_embed": (s["P"], D),
        "blocks": {
            "ln1_g": (L, D), "ln1_b": (L, D),
            "w_qkv": (L, D, 3 * D), "b_qkv": (L, 3 * D),
            "w_o": (L, D, D), "b_o": (L, D),
            "ln2_g": (L, D), "ln2_b": (L, D),
            "w_up": (L, D, F), "b_up": (L, F),
            "w_down": (L, F, D), "b_down": (L, D),
        },
        "lnf_g": (D,), "lnf_b": (D,),
    }


def _weights(key, cfg_items: tuple, dtype: str):
    cfg = dict(cfg_items)
    s = sizes(cfg)
    std = s["std"]
    shapes = weight_shapes(cfg)
    names = ["tok_embed", "pos_embed", "lnf_g", "lnf_b"] + [
        f"blocks/{n}" for n in BLOCK_LEAVES
    ]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def leaf(name, shape):
        z = jax.random.normal(keys[name], shape, jnp.float32)
        base = name.split("/")[-1]
        if base.startswith("w_"):
            # Unit gain: every sublayer adds about one unit of spread
            # to the residual, so the context, not the last token's own
            # embedding, decides the next token (see module docstring).
            return z / np.sqrt(shape[-2])
        if base == "pos_embed":
            return z * 0.01
        if base.endswith("_g"):
            return 1.0 + z * 0.02
        if base.endswith("_b") or base.startswith("b_"):
            return z * 0.02
        return z * std

    out = {n: leaf(n, shapes[n])
           for n in ("tok_embed", "pos_embed", "lnf_g", "lnf_b")}
    out["blocks"] = {
        n: leaf(f"blocks/{n}", shapes["blocks"][n]) for n in BLOCK_LEAVES
    }
    return jax.tree.map(lambda a: a.astype(jnp.dtype(dtype)), out)


_weights_jit = jax.jit(_weights, static_argnums=(1, 2))


def _hashable(cfg: dict) -> tuple:
    keep = ("n_embd", "n_layer", "n_head", "n_inner", "n_positions",
            "vocab_size", "layer_norm_epsilon", "initializer_range")
    return tuple((k, cfg.get(k)) for k in keep)


def make_weights(cfg: dict, seed: int, dtype: str = "float32"):
    """Parameters on the device, in one jitted call from the seed: drawn
    in float32 and rounded once to `dtype`, the type they are held in."""
    return _weights_jit(seed_key(seed), _hashable(cfg), dtype)


# ---------------------------------------------------------------- int8

def _fq(x, axis):
    """Round to 8-bit integers along `axis` (symmetric, one scale per
    slice), and back; the gradient passes straight through."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _r(x, quant):
    """Under the control, what an int8 program of a bfloat16
    configuration would still hold in bfloat16: every activation."""
    if quant is None:
        return x
    # reduce_precision, not a pair of casts: XLA drops a cast to bfloat16
    # and back as excess precision (chip call 12, PR 23: the emulated
    # bfloat16 pass then agreed with float32 on every token of medium).
    return x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) - x)


def _f8(x):
    """Round to float8 (e4m3, one scale for the tensor), and back; the
    gradient passes straight through."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    """x (..., K) @ w (K, N)."""
    if quant == "int8":
        x, w = _fq(x, -1), _fq(w, 0)
    elif quant == "fp8":
        x, w = _f8(x), _f8(w)
    elif quant == "bf16":
        x, w = _r(x, quant), _r(w, quant)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _r(x @ w, quant)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, blk, s, quant):
    B, T, D = x.shape
    H = s["H"]
    Dh = D // H
    h = _r(_layer_norm(x, blk["ln1_g"], blk["ln1_b"], s["eps"]), quant)
    qkv = _r(_mm(h, blk["w_qkv"], quant) + blk["b_qkv"], quant)
    q, k, v = (a.reshape(B, T, H, Dh) for a in jnp.split(qkv, 3, -1))
    if quant == "int8":
        q, k, v = _fq(q, -1), _fq(k, -1), _fq(v, -1)
    elif quant == "fp8":
        q, k, v = _f8(q), _f8(k), _f8(v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(Dh)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = _r(jax.nn.softmax(scores, -1), quant)
    if quant == "int8":
        probs = _fq(probs, -1)
    elif quant == "fp8":
        probs = _f8(probs)
    o = _r(jnp.einsum("bhqk,bkhd->bqhd", probs, v), quant).reshape(B, T, D)
    x = _r(x + _mm(o, blk["w_o"], quant) + blk["b_o"], quant)
    h = _r(_layer_norm(x, blk["ln2_g"], blk["ln2_b"], s["eps"]), quant)
    h = _r(_gelu_new(_mm(h, blk["w_up"], quant) + blk["b_up"]), quant)
    return _r(x + _mm(h, blk["w_down"], quant) + blk["b_down"], quant)


def hidden(params, tokens, cfg: dict, quant=None, remat: bool = False):
    """Final-LayerNorm output (B, T, D) for tokens (B, T)."""
    s = sizes(cfg)
    T = tokens.shape[1]
    x = _r(params["tok_embed"][tokens] + params["pos_embed"][:T], quant)
    body = lambda x, blk: (_block(x, blk, s, quant), None)  # noqa: E731
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _r(_layer_norm(x, params["lnf_g"], params["lnf_b"], s["eps"]),
              quant)


def logits_from(params, h, quant=None):
    return _mm(h, params["tok_embed"].T, quant)


def token_losses(params, tokens, cfg: dict, quant=None, remat=False):
    """Per-position next-token cross-entropy (B, T-1) of rows (B, T)."""
    h = hidden(params, tokens[:, :-1], cfg, quant, remat)
    logp = jax.nn.log_softmax(logits_from(params, h, quant), -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]


# ------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _served_gaps(params, rows, cfg_items, prompt_len, quant):
    """For rows (B, prompt_len + n): at each served position the
    reference's logits, reduced to what the comparison needs."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, rows[:, :-1], cfg, None)[:, prompt_len - 1:]
        ref = logits_from(params, h, None)            # (B, n, V) f32
        served = rows[:, prompt_len:]
        best = jnp.max(ref, -1)
        gap_served = best - jnp.take_along_axis(
            ref, served[..., None], -1)[..., 0]
        out = {"gap_served": gap_served}
        if quant is not None:
            hq = hidden(params, rows[:, :-1], cfg, quant)[:, prompt_len - 1:]
            low = jnp.argmax(logits_from(params, hq, quant), -1)
            out["gap_control"] = best - jnp.take_along_axis(
                ref, low[..., None], -1)[..., 0]
        return out


def served_gaps(params, rows: np.ndarray, cfg: dict, prompt_len: int,
                quant=None) -> dict:
    """rows (B, prompt_len + n) int: prompt then served tokens.

    Returns numpy arrays (B, n): `gap_served`, how far the served
    token's reference logit lies below the reference's best at its
    position; with `quant`, `gap_control`, the same for the token that
    the lower precision puts first there."""
    out = _served_gaps(params, jnp.asarray(rows, jnp.int32), _hashable(cfg),
                       int(prompt_len), quant)
    return {k: np.asarray(v) for k, v in out.items()}


# ------------------------------------------------------------ training

def _loss_sum(params, tokens, cfg_items, quant):
    cfg = dict(cfg_items)
    return jnp.sum(token_losses(params, tokens, cfg, quant, remat=True))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_grads(params, tokens, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_loss_sum)(params, tokens, cfg_items, quant)


@functools.partial(jax.jit, donate_argnums=(0,))
def _acc(total, part):
    return jax.tree.map(jnp.add, total, part)


def loss_and_grads(params, batch: np.ndarray, cfg: dict, quant=None,
                   rows_per_block: int = 4):
    """Mean next-token loss of batch (B, T+1) and its gradient, taken
    in blocks of rows so that float32 activations fit beside the state."""
    n_tok = batch.shape[0] * (batch.shape[1] - 1)
    total, grads = 0.0, None
    for i in range(0, batch.shape[0], rows_per_block):
        rows = jnp.asarray(batch[i:i + rows_per_block], jnp.int32)
        part, g = _block_grads(params, rows, _hashable(cfg), quant)
        total = total + part
        grads = g if grads is None else _acc(grads, g)
    scale = 1.0 / n_tok
    return total * scale, jax.tree.map(lambda g: g * scale, grads)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grads, mu, nu, t, lr, wd):
    b1, b2, eps = 0.9, 0.999, 1e-8

    def leaf(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v

    out = jax.tree.map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def adamw_step(params, grads, mu, nu, t: int, lr: float, wd: float):
    """AdamW as published (Loshchilov & Hutter): decoupled decay,
    bias-corrected moments, eps 1e-8 outside the root."""
    return _adamw(params, grads, mu, nu, jnp.float32(t), jnp.float32(lr),
                  jnp.float32(wd))


def _norms(tree) -> dict:
    """Traced: the norm of every leaf, a stacked block leaf giving one
    norm a layer.  c_attn is three matrices side by side, so q, k and v
    count as leaves of their own (a key's bias has no gradient)."""
    out = {}
    for name in ("tok_embed", "pos_embed", "lnf_g", "lnf_b"):
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    for name in BLOCK_LEAVES:
        a = tree["blocks"][name].astype(jnp.float32)
        parts = zip("qkv", jnp.split(a, 3, -1)) \
            if name in ("w_qkv", "b_qkv") else (("", a),)
        for tag, part in parts:
            out["blocks/" + name + ("." + tag if tag else "")] = jnp.sqrt(
                jnp.sum(part.reshape(part.shape[0], -1) ** 2, -1))
    return out


def _flat(norms: dict) -> dict:
    flat = {}
    for name, v in norms.items():
        v = np.asarray(v)
        if v.ndim == 0:
            flat[name] = float(v)
        else:
            for i, x in enumerate(v):
                flat[f"{name}/{i}"] = float(x)
    return flat


def leaf_norms(tree) -> dict:
    """{"blocks/w_qkv.k/3": norm, "tok_embed": norm, ...} of a pytree
    in the parameters' layout."""
    return _flat(jax.jit(_norms)(tree))


@functools.partial(jax.jit, static_argnums=(2,))
def _change_norms(params, key, cfg_items):
    start = _weights(key, cfg_items, "float32")
    return _norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y, params, start))


def change_norms(params, cfg: dict, seed: int) -> dict:
    """Per-leaf norm of `params` minus the seed's initial weights, which
    are drawn again inside the one program that reduces them: nothing
    the size of the model is kept."""
    return _flat(_change_norms(params, seed_key(seed), _hashable(cfg)))
