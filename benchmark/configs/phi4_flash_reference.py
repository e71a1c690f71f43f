"""Plain reference for the Phi-4-mini-flash configuration (SambaY), and its seeded weights.

The layer equations (ISSUE 31, Tentpole; the configuration's `assumed`
lists every value the public `config.json` does not carry).  l is the
0-based published layer index, and the kind of layer l is read from the
configuration's `published.layer_kinds`:

    x = x + mixer_l(LN1_l(x));   x = x + MLP_l(LN2_l(x))
    LN = LayerNorm with gain and bias, eps 1e-5
    MLP(h) = (silu(g) * u) W2,  [g, u] = h W1
    logits = LN_f(x_L) E^T            E the embedding (tied), no positions

`mamba`: [xm, z] = h W_in; xc_t = silu(sum_j w_j xm_{t-K+1+j} + b_c)
(zeros before t = 0); [dr, B_t, C_t] = xc W_x; dt = softplus(dr W_dt +
b_dt); A = -exp(A_log); s_t = exp(dt_t A) s_{t-1} + (dt_t xc_t) (x) B_t
(float32, s_{-1} = 0); y_t = s_t . C_t + D xc_t; out = (y silu(z))
W_out.  The LAST mamba layer's y is the memory m of the gated memory
units: `gmu`: out = (m silu(h W_1)) W_2, m of the same position.

`window`, `full`, `cross`: differential attention.  [q, k, v] = h W_qkv
+ b (`cross`: q = h W_q + b_q alone, k and v those of the `full`
layer).  For the query pair p < H / 2 on the K/V pair r = p // 2: q1 =
q[2p], q2 = q[2p + 1], k1 = k[2r], k2 = k[2r + 1], v_r = [v[2r], v[2r +
1]]; A1 = softmax(q1 k1^T / sqrt(d) + mask), A2 likewise; lam =
exp(lq1 . lk1) - exp(lq2 . lk2) + lam0, lam0 = 0.8 - 0.6 exp(-0.3 l);
o_p = RMSNorm_2d((A1 - lam A2) v_r) (1 - lam0); out = concat_p(o_p) W_o
+ b_o.  Key j is visible to query i iff 0 <= i - j, and in a `window`
layer also i - j < sliding_window.

Everything is straightforward `jax.numpy` in float32 at matmul precision
`highest`: one full forward over the whole row, no cache, no rings (a
banded mask), the scan as a `lax.scan` over positions, the queries a
block at a time only where a whole (T, T) array would not fit.  It
imports nothing of the program.  The weights are made here from the
seed, in the pytree layout that the program takes (stacked by kind;
`A_log` channels last): they are the benchmark's input, like the prompts.

Weights (`assumed.weights`): matrices N(0, 1 / fan_in); the embedding
N(0, 1 / hidden_size) (0.0198 a component at 2560), so that the tied
head spreads logits by about one (|LN_f(x)| = sqrt(hidden_size)) and h0
is small beside one sublayer's addition: the context, not the last
token, decides the next one; the B and C columns of W_x N(0, 9 /
fan_in), so that the scan's read-out s . C is of the size of the skip
D xc and a state that is wrong, or held in a lower precision, shows in
the logits (with 1 / fan_in it is a tenth of it); gains 1 + N(0,
0.02), biases N(0, 0.02), lambda vectors N(0, 0.1); Mamba-1's defaults
A_log = log(1 .. d_state), D = 1, dt bias = inverse softplus of
log-uniform [1e-3, 1e-1].  Drawn in float32, rounded once to bfloat16;
`dtype="float32"` widens those rounded values.  Each layer's leaves
have keys of their own: a layer can be made alone (`layer_weights`),
which is how the reference runs at the published widths (15.4 GB whole).

`quant` (`"bf16"`, `"int8"`, `"fp8"`) are the controls of
`gpt2_reference.py`: the same mathematics with every activation held in
bfloat16 and every matmul operand rounded.  The scan's state stays
float32 under all of them, as the configuration states it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
STACKS = {MAMBA: "mamba", WINDOW: "attn", FULL: "attn", GMU: "gmu",
          CROSS: "cross"}
QUERY_BLOCK = 256  # queries scored at a time where (T, T) would not fit


def sizes(cfg: dict) -> dict:
    mamba = cfg.get("mamba", {})
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    expand = int(mamba.get("expand", 2))
    kinds = list(cfg["published"]["layer_kinds"])
    if len(kinds) != int(cfg["num_hidden_layers"]):
        raise ValueError("published.layer_kinds does not list every layer")
    return {
        "D": D, "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]), "H": H,
        "G": int(cfg["num_key_value_heads"]), "d": D // H,
        "E": expand * D, "N": int(mamba.get("d_state", 16)),
        "K": int(mamba.get("d_conv", 4)), "R": math.ceil(D / 16),
        "W": int(cfg["sliding_window"]), "eps": float(cfg["layer_norm_eps"]),
        "kinds": kinds,
    }


def _hashable(cfg: dict) -> tuple:
    s = sizes(cfg)
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(s.items()))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


# --------------------------------------------------------------- weights

def layer_shapes(s: dict, kind: str) -> dict:
    D, F, E, d = s["D"], s["F"], s["E"], s["d"]
    Hd, Gd = s["H"] * d, s["G"] * d
    out = {"ln1_g": (D,), "ln1_b": (D,), "ln2_g": (D,), "ln2_b": (D,),
           "w1": (D, 2 * F), "w2": (F, D)}
    diff = {"w_o": (Hd, D), "b_o": (D,), "lq1": (d,), "lk1": (d,),
            "lq2": (d,), "lk2": (d,), "sub_g": (2 * d,)}
    if kind == MAMBA:
        out.update({"w_in": (D, 2 * E), "conv_w": (s["K"], E),
                    "conv_b": (E,), "w_x": (E, s["R"] + 2 * s["N"]),
                    "w_dt": (s["R"], E), "b_dt": (E,), "a_log": (s["N"], E),
                    "d_skip": (E,), "w_out": (E, D)})
    elif kind in (WINDOW, FULL):
        out.update({"w_qkv": (D, Hd + 2 * Gd), "b_qkv": (Hd + 2 * Gd,),
                    **diff})
    elif kind == GMU:
        out.update({"wg1": (D, E), "wg2": (E, D)})
    elif kind == CROSS:
        out.update({"w_q": (D, Hd), "b_q": (Hd,), **diff})
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return out


_LEAF_IDS = {n: i for i, n in enumerate(sorted(
    ["ln1_g", "ln1_b", "ln2_g", "ln2_b", "w1", "w2", "w_in", "conv_w",
     "conv_b", "w_x", "w_dt", "b_dt", "a_log", "d_skip", "w_out", "w_qkv",
     "b_qkv", "w_o", "b_o", "lq1", "lk1", "lq2", "lk2", "sub_g", "wg1",
     "wg2", "w_q", "b_q", "embed", "lnf_g", "lnf_b"]))}


def _leaf(key, name, shape, dtype, layer=0, rank=0):
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS[name]), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if name == "w_x":  # the B and C columns: see `assumed.weights`
        z = z * jnp.where(jnp.arange(shape[1]) < rank, 1.0, 3.0)
    if name == "embed":
        z = z / np.sqrt(shape[1])
    elif name == "a_log":
        z = jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    elif name == "d_skip":
        z = jnp.ones(shape, jnp.float32)
    elif name == "b_dt":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                     * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        z = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    elif name.startswith("w") or name == "conv_w":
        z = z / np.sqrt(shape[0])
    elif name.endswith("_g"):
        z = 1.0 + 0.02 * z
    elif name in ("lq1", "lk1", "lq2", "lk2"):
        z = 0.1 * z
    else:
        z = 0.02 * z
    return z.astype(jnp.bfloat16).astype(jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layer_weights(key, cfg_items, kind, layer, dtype):
    s = dict(cfg_items)
    return {n: _leaf(key, n, shape, dtype, layer + 1, s["R"])
            for n, shape in layer_shapes(s, kind).items()}


def layer_weights(cfg: dict, seed: int, layer: int, dtype: str = "float32"):
    """The leaves of the layer with published index `layer`."""
    items = _hashable(cfg)
    kind = dict(items)["kinds"][layer]
    return _layer_weights(seed_key(seed), items, kind, int(layer), dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _top_weight(key, cfg_items, name, dtype):
    s = dict(cfg_items)
    shape = {"embed": (s["V"], s["D"]), "lnf_g": (s["D"],),
             "lnf_b": (s["D"],)}[name]
    return _leaf(key, name, shape, dtype)


def top_weight(cfg: dict, seed: int, name: str, dtype: str = "float32"):
    return _top_weight(seed_key(seed), _hashable(cfg), name, dtype)


def stack_of(cfg: dict, name: str) -> list:
    """Published indices of the layers in the stack `name` of the
    program's parameters, in its order (`attn`: the window layers, then
    the full one)."""
    kinds = sizes(cfg)["kinds"]
    order = [k for k in (MAMBA, WINDOW, FULL, GMU, CROSS)
             if STACKS[k] == name]
    return [l for kind in order for l, k in enumerate(kinds) if k == kind]


def make_weights(cfg: dict, seed: int, dtype: str = "float32"):
    """Parameters on the device, stacked by kind as the program takes
    them: drawn in float32, rounded once to bfloat16, held in `dtype`."""
    out = {n: top_weight(cfg, seed, n, dtype)
           for n in ("embed", "lnf_g", "lnf_b")}
    for name in ("mamba", "attn", "gmu", "cross"):
        layers = [layer_weights(cfg, seed, l, dtype)
                  for l in stack_of(cfg, name)]
        out[name] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return out


def layer_of(params: dict, cfg: dict, layer: int) -> dict:
    """The layer with published index `layer`, out of stacked parameters."""
    name = STACKS[sizes(cfg)["kinds"][layer]]
    i = stack_of(cfg, name).index(layer)
    return jax.tree.map(lambda a: a[i], params[name])


# -------------------------------------------------------------- controls

def _fq(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _r(x, quant):
    """Under a control every activation is held in bfloat16."""
    if quant is None:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _f8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _operand(x, quant, axis=-1):
    """A matmul operand under the control: rounded along `axis`."""
    if quant is None:
        return x
    if quant == "int8":
        return _fq(x, axis)
    if quant == "fp8":
        return _f8(x)
    if quant == "bf16":
        return _r(x, quant)
    raise ValueError(f"unknown control precision {quant!r}")


def _mm(x, w, quant):
    """x (..., K) @ w (K, N)."""
    return _r(_operand(x, quant, -1) @ _operand(w, quant, 0), quant)


# ------------------------------------------------------------------ math

def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _mlp(x, w, s, quant):
    h = _r(_ln(x, w["ln2_g"], w["ln2_b"], s["eps"]), quant)
    gu = _mm(h, w["w1"], quant)
    a = _r(jax.nn.silu(gu[:, :s["F"]]) * gu[:, s["F"]:], quant)
    return _r(x + _mm(a, w["w2"], quant), quant)


def mamba_mixer(h, w, s, quant=None):
    """h (T, D) = LN1(x) -> (the mixer's output (T, D), y (T, E) before
    the gate): the convolution, then the scan a position at a time."""
    T, E, N, K, R = h.shape[0], s["E"], s["N"], s["K"], s["R"]
    xz = _mm(h, w["w_in"], quant)
    xm, z = xz[:, :E], xz[:, E:]
    padded = jnp.concatenate([jnp.zeros((K - 1, E), xm.dtype), xm])
    conv = sum(w["conv_w"][j] * padded[j:j + T] for j in range(K))
    xc = _r(jax.nn.silu(conv + w["conv_b"]), quant)
    dbc = _mm(xc, w["w_x"], quant)
    dt = jax.nn.softplus(_mm(dbc[:, :R], w["w_dt"], quant) + w["b_dt"])
    B, C = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(w["a_log"].T)  # (E, N)

    def step(state, inputs):
        dt_t, x_t, b_t, c_t = inputs
        state = jnp.exp(dt_t[:, None] * A) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((E, N), jnp.float32),
                        (dt, xc, B, C))
    y = _r(y + w["d_skip"] * xc, quant)
    return _mm(_r(y * jax.nn.silu(z), quant), w["w_out"], quant), y


def lam0(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_attention(q, k, v, w, s, layer, window, quant=None):
    """q (T, H, d), k and v (T, G, d) -> concat_p(o_p) (T, H d): key j
    visible to query i iff 0 <= i - j (< window where one is given)."""
    T, d = q.shape[0], s["d"]
    pairs = s["H"] // 2
    l0 = lam0(layer)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + l0
    q, k, v = _operand(q, quant), _operand(k, quant), _operand(v, quant)

    def probs(qh, kh, mask):
        sc = (qh @ kh.T) / np.sqrt(d)
        return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)

    def block(args):
        qb, i = args  # (Q, H, d), (Q,)
        lag = i[:, None] - jnp.arange(T)[None, :]
        mask = lag >= 0
        if window is not None:
            mask &= lag < window
        outs = []
        for p in range(pairs):
            r = p // 2
            a1 = probs(qb[:, 2 * p], k[:, 2 * r], mask)
            a2 = probs(qb[:, 2 * p + 1], k[:, 2 * r + 1], mask)
            vr = jnp.concatenate([v[:, 2 * r], v[:, 2 * r + 1]], -1)
            o = _operand(_r(a1 - lam * a2, quant), quant) @ vr
            o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + s["eps"]) \
                * w["sub_g"] * (1.0 - l0)
            outs.append(o)
        return _r(jnp.concatenate(outs, -1), quant)

    idx = jnp.arange(T)
    if T <= QUERY_BLOCK:
        return block((q, idx))
    n = -(-T // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - T
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (n, QUERY_BLOCK) + q.shape[1:])
    ids = jnp.pad(idx, (0, pad), constant_values=T - 1).reshape(
        n, QUERY_BLOCK)
    out = jax.lax.map(block, (qs, ids))
    return out.reshape(n * QUERY_BLOCK, -1)[:T]


def layer(x, w, s, kind, index, memory, quant=None):
    """x (T, D) through the layer `index` of kind `kind`.  `memory` is
    {"m": y of the last mamba layer so far, "k", "v": the full layer's};
    returns (x, memory)."""
    T, H, G, d = x.shape[0], s["H"], s["G"], s["d"]
    h = _r(_ln(x, w["ln1_g"], w["ln1_b"], s["eps"]), quant)
    if kind == MAMBA:
        out, y = mamba_mixer(h, w, s, quant)
        memory = dict(memory, m=y)
    elif kind == GMU:
        gate = _r(jax.nn.silu(_mm(h, w["wg1"], quant)), quant)
        out = _mm(_r(memory["m"] * gate, quant), w["wg2"], quant)
    else:
        if kind == CROSS:
            q = _r(_mm(h, w["w_q"], quant) + w["b_q"], quant)
            k, v = memory["k"], memory["v"]
        else:
            qkv = _r(_mm(h, w["w_qkv"], quant) + w["b_qkv"], quant)
            q = qkv[:, :H * d]
            k = qkv[:, H * d:(H + G) * d].reshape(T, G, d)
            v = qkv[:, (H + G) * d:].reshape(T, G, d)
            if kind == FULL:
                memory = dict(memory, k=k, v=v)
        o = diff_attention(q.reshape(T, H, d), k, v, w, s, index,
                           s["W"] if kind == WINDOW else None, quant)
        out = _r(_mm(o, w["w_o"], quant) + w["b_o"], quant)
    return _mlp(_r(x + out, quant), w, s, quant), memory


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _layer(x, w, memory, index, cfg_items, kind, quant):
    with jax.default_matmul_precision("highest"):
        return layer(x, w, dict(cfg_items), kind, index, memory, quant)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(embed, tokens, quant):
    return _r(embed[tokens], quant)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(x, g, b, embed, cfg_items, quant):
    s = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _r(_ln(x, g, b, s["eps"]), quant)
        return _mm(h, embed.T, quant)


def _hidden(get, rows, cfg: dict, quant, keep_from: int = 0) -> list:
    """The last layer's output (T - keep_from, D) of each row of rows
    (B, T): `get(name)` gives a top-level leaf, `get(l)` layer l's
    leaves; one layer of weights is held at a time, every row passes
    it, then the next."""
    items = _hashable(cfg)
    s = dict(items)
    embed = get("embed")
    xs = [_embed(embed, jnp.asarray(r, jnp.int32), quant) for r in rows]
    del embed
    memories = [{} for _ in xs]
    for l, kind in enumerate(s["kinds"]):
        w = get(l)
        for i, x in enumerate(xs):
            xs[i], memories[i] = _layer(x, w, memories[i], l, items, kind,
                                        quant)
        del w
    return [x[keep_from:] for x in xs]


def _head(get, cfg: dict, quant):
    """x (T, D) -> logits (T, V) under the tied head."""
    items = _hashable(cfg)
    g, b, embed = get("lnf_g"), get("lnf_b"), get("embed")
    return lambda x: _logits(x, g, b, embed, items, quant)


def _forward(get, rows, cfg: dict, quant, keep_from: int = 0):
    """Logits (B, T - keep_from, V) of rows (B, T)."""
    head = _head(get, cfg, quant)
    return jnp.stack([head(x)
                      for x in _hidden(get, rows, cfg, quant, keep_from)])


def _getter(params, cfg):
    return lambda name: (params[name] if isinstance(name, str)
                         else layer_of(params, cfg, name))


def logits(params, tokens, cfg: dict, quant=None):
    """Full-forward logits (B, T, V) float32 of tokens (B, T)."""
    return _forward(_getter(params, cfg), np.asarray(tokens), cfg, quant)


# ------------------------------------------------------------- serving

def _gaps(get, rows, cfg, prompt_len, quant):
    """A row's logits (one (T, V) array in float32: 0.8 GB a thousand
    positions at the published vocabulary) are made, compared and
    dropped before the next row's."""
    rows = np.asarray(rows)
    at = prompt_len - 1
    hidden = _hidden(get, rows[:, :-1], cfg, None, at)
    low = _hidden(get, rows[:, :-1], cfg, quant, at) \
        if quant is not None else None
    head = _head(get, cfg, None)
    out = {"gap_served": []}
    if quant is not None:
        out["gap_control"], head_low = [], _head(get, cfg, quant)
    for i, x in enumerate(hidden):
        ref = head(x)
        best = jnp.max(ref, -1)
        pick = lambda ids: np.asarray(best - jnp.take_along_axis(  # noqa: E731
            ref, ids[:, None], -1)[:, 0])
        out["gap_served"].append(
            pick(jnp.asarray(rows[i, prompt_len:], jnp.int32)))
        if quant is not None:
            out["gap_control"].append(pick(jnp.argmax(head_low(low[i]), -1)))
    return {k: np.stack(v) for k, v in out.items()}


def served_gaps(params, rows: np.ndarray, cfg: dict, prompt_len: int,
                quant=None) -> dict:
    """rows (B, prompt_len + n) int: prompt then served tokens.

    Returns numpy arrays (B, n): `gap_served`, how far the served
    token's reference logit lies below the reference's best at its
    position; with `quant`, `gap_control`, the same for the token that
    the lower precision puts first there."""
    return _gaps(_getter(params, cfg), rows, cfg, int(prompt_len), quant)


def served_gaps_from_seed(cfg: dict, seed: int, rows: np.ndarray,
                          prompt_len: int, quant=None) -> dict:
    """`served_gaps` with the float32 weights made from the seed one
    layer at a time: at the published widths they are 15.4 GB whole."""
    def get(name):
        if isinstance(name, str):
            return top_weight(cfg, seed, name, "float32")
        return layer_weights(cfg, seed, name, "float32")

    return _gaps(get, rows, cfg, int(prompt_len), quant)
