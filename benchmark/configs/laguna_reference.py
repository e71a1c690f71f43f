"""Plain reference for the Laguna configuration, and its seeded weights.

The layer equations (ISSUE 37, Tentpole; the configuration's `assumed`
lists every value the public `config.json` does not carry).  T positions,
layer l of kind full or window, RMS = RMSNorm with gain, eps
`rms_norm_eps`:

    h = x + Attn_l(RMS1_l(x));   x' = h + FFN_l(RMS2_l(h))
    logits = RMS_f(x_L) W_head                      (untied, no bias)
    FFN_l = SwiGLU(intermediate_size) where mlp_layer_types says dense, else MoE_l
    SwiGLU(u; W_gu, W_d) = (silu(g) * v) W_d,  [g, v] = u W_gu

    Attn_l(u), position t, H_l query heads over G K/V heads of d:
      q_i = u W_q,i;  k_g = u W_k,g;  v_g = u W_v,g;  no bias, no QK-norm
      full layers:   dims 0..r-1 of q_i and k_g rotated at t (r = d partial_rotary_factor),
                     YaRN frequencies w_j (j < r/2) from rope_parameters.full_attention,
                     cos and sin times attention_factor; dims r.. pass
      window layers: all d dims rotated at t, f_j = theta^(-2j/d), no scaling
      rope: plane j pairs dimension j with j + r/2 (`assumed.rope_pairing`)
      score_i(t, s) = q_i,t . k_g(i),s / sqrt(d),  g(i) = floor(i / (H_l / G))
      visible: s <= t; window layers also t - s < W (`sliding_window`)
      o_i = sum_s softmax_s(score_i) v_g(i),s;  o_i <- o_i * sigmoid(u . w_gate,i)
      out = concat_i(o_i) W_o

    MoE(u), one token:
      p = softmax(u W_r)  (router_width), float32
      chosen = top_k(p, k);  g_e = p_e / sum_{e' in chosen} p_e' * moe_routed_scaling_factor
      MoE(u) = SwiGLU(u; shared) + sum_{e in chosen, e held here} g_e SwiGLU(u; expert e)

The normalisation runs over all k chosen, held here or not; what the
experts held elsewhere would add is left out (`experts_held`: the chip's
share of the deployment the configuration states), and that partial sum
goes on to the next layer.  An expert's matrices are drawn from (seed,
layer, expert id) alone, so every share of a layer, and the uncut layer,
hold the same expert e.

Everything is straightforward `jax.numpy` in float32 at matmul precision
`highest`: one full forward over the whole row, no cache, no ring (every
position's K and V are kept whole and the window is a mask over the
whole sequence), the routed sum a loop over the experts held with g_e
zero where not chosen, the queries a block at a time only where a whole
(H, T, T) array would not fit.  It imports nothing of the program.  The
weights are made here from the seed, in the pytree layout that the
program takes: they are the benchmark's input, like the prompts.

Weights (`assumed.weights`): matrices N(0, 1 / fan_in); embedding and
head N(0, 1 / hidden_size); gains 1 + N(0, 0.02).  Drawn in float32,
rounded once to bfloat16; `dtype="float32"` widens those rounded values.
A layer can be made alone (`layer_weights`), which is how the reference
runs at the published widths.

`quant` (`"bf16"`, `"int8"`, `"fp8"`) are the controls of
`gpt2_reference.py`: the same mathematics with every activation held in
bfloat16 and every matmul operand rounded.  The router's product,
softmax and top-k stay float32 under all of them, as the configuration
states them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
QUERY_BLOCK = 256  # queries scored at a time where (H, T, T) would not fit


def sizes(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    n_held = int(cfg["num_experts"])
    ids = (cfg.get("experts_held") or {}).get("ids")
    rope = cfg.get("rope_parameters") or {}
    full, window = rope.get(FULL, {}), rope.get(WINDOW, {})
    d = int(cfg["head_dim"])
    yarn = full.get("rope_type") == "yarn"
    return {
        "D": int(cfg["hidden_size"]), "d": d,
        "G": int(cfg["num_key_value_heads"]),
        "kinds": tuple(cfg["layer_types"][:L]),
        "mlp": tuple(cfg["mlp_layer_types"][:L]),
        "heads": tuple(int(h) for h in
                       cfg["num_attention_heads_per_layer"][:L]),
        "F0": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["shared_expert_intermediate_size"]),
        "V": int(cfg["vocab_size"]), "L": L,
        "E": int(cfg.get("router_width", n_held)),
        "held": tuple(range(n_held)) if ids is None else tuple(
            int(e) for e in ids),
        "k": int(cfg["num_experts_per_tok"]),
        "route_scale": float(cfg.get("moe_routed_scaling_factor", 1.0)),
        "eps": float(cfg.get("rms_norm_eps", 1e-6)),
        "W": int(cfg["sliding_window"]),
        "full_theta": float(full.get("rope_theta", 10000.0)),
        "full_rot": int(round(d * float(full.get("partial_rotary_factor",
                                                 1.0)))),
        "factor": float(full.get("factor", 1.0)) if yarn else 1.0,
        "L0": int(full.get("original_max_position_embeddings",
                           cfg["max_position_embeddings"])),
        "beta_fast": float(full.get("beta_fast", 32)),
        "beta_slow": float(full.get("beta_slow", 1)),
        "attention_factor": float(full.get("attention_factor", 1.0))
        if yarn else 1.0,
        "window_theta": float(window.get("rope_theta", 10000.0)),
        "window_rot": int(round(d * float(window.get(
            "partial_rotary_factor", 1.0)))),
    }


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted(sizes(cfg).items()))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


# --------------------------------------------------------------- weights

ATTN_LEAVES = ("ln1_g", "ln2_g", "w_q", "w_k", "w_v", "w_gate", "w_o")
_LEAF_IDS = {n: i for i, n in enumerate(sorted(
    ATTN_LEAVES + ("w_gu", "w_d", "w_r", "sh_gu", "sh_d", "ex_gu", "ex_d",
                   "embed", "head", "lnf_g")))}


def layer_shapes(s: dict, layer: int) -> dict:
    """Layer `layer`'s leaves; an expert's two matrices stand once
    (`ex_gu`, `ex_d`) and are drawn for each expert held."""
    D, d, G, H = s["D"], s["d"], s["G"], s["heads"][layer]
    out = {"ln1_g": (D,), "ln2_g": (D,), "w_q": (D, H * d),
           "w_k": (D, G * d), "w_v": (D, G * d), "w_gate": (D, H),
           "w_o": (H * d, D)}
    if s["mlp"][layer] == DENSE:
        out.update({"w_gu": (D, 2 * s["F0"]), "w_d": (s["F0"], D)})
    else:
        out.update({"w_r": (D, s["E"]), "sh_gu": (D, 2 * s["Fs"]),
                    "sh_d": (s["Fs"], D), "ex_gu": (D, 2 * s["Fe"]),
                    "ex_d": (s["Fe"], D)})
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, name, shape, dtype, layer=0, expert=0):
    """One leaf from (seed, name, layer, expert id): `layer` and
    `expert` count from 1, 0 for a leaf that has none."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, _LEAF_IDS[name]), layer), expert)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in ("embed", "head"):
        z = z / np.sqrt(shape[1])
    elif name.endswith("_g"):
        z = 1.0 + 0.02 * z
    else:
        z = z / np.sqrt(shape[0])
    return z.astype(jnp.bfloat16).astype(jnp.dtype(dtype))


def layer_weights(cfg: dict, seed: int, layer: int, dtype: str = "float32"):
    """The leaves of layer `layer`; `ex_gu`, `ex_d` stacked over the
    experts held, each drawn by its id."""
    s = sizes(cfg)
    key, out = seed_key(seed), {}
    for name, shape in layer_shapes(s, layer).items():
        if name.startswith("ex_"):
            out[name] = jnp.stack([
                _leaf(key, name, shape, dtype, layer + 1, e + 1)
                for e in s["held"]])
        else:
            out[name] = _leaf(key, name, shape, dtype, layer + 1, 0)
    return out


def top_weight(cfg: dict, seed: int, name: str, dtype: str = "float32"):
    s = sizes(cfg)
    shape = {"embed": (s["V"], s["D"]), "head": (s["V"], s["D"]),
             "lnf_g": (s["D"],)}[name]
    return _leaf(seed_key(seed), name, shape, dtype)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(buf, part, at):
    return jax.lax.dynamic_update_slice(
        buf, part[(None,) * (buf.ndim - part.ndim)],
        tuple(at) + (0,) * part.ndim)


def _group(s: dict, layer: int, name: str) -> tuple:
    """(the program's group of leaf `name` of layer `layer`, the layer's
    index in that group's stack)."""
    if name in ATTN_LEAVES:
        kind = s["kinds"][layer]
        group, kinds = ("full" if kind == FULL else "window"), s["kinds"]
    else:
        kind = s["mlp"][layer]
        group, kinds = ("dense" if kind == DENSE else "moe"), s["mlp"]
    return group, sum(1 for k in kinds[:layer] if k == kind)


def make_weights(cfg: dict, seed: int, dtype: str = "float32"):
    """Parameters on the device, stacked as the program takes them
    (`full` and `window` attention by kind, `dense` and `moe`
    feed-forwards by kind, an expert layer's matrices over the experts
    held): drawn in float32, rounded once to bfloat16, held in `dtype`.
    Each stack is filled in place a leaf at a time, so that no more than
    one leaf in float32 stands beside the parameters."""
    s = sizes(cfg)
    key = seed_key(seed)
    dt = jnp.dtype(dtype)
    out = {n: top_weight(cfg, seed, n, dtype)
           for n in ("embed", "head", "lnf_g")}
    shapes = {}
    for layer in range(s["L"]):
        for name, shape in layer_shapes(s, layer).items():
            group, _ = _group(s, layer, name)
            per = (len(s["held"]),) + shape if name.startswith("ex_") \
                else shape
            n = shapes.get((group, name), (0, per))[0]
            shapes[(group, name)] = (n + 1, per)
    bufs = {(g, n): jnp.zeros((count,) + per, dt)
            for (g, n), (count, per) in shapes.items()}
    for layer in range(s["L"]):
        for name, shape in layer_shapes(s, layer).items():
            group, i = _group(s, layer, name)
            buf = bufs[(group, name)]
            if name.startswith("ex_"):
                for n, e in enumerate(s["held"]):
                    buf = _put(buf, _leaf(key, name, shape, dtype, layer + 1,
                                          e + 1), np.asarray([i, n], np.int32))
            else:
                buf = _put(buf, _leaf(key, name, shape, dtype, layer + 1, 0),
                           np.asarray([i], np.int32))
            bufs[(group, name)] = buf
    for group in ("full", "window", "dense", "moe"):
        out[group] = {n: b for (g, n), b in bufs.items() if g == group}
    return out


def layer_of(params: dict, cfg: dict, layer: int) -> dict:
    """Layer `layer` out of stacked parameters."""
    s = sizes(cfg)
    out = {}
    for name in layer_shapes(s, layer):
        group, i = _group(s, layer, name)
        out[name] = params[group][name][i]
    return out


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

def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu(u, w_gu, w_d, quant):
    gu = _mm(u, w_gu, quant)
    F = w_d.shape[0]
    return _mm(_r(jax.nn.silu(gu[:, :F]) * gu[:, F:], quant), w_d, quant)


def yarn_freqs(s: dict) -> np.ndarray:
    """A full layer's w_j, j < r / 2, float64: f_j = theta^(-2j/r); the
    correction range [low, high] = [floor, ceil] of r ln(L0 / (beta 2
    pi)) / (2 ln theta) at beta_fast, beta_slow; ramp_j = clip((j - low)
    / (high - low), 0, 1); w_j = f_j / factor ramp_j + f_j (1 - ramp_j)."""
    r, theta = s["full_rot"], s["full_theta"]
    j = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / r)
    if s["factor"] <= 1:
        return f
    at = lambda beta: r * math.log(  # noqa: E731
        s["L0"] / (beta * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(at(s["beta_fast"])), 0)
    high = min(math.ceil(at(s["beta_slow"])), r - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / s["factor"] * ramp + f * (1.0 - ramp)


def rope(x, t, s, kind):
    """x (T, heads, d) rotated at positions t (T,) with kind's scheme."""
    if kind == FULL:
        freqs, scale = yarn_freqs(s), s["attention_factor"]
    else:
        r = s["window_rot"]
        freqs = s["window_theta"] ** (-2.0 * np.arange(r // 2) / r)
        scale = 1.0
    r = 2 * len(freqs)
    ang = t.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           -1)


def attention(u, w, s, kind, quant=None):
    """u (T, D) = RMS1(x) -> Attn(u) (T, D): every position's K and V
    kept whole, a softmax a head over the positions its kind sees."""
    T, G, d = u.shape[0], s["G"], s["d"]
    H = w["w_gate"].shape[1]
    g = H // G
    t = jnp.arange(T)
    q = _r(rope(_mm(u, w["w_q"], quant).reshape(T, H, d), t, s, kind), quant)
    k = _r(rope(_mm(u, w["w_k"], quant).reshape(T, G, d), t, s, kind), quant)
    v = _mm(u, w["w_v"], quant).reshape(T, G, d)
    gate = jax.nn.sigmoid(_mm(u, w["w_gate"], quant))  # (T, H)
    qg, kk, vv = (_operand(a, quant) for a in (q.reshape(T, G, g, d), k, v))

    def block(args):
        qb, i = args  # (Q, G, g, d), (Q,)
        sc = jnp.einsum("qgjd,kgd->gjqk", qb, kk) / math.sqrt(d)
        seen = t[None, :] <= i[:, None]
        if kind == WINDOW:
            seen = seen & (i[:, None] - t[None, :] < s["W"])
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("gjqk,kgd->qgjd", _operand(_r(p, quant), quant),
                          vv)

    if T <= QUERY_BLOCK:
        o = block((qg, t))
    else:
        n = -(-T // QUERY_BLOCK)
        pad = n * QUERY_BLOCK - T
        cut = jnp.pad(qg, ((0, pad),) + ((0, 0),) * 3).reshape(
            (n, QUERY_BLOCK) + qg.shape[1:])
        ids = jnp.pad(t, (0, pad), constant_values=T - 1).reshape(
            n, QUERY_BLOCK)
        o = jax.lax.map(block, (cut, ids)).reshape(
            (n * QUERY_BLOCK,) + qg.shape[1:])[:T]
    o = _r(o.reshape(T, H, d) * gate[:, :, None], quant)
    return _mm(o.reshape(T, H * d), w["w_o"], quant)


def route(u, w, s):
    """u (T, D) -> (chosen (T, k) expert ids, g (T, k)), float32."""
    p = jax.nn.softmax(u @ w["w_r"], -1)
    g, chosen = jax.lax.top_k(p, s["k"])
    return chosen, g / jnp.sum(g, -1, keepdims=True) * s["route_scale"]


def moe_parts(u, w, s, quant=None):
    """u (T, D) = RMS2(h) -> (the shared expert's output, the part of
    the routed sum that the experts held here give), each (T, D)."""
    chosen, g = route(u, w, s)
    shared = _swiglu(u, w["sh_gu"], w["sh_d"], quant)
    held = jnp.asarray(s["held"], jnp.int32)

    def one(routed, n):
        g_e = jnp.sum(jnp.where(chosen == held[n], g, 0.0), -1,
                      keepdims=True)
        return routed + g_e * _swiglu(u, w["ex_gu"][n], w["ex_d"][n],
                                      quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(shared),
                             jnp.arange(len(s["held"])))
    return shared, _r(routed, quant)


def layer(x, w, s, kind, mlp, quant=None):
    """x (T, D) through one layer of attention kind `kind` and
    feed-forward kind `mlp`."""
    u = _r(_rms(x, w["ln1_g"], s["eps"]), quant)
    h = _r(x + attention(u, w, s, kind, quant), quant)
    u = _r(_rms(h, w["ln2_g"], s["eps"]), quant)
    if mlp == DENSE:
        return _r(h + _swiglu(u, w["w_gu"], w["w_d"], quant), quant)
    shared, routed = moe_parts(u, w, s, quant)
    return _r(h + shared + routed, quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, cfg_items, kind, mlp, quant):
    with jax.default_matmul_precision("highest"):
        return layer(x, w, dict(cfg_items), kind, mlp, quant)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(embed, tokens, quant):
    return _r(embed[tokens], quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(x, g, head, cfg_items, quant):
    s = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return _mm(_r(_rms(x, g, s["eps"]), quant), head.T, quant)


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
    for l in range(s["L"]):
        w = get(l)
        for i, x in enumerate(xs):
            xs[i] = _layer(x, w, items, s["kinds"][l], s["mlp"][l], quant)
        del w
    return [x[keep_from:] for x in xs]


def _head(get, cfg: dict, quant):
    items = _hashable(cfg)
    g, head = get("lnf_g"), get("head")
    return lambda x: _logits(x, g, head, items, quant)


def _getter(params, cfg):
    return lambda name: (params[name] if isinstance(name, str)
                         else layer_of(params, cfg, name))


def logits(params, tokens, cfg: dict, quant=None):
    """Full-forward logits (B, T, V) float32 of tokens (B, T)."""
    get = _getter(params, cfg)
    head = _head(get, cfg, quant)
    return jnp.stack([head(x) for x in _hidden(
        get, np.asarray(tokens), cfg, quant)])


# ------------------------------------------------------------- serving

def _gaps(get, rows, cfg, prompt_len, quant):
    """A row's logits are made, compared and dropped before the next
    row's."""
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
    layer at a time: at the published widths an expert layer of this
    chip's share is 5.1 GB in float32."""
    def get(name):
        if isinstance(name, str):
            return top_weight(cfg, seed, name, "float32")
        return layer_weights(cfg, seed, name, "float32")

    return _gaps(get, rows, cfg, int(prompt_len), quant)
