"""Plain reference for the Kimi-K2 configuration (the DeepSeek-V3 block), and its seeded weights.

The layer equations (ISSUE 33, Tentpole; the configuration's `assumed`
lists every value the public `config.json` does not carry).  T positions,
layer l, RMS = RMSNorm with gain, eps `rms_norm_eps`:

    h = x + Attn_l(RMS1_l(x));   x' = h + FFN_l(RMS2_l(h))
    logits = RMS_f(x_L) W_head                      (untied, no bias)
    FFN_l = SwiGLU(intermediate_size) for l < first_k_dense_replace, else MoE_l
    SwiGLU(u; W_gu, W_d) = (silu(g) * v) W_d,  [g, v] = u W_gu

    Attn(u), position t:
      c_q = RMS_q(u W_qa);  [q_n, q_r]_i = c_q W_qb, head i   (d_n + d_r each)
      [c, k_r] = u W_kva;  c = RMS_kv(c);  k_r = rope_t(k_r)  (ONE key a position)
      q_r,i = rope_t(q_r,i);  [k_n,i, v_i]_s = c_s W_kvb, head i, every position s
      score_i(t, s) = (q_n,i . k_n,i,s + q_r,i . k_r,s) sigma,  s <= t
      sigma = (d_n + d_r)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
      o_i = sum_s softmax_s(score_i)(t, s) v_i,s;  out = concat_i(o_i) W_o

    rope (YaRN): f_j = theta^(-2j/d_r), j < d_r / 2; the correction range
      [low, high] = [floor, ceil] of d_r ln(L0 / (beta 2 pi)) / (2 ln theta)
      at beta_fast, beta_slow; ramp_j = clip((j - low) / (high - low), 0, 1);
      w_j = f_j / factor ramp_j + f_j (1 - ramp_j); angle = t w_j; plane j
      pairs dimension j with j + d_r / 2 (`assumed.rope_pairing`)

    MoE(u), one token:
      s = sigmoid(u W_r)  (router_width), float32
      choose = top_k(s + b, k);  g_e = s_e / (sum_{e' in choose} s_e' + 1e-20) * routed_scaling_factor
      MoE(u) = SwiGLU(u; shared) + sum_{e in choose, e held here} g_e SwiGLU(u; expert e)

The normalisation runs over all k chosen, held here or not; what the
experts held elsewhere would add is left out (`experts_held`: the chip's
share of the deployment the configuration states), and that partial sum
goes on to the next layer.  Given the same `experts_held` this is the
same share of the same model as the program's: an expert's matrices are
drawn from (seed, layer, expert id) alone, so every share of a layer,
and the uncut layer, hold the same expert e.

Everything is straightforward `jax.numpy` in float32 at matmul precision
`highest`: one full forward over the whole row, no cache, no absorbed
form (K and V are expanded at every position), the routed sum a plain
loop over the experts held with g_e zero where not chosen, the queries
a block at a time only where a whole (H, T, T) array would not fit.  It
imports nothing of the program.  The weights are made here from the
seed, in the pytree layout that the program takes: they are the
benchmark's input, like the prompts.

Weights (`assumed.weights`): matrices N(0, 1 / fan_in); embedding and
head N(0, 1 / hidden_size), so that logits spread by about one over the
vocabulary's slice and h0 is small beside one sublayer's addition;
gains 1 + N(0, 0.02); the selection bias b N(0, 0.05^2) (zeros would
leave "b chooses, it does not weigh" untested; a file may state another
deviation under `selection_bias_std`, as the toy size does).  Drawn in float32,
rounded once to bfloat16; `dtype="float32"` widens those rounded values.
A layer can be made alone (`layer_weights`), which is how the reference
runs at the published widths (16.7 GB whole in float32).

`quant` (`"bf16"`, `"int8"`, `"fp8"`) are the controls of
`gpt2_reference.py`: the same mathematics with every activation held in
bfloat16 and every matmul operand rounded.  The router's product,
sigmoid, bias and top-k stay float32 under all of them, as the
configuration states them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DENSE, MOE = "dense", "moe"
QUERY_BLOCK = 256  # queries scored at a time where (H, T, T) would not fit


def sizes(cfg: dict) -> dict:
    rope = dict(cfg.get("rope_scaling") or {})
    n_held = int(cfg["n_routed_experts"])
    ids = (cfg.get("experts_held") or {}).get("ids")
    L, Ld = int(cfg["num_hidden_layers"]), int(cfg.get(
        "first_k_dense_replace", 0))
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "rq": int(cfg["q_lora_rank"]), "rkv": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "F0": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]), "V": int(cfg["vocab_size"]),
        "L": L, "Ld": Ld, "E": int(cfg.get("router_width", n_held)),
        "held": tuple(range(n_held)) if ids is None else tuple(
            int(e) for e in ids),
        "k": int(cfg["num_experts_per_tok"]),
        "route_scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "eps": float(cfg.get("rms_norm_eps", 1e-5)),
        "theta": float(cfg.get("rope_theta", 10000.0)),
        "factor": float(rope.get("factor", 1.0)),
        "L0": int(rope.get("original_max_position_embeddings",
                           cfg["max_position_embeddings"])),
        "beta_fast": float(rope.get("beta_fast", 32)),
        "beta_slow": float(rope.get("beta_slow", 1)),
        "mscale": float(rope.get("mscale", 1)),
        "mscale_all_dim": float(rope.get("mscale_all_dim", 0)),
        "bias_std": float(cfg.get("selection_bias_std", 0.05)),
    }


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted(sizes(cfg).items()))


def kind_of(s: dict, layer: int) -> str:
    return DENSE if layer < s["Ld"] else MOE


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


# --------------------------------------------------------------- weights

def layer_shapes(s: dict, kind: str) -> dict:
    """A layer's leaves; an expert's two matrices stand once (`ex_gu`,
    `ex_d`) and are drawn for each expert held."""
    D, H = s["D"], s["H"]
    out = {"ln1_g": (D,), "ln2_g": (D,), "w_qa": (D, s["rq"]),
           "qa_g": (s["rq"],), "w_qb": (s["rq"], H * (s["dn"] + s["dr"])),
           "w_kva": (D, s["rkv"] + s["dr"]), "kva_g": (s["rkv"],),
           "w_kvb": (s["rkv"], H * (s["dn"] + s["dv"])),
           "w_o": (H * s["dv"], D)}
    if kind == DENSE:
        out.update({"w_gu": (D, 2 * s["F0"]), "w_d": (s["F0"], D)})
    else:
        out.update({"w_r": (D, s["E"]), "b_r": (s["E"],),
                    "sh_gu": (D, 2 * s["Fe"]), "sh_d": (s["Fe"], D),
                    "ex_gu": (D, 2 * s["Fe"]), "ex_d": (s["Fe"], D)})
    return out


ATTN_LEAVES = ("ln1_g", "ln2_g", "w_qa", "qa_g", "w_qb", "w_kva", "kva_g",
               "w_kvb", "w_o")
_LEAF_IDS = {n: i for i, n in enumerate(sorted(
    ATTN_LEAVES + ("w_gu", "w_d", "w_r", "b_r", "sh_gu", "sh_d", "ex_gu",
                   "ex_d", "embed", "head", "lnf_g")))}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, name, shape, dtype, layer=0, expert=0, bias_std=0.05):
    """One leaf from (seed, name, layer, expert id): `layer` and
    `expert` count from 1, 0 for a leaf that has none."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, _LEAF_IDS[name]), layer), expert)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in ("embed", "head"):
        z = z / np.sqrt(shape[1])
    elif name.endswith("_g"):
        z = 1.0 + 0.02 * z
    elif name == "b_r":
        z = bias_std * z
    else:
        z = z / np.sqrt(shape[0])
    return z.astype(jnp.bfloat16).astype(jnp.dtype(dtype))


def layer_weights(cfg: dict, seed: int, layer: int, dtype: str = "float32"):
    """The leaves of layer `layer`; `ex_gu`, `ex_d` stacked over the
    experts held, each drawn by its id."""
    s = sizes(cfg)
    key, out = seed_key(seed), {}
    for name, shape in layer_shapes(s, kind_of(s, layer)).items():
        if name.startswith("ex_"):
            out[name] = jnp.stack([
                _leaf(key, name, shape, dtype, layer + 1, e + 1)
                for e in s["held"]])
        else:
            out[name] = _leaf(key, name, shape, dtype, layer + 1, 0,
                              s["bias_std"])
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


def make_weights(cfg: dict, seed: int, dtype: str = "float32"):
    """Parameters on the device, stacked as the program takes them
    (`attn` over every layer, `dense` over the leading ones, `moe` over
    the rest, an expert layer's matrices over the experts held): drawn
    in float32, rounded once to bfloat16, held in `dtype`.  Each stack
    is filled in place a leaf at a time, so that no more than one leaf
    in float32 stands beside the parameters."""
    s = sizes(cfg)
    key = seed_key(seed)
    L, Ld, N = s["L"], s["Ld"], len(s["held"])
    out = {n: top_weight(cfg, seed, n, dtype)
           for n in ("embed", "head", "lnf_g")}
    dense, moe = layer_shapes(s, DENSE), layer_shapes(s, MOE)
    stacks = {
        "attn": {n: (L,) + dense[n] for n in ATTN_LEAVES},
        "dense": {n: (Ld,) + dense[n] for n in ("w_gu", "w_d")},
        "moe": {n: (L - Ld,) + ((N,) if n.startswith("ex_") else ())
                + moe[n] for n in moe if n not in ATTN_LEAVES},
    }
    dt = jnp.dtype(dtype)
    for group, leaves in stacks.items():
        out[group] = {}
        for name, shape in leaves.items():
            buf = jnp.zeros(shape, dt)
            for layer in range(L):
                kind = kind_of(s, layer)
                if group != "attn" and kind != group:
                    continue
                i = layer if group == "attn" else \
                    layer - (Ld if group == "moe" else 0)
                if name.startswith("ex_"):
                    for n, e in enumerate(s["held"]):
                        buf = _put(buf, _leaf(key, name, moe[name], dtype,
                                              layer + 1, e + 1),
                                   np.asarray([i, n], np.int32))
                else:
                    buf = _put(buf, _leaf(key, name, shape[1:], dtype,
                                          layer + 1, 0, s["bias_std"]),
                               np.asarray([i], np.int32))
            out[group][name] = buf
    return out


def layer_of(params: dict, cfg: dict, layer: int) -> dict:
    """Layer `layer` out of stacked parameters."""
    s = sizes(cfg)
    group = kind_of(s, layer)
    i = layer - (s["Ld"] if group == MOE else 0)
    out = {n: a[layer] for n, a in params["attn"].items()}
    out.update({n: a[i] for n, a in params[group].items()})
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
    """w_j (module docstring), float64."""
    dr, theta = s["dr"], s["theta"]
    j = np.arange(dr // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / dr)
    if s["factor"] <= 1:
        return f
    at = lambda beta: dr * math.log(  # noqa: E731
        s["L0"] / (beta * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(at(s["beta_fast"])), 0)
    high = min(math.ceil(at(s["beta_slow"])), dr - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / s["factor"] * ramp + f * (1.0 - ramp)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def sigma(s: dict) -> float:
    """The scores' scale: (d_n + d_r)^-1/2 m^2."""
    m = _mscale(s["factor"], s["mscale_all_dim"])
    return m * m / math.sqrt(s["dn"] + s["dr"])


def rope(x, t, s):
    """x (T, ..., d_r) rotated at positions t (T,)."""
    half = s["dr"] // 2
    ang = t.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(yarn_freqs(s), jnp.float32)
    ratio = _mscale(s["factor"], s["mscale"]) \
        / _mscale(s["factor"], s["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(u, w, s, quant=None):
    """u (T, D) = RMS1(x) -> Attn(u) (T, D): every position's K and V
    expanded from its latent row, a causal softmax a head."""
    T, H, dn, dv = u.shape[0], s["H"], s["dn"], s["dv"]
    t = jnp.arange(T)
    cq = _r(_rms(_mm(u, w["w_qa"], quant), w["qa_g"], s["eps"]), quant)
    q = _mm(cq, w["w_qb"], quant).reshape(T, H, dn + s["dr"])
    q_n, q_r = q[..., :dn], _r(rope(q[..., dn:], t, s), quant)
    ckv = _mm(u, w["w_kva"], quant)
    c = _r(_rms(ckv[:, :s["rkv"]], w["kva_g"], s["eps"]), quant)
    k_r = _r(rope(ckv[:, s["rkv"]:], t, s), quant)
    kv = _mm(c, w["w_kvb"], quant).reshape(T, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    qn, qr, kn, kr, vv = (_operand(a, quant) for a in (q_n, q_r, k_n, k_r, v))

    def block(args):
        qnb, qrb, i = args  # (Q, H, d_n), (Q, H, d_r), (Q,)
        sc = (jnp.einsum("qhd,khd->hqk", qnb, kn)
              + jnp.einsum("qhd,kd->hqk", qrb, kr)) * sigma(s)
        seen = t[None, :] <= i[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", _operand(_r(p, quant), quant), vv)

    if T <= QUERY_BLOCK:
        o = block((qn, qr, t))
    else:
        n = -(-T // QUERY_BLOCK)
        pad = n * QUERY_BLOCK - T
        cut = lambda a: jnp.pad(  # noqa: E731
            a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
                (n, QUERY_BLOCK) + a.shape[1:])
        ids = jnp.pad(t, (0, pad), constant_values=T - 1).reshape(
            n, QUERY_BLOCK)
        o = jax.lax.map(block, (cut(qn), cut(qr), ids)).reshape(
            n * QUERY_BLOCK, H, dv)[:T]
    return _mm(_r(o, quant).reshape(T, H * dv), w["w_o"], quant)


def route(u, w, s):
    """u (T, D) -> (chosen (T, k) expert ids, g (T, k)), float32."""
    sc = jax.nn.sigmoid(u @ w["w_r"])
    _, chosen = jax.lax.top_k(sc + w["b_r"], s["k"])
    g = jnp.take_along_axis(sc, chosen, -1)
    return chosen, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) \
        * s["route_scale"]


def moe_parts(u, w, s, quant=None):
    """u (T, D) = RMS2(h) -> (the shared expert's output, the part of
    the routed sum that the experts held here give), each (T, D)."""
    chosen, g = route(u, w, s)
    shared = _swiglu(u, w["sh_gu"], w["sh_d"], quant)
    routed = jnp.zeros_like(shared)
    for n, e in enumerate(s["held"]):
        g_e = jnp.sum(jnp.where(chosen == e, g, 0.0), -1, keepdims=True)
        routed = routed + g_e * _swiglu(u, w["ex_gu"][n], w["ex_d"][n], quant)
    return shared, _r(routed, quant)


def layer(x, w, s, kind, quant=None):
    """x (T, D) through one layer of kind `kind`."""
    u = _r(_rms(x, w["ln1_g"], s["eps"]), quant)
    h = _r(x + attention(u, w, s, quant), quant)
    u = _r(_rms(h, w["ln2_g"], s["eps"]), quant)
    if kind == DENSE:
        return _r(h + _swiglu(u, w["w_gu"], w["w_d"], quant), quant)
    shared, routed = moe_parts(u, w, s, quant)
    return _r(h + shared + routed, quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, w, cfg_items, kind, quant):
    with jax.default_matmul_precision("highest"):
        return layer(x, w, dict(cfg_items), kind, quant)


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
            xs[i] = _layer(x, w, items, kind_of(s, l), quant)
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
    layer at a time: at the published widths they are 16.7 GB whole."""
    def get(name):
        if isinstance(name, str):
            return top_weight(cfg, seed, name, "float32")
        return layer_weights(cfg, seed, name, "float32")

    return _gaps(get, rows, cfg, int(prompt_len), quant)
