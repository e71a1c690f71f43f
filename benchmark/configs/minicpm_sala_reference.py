"""Plain reference for the MiniCPM-SALA configuration, and its seeded weights.

The layer equations (ISSUE 26, section A; the configuration's `assumed`
lists every value the public `config.json` does not carry):

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g
    h0 = E[token] * scale_emb
    h <- h + r * Mixer_l(RMSNorm(h));  h <- h + r * SwiGLU(RMSNorm(h))
    r = scale_depth / sqrt(published depth)
    logits = W_head RMSNorm(h) / (hidden_size / dim_model_base)

`lightning-attn`: q, k, v projections, per-head RMSNorm on q and k, RoPE
(rotate-half, theta 10000, whole head) on q and k, q / sqrt(d); per head
S_t = lambda S_{t-1} + k_t^T v_t, o_t = q_t S_t, lambda = exp(-s_h (1 -
l / (L - 1) + 1e-5)), s_h = 2^(-8 (h + 1) / H), l the PUBLISHED layer
index; RMSNorm over the concatenated heads, sigmoid gate, W_o.

`minicpm4`: 32 query heads on 2 K/V heads, per-head RMSNorm on q and k,
no rotation, scale 1 / sqrt(d).  A query at t with t + 1 <= dense_len
attends every key <= t.  Beyond: compressed keys mean(k[16 j : 16 j +
32]) whose kernel ends at or before t; softmax over them per query
head, summed over the 16 heads of a group; a 64-token block's score is
the largest summed probability among the compressed positions whose
kernel overlaps it; attended are block 0, the blocks that hold the last
2048 positions and the 64 best of the rest.  Sigmoid gate, W_o.

Everything is straightforward `jax.numpy` in float32 at matmul precision
`highest`: no cache, no chunking of the sequence into dependent pieces
(positions are only taken a block at a time where a whole array would
not fit the chip), the recurrence as a `lax.scan` over positions, the
selection by `lax.top_k` per query.  It imports nothing of the program.
The weights are made here from the seed, in the pytree layout that the
program takes: they are the benchmark's input, like the prompts.

Weights (`assumed.weights`): matrices N(0, 1 / fan_in); the embedding
N(0, 0.02) so that h0 (after scale_emb 12) has the spread of one
sublayer's addition and the context, not the last token, decides the
next token; the head N(0, (hidden_size / dim_model_base)^2 / hidden_size)
so that logits spread by about one; gains 1 + N(0, 0.02).  Drawn in
float32, rounded once to bfloat16; `dtype="float32"` widens those
rounded values, so that a comparison measures the computation and not
the rounding of weights.  Each layer's leaves have keys of their own:
a layer can be made alone (`layer_weights`), which is how the reference
runs at the published widths beside 16 GB.

`quant` (`"bf16"`, `"int8"`, `"fp8"`) are the controls of
`gpt2_reference.py`: the same mathematics with every activation held in
bfloat16 and every matmul operand rounded.  The lightning state stays
float32 under all of them, as the configuration states it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# Positions taken at a time where a whole (T, ...) array would not fit.
ROW_BLOCK = 4096
QUERY_BLOCK = 256


def sizes(cfg: dict) -> dict:
    pub = cfg.get("published", {})
    mixers = list(cfg["mixer_types"])
    sp = cfg["sparse_config"]
    return {
        "D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]), "Hq": int(cfg["num_attention_heads"]),
        "G": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "Hl": int(cfg["lightning_nh"]), "Dl": int(cfg["lightning_head_dim"]),
        "mixers": mixers,
        "ids": list(pub.get("layer_ids", range(len(mixers)))),
        "depth": int(pub.get("num_hidden_layers", len(mixers))),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "scale_emb": float(cfg["scale_emb"]),
        "r": float(cfg["scale_depth"]) / float(np.sqrt(
            pub.get("num_hidden_layers", len(mixers)))),
        "logit_div": float(cfg["hidden_size"]) / float(cfg["dim_model_base"]),
        "ksz": int(sp["kernel_size"]), "stride": int(sp["kernel_stride"]),
        "blk": int(sp["block_size"]), "topk": int(sp["topk"]),
        "window": int(sp["window_size"]), "init": int(sp["init_blocks"]),
        "dense_len": int(sp["dense_len"]),
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
    D, F = s["D"], s["F"]
    if kind == SPARSE:
        qd, kd, dh = s["Hq"] * s["Dh"], s["G"] * s["Dh"], s["Dh"]
        extra = {}
    else:
        qd = kd = s["Hl"] * s["Dl"]
        dh = s["Dl"]
        extra = {"o_norm": (qd,)}
    return {"norm1": (D,), "wq": (D, qd), "wk": (D, kd), "wv": (D, kd),
            "wg": (D, qd), "wo": (qd, D), "q_norm": (dh,), "k_norm": (dh,),
            "norm2": (D,), "w_gate": (D, F), "w_up": (D, F),
            "w_down": (F, D), **extra}


_LEAF_IDS = {n: i for i, n in enumerate(sorted(
    ["norm1", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm", "o_norm",
     "norm2", "w_gate", "w_up", "w_down", "embed", "head", "norm_f"]))}


def _leaf(key, name, shape, s, dtype, layer=0):
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS[name]), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if name == "embed":
        z = z * 0.02
    elif name == "head":
        z = z * (s["logit_div"] / np.sqrt(shape[0]))
    elif len(shape) == 2:
        z = z / np.sqrt(shape[0])
    else:
        z = 1.0 + 0.02 * z
    return z.astype(jnp.bfloat16).astype(jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layer_weights(key, cfg_items, kind, layer, dtype):
    s = dict(cfg_items)
    return {n: _leaf(key, n, shape, s, dtype, layer + 1)
            for n, shape in layer_shapes(s, kind).items()}


def layer_weights(cfg: dict, seed: int, layer: int, dtype: str = "float32"):
    """The leaves of layer `layer` (its index in the stack as run)."""
    items = _hashable(cfg)
    kind = dict(items)["mixers"][layer]
    return _layer_weights(seed_key(seed), items, kind, int(layer), dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _top_weight(key, cfg_items, name, dtype):
    s = dict(cfg_items)
    shape = {"embed": (s["V"], s["D"]), "head": (s["D"], s["V"]),
             "norm_f": (s["D"],)}[name]
    return _leaf(key, name, shape, s, dtype)


def top_weight(cfg: dict, seed: int, name: str, dtype: str = "float32"):
    return _top_weight(seed_key(seed), _hashable(cfg), name, dtype)


def make_weights(cfg: dict, seed: int, dtype: str = "float32"):
    """Parameters on the device, stacked by kind as the program takes
    them: drawn in float32, rounded once to bfloat16, held in `dtype`."""
    s = sizes(cfg)
    out = {n: top_weight(cfg, seed, n, dtype)
           for n in ("embed", "head", "norm_f")}
    for kind, name in ((SPARSE, "sparse"), (LIGHTNING, "lightning")):
        layers = [layer_weights(cfg, seed, i, dtype)
                  for i, m in enumerate(s["mixers"]) if m == kind]
        if layers:
            out[name] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
        else:
            out[name] = {n: jnp.zeros((0,) + shape, jnp.dtype(dtype))
                         for n, shape in layer_shapes(s, kind).items()}
    return out


def layer_of(params: dict, cfg: dict, layer: int) -> dict:
    """Layer `layer` of the stack as run, out of stacked parameters."""
    mixers = sizes(cfg)["mixers"]
    kind = mixers[layer]
    i = sum(m == kind for m in mixers[:layer])
    name = "sparse" if kind == SPARSE else "lightning"
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

def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _by_blocks(fn, xs, block):
    """fn over the arrays xs (each (T, ...)) a block of positions at a
    time; fn of the whole where that is one block."""
    T = jax.tree.leaves(xs)[0].shape[0]
    if T <= block:
        return fn(xs)
    n = -(-T // block)
    cut = lambda a: jnp.pad(  # noqa: E731
        a, ((0, n * block - T),) + ((0, 0),) * (a.ndim - 1)
    ).reshape((n, block) + a.shape[1:])
    out = jax.lax.map(fn, jax.tree.map(cut, xs))
    return jax.tree.map(
        lambda a: a.reshape((n * block,) + a.shape[2:])[:T], out)


def _mlp(x, w, s, quant):
    def part(x):
        h = _r(_rms(x, w["norm2"], s["eps"]), quant)
        a = _r(jax.nn.silu(_mm(h, w["w_gate"], quant))
               * _mm(h, w["w_up"], quant), quant)
        return _r(x + s["r"] * _mm(a, w["w_down"], quant), quant)

    return _by_blocks(part, x, ROW_BLOCK)


def _rope(x, theta):
    """x (T, H, d): rotate-half over the whole head, position = row."""
    T, _, d = x.shape
    half = d // 2
    inv = (1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
           ).astype(np.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def decay(s: dict, layer_id: int) -> np.ndarray:
    """lambda (H,) of the lightning layer with PUBLISHED index layer_id."""
    H, L = s["Hl"], s["depth"]
    slopes = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    return np.exp(-slopes * (1.0 - layer_id / max(L - 1, 1) + 1e-5)
                  ).astype(np.float32)


def _projections(x, w, s, quant, heads, kv_heads, dh):
    def part(x):
        h = _r(_rms(x, w["norm1"], s["eps"]), quant)
        return (_mm(h, w["wq"], quant), _mm(h, w["wk"], quant),
                _mm(h, w["wv"], quant),
                _r(jax.nn.sigmoid(_mm(h, w["wg"], quant)), quant))

    q, k, v, gate = _by_blocks(part, x, ROW_BLOCK)
    T = x.shape[0]
    q = _r(_rms(q.reshape(T, heads, dh), w["q_norm"], s["eps"]), quant)
    k = _r(_rms(k.reshape(T, kv_heads, dh), w["k_norm"], s["eps"]), quant)
    return q, k, v.reshape(T, kv_heads, dh), gate


def _mixer_out(x, o, gate, w, s, quant):
    def part(args):
        x, o, gate = args
        return _r(x + s["r"] * _mm(_r(o * gate, quant), w["wo"], quant),
                  quant)

    return _by_blocks(part, (x, o, gate), ROW_BLOCK)


def lightning_layer(x, w, s, lam, quant=None):
    """x (T, D) through one lightning-attn layer and its MLP; lam (H,)
    is the layer's `decay`."""
    T = x.shape[0]
    H, d = s["Hl"], s["Dl"]
    q, k, v, gate = _projections(x, w, s, quant, H, H, d)
    q = _r(_rope(q, s["theta"]), quant)
    k = _r(_rope(k, s["theta"]), quant)
    q, k, v = (_operand(a, quant) for a in (q / np.sqrt(d), k, v))
    lam = lam[:, None, None]

    def step(S, qkv):
        q, k, v = qkv
        S = lam * S + k[:, :, None] * v[:, None, :]
        return S, jnp.sum(q[:, :, None] * S, axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    o = _r(_rms(_r(o, quant).reshape(T, H * d), w["o_norm"], s["eps"]), quant)
    return _mlp(_mixer_out(x, o, gate, w, s, quant), w, s, quant)


def compressed_keys(k, s):
    """k (T, G, d) -> (NC, G, d): mean of each whole kernel."""
    T = k.shape[0]
    n = max((T - s["ksz"]) // s["stride"] + 1, 0)
    idx = (s["stride"] * np.arange(n)[:, None]
           + np.arange(s["ksz"])[None, :])
    return jnp.mean(k[idx], axis=1) if n else jnp.zeros(
        (0,) + k.shape[1:], k.dtype)


def select(q, kbar, t, s, n_blocks):
    """Selected blocks of the queries q (Q, Hq, d) at positions t (Q,):
    (sel (Q, G, NB) bool, score (Q, G, NB) with -inf off the candidates).
    """
    Q = q.shape[0]
    G, g, d = s["G"], s["Hq"] // s["G"], s["Dh"]
    NC, NB = kbar.shape[0], n_blocks
    b = jnp.arange(NB)
    first_local = jnp.maximum(t - (s["window"] - 1), 0) // s["blk"]
    forced = (b[None, :] < s["init"]) | (
        (b[None, :] >= first_local[:, None])
        & (b[None, :] <= (t // s["blk"])[:, None]))
    candidate = (b[None, :] >= s["init"]) & (b[None, :] < first_local[:, None])
    dense = (t + 1 <= s["dense_len"])[:, None, None]
    if NC == 0:
        sel = jnp.broadcast_to(dense | forced[:, None, :], (Q, G, NB))
        return sel, jnp.full((Q, G, NB), -jnp.inf)
    sc = jnp.einsum("qghd,jgd->qghj", q.reshape(Q, G, g, d), kbar) / np.sqrt(d)
    ends = s["stride"] * jnp.arange(NC) + s["ksz"] - 1
    vis = (ends[None, :] <= t[:, None])[:, None, None, :]
    sc = jnp.where(vis, sc, -jnp.inf)
    top = jnp.max(sc, -1, keepdims=True)
    e = jnp.where(vis, jnp.exp(sc - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    p = jnp.sum(p, axis=2)  # (Q, G, NC)
    # Block b is overlapped by the kernels j with stride j < blk (b + 1)
    # and stride j + ksz > blk b: listed per block, -1 where fewer.
    j, bb = np.arange(NC)[None, :], np.arange(NB)[:, None]
    overlap = (s["stride"] * j < s["blk"] * (bb + 1)) \
        & (s["stride"] * j + s["ksz"] > s["blk"] * bb)
    width = max(int(overlap.sum(1).max()), 1)
    lists = np.full((NB, width), -1)
    for row, hit in enumerate(overlap):
        at = np.flatnonzero(hit)
        lists[row, :len(at)] = at
    score = jnp.max(jnp.where(lists >= 0, p[..., np.maximum(lists, 0)], 0.0),
                    -1)  # (Q, G, NB)
    score = jnp.where(candidate[:, None, :], score, -jnp.inf)
    vals, ids = jax.lax.top_k(score, min(s["topk"], NB))
    picked = jnp.sum(jax.nn.one_hot(ids, NB) * jnp.isfinite(vals)[..., None],
                     axis=-2) > 0
    return dense | forced[:, None, :] | picked, score


def attend(q, k, v, sel, t, s, quant=None):
    """Causal softmax attention of q (Q, Hq, d) at t (Q,) over k, v
    (T, G, d), restricted to the tokens of the selected blocks."""
    Q, T = q.shape[0], k.shape[0]
    G, g, d = s["G"], s["Hq"] // s["G"], s["Dh"]
    key = jnp.arange(T)
    mask = (key[None, :] <= t[:, None])[:, None, :] \
        & jnp.repeat(sel, s["blk"], axis=-1)[..., :T]
    sc = jnp.einsum("qghd,kgd->qghk", _operand(q.reshape(Q, G, g, d), quant),
                    _operand(k, quant)) / np.sqrt(d)
    sc = jnp.where(mask[:, :, None, :], sc, -jnp.inf)
    probs = _operand(_r(jax.nn.softmax(sc, -1), quant), quant)
    o = jnp.einsum("qghk,kgd->qghd", probs, _operand(v, quant))
    return _r(o, quant).reshape(Q, G * g * d)


def sparse_layer(x, w, s, quant=None):
    """x (T, D) through one minicpm4 layer and its MLP."""
    T = x.shape[0]
    q, k, v, gate = _projections(x, w, s, quant, s["Hq"], s["G"], s["Dh"])
    kbar = _r(compressed_keys(k, s), quant)
    NB = -(-T // s["blk"])

    def part(args):
        q, t = args
        sel, _ = select(q, kbar, t, s, NB)
        return attend(q, k, v, sel, t, s, quant)

    o = _by_blocks(part, (q, jnp.arange(T)), QUERY_BLOCK)
    return _mlp(_mixer_out(x, o, gate, w, s, quant), w, s, quant)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, w, lam, cfg_items, kind, quant):
    s = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        if kind == SPARSE:
            return sparse_layer(x, w, s, quant)
        return lightning_layer(x, w, s, lam, quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(embed, tokens, cfg_items, quant):
    return _r(embed[tokens] * dict(cfg_items)["scale_emb"], quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(x, norm_f, head, cfg_items, quant):
    s = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _r(_rms(x, norm_f, s["eps"]), quant)
        return _mm(h, head, quant) / s["logit_div"]


def _forward(get, rows, cfg: dict, quant, keep_from: int = 0):
    """Logits (B, T - keep_from, V) of rows (B, T): `get(name)` gives a
    top-level leaf, `get(i)` layer i's leaves; one layer of weights is
    held at a time, every row passes it, then the next."""
    items = _hashable(cfg)
    s = dict(items)
    embed = get("embed")
    xs = [_embed(embed, jnp.asarray(r, jnp.int32), items, quant) for r in rows]
    del embed
    for i, (kind, lid) in enumerate(zip(s["mixers"], s["ids"])):
        w = get(i)
        lam = jnp.asarray(decay(s, int(lid)))
        xs = [_layer(x, w, lam, items, kind, quant) for x in xs]
        del w
    norm_f, head = get("norm_f"), get("head")
    return jnp.stack([_logits(x[keep_from:], norm_f, head, items, quant)
                      for x in xs])


def _getter(params, cfg):
    return lambda name: (params[name] if isinstance(name, str)
                         else layer_of(params, cfg, name))


def logits(params, tokens, cfg: dict, quant=None):
    """Full-forward logits (B, T, V) float32 of tokens (B, T)."""
    return _forward(_getter(params, cfg), np.asarray(tokens), cfg, quant)


# ------------------------------------------------------------- serving

def _gaps(get, rows, cfg, prompt_len, quant):
    rows = np.asarray(rows)
    served = jnp.asarray(rows[:, prompt_len:], jnp.int32)
    ref = _forward(get, rows[:, :-1], cfg, None, prompt_len - 1)
    best = jnp.max(ref, -1)
    out = {"gap_served": best - jnp.take_along_axis(
        ref, served[..., None], -1)[..., 0]}
    if quant is not None:
        low = jnp.argmax(
            _forward(get, rows[:, :-1], cfg, quant, prompt_len - 1), -1)
        out["gap_control"] = best - jnp.take_along_axis(
            ref, low[..., None], -1)[..., 0]
    return {k: np.asarray(v) for k, v in out.items()}


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
    layer at a time: at the published widths they are 11.3 GB whole."""
    def get(name):
        if isinstance(name, str):
            return top_weight(cfg, seed, name, "float32")
        return layer_weights(cfg, seed, name, "float32")

    return _gaps(get, rows, cfg, int(prompt_len), quant)
