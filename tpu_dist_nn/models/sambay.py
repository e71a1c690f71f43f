"""Phi-4-mini-flash's block family (SambaY): five kinds of mixer in one stack.

A third block family beside GPT-2's (:mod:`.transformer`) and
MiniCPM-SALA's (:mod:`.sala`): LayerNorm with bias, SwiGLU, a TIED head,
no positional encoding, and a mixer that differs by layer (``l`` the
0-based index, ``half = L / 2``):

* ``mamba`` (``l`` even, ``l <= half``) — Mamba-1: a depthwise causal
  convolution over the last ``d_conv`` inputs ahead of a selective scan
  with a float32 state ``(d_state, d_inner)`` a slot;
* ``window`` (``l`` odd, ``l < half``) — differential attention over
  the last ``sliding_window`` positions, its K/V a RING (lane =
  position mod window, no rotation needed without positions);
* ``full`` (``l = half + 1``) — differential attention over every
  position; its K/V are the ONE cache that grows with position;
* ``gmu`` (``l`` even, ``l > half + 1``) — a gated memory unit: the
  last Mamba layer's scan output ``m`` of the same position, gated;
* ``cross`` (``l`` odd, ``l > half + 1``) — differential attention of
  its own queries onto the ``full`` layer's K/V.

Only a prompt's last position ever enters layers ``half + 1 ..``: a
prefill chunk that ends no prompt needs layers ``0 .. half`` and the
``full`` layer's K/V projection, and no head
(:func:`prefill_body_into_cache`); the chunk that ends one adds the
rest on its last position alone, which is a decode step's second half
(:func:`_tail`, written once).

Parameters are stacked BY KIND (``params["mamba"]``, ``["attn"]`` — the
window layers, then the full one — ``["gmu"]``, ``["cross"]``), each
kind's dict also holding its layers' LayerNorms and MLP; the stack is
walked as a ``lax.scan`` over (mamba, window) pairs, the last Mamba
layer, the full layer, a scan over (gmu, cross) pairs.

The slot cache, every leaf ``(layers, slots, ...)`` with positions or
channels in the 128 lanes: ``k``, ``v`` ``(1, S, G, d, M)``; the rings
``wk``, ``wv`` ``(window layers, S, G, d, W)``; ``conv`` ``(mamba
layers, S, d_conv - 1, E)`` the last inputs of each convolution;
``state`` ``(mamba layers, S, d_state, E)`` float32.  docs/MODEL_CONFIG.md
has the equations' provenance; the plain reference is
benchmark/configs/phi4_flash_reference.py.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_dist_nn.kernels import decode_attend
from tpu_dist_nn.kernels.kv_write import write_rows
from tpu_dist_nn.models.sala import (
    _einsum32,
    _put_slot,
    _ring_after_chunk,
    _take_slot,
)
from tpu_dist_nn.models.slot_model import SlotModel

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
_LANES = 128
# Positions of a chunk whose decays and inputs are laid out at the
# state's size at once (a block of the scan).
_SCAN_BLOCK = 16


def layer_kinds(n_layers: int) -> tuple:
    """SambaY's layer map (arXiv:2507.06607): a Samba self-decoder of
    ``L / 2 + 1`` layers, one full-attention layer, a cross-decoder that
    alternates GMU and cross-attention."""
    half = n_layers // 2
    out = []
    for l in range(n_layers):
        if l <= half:
            out.append(MAMBA if l % 2 == 0 else WINDOW)
        elif l == half + 1:
            out.append(FULL)
        else:
            out.append(GMU if l % 2 == 0 else CROSS)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    """Static description of one SambaY stack (hashable)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    n_heads: int
    n_kv_heads: int
    n_layers: int
    sliding_window: int
    max_seq_len: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    ln_eps: float = 1e-5
    param_dtype: str = "bfloat16"
    state_dtype: str = "float32"

    causal = True  # every mixer is; the generation contract asks

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(
                "num_hidden_layers must be a multiple of 4 and at least 8 "
                f"(a (mamba, window) and a (gmu, cross) pair), got "
                f"{self.n_layers}")
        if self.hidden_size % self.n_heads:
            raise ValueError("hidden_size must be a multiple of the heads")
        if self.n_heads != 2 * self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(
                "differential attention pairs the heads: two query pairs "
                "on each pair of K/V heads, so num_attention_heads = 2 x "
                "num_key_value_heads, an even number")

    # ------------------------------------------------------------ sizes
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.hidden_size / 16)

    @property
    def layer_kinds(self) -> tuple:
        return layer_kinds(self.n_layers)

    @property
    def n_pairs(self) -> int:
        """(mamba, window) pairs: layers ``0 .. L / 2 - 1``."""
        return self.n_layers // 4

    @property
    def n_mamba(self) -> int:
        return self.n_pairs + 1

    @property
    def n_cross(self) -> int:
        """(gmu, cross) pairs: layers ``L / 2 + 2 ..``."""
        return self.n_layers // 4 - 1

    def layer_ids(self, kind: str) -> tuple:
        """Published indices of a kind's layers; ``"attn"``: the stack
        of ``params["attn"]``, the window layers then the full one."""
        kinds = (WINDOW, FULL) if kind == "attn" else (kind,)
        return tuple(l for k in kinds
                     for l, x in enumerate(self.layer_kinds) if x == k)

    def lambda_init(self) -> dict:
        """``lam0 = 0.8 - 0.6 exp(-0.3 l)`` of each attention layer by
        its published index, stacked as the parameters are."""
        lam0 = lambda ids: (  # noqa: E731
            0.8 - 0.6 * np.exp(-0.3 * np.asarray(ids, np.float64))
        ).astype(np.float32)
        return {"attn": lam0(self.layer_ids("attn")),
                "cross": lam0(self.layer_ids(CROSS))}

    def cast_params(self, params):
        dtype = jnp.dtype(self.param_dtype)
        return jax.tree.map(
            lambda a: a if a.dtype == dtype else a.astype(dtype), params)

    def init_params(self, key):
        return init_sambay(key, self)

    def num_params(self) -> int:
        return num_params(self)

    def slot_model(self) -> SlotModel:
        return SlotModel(
            init_slot_cache=init_slot_cache,
            prefill_chunk_into_cache=prefill_chunk_into_cache,
            decode_step_slots=decode_step_slots,
            copy_cache_slot=copy_cache_slot,
            flop_model=SambaYFlopModel,
            cache_bytes=cache_bytes,
            recurrent=True,
            prefill_body_into_cache=prefill_body_into_cache,
            step_kv_tiles=self.step_kv_tiles,
        )

    def step_kv_tiles(self, slots: int, max_len: int):
        """``pos (int array) -> (fetched, skipped)``: of the 128-lane
        position tiles of the shared K/V extent, how many a decode step
        copies for queries at ``pos`` and how many it leaves in HBM; or
        ``None`` where the step of ``slots`` slots into a cache made for
        ``max_len`` reads the whole extent (:func:`_tail`'s dispatch,
        asked from outside the program)."""
        M = _extent(max_len)
        if decode_attend.tiles(slots, self.n_kv_heads, self.head_dim, M,
                               self.param_dtype) is None:
            return None

        def count(pos):
            fetched = int(decode_attend.fetched_tiles(pos).sum())
            return fetched, len(pos) * (M // _LANES) - fetched

        return count

    # ---------------------------------------------------------- loading
    @classmethod
    def from_dict(cls, d: dict) -> "SambaYConfig":
        """From a ``config.json`` in the source's own keys
        (``model_type: phi4flash``).  What the source does not carry
        (Mamba-1's sizes) may stand under ``mamba``; ``published.
        layer_kinds``, where given, must be the map this family
        derives."""
        if d.get("model_type") != "phi4flash":
            raise ValueError(
                f"model_type {d.get('model_type')!r} is not 'phi4flash'")
        if int(d.get("mb_per_layer", 2)) != 2:
            raise ValueError("mb_per_layer must be 2: Mamba every other layer")
        if not d.get("tie_word_embeddings", True):
            raise ValueError("this family ties its head to the embedding")
        mamba = {k: int(v) for k, v in d.get("mamba", {}).items()
                 if k in ("d_state", "d_conv", "expand")}
        cfg = cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]),
            intermediate_size=int(d["intermediate_size"]),
            n_heads=int(d["num_attention_heads"]),
            n_kv_heads=int(d["num_key_value_heads"]),
            n_layers=int(d["num_hidden_layers"]),
            sliding_window=int(d["sliding_window"]),
            max_seq_len=int(d["max_position_embeddings"]),
            ln_eps=float(d["layer_norm_eps"]),
            param_dtype=str(d.get("param_dtype", "bfloat16")),
            **mamba,
        )
        listed = d.get("published", {}).get("layer_kinds")
        if listed is not None and tuple(listed) != cfg.layer_kinds:
            raise ValueError(
                "published.layer_kinds is not the map this family derives "
                f"from num_hidden_layers {cfg.n_layers}")
        return cfg


# ------------------------------------------------------------ parameters

def param_shapes(cfg: SambaYConfig) -> dict:
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    Hd, Gd, d = D, cfg.n_kv_heads * cfg.head_dim, cfg.head_dim
    E, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank

    def common(L):
        return {"ln1_g": (L, D), "ln1_b": (L, D), "ln2_g": (L, D),
                "ln2_b": (L, D), "w1": (L, D, 2 * F), "w2": (L, F, D)}

    def diff(L):
        return {"w_o": (L, Hd, D), "b_o": (L, D), "lq1": (L, d), "lk1": (L, d),
                "lq2": (L, d), "lk2": (L, d), "sub_g": (L, 2 * d)}

    Lm, La, Lc = cfg.n_mamba, cfg.n_pairs + 1, cfg.n_cross
    return {
        "embed": (V, D), "lnf_g": (D,), "lnf_b": (D,),
        "mamba": {
            **common(Lm), "w_in": (Lm, D, 2 * E), "conv_w": (Lm, K, E),
            "conv_b": (Lm, E), "w_x": (Lm, E, R + 2 * N), "w_dt": (Lm, R, E),
            "b_dt": (Lm, E), "a_log": (Lm, N, E), "d_skip": (Lm, E),
            "w_out": (Lm, E, D),
        },
        "attn": {**common(La), "w_qkv": (La, D, Hd + 2 * Gd),
                 "b_qkv": (La, Hd + 2 * Gd), **diff(La)},
        "gmu": {**common(Lc), "wg1": (Lc, D, E), "wg2": (Lc, E, D)},
        "cross": {**common(Lc), "w_q": (Lc, D, Hd), "b_q": (Lc, Hd),
                  **diff(Lc)},
    }


def num_params(cfg: SambaYConfig) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_sambay(key: jax.Array, cfg: SambaYConfig):
    """Seeded random parameters in ``cfg.param_dtype``: matrices N(0,
    1/fan_in), embedding N(0, 1/hidden_size) (a tied head then spreads
    logits by about one and the context decides the next token), gains 1 + N(0,
    0.02), biases and lambda vectors N(0, 0.02) and N(0, 0.1), the B
    and C columns of ``w_x`` N(0, 9/fan_in) (the scan's read-out then has
    the size of the skip); Mamba-1's
    defaults for ``A_log`` (log 1..N), ``D`` (1) and the ``dt`` bias
    (inverse softplus of log-uniform [1e-3, 1e-1])."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(cfg.param_dtype)
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(leaves)), leaves):
        name = path[-1].key
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            z = z / np.sqrt(shape[1])
        elif name == "a_log":
            z = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[-2] + 1, dtype=jnp.float32))[:, None], shape)
        elif name == "d_skip":
            z = jnp.ones(shape, jnp.float32)
        elif name == "b_dt":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
            z = dt + jnp.log(-jnp.expm1(-dt))
        elif name.startswith("w") or name == "conv_w":
            z = z / np.sqrt(shape[-2])
            if name == "w_x":  # B and C: the scan's read-out beside the skip
                z = z * jnp.where(jnp.arange(shape[-1]) < cfg.dt_rank, 1.0,
                                  3.0)
        elif name.endswith("_g"):
            z = 1.0 + 0.02 * z
        elif name in ("lq1", "lk1", "lq2", "lk2"):
            z = 0.1 * z
        else:
            z = 0.02 * z
        out.append(z.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- the math

def _ln(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("sambay.mlp")
def _mlp(x, blk, cfg):
    F = cfg.intermediate_size
    gu = _ln(x, blk["ln2_g"], blk["ln2_b"], cfg.ln_eps) @ blk["w1"]
    return x + (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ blk["w2"]


def _unembed(params, x, cfg):
    h = _ln(x, params["lnf_g"], params["lnf_b"], cfg.ln_eps)
    return _einsum32("ad,vd->av", h, params["embed"])


def _layer(stack: dict, i):
    """Layer ``i`` (traced or not) of a kind's stacked parameters."""
    return jax.tree.map(
        lambda p: lax.dynamic_index_in_dim(p, i, 0, keepdims=False), stack)


# -------------------------------------------------------- mamba layers

def _mamba_in(x, blk, cfg):
    E = cfg.d_inner
    xz = _ln(x, blk["ln1_g"], blk["ln1_b"], cfg.ln_eps) @ blk["w_in"]
    return xz[..., :E], xz[..., E:]


def _mamba_gates(acc, blk, cfg):
    """From the convolution's sums ``acc (A, E)`` float32: the scan's
    inputs ``xc (A, E)`` in the activations' type and ``dt (A, E)``,
    ``B``, ``C (A, N)`` float32, with ``A_neg (N, E)``."""
    N, R = cfg.d_state, cfg.dt_rank
    dtype = blk["w_x"].dtype
    xc = jax.nn.silu(acc + blk["conv_b"].astype(jnp.float32)).astype(dtype)
    dbc = _einsum32("ae,ef->af", xc, blk["w_x"])
    dt = jax.nn.softplus(
        _einsum32("ar,re->ae", dbc[:, :R].astype(dtype), blk["w_dt"])
        + blk["b_dt"].astype(jnp.float32))
    a_neg = -jnp.exp(blk["a_log"].astype(jnp.float32))
    return xc, dt, dbc[:, R:R + N], dbc[:, R + N:], a_neg


def _mamba_out(x, y, xc, z, blk, cfg):
    """``y (A, E)`` float32 from the scan: skip, gate, projection,
    residual, MLP; also ``m``, the scan's output before the gate."""
    m = (y + blk["d_skip"].astype(jnp.float32)
         * xc.astype(jnp.float32)).astype(x.dtype)
    x = x + (m * jax.nn.silu(z)) @ blk["w_out"]
    return _mlp(x, blk, cfg), m


def _scan_blocked(dt, xc, B, C, a_neg, state):
    """The selective scan over a chunk: ``dt``, ``xc (T, E)``, ``B``,
    ``C (T, N)``, ``state (N, E)`` float32 -> ``(y (T, E), state)``.
    A block of positions at a time: the decays ``exp(dt A)`` and the
    inputs ``dt xc (x) B`` of the block are laid out ``(block, N, E)``,
    the recurrence inside it is ``block`` multiply-adds on ``(N, E)``,
    the state is carried between blocks.  A padded position has ``dt =
    0``: decay one, input none."""
    T = dt.shape[0]
    blk = min(_SCAN_BLOCK, T)
    n = -(-T // blk)
    def cut(a):
        return jnp.pad(a, ((0, n * blk - T), (0, 0))).reshape(
            (n, blk) + a.shape[1:])

    def body(s, inputs):
        dt_b, x_b, b_b, c_b = inputs
        decay = jnp.exp(dt_b[:, None, :] * a_neg[None])
        drive = (dt_b * x_b)[:, None, :] * b_b[:, :, None]
        ys = []
        for j in range(blk):
            s = decay[j] * s + drive[j]
            ys.append(jnp.sum(s * c_b[j][:, None], axis=0))
        return s, jnp.stack(ys)

    state, y = lax.scan(
        body, state, (cut(dt), cut(xc.astype(jnp.float32)), cut(B), cut(C)))
    return y.reshape(n * blk, -1)[:T], state


@jax.named_scope("sambay.mamba")
def _mamba_chunk_layer(x, blk, conv, state, cfg):
    """One Mamba layer over a chunk ``x (C, D)``: ``conv (K - 1, E)``
    the convolution's inputs before the chunk and ``state (N, E)``
    float32 come back as after it; also ``m (C, E)``."""
    C, K = x.shape[0], cfg.d_conv
    xm, z = _mamba_in(x, blk, cfg)
    window = jnp.concatenate([conv.astype(xm.dtype), xm])  # (C + K - 1, E)
    w = blk["conv_w"].astype(jnp.float32)
    acc = sum(window[j:j + C].astype(jnp.float32) * w[j] for j in range(K))
    xc, dt, B, Cm, a_neg = _mamba_gates(acc, blk, cfg)
    y, state = _scan_blocked(dt, xc, B, Cm, a_neg, state)
    x, m = _mamba_out(x, y, xc, z, blk, cfg)
    return x, window[C:].astype(conv.dtype), state, m


@jax.named_scope("sambay.mamba")
def _mamba_step_layer(x, blk, conv, state, active, cfg):
    """One Mamba layer of the decode step: ``x (S, D)``, ``conv (S, K -
    1, E)``, ``state (S, N, E)``; an inactive slot's stay bit for bit."""
    xm, z = _mamba_in(x, blk, cfg)
    window = jnp.concatenate([conv.astype(xm.dtype), xm[:, None]], 1)
    acc = jnp.einsum("ske,ke->se", window.astype(jnp.float32),
                     blk["conv_w"].astype(jnp.float32))
    xc, dt, B, Cm, a_neg = _mamba_gates(acc, blk, cfg)
    new = jnp.exp(dt[:, None, :] * a_neg[None]) * state \
        + (dt * xc.astype(jnp.float32))[:, None, :] * B[:, :, None]
    y = jnp.sum(new * Cm[:, :, None], axis=1)
    x, m = _mamba_out(x, y, xc, z, blk, cfg)
    conv = jnp.where(active[:, None, None], window[:, 1:].astype(conv.dtype),
                     conv)
    state = jnp.where(active[:, None, None], new, state)
    return x, conv, state, m


# ---------------------------------------------------- attention layers

def _qkv(x, blk, cfg):
    """``x (A, D)`` -> q ``(A, R, 2, 2, d)`` (K/V pair r, the query pair
    on it, first or second softmax), k ``(A, G, d)``, v ``(A, G, d)``:
    query head ``4 r + 2 p + c``, K/V head ``2 r + c``."""
    qkv = _ln(x, blk["ln1_g"], blk["ln1_b"], cfg.ln_eps) @ blk["w_qkv"] \
        + blk["b_qkv"]
    A, d = qkv.shape[0], cfg.head_dim
    Hd, Gd = cfg.hidden_size, cfg.n_kv_heads * cfg.head_dim
    q = qkv[:, :Hd].reshape(A, cfg.n_kv_heads // 2, 2, 2, d)
    k = qkv[:, Hd:Hd + Gd].reshape(A, cfg.n_kv_heads, d)
    return q, k, qkv[:, Hd + Gd:].reshape(A, cfg.n_kv_heads, d)


def _lam(blk):
    f = lambda n: blk[n].astype(jnp.float32)  # noqa: E731
    return jnp.exp(jnp.sum(f("lq1") * f("lk1"))) \
        - jnp.exp(jnp.sum(f("lq2") * f("lk2")))


def _diff_out(x, o, blk, lam0, cfg):
    """``o (A, R, 2, 2 d)`` float32, the difference of the two softmaxes
    applied to the values: the 128-wide RMSNorm, ``1 - lam0``, the
    output projection, residual, MLP."""
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.ln_eps) \
        * blk["sub_g"].astype(jnp.float32) * (1.0 - lam0)
    o = o.reshape(o.shape[0], -1).astype(x.dtype)
    return _mlp(x + o @ blk["w_o"] + blk["b_o"], blk, cfg)


def _attend_rows(q, K, V, k_own, v_own, visible, lam):
    """Differential attention of one query a slot: ``q (S, R, 2, 2,
    d)`` over the rows ``K``, ``V (S, G, d, M)`` where ``visible (S,
    M)`` and over the position's own ``k_own``, ``v_own (S, G, d)``,
    which no cache holds yet.  Returns ``(S, R, 2, 2 d)`` float32.

    ``K`` and ``V`` enter both products as they lie, heads as a batch
    dimension: a reshape between the cache's layer and the product made
    the compiler lay the layer out again (AOT; PERF.md section 6, PR
    31).  So the queries are regrouped by K/V head instead, and the
    difference of a pair's two softmaxes is handed to both value heads
    of the pair."""
    S, R, _, _, d = q.shape
    G, M = K.shape[1], K.shape[-1]
    scale = 1.0 / np.sqrt(d)
    qg = q.transpose(0, 1, 3, 2, 4).reshape(S, G, 2, d)  # head 2 r + c
    s = _einsum32("sgpd,sgdm->sgpm", qg, K) * scale
    own = _einsum32("sgpd,sgd->sgp", qg.astype(jnp.float32),
                    k_own.astype(jnp.float32)) * scale
    s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), -1)
    p = p.reshape(S, R, 2, 2, M + 1)  # (S, R, c, p, M + 1)
    a = jnp.repeat(p[:, :, 0] - lam * p[:, :, 1], 2, axis=1)  # (S, G, p, .)
    o = _einsum32("sgpm,sgdm->sgpd", a[..., :M].astype(V.dtype), V) \
        + a[..., M:] * v_own[:, :, None, :].astype(jnp.float32)
    return o.reshape(S, R, 2, 2, d).transpose(0, 1, 3, 2, 4).reshape(
        S, R, 2, 2 * d)


def _attend_chunk(q, keys, vals, mask, lam):
    """Differential attention of a chunk's queries ``q (C, R, 2, 2,
    d)`` over ``keys``, ``vals (G, d, Kn)`` under ``mask (C, Kn)``.
    Returns ``(C, R, 2, 2 d)`` float32."""
    C, R, _, _, d = q.shape
    Kn = keys.shape[-1]
    s = _einsum32("irpcd,rcdk->rpcik", q, keys.reshape(R, 2, d, Kn)) \
        / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf), -1)
    a = p[:, :, 0] - lam * p[:, :, 1]  # (R, 2, C, Kn)
    return _einsum32("rpik,rek->irpe", a.astype(vals.dtype),
                     vals.reshape(R, 2 * d, Kn))


def _ring_lane(pos, W):
    """The lane of a ring that position ``pos`` is written to."""
    return pos % W


def _ring_visible(pos, W):
    """``(S, W)``: the lanes of a ring a query at ``pos (S,)`` attends
    before its own row is landed: the ``min(pos, W - 1)`` positions
    before it, so not the lane that still holds ``pos - W``."""
    lane = jnp.arange(W)[None, :]
    return (lane != _ring_lane(pos, W)[:, None]) & (lane < pos[:, None])


@jax.named_scope("sambay.attn.window")
def _window_chunk_layer(x, blk, lam0, wk, wv, start, cfg):
    """One window layer over a chunk ``x (C, D)`` at ``start ..``: the
    slot's rings ``(G, d, W)`` hold the positions before the chunk and
    come back holding the last ``W`` up to its end."""
    C, W = x.shape[0], cfg.sliding_window
    q, k, v = _qkv(x, blk, cfg)
    t = start + jnp.arange(C)
    # A ring lane holds the latest position before the chunk at its
    # residue; a softmax does not mind the order of its keys.
    lane = jnp.arange(W)
    held = (start - 1) - (start - 1 - lane) % W
    before = (held[None, :] >= 0) & (t[:, None] - held[None, :] < W)
    lag = t[:, None] - t[None, :]
    mask = jnp.concatenate([before, (lag >= 0) & (lag < W)], -1)
    keys = jnp.concatenate([wk, k.transpose(1, 2, 0).astype(wk.dtype)], -1)
    vals = jnp.concatenate([wv, v.transpose(1, 2, 0).astype(wv.dtype)], -1)
    o = _attend_chunk(q, keys, vals, mask, _lam(blk) + lam0)
    return (_diff_out(x, o, blk, lam0, cfg), _ring_after_chunk(wk, k, start),
            _ring_after_chunk(wv, v, start))


@jax.named_scope("sambay.attn.window")
def _window_step_layer(x, blk, lam0, wk, wv, pos, cfg):
    """One window layer of the decode step: ``x (S, D)``, the rings
    ``(S, G, d, W)`` read where they lie.  Returns the hidden state and
    the new rows ``(S, G, d)``, which the caller lands at ``pos mod
    W``: the lane that still holds position ``pos - W``."""
    q, k, v = _qkv(x, blk, cfg)
    o = _attend_rows(q, wk, wv, k, v, _ring_visible(pos, cfg.sliding_window),
                     _lam(blk) + lam0)
    return _diff_out(x, o, blk, lam0, cfg), k, v


def _tail(params, cfg, x, m, K, V, pos):
    """Layers ``L / 2 + 1 ..`` on one position a row: ``x (S, D)`` out
    of the last Mamba layer with its ``m (S, E)``, row ``s`` at ``pos[s]``
    over the positions before it of slot ``s`` of the whole cache arrays
    ``K``, ``V (1, slots, G, d, M)``, ``slots >= S``.  The full layer's
    own key and value ``(S, G, d)`` come back for the caller to land at
    ``pos``; the cross layers attend them as the full layer does.  A
    decode step's second half, and a prompt's last position in the chunk
    that ends it.

    The eight layers read K and V through
    :func:`tpu_dist_nn.kernels.decode_attend.attend_rows` where its
    ``tiles`` says the shapes tile (each slot's live tiles once a layer,
    the scores on the chip), and through :func:`_attend_rows` over the
    whole extent elsewhere: only the shapes decide."""
    S = x.shape[0]
    lam0 = cfg.lambda_init()
    blk = _layer(params["attn"], cfg.n_pairs)
    with jax.named_scope("sambay.attn.full"):
        q, k, v = _qkv(x, blk, cfg)
        if decode_attend.tiles(S, *K.shape[2:], K.dtype) is not None:
            def attend(q, lam):
                return decode_attend.attend_rows(q, K, V, k, v, pos, lam)
        else:
            visible = jnp.arange(K.shape[-1])[None, :] < pos[:, None]

            def attend(q, lam):
                return _attend_rows(q, K[0, :S], V[0, :S], k, v, visible,
                                    lam)

        first = float(lam0["attn"][-1])
        x = _diff_out(x, attend(q, _lam(blk) + first), blk, first, cfg)

    def pair(x, inputs):
        i, l0 = inputs
        with jax.named_scope("sambay.gmu"):
            blk = _layer(params["gmu"], i)
            h = _ln(x, blk["ln1_g"], blk["ln1_b"], cfg.ln_eps)
            x = _mlp(x + (m * jax.nn.silu(h @ blk["wg1"])) @ blk["wg2"],
                     blk, cfg)
        with jax.named_scope("sambay.attn.cross"):
            blk = _layer(params["cross"], i)
            qc = _ln(x, blk["ln1_g"], blk["ln1_b"], cfg.ln_eps) \
                @ blk["w_q"] + blk["b_q"]
            x = _diff_out(x, attend(qc.reshape(q.shape), _lam(blk) + l0),
                          blk, l0, cfg)
        return x, None

    x, _ = lax.scan(pair, x, (jnp.arange(cfg.n_cross),
                              jnp.asarray(lam0["cross"])))
    return x, k, v


# ----------------------------------------------------------- slot cache

def _extent(max_len: int) -> int:
    """``max_len`` rounded up to whole 128-lane tiles."""
    return -(-int(max_len) // _LANES) * _LANES


def init_slot_cache(cfg: SambaYConfig, slots: int, max_len: int) -> dict:
    """The zeroed slot cache (module docstring): only ``k`` and ``v``
    grow with ``max_len``."""
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if max_len < 1 or max_len > cfg.max_seq_len:
        raise ValueError(
            f"max_len must be in [1, {cfg.max_seq_len}], got {max_len}")
    dtype = jnp.dtype(cfg.param_dtype)
    G, d, E = cfg.n_kv_heads, cfg.head_dim, cfg.d_inner
    kv = (1, slots, G, d, _extent(max_len))
    ring = (cfg.n_pairs, slots, G, d, cfg.sliding_window)
    return {
        "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
        "wk": jnp.zeros(ring, dtype), "wv": jnp.zeros(ring, dtype),
        "conv": jnp.zeros((cfg.n_mamba, slots, cfg.d_conv - 1, E), dtype),
        "state": jnp.zeros((cfg.n_mamba, slots, cfg.d_state, E),
                           jnp.dtype(cfg.state_dtype)),
    }


def cache_bytes(cache: dict) -> dict:
    """Bytes of the cache by kind, for ``tdn_gen_cache_bytes``."""
    size = lambda a: int(a.size) * a.dtype.itemsize  # noqa: E731
    return {"kv": size(cache["k"]) + size(cache["v"]),
            "window": size(cache["wk"]) + size(cache["wv"]),
            "state": size(cache["conv"]) + size(cache["state"])}


def copy_cache_slot(cache: dict, src, dst) -> dict:
    """Copy slot ``src`` onto slot ``dst``, every kind of state.  The
    scan's state and the convolution's inputs are those after the LAST
    position prefilled: the copy is a prefix's only if it ends there."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return jax.tree.map(lambda a: _put_slot(a, _take_slot(a, src), dst), cache)


def _chunk_body(params, cfg, cache, slot, tokens, start):
    """The chunk ``tokens (1, C)`` at positions ``start ..`` of slot
    ``slot`` through layers ``0 .. L / 2`` and the full layer's K/V
    projection: ``(x (C, D), m (C, E), the slot's rows, cache)``."""
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    lam0 = cfg.lambda_init()
    x = params["embed"][tokens[0]]
    mine = {name: _take_slot(a, slot) for name, a in cache.items()}
    # A chunk that starts a prompt starts from no state and no inputs:
    # a position masks a stale K/V row out, not these.
    mine["state"] = jnp.where(start == 0, 0.0, mine["state"])
    mine["conv"] = jnp.where(start == 0, 0.0, mine["conv"]).astype(
        mine["conv"].dtype)

    def pair(x, inputs):
        i, l0, conv, state, wk, wv = inputs
        x, conv, state, _ = _mamba_chunk_layer(
            x, _layer(params["mamba"], i), conv, state, cfg)
        x, wk, wv = _window_chunk_layer(
            x, _layer(params["attn"], i), l0, wk, wv, start, cfg)
        return x, (conv, state, wk, wv)

    P = cfg.n_pairs
    x, (conv, state, wk, wv) = lax.scan(pair, x, (
        jnp.arange(P), jnp.asarray(lam0["attn"][:P]), mine["conv"][:P],
        mine["state"][:P], mine["wk"], mine["wv"]))
    x, conv_last, state_last, m = _mamba_chunk_layer(
        x, _layer(params["mamba"], P), mine["conv"][P], mine["state"][P], cfg)
    with jax.named_scope("sambay.attn.full"):
        Hd = cfg.hidden_size
        blk = _layer(params["attn"], P)
        kv = _ln(x, blk["ln1_g"], blk["ln1_b"], cfg.ln_eps) \
            @ blk["w_qkv"][:, Hd:] + blk["b_qkv"][Hd:]
        k, v = (a.reshape(-1, cfg.n_kv_heads, cfg.head_dim).transpose(1, 2, 0)
                for a in jnp.split(kv, 2, axis=-1))
        at = (0, 0, 0, start)
        mine["k"] = lax.dynamic_update_slice(
            mine["k"], k[None].astype(mine["k"].dtype), at)
        mine["v"] = lax.dynamic_update_slice(
            mine["v"], v[None].astype(mine["v"].dtype), at)
    mine.update(conv=jnp.concatenate([conv, conv_last[None]]),
                state=jnp.concatenate([state, state_last[None]]),
                wk=wk, wv=wv)
    cache = {name: _put_slot(cache[name], rows, slot)
             for name, rows in mine.items()}
    return x, m, mine, cache


def prefill_body_into_cache(params: dict, cfg: SambaYConfig, cache: dict,
                            slot, tokens: jnp.ndarray, start):
    """Prefill ONE CHUNK that ends no prompt (or whose token nobody
    reads) into slot ``slot``: layers ``0 .. L / 2`` over ``tokens (1,
    C)`` at ``[start, start + C)`` and the full layer's K/V rows; no
    cross-decoder, no head.  Leaves the cache what
    :func:`prefill_chunk_into_cache` leaves: the same operations on the
    same values, bit for bit in bfloat16 (tests/test_sambay.py; two
    programs, so a float32 sum may be ordered differently: 4e-7 seen).
    Returns ``cache``."""
    return _chunk_body(params, cfg, cache, slot, tokens, start)[-1]


def prefill_chunk_into_cache(params: dict, cfg: SambaYConfig, cache: dict,
                             slot, tokens: jnp.ndarray, start):
    """Prefill ONE CHUNK into slot ``slot`` and give the logits of its
    last position: the body, then layers ``L / 2 + 1 ..`` and the head
    on that position alone.  ``slot`` and ``start`` are traced.
    Returns ``(logits (1, V), cache)``."""
    x, m, mine, cache = _chunk_body(params, cfg, cache, slot, tokens, start)
    last = jnp.asarray(start, jnp.int32) + tokens.shape[1] - 1
    with jax.named_scope("sambay.tail"):
        x, _, _ = _tail(params, cfg, x[-1:], m[-1:], mine["k"][:, None],
                        mine["v"][:, None], last[None])
        return _unembed(params, x, cfg), cache


def decode_step_slots(params: dict, cache: dict, pos: jnp.ndarray,
                      token: jnp.ndarray, cfg: SambaYConfig,
                      active: jnp.ndarray | None = None):
    """One decode step for the first ``S`` slots: ``token (S,)`` at
    per-slot ``pos (S,)``, gated by ``active (S,)``.  The window layers'
    new rows land at ``pos mod W`` and the full layer's at ``pos``, both
    in place (:func:`~tpu_dist_nn.kernels.kv_write.write_rows`); the
    scan's state and the convolution's inputs are written for active
    slots only; ``m`` lives inside the step.  Returns ``(logits (S, V),
    cache)``."""
    S = token.shape[0]
    if active is None:
        active = jnp.ones((S,), bool)
    M, W, P = cache["k"].shape[-1], cfg.sliding_window, cfg.n_pairs
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, M - 1)
    lam0 = cfg.lambda_init()
    x = params["embed"][token]

    def rows(a, layer):
        return lax.dynamic_slice(
            a, (layer,) + (0,) * (a.ndim - 1), (1, S) + a.shape[2:])[0]

    def put(a, new, layer):
        return lax.dynamic_update_slice(
            a, new[None], (layer,) + (0,) * (a.ndim - 1))

    def mamba(x, conv_all, state_all, layer):
        x, conv, state, m = _mamba_step_layer(
            x, _layer(params["mamba"], layer), rows(conv_all, layer),
            rows(state_all, layer), active, cfg)
        return x, put(conv_all, conv, layer), put(state_all, state, layer), m

    def pair(carry, inputs):
        i, l0 = inputs
        x, conv_all, state_all, _ = mamba(*carry, i)
        x, k, v = _window_step_layer(
            x, _layer(params["attn"], i), l0, rows(cache["wk"], i),
            rows(cache["wv"], i), pos, cfg)
        return (x, conv_all, state_all), (k, v)

    carry, (wk_new, wv_new) = lax.scan(
        pair, (x, cache["conv"], cache["state"]),
        (jnp.arange(P), jnp.asarray(lam0["attn"][:P])))
    x, conv_all, state_all, m = mamba(*carry, P)
    # A slot the step does not decode attends its own key alone: nothing
    # of its rows is read.
    x, k, v = _tail(params, cfg, x, m, cache["k"], cache["v"],
                    jnp.where(active, pos, 0))
    wk, wv = write_rows(cache["wk"], cache["wv"],
                        wk_new.astype(cache["wk"].dtype),
                        wv_new.astype(cache["wv"].dtype), _ring_lane(pos, W),
                        active)
    k_all, v_all = write_rows(cache["k"], cache["v"],
                              k[None].astype(cache["k"].dtype),
                              v[None].astype(cache["v"].dtype), pos, active)
    cache = {"k": k_all, "v": v_all, "wk": wk, "wv": wv, "conv": conv_all,
             "state": state_all}
    return _unembed(params, x, cfg), cache


def forward(params: dict, tokens: jnp.ndarray, cfg: SambaYConfig):
    """Full-sequence logits ``(B, T, V)`` of ``tokens (B, T)``, for
    tests: each row as one whole-prompt chunk into a scratch cache, the
    tail on every position (a row a position)."""
    T = tokens.shape[1]

    def row(toks):
        x, m, mine, _ = _chunk_body(
            params, cfg, init_slot_cache(cfg, 1, T), 0, toks[None], 0)
        spread = lambda a: jnp.broadcast_to(a, (T,) + a.shape[1:])  # noqa: E731
        x, _, _ = _tail(params, cfg, x, m, spread(mine["k"])[None],
                        spread(mine["v"])[None], jnp.arange(T))
        return _unembed(params, x, cfg)

    return jnp.stack([row(toks) for toks in tokens])


# ----------------------------------------------------------- FLOP model

class SambaYFlopModel:
    """Analytic FLOPs of the generation kernels, with the method names
    of :class:`tpu_dist_nn.obs.goodput.LMFlopModel`.  Multiply-adds
    count two.  A position costs its BODY (layers ``0 .. L / 2`` and the
    full layer's K/V projection: matrices, the convolution and the scan,
    the window layers' keys) wherever it is computed, and its TAIL
    (layers ``L / 2 + 1 ..``, the keys up to it in the full and cross
    layers, the head) only where a token is read from it: every decoded
    position, a prompt's last.  USEFUL counts the keys a position
    attends; the STATIC launch counts the whole extent for the step and
    a chunk's tail (what ``_attend_rows`` computes; where
    kernels/decode_attend.py runs it stops at each slot's frontier
    tile, and the count is an upper bound), the ring and the chunk for
    a chunk's window layers."""

    def __init__(self, cfg: SambaYConfig, cache_extent: int):
        self.cfg, self.M = cfg, _extent(cache_extent)
        D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
        N, K, R = cfg.d_state, cfg.d_conv, cfg.dt_rank
        Gd = cfg.n_kv_heads * cfg.head_dim
        mlp = 6 * D * F
        mamba = 2 * D * 2 * E + 2 * K * E + 2 * E * (R + 2 * N) \
            + 2 * R * E + 6 * E * N + 2 * E * D
        window = 2 * D * (D + 2 * Gd) + 2 * D * D
        self.W = cfg.sliding_window
        self._body = cfg.n_mamba * (mamba + mlp) \
            + cfg.n_pairs * (window + mlp) + 2 * D * 2 * Gd
        self._tail = 4 * D * D + mlp \
            + cfg.n_cross * (4 * D * E + 4 * D * D + 2 * mlp)
        self._win_key = 4 * D * cfg.n_pairs
        self._full_key = 4 * D * (1 + cfg.n_cross)
        self._logit = 2 * D * cfg.vocab_size

    def _win_keys(self, start: int, n: int) -> int:
        p = np.arange(int(start), int(start) + int(n), dtype=np.int64)
        return int(np.minimum(p + 1, self.W).sum())

    def _tail_useful(self, pos: int) -> int:
        return self._tail + self._full_key * (int(pos) + 1) + self._logit

    def step_flops(self) -> int:
        return self._body + self._win_key * self.W + self._tail \
            + self._full_key * self.M + self._logit

    def step_useful_flops(self, pos: int) -> int:
        return self._body + self._win_key * self._win_keys(pos, 1) \
            + self._tail_useful(pos)

    def steps_useful_sum(self, start_pos: int, n_steps: int) -> int:
        n, s = max(int(n_steps), 0), int(start_pos)
        return n * (self._body + self._tail + self._logit) \
            + self._win_key * self._win_keys(s, n) \
            + self._full_key * (n * s + n * (n + 1) // 2)

    def body_flops(self, size: int) -> int:
        """Static cost of a launch of the chunk program that ends
        without logits."""
        c = int(size)
        return c * (self._body + self._win_key * (self.W + c))

    def chunk_flops(self, size: int) -> int:
        return self.body_flops(size) + self._tail \
            + self._full_key * self.M + self._logit

    def chunk_useful_flops(self, start: int, size: int, final: bool) -> int:
        s, c = int(start), int(size)
        return c * self._body + self._win_key * self._win_keys(s, c) \
            + (self._tail_useful(s + c - 1) if final else 0)

    def prefill_chunks_flops(self, start: int, end: int,
                             chunk: int | None) -> int:
        """What a prefix hit of ``[start, end)`` saves: chunks that end
        no prompt, launched without logits."""
        total, pos, end = 0, int(start), int(end)
        while pos < end:
            c = end - pos if chunk is None else min(int(chunk), end - pos)
            total += self.body_flops(c)
            pos += c
        return total
