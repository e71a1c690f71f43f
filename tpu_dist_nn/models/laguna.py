"""Laguna's block family: window and full GQA layers with their own head counts, per-head gates, routed experts.

A fifth block family beside GPT-2's (:mod:`.transformer`), MiniCPM-SALA's
(:mod:`.sala`), SambaY's (:mod:`.sambay`) and Kimi-K2's (:mod:`.mla_moe`):
RMSNorm, SwiGLU, an UNTIED head, and in every layer

* **grouped-query attention** over ``n_kv_heads`` K/V heads of
  ``head_dim``, with as many query heads as the layer's KIND gives
  (``num_attention_heads_per_layer``: 48 on a full layer, 72 on a window
  layer of the published model, so groups of 6 and 9).  No bias, no
  QK-norm.  A **full** layer attends every position up to its own, with
  **partial YaRN** rotary (the first ``full_rotary_dim`` of a head's
  dimensions, YaRN frequencies, cos and sin scaled by the
  ``attention_factor``); a **window** layer attends its own position and
  the ``sliding_window - 1`` before it, with plain RoPE on every
  dimension.  Each key is rotated ONCE, at the position it is written
  for, with its own layer kind's scheme: a window layer's ring of the
  last ``W`` positions (lane ``pos mod W``, :func:`.sambay._ring_lane`)
  holds keys already rotated, so relative rotation holds across the
  wrap and nothing is rotated at read time;
* a **per-head sigmoid output gate**: one scalar a head, ``sigmoid(u
  w_gate,i)`` of the same normed input ``u`` as q, k and v, on the
  head's output before ``W_o``;
* a feed-forward that is a dense SwiGLU in the ``mlp_only_layers`` and
  a **routed mixture** in the others: a softmax over ``router_width``
  experts, the top ``k`` renormalised over the ``k`` and scaled by
  ``moe_routed_scaling_factor``, plus a shared expert.  The expert
  layer is :mod:`.experts`, told which experts this chip holds
  (``experts_held``) as Kimi-K2's is; the router is this family's own
  (:func:`route`).

Parameters are stacked BY KIND (``params["full"]``, ``params["window"]``
for attention, ``params["dense"]``, ``params["moe"]`` for the
feed-forward), and the stack is walked as runs of one (attention,
feed-forward) kind (:meth:`LagunaConfig.runs`), each run one
``lax.scan``.

The slot cache: the full layers' K and V ``(Lf, S, G, d, M)`` (positions
in the 128 lanes, PR 25's rule), the window layers' rings ``(Lw, S, G,
d, W)``, both landed in place by
:func:`tpu_dist_nn.kernels.kv_write.write_rows` in a step; and
``routed``, the expert layer's running routing counts.
docs/MODEL_CONFIG.md has the equations' provenance; the plain
reference is benchmark/configs/laguna_reference.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_dist_nn.kernels.kv_write import write_rows
from tpu_dist_nn.models import experts
from tpu_dist_nn.models.experts import experts_form
from tpu_dist_nn.models.mla_moe import yarn_freqs
from tpu_dist_nn.models.sala import (
    _einsum32,
    _put_slot,
    _ring_after_chunk,
    _rms,
    _take_slot,
)
from tpu_dist_nn.models.sambay import _ring_lane, _ring_visible
from tpu_dist_nn.models.slot_model import SlotModel

FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
_LANES = 128
# Key positions a full layer's chunk attends at a time.
_KEY_TILES = (512, 256, 128)
# Queries a window layer's chunk attends at a time: each tile reads its
# own positions and the window before them, never the whole extent.
_QUERY_TILE = 512


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Static description of one stack (hashable)."""

    vocab_size: int
    hidden_size: int
    head_dim: int
    n_kv_heads: int
    layer_types: tuple
    mlp_layer_types: tuple
    heads_per_layer: tuple
    intermediate_size: int
    moe_intermediate_size: int
    shared_intermediate_size: int
    router_width: int
    experts_held: tuple
    n_experts_per_tok: int
    routed_scaling_factor: float
    sliding_window: int
    max_seq_len: int
    full_rope_theta: float = 500000.0
    full_rotary_dim: int = 64
    rope_factor: float = 1.0
    rope_original_len: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    attention_factor: float = 1.0
    window_rope_theta: float = 10000.0
    window_rotary_dim: int = 128
    rms_eps: float = 1e-6
    param_dtype: str = "bfloat16"

    causal = True  # the generation contract asks

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types", "heads_per_layer",
                     "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        L = len(self.layer_types)
        if not L or len(self.mlp_layer_types) != L or \
                len(self.heads_per_layer) != L:
            raise ValueError(
                "layer_types, mlp_layer_types and "
                "num_attention_heads_per_layer must name every layer")
        if set(self.layer_types) - {FULL, WINDOW} or \
                set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(
                f"layer kinds are {FULL!r}/{WINDOW!r} and {DENSE!r}/"
                f"{SPARSE!r}, got {set(self.layer_types)} and "
                f"{set(self.mlp_layer_types)}")
        for kind in (FULL, WINDOW):
            counts = {h for a, h in zip(self.layer_types,
                                        self.heads_per_layer) if a == kind}
            if len(counts) > 1:
                raise ValueError(
                    f"every {kind} layer must have the same head count "
                    f"(they are stacked by kind), got {sorted(counts)}")
            if counts and next(iter(counts)) % self.n_kv_heads:
                raise ValueError(
                    f"{kind} heads {counts} are no multiple of "
                    f"{self.n_kv_heads} K/V heads")
        held = self.experts_held
        if not held or len(set(held)) != len(held) or \
                min(held) < 0 or max(held) >= self.router_width:
            raise ValueError(
                f"experts_held {held} must be distinct ids in "
                f"[0, {self.router_width})")
        if not 0 < self.n_experts_per_tok <= self.router_width:
            raise ValueError("num_experts_per_tok must be in (0, router width]")
        if SPARSE not in self.mlp_layer_types:
            raise ValueError("mlp_layer_types must leave an expert layer")
        for dim in (self.full_rotary_dim, self.window_rotary_dim):
            if dim % 2 or not 0 < dim <= self.head_dim:
                raise ValueError(
                    f"a rotary width {dim} must be even and at most "
                    f"head_dim {self.head_dim}")

    # ------------------------------------------------------------ sizes
    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def _count(self, kinds, kind) -> int:
        return sum(1 for k in kinds if k == kind)

    @property
    def n_full(self) -> int:
        return self._count(self.layer_types, FULL)

    @property
    def n_window(self) -> int:
        return self._count(self.layer_types, WINDOW)

    @property
    def n_dense(self) -> int:
        return self._count(self.mlp_layer_types, DENSE)

    @property
    def n_moe(self) -> int:
        return self._count(self.mlp_layer_types, SPARSE)

    @property
    def n_held(self) -> int:
        return len(self.experts_held)

    def heads(self, kind: str) -> int:
        """Query heads of a layer of attention kind ``kind``."""
        return next(h for a, h in zip(self.layer_types, self.heads_per_layer)
                    if a == kind)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(f"{a}+{m}" for a, m in zip(self.layer_types,
                                                self.mlp_layer_types))

    def runs(self) -> tuple:
        """Maximal runs of layers of one (attention, feed-forward) kind:
        ``(attn, mlp, first layer, end, first of the attention kind,
        first of the feed-forward kind)``."""
        out, seen = [], {}
        for i, kinds in enumerate(zip(self.layer_types,
                                      self.mlp_layer_types)):
            if out and tuple(out[-1][:2]) == kinds:
                out[-1][3] = i + 1
            else:
                out.append([*kinds, i, i + 1, seen.get(kinds[0], 0),
                            seen.get(kinds[1], 0)])
            for k in kinds:
                seen[k] = seen.get(k, 0) + 1
        return tuple(tuple(r) for r in out)

    def rope_freqs(self, kind: str) -> np.ndarray:
        """A layer kind's angular frequencies ``w_j``, ``j < rotary / 2``:
        YaRN's on a full layer, plain ``theta^(-2j/dim)`` on a window
        layer."""
        if kind == FULL:
            return yarn_freqs(self.full_rotary_dim, self.full_rope_theta,
                              self.rope_factor, self.rope_original_len,
                              self.rope_beta_fast, self.rope_beta_slow)
        d = self.window_rotary_dim
        return self.window_rope_theta ** (
            -2.0 * np.arange(d // 2, dtype=np.float64) / d)

    def cast_params(self, params):
        dtype = jnp.dtype(self.param_dtype)
        return jax.tree.map(
            lambda a: a if a.dtype == dtype else a.astype(dtype), params)

    def init_params(self, key):
        return init_laguna(key, self)

    def num_params(self) -> int:
        return num_params(self)

    def slot_model(self) -> SlotModel:
        return SlotModel(
            init_slot_cache=init_slot_cache,
            prefill_chunk_into_cache=prefill_chunk_into_cache,
            decode_step_slots=decode_step_slots,
            copy_cache_slot=copy_cache_slot,
            flop_model=LagunaFlopModel,
            cache_bytes=cache_bytes,
            # A ring holds the last W positions before wherever its slot
            # got to: a prefix's only where a chunk ended.
            recurrent=True,
            routing_counts=self.routing_counts,
        )

    def routing_counts(self, cache: dict) -> dict:
        """The cache's routing counts by name (:func:`.experts.
        routing_counts`)."""
        return experts.routing_counts(cache["routed"], self.n_held)

    # ---------------------------------------------------------- loading
    @classmethod
    def from_dict(cls, d: dict) -> "LagunaConfig":
        """From a ``config.json`` in the source's own keys (``model_type:
        laguna``).  ``num_experts`` counts the experts HELD here where the
        file is a chip's share; ``router_width`` (default: the same) is
        what the router scores, and ``experts_held.ids`` which of them
        these are (default: the first).  The per-layer lists are read
        up to ``num_hidden_layers``."""
        if d.get("model_type") != "laguna":
            raise ValueError(
                f"model_type {d.get('model_type')!r} is not 'laguna'")
        for key, want in (("norm_topk_prob", True), ("decoder_sparse_step", 1),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False),
                          ("moe_apply_router_weight_on_input", False),
                          ("moe_router_logit_softcapping", 0),
                          ("gating", "per-head")):
            if d.get(key, want) != want:
                raise ValueError(
                    f"{key} must be {want!r}: weights normalised over the "
                    "chosen, every layer past the dense ones routed, an "
                    "untied head, no bias, weights applied behind the "
                    "experts, no soft cap, one output gate a head")
        if set(d.get("gating_types", ("per_head",))) != {"per_head"}:
            raise ValueError("gating_types must all be 'per_head'")
        L = int(d["num_hidden_layers"])
        kinds = tuple(d["layer_types"])[:L]
        mlp = tuple(d["mlp_layer_types"])[:L]
        heads = tuple(int(h) for h in d["num_attention_heads_per_layer"])[:L]
        rope = d.get("rope_parameters") or {}
        full, window = rope.get(FULL, {}), rope.get(WINDOW, {})
        for name, part in ((FULL, full), (WINDOW, window)):
            if part.get("rope_type", "default") not in ("default", "yarn"):
                raise ValueError(
                    f"rope_parameters.{name}.rope_type must be 'default' "
                    "or 'yarn'")
        if window.get("rope_type", "default") != "default":
            raise ValueError("a window layer's rotary is plain RoPE")
        d_head = int(d["head_dim"])
        yarn = full.get("rope_type") == "yarn"
        n_held = int(d["num_experts"])
        width = int(d.get("router_width", n_held))
        ids = (d.get("experts_held") or {}).get("ids")
        held = tuple(range(n_held)) if ids is None else tuple(ids)
        if len(held) != n_held:
            raise ValueError(
                f"experts_held.ids names {len(held)} experts, "
                f"num_experts says {n_held} are held")
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]),
            head_dim=d_head,
            n_kv_heads=int(d["num_key_value_heads"]),
            layer_types=kinds, mlp_layer_types=mlp, heads_per_layer=heads,
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            shared_intermediate_size=int(
                d["shared_expert_intermediate_size"]),
            router_width=width, experts_held=held,
            n_experts_per_tok=int(d["num_experts_per_tok"]),
            routed_scaling_factor=float(d.get("moe_routed_scaling_factor",
                                              1.0)),
            sliding_window=int(d["sliding_window"]),
            max_seq_len=int(d["max_position_embeddings"]),
            full_rope_theta=float(full.get("rope_theta", 10000.0)),
            full_rotary_dim=int(round(
                d_head * float(full.get("partial_rotary_factor", 1.0)))),
            rope_factor=float(full.get("factor", 1.0)) if yarn else 1.0,
            rope_original_len=int(full.get(
                "original_max_position_embeddings",
                d["max_position_embeddings"])),
            rope_beta_fast=float(full.get("beta_fast", 32)),
            rope_beta_slow=float(full.get("beta_slow", 1)),
            attention_factor=float(full.get("attention_factor", 1.0))
            if yarn else 1.0,
            window_rope_theta=float(window.get("rope_theta", 10000.0)),
            window_rotary_dim=int(round(
                d_head * float(window.get("partial_rotary_factor", 1.0)))),
            rms_eps=float(d.get("rms_norm_eps", 1e-6)),
            param_dtype=str(d.get("param_dtype", "bfloat16")),
        )


# ------------------------------------------------------------ parameters

def param_shapes(cfg: LagunaConfig) -> dict:
    D, V, G, d = cfg.hidden_size, cfg.vocab_size, cfg.n_kv_heads, cfg.head_dim
    F0, Fe, Fs = cfg.intermediate_size, cfg.moe_intermediate_size, \
        cfg.shared_intermediate_size
    Lm, N = cfg.n_moe, cfg.n_held

    def attn(L, H):
        return {"ln1_g": (L, D), "ln2_g": (L, D), "w_q": (L, D, H * d),
                "w_k": (L, D, G * d), "w_v": (L, D, G * d),
                "w_gate": (L, D, H), "w_o": (L, H * d, D)}

    n = {FULL: cfg.n_full, WINDOW: cfg.n_window}
    return {
        "embed": (V, D), "head": (V, D), "lnf_g": (D,),
        "full": attn(n[FULL], cfg.heads(FULL) if n[FULL] else 0),
        "window": attn(n[WINDOW], cfg.heads(WINDOW) if n[WINDOW] else 0),
        "dense": {"w_gu": (cfg.n_dense, D, 2 * F0),
                  "w_d": (cfg.n_dense, F0, D)},
        "moe": {"w_r": (Lm, D, cfg.router_width), "sh_gu": (Lm, D, 2 * Fs),
                "sh_d": (Lm, Fs, D), "ex_gu": (Lm, N, D, 2 * Fe),
                "ex_d": (Lm, N, Fe, D)},
    }


def num_params(cfg: LagunaConfig) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_laguna(key: jax.Array, cfg: LagunaConfig):
    """Seeded random parameters in ``cfg.param_dtype``: matrices N(0,
    1/fan_in), embedding and head N(0, 1/hidden_size), gains 1 + N(0,
    0.02).  A stacked leaf is drawn a layer at a time, and an expert's
    matrices from (leaf, layer, expert id) alone: every share of a layer
    holds the same expert e, and no float32 draw is larger than one
    expert's matrix."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(cfg.param_dtype)
    held = jnp.asarray(cfg.experts_held, jnp.int32)

    def draw(k, name, shape):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("embed", "head"):
            z = z / np.sqrt(shape[-1])
        elif name.endswith("_g"):
            z = 1.0 + 0.02 * z
        else:
            z = z / np.sqrt(shape[-2])
        return z.astype(dtype)

    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(leaves)), leaves):
        name = path[-1].key
        if len(shape) < 3 or not shape[0]:
            out.append(draw(k, name, shape))
        elif name.startswith("ex_"):
            out.append(lax.map(lambda i: lax.map(
                lambda e: draw(jax.random.fold_in(
                    jax.random.fold_in(k, i), e), name, shape[2:]), held),
                jnp.arange(shape[0])))
        else:
            out.append(lax.map(lambda i: draw(jax.random.fold_in(k, i), name,
                                              shape[1:]),
                               jnp.arange(shape[0])))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- the math

def _layer(stack: dict, i):
    """Layer ``i`` (traced or not) of stacked parameters."""
    return jax.tree.map(
        lambda p: lax.dynamic_index_in_dim(p, i, 0, keepdims=False), stack)


def _swiglu(u, w_gu, w_d):
    gu = u @ w_gu
    F = w_d.shape[0]
    return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w_d


def _unembed(params, x, cfg):
    return _einsum32("ad,vd->av", _rms(x, params["lnf_g"], cfg.rms_eps),
                     params["head"])


def _rope(x, pos, cfg: LagunaConfig, kind: str):
    """Rotate the first rotary dimensions of ``x (A, heads, d)`` at
    ``pos (A,)`` with layer kind ``kind``'s scheme; the rest pass as they
    are.  Within the rotated block plane ``j`` pairs dimension ``j`` with
    ``j + rotary / 2`` (the configuration's ``assumed.rope_pairing``);
    on a full layer cos and sin carry YaRN's ``attention_factor``."""
    freqs = cfg.rope_freqs(kind)
    r = 2 * len(freqs)
    scale = cfg.attention_factor if kind == FULL else 1.0
    ang = jnp.asarray(pos, jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    a, b = xf[..., :r // 2], xf[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, xf[..., r:]],
                           -1).astype(x.dtype)


@jax.named_scope("laguna.attn.project")
def _project(x, blk, pos, cfg, kind):
    """``x (A, D)`` at ``pos (A,)`` -> the normed input ``u``, queries
    ``(A, G, group, d)`` and keys ``(A, G, d)`` rotated at ``pos``,
    values ``(A, G, d)``, and the heads' gates ``(A, H)`` float32."""
    A, G, d = x.shape[0], cfg.n_kv_heads, cfg.head_dim
    u = _rms(x, blk["ln1_g"], cfg.rms_eps)
    q = _rope((u @ blk["w_q"]).reshape(A, -1, d), pos, cfg, kind)
    k = _rope((u @ blk["w_k"]).reshape(A, G, d), pos, cfg, kind)
    v = (u @ blk["w_v"]).reshape(A, G, d)
    gate = jax.nn.sigmoid(_einsum32("ad,dh->ah", u, blk["w_gate"]))
    return u, q.reshape(A, G, -1, d), k, v, gate


@jax.named_scope("laguna.attn.project")
def _attn_out(x, o, gate, blk):
    """``o (A, G, group, d)`` float32 -> each head scaled by its own gate
    ``(A, H)``, then ``W_o`` and the residual."""
    A = o.shape[0]
    o = o.reshape(A, gate.shape[1], -1) * gate[:, :, None]
    return x + o.reshape(A, -1).astype(x.dtype) @ blk["w_o"]


def _key_tile(M: int) -> int:
    return next((t for t in _KEY_TILES if M % t == 0), M)


@jax.named_scope("laguna.attn.full")
def _full_chunk_attend(q, k_rows, v_rows, t):
    """A full layer's chunk: queries ``q (C, G, g, d)`` at positions ``t
    (C,)`` (consecutive) over the slot's rows ``(G, d, M)``, which
    already hold the chunk's own; a running softmax in float32 over the
    key tiles up to the chunk's last position.  ``(C, G, g, d)``
    float32."""
    C, G, g, d = q.shape
    M = k_rows.shape[-1]
    KT = _key_tile(M)
    qh = q.transpose(1, 2, 0, 3)  # (G, g, C, d)

    def tile(j, carry):
        m, l, acc = carry
        kt = lax.dynamic_slice(k_rows, (0, 0, j * KT), (G, d, KT))
        vt = lax.dynamic_slice(v_rows, (0, 0, j * KT), (G, d, KT))
        s = _einsum32("gqcd,gdk->gqck", qh, kt) / np.sqrt(d)
        seen = (j * KT + jnp.arange(KT))[None, :] <= t[:, None]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        # The first tile holds position 0, which every query sees: no
        # row of `m_new` is -inf.
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + _einsum32(
            "gqck,gdk->gqcd", p.astype(v_rows.dtype), vt)
        return m_new, l * fade + jnp.sum(p, -1), acc

    init = (jnp.full((G, g, C), -jnp.inf, jnp.float32),
            jnp.zeros((G, g, C), jnp.float32),
            jnp.zeros((G, g, C, d), jnp.float32))
    _, l, acc = lax.fori_loop(0, t[-1] // KT + 1, tile, init)
    return (acc / l[..., None]).transpose(2, 0, 1, 3)


@jax.named_scope("laguna.attn.window")
def _window_chunk_attend(q, k, v, wk, wv, start, W):
    """A window layer's chunk: queries ``q (C, G, g, d)`` at ``start ..``
    over the ring ``wk``, ``wv (G, d, W)`` (the ``W`` positions before
    the chunk, lane ``pos mod W``) and the chunk's own keys and values
    ``(C, G, d)``.  Laid out in position order (``start - W ..``), a
    tile of ``QT`` queries attends the ``QT + W`` keys its window can
    reach, with a plain softmax in float32: ``t - s < W``, ``s <= t``,
    ``s >= 0``.  ``(C, G, g, d)`` float32."""
    C, G, g, d = q.shape
    QT = min(C, _QUERY_TILE)
    n = -(-C // QT)
    pad = n * QT - C
    order = (start + jnp.arange(W)) % W  # ring lanes in position order

    def line(ring, new):
        new = jnp.pad(new.transpose(1, 2, 0).astype(ring.dtype),
                      ((0, 0), (0, 0), (0, pad)))
        return jnp.concatenate([jnp.take(ring, order, axis=-1), new], -1)

    keys, vals = line(wk, k), line(wv, v)  # (G, d, W + n QT)
    qt = jnp.pad(q, ((0, pad),) + ((0, 0),) * 3).reshape(n, QT, G, g, d)

    def tile(args):
        j, qj = args
        kt = lax.dynamic_slice(keys, (0, 0, j * QT), (G, d, QT + W))
        vt = lax.dynamic_slice(vals, (0, 0, j * QT), (G, d, QT + W))
        s = _einsum32("cgqd,gdk->gqck", qj, kt) / np.sqrt(d)
        tq = start + j * QT + jnp.arange(QT)
        ts = start - W + j * QT + jnp.arange(QT + W)
        lag = tq[:, None] - ts[None, :]
        seen = (lag >= 0) & (lag < W) & (ts >= 0)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return _einsum32("gqck,gdk->cgqd", p.astype(vals.dtype), vt)

    o = lax.map(tile, (jnp.arange(n), qt))
    return o.reshape(n * QT, G, g, d)[:C]


def _attend_rows(q, K, V, k_own, v_own, visible):
    """One query a slot: ``q (S, G, g, d)`` over the rows ``K``, ``V (S,
    G, d, M)`` where ``visible (S, M)`` and over the position's own
    ``k_own``, ``v_own (S, G, d)``, which no cache holds yet; one
    softmax in float32 over both.  The rows enter both products as they
    lie.  ``(S, G, g, d)`` float32."""
    M, d = K.shape[-1], K.shape[-2]
    s = _einsum32("sgqd,sgdm->sgqm", q, K) / np.sqrt(d)
    own = _einsum32("sgqd,sgd->sgq", q.astype(jnp.float32),
                    k_own.astype(jnp.float32)) / np.sqrt(d)
    s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), -1)
    return _einsum32("sgqm,sgdm->sgqd", p[..., :M].astype(V.dtype), V) \
        + p[..., M:] * v_own[:, :, None, :].astype(jnp.float32)


@jax.named_scope("laguna.mlp")
def _dense_ffn(x, norm_g, blk, cfg):
    return x + _swiglu(_rms(x, norm_g, cfg.rms_eps), blk["w_gu"], blk["w_d"])


@jax.named_scope("laguna.router")
def route(u, blk, cfg):
    """``u (A, D)`` -> the chosen experts ``(A, k)`` and their weights
    ``(A, k)`` float32: a softmax over every expert, the top ``k``
    renormalised over the ``k`` and scaled.  The product, the softmax
    and ``top_k`` in float32 (a flipped choice is another expert, not a
    rounding)."""
    p = jax.nn.softmax(jnp.einsum(
        "ad,de->ae", u.astype(jnp.float32), blk["w_r"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST), -1)
    w, chosen = lax.top_k(p, cfg.n_experts_per_tok)
    return chosen, w / jnp.sum(w, -1, keepdims=True) \
        * cfg.routed_scaling_factor


@jax.named_scope("laguna.shared")
def _shared_expert(u, blk):
    return _swiglu(u, blk["sh_gu"], blk["sh_d"])


def _moe_ffn(x, norm_g, moe, layer, counted, cfg):
    """``x (A, D)`` through expert layer ``layer`` of the stacked
    ``moe`` parameters: the shared expert plus the held experts' part of
    the routed sum (:mod:`.experts`), and the layer's routing counts
    over the tokens ``counted (A,)``."""
    blk = _layer({n: a for n, a in moe.items() if not n.startswith("ex_")},
                 layer)
    u = _rms(x, norm_g, cfg.rms_eps)
    chosen, w = route(u, blk, cfg)
    on, gates = experts.held_gates(chosen, w, cfg.experts_held)
    routed, pairs = experts.routed(u, on, gates, moe["ex_gu"], moe["ex_d"],
                                   layer, counted, "laguna.experts")
    return x + _shared_expert(u, blk) + routed.astype(x.dtype), pairs


def _ffn(x, blk, params, mlp, i, counted, cfg):
    """The feed-forward of kind ``mlp``, the ``i``-th of its kind:
    ``(x, pairs (n_held,))``, no pairs in a dense layer."""
    if mlp == DENSE:
        return _dense_ffn(x, blk["ln2_g"], _layer(params["dense"], i),
                          cfg), jnp.zeros((cfg.n_held,), jnp.int32)
    return _moe_ffn(x, blk["ln2_g"], params["moe"], i, counted, cfg)


def _counts(cfg, pairs, tokens, step: bool):
    """What one launch adds to ``cache["routed"]``: ``pairs (n_moe,
    n_held)`` (:func:`.experts.counts`)."""
    return experts.counts(cfg.n_moe, cfg.n_held, cfg.n_experts_per_tok,
                          pairs, tokens, step)


# ----------------------------------------------------------- slot cache

def _extent(max_len: int) -> int:
    """``max_len`` rounded up to whole 128-lane tiles."""
    return -(-int(max_len) // _LANES) * _LANES


def init_slot_cache(cfg: LagunaConfig, slots: int, max_len: int) -> dict:
    """The zeroed slot cache (module docstring): only the full layers'
    ``k`` and ``v`` grow with ``max_len``."""
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if max_len < 1 or max_len > cfg.max_seq_len:
        raise ValueError(
            f"max_len must be in [1, {cfg.max_seq_len}], got {max_len}")
    dtype = jnp.dtype(cfg.param_dtype)
    G, d = cfg.n_kv_heads, cfg.head_dim
    kv = (cfg.n_full, slots, G, d, _extent(max_len))
    ring = (cfg.n_window, slots, G, d, cfg.sliding_window)
    return {
        "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
        "wk": jnp.zeros(ring, dtype), "wv": jnp.zeros(ring, dtype),
        "routed": jnp.zeros((cfg.n_held + 3,), jnp.int32),
    }


def cache_bytes(cache: dict) -> dict:
    """Bytes of the cache by kind, for ``tdn_gen_cache_bytes``."""
    size = lambda a: int(a.size) * a.dtype.itemsize  # noqa: E731
    return {"kv": size(cache["k"]) + size(cache["v"]),
            "window": size(cache["wk"]) + size(cache["wv"])}


_ROWS = ("k", "v", "wk", "wv")


def copy_cache_slot(cache: dict, src, dst) -> dict:
    """Copy slot ``src``'s K/V rows and rings onto slot ``dst``.  Rows
    past a prefix's length ride along and are masked by position; a
    ring is the prefix's only where a chunk ended (``recurrent``); the
    routing counts belong to no slot."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return dict(cache, **{n: _put_slot(cache[n], _take_slot(cache[n], src),
                                       dst) for n in _ROWS})


def _chunk_hidden(params, cfg, cache, slot, tokens, start):
    """The chunk ``tokens (1, C)`` at positions ``start ..`` of slot
    ``slot`` through every layer: ``(x (C, D), cache)``."""
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    C, W = tokens.shape[1], cfg.sliding_window
    t = start + jnp.arange(C)
    x = params["embed"][tokens[0]]
    every = jnp.ones((C,), bool)
    mine = {n: _take_slot(cache[n], slot) for n in _ROWS}
    pairs = []

    def full(x, blk, rows):
        k_rows, v_rows = rows
        _, q, k, v, gate = _project(x, blk, t, cfg, FULL)
        at = (0, 0, start)
        k_rows = lax.dynamic_update_slice(
            k_rows, k.transpose(1, 2, 0).astype(k_rows.dtype), at)
        v_rows = lax.dynamic_update_slice(
            v_rows, v.transpose(1, 2, 0).astype(v_rows.dtype), at)
        o = _full_chunk_attend(q, k_rows, v_rows, t)
        return _attn_out(x, o, gate, blk), (k_rows, v_rows)

    def window(x, blk, rings):
        wk, wv = rings
        _, q, k, v, gate = _project(x, blk, t, cfg, WINDOW)
        o = _window_chunk_attend(q, k, v, wk, wv, start, W)
        with jax.named_scope("laguna.attn.window"):
            rings = (_ring_after_chunk(wk, k, start),
                     _ring_after_chunk(wv, v, start))
        return _attn_out(x, o, gate, blk), rings

    for attn, mlp, a, b, ai, fi in cfg.runs():
        names = ("k", "v") if attn == FULL else ("wk", "wv")
        group = "full" if attn == FULL else "window"

        def body(x, inputs, attn=attn, mlp=mlp, group=group):
            i, j, rows = inputs
            blk = _layer(params[group], i)
            x, rows = (full if attn == FULL else window)(x, blk, rows)
            x, p = _ffn(x, blk, params, mlp, j, every, cfg)
            return x, (rows, p)

        n = b - a
        x, (rows, p) = lax.scan(body, x, (
            ai + jnp.arange(n), fi + jnp.arange(n),
            tuple(mine[m][ai:ai + n] for m in names)))
        for m, r in zip(names, rows):
            mine[m] = lax.dynamic_update_slice(
                mine[m], r, (ai,) + (0,) * (r.ndim - 1))
        if mlp == SPARSE:
            pairs.append(p)
    cache = dict(cache, **{n: _put_slot(cache[n], mine[n], slot)
                           for n in _ROWS})
    cache["routed"] = cache["routed"] + _counts(
        cfg, jnp.concatenate(pairs), jnp.int32(C), False)
    return x, cache


def prefill_chunk_into_cache(params: dict, cfg: LagunaConfig, cache: dict,
                             slot, tokens: jnp.ndarray, start):
    """Prefill ONE CHUNK into slot ``slot`` and give the logits of its
    last position.  ``tokens (1, C)`` at ``[start, start + C)``; the full
    layers' rows land at their positions, each window layer's ring comes
    back holding the last ``W`` positions up to the chunk's end, keys
    rotated.  ``slot`` and ``start`` are traced.  Returns ``(logits (1,
    V), cache)``."""
    x, cache = _chunk_hidden(params, cfg, cache, slot, tokens, start)
    return _unembed(params, x[-1:], cfg), cache


def decode_step_slots(params: dict, cache: dict, pos: jnp.ndarray,
                      token: jnp.ndarray, cfg: LagunaConfig,
                      active: jnp.ndarray | None = None):
    """One decode step for the first ``S`` slots: ``token (S,)`` at
    per-slot ``pos (S,)``, gated by ``active (S,)``.  Every layer reads
    its rows where they lie; the full layers' new rows land at ``pos``
    and the window layers' at ``pos mod W`` (the lane that still holds
    ``pos - W``), in place; an inactive slot's rows stay bit for bit and
    its tokens are in no routing count.  Returns ``(logits (S, V),
    cache)``."""
    S = token.shape[0]
    if active is None:
        active = jnp.ones((S,), bool)
    M, W = cache["k"].shape[-1], cfg.sliding_window
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, M - 1)
    # A slot the step does not decode attends its own row alone: nothing
    # of its rows is read into a softmax.
    live = jnp.where(active, pos, 0)
    x = params["embed"][token]

    def rows(a, layer):
        return lax.dynamic_slice(
            a, (layer,) + (0,) * (a.ndim - 1), (1, S) + a.shape[2:])[0]

    def full(x, blk, i):
        _, q, k, v, gate = _project(x, blk, pos, cfg, FULL)
        with jax.named_scope("laguna.attn.full"):
            visible = jnp.arange(M)[None, :] < live[:, None]
            o = _attend_rows(q, rows(cache["k"], i), rows(cache["v"], i), k,
                             v, visible)
        return _attn_out(x, o, gate, blk), (k, v)

    def window(x, blk, i):
        _, q, k, v, gate = _project(x, blk, pos, cfg, WINDOW)
        with jax.named_scope("laguna.attn.window"):
            visible = _ring_visible(live, W)
            o = _attend_rows(q, rows(cache["wk"], i), rows(cache["wv"], i),
                             k, v, visible)
        return _attn_out(x, o, gate, blk), (k, v)

    new = {FULL: [], WINDOW: []}
    pairs = []
    for attn, mlp, a, b, ai, fi in cfg.runs():
        group = "full" if attn == FULL else "window"

        def body(x, inputs, attn=attn, mlp=mlp, group=group):
            i, j = inputs
            blk = _layer(params[group], i)
            x, kv = (full if attn == FULL else window)(x, blk, i)
            x, p = _ffn(x, blk, params, mlp, j, active, cfg)
            return x, (kv, p)

        n = b - a
        x, (kv, p) = lax.scan(body, x, (ai + jnp.arange(n),
                                        fi + jnp.arange(n)))
        new[attn].append(kv)
        if mlp == SPARSE:
            pairs.append(p)

    def land(k_all, v_all, parts, at):
        if not parts:
            return k_all, v_all
        return write_rows(
            k_all, v_all,
            jnp.concatenate([k for k, _ in parts]).astype(k_all.dtype),
            jnp.concatenate([v for _, v in parts]).astype(v_all.dtype),
            at, active)

    k_all, v_all = land(cache["k"], cache["v"], new[FULL], pos)
    wk, wv = land(cache["wk"], cache["wv"], new[WINDOW], _ring_lane(pos, W))
    cache = {"k": k_all, "v": v_all, "wk": wk, "wv": wv,
             "routed": cache["routed"] + _counts(
                 cfg, jnp.concatenate(pairs),
                 jnp.sum(active, dtype=jnp.int32), True)}
    return _unembed(params, x, cfg), cache


def forward(params: dict, tokens: jnp.ndarray, cfg: LagunaConfig):
    """Full-sequence logits ``(B, T, V)`` of ``tokens (B, T)``, for
    tests: each row as one whole-prompt chunk into a scratch cache."""
    T = tokens.shape[1]

    def row(toks):
        x, _ = _chunk_hidden(params, cfg, init_slot_cache(cfg, 1, T), 0,
                             toks[None], 0)
        return _unembed(params, x, cfg)

    return jnp.stack([row(toks) for toks in tokens])


# ----------------------------------------------------------- FLOP model

class LagunaFlopModel:
    """Analytic FLOPs of the generation kernels, with the method names
    of :class:`tpu_dist_nn.obs.goodput.LMFlopModel`.  Multiply-adds
    count two.  USEFUL counts, a position: every layer's projections (q,
    k, v, the heads' gates, o), the dense layers' SwiGLU, the router and
    the shared expert, and of the routed experts the ``k n_held /
    router_width`` pairs a token sends to experts held HERE on average
    (the device's own count is ``tdn_gen_expert_pairs_total``); and the
    keys it attends, ``4 H d`` a key: a full layer's ``pos + 1``, a
    window layer's ``min(pos + 1, W)``.  The STATIC launch counts what
    the programs compute: a step's whole extent on a full layer, the
    whole ring on a window layer and every held expert on every token; a
    chunk's whole extent on a full layer (an upper bound: its key tiles
    stop at its end), ``QT + W`` keys a query on a window layer, and
    where its product is ragged the average pairs or a tile of every
    held expert, whichever is more."""

    def __init__(self, cfg: LagunaConfig, cache_extent: int):
        self.cfg, self.M = cfg, _extent(cache_extent)
        D, G, d = cfg.hidden_size, cfg.n_kv_heads, cfg.head_dim
        self._expert = 6 * D * cfg.moe_intermediate_size
        self._fixed = sum(2 * D * (2 * H * d + 2 * G * d + H)
                          for H in cfg.heads_per_layer) \
            + cfg.n_dense * 6 * D * cfg.intermediate_size \
            + cfg.n_moe * (2 * D * cfg.router_width
                           + 6 * D * cfg.shared_intermediate_size)
        # FLOPs a key and position, summed over the layers of a kind.
        self._key = {kind: sum(4 * H * d for a, H in zip(
            cfg.layer_types, cfg.heads_per_layer) if a == kind)
            for kind in (FULL, WINDOW)}
        self._logit = 2 * D * cfg.vocab_size
        self._pairs = (cfg.n_experts_per_tok * cfg.n_held, cfg.router_width)

    def _routed(self, tokens: int) -> int:
        num, den = self._pairs
        return self.cfg.n_moe * self._expert * int(tokens) * num // den

    def _keys(self, start: int, n: int) -> int:
        """Attention FLOPs of positions ``start .. start + n - 1``."""
        s, n, W = int(start), max(int(n), 0), self.cfg.sliding_window
        full = n * s + n * (n + 1) // 2
        # sum of min(p + 1, W): positions below W - 1 see p + 1, the rest W.
        below = max(min(s + n, W - 1) - s, 0)
        window = below * s + below * (below + 1) // 2 + (n - below) * W
        return self._key[FULL] * full + self._key[WINDOW] * window

    # -- decode step ---------------------------------------------------
    def step_flops(self) -> int:
        return self._fixed + self._key[FULL] * self.M \
            + self._key[WINDOW] * self.cfg.sliding_window \
            + self.cfg.n_moe * self.cfg.n_held * self._expert + self._logit

    def step_useful_flops(self, pos: int) -> int:
        return self._fixed + self._keys(pos, 1) + self._routed(1) \
            + self._logit

    def steps_useful_sum(self, start_pos: int, n_steps: int) -> int:
        n = max(int(n_steps), 0)
        return n * (self._fixed + self._logit) + self._keys(start_pos, n) \
            + self._routed(n)

    # -- prefill chunk -------------------------------------------------
    def chunk_flops(self, size: int) -> int:
        c = int(size)
        if experts_form(c) == "dense":
            routed = c * self.cfg.n_moe * self.cfg.n_held * self._expert
        else:
            routed = max(self._routed(c), self.cfg.n_moe * self.cfg.n_held
                         * experts.PAIR_TILE * self._expert)
        QT = min(c, _QUERY_TILE)
        padded = -(-c // QT) * QT
        return c * self._fixed + self._key[FULL] * c * self.M \
            + self._key[WINDOW] * padded * (QT + self.cfg.sliding_window) \
            + routed + self._logit

    def chunk_useful_flops(self, start: int, size: int, final: bool) -> int:
        c = int(size)
        return c * self._fixed + self._keys(start, c) + self._routed(c) \
            + (self._logit if final else 0)

    def prefill_chunks_flops(self, start: int, end: int,
                             chunk: int | None) -> int:
        total, pos, end = 0, int(start), int(end)
        while pos < end:
            c = end - pos if chunk is None else min(int(chunk), end - pos)
            total += self.chunk_flops(c)
            pos += c
        return total
