"""MiniCPM-SALA's block family: layers of two kinds in one stack.

A second block family beside GPT-2's (:mod:`.transformer`): RMSNorm,
SwiGLU, separate q/k/v/o projections, QK-norm, sigmoid output gates,
muP scales and an untied head, with a MIXER that differs by layer:

* ``lightning-attn`` — linear attention with a per-head decaying
  ``(Dh, Dh)`` state, RoPE on q and k, an output norm over the
  concatenated heads.  The state does not grow with position.
* ``minicpm4`` — softmax attention with grouped K/V heads and no
  positional encoding, dense up to ``dense_len`` positions and
  block-sparse beyond (InfLLM-v2, parameter-free: block scores from
  mean-pooled "compressed" keys, the top-k blocks plus the first block
  and a local window attended).

Parameters are stacked BY KIND (``params["sparse"]``,
``params["lightning"]``, each leaf with a leading per-kind layer axis)
and the stack is walked as runs of one kind (:meth:`SalaConfig.runs`),
each run one ``lax.scan``: compile time does not grow with depth.

The slot cache is one pytree of two kinds of state: K/V rows
``(Ls, S, G, Dh, M)``, compressed keys ``(Ls, S, G, Dh, M / stride)``
and a ring of the last ``kernel_size`` keys for the sparse layers
(positions last, as the chip stores them), and the float32 state
``(Ll, S, H, Dh, Dh)`` of the lightning layers.  The
four functions the continuous scheduler's three programs are built
from — :func:`init_slot_cache`, :func:`prefill_chunk_into_cache`,
:func:`decode_step_slots`, :func:`copy_cache_slot` — have the
signatures of their GPT-2 namesakes in :mod:`.generate` and are handed
to the scheduler by :meth:`SalaConfig.slot_model`.  docs/MODEL_CONFIG.md
has the equations' provenance; the plain reference is
benchmark/configs/minicpm_sala_reference.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_dist_nn.kernels import sparse_attend
from tpu_dist_nn.kernels.kv_write import write_rows
from tpu_dist_nn.models.slot_model import (  # noqa: F401
    SlotModel,
    load_model_config,
)

LIGHTNING = "lightning-attn"
SPARSE = "minicpm4"
_HIGHEST = lax.Precision.HIGHEST
# Key positions scored per pass of the chunk's attention loop, and
# positions per pass of the chunk-parallel linear attention.
_KEY_TILE = 1024
_LIGHTNING_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class SalaConfig:
    """Static description of one MiniCPM-SALA stack (hashable).

    ``mixer_types`` are the layers AS RUN and ``layer_ids`` their
    indices in the published stack of ``published_layers``: a cut in
    depth keeps each layer's own decay and the published residual scale
    ``scale_depth / sqrt(published_layers)``.
    """

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    lightning_heads: int
    lightning_head_dim: int
    mixer_types: tuple
    layer_ids: tuple
    published_layers: int
    max_seq_len: int
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 256
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    dense_len: int = 8192
    param_dtype: str = "bfloat16"
    state_dtype: str = "float32"

    causal = True  # both mixers are; the generation contract asks

    def __post_init__(self):
        if len(self.mixer_types) != len(self.layer_ids):
            raise ValueError("mixer_types and layer_ids differ in length")
        bad = set(self.mixer_types) - {LIGHTNING, SPARSE}
        if bad:
            raise ValueError(f"unknown mixer types {sorted(bad)}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.block_size % self.kernel_stride \
                or self.kernel_size % self.kernel_stride:
            raise ValueError(
                "block_size and kernel_size must be multiples of kernel_stride")

    # ------------------------------------------------------------ sizes
    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def n_sparse(self) -> int:
        return sum(m == SPARSE for m in self.mixer_types)

    @property
    def n_lightning(self) -> int:
        return sum(m == LIGHTNING for m in self.mixer_types)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def residual_scale(self) -> float:
        return float(self.scale_depth / np.sqrt(self.published_layers))

    @property
    def logit_divisor(self) -> float:
        return float(self.hidden_size / self.dim_model_base)

    def runs(self) -> list:
        """``[(kind, a, b)]``: layers ``a..b-1`` of that kind's stack,
        in the order the stack is walked."""
        out, seen = [], {SPARSE: 0, LIGHTNING: 0}
        for kind in self.mixer_types:
            i = seen[kind]
            seen[kind] += 1
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], i + 1)
            else:
                out.append((kind, i, i + 1))
        return out

    def decay_rates(self) -> np.ndarray:
        """``(Ll, H)`` float32: ``a`` with ``lambda = exp(-a)``, the
        Lightning Attention family's slopes ``2^(-8 (h+1) / H)`` scaled
        by the published layer's ``1 - l / (L - 1) + 1e-5``."""
        H, L = self.lightning_heads, self.published_layers
        slopes = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
        ids = [l for l, m in zip(self.layer_ids, self.mixer_types)
               if m == LIGHTNING]
        factor = 1.0 - np.asarray(ids, np.float64) / max(L - 1, 1) + 1e-5
        return (factor[:, None] * slopes[None, :]).astype(np.float32)

    def cast_params(self, params):
        dtype = jnp.dtype(self.param_dtype)
        return jax.tree.map(
            lambda a: a if a.dtype == dtype else a.astype(dtype), params)

    def slot_model(self) -> SlotModel:
        return SlotModel(
            init_slot_cache=init_slot_cache,
            prefill_chunk_into_cache=prefill_chunk_into_cache,
            decode_step_slots=decode_step_slots,
            copy_cache_slot=copy_cache_slot,
            flop_model=SalaFlopModel,
            cache_bytes=cache_bytes,
            recurrent=self.n_lightning > 0,
            sparse_positions=self.sparse_positions,
            attend_kernel=self.attend_kernel,
        )

    def sparse_positions(self, pos) -> int:
        """How many of the query positions ``pos`` lie past
        ``dense_len``, where the block selection serves them."""
        if not self.n_sparse:
            return 0
        return int((np.asarray(pos) >= self.dense_len).sum())

    def attend_kernel(self, size, max_len) -> bool:
        """Whether a chunk of ``size`` positions into a slot cache made
        for ``max_len`` runs the sparse layers' attention as the Pallas
        kernel: the dispatch of :func:`_sparse_chunk_layer`, asked from
        outside the program."""
        return attend_kernel_tiles(self, int(size), _extent(self, max_len))

    # ---------------------------------------------------------- loading
    @classmethod
    def from_dict(cls, d: dict) -> "SalaConfig":
        """From a ``config.json`` in the source's own keys
        (``model_type: minicpm_sala``).  ``sparse_config`` holds the
        InfLLM-v2 settings; ``published`` (``num_hidden_layers``,
        ``layer_ids``) says which layers of the published stack a cut
        in depth kept."""
        if d.get("model_type") != "minicpm_sala":
            raise ValueError(
                f"model_type {d.get('model_type')!r} is not 'minicpm_sala'")
        mixers = tuple(d["mixer_types"])
        if len(mixers) != int(d["num_hidden_layers"]):
            raise ValueError("mixer_types does not list num_hidden_layers")
        pub = d.get("published", {})
        sparse = {k: int(v) for k, v in d.get("sparse_config", {}).items()
                  if k in ("kernel_size", "kernel_stride", "block_size",
                           "topk", "window_size", "init_blocks", "dense_len")}
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]),
            intermediate_size=int(d["intermediate_size"]),
            n_heads=int(d["num_attention_heads"]),
            n_kv_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            lightning_heads=int(d["lightning_nh"]),
            lightning_head_dim=int(d["lightning_head_dim"]),
            mixer_types=mixers,
            layer_ids=tuple(pub.get("layer_ids", range(len(mixers)))),
            published_layers=int(pub.get("num_hidden_layers", len(mixers))),
            max_seq_len=int(d["max_position_embeddings"]),
            rms_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            scale_emb=float(d["scale_emb"]),
            scale_depth=float(d["scale_depth"]),
            dim_model_base=int(d["dim_model_base"]),
            param_dtype=str(d.get("param_dtype", "bfloat16")),
            **sparse,
        )


# ------------------------------------------------------------ parameters

def param_shapes(cfg: SalaConfig) -> dict:
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    Hq, G, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hl, Dl = cfg.lightning_heads, cfg.lightning_head_dim
    Ls, Ll = cfg.n_sparse, cfg.n_lightning

    def mlp(L):
        return {"norm2": (L, D), "w_gate": (L, D, F), "w_up": (L, D, F),
                "w_down": (L, F, D)}

    return {
        "embed": (V, D), "head": (D, V), "norm_f": (D,),
        "sparse": {
            "norm1": (Ls, D), "wq": (Ls, D, Hq * Dh), "wk": (Ls, D, G * Dh),
            "wv": (Ls, D, G * Dh), "wg": (Ls, D, Hq * Dh),
            "wo": (Ls, Hq * Dh, D), "q_norm": (Ls, Dh), "k_norm": (Ls, Dh),
            **mlp(Ls),
        },
        "lightning": {
            "norm1": (Ll, D), "wq": (Ll, D, Hl * Dl), "wk": (Ll, D, Hl * Dl),
            "wv": (Ll, D, Hl * Dl), "wg": (Ll, D, Hl * Dl),
            "wo": (Ll, Hl * Dl, D), "q_norm": (Ll, Dl), "k_norm": (Ll, Dl),
            "o_norm": (Ll, Hl * Dl), **mlp(Ll),
        },
    }


def num_params(cfg: SalaConfig) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_sala(key: jax.Array, cfg: SalaConfig):
    """Seeded random parameters in ``cfg.param_dtype``: matrices
    N(0, 1/fan_in), embedding N(0, 1), gains 1 + N(0, 0.02)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(cfg.param_dtype)
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(leaves)), leaves):
        name = path[-1].key
        z = jax.random.normal(k, shape, jnp.float32)
        if name.startswith("w"):
            z = z / np.sqrt(shape[-2])
        elif name == "head":
            z = z / np.sqrt(shape[0])
        elif name != "embed":
            z = 1.0 + 0.02 * z
        out.append(z.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- the math

def _einsum32(spec, a, b):
    """``einsum`` of two operands with float32 accumulation and result.
    The CPU backend has no bfloat16 x bfloat16 = float32 product: there
    the operands are widened first, which gives the same numbers."""
    def narrow(a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)

    def wide(a, b):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))

    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return narrow(a, b)
    return lax.platform_dependent(a, b, cpu=wide, default=narrow)


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head: ``x (T, H, Dh)`` at
    positions ``pos (T,)``."""
    half = x.shape[-1] // 2
    inv = (1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
           ).astype(np.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _residual(x, y, cfg):
    return (x.astype(jnp.float32)
            + cfg.residual_scale * y.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("sala.mlp")
def _mlp(x, blk, cfg):
    h = _rms(x, blk["norm2"], cfg.rms_eps)
    y = (jax.nn.silu(h @ blk["w_gate"]) * (h @ blk["w_up"])) @ blk["w_down"]
    return _residual(x, y, cfg)


def _unembed(params, x, cfg):
    h = _rms(x, params["norm_f"], cfg.rms_eps)
    return _einsum32("ad,dv->av", h, params["head"]) / cfg.logit_divisor


def _embed(params, tokens, cfg):
    e = params["embed"][tokens]
    return (e.astype(jnp.float32) * cfg.scale_emb).astype(e.dtype)


# ----------------------------------------------------- block selection

def select_blocks(s, t, cfg: SalaConfig, M: int, return_scores=False):
    """Which key blocks each query attends: ``s (A, G, group, NC)``
    float32 scores of each query head over the compressed keys, ``t
    (A,)`` the queries' positions; returns ``(A, G, NB)`` bool.

    A query with ``t + 1 <= dense_len`` attends every block.  Beyond:
    the ``init_blocks`` first blocks, the blocks that hold its last
    ``window_size`` positions, and the ``topk`` best of the blocks
    between by score — the softmax over the VISIBLE compressed keys
    (kernel ends at or before ``t``), summed over the group's heads,
    max-pooled onto each block from the kernels that overlap it.
    """
    stride, ksz, blk = cfg.kernel_stride, cfg.kernel_size, cfg.block_size
    NC, NB = M // stride, M // blk
    t = t.astype(jnp.int32)
    visible = (stride * jnp.arange(NC) + ksz - 1)[None, :] <= t[:, None]
    vis = visible[:, None, None, :]
    top = jnp.max(jnp.where(vis, s, -jnp.inf), -1, keepdims=True)
    e = jnp.where(vis, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    p = jnp.sum(p, axis=2)  # over the group's heads: (A, G, NC)
    ratio, kk = blk // stride, ksz // stride
    pooled = lax.reduce_window(
        jnp.pad(p, ((0, 0), (0, 0), (kk - 1, 0))), -jnp.inf, lax.max,
        (1, 1, ratio + kk - 1), (1, 1, ratio), "VALID")  # (A, G, NB)
    b = jnp.arange(NB)[None, :]
    first_local = jnp.maximum(t - (cfg.window_size - 1), 0) // blk
    forced = (b < cfg.init_blocks) | (
        (b >= first_local[:, None]) & (b <= (t // blk)[:, None]))
    candidate = (b >= cfg.init_blocks) & (b < first_local[:, None])
    scores = jnp.where(candidate[:, None, :], pooled, -jnp.inf)
    vals, ids = lax.top_k(scores, min(cfg.topk, NB))
    chosen = jnp.any(
        (ids[..., None] == jnp.arange(NB)) & jnp.isfinite(vals)[..., None],
        axis=-2)
    dense = (t + 1 <= cfg.dense_len)[:, None, None]
    sel = dense | forced[:, None, :] | chosen
    return (sel, scores) if return_scores else sel


# -------------------------------------------------------- sparse layers

def _sparse_qkv(x, blk, cfg):
    """``x (A, D)`` -> q ``(A, G, group, Dh)``, k and v ``(A, G, Dh)``,
    the output gate ``(A, Hq * Dh)``; q and k RMS-normed per head, no
    rotation."""
    A = x.shape[0]
    G, g, Dh = cfg.n_kv_heads, cfg.group, cfg.head_dim
    h = _rms(x, blk["norm1"], cfg.rms_eps)
    q = _rms((h @ blk["wq"]).reshape(A, G, g, Dh), blk["q_norm"], cfg.rms_eps)
    k = _rms((h @ blk["wk"]).reshape(A, G, Dh), blk["k_norm"], cfg.rms_eps)
    v = (h @ blk["wv"]).reshape(A, G, Dh)
    return q, k, v, jax.nn.sigmoid(h @ blk["wg"])


def _new_compressed(k_rows, k_chunk, start, cfg, NC):
    """Compressed keys whose kernel ENDS inside the chunk ``[start,
    start + C)``: ``k_rows (G, Dh, M)`` the slot's keys before the
    chunk, ``k_chunk (C, G, Dh)``.  Returns ``(values (G, Dh, NC),
    fresh (NC,) bool)``: a mean over each kernel's ``kernel_size`` keys
    as one product with a 0 / (1 / kernel_size) pooling matrix, so no
    lane is addressed by a traced offset."""
    ksz, stride = cfg.kernel_size, cfg.kernel_stride
    C, M = k_chunk.shape[0], k_rows.shape[-1]
    at = jnp.clip(start - ksz, 0, M - ksz)
    before = lax.dynamic_slice(k_rows, (0, 0, at), k_rows.shape[:2] + (ksz,))
    src = jnp.concatenate([before, k_chunk.transpose(1, 2, 0)], -1)
    src_pos = jnp.concatenate([at + jnp.arange(ksz), start + jnp.arange(C)])
    src_ok = jnp.concatenate([at + jnp.arange(ksz) < start,
                              jnp.ones((C,), bool)])
    first = stride * jnp.arange(NC)
    ends = first + ksz - 1
    fresh = (ends >= start) & (ends < start + C)
    pool = (src_ok[:, None] & fresh[None, :]
            & (src_pos[:, None] >= first[None, :])
            & (src_pos[:, None] <= ends[None, :]))
    vals = jnp.einsum("gdr,rj->gdj", src.astype(jnp.float32),
                      pool.astype(jnp.float32) / ksz, precision=_HIGHEST)
    return vals.astype(k_rows.dtype), fresh


def _attend_chunk(q, k_rows, v_rows, sel, t, cfg):
    """Causal softmax attention of the chunk's queries ``q (C, G,
    group, Dh)`` at positions ``t (C,)`` over the slot's keys and values
    ``(G, Dh, M)``, restricted to each query's selected blocks ``sel
    (C, G, NB)``: online softmax over tiles of keys, as many tiles as
    hold a visible key."""
    C, G, g, Dh = q.shape
    M = k_rows.shape[-1]
    blk = cfg.block_size
    KT = min(M, max(_KEY_TILE // blk, 1) * blk)
    scale = 1.0 / np.sqrt(Dh)
    qh = q.transpose(1, 2, 0, 3)  # (G, group, C, Dh)
    sel_g = sel.transpose(1, 0, 2)  # (G, C, NB)

    def body(i, carry):
        m, l, acc = carry
        at = jnp.minimum(i * KT, M - KT)
        kt = lax.dynamic_slice(k_rows, (0, 0, at), (G, Dh, KT))
        vt = lax.dynamic_slice(v_rows, (0, 0, at), (G, Dh, KT))
        s = _einsum32("ghcd,gdk->ghck", qh, kt) * scale
        key_pos = at + jnp.arange(KT)
        seen = (key_pos[None, :] <= t[:, None]) & (key_pos >= i * KT)[None, :]
        picked = jnp.repeat(lax.dynamic_slice(
            sel_g, (0, 0, at // blk), (G, C, KT // blk)), blk, axis=-1)
        # The mask is a group's, not a head's: it broadcasts over the
        # heads and is never laid out at the scores' size.
        s = jnp.where((picked & seen[None])[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        alpha = jnp.exp(m - m_safe)
        l = alpha * l + jnp.sum(p, -1)
        acc = alpha[..., None] * acc + _einsum32(
            "ghck,gdk->ghcd", p.astype(v_rows.dtype), vt)
        return m_new, l, acc

    init = (jnp.full((G, g, C), -jnp.inf, jnp.float32),
            jnp.zeros((G, g, C), jnp.float32),
            jnp.zeros((G, g, C, Dh), jnp.float32))
    _, l, acc = lax.fori_loop(0, t[-1] // KT + 1, body, init)
    o = (acc / l[..., None]).astype(q.dtype)
    return o.transpose(2, 0, 1, 3).reshape(C, G * g * Dh)


def attend_kernel_tiles(cfg: SalaConfig, C: int, M: int) -> bool:
    """Whether a chunk of ``C`` positions over a slot of ``M`` key
    positions runs its block-masked attention as the Pallas kernel
    (:mod:`tpu_dist_nn.kernels.sparse_attend`): only the shapes decide."""
    return cfg.n_sparse > 0 and sparse_attend.tiles(
        C, cfg.group, cfg.head_dim, M, cfg.block_size) is not None


def _ring_after_chunk(tail, k_chunk, start):
    """The ring of the slot's last keys ``(G, Dh, ksz)`` (lane =
    position mod ksz) after a chunk ``k_chunk (C, G, Dh)`` at ``start``:
    each lane takes the chunk's last key at its residue, if it has one."""
    C, ksz = k_chunk.shape[0], tail.shape[-1]
    last = start + C - 1
    lane = jnp.arange(ksz)
    newest = last - (last - lane) % ksz  # latest position <= last on the lane
    rows = k_chunk[jnp.clip(newest - start, 0, C - 1)].transpose(1, 2, 0)
    return jnp.where((newest >= start)[None, None, :],
                     rows.astype(tail.dtype), tail)


def _sparse_chunk_layer(x, blk, k_rows, v_rows, ck_rows, tail, start, cfg):
    """One ``minicpm4`` layer over a chunk ``x (C, D)`` at positions
    ``start ..``; the slot's rows ``(G, Dh, M)``, compressed keys
    ``(G, Dh, NC)`` and ring of last keys come back with the chunk's
    appended."""
    C = x.shape[0]
    M, NC = k_rows.shape[-1], ck_rows.shape[-1]
    t = start + jnp.arange(C)
    q, k, v, gate = _sparse_qkv(x, blk, cfg)
    new_ck, fresh = _new_compressed(k_rows, k, start, cfg, NC)
    ck_rows = jnp.where(fresh[None, None, :], new_ck, ck_rows)
    tail = _ring_after_chunk(tail, k, start)
    k_rows = lax.dynamic_update_slice(
        k_rows, k.transpose(1, 2, 0).astype(k_rows.dtype), (0, 0, start))
    v_rows = lax.dynamic_update_slice(
        v_rows, v.transpose(1, 2, 0).astype(v_rows.dtype), (0, 0, start))
    with jax.named_scope("sala.sparse.select"):
        s = _einsum32("cghd,gdj->cghj", q, ck_rows) \
            / np.sqrt(cfg.head_dim)
        sel = select_blocks(s, t, cfg, M)
    with jax.named_scope("sala.sparse.attend"):
        # Which path runs is read from the shapes: the Pallas kernel
        # where they tile, the XLA loop (its oracle) where they do not.
        if attend_kernel_tiles(cfg, C, M):
            o = sparse_attend.attend_chunk(
                q, k_rows, v_rows, sel, start, cfg.block_size)
        else:
            o = _attend_chunk(q, k_rows, v_rows, sel, t, cfg)
    x = _residual(x, (o * gate) @ blk["wo"], cfg)
    return _mlp(x, blk, cfg), k_rows, v_rows, ck_rows, tail


def _sparse_step_layer(x, blk, layer, cache, pos, active, cfg):
    """One ``minicpm4`` layer of the decode step: ``x (S, D)``, each
    slot at its own ``pos``.  The cache is read where it lies; returns
    the new hidden state, the new key and value rows ``(S, G, Dh)``,
    the layer's compressed keys ``(S, G, Dh, NC)`` with the kernel that
    this position completed, if it did, and the ring of last keys."""
    S = x.shape[0]
    G, g, Dh = cfg.n_kv_heads, cfg.group, cfg.head_dim
    ksz, stride, blk_sz = cfg.kernel_size, cfg.kernel_stride, cfg.block_size
    M, NC = cache["k"].shape[-1], cache["ck"].shape[-1]
    q, k, v, gate = _sparse_qkv(x, blk, cfg)
    K = lax.dynamic_slice(cache["k"], (layer, 0, 0, 0, 0), (1, S, G, Dh, M))[0]
    V = lax.dynamic_slice(cache["v"], (layer, 0, 0, 0, 0), (1, S, G, Dh, M))[0]
    ck = lax.dynamic_slice(cache["ck"], (layer, 0, 0, 0, 0),
                           (1, S, G, Dh, NC))[0]
    tail = lax.dynamic_slice(cache["tail"], (layer, 0, 0, 0, 0),
                             (1, S, G, Dh, ksz))[0]
    with jax.named_scope("sala.sparse.select"):
        # The slot's last `ksz` keys live in a ring (lane = position
        # mod ksz), so the kernel [pos - ksz + 1, pos] that ends here
        # when pos + 1 is a multiple of the stride is the ring's mean:
        # no lane of the big K buffer is addressed by a traced offset.
        mine = active[:, None] & (jnp.arange(ksz)[None, :]
                                  == (pos % ksz)[:, None])
        tail = jnp.where(mine[:, None, None, :],
                         k[..., None].astype(tail.dtype), tail)
        mean = (jnp.sum(tail.astype(jnp.float32), -1) / ksz).astype(ck.dtype)
        done = ((pos + 1) % stride == 0) & (pos + 1 >= ksz) & active
        at = (done[:, None]
              & (jnp.arange(NC)[None, :] == ((pos + 1 - ksz) // stride)[:, None]))
        ck = jnp.where(at[:, None, None, :], mean[..., None], ck)
        s = _einsum32("sghd,sgdj->sghj", q, ck) / np.sqrt(Dh)
        sel = select_blocks(s, pos, cfg, M)  # (S, G, NB)
    with jax.named_scope("sala.sparse.attend"):
        scores = _einsum32("sghd,sgdm->sghm", q, K) / np.sqrt(Dh)
        own = _einsum32("sghd,sgd->sgh", q.astype(jnp.float32),
                         k.astype(jnp.float32))[..., None] / np.sqrt(Dh)
        stored = (jnp.arange(M)[None, None, :] < pos[:, None, None]) \
            & jnp.repeat(sel, blk_sz, axis=-1)
        scores = jnp.where(stored[:, :, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([scores, own], -1), -1)
        o = _einsum32("sghm,sgdm->sghd", probs[..., :M].astype(V.dtype), V) \
            + probs[..., M:] * v.astype(jnp.float32)[:, :, None, :]
        o = o.astype(x.dtype).reshape(S, G * g * Dh)
    x = _residual(x, (o * gate) @ blk["wo"], cfg)
    return _mlp(x, blk, cfg), k, v, ck, tail


# ----------------------------------------------------- lightning layers

def _lightning_qkv(x, blk, pos, cfg):
    A = x.shape[0]
    H, Dh = cfg.lightning_heads, cfg.lightning_head_dim
    h = _rms(x, blk["norm1"], cfg.rms_eps)
    q = _rms((h @ blk["wq"]).reshape(A, H, Dh), blk["q_norm"], cfg.rms_eps)
    k = _rms((h @ blk["wk"]).reshape(A, H, Dh), blk["k_norm"], cfg.rms_eps)
    v = (h @ blk["wv"]).reshape(A, H, Dh)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    return q, k, v, jax.nn.sigmoid(h @ blk["wg"])


def _lightning_out(x, o, gate, blk, cfg):
    """``o (A, H, Dh)`` float32: norm over the concatenated heads,
    gate, output projection, residual, then the MLP."""
    o = _rms(o.reshape(o.shape[0], -1), blk["o_norm"],
             cfg.rms_eps).astype(x.dtype)
    x = _residual(x, (o * gate) @ blk["wo"], cfg)
    return _mlp(x, blk, cfg)


@jax.named_scope("sala.lightning")
def _lightning_chunk_layer(x, blk, rate, state, start, cfg):
    """One ``lightning-attn`` layer over a chunk ``x (C, D)``:
    ``state (H, Dh, Dh)`` float32 is the slot's state before the chunk
    and comes back as after it.  Chunk-parallel: blocks of positions,
    quadratic with the decay inside a block, the state carried between.
    Every decay is an ``exp`` of a non-positive number."""
    C = x.shape[0]
    H, Dh = cfg.lightning_heads, cfg.lightning_head_dim
    q, k, v, gate = _lightning_qkv(x, blk, start + jnp.arange(C), cfg)
    B = min(C, _LIGHTNING_BLOCK)
    n = -(-C // B)
    pad = ((0, n * B - C), (0, 0), (0, 0))
    qb, kb, vb = (jnp.pad(a, pad).reshape(n, B, H, Dh) for a in (q, k, v))
    valid = jnp.clip(C - B * jnp.arange(n), 0, B)  # real positions a block
    i = jnp.arange(B, dtype=jnp.float32)
    lag = i[:, None] - i[None, :]
    within = jnp.where(lag >= 0, jnp.exp(-rate[:, None, None]
                                         * jnp.maximum(lag, 0.0)), 0.0)
    scale = 1.0 / np.sqrt(Dh)

    def body(S0, inputs):
        qi, ki, vi, nv = inputs
        a = _einsum32("ihd,jhd->hij", qi, ki) * scale * within
        o = _einsum32("hij,jhd->ihd", a.astype(vi.dtype), vi)
        q_in = qi.astype(jnp.float32) * scale \
            * jnp.exp(-rate[None, :] * (i[:, None] + 1.0))[..., None]
        o = o + jnp.einsum("ihd,hde->ihe", q_in, S0, precision=_HIGHEST)
        nvf = nv.astype(jnp.float32)
        left = nvf - 1.0 - i
        k_out = ki.astype(jnp.float32) * jnp.where(
            left[:, None] >= 0,
            jnp.exp(-rate[None, :] * jnp.maximum(left, 0.0)[:, None]),
            0.0)[..., None]
        S1 = jnp.exp(-rate * nvf)[:, None, None] * S0 + jnp.einsum(
            "jhd,jhe->hde", k_out, vi.astype(jnp.float32), precision=_HIGHEST)
        return S1, o

    state, o = lax.scan(body, state, (qb, kb, vb, valid))
    o = o.reshape(n * B, H, Dh)[:C]
    return _lightning_out(x, o, gate, blk, cfg), state


@jax.named_scope("sala.lightning")
def _lightning_step_layer(x, blk, rate, state, pos, active, cfg):
    """One ``lightning-attn`` layer of the decode step: ``state (S, H,
    Dh, Dh)``; an inactive slot's state stays bit for bit."""
    q, k, v, gate = _lightning_qkv(x, blk, pos, cfg)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    new = jnp.exp(-rate)[None, :, None, None] * state \
        + kf[..., :, None] * vf[..., None, :]
    qf = q.astype(jnp.float32) / np.sqrt(cfg.lightning_head_dim)
    o = jnp.sum(qf[..., :, None] * new, axis=-2)
    state = jnp.where(active[:, None, None, None], new, state)
    return _lightning_out(x, o, gate, blk, cfg), state


# ----------------------------------------------------------- slot cache

def _extent(cfg: SalaConfig, max_len: int) -> int:
    """``max_len`` rounded up to whole blocks (and to a whole kernel)."""
    blk = cfg.block_size
    return max(-(-int(max_len) // blk) * blk,
               -(-cfg.kernel_size // blk) * blk)


def init_slot_cache(cfg: SalaConfig, slots: int, max_len: int) -> dict:
    """The zeroed slot cache: per sparse layer K and V ``(S, G, Dh,
    M)``, compressed keys ``(S, G, Dh, M / stride)`` and a ring of the
    last ``kernel_size`` keys (what the next compressed key is the mean
    of) in the parameters' type, per lightning layer the state ``(S, H,
    Dh, Dh)`` in float32, each kind stacked over its layers.  ``M`` is
    ``max_len`` rounded up to whole blocks."""
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if max_len < 1 or max_len > cfg.max_seq_len:
        raise ValueError(
            f"max_len must be in [1, {cfg.max_seq_len}], got {max_len}")
    M = _extent(cfg, max_len)
    dtype = jnp.dtype(cfg.param_dtype)
    G, Dh = cfg.n_kv_heads, cfg.head_dim
    H, Dl = cfg.lightning_heads, cfg.lightning_head_dim
    kv = (cfg.n_sparse, slots, G, Dh, M)
    return {
        "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
        "ck": jnp.zeros(kv[:-1] + (M // cfg.kernel_stride,), dtype),
        "tail": jnp.zeros(kv[:-1] + (cfg.kernel_size,), dtype),
        "state": jnp.zeros((cfg.n_lightning, slots, H, Dl, Dl),
                           jnp.dtype(cfg.state_dtype)),
    }


def cache_bytes(cache: dict) -> dict:
    """Bytes of the cache by kind, for ``tdn_gen_cache_bytes``."""
    size = lambda a: int(a.size) * a.dtype.itemsize  # noqa: E731
    return {"kv": size(cache["k"]) + size(cache["v"]),
            "compressed": size(cache["ck"]) + size(cache["tail"]),
            "state": size(cache["state"])}


def copy_cache_slot(cache: dict, src, dst) -> dict:
    """Copy slot ``src`` onto slot ``dst``, every kind of state.  What
    a prefix tier may copy is the slot as a chunk left it: the lightning
    state is the state after the LAST position prefilled, so the copy
    is a prefix's only if the prefix ends there."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return jax.tree.map(
        lambda a: _put_slot(a, _take_slot(a, src), dst) if a.shape[0] else a,
        cache)


def _take_slot(a, slot):
    return lax.dynamic_slice(
        a, (0, slot) + (0,) * (a.ndim - 2),
        a.shape[:1] + (1,) + a.shape[2:])[:, 0]


def _put_slot(a, rows, slot):
    return lax.dynamic_update_slice(
        a, rows[:, None].astype(a.dtype), (0, slot) + (0,) * (a.ndim - 2))


def _chunk_hidden(params, cfg, cache, slot, tokens, start):
    """The chunk ``tokens (1, C)`` at positions ``start ..`` of slot
    ``slot`` through every layer: ``(x (C, D), cache)``."""
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    rates = jnp.asarray(cfg.decay_rates())
    x = _embed(params, tokens[0], cfg)
    mine = {name: _take_slot(a, slot) for name, a in cache.items()}
    # A chunk that starts a prompt starts from no state: whatever the
    # slot's last occupant left is not masked out by a position, as
    # stale K/V rows are.
    mine["state"] = jnp.where(start == 0, 0.0, mine["state"])
    for kind, a, b in cfg.runs():
        if kind == SPARSE:
            def body(x, inputs):
                blk, rows = inputs
                x, *rows = _sparse_chunk_layer(x, blk, *rows, start, cfg)
                return x, tuple(rows)

            kinds = ("k", "v", "ck", "tail")
            blocks = jax.tree.map(lambda p: p[a:b], params["sparse"])
            x, new = lax.scan(body, x, (blocks, tuple(
                mine[name][a:b] for name in kinds)))
            for name, rows in zip(kinds, new):
                mine[name] = lax.dynamic_update_slice(
                    mine[name], rows, (a,) + (0,) * (rows.ndim - 1))
        else:
            def body(x, inputs):
                blk, rate, state = inputs
                x, state = _lightning_chunk_layer(
                    x, blk, rate, state, start, cfg)
                return x, state

            blocks = jax.tree.map(lambda p: p[a:b], params["lightning"])
            x, new = lax.scan(body, x, (blocks, rates[a:b],
                                        mine["state"][a:b]))
            mine["state"] = lax.dynamic_update_slice(
                mine["state"], new, (a, 0, 0, 0))
    cache = {name: _put_slot(cache[name], rows, slot)
             for name, rows in mine.items()}
    return x, cache


def prefill_chunk_into_cache(params: dict, cfg: SalaConfig, cache: dict,
                             slot, tokens: jnp.ndarray, start):
    """Prefill ONE CHUNK into slot ``slot``: ``tokens (1, C)`` occupy
    positions ``[start, start + C)``.  The lightning state is carried
    from the chunk before (from nothing at ``start == 0``), K/V rows and
    the compressed keys whose kernels end inside the chunk are appended,
    and each query attends densely up to ``dense_len`` and by selection
    beyond it.  ``slot`` and ``start`` are traced.  Returns ``(logits
    (1, V)`` of the chunk's last position, ``cache)``."""
    x, cache = _chunk_hidden(params, cfg, cache, slot, tokens, start)
    return _unembed(params, x[-1:], cfg), cache


def decode_step_slots(params: dict, cache: dict, pos: jnp.ndarray,
                      token: jnp.ndarray, cfg: SalaConfig,
                      active: jnp.ndarray | None = None):
    """One decode step for the first ``S`` slots: ``token (S,)`` at
    per-slot ``pos (S,)``, gated by ``active (S,)``.  New K/V rows land
    at ``pos`` in place (:func:`~tpu_dist_nn.kernels.kv_write.write_rows`),
    a completed kernel's compressed key and the lightning state are
    written for active slots only; an inactive slot's cache stays bit
    for bit.  Returns ``(logits (S, V), cache)``."""
    S = token.shape[0]
    if active is None:
        active = jnp.ones((S,), bool)
    M = cache["k"].shape[-1]
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, M - 1)
    rates = jnp.asarray(cfg.decay_rates())
    x = _embed(params, token, cfg)
    ck_all, tail_all = cache["ck"], cache["tail"]
    state_all = cache["state"]
    new_k, new_v = [], []
    for kind, a, b in cfg.runs():
        if kind == SPARSE:
            def body(carry, inputs):
                x, ck_all, tail_all = carry
                blk, layer = inputs
                x, k, v, ck, tail = _sparse_step_layer(
                    x, blk, layer, dict(cache, ck=ck_all, tail=tail_all),
                    pos, active, cfg)
                at = (layer, 0, 0, 0, 0)
                return (x, lax.dynamic_update_slice(ck_all, ck[None], at),
                        lax.dynamic_update_slice(tail_all, tail[None], at)
                        ), (k, v)

            blocks = jax.tree.map(lambda p: p[a:b], params["sparse"])
            (x, ck_all, tail_all), (ks, vs) = lax.scan(
                body, (x, ck_all, tail_all), (blocks, jnp.arange(a, b)))
            new_k.append(ks)
            new_v.append(vs)
        else:
            def body(carry, inputs):
                x, state_all = carry
                blk, rate, layer = inputs
                at = (layer, 0, 0, 0, 0)
                state = lax.dynamic_slice(
                    state_all, at, (1, S) + state_all.shape[2:])[0]
                x, state = _lightning_step_layer(
                    x, blk, rate, state, pos, active, cfg)
                return (x, lax.dynamic_update_slice(
                    state_all, state[None], at)), None

            blocks = jax.tree.map(lambda p: p[a:b], params["lightning"])
            (x, state_all), _ = lax.scan(
                body, (x, state_all), (blocks, rates[a:b], jnp.arange(a, b)))
    k_all, v_all = cache["k"], cache["v"]
    if new_k:
        k_all, v_all = write_rows(
            k_all, v_all, jnp.concatenate(new_k).astype(k_all.dtype),
            jnp.concatenate(new_v).astype(v_all.dtype), pos, active)
    cache = {"k": k_all, "v": v_all, "ck": ck_all, "tail": tail_all,
             "state": state_all}
    return _unembed(params, x, cfg), cache


def forward(params: dict, tokens: jnp.ndarray, cfg: SalaConfig):
    """Full-sequence logits ``(B, T, V)`` of ``tokens (B, T)``, for
    tests: each row as one whole-prompt chunk into a scratch cache."""
    T = tokens.shape[1]

    def row(toks):
        x, _ = _chunk_hidden(params, cfg, init_slot_cache(cfg, 1, T), 0,
                             toks[None], 0)
        return _unembed(params, x, cfg)

    return jnp.stack([row(toks) for toks in tokens])


# ----------------------------------------------------------- FLOP model

class SalaFlopModel:
    """Analytic FLOPs of the generation kernels, with the method names
    of :class:`tpu_dist_nn.obs.goodput.LMFlopModel` (what the goodput
    ledger calls).  Multiply-adds count two.  USEFUL counts the keys a
    position attends (all up to ``dense_len``, the selected blocks'
    beyond) and the compressed keys it scores; the STATIC launch counts
    what the programs compute: the decode step scores the whole extent
    and masks, a chunk every tile up to its last position."""

    def __init__(self, cfg: SalaConfig, cache_extent: int):
        self.cfg, self.M = cfg, int(cache_extent)
        D, F = cfg.hidden_size, cfg.intermediate_size
        qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        ld = cfg.lightning_heads * cfg.lightning_head_dim
        mlp = 6 * D * F
        self._proj = (
            cfg.n_sparse * (2 * D * (2 * qd + 2 * kvd) + 2 * qd * D + mlp)
            + cfg.n_lightning * (2 * D * 4 * ld + 2 * ld * D + mlp
                                 + 4 * ld * cfg.lightning_head_dim))
        self._per_key = 4 * qd * cfg.n_sparse
        self._per_comp = 2 * qd * cfg.n_sparse
        self._logit = 2 * D * cfg.vocab_size

    def counts(self, start: int, n: int):
        """For positions ``start .. start + n - 1``: the keys each
        attends in a sparse layer and the compressed keys it scores."""
        c = self.cfg
        p = np.arange(int(start), int(start) + int(n), dtype=np.int64)
        dense = p + 1 <= c.dense_len
        blk = c.block_size
        first_local = np.maximum(p - (c.window_size - 1), 0) // blk
        whole = np.minimum(c.init_blocks, first_local) \
            + np.minimum(c.topk, np.maximum(first_local - c.init_blocks, 0)) \
            + (p // blk - first_local)
        keys = np.where(dense, p + 1, whole * blk + p % blk + 1)
        comp = np.where(dense | (p + 1 < c.kernel_size), 0,
                        (p + 1 - c.kernel_size) // c.kernel_stride + 1)
        return keys, comp

    def _attn(self, start: int, n: int = 1) -> int:
        keys, comp = self.counts(start, n)
        return int(self._per_key * keys.sum() + self._per_comp * comp.sum())

    def step_flops(self) -> int:
        return self._proj + self._per_key * self.M \
            + self._per_comp * (self.M // self.cfg.kernel_stride) + self._logit

    def step_useful_flops(self, pos: int) -> int:
        return self._proj + self._attn(pos) + self._logit

    def steps_useful_sum(self, start_pos: int, n_steps: int) -> int:
        n = max(int(n_steps), 0)
        return n * (self._proj + self._logit) + self._attn(start_pos, n)

    def chunk_flops(self, size: int) -> int:
        c = int(size)
        return c * (self._proj + self._per_key * self.M
                    + self._per_comp * (self.M // self.cfg.kernel_stride)) \
            + self._logit

    def chunk_useful_flops(self, start: int, size: int, final: bool) -> int:
        s, c = int(start), int(size)
        return c * self._proj + self._attn(s, c) \
            + (self._logit if final else 0)

    def prefill_chunks_flops(self, start: int, end: int,
                             chunk: int | None) -> int:
        total, pos, end = 0, int(start), int(end)
        while pos < end:
            c = end - pos if chunk is None else min(int(chunk), end - pos)
            total += self.chunk_flops(c)
            pos += c
        return total


# ------------------------------------------- the --model-config entry
# `load_model_config`, which reads the file and dispatches on its
# `model_type`, is models/slot_model.py's; the name stays importable
# from here, where the benchmark's drivers import it.

def init_model_config(key: jax.Array, cfg):
    """What ``tdn lm --model-config`` needs of a loaded config, whatever
    its family: ``(seeded parameters, their count, its layers' kinds)``."""
    if isinstance(cfg, SalaConfig):
        return init_sala(key, cfg), num_params(cfg), cfg.mixer_types
    return cfg.init_params(key), cfg.num_params(), cfg.layer_kinds
