"""Kimi-K2's block family (the DeepSeek-V3 block): latent attention and routed experts.

A fourth block family beside GPT-2's (:mod:`.transformer`), MiniCPM-SALA's
(:mod:`.sala`) and SambaY's (:mod:`.sambay`): RMSNorm, SwiGLU, an UNTIED
head, and in every layer

* **multi-head latent attention** — queries through a low-rank pair
  (``w_qa``, ``w_qb``); keys and values through ONE latent row a
  position, ``[c, k_r]``: ``kv_lora_rank`` numbers after their norm and
  ``qk_rope_head_dim`` of a single rotated key that every head shares.
  That row is all of attention that is cached.  A prefill chunk EXPANDS
  the rows it attends to per-head keys and values (``w_kvb``), a tile of
  positions at a time and only up to the chunk's end; the decode step
  never expands: it folds ``w_kvb``'s key half into the query and its
  value half behind the softmax, and attends the latent rows where they
  lie, every head reading the same bytes;
* **decoupled, YaRN-scaled rotary** on ``qk_rope_head_dim`` of a head's
  query dimensions and on the one shared key, rotated once, at the
  position it is written for;
* a feed-forward that is dense in the first ``first_k_dense`` layers and
  a **routed mixture** after them: sigmoid scores over ``router_width``
  experts, a selection bias that chooses but does not weigh, weights
  normalised over the ``k`` chosen and scaled, plus a shared expert.
  The layer is TOLD which experts it holds (``experts_held``, the chip's
  share of an expert-parallel deployment): it routes over all of them,
  computes the part of the result its own give, and knows nothing else
  of the deployment.  It is dropless: no capacity, no dropped token.
  That layer is :mod:`.experts`, which Laguna's family (:mod:`.laguna`)
  calls too with its own router: a dense form for a step, a ragged one
  for a chunk (:func:`~.experts.experts_form`).

The slot cache: ``lat (L, S, 1, kv_lora_rank + qk_rope_head_dim, M)``,
positions in the 128 lanes (PR 25's rule), the new rows landed in place
by :func:`tpu_dist_nn.kernels.kv_write.write_row`; and ``routed``, a
small int32 vector of routing counts that accumulates on the device
(:func:`routing_counts`; the scheduler reads it now and then, never a
step).  docs/MODEL_CONFIG.md has the equations' provenance; the plain
reference is benchmark/configs/kimi_k2_reference.py.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_dist_nn.kernels import expand_attend, latent_attend
from tpu_dist_nn.kernels.kv_write import write_row
from tpu_dist_nn.models import experts
from tpu_dist_nn.models.experts import experts_form
from tpu_dist_nn.models.sala import _einsum32, _put_slot, _rms, _take_slot
from tpu_dist_nn.models.slot_model import SlotModel

_LANES = 128
# Key positions a chunk expands and attends at a time.
_KEY_TILES = (512, 256, 128)


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """Static description of one stack (hashable)."""

    vocab_size: int
    hidden_size: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_layers: int
    first_k_dense: int
    router_width: int
    experts_held: tuple
    n_experts_per_tok: int
    routed_scaling_factor: float
    max_seq_len: int
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rms_eps: float = 1e-5
    param_dtype: str = "bfloat16"

    causal = True  # the generation contract asks

    def __post_init__(self):
        held = tuple(int(e) for e in self.experts_held)
        object.__setattr__(self, "experts_held", held)
        if not held or len(set(held)) != len(held) or \
                min(held) < 0 or max(held) >= self.router_width:
            raise ValueError(
                f"experts_held {held} must be distinct ids in "
                f"[0, {self.router_width})")
        if not 0 < self.n_experts_per_tok <= self.router_width:
            raise ValueError("num_experts_per_tok must be in (0, router width]")
        if not 0 <= self.first_k_dense < self.n_layers:
            raise ValueError(
                "first_k_dense_replace must leave at least one expert layer")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even: it is rotated")

    # ------------------------------------------------------------ sizes
    @property
    def latent_dim(self) -> int:
        """Numbers cached a position and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def n_held(self) -> int:
        return len(self.experts_held)

    @property
    def layer_kinds(self) -> tuple:
        return ("dense",) * self.first_k_dense + ("moe",) * self.n_moe

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-1/2`` times YaRN's ``mscale(all_dim)^2``."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return float(m * m / math.sqrt(self.qk_head_dim))

    def rope_freqs(self) -> np.ndarray:
        """The rotary angular frequencies ``w_j``, YaRN-scaled."""
        return yarn_freqs(self.qk_rope_head_dim, self.rope_theta,
                          self.rope_factor, self.rope_original_len,
                          self.rope_beta_fast, self.rope_beta_slow)

    @property
    def rope_cos_sin_scale(self) -> float:
        return yarn_mscale(self.rope_factor, self.rope_mscale) \
            / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)

    def cast_params(self, params):
        dtype = jnp.dtype(self.param_dtype)
        return jax.tree.map(
            lambda a: a if a.dtype == dtype else a.astype(dtype), params)

    def init_params(self, key):
        return init_mla_moe(key, self)

    def num_params(self) -> int:
        return num_params(self)

    def slot_model(self) -> SlotModel:
        return SlotModel(
            init_slot_cache=init_slot_cache,
            prefill_chunk_into_cache=prefill_chunk_into_cache,
            decode_step_slots=decode_step_slots,
            copy_cache_slot=copy_cache_slot,
            flop_model=MlaMoeFlopModel,
            cache_bytes=cache_bytes,
            routing_counts=self.routing_counts,
            attend_kernel=self.attend_kernel,
            step_kv_tiles=self.step_kv_tiles,
        )

    def attend_kernel(self, size, max_len) -> bool:
        """Whether a chunk of ``size`` positions into a slot cache made
        for ``max_len`` runs its expanded attention as the Pallas kernel:
        the dispatch of :func:`_attend_expanded`, asked from outside the
        program."""
        return attend_kernel_tiles(self, int(size), _extent(max_len))

    def step_kv_tiles(self, slots: int, max_len: int):
        """``pos (int array) -> (fetched, skipped)``: of the 128-lane
        position tiles of the slots' latent extent, how many a decode
        step copies a layer for queries at ``pos`` and how many it
        leaves in HBM; or ``None`` where the step of ``slots`` slots into
        a cache made for ``max_len`` reads the whole extent
        (:func:`_attend_latent`'s dispatch, asked from outside the
        program)."""
        M = _extent(max_len)
        if step_kernel_tile(self, slots, M) is None:
            return None

        def count(pos):
            fetched = int(latent_attend.fetched_tiles(pos).sum())
            return fetched, len(pos) * (M // _LANES) - fetched

        return count

    def routing_counts(self, cache: dict) -> dict:
        """The cache's routing counts by name (device values; the
        scheduler fetches them together): ``expert_pairs (n_held,)``,
        ``routed_pairs``, ``expert_touched``, ``expert_visits``."""
        return experts.routing_counts(cache["routed"], self.n_held)

    # ---------------------------------------------------------- loading
    @classmethod
    def from_dict(cls, d: dict) -> "MlaMoeConfig":
        """From a ``config.json`` in the source's own keys (``model_type:
        kimi_k2``).  ``n_routed_experts`` counts the experts HELD here
        where the file is a chip's share; ``router_width`` (default: the
        same) is what the router scores, and ``experts_held.ids`` which
        of them these are (default: the first)."""
        if d.get("model_type") != "kimi_k2":
            raise ValueError(
                f"model_type {d.get('model_type')!r} is not 'kimi_k2'")
        for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                          ("topk_group", 1), ("moe_layer_freq", 1),
                          ("norm_topk_prob", True),
                          ("tie_word_embeddings", False)):
            if d.get(key, want) != want:
                raise ValueError(
                    f"{key} must be {want!r}: sigmoid scores, one group, "
                    "every layer past the dense ones routed, weights "
                    "normalised over the chosen, an untied head")
        rope = dict(d.get("rope_scaling") or {})
        if rope and rope.get("type", "yarn") != "yarn":
            raise ValueError("rope_scaling.type must be 'yarn'")
        n_held = int(d["n_routed_experts"])
        width = int(d.get("router_width", n_held))
        ids = (d.get("experts_held") or {}).get("ids")
        held = tuple(range(n_held)) if ids is None else tuple(ids)
        if len(held) != n_held:
            raise ValueError(
                f"experts_held.ids names {len(held)} experts, "
                f"n_routed_experts says {n_held} are held")
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]),
            n_heads=int(d["num_attention_heads"]),
            q_lora_rank=int(d["q_lora_rank"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            n_layers=int(d["num_hidden_layers"]),
            first_k_dense=int(d.get("first_k_dense_replace", 0)),
            router_width=width,
            experts_held=held,
            n_experts_per_tok=int(d["num_experts_per_tok"]),
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            max_seq_len=int(d["max_position_embeddings"]),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rope_factor=float(rope.get("factor", 1.0)),
            rope_original_len=int(rope.get(
                "original_max_position_embeddings",
                d["max_position_embeddings"])),
            rope_beta_fast=float(rope.get("beta_fast", 32)),
            rope_beta_slow=float(rope.get("beta_slow", 1)),
            rope_mscale=float(rope.get("mscale", 1)),
            rope_mscale_all_dim=float(rope.get("mscale_all_dim", 0)),
            rms_eps=float(d.get("rms_norm_eps", 1e-5)),
            param_dtype=str(d.get("param_dtype", "bfloat16")),
        )


# ------------------------------------------------------------------ YaRN

def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1`` past factor 1, else 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original_len: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """``w_j``, ``j < dim / 2``: the plain ``f_j = theta^(-2j/dim)`` where
    a plane turns more than ``beta_fast`` times over the trained length,
    ``f_j / factor`` where fewer than ``beta_slow``, a linear ramp over
    the planes between (their indices floored and ceiled)."""
    j = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / dim)
    if factor <= 1:
        return f

    def plane(turns):
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(plane(beta_fast)), 0)
    high = min(math.ceil(plane(beta_slow)), dim - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def _rope(x, pos, cfg: MlaMoeConfig):
    """Rotate ``x (..., d_r)`` at ``pos``, whose shape is ``x``'s
    leading one (or broadcasts to it).  Planes pair dimension ``j`` with
    ``j + d_r / 2`` (the configuration's ``assumed.rope_pairing``)."""
    half = x.shape[-1] // 2
    ang = jnp.asarray(pos, jnp.float32)[..., None] \
        * jnp.asarray(cfg.rope_freqs(), jnp.float32)
    scale = cfg.rope_cos_sin_scale
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


# ------------------------------------------------------------ parameters

def param_shapes(cfg: MlaMoeConfig) -> dict:
    D, H, V = cfg.hidden_size, cfg.n_heads, cfg.vocab_size
    rq, rkv, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    F0, Fe = cfg.intermediate_size, cfg.moe_intermediate_size
    L, Ld, Lm, N = cfg.n_layers, cfg.first_k_dense, cfg.n_moe, cfg.n_held
    return {
        "embed": (V, D), "head": (V, D), "lnf_g": (D,),
        "attn": {
            "ln1_g": (L, D), "ln2_g": (L, D), "w_qa": (L, D, rq),
            "qa_g": (L, rq), "w_qb": (L, rq, H * cfg.qk_head_dim),
            "w_kva": (L, D, rkv + dr), "kva_g": (L, rkv),
            "w_kvb": (L, rkv, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "w_o": (L, H * cfg.v_head_dim, D),
        },
        "dense": {"w_gu": (Ld, D, 2 * F0), "w_d": (Ld, F0, D)},
        "moe": {
            "w_r": (Lm, D, cfg.router_width), "b_r": (Lm, cfg.router_width),
            "sh_gu": (Lm, D, 2 * Fe), "sh_d": (Lm, Fe, D),
            "ex_gu": (Lm, N, D, 2 * Fe), "ex_d": (Lm, N, Fe, D),
        },
    }


def num_params(cfg: MlaMoeConfig) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_mla_moe(key: jax.Array, cfg: MlaMoeConfig):
    """Seeded random parameters in ``cfg.param_dtype``: matrices N(0,
    1/fan_in), embedding and head N(0, 1/hidden_size) (logits spread by
    about one), gains 1 + N(0, 0.02), the selection bias N(0, 0.05)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(cfg.param_dtype)

    def draw(k, name, shape):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("embed", "head"):
            z = z / np.sqrt(shape[-1])
        elif name.endswith("_g"):
            z = 1.0 + 0.02 * z
        elif name == "b_r":
            z = 0.05 * z
        else:
            z = z / np.sqrt(shape[-2])
        return z.astype(dtype)

    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(leaves)), leaves):
        name = path[-1].key
        if len(shape) < 3 or not shape[0]:
            out.append(draw(k, name, shape))
        else:
            # A layer at a time: the float32 draw of a whole stack of
            # expert matrices is twice the stack.
            out.append(lax.map(lambda kk: draw(kk, name, shape[1:]),
                               jax.random.split(k, shape[0])))
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


@jax.named_scope("mla_moe.attn.project")
def _project(x, blk, pos, cfg):
    """``x (A, D)`` at ``pos (A,)`` -> the queries' two parts ``q_n (A,
    H, d_n)`` and ``q_r (A, H, d_r)`` (rotated), and the position's
    latent row ``(A, r_kv + d_r)``: ``c`` after its norm, then the one
    key every head shares, rotated at ``pos``."""
    A, H, dn = x.shape[0], cfg.n_heads, cfg.qk_nope_head_dim
    h = _rms(x, blk["ln1_g"], cfg.rms_eps)
    q = _rms(h @ blk["w_qa"], blk["qa_g"], cfg.rms_eps) @ blk["w_qb"]
    q = q.reshape(A, H, cfg.qk_head_dim)
    q_r = _rope(q[..., dn:], pos[:, None], cfg)
    ckv = h @ blk["w_kva"]
    c = _rms(ckv[:, :cfg.kv_lora_rank], blk["kva_g"], cfg.rms_eps)
    k_r = _rope(ckv[:, cfg.kv_lora_rank:], pos, cfg)
    return q[..., :dn], q_r, jnp.concatenate([c, k_r], -1)


def _kvb(blk, cfg):
    """``w_kvb`` by head: keys ``(r_kv, H, d_n)``, values ``(r_kv, H,
    d_v)``."""
    w = blk["w_kvb"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


@jax.named_scope("mla_moe.attn.project")
def _attn_out(x, o, blk):
    """``o (A, H, d_v)`` float32 -> the residual stream after attention."""
    return x + o.reshape(o.shape[0], -1).astype(x.dtype) @ blk["w_o"]


def _key_tile(M: int) -> int:
    return next(t for t in _KEY_TILES if M % t == 0)


def attend_kernel_tiles(cfg: MlaMoeConfig, C: int, M: int) -> bool:
    """Whether a chunk of ``C`` positions over a slot of ``M`` latent
    rows runs its expanded attention as the Pallas kernel
    (:mod:`tpu_dist_nn.kernels.expand_attend`): only the shapes decide."""
    return expand_attend.tiles(
        C, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim, M,
        cfg.param_dtype) is not None


@jax.named_scope("mla_moe.attn.expand")
def _attend_expanded(q_n, q_r, rows, t, blk, cfg):
    """A chunk's attention: queries ``q_n (C, H, d_n)``, ``q_r (C, H,
    d_r)`` at positions ``t (C,)`` (consecutive) over the slot's latent
    rows ``(1, r, M)``, which already hold the chunk's own.  A tile of
    key positions at a time, up to the chunk's last: the tile's rows
    are expanded to per-head keys and values (``w_kvb``) and attended
    with a running softmax in float32.  Returns ``(C, H, d_v)``
    float32.

    Which path runs is read from the shapes: the Pallas kernel where
    they tile (a tile's keys, values and scores never leave VMEM), the
    XLA loop, its oracle, where they do not."""
    wk, wv = _kvb(blk, cfg)
    if attend_kernel_tiles(cfg, q_n.shape[0], rows.shape[-1]):
        return expand_attend.attend_chunk(q_n, q_r, rows, wk, wv, t[0],
                                          cfg.softmax_scale)
    return _expanded_loop(q_n, q_r, rows, t, wk, wv, cfg.softmax_scale)


def _expanded_loop(q_n, q_r, rows, t, wk, wv, scale):
    """:func:`_attend_expanded` as a ``fori_loop`` over the visible key
    tiles, each tile's scores ``(H, C, KT)`` float32 an XLA value."""
    C, H = q_n.shape[:2]
    rkv, M = wk.shape[0], rows.shape[-1]
    KT = _key_tile(M)

    def tile(j, carry):
        m, l, acc = carry
        part = lax.dynamic_slice(rows, (0, 0, j * KT), (1, rows.shape[1], KT))
        c, k_r = part[0, :rkv], part[0, rkv:]
        k_n = _einsum32("rk,rhd->hdk", c, wk).astype(c.dtype)
        v = _einsum32("rk,rhd->hkd", c, wv).astype(c.dtype)
        s = (_einsum32("chd,hdk->hck", q_n, k_n)
             + _einsum32("chd,dk->hck", q_r, k_r)) * scale
        seen = (j * KT + jnp.arange(KT))[None, :] <= t[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        # The first tile holds position 0, which every query sees: no
        # row of `m_new` is -inf.
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] \
            + _einsum32("hck,hkd->hcd", p.astype(v.dtype), v)
        return m_new, l * fade + jnp.sum(p, -1), acc

    init = (jnp.full((H, C), -jnp.inf, jnp.float32),
            jnp.zeros((H, C), jnp.float32),
            jnp.zeros((H, C, wv.shape[-1]), jnp.float32))
    _, l, acc = lax.fori_loop(0, t[-1] // KT + 1, tile, init)
    return (acc / l[..., None]).transpose(1, 0, 2)


def step_kernel_tile(cfg: MlaMoeConfig, S: int, M: int):
    """The position tile a step of ``S`` slots over an extent of ``M``
    runs its latent attention with as the Pallas kernel
    (:mod:`tpu_dist_nn.kernels.latent_attend`), or ``None`` where it
    keeps the XLA einsums: only the shapes decide."""
    return latent_attend.tiles(S, cfg.n_heads, cfg.latent_dim,
                               cfg.kv_lora_rank, M, cfg.param_dtype)


@jax.named_scope("mla_moe.attn.latent")
def _attend_latent(q_n, q_r, lat, layer, own, live, blk, cfg):
    """The decode step's attention, one query a slot: ``q_n (S, H,
    d_n)``, ``q_r (S, H, d_r)`` over the first ``live (S,)`` positions
    of slots ``[0, S)`` of layer ``layer`` (traced) of the whole cache
    ``lat (L, slots, 1, r, M)`` and over the position's own row ``own
    (S, r)``, which no cache holds yet.  ``w_kvb``'s key half is folded
    into the query and its value half applied behind the softmax: the
    rows enter both products as they lie (the rotated key's ``d_r`` are
    no values: no slice of the cache is made).  Returns ``(S, H, d_v)``
    float32.

    Which path runs is read from the shapes: the Pallas kernel where
    they tile (each slot's live rows once, the scores on the chip), the
    XLA einsums over the whole extent, its oracle, where they do not."""
    S, M, rkv = q_n.shape[0], lat.shape[-1], cfg.kv_lora_rank
    wk, wv = _kvb(blk, cfg)
    q = jnp.concatenate(
        [_einsum32("shd,rhd->shr", q_n, wk).astype(q_r.dtype), q_r], -1)
    if step_kernel_tile(cfg, S, M) is not None:
        o = latent_attend.attend_rows(q, lat, layer, own, live, rkv,
                                      cfg.softmax_scale)
    else:
        rows = lax.dynamic_slice(
            lat, (layer, 0, 0, 0, 0), (1, S) + lat.shape[2:])[0]
        o = _latent_einsums(q, rows, own, live, cfg.softmax_scale)[..., :rkv]
    return _einsum32("shr,rhd->shd", o.astype(lat.dtype), wv)


def _latent_einsums(q, rows, own, live, scale):
    """:func:`_attend_latent` over ``rows (S, 1, r, M)`` as XLA values:
    every slot's whole extent scored and masked, all ``r`` numbers of a
    row through the second product.  ``(S, H, r)`` float32."""
    M = rows.shape[-1]
    q = q[:, None]  # (S, 1, H, r): the one latent "head" a batch dimension
    s = _einsum32("sghr,sgrm->sghm", q, rows) * scale
    mine = _einsum32("sghr,sgr->sgh", q.astype(jnp.float32),
                     own[:, None].astype(jnp.float32)) * scale
    visible = jnp.arange(M)[None, :] < live[:, None]
    s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, mine[..., None]], -1), -1)
    o = _einsum32("sghm,sgrm->sghr", p[..., :M].astype(rows.dtype), rows) \
        + p[..., M:] * own[:, None, None, :].astype(jnp.float32)
    return o[:, 0]


@jax.named_scope("mla_moe.mlp")
def _dense_ffn(x, norm_g, blk, cfg):
    return x + _swiglu(_rms(x, norm_g, cfg.rms_eps), blk["w_gu"], blk["w_d"])


@jax.named_scope("mla_moe.router")
def route(u, blk, cfg):
    """``u (A, D)`` -> the chosen experts ``(A, k)`` and their weights
    ``(A, k)`` float32.  Scores, bias, ``top_k`` and weights in
    float32 from a float32 product (a flipped choice is another expert,
    not a rounding): the bias chooses, the scores weigh."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ad,de->ae", u.astype(jnp.float32), blk["w_r"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(s + blk["b_r"].astype(jnp.float32),
                          cfg.n_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling_factor
    return chosen, w


def _held_gates(chosen, w, cfg):
    """``(A, n_held)``: whether each token chose each held expert, and
    the expert's weight for it (:func:`.experts.held_gates`)."""
    return experts.held_gates(chosen, w, cfg.experts_held)


@jax.named_scope("mla_moe.shared")
def _shared_expert(u, blk):
    return _swiglu(u, blk["sh_gu"], blk["sh_d"])


def _moe_ffn(x, norm_g, moe, layer, counted, cfg):
    """``x (A, D)`` through expert layer ``layer`` of the stacked
    ``moe`` parameters: the shared expert plus the held experts' part of
    the routed sum (:mod:`.experts`).  Also the layer's routing counts
    over the tokens ``counted (A,)``: pairs by held expert
    ``(n_held,)``."""
    blk = _layer({n: a for n, a in moe.items() if not n.startswith("ex_")},
                 layer)
    u = _rms(x, norm_g, cfg.rms_eps)
    chosen, w = route(u, blk, cfg)
    on, gates = _held_gates(chosen, w, cfg)
    routed, pairs = experts.routed(u, on, gates, moe["ex_gu"], moe["ex_d"],
                                   layer, counted, "mla_moe.experts")
    return x + _shared_expert(u, blk) + routed.astype(x.dtype), pairs


def _counts(cfg, pairs, tokens, step: bool):
    """What one launch adds to ``cache["routed"]``
    (:func:`.experts.counts`)."""
    return experts.counts(cfg.n_moe, cfg.n_held, cfg.n_experts_per_tok,
                          pairs, tokens, step)


# ----------------------------------------------------------- slot cache

def _extent(max_len: int) -> int:
    """``max_len`` rounded up to whole 128-lane tiles."""
    return -(-int(max_len) // _LANES) * _LANES


def init_slot_cache(cfg: MlaMoeConfig, slots: int, max_len: int) -> dict:
    """The zeroed slot cache (module docstring)."""
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if max_len < 1 or max_len > cfg.max_seq_len:
        raise ValueError(
            f"max_len must be in [1, {cfg.max_seq_len}], got {max_len}")
    return {
        "lat": jnp.zeros((cfg.n_layers, slots, 1, cfg.latent_dim,
                          _extent(max_len)), jnp.dtype(cfg.param_dtype)),
        "routed": jnp.zeros((cfg.n_held + 3,), jnp.int32),
    }


def cache_bytes(cache: dict) -> dict:
    """Bytes of the cache by kind, for ``tdn_gen_cache_bytes``."""
    return {"latent": int(cache["lat"].size) * cache["lat"].dtype.itemsize}


def copy_cache_slot(cache: dict, src, dst) -> dict:
    """Copy slot ``src``'s latent rows onto slot ``dst``.  Rows past a
    prefix's length ride along and are masked by position, as K/V rows
    are; the routing counts belong to no slot."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    lat = cache["lat"]
    return dict(cache, lat=_put_slot(lat, _take_slot(lat, src), dst))


def _chunk_hidden(params, cfg, cache, slot, tokens, start):
    """The chunk ``tokens (1, C)`` at positions ``start ..`` of slot
    ``slot`` through every layer: ``(x (C, D), cache)``."""
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    C = tokens.shape[1]
    t = start + jnp.arange(C)
    x = params["embed"][tokens[0]]
    every = jnp.ones((C,), bool)

    def attend(x, blk, rows):
        q_n, q_r, new = _project(x, blk, t, cfg)
        rows = lax.dynamic_update_slice(
            rows, new.T[None].astype(rows.dtype), (0, 0, start))
        return _attn_out(x, _attend_expanded(q_n, q_r, rows, t, blk, cfg),
                         blk), rows

    def dense(x, inputs):
        i, rows = inputs
        blk = _layer(params["attn"], i)
        x, rows = attend(x, blk, rows)
        return _dense_ffn(x, blk["ln2_g"], _layer(params["dense"], i),
                          cfg), rows

    def moe(x, inputs):
        i, rows = inputs
        blk = _layer(params["attn"], cfg.first_k_dense + i)
        x, rows = attend(x, blk, rows)
        x, pairs = _moe_ffn(x, blk["ln2_g"], params["moe"], i, every, cfg)
        return x, (rows, pairs)

    mine = _take_slot(cache["lat"], slot)  # (L, 1, r, M)
    Ld = cfg.first_k_dense
    x, head = lax.scan(dense, x, (jnp.arange(Ld), mine[:Ld]))
    x, (tail, pairs) = lax.scan(moe, x, (jnp.arange(cfg.n_moe), mine[Ld:]))
    cache = {
        "lat": _put_slot(cache["lat"], jnp.concatenate([head, tail]), slot),
        "routed": cache["routed"] + _counts(cfg, pairs, jnp.int32(C), False),
    }
    return x, cache


def prefill_chunk_into_cache(params: dict, cfg: MlaMoeConfig, cache: dict,
                             slot, tokens: jnp.ndarray, start):
    """Prefill ONE CHUNK into slot ``slot`` and give the logits of its
    last position.  ``tokens (1, C)`` at ``[start, start + C)``;
    ``slot`` and ``start`` are traced.  Returns ``(logits (1, V),
    cache)``."""
    x, cache = _chunk_hidden(params, cfg, cache, slot, tokens, start)
    return _unembed(params, x[-1:], cfg), cache


def decode_step_slots(params: dict, cache: dict, pos: jnp.ndarray,
                      token: jnp.ndarray, cfg: MlaMoeConfig,
                      active: jnp.ndarray | None = None):
    """One decode step for the first ``S`` slots: ``token (S,)`` at
    per-slot ``pos (S,)``, gated by ``active (S,)``.  Every layer reads
    its latent rows where they lie; the new rows of all layers land at
    ``pos`` in place, an inactive slot's rows stay bit for bit and its
    tokens are in no routing count.  Returns ``(logits (S, V),
    cache)``."""
    S = token.shape[0]
    if active is None:
        active = jnp.ones((S,), bool)
    lat = cache["lat"]
    M = lat.shape[-1]
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, M - 1)
    # A slot the step does not decode attends its own row alone: nothing
    # of its rows is read into a softmax.
    live = jnp.where(active, pos, 0)
    x = params["embed"][token]

    def attend(x, blk, layer):
        q_n, q_r, new = _project(x, blk, pos, cfg)
        o = _attend_latent(q_n, q_r, lat, layer, new, live, blk, cfg)
        return _attn_out(x, o, blk), new

    def dense(x, i):
        blk = _layer(params["attn"], i)
        x, new = attend(x, blk, i)
        return _dense_ffn(x, blk["ln2_g"], _layer(params["dense"], i),
                          cfg), new

    def moe(x, i):
        layer = cfg.first_k_dense + i
        blk = _layer(params["attn"], layer)
        x, new = attend(x, blk, layer)
        x, pairs = _moe_ffn(x, blk["ln2_g"], params["moe"], i, active, cfg)
        return x, (new, pairs)

    x, head = lax.scan(dense, x, jnp.arange(cfg.first_k_dense))
    x, (tail, pairs) = lax.scan(moe, x, jnp.arange(cfg.n_moe))
    new = jnp.concatenate([head, tail])[:, :, None, :]  # (L, S, 1, r)
    cache = {
        "lat": write_row(lat, new.astype(lat.dtype), pos, active),
        "routed": cache["routed"] + _counts(
            cfg, pairs, jnp.sum(active, dtype=jnp.int32), True),
    }
    return _unembed(params, x, cfg), cache


def forward(params: dict, tokens: jnp.ndarray, cfg: MlaMoeConfig):
    """Full-sequence logits ``(B, T, V)`` of ``tokens (B, T)``, for
    tests: each row as one whole-prompt chunk into a scratch cache."""
    T = tokens.shape[1]

    def row(toks):
        x, _ = _chunk_hidden(params, cfg, init_slot_cache(cfg, 1, T), 0,
                             toks[None], 0)
        return _unembed(params, x, cfg)

    return jnp.stack([row(toks) for toks in tokens])


# ----------------------------------------------------------- FLOP model

class MlaMoeFlopModel:
    """Analytic FLOPs of the generation kernels, with the method names
    of :class:`tpu_dist_nn.obs.goodput.LMFlopModel`.  Multiply-adds
    count two.  USEFUL counts, a position and layer: the five attention
    projections, the keys it attends (the absorbed form's ``2 H (r +
    r_kv)`` a key in a step; a chunk's ``2 H (d_qk + d_v)`` and the
    expansion of each attended position once a chunk), the router, the
    shared expert, and of the routed experts the ``k n_held /
    router_width`` pairs a token sends to experts held HERE on average
    (the host does not see the routing; the device's own count is
    ``tdn_gen_expert_pairs_total``), never a dense ``4 d f``.  The
    STATIC launch counts the step's whole extent and every held expert
    on every token; for a chunk the whole extent too (an upper bound: its
    key tiles stop at its end) and, where its product is ragged, the
    average pairs or a tile of every held expert, whichever is more."""

    def __init__(self, cfg: MlaMoeConfig, cache_extent: int):
        self.cfg, self.M = cfg, _extent(cache_extent)
        D, H = cfg.hidden_size, cfg.n_heads
        rq, rkv, r = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.latent_dim
        dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        L, Lm = cfg.n_layers, cfg.n_moe
        proj = 2 * (D * rq + rq * H * cfg.qk_head_dim + D * r + H * dv * D)
        self._expert = 6 * D * cfg.moe_intermediate_size
        self._fixed = L * proj \
            + cfg.first_k_dense * 6 * D * cfg.intermediate_size \
            + Lm * (2 * D * cfg.router_width + self._expert)
        self._absorb = L * 2 * H * rkv * (dn + dv)
        self._step_key = L * 2 * H * (r + rkv)
        self._chunk_key = L * 2 * H * (cfg.qk_head_dim + dv)
        self._expand = L * 2 * rkv * H * (dn + dv)
        self._logit = 2 * D * cfg.vocab_size
        # Routed pairs a token sends to experts held here, as a ratio.
        self._pairs = (cfg.n_experts_per_tok * cfg.n_held, cfg.router_width)

    def _routed(self, tokens: int) -> int:
        num, den = self._pairs
        return self.cfg.n_moe * self._expert * int(tokens) * num // den

    # -- decode step ---------------------------------------------------
    def step_flops(self) -> int:
        return self._fixed + self._absorb + self._step_key * self.M \
            + self.cfg.n_moe * self.cfg.n_held * self._expert + self._logit

    def step_useful_flops(self, pos: int) -> int:
        return self._fixed + self._absorb + self._step_key * (int(pos) + 1) \
            + self._routed(1) + self._logit

    def steps_useful_sum(self, start_pos: int, n_steps: int) -> int:
        n, s = max(int(n_steps), 0), int(start_pos)
        return n * (self._fixed + self._absorb + self._logit) \
            + self._step_key * (n * s + n * (n + 1) // 2) + self._routed(n)

    # -- prefill chunk -------------------------------------------------
    def chunk_flops(self, size: int) -> int:
        c = int(size)
        if experts_form(c) == "dense":
            routed = c * self.cfg.n_moe * self.cfg.n_held * self._expert
        else:
            routed = max(self._routed(c), self.cfg.n_moe * self.cfg.n_held
                         * experts.PAIR_TILE * self._expert)
        return c * self._fixed + (self._chunk_key * c + self._expand) \
            * self.M + routed + self._logit

    def chunk_useful_flops(self, start: int, size: int, final: bool) -> int:
        s, c = int(start), int(size)
        keys = c * s + c * (c + 1) // 2
        return c * self._fixed + self._chunk_key * keys \
            + self._expand * (s + c) + self._routed(c) \
            + (self._logit if final else 0)

    def prefill_chunks_flops(self, start: int, end: int,
                             chunk: int | None) -> int:
        total, pos, end = 0, int(start), int(end)
        while pos < end:
            c = end - pos if chunk is None else min(int(chunk), end - pos)
            total += self.chunk_flops(c)
            pos += c
        return total
