"""Autoregressive decoding with a static KV cache.

The reference serves only feed-forward classifiers; the transformer
family adds next-token generation, built TPU-first:

* **Static shapes throughout**: the KV cache is a fixed
  ``(L, B, max_len, H, Dh)`` buffer written with
  ``lax.dynamic_update_slice``; the decode loop is one ``lax.scan``
  over ``max_new_tokens`` steps — one compile regardless of prompt or
  generation length.
* **Prefill + decode split**: the prompt runs through the full batched
  forward once (MXU-shaped matmuls), recording each layer's K/V from
  the shared attention sublayer; per-token decode then attends a
  single query against the cache.
* **Sampling**: greedy at ``temperature == 0`` (exact argmax of the
  full forward — tested against the teacher-forced oracle), else
  softmax sampling with an explicit PRNG key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_dist_nn.kernels.kv_write import write_rows
from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    attn_sublayer,
    embed,
    ffn_sublayer,
    layer_norm,
    unembed,
)


def prefill_blocks(blocks: dict, x: jnp.ndarray, cfg: TransformerConfig,
                   max_len: int):
    """Run ``x (B, T, D)`` through a stacked block group, filling a
    ``max_len`` cache for THOSE blocks — the per-stage building block
    of :func:`prefill` and the pipelined decoder
    (:mod:`tpu_dist_nn.parallel.pp_generate`)."""
    T = x.shape[1]

    def body(carry, block):
        y, k, v = attn_sublayer(block, carry, cfg, return_kv=True)
        return ffn_sublayer(block, y), (k, v)

    x, (ks, vs) = lax.scan(body, x, blocks)
    pad = [(0, 0), (0, 0), (0, max_len - T), (0, 0), (0, 0)]
    return x, {"k": jnp.pad(ks, pad), "v": jnp.pad(vs, pad)}


def prefill(params: dict, tokens: jnp.ndarray, cfg: TransformerConfig,
            max_len: int):
    """Run the prompt ``(B, T)``, filling a ``max_len`` cache.

    Returns ``(logits (B, T, V), cache)`` — the caller samples from
    ``logits[:, T-1]`` and decodes from position ``T``.
    """
    params = cfg.cast_params(params)
    T = tokens.shape[1]
    if T > max_len:
        raise ValueError(f"prompt length {T} exceeds cache length {max_len}")
    x = embed(params, tokens)
    x, cache = prefill_blocks(params["blocks"], x, cfg, max_len)
    return unembed(params, x), cache


def decode_blocks(blocks: dict, cache: dict, pos, x: jnp.ndarray,
                  cfg: TransformerConfig):
    """One decode step through a stacked block group: ``x (B, 1, D)``
    attends against the group's cache (updated at ``pos``). The
    per-stage building block of :func:`decode_step` and the pipelined
    decoder. Attention masks positions ``> pos`` (the rest of the
    buffer is zero-filled future space).

    Numerics here and in :func:`decode_blocks_slots` must stay in step
    (same casts, f32 scores and softmax, one rounding of the values):
    the slot step sums the same ``pos + 1`` terms with the token's own
    key last instead of at index ``pos``, so logits agree to rounding,
    and the continuous scheduler's greedy tokens are held equal to the
    static decode's (CI: test_continuous_matches_static_greedy_tokens).
    """
    B = x.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    M = cache["k"].shape[2]

    def body(carry, inputs):
        x = carry
        block, k_cache, v_cache = inputs
        h = layer_norm(x, block["ln1_g"], block["ln1_b"])
        qkv = h @ block["w_qkv"] + block["b_qkv"]
        q, k, v = jnp.split(qkv.reshape(B, 1, 3 * H, Dh), 3, axis=2)
        k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
        v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32),
            k_cache.astype(jnp.float32),
        ) / np.sqrt(Dh)
        live = jnp.arange(M) <= pos
        scores = jnp.where(live[None, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache).reshape(B, 1, H * Dh)
        x = x + o @ block["w_o"] + block["b_o"]
        return ffn_sublayer(block, x), (k_cache, v_cache)

    x, (ks, vs) = lax.scan(body, x, (blocks, cache["k"], cache["v"]))
    return x, {"k": ks, "v": vs}


def decode_step(params: dict, cache: dict, pos, token: jnp.ndarray,
                cfg: TransformerConfig):
    """One decode step: ``token (B,) int32`` at position ``pos``.

    Returns ``(logits (B, V), cache)`` with the cache updated at
    ``pos``.
    """
    params = cfg.cast_params(params)
    x = params["tok_embed"][token][:, None, :] + params["pos_embed"][pos][None, None, :]
    x, cache = decode_blocks(params["blocks"], cache, pos, x, cfg)
    return unembed(params, x)[:, 0], cache


def _truncate_logits(logits: jnp.ndarray, top_k: int | None,
                     top_p: float | None) -> jnp.ndarray:
    """Restrict ``logits (B, V)`` to the top-k and/or nucleus (top-p)
    candidate sets by pushing everything else to -inf.

    Both filters are static (jit-recompiles per setting, like
    temperature). Top-p keeps the smallest prefix of
    probability-sorted tokens whose cumulative mass reaches ``p``
    (the first token always survives, so the set is never empty).
    """
    neg = jnp.finfo(jnp.float32).min
    logits = logits.astype(jnp.float32)
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Token i survives if the mass *before* it is < p; the largest
        # surviving sorted logit is the cutoff.
        keep = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < top_p],
            axis=-1,
        )
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, neg, logits)
    return logits


def validate_generate_args(cfg: TransformerConfig, prompt_len: int,
                           max_new_tokens: int, temperature: float,
                           top_k: int | None, top_p: float | None,
                           key: jax.Array | None,
                           eos_id: int | None = None) -> jax.Array:
    """The generation argument contract, shared by the single-chip and
    tensor-parallel decode paths (so they cannot drift). Returns the key
    to use (a dummy on the greedy path)."""
    total = prompt_len + max_new_tokens
    if not cfg.causal:
        raise ValueError(
            "generation requires a causal model (decode_step always "
            "masks future positions; cfg.causal=False would disagree "
            "with the prefill logits)"
        )
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    # The decoders embed positions 0 .. total-2 only (the final sampled
    # token is returned, never fed back), so the positional table needs
    # total-1 rows — total == max_seq_len + 1 is a VALID boundary call
    # (every decode path sizes its cache total-1; ADVICE r5: the shared
    # validator must not reject what the decoders accept).
    if total - 1 > cfg.max_seq_len:
        raise ValueError(
            f"prompt {prompt_len} + new {max_new_tokens} needs "
            f"{total - 1} positions, exceeding max_seq_len "
            f"{cfg.max_seq_len}"
        )
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(
            f"top_k must be in [1, {cfg.vocab_size}], got {top_k}"
        )
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature == 0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p shape the sampling distribution; greedy "
            "decoding (temperature == 0) would silently ignore them"
        )
    if eos_id is not None and not 0 <= int(eos_id) < cfg.vocab_size:
        raise ValueError(
            f"eos_id must be in [0, {cfg.vocab_size}), got {eos_id}"
        )
    return key if key is not None else jax.random.key(0)


def generate(params: dict, cfg: TransformerConfig, prompt: jnp.ndarray,
             max_new_tokens: int, *, temperature: float = 0.0,
             top_k: int | None = None, top_p: float | None = None,
             key: jax.Array | None = None, eos_id: int | None = None):
    """Generate ``(B, max_new_tokens)`` continuations of ``prompt (B, T)``.

    Greedy when ``temperature == 0`` (no key needed), else samples from
    ``softmax(logits / temperature)`` using ``key``, optionally
    restricted to the ``top_k`` highest-probability tokens and/or the
    ``top_p`` nucleus. ``T + max_new_tokens - 1`` positions must fit
    ``cfg.max_seq_len`` (the final sampled token is never embedded, so
    the positional table needs one row fewer than the total length).
    jit-compatible: static
    ``max_new_tokens``/``temperature``/``top_k``/``top_p``/``eos_id``.

    ``eos_id`` enables stop-token semantics under the static shape: a
    row that emits ``eos_id`` is FROZEN by a done-mask in the scan
    carry — every later position emits ``eos_id`` (the pad) and its
    sampling draws no longer affect the output. The shape stays
    ``(B, max_new_tokens)``; the continuous-batching scheduler
    (:mod:`tpu_dist_nn.serving.continuous`) reuses exactly these
    semantics so the two schedulers are output-comparable.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    B, T = prompt.shape
    key = validate_generate_args(
        cfg, T, max_new_tokens, temperature, top_k, top_p, key, eos_id
    )
    # Sampling knobs become lru-cache keys: coerce to python scalars so
    # concrete jax/numpy values (unhashable) keep working.
    temperature = float(temperature)
    top_k = None if top_k is None else int(top_k)
    top_p = None if top_p is None else float(top_p)
    eos_id = None if eos_id is None else int(eos_id)
    run = _compiled_generate(
        cfg, T, max_new_tokens, temperature, top_k, top_p, eos_id
    )
    return run(params, prompt, key)


@functools.lru_cache(maxsize=64)
def _compiled_generate(cfg: TransformerConfig, T: int, max_new_tokens: int,
                       temperature, top_k, top_p, eos_id=None):
    """One jitted prefill+decode program per (cfg, lengths, sampling)
    configuration — rebuilding the scan per generate() call would pay
    the trace (and, without the persistent cache, the compile) every
    time."""
    total = T + max_new_tokens

    def sample(logits, k):
        if temperature == 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = _truncate_logits(logits, top_k, top_p)
        return jax.random.categorical(
            k, logits / temperature, axis=-1
        ).astype(jnp.int32)

    def freeze(done, tok):
        """Stop-token semantics: a finished row keeps emitting the pad
        (eos_id itself); the token that EQUALS eos_id is still emitted
        (then marks the row done)."""
        if eos_id is None:
            return done, tok
        tok = jnp.where(done, jnp.int32(eos_id), tok)
        return done | (tok == eos_id), tok

    @jax.jit
    def run(params, prompt, key):
        # The last decode writes position T + N - 2; size the cache
        # exactly.
        logits, cache = prefill(params, prompt, cfg, max_len=total - 1)
        first = sample(logits[:, T - 1], key)
        done0, first = freeze(jnp.zeros(prompt.shape[0], bool), first)
        if max_new_tokens == 1:
            return first[:, None]

        def body(carry, step_key):
            cache, token, pos, done = carry
            logits, cache = decode_step(params, cache, pos, token, cfg)
            nxt = sample(logits, step_key)
            done, nxt = freeze(done, nxt)
            return (cache, nxt, pos + 1, done), nxt

        keys = jax.random.split(
            jax.random.fold_in(key, 1), max_new_tokens - 1
        )
        (_, _, _, _), rest = lax.scan(
            body, (cache, first, jnp.int32(T), done0), keys
        )
        return jnp.concatenate(
            [first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1
        )  # (B, max_new_tokens)

    return run


# ---------------------------------------------------------------------------
# Slot-wise decoding: the kernels under the continuous-batching scheduler
# (serving/continuous.py). One fixed (L, S, H, Dh, max_len) cache holds S
# independent request slots; prefill lands a prompt's K/V into ANY free
# slot, and one compiled step advances every slot at its OWN position.
# ---------------------------------------------------------------------------


def init_slot_cache(cfg: TransformerConfig, slots: int, max_len: int,
                    dtype=None) -> dict:
    """A zeroed ``(L, S, H, Dh, max_len)`` slot KV cache.

    The POSITION axis is last, which is how a TPU stores such a buffer
    whatever shape it is declared in: with ``Dh = 64`` it puts positions
    in the 128 lanes and ``Dh`` in the sublanes rather than pad every
    head to 128 lanes (a ``(L, S, M, H, Dh)`` array lives on a v5e as
    ``{2,4,3,1,0:T(8,128)(2,1)}``). Declaring what is stored lets the
    decode step read a layer where it lies and lets the three programs
    that share the buffer (step, chunk prefill, slot copy) agree on one
    layout by construction. :func:`prefill`'s batch cache keeps its
    ``(L, B, max_len, H, Dh)``; :func:`rows_to_slots` turns one into
    the other. Static by construction: admission and retirement never
    change the shape, only which slots the active mask selects (the
    TPU-friendly answer to paged KV — see docs/PERF.md "Continuous
    batching").
    """
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    if max_len < 1 or max_len > cfg.max_seq_len:
        raise ValueError(
            f"max_len must be in [1, {cfg.max_seq_len}], got {max_len}"
        )
    dtype = jnp.dtype(cfg.compute_dtype) if dtype is None else dtype
    shape = (cfg.n_layers, slots, cfg.n_heads, cfg.head_dim, max_len)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_bytes(cache: dict) -> dict:
    """Bytes of the slot cache by kind (``tdn_gen_cache_bytes``)."""
    return {"kv": sum(int(a.size) * a.dtype.itemsize
                      for a in (cache["k"], cache["v"]))}


def rows_to_slots(rows: jnp.ndarray) -> jnp.ndarray:
    """Batch-cache axes ``(L, B, M, H, Dh)`` -> slot-cache axes."""
    return rows.transpose(0, 1, 3, 4, 2)


def slots_to_rows(slots: jnp.ndarray) -> jnp.ndarray:
    """Slot-cache axes ``(L, S, H, Dh, M)`` -> batch-cache axes."""
    return slots.transpose(0, 1, 4, 2, 3)


def prefill_into_cache(params: dict, cfg: TransformerConfig, cache: dict,
                       slot, tokens: jnp.ndarray):
    """Prefill one prompt ``(1, T)`` INTO slot ``slot`` of a slot cache.

    Runs the full prompt forward once and lands its K/V at an ARBITRARY
    (traced) slot index via ``lax.dynamic_update_slice`` — admission at
    decode-step granularity needs to fill whichever slot just retired,
    not a static position. The whole ``max_len`` extent of the slot is
    overwritten (the prefill cache is zero-padded past ``T``), so a
    reused slot can never leak its previous occupant's K/V.

    Returns ``(logits (1, V), cache)``: the last prompt position's
    logits (the caller samples the first generated token from them)
    and the updated slot cache.
    """
    M = cache["k"].shape[-1]
    logits, row = prefill(params, tokens, cfg, max_len=M)
    slot = jnp.asarray(slot, jnp.int32)
    at = (0, slot, 0, 0, 0)
    cache = {
        part: lax.dynamic_update_slice(
            cache[part], rows_to_slots(row[part]).astype(cache[part].dtype),
            at,
        )
        for part in ("k", "v")
    }
    return logits[:, tokens.shape[1] - 1], cache


def copy_cache_slot(cache: dict, src, dst) -> dict:
    """Copy slot ``src``'s FULL ``max_len`` extent onto slot ``dst``
    (both traced indices) — the prefix-cache transfer primitive
    (:mod:`tpu_dist_nn.serving.continuous`): pool-block -> request-slot
    on a prefix HIT (the copy-on-write admission, after which the
    request decodes into its own slot and can never mutate the shared
    block), and request-slot -> pool-block on INSERT.

    Copying the whole extent (not just the prefix length) keeps the
    kernel one compile for every (src, dst, length) combination; the
    bytes past the prefix frontier are dead either way — a suffix
    prefill overwrites ``[len, T)`` and attention masks positions
    beyond the decode frontier (the same argument that makes slot
    reuse safe).
    """
    L, _, H, Dh, M = cache["k"].shape
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    at_src = (0, src, 0, 0, 0)
    at_dst = (0, dst, 0, 0, 0)
    size = (L, 1, H, Dh, M)
    return {
        "k": lax.dynamic_update_slice(
            cache["k"], lax.dynamic_slice(cache["k"], at_src, size), at_dst
        ),
        "v": lax.dynamic_update_slice(
            cache["v"], lax.dynamic_slice(cache["v"], at_src, size), at_dst
        ),
    }


def prefill_chunk_into_cache(params: dict, cfg: TransformerConfig,
                             cache: dict, slot, tokens: jnp.ndarray,
                             start):
    """Prefill ONE CHUNK of a prompt into slot ``slot``: ``tokens
    (1, C)`` occupy positions ``[start, start + C)`` and attend to the
    slot's already-filled cache (positions ``< start`` — a cached
    prefix block copied in by :func:`copy_cache_slot`, or earlier
    chunks of this same prompt) plus themselves, causally.

    With ``start == 0`` and ``C == T`` this is a whole-prompt prefill
    (the monolithic :func:`prefill_into_cache` path expressed in chunk
    form) — the continuous scheduler routes EVERY admission through
    this kernel so cache-on and cache-off prefills share one numeric
    path and the greedy bit-parity anchor holds by construction.
    Numerics deliberately mirror :func:`decode_blocks` (same casts,
    same f32 score/softmax order, reduction over the full ``max_len``
    key extent) for the same reason.

    ``slot`` and ``start`` are traced: one compile per chunk LENGTH
    covers every slot and every chunk position. Returns
    ``(logits (1, V) of the chunk's last position, cache)`` — only the
    final chunk's logits are sampled from (they are the prompt's
    last-position logits).
    """
    with jax.named_scope("params.cast"):
        params = cfg.cast_params(params)
    Lc, S, H, Dh, M = cache["k"].shape
    C = tokens.shape[1]
    D = cfg.d_model
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    with jax.named_scope("embed"):
        x = params["tok_embed"][tokens] + lax.dynamic_slice(
            params["pos_embed"], (start, 0), (C, D)
        )[None]
    # Key position j is visible to chunk-local query i iff j <= start+i
    # (the causal mask, offset into the slot's timeline); everything
    # beyond the chunk's own frontier is future space.
    allowed = (
        jnp.arange(M)[None, :]
        <= (start + jnp.arange(C))[:, None]
    )  # (C, M)
    # The slot's own extent, one prompt's worth, in the batch cache's
    # axes: small enough that the compiler lays it out as it likes.
    k_rows = slots_to_rows(lax.dynamic_slice(
        cache["k"], (0, slot, 0, 0, 0), (Lc, 1, H, Dh, M)
    ))
    v_rows = slots_to_rows(lax.dynamic_slice(
        cache["v"], (0, slot, 0, 0, 0), (Lc, 1, H, Dh, M)
    ))

    def body(carry, inputs):
        x = carry
        block, k_cache, v_cache = inputs
        h = layer_norm(x, block["ln1_g"], block["ln1_b"])
        qkv = h @ block["w_qkv"] + block["b_qkv"]
        q, k, v = jnp.split(qkv.reshape(1, C, 3 * H, Dh), 3, axis=2)
        with jax.named_scope("kv.write"):
            k_cache = lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, start, 0, 0)
            )
            v_cache = lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, start, 0, 0)
            )
        with jax.named_scope("attn.scores"):
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q.astype(jnp.float32),
                k_cache.astype(jnp.float32),
            ) / np.sqrt(Dh)
        with jax.named_scope("attn.softmax"):
            scores = jnp.where(allowed[None, None, :, :], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        with jax.named_scope("attn.values"):
            o = jnp.einsum(
                "bhqk,bkhd->bqhd", probs, v_cache
            ).reshape(1, C, H * Dh)
            x = x + o @ block["w_o"] + block["b_o"]
        with jax.named_scope("ffn"):
            x = ffn_sublayer(block, x)
        return x, (k_cache, v_cache)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], k_rows, v_rows))
    with jax.named_scope("kv.write"):
        cache = {
            "k": lax.dynamic_update_slice(
                cache["k"], rows_to_slots(ks), (0, slot, 0, 0, 0)
            ),
            "v": lax.dynamic_update_slice(
                cache["v"], rows_to_slots(vs), (0, slot, 0, 0, 0)
            ),
        }
    with jax.named_scope("unembed"):
        logits = unembed(params, x)[:, C - 1]
    return logits, cache


def decode_blocks_slots(blocks: dict, cache: dict, pos: jnp.ndarray,
                        x: jnp.ndarray, cfg: TransformerConfig,
                        active: jnp.ndarray):
    """One decode step through a stacked block group with PER-SLOT
    positions: ``x (S, 1, D)`` attends against the cache of slots
    ``[0, S)`` (the cache may hold more: the scheduler's prefix pool
    rides behind them and is neither read nor written here), and the
    token's own key and value land at ``pos[s]`` for active slots only.

    The cache is READ where it lies: the layer scan closes over it and
    slices its layer out inside the body, so no layer is carried from
    ``xs`` to ``ys`` (which the compiler can only do through a second
    whole cache). A layer's attention is one softmax over the ``pos[s]``
    stored keys (positions ``>= pos[s]`` masked: stale K/V beyond a
    slot's frontier is unreachable) and, as one more column, the
    token's own key — the same ``pos[s] + 1`` keys the scalar-``pos``
    :func:`decode_blocks` sees after its write. The scan's ``ys`` are
    the new ``(S, H, Dh)`` key and value of each layer, which
    :func:`~tpu_dist_nn.kernels.kv_write.write_rows` lands after it, in
    place; a retired slot's cache stays bit for bit.
    """
    S = x.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    k_cache, v_cache = cache["k"], cache["v"]
    L, _, _, _, M = k_cache.shape
    stored = jnp.arange(M)[None, :] < pos[:, None]  # (S, M)

    def body(carry, inputs):
        x = carry
        block, layer = inputs
        h = layer_norm(x, block["ln1_g"], block["ln1_b"])
        qkv = h @ block["w_qkv"] + block["b_qkv"]
        q, k, v = jnp.split(qkv.reshape(S, 1, 3 * H, Dh), 3, axis=2)
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        at, size = (layer, 0, 0, 0, 0), (1, S, H, Dh, M)
        with jax.named_scope("attn.scores"):
            q = q.astype(jnp.float32)
            scores = jnp.einsum(
                "bqhd,bhdk->bhqk", q,
                lax.dynamic_slice(k_cache, at, size)[0].astype(jnp.float32),
            ) / np.sqrt(Dh)
            own = jnp.einsum(
                "bqhd,bqhd->bhq", q, k.astype(jnp.float32)
            )[..., None] / np.sqrt(Dh)
        with jax.named_scope("attn.softmax"):
            scores = jnp.where(stored[:, None, None, :], scores, -jnp.inf)
            probs = jax.nn.softmax(
                jnp.concatenate([scores, own], axis=-1), axis=-1
            ).astype(x.dtype)
        with jax.named_scope("attn.values"):
            o = jnp.einsum(
                "bhqk,bhdk->bqhd", probs[..., :M],
                lax.dynamic_slice(v_cache, at, size)[0],
                preferred_element_type=jnp.float32,
            ) + (probs[..., M:].transpose(0, 2, 1, 3).astype(jnp.float32)
                 * v.astype(jnp.float32))
            o = o.astype(x.dtype).reshape(S, 1, H * Dh)
            x = x + o @ block["w_o"] + block["b_o"]
        with jax.named_scope("ffn"):
            x = ffn_sublayer(block, x)
        return x, (k[:, 0], v[:, 0])

    x, (ks, vs) = lax.scan(body, x, (blocks, jnp.arange(L)))
    with jax.named_scope("kv.write"):
        k_cache, v_cache = write_rows(k_cache, v_cache, ks, vs, pos, active)
    return x, {"k": k_cache, "v": v_cache}


def decode_step_slots(params: dict, cache: dict, pos: jnp.ndarray,
                      token: jnp.ndarray, cfg: TransformerConfig,
                      active: jnp.ndarray | None = None):
    """One decode step for ALL slots: ``token (S,) int32`` at per-slot
    positions ``pos (S,) int32``, gated by ``active (S,) bool``.

    The slot-cache analogue of :func:`decode_step` (with
    ``pos = full(S, p)`` and all slots active it computes the same
    logits, to rounding, and writes the same rows). Retired slots cost
    nothing correctness-wise: their cache is not written, their logits
    are garbage the scheduler never samples from, and their (clipped)
    position only bounds the attention mask of a slot nobody reads.
    ``cache`` may hold more slots than ``token`` has entries: the step
    is over the first ``S``.

    Returns ``(logits (S, V), cache)``.
    """
    with jax.named_scope("params.cast"):
        params = cfg.cast_params(params)
    if active is None:
        active = jnp.ones(token.shape, bool)
    pos = jnp.asarray(pos, jnp.int32)
    # Clip so a retired slot's stale position can never over-index the
    # positional table (its logits are masked out by `active` anyway).
    safe = jnp.clip(pos, 0, params["pos_embed"].shape[0] - 1)
    with jax.named_scope("embed"):
        x = params["tok_embed"][token][:, None, :] \
            + params["pos_embed"][safe][:, None, :]
    x, cache = decode_blocks_slots(
        params["blocks"], cache, safe, x, cfg, active
    )
    with jax.named_scope("unembed"):
        logits = unembed(params, x)[:, 0]
    return logits, cache
