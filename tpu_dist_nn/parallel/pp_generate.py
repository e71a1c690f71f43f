"""Pipelined autoregressive decoding: generation with the blocks
sharded over the ``stage`` mesh axis.

The missing serving leg of the pipeline family: training shards blocks
over ``stage`` (transformer_pipeline), single-chip and tensor-parallel
decode existed (models/generate.py, parallel/tp_generate.py), but a
pipeline-trained model had to be gathered onto one device to sample.
This module decodes IN the training placement: each stage holds its
block group's KV cache, activations hop the stage ring, and the
sampled token rides a ``psum`` broadcast from the last stage back to
the embedding on stage 0.

TPU-first structure (no data-dependent control flow, no branches):

* **Prefill**: ``S`` uniform ticks. Every tick every stage runs its
  block group (:func:`~tpu_dist_nn.models.generate.prefill_blocks`)
  on whatever its wire holds and commits its cache only on its OWN
  tick (``jnp.where`` predication — the padded/masked SPMD trade the
  dense pipeline executor makes, one compiled program for all
  stages).
* **Decode**: one ``lax.scan`` over new tokens; each step is an inner
  ``lax.scan`` of ``S`` ticks through
  :func:`~tpu_dist_nn.models.generate.decode_blocks` with predicated
  cache commits, a greedy argmax on the last stage's tick, and the
  ``psum``-broadcast hand-back. Cost per token: every stage computes
  every tick (S× redundant FLOPs — masking instead of branching);
  the real win is MEMORY placement: the model and its caches never
  leave the training shards. Overlapping multiple sequences into the
  bubble (continuous batching) is the natural extension and would
  reuse these tables.

Parity vs the single-chip :func:`~tpu_dist_nn.models.generate.generate`
(both decoders, tested): greedy is token-for-token on any mesh; sampled
(``temperature > 0``) is token-for-token when the data axis is 1. With
data > 1 the key folds in the data-shard index (the tp_generate rule)
so shards draw independent noise — a documented stream divergence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_dist_nn.models.generate import (
    _truncate_logits,
    decode_blocks,
    prefill_blocks,
    validate_generate_args,
)
from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    layer_norm,
)
from tpu_dist_nn.parallel.mesh import AXIS_DATA, AXIS_STAGE


def _make_sampler(temperature: float, top_k, top_p):
    """The single-chip sampler (generate.py's), shared so the
    pipelined decoders are token-for-token comparable at ANY
    temperature: greedy argmax at 0, else truncated categorical."""
    if temperature == 0:
        return lambda logits, k: jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample(logits, k):
        t = _truncate_logits(logits, top_k, top_p)
        return jax.random.categorical(k, t / temperature, axis=-1).astype(
            jnp.int32
        )

    return sample


def _step_keys(key, n_steps: int):
    """The single-chip decode key schedule (generate.py:
    ``split(fold_in(key, 1), N-1)``) — reproduced exactly so sampled
    pipelined streams equal the single-chip ones key-for-key."""
    return jax.random.split(jax.random.fold_in(key, 1), n_steps)


def make_pipeline_generate(mesh, cfg: TransformerConfig, num_stages: int,
                           max_new_tokens: int, *, temperature: float = 0.0,
                           top_k=None, top_p=None):
    """-> ``fn(params_staged, prompt (B, T), key=None) -> (B, T + N)``.

    ``params_staged["blocks"]`` in
    :func:`~tpu_dist_nn.parallel.transformer_pipeline.shard_blocks`
    layout (the training layout); embedding/unembed params replicated.
    The batch shards over ``data`` if the mesh has that axis. Sampling
    follows the single-chip semantics and KEY SCHEDULE exactly
    (greedy at ``temperature == 0``, no key needed). Greedy streams
    match :func:`~tpu_dist_nn.models.generate.generate`
    token-for-token on any mesh; sampled streams match when the data
    axis is 1. With data > 1 each data shard folds its shard index
    into the key (tp_generate.py's rule — identical keys would draw
    identical noise on every shard, duplicating continuations), so
    sampled streams are a documented divergence from the single-chip
    order, not a silent one.
    """
    S = num_stages
    N = max_new_tokens
    sample = _make_sampler(float(temperature), top_k, top_p)

    def device_fn(embed_params, blocks_st, prompt, key):
        blocks = jax.tree.map(lambda a: a[0], blocks_st)  # (L/S, ...)
        s_idx = lax.axis_index(AXIS_STAGE)
        B, T = prompt.shape
        if fold_data:
            # Each data shard holds DIFFERENT batch rows: fold the
            # shard index into the key (the rule tp_generate shares) or
            # every shard would draw identical gumbel noise —
            # duplicated continuations at matching local indices.
            # Stage shards keep the same folded key: they must agree on
            # the token. Skipped at data == 1 so those streams stay
            # key-for-key equal to the single-chip schedule
            # (fold_in(key, 0) would still be a different key).
            key = jax.random.fold_in(key, lax.axis_index(AXIS_DATA))
        step_keys = _step_keys(key, max(N - 1, 1))
        D = cfg.d_model
        total = T + N
        max_len = total - 1  # last decode writes position total - 2
        vary = (AXIS_STAGE, *data_axes)

        def vcast(z):
            # Scan carries become (stage, data)-varying after the first
            # tick (ppermute + stage-predicated selects); mark the
            # initial values to match (idempotent — one_f_one_b.py).
            have = jax.typeof(z).vma
            need = tuple(a for a in vary if a not in have)
            return lax.pcast(z, need, to="varying") if need else z

        def unembed_local(x):
            h = layer_norm(x, embed_params["lnf_g"], embed_params["lnf_b"])
            return h @ embed_params["tok_embed"].T

        # ---- Prefill: S uniform ticks, cache committed on own tick.
        x0 = (
            embed_params["tok_embed"][prompt]
            + embed_params["pos_embed"][jnp.arange(T)]
        )
        dt = x0.dtype
        zeros_cache = {
            "k": vcast(jnp.zeros(
                (blocks["w_qkv"].shape[0], B, max_len, cfg.n_heads,
                 cfg.head_dim), dt,
            )),
        }
        zeros_cache["v"] = zeros_cache["k"]

        def prefill_tick(carry, t):
            wire, cache = carry
            x_in = jnp.where(s_idx == 0, x0, wire)
            y, new_cache = prefill_blocks(blocks, x_in, cfg, max_len)
            active = t == s_idx
            cache = jax.tree.map(
                lambda new, old: jnp.where(active, new, old), new_cache, cache
            )
            y = jnp.where(active, y, wire)
            wire = (
                lax.ppermute(y, AXIS_STAGE, [(i, i + 1) for i in range(S - 1)])
                if S > 1 else y
            )
            return (wire, cache), y

        (wire, cache), ys = lax.scan(
            prefill_tick, (vcast(x0 * 0.0), zeros_cache), jnp.arange(S)
        )
        # The last stage's own tick (t = S-1) produced the final
        # activation — it is ys[-1] on that device.
        y_last = ys[S - 1]
        logits = unembed_local(y_last[:, T - 1])
        first = sample(logits, key)
        # Broadcast the sampled token from the last stage to everyone.
        first = lax.psum(jnp.where(s_idx == S - 1, first, 0), AXIS_STAGE)

        # ---- Decode: N-1 steps x S ticks (the single-chip loop's
        # count: `first` came from the prefill logits).
        def decode_token(carry, n):
            cache, token = carry
            pos = T + n
            x_in0 = (
                embed_params["tok_embed"][token][:, None, :]
                + embed_params["pos_embed"][pos][None, None, :]
            )

            def tick(tc, t):
                wire, cache = tc
                x_in = jnp.where(s_idx == 0, x_in0, wire)
                y, new_cache = decode_blocks(blocks, cache, pos, x_in, cfg)
                active = t == s_idx
                cache = jax.tree.map(
                    lambda new, old: jnp.where(active, new, old),
                    new_cache, cache,
                )
                y = jnp.where(active, y, wire)
                wire = (
                    lax.ppermute(
                        y, AXIS_STAGE, [(i, i + 1) for i in range(S - 1)]
                    )
                    if S > 1 else y
                )
                return (wire, cache), y

            (_, cache), ys = lax.scan(
                tick, (vcast(x_in0 * 0.0), cache), jnp.arange(S)
            )
            logits = unembed_local(ys[S - 1][:, 0])
            nxt = sample(logits, step_keys[n])
            nxt = lax.psum(jnp.where(s_idx == S - 1, nxt, 0), AXIS_STAGE)
            return (cache, nxt), nxt

        if N == 1:
            new_tokens = first[:, None]
        else:
            (_, _), rest = lax.scan(
                decode_token, (cache, first), jnp.arange(N - 1)
            )
            new_tokens = jnp.concatenate(
                [first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1
            )
        return jnp.concatenate([prompt, new_tokens], axis=1)

    data_axes = (AXIS_DATA,) if AXIS_DATA in mesh.shape else ()
    fold_data = AXIS_DATA in mesh.shape and mesh.shape[AXIS_DATA] > 1
    # One compiled program for the whole prefill+decode loop (the
    # sibling single-chip/tp decoders enforce the same property).
    fn = jax.jit(jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(), P(AXIS_STAGE), P(*data_axes), P()),
        out_specs=P(*data_axes),
    ))

    def generate_fn(params, prompt, key=None):
        params = cfg.cast_params(params)
        # The shared argument contract (models/generate.py) — the same
        # validator the single-chip and tp paths call, so the three
        # decoders cannot drift (lengths, causality, sampling ranges,
        # greedy-vs-top_k conflicts). Returns a dummy key when greedy.
        key = validate_generate_args(
            cfg, prompt.shape[1], N, temperature, top_k, top_p, key
        )
        embed_params = {
            k: v for k, v in params.items() if k != "blocks"
        }
        return fn(embed_params, params["blocks"], prompt, key)

    return generate_fn


def make_pipeline_generate_overlapped(mesh, cfg: TransformerConfig,
                                      num_stages: int, max_new_tokens: int,
                                      num_groups: int, *,
                                      temperature: float = 0.0,
                                      top_k=None, top_p=None):
    """Continuous-batching-style pipelined decode: ``G`` request groups
    round-robin through the stage ring so that in steady state EVERY
    stage does useful work EVERY tick — one token leaves the pipe per
    tick — instead of :func:`make_pipeline_generate`'s one-group
    scheme, where each tick only one stage's compute is live (S×
    redundant FLOPs and ~S× the wall time for the same batch).

    Static round-robin tables, no branches: at tick ``t`` stage ``s``
    works on group ``g = (t - s) mod G`` decoding token ``n = (t - s)
    div G`` (valid while ``0 <= t - s`` and ``n`` in range). The
    sampled token for a group leaves the last stage and rides a
    dedicated ``(S-1 -> 0)`` ppermute hop back to the embedding
    stage's token buffer; ``G >= S`` guarantees it lands before the
    group's next decode tick (the fill/drain bubble is ``S - 1`` ticks
    total, amortized over ``(N-1) * G`` useful ticks). Per-stage KV
    caches gain a leading group axis — the continuous-batching memory
    trade.

    -> ``fn(params_staged, prompts (G, Bg, T)) -> (G, Bg, T + N)``;
    token-for-token equal to decoding each group alone (greedy on any
    mesh; sampled when data == 1 — data > 1 folds the shard index into
    the key, see :func:`make_pipeline_generate`). That parity contract
    means every group SHARES the one key schedule — identical prompts
    in different groups sample identical continuations, exactly as G
    separate single-chip ``generate`` calls with the same key would.
    Best-of-N over groups needs per-group keys; fold the group index
    yourself (``fold_in(key, g)``) and decode groups against their own
    keys, or accept the duplication.
    """
    S, N, G = num_stages, max_new_tokens, num_groups
    sample = _make_sampler(float(temperature), top_k, top_p)
    if G < S:
        raise ValueError(
            f"num_groups ({G}) must be >= num_stages ({S}): a group's "
            f"sampled token takes {S} ticks to cross the pipe and ride "
            f"the feedback hop, and the round-robin grants it G ticks "
            "before that group decodes again"
        )

    def device_fn(embed_params, blocks_st, prompts, key):
        blocks = jax.tree.map(lambda a: a[0], blocks_st)  # (L/S, ...)
        s_idx = lax.axis_index(AXIS_STAGE)
        _, Bg, T = prompts.shape  # group count == G (validated outside)
        if fold_data:
            # Same rule as make_pipeline_generate: distinct noise per
            # data shard, shared across the stage ring; skipped at
            # data == 1 to preserve the single-chip key schedule.
            key = jax.random.fold_in(key, lax.axis_index(AXIS_DATA))
        step_keys = _step_keys(key, max(N - 1, 1))
        total = T + N
        max_len = total - 1
        vary = (AXIS_STAGE, *data_axes)

        def vcast(z):
            have = jax.typeof(z).vma
            need = tuple(a for a in vary if a not in have)
            return lax.pcast(z, need, to="varying") if need else z

        def unembed_local(x):
            h = layer_norm(x, embed_params["lnf_g"], embed_params["lnf_b"])
            return h @ embed_params["tok_embed"].T

        x0 = (
            embed_params["tok_embed"][prompts]
            + embed_params["pos_embed"][jnp.arange(T)]
        )  # (G, Bg, T, D)
        dt = x0.dtype
        Lc = blocks["w_qkv"].shape[0]
        cache0 = {
            "k": vcast(jnp.zeros(
                (G, Lc, Bg, max_len, cfg.n_heads, cfg.head_dim), dt
            )),
        }
        cache0["v"] = cache0["k"]

        # ---- Prefill: G + S - 1 round-robin ticks; firsts collected
        # on the last stage and psum-shared afterwards.
        def prefill_tick(carry, t):
            wire, cache, firsts = carry
            g = jnp.clip(t - s_idx, 0, G - 1)
            valid = (t - s_idx >= 0) & (t - s_idx < G)
            x_in = jnp.where(
                s_idx == 0,
                lax.dynamic_index_in_dim(x0, g, 0, keepdims=False),
                wire,
            )
            y, new_cache_g = prefill_blocks(blocks, x_in, cfg, max_len)
            # Predicate the SLICE, then write unconditionally: the
            # select touches one group's cache, not all G (and the
            # scan carry stays aliasable for XLA).
            cache = jax.tree.map(
                lambda c, newg: lax.dynamic_update_index_in_dim(
                    c,
                    jnp.where(
                        valid, newg,
                        lax.dynamic_index_in_dim(c, g, 0, keepdims=False),
                    ),
                    g, 0,
                ),
                cache, new_cache_g,
            )
            emit = valid & (s_idx == S - 1)
            tok = sample(unembed_local(y[:, T - 1]), key)
            firsts = jnp.where(
                emit,
                lax.dynamic_update_index_in_dim(firsts, tok, g, 0),
                firsts,
            )
            wire = (
                lax.ppermute(y, AXIS_STAGE, [(i, i + 1) for i in range(S - 1)])
                if S > 1 else y
            )
            return (wire, cache, firsts), None

        firsts0 = vcast(jnp.zeros((G, Bg), jnp.int32))
        (_w, cache, firsts), _ = lax.scan(
            prefill_tick,
            (vcast(jnp.zeros((Bg, T, cfg.d_model), dt)), cache0, firsts0),
            jnp.arange(G + S - 1),
        )
        firsts = lax.psum(
            jnp.where(s_idx == S - 1, firsts, 0), AXIS_STAGE
        )  # (G, Bg) on every stage

        if N == 1:
            return jnp.concatenate([prompts, firsts[:, :, None]], axis=2)

        # ---- Overlapped decode: (N-1)*G + S - 1 ticks.
        TK = (N - 1) * G + S - 1

        def tick(carry, t):
            wire, fb_wire, cache, tokbuf, outbuf = carry
            # Receive: last tick's feedback token belongs to group
            # (t - S) mod G (emitted by the last stage at t-1 for its
            # group (t-1) - (S-1)).
            g_fb = (t - S) % G
            fb_valid = (t - S >= 0) & ((t - S) // G < N - 1) & (s_idx == 0)
            tokbuf = jnp.where(
                fb_valid,
                lax.dynamic_update_index_in_dim(tokbuf, fb_wire, g_fb, 0),
                tokbuf,
            )
            d = t - s_idx
            g = jnp.clip(d, 0, 10 ** 9) % G
            n = jnp.clip(d, 0, 10 ** 9) // G
            valid = (d >= 0) & (n < N - 1)
            pos = T + n
            tok_g = lax.dynamic_index_in_dim(tokbuf, g, 0, keepdims=False)
            x_emb = (
                embed_params["tok_embed"][tok_g][:, None, :]
                + embed_params["pos_embed"][pos][None, None, :]
            )
            x_in = jnp.where(s_idx == 0, x_emb, wire)
            cache_g = jax.tree.map(
                lambda c: lax.dynamic_index_in_dim(c, g, 0, keepdims=False),
                cache,
            )
            y, new_cache_g = decode_blocks(blocks, cache_g, pos, x_in, cfg)
            # Slice-predicated write (prefill_tick's note): one group's
            # select, unconditional group write.
            cache = jax.tree.map(
                lambda c, newg, oldg: lax.dynamic_update_index_in_dim(
                    c, jnp.where(valid, newg, oldg), g, 0
                ),
                cache, new_cache_g, cache_g,
            )
            emit = valid & (s_idx == S - 1)
            tok = sample(unembed_local(y[:, 0]), step_keys[n])
            outbuf = lax.dynamic_update_slice(
                outbuf,
                jnp.where(
                    emit, tok,
                    lax.dynamic_slice(outbuf, (g, n, 0), (1, 1, Bg))[0, 0],
                )[None, None, :],
                (g, n, 0),
            )
            wire = (
                lax.ppermute(y, AXIS_STAGE, [(i, i + 1) for i in range(S - 1)])
                if S > 1 else y
            )
            fb_wire = (
                lax.ppermute(tok, AXIS_STAGE, [(S - 1, 0)])
                if S > 1 else tok
            )
            return (wire, fb_wire, cache, tokbuf, outbuf), None

        outbuf0 = vcast(jnp.zeros((G, N - 1, Bg), jnp.int32))
        (_w, _f, _c, _tb, outbuf), _ = lax.scan(
            tick,
            (
                vcast(jnp.zeros((Bg, 1, cfg.d_model), dt)),
                vcast(jnp.zeros((Bg,), jnp.int32)),
                cache, vcast(firsts), outbuf0,
            ),
            jnp.arange(TK),
        )
        rest = lax.psum(
            jnp.where(s_idx == S - 1, outbuf, 0), AXIS_STAGE
        )  # (G, N-1, Bg)
        new_tokens = jnp.concatenate(
            [firsts[:, :, None], jnp.transpose(rest, (0, 2, 1))], axis=2
        )
        return jnp.concatenate([prompts, new_tokens], axis=2)

    data_axes = (AXIS_DATA,) if AXIS_DATA in mesh.shape else ()
    fold_data = AXIS_DATA in mesh.shape and mesh.shape[AXIS_DATA] > 1
    fn = jax.jit(jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(), P(AXIS_STAGE), P(None, *data_axes), P()),
        out_specs=P(None, *data_axes),
    ))

    def generate_fn(params, prompts, key=None):
        params = cfg.cast_params(params)
        if prompts.ndim != 3 or prompts.shape[0] != G:
            raise ValueError(
                f"prompts must be (num_groups={G}, Bg, T), got "
                f"{prompts.shape}"
            )
        # Shared contract (models/generate.py) — see make_pipeline_
        # generate's wrapper.
        key = validate_generate_args(
            cfg, prompts.shape[2], N, temperature, top_k, top_p, key
        )
        embed_params = {k: v for k, v in params.items() if k != "blocks"}
        return fn(embed_params, params["blocks"], prompts, key)

    return generate_fn
