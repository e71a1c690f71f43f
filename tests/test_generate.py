"""KV-cached autoregressive decoding: greedy parity vs the
teacher-forced full forward, sampling reproducibility, and bounds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.models.generate import decode_step, generate, prefill
from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    forward,
    init_transformer,
)

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=3, d_ff=64, max_seq_len=48
)


def _prompt(batch, t, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (batch, t)), jnp.int32)


def test_prefill_logits_match_forward():
    params = init_transformer(jax.random.key(0), CFG)
    tokens = _prompt(2, 12)
    logits, cache = prefill(params, tokens, CFG, max_len=20)
    ref = forward(params, tokens, CFG)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert cache["k"].shape == (3, 2, 20, 4, 8)


def test_greedy_generation_matches_teacher_forced_oracle():
    params = init_transformer(jax.random.key(1), CFG)
    prompt = _prompt(2, 8, seed=2)
    n_new = 10
    got = generate(params, CFG, prompt, n_new)

    # Oracle: grow the sequence one token at a time through the full
    # batched forward (no cache) and take argmax each step. Jitted per
    # length: the growing-shape eager loop re-executes op-by-op every
    # run, while the 10 small compiles land in the persistent cache.
    jfwd = jax.jit(forward, static_argnums=2)
    seq = prompt
    want = []
    for _ in range(n_new):
        logits = jfwd(params, seq, CFG)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    want = jnp.stack(want, axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generation_is_jittable():
    params = init_transformer(jax.random.key(1), CFG)
    prompt = _prompt(2, 8, seed=2)
    eager = generate(params, CFG, prompt, 6)
    jitted = jax.jit(
        lambda p, t: generate(p, CFG, t, 6)
    )(params, prompt)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


def test_sampling_reproducible_and_varies_with_key():
    params = init_transformer(jax.random.key(3), CFG)
    prompt = _prompt(2, 6, seed=4)
    a = generate(params, CFG, prompt, 8, temperature=1.0, key=jax.random.key(7))
    b = generate(params, CFG, prompt, 8, temperature=1.0, key=jax.random.key(7))
    c = generate(params, CFG, prompt, 8, temperature=1.0, key=jax.random.key(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert int(a.min()) >= 0 and int(a.max()) < CFG.vocab_size


def test_generate_boundary_total_fits_positional_table():
    # T + N == max_seq_len + 1 is VALID: the decode loop embeds
    # positions 0..T+N-2 only (the last sampled token is returned, not
    # fed back), so the positional table is never over-indexed. The
    # shared validator must accept what the decoders accept (ADVICE r5).
    params = init_transformer(jax.random.key(0), CFG)
    out = generate(params, CFG, _prompt(1, 40), CFG.max_seq_len + 1 - 40)
    assert out.shape == (1, CFG.max_seq_len + 1 - 40)
    assert int(out.min()) >= 0 and int(out.max()) < CFG.vocab_size


def test_generate_bounds_and_key_requirements():
    params = init_transformer(jax.random.key(0), CFG)
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(params, CFG, _prompt(1, 40), 20)
    with pytest.raises(ValueError, match="PRNG key"):
        generate(params, CFG, _prompt(1, 4), 4, temperature=0.5)
    with pytest.raises(ValueError, match="temperature"):
        generate(params, CFG, _prompt(1, 4), 4, temperature=-0.5)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(params, CFG, _prompt(1, 4), 0)
    import dataclasses

    noncausal = dataclasses.replace(CFG, causal=False)
    with pytest.raises(ValueError, match="causal"):
        generate(params, noncausal, _prompt(1, 4), 4)


def test_generate_single_token():
    params = init_transformer(jax.random.key(1), CFG)
    prompt = _prompt(2, 8, seed=2)
    got = generate(params, CFG, prompt, 1)
    want = jnp.argmax(forward(params, prompt, CFG)[:, -1], -1)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(want))


def test_decode_step_updates_cache_in_place_positions():
    params = init_transformer(jax.random.key(0), CFG)
    tokens = _prompt(1, 4)
    _, cache = prefill(params, tokens, CFG, max_len=10)
    before = np.asarray(cache["k"][:, :, 4])
    assert np.all(before == 0)  # position 4 still empty
    _, cache = decode_step(
        params, cache, jnp.int32(4), tokens[:, 0], CFG
    )
    after = np.asarray(cache["k"][:, :, 4])
    assert np.any(after != 0)  # now written
    # Earlier positions untouched.
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, :, :4]),
        np.asarray(prefill(params, tokens, CFG, max_len=10)[1]["k"][:, :, :4]),
    )


def test_top_k_restricts_candidates():
    from tpu_dist_nn.models.generate import _truncate_logits

    logits = jnp.asarray([[1.0, 5.0, 3.0, 4.0, 2.0]])
    out = np.asarray(_truncate_logits(logits, top_k=2, top_p=None))
    neg = np.finfo(np.float32).min
    np.testing.assert_array_equal(out[0] > neg, [False, True, False, True, False])


def test_top_p_keeps_minimal_nucleus():
    from tpu_dist_nn.models.generate import _truncate_logits

    # softmax of [0, ln4, ln5, ln1e-3-ish]: probs ~ [.1, .4, .5, ~0]
    logits = jnp.log(jnp.asarray([[1.0, 4.0, 5.0, 1e-3]]))
    out = np.asarray(_truncate_logits(logits, top_k=None, top_p=0.85))
    neg = np.finfo(np.float32).min
    # Nucleus at p=0.85: {5.0 (.5), 4.0 (.4)} reaches 0.9 >= 0.85 with
    # the previous mass 0.5 < 0.85; the 0.1 and ~0 tokens are cut.
    np.testing.assert_array_equal(out[0] > neg, [False, True, True, False])
    # p=1.0 keeps everything.
    full = np.asarray(_truncate_logits(logits, top_k=None, top_p=1.0))
    assert (full[0] > neg).all()


def test_generate_top_k_one_is_greedy():
    cfg = CFG
    params = init_transformer(jax.random.key(0), cfg)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    greedy = np.asarray(generate(params, cfg, prompt, 8))
    topk1 = np.asarray(
        generate(params, cfg, prompt, 8, temperature=1.0, top_k=1,
                 key=jax.random.key(7))
    )
    np.testing.assert_array_equal(greedy, topk1)


def test_generate_top_k_samples_within_set():
    cfg = CFG
    params = init_transformer(jax.random.key(0), cfg)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    # Every emitted token must be among the 2 highest-logit tokens for
    # its position, verified by teacher-forcing the full sequence
    # through the batched forward (high temperature would escape the
    # set immediately if the mask were broken).
    out = np.asarray(
        generate(params, cfg, prompt, 8, temperature=4.0, top_k=2,
                 key=jax.random.key(3))
    )
    seq = np.concatenate([np.asarray(prompt), out], axis=1)
    logits = np.asarray(forward(params, jnp.asarray(seq), cfg))
    T = prompt.shape[1]
    for i in range(out.shape[1]):
        step_logits = logits[0, T - 1 + i]
        top2 = np.argsort(step_logits)[-2:]
        assert out[0, i] in top2, (i, out[0, i], top2)


def test_greedy_rejects_truncation_flags():
    params = init_transformer(jax.random.key(0), CFG)
    prompt = jnp.asarray([[1]], jnp.int32)
    with pytest.raises(ValueError, match="greedy"):
        generate(params, CFG, prompt, 2, temperature=0.0, top_k=5)


def test_generate_validates_top_k_top_p():
    cfg = CFG
    params = init_transformer(jax.random.key(0), cfg)
    prompt = jnp.asarray([[1]], jnp.int32)
    with pytest.raises(ValueError, match="top_k"):
        generate(params, cfg, prompt, 2, temperature=1.0, top_k=0,
                 key=jax.random.key(0))
    with pytest.raises(ValueError, match="top_p"):
        generate(params, cfg, prompt, 2, temperature=1.0, top_p=1.5,
                 key=jax.random.key(0))


def test_generate_eos_freezes_rows_and_pads():
    # Stop-token semantics under the static shape: pick a token the
    # greedy decode ACTUALLY emits mid-stream for row 0, rerun with it
    # as eos_id — the prefix through the stop token is unchanged, the
    # tail is all pad (eos_id), and rows that never emit it are
    # untouched (per-row done-mask, not a batch-wide abort).
    params = init_transformer(jax.random.key(1), CFG)
    prompt = _prompt(2, 8, seed=2)
    base = np.asarray(generate(params, CFG, prompt, 10))
    eos = int(base[0, 3])
    out = np.asarray(generate(params, CFG, prompt, 10, eos_id=eos))
    np.testing.assert_array_equal(out[0, :4], base[0, :4])
    assert (out[0, 4:] == eos).all()
    for r in range(1, 2):
        first = np.flatnonzero(base[r] == eos)
        if first.size == 0:
            np.testing.assert_array_equal(out[r], base[r])


def test_generate_eos_validated():
    params = init_transformer(jax.random.key(1), CFG)
    with pytest.raises(ValueError, match="eos_id"):
        generate(params, CFG, _prompt(1, 4), 4, eos_id=CFG.vocab_size)
    with pytest.raises(ValueError, match="eos_id"):
        generate(params, CFG, _prompt(1, 4), 4, eos_id=-1)


# ---------------------------------------------------------------------------
# Slot-wise decoding (the continuous-batching kernels)
# ---------------------------------------------------------------------------


def test_decode_step_slots_matches_scalar_decode_step():
    # With a uniform position vector and every slot active, the
    # slot-wise step IS the batched scalar step: the rows it writes are
    # bit-identical to dynamic_update_slice's and every other byte of
    # the cache is untouched. The logits agree to rounding, not to the
    # bit: the slot step's softmax takes the token's own key as one
    # more column after the stored ones instead of at index `pos`, so
    # the same pos + 1 terms are summed in another order.
    from tpu_dist_nn.models.generate import (
        decode_step_slots,
        rows_to_slots,
        slots_to_rows,
    )

    params = init_transformer(jax.random.key(0), CFG)
    prompts = _prompt(4, 8, seed=3)
    _, cache = prefill(params, prompts, CFG, max_len=13)
    tok = prompts[:, 0]
    ref_logits, ref_cache = decode_step(
        params, cache, jnp.int32(8), tok, CFG
    )
    got_logits, got_cache = decode_step_slots(
        params, {p: rows_to_slots(a) for p, a in cache.items()},
        jnp.full((4,), 8, jnp.int32), tok, CFG,
    )
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(got_logits), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(ref_logits).argmax(-1), np.asarray(got_logits).argmax(-1)
    )
    np.testing.assert_allclose(  # layer 0's row is exact, deeper to rounding
        np.asarray(ref_cache["k"]), np.asarray(slots_to_rows(got_cache["k"])),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(ref_cache["v"]), np.asarray(slots_to_rows(got_cache["v"])),
        rtol=1e-5, atol=1e-5,
    )
    for part in ("k", "v"):
        got = np.asarray(slots_to_rows(got_cache[part]))
        np.testing.assert_array_equal(  # everything but row 8: untouched
            np.delete(got, 8, axis=2),
            np.delete(np.asarray(cache[part]), 8, axis=2),
        )
        np.testing.assert_array_equal(
            got[0, :, 8], np.asarray(ref_cache[part])[0, :, 8]
        )


def test_decode_step_slots_staggered_positions_match_oracle():
    # The point of the per-slot pos vector: slots at DIFFERENT depths
    # advance in one launch. Slot 0 is 3 tokens ahead of slot 1 (walked
    # there with slot 1 masked inactive); a joint step must match the
    # teacher-forced full forward of each slot's own sequence.
    from tpu_dist_nn.models.generate import (
        decode_step_slots,
        init_slot_cache,
        prefill_into_cache,
    )
    from tpu_dist_nn.models.transformer import forward

    params = init_transformer(jax.random.key(5), CFG)
    T, S = 6, 2
    prompts = _prompt(S, T, seed=6)
    cache = init_slot_cache(CFG, S, 16)

    # Admit slot 0 and walk it 3 greedy steps alone (slot 1 inactive).
    logits0, cache = prefill_into_cache(params, CFG, cache, 0, prompts[:1])
    seq0 = list(np.asarray(prompts[0]))
    tok = jnp.array([int(jnp.argmax(logits0[0])), 0], jnp.int32)
    seq0.append(int(tok[0]))
    pos = jnp.array([T, 0], jnp.int32)
    active = jnp.array([True, False])
    for _ in range(3):
        logits, cache = decode_step_slots(params, cache, pos, tok, CFG,
                                          active=active)
        nxt = int(jnp.argmax(logits[0]))
        seq0.append(nxt)
        tok = jnp.array([nxt, 0], jnp.int32)
        pos = pos + jnp.array([1, 0], jnp.int32)

    # Admit slot 1 mid-flight, then step BOTH in one launch.
    logits1, cache = prefill_into_cache(params, CFG, cache, 1, prompts[1:])
    seq1 = list(np.asarray(prompts[1])) + [int(jnp.argmax(logits1[0]))]
    tok = jnp.array([seq0[-1], seq1[-1]], jnp.int32)
    pos = jnp.array([T + 3, T], jnp.int32)
    logits, cache = decode_step_slots(
        params, cache, pos, tok, CFG, active=jnp.array([True, True])
    )
    for s, seq in ((0, seq0), (1, seq1)):
        ref = forward(params, jnp.asarray([seq], jnp.int32), CFG)[0, -1]
        np.testing.assert_allclose(
            np.asarray(logits[s]), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


# A head count that is no power of two (gpt2-large has 20), and a
# position axis that is no multiple of anything.
SLOT_CFG = TransformerConfig(
    vocab_size=64, d_model=24, n_heads=3, n_layers=2, d_ff=48, max_seq_len=32
)
SLOT_M = 13


@pytest.mark.parametrize(
    "pos, active, pool",
    [
        pytest.param([3, 5, 2, 7], [1, 1, 1, 1], 0, id="all_active"),
        pytest.param([3, 5, 2, 7], [1, 0, 1, 0], 0, id="some_inactive"),
        pytest.param([0, 4, 0, 6], [1, 1, 0, 1], 0, id="pos_zero"),
        pytest.param([SLOT_M - 1, 4, SLOT_M - 1, 6], [1, 1, 0, 1], 0,
                     id="pos_last"),
        pytest.param([0, SLOT_M - 1, 1, SLOT_M // 2], [1, 1, 1, 1], 0,
                     id="positions_far_apart"),
        pytest.param([3, 0, SLOT_M - 1, 7], [1, 1, 1, 0], 2,
                     id="prefix_pool_through_scheduler_step"),
    ],
)
def test_slot_step_against_plain_forward(pos, active, pool):
    # The slot step against a plain no-cache f32 forward of each slot's
    # own tokens: the logits of every active slot, the one row it
    # writes, and every byte it must leave alone (other positions, an
    # inactive slot — mid-prefill, say — and the prefix pool's slots
    # behind the request region).
    from tpu_dist_nn.models.generate import (
        decode_step_slots,
        init_slot_cache,
        prefill_into_cache,
        slots_to_rows,
    )
    from tpu_dist_nn.serving.continuous import slot_kernels

    cfg, M, S = SLOT_CFG, SLOT_M, len(pos)
    params = init_transformer(jax.random.key(3), cfg)
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, cfg.vocab_size, p + 1) for p in pos]
    cache = init_slot_cache(cfg, S + pool, M)
    for s, seq in enumerate(seqs):
        if pos[s]:
            _, cache = prefill_into_cache(
                params, cfg, cache, s, jnp.asarray(seq[None, :-1], jnp.int32)
            )
    # Garbage wherever no key is stored yet (and all over the pool): the
    # mask, not a zero, must keep it out.
    stored = np.arange(M)[None, :] < np.asarray(pos + [0] * pool)[:, None]
    junk = jnp.asarray(rng.normal(size=cache["k"].shape), jnp.float32)
    cache = {
        part: jnp.where(stored[None, :, None, None, :], a, junk + 7.5)
        for part, a in cache.items()
    }
    before = {part: np.asarray(a) for part, a in cache.items()}
    tok = jnp.asarray([seq[-1] for seq in seqs], jnp.int32)
    pos_v = jnp.asarray(pos, jnp.int32)
    active_v = jnp.asarray(active, bool)
    if pool:
        _, _, step = slot_kernels(cfg, 0.0, None, None)
        toks, ok, cache = step(params, cache, pos_v, active_v, tok, None)
        assert np.asarray(ok).all()
    else:
        logits, cache = decode_step_slots(
            params, cache, pos_v, tok, cfg, active=active_v
        )
    for s, seq in enumerate(seqs):
        if not active[s]:
            continue
        ref = forward(params, jnp.asarray(seq[None], jnp.int32), cfg)[0, -1]
        if pool:
            assert int(toks[s]) == int(jnp.argmax(ref))
        else:
            np.testing.assert_allclose(
                np.asarray(logits[s]), np.asarray(ref), rtol=2e-5, atol=2e-5
            )
        _, want = prefill(params, jnp.asarray(seq[None], jnp.int32), cfg, M)
        for part in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(slots_to_rows(cache[part]))[:, s, pos[s]],
                np.asarray(want[part])[:, 0, pos[s]], rtol=2e-5, atol=2e-5,
            )
    written = np.zeros((S + pool, M), bool)
    for s in range(S):
        written[s, pos[s]] = bool(active[s])
    for part in ("k", "v"):
        keep = np.broadcast_to(
            ~written[None, :, None, None, :], before[part].shape
        )
        np.testing.assert_array_equal(
            np.asarray(cache[part])[keep], before[part][keep]
        )


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (scan and while bodies, jitted calls), a Pallas kernel's own body
    left out: what is in there works on one block in VMEM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)


def test_slot_step_never_carries_or_selects_the_cache():
    # The structural guard against the two things that made the step
    # move the whole cache every layer: the cache as the layer scan's
    # xs -> ys (every layer sliced out, changed, written into a second
    # cache) and a select over a layer's whole extent to change S rows.
    # Nothing but the in-place write may produce a layer of the cache.
    from tpu_dist_nn.models.generate import decode_step_slots, init_slot_cache

    cfg, M, S = SLOT_CFG, SLOT_M, 4
    params = init_transformer(jax.random.key(3), cfg)
    cache = init_slot_cache(cfg, S, M)
    layer = S * M * cfg.n_heads * cfg.head_dim
    jaxpr = jax.make_jaxpr(
        lambda params, cache, pos, tok, active: decode_step_slots(
            params, cache, pos, tok, cfg, active=active
        )
    )(params, cache, jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.int32),
      jnp.ones(S, bool)).jaxpr
    big = {}
    for eqn in _walk_eqns(jaxpr):
        sizes = [int(np.prod(v.aval.shape)) for v in eqn.outvars
                 if hasattr(v.aval, "shape")]
        if any(n >= layer for n in sizes):
            big.setdefault(eqn.primitive.name, []).append(sizes)
    assert "scan" in {e.primitive.name for e in _walk_eqns(jaxpr)}
    for name in ("scan", "while", "select_n"):
        assert name not in big, (name, big[name])
    # The one thing that yields the cache: the write of K and V, each
    # aliased to its input (the layer reads are slices a layer large,
    # which the compiler fuses into the scores and the values). It is
    # there once for each platform it can be lowered for, under the
    # `cond` that lax.platform_dependent leaves for lowering to resolve.
    whole = cfg.n_layers * layer
    yields_cache = {name: sizes for name, sizes in big.items()
                    if any(n >= whole for ns in sizes for n in ns)}
    assert yields_cache == {"pallas_call": [[whole, whole]] * 2,
                            "cond": [[whole, whole]]}
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            assert dict(eqn.params["input_output_aliases"]) == {4: 0, 5: 1}


def test_prefill_into_cache_lands_slot_and_clears_stale():
    # Admission into an arbitrary slot index: the chosen slot's FULL
    # extent is overwritten (a reused slot cannot leak its previous
    # occupant's K/V — the stale tail is zeroed by the prefill pad) and
    # every other slot's contents are untouched.
    from tpu_dist_nn.models.generate import (
        init_slot_cache,
        prefill_into_cache,
        rows_to_slots,
    )

    params = init_transformer(jax.random.key(0), CFG)
    prompts = _prompt(3, 8, seed=7)
    cache = init_slot_cache(CFG, 3, 12)
    cache = {k: v + 7.5 for k, v in cache.items()}  # stale garbage
    before_k = np.asarray(cache["k"])
    logits, cache = prefill_into_cache(params, CFG, cache, 1, prompts[1:2])
    # Parity with the batch prefill's row 1 — including the zero pad.
    _, ref = prefill(params, prompts, CFG, max_len=12)
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, 1]),
        np.asarray(rows_to_slots(ref["k"])[:, 1]),
    )
    assert np.all(np.asarray(cache["k"][:, 1, :, :, 8:]) == 0)
    # Slots 0 and 2 keep their garbage (untouched by the slot write).
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 0]), before_k[:, 0])
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 2]), before_k[:, 2])
    # And the returned logits sample the same first token the full
    # generate() would.
    want = np.asarray(generate(params, CFG, prompts[1:2], 1))[0, 0]
    assert int(jnp.argmax(logits[0])) == want


def test_slot_cache_bounds_validated():
    from tpu_dist_nn.models.generate import init_slot_cache

    with pytest.raises(ValueError, match="slots"):
        init_slot_cache(CFG, 0, 8)
    with pytest.raises(ValueError, match="max_len"):
        init_slot_cache(CFG, 2, CFG.max_seq_len + 1)


def test_prefill_chunk_into_cache_bitwise_matches_monolithic():
    # The chunk kernel IS the monolithic prefill when the chunk covers
    # the whole prompt — and splitting the prompt across chunk calls
    # must land the exact same logits and cache bytes (the continuous
    # scheduler's cache-on/cache-off bit-parity anchor rides on this).
    from tpu_dist_nn.models.generate import (
        init_slot_cache,
        prefill_chunk_into_cache,
        prefill_into_cache,
    )

    params = init_transformer(jax.random.key(0), CFG)
    T = 8
    prompts = _prompt(1, T, seed=8)
    cache0 = init_slot_cache(CFG, 3, 12)
    ref_logits, ref_cache = prefill_into_cache(params, CFG, cache0, 1, prompts)
    # One whole-prompt chunk.
    lg, c = prefill_chunk_into_cache(params, CFG, cache0, 1, prompts, 0)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref_logits))
    np.testing.assert_array_equal(
        np.asarray(c["k"][:, 1, :, :, :T]),
        np.asarray(ref_cache["k"][:, 1, :, :, :T]),
    )
    # Split 3 + 5: the second chunk attends to the first's K/V.
    lg2, c2 = prefill_chunk_into_cache(
        params, CFG, cache0, 1, prompts[:, :3], 0
    )
    lg2, c2 = prefill_chunk_into_cache(params, CFG, c2, 1, prompts[:, 3:], 3)
    np.testing.assert_array_equal(np.asarray(lg2), np.asarray(ref_logits))
    np.testing.assert_array_equal(
        np.asarray(c2["k"][:, 1, :, :, :T]),
        np.asarray(ref_cache["k"][:, 1, :, :, :T]),
    )
    np.testing.assert_array_equal(
        np.asarray(c2["v"][:, 1, :, :, :T]),
        np.asarray(ref_cache["v"][:, 1, :, :, :T]),
    )


def test_copy_cache_slot_full_extent_and_isolation():
    # The prefix-cache transfer primitive: dst becomes a bit-exact copy
    # of src's whole extent; every other slot is untouched; and both
    # indices are traced (one compile serves any src/dst pair).
    from tpu_dist_nn.models.generate import (
        copy_cache_slot,
        init_slot_cache,
        prefill_chunk_into_cache,
    )

    params = init_transformer(jax.random.key(1), CFG)
    prompts = _prompt(1, 8, seed=9)
    cache = init_slot_cache(CFG, 3, 12)
    cache = {k: v + 2.5 for k, v in cache.items()}  # distinguishable
    _, cache = prefill_chunk_into_cache(params, CFG, cache, 2, prompts, 0)
    before = {k: np.asarray(v).copy() for k, v in cache.items()}
    out = copy_cache_slot(cache, 2, 0)
    for part in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(out[part][:, 0]), before[part][:, 2]
        )
        np.testing.assert_array_equal(  # src and bystander untouched
            np.asarray(out[part][:, 1]), before[part][:, 1]
        )
        np.testing.assert_array_equal(
            np.asarray(out[part][:, 2]), before[part][:, 2]
        )


def test_prefill_chunk_after_copied_prefix_matches_monolithic():
    # The COW admission path end-to-end at the kernel level: prefix
    # prefilled into a POOL slot, copied into a request slot, suffix
    # chunked on top — last-position logits and the request slot's
    # prompt extent must be bit-identical to a monolithic prefill.
    from tpu_dist_nn.models.generate import (
        copy_cache_slot,
        init_slot_cache,
        prefill_chunk_into_cache,
        prefill_into_cache,
    )

    params = init_transformer(jax.random.key(2), CFG)
    T, pool_slot, req_slot = 8, 2, 0
    prompts = _prompt(1, T, seed=10)
    cache0 = init_slot_cache(CFG, 3, 12)
    ref_logits, ref_cache = prefill_into_cache(
        params, CFG, cache0, req_slot, prompts
    )
    _, c = prefill_chunk_into_cache(
        params, CFG, cache0, pool_slot, prompts[:, :4], 0
    )
    c = copy_cache_slot(c, pool_slot, req_slot)
    lg, c = prefill_chunk_into_cache(
        params, CFG, c, req_slot, prompts[:, 4:], 4
    )
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref_logits))
    np.testing.assert_array_equal(
        np.asarray(c["k"][:, req_slot, :, :, :T]),
        np.asarray(ref_cache["k"][:, req_slot, :, :, :T]),
    )


# ---------------------------------------------------------------------------
# Tensor-parallel decode
# ---------------------------------------------------------------------------


def _tp_setup(n_heads=4, n_layers=2):
    from tpu_dist_nn.models.transformer import TransformerConfig, init_transformer
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.tensor_parallel import tp_shard_blocks

    cfg = TransformerConfig(
        vocab_size=31, d_model=16, n_heads=n_heads, n_layers=n_layers,
        d_ff=32, max_seq_len=24,
    )
    mesh = build_mesh(MeshSpec(model=2, data=2))
    params = init_transformer(jax.random.key(7), cfg)
    params_tp = dict(params, blocks=tp_shard_blocks(params["blocks"], cfg, 2))
    return cfg, mesh, params, params_tp


def test_tp_generate_greedy_matches_single_chip():
    from tpu_dist_nn.models.generate import generate
    from tpu_dist_nn.parallel.tp_generate import tp_generate

    cfg, mesh, params, params_tp = _tp_setup()
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 6)), jnp.int32)
    ref = generate(params, cfg, prompt, 8)
    out = tp_generate(mesh, params_tp, cfg, prompt, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # Single-token edge case.
    np.testing.assert_array_equal(
        np.asarray(tp_generate(mesh, params_tp, cfg, prompt, 1)),
        np.asarray(generate(params, cfg, prompt, 1)),
    )


def test_tp_generate_sampled_is_valid_and_deterministic():
    from tpu_dist_nn.parallel.tp_generate import tp_generate

    cfg, mesh, _, params_tp = _tp_setup()
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)), jnp.int32)
    key = jax.random.key(3)
    a = tp_generate(mesh, params_tp, cfg, prompt, 6,
                    temperature=0.8, top_k=10, key=key)
    b = tp_generate(mesh, params_tp, cfg, prompt, 6,
                    temperature=0.8, top_k=10, key=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(a).shape == (2, 6)
    assert (np.asarray(a) >= 0).all() and (np.asarray(a) < cfg.vocab_size).all()


def test_tp_generate_rejects_indivisible_heads():
    from tpu_dist_nn.parallel.tp_generate import tp_generate

    cfg, mesh, _, params_tp = _tp_setup()
    import dataclasses

    bad = dataclasses.replace(cfg, n_heads=3, d_model=18, d_ff=36)
    with pytest.raises(ValueError, match="divisible"):
        tp_generate(mesh, params_tp, bad, jnp.zeros((2, 3), jnp.int32), 2)


def test_tp_generate_data_shards_sample_independently():
    """Same prompt in every row, data axis 2: rows in different shards
    must NOT draw identical noise (the key folds in the shard index)."""
    from tpu_dist_nn.parallel.tp_generate import tp_generate

    cfg, mesh, _, params_tp = _tp_setup()
    prompt = jnp.tile(jnp.asarray([[1, 2, 3, 4]], jnp.int32), (4, 1))
    out = np.asarray(
        tp_generate(mesh, params_tp, cfg, prompt, 8,
                    temperature=1.0, key=jax.random.key(5))
    )
    # Rows 0/1 live on shard 0, rows 2/3 on shard 1. Identical outputs
    # across shards would mean correlated sampling.
    assert not np.array_equal(out[0], out[2]) or not np.array_equal(out[1], out[3])


def test_tp_generate_rejects_bad_top_p():
    from tpu_dist_nn.parallel.tp_generate import tp_generate

    cfg, mesh, _, params_tp = _tp_setup()
    prompt = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="top_p"):
        tp_generate(mesh, params_tp, cfg, prompt, 2, temperature=1.0,
                    top_p=1.5, key=jax.random.key(0))


def test_pipeline_generate_matches_single_chip():
    # Pipelined decode: generation IN the training placement (blocks
    # sharded over `stage`, per-stage KV caches, activations on the
    # stage ring, token psum-broadcast back to the embedding) must be
    # token-for-token the single-chip greedy decode.
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.pp_generate import make_pipeline_generate
    from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(51), cfg)
    rng = np.random.default_rng(52)
    prompt = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)

    ref = generate(params, cfg, prompt, max_new_tokens=10, temperature=0.0)

    for stage, data in [(2, 2), (4, 1)]:
        mesh = build_mesh(MeshSpec(stage=stage, data=data))
        fn = make_pipeline_generate(mesh, cfg, stage, max_new_tokens=10)
        params_pp = dict(params, blocks=shard_blocks(params["blocks"], stage))
        out = jax.jit(fn)(params_pp, prompt)
        np.testing.assert_array_equal(np.asarray(out[:, 8:]), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(out[:, :8]), np.asarray(prompt))

    # N=1 short-circuit parity.
    ref1 = generate(params, cfg, prompt, max_new_tokens=1, temperature=0.0)
    mesh = build_mesh(MeshSpec(stage=2, data=1))
    fn1 = make_pipeline_generate(mesh, cfg, 2, max_new_tokens=1)
    params_pp = dict(params, blocks=shard_blocks(params["blocks"], 2))
    out1 = jax.jit(fn1)(params_pp, prompt)
    np.testing.assert_array_equal(np.asarray(out1[:, 8:]), np.asarray(ref1))


def test_cli_lm_sample_pipeline_stages(capsys):
    # tdn lm --sample-pipeline-stages: train, then decode IN the
    # pipeline placement; greedy-only and flag-compatibility rejections.
    from tpu_dist_nn.cli import main

    rc = main([
        "--platform", "cpu", "lm", "--steps", "2", "--batch-size", "4",
        "--seq-len", "24", "--d-model", "16", "--heads", "2",
        "--layers", "2", "--sample-bytes", "6", "--prompt", "ab",
        "--sample-pipeline-stages", "2", "--temperature", "0",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "sample" in out
    # temperature > 0 sampling works through the pipelined decoder too
    # (the single-chip key schedule is reproduced exactly).
    rc = main([
        "--platform", "cpu", "lm", "--steps", "1", "--batch-size", "4",
        "--seq-len", "24", "--d-model", "16", "--heads", "2",
        "--layers", "2", "--sample-bytes", "4", "--prompt", "ab",
        "--sample-pipeline-stages", "2", "--temperature", "0.8",
    ])
    assert rc == 0
    assert "sample" in capsys.readouterr().out
    # without --sample-bytes the flag rejects eagerly.
    assert main([
        "--platform", "cpu", "lm", "--steps", "1",
        "--sample-pipeline-stages", "2",
    ]) != 0


def test_pipeline_generate_overlapped_matches_single_chip():
    # Continuous-batching-style pipelined decode: G request groups
    # round-robin through the stage ring (steady state: one token
    # leaves the pipe per tick, no redundant compute). Every group's
    # stream must equal decoding its rows alone on one chip.
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.pp_generate import (
        make_pipeline_generate_overlapped,
    )
    from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(61), cfg)
    rng = np.random.default_rng(62)
    G, Bg, T, N = 4, 2, 8, 9
    prompts = jnp.asarray(rng.integers(0, 64, (G, Bg, T)), jnp.int32)

    refs = [
        np.asarray(generate(params, cfg, prompts[g], N, temperature=0.0))
        for g in range(G)
    ]

    for stage, data in [(2, 2), (4, 1)]:
        mesh = build_mesh(MeshSpec(stage=stage, data=data))
        fn = make_pipeline_generate_overlapped(
            mesh, cfg, stage, max_new_tokens=N, num_groups=G
        )
        params_pp = dict(params, blocks=shard_blocks(params["blocks"], stage))
        out = np.asarray(jax.jit(fn)(params_pp, prompts))
        assert out.shape == (G, Bg, T + N)
        for g in range(G):
            np.testing.assert_array_equal(out[g, :, :T], np.asarray(prompts[g]))
            np.testing.assert_array_equal(out[g, :, T:], refs[g], err_msg=str(g))

    # G < S rejected; N=1 short-circuit parity.
    mesh = build_mesh(MeshSpec(stage=4, data=1))
    with pytest.raises(ValueError, match="num_groups"):
        make_pipeline_generate_overlapped(mesh, cfg, 4, 5, num_groups=2)
    fn1 = make_pipeline_generate_overlapped(mesh, cfg, 4, 1, num_groups=4)
    params_pp = dict(params, blocks=shard_blocks(params["blocks"], 4))
    out1 = np.asarray(jax.jit(fn1)(params_pp, prompts))
    for g in range(G):
        ref1 = np.asarray(generate(params, cfg, prompts[g], 1, temperature=0.0))
        np.testing.assert_array_equal(out1[g, :, T:], ref1, err_msg=str(g))


def test_pipeline_generate_sampled_matches_single_chip():
    # Sampling at temperature > 0: the pipelined decoders reproduce the
    # single-chip KEY SCHEDULE (first from `key`, step n from
    # split(fold_in(key, 1), N-1)[n]), so streams match key-for-key.
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.pp_generate import (
        make_pipeline_generate,
        make_pipeline_generate_overlapped,
    )
    from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(71), cfg)
    rng = np.random.default_rng(72)
    G, Bg, T, N = 2, 2, 8, 7
    prompts = jnp.asarray(rng.integers(0, 64, (G, Bg, T)), jnp.int32)
    key = jax.random.key(9)

    refs = [
        np.asarray(generate(params, cfg, prompts[g], N, temperature=1.0,
                            top_k=8, key=key))
        for g in range(G)
    ]

    mesh = build_mesh(MeshSpec(stage=2, data=1))
    params_pp = dict(params, blocks=shard_blocks(params["blocks"], 2))

    fn = make_pipeline_generate(mesh, cfg, 2, N, temperature=1.0, top_k=8)
    for g in range(G):
        out = np.asarray(fn(params_pp, prompts[g], key=key))
        np.testing.assert_array_equal(out[:, T:], refs[g], err_msg=str(g))

    fno = make_pipeline_generate_overlapped(
        mesh, cfg, 2, N, num_groups=G, temperature=1.0, top_k=8
    )
    out = np.asarray(fno(params_pp, prompts, key=key))
    for g in range(G):
        np.testing.assert_array_equal(out[g, :, T:], refs[g], err_msg=str(g))

    # temperature > 0 without a key rejects.
    with pytest.raises(ValueError, match="PRNG key"):
        fn(params_pp, prompts[0])


def test_pipeline_generate_data_shards_sample_independently():
    # ADVICE r4 (medium): sampled pipelined decode on a data > 1 mesh
    # must fold the data-shard index into the key (tp_generate.py's
    # rule) — identical keys would draw identical gumbel noise on
    # every shard, duplicating continuations at matching local
    # indices. Same-prompt rows in different shards must diverge.
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.pp_generate import (
        make_pipeline_generate,
        make_pipeline_generate_overlapped,
    )
    from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(81), cfg)
    params_pp = dict(params, blocks=shard_blocks(params["blocks"], 2))
    mesh = build_mesh(MeshSpec(stage=2, data=2))
    N = 8

    # Rows 0/1 on data shard 0, rows 2/3 on shard 1 — identical prompts.
    prompt = jnp.tile(jnp.asarray([[3, 1, 4, 1, 5, 9]], jnp.int32), (4, 1))
    fn = make_pipeline_generate(mesh, cfg, 2, N, temperature=1.0)
    out = np.asarray(fn(params_pp, prompt, key=jax.random.key(5)))
    assert (not np.array_equal(out[0], out[2])
            or not np.array_equal(out[1], out[3]))

    # Same property through the overlapped decoder (Bg shards on data).
    prompts = jnp.tile(
        jnp.asarray([[2, 7, 1, 8, 2, 8]], jnp.int32), (2, 4, 1)
    )  # (G=2, Bg=4, T=6)
    fno = make_pipeline_generate_overlapped(
        mesh, cfg, 2, N, num_groups=2, temperature=1.0
    )
    outo = np.asarray(fno(params_pp, prompts, key=jax.random.key(5)))
    assert (not np.array_equal(outo[0, 0], outo[0, 2])
            or not np.array_equal(outo[0, 1], outo[0, 3]))


def test_pipeline_generate_shares_validator_contract():
    # ADVICE r4 (low): the pipelined wrappers route through
    # validate_generate_args — the same contract as the single-chip /
    # tp paths — instead of ad-hoc checks that drifted (they accepted
    # T + N == max_seq_len + 1 and silently ignored top_k at
    # temperature 0).
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.pp_generate import (
        make_pipeline_generate,
        make_pipeline_generate_overlapped,
    )
    from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(91), cfg)
    params_pp = dict(params, blocks=shard_blocks(params["blocks"], 2))
    mesh = build_mesh(MeshSpec(stage=2, data=1))
    prompt = jnp.zeros((2, 8), jnp.int32)

    # T + N == max_seq_len + 2 (one past the boundary: the decoders
    # embed total-1 positions, so T + N == max_seq_len + 1 is valid):
    # single-chip rejects; pipelined must too.
    fn = make_pipeline_generate(mesh, cfg, 2, max_new_tokens=18)
    with pytest.raises(ValueError, match="max_seq_len"):
        fn(params_pp, prompt)

    # top_k at temperature == 0 would be silently ignored — reject.
    fnk = make_pipeline_generate(mesh, cfg, 2, 4, temperature=0.0, top_k=5)
    with pytest.raises(ValueError, match="top_k"):
        fnk(params_pp, prompt)

    # Same contract through the overlapped wrapper.
    prompts = jnp.zeros((2, 2, 8), jnp.int32)
    fno = make_pipeline_generate_overlapped(
        mesh, cfg, 2, 18, num_groups=2
    )
    with pytest.raises(ValueError, match="max_seq_len"):
        fno(params_pp, prompts)
    fnob = make_pipeline_generate_overlapped(
        mesh, cfg, 2, 4, num_groups=2, temperature=1.0, top_p=1.5
    )
    with pytest.raises(ValueError, match="top_p"):
        fnob(params_pp, prompts, key=jax.random.key(0))
