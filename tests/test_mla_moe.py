"""Kimi-K2's block family (models/mla_moe.py) against its plain reference
(benchmark/configs/kimi_k2_reference.py) at a toy size of the same
family: one dense layer and two expert layers, 4 heads of 16 + 8 over a
latent of 32 + 8, a router of 16 outputs of which 4 are chosen and 6 are
held here, YaRN past a trained length of 32.  Logits are compared, never
tokens.

Tolerances.  With float32 parameters at matmul precision `highest` (the
suite's default) program and reference compute the same mathematics in
another order (a running softmax over key tiles against one over a row,
the absorbed products against the expanded ones, pairs sorted into
tiles against a loop over experts): 1e-4 on logits that spread by one
covers the float32 reordering (measured 9e-6).  With bfloat16 parameters
the program rounds every activation to 8 bits of mantissa, and a
rounding now and then flips one of a token's four experts, which moves
that position's logits by tenths: so the bound is on the MEDIAN over
positions of a position's RMS logit error, 0.035, twice what the program
reads (0.0175 forward, the same through the cache) and what the
reference's own bfloat16 emulation reads (0.018), under half of its int8
emulation's (0.079) and a tenth of its fp8 emulation's (0.35).  What
tells a precision from another end to end is the served-gap comparison,
by the limit the benchmark's rehearsal uses
(tests/bench_harness/test_bench_kimi.py).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import lookup
from tpu_dist_nn.models import experts, mla_moe, sala
from tpu_dist_nn.serving.continuous import ContinuousScheduler, slot_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
ref = lookup.load_module(
    os.path.join(CONFIGS, "kimi_k2_reference.py"), "kimi_k2_reference")

with open(os.path.join(CONFIGS, "rehearsal-kimi-tiny.json")) as f:
    TOY = json.load(f)
CFG32 = mla_moe.MlaMoeConfig.from_dict(dict(TOY, param_dtype="float32"))
CFG16 = mla_moe.MlaMoeConfig.from_dict(TOY)
# A prompt longer than the 128 tokens the masked expert form serves, so
# that a whole-prompt chunk takes the ragged one.
T, N, S = 200, 30, 3


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(TOY, 3, "float32")


@pytest.fixture(scope="module")
def rows():
    return np.random.default_rng(1).integers(0, 512, (S, T + N))


@pytest.fixture(scope="module")
def full(weights, rows):
    return np.asarray(ref.logits(weights, rows, TOY))


def _median_rms(a):
    """Median over positions of a position's RMS over the vocabulary."""
    return float(np.median(np.sqrt(np.mean(np.square(a), -1))))


@pytest.fixture(scope="module")
def programs(weights):
    made = {}

    def get(cfg):
        if cfg not in made:
            params = cfg.cast_params(weights)
            made[cfg] = (
                jax.jit(lambda c, slot, t, st:
                        mla_moe.prefill_chunk_into_cache(
                            params, cfg, c, slot, t, st)),
                jax.jit(lambda c, pos, tok, act: mla_moe.decode_step_slots(
                    params, c, pos, tok, cfg, active=act)))
        return made[cfg]

    return get


def _prefill(pre, cache, slot, tokens, chunk, start=0):
    at, logits = start, None
    while at < len(tokens):
        c = min(chunk, len(tokens) - at)
        logits, cache = pre(cache, slot, jnp.asarray(tokens[None, at:at + c]),
                            at)
        at += c
    return logits, cache


def _decode(step, cache, rows, slots=S):
    out = []
    for t in range(T, T + N):
        logits, cache = step(cache, jnp.full((slots,), t),
                             jnp.asarray(rows[:slots, t]),
                             jnp.ones((slots,), bool))
        out.append(np.asarray(logits))
    return np.stack(out, 1), cache


# ------------------------------------------------------------ (i) forward

def test_forward_matches_reference(weights, rows, full):
    got = mla_moe.forward(weights, jnp.asarray(rows), CFG32)
    np.testing.assert_allclose(np.asarray(got), full, atol=1e-4)


def test_forward_bf16_within_its_rounding(weights, rows, full):
    got = mla_moe.forward(CFG16.cast_params(weights), jnp.asarray(rows),
                          CFG16)
    assert got.dtype == jnp.float32
    assert _median_rms(np.asarray(got) - full) < 0.035
    # The bound tells precisions apart: int8's emulation is outside it.
    low = np.asarray(ref.logits(weights, rows[:1], TOY, "int8"))
    assert _median_rms(low - full[:1]) > 2 * 0.035


# ------------------------------------- (ii) chunks, then the latent cache

@pytest.mark.parametrize("chunk", [64, 136, 23])
def test_prefill_then_decode_matches_full_forward(programs, rows, full, chunk):
    """Chunks of 64 take the masked expert form, 136 the ragged one (and
    its 64-token rest the masked); the step attends the latent rows in
    the absorbed form.  Both against the reference's one full pass."""
    pre, step = programs(CFG32)
    cache = mla_moe.init_slot_cache(CFG32, S, T + N - 1)
    for s in range(S):
        logits, cache = _prefill(pre, cache, s, rows[s, :T], chunk)
        np.testing.assert_allclose(np.asarray(logits[0]), full[s, T - 1],
                                   atol=1e-4)
    got, _ = _decode(step, cache, rows)
    np.testing.assert_allclose(got, full[:, T:], atol=1e-4)


def test_prefill_then_decode_bf16(programs, rows, full):
    pre, step = programs(CFG16)
    cache = mla_moe.init_slot_cache(CFG16, S, T + N - 1)
    assert cache["lat"].dtype == jnp.bfloat16
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 136)
    got, _ = _decode(step, cache, rows)
    assert _median_rms(got - full[:, T:]) < 0.035


def test_absorbed_step_equals_expanded_form():
    """One query over the same latent rows, attended as the step does
    (w_kvb folded into the query and applied behind the softmax, the
    query's own row beside the cache) and as a chunk does (keys and
    values expanded a tile at a time, the row in the cache)."""
    cfg, M, pos = CFG32, 256, 177
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_n, q_r = f(1, cfg.n_heads, 16), f(1, cfg.n_heads, 8)
    rows, own = f(1, 1, cfg.latent_dim, M), f(1, cfg.latent_dim)
    blk = {"w_kvb": f(32, cfg.n_heads * 32) / 6}
    absorbed = mla_moe._attend_latent(q_n, q_r, rows[None], 0, own,
                                      jnp.asarray([pos]), blk, cfg)
    held = rows[0].at[:, :, pos].set(own)
    expanded = mla_moe._attend_expanded(q_n, q_r, held, jnp.asarray([pos]),
                                        blk, cfg)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5)


def test_inactive_slots_rows_ride_through_a_step_bit_for_bit(programs, rows):
    pre, step = programs(CFG16)
    cache = mla_moe.init_slot_cache(CFG16, S, T + N - 1)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 136)
    before = np.asarray(cache["lat"].astype(jnp.float32))
    counted = np.asarray(cache["routed"])
    _, after = step(cache, jnp.asarray([T, 7, T]), jnp.asarray(rows[:, T]),
                    jnp.asarray([True, False, True]))
    got = np.array(after["lat"].astype(jnp.float32))
    assert (got[:, 1] == before[:, 1]).all()
    assert (got[:, 0, :, :, T] != before[:, 0, :, :, T]).any()
    got[:, (0, 2), :, :, T] = before[:, (0, 2), :, :, T]
    assert (got == before).all()  # one lane a layer and active slot
    # The idle slot's token is in no count: 2 tokens x 4 experts x 2 layers.
    grown = np.asarray(after["routed"]) - counted
    assert grown[CFG16.n_held] == 2 * 4 * 2


def test_copied_slot_decodes_as_its_source(programs, rows, full):
    """`copy_cache_slot`: the copy's latent rows are the source's, and
    the routing counts belong to no slot."""
    pre, step = programs(CFG32)
    cache = mla_moe.init_slot_cache(CFG32, S, T + N - 1)
    _, cache = _prefill(pre, cache, 0, rows[0, :T], 64)
    counted = np.asarray(cache["routed"])
    cache = jax.jit(mla_moe.copy_cache_slot)(cache, 0, 2)
    assert (np.asarray(cache["routed"]) == counted).all()
    logits, _ = step(cache, jnp.asarray([0, 0, T]),
                     jnp.asarray([0, 0, rows[0, T]]),
                     jnp.asarray([False, False, True]))
    np.testing.assert_allclose(np.asarray(logits[2]), full[0, T], atol=1e-4)


# ------------------------------------------------ (iii) the expert layer

def _moe_block(cfg_dict, layer=1, seed=3):
    """Layer `layer`'s expert-layer leaves as the program stacks them
    (one layer), from the reference's draw for `cfg_dict`."""
    w = ref.layer_weights(cfg_dict, seed, layer, "float32")
    return {n: w[n][None] for n in ("w_r", "b_r", "sh_gu", "sh_d", "ex_gu",
                                    "ex_d")}, w


def _routed(u, moe, cfg, form):
    blk = mla_moe._layer({n: a for n, a in moe.items()
                          if not n.startswith("ex_")}, 0)
    chosen, w = mla_moe.route(u, blk, cfg)
    on, gates = mla_moe._held_gates(chosen, w, cfg)
    return form(u, on, gates, moe["ex_gu"], moe["ex_d"], 0)


def test_a_tokens_result_does_not_depend_on_its_batch():
    """Dropless: 160 tokens through the ragged form (pairs sorted into
    tiles), the same tokens through the masked form 5 at a time and one
    alone, give each token the same routed sum."""
    moe, _ = _moe_block(TOY)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(160, 64)),
                    jnp.float32)
    assert mla_moe.experts_form(160) == "ragged"
    assert mla_moe.experts_form(5) == "dense"
    ragged = np.asarray(_routed(u, moe, CFG32, experts.experts_ragged))
    assert np.abs(ragged).max() > 0.1
    for lo, hi in ((0, 5), (77, 78), (155, 160)):
        alone = _routed(u[lo:hi], moe, CFG32, experts.experts_dense)
        np.testing.assert_allclose(np.asarray(alone), ragged[lo:hi],
                                   atol=1e-5)


def test_ragged_form_is_sized_for_every_token_on_every_held_expert():
    """The worst load (a router that sends every token to every expert
    held) fills every tile the shapes allow, and nothing is dropped."""
    wide = dict(TOY, router_width=6, num_experts_per_tok=6)
    cfg = mla_moe.MlaMoeConfig.from_dict(dict(wide, param_dtype="float32"))
    moe, _ = _moe_block(wide)
    u = jnp.asarray(np.random.default_rng(6).normal(size=(300, 64)),
                    jnp.float32)
    ragged = _routed(u, moe, cfg, experts.experts_ragged)
    dense = _routed(u, moe, cfg, experts.experts_dense)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test of the `model-configs` guide: a router of 12
    experts, 4 chips of 3.  The routed parts that the four shares give
    (the program's layer, told each share's ids) plus the shared expert,
    counted once, are what the reference gives for the whole layer with
    all 12 held.  An expert's matrices depend on its id alone."""
    base = dict(TOY, router_width=12, n_routed_experts=12,
                experts_held={"rank": 0, "of": 1, "ids": list(range(12))})
    _, whole = _moe_block(base)
    u = jnp.asarray(np.random.default_rng(7).normal(size=(40, 64)),
                    jnp.float32)
    shared, routed = ref.moe_parts(u, whole, ref.sizes(base))
    total = 0.0
    for rank in range(4):
        ids = list(range(3 * rank, 3 * rank + 3))
        share = dict(base, n_routed_experts=3,
                     experts_held={"rank": rank, "of": 4, "ids": ids})
        cfg = mla_moe.MlaMoeConfig.from_dict(
            dict(share, param_dtype="float32"))
        assert cfg.experts_held == tuple(ids) and cfg.router_width == 12
        moe, w = _moe_block(share)
        np.testing.assert_array_equal(np.asarray(w["ex_gu"]),
                                      np.asarray(whole["ex_gu"])[ids])
        part = np.asarray(_routed(u, moe, cfg, experts.experts_dense))
        assert np.abs(part).max() > 0.05  # every share has work here
        total = total + part
    np.testing.assert_allclose(total, np.asarray(routed), atol=1e-5)
    # And the program's whole layer on one share: residual + shared
    # expert (once, on every chip) + that share's part.
    out, _ = mla_moe._moe_ffn(u, jnp.ones((64,)), moe, 0,
                              jnp.ones((40,), bool), cfg)
    un = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
    shared_n, routed_n = ref.moe_parts(un, w, ref.sizes(share))
    np.testing.assert_allclose(np.asarray(out - u),
                               np.asarray(shared_n + routed_n), atol=1e-5)


def test_the_bias_changes_a_choice_and_not_a_weight():
    moe, _ = _moe_block(TOY)
    blk = mla_moe._layer({n: a for n, a in moe.items()
                          if not n.startswith("ex_")}, 0)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(400, 64)),
                    jnp.float32)
    chosen, w = mla_moe.route(u, blk, CFG32)
    plain, w_plain = mla_moe.route(u, dict(blk, b_r=0 * blk["b_r"]), CFG32)
    same = np.sort(np.asarray(chosen), -1) == np.sort(np.asarray(plain), -1)
    flipped = ~same.all(-1)
    assert 0 < flipped.sum() < len(flipped)  # it chooses
    # It does not weigh: the weights are the sigmoid scores of whatever
    # was chosen, normalised over the chosen and scaled.
    s = np.asarray(jax.nn.sigmoid(u @ blk["w_r"]))
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    want = picked / picked.sum(-1, keepdims=True) * 2.827
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.827, rtol=1e-5)


# --------------------------------------------------------- (iv) rotary

def test_yarn_frequencies_by_hand():
    """The published rope_scaling (theta 50000, factor 64 over 4096,
    beta 32 and 1, 32 planes): plain below plane 8, f / 64 from plane 20,
    a linear ramp between."""
    with open(os.path.join(CONFIGS, "kimi-k2.7-code.json")) as f:
        cfg = mla_moe.MlaMoeConfig.from_dict(json.load(f))
    w = cfg.rope_freqs()
    assert w.shape == (32,)
    by_hand = {0: 1.0, 8: 0.0668740304976422, 14: 0.0044658487485676485,
               20: 1.8070233867863153e-05, 31: 4.382206455794937e-07}
    for j, want in by_hand.items():
        assert w[j] == pytest.approx(want, rel=1e-12), j
    # Plane 14 is halfway up the ramp: (f + f / 64) / 2.
    f14 = 50000.0 ** (-28 / 64)
    assert w[14] == pytest.approx(f14 * (1 + 1 / 64) / 2, rel=1e-12)
    assert cfg.softmax_scale == pytest.approx(0.14468, abs=5e-6)
    assert cfg.rope_cos_sin_scale == 1.0
    np.testing.assert_allclose(ref.yarn_freqs(ref.sizes(TOY)),
                               CFG32.rope_freqs(), rtol=1e-12)


def test_the_shared_key_is_rotated_at_the_position_it_is_written_for():
    """Scores depend on the distance between query and key alone: the
    same row one position later, under a query one position later."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8)), jnp.float32)

    def score(tq, tk):
        return jnp.einsum("ahd,ad->ah",
                          mla_moe._rope(q, jnp.asarray([[tq]]), CFG32),
                          mla_moe._rope(k, jnp.asarray([tk]), CFG32))

    np.testing.assert_allclose(np.asarray(score(40, 33)),
                               np.asarray(score(47, 40)), atol=1e-5)
    assert np.abs(np.asarray(score(40, 33) - score(40, 34))).max() > 1e-3


# ----------------------------------------------------- (v) the scheduler

def _drain(stream, at_least=None):
    toks = []
    while at_least is None or len(toks) < at_least:
        event = stream.next_event(60.0)
        assert event is not None, "stream stalled"
        kind, data = event
        if kind != "tokens":
            break
        toks.extend(data)
    return toks


def _serve(params, cfg, prompts, budgets, **kw):
    sched = ContinuousScheduler(params, cfg, slots=2, prompt_len=T,
                                max_new_tokens=24, **kw)
    try:
        streams = [sched.submit_stream(p[None], max_new_tokens=b)
                   for p, b in zip(prompts, budgets)]
        return [_drain(s) for s in streams], sched
    finally:
        sched.close()


@pytest.fixture(scope="module")
def served(weights, rows):
    prompts, budgets = rows[:, :T], [24, 16, 20]
    out, sched = _serve(CFG16.cast_params(weights), CFG16, prompts, budgets,
                        prefill_chunk=136)
    return prompts, budgets, out, sched


def test_scheduler_streams_complete_with_lengths_as_asked(served):
    _, budgets, out, sched = served
    assert [len(o) for o in out] == budgets
    assert sched.prefill_chunks_total == 3 * 2
    assert sched.cache_bytes == {
        "latent": 3 * 2 * 40 * 256 * 2}  # layers, slots, 32 + 8, extent


def test_scheduler_serves_what_the_reference_ranks_first(weights, served):
    prompts, _, out, _ = served
    served_rows = np.concatenate([prompts, np.zeros((S, 24), int)], 1)
    for i, o in enumerate(out):
        served_rows[i, T:T + len(o)] = o
    gaps = ref.served_gaps(weights, served_rows, TOY, T)["gap_served"]
    kept = np.concatenate([gaps[i, :len(o)] for i, o in enumerate(out)])
    assert kept.mean() < 0.05


def test_scheduler_books_the_devices_routing_counts(served):
    """The counts ride in the cache and are fetched when the loop runs
    dry: every prefilled and decoded position routed 4 pairs in each of
    2 expert layers; every decode step visited 2 x 6 held experts."""
    _, budgets, out, sched = served
    got = sched.routing_totals
    positions = S * T + sched.slot_steps_total
    assert int(got["routed_pairs"]) == positions * 4 * 2
    assert int(got["expert_visits"]) == sched.steps_total * 2 * 6
    assert 0 < int(got["expert_touched"]) <= int(got["expert_visits"])
    held = np.asarray(got["expert_pairs"])
    assert held.shape == (6,) and (held > 0).all()
    # 6 of the router's 16 are here: about 6/16 of the pairs.
    assert 0.2 < held.sum() / int(got["routed_pairs"]) < 0.55
    assert sched.experts_held == (0, 1, 2, 3, 4, 5)


def test_routing_counters_reach_the_registry(weights, rows):
    from tpu_dist_nn.obs.registry import Registry
    from tpu_dist_nn.obs.runtime import RuntimeSampler

    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    sched = ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                                max_new_tokens=8, prefill_chunk=136)
    sampler.add_generation_scheduler(sched)
    try:
        assert len(_drain(sched.submit_stream(rows[:1, :T],
                                              max_new_tokens=5))) == 5
        deadline = 50
        while not int(sched.routing_totals["routed_pairs"]) and deadline:
            deadline -= 1
            import time
            time.sleep(0.1)
        sampler.sample_once()
    finally:
        sched.close()
    scraped = {m.name: {k: c.value for k, c in m.samples()}
               for m in reg.collect()}
    totals = sched.routing_totals
    assert scraped["tdn_gen_routed_pairs_total"][()] \
        == int(totals["routed_pairs"]) == (T + 4) * 4 * 2
    assert scraped["tdn_gen_expert_visits_total"][()] == 4 * 2 * 6
    assert scraped["tdn_gen_expert_pairs_total"] == {
        (str(e),): int(n) for e, n in enumerate(totals["expert_pairs"])}
    assert scraped["tdn_gen_cache_bytes"] == {
        ("latent",): float(sched.cache_bytes["latent"])}


def test_other_families_hand_over_no_routing_counts():
    """GPT-2, SALA and SambaY give none: the scheduler asks once and
    fetches nothing."""
    from tpu_dist_nn.models.transformer import TransformerConfig

    with open(os.path.join(CONFIGS, "rehearsal-sala-tiny.json")) as f:
        sala_cfg = sala.SalaConfig.from_dict(json.load(f))
    flash = sala.load_model_config(
        os.path.join(CONFIGS, "rehearsal-phi4flash-tiny.json"))
    for cfg in (TransformerConfig(), sala_cfg, flash):
        assert cfg.slot_model().routing_counts is None
        assert len(slot_kernels(cfg, 0.0, None, None)) == 3
    assert CFG16.slot_model().routing_counts is not None


def test_prefix_pool_hit_resumes_from_the_copied_rows(weights, rows):
    """With the pool on, a second request that shares 136 positions takes
    the tier and streams what it streams with the pool off: latent rows
    are positional, so a tier is copied out of a slot wherever a block
    ends (no recurrent state)."""
    prompts = np.stack([rows[0, :T], np.concatenate(
        [rows[0, :136], rows[1, 136:T]])])
    plain, _ = _serve(weights, CFG32, prompts, [6, 6], prefill_chunk=68)
    sched = ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                                max_new_tokens=24, prefill_chunk=68,
                                prefix_cache_blocks=4)
    try:
        first = _drain(sched.submit_stream(prompts[:1], max_new_tokens=6))
        second = _drain(sched.submit_stream(prompts[1:], max_new_tokens=6))
        assert sched.prefix_hits_total == 1
        assert sched.prefill_chunks_total < 3 + 3
        assert [first, second] == plain
    finally:
        sched.close()


def test_preempted_row_resumes_its_stream(weights, rows):
    prompts = rows[:, :T]
    plain, _ = _serve(weights, CFG32, prompts[:1], [10], prefill_chunk=136)
    sched = ContinuousScheduler(weights, CFG32, slots=1, prompt_len=T,
                                max_new_tokens=24, prefill_chunk=136)
    try:
        low = sched.submit_stream(prompts[:1], max_new_tokens=10,
                                  slo_class="best_effort")
        got = _drain(low, at_least=3)
        urgent = sched.submit_stream(prompts[1:2], max_new_tokens=3,
                                     slo_class="critical")
        assert len(_drain(urgent)) == 3
        got += _drain(low)
        assert sched.preempted_total == 1
        assert got == plain[0]
    finally:
        sched.close()


# --------------------------------------------------- config, loader, counts

def test_loader_reads_the_benchmarks_configuration():
    cfg = sala.load_model_config(os.path.join(CONFIGS, "kimi-k2.7-code.json"))
    assert isinstance(cfg, mla_moe.MlaMoeConfig)
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 5
    assert (cfg.latent_dim, cfg.qk_head_dim, cfg.router_width, cfg.n_held,
            cfg.n_experts_per_tok) == (576, 192, 384, 12, 8)
    assert cfg.experts_held == tuple(range(12))
    # By hand (ISSUE 33's table): attention 101.12 M, an expert 44.04 M,
    # the router 2.75 M; a layer of the share 676.4 M, the dense layer
    # 497.48 M, embedding and head slices 146.8 M each: 4 173 M, and the
    # gains and the selection bias on top.
    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 7168
    expert, router = 3 * 7168 * 2048, 7168 * 384
    layer = attn + expert + router + 12 * expert
    assert (attn, expert, router, layer) == (
        101_122_048, 44_040_192, 2_752_512, 676_397_056)
    small = 6 * (2 * 7168 + 1536 + 512) + 5 * 384 + 7168
    by_hand = attn + 3 * 7168 * 18432 + 5 * layer + 2 * 20480 * 7168 + small
    assert cfg.num_params() == by_hand
    assert abs(by_hand - 4173e6) < 1e6


def test_loader_refuses_what_the_family_is_not(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"model_type": "gpt2"}))
    with pytest.raises(ValueError, match="phi4flash.*kimi_k2"):
        sala.load_model_config(str(path))
    for bad, match in ((dict(TOY, scoring_func="softmax"), "scoring_func"),
                       (dict(TOY, n_group=8), "n_group"),
                       (dict(TOY, experts_held={"ids": [0, 1]}),
                        "n_routed_experts"),
                       (dict(TOY, experts_held={"ids": [0, 1, 2, 3, 4, 16]}),
                        "distinct ids")):
        path.write_text(json.dumps(bad))
        with pytest.raises(ValueError, match=match):
            sala.load_model_config(str(path))


def test_configuration_holds_the_catalogs_numbers():
    with open(os.path.join(CONFIGS, "kimi-k2.7-code.json")) as f:
        cfg = json.load(f)
    assert cfg["source"].endswith(
        "moonshotai/Kimi-K2.7-Code/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "kimi_k2",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 384, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 50000, "routed_scaling_factor": 2.827,
        "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    differs = {k for k, v in catalog.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"])
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: catalog[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_width"]) == (6, 12, 20480, 384)
    assert cfg["experts_held"] == {"rank": 0, "of": 32,
                                   "ids": list(range(12))}
    assert "32 chips" in cfg["deployment"]
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())


def test_flop_model_sums_are_the_sum_of_their_steps():
    model = mla_moe.MlaMoeFlopModel(CFG32, 230)
    assert model.M == 256
    assert model.steps_useful_sum(10, 20) == sum(
        model.step_useful_flops(p) for p in range(10, 30))
    # A chunk's useful work grows by its keys, its expansion and its
    # head, and never passes what the launch computes.
    assert model.chunk_useful_flops(64, 64, True) \
        - model.chunk_useful_flops(64, 64, False) == model._logit
    assert model.step_flops() >= model.step_useful_flops(255)
    for size in (64, 136):
        assert model.chunk_flops(size) >= model.chunk_useful_flops(
            256 - size, size, True)
    assert model.prefill_chunks_flops(0, 200, 136) \
        == model.chunk_flops(136) + model.chunk_flops(64)
    # Useful routed work is the pairs sent to experts held here (4 of a
    # token's choices x 6 of 16 experts), never every held expert.
    routed = model._routed(16)
    assert routed == 2 * model._expert * 16 * 4 * 6 // 16
    assert model.step_flops() - model.step_useful_flops(255) \
        == 2 * 6 * model._expert - model._routed(1)


# ------------------------------------------------------- the entry point

def test_cli_lm_model_config_serves_over_the_wire():
    """`tdn lm --model-config F --serve-generate P` with this family's
    file: seeded weights behind the normal gRPC endpoint, on the
    continuous scheduler."""
    import socket
    import threading
    import time

    from tpu_dist_nn.cli import main
    from tpu_dist_nn.serving import GrpcClient

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    t = threading.Thread(
        target=main,
        args=([
            "--platform", "cpu", "lm", "--model-config",
            os.path.join(CONFIGS, "rehearsal-kimi-tiny.json"),
            "--serve-generate", str(port), "--serve-prompt-len", "40",
            "--serve-new-tokens", "4", "--temperature", "0",
            "--gen-slots", "2", "--prefill-chunk", "16",
            "--serve-seconds", "20",
        ],),
        daemon=True,
    )
    t.start()
    client = GrpcClient(f"127.0.0.1:{port}", timeout=30.0)
    prompts = np.full((2, 40), 7)
    deadline, out = time.monotonic() + 90, None
    while time.monotonic() < deadline:
        try:
            out = client.generate(prompts)
            break
        except Exception:  # noqa: BLE001 — the port is not open yet
            time.sleep(1.0)
    assert out is not None, "server never came up"
    assert out.shape == (2, 44) and (out[:, :40] == 7).all()
    assert (out[0] == out[1]).all() and (out[:, 40:] < 512).all()
