"""Laguna's block family (models/laguna.py) against its plain reference
(benchmark/configs/laguna_reference.py) at a toy size of the same
family, cut as the cell is cut: a dense full layer, three window layers
and a full layer; 4 query heads on a full layer and 6 on a window layer
over 2 K/V heads of 32; a window of 8 that the prompts and the decode
wrap many times; partial YaRN (16 of 32 dimensions) past a trained
length of 64 on the full layers, plain RoPE on all 32 on the window
layers; a softmax router of 16 outputs of which 4 are chosen and 6 are
held here.  Logits are compared, never tokens.

Tolerances.  With float32 parameters at matmul precision `highest` (the
suite's default) program and reference compute the same mathematics in
another order (a running softmax over key tiles against one over a row,
a ring in lane order against a banded mask over the whole row, pairs
sorted into tiles against a loop over experts): 1e-4 on logits that
spread by one covers the float32 reordering (measured 1e-5).  With
bfloat16 parameters the program rounds every activation to 8 bits of
mantissa, and a rounding now and then flips one of a token's four
experts: the bound is on the MEDIAN over positions of a position's RMS
logit error, 0.04, 1.6 times what the program reads forward (0.025) and
1.8 times the reference's own bfloat16 emulation (0.022), under half of
its int8 emulation's (0.099) and a ninth of its fp8 emulation's (0.36);
my CPU runs, PR 37.  What tells a precision from
another end to end is the served-gap comparison, by the limit the
benchmark's rehearsal uses (tests/bench_harness/test_bench_laguna.py).

The last section pins what the PR that brought this family moved: the
expert layer that Kimi-K2's programs call (models/experts.py) and the
ring helpers SambaY's call leave their programs as they were.
"""

import collections
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import lookup
from tpu_dist_nn.models import experts, laguna, mla_moe, sala, sambay, slot_model
from tpu_dist_nn.serving.continuous import ContinuousScheduler, slot_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
ref = lookup.load_module(
    os.path.join(CONFIGS, "laguna_reference.py"), "laguna_reference")

with open(os.path.join(CONFIGS, "rehearsal-laguna-tiny.json")) as f:
    TOY = json.load(f)
CFG32 = laguna.LagunaConfig.from_dict(dict(TOY, param_dtype="float32"))
CFG16 = laguna.LagunaConfig.from_dict(TOY)
# A prompt longer than the 128 tokens the masked expert form serves, so
# that a whole-prompt chunk takes the ragged one; a window of 8.
T, N, S, W = 200, 30, 3, 8
FULL, WINDOW = laguna.FULL, laguna.WINDOW


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
                        laguna.prefill_chunk_into_cache(
                            params, cfg, c, slot, t, st)),
                jax.jit(lambda c, pos, tok, act: laguna.decode_step_slots(
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
    got = laguna.forward(weights, jnp.asarray(rows), CFG32)
    np.testing.assert_allclose(np.asarray(got), full, atol=1e-4)


def test_forward_bf16_within_its_rounding(weights, rows, full):
    got = laguna.forward(CFG16.cast_params(weights), jnp.asarray(rows), CFG16)
    assert _median_rms(np.asarray(got) - full) < 0.04


@pytest.mark.parametrize("chunk", [23, 136, T])
def test_prefill_then_decode_matches_full_forward(programs, rows, full, chunk):
    """Chunks that straddle the window's edge and the expert forms'
    (23 and 136 + 64: masked, ragged), then 30 steps through both caches
    past many turns of the ring."""
    pre, step = programs(CFG32)
    cache = laguna.init_slot_cache(CFG32, S, T + N)
    for s in range(S):
        last, cache = _prefill(pre, cache, s, rows[s, :T], chunk)
        np.testing.assert_allclose(np.asarray(last[0]), full[s, T - 1],
                                   atol=1e-4)
    got, _ = _decode(step, cache, rows)
    np.testing.assert_allclose(got, full[:, T:T + N], atol=1e-4)


def test_prefill_then_decode_bf16(programs, rows, full):
    pre, step = programs(CFG16)
    cache = laguna.init_slot_cache(CFG16, S, T + N)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 136)
    got, _ = _decode(step, cache, rows)
    assert _median_rms(got - full[:, T:T + N]) < 0.04


def test_inactive_slots_rows_ride_through_a_step_bit_for_bit(programs, rows):
    pre, step = programs(CFG32)
    cache = laguna.init_slot_cache(CFG32, S, T + N)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 136)
    before = {n: np.asarray(cache[n]) for n in ("k", "v", "wk", "wv")}
    routed = np.asarray(cache["routed"])
    _, after = step(cache, jnp.full((S,), T), jnp.asarray(rows[:, T]),
                    jnp.asarray([True, False, False]))
    for n, a in before.items():
        np.testing.assert_array_equal(np.asarray(after[n])[:, 1:], a[:, 1:])
        assert not np.array_equal(np.asarray(after[n])[:, 0], a[:, 0])
    # One token decoded, 4 pairs in each of 4 expert layers.
    assert int(np.asarray(after["routed"])[6] - routed[6]) == 4 * 4


# ---------------------------------------------------- (ii) window and rotary

def test_a_ring_key_is_rotated_once_at_write(weights, rows, programs):
    """After a prompt of 200 and 30 decoded positions, each lane of the
    first window layer's ring holds the key of the latest position at its
    residue, rotated at THAT position and at no other: the reference's
    unrotated u W_k, rotated once by the window's scheme."""
    pre, step = programs(CFG32)
    cache = laguna.init_slot_cache(CFG32, S, T + N)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 23)
    _, cache = _decode(step, cache, rows)
    s = ref.sizes(TOY)
    w0, w1 = ref.layer_of(weights, TOY, 0), ref.layer_of(weights, TOY, 1)
    x = weights["embed"][jnp.asarray(rows[0, :T + N])]
    with jax.default_matmul_precision("highest"):
        x = ref.layer(x, w0, s, FULL, "dense")
        u = ref._rms(x, w1["ln1_g"], s["eps"])
        raw = (u @ w1["w_k"]).reshape(-1, 2, 32)
    last = T + N - 1  # the last position the decode wrote
    newest = last - (last - np.arange(W)) % W
    want = np.asarray(ref.rope(raw, jnp.arange(T + N), s, WINDOW))
    ring = np.asarray(cache["wk"])[0, 0]  # (G, d, W)
    np.testing.assert_allclose(ring.transpose(2, 0, 1), want[newest],
                               atol=1e-4)
    # Rotated twice (again at read, or at the lane's index) it is not.
    twice = np.asarray(ref.rope(jnp.asarray(want[newest]),
                                jnp.asarray(newest), s, WINDOW))
    assert np.abs(twice - want[newest]).max() > 0.1


def test_full_layers_rotate_a_yarn_half_and_window_layers_all():
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 3, 32)),
                    jnp.float32)
    pos = jnp.asarray([0, 100])
    full = np.asarray(laguna._rope(x, pos, CFG32, FULL))
    window = np.asarray(laguna._rope(x, pos, CFG32, WINDOW))
    np.testing.assert_array_equal(full[..., 16:], np.asarray(x)[..., 16:])
    # At position 0 a full layer's rotated half only carries the factor.
    np.testing.assert_allclose(full[0, :, :16],
                               CFG32.attention_factor * np.asarray(x)[0, :, :16],
                               rtol=1e-6)
    np.testing.assert_allclose(window[0], np.asarray(x)[0], rtol=1e-6)
    assert np.abs(window[1, :, 16:] - np.asarray(x)[1, :, 16:]).max() > 0.1
    np.testing.assert_allclose(CFG32.rope_freqs(FULL),
                               ref.yarn_freqs(ref.sizes(TOY)), rtol=1e-12)


def test_yarn_frequencies_by_hand():
    """The published full layers: theta 500000 on 64 of 128 dimensions,
    factor 128 over 8192, beta 32 and 1: plain below plane 9, f / 128
    from plane 18, a linear ramp between; cos and sin times 1.4852030."""
    cfg = sala.load_model_config(os.path.join(CONFIGS, "laguna-s-2.1.json"))
    w = cfg.rope_freqs(FULL)
    f = 500000.0 ** (-2 * np.arange(32) / 64)
    assert w.shape == (32,) and cfg.full_rotary_dim == 64
    np.testing.assert_allclose(w[:10], f[:10], rtol=1e-12)
    np.testing.assert_allclose(w[18:], f[18:] / 128, rtol=1e-12)
    ramp = (13.5 - 9) / (18 - 9)
    assert w[13] == pytest.approx(f[13] / 128 * (4 / 9)
                                  + f[13] * (5 / 9), rel=1e-12)
    assert 0 < ramp < 1
    assert cfg.attention_factor == pytest.approx(0.1 * np.log(128) + 1)
    np.testing.assert_allclose(cfg.rope_freqs(WINDOW),
                               10000.0 ** (-2 * np.arange(64) / 128),
                               rtol=1e-12)
    assert cfg.window_rotary_dim == 128


def test_the_per_head_gate_scales_each_head_by_its_own_scalar():
    rng = np.random.default_rng(4)
    A, G, g, d, D = 3, 2, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(A, D)), jnp.float32)
    o = jnp.asarray(rng.normal(size=(A, G, g, d)), jnp.float32)
    gate = jnp.asarray(rng.uniform(size=(A, G * g)), jnp.float32)
    w_o = jnp.asarray(rng.normal(size=(G * g * d, D)), jnp.float32)
    got = laguna._attn_out(x, o, gate, {"w_o": w_o})
    heads = np.asarray(o).reshape(A, G * g, d) * np.asarray(gate)[:, :, None]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(x) + heads.reshape(A, -1) @ w_o,
                               rtol=1e-5)
    # The gate is one sigmoid a head of the normed input.
    blk = ref.layer_of(ref.make_weights(TOY, 3, "float32"), TOY, 1)
    u, *_, gates = laguna._project(x[:, :1].repeat(64, 1), blk,
                                   jnp.arange(A), CFG32, WINDOW)
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(jax.nn.sigmoid(u @ blk["w_gate"])),
                               rtol=1e-5)
    assert gates.shape == (A, 6)


# ------------------------------------------------ (iii) the expert layer

def _moe_block(cfg_dict, layer=1, seed=3):
    """Layer `layer`'s expert-layer leaves as the program stacks them
    (one layer), from the reference's draw for `cfg_dict`."""
    w = ref.layer_weights(cfg_dict, seed, layer, "float32")
    return {n: w[n][None] for n in ("w_r", "sh_gu", "sh_d", "ex_gu",
                                    "ex_d")}, w


def _routed(u, moe, cfg):
    blk = laguna._layer({n: a for n, a in moe.items()
                         if not n.startswith("ex_")}, 0)
    chosen, w = laguna.route(u, blk, cfg)
    on, gates = experts.held_gates(chosen, w, cfg.experts_held)
    form = experts.experts_dense if experts.experts_form(u.shape[0]) \
        == "dense" else experts.experts_ragged
    return form(u, on, gates, moe["ex_gu"], moe["ex_d"], 0)


def test_router_weights_sum_to_the_scale_and_only_held_experts_count():
    """A softmax over all 16, the top 4 renormalised over the 4 and
    times 2.5, whether they are held here or not; the held experts'
    part of the sum is what the reference's loop over them gives, and
    an expert held elsewhere adds nothing."""
    moe, w = _moe_block(TOY)
    blk = laguna._layer({n: a for n, a in moe.items()
                         if not n.startswith("ex_")}, 0)
    u = jnp.asarray(np.random.default_rng(8).normal(size=(300, 64)),
                    jnp.float32)
    chosen, weight = laguna.route(u, blk, CFG32)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-6)
    p = np.asarray(jax.nn.softmax(u @ blk["w_r"], -1))
    top = np.sort(p, -1)[:, ::-1][:, :4]
    np.testing.assert_allclose(np.asarray(weight),
                               top / top.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-5)
    elsewhere = ~np.isin(np.asarray(chosen), CFG32.experts_held)
    assert 0 < elsewhere.mean() < 1
    on, gates = experts.held_gates(chosen, weight, CFG32.experts_held)
    # A token's held weights are its chosen weights that are held here:
    # what goes elsewhere is not renormalised away.
    np.testing.assert_allclose(
        np.asarray(gates).sum(-1),
        np.where(elsewhere, 0.0, np.asarray(weight)).sum(-1), rtol=1e-6)
    assert (np.asarray(gates).sum(-1) < 2.5 - 1e-3).mean() > 0.5
    _, routed = ref.moe_parts(u, w, ref.sizes(TOY))
    np.testing.assert_allclose(np.asarray(_routed(u, moe, CFG32)),
                               np.asarray(routed), atol=1e-5)
    for lo, hi in ((0, 5), (150, 151)):  # the masked form, a token alone
        np.testing.assert_allclose(np.asarray(_routed(u[lo:hi], moe, CFG32)),
                                   np.asarray(routed)[lo:hi], atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test of the `model-configs` guide, as the cell is cut:
    a router of 16, 2 chips of 8.  The routed parts that ranks 0 and 1
    give (the program's layer, told each share's ids) plus the shared
    expert, counted once, are what the reference gives for the whole
    layer with all 16 held.  An expert's matrices depend on its id
    alone."""
    base = dict(TOY, num_experts=16,
                experts_held={"rank": 0, "of": 1, "ids": list(range(16))})
    _, whole = _moe_block(base)
    u = jnp.asarray(np.random.default_rng(7).normal(size=(40, 64)),
                    jnp.float32)
    shared, routed = ref.moe_parts(u, whole, ref.sizes(base))
    total = 0.0
    for rank in range(2):
        ids = list(range(8 * rank, 8 * rank + 8))
        share = dict(base, num_experts=8,
                     experts_held={"rank": rank, "of": 2, "ids": ids})
        cfg = laguna.LagunaConfig.from_dict(dict(share, param_dtype="float32"))
        assert cfg.experts_held == tuple(ids) and cfg.router_width == 16
        moe, w = _moe_block(share)
        np.testing.assert_array_equal(np.asarray(w["ex_gu"]),
                                      np.asarray(whole["ex_gu"])[ids])
        part = np.asarray(_routed(u, moe, cfg))
        assert np.abs(part).max() > 0.05  # every share has work here
        total = total + part
    np.testing.assert_allclose(total, np.asarray(routed), atol=1e-5)
    # And the program's whole layer on one share: residual + shared
    # expert (once, on every chip) + that share's part.
    out, _ = laguna._moe_ffn(u, jnp.ones((64,)), moe, 0,
                             jnp.ones((40,), bool), cfg)
    un = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-6)
    shared_n, routed_n = ref.moe_parts(un, w, ref.sizes(share))
    np.testing.assert_allclose(np.asarray(out - u),
                               np.asarray(shared_n + routed_n), atol=1e-5)


# ----------------------------------------------------- (iv) the scheduler

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
    # 2 full layers' K and V over an extent of 256, 3 window layers'
    # rings of 8: 2 slots, 2 heads of 32, bfloat16.
    assert sched.cache_bytes == {"kv": 2 * 2 * 2 * 2 * 32 * 256 * 2,
                                 "window": 2 * 3 * 2 * 2 * 32 * 8 * 2}


def test_scheduler_serves_what_the_reference_ranks_first(weights, served):
    prompts, _, out, _ = served
    served_rows = np.concatenate([prompts, np.zeros((S, 24), int)], 1)
    for i, o in enumerate(out):
        served_rows[i, T:T + len(o)] = o
    gaps = ref.served_gaps(weights, served_rows, TOY, T)["gap_served"]
    kept = np.concatenate([gaps[i, :len(o)] for i, o in enumerate(out)])
    assert kept.mean() < 0.05


def test_scheduler_books_the_devices_routing_counts(served):
    """Every prefilled and decoded position routed 4 pairs in each of 4
    expert layers; every decode step visited 4 x 6 held experts."""
    _, _, _, sched = served
    got = sched.routing_totals
    positions = S * T + sched.slot_steps_total
    assert int(got["routed_pairs"]) == positions * 4 * 4
    assert int(got["expert_visits"]) == sched.steps_total * 4 * 6
    assert 0 < int(got["expert_touched"]) <= int(got["expert_visits"])
    held = np.asarray(got["expert_pairs"])
    assert held.shape == (6,) and (held > 0).all()
    # 6 of the router's 16 are here: about 6/16 of the pairs.
    assert 0.2 < held.sum() / int(got["routed_pairs"]) < 0.55


def test_prefix_pool_needs_chunks_for_the_rings(weights):
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                            max_new_tokens=4, prefix_cache_blocks=2)


def test_prefix_pool_hit_resumes_from_the_tier_that_a_chunk_ended(
        weights, rows):
    """With the pool on, a second request that shares 136 positions takes
    the tier the first chunk ended at (its rings hold the 8 positions
    before 136, and no later ones) and streams what it streams with the
    pool off."""
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
    cfg = sala.load_model_config(os.path.join(CONFIGS, "laguna-s-2.1.json"))
    assert isinstance(cfg, laguna.LagunaConfig)
    assert cfg.layer_types == (FULL,) + (WINDOW,) * 3 + (FULL,)
    assert (cfg.heads(FULL), cfg.heads(WINDOW), cfg.n_kv_heads,
            cfg.head_dim, cfg.sliding_window) == (48, 72, 8, 128, 512)
    assert (cfg.router_width, cfg.n_held, cfg.n_experts_per_tok,
            cfg.n_dense, cfg.n_moe) == (256, 128, 10, 1, 4)
    assert cfg.experts_held == tuple(range(128))
    # By hand (ISSUE 37's tables): full attention 44.19 M, window 63.14 M,
    # an expert (and the shared one) 9.44 M, the router 0.79 M, the dense
    # layer's SwiGLU 113.2 M, embedding and head halves 154.1 M each.
    D = 3072
    full = D * 6144 + 2 * D * 1024 + D * 48 + 6144 * D
    window = D * 9216 + 2 * D * 1024 + D * 72 + 9216 * D
    expert, router = 3 * D * 1024, D * 256
    assert (full, window, expert, router) == (
        44_187_648, 63_135_744, 9_437_184, 786_432)
    layers = full + 3 * D * 12288 + 3 * (window + router + expert
                                         + 128 * expert) \
        + full + router + expert + 128 * expert
    by_hand = layers + 2 * 50176 * D + 2 * 5 * D + D
    assert cfg.num_params() == by_hand
    assert abs(by_hand - 5572e6) < 1e6  # 11.14 GB in bfloat16


def test_loader_refuses_what_the_family_is_not(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"model_type": "gpt2"}))
    with pytest.raises(ValueError, match="kimi_k2.*laguna"):
        sala.load_model_config(str(path))
    for bad, match in ((dict(TOY, norm_topk_prob=False), "norm_topk_prob"),
                       (dict(TOY, gating="none"), "gating"),
                       (dict(TOY, moe_router_logit_softcapping=30.0),
                        "soft"),
                       (dict(TOY, experts_held={"ids": [0, 1]}),
                        "num_experts"),
                       (dict(TOY, experts_held={"ids": [0, 1, 2, 3, 4, 16]}),
                        "distinct ids"),
                       (dict(TOY, num_attention_heads_per_layer=[
                           4, 6, 4, 6, 4]), "same head count"),
                       (dict(TOY, mlp_layer_types=["dense"] * 5),
                        "expert layer")):
        path.write_text(json.dumps(bad))
        with pytest.raises(ValueError, match=match):
            sala.load_model_config(str(path))


def test_configuration_holds_the_catalogs_numbers():
    """Every number and group of the catalog row's `config` under its
    own key, the cut ones named in `reduced` with their published values
    beside them."""
    with open(os.path.join(CONFIGS, "laguna-s-2.1.json")) as f:
        cfg = json.load(f)
    assert cfg["source"] == \
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    layer_types = ["full_attention"] + ["sliding_attention"] * 3
    catalog = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": layer_types * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}
    differs = {k for k, v in catalog.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"}
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: catalog[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["router_width"]) == (5, 128, 50176, 256)
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == catalog[key][:5]
    assert cfg["experts_held"] == {"rank": 0, "of": 2,
                                   "ids": list(range(128))}
    assert "2 chips" in cfg["deployment"]
    assert (cfg["param_dtype"], cfg["compute_dtype"]) == ("bfloat16",
                                                          "bfloat16")
    assert cfg["reference"] == "laguna_reference.py"
    for key in ("softmax_scoring", "shared_expert_gate", "per_head_gate",
                "window", "rope_pairing", "qk_norm", "weights", "traffic"):
        assert isinstance(cfg["assumed"][key], str) and cfg["assumed"][key]


def test_flop_model_sums_are_the_sum_of_their_steps():
    model = laguna.LagunaFlopModel(CFG32, 230)
    assert model.M == 256
    assert model.steps_useful_sum(3, 20) == sum(
        model.step_useful_flops(p) for p in range(3, 23))
    # A window layer counts min(pos + 1, 8) keys, a full layer pos + 1.
    key_f, key_w = 4 * 32 * 4 * 2, 4 * 32 * 6 * 3
    assert model.step_useful_flops(100) - model.step_useful_flops(99) \
        == key_f
    assert model.step_useful_flops(5) - model.step_useful_flops(4) \
        == key_f + key_w
    assert model.chunk_useful_flops(64, 64, True) \
        - model.chunk_useful_flops(64, 64, False) == model._logit
    assert model.chunk_useful_flops(0, 30, False) == sum(
        model.step_useful_flops(p) - model._logit for p in range(30))
    assert model.step_flops() >= model.step_useful_flops(255)
    for size in (23, 64, 136):
        assert model.chunk_flops(size) >= model.chunk_useful_flops(
            256 - size, size, True)
    assert model.prefill_chunks_flops(0, 200, 136) \
        == model.chunk_flops(136) + model.chunk_flops(64)
    # Useful routed work is the pairs sent to experts held here (4 of a
    # token's choices x 6 of 16 experts), never every held expert.
    assert model._routed(16) == 4 * model._expert * 16 * 4 * 6 // 16


# ------------------------------------------------------- the entry point

def test_load_model_config_lives_with_the_protocol():
    """Every family's loader is models/slot_model.py's; the benchmark's
    drivers import it from models/sala.py, where it stays."""
    assert sala.load_model_config is slot_model.load_model_config
    assert set(slot_model.FAMILIES) == {"minicpm_sala", "phi4flash",
                                        "kimi_k2", "laguna"}
    cfg = slot_model.load_model_config(
        os.path.join(CONFIGS, "rehearsal-laguna-tiny.json"))
    assert cfg == CFG16
    assert cfg.slot_model().recurrent
    assert len(slot_kernels(cfg, 0.0, None, None)) == 3


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
            os.path.join(CONFIGS, "rehearsal-laguna-tiny.json"),
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


def test_init_params_draws_an_expert_from_its_id_alone():
    """The program's own seeded weights (`tdn lm --model-config`): rank
    1's experts are the uncut model's experts 8..15."""
    base = dict(TOY, param_dtype="float32", num_experts=16,
                experts_held={"ids": list(range(16))})
    key = jax.random.key(5)
    whole = laguna.LagunaConfig.from_dict(base).init_params(key)
    half = laguna.LagunaConfig.from_dict(dict(
        base, num_experts=8, experts_held={"ids": list(range(8, 16))})
    ).init_params(key)
    np.testing.assert_array_equal(np.asarray(half["moe"]["ex_d"]),
                                  np.asarray(whole["moe"]["ex_d"])[:, 8:])
    np.testing.assert_array_equal(np.asarray(half["window"]["w_q"]),
                                  np.asarray(whole["window"]["w_q"]))


# ------------------------------- (v) what the sharing left as it was

def _ops(lowered) -> dict:
    """Operations by name in a lowered program."""
    return dict(collections.Counter(re.findall(
        r"= \"?([a-z_]+\.[a-z_.]+)", lowered.as_text())))


def _digest(ops: dict) -> str:
    return hashlib.sha256(json.dumps(sorted(ops.items())).encode()
                          ).hexdigest()[:16]


def _kimi_programs():
    with open(os.path.join(CONFIGS, "rehearsal-kimi-tiny.json")) as f:
        cfg = mla_moe.MlaMoeConfig.from_dict(json.load(f))
    p = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: mla_moe.init_slot_cache(cfg, 3, 256))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    v3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    b3 = jax.ShapeDtypeStruct((3,), jnp.bool_)

    def chunk(C):
        return jax.jit(lambda p, c, s, t, st: mla_moe.prefill_chunk_into_cache(
            p, cfg, c, s, t, st)).lower(
                p, cache, i32, jax.ShapeDtypeStruct((1, C), jnp.int32), i32)

    return {
        "chunk_16": lambda: chunk(16), "chunk_136": lambda: chunk(136),
        "step": lambda: jax.jit(lambda p, c, pos, t, a: mla_moe.
                                decode_step_slots(p, c, pos, t, cfg,
                                                  active=a)).lower(
            p, cache, v3, v3, b3)}


# (operations, digest of their counts by name) of each program at the
# parent commit of the PR that moved the expert layer (PR 37): the same
# operations, whatever file they are written in.
KIMI_PARENT = {"chunk_16": (1057, "d05415e83ced1c64"),
               "chunk_136": (1216, "0178b40165b5280a"),
               "step": (1293, "0f0bf7a24a561108")}


@pytest.mark.parametrize("program", sorted(KIMI_PARENT))
def test_kimis_programs_are_the_parents_operation_for_operation(program):
    lowered = _kimi_programs()[program]()
    ops = _ops(lowered)
    assert (sum(ops.values()), _digest(ops)) == KIMI_PARENT[program], ops
    scopes = set(re.findall(r"(mla_moe\.[a-z_.]+?)/",
                            lowered.as_text(debug_info=True)))
    want = {"mla_moe.attn.project", "mla_moe.router", "mla_moe.experts",
            "mla_moe.shared", "mla_moe.mlp",
            "mla_moe.attn.latent" if program == "step"
            else "mla_moe.attn.expand"}
    assert scopes == want
    assert not re.search(r"laguna\.(attn|router|experts|shared|mlp)",
                         lowered.as_text(debug_info=True))


SAMBAY_PARENT = {"chunk": (2520, "43617b382a0c889d"),
                 "body": (1740, "d9ca704e164b5552"),
                 "step": (2878, "cb9e21880fa76da1")}


def test_sambays_programs_and_rehearsal_are_the_parents():
    """The ring helpers Laguna's window layers share (`_ring_lane`,
    `_ring_visible`, `_ring_after_chunk`) leave phi4-mini-flash's
    programs as they were, and its rehearsal's prefill and decode give
    the parent's logits bit for bit (a digest of the float32 bytes,
    taken at the parent on this CPU)."""
    ref_flash = lookup.load_module(
        os.path.join(CONFIGS, "phi4_flash_reference.py"), "phi4_ref_pin")
    with open(os.path.join(CONFIGS, "rehearsal-phi4flash-tiny.json")) as f:
        toy = json.load(f)
    cfg = sambay.SambaYConfig.from_dict(toy)
    p = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: sambay.init_slot_cache(cfg, 3, 96))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    v3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    b3 = jax.ShapeDtypeStruct((3,), jnp.bool_)
    tok = jax.ShapeDtypeStruct((1, 13), jnp.int32)
    lowered = {
        "chunk": jax.jit(lambda p, c, s, t, st: sambay.prefill_chunk_into_cache(
            p, cfg, c, s, t, st)).lower(p, cache, i32, tok, i32),
        "body": jax.jit(lambda p, c, s, t, st: sambay.prefill_body_into_cache(
            p, cfg, c, s, t, st)).lower(p, cache, i32, tok, i32),
        "step": jax.jit(lambda p, c, pos, t, a: sambay.decode_step_slots(
            p, c, pos, t, cfg, active=a)).lower(p, cache, v3, v3, b3)}
    for name, lo in lowered.items():
        ops = _ops(lo)
        assert (sum(ops.values()), _digest(ops)) == SAMBAY_PARENT[name], name

    params = cfg.cast_params(ref_flash.make_weights(toy, 3, "float32"))
    rows = np.random.default_rng(1).integers(0, 512, (3, 80))
    pre = jax.jit(lambda c, s, t, st: sambay.prefill_chunk_into_cache(
        params, cfg, c, s, t, st))
    step = jax.jit(lambda c, pos, t, a: sambay.decode_step_slots(
        params, c, pos, t, cfg, active=a))
    c = sambay.init_slot_cache(cfg, 3, 80)
    h = hashlib.sha256()
    for s in range(3):
        for at in range(0, 50, 13):
            n = min(13, 50 - at)
            logits, c = pre(c, s, jnp.asarray(rows[s:s + 1, at:at + n]), at)
        h.update(np.asarray(logits, np.float32).tobytes())
    for t in range(50, 79):
        logits, c = step(c, jnp.full((3,), t), jnp.asarray(rows[:, t]),
                         jnp.ones((3,), bool))
        h.update(np.asarray(logits, np.float32).tobytes())
    assert h.hexdigest() == SAMBAY_REHEARSAL_DIGEST


SAMBAY_REHEARSAL_DIGEST = \
    "9b8069f3d0fac73e8b90f8335918a2a8b5b14d6488e144e22d45ac7498731e59"
