"""Phi-4-mini-flash's block family (models/sambay.py) against its plain
reference (benchmark/configs/phi4_flash_reference.py) at a toy size of
the same family: every kind of layer (two (mamba, window) pairs, the
last mamba layer, the full layer, one (gmu, cross) pair), a window of 16
shorter than any prompt here.  Logits are compared, never tokens.

Tolerances.  With float32 parameters at matmul precision `highest` (the
suite's default) program and reference compute the same mathematics in
another order: 1e-4 on logits that spread by one covers the float32
reordering (a blocked scan against one a position, a ring in lane order
against a banded mask, one softmax over [cache | own key] against one
over a row of the mask; measured 1.4e-5).  With bfloat16 parameters the
program rounds every activation to 8 bits of mantissa: the bound is on
the RMS error of the logits, 0.07, twice what the program reads (0.037
forward, 0.037 through the cache) and what the reference's own bfloat16
emulation reads (0.033), half of what its int8 emulation reads (0.125)
and a sixth of its fp8 emulation's (0.40).  What tells a precision from
another end to end is the served-gap comparison at the end, by the limit
the benchmark's rehearsal uses.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import lookup
from tpu_dist_nn.models import sala, sambay
from tpu_dist_nn.serving.continuous import (
    ContinuousScheduler,
    slot_body_kernel,
    slot_kernels,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
ref = lookup.load_module(
    os.path.join(CONFIGS, "phi4_flash_reference.py"), "phi4_flash_reference")

with open(os.path.join(CONFIGS, "rehearsal-phi4flash-tiny.json")) as f:
    TOY = json.load(f)
CFG32 = sambay.SambaYConfig.from_dict(dict(TOY, param_dtype="float32"))
CFG16 = sambay.SambaYConfig.from_dict(TOY)
T, N, S, W = 50, 30, 3, 16  # prompt, new tokens, slots, the window


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(TOY, 3, "float32")


@pytest.fixture(scope="module")
def rows():
    return np.random.default_rng(1).integers(0, 512, (S, T + N))


@pytest.fixture(scope="module")
def full(weights, rows):
    return np.asarray(ref.logits(weights, rows, TOY))


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a))))


@pytest.fixture(scope="module")
def programs(weights):
    """(chunk with logits, chunk without, step) of each type, jitted
    once for the whole file."""
    made = {}

    def get(cfg):
        if cfg not in made:
            made[cfg] = _programs(cfg.cast_params(weights), cfg)
        return made[cfg]

    return get


def _programs(params, cfg):
    pre = jax.jit(lambda c, slot, t, st: sambay.prefill_chunk_into_cache(
        params, cfg, c, slot, t, st))
    body = jax.jit(lambda c, slot, t, st: (None, sambay.prefill_body_into_cache(
        params, cfg, c, slot, t, st)))
    step = jax.jit(lambda c, pos, tok, act: sambay.decode_step_slots(
        params, c, pos, tok, cfg, active=act))
    return pre, body, step


def _prefill(pre, cache, slot, tokens, chunk, start=0, body=None):
    """Chunks of `tokens[start:]` into `slot`; with `body`, every chunk
    but the last through the program that ends without logits."""
    at, logits = start, None
    while at < len(tokens):
        c = min(chunk, len(tokens) - at)
        run = body if body is not None and at + c < len(tokens) else pre
        logits, cache = run(cache, slot, jnp.asarray(tokens[None, at:at + c]),
                            at)
        at += c
    return logits, cache


# ------------------------------------------------------------ (i) forward

def test_forward_matches_reference(weights, rows, full):
    got = np.asarray(sambay.forward(weights, jnp.asarray(rows[:2]), CFG32))
    assert full.std() > 0.5  # logits that spread ...
    # ... and the context, not the last token, decides the next one.
    assert (full.argmax(-1) == rows).mean() < 0.1
    np.testing.assert_allclose(got, full[:2], atol=1e-4)


def test_forward_bf16_within_its_rounding(weights, rows, full):
    params = CFG16.cast_params(weights)
    got = np.asarray(sambay.forward(params, jnp.asarray(rows[:1]), CFG16))
    assert _rms(got - full[:1]) < 0.07
    low = np.asarray(ref.logits(weights, rows[:1], TOY, "fp8"))
    assert _rms(low - full[:1]) > 0.2  # the control reads well above


# ------------------------------------- (ii) chunked prefill, then decode

@pytest.mark.parametrize("chunk", [32, 13, 7])
def test_prefill_then_decode_matches_full_forward(programs, rows, full, chunk):
    """Slots prefilled to different lengths at several chunk lengths (32
    is longer than the window of 16, 13 shorter and no divisor of a
    prompt, 7 shorter than half of it; the whole prompt as one chunk is
    `forward`'s case above), all but a prompt's last chunk by the body
    program, then decoded together past the rings' wrap (every slot
    starts past position 16 and decodes 30 more), one of them joining
    late: every logit row against the reference's one full forward."""
    pre, body, step = programs(CFG32)
    cache = sambay.init_slot_cache(CFG32, S + 1, T + N - 1)
    pos = np.zeros(S, np.int32)
    for s in range(S):
        n = T - 10 * s
        logits, cache = _prefill(pre, cache, s, rows[s, :n], chunk, body=body)
        np.testing.assert_allclose(np.asarray(logits)[0], full[s, n - 1],
                                   atol=1e-4)
        pos[s] = n
    active = np.array([True, True, False])
    for i in range(N - 1):
        tok = np.array([rows[s, pos[s]] for s in range(S)], np.int32)
        logits, cache = step(cache, jnp.asarray(pos), jnp.asarray(tok),
                             jnp.asarray(active))
        for s in np.flatnonzero(active):
            np.testing.assert_allclose(np.asarray(logits)[s],
                                       full[s, pos[s]], atol=1e-4)
            pos[s] += 1
        active[2] |= i == 10


def test_prefill_then_decode_bf16(programs, rows, full):
    pre, body, step = programs(CFG16)
    cache = sambay.init_slot_cache(CFG16, 1, T + N - 1)
    logits, cache = _prefill(pre, cache, 0, rows[0, :T], 13, body=body)
    errors = [np.asarray(logits)[0] - full[0, T - 1]]
    for p in range(T, T + 20):
        logits, cache = step(cache, jnp.asarray([p]),
                             jnp.asarray(rows[0, p:p + 1], jnp.int32),
                             jnp.asarray([True]))
        errors.append(np.asarray(logits)[0] - full[0, p])
    assert _rms(np.stack(errors)) < 0.07


@pytest.mark.parametrize("cfg", [CFG32, CFG16], ids=["f32", "bf16"])
def test_body_program_leaves_the_cache_the_logits_program_leaves(
        programs, rows, cfg):
    """Every leaf, from a first chunk and from chunks that start inside
    a prompt: what a later chunk, a tier's copy or a resume finds does
    not depend on which of the two programs ran.  Bit for bit in
    bfloat16, the served type.  In float32 to 1e-6: they are two
    compiled programs, and the compiler orders one float32 sum
    differently in them (seen: 4e-7 on 31 of 8192 entries of the full
    layer's K, for the chunk of 5 positions alone)."""
    pre, body, _ = programs(cfg)
    a = b = sambay.init_slot_cache(cfg, 2, T + N - 1)
    for start, size in ((0, 13), (13, 20), (33, 5)):
        tokens = jnp.asarray(rows[0, None, start:start + size])
        _, a = pre(a, 1, tokens, start)
        _, b = body(b, 1, tokens, start)
        for name in a:
            got = np.asarray(a[name].astype(jnp.float32))
            want = np.asarray(b[name].astype(jnp.float32))
            if cfg is CFG16:
                assert (got == want).all(), name
            else:
                np.testing.assert_allclose(got, want, atol=1e-6, err_msg=name)
    assert np.asarray(a["wk"].astype(jnp.float32))[:, 1].any()
    assert not np.asarray(a["k"].astype(jnp.float32))[:, 0].any()


# ------------------------------------------- (iii) a slot copied at a boundary

@pytest.mark.parametrize("cfg", [CFG32, CFG16], ids=["f32", "bf16"])
def test_slot_copied_at_chunk_boundary_resumes_bit_identically(
        programs, rows, cfg):
    """The prefix pool's and preemption's contract: copy a slot where a
    chunk ended, go on in the copy, and every later logit and every
    leaf of the cache is bit for bit what the original gives."""
    pre, body, step = programs(cfg)
    copy = jax.jit(sambay.copy_cache_slot)
    cache = sambay.init_slot_cache(cfg, 3, T + N - 1)
    _, cache = _prefill(body, cache, 0, rows[0, :26], 13)
    cache = copy(cache, 0, 2)
    la, cache = _prefill(pre, cache, 0, rows[0, :T], 13, start=26, body=body)
    lb, cache = _prefill(pre, cache, 2, rows[0, :T], 13, start=26, body=body)
    assert (np.asarray(la) == np.asarray(lb)).all()
    for p in range(T, T + 5):
        tok = jnp.asarray([rows[0, p]] * 3, jnp.int32)
        logits, cache = step(cache, jnp.asarray([p, 0, p]), tok,
                             jnp.asarray([True, False, True]))
        assert (np.asarray(logits)[0] == np.asarray(logits)[2]).all()
    for name, leaf in cache.items():
        leaf = np.asarray(leaf.astype(jnp.float32))
        assert (leaf[:, 0] == leaf[:, 2]).all(), name
        assert not leaf[:, 1].any(), name  # the idle slot stayed untouched


# ----------------------------------- (iv) retiring and binding leaves nothing

def test_rebinding_a_slot_leaves_no_state_behind(programs, rows):
    """Neither the scan's state, nor a convolution's inputs, nor a ring
    row is masked out by `pos` the way a stale row of the full layer's
    K/V is: a chunk at start 0 must start from none of them, and a ring
    lane the new occupant has not reached must stay unseen."""
    pre, body, step = programs(CFG32)
    used = sambay.init_slot_cache(CFG32, 1, T + N - 1)
    _, used = _prefill(pre, used, 0, rows[1, :T], 13, body=body)
    for p in range(T, T + 8):
        _, used = step(used, jnp.asarray([p]),
                       jnp.asarray(rows[1, p:p + 1], jnp.int32),
                       jnp.asarray([True]))
    fresh = sambay.init_slot_cache(CFG32, 1, T + N - 1)
    # Ten positions: shorter than the window, so six ring lanes still
    # hold the last occupant's rows.
    la, used = _prefill(pre, used, 0, rows[2, :10], 7, body=body)
    lb, fresh = _prefill(pre, fresh, 0, rows[2, :10], 7, body=body)
    assert (np.asarray(la) == np.asarray(lb)).all()
    for name in ("state", "conv"):
        assert (np.asarray(used[name]) == np.asarray(fresh[name])).all(), name
    assert not (np.asarray(used["wk"]) == np.asarray(fresh["wk"])).all()
    for p in range(10, 30):
        tok = jnp.asarray(rows[2, p:p + 1], jnp.int32)
        la, used = step(used, jnp.asarray([p]), tok, jnp.asarray([True]))
        lb, fresh = step(fresh, jnp.asarray([p]), tok, jnp.asarray([True]))
        assert (np.asarray(la) == np.asarray(lb)).all()


def test_inactive_slots_cache_is_not_written(programs, rows):
    _, _, step = programs(CFG32)
    cache = jax.tree.map(
        lambda a: jnp.asarray(np.random.default_rng(0).normal(
            size=a.shape), a.dtype), sambay.init_slot_cache(CFG32, 2, 96))
    _, after = step(cache, jnp.asarray([70, 71]),
                    jnp.asarray(rows[:2, 0], jnp.int32),
                    jnp.asarray([False, True]))
    for name in cache:
        assert (np.asarray(after[name])[:, 0]
                == np.asarray(cache[name])[:, 0]).all(), name
        assert not (np.asarray(after[name])[:, 1]
                    == np.asarray(cache[name])[:, 1]).all(), name


# ------------------------------------------- (v) the scheduler, end to end

def _drain(stream, at_least=None):
    """Tokens of a TokenStream: all of them, or the first `at_least`
    or more as they come."""
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
                        prefill_chunk=13)
    return prompts, budgets, out, sched


def test_scheduler_streams_complete_with_lengths_as_asked(served):
    _, budgets, out, sched = served
    assert [len(o) for o in out] == budgets
    # Four chunks a prompt (13, 13, 13, 11): three end without logits.
    assert sched.retired_total == 3 and sched.prefill_chunks_total == 12
    assert sched.prefill_body_chunks_total == 9
    assert all(0 <= t < 512 for o in out for t in o)


def test_scheduler_books_cache_bytes_by_kind(served):
    sched = served[3]
    kinds = sched.cache_bytes
    assert set(kinds) == {"kv", "window", "state"}
    # 2 slots; G 4 x d 8; extent 50 + 24 - 1 -> 128; E 128, N 16, K 4.
    assert kinds["kv"] == 2 * 1 * 2 * 4 * 8 * 128 * 2
    assert kinds["window"] == 2 * 2 * 2 * 4 * 8 * 16 * 2
    assert kinds["state"] == 3 * 2 * (16 * 128 * 4 + 3 * 128 * 2)
    assert sched.sparse_positions_total == 0  # no block selection here


def test_scheduler_serves_what_the_reference_ranks_first(weights, served):
    """Served tokens against the reference's full forward: the mean
    distance of the served token's reference logit below the best, by
    the benchmark's comparison, and the controls by the same limit."""
    prompts, budgets, out, _ = served
    with open(os.path.join(ROOT, "benchmark", "cells",
                           "rehearsal-phi4flash-tiny.reason-decode.json")) as f:
        limit = json.load(f)["limits"]["served_logit_gap_mean"]
    width = T + max(budgets)
    means = {}
    for quant in (None, "bf16", "fp8"):
        rows_ = np.zeros((len(out), width), np.int64)
        for i, (p, o) in enumerate(zip(prompts, out)):
            rows_[i, :T], rows_[i, T:T + len(o)] = p, o
        got = ref.served_gaps(weights, rows_, TOY, T, quant)
        gaps = got["gap_served" if quant is None else "gap_control"]
        means[quant] = float(np.concatenate(
            [g[:len(o)] for g, o in zip(gaps, out)]).mean())
    assert means[None] <= limit and means["bf16"] <= limit < means["fp8"], means


def test_prefix_pool_needs_chunks_for_recurrent_state(weights):
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                            max_new_tokens=4, prefix_cache_blocks=2)


def test_prefix_pool_hit_resumes_from_the_tier_that_a_chunk_ended(
        weights, rows):
    """With the pool on, a second request that shares 26 positions takes
    the tier a body chunk ended at and streams what it streams with the
    pool off."""
    prompts = np.stack([rows[0, :T], np.concatenate(
        [rows[0, :26], rows[1, 26:T]])])
    plain, _ = _serve(weights, CFG32, prompts, [6, 6], prefill_chunk=13)
    sched = ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                                max_new_tokens=24, prefill_chunk=13,
                                prefix_cache_blocks=4)
    try:
        first = _drain(sched.submit_stream(prompts[:1], max_new_tokens=6))
        second = _drain(sched.submit_stream(prompts[1:], max_new_tokens=6))
        assert sched.prefix_hits_total == 1
        assert sched.prefill_chunks_total == 4 + 2
        assert [first, second] == plain
    finally:
        sched.close()


def test_preempted_row_resumes_its_stream(weights, rows):
    """Preemption's resume re-prefills the prompt with the body program
    alone (its first token is known: nobody reads the last chunk's),
    then replays the forced tokens through the step."""
    prompts = rows[:, :T]
    plain, _ = _serve(weights, CFG32, prompts[:1], [10], prefill_chunk=13)
    sched = ContinuousScheduler(weights, CFG32, slots=1, prompt_len=T,
                                max_new_tokens=24, prefill_chunk=13)
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
        # 3 prompts' worth of chunks; the resume's four all without logits.
        assert sched.prefill_chunks_total == 12
        assert sched.prefill_body_chunks_total == 3 + 3 + 4
    finally:
        sched.close()


def test_streams_get_the_serial_orders_tokens(weights, rows, served):
    """This family through the loop that launches step N+1 before it
    reads step N: state, rings and convolution inputs of a lane computed
    for nobody are never a later occupant's."""
    prompts, budgets, out, _ = served
    runs = []
    for hold in (True, False):
        sched = ContinuousScheduler(
            CFG16.cast_params(weights), CFG16, slots=2, prompt_len=T,
            max_new_tokens=24, prefill_chunk=13, eos_id=int(out[0][5]))
        if hold:
            sched.launch_hook = lambda tok, s=sched: s._land()
        try:
            streams = [sched.submit_stream(p[None], max_new_tokens=24)
                       for p in prompts]
            runs.append(([_drain(s) for s in streams], sched))
        finally:
            sched.close()
    (serial, held), (ahead, sched) = runs
    assert ahead == serial
    assert ahead[0] == out[0][:out[0].index(out[0][5]) + 1]
    assert held.overlapped_total == 0 and sched.overlapped_total > 0
    assert sched.discarded_lanes_total >= 1


# --------------------------------------------------- config, loader, counts

def test_loader_reads_the_benchmarks_configuration():
    cfg = sala.load_model_config(os.path.join(CONFIGS, "phi4-mini-flash.json"))
    assert isinstance(cfg, sambay.SambaYConfig)
    kinds = cfg.layer_kinds
    assert kinds[:18:2] == ("mamba",) * 9 and kinds[1:16:2] == ("window",) * 8
    assert kinds[17] == "full"
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7
    assert (cfg.head_dim, cfg.d_inner, cfg.dt_rank, cfg.d_state, cfg.d_conv) \
        == (64, 5120, 160, 16, 4)
    # By hand (ISSUE 31's table, M parameters): 32 MLPs 2516.6, 9 mamba
    # 371.2, 9 attention 177.0, 7 gmu 183.5, 7 cross 91.8, the tied
    # embedding 512.2: 3852 M, and LayerNorms and lambdas on top.
    mlp = 2560 * 20480 + 10240 * 2560
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 4 * 5120 + 5120 \
        + 5120 + 16 * 5120 + 5120 + 5120 * 2560
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560
    cross = 2 * (2560 * 2560 + 2560)
    small = 32 * 4 * 2560 + 2 * 2560 + 16 * (4 * 64 + 128)
    by_hand = 32 * mlp + 9 * mamba + 9 * attn + 7 * 2 * 2560 * 5120 \
        + 7 * cross + 200064 * 2560 + small
    assert cfg.num_params() == by_hand
    assert abs(by_hand - 3852e6) < 1e6
    lam0 = cfg.lambda_init()
    np.testing.assert_allclose(lam0["attn"][-1], 0.8 - 0.6 * np.exp(-5.1),
                               rtol=1e-6)
    assert lam0["attn"].shape == (9,) and lam0["cross"].shape == (7,)


def test_loader_names_both_known_types(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"model_type": "gpt2"}))
    with pytest.raises(ValueError, match="minicpm_sala.*phi4flash"):
        sala.load_model_config(str(path))
    bad = dict(TOY, published={"layer_kinds": ["mamba"] * 8})
    path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="layer_kinds"):
        sala.load_model_config(str(path))


def test_configuration_holds_the_catalogs_numbers():
    with open(os.path.join(CONFIGS, "phi4-mini-flash.json")) as f:
        cfg = json.load(f)
    assert cfg["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert cfg["reduced"] == []
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cfg[k] for k in catalog} == catalog
    assert len(cfg["published"]["layer_kinds"]) == 32
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())


def test_flop_model_sums_are_the_sum_of_their_steps():
    model = sambay.SambaYFlopModel(CFG32, 100)
    assert model.M == 128
    assert model.steps_useful_sum(10, 20) == sum(
        model.step_useful_flops(p) for p in range(10, 30))
    # A prompt's useful work: the body over its positions, the tail and
    # the head once.
    tail = model.step_useful_flops(45) - model.chunk_useful_flops(45, 1, False)
    assert model.chunk_useful_flops(13, 33, True) == sum(
        model.chunk_useful_flops(p, 1, False) for p in range(13, 46)) + tail
    assert tail == model._tail + model._full_key * 46 + model._logit
    # Window keys stop growing at the window.
    assert model.step_useful_flops(40) - model.step_useful_flops(39) \
        == model._full_key
    assert model.step_useful_flops(10) - model.step_useful_flops(9) \
        == model._full_key + model._win_key
    assert model.chunk_flops(13) - model.body_flops(13) \
        == model._tail + model._full_key * 128 + model._logit
    assert model.prefill_chunks_flops(0, 26, 13) == 2 * model.body_flops(13)
    assert model.step_flops() >= model.step_useful_flops(127)
    assert model.chunk_flops(13) >= model.chunk_useful_flops(30, 13, True)


def test_other_families_hand_over_no_body_program():
    from tpu_dist_nn.models.transformer import TransformerConfig

    with open(os.path.join(CONFIGS, "rehearsal-sala-tiny.json")) as f:
        sala_cfg = sala.SalaConfig.from_dict(json.load(f))
    for cfg in (TransformerConfig(), sala_cfg):
        assert cfg.slot_model().prefill_body_into_cache is None
        assert slot_body_kernel(cfg) is None
        assert len(slot_kernels(cfg, 0.0, None, None)) == 3
    assert slot_body_kernel(CFG16) is not None
    assert len(slot_kernels(CFG16, 0.0, None, None)) == 3


# ------------------ the shared K/V through kernels/decode_attend.py

# Heads of 16: a whole bfloat16 sublane tile, so the served type tiles
# too.  CFG32 (heads of 8, float32) tiles, CFG16 (heads of 8, bfloat16)
# does not: every test above with CFG32 already runs the kernel.
WIDE = dict(TOY, hidden_size=128)
WIDE16 = sambay.SambaYConfig.from_dict(WIDE)


@pytest.mark.parametrize("cfg, tiled", [
    (CFG32, True), (CFG16, False), (WIDE16, True)],
    ids=["f32-heads-of-8", "bf16-heads-of-8", "bf16-heads-of-16"])
def test_the_shapes_alone_decide_how_the_tail_reads_the_cache(cfg, tiled):
    """What `SlotModel.step_kv_tiles` says is what the step and the
    final chunk hold: a `pallas_call` named decode_attend where the
    shapes tile, `_attend_rows` over the whole extent elsewhere."""
    assert (cfg.slot_model().step_kv_tiles(S, T + N - 1) is not None) is tiled
    params = jax.eval_shape(lambda: sambay.init_sambay(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: sambay.init_slot_cache(cfg, S + 1, T + N - 1))
    ints = jax.ShapeDtypeStruct((S,), jnp.int32)
    step = str(jax.make_jaxpr(lambda p, c, pos, tok: sambay.decode_step_slots(
        p, c, pos, tok, cfg))(params, cache, ints, ints))
    chunk = str(jax.make_jaxpr(
        lambda p, c, tok, st: sambay.prefill_chunk_into_cache(
            p, cfg, c, 1, tok, st))(
        params, cache, jax.ShapeDtypeStruct((1, 13), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
    for text in (step, chunk):
        assert ("decode_attend" in text) is tiled


@pytest.mark.parametrize("wide", [False, True], ids=["f32", "bf16"])
def test_kernel_and_xla_paths_leave_the_same_cache_and_tokens(
        monkeypatch, weights, rows, wide):
    """Three slots prefilled in chunks (the final chunk's tail is the
    kernel at one slot) and decoded for 24 steps, one of them joining
    late, with the kernel and with the dispatch forced to
    `_attend_rows`: every leaf of the cache bit for bit and logits
    within the file's tolerances.  In float32 the decode is greedy and
    the tokens are the same; in bfloat16, where `_attend_rows` rounds
    `a` to 8 bits and the kernel does not, a near-tie among 512 logits
    may fall either way (seen: 1 of 65), so the tokens are given and
    the greedy choices agree but for such ties."""
    from tpu_dist_nn.kernels import decode_attend

    cfg = WIDE16 if wide else CFG32
    params = cfg.cast_params(
        ref.make_weights(WIDE, 3, "float32") if wide else weights)

    def run():
        pre, body, step = _programs(params, cfg)
        cache = sambay.init_slot_cache(cfg, S + 1, T + N - 1)
        pos, tok, seen = np.zeros(S, np.int32), np.zeros(S, np.int32), []
        for s in range(S):
            n = T - 10 * s
            logits, cache = _prefill(pre, cache, s, rows[s, :n], 13,
                                     body=body)
            seen.append(np.asarray(logits, np.float32)[0])
            pos[s], tok[s] = n, int(np.argmax(seen[-1]))
        active, toks = np.array([True, True, False]), []
        for i in range(24):
            logits, cache = step(cache, jnp.asarray(pos), jnp.asarray(tok),
                                 jnp.asarray(active))
            logits = np.asarray(logits, np.float32)
            seen.extend(logits[active])
            toks.extend(logits.argmax(-1)[active].tolist())
            pos += active
            given = rows[np.arange(S), pos] if wide else logits.argmax(-1)
            tok = np.where(active, given, tok).astype(np.int32)
            active[2] |= i == 6
        return toks, np.stack(seen), cache

    assert cfg.slot_model().step_kv_tiles(S, T + N - 1) is not None
    got_toks, got, got_cache = run()
    monkeypatch.setattr(decode_attend, "tiles", lambda *a: None)
    assert cfg.slot_model().step_kv_tiles(S, T + N - 1) is None
    want_toks, want, want_cache = run()
    agree = np.mean(np.asarray(got_toks) == np.asarray(want_toks))
    assert agree >= 0.95 if wide else agree == 1.0
    for name in want_cache:
        assert (np.asarray(got_cache[name].astype(jnp.float32))
                == np.asarray(want_cache[name].astype(jnp.float32))).all(), name
    assert want.std() > 0.5
    if wide:
        assert _rms(got - want) < 0.02
    else:
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_scheduler_counts_the_kv_tiles_its_steps_copy_and_skip(weights, rows):
    """Two slots of extent 256 (two 128-lane tiles) decoding from
    position 50: a step copies the first tile of each and skips the
    second; a slot the step does not decode is read as at position 0.
    Fetched and skipped add up to slots x tiles a step, on the scheduler
    and on /metrics."""
    from tpu_dist_nn.obs.registry import Registry
    from tpu_dist_nn.obs.runtime import RuntimeSampler

    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    sched = ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                                max_new_tokens=150, prefill_chunk=25)
    sampler.add_generation_scheduler(sched)
    try:
        streams = [sched.submit_stream(rows[i:i + 1, :T], max_new_tokens=b)
                   for i, b in enumerate((5, 3))]
        assert [len(_drain(s)) for s in streams] == [5, 3]
        sampler.sample_once()
    finally:
        sched.close()
    visited, skipped = (sched.step_kv_tiles_visited_total,
                        sched.step_kv_tiles_skipped_total)
    assert sched.steps_total >= 4
    assert visited + skipped == sched.steps_total * 2 * 2
    assert visited == skipped  # nobody got past position 128
    scraped = {m.name: {k: c.value for k, c in m.samples()}
               for m in reg.collect()}["tdn_gen_step_kv_tiles_total"]
    assert scraped == {("visited",): visited, ("skipped",): skipped}


def test_a_step_that_reads_the_whole_extent_counts_no_tiles(served):
    """CFG16's heads of 8 do not tile; GPT-2 and SALA have no such
    kernel: the scheduler asks once and does no work for them."""
    from tpu_dist_nn.models.transformer import TransformerConfig

    sched = served[3]
    assert sched._kv_tiles is None and sched.steps_total > 0
    assert sched.step_kv_tiles_visited_total == 0
    assert sched.step_kv_tiles_skipped_total == 0
    with open(os.path.join(CONFIGS, "rehearsal-sala-tiny.json")) as f:
        sala_cfg = sala.SalaConfig.from_dict(json.load(f))
    for cfg in (TransformerConfig(), sala_cfg):
        assert cfg.slot_model().step_kv_tiles(8, 383) is None


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
            os.path.join(CONFIGS, "rehearsal-phi4flash-tiny.json"),
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
