"""MiniCPM-SALA's block family (models/sala.py) against its plain
reference (benchmark/configs/minicpm_sala_reference.py) at a toy size of
the same family: every mixer kind, group 4, and `dense_len`, blocks and
window scaled down so that the block selection runs within ~200
positions.  Logits are compared, never tokens.

Tolerances.  With float32 parameters at matmul precision `highest` (the
suite's default) program and reference compute the same mathematics in
another order: 1e-4 on logits that spread by one covers the float32
reordering (chunk-parallel against per-position recurrence, online
against one-pass softmax; measured 6e-6).  With bfloat16 parameters the
program rounds every activation to 8 bits of mantissa, and a rounding
that flips a block in or out of the top-k moves single logits by 0.2:
the bound is on the RMS error, 0.03, twice what the program reads
(0.0134) and what the reference's own bfloat16 emulation reads (0.0136),
a quarter of what its fp8 emulation reads (0.114).  What tells a
precision from another end to end is the served-gap comparison at the
end, by the limit the benchmark's rehearsal uses.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import lookup
from tpu_dist_nn.kernels import sparse_attend
from tpu_dist_nn.models import sala
from tpu_dist_nn.serving.continuous import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
ref = lookup.load_module(
    os.path.join(CONFIGS, "minicpm_sala_reference.py"), "sala_reference")

with open(os.path.join(CONFIGS, "rehearsal-sala-tiny.json")) as f:
    TOY = json.load(f)
CFG32 = sala.SalaConfig.from_dict(dict(TOY, param_dtype="float32"))
CFG16 = sala.SalaConfig.from_dict(TOY)
T, N, S = 150, 40, 3  # prompt, new tokens, slots; dense_len is 64


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


def _programs(params, cfg):
    pre = jax.jit(lambda c, slot, t, st: sala.prefill_chunk_into_cache(
        params, cfg, c, slot, t, st))
    step = jax.jit(lambda c, pos, tok, act: sala.decode_step_slots(
        params, c, pos, tok, cfg, active=act))
    return pre, step


def _prefill(pre, cache, slot, tokens, chunk, start=0):
    at, logits = start, None
    while at < len(tokens):
        c = min(chunk, len(tokens) - at)
        logits, cache = pre(cache, slot, jnp.asarray(tokens[None, at:at + c]),
                            at)
        at += c
    return logits, cache


# ------------------------------------------------------------ (i) forward

def test_forward_matches_reference(weights, rows, full):
    got = np.asarray(sala.forward(weights, jnp.asarray(rows[:2]), CFG32))
    assert full.std() > 0.5  # logits that spread: the context decides
    np.testing.assert_allclose(got, full[:2], atol=1e-4)


def test_forward_bf16_within_its_rounding(weights, rows, full):
    params = CFG16.cast_params(weights)
    got = np.asarray(sala.forward(params, jnp.asarray(rows[:1]), CFG16))
    assert _rms(got - full[:1]) < 0.03
    low = np.asarray(ref.logits(weights, rows[:1], TOY, "fp8"))
    assert _rms(low - full[:1]) > 0.06  # the control reads well above


# ------------------------------------- (ii) chunked prefill, then decode

@pytest.mark.parametrize("chunk", [T, 64, 37, 16])
def test_prefill_then_decode_matches_full_forward(weights, rows, full, chunk):
    """Slots prefilled to different lengths at several chunk lengths
    (37 is no multiple of the kernel stride or of a block), then
    decoded together, one of them joining late: every logit row against
    the reference's one full forward."""
    pre, step = _programs(weights, CFG32)
    cache = sala.init_slot_cache(CFG32, S + 1, T + N - 1)
    pos = np.zeros(S, np.int32)
    for s in range(S):
        n = T - 10 * s
        logits, cache = _prefill(pre, cache, s, rows[s, :n], chunk)
        np.testing.assert_allclose(np.asarray(logits)[0], full[s, n - 1],
                                   atol=1e-4)
        pos[s] = n
    active = np.array([True, True, False])
    for i in range(30):
        tok = np.array([rows[s, pos[s]] for s in range(S)], np.int32)
        logits, cache = step(cache, jnp.asarray(pos), jnp.asarray(tok),
                             jnp.asarray(active))
        for s in np.flatnonzero(active):
            np.testing.assert_allclose(np.asarray(logits)[s],
                                       full[s, pos[s]], atol=1e-4)
            pos[s] += 1
        active[2] |= i == 10


def test_prefill_then_decode_bf16(weights, rows, full):
    params = CFG16.cast_params(weights)
    pre, step = _programs(params, CFG16)
    cache = sala.init_slot_cache(CFG16, 1, T + N - 1)
    logits, cache = _prefill(pre, cache, 0, rows[0, :T], 64)
    errors = [np.asarray(logits)[0] - full[0, T - 1]]
    for p in range(T, T + 20):
        logits, cache = step(cache, jnp.asarray([p]),
                             jnp.asarray(rows[0, p:p + 1], jnp.int32),
                             jnp.asarray([True]))
        errors.append(np.asarray(logits)[0] - full[0, p])
    assert _rms(np.stack(errors)) < 0.03


# ---------------------------------------------------------- (iii) selection

def _qkv(seed=5, n=T + N, dim=16):
    """bfloat16-representable q, k, v as a sparse layer would hold them."""
    rng = np.random.default_rng(seed)
    cut = lambda a: np.asarray(  # noqa: E731
        jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    return (cut(rng.normal(size=(n, 8, dim))),
            cut(rng.normal(size=(n, 2, dim))),
            cut(rng.normal(size=(n, 2, dim))))


def _program_selection(q, k, n):
    """The program's compressed keys and selection for n positions, as
    one whole-prompt chunk leaves them."""
    M = -(-n // CFG32.block_size) * CFG32.block_size
    rows_k = jnp.zeros((2, 16, M), jnp.float32)
    ck, fresh = sala._new_compressed(rows_k, jnp.asarray(k), 0, CFG32,
                                     M // CFG32.kernel_stride)
    ck = jnp.where(fresh[None, None, :], ck, 0.0)
    s = jnp.einsum("cghd,gdj->cghj", jnp.asarray(q).reshape(n, 2, 4, 16),
                   ck) / np.sqrt(16)
    sel, scores = sala.select_blocks(s, jnp.arange(n), CFG32, M,
                                     return_scores=True)
    return np.asarray(sel), np.asarray(scores), M


def test_block_scores_and_selected_sets_match_reference():
    q, k, _ = _qkv()
    n = len(q)
    s = ref.sizes(TOY)
    got_sel, got_scores, M = _program_selection(q, k, n)
    kbar = ref.compressed_keys(jnp.asarray(k), s)
    want_sel, want_scores = ref.select(jnp.asarray(q), kbar, jnp.arange(n), s,
                                       M // s["blk"])
    want_sel, want_scores = np.asarray(want_sel), np.asarray(want_scores)
    finite = np.isfinite(want_scores)
    assert (np.isfinite(got_scores) == finite).all()
    tol = 1e-5  # float32 reordering of a softmax over <= 100 terms
    assert np.abs(got_scores[finite] - want_scores[finite]).max() < tol
    # Where the reference's margin at the last block taken exceeds the
    # tolerance, the two sets are the same set.
    ranked = np.sort(np.where(finite, want_scores, -np.inf), -1)[..., ::-1]
    k_th, nxt = ranked[..., s["topk"] - 1], ranked[..., s["topk"]]
    clear = ~np.isfinite(nxt) | (k_th - nxt > tol)
    assert clear.mean() > 0.9
    assert (got_sel[clear] == want_sel[clear]).all()
    sparse = np.arange(n) + 1 > s["dense_len"]
    assert sparse.sum() > 100 and not got_sel[sparse].all()  # it does select


# The toy, whose heads of 16 no TPU tile fits (the XLA loop), and the
# same family with heads of 128, blocks of 64 and an extent of three
# 128-lane tiles, where the chunk's attention is the Pallas kernel
# (kernels/sparse_attend.py; interpret mode here).
WIDE = dict(TOY, head_dim=128, sparse_config=dict(
    TOY["sparse_config"], kernel_size=32, kernel_stride=16, block_size=64,
    topk=2, window_size=128, dense_len=128))


@pytest.mark.parametrize("toy, n, kernel", [(TOY, T + N, False),
                                            (WIDE, 384, True)],
                         ids=["loop", "kernel"])
def test_attention_with_the_references_selection_forced(toy, n, kernel):
    q, k, v = _qkv(7, n, toy["head_dim"])
    s = ref.sizes(toy)
    cfg = sala.SalaConfig.from_dict(dict(toy, param_dtype="float32"))
    M = -(-n // s["blk"]) * s["blk"]
    kbar = ref.compressed_keys(jnp.asarray(k), s)
    sel, _ = ref.select(jnp.asarray(q), kbar, jnp.arange(n), s, M // s["blk"])
    want = np.asarray(ref.attend(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), sel, jnp.arange(n), s))
    assert not np.asarray(sel)[-1].all()  # the selection does select
    pad = ((0, 0), (0, 0), (0, M - n))
    rows = (jnp.asarray(q).reshape(n, 2, 4, s["Dh"]),
            jnp.pad(jnp.asarray(k).transpose(1, 2, 0), pad),
            jnp.pad(jnp.asarray(v).transpose(1, 2, 0), pad), sel)
    assert sala.attend_kernel_tiles(cfg, n, M) is kernel
    if kernel:
        got = sparse_attend.attend_chunk(*rows, 0, cfg.block_size)
    else:
        got = sala._attend_chunk(*rows, jnp.arange(n), cfg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


# ------------------------------------------- (iv) a slot copied at a boundary

@pytest.mark.parametrize("cfg", [CFG32, CFG16], ids=["f32", "bf16"])
def test_slot_copied_at_chunk_boundary_resumes_bit_identically(
        weights, rows, cfg):
    """The prefix pool's and preemption's contract: copy a slot where a
    chunk ended, go on in the copy, and every later logit and every
    leaf of the cache is bit for bit what the original gives."""
    params = cfg.cast_params(weights)
    pre, step = _programs(params, cfg)
    copy = jax.jit(sala.copy_cache_slot)
    cache = sala.init_slot_cache(cfg, 3, T + N - 1)
    _, cache = _prefill(pre, cache, 0, rows[0, :64], 32)
    cache = copy(cache, 0, 2)
    la, cache = _prefill(pre, cache, 0, rows[0, :T], 32, start=64)
    lb, cache = _prefill(pre, cache, 2, rows[0, :T], 32, start=64)
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


# ----------------------------------- (v) retiring and binding leaves nothing

def test_rebinding_a_slot_leaves_no_state_behind(weights, rows):
    """A lightning state is not masked out by `pos` the way stale K/V
    rows are: a chunk at start 0 must start from none."""
    pre, step = _programs(weights, CFG32)
    used = sala.init_slot_cache(CFG32, 1, T + N - 1)
    _, used = _prefill(pre, used, 0, rows[1, :T], 64)
    for p in range(T, T + 8):
        _, used = step(used, jnp.asarray([p]),
                       jnp.asarray(rows[1, p:p + 1], jnp.int32),
                       jnp.asarray([True]))
    fresh = sala.init_slot_cache(CFG32, 1, T + N - 1)
    la, used = _prefill(pre, used, 0, rows[2, :100], 64)
    lb, fresh = _prefill(pre, fresh, 0, rows[2, :100], 64)
    assert (np.asarray(la) == np.asarray(lb)).all()
    assert (np.asarray(used["state"]) == np.asarray(fresh["state"])).all()
    for p in range(100, 110):
        tok = jnp.asarray(rows[2, p:p + 1], jnp.int32)
        la, used = step(used, jnp.asarray([p]), tok, jnp.asarray([True]))
        lb, fresh = step(fresh, jnp.asarray([p]), tok, jnp.asarray([True]))
        assert (np.asarray(la) == np.asarray(lb)).all()


def test_inactive_slots_cache_is_not_written(weights, rows):
    _, step = _programs(weights, CFG32)
    cache = jax.tree.map(
        lambda a: jnp.asarray(np.random.default_rng(0).normal(
            size=a.shape), a.dtype), sala.init_slot_cache(CFG32, 2, 96))
    _, after = step(cache, jnp.asarray([70, 71]),
                    jnp.asarray(rows[:2, 0], jnp.int32),
                    jnp.asarray([False, True]))
    for name in cache:
        assert (np.asarray(after[name])[:, 0]
                == np.asarray(cache[name])[:, 0]).all(), name
    assert not (np.asarray(after["state"])[:, 1]
                == np.asarray(cache["state"])[:, 1]).all()


# ------------------------------------------- (vi) the scheduler, end to end

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
    sched = ContinuousScheduler(params, cfg, slots=2, prompt_len=100,
                                max_new_tokens=24, **kw)
    try:
        streams = [sched.submit_stream(p[None], max_new_tokens=b)
                   for p, b in zip(prompts, budgets)]
        return [_drain(s) for s in streams], sched
    finally:
        sched.close()


@pytest.fixture(scope="module")
def served(weights, rows):
    prompts, budgets = rows[:, :100], [24, 16, 20]
    out, sched = _serve(CFG16.cast_params(weights), CFG16, prompts, budgets,
                        prefill_chunk=32)
    return prompts, budgets, out, sched


def test_scheduler_streams_complete_with_lengths_as_asked(served):
    prompts, budgets, out, sched = served
    assert [len(o) for o in out] == budgets
    assert sched.retired_total == 3 and sched.prefill_chunks_total == 12
    assert sched.attend_kernel_chunks_total == 0  # heads of 16: the loop
    assert all(0 <= t < 512 for o in out for t in o)


def test_scheduler_books_positions_and_cache_bytes(served):
    _, budgets, _, sched = served
    # dense_len 64: of each prompt's 100 positions 36 lie past it, and
    # every decoded position does.
    steps = sum(b - 1 for b in budgets)
    assert sched.sparse_positions_total == 3 * 36 + steps
    assert sched.dense_positions_total == 3 * 64
    kinds = sched.cache_bytes
    assert set(kinds) == {"kv", "compressed", "state"}
    assert kinds["state"] == 2 * 2 * 4 * 16 * 16 * 4
    assert kinds["kv"] == 2 * 2 * 2 * 2 * 16 * 128 * 2  # extent 123 -> 128


def test_scheduler_serves_what_the_reference_ranks_first(weights, served):
    """Served tokens against the reference's full forward: the mean
    distance of the served token's reference logit below the best, by
    the benchmark's comparison, and the controls by the same limit."""
    prompts, budgets, out, _ = served
    limit = 0.002  # the rehearsal cell's: benchmark/cells/rehearsal-sala-tiny.*
    width = 100 + max(budgets)
    means = {}
    for quant in (None, "bf16", "fp8"):
        gaps = []
        for p, o in zip(prompts, out):
            row = np.zeros((1, width), np.int64)
            row[0, :100], row[0, 100:100 + len(o)] = p, o
            got = ref.served_gaps(weights, row, TOY, 100, quant)
            gaps.append(got["gap_served" if quant is None else "gap_control"]
                        [0, :len(o)])
        means[quant] = float(np.concatenate(gaps).mean())
    assert means[None] <= limit and means["bf16"] <= limit < means["fp8"], means


def test_prefix_pool_needs_chunks_for_recurrent_state(weights):
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousScheduler(weights, CFG32, slots=2, prompt_len=100,
                            max_new_tokens=4, prefix_cache_blocks=2)


def test_prefix_pool_hit_resumes_from_the_tier_that_a_chunk_ended(
        weights, rows):
    """With the pool on, a second request that shares 64 positions takes
    the tier a chunk ended at (32 and 96 end chunks too, 64 is the
    longest shared) and streams what it streams with the pool off."""
    prompts = np.stack([rows[0, :100], np.concatenate(
        [rows[0, :64], rows[1, 64:100]])])
    plain, _ = _serve(weights, CFG32, prompts, [6, 6], prefill_chunk=32)
    sched = ContinuousScheduler(weights, CFG32, slots=2, prompt_len=100,
                                max_new_tokens=24, prefill_chunk=32,
                                prefix_cache_blocks=4)
    try:
        first = _drain(sched.submit_stream(prompts[:1], max_new_tokens=6))
        second = _drain(sched.submit_stream(prompts[1:], max_new_tokens=6))
        assert sched.prefix_hits_total == 1
        assert [first, second] == plain
    finally:
        sched.close()


def test_preempted_row_resumes_its_stream(weights, rows):
    """Preemption's resume (prompt re-prefill, then replay of the forced
    tokens through the step) needs nothing of the model but the step."""
    prompts = rows[:, :100]
    plain, _ = _serve(weights, CFG32, prompts[:1], [10], prefill_chunk=32)
    sched = ContinuousScheduler(weights, CFG32, slots=1, prompt_len=100,
                                max_new_tokens=24, prefill_chunk=32)
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


def _serial_and_ahead(params, cfg, prompts, budgets, alone=0, **kw):
    """The same submissions with the loop held to the serial order (a
    launch hook that reads everything unread before the next step is
    launched) and as it runs, one launch ahead: (tokens, scheduler)
    of each. The first ``alone`` streams are drained before the rest
    are submitted."""
    runs = []
    for hold in (True, False):
        sched = ContinuousScheduler(params, cfg, slots=2, prompt_len=100,
                                    max_new_tokens=24, **kw)
        if hold:
            sched.launch_hook = lambda tok, s=sched: s._land()
        try:
            work = list(zip(prompts, budgets))
            got = [_drain(sched.submit_stream(p[None], max_new_tokens=b))
                   for p, b in work[:alone]]
            streams = [sched.submit_stream(p[None], max_new_tokens=b)
                       for p, b in work[alone:]]
            runs.append((got + [_drain(s) for s in streams], sched))
        finally:
            sched.close()
    return runs


@pytest.mark.parametrize("case", ["budgets_and_chunks", "eos_mid_stream",
                                  "prefix_pool_hit"])
def test_streams_get_the_serial_orders_tokens(weights, rows, served, case):
    """This family through the loop that launches step N+1 before it
    reads step N (tests/test_continuous.py has GPT-2's and the stub
    kernels' cases): lightning state, rings and compressed keys of a
    lane computed for nobody are never a later occupant's."""
    prompts, budgets, out, _ = served
    params, cfg, kw = CFG16.cast_params(weights), CFG16, {"prefill_chunk": 32}
    if case == "eos_mid_stream":
        # A token every stream of `served` holds past its first: each
        # ends there, the slot's next occupant starts behind a lane
        # that was computed and thrown away.
        kw["eos_id"] = int(out[0][5])
        budgets = [24, 24, 24]
    elif case == "prefix_pool_hit":
        params, cfg = weights, CFG32
        prompts = np.stack([rows[0, :100], np.concatenate(
            [rows[0, :64], rows[1, 64:100]]), rows[2, :100]])
        budgets = [6, 6, 3]
        kw.update(prefix_cache_blocks=4, alone=1)
    (serial, held), (ahead, sched) = _serial_and_ahead(
        params, cfg, prompts, budgets, **kw)
    assert ahead == serial
    assert held.overlapped_total == 0 and held.discarded_lanes_total == 0
    assert sched.overlapped_total > 0
    if case == "budgets_and_chunks":
        assert ahead == out and sched.discarded_lanes_total == 0
    elif case == "eos_mid_stream":
        assert ahead[0] == out[0][:out[0].index(kw["eos_id"]) + 1]
        assert sched.discarded_lanes_total >= 1
    else:
        assert sched.prefix_hits_total == held.prefix_hits_total >= 1


# --------------------------------------------------- config, loader, counts

def test_loader_reads_the_benchmarks_configuration():
    cfg = sala.load_model_config(os.path.join(CONFIGS, "minicpm-sala.json"))
    assert cfg.runs() == [(sala.SPARSE, 0, 1), (sala.LIGHTNING, 0, 6),
                          (sala.SPARSE, 1, 2)]
    assert cfg.layer_ids == tuple(range(9, 17)) and cfg.published_layers == 32
    # 2820.5 M in matrices and 32 512 gains: 5.64 GB in bfloat16.
    assert sala.num_params(cfg) == 2_820_536_576 + 32_512
    assert abs(cfg.residual_scale - 1.4 / np.sqrt(32)) < 1e-12
    assert cfg.logit_divisor == 16.0
    rates = cfg.decay_rates()
    assert rates.shape == (6, 32)
    np.testing.assert_allclose(
        rates[0, 31], 2.0 ** -8 * (1 - 10 / 31 + 1e-5), rtol=1e-6)


def test_loader_refuses_another_model_type(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"model_type": "gpt2"}))
    with pytest.raises(ValueError, match="no loader"):
        sala.load_model_config(str(path))


def test_configuration_holds_the_catalogs_numbers():
    with open(os.path.join(CONFIGS, "minicpm-sala.json")) as f:
        cfg = json.load(f)
    assert cfg["source"].endswith("openbmb/MiniCPM-SALA/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    published = cfg["published"]["mixer_types"]
    assert len(published) == 32 and published[9:17] == cfg["mixer_types"]
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_key_value_heads"], cfg["vocab_size"]) == (
                4096, 16384, 128, 2, 73448)


@pytest.mark.parametrize("pos, keys, comp", [
    (0, 1, 0), (63, 64, 0),            # dense up to dense_len 64
    (64, 8 + 3 * 8 + 32 + 1, 31),      # block 0, all 3 between, blocks 4..8
    (149, 8 + 4 * 8 + 32 + 6, 74),     # block 0, 4 of the rest, blocks 14..18
])
def test_flop_model_counts_attended_keys(pos, keys, comp):
    model = sala.SalaFlopModel(CFG32, 192)
    got_keys, got_comp = model.counts(pos, 1)
    assert (int(got_keys[0]), int(got_comp[0])) == (keys, comp)
    assert model.step_useful_flops(pos) == (
        model._proj + model._per_key * keys + model._per_comp * comp
        + model._logit)


def test_flop_model_sums_are_the_sum_of_their_steps():
    model = sala.SalaFlopModel(CFG32, 192)
    assert model.steps_useful_sum(60, 10) == sum(
        model.step_useful_flops(p) for p in range(60, 70))
    assert model.chunk_useful_flops(32, 64, True) == sum(
        model.step_useful_flops(p) for p in range(32, 96)) - 63 * model._logit
    assert model.prefill_chunks_flops(0, 100, 32) == \
        3 * model.chunk_flops(32) + model.chunk_flops(4)
    assert model.step_flops() >= model.step_useful_flops(191)


@pytest.mark.parametrize("pos, sparse", [
    ([0, 63], 0),                # up to dense_len 64: dense attention
    ([64], 1),                   # the first query the selection serves
    (list(range(32, 96)), 32),   # a chunk astride the edge
    ([], 0),
])
def test_model_says_which_positions_its_selection_serves(pos, sparse):
    """What the scheduler sums into `sparse_positions_total`: the model's
    own account of a position, not the scheduler's."""
    assert CFG16.slot_model().sparse_positions(np.asarray(pos, int)) == sparse
    dense_only = dataclasses.replace(CFG16, mixer_types=(sala.LIGHTNING,) * 2,
                                     layer_ids=(0, 1))
    assert dense_only.slot_model().sparse_positions(np.asarray(pos, int)) == 0


def test_gpt2_config_hands_over_the_same_protocol():
    from tpu_dist_nn.models import generate
    from tpu_dist_nn.models.transformer import TransformerConfig

    model = TransformerConfig().slot_model()
    assert model.decode_step_slots is generate.decode_step_slots
    assert not model.recurrent and model.sparse_positions(np.arange(9)) == 0
    cache = model.init_slot_cache(TransformerConfig(), 2, 16)
    assert model.cache_bytes(cache) == {"kv": 2 * 4 * 2 * 4 * 32 * 16 * 4}


# ------------------------------------------------------- the entry point

def test_cli_lm_model_config_serves_over_the_wire():
    """`tdn lm --model-config F --serve-generate P`: no training, the
    file's architecture with seeded weights behind the normal gRPC
    endpoint, on the continuous scheduler."""
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
            os.path.join(CONFIGS, "rehearsal-sala-tiny.json"),
            "--serve-generate", str(port), "--serve-prompt-len", "80",
            "--serve-new-tokens", "4", "--temperature", "0",
            "--gen-slots", "2", "--prefill-chunk", "32",
            "--serve-seconds", "20",
        ],),
        daemon=True,
    )
    t.start()
    client = GrpcClient(f"127.0.0.1:{port}", timeout=30.0)
    prompts = np.full((2, 80), 7)
    deadline, out = time.monotonic() + 90, None
    while time.monotonic() < deadline:
        try:
            out = client.generate(prompts)
            break
        except Exception:  # noqa: BLE001 — the port is not open yet
            time.sleep(1.0)
    assert out is not None, "server never came up"
    assert out.shape == (2, 84) and (out[:, :80] == 7).all()
    assert (out[0] == out[1]).all() and (out[:, 80:] < 512).all()


def test_cli_lm_model_config_needs_the_continuous_scheduler():
    from tpu_dist_nn.cli import main

    toy = os.path.join(CONFIGS, "rehearsal-sala-tiny.json")
    assert main(["--platform", "cpu", "lm", "--model-config", toy]) != 0
    assert main(["--platform", "cpu", "lm", "--model-config", toy,
                 "--serve-generate", "0", "--scheduler", "static"]) != 0
