"""Falcon-H1's block family (models/falcon_h1.py) against its plain
reference (benchmark/configs/falcon_h1_reference.py) at a toy size of
the same family (benchmark/configs/rehearsal-falcon-h1-tiny.json): three
layers of grouped-query attention (10 query heads of 16 over 2 K/V
heads) beside Mamba-2 (8 heads of 8, a state of 16, 2 groups of B and
C, blocks of 8 positions) under the published muP multipliers.  Chunks
of 13 and 36 positions are no whole number of blocks; logits are
compared, never tokens.

Tolerances.  With float32 parameters at matmul precision `highest` (the
suite's default) program and reference compute the same mathematics in
another order: the program's mixer in the chunked form (products inside
a block, states carried between blocks and chunks), the reference's as
the sequential recurrence; a running softmax over key tiles against one
over a row.  1e-4 on logits that spread by one covers that reordering
(measured 6e-6 forward).  The chunked form against the recurrence on the
same float32 inputs: 1e-5 of the read-out's largest value (the sum of a
block's terms in another order).  With bfloat16 parameters the program
rounds every activation to 8 bits of mantissa: the bound is on the
MEDIAN over positions of a position's RMS logit error, 0.03 (what the
program reads, and the reference's bfloat16 emulation, are in the test's
docstring).  What tells a precision from another end to end is the
served-gap comparison, by the limit the benchmark's rehearsal uses
(tests/bench_harness/test_bench_falcon_h1.py).

The last section pins what this family's sharing moved: Laguna's
full-layer helpers now live in models/gqa.py, and Laguna's and SambaY's
chunk and step stay what they were, operation for operation and scope
for scope.
"""

import collections
import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import lookup
from tpu_dist_nn.models import falcon_h1 as fh
from tpu_dist_nn.models import gqa, laguna, sala, sambay, slot_model
from tpu_dist_nn.serving.continuous import ContinuousScheduler, slot_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
ref = lookup.load_module(
    os.path.join(CONFIGS, "falcon_h1_reference.py"), "falcon_h1_reference")

with open(os.path.join(CONFIGS, "rehearsal-falcon-h1-tiny.json")) as f:
    TOY = json.load(f)
CFG32 = fh.FalconH1Config.from_dict(dict(TOY, param_dtype="float32"))
CFG16 = fh.FalconH1Config.from_dict(TOY)
T, N, S = 60, 20, 3
SIZES = ref.sizes(TOY)


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
                jax.jit(lambda c, slot, t, st: fh.prefill_chunk_into_cache(
                    params, cfg, c, slot, t, st)),
                jax.jit(lambda c, pos, tok, act: fh.decode_step_slots(
                    params, c, pos, tok, cfg, active=act)))
        return made[cfg]

    return get


def _prefill(pre, cache, slot, tokens, chunk):
    at, logits = 0, None
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
    got = fh.forward(weights, jnp.asarray(rows), CFG32)
    np.testing.assert_allclose(np.asarray(got), full, atol=1e-4)


@pytest.mark.parametrize("chunk", [13, 36, T])
def test_prefill_then_decode_matches_full_forward(programs, rows, full, chunk):
    """Ragged chunks (13 and 36 are no whole number of blocks of 8), then
    20 steps through the K/V rows, the convolution's window and the
    state."""
    pre, step = programs(CFG32)
    cache = fh.init_slot_cache(CFG32, S, T + N)
    for s in range(S):
        last, cache = _prefill(pre, cache, s, rows[s, :T], chunk)
        np.testing.assert_allclose(np.asarray(last[0]), full[s, T - 1],
                                   atol=1e-4)
    got, _ = _decode(step, cache, rows)
    np.testing.assert_allclose(got, full[:, T:T + N], atol=1e-4)


def test_prefill_then_decode_bf16(programs, rows, full):
    """bfloat16 parameters: the program reads 0.0118, the reference's
    bfloat16 emulation 0.0125, its int8 emulation 0.041 and its fp8
    emulation 0.156 (CPU, seed 3): 0.03 lies 2.5 times above the
    program and under the int8 emulation."""
    pre, step = programs(CFG16)
    cache = fh.init_slot_cache(CFG16, S, T + N)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 36)
    got, _ = _decode(step, cache, rows)
    assert _median_rms(got - full[:, T:T + N]) < 0.03


def test_a_state_held_in_bfloat16_stays_so_through_chunk_and_step(
        programs, rows, full):
    """`state_dtype` chooses what the cache holds; the update is float32
    whatever holds it, and the logits stay within the bfloat16 tolerance
    above (the benchmark's cell tells such a state apart by its
    mantissa: tests/bench_harness/test_bench_falcon_h1.py)."""
    cfg = dataclasses.replace(CFG16, state_dtype="bfloat16")
    pre, step = programs(cfg)
    cache = fh.init_slot_cache(cfg, S, T + N)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 36)
    assert cache["state"].dtype == jnp.bfloat16
    got, cache = _decode(step, cache, rows)
    assert cache["state"].dtype == jnp.bfloat16
    assert _median_rms(got - full[:, T:T + N]) < 0.03


# ------------------------------------------------- (ii) the two forms

def _sequential(x, dt, a_neg, B, C, state):
    """The recurrence position by position in float64, from `state`."""
    g = x.shape[1] // B.shape[1]
    S_ = np.asarray(state, np.float64)
    ys = []
    for t in range(x.shape[0]):
        Bh, Ch = np.repeat(B[t], g, 0), np.repeat(C[t], g, 0)
        S_ = np.exp(dt[t] * a_neg)[:, None, None] * S_ \
            + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", S_, Ch))
    return np.stack(ys), S_


def test_chunked_form_equals_the_recurrence_across_blocks_and_chunks():
    """Two chunks of 23 and 27 positions at blocks of 8 from a state that
    is not zero, the second carried in on the first's: the read-out and
    the state after each chunk are the recurrence's."""
    rng = np.random.default_rng(4)
    H, P, G, Nst, Tn = 8, 8, 2, 16, 50
    x = rng.normal(size=(Tn, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (Tn, H))).astype(
        np.float32)
    a_neg = -np.linspace(1.0, 16.0, H).astype(np.float32)
    B = rng.normal(size=(Tn, G, Nst)).astype(np.float32)
    C = rng.normal(size=(Tn, G, Nst)).astype(np.float32)
    s0 = rng.normal(size=(H, P, Nst)).astype(np.float32)
    want_y, want_s = _sequential(x, dt, a_neg, B, C, s0)
    ssd = jax.jit(fh.ssd, static_argnums=6)
    recur = jax.jit(fh.recur, static_argnums=6)
    state, ys = jnp.asarray(s0), []
    for lo, hi in ((0, 23), (23, Tn)):
        y, state = ssd(*(jnp.asarray(a[lo:hi]) for a in (x, dt)),
                       jnp.asarray(a_neg),
                       *(jnp.asarray(a[lo:hi]) for a in (B, C)), state, 8)
        ys.append(np.asarray(y))
        _, mid = _sequential(x[:hi], dt[:hi], a_neg, B[:hi], C[:hi], s0)
        np.testing.assert_allclose(np.asarray(state), mid,
                                   atol=1e-5 * np.abs(mid).max())
    np.testing.assert_allclose(np.concatenate(ys), want_y,
                               atol=1e-5 * np.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(state), want_s,
                               atol=1e-5 * np.abs(want_s).max())
    # The step's form, one position at a time, from the same state.
    state = jnp.asarray(s0)[None]
    for t in range(Tn):
        y, state = recur(*(jnp.asarray(a[t:t + 1]) for a in (x, dt)),
                         jnp.asarray(a_neg),
                         *(jnp.asarray(a[t:t + 1]) for a in (B, C)), state, G)
    np.testing.assert_allclose(np.asarray(state[0]), want_s,
                               atol=1e-5 * np.abs(want_s).max())


@jax.jit
def _reference_states(weights, row):
    """The reference's state after the whole row in every layer."""
    x = weights["embed"][row] * SIZES["emb"]
    out = []
    for layer in range(SIZES["L"]):
        w = ref.layer_of(weights, layer)
        u = ref._rms(x, w["ln1_g"], SIZES["eps"]) * SIZES["ssm_in"]
        _, xs, dt, a_neg, B, C = ref.scan_inputs(u, w, SIZES)
        out.append(ref.recurrence(xs, dt, a_neg, B, C, SIZES)[1])
        x = ref.layer(x, w, SIZES)
    return jnp.stack(out)


def test_the_steps_state_is_the_recurrences_after_n_steps(
        programs, rows, weights):
    pre, step = programs(CFG32)
    cache = fh.init_slot_cache(CFG32, S, T + N)
    for s in range(S):
        _, cache = _prefill(pre, cache, s, rows[s, :T], 36)
    _, cache = _decode(step, cache, rows)
    for s in range(S):
        want = np.asarray(_reference_states(weights,
                                            jnp.asarray(rows[s, :T + N])))
        got = np.asarray(cache["state"][:, s])
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_a_readmitted_slot_starts_from_zero_state(programs, rows):
    """A slot that held another request, decoded through it, takes a new
    prompt: its logits are those of a slot never used, bit for bit, and
    a slot the step left out keeps its state and window bit for bit."""
    pre, step = programs(CFG32)
    fresh = fh.init_slot_cache(CFG32, S, T + N)
    want, _ = _prefill(pre, fresh, 1, rows[2, :T], 36)
    cache = fresh
    _, cache = _prefill(pre, cache, 1, rows[0, :T], 36)
    for t in range(T, T + 5):
        _, cache = step(cache, jnp.full((S,), t), jnp.asarray(rows[:, t]),
                        jnp.asarray([False, True, False]))
    assert float(jnp.abs(cache["state"][:, 1]).max()) > 0
    np.testing.assert_array_equal(np.asarray(cache["state"][:, 0]), 0)
    np.testing.assert_array_equal(np.asarray(cache["conv"][:, 2]), 0)
    got, cache = _prefill(pre, cache, 1, rows[2, :T], 36)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------- (iii) the scheduler

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
                        prefill_chunk=36)
    return prompts, budgets, out, sched


def test_scheduler_streams_complete_with_lengths_as_asked(served):
    _, budgets, out, sched = served
    assert [len(o) for o in out] == budgets
    assert sched.prefill_chunks_total == 3 * 2
    # K and V of 3 layers, 2 slots, 2 heads of 16 over an extent of 128
    # in bfloat16; the window (3 inputs of 64 + 2 x 2 x 16) in bfloat16
    # and the state (8 heads x 8 x 16) in float32.
    assert sched.cache_bytes == {
        "kv": 2 * 3 * 2 * 2 * 16 * 128 * 2,
        "state": 3 * 2 * 3 * 128 * 2 + 3 * 2 * 8 * 8 * 16 * 4}


def test_scheduler_serves_what_the_reference_ranks_first(weights, served):
    prompts, _, out, _ = served
    served_rows = np.concatenate([prompts, np.zeros((S, 24), int)], 1)
    for i, o in enumerate(out):
        served_rows[i, T:T + len(o)] = o
    gaps = ref.served_gaps(weights, served_rows, TOY, T)["gap_served"]
    kept = np.concatenate([gaps[i, :len(o)] for i, o in enumerate(out)])
    assert kept.mean() < 0.01


def test_prefix_pool_needs_chunks_for_the_state(weights):
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                            max_new_tokens=4, prefix_cache_blocks=2)


def test_prefix_pool_hit_resumes_from_the_tier_that_a_chunk_ended(
        weights, rows):
    """With the pool on, a second request that shares 40 positions takes
    the tier the second chunk ended at (its state and window are those
    after position 39, and no later ones) and streams what it streams
    with the pool off."""
    prompts = np.stack([rows[0, :T], np.concatenate(
        [rows[0, :40], rows[1, 40:T]])])
    plain, _ = _serve(weights, CFG32, prompts, [6, 6], prefill_chunk=20)
    sched = ContinuousScheduler(weights, CFG32, slots=2, prompt_len=T,
                                max_new_tokens=24, prefill_chunk=20,
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
    plain, _ = _serve(weights, CFG32, prompts[:1], [10], prefill_chunk=36)
    sched = ContinuousScheduler(weights, CFG32, slots=1, prompt_len=T,
                                max_new_tokens=24, prefill_chunk=36)
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
    cfg = sala.load_model_config(os.path.join(CONFIGS, "falcon-h1-34b.json"))
    assert isinstance(cfg, fh.FalconH1Config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.n_layers, cfg.vocab_size) == (
        5120, 21504, 20, 4, 128, 6, 32640)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state, cfg.n_groups,
            cfg.d_conv, cfg.ssm_block) == (32, 128, 256, 2, 4, 128)
    assert (cfg.d_ssm, cfg.conv_dim, cfg.proj_dim) == (4096, 5120, 9248)
    assert cfg.rope_theta == 1e11 and cfg.state_dtype == "float32"
    # A layer 430 120 032 (attention 31.46 M, W_in 47.35 M, W_out
    # 20.97 M, the MLP 330.30 M, the convolution, norms and per-head
    # vectors); the two slices of 32 640 rows 167.1 M each.
    layer = 31_457_280 + 47_349_760 + 20_971_520 + 330_301_440 \
        + 4 * 5120 + 5120 + 3 * 32 + 4096 + 2 * 5120
    assert layer == 430_120_032
    assert cfg.num_params() == 6 * layer + 2 * 32640 * 5120 + 5120 \
        == 2_914_958_912
    # One slot's state a layer: 32 x 128 x 256 float32 = 4 194 304 B.
    cache = jax.eval_shape(lambda: fh.init_slot_cache(cfg, 128, 1535))
    assert cache["state"].shape == (6, 128, 32, 128, 256)
    assert cache["k"].shape == (6, 128, 4, 128, 1536)
    assert fh.cache_bytes(cache)["state"] == 6 * 128 * (
        4_194_304 + 3 * 5120 * 2)


def test_loader_refuses_what_the_family_is_not(tmp_path):
    for key, bad in (("model_type", "falcon_mamba"),
                     ("attn_layer_indices", [0, 2]),
                     ("mamba_norm_before_gate", True),
                     ("mamba_proj_bias", True),
                     ("tie_word_embeddings", True),
                     ("mamba_d_ssm", 48)):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps(dict(TOY, **{key: bad})))
        with pytest.raises(ValueError, match=key):
            slot_model.load_model_config(str(path))


def test_flop_model_sums_are_the_sum_of_their_steps():
    model = fh.FalconH1FlopModel(CFG32, 83)
    assert model.M == 128
    assert model.steps_useful_sum(3, 20) == sum(
        model.step_useful_flops(p) for p in range(3, 23))
    # Every layer's 10 heads of 16 attend one more key a position.
    assert model.step_useful_flops(50) - model.step_useful_flops(49) \
        == 3 * 4 * 10 * 16
    assert model.chunk_useful_flops(36, 24, True) \
        - model.chunk_useful_flops(36, 24, False) == model._logit
    assert model.step_flops() >= model.step_useful_flops(127)
    # A chunk of 13 runs two whole blocks of 8.
    assert model.chunk_flops(13) - model.chunk_flops(12) \
        == model._fixed + model._key * 128
    for size in (13, 36, 60):
        assert model.chunk_flops(size) >= model.chunk_useful_flops(
            128 - size, size, True)
    assert model.prefill_chunks_flops(0, 60, 36) \
        == model.chunk_flops(36) + model.chunk_flops(24)


# ------------------------------------------------------- the entry point

def test_the_family_is_loaded_by_its_model_type():
    assert slot_model.FAMILIES["falcon_h1"] == ("falcon_h1", "FalconH1Config")
    cfg = slot_model.load_model_config(
        os.path.join(CONFIGS, "rehearsal-falcon-h1-tiny.json"))
    assert cfg == CFG16
    model = cfg.slot_model()
    assert model.recurrent and model.copy_cache_slot is not None
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
            os.path.join(CONFIGS, "rehearsal-falcon-h1-tiny.json"),
            "--serve-generate", str(port), "--serve-prompt-len", "40",
            "--serve-new-tokens", "4", "--temperature", "0",
            "--gen-slots", "2", "--prefill-chunk", "12",
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


def test_the_six_scopes_name_the_step_and_the_chunk():
    p = jax.eval_shape(lambda: CFG16.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: fh.init_slot_cache(CFG16, 2, 80))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    v2 = jax.ShapeDtypeStruct((2,), jnp.int32)
    want = {"falcon_h1.attn.project", "falcon_h1.attn", "falcon_h1.ssm.in",
            "falcon_h1.ssm.scan", "falcon_h1.ssm.out", "falcon_h1.mlp"}
    for lowered in (
            jax.jit(lambda p, c, s, t, st: fh.prefill_chunk_into_cache(
                p, CFG16, c, s, t, st)).lower(
                    p, cache, i32, jax.ShapeDtypeStruct((1, 13), jnp.int32),
                    i32),
            jax.jit(lambda p, c, pos, t, a: fh.decode_step_slots(
                p, c, pos, t, CFG16, active=a)).lower(
                    p, cache, v2, v2, jax.ShapeDtypeStruct((2,), jnp.bool_))):
        text = lowered.as_text(debug_info=True)
        assert set(re.findall(r"(falcon_h1\.[a-z_.]+?)/", text)) == want
        assert not re.search(r"laguna\.(attn|router|experts|shared|mlp)",
                             text)


# ------------------------------- (iv) what the sharing left as it was

def _ops(lowered) -> dict:
    """Operations by name in a lowered program."""
    return dict(collections.Counter(re.findall(
        r"= \"?([a-z_]+\.[a-z_.]+)", lowered.as_text())))


def _digest(ops: dict) -> str:
    return hashlib.sha256(json.dumps(sorted(ops.items())).encode()
                          ).hexdigest()[:16]


with open(os.path.join(CONFIGS, "rehearsal-laguna-tiny.json")) as f:
    LAGUNA_TOY = json.load(f)
# The toy, and the toy at heads whose chunk the Pallas kernel attends.
LAGUNA = {"toy": LAGUNA_TOY,
          "wide": dict(LAGUNA_TOY, head_dim=128, param_dtype="float32",
                       num_attention_heads_per_layer=[12, 6, 6, 6, 12])}
# (operations, digest of their counts by name) of Laguna's programs
# since the expert layer sorts only a chunk's choices and counts its
# tile rows (models/experts.py): the same operations, whatever file they
# are written in (its full-layer helpers live in models/gqa.py).
LAGUNA_PARENT = {
    ("toy", "chunk_16"): (1685, "044f2bf28cf6c05f"),
    ("toy", "chunk_136"): (2129, "542ac3b064d6e44d"),
    ("toy", "step"): (2421, "459154b6001bbac4"),
    ("wide", "chunk_128"): (9159, "c21183168606602d"),
    ("wide", "chunk_136"): (1984, "5c0fb9ac9715521b"),
    ("wide", "step"): (2271, "4ab08267c63c995e")}


@pytest.mark.parametrize("toy,program", sorted(LAGUNA_PARENT))
def test_lagunas_programs_are_the_parents_operation_for_operation(
        toy, program):
    cfg = laguna.LagunaConfig.from_dict(LAGUNA[toy])
    p = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: laguna.init_slot_cache(cfg, 3, 512))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    v3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    if program == "step":
        lowered = jax.jit(lambda p, c, pos, t, a: laguna.decode_step_slots(
            p, c, pos, t, cfg, active=a)).lower(
                p, cache, v3, v3, jax.ShapeDtypeStruct((3,), jnp.bool_))
    else:
        C = int(program.split("_")[1])
        lowered = jax.jit(lambda p, c, s, t, st: laguna.prefill_chunk_into_cache(
            p, cfg, c, s, t, st)).lower(
                p, cache, i32, jax.ShapeDtypeStruct((1, C), jnp.int32), i32)
    ops = _ops(lowered)
    assert (sum(ops.values()), _digest(ops)) == LAGUNA_PARENT[toy, program]
    scopes = set(re.findall(r"(laguna\.[a-z_.]+?)/",
                            lowered.as_text(debug_info=True)))
    assert scopes == {"laguna.attn.project", "laguna.attn.window",
                      "laguna.attn.full", "laguna.router", "laguna.experts",
                      "laguna.shared", "laguna.mlp"}
    assert not re.search(r"falcon_h1\.(attn|ssm|mlp)",
                         lowered.as_text(debug_info=True))


with open(os.path.join(CONFIGS, "rehearsal-phi4flash-tiny.json")) as f:
    SAMBAY_TOY = json.load(f)
# SambaY's programs at the same parent: this family imports its slot
# copy and layer helpers, and they stay what they were.
SAMBAY_PARENT = {"chunk_13": (2520, "43617b382a0c889d"),
                 "chunk_32": (2604, "bd0e8c29becee0f5"),
                 "step": (2878, "cb9e21880fa76da1")}


@pytest.mark.parametrize("program", sorted(SAMBAY_PARENT))
def test_sambays_programs_are_the_parents_operation_for_operation(program):
    cfg = sambay.SambaYConfig.from_dict(SAMBAY_TOY)
    p = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: sambay.init_slot_cache(cfg, 3, 96))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    v3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    if program == "step":
        lowered = jax.jit(lambda p, c, pos, t, a: sambay.decode_step_slots(
            p, c, pos, t, cfg, active=a)).lower(
                p, cache, v3, v3, jax.ShapeDtypeStruct((3,), jnp.bool_))
    else:
        C = int(program.split("_")[1])
        lowered = jax.jit(lambda p, c, s, t, st: sambay.prefill_chunk_into_cache(
            p, cfg, c, s, t, st)).lower(
                p, cache, i32, jax.ShapeDtypeStruct((1, C), jnp.int32), i32)
    ops = _ops(lowered)
    assert (sum(ops.values()), _digest(ops)) == SAMBAY_PARENT[program]
    text = lowered.as_text(debug_info=True)
    want = {"sambay.attn.cross", "sambay.attn.full", "sambay.attn.window",
            "sambay.gmu", "sambay.mamba", "sambay.mlp"}
    assert set(re.findall(r"(sambay\.[a-z_.]+?)/", text)) == (
        want if program == "step" else want | {"sambay.tail"})
    assert not re.search(r"falcon_h1\.(attn|ssm|mlp)", text)


def test_laguna_keeps_the_names_its_tests_import():
    assert laguna._full_chunk_loop is gqa.chunk_loop
