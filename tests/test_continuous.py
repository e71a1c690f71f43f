"""Continuous-batching decode scheduler (serving/continuous.py):
static-scheduler output parity, slot reuse, per-request budgets,
admission/close semantics, observability, the loopback endpoint, the
KV-reuse layer — prefix-cache bit parity / refcount lifecycle / COW
isolation, chunked-prefill parity, mid-prefill faults, drain with
half-prefilled slots — and what iteration-level scheduling and the
prefix pool save, counted in step launches and prefilled tokens."""

import functools
import threading
import time

import jax
import jax.numpy as jnp  # noqa: F401 — parity helpers
import numpy as np
import pytest

from tpu_dist_nn.models.generate import generate
from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from tpu_dist_nn.serving.continuous import ContinuousScheduler

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=3, d_ff=64, max_seq_len=48
)
PARAMS = init_transformer(jax.random.key(11), CFG)
T, N = 8, 10


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, (n, T))


def _sched(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("prompt_len", T)
    kw.setdefault("max_new_tokens", N)
    return ContinuousScheduler(PARAMS, CFG, **kw)


def _fake_sched(step_cost=0.0, chunk_cost=0.0, on_prefill=None,
                on_step=None, **kw):
    """Cost-model scheduler (no device work): the deterministic arm of
    the admission/close/shed/prefix-lifecycle tests. ``chunk_cost`` is
    per prefill-chunk TOKEN (chunked prefill pays proportionally to
    the tokens it actually runs). ``on_prefill(tokens)`` / ``on_step()``
    let a test count what a kernel was handed, or hold the device."""

    def fake_prefill(params, cache, slot, tokens, start, key):
        if chunk_cost:
            time.sleep(chunk_cost * tokens.shape[1])
        if on_prefill is not None:
            on_prefill(tokens)
        return np.int32(1), cache

    def fake_step(params, cache, pos, active, tok, key):
        if step_cost:
            time.sleep(step_cost)
        if on_step is not None:
            on_step()
        return np.asarray(tok) + 1, cache

    kw.setdefault("slots", 2)
    kw.setdefault("prompt_len", T)
    kw.setdefault("max_new_tokens", N)
    return ContinuousScheduler(
        None, None, prefill_fn=fake_prefill, step_fn=fake_step, **kw
    )


# ------------------------------------------------------------ parity


def test_continuous_matches_static_greedy_tokens():
    # The acceptance core: temperature=0 outputs are identical between
    # the two schedulers — INCLUDING eos early-retire/pad semantics —
    # with more rows than slots (so queueing + slot reuse are on the
    # path) and requests arriving both as one multi-row submit and as
    # concurrent single rows.
    prompts = _prompts(6, seed=1)
    base = np.asarray(generate(PARAMS, CFG, prompts, N))
    eos = int(base[0, N // 2])
    ref = np.asarray(generate(PARAMS, CFG, prompts, N, eos_id=eos))
    want = np.concatenate([prompts, ref], axis=1)

    sched = _sched(slots=4, eos_id=eos)
    try:
        out = sched.submit(prompts)
        np.testing.assert_array_equal(out, want)
        # Same prompts again as concurrent one-row requests.
        outs = [None] * 6

        def call(i):
            outs[i] = sched.submit(prompts[i:i + 1])

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(6)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for i in range(6):
            np.testing.assert_array_equal(outs[i][0], want[i])
        assert sched.retired_total == 12
        assert sched.rows_total == 12
    finally:
        sched.close()


def test_slot_reuse_does_not_leak_stale_kv():
    # One slot, sequential occupants: every sequence must equal its
    # fresh single-row decode — occupant k's K/V cannot contaminate
    # occupant k+1 (the prefill overwrites the slot's full extent and
    # attention masks beyond the frontier).
    prompts = _prompts(3, seed=2)
    sched = _sched(slots=1)
    try:
        for i in range(3):
            out = sched.submit(prompts[i:i + 1])
            ref = np.asarray(generate(PARAMS, CFG, prompts[i:i + 1], N))
            np.testing.assert_array_equal(out[0, T:], ref[0])
    finally:
        sched.close()


def test_per_request_budget_caps_and_pads():
    prompts = _prompts(2, seed=3)
    ref = np.asarray(generate(PARAMS, CFG, prompts, N))
    sched = _sched(slots=2, eos_id=None)
    try:
        out = sched.submit(prompts, max_new_tokens=3)
        # The 3 requested tokens match the full decode's first 3; the
        # rest of the static-width row is pad (0 without an eos_id).
        np.testing.assert_array_equal(out[:, T:T + 3], ref[:, :3])
        assert (out[:, T + 3:] == 0).all()
        with pytest.raises(ValueError, match="max_new_tokens"):
            sched.submit(prompts, max_new_tokens=N + 1)
        with pytest.raises(ValueError, match="shape"):
            sched.submit(np.zeros((1, T + 2), np.int32))
    finally:
        sched.close()


def test_zero_row_submit_returns_empty_without_touching_the_loop():
    # A (0, T) submit must answer immediately (the static batcher
    # round-trips empty matrices too) — queueing it would hand the loop
    # a rowless item that corrupts the pending ledger and kills the
    # scheduler thread.
    sched = _sched(slots=2)
    try:
        out = sched.submit(np.zeros((0, T), np.int32))
        assert out.shape == (0, T + N)
        assert sched.pending_rows == 0 and sched.requests_total == 0
        # The scheduler is still fully alive for real work.
        ref = np.asarray(generate(PARAMS, CFG, _prompts(1, seed=12), N))
        np.testing.assert_array_equal(
            sched.submit(_prompts(1, seed=12))[0, T:], ref[0]
        )
    finally:
        sched.close()


def test_sampled_generation_fresh_and_in_vocab():
    # temperature > 0: repeated identical prompts draw fresh
    # continuations (per-event key folds), everything stays in-vocab.
    prompts = np.full((2, T), 5)
    sched = _sched(slots=2, temperature=1.0, seed=3)
    try:
        a = sched.submit(prompts)
        b = sched.submit(prompts)
        assert not np.array_equal(a, b)
        assert (a[:, T:] >= 0).all() and (a[:, T:] < CFG.vocab_size).all()
    finally:
        sched.close()


def test_scheduler_validates_contract_at_construction():
    with pytest.raises(ValueError, match="slots"):
        _sched(slots=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        _sched(max_new_tokens=CFG.max_seq_len)
    with pytest.raises(ValueError, match="top_k"):
        _sched(temperature=0.0, top_k=5)
    with pytest.raises(ValueError, match="eos_id"):
        _sched(eos_id=CFG.vocab_size)
    with pytest.raises(ValueError, match="together"):
        ContinuousScheduler(
            None, None, slots=1, prompt_len=T, max_new_tokens=N,
            prefill_fn=lambda *a: None,
        )


# ------------------------------------------------------------ observability


def test_metrics_ttft_occupancy_and_sampler_gauges():
    from tpu_dist_nn.obs import RuntimeSampler
    from tpu_dist_nn.obs.registry import REGISTRY

    def total(name, label=None):
        m = REGISTRY.get(name)
        if m is None:
            return 0.0
        # samples() keys are label-VALUE tuples ((value,) here).
        return float(sum(
            c.value for k, c in m.samples()
            if label is None or tuple(k) == (label,)
        ))

    tok0 = total("tdn_gen_tokens_total")
    eos_retired0 = total("tdn_gen_requests_retired_total", "eos")
    max_retired0 = total("tdn_gen_requests_retired_total", "max_tokens")
    prompts = _prompts(4, seed=4)
    base = np.asarray(generate(PARAMS, CFG, prompts, N))
    eos = int(base[0, N // 2])
    sched = _sched(slots=2, eos_id=eos)
    try:
        sched.submit(prompts)
        # TTFT recorded per row, and the histogram family moved.
        assert len(sched.ttft_recent) == 4
        m = REGISTRY.get("tdn_gen_ttft_seconds")
        assert m is not None
        # Retire reasons: row 0 hit the stop token, so the eos counter
        # moved; tokens counter moved by every emitted token.
        assert total("tdn_gen_requests_retired_total", "eos") > eos_retired0
        assert total("tdn_gen_requests_retired_total",
                     "max_tokens") >= max_retired0
        assert total("tdn_gen_tokens_total") > tok0
        # The runtime sampler publishes the slot gauges.
        sampler = RuntimeSampler()
        sampler.add_generation_scheduler(sched)
        sampler.add_batcher(sched, method="Generate")
        sampler.sample_once()
        occ = REGISTRY.get("tdn_gen_slot_occupancy_ratio")
        assert occ is not None
        vals = {tuple(k): c.value for k, c in occ.samples()}
        assert 0.0 < list(vals.values())[0] <= 1.0
        assert REGISTRY.get("tdn_gen_slots_active") is not None
        assert sched.slot_steps_total <= sched.steps_total * sched.slots
    finally:
        sched.close()


def test_traced_request_records_prefill_and_decode_spans():
    from tpu_dist_nn.obs.trace import TRACER

    span = TRACER.start("rpc.Generate")
    assert span.ctx.sampled
    sched = _sched(slots=2)
    try:
        sched.submit(_prompts(1, seed=5), ctx=span.ctx)
    finally:
        span.end()
        sched.close()
    names = {
        s.name for s in TRACER.snapshot()
        if s.trace_id == span.ctx.trace_id
    }
    assert {"queue_wait", "prefill", "decode"} <= names
    # One span a request, none a step: the launches it rode are
    # attributes of its `decode` span.
    assert "decode.step" not in names


# ------------------------------------------------------------ admission


def test_shed_at_watermark_and_oversized_admitted_when_empty():
    from tpu_dist_nn.utils.errors import ResourceExhaustedError

    # One slow slot: the first request occupies it for ~budget * cost
    # seconds, so later arrivals deterministically queue behind it.
    sched = _fake_sched(step_cost=0.05, slots=1, max_pending_rows=2)
    outs, errs = [], []

    def call(rows):
        try:
            outs.append(sched.submit(rows))
        except Exception as e:  # noqa: BLE001 — collected
            errs.append(e)

    try:
        t1 = threading.Thread(target=call, args=(_prompts(1, seed=6),))
        t1.start()
        deadline = time.monotonic() + 5
        while sched.rows_total < 1 and time.monotonic() < deadline:
            time.sleep(0.001)  # row 1 resident in the slot
        # 3 rows against an EMPTY queue: oversized vs the watermark but
        # admitted anyway (the watermark bounds backlog, not size).
        t2 = threading.Thread(target=call, args=(_prompts(3, seed=7),))
        t2.start()
        deadline = time.monotonic() + 5
        while sched.pending_rows < 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        # Now the queue is past the watermark: the next submit sheds.
        with pytest.raises(ResourceExhaustedError, match="watermark"):
            sched.submit(_prompts(1, seed=8))
        assert sched.shed_total == 1
        t1.join(30)
        t2.join(30)
        assert len(outs) == 2 and not errs
    finally:
        sched.close()


def test_close_fails_pending_over_and_post_close_submit_raises():
    from tpu_dist_nn.utils.errors import UnavailableError

    sched = _fake_sched(step_cost=0.05, slots=1)
    errs, oks = [], []

    def caller(i):
        try:
            oks.append(sched.submit(_prompts(1, seed=i)))
        except Exception as e:  # noqa: BLE001 — collected
            errs.append(e)

    threads = [
        threading.Thread(target=caller, args=(i,)) for i in range(4)
    ]
    for t in threads:
        t.start()
    time.sleep(0.08)  # first request resident, rest pending
    sched.close()
    for t in threads:
        t.join(20)
    # Resident work finished; still-pending waiters failed over.
    assert len(oks) >= 1
    assert len(errs) >= 1
    assert all(isinstance(e, UnavailableError) for e in errs)
    with pytest.raises(UnavailableError):
        sched.submit(_prompts(1, seed=9))


# ------------------------------------------------------------ endpoint


def test_serve_continuous_loopback_parity_and_counters():
    from tpu_dist_nn.serving import GrpcClient, serve_lm_generate

    prompts = _prompts(5, seed=10)
    base = np.asarray(generate(PARAMS, CFG, prompts, 6))
    eos = int(base[0, 2])
    ref = np.asarray(generate(PARAMS, CFG, prompts, 6, eos_id=eos))
    server, port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=6, prompt_len=T, host="127.0.0.1",
        gen_slots=3, eos_id=eos, warm_rows=1,
    )
    try:
        assert server.scheduler is not None  # auto => continuous
        client = GrpcClient(f"127.0.0.1:{port}")
        out = client.generate(prompts)
        np.testing.assert_array_equal(out[:, :T], prompts)
        np.testing.assert_array_equal(out[:, T:], ref)
        s = server.scheduler
        assert s.rows_total == 5 and s.retired_total == 5
        assert s.steps_total == s.batches_total > 0
        client.close()
    finally:
        server.stop(0)
    # stop() closed the scheduler: its loop thread is gone.
    assert not server.scheduler._thread.is_alive()


def test_serve_scheduler_flag_validation():
    from tpu_dist_nn.serving import serve_lm_generate

    with pytest.raises(ValueError, match="single-chip"):
        serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
            num_stages=2, scheduler="continuous", host="127.0.0.1",
        )
    with pytest.raises(ValueError, match="scheduler"):
        serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
            scheduler="orca", host="127.0.0.1",
        )
    with pytest.raises(ValueError, match="eos_id"):
        serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
            num_stages=2, eos_id=3, host="127.0.0.1",
        )
    # coalesce=False keeps its documented lock-path meaning: auto
    # resolves to static (server.batcher is None), and an EXPLICIT
    # continuous request rejects the combination.
    with pytest.raises(ValueError, match="coalesce"):
        serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
            scheduler="continuous", coalesce=False, host="127.0.0.1",
        )
    server, _port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
        coalesce=False, host="127.0.0.1",
    )
    try:
        assert server.scheduler is None and server.batcher is None
    finally:
        server.stop(0)


def test_cli_lm_flags_validated_eagerly():
    from tpu_dist_nn.cli import main

    # Bad eos byte id fails before any training happens.
    assert main([
        "--platform", "cpu", "lm", "--steps", "1", "--eos-id", "300",
    ]) != 0
    # Continuous x pipelined serving is rejected up front.
    assert main([
        "--platform", "cpu", "lm", "--steps", "1",
        "--serve-generate", "0", "--serve-stages", "2",
        "--scheduler", "continuous",
    ]) != 0
    # eos through the pipelined serve placement is rejected up front.
    assert main([
        "--platform", "cpu", "lm", "--steps", "1",
        "--serve-generate", "0", "--serve-stages", "2",
        "--eos-id", "0",
    ]) != 0


def test_cli_warmup_lm_generation_kernels(capsys):
    import json

    from tpu_dist_nn.cli import main

    rc = main([
        "--platform", "cpu", "warmup", "--lm", "--d-model", "16",
        "--heads", "2", "--layers", "2", "--seq-len", "24",
        "--gen-slots", "2", "--serve-prompt-len", "6",
        "--serve-new-tokens", "4",
    ])
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["warmed_kernels"] == [
        "prefill_chunk_into_cache", "decode_step_slots"
    ]
    assert report["gen_slots"] == 2
    # Without --lm, the engine path still requires --config.
    assert main(["--platform", "cpu", "warmup"]) != 0


# ------------------------------------------------ prefix cache + chunking


def _shared_prefix_prompts(n, header_len, seed=20):
    """Prompts sharing an exact ``header_len``-token header with unique
    tails — the workload shape the prefix pool exists for."""
    rng = np.random.default_rng(seed)
    header = rng.integers(0, CFG.vocab_size, header_len)
    return np.stack([
        np.concatenate([header, rng.integers(0, CFG.vocab_size, T - header_len)])
        for _ in range(n)
    ]).astype(np.int32)


def test_prefix_cache_greedy_bit_parity_including_eos():
    # THE acceptance anchor: temperature=0 outputs bit-identical with
    # prefix cache + chunked prefill ON vs OFF — including EOS
    # early-retire/pad semantics — on prompts that actually share a
    # header (so the ON arm really serves hits, asserted below), with
    # more rows than slots so queueing and slot reuse are on the path.
    prompts = _shared_prefix_prompts(6, header_len=4)
    base = np.asarray(generate(PARAMS, CFG, prompts, N))
    eos = int(base[0, N // 2])
    want = np.asarray(generate(PARAMS, CFG, prompts, N, eos_id=eos))

    off = _sched(slots=2, eos_id=eos)
    on = _sched(slots=2, eos_id=eos, prefix_cache_blocks=3, prefill_chunk=4)
    try:
        out_off = off.submit(prompts)
        # Sequential single-row submits on the ON arm so later rows
        # deterministically hit the tiers the first row inserted.
        rows_on = [on.submit(prompts[i:i + 1])[0] for i in range(6)]
        np.testing.assert_array_equal(out_off[:, T:], want)
        for i in range(6):
            np.testing.assert_array_equal(rows_on[i][T:], want[i])
        assert on.prefix_hits_total >= 4  # rows 2.. hit the header tier
        assert on.prefix_misses_total >= 1
        assert off.prefix_hits_total == 0 and off.prefix_blocks == 0
    finally:
        off.close()
        on.close()


def test_chunked_prefill_parity_with_monolithic():
    # Chunk sizes that divide T, don't divide T, and exceed T must all
    # produce the monolithic scheduler's exact greedy tokens.
    prompts = _prompts(3, seed=21)
    ref = np.asarray(generate(PARAMS, CFG, prompts, N))
    for chunk in (1, 3, T, T + 5):
        sched = _sched(slots=2, prefill_chunk=chunk)
        try:
            out = sched.submit(prompts)
            np.testing.assert_array_equal(out[:, T:], ref)
        finally:
            sched.close()


def test_cow_isolation_decode_never_mutates_shared_block():
    # A hit COPIES the pool block into the request slot; the decoding
    # request then writes only its own slot. The block's bytes must be
    # bit-identical before and after other requests decode FROM it —
    # and a later hit must still produce exact outputs.
    prompts = _shared_prefix_prompts(3, header_len=6, seed=22)
    prompts[1:] = prompts[0]  # identical prompts: deepest-tier hits
    ref = np.asarray(generate(PARAMS, CFG, prompts[:1], N))
    sched = _sched(slots=1, prefix_cache_blocks=1, prefill_chunk=4)
    try:
        out0 = sched.submit(prompts[0:1])
        np.testing.assert_array_equal(out0[0, T:], ref[0])
        assert sched.prefix_blocks_used == 1
        block_slot = sched.slots  # pool block 0 lives at slot index S
        k_before = np.asarray(sched._cache["k"][:, block_slot]).copy()
        v_before = np.asarray(sched._cache["v"][:, block_slot]).copy()
        out1 = sched.submit(prompts[1:2])  # hit: COW copy + decode
        np.testing.assert_array_equal(out1[0, T:], ref[0])
        assert sched.prefix_hits_total == 1
        np.testing.assert_array_equal(
            np.asarray(sched._cache["k"][:, block_slot]), k_before
        )
        np.testing.assert_array_equal(
            np.asarray(sched._cache["v"][:, block_slot]), v_before
        )
        out2 = sched.submit(prompts[2:3])  # still exact after reuse
        np.testing.assert_array_equal(out2[0, T:], ref[0])
    finally:
        sched.close()


def test_prefix_pool_refcount_lifecycle():
    from tpu_dist_nn.serving.continuous import PrefixCachePool

    pool = PrefixCachePool(2)
    b0, ev = pool.insert(b"aa", 4)
    assert (b0, ev) == (0, False) and pool.used == 1
    # A hit takes a reference; a referenced block is never evicted.
    hit = pool.lookup([(4, b"aa")])
    assert hit == (0, 4) and pool.refs(0) == 1 and pool.hits_total == 1
    b1, _ = pool.insert(b"bb", 4)
    assert b1 == 1
    blk, ev = pool.insert(b"cc", 4)  # full: only refcount-0 "bb" evicts
    assert ev and blk == 1 and pool.evictions_total == 1
    assert pool.lookup([(4, b"bb")]) is None  # evicted
    assert pool.misses_total == 1
    pool.release(0)  # release "aa"
    assert pool.refs(0) == 0
    blk, ev = pool.insert(b"dd", 4)  # now "aa" (LRU refcount-0) evicts
    assert ev and blk == 0
    with pytest.raises(AssertionError):
        pool.release(0)  # unreferenced: double-release is a bug
    # All blocks referenced -> insertion skipped, no eviction.
    pool.lookup([(4, b"cc")])
    pool.lookup([(4, b"dd")])
    assert pool.insert(b"ee", 4) == (None, False)
    with pytest.raises(AssertionError):
        pool.clear()  # live refs: clear would strand them
    pool.release(1)
    pool.release(0)
    pool.clear()
    assert pool.used == 0 and pool.hits_total == 3  # counters survive


def test_prefix_metrics_counters_and_sampler_gauge():
    from tpu_dist_nn.obs import RuntimeSampler
    from tpu_dist_nn.obs.registry import REGISTRY

    def total(name):
        m = REGISTRY.get(name)
        return 0.0 if m is None else float(
            sum(c.value for _, c in m.samples())
        )

    hits0 = total("tdn_prefix_cache_hits_total")
    miss0 = total("tdn_prefix_cache_misses_total")
    sched = _fake_sched(slots=1, prefix_cache_blocks=1, prefill_chunk=4)
    try:
        p = _prompts(1, seed=23)
        sched.submit(p)           # miss + tier insert
        sched.submit(p)           # deepest-tier hit
        assert sched.prefix_misses_total == 1
        assert sched.prefix_hits_total == 1
        assert sched.prefix_blocks_used == 1
        assert 0.0 < sched.prefix_hit_ratio < 1.0
        assert total("tdn_prefix_cache_hits_total") == hits0 + 1
        assert total("tdn_prefix_cache_misses_total") == miss0 + 1
        sampler = RuntimeSampler()
        sampler.add_generation_scheduler(sched)
        sampler.add_batcher(sched, method="Generate")
        sampler.sample_once()
        g = REGISTRY.get("tdn_prefix_cache_blocks_used")
        assert g is not None
        assert [c.value for _, c in g.samples()] == [1.0]
    finally:
        sched.close()


def test_prefill_chunk_spans_recorded_and_profiled():
    from tpu_dist_nn.obs.profile import profile_snapshot
    from tpu_dist_nn.obs.trace import TRACER

    span = TRACER.start("rpc.Generate")
    sched = _sched(slots=1, prefill_chunk=3)
    try:
        sched.submit(_prompts(1, seed=24), ctx=span.ctx)
    finally:
        span.end()
        sched.close()
    mine = [
        s for s in TRACER.snapshot() if s.trace_id == span.ctx.trace_id
    ]
    names = {s.name for s in mine}
    assert {"queue_wait", "prefill", "prefill.chunk", "decode"} <= names
    # ceil(8 / 3) chunks, each its own span, joined to the request trace.
    assert sum(1 for s in mine if s.name == "prefill.chunk") == 3
    # The /profile stage table picks the new span up as a stage.
    prof = profile_snapshot(TRACER)
    stages = {
        s["stage"] for s in prof["methods"]["Generate"]["stages"]
    }
    assert "prefill.chunk" in stages


def test_mid_prefill_fault_frees_slot_and_releases_ref():
    from tpu_dist_nn.testing import faults
    from tpu_dist_nn.utils.errors import InternalError

    # T=8, chunk=3: request 1 runs chunks 1-3 (inserting tiers 3 and
    # 6); request 2 hits tier 6 and its single suffix chunk is call 4
    # — which the plan faults. The fault must fail ONLY that request,
    # free its slot, and release its block reference so the pool can
    # evict again.
    sched = _fake_sched(slots=1, prefix_cache_blocks=2, prefill_chunk=3)
    sched.prefill_hook = faults.FaultPlan(at={4: faults.internal()}).fire
    p = _prompts(1, seed=25)
    try:
        sched.submit(p)
        assert sched.prefix_blocks_used == 2
        with pytest.raises(InternalError):
            sched.submit(p)
        assert sched.prefix_hits_total == 1
        assert sched.inflight_rows == 0  # slot freed
        assert all(
            sched._pool.refs(b) == 0 for b in range(sched.prefix_blocks)
        )  # the hit's reference was released
        # The scheduler keeps serving (call 5+ passes).
        out = sched.submit(p)
        assert out.shape == (1, T + N)
        assert sched.prefix_hits_total == 2
    finally:
        sched.close()


def test_drain_with_half_prefilled_slot_completes():
    # close() must let a slot that is MID-PREFILL finish its remaining
    # chunks and decode (the GracefulDrain in-flight contract), not
    # strand or fail it.
    sched = _fake_sched(chunk_cost=0.03, slots=1, prefill_chunk=2)
    outs, errs = [], []

    def caller():
        try:
            outs.append(sched.submit(_prompts(1, seed=26)))
        except Exception as e:  # noqa: BLE001 — collected
            errs.append(e)

    t = threading.Thread(target=caller)
    t.start()
    deadline = time.monotonic() + 5
    while sched.inflight_rows < 1 and time.monotonic() < deadline:
        time.sleep(0.002)  # bound to a slot, prefill still chunking
    assert sched.inflight_rows == 1
    sched.close(timeout=30.0)
    t.join(30)
    assert not errs and len(outs) == 1
    assert outs[0].shape == (1, T + N)
    assert sched.retired_total == 1


def test_scheduler_validates_prefix_chunk_contract():
    with pytest.raises(ValueError, match="prefill_chunk"):
        _fake_sched(prefill_chunk=0)
    with pytest.raises(ValueError, match="prefix_cache_blocks"):
        _fake_sched(prefix_cache_blocks=-1)
    # No cacheable tier: chunk spans the whole prompt, so the pool
    # could never hit — fail fast instead of reserving dead blocks.
    with pytest.raises(ValueError, match="cacheable tier"):
        _fake_sched(prefix_cache_blocks=1, prefill_chunk=T)
    # copy_fn only makes sense alongside the other injected kernels.
    with pytest.raises(ValueError, match="copy_fn"):
        ContinuousScheduler(
            PARAMS, CFG, slots=1, prompt_len=T, max_new_tokens=N,
            copy_fn=lambda cache, src, dst: cache,
        )


def test_serve_rejects_prefix_flags_on_static_scheduler():
    from tpu_dist_nn.serving import serve_lm_generate

    with pytest.raises(ValueError, match="continuous-scheduler"):
        serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
            scheduler="static", prefix_cache_blocks=2, host="127.0.0.1",
        )
    with pytest.raises(ValueError, match="continuous-scheduler"):
        serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=4, prompt_len=T,
            coalesce=False, prefill_chunk=4, host="127.0.0.1",
        )


def test_serve_loopback_with_prefix_cache_exact_and_accounted():
    from tpu_dist_nn.serving import GrpcClient, serve_lm_generate

    prompts = _shared_prefix_prompts(4, header_len=6, seed=27)
    ref = np.asarray(generate(PARAMS, CFG, prompts, 6))
    server, port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=6, prompt_len=T, host="127.0.0.1",
        gen_slots=2, warm_rows=1, prefix_cache_blocks=2, prefill_chunk=4,
    )
    try:
        client = GrpcClient(f"127.0.0.1:{port}")
        out = np.vstack([
            client.generate(prompts[i:i + 1]) for i in range(4)
        ])
        np.testing.assert_array_equal(out[:, T:], ref)
        s = server.scheduler
        assert s.prefix_hits_total >= 2  # shared header served from pool
        assert s.prefix_blocks_used >= 1
        client.close()
    finally:
        server.stop(0)


# ------------------------------------- what the schedule saves, by counts


def test_mixed_budgets_take_fewer_steps_than_run_to_completion():
    """16 rows on 4 slots, every other one wanting 2 tokens of 32: all
    retire, each slot-step yields a token somebody asked for, the step
    kernel is launched fewer times than run-to-completion's fixed trip
    count needs, and a row's first token is out before its last. The
    loop runs one launch ahead: the step behind a prompt's last chunk
    is launched before the chunk's token is read, so the first token is
    out before any step has been READ, and the gate holds the reads."""
    slots, rows, budget, short = 4, 16, 32, 2
    budgets = [short if i % 2 else budget for i in range(rows)]
    go = threading.Event()

    def wait_for_go(toks):
        assert go.wait(30.0)

    sched = _fake_sched(slots=slots, max_new_tokens=budget)
    sched.fetch_hook = wait_for_go
    try:
        streams = [
            sched.submit_stream(_prompts(1, seed=i), max_new_tokens=b)
            for i, b in enumerate(budgets)
        ]
        # The first row's first token is its prefill's: published while
        # no step has been read and nothing has retired.
        assert streams[0].next_event(30.0) == ("tokens", [1])
        assert sched.steps_total == 0 and sched.retired_total == 0
        go.set()
        got = []
        for stream in streams:
            tokens = [1] if stream is streams[0] else []
            while True:
                kind, data = stream.next_event(30.0)
                if kind == "end":
                    assert data["reason"] == "max_tokens", data
                    break
                tokens += data
            got.append(tokens)
    finally:
        go.set()
        sched.close()
    assert got == [list(range(1, b + 1)) for b in budgets]
    assert sched.retired_total == rows
    # A prefill yields a row's first token and every slot-step one more.
    assert sched.slot_steps_total == sum(b - 1 for b in budgets)
    # Run-to-completion decodes every batch of `slots` rows for the
    # whole budget (generate()'s scan has a fixed trip count).
    assert sched.steps_total < -(-rows // slots) * budget
    assert 0.0 < sched.slot_steps_total / (sched.steps_total * slots) <= 1.0
    # A budget's end is known before the launch that would pass it: no
    # lane was computed for nobody.
    assert sched.discarded_lanes_total == 0


@functools.lru_cache(maxsize=None)
def _prefilled_tokens(prompt_len, pool):
    """Tokens handed to the prefill kernel, and the pool's hit ratio,
    over 12 prompts that share all but their last 4 tokens, one after
    the other on a warm scheduler."""
    handed = []

    rng = np.random.default_rng(prompt_len)
    header = rng.integers(0, 64, prompt_len - 4)
    prompts = [
        np.concatenate([header, rng.integers(0, 64, 4)])[None, :]
        for _ in range(13)
    ]
    sched = _fake_sched(
        on_prefill=lambda tokens: handed.append(tokens.shape[1]),
        slots=4, prompt_len=prompt_len, max_new_tokens=8,
        prefix_cache_blocks=4 if pool else 0,
        prefill_chunk=8 if pool else None,
    )
    try:
        sched.submit(prompts[0])  # the header enters the pool
        warm = len(handed)
        hits0, misses0 = sched.prefix_hits_total, sched.prefix_misses_total
        for prompt in prompts[1:]:
            sched.submit(prompt)
        hits = sched.prefix_hits_total - hits0
        misses = sched.prefix_misses_total - misses0
    finally:
        sched.close()
    return sum(handed[warm:]), hits / max(hits + misses, 1)


@pytest.mark.parametrize("prompt_len", [16, 32])
def test_warm_prefix_pool_hits_and_prefills_fewer_tokens(prompt_len):
    on, hit_ratio = _prefilled_tokens(prompt_len, pool=True)
    off, _ = _prefilled_tokens(prompt_len, pool=False)
    assert hit_ratio > 0.5
    assert off == 12 * prompt_len  # the whole prompt, every request
    assert on < off


def test_prefilled_tokens_with_the_pool_do_not_grow_with_prompt_length():
    # The uncached remainder is the tail's chunk whatever the header's
    # length: chunked prefill keeps a hit's cost flat.
    short, _ = _prefilled_tokens(16, pool=True)
    long_, _ = _prefilled_tokens(32, pool=True)
    assert long_ <= short


# ------------------------------------------ one launch ahead (ISSUE 30)
#
# The loop launches step N+1 from the tokens still on the device before
# it reads step N. Held to the serial order (a launch hook that reads
# everything still unread before the next step is launched, which is
# what the loop did before), the same submissions have to give every
# stream the same tokens.

EOS = 5


def _chain(first, budget, eos=None, prompt_len=T):
    """What the chain kernels below give one stream, worked out by
    hand in the serial order: token, then the next from it."""
    tokens, pos = [first], prompt_len
    while len(tokens) < budget and tokens[-1] != eos:
        tokens.append((tokens[-1] * 3 + pos) % 13 + 1)
        pos += 1
    return tokens


def _first(prompt):
    return int(prompt[0, -1]) % 5 + 1


def _chain_sched(launches=None, on_step=None, **kw):
    """Stub kernels whose tokens depend on what they are handed: a
    prompt's first token on its last id, every next token on the one
    before and its position. ``launches`` takes each step's (pos,
    active) as launched; ``on_step()`` can hold the device."""

    def fake_prefill(params, cache, slot, tokens, start, key):
        return np.int32(int(tokens[0, -1]) % 5 + 1), cache

    def fake_step(params, cache, pos, active, tok, key):
        if launches is not None:
            launches.append((np.array(pos), np.array(active)))
        if on_step is not None:
            on_step()
        return (np.asarray(tok) * 3 + np.asarray(pos)) % 13 + 1, cache

    kw.setdefault("slots", 3)
    kw.setdefault("prompt_len", T)
    kw.setdefault("max_new_tokens", 12)
    return ContinuousScheduler(
        None, None, prefill_fn=fake_prefill, step_fn=fake_step, **kw)


def _hold_to_serial_order(sched):
    """Every result is read before the next step is launched: the
    order of the loop before it ran ahead."""
    sched.launch_hook = lambda tok: sched._land()


def _drain(stream, take=None):
    """A stream's tokens and how it ended; with ``take`` the client
    reads that many and cancels, as the wire's clients do."""
    tokens = []
    while True:
        ev = stream.next_event(30.0)
        assert ev is not None, "a stream stalled"
        kind, data = ev
        if kind == "end":
            return tokens, data["reason"]
        tokens += data
        if take is not None and len(tokens) >= take:
            stream.cancel()
            return tokens[:take], "cancelled"


def _ahead_budgets(sched):
    budgets = [1, 2, 3, 5, 8, 12, 4, 1, 7]
    prompts = [_prompts(1, seed=40 + i) for i in range(len(budgets))]
    streams = [sched.submit_stream(p, max_new_tokens=b)
               for p, b in zip(prompts, budgets)]
    got = [_drain(s) for s in streams]
    assert got == [(_chain(_first(p), b), "max_tokens")
                   for p, b in zip(prompts, budgets)]
    return got


def _ahead_eos(sched):
    prompts = [_prompts(1, seed=60 + i) for i in range(10)]
    streams = [sched.submit_stream(p) for p in prompts]
    got = [_drain(s) for s in streams]
    want = [_chain(_first(p), 12, eos=EOS) for p in prompts]
    assert [g[0] for g in got] == want
    # Streams that end at their first token, mid-stream and by budget.
    ends = {(len(w), w[-1] == EOS) for w in want}
    assert any(n == 1 and e for n, e in ends)
    assert any(1 < n < 12 and e for n, e in ends)
    assert any(not e for n, e in ends)
    return got


def _ahead_cancel(sched):
    prompts = [_prompts(1, seed=80 + i) for i in range(6)]
    streams = [sched.submit_stream(p) for p in prompts]
    takes = [3, None, 5, None, 1, 4]
    got = [_drain(s, take=k) for s, k in zip(streams, takes)]
    assert [g[0] for g in got] == [
        _chain(_first(p), 12)[:k] for p, k in zip(prompts, takes)]
    return got


def _ahead_preempt(sched, permits):
    victim_p, crit_p = _prompts(1, seed=90), _prompts(1, seed=91)
    victim = sched.submit_stream(victim_p, slo_class="best_effort")
    permits.release(4)  # the device stops inside its fifth step
    head = []
    while len(head) < 3:  # mid-decode when the critical row arrives
        head += victim.next_event(30.0)[1]
    crit = sched.submit_stream(crit_p, slo_class="critical")
    permits.release(10_000)
    got_crit = _drain(crit)
    tail, reason = _drain(victim)
    assert sched.preempted_total == 1
    got = [(head + tail, reason), got_crit]
    assert got == [(_chain(_first(victim_p), 12), "max_tokens"),
                   (_chain(_first(crit_p), 12), "max_tokens")]
    return got


def _ahead_guard(sched):
    """Slot 1's lane is indicted in the first step that carries both
    slots: that stream fails alone before the step's token ships."""
    from tpu_dist_nn.serving import integrity

    wrapped = sched._step

    def poisoned(params, cache, pos, active, tok, key, *on_device):
        toks, _ok, cache = wrapped(params, cache, pos, active, tok, key,
                                   *on_device)
        ok = np.ones(len(active), bool)
        if active[0] and active[1]:
            ok[1] = False
        return toks, ok, cache

    sched._step = poisoned
    prev, integrity.GUARD.enabled = integrity.GUARD.enabled, True
    try:
        prompts = [_prompts(1, seed=95 + i) for i in range(2)]
        streams = [sched.submit_stream(p) for p in prompts]
        got = [_drain(s) for s in streams]
    finally:
        integrity.GUARD.enabled = prev
    assert got == [(_chain(_first(prompts[0]), 12), "max_tokens"),
                   ([_first(prompts[1])], "error")]
    return got


def _ahead_pool_and_chunks(sched):
    rng = np.random.default_rng(7)
    header = rng.integers(0, 64, T - 2)
    prompts = [np.concatenate([header, rng.integers(0, 64, 2)])[None, :]
               for _ in range(6)]
    got = [_drain(sched.submit_stream(prompts[0]))]  # the header goes in
    streams = [sched.submit_stream(p) for p in prompts[1:]]
    got += [_drain(s) for s in streams]
    assert sched.prefix_hits_total >= 4
    assert sched.prefill_chunks_total > len(prompts)
    assert got == [(_chain(_first(p), 12), "max_tokens") for p in prompts]
    return got


def _ahead_gpt2(sched):
    """The real kernels at a toy size, greedy: chunked prefill, the
    prefix pool, mixed budgets, an EOS id the model does produce."""
    prompts = _shared_prefix_prompts(7, header_len=6)
    budgets = [N, 3, 1, N, 6, 2, N]
    got = [_drain(sched.submit_stream(prompts[:1], max_new_tokens=N))]
    streams = [sched.submit_stream(prompts[i:i + 1], max_new_tokens=b)
               for i, b in list(enumerate(budgets))[1:]]
    got += [_drain(s) for s in streams]
    assert sched.prefix_hits_total >= 1
    return got


@functools.lru_cache(maxsize=None)
def _gpt2_eos():
    """A token the toy model produces mid-stream on these prompts."""
    ref = np.asarray(generate(
        PARAMS, CFG, jnp.asarray(_shared_prefix_prompts(7, header_len=6)), N))
    return int(ref[0, 4])


AHEAD_CASES = {
    "budgets_end_on_different_steps": (_ahead_budgets, {}),
    "eos_mid_stream": (_ahead_eos, {"eos_id": EOS}),
    "client_cancel": (_ahead_cancel, {}),
    "preemption_with_replay": (_ahead_preempt, {"slots": 1}),
    "guard_trips_one_slot": (_ahead_guard, {"slots": 2}),
    "prefix_pool_hit_and_chunked_prefill": (
        _ahead_pool_and_chunks,
        {"prefix_cache_blocks": 2, "prefill_chunk": 3}),
    "gpt2_family": (_ahead_gpt2, None),
}


@pytest.mark.parametrize("case", AHEAD_CASES)
def test_streams_get_the_serial_orders_tokens(case):
    drive, kw = AHEAD_CASES[case]
    runs = {}
    for order in ("serial", "ahead"):
        if kw is None:
            sched = _sched(slots=3, prefix_cache_blocks=2, prefill_chunk=3,
                           eos_id=_gpt2_eos())
        elif drive is _ahead_preempt:
            permits = threading.Semaphore(0)
            sched = _chain_sched(
                on_step=lambda p=permits: p.acquire(timeout=30.0), **kw)
        else:
            sched = _chain_sched(**kw)
        if order == "serial":
            _hold_to_serial_order(sched)
        try:
            if drive is _ahead_preempt:
                runs[order] = drive(sched, permits)
            else:
                runs[order] = drive(sched)
        finally:
            sched.close()
        if order == "serial":
            # Held to the serial order nothing is launched ahead, and
            # an EOS is read before the launch that would carry it on.
            assert sched.overlapped_total == 0
            # (A cancel comes from another thread whenever it comes:
            # it can fall between any loop's reap and its launch.)
            assert (sched.discarded_lanes_total == 0
                    or case == "client_cancel")
        else:
            assert sched.overlapped_total > 0
    assert runs["ahead"] == runs["serial"]
    if kw is None:
        reasons = [r for _, r in runs["ahead"]]
        assert "eos" in reasons and "max_tokens" in reasons


def test_of_n_step_launches_all_but_the_first_are_made_ahead():
    sched = _chain_sched(slots=2)
    try:
        sched.submit(_prompts(1, seed=30))  # budget 12: 11 steps
    finally:
        sched.close()
    assert sched.steps_total == 11
    assert sched.overlapped_total == 10
    assert sched.discarded_lanes_total == 0
    records = [r for r in _iteration_records(sched)]
    assert records[-1]["steps_ahead"] == 10
    ahead = [b["steps_ahead"] - a["steps_ahead"]
             for a, b in zip(records, records[1:])]
    # One more an iteration, but for the last, which only reads.
    assert ahead == [1] * 10 + [0]


def _iteration_records(sched):
    from tpu_dist_nn.obs.trace import ITER_FIELDS, ITERATIONS

    me = sched.loop_totals()["sched"]
    return [dict(zip(ITER_FIELDS, r)) for r in ITERATIONS.snapshot()
            if r[ITER_FIELDS.index("sched")] == me]


def test_no_launch_has_a_slot_active_past_its_budget():
    """A slot whose budget the launches so far fill is not in the next
    one: no step writes a row past the extent, read or unread."""
    launches = []
    budgets = [1, 2, 3, 5, 8, 12, 4, 1, 7, 12, 2]
    sched = _chain_sched(launches=launches, slots=3)
    try:
        streams = [
            sched.submit_stream(_prompts(1, seed=i), max_new_tokens=b)
            for i, b in enumerate(budgets)
        ]
        assert all(_drain(s)[1] == "max_tokens" for s in streams)
    finally:
        sched.close()
    assert sched.slot_steps_total == sum(b - 1 for b in budgets)
    assert sum(int(a.sum()) for _, a in launches) == sched.slot_steps_total
    # The last decode of the longest budget writes position T + 12 - 2.
    assert max(int(p[a].max()) for p, a in launches if a.any()) == T + 10
    assert sched.discarded_lanes_total == 0


def test_one_discarded_lane_an_eos_or_cancel_and_none_a_budget_end():
    launches = []
    sched = _chain_sched(launches=launches, slots=2, eos_id=EOS)
    prompts = [_prompts(1, seed=60 + i) for i in range(10)]
    try:
        got = [_drain(sched.submit_stream(p)) for p in prompts]
    finally:
        sched.close()
    # One at a time: an EOS is read while the step launched behind it
    # still carries the lane, unless it was the budget's last token.
    eos = sum(r == "eos" and len(t) < 12 for t, r in got)
    assert eos >= 3 and any(r == "max_tokens" for _, r in got)
    assert sched.discarded_lanes_total == eos
    # Computed, not shipped, and no slot-step: every lane launched is
    # a slot-step or a discarded lane.
    assert (sum(int(a.sum()) for _, a in launches)
            == sched.slot_steps_total + sched.discarded_lanes_total)
    # A client that cancels is found one launch late too: the device
    # is held inside the step behind the two tokens the client wants.
    permits = threading.Semaphore(0)
    sched = _chain_sched(on_step=lambda: permits.acquire(timeout=30.0),
                         slots=2)
    try:
        stream = sched.submit_stream(_prompts(1, seed=81))
        permits.release(2)
        tokens, _ = _drain(stream, take=2)
        assert len(tokens) == 2
        permits.release(10_000)
        deadline = time.monotonic() + 10
        while sched.slots_active and time.monotonic() < deadline:
            time.sleep(0.002)
        assert sched.slots_active == 0
    finally:
        permits.release(10_000)
        sched.close()
    assert sched.discarded_lanes_total == 1
    assert sched.steps_total == 3 and sched.slot_steps_total == 2


def test_fetch_fault_with_a_launch_outstanding_is_a_device_fault():
    """The step launched behind the one whose fetch failed took the
    same donated buffer: it is dropped unread, every resident fails
    over, the pool goes cold, and the scheduler keeps serving."""
    from tpu_dist_nn.testing import faults
    from tpu_dist_nn.utils.errors import InternalError

    sched = _chain_sched(slots=2, prefix_cache_blocks=2, prefill_chunk=3)
    p = _prompts(2, seed=33)
    try:
        assert sched.submit(p[:1]).shape == (1, T + 12)
        assert sched.prefix_blocks_used == 2
        steps0 = sched.steps_total
        unread = []

        def fetch(toks):
            unread.append(len(sched._unread))
            plan.fire(toks)

        plan = faults.FaultPlan(at={3: faults.internal()})
        sched.fetch_hook = fetch
        with pytest.raises(InternalError):
            sched.submit(p)
        # A step was out behind the one that failed, and went unread.
        assert unread[2] >= 1
        assert sched.steps_total == steps0 + 2
        assert sched.inflight_rows == 0 and not sched._unread
        assert sched.prefix_blocks_used == 0  # the device fault's mark
        out = sched.submit(p[1:])
        np.testing.assert_array_equal(
            out[0, T:], _chain(_first(p[1:]), 12))
    finally:
        sched.close()


def test_the_loop_waits_for_the_step_it_launched_and_reads_it_a_launch_late():
    """The order of the loop on a device that runs behind the host:
    step n+1 is launched when step n has FINISHED (the one place the
    loop waits) and before step n is READ, and a prompt's first token
    is read behind the step launched after its chunk."""
    log = []

    class OnDevice:
        """A result still on the device: waiting for it and reading it
        are two things, as for a jax.Array."""

        def __init__(self, name, value):
            self.name, self.value = name, value

        def block_until_ready(self):
            log.append(("finished", self.name))
            return self

        def __array__(self, dtype=None, copy=None):
            log.append(("read", self.name))
            return np.asarray(self.value, dtype)

        def __int__(self):
            log.append(("read", self.name))
            return int(self.value)

    steps = iter(range(1000))

    def fake_prefill(params, cache, slot, tokens, start, key):
        log.append(("launch", "chunk"))
        return OnDevice("chunk", np.int32(1)), cache

    def fake_step(params, cache, pos, active, tok, key):
        name = next(steps)
        log.append(("launch", name))
        return OnDevice(name, np.asarray(tok) + 1), cache

    sched = ContinuousScheduler(
        None, None, prefill_fn=fake_prefill, step_fn=fake_step, slots=2,
        prompt_len=T, max_new_tokens=5)
    try:
        out = sched.submit(_prompts(1, seed=31))
    finally:
        sched.close()
    assert list(out[0, T:]) == [1, 2, 3, 4, 5]
    # (An injected step is handed its tokens whole, so the stub's
    # wrapper takes the value for the merge the real program does on
    # the device: the reads the loop itself makes are the LAST of each.)
    log = [e for i, e in enumerate(log)
           if e[0] != "read" or e not in log[i + 1:]]
    order = [e for e in log if e[0] != "read" or e[1] != "chunk"]
    launches = [i for i, e in enumerate(order) if e[0] == "launch"
                and e[1] != "chunk"]
    for n, at in enumerate(launches[1:], start=1):
        before = order[:at]
        assert ("finished", n - 1) in before  # waited for, then launched
        # ... from tokens the host has not taken for itself: the read
        # that accounts and publishes step n-1 comes behind the launch.
        assert order.index(("finished", n - 1)) < at
        later = order[at:]
        assert ("read", n - 1) in later
    assert sched.overlapped_total == len(launches) - 1 == 3


@pytest.mark.parametrize("chunk_s, first_goes_first", [(0.0, True),
                                                       (0.002, True)])
def test_a_first_token_goes_ahead_of_the_round_when_its_chunk_is_through(
        chunk_s, first_goes_first):
    """A round's handlers are woken together, behind the first token of
    a prompt whose chunk is through by then or soon (as long again as
    it has had since its launch). (That a LONG chunk lets the round go
    first cannot be shown through the injected seams, whose step reads
    the chunk's token when it is launched; on the chip it is the
    long-document cell's `itl_p95_ms`: PERF.md section 6, PR 30.)"""
    woken = []

    class Chunk:
        """The chunk's token, on a device that needs `chunk_s` for it."""

        def __init__(self):
            self.done_at = time.monotonic() + chunk_s

        def is_ready(self):
            return time.monotonic() >= self.done_at

        def __int__(self):
            time.sleep(max(0.0, self.done_at - time.monotonic()))
            return 1

    def fake_prefill(params, cache, slot, tokens, start, key):
        woken.clear()  # what follows is this chunk's iteration
        return Chunk(), cache

    def fake_step(params, cache, pos, active, tok, key):
        time.sleep(0.005)
        return np.asarray(tok) + 1, cache

    sched = ContinuousScheduler(
        None, None, prefill_fn=fake_prefill, step_fn=fake_step, slots=2,
        prompt_len=T, max_new_tokens=400)
    try:
        old = sched.submit_stream(_prompts(1, seed=1))
        assert old.next_event(10.0)[0] == "tokens"  # decoding now
        wake = type(old).wake
        old.wake = lambda: (woken.append("round"), wake(old))[1]
        new = sched.submit_stream(_prompts(1, seed=2))
        publish = type(new).publish

        def spy(tokens, **kw):
            if "first" not in woken:
                woken.append("first")
            return publish(new, tokens, **kw)

        new.publish = spy
        assert new.next_event(10.0)[0] == "tokens"
        new.cancel()
        old.cancel()
    finally:
        sched.close()
    at = woken.index("first")
    # The round published beside the chunk's launch was woken before
    # the first token only where the chunk was long.
    assert ("round" in woken[:at]) is not first_goes_first
