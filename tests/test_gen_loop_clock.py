"""The generation scheduler's loop on the record (ISSUE 24): phase
accounting that partitions the loop thread's wall time, the bounded
process-global ring of iteration records, queue wait / prefill wait /
stream-out lag as cumulative counters, the `decode` span's ride
attributes in place of per-step `decode.step` spans, the
`tdn_gen_loop_*` families, and the `named_scope`s of the step programs.
Since ISSUE 35 also: the loop thread's CPU time inside the publish, the
process's,
the time the device was starved, which iterations a `jax.profiler`
capture holds, and the scheduler's own counters as ring columns.

Driven with stub kernels on the CPU: what is asserted is where time is
booked, never how much a device would take.
"""

import re
import threading
import time

import numpy as np
import pytest

from tpu_dist_nn.obs.registry import Registry
from tpu_dist_nn.obs.runtime import RuntimeSampler
from tpu_dist_nn.obs.trace import (
    ITER_FIELDS,
    ITERATIONS,
    LOOP_HOST_PHASES,
    LOOP_PHASES,
    LOOP_WAIT_PHASES,
    TRACER,
    IterationRing,
)
from tpu_dist_nn.serving.continuous import ContinuousScheduler

T, N = 8, 10
CUMULATIVE = ITER_FIELDS[ITER_FIELDS.index("active_slots") + 1:]
# No capture runs, no stream is cancelled, and the stub kernels are no
# model with an attention kernel or a step that skips K/V tiles.
NOT_IN_THIS_RUN = ("captured", "discarded_lanes", "attend_kernel_chunks",
                   "kv_tiles_visited", "kv_tiles_skipped")


def _fake_sched(step_cost=0.0, readings=None, **kw):
    """``readings``: a list that takes the loop thread's reading of the
    clock inside every call into a stub kernel."""

    def fake_prefill(params, cache, slot, tokens, start, key):
        if readings is not None:
            readings.append(time.monotonic_ns())
        return np.int32(1), cache

    def fake_step(params, cache, pos, active, tok, key):
        if step_cost:
            time.sleep(step_cost)
        if readings is not None:
            readings.append(time.monotonic_ns())
        return np.asarray(tok) + 1, cache

    kw.setdefault("slots", 2)
    kw.setdefault("prompt_len", T)
    kw.setdefault("max_new_tokens", N)
    return ContinuousScheduler(
        None, None, prefill_fn=fake_prefill, step_fn=fake_step, **kw
    )


def _prompts(n, seed=0):
    return np.random.default_rng(seed).integers(0, 64, (n, T))


def _mine(sched):
    """This scheduler's iteration records in the process-global ring."""
    me = sched.loop_totals()["sched"]
    return [dict(zip(ITER_FIELDS, r)) for r in ITERATIONS.snapshot()
            if r[ITER_FIELDS.index("sched")] == me]


# ------------------------------------------------------------ phases


def test_phase_names_partition_into_host_and_wait():
    assert set(LOOP_HOST_PHASES) | set(LOOP_WAIT_PHASES) == set(LOOP_PHASES)
    assert not set(LOOP_HOST_PHASES) & set(LOOP_WAIT_PHASES)
    assert LOOP_WAIT_PHASES == ("idle", "prefill.fetch", "step.fetch")
    assert set(LOOP_PHASES) < set(ITER_FIELDS)


def test_phase_totals_sum_to_the_loops_wall_time():
    # No reading here is compared with a time the test's own thread
    # took while the loop ran: how soon a loaded host starts or joins a
    # thread is not the loop's to answer for.
    readings = []  # the loop thread's, inside the stub kernels
    t0 = time.monotonic_ns()
    sched = _fake_sched(step_cost=0.004, slots=2, readings=readings)
    sched.submit(_prompts(5))
    time.sleep(0.05)  # some idle belongs to the partition too
    sched.submit(_prompts(2, seed=1))
    t_close = time.monotonic_ns()
    sched.close()
    t1 = time.monotonic_ns()
    totals = sched.loop_totals()

    def booked(record):
        return sum(record[p] for p in LOOP_PHASES)

    def t_end(record):  # the clock's last mark: a float of seconds
        return round(record["t_end"] * 1e9)

    # The partition is exact: between the first iteration's end (a
    # record the loop wrote itself) and stop(), every nanosecond that
    # passed on the clock is booked to one phase. 1 us is what t_end
    # loses as a float.
    first = _mine(sched)[0]
    assert (booked(totals) - booked(first)
            == pytest.approx(t_end(totals) - t_end(first), abs=1000))
    # And it covers the loop's whole life. Booked back from the last
    # mark is where the clock started: after the scheduler was made,
    # before the loop first called a kernel. It stopped after close()
    # was called and before it returned.
    started = t_end(totals) - booked(totals)
    assert t0 - 1000 <= started <= readings[0] + 1000
    assert t_close - 1000 <= t_end(totals) <= t1 + 1000
    assert totals["idle"] / 1e9 >= 0.04
    # The stub step sleeps inside the call into it: that is dispatch.
    assert totals["step.dispatch"] / 1e9 >= 0.004 * totals["seq"] * 0.5
    # The loop thread's CPU time is its host phases' (it sleeps in its
    # waits; the stub step's sleep is inside dispatch): never more than
    # their wall time, the clocks' granularity apart.
    host = sum(totals[p] for p in LOOP_HOST_PHASES)
    assert 0 < totals["cpu_ns"] <= host * 1.05


@pytest.fixture(scope="module")
def sampled_run():
    """Totals read from another thread while a scheduler works, then
    its records: [loop_totals() ...], [record ...]."""
    sched = _fake_sched(step_cost=0.002, slots=2, prefill_chunk=3)
    samples = []
    stop = threading.Event()

    def sample():
        while not stop.is_set():
            samples.append(sched.loop_totals())
            time.sleep(0.001)

    th = threading.Thread(target=sample)
    th.start()
    try:
        stream = sched.submit_stream(_prompts(1, seed=3))
        sched.submit(_prompts(4))
        while stream.next_event(5.0)[0] != "end":
            pass
        sched.submit(_prompts(1, seed=4))
    finally:
        stop.set()
        th.join(10)
        sched.close()
    assert not th.is_alive() and len(samples) > 20
    samples.append(sched.loop_totals())
    return samples, _mine(sched)


@pytest.mark.parametrize("field", CUMULATIVE)
def test_every_total_is_monotone(sampled_run, field):
    samples, records = sampled_run
    for series in (samples, records):
        values = [s[field] for s in series]
        assert all(b >= a for a, b in zip(values, values[1:])), field
    if field not in ("stream_lag_ns", "stream_frames", "stream_send_ns",
                     "stream_sends", *NOT_IN_THIS_RUN):
        assert samples[-1][field] > 0, field


def test_records_carry_sequence_time_and_a_window_is_last_minus_first(
        sampled_run):
    _, records = sampled_run
    assert [r["seq"] for r in records] == list(
        range(records[0]["seq"], records[0]["seq"] + len(records)))
    ends = [r["t_end"] for r in records]
    assert ends == sorted(ends)
    assert any(r["prefilled"] for r in records)
    assert max(r["active_slots"] for r in records) == 2
    first, last = records[0], records[-1]
    # Phases partition the time between two records exactly.
    booked = sum(last[p] - first[p] for p in LOOP_PHASES) / 1e9
    assert booked == pytest.approx(last["t_end"] - first["t_end"], rel=1e-6)
    inside = ITERATIONS.window(first["t_end"], last["t_end"])
    assert len([r for r in inside if r[0] == first["sched"]]) \
        == len(records) - 1


def test_an_iterations_growth_of_the_prefill_totals_is_its_chunk():
    """A prompt of 8 in chunks of 3, one slot: the records' neighbour
    differences of `prefill_tokens` and `prefill_starts` are each
    chunk's size and first position, 0 where the iteration only
    decoded."""
    sched = _fake_sched(slots=1, prefill_chunk=3)
    sched.submit(_prompts(2))
    sched.close()
    records = _mine(sched)
    zero = dict.fromkeys(("prefill_tokens", "prefill_starts"), 0)
    chunks = [(b["prefill_starts"] - a["prefill_starts"],
               b["prefill_tokens"] - a["prefill_tokens"], b["prefilled"])
              for a, b in zip([zero] + records, records)]
    assert [c[:2] for c in chunks if c[2]] == [(0, 3), (3, 3), (6, 2)] * 2
    assert all(c[:2] == (0, 0) for c in chunks if not c[2])
    assert sched.loop_totals()["prefill_tokens"] == 2 * T


# -------------------------------------------------------------- ring


def test_ring_is_bounded_and_keeps_the_newest():
    ring = IterationRing(capacity=8)
    for i in range(20):
        ring.append((1, i, float(i)) + (0,) * (len(ITER_FIELDS) - 3))
    assert len(ring) == ring.capacity == 8
    assert [r[1] for r in ring.snapshot()] == list(range(12, 20))
    assert [r[1] for r in ring.window(14.0, 16.0)] == [14, 15]
    assert 1000 <= ITERATIONS.capacity <= 10000


def test_ring_survives_close_and_tells_schedulers_apart():
    a, b = _fake_sched(), _fake_sched()
    a.submit(_prompts(1))
    b.submit(_prompts(2))
    a.close()
    b.close()
    del a.__dict__["_thread"], b.__dict__["_thread"]
    ra, rb = _mine(a), _mine(b)
    assert ra and rb and ra[0]["sched"] != rb[0]["sched"]
    assert ra[-1]["binds"] == 1 and rb[-1]["binds"] == 2


# ------------------------------------------------- waits and stream lag


def test_queue_wait_counts_a_request_held_back_by_a_full_slot_set():
    sched = _fake_sched(step_cost=0.02, slots=1)
    done = []

    def call():
        t0 = time.monotonic()
        sched.submit(_prompts(1))
        done.append(time.monotonic() - t0)

    first = threading.Thread(target=call)
    first.start()
    while sched.loop_totals()["binds"] < 1:
        time.sleep(0.001)
    t_second = time.monotonic()
    sched.submit(_prompts(1, seed=1))
    second_total = time.monotonic() - t_second
    first.join(10)
    sched.close()
    totals = sched.loop_totals()
    assert totals["binds"] == 2 and totals["first_tokens"] == 2
    waited = totals["queue_wait_ns"] / 1e9
    # The second request stood in the queue while the first decoded its
    # nine steps of 20 ms; the first hardly waited at all.
    assert 0.1 < waited < second_total
    assert totals["prefill_wait_ns"] / 1e9 < 0.05


def test_sleeping_fetch_hook_lands_in_step_fetch():
    sched = _fake_sched(slots=2)
    sched.fetch_hook = lambda toks: time.sleep(0.005)
    sched.submit(_prompts(2))
    sched.close()
    totals = sched.loop_totals()
    steps = sched.steps_total
    assert steps >= N - 1
    assert totals["step.fetch"] / 1e9 >= 0.005 * steps
    others = sum(totals[p] for p in LOOP_HOST_PHASES) / 1e9
    assert others < totals["step.fetch"] / 1e9


@pytest.mark.parametrize("consumer_sleep, lo_ms, hi_ms",
                         [(0.0, 0.0, 5.0), (0.03, 10.0, 100.0)])
def test_stream_consumer_delay_lands_in_stream_lag(consumer_sleep, lo_ms,
                                                   hi_ms):
    sched = _fake_sched(step_cost=0.01, slots=1, max_new_tokens=24)
    stream = sched.submit_stream(_prompts(1))
    got = []
    while True:
        kind, data = stream.next_event(10.0)
        if kind == "end":
            break
        got += data
        time.sleep(consumer_sleep)
    sched.close()
    totals = sched.loop_totals()
    assert len(got) == 24
    # Frames taken after the last publish are never folded in.
    assert 0 < totals["stream_frames"] < 24
    mean_ms = totals["stream_lag_ns"] / totals["stream_frames"] / 1e6
    assert lo_ms <= mean_ms < hi_ms


# ------------------------------------- CPU by part, starved, captured


def _burn(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("where", ["publish", "dispatch", None])
def test_publish_cpu_is_the_publishs_own_and_inside_the_threads_total(
        where, monkeypatch):
    """A publish that spins for 2 ms is CPU time of `cpu.publish`; a
    step kernel that spins inside the call into it, or sleeps there, is
    none of it. Either way the publish's CPU time stays inside its
    phase's wall time and the thread's own total, and the process's
    total holds the thread's."""
    from tpu_dist_nn.serving import continuous

    monkeypatch.setattr(continuous, "_PROC_CPU_EVERY_NS", 0)

    def fake_prefill(params, cache, slot, tokens, start, key):
        return np.int32(1), cache

    def fake_step(params, cache, pos, active, tok, key):
        _burn(0.002) if where == "dispatch" else time.sleep(0.002)
        return np.asarray(tok) + 1, cache

    sched = ContinuousScheduler(None, None, prefill_fn=fake_prefill,
                                step_fn=fake_step, slots=2, prompt_len=T,
                                max_new_tokens=N)
    publish, published = sched._publish, []

    def slow_publish(occ, first=False):
        if not first:  # a first token leaves in `prefill.post`
            published.append(_burn(0.002))
        publish(occ, first=first)

    if where == "publish":
        sched._publish = slow_publish
    sched.submit(_prompts(4))
    sched.close()
    totals = sched.loop_totals()
    cpu, steps = totals["cpu.publish"], sched.steps_total
    assert 0 <= cpu <= totals["step.publish"] * 1.05 + 2e5
    assert cpu <= totals["cpu_ns"]
    assert totals["step.dispatch"] >= 0.002e9 * steps
    if where == "publish":
        assert len(published) >= 4 * (N - 1)
        assert cpu >= 0.0015e9 * len(published)
    else:
        assert cpu <= 0.3 * totals["step.dispatch"]
    if where == "dispatch":
        assert totals["cpu_ns"] - cpu >= 0.0015e9 * steps
    # The process's CPU time holds the loop thread's.
    assert totals["proc_cpu_ns"] >= totals["cpu_ns"] > 0
    records = _mine(sched)
    assert all(r["proc_cpu_ns"] >= r["cpu_ns"] for r in records)


def test_the_process_clock_is_read_every_tenth_of_a_second():
    sched = _fake_sched(step_cost=0.002, slots=2, max_new_tokens=64)
    sched.submit(_prompts(4))
    sched.close()
    records = _mine(sched)
    proc = [r["proc_cpu_ns"] for r in records]
    assert proc == sorted(proc) and proc[0] > 0
    lasted = records[-1]["t_end"] - records[0]["t_end"]
    assert lasted > 0.25 and len(records) > 100
    assert 2 <= len(set(proc)) <= lasted / 0.1 + 2


class _OnDevice:
    """A kernel's result still on the device: waiting for it and
    reading it are two things, as for a jax.Array."""

    def __init__(self, value, log):
        self.value, self.log = value, log

    def block_until_ready(self):
        time.sleep(0.002)
        self.log.append(("ready", time.monotonic_ns()))
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value, dtype)

    def __int__(self):
        return int(self.value)


def _device_sched(log, **kw):
    """Stub kernels whose results have to be waited for; `log` takes
    ("ready", t) when a wait ends and ("fed", t) as a dispatch is about
    to return, 3 ms after it was called."""
    def fake_prefill(params, cache, slot, tokens, start, key):
        time.sleep(0.003)
        log.append(("fed", time.monotonic_ns()))
        return _OnDevice(np.int32(1), log), cache

    def fake_step(params, cache, pos, active, tok, key):
        time.sleep(0.003)
        log.append(("fed", time.monotonic_ns()))
        return _OnDevice(np.asarray(tok) + 1, log), cache

    kw.setdefault("slots", 2)
    return ContinuousScheduler(None, None, prefill_fn=fake_prefill,
                               step_fn=fake_step, prompt_len=T,
                               max_new_tokens=N, **kw)


def test_starved_time_runs_from_the_waits_end_to_the_next_dispatch():
    log = []
    sched = _device_sched(log)
    sched.submit(_prompts(3))
    sched.close()
    totals = sched.loop_totals()
    # From each wait's end to the first dispatch that returned after it
    # (an iteration's chunk where it has one, else its step).
    gaps, ready = [], None
    for kind, t in log:
        if kind == "ready":
            ready = t
        elif ready is not None:
            gaps.append(t - ready)
            ready = None
    assert len(gaps) >= N
    # The loop's own readings lie just outside the stub's: it notes the
    # wait's end after the stub, the dispatch's return after the stub.
    assert sum(gaps) <= totals["starved_ns"] <= sum(gaps) * 1.3 + 2e7
    assert totals["starved_ns"] >= 0.003e9 * len(gaps)
    # It is time of reap, admit, bind and the dispatches.
    assert totals["starved_ns"] <= sum(
        totals[p] for p in ("reap", "admit", "bind", "prefill.dispatch",
                            "step.dispatch")
    ) + 1e6 * len(gaps)
    records = _mine(sched)
    assert [r["starved_ns"] for r in records] == sorted(
        r["starved_ns"] for r in records)


def test_starved_time_leaves_out_the_loops_sleep_for_want_of_work():
    sched = _fake_sched(slots=2)
    sched.submit(_prompts(1))
    before = sched.loop_totals()
    time.sleep(0.3)  # nothing to do: the loop sleeps in `idle`
    sched.submit(_prompts(1, seed=1))
    sched.close()
    totals = sched.loop_totals()
    assert totals["idle"] - before["idle"] >= 0.25e9
    assert 0 < totals["starved_ns"] < 0.1e9


def test_captured_counts_the_iterations_a_profiler_capture_holds(tmp_path):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # a short stop_trace
    sched = _fake_sched(step_cost=0.001, slots=2)
    made, real = [], sched._clock._host_span
    sched._clock._host_span = lambda name: (made.append(name), real(name))[1]
    try:
        sched.submit(_prompts(2))
        assert not made  # no annotation while nobody records it
        t0 = time.monotonic()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            t1 = time.monotonic()
            sched.submit(_prompts(2, seed=1))
            t2 = time.monotonic()
        finally:
            jax.profiler.stop_trace()
        t3 = time.monotonic()
        time.sleep(0.05)  # the loop's next mark ends the last one
        made_by_stop = len(made)
        sched.submit(_prompts(2, seed=2))
    finally:
        sched.close()
    records = _mine(sched)
    zero = dict.fromkeys(ITER_FIELDS, 0)
    grew = [(b["t_end"], b["captured"] - a["captured"])
            for a, b in zip([zero] + records, records)]
    assert {g for _, g in grew} == {0, 1}  # by one a recorded iteration
    assert all(g == 0 for t, g in grew if t < t0 or t > t3)
    inside = [g for t, g in grew if t1 <= t <= t2]
    assert len(inside) >= N and all(g == 1 for g in inside)
    assert records[-1]["captured"] == sum(g for _, g in grew) >= len(inside)
    # The phases under the capture were annotated, each on entry.
    assert {"tdn.gen.step.dispatch", "tdn.gen.step.publish",
            "tdn.gen.reap"} <= set(made)
    assert len(made) >= 6 * len(inside)
    assert len(made) == made_by_stop  # and none after it


def test_the_schedulers_own_counters_ride_the_ring():
    """`discarded_lanes`, `slot_steps`, `attend_kernel_chunks`,
    `prefill_chunks` and the two `kv_tiles_*` columns are the
    scheduler's counters as of each iteration's end."""
    sched = _fake_sched(step_cost=0.002, slots=2, max_new_tokens=64)
    stream = sched.submit_stream(_prompts(1))
    got = []
    while len(got) < 5:
        got += stream.next_event(5.0)[1]
    stream.cancel()  # found one launch late: a lane computed for nobody
    sched.submit(_prompts(2, seed=1))
    sched.close()
    last = _mine(sched)[-1]
    assert last["discarded_lanes"] == sched.discarded_lanes_total >= 1
    assert last["slot_steps"] == sched.slot_steps_total > 0
    assert last["prefill_chunks"] == sched.prefill_chunks_total == 3
    assert last["attend_kernel_chunks"] == sched.attend_kernel_chunks_total
    assert last["kv_tiles_visited"] == sched.step_kv_tiles_visited_total
    assert last["kv_tiles_skipped"] == sched.step_kv_tiles_skipped_total


def test_a_handlers_time_away_with_a_frame_lands_in_stream_send():
    sched = _fake_sched(step_cost=0.01, slots=1, max_new_tokens=24)
    stream = sched.submit_stream(_prompts(1))
    got = []
    while True:
        kind, data = stream.next_event(10.0)
        if kind == "end":
            break
        got += data
        time.sleep(0.02)  # the handler's send
    sched.close()
    totals = sched.loop_totals()
    assert len(got) == 24
    # Sends that ended after the last publish are never folded in.
    assert 0 < totals["stream_sends"] < 24
    mean_ms = totals["stream_send_ns"] / totals["stream_sends"] / 1e6
    assert 20.0 <= mean_ms < 100.0


# -------------------------------------------------------------- spans


def test_decode_span_says_what_the_request_rode_and_no_step_spans():
    root = TRACER.start("rpc.Generate")
    assert root.ctx.sampled
    sched = _fake_sched(step_cost=0.002, slots=2)
    sched.fetch_hook = lambda toks: time.sleep(0.001)
    try:
        sched.submit(_prompts(1), ctx=root.ctx)
    finally:
        root.end()
        sched.close()
    spans = [s for s in TRACER.snapshot()
             if s.trace_id == root.ctx.trace_id]
    names = [s.name for s in spans]
    assert {"queue_wait", "prefill", "decode"} <= set(names)
    assert "decode.step" not in names
    decode = next(s for s in spans if s.name == "decode")
    attrs = decode.attrs
    assert attrs["tokens"] == N and attrs["steps"] == N - 1
    assert attrs["step_fetch_s"] >= 0.001 * (N - 1)
    assert 0 < attrs["host_s"] < decode.dur
    # It joins the iteration records by sequence number: the iteration
    # that prefilled it launched its first step too, and every step is
    # read in the iteration after the one that launched it.
    assert attrs["iter_last"] - attrs["iter_first"] == attrs["steps"]
    seqs = {r["seq"] for r in _mine(sched)}
    assert set(range(attrs["iter_first"], attrs["iter_last"] + 1)) <= seqs


def test_cancelled_stream_leaves_a_decode_span_too():
    """The wire carries no budget, so most streams end by the client's
    cancel; the loop's reap pass records their decode phase."""
    root = TRACER.start("rpc.GenerateStream")
    sched = _fake_sched(step_cost=0.002, slots=1, max_new_tokens=64)
    try:
        stream = sched.submit_stream(_prompts(1), ctx=root.ctx)
        got = []
        while len(got) < 5:
            got += stream.next_event(5.0)[1]
        stream.cancel()
        deadline = time.monotonic() + 5
        while sched.slots_active and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sched.slots_active == 0
    finally:
        root.end()
        sched.close()
    decode = [s for s in TRACER.snapshot()
              if s.trace_id == root.ctx.trace_id and s.name == "decode"]
    assert len(decode) == 1
    attrs = decode[0].attrs
    assert attrs["reason"] == "cancelled" and attrs["tokens"] >= 5
    assert attrs["steps"] >= 4 and attrs["iter_last"] > attrs["iter_first"]


# ------------------------------------------------------------ /metrics


@pytest.fixture(scope="module")
def scraped():
    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    sched = _fake_sched(step_cost=0.001, slots=2)
    sampler.add_generation_scheduler(sched)
    stream = sched.submit_stream(_prompts(1))
    while stream.next_event(5.0)[0] != "end":
        pass
    sched.submit(_prompts(3))
    sampler.sample_once()
    once = {m.name: {k: c.value for k, c in m.samples()}
            for m in reg.collect()}
    sampler.sample_once()  # nothing ran since: counters must not move
    twice = {m.name: {k: c.value for k, c in m.samples()}
             for m in reg.collect()}
    sched.close()
    return once, twice, sched.loop_totals()


@pytest.mark.parametrize("family, field", [
    ("tdn_gen_loop_iterations_total", "seq"),
    ("tdn_gen_loop_cpu_seconds_total", "cpu_ns"),
    ("tdn_gen_queue_wait_seconds_total", "queue_wait_ns"),
    ("tdn_gen_queue_wait_requests_total", "binds"),
    ("tdn_gen_prefill_wait_seconds_total", "prefill_wait_ns"),
    ("tdn_gen_prefill_wait_requests_total", "first_tokens"),
    ("tdn_gen_stream_lag_seconds_total", "stream_lag_ns"),
    ("tdn_gen_stream_lag_frames_total", "stream_frames"),
    ("tdn_gen_loop_publish_cpu_seconds_total", "cpu.publish"),
    ("tdn_gen_process_cpu_seconds_total", "proc_cpu_ns"),
    ("tdn_gen_stream_send_seconds_total", "stream_send_ns"),
    ("tdn_gen_stream_send_frames_total", "stream_sends"),
    ("tdn_gen_device_starved_seconds_total", "starved_ns"),
])
def test_loop_totals_are_scraped_as_counters(scraped, family, field):
    once, twice, totals = scraped
    value = once[family][()]
    assert value > 0 and twice[family][()] == value
    scale = 1e9 if field.endswith("_ns") or field == "cpu.publish" else 1
    assert value <= totals[field] / scale + 1e-9


def test_every_cumulative_column_but_the_readers_aid_has_a_counter():
    """`captured` is for a reader of the ring alone; the scheduler's own
    counters keep the `tdn_gen_*` families they had."""
    sampler = RuntimeSampler(registry=Registry())
    own = {"discarded_lanes", "slot_steps", "attend_kernel_chunks",
           "prefill_chunks", "kv_tiles_visited", "kv_tiles_skipped",
           "prefill_tokens", "prefill_starts", "steps_ahead"}
    assert set(CUMULATIVE) - set(LOOP_PHASES) - set(sampler._c_gen_loop) \
        - own == {"captured"}


@pytest.mark.parametrize("phase", [p for p in LOOP_PHASES if p != "idle"])
def test_loop_seconds_has_a_series_per_phase(scraped, phase):
    once, twice, totals = scraped
    series = once["tdn_gen_loop_seconds_total"]
    assert 0 < series[(phase,)] <= totals[phase] / 1e9 + 1e-9
    assert twice["tdn_gen_loop_seconds_total"][(phase,)] == series[(phase,)]


# ------------------------------------------------------- named scopes


@pytest.fixture(scope="module")
def lowered_programs():
    """The scheduler's own step and prefill programs as lowered, with
    the names `jax.named_scope` gave their operations (the compiler
    keeps them as `op_name` metadata, which a device capture shows per
    operation). Lowered, not compiled: a compile may come from the
    persistent cache, whose key leaves names out."""
    import jax

    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq_len=24)
    sched = ContinuousScheduler(
        init_transformer(jax.random.key(0), cfg), cfg, slots=2,
        prompt_len=T, max_new_tokens=N)
    try:
        key = sched._next_key()
        step = sched._step.lower(
            sched._params, sched._cache, sched._pos, sched._active,
            sched._tok, key, sched._prev, sched._first,
        ).as_text(debug_info=True)
        prefill = sched._prefill.lower(
            sched._params, sched._cache, np.int32(0),
            np.zeros((1, T), np.int32), np.int32(0), key,
        ).as_text(debug_info=True)
    finally:
        sched.close()
    return {"step": step, "prefill": prefill}


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "kv.write", "attn.scores", "attn.softmax", "attn.values",
    "ffn", "unembed", "sample"])
def test_step_programs_name_their_scopes(lowered_programs, program, scope):
    assert re.search(rf'[/"]{re.escape(scope)}[/"]',
                     lowered_programs[program])


@pytest.mark.parametrize("scope", ["guard", "next_token"])
def test_guard_and_token_merge_scopes_are_in_the_step_program(
        lowered_programs, scope):
    assert re.search(rf'[/"]{scope}[/"]', lowered_programs["step"])
