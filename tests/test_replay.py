"""Scenario engine (ISSUE 18: obs/replay.py + scenarios/ + tdn replay):
seeded workload generators (bit-deterministic), the incident-bundle ->
WorkloadTrace -> replay round trip (exact request mix, session pinning,
per-decile arrival fidelity), FaultPlan's seeded-probability mode, the
stream-resume metadata bound at its exact boundary (router ledger +
replica backstop), and a quick-scaled scenario verdict smoke."""

import os

import grpc
import numpy as np
import pytest

from tpu_dist_nn.obs import replay as R
from tpu_dist_nn.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------- generators


def test_generators_deterministic_and_well_formed():
    # Same seed -> byte-identical trace JSON; different seed differs.
    # Arrivals are sorted and stay inside the declared duration for
    # every registered generator (the scenario files lean on both).
    for gen in sorted(R.GENERATORS):
        a = R.make_workload(gen, seed=42, requests=24, duration=4.0)
        b = R.make_workload(gen, seed=42, requests=24, duration=4.0)
        assert a.to_json() == b.to_json(), gen
        c = R.make_workload(gen, seed=43, requests=24, duration=4.0)
        assert a.to_json() != c.to_json(), gen
        assert len(a.requests) == 24, gen
        arrivals = [r.arrival_s for r in a.requests]
        assert arrivals == sorted(arrivals), gen
        assert all(0.0 <= t <= 4.0 for t in arrivals), gen


def test_trace_json_and_file_round_trip(tmp_path):
    t = R.make_workload("heavy_tail", seed=5, requests=12, duration=2.0,
                        stream_fraction=0.5)
    again = R.WorkloadTrace.from_json(t.to_json())
    assert again.mix() == t.mix()
    # to_json rounds arrival offsets (microsecond-ish) — deciles agree
    # to far better than the 5 ms decile floor.
    assert again.inter_arrival_deciles() == pytest.approx(
        t.inter_arrival_deciles(), abs=1e-5)
    p = str(tmp_path / "trace.json")
    t.save(p)
    assert R.WorkloadTrace.load(p).to_json() == t.to_json()
    # Heavy-tail really is heavy-tailed: prompt lengths spread past
    # the minimum, and the streaming fraction survived.
    lens = {r.prompt_len for r in t.requests}
    assert len(lens) > 1
    assert any(r.stream for r in t.requests)


# --------------------------------------------- FaultPlan seeded p mode


def test_fault_plan_probability_mode_deterministic_under_seed():
    def sequence(seed, calls=80):
        plan = faults.FaultPlan(p=0.2, fault=faults.unavailable(),
                                seed=seed)
        return [plan.next_fault() is not None for _ in range(calls)]

    a, b = sequence(7), sequence(7)
    assert a == b, "same seed must reproduce the same storm"
    assert any(a) and not all(a)
    assert sequence(8) != a, "different seed, different storm"
    # Mixed plan: at= hits land exactly where named, and the rng draw
    # happens on EVERY call, so the probabilistic hits are the same
    # whether or not a deterministic hit already decided the call.
    mixed = faults.FaultPlan(at={3: faults.delay(0.0)}, p=0.2,
                             fault=faults.unavailable(), seed=7)
    got = [mixed.next_fault() for _ in range(80)]
    assert got[2] is not None and got[2].kind == "delay"
    assert [f is not None for f in got[:2]] == a[:2]
    assert [f is not None for f in got[3:]] == a[3:]


def test_fault_plan_p_validation():
    with pytest.raises(ValueError):
        faults.FaultPlan(p=1.5, fault=faults.unavailable())
    with pytest.raises(ValueError):
        faults.FaultPlan(p=0.1)  # p= needs fault=


# ------------------------------------------- capture -> replay fidelity


def test_bundle_round_trip_exact_mix_and_arrival_deciles():
    # The acceptance core: drive a seeded mixed-class workload at a
    # live loopback fleet, capture a REAL incident bundle, extract the
    # WorkloadTrace back out of trace.json — the request mix must match
    # EXACTLY (methods, classes, shapes, sessions, streams) and every
    # inter-arrival decile must land within 10%.
    from tpu_dist_nn.obs.incident import capture_bundle
    from tpu_dist_nn.obs.trace import TRACER

    original = R.make_workload("mixed_class", seed=9, requests=16,
                               duration=2.5, sessions=4)
    fleet = R.LoopbackFleet(replicas=2, per_row_ms=0.5)
    try:
        fleet.start()
        cursor = TRACER.chrome_trace(limit=1)["cursor"]
        report = R.replay(original, fleet.target, speed=1.0)
        doc = TRACER.chrome_trace(since=cursor)
        _, bundle = capture_bundle(
            "test_round_trip", reason="round-trip test",
            tracer=R._FrozenTracer(doc),
        )
    finally:
        fleet.stop()
    assert report["ok"] == len(original.requests)
    # The replay driver itself paced faithfully (sent-vs-trace decile
    # error is part of every replay report).
    assert report["arrival"]["max_decile_error"] <= 0.10
    extracted = R.trace_from_bundle(bundle)
    assert extracted.source.startswith("bundle:")
    assert extracted.mix() == original.mix()
    errs = R.decile_errors(original.inter_arrival_deciles(),
                           extracted.inter_arrival_deciles())
    assert errs and max(errs) <= 0.10

    # Session pinning survives the wire: per-session request counts in
    # the extracted trace equal the original's.
    def per_session(t):
        out = {}
        for r in t.requests:
            out[r.session] = out.get(r.session, 0) + 1
        return out

    assert per_session(extracted) == per_session(original)


def test_capture_attrs_survive_fleet_trace_stitching():
    # The capture satellite end-to-end at the doc level: handler root
    # spans' request attrs ride chrome-trace args VERBATIM through
    # stitch_chrome_traces, so a router's stitched trace_fleet.json is
    # just as replayable as a single process's trace.json.
    from tpu_dist_nn.obs.collect import stitch_chrome_traces
    from tpu_dist_nn.obs.trace import TRACER

    original = R.make_workload("mixed_class", seed=21, requests=10,
                               duration=1.5, sessions=3)
    fleet = R.LoopbackFleet(replicas=2, per_row_ms=0.5)
    try:
        fleet.start()
        cursor = TRACER.chrome_trace(limit=1)["cursor"]
        R.replay(original, fleet.target, speed=2.0)
        doc = TRACER.chrome_trace(since=cursor)
    finally:
        fleet.stop()
    stitched = stitch_chrome_traces({"router:9100": doc})
    extracted = R.trace_from_chrome(stitched)
    assert extracted.mix() == original.mix()


# ------------------------------------------------ stream-resume bound


def test_stream_resume_bound_boundary_and_overflow_counter():
    # Exactly AT the bound the metadata-borne resume path still works
    # (the router's failover ledger and the replica both accept 1024
    # tokens); ONE past it the router refuses with a clear OUT_OF_RANGE
    # + the overflow counter, and the replica backstops hand-rolled
    # clients with the same status.
    from tpu_dist_nn.serving.router import ROUTER_STREAM_RESUME_OVERFLOW
    from tpu_dist_nn.serving.wire import (
        GENERATE_STREAM_METHOD,
        STREAM_RESUME_HEADER,
        STREAM_RESUME_MAX_TOKENS,
        decode_frame,
        encode_matrix,
    )

    def drain(call_iter):
        toks = []
        for f in call_iter:
            kind, data = decode_frame(f)
            if kind == "tokens":
                toks.extend(data)
        return toks

    extra = 6
    fleet = R.LoopbackFleet(
        replicas=1, max_new_tokens=STREAM_RESUME_MAX_TOKENS + extra,
        per_token_ms=0.0, prefill_ms=0.0,
    )
    try:
        fleet.start()
        prompt = encode_matrix(
            np.zeros((1, fleet.prompt_len), dtype=np.int64))
        at_bound = ",".join(["1"] * STREAM_RESUME_MAX_TOKENS)
        past_bound = at_bound + ",1"
        ch = grpc.insecure_channel(fleet.target)
        stream = ch.unary_stream(GENERATE_STREAM_METHOD,
                                 request_serializer=bytes,
                                 response_deserializer=bytes)
        # 1024 delivered tokens: resume accepted, only the unseen
        # suffix flows.
        toks = drain(stream(
            prompt, timeout=20.0,
            metadata=((STREAM_RESUME_HEADER, at_bound),)))
        assert len(toks) == extra
        # 1025: the router abandons failover-resume loudly.
        before = sum(
            c.value for _, c in ROUTER_STREAM_RESUME_OVERFLOW.samples())
        with pytest.raises(grpc.RpcError) as ei:
            drain(stream(
                prompt, timeout=20.0,
                metadata=((STREAM_RESUME_HEADER, past_bound),)))
        assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE
        assert "restart the stream" in ei.value.details()
        after = sum(
            c.value for _, c in ROUTER_STREAM_RESUME_OVERFLOW.samples())
        assert after == before + 1
        ch.close()
        # Replica backstop: the bound holds even without the router in
        # front (a hand-rolled client talking straight to a replica).
        ch2 = grpc.insecure_channel(fleet.targets[0])
        direct = ch2.unary_stream(GENERATE_STREAM_METHOD,
                                  request_serializer=bytes,
                                  response_deserializer=bytes)
        with pytest.raises(grpc.RpcError) as ei:
            drain(direct(
                prompt, timeout=20.0,
                metadata=((STREAM_RESUME_HEADER, past_bound),)))
        assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE
        assert str(STREAM_RESUME_MAX_TOKENS) in ei.value.details()
        ch2.close()
    finally:
        fleet.stop()


# -------------------------------------------------- scenario verdicts


def test_scenario_quick_smoke_deterministic_verdict():
    # The quick-tier replay smoke: one checked-in scenario at quick
    # scale produces a machine-readable PASS verdict, and the verdict
    # is deterministic where it must be (request mix under the seed).
    path = os.path.join(REPO, "scenarios", "diurnal_baseline.json")
    v = R.run_scenario_file(path, quick_scale=0.4)
    assert v["passed"] is True
    assert v["scenario"] == "diurnal_baseline" and v["seed"] == 101
    assert v["objectives"], "SLO verdicts must be embedded"
    for o in v["objectives"]:
        assert o["passed"] == (o["burn_rate"] <= 1.0)
    v2 = R.run_scenario_file(path, quick_scale=0.4)
    assert v2["workload"] == v["workload"], "seeded mix must reproduce"


def test_scenario_dir_has_full_matrix():
    # The checked-in matrix `--scenario-dir` runs: at least 8 cells, at
    # least 3 distinct generators, at least 2 with fault crossings,
    # and at least one bundle-derived (capture) cell.
    paths = R.scenario_paths(os.path.join(REPO, "scenarios"))
    assert len(paths) >= 8
    gens, faulted, captured = set(), 0, 0
    for p in paths:
        spec = R.load_scenario(p)
        wl = spec["workload"]
        if "capture" in wl:
            captured += 1
            gens.add(wl["capture"]["generator"])
        else:
            gens.add(wl["generator"])
        if spec.get("fleet", {}).get("faults") or spec.get("chaos"):
            faulted += 1
    assert len(gens) >= 3
    assert faulted >= 2
    assert captured >= 1


