"""Silent-corruption defense plane (ISSUE 19: serving/integrity.py +
pool quarantine choreography): checkpoint fingerprints detect a single
flipped bit and gate the orbax restore path, the numeric guard fails
exactly the poisoned rows (unaffected rows ship bit-identical) and
surfaces DATA_LOSS on the wire, canary goldens are stable across prober
restarts, the quarantine lifecycle runs detect -> drain-refusal ->
evidence bundle -> reverify-readmit (+ the operator's force break-glass
and the three-strikes guard verdict), shadow spot-checks arbitrate a
reply-byte tamper down to the guilty replica, and the checked-in
corruption drill scenario quarantines exactly the planted replica."""

import os
import time

import numpy as np
import pytest

from tpu_dist_nn.serving import integrity
from tpu_dist_nn.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _armed_guard():
    """Every test here assumes the guard is armed (another test's A/B and a
    TDN_INTEGRITY_GUARD=0 environment may have disarmed the process
    singleton); restore whatever the session had."""
    prev = integrity.GUARD.enabled
    integrity.GUARD.enabled = True
    yield
    integrity.GUARD.enabled = prev


# ------------------------------------------------ fingerprints (rung 1)


def test_array_checksum_and_fingerprint_detect_bitflip():
    rng = np.random.default_rng(0)
    tree = {
        "w": rng.normal(size=(4, 6)),
        "b": rng.normal(size=(6,)),
    }
    fp = integrity.fingerprint_tree(tree)
    assert fp["count"] == 2
    assert integrity.verify_tree(tree, fp) == []
    # Same values, fresh buffers -> same fingerprint (it hashes bytes,
    # not identities).
    copy = {k: v.copy() for k, v in tree.items()}
    assert integrity.fingerprint_tree(copy)["model"] == fp["model"]
    # One flipped mantissa bit — far below any tolerance a numeric
    # check would use — must change the array's checksum, the model
    # fingerprint, and be NAMED by verify_tree.
    index, bit = faults.bitflip_array(copy["w"], seed=3)
    assert bit < 8  # low mantissa: corrupts, does not explode
    assert integrity.fingerprint_tree(copy)["model"] != fp["model"]
    mismatches = integrity.verify_tree(copy, fp)
    assert len(mismatches) == 1 and mismatches[0].startswith("w:")
    # dtype is part of the digest: an f32 cast of identical values must
    # not collide with the f64 original.
    assert integrity.array_checksum(
        tree["b"].astype(np.float32)
    ) != integrity.array_checksum(tree["b"])


def test_fingerprint_structure_drift_reported_both_directions():
    tree = {"w": np.ones((2, 2)), "b": np.zeros(3)}
    fp = integrity.fingerprint_tree(tree)
    # A truncated restore (missing array) and a renamed/extra array are
    # both corruption, not tolerable drift.
    missing = {"w": tree["w"]}
    assert any("missing from restored state" in m
               for m in integrity.verify_tree(missing, fp))
    extra = dict(tree, v=np.ones(1))
    assert any("not in saved fingerprint" in m
               for m in integrity.verify_tree(extra, fp))


def test_orbax_round_trip_verifies_and_tamper_fails_data_loss(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from tpu_dist_nn.checkpoint.orbax_store import OrbaxCheckpointManager
    from tpu_dist_nn.utils.errors import IntegrityError

    state = {"w": np.arange(6.0).reshape(2, 3), "b": np.ones(3)}
    template = {"w": np.zeros((2, 3)), "b": np.zeros(3)}

    mgr = OrbaxCheckpointManager(tmp_path / "ck", keep=3)
    try:
        # Honest round trip: the fingerprint is written into the
        # checkpoint metadata at save and verified clean at restore.
        mgr.save(1, state)
        mgr.wait()
        meta = mgr.read_metadata(1)
        assert meta is not None and "integrity" in meta
        assert meta["integrity"]["model"] == \
            integrity.fingerprint_tree(state)["model"]
        step, got = mgr.restore(template, 1)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(got["w"]), state["w"])

        # Corrupt read: a checkpoint whose bytes disagree with the
        # fingerprint written at save time (simulated by saving the
        # fingerprint of a bit-flipped twin — save() setdefaults, so an
        # explicit metadata fingerprint wins) fails LOUDLY at load.
        flipped = {k: v.copy() for k, v in state.items()}
        faults.bitflip_array(flipped["w"], seed=9)
        mgr.save(2, state,
                 metadata={"integrity": integrity.fingerprint_tree(flipped)})
        mgr.wait()
        with pytest.raises(IntegrityError, match="w:"):
            mgr.restore(template, 2)
        # verify=False is the forensics opt-out on a known-corrupt step.
        step, got = mgr.restore(template, 2, verify=False)
        assert step == 2
    finally:
        mgr.close()


# ------------------------------------- numeric guard (rung 2) + engine


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Two real engines of the SAME weights, each behind its own gRPC
    server — replica A is the corruption victim (tests attach/clear its
    launch_hook), replica B stays golden."""
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.core.schema import save_model
    from tpu_dist_nn.serving import serve_engine
    from tpu_dist_nn.testing.factories import random_model

    model = random_model([12, 10, 6], seed=3)
    path = tmp_path_factory.mktemp("integrity") / "model.json"
    save_model(model, path)
    eng_a = Engine.up(str(path), [1, 1])
    eng_b = Engine.up(str(path), [1, 1])
    server_a, port_a = serve_engine(eng_a, 0)
    server_b, port_b = serve_engine(eng_b, 0)
    # Warm the compile caches so canary-probe timeouts never race a jit.
    warm = np.zeros((2, 12))
    eng_a.infer(warm.copy())
    eng_b.infer(warm.copy())
    yield {"eng_a": eng_a, "eng_b": eng_b,
           "port_a": port_a, "port_b": port_b, "path": str(path)}
    server_a.stop(grace=0.5)
    server_b.stop(grace=0.5)
    eng_a.down()
    eng_b.down()


def test_guard_partial_rows_failover_bit_parity(fleet):
    """The guard's core contract: poisoned rows fail, unaffected rows
    in the SAME launch ship bit-identical to a clean run."""
    eng = fleet["eng_a"]
    rng = np.random.default_rng(11)
    x = rng.uniform(0, 1, (3, 12))
    clean = eng.infer(x.copy())
    eng.launch_hook = faults.nan_launch(rows=(1,))
    try:
        pending = eng.infer_async(x.copy())
        out = eng.fetch(pending)
    finally:
        eng.launch_hook = None
    bad = pending.bad_rows
    assert bad is not None and bad.tolist() == [False, True, False]
    assert np.isnan(out[1]).all()
    # Bit parity, not allclose: the unaffected rows rode the same
    # launch shape, so they must be the SAME bytes.
    assert np.array_equal(out[0], clean[0])
    assert np.array_equal(out[2], clean[2])


def test_guard_all_rows_poisoned_fails_the_launch(fleet):
    from tpu_dist_nn.utils.errors import IntegrityError

    eng = fleet["eng_a"]
    x = np.random.default_rng(12).uniform(0, 1, (3, 12))
    eng.launch_hook = faults.nan_launch(rows=(0, 1, 2))
    try:
        with pytest.raises(IntegrityError, match="numeric guard"):
            eng.fetch(eng.infer_async(x))
    finally:
        eng.launch_hook = None


def test_direct_infer_raises_on_any_bad_row(fleet):
    """engine.infer() is ONE request: row-level failover collapses to
    request granularity — a partially poisoned direct call must raise,
    never hand the caller a batch with NaN rows hidden inside."""
    from tpu_dist_nn.utils.errors import IntegrityError

    eng = fleet["eng_a"]
    x = np.random.default_rng(13).uniform(0, 1, (3, 12))
    eng.launch_hook = faults.nan_launch(rows=(1,))
    try:
        with pytest.raises(IntegrityError, match="numeric guard"):
            eng.infer(x)
    finally:
        eng.launch_hook = None


def test_wire_poisoned_request_is_data_loss_clean_request_ships(fleet):
    import grpc

    from tpu_dist_nn.serving import GrpcClient

    eng = fleet["eng_a"]
    client = GrpcClient(f"127.0.0.1:{fleet['port_a']}")
    try:
        x = np.random.default_rng(14).uniform(0, 1, (1, 12))
        eng.launch_hook = faults.nan_launch(rows=(0,))
        try:
            with pytest.raises(grpc.RpcError) as e:
                client.process(x)
            assert e.value.code() == grpc.StatusCode.DATA_LOSS
        finally:
            eng.launch_hook = None
        # The replica is not broken, only that launch was: the next
        # request ships normally (the router's failover + strike
        # accounting owns the replica-level consequence).
        out = client.process(x)
        assert np.isfinite(out).all()
    finally:
        client.close()


def test_guard_mask_semantics_and_disable_opt_outs():
    g = integrity.NumericGuard(enabled=True, abs_limit=1e8)
    out = np.ones((4, 3))
    out[1, 2] = np.nan
    out[3, 0] = 1e9  # finite but absurd: past abs_limit
    assert g.bad_rows(out).tolist() == [False, True, False, True]
    # Non-float, empty, and 0-d outputs are not the guard's domain.
    assert g.bad_rows(np.ones((2, 2), dtype=np.int64)) is None
    assert g.bad_rows(np.ones((0, 3))) is None
    assert g.bad_rows(np.float64(np.nan)) is None
    assert integrity.NumericGuard(enabled=False).bad_rows(out) is None


# ------------------------------------------------------ canary (rung 3)


class _FakeRep:
    """The prober's minimal replica surface: .call + .target. ``mangle``
    post-processes the deterministic reply (the tamper arm)."""

    def __init__(self, target, mangle=None, per_call_s=0.0):
        self.target = target
        self._mangle = mangle
        self._per_call_s = per_call_s
        self.calls = 0

    def call(self, method, payload, *, timeout=None, metadata=()):
        self.calls += 1
        if self._per_call_s:
            time.sleep(self._per_call_s)
        reply = b"reply:" + method.encode() + b":" + payload
        if self._mangle is not None:
            reply = self._mangle(reply)
        return reply


def _tamper_last_byte(reply: bytes) -> bytes:
    b = bytearray(reply)
    b[-1] ^= 0x01  # the wire float's low-order bits: decodes, lies
    return bytes(b)


def test_canary_golden_stable_across_prober_restarts():
    """The canary input is a constant of the system (CANARY_SEED), so a
    restarted prober — a new router process — regenerates the SAME
    payload and converges on the SAME golden digest. No state handoff
    needed for the golden to survive restarts."""
    p1 = integrity.CanaryProber(dim=8, timeout=1.0)
    p2 = integrity.CanaryProber(dim=8, timeout=1.0)
    assert p1._payloads["Process"] == p2._payloads["Process"]

    rep = _FakeRep("10.0.0.1:9")
    verdict, ev = p1.probe(rep)
    assert verdict is True and ev.get("methods") == ["Process"]
    verdict, _ = p2.probe(rep)  # the "restarted" prober
    assert verdict is True
    assert p1.golden == p2.golden
    assert p1.snapshot()["golden_source"]["Process"] == rep.target

    # A different seed is a DIFFERENT canary — the fleet-wide constant
    # is what makes digests comparable at all.
    assert integrity.CanaryProber(
        dim=8, seed=integrity.CANARY_SEED + 1, timeout=1.0
    )._payloads["Process"] != p1._payloads["Process"]


def test_canary_flags_tampered_reply_and_transport_is_not_a_verdict():
    prober = integrity.CanaryProber(dim=8, timeout=1.0)
    honest = _FakeRep("good:1")
    liar = _FakeRep("bad:1", mangle=_tamper_last_byte)
    assert prober.probe(honest)[0] is True  # establishes the golden

    verdict, ev = prober.probe(liar)
    assert verdict is False
    assert ev["golden"] == prober.golden["Process"]
    assert ev["golden_source"] == honest.target
    assert ev["digest"] != ev["golden"]

    class _Dead:
        target = "dead:1"

        def call(self, *a, **k):
            raise ConnectionError("refused")

    # Unreachable is the breaker's problem: verdict None, not False.
    verdict, ev = prober.probe(_Dead())
    assert verdict is None and "error" in ev


# ------------------------------------------- quarantine choreography


def test_quarantine_lifecycle_detect_drain_refusal_evidence_reverify(fleet):
    """The full ladder against two REAL replicas: verdict -> placement
    stops + evidence bundle, drain refuses to bypass the quarantine,
    reverify refuses while the replica is still corrupt, readmits once
    it answers on-golden again, three guard strikes re-quarantine, and
    force=True is the operator's break-glass."""
    from tpu_dist_nn.serving.pool import ReplicaPool

    target_a = f"127.0.0.1:{fleet['port_a']}"
    target_b = f"127.0.0.1:{fleet['port_b']}"
    pool = ReplicaPool([target_a, target_b], seed=5)
    try:
        prober = integrity.CanaryProber(dim=12, timeout=10.0)
        pool.canary = prober
        rep_b = next(r for r in pool.replicas() if r.target == target_b)
        verdict, _ = prober.probe(rep_b)  # golden from the healthy side
        assert verdict is True

        events = []
        pool.on_quarantine = lambda t, r, e: events.append((t, r, dict(e)))

        # Detect: the verdict moves A out of rotation and freezes the
        # evidence through the incident hook.
        assert pool.quarantine(target_a, reason="drill",
                               evidence={"planted": True}) is True
        assert pool.quarantine(target_a, reason="drill") is False  # no-op
        snap = {s["target"]: s for s in pool.snapshot()}
        assert snap[target_a]["state"] == "quarantined"
        assert snap[target_a]["quarantine_reason"] == "drill"
        assert events == [(target_a, "drill", {"planted": True})]

        # Quarantine dominates drain: the drain path would auto-rejoin
        # on the next ready scrape, bypassing reverify.
        assert pool.drain(target_a) is False
        for _ in range(12):
            placed = pool.place()
            assert placed is not None and placed.target == target_b

        # Reverify refuses while A still computes wrong: every canary
        # row poisoned -> the guard fails the probe launch -> no
        # on-golden answer, no readmission.
        fleet["eng_a"].launch_hook = faults.nan_launch(rows=(0, 1))
        try:
            res = pool.unquarantine(target_a)
            assert res["ok"] is False
            assert res["checks"]["canary"]["ok"] is False
        finally:
            fleet["eng_a"].launch_hook = None

        # Fault cleared -> the canary answers on-golden -> readmitted
        # with strikes reset and placement restored.
        res = pool.unquarantine(target_a)
        assert res["ok"] is True and res["checks"]["canary"]["ok"] is True
        snap = {s["target"]: s for s in pool.snapshot()}
        assert snap[target_a]["state"] == "active"
        assert snap[target_a].get("integrity_strikes", 0) == 0

        # Three observed INTEGRITY replies = the guard verdict: the
        # router's strike counter quarantines without any probe.
        for _ in range(pool.guard_quarantine_threshold):
            pool.note_integrity_error(target_a)
        snap = {s["target"]: s for s in pool.snapshot()}
        assert snap[target_a]["state"] == "quarantined"
        assert snap[target_a]["quarantine_reason"] == "guard"
        assert events[-1][1] == "guard"
        assert events[-1][2]["integrity_errors"] == \
            pool.guard_quarantine_threshold

        # Break-glass: force skips the checks (and says so).
        res = pool.unquarantine(target_a, force=True)
        assert res["ok"] is True and res["forced"] is True
    finally:
        pool.close(grace=0.5)


# --------------------------------------------- spot-checking (rung 4)


class _FakePool:
    """The SpotChecker's minimal pool surface over _FakeRep shadows."""

    def __init__(self, reps):
        self._reps = list(reps)
        self.begun = []

    def replicas(self):
        return list(self._reps)

    def place(self, session_key=None, exclude=frozenset()):
        for r in self._reps:
            if r.target not in exclude:
                return r
        return None

    def begin(self, rep):
        self.begun.append(rep.target)

    def done(self, rep):
        pass


def test_spotcheck_tamper_mismatch_arbitrates_to_guilty_replica():
    """Two replicas disagree on a real request's bytes; disagreement
    alone cannot convict, so the checker canary-probes BOTH and indicts
    only the one answering off-golden."""
    honest = _FakeRep("good:2")
    liar = _FakeRep("bad:2", mangle=_tamper_last_byte)
    pool = _FakePool([honest, liar])
    prober = integrity.CanaryProber(dim=4, timeout=1.0)
    assert prober.probe(honest)[0] is True  # golden established

    verdicts = []
    checker = integrity.SpotChecker(
        pool, rate=1.0, seed=21, timeout=1.0, canary=prober,
        on_verdict=lambda t, reason, ev: verdicts.append((t, reason, ev)),
    )
    # Only Process traffic is shadowed (Generate is stateful).
    assert checker.maybe_check("Generate", b"p", b"r", liar.target) is False

    # The liar served a real request; its tampered reply disagrees with
    # the honest shadow's bytes.
    payload = b"real-request-payload"
    tampered_reply = liar.call("Process", payload)
    assert checker.maybe_check(
        "Process", payload, tampered_reply, liar.target
    ) is True
    deadline = time.monotonic() + 5.0
    while not verdicts and time.monotonic() < deadline:
        time.sleep(0.01)

    assert [(t, r) for t, r, _ in verdicts] == [(liar.target, "spotcheck")]
    ev = verdicts[0][2]
    assert ev["detector"] == "spotcheck"
    assert ev["disagreed_with"] == honest.target
    assert checker.mismatches == 1
    # The shadow went through the pool's load accounting, excluded from
    # the primary.
    assert pool.begun == [honest.target]


def test_spotcheck_match_is_silent_and_rate_zero_never_samples():
    honest = _FakeRep("good:3")
    twin = _FakeRep("good:4")
    pool = _FakePool([twin, honest])
    verdicts = []
    checker = integrity.SpotChecker(
        pool, rate=1.0, seed=2, timeout=1.0,
        canary=None, on_verdict=lambda *a: verdicts.append(a),
    )
    payload = b"agreeing-payload"
    reply = honest.call("Process", payload)
    assert checker.maybe_check("Process", payload, reply,
                               honest.target) is True
    deadline = time.monotonic() + 5.0
    while checker._inflight and time.monotonic() < deadline:
        time.sleep(0.01)
    assert checker.mismatches == 0 and verdicts == []

    never = integrity.SpotChecker(pool, rate=0.0, seed=2)
    assert never.maybe_check("Process", b"p", b"r", honest.target) is False
    with pytest.raises(ValueError):
        integrity.SpotChecker(pool, rate=1.5)


# ------------------------------------------------- end-to-end drill


def test_corruption_drill_scenario_quarantines_exactly_one():
    """The checked-in corruption cell end-to-end: replica 0 poisons
    every launch, the guard fails them DATA_LOSS, the router fails over
    (clients keep getting answers — zero wrong bytes shipped), three
    strikes quarantine exactly that replica, and the availability SLO
    holds on the surviving pair."""
    from tpu_dist_nn.obs import replay as R

    verdict = R.run_scenario_file(
        os.path.join(REPO, "scenarios", "silent_corruption_quarantine.json"),
        quick_scale=0.5,
    )
    assert verdict["passed"] is True
    integ = verdict["integrity"]
    assert integ["passed"] is True
    assert [q["reason"] for q in integ["quarantined"]] == ["guard"]
    assert integ["quarantined"][0]["strikes"] >= 3
    # The guard fired (faults_fired counts the poisoned launches) and
    # the client-side replay saw NO errors: every request that landed
    # on the corrupt replica failed over to a clean answer.
    assert verdict["faults_fired"] > 0
    assert verdict["replay"]["errors"] == {}
    assert all(o["passed"] for o in verdict["objectives"])


def test_decode_step_guard_fails_bad_slot_alone():
    """The in-launch decode guard: a slot whose step comes back not-ok
    fails over ALONE with IntegrityError mid-generation; the other
    resident slot's stream is untouched and completes. Driven through
    the injected-kernel scheduler by replacing the internal ``_step``
    with one that returns the 3-tuple an ok vector rides on (the public
    ``step_fn`` seam stays 2-tuple — construction wraps it to ok=None,
    which must leave the guard disarmed)."""
    import threading

    from tpu_dist_nn.serving.continuous import ContinuousScheduler
    from tpu_dist_nn.utils.errors import IntegrityError

    T, N = 4, 40  # a long budget: the victim pair overlaps for ~200ms

    def fake_prefill(params, cache, slot, tokens, start, key):
        return np.int32(1), cache

    def fake_step(params, cache, pos, active, tok, key):
        time.sleep(0.005)
        return np.asarray(tok) + 1, cache

    sched = ContinuousScheduler(
        None, None, prefill_fn=fake_prefill, step_fn=fake_step,
        slots=2, prompt_len=T, max_new_tokens=N,
    )
    wrapped = sched._step
    try:
        # The ctor-wrapped seam reports ok=None: guard disarmed, a
        # plain submit completes even with GUARD force-enabled.
        out = sched.submit(np.ones((1, T), np.int32), max_new_tokens=2)
        assert out.shape == (1, T + N)

        def poisoned(params, cache, pos, active, tok, key, *on_device):
            toks, _ok, cache = wrapped(params, cache, pos, active, tok, key,
                                       *on_device)
            ok = np.ones(2, bool)
            if active[0] and active[1]:  # both resident: indict slot 1
                ok[1] = False
            return toks, ok, cache

        sched._step = poisoned
        outs, errs = [], []

        def caller(seed):
            try:
                outs.append(sched.submit(np.full((1, T), seed, np.int32)))
            except Exception as e:  # noqa: BLE001 — collected
                errs.append(e)

        threads = [threading.Thread(target=caller, args=(s,))
                   for s in (3, 4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        # Exactly one row was indicted (whichever bound slot 1) and the
        # other finished its full budget despite sharing every launch
        # with the poisoned slot.
        assert len(errs) == 1 and isinstance(errs[0], IntegrityError)
        assert "slot 1" in str(errs[0])
        assert len(outs) == 1 and outs[0].shape == (1, T + N)
    finally:
        sched._step = wrapped
        sched.close(timeout=5.0)
