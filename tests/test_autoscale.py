"""Fleet autopilot (ISSUE 12): burn-rate-driven autoscaling, request
hedging, weighted p2c, and the fleet manifest generator.

The control loop is unit-tested on a real :class:`ReplicaPool` with
synthetic targets and driven ticks (injected clock — no sleeps paced
by cooldowns); the quick-tier smoke runs the REAL loop over loopback
fake-engine replicas with a deterministic ``faults.py``-paced burst:
2 replicas scale to 3 under the burst and back down after it, with
every request answered. Hedging races fake futures so first-reply-
wins / loser-cancelled are asserted exactly, plus a real loopback
straggler-rescue; the manifest generator is asserted on content.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests.test_batcher_pipeline import AsyncFakeEngine
from tpu_dist_nn.obs.exposition import MetricsServer
from tpu_dist_nn.obs.registry import REGISTRY, Registry
from tpu_dist_nn.serving import (
    CircuitBreaker,
    GrpcClient,
    ReplicaPool,
    serve_engine,
    serve_router,
)
from tpu_dist_nn.serving.autoscale import Autoscaler
from tpu_dist_nn.serving.pool import ACTIVE, DRAINING
from tpu_dist_nn.serving.router import (
    HedgePolicy,
    Router,
    admin_post_routes,
    admin_routes,
)
from tpu_dist_nn.testing import faults


def _counter_total(name: str) -> float:
    m = REGISTRY.get(name)
    if m is None:
        return 0.0
    return float(sum(child.value for _, child in m.samples()))


def _fresh_targets(*names):
    for n in names:
        CircuitBreaker.evict(n)
    return names


def _wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class _FakeSLO:
    """An SLOTracker stand-in whose fast burn the test dials."""

    def __init__(self, burn=0.0, total=10.0):
        self.burn = burn
        self.total = total

    def status(self):
        return {"objectives": [{
            "name": "synthetic",
            "windows": {"fast": {"burn_rate": self.burn,
                                 "total": self.total}},
        }]}


def _scaler(pool, **kw):
    """An Autoscaler with test-friendly defaults: everything decided
    in one tick, no cooldowns, virtual clock."""
    clk = kw.pop("clk", [0.0])
    defaults = dict(
        min_replicas=1, max_replicas=5,
        up_cooldown=0.0, down_cooldown=0.0,
        up_stable_ticks=1, down_stable_ticks=1,
        decommission_grace=30.0,
        clock=lambda: clk[0],
    )
    defaults.update(kw)
    a = Autoscaler(pool, **defaults)
    a._clk = clk  # the test advances it
    return a


def _recording_spawner(pool, prefix="spawned"):
    """A spawner that adds a synthetic replica and records the call."""
    calls = []

    def spawner():
        t = f"{prefix}:{len(calls)}"
        CircuitBreaker.evict(t)
        calls.append(t)
        pool.add(t)

    return spawner, calls


# ----------------------------------------------------- control loop


def test_synthetic_burn_scales_up_within_one_tick():
    targets = _fresh_targets("as-burn:a")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=5.0)
    spawner, calls = _recording_spawner(pool, "as-burn-spawn")
    a = _scaler(pool, spawner=spawner, slo=slo, min_replicas=1,
                max_replicas=3)
    ups0 = _counter_total("tdn_autoscale_decisions_total")
    a.tick()
    assert _wait_until(lambda: calls and a._spawning == 0), \
        "fast burn > 1 must trigger a spawn within ONE evaluation tick"
    assert len(calls) == 1
    assert len(pool.targets()) == 2
    assert _counter_total("tdn_autoscale_decisions_total") == ups0 + 1
    pool.close()


def test_occupancy_over_ceiling_scales_up_and_band_is_quiet():
    targets = _fresh_targets("as-occ:a", "as-occ:b")
    pool = ReplicaPool(list(targets), seed=0)
    spawner, calls = _recording_spawner(pool, "as-occ-spawn")
    a = _scaler(pool, spawner=spawner, target_occupancy=0.6,
                hysteresis=0.25, min_replicas=2, max_replicas=4)
    now = time.monotonic()
    # Inside the hysteresis band (util == target): no decision.
    for r in pool.replicas():
        r.occupancy, r.pending_rows, r.scraped_at = 0.6, 0.0, now
    a.tick()
    time.sleep(0.05)
    assert not calls, "utilization inside the band must not scale"
    # Saturated decode ladders (occupancy 1.0 > 0.75 ceiling): scale.
    for r in pool.replicas():
        r.occupancy = 1.0
        r.scraped_at = time.monotonic()
    a.tick()
    assert _wait_until(lambda: calls and a._spawning == 0)
    assert len(calls) == 1
    pool.close()


def test_scale_down_below_floor_via_observed_drain_zero_dropped():
    """The victim drains before it is removed: with a forward still
    outstanding it stays DRAINING (un-placed but alive); only at
    outstanding == 0 does the next tick remove it. (Pool-SPAWNED
    replicas get membership removal; static ones are parked — see
    the park/unpark test below.)"""
    targets = _fresh_targets("as-down:a", "as-down:b", "as-down:c")
    pool = ReplicaPool(list(targets), seed=0)
    a = _scaler(pool, min_replicas=2, max_replicas=3)
    reps = {r.target: r for r in pool.replicas()}
    for r in reps.values():
        r.spawn_argv = ["stub"]  # pool-spawned: removal is the end state
    # Idle fleet except the victim's one in-flight forward; the others
    # look busier so the victim choice is deterministic.
    pool.begin(reps["as-down:a"])
    for _ in range(5):
        pool.begin(reps["as-down:b"])
        pool.begin(reps["as-down:c"])
    # Utilization: (1 + 5 + 5) / (32 * 3) ~ 0.11 < 0.45 floor.
    a.tick()
    assert reps["as-down:a"].state == DRAINING
    assert reps["as-down:a"].decommissioning
    assert "as-down:a" in pool.targets(), \
        "a replica with an outstanding forward must NOT be removed"
    a.tick()
    assert "as-down:a" in pool.targets()
    pool.done(reps["as-down:a"])  # the in-flight reply lands
    a.tick()
    assert "as-down:a" not in pool.targets(), \
        "drain observed (outstanding 0) -> removed"
    assert sorted(pool.targets()) == ["as-down:b", "as-down:c"]
    # At min_replicas now: no further shrink.
    a.tick()
    a.tick()
    assert len(pool.targets()) == 2
    pool.close()


def test_operator_undrain_cancels_decommission_not_removed():
    """Regression: pool.undrain during a scale-down clears the
    replica's decommissioning flag (it is back in service), but the
    autoscaler's pending-removal entry used to survive — and the next
    tick silently removed the in-service replica."""
    targets = _fresh_targets("as-cancel:a", "as-cancel:b", "as-cancel:c")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=0.0)
    a = _scaler(pool, slo=slo, min_replicas=2, max_replicas=3)
    reps = {r.target: r for r in pool.replicas()}
    for r in reps.values():
        r.spawn_argv = ["stub"]
    pool.begin(reps["as-cancel:a"])  # deterministic victim, held busy
    for _ in range(5):
        pool.begin(reps["as-cancel:b"])
        pool.begin(reps["as-cancel:c"])
    a.tick()
    assert reps["as-cancel:a"].decommissioning
    assert pool.undrain("as-cancel:a"), "operator cancels the scale-down"
    assert not reps["as-cancel:a"].decommissioning
    pool.done(reps["as-cancel:a"])  # now idle AND removable-looking
    slo.burn = 5.0  # burning budget: no further scale-down decisions
    a.tick()
    a.tick()
    assert "as-cancel:a" in pool.targets(), \
        "an undrained (in-service) replica must never be removed"
    assert reps["as-cancel:a"].state == ACTIVE
    assert a.status()["decommissioning"] == []
    pool.close()


def test_static_fleet_parks_and_unparks_instead_of_ratcheting():
    """Regression: on a fleet the pool did not spawn (static /
    manifest-managed), scale-down used to REMOVE membership — and with
    no spawner, nothing could ever grow the fleet back. Static victims
    are parked (drained, rejoin-exempt) and scale-up un-parks them."""
    targets = _fresh_targets("as-park:a", "as-park:b", "as-park:c")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=0.0)
    a = _scaler(pool, slo=slo, min_replicas=1, max_replicas=3,
                flap_reversals=99)  # the down→up cycle IS the test
    a.tick()  # idle: park one
    assert sorted(pool.targets()) == sorted(targets), \
        "static membership must survive a scale-down"
    parked = a.status()["parked"]
    assert len(parked) == 1
    rep = {r.target: r for r in pool.replicas()}[parked[0]]
    assert rep.state == DRAINING and rep.decommissioning
    a._clk[0] += 10.0
    a.tick()
    assert len(a.status()["parked"]) == 2, "keeps parking down to min"
    # Load returns: scale-up re-admits parked capacity (no spawner
    # needed) instead of being stuck at min forever.
    slo.burn = 5.0
    a._clk[0] += 10.0
    a.tick()
    assert a.current_size() == 2
    a._clk[0] += 10.0
    a.tick()
    assert a.current_size() == 3
    assert a.status()["parked"] == []
    assert all(r.state == ACTIVE and not r.decommissioning
               for r in pool.replicas())
    pool.close()


def test_up_cooldown_suppresses_back_to_back_spawns():
    targets = _fresh_targets("as-cool:a")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=5.0)
    spawner, calls = _recording_spawner(pool, "as-cool-spawn")
    a = _scaler(pool, spawner=spawner, slo=slo, up_cooldown=100.0,
                max_replicas=5)
    a.tick()
    assert _wait_until(lambda: len(calls) == 1 and a._spawning == 0)
    a._clk[0] += 1.0  # still inside the cooldown
    a.tick()
    a.tick()
    time.sleep(0.05)
    assert len(calls) == 1, "a second spawn inside up_cooldown"
    a._clk[0] += 200.0  # cooldown over, burn persists
    a.tick()
    assert _wait_until(lambda: len(calls) == 2 and a._spawning == 0)
    pool.close()


def test_flap_reversals_suppress_and_count_and_recover():
    targets = _fresh_targets("as-flap:a", "as-flap:b")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=5.0)
    spawner, calls = _recording_spawner(pool, "as-flap-spawn")
    a = _scaler(pool, spawner=spawner, slo=slo, min_replicas=1,
                max_replicas=5, flap_window=1000.0, flap_reversals=2,
                flap_cooldown=500.0)
    flaps0 = _counter_total("tdn_autoscale_flaps_total")
    a.tick()  # up
    assert _wait_until(lambda: len(calls) == 1 and a._spawning == 0)
    slo.burn = 0.0  # idle fleet -> down (reversal #1, allowed)
    a._clk[0] += 1.0
    a.tick()
    assert any(r.decommissioning for r in pool.replicas())
    slo.burn = 5.0  # burn again -> up would be reversal #2: FLAP
    a._clk[0] += 1.0
    a.tick()
    time.sleep(0.05)
    assert len(calls) == 1, "the flapping reversal must be suppressed"
    assert _counter_total("tdn_autoscale_flaps_total") == flaps0 + 1
    assert a.status()["flap_suppressed"] is True
    # Still muted inside the flap cooldown.
    a._clk[0] += 100.0
    a.tick()
    time.sleep(0.05)
    assert len(calls) == 1
    assert a.current_size() == 2
    # Past the cooldown the policy re-arms: the scale-up re-admits the
    # PARKED static victim (cheaper than a spawn) and capacity is back.
    a._clk[0] += 1000.0
    a.tick()
    assert _wait_until(lambda: a.current_size() == 3
                       and a._spawning == 0)
    assert len(calls) == 1, "un-park must be preferred over a spawn"
    assert a.status()["flap_suppressed"] is False
    assert a.status()["parked"] == []
    pool.close()


def test_bounds_are_hard_and_crash_respawn_counts_as_capacity():
    targets = _fresh_targets("as-bound:a", "as-bound:b")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=9.0)
    spawner, calls = _recording_spawner(pool, "as-bound-spawn")
    a = _scaler(pool, spawner=spawner, slo=slo, min_replicas=2,
                max_replicas=2)
    a.tick()
    time.sleep(0.05)
    assert not calls, "at max_replicas a burning SLO must not spawn"
    # A crashed child mid-respawn is DRAINING but still counts as
    # capacity: min_replicas is satisfied, so no double-spawn.
    rep = pool.replicas()[0]
    rep.state = DRAINING
    rep.respawning = True
    slo.burn = 0.0
    a._clk[0] += 10.0
    a.tick()
    time.sleep(0.05)
    assert not calls, \
        "a crash-respawn in flight must not read as a shrunken fleet"
    assert a.current_size() == 2
    pool.close()


def test_manual_scale_override_via_post_route_and_status_route():
    targets = _fresh_targets("as-post:a")
    pool = ReplicaPool(list(targets), seed=0)
    spawner, calls = _recording_spawner(pool, "as-post-spawn")
    a = _scaler(pool, spawner=spawner, min_replicas=1, max_replicas=3,
                up_cooldown=1e9)  # cooldown must NOT gate the override
    srv = MetricsServer(0, "127.0.0.1",
                        routes=admin_routes(pool, autoscaler=a),
                        post_routes=admin_post_routes(pool, a))
    try:
        base = f"http://127.0.0.1:{srv.port}"

        def post(path):
            req = urllib.request.Request(base + path, data=b"",
                                         method="POST")
            with urllib.request.urlopen(req, timeout=5) as resp:
                return resp.status, json.loads(resp.read())

        status, doc = post("/router/scale?replicas=3")
        assert status == 200 and doc["mode"] == "manual"
        assert doc["granted"] == 3
        a.tick()
        assert _wait_until(lambda: len(calls) == 1 and a._spawning == 0)
        a.tick()
        assert _wait_until(lambda: len(calls) == 2 and a._spawning == 0)
        a.tick()
        time.sleep(0.05)
        assert len(calls) == 2, "override converged at 3, stop there"
        # Out-of-bounds requests clamp to the envelope.
        _, doc = post("/router/scale?replicas=99")
        assert doc["granted"] == 3
        # GET on the POST-only path is rejected (a scraper sweep must
        # not actuate the fleet).
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/router/scale?replicas=1",
                                   timeout=5)
        assert ei.value.code == 405
        # Back to the policy.
        status, doc = post("/router/scale?mode=auto")
        assert status == 200 and doc["mode"] == "auto"
        with urllib.request.urlopen(base + "/router/autoscale",
                                    timeout=5) as resp:
            doc = json.loads(resp.read())
        assert doc["mode"] == "auto" and doc["current"] == 3
    finally:
        srv.close()
        pool.close()


def test_override_resets_stability_counters():
    """Regression: a breach tick counted BEFORE a manual override used
    to survive it frozen — one noisy scrape after mode=auto completed
    the streak and scaled immediately. The streak restarts."""
    targets = _fresh_targets("as-reset:a")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=5.0)
    spawner, calls = _recording_spawner(pool, "as-reset-spawn")
    a = _scaler(pool, spawner=spawner, slo=slo, up_stable_ticks=2,
                min_replicas=1, max_replicas=3)
    a.tick()  # breach tick 1 of 2: no action yet
    time.sleep(0.05)
    assert not calls
    a.set_override(1)  # park the fleet at its current size
    a._clk[0] += 1.0
    a.tick()
    a.clear_override()
    a._clk[0] += 1.0
    a.tick()  # back to auto, still breaching: tick 1 of 2 AGAIN
    time.sleep(0.05)
    assert not calls, "stability streak must restart after an override"
    a._clk[0] += 1.0
    a.tick()  # second consecutive breach: now act
    assert _wait_until(lambda: len(calls) == 1 and a._spawning == 0)
    pool.close()


def test_stale_park_pruned_and_noop_scale_up_burns_no_cooldown():
    """Regression: an operator undraining a parked replica left a
    stale park entry; the next scale-up consumed its cooldown slot and
    a flap-history action on an un-park that could not happen."""
    targets = _fresh_targets("as-stale:a", "as-stale:b")
    pool = ReplicaPool(list(targets), seed=0)
    slo = _FakeSLO(burn=0.0)
    a = _scaler(pool, slo=slo, min_replicas=1, max_replicas=3,
                flap_reversals=99)
    a.tick()  # idle: parks one
    parked = a.status()["parked"]
    assert len(parked) == 1
    assert pool.undrain(parked[0]), "operator takes the replica back"
    slo.burn = 5.0
    a._clk[0] += 10.0
    a.tick()  # prune drops the stale entry; no actuator remains
    assert a.status()["parked"] == []
    assert a._last_up is None, \
        "a no-op scale-up must not consume the cooldown slot"
    pool.close()


def test_post_scale_without_autoscaler_is_conflict():
    srv = MetricsServer(0, "127.0.0.1",
                        post_routes=admin_post_routes())
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/router/scale?replicas=2",
            data=b"", method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 409
        assert b"--autoscale-min" in ei.value.read()
    finally:
        srv.close()


# ----------------------------------------------------- weighted p2c


def test_weighted_p2c_explicit_weights_blend_heterogeneous_fleet():
    a, b = _fresh_targets("w:fast", "w:slow")
    pool = ReplicaPool([a, b], weights=[4.0, 1.0], seed=0)
    ra, rb = pool.replicas()
    # Equal raw backlog: the 4x replica scores 1/4 of the 1x one and
    # keeps winning until it holds ~4x the work.
    for _ in range(4):
        pool.begin(ra)
        pool.begin(rb)
    assert {pool.place().target for _ in range(20)} == {a}
    for _ in range(12):
        pool.begin(ra)  # fast replica now at 16 vs 4: scores 4 vs 4
    for _ in range(2):
        pool.begin(ra)  # past its fair share: slow one wins again
    assert {pool.place().target for _ in range(20)} == {b}
    pool.close()


def test_weight_derives_from_scraped_warm_buckets_unless_explicit():
    a, b = _fresh_targets("w:warm", "w:cold")
    pool = ReplicaPool([a, b], seed=0)
    ra, rb = pool.replicas()
    assert ra.capacity_weight == 1.0, "no signal -> homogeneous"
    ra.warm_buckets = 8.0
    rb.warm_buckets = 2.0
    assert ra.capacity_weight == 8.0 and rb.capacity_weight == 2.0
    ra.weight = 1.5  # explicit flag beats the derived signal
    assert ra.capacity_weight == 1.5
    pool.close()


# --------------------------------------------------------- hedging


class _FakeFuture:
    def __init__(self, result=None, error=None, delay=0.0):
        self._result = result
        self._error = error
        self._done = threading.Event()
        self._cancelled = False
        self._callbacks = []
        self._lock = threading.Lock()
        if delay <= 0:
            self._complete()
        else:
            t = threading.Timer(delay, self._complete)
            t.daemon = True  # a cancelled long-delay fake must not
            t.start()        # hold interpreter exit hostage

    def _complete(self):
        with self._lock:
            if self._done.is_set():
                return
            self._done.set()
            callbacks = list(self._callbacks)
        for cb in callbacks:
            cb(self)

    def add_done_callback(self, cb):
        with self._lock:
            if not self._done.is_set():
                self._callbacks.append(cb)
                return
        cb(self)

    def done(self):
        return self._done.is_set()

    def cancelled(self):
        return self._cancelled

    def cancel(self):
        with self._lock:
            if self._done.is_set():
                return False
            self._cancelled = True
            self._error = RuntimeError("cancelled")
        self._complete()
        return True

    def result(self, timeout=None):
        self._done.wait(timeout)
        if self._error is not None:
            raise self._error
        return self._result


class _Ctx:
    def invocation_metadata(self):
        return ()

    def time_remaining(self):
        return None

    def set_trailing_metadata(self, md):
        pass

    def abort(self, code, msg):
        raise AssertionError(f"aborted {code}: {msg}")


def _primed_latency(seconds=0.02, n=30):
    reg = Registry()
    fam = reg.histogram("t_hedge_seconds", "test latency",
                        labels=("method",))
    child = fam.labels(method="Process")
    for _ in range(n):
        child.observe(seconds)
    return fam


def test_hedge_fires_once_first_reply_wins_loser_cancelled():
    a, b = _fresh_targets("hedge:slow", "hedge:fast")
    pool = ReplicaPool([a, b], seed=0)
    ra, rb = pool.replicas()
    futures = {}

    def make_call_future(rep, result, delay):
        def call_future(method, payload, *, timeout=None, metadata=()):
            fut = _FakeFuture(result=result, delay=delay)
            futures[rep.target] = fut
            return fut

        return call_future

    ra.call_future = make_call_future(ra, b"slow-reply", 1.0)
    rb.call_future = make_call_future(rb, b"fast-reply", 0.01)
    # p2c must pick the slow replica as primary.
    for _ in range(5):
        pool.begin(rb)
    hedge = HedgePolicy(1.0, min_observations=1,
                        latency=_primed_latency(0.02))
    router = Router(pool, hedge=hedge)
    fired0 = _counter_total("tdn_router_hedges_total")
    wins0 = _counter_total("tdn_router_hedge_wins_total")
    reply = router.handle("Process", b"req", _Ctx())
    assert reply == b"fast-reply", "first reply wins"
    assert _counter_total("tdn_router_hedges_total") == fired0 + 1, \
        "exactly one hedge per request"
    assert _counter_total("tdn_router_hedge_wins_total") == wins0 + 1
    assert futures["hedge:slow"].cancelled(), "the loser is cancelled"
    assert _wait_until(
        lambda: ra.outstanding == 0 and rb.outstanding == 5
    ), "both copies' outstanding bookkeeping must settle"
    pool.close()


def test_hedge_primary_wins_inside_patience_no_hedge_fired():
    a, b = _fresh_targets("hedgefast:a", "hedgefast:b")
    pool = ReplicaPool([a, b], seed=0)
    for rep in pool.replicas():
        rep.call_future = (
            lambda method, payload, timeout=None, metadata=():
            _FakeFuture(result=b"quick", delay=0.0)
        )
    hedge = HedgePolicy(1.0, min_observations=1,
                        latency=_primed_latency(0.05))
    router = Router(pool, hedge=hedge)
    fired0 = _counter_total("tdn_router_hedges_total")
    assert router.handle("Process", b"req", _Ctx()) == b"quick"
    assert _counter_total("tdn_router_hedges_total") == fired0, \
        "a primary inside the patience window must not hedge"
    pool.close()


def test_hedge_deterministic_error_propagates_without_waiting():
    """Regression: a non-transient verdict (INVALID_ARGUMENT) from one
    hedge copy used to wait out the OTHER in-flight copy before
    surfacing — up to the full forward timeout. It must propagate
    immediately and cancel the survivor."""
    import grpc

    a, b = _fresh_targets("hedgedet:a", "hedgedet:b")
    pool = ReplicaPool([a, b], seed=0)
    ra, rb = pool.replicas()

    class _Invalid(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.INVALID_ARGUMENT

        def details(self):
            return "bad matrix"

    futures = {}

    def make(rep, **kw):
        def call_future(method, payload, *, timeout=None, metadata=()):
            fut = _FakeFuture(**kw)
            futures[rep.target] = fut
            return fut

        return call_future

    # Primary stalls (hedge fires), then errors DETERMINISTICALLY at
    # ~60ms while the hedge would take 10s.
    ra.call_future = make(ra, error=_Invalid(), delay=0.06)
    rb.call_future = make(rb, result=b"slow", delay=10.0)
    for _ in range(5):
        pool.begin(rb)  # primary = ra
    hedge = HedgePolicy(1.0, min_observations=1,
                        latency=_primed_latency(0.02))
    router = Router(pool, hedge=hedge)

    class AbortCtx(_Ctx):
        def abort(self, code, msg):
            raise _Abort(code, msg)

    class _Abort(Exception):
        def __init__(self, code, msg):
            super().__init__(msg)
            self.code = code

    t0 = time.monotonic()
    with pytest.raises(_Abort) as ei:
        router.handle("Process", b"req", AbortCtx())
    elapsed = time.monotonic() - t0
    assert ei.value.code == grpc.StatusCode.INVALID_ARGUMENT
    assert elapsed < 2.0, (
        f"deterministic verdict must not wait out the 10s hedge copy "
        f"(took {elapsed:.1f}s)"
    )
    assert futures["hedgedet:b"].cancelled(), \
        "the surviving copy is cancelled, not awaited"
    pool.close()


def test_hedge_wedged_copies_cancelled_no_outstanding_leak():
    """Regression: when BOTH hedge copies wedge past the wait cap, the
    pending futures must be cancelled on the bail-out path — each
    holds a pool.begin() that only its done callback releases, and
    leaking it biased p2c away from the replica forever and wedged
    any later drain's outstanding==0 barrier."""
    import grpc

    a, b = _fresh_targets("hedgewedge:a", "hedgewedge:b")
    pool = ReplicaPool([a, b], seed=0)
    ra, rb = pool.replicas()
    futures = {}

    def make(rep):
        def call_future(method, payload, *, timeout=None, metadata=()):
            fut = _FakeFuture(result=b"never", delay=3600.0)
            futures[rep.target] = fut
            return fut

        return call_future

    ra.call_future = make(ra)
    rb.call_future = make(rb)
    for _ in range(3):
        pool.begin(rb)  # primary = ra
    hedge = HedgePolicy(1.0, min_observations=1,
                        latency=_primed_latency(0.02))
    # retry=None: one attempt, so the bail-out path surfaces directly.
    router = Router(pool, retry=None, forward_timeout=0.2, hedge=hedge)

    class _Abort(Exception):
        def __init__(self, code, msg):
            super().__init__(msg)
            self.code = code

    class AbortCtx(_Ctx):
        def abort(self, code, msg):
            raise _Abort(code, msg)

    with pytest.raises(_Abort) as ei:
        router.handle("Process", b"req", AbortCtx())
    assert ei.value.code == grpc.StatusCode.DEADLINE_EXCEEDED
    assert futures["hedgewedge:a"].cancelled()
    assert futures["hedgewedge:b"].cancelled()
    assert _wait_until(
        lambda: ra.outstanding == 0 and rb.outstanding == 3
    ), "wedged copies must release their outstanding accounting"
    pool.close()


def test_hedge_off_for_generate_by_default():
    a, b = _fresh_targets("hedgegen:a", "hedgegen:b")
    pool = ReplicaPool([a, b], seed=0)
    for rep in pool.replicas():
        rep.call = (
            lambda method, payload, timeout=None, metadata=(): b"tokens"
        )
        rep.call_future = _boom
    hedge = HedgePolicy(1.0, min_observations=1,
                        latency=_primed_latency(0.02))
    assert not hedge.applies("Generate")
    router = Router(pool, hedge=hedge)
    fired0 = _counter_total("tdn_router_hedges_total")
    assert router.handle("Generate", b"req", _Ctx()) == b"tokens"
    assert _counter_total("tdn_router_hedges_total") == fired0, \
        "Generate is not idempotent under sampling: no hedging unless " \
        "opted in"
    pool.close()


def _boom(*a, **k):
    raise AssertionError("call_future must not be used on this path")


def test_hedge_skipped_without_latency_history():
    a, b = _fresh_targets("hedgecold:a", "hedgecold:b")
    pool = ReplicaPool([a, b], seed=0)
    for rep in pool.replicas():
        rep.call = (
            lambda method, payload, timeout=None, metadata=(): b"ok"
        )
        rep.call_future = _boom
    reg = Registry()
    empty = reg.histogram("t_cold_seconds", "", labels=("method",))
    hedge = HedgePolicy(1.0, min_observations=5, latency=empty)
    assert hedge.delay("Process") is None
    router = Router(pool, hedge=hedge)
    assert router.handle("Process", b"req", _Ctx()) == b"ok"
    pool.close()


def test_hedge_rescues_straggler_over_loopback_wire():
    """End-to-end: a 2-replica loopback fleet where one replica is a
    deliberate straggler; hedged Process requests are rescued by the
    fast replica and p99 improves vs the same fleet unhedged."""
    slow = AsyncFakeEngine(dim=8, dispatch_seconds=0.12)
    fast = AsyncFakeEngine(dim=8, dispatch_seconds=0.002)
    servers, targets = [], []
    for e in (slow, fast):
        srv, port = serve_engine(e, 0, host="127.0.0.1")
        servers.append(srv)
        targets.append(f"127.0.0.1:{port}")
    _fresh_targets(*targets)
    hedge = HedgePolicy(1.0, min_observations=1, min_delay_s=0.02,
                        latency=_primed_latency(0.02))
    pool = ReplicaPool(targets, seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1", hedge=hedge)
    try:
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=10.0, breaker=None)
        x = np.zeros((1, 8))
        fired0 = _counter_total("tdn_router_hedges_total")
        lats = []
        for _ in range(12):
            t0 = time.monotonic()
            out = c.process(x)
            lats.append(time.monotonic() - t0)
            assert out.shape == (1, 8)
        c.close()
        assert _counter_total("tdn_router_hedges_total") > fired0, \
            "requests placed on the straggler must have hedged"
        # Every request beat the straggler's 120ms dispatch: the hedge
        # (patience ~20-30ms + fast replica ~ms) rescued the tail.
        assert max(lats) < 0.12, (
            f"hedge should cap the tail below the straggler's 120ms "
            f"service time, got max {max(lats) * 1e3:.0f}ms"
        )
    finally:
        rsrv.stop(0)
        pool.close()
        for srv in servers:
            srv.stop(0)


# ------------------------------------------------ quick-tier smoke


def test_autoscale_smoke_fleet_scales_up_and_back_down():
    """The acceptance drill: a 2-replica loopback fleet under a
    deterministic faults.py-paced burst scales to 3 within the burst
    and drains back to 2 after it, with every request answered (zero
    dropped). The control loop is driven tick-by-tick so nothing
    depends on wall-clock cadence."""
    engines, servers, targets = [], [], []

    def add_replica():
        e = AsyncFakeEngine(dim=8)
        # The deterministic pacing: every launch pays a fixed
        # faults.py delay, so the burst's backlog (and the signal the
        # autoscaler sees) is load-shaped, not scheduler noise.
        e.infer_async = faults.wrap(
            e.infer_async,
            faults.FaultPlan(every=1, fault=faults.delay(0.03)),
        )
        srv, port = serve_engine(e, 0, host="127.0.0.1")
        engines.append(e)
        servers.append(srv)
        t = f"127.0.0.1:{port}"
        CircuitBreaker.evict(t)
        targets.append(t)
        return t

    for _ in range(2):
        add_replica()
    pool = ReplicaPool(targets[:2], seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    spawned = []

    def spawner():
        t = add_replica()
        spawned.append(t)
        pool.add(t)

    a = Autoscaler(
        pool, min_replicas=2, max_replicas=3, spawner=spawner,
        rows_capacity=2.0, target_occupancy=0.6, hysteresis=0.25,
        up_cooldown=0.0, down_cooldown=0.0,
        up_stable_ticks=2, down_stable_ticks=2,
        decommission_grace=10.0,
    )
    replies = []
    errors = []
    lock = threading.Lock()

    def worker(i):
        try:
            c = GrpcClient(f"127.0.0.1:{rport}", timeout=30.0,
                           breaker=None)
            x = np.full((1, 8), float(i))
            for _ in range(6):
                out = c.process(x)
                with lock:
                    replies.append(out[0, 0])
            c.close()
        except Exception as e:  # noqa: BLE001 — the assertion below reports it
            with lock:
                errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for th in threads:
        th.start()
    # Drive the control loop while the burst runs: 8 concurrent rows
    # over 2 replicas at rows_capacity 2 pushes utilization ~2x the
    # 0.75 ceiling; two stable ticks fire the spawn.
    deadline = time.monotonic() + 20.0
    while any(th.is_alive() for th in threads):
        a.tick()
        if time.monotonic() > deadline:
            break
        time.sleep(0.02)
    for th in threads:
        th.join(timeout=30.0)
    assert not errors, f"burst must complete cleanly: {errors[:3]}"
    assert len(replies) == 48, "zero dropped requests through the scale-up"
    assert len(spawned) == 1 and len(targets) == 3, \
        "the burst must have scaled 2 -> 3"
    # Post-burst: idle utilization below the floor drains capacity
    # back out through the observed-drain choreography. The replicas
    # here are in-process (not pool-spawned), so the victim is PARKED
    # — drained, out of rotation, re-admittable — not removed.
    assert _wait_until(lambda: a._spawning == 0)
    active = []
    for _ in range(20):
        a.tick()
        active = [r for r in pool.replicas() if r.state == ACTIVE
                  and not r.decommissioning]
        if len(active) == 2:
            break
        time.sleep(0.02)
    assert len(active) == 2, "idle fleet must scale back down"
    assert a.current_size() == 2
    assert len(a.status()["parked"]) == 1
    assert _counter_total("tdn_autoscale_decisions_total") >= 2
    rsrv.stop(0)
    pool.close()
    for srv in servers:
        srv.stop(0)


# ------------------------------------------------------- manifests


def test_compose_manifest_wires_drain_choreography():
    from tpu_dist_nn.serving.manifest import build_spec, compose_manifest

    spec = build_spec(3, drain_grace_seconds=10.0,
                      autoscale={"min": 2, "max": 4,
                                 "target_occupancy": 0.7},
                      hedge_after_p99_ratio=2.0)
    text = compose_manifest(spec)
    for i in range(3):
        assert f"tdn-replica-{i}:" in text
    assert "/healthz" in text, "healthcheck must speak the pool's probe"
    assert "stop_grace_period: 15s" in text, \
        "stop grace must cover --drain-grace-seconds"
    assert "restart: unless-stopped" in text
    assert ("\"--replicas\", \"tdn-replica-0:5101,tdn-replica-1:5101,"
            "tdn-replica-2:5101\"") in text
    assert "\"--replica-metrics\", \"tdn-replica-0:9101" in text
    assert "--autoscale-min" in text and "--hedge-after-p99-ratio" in text
    assert "condition: service_healthy" in text


def test_k8s_manifest_stable_dns_probes_and_grace():
    from tpu_dist_nn.serving.manifest import build_spec, k8s_manifest

    spec = build_spec(2, drain_grace_seconds=10.0)
    text = k8s_manifest(spec)
    assert "kind: StatefulSet" in text and "clusterIP: None" in text, \
        "replicas need stable per-pod DNS (headless Service)"
    assert "tdn-replica-0.tdn-replica:5101,tdn-replica-1.tdn-replica:5101" \
        in text.replace('", "', "|").replace('"', "").replace("|", ",") \
        or "tdn-replica-0.tdn-replica" in text
    assert "readinessProbe" in text and "path: /healthz" in text
    assert "terminationGracePeriodSeconds: 15" in text
    assert "kind: Deployment" in text  # the router
    assert text.count("kind: Service") == 2


def test_manifest_rejects_invalid_autoscale_bounds():
    """The same envelope Autoscaler enforces: an invalid manifest must
    fail at generation, not crash-loop the deployed router."""
    from tpu_dist_nn.serving.manifest import build_spec

    with pytest.raises(ValueError):
        build_spec(2, autoscale={"min": 5, "max": 2})
    with pytest.raises(ValueError):
        build_spec(2, autoscale={"min": 0, "max": 2})
    with pytest.raises(ValueError):
        build_spec(2, autoscale={"min": 1, "max": 2,
                                 "target_occupancy": 0.0})
    with pytest.raises(ValueError):
        build_spec(2, autoscale={"max": 2})


def test_manifest_sized_from_running_pool_snapshot():
    from tpu_dist_nn.serving.manifest import spec_from_snapshot

    snap = [
        {"target": "a:1", "state": "active"},
        {"target": "b:1", "state": "draining"},
        {"target": "c:1", "state": "removed"},
    ]
    spec = spec_from_snapshot(snap)
    assert spec["replicas"] == 2, "removed replicas don't count"
    with pytest.raises(ValueError):
        spec_from_snapshot([{"target": "x", "state": "removed"}])


def test_fleet_manifest_cli_emits_compose(capsys):
    from tpu_dist_nn import cli

    rc = cli.main(["fleet", "manifest", "--replicas-count", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "services:" in out and "tdn-replica-1:" in out
    assert "tdn-router:" in out


# ------------------------------------------------------ bench gate


