"""Multi-replica data plane (ISSUE 8): load-aware router + replica
pool with session affinity.

Placement policy (p2c over blended load, staleness fallback,
rendezvous cold-pool hashing, breaker gating) is unit-tested directly
on the pool; wire behavior — spread, failover on a killed replica,
zero-downtime rolling restart, session affinity, trace propagation —
runs over real loopback gRPC hops. Fake replica engines follow the
test_batcher_pipeline convention (this jax lacks the mesh API
Engine.up needs); the Generate-path failover test uses the real
continuous scheduler on a toy LM.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from tests.test_batcher_pipeline import AsyncFakeEngine
from tpu_dist_nn.obs import start_http_server
from tpu_dist_nn.obs.exposition import parse_prometheus_text
from tpu_dist_nn.obs.registry import REGISTRY
from tpu_dist_nn.serving import (
    CircuitBreaker,
    GracefulDrain,
    GrpcClient,
    ReplicaPool,
    serve_engine,
    serve_router,
)
from tpu_dist_nn.serving.pool import ACTIVE, DRAINING
from tpu_dist_nn.serving.router import admin_routes, router_health
from tpu_dist_nn.testing import faults


def _counter_total(name: str) -> float:
    m = REGISTRY.get(name)
    if m is None:
        return 0.0
    return float(sum(child.value for _, child in m.samples()))


def _fresh_targets(*names):
    """Synthetic targets with clean breaker registry entries (tests
    share the process-global CircuitBreaker registry)."""
    for n in names:
        CircuitBreaker.evict(n)
    return names


# ------------------------------------------------------------ placement


def test_p2c_places_on_less_loaded_replica():
    a, b = _fresh_targets("p2c:a", "p2c:b")
    pool = ReplicaPool([a, b], seed=0)
    ra, rb = pool.replicas()
    # Outstanding-only load (no scrapes): p2c with two candidates
    # compares both every draw, so the less loaded one always wins.
    for _ in range(5):
        pool.begin(ra)
    picks = {pool.place().target for _ in range(20)}
    assert picks == {b}
    # Load flips, placement follows.
    for _ in range(12):
        pool.begin(rb)
    picks = {pool.place().target for _ in range(20)}
    assert picks == {a}


def test_gauge_load_is_staleness_bounded():
    a, b = _fresh_targets("stale:a", "stale:b")
    pool = ReplicaPool([a, b], seed=0, load_staleness=5.0)
    ra, rb = pool.replicas()
    now = time.monotonic()
    # Fresh gauges say A is backlogged (pending rows dominate its
    # otherwise-equal outstanding count).
    ra.pending_rows, ra.scraped_at = 500.0, now
    rb.pending_rows, rb.scraped_at = 0.0, now
    assert {pool.place().target for _ in range(20)} == {b}
    # The same gauge view gone stale is IGNORED: outstanding (now
    # higher on B) decides instead.
    ra.scraped_at = rb.scraped_at = now - 60.0
    for _ in range(3):
        pool.begin(rb)
    assert {pool.place().target for _ in range(20)} == {a}


def test_occupancy_gauge_counts_toward_load():
    a, b = _fresh_targets("occ:a", "occ:b")
    pool = ReplicaPool([a, b], seed=0, occupancy_weight=32.0)
    ra, rb = pool.replicas()
    now = time.monotonic()
    ra.pending_rows, ra.occupancy, ra.scraped_at = 0.0, 1.0, now
    rb.pending_rows, rb.occupancy, rb.scraped_at = 0.0, 0.0, now
    # A full decode slot ladder (occupancy 1.0) outweighs an idle one.
    assert {pool.place().target for _ in range(20)} == {b}


def test_session_affinity_pins_until_unplaceable():
    a, b = _fresh_targets("sess:a", "sess:b")
    pool = ReplicaPool([a, b], seed=0)
    first = pool.place(session_key="s1")
    pool.pin("s1", first.target)
    # Load the pinned replica heavily: affinity still wins (the KV
    # state lives there; p2c is for unpinned traffic).
    for _ in range(10):
        pool.begin(first)
    assert all(
        pool.place(session_key="s1").target == first.target
        for _ in range(10)
    )
    # Unpinnable (draining) -> re-placed onto the other replica.
    pool.drain(first.target)
    other = pool.place(session_key="s1")
    assert other is not None and other.target != first.target


def test_rendezvous_fallback_spreads_cold_sessions_consistently():
    targets = _fresh_targets("rdv:a", "rdv:b", "rdv:c")
    pool = ReplicaPool(targets, seed=0)
    # No gauge data, no outstanding: session first-placements use
    # rendezvous hashing — stable per session and spread across the
    # fleet (a second pool over the same targets maps identically).
    keys = [f"session-{i}" for i in range(24)]
    placed = {k: pool.place(session_key=k).target for k in keys}
    assert {placed[k] for k in keys} == set(targets), \
        "24 sessions over 3 replicas must touch every replica"
    pool2 = ReplicaPool(targets, seed=99)
    assert all(
        pool2.place(session_key=k).target == placed[k] for k in keys
    ), "rendezvous placement must not depend on pool instance or seed"


def test_open_breaker_skipped_then_probed_after_cooldown():
    a, b = _fresh_targets("brk:a", "brk:b")
    t = [0.0]
    br = CircuitBreaker.for_target(
        a, failure_threshold=1, cooldown_seconds=10.0, clock=lambda: t[0]
    )
    pool = ReplicaPool([a, b], seed=0)
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    # Open breaker: never placed.
    assert {pool.place().target for _ in range(10)} == {b}
    # Cooldown elapsed: exactly one request rides the half-open probe.
    t[0] = 11.0
    assert pool.place().target == a
    assert {pool.place().target for _ in range(5)} == {b}, \
        "only ONE probe per cooldown"
    br.record_success()
    assert pool.place(exclude={b}).target == a


def test_replica_healthy_gauge_tracks_breaker_state():
    """Regression: the gauge's contract is '0 = draining, removed, or
    breaker-open', but breakers open at request time in the router —
    only membership changes ever wrote the gauge, so a hard-down
    replica the pool had stopped placing on kept reporting healthy=1.
    The scrape tick must reconcile the gauge with the breaker."""
    from tpu_dist_nn.serving.pool import REPLICA_HEALTHY

    a, b = _fresh_targets("hgauge:a", "hgauge:b")
    t = [0.0]
    br = CircuitBreaker.for_target(
        a, failure_threshold=1, cooldown_seconds=10.0, clock=lambda: t[0]
    )
    pool = ReplicaPool([a, b], seed=0)
    assert REPLICA_HEALTHY.labels(replica=a).value == 1.0
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    # Breaker opened at request time: the gauge catches up on the
    # next scrape tick, not only on membership changes.
    pool.scrape_once()
    assert REPLICA_HEALTHY.labels(replica=a).value == 0.0
    assert REPLICA_HEALTHY.labels(replica=b).value == 1.0
    # Recovery: the half-open probe succeeds, breaker closes, the
    # next tick restores healthy=1.
    t[0] = 11.0
    assert br.allow()
    br.record_success()
    pool.scrape_once()
    assert REPLICA_HEALTHY.labels(replica=a).value == 1.0
    pool.close()
    CircuitBreaker.evict(a)
    CircuitBreaker.evict(b)


# ------------------------------------ breaker registry eviction (satellite)


def test_pool_remove_evicts_breaker_registry_for_reused_address():
    (t,) = _fresh_targets("evict:a")
    pool = ReplicaPool([t], seed=0)
    old = pool.replicas()[0].breaker
    for _ in range(old.failure_threshold):
        old.record_failure()
    assert old.state == CircuitBreaker.OPEN
    pool.remove(t)
    # The registry entry is PRUNED (the regression: it never was), so
    # a respawned server on the reused address starts closed.
    assert t not in CircuitBreaker._registry
    # ... and the tdn_breaker_state series goes with it: a departed
    # target's stale last value must not sit on /metrics forever.
    from tpu_dist_nn.serving.resilience import BREAKER_STATE
    assert (t,) not in dict(BREAKER_STATE.samples())
    fresh = CircuitBreaker.for_target(t)
    assert (t,) in dict(BREAKER_STATE.samples())  # recreated live
    assert fresh is not old and fresh.state == CircuitBreaker.CLOSED
    # undrain() after a rolling restart resets the same way.
    pool2 = ReplicaPool([t], seed=0)
    br2 = pool2.replicas()[0].breaker
    for _ in range(br2.failure_threshold):
        br2.record_failure()
    pool2.drain(t)
    assert pool2.undrain(t)
    assert pool2.replicas()[0].breaker.state == CircuitBreaker.CLOSED
    CircuitBreaker.evict(t)


def test_undrain_refuses_active_replica():
    """Regression: undrain() on a never-drained ACTIVE replica wiped a
    live breaker and its load view — a hard-down replica the breaker
    correctly opened on re-entered rotation off a typo'd admin call."""
    (t,) = _fresh_targets("undrainactive:a")
    pool = ReplicaPool([t], seed=0)
    rep = pool.replicas()[0]
    old = rep.breaker
    for _ in range(old.failure_threshold):
        old.record_failure()
    assert old.state == CircuitBreaker.OPEN
    assert not pool.undrain(t)
    assert rep.breaker is old and old.state == CircuitBreaker.OPEN
    pool.close()


def test_remove_retires_request_counter_series():
    """Membership churn retires the per-replica
    tdn_router_requests_total children too — the same unbounded
    label-growth class the gauges already handle (a long-lived process
    cycling pools over ephemeral-port replicas must not accumulate
    dead counter series forever)."""
    from tpu_dist_nn.serving.router import ROUTER_REQUESTS

    (t,) = _fresh_targets("retirereq:a")
    pool = ReplicaPool([t], seed=0)
    ROUTER_REQUESTS.labels(replica=t, outcome="ok").inc()
    ROUTER_REQUESTS.labels(replica=t, outcome="UNAVAILABLE").inc()
    pool.remove(t)
    assert not [k for k, _ in ROUTER_REQUESTS.samples() if k[0] == t]
    pool.close()


class _FakeChildProc:
    """Duck-typed stand-in for a pool-spawned subprocess handle."""

    def __init__(self):
        self.terminated = False

    def poll(self):
        return 0 if self.terminated else None

    def terminate(self):
        self.terminated = True

    def wait(self, timeout=None):
        if not self.terminated:
            raise RuntimeError("still running")
        return 0

    def kill(self):
        self.terminated = True


def test_pool_remove_terminates_spawned_child():
    """Regression: remove() popped the entry without terminating a
    pool-spawned child — the live engine kept serving on its ports
    forever, and once popped even close()'s sweep could no longer
    reach it ('pool-spawned children are OWNED by the pool')."""
    (t,) = _fresh_targets("rmspawn:a")
    pool = ReplicaPool([t], seed=0)
    fake = _FakeChildProc()
    pool.replicas()[0].proc = fake
    pool.remove(t)
    assert fake.terminated, "removed replica's child was orphaned"
    pool.close()


def test_admin_drain_not_undone_by_ready_scrape():
    """Regression: an admin-drained STATIC replica (no subprocess to
    SIGTERM) keeps answering ready on /healthz — the scrape loop must
    NOT auto-undrain it, or `--drain-replica` reverts within one
    scrape tick. Rejoin happens only after the drain was OBSERVED:
    draining:true scraped, or the replica went unreachable (restart),
    then ready again."""
    a, b = _fresh_targets("stillready:a", "stillready:b")
    state = {"draining": False, "ready": True}
    msrv = start_http_server(0, host="127.0.0.1",
                             health_fn=lambda: dict(state))
    try:
        pool = ReplicaPool([a, b],
                           [f"127.0.0.1:{msrv.port}", None], seed=0)
        assert pool.drain(a)
        # The replica never began restarting: ready scrapes must keep
        # it OUT of rotation.
        for _ in range(3):
            pool.scrape_once()
            assert pool.replicas()[0].state == DRAINING
        assert {pool.place().target for _ in range(5)} == {b}
        # ONE lost probe is a blip (GC pause, timeout on a busy but
        # still-running replica) — ready right after must NOT rejoin.
        good_port = msrv.port
        pool.replicas()[0].metrics_target = "127.0.0.1:1"  # unreachable
        pool.scrape_once()
        assert pool.replicas()[0].state == DRAINING
        pool.replicas()[0].metrics_target = f"127.0.0.1:{good_port}"
        pool.scrape_once()
        assert pool.replicas()[0].state == DRAINING, \
            "single unreachable blip must not count as drain observed"
        # Operator restarts it: a SUSTAINED down window (2+ ticks) IS
        # the restart being observed...
        pool.replicas()[0].metrics_target = "127.0.0.1:1"
        pool.scrape_once()
        pool.scrape_once()
        assert pool.replicas()[0].state == DRAINING
        # ...and the restarted server's ready scrape rejoins it.
        pool.replicas()[0].metrics_target = f"127.0.0.1:{good_port}"
        pool.scrape_once()
        assert pool.replicas()[0].state == ACTIVE
        pool.close()
    finally:
        msrv.close()
        CircuitBreaker.evict(a)


def test_fast_restart_detected_via_boot_id_change():
    """A restart faster than the scraper's timing detectors (the
    draining:true window AND the downtime both fell between ticks)
    is still observed: /healthz carries a per-process boot_id
    (GracefulDrain.wrap_health), and a DRAINING replica answering
    ready under a NEW identity IS the drain having completed. Same
    identity answering ready stays out of rotation (the operator's
    --drain-replica is not undone)."""
    a, b = _fresh_targets("bootid:a", "bootid:b")
    state = {"draining": False, "ready": True, "boot_id": "boot-1"}
    msrv = start_http_server(0, host="127.0.0.1",
                             health_fn=lambda: dict(state))
    try:
        pool = ReplicaPool([a, b],
                           [f"127.0.0.1:{msrv.port}", None], seed=0)
        pool.scrape_once()  # records boot-1 while ACTIVE
        assert pool.replicas()[0].boot_id == "boot-1"
        assert pool.drain(a)
        pool.scrape_once()  # same process, still ready: no rejoin
        assert pool.replicas()[0].state == DRAINING
        state["boot_id"] = "boot-2"  # restart between two ticks
        pool.scrape_once()
        assert pool.replicas()[0].state == ACTIVE
        pool.close()
    finally:
        msrv.close()
        CircuitBreaker.evict(a)


def test_wrap_health_carries_boot_id():
    from tpu_dist_nn.serving.resilience import BOOT_ID

    drain = GracefulDrain(grace_seconds=0.1)
    assert drain.wrap_health()()["boot_id"] == BOOT_ID
    # An engine health_fn that sets its own value wins (setdefault).
    assert drain.wrap_health(lambda: {"ready": True, "boot_id": "x"})()[
        "boot_id"] == "x"


def test_spawn_local_refuses_after_close():
    """Regression (orphan race): spawn_local on a closing pool would
    Popen a child that close()'s sweep can never see. The pre-spawn
    gate refuses outright."""
    (t,) = _fresh_targets("spawnclosed:a")
    pool = ReplicaPool([t], seed=0)
    pool.close()
    with pytest.raises(RuntimeError, match="closed"):
        pool.spawn_local("model.json")


def test_scrape_survives_garbled_healthz_body():
    """Regression: a 200 /healthz whose body is not JSON (proxy error
    page, misconfigured port) or not a dict (bare ``null``) must not
    raise out of scrape_once — it crashed pool.start() at router
    bring-up and aborted every later tick's reconcile pass fleet-wide.
    Something ANSWERED, so it is neither a drain observation nor a
    rejoin signal; the health view simply stays unknown for the tick."""
    import http.server

    body = {"value": b"<html>502 Bad Gateway</html>"}

    class Garbled(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(body["value"])))
            self.end_headers()
            self.wfile.write(body["value"])

        def log_message(self, *args):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Garbled)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    a, b = _fresh_targets("garbled:a", "garbled:b")
    try:
        pool = ReplicaPool([a, b],
                           [f"127.0.0.1:{srv.server_address[1]}", None],
                           seed=0)
        pool.scrape_once()  # must not raise
        rep = pool.replicas()[0]
        assert rep.state == ACTIVE
        body["value"] = b"null"  # valid JSON, not a dict
        pool.scrape_once()  # must not raise either
        assert rep.state == ACTIVE
        body["value"] = b"\xff\xfe<html>502</html>"  # not even UTF-8
        pool.scrape_once()  # UnicodeDecodeError must not escape
        assert rep.state == ACTIVE
        # Nor does a garbled answer observe (or undo) a drain: the
        # admin-drained replica stays out of rotation.
        assert pool.drain(a)
        pool.scrape_once()
        pool.scrape_once()
        assert rep.state == DRAINING and not rep.drain_observed
        pool.close()
    finally:
        srv.shutdown()
        CircuitBreaker.evict(a)


# ------------------------------------------------------- loopback serving


def _replica_fleet(n, dim=8, dispatch_seconds=0.002):
    """n loopback fake-engine replicas; per-row dispatch cost so one
    replica is launch-bound (the spread has something to win)."""
    engines, servers, targets = [], [], []
    for _ in range(n):
        e = AsyncFakeEngine(dim=dim, dispatch_seconds=dispatch_seconds,
                            per_row=True)
        srv, port = serve_engine(e, 0, host="127.0.0.1")
        engines.append(e)
        servers.append(srv)
        targets.append(f"127.0.0.1:{port}")
    return engines, servers, targets


def test_router_loopback_spreads_load_and_exposes_metrics():
    """The quick-tier smoke: p2c over 2 in-process replicas spreads a
    concurrent burst (both replicas serve > 25% of rows) and the
    router's /metrics exposes the tdn_router_* family."""
    engines, servers, targets = _replica_fleet(2)
    pool = ReplicaPool(targets, seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    metrics = start_http_server(0, host="127.0.0.1",
                                health_fn=router_health(pool))
    outs = {}
    lock = threading.Lock()

    def worker(i):
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=15.0, breaker=None)
        mine = [c.process(np.full((1, 8), float(i))) for _ in range(8)]
        c.close()
        with lock:
            outs[i] = mine

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    try:
        assert len(outs) == 8
        for i, mine in outs.items():
            assert len(mine) == 8
            for o in mine:
                np.testing.assert_allclose(o, np.full((1, 8), 2.0 * i))
        served = [sum(len(r) for r in e.dispatched_rows) for e in engines]
        total = sum(served)
        # >= not ==: the batcher rounds coalesced batches up to bucket
        # sizes, so dispatched rows include occasional zero-pad tails
        # (3 requests coalescing into a 4-bucket). Exactly-one-reply is
        # asserted above per worker; this counts launch-side work.
        assert total >= 64
        assert min(served) / total > 0.25, (
            f"p2c must spread the burst; got {served}"
        )
        with urllib.request.urlopen(
            f"http://127.0.0.1:{metrics.port}/metrics", timeout=5.0
        ) as r:
            parsed = parse_prometheus_text(r.read().decode())
        for t in targets:
            key = f'tdn_router_requests_total{{replica="{t}",outcome="ok"}}'
            assert parsed.get(key, 0) > 0, f"missing series {key}"
        assert parsed.get("tdn_router_placement_seconds_count", 0) >= 64
        for t in targets:
            assert parsed.get(
                f'tdn_router_replica_healthy{{replica="{t}"}}'
            ) == 1.0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{metrics.port}/healthz", timeout=5.0
        ) as r:
            health = json.loads(r.read().decode())
        assert health["ready"] and health["role"] == "router"
    finally:
        metrics.close()
        rsrv.stop(0)
        for s in servers:
            s.stop(0)
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)


def test_replica_kill_mid_burst_fails_over_without_loss():
    """Chaos: one of three replicas dies mid-burst. Every request
    completes via router failover (clients carry NO retry policy — the
    fleet absorbs the loss), tdn_router_failovers_total rises, and
    each request yields exactly one reply."""
    engines, servers, targets = _replica_fleet(3)
    pool = ReplicaPool(targets, seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    failovers0 = _counter_total("tdn_router_failovers_total")
    outs = {}
    errs = []
    lock = threading.Lock()
    started = threading.Event()

    def worker(i):
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=30.0,
                       retry=None, breaker=None)
        mine = []
        try:
            for k in range(10):
                mine.append(c.process(np.full((1, 8), float(i * 100 + k))))
                started.set()
        except Exception as e:  # noqa: BLE001 — the test inspects it
            with lock:
                errs.append(f"{type(e).__name__}: {e}"[:200])
        finally:
            c.close()
            with lock:
                outs[i] = mine

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    assert started.wait(15.0), "burst never started"
    servers[0].stop(None)  # hard kill, no grace: in-flight RPCs die too
    for t in threads:
        t.join(60)
    try:
        assert not errs, errs[:3]
        assert len(outs) == 6
        for i, mine in outs.items():
            # Exactly one reply per request, each bit-correct — a
            # failover can recompute, but must never double-deliver.
            assert len(mine) == 10
            for k, o in enumerate(mine):
                np.testing.assert_allclose(
                    o, np.full((1, 8), 2.0 * (i * 100 + k))
                )
        assert _counter_total("tdn_router_failovers_total") > failovers0, \
            "the kill must be visible as failovers"
    finally:
        rsrv.stop(0)
        for s in servers[1:]:
            s.stop(0)
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)


def test_rolling_restart_zero_dropped_requests():
    """The zero-downtime choreography over a live burst: each replica
    in turn is drained (stop placing -> outstanding hits zero ->
    server restarted on the SAME address -> re-admitted with a fresh
    breaker). No request is dropped or duplicated across the full
    cycle."""
    engines, servers, targets = _replica_fleet(3, dispatch_seconds=0.001)
    pool = ReplicaPool(targets, seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    stop = threading.Event()
    counts = {}
    errs = []
    lock = threading.Lock()

    def worker(i):
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=30.0,
                       retry=None, breaker=None)
        n = 0
        try:
            while not stop.is_set():
                out = c.process(np.full((1, 8), float(i)))
                np.testing.assert_allclose(out, np.full((1, 8), 2.0 * i))
                n += 1
        except Exception as e:  # noqa: BLE001 — zero tolerated
            with lock:
                errs.append(f"{type(e).__name__}: {e}"[:200])
        finally:
            c.close()
            with lock:
                counts[i] = n

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    try:
        for idx, target in enumerate(targets):
            host, port = target.rsplit(":", 1)
            assert pool.drain(target)
            assert pool.wait_drained(target, timeout=20.0), \
                f"{target} never quiesced"
            servers[idx].stop(grace=5.0).wait(10.0)
            # Restart on the REUSED address (grpc sets SO_REUSEADDR);
            # a fresh engine models the restarted process.
            engines[idx] = AsyncFakeEngine(dim=8, dispatch_seconds=0.001,
                                           per_row=True)
            servers[idx], bound = serve_engine(
                engines[idx], int(port), host=host
            )
            assert bound == int(port)
            assert pool.undrain(target)
            time.sleep(0.05)  # let the burst exercise the rejoined replica
        time.sleep(0.1)
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        rsrv.stop(0)
        for s in servers:
            s.stop(0)
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)
    assert not errs, errs[:3]
    assert all(n > 0 for n in counts.values())
    # Every restarted replica rejoined and served part of the burst.
    for e in engines:
        assert len(e.dispatched_rows) > 0, \
            "a restarted replica never received traffic after rejoin"


def test_healthz_scrape_drives_drain_and_rejoin():
    """The scrape half of the choreography: a replica whose /healthz
    reports draining:true stops receiving placements with NO admin
    call (the operator SIGTERMed it directly); when the restarted
    server answers ready again, the pool re-admits it with a fresh
    breaker."""
    a, b = _fresh_targets("scrape:a", "scrape:b")
    state = {"draining": False, "ready": True}

    def health():
        return dict(state)

    msrv = start_http_server(0, host="127.0.0.1", health_fn=health)
    try:
        pool = ReplicaPool(
            [a, b], [f"127.0.0.1:{msrv.port}", None], seed=0
        )
        pool.scrape_once()
        assert pool.replicas()[0].state == ACTIVE
        # SIGTERM landed on the replica: its own GracefulDrain flips
        # /healthz (wrap_health semantics: ready False, draining True).
        state.update(draining=True, ready=False)
        pool.scrape_once()
        rep = pool.replicas()[0]
        assert rep.state == DRAINING and rep.reported_draining
        assert {pool.place().target for _ in range(5)} == {b}
        # Trip the breaker while down; the restart must not inherit it.
        old = rep.breaker
        for _ in range(old.failure_threshold):
            old.record_failure()
        state.update(draining=False, ready=True)
        pool.scrape_once()
        rep = pool.replicas()[0]
        assert rep.state == ACTIVE
        assert rep.breaker.state == CircuitBreaker.CLOSED
        assert rep.breaker is not old
        pool.close()
    finally:
        msrv.close()
        CircuitBreaker.evict(a)


def test_drain_not_reverted_by_metrics_scrape_blip():
    """Regression: one blown /metrics fetch on an admin-drained STATIC
    replica set drain_observed (the 'unreachable = process exited'
    heuristic fired on a single endpoint failure), so the very next
    ready scrape auto-undrained the replica the operator just drained.
    /healthz reachability is the arbiter of 'exited'."""
    import http.server

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.startswith("/healthz"):
                body = b'{"ready": true, "draining": false}'
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_error(500)  # the metrics fetch blows up

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    (a,) = _fresh_targets("blip:a")
    pool = ReplicaPool([a], [f"127.0.0.1:{srv.server_address[1]}"],
                       seed=0)
    try:
        assert pool.drain(a)
        rep = pool.replicas()[0]
        pool.scrape_once()  # metrics 500s, healthz answers ready
        assert not rep.drain_observed, \
            "a metrics blip is not a drain observation"
        assert rep.state == DRAINING, \
            "admin drain must survive a metrics scrape blip"
        pool.scrape_once()  # nor does a second ready scrape undrain
        assert rep.state == DRAINING
    finally:
        srv.shutdown()
        pool.close()
        CircuitBreaker.evict(a)


def test_failover_tries_every_placeable_replica_before_abort():
    """Regression: the attempt cap was the client-oriented
    policy.max_attempts=3 regardless of fleet size — on a pool where
    3 replicas died together (breakers still closed, and dead-fast
    failures keep their outstanding at 0 so p2c PREFERS them) a
    request aborted UNAVAILABLE with healthy replicas never tried.
    Every replica in the request's view gets at least one shot."""
    import grpc

    from tpu_dist_nn.serving.router import Router

    targets = _fresh_targets("fleet:d1", "fleet:d2", "fleet:d3",
                             "fleet:ok")
    pool = ReplicaPool(list(targets), seed=0)
    healthy = "fleet:ok"

    class _Unavail(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.UNAVAILABLE

        def details(self):
            return "replica down"

    calls = []

    def make_call(r):
        def call(method, payload, *, timeout=None, metadata=()):
            calls.append(r.target)
            if r.target != healthy:
                raise _Unavail()
            return b"reply"

        return call

    for rep in pool.replicas():
        rep.call = make_call(rep)
        if rep.target == healthy:
            # p2c must prefer the dead replicas: the healthy one looks
            # maximally loaded, the dead ones fail fast at 0.
            rep.outstanding = 1000

    class Ctx:
        def invocation_metadata(self):
            return ()

        def time_remaining(self):
            return None

        def set_trailing_metadata(self, md):
            pass

        def abort(self, code, msg):
            raise AssertionError(f"aborted {code}: {msg}")

    router = Router(pool)
    assert router.handle("Process", b"req", Ctx()) == b"reply"
    assert calls[-1] == healthy
    assert len(set(calls[:-1])) == 3, "all three dead replicas tried"
    pool.close()
    for t in targets:
        CircuitBreaker.evict(t)


# -------------------------------------------- session affinity on the wire


def test_generate_failover_and_session_affinity_over_wire():
    """Generate over the router: a replica answering UNAVAILABLE to
    everything (fault interceptor) is transparently failed over; the
    greedy tokens match the single-server reference exactly, each
    request yields ONE output, and the session key pins follow-ups to
    the surviving replica."""
    import jax

    from tpu_dist_nn.models.generate import generate
    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.serving import SESSION_HEADER, serve_lm_generate

    assert SESSION_HEADER == "x-tdn-session"
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(3), cfg)
    prompts = (np.arange(8, dtype=np.int64)[None, :] % 7)
    ref = np.asarray(generate(params, cfg, prompts, 6))

    # Replica A rejects EVERY request; replica B serves.
    plan = faults.FaultPlan(every=1, fault=faults.unavailable())
    srv_a, port_a = serve_lm_generate(
        params, cfg, 0, max_new_tokens=6, prompt_len=8, host="127.0.0.1",
        gen_slots=2, warm_rows=1,
        interceptors=(faults.FaultInterceptor(plan),),
    )
    srv_b, port_b = serve_lm_generate(
        params, cfg, 0, max_new_tokens=6, prompt_len=8, host="127.0.0.1",
        gen_slots=2, warm_rows=1,
    )
    ta, tb = f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"
    CircuitBreaker.evict(ta)
    CircuitBreaker.evict(tb)
    pool = ReplicaPool([ta, tb], seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    failovers0 = _counter_total("tdn_router_failovers_total")
    try:
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=30.0, retry=None,
                       breaker=None, session_key="chat-42")
        outs = [c.generate(prompts) for _ in range(3)]
        c.close()
        assert len(outs) == 3
        for out in outs:
            np.testing.assert_array_equal(out[:, 8:], ref)
        # The session ended up pinned to the replica that actually
        # served it — follow-ups skip the faulty replica entirely.
        assert pool.pinned("chat-42") == tb
        if plan.fired:
            assert _counter_total("tdn_router_failovers_total") > failovers0
    finally:
        rsrv.stop(0)
        srv_a.stop(0)
        srv_b.stop(0)
        pool.close()
        CircuitBreaker.evict(ta)
        CircuitBreaker.evict(tb)


def test_same_replica_retry_is_not_a_failover():
    """Regression: tdn_router_failovers_total means 're-placed onto
    ANOTHER replica'. A single-replica pool retrying the same replica
    after a transient fault (and succeeding) must not count."""
    e = AsyncFakeEngine(dim=8, dispatch_seconds=0.0, per_row=True)
    plan = faults.FaultPlan(at={1: faults.unavailable()})
    srv, port = serve_engine(
        e, 0, host="127.0.0.1",
        interceptors=(faults.FaultInterceptor(plan),),
    )
    (t,) = _fresh_targets(f"127.0.0.1:{port}")
    pool = ReplicaPool([t], seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    failovers0 = _counter_total("tdn_router_failovers_total")
    try:
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=15.0, retry=None,
                       breaker=None)
        out = c.process(np.full((1, 8), 3.0))
        c.close()
        np.testing.assert_allclose(out, np.full((1, 8), 6.0))
        assert plan.fired == 1, "the injected fault must have fired"
        assert _counter_total("tdn_router_failovers_total") == failovers0, \
            "a same-replica retry is not a failover"
    finally:
        rsrv.stop(0)
        srv.stop(0)
        pool.close()
        CircuitBreaker.evict(t)


def test_backoff_paces_same_replica_retries_despite_draining_peer():
    """Regression: retry_same_set was computed over ALL registered
    targets, so any unplaceable (draining / breaker-open) replica in
    the pool suppressed the jittered backoff forever and the router
    hammered the one struggling replica back-to-back with zero delay.
    The set must be built from PLACEABLE replicas."""
    from tpu_dist_nn.serving.resilience import RetryPolicy

    e = AsyncFakeEngine(dim=8, dispatch_seconds=0.0, per_row=True)
    plan = faults.FaultPlan(every=1, fault=faults.unavailable())
    srv, port = serve_engine(
        e, 0, host="127.0.0.1",
        interceptors=(faults.FaultInterceptor(plan),),
    )
    a, b = _fresh_targets(f"127.0.0.1:{port}", "backoff:drained")
    pool = ReplicaPool([a, b], seed=0)
    pool.drain(b)  # unplaceable peer that place() will never return
    sleeps = []
    policy = RetryPolicy(max_attempts=3, base_delay=0.001,
                         max_delay=0.002, seed=7,
                         sleep=lambda s: sleeps.append(s))
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1", retry=policy)
    try:
        import grpc as _grpc

        c = GrpcClient(f"127.0.0.1:{rport}", timeout=10.0,
                       retry=None, breaker=None)
        with pytest.raises(_grpc.RpcError) as err:
            c.process(np.full((1, 8), 3.0))
        c.close()
        assert err.value.code() == _grpc.StatusCode.UNAVAILABLE
        assert plan.fired == 3, "all attempts must have hit replica a"
        assert sleeps, (
            "same-replica retries must be paced by the backoff even "
            "while a draining replica is registered"
        )
    finally:
        rsrv.stop(0)
        srv.stop(0)
        pool.close()
        CircuitBreaker.evict(a)
        CircuitBreaker.evict(b)


def test_router_propagates_deterministic_status_without_failover():
    """INVALID_ARGUMENT is the replica's verdict, not a replica
    failure: the router propagates it verbatim and does NOT fail over
    (another replica would say the same thing)."""
    import grpc as _grpc

    engines, servers, targets = _replica_fleet(2)
    pool = ReplicaPool(targets, seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    failovers0 = _counter_total("tdn_router_failovers_total")
    try:
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=10.0,
                       retry=None, breaker=None)
        with pytest.raises(_grpc.RpcError) as e:
            c.process(np.zeros((1, 5)))  # wrong width for dim=8
        assert e.value.code() == _grpc.StatusCode.INVALID_ARGUMENT
        assert "(N, 8)" in (e.value.details() or "")
        c.close()
        assert _counter_total("tdn_router_failovers_total") == failovers0
        # Reachability evidence: the verdict must not have opened the
        # breaker of the replica that answered.
        assert all(
            r.breaker.state == CircuitBreaker.CLOSED
            for r in pool.replicas()
        )
    finally:
        rsrv.stop(0)
        for s in servers:
            s.stop(0)
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)


def test_router_trace_propagation_names_router_stages():
    """The router hop joins the caller's trace: one trace id spans
    client -> router (router.forward) -> replica handler, so /profile
    attributes router time as its own stage."""
    from tpu_dist_nn.obs.trace import TRACER

    engines, servers, targets = _replica_fleet(1)
    pool = ReplicaPool(targets, seed=0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    try:
        c = GrpcClient(f"127.0.0.1:{rport}", timeout=10.0, breaker=None)
        c.process(np.ones((1, 8)))
        c.close()
        doc = json.loads(TRACER.render_json(None))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert "router.forward" in by_name
        fwd = by_name["router.forward"][-1]
        trace_id = fwd["args"]["trace_id"]
        names_in_trace = {
            s["name"] for s in spans
            if s["args"].get("trace_id") == trace_id
        }
        # Client span, router root + forward, and the replica's own
        # handler tree all share ONE trace id.
        assert {"client.Process", "rpc.Process",
                "router.forward"} <= names_in_trace
        assert fwd["args"]["replica"] == targets[0]
    finally:
        rsrv.stop(0)
        for s in servers:
            s.stop(0)
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)


# ------------------------------------------------------- admin + aggregate


def test_admin_routes_drain_undrain_and_cli_client(capsys):
    engines, servers, targets = _replica_fleet(2)
    pool = ReplicaPool(targets, seed=0)
    from tpu_dist_nn.serving.router import admin_post_routes

    msrv = start_http_server(
        0, host="127.0.0.1", health_fn=router_health(pool),
        routes=admin_routes(pool),
        post_routes=admin_post_routes(pool),
    )
    try:
        from tpu_dist_nn.cli import main

        admin = f"127.0.0.1:{msrv.port}"
        rc = main(["router", "--admin", admin, "--list-replicas"])
        assert rc == 0
        snap = json.loads(capsys.readouterr().out.strip())
        assert {s["target"] for s in snap} == set(targets)
        rc = main(["router", "--admin", admin,
                   "--drain-replica", targets[0]])
        assert rc == 0
        assert json.loads(capsys.readouterr().out.strip())["draining"]
        assert pool.replicas()[0].state == DRAINING
        assert {pool.place().target for _ in range(5)} == {targets[1]}
        rc = main(["router", "--admin", admin,
                   "--undrain-replica", targets[0]])
        assert rc == 0
        assert json.loads(capsys.readouterr().out.strip())["active"]
        assert pool.replicas()[0].state == ACTIVE
        # Unknown replica: a clean 404-shaped error, not a traceback —
        # and the route's JSON verdict surfaces in the message instead
        # of a generic "could not fetch" (the operator must be able to
        # tell a typo'd replica name from a down router).
        rc = main(["router", "--admin", admin,
                   "--drain-replica", "nope:1"])
        assert rc == 2
        err = capsys.readouterr().err
        assert "HTTP 404" in err and '"draining": false' in err
    finally:
        msrv.close()
        for s in servers:
            s.stop(0)
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)


def test_aggregate_fleet_sums_counters_keeps_gauges_per_source():
    from tpu_dist_nn.cli import _aggregate_fleet

    router = {
        "__type__:tdn_router_requests_total": "counter",
        'tdn_router_requests_total{replica="a",outcome="ok"}': 5.0,
        "__type__:tdn_host_rss_bytes": "gauge",
        "tdn_host_rss_bytes": 100.0,
    }
    rep_a = {
        "__type__:tdn_rpc_requests_total": "counter",
        'tdn_rpc_requests_total{method="Process"}': 5.0,
        "__type__:tdn_host_rss_bytes": "gauge",
        "tdn_host_rss_bytes": 200.0,
        "__type__:tdn_batch_wait_seconds": "histogram",
        'tdn_batch_wait_seconds_count{method="Process"}': 5.0,
    }
    rep_b = {
        "__type__:tdn_rpc_requests_total": "counter",
        'tdn_rpc_requests_total{method="Process"}': 7.0,
        "__type__:tdn_host_rss_bytes": "gauge",
        "tdn_host_rss_bytes": 300.0,
        "__type__:tdn_batch_wait_seconds": "histogram",
        'tdn_batch_wait_seconds_count{method="Process"}': 7.0,
    }
    agg = _aggregate_fleet({"router": router, "a": rep_a, "b": rep_b})
    assert agg["summed"][
        'tdn_rpc_requests_total{method="Process"}'
    ] == 12.0
    assert agg["summed"][
        'tdn_batch_wait_seconds_count{method="Process"}'
    ] == 12.0
    assert agg["gauges"]["tdn_host_rss_bytes"] == {
        "router": 100.0, "a": 200.0, "b": 300.0,
    }


def test_cli_metrics_aggregate_scrapes_router_and_replicas(capsys):
    """`tdn metrics --target <router> --aggregate`: fleet discovery via
    /router/replicas, one command for router + every replica. Replica
    endpoints use private registries so the summed counters are real
    per-process series, not the shared test-process registry twice."""
    from tpu_dist_nn.cli import main
    from tpu_dist_nn.obs.registry import Registry

    regs = [Registry(), Registry()]
    for i, reg in enumerate(regs):
        reg.counter(
            "tdn_rpc_requests_total", "rpcs", labels=("method",)
        ).labels(method="Process").inc(10 * (i + 1))
        reg.gauge("tdn_batcher_queue_depth", "depth",
                  labels=("method",)).labels(method="Process").set(i + 1)
    rep_srvs = [
        start_http_server(0, host="127.0.0.1", registry=reg)
        for reg in regs
    ]
    a, b = _fresh_targets("agg:a", "agg:b")
    pool = ReplicaPool(
        [a, b],
        [f"127.0.0.1:{s.port}" for s in rep_srvs],
    )
    # Private registry for the router endpoint too: the global test-
    # process registry carries series from every other test.
    router_srv = start_http_server(
        0, host="127.0.0.1", registry=Registry(),
        routes=admin_routes(pool),
    )
    try:
        rc = main(["metrics", "--target",
                   f"127.0.0.1:{router_srv.port}", "--aggregate"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "router + 2 replica" in out
        assert '[sum] tdn_rpc_requests_total{method="Process"} = 30' in out
        assert ('[gauge] tdn_batcher_queue_depth{method="Process"} '
                f'@{a} = 1') in out
        assert ('[gauge] tdn_batcher_queue_depth{method="Process"} '
                f'@{b} = 2') in out
    finally:
        router_srv.close()
        for s in rep_srvs:
            s.close()
        pool.close()
        CircuitBreaker.evict(a)
        CircuitBreaker.evict(b)


# ------------------------------------------------------------ sampler + CLI


def test_runtime_sampler_publishes_pool_gauges():
    from tpu_dist_nn.obs import RuntimeSampler
    from tpu_dist_nn.obs.registry import Registry
    from tpu_dist_nn.serving.pool import REPLICA_HEALTHY

    a, b = _fresh_targets("smp:a", "smp:b")
    pool = ReplicaPool([a, b], seed=0)
    ra, _rb = pool.replicas()
    pool.begin(ra)
    ra.pending_rows = 17.0
    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    sampler.add_pool(pool)
    sampler.sample_once()
    out = reg.get("tdn_router_replica_outstanding")
    assert out.labels(replica=a).value == 1.0
    assert out.labels(replica=b).value == 0.0
    pend = reg.get("tdn_router_replica_pending_rows")
    assert pend.labels(replica=a).value == 17.0
    # Membership churn retires the dead series (regression: the
    # outstanding=1 phantom survived remove() at its last value
    # forever, and the label set grew unboundedly).
    pool.remove(a)
    sampler.sample_once()
    assert (a,) not in dict(out.samples())
    assert (a,) not in dict(pend.samples())
    assert (a,) not in dict(REPLICA_HEALTHY.samples())
    assert out.labels(replica=b).value == 0.0
    pool.close()
    CircuitBreaker.evict(a)
    CircuitBreaker.evict(b)


def test_scrape_once_fans_out_not_serial():
    """Regression: replicas were scraped serially, so a few wedged
    hosts (each costing up to 2x scrape_timeout of blocked HTTP) aged
    every HEALTHY replica's gauges past the staleness bound — p2c
    silently degraded fleet-wide. One tick must cost max(replica),
    not sum(replica)."""
    a, b, c = _fresh_targets("fan:a", "fan:b", "fan:c")
    pool = ReplicaPool([a, b, c], seed=0)
    seen = []

    def slow_scrape(rep):
        seen.append(rep.target)
        time.sleep(0.2)

    pool._scrape_one = slow_scrape
    t0 = time.monotonic()
    pool.scrape_once()
    dt = time.monotonic() - t0
    assert sorted(seen) == sorted([a, b, c])
    assert dt < 0.45, f"serial scrape: 3 x 0.2s took {dt:.2f}s"
    pool.close()
    for t in (a, b, c):
        CircuitBreaker.evict(t)


def test_cli_router_rejects_duplicate_replicas(capsys):
    """Regression: ReplicaPool.add() dedups on target, so a duplicate
    in --replicas silently ran the fleet at N-1 AND shifted every
    later --replica-metrics endpoint onto the wrong replica — the
    silent-misconfiguration class the parallel-list check fails
    loudly."""
    from tpu_dist_nn.cli import main

    rc = main(["router", "--replicas", "r:1,r:1,r:2",
               "--replica-metrics", "m:1,m:2,m:3"])
    assert rc == 2
    assert "duplicate" in capsys.readouterr().err


def test_cli_help_lists_router_and_session_flags(capsys):
    from tpu_dist_nn.cli import main

    with pytest.raises(SystemExit) as e:
        main(["router", "--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    for flag in ("--replicas", "--spawn", "--drain-replica",
                 "--scrape-interval", "--load-staleness"):
        assert flag in out
    with pytest.raises(SystemExit) as e:
        main(["infer", "--help"])
    assert e.value.code == 0
    assert "--session-key" in capsys.readouterr().out
    with pytest.raises(SystemExit) as e:
        main(["metrics", "--help"])
    assert e.value.code == 0
    assert "--aggregate" in capsys.readouterr().out
    # Serve mode without replicas is a clean user error.
    assert main(["router"]) == 2
    assert main(["router", "--spawn", "2"]) == 2
    assert main(["router", "--drain-replica", "x"]) == 2  # no --admin
    # --replica-metrics must be parallel to --replicas (a count
    # mismatch would silently leave tail replicas unscraped).
    assert main(["router", "--replicas", "a:1,b:2",
                 "--replica-metrics", "m:1"]) == 2


def _stub_spawn(monkeypatch, platforms):
    """Stub the subprocess half of spawn_local: each Popen records its
    argv, and the child 'reports' the next platform and fresh ports."""
    import subprocess

    import tpu_dist_nn.serving.pool as pool_mod

    argvs = []

    class FakeProc:
        stdout = None

        def __init__(self, argv, **kw):
            argvs.append(argv)

        def poll(self):
            return None

        def terminate(self):
            pass

        def wait(self, timeout=None):
            return 0

    reports = iter(
        {"grpc_port": 5101 + i, "metrics_port": 9100 + i, "platform": p}
        for i, p in enumerate(platforms)
    )

    def read(proc, timeout):
        report = next(reports)
        if report["platform"] is None:  # died before its ready line
            raise RuntimeError("child exited before printing its ports")
        return report

    monkeypatch.setattr(subprocess, "Popen", FakeProc)
    monkeypatch.setattr(pool_mod, "_read_child_ports", read)
    return argvs


def test_spawn_argv_shape(monkeypatch):
    """The subprocess command `--spawn` launches (the slow end-to-end
    spawn itself is exercised operationally, not in tier-1)."""
    import sys as _sys

    argvs = _stub_spawn(monkeypatch, ["cpu"])
    pool = ReplicaPool([], seed=0)
    # spawn_local builds `python -m tpu_dist_nn.cli --platform P up
    # --config ... --grpc-port 0 --metrics-port 0`.
    rep = pool.spawn_local("model.json", platform="cpu",
                           extra_args=["--serve-warm-rows", "8"])
    (argv,) = argvs
    assert argv[0] == _sys.executable
    assert argv[1:6] == ["-m", "tpu_dist_nn.cli", "--platform", "cpu", "up"]
    assert "--config" in argv and "model.json" in argv
    assert "--grpc-port" in argv and "--metrics-port" in argv
    assert rep.target == "127.0.0.1:5101"
    assert rep.metrics_target == "127.0.0.1:9100"
    # The respawn argv reuses the now-known ports (reused address) and
    # keeps the platform.
    assert "5101" in rep.spawn_argv and "9100" in rep.spawn_argv
    assert rep.spawn_argv[3:5] == ["--platform", "cpu"]
    pool.close()
    CircuitBreaker.evict(rep.target)


@pytest.mark.parametrize("platforms,refused", [
    (["cpu", "cpu", "cpu"], None),   # host replicas: as many as asked
    (["tpu"], None),                 # one child may own the chips
    (["tpu", "tpu"], 1),             # a second would open them again
    (["cpu", "tpu"], 1),
    (["tpu", None], 1),              # ... or dies on the taken chips
])
def test_spawn_refuses_second_replica_on_an_accelerator(
        monkeypatch, platforms, refused):
    """Spawned replicas are not assigned chips, and a chip belongs to
    one process: a child that reports an accelerator next to another
    spawned child is terminated and the spawn fails, saying why."""
    _stub_spawn(monkeypatch, platforms)
    pool = ReplicaPool([], seed=0)
    try:
        for i in range(len(platforms)):
            if i == refused:
                with pytest.raises(RuntimeError,
                                   match="one process at a time"):
                    pool.spawn_local("model.json", platform="tpu")
            else:
                pool.spawn_local("model.json", platform="tpu")
        assert len(pool.replicas()) == len(platforms) - (refused is not None)
    finally:
        targets = [r.target for r in pool.replicas()]
        pool.close()
        for t in targets:
            CircuitBreaker.evict(t)


def test_scrape_respawns_exited_spawned_replica():
    """Regression: admin-draining a POOL-SPAWNED replica SIGTERMed the
    child but nothing ever respawned it — the fleet ran at N-1 forever.
    The scrape loop must respawn an exited spawned replica on the same
    address so the ready scrape rejoins it (the other half of the
    rolling restart `--drain-replica` promises)."""
    import subprocess
    import sys as _sys

    import tpu_dist_nn.serving.pool as pool_mod

    (t,) = _fresh_targets("respawn:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()

    class ExitedProc:
        def poll(self):
            return 0  # the drained child has exited

    spawned = []

    class FakeProc:
        def __init__(self, argv, **kw):
            spawned.append(argv)

        def poll(self):
            return None

        def terminate(self):
            pass

    rep.proc = ExitedProc()
    rep.spawn_argv = [_sys.executable, "-m", "tpu_dist_nn.cli", "up",
                      "--config", "m.json", "--grpc-port", "5101",
                      "--metrics-port", "9100"]
    pool.drain(t, signal_process=False)
    real_popen = subprocess.Popen
    real_reader = pool_mod._read_child_ports
    subprocess.Popen = FakeProc
    proc_at_port_wait = []

    def fake_reader(proc, timeout):
        # The child must already be on rep.proc while the port wait is
        # in flight: router shutdown mid-boot terminates rep.proc, and
        # a child parked in a local only there would be orphaned
        # holding the reused ports.
        proc_at_port_wait.append(rep.proc)
        return {"grpc_port": 5101, "metrics_port": 9100}

    pool_mod._read_child_ports = fake_reader
    try:
        pool.scrape_once()
        # The respawn runs on its own thread (a minutes-long engine
        # boot must not freeze scraping for the other replicas) —
        # wait for it before un-monkeypatching.
        deadline = time.monotonic() + 5.0
        while rep.respawning and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        subprocess.Popen = real_popen
        pool_mod._read_child_ports = real_reader
    assert spawned == [rep.spawn_argv], "the exited child must respawn"
    assert isinstance(rep.proc, FakeProc)
    assert [type(p) for p in proc_at_port_wait] == [FakeProc], \
        "rep.proc must carry the booting child BEFORE the port wait"
    assert rep.drain_observed, "the exit IS the drain being observed"
    assert not rep.respawning
    assert rep.state == DRAINING  # rejoin waits for the ready scrape
    # A second scrape must not double-spawn the now-running child.
    pool.scrape_once()
    assert len(spawned) == 1
    pool.close()
    CircuitBreaker.evict(t)


def test_scrape_respawns_crashed_active_replica():
    """Regression: auto-respawn was gated on state == DRAINING, so a
    spawned child that CRASHED (OOM/segfault — still ACTIVE when
    poll() returned) was never respawned: the dead target kept being
    placed until its breaker opened, then the fleet sat at N-1
    forever. A crash routes through the same drain-rejoin
    choreography as a rolling restart."""
    import subprocess
    import sys as _sys

    import tpu_dist_nn.serving.pool as pool_mod

    (t,) = _fresh_targets("crash:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()

    class CrashedProc:
        def poll(self):
            return -11  # SIGSEGV, no drain ran

    spawned = []

    class FakeProc:
        def __init__(self, argv, **kw):
            spawned.append(argv)

        def poll(self):
            return None

    rep.proc = CrashedProc()
    rep.spawn_argv = [_sys.executable, "-m", "tpu_dist_nn.cli", "up",
                      "--config", "m.json", "--grpc-port", "5103",
                      "--metrics-port", "9103"]
    assert rep.state == ACTIVE
    real_popen = subprocess.Popen
    real_reader = pool_mod._read_child_ports
    subprocess.Popen = FakeProc
    pool_mod._read_child_ports = lambda proc, timeout: {
        "grpc_port": 5103, "metrics_port": 9103,
    }
    try:
        pool.scrape_once()
        deadline = time.monotonic() + 5.0
        while rep.respawning and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        subprocess.Popen = real_popen
        pool_mod._read_child_ports = real_reader
    assert spawned == [rep.spawn_argv], "a crashed child must respawn"
    assert isinstance(rep.proc, FakeProc)
    # Placement stops until the restarted server's ready scrape
    # rejoins it (fresh breaker) — same choreography as a drain.
    assert rep.state == DRAINING and rep.drain_observed
    assert pool.place() is None
    pool.close()
    CircuitBreaker.evict(t)


def test_failed_respawn_backs_off():
    """A crash-looping child (bad config, stolen port) must not become
    a hot spawn loop: a FAILED respawn pauses further attempts for a
    backoff window."""
    import subprocess
    import sys as _sys

    import tpu_dist_nn.serving.pool as pool_mod

    (t,) = _fresh_targets("crashloop:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()

    spawned = []

    class DeadProc:
        def __init__(self, argv=None, **kw):
            if argv is not None:
                spawned.append(argv)

        def poll(self):
            return 1  # exits immediately, never prints ports

    def failing_reader(proc, timeout):
        raise RuntimeError("child exited before printing its ports")

    rep.proc = DeadProc()
    rep.spawn_argv = [_sys.executable, "-m", "tpu_dist_nn.cli", "up",
                      "--config", "bad.json"]
    pool.drain(t, signal_process=False)
    real_popen = subprocess.Popen
    real_reader = pool_mod._read_child_ports
    subprocess.Popen = DeadProc
    pool_mod._read_child_ports = failing_reader
    try:
        pool.scrape_once()
        deadline = time.monotonic() + 5.0
        while rep.respawning and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(spawned) == 1
        assert rep.respawn_backoff_until > time.monotonic()
        # Within the backoff window: no second spawn attempt.
        pool.scrape_once()
        time.sleep(0.05)
        assert len(spawned) == 1, "failed respawn must back off"
    finally:
        subprocess.Popen = real_popen
        pool_mod._read_child_ports = real_reader
    pool.close()
    CircuitBreaker.evict(t)


def test_respawn_aborts_when_pool_stopping():
    """A respawn thread still in its pre-spawn window when the pool
    shuts down must NOT spawn: the child would be born after cleanup
    already terminated rep.proc (the OLD exited process) and be
    orphaned holding the reused ports."""
    import subprocess
    import sys as _sys

    import tpu_dist_nn.serving.pool as pool_mod

    (t,) = _fresh_targets("stopspawn:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()

    class ExitedProc:
        def poll(self):
            return 0

    spawned = []

    class FakeProc:
        def __init__(self, argv, **kw):
            spawned.append(argv)

        def poll(self):
            return None

    rep.proc = ExitedProc()
    rep.spawn_argv = [_sys.executable, "-m", "tpu_dist_nn.cli", "up",
                      "--config", "m.json"]
    pool.drain(t, signal_process=False)
    pool._stop.set()  # shutdown began
    real_popen = subprocess.Popen
    real_reader = pool_mod._read_child_ports
    subprocess.Popen = FakeProc
    pool_mod._read_child_ports = lambda proc, timeout: {
        "grpc_port": 1, "metrics_port": 2,
    }
    try:
        pool.scrape_once()
        deadline = time.monotonic() + 5.0
        while rep.respawning and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        subprocess.Popen = real_popen
        pool_mod._read_child_ports = real_reader
    assert spawned == [], "no child may spawn once shutdown began"
    assert not rep.respawning
    pool.close()
    CircuitBreaker.evict(t)


def test_pool_close_releases_process_global_state():
    """Regression: close() left the per-target process-global claims
    behind — breaker registry entries, tdn_breaker_state and
    tdn_router_replica_healthy series — so a process cycling pools
    over ephemeral-port replicas (bench, tests) accumulated dead
    series forever, and a later pool on a reused address inherited
    the dead incumbent's breaker history."""
    from tpu_dist_nn.serving.pool import REPLICA_HEALTHY
    from tpu_dist_nn.serving.resilience import BREAKER_STATE

    a, b = _fresh_targets("closeg:a", "closeg:b")
    pool = ReplicaPool([a, b], seed=0)
    for _ in range(pool.replicas()[0].breaker.failure_threshold):
        pool.replicas()[0].breaker.record_failure()
    assert (a,) in dict(REPLICA_HEALTHY.samples())
    assert a in CircuitBreaker._registry

    # A pool-spawned child is OWNED by the pool: close() must reap it
    # (library callers don't get cmd_router's CLI cleanup).
    class LiveProc:
        def __init__(self):
            self.terminated = False

        def poll(self):
            return 0 if self.terminated else None

        def terminate(self):
            self.terminated = True

        def wait(self, timeout=None):
            return 0

    child = LiveProc()
    pool.replicas()[0].proc = child
    pool.close()
    assert child.terminated, "close() must reap pool-spawned children"
    for t in (a, b):
        assert (t,) not in dict(REPLICA_HEALTHY.samples())
        assert (t,) not in dict(BREAKER_STATE.samples())
        assert t not in CircuitBreaker._registry
    # A new pool on the reused address starts with a CLOSED breaker.
    pool2 = ReplicaPool([a], seed=0)
    assert pool2.replicas()[0].breaker.state == CircuitBreaker.CLOSED
    pool2.close()


def test_restart_replica_parks_child_before_port_wait():
    """Regression: restart_replica assigned rep.proc only AFTER the
    up-to-180s port wait — router shutdown mid-boot terminated the OLD
    exited process handle and orphaned the new child on the reused
    ports (the same bug fixed in the scrape loop's auto-respawn)."""
    import subprocess
    import sys as _sys

    import tpu_dist_nn.serving.pool as pool_mod

    (t,) = _fresh_targets("restartpark:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()

    class OldProc:
        def poll(self):
            return 0

        def wait(self, timeout=None):
            return 0

        def terminate(self):
            pass

    class FakeProc:
        def __init__(self, argv, **kw):
            pass

        def poll(self):
            return None

    rep.proc = OldProc()
    rep.spawn_argv = [_sys.executable, "-m", "tpu_dist_nn.cli", "up",
                      "--config", "m.json", "--grpc-port", "5102",
                      "--metrics-port", "9102"]
    proc_at_port_wait = []

    def fake_reader(proc, timeout):
        proc_at_port_wait.append(type(rep.proc))
        return {"grpc_port": 5102, "metrics_port": 9102}

    real_popen = subprocess.Popen
    real_reader = pool_mod._read_child_ports
    subprocess.Popen = FakeProc
    pool_mod._read_child_ports = fake_reader
    try:
        assert pool.restart_replica(t, grace=0.5)
    finally:
        subprocess.Popen = real_popen
        pool_mod._read_child_ports = real_reader
    assert proc_at_port_wait == [FakeProc], \
        "rep.proc must carry the booting child BEFORE the port wait"
    assert isinstance(rep.proc, FakeProc)
    assert rep.state == ACTIVE  # rejoined with a fresh breaker
    pool.close()
    CircuitBreaker.evict(t)


def test_restart_replica_true_when_scrape_rejoins_first():
    """Regression: undrain() refusing non-DRAINING replicas made
    restart_replica's final undrain() return False whenever the scrape
    loop's auto-rejoin observed the restarted server's ready /healthz
    first — a fully successful restart reported as failure (callers
    honoring the bool contract would retry or alert)."""
    import subprocess
    import sys as _sys

    import tpu_dist_nn.serving.pool as pool_mod

    (t,) = _fresh_targets("restartrace:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()
    rep.proc = _FakeChildProc()
    rep.proc.terminated = True  # old child already exited
    rep.spawn_argv = [_sys.executable, "-m", "tpu_dist_nn.cli", "up",
                      "--config", "m.json", "--grpc-port", "5103",
                      "--metrics-port", "9103"]

    class FakeProc:
        def __init__(self, argv, **kw):
            pass

        def poll(self):
            return None

    def fake_reader(proc, timeout):
        # The scrape tick observes the restarted server ready and
        # auto-rejoins at the same moment the ports print.
        rep.drain_observed = True
        assert pool.undrain(t)
        return {"grpc_port": 5103, "metrics_port": 9103}

    real_popen = subprocess.Popen
    real_reader = pool_mod._read_child_ports
    subprocess.Popen = FakeProc
    pool_mod._read_child_ports = fake_reader
    try:
        assert pool.restart_replica(t, grace=0.5), \
            "a restart the scraper already rejoined is still a success"
    finally:
        subprocess.Popen = real_popen
        pool_mod._read_child_ports = real_reader
    assert rep.state == ACTIVE
    pool.close()
    CircuitBreaker.evict(t)


def test_forward_timeout_caps_deadline_less_forwards():
    """Regression: a deadline-less caller (no gRPC deadline, no
    x-tdn-timeout-ms hint) forwarded with timeout=None — a replica
    that accepts TCP but never answers held a router worker thread
    forever, and 32 such wedged forwards stalled the whole front door
    (the engine path bounds these via the batcher's submit_timeout)."""
    from tpu_dist_nn.serving.router import Router

    (t,) = _fresh_targets("fwdcap:a")
    pool = ReplicaPool([t], seed=0)
    (rep,) = pool.replicas()
    seen = []

    def capture_call(method, payload, *, timeout=None, metadata=()):
        seen.append(timeout)
        return b"reply"

    rep.call = capture_call

    class Ctx:
        def invocation_metadata(self):
            return ()

        def time_remaining(self):
            return None

        def set_trailing_metadata(self, md):
            pass

    router = Router(pool, forward_timeout=45.0)
    assert router.handle("Process", b"req", Ctx()) == b"reply"
    assert seen == [45.0], "deadline-less forward must be capped"
    # A caller-supplied budget still wins over the cap.
    class DeadlineCtx(Ctx):
        def time_remaining(self):
            return 9.0

    seen.clear()
    assert router.handle("Process", b"req", DeadlineCtx()) == b"reply"
    assert seen and seen[0] is not None and seen[0] <= 9.0
    pool.close()
    CircuitBreaker.evict(t)


# ------------------------------------------------------------- bench gate


