"""Fleet observability plane (ISSUE 9): cross-replica trace stitching,
the embedded time-series ring, SLO burn-rate tracking, and `tdn top`.

The stitched-trace smoke runs a REAL 2-process loopback fleet: two
subprocess replicas (lightweight fake engines — no jax import in the
children) behind an in-parent router, so the stitched document
genuinely joins spans recorded by different processes' tracers. SLO
burn behavior is driven deterministically through testing/faults.py
delays and virtual clocks on the ring/tracker.
"""

import json
import logging
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests.test_batcher_pipeline import AsyncFakeEngine
from tpu_dist_nn.obs import start_http_server
from tpu_dist_nn.obs.collect import merge_profiles, stitch_chrome_traces
from tpu_dist_nn.obs.exposition import (
    parse_prometheus_text,
    parsed_histogram_quantile,
    split_series,
)
from tpu_dist_nn.obs.log import _TokenBucket, get_logger
from tpu_dist_nn.obs.registry import REGISTRY, Registry, histogram_quantile
from tpu_dist_nn.obs.slo import (
    SLOTracker,
    availability_objective,
    latency_objective,
)
from tpu_dist_nn.obs.timeseries import TimeSeriesRing
from tpu_dist_nn.obs.trace import Tracer
from tpu_dist_nn.serving import CircuitBreaker, GrpcClient, ReplicaPool
from tpu_dist_nn.serving.router import (
    admin_routes,
    router_health,
    serve_router,
)
from tpu_dist_nn.serving.server import serve_engine
from tpu_dist_nn.testing import faults


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5.0
    ) as r:
        return r.read()


# ------------------------------------------------- histogram quantiles


def test_histogram_quantile_known_distributions():
    # Exact small case: one observation per bucket.
    edges = (1.0, 2.0, 3.0)
    counts = [1, 1, 1, 1]  # 0.5, 1.5, 2.5, +Inf
    assert histogram_quantile(edges, counts, 0.25) == pytest.approx(1.0)
    assert histogram_quantile(edges, counts, 0.5) == pytest.approx(2.0)
    # q=1.0 lands in +Inf: clamps to the top finite edge.
    assert histogram_quantile(edges, counts, 1.0) == pytest.approx(3.0)
    # Empty histogram: no estimate, never a crash.
    assert histogram_quantile(edges, [0, 0, 0, 0], 0.99) is None
    with pytest.raises(ValueError):
        histogram_quantile(edges, counts, 1.5)

    # Uniform[0, 10) against unit buckets: every quantile is within
    # one bucket width of truth.
    reg = Registry()
    h = reg.histogram("tdn_q_test_seconds", "t",
                      buckets=[float(i) for i in range(1, 11)])
    rng = np.random.default_rng(0)
    values = rng.uniform(0.0, 10.0, 5000)
    child = h.labels()
    for v in values:
        child.observe(float(v))
    for q in (0.1, 0.5, 0.9, 0.99):
        est = child.quantile(q)
        truth = float(np.quantile(values, q))
        assert abs(est - truth) <= 1.0, (q, est, truth)
    # Metric-level convenience matches the child.
    assert h.quantile(0.5) == child.quantile(0.5)


def test_scrape_side_quantile_matches_registry_side():
    reg = Registry()
    h = reg.histogram("tdn_q_par_seconds", "t", labels=("method",))
    rng = np.random.default_rng(1)
    for v in rng.exponential(0.01, 2000):
        h.labels(method="Process").observe(float(v))
    from tpu_dist_nn.obs.exposition import render

    parsed = parse_prometheus_text(render(reg))
    for q in (0.5, 0.99):
        scrape = parsed_histogram_quantile(
            parsed, "tdn_q_par_seconds", q, method="Process"
        )
        assert scrape == pytest.approx(
            h.quantile(q, method="Process"), rel=1e-9
        )
    # No matching series -> None, not a crash.
    assert parsed_histogram_quantile(
        parsed, "tdn_q_par_seconds", 0.5, method="Generate"
    ) is None


def test_split_series_round_trip():
    assert split_series('tdn_x{a="1",b="with space"}') == (
        "tdn_x", {"a": "1", "b": "with space"}
    )
    assert split_series("tdn_x") == ("tdn_x", {})


# ------------------------------------------------------ timeseries ring


def test_timeseries_ring_windows_deltas_and_reset():
    reg = Registry()
    c = reg.counter("tdn_rpc_requests_total", "t", labels=("method",))
    g = reg.gauge("tdn_batcher_pending_rows", "t", labels=("method",))
    ring = TimeSeriesRing(resolution=1.0, retention=10.0, registry=reg)
    t0 = 1000.0
    c.labels(method="Process").inc(10)
    g.labels(method="Process").set(3)
    ring.collect(now=t0)
    c.labels(method="Process").inc(40)
    ring.collect(now=t0 + 5)
    key = 'tdn_rpc_requests_total{method="Process"}'
    assert ring.delta(key, window=100, now=t0 + 5) == (40.0, 5.0)
    # Window that opens between the samples still uses the point at or
    # before its start as the baseline.
    assert ring.delta(key, window=3, now=t0 + 5)[0] == 40.0
    # Gauges ride along for /timeseries and tdn top.
    series = ring.series(family="tdn_batcher_pending_rows")
    assert series['tdn_batcher_pending_rows{method="Process"}'][-1][1] == 3.0
    # Retention: the ring holds at most retention/resolution points.
    for i in range(30):
        ring.record(key, 50 + i, now=t0 + 6 + i)
    assert len(ring.series()[key]) <= 10
    # Counter reset (replica restart): delta restarts at the new value.
    ring.record(key, 2.0, now=t0 + 40)
    assert ring.delta(key, window=100, now=t0 + 40)[0] == 2.0


def test_timeseries_ring_seeds_series_born_mid_window():
    """A labeled error counter whose FIRST increment is the incident
    must be visible to windowed deltas immediately (the lazy-child
    corollary of the registry's unlabeled-counter rule)."""
    reg = Registry()
    e = reg.counter("tdn_rpc_errors_total", "t", labels=("method", "code"))
    ring = TimeSeriesRing(resolution=1.0, retention=60.0, registry=reg)
    ring.collect(now=1000.0)  # no error children exist yet
    e.labels(method="Process", code="INTERNAL").inc(7)
    ring.collect(now=1005.0)
    # Keys use the family's declared label order: (method, code).
    key = 'tdn_rpc_errors_total{method="Process",code="INTERNAL"}'
    assert ring.delta(key, window=30, now=1005.0)[0] == 7.0


def test_timeseries_endpoint_smoke():
    """Quick-tier smoke: GET /timeseries serves the ring's JSON (and
    404s with a reason before a ring is attached)."""
    reg = Registry()
    c = reg.counter("tdn_rpc_requests_total", "t", labels=("method",))
    c.labels(method="Process").inc(5)
    srv = start_http_server(0, host="127.0.0.1", registry=reg)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/timeseries")
        assert exc.value.code == 404
        ring = TimeSeriesRing(resolution=0.5, retention=60.0, registry=reg)
        ring.collect()
        srv.attach(timeseries=ring)
        doc = json.loads(_get(srv.port, "/timeseries"))
        assert doc["resolution_seconds"] == 0.5
        assert "tdn_rpc_requests_total" in doc["families"]
        key = 'tdn_rpc_requests_total{method="Process"}'
        assert doc["series"][key][-1][1] == 5.0
        filt = json.loads(_get(
            srv.port, "/timeseries?family=tdn_rpc_requests_total&window=60"
        ))
        assert set(filt["series"]) == {key}
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/timeseries?window=bogus")
        assert exc.value.code == 400
    finally:
        srv.close()


# ---------------------------------------------------------------- SLO


class _RecordingLogger:
    def __init__(self):
        self.events = []

    def warning(self, event, **fields):
        self.events.append((event, fields))


def test_slo_burn_rate_rises_under_injected_latency_and_recovers():
    """The acceptance scenario, end to end over a real loopback server:
    a deterministic injected delay (testing/faults.py) pushes p99 past
    the objective -> tdn_slo_burn_rate{window="fast"} > 1 within the
    fast window and a slo.burn event fires; removing the fault recovers
    the fast window and the budget accounting."""
    engine = AsyncFakeEngine(dim=8)
    # Call-indexed fault schedule (the batcher binds infer_async at
    # construction, so the plan wraps it up front): launch 1 is the
    # clean baseline, launches 2-9 hold 80ms >> the 25ms objective,
    # everything after is clean again — the injected latency fault and
    # its removal, bit-reproducible.
    plan = faults.FaultPlan(
        at={n: faults.delay(0.08) for n in range(2, 10)}
    )
    engine.infer_async = faults.wrap(engine.infer_async, plan)
    server, port = serve_engine(engine, 0, host="127.0.0.1")
    client = GrpcClient(f"127.0.0.1:{port}")
    ring = TimeSeriesRing(resolution=1.0, retention=600.0)
    slog = _RecordingLogger()
    tracker = SLOTracker(ring, [
        latency_objective(
            "process_latency", "tdn_batch_wait_seconds", 0.025,
            q=0.99, match={"method": "Process"},
        ),
    ], fast_window=30.0, slow_window=300.0, logger=slog)
    t0 = 10_000.0
    try:
        client.process(np.ones((1, 8)))  # families exist pre-baseline
        ring.collect(now=t0)
        for _ in range(8):
            client.process(np.ones((1, 8)))
        assert plan.fired >= 8
        ring.collect(now=t0 + 10)
        doc = tracker.evaluate(now=t0 + 10)
        obj = doc["objectives"][0]
        fast = obj["windows"]["fast"]
        assert fast["total"] >= 8
        assert fast["burn_rate"] > 1.0, fast
        assert obj["burning"]
        assert obj["error_budget_remaining"] < 1.0
        budget_during = obj["error_budget_remaining"]
        assert [e for e, _ in slog.events] == ["slo.burn"]
        assert REGISTRY.get("tdn_slo_burn_rate").labels(
            slo="process_latency", window="fast"
        ).value > 1.0
        # Fault removed (the schedule ends at launch 9): fast traffic
        # refills the fast window, burn drops under 1, and the
        # slow-window budget accounting recovers as good traffic
        # dilutes the incident.
        for _ in range(60):
            client.process(np.ones((1, 8)))
        ring.collect(now=t0 + 100)
        doc = tracker.evaluate(now=t0 + 100)
        obj = doc["objectives"][0]
        assert obj["windows"]["fast"]["burn_rate"] < 1.0, obj["windows"]
        assert obj["windows"]["fast"]["total"] >= 60
        assert not obj["burning"]
        # Once the slow (compliance) window slides past the incident,
        # the budget itself recovers.
        for _ in range(20):
            client.process(np.ones((1, 8)))
        ring.collect(now=t0 + 450)
        doc = tracker.evaluate(now=t0 + 450)
        obj = doc["objectives"][0]
        assert obj["windows"]["slow"]["bad"] == pytest.approx(0.0, abs=0.5)
        assert obj["error_budget_remaining"] > budget_during
        assert obj["error_budget_remaining"] == pytest.approx(1.0, abs=0.05)
    finally:
        client.close()
        server.stop(0)


def test_slo_endpoint_and_gauges_smoke():
    """Quick-tier smoke: GET /slo serves the tracker's status (404
    with a hint before attachment) and the tdn_slo_* gauges land on
    /metrics."""
    reg = Registry()
    total = reg.counter("tdn_rpc_requests_total", "t", labels=("method",))
    errors = reg.counter("tdn_rpc_errors_total", "t",
                         labels=("method", "code"))
    ring = TimeSeriesRing(resolution=1.0, retention=600.0, registry=reg)
    total.labels(method="Process").inc(1)
    ring.collect(now=2000.0)
    total.labels(method="Process").inc(100)
    errors.labels(method="Process", code="INTERNAL").inc(2)
    ring.collect(now=2010.0)
    tracker = SLOTracker(ring, [
        availability_objective(
            "availability", 0.999,
            total_family="tdn_rpc_requests_total",
            bad_family="tdn_rpc_errors_total",
        ),
    ], fast_window=60.0, slow_window=600.0, registry=reg,
        logger=_RecordingLogger())
    tracker.evaluate(now=2010.0)
    srv = start_http_server(0, host="127.0.0.1", registry=reg)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/slo")
        assert exc.value.code == 404
        srv.attach(slo=tracker)
        doc = json.loads(_get(srv.port, "/slo"))
        obj = doc["objectives"][0]
        assert obj["name"] == "availability"
        assert obj["windows"]["fast"]["bad"] == 2.0
        assert obj["windows"]["fast"]["burn_rate"] > 1.0
        parsed = parse_prometheus_text(_get(srv.port, "/metrics").decode())
        assert parsed[
            'tdn_slo_burn_rate{slo="availability",window="fast"}'
        ] > 1.0
        assert (
            'tdn_slo_error_budget_remaining{slo="availability"}' in parsed
        )
    finally:
        srv.close()


def test_slo_burn_rate_limit_is_per_objective():
    """Two simultaneously-burning objectives must BOTH alert: the
    slo.burn token bucket is per objective, so a continuously-burning
    latency SLO cannot starve the availability SLO's events."""
    reg = Registry()
    total = reg.counter("tdn_rpc_requests_total", "t", labels=("method",))
    errors = reg.counter("tdn_rpc_errors_total", "t",
                         labels=("method", "code"))
    h = reg.histogram("tdn_batch_wait_seconds", "t", labels=("method",))
    ring = TimeSeriesRing(resolution=1.0, retention=600.0, registry=reg)
    total.labels(method="Process").inc(1)
    h.labels(method="Process").observe(0.001)
    ring.collect(now=3000.0)
    for _ in range(50):
        total.labels(method="Process").inc()
        h.labels(method="Process").observe(0.5)  # >> objective
    errors.labels(method="Process", code="INTERNAL").inc(20)
    ring.collect(now=3010.0)
    tracker = SLOTracker(ring, [
        latency_objective("lat", "tdn_batch_wait_seconds", 0.025,
                          match={"method": "Process"}),
        availability_objective(
            "avail", 0.999, total_family="tdn_rpc_requests_total",
            bad_family="tdn_rpc_errors_total"),
    ], fast_window=60.0, slow_window=600.0, registry=reg)
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    slo_logger = logging.getLogger("tpu_dist_nn.obs.slo")
    handler = _Capture()
    slo_logger.addHandler(handler)
    old_level = slo_logger.level
    slo_logger.setLevel(logging.WARNING)
    try:
        for _ in range(4):  # past the per-objective burst of 2
            tracker.evaluate(now=3010.0)
    finally:
        slo_logger.removeHandler(handler)
        slo_logger.setLevel(old_level)
    lat_alerts = [r for r in records if "slo=lat" in r]
    avail_alerts = [r for r in records if "slo=avail" in r]
    assert len(lat_alerts) >= 2 and len(avail_alerts) >= 2, records


def test_slo_flag_validation_fails_fast():
    from tpu_dist_nn.cli import main

    assert main(["up", "--config", "/nonexistent.json",
                 "--slo-availability", "1.5"]) == 2
    assert main(["up", "--config", "/nonexistent.json",
                 "--slo-latency-p99-ms", "-3"]) == 2
    # Valid objective but nowhere to evaluate/serve it: silently-inert
    # flags are rejected, not ignored.
    assert main(["up", "--config", "/nonexistent.json",
                 "--slo-availability", "0.999"]) == 2
    assert main(["up", "--config", "/nonexistent.json",
                 "--metrics-port", "0",
                 "--slo-availability", "0.999"]) == 2  # no --grpc-port


# --------------------------------------------------- trace_id filtering


def test_trace_endpoint_trace_id_filter():
    tracer = Tracer(sample_rate=1.0)
    with tracer.start("rpc.Process") as a:
        pass
    with tracer.start("rpc.Process") as b:
        pass
    assert a.trace_id != b.trace_id
    srv = start_http_server(0, host="127.0.0.1", registry=Registry())
    srv._tracer = tracer
    try:
        doc = json.loads(_get(srv.port, f"/trace?trace_id={a.trace_id}"))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert spans and all(
            e["args"]["trace_id"] == a.trace_id for e in spans
        )
        full = json.loads(_get(srv.port, "/trace"))
        assert len([e for e in full["traceEvents"]
                    if e.get("ph") == "X"]) == 2
    finally:
        srv.close()


# ------------------------------------------------------ trace stitching


def _chrome_doc(pid, spans):
    evs = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": f"tdn[{pid}]"}}]
    for name, ts, dur, trace_id, span_id in spans:
        evs.append({
            "ph": "X", "cat": "tdn", "name": name, "ts": ts, "dur": dur,
            "pid": pid, "tid": 1,
            "args": {"trace_id": trace_id, "span_id": span_id},
        })
    return {"traceEvents": evs, "displayTimeUnit": "ms"}


def test_stitch_dedupes_filters_and_lanes_replica_restart():
    """Unit coverage for the stitcher, including the boot_id-changes-
    mid-trace shape: one source address contributing spans from TWO
    pids (a restart between scrapes) must yield two lanes, both named
    by the source."""
    router = _chrome_doc(100, [
        ("rpc.Process", 0, 100, "T1", "r-root"),
        ("router.forward", 10, 80, "T1", "r-fwd"),
    ])
    # One replica, restarted mid-trace: old boot's span and new boot's
    # span arrive under the same source label with different pids.
    replica = {"traceEvents": (
        _chrome_doc(200, [("rpc.Process", 20, 30, "T1", "a-old")])
        ["traceEvents"]
        + _chrome_doc(300, [("rpc.Process", 60, 20, "T1", "a-new")])
        ["traceEvents"]
    )}
    # A loopback endpoint re-exporting the router's span: deduped.
    dup = _chrome_doc(100, [("rpc.Process", 0, 100, "T1", "r-root")])
    st = stitch_chrome_traces(
        {"router": router, "replica 127.0.0.1:5101": replica, "dup": dup}
    )
    meta = st["metadata"]
    assert meta["deduped_events"] == 1
    lanes = {ln["name"]: ln for ln in meta["lanes"]}
    assert "router" in lanes
    assert "replica 127.0.0.1:5101" in lanes
    assert "replica 127.0.0.1:5101 #2" in lanes
    assert lanes["replica 127.0.0.1:5101"]["source_pid"] == 200
    assert lanes["replica 127.0.0.1:5101 #2"]["source_pid"] == 300
    spans = [e for e in st["traceEvents"] if e.get("ph") == "X"]
    assert {e["args"]["trace_id"] for e in spans} == {"T1"}
    assert len(spans) == 4  # r-root, r-fwd, a-old, a-new — no dup
    # trace_id filter drops other traces entirely.
    other = _chrome_doc(400, [("rpc.Process", 0, 10, "T2", "b1")])
    st2 = stitch_chrome_traces({"router": router, "o": other},
                               trace_id="T1")
    assert all(
        e["args"]["trace_id"] == "T1"
        for e in st2["traceEvents"] if e.get("ph") == "X"
    )


# The subprocess replica: a REAL serve_engine + /metrics endpoint with
# its own process-wide tracer, but no jax import (the fake engine is
# numpy-only), so startup is sub-second.
_CHILD = r"""
import json, threading
import numpy as np
from tpu_dist_nn.serving.server import serve_engine
from tpu_dist_nn.obs import start_http_server

class _M:
    input_dim = 8

class _Eng:
    model = _M()
    def infer_async(self, x):
        return np.asarray(x, dtype=np.float64) * 2.0
    def fetch(self, h):
        return h

srv, port = serve_engine(_Eng(), 0, host="127.0.0.1")
ms = start_http_server(0, host="127.0.0.1")
print(json.dumps({"grpc_port": port, "metrics_port": ms.port}),
      flush=True)
threading.Event().wait()
"""


def _spawn_replica():
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd="/root/repo",
    )
    line = proc.stdout.readline()
    if not line:
        err = proc.stderr.read()
        proc.kill()
        raise RuntimeError(f"replica failed to start: {err[-800:]}")
    ports = json.loads(line)
    return proc, ports["grpc_port"], ports["metrics_port"]


def test_two_process_loopback_stitched_trace():
    """Quick-tier acceptance smoke: a request routed through a
    2-replica loopback fleet yields ONE stitched Chrome trace with the
    router's router.forward span and the serving replica's rpc.*
    subtree under the same trace_id, via `tdn trace --aggregate`, with
    lanes named by process."""
    from tpu_dist_nn.cli import main

    procs = []
    pool = rsrv = metrics = client = None
    targets = []
    try:
        grpc_targets, metrics_targets = [], []
        for _ in range(2):
            proc, gport, mport = _spawn_replica()
            procs.append(proc)
            grpc_targets.append(f"127.0.0.1:{gport}")
            metrics_targets.append(f"127.0.0.1:{mport}")
        targets = grpc_targets
        for t in targets:
            CircuitBreaker.evict(t)
        pool = ReplicaPool(grpc_targets, metrics_targets, seed=0)
        rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
        metrics = start_http_server(
            0, host="127.0.0.1", health_fn=router_health(pool),
            routes=admin_routes(pool),
        )
        client = GrpcClient(f"127.0.0.1:{rport}", timeout=15.0,
                            breaker=None)
        for i in range(4):
            out = client.process(np.full((1, 8), float(i)))
            np.testing.assert_allclose(out, np.full((1, 8), 2.0 * i))

        out_path = "/tmp/_tdn_stitched_trace_test.json"
        rc = main(["trace", "--target", f"127.0.0.1:{metrics.port}",
                   "--aggregate", "-o", out_path])
        assert rc == 0
        with open(out_path) as f:
            doc = json.load(f)
        lane_names = {
            e["pid"]: e["args"]["name"]
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert "router" in lane_names.values()
        assert sum(
            1 for n in lane_names.values() if n.startswith("replica ")
        ) == 2, lane_names
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_trace = {}
        for e in spans:
            by_trace.setdefault(e["args"]["trace_id"], []).append(e)
        stitched = [
            tid for tid, evs in by_trace.items()
            if any(e["name"] == "router.forward"
                   and lane_names[e["pid"]] == "router" for e in evs)
            and any(e["name"].startswith("rpc.")
                    and lane_names[e["pid"]].startswith("replica ")
                    for e in evs)
        ]
        assert stitched, (
            f"no trace contains both the router.forward span and a "
            f"replica-lane rpc.* span: lanes={lane_names}, "
            f"traces={list(by_trace)}"
        )
        # The server-side twin: /trace/fleet on the router's endpoint.
        fleet = json.loads(_get(metrics.port, "/trace/fleet"))
        assert fleet["metadata"]["stitched_sources"][0].startswith(
            ("replica", "router")
        )
        assert len(fleet["metadata"]["lanes"]) == 3
        # One stitched trace can be pulled alone via ?trace_id=.
        one = json.loads(_get(
            metrics.port, f"/trace/fleet?trace_id={stitched[0]}"
        ))
        one_spans = [e for e in one["traceEvents"] if e.get("ph") == "X"]
        assert one_spans and {
            e["args"]["trace_id"] for e in one_spans
        } == {stitched[0]}
    finally:
        if client is not None:
            client.close()
        if metrics is not None:
            metrics.close()
        if rsrv is not None:
            rsrv.stop(0)
        if pool is not None:
            pool.close()
        for proc in procs:
            proc.kill()
        for t in targets:
            CircuitBreaker.evict(t)


# ------------------------------------------------------- profile merge


def test_fleet_profile_merge_recomputes_shares_and_keeps_router_lane():
    def pdoc(stage_rows, traces=4, wall=1.0):
        return {"traces": traces, "methods": {"Process": {
            "traces": traces, "wall_seconds_total": wall, "share_sum": 1.0,
            "stages": [
                {"stage": s, "count": c, "total_s": t, "share": t / wall,
                 "p50_s": p50, "p99_s": p99, "max_s": p99}
                for s, c, t, p50, p99 in stage_rows
            ],
            "slowest": [{"trace_id": "T", "wall_s": wall, "stages": {}}],
        }}}

    router = pdoc([("router.forward", 4, 0.6, 0.1, 0.2),
                   ("handler", 4, 0.4, 0.05, 0.1)], wall=1.0)
    replica = pdoc([("fetch", 4, 2.0, 0.3, 0.9),
                    ("handler", 4, 1.0, 0.15, 0.3)], wall=3.0)
    merged = merge_profiles({"router": router, "replica a": replica})
    m = merged["methods"]["Process"]
    assert m["traces"] == 8
    stages = {s["stage"]: s for s in m["stages"]}
    assert set(stages) == {"router.forward", "fetch", "handler"}
    assert m["share_sum"] == pytest.approx(1.0, abs=0.01)
    # Sums are exact; p99 is the fleet-worst source; p50 count-weighted.
    assert stages["handler"]["count"] == 8
    assert stages["handler"]["total_s"] == pytest.approx(1.4)
    assert stages["handler"]["p99_s"] == 0.3
    assert stages["handler"]["p50_s"] == pytest.approx(0.1)
    assert merged["sources"] == {"router": 4, "replica a": 4}
    assert [s["source"] for s in m["slowest"]] == ["replica a", "router"]


# ------------------------------------------------ log limiter threading


def test_log_rate_limiter_under_concurrent_emitters():
    """The token bucket's accounting must stay exact when hammered from
    many threads: allowed count bounded by burst + rate * elapsed, and
    every denial either reported as `suppressed` on a later emit or
    still pending in the bucket state."""
    bucket = _TokenBucket(rate=50.0, burst=20)
    allowed = []
    reported = []
    lock = threading.Lock()
    n_threads, per_thread = 8, 300
    start = threading.Barrier(n_threads)
    t0 = time.monotonic()

    def worker():
        start.wait()
        mine_allowed, mine_reported = 0, 0
        for _ in range(per_thread):
            ok, suppressed = bucket.allow(("log", "event"))
            if ok:
                mine_allowed += 1
                mine_reported += suppressed
            else:
                assert suppressed == 0
        with lock:
            allowed.append(mine_allowed)
            reported.append(mine_reported)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    elapsed = time.monotonic() - t0
    total = n_threads * per_thread
    n_allowed = sum(allowed)
    assert n_allowed >= 20  # the burst always gets through
    assert n_allowed <= 20 + 50.0 * elapsed + n_threads, (
        n_allowed, elapsed
    )
    # Conservation: every denied call is either already reported on a
    # subsequent allowed emit or still pending in the bucket.
    pending = bucket._state[("log", "event")][2]
    assert sum(reported) + pending == total - n_allowed


def test_structured_logger_concurrent_emit_keeps_records_bounded():
    logger = logging.getLogger("tdn.test.fleet_obs.limiter")
    logger.setLevel(logging.INFO)
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = _Capture()
    logger.addHandler(handler)
    try:
        slog = get_logger("tdn.test.fleet_obs.limiter", rate=1.0, burst=5)
        threads = [
            threading.Thread(target=lambda: [
                slog.warning("storm.event", i=i) for i in range(200)
            ])
            for _ in range(6)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        elapsed = time.monotonic() - t0
        assert 1 <= len(records) <= 5 + elapsed + 6
    finally:
        logger.removeHandler(handler)


# -------------------------------------------------------------- tdn top


def test_top_render_frame_rows_slo_and_sparkline():
    from tpu_dist_nn.obs.top import render_frame, sparkline

    state = {
        "target": "127.0.0.1:9100", "fleet": True, "at": 0.0,
        "rows": [
            {"source": "router", "state": "", "rps": 120.5,
             "p50_ms": 1.2, "p99_ms": 9.9, "pending": 0.0, "slots": 0.0,
             "occupancy": 0.0, "prefix_hit": None, "spark": [1, 2, 9]},
            {"source": "replica 127.0.0.1:5101", "state": "active",
             "breaker": "open", "rps": None, "p50_ms": None,
             "p99_ms": None, "pending": 4.0, "slots": 6.0,
             "occupancy": 0.77, "prefix_hit": 0.5, "spark": None},
            {"source": "replica dead", "error": "unreachable (x)"},
        ],
        "slo": {"objectives": [{
            "name": "latency", "objective": "p99 <= 100ms",
            "burning": True, "error_budget_remaining": 0.1,
            "windows": {"fast": {"burn_rate": 3.2},
                        "slow": {"burn_rate": 0.9}},
        }]},
    }
    frame = render_frame(state, color=False)
    assert "router" in frame and "replica 127.0.0.1:5101" in frame
    assert "active/open" in frame
    assert "unreachable (x)" in frame
    assert "p99 <= 100ms" in frame and "3.20" in frame
    assert sparkline([0, 0, 0], width=4) != "    "  # flat-but-nonzero
    assert sparkline([], width=4) == "    "


def test_cli_top_single_endpoint_iterations(capsys):
    reg = REGISTRY
    fam = reg.counter("tdn_rpc_requests_total", "t", labels=("method",))
    fam.labels(method="Process").inc(3)
    srv = start_http_server(0, host="127.0.0.1")
    try:
        from tpu_dist_nn.cli import main

        rc = main(["top", "--target", f"127.0.0.1:{srv.port}",
                   "--iterations", "2", "--interval", "0.05",
                   "--no-color"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "tdn top" in out and f"127.0.0.1:{srv.port}" in out
        assert "[single]" in out
        assert "no SLOs declared" in out
    finally:
        srv.close()


def test_cli_top_unreachable_is_user_error():
    from tpu_dist_nn.cli import main

    rc = main(["top", "--target", "127.0.0.1:1", "--iterations", "1",
               "--no-color", "--timeout", "0.5"])
    assert rc == 2


# --------------------------------------------------------- bench gate


