"""Flight recorder (ISSUE 11): anomaly-triggered incident bundles.

Coverage map:
* unit: the incident store's LRU bound, bundle capture/zip schema, the
  log ring's window/level filters, each detector's trigger + the
  recorder's per-detector cooldown, the /trace since= cursor, and the
  fleet /slo merge;
* crash path: SUBPROCESS tests where an injected unhandled exception
  and a SIGABRT each leave a valid bundle on disk whose manifest names
  the crash;
* loopback smoke (quick tier): a deterministic faults.py delay pushes
  p99 past the objective -> the burn detector fires -> a bundle exists
  and contains a trace with the faulted span;
* fleet drill (quick tier, the acceptance scenario): a 2-replica
  loopback fleet under a deterministic fault storm trips the burn
  detector ON THE ROUTER, which captures a stitched fleet bundle in
  one detector tick; `tdn incident ls/show/pull` and `tdn debug
  bundle` drive the same store over HTTP;
* overhead: an armed recorder's detectors run once a sampler tick and
  never while a request is served; quiet traffic captures nothing.
"""

import io
import json
import logging
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
import zipfile

import numpy as np
import pytest

from tests.test_batcher_pipeline import AsyncFakeEngine
from tpu_dist_nn.obs import start_http_server
from tpu_dist_nn.obs.collect import merge_slo, merge_timeseries
from tpu_dist_nn.obs.incident import (
    BreakerOpenDetector,
    DrainFailoverDetector,
    FlightRecorder,
    IncidentStore,
    SLOBurnDetector,
    SpikeDetector,
    capture_bundle,
    default_detectors,
    incident_routes,
)
from tpu_dist_nn.obs.log import LOG_RING, LogRing, get_logger
from tpu_dist_nn.obs.registry import Registry
from tpu_dist_nn.obs.slo import SLOTracker, latency_objective
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
        f"http://127.0.0.1:{port}{path}", timeout=10.0
    ) as r:
        return r.read()


def _zip_names(data: bytes) -> list[str]:
    return zipfile.ZipFile(io.BytesIO(data)).namelist()


def _zip_json(data: bytes, name: str):
    return json.loads(zipfile.ZipFile(io.BytesIO(data)).read(name))


# ------------------------------------------------------------- log ring


def test_log_ring_bounded_window_and_level():
    ring = LogRing(capacity=4)
    t0 = time.time()
    for i in range(6):
        ring.append({"ts": t0 + i, "level": "info", "event": f"e{i}",
                     "fields": {}})
    assert len(ring) == 4
    assert ring.dropped_total == 2
    assert [r["event"] for r in ring.snapshot()] == ["e2", "e3", "e4", "e5"]
    ring.append({"ts": t0 + 100, "level": "error", "event": "boom",
                 "fields": {}})
    # Minimum-severity filter: warning returns warnings AND errors.
    assert [r["event"] for r in ring.snapshot(level="warning")] == ["boom"]
    assert len(ring.snapshot(level="info")) == 4
    # Window keeps the recent tail; limit keeps the newest N.
    recent = ring.snapshot(window=time.time() - (t0 + 99))
    assert [r["event"] for r in recent] == ["boom"]
    assert [r["event"] for r in ring.snapshot(limit=2)] == ["e5", "boom"]
    with pytest.raises(ValueError):
        ring.snapshot(level="bogus")


def test_structured_logger_feeds_process_ring_and_logs_endpoint():
    logger_name = "tdn.test.incident.ring"
    logging.getLogger(logger_name).setLevel(logging.INFO)
    slog = get_logger(logger_name)
    marker = f"incident.ring_marker_{os.getpid()}"
    slog.info(marker, a=1, trace="none")
    events = [r["event"] for r in LOG_RING.snapshot(level="info")]
    assert marker in events
    srv = start_http_server(0, host="127.0.0.1", registry=Registry())
    try:
        doc = json.loads(_get(srv.port, "/logs?level=info"))
        assert doc["capacity"] == LOG_RING.capacity
        assert any(r["event"] == marker for r in doc["records"])
        # level filter excludes it at error severity
        doc2 = json.loads(_get(srv.port, "/logs?level=error&limit=5"))
        assert all(r["event"] != marker for r in doc2["records"])
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/logs?window=bogus")
        assert exc.value.code == 400
    finally:
        srv.close()


# ---------------------------------------------------------------- store


def test_incident_store_prunes_oldest_past_max(tmp_path):
    store = IncidentStore(str(tmp_path), max_incidents=3)
    # A foreign zip in the directory (an operator's pulled copy) must
    # neither list as an incident nor cost a max_incidents slot.
    (tmp_path / "pulled_copy.zip").write_bytes(b"PK\x05\x06" + b"\0" * 18)
    for i in range(5):
        iid, data = capture_bundle(f"trig{i}", "r", tracer=Tracer(),
                                   registry=Registry())
        store.save(iid, data)
        time.sleep(0.02)  # distinct mtimes: prune order is arrival order
    ids = store.ids()
    assert len(ids) == 3
    triggers = [m["trigger"] for m in store.list()]
    assert triggers == ["trig4", "trig3", "trig2"]  # newest first
    assert (tmp_path / "pulled_copy.zip").exists()  # never pruned
    # Reads: manifest + bytes round-trip, unknown id degrades to None.
    assert store.manifest(ids[0])["trigger"] in ("trig2", "trig3", "trig4")
    assert store.read("nonexistent") is None
    assert store.manifest("nonexistent") is None
    with pytest.raises(ValueError):
        IncidentStore(str(tmp_path), max_incidents=0)


def test_capture_bundle_sections_and_manifest():
    tracer = Tracer(sample_rate=1.0)
    with tracer.start("rpc.Process") as sp:
        sp.set("row_count", 3)
    reg = Registry()
    reg.counter("tdn_x_total", "t").inc(2)
    ring = TimeSeriesRing(resolution=1.0, retention=60.0, registry=reg)
    ring.collect(now=1000.0)
    iid, data = capture_bundle(
        "unit.test", "because", {"k": "v"},
        tracer=tracer, registry=reg, ring=ring,
    )
    names = _zip_names(data)
    for required in ("manifest.json", "trace.json", "profile.json",
                     "metrics.txt", "timeseries.json", "logs.json"):
        assert required in names, names
    m = _zip_json(data, "manifest.json")
    assert m["incident_id"] == iid
    assert m["trigger"] == "unit.test"
    assert m["reason"] == "because"
    assert m["details"] == {"k": "v"}
    assert m["pid"] == os.getpid()
    assert "python" in m["versions"]
    assert sorted(m["sections"]) == m["sections"]
    tr = _zip_json(data, "trace.json")
    assert any(e.get("name") == "rpc.Process"
               for e in tr["traceEvents"] if e.get("ph") == "X")
    assert "tdn_x_total 2" in zipfile.ZipFile(
        io.BytesIO(data)
    ).read("metrics.txt").decode()


def test_capture_bundle_salvages_past_broken_section():
    class _BrokenRing:
        resolution = 1.0
        retention = 60.0

        def series(self, window=None):
            raise RuntimeError("ring exploded")

    iid, data = capture_bundle("unit.broken", tracer=Tracer(),
                               registry=Registry(), ring=_BrokenRing())
    m = _zip_json(data, "manifest.json")
    assert "timeseries.json" in m["section_errors"]
    assert "trace.json" in m["sections"]  # the rest survived


# ------------------------------------------------------------ detectors


class _FakeSLO:
    def __init__(self, burn, total=10.0):
        self._burn = burn
        self._total = total

    def status(self):
        return {"objectives": [{
            "name": "latency", "objective": "p99 <= 25ms",
            "windows": {"fast": {"burn_rate": self._burn,
                                 "total": self._total}},
        }]}


def test_slo_burn_detector_fires_and_cooldown_bounds_recaptures(tmp_path):
    store = IncidentStore(str(tmp_path))
    rec = FlightRecorder(
        store, detectors=[SLOBurnDetector()], tracer=Tracer(),
        registry=Registry(), slo=_FakeSLO(burn=4.2), cooldown=100.0,
    )
    assert rec.check(now=0.0)  # fires
    assert rec.check(now=50.0) == []  # inside the cooldown
    assert rec.check(now=150.0)  # past it: the incident re-captures
    assert len(store.ids()) == 2
    m = store.list()[0]
    assert m["trigger"] == "slo.burn"
    assert "4.2" in m["reason"]
    # Zero-traffic windows never fire (burn of nothing is not a burn).
    rec2 = FlightRecorder(store, detectors=[SLOBurnDetector()],
                          tracer=Tracer(), registry=Registry(),
                          slo=_FakeSLO(burn=9.9, total=0.0))
    assert rec2.check(now=0.0) == []


def test_spike_detector_reads_ring_deltas_with_exclude():
    reg = Registry()
    c = reg.counter("tdn_router_requests_total", "t",
                    labels=("replica", "outcome"))
    ring = TimeSeriesRing(resolution=1.0, retention=600.0, registry=reg)
    c.labels(replica="a", outcome="ok").inc(50)
    ring.collect(now=1000.0)
    rec = FlightRecorder(None, tracer=Tracer(), registry=reg, ring=ring)
    det = SpikeDetector("router.error_spike", "tdn_router_requests_total",
                        window=60.0, min_count=5.0,
                        exclude={"outcome": "ok"})
    # 100 MORE ok outcomes: excluded, no spike.
    c.labels(replica="a", outcome="ok").inc(100)
    ring.collect(now=1010.0)
    assert det.check(rec, now=1010.0) is None
    # 6 UNAVAILABLE outcomes inside the window: spike.
    c.labels(replica="a", outcome="UNAVAILABLE").inc(6)
    ring.collect(now=1020.0)
    reason = det.check(rec, now=1020.0)
    assert reason is not None and "+6" in reason


def test_breaker_open_detector_is_edge_triggered():
    reg = Registry()
    g = reg.gauge("tdn_breaker_state", "t", labels=("target",))
    rec = FlightRecorder(None, tracer=Tracer(), registry=reg)
    det = BreakerOpenDetector()
    g.labels(target="127.0.0.1:5101").set(0.0)
    assert det.check(rec) is None
    g.labels(target="127.0.0.1:5101").set(2.0)  # OPEN
    reason = det.check(rec)
    assert reason is not None and "127.0.0.1:5101" in reason
    # Still open next tick: same incident, no re-fire.
    assert det.check(rec) is None
    # Close then re-open: a NEW incident.
    g.labels(target="127.0.0.1:5101").set(0.0)
    assert det.check(rec) is None
    g.labels(target="127.0.0.1:5101").set(2.0)
    assert det.check(rec) is not None


def test_drain_failover_detector_sees_pool_transitions():
    class _FakePool:
        transitions_total = 0

        def snapshot(self):
            return [{"target": "t1", "state": "draining"}]

    pool = _FakePool()
    rec = FlightRecorder(None, tracer=Tracer(), registry=Registry(),
                         pool=pool)
    det = DrainFailoverDetector()
    assert det.check(rec) is None  # baseline tick
    pool.transitions_total = 2
    reason = det.check(rec)
    assert reason is not None and "draining" in reason
    assert det.check(rec) is None  # no further movement


def test_recorder_survives_broken_detector(tmp_path):
    class _Broken:
        name = "broken"

        def check(self, rec, now=None):
            raise RuntimeError("detector bug")

    store = IncidentStore(str(tmp_path))
    rec = FlightRecorder(
        store, detectors=[_Broken(), SLOBurnDetector()], tracer=Tracer(),
        registry=Registry(), slo=_FakeSLO(burn=2.0),
    )
    captured = rec.check(now=0.0)
    assert len(captured) == 1  # the healthy detector still ran
    assert store.list()[0]["trigger"] == "slo.burn"


def test_sampler_ticks_an_armed_recorder_once_and_quiet_captures_nothing(
        tmp_path):
    """Arming costs the request path nothing because the detectors run
    on the sampler's tick and nowhere else: one pass a tick, and with
    served traffic inside its objective nothing is captured."""
    from tpu_dist_nn.obs.runtime import RuntimeSampler

    passes = []

    class _Counting:
        name = "counting"

        def check(self, rec, now=None):
            passes.append(rec)
            return None

    engine = AsyncFakeEngine(dim=8)
    server, port = serve_engine(engine, 0, host="127.0.0.1")
    client = GrpcClient(f"127.0.0.1:{port}")
    ring = TimeSeriesRing(resolution=1.0, retention=600.0)
    tracker = SLOTracker(ring, [
        latency_objective("never_burns", "tdn_batch_wait_seconds", 60.0,
                          q=0.99, match={"method": "Process"}),
    ], fast_window=30.0, slow_window=300.0)
    store = IncidentStore(str(tmp_path))
    rec = FlightRecorder(store, detectors=[*default_detectors(), _Counting()],
                         ring=ring, slo=tracker)
    sampler = RuntimeSampler(interval=3600.0)  # ticked by hand below
    sampler.add_batcher(server.batcher, method="Process")
    sampler.add_timeseries(ring)
    sampler.add_slo_tracker(tracker)
    sampler.add_incident_recorder(rec)
    try:
        for tick in range(1, 4):
            for _ in range(4):
                client.process(np.ones((1, 8)))
            assert len(engine.dispatched_rows) == 4 * tick
            assert len(passes) == tick - 1  # serving ran no detector
            sampler.sample_once()
            assert len(passes) == tick
        assert all(p is rec for p in passes)
        assert rec.captured_total == 0 and store.ids() == []
    finally:
        client.close()
        server.stop(0)


def test_debug_bundle_route_persist_contract(tmp_path):
    """?persist=1 saves to the store and serves the saved bytes;
    without a store it is a 409 with the --incident-dir hint, never a
    silently unpersisted 200."""
    routes = incident_routes(FlightRecorder(
        IncidentStore(str(tmp_path)), tracer=Tracer(), registry=Registry(),
    ))
    status, ctype, data = routes["/debug/bundle"]("persist=1&reason=x")
    assert status == 200 and ctype == "application/zip"
    store = IncidentStore(str(tmp_path))
    assert len(store.ids()) == 1
    assert store.read(store.ids()[0]) == data
    assert store.manifest(store.ids()[0])["trigger"] == "manual"
    # Plain capture does not persist.
    status, ctype, _ = routes["/debug/bundle"]("")
    assert status == 200 and len(store.ids()) == 1
    storeless = incident_routes(FlightRecorder(
        None, tracer=Tracer(), registry=Registry(),
    ))
    status, ctype, body = storeless["/debug/bundle"]("persist=1")
    assert status == 409 and b"--incident-dir" in body


# -------------------------------------------------------- since= cursor


def test_tracer_since_cursor_incremental_snapshots():
    tracer = Tracer(sample_rate=1.0)
    with tracer.start("rpc.Process"):
        pass
    doc1 = tracer.chrome_trace()
    cursor = doc1["cursor"]
    assert cursor >= 1
    assert len([e for e in doc1["traceEvents"]
                if e.get("ph") == "X"]) == 1
    # Nothing new: an incremental pull is empty (exemplars included —
    # the slow trace kept in an exemplar slot must not re-send).
    doc2 = tracer.chrome_trace(since=cursor)
    assert [e for e in doc2["traceEvents"] if e.get("ph") == "X"] == []
    with tracer.start("rpc.Generate"):
        pass
    doc3 = tracer.chrome_trace(since=cursor)
    spans = [e for e in doc3["traceEvents"] if e.get("ph") == "X"]
    assert [s["name"] for s in spans] == ["rpc.Generate"]
    assert doc3["cursor"] == cursor + 1


def test_trace_endpoint_since_param_and_cli_flag(tmp_path, capsys):
    tracer = Tracer(sample_rate=1.0)
    for _ in range(3):
        with tracer.start("rpc.Process"):
            pass
    srv = start_http_server(0, host="127.0.0.1", registry=Registry())
    srv._tracer = tracer
    try:
        full = json.loads(_get(srv.port, "/trace"))
        cur = full["cursor"]
        assert len([e for e in full["traceEvents"]
                    if e.get("ph") == "X"]) == 3
        incr = json.loads(_get(srv.port, f"/trace?since={cur}"))
        assert [e for e in incr["traceEvents"] if e.get("ph") == "X"] == []
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(srv.port, "/trace?since=bogus")
        assert exc.value.code == 400
        # The CLI consumer: --since pulls incrementally and prints the
        # cursor to pass back next poll.
        from tpu_dist_nn.cli import main

        out_path = str(tmp_path / "incr.json")
        rc = main(["trace", "--target", f"127.0.0.1:{srv.port}",
                   "--since", str(cur), "-o", out_path])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert summary["spans"] == 0
        assert summary["cursor"] == cur
    finally:
        srv.close()


# ------------------------------------------------------- fleet SLO merge


def _slo_doc(bad_fast, total_fast, bad_slow=0.0, total_slow=0.0,
             kind="availability", q_ms=None):
    obj = {
        "name": "avail" if kind == "availability" else "lat",
        "kind": kind,
        "objective": "availability >= 0.999" if kind == "availability"
        else "p99 <= 100ms",
        "budget_fraction": 0.001 if kind == "availability" else 0.01,
        "family": "f",
        "windows": {
            "fast": {"seconds": 300, "bad": bad_fast, "total": total_fast,
                     "bad_fraction": bad_fast / max(total_fast, 1),
                     "burn_rate": 0.0,
                     **({"measured_quantile_ms": q_ms}
                        if q_ms is not None else {})},
            "slow": {"seconds": 3600, "bad": bad_slow, "total": total_slow,
                     "bad_fraction": 0.0, "burn_rate": 0.0},
        },
        "error_budget_remaining": 1.0,
        "burning": False,
    }
    return {"fast_window_seconds": 300, "slow_window_seconds": 3600,
            "objectives": [obj]}


def test_merge_slo_recomputes_burn_from_summed_counts():
    # Busy replica burning hard + idle replica coasting: the fleet
    # verdict must reflect the SUM (2 bad / 1000 total), not an
    # average of per-source rates.
    merged = merge_slo({
        "replica a": _slo_doc(2.0, 990.0, 2.0, 990.0),
        "replica b": _slo_doc(0.0, 10.0, 0.0, 10.0),
    })
    obj = merged["objectives"][0]
    fast = obj["windows"]["fast"]
    assert fast["bad"] == 2.0 and fast["total"] == 1000.0
    assert fast["bad_fraction"] == pytest.approx(0.002)
    assert fast["burn_rate"] == pytest.approx(2.0)  # 0.002 / 0.001
    assert fast["measured_availability"] == pytest.approx(0.998)
    assert obj["burning"] is True
    assert sorted(obj["sources"]) == ["replica a", "replica b"]
    # Latency quantile: fleet-worst source, named in merged_estimates.
    lat = merge_slo({
        "a": _slo_doc(1.0, 100.0, kind="latency", q_ms=40.0),
        "b": _slo_doc(1.0, 100.0, kind="latency", q_ms=212.0),
    })
    assert lat["objectives"][0]["windows"]["fast"][
        "measured_quantile_ms"] == 212.0
    assert "fleet-worst" in lat["merged_estimates"]["measured_quantile_ms"]


def test_merge_timeseries_keeps_series_per_source():
    merged = merge_timeseries({
        "router": {"resolution_seconds": 5.0, "families": ["f"],
                   "series": {"f{}": [[1, 2]]}},
        "replica a": {"resolution_seconds": 5.0, "families": ["f", "g"],
                      "series": {"f{}": [[1, 7]]}},
    })
    assert merged["families"] == ["f", "g"]
    assert merged["series"]["f{}"] == {
        "router": [[1, 2]], "replica a": [[1, 7]],
    }


# ------------------------------------------------------------ crash path

_CRASH_CHILD = r"""
import sys, signal
from tpu_dist_nn.obs.incident import (FlightRecorder, IncidentStore,
                                      install_crash_hook)
from tpu_dist_nn.obs.trace import Tracer

store = IncidentStore(sys.argv[1], max_incidents=5)
tracer = Tracer(sample_rate=1.0)
with tracer.start("rpc.Process"):
    pass
rec = FlightRecorder(store, tracer=tracer)
install_crash_hook(rec)
print("armed", flush=True)
if sys.argv[2] == "exc":
    raise RuntimeError("injected crash for the flight recorder")
signal.raise_signal(signal.SIGABRT)
"""


def _run_crash_child(tmp_path, mode):
    proc = subprocess.run(
        [sys.executable, "-c", _CRASH_CHILD, str(tmp_path), mode],
        capture_output=True, text=True, timeout=120, cwd="/root/repo",
    )
    assert "armed" in proc.stdout, proc.stderr[-800:]
    return proc


def test_crash_unhandled_exception_leaves_valid_bundle(tmp_path):
    proc = _run_crash_child(tmp_path, "exc")
    assert proc.returncode == 1  # the process still died
    assert "RuntimeError" in proc.stderr  # previous excepthook still ran
    store = IncidentStore(str(tmp_path))
    ids = store.ids()
    assert len(ids) == 1
    m = store.manifest(ids[0])
    assert m["trigger"] == "crash.exception"
    assert "RuntimeError: injected crash" in m["reason"]
    assert "injected crash for the flight recorder" in \
        m["details"]["traceback"]
    data = store.read(ids[0])
    tr = _zip_json(data, "trace.json")
    assert any(e.get("name") == "rpc.Process"
               for e in tr["traceEvents"] if e.get("ph") == "X")


def test_crash_sigabrt_leaves_valid_bundle_then_dies_by_signal(tmp_path):
    proc = _run_crash_child(tmp_path, "abrt")
    # The handler captured, restored SIG_DFL, and re-raised: the
    # process status is the real SIGABRT death, not a swallowed one.
    assert proc.returncode == -signal.SIGABRT
    store = IncidentStore(str(tmp_path))
    ids = store.ids()
    assert len(ids) == 1
    m = store.manifest(ids[0])
    assert m["trigger"] == "crash.signal"
    assert m["reason"] == "SIGABRT"
    # faulthandler armed into the store directory for harder deaths.
    assert (tmp_path / "faulthandler.log").exists()


# ------------------------------------------------- loopback burn smoke


class _RecordingLogger:
    def __init__(self):
        self.events = []

    def warning(self, event, **fields):
        self.events.append((event, fields))


def test_burn_detector_captures_bundle_with_faulted_span(tmp_path):
    """Quick-tier acceptance smoke: deterministic faults.py delay
    pushes p99 past the objective -> the burn detector fires on the
    (manually driven) sampler tick -> a bundle exists on disk whose
    manifest names slo.burn and whose trace contains the faulted
    request's spans."""
    engine = AsyncFakeEngine(dim=8)
    plan = faults.FaultPlan(at={n: faults.delay(0.08)
                                for n in range(2, 10)})
    engine.infer_async = faults.wrap(engine.infer_async, plan)
    server, port = serve_engine(engine, 0, host="127.0.0.1")
    client = GrpcClient(f"127.0.0.1:{port}")
    ring = TimeSeriesRing(resolution=1.0, retention=600.0)
    tracker = SLOTracker(ring, [
        latency_objective("process_latency", "tdn_batch_wait_seconds",
                          0.025, q=0.99, match={"method": "Process"}),
    ], fast_window=30.0, slow_window=300.0, logger=_RecordingLogger())
    store = IncidentStore(str(tmp_path))
    rec = FlightRecorder(store, detectors=default_detectors(),
                         ring=ring, slo=tracker)
    # Virtual nows ANCHORED at wall time: the ring/SLO windows are
    # driven deterministically, while the bundle's wall-clock window
    # bracket (capture_bundle reads time.time()) still sees the points.
    t0 = time.time()
    try:
        client.process(np.ones((1, 8)))  # families exist pre-baseline
        ring.collect(now=t0)
        tracker.evaluate(now=t0)
        assert rec.check() == []  # armed, quiet: nothing fires
        for _ in range(8):
            client.process(np.ones((1, 8)))
        assert plan.fired >= 8
        ring.collect(now=t0 + 10)
        tracker.evaluate(now=t0 + 10)
        captured = rec.check()
        assert len(captured) == 1, captured
        m = store.manifest(captured[0])
        assert m["trigger"] == "slo.burn"
        assert "process_latency" in m["reason"]
        data = store.read(captured[0])
        names = _zip_names(data)
        for required in ("trace.json", "logs.json", "timeseries.json",
                         "slo.json", "profile.json", "metrics.txt"):
            assert required in names, names
        tr = _zip_json(data, "trace.json")
        spans = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
        # The faulted requests' spans survived into the bundle: a
        # fetch (where the injected delay sat) over the 80ms hold.
        slow = [e for e in spans
                if e["name"] in ("fetch", "launch")
                and e.get("dur", 0) >= 0.07 * 1e6]
        assert slow, [(e["name"], e.get("dur")) for e in spans][:20]
        ts = _zip_json(data, "timeseries.json")
        assert any(k.startswith("tdn_batch_wait_seconds")
                   for k in ts["series"])
        slo_doc = _zip_json(data, "slo.json")
        assert slo_doc["objectives"][0]["burning"] is True
    finally:
        client.close()
        server.stop(0)


# --------------------------------------------------------- fleet drill

# A subprocess replica with a DETERMINISTIC fault storm baked in:
# every launch holds 60ms, far past the router's 10ms p99 objective.
# Real serve_engine + /metrics endpoint, no jax import: sub-second boot
# (the test_fleet_obs child pattern).
_STORM_CHILD = r"""
import json, threading, time
import numpy as np
from tpu_dist_nn.serving.server import serve_engine
from tpu_dist_nn.obs import start_http_server

class _M:
    input_dim = 8

class _Eng:
    model = _M()
    def infer_async(self, x):
        time.sleep(0.06)  # the deterministic fault storm
        return np.asarray(x, dtype=np.float64) * 2.0
    def fetch(self, h):
        return h

srv, port = serve_engine(_Eng(), 0, host="127.0.0.1")
ms = start_http_server(0, host="127.0.0.1")
print(json.dumps({"grpc_port": port, "metrics_port": ms.port}),
      flush=True)
threading.Event().wait()
"""


def _spawn_storm_replica():
    proc = subprocess.Popen(
        [sys.executable, "-c", _STORM_CHILD],
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


def test_fleet_drill_burn_trips_router_recorder_stitched_bundle(
    tmp_path, capsys,
):
    """The ISSUE-11 acceptance drill: on a 2-replica loopback fleet, a
    deterministic fault storm trips the burn detector on the ROUTER,
    which captures a stitched fleet bundle within one detector tick;
    `tdn incident show` names the trigger and the bundle contains the
    cross-replica exemplar trace, the logs ring, and the timeseries
    window; `tdn debug bundle` captures the fleet on demand."""
    from tpu_dist_nn.cli import main

    procs = []
    pool = rsrv = metrics = client = None
    targets = []
    try:
        grpc_targets, metrics_targets = [], []
        for _ in range(2):
            proc, gport, mport = _spawn_storm_replica()
            procs.append(proc)
            grpc_targets.append(f"127.0.0.1:{gport}")
            metrics_targets.append(f"127.0.0.1:{mport}")
        targets = grpc_targets
        for t in targets:
            CircuitBreaker.evict(t)
        pool = ReplicaPool(grpc_targets, metrics_targets, seed=0)
        rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
        ring = TimeSeriesRing(resolution=1.0, retention=600.0)
        tracker = SLOTracker(ring, [
            latency_objective("router_latency",
                              "tdn_router_request_seconds", 0.010,
                              q=0.99),
        ], fast_window=30.0, slow_window=300.0,
            logger=_RecordingLogger())
        store = IncidentStore(str(tmp_path), max_incidents=10)
        recorder = FlightRecorder(
            store, detectors=[SLOBurnDetector()], ring=ring,
            slo=tracker, pool=pool, fleet_timeout=15.0,
        )
        metrics = start_http_server(
            0, host="127.0.0.1", health_fn=router_health(pool),
            routes=admin_routes(pool, recorder),
        )
        client = GrpcClient(f"127.0.0.1:{rport}", timeout=20.0,
                            breaker=None)
        t0 = time.time()  # anchored: see the burn-smoke note
        client.process(np.ones((1, 8)))  # family exists pre-baseline
        ring.collect(now=t0)
        tracker.evaluate(now=t0)
        assert recorder.check() == []  # armed + quiet baseline
        for i in range(8):  # the storm: every request ~60ms >> 10ms
            client.process(np.full((1, 8), float(i)))
        ring.collect(now=t0 + 10)
        tracker.evaluate(now=t0 + 10)
        captured = recorder.check()  # ONE detector tick captures
        assert len(captured) == 1, captured
        iid = captured[0]
        m = store.manifest(iid)
        assert m["trigger"] == "slo.burn"
        assert m["fleet"] is True
        assert len(m["replicas"]) == 2
        assert all("error" not in r for r in m["replicas"]), m["replicas"]
        data = store.read(iid)
        names = _zip_names(data)
        assert "trace_fleet.json" in names
        assert "logs.json" in names and "timeseries.json" in names
        assert sum(1 for n in names if n.startswith("replicas/")) == 2
        # The stitched fleet trace: router.forward on the router lane
        # and an rpc.* span on a replica lane sharing ONE trace id —
        # the cross-replica evidence of the exact slow requests.
        fleet = _zip_json(data, "trace_fleet.json")
        lane_names = {
            e["pid"]: e["args"]["name"]
            for e in fleet["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert "router" in lane_names.values()
        assert any(n.startswith("replica ") for n in lane_names.values())
        by_trace = {}
        for e in fleet["traceEvents"]:
            if e.get("ph") == "X":
                by_trace.setdefault(e["args"]["trace_id"], []).append(e)
        stitched = [
            tid for tid, evs in by_trace.items()
            if any(e["name"] == "router.forward"
                   and lane_names[e["pid"]] == "router" for e in evs)
            and any(e["name"].startswith("rpc.")
                    and lane_names[e["pid"]].startswith("replica ")
                    for e in evs)
        ]
        assert stitched, (lane_names, list(by_trace))
        # Replica timeseries windows rode along inside each sub-bundle.
        rep_zips = [n for n in names if n.startswith("replicas/")]
        sub = zipfile.ZipFile(io.BytesIO(data)).read(rep_zips[0])
        assert "trace.json" in _zip_names(sub)

        # ---- the CLI surface against the router's metrics endpoint.
        target = f"127.0.0.1:{metrics.port}"
        assert main(["incident", "ls", "--target", target]) == 0
        out = capsys.readouterr().out
        assert iid in out and "slo.burn" in out
        assert main(["incident", "show", iid, "--target", target]) == 0
        shown = json.loads(capsys.readouterr().out)
        assert shown["trigger"] == "slo.burn"
        pull_path = str(tmp_path / "pulled.zip")
        assert main(["incident", "pull", iid, "--target", target,
                     "-o", pull_path]) == 0
        capsys.readouterr()
        with open(pull_path, "rb") as f:
            assert f.read() == data
        # Manual fleet capture: tdn debug bundle -> a fresh stitched
        # bundle without any detector involved.
        manual_path = str(tmp_path / "manual.zip")
        assert main(["debug", "bundle", "--target", target,
                     "-o", manual_path]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["replicas"] and len(summary["replicas"]) == 2
        with open(manual_path, "rb") as f:
            manual = f.read()
        assert "trace_fleet.json" in _zip_names(manual)
        # GET /incidents lists it all for scrapers too.
        listing = json.loads(_get(metrics.port, "/incidents"))
        assert any(x.get("incident_id") == iid
                   for x in listing["incidents"])
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


# ----------------------------------------------------- flag validation


def test_cli_incident_flag_validation_fails_fast():
    from tpu_dist_nn.cli import main

    # --incident-dir without --metrics-port: the detectors would have
    # no sampler to ride — rejected, not silently inert.
    assert main(["up", "--config", "/nonexistent.json",
                 "--incident-dir", "/tmp/x"]) == 2
    # ... and without a serving path on this command.
    assert main(["up", "--config", "/nonexistent.json",
                 "--metrics-port", "0", "--incident-dir", "/tmp/x"]) == 2
    assert main(["up", "--config", "/nonexistent.json",
                 "--grpc-port", "0", "--metrics-port", "0",
                 "--incident-dir", "/tmp/x", "--incident-max", "0"]) == 2
    assert main(["router", "--replicas", "h:1",
                 "--incident-dir", "/tmp/x"]) == 2  # no metrics port
    assert main(["lm", "--incident-dir", "/tmp/x", "--metrics-port",
                 "0"]) == 2  # no --serve-generate


# ------------------------------------------------------ overhead smoke


