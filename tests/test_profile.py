"""Perf attribution + regression sentinel (ISSUE 6): per-stage
self-time breakdown (obs/profile.py + GET /profile + `tdn profile`),
on-demand device capture (GET /debug/profile), structured JSON logging
(obs/log.py), the int8 warmup payoff gauge, and tools/bench_gate.py.

The loopback acceptance path: a served engine hit through GrpcClient
must yield a /profile breakdown whose stage shares sum to within 5% of
the measured root-span wall time — for both the Process and Generate
wire paths. The bench gate must fail a synthetic >5% host-fed
regression, pass a -4% one, skip cleanly across backends, and exit
zero on the checked-in r04->r05 pair only in report-only mode.
"""

import dataclasses
import importlib.util
import io
import json
import logging
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request
import zipfile

import numpy as np
import pytest

from tpu_dist_nn.obs.profile import (
    HANDLER_STAGE,
    SpanRecord,
    compute_self_times,
    format_profile_table,
    profile_snapshot,
)
from tpu_dist_nn.obs.trace import TRACER, Tracer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_GATE = os.path.join(REPO_ROOT, "tools", "bench_gate.py")


def _load_bench_gate():
    spec = importlib.util.spec_from_file_location("bench_gate", BENCH_GATE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _get(url: str, timeout: float = 10.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ------------------------------------------------------ self-time math


def _rec(name, span_id, parent_id, t0, dur, trace="t1"):
    return SpanRecord(name, trace, span_id, parent_id, t0, dur)


def test_self_time_nests_time_nested_siblings():
    """decode.step spans hang off the handler by parent id but run
    INSIDE the decode phase span — the innermost-cover sweep must
    attribute them there, not double-count them against the root."""
    records = [
        _rec("rpc.Generate", "root", None, 0.0, 10.0),
        _rec("decode", "dec", "root", 2.0, 8.0),
        # parented to root, contained in dec:
        _rec("decode.step", "s1", "root", 3.0, 1.0),
        _rec("decode.step", "s2", "root", 5.0, 1.0),
    ]
    selfs = compute_self_times(records)
    assert selfs["s1"] == pytest.approx(1.0)
    assert selfs["s2"] == pytest.approx(1.0)
    assert selfs["dec"] == pytest.approx(6.0)   # 8 - two 1s steps
    assert selfs["root"] == pytest.approx(2.0)  # 10 - dec's 8
    # Self times partition the root wall exactly.
    assert sum(selfs.values()) == pytest.approx(10.0)


def test_self_time_partitions_partially_overlapping_siblings():
    """Two rows of one Generate request decode concurrently in
    different slots: their phase spans partially overlap. The sweep
    still partitions the covered wall exactly once."""
    records = [
        _rec("rpc.Generate", "root", None, 0.0, 10.0),
        _rec("decode", "d0", "root", 1.0, 5.0),   # [1, 6]
        _rec("decode", "d1", "root", 4.0, 5.0),   # [4, 9] — overlaps d0
        _rec("decode.step", "s1", "root", 4.5, 1.0),  # inside both
    ]
    selfs = compute_self_times(records)
    assert selfs["s1"] == pytest.approx(1.0)
    # Overlap region [4, 6] belongs to d1 (latest start), minus the
    # step; d0 keeps [1, 4].
    assert selfs["d0"] == pytest.approx(3.0)
    assert selfs["d1"] == pytest.approx(4.0)
    assert selfs["root"] == pytest.approx(2.0)  # [0,1] + [9,10]
    assert sum(selfs.values()) == pytest.approx(10.0)


def test_self_time_handles_children_leaking_past_parent():
    records = [
        _rec("root", "r", None, 0.0, 4.0),
        # cross-thread child measured slightly past the parent's end
        _rec("fetch", "f", "r", 3.0, 2.0),
    ]
    selfs = compute_self_times(records)
    assert selfs["r"] == pytest.approx(3.0)
    assert selfs["f"] == pytest.approx(2.0)
    # Total covered time [0, 5] partitions exactly.
    assert sum(selfs.values()) == pytest.approx(5.0)


def test_profile_snapshot_shares_sum_and_window():
    t = Tracer(capacity=256, sample_rate=1.0, exemplar_slots=0)
    root = t.start("rpc.Process")
    time.sleep(0.02)
    t.record_span("queue_wait", root.ctx, root.t0, 0.008)
    t.record_span("fetch", root.ctx, root.t0 + 0.008, 0.008)
    root.end()
    doc = profile_snapshot(t, top=3)
    assert doc["traces"] == 1
    m = doc["methods"]["Process"]
    assert 0.95 <= m["share_sum"] <= 1.05
    stages = {s["stage"] for s in m["stages"]}
    assert {"queue_wait", "fetch", HANDLER_STAGE} <= stages
    assert m["slowest"] and len(m["slowest"][0]["trace_id"]) == 32
    # A window entirely in the future excludes the trace.
    later = time.monotonic() + 100.0
    empty = profile_snapshot(t, window=1.0, now=later)
    assert empty["traces"] == 0 and empty["methods"] == {}
    # The table renderer covers both shapes without crashing.
    assert "Process" in format_profile_table(doc)
    assert "no completed request traces" in format_profile_table(empty)


def test_client_spans_are_not_attribution_roots():
    """Loopback double-count guard: a client.Process span containing
    the handler must not become a second root for the same wall."""
    t = Tracer(capacity=64, sample_rate=1.0, exemplar_slots=0)
    client = t.start("client.Process")
    handler = t.start("rpc.Process", parent=client.ctx)
    time.sleep(0.005)
    handler.end()
    client.end()
    doc = profile_snapshot(t)
    assert doc["traces"] == 1
    assert set(doc["methods"]) == {"Process"}


# ------------------------------------------------- serving loopback


class FakeEngine:
    """input_dim + infer — all serve_engine requires (the test_trace
    pattern); a small sleep gives every stage measurable width."""

    def __init__(self, dim=8):
        self.model = dataclasses.make_dataclass("M", ["input_dim"])(dim)

    def infer(self, x):
        time.sleep(0.002)
        return np.asarray(x) * 3.0


def _profile_over_http(params="") -> dict:
    from tpu_dist_nn.obs import start_http_server

    server = start_http_server(0, host="127.0.0.1")
    try:
        status, body = _get(
            f"http://127.0.0.1:{server.port}/profile{params}"
        )
        assert status == 200
        return json.loads(body)
    finally:
        server.close()


def _assert_shares_match_walls(doc: dict, method: str) -> None:
    """The acceptance bar: stage shares sum to within 5% of the
    measured root wall, and the wall matches the recorder's spans."""
    m = doc["methods"][method]
    assert 0.95 <= m["share_sum"] <= 1.05, m
    roots = [
        s for s in TRACER.snapshot()
        if s.name == f"rpc.{method}" and s.dur is not None
    ]
    measured = sum(s.dur for s in roots)
    assert m["wall_seconds_total"] == pytest.approx(measured, rel=0.05)
    assert m["traces"] == len(roots)


def test_loopback_profile_process_shares_sum_to_wall():
    from tpu_dist_nn.serving import GrpcClient, serve_engine

    TRACER.reset()
    TRACER.configure(sample_rate=1.0)
    engine = FakeEngine(dim=8)
    server, port = serve_engine(engine, 0, host="127.0.0.1", coalesce=True)
    try:
        client = GrpcClient(f"127.0.0.1:{port}")
        for _ in range(4):
            client.process(np.full((3, 8), 2.0))
        client.close()
    finally:
        server.stop(0)
    doc = _profile_over_http()
    _assert_shares_match_walls(doc, "Process")
    stages = {s["stage"] for s in doc["methods"]["Process"]["stages"]}
    assert {"queue_wait", "stage", "launch", "fetch", "decode",
            "encode", HANDLER_STAGE} <= stages, stages


def test_loopback_profile_generate_shares_sum_to_wall():
    import jax

    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.serving import GrpcClient, serve_lm_generate

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=24,
    )
    params = init_transformer(jax.random.key(3), cfg)
    TRACER.reset()
    TRACER.configure(sample_rate=1.0)
    server, port = serve_lm_generate(
        params, cfg, 0, max_new_tokens=6, prompt_len=8, host="127.0.0.1",
        gen_slots=2, warm_rows=1,
    )
    try:
        assert server.scheduler is not None  # continuous path
        client = GrpcClient(f"127.0.0.1:{port}")
        rng = np.random.default_rng(0)
        for _ in range(3):
            client.generate(rng.integers(0, 64, (2, 8)))
        client.close()
    finally:
        server.stop(0)
    doc = _profile_over_http()
    _assert_shares_match_walls(doc, "Generate")
    stages = {s["stage"] for s in doc["methods"]["Generate"]["stages"]}
    assert {"queue_wait", "prefill", "prefill.chunk", "decode",
            HANDLER_STAGE} <= stages, stages


def test_profile_route_rejects_garbled_params():
    from tpu_dist_nn.obs import start_http_server

    server = start_http_server(0, host="127.0.0.1")
    try:
        status, body = _get(
            f"http://127.0.0.1:{server.port}/profile?window=soon"
        )
        assert status == 400 and b"window" in body
    finally:
        server.close()


# -------------------------------------------- device capture endpoint


def test_debug_profile_capture_endpoint():
    from tpu_dist_nn.obs import start_http_server

    server = start_http_server(0, host="127.0.0.1")
    base = f"http://127.0.0.1:{server.port}"
    try:
        status, body = _get(f"{base}/debug/profile?seconds=0.2",
                            timeout=60.0)
        # 200 + a loadable zip where jax.profiler works; a JSON 503 is
        # the documented graceful degrade on profiler-less backends.
        assert status in (200, 503), (status, body[:200])
        if status == 200:
            zf = zipfile.ZipFile(io.BytesIO(body))
            assert zf.namelist(), "capture zip must not be empty"
        else:
            assert b"error" in body
        # Bounded and validated windows.
        status, body = _get(f"{base}/debug/profile?seconds=soon")
        assert status == 400
        status, body = _get(f"{base}/debug/profile?seconds=1e9")
        assert status == 400
    finally:
        server.close()


# ------------------------------------------------------- tdn profile


def test_cli_profile_table_and_json(capsys):
    from tpu_dist_nn.cli import main
    from tpu_dist_nn.obs import start_http_server

    TRACER.reset()
    TRACER.configure(sample_rate=1.0)
    root = TRACER.start("rpc.Process")
    time.sleep(0.01)
    TRACER.record_span("fetch", root.ctx, root.t0, 0.006)
    root.end()
    server = start_http_server(0, host="127.0.0.1")
    try:
        rc = main(["profile", "--target", f"127.0.0.1:{server.port}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "== Process" in out and "fetch" in out
        assert HANDLER_STAGE in out
        rc = main(["profile", "--target", f"127.0.0.1:{server.port}",
                   "--json", "--window", "3600", "--top", "2"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["methods"]["Process"]["traces"] >= 1
    finally:
        server.close()


def test_cli_profile_connection_error_is_user_error(capsys):
    from tpu_dist_nn.cli import main

    rc = main(["profile", "--target", "127.0.0.1:1", "--timeout", "0.5"])
    assert rc == 2
    assert "could not fetch" in capsys.readouterr().err


def test_cli_profile_capture_surfaces_endpoint_reason(capsys):
    """An HTTP-error degrade from /debug/profile must surface the
    endpoint's JSON reason, not a bare status line."""
    from tpu_dist_nn.cli import main
    from tpu_dist_nn.obs import start_http_server

    TRACER.reset()
    TRACER.configure(sample_rate=1.0)
    TRACER.start("rpc.Process").end()
    server = start_http_server(0, host="127.0.0.1")
    try:
        rc = main(["profile", "--target", f"127.0.0.1:{server.port}",
                   "--capture-seconds", "1e9"])  # over the endpoint cap
        assert rc == 2
        err = capsys.readouterr().err
        assert "device capture unavailable" in err
        assert "seconds must be in" in err  # the endpoint's own reason
    finally:
        server.close()


# ------------------------------------------------- structured logging


def _capture_records(structured=True):
    """A StructuredLogger wired to an in-memory stream, JSON-formatted."""
    from tpu_dist_nn.obs.log import JsonFormatter, get_logger

    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    handler.setFormatter(JsonFormatter())
    logger = logging.getLogger(f"tdn_test_log_{time.monotonic_ns()}")
    logger.setLevel(logging.DEBUG)
    logger.propagate = False
    logger.handlers[:] = [handler]
    return get_logger(logger.name), stream


def test_json_log_records_are_parseable_events():
    slog, stream = _capture_records()
    slog.info("server.start", port=5101, method="Process",
              note="two words")
    line = stream.getvalue().strip()
    doc = json.loads(line)
    assert doc["event"] == "server.start"
    assert doc["level"] == "info"
    assert doc["port"] == 5101 and doc["method"] == "Process"
    assert doc["note"] == "two words"
    assert isinstance(doc["ts"], float)


def test_json_log_reserved_keys_nest_instead_of_clobbering():
    slog, stream = _capture_records()
    slog.warning("odd.event", level="deep", value=3)
    doc = json.loads(stream.getvalue().strip())
    assert doc["level"] == "warning"          # envelope wins
    assert doc["fields"]["level"] == "deep"   # field preserved
    assert doc["value"] == 3


def test_log_correlates_with_active_span():
    slog, stream = _capture_records()
    tracer = Tracer(capacity=8, sample_rate=1.0, exemplar_slots=0)
    span = tracer.start("rpc.Process")
    with tracer.activate(span):
        slog.info("inside.span")
    span.end()
    slog.info("outside.span")
    lines = [json.loads(ln) for ln in stream.getvalue().strip().splitlines()]
    assert lines[0]["trace_id"] == span.trace_id
    assert lines[0]["span_id"] == span.span_id
    assert "trace_id" not in lines[1]


def test_log_exception_carries_traceback():
    slog, stream = _capture_records()
    try:
        raise RuntimeError("boom")
    except RuntimeError:
        slog.exception("gen.step_failed", active_slots=3)
    doc = json.loads(stream.getvalue().strip())
    assert doc["event"] == "gen.step_failed"
    assert doc["active_slots"] == 3
    assert "RuntimeError: boom" in doc["exc"]


def test_token_bucket_rate_limit_counts_suppressed():
    from tpu_dist_nn.obs.log import _TokenBucket

    b = _TokenBucket(rate=1.0, burst=2)
    assert b.allow("k", now=0.0) == (True, 0)
    assert b.allow("k", now=0.0) == (True, 0)
    assert b.allow("k", now=0.0) == (False, 0)   # bucket empty
    assert b.allow("k", now=0.1) == (False, 0)
    # A second elapses: one token back, and the gap is reported.
    allowed, suppressed = b.allow("k", now=1.2)
    assert allowed and suppressed == 2
    # Independent keys do not share a bucket.
    assert b.allow("other", now=1.2) == (True, 0)


def test_structured_logger_drops_when_bucket_denies():
    from tpu_dist_nn.obs.log import StructuredLogger, _TokenBucket

    slog, stream = _capture_records()
    limited = StructuredLogger(slog._logger, _TokenBucket(rate=0.001,
                                                          burst=1))
    for _ in range(5):
        limited.warning("storm.event", x=1)
    lines = stream.getvalue().strip().splitlines()
    assert len(lines) == 1


def test_plain_records_degrade_to_json_under_formatter():
    from tpu_dist_nn.obs.log import JsonFormatter

    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    handler.setFormatter(JsonFormatter())
    logger = logging.getLogger(f"tdn_test_plain_{time.monotonic_ns()}")
    logger.setLevel(logging.INFO)
    logger.propagate = False
    logger.handlers[:] = [handler]
    logger.info("plain %s message", "formatted")
    doc = json.loads(stream.getvalue().strip())
    assert doc["event"] == "plain formatted message"


# ------------------------------------------------- int8 warmup payoff


def test_quantized_warm_measures_int8_speedup_ratio():
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.obs.registry import REGISTRY
    from tpu_dist_nn.testing.factories import random_model

    model = random_model([8, 6, 4], seed=0)
    engine = Engine.up(model, quantize="int8", warmup=False)
    try:
        warmed = engine.warm_buckets(2)
        assert warmed == [1, 2]
        gauge = REGISTRY.get("tdn_int8_speedup_ratio")
        assert gauge is not None
        ratio = gauge.labels().value
        assert ratio > 0
        # Direct calls report the same figure they publish.
        again = engine.measure_int8_speedup(rows=2)
        assert again > 0
        assert gauge.labels().value == pytest.approx(again)
    finally:
        engine.down()


def test_unquantized_engine_skips_int8_measure():
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.testing.factories import random_model

    engine = Engine.up(random_model([8, 6, 4], seed=1), warmup=False)
    try:
        assert engine.measure_int8_speedup() is None
    finally:
        engine.down()


def test_int8_warm_measure_runs_once_and_honors_env_gate(monkeypatch):
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.testing.factories import random_model

    # Env gate: the automatic warm-time measurement can be disabled
    # (the f32-arm compile is not free on real hardware).
    monkeypatch.setenv("TDN_INT8_WARMUP_MEASURE", "0")
    engine = Engine.up(random_model([8, 6, 4], seed=2), quantize="int8",
                       warmup=False)
    try:
        calls = []
        monkeypatch.setattr(
            engine, "measure_int8_speedup",
            lambda rows=None: calls.append(rows) or 1.0,
        )
        engine.warm_buckets(2)
        assert calls == []
        # Gate back on: first warm measures, a re-warm does not.
        monkeypatch.setenv("TDN_INT8_WARMUP_MEASURE", "1")
        engine._warm_buckets.clear()
        engine.warm_buckets(2)
        assert len(calls) == 1
        engine._int8_measured = True  # what the real measure records
        engine._warm_buckets.clear()
        engine.warm_buckets(2)
        assert len(calls) == 1, "re-warm must not re-measure"
    finally:
        engine.down()


# ---------------------------------------------------------- bench gate


def _round(value=100000.0, *, backend="cpu", device=250000.0,
           rps=1000.0, gen_rps=60.0, ttft=12.0, prefix_rps=70.0,
           prefix_ttft=40.0) -> dict:
    return {
        "value": value,
        "device_resident_samples_per_sec": device,
        "backend": backend,
        "serving": {
            "coalesced": {"rps": rps},
            "generate": {"requests_per_s": gen_rps,
                         "ttft_p99_ms": ttft},
            "generate_prefix": {"rps": prefix_rps,
                                "ttft_p99_ms": prefix_ttft},
        },
    }


def test_bench_gate_passes_small_regression_fails_big():
    gate = _load_bench_gate()
    prev = _round(100000.0)
    ok = gate.compare(prev, _round(96000.0))       # -4%
    assert ok["regressions"] == []
    assert not any(r.get("failed") for r in ok["metrics"])
    bad = gate.compare(prev, _round(94000.0))      # -6%
    assert bad["regressions"] == ["host_fed_samples_per_sec"]
    row = next(r for r in bad["metrics"]
               if r["metric"] == "host_fed_samples_per_sec")
    assert row["failed"] and row["regression"] == pytest.approx(0.06)


def test_bench_gate_improvements_never_fail():
    gate = _load_bench_gate()
    v = gate.compare(_round(100000.0),
                     _round(150000.0, device=500000.0, rps=2000.0,
                            gen_rps=100.0, ttft=5.0))
    assert v["regressions"] == []


def test_bench_gate_ttft_gates_the_lower_is_better_direction():
    gate = _load_bench_gate()
    v = gate.compare(_round(ttft=10.0), _round(ttft=11.0))  # +10% TTFT
    assert v["regressions"] == ["generate_ttft_p99_ms"]
    # TTFT down 10% is an improvement, not a regression.
    v = gate.compare(_round(ttft=10.0), _round(ttft=9.0))
    assert v["regressions"] == []


def test_bench_gate_skips_cleanly_when_backends_differ():
    gate = _load_bench_gate()
    v = gate.compare(_round(backend="cpu-fallback"),
                     _round(50000.0, backend="tpu v4"))
    assert "skipped" in v and "backend" in v["skipped"]
    assert "metrics" not in v


def test_bench_gate_skips_absent_metrics_per_metric():
    gate = _load_bench_gate()
    prev = _round()
    cur = _round(96000.0)
    del cur["serving"]["generate"]
    v = gate.compare(prev, cur)
    skipped = {r["metric"] for r in v["metrics"] if "skipped" in r}
    assert {"generate_rps", "generate_ttft_p99_ms"} <= skipped
    assert v["regressions"] == []


def test_bench_gate_gates_shared_prefix_metrics_both_directions():
    gate = _load_bench_gate()
    prev = _round()
    # The shared-prefix rps dropping >5% fails; its TTFT p99 RISING
    # >5% fails (lower-is-better direction).
    v = gate.compare(prev, _round(prefix_rps=60.0))
    assert v["regressions"] == ["gen_prefix_rps"]
    v = gate.compare(prev, _round(prefix_ttft=45.0))
    assert v["regressions"] == ["gen_prefix_ttft_p99_ms"]
    # Improvements on both never fail.
    v = gate.compare(prev, _round(prefix_rps=90.0, prefix_ttft=30.0))
    assert v["regressions"] == []
    # Rounds that predate the generate_prefix section skip per-metric.
    old = _round()
    del old["serving"]["generate_prefix"]
    v = gate.compare(old, _round())
    skipped = {r["metric"] for r in v["metrics"] if "skipped" in r}
    assert {"gen_prefix_rps", "gen_prefix_ttft_p99_ms"} <= skipped
    assert v["regressions"] == []


def test_bench_gate_attribution_folds_profile_into_report():
    gate = _load_bench_gate()
    verdict = gate.compare(_round(), _round(90000.0))
    profile = {"methods": {"Process": {
        "traces": 10,
        "stages": [{"stage": "fetch", "share": 0.6, "p99_s": 0.004}],
    }}}
    report = gate.render_report(verdict, "cur.json", "prev.json", profile)
    assert "REGRESSED" in report
    assert "fetch 60.0%" in report


def test_bench_gate_report_only_on_checked_in_rounds():
    """The quick-tier smoke from the issue: the checked-in r04->r05
    pair (which carries a real serving regression) exits ZERO in
    report-only mode and NONZERO in enforce mode."""
    base = [sys.executable, BENCH_GATE,
            "--current", os.path.join(REPO_ROOT, "BENCH_r05.json"),
            "--previous", os.path.join(REPO_ROOT, "BENCH_r04.json")]
    report = subprocess.run(
        base + ["--report-only", "--json"], capture_output=True, text=True,
    )
    assert report.returncode == 0, report.stderr
    assert "host_fed_samples_per_sec" in report.stdout
    verdict = json.loads(report.stdout.strip().splitlines()[-1])
    assert verdict["report_only"] is True
    enforced = subprocess.run(base, capture_output=True, text=True)
    assert enforced.returncode == 1
    assert "REGRESSED" in enforced.stdout


def test_bench_gate_enforce_fails_synthetic_regression(tmp_path):
    """Enforce mode on a synthetic >5% host-fed regression exits
    nonzero; the same pair at -4% exits zero."""
    prev = tmp_path / "BENCH_r01.json"
    prev.write_text(json.dumps({"parsed": _round(100000.0)}))

    def run(cur_value):
        cur = tmp_path / "BENCH_r02.json"
        cur.write_text(json.dumps({"parsed": _round(cur_value)}))
        return subprocess.run(
            [sys.executable, BENCH_GATE, "--dir", str(tmp_path)],
            capture_output=True, text=True,
        )

    failing = run(90000.0)   # -10% host-fed
    assert failing.returncode == 1, failing.stdout + failing.stderr
    assert "host_fed_samples_per_sec" in failing.stdout
    passing = run(96000.0)   # -4%
    assert passing.returncode == 0, passing.stdout + passing.stderr


def test_bench_gate_explicit_previous_needs_only_one_round(tmp_path):
    """--previous pointing outside --dir must not demand a second
    discoverable round (the CI-checkout-with-one-artifact case)."""
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"parsed": _round(96000.0)})
    )
    prev = tmp_path / "elsewhere_prev.json"
    prev.write_text(json.dumps({"parsed": _round(100000.0)}))
    proc = subprocess.run(
        [sys.executable, BENCH_GATE, "--dir", str(tmp_path),
         "--previous", str(prev)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "host_fed_samples_per_sec" in proc.stdout


def test_bench_gate_usage_errors_exit_two(tmp_path):
    proc = subprocess.run(
        [sys.executable, BENCH_GATE, "--current", "nope.json",
         "--previous", "also_nope.json"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 2
    proc = subprocess.run(
        [sys.executable, BENCH_GATE, "--dir", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 2  # no rounds to discover


# ------------------------------------------- best-of-history gate mode


def test_bench_gate_history_fails_checked_in_host_fed_drift():
    """The ISSUE-10 quick-tier smoke: r02->r05 host-fed drifted −3%/
    round — under the pairwise 5% threshold every single time — and
    compounded to −15% vs the r02 best. Best-of-history mode must fail
    that trajectory on the CHECKED-IN rounds."""
    # --current is PINNED to r05: once a later (recovered) round is
    # checked in, discovery would gate that instead and the drift this
    # smoke exists to reproduce would vanish.
    proc = subprocess.run(
        [sys.executable, BENCH_GATE, "--history", "BENCH_r*.json",
         "--dir", REPO_ROOT, "--json",
         "--current", os.path.join(REPO_ROOT, "BENCH_r05.json")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["mode"] == "best-of-history"
    assert "host_fed_samples_per_sec" in verdict["regressions"]
    row = next(r for r in verdict["metrics"]
               if r["metric"] == "host_fed_samples_per_sec")
    # The bar is the r02 high-water mark, not the r04 predecessor.
    assert row["best_round"] == "BENCH_r02.json"
    assert row["regression"] > 0.10
    # r01 (a failed round with no payload) is no longer checked in.
    assert "BENCH_r01.json" not in verdict["history_rounds"]
    # Report-only still exits 0 on the same trajectory.
    report = subprocess.run(
        [sys.executable, BENCH_GATE, "--history", "BENCH_r*.json",
         "--dir", REPO_ROOT, "--report-only",
         "--current", os.path.join(REPO_ROOT, "BENCH_r05.json")],
        capture_output=True, text=True,
    )
    assert report.returncode == 0, report.stdout + report.stderr


def test_bench_gate_history_passes_flat_trajectory(tmp_path):
    """A flat (or improving) trajectory with per-round jitter under
    the threshold passes: best-of-history is a drift gate, not a
    noise amplifier."""
    for i, v in enumerate([100000.0, 99000.0, 101000.0, 99500.0], 1):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"parsed": _round(v)})
        )
    proc = subprocess.run(
        [sys.executable, BENCH_GATE, "--history", "BENCH_r*.json",
         "--dir", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all gated metrics within threshold" in proc.stdout


def test_bench_gate_history_compounding_drift_fails_where_pairwise_passes():
    """The boiling-frog unit case: −3%/round for 5 rounds. Every
    pairwise diff is green; best-of-history fails."""
    gate = _load_bench_gate()
    values = [100000.0]
    for _ in range(4):
        values.append(values[-1] * 0.97)
    rounds = [(f"BENCH_r{i:02d}.json", _round(v))
              for i, v in enumerate(values, 1)]
    cur = rounds[-1][1]
    # Pairwise: green.
    pair = gate.compare(rounds[-2][1], cur)
    assert pair["regressions"] == []
    # Best-of-history: −11.5% vs r01's high-water mark — fails.
    hist = gate.compare_history(rounds[:-1], cur)
    assert "host_fed_samples_per_sec" in hist["regressions"]
    row = next(r for r in hist["metrics"]
               if r["metric"] == "host_fed_samples_per_sec")
    assert row["best_round"] == "BENCH_r01.json"


def test_bench_gate_history_skips_other_backend_rounds_per_round():
    """History legitimately spans a backend flap: rounds from another
    backend are excluded per-ROUND; only when NO same-backend history
    exists does the whole gate skip."""
    gate = _load_bench_gate()
    history = [
        ("BENCH_r01.json", _round(500000.0, backend="tpu v4")),
        ("BENCH_r02.json", _round(100000.0, backend="cpu")),
    ]
    cur = _round(98000.0, backend="cpu")
    v = gate.compare_history(history, cur)
    assert v["history_rounds"] == ["BENCH_r02.json"]
    assert v["regressions"] == []  # −2% vs the cpu best, tpu best ignored
    all_tpu = [("BENCH_r01.json", _round(backend="tpu v4"))]
    v = gate.compare_history(all_tpu, cur)
    assert "skipped" in v and "backend" in v["skipped"]


def test_bench_gate_history_lower_is_better_uses_min_as_best():
    gate = _load_bench_gate()
    history = [
        ("BENCH_r01.json", _round(ttft=20.0)),
        ("BENCH_r02.json", _round(ttft=10.0)),  # the TTFT high-water mark
        ("BENCH_r03.json", _round(ttft=18.0)),
    ]
    v = gate.compare_history(history, _round(ttft=11.0))
    assert "generate_ttft_p99_ms" in v["regressions"]
    row = next(r for r in v["metrics"]
               if r["metric"] == "generate_ttft_p99_ms")
    assert row["best_round"] == "BENCH_r02.json"
    # Matching the best passes.
    v = gate.compare_history(history, _round(ttft=10.0))
    assert v["regressions"] == []
