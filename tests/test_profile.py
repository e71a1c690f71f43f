"""Perf attribution (ISSUE 6): per-stage
self-time breakdown (obs/profile.py + GET /profile + `tdn profile`),
on-demand device capture (GET /debug/profile), structured JSON logging
(obs/log.py) and the int8 warmup payoff gauge.

The loopback acceptance path: a served engine hit through GrpcClient
must yield a /profile breakdown whose stage shares sum to within 5% of
the measured root-span wall time — for both the Process and Generate
wire paths.
"""

import dataclasses
import io
import json
import logging
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
