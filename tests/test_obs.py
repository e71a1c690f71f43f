"""Observability subsystem: registry, exposition, /metrics + /healthz
endpoint, serving/trainer instrumentation, and the `tdn metrics` verb.

The loopback acceptance path (ISSUE 1): a served engine with the
metrics endpoint enabled must expose non-zero
``tdn_rpc_requests_total``, a populated ``tdn_batch_rows`` histogram,
and a ``/healthz`` that mirrors ``Engine.health()``. Engine-backed
variants are gated on the installed jax supporting the engine's mesh
API; fake-engine variants cover the same wiring everywhere.
"""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpu_dist_nn.obs import (
    REGISTRY,
    Registry,
    bridge_latency_stats,
    parse_prometheus_text,
    render,
    start_http_server,
)
from tpu_dist_nn.obs.registry import POW2_BUCKETS


def _get(url: str, timeout: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


class FakeEngine:
    """input_dim + infer + health — all serve_engine and the metrics
    wiring require (the _SlowEngine pattern from test_serving)."""

    def __init__(self, dim=8):
        self.model = dataclasses.make_dataclass("M", ["input_dim"])(dim)
        self.downed = False

    def infer(self, x):
        return np.asarray(x) * 3.0

    def health(self):
        return {"ready": not self.downed, "devices": 1, "pipelined": False}


# ---------------------------------------------------------------- registry


def test_counter_gauge_histogram_basics():
    r = Registry()
    c = r.counter("tdn_t_total", "c", labels=("method",))
    c.labels(method="A").inc()
    c.labels(method="A").inc(2)
    c.labels(method="B").inc()
    assert c.labels(method="A").value == 3
    assert c.labels(method="B").value == 1
    g = r.gauge("tdn_t_gauge", "g")
    g.set(7)
    g.inc()
    g.dec(0.5)
    assert g.labels().value == 7.5
    h = r.histogram("tdn_t_seconds", "h", buckets=(0.1, 1.0))
    for v in (0.05, 0.1, 0.5, 3.0):
        h.observe(v)
    child = h.labels()
    assert child.counts == [2, 1, 1]  # le=0.1 gets the boundary value
    assert child.value == 4 and child.sum == pytest.approx(3.65)


def test_registry_get_or_create_and_conflicts():
    r = Registry()
    a = r.counter("tdn_same_total", "x", labels=("m",))
    b = r.counter("tdn_same_total", "ignored", labels=("m",))
    assert a is b  # module-level sites converge on one family
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("tdn_same_total", "y", labels=("m",))
    with pytest.raises(ValueError, match="already registered"):
        r.counter("tdn_same_total", "z", labels=("other",))
    with pytest.raises(ValueError, match="invalid metric"):
        r.counter("bad name")
    with pytest.raises(ValueError, match="expected labels"):
        a.labels(wrong="x")
    with pytest.raises(ValueError, match="use"):
        a.inc()  # labeled family has no default child


def test_kind_misuse_is_rejected():
    r = Registry()
    c = r.counter("tdn_k_total", "c")
    with pytest.raises(ValueError, match="not valid"):
        c.observe(1.0)
    with pytest.raises(ValueError, match="not valid"):
        c.set(1.0)
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)
    h = r.histogram("tdn_k_seconds", "h")
    with pytest.raises(ValueError, match="not valid"):
        h.inc()
    with pytest.raises(ValueError, match="increasing"):
        r.histogram("tdn_k_bad", "h", buckets=(1.0, 1.0))


def test_latency_stats_bridge_keeps_callers_working():
    from tpu_dist_nn.utils.profiling import LatencyStats

    r = Registry()
    stats = bridge_latency_stats(LatencyStats("probe"), registry=r)
    stats.record(0.2)
    with stats.time():
        pass
    # Existing surface unchanged...
    assert len(stats) == 2 and stats.summary()["count"] == 2
    # ...and every span landed in the bridged histogram too.
    child = r.get("tdn_probe_seconds").labels()
    assert child.value == 2 and child.sum >= 0.2


# --------------------------------------------------------------- exposition


def test_render_text_format_and_round_trip():
    r = Registry()
    c = r.counter("tdn_req_total", "requests", labels=("method",))
    c.labels(method="Process").inc(5)
    h = r.histogram("tdn_rows", "rows", buckets=(1.0, 8.0))
    h.observe(1)
    h.observe(4)
    h.observe(100)
    text = render(r)
    assert "# TYPE tdn_req_total counter" in text
    assert "# HELP tdn_req_total requests" in text
    assert '# TYPE tdn_rows histogram' in text
    parsed = parse_prometheus_text(text)
    assert parsed['tdn_req_total{method="Process"}'] == 5
    assert parsed['tdn_rows_bucket{le="1"}'] == 1
    assert parsed['tdn_rows_bucket{le="8"}'] == 2
    assert parsed['tdn_rows_bucket{le="+Inf"}'] == 3
    assert parsed["tdn_rows_count"] == 3
    assert parsed["tdn_rows_sum"] == 105
    assert parsed["__type__:tdn_rows"] == "histogram"


def test_render_survives_non_finite_values():
    # A diverged-loss NaN gauge must not make the whole endpoint
    # unscrapable: the text format has NaN/+Inf literals.
    r = Registry()
    g = r.gauge("tdn_nan_gauge", "g", labels=("k",))
    g.labels(k="nan").set(float("nan"))
    g.labels(k="inf").set(float("inf"))
    g.labels(k="ninf").set(float("-inf"))
    text = render(r)
    assert 'tdn_nan_gauge{k="nan"} NaN' in text
    assert 'tdn_nan_gauge{k="inf"} +Inf' in text
    assert 'tdn_nan_gauge{k="ninf"} -Inf' in text


def test_histogram_bucket_conflict_is_rejected():
    r = Registry()
    r.histogram("tdn_b_seconds", "h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="buckets"):
        r.histogram("tdn_b_seconds", "h", buckets=(1.0, 5.0))
    # Re-registration without explicit buckets keeps the first schema.
    again = r.histogram("tdn_b_seconds", "h")
    assert again.buckets == (1.0, 2.0)


def test_unlabeled_families_render_at_zero_before_first_event():
    # An error counter must exist at 0 from registration: a series
    # born at its first increment is invisible to rate()/increase()
    # alerting for exactly the event that mattered.
    r = Registry()
    r.counter("tdn_zero_errors_total", "errors")
    r.histogram("tdn_zero_seconds", "spans", buckets=(1.0,))
    parsed = parse_prometheus_text(render(r))
    assert parsed["tdn_zero_errors_total"] == 0
    assert parsed["tdn_zero_seconds_count"] == 0
    # Labeled families stay lazy (open-ended label space).
    r2 = Registry()
    r2.counter("tdn_lazy_total", "c", labels=("m",))
    assert "tdn_lazy_total" not in render(r2)


def test_render_escapes_label_values():
    r = Registry()
    c = r.counter("tdn_esc_total", "e", labels=("path",))
    c.labels(path='a"b\\c\nd').inc()
    text = render(r)
    assert r'a\"b\\c\nd' in text


def test_http_endpoint_metrics_healthz_404():
    r = Registry()
    r.counter("tdn_http_total", "c").inc()
    health = {"ready": True, "devices": 8}
    server = start_http_server(
        0, host="127.0.0.1", registry=r, health_fn=lambda: dict(health)
    )
    try:
        body = _get(f"http://127.0.0.1:{server.port}/metrics")
        assert "tdn_http_total 1" in body
        hz = json.loads(_get(f"http://127.0.0.1:{server.port}/healthz"))
        assert hz == health
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(f"http://127.0.0.1:{server.port}/nope")
        assert e.value.code == 404
        # Not ready -> 503 with the health body (load balancers gate on
        # the status, humans read the JSON).
        health["ready"] = False
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(f"http://127.0.0.1:{server.port}/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read().decode())["ready"] is False
    finally:
        server.close()


def test_http_endpoint_default_health_is_liveness():
    server = start_http_server(0, host="127.0.0.1", registry=Registry())
    try:
        hz = json.loads(_get(f"http://127.0.0.1:{server.port}/healthz"))
        assert hz == {"ready": True}
    finally:
        server.close()


# ------------------------------------------------- serving instrumentation


def test_loopback_serving_metrics_and_healthz():
    """The ISSUE 1 acceptance path on the always-available engine fake:
    RPCs through the coalescing server populate the request counter and
    the rows histogram; /healthz mirrors engine.health()."""
    from tpu_dist_nn.serving import GrpcClient, serve_engine

    engine = FakeEngine(dim=8)
    server, port = serve_engine(engine, 0, host="127.0.0.1", coalesce=True)
    metrics = start_http_server(0, host="127.0.0.1", health_fn=engine.health)
    before = parse_prometheus_text(
        _get(f"http://127.0.0.1:{metrics.port}/metrics")
    )
    try:
        client = GrpcClient(f"127.0.0.1:{port}")
        for i in range(3):
            out = client.process(np.full((2, 8), float(i)))
            assert out.shape == (2, 8)
        client.close()
        after = parse_prometheus_text(
            _get(f"http://127.0.0.1:{metrics.port}/metrics")
        )

        def delta(key):
            return after.get(key, 0) - before.get(key, 0)

        assert delta('tdn_rpc_requests_total{method="Process"}') >= 3
        assert delta('tdn_batcher_submits_total{method="Process"}') >= 3
        assert delta('tdn_batch_rows_count{method="Process"}') >= 1
        assert delta('tdn_batch_rows_sum{method="Process"}') >= 6
        assert delta('tdn_batch_wait_seconds_count{method="Process"}') >= 3
        assert delta('tdn_batch_launches_total{method="Process"}') >= 1
        # Histogram buckets exist on the pow2 grid.
        assert after["__type__:tdn_batch_rows"] == "histogram"
        hz = json.loads(_get(f"http://127.0.0.1:{metrics.port}/healthz"))
        assert hz == engine.health() and hz["ready"] is True
        # Teardown flips /healthz to 503 — the same object the load
        # balancer would drain on.
        engine.downed = True
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(f"http://127.0.0.1:{metrics.port}/healthz")
        assert e.value.code == 503
    finally:
        server.stop(0)
        metrics.close()


def test_rpc_error_counter_on_invalid_argument():
    import grpc

    from tpu_dist_nn.serving import GrpcClient, serve_engine

    engine = FakeEngine(dim=8)
    server, port = serve_engine(engine, 0, host="127.0.0.1", coalesce=True)
    key = 'tdn_rpc_errors_total{method="Process",code="INVALID_ARGUMENT"}'
    before = parse_prometheus_text(render(REGISTRY)).get(key, 0)
    try:
        client = GrpcClient(f"127.0.0.1:{port}")
        with pytest.raises(grpc.RpcError) as e:
            client.process(np.zeros((1, 5)))  # engine wants 8 features
        assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        client.close()
        after = parse_prometheus_text(render(REGISTRY)).get(key, 0)
        assert after == before + 1
    finally:
        server.stop(0)


def test_runtime_sampler_gauges():
    from tpu_dist_nn.obs import RuntimeSampler
    from tpu_dist_nn.serving.server import _Batcher

    class Eng:
        def infer(self, x):
            return np.asarray(x)

    r = Registry()
    batcher = _Batcher(Eng(), method="Process")
    try:
        sampler = RuntimeSampler(interval=30.0, registry=r)
        sampler.add_batcher(batcher, method="Process")
        batcher.submit(np.zeros((3, 4)))
        sampler.sample_once()
        text = parse_prometheus_text(render(r))
        assert text['tdn_batcher_queue_depth{method="Process"}'] == 0
        assert text['tdn_batcher_coalesce_ratio{method="Process"}'] >= 1.0
        assert text["tdn_host_rss_bytes"] > 0
        # start() publishes immediately; stop() joins the thread.
        sampler.start()
        sampler.stop()
    finally:
        batcher.close()


def test_sampler_survives_broken_source():
    from tpu_dist_nn.obs import RuntimeSampler

    class Broken:
        @property
        def _pending(self):
            raise RuntimeError("boom")

        requests_total = 0
        batches_total = 0

    r = Registry()
    sampler = RuntimeSampler(interval=30.0, registry=r)
    sampler.add_batcher(Broken())
    with pytest.raises(RuntimeError):
        sampler.sample_once()  # direct call propagates (test visibility)
    sampler.start()  # the thread wrapper must swallow and keep serving
    time.sleep(0.05)
    sampler.stop()


# -------------------------------------------------------- engine + trainers


def test_engine_infer_metrics_and_compile_cache(tmp_path):
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.core.schema import save_model
    from tpu_dist_nn.testing.factories import random_model

    path = tmp_path / "model.json"
    save_model(random_model([6, 5, 4], seed=0), path)
    engine = Engine.up(str(path))
    snap = parse_prometheus_text(render(REGISTRY))
    engine.infer(np.zeros((3, 6)))
    engine.infer(np.zeros((3, 6)))  # same shape: compile-cache hit
    after = parse_prometheus_text(render(REGISTRY))
    assert (
        after["tdn_engine_infer_seconds_count"]
        - snap.get("tdn_engine_infer_seconds_count", 0)
    ) == 2
    assert (
        after["tdn_engine_infer_rows_total"]
        - snap.get("tdn_engine_infer_rows_total", 0)
    ) == 6
    assert (
        after["tdn_engine_compile_cache_hits_total"]
        - snap.get("tdn_engine_compile_cache_hits_total", 0)
    ) >= 1
    engine.down()


def test_lm_trainer_publishes_step_metrics():
    import jax

    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.train.lm_trainer import LMTrainConfig, train_lm

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq_len=16,
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 32, (2, 9)) for _ in range(4)]
    snap = parse_prometheus_text(render(REGISTRY))
    _, history = train_lm(
        params, cfg, iter(batches),
        LMTrainConfig(steps=4, batch_size=2, seq_len=8, log_every=2),
    )
    assert history  # sanity: the loop logged
    after = parse_prometheus_text(render(REGISTRY))
    key = 'tdn_train_steps_total{trainer="lm"}'
    assert after[key] - snap.get(key, 0) == 4
    tkey = 'tdn_train_tokens_total{trainer="lm"}'
    assert after[tkey] - snap.get(tkey, 0) == 4 * 2 * 8
    assert 'tdn_train_loss{trainer="lm"}' in after
    assert after['__type__:tdn_train_step_seconds'] == "histogram"


def test_lm_trainer_rejects_misaligned_checkpoint_every(tmp_path):
    # ADVICE r5: with steps_per_call=K>1 a checkpoint cadence off the
    # group grid was silently thinned to group boundaries — now it is
    # rejected up front, mirroring the log_every contract.
    import jax

    from tpu_dist_nn.checkpoint import CheckpointManager
    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.train.lm_trainer import LMTrainConfig, train_lm

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq_len=16,
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 32, (2, 9)) for _ in range(4)]
    with pytest.raises(ValueError, match="checkpoint_every"):
        train_lm(
            params, cfg, iter(batches),
            LMTrainConfig(steps=4, batch_size=2, seq_len=8, log_every=2,
                          steps_per_call=2),
            checkpoints=CheckpointManager(tmp_path / "ck"),
            checkpoint_every=3,
        )


# ------------------------------------------------------------- CLI surface


def test_cli_metrics_scrape_pretty_and_raw(capsys):
    from tpu_dist_nn.cli import main as cli_main

    r = REGISTRY
    r.counter("tdn_cli_demo_total", "demo").inc(4)
    server = start_http_server(0, host="127.0.0.1",
                               health_fn=lambda: {"ready": True})
    try:
        rc = cli_main(["metrics", "--target", f"127.0.0.1:{server.port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "[counter] tdn_cli_demo_total = 4" in out
        assert "healthz" in out and '"ready": true' in out
        rc = cli_main([
            "metrics", "--target", f"127.0.0.1:{server.port}", "--raw",
        ])
        out = capsys.readouterr().out
        assert rc == 0 and "# TYPE tdn_cli_demo_total counter" in out
    finally:
        server.close()


def test_cli_error_path_frees_metrics_port(capsys):
    # A command that fails AFTER --metrics-port bound (here: train_lm's
    # log_every % steps_per_call validation) must not leak the bound
    # port — main()'s drain closes it, so an immediate rerun can bind.
    import socket

    from tpu_dist_nn.cli import main as cli_main

    port = _free_port()
    args = [
        "--platform", "cpu", "lm", "--steps", "2", "--batch-size", "4",
        "--seq-len", "16", "--d-model", "16", "--heads", "2",
        "--layers", "1", "--steps-per-call", "3", "--log-every", "50",
        "--metrics-port", str(port),
    ]
    assert cli_main(args) == 2
    assert "log_every" in capsys.readouterr().err
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", port))  # leak would raise EADDRINUSE
    finally:
        s.close()
    # Busy port itself is a clean user error, not a traceback.
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", port))
    blocker.listen(1)
    try:
        assert cli_main(args) == 2
        assert "could not bind" in capsys.readouterr().err
    finally:
        blocker.close()


def test_cli_metrics_connection_error_is_user_error(capsys):
    from tpu_dist_nn.cli import main as cli_main

    rc = cli_main(["metrics", "--target", "127.0.0.1:1", "--timeout", "0.5"])
    assert rc == 2
    assert "could not fetch" in capsys.readouterr().err


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_cli_up_metrics_port_end_to_end(tmp_path):
    """The full --metrics-port acceptance path: `tdn up --grpc-port
    --metrics-port` serves /metrics next to the gRPC endpoint; RPC
    traffic shows up in tdn_rpc_requests_total and tdn_batch_rows, and
    /healthz mirrors Engine.health()."""
    from tpu_dist_nn.cli import main as cli_main
    from tpu_dist_nn.core.schema import save_model
    from tpu_dist_nn.serving import GrpcClient
    from tpu_dist_nn.testing.factories import random_model

    path = tmp_path / "model.json"
    save_model(random_model([8, 6, 4], seed=1), path)
    gport, mport = _free_port(), _free_port()
    t = threading.Thread(
        target=cli_main,
        args=([
            "--platform", "cpu", "up", "--config", str(path),
            "--grpc-port", str(gport), "--metrics-port", str(mport),
            "--serve-warm-rows", "0", "--serve-seconds", "30",
        ],),
        daemon=True,
    )
    t.start()
    deadline = time.monotonic() + 60
    out = None
    client = GrpcClient(f"127.0.0.1:{gport}", timeout=10.0)
    while time.monotonic() < deadline:
        try:
            out = client.process(np.zeros((2, 8)))
            break
        except Exception:
            time.sleep(0.5)
    assert out is not None, "server never came up"
    client.process(np.ones((3, 8)))
    client.close()
    parsed = parse_prometheus_text(_get(f"http://127.0.0.1:{mport}/metrics"))
    assert parsed['tdn_rpc_requests_total{method="Process"}'] >= 2
    assert parsed['tdn_batch_rows_count{method="Process"}'] >= 1
    hz = json.loads(_get(f"http://127.0.0.1:{mport}/healthz"))
    assert hz["ready"] is True and "devices" in hz


def test_cli_lm_metrics_port_with_serving():
    """`tdn lm --metrics-port --serve-generate`: training counters from
    the run plus Generate-side serving counters on one endpoint."""
    from tpu_dist_nn.cli import main as cli_main
    from tpu_dist_nn.serving import GrpcClient

    gport, mport = _free_port(), _free_port()
    t = threading.Thread(
        target=cli_main,
        args=([
            "--platform", "cpu", "lm", "--steps", "2", "--batch-size", "4",
            "--seq-len", "24", "--d-model", "16", "--heads", "2",
            "--layers", "1", "--serve-generate", str(gport),
            "--serve-prompt-len", "8", "--serve-new-tokens", "4",
            "--temperature", "0", "--serve-seconds", "30",
            "--eval-batches", "2", "--metrics-port", str(mport),
        ],),
        daemon=True,
    )
    t.start()
    client = GrpcClient(f"127.0.0.1:{gport}", timeout=15.0)
    prompts = np.full((2, 8), 5)
    deadline = time.monotonic() + 90
    out = None
    while time.monotonic() < deadline:
        try:
            out = client.generate(prompts)
            break
        except Exception:
            time.sleep(1.0)
    client.close()
    assert out is not None, "generation endpoint never came up"
    parsed = parse_prometheus_text(_get(f"http://127.0.0.1:{mport}/metrics"))
    assert parsed['tdn_train_steps_total{trainer="lm"}'] >= 2
    assert parsed['tdn_rpc_requests_total{method="Generate"}'] >= 1
    assert parsed['tdn_batch_rows_count{method="Generate"}'] >= 1


# ------------------------------------------------------------- hot path cost


def test_instrumentation_is_cheap():
    """The acceptance bar is <1% on bench throughput; the structural
    guarantee is that one update is a dict-free float add. This guard
    only catches pathological regressions (e.g. rendering or locking
    on the update path) — 50k updates must stay well under a second
    even on a loaded 1-core runner."""
    r = Registry()
    c = r.counter("tdn_cheap_total", "c", labels=("m",))
    child = c.labels(m="x")
    h = r.histogram("tdn_cheap_rows", "h", buckets=POW2_BUCKETS)
    hchild = h.labels()
    t0 = time.monotonic()
    for _ in range(50_000):
        child.inc()
        hchild.observe(17)
    dt = time.monotonic() - t0
    assert dt < 1.0, f"50k updates took {dt:.3f}s"


# ------------------------------------------------- exposition conformance


def test_prometheus_exposition_conformance():
    """Text-format 0.0.4 conformance for the WHOLE process registry —
    the guard that keeps every newly added gauge scrape-compatible:
    HELP + TYPE lines precede every family's samples, histogram
    families expose exactly ``_bucket``/``_sum``/``_count`` with a
    cumulative le ladder whose ``+Inf`` equals ``_count``, and every
    series line matches the exposition grammar (incl. label escaping).
    """
    import re

    # Import every built-in instrumentation site so their families are
    # registered, then run one sampler pass so gauges materialize.
    import tpu_dist_nn.api.engine  # noqa: F401
    import tpu_dist_nn.serving.continuous  # noqa: F401
    import tpu_dist_nn.serving.resilience  # noqa: F401
    import tpu_dist_nn.serving.server  # noqa: F401
    import tpu_dist_nn.train.lm_trainer  # noqa: F401
    import tpu_dist_nn.train.trainer  # noqa: F401
    from tpu_dist_nn.obs.runtime import RuntimeSampler

    RuntimeSampler().sample_once()
    for m in REGISTRY.collect():
        assert m.help, f"{m.name}: every family must carry HELP text"

    # A label value exercising the escaping rules rides along.
    esc = REGISTRY.counter(
        "tdn_conformance_escape_total", "escaping probe", labels=("path",)
    )
    esc.labels(path='a"b\\c\nd').inc()

    text = render(REGISTRY)
    series_re = re.compile(
        r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\})?$'
    )
    seen_type: dict[str, str] = {}
    seen_help: set[str] = set()
    # histogram family -> labelset -> {"buckets": [(le, v)], suffixes}
    hists: dict[str, dict[str, dict]] = {}
    for line in text.splitlines():
        if line.startswith("# HELP "):
            seen_help.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert name not in seen_type, f"duplicate TYPE for {name}"
            seen_type[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment line: {line}"
        series, _, value = line.rpartition(" ")
        float(value)  # every sample value parses (incl. NaN/+Inf)
        m = series_re.match(series)
        assert m, f"series does not match the exposition grammar: {line}"
        base = m.group("name")
        family = base
        suffix = None
        for sfx in ("_bucket", "_sum", "_count"):
            stem = base[: -len(sfx)] if base.endswith(sfx) else None
            if stem and seen_type.get(stem) == "histogram":
                family, suffix = stem, sfx
                break
        assert family in seen_type, (
            f"sample before (or without) its TYPE line: {line}"
        )
        assert family in seen_help, (
            f"sample before (or without) its HELP line: {line}"
        )
        kind = seen_type[family]
        if kind == "histogram":
            assert suffix is not None, (
                f"histogram family {family} exposed a bare series: {line}"
            )
            labels = series[len(base):]
            pairs = re.findall(
                r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"', labels
            )
            key = tuple(sorted((k, v) for k, v in pairs if k != "le"))
            st = hists.setdefault(family, {}).setdefault(
                key, {"buckets": [], "sum": None, "count": None}
            )
            if suffix == "_bucket":
                le = re.search(r'le="([^"]*)"', labels)
                assert le, f"_bucket series without le label: {line}"
                st["buckets"].append((le.group(1), float(value)))
            elif suffix == "_sum":
                st["sum"] = float(value)
            else:
                st["count"] = float(value)
        else:
            assert suffix is None
            if kind == "counter":
                assert base.endswith("_total") or base.endswith("_info"), (
                    f"counter {base} should end in _total"
                )
    # Histogram ladders: cumulative, +Inf present and equal to _count.
    assert hists, "no histogram families rendered"
    for family, labelsets in hists.items():
        for key, st in labelsets.items():
            assert st["sum"] is not None, f"{family}{key}: missing _sum"
            assert st["count"] is not None, f"{family}{key}: missing _count"
            assert st["buckets"], f"{family}{key}: no buckets"
            assert st["buckets"][-1][0] == "+Inf", (
                f"{family}{key}: ladder must end at +Inf"
            )
            values = [v for _, v in st["buckets"]]
            assert values == sorted(values), (
                f"{family}{key}: bucket counts must be cumulative"
            )
            assert values[-1] == st["count"], (
                f"{family}{key}: +Inf bucket must equal _count"
            )
    # The new ISSUE-6 gauge family is registered and conformant.
    assert seen_type.get("tdn_int8_speedup_ratio") == "gauge"
