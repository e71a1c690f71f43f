"""Goodput & MFU accounting plane (ISSUE 14).

The contract under test is CONSERVATION: every recorded launch's FLOPs
split exactly into ``useful + pad == total`` (integer arithmetic, no
float slop) across the batcher (bucket pad rows), the continuous
scheduler (idle/mid-prefill slot lanes, attention tails), and the
static run-to-completion decode (EOS-frozen steps) — plus the peak
table, the ``/goodput`` endpoint, the timeseries and `tdn top`
satellites, and a disarmed tracker recording nothing.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpu_dist_nn.obs.exposition import MetricsServer, parse_prometheus_text
from tpu_dist_nn.obs.goodput import (
    GOODPUT,
    GoodputTracker,
    LMFlopModel,
    fcnn_flops_per_row,
    resolve_peak,
)
from tpu_dist_nn.obs.registry import Registry


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5.0
    ) as r:
        return r.read()


def _delta(after: dict, before: dict, *keys):
    node_a, node_b = after, before
    for k in keys:
        node_a = node_a[k]
        # A path/stage absent from the earlier snapshot is a 0
        # baseline (its first record created the key).
        node_b = node_b.get(k, {}) if isinstance(node_b, dict) else node_b
    return node_a - (node_b if isinstance(node_b, (int, float)) else 0)


# ------------------------------------------------------- FLOP models


def test_fcnn_flops_per_row_counts_matmuls():
    assert fcnn_flops_per_row([784, 128, 64, 10]) == 2 * (
        784 * 128 + 128 * 64 + 64 * 10
    )
    assert fcnn_flops_per_row([16]) == 0


def test_lm_model_identities_are_exact_ints():
    m = LMFlopModel(3, 32, 64, 48, 19)
    # A fully-live step (pos = extent - 1) has no attention tail.
    assert m.step_useful_flops(m.M - 1) == m.step_flops()
    assert m.step_useful_flops(0) < m.step_flops()
    # steps_useful_sum is the closed form of the per-step sum.
    assert m.steps_useful_sum(7, 5) == sum(
        m.step_useful_flops(p) for p in range(7, 12)
    )
    assert m.steps_useful_sum(7, 0) == 0
    # A final whole-extent chunk is fully live except nothing: its
    # static cost still spans the full key ladder.
    assert m.chunk_useful_flops(0, 4, final=True) <= m.chunk_flops(4)
    # Span cost = sum of its chunk launches.
    assert m.prefill_chunks_flops(0, 10, 4) == (
        2 * m.chunk_flops(4) + m.chunk_flops(2)
    )
    assert m.prefill_chunks_flops(0, 10, None) == m.chunk_flops(10)


# ------------------------------------------------ peak calibration


def test_ensure_peak_scales_by_device_count_and_keeps_max():
    """The ledger records whole multi-device launches, so the peak
    must be per-device x placement size — and the largest configured
    footprint wins (MFU stays conservative across engines)."""
    t = GoodputTracker(registry=Registry())
    assert t.ensure_peak(device_kind="v5p", device_count=4) == 4 * 459e12
    assert t.snapshot()["peak_source"] == "table:v5p x4"
    # A smaller later placement must not shrink the denominator...
    assert t.ensure_peak(device_kind="v5p", device_count=1) == 4 * 459e12
    # ...but a larger one raises it.
    assert t.ensure_peak(device_kind="v5p", device_count=8) == 8 * 459e12
    t2 = GoodputTracker(registry=Registry())
    assert t2.ensure_peak(device_kind="v4") == 275e12
    assert t2.snapshot()["peak_source"] == "table:v4"


def test_peak_resolution_table_then_measured_host():
    peak, source = resolve_peak("TPU v5e lite")
    assert peak == 197e12 and source == "table:TPU v5e lite"
    peak, source = resolve_peak(None)
    assert peak > 0 and source == "measured-host-blas"
    # Cached: a second resolve returns the same measurement.
    assert resolve_peak(None)[0] == peak


def test_unknown_accelerator_kind_is_an_error_not_a_host_peak():
    """A device kind missing from PEAK_FLOPS must fail the configure
    step: an MFU over the host-BLAS figure would look like a number."""
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        resolve_peak("TPU v9 imaginary")
    t = GoodputTracker(registry=Registry())
    with pytest.raises(ValueError, match="PEAK_FLOPS"):
        t.ensure_peak(device_kind="TPU v9 imaginary", device_count=4)
    assert t.snapshot()["peak_source"] is None


# ------------------------------------------------------ conservation


def test_decode_step_conservation_exact():
    m = LMFlopModel(2, 32, 64, 48, 11)
    for active_pos, idle, mid in (
        ([3, 7], 1, 1), ([], 4, 0), ([0, 1, 2, 10], 0, 0), ([5], 0, 3),
    ):
        t = GoodputTracker(registry=Registry())
        t.record_decode_step(m, active_pos, idle, mid)
        snap = t.snapshot()
        slots = len(active_pos) + idle + mid
        assert snap["flops"]["useful"] + snap["flops"]["pad"] \
            == slots * m.step_flops()
        assert snap["flops"]["total"] == slots * m.step_flops()
        if idle:
            assert snap["pad_reasons"]["idle_slot"] == idle * m.step_flops()
        if mid:
            assert snap["pad_reasons"]["mid_prefill_slot"] \
                == mid * m.step_flops()


def test_prefill_chunk_conservation_and_tail():
    m = LMFlopModel(2, 32, 64, 48, 11)
    t = GoodputTracker(registry=Registry())
    t.record_prefill_chunk(m, 0, 4, final=False)
    t.record_prefill_chunk(m, 4, 4, final=True)
    snap = t.snapshot()
    total = 2 * m.chunk_flops(4)
    assert snap["flops"]["total"] == total
    assert snap["flops"]["useful"] + snap["flops"]["pad"] == total
    assert snap["pad_reasons"]["chunk_tail"] == snap["flops"]["pad"]
    assert snap["stages"]["prefill"]["launches"] == 2


def test_static_generate_accounting_eos_frozen_exact():
    """Run-to-completion accounting: bucket pad rows cost their full
    ride, post-EOS positions are eos_frozen pad, and the whole launch
    conserves to the FLOP."""
    m = LMFlopModel(2, 32, 64, 48, 11)
    T, width = 8, 12
    out = np.zeros((3, width), np.int64)
    out[0, T:] = [5, 9, 9, 9]  # eos=9 as 2nd token -> 2 useful tokens
    out[1, T:] = [1, 2, 3, 4]  # no eos -> all 4 useful
    t = GoodputTracker(registry=Registry())
    t.record_static_generate(m, out, 2, 3, T, 9)
    snap = t.snapshot()
    steps = width - T - 1
    row_total = m.chunk_flops(T) + steps * m.step_flops()
    assert snap["flops"]["total"] == 3 * row_total
    assert snap["flops"]["useful"] + snap["flops"]["pad"] \
        == snap["flops"]["total"]
    # The bucket pad row costs its whole prefill + decode.
    assert snap["pad_reasons"]["pad_rows"] == row_total
    # Row 0 froze after its EOS: steps produce tokens 2..4, tokens 3-4
    # are post-EOS -> 2 frozen steps.
    assert snap["pad_reasons"]["eos_frozen"] == 2 * m.step_flops()
    # Without an eos_id nothing can freeze.
    t2 = GoodputTracker(registry=Registry())
    t2.record_static_generate(m, out, 2, 3, T, None)
    assert "eos_frozen" not in t2.snapshot()["pad_reasons"]
    assert t2.snapshot()["flops"]["total"] == 3 * row_total


def test_disabled_tracker_records_nothing():
    m = LMFlopModel(1, 8, 16, 8, 4)
    t = GoodputTracker(registry=Registry())
    t.enabled = False
    t.record_rows(100, 4, 3, path="batcher")
    t.record_decode_step(m, [1], 1, 0)
    t.record_prefill_chunk(m, 0, 2, final=True)
    t.record_prefix_saved(1000)
    snap = t.snapshot()
    assert snap["flops"]["total"] == 0 and snap["launches"] == 0
    assert snap["flops"]["prefix_saved"] == 0


def test_mfu_tick_and_pad_ratio_gauges():
    reg = Registry()
    t = GoodputTracker(registry=reg)
    t.set_peak(1e9, "test")
    t.tick(now=100.0)
    t.record_rows(500_000, 4, 3, path="batcher")
    t.tick(now=101.0)
    # 3 useful rows x 500k FLOPs over 1s against a 1 GFLOPS peak.
    mfu = reg.get("tdn_mfu_ratio").labels().value
    assert mfu == pytest.approx(1_500_000 / 1e9)
    pad = reg.get("tdn_pad_ratio").labels(path="batcher").value
    assert pad == pytest.approx(0.25)
    # Idle window: MFU decays to 0, cumulative pad ratio holds.
    t.tick(now=102.0)
    assert reg.get("tdn_mfu_ratio").labels().value == 0.0
    assert reg.get("tdn_pad_ratio").labels(path="batcher").value \
        == pytest.approx(0.25)


# --------------------------------------------------- serving paths


def test_engine_direct_infer_counts_all_useful():
    import jax

    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.models.fcnn import init_fcnn, spec_from_params

    params = init_fcnn(jax.random.key(0), [16, 8, 4])
    engine = Engine.up(spec_from_params(params, ["relu", "softmax"]))
    fpr = engine._flops_per_row
    assert fpr == 2 * (16 * 8 + 8 * 4)
    g0 = GOODPUT.snapshot()
    engine.infer(np.zeros((3, 16)))
    g1 = GOODPUT.snapshot()
    assert _delta(g1, g0, "flops", "useful") == 3 * fpr
    assert _delta(g1, g0, "flops", "pad") == 0
    assert g1["peak_flops"] and g1["peak_source"]


@pytest.mark.parametrize("armed", [True, False])
def test_loopback_serving_pad_accounting_exact(armed, monkeypatch):
    """The quick-tier smoke (acceptance): odd row counts force bucket
    pad on the loopback wire, useful + pad == total EXACTLY, and the
    /goodput endpoint's shares sum to 1. Disarmed, the same requests
    are served and the ledger does not move."""
    import jax

    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.models.fcnn import init_fcnn, spec_from_params
    from tpu_dist_nn.serving.server import GrpcClient, serve_engine

    params = init_fcnn(jax.random.key(0), [16, 8, 4])
    engine = Engine.up(spec_from_params(params, ["relu", "softmax"]))
    fpr = engine._flops_per_row
    srv, port = serve_engine(engine, 0, host="127.0.0.1", warm_rows=8)
    mserver = MetricsServer(0, host="127.0.0.1", goodput=GOODPUT)
    client = GrpcClient(f"127.0.0.1:{port}")
    try:
        monkeypatch.setattr(GOODPUT, "enabled", armed)
        g0 = GOODPUT.snapshot()
        client.process(np.zeros((3, 16)))  # 3 rows -> pow2 bucket of 4
        client.process(np.zeros((5, 16)))  # 5 rows -> bucket of 8
        g1 = GOODPUT.snapshot()
        if not armed:
            assert g1["flops"] == g0["flops"]
            assert g1["launches"] == g0["launches"]
            return
        du = _delta(g1, g0, "flops", "useful")
        dp = _delta(g1, g0, "flops", "pad")
        assert du == 8 * fpr, "3 + 5 useful rows"
        assert dp == 4 * fpr, "1 + 3 bucket pad rows"
        assert du + dp == _delta(g1, g0, "flops", "total")
        assert _delta(g1, g0, "paths", "batcher", "pad") == 4 * fpr
        doc = json.loads(_get(mserver.port, "/goodput"))
        assert doc["flops"]["useful"] + doc["flops"]["pad"] \
            == doc["flops"]["total"]
        assert doc["shares"]["useful"] + doc["shares"]["pad"] \
            == pytest.approx(1.0)
        assert sum(s["share"] for s in doc["stages"].values()) \
            == pytest.approx(1.0)
        # The registry counter mirrors the ledger.
        parsed = parse_prometheus_text(_get(mserver.port, "/metrics").decode())
        assert parsed['tdn_goodput_flops_total{kind="useful"}'] \
            == doc["flops"]["useful"]
        assert parsed['tdn_goodput_flops_total{kind="pad"}'] \
            == doc["flops"]["pad"]
    finally:
        client.close()
        mserver.close()
        srv.stop(0)


def test_goodput_endpoint_404_until_attached():
    mserver = MetricsServer(0, host="127.0.0.1")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(mserver.port, "/goodput")
        assert exc.value.code == 404
        mserver.attach(goodput=GoodputTracker(registry=Registry()))
        doc = json.loads(_get(mserver.port, "/goodput"))
        assert doc["flops"]["total"] == 0
    finally:
        mserver.close()


def test_continuous_scheduler_conservation_and_prefix_savings():
    """Iteration-level accounting over the REAL kernels: every step
    launch books all S slot lanes (idle + mid-prefill lanes as pad),
    every chunk launch books its static cost, a shared-prefix hit
    records savings — and the whole run conserves exactly against the
    scheduler's own launch counters."""
    import jax

    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.serving.continuous import ContinuousScheduler

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, max_seq_len=16)
    params = init_transformer(jax.random.key(0), cfg)
    g0 = GOODPUT.snapshot()
    sched = ContinuousScheduler(params, cfg, slots=2, prompt_len=8,
                                max_new_tokens=4, prefix_cache_blocks=2,
                                prefill_chunk=4)
    try:
        prompt = np.zeros((1, 8), np.int32)
        sched.submit(prompt)
        sched.submit(prompt)  # same prompt -> prefix hit on admission
    finally:
        sched.close()
    g1 = GOODPUT.snapshot()
    m = sched._gp_model
    du = _delta(g1, g0, "flops", "useful")
    dp = _delta(g1, g0, "flops", "pad")
    # Conservation against the scheduler's own launch ledger: every
    # chunk here is size 4 (T=8, chunk=4; a hit resumes at tier 4).
    expected = (
        sched.prefill_chunks_total * m.chunk_flops(4)
        + sched.steps_total * sched.slots * m.step_flops()
    )
    assert du + dp == expected
    assert du > 0 and dp > 0
    saved = _delta(g1, g0, "flops", "prefix_saved")
    assert saved == m.prefill_chunks_flops(0, 4, 4), \
        "the admission hit skipped exactly the 4-token prefix chunk"
    reasons = {
        k: g1["pad_reasons"].get(k, 0) - g0["pad_reasons"].get(k, 0)
        for k in g1["pad_reasons"]
    }
    assert reasons.get("idle_slot", 0) > 0, \
        "a 2-slot ladder decoding <2 rows at times must book idle lanes"


def test_static_generate_loopback_records():
    import jax

    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.serving.server import GrpcClient, serve_lm_generate

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, max_seq_len=16)
    params = init_transformer(jax.random.key(0), cfg)
    srv, port = serve_lm_generate(params, cfg, 0, max_new_tokens=4,
                                  prompt_len=8, host="127.0.0.1",
                                  scheduler="static")
    client = GrpcClient(f"127.0.0.1:{port}")
    try:
        g0 = GOODPUT.snapshot()
        client.generate(np.zeros((1, 8)))
        g1 = GOODPUT.snapshot()
        m = LMFlopModel.from_config(cfg, 8 + 4 - 1)
        row_total = m.chunk_flops(8) + (4 - 1) * m.step_flops()
        assert _delta(g1, g0, "flops", "total") == row_total
        assert _delta(g1, g0, "flops", "useful") \
            + _delta(g1, g0, "flops", "pad") == row_total
    finally:
        client.close()
        srv.stop(0)


# ------------------------------------------------------- satellites


def test_timeseries_goodput_families_and_counter_reset():
    """Satellite: DEFAULT_FAMILIES carries the goodput families; the
    ring records a real tracker's series and delta() restarts from the
    new value across a simulated counter reset (process restart)."""
    from tpu_dist_nn.obs.timeseries import DEFAULT_FAMILIES, TimeSeriesRing

    for fam in ("tdn_goodput_flops_total", "tdn_mfu_ratio",
                "tdn_pad_ratio", "tdn_prefix_flops_saved_total"):
        assert fam in DEFAULT_FAMILIES
    reg = Registry()
    tracker = GoodputTracker(registry=reg)
    tracker.set_peak(1e9, "test")
    ring = TimeSeriesRing(resolution=1.0, families=DEFAULT_FAMILIES,
                          registry=reg)
    t0 = 1000.0
    tracker.record_rows(1000, 4, 3, path="batcher")
    tracker.tick(now=t0)
    ring.collect(now=t0)
    tracker.record_rows(1000, 4, 4, path="batcher")
    tracker.tick(now=t0 + 5)
    ring.collect(now=t0 + 5)
    key = 'tdn_goodput_flops_total{kind="useful"}'
    delta, covered = ring.delta(key, window=60, now=t0 + 5)
    assert delta == 4000.0 and covered == 5.0
    assert 'tdn_mfu_ratio' in ring.series("tdn_mfu_ratio")
    assert any(k.startswith("tdn_pad_ratio{") for k in ring.keys())
    # Simulated restart: the cumulative series drops to a fresh
    # process's small value — delta() restarts from the new value
    # instead of going negative.
    ring.record(key, 500.0, family="tdn_goodput_flops_total",
                now=t0 + 10)
    delta, _ = ring.delta(key, window=60, now=t0 + 10)
    assert delta == 500.0


def test_top_renders_mfu_pad_columns_fleet_and_single():
    """Satellite: the MFU/pad column renders in both modes (pure
    render_frame), with '-' for sources that predate the plane."""
    from tpu_dist_nn.obs.top import render_frame

    row = {
        "source": "replica 127.0.0.1:5101", "state": "active",
        "rps": 10.0, "p50_ms": 1.0, "p99_ms": 2.0, "pending": 0.0,
        "slots": 2.0, "occupancy": 0.5, "prefix_hit": None,
        "mfu": 0.1234, "pad_ratio": 0.25, "spark": [1, 2],
        "mfu_spark": [0.1, 0.2, 0.1],
    }
    old = {
        "source": "replica old", "state": "active", "rps": 1.0,
        "p50_ms": 1.0, "p99_ms": 2.0, "pending": 0.0, "slots": 0.0,
        "occupancy": 0.0, "prefix_hit": None, "spark": None,
    }
    for fleet in (True, False):
        state = {"target": "t", "fleet": fleet, "at": 0.0,
                 "rows": [row, old], "slo": None}
        frame = render_frame(state, color=False)
        assert "mfu%" in frame and "pad%" in frame
        assert "12.34" in frame, "mfu renders as percent"
        assert "25" in frame, "pad ratio renders as percent"


def test_cli_top_iterations_reads_goodput_from_live_endpoint(capsys):
    """Satellite: the --iterations CI path against a real endpoint
    whose registry carries the goodput families."""
    from tpu_dist_nn.cli import main
    from tpu_dist_nn.obs import start_http_server
    from tpu_dist_nn.obs.registry import REGISTRY

    REGISTRY.gauge(
        "tdn_mfu_ratio", "useful FLOP rate over peak",
    ).set(0.42)
    srv = start_http_server(0, host="127.0.0.1")
    try:
        rc = main(["top", "--target", f"127.0.0.1:{srv.port}",
                   "--iterations", "1", "--interval", "0.05",
                   "--no-color"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "mfu%" in out
        assert "42.00" in out, "the live gauge lands in the column"
    finally:
        srv.close()


def test_fleet_goodput_merge_recomputes_from_sums():
    from tpu_dist_nn.obs.collect import merge_goodput

    docs = {
        "replica a": {
            "mfu": 0.2, "pad_ratio": 0.5, "peak_flops": 100.0,
            "peak_source": "test", "launches": 2,
            "flops": {"useful": 50, "pad": 50, "prefix_saved": 5},
            "stages": {"infer": {"useful": 50, "pad": 50, "launches": 2}},
            "pad_reasons": {"pad_rows": 50},
        },
        "replica b": {
            "mfu": 0.1, "pad_ratio": 0.0, "peak_flops": 300.0,
            "peak_source": "test", "launches": 1,
            "flops": {"useful": 150, "pad": 0, "prefix_saved": 0},
            "stages": {"decode": {"useful": 150, "pad": 0, "launches": 1}},
            "pad_reasons": {},
        },
        "router": {"error": "no tracker"},  # non-goodput doc: skipped
    }
    merged = merge_goodput(docs)
    assert merged["flops"] == {"useful": 200, "pad": 50, "total": 250,
                               "prefix_saved": 5}
    assert merged["pad_ratio"] == pytest.approx(50 / 250)
    # Fleet MFU = sum(mfu_i * peak_i) / sum(peak_i).
    assert merged["mfu"] == pytest.approx((0.2 * 100 + 0.1 * 300) / 400)
    assert merged["stages"]["infer"]["share"] == pytest.approx(100 / 250)
    assert set(merged["sources"]) == {"replica a", "replica b"}


