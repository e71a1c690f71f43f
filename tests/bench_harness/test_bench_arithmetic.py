"""The benchmark's own arithmetic: trace reduction, FLOP and byte
counts, percentiles and window accounting, seeded traffic."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import loadgen, stats, xplane  # noqa: E402
from benchmark.harness.counts import Gpt2Counts  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------ trace reduction

def test_union_counts_overlaps_once():
    assert xplane.union_seconds([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert xplane.union_seconds([]) == 0


def test_reduce_planes_busy_idle_programs_and_gaps():
    ms = 1e6  # ns
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_step(1)", 10 * ms, 30 * ms),
                             ("jit_prefill_chunk(2)", 50 * ms, 10 * ms),
                             ("jit_step(1)", 70 * ms, 30 * ms)]),
            ("XLA Ops", [("%while.1 = (f32[2]) while(...)", 10 * ms, 30 * ms),
                         ("%fusion.3 = bf16[4,8]{1,0} fusion(...)", 12 * ms, 8 * ms),
                         ("%copy.5 = bf16[4,8]{1,0} copy(...)", 25 * ms, 10 * ms),
                         ("%fusion.3 = bf16[4,8]{1,0} fusion(...)", 70 * ms, 30 * ms),
                         ("%fusion.9 = bf16[1,8]{1,0} fusion(...)", 50 * ms, 10 * ms)]),
        ]),
        ("/host:CPU", [("main", [("bench.window", 0.0, 100 * ms)])]),
    ]
    out = xplane.reduce_planes(planes, "bench.window")
    assert out["window_s"] == pytest.approx(0.100)
    # Operations cover 10-40 (the loop, with its body), 50-60 and 70-100
    # ms; in the table of operations the loop is not listed beside its body.
    assert out["busy_s"] == pytest.approx(0.070)
    assert out["programs"]["jit_step"] == {
        "launches": 2, "device_s": pytest.approx(0.060)}
    assert out["programs"]["jit_prefill_chunk"]["launches"] == 1
    ops = dict(out["ops"])
    assert ops["jit_step/fusion.3_bf16_4_8_"] == pytest.approx(0.038)
    assert "jit_step/while.1" not in ops
    gaps = dict(out["gaps"])
    assert gaps["bench.window:jit_step-_jit_prefill_chunk"] == pytest.approx(0.010)
    assert gaps["bench.window:jit_prefill_chunk-_jit_step"] == pytest.approx(0.010)


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by benchmark/tools/trace_probe.py:
    three launches of one small program inside a `bench.window` span."""
    planes = xplane.read_planes(os.path.join(HERE, "data", "probe.xplane.pb"))
    out = xplane.reduce_planes(planes, "bench.window")
    assert out["devices"] == 1
    assert out["programs"]["jit_probe"]["launches"] >= 2
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["programs"]["jit_probe"]["device_s"] <= out["busy_s"] * 1.01
    assert any(name.startswith("jit_probe/") for name, _ in out["ops"])


def test_no_device_plane_reads_nothing():
    out = xplane.reduce_planes(
        [("/host:CPU", [("main", [("bench.window", 0.0, 1e9)])])],
        "bench.window")
    assert out["busy_s"] == 0 and out["programs"] == {}


# ------------------------------------------------------ counts

@pytest.mark.parametrize("name,params,matmul", [
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 50257 x 1024, by hand.
    ("gpt2-medium", 354_823_168, 353_453_056),
    ("gpt2-large", 774_030_080, 772_117_760),
])
def test_parameter_counts(name, params, matmul):
    c = Gpt2Counts(_config(name))
    assert c.n_params() == params
    assert c.matmul_params() == matmul


def test_decode_and_prefill_flops_medium():
    c = Gpt2Counts(_config("gpt2-medium"))
    proj = 24 * (8 * 1024 ** 2 + 4 * 1024 * 4096)
    head = 2 * 1024 * 50257
    assert c.decode_token_flops(0) == proj + head + 4 * 1024 * 24
    assert c.decode_token_flops(200) == proj + head + 4 * 1024 * 24 * 201
    assert c.prefill_flops(128) == 128 * proj + head \
        + 4 * 1024 * 24 * (128 * 129 // 2)
    # 2.27 GFLOP a training token at 1024 positions (the issue's figure).
    assert c.train_token_flops(1024) == pytest.approx(2.272e9, rel=0.001)


def test_decode_step_bytes_large():
    c = Gpt2Counts(_config("gpt2-large"))
    # bf16: matrices once, live keys and values once (2 x L x d a key).
    assert c.decode_step_bytes(56 * 200) == 2 * (
        772_117_760 + 2 * 36 * 1280 * 56 * 200)


# ------------------------------------------------------ window accounting

def _stream(sent, first, n, gap, stalls=(), stall=0.0):
    """Token times of one stream; before each index in `stalls` it
    stands still for `stall` seconds."""
    times, t = [], first
    for i in range(n):
        if i in stalls:
            t += stall
        times.append(t)
        t += gap
    return {"sent": sent, "tokens": times, "ok": True, "done": times[-1]}


def test_percentile_matches_numpy_rule():
    xs = [1.0, 2.0, 3.0, 4.0, 10.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 95) == pytest.approx(8.8)
    assert stats.percentile([], 50) is None


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([10.0, 10.1, 10.2, 10.3, 10.4, 10.5]) == \
        pytest.approx((10.425 - 10.075) / 10.25)


def test_window_counts_edges_and_a_stall_moves_both_metrics():
    steady = [_stream(0.05 * i, 0.2 + 0.05 * i, 200, 0.1) for i in range(20)]
    w = stats.StreamWindow(steady, 5.0, 15.0)
    assert w.tokens == 20 * 100
    assert w.tokens_per_s() == pytest.approx(200.0)
    assert w.sent == 0 and w.ttft_s == []
    assert stats.percentile(w.gaps_s, 95) == pytest.approx(0.1)
    # One second in which every stream stands still, inside the window:
    # fewer tokens fall inside, and the one long gap of each stream (20
    # of 1800) is past the 95th percentile but moves the 99th.
    once = [_stream(0.05 * i, 0.2 + 0.05 * i, 200, 0.1, stalls=(100,),
                    stall=1.0) for i in range(20)]
    w1 = stats.StreamWindow(once, 5.0, 15.0)
    assert w1.tokens == 20 * 90
    assert w1.tokens_per_s() == pytest.approx(180.0)
    assert max(w1.gaps_s) == pytest.approx(1.1)
    assert stats.percentile(w1.gaps_s, 95) == pytest.approx(0.1)
    assert stats.percentile(w1.gaps_s, 99) > 0.5
    # Stalls of 50 ms before one token in twelve (a slow iteration now and
    # then): over a twentieth of the gaps are long, so itl_p95 moves, and
    # out_tokens_per_s with it.
    often = [_stream(0.05 * i, 0.2 + 0.05 * i, 200, 0.1,
                     stalls=range(60, 200, 12), stall=0.05)
             for i in range(20)]
    w2 = stats.StreamWindow(often, 5.0, 15.0)
    assert stats.percentile(w2.gaps_s, 95) == pytest.approx(0.15)
    assert stats.percentile(w2.gaps_s, 50) == pytest.approx(0.1)
    assert w2.tokens_per_s() < w.tokens_per_s()


def test_first_token_counts_only_when_sent_inside():
    reqs = [_stream(4.0, 4.5, 10, 0.1), _stream(6.0, 6.25, 10, 0.1),
            {"sent": 7.0, "tokens": [], "ok": False, "done": None}]
    w = stats.StreamWindow(reqs, 5.0, 15.0)
    assert w.sent == 2 and w.failed == 1
    assert w.ttft_s == [pytest.approx(0.25)]
    assert w.finished == 2  # at 5.4 and 7.15, both inside


def test_open_loop_first_token_is_timed_from_when_it_was_due():
    late = dict(_stream(6.3, 6.5, 10, 0.1), due=6.0)
    w = stats.StreamWindow([late], 5.0, 15.0)
    assert w.ttft_s == [pytest.approx(0.5)]


# ------------------------------------------------------ seeded traffic

def test_every_seed_offers_the_same_lengths_in_another_order():
    mix = {"dist": "uniform", "lo": 128, "hi": 256}
    a = loadgen.plan_lengths(mix, 1, 0, 144)
    b = loadgen.plan_lengths(mix, 2**31 + 7, 0, 144)
    assert sorted(a) == sorted(b) and a != b
    assert min(a) == 128 and max(a) == 256
    assert loadgen.plan_lengths(mix, 1, 0, 144) == a


def test_lognormal_lengths_are_seeded_and_clipped():
    mix = {"dist": "lognormal", "median": 64, "sigma": 1.0, "lo": 8, "hi": 256}
    a = loadgen.plan_lengths(mix, 3, 0, 512)
    assert a == loadgen.plan_lengths(mix, 3, 0, 512)
    assert a != loadgen.plan_lengths(mix, 4, 0, 512)
    assert min(a) == 8 and max(a) == 256
    assert 48 <= sorted(a)[256] <= 80
    with pytest.raises(SystemExit):
        loadgen.plan_lengths({"dist": "zipf"}, 3, 0, 4)


def test_prompts_repeat_by_seed_and_share_prefixes_by_group():
    a = loadgen.make_prompt(5, 3, 16, 50257, None)
    assert (a == loadgen.make_prompt(5, 3, 16, 50257, None)).all()
    assert (a != loadgen.make_prompt(6, 3, 16, 50257, None)).any()
    pre = {"groups": 2, "len": 8}
    x, y, z = (loadgen.make_prompt(5, i, 16, 50257, pre) for i in (0, 2, 1))
    assert (x[:8] == y[:8]).all() and (x[8:] != y[8:]).any()
    assert (x[:8] != z[:8]).any()
