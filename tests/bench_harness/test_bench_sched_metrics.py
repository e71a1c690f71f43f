"""The scheduler's own account of its loop as per-layer metrics (ISSUE
24): a traced CPU rehearsal of the serving driver prints every one of
them, they agree with the iteration records they are cut from, and a
program that keeps no such record (the parent commit) gives each reader
nothing to read and nothing to raise.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import device, lookup, sched_ring  # noqa: E402

PER_ITERATION = ["sched_host_ms", "sched_device_wait_ms", "sched_publish_ms",
                 "sched_account_ms", "sched_admit_ms", "sched_dispatch_ms"]
NEW_METRICS = PER_ITERATION + ["sched_offcpu_pct", "queue_wait_mean_ms",
                               "prefill_wait_mean_ms", "stream_out_lag_ms"]
CELLS = ["gpt2-medium.decode-sat", "gpt2-large.decode-sat"]


@pytest.fixture(scope="module")
def traced():
    """One traced rehearsal: (metrics of the last line, earlier lines)."""
    with pytest.MonkeyPatch.context() as mp:
        # A real run deletes every device array before the reference
        # runs; in a test process they may belong to other tests.
        mp.setattr(device, "free_device", lambda: None)
        out = io.StringIO()
        with redirect_stdout(out):
            assert bench_run.main([
                "--workload", "rehearsal-tiny.decode-sat", "--seed",
                str(2**31 + 24), "--seconds", "3", "--trace", "1",
                "--rehearse", "1"]) == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.strip()]
    assert lines[-1]["correct"] is True
    return lines[-1]["metrics"], lines[:-1]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_traced_rehearsal_prints_the_metric(traced, name):
    metrics, _ = traced
    got = metrics["rehearsal." + name]
    assert got["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert got["value"] >= 0
    if name == "sched_offcpu_pct":
        assert got["value"] <= 100


def test_host_and_device_wait_make_up_the_iteration(traced):
    """`sched_host_ms + sched_device_wait_ms` is the iteration, idle
    apart, against the records it is cut from."""
    from tpu_dist_nn.obs.trace import ITER_FIELDS, ITERATIONS, LOOP_PHASES

    metrics, notes = traced
    value = lambda n: metrics["rehearsal." + n]["value"]  # noqa: E731
    window = next(n["window"] for n in notes if "window" in n)
    records = [dict(zip(ITER_FIELDS, r)) for r in
               ITERATIONS.window(window["open"], window["close"])]
    assert len(records) > 100
    first, last = records[0], records[-1]
    iterations = last["seq"] - first["seq"]
    per = lambda p: (last[p] - first[p]) / iterations / 1e6  # noqa: E731
    busy = value("sched_host_ms") + value("sched_device_wait_ms")
    assert busy == pytest.approx(
        sum(per(p) for p in LOOP_PHASES if p != "idle"), rel=1e-9)
    # With the idle share the phases are the wall time between records.
    wall_ms = 1e3 * (last["t_end"] - first["t_end"]) / iterations
    assert busy + per("idle") == pytest.approx(wall_ms, rel=1e-6)
    # The four groups of host phases are all of the host time.
    assert value("sched_host_ms") == pytest.approx(
        value("sched_publish_ms") + value("sched_account_ms")
        + value("sched_admit_ms") + value("sched_dispatch_ms"), rel=1e-9)
    # Against 1000 / `decode_steps_per_s` the two agree within 0.3 % on
    # the chip (PERF.md section 5, PR 24). Not asserted here: the benchmark
    # counts steps until it reads the counters, which on a CPU is late by
    # the seconds the profiler takes to stop (stalling the loop
    # meanwhile), and a toy model leaves the loop idle between requests.
    assert value("decode_steps_per_s") > 0


def test_waits_add_up_to_the_first_token_time(traced):
    """Queue wait and prefill wait are the server's share of the time to
    the first token; the client's mean adds the wire on both sides."""
    metrics, _ = traced
    waits = (metrics["rehearsal.queue_wait_mean_ms"]["value"]
             + metrics["rehearsal.prefill_wait_mean_ms"]["value"])
    assert 0 < waits <= metrics["rehearsal.ttft_mean_ms"]["value"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_a_program_without_the_ring(
        traced, monkeypatch, name):
    import tpu_dist_nn.obs.trace as trace_mod

    class Client:
        t_open, t_close = 0.0, float("inf")

    class RunStub:
        client = Client()

    reader = lookup.metric_reader(name)
    assert reader.METRIC["name"] == name
    assert reader.read(RunStub()) is not None  # the rehearsal's records
    monkeypatch.delattr(trace_mod, "ITERATIONS")
    assert reader.read(RunStub()) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_needs_two_records_in_the_window(name):
    class Client:
        t_open = t_close = 0.0

    class RunStub:
        client = Client()

    assert lookup.metric_reader(name).read(RunStub()) is None
    RunStub.client = None
    assert lookup.metric_reader(name).read(RunStub()) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_is_listed_for_both_decode_cells_and_no_other(name):
    entry = next(m for m in lookup.benchmark_json()["per_layer"]
                 if m["name"] == name)
    reader = lookup.metric_reader(name)
    assert entry["workloads"] == CELLS
    assert entry["source"] == reader.METRIC["source"] == "program_counter"
    assert entry["better"] == "lower"
    for key in ("unit", "layer", "moves"):
        assert entry[key] == reader.METRIC[key]
    for cell in CELLS:
        assert name in lookup.Cell(cell).metric_names(True)
        assert name not in lookup.Cell(cell).metric_names(False)
    assert name not in lookup.Cell("gpt2-medium.train").metric_names(True)


def test_benchmarks_host_phases_are_the_programs():
    from tpu_dist_nn.obs.trace import LOOP_HOST_PHASES

    assert sched_ring.HOST_PHASES == LOOP_HOST_PHASES
