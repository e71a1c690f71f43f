"""The readers ISSUE 35 adds over the scheduler's iteration ring, off the
chip: who has the interpreter (`sched_cpu_pct`, `server_cpu_pct`,
`sched_publish_offcpu_pct`), a frame's send (`stream_send_ms`,
`stream_frames_per_s`), the idle time the loop owns up to
(`sched_starved_pct`) and the scheduler's own counters
(`discarded_lane_pct`, `attend_kernel_chunk_pct`, `kv_skipped_tile_pct`).

Each against a hand-made ring (the pattern of
`test_bench_sched_overlap.py`), silent on a program without its columns
(the parent commit lacks all but those of `sched_cpu_pct` and
`stream_frames_per_s`: `harness/sched_columns.py`), listed for the cells ISSUE 35 names and no other,
and printed by one traced rehearsal.  A file of its own: the tests that
were here are not this PR's to edit.
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
from benchmark.harness import device, lookup, sched_columns  # noqa: E402

FOUR = ["gpt2-medium.decode-sat", "gpt2-large.decode-sat",
        "minicpm-sala.longdoc-qa", "phi4-mini-flash.reason-decode"]
SCHED, KERNELS = "generation scheduler and slot cache", "kernels"
MS = 1_000_000  # ns

# name: (what one iteration of 10 ms adds to the ring's columns, the
# reading that gives, a column the reader is silent without, `better`,
# layer, unit, cells).
READERS = {
    "sched_starved_pct": (
        {"starved_ns": 2 * MS}, 20.0, "starved_ns", "lower", SCHED, "%", FOUR),
    "sched_publish_offcpu_pct": (
        {"step.publish": 4 * MS, "cpu.publish": 1 * MS}, 75.0,
        "cpu.publish", "lower", SCHED, "%", FOUR),
    "sched_cpu_pct": (
        {"cpu_ns": 2.5 * MS, "proc_cpu_ns": 12 * MS}, 25.0, "cpu_ns",
        "lower", SCHED, "%", FOUR),
    "server_cpu_pct": (
        {"cpu_ns": 2.5 * MS, "proc_cpu_ns": 12 * MS}, 120.0, "proc_cpu_ns",
        "lower", SCHED, "%", FOUR),
    "stream_send_ms": (
        {"stream_send_ns": 6 * MS, "stream_sends": 4}, 1.5, "stream_send_ns",
        "lower", SCHED, "ms", FOUR),
    "stream_frames_per_s": (
        {"stream_frames": 5}, 500.0, "stream_frames", "higher", SCHED,
        "1/s", FOUR),
    "discarded_lane_pct": (
        {"discarded_lanes": 1, "slot_steps": 49}, 2.0, "discarded_lanes",
        "lower", SCHED, "%", FOUR),
    "attend_kernel_chunk_pct": (
        {"attend_kernel_chunks": 3, "prefill_chunks": 4}, 75.0,
        "attend_kernel_chunks", "higher", KERNELS, "%",
        ["minicpm-sala.longdoc-qa"]),
    "kv_skipped_tile_pct": (
        {"kv_tiles_visited": 39, "kv_tiles_skipped": 11}, 22.0,
        "kv_tiles_skipped", "higher", KERNELS, "%",
        ["phi4-mini-flash.reason-decode"]),
}
# The two whose columns PR 24's ring already has: the parent reads them.
PARENT_READS = ("sched_cpu_pct", "stream_frames_per_s")


class _Client:
    t_open, t_close = 100.0, 200.0


class _Run:
    client = _Client()
    trace = None


def _traced(busy_s=2.4, window_s=4.0):
    class Traced(_Run):
        trace = {"busy_s": busy_s, "window_s": window_s, "programs": {}}

    return Traced()


def _ring(adds, n=41, fields=None, t0=100.5, sched=7, captured=()):
    """`n` records 10 ms apart from `t0`, each iteration adding `adds`
    to the cumulative columns; the records whose index is in `captured`
    were written while a capture ran.  `fields` stands for another
    program's columns."""
    from tpu_dist_nn.obs.trace import ITER_FIELDS, IterationRing

    fields = ITER_FIELDS if fields is None else fields
    ring, totals = IterationRing(), dict.fromkeys(fields, 0)
    totals["sched"] = sched
    for i in range(n):
        totals["seq"] += 1
        totals["t_end"] = t0 + 0.01 * i
        for column, add in adds.items():
            if column in totals:
                totals[column] += add
        if "captured" in totals and i in captured:
            totals["captured"] += 1
        ring.append(tuple(totals[k] for k in fields))
    return ring


def _use(monkeypatch, ring, fields=None):
    import tpu_dist_nn.obs.trace as trace_mod

    if fields is not None:
        monkeypatch.setattr(trace_mod, "ITER_FIELDS", fields)
    monkeypatch.setattr(trace_mod, "ITERATIONS", ring)


# ------------------------------------------------- the window's readers

@pytest.mark.parametrize("name", list(READERS))
def test_reading_is_the_columns_growth_over_the_window(monkeypatch, name):
    adds, want = READERS[name][:2]
    _use(monkeypatch, _ring(adds))
    assert lookup.metric_reader(name).read(_Run()) == pytest.approx(want)


@pytest.mark.parametrize("name", list(READERS))
def test_records_outside_the_window_and_other_schedulers_do_not_count(
        monkeypatch, name):
    adds, want = READERS[name][:2]
    ring = _ring({k: 7 * v for k, v in adds.items()}, n=30,
                 t0=50.0)                          # before the window
    for r in _ring(adds).snapshot():               # inside it
        ring.append(r)
    for r in _ring({}, n=5, sched=8).snapshot():   # a smaller scheduler
        ring.append(r)
    _use(monkeypatch, ring)
    assert lookup.metric_reader(name).read(_Run()) == pytest.approx(want)


@pytest.mark.parametrize("name", list(READERS))
def test_silent_on_a_program_whose_ring_has_no_such_column(
        monkeypatch, name):
    """The parent commit: the ring and its older columns, none of
    ISSUE 35's.  Nothing to read, nothing raised, but where the reader's
    columns are PR 24's; PR 24's readers still read it."""
    import tpu_dist_nn.obs.trace as trace_mod

    adds, want, gate = READERS[name][:3]
    at = trace_mod.ITER_FIELDS.index("steps_ahead") + 1
    parents = trace_mod.ITER_FIELDS[:at]
    for fields in (parents,
                   tuple(f for f in trace_mod.ITER_FIELDS if f != gate)):
        with monkeypatch.context() as mp:
            _use(mp, _ring(adds, fields=fields), fields)
            got = lookup.metric_reader(name).read(_Run())
            if fields is parents and name in PARENT_READS:
                assert got == pytest.approx(want)
            else:
                assert got is None
            assert lookup.metric_reader("sched_publish_ms").read(_Run()) \
                is not None


@pytest.mark.parametrize("name", list(READERS))
def test_silent_without_a_ring_a_window_or_two_records(monkeypatch, name):
    import tpu_dist_nn.obs.trace as trace_mod

    reader = lookup.metric_reader(name)
    _use(monkeypatch, _ring(READERS[name][0], n=1))
    assert reader.read(_traced()) is None

    class NoClient:
        client, trace = None, _traced().trace

    assert reader.read(NoClient()) is None
    monkeypatch.delattr(trace_mod, "ITERATIONS")
    assert reader.read(_traced()) is None


@pytest.mark.parametrize("name", ["discarded_lane_pct",
                                  "attend_kernel_chunk_pct",
                                  "kv_skipped_tile_pct", "stream_send_ms",
                                  "sched_publish_offcpu_pct"])
def test_a_share_of_nothing_is_not_a_reading(monkeypatch, name):
    """No lane computed, no chunk launched, no tile counted (a model
    whose step stops at no frontier), no frame sent, no time in the
    phase: the column is there and the reader still has nothing."""
    _use(monkeypatch, _ring({}))
    assert lookup.metric_reader(name).read(_Run()) is None


# ----------------------------------------- columns a program may lack

def test_delta_with_is_the_windows_growth_or_nothing(monkeypatch):
    _use(monkeypatch, _ring({"starved_ns": 2 * MS}, captured=range(10, 31)))
    d = sched_columns.delta_with(_Run(), "captured", "starved_ns")
    assert d["captured"] == 21 and d["starved_ns"] == 80 * MS
    assert d["iterations"] == 40
    assert sched_columns.delta_with(_Run(), "captured", "no_such") is None


# ------------------------------------------------------ BENCHMARK.json

@pytest.mark.parametrize("name", list(READERS))
def test_entry_agrees_with_its_reader_and_lists_the_issues_cells(name):
    better, layer, unit, cells = READERS[name][3:]
    bench = lookup.benchmark_json()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    meta = lookup.metric_reader(name).METRIC
    assert meta["name"] == name
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "layer", "moves", "source"):
        assert entry[key] == meta[key], key
    assert (entry["unit"], entry["layer"], entry["better"]) == (
        unit, layer, better)
    assert entry["moves"] == "itl_p95_ms"
    assert entry["source"] == "program_counter"
    assert entry["workloads"] == cells
    for w in bench["workloads"]:
        cell = lookup.Cell(w["name"])
        assert (name in cell.metric_names(True)) == (w["name"] in cells)
        assert name not in cell.metric_names(False)


def test_the_new_entries_are_appended_and_every_listed_cell_reports_itl():
    bench = lookup.benchmark_json()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(READERS):] == list(READERS)
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert set(FOUR) <= set(itl["workloads"])
    # The cell whose traced names an accepted test pins gets none.
    assert not set(READERS) & set(
        lookup.Cell("kimi-k2.7-code.repo-decode").metric_names(True))


# ------------------------------------------------- one traced rehearsal

@pytest.fixture(scope="module")
def traced():
    """(metrics of the last line, the window note) of one traced run."""
    with pytest.MonkeyPatch.context() as mp:
        # A real run deletes every device array before the reference
        # runs; in a test process they may belong to other tests.
        mp.setattr(device, "free_device", lambda: None)
        out = io.StringIO()
        with redirect_stdout(out):
            assert bench_run.main([
                "--workload", "rehearsal-tiny.decode-sat", "--seed",
                str(2**31 + 35), "--seconds", "3", "--trace", "1",
                "--rehearse", "1"]) == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.strip()]
    assert lines[-1]["correct"] is True
    return (lines[-1]["metrics"],
            next(n["window"] for n in lines if "window" in n))


@pytest.mark.parametrize("name", [
    "sched_starved_pct", "sched_publish_offcpu_pct", "sched_cpu_pct",
    "server_cpu_pct",
    "stream_send_ms", "stream_frames_per_s", "discarded_lane_pct"])
def test_traced_rehearsal_prints_the_metric(traced, name):
    metrics, _ = traced
    got = metrics["rehearsal." + name]
    assert got["unit"] == READERS[name][5]
    if name.endswith("offcpu_pct") or name == "sched_starved_pct":
        assert 0 <= got["value"] <= 100
    else:
        assert got["value"] >= 0


def test_the_rehearsals_readings_hang_together(traced):
    metrics, _ = traced
    value = lambda n: metrics["rehearsal." + n]["value"]  # noqa: E731
    # The loop thread is one of the process's threads.
    assert 0 < value("sched_cpu_pct") <= value("server_cpu_pct")
    # A frame carries at least one token, and the handlers took every
    # token the window's steps published.
    assert 0 < value("stream_frames_per_s")
    assert value("stream_send_ms") > 0
    # The toy model has no attention kernel and no frontier.
    for silent in ("attend_kernel_chunk_pct", "kv_skipped_tile_pct"):
        assert "rehearsal." + silent not in metrics


def test_the_capture_held_about_the_traced_second_of_records(traced):
    """`captured` cuts the traced iterations out of the ring on the
    ring's own clock: the rehearsal traces 1 s (the cell's
    `trace_seconds`) of its 3 s window, and the loop, stalled while the
    profiler stops on a CPU, records nothing more under it."""
    from tpu_dist_nn.obs.trace import ITER_FIELDS, ITERATIONS

    _, window = traced

    records = [dict(zip(ITER_FIELDS, r)) for r in
               ITERATIONS.window(window["open"], window["close"])]
    held = [b for a, b in zip(records, records[1:])
            if b["captured"] > a["captured"]]
    # One run of neighbours, each a recorded iteration under the capture.
    assert held[-1]["seq"] - held[0]["seq"] == len(held) - 1 > 50
    assert held[-1]["captured"] - held[0]["captured"] == len(held) - 1
    assert 0.8 <= held[-1]["t_end"] - held[0]["t_end"] <= 2.5
    # start_trace comes min(1, seconds / 4) into the window.
    assert 0.7 <= held[0]["t_end"] - window["open"] <= 2.0
