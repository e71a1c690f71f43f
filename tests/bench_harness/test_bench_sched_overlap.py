"""`sched_overlap_pct` (benchmark/metrics/sched_overlap_pct.py) off the
chip: a hand-worked reading on a made-up iteration ring, silence where
the program's ring has no `steps_ahead` column (the parent commit) or no
ring at all, and the three serving cells that list it.  A file of its
own: the tests that were here are not this PR's to edit.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import lookup  # noqa: E402

NAME = "sched_overlap_pct"
CELLS = ["gpt2-medium.decode-sat", "gpt2-large.decode-sat",
         "minicpm-sala.longdoc-qa"]


@pytest.fixture(scope="module")
def reader():
    return lookup.metric_reader(NAME)


class _Client:
    t_open, t_close = 100.0, 200.0


class _Run:
    client = _Client()


def _ring(ahead, fields=None, t0=100.5, sched=7):
    """One record an iteration, 10 ms apart from `t0`; `ahead[i]` says
    whether iteration i launched its step while the one before it was
    unread.  `fields` stands for another program's columns."""
    from tpu_dist_nn.obs.trace import ITER_FIELDS, IterationRing

    fields = ITER_FIELDS if fields is None else fields
    ring, totals = IterationRing(), dict.fromkeys(fields, 0)
    totals["sched"] = sched
    for i, a in enumerate(ahead):
        totals["seq"] += 1
        totals["t_end"] = t0 + 0.01 * i
        if "steps_ahead" in totals:
            totals["steps_ahead"] += int(a)
        ring.append(tuple(totals[k] for k in fields))
    return ring


@pytest.mark.parametrize("ahead, want", [
    ([1] * 41, 100.0),               # every iteration of the window
    ([0] * 41, 0.0),                 # a serial loop that kept the column
    ([1, 0, 1, 1] * 10 + [1], 75.0),  # 30 of the 40 after the first record
])
def test_reading_is_the_share_of_iterations_launched_ahead(
        reader, monkeypatch, ahead, want):
    import tpu_dist_nn.obs.trace as trace_mod

    monkeypatch.setattr(trace_mod, "ITERATIONS", _ring(ahead))
    assert reader.read(_Run()) == pytest.approx(want)


def test_records_outside_the_window_and_other_schedulers_do_not_count(
        reader, monkeypatch):
    import tpu_dist_nn.obs.trace as trace_mod

    ring = _ring([0] * 30, t0=50.0)            # before the window: serial
    for r in _ring([1] * 21).snapshot():       # inside: every one ahead
        ring.append(r)
    for r in _ring([0] * 5, sched=8).snapshot():  # a smaller scheduler
        ring.append(r)
    monkeypatch.setattr(trace_mod, "ITERATIONS", ring)
    assert reader.read(_Run()) == pytest.approx(100.0)


def test_silent_on_a_program_whose_ring_has_no_such_column(
        reader, monkeypatch):
    """The parent commit: the ring and every other column, no
    `steps_ahead`.  Nothing to read, nothing raised; the other
    scheduler metrics still read it."""
    import tpu_dist_nn.obs.trace as trace_mod

    fields = tuple(f for f in trace_mod.ITER_FIELDS if f != "steps_ahead")
    monkeypatch.setattr(trace_mod, "ITER_FIELDS", fields)
    monkeypatch.setattr(trace_mod, "ITERATIONS",
                        _ring([1] * 41, fields=fields))
    assert reader.read(_Run()) is None
    assert lookup.metric_reader("sched_host_ms").read(_Run()) == 0.0


def test_silent_without_a_ring_a_window_or_two_records(reader, monkeypatch):
    import tpu_dist_nn.obs.trace as trace_mod

    monkeypatch.setattr(trace_mod, "ITERATIONS", _ring([1]))
    assert reader.read(_Run()) is None

    class NoClient:
        client = None

    assert reader.read(NoClient()) is None
    monkeypatch.delattr(trace_mod, "ITERATIONS")
    assert reader.read(_Run()) is None


def test_metric_is_listed_for_the_three_serving_cells_and_no_other(reader):
    entry = next(m for m in lookup.benchmark_json()["per_layer"]
                 if m["name"] == NAME)
    assert entry["workloads"] == CELLS
    assert entry["source"] == reader.METRIC["source"] == "program_counter"
    assert entry["better"] == "higher"
    assert entry["moves"] == reader.METRIC["moves"] == "itl_p95_ms"
    for key in ("unit", "layer"):
        assert entry[key] == reader.METRIC[key]
    assert lookup.benchmark_json()["per_layer"][-1] is not None
    for cell in CELLS:
        assert NAME in lookup.Cell(cell).metric_names(True)
        assert NAME not in lookup.Cell(cell).metric_names(False)
    assert NAME not in lookup.Cell("gpt2-medium.train").metric_names(True)


def test_the_programs_loop_counts_what_the_metric_reads():
    """The column against the scheduler's own counter, on stub kernels:
    one request of 12 tokens is 11 steps, all but the first launched
    while the one before was unread."""
    import numpy as np

    from tpu_dist_nn.obs.trace import ITER_FIELDS, ITERATIONS
    from tpu_dist_nn.serving.continuous import ContinuousScheduler

    sched = ContinuousScheduler(
        None, None, slots=2, prompt_len=4, max_new_tokens=12,
        prefill_fn=lambda p, c, s, t, st, k: (np.int32(1), c),
        step_fn=lambda p, c, pos, a, tok, k: (np.asarray(tok) + 1, c))
    try:
        out = sched.submit(np.zeros((1, 4), np.int32))
    finally:
        sched.close()
    assert list(out[0, 4:]) == list(range(1, 13))
    mine = [dict(zip(ITER_FIELDS, r)) for r in ITERATIONS.snapshot()
            if r[0] == sched.loop_totals()["sched"]]
    assert mine[-1]["steps_ahead"] == sched.overlapped_total == 10
    assert sched.steps_total == 11
