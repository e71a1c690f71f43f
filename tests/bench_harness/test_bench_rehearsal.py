"""Whole runs of the rehearsal cells on the CPU: the command line, the
last line's keys, the data-driven lookup, the control, and `correct`
coming out false with the timed path broken underneath.

Each test drives `benchmark/run.py`'s main() with `--rehearse 1`, which
skips only the look for a chip; the rest of a run is the real one.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import device, lookup  # noqa: E402


@pytest.fixture(autouse=True)
def keep_other_tests_arrays(monkeypatch):
    # A real run deletes every device array before the reference runs;
    # in a test process they may belong to other tests.
    monkeypatch.setattr(device, "free_device", lambda: None)


def _run(workload, seed=7, seconds=2.0, trace=0, control=None):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse", "1"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(argv) == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.strip()]
    return lines[-1], lines[:-1]


def _note(notes, key):
    return next(n[key] for n in notes if key in n)


def _check_last_line(last):
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "compared"
    assert last["device"]["platform"] == "cpu"
    # Off the chip nothing stands under a device metric's name.
    assert last["metrics"] and all(
        k.startswith("rehearsal.") for k in last["metrics"])
    for c in last["compared"].values():
        assert set(c) == {"value", "limit"}


# ------------------------------------------------------------ serving

def test_serve_rehearsal_is_correct_and_seeded_past_2_31():
    last, notes = _run("rehearsal-tiny.decode-sat", seed=2**31 + 11)
    _check_last_line(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {
        "rehearsal.out_tokens_per_s", "rehearsal.ttft_p50_ms",
        "rehearsal.itl_p95_ms", "rehearsal.setup_s"}
    split = _note(notes, "setup_split")
    assert {"start_s", "backend_s", "weights_s", "server_start_and_warm_s",
            "slot_fill_s"} <= set(split)
    # Process start to window open, the backend's own bring-up with it.
    setup = last["metrics"]["rehearsal.setup_s"]["value"]
    assert setup == pytest.approx(sum(split.values()), abs=0.5)


def test_serve_traced_run_prints_per_layer_metrics_and_breakdown():
    last, _ = _run("rehearsal-tiny.decode-sat", seconds=3.0, trace=1)
    _check_last_line(last)
    assert "rehearsal.slot_occupancy_pct" in last["metrics"]
    assert "rehearsal.out_tokens_per_s" not in last["metrics"]
    # No device plane on a CPU: readers of the trace return nothing, and
    # no share of a roofline or of a peak is ever printed as 0.
    assert "rehearsal.decode_step_roofline" not in last["metrics"]
    assert "rehearsal.serve_mfu_pct" not in last["metrics"]
    assert last["metrics"]["rehearsal.backend_start_s"]["value"] >= 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}


def test_serve_control_in_fp8_is_judged_not_correct():
    # At this size (CPU runs, 6 seeds): program 2.0e-5 to 3.2e-5, fp8
    # control 1.7e-3 to 2.9e-3; int8 reads 7e-5 to 1.8e-4 and is not told
    # apart, as at the real sizes.
    last, notes = _run("rehearsal-tiny.decode-sat", control="bf16,fp8")
    assert last["correct"] is True
    by = {n["control"]["precision"]: n["control"] for n in notes
          if "control" in n}
    # The program's own precision emulated really rounds (a pair of casts
    # XLA may drop), and passes; the control goes through the same limits.
    assert 0 < by["bf16"]["mean_gap"] and by["bf16"]["correct"] is True
    control = by["fp8"]
    assert control["correct"] is False
    held = control["compared"]["served_logit_gap_mean"]
    assert held["value"] == control["mean_gap"] > held["limit"]
    assert held["limit"] == \
        last["compared"]["served_logit_gap_mean"]["limit"]


def test_serve_open_loop_rehearsal_is_correct():
    """The mix that drives what the planned cells ask of the generator:
    Poisson arrivals, lognormal lengths, prompts sharing a prefix."""
    last, notes = _run("rehearsal-tiny.rehearsal-open", seconds=3.0, trace=1)
    _check_last_line(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 10
    # It prints the per-layer metrics of the listed cell it names.
    assert "rehearsal.ttft_p50_ms.layer" in last["metrics"]
    assert "rehearsal.ttft_p95_ms" not in last["metrics"]
    assert _note(notes, "window")["finished"] > 0


def test_serve_fault_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the decode step's sample
    for one slot, before the scheduler publishes it."""
    driver = lookup.Cell("rehearsal-tiny.decode-sat").driver()
    real = driver.start_server

    def broken(run_, params):
        server, port = real(run_, params)
        sched = server.scheduler
        step = sched._step
        vocab = int(run_.config["vocab_size"])

        def altered(*a):
            toks, ok, cache = step(*a)
            return (toks + 1) % vocab, ok, cache

        sched._step = altered
        return server, port

    monkeypatch.setattr(lookup.Cell, "driver", lambda self: driver)
    monkeypatch.setattr(driver, "start_server", broken)
    last, _ = _run("rehearsal-tiny.decode-sat")
    assert last["correct"] is False
    c = last["compared"]["served_logit_gap_mean"]
    assert c["value"] > c["limit"]


# ----------------------------------------------------------- training

def _patched_train_driver(monkeypatch, wrap):
    from tpu_dist_nn.train import lm_trainer

    real = lm_trainer.make_lm_train_step

    def make(cfg, optimizer, **kw):
        kw.pop("donate", None)
        return wrap(real(cfg, optimizer, donate=False, **kw))

    monkeypatch.setattr(lm_trainer, "make_lm_train_step", make)


def test_train_rehearsal_is_correct():
    last, notes = _run("rehearsal-tiny.train", seed=2**31 + 5)
    _check_last_line(last)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"rehearsal.train_tokens_per_s",
                                    "rehearsal.setup_s"}
    assert {"loss_gap", "grad_norm_gap", "change_norm_gap"} <= \
        set(last["compared"])
    # A key's bias has no gradient under softmax: left out by the rule.
    numbers = _note(notes, "numbers")
    assert any("b_qkv.k" in leaf for leaf in numbers["left_out"])


def test_train_control_and_faults_each_fail_a_number():
    last, notes = _run("rehearsal-tiny.train", control="fp8")
    limits = {k: c["limit"] for k, c in last["compared"].items()}
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        got = _note(notes, name)
        # Each goes through the limits that decide `correct`.
        assert got["correct"] is False, name
        assert any(got[k] > limits[k] for k in
                   ("loss_gap", "grad_norm_gap", "change_norm_gap")), name
    assert _note(notes, "fault_state_unchanged")["change_norm_gap"] == \
        pytest.approx(1.0)


def test_train_fault_state_unchanged_is_not_correct(monkeypatch):
    _patched_train_driver(
        monkeypatch,
        lambda step: lambda p, o, t: (p, o, step(p, o, t)[2]))
    last, _ = _run("rehearsal-tiny.train")
    assert last["correct"] is False
    assert last["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_fault_half_batch_is_not_correct(monkeypatch):
    _patched_train_driver(
        monkeypatch,
        lambda step: lambda p, o, t: step(p, o, t[: t.shape[0] // 2]))
    last, _ = _run("rehearsal-tiny.train")
    assert last["correct"] is False
    c = last["compared"]["grad_norm_gap"]
    assert c["value"] > c["limit"]


# ------------------------------------------------------------- lookup

def test_a_cell_is_found_by_name_and_nothing_else():
    cell = lookup.Cell("rehearsal-tiny.decode-sat")
    assert cell.listed is False and cell.config["n_embd"] == 64
    assert cell.params["slots"] == 4 and cell.params["prompt_len"] == 8
    assert cell.params["lengths"]["dist"] == "uniform"  # from the mix
    with pytest.raises(SystemExit):
        lookup.Cell("no-such.cell")


def test_a_rehearsal_prints_the_metrics_of_the_listed_cell_it_names():
    if not lookup.benchmark_json():
        pytest.skip("no BENCHMARK.json yet")
    cell = lookup.Cell("rehearsal-tiny.decode-sat")
    listed = lookup.Cell(cell.own["metrics_as"])
    for trace in (False, True):
        assert cell.metric_names(trace) == listed.metric_names(trace)
    cell.metrics_as = None  # a cell file that names none is refused
    with pytest.raises(SystemExit):
        cell.metric_names(False)


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    bench = lookup.benchmark_json()
    if not bench:
        pytest.skip("no BENCHMARK.json yet")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        meta = lookup.metric_reader(m["name"]).METRIC
        assert meta["name"] == m["name"] and meta["unit"] == m["unit"]
        if "moves" in m:
            assert m["moves"] in e2e and meta["layer"] == m["layer"]
    for w in bench["workloads"]:
        cell = lookup.Cell(w["name"])
        assert cell.listed and cell.own["config"] == w["config"]
        assert cell.metric_names(False) and cell.metric_names(True)


def test_weights_repeat_by_seed_and_differ_between_seeds():
    cell = lookup.Cell("rehearsal-tiny.train")
    a = cell.reference.make_weights(cell.config, 2**31 + 3)
    b = cell.reference.make_weights(cell.config, 2**31 + 3)
    c = cell.reference.make_weights(cell.config, 3)
    assert np.array_equal(a["blocks"]["w_qkv"], b["blocks"]["w_qkv"])
    assert not np.array_equal(a["blocks"]["w_qkv"], c["blocks"]["w_qkv"])
