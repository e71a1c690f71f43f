"""`sparse_attend_roofline` (benchmark/metrics/sparse_attend_roofline.py)
off the chip: silent where no operation of the kernel was traced or the
scheduler's ring holds no chunk of the traced span, a hand-worked reading
on a made-up trace and ring at the cell's counts, the least time taken
over the chunks TRACED and not over a whole prompt's, and the cell that
lists it.  A file of its own: the tests that were here are not this PR's
to edit.
"""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, lookup  # noqa: E402
from benchmark.harness.counts import Gpt2Counts  # noqa: E402
from benchmark.harness.sala_counts import SalaCounts  # noqa: E402

NAME = "sparse_attend_roofline"
CELL = "minicpm-sala.longdoc-qa"
PEAKS = device.PEAKS["TPU v5 lite"]


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reader():
    return lookup.metric_reader(NAME)


T_OPEN, LEAD, SPAN = 1000.0, 1.0, 4.0  # the driver opens the trace 1 s in


def _ring(starts, size=2048, first_end=T_OPEN + LEAD + 0.1, every=0.08):
    """A scheduler's iteration ring: one record before the span, then an
    iteration with one chunk at each of `starts`, `every` seconds apart."""
    from tpu_dist_nn.obs.trace import ITER_FIELDS, IterationRing

    ring, totals = IterationRing(), dict.fromkeys(ITER_FIELDS, 0)
    totals.update(sched=7, t_end=T_OPEN)
    ring.append(tuple(totals[k] for k in ITER_FIELDS))
    for i, start in enumerate(starts):
        totals["seq"] += 1
        totals["t_end"] = first_end + i * every
        totals["prefilled"] = True
        totals["prefill_tokens"] += size
        totals["prefill_starts"] += start
        ring.append(tuple(totals[k] for k in ITER_FIELDS))
    return ring


def _run(ops, launches=32, counts=None, peaks=PEAKS):
    params = lookup.Cell(CELL).params
    trace = {"programs": {"jit_prefill_chunk": {
        "launches": launches, "device_s": 2.0}}, "ops": ops,
        "window_s": SPAN}
    return types.SimpleNamespace(
        trace=trace, peaks=peaks, params=params,
        client=types.SimpleNamespace(t_open=T_OPEN, t_close=T_OPEN + 51.0),
        args=types.SimpleNamespace(seconds=51.0),
        counts=counts or SalaCounts(_config("minicpm-sala"), params))


@pytest.fixture
def ring_of(monkeypatch):
    """Put a made-up ring in the program's place."""
    from tpu_dist_nn.obs import trace as trace_mod

    def plant(*a, **kw):
        monkeypatch.setattr(trace_mod, "ITERATIONS", _ring(*a, **kw))
    return plant


LOOP = [("jit_prefill_chunk/fusion.494_bf16_2048_16384_", 0.29),
        ("jit_prefill_chunk/fusion.502_f32_2_16_2048_128_", 0.18),
        ("jit_step/kv_write_rows.1", 0.05)]
KERNEL = [("jit_prefill_chunk/sparse_attend.1", 0.192),
          ("jit_prefill_chunk/sparse_attend.2", 0.192)]


@pytest.mark.parametrize("run", [
    _run(LOOP),                                        # the parent's trace
    _run([("jit_step/sparse_attend.1", 0.3)]),         # not in the chunk
    _run([("jit_prefill_chunk/sparse_attend.1", 0.3)], launches=0),
    _run([("jit_prefill_chunk/sparse_attend.1", 0.3)], peaks=None),
    _run([("jit_prefill_chunk/sparse_attend.1", 0.3)],
         counts=Gpt2Counts(_config("gpt2-medium"))),
    types.SimpleNamespace(trace=None, peaks=PEAKS, params={}, counts=None),
], ids=["no-kernel-op", "other-program", "no-launch", "off-chip",
        "gpt2-counts", "no-trace"])
def test_reader_is_silent_where_the_kernel_did_not_run(reader, ring_of, run):
    ring_of([30720] * 32)
    assert reader.read(run) is None


def test_reader_is_silent_where_the_ring_holds_no_traced_chunk(
        reader, ring_of, monkeypatch):
    from tpu_dist_nn.obs import trace as trace_mod

    ring_of([30720] * 32, first_end=T_OPEN + LEAD + SPAN + 1.0)  # all later
    assert reader.read(_run(LOOP + KERNEL)) is None
    ring_of([30720] * 32)
    assert reader.read(_run(LOOP + KERNEL)) is not None
    # A program whose ring lacks the two columns (the parent's).
    monkeypatch.setattr(trace_mod, "ITER_FIELDS", tuple(
        k for k in trace_mod.ITER_FIELDS if not k.startswith("prefill_")))
    assert reader.read(_run(LOOP + KERNEL)) is None


def test_reading_at_the_cells_counts_by_hand(reader, ring_of):
    """32 launches, the kernel's two operations (one a sparse layer)
    0.192 + 0.192 s: 12 ms a launch.  The ring says every traced chunk
    stood at position 15 (start 30 720): every query lies past dense_len
    and attends its first block, the 32 or 33 blocks of its window and
    64 more, so 64 x (1 + 64) + the window's keys; 4 x 32 x 128 x 2
    layers operations a key."""
    c = SalaCounts(_config("minicpm-sala"), {})
    keys, _ = c.attended(30720, 2048)
    assert c.per_key == 4 * 32 * 128 * 2 and c.Ls == 2
    assert 6150 < keys.mean() < 6300  # ~6 200 of up to 32 768 visible
    by_flops = c.per_key * int(keys.sum()) / 197e12
    by_bytes = 2 * 2 * (2 * 2048 * 4096 + 2 * 256 * 32768) / 819e9
    assert by_flops == pytest.approx(2.13e-3, rel=0.02)
    assert by_flops > by_bytes
    ring_of([30720] * 32)
    got = reader.read(_run(LOOP + KERNEL))
    assert got == pytest.approx(100 * by_flops / 12e-3)
    assert 17.0 < got < 18.5
    # Twice the kernel's time, half the share.
    slow = [(n, 2 * s) for n, s in KERNEL]
    assert reader.read(_run(LOOP + slow)) == pytest.approx(got / 2)


def test_least_time_is_of_the_chunks_traced_not_of_a_whole_prompt(
        reader, ring_of):
    """Which of a prompt's positions the traced span holds differs by
    run.  A span of first chunks (start 0: 2048 x 2049 / 2
    causal pairs, 0.35 ms at peak) over the 1.6 ms the kernel takes
    there reads 22 %; a prompt's mean least time (1.94 ms) over the same
    1.6 ms would read 121 %.  Chunks outside the span do not count."""
    fast = [(n, 32 * 0.8e-3) for n, _ in KERNEL]  # 1.6 ms a launch
    ring_of([0] * 32)
    early = reader.read(_run(fast))
    c = SalaCounts(_config("minicpm-sala"), {})
    assert early == pytest.approx(
        100 * c.per_key * (2048 * 2049 // 2) / 197e12 / 1.6e-3)
    assert 20.0 < early < 24.0
    # 50 iterations of 80 ms fill the 4 s; the late positions that
    # follow them lie outside and leave the reading where it was.
    ring_of([0] * 50 + [30720] * 30)
    assert reader.read(_run(fast)) == pytest.approx(early)
    # A span that holds positions 0 .. 15 once reads their mean.
    ring_of([2048 * i for i in range(16)])
    whole = reader.read(_run(KERNEL))
    assert whole == pytest.approx(100 * 1.94e-3 / 12e-3, rel=0.03)


def test_the_long_document_cell_lists_it_and_gpt2s_cells_do_not():
    bench = lookup.benchmark_json()
    entry = dict(next(m for m in bench["per_layer"] if m["name"] == NAME))
    assert CELL in entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "itl_p95_ms"}
    assert NAME in lookup.Cell(CELL).metric_names(True)
    assert NAME not in lookup.Cell(CELL).metric_names(False)
    for gpt2 in ("gpt2-medium.decode-sat", "gpt2-large.decode-sat",
                 "gpt2-medium.train"):
        assert NAME not in lookup.Cell(gpt2).metric_names(True)
