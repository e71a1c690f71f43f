"""tools/trace_gaps.py: device gaps put down to the scheduler's
`tdn.gen.*` spans, and device seconds by `named_scope`, on hand-made
planes and a hand-encoded XSpace (no chip, no profiler)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import trace_gaps  # noqa: E402

MS = 1e6  # ns


def _planes():
    """Two steps and a prefill on the device; the loop's phases on the
    host.  Times in ns, as `xplane.read_planes` gives them."""
    modules = [("jit_step(1)", 0 * MS, 100 * MS),
               ("jit_prefill_chunk(2)", 130 * MS, 2 * MS),
               ("jit_step(1)", 135 * MS, 100 * MS)]
    spans = [("tdn.gen.step.fetch", 1 * MS, 103 * MS),       # ends at 104
             ("tdn.gen.step.account", 104 * MS, 2 * MS),
             ("tdn.gen.step.publish", 106 * MS, 18 * MS),    # ends at 124
             ("tdn.gen.reap", 124 * MS, 1 * MS),
             ("tdn.gen.bind", 125 * MS, 1 * MS),
             ("tdn.gen.prefill.dispatch", 126 * MS, 5 * MS),  # ends at 131
             ("tdn.gen.prefill.fetch", 131 * MS, 2 * MS),    # ends at 133
             ("tdn.gen.step.dispatch", 133 * MS, 3 * MS),
             ("bench.window", 0, 300 * MS)]
    return [("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", [])]),
            ("/host:CPU", [("python3", spans)])]


def test_gaps_are_put_down_to_the_phases_that_cover_them():
    got = trace_gaps.attribute_gaps(_planes())
    assert got["idle_s"] == pytest.approx(0.033)
    assert got["attributed_s"] == pytest.approx(0.033)
    first = got["by_gap"]["jit_step->jit_prefill_chunk"]
    assert first["gaps"] == 1 and first["idle_s"] == pytest.approx(0.030)
    # The tail of the fetch (the copy back and the wake-up) is in the gap;
    # the part of it under the running program is not.
    assert first["step.fetch"] == pytest.approx(0.004)
    assert first["step.publish"] == pytest.approx(0.018)
    assert first["prefill.dispatch"] == pytest.approx(0.004)
    second = got["by_gap"]["jit_prefill_chunk->jit_step"]
    assert second["prefill.fetch"] == pytest.approx(0.001)
    assert second["step.dispatch"] == pytest.approx(0.002)
    assert "bench.window" not in got["by_phase"]
    assert max(got["by_phase"], key=got["by_phase"].get) == "step.publish"


def test_a_gap_says_how_long_a_handler_was_sending_under_it():
    """`tdn.stream.send` spans of three handler threads: the first gap
    (100 to 130 ms) has one from under the step to 110, two that overlap
    (105 to 112, 108 to 120) and one that it holds whole; overlapping
    sends count once. The second gap (132 to 135) lies wholly under one
    send, and a gap's sends are no phase of the loop."""
    planes = _planes()
    planes += [
        ("/host:CPU", [("handler-1", [("tdn.stream.send", 90 * MS, 20 * MS),
                                      ("tdn.stream.send", 131 * MS, 9 * MS)]),
                       ("handler-2", [("tdn.stream.send", 105 * MS, 7 * MS),
                                      ("tdn.stream.send", 125 * MS, 1 * MS)]),
                       ("handler-3", [("tdn.stream.send", 108 * MS, 12 * MS),
                                      ("decode", 100 * MS, 30 * MS)])])]
    got = trace_gaps.attribute_gaps(planes)
    first = got["by_gap"]["jit_step->jit_prefill_chunk"]
    assert first["under_send_s"] == pytest.approx(0.020 + 0.001)
    second = got["by_gap"]["jit_prefill_chunk->jit_step"]
    assert second["under_send_s"] == pytest.approx(0.003)
    assert got["under_send_s"] == pytest.approx(0.024)
    assert got["attributed_s"] == pytest.approx(0.033)  # as without them
    assert "stream.send" not in got["by_phase"]
    assert set(first) - set(got["by_phase"]) == {
        "gaps", "idle_s", "under_send_s"}


def test_without_a_send_span_no_gap_is_under_one():
    got = trace_gaps.attribute_gaps(_planes())
    assert got["under_send_s"] == 0
    assert all(g["under_send_s"] == 0 for g in got["by_gap"].values())


def test_a_gap_no_span_covers_stays_unattributed():
    planes = _planes()
    planes[1] = ("/host:CPU", [("python3", [])])
    got = trace_gaps.attribute_gaps(planes)
    assert got["idle_s"] == pytest.approx(0.033)
    assert got["attributed_s"] == 0 and got["by_phase"] == {}


@pytest.mark.parametrize("path, scope", [
    ("jit(step)/while/body/closed_call/kv.write/jit(_where)/select_n",
     "kv.write"),
    ("jit(step)/while/body/closed_call/attn.values/bhqk,bkhd->bqhd/"
     "dot_general", "attn.values"),
    ("jit(step)/sample/reduce", "sample"),
    ("jit(step)/while/body/dynamic_slice", "(no scope)"),
    ("", "(no scope)"),
])
def test_scope_of_an_operation_path(path, scope):
    assert trace_gaps.scope_of(path) == scope


# A hand-encoded XSpace: enough of tsl's xplane.proto for one device
# plane with one program and two operations under it.

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    stat_meta = _field(5, _entry(7, _field(1, 7) + _field(2, "tf_op")))

    def event_meta(key, name, tf_op=None):
        body = _field(1, key) + _field(2, name)
        if tf_op is not None:
            body += _field(5, _field(1, 7) + _field(5, tf_op))
        return _field(4, _entry(key, body))

    def event(meta, offset_ps, dur_ps):
        return _field(4, _field(1, meta) + _field(2, offset_ps)
                      + _field(3, dur_ps))

    metas = (event_meta(1, "jit_step(99)")
             + event_meta(2, "%while.1 = ...", "jit(step)/while")
             + event_meta(3, "%fusion.3 = ...",
                          "jit(step)/while/body/closed_call/kv.write/"
                          "jit(_where)/select_n")
             + event_meta(4, "%copy.35 = ..."))
    modules = _field(3, _field(2, "XLA Modules") + _field(3, 10)
                     + event(1, 0, 9_000_000))
    ops = _field(3, _field(2, "XLA Ops") + _field(3, 10)
                 + event(2, 0, 8_000_000)        # the loop: a container
                 + event(3, 1_000_000, 5_000_000)
                 + event(4, 6_000_000, 2_000_000))
    device = _field(1, 1) + _field(2, "/device:TPU:0") + modules + ops \
        + metas + stat_meta
    host = _field(1, 2) + _field(2, "/host:CPU")
    return _field(1, host) + _field(1, device)


def test_device_seconds_by_scope_from_event_metadata(tmp_path):
    pb = tmp_path / "x.xplane.pb"
    pb.write_bytes(_xspace())
    got = trace_gaps.device_seconds_by_scope(str(pb))
    assert set(got) == {"jit_step"}
    # The `while` spans its body and is not counted beside it.
    assert got["jit_step"]["kv.write"] == pytest.approx(5e-6)
    assert got["jit_step"]["(no scope)"] == pytest.approx(2e-6)


def test_open_capture_finds_the_plane_file_in_a_profile_zip(tmp_path):
    import zipfile

    z = tmp_path / "profile.zip"
    with zipfile.ZipFile(z, "w") as f:
        f.writestr("plugins/profile/2026_01_01/host.xplane.pb", _xspace())
        f.writestr("plugins/profile/2026_01_01/host.trace.json.gz", b"")
    inner = trace_gaps.open_capture(str(z))
    assert inner.endswith("host.xplane.pb")
    assert trace_gaps.device_seconds_by_scope(inner)
    assert trace_gaps.open_capture("a.xplane.pb") == "a.xplane.pb"
