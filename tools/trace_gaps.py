"""Say what the host was doing in each gap between device programs.

    python3 tools/trace_gaps.py <capture>      # .xplane.pb, or the zip
                                               # GET /debug/profile returns

The generation scheduler's loop (serving/continuous.py) writes each of
its phases into the host plane of any `jax.profiler` capture as a
`tdn.gen.<phase>` span, on the clock the device operations use.  This
tool reads one capture and prints two tables:

* every second in which the device ran no program, between two launches,
  put down to the loop phase(s) that cover it (a gap spans the tail of
  the fetch that waited for the earlier program, the host phases, and
  the head of the dispatch of the later one), and the part of it during
  which at least one handler thread was sending a frame
  (`tdn.stream.send`, serving/server.py: handlers busy on the loop's
  interpreter while the device waits);
* device seconds by `jax.named_scope` of the step programs
  (models/generate.py: `kv.write`, `attn.scores`, ...), from the name
  the compiler kept for each operation.

It reads the planes with the benchmark's reader
(benchmark/harness/xplane.py) and changes nothing of the benchmark.
"""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
import zipfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SPAN_PREFIX = "tdn.gen."
SEND_SPAN = "tdn.stream.send"
# The scopes models/generate.py and the scheduler's kernels name; an
# operation belongs to the innermost one on its path.
SCOPES = ("params.cast", "embed", "kv.write", "attn.scores", "attn.softmax",
          "attn.values", "ffn", "unembed", "sample", "guard")


def _program(event_name: str) -> str:
    return re.sub(r"\(.*\)$", "", event_name).strip()


def attribute_gaps(planes) -> dict:
    """planes as `xplane.read_planes` gives them.  Returns
    {"idle_s", "attributed_s", "under_send_s", "by_phase": {phase: s},
     "by_gap": {"<program>-><program>": {"gaps", "idle_s",
                                         "under_send_s", phase: s}}}
    over the first device plane that holds programs.  `under_send_s` is
    the part of the idle time during which at least one
    `tdn.stream.send` span was open (overlapping sends count once)."""
    modules, spans, sends = [], [], []
    for plane_name, lines in planes:
        device = plane_name.startswith("/device:")
        for line_name, events in lines:
            if device and line_name == "XLA Modules" and not modules:
                modules = sorted((s, s + d, _program(n)) for n, s, d in events)
            elif not device:
                spans += [(s, s + d, n[len(SPAN_PREFIX):])
                          for n, s, d in events if n.startswith(SPAN_PREFIX)]
                sends += [(s, s + d) for n, s, d in events if n == SEND_SPAN]
    spans.sort()
    # The sends of every handler thread as one set of disjoint intervals.
    busy: list[list] = []
    for s, e in sorted(sends):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    by_phase: dict[str, float] = {}
    by_gap: dict[str, dict] = {}
    idle = attributed = under_send = 0.0
    at = at_send = 0  # spans are in time order, and so are the gaps
    for (_, a_end, a), (b_start, _, b) in zip(modules, modules[1:]):
        if b_start <= a_end:
            continue
        gap = by_gap.setdefault(
            f"{a}->{b}", {"gaps": 0, "idle_s": 0.0, "under_send_s": 0.0})
        gap["gaps"] += 1
        gap["idle_s"] += (b_start - a_end) / 1e9
        idle += (b_start - a_end) / 1e9
        while at_send < len(busy) and busy[at_send][1] <= a_end:
            at_send += 1
        k = at_send
        while k < len(busy) and busy[k][0] < b_start:
            part = (min(busy[k][1], b_start) - max(busy[k][0], a_end)) / 1e9
            gap["under_send_s"] += part
            under_send += part
            k += 1
        while at < len(spans) and spans[at][1] <= a_end:
            at += 1
        for s, e, phase in spans[at:]:
            if s >= b_start:
                break
            part = (min(e, b_start) - max(s, a_end)) / 1e9
            if part <= 0:
                continue
            gap[phase] = gap.get(phase, 0.0) + part
            by_phase[phase] = by_phase.get(phase, 0.0) + part
            attributed += part
    return {"idle_s": idle, "attributed_s": attributed,
            "under_send_s": under_send, "by_phase": by_phase,
            "by_gap": by_gap}


def scope_of(op_path: str) -> str:
    """`jit(step)/while/body/closed_call/kv.write/jit(_where)/select_n`
    -> `kv.write`: the innermost named scope on an operation's path."""
    found = [part for part in op_path.split("/") if part in SCOPES]
    return found[-1] if found else "(no scope)"


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _map_entry(buf):
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def device_seconds_by_scope(path: str) -> dict:
    """{program: {scope: seconds}} over the leaf operations of the first
    device plane.  An operation's path (`jit(step)/while/body/.../
    kv.write/select_n`, what the compiler kept of `jax.named_scope`) is
    the `tf_op` stat of its event METADATA, which `ProfileData` does not
    hand out, so the file (tsl's XSpace protobuf) is read field by
    field: XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4
    .stat_metadata=5; XLine.name=2 .timestamp_ns=3 .events=4;
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3;
    XEventMetadata.name=2 .stats=5; XStat.metadata_id=1 .str_value=5
    .ref_value=7; XStatMetadata.name=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        parts: dict[int, list] = {}
        for k, v in _fields(plane):
            parts.setdefault(k, []).append(v)
        name = bytes(parts.get(2, [b""])[0]).decode()
        if not name.startswith("/device:") or "CUSTOM" in name.upper():
            continue
        stat_names = {}
        for raw in parts.get(5, ()):
            key, meta = _map_entry(raw)
            stat_names[key] = bytes(dict(_fields(meta)).get(2, b"")).decode()
        op_path, op_name = {}, {}
        for raw in parts.get(4, ()):
            key, meta = _map_entry(raw)
            for k, v in _fields(meta):
                if k == 2:
                    op_name[key] = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    op_path[key] = bytes(stat[5]).decode() if 5 in stat \
                        else stat_names.get(stat.get(7), "")
        lines = {}
        for raw in parts.get(3, ()):
            line: dict[int, list] = {}
            for k, v in _fields(raw):
                line.setdefault(k, []).append(v)
            t0 = line.get(3, [0])[0] * 1000  # ns -> ps
            events = []
            for ev in line.get(4, ()):
                e = dict(_fields(ev))
                start = t0 + e.get(2, 0)
                events.append((start, start + e.get(3, 0), e.get(1, 0)))
            lines[bytes(line.get(2, [b""])[0]).decode()] = events
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        modules = sorted((s, e, _program(op_name.get(m, "?")))
                         for s, e, m in lines["XLA Modules"])
        ops = sorted(lines["XLA Ops"], key=lambda o: (o[0], -o[1]))
        out: dict[str, dict] = {}
        at = 0
        for i, (s, e, meta) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < e:
                continue  # a `while` or a call: its body is listed itself
            while at < len(modules) and modules[at][1] <= s:
                at += 1
            prog = modules[at][2] if at < len(modules) \
                and modules[at][0] <= s else "?"
            per = out.setdefault(prog, {})
            scope = scope_of(op_path.get(meta, ""))
            per[scope] = per.get(scope, 0.0) + (e - s) / 1e12
        return out
    return {}


def open_capture(path: str) -> str:
    """The .xplane.pb itself, or the one inside a /debug/profile zip."""
    if not path.endswith(".zip"):
        return path
    tmp = tempfile.mkdtemp(prefix="trace_gaps_")
    with zipfile.ZipFile(path) as z:
        names = [n for n in z.namelist() if n.endswith(".xplane.pb")]
        if not names:
            raise SystemExit(f"{path}: no .xplane.pb inside")
        return z.extract(names[-1], tmp)


def main(path: str) -> int:
    from benchmark.harness import xplane

    pb = open_capture(path)
    got = attribute_gaps(xplane.read_planes(pb))
    idle = got["idle_s"]
    print(f"idle between programs: {idle:.4f} s, "
          f"{100 * got['attributed_s'] / idle if idle else 0:.1f} % of it "
          "under a tdn.gen.* span, "
          f"{100 * got['under_send_s'] / idle if idle else 0:.1f} % while "
          f"a handler was sending ({SEND_SPAN})")
    for phase, s in sorted(got["by_phase"].items(), key=lambda kv: -kv[1]):
        print(f"  {phase:<18}{s:9.4f} s {100 * s / idle:6.1f} %")
    for label, gap in sorted(got["by_gap"].items(),
                             key=lambda kv: -kv[1]["idle_s"]):
        phases = {k: v for k, v in gap.items()
                  if k not in ("gaps", "idle_s", "under_send_s")}
        top = ", ".join(f"{k} {1e3 * v / gap['gaps']:.2f}" for k, v in
                        sorted(phases.items(), key=lambda kv: -kv[1])[:6])
        print(f"gap {label}: {gap['gaps']} gaps, {gap['idle_s']:.4f} s, "
              f"{1e3 * gap['idle_s'] / gap['gaps']:.2f} ms each, "
              f"{100 * gap['under_send_s'] / gap['idle_s']:.1f} % under a "
              f"send; ms a gap by phase: {top}")
    scopes = device_seconds_by_scope(pb)
    for prog, per in sorted(scopes.items()):
        total = sum(per.values())
        print(f"device seconds of {prog} by named_scope ({total:.4f} s):")
        for scope, s in sorted(per.items(), key=lambda kv: -kv[1]):
            print(f"  {scope:<14}{s:9.4f} s {100 * s / total:6.1f} %")
    print(json.dumps({"gaps": got, "scopes": scopes}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    sys.exit(main(sys.argv[1]))
