"""Reduce a profiler trace (.xplane.pb) to what the metrics read.

Reads the file with `jax.profiler.ProfileData` alone.  On a TPU the
device plane is "/device:TPU:<n>"; its line "XLA Modules" holds one
event per launch of a jitted program (`jit_step(...)`), and "XLA Ops"
one per operation.  Host planes hold the `TraceAnnotation` spans that
the benchmark writes around its window.
"""

from __future__ import annotations

import glob
import os
import re


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def union_seconds(intervals) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _program_name(event_name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def _op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[1,2]{...} fusion(...)` -> `fusion.12_bf16_1_2_`."""
    m = re.match(r"%?([\w.\-]+)(?:\s*=\s*(\w+\[[\d,]*\]))?", event_name)
    if not m:
        return event_name[:60]
    name = m.group(1)
    if m.group(2):
        name += "_" + re.sub(r"[\[\],]", "_", m.group(2))
    return name


def _leaves(events):
    """Events that contain no other: a `while` or a call spans the
    operations of its body, which are listed themselves."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def reduce_planes(planes, span_name: str | None = None) -> dict:
    """planes: iterable of (plane_name, [(line_name, [(name, start_ns,
    dur_ns), ...]), ...]).  Returns seconds:

    window_s, busy_s (mean over device planes of the union of device-op
    intervals inside the window), programs {name: {launches, device_s}},
    ops [(name, seconds)] largest first, gaps [(label, seconds)] longest
    first, labelled by the host span and the programs either side.
    The window is the host span named `span_name` where it was written,
    else from the first to the last device event.
    """
    device, spans = [], []
    for plane_name, lines in planes:
        is_dev = plane_name.startswith("/device:") and \
            "CUSTOM" not in plane_name.upper()
        for line_name, events in lines:
            if is_dev and line_name == "XLA Modules":
                device.append((plane_name, "modules", events))
            elif is_dev and line_name == "XLA Ops":
                device.append((plane_name, "ops", events))
            elif not is_dev and span_name is not None:
                spans += [e for e in events if e[0] == span_name]
    modules = [e for _, k, ev in device if k == "modules" for e in ev]
    n_dev = len({p for p, _, _ in device}) or 1
    if spans:
        w0 = min(s for _, s, _ in spans)
        w1 = max(s + d for _, s, d in spans)
    elif modules:
        w0 = min(s for _, s, _ in modules)
        w1 = max(s + d for _, s, d in modules)
    else:
        return {"window_s": 0.0, "busy_s": 0.0, "programs": {}, "ops": [],
                "gaps": [], "devices": 0}

    def clip(events):
        return [(n, max(s, w0), min(s + d, w1)) for n, s, d in events
                if s + d > w0 and s < w1]

    programs: dict[str, dict] = {}
    ops: dict[str, float] = {}
    busy_ns = 0.0
    gaps: dict[str, float] = {}
    label = span_name or "trace"
    for plane in sorted({p for p, _, _ in device}):
        mods = clip([e for p, k, ev in device
                     if p == plane and k == "modules" for e in ev])
        opev = clip([e for p, k, ev in device
                     if p == plane and k == "ops" for e in ev])
        # Busy is the union of operations; where a trace has no op line,
        # of the programs.
        busy_ns += union_seconds([(s, e) for _, s, e in (opev or mods)])
        for n, s, e in mods:
            prog = programs.setdefault(
                _program_name(n), {"launches": 0, "device_s": 0.0})
            prog["launches"] += 1
            prog["device_s"] += (e - s) / 1e9
        owner = sorted(mods, key=lambda m: m[1])
        for n, s, e in _leaves(opev):
            prog = next((_program_name(m[0]) for m in owner
                         if m[1] <= s < m[2]), "?")
            key = f"{prog}/{_op_name(n)}"
            ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
        for a, b in zip(owner, owner[1:]):
            if b[1] > a[2]:
                key = (f"{label}:{_program_name(a[0])}-"
                       f"_{_program_name(b[0])}")
                gaps[key] = gaps.get(key, 0.0) + (b[1] - a[2]) / 1e9
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9 / n_dev,
        "programs": programs,
        "ops": sorted(ops.items(), key=lambda kv: -kv[1]),
        "gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
        "devices": n_dev,
    }


def read_planes(path: str):
    """The planes of an .xplane.pb as plain tuples (see reduce_planes)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        keep_host = plane.name.startswith("/host:")
        if not (plane.name.startswith("/device:") or keep_host):
            continue
        lines = []
        for line in plane.lines:
            if plane.name.startswith("/device:") and \
                    line.name not in ("XLA Modules", "XLA Ops"):
                continue
            lines.append((line.name, [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def reduce_trace(trace_dir: str, span_name: str | None = None) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(read_planes(path), span_name)
