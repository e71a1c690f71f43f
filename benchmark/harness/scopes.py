"""Device seconds under a `jax.named_scope`, out of a run's capture.

The compiler keeps an operation's scope path as its `tf_op` in the
capture; `jax.profiler.ProfileData` does not hand that out, and the
program's own `tools/trace_gaps.py:device_seconds_by_scope` reads it
from the file.  `scopes_of_trace` asks that reader with a family's
scopes and counts the capture's whole launches; `scope_ms` is what a
roofline reader divides by.  On a tree without the tool, or a capture
without such scopes, both give nothing.
"""

from __future__ import annotations

import os

from benchmark.harness import lookup, xplane


def scopes_of_trace(trace_dir: str, scopes: tuple) -> dict:
    """`{"scopes": {program: {scope: seconds}}, "launches": {program:
    n}}` over the whole capture (first device plane), or `{}`."""
    pb = xplane.find_xplane(trace_dir)
    tool = os.path.join(lookup.ROOT, "tools", "trace_gaps.py")
    if pb is None or not os.path.isfile(tool):
        return {}
    gaps = lookup.load_module(tool, "bench_trace_gaps")
    if not hasattr(gaps, "device_seconds_by_scope"):
        return {}
    gaps.SCOPES = tuple(scopes)
    launches: dict = {}
    for plane, lines in xplane.read_planes(pb):
        if plane.startswith("/device:") and "CUSTOM" not in plane.upper():
            for line, events in lines:
                if line == "XLA Modules":
                    for name, _, _ in events:
                        prog = xplane._program_name(name)
                        launches[prog] = launches.get(prog, 0) + 1
            break
    return {"scopes": gaps.device_seconds_by_scope(pb), "launches": launches}


def scope_ms(run, program: str, scope: str):
    """Device milliseconds under `scope` a launch of `program`, over
    the whole launches of the capture; None where it has none."""
    t = run.trace or {}
    seconds = (t.get("scopes") or {}).get(program, {}).get(scope)
    launches = (t.get("launches") or {}).get(program)
    if not seconds or not launches:
        return None
    return 1e3 * seconds / launches
