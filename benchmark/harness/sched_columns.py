"""The window's growth of iteration-ring columns a program may lack.

The ring's columns grow at its end, PR by PR (`obs/trace.py`
`ITER_FIELDS`), and the benchmark's files run on the parent's program
too: a reader names the columns it reads, and reads nothing from a
program without them.
"""

from __future__ import annotations

from benchmark.harness.sched_ring import window_delta


def delta_with(run, *columns) -> dict | None:
    """`sched_ring.window_delta`, or None where the program's ring lacks
    one of `columns`."""
    d = window_delta(run)
    if d is None or any(c not in d for c in columns):
        return None
    return d
