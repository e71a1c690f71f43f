"""The scheduler's own account of its loop, cut to the window.

The program keeps a process-global ring of iteration records
(`tpu_dist_nn.obs.trace.ITERATIONS`, columns `ITER_FIELDS`): one a loop
iteration, stamped with `time.monotonic()` at its end, every other
column cumulative.  It outlives the server, so a reader that runs after
the driver has stopped everything still finds it.  A window's mean of
anything is last-minus-first of the records that ended inside it.

A program without the ring (an older commit) gives every reader None.
"""

from __future__ import annotations

# What counts as host time between launches: every phase of the loop but
# `idle` (no work) and the two fetches (blocked on the device).
HOST_PHASES = ("reap", "admit", "bind", "prefill.dispatch", "prefill.post",
               "step.dispatch", "step.account", "step.publish")


def window_delta(run) -> dict | None:
    """Column -> growth between the first and the last iteration record
    that ended inside the window, of the scheduler that recorded most
    there; `iterations` is how many lie between the two.  None where
    fewer than two records fall inside."""
    try:
        from tpu_dist_nn.obs.trace import ITER_FIELDS, ITERATIONS
    except ImportError:
        return None
    if run.client is None:
        return None
    records = ITERATIONS.window(run.client.t_open, run.client.t_close)
    by_sched: dict = {}
    for r in records:
        by_sched.setdefault(r[ITER_FIELDS.index("sched")], []).append(r)
    records = max(by_sched.values(), key=len, default=[])
    if len(records) < 2:
        return None
    first = dict(zip(ITER_FIELDS, records[0]))
    last = dict(zip(ITER_FIELDS, records[-1]))
    delta = {k: last[k] - first[k] for k in ITER_FIELDS
             if k not in ("sched", "prefilled", "active_slots")}
    delta["iterations"] = delta.pop("seq")
    return delta


def per_iteration_ms(run, *phases):
    """Mean milliseconds a loop iteration spent in `phases` together."""
    d = window_delta(run)
    if d is None or not d["iterations"]:
        return None
    return sum(d[p] for p in phases) / d["iterations"] / 1e6


def per_event_ms(run, total: str, count: str):
    """Mean milliseconds an event: a nanosecond sum over its count."""
    d = window_delta(run)
    if d is None or not d[count]:
        return None
    return d[total] / d[count] / 1e6
