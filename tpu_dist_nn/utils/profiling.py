"""Tracing and latency profiling.

The reference measures everything with bare ``time.monotonic()`` spans
around RPCs (``run_grpc_inference.py:71,89,139-148``) and never records
the results (SURVEY.md §6). This module keeps those wall-clock counters
as a first-class object (:class:`LatencyStats` — the source of the
BASELINE "p50 per-stage pipeline step latency" metric) and adds what the
reference could not have: XLA device-level traces via ``jax.profiler``
(:func:`capture_trace`) and named sub-spans inside compiled programs
(:func:`annotate`), viewable in TensorBoard/Perfetto.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Iterator

import jax
import numpy as np


@dataclasses.dataclass
class LatencyStats:
    """Wall-clock samples with percentile summaries.

    The structured replacement for the reference's printed per-batch
    seconds (``run_grpc_inference.py:195,211,213-215``).

    ``window`` bounds the retained samples to the most recent N (a
    sliding window): a long-lived serving process can record spans
    forever without the sample list growing without limit, at the cost
    of percentiles covering the window rather than all time.
    ``summary()`` reports the cap so a windowed p99 is never mistaken
    for an all-time one. ``None`` (the default) keeps everything — the
    bounded-run behavior existing callers rely on.
    """

    name: str = "latency"
    samples_s: list[float] = dataclasses.field(default_factory=list)
    window: int | None = None

    def __post_init__(self) -> None:
        if self.window is not None:
            if self.window < 1:
                raise ValueError(
                    f"{self.name}: window must be >= 1, got {self.window}"
                )
            # A deque with maxlen IS the sliding window: append is O(1)
            # and eviction is automatic. Everything downstream only
            # iterates (np.asarray, sum, len), so the container swap is
            # invisible to summary()/percentile() callers.
            self.samples_s = collections.deque(
                self.samples_s, maxlen=self.window
            )

    def record(self, seconds: float) -> None:
        self.samples_s.append(float(seconds))

    @contextlib.contextmanager
    def time(self) -> Iterator[None]:
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.record(time.monotonic() - t0)

    def __len__(self) -> int:
        return len(self.samples_s)

    @property
    def total_s(self) -> float:
        return float(sum(self.samples_s))

    def percentile(self, q: float) -> float:
        if not self.samples_s:
            raise ValueError(f"{self.name}: no samples recorded")
        return float(np.percentile(np.asarray(self.samples_s), q))

    def summary(self) -> dict:
        """p50/p90/p99/mean/min/max/total over the recorded spans.

        When a ``window`` cap is configured the summary includes it —
        the numbers then cover (at most) the last ``window`` spans.
        """
        if not self.samples_s:
            base = {"name": self.name, "count": 0}
            if self.window is not None:
                base["window"] = self.window
            return base
        arr = np.asarray(self.samples_s)
        return {
            "name": self.name,
            **({"window": self.window} if self.window is not None else {}),
            "count": int(arr.size),
            "total_s": float(arr.sum()),
            "mean_s": float(arr.mean()),
            "min_s": float(arr.min()),
            "max_s": float(arr.max()),
            "p50_s": float(np.percentile(arr, 50)),
            "p90_s": float(np.percentile(arr, 90)),
            "p99_s": float(np.percentile(arr, 99)),
        }


def annotate(name: str):
    """Named sub-span usable both inside and outside compiled code.

    Inside a traced function this lowers to an XLA ``named_scope`` (the
    op shows up under ``name`` in a device trace); outside, it doubles
    as a host-side ``TraceAnnotation`` so client spans (the reference's
    RPC timers) land in the same profile.
    """
    return jax.named_scope(name)


def host_span(name: str) -> jax.profiler.TraceAnnotation:
    """Host-side annotation for un-traced code (client loops, data feed,
    the generation scheduler's loop phases): a span in the host plane of
    any ``jax.profiler`` capture, on the clock the device operations
    use. Near free while no capture runs. The object is a context
    manager; make a new one for each span (one made before a capture
    began records nothing in it)."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def capture_trace(log_dir: str) -> Iterator[None]:
    """Capture a device+host profile into ``log_dir`` (TensorBoard format).

    The TPU-native replacement for reading ``docker logs`` latencies: one
    trace shows per-stage compute, ppermute hops, and host feed gaps.
    """
    with jax.profiler.trace(str(log_dir)):
        yield


@contextlib.contextmanager
def timed() -> Iterator[dict]:
    """``with timed() as t: ...`` → ``t["seconds"]`` afterwards.

    The reference's ubiquitous ``t0 = time.monotonic(); ...; dt`` idiom
    (manual_nn.py:90-99) as a reusable span.
    """
    box = {"seconds": None}
    t0 = time.monotonic()
    try:
        yield box
    finally:
        box["seconds"] = time.monotonic() - t0
