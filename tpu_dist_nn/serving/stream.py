"""Per-request token streaming channel (PR 16, ISSUE 16).

The continuous scheduler (serving/continuous.py) produces one token
per resident row per device step — Orca's iteration-level scheduling —
but until this module the RPC boundary collapsed that back to
"everything at retirement". :class:`TokenStream` is the seam that
carries tokens OUT at step granularity: a bounded, lock-protected
channel between the scheduler thread (producer) and the
``GenerateStream`` gRPC handler thread (consumer).

Contract (docs/ROBUSTNESS.md "Stream deadline + cancellation"):

* **Producer never blocks.** The scheduler publishes from its decode
  loop; a slow/stuck consumer must not stall every other resident
  row's decode. The channel is bounded: past ``max_buffer`` undelivered
  tokens the stream flips to cancelled (backpressure-by-cancellation)
  and the scheduler frees the slot on its next iteration, exactly as
  if the client had disconnected.
* **Publish is idempotent over the known-token list.** The scheduler
  hands the FULL ``occ["tokens"]`` list each time; the channel's
  ``sent`` cursor enqueues only the unseen suffix. That single cursor
  is what makes failover/preemption replay exactly-once: a re-bound
  row rebuilds ``occ["tokens"]`` from scratch (forced-token replay,
  PR 15), republishing tokens the stream already delivered — the
  cursor suppresses them without any scheduler-side bookkeeping.
* **Exactly one terminal.** ``finish()`` is idempotent; the first
  call wins. Every scheduler exit path (retire, expiry, device fault,
  close-time sweep) reaches it through :class:`StreamDone`, the
  ``item["done"]`` Event subclass that converts the item's terminal
  state into the END frame as a side effect of ``set()``.

The wire framing itself (TOKENS / END frames) lives in
serving/wire.py with every other byte format; this module owns only
the channel semantics and the stream-plane metrics
(docs/OBSERVABILITY.md catalog).
"""

from __future__ import annotations

import threading
import time

from tpu_dist_nn.obs.registry import REGISTRY

# Stream-plane metrics (docs/OBSERVABILITY.md). Requests/frames/
# cancellations count the channel's lifecycle; the inter-token
# histogram is the stream-latency twin of tdn_gen_ttft_seconds —
# observed at PUBLISH time (scheduler-side token production cadence),
# so a slow consumer shows up in the buffer depth, not here.
_STREAM_REQUESTS = REGISTRY.counter(
    "tdn_gen_stream_requests_total",
    "GenerateStream requests admitted to the continuous scheduler",
)
_STREAM_FRAMES = REGISTRY.counter(
    "tdn_gen_stream_frames_total",
    "stream frames flushed to clients, by kind (tokens / end)",
    labels=("kind",),
)
_STREAM_CANCELLED = REGISTRY.counter(
    "tdn_gen_stream_cancelled_total",
    "streams cancelled before their terminal frame (client abandon, "
    "gRPC cancellation, or buffer-overflow backpressure)",
)
_STREAM_RESUMED = REGISTRY.counter(
    "tdn_gen_stream_resumed_total",
    "GenerateStream requests admitted WITH a resume prefix (router "
    "mid-stream failover replaying already-delivered tokens)",
)
_INTERTOKEN = REGISTRY.histogram(
    "tdn_gen_intertoken_seconds",
    "gap between consecutive published tokens of one stream (after "
    "the first token; TTFT owns submit -> first)",
)


class TokenStream:
    """Bounded single-producer/single-consumer token channel for one
    GenerateStream request.

    ``lag_sink`` (the scheduler's loop clock) is told the stream-out
    lag: how long each frame stood between the producer's ``publish``
    and the consumer taking it to hand to gRPC. The consumer sums its
    lag here under the lock it already holds; the producer moves the
    sums into the sink's ``stream_lag_ns`` / ``stream_frames`` at its
    next ``publish`` of this stream, so the sink keeps one writer and
    no lock is added. (The frames a consumer takes after the stream's
    last publish are therefore never counted: one of a request's ~190.)
    The same way goes the stream-send time: how long the consumer was
    away with each frame, from ``next_event`` handing it a batch to its
    next call of ``next_event`` (encoding, gRPC's write and the wait
    for its completion), into ``stream_send_ns`` / ``stream_sends``.
    With the lag it is a frame's whole life on the server.
    """

    def __init__(self, max_buffer: int = 4096, lag_sink=None):
        self._cond = threading.Condition()
        self._max = int(max_buffer)
        self._pending: list[int] = []  # guarded-by: _cond
        self._sent = 0  # guarded-by: _cond
        self._terminal: dict | None = None  # guarded-by: _cond
        self._cancelled = False  # guarded-by: _cond
        self._last_publish: float | None = None  # guarded-by: _cond
        self._lag_sink = lag_sink
        self._pending_since = 0.0  # guarded-by: _cond
        self._lag_s = 0.0  # guarded-by: _cond
        self._lag_frames = 0  # guarded-by: _cond
        self._taken_at: float | None = None  # guarded-by: _cond
        self._send_s = 0.0  # guarded-by: _cond
        self._sends = 0  # guarded-by: _cond
        _STREAM_REQUESTS.inc()

    # ---------------------------------------------------- producer side

    def seed(self, n: int) -> None:
        """Advance the sent cursor past ``n`` tokens the CLIENT already
        holds (router failover resume): the scheduler will republish
        the whole replayed prefix and the cursor swallows it."""
        with self._cond:
            self._sent = max(self._sent, int(n))

    def publish(self, tokens, *, notify: bool = True) -> bool:
        """Enqueue the unseen suffix of the full known-token list.

        Called from the scheduler loop with ``occ["tokens"]`` after
        every append; never blocks. Returns False once the stream is
        cancelled (client gone or buffer overflowed) — the scheduler's
        cue to abandon the row and free its slot. ``notify=False``
        leaves the consumer asleep: the producer has more streams to
        publish to and wakes them together (:meth:`wake`) when it is
        about to let go of the interpreter they all share.
        """
        with self._cond:
            if self._cancelled or self._terminal is not None:
                return not self._cancelled
            fresh = tokens[self._sent:]
            if not fresh:
                return True
            now = time.monotonic()
            if self._last_publish is not None:
                _INTERTOKEN.observe(now - self._last_publish)
            self._last_publish = now
            if self._lag_frames and self._lag_sink is not None:
                self._lag_sink.stream_lag_ns += int(self._lag_s * 1e9)
                self._lag_sink.stream_frames += self._lag_frames
                self._lag_s, self._lag_frames = 0.0, 0
            if self._sends and self._lag_sink is not None:
                self._lag_sink.stream_send_ns += int(self._send_s * 1e9)
                self._lag_sink.stream_sends += self._sends
                self._send_s, self._sends = 0.0, 0
            if not self._pending:
                self._pending_since = now
            self._sent += len(fresh)
            self._pending.extend(int(t) for t in fresh)
            if len(self._pending) > self._max:
                # Backpressure-by-cancellation: the consumer stopped
                # draining (wedged client) — the producer must never
                # block the shared decode loop, so the stream dies
                # instead.
                self._cancelled = True
                _STREAM_CANCELLED.inc()
                self._cond.notify_all()
                return False
            if notify:
                self._cond.notify_all()
            return True

    def wake(self) -> None:
        """The other half of ``publish(notify=False)``."""
        with self._cond:
            self._cond.notify_all()

    def finish(self, reason: str, code: str = "",
               message: str = "") -> None:
        """Idempotent terminal: "eos" / "max_tokens", or "error" with
        the canonical code name + message. First call wins."""
        with self._cond:
            if self._terminal is not None:
                return
            self._terminal = {"reason": reason, "code": code,
                              "message": message}
            self._cond.notify_all()

    # ---------------------------------------------------- consumer side

    def cancel(self) -> None:
        """Consumer-side teardown (client disconnected / handler
        exiting early): flips the channel so the next publish returns
        False and the scheduler reaps the slot."""
        with self._cond:
            if self._cancelled or self._terminal is not None:
                return
            self._cancelled = True
            _STREAM_CANCELLED.inc()
            self._cond.notify_all()

    @property
    def cancelled(self) -> bool:
        with self._cond:
            return self._cancelled

    @property
    def delivered(self) -> int:
        """Tokens handed to the consumer so far (the resume ledger)."""
        with self._cond:
            return self._sent - len(self._pending)

    def next_event(self, timeout: float | None = None):
        """Block for the next thing to flush: ``("tokens", [ids])``
        (the whole buffered delta, one frame), ``("end", {...})`` after
        the buffer drains, or ``None`` on timeout — the handler's
        per-token-gap deadline hook."""
        with self._cond:
            now = time.monotonic()
            if self._taken_at is not None:
                # Back from sending the batch the call before took.
                self._send_s += now - self._taken_at
                self._sends += 1
                self._taken_at = None
            deadline = None if timeout is None else now + timeout
            while True:
                if self._pending:
                    batch = self._pending
                    self._pending = []
                    self._taken_at = time.monotonic()
                    self._lag_s += self._taken_at - self._pending_since
                    self._lag_frames += 1
                    _STREAM_FRAMES.labels(kind="tokens").inc()
                    return "tokens", batch
                if self._terminal is not None:
                    _STREAM_FRAMES.labels(kind="end").inc()
                    return "end", dict(self._terminal)
                if self._cancelled:
                    return "end", {"reason": "error", "code": "CANCELLED",
                                   "message": "stream cancelled"}
                if deadline is None:
                    self._cond.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._cond.wait(remaining)


class StreamDone(threading.Event):
    """The ``item["done"]`` Event of a streaming request.

    Every terminal path in the scheduler/admission stack —
    ``_retire``, ``_free_slot_on_error``, queue expiry, close-time
    sweeps — already calls ``item["done"].set()`` after stamping
    ``item["err"]`` / ``item["finish_reason"]``. Subclassing the Event
    converts that existing contract into the stream's END frame
    without touching any of those call sites: ``set()`` reads the
    item's terminal state and finishes the channel.
    """

    def __init__(self, item: dict, stream: TokenStream):
        super().__init__()
        self._item = item
        self._stream = stream

    def set(self) -> None:  # noqa: A003 — matching threading.Event
        err = self._item.get("err")
        if err is not None:
            self._stream.finish(
                "error", getattr(err, "code", "INTERNAL"), str(err)
            )
        else:
            self._stream.finish(
                self._item.get("finish_reason") or "max_tokens"
            )
        super().set()


def note_stream_resumed() -> None:
    """Tick the failover-resume counter (called at admission when a
    resume prefix rides in — serving/server.py)."""
    _STREAM_RESUMED.inc()
