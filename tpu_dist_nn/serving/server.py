"""gRPC serving endpoint, wire-compatible with the reference's client.

Runs the reference's one RPC — ``LayerService.Process(Matrix) ->
Matrix`` (``src/proto/dist_nn.proto:13-15``) — in front of an
:class:`~tpu_dist_nn.api.engine.Engine`, so a user of docker-dist-nn
can point their EXISTING client (``run_grpc_inference.py``) at
``tdn serve`` unchanged. The difference is behind the socket: the
reference answers by chaining nested gRPC hops through one container
per stage (``grpc_node.py:120-147``); here the whole pipeline is one
SPMD program on the mesh, so the request crosses exactly one
serialization boundary instead of ``2 x num_stages``.

Concurrency: the reference overlaps concurrent requests only through
its 10-thread server pool, each request traversing the whole pipeline
alone (``grpc_node.py:169``). Here concurrent requests COALESCE: a
:class:`_Batcher` thread owns the device, and every request that
arrives while a batch is in flight joins the next one — rows from many
clients fuse into one padded device batch and split on reply. Under
load the device sees a few large launches instead of many one-row
launches (aggregate throughput scales with the coalesced batch size);
an idle server dispatches immediately, adding zero latency.

Error parity (``grpc_node.py:149-158``): a wrong input width returns
``INVALID_ARGUMENT`` with the dim message — validated per request
BEFORE coalescing so one bad client cannot poison a shared batch;
unexpected failures return ``INTERNAL``.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent import futures

import grpc
import numpy as np

from tpu_dist_nn.obs import trace as _trace
from tpu_dist_nn.obs.log import get_logger
from tpu_dist_nn.obs.registry import POW2_BUCKETS, REGISTRY
from tpu_dist_nn.serving.sched_core import SchedCore, normalize_class
from tpu_dist_nn.serving.stream import note_stream_resumed
from tpu_dist_nn.serving.wire import (
    CLASS_HEADER,
    GENERATE_METHOD,
    GENERATE_STREAM_METHOD,
    PROCESS_METHOD,
    RETRY_AFTER_HEADER,
    SERVICE_NAME,
    SESSION_HEADER,
    STREAM_RESUME_HEADER,
    STREAM_RESUME_MAX_TOKENS,
    WireMatrix,
    decode_frame,
    decode_matrix,
    decode_matrix_lazy,
    encode_matrix,
    encode_end_frame,
    encode_token_frame,
)

log = logging.getLogger(__name__)
# Structured channel for the operational events a log pipeline matches
# on (server.start, client.rpc_failed, ...): trace-correlated JSON
# records under `tdn --log-json`, readable key=value lines otherwise.
slog = get_logger(__name__)

# Serving metric families (docs/OBSERVABILITY.md catalog). All updates
# are host-side float adds — never a device touch on the hot path.
_RPC_REQUESTS = REGISTRY.counter(
    "tdn_rpc_requests_total", "RPCs received, per method",
    labels=("method",),
)
_RPC_ERRORS = REGISTRY.counter(
    "tdn_rpc_errors_total", "RPCs aborted, per method and status code",
    labels=("method", "code"),
)
_BATCH_ROWS = REGISTRY.histogram(
    "tdn_batch_rows", "coalesced rows per device launch (pre-padding)",
    labels=("method",), buckets=POW2_BUCKETS,
)
_SUBMITS = REGISTRY.counter(
    "tdn_batcher_submits_total", "requests entering the coalescing queue",
    labels=("method",),
)
_ABANDONED = REGISTRY.counter(
    "tdn_batcher_abandoned_total",
    "requests that timed out waiting for their batch",
    labels=("method",),
)
_LAUNCHES = REGISTRY.counter(
    "tdn_batch_launches_total", "device launches issued by the batcher",
    labels=("method",),
)
# tdn_batcher_shed_total / tdn_batch_wait_seconds and the class-labeled
# admission families moved to serving/sched_core.py — the ONE
# admission/shed/close implementation both schedulers rebase on.


class _Batcher:
    """Two-stage (double-buffered) micro-batching pipeline in front of
    one engine.

    ``submit(x)`` blocks the calling (gRPC worker) thread until its
    rows' results are ready. Two daemon threads own the device path:

    * **dispatch** grabs everything pending (up to ``max_batch_rows``
      rows), stages it into a reusable per-bucket host buffer (rows
      copied in, pad tail zeroed in place — no per-batch
      ``np.concatenate`` + ``np.zeros`` allocation), and LAUNCHES it
      (``engine.infer_async`` where the engine has one — JAX async
      dispatch returns a device handle without a host sync).
    * **drain** materializes launched batches in order (the one host
      sync per batch), slices the result back per request, and fans
      out to the waiting workers.

    So batch N+1 is assembled, padded, and launched while batch N's
    device result is still materializing — host serialization overlaps
    device execution instead of extending the launch critical section.
    ``pipeline_depth=1`` collapses to the old strictly-serial loop
    (dispatch fetches inline; tests use it as the control that never
    overlaps). Arrival during an in-flight batch remains the
    coalescing window — no artificial delay is ever inserted.
    """

    def __init__(self, engine, max_batch_rows: int = 65536,
                 submit_timeout: float | None = 120.0, run_fn=None,
                 method: str = "Process", pipeline_depth: int = 2,
                 max_pending_rows: int | None = None, account_fn=None,
                 class_watermarks: dict | None = None):
        self._engine = engine
        # The device launch the batcher owns, split into the dispatch
        # half (launch, ideally non-blocking) and the fetch half (the
        # host sync). engine.infer_async/fetch when available; any
        # ``rows (n, ...) -> rows (n, ...)`` closure otherwise (the LM
        # generation endpoint passes its decode runner — returning a
        # device array from it buys the same overlap) — coalescing,
        # bucketing, abandonment, and error fan-out are identical.
        # An engine whose infer_async takes ``useful_rows`` gets the
        # pre-padding row count declared per launch, so the goodput
        # plane (obs/goodput.py) books bucket pad exactly; fakes with a
        # plain one-arg infer_async keep working (signature-probed).
        self._useful_aware = False
        if run_fn is not None:
            self._dispatch_fn, self._fetch_fn = run_fn, np.asarray
        elif hasattr(engine, "infer_async") and hasattr(engine, "fetch"):
            self._dispatch_fn, self._fetch_fn = engine.infer_async, engine.fetch
            try:
                import inspect

                self._useful_aware = "useful_rows" in inspect.signature(
                    engine.infer_async
                ).parameters
            except (TypeError, ValueError):
                pass
        else:
            self._dispatch_fn, self._fetch_fn = engine.infer, np.asarray
        # Post-fetch accounting seam: called with (materialized output,
        # useful_rows, launched_rows) after each successful drain — the
        # static Generate path's goodput hook (EOS positions are only
        # visible in the materialized sequences). Must never fail a
        # request; exceptions are swallowed to a log line.
        self._account_fn = account_fn
        # Whether the accounting seam takes the dead-waiter row count
        # (rows whose caller abandoned mid-flight — goodput books them
        # as pad, not useful). Signature-probed so older account fakes
        # keep working.
        self._account_dead_aware = False
        if account_fn is not None:
            try:
                import inspect

                self._account_dead_aware = "dead_rows" in inspect.signature(
                    account_fn
                ).parameters
            except (TypeError, ValueError):
                pass
        self._max_rows = int(max_batch_rows)
        # The admission/shed/close/drain contract lives in the shared
        # scheduling core (serving/sched_core.py): pending queue +
        # rows ledger under core.cond, class watermarks, deadline
        # expiry, close-failover sweep. The dispatch loop below holds
        # core.cond exactly where it held its own condition before.
        self._core = SchedCore(
            method, max_pending_rows=max_pending_rows,
            submit_timeout=submit_timeout,
            class_watermarks=class_watermarks,
        )
        self._cond = self._core.cond
        self._serial = pipeline_depth <= 1
        # Launched-but-not-drained hand-off. The SEMAPHORE is the
        # launch-ahead bound — dispatch takes a slot BEFORE staging or
        # launching, drain returns it after the fetch, so at most
        # pipeline_depth batches of device work (and staging buffers)
        # are ever outstanding (depth 2 = classic double buffering).
        # Bounding the queue instead would be off by one: dispatch
        # would launch, THEN block on put.
        self._launched: queue.Queue = queue.Queue()
        self._slots = threading.Semaphore(max(1, pipeline_depth))
        # Reusable staging buffers, keyed (bucket, feature-shape,
        # dtype) -> free list. Dispatch pops (sole consumer), drain
        # returns a buffer only AFTER its batch's fetch completed —
        # so a backend that zero-copy-aliases host memory into device
        # buffers can never see a staging buffer mutate mid-flight.
        self._staging: dict[tuple, list[np.ndarray]] = {}
        self._staging_keep = max(2, pipeline_depth)
        # Observability: served totals let tests/operators confirm
        # coalescing actually happens (batches < requests under load).
        # requests/shed/pending ride the core (delegating properties
        # below keep the legacy attribute names the sampler and tests
        # read).
        self.batches_total = 0
        self.rows_total = 0
        # Launches issued while a previously launched batch had not
        # finished draining — the overlap evidence
        # (tdn_batcher_overlap_ratio = overlapped_total/batches_total).
        self.overlapped_total = 0
        # Rows launched and not yet drained (the runtime sampler's
        # in-flight gauge reads this attribute); with pipelining this
        # can span up to pipeline_depth batches.
        self.inflight_rows = 0
        self.inflight_batches = 0
        self._stats_lock = threading.Lock()
        self.method = method
        # Pre-bound registry children: the hot path does a float add,
        # not a label lookup.
        self._m_submits = _SUBMITS.labels(method=method)
        self._m_abandoned = _ABANDONED.labels(method=method)
        self._m_launches = _LAUNCHES.labels(method=method)
        self._m_rows = _BATCH_ROWS.labels(method=method)
        self._dispatch_thread = threading.Thread(
            target=self._dispatch_loop, name="tdn-serve-dispatch", daemon=True
        )
        self._drain_thread = None
        if not self._serial:
            self._drain_thread = threading.Thread(
                target=self._drain_loop, name="tdn-serve-drain", daemon=True
            )
            self._drain_thread.start()
        self._dispatch_thread.start()

    # Legacy counter/queue surface, now owned by the shared core (the
    # runtime sampler, drain plumbing, and the resilience tests read
    # these names).
    @property
    def pending_rows(self) -> int:
        return self._core.pending_rows

    @property
    def requests_total(self) -> int:
        return self._core.requests_total

    @property
    def shed_total(self) -> int:
        return self._core.shed_total

    @property
    def expired_total(self) -> int:
        return self._core.expired_total

    @property
    def _pending(self) -> list:
        return self._core.pending_items()

    @property
    def _closed(self) -> bool:
        return self._core.closed

    def queue_depth(self) -> int:
        """Entries queued (lock-free; the runtime sampler's per-tick
        read — the `_pending` property above copies the whole queue
        under the admission lock and exists for tests)."""
        return self._core.queue_depth()

    def pending_by_class(self) -> dict:
        return self._core.pending_by_class()

    def submit(self, x: np.ndarray,
               timeout: float | None = None,
               ctx=None, slo_class: str = "standard") -> np.ndarray:
        """Block until this request's rows are served.

        ``timeout`` is the CALLER's remaining budget (the RPC deadline);
        the effective wait is ``min(timeout, submit_timeout)`` — there
        is no point holding a worker thread past the moment its client
        gave up. The same budget is the entry's queue DEADLINE: if it
        expires before dispatch stages the entry, the entry fails
        DEADLINE_EXCEEDED without riding a launch.

        ``slo_class`` (``critical``/``standard``/``best_effort``, the
        ``x-tdn-class`` header) sets the entry's queue priority and
        shed watermark (docs/ROBUSTNESS.md "Degradation ladder").

        ``ctx`` is the request's :class:`~tpu_dist_nn.obs.trace
        .SpanContext`: when sampled, this entry's passage through the
        pipeline is recorded as queue_wait / stage / launch / fetch
        spans under it (each batch-level stage appears once per member
        request, so every trace tree is complete on its own).
        """
        item = {"x": x, "done": threading.Event(), "out": None, "err": None,
                "abandoned": False, "slo_class": slo_class,
                "t_submit": time.monotonic(),
                # Only a SAMPLED context is worth carrying: the per-item
                # skip below is then one None check.
                "ctx": ctx if ctx is not None and ctx.sampled else None}
        self._core.admit(item, timeout)
        self._m_submits.inc()
        try:
            self._core.wait(item, what="coalesced batch")
        except Exception:
            if item["abandoned"]:
                self._m_abandoned.inc()
            raise
        return item["out"]

    def _stage(self, group: list[dict]):
        """Assemble a width-group into a pow2-bucket staging buffer.

        Pads rows up to a power-of-two bucket: every distinct row count
        is a distinct jit shape, so unbucketed coalescing would
        recompile on nearly every batch (compile costs dwarf the launch
        overhead saved). Buckets cap the compiled-program set at
        log2(max_rows). Returns ``(xs, key, buf)``; ``buf`` is None on
        the zero-copy single-request fast path (a lone request already
        ON a bucket boundary launches the caller's array directly).
        """
        n = sum(len(it["x"]) for it in group)
        n_pad = 1 << (n - 1).bit_length() if n > 1 else 1
        if (len(group) == 1 and n == n_pad
                and not isinstance(group[0]["x"], WireMatrix)):
            return group[0]["x"], None, None
        feat = tuple(group[0]["x"].shape[1:])
        dtype = group[0]["x"].dtype
        key = (n_pad, feat, str(dtype))
        pool = self._staging.get(key)
        buf = pool.pop() if pool else None
        if buf is None:
            buf = np.empty((n_pad, *feat), dtype)
        ofs = 0
        for it in group:
            x = it["x"]
            k = len(x)
            if isinstance(x, WireMatrix):
                # Decode-into-staging: the request's payload goes wire
                # bytes -> this bucket buffer in ONE cast-copy (the
                # handler only probed the structure; nothing was
                # materialized in between).
                x.read_into(buf, ofs)
            else:
                buf[ofs:ofs + k] = x
            ofs += k
        if ofs < n_pad:
            buf[ofs:] = 0  # zero the pad tail in place
        return buf, key, buf

    def _release(self, key, buf) -> None:
        """Drain-side buffer return (after the fetch — the batch's
        device input can no longer alias it). Single producer (drain) /
        single consumer (dispatch) per list, so GIL-atomic list ops
        suffice; the pool keeps at most pipeline_depth buffers per
        bucket, the steady-state working set."""
        if buf is None:
            return
        pool = self._staging.setdefault(key, [])
        if len(pool) < self._staging_keep:
            pool.append(buf)

    def _drain_one(self, group, handle, key, buf, launched_rows) -> None:
        """Fetch one launched batch and fan results out per request."""
        t_fetch = time.monotonic()
        err = None
        notes: list = []
        traced = any(it["ctx"] is not None for it in group)
        try:
            if traced:
                with _trace.annotation_sink() as notes:
                    out = self._fetch_fn(handle)
            else:
                out = self._fetch_fn(handle)
            # Per-row integrity verdict (engine.fetch stashes a bad-row
            # mask on the launch handle when the numeric guard tripped):
            # only the requests whose rows are corrupt fail — with
            # INTEGRITY, not INTERNAL — and every other request in the
            # same coalesced launch ships its slice bit-identical.
            bad = getattr(handle, "bad_rows", None)
            ofs = 0
            for it in group:
                k = len(it["x"])
                if bad is not None and bad[ofs:ofs + k].any():
                    from tpu_dist_nn.utils.errors import IntegrityError

                    it["err"] = IntegrityError(
                        f"numeric guard: {int(bad[ofs:ofs + k].sum())} "
                        f"of this request's {k} rows carried non-finite "
                        f"or out-of-magnitude activations"
                    )
                else:
                    it["out"] = out[ofs:ofs + k]
                ofs += k
            if self._account_fn is not None:
                # Post-fetch goodput accounting (static Generate path:
                # EOS-frozen positions only exist in the materialized
                # sequences). Best-effort — accounting must never fail
                # a request that already has its result. Rows whose
                # waiter abandoned AFTER dispatch popped them (the one
                # window deadline expiry cannot close) are declared as
                # dead: goodput books the launch they rode as pad, not
                # useful (reason dead_waiter).
                try:
                    if self._account_dead_aware:
                        dead = sum(
                            len(it["x"]) for it in group if it["abandoned"]
                        )
                        self._account_fn(out, ofs, launched_rows,
                                         dead_rows=dead)
                    else:
                        self._account_fn(out, ofs, launched_rows)
                except Exception:  # noqa: BLE001 — accounting only
                    log.exception("goodput accounting failed")
        except Exception as e:  # noqa: BLE001 — per request
            err = e
            for it in group:
                it["err"] = e
        finally:
            dur = time.monotonic() - t_fetch
            if err is not None:
                notes = notes + [
                    (time.monotonic(), f"error: {type(err).__name__}: {err}")
                ]
            for it in group:
                if it["ctx"] is not None:
                    # The one host sync of the request's batch — the
                    # span that separates "device was slow" from "queue
                    # was long" in a trace.
                    _trace.TRACER.record_span(
                        "fetch", it["ctx"], t_fetch, dur,
                        attrs={"rows": len(it["x"]),
                               "batch_rows": launched_rows},
                        annotations=notes,
                    )
            with self._stats_lock:
                self.inflight_batches -= 1
                self.inflight_rows -= launched_rows
            if err is None:
                # Completions feed the drain-rate window behind the
                # shed replies' x-tdn-retry-after-ms hint.
                self._core.note_drained(
                    sum(len(it["x"]) for it in group)
                )
            self._release(key, buf)
            self._slots.release()
            for it in group:
                it["done"].set()

    def _dispatch_loop(self) -> None:
        core = self._core
        while True:
            with core.cond:
                while not core.has_pending() and not core.closed:
                    core.cond.wait()
                if not core.has_pending() and core.closed:
                    if not self._serial:
                        self._launched.put(None)  # drain's shutdown pill
                    return
                # Class-priority pop (critical first, FIFO within a
                # class); abandoned entries are discarded and
                # budget-expired ones failed DEADLINE_EXCEEDED here —
                # neither rides the launch.
                batch, rows = core.pop_group(self._max_rows)
                self.rows_total += rows
            core.drain_deferred()
            if not batch:
                continue
            # Queue wait ends the moment the dispatch stage owns the
            # request (recorded outside the condition lock — tracing
            # must not extend the producers' critical section).
            t_pop = time.monotonic()
            for it in batch:
                if it["ctx"] is not None:
                    _trace.TRACER.record_span(
                        "queue_wait", it["ctx"], it["t_submit"],
                        t_pop - it["t_submit"],
                    )
            # Group by feature width: engines without a declared
            # input_dim cannot be pre-validated in the handler, and a
            # mixed-width concatenation would fail EVERY request in the
            # batch. One launch per width keeps each group's fate its
            # own — a wrong-width group gets the engine's dim error.
            groups: dict[tuple, list[dict]] = {}
            for it in batch:
                groups.setdefault(
                    (it["x"].shape[1:], str(it["x"].dtype)), []
                ).append(it)
            for group in groups.values():
                # Take the launch-ahead slot BEFORE staging/launching:
                # the back-pressure that keeps dispatch honest (blocks
                # here when pipeline_depth batches are outstanding).
                self._slots.acquire()
                key = buf = None
                traced = [it for it in group if it["ctx"] is not None]
                group_rows = sum(len(it["x"]) for it in group)

                def _launch(xs):
                    # Goodput declaration: the engine books this
                    # launch's bucket-pad rows (bucket - useful) as pad
                    # FLOPs under path="batcher" (obs/goodput.py).
                    if self._useful_aware:
                        return self._dispatch_fn(
                            xs, useful_rows=group_rows
                        )
                    return self._dispatch_fn(xs)

                try:
                    t_stage = time.monotonic()
                    xs, key, buf = self._stage(group)
                    t_launch = time.monotonic()
                    if traced:
                        # Collect engine-side annotations (async
                        # dispatch, compile-cache misses) emitted while
                        # the launch runs; they attach to every member
                        # request's launch span below.
                        with _trace.annotation_sink() as notes:
                            handle = _launch(xs)
                    else:
                        handle = _launch(xs)
                    t_launched = time.monotonic()
                    for it in traced:
                        _trace.TRACER.record_span(
                            "stage", it["ctx"], t_stage, t_launch - t_stage,
                            attrs={"rows": len(it["x"]),
                                   "batch_rows": len(xs),
                                   "zero_copy": buf is None},
                        )
                        _trace.TRACER.record_span(
                            "launch", it["ctx"], t_launch,
                            t_launched - t_launch,
                            attrs={"batch_rows": len(xs)},
                            annotations=notes,
                        )
                except Exception as e:  # noqa: BLE001 — per request
                    # Dispatch-time failure (validation, trace error):
                    # fail the group here — it never reached the device,
                    # so the launch counters do NOT tick (a down engine
                    # must not render as healthy launch activity on the
                    # exact scrape diagnosing it).
                    self._release(key, buf)
                    self._slots.release()
                    for it in group:
                        it["err"] = e
                        it["done"].set()
                    continue
                self.batches_total += 1
                self._m_launches.inc()
                # tdn_batch_rows keeps the pre-padding count — the
                # useful-rows view; inflight_rows below reports what
                # the device is actually running.
                self._m_rows.observe(group_rows)
                with self._stats_lock:
                    if self.inflight_batches:
                        # A prior batch is still materializing while
                        # this one launched: that IS the overlap.
                        self.overlapped_total += 1
                    self.inflight_batches += 1
                    self.inflight_rows += len(xs)
                if self._serial:
                    self._drain_one(group, handle, key, buf, len(xs))
                else:
                    self._launched.put((group, handle, key, buf, len(xs)))

    def _drain_loop(self) -> None:
        while True:
            item = self._launched.get()
            if item is None:
                return
            self._drain_one(*item)

    def close(self, timeout: float = 10.0) -> None:
        self._core.close_begin()
        # Dispatch drains the queue then pills the drain queue; drain
        # finishes every launched batch before exiting — both stages
        # empty by the time close returns. Anything STILL pending (a
        # wedged dispatch never popped it) is failed over UNAVAILABLE
        # by the core's sweep, so its waiters don't sit out their full
        # submit timeout against a batcher that is already gone.
        self._dispatch_thread.join(timeout=timeout)
        if self._drain_thread is not None:
            self._drain_thread.join(timeout=timeout)
        self._core.sweep_leftovers()


def _request_span(context, method: str):
    """Begin the handler span for one RPC and derive its wait budget.

    Honors an inbound ``x-tdn-trace`` header (the remote parent makes
    this handler a child in the caller's trace — and inherits the
    caller's sampling decision); without one this is a new locally
    sampled root. Always names the trace back to the caller in
    trailing metadata so a failed RPC tells the client which trace to
    pull from ``/trace``. Returns ``(span, budget_seconds, metadata)``
    where the budget is ``min(grpc deadline remaining, x-tdn-timeout-ms
    hint)`` — whichever bounds exist — and ``metadata`` is the parsed
    invocation-metadata dict (the router reads ``x-tdn-session`` from
    it; engine handlers ignore it).
    """
    md = {}
    try:
        for k, v in context.invocation_metadata() or ():
            md[k] = v
    except Exception:  # noqa: BLE001 — tracing must never fail an RPC
        pass
    parent = _trace.SpanContext.from_header(md.get(_trace.TRACE_HEADER))
    span = _trace.TRACER.start(f"rpc.{method}", parent=parent)
    base_trailing = ((_trace.TRACE_ID_HEADER, span.ctx.trace_id),)
    try:
        # Stashed so a later abort path (shed replies' retry-after
        # hint) can EXTEND the trailing metadata instead of replacing
        # the trace id — set_trailing_metadata's last call wins.
        context._tdn_trailing = base_trailing
        context.set_trailing_metadata(base_trailing)
    except Exception:  # noqa: BLE001 — in-process fakes may not have it
        pass
    bounds = []
    try:
        rem = context.time_remaining()
        # Deadline-less calls can report a far-future sentinel (~1e10 s)
        # instead of None; a "budget" measured in centuries is no bound
        # at all and overflows condition waits downstream.
        if rem is not None and rem < 1e9:
            bounds.append(rem)
    except Exception:  # noqa: BLE001
        pass
    hint = md.get(_trace.TIMEOUT_HEADER)
    if hint is not None:
        try:
            bounds.append(float(hint) / 1000.0)
        except ValueError:
            pass  # a garbled hint must not fail the RPC
    return span, (min(bounds) if bounds else None), md


def _abort(context, method: str, code, message: str):
    """Count, then abort: context.abort raises, so the error counter
    must tick first (one funnel for every handler's abort)."""
    _RPC_ERRORS.labels(method=method, code=code.name).inc()
    context.abort(code, message)


def _abort_for_exception(context, e, what: str, method: str = "Process"):
    """Map framework exceptions to the reference's gRPC status taxonomy
    (grpc_node.py:149-158) — ONE mapping for every method so a new
    status cannot land in Process and miss Generate."""
    from tpu_dist_nn.utils.errors import (
        DeadlineExceededError,
        IntegrityError,
        InvalidArgumentError,
        ResourceExhaustedError,
        UnavailableError,
    )

    if isinstance(e, InvalidArgumentError):
        # The reference's dim-check path (grpc_node.py:149-153).
        _abort(context, method, grpc.StatusCode.INVALID_ARGUMENT, str(e))
    if isinstance(e, IntegrityError):
        # A correctness check refused to ship the answer: DATA_LOSS —
        # deliberately NOT in the transient-retry set, so a direct
        # client never retries the same weights; the router gives it
        # failover-to-a-DIFFERENT-replica semantics plus an integrity
        # strike toward quarantine (docs/ROBUSTNESS.md).
        _abort(context, method, grpc.StatusCode.DATA_LOSS, str(e))
    if isinstance(e, DeadlineExceededError):
        # Batcher wait expired (wedged engine): the reference's
        # per-RPC timeout semantics (grpc_node.py:133).
        _abort(context, method, grpc.StatusCode.DEADLINE_EXCEEDED, str(e))
    if isinstance(e, ResourceExhaustedError):
        # Admission-control shed: the queue is at its watermark — the
        # server is healthy and asking this client to back off. The
        # reply names HOW LONG in x-tdn-retry-after-ms (derived from
        # the current drain rate — serving/sched_core.py), which
        # RetryPolicy honors as its backoff floor so a shed storm
        # cannot re-synchronize into a hot-retry storm.
        retry_after = getattr(e, "retry_after_ms", None)
        if retry_after is not None:
            try:
                context.set_trailing_metadata(
                    tuple(getattr(context, "_tdn_trailing", ()))
                    + ((RETRY_AFTER_HEADER, str(int(retry_after))),)
                )
            except Exception:  # noqa: BLE001 — fakes without metadata
                pass
        _abort(context, method, grpc.StatusCode.RESOURCE_EXHAUSTED, str(e))
    if isinstance(e, UnavailableError):
        # Engine torn down mid-flight: the reference's dead-channel
        # semantics (clients may retry elsewhere).
        _abort(context, method, grpc.StatusCode.UNAVAILABLE, str(e))
    slog.exception("rpc.internal_error", method=method, what=what,
                   error=f"{type(e).__name__}: {e}")
    _abort(context, method, grpc.StatusCode.INTERNAL, f"{what} failed: {e}")


def _new_grpc_server(max_workers: int, interceptors=()):
    """The reference's server shape: thread pool + unlimited messages
    (grpc_node.py:169, run_grpc_inference.py:124-127). ``interceptors``
    is the fault-injection seam (testing/faults.FaultInterceptor) —
    empty in production."""
    return grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[
            ("grpc.max_send_message_length", -1),
            ("grpc.max_receive_message_length", -1),
        ],
        interceptors=tuple(interceptors),
    )


def _bind_or_close(server, host: str, port: int, batcher) -> int:
    bound = server.add_insecure_port(f"{host}:{port}")
    if bound == 0:
        if batcher is not None:
            batcher.close()
        raise OSError(f"could not bind gRPC server to port {port}")
    return bound


def _wrap_server_stop(server, batcher) -> None:
    """server.stop() must also stop the batcher thread (tests and the
    CLI call stop(), not a separate teardown hook) — but only AFTER the
    grace drain: closing immediately would turn in-flight RPCs that
    haven't reached submit() yet into UNAVAILABLE during the window the
    caller asked to protect."""
    if batcher is None:
        return
    inner_stop = server.stop

    def stop(grace=None):
        ev = inner_stop(grace)
        if grace:
            def _close_after_drain():
                ev.wait()
                batcher.close()

            threading.Thread(target=_close_after_drain, daemon=True).start()
        else:
            batcher.close()
        return ev

    server.stop = stop


def _engine_wire_dtype(engine):
    """The dtype the decoder should land rows in: the engine's own
    compute dtype where it declares one (the float64 wire contract
    stops at the socket — decoding straight to the engine dtype kills
    the (N, D) float64 intermediate), float64 otherwise."""
    dt = getattr(engine, "dtype", None)
    if dt is None:
        return np.float64
    try:
        return np.dtype(dt)
    except TypeError:
        return np.float64


def _make_handler(engine, batcher: _Batcher | None):
    lock = threading.Lock()
    # Per-request width validation BEFORE coalescing: a bad request must
    # fail alone, not poison the shared batch it would have joined.
    expected_dim = getattr(getattr(engine, "model", None), "input_dim", None)
    wire_dtype = _engine_wire_dtype(engine)

    def process(request_bytes: bytes, context) -> bytes:
        _RPC_REQUESTS.labels(method="Process").inc()
        span, budget, md = _request_span(context, "Process")
        # SLO class rides x-tdn-class (missing/unknown -> standard):
        # queue priority + shed watermark in the scheduling core.
        slo_class = normalize_class(md.get(CLASS_HEADER))
        try:
            try:
                # Structure probe only on the fast path: a WireMatrix
                # carries shape/width for validation while the payload
                # stays untouched until the batcher lands it directly
                # in a staging buffer (one cast-copy end-to-end). The
                # fallback (non-uniform layout) decodes fully here.
                with _trace.TRACER.span("decode", span.ctx):
                    x = decode_matrix_lazy(request_bytes, dtype=wire_dtype)
            except ValueError as e:
                span.annotate(f"abort INVALID_ARGUMENT: bad Matrix: {e}")
                _abort(context, "Process", grpc.StatusCode.INVALID_ARGUMENT,
                       f"bad Matrix: {e}")
            span.set("rows", len(x))
            # Capture-completeness attrs (ISSUE 18): a bundle's root
            # span alone must be a replayable request.
            _annotate_capture_attrs(span, md, slo_class, budget)
            span.set("dim", int(x.shape[1]))
            if (
                batcher is not None
                and expected_dim is not None
                and x.shape[1] != expected_dim
            ):
                # The reference's dim-check path (grpc_node.py:149-153),
                # message shape matching pipeline.pad_batch's error.
                span.annotate("abort INVALID_ARGUMENT: width mismatch")
                _abort(
                    context, "Process", grpc.StatusCode.INVALID_ARGUMENT,
                    f"expected input of shape (N, {expected_dim}), got "
                    f"{tuple(x.shape)}",
                )
            try:
                if batcher is not None:
                    # Pass the RPC's remaining budget (deadline and/or
                    # client hint) so the worker never waits for a
                    # client that already gave up; the span context
                    # rides the pending entry through the pipeline.
                    out = batcher.submit(x, timeout=budget, ctx=span.ctx,
                                         slo_class=slo_class)
                else:
                    with lock, _trace.TRACER.activate(span):
                        out = engine.infer(x)
            except Exception as e:  # noqa: BLE001 — map to status codes
                span.annotate(f"error: {type(e).__name__}: {e}")
                _abort_for_exception(context, e, "inference", "Process")
            with _trace.TRACER.span("encode", span.ctx):
                # Engine-dtype result straight into the codec: the cast
                # to wire float64 lands per-stripe in the encode buffer
                # (the old np.asarray(out, np.float64) full-matrix
                # materialization is gone).
                return encode_matrix(out)
        finally:
            span.end()

    rpc = grpc.unary_unary_rpc_method_handler(
        process,
        request_deserializer=bytes,   # raw bytes in, our codec decodes
        response_serializer=bytes,
    )
    service = grpc.method_handlers_generic_handler(
        SERVICE_NAME, {"Process": rpc}
    )
    return service


def serve_engine(engine, port: int, *, max_workers: int = 10,
                 host: str = "0.0.0.0", coalesce: bool = True,
                 max_batch_rows: int = 65536, warm_rows: int = 0,
                 submit_timeout: float | None = 120.0,
                 pipeline_depth: int = 2,
                 max_pending_rows: int | None = None,
                 class_watermarks: dict | None = None,
                 interceptors=()):
    """Start a gRPC server bound to ``host:port``; returns
    ``(server, bound_port)`` (``port=0`` picks an ephemeral port;
    ``host="127.0.0.1"`` keeps self-checks off the network).

    ``max_workers=10`` is the reference's thread-pool size
    (``grpc_node.py:169``); unlimited message sizes match its client
    channel options (``run_grpc_inference.py:124-127``).

    ``coalesce=True`` (default) batches concurrent requests into shared
    device launches (:class:`_Batcher`; ``server.batcher`` exposes its
    counters); ``False`` restores the serialized one-request-at-a-time
    engine lock. ``server.stop()`` also shuts the batcher down.

    ``warm_rows > 0`` precompiles the coalescing bucket shapes (powers
    of two up to ``warm_rows``) before the port opens: each bucket is a
    distinct XLA program, and an unwarmed bucket pays its compile on
    the first unlucky request mix (~hundreds of ms) instead of at
    startup.

    ``submit_timeout`` bounds how long a coalescing gRPC worker waits
    for its batch (``None`` = forever): a wedged engine turns into
    DEADLINE_EXCEEDED for the affected requests instead of stranding
    every worker thread.

    ``pipeline_depth`` sets the batcher's launch-ahead window (2 =
    double-buffered default: batch N+1 stages and launches while batch
    N materializes; 1 = the strictly serial legacy loop, which tests
    use as the control).

    ``max_pending_rows`` is the admission-control watermark (``tdn up
    --max-pending-rows``): a submit that would queue past it is shed
    with RESOURCE_EXHAUSTED instead of joining an unbounded backlog
    (None = unbounded, the legacy behavior). ``class_watermarks``
    overrides the per-SLO-class shed fractions of that watermark
    (``tdn up --class-watermarks``; docs/ROBUSTNESS.md "Degradation
    ladder"). ``interceptors`` are gRPC server interceptors — the
    fault-injection seam (:mod:`tpu_dist_nn.testing.faults`).
    """
    server = _new_grpc_server(max_workers, interceptors)
    batcher = (
        _Batcher(engine, max_batch_rows, submit_timeout,
                 pipeline_depth=pipeline_depth,
                 max_pending_rows=max_pending_rows,
                 class_watermarks=class_watermarks)
        if coalesce else None
    )
    if coalesce and warm_rows > 0:
        # Bucket shapes only exist on the coalescing path; the lock
        # path forwards raw client shapes and would never hit them.
        if hasattr(engine, "warm_buckets"):
            engine.warm_buckets(warm_rows)
        else:
            dim = getattr(getattr(engine, "model", None), "input_dim", None)
            if dim is not None:
                n = 1
                while n <= warm_rows:
                    engine.infer(np.zeros((n, dim)))
                    n *= 2
    server.add_generic_rpc_handlers((_make_handler(engine, batcher),))
    bound = _bind_or_close(server, host, port, batcher)
    server.batcher = batcher
    _wrap_server_stop(server, batcher)
    server.start()
    slog.info("server.start", method="Process", port=bound,
              coalesce=coalesce, pipeline_depth=pipeline_depth,
              warm_rows=warm_rows,
              max_pending_rows=max_pending_rows)
    return server, bound


def _make_generate_handler(run_submit, prompt_len: int, vocab_size: int,
                           max_new_tokens: int | None = None):
    """The Generate method: Matrix of token ids (N, prompt_len) ->
    Matrix (N, prompt_len + max_new_tokens). Same wire format, same
    status taxonomy as Process."""

    def generate(request_bytes: bytes, context) -> bytes:
        _RPC_REQUESTS.labels(method="Generate").inc()
        span, budget, md = _request_span(context, "Generate")
        slo_class = normalize_class(md.get(CLASS_HEADER))
        _annotate_capture_attrs(span, md, slo_class, budget,
                                prompt_len=prompt_len,
                                max_new_tokens=max_new_tokens)
        try:
            try:
                with _trace.TRACER.span("decode", span.ctx):
                    x = decode_matrix(request_bytes)
            except ValueError as e:
                span.annotate(f"abort INVALID_ARGUMENT: bad Matrix: {e}")
                _abort(context, "Generate", grpc.StatusCode.INVALID_ARGUMENT,
                       f"bad Matrix: {e}")
            span.set("rows", len(x))
            if x.ndim != 2 or x.shape[1] != prompt_len:
                # The decode program is compiled for ONE static prompt
                # length per endpoint (static shapes under jit); clients
                # pad/pack to it.
                span.annotate("abort INVALID_ARGUMENT: prompt shape")
                _abort(
                    context, "Generate", grpc.StatusCode.INVALID_ARGUMENT,
                    f"expected prompts of shape (N, {prompt_len}), got "
                    f"{tuple(x.shape)}",
                )
            ids = x.astype(np.int64)
            if (ids != x).any() or (ids < 0).any() or (ids >= vocab_size).any():
                span.annotate("abort INVALID_ARGUMENT: token id range")
                _abort(
                    context, "Generate", grpc.StatusCode.INVALID_ARGUMENT,
                    f"prompts must be integer token ids in [0, {vocab_size})",
                )
            try:
                out = run_submit(ids.astype(np.int32), budget, span.ctx,
                                 slo_class)
            except Exception as e:  # noqa: BLE001 — map to status codes
                span.annotate(f"error: {type(e).__name__}: {e}")
                _abort_for_exception(context, e, "generation", "Generate")
            with _trace.TRACER.span("encode", span.ctx):
                # Token ids encode straight from the decoder's int32
                # output — the per-stripe cast to wire float64 happens
                # inside the codec's one preallocated buffer.
                return encode_matrix(out)
        finally:
            span.end()

    rpc = grpc.unary_unary_rpc_method_handler(
        generate, request_deserializer=bytes, response_serializer=bytes
    )
    return grpc.method_handlers_generic_handler(
        SERVICE_NAME, {"Generate": rpc}
    )


def _status_from_code(name: str):
    """Stream END-frame / FrameworkError code name -> gRPC status (the
    stream-side twin of _abort_for_exception's isinstance ladder — by
    the time an error reaches a TokenStream terminal it is a string)."""
    if name == "INTEGRITY":
        # IntegrityError.code is the framework taxonomy name; its wire
        # status is DATA_LOSS (same mapping as _abort_for_exception).
        return grpc.StatusCode.DATA_LOSS
    try:
        return grpc.StatusCode[name]
    except KeyError:
        return grpc.StatusCode.INTERNAL


def _annotate_capture_attrs(span, md, slo_class, budget, *,
                            prompt_len=None, max_new_tokens=None,
                            stream=False):
    """Capture-completeness attrs (ISSUE 18): the handler root span
    carries every request attribute :mod:`tpu_dist_nn.obs.replay`
    needs, so an incident bundle's trace.json alone is a replayable
    workload. Attrs ride ``Span.set`` -> chrome ``args`` and survive
    ``stitch_chrome_traces`` (which passes args through verbatim)."""
    span.set("slo_class", slo_class)
    sess = md.get(SESSION_HEADER)
    if sess:
        span.set("session", sess)
    if prompt_len is not None:
        span.set("prompt_len", int(prompt_len))
    if max_new_tokens is not None:
        span.set("max_new_tokens", int(max_new_tokens))
    if budget is not None:
        span.set("budget_ms", int(budget * 1000))
    if stream:
        span.set("stream", True)


def _make_generate_stream_handler(run_submit_stream, prompt_len: int,
                                  vocab_size: int,
                                  max_new_tokens: int | None = None):
    """The GenerateStream method (PR 16): ONE prompt row in, a stream
    of wire frames out — TOKENS deltas as the continuous scheduler
    publishes them (serving/stream.py), then exactly one END frame
    naming the terminal (eos / max_tokens). Same Matrix request wire
    and status taxonomy as Generate; frames per serving/wire.py.

    Continuous-scheduler only: the static run-to-completion decode has
    no step-granular tokens to stream, so a static endpoint leaves the
    method unregistered (UNIMPLEMENTED — the honest answer).
    """

    from tpu_dist_nn.utils.profiling import host_span

    def generate_stream(request_bytes: bytes, context):
        _RPC_REQUESTS.labels(method="GenerateStream").inc()
        span, budget, md = _request_span(context, "GenerateStream")
        slo_class = normalize_class(md.get(CLASS_HEADER))
        _annotate_capture_attrs(span, md, slo_class, budget,
                                prompt_len=prompt_len,
                                max_new_tokens=max_new_tokens,
                                stream=True)
        stream = None
        try:
            try:
                with _trace.TRACER.span("decode", span.ctx):
                    x = decode_matrix(request_bytes)
            except ValueError as e:
                span.annotate(f"abort INVALID_ARGUMENT: bad Matrix: {e}")
                _abort(context, "GenerateStream",
                       grpc.StatusCode.INVALID_ARGUMENT, f"bad Matrix: {e}")
            if x.ndim != 2 or x.shape != (1, prompt_len):
                # One stream = one sequence: frame order and failover
                # resume are per-sequence concepts. A client streams N
                # prompts over N concurrent RPCs.
                span.annotate("abort INVALID_ARGUMENT: prompt shape")
                _abort(
                    context, "GenerateStream",
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"GenerateStream takes ONE prompt of shape "
                    f"(1, {prompt_len}), got {tuple(x.shape)}",
                )
            ids = x.astype(np.int64)
            if (ids != x).any() or (ids < 0).any() or (ids >= vocab_size).any():
                span.annotate("abort INVALID_ARGUMENT: token id range")
                _abort(
                    context, "GenerateStream",
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"prompts must be integer token ids in [0, {vocab_size})",
                )
            resume = None
            raw = md.get(STREAM_RESUME_HEADER)
            if raw:
                # The router's mid-stream-failover prefix: tokens the
                # client already received from the dead replica. Rides
                # the preemption-resume path (forced-token replay) so
                # the stream continues bit-identically at temperature 0.
                try:
                    resume = [int(t) for t in raw.split(",")]
                except ValueError:
                    span.annotate("abort INVALID_ARGUMENT: resume header")
                    _abort(
                        context, "GenerateStream",
                        grpc.StatusCode.INVALID_ARGUMENT,
                        f"bad {STREAM_RESUME_HEADER}: expected "
                        "comma-separated token ids",
                    )
                if len(resume) > STREAM_RESUME_MAX_TOKENS:
                    # Bit-exact resume needs EVERY delivered token; a
                    # clamped suffix would replay against KV state this
                    # replica does not have. Fail loudly (the router
                    # refuses to even attempt it — this is the backstop
                    # for hand-rolled clients).
                    span.annotate("abort OUT_OF_RANGE: resume too long")
                    _abort(
                        context, "GenerateStream",
                        grpc.StatusCode.OUT_OF_RANGE,
                        f"{STREAM_RESUME_HEADER} carries {len(resume)} "
                        f"tokens; the metadata-borne resume path is "
                        f"bounded at {STREAM_RESUME_MAX_TOKENS}",
                    )
            # Streams surface the trace id in INITIAL metadata (ISSUE
            # 16 satellite): trailing only lands at stream end — useless
            # while debugging a stream that is wedged mid-flight. Unary
            # methods keep the trailing-only contract (_request_span).
            try:
                context.send_initial_metadata(
                    ((_trace.TRACE_ID_HEADER, span.ctx.trace_id),)
                )
            except Exception:  # noqa: BLE001 — in-process fakes
                pass
            try:
                stream = run_submit_stream(
                    x.astype(np.int32), budget, span.ctx, slo_class, resume
                )
            except Exception as e:  # noqa: BLE001 — map to status codes
                span.annotate(f"error: {type(e).__name__}: {e}")
                _abort_for_exception(context, e, "stream admission",
                                     "GenerateStream")
            if resume:
                note_stream_resumed()
                span.set("resume_tokens", len(resume))
            # Client disconnect / gRPC cancellation must free the decode
            # slot: the callback flips the channel, the next publish
            # returns False, and the scheduler's reap pass releases the
            # slot + prefix-cache refs on its next iteration.
            try:
                context.add_callback(stream.cancel)
            except Exception:  # noqa: BLE001 — in-process fakes
                pass
            ntok = 0
            while True:
                # The budget is the STREAM deadline (docs/ROBUSTNESS.md):
                # it bounds each next-token gap — admission + prefill
                # before the first frame, decode cadence after — not
                # total stream duration. None = wait for the scheduler's
                # own terminal (every exit path reaches finish()).
                ev = stream.next_event(budget)
                if ev is None:
                    stream.cancel()
                    span.annotate("abort DEADLINE_EXCEEDED: token gap")
                    _abort(
                        context, "GenerateStream",
                        grpc.StatusCode.DEADLINE_EXCEEDED,
                        f"no token within the {budget:.3f}s stream gap "
                        "budget",
                    )
                kind, data = ev
                if kind == "tokens":
                    ntok += len(data)
                    # The generator resumes on the thread that left it:
                    # the span is this handler away with the frame
                    # (TokenStream counts the same as stream_send_ns).
                    with host_span("tdn.stream.send"):
                        yield encode_token_frame(data)
                    continue
                if data["reason"] == "error":
                    span.annotate(
                        f"stream error {data['code']}: {data['message']}"
                    )
                    _abort(context, "GenerateStream",
                           _status_from_code(data["code"]),
                           data["message"] or "stream failed")
                span.set("tokens", ntok)
                yield encode_end_frame(data["reason"], data["code"],
                                       data["message"])
                return
        finally:
            if stream is not None:
                stream.cancel()  # no-op after a clean terminal
            span.end()

    rpc = grpc.unary_stream_rpc_method_handler(
        generate_stream, request_deserializer=bytes,
        response_serializer=bytes,
    )
    return grpc.method_handlers_generic_handler(
        SERVICE_NAME, {"GenerateStream": rpc}
    )


def serve_lm_generate(params, cfg, port: int, *, max_new_tokens: int,
                      prompt_len: int, num_stages: int = 1,
                      num_groups: int | None = None,
                      temperature: float = 0.0, top_k: int | None = None,
                      top_p: float | None = None, seed: int = 0,
                      host: str = "0.0.0.0", max_workers: int = 10,
                      coalesce: bool = True, warm_rows: int = 0,
                      submit_timeout: float | None = 120.0,
                      pipeline_depth: int = 2,
                      max_pending_rows: int | None = None,
                      scheduler: str = "auto", gen_slots: int = 8,
                      eos_id: int | None = None,
                      prefix_cache_blocks: int = 0,
                      prefill_chunk: int | None = None,
                      class_watermarks: dict | None = None,
                      interceptors=()):
    """Serve LM GENERATION over the reference wire.

    ``scheduler`` picks the decode scheduling policy:

    * ``"continuous"`` — iteration-level continuous batching
      (:class:`~tpu_dist_nn.serving.continuous.ContinuousScheduler`):
      a fixed ladder of ``gen_slots`` KV-cache slots, requests admitted
      at decode-STEP granularity and retired early on ``eos_id`` or
      their token budget, so a short request never pays for a long
      neighbor and late arrivals don't convoy behind a full batch.
      Single-chip only (``num_stages == 1``).
    * ``"static"`` — the legacy run-to-completion coalescing batcher in
      front of :func:`~tpu_dist_nn.models.generate.generate` (kept as
      the control, exactly like ``pipeline_depth=1`` for the Process
      path, and as the only scheduler of the pipelined placement).
    * ``"auto"`` (default) — continuous when ``num_stages == 1`` and
      ``coalesce`` is on; static for the pipelined placement (whose
      overlapped round-robin decoder schedules groups itself) and for
      ``coalesce=False`` (the lock-serialized legacy arm, which keeps
      its ``server.batcher is None`` contract). ``pipeline_depth``
      applies to the static batcher only — the continuous scheduler's
      loop has no launch-ahead analogue.

    ``num_stages > 1`` decodes IN the pipeline placement with the
    OVERLAPPED round-robin decoder
    (:func:`~tpu_dist_nn.parallel.pp_generate.make_pipeline_generate_overlapped`):
    ``num_groups`` (default ``max(num_stages, 2)``) request groups ride
    the stage ring so every stage does useful work every tick — the
    batcher's coalesced rows are exactly the decoder's group slots
    (rows pad to a ``(G, Bg)`` grid, ``Bg`` power-of-two bucketed).

    One endpoint = one decode config (prompt_len, max_new_tokens,
    sampling knobs are compile-time static). Sampling at
    ``temperature > 0`` folds a per-batch counter into the key so
    repeated identical prompts draw fresh continuations. ``eos_id``
    enables stop-token semantics on BOTH schedulers (same freeze/pad
    rule, so their ``temperature == 0`` outputs are identical).

    ``prefix_cache_blocks > 0`` enables the continuous scheduler's
    shared-prefix KV reuse (ref-counted pool blocks, copy-on-write
    admission) and ``prefill_chunk`` bounds tokens per prefill launch
    so long prompts interleave with resident decodes — both continuous-
    scheduler features (docs/PERF.md "Prefix caching & chunked
    prefill"); requesting either with a static resolution is an error
    rather than a silently-ignored perf flag.

    Returns ``(server, bound_port)``; ``server.batcher`` exposes the
    scheduling counters (the continuous scheduler satisfies the
    batcher counter contract; ``server.scheduler`` names it
    explicitly, None on the static path). ``warm_rows > 0``
    precompiles the continuous prefill-at-slot + step kernels, or the
    static bucket ladder, before the port opens.
    """
    import itertools

    import jax

    from tpu_dist_nn.models.generate import validate_generate_args
    from tpu_dist_nn.models.transformer import TransformerConfig

    if scheduler not in ("auto", "static", "continuous"):
        raise ValueError(
            f"scheduler must be 'auto', 'static' or 'continuous', "
            f"got {scheduler!r}"
        )
    if scheduler == "continuous" and num_stages > 1:
        raise ValueError(
            "scheduler='continuous' is single-chip (its slot cache "
            "lives on one device); the pipelined placement's overlapped "
            "round-robin decoder already schedules groups — use "
            "scheduler='static' (or 'auto') with num_stages > 1"
        )
    if scheduler == "continuous" and not coalesce:
        raise ValueError(
            "coalesce=False is the lock-serialized legacy arm of the "
            "STATIC scheduler; the continuous scheduler owns the device "
            "by construction — drop coalesce=False or use "
            "scheduler='static'"
        )
    if scheduler == "auto":
        # coalesce=False keeps its documented meaning (the serialized
        # static lock path, server.batcher is None) rather than being
        # silently consumed by the continuous default.
        scheduler = (
            "static" if num_stages > 1 or not coalesce else "continuous"
        )
    if scheduler != "continuous" and not isinstance(cfg, TransformerConfig):
        raise ValueError(
            f"scheduler={scheduler!r} decodes the Tiny-Transformer block "
            f"only; a {type(cfg).__name__} model is served by "
            "scheduler='continuous' (models/slot_model.py)"
        )
    if scheduler != "continuous" and (
        prefix_cache_blocks or prefill_chunk is not None
    ):
        raise ValueError(
            "prefix_cache_blocks / prefill_chunk are continuous-"
            "scheduler features (the static run-to-completion decode "
            "has no slot cache to reuse or chunk into); drop them or "
            "serve scheduler='continuous'"
        )
    params = cfg.cast_params(params)
    N = int(max_new_tokens)
    T = int(prompt_len)
    counter = itertools.count()
    base_key = jax.random.key(seed)
    # Validate the WHOLE decode contract (lengths, causality, sampling
    # ranges, greedy-vs-top_k conflicts) ONCE at construction: a bad
    # combination must fail fast here, not surface as a per-RPC
    # INTERNAL from inside the decode runner (ADVICE r5).
    validate_generate_args(
        cfg, T, N, temperature, top_k, top_p,
        base_key if temperature > 0 else None, eos_id,
    )

    if scheduler == "continuous":
        from tpu_dist_nn.serving.continuous import ContinuousScheduler

        sched = ContinuousScheduler(
            params, cfg, slots=gen_slots, prompt_len=T, max_new_tokens=N,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, seed=seed, submit_timeout=submit_timeout,
            max_pending_rows=max_pending_rows,
            prefix_cache_blocks=prefix_cache_blocks,
            prefill_chunk=prefill_chunk,
            class_watermarks=class_watermarks,
        )
        if warm_rows > 0:
            sched.warm()

        def run_submit(ids: np.ndarray, time_remaining, ctx=None,
                       slo_class: str = "standard"):
            return sched.submit(ids, timeout=time_remaining, ctx=ctx,
                                slo_class=slo_class)

        def run_submit_stream(ids: np.ndarray, time_remaining, ctx=None,
                              slo_class: str = "standard", resume=None):
            return sched.submit_stream(
                ids, timeout=time_remaining, ctx=ctx, slo_class=slo_class,
                resume_tokens=resume,
            )

        server = _new_grpc_server(max_workers, interceptors)
        server.add_generic_rpc_handlers((
            _make_generate_handler(run_submit, T, cfg.vocab_size,
                                   max_new_tokens=N),
            _make_generate_stream_handler(
                run_submit_stream, T, cfg.vocab_size, max_new_tokens=N
            ),
        ))
        bound = _bind_or_close(server, host, port, sched)
        # The scheduler fulfils the batcher counter/close contract, so
        # stop-wrapping, GracefulDrain, and the runtime sampler work
        # unchanged; `scheduler` is the explicit handle.
        server.batcher = sched
        server.scheduler = sched
        _wrap_server_stop(server, sched)
        server.start()
        slog.info("server.start", method="Generate",
                  scheduler="continuous", port=bound, gen_slots=gen_slots,
                  prompt_len=T, max_new_tokens=N, eos_id=eos_id,
                  prefix_cache_blocks=prefix_cache_blocks,
                  prefill_chunk=prefill_chunk)
        return server, bound

    if num_stages > 1:
        if eos_id is not None:
            raise ValueError(
                "eos_id is not supported by the pipelined overlapped "
                "decoder (its round-robin loop has no done-mask); "
                "serve num_stages == 1 for stop-token semantics"
            )
        from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
        from tpu_dist_nn.parallel.pp_generate import (
            make_pipeline_generate_overlapped,
        )
        from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks

        S = int(num_stages)
        G = int(num_groups) if num_groups is not None else max(S, 2)
        mesh = build_mesh(MeshSpec(stage=S))
        params_served = dict(
            params, blocks=shard_blocks(params["blocks"], S)
        )
        fn = make_pipeline_generate_overlapped(
            mesh, cfg, S, N, G, temperature=temperature, top_k=top_k,
            top_p=top_p,
        )

        def run(rows: np.ndarray):
            n = len(rows)
            bg = -(-n // G)  # ceil: the batcher's bucket already padded
            grid = n if n == bg * G else bg * G
            if grid != n:
                rows = np.concatenate(
                    [rows, np.zeros((grid - n, T), rows.dtype)]
                )
            prompts = rows.reshape(G, -1, T)
            key = (
                jax.random.fold_in(base_key, next(counter))
                if temperature > 0 else None
            )
            # Return the DEVICE array (reshape/slice are lazy jax ops):
            # the batcher's drain stage pays the one host sync, so the
            # dispatch stage can stage+launch the next decode batch
            # while this one runs.
            out = fn(params_served, prompts, key=key)
            return out.reshape(-1, T + N)[:n]
    else:
        import jax.numpy as jnp

        from tpu_dist_nn.models.generate import generate

        params_served = params

        def run(rows: np.ndarray):
            key = (
                jax.random.fold_in(base_key, next(counter))
                if temperature > 0 else None
            )
            out = generate(
                params_served, cfg, rows, N, temperature=temperature,
                top_k=top_k, top_p=top_p, key=key, eos_id=eos_id,
            )
            # Device-side concat keeps the handle un-materialized for
            # the batcher's drain stage (same overlap contract as the
            # pipelined runner above).
            return jnp.concatenate([jnp.asarray(rows, out.dtype), out], axis=1)

    server = _new_grpc_server(max_workers, interceptors)
    # Goodput accounting for the run-to-completion decode: one record
    # per coalesced launch AT DRAIN (EOS-frozen positions only exist in
    # the materialized sequences). The coalesce=False lock path is the
    # legacy A/B control arm and stays unaccounted; the num_stages>1
    # grid pad beyond the bucket is invisible here (named model
    # simplification — docs/OBSERVABILITY.md "Goodput & MFU").
    from tpu_dist_nn.obs.goodput import GOODPUT, LMFlopModel

    gp_model = LMFlopModel.from_config(cfg, T + N - 1 if N > 1 else T)
    # The pipelined placement decodes over num_stages devices; the
    # single-chip path over one — the peak must match the footprint.
    GOODPUT.ensure_peak(device_count=max(int(num_stages), 1))

    def account(out, useful_rows, launched_rows, dead_rows=0):
        GOODPUT.record_static_generate(
            gp_model, out, useful_rows, launched_rows, T, eos_id,
            dead_rows=dead_rows,
        )

    batcher = (
        _Batcher(None, 65536, submit_timeout, run_fn=run, method="Generate",
                 pipeline_depth=pipeline_depth,
                 max_pending_rows=max_pending_rows, account_fn=account,
                 class_watermarks=class_watermarks)
        if coalesce else None
    )
    lock = threading.Lock()

    def run_submit(ids: np.ndarray, time_remaining, ctx=None,
                   slo_class: str = "standard"):
        if batcher is not None:
            return batcher.submit(ids, timeout=time_remaining, ctx=ctx,
                                  slo_class=slo_class)
        with lock:
            return run(ids)

    if warm_rows > 0:
        n = 1
        while n <= warm_rows:
            # np.asarray forces the decode so the compile really lands
            # before the port opens (run returns a lazy device array).
            np.asarray(run(np.zeros((n, T), np.int32)))
            n *= 2
    server.add_generic_rpc_handlers(
        (_make_generate_handler(run_submit, T, cfg.vocab_size,
                                max_new_tokens=N),)
    )
    bound = _bind_or_close(server, host, port, batcher)
    server.batcher = batcher
    server.scheduler = None  # continuous-mode handle; static here
    _wrap_server_stop(server, batcher)
    server.start()
    slog.info("server.start", method="Generate", scheduler="static",
              port=bound, num_stages=num_stages, prompt_len=T,
              max_new_tokens=N, coalesce=coalesce)
    return server, bound


_CLIENT_DEFAULT = object()  # "use the built-in default" sentinel


class StreamReply:
    """One streamed generation (``GrpcClient.generate_stream``).

    Iterate to receive token ids as the server publishes them; when
    iteration ends normally, ``finish`` holds the terminal frame
    (``{"reason": "eos" | "max_tokens", ...}``). ``trace_id`` carries
    the server's trace id from INITIAL metadata — available as soon as
    the stream opens, so a wedged stream can be debugged (``tdn trace``)
    before it ever terminates. ``cancel()`` tears the RPC down; the
    server frees the decode slot on its next scheduler iteration.

    A broken stream raises ``grpc.RpcError`` (enriched with
    ``server_trace_id``) from the iterator. There is deliberately NO
    client-side retry: a mid-stream failure is not idempotent from here
    (tokens were already delivered) — failover is the ROUTER's job,
    which resumes the stream on another replica via forced-token replay
    (docs/SCALING.md "Streaming failover").
    """

    def __init__(self, call, span):
        self._call = call
        self._span = span
        self._ended = False
        self.finish: dict | None = None
        self.trace_id: str | None = None

    def cancel(self) -> None:
        self._call.cancel()

    def _end_span(self) -> None:
        if not self._ended:
            self._ended = True
            self._span.end()

    def __iter__(self):
        try:
            try:
                for k, v in self._call.initial_metadata() or ():
                    if k == _trace.TRACE_ID_HEADER:
                        self.trace_id = v
            except Exception:  # noqa: BLE001 — metadata is best-effort
                pass
            for frame in self._call:
                kind, data = decode_frame(frame)
                if kind == "tokens":
                    yield from data
                else:
                    self.finish = data
                    self._span.annotate(f"end: {data['reason']}")
                    return
            # Stream closed OK without an END frame: a server that died
            # between its last TOKENS flush and the terminal. Surface it
            # rather than pretend the generation completed.
            raise grpc.RpcError(
                "stream closed without a terminal END frame"
            )
        except grpc.RpcError as e:
            code, trace_id = GrpcClient._enrich(e, self._span)
            if trace_id is not None:
                self.trace_id = trace_id
            self._span.annotate(
                f"stream failed {code}: server trace {trace_id}"
            )
            raise
        finally:
            self._end_span()


class GrpcClient:
    """Minimal client for the Process RPC — the ``tdn infer --target``
    transport (the reference client's ``run_batch_inference`` analogue,
    ``run_grpc_inference.py:112-158``: one persistent channel, unlimited
    message sizes, float64 rows).

    Resilient by default (docs/ROBUSTNESS.md): a transient failure
    (UNAVAILABLE / DEADLINE_EXCEEDED) is retried under a
    :class:`~tpu_dist_nn.serving.resilience.RetryPolicy` with capped
    jittered backoff, every attempt's deadline carved from the
    REMAINING ``timeout`` (a retried call never exceeds the budget of
    the original); a per-target
    :class:`~tpu_dist_nn.serving.resilience.CircuitBreaker` fails fast
    with :class:`~tpu_dist_nn.utils.errors.UnavailableError` while the
    target is known-dead. Pass ``retry=None`` / ``breaker=None`` to
    opt out (the reference's one-attempt behavior).

    ``wait_for_ready=True`` blocks construction on channel readiness
    (the reference orchestrator's TCP poll, run_grpc_fcnn.py:157-172)
    for up to ``ready_timeout`` seconds, raising ``UnavailableError``
    on expiry — instead of the first RPC silently eating the connect
    latency or failing with an opaque UNAVAILABLE.

    ``session_key`` rides every call as ``x-tdn-session`` metadata:
    against the multi-replica router (docs/SCALING.md) it pins this
    client's follow-up Generate requests to the replica holding their
    KV/prefix-cache state; a single engine server ignores it. Per-call
    override via ``process(..., session_key=)`` / ``generate(...,
    session_key=)`` for clients multiplexing many sessions over one
    channel.
    """

    def __init__(self, target: str, timeout: float = 30.0, *,
                 retry=_CLIENT_DEFAULT, breaker=_CLIENT_DEFAULT,
                 wait_for_ready: bool = False, ready_timeout: float = 5.0,
                 session_key: str | None = None,
                 slo_class: str | None = None):
        from tpu_dist_nn.serving.resilience import CircuitBreaker, RetryPolicy

        self.target = target
        self.timeout = timeout
        self.session_key = session_key
        # SLO class rides every call as x-tdn-class (None = send no
        # header — the server defaults to "standard"): queue priority,
        # shed watermark, and — behind a router — the hedging
        # exemption for best_effort (docs/ROBUSTNESS.md "Degradation
        # ladder"). Per-call override via process/generate(slo_class=).
        self.slo_class = slo_class
        self._retry = RetryPolicy() if retry is _CLIENT_DEFAULT else retry
        self._breaker = (
            CircuitBreaker.for_target(target)
            if breaker is _CLIENT_DEFAULT else breaker
        )
        self._channel = grpc.insecure_channel(
            target,
            options=[
                ("grpc.max_send_message_length", -1),
                ("grpc.max_receive_message_length", -1),
            ],
        )
        if wait_for_ready:
            from tpu_dist_nn.utils.errors import UnavailableError

            fut = grpc.channel_ready_future(self._channel)
            try:
                fut.result(timeout=ready_timeout)
            except grpc.FutureTimeoutError:
                fut.cancel()
                self._channel.close()
                raise UnavailableError(
                    f"server at {target} not ready within {ready_timeout}s "
                    "(readiness poll timed out; is it up?)"
                ) from None
        self._call = self._channel.unary_unary(
            PROCESS_METHOD,
            request_serializer=bytes,
            response_deserializer=bytes,
        )
        self._call_generate = self._channel.unary_unary(
            GENERATE_METHOD,
            request_serializer=bytes,
            response_deserializer=bytes,
        )
        self._call_generate_stream = self._channel.unary_stream(
            GENERATE_STREAM_METHOD,
            request_serializer=bytes,
            response_deserializer=bytes,
        )

    @staticmethod
    def _enrich(e, span) -> tuple:
        """Attach ``server_trace_id`` / ``retry_after_ms`` + extract
        the status code from a failed RPC (best-effort — in-process
        fakes may lack both)."""
        trace_id = span.ctx.trace_id  # the id we propagated
        retry_after = None
        try:
            for k, v in e.trailing_metadata() or ():
                if k == _trace.TRACE_ID_HEADER:
                    trace_id = v  # the server's own root, if any
                elif k == RETRY_AFTER_HEADER:
                    try:
                        retry_after = int(v)
                    except (TypeError, ValueError):
                        pass  # a garbled hint is no hint
        except Exception:  # noqa: BLE001 — best-effort enrichment
            pass
        e.server_trace_id = trace_id
        # The shed reply's backoff hint (x-tdn-retry-after-ms): the
        # server's drain-rate-derived floor for the next attempt.
        e.retry_after_ms = retry_after
        code = None
        try:
            code = e.code()
        except Exception:  # noqa: BLE001
            pass
        return code, trace_id

    def _traced_call(self, call, method: str, payload: bytes,
                     session_key=_CLIENT_DEFAULT,
                     slo_class=_CLIENT_DEFAULT) -> bytes:
        """One LOGICAL call (original attempt + bounded retries) under
        one client span: the trace context and the remaining-budget
        hint ride the metadata out on every attempt; a final failure
        comes back NAMING the server-side trace (``e.server_trace_id``)
        so the operator pulls exactly the right span tree from
        ``/trace`` instead of guessing from timestamps. Retried
        attempts are annotated onto the span and counted in
        ``tdn_client_retries_total``."""
        from tpu_dist_nn.serving.resilience import CLIENT_RETRIES
        from tpu_dist_nn.utils.errors import UnavailableError

        policy, breaker = self._retry, self._breaker
        session = (
            self.session_key if session_key is _CLIENT_DEFAULT
            else session_key
        )
        cls = (
            self.slo_class if slo_class is _CLIENT_DEFAULT else slo_class
        )
        span = _trace.TRACER.start(f"client.{method}")
        deadline = (
            time.monotonic() + self.timeout if self.timeout is not None
            else None
        )
        attempt = 0
        last_err = None
        try:
            while True:
                attempt += 1
                if breaker is not None and not breaker.allow():
                    span.annotate(f"breaker open for {self.target}: fail-fast")
                    raise UnavailableError(
                        f"circuit breaker open for {self.target} (too many "
                        "consecutive failures; cooling down)"
                    )
                remaining = None
                if deadline is not None:
                    # Budget carving: this attempt gets whatever the
                    # ORIGINAL call has left, never a fresh window.
                    remaining = deadline - time.monotonic()
                    if last_err is not None and remaining <= 0.001:
                        # A backoff sleep overshot the budget: re-raise
                        # the last REAL outcome instead of issuing a
                        # ~0ms attempt that fails client-side and
                        # counts a phantom failure against the breaker.
                        span.annotate(
                            f"retry budget exhausted before attempt {attempt}"
                        )
                        raise last_err
                metadata = ((_trace.TRACE_HEADER, span.ctx.header()),)
                if session is not None:
                    # Session affinity key for the router; an engine
                    # server just never reads it.
                    metadata += ((SESSION_HEADER, session),)
                if cls is not None:
                    # SLO class: admission priority + shed watermark
                    # at the scheduler, hedging exemption at the
                    # router (best_effort).
                    metadata += ((CLASS_HEADER, cls),)
                if remaining is not None:
                    # Remaining-budget hint (the grpc-timeout analogue,
                    # readable by the batcher even where a proxy
                    # rewrites deadlines).
                    metadata += (
                        (_trace.TIMEOUT_HEADER,
                         str(max(0, int(remaining * 1000)))),
                    )
                try:
                    reply = call(payload, timeout=remaining,
                                 metadata=metadata)
                    if breaker is not None:
                        breaker.record_success()
                    if attempt > 1:
                        span.annotate(f"succeeded on attempt {attempt}")
                    return reply
                except grpc.RpcError as e:
                    from tpu_dist_nn.serving.resilience import (
                        RETRYABLE_CODES,
                        _code_name,
                    )

                    code, trace_id = self._enrich(e, span)
                    last_err = e
                    # Transience classification feeds the breaker even
                    # with retries disabled (a no-retry client still
                    # learns the target is down); only TRANSIENT
                    # statuses say anything about target health —
                    # INVALID_ARGUMENT must not trip the breaker.
                    transient = (
                        policy.retryable(code) if policy is not None
                        else _code_name(code) in RETRYABLE_CODES
                    )
                    if breaker is not None:
                        if transient:
                            breaker.record_failure()
                        else:
                            # A non-transient status means the target
                            # RESPONDED — reachability evidence. This
                            # also closes the half-open probe instead
                            # of leaving it wedged (a probe answered
                            # INVALID_ARGUMENT proves the server is
                            # back even though the request was bad).
                            breaker.record_success()
                    # A shed (RESOURCE_EXHAUSTED) is retryable too —
                    # the server is healthy and explicitly asked for a
                    # paced retry — but stays NON-transient for the
                    # breaker above: a shed storm must never open
                    # breakers to a healthy server.
                    shed = _code_name(code) == "RESOURCE_EXHAUSTED"
                    retryable = policy is not None and (transient or shed)
                    out_of_attempts = (
                        policy is None or attempt >= policy.max_attempts
                    )
                    # The server's drain-rate hint is the backoff
                    # FLOOR: jitter still de-synchronizes the herd
                    # above it, but nobody retries before the backlog
                    # can have moved.
                    floor = (
                        e.retry_after_ms / 1000.0
                        if getattr(e, "retry_after_ms", None) else None
                    )
                    delay = (
                        0.0 if out_of_attempts
                        else policy.backoff(attempt, floor=floor)
                    )
                    out_of_budget = (
                        deadline is not None
                        and time.monotonic() + delay >= deadline
                    )
                    if not retryable or out_of_attempts or out_of_budget:
                        why = (
                            "not retryable" if not retryable
                            else "attempts exhausted" if out_of_attempts
                            else "retry budget exhausted"
                        )
                        span.annotate(
                            f"rpc error {code} on attempt {attempt} ({why}): "
                            f"server trace {trace_id}"
                        )
                        # Rate-limited: a dead target under a client
                        # loop logs its first occurrences then 1/s, not
                        # one line per failed RPC.
                        slog.warning(
                            "client.rpc_failed", method=method,
                            target=self.target, code=str(code),
                            attempt=attempt, why=why, trace_id=trace_id,
                            hint="pull the server span tree with "
                                 "`tdn trace --target <metrics-port>`",
                        )
                        raise
                    CLIENT_RETRIES.labels(method=method).inc()
                    span.annotate(
                        f"retry {attempt} after {code}: backoff {delay:.4f}s"
                    )
                    policy.sleep(delay)
        finally:
            span.end()

    def process(self, x: np.ndarray,
                session_key=_CLIENT_DEFAULT,
                slo_class=_CLIENT_DEFAULT) -> np.ndarray:
        # The codec owns the ONE cast to wire float64 (per-stripe into
        # its output buffer) — pre-casting here would materialize a
        # float64 copy just for encode_matrix to walk.
        reply = self._traced_call(
            self._call, "Process", encode_matrix(x),
            session_key=session_key, slo_class=slo_class,
        )
        return decode_matrix(reply)

    def generate(self, prompts: np.ndarray,
                 session_key=_CLIENT_DEFAULT,
                 slo_class=_CLIENT_DEFAULT) -> np.ndarray:
        """Token-id prompts ``(N, prompt_len)`` -> full sequences
        ``(N, prompt_len + max_new_tokens)`` (ids ride the Matrix wire
        as doubles — exact). ``session_key`` / ``slo_class`` override
        the client-level values for this call (None = send no such
        header)."""
        reply = self._traced_call(
            self._call_generate, "Generate", encode_matrix(prompts),
            session_key=session_key, slo_class=slo_class,
        )
        # Decode lands token ids straight in int64 — the wire doubles
        # are exact for ids < 2^53, so the cast-on-decode is lossless.
        return decode_matrix(reply, dtype=np.int64)

    def generate_stream(self, prompt: np.ndarray, *,
                        session_key=_CLIENT_DEFAULT,
                        slo_class=_CLIENT_DEFAULT,
                        timeout: float | None = None,
                        gap_timeout: float | None = None) -> StreamReply:
        """Stream ONE prompt's tokens as the server produces them.

        ``prompt`` is one sequence of token ids — ``(prompt_len,)`` or
        ``(1, prompt_len)``. Returns a :class:`StreamReply`; iterate it
        for token ids at decode-step granularity (first token at ~TTFT,
        not retirement).

        ``timeout`` bounds the WHOLE stream (gRPC deadline; None =
        unbounded — the streaming default, a long generation is not an
        error). ``gap_timeout`` is the stream-aware deadline
        (docs/ROBUSTNESS.md): the server bounds admission + prefill to
        first token and then every next-token gap by it, so a stalled
        stream dies fast while a steadily-producing one never expires.
        """
        x = np.asarray(prompt)
        if x.ndim == 1:
            x = x[None, :]
        session = (
            self.session_key if session_key is _CLIENT_DEFAULT
            else session_key
        )
        cls = (
            self.slo_class if slo_class is _CLIENT_DEFAULT else slo_class
        )
        span = _trace.TRACER.start("client.GenerateStream")
        metadata = ((_trace.TRACE_HEADER, span.ctx.header()),)
        if session is not None:
            metadata += ((SESSION_HEADER, session),)
        if cls is not None:
            metadata += ((CLASS_HEADER, cls),)
        if gap_timeout is not None:
            metadata += (
                (_trace.TIMEOUT_HEADER,
                 str(max(0, int(gap_timeout * 1000)))),
            )
        call = self._call_generate_stream(
            encode_matrix(x), timeout=timeout, metadata=metadata
        )
        return StreamReply(call, span)

    def close(self) -> None:
        self._channel.close()
