"""Continuous batching for LM generation: the iteration-level decode
scheduler (Orca, OSDI '22) on a slot-based KV cache, with
cross-request KV REUSE (RadixAttention-style shared-prefix caching,
exact-match tiers) and CHUNKED PREFILL (Sarathi-Serve).

The static Generate path (``serving/server.py``'s ``_Batcher`` over
``models.generate.generate``) is run-to-completion batching: a batch is
admitted, decodes ALL ``max_new_tokens`` steps, and only then does the
next batch start — a 4-token request pays for its 32-token neighbor,
and late arrivals convoy behind the whole batch. This module schedules
at DECODE-STEP granularity instead:

* One fixed ``(L, S + P, H, Dh, max_len)`` slot KV cache
  (:func:`~tpu_dist_nn.models.generate.init_slot_cache`) holds ``S``
  independent request slots plus ``P`` reserved PREFIX-POOL blocks
  (``--prefix-cache-blocks``). Shapes never change — admission and
  retirement only flip entries of a per-slot active mask, the
  TPU-friendly static-shape answer to vLLM-style paged KV (one request
  = one slot = one contiguous ``max_len`` extent; no block tables, no
  gathers on the hot path — trade-off discussion in docs/PERF.md).
* **Prefix caching**: most production Generate traffic shares a long
  common prefix (system prompt, few-shot header). The pool caches K/V
  for chunk-aligned token prefixes, keyed on the exact prefix bytes
  (exact-match tiers — no radix tree; rationale in docs/PERF.md). A
  hit admits by COPYING the block into the request's slot
  (:func:`~tpu_dist_nn.models.generate.copy_cache_slot` — copy-on-
  write: the request then decodes into its own slot and can never
  mutate the shared block) and prefilling only the SUFFIX. Blocks are
  ref-counted (held admission -> retire), evicted LRU at refcount 0,
  with hit/miss/evict accounting (``tdn_prefix_cache_*``).
* **Chunked prefill**: prefills longer than ``--prefill-chunk`` tokens
  are split across scheduler iterations — each iteration runs at most
  ONE chunk (:func:`~tpu_dist_nn.models.generate.
  prefill_chunk_into_cache`) alongside the resident decode step, so a
  4k-token prompt no longer freezes every live decode stream. The
  per-slot ``pos`` vector already supports the resulting staggered
  positions. Every admission routes through the chunk kernel (a
  monolithic prefill is just one whole-prompt chunk), so cache-on and
  cache-off share ONE numeric path and greedy outputs stay
  bit-identical — the correctness anchor
  (test_prefix_cache_greedy_bit_parity).
* **Admission at step granularity**: whenever a slot is free and a
  request is pending, it binds to that slot and starts chunking; the
  request starts decoding on the step after its last chunk — no
  waiting for the current "batch" to finish, because there is no
  batch.
* **One compiled step kernel**
  (:func:`~tpu_dist_nn.models.generate.decode_step_slots`) advances
  every slot at its OWN position (per-slot ``pos`` vector + active
  mask) — mixed-age requests share each device launch.
* **Early retirement**: a slot frees on EOS
  (:func:`~tpu_dist_nn.models.generate.generate`'s stop-token
  semantics, so the two schedulers are output-comparable) or its
  per-request ``max_new_tokens`` — and the freed slot is refilled on
  the same scheduler iteration while the remaining slots keep
  decoding. Finished rows stream back to their waiters immediately.

* **One launch ahead**: the loop launches step N+1 before it reads
  step N. Nearly all of what the launch needs is known without the
  read: a decoding slot's next input token is step N's output where it
  lies, on the device (the step program merges it with what only the
  host knows: a replay's forced token); ``pos`` advances by one; a slot
  that ends BY BUDGET at step N is known beforehand and is simply not
  in N+1, so no launch writes a row past a slot's extent. A slot that
  ends by EOS, by the numeric guard or by a client's cancel is found
  one launch late: its lane of step N+1 is computed and thrown away
  (the row it wrote lies past the frontier of whoever takes the slot
  next, and the device runs launches in order, so a prefill into the
  freed slot lands behind it). An iteration is: reap / admit / bind →
  launch the prefill chunk → launch step N+1 → read step N, account,
  publish, retire → read the chunk's token where it ended a prompt →
  wait until step N+1 has finished, the one place the loop waits for
  the device (:meth:`ContinuousScheduler._await_device`), and go
  around. Account, publish, retire and the handler threads' sends run
  while the device works; reap, admit, bind and the two dispatches run
  with nothing queued, so a request that arrived during the wait is
  bound and its chunk launched at once. (Launched as early as its
  inputs are known, with the wait behind the launch, the loop outran
  the stream plane it shares an interpreter with: PERF.md section 6,
  PR 30.) A round's publishes only enqueue and its handlers are woken
  together at its end, a request's first token ahead of them. At most
  one step is unread between iterations, and whatever needs the host's
  view of the streams to be final reads it first
  (:meth:`ContinuousScheduler._land`): a preemption, a launch hook's
  fault, close. Every stream gets the
  tokens the serial order gave it (sampling keys are drawn in launch
  order: chunk, step, chunk, step; a launch whose lanes are all thrown
  away draws one too).

What a layer is stays with the model. The three programs are built from
the four functions a model's config hands over with ``cfg.slot_model()``
(:mod:`tpu_dist_nn.models.slot_model`; the names above are GPT-2's, in
:mod:`~tpu_dist_nn.models.generate`; :mod:`~tpu_dist_nn.models.sala`
brings a cache of K/V rows, compressed keys and recurrent state). A
model whose cache holds recurrent state has prefix tiers inserted only
where a chunk ended (docs/MODEL_CONFIG.md).

Resilience contract (docs/ROBUSTNESS.md): the admission/shed/close/
drain machinery is the SHARED scheduling core
(:mod:`~tpu_dist_nn.serving.sched_core` — one implementation with the
Process batcher): class-priority admission with per-class shed
watermarks, deadline-aware expiry at bind time, ``close(timeout)``
letting resident rows — INCLUDING half-prefilled slots — finish before
failing still-pending waiters over as UNAVAILABLE (the ``_Batcher``
drain contract, so ``GracefulDrain`` works unchanged), and first-class
fault hook points — ``launch_hook`` fires before every step-kernel
dispatch (a fault there leaves the cache intact: what the device has
already computed is read and shipped, then every resident fails over),
``fetch_hook`` before a step's token fetch (a fault there is a device
fault: the step launched behind it took the same donated buffer and is
dropped unread, the cache is rebuilt, the prefix pool goes cold), and
``prefill_hook`` before every prefill-chunk dispatch (a mid-prefill
fault fails that request over, frees its slot, and releases its
prefix-block ref).
Assign a ``testing/faults.py`` plan's ``fire`` directly (the
``inject_engine_faults`` helper covers only engine hooks).

**Decode-slot preemption** (docs/ROBUSTNESS.md "Degradation ladder"):
a ``critical``-class request that cannot bind evicts the best victim
(dead-waiters first, then lowest class, then fewest generated tokens)
and binds into the freed slot the same iteration; the victim
re-queues with its generated prefix and resumes via prompt re-prefill
(prefix-cache hits make it cheap) + forced-token REPLAY through the
shared step kernel — the exact original computation, so greedy output
is bit-identical to an unpreempted run and sampled runs keep their
original stream.
"""

from __future__ import annotations

import collections
import functools
import itertools
import logging
import threading
import time

import numpy as np

from tpu_dist_nn.obs import trace as _trace
from tpu_dist_nn.obs.goodput import GOODPUT
from tpu_dist_nn.obs.log import get_logger
from tpu_dist_nn.obs.registry import POW2_BUCKETS, REGISTRY
from tpu_dist_nn.serving import integrity as _integrity
from tpu_dist_nn.serving.sched_core import (
    CLASS_RANK,
    SchedCore,
    slide_stream_deadline,
)
from tpu_dist_nn.serving.stream import StreamDone, TokenStream

log = logging.getLogger(__name__)  # plain channel (kept for debug use)
slog = get_logger(__name__)

# Generation metric families (docs/OBSERVABILITY.md catalog). Pushed by
# the scheduler loop; the slot gauges are sampled by obs/runtime.py.
_TTFT = REGISTRY.histogram(
    "tdn_gen_ttft_seconds",
    "time to first token: request submit to its first sampled token "
    "(prefill complete), continuous scheduler",
)
_TOKENS = REGISTRY.counter(
    "tdn_gen_tokens_total",
    "tokens emitted by the continuous decode scheduler",
)
_RETIRED = REGISTRY.counter(
    "tdn_gen_requests_retired_total",
    "request rows retired from a decode slot, by reason",
    labels=("reason",),
)
# tdn_batcher_shed_total / tdn_batch_wait_seconds moved to
# serving/sched_core.py — the shared admission/shed/close contract.
_PREEMPTED = REGISTRY.counter(
    "tdn_gen_preemptions_total",
    "decode-slot preemptions: a resident row evicted mid-stream so a "
    "critical-class request could bind, re-queued with its generated "
    "prefix for replay (by the VICTIM's class)",
    labels=("slo_class",),
)
# Same family (and meaning — rows per device launch) as the static
# batcher's, so dashboards read the Generate series unchanged across
# schedulers: here a "launch" is one slot step and its rows are the
# active slots it advanced.
_BATCH_ROWS = REGISTRY.histogram(
    "tdn_batch_rows", "coalesced rows per device launch (pre-padding)",
    labels=("method",), buckets=POW2_BUCKETS,
)
# Prefix-cache accounting (docs/OBSERVABILITY.md catalog; the
# tdn_prefix_cache_blocks_used gauge rides the runtime sampler).
_PREFIX_HITS = REGISTRY.counter(
    "tdn_prefix_cache_hits_total",
    "admissions served from a cached prefix block (copy-on-write "
    "block copy + suffix-only prefill)",
)
_PREFIX_MISSES = REGISTRY.counter(
    "tdn_prefix_cache_misses_total",
    "admissions whose prompt matched no cached prefix tier "
    "(full prefill)",
)
_PREFIX_EVICTIONS = REGISTRY.counter(
    "tdn_prefix_cache_evictions_total",
    "refcount-0 prefix blocks evicted (LRU) to admit a new prefix",
)

_SCHED_IDS = itertools.count(1)
(_IDLE, _REAP, _ADMIT, _BIND, _PREFILL_DISPATCH, _PREFILL_FETCH,
 _PREFILL_POST, _STEP_DISPATCH, _STEP_FETCH, _STEP_ACCOUNT,
 _STEP_PUBLISH) = range(len(_trace.LOOP_PHASES))
_HOST = tuple(i for i, p in enumerate(_trace.LOOP_PHASES)
              if p in _trace.LOOP_HOST_PHASES)
_SPAN_NAMES = tuple("tdn.gen." + p for p in _trace.LOOP_PHASES)
# What the host writes into a step's token input where the token is
# still on the device (an id is never negative): the lane's own output
# of the step launched before, or the token of the chunk launched before.
_FROM_STEP, _FROM_CHUNK = -1, -2


def slot_kernels(cfg, temperature, top_k, top_p):
    """The three jitted programs that share one slot cache (request
    slots, then the prefix pool's): ``(prefill_chunk, copy, step)``. Module-level so a tool can
    compile exactly what the scheduler launches without building one
    (``tools/aot_step_ops.py``). The cache is LINEAR through the
    scheduler (one owner, always rebound to the kernel's output), so
    every kernel donates it. Donation lets a program write into the
    buffer it was given; it does not make it do so — the step did not
    until it stopped carrying the cache through its layer scan — so
    ``tools/aot_step_ops.py`` lists what each compiled program still
    does at cache size, and tests/test_tpu_compile.py holds the step
    to its in-place write.
    """
    import jax
    import jax.numpy as jnp

    from tpu_dist_nn.models.generate import _truncate_logits

    # What a layer is stays with the model: its config hands over the
    # functions the three programs are built from (models/slot_model.py).
    model = cfg.slot_model()
    top_k = None if top_k is None else int(top_k)
    top_p = None if top_p is None else float(top_p)

    @jax.named_scope("sample")
    def sample(logits, key):
        if temperature == 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = _truncate_logits(logits, top_k, top_p)
        return jax.random.categorical(
            key, logits / temperature, axis=-1
        ).astype(jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, cache, slot, tokens, start, key):
        logits, cache = model.prefill_chunk_into_cache(
            params, cfg, cache, slot, tokens, start
        )
        return sample(logits, key)[0], cache

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, pos, active, tok, key, prev=None, first=None):
        # Decode advances the REQUEST region only: `pos`, `active` and
        # `tok` have S entries, and the step reads and writes slots
        # [0, S) of the one buffer where they lie. The pool blocks past
        # slot S hold cached prefixes, not decoding sequences, and are
        # not touched.
        if prev is not None:
            # The loop launches this step before it has read the one
            # before it: a lane's input token is then still on the
            # device, as `prev (S,)`, the step's output before, or as
            # `first ()`, the token of the chunk that ended a prompt.
            # The host marks those lanes in `tok` and writes what only
            # it knows (a replay's forced token) as the id itself.
            with jax.named_scope("next_token"):
                tok = jnp.where(tok == _FROM_STEP, prev, tok)
                tok = jnp.where(tok == _FROM_CHUNK, first, tok)
        logits, cache = model.decode_step_slots(
            params, cache, pos, tok, cfg, active=active
        )
        # Numeric guard folded into the SAME launch: one fused
        # isfinite reduction over the logits per slot (an (S,) bool
        # riding the step's existing device->host sync — always
        # computed so the compiled kernel never depends on the
        # runtime GUARD toggle; acting on it is a host decision).
        with jax.named_scope("guard"):
            ok = jnp.isfinite(logits).all(axis=-1)
        return sample(logits, key), ok, cache

    return (prefill_chunk,
            jax.jit(model.copy_cache_slot, donate_argnums=(0,)), step)


def slot_body_kernel(cfg):
    """The fourth program of a model whose protocol has the chunk
    without logits (``SlotModel.prefill_body_into_cache``), else None:
    ``prefill_body(params, cache, slot, tokens, start) -> (end, cache)``
    on the same donated cache. ``end`` (the position after the chunk) is
    the launch's handle: what :meth:`ContinuousScheduler._await_device`
    waits for where ``prefill_chunk`` gives its token. Jitted under a
    name of its own, so a trace tells ``jit_prefill_body`` from
    ``jit_prefill_chunk``; :func:`slot_kernels` keeps returning three."""
    import jax
    import jax.numpy as jnp

    model = cfg.slot_model()
    if model.prefill_body_into_cache is None:
        return None

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_body(params, cache, slot, tokens, start):
        cache = model.prefill_body_into_cache(
            params, cfg, cache, slot, tokens, start
        )
        return jnp.asarray(start, jnp.int32) + tokens.shape[1], cache

    return prefill_body


# Loop iterations between two fetches of a model's device counts
# (`SlotModel.routing_counts`): a few dozen bytes every second or two.
_ROUTING_EVERY = 64


# Nanoseconds between two reads of the process's CPU clock
# (`proc_cpu_ns`): a level over seconds needs no read an iteration.
_PROC_CPU_EVERY_NS = 100_000_000


class _LoopClock:
    """Where the scheduler loop's time goes, always on: the loop thread
    calls :meth:`mark` at each phase boundary, which closes the phase
    that was running into its cumulative total and opens the next, so
    the phases partition the thread's wall time exactly. Each phase is
    also a ``tdn.gen.<phase>`` annotation on the profiler's clock
    (``utils.profiling.host_span``), made only while a
    ``jax.profiler`` capture runs.

    The thread's own CPU time is kept beside them: whole (``cpu_ns``),
    read once an iteration at its end as since PR 24, and the publish's
    share of it (``cpu_publish_ns``), read on entering and on leaving
    ``step.publish``: three reads an iteration (a system call each,
    6 µs where the chip's host runs it). The thread burns next to none
    while it waits (``idle``, the two fetches), so the host phases'
    wall time minus ``cpu_ns`` is time the loop wanted to run and could
    not (the GIL, a lock, a blocking call, descheduled), and the
    publish's wall minus its CPU is how much of that lies there.
    ``proc_cpu_ns`` is the whole process's, every thread of it, read at
    an iteration's end once ``_PROC_CPU_EVERY_NS`` have passed since
    the read before.

    ``starved_ns`` is the device's idle time the loop can own up to:
    from the end of its wait for the device (:meth:`device_idle`:
    nothing is queued there and the loop knows it) to the return of the
    next iteration's first dispatch (:meth:`fed`), ``idle`` apart.
    ``captured`` counts the iterations recorded while a
    ``jax.profiler`` capture ran.

    One writer, no lock: every field is written by the loop thread
    alone and read as plain ints by anyone (the ``stream_*`` sums are
    moved in by the loop at a publish: ``serving/stream.py``). At the
    end of an iteration that launched something the totals go to
    ``obs.trace.ITERATIONS`` as one record (``obs.trace.ITER_FIELDS``),
    with ``counts()``, the scheduler's own counters, at its end.
    """

    def __init__(self, counts=lambda: (0,) * 6):
        import jax

        from tpu_dist_nn.utils.profiling import host_span

        self._host_span = host_span
        self._capturing = jax.profiler.TraceAnnotation.is_enabled
        self._counts = counts
        self.sched = next(_SCHED_IDS)
        self.seq = 0                 # iterations recorded
        self.ns = [0] * len(_trace.LOOP_PHASES)
        self.cpu_ns = self.cpu_publish_ns = self.proc_cpu_ns = 0
        self.queue_wait_ns = self.binds = 0
        self.prefill_wait_ns = self.first_tokens = 0
        self.stream_lag_ns = self.stream_frames = 0
        self.stream_send_ns = self.stream_sends = 0
        # Positions prefilled, and the first positions of their chunks
        # summed: neighbours' difference is an iteration's chunk.
        self.prefill_tokens = self.prefill_starts = 0
        # Step launches made while the step before them was unread.
        self.steps_ahead = 0
        self.starved_ns = 0
        self.captured = 0
        # Of the iteration in progress.
        self.prefilled = False
        self.active_slots = 0
        self.launched = False
        self._phase = _IDLE
        self._t = time.monotonic_ns()
        # Since when nothing is queued on the device, and `idle` then.
        self._starved: tuple | None = None
        self._span = None  # the phase's annotation, while a capture runs

    def start(self) -> None:
        """On the loop thread, before its first iteration."""
        self._t = time.monotonic_ns()
        self._cpu0 = self._cpu_at = time.thread_time_ns()
        self._proc0 = time.process_time_ns()
        self._proc_at = 0  # the first recorded iteration reads it

    def mark(self, phase: int) -> None:
        prev = self._phase
        now = time.monotonic_ns()
        self.ns[prev] += now - self._t
        self._t = now
        if phase != prev:
            if phase == _STEP_PUBLISH:
                self._cpu_at = time.thread_time_ns()
            elif prev == _STEP_PUBLISH:
                self.cpu_publish_ns += time.thread_time_ns() - self._cpu_at
        # A new annotation each time: one made before a capture began
        # records nothing in it. None is made while no capture runs.
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self._capturing():
            self._span = self._host_span(_SPAN_NAMES[phase])
            self._span.__enter__()
        self._phase = phase

    def stop(self) -> None:
        self.mark(_IDLE)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def device_idle(self) -> None:
        """The loop's wait for the device is over, or it had nothing to
        wait for: nothing is queued there until the next dispatch."""
        if self._starved is None:
            self._starved = (time.monotonic_ns(), self.ns[_IDLE])

    def fed(self) -> None:
        """A dispatch has returned: the device has work again."""
        if self._starved is not None:
            since, idle = self._starved
            self._starved = None
            self.starved_ns += (time.monotonic_ns() - since
                                - (self.ns[_IDLE] - idle))

    def host_ns(self) -> int:
        ns = self.ns
        return sum(ns[i] for i in _HOST)

    def record(self) -> tuple:
        """The totals as one iteration record (ITER_FIELDS)."""
        return (
            self.sched, self.seq, self._t / 1e9, self.prefilled,
            self.active_slots, *self.ns, self.cpu_ns,
            self.queue_wait_ns, self.binds,
            self.prefill_wait_ns, self.first_tokens,
            self.stream_lag_ns, self.stream_frames,
            self.prefill_tokens, self.prefill_starts, self.steps_ahead,
            self.cpu_publish_ns, self.proc_cpu_ns,
            self.stream_send_ns, self.stream_sends,
            self.starved_ns, self.captured, *self._counts(),
        )

    def end_iteration(self) -> None:
        """Close the iteration at the top of the next (``reap`` is its
        first phase) and put it on the record if it fed the device or
        read a step back from it."""
        self.mark(_REAP)
        if self.launched:
            self.seq += 1
            self.cpu_ns = time.thread_time_ns() - self._cpu0
            if self._t - self._proc_at >= _PROC_CPU_EVERY_NS:
                self._proc_at = self._t
                self.proc_cpu_ns = time.process_time_ns() - self._proc0
            self.captured += self._capturing()
            _trace.ITERATIONS.append(self.record())
            self.prefilled = self.launched = False

    def ride(self) -> tuple:
        """What a request's ``decode`` span is later told it rode: the
        iteration in progress and the totals so far."""
        return (self.seq + 1, self.ns[_STEP_FETCH], self.host_ns())


class PrefixCachePool:
    """Host-side bookkeeping for the reserved prefix region of the slot
    cache: which pool block holds which token-prefix, with refcounts
    and LRU eviction. Exact-match only — the key IS the prefix bytes,
    so there are no collisions and no radix tree (docs/PERF.md
    "exact-match vs radix").

    Single-threaded by design: the scheduler loop thread is the only
    caller (lookups/inserts happen at admission and chunk boundaries,
    releases at retirement — all loop-side events), so no lock.

    A block is REFERENCED from the admission that hit it until that
    request retires (or fails): a referenced block is never evicted, so
    a hot shared header cannot be thrashed out from under the requests
    using it. Eviction picks the least-recently-USED block among
    refcount-0 blocks; with every block referenced, insertion is simply
    skipped (caching is an optimization, never a correctness gate).
    """

    def __init__(self, blocks: int):
        if blocks < 1:
            raise ValueError(f"pool needs >= 1 block, got {blocks}")
        self.blocks = int(blocks)
        self._key: list[bytes | None] = [None] * self.blocks
        self._len = [0] * self.blocks
        self._refs = [0] * self.blocks
        self._last_use = [0] * self.blocks
        self._by_key: dict[bytes, int] = {}
        self._tick = itertools.count(1)
        self.hits_total = 0
        self.misses_total = 0
        self.evictions_total = 0

    @property
    def used(self) -> int:
        """Blocks currently holding a cached prefix."""
        return len(self._by_key)

    def refs(self, block: int) -> int:
        return self._refs[block]

    def block_len(self, block: int) -> int:
        return self._len[block]

    def lookup(self, candidates) -> tuple[int, int] | None:
        """The longest cached prefix among ``candidates`` (``(length,
        key_bytes)`` pairs, longest FIRST). A hit takes a reference and
        bumps recency, returning ``(block, length)``; a full miss
        returns None. Exactly one hit-or-miss is accounted per call
        (per admission)."""
        for length, key in candidates:
            b = self._by_key.get(key)
            if b is not None:
                self._refs[b] += 1
                self._last_use[b] = next(self._tick)
                self.hits_total += 1
                return b, length
        self.misses_total += 1
        return None

    def release(self, block: int) -> None:
        """Drop one reference (the request that held it retired)."""
        if self._refs[block] <= 0:
            raise AssertionError(
                f"release of unreferenced prefix block {block}"
            )
        self._refs[block] -= 1

    def clear(self) -> None:
        """Drop every cached block — the backing cache was rebuilt
        after a device fault, so the K/V the blocks pointed at is gone.
        Lifetime counters survive (they are totals, not state). The
        caller fails/releases every resident first, so no block can
        still be referenced."""
        if any(self._refs):
            raise AssertionError(
                "clear() with live references — release residents first"
            )
        self._key = [None] * self.blocks
        self._len = [0] * self.blocks
        self._last_use = [0] * self.blocks
        self._by_key.clear()

    def insert(self, key: bytes, length: int) -> tuple[int | None, bool]:
        """Reserve a block for a new prefix: a free block, else the LRU
        refcount-0 block (eviction), else None — all blocks referenced,
        insertion skipped. Returns ``(block, evicted)``; ``(None,
        False)`` when skipped or the key is already cached."""
        if key in self._by_key:
            return None, False
        free = next(
            (b for b in range(self.blocks) if self._key[b] is None), None
        )
        evicted = False
        if free is None:
            idle = [b for b in range(self.blocks) if self._refs[b] == 0]
            if not idle:
                return None, False
            free = min(idle, key=lambda b: self._last_use[b])
            del self._by_key[self._key[free]]
            self.evictions_total += 1
            evicted = True
        self._key[free] = key
        self._len[free] = int(length)
        self._refs[free] = 0
        self._last_use[free] = next(self._tick)
        self._by_key[key] = free
        return free, evicted


class ContinuousScheduler:
    """Iteration-level decode scheduler over a slot-based KV cache.

    ``submit(rows)`` blocks the calling (gRPC worker) thread until every
    row's sequence is finished, exactly like ``_Batcher.submit`` — the
    difference is behind the call: one daemon loop thread owns the
    device, interleaving per-iteration prefill CHUNKS (at most one per
    iteration, so no prompt ever stalls the decode frontier for more
    than one chunk) with single-token steps over all decoding slots,
    retiring each row the moment it hits EOS or its token budget.

    ``prefix_cache_blocks > 0`` reserves that many pool blocks at the
    tail of the slot cache and enables shared-prefix reuse: admission
    looks the prompt's chunk-aligned prefixes up (longest tier first),
    copies a hit's block into the request slot, and prefills only the
    suffix. ``prefill_chunk`` bounds tokens per prefill launch (None =
    whole prompt/suffix in one chunk) and doubles as the prefix tier
    granularity. Tuning guide: docs/PERF.md "Prefix caching & chunked
    prefill".

    Construction compiles nothing; :meth:`warm` precompiles the
    chunk-prefill, slot-copy, and step kernels so a port can open hot
    (``serve_lm_generate(warm_rows=...)`` / ``tdn warmup --lm``).

    Counter attributes mirror ``_Batcher`` (``requests_total``,
    ``batches_total`` = step-kernel launches, ``rows_total``,
    ``pending_rows``, ``inflight_rows`` = rows resident in slots,
    ``shed_total``) so the runtime sampler and drain plumbing work
    unchanged; generation-specific state (``slots_active``,
    ``steps_total``, ``slot_steps_total``, ``ttft_recent``, the
    ``prefix_*`` accessors) feeds the ``tdn_gen_*`` /
    ``tdn_prefix_cache_*`` families.

    ``prefill_fn`` / ``step_fn`` / ``copy_fn`` are testing seams (the
    count tests inject kernels that move no device); production
    always builds the real jitted kernels from ``params``/``cfg``.
    """

    method = "Generate"

    def __init__(self, params, cfg, *, slots: int, prompt_len: int,
                 max_new_tokens: int, temperature: float = 0.0,
                 top_k: int | None = None, top_p: float | None = None,
                 eos_id: int | None = None, seed: int = 0,
                 submit_timeout: float | None = 120.0,
                 max_pending_rows: int | None = None,
                 prefix_cache_blocks: int = 0,
                 prefill_chunk: int | None = None,
                 class_watermarks: dict | None = None,
                 preemption: bool = True,
                 prefill_fn=None, step_fn=None, copy_fn=None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self._S = int(slots)
        self._T = int(prompt_len)
        self._N = int(max_new_tokens)
        self._eos = None if eos_id is None else int(eos_id)
        # submit_timeout / max_pending_rows / class_watermarks live in
        # the shared scheduling core constructed below.
        self._counter = itertools.count()
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self._chunk = None if prefill_chunk is None else int(prefill_chunk)
        self._P = int(prefix_cache_blocks)
        if self._P < 0:
            raise ValueError(
                f"prefix_cache_blocks must be >= 0, got {prefix_cache_blocks}"
            )
        # Prefix tiers: the cacheable prefix lengths, chunk-aligned so a
        # hit resumes exactly at a chunk boundary. Without chunking the
        # single tier is the whole-prompt-but-last-token prefix (repeat
        # / retry traffic); capped at T-1 so a hit always leaves >= 1
        # suffix token to produce the last-position logits from.
        grain = self._chunk if self._chunk is not None else self._T - 1
        self._tiers: tuple[int, ...] = tuple(
            sorted(
                (k * grain for k in range(1, self._T)
                 if 1 <= k * grain <= self._T - 1),
                reverse=True,
            )
        ) if self._P else ()
        if self._P and not self._tiers:
            raise ValueError(
                f"prefix_cache_blocks={self._P} has no cacheable tier: "
                f"need a prefix length in [1, prompt_len - 1 = "
                f"{self._T - 1}] — lower prefill_chunk (got "
                f"{self._chunk}) or raise prompt_len"
            )
        self._pool = PrefixCachePool(self._P) if self._P else None
        # What the model says of its cache (models/slot_model.py);
        # injected kernels carry no model and keep these.
        self._tier_at_fill, self._sparse_positions = False, lambda pos: 0
        self._attend_kernel = lambda size: False
        self._kv_tiles = None
        self._routing = None
        self._prefill_body = None
        self.cache_bytes: dict = {}
        if prefill_fn is not None or step_fn is not None:
            if prefill_fn is None or step_fn is None:
                raise ValueError(
                    "prefill_fn and step_fn must be injected together"
                )
            # The public step_fn seam keeps its contract: six
            # arguments, `tok` whole (what the real program merges on
            # the device is merged here, where an injected kernel's
            # result is already the host's), and (toks, cache) back;
            # normalized to the internal 3-tuple with ok=None — injected
            # kernels carry no logits for the in-launch numeric guard.
            def _step_no_guard(params, cache, pos, active, tok, key, prev,
                               first, _fn=step_fn):
                tok = np.where(tok == _FROM_STEP, np.asarray(prev), tok)
                tok = np.where(tok == _FROM_CHUNK, np.asarray(first), tok)
                toks, cache = _fn(params, cache, pos, active,
                                  tok.astype(np.int32), key)
                return toks, None, cache

            self._prefill, self._step = prefill_fn, _step_no_guard
            # Fake caches have no block storage; the default injected
            # copy is the identity (pool bookkeeping still exercises).
            self._copy = (
                copy_fn if copy_fn is not None
                else (lambda cache, src, dst: cache)
            )
            self._params = params
            self._cache = None
            self._make_cache = None
            self._key = None
            self._temperature = float(temperature)
            # Injected fake kernels carry no architecture: the goodput
            # plane has no FLOP model to apply, so accounting is off.
            self._gp_model = None
        else:
            if copy_fn is not None:
                raise ValueError(
                    "copy_fn is an injection seam: pass it together "
                    "with prefill_fn/step_fn"
                )
            import jax

            from tpu_dist_nn.models.generate import validate_generate_args

            self._key = jax.random.key(int(seed))
            validate_generate_args(
                cfg, self._T, self._N, temperature, top_k, top_p,
                self._key if temperature > 0 else None, eos_id,
            )
            self._params = cfg.cast_params(params)
            self._temperature = float(temperature)
            self._build_kernels(
                cfg, float(temperature), top_k, top_p,
            )
        # Positions prefilled or decoded, by how the model says it
        # serves them (`SlotModel.sparse_positions`): its block
        # selection or dense attention.  From `pos` on the host; no
        # device fetch.
        self.sparse_positions_total = 0
        self.dense_positions_total = 0
        # Host-side slot state: the loop thread is the only writer.
        # _pos, _active and _tok are what the NEXT step launch is
        # given, advanced when a step is launched and not when it is
        # read: _active marks the slots that launch decodes (a bound
        # slot whose prefill is still chunking has an occupant but is
        # not active, nor is one whose budget the launches so far
        # fill), _tok holds _FROM_STEP / _FROM_CHUNK where the token is
        # still on the device. A launch is handed copies: the arrays
        # change while it may still be reading them.
        self._pos = np.zeros(self._S, np.int32)
        self._active = np.zeros(self._S, bool)
        self._tok = np.zeros(self._S, np.int32)
        self._occupant: list[dict | None] = [None] * self._S
        # Launches whose result the host has not read, in the order the
        # device runs them, one record each (_land_step's has "lanes",
        # _land_first's none): at most the step launched an iteration
        # ago, the token of this iteration's chunk where it ended a
        # prompt, and the step just launched; between iterations one
        # step at most.
        self._unread: collections.deque[dict] = collections.deque()
        # The last step's tokens and the token of the last chunk that
        # ended a prompt, where they were left (the device): the next
        # step's `prev` and `first`.
        self._prev = np.zeros(self._S, np.int32)
        self._first = np.int32(0)
        # Streams published to and not yet woken (_land wakes them).
        self._woken: list[TokenStream] = []
        # The iteration's last launch, as (phase its wait is booked to,
        # result): what _await_device waits for.
        self._launched: tuple | None = None
        self._prefill_rr = 0  # round-robin fairness over chunking slots
        # Fault-injection hook points (testing/faults.py): called at
        # the top of every step-kernel dispatch / token fetch /
        # prefill-chunk dispatch.
        self.launch_hook = None
        self.fetch_hook = None
        self.prefill_hook = None
        # Pending queue + admission ledger: the shared scheduling core
        # (serving/sched_core.py) — class-priority queue, watermark
        # sheds, deadline expiry, close-failover sweep. The loop holds
        # core.cond exactly where it held its own condition before.
        self._sched_core = SchedCore(
            self.method, max_pending_rows=max_pending_rows,
            submit_timeout=submit_timeout,
            class_watermarks=class_watermarks,
        )
        self._cond = self._sched_core.cond
        # Preempted rows awaiting re-bind: class-annotated resume
        # entries carrying the generated prefix for replay. Mutated
        # under _cond (the loop pops there already; appends and the
        # close sweep take it too, so a wedged-loop close can never
        # race a pop and strand an entry's waiter).
        self._resume: collections.deque[dict] = collections.deque()  # guarded-by: _cond
        self._preemption = bool(preemption)
        # _Batcher-compatible counters (runtime sampler contract;
        # requests/shed/pending ride the core via properties below).
        self.rows_total = 0        # rows that entered a slot
        self.batches_total = 0     # step-kernel launches (steps_total
        #                            is a read alias — one source of truth)
        self.preempted_total = 0   # rows evicted for a critical bind
        # Generation-specific stats.
        self.slot_steps_total = 0  # active slots summed over steps
        # Lanes a step computed for a slot whose occupant had gone by
        # the time it was read (EOS, a cancel, the guard: found one
        # launch late); pad, not slot-steps, and never published.
        self.discarded_lanes_total = 0
        self.retired_total = 0     # rows retired (eos + max_tokens)
        self.prefill_chunks_total = 0  # chunk-kernel launches
        # ... of which the program that ends without logits ran
        # (`SlotModel.prefill_body_into_cache`): nobody read their token.
        self.prefill_body_chunks_total = 0
        # ... of which the model says its program holds its attention
        # kernel (`SlotModel.attend_kernel`, asked once a chunk size).
        self.attend_kernel_chunks_total = 0
        # 128-lane position tiles of the K/V extent the decode steps
        # copied and left in HBM, summed over the slots of every step,
        # where the model's step stops at each slot's frontier
        # (`SlotModel.step_kv_tiles`, asked once); else both stay 0.
        self.step_kv_tiles_visited_total = 0
        self.step_kv_tiles_skipped_total = 0
        # Counts the model's programs accumulate on the device, inside
        # the cache (`SlotModel.routing_counts`, asked once; routing
        # load is known nowhere else): their growth by name since the
        # warm-up, read every `_ROUTING_EVERY` iterations and when the
        # loop runs dry, never a step.  Empty for a model with none.
        self.routing_totals: dict = {}
        if self._routing is not None:
            import jax

            self.routing_totals = {
                name: np.zeros(v.shape, np.int64) for name, v in
                jax.eval_shape(self._routing, self._cache).items()}
        self.experts_held = tuple(getattr(cfg, "experts_held", ()))
        self.ttft_recent: collections.deque[float] = collections.deque(
            maxlen=1024
        )
        self._m_rows = _BATCH_ROWS.labels(method=self.method)
        self._clock = _LoopClock(counts=lambda: (
            self.discarded_lanes_total, self.slot_steps_total,
            self.attend_kernel_chunks_total, self.prefill_chunks_total,
            self.step_kv_tiles_visited_total,
            self.step_kv_tiles_skipped_total))
        self._thread = threading.Thread(
            target=self._loop, name="tdn-gen-continuous", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------ kernels

    def _build_kernels(self, cfg, temperature, top_k, top_p) -> None:
        model = cfg.slot_model()
        if model.recurrent and self._P and self._chunk is None:
            raise ValueError(
                "prefix_cache_blocks needs prefill_chunk for this model: "
                "its cache holds recurrent state, which is a prefix's "
                "only where a chunk ended, so a tier can be copied out "
                "of a slot only at a chunk boundary"
            )
        self._tier_at_fill = model.recurrent
        self._sparse_positions = model.sparse_positions
        # The last decode writes position T + N - 2 (generate()'s cache
        # sizing rule), so the slot extent is total - 1. The prefix
        # pool rides the SAME cache as P extra slots past the request
        # region — one allocation, one shape, one copy kernel.
        M = self._T + self._N - 1 if self._N > 1 else self._T
        # One program a chunk size, so one answer: the model's dispatch
        # is asked when a size is first launched, not every chunk.
        self._attend_kernel = functools.lru_cache(maxsize=None)(
            lambda size: bool(model.attend_kernel(size, M)))
        self._kv_tiles = model.step_kv_tiles(self._S, M)
        self._make_cache = lambda: model.init_slot_cache(
            cfg, self._S + self._P, M)
        self._cache = self._make_cache()
        self.cache_bytes = model.cache_bytes(self._cache)
        self._routing = model.routing_counts
        self._routing_seen, self._routing_age = None, 0
        # Goodput FLOP model at the kernels' static shapes: the decode
        # step runs the REQUEST region only (S slots of extent M; the
        # pool blocks behind them are not read), so the model's extent
        # is M regardless of prefix_cache_blocks. Peak resolves here,
        # at configure time, never on a sampler tick.
        self._gp_model = model.flop_model(cfg, M)
        GOODPUT.ensure_peak(device_count=1)  # slot cache is single-chip
        self._prefill, self._copy, self._step = slot_kernels(
            cfg, temperature, top_k, top_p
        )
        self._prefill_body = slot_body_kernel(cfg)

    def _count_positions(self, pos) -> None:
        """Book the query positions ``pos`` of a chunk or a step under
        the path the model says serves them."""
        sparse = self._sparse_positions(pos)
        self.sparse_positions_total += sparse
        self.dense_positions_total += len(pos) - sparse

    def _next_key(self):
        """A fresh fold of the base key per sampling event (prefill or
        step): repeated identical prompts draw fresh continuations, the
        serving endpoint's existing contract."""
        if self._key is None:
            return None
        if self._temperature == 0:
            return self._key  # unused inside the greedy kernels
        import jax

        return jax.random.fold_in(self._key, next(self._counter))

    def _chunk_lengths(self) -> list[int]:
        """Every chunk length the scheduler can launch (the compile
        set): walking from each possible start position — 0, or any
        prefix tier a hit can resume at — in ``prefill_chunk`` strides.
        Small by construction: {chunk, T mod chunk} in the common case.
        """
        starts = {0, *self._tiers}
        lengths: set[int] = set()
        for s in starts:
            pos = s
            while pos < self._T:
                c = (
                    self._T - pos if self._chunk is None
                    else min(self._chunk, self._T - pos)
                )
                lengths.add(c)
                pos += c
        return sorted(lengths, reverse=True)

    def warm(self) -> list[str]:
        """Precompile every kernel the loop can launch — the
        chunk-prefill kernel at each chunk LENGTH the configuration can
        produce, the slot-copy kernel (prefix pool on), and the step
        kernel — so the port opens hot (with JAX_COMPILATION_CACHE_DIR
        the compiles also land on disk for later processes). Runs
        against slot 0 of the real cache with zero prompts — the slot
        is free, so the junk K/V is masked and the next real occupant's
        prefill overwrites it."""
        key = self._next_key()
        cache = self._cache
        for c in self._chunk_lengths():
            zeros = np.zeros((1, c), np.int32)
            _, cache = self._prefill(
                self._params, cache, np.int32(0), zeros, np.int32(0), key
            )
            if self._prefill_body is not None:
                _, cache = self._prefill_body(
                    self._params, cache, np.int32(0), zeros, np.int32(0)
                )
        warmed = ["prefill_chunk_into_cache"]
        if self._prefill_body is not None:
            warmed.append("prefill_body_into_cache")
        if self._P:
            # Self-copy of free slot 0: compiles the (src, dst)-traced
            # kernel without touching live state.
            cache = self._copy(cache, np.int32(0), np.int32(0))
            warmed.append("copy_cache_slot")
        toks, _ok, cache = self._step(
            self._params, cache,
            np.zeros(self._S, np.int32), np.zeros(self._S, bool),
            np.zeros(self._S, np.int32), key,
            np.zeros(self._S, np.int32), np.int32(0),
        )
        np.asarray(toks)  # force the compile + execution to finish
        self._cache = cache
        warmed.append("decode_step_slots")
        self._read_routing(book=False)  # what the warm-up routed is no load
        return warmed

    def _read_routing(self, book: bool = True) -> None:
        """Fetch the model's device counts out of the cache (the loop
        thread, with no launch in flight) and book their growth since
        the last fetch; ``book=False`` only moves the mark.  The
        device's totals are int32 and wrap; their growth between two
        fetches does not."""
        if self._routing is None:
            return
        import jax

        self._routing_age = 0
        try:
            now = {name: np.asarray(v, np.int64) for name, v in
                   jax.device_get(self._routing(self._cache)).items()}
        except Exception:  # noqa: BLE001 — a dead cache has no counts
            self._routing_seen = None
            return
        seen, self._routing_seen = self._routing_seen, now
        if not book:
            return
        for name, v in now.items():
            grown = (v - (0 if seen is None else seen[name])) & 0xFFFFFFFF
            self.routing_totals[name] = self.routing_totals[name] + grown

    # ------------------------------------------------------------ submit

    @property
    def inflight_rows(self) -> int:
        """Rows resident in slots — decoding OR mid-prefill."""
        return sum(1 for o in self._occupant if o is not None)

    # Legacy counter/queue surface, owned by the shared core (the
    # runtime sampler, drain plumbing, and resilience tests read these
    # names on both schedulers).
    @property
    def pending_rows(self) -> int:
        """Rows awaiting a slot: queued fresh rows plus preempted rows
        awaiting re-bind. Deliberately lock-free (GIL-atomic int read
        + deque len): the runtime sampler's gauge read must never
        queue behind admission."""
        return (self._sched_core.pending_rows
                + len(self._resume))  # tdnlint: disable=lock-discipline

    @property
    def requests_total(self) -> int:
        return self._sched_core.requests_total

    @property
    def shed_total(self) -> int:
        return self._sched_core.shed_total

    @property
    def expired_total(self) -> int:
        return self._sched_core.expired_total

    @property
    def _pending(self) -> list:
        return self._sched_core.pending_items()

    @property
    def _closed(self) -> bool:
        return self._sched_core.closed

    def queue_depth(self) -> int:
        """Entries awaiting a slot (deliberately lock-free — the
        runtime sampler's per-tick read): queued fresh items plus
        preempted rows awaiting resume."""
        return (self._sched_core.queue_depth()
                + len(self._resume))  # tdnlint: disable=lock-discipline

    def pending_by_class(self) -> dict:
        return self._sched_core.pending_by_class()

    @property
    def slots(self) -> int:
        return self._S

    @property
    def slots_active(self) -> int:
        """Alias of :attr:`inflight_rows` under its generation name."""
        return self.inflight_rows

    @property
    def steps_total(self) -> int:
        """Step-kernel launches read back, under the name the occupancy
        ratio reads naturally (alias of ``batches_total`` — a device
        launch IS a decode step here)."""
        return self.batches_total

    @property
    def overlapped_total(self) -> int:
        """Step launches made while the step before them was unread
        (``tdn_gen_steps_ahead_total``; over ``batches_total`` the
        sampler's ``tdn_batcher_overlap_ratio``)."""
        return self._clock.steps_ahead

    def loop_totals(self) -> dict:
        """The loop's cumulative accounting as of its last phase
        boundary, keyed by ``obs.trace.ITER_FIELDS``: nanoseconds per
        phase, the loop thread's CPU time whole and inside the publish,
        the process's, the queue-wait, prefill-wait, stream-out-lag and
        stream-send sums with their counts, and the starved time (the
        ``tdn_gen_loop_*`` families, the benchmark's ``sched_*``
        metrics)."""
        return dict(zip(_trace.ITER_FIELDS, self._clock.record()))

    # Prefix-cache accounting (None-safe: 0 with the pool off, so the
    # sampler reads one shape regardless of configuration).
    @property
    def prefix_blocks(self) -> int:
        return self._P

    @property
    def prefix_blocks_used(self) -> int:
        return self._pool.used if self._pool is not None else 0

    @property
    def prefix_hits_total(self) -> int:
        return self._pool.hits_total if self._pool is not None else 0

    @property
    def prefix_misses_total(self) -> int:
        return self._pool.misses_total if self._pool is not None else 0

    @property
    def prefix_evictions_total(self) -> int:
        return self._pool.evictions_total if self._pool is not None else 0

    @property
    def prefix_hit_ratio(self) -> float:
        n = self.prefix_hits_total + self.prefix_misses_total
        return self.prefix_hits_total / n if n else 0.0

    def submit(self, x: np.ndarray, *, max_new_tokens: int | None = None,
               timeout: float | None = None, ctx=None,
               slo_class: str = "standard") -> np.ndarray:
        """Block until every row of ``x (N, prompt_len)`` has finished
        generating; returns ``(N, prompt_len + max_new_tokens)`` int64
        (prompt included, post-retirement positions padded with
        ``eos_id``, or with token id 0 when no ``eos_id`` is configured
        — identical row semantics to the static scheduler, whose only
        retire reason without an eos is the full budget, so the 0-pad
        case is reachable only via per-request ``max_new_tokens``).

        ``max_new_tokens`` caps THIS request below the endpoint budget
        (iteration-level scheduling makes per-request budgets free:
        the row simply retires earlier); the output width stays the
        endpoint's. ``timeout``/``ctx`` follow ``_Batcher.submit``.
        ``slo_class`` sets queue priority and the shed watermark; a
        ``critical`` row that cannot bind may PREEMPT a lower-class
        resident (docs/ROBUSTNESS.md "Degradation ladder").
        """
        x = np.asarray(x, np.int32)
        if x.ndim != 2 or x.shape[1] != self._T:
            raise ValueError(
                f"expected prompts of shape (N, {self._T}), got "
                f"{tuple(x.shape)}"
            )
        budget = self._N if max_new_tokens is None else int(max_new_tokens)
        if not 1 <= budget <= self._N:
            raise ValueError(
                f"max_new_tokens must be in [1, {self._N}], got {budget}"
            )
        n = len(x)
        out = np.full(
            (n, self._T + self._N),
            self._eos if self._eos is not None else 0, np.int64,
        )
        out[:, :self._T] = x
        if n == 0:
            # Nothing to decode: answer immediately (the static batcher
            # round-trips an empty matrix too). Queueing it would hand
            # the loop a rowless item whose bogus occupant corrupts the
            # ledger.
            return out
        item = {
            "x": x, "budget": budget, "out": out, "next_row": 0,
            "remaining": n, "done": threading.Event(), "err": None,
            "abandoned": False, "t_submit": time.monotonic(),
            "slo_class": slo_class,
            "ctx": ctx if ctx is not None and ctx.sampled else None,
        }
        # Admission (class watermark, close check, deadline stamp) and
        # the bounded wait are the shared core's contract — identical
        # to _Batcher by construction. Abandoned rows already decoding
        # finish their (bounded) budget and are discarded; rows still
        # pending are skipped at bind.
        self._sched_core.admit(item, timeout)
        self._sched_core.wait(item, what="generation")
        return item["out"]

    def submit_stream(self, x: np.ndarray, *,
                      max_new_tokens: int | None = None,
                      timeout: float | None = None, ctx=None,
                      slo_class: str = "standard",
                      resume_tokens=None,
                      max_buffer: int = 4096) -> TokenStream:
        """Admit ONE prompt row ``(1, prompt_len)`` for streaming
        generation and return its :class:`TokenStream` immediately (the
        GenerateStream handler drains it; nothing blocks here beyond
        admission itself, which can shed). Single-row by contract:
        frame ordering and failover resume are per-sequence concepts —
        a client streams N prompts over N streams.

        ``timeout`` is STREAM-aware (docs/ROBUSTNESS.md): it bounds the
        submit-to-first-token wait (queue + prefill) and then each
        NEXT-TOKEN gap — the deadline slides forward at every published
        token — instead of total retirement time, so a long generation
        that is steadily producing tokens never expires mid-stream.

        ``resume_tokens`` is the router's mid-stream-failover prefix:
        tokens the CLIENT already holds. The row binds through the
        preemption-resume path (prompt re-prefill + forced-token
        replay, bit-identical at temperature 0) and the stream's sent
        cursor swallows the replayed prefix, so the client receives
        each token exactly once across the replica switch.
        """
        x = np.asarray(x, np.int32)
        if x.ndim != 2 or x.shape != (1, self._T):
            raise ValueError(
                f"streaming expects ONE prompt of shape (1, {self._T}), "
                f"got {tuple(x.shape)}"
            )
        budget = self._N if max_new_tokens is None else int(max_new_tokens)
        if not 1 <= budget <= self._N:
            raise ValueError(
                f"max_new_tokens must be in [1, {self._N}], got {budget}"
            )
        resume = [int(t) for t in resume_tokens] if resume_tokens else None
        stream = TokenStream(max_buffer, lag_sink=self._clock)
        if resume is not None:
            # The client already holds the whole replayed prefix.
            stream.seed(len(resume))
            # Degenerate resumes — the stream actually FINISHED on the
            # dead replica (terminal frame lost in the failover): there
            # is nothing left to generate, so answer the terminal
            # without burning a slot on a full replay.
            if self._eos is not None and self._eos in resume:
                stream.finish("eos")
                return stream
            if len(resume) >= budget:
                stream.finish("max_tokens")
                return stream
        out = np.full(
            (1, self._T + self._N),
            self._eos if self._eos is not None else 0, np.int64,
        )
        out[:, :self._T] = x
        item = {
            "x": x, "budget": budget, "out": out, "next_row": 0,
            "remaining": 1, "err": None,
            "abandoned": False, "t_submit": time.monotonic(),
            "slo_class": slo_class,
            "ctx": ctx if ctx is not None and ctx.sampled else None,
            "stream": stream,
            # Per-token-gap budget: _publish slides item["deadline"]
            # forward by this much at every published token.
            "gap_budget": timeout,
            # Consumed at bind: routes the row through the preemption-
            # resume path (forced-token replay).
            "resume_tokens": resume,
        }
        # The done Event is the terminal seam: every existing exit path
        # (_retire, _free_slot_on_error, queue expiry, close sweeps)
        # already stamps err/finish_reason then calls done.set() — the
        # StreamDone subclass turns that into the END frame.
        item["done"] = StreamDone(item, stream)
        self._sched_core.admit(item, timeout)
        return stream

    # ------------------------------------------------------------ loop

    def _publish(self, occ: dict, first: bool = False) -> None:
        """Flush the occupant's known-token list into its stream, if it
        has one (called after every ``occ["tokens"]`` append). A dead
        stream (client gone / buffer overflow) marks the item abandoned
        — the loop's reap pass frees the slot next iteration. A live
        publish slides the stream's next-token-gap deadline.

        A step's tokens are enqueued now and their handlers woken
        together with the rest of the round (:meth:`_land`): a handler
        woken in the middle of the loop's publishes can only take the
        interpreter away from them. A request's ``first`` token wakes
        its handler at once, ahead of that crowd."""
        item = occ["item"]
        stream = item.get("stream")
        if stream is None:
            return
        if not stream.publish(occ["tokens"], notify=first):
            item["abandoned"] = True
            return
        if not first:
            self._woken.append(stream)
        slide_stream_deadline(item, item.get("gap_budget"))

    def _reap_cancelled(self) -> None:
        """Free resident slots whose STREAM item died — client abandon,
        gRPC cancellation, or backpressure overflow (satellite 2: the
        cancel-propagation half of the streaming contract). Unary items
        keep their documented semantics: abandoned rows already
        decoding finish their bounded budget and are discarded."""
        for s in range(self._S):
            occ = self._occupant[s]
            if occ is None:
                continue
            item = occ["item"]
            if item.get("stream") is None:
                continue
            if not (item["abandoned"] or item["err"] is not None):
                continue
            self._occupant[s] = None
            self._active[s] = False
            self._release_block(occ)
            self.retired_total += 1
            _RETIRED.labels(reason="cancelled").inc()
            _TOKENS.inc(len(occ["tokens"]))
            self._sched_core.note_drained(1)
            if item["ctx"] is not None and occ["t_first"] is not None:
                # Most streams end here (the wire carries no budget, so
                # a client cancels when it has what it wants): without
                # this their traces would show no decode phase at all.
                _trace.TRACER.record_span(
                    "decode", item["ctx"], occ["t_first"],
                    time.monotonic() - occ["t_first"],
                    attrs={"slot": s, "tokens": len(occ["tokens"]),
                           "reason": "cancelled", **self._ride_attrs(occ)},
                )
            item["remaining"] -= 1
            slog.info(
                "gen.stream_cancelled", slot=s,
                tokens_generated=len(occ["tokens"]),
            )

    def _release_block(self, occ: dict) -> None:
        """Drop the occupant's prefix-block reference, if it holds one
        (once — retire, fault, and drain paths all funnel here)."""
        block = occ.pop("block", None)
        if block is not None and self._pool is not None:
            self._pool.release(block)

    def _free_slot_on_error(self, slot: int, e: Exception) -> None:
        """Fail ONE occupant's item over (a mid-prefill or per-request
        fault) and free its slot + prefix ref so the scheduler keeps
        serving later arrivals."""
        occ = self._occupant[slot]
        self._occupant[slot] = None
        self._active[slot] = False
        self._release_block(occ)
        item = occ["item"]
        if item["err"] is None:
            item["err"] = e
            item["done"].set()

    def _fail_occupants(self, e: Exception) -> None:
        """A step-kernel fault leaves the shared cache pytree in an
        unknown state, so it hits every resident row — decoding AND
        mid-prefill: fail their items over (a row cannot be replayed —
        its sampling position in the stream is gone) and free the
        slots so the scheduler keeps serving later arrivals."""
        for s in range(self._S):
            if self._occupant[s] is not None:
                self._free_slot_on_error(s, e)

    def _device_fault(self, e: Exception) -> None:
        """A REAL kernel call raised (not an injected hook fault, which
        fires before the dispatch), or a fetch did: the cache buffer was
        DONATED to that call and may already be consumed, so per-slot
        recovery is impossible — fail every resident over, rebuild a
        fresh zeroed cache (every slot is free after the fan-out, so
        zeroes are the correct contents), and drop the prefix pool,
        whose blocks lived in the dead cache. Whatever was launched
        after the call that failed took the same buffer: what is still
        unread is dropped unread. The scheduler then keeps serving
        later arrivals — the same contract as before, paid for with a
        cold prefix pool."""
        self._unread.clear()
        self._launched = None
        self._prev, self._first = np.zeros(self._S, np.int32), np.int32(0)
        self._fail_occupants(e)
        if self._make_cache is not None:
            try:
                self._cache = self._make_cache()
            except Exception:  # noqa: BLE001 — backend fully down
                log.exception("cache rebuild after device fault failed")
            self._routing_seen = None  # the new cache counts from zero
        if self._pool is not None:
            self._pool.clear()

    def _retire(self, slot: int, reason: str) -> None:
        occ = self._occupant[slot]
        item, row = occ["item"], occ["row"]
        toks = occ["tokens"]
        item["out"][row, self._T:self._T + len(toks)] = toks
        # Terminal state BEFORE done.set(): a streaming item's
        # StreamDone reads it to build the END frame.
        item["finish_reason"] = reason
        self._active[slot] = False
        self._occupant[slot] = None
        self._release_block(occ)
        self.retired_total += 1
        _RETIRED.labels(reason=reason).inc()
        _TOKENS.inc(len(toks))
        # Completions feed the drain-rate window behind the shed
        # replies' x-tdn-retry-after-ms hint.
        self._sched_core.note_drained(1)
        if item["ctx"] is not None:
            _trace.TRACER.record_span(
                "decode", item["ctx"], occ["t_first"],
                time.monotonic() - occ["t_first"],
                attrs={"slot": slot, "tokens": len(toks), "reason": reason,
                       **self._ride_attrs(occ)},
            )
        item["remaining"] -= 1
        if item["remaining"] == 0 and not item["abandoned"]:
            item["done"].set()

    def _note_first_token(self, occ: dict, now: float) -> None:
        """The occupant has its first token: stamp it, and for a traced
        request keep what the loop had counted by now, so that its
        ``decode`` span can say what it rode (:meth:`_ride_attrs`) at no cost
        per step."""
        occ["t_first"] = now
        if occ["item"]["ctx"] is not None:
            occ["rode"] = (self.batches_total, *self._clock.ride())

    def _ride_attrs(self, occ: dict) -> dict:
        """Attributes of a ``decode`` span: the step launches the
        request rode since its first token, the seconds of them the
        loop spent blocked on the device (``step_fetch_s``) and in its
        host phases (``host_s``), and the first and last loop iteration
        (``seq`` of ``obs.trace.ITERATIONS``), by which the request
        joins the iteration records."""
        steps0, iter0, fetch0, host0 = occ["rode"]
        iter1, fetch1, host1 = self._clock.ride()
        return {
            "steps": self.batches_total - steps0,
            "step_fetch_s": (fetch1 - fetch0) / 1e9,
            "host_s": (host1 - host0) / 1e9,
            "iter_first": iter0, "iter_last": iter1,
        }

    def _tier_keys(self, row: np.ndarray):
        """The prompt's cacheable-prefix candidates, longest first —
        the exact-match lookup/insert keys (the raw prefix bytes: no
        hash collisions to reason about). Lazy: ``lookup`` early-exits
        on the first (longest) hit, so a warm-pool deepest-tier hit
        copies exactly one prefix instead of materializing every tier
        of a long prompt on the scheduler loop thread."""
        return ((ln, row[:ln].tobytes()) for ln in self._tiers)

    def _bind_slot(self, item: dict, row: int,
                   resume: list | None = None) -> None:
        """Bind one pending row to a free slot (there is one — the
        caller checked): prefix-pool lookup, copy-on-write block copy
        on a hit, and the slot enters its chunked-prefill phase. No
        prompt tokens run here — chunks are the loop's per-iteration
        work, so binding never stalls the decode frontier.

        ``resume`` is a PREEMPTED row's generated token prefix: the
        slot re-prefills the prompt (prefix-cache hits make that
        cheap), then REPLAYS the prefix through the shared decode-step
        kernel with forced tokens — the exact computation the original
        run performed, so the resumed K/V and every subsequent greedy
        token are bit-identical to an unpreempted run (and a sampled
        run resumes its ORIGINAL stream instead of redrawing)."""
        slot = int(
            next(s for s in range(self._S) if self._occupant[s] is None)
        )
        now = time.monotonic()
        occ = {
            "item": item, "row": row, "tokens": [],
            "budget": item["budget"], "t_first": None,
            "t_bind": now, "fill": 0, "block": None,
            # Generated tokens to replay after the prompt re-prefill
            # (preemption resume); None on a fresh bind.
            "resume": list(resume) if resume else None,
        }
        self._occupant[slot] = occ
        self.rows_total += 1
        if resume is None:
            self._clock.queue_wait_ns += int((now - item["t_submit"]) * 1e9)
            self._clock.binds += 1
            if item["ctx"] is not None:
                _trace.TRACER.record_span(
                    "queue_wait", item["ctx"], item["t_submit"],
                    now - item["t_submit"],
                )
        if self._pool is None:
            return
        hit = self._pool.lookup(self._tier_keys(item["x"][row]))
        if hit is None:
            _PREFIX_MISSES.inc()
            return
        block, length = hit
        # Counted at lookup, BEFORE the copy, so this counter can never
        # diverge from the pool's own hits_total (which lookup() just
        # bumped) — a hit whose COW copy then faults is still a hit in
        # both ledgers.
        _PREFIX_HITS.inc()
        try:
            self._cache = self._copy(
                self._cache, np.int32(self._S + block), np.int32(slot)
            )
        except Exception as e:  # noqa: BLE001 — donated cache: global fault
            occ["block"] = block
            self._device_fault(e)
            return
        occ["fill"] = length
        occ["block"] = block
        if self._gp_model is not None:
            # The hit's savings: the chunk launches that will never run
            # for positions [0, length) (counted as savings, never as
            # useful work — the work was NOT done).
            GOODPUT.record_prefix_saved(
                self._gp_model.prefill_chunks_flops(0, length, self._chunk)
            )
        slog.info(
            "gen.prefix_hit", slot=slot, block=block, prefix_len=length,
            suffix_len=self._T - length,
        )

    def _next_prefill_slot(self) -> int | None:
        """The next slot with prefill work, round-robin so concurrent
        long prompts chunk fairly instead of head-of-line blocking each
        other."""
        for i in range(self._S):
            s = (self._prefill_rr + i) % self._S
            occ = self._occupant[s]
            if occ is not None and not self._active[s] \
                    and occ["fill"] < self._T:
                self._prefill_rr = (s + 1) % self._S
                return s
        return None

    def _maybe_insert_tiers(self, slot: int, occ: dict, start: int) -> None:
        """After a chunk lands, publish any newly-completed prefix tier
        in ``(start, fill]`` into the pool (slot -> block copy). Failure
        to insert — pool full of referenced blocks, or a copy fault —
        skips silently: caching is an optimization, never load-bearing."""
        row = occ["item"]["x"][occ["row"]]
        for length in reversed(self._tiers):  # ascending
            if not start < length <= occ["fill"]:
                continue
            if self._tier_at_fill and length != occ["fill"]:
                # Recurrent state is the prefix's only where the chunk
                # that just landed ended.
                continue
            block, evicted = self._pool.insert(row[:length].tobytes(), length)
            if evicted:
                _PREFIX_EVICTIONS.inc()
            if block is None:
                continue
            try:
                self._cache = self._copy(
                    self._cache, np.int32(slot), np.int32(self._S + block)
                )
            except Exception as e:  # noqa: BLE001 — donated cache: global
                log.warning("prefix-block insert copy failed: %s", e)
                self._device_fault(e)
                return

    def _launch_chunk(self, slot: int) -> None:
        """Launch ONE chunk of ``slot``'s pending prefill — the at-most-
        one-chunk-per-iteration budget that keeps a long prompt from
        freezing the resident decode streams — and wait for nothing:
        what the host books of a chunk it knows without the chunk's
        result. The final chunk yields the prompt's last-position
        sample, the request's first token (TTFT): the slot joins the
        step launched next, which takes that token from the device
        (``_FROM_CHUNK``), and the host reads it behind that launch
        (:meth:`_land_first`)."""
        clock = self._clock
        clock.mark(_PREFILL_DISPATCH)
        clock.prefilled = clock.launched = True
        occ = self._occupant[slot]
        item = occ["item"]
        start = occ["fill"]
        size = (
            self._T - start if self._chunk is None
            else min(self._chunk, self._T - start)
        )
        tokens = item["x"][occ["row"]:occ["row"] + 1, start:start + size]
        t0 = time.monotonic()
        if self.prefill_hook is not None:
            # Hook faults fire BEFORE the dispatch: the cache is still
            # intact, so only THIS request fails over — the mid-prefill
            # chaos contract (slot freed, prefix ref released).
            try:
                self.prefill_hook(tokens)
            except Exception as e:  # noqa: BLE001 — per item
                self._free_slot_on_error(slot, e)
                return
        # Nobody reads the token of a chunk that ends no prompt, nor of
        # a resume's re-prefill (its first token is known): where the
        # model has the program that ends without logits, that one runs.
        body = self._prefill_body is not None and (
            start + size < self._T or occ["resume"] is not None)
        try:
            if body:
                tok, cache = self._prefill_body(
                    self._params, self._cache, np.int32(slot), tokens,
                    np.int32(start),
                )
            else:
                tok, cache = self._prefill(
                    self._params, self._cache, np.int32(slot), tokens,
                    np.int32(start), self._next_key(),
                )
        except Exception as e:  # noqa: BLE001 — donated cache: global
            self._device_fault(e)
            return
        self._cache = cache
        self._launched = (_PREFILL_FETCH, tok)
        clock.fed()
        clock.mark(_PREFILL_POST)
        occ["fill"] = start + size
        self.prefill_chunks_total += 1
        self.prefill_body_chunks_total += body
        clock.prefill_tokens += size
        clock.prefill_starts += start
        self.attend_kernel_chunks_total += self._attend_kernel(size)
        self._count_positions(np.arange(start, start + size))
        if self._gp_model is not None:
            # A resume re-prefill's last-position logits are DISCARDED
            # (the first generated token is already known), so its
            # final chunk carries no sampled-unembed useful work.
            GOODPUT.record_prefill_chunk(
                self._gp_model, start, size,
                final=occ["fill"] >= self._T and occ["resume"] is None,
                body=body,
            )
        now = time.monotonic()
        if item["ctx"] is not None:
            # The launch alone: the loop waits for a chunk to end only
            # where it was a prompt's last (the `prefill` span) or
            # nothing decodes beside it.
            _trace.TRACER.record_span(
                "prefill.chunk", item["ctx"], t0, now - t0,
                attrs={"slot": slot, "start": start, "tokens": size},
            )
        if self._pool is not None:
            # The copy out of the slot runs behind the chunk: the
            # device takes launches in order.
            self._maybe_insert_tiers(slot, occ, start)
            if self._occupant[slot] is not occ:
                return  # an insert-copy fault failed the slot over
        if occ["fill"] < self._T:
            return
        self._pos[slot] = self._T
        # Tokens of this stream known or under way: the first is.
        occ["issued"] = 1
        if occ["resume"] is not None:
            # Preemption resume: the first generated token is KNOWN —
            # the prefill's last-position sample is discarded and never
            # fetched, the remaining prefix replays through the shared
            # step kernel with forced tokens (bit-identical K/V to the
            # original run; TTFT was observed on the first pass and is
            # not re-counted).
            known = occ["resume"]
            occ["resume"] = None
            occ["replay"] = collections.deque(known[1:])
            first = int(known[0])
            self._note_first_token(occ, now)
            if item["ctx"] is not None:
                _trace.TRACER.record_span(
                    "prefill", item["ctx"], occ["t_bind"],
                    now - occ["t_bind"],
                    attrs={
                        "slot": slot, "prompt_len": self._T,
                        "prefix_hit": occ["block"] is not None,
                        "resume_tokens": len(known),
                    },
                )
            occ["tokens"].append(first)
            self._publish(occ)
            self._active[slot] = True
            self._tok[slot] = first
            return
        if occ["budget"] > 1:
            self._active[slot] = True
            self._tok[slot] = _FROM_CHUNK
            self._first = tok
        self._unread.append(
            {"slot": slot, "occ": occ, "tok": tok, "t_launch": t0})

    def _land_first(self, rec: dict) -> None:
        """Read the token of a prompt's last chunk — the request's
        first — and publish it. The step launched behind the chunk
        already carries the slot; an EOS here is found one launch late
        like any other."""
        clock = self._clock
        clock.mark(_PREFILL_FETCH)
        slot, occ = rec["slot"], rec["occ"]
        try:
            tok = int(rec["tok"])  # the token fetch (host sync)
        except Exception as e:  # noqa: BLE001 — donated cache: global
            # On async backends a failed LAUNCH surfaces here, at the
            # first host sync of its results — the rebound cache is the
            # poisoned donated output, so this is a device fault, not a
            # per-item one (on the sync CPU backend a post-return fetch
            # failure is unreachable, so nothing is lost by escalating).
            self._device_fault(e)
            return
        clock.mark(_PREFILL_POST)
        if self._occupant[slot] is not occ:
            return  # failed over since the launch
        item = occ["item"]
        now = time.monotonic()
        # Prefill complete: `tok` is the sample from the prompt's last
        # position — the first generated token.
        ttft = now - item["t_submit"]
        _TTFT.observe(ttft)
        self.ttft_recent.append(ttft)
        clock.prefill_wait_ns += int((now - occ["t_bind"]) * 1e9)
        clock.first_tokens += 1
        self._note_first_token(occ, now)
        if item["ctx"] is not None:
            _trace.TRACER.record_span(
                "prefill", item["ctx"], occ["t_bind"], now - occ["t_bind"],
                attrs={
                    "slot": slot, "prompt_len": self._T,
                    "prefix_hit": occ["block"] is not None,
                },
            )
        occ["tokens"].append(tok)
        self._publish(occ, first=True)
        if self._eos is not None and tok == self._eos:
            self._retire(slot, "eos")
        elif len(occ["tokens"]) >= occ["budget"]:
            self._retire(slot, "max_tokens")

    def _step_failed(self, e: Exception, kernel: bool) -> None:
        # Rate-limited: a wedged backend fails every subsequent
        # step too — the first few stack traces are the signal,
        # thousands more per minute are noise.
        slog.exception(
            "gen.step_failed", error=f"{type(e).__name__}: {e}",
            active_slots=int(self._active.sum()),
            steps_total=self.batches_total,
        )
        if kernel:
            # A raise from the kernel call itself, or at the fetch of
            # its result, may have consumed the donated cache.
            self._device_fault(e)
        else:
            # A launch hook fires before the dispatch and leaves the
            # cache intact: what the device has already computed is
            # read and shipped first, then every resident fails over.
            self._land()
            self._fail_occupants(e)

    def _launch_step(self) -> bool:
        """Launch one compiled step over every decoding slot, from
        what is known without reading the step before it: a lane's
        input token is that step's output where it lies (the device),
        its position one further, and a slot whose budget the launches
        so far fill is simply not in this one, so no launch writes a
        row past a slot's extent. What only a read can tell (EOS, the
        guard, a cancel) is found one launch late: that lane is
        computed and thrown away (:meth:`_land_step`). False where the
        launch failed: nothing of it is left to read."""
        clock = self._clock
        clock.mark(_STEP_DISPATCH)
        clock.launched = True
        if self.launch_hook is not None:
            try:
                self.launch_hook(self._tok)
            except Exception as e:  # noqa: BLE001 — fan out to occupants
                self._step_failed(e, kernel=False)
                return False
        try:
            toks, ok, cache = self._step(
                self._params, self._cache, self._pos.copy(),
                self._active.copy(), self._tok.copy(), self._next_key(),
                self._prev, self._first,
            )
        except Exception as e:  # noqa: BLE001 — fan out to occupants
            self._step_failed(e, kernel=True)
            return False
        self._cache = cache
        self._prev = toks
        self._launched = (_STEP_FETCH, toks)
        clock.fed()
        if self._unread and "lanes" in self._unread[0]:
            clock.steps_ahead += 1
        lanes = []
        idle = mid = 0
        for s in range(self._S):
            occ = self._occupant[s]
            if not self._active[s]:
                if occ is not None and occ["fill"] < self._T:
                    mid += 1
                else:
                    idle += 1  # empty, or its last token is under way
                continue
            # A replayed lane's sample is discarded: its next token is
            # already known, and is the next launch's input.
            forced = occ["replay"].popleft() if occ.get("replay") else None
            lanes.append((s, occ, int(self._pos[s]), forced))
            self._pos[s] += 1
            self._tok[s] = _FROM_STEP if forced is None else forced
            occ["issued"] += 1
            if occ["issued"] >= occ["budget"]:
                self._active[s] = False  # its last token is under way
        self._unread.append({
            "toks": toks, "ok": ok, "lanes": lanes, "idle": idle,
            "mid": mid,
        })
        return True

    def _land_step(self, rec: dict) -> None:
        """Read a step's tokens (it has finished: the loop waited for
        it before it launched the next), account, publish, retire."""
        clock = self._clock
        clock.mark(_STEP_FETCH)
        clock.launched = True
        try:
            if self.fetch_hook is not None:
                self.fetch_hook(rec["toks"])
            toks = np.asarray(rec["toks"]).tolist()
            ok = np.asarray(rec["ok"]) if rec["ok"] is not None else None
        except Exception as e:  # noqa: BLE001 — fan out to occupants
            # Async backends surface a failed launch at this first host
            # sync: the rebound cache is the poisoned donated output,
            # and the step launched behind this one took it, so recover
            # as a device fault, unlike the launch hook's fault, which
            # fires before the dispatch and leaves the cache intact.
            self._step_failed(e, kernel=True)
            return
        clock.mark(_STEP_ACCOUNT)
        # A lane whose occupant has gone since the launch (EOS at the
        # read before, a cancel, a fault) was computed for nobody: the
        # row it wrote lies past the frontier of whoever holds the slot
        # now, and its token is not shipped.
        lanes = [ln for ln in rec["lanes"] if self._occupant[ln[0]] is ln[1]]
        discarded = len(rec["lanes"]) - len(lanes)
        # Act on the in-kernel numeric guard (host decision — the
        # runtime opt-out never reshapes the compiled kernel): a slot
        # whose logits went non-finite fails over ALONE with INTEGRITY
        # before its garbage token ships; every other slot's stream is
        # untouched (bit-parity preserved).
        bad_slots: list[int] = []
        if ok is not None and _integrity.GUARD.enabled:
            bad_slots = [ln[0] for ln in lanes if not ok[ln[0]]]
        if bad_slots:
            _integrity.GUARD_ROWS_FAILED.inc(len(bad_slots))
            _integrity.GUARD_LAUNCHES.inc()
            from tpu_dist_nn.utils.errors import IntegrityError

            for s in bad_slots:
                slog.warning(
                    "gen.integrity_guard_tripped", slot=s,
                    tokens_generated=len(self._occupant[s]["tokens"]),
                )
                self._free_slot_on_error(s, IntegrityError(
                    f"numeric guard: decode step produced non-finite "
                    f"logits for slot {s} — failing this row instead "
                    f"of shipping a garbage token"
                ))
            lanes = [ln for ln in lanes if ln[0] not in bad_slots]
        self.batches_total += 1
        self.discarded_lanes_total += discarded
        active = clock.active_slots = len(lanes)
        self._count_positions(np.array([ln[2] for ln in lanes], np.int32))
        if self._kv_tiles is not None:
            # What the launch read: every lane it decoded at its
            # position, the step's other slots as at position 0.
            at = np.zeros(self._S, np.int32)
            for ln in rec["lanes"]:
                at[ln[0]] = ln[2]
            visited, skipped = self._kv_tiles(at)
            self.step_kv_tiles_visited_total += visited
            self.step_kv_tiles_skipped_total += skipped
        self.slot_steps_total += active
        self._m_rows.observe(active)
        if self._gp_model is not None:
            # Goodput split of this launch at slot granularity (Orca's
            # waste taxonomy): active lanes are useful up to their live
            # attention frontier (launch-time pos), lanes re-doing what
            # a preemption threw away are pad (preempt_replay), lanes
            # computed for an occupant that had gone are pad
            # (discarded_lane), occupied-but-chunking lanes are
            # mid_prefill pad, the rest idle pad.
            GOODPUT.record_decode_step(
                self._gp_model,
                [ln[2] for ln in lanes if ln[3] is None],
                rec["idle"] + len(bad_slots), rec["mid"],
                replay_slots=sum(ln[3] is not None for ln in lanes),
                discarded_slots=discarded,
            )
        clock.mark(_STEP_PUBLISH)
        for s, occ, _pos, forced in lanes:
            if forced is not None:
                # Preemption replay: the step WROTE this position's
                # K/V from the forced token (the same computation the
                # original run performed); its sample is discarded —
                # the next token is already known. No retire checks:
                # the replayed stream was mid-decode when preempted.
                occ["tokens"].append(int(forced))
                self._publish(occ)
                continue
            tok = toks[s]
            occ["tokens"].append(tok)
            self._publish(occ)
            if self._eos is not None and tok == self._eos:
                self._retire(s, "eos")
            elif len(occ["tokens"]) >= occ["budget"]:
                self._retire(s, "max_tokens")

    def _await_device(self) -> None:
        """Wait, off the interpreter, until the last launch of this
        iteration (its step; its chunk where nothing decodes yet) has
        finished, without reading it. The loop has to wait for the
        device somewhere; here, the wait ends right before the next
        reap / admit / bind, so a request that arrived meanwhile is
        bound and its chunk launched at once and not behind a step
        queued earlier, and the handler threads the publishes woke have
        the interpreter to themselves. The next step is then launched
        from this one's tokens where they lie and this one is read
        behind that launch: account, publish and retire stay in the
        step's shadow, the dispatch does not (nothing is queued while
        the loop admits and launches). Where the host is the slower
        (the launch finished long ago) this waits for nothing."""
        launched, self._launched = self._launched, None
        if launched is None:
            return
        phase, result = launched
        wait = getattr(result, "block_until_ready", None)
        if wait is None:
            return  # an injected kernel's result is the host's already
        self._clock.mark(phase)
        try:
            wait()
        except Exception as e:  # noqa: BLE001 — fan out to occupants
            # A failed launch surfaces at the first wait for it.
            self._step_failed(e, kernel=True)

    def _land(self, keep: int = 0) -> None:
        """Read, oldest first, every launch still unread but the
        ``keep`` newest. Whatever needs the host's view of the streams
        to be final (a preemption keeps the tokens it replays; a launch
        hook's fault fails over what was shipped) calls it with none
        kept. A device fault on the way drops the rest unread.

        A step's publishes only enqueue (:meth:`_publish`): its
        handlers are woken here, together. Behind a chunk that ended a
        prompt they wait for the first token, which then goes out ahead
        of them, but only as long again as the chunk has already had
        since its launch: one that is not through by then (a long
        chunk) lets the round go first."""
        while len(self._unread) > keep:
            rec = self._unread.popleft()
            if "lanes" in rec:
                self._land_step(rec)
                continue
            ready = getattr(rec["tok"], "is_ready", None)
            if ready is not None and self._woken:
                due = 2 * time.monotonic() - rec["t_launch"]
                while not ready() and time.monotonic() < due:
                    time.sleep(0)  # off the interpreter
                if not ready():
                    self._wake_round()
            self._land_first(rec)
        self._wake_round()

    def _wake_round(self) -> None:
        woken, self._woken = self._woken, []
        for stream in woken:
            stream.wake()

    def _resident(self) -> bool:
        """Any slot occupied — decoding or mid-prefill — or a launch
        still unread (all must drain before close() may stop the loop,
        or the loop may sleep)."""
        return bool(self._unread) or any(
            o is not None for o in self._occupant)

    def _next_bindable(self, max_rank: int | None = None):  # caller-holds: _cond
        """The next row to bind, in class-priority order across BOTH
        sources — preempted rows awaiting resume and the fresh queue
        (a tie goes to the resume row: it was admitted earlier).
        ``max_rank=0`` restricts to critical (the preemption pop).
        Returns ``("resume", entry)`` / ``("fresh", (item, row))`` /
        None."""
        core = self._sched_core
        while True:
            # Best-ranked resume entry, FIFO within rank: _resume is
            # one deque in preemption order, so a head-only peek would
            # let an earlier best_effort eviction shadow a later
            # standard one.
            entry = idx = None
            e_rank = 99
            for i, cand in enumerate(self._resume):
                r = CLASS_RANK.get(cand["slo_class"], 1)
                if max_rank is not None and r > max_rank:
                    continue
                if r < e_rank:
                    entry, idx, e_rank = cand, i, r
                    if r == 0:
                        break  # nothing outranks critical
            f_rank = core.peek_rank()
            if (f_rank is not None and max_rank is not None
                    and f_rank > max_rank):
                f_rank = None
            if entry is not None and (f_rank is None or e_rank <= f_rank):
                del self._resume[idx]
                item = entry["item"]
                if item["abandoned"] or item["err"] is not None:
                    continue  # waiter gone while awaiting resume
                dl = item.get("deadline")
                if dl is not None and time.monotonic() >= dl:
                    # Budget died while the row waited to resume: same
                    # expiry contract as a queued entry.
                    core._expire(item, time.monotonic())
                    continue
                return "resume", entry
            got = core.pop_row(max_rank=max_rank)
            if got is not None:
                return "fresh", got
            if entry is None:
                return None
            # Fresh queue exhausted (or all dead): retry the resume
            # head on the next pass.

    def _bind(self, bindable) -> None:
        kind, data = bindable
        if kind == "resume":
            self._bind_slot(data["item"], data["row"],
                            resume=data["tokens"])
        else:
            item, row = data
            # A streaming failover resume (submit_stream's
            # resume_tokens) rides the SAME replay path a preemption
            # victim uses: re-prefill the prompt, force-replay the
            # already-delivered tokens, continue bit-identically.
            self._bind_slot(item, row,
                            resume=item.pop("resume_tokens", None))

    def _pick_victim(self) -> int | None:
        """The slot to preempt for a critical bind: never a critical
        resident; prefer occupants whose waiter is already gone
        (abandoned / budget-expired — evicting them costs nothing),
        then the LOWEST class, then the fewest generated tokens (the
        cheapest replay). None when every resident is critical."""
        now = time.monotonic()
        best = best_key = None
        for s in range(self._S):
            occ = self._occupant[s]
            if occ is None:
                continue
            item = occ["item"]
            rank = CLASS_RANK.get(item.get("slo_class", "standard"), 1)
            if rank == 0:
                continue
            dl = item.get("deadline")
            dead = item["abandoned"] or (dl is not None and now >= dl)
            key = (0 if dead else 1, -rank, len(occ["tokens"]))
            if best_key is None or key < best_key:
                best_key, best = key, s
        return best

    def _preempt_slot(self, slot: int) -> None:
        """Evict one resident so a critical row can bind: the victim's
        prompt + generated prefix re-queue for resume (re-prefill +
        forced-token replay — bit-identical continuation), its slot
        and prefix-block reference free immediately."""
        now = time.monotonic()
        occ = self._occupant[slot]
        item = occ["item"]
        cls = item.get("slo_class", "standard")
        # The full known generated stream, whatever phase the victim
        # was in: mid-resume-prefill (resume holds it all), mid-replay
        # (tokens + the un-replayed remainder), or plain decoding.
        if occ.get("resume"):
            prefix = list(occ["resume"])
        else:
            prefix = list(occ["tokens"]) + list(occ.get("replay") or ())
        self._occupant[slot] = None
        self._active[slot] = False
        self._release_block(occ)
        self.preempted_total += 1
        _PREEMPTED.labels(slo_class=cls).inc()
        if item["ctx"] is not None and occ["t_first"] is not None:
            _trace.TRACER.record_span(
                "decode", item["ctx"], occ["t_first"],
                now - occ["t_first"],
                attrs={"slot": slot, "tokens": len(occ["tokens"]),
                       "reason": "preempted", **self._ride_attrs(occ)},
            )
        slog.info(
            "gen.preempted", slot=slot, slo_class=cls,
            tokens_generated=len(prefix),
        )
        if item["abandoned"] or item["err"] is not None:
            return  # nobody is waiting: evicted work is simply dropped
        with self._cond:
            self._resume.append({
                "item": item, "row": occ["row"], "tokens": prefix,
                "slo_class": cls,
            })

    def _preempt_for_critical(self) -> None:
        """While a critical row is queued with no free slot, evict the
        best victim and bind the critical row INTO the freed slot —
        same scheduler iteration, so the class the SLO pages on never
        waits out a lower-class resident's full decode."""
        while True:
            victim = self._pick_victim()
            if victim is None:
                return
            with self._cond:
                got = self._next_bindable(max_rank=0)
            if got is None:
                return
            # The victim's stream has to be whole before it is kept for
            # replay: read the step still out. That may retire a row
            # (the victim too), and then nobody has to go.
            self._land()
            if all(o is not None for o in self._occupant):
                self._preempt_slot(victim)
            self._bind(got)

    def _loop(self) -> None:
        core = self._sched_core
        clock = self._clock
        clock.start()
        while True:
            # Cancel propagation first: slots freed by dead streams are
            # bindable THIS iteration (satellite 2 — a cancel storm must
            # not strand slots for even one extra step).
            clock.end_iteration()  # the last one; this one opens in reap
            self._reap_cancelled()
            clock.mark(_ADMIT)
            admits = []
            with self._cond:
                while (not core.closed and not core.has_pending()
                       and not self._resume and not self._resident()):
                    clock.mark(_IDLE)
                    self._cond.wait()
                    clock.mark(_ADMIT)
                if core.closed and not self._resident():
                    clock.stop()
                    return  # close() sweeps whatever is still pending
                if not core.closed:
                    free = sum(1 for o in self._occupant if o is None)
                    while len(admits) < free:
                        got = self._next_bindable()
                        if got is None:
                            break
                        admits.append(got)
            core.drain_deferred()
            clock.mark(_BIND)
            # Device work OUTSIDE the lock: submitters must never block
            # behind a block copy, a prefill chunk, or a step.
            for bindable in admits:
                self._bind(bindable)
            if self._preemption and not core.closed:
                self._preempt_for_critical()
            # One launch ahead: the chunk and the next step go to the
            # device on what is known without a read, and only then is
            # the step launched an iteration ago read, and behind it
            # the chunk's token. Account, publish and the handlers'
            # sends run while the device works; then the loop waits
            # for it.
            slot = self._next_prefill_slot()
            if slot is not None:
                self._launch_chunk(slot)
            ahead = self._active.any() and self._launch_step()
            self._land(keep=1 if ahead else 0)
            self._await_device()
            clock.device_idle()
            if self._routing is not None:
                self._routing_age += 1
                if self._routing_age >= _ROUTING_EVERY \
                        or not self._resident():
                    self._read_routing()

    # ------------------------------------------------------------ close

    def close(self, timeout: float = 10.0) -> None:
        """Stop admitting, let resident rows — including half-prefilled
        slots, which finish their remaining chunks — complete their
        (bounded) decodes, then fail still-pending waiters over as
        UNAVAILABLE (preempted rows awaiting resume included) — the
        ``_Batcher.close`` contract ``GracefulDrain`` relies on, now
        one implementation in the shared core."""
        from tpu_dist_nn.utils.errors import UnavailableError

        self._sched_core.close_begin()
        self._thread.join(timeout=timeout)
        # Preempted rows still awaiting a resume slot are pending too:
        # their waiters fail over like any queued entry's. Popped
        # under _cond, so a still-alive (wedged past the join timeout)
        # loop thread and this sweep can never double-serve or strand
        # an entry.
        leftovers = []
        with self._cond:
            while self._resume:
                leftovers.append(self._resume.popleft())
        for entry in leftovers:
            item = entry["item"]
            if not item["abandoned"] and item["err"] is None:
                item["err"] = UnavailableError(
                    "server shut down before this request was served"
                )
                item["done"].set()
        self._sched_core.sweep_leftovers()
