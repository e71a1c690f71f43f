"""Replica pool for the multi-replica data plane (docs/SCALING.md).

The reference's orchestrator spawns N nodes and chains them into ONE
linear pipeline (``run_grpc_fcnn.py``); PRs 1-7 made a single engine
process fast, resilient, and observable, and this module is the lift
from "one pipeline" to "a fleet": a :class:`ReplicaPool` manages N
backend engine endpoints for the gRPC front door
(:mod:`tpu_dist_nn.serving.router`), owning the three things a router
must know about a replica —

* **Load.** Power-of-two-choices (Mitzenmacher 2001) needs a load
  signal: the pool scrapes each replica's existing
  ``tdn_batcher_pending_rows`` / ``tdn_gen_slot_occupancy_ratio``
  gauges from its ``--metrics-port`` endpoint on an interval, and
  blends them with the router's own live outstanding-request count.
  Gauge data is STALENESS-BOUNDED: past ``load_staleness`` seconds the
  score degrades to least-outstanding-requests (the signal the router
  can always trust because it produced it).
* **Health.** Each replica reuses the per-target
  :class:`~tpu_dist_nn.serving.resilience.CircuitBreaker`
  (``for_target``) the client stack already speaks — the router
  records outcomes, the pool stops placing onto open breakers and
  lets the post-cooldown probe through. ``remove()`` / respawn call
  ``CircuitBreaker.evict`` so a NEW server on a reused address never
  inherits its predecessor's open breaker (the registry is
  process-global and was never pruned before this).
* **Membership + drain.** ``drain()`` marks a replica not-placeable
  and (for pool-spawned local replicas) SIGTERMs it so its own
  :class:`~tpu_dist_nn.serving.resilience.GracefulDrain` runs the
  zero-downtime sequence — ``/healthz`` flips ``draining: true``, the
  pool's scraper observes it, in-flight work finishes, the process
  exits and is respawned on the SAME address, and the scraper
  re-admits it the moment ``/healthz`` reports ready again. Remote
  replicas follow the identical choreography with the operator (or
  their init system) doing the SIGTERM/restart.

Session affinity: ``place(session_key=...)`` pins a session to the
replica that served it last (the replica holding its KV/prefix-cache
state — Orca-style continuous batching makes that state valuable),
re-pinning only when the pinned replica stops being placeable. A
session's FIRST placement uses p2c when any load data exists, else
rendezvous (highest-random-weight) hashing so a cold pool still
spreads sessions consistently.

Everything here is stdlib + the in-repo obs/resilience modules; the
scraper uses ``urllib`` against the same ``/metrics`` + ``/healthz``
endpoints operators already curl.
"""

from __future__ import annotations

import collections
import concurrent.futures
import hashlib
import json
import logging
import random
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import grpc

from tpu_dist_nn.obs.log import get_logger
from tpu_dist_nn.obs.registry import REGISTRY
from tpu_dist_nn.serving.resilience import CircuitBreaker
from tpu_dist_nn.serving.wire import SERVICE_NAME

log = logging.getLogger(__name__)
slog = get_logger(__name__)

# 1 while the pool will place new requests on this replica (ACTIVE and
# last health scrape did not say draining), 0 otherwise — the
# per-replica availability view of the fleet (docs/OBSERVABILITY.md).
REPLICA_HEALTHY = REGISTRY.gauge(
    "tdn_router_replica_healthy",
    "1 while the router pool will place new requests on this replica "
    "(0 = draining, removed, or breaker-open)",
    labels=("replica",),
)

# 1 while a replica sits in integrity quarantine (docs/ROBUSTNESS.md
# "Silent corruption & quarantine") — distinct from healthy=0, which a
# drain also produces: quarantined means "answered WRONG", not "away".
REPLICA_QUARANTINED = REGISTRY.gauge(
    "tdn_router_replica_quarantined",
    "1 while the replica is quarantined by the integrity plane "
    "(canary/spot-check/guard/fingerprint verdict)",
    labels=("replica",),
)

QUARANTINES = REGISTRY.counter(
    "tdn_quarantines_total",
    "replicas moved to QUARANTINED by the integrity plane, by detector",
    labels=("reason",),
)

ACTIVE, DRAINING, REMOVED = "active", "draining", "removed"
# Integrity quarantine: the replica answered WRONG (canary mismatch,
# spot-check arbitration, repeated INTEGRITY errors, or a weights
# fingerprint disagreeing with the fleet). Not placeable, and —
# unlike DRAINING — never auto-rejoined by a mere ready scrape, and
# unlike an open breaker never half-open-probed back in: re-admission
# requires the fingerprint AND canary checks to pass (unquarantine).
QUARANTINED = "quarantined"


def _sum_series(parsed: dict, family: str) -> float | None:
    """Sum every labeled series of ``family`` in a parsed /metrics
    scrape (None when the family is absent — a replica that never
    served keeps 'no data' distinct from 'zero load')."""
    total, seen = 0.0, False
    for k, v in parsed.items():
        if k == family or (isinstance(k, str) and k.startswith(family + "{")):
            total += float(v)
            seen = True
    return total if seen else None


class Replica:
    """One backend endpoint: gRPC target, optional metrics endpoint,
    breaker, live load view, and (for pool-spawned replicas) the
    subprocess handle."""

    def __init__(self, target: str, metrics_target: str | None = None,
                 weight: float | None = None):
        self.target = target
        self.metrics_target = metrics_target
        self.state = ACTIVE
        self.breaker = CircuitBreaker.for_target(target)
        # Explicit capacity weight (--replica-weights): scales the p2c
        # load score so a replica that can absorb k x the rows of a
        # baseline one compares as 1/k as loaded at equal backlog —
        # heterogeneous fleets (TPU replica + CPU spillover) mix
        # without starving the fast one. None = derive from the
        # scraped warm-bucket ladder, else 1.0 (homogeneous).
        self.weight = float(weight) if weight is not None else None
        # Last scraped tdn_engine_warm_buckets value: the implicit
        # capacity signal when no explicit weight was configured (a
        # replica with a deeper precompiled bucket ladder is
        # provisioned for more concurrent rows).
        self.warm_buckets: float | None = None
        # Scale-down in progress (serving/autoscale.py): the replica is
        # draining toward REMOVAL, so the supervisor must not respawn
        # its exited child and the ready-scrape must not re-admit it.
        self.decommissioning = False
        # Requests this router currently has in flight on the replica —
        # the always-available load signal (and the drain barrier).
        self.outstanding = 0
        # Last scraped gauge view (None until a successful scrape).
        self.pending_rows: float | None = None
        self.occupancy: float | None = None
        self.scraped_at: float | None = None
        # /healthz said draining: the replica is mid-rolling-restart.
        self.reported_draining = False
        # The drain was OBSERVED (healthz said draining, or the replica
        # went unreachable while DRAINING): the gate for auto-rejoin. A
        # ready scrape alone must NOT undrain an admin-drained replica
        # that never began restarting — that would revert the
        # operator's `--drain-replica` within one scrape tick.
        self.drain_observed = False
        # Consecutive scrape ticks with /healthz unreachable. One blown
        # probe (GC pause, host load, transient timeout) on a DRAINING
        # replica is indistinguishable from "old process exited
        # mid-restart" — only repeated loss counts as drain observation.
        self.unreachable_ticks = 0
        # Last boot_id /healthz reported (None until one is seen). A
        # DRAINING replica answering ready with a DIFFERENT boot_id was
        # restarted — even when the whole restart fell between two
        # scrape ticks and neither timing detector could see it.
        self.boot_id: str | None = None
        # Integrity plane (serving/integrity.py). fingerprint is the
        # whole-model weights fingerprint /healthz last reported;
        # quarantine_boot_id records which process incarnation was
        # indicted, so only a RESPAWNED replica (different boot_id) is
        # eligible for automatic reverify-readmission.
        self.fingerprint: str | None = None
        self.canary_at: float = 0.0
        self.quarantine_reason: str | None = None
        self.quarantine_evidence: dict | None = None
        self.quarantine_boot_id: str | None = None
        self.quarantined_at: float | None = None
        # Cumulative INTEGRITY (DATA_LOSS) errors the router observed
        # from this replica — the numeric-guard verdict counter.
        self.integrity_strikes = 0
        # Pool-spawned local replica bookkeeping (tdn router --spawn).
        self.proc: subprocess.Popen | None = None
        self.spawn_argv: list[str] | None = None
        # A respawn is in flight (scraper auto-respawn or an explicit
        # restart_replica) — the other path must not double-spawn.
        self.respawning = False
        # Minimum spacing between auto-respawn attempts: claimed at
        # the START of every attempt, so neither a spawn that fails
        # outright NOR a child that boots, reports ports, then crashes
        # can turn the scrape loop into a hot spawn loop (each cycle
        # burns an engine compile/warmup).
        self.respawn_backoff_until = 0.0
        # (The mutable fields above are guarded by the POOL's lock —
        # cross-object guarding the lock-discipline rule cannot
        # express; only the channel state below is this object's own.)
        self._channel = None  # guarded-by: _lock
        self._stubs: dict[str, object] = {}  # guarded-by: _lock
        self._stream_stubs: dict[str, object] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    # ------------------------------------------------------------ wire

    def _stub(self, method: str):
        with self._lock:
            if self._channel is None:
                self._channel = grpc.insecure_channel(
                    self.target,
                    options=[
                        ("grpc.max_send_message_length", -1),
                        ("grpc.max_receive_message_length", -1),
                    ],
                )
            stub = self._stubs.get(method)
            if stub is None:
                stub = self._channel.unary_unary(
                    f"/{SERVICE_NAME}/{method}",
                    request_serializer=bytes,
                    response_deserializer=bytes,
                )
                self._stubs[method] = stub
        return stub

    def call(self, method: str, payload: bytes, *, timeout=None,
             metadata=()):
        """Forward raw request bytes to this replica (one persistent
        channel per replica, stubs cached per method)."""
        return self._stub(method)(payload, timeout=timeout,
                                  metadata=tuple(metadata))

    def call_future(self, method: str, payload: bytes, *, timeout=None,
                    metadata=()):
        """The non-blocking twin of :meth:`call`: returns the grpc
        future so the router's hedging path can race two replicas and
        ``cancel()`` the loser (a blocking call cannot be abandoned
        without leaking its worker thread for the full timeout)."""
        return self._stub(method).future(payload, timeout=timeout,
                                         metadata=tuple(metadata))

    def call_stream(self, method: str, payload: bytes, *, timeout=None,
                    metadata=()):
        """Server-streaming forward (GenerateStream): returns the grpc
        response iterator over raw frame bytes — the router relays them
        without decoding (serving/wire.py owns the frame format)."""
        with self._lock:
            if self._channel is None:
                self._channel = grpc.insecure_channel(
                    self.target,
                    options=[
                        ("grpc.max_send_message_length", -1),
                        ("grpc.max_receive_message_length", -1),
                    ],
                )
            stub = self._stream_stubs.get(method)
            if stub is None:
                stub = self._channel.unary_stream(
                    f"/{SERVICE_NAME}/{method}",
                    request_serializer=bytes,
                    response_deserializer=bytes,
                )
                self._stream_stubs[method] = stub
        return stub(payload, timeout=timeout, metadata=tuple(metadata))

    def close_channel(self) -> None:
        with self._lock:
            if self._channel is not None:
                self._channel.close()
            self._channel = None
            self._stubs = {}
            self._stream_stubs = {}

    # ------------------------------------------------------------ load

    def fresh(self, now: float, staleness: float) -> bool:
        return (
            self.scraped_at is not None
            and now - self.scraped_at <= staleness
            and self.pending_rows is not None
        )

    @property
    def capacity_weight(self) -> float:
        """Relative capacity for weighted p2c: the explicit
        ``--replica-weights`` value when configured, else the scraped
        warm-bucket ladder depth (a replica precompiled for more
        buckets is provisioned for more concurrent rows), else 1.0."""
        if self.weight is not None:
            return max(self.weight, 1e-6)
        if self.warm_buckets is not None and self.warm_buckets >= 1.0:
            return float(self.warm_buckets)
        return 1.0

    def load_score(self, now: float, staleness: float,
                   occupancy_weight: float) -> float:
        """The p2c comparison key: the router's own outstanding count,
        plus the scraped backlog while it is fresh. ``occupancy_weight``
        converts the slot-occupancy RATIO into row-equivalents (one
        full continuous-decode ladder ~ a gen_slots-sized backlog).
        The blend is divided by :attr:`capacity_weight`, so a 4x
        replica at backlog 8 ties a 1x replica at backlog 2 instead of
        losing every comparison the moment it absorbs its fair share."""
        score = float(self.outstanding)
        if self.fresh(now, staleness):
            score += float(self.pending_rows or 0.0)
            score += occupancy_weight * float(self.occupancy or 0.0)
        return score / self.capacity_weight

    def snapshot(self) -> dict:
        snap = {
            "target": self.target,
            "metrics_target": self.metrics_target,
            "state": self.state,
            "outstanding": self.outstanding,
            "pending_rows": self.pending_rows,
            "occupancy": self.occupancy,
            "breaker": self.breaker.state,
            "draining_reported": self.reported_draining,
            "spawned": self.proc is not None,
            "weight": self.capacity_weight,
            "decommissioning": self.decommissioning,
        }
        if self.fingerprint is not None:
            snap["fingerprint"] = self.fingerprint
        if self.state == QUARANTINED:
            snap["quarantine_reason"] = self.quarantine_reason
            snap["quarantined_at"] = self.quarantined_at
        if self.integrity_strikes:
            snap["integrity_strikes"] = self.integrity_strikes
        return snap


class ReplicaPool:
    """N engine replicas + the placement policy over them.

    ``place()`` implements power-of-two-choices over
    :meth:`Replica.load_score` (two uniform candidates, route to the
    less loaded — the classic exponential improvement over random
    placement without the herding of always-least-loaded), with:

    * session affinity — a ``session_key`` that placed before goes
      back to the same replica while it remains placeable;
    * a rendezvous-hash fallback for session FIRST placements when no
      replica has any load data (cold pool, no metrics endpoints);
    * breaker gating — open-breaker replicas are skipped until their
      cooldown, then exactly one request probes them.

    Thread-safe; the scrape loop (``start()``) refreshes load and
    health on ``scrape_interval``. Tests drive ``scrape_once()``
    directly.
    """

    def __init__(self, targets=(), metrics_targets=None, weights=None, *,
                 load_staleness: float = 5.0,
                 occupancy_weight: float = 32.0,
                 scrape_interval: float = 1.0,
                 scrape_timeout: float = 1.0,
                 session_capacity: int = 8192,
                 seed: int | None = None):
        self._lock = threading.RLock()
        self._replicas: dict[str, Replica] = {}  # guarded-by: _lock
        # guarded-by: _lock
        self._sessions: collections.OrderedDict[str, str] = (
            collections.OrderedDict()
        )
        self._session_capacity = int(session_capacity)
        self.load_staleness = float(load_staleness)
        self.occupancy_weight = float(occupancy_weight)
        self.scrape_interval = float(scrape_interval)
        self.scrape_timeout = float(scrape_timeout)
        self._rng = random.Random(seed)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # Cumulative membership/drain state transitions (drain, undrain,
        # remove, crash-respawn, scrape-observed drains): the flight
        # recorder's drain/failover detector fires on the DELTA, so the
        # choreography itself is an incident trigger without the
        # detector having to diff per-replica states.
        self.transitions_total = 0  # guarded-by: _lock
        # spawn_local calls still waiting for their child's ports.
        self._booting = 0  # guarded-by: _lock
        # Integrity plane (serving/integrity.py). canary: a
        # CanaryProber ridden on the scrape loop (None = probing off).
        # on_quarantine(target, reason, evidence): the incident hook —
        # serve_router wires it to the flight recorder so every verdict
        # freezes a bundle naming the evidence. fleet_fingerprint: the
        # golden whole-model weights fingerprint, established from the
        # first ACTIVE ready replica that reports one; any replica
        # reporting a DIFFERENT fingerprint is refused admission
        # (quarantined) while fingerprint_gate is on.
        self.canary = None
        self.on_quarantine = None
        self.fleet_fingerprint: str | None = None  # guarded-by: _lock
        self.fingerprint_gate = True
        # INTEGRITY (DATA_LOSS) replies from one replica before the
        # router's guard verdict quarantines it. 3, not 1: one launch
        # can fail rows for a transiently absurd input; a replica that
        # keeps producing non-finite activations is corrupt.
        self.guard_quarantine_threshold = 3
        self._scrape_pool: concurrent.futures.ThreadPoolExecutor | None \
            = None
        metrics_targets = list(metrics_targets or ())
        weights = list(weights or ())
        for i, t in enumerate(targets):
            self.add(t, metrics_targets[i] if i < len(metrics_targets)
                     else None,
                     weight=weights[i] if i < len(weights) else None)

    # ------------------------------------------------------ membership

    def add(self, target: str, metrics_target: str | None = None, *,
            weight: float | None = None) -> Replica:
        with self._lock:
            existing = self._replicas.get(target)
            if existing is not None and existing.state != REMOVED:
                if metrics_target is not None:
                    existing.metrics_target = metrics_target
                if weight is not None:
                    existing.weight = float(weight)
                return existing
            rep = Replica(target, metrics_target, weight)
            self._replicas[target] = rep
            REPLICA_HEALTHY.labels(replica=target).set(1.0)
            slog.info("router.replica_added", replica=target,
                      metrics_target=metrics_target)
            return rep

    def remove(self, target: str) -> None:
        """Take a replica out of the pool for good: stop placing, drop
        its channel AND its process-global breaker registration — a
        future server on the reused address must start with a closed
        breaker, not the dead incumbent's open one."""
        with self._lock:
            rep = self._replicas.pop(target, None)
            if rep is None:
                return
            rep.state = REMOVED
            self.transitions_total += 1
            # Unpin every session that pointed here; their next request
            # re-places (their KV state died with the replica anyway).
            for k in [k for k, v in self._sessions.items() if v == target]:
                del self._sessions[k]
            # Retire the series, don't pin it at 0: a replica that left
            # the pool for good has no health to report, and membership
            # churn must not grow the label set unboundedly.
            _retire_replica_series(target)
        rep.close_channel()
        CircuitBreaker.evict(target)
        # A pool-spawned child is OWNED by the pool: removal must not
        # leave the live engine serving on its ports forever — and once
        # the entry is popped, close()'s sweep can no longer reach it.
        if rep.proc is not None:
            _terminate_child(rep.proc)
        slog.info("router.replica_removed", replica=target)

    def drain(self, target: str, *, signal_process: bool = True) -> bool:
        """Begin the rolling-restart drain of one replica: stop placing
        new requests on it; for a pool-spawned replica also SIGTERM the
        process so its own GracefulDrain finishes in-flight work and
        exits. Returns False for an unknown target. The scrape loop
        re-admits the replica (fresh breaker) once its /healthz reports
        ready again — restart → rejoin needs no second command."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state == REMOVED:
                return False
            if rep.state == QUARANTINED:
                # Quarantine dominates: a drain would re-route the
                # replica onto the ready-scrape auto-rejoin path,
                # bypassing the fingerprint + canary reverify that
                # quarantine exists to enforce.
                return False
            if rep.state != DRAINING:
                self.transitions_total += 1
            rep.state = DRAINING
            REPLICA_HEALTHY.labels(replica=target).set(0.0)
        if signal_process and rep.proc is not None \
                and rep.proc.poll() is None:
            rep.proc.terminate()
        slog.info("router.replica_draining", replica=target,
                  spawned=rep.proc is not None)
        return True

    def undrain(self, target: str) -> bool:
        """Re-admit a drained replica (the restarted server on the
        reused address): evict the old breaker so the first requests
        are not fail-fasted by stale history. No-op (False) unless the
        replica is actually DRAINING — undrain on an ACTIVE replica
        would silently wipe a live breaker's state and load view (a
        hard-down replica the breaker correctly opened on would
        re-enter rotation off a typo'd admin call)."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state != DRAINING:
                return False
            rep.state = ACTIVE
            self.transitions_total += 1
            rep.reported_draining = False
            rep.drain_observed = False
            # An operator undrain cancels an autoscaler scale-down in
            # flight: the replica is back in service, not on its way
            # out (the autoscaler's next tick re-decides from signals).
            rep.decommissioning = False
            # Reused address: the OLD server's failure history must not
            # greet the new one.
            CircuitBreaker.evict(target)
            rep.breaker = CircuitBreaker.for_target(target)
            rep.scraped_at = None  # stale gauges are the old server's
            REPLICA_HEALTHY.labels(replica=target).set(1.0)
        slog.info("router.replica_undrained", replica=target)
        return True

    # ------------------------------------------------------ quarantine

    def quarantine(self, target: str, *, reason: str,
                   evidence: dict | None = None) -> bool:
        """Move a replica to QUARANTINED on an integrity verdict: stop
        placement, sever its channel so in-flight forwards fail over
        NOW (its in-flight answers are as suspect as its future ones),
        unpin its sessions, fire the incident hook with the evidence,
        and — for a pool-spawned child — SIGTERM it so the supervisor
        respawns a fresh process for reverify-readmission.

        Deliberately NOT the drain path: a drained replica auto-rejoins
        on the next ready scrape, and a breaker-opened one half-open
        probes back in. A wrong replica answers ready and serves probes
        perfectly — it re-enters only through :meth:`unquarantine`'s
        fingerprint + canary checks. Returns False for unknown/removed
        targets and no-ops (False) when already quarantined."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state in (REMOVED, QUARANTINED):
                return False
            rep.state = QUARANTINED
            self.transitions_total += 1
            rep.quarantine_reason = reason
            rep.quarantine_evidence = dict(evidence or {})
            rep.quarantine_boot_id = rep.boot_id
            rep.quarantined_at = time.monotonic()
            REPLICA_HEALTHY.labels(replica=target).set(0.0)
            REPLICA_QUARANTINED.labels(replica=target).set(1.0)
            QUARANTINES.labels(reason=reason).inc()
            # Unpin every session here: their next request re-places
            # (affinity to a corrupt replica is affinity to wrong
            # answers, and its KV state cannot be trusted either).
            for k in [k for k, v in self._sessions.items() if v == target]:
                del self._sessions[k]
        # Outside the lock: sever the channel so the router's in-flight
        # forwards fail immediately and ride the normal failover loop
        # to a healthy replica (clean in-flight failover, no waiting
        # for suspect answers to finish).
        rep.close_channel()
        hook = self.on_quarantine
        if hook is not None:
            try:
                hook(target, reason, dict(evidence or {}))
            except Exception:  # noqa: BLE001 — evidence capture is best-effort
                log.exception("on_quarantine hook failed for %s", target)
        if rep.proc is not None and rep.proc.poll() is None:
            # Respawn-with-reverify for spawned replicas: the exit
            # routes through _maybe_respawn (which preserves the
            # QUARANTINED state), and the fresh process re-admits only
            # via unquarantine's checks.
            rep.proc.terminate()
        slog.warning("router.replica_quarantined", replica=target,
                     reason=reason,
                     spawned=rep.proc is not None)
        return True

    def unquarantine(self, target: str, *, force: bool = False) -> dict:
        """Re-admission with reverify: the replica re-enters rotation
        only if its /healthz weights fingerprint agrees with the
        fleet's AND a fresh canary probe answers on-golden (each check
        skipped when unconfigured; ``force=True`` skips both — the
        operator's break-glass). Returns a structured result with the
        individual check outcomes; ``{"ok": True}`` means re-admitted."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state != QUARANTINED:
                return {"ok": False, "error": "not quarantined",
                        "target": target}
            golden = self.fleet_fingerprint
        checks: dict = {}
        if not force:
            if golden is not None and rep.fingerprint is not None \
                    and rep.fingerprint != golden:
                checks["fingerprint"] = {
                    "ok": False, "fingerprint": rep.fingerprint,
                    "fleet": golden,
                }
                return {"ok": False, "target": target, "checks": checks}
            if golden is not None and rep.fingerprint is not None:
                checks["fingerprint"] = {"ok": True}
            if self.canary is not None:
                verdict, ev = self.canary.probe(rep)
                checks["canary"] = {"ok": bool(verdict), **(
                    {} if verdict else {"evidence": ev}
                )}
                if not verdict:
                    # None (unreachable) also refuses: re-admitting a
                    # replica the prober cannot even reach proves
                    # nothing about its answers.
                    return {"ok": False, "target": target,
                            "checks": checks}
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state != QUARANTINED:
                return {"ok": False, "error": "not quarantined",
                        "target": target}
            rep.state = ACTIVE
            self.transitions_total += 1
            rep.quarantine_reason = None
            rep.quarantine_evidence = None
            rep.quarantine_boot_id = None
            rep.quarantined_at = None
            rep.integrity_strikes = 0
            rep.reported_draining = False
            rep.drain_observed = False
            # The quarantined incumbent's failure history must not
            # greet the re-verified (usually respawned) server.
            CircuitBreaker.evict(target)
            rep.breaker = CircuitBreaker.for_target(target)
            rep.scraped_at = None
            REPLICA_HEALTHY.labels(replica=target).set(1.0)
            REPLICA_QUARANTINED.labels(replica=target).set(0.0)
        slog.info("router.replica_unquarantined", replica=target,
                  forced=force, checks=list(checks) or None)
        return {"ok": True, "target": target, "checks": checks,
                "forced": force}

    def note_integrity_error(self, target: str) -> None:
        """Record one INTEGRITY (DATA_LOSS) reply the router observed
        from a replica — the numeric-guard verdict path. At
        ``guard_quarantine_threshold`` strikes the replica is
        quarantined (a healthy replica's guard essentially never
        fires; repeated firing means corrupt weights or a bad core)."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state != ACTIVE:
                return
            rep.integrity_strikes += 1
            strikes = rep.integrity_strikes
        if strikes >= self.guard_quarantine_threshold:
            self.quarantine(
                target, reason="guard",
                evidence={"integrity_errors": strikes,
                          "threshold": self.guard_quarantine_threshold},
            )

    def decommission(self, target: str) -> bool:
        """Begin a SCALE-DOWN drain (serving/autoscale.py): like
        :meth:`drain`, but toward permanent removal — the supervisor
        will not respawn a pool-spawned child's exit, and the ready
        scrape will not re-admit the replica. The caller removes it
        once :meth:`drained_for_removal` says the drain was observed
        (zero dropped in-flight requests is the whole point of going
        through the choreography instead of calling remove() cold)."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state == REMOVED:
                return False
            rep.decommissioning = True
        return self.drain(target)

    def drained_for_removal(self, target: str) -> bool:
        """True once a decommissioning replica can be removed with
        nothing in flight: the router holds zero outstanding forwards
        on it and — for a pool-spawned child — the process has exited
        (its own GracefulDrain finished). Unknown target = already
        gone = removable."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.state == REMOVED:
                return True
            if rep.outstanding > 0:
                return False
            return rep.proc is None or rep.proc.poll() is not None

    def wait_drained(self, target: str, timeout: float = 30.0) -> bool:
        """Block until the router has zero outstanding requests on a
        draining replica (the point it is safe to restart)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                rep = self._replicas.get(target)
                if rep is None or rep.outstanding == 0:
                    return True
            time.sleep(0.005)
        return False

    def replicas(self) -> list[Replica]:
        with self._lock:
            return list(self._replicas.values())

    def targets(self) -> list[str]:
        with self._lock:
            return list(self._replicas)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return [r.snapshot() for r in self._replicas.values()]

    # ------------------------------------------------------- placement

    def begin(self, rep: Replica) -> None:
        with self._lock:
            rep.outstanding += 1

    def done(self, rep: Replica) -> None:
        with self._lock:
            rep.outstanding = max(0, rep.outstanding - 1)

    def pin(self, session_key: str, target: str) -> None:
        with self._lock:
            self._sessions[session_key] = target
            self._sessions.move_to_end(session_key)
            while len(self._sessions) > self._session_capacity:
                self._sessions.popitem(last=False)

    def pinned(self, session_key: str) -> str | None:
        with self._lock:
            return self._sessions.get(session_key)

    @staticmethod
    def _rendezvous(session_key: str, cands: list[Replica]) -> Replica:
        """Highest-random-weight hash: stable per (session, target), so
        membership changes only move the sessions that must move."""
        return max(
            cands,
            key=lambda r: hashlib.sha1(
                f"{session_key}|{r.target}".encode()
            ).digest(),
        )

    def place(self, session_key: str | None = None,
              exclude=frozenset()) -> Replica | None:
        """Pick the replica for one request (None = nothing placeable).

        Order of precedence: a still-placeable session pin; a replica
        whose open breaker is due its half-open probe (exactly one
        request per cooldown rides this); p2c over the blended load
        score; rendezvous hashing for a session's first placement on a
        pool with no load data at all.
        """
        with self._lock:
            now = time.monotonic()
            cands = [
                r for r in self._replicas.values()
                if r.state == ACTIVE and r.target not in exclude
            ]
            if not cands:
                return None
            if session_key is not None:
                t = self._sessions.get(session_key)
                if t is not None:
                    rep = self._replicas.get(t)
                    if (rep is not None and rep.state == ACTIVE
                            and t not in exclude
                            and rep.breaker.state == CircuitBreaker.CLOSED):
                        self._sessions.move_to_end(session_key)
                        return rep
            closed = [
                r for r in cands
                if r.breaker.state == CircuitBreaker.CLOSED
            ]
            if len(closed) < len(cands):
                # A non-closed breaker that allows a call right now is
                # the due half-open probe — route THIS request to it
                # (its outcome closes or re-opens the breaker).
                for r in cands:
                    if (r.breaker.state != CircuitBreaker.CLOSED
                            and r.breaker.allow()):
                        return r
            if not closed:
                return None
            if len(closed) == 1:
                return closed[0]
            if session_key is not None and not any(
                r.fresh(now, self.load_staleness) for r in closed
            ) and all(r.outstanding == 0 for r in closed):
                # Cold pool, no load signal of any kind: spread session
                # first-placements consistently instead of randomly.
                return self._rendezvous(session_key, closed)
            a, b = self._rng.sample(closed, 2)
            sa = a.load_score(now, self.load_staleness,
                              self.occupancy_weight)
            sb = b.load_score(now, self.load_staleness,
                              self.occupancy_weight)
            return a if sa <= sb else b

    # --------------------------------------------------------- scrape

    def _scrape_one(self, rep: Replica) -> None:
        """Refresh one replica's gauge load + health view (no pool lock
        held during HTTP). Failures leave the last view to age out
        through the staleness bound."""
        from tpu_dist_nn.obs.exposition import parse_prometheus_text

        base = rep.metrics_target
        if base is None:
            return
        if "://" not in base:
            base = f"http://{base}"
        base = base.rstrip("/")
        pending = occupancy = warm = None
        metrics_ok = False
        try:
            with urllib.request.urlopen(
                base + "/metrics", timeout=self.scrape_timeout
            ) as resp:
                parsed = parse_prometheus_text(resp.read().decode())
            pending = _sum_series(parsed, "tdn_batcher_pending_rows")
            occupancy = _sum_series(parsed, "tdn_gen_slot_occupancy_ratio")
            warm = _sum_series(parsed, "tdn_engine_warm_buckets")
            metrics_ok = True
        except (urllib.error.URLError, OSError, ValueError):
            # Stale view ages out; the breaker covers hard-down. NOT a
            # drain-observation signal by itself: one blown fetch (GC
            # pause, garbled body) on an admin-drained STATIC replica
            # must not read as "the process exited" — the very next
            # ready scrape would then auto-undrain the replica the
            # operator just drained. /healthz below is the arbiter.
            pass
        draining = None
        ready = None
        boot_id = None
        fingerprint = None
        reachable = False
        try:
            req = urllib.request.urlopen(
                base + "/healthz", timeout=self.scrape_timeout
            )
            with req as resp:
                body = resp.read()
            reachable = True
            try:
                # json.loads takes the raw bytes: a non-UTF-8 body
                # raises UnicodeDecodeError, a ValueError subclass —
                # decoding OUTSIDE this try let a binary proxy error
                # page crash the whole scrape tick.
                health = json.loads(body)
                ready = bool(health.get("ready"))
                draining = bool(health.get("draining"))
                boot_id = health.get("boot_id")
                fingerprint = health.get("fingerprint")
            except (ValueError, AttributeError):
                # 200 with a garbled or non-dict body (proxy error
                # page, misconfigured port): something answered, so
                # this is neither a drain observation nor a rejoin
                # signal — health stays unknown for this tick.
                pass
        except urllib.error.HTTPError as e:
            # 503 carries the health JSON (not-ready / draining).
            reachable = True
            try:
                health = json.loads(e.read().decode())
                ready = bool(health.get("ready"))
                draining = bool(health.get("draining"))
                boot_id = health.get("boot_id")
                fingerprint = health.get("fingerprint")
            except (ValueError, AttributeError, OSError):
                pass
        except (urllib.error.URLError, OSError):
            pass
        with self._lock:
            if rep.state == REMOVED:
                return
            if metrics_ok:
                rep.pending_rows = pending
                rep.occupancy = occupancy
                if warm is not None:
                    # Capacity signal for weighted p2c: sticky (not
                    # aged by staleness) — a ladder already compiled
                    # does not un-compile when a scrape is missed.
                    rep.warm_buckets = warm
                rep.scraped_at = time.monotonic()
            if not reachable:
                # The health endpoint itself is gone: for a DRAINING
                # replica that IS the drain being observed (the old
                # process exited mid-rolling-restart) — record it so
                # the restarted server's ready scrape rejoins. Gated on
                # TWO consecutive lost ticks: a single blown probe on a
                # still-running admin-drained replica must not read as
                # "the process exited", or the next ready scrape would
                # undo the operator's --drain-replica. (A real restart
                # is observed via draining:true first anyway; this path
                # only covers an exit that fell between ticks.)
                rep.unreachable_ticks += 1
                if rep.state == DRAINING and rep.unreachable_ticks >= 2:
                    rep.drain_observed = True
                return
            rep.unreachable_ticks = 0
            if boot_id is not None:
                if (rep.state == DRAINING and rep.boot_id is not None
                        and boot_id != rep.boot_id):
                    # A DIFFERENT process answers on the address: the
                    # restart fell entirely between two ticks (downtime
                    # AND draining window each shorter than one scrape
                    # interval), so neither timing detector could see
                    # it — but the identity change IS the drain having
                    # completed.
                    rep.drain_observed = True
                rep.boot_id = boot_id
            if draining is not None:
                rep.reported_draining = draining
            if draining:
                rep.drain_observed = True
            if draining and rep.state == ACTIVE:
                # The replica began its own drain (operator SIGTERM):
                # stop placing — the other half of the choreography.
                rep.state = DRAINING
                self.transitions_total += 1
                REPLICA_HEALTHY.labels(replica=rep.target).set(0.0)
                slog.info("router.replica_draining", replica=rep.target,
                          source="healthz")
            fingerprint_mismatch = None
            if fingerprint is not None:
                rep.fingerprint = str(fingerprint)
                if self.fingerprint_gate:
                    if self.fleet_fingerprint is None and ready \
                            and rep.state == ACTIVE:
                        # First ACTIVE ready replica to report one
                        # establishes the fleet golden fingerprint.
                        self.fleet_fingerprint = rep.fingerprint
                        slog.info("integrity.fleet_fingerprint",
                                  source=rep.target,
                                  fingerprint=rep.fingerprint[:12])
                    elif (self.fleet_fingerprint is not None
                          and rep.fingerprint != self.fleet_fingerprint
                          and rep.state == ACTIVE):
                        fingerprint_mismatch = {
                            "fingerprint": rep.fingerprint,
                            "fleet_fingerprint": self.fleet_fingerprint,
                        }
        if fingerprint_mismatch is not None:
            # Outside the pool lock (quarantine takes it): the replica
            # loaded weights the rest of the fleet disagrees with —
            # refuse to keep serving from it.
            self.quarantine(rep.target, reason="fingerprint",
                            evidence=fingerprint_mismatch)
            return
        if ready and not draining and rep.state == QUARANTINED:
            # Reverify-readmission for a RESPAWNED quarantined replica:
            # a different boot_id proves the indicted process is gone
            # and a fresh one answers — run the fingerprint + canary
            # checks and re-admit only on a clean pass. The SAME
            # process incarnation never auto-readmits (its weights are
            # the ones that answered wrong); that path is the
            # operator's explicit unquarantine.
            if boot_id is not None and rep.quarantine_boot_id is not None \
                    and boot_id != rep.quarantine_boot_id:
                self.unquarantine(rep.target)
            return
        if rep.state == ACTIVE and ready and self.canary is not None:
            # Canary probing rides the scrape: at most one probe per
            # replica per canary interval, off the request path (this
            # runs on the scrape fan-out pool). A False verdict is a
            # corruption conviction; None (transport) is the breaker's
            # territory.
            now = time.monotonic()
            if now - rep.canary_at >= self.canary.interval:
                rep.canary_at = now
                verdict, evidence = self.canary.probe(rep)
                if verdict is False:
                    self.quarantine(rep.target, reason="canary",
                                    evidence=evidence)
                    return
        if ready and not draining and rep.state == DRAINING \
                and rep.drain_observed and not rep.decommissioning:
            # (decommissioning replicas never auto-rejoin: the drain is
            # toward removal, and re-admitting one that still answers
            # ready — a static replica being scaled down — would undo
            # the autoscaler's decision one scrape tick later.)
            # The restarted server answers ready on the reused address:
            # rejoin with a fresh breaker. Gated on the drain having
            # been OBSERVED (draining:true scraped, the replica
            # unreachable 2+ ticks while draining, or its boot_id
            # changed) — a still-ready replica that never began
            # restarting stays out of rotation, so an admin
            # `--drain-replica` on a static fleet is not undone by the
            # very next scrape.
            self.undrain(rep.target)

    def _maybe_respawn(self, rep: Replica) -> None:
        """Complete the drain choreography for a POOL-SPAWNED replica
        whose process has exited: respawn it on the same address so the
        next ready scrape rejoins it. Without this, an admin
        ``--drain-replica`` on a spawned fleet would SIGTERM the child
        and leave the fleet at N-1 forever — the drain is only half of
        the rolling restart the flag promises."""
        with self._lock:
            if (rep.state == REMOVED or rep.spawn_argv is None
                    or rep.respawning or rep.decommissioning
                    or time.monotonic() < rep.respawn_backoff_until
                    or rep.proc is None or rep.proc.poll() is None):
                # decommissioning: the exit IS the scale-down drain
                # completing — respawning it would undo the autoscaler
                # (and re-burn an engine compile for a replica that is
                # being removed on purpose).
                return
            if rep.state == DRAINING:
                # The exit IS the drain completing (GracefulDrain ran).
                rep.drain_observed = True
            elif rep.state == QUARANTINED:
                # Quarantine terminated the child on purpose: respawn a
                # fresh process but KEEP the quarantined state — the
                # new boot re-admits only through unquarantine's
                # fingerprint + canary reverify (the scrape's
                # boot_id-change path), never the drain auto-rejoin.
                pass
            else:
                # The child exited OUTSIDE any drain (crash, or an
                # undrain racing a child the drain already SIGTERMed):
                # --spawn promises a supervised fleet, not N-1 forever.
                # Route it through the same drain-rejoin choreography —
                # stop placing now, respawn, let the ready scrape
                # re-admit it with a fresh breaker.
                rep.state = DRAINING
                self.transitions_total += 1
                rep.drain_observed = True
                REPLICA_HEALTHY.labels(replica=rep.target).set(0.0)
                slog.warning("router.replica_exited_unexpectedly",
                             replica=rep.target,
                             returncode=rep.proc.poll())
            rep.respawning = True
            rep.respawn_backoff_until = time.monotonic() + 5.0
            argv = list(rep.spawn_argv)
        # The boot can take minutes (engine compile/warmup); it must
        # not run on the scrape thread, or health/load scraping — and
        # drain observation — for every OTHER replica freezes until
        # this one is up. `respawning` keeps the next ticks out.
        threading.Thread(
            target=self._respawn, args=(rep, argv),
            name=f"tdn-respawn-{rep.target}", daemon=True,
        ).start()

    def _respawn(self, rep: Replica, argv: list[str]) -> None:
        # Let forwards that raced the exit finish on the old channel
        # first: close_channel() turns in-flight RPCs into CANCELLED,
        # which the router classifies non-transient and propagates to
        # a client that never cancelled anything — the exact loss the
        # failover machinery exists to absorb (they fail UNAVAILABLE
        # on their own against the dead process, which DOES fail
        # over). Bounded wait: the process is gone, they fail fast.
        self.wait_drained(rep.target, 5.0)
        rep.close_channel()
        try:
            if self._stop.is_set() or rep.state == REMOVED:
                # The pool began shutting down — or remove() took this
                # replica out — while this thread was in its pre-spawn
                # window: a child spawned NOW would be born after
                # cleanup already terminated rep.proc (the OLD exited
                # process) and be orphaned on the reused ports.
                return
            proc = subprocess.Popen(
                argv, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True,
            )
            # Visible on rep BEFORE the (possibly minutes-long) port
            # wait: router shutdown mid-boot must find and terminate
            # this child, not orphan it holding the reused ports.
            # Re-check shutdown/removal under the lock: close() or
            # remove() may have run entirely between the pre-spawn
            # check and this assignment, in which case their proc
            # sweep saw the OLD exited process and nothing else will
            # ever terminate this child.
            with self._lock:
                if self._stop.is_set() or rep.state == REMOVED:
                    stillborn = proc
                else:
                    rep.proc = proc
                    stillborn = None
            if stillborn is not None:
                _terminate_child(stillborn)
                return
            _read_child_ports(proc, 180.0)
            slog.info("router.replica_respawned", replica=rep.target)
        except (OSError, RuntimeError):
            log.exception("respawn of drained replica %s failed",
                          rep.target)
        finally:
            with self._lock:
                rep.respawning = False

    def scrape_once(self) -> None:
        reps = [r for r in self.replicas() if r.state != REMOVED]
        for rep in reps:
            self._maybe_respawn(rep)
        # Fan the HTTP out: each unreachable replica blocks for up to
        # 2x scrape_timeout, and scraping serially would let a few
        # wedged hosts age EVERY healthy replica's gauges past the
        # staleness bound (p2c degrades fleet-wide) and delay drain
        # observation. One tick costs max(replica), not sum(replica).
        futs = []
        if len(reps) > 1:
            if self._scrape_pool is None:
                self._scrape_pool = (
                    concurrent.futures.ThreadPoolExecutor(
                        max_workers=16, thread_name_prefix="tdn-scrape"
                    )
                )
            futs = [self._scrape_pool.submit(self._scrape_one, rep)
                    for rep in reps[1:]]
        if reps:
            self._scrape_one(reps[0])
        for f in futs:
            f.result()
        # Reconcile the availability gauge with the breaker: membership
        # changes set it eagerly, but a breaker opening/closing happens
        # at request time in the router — without this tick a hard-down
        # replica the breaker already un-placed would keep reporting
        # healthy=1. Under the pool lock so a concurrent remove() (which
        # retires the series) cannot be resurrected by this write.
        with self._lock:
            for rep in reps:
                if rep.state != REMOVED:
                    REPLICA_HEALTHY.labels(replica=rep.target).set(
                        1.0 if (rep.state == ACTIVE
                                and rep.breaker.state
                                == CircuitBreaker.CLOSED)
                        else 0.0
                    )

    def start(self) -> "ReplicaPool":
        if self._thread is not None:
            return self
        self.scrape_once()
        self._thread = threading.Thread(
            target=self._run, name="tdn-router-scrape", daemon=True
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.scrape_interval):
            try:
                self.scrape_once()
            except Exception:  # noqa: BLE001 — scraping must never kill routing
                log.exception("replica scrape failed")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def close(self, *, grace: float = 10.0) -> None:
        self.stop()
        if self._scrape_pool is not None:
            self._scrape_pool.shutdown(wait=False)
            self._scrape_pool = None
        reps = self.replicas()
        for rep in reps:
            rep.close_channel()
            # Release the per-target PROCESS-GLOBAL state the pool
            # claimed: the breaker registry entry (+ its
            # tdn_breaker_state series) and the healthy series. A
            # long-lived process cycling pools over ephemeral-port
            # replicas (scenarios, tests) must not accumulate dead series
            # forever, and a later pool reusing an address must not
            # inherit this one's breaker history.
            _retire_replica_series(rep.target)
            CircuitBreaker.evict(rep.target)
        # Pool-spawned children are OWNED by the pool: a library caller
        # closing it must not orphan live engines holding their ports.
        # SIGTERM runs each child's own GracefulDrain; ``grace`` bounds
        # the wait before the hard kill (the CLI passes its
        # --drain-grace-seconds budget through). Defensive per-proc:
        # tests park duck-typed fakes on rep.proc.
        procs = [r.proc for r in reps if r.proc is not None]
        for p in procs:
            try:
                if p.poll() is None:
                    p.terminate()
            except Exception:  # noqa: BLE001 — best-effort teardown
                continue
        for p in procs:
            try:
                p.wait(timeout=grace)
            except Exception:  # noqa: BLE001 — last resort
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass

    # ----------------------------------------------------- local spawn

    def spawn_local(self, config: str, *, grpc_port: int = 0,
                    metrics_port: int = 0, extra_args=(),
                    startup_timeout: float = 180.0,
                    platform: str = "auto") -> Replica:
        """Spawn one local engine replica (``tdn up --grpc-port``) as a
        subprocess and add it to the pool. Ports default to ephemeral;
        the child prints its bound ports as JSON lines (the CLI's
        port-in-stdout convention) and this blocks until both appear.

        ``platform`` is the child's ``tdn --platform``. A child that
        reports an accelerator must be the only one this pool runs: a
        chip belongs to one process at a time and nothing here assigns
        chips to children, so a second child would open the chips the
        first one owns. That spawn is refused, loudly.
        """
        if self._stop.is_set():
            raise RuntimeError("pool is closed; refusing to spawn a replica")
        head = [
            sys.executable, "-m", "tpu_dist_nn.cli", "--platform", platform,
            "up", "--config", config,
        ]
        with self._lock:
            self._booting += 1
        try:
            proc = subprocess.Popen(
                [*head, "--grpc-port", str(grpc_port),
                 "--metrics-port", str(metrics_port), *extra_args],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True,
            )
            try:
                info = _read_child_ports(proc, startup_timeout)
            except RuntimeError as e:
                # The usual way a second accelerator child ends: the
                # chips were taken and it died before reporting.
                if platform != "cpu" and self._spawned_siblings():
                    raise RuntimeError(f"{e} — {_UNASSIGNED_CHIPS}") from e
                raise
            siblings = self._spawned_siblings()
            if info["platform"] != "cpu" and siblings:
                _terminate_child(proc)
                raise RuntimeError(
                    f"spawned replica runs on {info['platform']!r} next "
                    f"to {siblings} other spawned replica(s): "
                    f"{_UNASSIGNED_CHIPS}"
                )
        finally:
            with self._lock:
                self._booting -= 1
        target = f"127.0.0.1:{info['grpc_port']}"
        rep = self.add(target, f"127.0.0.1:{info['metrics_port']}")
        with self._lock:
            rep.proc = proc
            # Remember the exact argv WITH the now-known ports so a
            # rolling restart respawns on the same (reused) addresses.
            rep.spawn_argv = [
                *head, "--grpc-port", str(info["grpc_port"]),
                "--metrics-port", str(info["metrics_port"]), *extra_args,
            ]
            closing = self._stop.is_set()
        if closing:
            # close() swept the pool while this child was booting (the
            # proc landed on rep only now, and the membership entry
            # after the sweep's snapshot): tear both down ourselves —
            # same bug class _respawn/restart_replica guard against.
            self.remove(target)
            raise RuntimeError("pool closed during spawn_local")
        return rep

    def _spawned_siblings(self) -> int:
        """Other replicas this pool spawned, booting or live, as seen
        from inside one ``spawn_local`` call."""
        with self._lock:
            return self._booting - 1 + sum(
                r.proc is not None for r in self._replicas.values()
            )

    def restart_replica(self, target: str, *, grace: float = 30.0,
                        startup_timeout: float = 180.0) -> bool:
        """The full zero-downtime rolling-restart of one POOL-SPAWNED
        replica: drain (SIGTERM → its GracefulDrain) → wait for the
        router's outstanding work AND the process to finish → respawn
        on the same address → rejoin with a fresh breaker."""
        with self._lock:
            rep = self._replicas.get(target)
            if rep is None or rep.spawn_argv is None or rep.respawning:
                return False
            # Claim the respawn so the scrape loop's auto-respawn does
            # not race this explicit restart into a double spawn.
            rep.respawning = True
        try:
            self.drain(target)
            self.wait_drained(target, grace)
            if rep.proc is not None:
                try:
                    rep.proc.wait(timeout=grace)
                except subprocess.TimeoutExpired:
                    rep.proc.kill()
                    rep.proc.wait(timeout=5.0)
            rep.close_channel()
            proc = subprocess.Popen(
                rep.spawn_argv, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True,
            )
            # Same rule as _respawn: the child rides rep.proc through
            # the (possibly minutes-long) port wait so shutdown cleanup
            # terminates it instead of orphaning it on the reused
            # ports — and a concurrent close()/remove() that already
            # swept the OLD proc means this child is ours to kill.
            with self._lock:
                if self._stop.is_set() or rep.state == REMOVED:
                    stillborn = proc
                else:
                    rep.proc = proc
                    stillborn = None
            if stillborn is not None:
                _terminate_child(stillborn)
                return False
            _read_child_ports(proc, startup_timeout)
        finally:
            with self._lock:
                rep.respawning = False
        if self.undrain(target):
            return True
        # The scrape loop's auto-rejoin may have undrained the
        # restarted server before we got here (undrain refuses
        # non-DRAINING replicas, so ours returns False) — a replica
        # that ended up ACTIVE is a SUCCESSFUL restart either way.
        with self._lock:
            rep2 = self._replicas.get(target)
            return rep2 is not None and rep2.state == ACTIVE


def _retire_replica_series(target: str) -> None:
    """Retire every per-replica metric series a departed target owned:
    the healthy gauge plus the router's request counters (looked up by
    name — the router module imports this one, not vice versa). The
    sampler's outstanding/pending gauges retire via its own churn
    handling."""
    REPLICA_HEALTHY.remove(replica=target)
    REPLICA_QUARANTINED.remove(replica=target)
    requests = REGISTRY.get("tdn_router_requests_total")
    if requests is not None:
        requests.remove_matching(replica=target)


_UNASSIGNED_CHIPS = (
    "a chip belongs to one process at a time and spawned replicas are "
    "not assigned chips, so each would open every chip of the host. "
    "Spawn with --platform cpu, or start one pinned replica per chip "
    "yourself and list them with --replicas"
)


def _terminate_child(proc) -> None:
    """Best-effort SIGTERM (the child's own GracefulDrain) → bounded
    wait → SIGKILL. Duck-typed: tests park fakes on ``rep.proc``."""
    try:
        if proc.poll() is None:
            proc.terminate()
        proc.wait(timeout=10.0)
    except Exception:  # noqa: BLE001 — best-effort teardown
        try:
            proc.kill()
        except Exception:  # noqa: BLE001
            pass


def _read_child_ports(proc: subprocess.Popen,
                      timeout: float) -> dict:
    """Read a spawned replica's JSON stdout lines until both its
    metrics and gRPC ports are known (a reader thread bounds the wait —
    a wedged child must raise, not hang the router bring-up). Returns
    ``{"metrics_port", "grpc_port", "platform"}`` — the platform from
    the child's ``ready`` line, which precedes its port lines."""
    info: dict = {}
    done = threading.Event()
    err: list[str] = []

    def reader():
        try:
            for line in proc.stdout:  # type: ignore[union-attr]
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if "device" in doc:
                    info["platform"] = doc["device"]["platform"]
                for key in ("metrics_port", "grpc_port"):
                    if key in doc:
                        info[key] = int(doc[key])
                if "metrics_port" in info and "grpc_port" in info:
                    done.set()
                    return
            err.append("child exited before printing its ports")
        except Exception as e:  # noqa: BLE001 — surfaced to the waiter
            err.append(repr(e))
        finally:
            done.set()

    threading.Thread(target=reader, daemon=True).start()
    if not done.wait(timeout) or "grpc_port" not in info:
        _terminate_child(proc)
        raise RuntimeError(
            "spawned replica did not report its ports within "
            f"{timeout}s" + (f": {err[0]}" if err else "")
        )
    return info
