"""Closed-loop fleet autopilot: burn-rate-driven autoscaling.

PR 9 built the sensor plane (``tdn_slo_burn_rate``, slot-occupancy and
pending-rows gauges scraped into the router's pool view) and PR 8
built the actuator plane (``ReplicaPool.spawn_local`` / drain /
``remove`` with the full drain-rejoin choreography). This module is
the controller between them: an :class:`Autoscaler` that runs on the
router's EXISTING runtime-sampler tick
(:meth:`~tpu_dist_nn.obs.runtime.RuntimeSampler.add_autoscaler`),
reads the fleet state the pool has already scraped, and grows or
shrinks the fleet exclusively through the existing choreography — so
every replica the autoscaler touches gets the same zero-downtime
guarantees an operator's ``--drain-replica`` does.

**Signals** (all host-side reads, never a request-path cost):

* SLO burn rate — the fast-window verdict the attached
  :class:`~tpu_dist_nn.obs.slo.SLOTracker` computed earlier in the
  same sampler tick (the tracker evaluates before autoscalers tick).
  Fast burn > 1 means the fleet is on track to blow its error budget:
  the page condition, and here the scale-up condition.
* Fleet utilization — per active replica, the scraped continuous-
  decode slot occupancy plus the row backlog (scraped pending rows +
  the router's own live outstanding count) normalized by
  ``rows_capacity`` and the replica's capacity weight; averaged over
  the fleet. Above the hysteresis ceiling = saturated, below the
  floor = over-provisioned.

**Decisions** are deliberately slower than the signals:

* Hysteresis — the target occupancy is a BAND
  (``target * (1 ± hysteresis)``); inside it the fleet is left alone.
* Consecutive-tick stability — a breach must persist for
  ``up_stable_ticks`` / ``down_stable_ticks`` sampler ticks before it
  becomes a decision (one slow scrape is noise, not load).
* Cooldowns — at most one scale-up per ``up_cooldown`` seconds and
  one scale-down per ``down_cooldown`` (down is slower: adding
  capacity under load is urgent, removing it never is).
* Flap suppression — a direction reversal (up then down, or down then
  up) within ``flap_window`` seconds is a flap; at
  ``flap_reversals`` reversals the autoscaler SUPPRESSES itself for
  ``flap_cooldown``, bumps ``tdn_autoscale_flaps_total`` (the
  ``autoscale.flap`` incident detector rides the delta), and emits a
  structured warning. A crash-respawn storm cannot double-trigger
  either way: a replica mid-respawn still counts toward the fleet
  size (see :meth:`Autoscaler.current_size`), so a crash does not
  read as "fleet shrank, spawn another".

**Actuation**:

* Scale-up calls the injected ``spawner`` (the CLI wires
  ``pool.spawn_local``) on its own thread — an engine boot takes
  minutes and must never block the sampler tick; the in-flight spawn
  counts toward the fleet size so the next ticks do not double-spawn.
* Scale-down picks the least-loaded active replica and runs
  :meth:`~tpu_dist_nn.serving.pool.ReplicaPool.decommission` — the
  observed-drain choreography (stop placing → SIGTERM a spawned
  child → its GracefulDrain finishes in-flight work → exit) — and
  only calls ``remove`` once the router holds zero outstanding
  forwards on it, so a scale-down NEVER drops an in-flight request.

**Manual override**: ``POST /router/scale?replicas=N`` on the
router's admin surface parks the fleet at N (still clamped to
min/max, still through the same choreography, cooldowns and flap
suppression bypassed — the operator said so); ``?mode=auto`` hands
control back to the policy.

Everything is stdlib + in-repo modules; docs/SCALING.md "Autopilot"
is the operator guide.
"""

from __future__ import annotations

import collections
import logging
import threading
import time

from tpu_dist_nn.obs.log import get_logger
from tpu_dist_nn.obs.registry import REGISTRY
from tpu_dist_nn.serving.pool import ACTIVE, DRAINING, ReplicaPool

log = logging.getLogger(__name__)
slog = get_logger(__name__)
# A scale-up with no actuator (static fleet, nothing parked) can
# recur every sampler tick for as long as the overload lasts — news
# the first couple of times, log spam per-tick. Tight bucket, the
# slo.burn pattern.
_noact_log = get_logger(__name__ + ".no_actuator", rate=1.0 / 60.0,
                        burst=2)

AUTOSCALE_DESIRED = REGISTRY.gauge(
    "tdn_autoscale_desired_replicas",
    "fleet size the autoscaler is converging to (min/max-clamped; "
    "equals the current size while no decision is pending)",
)
AUTOSCALE_UTIL = REGISTRY.gauge(
    "tdn_autoscale_fleet_utilization",
    "blended fleet utilization the policy compares to its target "
    "band: mean over active replicas of slot occupancy + row backlog "
    "/ rows_capacity (1.0 ~ every replica exactly saturated)",
)
AUTOSCALE_DECISIONS = REGISTRY.counter(
    "tdn_autoscale_decisions_total",
    "scale decisions actually actuated, per direction",
    labels=("action",),
)
AUTOSCALE_FLAPS = REGISTRY.counter(
    "tdn_autoscale_flaps_total",
    "flap suppressions: scale decisions reversed direction within the "
    "flap window often enough that the autoscaler muted itself (the "
    "autoscale.flap incident detector fires on this delta)",
)
AUTOSCALE_SUPPRESSED = REGISTRY.gauge(
    "tdn_autoscale_flap_suppressed",
    "1 while flap suppression is muting automatic scale decisions",
)


class Autoscaler:
    """The policy engine. Construct it next to the router's pool and
    register with :meth:`RuntimeSampler.add_autoscaler`; every sampler
    tick calls :meth:`tick` once. Tests drive :meth:`tick` directly
    with an injected ``clock``.

    ``spawner`` is a zero-arg callable that adds one replica to the
    pool and blocks until it serves (the CLI wires
    ``pool.spawn_local(config, ...)``; tests and scenarios inject
    in-process fakes). ``slo`` is the router's
    :class:`~tpu_dist_nn.obs.slo.SLOTracker` (None = utilization-only
    policy).
    """

    def __init__(self, pool: ReplicaPool, *,
                 min_replicas: int = 1, max_replicas: int = 4,
                 spawner=None, slo=None,
                 target_occupancy: float = 0.6,
                 hysteresis: float = 0.25,
                 burn_threshold: float = 1.0,
                 rows_capacity: float = 32.0,
                 up_cooldown: float = 15.0,
                 down_cooldown: float = 60.0,
                 up_stable_ticks: int = 2,
                 down_stable_ticks: int = 5,
                 flap_window: float = 300.0,
                 flap_reversals: int = 2,
                 flap_cooldown: float = 600.0,
                 decommission_grace: float = 30.0,
                 clock=time.monotonic):
        if not 1 <= min_replicas <= max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas, got "
                f"{min_replicas}..{max_replicas}"
            )
        if not 0.0 < target_occupancy <= 1.5:
            raise ValueError(
                f"target_occupancy must be in (0, 1.5], got "
                f"{target_occupancy}"
            )
        if not 0.0 < hysteresis < 1.0:
            raise ValueError(
                f"hysteresis must be in (0, 1), got {hysteresis}"
            )
        self.pool = pool
        self.spawner = spawner
        self.slo = slo
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.target_occupancy = float(target_occupancy)
        self.hysteresis = float(hysteresis)
        self.burn_threshold = float(burn_threshold)
        self.rows_capacity = float(rows_capacity)
        self.up_cooldown = float(up_cooldown)
        self.down_cooldown = float(down_cooldown)
        self.up_stable_ticks = int(up_stable_ticks)
        self.down_stable_ticks = int(down_stable_ticks)
        self.flap_window = float(flap_window)
        self.flap_reversals = int(flap_reversals)
        self.flap_cooldown = float(flap_cooldown)
        self.decommission_grace = float(decommission_grace)
        self._clock = clock
        self._lock = threading.RLock()
        self._above = 0  # guarded-by: _lock
        self._below = 0  # guarded-by: _lock
        # guarded-by: _lock
        self._last_up = self._last_down = None  # type: float | None
        self._history: collections.deque = (  # guarded-by: _lock
            collections.deque(maxlen=32)
        )
        self._suppressed_until = 0.0  # guarded-by: _lock
        self._override: int | None = None  # guarded-by: _lock
        self._spawning = 0  # guarded-by: _lock
        # target -> removal deadline for POOL-SPAWNED replicas we are
        # draining out (the exit frees their resources, so membership
        # removal is the right end state).
        self._decommissions: dict[str, float] = {}  # guarded-by: _lock
        # Replicas the autoscaler PARKED instead of removed: a
        # non-spawned (static / orchestrator-managed) replica's process
        # is not ours to reclaim, and removing its membership would
        # ratchet the fleet down forever (nothing could ever re-add
        # the address). Parked replicas stay in the pool, drained and
        # rejoin-exempt; scale-up un-parks before it spawns.
        self._parked: set[str] = set()  # guarded-by: _lock
        self._last_signals: dict = {}  # guarded-by: _lock
        self.ticks_total = 0

    # --------------------------------------------------------- signals

    def signals(self, now: float | None = None):
        """-> (utilization, fast_burn): the two policy inputs, read
        from state the pool scraper / SLO tracker already computed
        this tick (never an HTTP fetch from here)."""
        mono = time.monotonic()
        utils = []
        for rep in self.pool.replicas():
            if rep.state != ACTIVE or rep.decommissioning:
                continue
            occ = pend = 0.0
            if rep.fresh(mono, self.pool.load_staleness):
                occ = float(rep.occupancy or 0.0)
                pend = float(rep.pending_rows or 0.0)
            rows = (pend + float(rep.outstanding)) / (
                self.rows_capacity * rep.capacity_weight
            )
            utils.append(occ + rows)
        util = sum(utils) / len(utils) if utils else None
        burn = None
        if self.slo is not None:
            doc = self.slo.status()
            for obj in doc.get("objectives", ()):
                fast = (obj.get("windows") or {}).get("fast") or {}
                if fast.get("total", 0.0) > 0:
                    b = float(fast.get("burn_rate", 0.0))
                    burn = b if burn is None else max(burn, b)
        return util, burn

    def current_size(self) -> int:
        """Replicas that are — or are about to be back — in service:
        ACTIVE ones, DRAINING ones that are NOT being decommissioned
        (a crash-respawn or rolling restart returns them on the same
        address; counting them gone would make every crash storm look
        like a shrunken fleet and double-trigger a spawn), plus spawns
        already in flight."""
        n = 0
        for rep in self.pool.replicas():
            if rep.state == ACTIVE and not rep.decommissioning:
                n += 1
            elif rep.state == DRAINING and not rep.decommissioning:
                n += 1
        with self._lock:
            return n + self._spawning

    # -------------------------------------------------------- override

    def set_override(self, n: int) -> int:
        """Park the fleet at ``n`` (clamped to min/max); returns the
        clamped value. The policy stops deciding; convergence still
        runs one step per tick through the same choreography."""
        n = max(self.min_replicas, min(self.max_replicas, int(n)))
        with self._lock:
            self._override = n
        slog.info("autoscale.override", replicas=n)
        return n

    def clear_override(self) -> None:
        with self._lock:
            self._override = None
        slog.info("autoscale.override", mode="auto")

    # ------------------------------------------------------------ tick

    def tick(self, now: float | None = None) -> None:
        """One control-loop evaluation (the sampler tick): finish any
        in-flight decommissions, read signals, decide, actuate."""
        t = self._clock() if now is None else float(now)
        self.ticks_total += 1
        self._finish_decommissions(t)
        self._prune_stale_parks()
        util, burn = self.signals(t)
        AUTOSCALE_UTIL.set(util if util is not None else 0.0)
        n = self.current_size()
        # The decision state (stability counters, last signals) shares
        # the lock with _admit/set_override/status: the tick thread is
        # normally the only writer, but an operator override landing
        # mid-decision must not interleave with a half-updated streak.
        with self._lock:
            suppressed = t < self._suppressed_until
            override = self._override
            desired = n
            if override is not None:
                # The stability counters restart when control returns
                # to auto: a breach tick frozen from BEFORE the
                # override must not let one noisy scrape afterward
                # complete the streak.
                self._above = self._below = 0
                desired = override
            else:
                high = self.target_occupancy * (1.0 + self.hysteresis)
                low = self.target_occupancy * (1.0 - self.hysteresis)
                over = (
                    burn is not None and burn > self.burn_threshold
                ) or (util is not None and util > high)
                # Never shrink while the SLO burns: low occupancy with
                # a burning budget means the fleet is slow, not idle.
                under = (
                    util is not None and util < low
                    and (burn is None or burn <= self.burn_threshold)
                )
                self._above = self._above + 1 if over else 0
                self._below = self._below + 1 if under else 0
                if self._above >= self.up_stable_ticks:
                    desired = n + 1
                elif self._below >= self.down_stable_ticks:
                    desired = n - 1
            desired = max(self.min_replicas,
                          min(self.max_replicas, desired))
            self._last_signals = {
                "utilization": round(util, 4) if util is not None
                else None,
                "burn_fast": round(burn, 4) if burn is not None
                else None,
                "current": n,
                "desired": desired,
            }
        AUTOSCALE_SUPPRESSED.set(1.0 if suppressed else 0.0)
        AUTOSCALE_DESIRED.set(desired)
        if desired > n:
            self._scale_up(t, n, desired, util, burn,
                           manual=override is not None)
        elif desired < n:
            self._scale_down(t, n, desired, util, burn,
                             manual=override is not None)

    # ------------------------------------------------------- actuation

    def _admit(self, action: str, t: float, *, manual: bool) -> bool:
        """Cooldown + flap gate for one decision. Manual overrides
        bypass both (the operator said so) but still RECORD the action
        so a later automatic reversal is judged against it."""
        with self._lock:
            if not manual:
                if t < self._suppressed_until:
                    return False
                last = self._last_up if action == "up" else self._last_down
                cool = (self.up_cooldown if action == "up"
                        else self.down_cooldown)
                if last is not None and t - last < cool:
                    return False
                # Flap detection BEFORE actuating: the reversal that
                # crosses the threshold is itself suppressed — a
                # crash-respawn storm oscillating the signals gets
                # muted, not amplified.
                reversals = 0
                prev = None
                for ht, ha in list(self._history) + [(t, action)]:
                    if t - ht > self.flap_window:
                        continue
                    if prev is not None and ha != prev:
                        reversals += 1
                    prev = ha
                if reversals >= self.flap_reversals:
                    self._suppressed_until = t + self.flap_cooldown
                    self._history.clear()
                    AUTOSCALE_FLAPS.inc()
                    AUTOSCALE_SUPPRESSED.set(1.0)
                    slog.warning(
                        "autoscale.flap", reversals=reversals,
                        window_s=self.flap_window,
                        suppressed_for_s=self.flap_cooldown,
                    )
                    return False
            self._history.append((t, action))
            if action == "up":
                self._last_up = t
                self._above = 0
            else:
                self._last_down = t
                self._below = 0
            return True

    def _scale_up(self, t, n, desired, util, burn, *, manual) -> None:
        with self._lock:
            can_unpark = bool(self._parked)
        if not can_unpark and self.spawner is None:
            # No actuator at all: do not burn a cooldown slot / flap
            # history entry on a decision that cannot happen.
            _noact_log.warning(
                "autoscale.no_actuator", current=n, desired=desired,
                detail="no spawner (static fleet without --config) "
                       "and nothing parked to un-park",
            )
            return
        if not self._admit("up", t, manual=manual):
            return
        # Un-parking a previously scaled-down replica is instant and
        # free; spawning costs an engine boot — always prefer the park.
        unparked = self._unpark_one()
        if unparked is not None:
            AUTOSCALE_DECISIONS.labels(action="up").inc()
            slog.info(
                "autoscale.decision", action="up", current=n,
                desired=desired, replica=unparked, via="unpark",
                utilization=util, burn_fast=burn, manual=manual,
            )
            return
        if self.spawner is None:
            return
        AUTOSCALE_DECISIONS.labels(action="up").inc()
        slog.info(
            "autoscale.decision", action="up", current=n,
            desired=desired, via="spawn", utilization=util,
            burn_fast=burn, manual=manual,
        )
        with self._lock:
            self._spawning += 1
        threading.Thread(
            target=self._spawn_one, name="tdn-autoscale-spawn",
            daemon=True,
        ).start()

    def _unpark_one(self) -> str | None:
        """Re-admit one parked replica (scale-up on a static fleet).
        Stale park entries — the operator undrained or removed the
        replica meanwhile — are discarded, never acted on."""
        with self._lock:
            parked = sorted(self._parked)
        for target in parked:
            ok = self.pool.undrain(target)
            with self._lock:
                self._parked.discard(target)
            if ok:
                return target
        return None

    def _prune_stale_parks(self) -> None:
        """Drop park entries whose replica is no longer ours to
        un-park (operator undrained it back into service, or removed
        it). Run every tick BEFORE decisions: a stale entry must not
        make ``_scale_up`` consume a cooldown slot and a flap-history
        action on an un-park that cannot happen — and ``status()``'s
        parked list stays honest."""
        with self._lock:
            parked = list(self._parked)
        if not parked:
            return
        reps = {r.target: r for r in self.pool.replicas()}
        for target in parked:
            rep = reps.get(target)
            if (rep is None or rep.state != DRAINING
                    or not rep.decommissioning):
                with self._lock:
                    self._parked.discard(target)

    def _spawn_one(self) -> None:
        # On its own thread: an engine boot (compile + warmup) can
        # take minutes and the sampler tick must keep ticking — the
        # in-flight spawn counts toward current_size() so later ticks
        # do not double-spawn meanwhile.
        try:
            self.spawner()
        except Exception:  # noqa: BLE001 — a failed spawn must not kill ticks
            log.exception("autoscale spawn failed")
            slog.warning("autoscale.spawn_failed")
        finally:
            with self._lock:
                self._spawning -= 1

    def _scale_down(self, t, n, desired, util, burn, *, manual) -> None:
        victim = self._pick_victim()
        if victim is None:
            return
        if not self._admit("down", t, manual=manual):
            return
        # A pool-spawned victim is drained then REMOVED (its process
        # exit frees the resources). A non-spawned victim — static
        # fleet, orchestrator-managed pod — is drained and PARKED:
        # membership removal would be irreversible (nothing can re-add
        # the address), so the replica stays in the pool out of
        # rotation and scale-up un-parks it.
        spawned = any(
            r.target == victim and r.spawn_argv is not None
            for r in self.pool.replicas()
        )
        AUTOSCALE_DECISIONS.labels(action="down").inc()
        slog.info(
            "autoscale.decision", action="down", current=n,
            desired=desired, replica=victim,
            via="decommission" if spawned else "park",
            utilization=util, burn_fast=burn, manual=manual,
        )
        if self.pool.decommission(victim):
            with self._lock:
                if spawned:
                    self._decommissions[victim] = (
                        t + self.decommission_grace
                    )
                else:
                    self._parked.add(victim)

    def _pick_victim(self) -> str | None:
        """Least-loaded active replica (fewest in-flight rows to wait
        out — and the one the fleet will miss least)."""
        now = time.monotonic()
        cands = [
            r for r in self.pool.replicas()
            if r.state == ACTIVE and not r.decommissioning
        ]
        if not cands:
            return None
        return min(
            cands,
            key=lambda r: r.load_score(now, self.pool.load_staleness,
                                       self.pool.occupancy_weight),
        ).target

    def _finish_decommissions(self, t: float) -> None:
        """Complete scale-downs whose drain has been observed: remove
        the replica once the router holds nothing in flight on it. A
        replica past its grace deadline but still carrying outstanding
        forwards is NOT force-removed (remove() would CANCEL them) —
        it stays drained and out of placement, which is already the
        safe state; only the removal waits."""
        with self._lock:
            pending = list(self._decommissions.items())
        for target, deadline in pending:
            rep = next(
                (r for r in self.pool.replicas() if r.target == target),
                None,
            )
            if rep is not None and not rep.decommissioning:
                # The operator undrained the replica mid-scale-down
                # (pool.undrain clears the flag): the scale-down is
                # CANCELLED — removing a replica that is back in
                # service would turn an operator override into an
                # outage one tick later.
                with self._lock:
                    self._decommissions.pop(target, None)
                slog.info("autoscale.decommission_cancelled",
                          replica=target)
                continue
            if self.pool.drained_for_removal(target):
                self.pool.remove(target)
                with self._lock:
                    self._decommissions.pop(target, None)
                slog.info("autoscale.decommissioned", replica=target)
            elif t >= deadline:
                # Evidence for the operator, once per grace window.
                with self._lock:
                    self._decommissions[target] = t + self.decommission_grace
                slog.warning(
                    "autoscale.decommission_stalled", replica=target,
                    grace_s=self.decommission_grace,
                )

    # ---------------------------------------------------------- status

    def status(self) -> dict:
        """The ``GET /router/autoscale`` body."""
        with self._lock:
            override = self._override
            suppressed_until = self._suppressed_until
            spawning = self._spawning
            decommissioning = sorted(self._decommissions)
            parked = sorted(self._parked)
            signals = dict(self._last_signals)
        now = self._clock()
        return {
            # Last tick's signal snapshot FIRST: the fresh fields
            # below must win (a tick-old "current" shadowing the live
            # fleet size misreported every mid-spawn status read).
            **signals,
            "mode": "manual" if override is not None else "auto",
            "override": override,
            "min_replicas": self.min_replicas,
            "max_replicas": self.max_replicas,
            "target_occupancy": self.target_occupancy,
            "hysteresis": self.hysteresis,
            "burn_threshold": self.burn_threshold,
            "current": self.current_size(),
            "spawning": spawning,
            "decommissioning": decommissioning,
            "parked": parked,
            "flap_suppressed": now < suppressed_until,
            "ticks_total": self.ticks_total,
        }
