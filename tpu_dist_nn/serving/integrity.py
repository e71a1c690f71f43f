"""Silent-corruption defense plane (docs/ROBUSTNESS.md "Silent
corruption & quarantine").

Every resilience layer so far assumes a failing replica fails LOUDLY —
UNAVAILABLE, DEADLINE_EXCEEDED, a crash the flight recorder catches.
The failure class that actually corrupts results at fleet scale is the
replica that answers fast and *wrong*: flipped weight bits after a bad
checkpoint read, a mercurial core producing garbage matmuls ("Cores
that don't count", Hochschild et al., HotOS '21; "Silent Data
Corruptions at Scale", Dixit et al. '21 — PAPERS.md), NaN/Inf blowups
that argmax into confident nonsense. This module is the detector
ladder the router uses to PROVE a replica computes correctly, not just
that it is reachable:

* **Checkpoint fingerprints** — per-array SHA-256 checksums over the
  raw bytes (dtype + shape + buffer), folded into one whole-model
  fingerprint. Written into checkpoint metadata at save, verified at
  restore (:mod:`tpu_dist_nn.checkpoint.orbax_store`), exposed on
  ``/healthz`` so the pool refuses to admit a replica whose loaded
  weights disagree with the fleet's.
* **Numeric guards** (:class:`NumericGuard`) — a cheap per-row
  ``isfinite`` + magnitude reduction at the existing launch
  boundaries (the serving batcher's fetch, the continuous scheduler's
  decode step). Affected rows fail with
  :class:`~tpu_dist_nn.utils.errors.IntegrityError` (wire: DATA_LOSS)
  instead of shipping NaN activations; unaffected rows in the same
  launch are untouched (bit-parity preserved). ``TDN_INTEGRITY_GUARD=0``
  or ``GUARD.enabled = False`` opts out (tests' control).
* **Canary probes** (:class:`CanaryProber`) — a fixed seeded input
  with a golden temperature-0 answer, ridden on the pool's scrape
  loop. The serving stack is bit-identical at temperature 0 across
  replicas of the same weights (the PR-15/16 replay guarantee), so the
  golden digest is established from the first healthy answer and every
  later disagreement is a corruption verdict, not noise.
* **Shadow spot-checks** (:class:`SpotChecker`) — a sampled fraction
  of real Process traffic duplicated to a second replica off the
  request path; reply-byte disagreement is arbitrated by an immediate
  canary probe of both replicas (two replicas disagreeing only says
  SOMEONE is wrong).

A verdict from any rung moves the replica to the pool's QUARANTINED
state (:meth:`~tpu_dist_nn.serving.pool.ReplicaPool.quarantine`) —
placement stops, an incident bundle freezes the evidence, and
re-admission requires fingerprint + canary to pass again. Deliberately
distinct from the circuit breaker: a breaker half-open probe asks "are
you reachable?", which a wrong replica answers perfectly.
"""

from __future__ import annotations

import hashlib
import os
import random
import threading

import numpy as np

from tpu_dist_nn.obs.log import get_logger
from tpu_dist_nn.obs.registry import REGISTRY

slog = get_logger(__name__)

# One fixed seed for every canary input in the fleet: the probe's whole
# value is that every replica of the same weights computes the SAME
# answer, so the input must be a constant of the system, not a knob.
CANARY_SEED = 0x7DD

# rows the numeric guard failed with INTEGRITY instead of shipping
# non-finite (or absurd-magnitude) activations downstream.
GUARD_ROWS_FAILED = REGISTRY.counter(
    "tdn_integrity_guard_rows_total",
    "rows failed by the numeric guard (non-finite or out-of-magnitude "
    "activations caught at the launch boundary)",
)
GUARD_LAUNCHES = REGISTRY.counter(
    "tdn_integrity_guard_launches_total",
    "device launches in which the numeric guard failed at least one row",
)
CANARY_PROBES = REGISTRY.counter(
    "tdn_canary_probes_total",
    "canary probes by verdict (pass / fail / error; error = transport "
    "failure, NOT an integrity verdict — the breaker owns reachability)",
    labels=("verdict",),
)
SPOTCHECKS = REGISTRY.counter(
    "tdn_integrity_spotchecks_total",
    "shadow spot-checks by verdict (match / mismatch / error)",
    labels=("verdict",),
)


# --------------------------------------------------------- fingerprints


def array_checksum(a) -> str:
    """SHA-256 over an array's dtype + shape + raw little-endian bytes.

    Deterministic across processes and hosts for equal values: the
    buffer is canonicalized to C-contiguous before hashing, and dtype
    is part of the digest so an f32/f64 confusion cannot collide."""
    a = np.asarray(a)
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(repr(tuple(a.shape)).encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _named_leaves(tree) -> list[tuple[str, object]]:
    """(path, leaf) pairs for every array-like leaf of a pytree. A
    plain ``{name: array}`` dict short-circuits without jax so the
    fingerprint helpers work where jax is absent (router-only
    processes)."""
    if isinstance(tree, dict) and all(
        hasattr(v, "shape") and hasattr(v, "dtype") for v in tree.values()
    ):
        return sorted(tree.items())
    import jax

    pairs, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [
        (jax.tree_util.keystr(path), leaf)
        for path, leaf in pairs
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
    ]


def fingerprint_tree(tree) -> dict:
    """Per-array checksums plus the whole-model fingerprint.

    Returns ``{"model": sha, "arrays": {path: sha}, "count": n}``.
    The model fingerprint hashes the sorted ``path=checksum`` lines, so
    it pins both every array's bytes AND the tree structure (a renamed
    or dropped array changes it)."""
    arrays = {path: array_checksum(leaf) for path, leaf in _named_leaves(tree)}
    h = hashlib.sha256()
    for path in sorted(arrays):
        h.update(f"{path}={arrays[path]}\n".encode())
    return {"model": h.hexdigest(), "arrays": arrays, "count": len(arrays)}


def verify_tree(tree, expected: dict) -> list[str]:
    """Check a pytree against a fingerprint written at save time.

    Returns human-readable mismatch descriptions (empty = verified).
    Structure drift (missing/extra arrays) is reported alongside value
    drift — a truncated restore is as corrupt as a flipped bit."""
    got = fingerprint_tree(tree)
    exp_arrays = dict(expected.get("arrays") or {})
    mismatches = []
    for path, sha in sorted(got["arrays"].items()):
        want = exp_arrays.pop(path, None)
        if want is None:
            mismatches.append(f"{path}: not in saved fingerprint")
        elif want != sha:
            mismatches.append(
                f"{path}: checksum {sha[:12]}… != saved {want[:12]}…"
            )
    for path in sorted(exp_arrays):
        mismatches.append(f"{path}: missing from restored state")
    want_model = expected.get("model")
    if not mismatches and want_model and want_model != got["model"]:
        mismatches.append(
            f"model fingerprint {got['model'][:12]}… != saved "
            f"{want_model[:12]}…"
        )
    return mismatches


# ------------------------------------------------------- numeric guard


class NumericGuard:
    """Cheap per-row corruption screen at a launch boundary.

    ``bad_rows(out)`` reduces a materialized float batch to a ``(N,)``
    bool mask of rows carrying non-finite values or magnitudes past
    ``abs_limit`` — one vectorized pass over memory the caller just
    materialized anyway; its cost on the decode loop has no chip
    reading yet (ROADMAP D7). Callers fail exactly the masked
    rows with IntegrityError and ship the rest untouched.

    Disabled via ``TDN_INTEGRITY_GUARD=0`` at import, or
    ``GUARD.enabled = False`` at runtime (what tests use as the
    control)."""

    def __init__(self, enabled: bool | None = None,
                 abs_limit: float = 1e8):
        if enabled is None:
            enabled = os.environ.get("TDN_INTEGRITY_GUARD", "1") != "0"
        self.enabled = bool(enabled)
        self.abs_limit = float(abs_limit)

    def bad_rows(self, out) -> np.ndarray | None:
        """``(N,)`` bool mask of corrupt rows; None when the guard is
        disabled or the output is not a float batch (token ids are
        screened in-kernel by the continuous scheduler instead)."""
        if not self.enabled:
            return None
        out = np.asarray(out)
        if out.dtype.kind != "f" or out.ndim == 0 or out.size == 0:
            return None
        axes = tuple(range(1, out.ndim))
        finite = np.isfinite(out)
        ok = finite.all(axis=axes) if axes else finite
        if self.abs_limit:
            # where() masks the non-finite entries first: abs(inf) >
            # limit is already caught by the finite check, and abs(nan)
            # comparisons would warn.
            bounded = np.abs(np.where(finite, out, 0.0)) <= self.abs_limit
            ok = ok & (bounded.all(axis=axes) if axes else bounded)
        bad = ~ok
        if bad.any():
            GUARD_ROWS_FAILED.inc(int(bad.sum()))
            GUARD_LAUNCHES.inc()
        return bad


# Process-wide guard instance — the serving batcher, the continuous
# scheduler, and the tests all arm/disarm THIS object.
GUARD = NumericGuard()


# ------------------------------------------------------- canary probes


def canary_rows(dim: int, rows: int = 2,
                seed: int = CANARY_SEED) -> np.ndarray:
    """The fixed seeded Process canary input: same (rows, dim) batch on
    every prober in the fleet."""
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (int(rows), int(dim)))


def canary_prompts(prompt_len: int, vocab_size: int, rows: int = 1,
                   seed: int = CANARY_SEED) -> np.ndarray:
    """The fixed seeded Generate canary prompt(s) — token ids ride the
    Matrix wire as exact doubles."""
    rng = np.random.default_rng(seed)
    return rng.integers(
        0, int(vocab_size), (int(rows), int(prompt_len))
    ).astype(np.float64)


def reply_digest(reply_bytes: bytes) -> str:
    """Digest of a raw wire reply. The encoder is deterministic and the
    serving stack bit-identical at temperature 0, so equal answers
    yield equal bytes — comparing digests needs no decode."""
    return hashlib.sha256(reply_bytes).hexdigest()


class CanaryProber:
    """Golden-answer probing for one fleet.

    The first successful answer per method establishes the golden
    digest (recording which replica set it); every later probe is an
    exact-match check against it. Thread-safe — the pool's scrape loop
    fans probes out across replicas concurrently.

    ``probe(rep)`` returns ``(verdict, evidence)``:

    * ``True`` — answered on-golden (or just established the golden).
    * ``False`` — answered OFF-golden: a corruption verdict.
    * ``None`` — no answer (transport error/timeout): reachability is
      the breaker's problem, not an integrity verdict.
    """

    def __init__(self, *, dim: int | None = None,
                 prompt_len: int | None = None,
                 vocab_size: int | None = None,
                 interval: float = 5.0, timeout: float = 5.0,
                 rows: int = 2, seed: int = CANARY_SEED):
        from tpu_dist_nn.serving.wire import encode_matrix

        self.interval = float(interval)
        self.timeout = float(timeout)
        self._lock = threading.Lock()
        self.golden: dict[str, str] = {}  # guarded-by: _lock
        self.golden_source: dict[str, str] = {}  # guarded-by: _lock
        self._payloads: dict[str, bytes] = {}
        if dim is not None:
            self._payloads["Process"] = encode_matrix(
                canary_rows(dim, rows=rows, seed=seed)
            )
        if prompt_len is not None:
            self._payloads["Generate"] = encode_matrix(
                canary_prompts(prompt_len, vocab_size or 64, seed=seed)
            )
        if not self._payloads:
            raise ValueError(
                "CanaryProber needs dim= (Process) and/or prompt_len= "
                "(Generate)"
            )

    def methods(self) -> tuple[str, ...]:
        return tuple(self._payloads)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "methods": list(self._payloads),
                "golden": dict(self.golden),
                "golden_source": dict(self.golden_source),
                "interval": self.interval,
            }

    def check_reply(self, method: str, reply_bytes: bytes,
                    source: str) -> tuple[bool, dict]:
        """Compare one raw reply against the golden digest,
        establishing it from ``source`` when first seen."""
        digest = reply_digest(reply_bytes)
        with self._lock:
            golden = self.golden.get(method)
            if golden is None:
                self.golden[method] = digest
                self.golden_source[method] = source
                slog.info("integrity.canary_golden", method=method,
                          source=source, digest=digest[:12])
                return True, {"method": method, "digest": digest,
                              "established": True}
            golden_source = self.golden_source.get(method)
        if digest == golden:
            return True, {"method": method, "digest": digest}
        return False, {
            "method": method, "digest": digest, "golden": golden,
            "golden_source": golden_source,
        }

    def probe(self, rep) -> tuple[bool | None, dict]:
        """Probe one replica (a :class:`~tpu_dist_nn.serving.pool.
        Replica` or anything with ``.call(method, payload, timeout=)``
        and ``.target``) across every armed method."""
        target = getattr(rep, "target", "?")
        evidence: dict = {"target": target}
        for method, payload in self._payloads.items():
            try:
                reply = rep.call(method, payload, timeout=self.timeout)
            except Exception as e:  # noqa: BLE001 — transport, not verdict
                CANARY_PROBES.labels(verdict="error").inc()
                evidence.update({"method": method, "error": repr(e)[:200]})
                return None, evidence
            ok, ev = self.check_reply(method, reply, target)
            if not ok:
                CANARY_PROBES.labels(verdict="fail").inc()
                evidence.update(ev)
                slog.warning("integrity.canary_mismatch", replica=target,
                             **{k: v for k, v in ev.items()
                                if k in ("method", "digest", "golden")})
                return False, evidence
            CANARY_PROBES.labels(verdict="pass").inc()
        evidence["methods"] = list(self._payloads)
        return True, evidence


# ------------------------------------------------------- spot-checking


class SpotChecker:
    """Low-rate shadow duplication of real Process traffic.

    The router hands each successful (request, reply, replica) triple
    to :meth:`maybe_check`; a seeded coin at ``rate`` picks requests to
    duplicate to a second replica on a background thread (zero added
    latency on the request path; at most ``max_inflight`` shadows in
    flight, excess samples dropped). Reply-byte mismatch is arbitrated
    by an immediate canary probe of BOTH replicas — disagreement alone
    cannot say which side is wrong — and the losing replica is handed
    to ``on_verdict``."""

    def __init__(self, pool, *, rate: float = 0.02, seed: int = 0,
                 timeout: float = 5.0, canary: CanaryProber | None = None,
                 on_verdict=None, max_inflight: int = 2):
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"rate must be in [0, 1], got {rate}")
        self.pool = pool
        self.rate = float(rate)
        self.timeout = float(timeout)
        self.canary = canary
        # on_verdict(target, reason, evidence) — the router wires this
        # to pool.quarantine.
        self.on_verdict = on_verdict
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._inflight = 0
        self._max_inflight = int(max_inflight)
        self.mismatches = 0

    def maybe_check(self, method: str, payload: bytes, reply: bytes,
                    primary_target: str) -> bool:
        """Sample-and-dispatch; returns True when a shadow launched."""
        if method != "Process" or self.rate <= 0.0:
            return False
        with self._lock:
            # One seeded stream under a lock: the sampled request
            # indices replay deterministically for a serial driver.
            if self._rng.random() >= self.rate:
                return False
            if self._inflight >= self._max_inflight:
                return False
            self._inflight += 1
        t = threading.Thread(
            target=self._run, args=(method, payload, reply, primary_target),
            name="tdn-spotcheck", daemon=True,
        )
        t.start()
        return True

    def _run(self, method: str, payload: bytes, reply: bytes,
             primary_target: str) -> None:
        try:
            shadow = self.pool.place(exclude=frozenset((primary_target,)))
            if shadow is None:
                return
            try:
                self.pool.begin(shadow)
                try:
                    shadow_reply = shadow.call(
                        method, payload, timeout=self.timeout
                    )
                finally:
                    self.pool.done(shadow)
            except Exception:  # noqa: BLE001 — transport, not verdict
                SPOTCHECKS.labels(verdict="error").inc()
                return
            if reply_digest(shadow_reply) == reply_digest(reply):
                SPOTCHECKS.labels(verdict="match").inc()
                return
            SPOTCHECKS.labels(verdict="mismatch").inc()
            with self._lock:
                self.mismatches += 1
            slog.warning("integrity.spotcheck_mismatch",
                         primary=primary_target, shadow=shadow.target)
            self._arbitrate(primary_target, shadow)
        finally:
            with self._lock:
                self._inflight -= 1

    def _arbitrate(self, primary_target: str, shadow) -> None:
        """Two replicas disagreed on the same input: canary-probe both
        and indict whichever answers off-golden."""
        if self.canary is None or self.on_verdict is None:
            return
        suspects = []
        primary = None
        for rep in self.pool.replicas():
            if rep.target == primary_target:
                primary = rep
        for name, rep in (("primary", primary), ("shadow", shadow)):
            if rep is None:
                continue
            verdict, ev = self.canary.probe(rep)
            if verdict is False:
                suspects.append((rep.target, name, ev))
        for target, name, ev in suspects:
            ev = dict(ev)
            ev["detector"] = "spotcheck"
            ev["disagreed_with"] = (
                shadow.target if name == "primary" else primary_target
            )
            self.on_verdict(target, "spotcheck", ev)


def overhead_snapshot() -> dict:
    """Counter totals in one dict (absent families read 0)."""
    def total(name: str) -> float:
        m = REGISTRY.get(name)
        if m is None:
            return 0.0
        return float(sum(child.value for _, child in m.samples()))

    return {
        "guard_rows_failed": total("tdn_integrity_guard_rows_total"),
        "canary_probes": total("tdn_canary_probes_total"),
        "spotchecks": total("tdn_integrity_spotchecks_total"),
        "quarantines": total("tdn_quarantines_total"),
    }
