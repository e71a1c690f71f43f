"""Scenario engine: trace-driven workload capture & replay (ISSUE 18).

The flight recorder (obs/incident.py) freezes exactly what traffic
looked like when something broke; the SLO plane (obs/slo.py) can score
any window. This module closes the loop:

* :class:`WorkloadTrace` — one portable, JSON-serializable description
  of a request stream: per-request arrival offset, method, SLO class,
  session key, prompt tokens/length, max_new_tokens, budget, stream
  flag. Two sources produce it:

  - :func:`trace_from_bundle` extracts one from an incident bundle.
    The serving handlers annotate their root spans with every request
    attribute replay needs (``_annotate_capture_attrs`` in
    serving/server.py), the spans are epoch-anchored in ``trace.json``
    — so a bundle ALONE is a replayable workload.
  - the seeded synthetic :data:`GENERATORS` (diurnal, flash-crowd,
    heavy-tail prompt lengths, adversarial shared-prefix flood,
    mixed-SLO-class) emit the same schema, bit-reproducible under a
    seed (``random.Random`` only — never the wall clock).

* :func:`replay` — fires a WorkloadTrace against any gRPC target (a
  live fleet, or the :class:`LoopbackFleet` below) at ``--speed``
  multiples, preserving sessions, classes, budgets, and streaming, and
  reports how faithfully the achieved send process matched the trace
  (per-decile inter-arrival error — Orca makes arrival-process shape
  the dominant serving variable, so fidelity is itself a primitive).

* :class:`LoopbackFleet` — an in-process fleet: N fake-engine replicas
  (numpy-only, paced; all three RPC methods) behind the REAL router /
  pool / breaker / failover stack on 127.0.0.1 ephemeral ports. In-
  process on purpose: one shared TRACER sees both router and handler
  root spans (so capture round-trips work in one process), and chaos
  can kill a replica mid-run by stopping its server.

* :func:`run_scenario` — the matrix cell: a declarative spec (see
  ``scenarios/*.json``) names workload x faults x fleet events x SLO
  objectives; the run is scored by the real
  :class:`~tpu_dist_nn.obs.slo.SLOTracker` over a
  :class:`~tpu_dist_nn.obs.timeseries.TimeSeriesRing`, and the verdict
  is machine-readable (``tdn replay --scenario-dir`` prints the
  matrix's ``pass_ratio``).

Stdlib + numpy + grpc only — importable (and runnable) without jax;
the tier-1 quick smoke drives a scenario end-to-end in seconds.
"""

from __future__ import annotations

import dataclasses
import io
import json
import math
import os
import random
import threading
import time
import zipfile
from concurrent import futures

import numpy as np

SCHEMA_VERSION = 1

#: Handler root-span names -> WorkloadTrace method names. Router root
#: spans share these names; the capture attrs (``slo_class`` is the
#: marker — the handlers always set it) tell the two apart.
_ROOT_SPANS = {
    "rpc.Process": "Process",
    "rpc.Generate": "Generate",
    "rpc.GenerateStream": "GenerateStream",
}

_CLASSES = ("critical", "standard", "best_effort")


# --------------------------------------------------------------- schema


@dataclasses.dataclass
class Request:
    """One request in a workload: WHEN it arrives (seconds from the
    trace start), WHAT it is, and the attrs that must survive replay
    (class, session affinity, budget, streaming)."""

    arrival_s: float
    method: str = "Process"
    rows: int = 1
    dim: int | None = None
    prompt_len: int | None = None
    prompt_tokens: list[int] | None = None
    max_new_tokens: int | None = None
    slo_class: str = "standard"
    session: str | None = None
    budget_ms: int | None = None
    stream: bool = False

    def to_dict(self) -> dict:
        d = {"arrival_s": round(float(self.arrival_s), 6),
             "method": self.method, "slo_class": self.slo_class}
        for k in ("rows", "dim", "prompt_len", "prompt_tokens",
                  "max_new_tokens", "session", "budget_ms"):
            v = getattr(self, k)
            if v is not None and v != (1 if k == "rows" else None):
                d[k] = v
        if self.stream:
            d["stream"] = True
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Request":
        return cls(
            arrival_s=float(d["arrival_s"]),
            method=str(d.get("method", "Process")),
            rows=int(d.get("rows", 1)),
            dim=d.get("dim"),
            prompt_len=d.get("prompt_len"),
            prompt_tokens=d.get("prompt_tokens"),
            max_new_tokens=d.get("max_new_tokens"),
            slo_class=str(d.get("slo_class", "standard")),
            session=d.get("session"),
            budget_ms=d.get("budget_ms"),
            stream=bool(d.get("stream", False)),
        )


@dataclasses.dataclass
class WorkloadTrace:
    """An ordered request stream plus the provenance needed to rebuild
    it (``seed`` for synthetic content, ``source`` for where it came
    from). The list is kept sorted by arrival offset."""

    name: str
    seed: int = 0
    source: str = "synthetic"
    requests: list[Request] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        self.requests.sort(key=lambda r: r.arrival_s)

    @property
    def duration_s(self) -> float:
        return self.requests[-1].arrival_s if self.requests else 0.0

    def to_dict(self) -> dict:
        return {"schema_version": SCHEMA_VERSION, "name": self.name,
                "seed": self.seed, "source": self.source,
                "requests": [r.to_dict() for r in self.requests]}

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def from_dict(cls, d: dict) -> "WorkloadTrace":
        ver = int(d.get("schema_version", SCHEMA_VERSION))
        if ver > SCHEMA_VERSION:
            raise ValueError(
                f"WorkloadTrace schema_version {ver} is newer than this "
                f"reader ({SCHEMA_VERSION})"
            )
        return cls(name=str(d.get("name", "trace")),
                   seed=int(d.get("seed", 0)),
                   source=str(d.get("source", "unknown")),
                   requests=[Request.from_dict(r)
                             for r in d.get("requests", ())])

    @classmethod
    def from_json(cls, text: str) -> "WorkloadTrace":
        return cls.from_dict(json.loads(text))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path: str) -> "WorkloadTrace":
        with open(path) as f:
            return cls.from_json(f.read())

    # ------------------------------------------------- canonical shape

    def mix(self) -> dict:
        """The request mix as a canonical comparable dict: two traces
        with equal ``mix()`` carry the same requests (methods, classes,
        sessions, shapes, stream flags) — arrival TIMING is deliberately
        excluded (that is :meth:`inter_arrival_deciles`' job)."""
        by_method: dict[str, int] = {}
        by_class: dict[str, int] = {}
        sessions: dict[str, int] = {}
        shapes: dict[str, int] = {}
        streams = 0
        for r in self.requests:
            by_method[r.method] = by_method.get(r.method, 0) + 1
            by_class[r.slo_class] = by_class.get(r.slo_class, 0) + 1
            if r.session:
                sessions[r.session] = sessions.get(r.session, 0) + 1
            shape = f"{r.method}:{r.rows}x{r.prompt_len or r.dim or '?'}"
            shapes[shape] = shapes.get(shape, 0) + 1
            if r.stream:
                streams += 1
        return {
            "requests": len(self.requests),
            "by_method": dict(sorted(by_method.items())),
            "by_class": dict(sorted(by_class.items())),
            "sessions": dict(sorted(sessions.items())),
            "shapes": dict(sorted(shapes.items())),
            "streams": streams,
        }

    def inter_arrival_deciles(self) -> list[float]:
        """Deciles (d10..d90) of the inter-arrival gaps, seconds — the
        arrival-process fingerprint replay fidelity is judged against."""
        arr = [r.arrival_s for r in self.requests]
        gaps = [b - a for a, b in zip(arr, arr[1:])]
        return deciles(gaps)


def deciles(values) -> list[float]:
    """d10..d90 by linear interpolation ([] for < 2 values)."""
    vs = sorted(values)
    if len(vs) < 2:
        return []
    out = []
    for q in range(1, 10):
        pos = (len(vs) - 1) * q / 10.0
        lo = int(math.floor(pos))
        hi = min(lo + 1, len(vs) - 1)
        out.append(vs[lo] + (vs[hi] - vs[lo]) * (pos - lo))
    return out


def decile_errors(reference: list[float], achieved: list[float],
                  floor_s: float = 0.005) -> list[float]:
    """Per-decile relative error of ``achieved`` against ``reference``
    inter-arrival deciles. ``floor_s`` keeps a near-zero reference
    decile (back-to-back arrivals) from turning scheduler-tick jitter
    into an unbounded relative error."""
    return [abs(a - r) / max(r, floor_s)
            for r, a in zip(reference, achieved)]


# --------------------------------------------------- bundle extraction


def trace_from_chrome(doc: dict, *, name: str = "capture",
                      source: str = "chrome") -> WorkloadTrace:
    """Extract a WorkloadTrace from a Chrome trace-event document
    (``trace.json`` / ``trace_fleet.json``).

    Extraction rules (docs/OBSERVABILITY.md "Capture & replay"):

    * only complete (``ph == "X"``) events named ``rpc.Process`` /
      ``rpc.Generate`` / ``rpc.GenerateStream`` are considered;
    * only events whose ``args`` carry the capture attrs count — the
      handlers always set ``slo_class``, router roots never do, so
      router spans (same names) are skipped rather than double-counted;
    * events sharing a ``trace_id`` are ONE logical request (router
      failover lands the same request on a second replica) — the
      earliest handler span wins;
    * arrival offsets are the span ``ts`` deltas from the earliest kept
      span (epoch-anchored microseconds in the export).
    """
    best: dict[str, dict] = {}
    anon = 0
    for e in doc.get("traceEvents", ()):
        if e.get("ph") != "X":
            continue
        method = _ROOT_SPANS.get(e.get("name"))
        if method is None:
            continue
        args = e.get("args") or {}
        if "slo_class" not in args:
            continue  # router root or pre-ISSUE-18 capture
        key = args.get("trace_id")
        if not key:
            anon += 1
            key = f"_anon{anon}"
        cur = best.get(key)
        if cur is None or e["ts"] < cur["ts"]:
            best[key] = e
    picked = sorted(best.values(), key=lambda e: e["ts"])
    reqs: list[Request] = []
    t0 = picked[0]["ts"] if picked else 0.0
    for e in picked:
        args = e.get("args") or {}
        reqs.append(Request(
            arrival_s=(e["ts"] - t0) / 1e6,
            method=_ROOT_SPANS[e["name"]],
            rows=int(args.get("rows", 1)),
            dim=args.get("dim"),
            prompt_len=args.get("prompt_len"),
            max_new_tokens=args.get("max_new_tokens"),
            slo_class=str(args.get("slo_class", "standard")),
            session=args.get("session"),
            budget_ms=args.get("budget_ms"),
            stream=bool(args.get("stream", False)),
        ))
    return WorkloadTrace(name=name, source=source, requests=reqs)


def trace_from_bundle(bundle, *, name: str | None = None) -> WorkloadTrace:
    """Extract a WorkloadTrace from an incident bundle (zip bytes, a
    path, or a file-like). Prefers the stitched ``trace_fleet.json``
    (fleet captures: every replica's handler spans in one document)
    over the local ``trace.json``."""
    if isinstance(bundle, (bytes, bytearray)):
        fh = io.BytesIO(bundle)
        label = name or "bundle"
    elif isinstance(bundle, (str, os.PathLike)):
        fh = open(bundle, "rb")
        label = name or os.path.basename(os.fspath(bundle))
    else:
        fh = bundle
        label = name or "bundle"
    try:
        with zipfile.ZipFile(fh) as zf:
            names = set(zf.namelist())
            pick = ("trace_fleet.json" if "trace_fleet.json" in names
                    else "trace.json")
            if pick not in names:
                raise ValueError(
                    f"bundle has no trace.json (sections: {sorted(names)})"
                )
            doc = json.loads(zf.read(pick))
            iid = None
            if "manifest.json" in names:
                iid = json.loads(zf.read("manifest.json")).get("incident_id")
    finally:
        if isinstance(bundle, (str, os.PathLike)):
            fh.close()
    return trace_from_chrome(doc, name=label,
                             source=f"bundle:{iid or 'unknown'}")


# ------------------------------------------------- synthetic generators

GENERATORS: dict[str, "callable"] = {}


def _generator(name):
    def reg(fn):
        GENERATORS[name] = fn
        return fn
    return reg


def make_workload(generator: str, seed: int = 0, **kwargs) -> WorkloadTrace:
    """Build a named synthetic workload. Same (generator, seed, kwargs)
    -> bit-identical WorkloadTrace, always."""
    try:
        fn = GENERATORS[generator]
    except KeyError:
        raise ValueError(
            f"unknown workload generator {generator!r}; have "
            f"{sorted(GENERATORS)}"
        ) from None
    return fn(seed=seed, **kwargs)


def _shaped_arrivals(rng: random.Random, n: int, duration: float,
                     weight) -> list[float]:
    """``n`` arrival offsets over ``[0, duration]`` following the
    relative rate ``weight(t in [0,1])``, by inverse-CDF over a fine
    grid — deterministic under the rng, no rejection loops."""
    grid = 512
    w = [max(weight((i + 0.5) / grid), 1e-9) for i in range(grid)]
    cum = []
    tot = 0.0
    for x in w:
        tot += x
        cum.append(tot)
    cum = [c / tot for c in cum]
    us = sorted(rng.random() for _ in range(n))
    out = []
    j = 0
    for u in us:
        while j < grid - 1 and cum[j] < u:
            j += 1
        lo = cum[j - 1] if j else 0.0
        hi = cum[j]
        frac = (u - lo) / (hi - lo) if hi > lo else 0.0
        out.append((j + frac) / grid * duration)
    return out


def _pick_class(rng: random.Random, classes: dict | None) -> str:
    if not classes:
        return "standard"
    names = sorted(classes)
    weights = [float(classes[c]) for c in names]
    return rng.choices(names, weights=weights, k=1)[0]


def _pick_session(rng: random.Random, sessions: int,
                  p_none: float = 0.25) -> str | None:
    if sessions <= 0 or rng.random() < p_none:
        return None
    return f"sess-{rng.randrange(sessions)}"


@_generator("diurnal")
def gen_diurnal(seed: int = 0, *, requests: int = 100,
                duration: float = 8.0, peak_ratio: float = 4.0,
                cycles: float = 1.0, dim: int = 8, sessions: int = 6,
                classes: dict | None = None,
                budget_ms: int | None = None) -> WorkloadTrace:
    """Sinusoidal day/night rate: trough 1x, peak ``peak_ratio``x,
    ``cycles`` full cycles over the (compressed) duration."""
    rng = random.Random(seed)

    def weight(t):
        return 1.0 + (peak_ratio - 1.0) * 0.5 * (
            1.0 - math.cos(2 * math.pi * cycles * t)
        )

    reqs = [Request(arrival_s=t, method="Process", rows=1, dim=dim,
                    slo_class=_pick_class(rng, classes),
                    session=_pick_session(rng, sessions),
                    budget_ms=budget_ms)
            for t in _shaped_arrivals(rng, requests, duration, weight)]
    return WorkloadTrace(name=f"diurnal-{seed}", seed=seed,
                         source="generator:diurnal", requests=reqs)


@_generator("flash_crowd")
def gen_flash_crowd(seed: int = 0, *, requests: int = 120,
                    duration: float = 8.0, spike_at: float = 0.5,
                    spike_width: float = 0.15, spike_ratio: float = 8.0,
                    dim: int = 8, sessions: int = 6,
                    classes: dict | None = None,
                    budget_ms: int | None = None) -> WorkloadTrace:
    """Steady background rate with one ``spike_ratio``x flash crowd
    centred at ``spike_at`` (fraction of the duration)."""
    rng = random.Random(seed)
    lo, hi = spike_at - spike_width / 2, spike_at + spike_width / 2

    def weight(t):
        return spike_ratio if lo <= t <= hi else 1.0

    reqs = [Request(arrival_s=t, method="Process", rows=1, dim=dim,
                    slo_class=_pick_class(rng, classes),
                    session=_pick_session(rng, sessions),
                    budget_ms=budget_ms)
            for t in _shaped_arrivals(rng, requests, duration, weight)]
    return WorkloadTrace(name=f"flash_crowd-{seed}", seed=seed,
                         source="generator:flash_crowd", requests=reqs)


@_generator("heavy_tail")
def gen_heavy_tail(seed: int = 0, *, requests: int = 60,
                   duration: float = 8.0, alpha: float = 1.3,
                   prompt_len: int = 8, max_new_tokens: int = 8,
                   vocab_size: int = 64, sessions: int = 4,
                   stream_fraction: float = 0.0,
                   classes: dict | None = None) -> WorkloadTrace:
    """Poisson arrivals, Pareto(``alpha``) prompt lengths clamped to
    ``[1, prompt_len]`` — the Orca regime where a few giant prompts
    convoy everyone else. Replay pads each prompt to the endpoint's
    static width, so the tail survives in token CONTENT (sampled-length
    prefix) and in the trace itself."""
    rng = random.Random(seed)
    reqs = []
    for t in _shaped_arrivals(rng, requests, duration, lambda t: 1.0):
        raw = rng.paretovariate(alpha)
        plen = max(1, min(prompt_len, int(raw)))
        tokens = [rng.randrange(vocab_size) for _ in range(plen)]
        streaming = rng.random() < stream_fraction
        reqs.append(Request(
            arrival_s=t,
            method="GenerateStream" if streaming else "Generate",
            rows=1, prompt_len=plen, prompt_tokens=tokens,
            max_new_tokens=max_new_tokens,
            slo_class=_pick_class(rng, classes),
            session=_pick_session(rng, sessions),
            stream=streaming,
        ))
    return WorkloadTrace(name=f"heavy_tail-{seed}", seed=seed,
                         source="generator:heavy_tail", requests=reqs)


@_generator("shared_prefix_flood")
def gen_shared_prefix_flood(seed: int = 0, *, requests: int = 60,
                            duration: float = 4.0,
                            prompt_len: int = 8,
                            prefix_fraction: float = 0.75,
                            max_new_tokens: int = 8,
                            vocab_size: int = 64,
                            sessions: int = 2,
                            classes: dict | None = None) -> WorkloadTrace:
    """Adversarial prefix-cache flood: every prompt shares one long
    common prefix (``prefix_fraction`` of the width) with unique
    tails, arriving in a front-loaded burst from few sessions."""
    rng = random.Random(seed)
    npre = max(1, int(prompt_len * prefix_fraction))
    prefix = [rng.randrange(vocab_size) for _ in range(npre)]

    def weight(t):  # front-loaded: 4x rate in the first quarter
        return 4.0 if t < 0.25 else 1.0

    reqs = []
    for t in _shaped_arrivals(rng, requests, duration, weight):
        tail = [rng.randrange(vocab_size)
                for _ in range(prompt_len - npre)]
        reqs.append(Request(
            arrival_s=t, method="Generate", rows=1,
            prompt_len=prompt_len, prompt_tokens=prefix + tail,
            max_new_tokens=max_new_tokens,
            slo_class=_pick_class(rng, classes),
            session=_pick_session(rng, sessions, p_none=0.0),
        ))
    return WorkloadTrace(name=f"shared_prefix_flood-{seed}", seed=seed,
                         source="generator:shared_prefix_flood",
                         requests=reqs)


@_generator("mixed_class")
def gen_mixed_class(seed: int = 0, *, requests: int = 90,
                    duration: float = 6.0, dim: int = 8,
                    sessions: int = 6,
                    classes: dict | None = None,
                    budget_ms: int | None = None) -> WorkloadTrace:
    """Poisson arrivals with an explicit SLO-class mix (default
    20/50/30 critical/standard/best_effort) — the degradation-ladder
    workload."""
    rng = random.Random(seed)
    classes = classes or {"critical": 0.2, "standard": 0.5,
                          "best_effort": 0.3}
    reqs = [Request(arrival_s=t, method="Process", rows=1, dim=dim,
                    slo_class=_pick_class(rng, classes),
                    session=_pick_session(rng, sessions),
                    budget_ms=budget_ms)
            for t in _shaped_arrivals(rng, requests, duration,
                                      lambda t: 1.0)]
    return WorkloadTrace(name=f"mixed_class-{seed}", seed=seed,
                         source="generator:mixed_class", requests=reqs)


# --------------------------------------------------------- replay driver


def _payload_rng(trace: WorkloadTrace, i: int) -> random.Random:
    # Content seed: trace seed x request index — replaying the same
    # trace sends bit-identical payloads, independent of thread timing.
    return random.Random((int(trace.seed) << 20) ^ (i * 2654435761 % (1 << 31)))


def _prompt_ids(req: Request, rng: random.Random, prompt_len: int,
                vocab_size: int) -> np.ndarray:
    """The prompt matrix for a Generate/GenerateStream request: the
    captured tokens when present (clamped into vocab), else seeded
    synthetics of the recorded length, padded to the endpoint's static
    ``prompt_len``."""
    want = int(req.prompt_len or prompt_len)
    toks = list(req.prompt_tokens or ())
    if not toks:
        toks = [rng.randrange(vocab_size) for _ in range(want)]
    toks = [int(t) % vocab_size for t in toks][:prompt_len]
    if len(toks) < prompt_len:
        toks = toks + [0] * (prompt_len - len(toks))
    rows = max(1, int(req.rows)) if req.method == "Generate" else 1
    return np.asarray([toks] * rows, dtype=np.int64)


def replay(trace: WorkloadTrace, target: str, *, speed: float = 1.0,
           dim: int = 8, prompt_len: int = 8, vocab_size: int = 64,
           timeout: float = 30.0, gap_timeout: float | None = 10.0,
           max_workers: int = 32, client=None,
           on_start=None) -> dict:
    """Fire ``trace`` at ``target`` and return a replay report.

    ``speed`` compresses (>1) or dilates (<1) the arrival process; the
    request MIX is never altered. Dispatch is absolute-time paced (each
    request fires at ``t0 + arrival_s/speed``, no drift accumulation)
    from one scheduler thread into a worker pool; sessions, classes,
    budgets, and streaming all ride the real client headers.

    The report carries outcome counts, latency/TTFT percentiles, and
    ``arrival`` — the achieved per-decile inter-arrival error against
    the (speed-scaled) trace, the fidelity figure the round-trip
    acceptance asserts on.

    ``client`` overrides the auto-built one (auto: ``retry=None,
    breaker=None`` — the target's OWN resilience stack is the thing
    under test; client-side retries would mask it). ``on_start`` is
    called with the monotonic start time just before the first
    dispatch (the chaos timeline anchors on it).
    """
    from tpu_dist_nn.serving.server import GrpcClient

    if speed <= 0:
        raise ValueError(f"speed must be > 0, got {speed}")
    own_client = client is None
    if own_client:
        # wait_for_ready: the ~100ms first-connect handshake must land
        # BEFORE t0, not inside request 0's arrival offset — it would
        # shift the fidelity anchor by a whole decile.
        client = GrpcClient(target, timeout=timeout, retry=None,
                            breaker=None, wait_for_ready=True,
                            ready_timeout=10.0)
    results: list[dict] = []
    lock = threading.Lock()

    def fire(i: int, req: Request, planned: float, t0: float):
        rng = _payload_rng(trace, i)
        rec = {"i": i, "method": req.method, "slo_class": req.slo_class,
               "session": req.session, "ok": False, "code": None,
               "sent_s": time.monotonic() - t0, "planned_s": planned}
        t_req = time.monotonic()
        try:
            if req.method == "Process":
                d = int(req.dim or dim)
                x = np.asarray(
                    [[rng.random() for _ in range(d)]
                     for _ in range(max(1, int(req.rows)))]
                )
                client.process(x, session_key=req.session,
                               slo_class=req.slo_class)
            elif req.method == "Generate":
                ids = _prompt_ids(req, rng, prompt_len, vocab_size)
                client.generate(ids, session_key=req.session,
                                slo_class=req.slo_class)
            elif req.method == "GenerateStream":
                ids = _prompt_ids(req, rng, prompt_len, vocab_size)
                reply = client.generate_stream(
                    ids, session_key=req.session, slo_class=req.slo_class,
                    timeout=timeout, gap_timeout=gap_timeout,
                )
                ntok = 0
                for tok in reply:
                    if ntok == 0:
                        rec["ttft_s"] = time.monotonic() - t_req
                    ntok += 1
                rec["tokens"] = ntok
            else:
                raise ValueError(f"unknown method {req.method!r}")
            rec["ok"] = True
            rec["code"] = "OK"
        except Exception as e:  # noqa: BLE001 — outcome, not crash
            try:
                rec["code"] = e.code().name  # grpc.RpcError
            except Exception:  # noqa: BLE001
                rec["code"] = type(e).__name__
        rec["latency_s"] = time.monotonic() - t_req
        with lock:
            results.append(rec)

    pool = futures.ThreadPoolExecutor(max_workers=max_workers)
    t0 = time.monotonic()
    if on_start is not None:
        on_start(t0)
    pending = []
    try:
        for i, req in enumerate(trace.requests):
            planned = req.arrival_s / speed
            delay = t0 + planned - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            pending.append(pool.submit(fire, i, req, planned, t0))
        for f in pending:
            f.result()
    finally:
        pool.shutdown(wait=True)
        if own_client:
            client.close()
    wall = time.monotonic() - t0
    results.sort(key=lambda r: r["i"])
    return _replay_report(trace, target, speed, wall, results)


def _pcts(vals: list[float]) -> dict:
    if not vals:
        return {}
    vs = sorted(vals)

    def p(q):
        pos = (len(vs) - 1) * q
        lo = int(pos)
        hi = min(lo + 1, len(vs) - 1)
        return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)

    return {"p50_ms": round(p(0.50) * 1e3, 3),
            "p95_ms": round(p(0.95) * 1e3, 3),
            "p99_ms": round(p(0.99) * 1e3, 3)}


def _replay_report(trace, target, speed, wall, results) -> dict:
    errors: dict[str, int] = {}
    for r in results:
        if not r["ok"]:
            errors[r["code"] or "?"] = errors.get(r["code"] or "?", 0) + 1
    ref = [d / speed for d in trace.inter_arrival_deciles()]
    sent = deciles([b["sent_s"] - a["sent_s"]
                    for a, b in zip(results, results[1:])])
    errs = decile_errors(ref, sent) if ref and sent else []
    report = {
        "trace": trace.name,
        "target": target,
        "speed": speed,
        "wall_s": round(wall, 3),
        "requests": len(results),
        "ok": sum(1 for r in results if r["ok"]),
        "errors": dict(sorted(errors.items())),
        "latency": _pcts([r["latency_s"] for r in results if r["ok"]]),
        "ttft": _pcts([r["ttft_s"] for r in results if "ttft_s" in r]),
        "tokens_streamed": sum(r.get("tokens", 0) for r in results),
        "arrival": {
            "trace_deciles_ms": [round(d * 1e3, 3) for d in ref],
            "sent_deciles_ms": [round(d * 1e3, 3) for d in sent],
            "per_decile_error": [round(e, 4) for e in errs],
            "max_decile_error": round(max(errs), 4) if errs else None,
        },
    }
    return report


# ------------------------------------------------------- loopback fleet


def _fault_from_spec(d: dict):
    """{"kind": "unavailable"|...,"p"/"every"/"at","seed","seconds",
    "hold"} -> (FaultPlan, hook) where hook is "interceptor"|"launch"
    |"nan_launch"|"tamper". An optional "replica" key scopes the fault
    to ONE replica index (corruption cells model a single bad machine,
    not a fleet-wide defect) — honoured by ``LoopbackFleet``."""
    from tpu_dist_nn.testing import faults as F

    kind = d.get("kind", "unavailable")
    hook = d.get("hook", "interceptor")
    if kind == "delay":
        fault = F.delay(float(d.get("seconds", 0.05)))
    elif kind == "drop":
        fault = F.drop(float(d.get("hold", 0.2)))
    elif kind in ("nan_launch", "reply_tamper"):
        # Silent-corruption kinds: the fault is a schedulable marker —
        # nothing raises; the hook poisons data instead
        # (docs/ROBUSTNESS.md "Silent corruption & quarantine").
        fault = F.tamper(kind)
        hook = "nan_launch" if kind == "nan_launch" else "tamper"
    else:
        factory = {"unavailable": F.unavailable,
                   "deadline_exceeded": F.deadline_exceeded,
                   "internal": F.internal,
                   "resource_exhausted": F.resource_exhausted}.get(kind)
        if factory is None:
            raise ValueError(f"unknown fault kind {kind!r}")
        fault = factory()
    at = {int(k): fault for k in d.get("at", ())} or None
    plan = F.FaultPlan(at=at, every=d.get("every"),
                      fault=fault, p=d.get("p"),
                      seed=int(d.get("seed", 0)))
    return plan, hook


class _FakeModel:
    def __init__(self, dim):
        self.input_dim = dim


class _FakeEngine:
    """Numpy-only paced engine: ``per_row_ms`` per Process row. The
    first-class fault hooks exist exactly like the real Engine's."""

    def __init__(self, dim: int, per_row_ms: float):
        self.model = _FakeModel(dim)
        self.per_row_s = per_row_ms / 1e3
        self.launch_hook = None
        self.fetch_hook = None

    def infer(self, x):
        # Materialize to an OWNED buffer first: the handler passes a
        # lazy WireMatrix, and the corruption hooks mutate their input
        # in place — poisoning a temporary would be a silent no-op.
        x = np.array(x, dtype=np.float64)
        if self.launch_hook is not None:
            self.launch_hook(x)
        if self.per_row_s:
            time.sleep(self.per_row_s * len(x))
        out = x * 2.0
        # Same numeric-guard contract as the real Engine's fetch
        # boundary: a poisoned launch (faults.nan_launch) must fail
        # DATA_LOSS at the wire, never ship NaN — the scenario cells
        # exercise the router's guard -> strike -> quarantine ladder
        # through exactly the production detection path.
        from tpu_dist_nn.serving import integrity

        bad = integrity.GUARD.bad_rows(out)
        if bad is not None and bad.any():
            from tpu_dist_nn.utils.errors import IntegrityError

            raise IntegrityError(
                f"numeric guard: {int(bad.sum())}/{len(bad)} rows of "
                f"the launch are non-finite or out of magnitude bounds"
            )
        return out


class LoopbackFleet:
    """N in-process fake replicas (Process + Generate + GenerateStream)
    behind the real router/pool stack — the scenario engine's
    self-hosted target.

    In-process replicas share the parent's TRACER, so handler root
    spans (with the ISSUE-18 capture attrs) land in the same buffer the
    incident plane exports — a capture -> extract -> replay round trip
    needs exactly one process. Chaos kills a replica by stopping its
    gRPC server (in-flight RPCs surface as UNAVAILABLE and the router
    fails over, same as a process crash at the wire)."""

    def __init__(self, replicas: int = 2, *, dim: int = 8,
                 prompt_len: int = 8, max_new_tokens: int = 8,
                 vocab_size: int = 64, per_row_ms: float = 1.0,
                 per_token_ms: float = 1.0, prefill_ms: float = 2.0,
                 faults=(), hedge: bool = False, seed: int = 0,
                 forward_timeout: float | None = 30.0,
                 canary: dict | None = None,
                 spotcheck: dict | None = None):
        self.n = int(replicas)
        self.dim = int(dim)
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.vocab_size = int(vocab_size)
        self.per_row_ms = float(per_row_ms)
        self.per_token_ms = float(per_token_ms)
        self.prefill_ms = float(prefill_ms)
        self.fault_specs = list(faults or ())
        self.hedge = bool(hedge)
        self.seed = int(seed)
        self.forward_timeout = forward_timeout
        self.canary_spec = dict(canary) if canary else None
        self.spotcheck_spec = dict(spotcheck) if spotcheck else None
        self.canary = None
        self.spotcheck = None
        self.servers: list = []
        self.engines: list[_FakeEngine] = []
        self.targets: list[str] = []
        self.fault_plans: list = []
        self.pool = None
        self.router_server = None
        self.target: str | None = None

    # ------------------------------------------------- replica innards

    def _gen_tokens(self, ids_row) -> list[int]:
        base = int(np.asarray(ids_row).sum()) % self.vocab_size
        return [(base + 7 * k) % self.vocab_size
                for k in range(1, self.max_new_tokens + 1)]

    def _make_replica(self, index: int):
        from tpu_dist_nn.serving.server import (
            _bind_or_close,
            _make_generate_handler,
            _make_generate_stream_handler,
            _make_handler,
            _new_grpc_server,
        )
        from tpu_dist_nn.serving.stream import TokenStream

        eng = _FakeEngine(self.dim, self.per_row_ms)
        prefill_s = self.prefill_ms / 1e3
        per_tok_s = self.per_token_ms / 1e3

        def run_submit(ids, budget, ctx=None, slo_class="standard"):
            if eng.launch_hook is not None:
                eng.launch_hook(ids)
            time.sleep(prefill_s + per_tok_s * self.max_new_tokens)
            out = np.asarray([self._gen_tokens(row) for row in ids],
                             dtype=np.int64)
            return np.concatenate(
                [np.asarray(ids, np.int64), out], axis=1
            )

        def run_submit_stream(ids, budget, ctx=None,
                              slo_class="standard", resume=None):
            ts = TokenStream()
            full = list(resume or ()) + self._gen_tokens(ids[0])[
                len(resume or ()):]

            def produce():
                time.sleep(prefill_s)
                nres = len(resume or ())
                if nres:
                    ts.seed(nres)
                known = list(full[:nres])
                for t in full[nres:]:
                    time.sleep(per_tok_s)
                    known.append(t)
                    if not ts.publish(list(known)):
                        return
                ts.finish("max_tokens")

            threading.Thread(target=produce, daemon=True).start()
            return ts

        interceptors = []
        for spec in self.fault_specs:
            if "replica" in spec and int(spec["replica"]) != index:
                continue
            plan, hook = _fault_from_spec(spec)
            self.fault_plans.append(plan)
            if hook == "launch":
                eng.launch_hook = plan.fire
            elif hook == "nan_launch":
                from tpu_dist_nn.testing.faults import nan_launch
                eng.launch_hook = nan_launch(
                    rows=tuple(spec.get("rows", (0,))), plan=plan
                )
            elif hook == "tamper":
                from tpu_dist_nn.testing.faults import (
                    make_tamper_interceptor,
                )
                interceptors.append(make_tamper_interceptor(plan))
            else:
                from tpu_dist_nn.testing.faults import make_interceptor
                interceptors.append(make_interceptor(plan))
        srv = _new_grpc_server(16, tuple(interceptors))
        srv.add_generic_rpc_handlers((
            _make_handler(eng, None),
            _make_generate_handler(run_submit, self.prompt_len,
                                   self.vocab_size,
                                   max_new_tokens=self.max_new_tokens),
            _make_generate_stream_handler(
                run_submit_stream, self.prompt_len, self.vocab_size,
                max_new_tokens=self.max_new_tokens),
        ))
        port = _bind_or_close(srv, "127.0.0.1", 0, None)
        srv.start()
        return srv, eng, f"127.0.0.1:{port}"

    # ------------------------------------------------------- lifecycle

    def start(self) -> "LoopbackFleet":
        from tpu_dist_nn.serving.pool import ReplicaPool
        from tpu_dist_nn.serving.router import HedgePolicy, serve_router

        for i in range(self.n):
            srv, eng, tgt = self._make_replica(i)
            self.servers.append(srv)
            self.engines.append(eng)
            self.targets.append(tgt)
        self.pool = ReplicaPool(self.targets, seed=self.seed)
        hedge = HedgePolicy() if self.hedge else None
        if self.canary_spec is not None or self.spotcheck_spec is not None:
            from tpu_dist_nn.serving.integrity import CanaryProber

            c = self.canary_spec or {}
            self.canary = CanaryProber(
                dim=self.dim, prompt_len=self.prompt_len,
                vocab_size=self.vocab_size,
                interval=float(c.get("interval", 1.0)),
                timeout=float(c.get("timeout", 5.0)),
                seed=int(c.get("seed", 0x7DD)),
            )
        if self.spotcheck_spec is not None:
            from tpu_dist_nn.serving.integrity import SpotChecker

            s = self.spotcheck_spec
            self.spotcheck = SpotChecker(
                self.pool, rate=float(s.get("rate", 0.25)),
                seed=int(s.get("seed", self.seed)),
                timeout=float(s.get("timeout", 5.0)),
                canary=self.canary,
                on_verdict=lambda tgt, reason, ev: self.pool.quarantine(
                    tgt, reason=reason, evidence=ev
                ),
            )
        self.router_server, port = serve_router(
            self.pool, 0, host="127.0.0.1",
            forward_timeout=self.forward_timeout, hedge=hedge,
            canary=self.canary, spotcheck=self.spotcheck,
        )
        self.target = f"127.0.0.1:{port}"
        return self

    def kill_replica(self, index: int) -> None:
        """Chaos: hard-stop replica ``index`` (in-flight RPCs die
        UNAVAILABLE at the wire, exactly like a crashed process)."""
        self.servers[index].stop(None)

    def drain_replica(self, index: int) -> None:
        self.pool.drain(self.targets[index], signal_process=False)

    def undrain_replica(self, index: int) -> None:
        self.pool.undrain(self.targets[index])

    def stop(self) -> None:
        if self.router_server is not None:
            self.router_server.stop(None)
        if self.pool is not None:
            self.pool.close(grace=0.5)
        for srv in self.servers:
            try:
                srv.stop(None)
            except Exception:  # noqa: BLE001 — already killed by chaos
                pass

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


# ------------------------------------------------------ scenario runner


def _objective_from_spec(d: dict):
    from tpu_dist_nn.obs.slo import (
        availability_objective,
        latency_objective,
    )

    kind = d.get("kind", "latency")
    if kind == "latency":
        return latency_objective(
            d["name"], d.get("family", "tdn_router_request_seconds"),
            float(d["threshold_ms"]) / 1e3, q=float(d.get("q", 0.99)),
            match=d.get("match"),
        )
    if kind == "availability":
        return availability_objective(
            d["name"], float(d["target"]),
            d.get("total_family", "tdn_router_requests_total"),
            bad_family=d.get("bad_family"),
            match=d.get("match"),
            bad_match=d.get("bad_match"),
            bad_exclude=d.get("bad_exclude",
                              None if d.get("bad_family")
                              or d.get("bad_match")
                              else {"outcome": "ok"}),
        )
    raise ValueError(f"unknown objective kind {kind!r}")


def load_scenario(path: str) -> dict:
    """Read + validate one scenario spec (see docs/ROBUSTNESS.md
    "Chaos-load matrix" for the format)."""
    with open(path) as f:
        spec = json.load(f)
    for key in ("name", "workload", "slo"):
        if key not in spec:
            raise ValueError(f"scenario {path}: missing {key!r}")
    wl = spec["workload"]
    if "generator" not in wl and "capture" not in wl and "trace" not in wl:
        raise ValueError(
            f"scenario {path}: workload needs generator|capture|trace"
        )
    if not spec["slo"].get("objectives"):
        raise ValueError(f"scenario {path}: slo.objectives is empty")
    spec.setdefault("_path", os.path.abspath(path))
    return spec


def _scale_workload_args(args: dict, scale: float) -> dict:
    """Quick-mode shrink: fewer requests over a shorter window, same
    shape (rates preserved — both axes scale together)."""
    out = dict(args)
    if "requests" in out:
        out["requests"] = max(8, int(out["requests"] * scale))
    if "duration" in out:
        out["duration"] = max(1.0, float(out["duration"]) * scale)
    return out


def _build_workload(spec: dict, seed: int, quick_scale: float | None):
    wl = spec["workload"]
    if "generator" in wl:
        args = dict(wl.get("args", {}))
        if quick_scale:
            args = _scale_workload_args(args, quick_scale)
        return make_workload(wl["generator"], seed=seed, **args)
    if "trace" in wl:
        path = wl["trace"]
        if not os.path.isabs(path) and "_path" in spec:
            path = os.path.join(os.path.dirname(spec["_path"]), path)
        return WorkloadTrace.load(path)
    # "capture": run a seed workload first, capture a bundle, extract.
    # Handled by run_scenario (needs the live fleet).
    return None


def run_scenario(spec: dict, *, seed: int | None = None,
                 speed: float | None = None,
                 quick_scale: float | None = None) -> dict:
    """Run one scenario cell end-to-end and return its verdict.

    Builds the workload (generator / checked-in trace / capture-then-
    replay), stands up the loopback fleet with the spec's fault plans,
    arms the chaos timeline, replays, and scores the run with the REAL
    SLOTracker over a TimeSeriesRing collected around the replay
    window. The verdict is machine-readable:

    ``{"scenario", "seed", "passed", "objectives": [{name, objective,
    burn_rate, measured, passed}], "replay": {...}, "fidelity": {...},
    "slo": <full tracker doc>}``

    An objective passes when its fast-window burn rate stays <= 1.0
    (the bad fraction fit the declared budget over the run window). A
    capture-derived scenario additionally requires the round-trip
    fidelity bar: exact mix match + per-decile inter-arrival error
    within ``fidelity_tolerance`` (default 0.10) at speed 1.
    """
    from tpu_dist_nn.obs.slo import SLOTracker
    from tpu_dist_nn.obs.timeseries import TimeSeriesRing

    seed = int(spec.get("seed", 0) if seed is None else seed)
    speed = float(spec.get("speed", 1.0) if speed is None else speed)
    fleet_spec = dict(spec.get("fleet", {}))
    chaos = list(spec.get("chaos", ()))
    for ev in chaos:
        if ev.get("action") == "overload":
            # Overload multiplier: the whole arrival process compressed
            # — an admission-control stressor, applied at setup.
            speed *= float(ev.get("factor", 2.0))
    tol = float(spec.get("fidelity_tolerance", 0.10))

    wl = _build_workload(spec, seed, quick_scale)
    capture_mode = wl is None

    fleet = LoopbackFleet(
        replicas=int(fleet_spec.get("replicas", 2)),
        dim=int(fleet_spec.get("dim", 8)),
        prompt_len=int(fleet_spec.get("prompt_len", 8)),
        max_new_tokens=int(fleet_spec.get("max_new_tokens", 8)),
        vocab_size=int(fleet_spec.get("vocab_size", 64)),
        per_row_ms=float(fleet_spec.get("per_row_ms", 1.0)),
        per_token_ms=float(fleet_spec.get("per_token_ms", 1.0)),
        prefill_ms=float(fleet_spec.get("prefill_ms", 2.0)),
        faults=fleet_spec.get("faults", ()),
        hedge=bool(fleet_spec.get("hedge", False)),
        seed=seed,
        canary=fleet_spec.get("canary"),
        spotcheck=fleet_spec.get("spotcheck"),
    )
    ring = TimeSeriesRing(resolution=0.5, retention=600.0)
    objectives = [_objective_from_spec(o)
                  for o in spec["slo"]["objectives"]]
    verdict: dict = {"scenario": spec["name"], "seed": seed,
                     "speed": round(speed, 3)}
    t_begin = time.monotonic()
    fidelity = None
    timers: list[threading.Timer] = []
    try:
        fleet.start()
        if capture_mode:
            wl, fidelity = _capture_leg(spec, fleet, seed, quick_scale,
                                        tol)
        # Window baseline AFTER any capture leg: the scored deltas
        # cover exactly the replay under chaos, nothing before it.
        ring.collect(now=time.time())
        # Both windows = the whole scored run (<= ring retention): the
        # verdict is "did the budget hold over THIS scenario", not a
        # production multi-window page.
        tracker = SLOTracker(ring, objectives,
                             fast_window=600.0, slow_window=600.0)

        def arm_chaos(_t0):
            for ev in chaos:
                action = ev.get("action")
                if action == "overload":
                    continue
                at = float(ev.get("at", 0.0)) / max(speed, 1e-9)
                idx = int(ev.get("replica", 0))
                fn = {"kill": fleet.kill_replica,
                      "drain": fleet.drain_replica,
                      "undrain": fleet.undrain_replica}.get(action)
                if fn is None:
                    raise ValueError(f"unknown chaos action {action!r}")
                t = threading.Timer(at, fn, args=(idx,))
                t.daemon = True
                t.start()
                timers.append(t)

        stop_tick = threading.Event()

        def tick():
            while not stop_tick.wait(0.5):
                ring.collect(now=time.time())

        ticker = threading.Thread(target=tick, daemon=True)
        ticker.start()
        report = replay(
            wl, fleet.target, speed=speed,
            dim=fleet.dim, prompt_len=fleet.prompt_len,
            vocab_size=fleet.vocab_size,
            timeout=float(spec.get("timeout_s", 15.0)),
            on_start=arm_chaos,
        )
        stop_tick.set()
        ticker.join(timeout=2.0)
        ring.collect(now=time.time())
        slo_doc = tracker.evaluate(now=time.time())
        quarantined = [
            {"target": s["target"], "reason": s.get("quarantine_reason"),
             "strikes": s.get("integrity_strikes", 0)}
            for s in fleet.pool.snapshot() if s["state"] == "quarantined"
        ]
    finally:
        for t in timers:
            t.cancel()
        fleet.stop()
    objs = []
    for o in slo_doc["objectives"]:
        burn = o["windows"]["fast"]["burn_rate"]
        measured = (o["windows"]["fast"].get("measured_quantile_ms")
                    if o["kind"] == "latency"
                    else o["windows"]["fast"].get("measured_availability"))
        objs.append({"name": o["name"], "objective": o["objective"],
                     "burn_rate": burn, "measured": measured,
                     "total": o["windows"]["fast"]["total"],
                     "passed": burn <= 1.0})
    passed = all(o["passed"] for o in objs)
    if fidelity is not None:
        passed = passed and fidelity["passed"]
        verdict["fidelity"] = fidelity
    integ_spec = spec.get("integrity")
    if integ_spec:
        # The corruption cell's teeth: the quarantine choreography must
        # have indicted the right number of replicas — catching the
        # corruption is the objective, not merely surviving it.
        lo = int(integ_spec.get("min_quarantines", 0))
        hi = integ_spec.get("max_quarantines")
        integ_ok = len(quarantined) >= lo and (
            hi is None or len(quarantined) <= int(hi)
        )
        verdict["integrity"] = {
            "quarantined": quarantined,
            "min_quarantines": lo,
            "max_quarantines": hi,
            "passed": integ_ok,
        }
        passed = passed and integ_ok
    elif quarantined:
        verdict["integrity"] = {"quarantined": quarantined}
    verdict.update({
        "passed": passed,
        "duration_s": round(time.monotonic() - t_begin, 3),
        "workload": wl.mix(),
        "replay": report,
        "objectives": objs,
        "slo": slo_doc,
        "faults_fired": sum(p.fired for p in fleet.fault_plans),
    })
    return verdict


def _capture_leg(spec, fleet, seed, quick_scale, tol):
    """The bundle-derived workload: drive the spec's seed generator
    against the live fleet, capture a REAL incident bundle from the
    shared tracer, extract the WorkloadTrace back out of it, and score
    round-trip fidelity (exact mix + per-decile arrival error)."""
    from tpu_dist_nn.obs.incident import capture_bundle
    from tpu_dist_nn.obs.trace import TRACER

    cap = spec["workload"]["capture"]
    args = dict(cap.get("args", {}))
    if quick_scale:
        args = _scale_workload_args(args, quick_scale)
    original = make_workload(cap["generator"], seed=seed, **args)
    cursor = TRACER.chrome_trace(limit=1)["cursor"]
    replay(original, fleet.target, speed=1.0, dim=fleet.dim,
           prompt_len=fleet.prompt_len, vocab_size=fleet.vocab_size)
    # Only spans finished after the cursor: an earlier scenario's
    # traffic in the same process must not leak into this bundle.
    doc = TRACER.chrome_trace(since=cursor)
    _, bundle = capture_bundle(
        "scenario_capture", reason=f"scenario {spec['name']} capture leg",
        tracer=_FrozenTracer(doc),
    )
    extracted = trace_from_bundle(bundle, name=f"{original.name}-replayed")
    mix_ok = extracted.mix() == original.mix()
    errs = decile_errors(original.inter_arrival_deciles(),
                         extracted.inter_arrival_deciles())
    fidelity = {
        "bundle_bytes": len(bundle),
        "mix_match": mix_ok,
        "per_decile_error": [round(e, 4) for e in errs],
        "max_decile_error": round(max(errs), 4) if errs else None,
        "tolerance": tol,
        "passed": bool(mix_ok and errs and max(errs) <= tol),
    }
    return extracted, fidelity


class _FrozenTracer:
    """Duck-typed tracer handing capture_bundle a pre-sliced chrome
    document (the since-cursor slice), so a long-lived process's older
    traffic stays out of the scenario's bundle."""

    def __init__(self, doc):
        self._doc = doc

    def chrome_trace(self, *a, **k):
        return self._doc

    def snapshot(self, *a, **k):
        return []


def run_scenario_file(path: str, *, seed: int | None = None,
                      speed: float | None = None,
                      quick_scale: float | None = None) -> dict:
    return run_scenario(load_scenario(path), seed=seed, speed=speed,
                        quick_scale=quick_scale)


def run_scenario_remote(spec: dict, target: str, *,
                        seed: int | None = None,
                        speed: float | None = None,
                        quick_scale: float | None = None) -> dict:
    """Fire a scenario's WORKLOAD at a live remote fleet — a load-test
    mode, not a scored verdict.

    Everything that makes a scenario a controlled experiment is
    loopback-only and is deliberately NOT applied here: no fault
    injection, no chaos timeline (killing someone's production replica
    from a load driver is not a feature), and no SLO scoring — the
    remote fleet's metrics live in ITS process, so burn rates must be
    read from the target's own ``/metrics``, not synthesized
    client-side. ``passed`` is ``None`` and the report says so in
    ``caveat``; what remains is the replay report — client-observed
    outcomes, latency/TTFT percentiles, and arrival fidelity.

    Capture-mode workloads (``workload.capture``) need the loopback
    fleet's shared tracer and are rejected."""
    seed = int(spec.get("seed", 0) if seed is None else seed)
    speed = float(spec.get("speed", 1.0) if speed is None else speed)
    wl = _build_workload(spec, seed, quick_scale)
    if wl is None:
        raise ValueError(
            f"scenario {spec['name']}: capture-mode workloads need the "
            f"loopback fleet; remote --target replay supports "
            f"generator|trace workloads"
        )
    fleet_spec = dict(spec.get("fleet", {}))
    disabled = sorted(
        k for k in ("chaos", "fleet", "slo", "integrity") if spec.get(k)
    )
    report = replay(
        wl, target, speed=speed,
        dim=int(fleet_spec.get("dim", 8)),
        prompt_len=int(fleet_spec.get("prompt_len", 8)),
        vocab_size=int(fleet_spec.get("vocab_size", 64)),
        timeout=float(spec.get("timeout_s", 15.0)),
    )
    return {
        "scenario": spec["name"], "seed": seed, "speed": round(speed, 3),
        "mode": "remote",
        "target": target,
        "caveat": (
            "remote load-test: fault injection, chaos events, and SLO "
            "scoring are loopback-only and were NOT applied; this "
            "report is the client-observed outcome only — score SLOs "
            "from the target fleet's own /metrics"
        ),
        "disabled": disabled,
        "passed": None,
        "duration_s": report["wall_s"],
        "workload": wl.mix(),
        "replay": report,
    }


def scenario_paths(directory: str) -> list[str]:
    """All scenario specs under ``directory``, sorted for stable run
    order."""
    return sorted(
        os.path.join(directory, f) for f in os.listdir(directory)
        if f.endswith(".json")
    )
