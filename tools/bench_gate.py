#!/usr/bin/env python
"""Bench regression gate: diff the newest BENCH round against its
predecessor and FAIL on a >5% hot-path regression.

The per-round ``BENCH_r*.json`` diffs have existed since round 2 and
caught nothing, because nothing enforced them — host-fed throughput
decayed 233k -> 199k samples/s across r02->r05 with every round green.
This tool is the enforcement half of the perf-attribution layer
(``tpu_dist_nn/obs/profile.py``): it gates the serving hot-path
metrics, and when one regresses it folds the ``/profile`` per-stage
breakdown into the report so the failure names WHERE the time went,
not just that it went.

Gated metrics (docs/PERF.md "Regression gate"):

    host_fed_samples_per_sec        parsed.value                 higher
    device_resident_samples_per_sec parsed.device_resident_...   higher
    serving_rps                     serving.coalesced.rps        higher
    generate_rps                    serving.generate.requests_per_s
                                                                 higher
    generate_ttft_p99_ms            serving.generate.ttft_p99_ms lower
    gen_prefix_rps                  serving.generate_prefix.rps  higher
    gen_prefix_ttft_p99_ms          serving.generate_prefix.ttft_p99_ms
                                                                 lower
    router_rps                      serving.router.rps           higher
    slo_process_p99_ms              serving.slo.latency.measured_p99_ms
                                                                 lower
    slo_availability                serving.slo.availability.measured
                                                                 higher
    incident_armed_ratio            serving.incident_overhead.ratio
                                                                 higher
    autoscale_replica_seconds_ratio serving.autoscale.replica_seconds_ratio
                                                                 lower
    serving_mfu                     serving.goodput.mfu          higher
    serving_pad_ratio               serving.goodput.pad_ratio    lower
    slo_class_critical_p99_ms       serving.slo_classes.critical_p99_ms
                                                                 lower
    gen_stream_ttft_p50_ms          serving.generate_stream.ttft_p50_ms
                                                                 lower

Rules:

* A metric regresses when it moves more than ``--threshold`` (default
  5%) in its BAD direction; improvements never fail.
* A metric absent from either round is skipped (reported as such) —
  older rounds predate some series.
* Rounds from DIFFERENT backends skip the whole gate with exit 0: a
  cpu-fallback round against a real-TPU round is not a regression
  signal, it is a hardware change.
* ``--report-only`` prints the identical report but always exits 0 —
  the mode the quick tier runs against the checked-in r04->r05 pair
  (which carries a real ~10% serving_rps regression; the enforced gate
  exists so the NEXT one cannot land silently).
* ``--history 'BENCH_r*.json'`` gates the current round against the
  BEST historical value of each metric (same-backend rounds only)
  instead of just the previous round. Pairwise diffing is blind to
  slow drift: host-fed throughput lost ~3%/round across r02->r05 —
  under the pairwise 5% threshold every single time — compounding to
  −15% vs the r02 best. Best-of-history is the anti-boiling-frog
  mode: each metric's high-water mark is the bar, so a trajectory of
  individually-green regressions still fails. Invalid/failed rounds
  (r01's error record) are skipped, as are rounds from other backends
  (per-ROUND here, not whole-gate: history legitimately spans a
  backend flap; only same-backend rounds say anything about the
  current one).

Exit codes: 0 pass/skip/report-only, 1 enforced regression, 2 usage.

Usage:
    python tools/bench_gate.py                          # newest pair
    python tools/bench_gate.py --current BENCH_r05.json \\
        --previous BENCH_r04.json
    python tools/bench_gate.py --threshold 0.05 --report-only
    python tools/bench_gate.py --profile http://host:9100/profile
    python tools/bench_gate.py --history 'BENCH_r*.json'  # best-of-history
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import urllib.request

DEFAULT_THRESHOLD = 0.05

# (label, path into the parsed bench doc, direction). "higher" means
# higher is better (throughput); "lower" means lower is better (TTFT).
GATED_METRICS = (
    ("host_fed_samples_per_sec", ("value",), "higher"),
    ("device_resident_samples_per_sec",
     ("device_resident_samples_per_sec",), "higher"),
    ("serving_rps", ("serving", "coalesced", "rps"), "higher"),
    ("generate_rps", ("serving", "generate", "requests_per_s"), "higher"),
    ("generate_ttft_p99_ms", ("serving", "generate", "ttft_p99_ms"),
     "lower"),
    # Shared-prefix workload (prefix cache + chunked prefill ON): the
    # KV-reuse win must not regress once landed. Absent in rounds that
    # predate the section -> per-metric skip.
    ("gen_prefix_rps", ("serving", "generate_prefix", "rps"), "higher"),
    ("gen_prefix_ttft_p99_ms",
     ("serving", "generate_prefix", "ttft_p99_ms"), "lower"),
    # Multi-replica router (controlled-regime 3-replica rps): the
    # fleet's scaling win must not regress once landed. Absent in
    # rounds that predate the section -> per-metric skip.
    ("router_rps", ("serving", "router", "rps"), "higher"),
    # SLO summary block (ISSUE 9): the serving run scored against the
    # fixed p99/availability objectives bench.py declares. Gated like
    # any other family — absent in pre-ISSUE-9 rounds -> per-metric
    # skip; a later round that blows the measured p99 or availability
    # past threshold fails the gate.
    ("slo_process_p99_ms",
     ("serving", "slo", "latency", "measured_p99_ms"), "lower"),
    ("slo_availability",
     ("serving", "slo", "availability", "measured"), "higher"),
    # Flight-recorder overhead (ISSUE 11): armed/disarmed serving rps
    # ratio with no detector firing — must stay ~1.0 (capture is free
    # until it fires). Absent in pre-ISSUE-11 rounds -> per-metric
    # skip.
    ("incident_armed_ratio",
     ("serving", "incident_overhead", "ratio"), "higher"),
    # Fleet autopilot (ISSUE 12): autoscaled / static-peak
    # replica-seconds over the synthetic diurnal load — the capacity
    # bill of holding the SLO, lower is better. Absent in pre-ISSUE-12
    # rounds -> per-metric skip.
    ("autoscale_replica_seconds_ratio",
     ("serving", "autoscale", "replica_seconds_ratio"), "lower"),
    # Goodput plane (ISSUE 14): the serving window's measured MFU
    # (analytic useful FLOPs over resolved peak — higher is better)
    # and its structural-pad FLOP share (bucket pad rows, idle slots —
    # lower is better). Absent in pre-ISSUE-14 rounds -> per-metric
    # skip.
    ("serving_mfu", ("serving", "goodput", "mfu"), "higher"),
    ("serving_pad_ratio", ("serving", "goodput", "pad_ratio"), "lower"),
    # Degradation ladder (ISSUE 15): the critical class's p99 under
    # the 2x mixed-class overload A/B — the latency the SLO pages on
    # when the fleet is saturated, lower is better (the ROADMAP
    # target: holds ~flat while best_effort absorbs the sheds).
    # Absent in pre-ISSUE-15 rounds -> per-metric skip.
    ("slo_class_critical_p99_ms",
     ("serving", "slo_classes", "critical_p99_ms"), "lower"),
    # Streaming plane (ISSUE 16): client-observed streamed TTFT
    # (submit -> first GenerateStream token frame on the wire) through
    # the loopback serving endpoint — the latency streaming exists to
    # surface, lower is better. Absent in pre-ISSUE-16 rounds ->
    # per-metric skip.
    ("gen_stream_ttft_p50_ms",
     ("serving", "generate_stream", "ttft_p50_ms"), "lower"),
    # Scenario matrix (ISSUE 18): fraction of the checked-in
    # scenarios/*.json cells (workload x chaos, SLO-scored by the
    # replay engine) that pass — higher is better; a cell newly
    # failing its SLO verdict shows up here as a ratio drop. Absent
    # in pre-ISSUE-18 rounds -> per-metric skip.
    ("scenario_pass_ratio",
     ("serving", "scenarios", "pass_ratio"), "higher"),
    # Silent-corruption defense plane (ISSUE 19): armed/disarmed
    # serving rps ratio with the numeric guard + spot-checking +
    # canary probes all ON and nothing corrupt — detection must stay
    # ~free (the <5% budget), higher is better. Absent in pre-ISSUE-19
    # rounds -> per-metric skip.
    ("integrity_armed_ratio",
     ("serving", "integrity_overhead", "ratio"), "higher"),
)

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def load_round(path: str) -> dict:
    """A BENCH_r*.json's parsed payload (the driver wraps the bench
    JSON line under "parsed"; a bare bench dump is accepted too)."""
    with open(path) as f:
        doc = json.load(f)
    parsed = doc.get("parsed") if isinstance(doc, dict) else None
    if isinstance(parsed, dict):
        return parsed
    if isinstance(doc, dict) and "value" in doc:
        return doc
    raise ValueError(f"{path}: not a BENCH round (no 'parsed' payload)")


def find_rounds(bench_dir: str) -> list[tuple[int, str]]:
    out = []
    for name in os.listdir(bench_dir):
        m = _ROUND_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(bench_dir, name)))
    return sorted(out)


def resolve_pair(args) -> tuple[str, str]:
    """(current_path, previous_path) from flags or discovery: newest
    round in --dir, previous from its recorded ``prev_bench.file`` (the
    lineage the bench itself wrote) else the next-lower round file."""
    if args.current and args.previous:
        return args.current, args.previous
    rounds = find_rounds(args.dir)
    if args.current:
        cur_path = args.current
    else:
        # With an explicit --previous only the current round needs
        # discovery; without one the previous must be discoverable too.
        need = 1 if args.previous else 2
        if len(rounds) < need:
            raise FileNotFoundError(
                f"need {need} BENCH_r*.json round(s) in {args.dir!r} "
                f"(found {len(rounds)})"
            )
        cur_path = rounds[-1][1]
    if args.previous:
        return cur_path, args.previous
    cur = load_round(cur_path)
    prev_name = (cur.get("prev_bench") or {}).get("file")
    if prev_name:
        prev_path = os.path.join(args.dir, prev_name)
        if os.path.exists(prev_path):
            return cur_path, prev_path
    m = _ROUND_RE.search(os.path.basename(cur_path))
    if m:
        below = [p for n, p in rounds if n < int(m.group(1))]
        if below:
            return cur_path, below[-1]
    raise FileNotFoundError(
        f"no previous round found for {cur_path!r} (pass --previous)"
    )


def _dig(doc: dict, path: tuple) -> float | None:
    node = doc
    for key in path:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    try:
        return float(node)
    except (TypeError, ValueError):
        return None


def compare(prev: dict, cur: dict,
            threshold: float = DEFAULT_THRESHOLD) -> dict:
    """The gate verdict for one round pair.

    Returns ``{"skipped": reason}`` on a backend mismatch, else
    ``{"metrics": [...], "regressions": [labels]}`` where each metric
    row carries prev/cur/regression fraction (positive = worse) or a
    per-metric skip reason.
    """
    prev_backend = str(prev.get("backend"))
    cur_backend = str(cur.get("backend"))
    if prev_backend != cur_backend:
        return {
            "skipped": (
                f"backend changed between rounds ({prev_backend!r} -> "
                f"{cur_backend!r}); cross-backend deltas are hardware "
                "changes, not regressions"
            ),
        }
    metrics = []
    regressions = []
    for label, path, direction in GATED_METRICS:
        p, c = _dig(prev, path), _dig(cur, path)
        if p is None or c is None:
            metrics.append({
                "metric": label,
                "skipped": "absent in "
                + ("both rounds" if p is None and c is None
                   else "previous round" if p is None else "current round"),
            })
            continue
        if p <= 0:
            metrics.append({
                "metric": label,
                "skipped": f"previous value not positive ({p})",
            })
            continue
        # regression fraction: positive = moved the BAD way.
        reg = (p - c) / p if direction == "higher" else (c - p) / p
        row = {
            "metric": label, "previous": p, "current": c,
            "direction": direction, "regression": round(reg, 4),
            "failed": reg > threshold,
        }
        metrics.append(row)
        if row["failed"]:
            regressions.append(label)
    return {"metrics": metrics, "regressions": regressions,
            "threshold": threshold, "backend": cur_backend}


def resolve_history(args) -> tuple[str, list[tuple[str, dict]]]:
    """(current_path, [(name, parsed), ...]) for ``--history`` mode.

    The glob expands relative to ``--dir``; the current round is
    ``--current`` (or the highest-numbered match), history is every
    OTHER lower-numbered valid round. A ``--current`` whose name does
    not parse as a round number (a fresh un-numbered local run) is
    gated against EVERY matched round — for a fresh run the whole
    checked-in history IS the bar; a stderr note says so, since gating
    an old commit's fresh bench against a glob holding newer rounds
    would otherwise silently include the future. Rounds that fail to
    load or carry no payload (a failed round's error record — r01)
    are skipped with a stderr note, never fatal: the round after a
    failure is exactly when the gate matters.
    """
    import glob as _glob

    pattern = args.history
    if not os.path.isabs(pattern) and os.path.dirname(pattern) == "":
        pattern = os.path.join(args.dir, pattern)
    matches = []
    for p in _glob.glob(pattern):
        m = _ROUND_RE.search(os.path.basename(p))
        if m:
            matches.append((int(m.group(1)), p))
    matches.sort()
    if not matches:
        raise FileNotFoundError(f"no BENCH_r*.json match {pattern!r}")
    if args.current:
        cur_path = args.current
        m = _ROUND_RE.search(os.path.basename(cur_path))
        cur_round = int(m.group(1)) if m else None
        if cur_round is None:
            print(
                f"# --current {cur_path!r} is not a numbered round; "
                "gating it against EVERY round in the glob (make sure "
                "none postdates the build under test)",
                file=sys.stderr,
            )
    else:
        cur_round, cur_path = matches[-1]
    history = []
    for n, p in matches:
        if os.path.abspath(p) == os.path.abspath(cur_path):
            continue
        if cur_round is not None and n >= cur_round:
            continue
        try:
            history.append((os.path.basename(p), load_round(p)))
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"# skipping invalid round {p}: {e}", file=sys.stderr)
    if not history:
        raise FileNotFoundError(
            f"no valid historical rounds behind {cur_path!r} in {pattern!r}"
        )
    return cur_path, history


def compare_history(history: list[tuple[str, dict]], cur: dict,
                    threshold: float = DEFAULT_THRESHOLD) -> dict:
    """Best-of-history verdict: each gated metric regresses when the
    current value is more than ``threshold`` past the BEST same-backend
    historical value in its bad direction (max for higher-is-better,
    min for lower-is-better). Compounding sub-threshold drift therefore
    fails against the high-water mark even though every pairwise diff
    stayed green. Metric rows carry ``best``/``best_round``."""
    cur_backend = str(cur.get("backend"))
    usable = [(name, doc) for name, doc in history
              if str(doc.get("backend")) == cur_backend]
    if not usable:
        return {
            "skipped": (
                f"no historical rounds share the current backend "
                f"({cur_backend!r}); cross-backend deltas are hardware "
                "changes, not regressions"
            ),
        }
    metrics = []
    regressions = []
    for label, path, direction in GATED_METRICS:
        c = _dig(cur, path)
        hist_vals = [(name, _dig(doc, path)) for name, doc in usable]
        hist_vals = [(name, v) for name, v in hist_vals
                     if v is not None and v > 0]
        if c is None or not hist_vals:
            metrics.append({
                "metric": label,
                "skipped": (
                    "absent in current round" if c is None
                    else "absent (or not positive) in every same-backend "
                         "historical round"
                ),
            })
            continue
        pick = max if direction == "higher" else min
        best_round, best = pick(hist_vals, key=lambda nv: nv[1])
        reg = (best - c) / best if direction == "higher" else (c - best) / best
        row = {
            "metric": label, "previous": best, "current": c,
            "best_round": best_round, "direction": direction,
            "regression": round(reg, 4), "failed": reg > threshold,
        }
        metrics.append(row)
        if row["failed"]:
            regressions.append(label)
    return {"metrics": metrics, "regressions": regressions,
            "threshold": threshold, "backend": cur_backend,
            "mode": "best-of-history",
            "history_rounds": [name for name, _ in usable]}


def load_profile(source: str | None) -> dict | None:
    """A /profile breakdown for attribution: an http(s) URL (a live
    ``--metrics-port`` endpoint), a JSON file path, or None. Fetch
    failures degrade to None — attribution is garnish, the verdict
    never depends on it."""
    if not source:
        return None
    try:
        if source.startswith(("http://", "https://")):
            with urllib.request.urlopen(source, timeout=5.0) as resp:
                return json.loads(resp.read())
        with open(source) as f:
            return json.load(f)
    except Exception as e:  # noqa: BLE001 — attribution is best-effort
        print(f"# profile attribution unavailable ({e!r})", file=sys.stderr)
        return None


def attribution_lines(profile: dict | None) -> list[str]:
    """Top stage shares per method — where the regressed time goes."""
    if not profile:
        return []
    lines = ["where the time goes (/profile stage shares):"]
    for method in sorted(profile.get("methods", {})):
        m = profile["methods"][method]
        tops = ", ".join(
            f"{s['stage']} {s['share'] * 100:.1f}% "
            f"(p99 {s['p99_s'] * 1e3:.2f}ms)"
            for s in m.get("stages", ())[:4]
        )
        lines.append(
            f"  {method}: {m.get('traces', 0)} traces — {tops}"
        )
    return lines


def lint_status_line() -> str:
    """One-line tdnlint verdict for the report header: regression
    reports and invariant drift surface in one place. Fail-safe — a
    missing or broken analyzer reports itself, never breaks the gate."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        # tdnlint lives right next to this script: a plain import with
        # tools/ on the path (sys.modules dedupes against any loader
        # that registered the package first).
        if here not in sys.path:
            sys.path.insert(0, here)
        import tdnlint

        target = os.path.join(os.path.dirname(here), "tpu_dist_nn")
        result = tdnlint.run_lint(
            [target], baseline_path=tdnlint.DEFAULT_BASELINE
        )
        new = len(result["new"])
        if new:
            return (f"lint: {new} non-baselined finding"
                    f"{'s' if new != 1 else ''} — run `tdn lint` "
                    "(docs/STATIC_ANALYSIS.md)")
        return (f"lint: clean ({len(result['baselined'])} baselined, "
                f"{result['suppressed_total']} suppressed)")
    except Exception as e:  # noqa: BLE001 — the gate must keep gating
        return f"lint: unavailable ({e!r})"


def render_report(verdict: dict, cur_path: str, prev_path: str,
                  profile: dict | None = None,
                  report_only: bool = False,
                  lint_status: str | None = None) -> str:
    lines = [
        f"bench gate: {os.path.basename(prev_path)} -> "
        f"{os.path.basename(cur_path)}"
        + (" [report-only]" if report_only else ""),
    ]
    if lint_status:
        lines.append(lint_status)
    if "skipped" in verdict:
        lines.append(f"SKIP: {verdict['skipped']}")
        return "\n".join(lines)
    lines.append(
        f"backend: {verdict['backend']}  threshold: "
        f"{verdict['threshold'] * 100:.0f}%"
    )
    for row in verdict["metrics"]:
        if "skipped" in row:
            lines.append(f"  SKIP {row['metric']:<34} {row['skipped']}")
            continue
        arrow = "v" if row["regression"] > 0 else "^"
        mark = "FAIL" if row["failed"] else " ok "
        best = (
            f"  (best: {row['best_round']})" if row.get("best_round") else ""
        )
        lines.append(
            f"  {mark} {row['metric']:<34} {row['previous']:>12.1f} -> "
            f"{row['current']:>12.1f}  {arrow}{abs(row['regression']) * 100:.1f}%"
            f"{best}"
        )
    if verdict["regressions"]:
        lines.append(
            f"REGRESSED past {verdict['threshold'] * 100:.0f}%: "
            + ", ".join(verdict["regressions"])
        )
        lines.extend(attribution_lines(profile))
        if not profile:
            lines.append(
                "  (no /profile attribution attached — rerun with "
                "--profile <url-or-json> against a serving run to see "
                "which stage ate the time)"
            )
    else:
        lines.append("all gated metrics within threshold")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail a PR that regresses the serving hot path >5% "
                    "between BENCH rounds",
    )
    ap.add_argument("--current", help="current round BENCH_r*.json "
                                      "(default: newest in --dir)")
    ap.add_argument("--previous",
                    help="previous round (default: the current round's "
                         "recorded prev_bench.file, else next-lower round)")
    ap.add_argument("--history", default=None, metavar="GLOB",
                    help="gate the current round against the BEST same-"
                         "backend historical value of each metric across "
                         "every round matching GLOB (e.g. 'BENCH_r*.json'; "
                         "relative patterns expand under --dir) — catches "
                         "sub-threshold drift that compounds across rounds")
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_r*.json (default .)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="regression fraction that fails the gate "
                         "(default 0.05 = 5%%)")
    ap.add_argument("--report-only", action="store_true",
                    help="print the identical report but always exit 0 "
                         "(the known-regressed-pair mode)")
    ap.add_argument("--profile", default=None,
                    help="a /profile URL or saved JSON for per-stage "
                         "attribution on failure")
    ap.add_argument("--json", action="store_true",
                    help="also print the machine verdict as one JSON line")
    args = ap.parse_args(argv)
    if not 0 < args.threshold < 1:
        print(f"error: --threshold must be in (0, 1), got {args.threshold}",
              file=sys.stderr)
        return 2
    try:
        if args.history:
            if args.previous:
                print("error: --history and --previous are exclusive "
                      "(best-of-history picks its own bar)", file=sys.stderr)
                return 2
            cur_path, history = resolve_history(args)
            cur = load_round(cur_path)
            verdict = compare_history(history, cur, args.threshold)
            prev_path = f"best-of-{len(history)}-rounds"
        else:
            cur_path, prev_path = resolve_pair(args)
            cur, prev = load_round(cur_path), load_round(prev_path)
            verdict = compare(prev, cur, args.threshold)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    # Attribution source priority: an explicit --profile (live /profile
    # endpoint or saved JSON), else the breakdown bench.py embeds in
    # the current round's serving section.
    profile = load_profile(args.profile) or (
        (cur.get("serving") or {}).get("profile")
    )
    print(render_report(
        verdict, cur_path, prev_path, profile,
        report_only=args.report_only,
        # The lint header rides report-only mode (the PR-report/CI
        # summary path); enforce mode stays a pure perf verdict.
        lint_status=lint_status_line() if args.report_only else None,
    ))
    if args.json:
        print(json.dumps({
            "current": os.path.basename(cur_path),
            "previous": os.path.basename(prev_path),
            "report_only": args.report_only,
            **verdict,
        }))
    if verdict.get("regressions") and not args.report_only:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
