"""Capture a served decode cell the operator's way, under the benchmark's load.

    chiprun -- python3 tools/capture_served.py <cell> --seed <n> --out <dir>

Serves the cell's configuration at its sizes exactly as the benchmark's
serve driver does (same weights from the seed, same `serve_lm_generate`
call, same load generator child), adds the metrics endpoint an operator
would have (`--metrics-port`), lets the load run, and then pulls over
HTTP what an operator would pull:

    GET /debug/profile?seconds=N   -> <out>/profile.zip (feed it to
                                      tools/trace_gaps.py)
    GET /trace                     -> <out>/trace.json
    GET /metrics                   -> <out>/metrics.txt (tdn_gen_* only)

and prints one JSON line about the request traces: how many spans are
named `decode.step`, and whether the longest-lived finished request
still holds its `queue_wait`, `prefill` and `decode` spans.  It edits and
measures nothing of the benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _get(port: int, path: str, timeout: float = 120.0) -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.read()


def trace_summary(doc: dict) -> dict:
    """What the acceptance asks of a `/trace` dump."""
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_trace: dict[str, list] = {}
    for e in spans:
        by_trace.setdefault(e["args"]["trace_id"], []).append(e)
    roots = [e for e in spans if e["name"].startswith("rpc.")]
    oldest = max(roots, key=lambda e: e["dur"], default=None)
    mine = by_trace[oldest["args"]["trace_id"]] if oldest else []
    names = sorted({e["name"] for e in mine})
    # The scheduler's `decode` span (the wire codec's has the same name
    # and no ride attributes).
    decode = next((e["args"] for e in mine if e["name"] == "decode"
                   and "iter_first" in e["args"]), None)
    return {
        "spans": len(spans), "traces": len(by_trace),
        "decode.step_spans": sum(e["name"] == "decode.step" for e in spans),
        "finished_roots": len(roots),
        "longest_root_s": oldest["dur"] / 1e6 if oldest else None,
        "longest_root_span_names": names,
        "longest_root_decode_attrs": decode,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--load-seconds", type=float, default=45.0,
                    help="load after the slots have filled, before the pulls")
    ap.add_argument("--profile-seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    args.t_start = time.monotonic()

    from benchmark.harness import lookup
    from benchmark.harness.result import Run

    cell = lookup.Cell(args.cell)
    driver = cell.driver()
    early = driver.before_backend(cell, args)
    child = early["child"]
    os.makedirs(args.out, exist_ok=True)
    try:
        import jax

        from benchmark.harness import device as dev
        from tpu_dist_nn.obs import RuntimeSampler, start_http_server

        dev.enable_compile_cache(ROOT)
        report = dev.require_chips(cell.chips, allow_cpu=bool(args.rehearse))
        run = Run(cell, args, report)
        params = cell.reference.make_weights(
            cell.config, args.seed, cell.config["param_dtype"])
        jax.block_until_ready(params)
        server, port = driver.start_server(run, params)
        del params
        sched = server.scheduler
        sampler = RuntimeSampler()
        sampler.add_generation_scheduler(sched)
        http = start_http_server(0, host="127.0.0.1")
        driver._expect(child, "ready", 120)
        child.stdin.write(f"go 127.0.0.1:{port}\n")
        child.stdin.flush()
        slots = int(cell.params["slots"])
        deadline = time.monotonic() + driver.FILL_TIMEOUT_S
        while not (sched.prefill_chunks_total >= slots
                   and sched.retired_total >= 1):
            if time.monotonic() > deadline:
                raise RuntimeError("slots never filled")
            time.sleep(0.05)
        time.sleep(args.load_seconds)

        body = _get(http.port,
                    f"/debug/profile?seconds={args.profile_seconds}")
        with open(os.path.join(args.out, "profile.zip"), "wb") as f:
            f.write(body)
        trace = _get(http.port, "/trace")
        with open(os.path.join(args.out, "trace.json"), "wb") as f:
            f.write(trace)
        sampler.sample_once()
        metrics = [ln for ln in _get(http.port, "/metrics").decode()
                   .splitlines() if ln.startswith("tdn_gen_")]
        with open(os.path.join(args.out, "metrics.txt"), "w") as f:
            f.write("\n".join(metrics) + "\n")
        print(json.dumps({"cell": args.cell, "device": report,
                          "profile_zip_bytes": len(body),
                          "trace": trace_summary(json.loads(trace)),
                          "loop_totals": sched.loop_totals()}), flush=True)
        child.stdin.write("stop\n")
        child.stdin.flush()
        driver._expect(child, "written", 150)
        http.close()
        server.stop(0).wait(30)
    finally:
        driver.after(early)
    return 0


if __name__ == "__main__":
    sys.exit(main())
