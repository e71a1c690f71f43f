"""Driver for served generation: `serve_lm_generate(scheduler="continuous")`
behind loopback gRPC, loaded by the generator child (harness/loadgen.py).

The mix (traffic/<name>.json, overridden key by key by the cell's own
`params`) gives: prompt_len, max_new_tokens (the endpoint's), slots,
prefill_chunk, prefix_cache_blocks, arrivals, lengths, prefix,
check_requests, trace_seconds.

Set-up: generator child started (it imports no JAX), weights made on
the device from the seed in the configuration's `param_dtype`, as the
program's own entry (`tdn lm --serve-generate`) holds them, server
started and warmed, slots filled once.  The window opens when every slot has been filled
once and the first request has retired; it lasts --seconds.  After it:
peak memory read, server stopped and freed, and a seeded sample of the
requests that finished inside the window compared with the plain
reference (see `check`).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmark.harness import device as dev
from benchmark.harness import lookup, xplane
from benchmark.harness.counts import Gpt2Counts
from benchmark.harness.program import transformer_config
from benchmark.harness.result import within
from benchmark.harness.stats import StreamWindow, percentile

KIND = "serve"
FILL_TIMEOUT_S = 240  # the slots fill in 144 iterations at the most


def before_backend(cell, args):
    """Start the generator child before JAX takes the chip."""
    p = cell.params
    tmp = lookup.tmp_dir()
    os.makedirs(tmp, exist_ok=True)
    spec = {
        "root": lookup.ROOT, "seed": args.seed,
        "prompt_len": p["prompt_len"], "max_new_tokens": p["max_new_tokens"],
        "vocab_size": cell.config["vocab_size"],
        "arrivals": _arrivals(p), "lengths": p["lengths"],
        "prefix": p.get("prefix"), "out": os.path.join(tmp, "records.json"),
    }
    spec_path = os.path.join(tmp, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # a pure client: no chip
    child = subprocess.Popen(
        [sys.executable, os.path.join(lookup.BENCH_DIR, "harness",
                                      "loadgen.py"), spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        cwd=lookup.ROOT)
    return {"child": child, "spec": spec, "tmp": tmp}


def _end(child):
    if child.poll() is None:
        child.kill()
    child.wait()
    for pipe in (child.stdin, child.stdout):
        if pipe and not pipe.closed:
            pipe.close()


def after(early):
    """Stop the generator if it still runs; drop the run's scratch files."""
    if early:
        _end(early["child"])
        shutil.rmtree(early["tmp"], ignore_errors=True)


def _arrivals(p: dict) -> dict:
    a = dict(p["arrivals"])
    if a["mode"] == "closed" and a.get("clients") == "per_slot":
        a["clients"] = int(p["slots"])
    return a


def _expect(child, word: str, timeout: float):
    """Read the child's lines until `word`; it prints nothing else."""
    import select

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if child.poll() is not None:
            raise RuntimeError(f"load generator died waiting for {word!r}")
        r, _, _ = select.select([child.stdout], [], [], 0.25)
        if r:
            line = child.stdout.readline().strip()
            if line == word:
                return
    raise RuntimeError(f"load generator never said {word!r}")


def _cpu_seconds(pid: int) -> float:
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def start_server(run, params):
    """The system under test, as `tdn lm --serve-generate` starts it."""
    from tpu_dist_nn.serving.server import serve_lm_generate

    p = run.params
    clients = _arrivals(p).get("clients") or 4 * int(p["slots"])
    server, port = serve_lm_generate(
        params, transformer_config(run.config), 0,
        max_new_tokens=int(p["max_new_tokens"]),
        prompt_len=int(p["prompt_len"]), temperature=0.0,
        host="127.0.0.1", max_workers=int(clients) + 16, warm_rows=1,
        scheduler="continuous", gen_slots=int(p["slots"]),
        prefix_cache_blocks=int(p.get("prefix_cache_blocks", 0)),
        prefill_chunk=p.get("prefill_chunk"))
    return server, port


def _counters(sched) -> dict:
    return {k: int(getattr(sched, k)) for k in (
        "steps_total", "slot_steps_total", "prefill_chunks_total",
        "retired_total", "rows_total", "prefix_hits_total",
        "prefix_misses_total")}


def run(run_, early):
    import jax

    args, p, cfg = run_.args, run_.params, run_.config
    child = early["child"]
    split = run_.setup_split
    run_.counts = Gpt2Counts(cfg)
    try:
        t0 = time.monotonic()
        params = run_.cell.reference.make_weights(
            cfg, args.seed, cfg["param_dtype"])
        jax.block_until_ready(params)
        split["weights_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        server, port = start_server(run_, params)
        del params  # the program holds what it serves from
        sched = server.scheduler
        split["server_start_and_warm_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        _expect(child, "ready", 120)
        child.stdin.write(f"go 127.0.0.1:{port}\n")
        child.stdin.flush()
        slots = int(p["slots"])
        deadline = time.monotonic() + FILL_TIMEOUT_S
        chunk = p.get("prefill_chunk") or int(p["prompt_len"])
        fills = slots * -(-int(p["prompt_len"]) // int(chunk))
        while not (sched.prefill_chunks_total >= fills
                   and sched.retired_total >= 1):
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"slots never filled: chunks {sched.prefill_chunks_total} retired "
                    f"{sched.retired_total} of {slots}")
            time.sleep(0.02)
        split["slot_fill_s"] = time.monotonic() - t0

        # ------------------------------------------------ the window
        t_open = time.monotonic()
        run_.open_window(t_open)
        before, cpu0 = _counters(sched), _cpu_seconds(child.pid)
        t_close = t_open + args.seconds
        trace_dir = None
        if args.trace:
            trace_dir = os.path.join(early["tmp"], "trace")
            time.sleep(min(1.0, args.seconds / 4))
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(min(float(p.get("trace_seconds", 4)),
                               max(0.5, t_close - time.monotonic() - 1)))
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t_close - time.monotonic()))
        after, cpu1 = _counters(sched), _cpu_seconds(child.pid)
        t_closed = time.monotonic()
        run_.window_s = args.seconds
        run_.counters = {k: after[k] - before[k] for k in after}
        run_.counters["slots"] = slots
        run_.loadgen_cpu_s = cpu1 - cpu0

        child.stdin.write("stop\n")
        child.stdin.flush()
        _expect(child, "written", 150)
        child.wait(timeout=30)
        run_.memory_peak_bytes = dev.memory_peak_bytes()
        server.stop(0).wait(30)
        del server, sched
        dev.free_device()
    finally:
        _end(child)

    with open(early["spec"]["out"]) as f:
        got = json.load(f)
    records = got["records"]
    win = StreamWindow(records, t_open, t_close)
    run_.client, run_.records = win, records
    run_.attempted, run_.failed = win.sent, win.failed + got["stuck_clients"]
    run_.note(ttft_ms={f"p{q}": round(1e3 * percentile(win.ttft_s, q), 1)
                       for q in (5, 25, 50, 75, 95)} if win.ttft_s else {},
              itl_ms={f"p{q}": round(1e3 * percentile(win.gaps_s, q), 1)
                      for q in (5, 25, 50, 75, 95, 99)} if win.gaps_s else {})
    run_.note(stalls=stalls(records, t_open, t_close))
    run_.note(window={"open": t_open, "close": t_close,
                      "closed_late_s": t_closed - t_close,
                      "requests_sent": win.sent, "finished": win.finished,
                      "tokens": win.tokens, "gaps": len(win.gaps_s),
                      "stuck_clients": got["stuck_clients"]},
              counters=run_.counters, setup_split=split,
              setup_s=run_.setup_s)
    if trace_dir:
        run_.trace = xplane.reduce_trace(trace_dir, "bench.window")
    t0 = time.monotonic()
    check(run_, records, t_open, t_close)
    run_.note(comparison_s=time.monotonic() - t0)


def stalls(records, t_open, t_close) -> dict:
    """Iterations as the clients saw them: bursts of tokens, and the
    longest times between bursts with when they fell in the window."""
    times = sorted(t for r in records for t in r["tokens"]
                   if t_open <= t < t_close)
    starts = [b for a, b in zip(times, times[1:]) if b - a > 0.05]
    steps = [(b - a, a - t_open) for a, b in zip(starts, starts[1:])]
    if not steps:
        return {}
    med = sorted(s for s, _ in steps)[len(steps) // 2]
    worst = sorted(steps, reverse=True)[:5]
    return {"iterations": len(steps), "median_ms": round(1e3 * med, 1),
            "over_1.5x_median": sum(s > 1.5 * med for s, _ in steps),
            "longest_ms_at_s": [[round(1e3 * s, 1), round(at, 2)]
                                for s, at in worst]}


def sample_finished(records, t_open, t_close, seed: int, n: int) -> list:
    """A seeded sample of the requests that finished inside the window,
    the longest among them."""
    done = [r for r in records if r["ok"] and r["done"] is not None
            and t_open <= r["done"] < t_close and r["ids"]]
    done.sort(key=lambda r: r["id"])
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["ids"]), -r["id"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 23])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def served_rows(sample, prompt_len: int, width: int):
    """(rows (B, width) int32 padded with 0, lengths (B,))."""
    rows = np.zeros((len(sample), width), np.int32)
    lens = np.zeros(len(sample), np.int64)
    for i, r in enumerate(sample):
        n = min(len(r["ids"]), width - prompt_len)
        rows[i, :prompt_len] = r["prompt"]
        rows[i, prompt_len:prompt_len + n] = r["ids"][:n]
        lens[i] = n
    return rows, lens


def gaps_of(reference, cfg, seed, sample, prompt_len, max_new, quant=None,
            block: int = 4) -> dict:
    """The reference over each sampled prompt with its served tokens:
    per served token, how far its reference logit lies below the
    reference's best (`served`), and with `quant` the same for the
    token the lower precision puts first (`control`)."""
    import jax

    params = reference.make_weights(cfg, seed, "float32")
    rows, lens = served_rows(sample, prompt_len, prompt_len + max_new)
    served, control = [], []
    for i in range(0, len(rows), block):
        part = rows[i:i + block]
        pad = block - len(part)
        if pad:
            part = np.concatenate([part, np.zeros((pad, rows.shape[1]),
                                                  np.int32)])
        out = reference.served_gaps(params, part, cfg, prompt_len, quant)
        for j in range(block - pad):
            n = int(lens[i + j])
            served.append(out["gap_served"][j, :n])
            if quant is not None:
                control.append(out["gap_control"][j, :n])
    del params
    jax.clear_caches()
    return {"served": served, "control": control}


def compared_with(limits: dict, mean, wrong_len, bad_ids, failed) -> dict:
    """The numbers that decide `correct`, each beside its limit."""
    return {
        "served_logit_gap_mean": {
            "value": mean, "limit": limits.get("served_logit_gap_mean")},
        "wrong_length": {"value": wrong_len, "limit": 0},
        "ids_out_of_range": {"value": bad_ids, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }


def check(run_, records, t_open, t_close):
    """`correct`: over the sampled served tokens, the mean distance of
    the served token's reference logit below the reference's best at its
    position is within the cell's limit, every finished request holds
    the tokens it asked for, and none failed.  With `--control` each
    lower precision is put in the program's place and judged by the
    same limits."""
    p, cfg = run_.params, run_.config
    limits = run_.cell.own.get("limits", {})
    sample = sample_finished(records, t_open, t_close, run_.args.seed,
                             int(p.get("check_requests", 12)))
    if not sample:
        run_.compared = {"no_finished_request": {"value": 1, "limit": 0}}
        run_.correct = False
        return
    gaps = gaps_of(run_.cell.reference, cfg, run_.args.seed, sample,
                   int(p["prompt_len"]), int(p["max_new_tokens"]))
    every = np.concatenate(gaps["served"])
    cap = int(p["max_new_tokens"])
    wrong_len = sum(1 for r in records if r["ok"] and r["done"] is not None
                    and len(r["ids"]) != min(r["want"], cap))
    bad_ids = sum(1 for r in sample for t in r["ids"]
                  if not 0 <= t < int(cfg["vocab_size"]))
    run_.compared = compared_with(limits, float(every.mean()), wrong_len,
                                  bad_ids, run_.failed)
    run_.note(check=dict(gap_stats(every), requests=len(sample),
                         longest=int(max(len(g) for g in gaps["served"]))))
    for control in (getattr(run_.args, "control", None) or "").split(","):
        if not control:
            continue
        low = np.concatenate(gaps_of(
            run_.cell.reference, cfg, run_.args.seed, sample,
            int(p["prompt_len"]), int(p["max_new_tokens"]),
            quant=control)["control"])
        held = compared_with(limits, float(low.mean()), wrong_len, bad_ids,
                             run_.failed)
        run_.note(control=dict(gap_stats(low), precision=control,
                               compared=held, correct=within(held)))
    run_.correct = within(run_.compared)


def gap_stats(gaps) -> dict:
    return {"tokens": len(gaps), "widest_gap": float(gaps.max()),
            "mean_gap": float(gaps.mean()),
            "rms_gap": float(np.sqrt((gaps ** 2).mean())),
            "not_best_share": float((gaps > 0).mean())}
