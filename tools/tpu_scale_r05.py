"""Round-5 TPU scale suite: close the real-workload MFU gap.

VERDICT r4 item 1: the synthetic dense step reached 0.9651 MFU while
the 85M LM trained at ~0.21 — with the suspects named (per-step host
dispatch, non-donated f32 master params re-allocated every step, XLA
attention below the flash crossover). Round 5 landed the fixes in the
trainer (``--steps-per-call`` K-step lax.scan superbatches; donated
(params, opt_state) buffers — train/lm_trainer.py); this runner is the
hardware half: on a TPU host it

1. re-runs the 85M config (d768/h12/L12, seq 1024, bf16+remat) with
   steps-per-call 1 vs 10 — the dispatch-overhead A/B — and computes
   steady-state model-flops MFU from the metrics JSONL, whose
   per-entry ``seconds`` follow a fetch of that entry's loss, so they
   cover finished device work;
2. captures a short profiler trace of the same step;
3. re-derives the 25.5M config (d512/h8/L8, seq 512) on the NEW 8 MB
   corpus — the first scale run with a VALID held-out perplexity
   (r4's eval degenerated: 12 rows < batch 16 on the 238 KB corpus);
4. runs the queued seq-8192 long-context config (flash-attention
   training path, T >= FLASH_MIN_SEQ).

Every leg is a bounded subprocess of the REAL CLI (``tdn lm``) with
``--platform tpu``, which fails instead of running on the host CPU
when there is no chip. This parent never imports JAX: a chip belongs to
one process at a time, and the legs need it. Writes
``artifacts/tpu_scale_r05/{metrics_*.jsonl, RECORD.json, trace_85m/}``.

MFU accounting: model flops/step = 6*N*tokens + 12*L*B*T^2*d (attention, fwd+bwd
triple-count), peak = 197 TF bf16 (v5e).

Usage: python tools/tpu_scale_r05.py [--skip-8k] [--budget 1800]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "artifacts", "tpu_scale_r05")
PEAK_TFLOPS_V5E = 197.0


def _now() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ"
    )


def _run_cli(args: list[str], timeout: float,
             extra_env: dict | None = None) -> tuple[int, str, str]:
    cmd = [sys.executable, "-m", "tpu_dist_nn.cli", "--platform", "tpu",
           "lm"] + args
    env = dict(os.environ, **(extra_env or {}))
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO,
            env=env,
        )
        return out.returncode, out.stdout, out.stderr
    except subprocess.TimeoutExpired as e:
        return 124, str(e.stdout or ""), str(e.stderr or "")


def _read_history(path: str) -> list[dict]:
    rows = []
    try:
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                if "step" in rec and "seconds" in rec:
                    rows.append(rec)
    except OSError:
        pass
    return rows


def _final_report(path: str) -> dict | None:
    try:
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                if "final_report" in rec:
                    return rec["final_report"]
    except OSError:
        pass
    return None


def steady_state(history: list[dict], skip_frac: float = 0.45) -> dict | None:
    """s/step between the first post-warmup entry and the last.

    Entries' ``seconds`` are value-fetch barriers (each fetched its
    loss), so deltas between them are honest wall time.
    """
    if len(history) < 3:
        return None
    j = max(1, int(len(history) * skip_frac))
    a, b = history[j], history[-1]
    dsteps = b["step"] - a["step"]
    if dsteps <= 0 or b["seconds"] <= a["seconds"]:
        return None
    return {
        "from_step": a["step"], "to_step": b["step"],
        "seconds": round(b["seconds"] - a["seconds"], 4),
        "s_per_step": round((b["seconds"] - a["seconds"]) / dsteps, 6),
    }


def model_flops_per_step(n_params: int, batch: int, seq: int, d_model: int,
                         n_layers: int) -> float:
    tokens = batch * seq
    return 6.0 * n_params * tokens + 12.0 * n_layers * batch * seq**2 * d_model


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=2700.0,
                    help="overall wall budget (s); later legs are "
                         "skipped when exceeded (sized for 3 85M arms "
                         "+ trace + 25.5M + seq-8192 with cold "
                         "compiles)")
    ap.add_argument("--skip-8k", action="store_true")
    ap.add_argument("--steps-85m", type=int, default=220)
    args = ap.parse_args()
    os.makedirs(ART, exist_ok=True)
    t_start = time.monotonic()
    record: dict = {
        "date": _now(), "round": 5,
        "task": "real-workload MFU (VERDICT r4 item 1): 85M LM with "
                "donated buffers + steps-per-call superbatches, "
                "25.5M re-derivation with VALID held-out eval on the "
                "8 MB corpus, queued seq-8192 long-context run",
        "corpus": "tpu_dist_nn/data/corpus/realtext_corpus.txt "
                  "(8.0 MB, realtext_manifest.json)",
        "peak_tflops": PEAK_TFLOPS_V5E,
    }

    def left() -> float:
        return args.budget - (time.monotonic() - t_start)

    # ---- Leg 1: 85M MFU, steps-per-call A/B -------------------------
    # Deliverable arms run FIRST (spc 1 vs 10 — the dispatch suspect);
    # the two PROBE arms run LAST, after every deliverable leg, so a
    # tight window can never starve a deliverable for a probe:
    # spc10_flash forces the flash kernel at seq 1024 (the attention
    # suspect — the r4 sweep said XLA wins below T=3072 at small
    # shapes, re-verified at the 85M config itself) and
    # spc10_noremat_b8 drops remat at batch 8 (remat's recomputed
    # forward inflates step time by ~1/3 without appearing in model
    # flops, so model-flops MFU understates the chip where HBM permits
    # no-remat). Per-arm batch/remat make the flops/tokens accounting
    # per-arm; the run-level fields describe the baseline arms only.
    n85 = 86_039_040
    record["run_85m"] = {
        "baseline_config": "d768/h12/L12 byte vocab, seq 1024, "
                           "batch 16, bf16 + remat, donated buffers "
                           "(per-arm batch/remat/flops recorded on "
                           "each arm)",
        "arms": {},
    }

    # arm: (name, steps_per_call, batch, remat, extra_env)
    def run_arm(arm_name, k, batch, remat, extra_env):
        if left() < 300:
            record["run_85m"]["arms"][arm_name] = {"skipped": "budget"}
            return
        metrics = os.path.join(ART, f"metrics_85m_{arm_name}.jsonl")
        rc, out, err = _run_cli(
            ["--d-model", "768", "--heads", "12", "--layers", "12",
             "--seq-len", "1024", "--steps", str(args.steps_85m),
             "--batch-size", str(batch), "--bf16",
             *(["--remat"] if remat else []),
             "--lr", "3e-4", "--lr-schedule", "cosine",
             "--warmup-steps", "20", "--steps-per-call", str(k),
             "--log-every", "10", "--metrics-out", metrics],
            timeout=min(left(), 900), extra_env=extra_env,
        )
        hist = _read_history(metrics)
        ss = steady_state(hist)
        arm = {
            "rc": rc, "cmd_steps_per_call": k, "batch": batch,
            "remat": remat,
            "model_flops_per_step": model_flops_per_step(
                n85, batch, 1024, 768, 12
            ),
            "steady_state": ss,
            "final_report": _final_report(metrics),
        }
        if extra_env:
            arm["env"] = extra_env
        if ss:
            tf = arm["model_flops_per_step"] / ss["s_per_step"] / 1e12
            arm["model_tflops_steady"] = round(tf, 2)
            arm["mfu"] = round(tf / PEAK_TFLOPS_V5E, 4)
            arm["tokens_per_sec"] = round(batch * 1024 / ss["s_per_step"])
        if rc != 0:
            arm["stderr_tail"] = err[-500:]
        record["run_85m"]["arms"][arm_name] = arm
        _flush(record)

    for spec in (("spc1", 1, 16, True, None), ("spc10", 10, 16, True, None)):
        run_arm(*spec)

    # ---- Leg 2: short profiler trace of the 85M step ----------------
    if left() > 240:
        trace_dir = os.path.join(ART, "trace_85m")
        rc, out, err = _run_cli(
            ["--d-model", "768", "--heads", "12", "--layers", "12",
             "--seq-len", "1024", "--steps", "16", "--batch-size", "16",
             "--bf16", "--remat", "--lr", "3e-4",
             "--steps-per-call", "4", "--log-every", "4",
             "--profile-dir", trace_dir],
            timeout=min(left(), 600),
        )
        tb = sum(
            os.path.getsize(os.path.join(r, f))
            for r, _, fs in os.walk(trace_dir) for f in fs
        ) if os.path.isdir(trace_dir) else 0
        record["trace_85m"] = {"rc": rc, "trace_bytes": tb}
        _flush(record)

    # ---- Leg 3: 25.5M with VALID held-out eval ----------------------
    if left() > 240:
        n25 = 25_543_168  # d512/h8/L8 byte-vocab param count (r4 record)
        metrics = os.path.join(ART, "metrics_25m.jsonl")
        rc, out, err = _run_cli(
            ["--d-model", "512", "--heads", "8", "--layers", "8",
             "--seq-len", "512", "--steps", "600", "--batch-size", "32",
             "--bf16", "--lr", "3e-4", "--lr-schedule", "cosine",
             "--warmup-steps", "40", "--steps-per-call", "10",
             "--log-every", "20", "--metrics-out", metrics],
            timeout=min(left(), 900),
        )
        hist = _read_history(metrics)
        ss = steady_state(hist)
        leg = {
            "rc": rc,
            "steady_state": ss,
            "final_report": _final_report(metrics),
            "eval_note": "eval_split must be 'held-out' now: the 8 MB "
                         "corpus leaves ~780 eval rows at seq 512 "
                         "(r4: 'full-dataset', train overlap)",
        }
        if ss:
            leg["tokens_per_sec"] = round(32 * 512 / ss["s_per_step"])
        if rc != 0:
            leg["stderr_tail"] = err[-500:]
        record["run_25m"] = leg
        _flush(record)

    # ---- Leg 4: queued seq-8192 long-context run --------------------
    if not args.skip_8k and left() > 240:
        metrics = os.path.join(ART, "metrics_seq8k.jsonl")
        rc, out, err = _run_cli(
            ["--d-model", "256", "--heads", "8", "--layers", "4",
             "--seq-len", "8192", "--steps", "60", "--batch-size", "2",
             "--bf16", "--remat", "--lr", "3e-4", "--warmup-steps", "10",
             "--log-every", "10", "--metrics-out", metrics],
            timeout=min(left(), 900),
        )
        hist = _read_history(metrics)
        ss = steady_state(hist)
        leg = {
            "rc": rc, "steady_state": ss,
            "final_report": _final_report(metrics),
            "note": "flash training path (T=8192 >= FLASH_MIN_SEQ)",
        }
        if ss:
            leg["tokens_per_sec"] = round(2 * 8192 / ss["s_per_step"])
        if rc != 0:
            leg["stderr_tail"] = err[-500:]
        record["run_seq8k"] = leg
        _flush(record)

    # ---- Probe arms LAST (never at a deliverable's expense) ---------
    for spec in (
        ("spc10_flash", 10, 16, True, {"TDN_FLASH_MIN_SEQ": "1024"}),
        ("spc10_noremat_b8", 10, 8, False, None),
    ):
        run_arm(*spec)

    # Green only if every DELIVERABLE leg that ran succeeded, the
    # headline arm produced an MFU, and no deliverable was
    # budget-skipped (a half-finished run must exit nonzero). The
    # flash-forced arm is a PROBE: its rc is recorded but a failure at
    # the never-before-exercised T=1024 training shape must not force
    # endless re-runs of an otherwise complete suite.
    deliverables = [
        record.get("run_85m", {}).get("arms", {}).get("spc1"),
        record.get("run_85m", {}).get("arms", {}).get("spc10"),
        record.get("trace_85m"), record.get("run_25m"),
        record.get("run_seq8k"),
    ]
    # Absent legs (budget ran out before they were attempted) and
    # {"skipped": "budget"} arms both lack rc == 0, so one test covers
    # every not-actually-done shape.
    rcs = [leg.get("rc") if isinstance(leg, dict) else None
           for leg in deliverables]
    mfu = record.get("run_85m", {}).get("arms", {}).get("spc10", {}).get("mfu")
    ok = all(rc == 0 for rc in rcs) and mfu is not None
    record["ok"] = ok
    _flush(record)
    print(json.dumps({
        "ok": ok, "leg_rcs": rcs, "mfu_spc10": mfu,
        "record": os.path.join(ART, "RECORD.json"),
    }))
    return 0 if ok else 1


def _flush(record: dict) -> None:
    with open(os.path.join(ART, "RECORD.json"), "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    sys.exit(main())
