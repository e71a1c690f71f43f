#!/usr/bin/env python3
"""Start the system on the chip, once, through what a user would call.

    python chip_smoke.py               # one chip, four phases
    python chip_smoke.py --multichip   # four chips, the pipelined paths

Every phase is a child process of the real CLI (``python -m
tpu_dist_nn.cli --platform tpu ...``), one at a time: a chip belongs to
one process, so this parent never initialises a JAX backend. It is the
gRPC client, the float64 numpy oracle and the judge. Platform, device
kind and count are taken from what the children report.

One chip:

* ``fcnn_f32`` / ``fcnn_int8`` — ``tdn up`` serves the paper's own
  784-128-64-10 network (random weights from ``--seed``); ``Process``
  batches of 512, 37, 1 and 127 rows and one ``tdn infer --target`` client
  run are checked against the oracle (values for f32, argmax for int8).
* ``lm_train`` / ``lm_generate`` — ``tdn lm`` trains the d768/L12 LM
  (seq 1024, bf16, remat, batch 16) a few steps on the vendored corpus,
  then serves generation from those params in the same process:
  ``Generate`` and ``GenerateStream`` requests, token counts as asked, a
  repeated prompt giving the same tokens at temperature 0.
* ``kernels`` — ``TDN_TEST_TPU=1 pytest tests/test_tpu_hardware.py``:
  every Pallas kernel against its reference, each compiled program
  holding a Mosaic kernel.

``--multichip`` runs the two paths that exist only across chips and what
each is compared with, and nothing else: the README's 8-layer MLP served
over 4 pipeline stages against the single-chip executor, and the LM
trained with ``--stages 4 --schedule 1f1b`` against the single-chip
trainer from the same seed — with the placement each reports.

One JSON object per phase, then as the last line only
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Exit 0 only if
every phase passed on a TPU. ``--platform cpu --tiny`` rehearses the
control flow off the chip (it can pass phases; it cannot print ok).
Child logs, inputs and metrics go to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import xml.etree.ElementTree as ET

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
DEADLINE = time.monotonic() + 1150.0  # the driver allows 1200 s

RTOL = ATOL = 2e-3      # tests/test_tpu_hardware.py: f32 MXU vs float64
ARGMAX_FLOOR = 0.97     # tests/test_tpu_hardware.py: the int8 gate
# Pipelined vs single-chip loss, same seed, bf16 compute. The first loss
# is a forward pass of the same weights: only summation order differs.
# Later ones also carry what Adam made of the gradients' rounding.
FIRST_LOSS_TOL = 5e-3
LOSS_RTOL = 5e-2
BATCHES = (512, 37, 1, 127)
CHILDREN: list[subprocess.Popen] = []


class PhaseFailed(Exception):
    pass


def remaining(cap: float) -> float:
    left = DEADLINE - time.monotonic()
    if left <= 0:
        raise PhaseFailed("out of time")
    return min(cap, left)


class Child:
    """One CLI child: stdout JSON lines collected by a reader thread,
    stderr (``--log-json`` records) in a file under OUT."""

    def __init__(self, name: str, platform: str, args, env=None):
        self.log_path = os.path.join(OUT, f"{name}.stderr.jsonl")
        self.docs: list[dict] = []
        self._cv = threading.Condition()
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tpu_dist_nn.cli", "--platform", platform,
             "--log-json", *args],
            stdout=subprocess.PIPE, stderr=self._log, text=True, cwd=HERE,
            env=dict(os.environ, **(env or {})), start_new_session=True,
        )
        CHILDREN.append(self.proc)
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self):
        for line in self.proc.stdout:
            with self._cv:
                if line.startswith("{"):
                    try:
                        self.docs.append(json.loads(line))
                    except ValueError:
                        pass
                self._cv.notify_all()
        with self._cv:
            self._cv.notify_all()

    def wait_doc(self, key: str, timeout: float) -> dict:
        """The first stdout JSON object holding ``key``."""
        end = time.monotonic() + remaining(timeout)
        with self._cv:
            while True:
                for doc in self.docs:
                    if key in doc:
                        return doc
                if self.proc.poll() is not None:
                    raise PhaseFailed(
                        f"child exited rc={self.proc.returncode} before "
                        f"printing {key!r}: {self.log_tail()}"
                    )
                if time.monotonic() >= end:
                    raise PhaseFailed(
                        f"no {key!r} within {timeout:.0f}s: {self.log_tail()}"
                    )
                self._cv.wait(1.0)

    def finish(self, timeout: float = 60.0, term: bool = False) -> int:
        """Wait for exit (after SIGTERM with ``term``: the servers'
        graceful drain); kill the group if it overstays."""
        if term and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=remaining(timeout))
        except (subprocess.TimeoutExpired, PhaseFailed):
            kill(self.proc)
        self._log.close()
        return self.proc.returncode

    def records(self) -> list[dict]:
        out = []
        with open(self.log_path) as f:
            for line in f:
                if line.startswith("{"):
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        pass
        return out

    def log_tail(self) -> str:
        if not self._log.closed:
            self._log.flush()
        with open(self.log_path) as f:
            return f.read()[-600:]

    def facts(self) -> dict:
        """What every phase line carries about its child."""
        first = {}
        for r in self.records():
            first.setdefault(r.get("event"), r)
        return {
            "native_codec": first.get("native.codec", {}).get("loaded"),
            "compile_cache_dir": first.get("backend.resolved", {}).get(
                "compile_cache_dir"
            ),
        }


def kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def describe(e: Exception) -> str:
    return f"{type(e).__name__}: {e}"[:800]


def emit(phase: str, ok: bool, **fields) -> dict:
    doc = {"phase": phase, "ok": bool(ok), **fields}
    print(json.dumps(doc), flush=True)
    return doc


# ------------------------------------------------------------------ FCNN


def serve_fcnn(name, platform, model_path, x, extra=(), env=None):
    """``tdn up`` a model, send BATCHES over gRPC, run one ``tdn infer
    --target`` client, drain the server. Returns ``(replies, ready_doc,
    child, client_accuracy, seconds)``."""
    import numpy as np

    from tpu_dist_nn.serving import GrpcClient

    t0 = time.monotonic()
    child = Child(name, platform, [
        "up", "--config", model_path, "--grpc-port", "0",
        "--metrics-port", "0", *extra,
    ], env=env)
    try:
        ready = child.wait_doc("ready", 420)
        port = child.wait_doc("grpc_port", 420)["grpc_port"]
        client = GrpcClient(f"127.0.0.1:{port}", timeout=120.0)
        try:
            replies, at = [], 0
            for n in BATCHES:
                replies.append(client.process(x[at:at + n]))
                at += n
        finally:
            client.close()
        cli_client = subprocess.run(
            [sys.executable, "-m", "tpu_dist_nn.cli", "--platform", platform,
             "infer", "--target", f"127.0.0.1:{port}",
             "--inputs", os.path.join(OUT, "examples.json"),
             "--batch-size", "100"],
            capture_output=True, text=True, cwd=HERE,
            timeout=remaining(180),
        )
        if cli_client.returncode != 0:
            raise PhaseFailed(
                f"tdn infer --target rc={cli_client.returncode}: "
                f"{cli_client.stderr[-400:]}"
            )
        accuracy = float(
            cli_client.stdout.split("(accuracy ")[1].split(")")[0]
        )
    finally:
        rc = child.finish(term=True)
    if rc != 0:
        raise PhaseFailed(f"server exited rc={rc} after SIGTERM: "
                          f"{child.log_tail()}")
    return (np.vstack(replies), ready, child, accuracy,
            time.monotonic() - t0)


def make_fcnn(sizes, seed, name):
    """Random model + inputs from the seed, written as the reference's
    JSON; the oracle's answers; 300 labelled examples for the CLI
    client. Returns ``(model_path, x, want)``."""
    import numpy as np

    from tpu_dist_nn.core.schema import save_examples, save_model
    from tpu_dist_nn.testing.factories import random_inputs, random_model
    from tpu_dist_nn.testing.oracle import oracle_forward_batch

    model = random_model(sizes, seed=seed)
    model_path = os.path.join(OUT, name)
    save_model(model, model_path)
    x = random_inputs(sum(BATCHES), sizes[0], seed=seed + 1)
    want = oracle_forward_batch(model, x)
    save_examples(x[:300], want[:300].argmax(-1).astype(np.int32),
                  os.path.join(OUT, "examples.json"))
    return model_path, x, want


def fcnn_phases(platform, seed):
    import numpy as np

    model_path, x, want = make_fcnn([784, 128, 64, 10], seed, "fcnn.json")
    results, f32 = [], None
    for name, extra, env in (
        ("fcnn_f32", (), None),
        # TDN_INT8_AUTO=0: serve the int8 path whatever its one-launch
        # timing against f32 says — this phase exists to run it.
        ("fcnn_int8", ("--quantize", "int8"), {"TDN_INT8_AUTO": "0"}),
    ):
        try:
            got, ready, child, accuracy, seconds = serve_fcnn(
                name, platform, model_path, x, extra, env
            )
            err = float(np.max(np.abs(got - want)))
            agree = float((got.argmax(-1) == want.argmax(-1)).mean())
            if name == "fcnn_f32":
                f32 = got
                ok = bool(np.allclose(got, want, rtol=RTOL, atol=ATOL)
                          and accuracy > 0.99)
            else:
                # A different path served: not the f32 replies again.
                ok = bool(agree > ARGMAX_FLOOR and accuracy > ARGMAX_FLOOR
                          and np.isfinite(got).all()
                          and (f32 is None or not np.array_equal(got, f32)))
            results.append(emit(
                name, ok, seconds=round(seconds, 2),
                compile_seconds=round(ready["setup_seconds"], 2),
                max_err=err, argmax_agreement=agree,
                client_accuracy=accuracy, rows=len(got),
                placement=ready["placement"], device=ready["device"],
                **child.facts(),
            ))
        except Exception as e:  # noqa: BLE001 — a phase reports, then fails
            results.append(emit(name, False, error=describe(e)))
            if "rc=3" in str(e):
                break  # no TPU: every later child would say the same
    return results


# -------------------------------------------------------------------- LM


def lm_args(tiny, seed, steps, metrics_path, extra=()):
    if os.path.exists(metrics_path):
        os.remove(metrics_path)  # --metrics-out appends
    shape = (
        ["--d-model", "64", "--heads", "4", "--layers", "4",
         "--seq-len", "64", "--batch-size", "8"] if tiny else
        ["--d-model", "768", "--heads", "12", "--layers", "12",
         "--seq-len", "1024", "--batch-size", "16"]
    )
    # Warm-up and clipping as a real run would set them: without, the
    # first full-size Adam steps at this width throw the loss upwards.
    return [
        "lm", *shape, "--bf16", "--remat", "--steps", str(steps),
        "--lr", "3e-4", "--warmup-steps", "10", "--clip-norm", "1.0",
        "--log-every", "1", "--eval-batches", "1",
        "--seed", str(seed), "--metrics-out", metrics_path, *extra,
    ]


def read_history(path):
    with open(path) as f:
        return [r for r in map(json.loads, f) if "step" in r]


def train_facts(history):
    """Loss trajectory + where the first step's compile ended."""
    import numpy as np

    losses = [h["loss"] for h in history]
    secs = [h["seconds"] for h in history]
    steady = float(np.median(np.diff(secs))) if len(secs) > 1 else 0.0
    return {
        "steps": len(history), "loss_first": losses[0],
        "loss_last": losses[-1], "losses": losses,
        "compile_seconds": round(max(secs[0] - steady, 0.0), 2),
        "finite": bool(np.isfinite(losses).all()),
    }


def lm_phases(platform, seed, tiny):
    import numpy as np

    from tpu_dist_nn.serving import GrpcClient

    steps = 12
    prompt_len, new_tokens = (8, 8) if tiny else (64, 32)
    metrics = os.path.join(OUT, "lm_metrics.jsonl")
    child = Child("lm", platform, lm_args(tiny, seed, steps, metrics, [
        "--serve-generate", "0", "--scheduler", "continuous",
        "--temperature", "0", "--gen-slots", "8",
        "--serve-prompt-len", str(prompt_len),
        "--serve-new-tokens", str(new_tokens),
    ]))
    results = []
    try:
        try:
            report = child.wait_doc("serving", 900)
            facts = train_facts(read_history(metrics))
            results.append(emit(
                "lm_train",
                facts["finite"] and facts["loss_last"] < facts["loss_first"]
                and facts["steps"] == steps,
                seconds=report["train_seconds"], **facts,
                eval_loss=report["loss_nats_per_token"],
                device=report["device"], **child.facts(),
            ))
        except Exception as e:  # noqa: BLE001 — a phase reports, then fails
            results.append(emit("lm_train", False, error=describe(e)))
            return results
        try:
            t1 = time.monotonic()
            rng = np.random.default_rng(seed + 2)
            prompts = rng.integers(32, 127, (4, prompt_len))  # ASCII bytes
            client = GrpcClient(
                f"127.0.0.1:{report['serving']['port']}", timeout=180.0
            )
            try:
                one = client.generate(prompts[:1])
                three = client.generate(prompts[1:])
                again = client.generate(prompts[:1])
                stream = client.generate_stream(prompts[0])
                streamed = list(stream)
            finally:
                client.close()
            total = prompt_len + new_tokens
            tokens = np.vstack([one, three, again])
            checks = {
                "shapes": one.shape == (1, total)
                and three.shape == (3, total),
                "prompts_echoed": bool(
                    (tokens[:, :prompt_len]
                     == np.vstack([prompts, prompts[:1]])).all()
                ),
                "byte_ids": bool(((tokens >= 0) & (tokens < 256)).all()),
                "repeat_identical": bool(np.array_equal(one, again)),
                "stream_count": len(streamed) == new_tokens,
                "stream_equals_unary": streamed
                == one[0, prompt_len:].tolist(),
            }
            # Port open = prefill and decode-step kernels compiled; the
            # gap since the last training log line also holds the eval.
            recs = child.records()
            start = next(r["ts"] for r in recs
                         if r.get("event") == "server.start")
            trained = max(r["ts"] for r in recs
                          if str(r.get("event", "")).startswith("step "))
            results.append(emit(
                "lm_generate", all(checks.values()),
                seconds=round(time.monotonic() - t1, 2),
                compile_seconds=round(start - trained, 2),
                requests=4, tokens_generated=4 * new_tokens + len(streamed),
                stream_finish=stream.finish, **checks,
                device=report["device"], **child.facts(),
            ))
        except Exception as e:  # noqa: BLE001 — a phase reports, then fails
            results.append(emit("lm_generate", False, error=describe(e)))
    finally:
        rc = child.finish(term=True)
        if rc != 0:
            results.append(emit("lm_exit", False, rc=rc,
                                error=child.log_tail()))
    return results


# --------------------------------------------------------------- kernels


def kernels_phase():
    t0 = time.monotonic()
    xml_path = os.path.join(OUT, "kernels.xml")
    with open(os.path.join(OUT, "kernels.log"), "w") as log:
        try:
            rc = subprocess.run(
                [sys.executable, "-m", "pytest", "tests/test_tpu_hardware.py",
                 "-q", "-p", "no:cacheprovider", f"--junitxml={xml_path}"],
                stdout=log, stderr=subprocess.STDOUT, cwd=HERE,
                env=dict(os.environ, TDN_TEST_TPU="1"),
                timeout=remaining(600),
            ).returncode
        except subprocess.TimeoutExpired:  # run() killed the child
            rc = 124
    try:
        suite = ET.parse(xml_path).getroot().find("testsuite")
    except (OSError, ET.ParseError) as e:
        return [emit("kernels", False, rc=rc,
                     error=f"no junit report: {e}")]
    counts = {k: int(suite.get(k, 0))
              for k in ("tests", "failures", "errors", "skipped")}
    props, errs = {}, []
    for case in suite.iter("testcase"):
        for p in case.iter("property"):
            if p.get("name") == "max_abs_err":
                errs.append(float(p.get("value")))
            else:
                props[p.get("name")] = p.get("value")
    device = {"platform": props["platform"], "kind": props["kind"],
              "count": int(props["count"])} if "platform" in props else None
    ok = (rc == 0 and counts["tests"] > 0 and not (
        counts["failures"] or counts["errors"] or counts["skipped"]))
    return [emit(
        "kernels", ok, seconds=round(time.monotonic() - t0, 2),
        compile_seconds=None, max_err=max(errs) if errs else None,
        **counts, device=device, native_codec=None,
        compile_cache_dir=props.get("compile_cache_dir"),
    )]


# ------------------------------------------------------------- multichip


def placed_on_four(placement, memory) -> bool:
    """4 stages, pipelined, parameters on 4 distinct devices, and every
    chip's allocator holding something."""
    return bool(
        placement.get("num_stages") == 4 and placement.get("pipelined")
        and len(set(placement["param_devices"])) == 4
        and len(memory) == 4
        and all((m["peak_bytes_in_use"] or 0) > 2**20 for m in memory)
    )


def multichip_fcnn(platform, seed):
    import numpy as np

    # README's 8-layer MLP, two layers a stage.
    model_path, x, want = make_fcnn(
        [784, 256, 128, 128, 64, 64, 32, 16, 10], seed, "mlp8.json"
    )
    try:
        piped, ready, child, _, seconds = serve_fcnn(
            "fcnn_pipeline", platform, model_path, x,
            ("--distribution", "2,2,2,2"),
        )
        single, ready1, _, _, _ = serve_fcnn(
            "fcnn_single", platform, model_path, x
        )
    except Exception as e:  # noqa: BLE001 — a phase reports, then fails
        return [emit("fcnn_pipeline", False, error=describe(e))]
    placed = placed_on_four(ready["placement"], ready["device_memory"])
    return [emit(
        "fcnn_pipeline",
        placed and not ready1["placement"]["pipelined"]
        and np.allclose(piped, single, rtol=RTOL, atol=ATOL)
        and np.allclose(piped, want, rtol=RTOL, atol=ATOL),
        seconds=round(seconds, 2),
        compile_seconds=round(ready["setup_seconds"], 2),
        max_err_vs_single_chip=float(np.max(np.abs(piped - single))),
        max_err=float(np.max(np.abs(piped - want))),
        placed_on_four=placed, placement=ready["placement"],
        device_memory=ready["device_memory"],
        single_chip_placement=ready1["placement"],
        device=ready["device"], **child.facts(),
    )]


def multichip_lm(platform, seed, tiny):
    steps = 6
    runs = {}
    for name, extra in (
        ("lm_pipeline", ["--stages", "4", "--schedule", "1f1b",
                         "--microbatches", "4"]),
        ("lm_single", []),
    ):
        metrics = os.path.join(OUT, f"{name}_metrics.jsonl")
        t0 = time.monotonic()
        child = Child(name, platform,
                      lm_args(tiny, seed, steps, metrics, extra))
        try:
            report = child.wait_doc("train_seconds", 900)
            rc = child.finish()
            if rc != 0:
                raise PhaseFailed(f"rc={rc}: {child.log_tail()}")
            runs[name] = (report, train_facts(read_history(metrics)),
                          child, time.monotonic() - t0)
        except Exception as e:  # noqa: BLE001 — a phase reports, then fails
            child.finish()
            return [emit("lm_pipeline", False,
                         error=f"{name}: {describe(e)}")]
    report, facts, child, seconds = runs["lm_pipeline"]
    single = runs["lm_single"][1]
    pairs = list(zip(facts["losses"], single["losses"]))
    gaps = [abs(a - b) for a, b in pairs]
    placed = placed_on_four(
        {"num_stages": 4, "pipelined": True,
         "param_devices": report["param_devices"]},
        report["device_memory"],
    )
    return [emit(
        "lm_pipeline",
        placed and facts["finite"] and facts["steps"] == steps
        and len(gaps) == steps and gaps[0] < FIRST_LOSS_TOL
        and all(g < LOSS_RTOL * max(p) for g, p in zip(gaps, pairs)),
        seconds=round(seconds, 2), **facts,
        single_chip_losses=single["losses"], loss_gaps=gaps,
        placed_on_four=placed, param_devices=report["param_devices"],
        device_memory=report["device_memory"],
        device=report["device"], **child.facts(),
    )]


# ------------------------------------------------------------------ main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, inputs and prompts are made from it")
    ap.add_argument("--multichip", action="store_true",
                    help="the four-chip pipelined paths and what they "
                         "are compared with, and no other phase")
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu",
                    help="cpu rehearses the control flow; only tpu can "
                         "print ok")
    ap.add_argument("--tiny", action="store_true",
                    help="toy LM widths, for the cpu rehearsal")
    args = ap.parse_args()

    results: list[dict] = []
    try:
        sys.path.insert(0, HERE)
        os.makedirs(OUT, exist_ok=True)
        if args.multichip:
            results += multichip_fcnn(args.platform, args.seed)
            if "rc=3" not in str(results[-1].get("error")):
                results += multichip_lm(args.platform, args.seed, args.tiny)
        else:
            results += fcnn_phases(args.platform, args.seed)
            if "rc=3" not in str(results[-1].get("error")):
                results += lm_phases(args.platform, args.seed, args.tiny)
                results += kernels_phase()
    except Exception as e:  # noqa: BLE001 — the last line must still come
        results.append(emit("chip_smoke", False, error=describe(e)))
    finally:
        for proc in CHILDREN:
            kill(proc)
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            results.append(emit("parent_stayed_off_jax", False))
    devices = [r["device"] for r in results if r.get("device")]
    device = devices[0] if devices else None
    ok = bool(
        results and all(r["ok"] for r in results)
        and all(d == device for d in devices)
        and device and device["platform"] == "tpu"
    )
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
