"""Driver for training: the step that `train_lm` runs on one chip
(`build_optimizer` + `make_lm_train_step(donate=True)`), fed seeded rows.

The mix (traffic/<name>.json, overridden by the cell's `params`) gives:
seq_len, batch_rows, learning_rate, weight_decay, remat, check_steps,
trace_steps, steps_in_flight.

Set-up builds ONE object, the compiled step with its state, and drives
it from the seed through its first `check_steps` steps by the window's
own call and feed, keeping what the comparison needs: each loss, the
per-leaf norm of the first gradient as the optimizer got it (Adam's
first moment after one step, over 1 - b1), and the per-leaf norm of the
parameters' change after the steps.  The same object then runs the
window, which counts whole steps and ends on block_until_ready.  After
it: peak memory read, every device array freed, and the plain reference
follows the same first steps (see `reference_steps`, `compare`).
"""

from __future__ import annotations

import collections
import os
import shutil
import time

import numpy as np

from benchmark.harness import device as dev
from benchmark.harness import lookup, xplane
from benchmark.harness.counts import Gpt2Counts
from benchmark.harness.program import transformer_config
from benchmark.harness.result import within

KIND = "train"
B1 = 0.9  # optax.adamw's default, which build_optimizer leaves alone


def feed(seed: int, rows: int, seq_len: int, vocab: int):
    """Step k's rows (rows, seq_len + 1): random ids, all different."""
    k = 0
    while True:
        rng = np.random.default_rng([int(seed), 31, k])
        yield rng.integers(0, vocab, (rows, seq_len + 1), dtype=np.int32)
        k += 1


class Trainer:
    """The compiled step with its state: built once, handed on."""

    def __init__(self, cfg: dict, p: dict, seed: int, reference):
        import jax.numpy as jnp

        from tpu_dist_nn.train.lm_trainer import make_lm_train_step
        from tpu_dist_nn.train.optimizers import build_optimizer

        self.jnp = jnp
        tcfg = transformer_config(cfg, remat=bool(p["remat"]))
        optimizer = build_optimizer(
            float(p["learning_rate"]), weight_decay=float(p["weight_decay"]))
        self.step = make_lm_train_step(tcfg, optimizer, donate=True)
        self.params = reference.make_weights(cfg, seed, cfg["param_dtype"])
        self.opt_state = optimizer.init(self.params)
        self.feed = feed(seed, int(p["batch_rows"]), int(p["seq_len"]),
                         int(cfg["vocab_size"]))
        self.steps = 0

    def advance(self):
        """One step by the one call and feed; returns its loss array."""
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, self.jnp.asarray(next(self.feed)))
        self.steps += 1
        return loss

    def first_moment(self):
        import optax

        return optax.tree_utils.tree_get(self.opt_state, "mu")


def first_steps(trainer: Trainer, reference, cfg, seed, n: int) -> dict:
    """Drive the first n steps and keep the program's readings."""
    out = {"losses": []}
    for i in range(n):
        out["losses"].append(float(trainer.advance()))
        if i == 0:
            out["grad_norms"] = {
                k: v / (1.0 - B1)
                for k, v in reference.leaf_norms(trainer.first_moment()).items()}
    out["change_norms"] = reference.change_norms(trainer.params, cfg, seed)
    return out


def reference_steps(reference, cfg, p, seed, n: int, quant=None,
                    rows: int | None = None, stay: bool = False) -> dict:
    """The plain reference through the same first n steps.  `quant`
    computes it in the control's precision; `rows` keeps only the first
    rows of each batch (the half-batch fault); `stay` returns the state
    unchanged from every step (the frozen-state fault)."""
    import jax

    params = reference.make_weights(cfg, seed, "float32")
    zeros = lambda: jax.tree.map(jax.numpy.zeros_like, params)  # noqa: E731
    mu, nu = zeros(), zeros()
    batches = feed(seed, int(p["batch_rows"]), int(p["seq_len"]),
                   int(cfg["vocab_size"]))
    out = {"losses": []}
    for i in range(n):
        batch = next(batches)
        if rows:
            batch = batch[:rows]
        loss, grads = reference.loss_and_grads(
            params, batch, cfg, quant)
        out["losses"].append(float(loss))
        if i == 0:
            out["grad_norms"] = reference.leaf_norms(grads)
        if not stay:
            params, mu, nu = reference.adamw_step(
                params, grads, mu, nu, i + 1, p["learning_rate"],
                p["weight_decay"])
        del grads
    out["change_norms"] = reference.change_norms(params, cfg, seed)
    return out


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared: gaps between the program's norms and the
    reference's (not norms of differences), each by the worst leaf and
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger.  Leaves whose reference gradient is under
    a thousandth of the median leaf's move under Adam by round-off alone
    and are left out of the change."""
    def worst(a: dict, b: dict, keep=None):
        med = float(np.median(list(b.values())))
        gaps = {k: abs(a[k] - b[k]) / max(b[k], med)
                for k in b if keep is None or k in keep}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    g_med = float(np.median(list(ref["grad_norms"].values())))
    moved = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * g_med}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], ref["losses"]))
    grad_gap, grad_leaf = worst(got["grad_norms"], ref["grad_norms"])
    chg_gap, chg_leaf = worst(got["change_norms"], ref["change_norms"], moved)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": chg_gap,
            "worst_leaves": {"grad": grad_leaf, "change": chg_leaf},
            "left_out": sorted(set(ref["grad_norms"]) - moved)}


def after(early):
    """Drop the run's scratch files (the trace)."""
    shutil.rmtree(lookup.tmp_dir(), ignore_errors=True)


def run(run_, early):
    import jax

    args, p, cfg = run_.args, run_.params, run_.config
    reference = run_.cell.reference
    split = run_.setup_split
    run_.counts = Gpt2Counts(cfg)
    n_check = int(p["check_steps"])

    t0 = time.monotonic()
    trainer = Trainer(cfg, p, args.seed, reference)
    jax.block_until_ready(trainer.params)
    split["weights_and_state_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    got = first_steps(trainer, reference, cfg, args.seed, n_check)
    split["compile_and_first_steps_s"] = time.monotonic() - t0

    # ---------------------------------------------------- the window
    t_open = time.monotonic()
    run_.open_window(t_open)
    start_steps = trainer.steps

    ahead = int(p.get("steps_in_flight", 2))

    def drive(until: float) -> float:
        """Steps with `steps_in_flight` dispatched ahead of the device
        (train_lm fetches a loss only where it logs), until a step is
        seen to end at or after `until`; returns when the last one
        dispatched has ended."""
        flying = collections.deque(trainer.advance() for _ in range(ahead - 1))
        while True:
            flying.append(trainer.advance())
            flying.popleft().block_until_ready()
            if time.monotonic() >= until:
                flying[-1].block_until_ready()
                return time.monotonic()

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(lookup.tmp_dir(), "trace")
        drive(t_open + min(2.0, args.seconds / 4))
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(int(p.get("trace_steps", 4))):
                last = trainer.advance()
            last.block_until_ready()
        jax.profiler.stop_trace()
    t_end = drive(t_open + args.seconds)
    steps = trainer.steps - start_steps
    tokens_per_step = int(p["batch_rows"]) * int(p["seq_len"])
    run_.window_s = t_end - t_open
    run_.train = {"steps": steps, "tokens_per_step": tokens_per_step,
                  "seconds": t_end - t_open, "seq_len": int(p["seq_len"])}
    run_.attempted, run_.failed = steps, 0
    last_loss = float(trainer.advance())  # the state is still sound
    run_.memory_peak_bytes = dev.memory_peak_bytes()
    run_.note(window={"steps": steps, "seconds": t_end - t_open,
                      "asked_s": args.seconds, "last_loss": last_loss},
              setup_split=split, setup_s=run_.setup_s,
              program_losses=got["losses"])
    del trainer
    dev.free_device()
    if trace_dir:
        run_.trace = xplane.reduce_trace(trace_dir, "bench.window")

    t0 = time.monotonic()
    ref = reference_steps(reference, cfg, p, args.seed, n_check)
    dev.free_device()
    numbers = compare(got, ref)
    limits = run_.cell.own.get("limits", {})

    def held(numbers: dict) -> dict:
        """The numbers that decide `correct`, each beside its limit."""
        out = {k: {"value": numbers[k], "limit": limits[k]}
               for k in ("loss_gap", "grad_norm_gap", "change_norm_gap")
               if k in limits}
        out["loss_is_finite"] = {
            "value": int(not np.isfinite(last_loss)), "limit": 0}
        return out

    run_.compared = held(numbers)
    run_.note(comparison_s=time.monotonic() - t0, numbers=numbers,
              reference_losses=ref["losses"])
    control = getattr(args, "control", None)
    if control:
        # Not in the benchmark's own runs: the control and the faults,
        # each put in the program's place, held against the reference
        # and judged by the same limits.
        half = int(p["batch_rows"]) // 2
        cases = [("control_" + c, {"quant": c}) for c in control.split(",")]
        cases += [("fault_half_batch", {"rows": half}),
                  ("fault_state_unchanged", {"stay": True})]
        for name, kw in cases:
            other = compare(reference_steps(reference, cfg, p, args.seed,
                                            n_check, **kw), ref)
            dev.free_device()
            run_.note(**{name: dict(other, correct=within(held(other)))})
    run_.correct = len(run_.compared) > 1 and within(run_.compared)
