"""Driver for the `reason-decode` mix: served generation of a
`--model-config` model whose prefill has a program that ends without
logits.

The same run as `drivers/serve.py`, through `drivers/
serve_model_config.py`, which IS that run with the model built by the
program's own loader (`tpu_dist_nn.models.sala.load_model_config`): this
module loads a private copy of it and rebinds three names (`REBOUND`,
checked at import; `serve_model_config.py` checks `serve.py`'s in its
turn).  What differs:

* `run.counts`: `harness/phi4flash_counts.py` `Phi4FlashCounts` (same
  method names as `Gpt2Counts`, plus the body chunk's);
* the counters: also the scheduler's `prefill_body_chunks_total` (chunk
  launches made with the program that ends without logits; 0 where the
  program has no such counter);
* the comparison's reference: `served_gaps_from_seed` over every sampled
  request in one call (a row is 3 071 positions here, not 33 023, so the
  float32 weights, 15.4 GB whole, are made a layer at a time once a
  pass and not once a request).

* before anything is started (`before_backend`), the program's loader
  reads the configuration: a tree that lacks the family stops there;
* a `--trace 1` run captures without the profiler's Python tracer
  (`_trace_without_python_tracer` says why).

PERF.md section 7 asks a `benchmark` PR to let `serve.py` take the model
builder and the counts from the configuration, so that the three
drivers become one.
"""

from __future__ import annotations

import os

from benchmark.harness import lookup
from benchmark.harness.phi4flash_counts import Phi4FlashCounts

_mc = lookup.load_module(
    os.path.join(lookup.BENCH_DIR, "drivers", "serve_model_config.py"),
    "bench_driver_serve_model_config_for_reason_decode")

# The names of `serve_model_config.py` (and of its `serve.py`) this
# module rebinds or calls.  One that is renamed or inlined there stops
# the run here, at import.
REBOUND = ("SalaCounts", "_counters", "gaps_of")
_missing = [n for n in REBOUND + ("run", "_serve", "config_path",
                                  "before_backend", "after")
            if not hasattr(_mc, n)]
_missing += [n for n in ("_counters", "gaps_of", "served_rows")
             if not hasattr(getattr(_mc, "_serve", None), n)]
if _missing:
    raise ImportError(
        f"drivers/serve_model_config.py no longer has {_missing}, which "
        "drivers/reason_decode.py rebinds or calls")

KIND = "serve"
after = _mc.after
config_path = _mc.config_path


def before_backend(cell, args):
    """The program has to know this family before anything is started:
    a tree whose loader lacks it (the parent of the PR that brought it)
    stops here within a second, with the loader's own error, and not
    after 7.7 GB of weights are made."""
    from tpu_dist_nn.models.sala import load_model_config

    load_model_config(config_path(cell))
    return _mc.before_backend(cell, args)


_base_counters = _mc._counters


def _counters(sched) -> dict:
    out = _base_counters(sched)
    out["prefill_body_chunks_total"] = int(
        getattr(sched, "prefill_body_chunks_total", 0))
    return out


def gaps_of(reference, cfg, seed, sample, prompt_len, max_new, quant=None,
            block: int = 1) -> dict:
    """`serve.gaps_of` with the weights made inside the reference, a
    layer at a time, every sampled request in one pass."""
    import jax

    rows, lens = _mc._serve.served_rows(sample, prompt_len,
                                        prompt_len + max_new)
    # No wider than the longest request: the reference is one full
    # forward over every row.
    rows = rows[:, :prompt_len + int(lens.max())]
    out = reference.served_gaps_from_seed(cfg, seed, rows, prompt_len, quant)
    jax.clear_caches()
    cut = lambda g: [g[i, :int(n)] for i, n in enumerate(lens)]  # noqa: E731
    return {"served": cut(out["gap_served"]),
            "control": cut(out["gap_control"]) if quant is not None else []}


_mc.SalaCounts = Phi4FlashCounts
_mc._serve._counters = _counters
_mc._serve.gaps_of = gaps_of


def _trace_without_python_tracer(start_trace):
    """`jax.profiler.start_trace` with the Python tracer off: that
    tracer hooks every call of every thread, and under it the server's
    handler threads (one a stream, 96 here) stop taking new calls, so a
    traced window admits no request and launches no prefill chunk.  The
    device planes and the `TraceAnnotation` spans the reduction reads
    (`bench.window`, `tdn.gen.*`) are the host tracer's, and stay."""
    import jax

    def start(log_dir, **kw):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        return start_trace(log_dir, profiler_options=options, **kw)

    return start


def run(run_, early):
    import jax

    start_trace = jax.profiler.start_trace
    jax.profiler.start_trace = _trace_without_python_tracer(start_trace)
    try:
        _mc.run(run_, early)
    finally:
        jax.profiler.start_trace = start_trace
    if not isinstance(run_.counts, Phi4FlashCounts):
        raise RuntimeError(
            "drivers/serve_model_config.py built run.counts from another "
            f"name than SalaCounts: got {type(run_.counts).__name__}")
