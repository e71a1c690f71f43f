"""Driver for the `repo-decode` mix: served generation of a
`--model-config` model with latent attention and routed experts.

The same run as `drivers/serve.py`, through `drivers/
serve_model_config.py` (that run with the model built by the program's
own loader, `tpu_dist_nn.models.sala.load_model_config`): this module
loads a private copy of it and rebinds three names (`REBOUND`, checked
at import), as `drivers/reason_decode.py` does for its family.  What
differs:

* `run.counts`: `harness/mla_moe_counts.py` `MlaMoeCounts`; after the
  run it is handed the pairs a token sent to experts held here, from
  the program's counters, in place of the expected k n_held / E;
* the counters: also the scheduler's `routing_totals` (what the model's
  programs count on the device: `routed_pairs`, `expert_touched`,
  `expert_visits`, and `expert_pairs.<i>` by held expert; nothing where
  the program has no such counts);
* as `drivers/reason_decode.py` (its functions, loaded from there): the
  comparison's reference is `served_gaps_from_seed` over every sampled
  request in one call (the float32 weights, 16.7 GB whole, are made a
  layer at a time once a pass); before anything is started
  (`before_backend`) the program's loader reads the configuration, so
  a tree that lacks the family stops there; a `--trace 1` run captures
  without the profiler's Python tracer;
* the capture is read once more for the device seconds under each
  `jax.named_scope` of this family (`harness/scopes.py`:
  `run.trace["scopes"]`, `run.trace["launches"]`, whole launches of the
  whole capture): `expert_ffn_roofline` and `latent_attend_roofline`
  read them; where the tree has no such reader or scopes, nothing is
  added and the two metrics stay silent.
"""

from __future__ import annotations

import os

from benchmark.harness import lookup
from benchmark.harness.mla_moe_counts import MlaMoeCounts
from benchmark.harness.scopes import scopes_of_trace

_mc = lookup.load_module(
    os.path.join(lookup.BENCH_DIR, "drivers", "serve_model_config.py"),
    "bench_driver_serve_model_config_for_repo_decode")
# What `drivers/reason_decode.py` wrote for its family and this one
# needs as it stands.  (Its own rebinding is of its own private copy.)
_rd = lookup.load_module(
    os.path.join(lookup.BENCH_DIR, "drivers", "reason_decode.py"),
    "bench_driver_reason_decode_for_repo_decode")

# The names of `serve_model_config.py` (and of its `serve.py`) this
# module rebinds, and those it calls there and in `reason_decode.py`.
# One that is renamed or inlined stops the run here, at import.
REBOUND = ("SalaCounts", "_counters", "gaps_of")
_missing = [n for n in REBOUND + ("run", "_serve", "config_path", "after")
            if not hasattr(_mc, n)]
_missing += [n for n in ("_counters", "gaps_of")
             if not hasattr(getattr(_mc, "_serve", None), n)]
_missing += [n for n in ("before_backend", "gaps_of",
                         "_trace_without_python_tracer")
             if not hasattr(_rd, n)]
if _missing:
    raise ImportError(
        "drivers/serve_model_config.py or drivers/reason_decode.py no "
        f"longer has {_missing}, which drivers/repo_decode.py rebinds or "
        "calls")

KIND = "serve"
after = _mc.after
config_path = _mc.config_path
before_backend = _rd.before_backend
SCOPES = ("mla_moe.attn.project", "mla_moe.attn.latent",
          "mla_moe.attn.expand", "mla_moe.router", "mla_moe.experts",
          "mla_moe.shared", "mla_moe.mlp", "kv_write_row", "sample", "guard")

_base_counters = _mc._counters


def _counters(sched) -> dict:
    out = _base_counters(sched)
    for name, v in dict(getattr(sched, "routing_totals", {})).items():
        if getattr(v, "ndim", 0):
            out.update({f"{name}.{i}": int(x) for i, x in enumerate(v)})
        else:
            out[name] = int(v)
    return out


_mc.SalaCounts = MlaMoeCounts
_mc._serve._counters = _counters
_mc._serve.gaps_of = _rd.gaps_of


def run(run_, early):
    import jax

    start_trace = jax.profiler.start_trace
    jax.profiler.start_trace = _rd._trace_without_python_tracer(start_trace)
    try:
        _mc.run(run_, early)
    finally:
        jax.profiler.start_trace = start_trace
    if not isinstance(run_.counts, MlaMoeCounts):
        raise RuntimeError(
            "drivers/serve_model_config.py built run.counts from another "
            f"name than SalaCounts: got {type(run_.counts).__name__}")
    c = run_.counters
    if c.get("routed_pairs"):
        held = sum(v for k, v in c.items() if k.startswith("expert_pairs."))
        run_.counts.held_pairs_per_token = \
            run_.counts.k * held / c["routed_pairs"]
    if run_.trace:
        run_.trace.update(scopes_of_trace(
            os.path.join(early["tmp"], "trace"), SCOPES))
        run_.note(scopes=run_.trace.get("scopes"),
                  launches=run_.trace.get("launches"))
