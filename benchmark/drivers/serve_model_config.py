"""Driver for served generation of a `--model-config` model.

The same run as `drivers/serve.py` — generator child, server behind
loopback gRPC, slots filled, window, sample of finished requests against
the plain reference — and it IS that run: this module loads a private
copy of `serve.py` and rebinds the five names that file hard-wires to
GPT-2 (`REBOUND`; checked at import, and `run.counts` after the run).  What differs:

* the model: built by the program's own loader of
  `tdn lm --model-config <file>` (`tpu_dist_nn.models.sala.
  load_model_config`) from the configuration's file, not by
  `harness/program.py:transformer_config`;
* `run.counts`: `harness/sala_counts.py` `SalaCounts` (same method
  names as `Gpt2Counts`, plus the chunk's);
* the counters: also the scheduler's `sparse_positions_total` /
  `dense_positions_total`, and its `cache_bytes` by kind under
  `run.counters["cache_bytes"]` (a level, not a delta);
* the comparison's reference: `served_gaps_from_seed`, which makes the
  float32 weights one layer at a time (11.3 GB whole at the published
  widths), a request a call;
* the fill timeout: 256 chunk iterations before the window may open.

PERF.md section 7 asks a `benchmark` PR to let `serve.py` take the model
builder and the counts from the configuration, so that the two drivers
become one.
"""

from __future__ import annotations

import os


from benchmark.harness import lookup
from benchmark.harness.sala_counts import SalaCounts

_serve = lookup.load_module(
    os.path.join(lookup.BENCH_DIR, "drivers", "serve.py"),
    "bench_driver_serve_for_model_config")

KIND = "serve"
before_backend = _serve.before_backend
after = _serve.after
_levels: dict = {}


def config_path(cell) -> str:
    entry = next((c for c in lookup.benchmark_json().get("configs", ())
                  if c["name"] == cell.config_name), None)
    return os.path.join(lookup.ROOT, entry["file"]) if entry else os.path.join(
        lookup.BENCH_DIR, "configs", cell.config_name + ".json")


def start_server(run, params):
    """The system under test, as `tdn lm --model-config F --serve-generate`
    starts it."""
    from tpu_dist_nn.models.sala import load_model_config
    from tpu_dist_nn.serving.server import serve_lm_generate

    p = run.params
    clients = _serve._arrivals(p).get("clients") or 4 * int(p["slots"])
    return serve_lm_generate(
        params, load_model_config(config_path(run.cell)), 0,
        max_new_tokens=int(p["max_new_tokens"]),
        prompt_len=int(p["prompt_len"]), temperature=0.0,
        host="127.0.0.1", max_workers=int(clients) + 16, warm_rows=1,
        scheduler="continuous", gen_slots=int(p["slots"]),
        prefix_cache_blocks=int(p.get("prefix_cache_blocks", 0)),
        prefill_chunk=p.get("prefill_chunk"))


def _counters(sched) -> dict:
    _levels["cache_bytes"] = dict(getattr(sched, "cache_bytes", {}))
    return {k: int(getattr(sched, k)) for k in (
        "steps_total", "slot_steps_total", "prefill_chunks_total",
        "retired_total", "rows_total", "prefix_hits_total",
        "prefix_misses_total", "sparse_positions_total",
        "dense_positions_total")}


def gaps_of(reference, cfg, seed, sample, prompt_len, max_new, quant=None,
            block: int = 1) -> dict:
    """`serve.gaps_of` with the weights made inside the reference, a
    layer at a time, and one request a call."""
    import jax

    rows, lens = _serve.served_rows(sample, prompt_len, prompt_len + max_new)
    served, control = [], []
    for i in range(len(rows)):
        out = reference.served_gaps_from_seed(
            cfg, seed, rows[i:i + 1], prompt_len, quant)
        n = int(lens[i])
        served.append(out["gap_served"][0, :n])
        if quant is not None:
            control.append(out["gap_control"][0, :n])
    jax.clear_caches()
    return {"served": served, "control": control}


# The names of `serve.py` this module rebinds or calls.  One that is
# renamed or inlined there stops the run here, at import, before it can
# pass as a GPT-2 run of this cell.
REBOUND = ("start_server", "_counters", "gaps_of", "FILL_TIMEOUT_S",
           "Gpt2Counts")
_missing = [n for n in REBOUND + ("run", "_arrivals", "served_rows")
            if not hasattr(_serve, n)]
if _missing:
    raise ImportError(
        f"drivers/serve.py no longer has {_missing}, which "
        "drivers/serve_model_config.py rebinds or calls")

_serve.start_server = start_server
_serve._counters = _counters
_serve.gaps_of = gaps_of
_serve.FILL_TIMEOUT_S = 900


def run(run_, early):
    _serve.Gpt2Counts = lambda cfg: SalaCounts(cfg, run_.params)
    _serve.run(run_, early)
    if not isinstance(run_.counts, SalaCounts):
        raise RuntimeError(
            "drivers/serve.py built run.counts from another name than "
            f"Gpt2Counts: got {type(run_.counts).__name__}")
    run_.note(counts=type(run_.counts).__name__)
    run_.counters["cache_bytes"] = _levels.get("cache_bytes", {})
    if run_.trace:
        # More of the device's operations than the last line's ten, for
        # tools/scope_breakdown.py to put under the program's scopes.
        run_.note(device_ops=[list(x) for x in run_.trace["ops"][:80]])


