"""Driver for the `repo-16k` mix: served generation of a `--model-config`
model with window and full attention layers and routed experts.

The same run as `drivers/repo_decode.py` (which is `drivers/serve.py`'s
run through `drivers/serve_model_config.py`, with the scheduler's
routing counts, one reference pass for every sampled request, and a
capture without the profiler's Python tracer read once more by scope):
this module loads a private copy of it and rebinds two names
(`REBOUND`, checked at import).  What differs:

* `run.counts`: `harness/laguna_counts.py` `LagunaCounts` in place of
  `MlaMoeCounts` (the same method names, plus what the three Laguna
  readers need); after the run it is handed the pairs a token sent to
  experts held here, from the program's counters, as there;
* the scopes read out of the capture: this family's (`laguna.*`):
  `window_attend_roofline`, `full_attend_roofline` and
  `moe_experts_roofline` read them; where the tree has no such scopes
  (the parent of the PR that brought the family) nothing is added and
  the three stay silent.

Before anything is started (`before_backend`, `drivers/reason_decode.
py`'s) the program's loader reads the configuration, so a tree that
lacks the family stops there within a second.
"""

from __future__ import annotations

import os

from benchmark.harness import lookup
from benchmark.harness.laguna_counts import LagunaCounts

_rd = lookup.load_module(
    os.path.join(lookup.BENCH_DIR, "drivers", "repo_decode.py"),
    "bench_driver_repo_decode_for_repo_16k")

# The names of `repo_decode.py` this module rebinds, and those it calls
# there and in the `serve_model_config.py` it loaded.  One that is
# renamed or inlined stops the run here, at import.
REBOUND = ("MlaMoeCounts", "SCOPES")
_missing = [n for n in REBOUND + ("run", "_mc", "config_path", "after",
                                  "before_backend") if not hasattr(_rd, n)]
_missing += [n for n in ("SalaCounts",)
             if not hasattr(getattr(_rd, "_mc", None), n)]
if _missing:
    raise ImportError(
        f"drivers/repo_decode.py no longer has {_missing}, which "
        "drivers/repo_16k.py rebinds or calls")

KIND = "serve"
after = _rd.after
config_path = _rd.config_path
before_backend = _rd.before_backend
SCOPES = ("laguna.attn.project", "laguna.attn.window", "laguna.attn.full",
          "laguna.router", "laguna.experts", "laguna.shared", "laguna.mlp",
          "kv_write_rows", "sample", "guard")

_rd.MlaMoeCounts = LagunaCounts
_rd._mc.SalaCounts = LagunaCounts
_rd.SCOPES = SCOPES
run = _rd.run
