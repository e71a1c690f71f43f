"""`tools/scope_breakdown.py` for the Kimi-K2 family's scopes.

    python3 benchmark/tools/scope_breakdown_mla_moe.py <run output> <hlo dir>

That tool's list of scopes is a constant of MiniCPM-SALA's; this one
hands it this family's (`mla_moe.attn.project`, `mla_moe.attn.latent`
the step's attention over latent rows, `mla_moe.attn.expand` the chunk's
expansion and attention, `mla_moe.router`, `mla_moe.experts` the routed
experts held here, `mla_moe.shared`, `mla_moe.mlp` the dense layer's)
and runs it.  `<hlo dir>` holds what `tools/aot_memory_mla_moe.py --hlo`
wrote for the same tree.  A `--trace 1` run of the cell also notes the
capture's own sums (`{"scopes": ...}`, all operations, by the `tf_op`
the compiler kept: `drivers/repo_decode.py`).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import scope_breakdown  # noqa: E402

SCOPES = ("mla_moe.attn.project", "mla_moe.attn.latent",
          "mla_moe.attn.expand", "mla_moe.router", "mla_moe.experts",
          "mla_moe.shared", "mla_moe.mlp", "kv_write_row", "sample", "guard")

if __name__ == "__main__":
    scope_breakdown.SCOPES = SCOPES
    sys.exit(scope_breakdown.main(*sys.argv[1:3]))
