"""`tools/scope_breakdown.py` for the SambaY family's scopes.

    python3 benchmark/tools/scope_breakdown_sambay.py <run output> <hlo dir>

That tool's list of scopes is a constant of MiniCPM-SALA's; this one
hands it this family's (`sambay.mamba`, `sambay.attn.window`,
`sambay.attn.full`, `sambay.attn.cross`, `sambay.gmu`, `sambay.mlp`,
with `sambay.tail` for what of a final chunk's layers 17-31 lies in none
of them) and runs it.  `<hlo dir>` holds what `tools/aot_memory_sala.py
--hlo` and `tools/aot_memory_body.py --hlo` wrote for the same tree.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import scope_breakdown  # noqa: E402

SCOPES = ("sambay.mamba", "sambay.attn.window", "sambay.attn.full",
          "sambay.attn.cross", "sambay.gmu", "sambay.mlp", "sambay.tail",
          "kv_write_rows", "sample", "guard")

if __name__ == "__main__":
    scope_breakdown.SCOPES = SCOPES
    sys.exit(scope_breakdown.main(*sys.argv[1:3]))
