"""Put a traced run's device operations under the program's scopes.

    python3 benchmark/tools/scope_breakdown.py <run output> <hlo dir>

`<run output>` holds the lines of a `--trace 1` run of a cell whose
driver notes `device_ops` (`drivers/serve_model_config.py`: the eighty
longest, `[program/operation, seconds]`); `<hlo dir>` the optimised HLO
that `tools/aot_memory_sala.py --hlo` wrote for the same tree.  The
compiler numbers its operations alike here and on the chip, so each
operation's `op_name` metadata says which `jax.named_scope` it came
from.  Prints seconds by program and scope (`sala.lightning`,
`sala.sparse.select`, `sala.sparse.attend`, `sala.mlp`, else the
outermost other), as a share of the listed operations' time.
"""

from __future__ import annotations

import json
import os
import re
import sys

SCOPES = ("sala.sparse.select", "sala.sparse.attend", "sala.lightning",
          "sala.mlp", "kv_write_rows", "sample", "guard")
_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def scopes_of(hlo_text: str) -> dict:
    found = {}
    for line in hlo_text.splitlines():
        m = _OP.match(line)
        if m:
            path = m.group(2)
            inner = [s for s in SCOPES if s in path]
            # sala.mlp nests inside a mixer's scope: the innermost wins.
            found[m.group(1)] = max(inner, key=path.rfind) if inner \
                else "other"
    return found


def main(run_output: str, hlo_dir: str) -> int:
    ops = None
    with open(run_output) as f:
        for line in f:
            if line.startswith('{"device_ops"'):
                ops = json.loads(line)["device_ops"]
    if not ops:
        print("no device_ops line in", run_output)
        return 1
    tables = {}
    for name in os.listdir(hlo_dir):
        program = name.rsplit(".hlo.txt", 1)[0].rsplit(".", 1)[-1]
        with open(os.path.join(hlo_dir, name)) as f:
            tables["jit_" + program] = scopes_of(f.read())
    by = {}
    for full, seconds in ops:
        program, _, op = full.partition("/")
        # The trace appends the result's shape to an operation's name.
        scope = next((s for n, s in tables.get(program, {}).items()
                      if op == n or op.startswith(n + "_")), "unknown")
        by[(program, scope)] = by.get((program, scope), 0.0) + seconds
    total = sum(by.values())
    for (program, scope), seconds in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"{program:22s} {scope:20s} {seconds:8.4f} s "
              f"{100 * seconds / total:5.1f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
