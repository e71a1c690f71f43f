"""Compile the Kimi-K2 cell's two programs for a described v5e without a
chip, print `memory_analysis()`, and list what each still does at the
size of the cache or of a layer's expanded keys and values.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_mla_moe.py kimi-k2.7-code.repo-decode

`tools/aot_memory_sala.py` builds any `--model-config` cell's chunk
program and decode step through `serving.continuous.slot_kernels`; this
runs it and then reads the optimised HLO with `tools/aot_step_ops.py`'s
`big_ops`: every operation whose result holds at least `--big` bytes'
worth of bfloat16 (default 64 MiB), by kind, shape and scope, leaving
out what only renames a buffer and what stands inside a fusion.  The step and the chunk should list
the row write (`kv_write_row`, aliased to the cache) and nothing else of
the cache's size, and no expanded K or V of a whole extent.  A compile
that passes is not a chip run.  `--hlo <dir>` also writes the HLO there,
for `tools/scope_breakdown_mla_moe.py`.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

def main(cells, hlo_dir=None, floor=64 << 20):
    from benchmark.tools.aot_memory_sala import compile_cell
    from tools.aot_step_ops import big_ops

    for name in cells:
        for prog, compiled in compile_cell(name, hlo_dir).items():
            # bfloat16 is the smallest type of anything large here.
            ops = big_ops(compiled.as_text(), floor // 2)["ops"]
            print(f"{name} {prog}: {len(ops)} results of at least "
                  f"{floor >> 20} MiB in bfloat16", flush=True)
            for op in ops[:24]:
                shapes = " ".join(f"{d}{list(dims)}"
                                  for d, dims, _, _ in op["shapes"])
                print(f"  {op['opcode']:30s} {shapes:40s} {op['name']} "
                      f"[{op['scope']}]")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--hlo", default=None)
    ap.add_argument("--big", type=int, default=64 << 20)
    args = ap.parse_args()
    main(args.cells, args.hlo, args.big)
