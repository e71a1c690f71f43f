"""What the compiled decode step does with the slot cache, without a chip.

    JAX_PLATFORMS=cpu python3 tools/aot_step_ops.py <decode cell> [...]
        [--prefix-blocks P] [--prefill 1] [--hlo-out <dir>]

Compiles the scheduler's own `step` program (serving/continuous.py
`slot_kernels`: the model step, the sampler and the numeric guard, cache
donated) at a benchmark cell's sizes for a described v5e, as
benchmark/tools/aot_memory.py does, and prints

* `memory_analysis()` of the program: arguments, aliased bytes,
  temporaries (a second whole cache shows here);
* every operation of the optimized HLO whose result holds at least one
  layer of one of the cache's two arrays (`S * M * H * Dh` elements), with
  its layout and the `named_scope` it came from.

An operation inside a fusion is not materialised and is not listed; an
operation that only renames a buffer (`parameter`, `tuple`, `bitcast`,
the `while` that carries the tuple, ...) is counted, not listed.  The
step is healthy when the list holds the in-place write of K and of V and
nothing else.  A compile is not a chip run: no time comes from here.
"""

from __future__ import annotations

import argparse
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Opcodes whose result is another name for a buffer that exists already.
ALIASING = frozenset({
    "parameter", "tuple", "get-tuple-element", "bitcast", "while",
    "conditional", "call", "optimization-barrier", "copy-start", "copy-done",
})

_HEADER = re.compile(r"^(ENTRY\s+)?(%?[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.*)$")
_ARRAY = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9,]*)\](\{[^}]*\})?")
_CALLS = re.compile(r"calls=(%?[\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def _result_and_opcode(rest: str):
    """Split `<result type> <opcode>(<operands>), <attributes>`.  The type
    is one array, whose layout may hold parentheses (`{1,0:T(8,128)}`), or
    a tuple of them in parentheses."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "({":
            depth += 1
        elif ch in ")}":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode, _, tail = rest[i + 1:].partition("(")
            return rest[:i], opcode, tail
    return rest, "", ""


def big_ops(hlo_text: str, min_elements: int) -> dict:
    """The operations of an optimized HLO module (`compiled.as_text()`)
    whose result, or one array of whose result tuple, has at least
    `min_elements` elements.

    Returns {"ops": [{"name", "opcode", "shapes": [(dtype, dims, layout,
    elements)], "computation", "scope"}], "aliasing": n}: operations of
    fused computations are left out, those of `ALIASING` only counted.
    """
    fused, blocks, current = set(), [], None
    for line in hlo_text.splitlines():
        head = _HEADER.match(line)
        if head and "=" not in line.split("(", 1)[0]:
            current = (head.group(2).lstrip("%"), [])
            blocks.append(current)
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        result, opcode, tail = _result_and_opcode(m.group(3))
        if opcode == "fusion":
            called = _CALLS.search(tail)
            if called:
                fused.add(called.group(1).lstrip("%"))
        current[1].append((m.group(2).lstrip("%"), result, opcode, tail))
    ops, aliasing = [], 0
    for computation, instrs in blocks:
        if computation in fused:
            continue
        for name, result, opcode, tail in instrs:
            shapes = []
            for dtype, dims, layout in _ARRAY.findall(result):
                dims = tuple(int(d) for d in dims.split(",") if d)
                shapes.append((dtype, dims, layout, math.prod(dims)))
            if not any(s[3] >= min_elements for s in shapes):
                continue
            if opcode in ALIASING:
                aliasing += 1
                continue
            scope = _OP_NAME.search(tail)
            target = _TARGET.search(tail)
            ops.append({
                "name": name,
                "opcode": opcode + (f" {target.group(1)}" if target else ""),
                "shapes": [s for s in shapes if s[3] >= min_elements],
                "computation": computation,
                "scope": scope.group(1) if scope else "",
            })
    return {"ops": ops, "aliasing": aliasing}


def render(found: dict) -> str:
    lines = []
    for op in found["ops"]:
        shapes = ", ".join(f"{d}[{','.join(map(str, dims))}]{layout}"
                           for d, dims, layout, _ in op["shapes"])
        lines.append(f"  {op['name']} ({op['opcode']}) {shapes}  "
                     f"in {op['computation']}  scope {op['scope'] or '-'}")
    lines.append(f"  {len(found['ops'])} listed, {found['aliasing']} that "
                 f"only rename a buffer")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--prefix-blocks", type=int, default=0,
                    help="compile with a prefix pool of this many blocks")
    ap.add_argument("--prefill", type=int, default=0,
                    help="1: list the prefill program and the slot copy too")
    ap.add_argument("--hlo-out", default=None,
                    help="write each program's optimized HLO text here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import lookup
    from benchmark.harness.program import transformer_config
    from benchmark.tools.aot_memory import _report
    from tpu_dist_nn.models.generate import init_slot_cache
    from tpu_dist_nn.serving.continuous import slot_kernels

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for name in args.cells:
        cell = lookup.Cell(name)
        cfg, p = cell.config, cell.params
        tcfg = transformer_config(cfg)
        S, T, P = int(p["slots"]), int(p["prompt_len"]), args.prefix_blocks
        M = T + int(p["max_new_tokens"]) - 1
        params = shaped(jax.eval_shape(
            lambda: cell.reference.make_weights(cfg, 0, cfg["param_dtype"])))
        cache = shaped(jax.eval_shape(lambda: init_slot_cache(tcfg, S + P, M)))
        layer = S * M * tcfg.n_heads * tcfg.head_dim
        key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
        prefill, copy, step = slot_kernels(tcfg, 0.0, None, None)
        ints, scalar = spec((S,), jnp.int32), spec((), jnp.int32)
        # The step as the loop launches it: `prev` and `first`, the
        # tokens still on the device, beside `tok`.
        programs = [("step", step, (params, cache, ints,
                                    spec((S,), jnp.bool_), ints, key, ints,
                                    scalar))]
        if args.prefill:
            programs += [
                ("prefill_chunk", prefill, (
                    params, cache, scalar, spec((1, T), jnp.int32), scalar,
                    key)),
                ("copy_cache_slot", copy, (cache, scalar, scalar)),
            ]
        for label, fn, shapes in programs:
            compiled = fn.lower(*shapes).compile()
            text = compiled.as_text()
            _report(f"{name} {label} ({S}+{P} slots, extent {M}; a layer of "
                    f"K is {layer} elements)", compiled)
            print(render(big_ops(text, layer)), flush=True)
            if args.hlo_out:
                os.makedirs(args.hlo_out, exist_ok=True)
                path = os.path.join(args.hlo_out,
                                    f"{name}.{label}.P{P}.hlo.txt")
                with open(path, "w") as f:
                    f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
