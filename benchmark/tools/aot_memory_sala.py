"""Compile a `--model-config` cell's two programs for a described v5e
without a chip and print `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_sala.py minicpm-sala.longdoc-qa

`tools/aot_memory.py` builds GPT-2's programs by name; this builds what
the scheduler launches for a configuration that the program's own
loader reads (`tpu_dist_nn.models.sala.load_model_config`), through
`serving.continuous.slot_kernels`.  A compile that passes is not a chip
run.  `--hlo <dir>` also writes each program's optimised HLO there.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def compile_cell(name: str, hlo_dir: str | None = None) -> dict:
    """{program: compiled} of the cell's chunk prefill and decode step."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import lookup
    from benchmark.tools.aot_memory import _report
    from tpu_dist_nn.models.sala import load_model_config
    from tpu_dist_nn.serving.continuous import slot_kernels

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    cell = lookup.Cell(name)
    cfg = load_model_config(cell.driver().config_path(cell))
    p = cell.params
    S, T = int(p["slots"]), int(p["prompt_len"])
    M = T + int(p["max_new_tokens"]) - 1
    C = int(p.get("prefill_chunk") or T)
    params = shaped(jax.eval_shape(lambda: cell.reference.make_weights(
        cell.config, 0, cell.config["param_dtype"])))
    model = cfg.slot_model()
    cache = shaped(jax.eval_shape(lambda: model.init_slot_cache(cfg, S, M)))
    prefill, _, step = slot_kernels(cfg, 0.0, None, None)
    ints = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=chip)
    mask = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=chip)
    toks = jax.ShapeDtypeStruct((1, C), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
    out = {
        "step": step.lower(params, cache, ints, mask, ints, key).compile(),
        "prefill_chunk": prefill.lower(
            params, cache, scalar, toks, scalar, key).compile(),
    }
    _report(f"{name} decode step ({S} slots, extent {M})", out["step"])
    _report(f"{name} prefill chunk ({C} tokens)", out["prefill_chunk"])
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        for prog, compiled in out.items():
            with open(os.path.join(hlo_dir, f"{name}.{prog}.hlo.txt"),
                      "w") as f:
                f.write(compiled.as_text())
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--hlo", default=None)
    args = ap.parse_args()
    for cell_name in args.cells:
        compile_cell(cell_name, args.hlo)
