"""Compile the FOURTH program of a `--model-config` cell, the prefill
chunk that ends without logits, for a described v5e without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_body.py phi4-mini-flash.reason-decode

`tools/aot_memory_sala.py` builds the chunk program and the decode step
through `serving.continuous.slot_kernels`, which returns three programs;
a model whose protocol has `prefill_body_into_cache` gets one more from
`serving.continuous.slot_body_kernel`, and this compiles that one the
same way (and refuses a cell whose model has none).  A compile that
passes is not a chip run.  `--hlo <dir>` also writes its optimised HLO
there, beside the other two's, for `tools/scope_breakdown_sambay.py`.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def compile_body(name: str, hlo_dir: str | None = None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import lookup
    from benchmark.tools.aot_memory import _report
    from tpu_dist_nn.models.sala import load_model_config
    from tpu_dist_nn.serving.continuous import slot_body_kernel

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
    body = slot_body_kernel(cfg)
    if body is None:
        raise SystemExit(f"{name}: its model gives no chunk without logits")
    p = cell.params
    S, T = int(p["slots"]), int(p["prompt_len"])
    M = T + int(p["max_new_tokens"]) - 1
    C = int(p.get("prefill_chunk") or T)
    params = shaped(jax.eval_shape(lambda: cell.reference.make_weights(
        cell.config, 0, cell.config["param_dtype"])))
    cache = shaped(jax.eval_shape(
        lambda: cfg.slot_model().init_slot_cache(cfg, S, M)))
    toks = jax.ShapeDtypeStruct((1, C), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled = body.lower(params, cache, scalar, toks, scalar).compile()
    _report(f"{name} prefill body ({C} tokens, no logits)", compiled)
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{name}.prefill_body.hlo.txt"),
                  "w") as f:
            f.write(compiled.as_text())
    return compiled


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--hlo", default=None)
    args = ap.parse_args()
    for cell_name in args.cells:
        compile_body(cell_name, args.hlo)
