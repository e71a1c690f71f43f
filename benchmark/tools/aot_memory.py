"""Compile a cell's step programs for a described v5e without a chip and
print `memory_analysis()`: what the compiler says each program needs.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py <cell> [<cell> ...]

A compile that passes is not a chip run; it counts one program at a
time, not what else the process holds.  Run it before asking the chip
for a cell whose sizes changed.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _report(name, compiled):
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)  # noqa: E731
    print(f"{name}: arguments {gb(m.argument_size_in_bytes)} GB, outputs "
          f"{gb(m.output_size_in_bytes)} GB, aliased "
          f"{gb(m.alias_size_in_bytes)} GB, temporaries "
          f"{gb(m.temp_size_in_bytes)} GB, code "
          f"{gb(m.generated_code_size_in_bytes)} GB; live at once about "
          f"{gb(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes)} GB",
          flush=True)


def main(cells):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import lookup
    from benchmark.harness.program import transformer_config

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    for name in cells:
        cell = lookup.Cell(name)
        cfg, p = cell.config, cell.params
        driver = cell.driver()
        if driver.KIND == "serve":
            from tpu_dist_nn.models.generate import (
                decode_step_slots, init_slot_cache, prefill_chunk_into_cache)

            tcfg = transformer_config(cfg)
            S, T = int(p["slots"]), int(p["prompt_len"])
            M = T + int(p["max_new_tokens"]) - 1
            params = shaped(jax.eval_shape(
                lambda: cell.reference.make_weights(cfg, 0, cfg["param_dtype"])))
            cache = shaped(jax.eval_shape(
                lambda: init_slot_cache(tcfg, S, M)))
            ints = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=chip)
            mask = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=chip)

            def step(params, cache, pos, active, tok):
                logits, cache = decode_step_slots(params, cache, pos, tok,
                                                  tcfg, active=active)
                return jnp.argmax(logits, -1), cache

            _report(f"{name} decode step ({S} slots, extent {M})",
                    jax.jit(step, donate_argnums=(1,)).lower(
                        params, cache, ints, mask, ints).compile())
            toks = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)
            scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

            def prefill(params, cache, slot, tokens, start):
                logits, cache = prefill_chunk_into_cache(
                    params, tcfg, cache, slot, tokens, start)
                return jnp.argmax(logits, -1), cache

            _report(f"{name} prefill chunk ({T} tokens)",
                    jax.jit(prefill, donate_argnums=(1,)).lower(
                        params, cache, scalar, toks, scalar).compile())
        elif driver.KIND == "train":
            import optax

            from tpu_dist_nn.kernels.flash_attention import select_attention
            from tpu_dist_nn.models.transformer import lm_loss
            from tpu_dist_nn.train.lm_trainer import make_step_body
            from tpu_dist_nn.train.optimizers import build_optimizer

            tcfg = transformer_config(cfg, remat=bool(p["remat"]))
            opt = build_optimizer(float(p["learning_rate"]),
                                  weight_decay=float(p["weight_decay"]))
            params = jax.eval_shape(
                lambda: cell.reference.make_weights(cfg, 0, "float32"))
            state = shaped(jax.eval_shape(opt.init, params))
            params = shaped(params)
            rows = jax.ShapeDtypeStruct(
                (int(p["batch_rows"]), int(p["seq_len"]) + 1), jnp.int32,
                sharding=chip)
            # On a TPU the trainer takes `select_attention` (XLA attention under
            # 3072 positions, the flash kernel from there).
            body = make_step_body(
                lambda q, t: lm_loss(q, t, tcfg, select_attention), opt)
            _report(f"{name} train step ({p['batch_rows']} rows of "
                    f"{p['seq_len']})",
                    jax.jit(body, donate_argnums=(0, 1)).lower(
                        params, state, rows).compile())
            del optax


if __name__ == "__main__":
    main(sys.argv[1:])
