"""Write one new key/value row per slot into the slot KV cache, in place.

The slot cache (:func:`tpu_dist_nn.models.generate.init_slot_cache`) is
``(L, S, H, Dh, M)`` with the position axis ``M`` in the TPU's lanes, so
the row a decode step adds to slot ``s`` is the single lane ``pos[s]``
of every ``(Dh, M)`` tile of that slot: ``L * H * Dh`` elements a slot,
7 MB a step on gpt2-medium where the cache is 5.4 GB.

XLA has no cheap way to say that. A select over the buffer rewrites all
of it; a scatter or a ``dynamic_update_slice`` with a dynamic LANE
offset makes layout assignment move the whole cache into a layout with
``M`` major and back (AOT for v5e; PERF.md section 6, PR 25). This
kernel visits the one 128-lane block of each ``(layer, slot)`` that
holds ``pos[s]`` (scalar-prefetched, so the block index is data),
replaces that lane and writes the block back over itself: the cache is
aliased to the output, and every block the grid does not visit — other
positions, the prefix pool's slots — stays bit for bit, as does an
inactive slot's block, which is rewritten with what it held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _kernel(pos_ref, active_ref, new_k_ref, new_v_ref, k_ref, v_ref,
            k_out, v_out):
    s = pl.program_id(1)
    block = k_ref.shape  # (1, 1, H, Dh, lanes)
    lanes, group = block[-1], new_k_ref.shape[-1]
    hit = (
        lax.broadcasted_iota(jnp.int32, block, 4) == pos_ref[s] % lanes
    ) & (active_ref[s] != 0)
    # The slot's new row is lane `s % group` of the (1, H, Dh, group)
    # block of new rows (slots in lanes, so that Dh is in the sublanes
    # here as it is in the cache): pick it out and spread it over the
    # lanes. Selects, never products: lanes past the array's edge hold
    # whatever they hold.
    mine = lax.broadcasted_iota(jnp.int32, new_k_ref.shape, 3) == s % group
    for new_ref, old_ref, out_ref in ((new_k_ref, k_ref, k_out),
                                      (new_v_ref, v_ref, v_out)):
        row = jnp.sum(
            jnp.where(mine, new_ref[...].astype(jnp.float32), 0.0),
            axis=3, keepdims=True,
        )[None]  # (1, 1, H, Dh, 1)
        out_ref[...] = jnp.where(
            hit, row, old_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def write_rows(k_cache: jnp.ndarray, v_cache: jnp.ndarray,
               new_k: jnp.ndarray, new_v: jnp.ndarray,
               pos: jnp.ndarray, active: jnp.ndarray):
    """``cache[:, s, :, :, pos[s]] = new[:, s]`` for the active ones of
    the first ``S`` slots, K and V in one launch.

    ``k_cache``/``v_cache`` ``(L, >=S, H, Dh, M)``; ``new_k``/``new_v``
    ``(L, S, H, Dh)``; ``pos (S,)`` int32 in ``[0, M)`` for every active
    slot (an inactive slot's is clipped and unused); ``active (S,)``
    bool. Returns the two caches; under ``jit`` with the caches donated
    the write is in place.
    """
    L, _, H, Dh, M = k_cache.shape
    S = new_k.shape[1]
    lanes, group = min(M, _LANES), min(S, _LANES)
    pos = jnp.clip(pos.astype(jnp.int32), 0, M - 1)
    cache_spec = pl.BlockSpec(
        (1, 1, H, Dh, lanes),
        lambda layer, s, pos_ref, active_ref: (
            layer, s, 0, 0, pos_ref[s] // lanes),
    )
    new_spec = pl.BlockSpec(
        (1, H, Dh, group),
        lambda layer, s, pos_ref, active_ref: (layer, 0, 0, s // group),
    )

    def call(interpret: bool):
        return pl.pallas_call(
            _kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(L, S),
                in_specs=[new_spec, new_spec, cache_spec, cache_spec],
                out_specs=[cache_spec, cache_spec],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
            ],
            # Operands count the two prefetched scalars first.
            input_output_aliases={4: 0, 5: 1},
            interpret=interpret,
            name="kv_write_rows",
        )

    # Chosen by the platform the program is LOWERED for, not by the
    # process's default backend: a compile for a described TPU from a
    # CPU-only process (benchmark/tools/aot_memory.py) gets the kernel.
    return lax.platform_dependent(
        pos, active.astype(jnp.int32),
        new_k.transpose(0, 2, 3, 1), new_v.transpose(0, 2, 3, 1),
        k_cache, v_cache,
        tpu=call(False), default=call(True),
    )


def _kernel_one(pos_ref, active_ref, new_ref, old_ref, out_ref):
    s = pl.program_id(1)
    block = old_ref.shape  # (1, 1, H, Dh, lanes)
    lanes, group = block[-1], new_ref.shape[-1]
    hit = (
        lax.broadcasted_iota(jnp.int32, block, 4) == pos_ref[s] % lanes
    ) & (active_ref[s] != 0)
    mine = lax.broadcasted_iota(jnp.int32, new_ref.shape, 3) == s % group
    row = jnp.sum(
        jnp.where(mine, new_ref[...].astype(jnp.float32), 0.0),
        axis=3, keepdims=True,
    )[None]
    out_ref[...] = jnp.where(
        hit, row, old_ref[...].astype(jnp.float32)
    ).astype(out_ref.dtype)


def write_row(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray,
              active: jnp.ndarray):
    """:func:`write_rows` for a model that caches ONE array a layer (a
    latent row, not a key and a value): ``cache[:, s, :, :, pos[s]] =
    new[:, s]`` for the active ones of the first ``S`` slots.  ``cache
    (L, >=S, H, Dh, M)``, ``new (L, S, H, Dh)``; the same visit of one
    128-lane block a layer and slot, the same aliasing."""
    L, _, H, Dh, M = cache.shape
    S = new.shape[1]
    lanes, group = min(M, _LANES), min(S, _LANES)
    pos = jnp.clip(pos.astype(jnp.int32), 0, M - 1)
    cache_spec = pl.BlockSpec(
        (1, 1, H, Dh, lanes),
        lambda layer, s, pos_ref, active_ref: (
            layer, s, 0, 0, pos_ref[s] // lanes),
    )
    new_spec = pl.BlockSpec(
        (1, H, Dh, group),
        lambda layer, s, pos_ref, active_ref: (layer, 0, 0, s // group),
    )

    def call(interpret: bool):
        return pl.pallas_call(
            _kernel_one,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(L, S),
                in_specs=[new_spec, cache_spec],
                out_specs=cache_spec,
            ),
            out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
            input_output_aliases={3: 0},
            interpret=interpret,
            name="kv_write_row",
        )

    return lax.platform_dependent(
        pos, active.astype(jnp.int32), new.transpose(0, 2, 3, 1), cache,
        tpu=call(False), default=call(True),
    )
