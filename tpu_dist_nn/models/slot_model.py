"""What the continuous scheduler asks of a model: one small protocol.

The scheduler (:mod:`tpu_dist_nn.serving.continuous`) builds its three
programs — chunk prefill, slot copy, decode step — from four functions
and never learns what a layer is.  A model's config hands them over
with ``cfg.slot_model()``; the two implementers are
:class:`~tpu_dist_nn.models.transformer.TransformerConfig` (GPT-2's
block, one ``{k, v}`` cache: :mod:`.generate`) and
:class:`~tpu_dist_nn.models.sala.SalaConfig` (two kinds of layer, K/V
rows plus compressed keys plus recurrent state: :mod:`.sala`).
docs/MODEL_CONFIG.md says what the prefix pool and preemption require
of an implementer.
"""

from __future__ import annotations

import dataclasses
from typing import Callable


def _all_dense(pos) -> int:
    return 0


def _no_kernel(size, max_len) -> bool:
    return False


@dataclasses.dataclass(frozen=True)
class SlotModel:
    # (cfg, slots, max_len) -> cache pytree, every leaf (layers, slots, ...)
    init_slot_cache: Callable
    # (params, cfg, cache, slot, tokens (1, C), start) -> (logits (1, V), cache)
    prefill_chunk_into_cache: Callable
    # (params, cache, pos (S,), token (S,), cfg, active=(S,)) -> (logits (S, V), cache)
    decode_step_slots: Callable
    # (cache, src, dst) -> cache
    copy_cache_slot: Callable
    # (cfg, cache_extent) -> the goodput ledger's FLOP model
    flop_model: Callable
    # (cache) -> {kind: bytes}, for tdn_gen_cache_bytes
    cache_bytes: Callable
    # The cache holds state that is a prefix's only where a chunk ended:
    # a prefix tier may be copied out of a slot only at that boundary.
    recurrent: bool = False
    # (pos: int array) -> how many of these query positions the model
    # serves by its block selection and not by dense attention.  It
    # reads positions, not what the device ran.
    sparse_positions: Callable = _all_dense
    # (size, max_len) -> whether the program of a chunk of `size`
    # positions into a cache made for `max_len` is built with the
    # model's attention kernel (kernels/sparse_attend.py) and not its
    # XLA loop.  It reads the shapes, as the model's dispatch does.
    attend_kernel: Callable = _no_kernel
