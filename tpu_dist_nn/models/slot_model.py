"""What the continuous scheduler asks of a model: one small protocol.

The scheduler (:mod:`tpu_dist_nn.serving.continuous`) builds its three
programs — chunk prefill, slot copy, decode step — from four functions
and never learns what a layer is.  A model's config hands them over
with ``cfg.slot_model()``; the five implementers are
:class:`~tpu_dist_nn.models.transformer.TransformerConfig` (GPT-2's
block, one ``{k, v}`` cache: :mod:`.generate`),
:class:`~tpu_dist_nn.models.sala.SalaConfig` (two kinds of layer, K/V
rows plus compressed keys plus recurrent state: :mod:`.sala`) and
:class:`~tpu_dist_nn.models.sambay.SambaYConfig` (five kinds of layer,
one K/V cache plus window rings plus convolution inputs and scan state,
and a fourth program: a chunk that ends without logits: :mod:`.sambay`)
and :class:`~tpu_dist_nn.models.mla_moe.MlaMoeConfig` (latent attention
over one compressed row a position, routed experts of which the chip
holds a share, and routing counts that accumulate on the device:
:mod:`.mla_moe`) and :class:`~tpu_dist_nn.models.laguna.LagunaConfig`
(full and window GQA layers with their own head counts, one K/V cache
beside window rings, and the same expert layer, :mod:`.experts`, under
a softmax router: :mod:`.laguna`).
docs/MODEL_CONFIG.md says what the prefix pool and preemption require
of an implementer.  :func:`load_model_config` reads a
``--model-config`` file into the config of its family.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
from typing import Callable

# model_type -> (module under tpu_dist_nn.models, its config class)
FAMILIES = {
    "minicpm_sala": ("sala", "SalaConfig"),
    "phi4flash": ("sambay", "SambaYConfig"),
    "kimi_k2": ("mla_moe", "MlaMoeConfig"),
    "laguna": ("laguna", "LagunaConfig"),
}


def load_model_config(path: str):
    """The config of ``tdn lm --model-config <file.json>``: the file's
    ``model_type`` names the family, whose ``from_dict`` reads the rest."""
    with open(path) as f:
        d = json.load(f)
    family = FAMILIES.get(d.get("model_type"))
    if family is None:
        known = ", ".join(repr(k) for k in FAMILIES)
        raise ValueError(
            f"{path}: model_type {d.get('model_type')!r} has no loader "
            f"(known: {known})")
    module = importlib.import_module(f"tpu_dist_nn.models.{family[0]}")
    return getattr(module, family[1]).from_dict(d)


def _all_dense(pos) -> int:
    return 0


def _no_kernel(size, max_len) -> bool:
    return False


def _whole_extent(slots, max_len) -> None:
    return None


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
    # model's Pallas attention kernel (kernels/sparse_attend.py for
    # SALA's block-masked attention and Laguna's full layers,
    # kernels/expand_attend.py for Kimi-K2's expanded latent
    # attention) and not its XLA loop.  It
    # reads the shapes, as the model's dispatch does.
    attend_kernel: Callable = _no_kernel
    # (params, cfg, cache, slot, tokens (1, C), start) -> cache: the
    # chunk without logits, for a model whose later layers see only the
    # position a token is read from.  Where a model gives it, the
    # scheduler launches it (a fourth program, `jit_prefill_body`) for
    # every chunk whose token nobody reads; it leaves the cache what
    # `prefill_chunk_into_cache` leaves.  None: every chunk is that one.
    prefill_body_into_cache: Callable | None = None
    # (slots, max_len) -> None, or `pos (int array) -> (fetched,
    # skipped)`: how many 128-lane position tiles of its K/V extent the
    # decode step of `slots` slots into a cache made for `max_len`
    # copies for queries at `pos`, and how many it leaves in HBM.  None:
    # the step reads the whole extent, and the scheduler counts nothing.
    # It reads the shapes and `pos`, as the model's kernel does.
    step_kv_tiles: Callable = _whole_extent
    # (cache) -> {name: small device array}, or None: counts the model's
    # programs accumulate on the device, inside the cache they are handed
    # and hand back (routing load is known nowhere else).  Every value
    # is a running int32 total, a scalar or a vector.  The scheduler
    # asks once at construction whether there are any, fetches them
    # together every few dozen launches and when it runs dry (never a
    # step), and keeps the growth as `routing_totals`.  None: nothing
    # is fetched.
    routing_counts: Callable | None = None
