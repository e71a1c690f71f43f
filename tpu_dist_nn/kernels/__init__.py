"""Pallas TPU kernels: the hand-tuned hot path.

The reference leaned on vendored OpenBLAS for every FLOP
(``grpc_node.py:87``, SURVEY.md §2.2); the TPU build's equivalent lever
is Pallas kernels that shape data movement for the MXU/VMEM hierarchy
where it pays: the fused FCNN chain keeps inter-layer activations in
VMEM instead of round-tripping HBM between layers (XLA fuses
elementwise into matmuls but not matmul→matmul chains).

The serving kernels are imported where they are dispatched, by shape
(each has a ``tiles`` that says whether the shapes tile, and an XLA
path that stays as its oracle):

* :mod:`.kv_write` — the decode step's new rows into the slot cache in
  place (every family);
* :mod:`.sparse_attend` — a chunk's block-masked attention
  (:mod:`tpu_dist_nn.models.sala`), and without a selection a full
  layer's causal grouped-query attention (:mod:`tpu_dist_nn.models.laguna`);
* :mod:`.decode_attend` — a step's differential attention over the
  shared K/V, live tiles only (:mod:`tpu_dist_nn.models.sambay`);
* :mod:`.expand_attend` — a chunk's latent attention in its expanded
  form (:mod:`tpu_dist_nn.models.mla_moe`);
* :mod:`.latent_attend` — a step's absorbed latent attention over the
  latent rows, live tiles only (:mod:`tpu_dist_nn.models.mla_moe`).
"""

from tpu_dist_nn.kernels.fused_dense import (
    fcnn_fused_forward,
    fused_dense,
)
from tpu_dist_nn.kernels.flash_attention import (
    default_attn_fn,
    flash_attention,
)
from tpu_dist_nn.kernels.quantized import (
    fcnn_quantized_forward,
    forward_quantized,
    quantize_fcnn,
)

__all__ = [
    "default_attn_fn",
    "fcnn_fused_forward",
    "fcnn_quantized_forward",
    "flash_attention",
    "forward_quantized",
    "fused_dense",
    "quantize_fcnn",
]
