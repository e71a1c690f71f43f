"""Operations and least bytes of the MiniCPM-SALA block, from shapes alone.

The method names of `harness/counts.py` `Gpt2Counts` that the readers
call (`prefill_flops`, `decode_token_flops`, `decode_step_bytes`), so
that `serve_mfu_pct` and `decode_step_roofline` read this cell as they
read GPT-2's, plus the chunk's (`chunk_flops`, `chunk_bytes`) for
`prefill_chunk_roofline`.  The arithmetic follows ISSUE 26 section A,
whatever implements it; multiply-adds count two operations.

Per token: a `minicpm4` layer's projections cost 2 D (2 Hq Dh + 2 G Dh)
+ 2 Hq Dh D (q, gate, k, v, o), a `lightning-attn` layer's 2 D 4 H Dl +
2 H Dl D, both the SwiGLU's 6 D F; the linear recurrence 4 H Dl^2
(update and read-out of the state); softmax attention 4 Hq Dh for every
key ATTENDED (every key up to dense_len; beyond it the selected blocks'
keys: the first block, the blocks of the last `window_size` positions,
`topk` more) and 2 Hq Dh for every compressed key scored; the head 2 D V
where logits are used.

Least bytes: the matrices once in bfloat16; per decoding slot and sparse
layer the attended keys and values and the visible compressed keys once;
per slot and lightning layer the float32 state in and out.
"""

from __future__ import annotations

import numpy as np

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


class SalaCounts:
    def __init__(self, cfg: dict, params: dict | None = None):
        self.D, self.F = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
        self.V = int(cfg["vocab_size"])
        self.qd = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
        self.kvd = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
        self.ld = int(cfg["lightning_nh"]) * int(cfg["lightning_head_dim"])
        self.dl = int(cfg["lightning_head_dim"])
        self.Ls = sum(m == SPARSE for m in cfg["mixer_types"])
        self.Ll = sum(m == LIGHTNING for m in cfg["mixer_types"])
        sp = cfg["sparse_config"]
        self.ksz, self.stride = int(sp["kernel_size"]), int(sp["kernel_stride"])
        self.blk, self.topk = int(sp["block_size"]), int(sp["topk"])
        self.window, self.init = int(sp["window_size"]), int(sp["init_blocks"])
        self.dense_len = int(sp["dense_len"])
        mlp = 6 * self.D * self.F
        self.proj = (
            self.Ls * (2 * self.D * (2 * self.qd + 2 * self.kvd)
                       + 2 * self.qd * self.D + mlp)
            + self.Ll * (2 * self.D * 4 * self.ld + 2 * self.ld * self.D
                         + mlp + 4 * self.ld * self.dl))
        self.per_key = 4 * self.qd * self.Ls
        self.per_comp = 2 * self.qd * self.Ls
        self.logit = 2 * self.D * self.V
        p = params or {}
        # Where a decoding slot stands on average: the step's bytes are
        # per slot, and the reader hands over live keys summed over slots.
        lengths = p.get("lengths") or {}
        self.mean_pos = int(p.get("prompt_len", 0)) + (
            float(lengths.get("lo", 0)) + float(lengths.get("hi", 0))) / 4.0

    # ------------------------------------------------------- parameters
    def layer_params(self) -> int:
        """Matrices of the layers (gains left out)."""
        mlp = 3 * self.D * self.F
        return (self.Ls * (self.D * (2 * self.qd + 2 * self.kvd)
                           + self.qd * self.D + mlp)
                + self.Ll * (self.D * 4 * self.ld + self.ld * self.D + mlp))

    def matmul_params(self) -> int:
        """Weights a decode step reads once: layers and the untied head."""
        return self.layer_params() + self.D * self.V

    # -------------------------------------------------------- attention
    def attended(self, start: int, n: int = 1):
        """For positions start .. start + n - 1: (keys attended in a
        sparse layer, compressed keys scored), as integer arrays."""
        p = np.arange(int(start), int(start) + int(n), dtype=np.int64)
        dense = p + 1 <= self.dense_len
        first_local = np.maximum(p - (self.window - 1), 0) // self.blk
        whole = (np.minimum(self.init, first_local)
                 + np.minimum(self.topk, np.maximum(first_local - self.init, 0))
                 + (p // self.blk - first_local))
        keys = np.where(dense, p + 1, whole * self.blk + p % self.blk + 1)
        comp = np.where(dense | (p + 1 < self.ksz), 0,
                        (p + 1 - self.ksz) // self.stride + 1)
        return keys, comp

    def _attn_flops(self, start: int, n: int = 1) -> int:
        keys, comp = self.attended(start, n)
        return int(self.per_key * keys.sum() + self.per_comp * comp.sum())

    # ----------------------------------------------------------- decode
    def decode_token_flops(self, pos: int) -> int:
        return self.proj + self._attn_flops(pos) + self.logit

    def prefill_flops(self, prompt_len: int) -> int:
        """A whole prompt; the head at the last position only."""
        t = int(prompt_len)
        return t * self.proj + self._attn_flops(0, t) + self.logit

    def slot_step_bytes(self, pos: float, bytes_per_el: int = 2) -> float:
        """Least bytes one decoding slot at `pos` adds to a step."""
        keys, comp = self.attended(int(pos), 1)
        kv = 2 * self.kvd * int(keys[0]) + self.kvd * int(comp[0])
        state = 2 * 4 * self.ld * self.dl
        return self.Ls * bytes_per_el * kv + self.Ll * state

    def decode_step_bytes(self, live_keys: float, bytes_per_el: int = 2) -> float:
        """The least a decode step must move: the matrices once, and for
        each decoding slot its attended keys and values, its compressed
        keys and its states in and out.  `live_keys` is the sum over
        decoding slots of the positions each attends (as `Gpt2Counts`
        takes it); the slots are counted as live_keys / mean position."""
        slots = float(live_keys) / self.mean_pos if self.mean_pos else 0.0
        return bytes_per_el * self.matmul_params() \
            + slots * self.slot_step_bytes(self.mean_pos, bytes_per_el)

    # ------------------------------------------------------------ chunk
    def chunk_flops(self, start: int, size: int, final: bool = False) -> int:
        return int(size) * self.proj + self._attn_flops(start, size) \
            + (self.logit if final else 0)

    def chunk_bytes(self, start: int, size: int, final: bool = False,
                    bytes_per_el: int = 2) -> int:
        """Least bytes of one chunk: the layers' matrices once (the head
        where its logits are used), the slot's visible keys and values
        and compressed keys once, the chunk's own rows written, the
        states in and out."""
        end = int(start) + int(size)
        comp = max((end - self.ksz) // self.stride + 1, 0)
        kv = self.Ls * (2 * self.kvd * end + self.kvd * comp)
        state = self.Ll * 2 * 4 * self.ld * self.dl
        head = self.D * self.V if final else 0
        return bytes_per_el * (self.layer_params() + head + kv) + state
