"""Operations and least bytes of the Phi-4-mini-flash (SambaY) stack, from shapes alone.

The method names of `harness/counts.py` `Gpt2Counts` that the readers
call (`prefill_flops`, `decode_token_flops`, `decode_step_bytes`), so
that `serve_mfu_pct` and `decode_step_roofline` read this cell as they
read GPT-2's, plus the body chunk's (`body_chunk_flops`,
`body_chunk_bytes`) for `prefill_body_roofline`.  The arithmetic follows
ISSUE 31's equations, whatever implements them; multiply-adds count two
operations, and where in doubt it counts less, so that no share reads
over 100.

Per position, the BODY (layers 0-16 and layer 17's K/V projection): 17
SwiGLU MLPs of 6 D F; 9 Mamba layers of 2 D 2E + 2 E (R + 2N) + 2 R E +
2 E D in matrices, 2 K E for the convolution and 6 E N for the scan
(decay times state, input, add, read-out); 8 window layers of 2 D (H d +
2 G d) + 2 H d D and 4 H d for every key attended, min(pos + 1, W) of
them; 2 D 2 G d for layer 17's keys and values.  The TAIL (layers 17-31
at a position whose token is read): 15 MLPs, layer 17's query and output
projections, 7 GMUs of 4 D E, 7 cross layers of 4 D H d, 4 H d for every
key up to the position in each of the 8 layers that attend the shared
K/V, and the tied head 2 D V.

Least bytes of a decode step: every matrix once in bfloat16 (the tied
embedding once: it is the head); per decoding slot the shared K and V up
to its position once FOR EACH of the 8 layers that attend them (no
on-chip memory holds 1.4 GB from one layer to the next), the 8 rings up
to min(pos, W) positions, and the 9 scan states (float32) and
convolution inputs in and out.
"""

from __future__ import annotations

import math

import numpy as np

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


class Phi4FlashCounts:
    def __init__(self, cfg: dict, params: dict | None = None):
        kinds = list(cfg["published"]["layer_kinds"])
        count = lambda k: sum(x == k for x in kinds)  # noqa: E731
        self.D, self.F = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
        self.V, self.W = int(cfg["vocab_size"]), int(cfg["sliding_window"])
        self.Hd = self.D
        self.Gd = int(cfg["num_key_value_heads"]) * (
            self.D // int(cfg["num_attention_heads"]))
        mamba = cfg.get("mamba", {})
        self.E = int(mamba.get("expand", 2)) * self.D
        self.N = int(mamba.get("d_state", 16))
        self.K = int(mamba.get("d_conv", 4))
        self.R = math.ceil(self.D / 16)
        self.Lm, self.Lw = count(MAMBA), count(WINDOW)
        self.Lg, self.Lc = count(GMU), count(CROSS)
        if count(FULL) != 1:
            raise ValueError("one full-attention layer is what is counted")
        self.body_layers = self.Lm + self.Lw
        self.tail_layers = 1 + self.Lg + self.Lc
        D, F, E = self.D, self.F, self.E
        self.mamba_mats = D * 2 * E + E * (self.R + 2 * self.N) \
            + self.R * E + E * D
        self.window_mats = D * (self.Hd + 2 * self.Gd) + self.Hd * D
        mlp = 6 * D * F
        self.body = self.Lm * (2 * self.mamba_mats + 2 * self.K * E
                               + 6 * E * self.N + mlp) \
            + self.Lw * (2 * self.window_mats + mlp) + 2 * D * 2 * self.Gd
        self.tail = 4 * D * self.Hd + mlp + self.Lg * (4 * D * E + mlp) \
            + self.Lc * (4 * D * self.Hd + mlp)
        self.win_key = 4 * self.Hd * self.Lw
        self.full_key = 4 * self.Hd * (1 + self.Lc)
        self.logit = 2 * D * self.V
        p = params or {}
        lengths = p.get("lengths") or {}
        # Where a decoding slot stands on average (as `SalaCounts`).
        self.mean_pos = int(p.get("prompt_len", 0)) + (
            float(lengths.get("lo", 0)) + float(lengths.get("hi", 0))) / 4.0

    # ------------------------------------------------------- parameters
    def body_params(self) -> int:
        """Matrices a body chunk reads (gains, biases and the
        convolution left out): layers 0-16, layer 17's K/V columns."""
        mlp = 3 * self.D * self.F
        return self.Lm * (self.mamba_mats + mlp) \
            + self.Lw * (self.window_mats + mlp) + self.D * 2 * self.Gd

    def matmul_params(self) -> int:
        """Weights a decode step reads once: every layer's matrices and
        the tied embedding as the head."""
        mlp = 3 * self.D * self.F
        tail = 2 * self.D * self.Hd + mlp \
            + self.Lg * (2 * self.D * self.E + mlp) \
            + self.Lc * (2 * self.D * self.Hd + mlp)
        return self.body_params() + tail + self.D * self.V

    # ----------------------------------------------------------- decode
    def _win_keys(self, start: int, n: int = 1) -> int:
        p = np.arange(int(start), int(start) + int(n), dtype=np.int64)
        return int(np.minimum(p + 1, self.W).sum())

    def decode_token_flops(self, pos: int) -> int:
        return self.body + self.win_key * self._win_keys(pos) + self.tail \
            + self.full_key * (int(pos) + 1) + self.logit

    def prefill_flops(self, prompt_len: int) -> int:
        """A whole prompt: the body over its positions, the tail and the
        head at the last one only."""
        t = int(prompt_len)
        return t * self.body + self.win_key * self._win_keys(0, t) \
            + self.tail + self.full_key * t + self.logit

    def slot_step_bytes(self, pos: float) -> float:
        """Least bytes one decoding slot at `pos` adds to a step."""
        kv = 2 * (1 + self.Lc) * 2 * self.Gd * pos
        rings = 2 * self.Lw * 2 * self.Gd * min(pos, self.W)
        state = 2 * self.Lm * (4 * self.E * self.N
                               + 2 * (self.K - 1) * self.E)
        return kv + rings + state

    def decode_step_bytes(self, live_keys: float,
                          bytes_per_el: int = 2) -> float:
        """`live_keys` is the sum over decoding slots of the positions
        each attends (as `Gpt2Counts` takes it); the slots are counted
        as live_keys / mean position."""
        slots = float(live_keys) / self.mean_pos if self.mean_pos else 0.0
        return bytes_per_el * self.matmul_params() \
            + slots * self.slot_step_bytes(self.mean_pos)

    # ------------------------------------------------------- body chunk
    def body_chunk_flops(self, start: int, size: int) -> int:
        return int(size) * self.body \
            + self.win_key * self._win_keys(start, size)

    def body_chunk_bytes(self, start: int, size: int) -> int:
        """Least bytes of a chunk that ends without logits: the body's
        matrices once, the embedding rows and the K/V rows of the chunk,
        the rings in and out, the states in and out."""
        n = int(size)
        rows = 2 * n * (self.D + 2 * self.Gd)
        rings = 2 * 2 * self.Lw * 2 * self.Gd * min(int(start) + n, self.W)
        state = 2 * self.Lm * (4 * self.E * self.N
                               + 2 * (self.K - 1) * self.E)
        return 2 * self.body_params() + rows + rings + state
