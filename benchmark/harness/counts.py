"""Operations and bytes of the GPT-2 block, from shapes alone.

The arithmetic is copied from `tpu_dist_nn/obs/goodput.py` `LMFlopModel`
(sound, exact integers) and kept here so that no later PR can move the
yardstick; the original is listed under Open questions in PERF.md.

Per token and layer: the q/k/v and output projections cost 8 d^2, the
MLP 4 d f; attention's scores and apply cost 4 d for every key position
that is live (causally visible), and the head 2 d V at each position
whose logits are used. Multiply-adds count two operations.
"""

from __future__ import annotations


class Gpt2Counts:
    def __init__(self, cfg: dict):
        self.d = int(cfg["n_embd"])
        self.L = int(cfg["n_layer"])
        self.f = int(cfg.get("n_inner") or 4 * self.d)
        self.V = int(cfg["vocab_size"])
        self.P = int(cfg["n_positions"])
        self.proj = self.L * (8 * self.d * self.d + 4 * self.d * self.f)
        self.attn_per_key = 4 * self.d * self.L
        self.logit = 2 * self.d * self.V

    # ------------------------------------------------------- parameters
    def matmul_params(self) -> int:
        """Weights that a decode step reads once: block matrices and
        the tied embedding used as the head."""
        return self.L * (4 * self.d * self.d + 2 * self.d * self.f) \
            + self.V * self.d

    def n_params(self) -> int:
        per_layer = (4 * self.d * self.d + 2 * self.d * self.f  # matrices
                     + 3 * self.d + self.d + self.f + self.d    # biases
                     + 4 * self.d)                              # 2 LayerNorms
        return self.L * per_layer + (self.V + self.P) * self.d + 2 * self.d

    # ----------------------------------------------------------- decode
    def decode_token_flops(self, pos: int) -> int:
        """One decoded token at position `pos` (attends pos + 1 keys)."""
        return self.proj + self.attn_per_key * (int(pos) + 1) + self.logit

    def prefill_flops(self, prompt_len: int) -> int:
        """A whole prompt: causal attention counted once, the head at
        the last position only (its logits give the first token)."""
        t = int(prompt_len)
        return t * self.proj + self.attn_per_key * (t * (t + 1) // 2) \
            + self.logit

    def decode_step_bytes(self, live_keys: int, bytes_per_el: int = 2) -> int:
        """The least a decode step must move: the matrices once in the
        compute type, and the live keys and values once.  `live_keys`
        is the sum over decoding slots of the positions each attends."""
        kv = 2 * self.L * self.d * int(live_keys)
        return bytes_per_el * (self.matmul_params() + kv)

    # ------------------------------------------------------------ train
    def train_token_flops(self, seq_len: int) -> int:
        """Forward and backward per token of a row of `seq_len` inputs:
        three times the forward, causal attention counted once (mean
        live keys (T + 1) / 2), recomputation not counted."""
        t = int(seq_len)
        fwd = self.proj + self.logit + self.attn_per_key * (t + 1) / 2
        return 3 * fwd
