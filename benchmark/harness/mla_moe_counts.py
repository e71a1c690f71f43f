"""Operations and least bytes of the Kimi-K2 (latent attention + routed experts) stack, from shapes alone.

The method names of `harness/counts.py` `Gpt2Counts` that the readers
call (`prefill_flops`, `decode_token_flops`, `decode_step_bytes`), so
that `serve_mfu_pct` and `decode_step_roofline` read this cell as they
read GPT-2's, plus what the two kernels' rooflines need
(`expert_step_bytes`, `expert_pair_flops`, `latent_position_bytes`,
`latent_key_flops`).  The arithmetic follows ISSUE 33's equations,
whatever implements them; multiply-adds count two operations, and where
in doubt it counts less, so that no share reads over 100.

Matrices of a layer (D 7168, 64 heads, r_q 1536, r_kv 512, d_n 128, d_r
64, d_v 128): attention W_qa D r_q + W_qb r_q H (d_n + d_r) + W_kva D
(r_kv + d_r) + W_kvb r_kv H (d_n + d_v) + W_o H d_v D = 101.12 M; a
SwiGLU of width F 3 D F (the shared expert and each routed one 44.04 M,
the dense layer's 396.4 M); the router D E = 2.75 M.  A layer of the
chip's share: attention, shared expert, router and the experts HELD
(12): 676.4 M.

Per position processed: 2 FLOPs a matrix parameter it passes (every
matrix but the routed experts'; of those, 6 D F_e for each (token,
expert) PAIR routed to an expert held here: `held_pairs_per_token`, the
expected k n_held / E a layer until the driver hands over the run's own
count from the program's counters), and the keys it attends: in a
decode step (the absorbed form) 2 H (r + r_kv) a key and layer plus the
two folds 2 H r_kv (d_n + d_v) a layer; in a prefill chunk (the
expanded form) 2 H (d_n + d_r + d_v) a key and layer plus 2 r_kv H (d_n
+ d_v) for each position expanded, once a chunk that attends it.  The
head 2 D V where a token is read.

Least bytes of a decode step: every matrix held once in bfloat16 (the
head, not the embedding, whose rows a step gathers), and per decoding
slot its live latent rows once a layer: (r_kv + d_r) 2 B = 1152 B a
position and layer, 6912 B a position over 6 layers.
"""

from __future__ import annotations

import math


class MlaMoeCounts:
    def __init__(self, cfg: dict, params: dict | None = None):
        g = lambda k, d=None: int(cfg[k] if d is None else cfg.get(k, d))  # noqa: E731
        self.D, self.H, self.V = g("hidden_size"), \
            g("num_attention_heads"), g("vocab_size")
        self.rq, self.rkv = g("q_lora_rank"), g("kv_lora_rank")
        self.dn, self.dr, self.dv = g("qk_nope_head_dim"), \
            g("qk_rope_head_dim"), g("v_head_dim")
        self.F0, self.Fe = g("intermediate_size"), g("moe_intermediate_size")
        self.L = g("num_hidden_layers")
        self.Ld = g("first_k_dense_replace", 0)
        self.Lm = self.L - self.Ld
        self.N = g("n_routed_experts")
        self.E = g("router_width", self.N)
        self.k = g("num_experts_per_tok")
        self.r = self.rkv + self.dr
        D, H = self.D, self.H
        self.attn_params = D * self.rq + self.rq * H * (self.dn + self.dr) \
            + D * self.r + self.rkv * H * (self.dn + self.dv) + H * self.dv * D
        self.expert_params = 3 * D * self.Fe
        self.router_params = D * self.E
        self.dense_params = self.attn_params + 3 * D * self.F0
        # Pairs (token, expert held here) a token and expert layer.
        self.held_pairs_per_token = self.k * self.N / self.E
        p = params or {}
        self.chunk = int(p.get("prefill_chunk") or p.get("prompt_len") or 1)
        # The scores' scale, (d_n + d_r)^-1/2 m^2 (YaRN's mscale_all_dim).
        rope = cfg.get("rope_scaling") or {}
        f = float(rope.get("factor", 1.0))
        m = 1.0 if f <= 1 else \
            0.1 * float(rope.get("mscale_all_dim", 0)) * math.log(f) + 1.0
        self.sigma = m * m / math.sqrt(self.dn + self.dr)

    # ------------------------------------------------------- parameters
    def layer_params(self) -> int:
        """An expert layer of the chip's share."""
        return self.attn_params + self.expert_params + self.router_params \
            + self.N * self.expert_params

    def token_params(self) -> int:
        """Matrix parameters every position passes: all but the routed
        experts'."""
        return self.Ld * self.dense_params + self.Lm * (
            self.attn_params + self.expert_params + self.router_params)

    def matmul_params(self) -> int:
        """Weights a decode step reads once: every layer's matrices,
        the experts held among them, and the head."""
        return self.Ld * self.dense_params + self.Lm * self.layer_params() \
            + self.D * self.V

    # ----------------------------------------------------- the kernels
    def latent_position_bytes(self) -> int:
        """One cached position, every layer: 6912 B."""
        return self.L * self.r * 2

    def latent_key_flops(self) -> int:
        """The absorbed form's products a live position and layer."""
        return 2 * self.H * (self.r + self.rkv)

    def expert_step_bytes(self) -> int:
        """The held experts' matrices once, every expert layer."""
        return 2 * self.Lm * self.N * self.expert_params

    def expert_pair_flops(self) -> int:
        return 2 * self.expert_params

    def _routed_flops(self, tokens: float) -> float:
        return tokens * self.Lm * self.held_pairs_per_token \
            * self.expert_pair_flops()

    # ----------------------------------------------------------- decode
    def decode_token_flops(self, pos: int) -> int:
        return int(2 * self.token_params() + self._routed_flops(1)
                   + self.L * (2 * self.H * self.rkv * (self.dn + self.dv)
                               + self.latent_key_flops() * (int(pos) + 1))
                   + 2 * self.D * self.V)

    def prefill_flops(self, prompt_len: int) -> int:
        """A whole prompt in chunks of `prefill_chunk`: every position's
        matrices and pairs, the keys each attends in the expanded form,
        each attended position expanded once a chunk, the head once."""
        t = int(prompt_len)
        keys = t * (t + 1) // 2
        expanded = sum(min(s + self.chunk, t)
                       for s in range(0, t, self.chunk))
        return int(t * 2 * self.token_params() + self._routed_flops(t)
                   + self.L * (2 * self.H * (self.dn + self.dr + self.dv)
                               * keys
                               + 2 * self.rkv * self.H * (self.dn + self.dv)
                               * expanded)
                   + 2 * self.D * self.V)

    def decode_step_bytes(self, live_keys: float,
                          bytes_per_el: int = 2) -> float:
        """`live_keys` is the sum over decoding slots of the positions
        each attends (as `Gpt2Counts` takes it)."""
        return bytes_per_el * self.matmul_params() \
            + float(live_keys) * self.latent_position_bytes()
