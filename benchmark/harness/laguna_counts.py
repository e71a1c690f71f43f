"""Operations and least bytes of the Laguna stack (window and full GQA layers, routed experts), from shapes alone.

The method names of `harness/counts.py` `Gpt2Counts` that the readers
call (`prefill_flops`, `decode_token_flops`, `decode_step_bytes`), so
that `serve_mfu_pct` and `decode_step_roofline` read this cell as they
read GPT-2's; those of `harness/mla_moe_counts.py` that the expert
readers take (`expert_step_bytes`, `expert_pair_flops`, `Lm`, `k`,
`held_pairs_per_token`); and what the two attention rooflines need
(`window_key_bytes`, `window_key_flops`, `full_key_bytes`,
`full_key_flops`, `span_keys`).  The arithmetic follows ISSUE 37's
equations, whatever implements them; multiply-adds count two
operations, and where in doubt it counts less, so that no share reads
over 100.

Matrices of a layer (D 3072, d 128, G 8 K/V heads, H 48 on a full layer
and 72 on a window layer): attention W_q D H d + W_k, W_v D G d each +
the heads' gates D H + W_o H d D (44.19 M full, 63.14 M window); a
SwiGLU of width F 3 D F (the shared expert and each routed one 9.44 M,
the dense layer's 113.2 M); the router D E = 0.79 M.

Per position processed: 2 FLOPs a matrix parameter it passes (every
matrix but the routed experts'; of those, 6 D F_e for each (token,
expert) PAIR routed to an expert held here: `held_pairs_per_token`, the
expected k n_held / E a layer until the driver hands over the run's own
count from the program's counters), and 4 H d a key it attends in a
layer: a full layer's every position up to its own, a window layer's
min(pos + 1, W).  The head 2 D V where a token is read.

Least bytes of a decode step: every matrix held once in bfloat16 (the
head, not the embedding, whose rows a step gathers), and per decoding
slot its live K and V rows once a layer: 2 G d 2 B = 4096 B a position
and layer, 8192 B over the two full layers; the window layers' rings
min(pos + 1, W) rows each, counted for slots at least as long as the
window (the readers hand over a sum of positions, not the slots).
"""

from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"


class LagunaCounts:
    def __init__(self, cfg: dict, params: dict | None = None):
        g = lambda k, d=None: int(cfg[k] if d is None else cfg.get(k, d))  # noqa: E731
        self.D, self.d = g("hidden_size"), g("head_dim")
        self.G, self.V = g("num_key_value_heads"), g("vocab_size")
        self.L = g("num_hidden_layers")
        self.kinds = tuple(cfg["layer_types"][:self.L])
        self.mlp = tuple(cfg["mlp_layer_types"][:self.L])
        self.heads = tuple(int(h) for h in
                           cfg["num_attention_heads_per_layer"][:self.L])
        self.F0, self.Fe = g("intermediate_size"), g("moe_intermediate_size")
        self.Fs = g("shared_expert_intermediate_size")
        self.N = g("num_experts")
        self.E = g("router_width", self.N)
        self.k = g("num_experts_per_tok")
        self.W = g("sliding_window")
        self.Ld = sum(1 for m in self.mlp if m == "dense")
        self.Lm = self.L - self.Ld
        self.Lf = sum(1 for a in self.kinds if a == FULL)
        self.Lw = self.L - self.Lf
        D, d, G = self.D, self.d, self.G
        self.attn_params = [D * H * d + 2 * D * G * d + D * H + H * d * D
                            for H in self.heads]
        self.expert_params = 3 * D * self.Fe
        self.shared_params = 3 * D * self.Fs
        self.router_params = D * self.E
        self.dense_params = 3 * D * self.F0
        # Pairs (token, expert held here) a token and expert layer.
        self.held_pairs_per_token = self.k * self.N / self.E
        p = params or {}
        self.plen = int(p.get("prompt_len") or 1)
        self.extent = self.plen + int(p.get("max_new_tokens") or 0)
        # 4 H d a key, summed over the layers of a kind.
        self._key = {kind: sum(4 * H * d for a, H in zip(self.kinds,
                                                        self.heads)
                               if a == kind) for kind in (FULL, WINDOW)}

    # ------------------------------------------------------- parameters
    def token_params(self) -> int:
        """Matrix parameters every position passes: all but the routed
        experts'."""
        return sum(self.attn_params) + self.Ld * self.dense_params \
            + self.Lm * (self.router_params + self.shared_params)

    def matmul_params(self) -> int:
        """Weights a decode step reads once: every layer's matrices,
        the experts held among them, and the head."""
        return self.token_params() + self.Lm * self.N * self.expert_params \
            + self.D * self.V

    # ----------------------------------------------------- the kernels
    def full_key_bytes(self) -> int:
        """One live position's K and V rows in every full layer: 8192 B."""
        return self.Lf * 2 * self.G * self.d * 2

    def full_key_flops(self) -> int:
        return self._key[FULL]

    def window_key_bytes(self) -> int:
        """One live ring row's K and V in every window layer: 12288 B."""
        return self.Lw * 2 * self.G * self.d * 2

    def window_key_flops(self) -> int:
        return self._key[WINDOW]

    def expert_step_bytes(self) -> int:
        """The held experts' matrices once, every expert layer."""
        return 2 * self.Lm * self.N * self.expert_params

    def expert_pair_flops(self) -> int:
        return 2 * self.expert_params

    def _routed_flops(self, tokens: float) -> float:
        return tokens * self.Lm * self.held_pairs_per_token \
            * self.expert_pair_flops()

    def _window_keys(self, t: int) -> int:
        """sum over positions p < t of min(p + 1, W)."""
        below = min(t, self.W - 1)
        return below * (below + 1) // 2 + (t - below) * self.W

    # ----------------------------------------------------------- decode
    def decode_token_flops(self, pos: int) -> int:
        n = int(pos) + 1
        return int(2 * self.token_params() + self._routed_flops(1)
                   + self._key[FULL] * n + self._key[WINDOW] * min(n, self.W)
                   + 2 * self.D * self.V)

    def prefill_flops(self, prompt_len: int) -> int:
        """A whole prompt: every position's matrices and pairs, the keys
        each attends, the head once."""
        t = int(prompt_len)
        return int(t * 2 * self.token_params() + self._routed_flops(t)
                   + self._key[FULL] * (t * (t + 1) // 2)
                   + self._key[WINDOW] * self._window_keys(t)
                   + 2 * self.D * self.V)

    def decode_step_bytes(self, live_keys: float,
                          bytes_per_el: int = 2) -> float:
        """`live_keys` is the sum over decoding slots of the positions
        each attends (as `Gpt2Counts` takes it); no slot attends more
        than the extent, so at least `live_keys / extent` slots decode,
        each reading a whole ring."""
        rings = self.W * float(live_keys) / max(self.extent, 1)
        return bytes_per_el * self.matmul_params() \
            + float(live_keys) * self.full_key_bytes() \
            + min(rings, float(live_keys)) * self.window_key_bytes()

    # -------------------------------------------------- the traced span
    def span_keys(self, run, cap: int | None = None) -> float:
        """Keys attended a launch of `jit_step` in the traced span: over
        the tokens streamed inside it (token j > 0 of a request is decoded
        at position prompt_len + j - 1 and attends prompt_len + j keys,
        at most `cap`) over the steps launched in it; 0 where there are
        none."""
        from benchmark.harness.lookup import metric_reader

        w = run.client
        step = (run.trace or {}).get("programs", {}).get("jit_step")
        if w is None or not step or not step["launches"]:
            return 0.0
        start = metric_reader("sparse_attend_roofline").PROFILER_START_S
        t0 = w.t_open + min(1.0, run.args.seconds / 4) + start
        t1 = t0 + run.trace["window_s"]
        plen = int(run.params["prompt_len"])
        live = sum(min(plen + j, cap or plen + j) for r in run.records
                   for j, t in enumerate(r["tokens"]) if j > 0 and t0 <= t < t1)
        return live / step["launches"]
