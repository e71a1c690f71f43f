from benchmark.harness.scopes import scope_ms

METRIC = {
    "name": "moe_experts_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time of the held experts' FFN in a decode step, OF THE WORK whatever implements it (the held experts' matrices of every expert layer once in bf16 over HBM bandwidth, or the FLOPs of the step's pairs routed to them over the bf16 peak if longer; harness/laguna_counts.py) over the device seconds under the scope laguna.experts a launch of jit_step, whole launches of the capture on both sides. Silent where the capture names no such scope.",
    "moves": "itl_p95_ms",
}

SCOPE = "laguna.experts"


def read(run):
    ms = scope_ms(run, "jit_step", SCOPE)
    c = run.counts
    steps = run.counters.get("steps_total")
    if ms is None or run.peaks is None or not steps \
            or not hasattr(c, "window_key_bytes"):
        return None
    # Pairs a step routes to experts held here: the active lanes' share.
    lanes = run.counters.get("slot_steps_total", 0) / steps
    flops = lanes * c.Lm * c.held_pairs_per_token * c.expert_pair_flops()
    least = max(c.expert_step_bytes() / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops"])
    return 100.0 * least / (ms / 1e3)
