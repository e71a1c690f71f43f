METRIC = {
    "name": "expert_load_max_over_mean",
    "unit": "x",
    "layer": "model step",
    "source": "program_counter",
    "why": "The busiest held expert's (token, expert) pairs over the mean of the held experts', in the window, prefill and decode, summed over layers (tdn_gen_expert_pairs_total{expert}, counted on the device): 1.0-1.1 with random weights and a small selection bias; the number a skewed-routing cell will move.",
    "moves": "itl_p95_ms",
}


def read(run):
    pairs = [v for k, v in run.counters.items()
             if k.startswith("expert_pairs.")]
    if not pairs or not sum(pairs):
        return None
    return max(pairs) * len(pairs) / sum(pairs)
