from benchmark.harness.lookup import metric_reader
from benchmark.harness.scopes import scope_ms

METRIC = {
    "name": "latent_attend_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time of the decode step's attention over the latent rows (each decoding slot's live rows once a layer in bf16 over HBM bandwidth, or 2 H (r + r_kv) FLOPs a live position and layer over the bf16 peak if longer; harness/mla_moe_counts.py) over the device seconds under the scope mla_moe.attn.latent a launch of jit_step. Live positions are those of the tokens streamed inside the traced span over the steps launched in it. Silent where the capture names no such scope.",
    "moves": "itl_p95_ms",
}

SCOPE = "mla_moe.attn.latent"
# The traced span on the host clock, as metrics/sparse_attend_roofline.py
# places it (one measured constant, kept there).
PROFILER_START_S = metric_reader("sparse_attend_roofline").PROFILER_START_S


def read(run):
    ms = scope_ms(run, "jit_step", SCOPE)
    c, w = run.counts, run.client
    if ms is None or run.peaks is None or w is None \
            or not hasattr(c, "latent_position_bytes"):
        return None
    step = run.trace["programs"].get("jit_step")
    if not step or not step["launches"]:
        return None
    t0 = w.t_open + min(1.0, run.args.seconds / 4) + PROFILER_START_S
    t1 = t0 + run.trace["window_s"]
    plen = int(run.params["prompt_len"])
    live = sum(plen + j for r in run.records
               for j, t in enumerate(r["tokens"]) if j > 0 and t0 <= t < t1)
    if not live:
        return None
    live /= step["launches"]
    least = max(live * c.latent_position_bytes()
                / run.peaks["hbm_bytes_per_s"],
                live * c.L * c.latent_key_flops() / run.peaks["bf16_flops"])
    return 100.0 * least / (ms / 1e3)
