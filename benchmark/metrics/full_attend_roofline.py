from benchmark.harness.scopes import scope_ms

METRIC = {
    "name": "full_attend_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time of the decode step's full attention (each decoding slot's live K and V rows once a full layer in bf16 over HBM bandwidth, or 4 H d FLOPs a row and layer over the bf16 peak if longer; harness/laguna_counts.py) over the device seconds under the scope laguna.attn.full a launch of jit_step. Live rows are those of the tokens streamed inside the traced span over the steps launched in it. Silent where the capture names no such scope.",
    "moves": "itl_p95_ms",
}

SCOPE = "laguna.attn.full"


def read(run):
    ms = scope_ms(run, "jit_step", SCOPE)
    c = run.counts
    if ms is None or run.peaks is None or not hasattr(c, "full_key_bytes"):
        return None
    live = c.span_keys(run)
    if not live:
        return None
    least = max(live * c.full_key_bytes() / run.peaks["hbm_bytes_per_s"],
                live * c.full_key_flops() / run.peaks["bf16_flops"])
    return 100.0 * least / (ms / 1e3)
