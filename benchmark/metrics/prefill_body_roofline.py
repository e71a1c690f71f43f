from benchmark.harness.readers import program_ms

METRIC = {
    "name": "prefill_body_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time of one prefill chunk that ends without logits (FLOPs of layers 0-16 and layer 17's K/V projection, the window layers' attended keys, the convolution and the scan over the bf16 peak, or its least bytes over HBM bandwidth if longer; mean over the body-chunk positions of a prompt, from shapes: harness/phi4flash_counts.py) over prefill_body_dev_ms.",
    "moves": "itl_p95_ms",
}


def read(run):
    ms = program_ms(run, "jit_prefill_body")
    c, p = run.counts, run.params
    if ms is None or run.peaks is None or not hasattr(c, "body_chunk_flops"):
        return None
    prompt = int(p["prompt_len"])
    size = int(p.get("prefill_chunk") or prompt)
    least = [max(c.body_chunk_flops(start, size) / run.peaks["bf16_flops"],
                 c.body_chunk_bytes(start, size)
                 / run.peaks["hbm_bytes_per_s"])
             for start in range(0, prompt - size, size)]
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (ms / 1e3)
