from benchmark.harness.readers import program_ms

METRIC = {
    "name": "prefill_chunk_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time of one prefill chunk (its FLOPs over the bf16 peak, or its least bytes over HBM bandwidth if longer; mean over the chunk positions of a prompt, from shapes: harness/sala_counts.py) over the device time of jit_prefill_chunk a launch.",
    "moves": "itl_p95_ms",
}


def read(run):
    ms = program_ms(run, "jit_prefill_chunk")
    c, p = run.counts, run.params
    if ms is None or run.peaks is None or not hasattr(c, "chunk_flops"):
        return None
    prompt = int(p["prompt_len"])
    size = int(p.get("prefill_chunk") or prompt)
    least = []
    for start in range(0, prompt, size):
        n = min(size, prompt - start)
        final = start + n >= prompt
        least.append(max(
            c.chunk_flops(start, n, final) / run.peaks["bf16_flops"],
            c.chunk_bytes(start, n, final) / run.peaks["hbm_bytes_per_s"]))
    return 100.0 * (sum(least) / len(least)) / (ms / 1e3)
