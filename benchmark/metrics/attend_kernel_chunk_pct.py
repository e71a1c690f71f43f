from benchmark.harness.sched_columns import delta_with

METRIC = {
    "name": "attend_kernel_chunk_pct",
    "unit": "%",
    "layer": "kernels",
    "source": "program_counter",
    "why": "Share of the window's prefill chunk launches whose shapes tile for the model's Pallas attention kernel (ring columns attend_kernel_chunks over prefill_chunks; tdn_gen_attend_kernel_chunks_total over tdn_gen_prefill_chunks_total); the rest ran its XLA loop.",
    "moves": "itl_p95_ms",
}


def read(run):
    d = delta_with(run, "attend_kernel_chunks", "prefill_chunks")
    if d is None or not d["prefill_chunks"]:
        return None
    return 100.0 * d["attend_kernel_chunks"] / d["prefill_chunks"]
