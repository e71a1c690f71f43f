from benchmark.harness.readers import decode_step_least_s, program_ms

METRIC = {
    "name": "decode_step_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time a decode step could take (bf16 matrices once plus live keys and values once over HBM bandwidth, or its FLOPs over the bf16 peak if larger) over decode_step_dev_ms; bytes from shapes and live lengths, whatever implements the step.",
    "moves": "out_tokens_per_s",
}


def read(run):
    ms = program_ms(run, "jit_step")
    least = decode_step_least_s(run)
    if ms is None or least is None:
        return None
    return 100.0 * least / (ms / 1e3)
