from benchmark.harness.readers import program_ms

METRIC = {
    "name": "decode_step_dev_ms",
    "unit": "ms",
    "layer": "model step",
    "source": "device_trace",
    "why": "Device time of the decode step program (jit_step) per launch, from the trace.",
    "moves": "itl_p95_ms",
}


def read(run):
    return program_ms(run, "jit_step")
