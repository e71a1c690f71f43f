from benchmark.harness.readers import program_ms

METRIC = {
    "name": "prefill_dev_ms",
    "unit": "ms",
    "layer": "model step",
    "source": "device_trace",
    "why": "Device time of the prefill chunk program (jit_prefill_chunk) per launch, from the trace: it lengthens the iteration it rides in, for every slot that decodes in it.",
    "moves": "out_tokens_per_s",
}


def read(run):
    return program_ms(run, "jit_prefill_chunk")
