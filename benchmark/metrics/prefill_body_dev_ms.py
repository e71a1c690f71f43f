from benchmark.harness.readers import program_ms

METRIC = {
    "name": "prefill_body_dev_ms",
    "unit": "ms",
    "layer": "model step",
    "source": "device_trace",
    "why": "Device time a launch of jit_prefill_body, the prefill chunk program that ends without logits (layers 0-16 and layer 17's K/V projection of a SambaY stack; SlotModel.prefill_body_into_cache), from the trace: it lengthens the iteration it rides in, for every slot that decodes in it. Silent where the program has no such program.",
    "moves": "itl_p95_ms",
}


def read(run):
    return program_ms(run, "jit_prefill_body")
