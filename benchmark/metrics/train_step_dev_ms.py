from benchmark.harness.readers import program_ms

METRIC = {
    "name": "train_step_dev_ms",
    "unit": "ms",
    "layer": "trainer",
    "source": "device_trace",
    "why": "Device time of the train step program (jit_step) per launch, from the trace.",
    "moves": "train_tokens_per_s",
}


def read(run):
    return program_ms(run, "jit_step")
