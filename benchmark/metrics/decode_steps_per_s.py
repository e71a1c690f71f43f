METRIC = {
    "name": "decode_steps_per_s",
    "unit": "1/s",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "steps_total delta over the window: scheduler iterations a second.",
    "moves": "out_tokens_per_s",
}


def read(run):
    c = run.counters
    if not c.get("steps_total") or not run.window_s:
        return None
    return c["steps_total"] / run.window_s
