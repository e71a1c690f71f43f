METRIC = {
    "name": "train_tokens_per_s",
    "unit": "tokens/s",
    "layer": "service",
    "source": "host_clock",
    "why": "Whole steps completed in the window times the tokens a step, over the time to the last step's block_until_ready.",
}


def read(run):
    t = run.train
    if not t or not t["steps"]:
        return None
    return t["steps"] * t["tokens_per_step"] / t["seconds"]
