METRIC = {
    "name": "train_peak_hbm_gb",
    "unit": "GB",
    "layer": "device",
    "source": "program_counter",
    "why": "memory_stats()['peak_bytes_in_use'] of the fullest chip after the window, before the reference runs.",
    "moves": "train_tokens_per_s",
}


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
