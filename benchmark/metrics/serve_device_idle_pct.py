METRIC = {
    "name": "serve_device_idle_pct",
    "unit": "%",
    "layer": "device",
    "source": "device_trace",
    "why": "One minus the union of device-operation intervals over the traced part of the window.",
    "moves": "out_tokens_per_s",
}


def read(run):
    t = run.trace
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
