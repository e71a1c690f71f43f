METRIC = {
    "name": "setup_s",
    "unit": "s",
    "layer": "harness",
    "source": "host_clock",
    "why": "Process start to window open, all of it: imports, the TPU runtime coming up (backend_start_s), weights, compile or cache load, server start and warm-up, slot fill, first steps. Work moved into set-up shows here.",
}


def read(run):
    return run.setup_s
