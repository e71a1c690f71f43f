METRIC = {
    "name": "backend_start_s",
    "unit": "s",
    "layer": "device",
    "source": "host_clock",
    "why": "The seconds of set-up inside jax.devices(): the TPU runtime coming up, 7 to 17 s by the machine and the call, in which no code of the repo runs. It is the part of setup_s that no PR can move and that moves by itself.",
    "moves": "setup_s",
}


def read(run):
    return run.setup_split.get("backend_s")
