"""Backend selection rules (utils/backend.py, cli.main).

* ``--platform tpu`` is asserted, not hoped for: on a process whose JAX
  resolved the CPU it exits non-zero and says what it found.
* Pure gRPC clients and the router never resolve a platform — they must
  not open a chip that the server they talk to owns.
* The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
  at one fixed path under the checkout.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from tpu_dist_nn import cli
from tpu_dist_nn.core.schema import save_examples, save_model
from tpu_dist_nn.testing.factories import random_inputs, random_model
from tpu_dist_nn.utils import backend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("platform")
    save_model(random_model([8, 6, 4], seed=0), d / "model.json")
    save_examples(random_inputs(5, 8), np.zeros(5, np.int32), d / "ex.json")
    return str(d / "model.json"), str(d / "ex.json")


def _argv(command, files):
    model, examples = files
    return {
        "up": ["up", "--config", model],
        "infer": ["infer", "--config", model, "--inputs", examples],
        "train": ["train", "--layers", "8,4", "--data", "synthetic"],
        "lm": ["lm", "--steps", "1"],
        "warmup": ["warmup", "--lm"],
        "doctor": ["doctor"],
        "infer --target": ["infer", "--target", "127.0.0.1:1",
                           "--inputs", examples],
        "infer --port": ["infer", "--port", "5101", "--inputs", examples],
        "lm --stream": ["lm", "--stream", "--target", "127.0.0.1:1"],
        "router": ["router", "--replicas", "127.0.0.1:1"],
        "metrics": ["metrics", "--target", "127.0.0.1:1"],
        "oracle": ["oracle", "--config", model, "--inputs", examples],
    }[command]


@pytest.mark.parametrize(
    "command", ["up", "infer", "train", "lm", "warmup", "doctor"]
)
def test_platform_tpu_on_a_cpu_process_exits_nonzero_naming_cpu(
        command, files, capsys):
    rc = cli.main(["--platform", "tpu", *_argv(command, files)])
    assert rc == 3
    err = capsys.readouterr().err
    assert "--platform tpu" in err and "'cpu'" in err


@pytest.mark.parametrize("command,uses", [
    ("up", True), ("infer", True), ("lm", True), ("doctor", True),
    ("infer --target", False), ("infer --port", False),
    ("lm --stream", False), ("router", False), ("metrics", False),
    ("oracle", False),
])
def test_only_device_commands_resolve_a_platform(command, uses, files):
    args = cli.build_parser().parse_args(_argv(command, files))
    assert cli._uses_backend(args) is uses


def test_clients_and_router_initialise_no_backend(files):
    """A real `tdn infer --target` against a live server, and a real
    `tdn router`, in a process told that a TPU comes first
    (JAX_PLATFORMS=tpu,cpu — what a chip host sets): both finish without
    any JAX backend ever coming up. The same process reports the
    compile cache's fixed place."""
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.serving import serve_engine

    model, examples = files
    engine = Engine.up(model)
    server, port = serve_engine(engine, 0, host="127.0.0.1")
    code = """
import json, sys
from tpu_dist_nn import cli
from tpu_dist_nn.utils.backend import compile_cache_dir
target, examples = sys.argv[1:]
infer = cli.main(["--platform", "tpu", "infer", "--target", target,
                  "--inputs", examples])
router = cli.main(["--platform", "tpu", "router", "--replicas", target,
                   "--port", "0", "--serve-seconds", "0.2"])
from jax._src import xla_bridge
print(json.dumps({"infer": infer, "router": router,
                  "backends": xla_bridge.backends_are_initialized(),
                  "cache": compile_cache_dir()}))
"""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "tpu,cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code, f"127.0.0.1:{port}", examples],
            capture_output=True, text=True, timeout=120, cwd=ROOT, env=env,
        )
    finally:
        server.stop(grace=0.2)
        engine.down()
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Total inference time" in proc.stdout
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"infer": 0, "router": 0, "backends": False,
                   "cache": os.path.join(ROOT, ".jax_cache")}


def test_compile_cache_dir_honours_env_and_sets_no_other(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    assert backend.compile_cache_dir() == "/x"
    assert backend.enable_compile_cache() == "/x"
    assert "jax_compilation_cache_dir" not in updates


def test_compile_cache_dir_unset_is_fixed_under_the_checkout(monkeypatch):
    """No temp dir, user, pid or time in the path: it is part of the
    cache key. The CPU tests add only a digest of the CPU's features."""
    assert jax.config.jax_platforms == "cpu"  # the conftest's pin
    with monkeypatch.context() as m:
        m.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = backend.compile_cache_dir()
    assert path == os.path.join(
        ROOT, ".jax_cache", f"cpu-{backend._cpu_fingerprint()}"
    )
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        # ... and it is the one this very suite compiles into.
        assert jax.config.jax_compilation_cache_dir == path


def test_require_platform_reports_the_device_it_found():
    assert backend.require_platform("auto") == backend.require_platform(
        "cpu"
    ) == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
          "count": len(jax.devices())}


@pytest.mark.parametrize("env,joins", [
    ({}, False),
    # What a one-host TPU machine sets. Joining a job there makes JAX
    # ask the cloud metadata server for peers the job does not have.
    ({"TPU_WORKER_ID": "0", "TPU_WORKER_HOSTNAMES": "localhost"}, False),
    ({"TPU_WORKER_ID": "1", "TPU_WORKER_HOSTNAMES": "host-0,host-1"}, True),
    ({"COORDINATOR_ADDRESS": "host-0:8476"}, True),
])
def test_only_a_several_host_environment_joins_a_job(monkeypatch, env, joins):
    from tpu_dist_nn.parallel.multihost import multihost_environment

    for name in ("COORDINATOR_ADDRESS", "TPU_WORKER_ID",
                 "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert multihost_environment() is joins
