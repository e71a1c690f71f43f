"""Multi-host (multi-process) initialization over DCN.

The reference scales by spawning more Docker containers on one bridge
network (``run_grpc_fcnn.py:83-155``); its cross-"host" transport is
gRPC. The TPU-native equivalent of adding hosts is JAX multi-process:
each host runs the same SPMD program, ``jax.distributed.initialize``
wires the processes together, and ``jax.devices()`` becomes the global
device list — the same ``Mesh``/``shard_map`` code then spans hosts,
with XLA routing collectives over ICI within a slice and DCN across
slices. No framework code changes between 1 host and N hosts; mesh axis
layout (``mesh.py``) keeps DCN-tolerant axes (data) outermost.
"""

from __future__ import annotations

import dataclasses
import functools as _functools
import os

import jax


@dataclasses.dataclass(frozen=True)
class HostTopology:
    """One process's view of the multi-host job."""

    process_id: int
    num_processes: int
    local_device_count: int
    global_device_count: int

    @property
    def is_multihost(self) -> bool:
        return self.num_processes > 1


def multihost_environment() -> bool:
    """Whether the environment describes a job of several processes: a
    coordinator address, or a TPU slice whose worker list names more
    than one host.

    ``TPU_WORKER_ID`` alone does not: every TPU VM sets it, a one-host
    one too, and joining a job there makes JAX ask the cloud metadata
    server for its peers — which fails on a host with no network, for a
    job that has none."""
    if "COORDINATOR_ADDRESS" in os.environ:
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return sum(bool(h.strip()) for h in hosts.split(",")) > 1


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> HostTopology:
    """Join (or skip joining) a multi-process JAX job; idempotent.

    With no arguments and no cluster environment this is a no-op
    single-process topology — the moral equivalent of the reference
    running all containers on one machine. With arguments (or under a
    TPU pod environment where JAX auto-detects them), wires this
    process into the job before any backend use.
    """
    explicit = coordinator_address is not None
    auto_env = multihost_environment()
    # NB: nothing before this point may touch the backend (even
    # jax.process_count() initializes it, which would make
    # jax.distributed.initialize fail with "must be called before any
    # JAX computations" on every multi-host launch).
    if explicit or auto_env:
        try:
            # Cross-process collectives on the CPU backend need a real
            # transport (the default deadlocks); gloo ships with jaxlib.
            # A no-op for TPU jobs (the flag only affects XLA:CPU) but
            # makes "N processes on one box" — the moral equivalent of
            # the reference's N containers on one bridge network — work
            # out of the box, which is also how the real-multi-process
            # tests run (tests/test_multihost_real.py).
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass  # older jaxlib without the option
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        except RuntimeError as e:
            # Second call in the same process (idempotent relaunch, the
            # reference's sweep-and-respawn contract run_grpc_fcnn.py:64-81).
            if "already" not in str(e).lower():
                raise
    return current_topology()


def current_topology() -> HostTopology:
    return HostTopology(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )


def assert_same_across_hosts_note() -> str:
    """The invariant multi-host callers must hold: every process runs the
    same program with the same mesh spec (single-controller-per-host
    SPMD). Returned as text so CLIs can print it in --help/errors."""
    return (
        "All hosts must execute the same program with identical mesh axes; "
        "per-host differences belong in data loading (process_id-sharded "
        "input files), never in model or mesh construction."
    )


def to_host_numpy(tree):
    """Materialize a pytree of jax.Arrays as host numpy on EVERY process.

    Single-process (or fully-addressable / fully-replicated leaves) this
    is plain ``np.asarray``. In a multi-process job, arrays sharded over
    a mesh that spans processes are not fully addressable, so reading
    them host-side (export, checkpoint save, eval metrics) first
    all-gathers them to a replicated layout — a collective, so EVERY
    process must call this at the same point even if only process 0
    consumes the result (the reference's analogue: every container
    participates in the reply chain even though only the client reads
    it, grpc_node.py:120-147).
    """
    import numpy as np

    def fetch(a):
        if not isinstance(a, jax.Array):
            return np.asarray(a)
        if a.is_fully_replicated or a.is_fully_addressable:
            return np.asarray(a)
        return np.asarray(_replicating_identity(a.sharding.mesh)(a))

    return jax.tree.map(fetch, tree)


@_functools.lru_cache(maxsize=16)
def _replicating_identity(mesh):
    """One jitted all-gather-to-replicated per mesh — a fresh
    ``jax.jit(lambda x: x)`` per call would retrace and recompile the
    gather every time (per eval batch, per checkpoint leaf)."""
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(lambda x: x, out_shardings=rep)
