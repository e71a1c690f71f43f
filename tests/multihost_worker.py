"""Worker process for the REAL multi-host tests (test_multihost_real.py).

Each worker is a fresh interpreter that joins a 2-process JAX job over a
localhost coordinator (the gloo CPU collectives transport that
``initialize_multihost`` configures), runs one scenario, and prints
machine-checkable ``RESULT <json>`` lines the parent asserts on. This is
the true analogue of the reference's production topology — N cooperating
processes on one machine (its N containers on one bridge network,
run_grpc_fcnn.py:83-155) — where the virtual-device tests only emulate
the device count inside one process.
"""

import json
import os
import sys


def main() -> int:
    scenario, pid, port = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    from tpu_dist_nn.parallel.multihost import initialize_multihost

    topo = initialize_multihost(f"localhost:{port}", 2, pid)
    assert topo.num_processes == 2, topo
    assert topo.global_device_count == 8, topo

    out = globals()[f"scenario_{scenario}"]()
    print(f"RESULT {json.dumps({'pid': pid, **out})}", flush=True)
    return 0


def scenario_collectives() -> dict:
    """Cross-process psum ground truth: a global array spanning both
    processes' devices reduces to the full-set sum on every host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_dist_nn.data.feed import global_batch, shard_for_host
    from tpu_dist_nn.parallel.mesh import AXIS_DATA, MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=8))
    rows = np.arange(32, dtype=np.float32).reshape(8, 4)
    local = shard_for_host(rows)
    ga = global_batch(mesh, P(AXIS_DATA), local)
    total = float(jax.jit(lambda a: a.sum())(ga))
    return {"sum": total, "expect": float(rows.sum())}


def scenario_train_pipelined(schedule: str = "gpipe") -> dict:
    """Data-parallel pipelined training across processes: both hosts must
    see the IDENTICAL loss stream and end with identical weights, equal
    to the single-process result on the same global data (computed in
    the parent)."""
    import numpy as np

    from tpu_dist_nn.core.schema import partition_model
    from tpu_dist_nn.data.datasets import Dataset
    from tpu_dist_nn.data.feed import shard_for_host
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.multihost import to_host_numpy
    from tpu_dist_nn.parallel.pipeline import build_pipeline_params
    from tpu_dist_nn.testing.factories import random_model
    from tpu_dist_nn.train.pipeline_trainer import TrainConfig, train_pipelined

    mesh = build_mesh(MeshSpec(stage=2, data=4))
    model = random_model([12, 10, 6], seed=0)
    params = build_pipeline_params(partition_model(model, [1, 1]))
    full = _global_dataset()
    sx, sy = shard_for_host(full.x, full.y)
    data = Dataset(sx, sy, full.num_classes)
    cfg = TrainConfig(epochs=2, batch_size=32, learning_rate=1e-2, seed=0)
    params, history = train_pipelined(
        params, mesh, data, cfg, num_microbatches=4, eval_data=full,
        schedule=schedule,
    )
    w = to_host_numpy(params.weights.w)
    return {
        "losses": [round(h["loss"], 6) for h in history],
        "eval_acc": history[-1]["eval"]["accuracy"],
        "w_digest": float(np.abs(w).sum()),
        "w00": float(w[0, 0, 0, 0]),
    }


def scenario_train_pipelined_1f1b() -> dict:
    return scenario_train_pipelined("1f1b")


def scenario_train_lm_pipelined() -> dict:
    """Pipelined LM training across processes with the global-batch
    feed; both hosts must report the identical loss stream."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_dist_nn.data.feed import global_batch, shard_for_host
    from tpu_dist_nn.models.transformer import TransformerConfig, init_transformer
    from tpu_dist_nn.parallel.mesh import AXIS_DATA, MeshSpec, build_mesh
    from tpu_dist_nn.parallel.multihost import to_host_numpy
    from tpu_dist_nn.train.lm_trainer import LMTrainConfig, train_lm
    import jax

    mesh = build_mesh(MeshSpec(stage=2, data=4))
    cfg = TransformerConfig(
        vocab_size=31, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_seq_len=12
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, cfg.vocab_size, (64, 13)).astype(np.int32)
    local_rows = shard_for_host(rows)
    batches = [local_rows[i * 8:(i + 1) * 8] for i in range(4)]
    globalize = lambda b: global_batch(mesh, P(AXIS_DATA, None), b)  # noqa: E731
    params, history = train_lm(
        params, cfg, batches,
        LMTrainConfig(steps=4, log_every=1),
        mesh=mesh, num_stages=2, num_microbatches=2, globalize=globalize,
    )
    tok = to_host_numpy(params["tok_embed"])
    return {
        "losses": [round(h["loss"], 6) for h in history],
        "tok_digest": float(np.abs(tok).sum()),
    }


def scenario_train_lm_3d() -> dict:
    """PP x TP x DP across REAL processes, under BOTH wire layouts.

    Phase 1 — the production layout (`build_mesh`: data outermost, so
    the DATA-axis gradient all-reduce is what rides the DCN transport
    while stage ppermutes and Megatron psums stay intra-host — the
    canonical DCN/ICI split the mesh module documents). Phase 2 — a
    hand-made mesh with STAGE outermost, so every tick's forward and
    backward inter-stage ppermute hand-off crosses the process
    boundary instead. Same math either way: both hosts must see one
    identical loss stream across BOTH layouts, proving the 3D step is
    wire-placement-invariant on the real 2-process topology."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_dist_nn.data.feed import global_batch, shard_for_host
    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.parallel.mesh import (
        AXIS_DATA,
        AXIS_MODEL,
        AXIS_STAGE,
        MeshSpec,
        build_mesh,
    )
    from tpu_dist_nn.parallel.multihost import to_host_numpy
    from tpu_dist_nn.parallel.transformer_pipeline import shard_blocks_pp_tp
    from tpu_dist_nn.train.lm_trainer import (
        LMTrainConfig,
        make_pipeline_lm_train_step,
        train_lm,
    )

    cfg = TransformerConfig(
        vocab_size=31, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_seq_len=12,
    )
    base = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, cfg.vocab_size, (64, 13)).astype(np.int32)
    local_rows = shard_for_host(rows)

    meshes = {
        # data outermost: DCN carries the data all-reduce.
        "dcn_data": build_mesh(MeshSpec(stage=2, model=2, data=2)),
        # stage outermost: DCN carries every inter-stage ppermute.
        # (Auto axis types, like build_mesh: jax 0.9's make_mesh
        # defaults to Explicit, which flips eager ops into
        # sharding-in-types mode.)
        "dcn_stage": jax.make_mesh(
            (2, 2, 2), (AXIS_STAGE, AXIS_MODEL, AXIS_DATA),
            axis_types=(jax.sharding.AxisType.Auto,) * 3,
        ),
    }
    only = os.environ.get("TDN_3D_ONLY")
    if only:
        meshes = {only: meshes[only]}
    out = {}
    for name, mesh in meshes.items():
        params = dict(
            base, blocks=shard_blocks_pp_tp(base["blocks"], cfg, 2, 2)
        )
        if name == "dcn_data":
            # data spans the hosts: per-process stripes through the
            # global-batch assembler (the production feed).
            batches = [local_rows[i * 8:(i + 1) * 8] for i in range(4)]
            globalize = lambda b, m=mesh: global_batch(  # noqa: E731
                m, P(AXIS_DATA, None), b
            )
        else:
            # stage spans the hosts: BOTH data shards live on every
            # process, so per-process stripes would feed different
            # rows into replicated shards (the documented
            # N-diverging-models hazard). Every host supplies the
            # FULL global batch; make_array_from_callback slices each
            # addressable shard out of it.
            from jax.sharding import NamedSharding

            # The same global batches the dcn_data feed assembles:
            # [process 0's stripe; process 1's stripe] per step.
            batches = [
                np.concatenate(
                    [rows[i * 8:(i + 1) * 8],
                     rows[32 + i * 8:32 + (i + 1) * 8]]
                )
                for i in range(4)
            ]
            sharding = NamedSharding(mesh, P(AXIS_DATA, None))
            globalize = lambda b, sh=sharding: jax.make_array_from_callback(  # noqa: E731
                b.shape, sh, lambda idx, bb=b: bb[idx]
            )
        step_fn = lambda opt, m=mesh: make_pipeline_lm_train_step(  # noqa: E731
            m, cfg, 2, 2, opt, schedule="1f1b", tensor_parallel=2
        )
        params, history = train_lm(
            params, cfg, batches,
            LMTrainConfig(steps=4, log_every=1),
            mesh=mesh, num_stages=2, num_microbatches=2,
            globalize=globalize, step_fn=step_fn,
        )
        tok = to_host_numpy(params["tok_embed"])
        out[f"losses_{name}"] = [round(h["loss"], 6) for h in history]
        out[f"tok_digest_{name}"] = float(np.abs(tok).sum())
    return out


def scenario_step_parity() -> dict:
    """ONE optimizer step on a FIXED global batch: loss and updated
    weights are row-partition-invariant, so this must match the parent's
    single-process step bit-for-tolerance — exact numerical parity of
    the cross-host path."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_dist_nn.core.schema import partition_model
    from tpu_dist_nn.data.feed import global_batch, shard_for_host
    from tpu_dist_nn.parallel.mesh import AXIS_DATA, MeshSpec, build_mesh
    from tpu_dist_nn.parallel.multihost import to_host_numpy
    from tpu_dist_nn.parallel.pipeline import build_pipeline_params
    from tpu_dist_nn.testing.factories import random_model
    from tpu_dist_nn.train.pipeline_trainer import (
        make_pipeline_train_step,
        prepare_pipeline_batch,
    )

    mesh = build_mesh(MeshSpec(stage=2, data=4))
    model = random_model([12, 10, 6], seed=0)
    params = build_pipeline_params(partition_model(model, [1, 1]))
    full = _global_dataset()
    x, y = shard_for_host(full.x[:32], full.y[:32])
    xs, labels, mask = prepare_pipeline_batch(params.meta, x, y, 4, 2)
    xs, labels, mask = global_batch(
        mesh, (P(None, AXIS_DATA, None), P(None, AXIS_DATA), P(None, AXIS_DATA)),
        xs, labels, mask,
    )
    opt = optax.adam(1e-2)
    step = make_pipeline_train_step(mesh, params.meta, 4, opt)
    w, _, loss = step(params.weights, opt.init(params.weights), xs, labels, mask)
    wn = to_host_numpy(w.w)
    return {"loss": float(loss), "w_digest": float(np.abs(wn).sum())}


def scenario_train_lm_zero1(make_name: str = "make_zero_lm_train_step") -> dict:
    """ZeRO-1 / FSDP data-parallel LM training across processes with the
    global-batch feed: identical loss streams on both hosts."""
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import jax
    import optax

    from tpu_dist_nn.data.feed import global_batch, shard_for_host
    from tpu_dist_nn.models.transformer import TransformerConfig, init_transformer
    from tpu_dist_nn.parallel.mesh import AXIS_DATA, MeshSpec, build_mesh
    from tpu_dist_nn.parallel import zero
    from tpu_dist_nn.parallel.multihost import to_host_numpy

    mesh = build_mesh(MeshSpec(data=8))
    cfg = TransformerConfig(
        vocab_size=29, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_seq_len=12
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(2)
    rows = rng.integers(0, cfg.vocab_size, (64, 13)).astype(np.int32)
    local = shard_for_host(rows)
    optimizer = optax.adam(1e-3)
    step = getattr(zero, make_name)(mesh, cfg, optimizer, params)
    opt_state = step.init_opt_state(params)
    losses = []
    for i in range(3):
        batch = global_batch(mesh, P(AXIS_DATA, None), local[i * 8:(i + 1) * 8])
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(round(float(loss), 6))
    tok = to_host_numpy(params["tok_embed"])
    return {"losses": losses, "tok_digest": float(np.abs(np.asarray(tok)).sum())}


def scenario_train_lm_fsdp() -> dict:
    return scenario_train_lm_zero1("make_fsdp_lm_train_step")


def scenario_pipeline_infer_crosshost() -> dict:
    """Pure cross-host pipeline inference: 8 stages spanning 2 processes
    (4 each), data axis 1 — batches replicate across hosts and the
    hand-off rides the inter-process links; outputs must be identical
    on every host."""
    import numpy as np

    from tpu_dist_nn.core.schema import partition_model
    from tpu_dist_nn.parallel.mesh import MeshSpec, build_mesh
    from tpu_dist_nn.parallel.multihost import to_host_numpy
    from tpu_dist_nn.parallel.pipeline import build_pipeline_params, pipeline_forward
    from tpu_dist_nn.testing.factories import random_model

    mesh = build_mesh(MeshSpec(stage=8, data=1))
    model = random_model([20, 18, 16, 14, 12, 10, 8, 7, 6], seed=11)
    params = build_pipeline_params(partition_model(model, [1] * 8))
    rng = np.random.default_rng(4)
    x = rng.uniform(0, 1, (12, 20)).astype(np.float32)
    out = to_host_numpy(pipeline_forward(mesh, params, x, num_microbatches=2))
    return {
        "digest": float(np.abs(out).sum()),
        "row0": [round(float(v), 8) for v in out[0]],
    }


def scenario_checkpoint_resume() -> dict:
    """Multi-host checkpoint round trip with NON-shared filesystems:
    only process 0's directory receives files (save_pytree gathers on
    every process, writes on 0), and resume_or_init must broadcast the
    restored state so a host with an empty directory resumes in sync
    instead of silently restarting from scratch."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist_nn.checkpoint.store import (
        AsyncCheckpointManager,
        resume_or_init,
    )

    pid = jax.process_index()
    # DIFFERENT directory per process = no shared FS.
    d = tempfile.mkdtemp(prefix=f"tdn_mh_ck_p{pid}_")
    mgr = AsyncCheckpointManager(d, keep=2)
    state = {"w": jnp.arange(8.0) * (1.0 + pid * 0.0), "step_marker": jnp.ones(())}
    # Both processes call save in lockstep (the collective contract).
    saved = {"w": state["w"] * 3.0, "step_marker": state["step_marker"] * 7.0}
    mgr.save(5, saved, metadata={"note": "mh"})
    mgr.wait()
    n_files = len(list(__import__("pathlib").Path(d).glob("ckpt_*")))
    # Fresh manager on the same per-process dir: process 1's is empty.
    mgr2 = AsyncCheckpointManager(d, keep=2)
    step, restored = resume_or_init(mgr2, state)
    return {
        "n_files": n_files,
        "step": step,
        "w_digest": float(np.abs(np.asarray(restored["w"])).sum()),
        "marker": float(np.asarray(restored["step_marker"])),
    }


def scenario_checkpoint_resume_zero1() -> dict:
    """Multi-host ZeRO-1 save AND resume with non-shared filesystems:
    the opt state is jitted with sharded out_shardings, so its leaves
    span the processes — on process 1 they are NON-addressable. Saving
    gathers (fine); the regression under test is resume_or_init, whose
    broadcast on non-source processes must build its payload from leaf
    METADATA (np.zeros_like on a non-addressable array raises). Ends
    with a retention-window violation that must raise the SAME
    ValueError on BOTH processes (the validation verdict is broadcast
    after the gather; a process-0-only raise would hang the peer in the
    collective)."""
    import tempfile

    import jax
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_dist_nn.checkpoint.store import AsyncCheckpointManager, resume_or_init
    from tpu_dist_nn.data.feed import global_batch, shard_for_host
    from tpu_dist_nn.models.transformer import TransformerConfig, init_transformer
    from tpu_dist_nn.parallel import zero
    from tpu_dist_nn.parallel.mesh import AXIS_DATA, MeshSpec, build_mesh
    from tpu_dist_nn.parallel.multihost import to_host_numpy

    pid = jax.process_index()
    mesh = build_mesh(MeshSpec(data=8))
    cfg = TransformerConfig(
        vocab_size=29, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_seq_len=12
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    rows = rng.integers(0, cfg.vocab_size, (16, 13)).astype(np.int32)
    local = shard_for_host(rows)
    step_fn = zero.make_zero_lm_train_step(mesh, cfg, optax.adam(1e-3), params)
    opt_state = step_fn.init_opt_state(params)  # sharded across processes
    batch = global_batch(mesh, P(AXIS_DATA, None), local)
    params, opt_state, _ = step_fn(params, opt_state, batch)

    d = tempfile.mkdtemp(prefix=f"tdn_mh_z1_p{pid}_")  # no shared FS
    mgr = AsyncCheckpointManager(d, keep=2)
    state = {"params": params, "opt_state": opt_state}
    mgr.save(7, state)
    mgr.wait()

    # Fresh manager; the template is the LIVE sharded state — its
    # opt-state leaves are non-addressable on process 1.
    mgr2 = AsyncCheckpointManager(d, keep=2)
    step, restored = resume_or_init(mgr2, state)
    tok = np.abs(np.asarray(restored["params"]["tok_embed"])).sum()
    saved_tok = np.abs(np.asarray(to_host_numpy(params["tok_embed"]))).sum()

    # Retention violation: keep=2 with steps {7, 9} on disk makes step 1
    # too old. Only process 0's manifest knows that; both must raise.
    mgr2.save(9, state)
    mgr2.wait()
    retention_raised = False
    try:
        mgr2.save(1, state)
    except ValueError:
        retention_raised = True
    mgr2.wait()
    return {
        "step": step,
        "tok_digest": float(tok),
        "saved_tok_digest": float(saved_tok),
        "retention_raised": retention_raised,
    }


def scenario_checkpoint_io_failure_agreed() -> dict:
    """A checkpoint-write IO failure on process 0 (the only writer)
    must raise on BOTH processes — not leave process 1 marching into
    the next training-step collective alone. Induced by pointing
    process 0's writer at a directory that vanished between saves
    (chmod tricks don't bite: tests run as root)."""
    import pathlib
    import tempfile

    import jax
    import numpy as np

    from tpu_dist_nn.checkpoint.store import CheckpointManager

    pid = jax.process_index()
    d = tempfile.mkdtemp(prefix=f"tdn_mh_io_p{pid}_")
    mgr = CheckpointManager(d, keep=2)
    mgr.save(1, {"w": np.ones(4) * (pid + 1)})
    first_ok = mgr.latest_step() == (1 if pid == 0 else None)

    if pid == 0:
        mgr.directory = pathlib.Path(d) / "vanished"  # mkstemp will fail
    raised = False
    try:
        mgr.save(2, {"w": np.ones(4)})
    except ValueError:
        raised = True
    return {"first_ok": bool(first_ok), "raised": raised}


def _global_dataset():
    from tpu_dist_nn.data.datasets import Dataset
    import numpy as np

    rng = np.random.default_rng(7)
    x = rng.uniform(0, 1, (256, 12)).astype(np.float32)
    y = rng.integers(0, 6, 256)
    return Dataset(x, y, 6)


if __name__ == "__main__":
    sys.exit(main())
