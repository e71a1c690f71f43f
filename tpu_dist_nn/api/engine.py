"""The Engine: orchestrator + client surface in one object.

Replaces both reference drivers with a single-controller JAX program:

* ``run_grpc_fcnn.py`` (orchestrator): validate the distribution, infer
  the input dim, place stages, readiness-check, teardown — here
  ``Engine.up()`` validates, builds the mesh, compiles the executor
  (compilation *is* the readiness gate; there is no daemon to babysit,
  so the reference's supervisor sleep loop and container sweeps
  disappear), and ``setup_seconds`` mirrors its bring-up timing
  (run_grpc_fcnn.py:321-322).
* ``run_grpc_inference.py`` (client): single / whole-set / chunked-batch
  inference with accuracy + latency reporting
  (run_grpc_inference.py:162-216).

Placement semantics: ``layer_distribution`` comes from the model file's
metadata (the reference reads it from the same config JSON,
run_grpc_fcnn.py:266) or the caller. When the distribution names more
stages than there are devices, the engine collapses to the single-chip
executor — the TPU analogue of the reference running N containers on
one box — and notes it in the placement summary. A single-stage plan
always uses the unpadded single-chip path (no reason to pay padded
uniform-width matmuls on one device).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as np

from tpu_dist_nn.core.schema import (
    ModelSpec,
    load_examples,
    load_model,
    partition_model,
)
from tpu_dist_nn.data.datasets import Dataset
from tpu_dist_nn.data.feed import batch_iterator
from tpu_dist_nn.models.fcnn import params_from_spec
from tpu_dist_nn.models.network import (
    build_network,
    jitted_network_forward,
    network_model_from_params,
)
from tpu_dist_nn.train.trainer import jitted_forward, train_network
from tpu_dist_nn.parallel.mesh import (
    AXIS_STAGE,
    MeshSpec,
    batch_sharding,
    build_mesh,
    replicated,
)
from tpu_dist_nn.parallel.pipeline import (
    build_pipeline_params,
    extract_model,
    pipeline_forward,
    pipeline_spec_summary,
)
from tpu_dist_nn.obs import trace as _trace
from tpu_dist_nn.obs.goodput import GOODPUT, fcnn_flops_per_row
from tpu_dist_nn.obs.log import get_logger
from tpu_dist_nn.obs.registry import REGISTRY
from tpu_dist_nn.train.metrics import classification_metrics
from tpu_dist_nn.train.trainer import TrainConfig, train_fcnn
from tpu_dist_nn.train.pipeline_trainer import train_pipelined
from tpu_dist_nn.utils.backend import param_devices

log = logging.getLogger("tpu_dist_nn.engine")
slog = get_logger("tpu_dist_nn.engine")

# Engine metric families (docs/OBSERVABILITY.md). Host-side float adds
# only — a time.monotonic() pair around a device call, never a fetch.
_INFER_SECONDS = REGISTRY.histogram(
    "tdn_engine_infer_seconds", "Engine.infer wall time per call",
)
_INFER_ROWS = REGISTRY.counter(
    "tdn_engine_infer_rows_total",
    "rows computed by Engine.infer (includes coalescing padding; "
    "tdn_batch_rows is the useful-rows view)",
)
_INFER_ERRORS = REGISTRY.counter(
    "tdn_engine_infer_errors_total", "Engine.infer calls that raised",
)
# jit caches one program per input shape: a shape this engine has not
# served before implies a compile (the bucketed batcher keeps this set
# at ~log2(max_rows)); a repeat shape is a cache hit.
_COMPILE_HITS = REGISTRY.counter(
    "tdn_engine_compile_cache_hits_total",
    "infer calls whose batch shape was already compiled",
)
_COMPILE_MISSES = REGISTRY.counter(
    "tdn_engine_compile_cache_misses_total",
    "infer calls whose batch shape was new (implies an XLA compile)",
)
_TRAIN_SECONDS = REGISTRY.histogram(
    "tdn_engine_train_seconds", "Engine.train wall time per call",
    buckets=(1.0, 5.0, 15.0, 60.0, 300.0, 1800.0, 7200.0),
)
_TRAIN_CALLS = REGISTRY.counter(
    "tdn_engine_train_calls_total", "Engine.train invocations",
)
# Warm state of the pow2 row-bucket ladder (warm_buckets): how many
# bucket programs this process has already compiled and executed, so an
# operator can tell "no live request will eat a compile" from a scrape.
_WARM_BUCKETS = REGISTRY.gauge(
    "tdn_engine_warm_buckets",
    "precompiled pow2 row-bucket programs resident in the jit cache",
)
# Measured at warm_buckets time on quantized engines: f32 wall time /
# int8 wall time for one warmed-bucket launch. > 1 means the int8 path
# pays off on the active backend; < 1 means quantized serving is
# SLOWER here (0.24-0.48x on a CPU in rounds 4 and 5; made visible
# at serve time, where the operator of that backend can see it).
_INT8_RATIO = REGISTRY.gauge(
    "tdn_int8_speedup_ratio",
    "f32 launch wall time / int8 launch wall time on the largest warm "
    "bucket (quantized engines; < 1 = int8 is slower on this backend; "
    "NaN until a quantized engine has measured)",
)
# Unlabeled gauges materialize at 0 immediately — which would read as
# "int8 is catastrophically slow" on every UNquantized process under
# the `< 1` alert the HELP text invites. NaN is the scrape-safe
# "no measurement yet" (renders as the text format's NaN literal;
# comparisons against it are false in PromQL).
_INT8_RATIO.set(float("nan"))


@dataclasses.dataclass
class PendingInference:
    """Handle from :meth:`Engine.infer_async`: a dispatched-but-not-
    materialized result. ``value`` is whatever the placement's executor
    returned (a device array on the async paths); ``materialize`` is
    the path-correct host read (``np.asarray`` for addressable arrays,
    the replicating collective for process-spanning ones). Pass to
    :meth:`Engine.fetch` — the fetch is the host sync, so everything
    between dispatch and fetch overlaps with device execution.
    ``release`` (when set) returns the launch's pooled host staging
    buffer; fetch calls it once the device can no longer alias the
    buffer (same discipline as the serving batcher's staging pool)."""

    value: object
    materialize: object
    t0: float
    release: object = None


@dataclasses.dataclass
class InferenceResult:
    """Client-side report (run_grpc_inference.py:185-216)."""

    outputs: np.ndarray
    seconds: float
    batch_seconds: list[float]
    metrics: dict | None = None

    @property
    def predictions(self) -> np.ndarray:
        return self.outputs.argmax(-1)

    def latency_summary(self) -> dict:
        """Percentiles over per-batch wall times — the structured form of
        the per-batch seconds the reference printed and discarded."""
        from tpu_dist_nn.utils.profiling import LatencyStats

        return LatencyStats("batch_infer", list(self.batch_seconds)).summary()


class Engine:
    """A brought-up model: placed, compiled, ready to serve or train."""

    def __init__(self, model: ModelSpec, distribution, mesh_spec: MeshSpec,
                 num_microbatches: int, dtype, devices=None,
                 quantize: str | None = None, virtual_stages: int = 1):
        # Fail fast on quantize mode/placement BEFORE building any
        # placement state (matches up()'s fail-fast convention).
        if quantize is not None:
            from tpu_dist_nn.utils.errors import InvalidArgumentError

            if quantize != "int8":
                raise InvalidArgumentError(
                    f"unknown quantize mode {quantize!r}; supported: 'int8'"
                )
            if not model.is_dense:
                raise InvalidArgumentError(
                    "quantize='int8' serves dense models only (conv/pool "
                    "layers have no int8 path); it composes with pipeline, "
                    "data-parallel, AND interleaved placements"
                )
        self.virtual_stages = int(virtual_stages)
        # Engine.up overwrites this with the ORIGINAL request when the
        # device-shortage degrade resets virtual_stages (train() uses
        # it to warn-and-fallback instead of raising a contradictory
        # "pass --virtual-stages" error).
        self.requested_virtual_stages = int(virtual_stages)
        # Copy metadata so export()'s annotations never mutate a
        # ModelSpec the caller still holds.
        self.model = ModelSpec(model.layers, dict(model.metadata))
        self.distribution = list(distribution)
        self.mesh_spec = mesh_spec
        self.num_microbatches = num_microbatches
        self.dtype = dtype
        # Interleaved placements pipeline V = stage*v chunks over a
        # stage-axis mesh of size V/v, so stage==1 with v>1 still runs
        # the (virtual-stage) pipeline executor.
        self.pipelined = mesh_spec.stage > 1 or virtual_stages > 1
        self.mesh = build_mesh(mesh_spec, devices)
        # Pure data parallelism on a single-stage plan: batch sharded
        # over the data axis, params replicated.
        self.data_sharded = not self.pipelined and mesh_spec.data > 1
        self._plan = None  # mixed-layer (conv/pool) networks only
        self._hp = None  # heterogeneous (non-dense) pipeline executor
        if self.pipelined and not model.is_dense:
            from tpu_dist_nn.parallel.hetero_pipeline import HeteroPipeline

            self._hp = HeteroPipeline(
                model, self.distribution,
                devices=list(self.mesh.devices.flat), dtype=dtype,
            )
            self._pp = None
            self._params = None
        elif self.pipelined:
            stages = partition_model(model, self.distribution)
            self._pp = build_pipeline_params(stages, dtype)
            if jax.process_count() == 1:
                # Each stage's block lives on that stage's device. Left
                # on the default device, the executor would copy every
                # other stage's weights out of it on every launch. (A
                # multi-process job is left as it was: its arrays are
                # host-local, and placing them is untested here.)
                self._pp = self._pp._replace(weights=jax.device_put(
                    self._pp.weights,
                    NamedSharding(self.mesh, P(AXIS_STAGE)),
                ))
            self._params = None
        else:
            self._pp = None
            if model.is_dense:
                self._params = params_from_spec(model, dtype)
            else:
                self._plan, self._params = build_network(model, dtype)
            if self.data_sharded:
                self._params = jax.device_put(self._params, replicated(self.mesh))
        self._q = None  # int8 serving path, single-program placement
        self._q_pp = None  # int8 serving path, pipelined placement
        # Batch shapes this engine has LAUNCHED — the compile-cache
        # hit/miss proxy (jit compiles one program per input shape).
        # Keys are the device-launch shape recorded by _infer_impl
        # (after any internal padding), not the caller's row count.
        self._seen_infer_shapes: set[tuple] = set()
        # The numpy view of the engine dtype: the hot path casts input
        # ONCE, straight to this (the float64 wire contract stops at
        # the serving boundary).
        self._np_dtype = np.dtype(dtype)
        # Reusable host staging buffers for the feed path, keyed by
        # launch shape: a host-fed caller whose input needs a cast (or
        # pad) lands it in a pooled buffer instead of a fresh alloc
        # per batch. Buffers return to the pool at FETCH time
        # (PendingInference.release) — a backend that zero-copy-aliases
        # host memory into device buffers must never see one mutate
        # mid-flight (the serving batcher's staging rule). Depth 2 per
        # shape = the double-buffered steady state.
        self._host_staging: dict[tuple, list[np.ndarray]] = {}
        self._host_staging_keep = 2
        # Pow2 row buckets already compiled+executed by warm_buckets.
        self._warm_buckets: set[int] = set()
        # One automatic int8-payoff measurement per engine (warm_buckets
        # is idempotent and re-entered; the f32-arm compile is not free).
        self._int8_measured = False
        # When the payoff measurement finds int8 SLOWER than f32 on
        # this backend (ratio < 1), serving launches auto-route to the
        # f32 path instead of shipping the regression (TDN_INT8_AUTO=0
        # opts out; the quantized state is kept, so train()'s
        # re-quantization and explicit re-measurement still work).
        self.int8_auto_disabled = False
        # First-class fault-injection hook points (monkeypatch-free):
        # when set, called at the top of infer_async / fetch with the
        # batch / pending handle. tpu_dist_nn.testing.faults attaches
        # deterministic plans here; None costs one attribute check.
        self.launch_hook = None
        self.fetch_hook = None
        # Static activation names: passed explicitly on the hot path so
        # infer() never reads act ids back from the device.
        self._act_names = tuple(l.activation for l in model.layers)
        # Goodput accounting (obs/goodput.py): the analytic per-row
        # FLOP cost of this engine's dense chain, recorded per launch
        # at the infer_async boundary. None for non-dense models (no
        # FLOP model -> no accounting). Peak resolution happens here,
        # at configure time — the host-anchor measurement must never
        # ride a sampler tick.
        self._flops_per_row = (
            fcnn_flops_per_row(self.model.layer_sizes)
            if model.is_dense else None
        )
        if self._flops_per_row:
            # The peak must match the ledger's footprint: launches are
            # recorded whole, so a sharded placement's denominator is
            # per-device peak x mesh size.
            GOODPUT.ensure_peak(device_count=mesh_spec.num_devices)
        if quantize is not None:
            if self.pipelined:
                from tpu_dist_nn.kernels.quantized import (
                    quantize_pipeline_weights,
                )

                self._q_pp = quantize_pipeline_weights(self._pp.weights)
            else:
                from tpu_dist_nn.kernels.quantized import quantize_fcnn

                self._q = quantize_fcnn(self._params)
        self.setup_seconds: float | None = None

    # ---------------------------------------------------------------- up

    @classmethod
    def up(
        cls,
        model,
        distribution=None,
        *,
        data_parallel: int = 1,
        num_microbatches: int = 4,
        dtype=jnp.float32,
        devices=None,
        warmup: bool = True,
        quantize: str | None = None,
        virtual_stages: int = 1,
        warm_rows: int = 0,
    ) -> "Engine":
        """Validate, place, compile; returns a ready engine.

        ``model`` is a path or a ModelSpec. Bring-up wall time lands in
        ``engine.setup_seconds`` (run_grpc_fcnn.py:321-322 parity).
        ``quantize="int8"`` serves the dense chain through the fused
        int8 Pallas path (f32 masters kept for train/export).

        ``warm_rows > 0`` precompiles the whole pow2 row-bucket ladder
        up to that many rows at bring-up (:meth:`warm_buckets`), so a
        served engine never pays an XLA compile on a live request mix.

        ``virtual_stages=v > 1`` selects the INTERLEAVED (virtual-stage)
        inference placement: the distribution's ``V`` entries become
        ``V`` pipeline chunks with chunk ``c`` on device ``c % (V/v)``
        — a V-chunk pipeline on V/v devices, served by the table-driven
        forward executor (parallel/interleaved.make_interleaved_forward).
        """
        t0 = time.monotonic()
        if not isinstance(model, ModelSpec):
            model = load_model(model)
        if distribution is None:
            distribution = model.metadata.get("layer_distribution")
        if distribution is None:
            distribution = [len(model.layers)]
        # Fail fast on an invalid plan (run_grpc_fcnn.py:182-183).
        partition_model(model, distribution)

        n_devices = len(devices or jax.devices())
        stages = len(distribution)
        from tpu_dist_nn.utils.errors import InvalidArgumentError

        if virtual_stages < 1:
            raise InvalidArgumentError(
                f"virtual_stages must be >= 1, got {virtual_stages}"
            )
        # Remember the REQUEST: the device-shortage degrade below may
        # reset virtual_stages to 1, and train(schedule="interleaved")
        # must then warn-and-fallback rather than tell the user to pass
        # the flag they already passed.
        requested_virtual = virtual_stages
        if virtual_stages > 1:
            if not model.is_dense:
                raise InvalidArgumentError(
                    "virtual_stages applies to dense pipelined models "
                    "(the heterogeneous executor pins one stage per device)"
                )
            if stages % virtual_stages:
                raise InvalidArgumentError(
                    f"distribution has {stages} entries (chunks), not "
                    f"divisible by virtual_stages={virtual_stages}"
                )
            stage_devices = stages // virtual_stages
            if stage_devices * data_parallel > n_devices:
                # Same graceful-degradation contract as the plain
                # placement below: serve single-chip rather than fail.
                log.info(
                    "placement: interleaved %d stage device(s) x %d data "
                    "shards exceed %d device(s); collapsing to the "
                    "single-chip executor",
                    stage_devices, data_parallel, n_devices,
                )
                virtual_stages = 1
                mesh_spec = MeshSpec(stage=1, data=1)
                distribution = [len(model.layers)]
            else:
                mesh_spec = MeshSpec(stage=stage_devices, data=data_parallel)
        else:
            if stages > 1 and not model.is_dense and data_parallel > 1:
                # The heterogeneous executor pins one stage per device
                # and has no data axis; pipeline placement wins.
                log.info(
                    "placement: non-dense pipeline ignores data_parallel=%d",
                    data_parallel,
                )
                data_parallel = 1
            if stages * data_parallel > n_devices:
                log.info(
                    "placement: %d stages x %d data shards exceed %d "
                    "device(s); collapsing to the single-chip executor",
                    stages, data_parallel, n_devices,
                )
                mesh_spec = MeshSpec(stage=1, data=1)
                distribution = [len(model.layers)]
            else:
                mesh_spec = MeshSpec(stage=stages, data=data_parallel)
            if mesh_spec.stage == 1:
                distribution = [len(model.layers)]

        engine = cls(model, distribution, mesh_spec, num_microbatches, dtype,
                     devices, quantize=quantize,
                     virtual_stages=virtual_stages)
        engine.requested_virtual_stages = requested_virtual
        if warmup or warm_rows > 0:
            # Compilation is the readiness check (the analogue of the
            # orchestrator's TCP poll, run_grpc_fcnn.py:157-172); with
            # warm_rows the whole bucket ladder compiles here instead
            # of on the first unlucky live request mix.
            engine.warm_buckets(max(warm_rows, 1 if warmup else 0))
        engine.setup_seconds = time.monotonic() - t0
        slog.info("engine.up", seconds=round(engine.setup_seconds, 3),
                  placement=engine.placement())
        return engine

    def placement(self) -> dict:
        """Placement summary — the spawn-log analogue (run_grpc_fcnn.py:133-143)."""
        base = {
            "devices": self.mesh_spec.num_devices,
            "distribution": self.distribution,
            "data_parallel": self.mesh_spec.data,
            "pipelined": self.pipelined,
        }
        if self.virtual_stages > 1:
            base["virtual_stages"] = self.virtual_stages
        base["param_devices"] = param_devices(
            [s["params"] for s in self._hp.stages] if self._hp is not None
            else self._pp.weights if self._pp is not None
            else self._params
        )
        if self._hp is not None:
            base.update(self._hp.placement_summary())
        elif self.pipelined:
            base.update(pipeline_spec_summary(self._pp))
        else:
            base.update(
                {
                    "num_stages": 1,
                    "input_dim": self.model.input_dim,
                    "output_dim": self.model.output_dim,
                }
            )
        return base

    # ------------------------------------------------------------- infer

    def infer(self, x) -> np.ndarray:
        """Forward a batch → (N, out_dim) probabilities.

        Raises :class:`~tpu_dist_nn.utils.errors.InvalidArgumentError` on
        a feature-dim mismatch (the reference's per-forward check,
        grpc_node.py:83-84 → INVALID_ARGUMENT) and
        :class:`~tpu_dist_nn.utils.errors.UnavailableError` after
        :meth:`down` (the reference's dead-channel UNAVAILABLE).

        A direct call is ONE request, so the numeric guard's per-row
        failover collapses to request granularity here: any corrupt
        row raises :class:`~tpu_dist_nn.utils.errors.IntegrityError`
        rather than shipping a partially-poisoned batch (the batcher
        path keeps row granularity via ``PendingInference.bad_rows``).
        """
        pending = self.infer_async(x)
        out = self.fetch(pending)
        bad = getattr(pending, "bad_rows", None)
        if bad is not None and bad.any():
            from tpu_dist_nn.utils.errors import IntegrityError

            raise IntegrityError(
                f"numeric guard: {int(bad.sum())}/{len(out)} rows of "
                f"the result are non-finite or out of magnitude bounds"
            )
        return out

    def infer_async(self, x, *, useful_rows=None) -> PendingInference:
        """Validate, stage, and LAUNCH a batch without waiting for it.

        Returns a :class:`PendingInference` whose device result is
        still materializing (JAX async dispatch); :meth:`fetch` is the
        host sync. The serving batcher's dispatch stage launches batch
        N+1 through this while batch N's fetch is in flight — the
        double-buffered fast path. Validation errors raise HERE (at
        dispatch), so a bad request fails before it occupies the
        pipeline.

        ``useful_rows`` is the goodput declaration (obs/goodput.py):
        how many of this batch's rows carry request data. The batcher
        passes its pre-padding row count so bucket pad is accounted as
        pad FLOPs under ``path="batcher"``; direct callers omit it and
        the launch counts as all-useful under ``path="engine"``
        (data-shard padding on direct calls rides as useful — a named
        model simplification, single-chip launches have none).
        """
        t0 = time.monotonic()
        try:
            # getattr: hand-constructed engines (tests build the
            # single-chip path via Engine.__new__) may predate the slot.
            hook = getattr(self, "launch_hook", None)
            if hook is not None:
                hook(x)  # fault injection: may raise or delay
            out, materialize, shape, release = self._infer_impl(x)
        except Exception:
            _INFER_ERRORS.inc()
            raise
        # Goodput accounting at the launch boundary: one integer record
        # per device launch (never per row). getattr: hand-constructed
        # engines (Engine.__new__ in tests) may predate the slot.
        fpr = getattr(self, "_flops_per_row", None)
        if fpr:
            total_rows = int(shape[0])
            if useful_rows is None:
                GOODPUT.record_rows(fpr, total_rows, total_rows,
                                    path="engine")
            else:
                GOODPUT.record_rows(fpr, total_rows, int(useful_rows),
                                    path="batcher")
        # Trace annotations attach to whatever request span is active
        # on this thread (the batcher's launch span, a handler span, or
        # nothing) — the active() guard keeps the f-strings off the
        # untraced path entirely.
        if _trace.active():
            _trace.annotate(
                f"engine.infer_async launch_shape={shape} "
                f"dispatch_s={time.monotonic() - t0:.6f}"
            )
        # Compile-cache proxy keyed on the DEVICE-LAUNCH shape returned
        # by _infer_impl (after internal padding — e.g. the data-sharded
        # path pads rows to the shard count): jit compiles one program
        # per launch shape, so keying on the caller's unpadded row count
        # would overcount misses. Returned, not read off instance state:
        # concurrent infer callers (batcher dispatch + a health probe)
        # must not read each other's shapes.
        seen = self._seen_infer_shapes
        if shape in seen:
            _COMPILE_HITS.inc()
        else:
            seen.add(shape)
            _COMPILE_MISSES.inc()
            if _trace.active():
                # The event a slow-request trace most wants named: this
                # launch shape was new, so the request likely paid an
                # XLA compile (hundreds of ms) nothing else explains.
                _trace.annotate(f"engine.compile_cache_miss shape={shape}")
        return PendingInference(out, materialize, t0, release)

    def fetch(self, pending: PendingInference) -> np.ndarray:
        """Materialize an :meth:`infer_async` handle as host numpy —
        the ONE host sync of an inference. Wall time from dispatch to
        materialized result lands in ``tdn_engine_infer_seconds``."""
        try:
            hook = getattr(self, "fetch_hook", None)
            if hook is not None:
                hook(pending)  # fault injection: may raise or delay
            out = pending.materialize(pending.value)
            # Numeric guard at the ONE host sync: the result is already
            # materialized host-side, so the isfinite reduction is one
            # vectorized pass over hot memory. Partial corruption is
            # stashed as a row mask for the batcher's per-row failover
            # (unaffected rows ship bit-identical); a fully-bad launch
            # has no salvageable rows and raises outright.
            from tpu_dist_nn.serving.integrity import GUARD

            bad = GUARD.bad_rows(out) if GUARD.enabled else None
            if bad is not None and bad.any():
                pending.bad_rows = bad
                if bad.all():
                    from tpu_dist_nn.utils.errors import IntegrityError

                    raise IntegrityError(
                        f"numeric guard: all {len(out)} rows of the "
                        f"launch are non-finite or out of magnitude — "
                        f"refusing to ship the batch"
                    )
        except Exception:
            _INFER_ERRORS.inc()
            raise
        finally:
            # Return the launch's pooled host staging buffer: after the
            # materialize attempt the device result is (or will never
            # be) realized, so the input buffer can no longer alias a
            # mutating transfer. Cleared first — a double fetch must
            # not double-free the buffer into the pool.
            rel = getattr(pending, "release", None)
            if rel is not None:
                pending.release = None
                rel()
        _INFER_SECONDS.observe(time.monotonic() - pending.t0)
        _INFER_ROWS.inc(len(out))
        if _trace.active():
            _trace.annotate(
                f"engine.fetch rows={len(out)} "
                f"since_dispatch_s={time.monotonic() - pending.t0:.6f}"
            )
        return out

    def warm_buckets(self, max_rows: int) -> list[int]:
        """Precompile the pow2 row-bucket ladder (1, 2, 4, … up to the
        pow2 CEILING of ``max_rows`` — a coalesced batch of
        ``max_rows`` rows pads into that bucket, so stopping at the
        last pow2 below it would leave exactly the top bucket cold)
        so no live request ever eats an XLA compile.

        Each bucket runs one real zeros-batch inference rather than an
        AOT ``lower().compile()``: executing through the jit call site
        is the only warm that seeds the dispatch cache the live path
        actually hits (an AOT Compiled object is a separate executable),
        and it additionally lands the program in the persistent compile
        cache when ``JAX_COMPILATION_CACHE_DIR`` is set — which is what
        makes a standalone ``tdn warmup`` run pay off across processes.

        Already-warm buckets are skipped (idempotent); the warm-state
        count is published as the ``tdn_engine_warm_buckets`` gauge.
        Returns the bucket sizes newly warmed by THIS call.
        """
        warmed: list[int] = []
        if max_rows < 1:
            return warmed
        dim = self.model.input_dim
        top = 1 << (max_rows - 1).bit_length() if max_rows > 1 else 1
        n = 1
        while n <= top:
            if n not in self._warm_buckets:
                self.infer(np.zeros((n, dim), self._np_dtype))
                self._warm_buckets.add(n)
                warmed.append(n)
                # Per-bucket, not once at the end: a scrape DURING a
                # long warm (tdn warmup --metrics-port) sees progress.
                # This method is the gauge's ONLY writer — one-engine-
                # per-process semantics; a second engine's warm
                # overwrites with its own count.
                _WARM_BUCKETS.set(len(self._warm_buckets))
            n *= 2
        if (
            warmed
            and (self._q is not None or self._q_pp is not None)
            and not self._int8_measured
            and os.environ.get("TDN_INT8_WARMUP_MEASURE", "1") != "0"
        ):
            # The int8 payoff check rides the FIRST warm (the port is
            # not open yet): the BENCH int8_vs_f32 regression becomes a
            # serve-time gauge + structured warning instead of a
            # round-artifact archaeology find. Costs one f32 compile of
            # the never-warmed float path plus a few launches —
            # TDN_INT8_WARMUP_MEASURE=0 skips it where that compile is
            # too expensive (explicit measure_int8_speedup() calls
            # still work).
            self.measure_int8_speedup()
        return warmed

    def measure_int8_speedup(self, rows: int | None = None) -> float | None:
        """Time one f32 vs one int8 launch on the largest warm bucket
        (or ``rows``) and publish ``tdn_int8_speedup_ratio``.

        Returns f32_seconds / int8_seconds (> 1: the quantized path is
        faster on this backend), or None on a non-quantized engine.
        Runs the engine's OWN dispatch both ways — the f32 arm
        temporarily clears the quantized state so ``_infer_impl``
        selects the float path for any placement (single-chip, sharded,
        pipelined, interleaved). Best-of-3 after one warm call per arm,
        so neither side pays its XLA compile inside the timed window.
        Bring-up only: not safe concurrent with live traffic.
        """
        if self._q is None and self._q_pp is None:
            return None
        if rows is None:
            rows = max(self._warm_buckets) if self._warm_buckets else 1
        x = np.zeros((int(rows), self.model.input_dim), self._np_dtype)

        def best_of(n: int = 3) -> float:
            self.infer(x)  # warm (compile lands outside the timing)
            times = []
            for _ in range(n):
                t0 = time.monotonic()
                self.infer(x)
                times.append(time.monotonic() - t0)
            return min(times)

        q, q_pp, q_apply = self._q, self._q_pp, getattr(self, "_q_apply", None)
        self._q = self._q_pp = self._q_apply = None
        try:
            f32_s = best_of()
        finally:
            self._q, self._q_pp, self._q_apply = q, q_pp, q_apply
        # A RE-measurement on an auto-disabled engine must time the real
        # int8 path, not the f32 reroute the gate would select.
        gate = self.int8_auto_disabled
        self.int8_auto_disabled = False
        try:
            int8_s = best_of()
        finally:
            self.int8_auto_disabled = gate
        ratio = f32_s / int8_s if int8_s > 0 else float("inf")
        self._int8_measured = True
        _INT8_RATIO.set(ratio)
        if ratio < 1.0:
            slog.warning(
                "int8.slower_than_f32", ratio=round(ratio, 3),
                rows=int(rows), f32_ms=round(f32_s * 1e3, 3),
                int8_ms=round(int8_s * 1e3, 3),
                backend=jax.default_backend(),
                hint="serve without --quantize on this backend (int8 "
                     "is a dequantize-dominated loss here)",
            )
            if os.environ.get("TDN_INT8_AUTO", "1") != "0":
                # Close the regression instead of just warning about
                # it: the measured-slower path never serves traffic.
                # The f32 programs are already compiled (the f32 arm
                # of the measurement just ran them), so the reroute is
                # warm.
                self.int8_auto_disabled = True
                slog.warning(
                    "int8.auto_disabled", ratio=round(ratio, 3),
                    backend=jax.default_backend(),
                    hint="serving launches rerouted to the f32 path "
                         "(TDN_INT8_AUTO=0 opts out of the fallback)",
                )
            else:
                # Explicit opt-out means measure + warn ONLY: a
                # re-measurement must also clear any reroute a prior
                # env-enabled run left armed, or the opt-out would
                # leave the engine stuck on f32.
                self.int8_auto_disabled = False
        else:
            self.int8_auto_disabled = False
            slog.info(
                "int8.speedup", ratio=round(ratio, 3), rows=int(rows),
                backend=jax.default_backend(),
            )
        return ratio

    @property
    def warm_bucket_count(self) -> int:
        """Attribute-only warm state (the obs runtime sampler reads
        this — no device work, mirroring ``is_ready``)."""
        return len(self._warm_buckets)

    def _host_buffer(self, shape) -> tuple[np.ndarray, object]:
        """Pooled engine-dtype host staging buffer for a feed-path
        launch shape, plus its return-to-pool callable.

        The host-feed analogue of the batcher's per-bucket staging
        pool: a caller whose input needs a cast (or shard pad) fills a
        REUSED buffer instead of paying a fresh alloc per batch. The
        release callable runs at fetch time (PendingInference.release)
        — never earlier, so a backend that zero-copy-aliases host
        memory into device buffers cannot see the buffer mutate under
        an in-flight batch. getattr-guarded: hand-constructed engines
        (tests build the single-chip path via ``Engine.__new__``) may
        predate the pool slot."""
        pool = getattr(self, "_host_staging", None)
        if pool is None:
            pool = self._host_staging = {}
        bufs = pool.get(shape)
        buf = None
        if bufs:
            try:
                buf = bufs.pop()
            except IndexError:  # concurrent infer callers raced the pop
                buf = None
        if buf is None:
            buf = np.empty(shape, self._np_dtype)
        keep = getattr(self, "_host_staging_keep", 2)

        def release():
            held = pool.setdefault(shape, [])
            if len(held) < keep:
                held.append(buf)

        return buf, release

    def _infer_impl(self, x):
        from tpu_dist_nn.utils.errors import UnavailableError, check_input_dim

        if self._pp is None and self._params is None and self._hp is None:
            raise UnavailableError(
                "engine is down; relaunch with Engine.up from the model JSON"
            )
        x = np.asarray(x)
        in_dim = self.model.input_dim
        if x.ndim >= 2:
            check_input_dim(in_dim, int(x.shape[-1]), stage=0)
        elif x.size != in_dim:
            check_input_dim(in_dim, int(x.size), stage=0)
        x = x.reshape(-1, in_dim)
        # ONE cast, straight to the engine dtype (no float64 staging
        # array): the float64 wire contract lives at the serving
        # boundary only, and the dtype-aware decoder usually lands
        # rows here already converted — this is then a no-op. When a
        # cast IS needed (host-fed callers with f64/u8 inputs), it
        # lands in a pooled staging buffer released at fetch, so the
        # double-buffered feed loop recycles two buffers per shape
        # instead of allocating per batch.
        release = None
        if x.dtype != self._np_dtype:
            buf, release = self._host_buffer((len(x), in_dim))
            np.copyto(buf, x, casting="unsafe")
            x = buf
        # The shape the device actually launches (the compile-cache
        # proxy key); branches that pad internally override it.
        launch = (len(x), in_dim)
        if self._hp is not None:
            mb = max(1, len(x) // self.num_microbatches)
            return (self._hp.forward(x, microbatch_size=mb), np.asarray,
                    launch, release)
        # The int8 serving paths are skipped entirely when the warmup
        # payoff measurement auto-disabled them (measured slower than
        # f32 on this backend; measure_int8_speedup).
        use_int8 = not self.int8_auto_disabled
        if self.pipelined:
            from tpu_dist_nn.parallel.multihost import to_host_numpy

            if use_int8 and self._q_pp is not None \
                    and self.virtual_stages > 1:
                from tpu_dist_nn.parallel.pipeline import (
                    pipeline_forward_interleaved_quantized,
                )

                out = pipeline_forward_interleaved_quantized(
                    self.mesh, self._q_pp, self._pp.meta, x,
                    num_virtual=self.virtual_stages,
                    num_microbatches=self.num_microbatches,
                )
                return out, to_host_numpy, launch, release
            if use_int8 and self._q_pp is not None:
                from tpu_dist_nn.parallel.pipeline import (
                    pipeline_forward_quantized,
                )

                out = pipeline_forward_quantized(
                    self.mesh, self._q_pp, self._pp.meta, x,
                    num_microbatches=self.num_microbatches,
                )
                return out, to_host_numpy, launch, release
            if self.virtual_stages > 1:
                from tpu_dist_nn.parallel.pipeline import (
                    pipeline_forward_interleaved,
                )

                out = pipeline_forward_interleaved(
                    self.mesh, self._pp, x,
                    num_virtual=self.virtual_stages,
                    num_microbatches=self.num_microbatches,
                )
                return out, to_host_numpy, launch, release
            out = pipeline_forward(
                self.mesh, self._pp, x, num_microbatches=self.num_microbatches
            )
            return out, to_host_numpy, launch, release
        if use_int8 and self._q is not None and not self.data_sharded:
            from tpu_dist_nn.kernels.quantized import fcnn_quantized_forward

            return (
                fcnn_quantized_forward(
                    self._q, jnp.asarray(x, jnp.float32),
                    activations=self._act_names,
                ),
                np.asarray,
                launch,
                release,
            )
        if use_int8 and self._q is not None:
            # Data-sharded int8: the jnp quantized chain under jit on the
            # batch-sharded global array (weights replicated); XLA keeps
            # the int8 matmuls sharded over the data axis.
            apply = self._quantized_apply()
        else:
            apply = (
                jitted_forward
                if self._plan is None
                else jitted_network_forward(self._plan)
            )
        if self.data_sharded:
            from tpu_dist_nn.parallel.multihost import to_host_numpy

            n = len(x)
            shards = self.mesh_spec.data
            pad = -n % shards
            if pad:
                # Shard padding lands in a pooled staging buffer too
                # (rows copied in, pad tail zeroed in place) — np.pad
                # allocated a fresh padded matrix every batch. Chain
                # the cast buffer's release when one is outstanding so
                # both return to the pool at fetch.
                xb, pad_release = self._host_buffer((n + pad, in_dim))
                np.copyto(xb[:n], x, casting="unsafe")
                xb[n:] = 0
                if release is None:
                    release = pad_release
                else:
                    cast_release = release

                    def release(a=cast_release, b=pad_release):
                        a()
                        b()
            else:
                xb = x
            # jit sees the PADDED batch: that is the compiled shape.
            launch = (len(xb), in_dim)
            if jax.process_count() > 1:
                # Every host computed the same padded batch; each device
                # receives exactly the chunk the sharding assigns it.
                # (Deriving rows from process_index arithmetic instead
                # would silently permute outputs on meshes whose data
                # axis is not process-contiguous.)
                from tpu_dist_nn.data.feed import global_from_replicated
                from tpu_dist_nn.parallel.mesh import AXIS_DATA

                xb = global_from_replicated(self.mesh, P(AXIS_DATA), xb)
            else:
                xb = jax.device_put(xb, batch_sharding(self.mesh))
            # The [:n] slice is a lazy device op: the unpadded view
            # materializes at fetch, the launch stays padded.
            return apply(self._params, xb)[:n], to_host_numpy, launch, release
        return (apply(self._params, jnp.asarray(x, self.dtype)), np.asarray,
                launch, release)

    def _quantized_apply(self):
        """Cached jitted (params, xb) -> logits closure over the int8
        blocks, signature-compatible with the data-sharded dispatch."""
        if getattr(self, "_q_apply", None) is None:
            from tpu_dist_nn.kernels.quantized import forward_quantized

            q, acts = self._q, self._act_names
            self._q_apply = jax.jit(
                lambda _params, xb: forward_quantized(q, xb, acts)
            )
        return self._q_apply

    def infer_single(self, x) -> tuple[np.ndarray, float]:
        """One example, with its wall time (run_grpc_inference.py:54-99)."""
        t0 = time.monotonic()
        out = self.infer(np.asarray(x).reshape(1, -1))[0]
        return out, time.monotonic() - t0

    def step_latency(self, batch_size: int = 256, iters: int = 20) -> dict:
        """The BASELINE "p50 per-stage pipeline step latency" probe.

        Times ``iters`` synchronous forward steps on a synthetic batch
        and reports the :class:`~tpu_dist_nn.utils.profiling.LatencyStats`
        percentiles plus ``p50_per_stage_s`` (step p50 divided by the
        stage count — the per-stage share of one pipeline step).
        """
        from tpu_dist_nn.utils.errors import InvalidArgumentError
        from tpu_dist_nn.utils.profiling import LatencyStats

        if iters < 1 or batch_size < 1:
            raise InvalidArgumentError(
                f"step_latency needs iters >= 1 and batch_size >= 1, "
                f"got iters={iters}, batch_size={batch_size}"
            )
        rng = np.random.default_rng(0)
        x = rng.uniform(0.0, 1.0, (batch_size, self.model.input_dim))
        self.infer(x)  # warmup / compile
        stats = LatencyStats("pipeline_step")
        for _ in range(iters):
            t0 = time.monotonic()
            self.infer(x)
            stats.record(time.monotonic() - t0)
        num_stages = self.placement().get("num_stages", 1)
        summary = stats.summary()
        summary["num_stages"] = num_stages
        summary["p50_per_stage_s"] = summary["p50_s"] / num_stages
        return summary

    def run_inference(
        self,
        inputs,
        labels=None,
        *,
        batch_size: int | None = None,
        num_classes: int | None = None,
    ) -> InferenceResult:
        """Whole-set or chunked-batch inference with accuracy + latency —
        the reference client's main loop (run_grpc_inference.py:185-216).

        The chunked path is a double-buffered host-feed loop: batch
        ``i+1`` is staged (pooled cast buffer) and LAUNCHED before
        batch ``i``'s fetch pays the host sync, so the host->device
        transfer of the next batch overlaps the previous batch's
        compute — the same overlap the serving batcher's dispatch/drain
        split buys, without a thread. Results and their order are
        identical to the serial loop; ``batch_seconds[i]`` spans batch
        i's dispatch to its materialized result.
        """
        inputs = np.asarray(inputs)
        t0 = time.monotonic()
        outputs = []
        batch_seconds = []
        if batch_size is None:
            bt0 = time.monotonic()
            outputs.append(self.infer(inputs))
            batch_seconds.append(time.monotonic() - bt0)
        else:
            pending = None
            pt0 = 0.0
            for bx in batch_iterator(inputs, batch_size=batch_size):
                bt0 = time.monotonic()
                nxt = self.infer_async(bx)
                if pending is not None:
                    outputs.append(self.fetch(pending))
                    batch_seconds.append(time.monotonic() - pt0)
                pending, pt0 = nxt, bt0
            if pending is not None:
                outputs.append(self.fetch(pending))
                batch_seconds.append(time.monotonic() - pt0)
        outputs = np.concatenate(outputs)
        seconds = time.monotonic() - t0
        metrics = None
        if labels is not None:
            metrics = classification_metrics(outputs, labels, num_classes)
        return InferenceResult(outputs, seconds, batch_seconds, metrics)

    # ------------------------------------------------------------- train

    def train(
        self,
        train_data: Dataset,
        config: TrainConfig = TrainConfig(),
        eval_data: Dataset | None = None,
        checkpoints=None,
        schedule: str = "gpipe",
    ) -> list[dict]:
        """Train in place (pipelined if placed that way); returns history."""
        _TRAIN_CALLS.inc()
        t0 = time.monotonic()
        try:
            return self._train_impl(
                train_data, config, eval_data, checkpoints, schedule
            )
        finally:
            _TRAIN_SECONDS.observe(time.monotonic() - t0)

    def _train_impl(
        self,
        train_data: Dataset,
        config: TrainConfig,
        eval_data: Dataset | None = None,
        checkpoints=None,
        schedule: str = "gpipe",
    ) -> list[dict]:
        """Train in place (pipelined if placed that way); returns history.

        ``checkpoints`` (a :class:`tpu_dist_nn.checkpoint.CheckpointManager`)
        turns on epoch-level save + resume for whichever trainer flavor
        this engine's placement selects. ``schedule``
        ("gpipe" | "1f1b" | "interleaved") picks the pipeline training
        schedule; it only applies to the pipelined placement. An
        interleaved (``virtual_stages > 1``) placement auto-selects
        "interleaved" (the default "gpipe" is upgraded; "1f1b" is
        rejected there — it assumes chunk-per-device); "interleaved" on
        a non-virtual placement is rejected with a pointer at
        ``virtual_stages``.
        """
        # Validate regardless of placement: a typo'd schedule on a
        # non-pipelined engine must not silently train with the default.
        from tpu_dist_nn.parallel.one_f_one_b import validate_schedule

        validate_schedule(schedule)
        if schedule in ("zb", "zb-v"):
            raise ValueError(
                "zero-bubble schedules are implemented for the "
                "transformer LM pipeline only (tdn lm --schedule zb); "
                "the classifier engine supports gpipe/1f1b/interleaved"
            )
        if self.virtual_stages > 1:
            # The placement determines the schedule: V chunks on V/v
            # devices can only run the table-driven interleaved
            # executors (gpipe/1f1b assume chunk-per-device).
            if schedule == "1f1b":
                raise ValueError(
                    "schedule='1f1b' does not apply to an interleaved "
                    "(virtual_stages > 1) placement; the schedule is "
                    "'interleaved' there (the default 'gpipe' auto-"
                    "selects it)"
                )
            if schedule == "gpipe":
                log.info(
                    "train: interleaved placement (virtual_stages=%d) "
                    "selects schedule='interleaved'", self.virtual_stages,
                )
            schedule = "interleaved"
        elif schedule == "interleaved":
            if self.requested_virtual_stages > 1:
                # The user DID request a virtual placement; the
                # device-shortage degrade collapsed it to single-chip.
                # Honor the degradation contract: train single-chip
                # with the default schedule instead of raising an error
                # that tells them to pass the flag they already passed.
                log.warning(
                    "train: interleaved placement was collapsed to the "
                    "single-chip executor at up() (too few devices); "
                    "training with the default schedule"
                )
                schedule = "gpipe"
            else:
                raise ValueError(
                    "schedule='interleaved' needs an interleaved "
                    "placement: bring the engine up with virtual_stages=v "
                    "(tdn train --virtual-stages v) so the distribution's "
                    "V chunks land on V/v devices"
                )
        # The heterogeneous executor trains through its own hand-rolled
        # GPipe schedule (train_hetero), which has no 1f1b variant.
        if schedule != "gpipe" and (not self.pipelined or self._hp is not None):
            raise ValueError(
                f"schedule={schedule!r} applies to the dense pipelined "
                "placement only (this engine was placed "
                + ("heterogeneous" if self._hp is not None else "single-program")
                + "); place a dense model with a multi-stage distribution "
                "to use it"
            )
        if self._hp is not None:
            # Train THROUGH the pipeline placement: per-stage jitted
            # VJPs with device_put hand-offs mirroring the forward
            # (parallel/hetero_pipeline.py training section; global-norm
            # clipping is applied across the stages by the step).
            from tpu_dist_nn.parallel.hetero_pipeline import train_hetero

            # num_microbatches is an inference knob set at up() time;
            # training only needs SOME equal split of the batch, so take
            # the largest batch_size divisor not exceeding it — any
            # batch_size trains, as it did pre-pipelined-training.
            mb = max(
                d for d in range(1, self.num_microbatches + 1)
                if config.batch_size % d == 0
            )
            if mb != self.num_microbatches:
                # mb == 1 means NO pipeline overlap at all (e.g. a prime
                # batch size): the user configured a pipelined placement
                # but training would fully serialize — warn, don't bury.
                log.log(
                    logging.WARNING if mb == 1 else logging.INFO,
                    "train: using %d microbatches (engine's %d does not "
                    "divide batch_size %d)%s",
                    mb, self.num_microbatches, config.batch_size,
                    " — pipelined training fully serializes; choose a "
                    "batch size with a divisor > 1" if mb == 1 else "",
                )
            params_list, history = train_hetero(
                self._hp, train_data, config,
                eval_data=eval_data, checkpoints=checkpoints,
                num_microbatches=mb,
            )
            flat = [p for stage_params in params_list for p in stage_params]
            self.model = network_model_from_params(self.model, flat)
            return history
        if self.pipelined:
            self._pp, history = train_pipelined(
                self._pp,
                self.mesh,
                train_data,
                config,
                num_microbatches=self.num_microbatches,
                eval_data=eval_data,
                checkpoints=checkpoints,
                schedule=schedule,
                num_virtual=self.virtual_stages,
            )
            self.model = extract_model(self._pp, self.model, self.distribution)
        elif self._plan is not None:
            self._params, history = train_network(
                self._plan, self._params, train_data, config,
                eval_data=eval_data, checkpoints=checkpoints,
            )
            self.model = network_model_from_params(self.model, self._params)
        else:
            self._params, history = train_fcnn(
                self._params, train_data, config,
                eval_data=eval_data, checkpoints=checkpoints,
                # Data-sharded placement: train over the data axis too
                # (batch sharded, params replicated, grads all-reduced).
                mesh=self.mesh if self.data_sharded else None,
            )
            trained = [
                {"weights": np.asarray(p["w"], np.float64),
                 "biases": np.asarray(p["b"], np.float64)}
                for p in self._params
            ]
            new_layers = [
                dataclasses.replace(l, weights=t["weights"], biases=t["biases"])
                for l, t in zip(self.model.layers, trained)
            ]
            self.model = ModelSpec(new_layers, dict(self.model.metadata))
        if self._q is not None:
            # Re-quantize so the int8 serving path tracks the trained
            # weights (it would otherwise serve the pre-training copy).
            from tpu_dist_nn.kernels.quantized import quantize_fcnn

            self._q = quantize_fcnn(self._params)
            self._q_apply = None
        if self._q_pp is not None:
            from tpu_dist_nn.kernels.quantized import quantize_pipeline_weights

            self._q_pp = quantize_pipeline_weights(self._pp.weights)
        return history

    # ------------------------------------------------------------ export

    def export(self, path, metrics: dict | None = None) -> ModelSpec:
        """Write the current weights to the public JSON schema, embedding
        metrics under inference_metrics (notebook cell 10 parity)."""
        from tpu_dist_nn.core.schema import save_model

        if metrics is not None:
            self.model.metadata["inference_metrics"] = metrics
        if "layer_distribution" not in self.model.metadata and self.pipelined:
            self.model.metadata["layer_distribution"] = self.distribution
        save_model(self.model, path)
        return self.model

    # -------------------------------------------------------------- down

    def down(self) -> None:
        """Release references. Idempotent; relaunch = ``Engine.up`` again
        from the JSON model (the reference's clean-teardown/stateless-
        relaunch contract, run_grpc_fcnn.py:329-344)."""
        self._pp = None
        self._params = None
        self._q = None
        self._q_pp = None
        self._q_apply = None
        self._hp = None

    # ------------------------------------------------------------ health

    @property
    def is_ready(self) -> bool:
        """Attribute-only readiness (no device work) — the ONE
        predicate health(), /healthz, and the obs runtime sampler
        share, so a new placement slot cannot silently drift one of
        them out of sync."""
        return (
            self._pp is not None
            or self._params is not None
            or self._hp is not None
        )

    def fingerprint(self) -> str:
        """Whole-model weights fingerprint (integrity.fingerprint_tree
        over every layer's host-side float64 weights/biases) — the
        value ``/healthz`` exposes so the pool can refuse to admit a
        replica whose loaded weights disagree with the fleet's.

        Computed from ``self.model`` (the canonical host copy every
        placement shares), so replicas of the same model file agree
        regardless of device layout or quantization. Cached per model
        object — training swaps ``self.model`` wholesale, which
        naturally invalidates."""
        cached = getattr(self, "_fingerprint_cache", None)
        if cached is not None and cached[0] is self.model:
            return cached[1]
        from tpu_dist_nn.serving.integrity import fingerprint_tree

        tree = {}
        for i, layer in enumerate(self.model.layers):
            tree[f"layer{i}/weights"] = layer.weights
            tree[f"layer{i}/biases"] = layer.biases
        fp = fingerprint_tree(tree)["model"]
        self._fingerprint_cache = (self.model, fp)
        return fp

    def health(self, probe: bool = True) -> dict:
        """Structured readiness report — the reference's TCP readiness
        poll (run_grpc_fcnn.py:157-172) as an inspectable status.

        ``probe=False`` skips the device inference probe: the
        per-request form served by ``/healthz`` (a liveness poller must
        not dispatch device work concurrent with training/serving, nor
        pay an XLA compile on its first hit).
        """
        ready = self.is_ready
        status = {
            "ready": ready,
            "devices": self.mesh_spec.num_devices,
            "pipelined": self.pipelined,
            "setup_seconds": self.setup_seconds,
        }
        try:
            # getattr-shaped: hand-constructed engines (Engine.__new__
            # in tests) may lack a model.
            status["fingerprint"] = self.fingerprint()
        except Exception:  # noqa: BLE001 — health must never crash
            pass
        if ready and probe:
            try:
                probe_x = np.zeros((1, self.model.input_dim))
                out = self.infer(probe_x)
                status["probe_ok"] = bool(np.isfinite(out).all())
            except Exception as e:  # a failing probe is the finding, not a crash
                status["probe_ok"] = False
                status["probe_error"] = repr(e)
        return status


def load_inputs(path) -> tuple[np.ndarray, np.ndarray]:
    """Examples-file loader re-export for driver code."""
    return load_examples(path)
