"""Pipelined serving fast path (ISSUE 2): double-buffered batcher,
zero-copy staging, AOT bucket warm, launch-shape compile-cache keying.

The real Engine needs jax's mesh API (jax.sharding.AxisType), which
this container's jax may lack — engine-path tests either build a
mesh-free single-chip engine by hand (exercising the REAL
infer_async/fetch/warm_buckets code on the plain dense path) or
skip-gate on the mesh API. Batcher mechanics run against controlled
fake engines, the same convention as test_serving's _SlowEngine.
"""

import dataclasses
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.serving.server import _Batcher


class _Handle:
    def __init__(self, value):
        self.value = value


class AsyncFakeEngine:
    """Models JAX async dispatch: infer_async returns a handle after a
    host-side staging cost; fetch (the one host sync) pays the device
    time. ``per_row=True`` scales both costs with the batch's rows (so
    coalescing cannot amortize them away — the regime where pipelining
    pays). Gate lets tests hold a batch 'on the device' deliberately."""

    def __init__(self, dim=8, dispatch_seconds=0.0, fetch_seconds=0.0,
                 per_row=False):
        self.model = dataclasses.make_dataclass("M", ["input_dim"])(dim)
        self.dispatch_seconds = dispatch_seconds
        self.fetch_seconds = fetch_seconds
        self.per_row = per_row
        self.gate = threading.Event()
        self.gate.set()  # open unless a test closes it
        self.fetch_entered = threading.Event()
        self.dispatched_rows: list[list[float]] = []

    def _cost(self, seconds, n):
        if seconds:
            time.sleep(seconds * n if self.per_row else seconds)

    def infer_async(self, x):
        x = np.asarray(x)
        self._cost(self.dispatch_seconds, len(x))
        self.dispatched_rows.append(x[:, 0].tolist())
        return _Handle(x * 2.0)

    def fetch(self, handle):
        self.fetch_entered.set()
        self.gate.wait(10.0)
        self._cost(self.fetch_seconds, len(handle.value))
        return handle.value


def until(cond, timeout=5.0):
    """Poll a counter, not sleep: True once ``cond()`` holds."""
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.002)
    return cond()


def _mesh_free_engine(sizes=(8, 6, 4)):
    """A REAL Engine on the plain single-chip dense path, constructed
    without build_mesh (unavailable on this jax): every attribute
    _infer_impl/infer_async/fetch/warm_buckets touch is set the way
    __init__ would."""
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.models.fcnn import params_from_spec
    from tpu_dist_nn.testing.factories import random_model

    model = random_model(list(sizes), seed=0)
    e = Engine.__new__(Engine)
    e.model = model
    e._pp = e._hp = e._plan = e._q = e._q_pp = None
    e.int8_auto_disabled = False
    e._params = params_from_spec(model, jnp.float32)
    e.pipelined = False
    e.data_sharded = False
    e.dtype = jnp.float32
    e._np_dtype = np.dtype(jnp.float32)
    e._seen_infer_shapes = set()
    e._warm_buckets = set()
    e.num_microbatches = 4
    return e


# ------------------------------------------------------- batcher overlap


@pytest.mark.parametrize("depth", [2, 1])
def test_batches_launch_while_prior_fetch_in_flight(depth):
    # The tentpole behavior: with the fetch of batch 1 held open, the
    # dispatch stage must still assemble and LAUNCH batch 2 — launches
    # advance while a prior batch is materializing. At pipeline_depth=1
    # (the serial loop, kept as the control) dispatch fetches inline:
    # nothing launches until batch 1 is out, and nothing overlaps.
    eng = AsyncFakeEngine()
    eng.gate.clear()
    b = _Batcher(eng, submit_timeout=10.0, pipeline_depth=depth)
    outs: dict[int, np.ndarray] = {}

    def client(i):
        outs[i] = b.submit(np.full((1, 8), float(i)))

    try:
        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        assert eng.fetch_entered.wait(5.0)  # batch 1 is 'on the device'
        t2 = threading.Thread(target=client, args=(2,))
        t3 = threading.Thread(target=client, args=(3,))
        t2.start(), t3.start()
        if depth == 1:
            assert until(lambda: b.pending_rows == 2)
            assert len(eng.dispatched_rows) == 1 and b.batches_total == 1
        else:
            # Batch 2 (rows 2+3, coalesced) must LAUNCH while batch 1's
            # fetch is still blocked.
            assert until(lambda: len(eng.dispatched_rows) >= 2), (
                "no overlap: second batch never launched while the first "
                "was in flight"
            )
        eng.gate.set()
        for t in (t1, t2, t3):
            t.join(timeout=5.0)
        # Fan-out stayed correct either way: each request got exactly
        # its own rows back, in its own slot.
        for i in (1, 2, 3):
            np.testing.assert_array_equal(outs[i], np.full((1, 8), 2.0 * i))
        assert b.batches_total >= 2
        assert (b.overlapped_total >= 1) if depth == 2 else (
            b.overlapped_total == 0)
        assert b.inflight_batches == 0 and b.inflight_rows == 0
    finally:
        eng.gate.set()
        b.close()


def test_pipeline_depth_bounds_outstanding_launches():
    # pipeline_depth is a hard launch-ahead bound: with the drain gated
    # shut and depth=2, exactly 2 batches may be launched-but-undrained;
    # a 3rd must wait for a slot, not pile device work unboundedly.
    eng = AsyncFakeEngine()
    eng.gate.clear()
    b = _Batcher(eng, submit_timeout=10.0, pipeline_depth=2)
    threads = [
        threading.Thread(
            target=lambda i=i: b.submit(np.full((1, 8), float(i)))
        )
        for i in range(4)
    ]
    try:
        threads[0].start()
        assert eng.fetch_entered.wait(5.0)
        for t in threads[1:]:
            t.start()
            time.sleep(0.05)  # force each into its own batch
        deadline = time.monotonic() + 2.0
        while len(eng.dispatched_rows) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.15)  # would-be 3rd launch gets every chance to leak
        assert len(eng.dispatched_rows) == 2, eng.dispatched_rows
        assert b.inflight_batches == 2
        eng.gate.set()
        for t in threads:
            t.join(timeout=5.0)
        assert len(eng.dispatched_rows) >= 3  # freed slots drained the rest
    finally:
        eng.gate.set()
        b.close()


def test_ordering_and_error_fanout_survive_concurrency():
    # Per-request ordering and error isolation across many concurrent
    # submitters: wrong-width requests fail with the engine's dispatch
    # error while every well-formed request gets its own rows.
    from concurrent.futures import ThreadPoolExecutor

    from tpu_dist_nn.utils.errors import InvalidArgumentError

    class WidthCheckingEngine(AsyncFakeEngine):
        def infer_async(self, x):
            if np.asarray(x).shape[1] != 8:
                raise InvalidArgumentError("expected (N, 8)")
            return super().infer_async(x)

    eng = WidthCheckingEngine(fetch_seconds=0.002)
    b = _Batcher(eng, submit_timeout=10.0)
    try:
        def call(i):
            if i % 5 == 4:
                with pytest.raises(InvalidArgumentError):
                    b.submit(np.full((1, 5), float(i)))
                return None
            return b.submit(np.full((2, 8), float(i)))

        with ThreadPoolExecutor(max_workers=10) as ex:
            outs = list(ex.map(call, range(20)))
        for i, out in enumerate(outs):
            if i % 5 == 4:
                assert out is None
            else:
                np.testing.assert_array_equal(out, np.full((2, 8), 2.0 * i))
    finally:
        b.close()


def test_abandoned_requests_discarded_at_pop():
    # The discard-at-pop contract survives the two-stage split: a
    # request that timed out while the dispatch stage was busy must
    # never be computed once the stage recovers.
    from tpu_dist_nn.utils.errors import DeadlineExceededError

    release = threading.Event()
    seen: list[list[float]] = []

    def wedged_run(xs):
        release.wait(10.0)
        seen.append(np.asarray(xs)[:, 0].tolist())
        return np.asarray(xs)

    b = _Batcher(None, run_fn=wedged_run, submit_timeout=10.0)
    try:
        t1 = threading.Thread(target=lambda: b.submit(np.zeros((1, 8))))
        t1.start()
        time.sleep(0.05)  # let request 1 wedge inside the dispatch fn
        with pytest.raises(DeadlineExceededError):
            b.submit(np.full((1, 8), 7.0), timeout=0.1)
        release.set()
        out = b.submit(np.full((1, 8), 3.0), timeout=5.0)
        np.testing.assert_array_equal(out, np.full((1, 8), 3.0))
        t1.join(timeout=5.0)
        assert not any(7.0 in rows for rows in seen), seen
    finally:
        release.set()
        b.close()


def test_close_drains_both_stages():
    # Everything submitted before close() must complete through BOTH
    # stages; a submit after close() is UNAVAILABLE; no batch is left
    # in flight.
    from tpu_dist_nn.utils.errors import UnavailableError

    eng = AsyncFakeEngine(fetch_seconds=0.02)
    b = _Batcher(eng, submit_timeout=10.0)
    outs: dict[int, np.ndarray] = {}
    threads = [
        threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, b.submit(np.full((1, 8), float(i)))
            )
        )
        for i in range(6)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5.0
    while b.requests_total < 6 and time.monotonic() < deadline:
        time.sleep(0.002)
    b.close()
    for t in threads:
        t.join(timeout=5.0)
    assert sorted(outs) == list(range(6))
    for i, out in outs.items():
        np.testing.assert_array_equal(out, np.full((1, 8), 2.0 * i))
    assert b.inflight_batches == 0 and b.inflight_rows == 0
    with pytest.raises(UnavailableError):
        b.submit(np.zeros((1, 8)))


# ---------------------------------------------------- zero-copy staging


def test_staging_pads_to_bucket_zeroes_tail_and_reuses_buffer():
    eng = AsyncFakeEngine()
    b = _Batcher(eng)
    try:
        group = [
            {"x": np.full((2, 4), 1.0)},
            {"x": np.full((3, 4), 2.0)},
        ]
        xs, key, buf = b._stage(group)
        assert xs.shape == (8, 4)  # 5 rows -> pow2 bucket 8
        np.testing.assert_array_equal(xs[:2], 1.0)
        np.testing.assert_array_equal(xs[2:5], 2.0)
        np.testing.assert_array_equal(xs[5:], 0.0)  # pad tail zeroed
        b._release(key, buf)
        # Same bucket again: the SAME buffer comes back (no per-batch
        # allocation), previous garbage overwritten in place.
        xs2, key2, buf2 = b._stage(group)
        assert buf2 is buf and key2 == key
        np.testing.assert_array_equal(xs2[5:], 0.0)
    finally:
        b.close()


def test_staging_single_request_on_bucket_is_zero_copy():
    eng = AsyncFakeEngine()
    b = _Batcher(eng)
    try:
        x = np.zeros((4, 8))  # already a pow2 bucket
        xs, key, buf = b._stage([{"x": x}])
        assert xs is x and buf is None  # launched as-is, nothing staged
    finally:
        b.close()


def test_decode_matrix_lands_in_requested_dtype():
    from tpu_dist_nn.serving.wire import decode_matrix, encode_matrix

    rng = np.random.default_rng(3)
    x = rng.normal(size=(6, 5))
    out = decode_matrix(encode_matrix(x), dtype=np.float32)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, x.astype(np.float32))
    # Default stays the reference's exact float64 wire contract.
    np.testing.assert_array_equal(decode_matrix(encode_matrix(x)), x)


# --------------------------------------- engine async path + warm state


def test_engine_infer_async_fetch_matches_infer_and_defers_sync():
    import jax

    e = _mesh_free_engine()
    x = np.random.default_rng(1).uniform(0, 1, (3, 8))
    pending = e.infer_async(x)
    # The handle holds a DEVICE array: the host sync (np.asarray)
    # happens at fetch, not inside the launch critical section.
    assert isinstance(pending.value, jax.Array)
    out = e.fetch(pending)
    np.testing.assert_allclose(out, e.infer(x), rtol=1e-6)


def test_warm_buckets_ladder_gauge_and_no_misses_after_warm():
    from tpu_dist_nn.obs.registry import REGISTRY

    e = _mesh_free_engine()
    # Non-pow2 max warms through the CEILING bucket: a 5-row coalesced
    # batch pads to 8, so 8 must be warm too.
    assert e.warm_buckets(5) == [1, 2, 4, 8]
    assert e.warm_bucket_count == 4
    assert REGISTRY.get("tdn_engine_warm_buckets").labels().value == 4.0
    # Idempotent: a second warm compiles nothing new.
    assert e.warm_buckets(8) == []
    # After warm, bucket-shaped traffic never eats a compile: the miss
    # counter must not move.
    misses = REGISTRY.get("tdn_engine_compile_cache_misses_total")
    before = misses.labels().value
    for n in (1, 2, 4, 8):
        e.infer(np.zeros((n, 8), np.float32))
    assert misses.labels().value == before


def test_compile_cache_proxy_keys_on_launch_shape_plain_path():
    from tpu_dist_nn.obs.registry import REGISTRY

    e = _mesh_free_engine()
    misses = REGISTRY.get("tdn_engine_compile_cache_misses_total")
    hits = REGISTRY.get("tdn_engine_compile_cache_hits_total")
    m0, h0 = misses.labels().value, hits.labels().value
    e.infer(np.zeros((3, 8)))
    assert (misses.labels().value, hits.labels().value) == (m0 + 1, h0)
    e.infer(np.zeros((3, 8)))
    assert (misses.labels().value, hits.labels().value) == (m0 + 1, h0 + 1)
    assert (3, 8) in e._seen_infer_shapes


def test_compile_cache_proxy_counts_padded_launch_shape_data_sharded():
    # The satellite fix: the data-sharded path pads rows to the shard
    # count before jit sees them, so 3 rows and 4 rows on a 2-shard
    # mesh are the SAME compiled program — the second call must be a
    # cache hit, not a phantom miss.
    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.obs.registry import REGISTRY
    from tpu_dist_nn.testing.factories import random_model

    engine = Engine.up(random_model([6, 5, 4], seed=0), data_parallel=2,
                       warmup=False)
    misses = REGISTRY.get("tdn_engine_compile_cache_misses_total")
    engine.infer(np.zeros((3, 6)))  # launches padded (4, 6): miss
    before = misses.labels().value
    engine.infer(np.zeros((4, 6)))  # same launch shape: hit
    assert misses.labels().value == before
    engine.down()


def test_engine_single_cast_straight_to_engine_dtype():
    # _infer_impl must not stage a float64 copy: float32 input reaches
    # the launch unconverted (the old path went f64 -> f32 for every
    # batch, a full extra matrix per launch).
    e = _mesh_free_engine()
    x64 = np.random.default_rng(2).uniform(0, 1, (4, 8))
    out64 = e.infer(x64)
    out32 = e.infer(x64.astype(np.float32))
    np.testing.assert_allclose(out64, out32, rtol=1e-6)
    out, _mat, _launch, release = e._infer_impl(x64.astype(np.float32))
    assert out.dtype == jnp.float32
    # Matching dtype means no staging buffer was drawn from the pool.
    assert release is None


def test_cli_warmup_verb_reports_warm_state(monkeypatch, capsys):
    # `tdn warmup`: bring up, warm the ladder, report — engine bring-up
    # is stubbed with the mesh-free real engine (Engine.up needs the
    # mesh API this container's jax lacks; warm_buckets itself is real).
    import json

    import tpu_dist_nn.cli as cli

    eng = _mesh_free_engine()
    eng.setup_seconds = 0.0
    eng.placement = lambda: {"devices": 1}  # instance shadow: no mesh_spec
    eng.down = lambda: None
    monkeypatch.setattr(cli, "_engine_from_args", lambda args, **kw: eng)
    rc = cli.main(["warmup", "--config", "unused.json", "--rows", "8"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["warmed_buckets"] == [1, 2, 4, 8]
    assert out["warm_bucket_count"] == 4
    assert out["persists_across_processes"] == bool(
        out["persistent_cache_dir"]
    )
