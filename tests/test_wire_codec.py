"""Wire-codec fast lane: vectorized/scalar equivalence, fallback
contract and decode-into-staging (ISSUE 10).

The fast path's contract is exact: for ANY byte string, the vectorized
decoder must produce a byte-identical result — or raise the same error
— as the general per-row parser; for ANY array, the vectorized encoder
must emit byte-identical wire bytes to the legacy per-row encoder.
These tests fuzz both directions and drive every documented fallback
trigger (unpacked fixed64 rows, interleaved unknown fields, truncated
payloads, ragged widths, non-uniform headers).
"""

import threading

import numpy as np
import pytest

from tpu_dist_nn.obs.registry import REGISTRY
from tpu_dist_nn.serving.wire import (
    WireMatrix,
    decode_matrix,
    decode_matrix_into,
    decode_matrix_lazy,
    decode_matrix_scalar,
    encode_matrix,
    encode_matrix_scalar,
)


def _counter(name):
    return REGISTRY.get(name).labels().value


def _encode_unpacked(x):
    """proto2-style writer: one fixed64 field per value (legal, never
    fast-path-shaped)."""
    parts = []
    for row in np.asarray(x, np.float64):
        body = b"".join(b"\x09" + np.float64(v).tobytes() for v in row)
        parts.append(b"\x0a" + bytes([len(body)]) + body)
    return b"".join(parts)


def _encode_with_unknown_fields(x):
    """Conforming message with an unknown varint field interleaved
    between rows (field 2, wire type 0) — parsers must skip it."""
    out = bytearray()
    for row in np.asarray(x, np.float64):
        payload = row.tobytes()
        body = b"\x0a" + bytes([len(payload)]) + payload
        out += b"\x0a" + bytes([len(body)]) + body
        out += b"\x10\x2a"  # field 2 varint 42
    return bytes(out)


# ------------------------------------------------------------ equivalence


def test_encode_vectorized_matches_scalar_bytes_exactly():
    rng = np.random.default_rng(0)
    for shape in [(1, 1), (1, 784), (2, 3), (7, 13), (64, 784), (3, 0),
                  (0, 0), (33, 1), (256, 16)]:
        x = rng.normal(scale=10.0 ** rng.integers(-4, 5), size=shape)
        assert encode_matrix(x) == encode_matrix_scalar(x), shape
        # Engine-dtype input: the codec owns the one f64 cast, and the
        # bytes must match the scalar path's pre-cast pipeline.
        x32 = x.astype(np.float32)
        assert encode_matrix(x32) == encode_matrix_scalar(x32), shape
    # Integer input (the Generate client's token ids).
    ids = rng.integers(0, 1 << 20, (5, 9))
    assert encode_matrix(ids) == encode_matrix_scalar(ids)
    # Non-contiguous input encodes by value, not by memory layout.
    base = rng.normal(size=(8, 20))
    view = base[::2, ::3]
    assert encode_matrix(view) == encode_matrix_scalar(np.ascontiguousarray(view))


def test_decode_fast_path_matches_scalar_on_random_shapes():
    rng = np.random.default_rng(1)
    for _ in range(50):
        n, d = int(rng.integers(1, 40)), int(rng.integers(0, 50))
        x = rng.normal(scale=10.0 ** rng.integers(-3, 4), size=(n, d))
        wire = encode_matrix(x)
        fast = decode_matrix(wire)
        general = decode_matrix_scalar(wire)
        assert fast.shape == general.shape == (n, d)
        np.testing.assert_array_equal(fast, general)
        # dtype-landing parity too (the serving path's engine dtype).
        np.testing.assert_array_equal(
            decode_matrix(wire, dtype=np.float32),
            decode_matrix_scalar(wire, dtype=np.float32),
        )


def test_decode_fuzz_fast_and_scalar_agree_on_mutated_bytes():
    """Random truncations/bit-flips/appends: both parsers must agree —
    same array or both raise ValueError. The fast path may only ever
    DECLINE to a fallback, never diverge."""
    rng = np.random.default_rng(2)
    base = encode_matrix(rng.normal(size=(5, 7)))
    for _ in range(400):
        b = bytearray(base)
        op = rng.integers(0, 3)
        if op == 0 and len(b) > 1:
            b = b[: int(rng.integers(1, len(b)))]
        elif op == 1:
            i = int(rng.integers(0, len(b)))
            b[i] ^= 1 << int(rng.integers(0, 8))
        else:
            b += bytes(rng.integers(0, 256, int(rng.integers(1, 16))))
        data = bytes(b)
        try:
            general = decode_matrix_scalar(data)
            g_err = None
        except ValueError as e:
            general, g_err = None, str(e)
        try:
            fast = decode_matrix(data)
            f_err = None
        except ValueError as e:
            fast, f_err = None, str(e)
        assert (g_err is None) == (f_err is None), (g_err, f_err)
        if g_err is None:
            np.testing.assert_array_equal(fast, general)
        else:
            assert f_err == g_err


# -------------------------------------------------------- fallback triggers


def test_fallback_unpacked_fixed64_rows_decode_identically():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 6))
    wire = _encode_unpacked(x)
    before = _counter("tdn_wire_decode_fallback_total")
    np.testing.assert_array_equal(decode_matrix(wire), x)
    assert _counter("tdn_wire_decode_fallback_total") == before + 1
    # The lazy entry point falls back to a fully-decoded ndarray.
    out = decode_matrix_lazy(wire, dtype=np.float32)
    assert isinstance(out, np.ndarray)
    np.testing.assert_array_equal(out, x.astype(np.float32))


def test_fallback_interleaved_unknown_fields_decode_identically():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(3, 5))
    wire = _encode_with_unknown_fields(x)
    np.testing.assert_array_equal(decode_matrix(wire), x)
    np.testing.assert_array_equal(decode_matrix_scalar(wire), x)


def test_fallback_truncated_payload_raises_same_error():
    x = np.arange(12.0).reshape(2, 6)
    wire = encode_matrix(x)
    cut = wire[:-16]  # lengths still claim 6 doubles; only 4 remain
    with pytest.raises(ValueError, match="truncated"):
        decode_matrix(cut)
    with pytest.raises(ValueError, match="truncated"):
        decode_matrix_scalar(cut)
    with pytest.raises(ValueError, match="truncated"):
        decode_matrix_lazy(cut)


def test_fallback_ragged_widths_raise_same_error():
    r2 = b"\x0a\x10" + np.zeros(2).tobytes()
    r1 = b"\x0a\x08" + np.zeros(1).tobytes()
    ragged = (b"\x0a" + bytes([len(r2)]) + r2
              + b"\x0a" + bytes([len(r1)]) + r1)
    for fn in (decode_matrix, decode_matrix_scalar, decode_matrix_lazy):
        with pytest.raises(ValueError, match="ragged"):
            fn(ragged)


def test_fast_counter_ticks_and_uniform_rows_stay_fast():
    rng = np.random.default_rng(5)
    wire = encode_matrix(rng.normal(size=(9, 4)))
    fast0 = _counter("tdn_wire_decode_fast_total")
    fb0 = _counter("tdn_wire_decode_fallback_total")
    decode_matrix(wire)
    assert isinstance(decode_matrix_lazy(wire), WireMatrix)
    assert _counter("tdn_wire_decode_fast_total") == fast0 + 2
    assert _counter("tdn_wire_decode_fallback_total") == fb0


def test_protoc_shaped_single_and_multi_row_messages_hit_fast_path():
    """Bytes built the way protoc's serializer emits them (minimal
    varints, packed field 1) must probe fast — the whole point is that
    the reference's own clients ride the fast lane."""
    for n, d in [(1, 3), (2, 3), (17, 784)]:
        x = np.arange(n * d, dtype=np.float64).reshape(n, d)
        wire = encode_matrix_scalar(x)  # scalar = the protoc layout
        assert isinstance(decode_matrix_lazy(wire), WireMatrix), (n, d)


# --------------------------------------------------- decode-into-staging


def test_decode_into_lands_rows_at_offset_in_target_dtype():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(4, 7))
    staging = np.full((10, 7), -1.0, np.float32)
    n = decode_matrix_into(encode_matrix(x), staging, row_offset=3)
    assert n == 4
    np.testing.assert_array_equal(staging[3:7], x.astype(np.float32))
    assert (staging[:3] == -1.0).all() and (staging[7:] == -1.0).all()
    # The fallback layout lands through the same call.
    n = decode_matrix_into(_encode_unpacked(x), staging, row_offset=0)
    assert n == 4
    np.testing.assert_array_equal(staging[0:4], x.astype(np.float32))


def test_decode_into_rejects_width_mismatch_and_overflow():
    x = np.zeros((2, 5))
    with pytest.raises(ValueError, match="width"):
        decode_matrix_into(encode_matrix(x), np.zeros((4, 6)))
    with pytest.raises(ValueError, match="overflow"):
        decode_matrix_into(encode_matrix(x), np.zeros((2, 5)), row_offset=1)


def test_wire_matrix_shape_len_array_and_read_into():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(3, 8))
    w = decode_matrix_lazy(encode_matrix(x), dtype=np.float32)
    assert isinstance(w, WireMatrix)
    assert len(w) == 3 and w.shape == (3, 8) and w.ndim == 2
    assert w.dtype == np.float32
    # np.asarray materializes through __array__ in the carried dtype.
    np.testing.assert_array_equal(np.asarray(w), x.astype(np.float32))
    buf = np.zeros((8, 8), np.float32)
    assert w.read_into(buf, 2) == 3
    np.testing.assert_array_equal(buf[2:5], x.astype(np.float32))
    with pytest.raises(ValueError, match="width"):
        w.read_into(np.zeros((8, 9), np.float32))
    with pytest.raises(ValueError, match="overflow"):
        w.read_into(buf, 6)


def test_single_row_lazy_matrix_broadcasts_into_staging():
    # n == 1 rides a contiguous offset-frombuffer view (no reshape);
    # it must still land correctly in a staging slot.
    x = np.arange(5.0).reshape(1, 5) * 1.5
    w = decode_matrix_lazy(encode_matrix(x))
    assert isinstance(w, WireMatrix) and w.shape == (1, 5)
    buf = np.zeros((4, 5))
    w.read_into(buf, 1)
    np.testing.assert_array_equal(buf[1], x[0])


def test_batcher_stages_wire_matrices_straight_into_bucket_buffer():
    """End-to-end through the real _Batcher: WireMatrix submissions
    coalesce with ndarray submissions, results fan out correctly, and
    the decode happened straight into the staging buffer (the fake
    engine sees one contiguous engine-dtype batch)."""
    from tpu_dist_nn.serving.server import _Batcher

    seen = []

    class Echo:
        def infer(self, x):
            seen.append(np.asarray(x).copy())
            return np.asarray(x) * 2.0

    b = _Batcher(Echo(), submit_timeout=10.0, pipeline_depth=1)
    try:
        rng = np.random.default_rng(8)
        x1 = rng.normal(size=(2, 6)).astype(np.float32)
        x2 = rng.normal(size=(3, 6)).astype(np.float32)
        w1 = decode_matrix_lazy(encode_matrix(x1), dtype=np.float32)
        outs = {}
        t1 = threading.Thread(
            target=lambda: outs.__setitem__(1, b.submit(w1))
        )
        t2 = threading.Thread(
            target=lambda: outs.__setitem__(2, b.submit(x2))
        )
        t1.start(), t2.start()
        t1.join(5.0), t2.join(5.0)
        np.testing.assert_allclose(outs[1], x1 * 2.0, rtol=1e-6)
        np.testing.assert_allclose(outs[2], x2 * 2.0, rtol=1e-6)
        for batch in seen:
            assert batch.dtype == np.float32
    finally:
        b.close()


def test_single_wire_matrix_request_stages_rather_than_zero_copies():
    """A lone WireMatrix on a bucket boundary must still go through
    the staging buffer (there is no caller array to zero-copy-launch);
    the launch sees a real ndarray."""
    from tpu_dist_nn.serving.server import _Batcher

    launched = []

    class Echo:
        def infer(self, x):
            launched.append(x)
            return np.asarray(x) * 1.0

    b = _Batcher(Echo(), submit_timeout=10.0, pipeline_depth=1)
    try:
        x = np.arange(8.0, dtype=np.float32).reshape(2, 4)
        w = decode_matrix_lazy(encode_matrix(x), dtype=np.float32)
        out = b.submit(w)  # 2 rows == pow2 bucket boundary
        np.testing.assert_array_equal(out, x)
        assert isinstance(launched[0], np.ndarray)
    finally:
        b.close()


# ------------------------------------------------------------- bench A/B


def test_loopback_serving_round_trip_rides_fast_path():
    """A real GrpcClient -> server -> engine loop must keep every hop
    on the fast lane: the fallback counter does not move, the fast
    counter does, and results match the engine exactly."""
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from tpu_dist_nn.serving.server import GrpcClient, serve_engine

    class TinyEngine:
        dtype = np.float32

        class model:
            input_dim = 6

        def infer(self, x):
            return np.asarray(x, np.float32) + 1.0

    eng = TinyEngine()
    server, port = serve_engine(eng, 0, host="127.0.0.1", coalesce=True)
    try:
        fb0 = _counter("tdn_wire_decode_fallback_total")
        fast0 = _counter("tdn_wire_decode_fast_total")
        client = GrpcClient(f"127.0.0.1:{port}")
        try:
            x = np.arange(18.0).reshape(3, 6)
            out = client.process(x)
            np.testing.assert_allclose(out, x + 1.0, rtol=1e-6)
        finally:
            client.close()
        assert _counter("tdn_wire_decode_fallback_total") == fb0
        # Server decode + client reply decode both probed fast.
        assert _counter("tdn_wire_decode_fast_total") >= fast0 + 2
    finally:
        server.stop(0)
