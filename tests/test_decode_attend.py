"""The decode step's shared-K/V attention kernel
(kernels/decode_attend.py) against the XLA path it replaces where the
shapes tile (models/sambay.py:_attend_rows), in interpret mode on the CPU.

Four K/V heads of 16 in two pairs, an extent of 768: the kernel walks it
in tiles of 256 (512 does not divide it) and copies by 128 lanes, so a
slot's frontier falls inside a tile, on its edge and on a 128-lane edge.
The cache the kernel is handed has two slots more than it is asked
about, NaN throughout, and NaN in every 128-lane tile of the asked slots
past the one their frontier lies in: a byte read that should not be
shows as NaN (a dead V lane times a zero probability).  With float32
operands both paths compute one mathematics in another order: 2e-6 on
outputs that spread by one.  With bfloat16 operands the XLA path rounds
``a`` to 8 bits of mantissa before the values' product and the kernel
does not: 2e-2.  tests/test_tpu_compile.py compiles the kernel for a
described v5e inside the step at the cell's shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.kernels import decode_attend
from tpu_dist_nn.models import sambay

G, D, M, SPARE = 4, 16, 768, 2
TOL = {"float32": 2e-6, "bfloat16": 2e-2}
POS = {
    "nothing-cached": [0, 1, 0, 1],
    "tile-edges": [255, 256, 257, 767],   # below, at, past an edge; M - 1
    "lane-edges": [127, 128, 129, 384],
    "all-equal": [300, 300, 300, 300],
    "all-different": [5, 200, 455, 700],
    "one-slot": [333],
    "one-slot-empty": [0],
    "one-slot-full": [767],
}


def _operands(pos, dtype, seed=0):
    """(q, K, V, k_own, v_own) for slots at ``pos``, and the cache as the
    kernel gets it: spare slots and unread tiles poisoned."""
    S, dtype = len(pos), jnp.dtype(dtype)
    ks = jax.random.split(jax.random.key(seed), 5)
    draw = lambda k, shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    q = draw(ks[0], (S, G // 2, 2, 2, D))
    K, V = draw(ks[1], (1, S, G, D, M)), draw(ks[2], (1, S, G, D, M))
    dead = np.arange(M)[None, :] >= 128 * decode_attend.fetched_tiles(
        pos)[:, None]
    poison = lambda a: jnp.concatenate([  # noqa: E731
        jnp.where(dead[None, :, None, None, :], jnp.nan, a),
        jnp.full((1, SPARE) + a.shape[2:], jnp.nan, dtype)], axis=1)
    return (q, K, V, draw(ks[3], (S, G, D)), draw(ks[4], (S, G, D)),
            poison(K), poison(V))


def _oracle(q, K, V, k_own, v_own, pos, lam):
    visible = jnp.arange(M)[None, :] < jnp.asarray(pos)[:, None]
    return np.asarray(jax.jit(sambay._attend_rows)(
        q, K[0], V[0], k_own, v_own, visible, lam))


def _kernel(q, K, V, k_own, v_own, pos, lam, **kw):
    return np.asarray(jax.jit(
        lambda *a: decode_attend.attend_rows(*a, **kw))(
        q, K, V, k_own, v_own, jnp.asarray(pos, jnp.int32), jnp.float32(lam)))


@pytest.mark.parametrize("dtype, lam", [("float32", 0.6), ("bfloat16", -0.4)])
@pytest.mark.parametrize("case", sorted(POS))
def test_kernel_matches_the_xla_path(case, dtype, lam):
    pos = POS[case]
    assert decode_attend.tiles(len(pos), G, D, M, dtype) == 256
    q, K, V, k_own, v_own, K_seen, V_seen = _operands(pos, dtype)
    want = _oracle(q, K, V, k_own, v_own, pos, lam)
    got = _kernel(q, K_seen, V_seen, k_own, v_own, pos, lam)
    assert got.shape == want.shape == (len(pos), G // 2, 2, 2 * D)
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < TOL[dtype]


@pytest.mark.parametrize("dtype, lam, tile", [
    ("float32", -0.4, 128), ("bfloat16", 0.6, 128), ("float32", 0.6, 384)])
def test_other_tile_widths_and_the_other_sign(dtype, lam, tile):
    """What tools/decode_attend_sweep.py times: a tile of one copy, and
    one of three."""
    pos = POS["all-different"]
    q, K, V, k_own, v_own, K_seen, V_seen = _operands(pos, dtype, seed=1)
    got = _kernel(q, K_seen, V_seen, k_own, v_own, pos, lam, tile=tile)
    want = _oracle(q, K, V, k_own, v_own, pos, lam)
    assert np.abs(got - want).max() < TOL[dtype]


def test_a_kernel_that_reads_one_tile_too_few_is_caught(monkeypatch):
    """Planted: the copies and the walk stop 128 lanes short of the
    frontier; the lanes they miss keep the buffer's zeros."""
    pos = POS["all-different"]
    q, K, V, k_own, v_own, K_seen, V_seen = _operands(pos, "float32")
    want = _oracle(q, K, V, k_own, v_own, pos, 0.6)
    reach = decode_attend._reach
    monkeypatch.setattr(decode_attend, "_reach",
                        lambda pos: jnp.maximum(reach(pos) - 128, 1))
    decode_attend._call.cache_clear()  # the kernel traced whole
    got = _kernel(q, K_seen, V_seen, k_own, v_own, pos, 0.6)
    decode_attend._call.cache_clear()
    assert np.abs(got - want)[1:].max() > 1e3 * TOL["float32"]
    # The slot at 5 has one tile: there is none to leave out.
    assert np.abs(got - want)[0].max() < TOL["float32"]


def test_a_kernel_that_leaves_the_own_column_out_is_caught(monkeypatch):
    pos = POS["all-different"]
    q, K, V, k_own, v_own, K_seen, V_seen = _operands(pos, "float32")
    want = _oracle(q, K, V, k_own, v_own, pos, 0.6)
    own = decode_attend._own_scores
    monkeypatch.setattr(decode_attend, "_own_scores",
                        lambda qg, k: jnp.full_like(own(qg, k), -jnp.inf))
    got = _kernel(q, K_seen, V_seen, k_own, v_own, pos, 0.6)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() > 1e3 * TOL["float32"]


@pytest.mark.parametrize("shape, dtype, tile", [
    ((96, 20, 64, 3072), "bfloat16", 512),   # the benchmark cell's step
    ((1, 20, 64, 3072), "bfloat16", 512),    # ... and its final chunk's tail
    ((3, 4, 8, 128), "float32", 128),        # the toy of tests/test_sambay.py
    ((3, 4, 8, 128), "bfloat16", None),      # heads of 8: half a bfloat16 tile
    ((3, 3, 16, 768), "bfloat16", None),     # K/V heads that do not pair
    ((3, 4, 16, 700), "bfloat16", None),     # an extent of no whole lane tiles
    ((3, 4, 16, 768), "int8", None),
    ((8, 20, 64, 262144), "bfloat16", None),  # scores past what VMEM holds
], ids=["cell-step", "cell-tail", "toy-f32", "toy-bf16", "odd-heads",
        "ragged-extent", "int8", "long-extent"])
def test_the_shapes_alone_decide(shape, dtype, tile):
    assert decode_attend.tiles(*shape, dtype) == tile


def test_fetched_tiles_are_those_with_a_position_before_pos():
    got = decode_attend.fetched_tiles(np.array([0, 1, 128, 129, 3071]))
    assert got.tolist() == [1, 1, 1, 2, 24] and isinstance(got, np.ndarray)
