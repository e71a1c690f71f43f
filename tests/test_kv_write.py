"""kernels/kv_write.py: one new K/V row per slot, in place (interpret
mode here; tests/test_tpu_compile.py compiles it for a v5e)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.kernels.kv_write import write_row, write_rows


@pytest.mark.parametrize(
    "L, total, S, H, Dh, M, dtype",
    [
        pytest.param(2, 5, 3, 3, 8, 13, "float32", id="one_block_f32"),
        # The position axis spans three 128-lane blocks, the last partial.
        pytest.param(2, 4, 4, 5, 16, 300, "bfloat16", id="partial_lane_block"),
        # More slots than one 128-lane group of new rows, and a pool behind.
        pytest.param(1, 140, 133, 2, 8, 130, "bfloat16", id="two_slot_groups"),
    ],
)
def test_write_rows_lands_rows_and_nothing_else(L, total, S, H, Dh, M, dtype):
    keys = jax.random.split(jax.random.key(0), 4)
    k = jax.random.normal(keys[0], (L, total, H, Dh, M)).astype(dtype)
    v = jax.random.normal(keys[1], (L, total, H, Dh, M)).astype(dtype)
    new_k = jax.random.normal(keys[2], (L, S, H, Dh)).astype(dtype)
    new_v = jax.random.normal(keys[3], (L, S, H, Dh)).astype(dtype)
    pos = np.array(jax.random.randint(jax.random.key(5), (S,), 0, M))
    pos[0], pos[1] = 0, M - 1
    active = np.arange(S) % 3 != 2
    # An inactive slot's position may be stale and out of range.
    pos[2] = M + 7
    got_k, got_v = jax.jit(write_rows)(
        k, v, new_k, new_v, jnp.asarray(pos, jnp.int32), jnp.asarray(active)
    )
    for got, old, new in ((got_k, k, new_k), (got_v, v, new_v)):
        want = np.asarray(old.astype(jnp.float32)).copy()
        new = np.asarray(new.astype(jnp.float32))
        for s in range(S):
            if active[s]:
                want[:, s, :, :, pos[s]] = new[:, s]
        assert got.dtype == old.dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)), want
        )


@pytest.mark.parametrize(
    "L, total, S, H, Dh, M, dtype",
    [
        # One latent "head" of 40 numbers, three lane blocks, a pool behind.
        pytest.param(3, 5, 4, 1, 40, 300, "bfloat16", id="latent_row"),
        pytest.param(2, 3, 3, 2, 8, 13, "float32", id="one_block_f32"),
    ],
)
def test_write_row_is_write_rows_on_one_array(L, total, S, H, Dh, M, dtype):
    """The one-array twin (a model that caches a latent row, not a key
    and a value) leaves what `write_rows` leaves in either of its two."""
    keys = jax.random.split(jax.random.key(1), 2)
    cache = jax.random.normal(keys[0], (L, total, H, Dh, M)).astype(dtype)
    new = jax.random.normal(keys[1], (L, S, H, Dh)).astype(dtype)
    pos = jnp.asarray([0, M - 1, M + 7, 5][:S], jnp.int32)
    active = jnp.asarray([True, True, False, True][:S])
    got = jax.jit(write_row)(cache, new, pos, active)
    want, _ = jax.jit(write_rows)(cache, cache, new, new, pos, active)
    assert got.dtype == cache.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    assert (np.asarray(got.astype(jnp.float32))
            != np.asarray(cache.astype(jnp.float32))).any()
