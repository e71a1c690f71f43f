"""The dropless expert layer that a family with routed experts calls.

A layer TOLD which experts it holds (``held``, the chip's share of an
expert-parallel deployment): the family routes over all of them with its
own router (Kimi-K2's sigmoid scores and selection bias,
:func:`tpu_dist_nn.models.mla_moe.route`; Laguna's softmax,
:func:`tpu_dist_nn.models.laguna.route`), and this layer computes the
part of the result its own experts give and knows nothing else of the
deployment.  No capacity, no dropped token.  Two forms, chosen by the
number of tokens (:func:`experts_form`): every held expert on every
token under the gate (a decode step: the matrices are read once either
way), or the (token, expert) pairs sorted by expert into row tiles, a
loop over the tiles that hold a pair (a chunk: ragged by load).

It also counts routing on the device: what a launch adds to a family's
running ``routed`` vector (:func:`counts`), read back by name with
:func:`routing_counts` (``SlotModel.routing_counts``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist_nn.models.sala import _einsum32

# Rows of a tile of (token, expert) pairs in the ragged form: one
# expert's matrices are read once a tile.
PAIR_TILE = 128
# The most tokens the masked form serves: below it a held expert's
# matrices are read longer than its products take on every token (v5e:
# 6 D F bytes at 819 GB/s against tokens x 6 D F FLOPs at 197 TFLOP/s
# cross at 240 tokens).
DENSE_TOKENS = 128


def experts_form(tokens: int) -> str:
    """``"dense"`` or ``"ragged"``: which form of the routed product a
    program of ``tokens`` tokens is built with.  It reads the shape,
    once, at trace time."""
    return "dense" if int(tokens) <= DENSE_TOKENS else "ragged"


def held_gates(chosen, w, held: tuple):
    """``(A, n_held)``: whether each token chose each held expert, and
    the expert's weight for it (float32, zero where it did not)."""
    ids = jnp.asarray(held, jnp.int32)
    hit = chosen[:, :, None] == ids[None, None, :]
    return jnp.any(hit, 1), jnp.sum(jnp.where(hit, w[:, :, None], 0.0), 1)


def experts_dense(u, on, gates, ex_gu, ex_d, layer):
    """Every held expert of layer ``layer`` of the stacks ``ex_gu (Lm,
    N, D, 2 F)``, ``ex_d (Lm, N, F, D)`` on every token, under the
    gate."""
    gu = jnp.einsum("ad,ndf->naf", u, lax.dynamic_index_in_dim(
        ex_gu, layer, 0, keepdims=False))
    F = ex_d.shape[-2]
    y = _einsum32("naf,nfd->nad", jax.nn.silu(gu[..., :F]) * gu[..., F:],
                  lax.dynamic_index_in_dim(ex_d, layer, 0, keepdims=False))
    return jnp.sum(y * gates.T[:, :, None], 0)


def experts_ragged(u, on, gates, ex_gu, ex_d, layer):
    """The pairs (token, held expert) that were chosen, sorted by
    expert into tiles of ``PAIR_TILE`` rows, each expert's rows padded
    to whole tiles; a loop over the tiles that hold a pair, one expert's
    matrices a tile, sliced out of the stacks inside the tile's own
    products (a layer of them handed to the loop whole would be copied
    first: 1.06 GB a layer of Kimi-K2's share, 2.42 GB of Laguna's).
    Sized for the worst case (every token on every held expert) by
    shape, and as long as the load by trip count."""
    A, N = gates.shape
    R = PAIR_TILE
    on = on.T  # (N, A): expert-major, so a sort is a cumsum
    count = jnp.sum(on, -1)
    tiles = -(-count // R)
    first_tile = jnp.cumsum(tiles) - tiles
    n_tiles = -(-A // R) * N  # every expert full
    # Row of the pair (n, a) in the tiled table, or past it.
    rank = jnp.cumsum(on, -1) - 1
    dest = jnp.where(on, first_tile[:, None] * R + rank, n_tiles * R)
    token = jnp.full((n_tiles * R + 1,), A, jnp.int32).at[dest.ravel()].set(
        jnp.broadcast_to(jnp.arange(A, dtype=jnp.int32), (N, A)).ravel())
    gate = jnp.zeros((n_tiles * R + 1,), jnp.float32).at[dest.ravel()].set(
        gates.T.ravel())
    expert_of_tile = jnp.sum(
        jnp.arange(n_tiles)[:, None] >= (first_tile + tiles)[None, :], -1)
    F = ex_d.shape[-2]
    padded = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])

    def expert(stack, n):
        return lax.dynamic_slice(stack, (layer, n, 0, 0),
                                 (1, 1) + stack.shape[2:])[0, 0]

    def tile(i, out):
        rows = lax.dynamic_slice(token, (i * R,), (R,))
        g = lax.dynamic_slice(gate, (i * R,), (R,))
        n = jnp.minimum(expert_of_tile[i], N - 1)
        gu = padded[rows] @ expert(ex_gu, n)
        y = _einsum32("rf,fd->rd", jax.nn.silu(gu[:, :F]) * gu[:, F:],
                      expert(ex_d, n)) * g[:, None]
        # Back to the tokens by a product with the rows' one-hot: the
        # MXU's work, where a scatter-add is the scalar core's.
        hot = rows[None, :] == jnp.arange(A, dtype=jnp.int32)[:, None]
        return out + _einsum32("ar,rd->ad", hot.astype(u.dtype),
                               y.astype(u.dtype))

    return lax.fori_loop(0, jnp.sum(tiles), tile,
                         jnp.zeros((A, u.shape[1]), jnp.float32))


def routed(u, on, gates, ex_gu, ex_d, layer, counted, scope: str):
    """The held experts' part of the routed sum of ``u (A, D)``
    (float32), for the tokens' choices of held experts ``on`` and their
    weights ``gates`` (:func:`held_gates`), under the family's traced
    scope ``scope``; and the layer's routing counts over the tokens
    ``counted (A,)``: pairs by held expert ``(n_held,)``."""
    with jax.named_scope(scope):
        form = experts_dense if experts_form(u.shape[0]) == "dense" \
            else experts_ragged
        out = form(u, on, gates, ex_gu, ex_d, layer)
    return out, jnp.sum(on & counted[:, None], 0, dtype=jnp.int32)


def counts(n_moe: int, n_held: int, k: int, pairs, tokens, step: bool):
    """What one launch adds to a family's ``routed`` vector (``n_held +
    3`` int32): ``pairs (n_moe, n_held)`` by layer and held expert over
    ``tokens`` counted tokens, then the pairs routed anywhere, and in a
    step the (layer, held expert) visits that got a pair and all of
    them."""
    visits = n_moe * n_held
    return jnp.concatenate([
        jnp.sum(pairs, 0),
        jnp.stack([
            tokens * (k * n_moe),
            jnp.sum(pairs > 0, dtype=jnp.int32) if step else jnp.int32(0),
            jnp.where(tokens > 0, visits, 0) if step else jnp.int32(0),
        ]).astype(jnp.int32)])


def routing_counts(routed_vec, n_held: int) -> dict:
    """A ``routed`` vector by name (device values; the scheduler
    fetches them together): ``expert_pairs (n_held,)``,
    ``routed_pairs``, ``expert_touched``, ``expert_visits``."""
    r, n = routed_vec, n_held
    return {"expert_pairs": r[:n], "routed_pairs": r[n],
            "expert_touched": r[n + 1], "expert_visits": r[n + 2]}
