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


def held_choices(chosen, held: tuple):
    """``(A, k)`` int32: the place in ``held`` of each expert a token
    chose, or ``len(held)`` where it is held elsewhere.  Elementwise,
    one comparison a run of consecutive ids in ``held`` (a chip's share
    of an expert-parallel deployment is one run), made at trace time: a
    lookup table would be a gather of ``A k`` scalars, 0.17 ms a layer
    of the 16k repository cell's chunk on a v5e (tools/experts_sweep.py,
    PERF.md)."""
    pick = jnp.full(chosen.shape, len(held), jnp.int32)
    first = 0
    for n in range(1, len(held) + 1):
        if n == len(held) or held[n] != held[n - 1] + 1:
            lo = held[first]
            pick = jnp.where((chosen >= lo) & (chosen <= held[n - 1]),
                             chosen - lo + first, pick)
            first = n
    return pick


def experts_ragged(u, pick, w, counted, ex_gu, ex_d, layer):
    """The chosen pairs (token, held expert) of ``pick (A, k)``
    (:func:`held_choices`) with weights ``w (A, k)``, sorted by expert
    and cut into tiles of ``PAIR_TILE`` rows, each expert's last tile
    padded; a loop over the tiles that hold a pair, one expert's
    matrices a tile, sliced out of the stacks inside the tile's own
    products (a layer of them handed to the loop whole would be copied
    first: 1.06 GB a layer of Kimi-K2's share, 2.42 GB of Laguna's).
    Only the ``A k`` choices are sorted, and a tile reads its rows and
    weights as slices of the sorted choices: nothing is sized by every
    token on every held expert.  Each tile's rows go back to their
    tokens by a product with their one-hot, the MXU's work (a gather of
    every choice's row, or a scatter of a tile's, moves rows at a fifth
    of the bytes' rate or less: PERF.md).

    Returns ``(out (A, D) float32, pairs (N,), rows (2,))``: the pairs
    by held expert over the tokens ``counted (A,)``, and the tile rows
    the loop computed and the pair rows among them."""
    A, k = pick.shape
    N, R, D = ex_gu.shape[1], PAIR_TILE, u.shape[1]
    E = A * k
    # Choices held elsewhere sort last, past every held expert's.
    key, entry, gate = lax.sort(
        (pick.ravel(), jnp.arange(E, dtype=jnp.int32),
         w.ravel().astype(jnp.float32)), num_keys=1, is_stable=True)
    bound = jnp.searchsorted(key, jnp.arange(N + 1, dtype=key.dtype),
                             method="scan_unrolled").astype(jnp.int32)
    start, count = bound[:-1], jnp.diff(bound)
    tiles = -(-count // R)
    first_tile = jnp.cumsum(tiles) - tiles
    # The most tiles the choices can fill: each expert's last one partly.
    n_tiles = min(-(-A // R) * N, (A * min(k, N) + N * (R - 1)) // R)
    owner = jnp.minimum(jnp.sum(jnp.arange(n_tiles)[:, None]
                                >= (first_tile + tiles)[None, :], -1), N - 1)
    done = (jnp.arange(n_tiles) - first_tile[owner]) * R
    # A tile's expert, first row among the sorted choices, live rows.
    plan = jnp.stack([owner, start[owner] + done, count[owner] - done], 1)
    # Past the last choice a tile's slices read padding.
    token = jnp.concatenate([entry // k, jnp.full((R,), A, jnp.int32)])
    gate = jnp.concatenate([gate, jnp.zeros((R,), jnp.float32)])
    F = ex_d.shape[-2]
    padded = jnp.concatenate([u, jnp.zeros((1, D), u.dtype)])

    def expert(stack, n):
        return lax.dynamic_slice(stack, (layer, n, 0, 0),
                                 (1, 1) + stack.shape[2:])[0, 0]

    def tile(i, out):
        n, at, live = lax.dynamic_index_in_dim(plan, i, keepdims=False)
        live = jnp.arange(R) < live
        rows = jnp.where(live, lax.dynamic_slice(token, (at,), (R,)), A)
        g = jnp.where(live, lax.dynamic_slice(gate, (at,), (R,)), 0.0)
        gu = padded[rows] @ expert(ex_gu, n)
        y = _einsum32("rf,fd->rd", jax.nn.silu(gu[:, :F]) * gu[:, F:],
                      expert(ex_d, n)) * g[:, None]
        hot = rows[None, :] == jnp.arange(A, dtype=jnp.int32)[:, None]
        return out + _einsum32("ar,rd->ad", hot.astype(u.dtype),
                               y.astype(u.dtype))

    out = lax.fori_loop(0, jnp.sum(tiles), tile,
                        jnp.zeros((A, D), jnp.float32))
    seen = jnp.cumsum(counted[entry // k], dtype=jnp.int32)
    seen = jnp.concatenate([jnp.zeros((1,), jnp.int32), seen])
    return out, seen[bound[1:]] - seen[bound[:-1]], \
        jnp.stack([jnp.sum(tiles) * R, jnp.sum(count)])


def routed(u, chosen, w, held: tuple, ex_gu, ex_d, layer, counted,
           scope: str):
    """The held experts' part of the routed sum of ``u (A, D)``
    (float32), for the tokens' ``chosen (A, k)`` experts of the router
    and their weights ``w``, of which this layer holds ``held``, under
    the family's traced scope ``scope``; and the layer's routing counts
    ``(n_held + 2,)``: pairs by held expert over the tokens ``counted
    (A,)``, then the tile rows the ragged form computed and the pair
    rows among them (none in the masked form)."""
    if experts_form(u.shape[0]) == "dense":
        on, gates = held_gates(chosen, w, held)
        with jax.named_scope(scope):
            out = experts_dense(u, on, gates, ex_gu, ex_d, layer)
        return out, jnp.concatenate([
            jnp.sum(on & counted[:, None], 0, dtype=jnp.int32),
            jnp.zeros((2,), jnp.int32)])
    with jax.named_scope(scope):
        out, pairs, rows = experts_ragged(
            u, held_choices(chosen, held), w, counted, ex_gu, ex_d, layer)
    return out, jnp.concatenate([pairs, rows])


def no_pairs(n_held: int):
    """What a layer without routed experts adds to the routing counts
    (:func:`routed`)."""
    return jnp.zeros((n_held + 2,), jnp.int32)


def counts(n_moe: int, n_held: int, k: int, pairs, tokens, step: bool):
    """What one launch adds to a family's ``routed`` vector (``n_held +
    5`` int32, :func:`init_counts`): ``pairs (n_moe, n_held + 2)`` by
    layer (:func:`routed`) over ``tokens`` counted tokens.  The pairs by
    held expert, then the pairs routed anywhere, in a step the (layer,
    held expert) visits that got a pair and all of them, then the
    ragged form's tile rows and the pair rows among them."""
    visits = n_moe * n_held
    held = pairs[:, :n_held]
    total = jnp.sum(pairs, 0)
    return jnp.concatenate([
        total[:n_held],
        jnp.stack([
            tokens * (k * n_moe),
            jnp.sum(held > 0, dtype=jnp.int32) if step else jnp.int32(0),
            jnp.where(tokens > 0, visits, 0) if step else jnp.int32(0),
        ]).astype(jnp.int32),
        total[n_held:]])


def init_counts(n_held: int):
    """A slot cache's zeroed ``routed`` vector (:func:`counts`)."""
    return jnp.zeros((n_held + 5,), jnp.int32)


def routing_counts(routed_vec, n_held: int) -> dict:
    """A ``routed`` vector by name (device values; the scheduler
    fetches them together): ``expert_pairs (n_held,)``,
    ``routed_pairs``, ``expert_touched``, ``expert_visits``, and the
    ragged form's tile rows by what they held, ``tile_rows_pair`` and
    ``tile_rows_pad``."""
    r, n = routed_vec, n_held
    return {"expert_pairs": r[:n], "routed_pairs": r[n],
            "expert_touched": r[n + 1], "expert_visits": r[n + 2],
            "tile_rows_pair": r[n + 4], "tile_rows_pad": r[n + 3] - r[n + 4]}
