"""The expert layer both routed families call (models/experts.py): the
ragged form against the masked form on loads picked to reach its
edges, what the ragged form is built from at a chunk of the 16k
repository cell's counts, and the tile rows it counts.

Tolerances.  In float32 at matmul precision `highest` (the suite's
default) the two forms compute the same products and differ in the
order of a token's sum alone: 1e-5.  In bfloat16 the ragged form rounds
each pair's row to bfloat16 before the float32 sum, where the masked
form sums float32 rows: a relative 2^-8 of each of a token's rows, so
the bound is 1/64 of the largest result.
"""

import collections
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.models import experts, falcon_h1, generate, laguna, sala
from tpu_dist_nn.models.transformer import TransformerConfig, init_transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Six held experts of a router of 16, not in a row; 4 chosen a token.
HELD = (1, 4, 6, 9, 12, 15)
ROUTER, K, D, F = 16, 4, 64, 32
A = 300  # past the masked form's 128 tokens: the ragged form


def _choices(case: str, seed=0):
    """``chosen (A, K)`` router ids, distinct in a row, for ``case``."""
    rng = np.random.default_rng(seed)
    elsewhere = [e for e in range(ROUTER) if e not in HELD]
    rows = []
    for a in range(A):
        if case == "crowded" and a < 200:  # 200 pairs: two tiles
            row = [HELD[0]] + list(rng.choice(
                [e for e in range(ROUTER) if e != HELD[0]], K - 1, False))
        elif case == "empty":  # held expert 9 is chosen by no token
            row = list(rng.choice([e for e in range(ROUTER) if e != 9], K,
                                  False))
        elif case == "elsewhere" and a % 3 == 0:  # no held expert at all
            row = list(rng.choice(elsewhere, K, False))
        else:
            row = list(rng.choice(ROUTER, K, False))
        rows.append(row)
    return jnp.asarray(np.array(rows, np.int32))


def _places(chosen):
    """Each choice's place in ``HELD``, ``len(HELD)`` where elsewhere."""
    where = {e: n for n, e in enumerate(HELD)}
    return np.vectorize(lambda e: where.get(int(e), len(HELD)))(
        np.asarray(chosen))


def _layer(dtype, seed=1):
    ks = jax.random.split(jax.random.key(seed), 4)
    u = jax.random.normal(ks[0], (A, D), jnp.float32)
    w = jax.random.uniform(ks[1], (A, K), jnp.float32, 0.1, 1.0)
    ex_gu = jax.random.normal(ks[2], (1, len(HELD), D, 2 * F)) / D ** 0.5
    ex_d = jax.random.normal(ks[3], (1, len(HELD), F, D)) / F ** 0.5
    return (u.astype(dtype), w, ex_gu.astype(dtype), ex_d.astype(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["crowded", "empty", "elsewhere"])
def test_ragged_form_is_the_masked_form(case, dtype):
    """An expert with more pairs than a tile, an expert with none, and
    tokens that chose no held expert: the ragged form gives each token
    what the masked form gives it."""
    chosen = _choices(case)
    u, w, ex_gu, ex_d = _layer(jnp.dtype(dtype))
    pick = _places(chosen)
    np.testing.assert_array_equal(experts.held_choices(chosen, HELD), pick)
    count = np.bincount(pick.ravel(), minlength=len(HELD) + 1)[:-1]
    if case == "crowded":
        assert count[0] > experts.PAIR_TILE
    if case == "empty":
        assert count[HELD.index(9)] == 0
    if case == "elsewhere":
        assert (pick[::3] == len(HELD)).all()
    ragged = jax.jit(lambda *a: experts.routed(
        *a, HELD, ex_gu, ex_d, 0, jnp.ones((A,), bool), "x")[0])(
            u, chosen, w)
    on, gates = experts.held_gates(chosen, w, HELD)
    dense = experts.experts_dense(u, on, gates, ex_gu, ex_d, 0)
    ragged, dense = np.asarray(ragged), np.asarray(dense)
    assert np.abs(dense).max() > 0.1
    atol = 1e-5 if dtype == "float32" else np.abs(dense).max() / 64
    np.testing.assert_allclose(ragged, dense, atol=atol)
    if case == "elsewhere":
        assert (ragged[::3] == 0).all()


def test_ragged_form_counts_its_tile_rows_and_pairs():
    """The routing counts of a ragged layer: pairs by held expert over
    the counted tokens, then ``sum(ceil(count / 128)) * 128`` tile rows
    computed and ``sum(count)`` pair rows among them, every token's
    pairs counted whether or not the token is."""
    chosen = _choices("crowded", seed=3)
    u, w, ex_gu, ex_d = _layer(jnp.float32)
    counted = jnp.asarray(np.arange(A) % 4 != 1)
    _, got = experts.routed(u, chosen, w, HELD, ex_gu, ex_d, 0, counted, "x")
    pick = _places(chosen)
    count = np.bincount(pick.ravel(), minlength=len(HELD) + 1)[:-1]
    mine = np.bincount(pick[np.asarray(counted)].ravel(),
                       minlength=len(HELD) + 1)[:-1]
    R = experts.PAIR_TILE
    want = np.concatenate([mine, [int(np.sum(-(-count // R))) * R,
                                  int(count.sum())]])
    np.testing.assert_array_equal(np.asarray(got), want)
    # The masked form computes no tile.
    _, dense = experts.routed(u[:100], chosen[:100], w[:100], HELD, ex_gu,
                              ex_d, 0, counted[:100], "x")
    assert (np.asarray(dense)[-2:] == 0).all()


def _shapes(text: str):
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text):
        yield tuple(int(d) for d in dims.rstrip("x").split("x"))


def test_ragged_form_sorts_the_choices_and_sizes_nothing_by_the_worst_case():
    """At a chunk of the 16k repository cell's counts (2048 tokens, 10
    experts a token of a router of 256, 128 held) and a toy width, the
    lowered layer sorts the 20 480 choices and no more, and no array
    of it is as long as every token on every held expert in tiles
    (ceil(A / 128) x 128 x 128 rows: the tables the form was once
    built from)."""
    Ac, k, N, R = 2048, 10, 128, experts.PAIR_TILE
    held = tuple(range(128, 256))
    d, f = 8, 4
    spec = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda u, chosen, w, gu, dn: experts.routed(
        u, chosen, w, held, gu, dn, 0, jnp.ones((Ac,), bool),
        "laguna.experts")).lower(
            spec((Ac, d), jnp.bfloat16), spec((Ac, k), jnp.int32),
            spec((Ac, k), jnp.float32), spec((1, N, d, 2 * f), jnp.bfloat16),
            spec((1, N, f, d), jnp.bfloat16))
    text = lowered.as_text()
    sorts = re.findall(r"stablehlo\.sort.*?\}\) : \(([^)]*)\)", text, re.S)
    assert len(sorts) == 1
    assert set(re.findall(r"tensor<(\d+)x", sorts[0])) == {str(Ac * k)}
    worst = -(-Ac // R) * N * R
    longest = max(max(s) for s in _shapes(text))
    assert longest < worst, longest


def test_laguna_chunk_counts_tile_rows_and_a_step_none():
    """Through the model: a 136-token chunk (ragged) adds tile rows, a
    whole number of tiles a layer, of which its pairs held here are the
    pair rows; a decode step (masked) adds none."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "rehearsal-laguna-tiny.json")) as fh:
        cfg = laguna.LagunaConfig.from_dict(
            dict(json.load(fh), param_dtype="float32"))
    params = cfg.init_params(jax.random.key(2))
    cache = laguna.init_slot_cache(cfg, 2, 160)
    names = cfg.routing_counts
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 512, (1, 136)))
    _, cache = jax.jit(lambda c, t: laguna.prefill_chunk_into_cache(
        params, cfg, c, 0, t, 0))(cache, toks)
    got = {n: np.asarray(v) for n, v in names(cache).items()}
    pair, pad = int(got["tile_rows_pair"]), int(got["tile_rows_pad"])
    assert pair == int(got["expert_pairs"].sum()) > 0
    assert (pair + pad) % experts.PAIR_TILE == 0 and pad >= 0
    # At most one tile an expert and layer is part padding.
    assert pad < cfg.n_moe * cfg.n_held * experts.PAIR_TILE
    _, after = jax.jit(lambda c: laguna.decode_step_slots(
        params, c, jnp.full((2,), 136), jnp.zeros((2,), jnp.int32), cfg))(
            cache)
    step = {n: np.asarray(v) for n, v in names(after).items()}
    assert int(step["routed_pairs"]) > int(got["routed_pairs"])
    assert (int(step["tile_rows_pair"]), int(step["tile_rows_pad"])) \
        == (pair, pad)


# ------------------------------- what the change left as it was

def _ops(lowered) -> dict:
    """Operations by name in a lowered program."""
    return dict(collections.Counter(re.findall(
        r"= \"?([a-z_]+\.[a-z_.]+)", lowered.as_text())))


def _digest(ops: dict) -> str:
    return hashlib.sha256(json.dumps(sorted(ops.items())).encode()
                          ).hexdigest()[:16]


def _family(name):
    """(config, module, parameter init, cache extent, chunk) of a family
    without routed experts at its toy size."""
    if name == "gpt2":
        return (TransformerConfig(), generate,
                lambda cfg: init_transformer(jax.random.key(0), cfg), 64, 24)
    cfg = sala.load_model_config(os.path.join(
        ROOT, "benchmark", "configs", "rehearsal-falcon-h1-tiny.json"))
    return (cfg, falcon_h1, lambda cfg: cfg.init_params(jax.random.key(0)),
            80, 13)


# (operations, digest of their counts by name) of GPT-2's and
# Falcon-H1's programs at the parent of the change that sorted only the
# chunk's choices: neither imports the expert layer, and the counter
# the runtime sampler gained reaches no program.  MiniCPM-SALA's and
# SambaY's are pinned in tests/test_laguna.py and tests/test_falcon_h1.py.
UNROUTED_PARENT = {("gpt2", "chunk"): (320, "e82357ef1d26008b"),
                   ("gpt2", "step"): (845, "3176cfdda7410083"),
                   ("falcon_h1", "chunk"): (892, "4b0060175903bacf"),
                   ("falcon_h1", "step"): (1205, "2512ba8970194523")}


@pytest.mark.parametrize("family,program", sorted(UNROUTED_PARENT))
def test_families_without_routed_experts_build_the_parents_programs(
        family, program):
    cfg, mod, init, M, C = _family(family)
    p = jax.eval_shape(lambda: init(cfg))
    cache = jax.eval_shape(lambda: mod.init_slot_cache(cfg, 3, M))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    v3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    if program == "step":
        lowered = jax.jit(lambda p, c, pos, t, a: mod.decode_step_slots(
            p, c, pos, t, cfg, active=a)).lower(
                p, cache, v3, v3, jax.ShapeDtypeStruct((3,), jnp.bool_))
    else:
        lowered = jax.jit(lambda p, c, s, t, st: mod.prefill_chunk_into_cache(
            p, cfg, c, s, t, st)).lower(
                p, cache, i32, jax.ShapeDtypeStruct((1, C), jnp.int32), i32)
    ops = _ops(lowered)
    assert (sum(ops.values()), _digest(ops)) == UNROUTED_PARENT[
        family, program]
