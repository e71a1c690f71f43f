"""The chunk's block-masked attention kernel (kernels/sparse_attend.py)
against the XLA loop it replaces where the shapes tile
(models/sala.py:_attend_chunk), in interpret mode on the CPU.

A toy of the family at widths a TPU tile fits: heads of 128, blocks of
64, `dense_len` 256, an extent of 1152 = 9 x 128 positions, which no
whole number of the loop's 1024-key tiles covers (the kernel takes 384;
the benchmark cell's 33 024 is the same case with 768).  With float32
operands both paths compute one mathematics in another order of key
tiles: 1e-5 covers the reordering.  With bfloat16 operands each path
rounds its probabilities to 8 bits of mantissa after subtracting ITS
running maximum, which differs with the tiling: 0.02 on outputs that
spread by one is four such roundings.  tests/test_tpu_compile.py
compiles the same kernel for a described v5e at the cell's shapes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.kernels import sparse_attend
from tpu_dist_nn.models import sala
from tpu_dist_nn.models.transformer import TransformerConfig
from tpu_dist_nn.obs.registry import Registry
from tpu_dist_nn.obs.runtime import RuntimeSampler
from tpu_dist_nn.serving.continuous import ContinuousScheduler

WIDE = sala.SalaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, n_heads=4,
    n_kv_heads=2, head_dim=128, lightning_heads=2, lightning_head_dim=16,
    mixer_types=(sala.SPARSE, sala.LIGHTNING, sala.SPARSE),
    layer_ids=(9, 10, 16), published_layers=32, max_seq_len=4096,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=16,
    kernel_size=32, kernel_stride=16, block_size=64, topk=2,
    window_size=128, init_blocks=1, dense_len=256, param_dtype="float32",
)
NARROW = dataclasses.replace(WIDE, head_dim=16)
C, M = 128, 1152
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _operands(start, dtype, seed=0):
    """q, K, V and the selection `select_blocks` makes of random block
    scores for the chunk at `start`; rows past the chunk hold what a
    slot's last occupant left."""
    G, g, Dh = WIDE.n_kv_heads, WIDE.group, WIDE.head_dim
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (C, G, g, Dh)).astype(dtype)
    k = jax.random.normal(ks[1], (G, Dh, M)).astype(dtype)
    v = jax.random.normal(ks[2], (G, Dh, M)).astype(dtype)
    s = jax.random.normal(ks[3], (C, G, g, M // WIDE.kernel_stride))
    t = start + jnp.arange(C)
    return q, k, v, sala.select_blocks(s, t, WIDE, M), t


def _both(q, k, v, sel, t):
    want = jax.jit(lambda *a: sala._attend_chunk(*a, WIDE))(q, k, v, sel, t)
    got = jax.jit(lambda *a: sparse_attend.attend_chunk(
        *a, WIDE.block_size))(q, k, v, sel, t[0])
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


# start 0 and 128 lie inside dense_len, 512 past it, 1024 is the
# extent's last chunk: its last key tile is the loop's clamped one.
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("start", [0, 128, 512, 1024])
def test_kernel_matches_the_loop(start, dtype):
    q, k, v, sel, t = _operands(start, jnp.dtype(dtype))
    assert sparse_attend.tiles(C, WIDE.group, WIDE.head_dim, M,
                               WIDE.block_size) == (128, 384)
    if start >= 512:
        # It does select: of the blocks a query could see it takes the
        # first, its window's and two more.
        seen = (np.arange(M // 64)[None, :] <= (np.asarray(t) // 64)[:, None])
        assert np.asarray(sel)[:, 0][seen].mean() < 0.75
    got, want = _both(q, k, v, sel, t)
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < TOL[dtype]


def test_kernel_with_a_key_tile_nobody_selected():
    """The chunk at 768 sees key tiles 0, 1 and its own; tile 1
    (positions 384 .. 767) is selected by no query, then tile 0 by none:
    a tile that adds nothing leaves the statistics as they were, also
    when it is the first and the maximum is still -inf."""
    q, k, v, sel, t = _operands(768, jnp.float32, seed=1)
    tile = np.arange(M // 64) // (384 // 64)
    for dead in (1, 0):
        got, want = _both(q, k, v, sel & jnp.asarray(tile != dead), t)
        assert np.abs(got - want).max() < 1e-5


def test_a_row_with_nothing_selected_comes_back_zero():
    q, k, v, sel, t = _operands(512, jnp.float32, seed=2)
    sel = sel.at[5].set(False)
    got = np.asarray(jax.jit(lambda *a: sparse_attend.attend_chunk(
        *a, WIDE.block_size))(q, k, v, sel, t[0])).reshape(C, -1)
    assert np.isfinite(got).all() and not got[5].any() and got[4].any()


@pytest.mark.parametrize("cfg, size, max_len, tiled", [
    (WIDE, 128, 1152, True),
    (WIDE, 2048, 33023, True),    # the benchmark cell's: extent 33 024
    (WIDE, 100, 1152, False),     # a chunk that is no whole query tile
    (WIDE, 128, 1088, False),     # an extent that no 128-lane tile divides
    (WIDE, 128, 640, False),      # ... that only an untimed key tile does
    (NARROW, 128, 1152, False),   # heads narrower than the lanes
], ids=["toy", "cell", "ragged-chunk", "ragged-extent", "small-key-tile",
        "narrow-heads"])
def test_the_shapes_alone_decide_which_path_runs(cfg, size, max_len, tiled):
    """What `SlotModel.attend_kernel` says is what the chunk program
    holds: a `pallas_call` where the shapes tile, the loop elsewhere."""
    assert cfg.slot_model().attend_kernel(size, max_len) is tiled
    if size > 128:
        return  # the cell's program: tests/test_tpu_compile.py
    params = jax.eval_shape(lambda: sala.init_sala(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: sala.init_slot_cache(cfg, 1, max_len))
    text = str(jax.make_jaxpr(
        lambda p, c, tok, st: sala.prefill_chunk_into_cache(
            p, cfg, c, 0, tok, st))(
        params, cache, jax.ShapeDtypeStruct((1, size), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
    assert ("pallas_call" in text) is tiled
    assert ("sparse_attend" in text) is tiled


def test_gpt2_has_no_attention_kernel_to_report():
    assert not TransformerConfig().slot_model().attend_kernel(128, 1152)


def test_chunks_through_the_model_agree_with_the_loop(monkeypatch):
    """A prompt of 512 in chunks of 128 through every layer, into a
    cache of 768 (one key tile of the cell's size): last-position logits
    of each chunk with the kernel and with the dispatch forced to the
    loop."""
    params = sala.init_sala(jax.random.key(3), WIDE)
    tokens = np.random.default_rng(4).integers(0, 256, (1, 512))

    def run():
        pre = jax.jit(lambda c, t, st: sala.prefill_chunk_into_cache(
            params, WIDE, c, 0, t, st))
        cache, out = sala.init_slot_cache(WIDE, 1, 768), []
        for at in range(0, 512, 128):
            logits, cache = pre(cache, jnp.asarray(tokens[:, at:at + 128]), at)
            out.append(np.asarray(logits))
        return np.stack(out), cache

    assert WIDE.attend_kernel(128, 768)
    got, got_cache = run()
    monkeypatch.setattr(sparse_attend, "tiles", lambda *a: None)
    assert not WIDE.attend_kernel(128, 768)
    want, want_cache = run()
    assert np.abs(got - want).max() < 1e-4 and np.abs(want).max() > 0.5
    for name in ("k", "v", "ck", "state"):
        np.testing.assert_allclose(got_cache[name], want_cache[name],
                                   atol=1e-4)


@pytest.fixture(scope="module")
def served():
    """One request of 200 prompt positions in chunks of 128 (a whole
    query tile, then 72 positions that are none) into an extent of 384,
    and the scheduler's counters as /metrics shows them."""
    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    params = sala.init_sala(jax.random.key(5), WIDE)
    sched = ContinuousScheduler(params, WIDE, slots=1, prompt_len=200,
                                max_new_tokens=185, prefill_chunk=128)
    sampler.add_generation_scheduler(sched)
    try:
        prompt = np.random.default_rng(6).integers(0, 256, (1, 200))
        stream = sched.submit_stream(prompt, max_new_tokens=3)
        while stream.next_event(120.0)[0] == "tokens":
            pass
        sampler.sample_once()
    finally:
        sched.close()
    scraped = {m.name: {k: c.value for k, c in m.samples()}
               for m in reg.collect()}
    return sched, scraped


def test_scheduler_counts_the_chunks_the_kernel_served(served):
    sched, _ = served
    assert sched.prefill_chunks_total == 2
    assert sched.attend_kernel_chunks_total == 1


@pytest.mark.parametrize("family, value", [
    ("tdn_gen_prefill_chunks_total", 2),
    ("tdn_gen_attend_kernel_chunks_total", 1),
])
def test_kernel_hit_share_is_scraped(served, family, value):
    assert served[1][family][()] == value
