"""The chunk's expanded-attention kernel (kernels/expand_attend.py)
against the XLA loop it replaces where the shapes tile
(models/mla_moe.py:_expanded_loop), in interpret mode on the CPU.

A toy of the family at widths a TPU tile fits: 4 heads of 32 + 16 over a
latent of 128 + 16, values of 128, chunks of 256 into an extent of 1536
= three key tiles of 512 (the benchmark cell's 9216 is eighteen).  With
float32 operands both paths compute one mathematics in another order
(one product of depth ``d_n + d_r`` against two, added): 1e-5 covers the
reordering.  With bfloat16 operands both round the expanded keys, the
values and the probabilities to 8 bits of mantissa at the same places,
after subtracting the same running maxima (the key tiles are the
loop's): what is left is the scores' order of summation moving a
rounding now and then, 0.01 on outputs that spread by one.
tests/test_tpu_compile.py compiles the same kernel for a described v5e
at the cell's shapes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.kernels import expand_attend
from tpu_dist_nn.models import mla_moe
from tpu_dist_nn.obs.registry import Registry
from tpu_dist_nn.obs.runtime import RuntimeSampler
from tpu_dist_nn.serving.continuous import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(**over):
    return mla_moe.MlaMoeConfig(**{**dict(
        vocab_size=256, hidden_size=64, n_heads=4, q_lora_rank=32,
        kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=128, intermediate_size=128, moe_intermediate_size=64,
        n_layers=2, first_k_dense=1, router_width=8, experts_held=(0, 1, 2),
        n_experts_per_tok=2, routed_scaling_factor=1.5, max_seq_len=4096,
        rope_factor=4.0, rope_original_len=64, rope_mscale_all_dim=1.0,
        param_dtype="float32"), **over})


WIDE = _config()
C, M = 256, 1536
TOL = {"float32": 1e-5, "bfloat16": 1e-2}


def _operands(start, dtype, seed=0):
    """q, the slot's rows and ``w_kvb`` by head; rows past the chunk hold
    what a slot's last occupant left."""
    H, rkv = WIDE.n_heads, WIDE.kv_lora_rank
    dn, dr, dv = WIDE.qk_nope_head_dim, WIDE.qk_rope_head_dim, WIDE.v_head_dim
    ks = jax.random.split(jax.random.key(seed), 5)
    q_n = jax.random.normal(ks[0], (C, H, dn)).astype(dtype)
    q_r = jax.random.normal(ks[1], (C, H, dr)).astype(dtype)
    rows = jax.random.normal(ks[2], (1, rkv + dr, M)).astype(dtype)
    wk = (jax.random.normal(ks[3], (rkv, H, dn)) / np.sqrt(rkv)).astype(dtype)
    wv = (jax.random.normal(ks[4], (rkv, H, dv)) / np.sqrt(rkv)).astype(dtype)
    return q_n, q_r, rows, wk, wv, start + jnp.arange(C)


def _both(q_n, q_r, rows, wk, wv, t):
    scale = WIDE.softmax_scale
    want = jax.jit(lambda *a: mla_moe._expanded_loop(*a, scale))(
        q_n, q_r, rows, t, wk, wv)
    got = jax.jit(lambda *a: expand_attend.attend_chunk(*a, scale))(
        q_n, q_r, rows, wk, wv, t[0])
    return np.asarray(got), np.asarray(want)


# 0: a prompt's first chunk (the second key tile is skipped whole, and
# half of the first one's pairs); 512: a tile's edge; 300: mid-tile,
# three tiles visited by a chunk of half a tile; 1280: the extent's last
# chunk, whose last key tile is the grid's last; 37: a resume after a
# prefix hit, no multiple of the chunk or of anything else.
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("start", [0, 512, 300, 1280, 37])
def test_kernel_matches_the_loop(start, dtype):
    assert expand_attend.tiles(C, 4, 128, 32, 16, 128, M, dtype) \
        == (4, 256, 512)
    got, want = _both(*_operands(start, jnp.dtype(dtype), seed=start))
    assert got.shape == want.shape == (C, 4, 128)
    assert got.dtype == want.dtype == np.float32
    assert np.isfinite(got).all() and np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < TOL[dtype]


def test_rows_past_the_chunk_are_never_read():
    """Key tiles past the chunk's last position (a former occupant's
    rows, here NaN) reach no product: later grid steps re-name the last
    visited tile and skip the body, as the loop's trip count stops."""
    q_n, q_r, rows, wk, wv, t = _operands(512, jnp.float32, seed=7)
    dirty = rows.at[:, :, 1024:].set(jnp.nan)
    got, want = _both(q_n, q_r, rows, wk, wv, t)
    scale = WIDE.softmax_scale
    again = np.asarray(jax.jit(lambda *a: expand_attend.attend_chunk(
        *a, scale))(q_n, q_r, dirty, wk, wv, t[0]))
    np.testing.assert_array_equal(again, got)


def test_other_tilings_give_the_same_numbers():
    """The sweep's knob (`tile=`): two heads a group, query tiles of
    128."""
    q_n, q_r, rows, wk, wv, t = _operands(300, jnp.float32, seed=8)
    got, want = _both(q_n, q_r, rows, wk, wv, t)
    other = np.asarray(jax.jit(lambda *a: expand_attend.attend_chunk(
        *a, WIDE.softmax_scale, tile=(2, 128, 512)))(
        q_n, q_r, rows, wk, wv, t[0]))
    assert np.abs(other - want).max() < 1e-5


def _rehearsal():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "rehearsal-kimi-tiny.json")) as f:
        return mla_moe.MlaMoeConfig.from_dict(json.load(f))


CELL = dict(n_heads=64, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, max_seq_len=262144,
            param_dtype="bfloat16")


@pytest.mark.parametrize("cfg, size, max_len, tiled", [
    (WIDE, 256, 1536, True),
    (_config(**CELL), 1024, 9215, True),   # the benchmark cell's
    (_rehearsal, 136, 968, False),         # the rehearsal's: heads of 16
    (WIDE, 200, 1536, False),     # a chunk that is no whole query tile
    (WIDE, 256, 1400, False),     # an extent (1408) no key tile divides
    (_config(v_head_dim=64), 256, 1536, False),  # values under a lane tile
    (_config(kv_lora_rank=96), 256, 1536, False),  # a latent under one
], ids=["toy", "cell", "rehearsal", "ragged-chunk", "ragged-extent",
        "narrow-values", "narrow-latent"])
def test_the_shapes_alone_decide_which_path_runs(cfg, size, max_len, tiled):
    """What `SlotModel.attend_kernel` says is what the chunk program
    holds: a `pallas_call` named `expand_attend` where the shapes tile,
    the loop elsewhere."""
    cfg = cfg() if callable(cfg) else cfg
    assert cfg.slot_model().attend_kernel(size, max_len) is tiled
    if size > 256:
        return  # the cell's program: tests/test_tpu_compile.py
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: mla_moe.init_slot_cache(cfg, 1, max_len))
    text = str(jax.make_jaxpr(
        lambda p, c, tok, st: mla_moe.prefill_chunk_into_cache(
            p, cfg, c, 0, tok, st))(
        params, cache, jax.ShapeDtypeStruct((1, size), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
    assert ("expand_attend" in text) is tiled
    assert ("pallas_call" in text) is tiled


def test_cell_tiling_is_the_one_timed():
    """The cell's `(1024, 64, 512, 128, 64, 128, 9216)` in bfloat16 takes
    the tiling PERF.md section 6 quotes; the rehearsal's says no."""
    assert expand_attend.tiles(
        1024, 64, 512, 128, 64, 128, 9216, "bfloat16") == (8, 512, 1024)
    assert expand_attend.tiles(
        136, 4, 32, 16, 8, 16, 1024, "bfloat16") is None


def test_chunks_then_steps_through_the_model_agree_with_the_loop(monkeypatch):
    """A prompt of 768 in chunks of 256 through every layer into a cache
    of 1024, then three decode steps: last-position logits of each chunk
    and each step with the kernel and with the dispatch forced to the
    loop."""
    params = WIDE.init_params(jax.random.key(3))
    tokens = np.random.default_rng(4).integers(0, 256, (1, 768))

    def run():
        pre = jax.jit(lambda c, t, st: mla_moe.prefill_chunk_into_cache(
            params, WIDE, c, 0, t, st))
        step = jax.jit(lambda c, pos, tok: mla_moe.decode_step_slots(
            params, c, pos, tok, WIDE))
        cache, out = mla_moe.init_slot_cache(WIDE, 1, 1024), []
        for at in range(0, 768, 256):
            logits, cache = pre(cache, jnp.asarray(tokens[:, at:at + 256]), at)
            out.append(np.asarray(logits))
        for pos in range(768, 771):
            logits, cache = step(cache, jnp.asarray([pos]),
                                 jnp.argmax(logits, -1).astype(jnp.int32))
            out.append(np.asarray(logits))
        return np.stack(out), cache

    assert WIDE.attend_kernel(256, 1024)
    got, got_cache = run()
    monkeypatch.setattr(expand_attend, "tiles", lambda *a, **k: None)
    assert not WIDE.attend_kernel(256, 1024)
    want, want_cache = run()
    assert np.abs(got - want).max() < 1e-4 and np.abs(want).max() > 0.5
    np.testing.assert_allclose(got_cache["lat"], want_cache["lat"], atol=1e-4)
    np.testing.assert_array_equal(got_cache["routed"], want_cache["routed"])


@pytest.fixture(scope="module")
def served():
    """One request of 600 prompt positions in chunks of 256 (two whole
    query tiles, then 88 positions that are none) into an extent of
    1024, and the scheduler's counters as /metrics shows them."""
    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    params = WIDE.init_params(jax.random.key(5))
    sched = ContinuousScheduler(params, WIDE, slots=1, prompt_len=600,
                                max_new_tokens=425, prefill_chunk=256)
    sampler.add_generation_scheduler(sched)
    try:
        prompt = np.random.default_rng(6).integers(0, 256, (1, 600))
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
    assert sched.prefill_chunks_total == 3
    assert sched.attend_kernel_chunks_total == 2


@pytest.mark.parametrize("family, value", [
    ("tdn_gen_prefill_chunks_total", 3),
    ("tdn_gen_attend_kernel_chunks_total", 2),
])
def test_kernel_hit_share_is_scraped(served, family, value):
    assert served[1][family][()] == value
