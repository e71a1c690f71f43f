"""The decode step's absorbed latent attention kernel
(kernels/latent_attend.py) against the XLA path it replaces where the
shapes tile (models/mla_moe.py:_latent_einsums), in interpret mode on the
CPU.

Sixteen heads on rows of 128 + 16, an extent of 768: the kernel walks it
in tiles of 256 (1024 and 512 do not divide it) and copies by 128 lanes,
so a slot's frontier falls inside a tile, on its edge and on a 128-lane
edge.  The cache the kernel is handed has three layers and two slots
more than it is asked about, NaN in every layer but the one named, in
the spare slots, and in every 128-lane tile of the asked slots past the
one their frontier lies in: a byte read that should not be shows as NaN
(a dead lane times a zero probability).  With float32 operands both
paths compute one mathematics in another order (a running softmax over
tiles against one over the row): 2e-6 on outputs that spread by one.
With bfloat16 operands the XLA path rounds the normalised probabilities
to 8 bits of mantissa and the kernel the unnormalised ones: 2e-2.
tests/test_tpu_compile.py compiles the kernel for a described v5e alone
and inside the step at the cell's shapes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist_nn.kernels import latent_attend
from tpu_dist_nn.models import mla_moe
from tpu_dist_nn.serving.continuous import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, RKV, DR, M, L, SPARE = 16, 128, 16, 768, 3, 2
R = RKV + DR
SCALE = 0.11
TOL = {"float32": 2e-6, "bfloat16": 2e-2}
POS = {
    "nothing-cached": [0, 1, 0, 1],
    "tile-edges": [255, 256, 257, 767],   # below, at, past an edge; M - 1
    "lane-edges": [127, 128, 129, 384],
    "all-equal": [300, 300, 300, 300],
    "all-different": [5, 200, 455, 700],
    "long-then-short": [767, 3, 640, 0],  # dead lanes hold the slot before
    "one-slot": [333],
    "one-slot-empty": [0],
    "one-slot-full": [767],
}


def _operands(pos, dtype, layer, seed=0):
    """(q, own, lat) for slots at ``pos``, and the cache as the kernel
    gets it: other layers, spare slots and unread tiles poisoned."""
    S, dtype = len(pos), jnp.dtype(dtype)
    ks = jax.random.split(jax.random.key(seed), 3)
    draw = lambda k, shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    q, own = draw(ks[0], (S, H, R)), draw(ks[1], (S, R))
    rows = draw(ks[2], (S, 1, R, M))
    dead = np.arange(M)[None, :] >= 128 * latent_attend.fetched_tiles(
        pos)[:, None]
    seen = jnp.full((L, S + SPARE, 1, R, M), jnp.nan, dtype).at[
        layer, :S].set(jnp.where(dead[:, None, None, :], jnp.nan, rows))
    return q, own, rows, seen


def _oracle(q, rows, own, pos):
    return np.asarray(jax.jit(
        lambda *a: mla_moe._latent_einsums(*a, SCALE)[..., :RKV])(
        q, rows, own, jnp.asarray(pos, jnp.int32)))


def _kernel(q, lat, layer, own, pos, **kw):
    return np.asarray(jax.jit(lambda q, lat, layer, own, pos:
                              latent_attend.attend_rows(
                                  q, lat, layer, own, pos, RKV, SCALE, **kw))(
        q, lat, jnp.int32(layer), own, jnp.asarray(pos, jnp.int32)))


@pytest.mark.parametrize("dtype, layer", [("float32", 0), ("bfloat16", L - 1)])
@pytest.mark.parametrize("case", sorted(POS))
def test_kernel_matches_the_xla_path(case, dtype, layer):
    pos = POS[case]
    assert latent_attend.tiles(len(pos), H, R, RKV, M, dtype) == 256
    q, own, rows, seen = _operands(pos, dtype, layer)
    want = _oracle(q, rows, own, pos)
    got = _kernel(q, seen, layer, own, pos)
    assert got.shape == want.shape == (len(pos), H, RKV)
    assert got.dtype == np.float32
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < TOL[dtype]


@pytest.mark.parametrize("dtype, layer, tile, in_flight", [
    ("float32", 1, 128, 3), ("bfloat16", 0, 128, 2), ("float32", L - 1, 384, 4)])
def test_other_tile_widths_depths_and_layers(dtype, layer, tile, in_flight):
    """What tools/latent_attend_sweep.py times: a tile of one copy and
    one of three, two to four jobs in flight."""
    pos = POS["all-different"]
    q, own, rows, seen = _operands(pos, dtype, layer, seed=1)
    got = _kernel(q, seen, layer, own, pos, tile=tile, in_flight=in_flight)
    assert np.abs(got - _oracle(q, rows, own, pos)).max() < TOL[dtype]


def test_a_slot_not_decoded_gives_its_own_row_and_reads_one_piece():
    """``pos`` 0 after the gate: the softmax holds the own column alone,
    so the output is the own row's values on every head, whatever the
    slot's rows hold (NaN here past the first 128 lanes, garbage in
    them)."""
    pos = [0, 500, 0]
    q, own, rows, seen = _operands(pos, "float32", 1)
    got = _kernel(q, seen, 1, own, pos)
    for s in (0, 2):
        np.testing.assert_allclose(
            got[s], np.broadcast_to(np.asarray(own)[s, :RKV], (H, RKV)),
            atol=1e-6)
    assert np.abs(got[1] - _oracle(q, rows, own, pos)[1]).max() < TOL["float32"]


def test_a_kernel_that_reads_one_tile_too_few_is_caught(monkeypatch):
    """Planted: the copies and the walk stop 128 lanes short of the
    frontier; the lanes they miss keep what the buffer held."""
    pos = POS["all-different"]
    q, own, rows, seen = _operands(pos, "float32", 0)
    want = _oracle(q, rows, own, pos)
    reach = latent_attend._reach
    monkeypatch.setattr(latent_attend, "_reach",
                        lambda pos: jnp.maximum(reach(pos) - 128, 1))
    latent_attend._call.cache_clear()  # the kernel traced whole
    got = _kernel(q, seen, 0, own, pos)
    latent_attend._call.cache_clear()
    assert np.abs(got - want)[1:].max() > 1e3 * TOL["float32"]
    # The slot at 5 has one tile: there is none to leave out.
    assert np.abs(got - want)[0].max() < TOL["float32"]


def test_a_kernel_that_reads_another_layer_is_caught():
    """Planted from outside: the layer's index off by one names rows
    that are NaN here, and would be another layer's in a server."""
    pos = POS["all-different"]
    q, own, _, seen = _operands(pos, "float32", 1)
    assert np.isfinite(_kernel(q, seen, 1, own, pos)).all()
    assert np.isnan(_kernel(q, seen, 2, own, pos)).any()


@pytest.mark.parametrize("shape, dtype, tile", [
    ((48, 64, 576, 512, 9216), "bfloat16", 1024),  # the benchmark cell's step
    ((48, 64, 576, 512, 9216), "float32", 1024),
    ((3, 16, 144, 128, 768), "bfloat16", 256),     # this file's
    ((3, 8, 136, 128, 384), "float32", 128),
    ((3, 4, 40, 32, 256), "float32", None),        # the rehearsal's widths
    ((3, 4, 40, 32, 256), "bfloat16", None),
    ((3, 8, 136, 128, 384), "bfloat16", None),     # half a bfloat16 tile of heads
    ((3, 16, 128, 128, 768), "bfloat16", None),    # no rotated key: not this family
    ((3, 16, 144, 128, 700), "bfloat16", None),    # an extent of no whole lane tiles
    ((3, 16, 144, 128, 768), "int8", None),
    ((3, 4096, 576, 512, 9216), "bfloat16", 256),  # scores past what VMEM holds at 512
    ((3, 8192, 576, 512, 9216), "bfloat16", None),  # ... and at any width
], ids=["cell-step", "cell-f32", "toy-bf16", "toy-f32", "rehearsal-f32",
        "rehearsal-bf16", "few-heads", "no-key-part", "ragged-extent", "int8",
        "many-heads", "too-many-heads"])
def test_the_shapes_alone_decide(shape, dtype, tile):
    assert latent_attend.tiles(*shape, dtype) == tile


# ------------------------------------------------- inside the step program

with open(os.path.join(ROOT, "benchmark", "configs",
                       "rehearsal-kimi-tiny.json")) as f:
    TOY = json.load(f)
# The rehearsal's toy with heads and rows that tile: 16 heads, 128 + 16.
WIDE = dict(TOY, num_attention_heads=16, num_key_value_heads=16,
            kv_lora_rank=RKV, qk_rope_head_dim=DR)
WIDE32 = mla_moe.MlaMoeConfig.from_dict(dict(WIDE, param_dtype="float32"))
WIDE16 = mla_moe.MlaMoeConfig.from_dict(WIDE)
NARROW32 = mla_moe.MlaMoeConfig.from_dict(dict(TOY, param_dtype="float32"))
S, T, N = 3, 200, 12


@pytest.fixture(scope="module")
def weights():
    return mla_moe.init_mla_moe(jax.random.key(3), WIDE32)


@pytest.fixture(scope="module")
def rows():
    return np.random.default_rng(1).integers(0, 512, (S, T + N))


@pytest.mark.parametrize("cfg, tiled", [
    (WIDE32, True), (WIDE16, True), (NARROW32, False)],
    ids=["f32-16-heads", "bf16-16-heads", "rehearsal"])
def test_the_shapes_alone_decide_how_the_step_reads_the_cache(cfg, tiled):
    """What `SlotModel.step_kv_tiles` says is what the step holds: a
    `pallas_call` named latent_attend where the shapes tile, the einsums
    over the whole extent elsewhere."""
    assert (cfg.slot_model().step_kv_tiles(S, T + N - 1) is not None) is tiled
    params = jax.eval_shape(lambda: mla_moe.init_mla_moe(jax.random.key(0), cfg))
    cache = jax.eval_shape(
        lambda: mla_moe.init_slot_cache(cfg, S + 1, T + N - 1))
    ints = jax.ShapeDtypeStruct((S,), jnp.int32)
    step = str(jax.make_jaxpr(lambda p, c, pos, tok: mla_moe.decode_step_slots(
        p, c, pos, tok, cfg))(params, cache, ints, ints))
    assert ("latent_attend" in step) is tiled


def test_step_kv_tiles_are_the_tiles_the_kernels_plan_copies():
    """The count the scheduler books is `fetched_tiles`, which is what
    `_operands` leaves readable: the kernel is right with every other
    tile NaN (above) and wrong with the last counted one NaN too."""
    count = WIDE16.slot_model().step_kv_tiles(4, M - 1)
    pos = np.array([0, 1, 128, 129, 767])
    assert latent_attend.fetched_tiles(pos).tolist() == [1, 1, 1, 2, 6]
    assert count(pos) == (11, 5 * 6 - 11)
    assert count(np.zeros(4, np.int32)) == (4, 20)
    pos = POS["all-different"]
    q, own, _, seen = _operands(pos, "float32", 0)
    last = 128 * (latent_attend.fetched_tiles(pos) - 1)
    for s, at in enumerate(last):
        poisoned = seen.at[0, s, :, :, at:at + 128].set(jnp.nan)
        got = _kernel(q, poisoned, 0, own, pos)
        assert np.isnan(got[s]).all() and np.isfinite(np.delete(got, s, 0)).all()


def test_the_benchmarks_cell_counts_what_its_traffic_leaves_in_hbm():
    """The published widths at the repository cell's 48 slots of extent
    9 215: 47 slots decoding at 8 192-9 215 and one being prefilled
    (read as at position 0) skip a fifteenth of the extent's tiles."""
    from tpu_dist_nn.models import sala

    cfg = sala.load_model_config(os.path.join(
        ROOT, "benchmark", "configs", "kimi-k2.7-code.json"))
    assert mla_moe.step_kernel_tile(cfg, 48, 9216) == 1024
    count = cfg.slot_model().step_kv_tiles(48, 9215)
    pos = np.append(np.linspace(8192, 9215, 47).astype(np.int32), 0)
    visited, skipped = count(pos)
    assert visited + skipped == 48 * 72
    assert visited == int(np.ceil(pos[:47] / 128).sum()) + 1
    assert 0.06 < skipped / (48 * 72) < 0.08


@pytest.mark.parametrize("cfg", [WIDE32, WIDE16], ids=["f32", "bf16"])
def test_kernel_and_xla_paths_decode_alike_and_leave_the_same_rows(
        monkeypatch, weights, rows, cfg):
    """Three slots prefilled to different lengths and decoded for 12
    steps, one of them joining late, with the kernel and with the
    dispatch forced to the einsums: logits within tests/test_mla_moe.py's
    tolerances (1e-4 in float32; in bfloat16 the median over positions of
    a position's RMS under 0.035), and an idle slot's rows bit for bit."""
    params = cfg.cast_params(weights)

    def run():
        pre = jax.jit(lambda c, slot, t, st: mla_moe.prefill_chunk_into_cache(
            params, cfg, c, slot, t, st))
        step = jax.jit(lambda c, pos, tok, act: mla_moe.decode_step_slots(
            params, c, pos, tok, cfg, active=act))
        cache = mla_moe.init_slot_cache(cfg, S + 1, T + N - 1)
        pos = np.array([T - 70 * s for s in range(S)], np.int32)
        for s in range(S):
            _, cache = pre(cache, s, jnp.asarray(rows[s:s + 1, :pos[s]]), 0)
        active, seen = np.array([True, True, False]), []
        for i in range(N):
            logits, cache = step(cache, jnp.asarray(pos),
                                 jnp.asarray(rows[np.arange(S), pos]),
                                 jnp.asarray(active))
            seen.extend(np.asarray(logits, np.float32)[active])
            pos += active
            active[2] |= i == 4
        return np.stack(seen), np.asarray(cache["lat"].astype(jnp.float32))

    assert cfg.slot_model().step_kv_tiles(S, T + N - 1) is not None
    got, got_rows = run()
    monkeypatch.setattr(latent_attend, "tiles", lambda *a: None)
    assert cfg.slot_model().step_kv_tiles(S, T + N - 1) is None
    want, want_rows = run()
    assert want.std() > 0.2 and np.isfinite(got).all()
    if cfg is WIDE32:
        np.testing.assert_allclose(got, want, atol=1e-4)
        np.testing.assert_allclose(got_rows, want_rows, atol=1e-4)
    else:
        assert float(np.median(np.sqrt(np.mean(
            np.square(got - want), -1)))) < 0.035
    # The pool's slot, which no step names, stays as it was made.
    assert (got_rows[:, S] == 0).all()


def _drain(stream):
    toks = []
    while True:
        event = stream.next_event(60.0)
        assert event is not None, "stream stalled"
        kind, data = event
        if kind != "tokens":
            return toks
        toks.extend(data)


@pytest.mark.parametrize("cfg, tiled", [(WIDE32, True), (NARROW32, False)],
                         ids=["tiles", "rehearsal"])
def test_scheduler_counts_the_latent_tiles_its_steps_copy_and_skip(
        rows, cfg, tiled):
    """Two slots of extent 256 (two 128-lane tiles) decoding from
    position 50: a step copies the first tile of each and skips the
    second; a slot the step does not decode is read as at position 0.
    Fetched and skipped add up to slots x tiles a step, on the scheduler
    and on /metrics; a shape that keeps the XLA path counts nothing."""
    from tpu_dist_nn.obs.registry import Registry
    from tpu_dist_nn.obs.runtime import RuntimeSampler

    reg = Registry()
    sampler = RuntimeSampler(registry=reg)
    sched = ContinuousScheduler(
        mla_moe.init_mla_moe(jax.random.key(3), cfg), cfg, slots=2,
        prompt_len=50, max_new_tokens=150, prefill_chunk=25)
    sampler.add_generation_scheduler(sched)
    try:
        streams = [sched.submit_stream(rows[i:i + 1, :50], max_new_tokens=b)
                   for i, b in enumerate((5, 3))]
        assert [len(_drain(s)) for s in streams] == [5, 3]
        sampler.sample_once()
    finally:
        sched.close()
    visited, skipped = (sched.step_kv_tiles_visited_total,
                        sched.step_kv_tiles_skipped_total)
    assert sched.steps_total >= 4
    scraped = {m.name: {k: c.value for k, c in m.samples()}
               for m in reg.collect()}["tdn_gen_step_kv_tiles_total"]
    assert scraped == {("visited",): visited, ("skipped",): skipped}
    if tiled:
        assert visited + skipped == sched.steps_total * 2 * 2
        assert visited == skipped > 0  # nobody got past position 128
    else:
        assert sched._kv_tiles is None and visited == skipped == 0
