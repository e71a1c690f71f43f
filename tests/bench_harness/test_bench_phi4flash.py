"""The `reason-decode` cell of the benchmark, off the chip: whole runs of
its rehearsal at a toy size of the Phi-4-mini-flash (SambaY) family (the
driver `drivers/reason_decode.py`, the comparison with
`configs/phi4_flash_reference.py`, a control, three faults planted in the
timed path), and the arithmetic of `harness/phi4flash_counts.py` against
hand-worked numbers.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, lookup  # noqa: E402
from benchmark.harness.phi4flash_counts import Phi4FlashCounts  # noqa: E402
from tests.bench_harness.test_bench_rehearsal import (  # noqa: E402
    _check_last_line,
    _note,
    _run,
)

CELL = "rehearsal-phi4flash-tiny.reason-decode"


@pytest.fixture(autouse=True)
def keep_other_tests_arrays(monkeypatch):
    # A real run deletes every device array before the reference runs;
    # in a test process they may belong to other tests.
    monkeypatch.setattr(device, "free_device", lambda: None)


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# --------------------------------------------------- the rehearsal's runs

def test_rehearsal_is_correct_and_the_fp8_control_is_not():
    """The new cell's whole run at the toy size of the same family:
    prompts of 50 in chunks of 13 (three of four end without logits)
    over a window of 16, then 8-24 decoded tokens past the rings' wrap.
    CPU runs, 4 seeds (88-112 served tokens each): program 0.0007 to
    0.0048, bf16 control 0.0004 to 0.0023, int8 control 0.020 to 0.034,
    fp8 control 0.26 to 0.34, against the limit 0.01."""
    last, notes = _run(CELL, seed=2**31 + 3, control="fp8")
    _check_last_line(last)
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {
        "rehearsal.out_tokens_per_s", "rehearsal.itl_p95_ms",
        "rehearsal.setup_s"}
    assert _note(notes, "counts") == "Phi4FlashCounts"
    counters = _note(notes, "counters")
    assert counters["prefill_chunks_total"] >= 4
    assert 0 < counters["prefill_body_chunks_total"] \
        < counters["prefill_chunks_total"]
    control = next(n["control"] for n in notes if "control" in n)
    assert control["precision"] == "fp8" and control["correct"] is False


def test_the_driver_refuses_a_driver_that_lost_a_name_it_rebinds(monkeypatch):
    """`drivers/reason_decode.py` is `drivers/serve_model_config.py` (and
    through it `drivers/serve.py`) with three names rebound: each has to
    be there, or the cell would run with another family's counts."""
    driver = lookup.Cell(CELL).driver()
    assert driver.REBOUND == ("SalaCounts", "_counters", "gaps_of")
    real = lookup.load_module

    def without_counts(path, name):
        mod = real(path, name)
        if path.endswith(os.path.join("drivers", "serve_model_config.py")):
            del mod.SalaCounts
        return mod

    monkeypatch.setattr(lookup, "load_module", without_counts)
    with pytest.raises(ImportError, match="SalaCounts"):
        lookup.Cell(CELL).driver()


def test_traced_rehearsal_prints_the_new_metrics():
    last, _ = _run(CELL, seconds=3.0, trace=1)
    _check_last_line(last)
    got = last["metrics"]
    # Chunks of 13, 13, 13, 11: three of four end without logits.
    assert got["rehearsal.prefill_body_chunk_pct"]["value"] == \
        pytest.approx(75.0, abs=3.0)
    # 3 slots; G 4 x d 8; extent 73 -> 128; window 16; E 128, N 16, K 4.
    kv = 2 * 3 * 4 * 8 * 128 * 2
    rings = 2 * 2 * 3 * 4 * 8 * 16 * 2
    state = 3 * 3 * (16 * 128 * 4 + 3 * 128 * 2)
    total = kv + rings + state
    assert got["rehearsal.cache_window_share_pct"]["value"] == \
        pytest.approx(100.0 * rings / total)
    assert got["rehearsal.cache_state_share_pct"]["value"] == \
        pytest.approx(100.0 * state / total)
    # No device plane on a CPU: no device time, no share of a roofline.
    for name in ("prefill_body_dev_ms", "prefill_body_roofline",
                 "decode_step_roofline", "decode_step_dev_ms",
                 "serve_mfu_pct"):
        assert "rehearsal." + name not in got
    for name in ("itl_p50_ms", "backend_start_s", "slot_occupancy_pct",
                 "decode_steps_per_s", "ttft_mean_ms", "loadgen_cpu_pct"):
        assert "rehearsal." + name in got


# ---------------------------- faults planted in the timed path come out

def _window_one_key_too_wide(monkeypatch, sambay):
    """The step attends the ring's lane that still holds position `pos -
    W`: 17 keys where the window has 16."""
    import jax.numpy as jnp

    monkeypatch.setattr(
        sambay, "_ring_visible",
        lambda pos, W: jnp.arange(W)[None, :] < pos[:, None])


def _ring_written_unwrapped(monkeypatch, sambay):
    """The ring's row lands at `pos`, not at `pos mod W` (clipped to the
    ring's last lane by the row write)."""
    monkeypatch.setattr(sambay, "_ring_lane", lambda pos, W: pos)


def _gmu_fed_another_positions_memory(monkeypatch, sambay):
    """The gated memory units get a memory that is not their position's:
    a chunk's `m` shifted by one position (the previous position's, so
    a prompt's last position is gated by the one before it), and in the
    step, which holds no earlier `m`, the `m` of the lane before (with
    `D = 1` the read-out of a stale state alone would hide behind the
    skip `D xc`, which is the position's own)."""
    import jax.numpy as jnp

    chunk, tail = sambay._mamba_chunk_layer, sambay._tail

    def shifted_chunk(x, blk, conv, state, cfg):
        x, conv, state, m = chunk(x, blk, conv, state, cfg)
        return x, conv, state, jnp.concatenate([jnp.zeros_like(m[:1]), m[:-1]])

    def rolled_tail(params, cfg, x, m, K, V, pos):
        return tail(params, cfg, x, jnp.roll(m, 1, axis=0), K, V, pos)

    monkeypatch.setattr(sambay, "_mamba_chunk_layer", shifted_chunk)
    monkeypatch.setattr(sambay, "_tail", rolled_tail)


@pytest.mark.parametrize("plant", [
    _window_one_key_too_wide, _ring_written_unwrapped,
    _gmu_fed_another_positions_memory], ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(monkeypatch, plant):
    """Each fault leaves lengths, ids and requests as they were: it is
    the served tokens that the reference no longer ranks first, by the
    same comparison and the same limit."""
    from tpu_dist_nn.models import sambay

    plant(monkeypatch, sambay)
    last, _ = _run(CELL)
    assert last["correct"] is False
    c = last["compared"]["served_logit_gap_mean"]
    assert c["value"] > c["limit"]
    assert last["failed"] == 0 and last["compared"]["wrong_length"]["value"] == 0


# ----------------------------------------- the SambaY stack's counts

PARAMS = {"prompt_len": 2048, "slots": 96,
          "lengths": {"dist": "uniform", "lo": 512, "hi": 1024}}


@pytest.fixture(scope="module")
def counts():
    return Phi4FlashCounts(_config("phi4-mini-flash"), PARAMS)


def test_counts_parameters_by_hand(counts):
    mlp = 3 * 2560 * 10240                                  # 78.64 M
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    window = 2560 * 5120 + 2560 * 2560
    assert (mlp, mamba, window) == (78_643_200, 41_123_840, 19_660_800)
    body = 9 * (mamba + mlp) + 8 * (window + mlp) + 2560 * 2560
    assert counts.body_params() == body == 1_870_888_960
    tail = 2 * 2560 * 2560 + mlp + 7 * (2 * 2560 * 5120 + mlp) \
        + 7 * (2 * 2560 * 2560 + mlp)
    # What a decode step reads once: 7.70 GB in bfloat16.
    assert counts.matmul_params() == body + tail + 2560 * 200064
    assert 2 * counts.matmul_params() == 7_702_118_400


def test_counts_flops_by_hand(counts):
    scan = 2 * 4 * 5120 + 6 * 5120 * 16
    body = 2 * 1_870_888_960 + 9 * scan
    assert counts.body == body
    # A body chunk of 512 at the prompt's start: 1.92 TFLOP of matrices
    # (9.7 ms at the bf16 peak), the scan, and 8 window layers' keys.
    assert 512 * 2 * 1_870_888_960 == 1_915_790_295_040
    assert counts.body_chunk_flops(0, 512) == 512 * body \
        + 8 * 4 * 2560 * (512 * 513 // 2)
    # Past the window every position attends 512 keys a window layer.
    assert counts.body_chunk_flops(1024, 512) == 512 * body \
        + 8 * 4 * 2560 * 512 * 512
    tail = counts.tail
    assert tail == 2 * (2 * 2560 * 2560 + 15 * 78_643_200
                        + 7 * 2 * 2560 * 5120 + 7 * 2 * 2560 * 2560)
    # One decoded token at 2500: body, 512 keys on 8 rings, the tail,
    # 2501 keys in each of the 8 layers on the shared K/V, the head.
    assert counts.decode_token_flops(2500) == body + 8 * 4 * 2560 * 512 \
        + tail + 8 * 4 * 2560 * 2501 + 2 * 2560 * 200064
    # A prompt: the body over its positions, tail and head once.
    assert counts.prefill_flops(2048) == sum(
        counts.body_chunk_flops(s, 512) for s in range(0, 2048, 512)) \
        + tail + 8 * 4 * 2560 * 2048 + 2 * 2560 * 200064


def test_counts_least_bytes_by_hand(counts):
    # A slot at 2432: the shared K and V (5120 B a position) once for
    # each of 8 layers, 8 rings of 512, 9 states and conv inputs in and out.
    assert counts.mean_pos == 2048 + 384
    slot = 8 * 5120 * 2432 + 8 * 5120 * 512 \
        + 2 * 9 * (4 * 5120 * 16 + 2 * 3 * 5120)
    assert counts.slot_step_bytes(counts.mean_pos) == slot == 127_037_440
    assert counts.decode_step_bytes(96 * counts.mean_pos) == pytest.approx(
        7_702_118_400 + 96 * slot)          # 19.9 GB: 24.2 ms at 819 GB/s
    # A body chunk's least bytes: its matrices once (3.74 GB), nothing
    # else within a hundredth of that.
    assert counts.body_chunk_bytes(512, 512) == 2 * 1_870_888_960 \
        + 2 * 512 * (2560 + 2560) + 2 * 2 * 8 * 2560 * 512 \
        + 2 * 9 * (4 * 5120 * 16 + 2 * 3 * 5120)
