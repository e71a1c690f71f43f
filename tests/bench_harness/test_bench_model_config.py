"""The `--model-config` cell of the benchmark, off the chip: whole runs of
its rehearsal at a toy size of the MiniCPM-SALA family (the driver
`drivers/serve_model_config.py`, the comparison with
`configs/minicpm_sala_reference.py`, a control, the selection broken
underneath), and the arithmetic of `harness/sala_counts.py` against
hand-worked numbers.  A file of its own: the tests that were here are not
this PR's to edit.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, lookup  # noqa: E402
from benchmark.harness.sala_counts import SalaCounts  # noqa: E402
from tests.bench_harness.test_bench_rehearsal import (  # noqa: E402
    _check_last_line,
    _note,
    _run,
)


@pytest.fixture(autouse=True)
def keep_other_tests_arrays(monkeypatch):
    # A real run deletes every device array before the reference runs;
    # in a test process they may belong to other tests.
    monkeypatch.setattr(device, "free_device", lambda: None)


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------- a --model-config model served

SALA_CELL = "rehearsal-sala-tiny.longdoc-qa"


def test_model_config_rehearsal_is_correct_and_the_control_is_not():
    """The new cell's whole run at the toy size of the same family:
    `drivers/serve_model_config.py` (serve.py's run, the model from the
    program's --model-config loader), chunked prefill past dense_len,
    the comparison with `minicpm_sala_reference.py`, fp8 as control.
    CPU runs, 4 seeds: program 0 to 1.0e-4, bf16 control 2e-4 to 6e-4,
    fp8 control 0.010 to 0.018, against the limit 0.002."""
    last, notes = _run(SALA_CELL, seed=2**31 + 3, control="fp8")
    _check_last_line(last)
    assert last["correct"] is True and last["failed"] == 0
    # `out_tokens_per_s` is not end to end in this cell: it spreads by more
    # than half its bound there (PERF.md section 6, PR 26).
    assert set(last["metrics"]) == {"rehearsal.itl_p95_ms",
                                    "rehearsal.setup_s"}
    assert _note(notes, "counts") == "SalaCounts"  # not serve.py's GPT-2
    counters = _note(notes, "counters")
    assert counters["prefill_chunks_total"] >= 5  # 160 in chunks of 32
    # 96 of a prompt's 160 positions and every decoded one lie past 64.
    assert counters["sparse_positions_total"] > counters[
        "dense_positions_total"] > 0
    control = next(n["control"] for n in notes if "control" in n)
    assert control["precision"] == "fp8" and control["correct"] is False


def test_the_driver_refuses_a_serve_py_that_lost_a_name_it_rebinds(
        monkeypatch):
    """`drivers/serve_model_config.py` is `drivers/serve.py` with five
    names rebound: each has to be there, or the cell would run as
    GPT-2's."""
    driver = lookup.Cell(SALA_CELL).driver()
    base = lookup.load_module(
        os.path.join(lookup.BENCH_DIR, "drivers", "serve.py"), "serve_pin")
    assert len(driver.REBOUND) == 5
    assert all(hasattr(base, name) for name in driver.REBOUND)
    real = lookup.load_module

    def without_counts(path, name):
        mod = real(path, name)
        if path.endswith(os.path.join("drivers", "serve.py")):
            del mod.Gpt2Counts
        return mod

    monkeypatch.setattr(lookup, "load_module", without_counts)
    with pytest.raises(ImportError, match="Gpt2Counts"):
        lookup.Cell(SALA_CELL).driver()


def test_model_config_traced_run_prints_the_new_metrics():
    last, _ = _run(SALA_CELL, seconds=3.0, trace=1)
    _check_last_line(last)
    got = last["metrics"]
    assert 50 < got["rehearsal.sparse_path_token_pct"]["value"] < 100
    # 2 lightning layers x 3 slots x 4 heads x 16 x 16 float32 over all.
    state = 2 * 3 * 4 * 16 * 16 * 4
    kv = 2 * 2 * 3 * 2 * 16 * 184 * 2          # extent 183 -> 23 blocks of 8
    small = 2 * 3 * 2 * 16 * (92 + 4) * 2      # compressed keys and ring
    assert got["rehearsal.cache_state_share_pct"]["value"] == pytest.approx(
        100.0 * state / (state + kv + small))
    # No device plane on a CPU: no share of a roofline is printed.
    assert "rehearsal.prefill_chunk_roofline" not in got
    assert "rehearsal.decode_step_roofline" not in got
    assert "rehearsal.itl_p50_ms" in got and "rehearsal.backend_start_s" in got
    # What moves `out_tokens_per_s` is this cell's under a name of its
    # own that moves `itl_p95_ms`: throughput is read per layer here.
    assert "rehearsal.slot_occupancy_pct" not in got
    assert 0 < got["rehearsal.slot_occupancy_pct.itl"]["value"] <= 100
    assert got["rehearsal.out_tokens_per_s.layer"]["value"] > 0
    assert got["rehearsal.ttft_mean_ms.itl"]["value"] > 0
    # A share of a peak needs the chip's peaks: none on a CPU.
    assert "rehearsal.serve_mfu_pct.itl" not in got


def test_model_config_fault_selection_broken_is_not_correct(monkeypatch):
    """The top-k replaced by the FIRST blocks (scores that fall with the
    compressed position put in the real ones' place): every position past
    dense_len attends other keys than the reference's, and the served
    tokens are no longer what the reference ranks first."""
    import jax.numpy as jnp

    from tpu_dist_nn.models import sala

    real = sala.select_blocks

    def first_blocks(s, t, cfg, M, return_scores=False):
        falling = -jnp.arange(s.shape[-1], dtype=s.dtype)
        return real(jnp.broadcast_to(falling, s.shape), t, cfg, M,
                    return_scores)

    monkeypatch.setattr(sala, "select_blocks", first_blocks)
    last, _ = _run(SALA_CELL)
    assert last["correct"] is False
    c = last["compared"]["served_logit_gap_mean"]
    assert c["value"] > c["limit"]
    assert last["failed"] == 0 and last["compared"]["wrong_length"]["value"] == 0


# ------------------------------------- the MiniCPM-SALA block's counts

SALA_PARAMS = {"prompt_len": 32768, "slots": 16,
               "lengths": {"dist": "uniform", "lo": 128, "hi": 256}}


@pytest.fixture(scope="module")
def sala():
    return SalaCounts(_config("minicpm-sala"), SALA_PARAMS)


def test_sala_counts_parameters_by_hand(sala):
    # minicpm4 layer: 4096 (2 x 4096 + 2 x 256) + 4096^2 + 3 x 4096 x 16384.
    sparse = 4096 * 8704 + 4096 * 4096 + 3 * 4096 * 16384
    light = 4096 * 16384 + 4096 * 4096 + 3 * 4096 * 16384
    assert (sparse, light) == (253_755_392, 285_212_672)
    assert sala.layer_params() == 2 * sparse + 6 * light == 2_218_786_816
    # What a decode step reads once: the layers and the untied head,
    # 5.04 GB in bfloat16.
    assert sala.matmul_params() == 2_218_786_816 + 4096 * 73448
    assert 2 * sala.matmul_params() == 5_039_259_648


@pytest.mark.parametrize("pos, keys, comp", [
    (0, 1, 0),
    (8191, 8192, 0),                      # the last dense position
    (8192, 97 * 64 + 1, 511),             # block 0, 64 of 95, blocks 96..128
    (32768, 97 * 64 + 1, 2047),           # 97 of 513 blocks: 1 + 64 + 32
    (33022, 97 * 64 + 63, 2062),
    (32800, 97 * 64 + 33, 2049),          # a 98th block, its first 33 keys
])
def test_sala_counts_attended_keys_by_hand(sala, pos, keys, comp):
    got_keys, got_comp = sala.attended(pos)
    assert (int(got_keys[0]), int(got_comp[0])) == (keys, comp)


def test_sala_counts_flops_by_hand(sala):
    proj = 2 * sala.layer_params() + 6 * 4 * 4096 * 128
    assert sala.proj == proj
    # One decoded token at 32768: matrices, the six recurrences, 6209 keys
    # at 4 x 4096 and 2047 compressed keys at 2 x 4096 on two layers, head.
    assert sala.decode_token_flops(32768) == proj \
        + 2 * (4 * 4096 * 6209 + 2 * 4096 * 2047) + 2 * 4096 * 73448
    # A chunk of 2048: 9.09 TFLOP of matrices (46 ms at the bf16 peak).
    assert 2048 * 2 * sala.layer_params() == 9_088_150_798_336
    first = sala.chunk_flops(0, 2048)
    assert first == 2048 * proj + 2 * 4 * 4096 * (2048 * 2049 // 2)
    assert sala.chunk_flops(30720, 2048, final=True) > first + sala.logit
    assert sala.prefill_flops(32768) == sum(
        sala.chunk_flops(s, 2048) for s in range(0, 32768, 2048)) + sala.logit


def test_sala_counts_least_bytes_by_hand(sala):
    # A slot at 32864: per sparse layer 97 blocks and 33 keys of K and V
    # (6.4 MB) and 2053 compressed keys, per lightning layer the state in
    # and out.
    keys, comp = 97 * 64 + 33, (32865 - 32) // 16 + 1
    slot = 2 * 2 * (2 * 256 * keys + 256 * comp) + 6 * 2 * 4 * 4096 * 128
    assert sala.mean_pos == 32768 + 96
    assert sala.slot_step_bytes(sala.mean_pos) == slot
    assert 2 * 2 * 256 * 97 * 64 == 6_356_992       # "6.4 MB a slot and layer"
    # Twelve decoding slots' live keys, as the reader hands them over.
    assert sala.decode_step_bytes(12 * sala.mean_pos) == pytest.approx(
        2 * sala.matmul_params() + 12 * slot)
    # A chunk's least bytes: layers once, visible K/V and compressed keys.
    assert sala.chunk_bytes(2048, 2048) == 2 * (
        sala.layer_params() + 2 * (2 * 256 * 4096 + 256 * 255)) \
        + 6 * 2 * 4 * 4096 * 128
