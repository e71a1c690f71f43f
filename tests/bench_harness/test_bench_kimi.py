"""The `repo-decode` cell of the benchmark, off the chip: whole runs of
its rehearsal at a toy size of the Kimi-K2 family (the driver
`drivers/repo_decode.py`, the comparison with
`configs/kimi_k2_reference.py`, the three controls, six faults planted
in the timed path), the arithmetic of `harness/mla_moe_counts.py`
against hand-worked numbers, and the four new readers on a fabricated
run.
"""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, lookup  # noqa: E402
from benchmark.harness.mla_moe_counts import MlaMoeCounts  # noqa: E402
from tests.bench_harness.test_bench_rehearsal import (  # noqa: E402
    _check_last_line,
    _note,
    _run,
)

CELL = "rehearsal-kimi-tiny.repo-decode"
LISTED = "kimi-k2.7-code.repo-decode"


@pytest.fixture(autouse=True)
def keep_other_tests_arrays(monkeypatch):
    # A real run deletes every device array before the reference runs;
    # in a test process they may belong to other tests.
    monkeypatch.setattr(device, "free_device", lambda: None)


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# --------------------------------------------------- the rehearsal's runs

def test_rehearsal_is_correct_and_each_control_is_not():
    """The new cell's whole run at the toy size of the same family:
    prompts of 200 in chunks of 136 and 64 (the ragged and the masked
    expert form), then 32-64 decoded tokens through the latent cache; 16
    sampled requests, 700-850 served tokens.  CPU runs, 3 seeds: program
    0.0015 to 0.0059, bf16 control 0.0013 to 0.0049, int8 control 0.0129
    to 0.028, fp8 control 0.16 to 0.20, against the limit 0.01.  (The six
    planted faults below, two seeds each: 0.047 to 1.10.)"""
    last, notes = _run(CELL, seed=2**31 + 3, seconds=3.0,
                       control="bf16,int8,fp8")
    _check_last_line(last)
    assert last["correct"] is True and last["failed"] == 0
    # Held end to end by the gap between tokens alone (PR 26's way out:
    # tokens/s spreads 2.2 to 4.3 % over six seeds on the chip).
    assert set(last["metrics"]) == {"rehearsal.itl_p95_ms",
                                    "rehearsal.setup_s"}
    assert _note(notes, "counts") == "MlaMoeCounts"
    counters = _note(notes, "counters")
    assert counters["prefill_chunks_total"] >= 4
    # 4 of 16 experts a token, 2 expert layers; 6 held here.
    positions = 136 * counters["prefill_chunks_total"] // 2 \
        + 64 * counters["prefill_chunks_total"] // 2
    assert counters["routed_pairs"] > 0
    assert abs(counters["routed_pairs"] / 8
               - (positions + counters["slot_steps_total"])) \
        <= 0.1 * positions  # the counts are fetched every 64 iterations
    assert 0 < counters["expert_touched"] <= counters["expert_visits"]
    assert all(counters[f"expert_pairs.{i}"] > 0 for i in range(6))
    controls = {n["control"]["precision"]: n["control"]
                for n in notes if "control" in n}
    assert controls["bf16"]["correct"] is True  # the program's own level
    assert controls["int8"]["correct"] is False
    assert controls["fp8"]["correct"] is False


def test_the_driver_refuses_a_driver_that_lost_a_name_it_rebinds(monkeypatch):
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


def test_a_tree_without_the_family_stops_before_anything_is_started(
        monkeypatch):
    """The parent of the PR that brought the family: its loader raises,
    `before_backend` lets that through, and no generator child exists."""
    from tpu_dist_nn.models import sala

    def parents_loader(path):
        raise ValueError(f"{path}: model_type 'kimi_k2' has no loader")

    monkeypatch.setattr(sala, "load_model_config", parents_loader)
    cell = lookup.Cell(CELL)
    started = []
    driver = cell.driver()
    monkeypatch.setattr(driver._rd._mc, "before_backend",
                        lambda *a: started.append(a))
    with pytest.raises(ValueError, match="kimi_k2"):
        driver.before_backend(cell, types.SimpleNamespace(seed=1))
    assert not started


def test_traced_rehearsal_prints_the_counter_metrics_and_no_device_metric():
    last, notes = _run(CELL, seconds=3.0, trace=1)
    _check_last_line(last)
    got = last["metrics"]
    counters = _note(notes, "counters")
    assert got["rehearsal.expert_touched_pct"]["value"] == pytest.approx(
        100.0 * counters["expert_touched"] / counters["expert_visits"])
    pairs = [counters[f"expert_pairs.{i}"] for i in range(6)]
    assert got["rehearsal.expert_load_max_over_mean"]["value"] == \
        pytest.approx(max(pairs) * 6 / sum(pairs))
    assert got["rehearsal.expert_load_max_over_mean"]["value"] >= 1.0
    # No device plane on a CPU: no device time, no share of a roofline.
    for name in ("expert_ffn_roofline", "latent_attend_roofline",
                 "decode_step_roofline.itl", "decode_step_dev_ms",
                 "prefill_dev_ms.itl", "serve_mfu_pct.itl"):
        assert "rehearsal." + name not in got
    for name in ("itl_p50_ms", "backend_start_s", "slot_occupancy_pct.itl",
                 "decode_steps_per_s.itl", "ttft_mean_ms.itl",
                 "loadgen_cpu_pct.itl", "out_tokens_per_s.layer"):
        assert "rehearsal." + name in got


# ---------------------------- faults planted in the timed path come out

def _weights_normalised_over_the_held_only(monkeypatch, m):
    """g_e = s_e over the sum of the chosen experts HELD HERE, not of
    all that were chosen."""
    import jax.numpy as jnp

    real = m._held_gates

    def held_only(chosen, w, cfg):
        on, gates = real(chosen, w, cfg)
        total = jnp.sum(gates, -1, keepdims=True)
        return on, jnp.where(total > 0, gates / (total + 1e-20)
                             * cfg.routed_scaling_factor, 0.0)

    monkeypatch.setattr(m, "_held_gates", held_only)


def _weights_taken_from_the_biased_scores(monkeypatch, m):
    """The bias weighs as well as chooses: g from s + b."""
    import jax
    import jax.numpy as jnp

    def biased(u, blk, cfg):
        s = jax.nn.sigmoid(u.astype(jnp.float32)
                           @ blk["w_r"].astype(jnp.float32)) \
            + blk["b_r"].astype(jnp.float32)
        w, chosen = jax.lax.top_k(s, cfg.n_experts_per_tok)
        return chosen, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
            * cfg.routed_scaling_factor

    monkeypatch.setattr(m, "route", biased)


def _shared_expert_dropped(monkeypatch, m):
    import jax.numpy as jnp

    monkeypatch.setattr(m, "_shared_expert",
                        lambda u, blk: jnp.zeros_like(u))


def _key_rotated_one_position_off_in_the_step(monkeypatch, m):
    """The step writes its shared key rotated for `pos + 1`; chunks are
    as they were."""
    import jax.numpy as jnp

    project, step = m._project, m.decode_step_slots
    in_step = []

    def shifted(x, blk, pos, cfg):
        q_n, q_r, new = project(x, blk, pos, cfg)
        if in_step:
            off = project(x, blk, pos + 1, cfg)[2]
            new = jnp.concatenate([new[:, :cfg.kv_lora_rank],
                                   off[:, cfg.kv_lora_rank:]], -1)
        return q_n, q_r, new

    def marked(*a, **kw):
        in_step.append(1)
        try:
            return step(*a, **kw)
        finally:
            in_step.pop()

    monkeypatch.setattr(m, "_project", shifted)
    monkeypatch.setattr(m, "decode_step_slots", marked)


def _scores_scaled_without_yarns_factor(monkeypatch, m):
    """sigma = (d_n + d_r)^-1/2, without m^2 (2.0 at factor 64)."""
    monkeypatch.setattr(
        m.MlaMoeConfig, "softmax_scale",
        property(lambda self: float(self.qk_head_dim) ** -0.5))


def _latent_cached_before_its_norm(monkeypatch, m):
    import jax.numpy as jnp

    project = m._project

    def raw(x, blk, pos, cfg):
        q_n, q_r, new = project(x, blk, pos, cfg)
        h = m._rms(x, blk["ln1_g"], cfg.rms_eps)
        c = (h @ blk["w_kva"])[:, :cfg.kv_lora_rank]
        return q_n, q_r, jnp.concatenate(
            [c.astype(new.dtype), new[:, cfg.kv_lora_rank:]], -1)

    monkeypatch.setattr(m, "_project", raw)


@pytest.mark.parametrize("plant", [
    _weights_normalised_over_the_held_only,
    _weights_taken_from_the_biased_scores, _shared_expert_dropped,
    _key_rotated_one_position_off_in_the_step,
    _scores_scaled_without_yarns_factor, _latent_cached_before_its_norm],
    ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(monkeypatch, plant):
    """Each fault leaves lengths, ids and requests as they were: it is
    the served tokens that the reference no longer ranks first, by the
    same comparison and the same limit."""
    from tpu_dist_nn.models import mla_moe

    plant(monkeypatch, mla_moe)
    last, _ = _run(CELL, seconds=3.0)
    assert last["correct"] is False
    c = last["compared"]["served_logit_gap_mean"]
    assert c["value"] > c["limit"]
    assert last["failed"] == 0 and last["compared"]["wrong_length"]["value"] == 0


# ----------------------------------------------- the stack's counts

PARAMS = {"prompt_len": 8192, "slots": 48, "prefill_chunk": 1024,
          "lengths": {"dist": "uniform", "lo": 512, "hi": 1024}}


@pytest.fixture(scope="module")
def counts():
    return MlaMoeCounts(_config("kimi-k2.7-code"), PARAMS)


def test_counts_parameters_by_hand(counts):
    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 7168
    assert counts.attn_params == attn == 101_122_048
    assert counts.expert_params == 3 * 7168 * 2048 == 44_040_192
    assert counts.router_params == 7168 * 384 == 2_752_512
    # A layer of the chip's share: 676.4 M, 1.353 GB in bfloat16.
    assert counts.layer_params() == attn + 44_040_192 + 2_752_512 \
        + 12 * 44_040_192 == 676_397_056
    assert counts.dense_params == attn + 3 * 7168 * 18432 == 497_483_776
    # What a step reads once: every layer and the head, not the
    # embedding: 8.05 GB in bfloat16.
    assert counts.matmul_params() == 497_483_776 + 5 * 676_397_056 \
        + 7168 * 20480
    assert 2 * counts.matmul_params() == 8_052_539_392
    # 6912 B a cached position: 576 bfloat16 numbers in each of 6 layers.
    assert counts.latent_position_bytes() == 6 * 576 * 2 == 6912
    assert counts.sigma == pytest.approx(0.14468, abs=5e-6)


def test_counts_flops_by_hand(counts):
    token = 497_483_776 + 5 * (101_122_048 + 44_040_192 + 2_752_512)
    assert counts.token_params() == token
    # 8 of 384 chosen, 12 held: a quarter of a pair a token and layer.
    assert counts.held_pairs_per_token == 0.25
    routed = 5 * 0.25 * 6 * 7168 * 2048
    fold = 6 * 2 * 64 * 512 * 256
    key = 6 * 2 * 64 * (576 + 512)
    assert counts.latent_key_flops() == 2 * 64 * 1088
    assert counts.decode_token_flops(9000) == int(
        2 * token + routed + fold + key * 9001 + 2 * 7168 * 20480)
    # A prompt of 8192 in chunks of 1024: the expanded form's keys and
    # the expansion of each attended position once a chunk.
    keys = 8192 * 8193 // 2
    expanded = sum(1024 * j for j in range(1, 9))
    assert counts.prefill_flops(8192) == int(
        8192 * 2 * token + 8192 * routed
        + 6 * (2 * 64 * 320 * keys + 2 * 512 * 64 * 256 * expanded)
        + 2 * 7168 * 20480)
    # 2.65 TFLOP of matrices a chunk of 1024 (ISSUE 33).
    assert 1024 * (2 * token + routed) == pytest.approx(2.65e12, rel=0.01)
    # The run's own pairs take the expectation's place.
    counts.held_pairs_per_token = 0.5
    try:
        assert counts.decode_token_flops(0) - int(
            2 * token + fold + key + 2 * 7168 * 20480) == int(2 * routed)
    finally:
        counts.held_pairs_per_token = 0.25


def test_counts_least_bytes_by_hand(counts):
    # The held experts of five layers once: 5 x 1.06 GB, 6.45 ms at 819 GB/s.
    assert counts.expert_step_bytes() == 2 * 5 * 12 * 44_040_192 \
        == 5_284_823_040
    assert counts.expert_pair_flops() == 6 * 7168 * 2048
    # 48 slots at a mean position of 8192 + 384: 2.85 GB of live rows.
    live = 48 * (8192 + 384)
    assert counts.decode_step_bytes(live) == pytest.approx(
        8_052_539_392 + live * 6912)
    assert live * 6912 == 2_845_310_976
    # 10.9 GB in all: 13.3 ms at 819 GB/s.
    assert counts.decode_step_bytes(live) / 819e9 == pytest.approx(
        0.0133, abs=1e-4)


# ------------------------------------- the new readers, on a made-up run

def _fabricated_run():
    cfg = _config("kimi-k2.7-code")
    counts = MlaMoeCounts(cfg, PARAMS)
    t_open = 1000.0
    # One request that streamed 100 tokens, 10 ms apart, from 1.2 s into
    # the window: tokens 1..99 are decoded at positions 8192 + j - 1.
    record = {"tokens": [t_open + 1.2 + 0.01 * j for j in range(100)]}
    return types.SimpleNamespace(
        counts=counts, params=dict(PARAMS),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        args=types.SimpleNamespace(seconds=51.0),
        client=types.SimpleNamespace(t_open=t_open, t_close=t_open + 51),
        records=[record],
        counters={"steps_total": 1000, "slot_steps_total": 48_000,
                  "routed_pairs": 1_000_000, "expert_touched": 38_000,
                  "expert_visits": 60_000,
                  **{f"expert_pairs.{i}": 2000 + 100 * i for i in range(12)}},
        trace={"window_s": 4.0,
               "programs": {"jit_step": {"launches": 50, "device_s": 1.2}},
               "launches": {"jit_step": 52, "jit_prefill_chunk": 25},
               "scopes": {"jit_step": {"mla_moe.experts": 52 * 0.008,
                                       "mla_moe.attn.latent": 52 * 0.012}}})


def test_the_four_new_readers_on_a_fabricated_run():
    run = _fabricated_run()
    read = lambda name: lookup.metric_reader(name).read(run)  # noqa: E731
    assert read("expert_touched_pct") == pytest.approx(100 * 38 / 60)
    assert read("expert_load_max_over_mean") == pytest.approx(
        3100 * 12 / sum(2000 + 100 * i for i in range(12)))
    # 5.28 GB of held matrices over 819 GB/s = 6.45 ms least (the pairs'
    # FLOPs are far under it), over 8 ms a launch under the scope.
    assert read("expert_ffn_roofline") == pytest.approx(
        100 * (5_284_823_040 / 819e9) / 0.008)
    # The span is [t_open + 1.05, + 4 s): every token but the first was
    # decoded in it, each attending 8192 + j positions, over 50 launches.
    live = sum(8192 + j for j in range(1, 100)) / 50
    assert read("latent_attend_roofline") == pytest.approx(
        100 * (live * 6912 / 819e9) / 0.012)
    assert read("latent_attend_roofline") < 100
    assert read("expert_ffn_roofline") < 100


def test_the_new_readers_are_silent_where_the_program_has_nothing_to_read():
    """The parent's runs: no routing counts, no such scope."""
    run = _fabricated_run()
    run.counters = {"steps_total": 1000, "slot_steps_total": 48_000}
    run.trace = {"window_s": 4.0, "programs": {
        "jit_step": {"launches": 50, "device_s": 1.2}}}
    for name in ("expert_touched_pct", "expert_load_max_over_mean",
                 "expert_ffn_roofline", "latent_attend_roofline"):
        assert lookup.metric_reader(name).read(run) is None
    run.trace = None
    assert lookup.metric_reader("expert_ffn_roofline").read(run) is None
    assert lookup.metric_reader("latent_attend_roofline").read(run) is None


def test_scopes_of_a_capture_without_the_programs_reader_are_empty(tmp_path):
    from benchmark.harness.scopes import scopes_of_trace

    driver = lookup.Cell(CELL).driver()
    assert scopes_of_trace(str(tmp_path), driver.SCOPES) == {}
    assert "mla_moe.experts" in driver.SCOPES
    assert "mla_moe.attn.latent" in driver.SCOPES


def test_benchmark_json_lists_the_cell_and_its_metrics():
    bench = lookup.benchmark_json()
    entry = next(w for w in bench["workloads"] if w["name"] == LISTED)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi-k2.7-code", "repo-decode", 1)
    assert len(entry["why"]) <= 200
    cfg = next(c for c in bench["configs"] if c["name"] == "kimi-k2.7-code")
    assert cfg["reduced"] == _config("kimi-k2.7-code")["reduced"]
    cell = lookup.Cell(LISTED)
    p = cell.params
    assert (p["slots"], p["prompt_len"], p["prefill_chunk"],
            p["max_new_tokens"], p["prefix_cache_blocks"]) == (
        48, 8192, 1024, 1024, 0)
    assert p["lengths"] == {"dist": "uniform", "lo": 512, "hi": 1024}
    assert p["arrivals"]["mode"] == "closed"
    assert cell.metric_names(False) == ["itl_p95_ms", "setup_s"]
    traced = cell.metric_names(True)
    # What ISSUE 33 lists, those that move out_tokens_per_s elsewhere
    # under their names that move itl_p95_ms (three of them new here).
    assert sorted(traced) == sorted([
        "loadgen_cpu_pct.itl", "slot_occupancy_pct.itl",
        "decode_steps_per_s.itl", "ttft_mean_ms.itl", "itl_p50_ms",
        "decode_step_dev_ms", "prefill_dev_ms.itl", "serve_mfu_pct.itl",
        "decode_step_roofline.itl", "serve_device_idle_pct.itl",
        "serve_peak_hbm_gb.itl", "out_tokens_per_s.layer", "backend_start_s",
        "expert_ffn_roofline", "latent_attend_roofline",
        "expert_touched_pct", "expert_load_max_over_mean"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if LISTED in m.get("workloads", ()):
            assert m["moves"] in ("itl_p95_ms", "setup_s") and m["moves"] in e2e
    assert cell.own["limits"] == {"served_logit_gap_mean": 0.04}
