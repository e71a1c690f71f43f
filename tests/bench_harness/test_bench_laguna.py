"""The `repo-16k` cell of the benchmark, off the chip: whole runs of its
rehearsal at a toy size of the Laguna family (the driver
`drivers/repo_16k.py`, the comparison with `configs/laguna_reference.py`,
the three controls, six faults planted in the timed path), the
arithmetic of `harness/laguna_counts.py` against hand-worked numbers,
and the three new readers on a fabricated run.
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
from benchmark.harness.laguna_counts import LagunaCounts  # noqa: E402
from tests.bench_harness.test_bench_rehearsal import (  # noqa: E402
    _check_last_line,
    _note,
    _run,
)

CELL = "rehearsal-laguna-tiny.repo-16k"
LISTED = "laguna-s-2.1.repo-16k"


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
    expert form) through a window of 8, then 32-64 decoded tokens
    through both caches; 16 sampled requests, ~770 served tokens.  CPU
    runs, 2 seeds: program 0.012 to 0.015, bf16 control 0.010 to 0.017,
    int8 control 0.043 to 0.056, fp8 control 0.27 to 0.28, against the
    limit 0.03.  (The six planted faults below, two seeds each: 0.16 to
    1.9.)"""
    last, notes = _run(CELL, seed=2**31 + 3, seconds=3.0,
                       control="bf16,int8,fp8")
    _check_last_line(last)
    assert last["correct"] is True and last["failed"] == 0
    # Held end to end by the gap between tokens alone (PR 26's way out).
    assert set(last["metrics"]) == {"rehearsal.itl_p95_ms",
                                    "rehearsal.setup_s"}
    assert _note(notes, "counts") == "LagunaCounts"
    counters = _note(notes, "counters")
    assert counters["prefill_chunks_total"] >= 4
    # 4 of 16 experts a token, 4 expert layers; 6 held here.
    positions = 136 * counters["prefill_chunks_total"] // 2 \
        + 64 * counters["prefill_chunks_total"] // 2
    assert counters["routed_pairs"] > 0
    # The counts are fetched every 64 iterations and when the loop runs
    # dry: the window's delta is off by up to that many iterations.
    assert counters["routed_pairs"] / 16 == pytest.approx(
        positions + counters["slot_steps_total"], rel=0.25)
    assert 0 < counters["expert_touched"] <= counters["expert_visits"]
    assert all(counters[f"expert_pairs.{i}"] > 0 for i in range(6))
    controls = {n["control"]["precision"]: n["control"]
                for n in notes if "control" in n}
    assert controls["bf16"]["correct"] is True  # the program's own level
    assert controls["int8"]["correct"] is False
    assert controls["fp8"]["correct"] is False


def test_the_driver_refuses_a_repo_decode_that_lost_a_name_it_rebinds(
        monkeypatch):
    driver = lookup.Cell(CELL).driver()
    assert driver.REBOUND == ("MlaMoeCounts", "SCOPES")
    real = lookup.load_module

    def without_counts(path, name):
        mod = real(path, name)
        if path.endswith(os.path.join("drivers", "repo_decode.py")):
            del mod.MlaMoeCounts
        return mod

    monkeypatch.setattr(lookup, "load_module", without_counts)
    with pytest.raises(ImportError, match="MlaMoeCounts"):
        lookup.Cell(CELL).driver()


def test_a_tree_without_the_family_stops_before_anything_is_started(
        monkeypatch):
    """The parent of the PR that brought the family: its loader raises,
    `before_backend` lets that through, and no generator child exists."""
    from tpu_dist_nn.models import sala

    def parents_loader(path):
        raise ValueError(f"{path}: model_type 'laguna' has no loader")

    monkeypatch.setattr(sala, "load_model_config", parents_loader)
    cell = lookup.Cell(CELL)
    started = []
    driver = cell.driver()
    monkeypatch.setattr(driver._rd._rd._mc, "before_backend",
                        lambda *a: started.append(a))
    with pytest.raises(ValueError, match="laguna"):
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
    # Three rings of 8 beside two layers of K/V over an extent of 384.
    assert got["rehearsal.cache_window_share_pct"]["value"] == \
        pytest.approx(100 * 3 * 8 / (3 * 8 + 2 * 384))
    # No device plane on a CPU: no device time, no share of a roofline.
    for name in ("window_attend_roofline", "full_attend_roofline",
                 "moe_experts_roofline", "decode_step_roofline.itl",
                 "decode_step_dev_ms", "prefill_dev_ms.itl",
                 "serve_mfu_pct.itl"):
        assert "rehearsal." + name not in got
    for name in ("itl_p50_ms", "backend_start_s", "slot_occupancy_pct.itl",
                 "decode_steps_per_s.itl", "ttft_mean_ms.itl",
                 "loadgen_cpu_pct.itl", "out_tokens_per_s.layer"):
        assert "rehearsal." + name in got


# ---------------------------- faults planted in the timed path come out

def _window_one_wider_in_the_step(monkeypatch, m):
    """The step also attends the ring lane that still holds pos - W."""
    import jax.numpy as jnp

    monkeypatch.setattr(m, "_ring_visible", lambda pos, W: (
        jnp.arange(W)[None, :] < pos[:, None]))


def _yarn_on_the_window_layers(monkeypatch, m):
    real = m.LagunaConfig.rope_freqs

    def yarn_everywhere(self, kind):
        if kind == m.FULL:
            return real(self, kind)
        return m.yarn_freqs(self.window_rotary_dim, self.window_rope_theta,
                            self.rope_factor, self.rope_original_len,
                            self.rope_beta_fast, self.rope_beta_slow)

    monkeypatch.setattr(m.LagunaConfig, "rope_freqs", yarn_everywhere)


def _attention_factor_missing(monkeypatch, m):
    """A full layer's cos and sin without YaRN's attention factor."""
    import jax.numpy as jnp

    real = m._rope

    def plain(x, pos, cfg, kind):
        y = real(x, pos, cfg, kind)
        if kind != m.FULL:
            return y
        r = cfg.full_rotary_dim
        rotated = y[..., :r].astype(jnp.float32) / cfg.attention_factor
        return jnp.concatenate([rotated.astype(y.dtype), y[..., r:]], -1)

    monkeypatch.setattr(m, "_rope", plain)


def _per_head_gate_dropped(monkeypatch, m):
    import jax.numpy as jnp

    real = m._attn_out
    monkeypatch.setattr(m, "_attn_out", lambda x, o, gate, blk: real(
        x, o, jnp.ones_like(gate), blk))


def _weights_not_renormalised_over_the_chosen(monkeypatch, m):
    """g_e = p_e * 2.5 over the softmax of all experts."""
    import jax
    import jax.numpy as jnp

    def route(u, blk, cfg):
        p = jax.nn.softmax(u.astype(jnp.float32)
                           @ blk["w_r"].astype(jnp.float32), -1)
        w, chosen = jax.lax.top_k(p, cfg.n_experts_per_tok)
        return chosen, w * cfg.routed_scaling_factor

    monkeypatch.setattr(m, "route", route)


def _ring_keys_rotated_at_read(monkeypatch, m):
    """A window layer's keys kept unrotated and rotated when read, at
    their lane's index: right until the ring wraps, other phases after."""
    real = m._project

    def at_lane(x, blk, pos, cfg, kind):
        u, q, k, v, gate = real(x, blk, pos, cfg, kind)
        if kind == m.WINDOW:
            k = real(x, blk, pos % cfg.sliding_window, cfg, kind)[2]
        return u, q, k, v, gate

    monkeypatch.setattr(m, "_project", at_lane)


@pytest.mark.parametrize("plant", [
    _window_one_wider_in_the_step, _yarn_on_the_window_layers,
    _attention_factor_missing, _per_head_gate_dropped,
    _weights_not_renormalised_over_the_chosen, _ring_keys_rotated_at_read],
    ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(monkeypatch, plant):
    """Each fault leaves lengths, ids and requests as they were: it is
    the served tokens that the reference no longer ranks first, by the
    same comparison and the same limit."""
    from tpu_dist_nn.models import laguna

    plant(monkeypatch, laguna)
    last, _ = _run(CELL, seconds=3.0)
    assert last["correct"] is False
    c = last["compared"]["served_logit_gap_mean"]
    assert c["value"] > 2 * c["limit"]
    assert last["failed"] == 0 and last["compared"]["wrong_length"]["value"] == 0


# ----------------------------------------------- the stack's counts

PARAMS = {"prompt_len": 16384, "slots": 16, "prefill_chunk": 2048,
          "max_new_tokens": 1024,
          "lengths": {"dist": "uniform", "lo": 512, "hi": 1024}}


@pytest.fixture(scope="module")
def counts():
    return LagunaCounts(_config("laguna-s-2.1"), PARAMS)


def test_counts_parameters_by_hand(counts):
    D = 3072
    assert counts.attn_params == [44_187_648, 63_135_744, 63_135_744,
                                  63_135_744, 44_187_648]
    assert counts.expert_params == counts.shared_params == 3 * D * 1024 \
        == 9_437_184
    assert counts.router_params == D * 256 == 786_432
    assert counts.dense_params == 3 * D * 12288 == 113_246_208
    token = 2 * 44_187_648 + 3 * 63_135_744 + 113_246_208 \
        + 4 * (786_432 + 9_437_184)
    assert counts.token_params() == token == 431_923_200
    # What a step reads once: every matrix, the 4 x 128 experts held, the
    # head's half (not the embedding): 10.84 GB in bfloat16.
    assert counts.matmul_params() == token + 4 * 128 * 9_437_184 \
        + D * 50176 == 5_417_902_080
    assert counts.full_key_bytes() == 2 * 2 * 8 * 128 * 2 == 8192
    assert counts.window_key_bytes() == 3 * 2 * 8 * 128 * 2 == 12288
    assert (counts.Lf, counts.Lw, counts.Ld, counts.Lm) == (2, 3, 1, 4)


def test_counts_flops_by_hand(counts):
    token = 431_923_200
    # 10 of 256 chosen, 128 held: 5 pairs a token and layer.
    assert counts.held_pairs_per_token == 5.0
    routed = 4 * 5 * 6 * 3072 * 1024
    key_f, key_w = 2 * 4 * 48 * 128, 3 * 4 * 72 * 128
    assert (counts.full_key_flops(), counts.window_key_flops()) == (
        key_f, key_w)
    head = 2 * 3072 * 50176
    assert counts.decode_token_flops(17000) == \
        2 * token + routed + key_f * 17001 + key_w * 512 + head
    assert counts.decode_token_flops(99) == \
        2 * token + routed + key_f * 100 + key_w * 100 + head
    t = 16384
    window = 511 * 512 // 2 + (t - 511) * 512
    assert counts.prefill_flops(t) == \
        t * 2 * token + t * routed + key_f * (t * (t + 1) // 2) \
        + key_w * window + head


def test_counts_least_bytes_by_hand(counts):
    # The held experts of four layers once: 9.66 GB, 11.8 ms at 819 GB/s.
    assert counts.expert_step_bytes() == 2 * 4 * 128 * 9_437_184 \
        == 9_663_676_416
    assert counts.expert_pair_flops() == 6 * 3072 * 1024
    # 16 slots at a mean position of 16384 + 384: 2.2 GB of live K/V in
    # the full layers, and at least 16 rings' worth in the window layers.
    live = 16 * (16384 + 384)
    rings = 512 * live / 17408
    assert counts.decode_step_bytes(live) == pytest.approx(
        2 * 5_417_902_080 + live * 8192 + rings * 12288)
    assert 15.4 < live / 17408 < 16
    assert counts.decode_step_bytes(live) / 819e9 == pytest.approx(
        0.01613, abs=1e-4)


# ------------------------------------- the new readers, on a made-up run

def _fabricated_run():
    cfg = _config("laguna-s-2.1")
    counts = LagunaCounts(cfg, PARAMS)
    t_open = 1000.0
    # One request that streamed 100 tokens, 10 ms apart, from 1.2 s into
    # the window: tokens 1..99 are decoded at positions 16384 + j - 1.
    record = {"tokens": [t_open + 1.2 + 0.01 * j for j in range(100)]}
    return types.SimpleNamespace(
        counts=counts, params=dict(PARAMS),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        args=types.SimpleNamespace(seconds=51.0),
        client=types.SimpleNamespace(t_open=t_open, t_close=t_open + 51),
        records=[record],
        counters={"steps_total": 1000, "slot_steps_total": 16_000},
        trace={"window_s": 4.0,
               "programs": {"jit_step": {"launches": 50, "device_s": 1.0}},
               "launches": {"jit_step": 52, "jit_prefill_chunk": 12},
               "scopes": {"jit_step": {
                   "laguna.attn.window": 52 * 0.0002,
                   "laguna.attn.full": 52 * 0.004,
                   "laguna.experts": 52 * 0.013}}})


def test_the_three_new_readers_on_a_fabricated_run():
    run = _fabricated_run()
    read = lambda name: lookup.metric_reader(name).read(run)  # noqa: E731
    # The span is [t_open + 1.05, + 4 s): every token but the first was
    # decoded in it, each reading a whole ring of 512 in 3 layers and
    # 16384 + j rows in 2, over 50 launches.
    ring = 99 * 512 / 50
    assert read("window_attend_roofline") == pytest.approx(
        100 * (ring * 12288 / 819e9) / 0.0002)
    live = sum(16384 + j for j in range(1, 100)) / 50
    assert read("full_attend_roofline") == pytest.approx(
        100 * (live * 8192 / 819e9) / 0.004)
    # 9.66 GB of held matrices over 819 GB/s = 11.8 ms least (the pairs'
    # FLOPs are far under it), over 13 ms a launch under the scope.
    assert read("moe_experts_roofline") == pytest.approx(
        100 * (9_663_676_416 / 819e9) / 0.013)
    for name in ("window_attend_roofline", "full_attend_roofline",
                 "moe_experts_roofline"):
        assert 0 < read(name) < 100


def test_the_new_readers_are_silent_where_the_program_has_nothing_to_read():
    """The parent's runs: no such scope, no trace; and another family's
    counts."""
    names = ("window_attend_roofline", "full_attend_roofline",
             "moe_experts_roofline")
    run = _fabricated_run()
    run.trace = {"window_s": 4.0, "programs": {
        "jit_step": {"launches": 50, "device_s": 1.2}}}
    for name in names:
        assert lookup.metric_reader(name).read(run) is None
    run.trace = None
    for name in names:
        assert lookup.metric_reader(name).read(run) is None
    from benchmark.harness.mla_moe_counts import MlaMoeCounts

    run = _fabricated_run()
    run.counts = MlaMoeCounts(_config("kimi-k2.7-code"), PARAMS)
    for name in names:
        assert lookup.metric_reader(name).read(run) is None


def test_scopes_of_a_capture_without_the_programs_reader_are_empty(tmp_path):
    from benchmark.harness.scopes import scopes_of_trace

    driver = lookup.Cell(CELL).driver()
    assert scopes_of_trace(str(tmp_path), driver.SCOPES) == {}
    for scope in ("laguna.attn.window", "laguna.attn.full",
                  "laguna.experts", "kv_write_rows"):
        assert scope in driver.SCOPES
    for reader in ("window_attend_roofline", "full_attend_roofline",
                   "moe_experts_roofline"):
        assert lookup.metric_reader(reader).SCOPE in driver.SCOPES


# ------------------------------------------------- BENCHMARK.json's entries

def test_benchmark_json_lists_the_configuration_cell_and_metrics():
    bench = lookup.benchmark_json()
    cfg = next(c for c in bench["configs"] if c["name"] == "laguna-s-2.1")
    assert cfg["file"] == "benchmark/configs/laguna-s-2.1.json"
    assert cfg["reduced"] == _config("laguna-s-2.1")["reduced"]
    assert cfg["source"] == _config("laguna-s-2.1")["source"]
    entry = next(w for w in bench["workloads"] if w["name"] == LISTED)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "laguna-s-2.1", "repo-16k", 1)
    assert len(entry["why"]) <= 200 and len(cfg["why"]) <= 200
    cell = lookup.Cell(LISTED)
    p = cell.params
    assert (p["slots"], p["prompt_len"], p["prefill_chunk"],
            p["max_new_tokens"], p["prefix_cache_blocks"],
            p["check_requests"], p["trace_seconds"]) == (
        16, 16384, 2048, 1024, 0, 4, 4)
    assert p["lengths"] == {"dist": "uniform", "lo": 512, "hi": 1024}
    assert p["arrivals"] == {"mode": "closed", "clients": "per_slot"}
    assert p["prefix"] is None
    assert cell.driver().KIND == "serve"
    assert cell.metric_names(False) == ["itl_p95_ms", "setup_s"]
    assert sorted(cell.metric_names(True)) == sorted([
        "itl_p50_ms", "decode_step_dev_ms", "prefill_dev_ms.itl",
        "decode_steps_per_s.itl", "serve_mfu_pct.itl",
        "decode_step_roofline.itl", "serve_device_idle_pct.itl",
        "serve_peak_hbm_gb.itl", "slot_occupancy_pct.itl",
        "ttft_mean_ms.itl", "out_tokens_per_s.layer", "loadgen_cpu_pct.itl",
        "expert_touched_pct", "expert_load_max_over_mean",
        "cache_window_share_pct", "backend_start_s",
        "window_attend_roofline", "full_attend_roofline",
        "moe_experts_roofline"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if LISTED in m.get("workloads", ()):
            assert m["moves"] == "itl_p95_ms" and m["moves"] in e2e
    new = [m for m in bench["per_layer"] if m["name"] in (
        "window_attend_roofline", "full_attend_roofline",
        "moe_experts_roofline")]
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        m["name"] for m in new]
    for m in new:
        reader = lookup.metric_reader(m["name"]).METRIC
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            reader["unit"], reader["source"], reader["layer"],
            reader["moves"]) == ("%", "device_trace", "kernels",
                                 "itl_p95_ms")
        assert m["workloads"] == [LISTED]
    assert cell.own["limits"]["served_logit_gap_mean"] > 0
