"""The shipped examples stay runnable (reference C10 toolchain parity)."""

import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))


def test_centralized_experiments_smoke(tmp_path, capsys):
    import centralized_experiments as ce

    from tpu_dist_nn.data.datasets import synthetic_mnist

    full = synthetic_mnist(600, dim=64)
    data, eval_data = full.split(0.9)
    acc = ce.experiment_linear_softmax(data, eval_data)
    assert 0.0 <= acc <= 1.0
    params, metrics = ce.experiment_serving_mlp(data, eval_data)
    assert set(metrics) >= {"accuracy", "precision", "recall", "f1_score"}
    ce.experiment_per_sample_latency(params, eval_data, n=5)
    out = tmp_path / "model.json"
    obj = ce.experiment_export(params, metrics, out)
    assert obj["inference_metrics"] == metrics
    assert len(obj["layers"]) == 3
    nbytes = ce.experiment_payload_size(data)
    assert nbytes == 64 * 8
    assert "[e]" in capsys.readouterr().out


def test_centralized_experiments_on_real_digits(tmp_path):
    # C10 closure: the experiment suite on the vendored REAL digits —
    # the accuracies are genuine held-out numbers, not synthetic ~1.0s.
    import centralized_experiments as ce

    from tpu_dist_nn.data.datasets import real_digits

    data, eval_data = real_digits("train"), real_digits("test")
    # Short linear run (full budget asserted in the example itself).
    acc = ce.experiment_linear_softmax(data, eval_data, epochs=30)
    assert acc > 0.85
    params, metrics = ce.experiment_serving_mlp(data, eval_data)
    assert metrics["accuracy"] > 0.9  # real generalization, real data
    obj = ce.experiment_export(params, metrics, tmp_path / "m.json")
    assert obj["inference_metrics"]["accuracy"] == metrics["accuracy"]


def test_deep_pipeline_8stage_experiment(tmp_path):
    # BASELINE configs[2] closure: the
    # 8-layer MLP trains THROUGH the one-layer-per-stage 8-device
    # pipeline on real digits, exports, re-serves at three placements,
    # and the deep placement's latency overhead tracks the tick model.
    import deep_pipeline_8stage as dp

    record = dp.run(str(tmp_path / "deep8.json"), epochs=6)
    assert record["placement"]["num_stages"] == 8
    assert record["held_out_accuracy"] > 0.85  # real data, short budget
    lat = record["step_latency"]
    assert lat["deep_8stage"]["num_stages"] == 8
    assert lat["shallow_3stage"]["num_stages"] == 3
    assert lat["single_chip"]["num_stages"] == 1
    for block in lat.values():
        assert block["p50_per_stage_s"] > 0
    # Deeper pipeline, same model: more fill/drain ticks per step.
    # Wall-clock ordering on 8 virtual devices sharing one core is
    # contention-sensitive (observed inverted once under a saturated
    # box while the full suite shared the host with TPU compiles), so
    # one fresh re-measurement is allowed before declaring failure —
    # latency only, from the already-exported model; no retraining.
    if not lat["deep_8stage"]["p50_s"] > lat["shallow_3stage"]["p50_s"]:
        from tpu_dist_nn.api.engine import Engine
        from tpu_dist_nn.core import load_model

        m = load_model(str(tmp_path / "deep8.json"))
        lat = {
            "deep_8stage": Engine.up(m, dp.DEEP_DIST).step_latency(256, 30),
            "shallow_3stage": Engine.up(
                m, dp.SHALLOW_DIST).step_latency(256, 30),
        }
    assert lat["deep_8stage"]["p50_s"] > lat["shallow_3stage"]["p50_s"]


def test_four_d_training_example(tmp_path, capsys, monkeypatch):
    # The 4D composition example: PP x TP x SP
    # trains on real text under all four schedules and their
    # trajectories agree to float tolerance. Short step budget for CI.
    import runpy

    import pytest

    out = tmp_path / "four_d.json"
    monkeypatch.setattr(
        sys, "argv", ["four_d_training.py", "--steps", "2",
                      "--out", str(out)],
    )
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(
            str(Path(__file__).resolve().parents[1] / "examples"
                / "four_d_training.py"),
            run_name="__main__",
        )
    assert exc.value.code == 0
    record = json.loads(out.read_text())
    assert record["final_loss_spread_across_schedules"] < 1e-3
    assert set(record["schedules"]) == {"gpipe", "1f1b", "interleaved", "zb"}


def test_pp_decode_throughput_example(tmp_path, capsys, monkeypatch):
    # Overlapped vs masked pipelined decode: identical outputs
    # (wall-clock on a contended CI box is noisy, so the assertion is
    # outputs + record shape).
    import runpy

    import pytest

    out = tmp_path / "pp_decode.json"
    monkeypatch.setattr(
        sys, "argv", ["pp_decode_throughput.py", "--out", str(out),
                      "--repeat", "1"],
    )
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(
            str(Path(__file__).resolve().parents[1] / "examples"
                / "pp_decode_throughput.py"),
            run_name="__main__",
        )
    assert exc.value.code == 0
    record = json.loads(out.read_text())
    assert record["identical_outputs"] is True
    assert record["overlapped_round_robin"]["tokens_per_s"] > 0
