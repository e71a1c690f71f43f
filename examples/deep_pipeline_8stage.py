"""BASELINE configs[2]: an 8-layer MLP on an 8-stage pipeline, one
layer per device, end to end — train on REAL digits, export to the
reference JSON schema, serve, and measure what the deep placement
costs.

The reference never recorded numbers for its deep-pipeline shape
("Fashion-MNIST 8-layer MLP, 8-stage pipeline (one layer per core)");
this experiment closes that config (tests/test_examples.py runs it).
Workload: the vendored real handwritten digits (64-dim — the
zero-egress real-data anchor, tests/test_real_data.py), an
8-dense-layer MLP sized
64-96-80-64-48-32-24-16-10, distribution [1]*8 so every layer is its
own pipeline stage.

Measurements, all through the public Engine surface:

* held-out accuracy of the 8-layer model trained THROUGH the 8-stage
  pipelined trainer (gradients cross 7 ppermute hops every step);
* p50 step latency + p50 per-stage share (``Engine.step_latency`` —
  the BASELINE metric) for the 8-stage placement vs a 3-stage
  placement of the same model vs single-chip;
* pipeline bubble overhead: measured step-latency ratios next to the
  tick model's prediction ((M + S - 1)/M forward ticks).

Run (8 virtual devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/deep_pipeline_8stage.py
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

SIZES = [64, 96, 80, 64, 48, 32, 24, 16, 10]  # 8 dense layers
DEEP_DIST = [1] * 8
SHALLOW_DIST = [3, 3, 2]


def run(out_json: str | None = None, epochs: int = 30) -> dict:
    import jax

    from tpu_dist_nn.api.engine import Engine
    from tpu_dist_nn.data.datasets import real_digits
    from tpu_dist_nn.models.fcnn import init_fcnn, spec_from_params
    from tpu_dist_nn.train.trainer import TrainConfig

    n_dev = len(jax.devices())
    data, eval_data = real_digits("train"), real_digits("test")
    acts = ["relu"] * 7 + ["softmax"]
    model = spec_from_params(init_fcnn(jax.random.key(0), SIZES), acts)

    # --- train THROUGH the 8-stage pipeline (one layer per stage) ----
    engine = Engine.up(model, DEEP_DIST)
    placement = engine.placement()
    t0 = time.monotonic()
    engine.train(
        data,
        TrainConfig(epochs=epochs, batch_size=64, learning_rate=1e-3),
        eval_data=eval_data,
    )
    train_seconds = time.monotonic() - t0
    res = engine.run_inference(eval_data.x, eval_data.y, batch_size=256)
    metrics = res.metrics

    # --- export (reference JSON schema, metrics embedded) and re-serve
    import tempfile

    path = out_json or (tempfile.mkdtemp() + "/deep8_model.json")
    exported = engine.export(path, metrics=metrics)

    # --- the BASELINE latency metric across placements ---------------
    lat_deep = Engine.up(exported, DEEP_DIST).step_latency(256, 30)
    lat_shallow = Engine.up(exported, SHALLOW_DIST).step_latency(256, 30)
    lat_single = Engine.up(exported, [8]).step_latency(256, 30)

    M = 4  # engine default microbatches
    record = {
        "experiment": "BASELINE configs[2] — 8-layer MLP, 8-stage pipeline (one layer/stage)",
        "devices": n_dev,
        "model_sizes": SIZES,
        "placement": placement,
        "train_seconds": round(train_seconds, 2),
        "epochs": epochs,
        "held_out_accuracy": metrics["accuracy"],
        "metrics": metrics,
        "step_latency": {
            "deep_8stage": lat_deep,
            "shallow_3stage": lat_shallow,
            "single_chip": lat_single,
        },
        "bubble_model": {
            "note": "forward tick count is M + S - 1; overhead vs an "
                    "ideal bubble-free pipeline is (S - 1)/M extra ticks",
            "deep_ticks": M + 8 - 1,
            "shallow_ticks": M + 3 - 1,
            "predicted_deep_vs_shallow": round((M + 7) / (M + 2), 3),
            "measured_deep_vs_shallow_p50": round(
                lat_deep["p50_s"] / lat_shallow["p50_s"], 3
            ),
            "measured_deep_vs_single_p50": round(
                lat_deep["p50_s"] / lat_single["p50_s"], 3
            ),
        },
    }
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="export trained model JSON here")
    ap.add_argument("--record", default=None, help="write the experiment record JSON here")
    ap.add_argument("--epochs", type=int, default=30)
    args = ap.parse_args(argv)
    record = run(args.out, epochs=args.epochs)
    text = json.dumps(record, indent=1, default=float)
    print(text)
    if args.record:
        with open(args.record, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
