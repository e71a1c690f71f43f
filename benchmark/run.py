"""Run one cell once:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  Exits 3 and prints no result where JAX finds
no TPU or fewer chips than the cell asks for (`--rehearse 1` lets a CPU
through for rehearsals, whose lines carry no device metric's name).
See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    # Not for the benchmark's own runs: also reads the control (the
    # reference in a lower precision: int8, fp8 or both, comma-separated)
    # and, in training, the planted faults.
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    args.t_start = T_START

    from benchmark.harness import lookup
    from benchmark.harness.result import Run

    cell = lookup.Cell(args.workload)
    driver = cell.driver()
    # A driver may start pure-client children before JAX takes the chip.
    early = driver.before_backend(cell, args) \
        if hasattr(driver, "before_backend") else None

    from benchmark.harness import device as dev

    try:
        dev.enable_compile_cache(ROOT)
        t_up = time.monotonic()
        report = dev.require_chips(cell.chips, allow_cpu=bool(args.rehearse))
        run = Run(cell, args, report)
        on_chip = report["platform"] == "tpu"
        run.peaks = dev.peaks(report["kind"]) if on_chip else None
        # `backend_s` is `jax.devices()` alone: the TPU runtime coming up,
        # 7 to 17 s by the machine and none of the repo's code.
        run.setup_split["start_s"] = t_up - T_START
        run.setup_split["backend_s"] = time.monotonic() - t_up
        driver.run(run, early)
    finally:
        # Whatever happened, no child and no scratch file outlives the run.
        if hasattr(driver, "after"):
            driver.after(early)
    run.memory_peak_bytes = run.memory_peak_bytes or 0

    metrics = {}
    for name in cell.metric_names(bool(args.trace)):
        reader = lookup.metric_reader(name)
        value = reader.read(run)
        if value is None:
            continue
        if not on_chip:
            name = "rehearsal." + name  # never a device metric's name
        metrics[name] = {"value": value, "unit": reader.METRIC["unit"]}
    run.emit(metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
