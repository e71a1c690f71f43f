"""What one run knows, and the line it prints last."""

from __future__ import annotations

import json
import sys


def within(compared: dict) -> bool:
    """The one verdict: every number compared lies within its limit.
    The program's numbers, the control's and each planted fault's all
    pass through here."""
    return bool(compared) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in compared.values())


class Run:
    """Everything the metric readers may read.  A driver fills what its
    kind of traffic has; a reader that finds its field empty returns
    None and the metric is left out of the line."""

    def __init__(self, cell, args, device: dict):
        self.cell, self.args, self.device = cell, args, device
        self.config, self.params = cell.config, cell.params
        self.peaks = None          # table of peaks for this device kind
        self.counts = None         # harness.counts object of the config
        self.window_s = None       # length of the measured window
        self.setup_s = None        # see open_window
        self.setup_split = {}      # where set-up went
        self.client = None         # stats.StreamWindow (serving)
        self.records = []          # the generator's records in the window
        self.counters = {}         # the program's counters, deltas
        self.loadgen_cpu_s = None  # generator's CPU seconds in the window
        self.train = None          # {"steps", "tokens_per_step", "seconds"}
        self.trace = None          # xplane.reduce_planes(...) or None
        self.memory_peak_bytes = None
        self.attempted = self.failed = 0
        self.compared = {}         # name -> {"value": x, "limit": y}
        self.correct = False

    def open_window(self, t_open: float):
        """`setup_s`: process start to window open, all of it."""
        self.setup_s = t_open - self.args.t_start

    def note(self, **kv):
        """An earlier line: everything the last line has no key for."""
        print(json.dumps(kv), flush=True)

    def emit(self, metrics: dict):
        device = dict(self.device)
        device["memory_peak_bytes"] = self.memory_peak_bytes
        line = {"correct": bool(self.correct), "attempted": self.attempted,
                "failed": self.failed, "metrics": metrics, "device": device}
        if self.args.trace and self.trace:
            device["busy_s"] = self.trace["busy_s"]
            device["window_s"] = self.trace["window_s"]
            line["breakdown"] = {
                "device_ops": [list(x) for x in self.trace["ops"][:10]],
                "idle_gaps": [list(x) for x in self.trace["gaps"][:10]],
            }
        line["compared"] = self.compared
        for name, c in self.compared.items():
            print(f"compared {name}: value {c['value']!r} limit "
                  f"{c['limit']!r}", file=sys.stderr)
        print(f"correct: {self.correct}", file=sys.stderr, flush=True)
        print(json.dumps(line), flush=True)
