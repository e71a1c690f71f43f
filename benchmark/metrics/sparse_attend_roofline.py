METRIC = {
    "name": "sparse_attend_roofline",
    "unit": "%",
    "layer": "kernels",
    "source": "device_trace",
    "why": "Least time of the sparse layers' attention in a prefill chunk (4 Hq Dh for every key ATTENDED over the bf16 peak, or q in, o out and the visible K and V once over HBM bandwidth if longer; from shapes: harness/sala_counts.py) over the device time of the operations named sparse_attend (kernels/sparse_attend.py) a launch of jit_prefill_chunk. Both sides a launch TRACED: the scheduler's iteration ring gives each traced chunk's start and size (the kernel's time grows tenfold over a prompt's 16 positions, and which of them the 4 s hold differs by run: 5.9 and 7.8 ms a launch read). Silent where no such operation ran or the ring holds no chunk of the traced span.",
    "moves": "itl_p95_ms",
}

KERNEL = "sparse_attend"
# The driver opens the profiler `min(1, seconds / 4)` s into its window
# (drivers/serve.py) and `start_trace` takes this long to return (0.045
# and 0.046 s measured on the chip, PERF.md section 6, PR 28: half an
# iteration); the span then lasts `window_s`.
PROFILER_START_S = 0.05


def traced_chunks(run):
    """``[(start, size)]`` of the chunks whose iteration ended inside
    the traced span, from the ring of the scheduler that recorded most
    there; None where the program keeps no such columns."""
    try:
        from tpu_dist_nn.obs.trace import ITER_FIELDS, ITERATIONS
    except ImportError:
        return None
    if "prefill_starts" not in ITER_FIELDS or run.client is None:
        return None
    col = {k: ITER_FIELDS.index(k) for k in (
        "sched", "seq", "t_end", "prefill_tokens", "prefill_starts")}
    t0 = run.client.t_open + min(1.0, run.args.seconds / 4) + PROFILER_START_S
    t1 = t0 + run.trace["window_s"]
    by_sched: dict = {}
    for r in ITERATIONS.snapshot():
        by_sched.setdefault(r[col["sched"]], []).append(r)
    best = []
    for records in by_sched.values():
        records.sort(key=lambda r: r[col["seq"]])
        chunks = []
        for prev, cur in zip(records, records[1:]):
            size = cur[col["prefill_tokens"]] - prev[col["prefill_tokens"]]
            if size > 0 and t0 <= cur[col["t_end"]] < t1 \
                    and cur[col["seq"]] == prev[col["seq"]] + 1:
                chunks.append(
                    (cur[col["prefill_starts"]] - prev[col["prefill_starts"]],
                     size))
        best = max(best, chunks, key=len)
    return best


def read(run):
    t, c = run.trace, run.counts
    if not t or run.peaks is None or not hasattr(c, "attended"):
        return None
    chunk = t["programs"].get("jit_prefill_chunk")
    ops = [s for name, s in t["ops"]
           if name.startswith("jit_prefill_chunk/") and KERNEL in name]
    if not chunk or not chunk["launches"] or not ops:
        return None
    chunks = traced_chunks(run)
    if not chunks:
        return None
    least = 0.0
    for start, n in chunks:
        keys, _ = c.attended(start, n)
        moved = 2 * c.Ls * (2 * n * c.qd + 2 * c.kvd * (start + n))
        least += max(c.per_key * int(keys.sum()) / run.peaks["bf16_flops"],
                     moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * (least / len(chunks)) / (sum(ops) / chunk["launches"])
