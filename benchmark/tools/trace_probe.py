"""Record a small trace on the device and print what its planes hold.

    python3 benchmark/tools/trace_probe.py <out_dir>

Look at a trace by hand before trusting code that reads one: which
planes are devices, which lines hold programs and operations, how they
are named.  The file it leaves (a few launches of one small program) is
what tests/bench_harness checks the reduction on.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str):
    import jax
    import jax.numpy as jnp

    from benchmark.harness import xplane

    @jax.jit
    def probe(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((512, 512), jnp.bfloat16)
    probe(x).block_until_ready()
    tmp = os.path.join(out_dir, "trace_tmp")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            probe(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(tmp)
    print("xplane:", path, os.path.getsize(path), "bytes")
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:4]:
                print("     ", repr(ev.name)[:150], ev.start_ns,
                      ev.duration_ns)
    print(xplane.reduce_trace(tmp, "bench.window"))
    shutil.copy(path, os.path.join(out_dir, "probe.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
