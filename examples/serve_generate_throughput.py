"""Loopback throughput for the LM generation endpoint (VERDICT r4
item 7's artifact): the continuous-batching pipelined decoder behind
the real gRPC wire, measured end to end.

Two servings of the SAME model answer the same request mix:

* single-chip KV-cached decode (`serve_lm_generate(num_stages=1)`)
* pipelined OVERLAPPED round-robin decode (`num_stages=2`), where the
  batcher's coalesced rows pad into the decoder's (G, Bg) group grid

Measured: wall seconds for R concurrent clients x K requests of
(rows, T) prompts each, -> requests/s and generated tokens/s, plus the
coalescing counters (batches < requests proves rows actually fused).

Honest scope (same rule as examples/schedule_walltime.py): the 8
virtual devices share ONE physical core, so the pipelined endpoint's
wall time reflects total compute + collective overhead, not parallel
makespan — single-chip WINS here by construction. The pipelined row's
evidentiary value is end-to-end function + coalescing into group
slots; the decoder-level overlapped-vs-masked comparison is
examples/pp_decode_throughput.py. Parity of every served
token against models.generate is asserted inline.

Prints the record as JSON.
Run: python examples/serve_generate_throughput.py [--fast]
"""

from __future__ import annotations

import argparse
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from tpu_dist_nn.models.generate import generate  # noqa: E402
from tpu_dist_nn.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_transformer,
)
from tpu_dist_nn.serving import GrpcClient, serve_lm_generate  # noqa: E402

T, N = 16, 24


def drive(port: int, clients: int, rpcs: int, rows: int, ref) -> dict:
    pool = [GrpcClient(f"127.0.0.1:{port}", timeout=120.0) for _ in range(clients)]
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, 64, (rows, T)) for _ in range(clients * rpcs)
    ]

    def worker(i):
        c = pool[i % clients]
        outs = []
        for j in range(rpcs):
            outs.append(c.generate(prompts[i * rpcs + j]))
        return outs

    t0 = time.monotonic()
    with ThreadPoolExecutor(max_workers=clients) as ex:
        all_outs = list(ex.map(worker, range(clients)))
    wall = time.monotonic() - t0
    # Parity: every served row equals the single-chip decode of its
    # prompt (greedy endpoint).
    for i, outs in enumerate(all_outs):
        for j, out in enumerate(outs):
            want = ref(prompts[i * rpcs + j])
            np.testing.assert_array_equal(out[:, T:], want)
    n_req = clients * rpcs
    return {
        "clients": clients, "rpcs_per_client": rpcs, "rows_per_rpc": rows,
        "wall_s": round(wall, 3),
        "requests_per_s": round(n_req / wall, 2),
        "generated_tokens_per_s": round(n_req * rows * N / wall, 1),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    clients, rpcs, rows = (4, 2, 2) if args.fast else (8, 4, 2)

    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_layers=4, d_ff=128,
        max_seq_len=T + N,
    )
    params = init_transformer(jax.random.key(11), cfg)

    def ref(prompts):
        return np.asarray(generate(params, cfg, prompts, N, temperature=0.0))

    record = {
        "task": "LM generation endpoint loopback throughput "
                "(VERDICT r4 item 7)",
        "model": "d64/h4/L4 byte-vocab toy", "prompt_len": T,
        "max_new_tokens": N,
        "scope_note": "1 physical core under 8 virtual devices: the "
                      "pipelined row evidences end-to-end function + "
                      "coalescing into group slots, not parallel "
                      "speedup (examples/pp_decode_throughput.py "
                      "compares the decoders)",
        "endpoints": {},
    }

    for name, kw in (
        ("single_chip", dict(num_stages=1)),
        ("pipelined_overlapped", dict(num_stages=2, num_groups=4)),
    ):
        server, port = serve_lm_generate(
            params, cfg, 0, max_new_tokens=N, prompt_len=T,
            host="127.0.0.1", warm_rows=rows * clients, **kw,
        )
        try:
            m = drive(port, clients, rpcs, rows, ref)
            b = server.batcher
            m["requests_total"] = b.requests_total
            m["batches_total"] = b.batches_total
            m["coalesced"] = b.batches_total < b.requests_total
            record["endpoints"][name] = m
        finally:
            server.stop(0)
    print(json.dumps(record, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
