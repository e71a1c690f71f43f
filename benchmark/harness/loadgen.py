"""The one general load generator, run as a child process.

It is a pure client: it imports the wire codec and gRPC, never JAX, so
its threads share no interpreter lock with the server's scheduler and
hold no chip.  Everything it sends is drawn from the seed and the mix's
parameters; what it measures is the client's clock (time.monotonic(),
which Linux keeps system-wide, so the parent reads the same clock).

    python3 benchmark/harness/loadgen.py <spec.json>

spec: seed, prompt_len, vocab_size, max_new_tokens (the
endpoint's), out (result path) and the mix's `arrivals`, `lengths` and
`prefix` as benchmark/README.md documents them.  `GenerateStream`
carries no per-request budget, so a client reads the tokens it wants
and cancels the stream.  It
prints "ready" when every client thread stands, starts on
"go <host:port>", and on "stop" sends nothing new, waits for the first token of whatever it has
in flight, cancels the rest, writes `out` and prints "written".
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np

CHANNELS = 4  # gRPC channels the streams are spread over


def plan_lengths(lengths: dict, seed: int, round_: int, n: int) -> list[int]:
    """Output lengths for `n` requests of round `round_`.

    `uniform` spreads them evenly over [lo, hi] and only their ORDER
    comes from the seed, so every seed offers the same work (lo = hi
    fixes the length).  `lognormal` draws median `median`, shape
    `sigma`, clipped to [lo, hi] (heavy-tailed mixes).
    """
    rng = np.random.default_rng([int(seed), 7, int(round_)])
    kind = lengths["dist"]
    if kind not in ("uniform", "lognormal"):
        raise SystemExit(f"unknown length distribution {kind!r}")
    lo, hi = int(lengths["lo"]), int(lengths["hi"])
    if kind == "uniform":
        grid = np.round(np.linspace(lo, hi, n)).astype(int)
        return [int(x) for x in rng.permutation(grid)]
    draw = rng.lognormal(np.log(lengths["median"]), lengths["sigma"], n)
    return [int(x) for x in np.clip(np.round(draw), lo, hi)]


def make_prompt(seed: int, ident: int, prompt_len: int, vocab: int,
                prefix: dict | None) -> np.ndarray:
    """Prompt `ident` of this seed: random ids over the whole
    vocabulary; with `prefix` {"groups": g, "len": p} its first p ids
    are those of its group, ident % g."""
    rng = np.random.default_rng([int(seed), 11, int(ident)])
    ids = rng.integers(0, vocab, prompt_len, dtype=np.int64)
    if prefix and prefix.get("len"):
        grp = np.random.default_rng(
            [int(seed), 13, int(ident) % int(prefix["groups"])])
        p = int(prefix["len"])
        ids[:p] = grp.integers(0, vocab, p, dtype=np.int64)
    return ids


class Generator:
    def __init__(self, spec: dict):
        import grpc

        from tpu_dist_nn.serving.wire import (
            GENERATE_STREAM_METHOD, decode_frame, encode_matrix)

        self.spec = spec
        self.grpc = grpc
        self.decode_frame, self.encode = decode_frame, encode_matrix
        self.method = GENERATE_STREAM_METHOD
        self.channels, self.calls = [], []
        self.stop = threading.Event()
        self.go = threading.Event()
        self.lock = threading.Lock()
        self.records: list[dict] = []
        self.server_cap = int(spec["max_new_tokens"])

    # one request, start to finish, on the calling thread
    def request(self, ident: int, want: int, due: float | None, who: int):
        s = self.spec
        prompt = make_prompt(s["seed"], ident, s["prompt_len"],
                             s["vocab_size"], s.get("prefix"))
        payload = self.encode(prompt[None, :].astype(np.float64))
        rec = {"id": ident, "want": want, "due": due, "tokens": [],
               "ids": [], "ok": True, "done": None, "error": None,
               "prompt": [int(x) for x in prompt]}
        want = min(want, self.server_cap)
        rec["sent"] = time.monotonic()
        call = self.calls[who % len(self.calls)](payload)
        ours = False
        try:
            for frame in call:
                now = time.monotonic()
                kind, data = self.decode_frame(frame)
                if kind != "tokens":
                    if data.get("reason") == "error":
                        rec["ok"], rec["error"] = False, str(data)
                    else:
                        rec["done"] = now
                    ours = True
                    break
                for tok in data:
                    rec["tokens"].append(now)
                    rec["ids"].append(int(tok))
                if len(rec["ids"]) >= want:
                    rec["done"] = now
                    rec["tokens"] = rec["tokens"][:want]
                    rec["ids"] = rec["ids"][:want]
                    ours = True
                    call.cancel()
                    break
                if self.stop.is_set():
                    ours = True  # window over: first token seen, let go
                    call.cancel()
                    break
            else:
                rec["ok"], rec["error"] = False, "stream ended with no END"
        except self.grpc.RpcError as e:
            if not ours:
                rec["ok"] = False
                rec["error"] = f"{e.code()}: {e.details()}"
        with self.lock:
            self.records.append(rec)

    def connect(self, target: str):
        self.channels = [
            self.grpc.insecure_channel(target)
            for _ in range(CHANNELS)
        ]
        self.calls = [
            ch.unary_stream(self.method, request_serializer=bytes,
                            response_deserializer=bytes)
            for ch in self.channels
        ]

    def closed_client(self, who: int, clients: int):
        self.go.wait()
        k = 0
        while not self.stop.is_set():
            want = plan_lengths(self.spec["lengths"], self.spec["seed"],
                                k, clients)[who]
            self.request(k * clients + who, want, None, who)
            k += 1

    def open_loop(self, arrivals: dict):
        """Requests on a schedule drawn from the seed, whether or not
        earlier ones have finished; each is timed from when it was due."""
        self.go.wait()
        rng = np.random.default_rng([int(self.spec["seed"]), 17])
        rate = float(arrivals["rate_per_s"])
        block = 4096
        wants = plan_lengths(self.spec["lengths"], self.spec["seed"], 0, block)
        t = time.monotonic()
        threads, k = [], 0
        while not self.stop.is_set():
            t += rng.exponential(1.0 / rate)  # Poisson arrivals
            wait = t - time.monotonic()
            if wait > 0 and self.stop.wait(wait):
                break
            th = threading.Thread(
                target=self.request, args=(k, wants[k % block], t, k),
                daemon=True)
            th.start()
            threads.append(th)
            k += 1
        for th in threads:
            th.join(timeout=90)

    def run(self):
        arrivals = self.spec["arrivals"]
        if arrivals["mode"] == "closed":
            n = int(arrivals["clients"])
            threads = [threading.Thread(target=self.closed_client,
                                        args=(i, n), daemon=True)
                       for i in range(n)]
        elif arrivals["mode"] == "open":
            threads = [threading.Thread(target=self.open_loop,
                                        args=(arrivals,), daemon=True)]
        else:
            raise SystemExit(f"unknown arrivals mode {arrivals['mode']!r}")
        for th in threads:
            th.start()
        print("ready", flush=True)
        for line in sys.stdin:
            words = line.split()
            if words and words[0] == "go":
                self.connect(words[1])
                self.go.set()
            elif words and words[0] == "stop":
                break
        self.stop.set()
        self.go.set()
        deadline = time.monotonic() + 90
        for th in threads:
            th.join(timeout=max(0.0, deadline - time.monotonic()))
        stuck = sum(th.is_alive() for th in threads)
        with self.lock:
            out = {"records": self.records, "stuck_clients": stuck}
        with open(self.spec["out"], "w") as f:
            json.dump(out, f)
        for ch in self.channels:
            ch.close()
        print("written", flush=True)


def main():
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    sys.path.insert(0, spec["root"])
    Generator(spec).run()


if __name__ == "__main__":
    main()
