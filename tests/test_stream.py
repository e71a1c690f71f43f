"""Streaming plane (ISSUE 16: serving/stream.py + GenerateStream):
frame codec, TokenStream channel semantics (sent-cursor dedupe,
overflow-cancel, terminal ordering), streamed-vs-unary greedy bit
parity over the loopback wire (incl. EOS freeze and per-request
budgets), the router-hop quick smoke (first token before retirement),
cancel-storm slot/prefix-ref reclamation, mid-stream replica-kill
replay-resume with exactly-once delivery, and the hedging exemption."""

import time

import grpc
import jax
import numpy as np
import pytest

from tpu_dist_nn.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from tpu_dist_nn.serving.continuous import ContinuousScheduler
from tpu_dist_nn.serving.server import GrpcClient, serve_lm_generate
from tpu_dist_nn.serving.stream import TokenStream
from tpu_dist_nn.serving.wire import (
    decode_frame,
    encode_end_frame,
    encode_token_frame,
)

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    max_seq_len=24,
)
PARAMS = init_transformer(jax.random.key(7), CFG)
T, N = 8, 10


def _prompt(seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, (1, T))


def _drain(stream, timeout=30.0):
    """Consume a TokenStream in-process: (tokens list, end dict)."""
    toks, end = [], None
    while True:
        ev = stream.next_event(timeout)
        assert ev is not None, "stream stalled"
        kind, data = ev
        if kind == "tokens":
            toks.extend(data)
        else:
            end = data
            break
    return toks, end


# ------------------------------------------------------------- codec


def test_frame_codec_roundtrips_and_rejects_garbage():
    kind, ids = decode_frame(encode_token_frame([0, 5, 63, 1 << 20]))
    assert kind == "tokens" and ids == [0, 5, 63, 1 << 20]
    kind, data = decode_frame(encode_end_frame("eos", "OK", "done"))
    assert kind == "end"
    assert data == {"reason": "eos", "code": "OK", "message": "done"}
    # Empty strings survive the roundtrip (the common END payload).
    assert decode_frame(encode_end_frame("max_tokens"))[1] == {
        "reason": "max_tokens", "code": "", "message": ""}
    with pytest.raises(ValueError):
        decode_frame(b"")
    with pytest.raises(ValueError):
        decode_frame(bytes((9, 1, 2)))  # unknown frame type
    with pytest.raises(ValueError):
        decode_frame(encode_token_frame([1, 2, 300])[:-1])  # truncated
    with pytest.raises(ValueError):
        decode_frame(encode_token_frame([1]) + b"\x00")  # trailing


# ------------------------------------------------- TokenStream channel


def test_token_stream_cursor_dedupes_replayed_prefix():
    # publish() receives the FULL known-token list every time (the
    # scheduler hands it occ["tokens"]); the sent cursor must emit
    # each token exactly once even when the prefix is republished
    # (preemption replay, failover resume).
    s = TokenStream()
    assert s.publish([1, 2, 3])
    assert s.publish([1, 2, 3, 4])
    assert s.next_event(1.0) == ("tokens", [1, 2, 3, 4])
    assert s.delivered == 4
    assert s.publish([1, 2, 3, 4]) and s.next_event(0.02) is None
    # seed(): the client already holds 2 tokens (resume), so only the
    # unseen suffix flows.
    s2 = TokenStream()
    s2.seed(2)
    assert s2.publish([7, 8, 9])
    assert s2.next_event(1.0) == ("tokens", [9])


def test_token_stream_terminal_after_pending_and_first_finish_wins():
    s = TokenStream()
    s.publish([1, 2])
    s.finish("eos")
    s.finish("max_tokens", message="late loser")
    # Pending tokens drain BEFORE the terminal, and the first finish
    # wins — the ordering the handler's flush loop relies on.
    assert s.next_event(1.0) == ("tokens", [1, 2])
    assert s.next_event(1.0) == (
        "end", {"reason": "eos", "code": "", "message": ""})


def test_token_stream_overflow_and_cancel_flip_the_channel():
    s = TokenStream(max_buffer=2)
    assert s.publish([1, 2]) is True
    assert s.publish([1, 2, 3, 4, 5]) is False  # consumer wedged
    assert s.cancelled
    s2 = TokenStream()
    s2.cancel()
    assert s2.publish([1]) is False  # scheduler's cue to reap the row
    kind, data = s2.next_event(1.0)
    assert kind == "end" and data["code"] == "CANCELLED"


def test_publish_without_notify_leaves_the_consumer_asleep_until_woken():
    """The scheduler publishes a round stream by stream and wakes the
    handlers together at its end (`ContinuousScheduler._land`): a
    publish with `notify=False` enqueues and wakes nobody, `wake` does;
    a consumer that was not waiting finds the tokens without a wake."""
    import threading

    s = TokenStream()
    got = []
    consumer = threading.Thread(target=lambda: got.append(s.next_event(10.0)))
    consumer.start()
    time.sleep(0.05)  # blocked in next_event
    assert s.publish([1], notify=False) and s.publish([1, 2], notify=False)
    time.sleep(0.05)
    assert not got
    s.wake()
    consumer.join(5)
    assert got == [("tokens", [1, 2])]
    assert s.publish([1, 2, 3], notify=False)
    assert s.next_event(1.0) == ("tokens", [3])


def test_send_time_is_one_call_handing_out_tokens_to_the_next_one_entered():
    """A consumer is away with a frame from `next_event` returning it a
    batch until it calls `next_event` again, whatever that call then
    finds; the sums reach the sink at the stream's next `publish`, as
    the stream-out lag's do."""
    class Sink:
        stream_lag_ns = stream_frames = stream_send_ns = stream_sends = 0

    sink = Sink()
    s = TokenStream(lag_sink=sink)
    assert s.publish([1])
    assert s.next_event(1.0) == ("tokens", [1])
    time.sleep(0.03)  # away with the frame
    assert s.next_event(0.01) is None  # back: that send is over
    time.sleep(0.03)  # waiting for a token is no send
    assert s.next_event(0.01) is None
    assert (sink.stream_sends, sink.stream_send_ns) == (0, 0)
    assert s.publish([1, 2])  # the loop's next publish moves the sums
    assert sink.stream_sends == 1 == sink.stream_frames
    assert 0.03e9 <= sink.stream_send_ns < 0.06e9
    assert s.next_event(1.0) == ("tokens", [2])
    time.sleep(0.02)
    s.finish("max_tokens")
    assert s.next_event(1.0)[0] == "end"  # the last send ends here,
    assert s.publish([1, 2, 3])           # after the stream's last publish:
    assert sink.stream_sends == 1         # never folded in


# ------------------------------------------------------ wire parity


def test_streamed_greedy_bit_identical_to_unary_loopback():
    # Acceptance core: at temperature 0 the streamed tokens are the
    # unary Generate tail, bit for bit, through the real wire —
    # including EOS freeze (early retire on eos_id).
    prompt = _prompt(1)
    srv, port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=N, prompt_len=T,
        host="127.0.0.1",
    )
    try:
        c = GrpcClient(f"127.0.0.1:{port}")
        want = c.generate(prompt)[0, T:]
        reply = c.generate_stream(prompt)
        got = np.asarray(list(reply))
        np.testing.assert_array_equal(got, want)
        assert reply.finish["reason"] == "max_tokens"
        # Satellite: the server trace id rides the INITIAL metadata —
        # available while the stream is still flowing.
        assert reply.trace_id
        c.close()
    finally:
        srv.stop(0)
    # EOS freeze: pick an eos the reference actually emits mid-stream,
    # re-serve with it, and the stream must retire early at exactly
    # the unary truncation point.
    eos = int(want[N // 2])
    srv, port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=N, prompt_len=T,
        host="127.0.0.1", eos_id=eos,
    )
    try:
        c = GrpcClient(f"127.0.0.1:{port}")
        tail = c.generate(prompt)[0, T:]
        stop = int(np.argmax(tail == eos))
        reply = c.generate_stream(prompt)
        got = np.asarray(list(reply))
        np.testing.assert_array_equal(got, tail[:stop + 1])
        assert reply.finish["reason"] == "eos"
        c.close()
    finally:
        srv.stop(0)


def test_stream_per_request_budget_matches_unary():
    # Per-request max_new_tokens caps the stream exactly like the
    # unary path: same tokens, "max_tokens" terminal at the cap.
    sched = ContinuousScheduler(
        PARAMS, CFG, slots=2, prompt_len=T, max_new_tokens=N,
    )
    try:
        prompt = _prompt(2)
        want = sched.submit(prompt, max_new_tokens=4)[0, T:T + 4]
        stream = sched.submit_stream(prompt, max_new_tokens=4)
        toks, end = _drain(stream)
        np.testing.assert_array_equal(np.asarray(toks), want)
        assert end["reason"] == "max_tokens" and len(toks) == 4
    finally:
        sched.close()


# ----------------------------------------------------- router smokes


def _lm_replicas(n):
    servers, targets = [], []
    for _ in range(n):
        srv, port = serve_lm_generate(
            PARAMS, CFG, 0, max_new_tokens=N, prompt_len=T,
            host="127.0.0.1",
        )
        servers.append(srv)
        targets.append(f"127.0.0.1:{port}")
    return servers, targets


def _teardown(rsrv, servers, pool, targets):
    from tpu_dist_nn.serving.resilience import CircuitBreaker

    rsrv.stop(0)
    for s in servers:
        s.stop(0)
    pool.close()
    for t in targets:
        CircuitBreaker.evict(t)


def test_stream_first_token_before_retirement_through_router():
    # The quick-tier smoke: a stream through the ROUTER hop delivers
    # its first token while the row is still decoding (streaming's
    # reason to exist — run-to-completion could only return at
    # retirement), and the full stream bit-matches unary Generate
    # through the same hop.
    from tpu_dist_nn.serving.pool import ReplicaPool
    from tpu_dist_nn.serving.router import serve_router

    servers, targets = _lm_replicas(1)
    pool = ReplicaPool(targets, scrape_interval=30.0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    try:
        c = GrpcClient(f"127.0.0.1:{rport}")
        prompt = _prompt(3)
        want = c.generate(prompt)[0, T:]
        reply = c.generate_stream(prompt)
        it = iter(reply)
        first = next(it)
        # The first token crossed two hops while the request still
        # owns its decode slot: delivery is mid-generation, not
        # post-retirement.
        assert servers[0].scheduler.slots_active >= 1
        got = np.asarray([first] + list(it))
        np.testing.assert_array_equal(got, want)
        assert reply.finish["reason"] == "max_tokens"
        assert reply.trace_id
        c.close()
    finally:
        _teardown(rsrv, servers, pool, targets)


def test_cancel_storm_releases_slots_and_prefix_refs():
    # Satellite: a client abandoning mid-stream must free the decode
    # slot and drop prefix-cache refs at the next scheduler iteration
    # — a storm of cancels leaves slots_active (the
    # tdn_gen_slots_active source) at 0 with every block refcount 0.
    srv, port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=16, prompt_len=T,
        host="127.0.0.1", gen_slots=2, prefix_cache_blocks=4,
    )
    sched = srv.scheduler
    try:
        c = GrpcClient(f"127.0.0.1:{port}")
        for i in range(4):
            reply = c.generate_stream(_prompt(10 + i))
            it = iter(reply)
            next(it)  # first token: the row is live in a slot
            reply.cancel()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if (sched.slots_active == 0
                    and not any(sched._pool._refs)):
                break
            time.sleep(0.05)
        assert sched.slots_active == 0
        assert not any(sched._pool._refs), "leaked prefix-cache refs"
        c.close()
    finally:
        srv.stop(0)


def test_mid_stream_replica_kill_resumes_exactly_once():
    # The failover acceptance: kill the serving replica mid-stream
    # (injected UNAVAILABLE under the decode loop) and the router
    # re-places with the delivered prefix as forced-token replay —
    # the client sees every token exactly once, bit-identical to an
    # unkilled run, across the replica switch.
    from tpu_dist_nn.serving.pool import ReplicaPool
    from tpu_dist_nn.serving.router import (
        ROUTER_STREAM_RESUMES,
        serve_router,
    )
    from tpu_dist_nn.testing import faults

    servers, targets = _lm_replicas(2)
    pool = ReplicaPool(targets, scrape_interval=30.0)
    rsrv, rport = serve_router(pool, 0, host="127.0.0.1")
    try:
        prompt = _prompt(4)
        # Reference from the healthy replica directly: both replicas
        # hold the same params, so temp-0 output is fleet-invariant.
        ref_c = GrpcClient(targets[1])
        want = ref_c.generate(prompt)[0, T:]
        ref_c.close()

        resumed_before = sum(
            c.value for _, c in ROUTER_STREAM_RESUMES.samples())
        # Pin the session to replica 0, then blow it up mid-decode.
        pool.pin("doomed", targets[0])
        plan = faults.FaultPlan(at={4: faults.unavailable()})
        servers[0].scheduler.launch_hook = plan.fire

        c = GrpcClient(f"127.0.0.1:{rport}", session_key="doomed")
        reply = c.generate_stream(prompt)
        got = np.asarray(list(reply))
        np.testing.assert_array_equal(got, want)
        assert reply.finish["reason"] == "max_tokens"
        resumed_after = sum(
            c.value for _, c in ROUTER_STREAM_RESUMES.samples())
        assert resumed_after >= resumed_before + 1
        c.close()
    finally:
        _teardown(rsrv, servers, pool, targets)


# -------------------------------------------------- hedging exemption


def test_hedge_policy_rejects_generate_stream():
    from tpu_dist_nn.serving.router import HedgePolicy

    with pytest.raises(ValueError, match="replay-resume"):
        HedgePolicy(methods=("Process", "GenerateStream"))
    HedgePolicy(methods=("Process", "Generate"))  # still fine


def test_static_endpoint_leaves_stream_unimplemented():
    # The static run-to-completion path has no step-granular tokens to
    # stream: GenerateStream stays unregistered and the client gets
    # the honest UNIMPLEMENTED, not a buffered imitation.
    srv, port = serve_lm_generate(
        PARAMS, CFG, 0, max_new_tokens=N, prompt_len=T,
        host="127.0.0.1", scheduler="static",
    )
    try:
        c = GrpcClient(f"127.0.0.1:{port}")
        with pytest.raises(grpc.RpcError) as ei:
            list(c.generate_stream(_prompt(5)))
        assert ei.value.code() == grpc.StatusCode.UNIMPLEMENTED
        c.close()
    finally:
        srv.stop(0)
