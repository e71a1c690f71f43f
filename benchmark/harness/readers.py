"""Arithmetic that several metric readers share."""

from __future__ import annotations


def program_ms(run, program: str):
    """Device milliseconds per launch of a jitted program, from the trace."""
    t = run.trace
    if not t:
        return None
    p = t["programs"].get(program)
    if not p or not p["launches"]:
        return None
    return 1e3 * p["device_s"] / p["launches"]


def _window_tokens(run):
    """(request, index of token) for each token streamed in the window."""
    w = run.client
    for r in run.records:
        for j, t in enumerate(r["tokens"]):
            if w.t_open <= t < w.t_close:
                yield r, j


def served_flops(run) -> int:
    """Useful FLOPs of the tokens streamed in the window: a request's
    first token carries its prompt's prefill, each later one a decode
    step at its own position."""
    c, plen = run.counts, int(run.params["prompt_len"])
    total = 0
    for _, j in _window_tokens(run):
        total += c.prefill_flops(plen) if j == 0 \
            else c.decode_token_flops(plen + j - 1)
    return total


def decode_step_least_s(run):
    """The least one decode step could take on this chip: the mean
    step's bytes over HBM bandwidth, or its FLOPs over the peak if that
    is longer.  Live keys come from the streamed tokens' positions."""
    steps = run.counters.get("steps_total")
    if not steps or run.peaks is None or run.client is None:
        return None
    c, plen = run.counts, int(run.params["prompt_len"])
    keys = flops = 0
    for _, j in _window_tokens(run):
        if j > 0:
            keys += plen + j
            flops += c.decode_token_flops(plen + j - 1)
    if not keys:
        return None
    by_bytes = c.decode_step_bytes(keys / steps) / run.peaks["hbm_bytes_per_s"]
    by_flops = flops / steps / run.peaks["bf16_flops"]
    return max(by_bytes, by_flops)
