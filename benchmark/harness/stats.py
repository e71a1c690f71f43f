"""Percentiles and the accounting of a window over streamed tokens."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default rule); None
    where there is nothing to take it from."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def spread(values) -> float | None:
    """Interquartile distance over the median, quartiles as Python's
    statistics.quantiles(values, n=4) gives them."""
    import statistics

    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


class StreamWindow:
    """What a window [t_open, t_close) holds of a set of requests.

    A request is {"sent": t, "tokens": [t0, t1, ...], "ok": bool}: the
    client's clock at the send and at each token's arrival.  Requests in
    flight at either edge count for the tokens and gaps that fall inside;
    a first-token time counts if the request was sent inside.  In an
    open loop a request also has "due", when its schedule wanted it
    sent, and its first token is timed from then: a generator that
    runs late hides no wait.
    """

    def __init__(self, requests, t_open: float, t_close: float):
        self.t_open, self.t_close = float(t_open), float(t_close)
        self.seconds = self.t_close - self.t_open
        self.tokens = 0
        self.ttft_s: list[float] = []
        self.gaps_s: list[float] = []
        self.sent = self.failed = self.finished = 0
        for r in requests:
            times = r["tokens"]
            inside = t_open <= r["sent"] < t_close
            if inside:
                self.sent += 1
                if not r.get("ok", True):
                    self.failed += 1
                elif times:
                    self.ttft_s.append(times[0] - (r.get("due") or r["sent"]))
            if r.get("ok", True) and r.get("done") is not None \
                    and t_open <= r["done"] < t_close:
                self.finished += 1
            prev = None
            for t in times:
                if t_open <= t < t_close:
                    self.tokens += 1
                    if prev is not None:
                        self.gaps_s.append(t - prev)
                prev = t

    def tokens_per_s(self) -> float:
        return self.tokens / self.seconds
