"""Metric arithmetic: from stamped records to end-to-end numbers. Pure
functions, pinned by the CPU tests. None of them divides a count of whole
steps or whole requests by the length of the window."""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == xs[lo] or k == lo:     # also keeps inf - inf out
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def ttft_ms(records, q: float) -> float:
    """Percentile q of time to first token over the measured requests,
    from the instant each was DUE (not sent, not admitted). A request that
    failed or streamed nothing counts as missing: infinitely late."""
    vals = [(r["token_times"][0] - r["due_t"]) * 1e3
            if r.get("ok") and r.get("token_times") else float("inf")
            for r in records]
    return percentile(vals, q)


def tpot_ms(records, q: float) -> float:
    """Percentile q over requests of (last token - first token) /
    (output tokens - 1). Per request, not per gap: tokens leave the server
    in chunks of decode_chunk, so single gaps measure the chunking."""
    vals = []
    for r in records:
        t = r.get("token_times") or []
        if not r.get("ok"):
            vals.append(float("inf"))
        elif len(t) > 1:
            vals.append((t[-1] - t[0]) / (len(t) - 1) * 1e3)
    return percentile(vals, q)


def serve_tok_s(records, t0: float, t1: float) -> float:
    """Tokens processed inside [t0, t1) over its length: a request's
    prompt tokens are credited at its first token, each generated token at
    its arrival. Not 'tokens of requests that finished', which steps by
    whole documents."""
    total = 0
    for r in records:
        times = r.get("token_times") or []
        if times and t0 <= times[0] < t1:
            total += r["prompt_tokens"]
        total += sum(1 for t in times if t0 <= t < t1)
    return total / (t1 - t0)


def prefilled_in(records, window):
    """Requests whose first token (so whose prefill) fell inside `window`,
    by the client's clock: for the readers of a traced serving run, whose
    capture was asked for at window[0]. Good to about one prefill."""
    if not window:
        return []
    return [r for r in records or []
            if r.get("token_times")
            and window[0] <= r["token_times"][0] < window[1]]


def whole_steps(step_ends, t0: float, t1: float):
    """(count, span_seconds) of the steps that both started and ended
    inside [t0, t1]. `step_ends` are the instants at which consecutive
    steps ended; step i starts when step i-1 ends."""
    inside = [t for t in step_ends if t0 <= t <= t1]
    if len(inside) < 2:
        return 0, 0.0
    return len(inside) - 1, inside[-1] - inside[0]


def train_tok_s(step_ends, tokens_per_step: int, t0: float,
                t1: float) -> float:
    """Tokens of the whole steps inside the window over the time from the
    first of those steps' start to the last one's end. Never steps /
    --seconds: a window holds 12 or 13 steps of 1.56 s, never 12.85."""
    n, span = whole_steps(step_ends, t0, t1)
    if not n:
        raise ValueError("no whole step inside the window")
    return n * tokens_per_step / span


def spread(values) -> float:
    """The contract's spread: interquartile distance over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
