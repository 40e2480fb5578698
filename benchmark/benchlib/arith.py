"""Metric arithmetic: from stamped records to end-to-end numbers. Pure
functions, pinned by the CPU tests. None of them divides a count of whole
steps or whole requests by the length of the window; `serve_tok_s` does
divide whole prompts by it, and `serve_rate`, between events, stands
beside it."""

from __future__ import annotations

import bisect
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


# First tokens closer together than this came from ONE prefill dispatch
# and are one instant. It sits between what the chip runs' records show
# inside a dispatch and the shortest prefill dispatch a doc cell makes
# (PERF.md section 2 has both numbers).
SAME_DISPATCH_S = 0.010


def first_token_instants(records, t0: float, t1: float) -> list:
    """The instants inside [t0, t1) at which prefill dispatches gave their
    first tokens, in order. First tokens less than SAME_DISPATCH_S after
    the one before are the same dispatch's, and the instant is the last of
    them; a dispatch whose first tokens straddle an edge is in or out as a
    whole, by that last one."""
    firsts = sorted(r["token_times"][0] for r in records
                    if r.get("token_times"))
    instants = []
    for i, t in enumerate(firsts):
        last_of_its_dispatch = (i + 1 == len(firsts)
                                or firsts[i + 1] - t >= SAME_DISPATCH_S)
        if last_of_its_dispatch and t0 <= t < t1:
            instants.append(t)
    return instants


def serve_rate(records, t0: float, t1: float) -> dict:
    """The serving rate between events, as train_tok_s is one between step
    ends (beside the metrics, not one of them): the tokens credited in
    (f_1, f_N] over f_N - f_1, f_1 and f_N the first and the last of
    `first_token_instants`. A request's prompt tokens are credited at its
    first token, each generated token at its arrival. The window's edges
    choose which events count and never cut a prompt in two: a window
    holds 12 or 13 prompts of 10 000 tokens, never 12.6.
    Beside `tok_s`: `instants` (N), `tokens`, `span_s`, and
    `largest_credit_share`, the most one instant credits in prompt tokens
    over all tokens counted, which says how coarse this run's count is."""
    instants = first_token_instants(records, t0, t1)
    if len(instants) < 2:
        raise ValueError("fewer than two first-token instants inside the "
                         "window")
    f1, fn = instants[0], instants[-1]
    total, credit = 0, [0] * (len(instants) - 1)
    for r in records:
        times = r.get("token_times") or []
        if times and f1 < times[0] <= fn:
            total += r["prompt_tokens"]
            credit[bisect.bisect_left(instants, times[0]) - 1] += \
                r["prompt_tokens"]
        total += sum(1 for t in times if f1 < t <= fn)
    return {"tok_s": total / (fn - f1), "instants": len(instants),
            "tokens": total, "span_s": fn - f1,
            "largest_credit_share": max(credit) / total}


def serve_tok_s(records, t0: float, t1: float) -> float:
    """Tokens processed inside [t0, t1) over its length: a request's
    prompt tokens are credited at its first token, each generated token at
    its arrival. Not 'tokens of requests that finished', which steps by
    whole documents; but it still steps by whole PROMPTS: one is 1/N of a
    window's tokens, N the first tokens inside it. `serve_rate` is the
    reading that does not, printed beside this one by every run (why it
    is not in this one's place: PERF.md section 6, PR 44)."""
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
