"""The one general traffic generator. A mix is a data file of parameters;
this module turns it and a seed into requests, arrivals or documents.

Steadiness rule: the seed never changes the WORK. Every seed gets the same
multiset of (prompt, output) lengths and the same number of arrivals; the
seed only decides which request gets which pair, the token ids, and where
the arrivals fall (a Poisson process conditioned on its count: sorted
uniforms). Lengths are the quantiles of the mix's distributions, so the
multiset is a deterministic function of the count alone."""

from __future__ import annotations

import math
import statistics

import numpy as np

_PAIRING_SEED = 20230923   # fixed: pairs prompt quantiles with output ones


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + 0.5) / n of `dist`, clipped."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        norm = statistics.NormalDist()
        z = np.array([norm.inv_cdf(float(x)) for x in p])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + p * (dist["max"] - dist["min"])
    elif kind == "fixed":
        vals = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def length_pairs(lengths: dict, n: int, block: int = 0) -> np.ndarray:
    """[n, 2] (prompt, output) pairs: the same for every seed. With
    `block`, the list is a run of blocks that each span the whole of both
    distributions, so that any prefix of it is balanced (a closed loop
    consumes a prefix whose length the system decides)."""
    fixed = np.random.default_rng(_PAIRING_SEED)
    if not block:
        prompts = quantile_lengths(lengths["prompt"], n)
        outputs = quantile_lengths(lengths["output"], n)
        return np.stack([prompts, fixed.permutation(outputs)], axis=1)
    blocks = math.ceil(n / block)
    prompts = quantile_lengths(lengths["prompt"], blocks * block)
    outputs = quantile_lengths(lengths["output"], blocks * block)
    # Block b takes quantiles b, b + blocks, b + 2*blocks, ...: each block
    # is itself a spread over the distribution.
    prompts = prompts.reshape(block, blocks).T
    outputs = outputs.reshape(block, blocks).T
    outputs = np.stack([fixed.permutation(row) for row in outputs])
    return np.stack([prompts, outputs], axis=2).reshape(-1, 2)[:n]


def arrivals(count: int, start: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """`count` arrival instants in [start, start + seconds): a Poisson
    process conditioned on its count."""
    return start + np.sort(rng.random(count)) * seconds


def make_requests(pairs: np.ndarray, vocab: int, rng: np.random.Generator,
                  block: int = 0) -> list:
    """Shuffle which request gets which pair (inside blocks and the order
    of blocks, when blocked) and draw the prompt token ids."""
    n = len(pairs)
    if block:
        order = np.concatenate([
            b * block + rng.permutation(min(block, n - b * block))
            for b in rng.permutation(math.ceil(n / block))
            if b * block < n])
    else:
        order = rng.permutation(n)
    out = []
    for i in order:
        plen, olen = int(pairs[i, 0]), int(pairs[i, 1])
        out.append({"prompt_ids": rng.integers(1, vocab, plen).tolist(),
                    "max_tokens": olen})
    return out


def _stratified(mix, count, start, seconds, vocab, rng, measured):
    """`count` requests over [start, start + seconds).

    With `stratum_seconds` in the mix, the span is a run of episodes of
    about that length. Each episode is FROZEN: its arrival instants (a
    Poisson process conditioned on its count) and its block of lengths
    (one that spans both distributions) come from a fixed draw, the same
    for every seed. The seed only decides the ORDER of the episodes and
    the token ids: every seed gets the same set of sizes and arrivals, in
    another order. (The study of PR 23: with arrivals drawn afresh per
    seed, six seeds spread `ttft_p50_ms` by 13 % and `tpot_p90_ms` by 9 %
    at 180 requests a window; one seed's first half read 250 ms where its
    second read 160.) Without `stratum_seconds`: one seeded draw."""
    width = float(mix.get("stratum_seconds") or 0)
    if not width:
        reqs = make_requests(length_pairs(mix["lengths"], count), vocab, rng)
        for req, due in zip(reqs, arrivals(count, start, seconds, rng)):
            req.update(due=float(due), measured=measured)
        return reqs
    k = max(1, int(round(seconds / width)))
    sizes = [count // k + (1 if i < count % k else 0) for i in range(k)]
    block = max(sizes)
    pairs = length_pairs(mix["lengths"], block * k, block)
    frozen = np.random.default_rng([_PAIRING_SEED, k, count])
    episodes = []
    for i, n in enumerate(sizes):
        chosen = pairs[i * block:(i + 1) * block][frozen.permutation(block)[:n]]
        episodes.append((np.sort(frozen.random(n)) * seconds / k, chosen))
    out = []
    for slot, e in enumerate(rng.permutation(k)):
        offsets, chosen = episodes[e]
        for off, (plen, olen) in zip(offsets, chosen):
            out.append({"prompt_ids": rng.integers(1, vocab,
                                                   int(plen)).tolist(),
                        "max_tokens": int(olen), "measured": measured,
                        "due": float(start + slot * seconds / k + off)})
    return out


def open_loop_schedule(mix: dict, seconds: float, vocab: int, seed: int,
                       rate: float = None) -> dict:
    """Ramp + window of an open loop: requests with their due instants
    (seconds from the start of the ramp). Counts are fixed by rate x time."""
    rng = np.random.default_rng([seed, 1])
    rate = float(mix["rate_per_s"] if rate is None else rate)
    ramp_s = float(mix["ramp_seconds"])
    ramp = _stratified(mix, int(round(rate * ramp_s)), 0.0, ramp_s, vocab,
                       rng, False)
    win = _stratified(mix, int(round(rate * seconds)), ramp_s, seconds,
                      vocab, rng, True)
    return {"requests": ramp + win, "window": (ramp_s, ramp_s + seconds),
            "rate_per_s": rate}


def closed_loop_list(mix: dict, vocab: int, seed: int) -> list:
    """The ordered list the clients of a closed loop draw from."""
    rng = np.random.default_rng([seed, 2])
    block = int(mix.get("block", 16))
    pairs = length_pairs(mix["lengths"], int(mix["list_size"]), block)
    return make_requests(pairs, vocab, rng, block)


def train_documents(mix: dict, vocab: int, seed: int) -> list:
    """Token-id documents of a training job: the same multiset of lengths
    for every seed, in a seeded order, with seeded ids."""
    rng = np.random.default_rng([seed, 3])
    docs = mix["documents"]
    lens = quantile_lengths(docs["length"], int(docs["count"]))
    return [rng.integers(1, vocab, int(n)).tolist()
            for n in rng.permutation(lens)]


def pack_rows(token_docs, seq_len: int):
    """Greedy packing of documents into rows of seq_len + 1 tokens, as
    runbooks_tpu/train/data.pack_documents does it (a plain copy, so that
    the reference sees the rows the trainer sees without importing it).
    Yields dicts of tokens, targets, segment_ids, positions, loss_mask."""
    toks, segs, pos, seg = [], [], [], 0
    n = seq_len + 1

    def flush():
        nonlocal toks, segs, pos, seg
        t, s, p = toks[:n], segs[:n], pos[:n]
        pad = n - len(t)
        t, s, p = t + [0] * pad, s + [0] * pad, p + [0] * pad
        row = {
            "tokens": np.asarray(t[:-1], np.int32),
            "targets": np.asarray(t[1:], np.int32),
            "segment_ids": np.asarray(s[:-1], np.int32),
            "positions": np.asarray(p[:-1], np.int32),
            "loss_mask": np.asarray(
                [1.0 if s[i] != 0 and s[i] == s[i + 1] else 0.0
                 for i in range(seq_len)], np.float32)}
        toks, segs, pos = toks[n:], segs[n:], pos[n:]
        if toks:   # continuation of a split document
            seg += 1
            segs = [seg] * len(toks)
        return row

    for doc in token_docs:
        doc = list(doc)
        if not doc:
            continue
        seg += 1
        toks += doc
        segs += [seg] * len(doc)
        pos += list(range(len(doc)))
        while len(toks) >= n:
            yield flush()
    if toks:
        yield flush()
