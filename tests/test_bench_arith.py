"""The benchmark's arithmetic that a LONG serving cell's result rests on,
pinned in tier-1 (benchmark/tests is not part of it): `serve_tok_s` and
`serve_rate` (benchmark/benchlib/arith.py) on a window that holds few,
large prompts, and the length and row buckets the check pads a served
sequence to (benchmark/checker.py). Both files are loaded by path; neither
imports JAX at its top level."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].removesuffix(".py"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arith = load("benchlib", "arith.py")


@pytest.fixture(scope="module")
def checker():
    import sys

    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)     # its `from benchlib import spec`
    try:
        return load("checker.py")
    finally:
        sys.path.remove(bench)


def rec(first, gaps, prompt=15000):
    """A request whose first token came at `first`, the next ones `gaps`
    apart."""
    times = [first]
    for g in gaps:
        times.append(times[-1] + g)
    return {"ok": True, "prompt_tokens": prompt, "token_times": times}


# Four 15 000-token prompts, first tokens 1.2 s apart from 10.0 on, each
# with three more tokens 0.1 s apart.
LONG = [rec(10.0 + 1.2 * i, [0.1] * 3) for i in range(4)]


def test_serve_tok_s_credits_a_prompt_at_its_first_token():
    # [10.5, 13.0): the first tokens at 11.2 and 12.4, so two prompts,
    # and every token that arrived inside: 2 x 4 of those two requests.
    assert arith.serve_tok_s(LONG, 10.5, 13.0) == pytest.approx(
        (2 * 15000 + 8) / 2.5)
    # A window's edge moves it by a whole prompt: 1 / N of the count.
    assert arith.serve_tok_s(LONG, 9.9, 13.0) == pytest.approx(
        (3 * 15000 + 12) / 3.1)


def test_serve_rate_is_between_first_token_instants():
    rate = arith.serve_rate(LONG, 9.0, 14.0)
    # (f_1, f_N] = (10.0, 13.6]: three prompts; the first request's three
    # later tokens, the next two's four each, the last one's first.
    assert rate["instants"] == 4 and rate["span_s"] == pytest.approx(3.6)
    assert rate["tokens"] == 3 * 15000 + 3 + 2 * 4 + 1
    assert rate["tok_s"] == pytest.approx(rate["tokens"] / 3.6)
    assert rate["largest_credit_share"] == pytest.approx(
        15000 / rate["tokens"])
    # The window's edges choose events and never cut a prompt in two.
    assert arith.serve_rate(LONG, 9.0, 14.0)["tok_s"] == pytest.approx(
        arith.serve_rate(LONG, 9.9, 13.7)["tok_s"])
    with pytest.raises(ValueError, match="fewer than two"):
        arith.serve_rate(LONG, 10.5, 12.0)


def test_first_tokens_of_one_dispatch_are_one_instant():
    pair = [rec(10.0, []), rec(10.0 + arith.SAME_DISPATCH_S / 2, [])]
    assert arith.first_token_instants(pair + LONG[1:], 9.0, 14.0) == [
        pytest.approx(10.0 + arith.SAME_DISPATCH_S / 2), 11.2,
        pytest.approx(12.4), pytest.approx(13.6)]


@pytest.mark.parametrize("n,want", [
    (200, 256), (2048, 2048), (4097, 6144), (10100, 10240),
    (15900, 16384), (16000, 16384), (16385, 18432)])
def test_length_buckets_of_the_check(checker, n, want):
    assert checker.bucket(n, checker.SEQ_BUCKETS, checker.SEQ_STEP) == want


@pytest.mark.parametrize("n,want", [
    (1, 32), (32, 32), (33, 64), (128, 128), (129, 256), (257, 512)])
def test_row_buckets_of_the_check(checker, n, want):
    assert checker.bucket(n, checker.ROW_BUCKETS, checker.ROW_STEP) == want
