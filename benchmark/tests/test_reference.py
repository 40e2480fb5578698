"""The plain reference against the program's own forward at a tiny Falcon
shape, for both norm layouts; and the control: int8 products in the
reference's place must come out as not correct."""

import json
import os

import numpy as np
import pytest

from benchlib import spec

FIX = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def ref():
    return spec.load_module(
        os.path.join(spec.BENCH_DIR, "reference", "falcon.py"), "ref_falcon")


def tiny(name):
    with open(os.path.join(FIX, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tiny-falcon", "tiny-falcon-2norm"])
def test_reference_agrees_with_the_programs_forward(ref, name):
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import forward, init_params
    from runbooks_tpu.train.step import layout_invariant_init

    conf = tiny(name)
    cfg = get_config(conf["model"], **dict(
        conf["model_overrides"], dtype="float32", attention_impl="xla"))
    with layout_invariant_init():
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(7))
    w = ref.init_weights(conf["as_run"], 7)
    layers = params["layers"]
    theirs = {"embed": params["embed"], "wq": layers["attn"]["wq"],
              "wk": layers["attn"]["wk"], "wv": layers["attn"]["wv"],
              "wo": layers["attn"]["wo"], "mlp_in": layers["mlp"]["wi"],
              "mlp_out": layers["mlp"]["wo"]}
    for key, val in theirs.items():     # the seeded input is the same
        assert jnp.array_equal(val, w[key]), key
    assert ("ln2" in layers) == (conf["as_run"]["layer_norms_per_block"] == 2)
    toks = np.random.default_rng(0).integers(1, 512, 50)
    with jax.default_matmul_precision("highest"):
        want = forward(cfg, params, jnp.asarray(toks)[None])[0][0]
    for q_block in (512, 16):           # whole, and in blocks of rows
        ref.Q_BLOCK = q_block
        got = ref.logits_at(conf["as_run"], w, toks, np.arange(50))
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        # float32 round-off of sums taken in another order.
        assert rel < 2e-6, (q_block, rel)
    ref.Q_BLOCK = 512


# At this toy size, over 3840 positions a seed: the stated precision reads
# 3.7e-5 .. 7.9e-5 and the control 6.2e-4 .. 7.0e-4 (five seeds, CPU). The
# limit is three times the sound runs' largest, as the cells' limits are.
TOY_LIMIT_GAP_MEAN = 2.4e-4


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_comes_out_not_correct(ref, seed):
    """The control at test size. At each position of the same sequences:
    the token that the program puts first in its stated precision
    (bfloat16) lies below the reference's best by less than the limit on
    average, the token that int8 products put first by more."""
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import forward, init_params
    from runbooks_tpu.train.step import layout_invariant_init

    conf = tiny("tiny-falcon")
    as_run = conf["as_run"]
    w = ref.init_weights(as_run, seed)
    cfg = get_config(conf["model"], **dict(conf["model_overrides"],
                                           attention_impl="xla"))
    with layout_invariant_init():
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))
    toks = np.random.default_rng(seed).integers(1, 512, (40, 96))
    served = np.asarray(jnp.argmax(jax.jit(
        lambda p, t: forward(cfg, p, t)[0])(params, jnp.asarray(toks)), -1))
    rows = np.arange(96)
    sound, control = [], []
    for i in range(len(toks)):
        logits = np.asarray(ref.logits_at(as_run, w, toks[i], rows))
        low = np.asarray(ref.logits_at(as_run, w, toks[i], rows, low=True))
        best = logits.max(-1)
        sound.append(best - logits[rows, served[i]])
        control.append(best - logits[rows, low.argmax(-1)])
    sound, control = np.mean(sound), np.mean(control)
    assert sound <= TOY_LIMIT_GAP_MEAN < control, (sound, control)
    assert control > 3 * sound


class StubReference:
    """Records what serve_gaps asks for; its best token at row p is token
    p + 1 of the sequence (of `truth`, where the served one was altered),
    so a sequence served as the reference would reads a gap of nought."""
    VOCAB = 16

    def __init__(self, truth=None):
        self.calls, self.truth = [], truth

    def logits_at(self, as_run, w, tokens, rows, low=False):
        tokens, rows = np.asarray(tokens), np.asarray(rows)
        self.calls.append({"tokens": tokens, "rows": rows, "low": low})
        logits = np.zeros((len(rows), self.VOCAB), np.float32)
        best = tokens if self.truth is None else np.asarray(self.truth)
        logits[np.arange(len(rows)), best[rows + 1]] = 1.0
        if low:     # the control puts another token first everywhere
            logits = np.roll(logits, 1, axis=-1)
        return logits


def sequence(rng, prompt, served):
    ids = rng.integers(1, StubReference.VOCAB, prompt + served).tolist()
    return {"prompt_ids": ids[:prompt], "served_ids": ids[prompt:]}


@pytest.mark.parametrize("prompt,served,length,n_rows", [
    (10_000, 100, 10_240, 128),   # beyond the buckets: the next 2048
    (1_500, 100, 2_048, 128),     # the doc cells' shape, as before
    (4_000, 96, 4_096, 128),
    (4_000, 97, 6_144, 128),
    (16_300, 300, 18_432, 512),   # rows beyond their buckets: the next 256
    (200, 20, 256, 32),
    (700, 256, 1_024, 256),       # the chat cell's longest reply
])
def test_serve_gaps_pads_lengths_and_rows_to_buckets_of_their_own(
        prompt, served, length, n_rows):
    import checker

    stub = StubReference()
    seq = sequence(np.random.default_rng(prompt), prompt, served)
    got = checker.serve_gaps(stub, {}, None, [seq], control=True)
    assert [c["low"] for c in stub.calls] == [False, True]
    for call in stub.calls:
        assert call["tokens"].shape == (length,)
        assert call["tokens"][:prompt + served].tolist() == (
            seq["prompt_ids"] + seq["served_ids"])
        assert not call["tokens"][prompt + served:].any()
        # The served rows, then the last repeated; rows[0] + 1 is the
        # prompt's length.
        assert call["rows"].tolist() == (
            list(range(prompt - 1, prompt + served - 1))
            + [prompt + served - 2] * (n_rows - served))
    assert got["gaps"].shape == got["agree"].shape == (served,)
    assert not got["gaps"].any() and got["agree"].all()
    assert got["control"].shape == (served,) and (got["control"] == 1).all()


def test_serve_gaps_sees_an_altered_token_at_any_length():
    import checker

    seq = sequence(np.random.default_rng(5), 9_000, 40)
    stub = StubReference(seq["prompt_ids"] + seq["served_ids"] + [0] * 2048)
    seq["served_ids"][17] = seq["served_ids"][17] % 15 + 1   # another token
    got = checker.serve_gaps(stub, {}, None, [seq])
    assert got["gaps"].tolist() == [0.0] * 17 + [1.0] + [0.0] * 22
    assert got["agree"].sum() == 39 and not len(got["control"])


def test_tiny_fixtures_check_reads_the_numbers_it_read_before(ref):
    """The same rows, the same logits: asking the reference for a row
    bucket gives the gaps that asking it for the whole length bucket gave
    (what the checker did before PR 44), to float32 round-off."""
    import checker

    as_run = tiny("tiny-falcon")["as_run"]
    w = ref.init_weights(as_run, 3)
    rng = np.random.default_rng(11)
    seqs = []
    for prompt, served in ((40, 9), (200, 33), (300, 70)):
        ids = rng.integers(1, 512, prompt + served).tolist()
        seqs.append({"prompt_ids": ids[:prompt], "served_ids": ids[prompt:]})
    got = checker.serve_gaps(ref, as_run, w, seqs, control=True)
    before, before_low = [], []
    for seq in seqs:
        toks = seq["prompt_ids"] + seq["served_ids"]
        pad = next(b for b in checker.SEQ_BUCKETS if b >= len(toks))
        padded = np.zeros(pad, np.int32)
        padded[:len(toks)] = toks
        rows = np.arange(len(seq["prompt_ids"]) - 1, len(toks) - 1)
        rows_p = np.full(pad, rows[-1], np.int32)
        rows_p[:len(rows)] = rows
        logits = np.asarray(ref.logits_at(as_run, w, padded,
                                          rows_p))[:len(rows)]
        low = np.asarray(ref.logits_at(as_run, w, padded, rows_p,
                                       low=True))[:len(rows)]
        best = logits.max(-1)
        at = np.arange(len(rows))
        before.append(best - logits[at, seq["served_ids"]])
        before_low.append(best - logits[at, low.argmax(-1)])
    assert got["gaps"].shape == (9 + 33 + 70,) and got["gaps"].max() > 1
    np.testing.assert_allclose(got["gaps"], np.concatenate(before),
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(got["control"], np.concatenate(before_low),
                               rtol=0, atol=2e-5)
