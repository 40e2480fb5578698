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
