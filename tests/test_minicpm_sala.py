"""Full-attention layers that read SPARSELY (a window, the initial block and
the blocks a query chooses by scores against compressed keys) beside
lightning linear-attention layers, 1 : 3, under MiniCPM's scalings, against
the plain reference (benchmark/reference/minicpm_sala.py;
docs/hybrid-models.md).

The toy preset `debug-minicpm-sala` keeps the published SHAPE (2 periods of
1 sparse-read full + 3 lightning layers, 4 query heads on 2 KV heads with a
QK norm a head and no rotary, lightning heads with a rotary of their own,
an elementwise gate on both, embeddings x 12, residuals x 1.4 / sqrt(8),
the head's input / 4) with blocks of 8 keys, 4 chosen with the initial one,
a window of 16, compressed keys over 4 keys 2 apart, dense below 64: at 100
tokens a query has 10 candidates for 3 places, so the choice really drops
blocks. Seeded random weights on the CPU; LOGITS are compared, never
sampled tokens. Activations run in float32 under "highest" matmul
precision, weights are the bfloat16 the recipe stores, so what separates
program and reference is the order of float32 sums (chunks against a token
scan, a running softmax against one softmax): every tolerance below is
2e-4 absolute on logits of order 0.2 for that reason (sound runs read
6e-5), unless it says otherwise. A term left out moves them by 1e-2 and
more, and `test_a_program_with_a_term_left_out_fails` holds that.
"""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import (
    KVCache,
    cache_leaves,
    forward,
    init_params,
    param_logical_axes,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
FULL, LINEAR = "full_attention", "linear_attention"


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "minicpm_sala.py")
    spec = importlib.util.spec_from_file_location("ref_minicpm_sala", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def toy(**over):
    kw = dict(dtype="float32", param_dtype="bfloat16")
    kw.update(over)
    return get_config("debug-minicpm-sala", **kw)


def as_run_of(cfg, **readings) -> dict:
    """The reference's description of a ModelConfig of this family, under
    the published keys (and the configuration file's for what the row
    lacks)."""
    sp = cfg.sparse_read
    period = ["minicpm4" if kind == FULL else "lightning-attn"
              for kind in cfg.layer_pattern]
    published = cfg.lightning_decay_layers + 1
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "lightning_nh": cfg.linear_num_heads,
        "lightning_nkv": cfg.linear_num_heads,
        "lightning_head_dim": cfg.linear_key_head_dim,
        "rope_theta": cfg.linear_rope_theta,
        "scale_emb": cfg.embed_multiplier,
        "scale_depth": cfg.residual_scale * published ** 0.5,
        "published_num_hidden_layers": published,
        "dim_model_base": cfg.hidden_size / cfg.logit_divisor,
        "num_hidden_layers": cfg.num_layers, "layer_period": period,
        "mixer_types": period * cfg.num_periods,
        "sparse_config": {
            "block_size": sp.block, "topk": sp.topk,
            "window_size": sp.window, "init_blocks": sp.init,
            "kernel_size": sp.kernel, "kernel_stride": sp.stride,
            "dense_len": sp.dense_len},
        "readings": readings}


@functools.lru_cache(maxsize=None)
def seeded(cfg, seed: int):
    """The program's own seeded weights (jitted, as the server draws them:
    the reference's draw is a jitted one too, and a handful of elements
    round the other way in an eager one)."""
    return jax.jit(functools.partial(init_params, cfg))(jax.random.key(seed))


def tokens_for(cfg, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def reference_logits(cfg, seed: int, tokens, prompt_len=None,
                     **readings) -> np.ndarray:
    """[s, vocab] logits of one sequence whose first prompt_len tokens
    (all of them when None) were prefilled whole and the rest decoded.
    Kept by its arguments: several tests ask for the same one."""
    return _reference_logits(
        cfg, seed, tuple(int(t) for t in tokens),
        len(tokens) if prompt_len is None else prompt_len,
        tuple(sorted(readings.items())))


_WEIGHTS: dict = {}


def reference_weights(as_run: dict, seed: int) -> dict:
    """ref.init_weights, kept by what it depends on (the leaves' shapes
    and the seed: two readings may share every shape). It jits a draw a
    leaf, most of a toy test's time."""
    key = (repr(ref.weight_recipe(ref.dims(as_run))), seed)
    if key not in _WEIGHTS:
        _WEIGHTS[key] = ref.init_weights(as_run, seed)
    return _WEIGHTS[key]


@functools.lru_cache(maxsize=None)
def _reference_logits(cfg, seed, tokens, prompt_len, readings):
    as_run = as_run_of(cfg, **dict(readings))
    dm = ref.dims(as_run)
    w = reference_weights(as_run, seed)

    @jax.jit
    def logits(w, tokens):
        x = ref.hidden_states(dm, w, tokens, prompt_len, ref.matmul)
        return ref.matmul(x / dm["logit_div"], w["head"].astype(jnp.float32))

    return np.asarray(logits(w, jnp.asarray(tokens, jnp.int32)))


def program_logits(cfg, seed: int, tokens) -> np.ndarray:
    run = jax.jit(lambda p, t: forward(cfg, p, t)[0])
    return np.asarray(run(seeded(cfg, seed), jnp.asarray(tokens)[None])[0])


# --------------------------------------------------------------------------
# The model against its reference
# --------------------------------------------------------------------------

def test_seeded_weights_are_the_references():
    cfg = toy()
    p, w = seeded(cfg, 3), reference_weights(as_run_of(cfg), 3)
    full, lin = p["layers"], p["linear_layers"]
    for ours, theirs in (
            (p["embed"], w["embed"]), (p["head"], w["head"]),
            (full["attn"]["wg"], w["wg"]), (full["mlp"]["wo"], w["mlp_down"]),
            # Lightning layer l is period l // 3, position l % 3.
            (lin[1]["mixer"]["wo"][1], w["lin_wo"][4]),
            (lin[2]["mlp"]["wi_up"][0], w["lin_mlp_up"][2])):
        np.testing.assert_array_equal(np.asarray(ours, np.float32),
                                      np.asarray(theirs, np.float32))
    assert sum(x.size for x in jax.tree.leaves(p)) == cfg.num_params
    jax.tree.map(lambda a, axes: None if a.ndim == len(axes) else 1 / 0, p,
                 param_logical_axes(cfg),
                 is_leaf=lambda x: isinstance(x, tuple))


@pytest.mark.parametrize("n", [40, 100])
def test_forward_without_a_cache_matches_reference(n):
    """Below dense_len (40 tokens: every read is the dense one) and beyond
    it (100: the tokens from 25 on have more candidates than places)."""
    cfg = toy()
    toks = tokens_for(cfg, n, n)
    np.testing.assert_allclose(program_logits(cfg, 3, toks),
                               reference_logits(cfg, 3, toks), atol=TOL)


def test_the_choice_really_drops_blocks():
    """At 100 tokens the sparse read is another function than the dense
    one: the same weights read densely (sparse_topk 0) are far from the
    reference, and the counts say how many pairs the choice leaves out."""
    from runbooks_tpu.ops.block_sparse_attention import read_counts

    cfg = toy()
    toks = tokens_for(cfg, 100, 100)
    dense = dataclasses.replace(cfg, sparse_topk=0)
    p = {k: v for k, v in seeded(cfg, 3).items()}
    got = np.asarray(jax.jit(lambda p, t: forward(dense, p, t)[0])(
        p, jnp.asarray(toks)[None])[0])
    assert np.abs(got - reference_logits(cfg, 3, toks)).max() > 50 * TOL
    at = np.arange(100)
    sparse = np.ones(100, bool)
    # Token 99: its window of 16, the initial block, 3 blocks of 8.
    assert read_counts(at[99:], sparse[99:], cfg.sparse_read) == (
        16 + 8 + 24, 1 + 3)
    assert read_counts(at, sparse, cfg.sparse_read)[0] < 100 * 101 // 2


@pytest.mark.parametrize("field,value,reading", [
    ("attn_gate_width", "head", {"gate": "head"}),
    ("lightning_decay_layers", 0, {"decay_layer_factor": False}),
    ("sparse_exclude_window", False,
     {"window_blocks_are_candidates": True}),
])
def test_the_other_reading_of_each_assumed_alternative(field, value,
                                                       reading):
    """Where the published row cannot settle a reading the reference takes
    it as an argument; the program has a field for each, and the two agree
    on both (the cell runs the first: the configuration file's
    `readings`)."""
    cfg = toy(**{field: value})
    toks = tokens_for(cfg, 100, 100)
    got = program_logits(cfg, 3, toks)
    np.testing.assert_allclose(
        got, reference_logits(cfg, 3, toks, **reading), atol=TOL)
    # ... and each reading is another function than the cell's.
    assert np.abs(got - reference_logits(toy(), 3, toks)).max() > 10 * TOL


@pytest.mark.parametrize("left_out", [
    dict(sparse_init_blocks=0), dict(sparse_window=1),
    dict(lightning_decay_layers=0), dict(linear_rope_theta=0.0),
    dict(residual_scale=1.0), dict(logit_divisor=1.0),
    dict(embed_multiplier=0.0),
])
def test_a_program_with_a_term_left_out_fails(left_out):
    """The initial block, the window, the decay's layer factor, the linear
    layers' rotary, each of the three scalings: a program without one is
    beyond the tolerance by a factor of ten at least."""
    cfg = toy()
    toks = tokens_for(cfg, 100, 100)
    got = program_logits(dataclasses.replace(cfg, **left_out), 3, toks)
    assert np.abs(got - reference_logits(cfg, 3, toks)).max() > 10 * TOL


# --------------------------------------------------------------------------
# The two cores against their plain forms
# --------------------------------------------------------------------------

def test_chunked_lightning_core_matches_its_token_scan():
    """Chunks of 16 over 70 tokens (a ragged last chunk), two rows: one
    whole, one whose tokens from 37 on are padding; from a state that is
    not zero. The padded row's state is the state after its 37 tokens, and
    a parked row of a decode step keeps its state bit for bit."""
    from runbooks_tpu.ops.lightning_attention import (
        decay_rates,
        lightning_chunked,
        lightning_reference,
        lightning_step,
    )

    rng = np.random.default_rng(0)
    b, s, H, d = 2, 70, 4, 16
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, H, d)), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(rng.standard_normal((b, H, d, d)), jnp.float32)
    mask = jnp.asarray(np.arange(s)[None] < np.array([[70], [37]]))
    rate = decay_rates(H, 3, 7)
    np.testing.assert_allclose(
        np.asarray(rate), 2.0 ** (-8 * np.arange(1, 5) / 4)
        * (1 - 3 / 7 + 1e-5), rtol=1e-6)
    want_o, want_s = lightning_reference(q, k, v, rate, d ** -0.5, s0, mask)
    got_o, got_s = lightning_chunked(q, k, v, rate, d ** -0.5, s0, mask,
                                     chunk=16)
    # (Sums of up to 70 products of order 1, in another order.)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_o[0], want_o[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_o[1, :37], want_o[1, :37], atol=1e-5,
                               rtol=1e-5)
    _, short = lightning_reference(q[1:, :37], k[1:, :37], v[1:, :37], rate,
                                   d ** -0.5, s0[1:])
    np.testing.assert_allclose(got_s[1:], short, atol=1e-5, rtol=1e-5)
    _, stepped = lightning_step(q[:, 0], k[:, 0], v[:, 0], rate, s0,
                                d ** -0.5, jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(stepped[1]), np.asarray(s0[1]))
    assert np.abs(np.asarray(stepped[0] - s0[0])).max() > 0.1


# name: (queries, keys beyond them, query heads, block_q, block_k,
#        exclude_window, heads a grid step, tokens a select step, row 1)
CORE_CASES = {
    "a long and a short row": (128, 0, 4, 32, 64, True, None, None, "short"),
    "a parked tail": (128, 0, 4, 32, 64, True, None, None, "parked"),
    # (136 queries on 137 keys, a trash slot beyond them, in both.)
    "queries no block divides": (136, 1, 4, 32, 64, True, None, 96, "short"),
    "keys no block divides": (136, 1, 4, 32, 64, True, None, 96, "parked"),
    "window blocks are candidates": (128, 0, 4, 32, 64, False, None, None,
                                     "long"),
    "a group the head block does not divide": (128, 0, 6, 32, 64, True, 2,
                                               None, "short"),
}


@functools.partial(jax.jit, static_argnums=(5, 6))
def _core_reference(q, k, v, pos, long_row, sp, exclude):
    from runbooks_tpu.ops.block_sparse_attention import (
        block_sparse_attention_reference,
    )

    return block_sparse_attention_reference(
        q, k, v, pos, sp, q.shape[-1] ** -0.5, dense_rows=~long_row,
        exclude_window=exclude)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _core_program(q, k, v, pos, long_row, sp, exclude, block_q, block_k,
                  patched):
    """(`patched`: what the case put in place of the module's constants,
    which a kept trace must not outlive.)"""
    from runbooks_tpu.ops.block_sparse_attention import (
        compress_keys,
        sparse_prefill,
    )

    return sparse_prefill(q, k, v, compress_keys(k, sp), pos, long_row, sp,
                          q.shape[-1] ** -0.5, exclude, block_q, block_k)


@pytest.mark.parametrize("case", list(CORE_CASES))
def test_sparse_walk_matches_one_masked_softmax(monkeypatch, case):
    """The prefill's core — one call of the flash forward under each
    token's choice, interpreted here, at small pinned blocks (several
    query blocks and key steps) — against the read in one piece
    (block_sparse_attention_reference: the choice, then one masked softmax
    over all keys). Row 0 is long (read sparsely: at 128 tokens 14
    candidates for 3 places); row 1 is long too, or short (read whole, in
    the same call), or a prompt of 50 whose tail is parked at -1 (output
    exactly 0). The choice is made in one step, or 48 tokens of a row at a
    time with a padded last step. The counts are the kernel's: every pair
    of a step that runs, no fewer than the choices need."""
    import runbooks_tpu.ops.block_sparse_attention as bsa
    import runbooks_tpu.ops.flash_attention as fa

    (s, beyond, H, block_q, block_k, exclude, g_step, select,
     row1) = CORE_CASES[case]
    sp, rng = toy().sparse_read, np.random.default_rng(1)
    b, g, d = 2, 2, 16
    L = s + beyond
    q = jnp.asarray(rng.standard_normal((b, s, H, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, L, g, d)), jnp.float32)
            for _ in range(2))
    pos = np.broadcast_to(np.arange(s), (b, s)).copy()
    if row1 == "parked":
        pos[1, 50:] = -1
    long_row = jnp.asarray([True, row1 == "long"])
    args = (q, k, v, jnp.asarray(pos), long_row, sp)
    want = _core_reference(*args, exclude)
    if g_step:
        monkeypatch.setattr(fa, "head_block",
                            lambda n_rep, *a: min(n_rep, g_step))
    if select:
        monkeypatch.setattr(bsa, "SELECT_TOKENS", select)
    got = _core_program(*args, exclude, block_q, block_k, (g_step, select))
    np.testing.assert_allclose(got, want, atol=1e-5)
    if row1 == "parked":
        assert not np.asarray(got[1, 50:]).any()
    if not exclude:
        # The other reading of the window's blocks is another function.
        assert np.abs(np.asarray(
            got - _core_reference(*args, True))).max() > 1e-3
    needed, visited, _ = bsa.prefill_counts(pos, pos < 0, sp, L, H // g,
                                            block_q, block_k)
    causal = sum(n * (n + 1) // 2 for n in (pos.max(axis=-1) + 1))
    assert needed <= visited and causal < visited
    if row1 == "long":
        assert needed < causal


def test_flash_forward_under_a_choice_refuses_a_backward_by_name():
    from runbooks_tpu.ops.flash_attention import (
        BlockChoiceBackward,
        flash_attention,
    )

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 32, 4, 16)), jnp.float32)
    k = v = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.float32)
    pos = jnp.arange(32)[None]
    chosen = jnp.ones((1, 2, 32, 4), jnp.int8)
    with pytest.raises(BlockChoiceBackward, match="forward only"):
        jax.eval_shape(jax.grad(lambda q: flash_attention(
            q, k, v, pos, pos, None, None, True, None, 16, 16, window=8,
            choice=chosen, choice_block=8).sum()), q)


@pytest.mark.parametrize("rows,queries,real", [
    (1, 16384, 14592), (1, 16384, 13312), (8, 8192, 8192), (1, 8192, 6000)])
def test_the_prefill_counter_is_the_kernels(rows, queries, real):
    """`visited` of a dispatch is block_q x block_k pairs for every grid
    step of the core whose body runs: for each row and query block the key
    steps up to the one that holds its last real position (the causal
    ranges; a parked block runs none), at the block shape the call
    compiles with. Here for the long cell's dispatches: a 14 592-token
    prompt in the 16 384 bucket is 225 steps of 512 x 1024."""
    import runbooks_tpu.ops.block_sparse_attention as bsa
    from runbooks_tpu.ops.flash_attention import block_shape

    sp = get_config("minicpm-sala").sparse_read
    keys, group = 16385, 16
    pos = np.broadcast_to(np.arange(queries), (rows, queries)).copy()
    pos[:, real:] = keys - 1                    # parked, as the engine parks
    parked = pos >= keys - 1
    block_q, block_k = block_shape("fwd", queries, keys, group, sp.window)
    assert (block_q, block_k) == (512, 1024)
    steps = 0
    for row in np.where(parked, -1, pos):
        for i in range(0, queries, block_q):
            last = row[i:i + block_q].max()
            steps += last // block_k + 1 if last >= 0 else 0
    needed, visited, chosen = bsa.prefill_counts(pos, parked, sp, keys,
                                                 group)
    assert visited == steps * block_q * block_k
    assert (rows, real) != (1, 14592) or steps == 225
    assert 0 < needed <= visited
    # A row under dense_len reads every earlier key and chooses nothing.
    assert (chosen > 0) == (real >= sp.dense_len)


def test_a_near_tie_in_the_choice_falls_on_one_of_the_two():
    """Two candidate blocks whose scores differ by less than a bfloat16
    step: keys 1 + 2**-10 (row 0; row 1: 1 - 2**-10) along the query's
    direction in block 12 beside keys 1 in block 9. The reference ranks
    the float32 keys and takes the larger; the program ranks the keys AS
    STORED, where both are 1.0, and its tie goes to the lower index. On
    either side the two choices are equal but for those two blocks, each
    takes exactly one of them, and the layer's output stays within the
    served check's limits (benchmark/configs/minicpm-sala.json `limits`:
    mean 0.0003, max 0.01) of the reference's: a block of nearly the same
    score carries nearly the same, small weight. (The shapes of the
    kernel's first case above, whose compiled functions this reuses.)"""
    import runbooks_tpu.ops.block_sparse_attention as bsa

    sp = toy().sparse_read
    s, H, g, d, led, tied = 128, 4, 2, 16, [3, 6], [9, 12]
    scale = d ** -0.5
    q = jnp.zeros((2, s, H, d), jnp.float32).at[..., 0].set(64.0)
    v = jnp.asarray(np.random.default_rng(0).standard_normal((2, s, g, d)),
                    jnp.float32)
    # (A compressed key that straddles a block's edge is the mean of both
    # sides: 0.75 beside the two leading blocks, below the tie.)
    along = np.zeros((2, s // sp.block), np.float32)
    along[:, led], along[:, tied[0]] = 1.5, 1.0
    along[:, tied[1]] = 1.0 + 2.0 ** -10, 1.0 - 2.0 ** -10
    k = jnp.zeros((2, s, g, d), jnp.float32).at[..., 0].set(
        np.repeat(along, sp.block, axis=1)[:, :, None])
    stored = k.astype(jnp.bfloat16).astype(jnp.float32)
    assert (np.asarray(stored[:, tied[1] * sp.block, :, 0]) == 1.0).all()
    pos = jnp.broadcast_to(jnp.arange(s), (2, s))
    select = jax.jit(lambda keys: bsa.select_blocks(
        q, bsa.compress_keys(keys, sp), pos, sp, bsa.n_blocks(s, sp), scale))
    theirs, ours = np.asarray(select(k)), np.asarray(select(stored))
    differ = np.argwhere(theirs != ours)
    assert set(differ[:, -1]) == set(tied)
    # The larger key in the later block is the side where the two part.
    assert set(differ[:, 0]) == {0}
    for pick in (theirs, ours):
        assert (pick[:, -1][..., tied].sum(axis=-1) == 1).all()
        assert pick[:, -1][..., led].all()
    args = (q, v, pos, jnp.asarray([True, True]), sp, True)
    want = _core_reference(q, k, *args[1:])
    got = _core_program(q, stored, *args[1:], 32, 64, (None, None))
    gap = np.abs(np.asarray(got - want))
    assert gap.max() <= 0.01 and gap.mean() <= 0.0003


# --------------------------------------------------------------------------
# The cache
# --------------------------------------------------------------------------

def test_cache_leaves_of_the_configuration():
    cfg = toy()
    leaves = {leaf.name: leaf for leaf in cache_leaves(cfg)}
    assert list(leaves) == ["k", "v", "state", "ckeys"]     # no conv tail
    assert leaves["state"].shape(cfg, 3, 129) == (6, 3, 4, 32, 32)
    # (129 - 4) // 2 + 1 compressed keys a row, float32, by KV head.
    assert leaves["ckeys"].shape(cfg, 3, 129) == (2, 3, 63, 2, 32)
    assert leaves["ckeys"].dtype == jnp.float32 \
        and leaves["ckeys"].group == "kv_compressed"
    cache = KVCache.create(cfg, 3, 128, trash_slot=True)
    assert cache.conv is None and cache.ckeys.shape == (2, 3, 63, 2, 32)
    with pytest.raises(NotImplementedError, match="quantize_kv.*sparse"):
        cache_leaves(cfg, quantize_kv=True)


@pytest.mark.parametrize("over,text", [
    (dict(layer_types=("full_attention", "sliding_attention"),
          sliding_window=8), "sparse read"),
    (dict(linear_conv_kernel=4), "lightning has no short convolution"),
    (dict(sparse_stride=3), "sparse_stride dividing"),
    (dict(linear_mixer="delta"), "unknown linear_mixer"),
])
def test_config_refuses_what_cannot_be(over, text):
    with pytest.raises(ValueError, match=text):
        if "layer_types" in over:
            toy(num_layers=4, **over)
        else:
            toy(**over)


def test_packed_sequences_are_refused():
    cfg = toy()
    with pytest.raises(NotImplementedError, match="segment_ids"):
        forward(cfg, seeded(cfg, 0), jnp.zeros((1, 8), jnp.int32),
                segment_ids=jnp.ones((1, 8), jnp.int32))
