"""The declaration of a KVCache's leaves (models/transformer.cache_leaves,
LEAF_TRAITS) against literal tables written from the values of the tree
before there was one (PR 42): what KVCache.create allocates, where forward
finds each layer's part, and what analysis/loop_copies allows a loop to
write in place. Shapes only (jax.eval_shape): no engine, no compile."""

import dataclasses

import jax
import pytest

from runbooks_tpu.analysis.loop_copies import cache_shapes
from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import (
    LEAF_TRAITS,
    KVCache,
    _leaf_index,
    cache_leaves,
)

SLOTS, MAX_LEN = 3, 16          # a pool of 3 rows, 16 tokens + a trash slot

# (preset, quantize_kv where the kind is served) -> {field: (shape, dtype)}
LEAVES = {
    ("debug", False): {"k": ((2, 3, 17, 2, 32), "bfloat16"),
                       "v": ((2, 3, 17, 2, 32), "bfloat16")},
    ("debug", True): {"k": ((2, 3, 17, 2, 32), "int8"),
                      "v": ((2, 3, 17, 2, 32), "int8"),
                      "k_scale": ((2, 3, 17, 2), "float32"),
                      "v_scale": ((2, 3, 17, 2), "float32")},
    ("debug-hybrid", False): {"k": ((1, 3, 17, 4, 32), "bfloat16"),
                              "v": ((1, 3, 17, 4, 32), "bfloat16"),
                              "state": ((3, 3, 4, 32, 64), "float32"),
                              "conv": ((3, 3, 3, 512), "bfloat16")},
    ("debug-hybrid", True): {"k": ((1, 3, 17, 4, 32), "int8"),
                             "v": ((1, 3, 17, 4, 32), "int8"),
                             "k_scale": ((1, 3, 17, 4), "float32"),
                             "v_scale": ((1, 3, 17, 4), "float32"),
                             "state": ((3, 3, 4, 32, 64), "float32"),
                             "conv": ((3, 3, 3, 512), "bfloat16")},
    ("debug-sparse-latent", False): {"k": ((0, 3, 17, 4, 80), "bfloat16"),
                                     "v": ((0, 3, 17, 4, 80), "bfloat16"),
                                     "latent": ((3, 3, 17, 80), "bfloat16")},
    ("debug-window-full", False): {
        "k": ((2, 3, 17, 2, 24), "bfloat16"),
        "v": ((2, 3, 17, 2, 16), "bfloat16"),
        "ring_k": ((3, 3, 16, 4, 24), "bfloat16"),
        "ring_v": ((3, 3, 16, 4, 16), "bfloat16")},
    ("debug-laguna", False): {"k": ((2, 3, 17, 2, 16), "bfloat16"),
                              "v": ((2, 3, 17, 2, 16), "bfloat16"),
                              "ring_k": ((3, 3, 16, 2, 16), "bfloat16"),
                              "ring_v": ((3, 3, 16, 2, 16), "bfloat16")},
    ("debug-lfm2", False): {"k": ((2, 3, 17, 2, 32), "bfloat16"),
                            "v": ((2, 3, 17, 2, 32), "bfloat16"),
                            "conv": ((7, 3, 2, 128), "bfloat16")},
    ("debug-lfm2", True): {"k": ((2, 3, 17, 2, 32), "int8"),
                           "v": ((2, 3, 17, 2, 32), "int8"),
                           "k_scale": ((2, 3, 17, 2), "float32"),
                           "v_scale": ((2, 3, 17, 2), "float32"),
                           "conv": ((7, 3, 2, 128), "bfloat16")},
}

# The same pools -> {(dtype, dims): the largest in-place update in a loop}
ALLOWED = {
    ("debug", False): {("bf16", (1, 3, 17, 2, 32)): 3263,
                       ("bf16", (2, 3, 17, 2, 32)): 3263,
                       ("bf16", (3, 17, 2, 32)): 3263},
    ("debug", True): {("f32", (1, 3, 17, 2)): 101,
                      ("f32", (2, 3, 17, 2)): 101,
                      ("f32", (3, 17, 2)): 101,
                      ("s8", (1, 3, 17, 2, 32)): 3263,
                      ("s8", (2, 3, 17, 2, 32)): 3263,
                      ("s8", (3, 17, 2, 32)): 3263},
    # One full layer: the whole leaf and its one layer have the same dims.
    ("debug-hybrid", False): {("bf16", (1, 3, 17, 4, 32)): 6527,
                              ("bf16", (3, 3, 3, 512)): 4608,
                              ("bf16", (3, 17, 4, 32)): 6527,
                              ("f32", (3, 3, 4, 32, 64)): 24576},
    ("debug-hybrid", True): {("bf16", (3, 3, 3, 512)): 4608,
                             ("f32", (1, 3, 17, 4)): 203,
                             ("f32", (3, 3, 4, 32, 64)): 24576,
                             ("f32", (3, 17, 4)): 203,
                             ("s8", (1, 3, 17, 4, 32)): 6527,
                             ("s8", (3, 17, 4, 32)): 6527},
    # The empty k and v of a latent cache allow nothing.
    ("debug-sparse-latent", False): {("bf16", (1, 3, 17, 80)): 4079,
                                     ("bf16", (3, 3, 17, 80)): 4079,
                                     ("bf16", (3, 17, 80)): 4079},
    # Of a ring only the whole leaf, less one element.
    ("debug-window-full", False): {("bf16", (1, 3, 17, 2, 16)): 1631,
                                   ("bf16", (1, 3, 17, 2, 24)): 2447,
                                   ("bf16", (2, 3, 17, 2, 16)): 1631,
                                   ("bf16", (2, 3, 17, 2, 24)): 2447,
                                   ("bf16", (3, 3, 16, 4, 16)): 3071,
                                   ("bf16", (3, 3, 16, 4, 24)): 4607,
                                   ("bf16", (3, 17, 2, 16)): 1631,
                                   ("bf16", (3, 17, 2, 24)): 2447},
    ("debug-laguna", False): {("bf16", (1, 3, 17, 2, 16)): 1631,
                              ("bf16", (2, 3, 17, 2, 16)): 1631,
                              ("bf16", (3, 3, 16, 2, 16)): 1535,
                              ("bf16", (3, 17, 2, 16)): 1631},
    # A tail changes a whole layer at a time.
    ("debug-lfm2", False): {("bf16", (1, 3, 17, 2, 32)): 3263,
                            ("bf16", (2, 3, 17, 2, 32)): 3263,
                            ("bf16", (3, 17, 2, 32)): 3263,
                            ("bf16", (7, 3, 2, 128)): 768},
    ("debug-lfm2", True): {("bf16", (7, 3, 2, 128)): 768,
                           ("f32", (1, 3, 17, 2)): 101,
                           ("f32", (2, 3, 17, 2)): 101,
                           ("f32", (3, 17, 2)): 101,
                           ("s8", (1, 3, 17, 2, 32)): 3263,
                           ("s8", (2, 3, 17, 2, 32)): 3263,
                           ("s8", (3, 17, 2, 32)): 3263},
}

CASES = [pytest.param(*case, id=f"{case[0]}{'-int8' if case[1] else ''}")
         for case in LEAVES]
ARRAY_FIELDS = [f.name for f in dataclasses.fields(KVCache)
                if f.name != "index"]


def pool_shapes(preset, quantize_kv):
    cfg = get_config(preset)
    return cfg, jax.eval_shape(lambda: KVCache.create(
        cfg, SLOTS, MAX_LEN, trash_slot=True, quantize_kv=quantize_kv))


def test_every_array_field_is_declared_in_field_order():
    assert list(LEAF_TRAITS) == ARRAY_FIELDS


@pytest.mark.parametrize("preset, quantize_kv", CASES)
def test_create_gives_exactly_the_declared_leaves(preset, quantize_kv):
    cfg, pool = pool_shapes(preset, quantize_kv)
    declared = cache_leaves(cfg, quantize_kv)
    names = [leaf.name for leaf in declared]
    assert names == [n for n in ARRAY_FIELDS if n in names]   # field order
    for leaf in declared:
        assert leaf[4:] == LEAF_TRAITS[leaf.name]
        assert len(leaf.axes) == len(leaf.shape(cfg, SLOTS, MAX_LEN + 1))
    assert {leaf.name: (leaf.shape(cfg, SLOTS, MAX_LEN + 1),
                        jax.numpy.dtype(leaf.dtype).name)
            for leaf in declared} == LEAVES[preset, quantize_kv]
    assert {name: (a.shape, a.dtype.name) for name in ARRAY_FIELDS
            if (a := getattr(pool, name)) is not None
            } == LEAVES[preset, quantize_kv]
    assert pool.index.shape == () and pool.index.dtype.name == "int32"


@pytest.mark.parametrize("preset", sorted({p for p, _ in LEAVES}))
def test_the_layer_index_covers_each_kinds_leaves_in_layer_order(preset):
    cfg = get_config(preset)
    pattern = cfg.layer_pattern
    # Every layer of the model in layer order: the leading ones, then the
    # periods', each with its number among the layers of its kind.
    found = {kind: [] for kind in {*pattern, cfg.leading_layer_kind}}
    for i in range(cfg.leading_dense_layers):
        found[cfg.leading_layer_kind].append(i)
    for period in range(cfg.num_periods):
        for at, kind in enumerate(pattern):
            found[kind].append(_leaf_index(
                cfg, kind, period, pattern[:at].count(kind)))
    assert sum(map(len, found.values())) == cfg.num_layers
    for kind, indices in found.items():
        assert indices == list(range(cfg.layers_of(kind))), kind
    for leaf in cache_leaves(cfg):
        # A leaf with no layer (a latent cache's k, v) is nobody's.
        assert bool(cfg.layers_of(leaf.kind)) == (leaf.kind in found)


@pytest.mark.parametrize("preset, quantize_kv", CASES)
def test_loop_copy_allowances_follow_the_declaration(preset, quantize_kv):
    _, pool = pool_shapes(preset, quantize_kv)
    assert cache_shapes(pool) == ALLOWED[preset, quantize_kv]
