"""Every way forward() writes and reads a cache, driven the same way for
every kind of model: prefill a few tokens, then decode one at a time, and
hand back the logits of each row's real positions, to be held against the
forward without a cache. Shared by the cache tests of test_transformer.py
(float pool), test_quantization.py (int8 pool) and test_hybrid_model.py
(recurrent state beside the pool)."""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from runbooks_tpu.models.transformer import KVCache, forward
from runbooks_tpu.serve.engine import make_decode_fn

MAX_LEN, SHORT_VIEW, TOKENS, PREFILL = 24, 16, 12, 6

# (ids, values) for parametrize: write mode x read view.
MODES_AND_VIEWS = list(itertools.product(("index", "scatter"),
                                         (None, SHORT_VIEW)))
MODE_VIEW_IDS = [f"{mode}-{'whole' if view is None else 'short-view'}"
                 for mode, view in MODES_AND_VIEWS]


def logits_through_cache(cfg, params, tokens, mode: str, view,
                         int8_pool: bool = False):
    """[(row's logits [n_r, vocab], n_r)] for tokens [2, TOKENS].

    ``index``: scalar-index mode, both rows append together, PREFILL
    tokens then one at a time. ``scatter``: position-scatter mode on a
    pool with a trash slot; row 1's prompt is two tokens shorter than the
    prefill's width (its padding parked at the trash slot, and masked out
    of a hybrid's recurrent state), so the rows advance independently."""
    b, n = tokens.shape
    assert (b, n) == (2, TOKENS)
    scatter = mode == "scatter"
    step = jax.jit(functools.partial(forward, cfg),
                   static_argnames=("cache_view",))
    cache = KVCache.create(cfg, b, MAX_LEN, trash_slot=scatter,
                           quantize_kv=int8_pool)
    if not scatter:
        got = []
        for lo, hi in [(0, PREFILL)] + [(i, i + 1)
                                        for i in range(PREFILL, n)]:
            logits, cache = step(params, tokens[:, lo:hi], cache=cache,
                                 cache_view=view)
            got.append(logits)
        assert int(cache.index) == n
        logits = jnp.concatenate(got, axis=1)
        return [(logits[r], n) for r in range(b)]
    lengths = np.array([PREFILL, PREFILL - 2])
    col = np.arange(PREFILL)[None]
    real = col < lengths[:, None]
    pos = np.where(real, col, MAX_LEN)              # MAX_LEN = trash slot
    logits, cache = step(params, tokens[:, :PREFILL],
                         positions=jnp.asarray(pos, jnp.int32), cache=cache,
                         cache_view=view, token_mask=jnp.asarray(real))
    rows = [[logits[r, :lengths[r]]] for r in range(b)]
    steps = n - PREFILL
    for t in range(steps):
        at = lengths + t
        tok = jnp.stack([tokens[r, at[r]] for r in range(b)])[:, None]
        logits, cache = step(params, tok, cache=cache, cache_view=view,
                             positions=jnp.asarray(at, jnp.int32)[:, None])
        for r in range(b):
            rows[r].append(logits[r])
    assert int(cache.index) == 0        # rows advance, the index does not
    return [(jnp.concatenate(rows[r], axis=0), int(lengths[r]) + steps)
            for r in range(b)]


def worst_gap(cfg, params, tokens, mode, view, int8_pool=False) -> float:
    """Largest |logit through the cache - logit without one| over every
    real position of both rows."""
    whole = jax.jit(lambda p, t: forward(cfg, p, t)[0])(params, tokens)
    gap = 0.0
    for r, (got, n_r) in enumerate(logits_through_cache(
            cfg, params, tokens, mode, view, int8_pool)):
        assert got.shape[0] == n_r
        gap = max(gap, float(jnp.max(jnp.abs(got - whole[r, :n_r]))))
    return gap


def greedy_chunk(cfg, params, steps: int = 8):
    """Greedy tokens [steps, 3] of one decode chunk as the engine runs it
    (make_decode_fn, pool donated, a view shorter than the pool) after a
    short prompt in rows 0 and 2; row 1 is parked."""
    slots, view = 3, SHORT_VIEW
    rng = np.random.default_rng(11)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (slots, 5)),
                         jnp.int32)
    alive = np.array([True, False, True])
    pos = np.where(alive[:, None], np.arange(5)[None], MAX_LEN)
    pool = KVCache.create(cfg, slots, MAX_LEN, trash_slot=True)
    logits, pool = jax.jit(functools.partial(forward, cfg))(
        params, prompt, positions=jnp.asarray(pos, jnp.int32), cache=pool,
        token_mask=jnp.asarray(np.broadcast_to(alive[:, None], (slots, 5))))
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    decode = jax.jit(make_decode_fn(cfg, steps, MAX_LEN, MAX_LEN, view),
                     donate_argnums=(1,))
    toks, valid, _, pool, _ = decode(
        params, pool, first, jnp.full(slots, 5, jnp.int32),
        jax.random.key(0), jnp.zeros(slots), jnp.zeros(slots, jnp.int32),
        jnp.ones(slots), jnp.full(slots, -1), jnp.full(slots, 100),
        jnp.asarray(alive))
    assert np.array_equal(np.asarray(valid),
                          np.broadcast_to(alive, (steps, slots)))
    return np.asarray(toks)[:, alive].tolist()
