"""Token sampling: greedy / temperature / top-k / top-p, jit-friendly.

Static-shape throughout (top-k uses lax.top_k with a static k; top-p masks
the sorted tail) so one compiled sampler serves every request — request-level
parameters are traced scalars, not Python branches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# max_top_k (the static sorted-lane width): requests with top_k=0 AND
# top_p=1.0 sample the full vocab; requests using top_p are truncated to the
# lane (an explicit engineering cap — probability mass beyond the top
# max_top_k logits is negligible for real models).


@jax.named_scope("sample")
def sample(
    logits: jax.Array,              # [batch, vocab] float32
    rng: jax.Array,
    temperature: jax.Array,         # [batch] or scalar; 0 => greedy
    top_k: jax.Array,               # [batch] int32; 0 => disabled
    top_p: jax.Array,               # [batch] float32; 1.0 => disabled
    max_top_k: int = 64,
    gmask: jax.Array = None,        # [batch, vocab] bool; None/all-True => off
) -> jax.Array:
    """Returns sampled token ids [batch].

    ``gmask`` is the grammar-constrained decoding operand
    (serve/grammar.py): allowed-token bool rows applied as a -inf logit
    mask BEFORE every path below, so greedy argmax, the static top-k
    lane, and the full-vocab categorical all respect the constraint
    identically. An all-True row is the identity — unconstrained lanes
    batch with constrained ones in the same dispatch.
    """
    if gmask is not None:
        logits = jnp.where(gmask, logits, -jnp.inf)
    vocab = logits.shape[-1]
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                                   logits.shape[:1])
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), logits.shape[:1])
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), logits.shape[:1])

    greedy = jnp.argmax(logits, axis=-1)

    # Temperature (guard 0 -> greedy path selected at the end).
    temp_safe = jnp.where(temperature <= 0.0, 1.0, temperature)
    scaled = logits / temp_safe[:, None]

    # Top-k over a static-width lane.
    k_cap = min(max_top_k, vocab)
    top_vals, top_idx = jax.lax.top_k(scaled, k_cap)       # [b, k_cap] sorted
    ranks = jnp.arange(k_cap, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(top_k <= 0, k_cap, jnp.minimum(top_k, k_cap))
    keep_k = ranks < k_eff[:, None]

    # Top-p on the sorted lane: keep the smallest prefix with cumprob >= p
    # (always keep the first token).
    probs = jax.nn.softmax(jnp.where(keep_k, top_vals, -jnp.inf), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < top_p[:, None]
    keep = keep_k & keep_p
    keep = keep.at[:, 0].set(True)

    masked = jnp.where(keep, top_vals, -jnp.inf)
    rng_lane, rng_full = jax.random.split(rng)
    choice = jax.random.categorical(rng_lane, masked, axis=-1)  # lane space
    lane_sampled = jnp.take_along_axis(top_idx, choice[:, None], axis=1)[:, 0]

    # top_k=0 and top_p=1.0 => unrestricted sampling over the full vocab
    # (the lane would otherwise silently cap the distribution at max_top_k).
    full_sampled = jax.random.categorical(rng_full, scaled, axis=-1)
    restricted = (top_k > 0) | (top_p < 1.0)
    sampled = jnp.where(restricted, lane_sampled, full_sampled)

    return jnp.where(temperature <= 0.0, greedy, sampled)


def _filtered_draft_stats(logits, draft, rng, temperature, top_k, top_p,
                          max_top_k):
    """(p_draft, resid) for one flattened row set: the draft token's
    probability under the SAME temperature/top-k/top-p-filtered
    distribution ``sample`` draws from, and an independent draw from that
    distribution with the draft masked out (the normalized residual
    ``(pi - q)+`` for a deterministic point-mass proposal q)."""
    n, vocab = logits.shape
    temp_safe = jnp.where(temperature <= 0.0, 1.0, temperature)
    scaled = logits / temp_safe[:, None]

    # The lane-restricted distribution, byte-for-byte the construction in
    # sample() above — verify exactness is exactness w.r.t. the engine's
    # OWN sampler, lane truncation included.
    k_cap = min(max_top_k, vocab)
    top_vals, top_idx = jax.lax.top_k(scaled, k_cap)
    ranks = jnp.arange(k_cap, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(top_k <= 0, k_cap, jnp.minimum(top_k, k_cap))
    keep_k = ranks < k_eff[:, None]
    probs = jax.nn.softmax(jnp.where(keep_k, top_vals, -jnp.inf), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < top_p[:, None]
    keep = (keep_k & keep_p).at[:, 0].set(True)
    masked = jnp.where(keep, top_vals, -jnp.inf)
    lane_probs = jax.nn.softmax(masked, axis=-1)
    is_draft = top_idx == draft[:, None]
    p_lane = jnp.sum(jnp.where(is_draft & keep, lane_probs, 0.0), axis=-1)

    # Unrestricted path (top_k=0, top_p=1.0): full-vocab softmax.
    full_probs = jax.nn.softmax(scaled, axis=-1)
    p_full = jnp.take_along_axis(full_probs, draft[:, None], axis=1)[:, 0]

    restricted = (top_k > 0) | (top_p < 1.0)
    p_draft = jnp.where(restricted, p_lane, p_full)

    rng_lane, rng_full = jax.random.split(rng)
    choice = jax.random.categorical(
        rng_lane, jnp.where(is_draft, -jnp.inf, masked), axis=-1)
    lane_resid = jnp.take_along_axis(top_idx, choice[:, None], axis=1)[:, 0]
    vocab_ids = jnp.arange(vocab, dtype=draft.dtype)[None, :]
    full_resid = jax.random.categorical(
        rng_full, jnp.where(vocab_ids == draft[:, None], -jnp.inf, scaled),
        axis=-1)
    resid = jnp.where(restricted, lane_resid, full_resid)
    return p_draft, resid


@jax.named_scope("sample")
def speculative_verify(
    logits: jax.Array,              # [batch, s, vocab] float32
    drafts: jax.Array,              # [batch, s-1] int32 drafted tokens
    rng: jax.Array,
    temperature: jax.Array,         # [batch]; 0 => greedy
    top_k: jax.Array,               # [batch] int32; 0 => disabled
    top_p: jax.Array,               # [batch] float32; 1.0 => disabled
    max_top_k: int = 64,
    gmask: jax.Array = None,        # [batch, s, vocab] bool; None => off
):
    """Draft-verify verdicts for speculative decoding, distribution-exact
    w.r.t. ``sample``. ``logits[b, i]`` is the model's next-token
    distribution after verify input ``i``; ``drafts[b, i]`` is the
    PROPOSED token at input position ``i + 1`` (so logits row ``i``
    verifies drafts row ``i``; the trailing logits row has no draft and
    only feeds ``full``). Returns ``(accept, resid, full)``:

    - ``accept [b, s-1] bool``: the draft survives exact speculative
      rejection sampling — greedy: ``draft == argmax``; temperature:
      ``u < pi(draft)`` with ``pi`` the same filtered distribution
      ``sample`` draws from (a deterministic prompt-lookup proposal has
      q = point mass, so the accept probability is just ``pi(draft)``).
    - ``resid [b, s-1] int32``: the replacement token when position i is
      the FIRST rejection — greedy: the argmax itself; temperature: a
      draw from ``pi`` with the draft masked (the normalized residual),
      so the emitted-token marginal equals ``sample``'s exactly:
      P(emit y != draft) = (1 - pi(draft)) * pi(y)/(1 - pi(draft)).
    - ``full [b, s] int32``: an ordinary ``sample`` draw at every
      position — the bonus token after a fully accepted draft run, and
      the plain one-token decode for slots that proposed nothing.

    ``gmask[b, i]`` constrains the distribution at verify position i
    (grammar-constrained slots: the DFA state after consuming the draft
    prefix ``drafts[b, :i]``). Applied to the logits up front, so the
    accept/resid/full math below is exact w.r.t. the MASKED
    distribution — the engine pre-truncates drafts to legal prefixes, so
    every drafted token has nonzero mass under its row's mask.
    """
    if gmask is not None:
        logits = jnp.where(gmask, logits, -jnp.inf)
    b, s, vocab = logits.shape
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), (b,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    rng_accept, rng_resid, rng_full = jax.random.split(rng, 3)

    # Row-major flatten keeps [b, s] <-> [b*s] index math aligned with
    # jnp.repeat of the per-slot sampling params.
    full = sample(logits.reshape(b * s, vocab), rng_full,
                  jnp.repeat(temperature, s), jnp.repeat(top_k, s),
                  jnp.repeat(top_p, s), max_top_k).reshape(b, s)

    vlogits = logits[:, :-1].reshape(b * (s - 1), vocab)
    vdraft = drafts.reshape(b * (s - 1)).astype(jnp.int32)
    vt = jnp.repeat(temperature, s - 1)
    vk = jnp.repeat(top_k, s - 1)
    vp = jnp.repeat(top_p, s - 1)
    p_draft, resid = _filtered_draft_stats(vlogits, vdraft, rng_resid,
                                           vt, vk, vp, max_top_k)
    greedy = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
    u = jax.random.uniform(rng_accept, p_draft.shape)
    accept = jnp.where(vt <= 0.0, vdraft == greedy, u < p_draft)
    resid = jnp.where(vt <= 0.0, greedy, resid)
    return (accept.reshape(b, s - 1), resid.reshape(b, s - 1),
            full.astype(jnp.int32))
