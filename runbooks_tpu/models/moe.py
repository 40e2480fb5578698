"""Mixture-of-Experts layer: dropless, and told which experts it holds.

One layer for every sparse model (docs/sparse-latent-models.md). A token
is scored over ALL ``moe_num_experts`` experts, ``moe_top_k`` of them are
chosen, and this layer computes

    sum over e in (chosen AND held here) of  g_e * Expert_e(x)

for the contiguous range of experts whose weights it was given: the
leading axis of ``p["wi_gate"]`` is the experts HELD, ``held`` the id of
the first. What the other experts would add is computed by whoever holds
them — here, by nobody — and adds nothing. A process that holds every
expert computes the whole layer; a chip that holds one share of them
(``ModelConfig.moe_experts_held``) computes its share; under an "expert"
mesh axis the same function runs a shard (tokens replicated over that
axis) and the parts are summed. The share and expert parallelism are one
mechanism.

Dropless: no capacity, no token is dropped and a token's output does not
depend on its batch-mates. The (token, expert) assignments are put in order
of expert by a count (``_rank``: the key has held + 1 values; where an
assignment stands follows from the groups' sizes and the earlier
assignments of its own group, and no sort is made), the experts run as one
grouped (ragged) matrix product whose work follows the group sizes
(``grouped_matmul``), and the gate weights are applied on the way back to
the tokens. The assignments routed elsewhere stand last, so the held ones
are the first rows of the order, and the layer moves those: a cached
forward that holds a share of the experts gathers, runs and brings back a
window of ``row_window`` rows at a time (what its share can expect of the
chunk's tokens x top_k, in whole row tiles) and as many windows as hold
rows — one, unless routing sends the share more than its part, so nothing
is dropped whatever the routing. Static shapes throughout. Measured on
the chip (TPU v5 lite, PERF.md section 6, PR 42; 2048 tokens, top-8, 32 of
256 experts held, ms): a stable argsort of 16 384 keys 0.03 and the
bincount beside it 0.18, the count that replaces both and the second
argsort 0.04; the assignments at a window's 2048 rows read back from the
count's tables 0.07 by gathers of the tables' rows and 0.03 less by
one-hot products (a scatter of all 16 384: 0.11); the way back as a
product with the [tokens, window] matrix of gate weights 0.17 at hidden
2048 and 0.28 at 4096, against a scatter-add of the rows 0.29 and 0.42 and
the gather of tokens x top_k rows with its sum over k 0.30 and 1.70; the
activation between the products over 2048 rows 0.06 at width 2048,
against 0.40 over 16 384.

Router kinds are config, not code paths by model name
(``ModelConfig.moe_router``):
  softmax  probabilities over all experts, the chosen k renormalised
  sigmoid  a sigmoid a score; the selection bias (``router_bias``) is
           added ONLY to choose; gate weights are the chosen scores over
           their sum (plus ``moe_router_eps`` where the model adds
           one), times ``moe_routed_scale``
The router runs in float32: near-ties decide which expert runs.

The shared expert is the dense gated MLP at width ``moe_intermediate_size
x moe_shared_experts``, added once, unscaled, by whoever calls with
``shared=True`` (one caller among the shares).

The tiles of the Pallas grouped product (megablox ``gmm``, the cached
forward on a TPU) are a function of the call (``_gmm_tiling``: the rows,
the widths, the item size), read from two sweeps on the chip (TPU v5 lite,
PERF.md section 6, PR 41). Cost model, from the kernel's text: it visits a
(group, row tile) pair once for every tile of tm rows a group touches —
V = ``tile_visits(sizes, tm)``, about rows / tm + groups — computes the
WHOLE [tm, k] x [k, n] product a visit (the store is masked) and fetches
the group's [k, n] weights again every visit. A visit costs
max(tm k n 2 / 197 TFLOP/s, k n 2 B / 819 GB/s): the MXU binds above
about 240 rows, the weights' bytes below, so a tile of 512 rows that
holds a group of 45-90 computes 512 rows for them, and a tile under 240
reads the weights as often for no cheaper visit. Beside the weights the
kernel fetches the [tm, tk] tile of the rows every visit AND k step —
unless the k axis is whole (tk = k): then consecutive visits of one row
tile (several groups inside it) repeat the block index, which issues no
DMA, and the float32 accumulator is written once and not re-read a k step.
At 256 rows a visit is near the balance of MXU and memory, so that
second stream decides. ms a call of ONE product, median of 8, the group
metadata's operations included; group sizes drawn as the cells' counters
say (about 1460 real tokens of a 2048 bucket; held experts' max over mean
1.5 / 3.4 / 7.4 / 2.1), the whole [layers x held, k, n] stack handed over:
  [rows, k] x n, groups (rows in groups)   (512,1024,1024) (256,1024,<=1024) (128,k,tn) (256,k,tn)  tn
  lfm2 gate/up [8192,2048] x 1536, 64 (5840)     1.954          1.199          0.969      0.991     768
  lfm2 down    [8192,1536] x 2048                1.958          1.211          1.001      1.012    1024
  sarvam gate/up [16384,4096] x 2048, 32 (2908)  1.932          1.456          1.253      1.257     512
  sarvam down    [16384,2048] x 4096             2.002          1.465          1.281      1.247    1024
  mimo gate/up, the same shapes, 32 (900)        1.633          1.133          1.001      1.002     512
  mimo down                                      1.663          1.137          0.960      0.977    1024
  laguna gate/up [16384,2048] x 512, 32 (1507)   0.370          0.313          0.268      0.274     512
  laguna down    [16384,512] x 2048              0.361          0.288          0.266      0.275    2048
  8 groups of 2048 rows, [16384,4096] x 2048     2.245          2.477            -        2.073     512
  the same, [16384,2048] x 4096                  2.293          2.659            -        2.098    1024
(the second column's width tiles divide the widths: 768 for 1536, where
1024 computes a half-empty second tile of n and masks a 512-deep remainder
of k in float32.) Other shapes at 256 rows, lfm2 gate/up: (1024, 1536)
1.057, (2048, 512) 1.008, (2048, 256) 1.111, (512, 1536) 1.114; sarvam
gate/up: (1024, 2048) 1.281, (4096, 256) 1.307, (2048, 1024) 1.426,
(2048, 512) 1.698; sarvam down: (256, 4096) 1.346, (1024, 2048) 1.344,
(2048, 512) 1.315; at 512 rows the best of the huge groups is
(512, 2048) 2.157 and (2048, 512) 2.152; at 64 rows a whole layer's gate
and down read 1.99 (lfm2) and 3.48 (sarvam) against 1.88 and 2.52-2.93 at
256. A decode step (one tile of all its rows; 9-32 rows in 7-25 groups)
is the hit experts' bytes whatever the tile, and the sweep cannot tell its
widths apart: lfm2 gate/up (32, 1024, 1024) 0.339 against (32, 2048, 1536)
0.334, down 0.341 / 0.330; sarvam (64, 1024, 1024) 0.403 against
(64, 4096, 512) 0.378, down 0.412 / (64, 2048, 1024) 0.407; mimo 0.351 /
0.341, 0.365 / 0.352; laguna 0.076 / 0.074, 0.072 / 0.073. In the cells
(traced runs, kernel time alone) nothing speaks for another tile there:
with k whole one pair of lfm2moe_doc on one machine read ``decode_fn/gmm``
0.1963 -> 0.2014 ms a call gate/up and 0.2015 -> 0.2024 down (a 6 MiB
weight tile's first fetch is exposed once a call of 25 visits), sarvam's
read 0.2529 / 0.2511 and 0.2510 / 0.2511, machines differ by 4 % on one
program, and one row tile has no second visit to
share its rows with. So the rule, of shapes only:
  tm  the largest of 256, 128, 64, 32, 16 that divides the rows — whatever
      a group holds: 256 beats 512 for groups of 28 rows and of 2048, and
      128 is level with it (ahead by 1-3 % in five of the eight products,
      within 0.3 % in two, behind by 3 % in one) for a quarter more
      visits, so the rows a group can expect, which the call could pass,
      decide nothing and are not passed;
  several row tiles (a prefill chunk):
  tk  k whole, or its largest divisor whose [tm, tk] tile is 2 MiB;
  tn  the widest divisor of n in whole lanes that fits the budget;
  one row tile (a decode step, a bucket of 16 tokens): width tiles of at
      most 2 MiB, (1024, 1024) in bfloat16, as every call had before.
The budget (``GMM_VMEM_BYTES``, 13 MiB of the 16 MiB of scoped VMEM, by
``_gmm_vmem_bytes``: two buffers each of the [tm, tk], [tk, tn] and
[tm, tn] tiles and the float32 accumulator): prefill lfm2 9.5 and 9.5
MiB, sarvam / mimo 13.0 and 12.0, laguna 7.0 and 8.5; a decode step 4.4
(32 rows) or 4.75 (64); the parent's (512, 1024, 1024) was 10. The
reckoning leaves out the compiler's own temporaries: (512, 4096, 256)
counts 13.0 and does not compile, (512, 768, 2048) counts 15.5 and does,
(512, 1024, 2048) counts 18 and does not — tests/test_decode_in_place.py
compiles the cells' shapes.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Tokens whose assignments are ranked and run at once. A forward that
# works all its rows (every expert held, no cache) gathers tokens x top_k
# rows of the layer's input; a [8, 2048] prefill at top-8 would gather a
# gigabyte a layer.
TOKEN_CHUNK = 2048


def route(cfg, p, xt: jax.Array):
    """xt [T, h] -> (scores [T, E] f32, chosen ids [T, k] int32, gate
    weights [T, k] f32, the weights already scaled)."""
    logits = jnp.einsum("th,he->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores
        if "router_bias" in p:
            # The bias moves the CHOICE only (load balancing without an
            # auxiliary loss); the weights are the unbiased scores.
            choose = scores + p["router_bias"].astype(jnp.float32)
    else:
        scores = choose = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(choose, cfg.moe_top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    total = gate.sum(-1, keepdims=True)
    gate = gate / (total + cfg.moe_router_eps if cfg.moe_router_eps
                   else jnp.maximum(total, 1e-9))
    return scores, idx.astype(jnp.int32), gate * cfg.moe_routed_scale


def _balance_loss(cfg, scores, idx):
    """Switch load-balance loss: E * sum_e mean_score_e * mean_first_e
    (first-choice assignment fraction), minimized by uniform routing."""
    E = cfg.moe_num_experts
    probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(idx[:, 0], E, dtype=probs.dtype).mean(axis=0)
    return (E * (me * ce).sum()).astype(jnp.float32)


# What the tiles of one grouped product may take of the 16 MiB of scoped
# VMEM by _gmm_vmem_bytes, which counts the pipeline's buffers and not the
# compiler's temporaries: 13 MiB is the most that compiled and ran on the
# chip ((256, 4096, 512); 15.5 ran at a narrow row tile, 18 did not), and
# tests/test_decode_in_place.py holds the cells' shapes to the compiler.
GMM_VMEM_BYTES = 13 * 2**20
# Rows of a tile: the most that divide the call's rows, up to the chip's
# balance point (module docstring).
GMM_ROW_TILES = (256, 128, 64, 32, 16)


def tile_visits(sizes, tm: int) -> int:
    """(group, row tile) pairs the grouped product visits: the V of the
    cost model. A group of `sizes` (rows sorted by group, no gap between
    groups) is visited once for every tile of tm rows it touches."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    tiles = -(-ends // tm) - (ends - sizes) // tm
    return int(tiles[sizes > 0].sum())


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Two buffers each of a [tm, tk] and a [tk, tn] operand tile and of a
    [tm, tn] result tile, and the float32 accumulator."""
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4


def _gmm_tiling(m: int, k: int, n: int, itemsize: int = 2):
    """(tm, tk, tn) for the Pallas grouped matmul from the call's shapes,
    or None where its tiles do not divide the problem (tm must divide the
    rows, the widths are whole lanes). The rule of the module docstring's
    sweep: the row tile at the balance point whatever a group holds; where
    that leaves several row tiles (a prefill chunk) the k axis whole (up to
    a 2 MiB row tile), then the widest tile of n that divides it inside
    GMM_VMEM_BYTES; where all the rows are one tile (a decode step) width
    tiles of 2 MiB, which keep the pipeline's first fetch short."""
    tm = next((t for t in GMM_ROW_TILES if m % t == 0), None)
    if tm is None or k % 128 or n % 128:
        return None
    if tm == m:
        return tm, min(k, 2048 // itemsize), min(n, 1024)

    def divisors(width):
        return (t for t in range(width, 0, -128) if width % t == 0)

    tk = next(t for t in divisors(k) if tm * t * itemsize <= 2 * 2**20)
    tn = next(t for t in divisors(n)
              if t == 128 or _gmm_vmem_bytes(tm, tk, t, itemsize)
              <= GMM_VMEM_BYTES)
    return tm, tk, tn


def gmm_tilings(cfg, tokens: int) -> dict:
    """{"gate_up": [tm, tk, tn], "down": [tm, tk, tn]}: the tiles the
    grouped products of a sparse layer compile with in a cached forward
    over `tokens` tokens, by the chooser grouped_matmul asks; {} where they
    run as `jax.lax.ragged_dot` (off the TPU, shapes no tile divides).
    Static per compiled program, so the engine publishes it."""
    from runbooks_tpu.utils.hw import on_tpu

    _, rows = chunk_window(cfg, tokens)
    itemsize = jnp.dtype(cfg.activation_dtype).itemsize
    h, f = cfg.hidden_size, cfg.moe_width
    tiles = {"gate_up": _gmm_tiling(rows, h, f, itemsize),
             "down": _gmm_tiling(rows, f, h, itemsize)}
    if not on_tpu() or None in tiles.values():
        return {}
    return {name: list(tile) for name, tile in tiles.items()}


def grouped_matmul(lhs, w, sizes, layer=None):
    """lhs [m, k], rows sorted by group, times each group's matrix ->
    [m, n] in lhs's dtype, float32 accumulation. Rows past the groups'
    total belong to no group: nothing is computed for them and what they
    hold is unspecified.

    w [groups, k, n] with sizes [groups]: `jax.lax.ragged_dot` (portable,
    differentiable, partitionable). w [layers, groups, k, n] with `layer`
    (the cached, serving forward): the WHOLE stack is handed over and the
    layer's groups are named by their sizes among zeros — a layer's slice
    of the stack is never cut out. Cut out inside a layer scan, it is a
    copy of every held expert's weights a layer and step, whichever
    experts the step hits (measured: 20 of a 27 ms decode step, PERF.md
    section 6, PR 30). On a TPU that case runs the Pallas grouped matmul
    (megablox), which visits only the (group, row tile) pairs that hold
    rows and keeps the program's scope names, which the compiler's own
    ragged dot loses."""
    w = w.astype(lhs.dtype)
    if layer is not None:
        from runbooks_tpu.utils.hw import on_tpu

        n_layers, n_groups = w.shape[:2]
        w = w.reshape((n_layers * n_groups,) + w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(n_layers * n_groups, jnp.int32), sizes,
            (layer * n_groups,))
        tiling = _gmm_tiling(lhs.shape[0], *w.shape[1:], lhs.dtype.itemsize)
        if on_tpu() and tiling is not None:
            from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

            return gmm(lhs, w, sizes, preferred_element_type=lhs.dtype,
                       tiling=tiling)
    return jax.lax.ragged_dot(
        lhs, w, sizes, preferred_element_type=jnp.float32).astype(lhs.dtype)


# Assignments ranked a block at a time: one product with a triangle of ones
# counts, for every assignment of a block, the block-mates before it by
# group; a running sum over the blocks' totals does the rest.
RANK_BLOCK = 128


def row_window(assignments: int, n_held: int, n_experts: int) -> int:
    """Rows of the order by expert that a cached forward sends to the held
    experts, and brings back, at once: what `n_held` of `n_experts` experts
    can expect of a chunk's `assignments` (tokens x top_k), in whole row
    tiles of the grouped product. A chunk whose held rows pass it takes a
    second window (_held_part), so the bound drops nothing; a process that
    holds every expert, a decode step or a small bucket gets all its rows
    as one window."""
    tile = GMM_ROW_TILES[0]
    expected = -(-assignments * n_held // n_experts)
    return min(-(-expected // tile) * tile, assignments)


def chunk_window(cfg, tokens: int) -> Tuple[int, int]:
    """(assignments of a chunk, rows of a window) of a cached forward over
    `tokens` tokens by the process `cfg` describes: static per compiled
    program, so the engine publishes the window."""
    assignments = min(tokens, TOKEN_CHUNK) * cfg.moe_top_k
    return assignments, row_window(assignments, cfg.moe_experts_here,
                                   cfg.moe_num_experts)


def rows_moved(cfg, tokens: int, held) -> Tuple[int, bool]:
    """(rows of the order by expert that the sparse layers of a cached
    forward over `tokens` tokens gathered, ran through their experts and
    brought back, given the `held` assignments [layers] their held experts
    got; whether a layer's window is all the chunk's rows, whatever it
    holds). Host arithmetic on the counts a dispatch returns anyway. Exact
    for one chunk (tokens <= TOKEN_CHUNK); several chunks round up each on
    its own, and this is the least they can have moved."""
    held = np.atleast_1d(held)
    assignments, window = chunk_window(cfg, tokens)
    if window == assignments:
        return -(-tokens // TOKEN_CHUNK) * assignments * held.size, True
    return int((-(-held // window) * window).sum()), False


def _rank(group, n_groups: int):
    """group [A] int32 in [0, n_groups) -> (counts [n_groups] int32, place
    [A] int32, the tables _sources reads). place is where an assignment
    stands once they are in order of group, and of assignment (token, then
    choice) inside a group: the sizes of the groups before its own plus the
    earlier assignments of its own group. A count — the key has a few dozen
    values — and not a sort; the order it gives is the stable sort's."""
    A = group.shape[0]
    block = min(RANK_BLOCK, A)
    pad = -A % block
    # Padding joins the last group, behind every real assignment.
    blocks = jnp.pad(group, (0, pad), constant_values=n_groups - 1) \
        .reshape(-1, block)
    member = blocks[..., None] == jnp.arange(n_groups, dtype=jnp.int32)
    # 0 / 1 in bfloat16, float32 sums: exact below 2**24 assignments.
    within = jnp.einsum(
        "ij,bjg->big", jnp.tril(jnp.ones((block, block), jnp.bfloat16)),
        member.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    totals = within[:, -1]
    before = jnp.cumsum(totals, axis=0) - totals
    counts = (before[-1] + totals[-1]).astype(jnp.int32).at[-1].add(-pad)
    starts = jnp.cumsum(counts) - counts
    place = jnp.where(member, within - 1 + before[:, None]
                      + starts.astype(jnp.float32), 0).sum(-1)
    return counts, place.reshape(-1)[:A].astype(jnp.int32), \
        (blocks, before, starts)


def _sources(tables, rows):
    """The assignment that stands at each of `rows` [R] of _rank's order:
    place turned round, for these rows only and without a sort or a
    scatter. The row's group and its number inside the group follow from
    the groups' sizes; the block that holds that member from the blocks'
    running totals; the member inside the block from a count over the
    block's 128 group ids. The two tables are read by one-hot products,
    which the chip does faster than gathers of 128-wide rows. Rows past
    the last assignment give some assignment: the caller knows which rows
    are real."""
    blocks, before, starts = tables
    n_blocks, block = blocks.shape
    group = (starts[None, 1:] <= rows[:, None]).sum(-1, dtype=jnp.int32)
    nth = rows - starts[group]

    def read(rows_of, table):
        # Totals and ids pass bfloat16's 8 bits: float32, full precision.
        return jnp.dot(rows_of.astype(jnp.float32),
                       table.astype(jnp.float32),
                       precision="highest").astype(jnp.int32)

    ahead = read(group[:, None] == jnp.arange(
        starts.shape[0], dtype=jnp.int32), before.T)        # [R, blocks]
    b = (ahead <= nth[:, None]).sum(-1, dtype=jnp.int32) - 1
    there = b[:, None] == jnp.arange(n_blocks, dtype=jnp.int32)
    nth = nth - jnp.where(there, ahead, 0).sum(-1)
    ids = read(there, blocks)                               # [R, block]
    upto = jnp.dot((ids == group[:, None]).astype(jnp.bfloat16),
                   jnp.triu(jnp.ones((block, block), jnp.bfloat16)),
                   preferred_element_type=jnp.float32)
    inside = (upto <= nth[:, None].astype(jnp.float32)).sum(
        -1, dtype=jnp.int32)
    return b * block + jnp.minimum(inside, block - 1)


def _held_part(cfg, p, xt, idx, gate, first, layer=None):
    """The held experts' part of the sum for tokens xt [T, h] ->
    (y [T, h] in the activation dtype, counts [held + 1] int32: the
    assignments each held expert got, and last those routed elsewhere).
    `layer`: see grouped_matmul (the expert weights are then stacks).

    The assignments are put in order of expert by _rank. A cached forward
    (`layer` given) that holds a share of the experts then works a window
    of row_window rows of that order at a time — the held assignments are
    its first rows — and as many windows as hold them: one, unless routing
    sends this share more than its part. The forward without a cache,
    which is differentiated, and a forward whose window is all its rows
    work all tokens x top_k rows at once."""
    from runbooks_tpu.models.transformer import _activation

    ad = cfg.activation_dtype
    T, k = idx.shape
    n_held = p["wi_gate"].shape[0 if layer is None else 1]
    window = T * k if layer is None else \
        row_window(T * k, n_held, cfg.moe_num_experts)
    xt = xt.astype(ad)

    def experts(xs, sizes):
        with jax.named_scope("moe.experts"):
            def grouped(lhs, w):
                return grouped_matmul(lhs, w, sizes, layer)

            hidden = _activation(cfg, grouped(xs, p["wi_gate"])) \
                * grouped(xs, p["wi_up"])
            return grouped(hidden, p["wo"])

    with jax.named_scope("moe.sort"):
        local = idx.reshape(-1) - first
        here = (local >= 0) & (local < n_held)
        # Routed elsewhere: one group past the held ones, so last.
        group = jnp.where(here, local, n_held)
        counts, place, tables = _rank(group, n_held + 1)
        sizes = counts[:n_held]

    if window == T * k:
        with jax.named_scope("moe.sort"):
            source = _sources(tables, jnp.arange(T * k, dtype=jnp.int32))
            xs = xt[source // k]
        out = experts(xs, sizes)
        with jax.named_scope("moe.combine"):
            # Back to token order, then the gate-weighted sum over a
            # token's k assignments (float32 accumulation). Rows past the
            # held groups hold nothing anybody computed: they are dropped
            # by selection, never by a multiplication with 0.
            rows = out[place].reshape(T, k, -1)
            held_here = here.reshape(T, k)
            y = jnp.einsum(
                "tkh,tk->th", jnp.where(held_here[..., None], rows, 0),
                jnp.where(held_here, gate, 0.0).astype(ad),
                preferred_element_type=jnp.float32).astype(ad)
        return y, counts

    total = sizes.sum()
    starts = tables[2][:n_held]
    weights = gate.reshape(-1).astype(ad)

    def one_window(i, y):
        lo = i * window
        with jax.named_scope("moe.sort"):
            rows = lo + jnp.arange(window, dtype=jnp.int32)
            source = _sources(tables, rows)
            token = source // k
            xs = xt[token]
            # A group that lies across two windows is run in two parts.
            inside = jnp.clip(starts + sizes, lo, lo + window) \
                - jnp.clip(starts, lo, lo + window)
        out = experts(xs, inside)
        with jax.named_scope("moe.combine"):
            # Every row adds its gate weight times what its expert made
            # of it to its token's sum: a product with the [T, window]
            # matrix that holds a row's weight at its token, float32
            # accumulation, a token's rows in order of expert whoever its
            # batch-mates are. Rows past the held ones hold nothing
            # anybody computed: selected away first, never multiplied.
            out = jnp.where((rows < total)[:, None], out, 0)
            spread = jnp.where(
                token == jnp.arange(T, dtype=jnp.int32)[:, None],
                weights[source], 0)
            return y + jnp.dot(spread, out,
                               preferred_element_type=jnp.float32)

    y = jax.lax.fori_loop(0, -(-total // window), one_window,
                          jnp.zeros(xt.shape, jnp.float32))
    return y.astype(ad), counts


def _held_part_chunked(cfg, p, xt, idx, gate, first, layer=None):
    """_held_part, TOKEN_CHUNK tokens at a time (one chunk: as it is)."""
    T, k = idx.shape
    if T <= TOKEN_CHUNK:
        return _held_part(cfg, p, xt, idx, gate, first, layer)
    n = -(-T // TOKEN_CHUNK)
    pad = n * TOKEN_CHUNK - T

    def chunks(a, value=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=value)
        return a.reshape((n, TOKEN_CHUNK) + a.shape[1:])

    # Padding is routed to an expert nobody has: it reads as `elsewhere`,
    # and is taken off that count again.
    y, counts = jax.lax.map(
        lambda c: _held_part(cfg, p, *c, first, layer),
        (chunks(xt), chunks(idx, cfg.moe_num_experts), chunks(gate)))
    counts = counts.sum(axis=0).at[-1].add(-pad * k)
    return y.reshape(n * TOKEN_CHUNK, -1)[:T], counts


def _expert_axis_size() -> int:
    from runbooks_tpu.parallel.sharding import _current_mesh

    mesh = _current_mesh()
    return int(mesh.shape.get("expert", 1)) if mesh is not None else 1


def moe_block(cfg, p, x: jax.Array, held: Optional[int] = None,
              shared: bool = True, token_mask: Optional[jax.Array] = None,
              layer=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sparse FFN over x [b, s, h] -> (out [b, s, h], aux loss scalar,
    counts [experts held + 1] int32: assignments by held expert, and last
    the assignments routed to experts held elsewhere).

    ``held``: id of the first expert whose weights ``p`` holds (default
    ``cfg.moe_experts_first``); how many it holds is the leading axis of
    the expert weights. ``token_mask`` [b, s] bool: tokens that are nobody's
    (a bucket's padding, a parked decode row) are routed to no expert,
    cost nothing in the grouped product and are in no count. ``layer``:
    the expert matrices in ``p`` are whole stacks [layers, held, …] and
    this is the layer's index in them (grouped_matmul)."""
    from runbooks_tpu.models.transformer import _mlp_block

    b, s, h = x.shape
    xt = x.reshape(b * s, h)
    first = cfg.moe_experts_first if held is None else held
    with jax.named_scope("moe.router"):
        scores, idx, gate = route(cfg, p, xt)
        aux = _balance_loss(cfg, scores, idx)
        unreal = 0
        if token_mask is not None:
            real = token_mask.reshape(b * s)
            # An expert nobody has: sorted last with `elsewhere`, and
            # taken off that count below.
            idx = jnp.where(real[:, None], idx, cfg.moe_num_experts)
            unreal = (real.size - real.sum(dtype=jnp.int32)) * cfg.moe_top_k

    ep = _expert_axis_size()
    n_held = p["wi_gate"].shape[0 if layer is None else 1]
    if layer is None and ep > 1 and n_held % ep == 0:
        # Expert parallelism: every shard of the "expert" axis holds
        # n_held / ep experts, sees every token, and computes its part.
        from jax.sharding import PartitionSpec as P

        from runbooks_tpu.parallel.sharding import _current_mesh

        experts = {k_: p[k_] for k_ in ("wi_gate", "wi_up", "wo")}

        def shard(w, mine, xt_, idx_, gate_):
            y_, counts_ = _held_part_chunked(cfg, w, xt_, idx_, gate_,
                                             mine[0])
            return jax.lax.psum(y_.astype(jnp.float32), "expert"), \
                counts_[:-1]

        # Each shard is TOLD the id of its first expert (an operand split
        # over the axis): lax.axis_index would have to be lowered over
        # every other mesh axis, one of which the pipeline already holds.
        starts = first + jnp.arange(ep, dtype=jnp.int32) * (n_held // ep)
        y, held_counts = jax.shard_map(
            shard, mesh=_current_mesh(), axis_names={"expert"},
            in_specs=(P("expert"), P("expert"), P(), P(), P()),
            out_specs=(P(), P("expert")), check_vma=False)(
                experts, starts, xt, idx, gate)
        y = y.astype(cfg.activation_dtype)
        counts = jnp.concatenate(
            [held_counts, (idx.size - held_counts.sum())[None]])
    else:
        y, counts = _held_part_chunked(cfg, p, xt, idx, gate, first, layer)
    if token_mask is not None:
        counts = counts.at[-1].add(-unreal)
    y = y.reshape(b, s, h)
    if shared and "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + _mlp_block(cfg, p["shared"], x)
    return y, aux, counts


def moe_logical_axes(cfg):
    """Logical axes for the stacked [L, ...] MoE params."""
    axes = {
        "router": ("layers", "embed", None),
        "wi_gate": ("layers", "experts", "embed", "mlp"),
        "wi_up": ("layers", "experts", "embed", "mlp"),
        "wo": ("layers", "experts", "mlp", "embed"),
    }
    if cfg.moe_router_bias:
        axes["router_bias"] = ("layers", None)
    if cfg.moe_shared_experts:
        axes["shared"] = {"wo": ("layers", "mlp", "embed"),
                          "wi_gate": ("layers", "embed", "mlp"),
                          "wi_up": ("layers", "embed", "mlp")}
    return axes
