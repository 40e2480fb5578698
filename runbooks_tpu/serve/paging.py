"""Paged KV cache with radix-tree prefix sharing for the serve engine.

The dense engine (serve/engine.py) reserves a whole ``[max_seq_len+1]``
cache row per slot, so concurrency is fixed by worst-case sequence length
and a shared system prompt is stored once per slot. This module breaks the
cache into fixed-size pages and shares physical pages between requests:

- **Page pool** (``PagePool`` + ``PageAllocator``): K/V live in
  ``[layers, num_pages+1, page_size, kv_heads, head_dim]`` arrays (the
  last page is the trash page — the scatter target for padding, never
  allocated). A host-side free list hands out pages; refcounts track how
  many owners (slots, the radix tree) hold each page.
- **Radix tree** (``RadixTree``): a host-side trie over token ids at page
  granularity — each edge is exactly ``page_size`` tokens and each node
  owns the physical page holding that span's K/V. Admission matches the
  longest registered prefix and maps the slot's leading page-table
  entries to the *same* physical pages; finished requests adopt their
  fully-written pages into the tree, so every served prompt seeds reuse
  for the next one (the many-user generalization of the dense engine's
  single-prefix ``auto_prefix``). Unreferenced prefix pages evict LRU
  under page pressure.
- **Copy-on-write by construction**: shared pages hold only *complete*
  pages of prompt prefix, and decode writes land at positions at or past
  the prompt length — always in the slot's private pages. Two requests
  sharing a prefix therefore diverge mid-generation without ever copying
  a page or corrupting each other (tests/test_paging.py proves it). The
  partial page at a prefix boundary is never shared; its tokens prefill
  into the slot's first private page.
- **Static shapes**: the compiled programs see a fixed page count, a
  fixed ``[rows, max_pages_per_slot]`` int32 page-table operand, and
  bucketed prefix-page counts (powers of two), so the program census
  stays small and the compile sentinel stays quiet after warmup
  (``paged_prefill_shapes`` enumerates the full set — warmup, ``rbt
  check`` and the baseline all walk it).

Attention runs over a **gathered view**: decode flattens the pool to
``[layers, (num_pages+1)*page_size, ...]``, gathers each slot's pages
into a contiguous ``[slots, view, ...]`` view by flat token index, runs
the existing ``forward`` on it, and scatters each newly written token
back to its page. The gather streams the same bytes the dense view slice
would; the cost is one extra materialized copy per chunk (a fused paged
attention kernel can fold it away later — docs/paged-kv.md discusses the
tradeoff). int8 KV quantization composes: pages store int8 plus the same
per-token-per-head scales, spliced by the same quantize path.

Sizing guidance and the page-size tradeoff live in docs/paged-kv.md;
``serve_kv_pages_{free,used,shared}`` gauges (docs/observability.md)
report the pool live.
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from runbooks_tpu.models.config import ModelConfig
from runbooks_tpu.models.transformer import KVCache, forward
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.obs import metrics as obs_metrics
from runbooks_tpu.obs.trace import complete as trace_complete
from runbooks_tpu.obs.trace import record_enabled
from runbooks_tpu.ops.sampling import sample, speculative_verify
from runbooks_tpu.serve.engine import (
    PRIORITY_RANK,
    EngineStepFailed,
    InferenceEngine,
    Request,
    WarmupRun,
    advance_rows,
    view_buckets_for,
)

Params = Any


# ---------------------------------------------------------------------------
# Page pool
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagePool:
    """Device-side paged KV storage.

    k, v: [num_layers, num_pages + 1, page_size, num_kv_heads, head_dim]
    — page ``num_pages`` is the TRASH page: the scatter destination for
    padding rows and parked decode slots, never handed out by the
    allocator. With quantize_kv, k/v are int8 and k_scale/v_scale carry
    one f32 scale per (layer, page, slot-in-page, kv-head) — the same
    per-token-per-head granularity as the dense int8 pool, so the
    splice-quantize/dequantize-at-read path is unchanged.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @classmethod
    def create(cls, cfg: ModelConfig, num_pages: int, page_size: int,
               quantize_kv: bool = False) -> "PagePool":
        shape = (cfg.num_layers, num_pages + 1, page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        if quantize_kv:
            return cls(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(shape[:-1], jnp.float32),
                       v_scale=jnp.zeros(shape[:-1], jnp.float32))
        return cls(k=jnp.zeros(shape, cfg.activation_dtype),
                   v=jnp.zeros(shape, cfg.activation_dtype))

    @property
    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8

    @property
    def nbytes(self) -> int:
        return sum(x.nbytes for x in (self.k, self.v, self.k_scale,
                                      self.v_scale) if x is not None)


class PageAllocator:
    """Host-side free-list allocator with refcounts over a fixed page set.

    Page ids 0..num_pages-1 are allocatable. A freshly alloc'd page has
    refcount 1 (the caller's); incref/decref add and drop owners, and a
    page returns to the free list exactly when its count hits zero. All
    methods run on the engine worker thread (the engine is
    single-threaded by design); the counts read by /metrics are plain
    ints, safe to read racily.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        # pop() hands out ascending ids — deterministic tests.
        self._free = list(range(num_pages - 1, -1, -1))
        self._ref = np.zeros(num_pages, np.int64)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self._free)

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages (refcount 1 each), or None — all-or-nothing, so
        a half-admitted request can never hold pages it cannot use."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, pages) -> None:
        for p in pages:
            if self._ref[p] <= 0:
                raise RuntimeError(f"incref of free page {p}")
            self._ref[p] += 1

    def decref(self, pages) -> List[int]:
        """Drop one reference per page; returns the pages actually freed
        (count hit zero)."""
        freed = []
        for p in pages:
            p = int(p)
            if self._ref[p] <= 0:
                raise RuntimeError(f"decref of free page {p}")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
                freed.append(p)
        return freed


# ---------------------------------------------------------------------------
# Host swap tier (docs/paged-kv.md "Host tier")
# ---------------------------------------------------------------------------

class HostPagePool:
    """Host-RAM staging tier under the device page pool.

    When the radix tree must evict an HBM page, the page's K/V copies
    into one of these preallocated host buffers instead of dropping —
    the node survives as *host-resident* and a later admission that
    matches it swaps the page back into HBM (`device_put`-class cost)
    instead of recomputing the prefix from scratch. Buffers are plain
    pinned numpy arrays, allocated ONCE at construction: steady-state
    swap traffic does zero host allocation, and the arrays' dtype is
    exactly the device pool's (int8 + f32 scales when quantized,
    activation dtype otherwise) so a swap round-trip is bit-identical.

    Single-threaded like the engine that owns it (all mutation happens
    on the serving thread); the ints /metrics reads are safe racily.
    Sizing guidance (`kv_host_pages` from host-RAM headroom) lives in
    docs/paged-kv.md.
    """

    def __init__(self, cfg: ModelConfig, host_pages: int, page_size: int,
                 quantize_kv: bool = False):
        if host_pages < 1:
            raise ValueError(
                f"kv_host_pages must be >= 1 to enable the host tier, "
                f"got {host_pages}")
        self.num_pages = int(host_pages)
        self.page_size = int(page_size)
        self.quantized = bool(quantize_kv)
        dtype = np.dtype(jnp.int8 if quantize_kv
                         else cfg.activation_dtype)
        shape = (self.num_pages, cfg.num_layers, self.page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.k = np.zeros(shape, dtype)
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.v = np.zeros(shape, dtype)
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.k_scale = (np.zeros(shape[:-1], np.float32)
                        if quantize_kv else None)
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.v_scale = (np.zeros(shape[:-1], np.float32)
                        if quantize_kv else None)
        # pop() hands out ascending ids — deterministic tests.
        # guarded-by: engine worker thread (single-threaded serving loop)
        self._free = list(range(self.num_pages - 1, -1, -1))
        # guarded-by: engine worker thread (single-threaded serving loop)
        self._used = np.zeros(self.num_pages, bool)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in (self.k, self.v, self.k_scale,
                                           self.v_scale) if x is not None)

    @property
    def bytes_per_page(self) -> int:
        return self.nbytes // self.num_pages

    def alloc(self) -> Optional[int]:
        """One free host slot, or None — the caller decides whether to
        make room (RadixTree.evict_host) or degrade to dropping."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._used[slot] = True
        return slot

    def free(self, slot: int) -> None:
        if not self._used[slot]:
            raise RuntimeError(f"free of unallocated host page {slot}")
        self._used[slot] = False
        self._free.append(slot)

    def store(self, slot: int, k, v, k_scale=None, v_scale=None) -> None:
        """Copy one page's K/V (shape [layers, page_size, kv_heads,
        head_dim], already pulled to host) into the slot's buffer."""
        if not self._used[slot]:
            raise RuntimeError(f"store to unallocated host page {slot}")
        self.k[slot] = k
        self.v[slot] = v
        if self.quantized:
            self.k_scale[slot] = k_scale
            self.v_scale[slot] = v_scale

    def load(self, slot: int) -> tuple:
        """The slot's page payload, as the operand tuple the swap-in
        program takes (scales included exactly when quantized)."""
        if not self._used[slot]:
            raise RuntimeError(f"load of unallocated host page {slot}")
        if self.quantized:
            return (self.k[slot], self.v[slot],
                    self.k_scale[slot], self.v_scale[slot])
        return (self.k[slot], self.v[slot])


# ---------------------------------------------------------------------------
# Radix tree over token prefixes (page granularity)
# ---------------------------------------------------------------------------

class _RadixNode:
    __slots__ = ("children", "page", "parent", "edge", "last_used",
                 "host_slot")

    def __init__(self, parent=None, edge=None, page: int = -1):
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.page = page
        self.parent = parent
        self.edge = edge
        self.last_used = 0
        # >= 0: the page's K/V live in the host tier (page is then -1).
        # A node owns exactly one residency — HBM page, host slot, or
        # neither (namespace stubs only).
        self.host_slot = -1


class RadixTree:
    """Trie over token-id sequences at page granularity.

    Each edge is a tuple of exactly ``page_size`` token ids; the child
    node owns the physical page holding that span's K/V. The tree itself
    holds one allocator reference per adopted page (so a page shared by
    the tree and two slots has refcount 3); ``evict`` drops LRU leaves
    whose pages nobody but the tree references. Only *complete* pages
    are ever inserted — a prefix ending mid-page shares its full pages
    and recomputes the partial tail (copy-on-write by construction; see
    the module docstring).
    """

    def __init__(self, page_size: int, allocator: PageAllocator):
        self.page_size = page_size
        self.allocator = allocator
        self.root = _RadixNode()
        self.nodes = 0            # HBM pages currently owned by the tree
        self.pages_evicted = 0    # cumulative HBM evictions (observability)
        self._clock = 0           # logical LRU clock (match/insert ticks)
        # Host swap tier, wired by the paged engine when kv_host_pages
        # > 0 (PagedInferenceEngine._wire_host_tier). None = eviction
        # drops pages, the pre-host-tier behavior.
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.host: Optional[HostPagePool] = None
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.swap_out = None  # engine callback: page -> Optional[host slot]
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.host_nodes = 0          # nodes resident only in the host tier
        self.pages_swapped_out = 0   # cumulative HBM -> host demotions
        self.pages_swap_dropped = 0  # evictions that found no host room
        self.host_pages_evicted = 0  # host-tier LRU drops (evict_host)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _root_for(self, ns, create: bool = False):
        """Per-namespace subtree root. Namespaces isolate ADAPTERS
        (docs/multi-tenant-lora.md): the same prompt tokens produce
        different K/V under different LoRA adapters, so cross-tenant
        page sharing would serve one tenant another's cache. The
        namespace edge is a ("__adapter__", name) tuple — token edges
        are all-int tuples, so no collision is possible. Stub nodes own
        no page (page = -1) and are skipped by eviction; their count is
        bounded by distinct adapters ever served."""
        if ns is None:
            return self.root
        key = ("__adapter__", ns)
        node = self.root.children.get(key)
        if node is None and create:
            node = _RadixNode(parent=self.root, edge=key, page=-1)
            self.root.children[key] = node
        return node

    def match_nodes(self, tokens, ns=None) -> List["_RadixNode"]:
        """Nodes for the longest full-page prefix of ``tokens`` present
        in EITHER tier — HBM (page >= 0) or host-resident (host_slot >=
        0) — within the ``ns`` adapter namespace. Refreshes LRU recency
        on the matched path (in both tiers: a matched host node is the
        one evict_host must NOT drop). Does NOT take references — the
        caller commits via PagedKVManager.admit, which pins HBM matches
        and promotes host ones."""
        ps = self.page_size
        node = self._root_for(ns)
        if node is None:
            return []
        out: List[_RadixNode] = []
        now = self._tick()
        for i in range(len(tokens) // ps):
            child = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            child.last_used = now
            out.append(child)
            node = child
        return out

    def match(self, tokens, ns=None) -> List[int]:
        """Per-node page ids for the longest matched prefix (host-
        resident nodes report -1: resident, but not yet in HBM). Length
        is what prefix-presence callers (has_prefix, register_prefix)
        care about; admission uses match_nodes directly."""
        return [n.page for n in self.match_nodes(tokens, ns=ns)]

    def insert(self, tokens, pages, ns=None) -> int:
        """Adopt ``pages[i]`` as the shared page for the i-th full page
        of ``tokens``, for every position not already in the tree (the
        tree increfs adopted pages; an existing node keeps its page and
        the caller's duplicate stays private — it frees with the slot).
        Returns the number of pages adopted."""
        ps = self.page_size
        node = self._root_for(ns, create=True)
        adopted = 0
        now = self._tick()
        for i in range(min(len(tokens) // ps, len(pages))):
            edge = tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
            child = node.children.get(edge)
            if child is None:
                child = _RadixNode(parent=node, edge=edge,
                                   page=int(pages[i]))
                node.children[edge] = child
                self.allocator.incref([child.page])
                self.nodes += 1
                adopted += 1
            elif child.page < 0 and child.host_slot >= 0:
                # Free promotion: the releasing slot just held this very
                # span's K/V in HBM (same tokens, same namespace, so the
                # bytes are identical by construction) — adopt its page
                # and retire the host copy, skipping a future swap-in.
                child.page = int(pages[i])
                self.allocator.incref([child.page])
                if self.host is not None:
                    self.host.free(child.host_slot)
                child.host_slot = -1
                self.host_nodes -= 1
                self.nodes += 1
                adopted += 1
            child.last_used = now
            node = child
        return adopted

    def _resident_flags(self):
        """(order, hbm_desc): every node in parent-before-child order,
        and per node whether any STRICT descendant holds an HBM page.
        One linear walk — eviction candidacy in both tiers keys on it
        (a node with HBM descendants cannot leave the tree: dropping it
        would orphan the descendants' tree references)."""
        order: List[_RadixNode] = []
        stack = [self.root]
        while stack:
            n = stack.pop()
            order.append(n)
            stack.extend(n.children.values())
        hbm_desc: Dict[int, bool] = {}
        for n in reversed(order):   # children before parents
            hbm_desc[id(n)] = any(c.page >= 0 or hbm_desc[id(c)]
                                  for c in n.children.values())
        return order, hbm_desc

    def _has_hbm_descendant(self, node: _RadixNode) -> bool:
        stack = list(node.children.values())
        while stack:
            c = stack.pop()
            if c.page >= 0:
                return True
            stack.extend(c.children.values())
        return False

    def _drop_subtree(self, v: _RadixNode) -> int:
        """Unlink ``v`` and its whole subtree, dropping the tree's
        ownership of every page in it: HBM pages decref (a slot still
        sharing one keeps it alive — only the tree's reference goes),
        host slots free. Returns host slots freed."""
        del v.parent.children[v.edge]
        host_freed = 0
        stack = [v]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            n.children = {}
            if n.page >= 0:
                self.allocator.decref([n.page])
                self.nodes -= 1
                n.page = -1
            if n.host_slot >= 0:
                self.host.free(n.host_slot)
                n.host_slot = -1
                self.host_nodes -= 1
                host_freed += 1
        return host_freed

    def evict(self, want: int) -> int:
        """Free up to ``want`` HBM pages from least-recently-used
        eviction candidates: nodes whose page only the tree references
        (allocator refcount == 1) and with no HBM-resident strict
        descendant — the generalization of "leaf" once host-resident
        interior nodes can grow fresh HBM children beneath them (it
        degenerates to exactly the old leaf rule when no host tier is
        configured). With a host tier, a victim's page COPIES to a host
        buffer via the engine's swap_out callback and the node survives
        as host-resident (a later admission swaps it back in); without
        one — or when the copy fails (swapfail fault) or the host tier
        stays full after its own LRU pass — the node and its host-only
        subtree drop. Freeing a victim can expose its parent as the
        next candidate; the parent joins the same LRU heap instead of
        re-walking the tree per round. Returns HBM pages freed."""
        order, hbm_desc = self._resident_flags()
        heap = [(n.last_used, id(n), n) for n in order
                if n.page >= 0 and not hbm_desc[id(n)]
                and self.allocator.refcount(n.page) == 1]
        heapq.heapify(heap)
        freed = 0
        while heap and freed < want:
            _, _, v = heapq.heappop(heap)
            page = v.page
            slot = None
            if self.host is not None and self.swap_out is not None:
                slot = self.swap_out(page)
            if slot is not None:
                # Demote: the HBM page frees, the node lives on pointing
                # at its host copy, and its subtree stays matchable.
                self.allocator.decref([page])
                self.nodes -= 1
                v.page = -1
                v.host_slot = int(slot)
                self.host_nodes += 1
                self.pages_swapped_out += 1
            else:
                if self.host is not None:
                    self.pages_swap_dropped += 1
                self._drop_subtree(v)
            freed += 1
            p = v.parent
            # Refcounts can't move under us (eviction runs on the single
            # serving thread), so a pinned parent is skipped for good —
            # exactly the pin-before-evict contract _admit relies on.
            # Namespace stubs (page < 0) never enter the heap.
            if (p is not self.root and p.page >= 0
                    and self.allocator.refcount(p.page) == 1
                    and not self._has_hbm_descendant(p)):
                heapq.heappush(heap, (p.last_used, id(p), p))
        self.pages_evicted += freed
        return freed

    def evict_host(self, want: int) -> int:
        """Make room in the HOST tier: drop up to ``want`` host slots
        from least-recently-used host-resident nodes with no HBM
        descendant (their subtrees are host-only, so dropping leaks
        nothing). Called by the engine's swap_out callback when the
        host pool is full — the returning-session bet is freshness-
        weighted at both tiers. Returns host slots freed."""
        if self.host is None or want < 1:
            return 0
        order, hbm_desc = self._resident_flags()
        heap = [(n.last_used, id(n), n) for n in order
                if n.host_slot >= 0 and not hbm_desc[id(n)]]
        heapq.heapify(heap)
        freed = 0
        while heap and freed < want:
            _, _, v = heapq.heappop(heap)
            if v.host_slot < 0:
                continue   # freed by an earlier victim's subtree drop
            freed += self._drop_subtree(v)
        self.host_pages_evicted += freed
        return freed


# ---------------------------------------------------------------------------
# Bucketing helpers (shared by the engine, warmup, and `rbt check`)
# ---------------------------------------------------------------------------

def prefix_page_buckets(max_pages_per_slot: int) -> List[int]:
    """The static prefix-page-count buckets the splice programs compile
    at: powers of two up to (and always including) max_pages_per_slot.
    A bounded set keeps the program census a budget — an arbitrary
    per-prompt shared-page count would mint a fresh XLA program per
    distinct prefix length (the dense engine's auto_prefix quantization,
    one level up)."""
    out, b = [], 1
    while b < max_pages_per_slot:
        out.append(b)
        b *= 2
    out.append(max_pages_per_slot)
    return out


def page_bucket(n_pages: int, max_pages_per_slot: int) -> int:
    """Smallest prefix-page bucket covering n_pages (0 stays 0)."""
    if n_pages <= 0:
        return 0
    for b in prefix_page_buckets(max_pages_per_slot):
        if n_pages <= b:
            return b
    return max_pages_per_slot


def view_page_buckets_for(max_seq_len: int, page_size: int) -> List[int]:
    """Decode view buckets in PAGES: the dense engine's token views
    (view_buckets_for) rounded up to whole pages."""
    return sorted({-(-v // page_size)
                   for v in view_buckets_for(max_seq_len)})


def paged_prefill_shapes(prefill_buckets: List[int],
                         max_pages_per_slot: int, page_size: int,
                         max_seq_len: int) -> List[Tuple[int, int]]:
    """Every reachable (suffix bucket, prefix-page bucket) combination —
    the paged prefill program census. A combination is reachable when
    some prompt can land in it: the smallest shared-page count mapping
    to the bucket leaves room inside the context window for a suffix
    that maps to the suffix bucket. Warmup compiles exactly this set;
    `rbt check` audits the same enumeration (program-census-drift)."""
    ppbs = prefix_page_buckets(max_pages_per_slot)
    shapes: List[Tuple[int, int]] = []
    for ppb in [0] + ppbs:
        if ppb == 0:
            m_min = 0
        else:
            idx = ppbs.index(ppb)
            m_min = 1 if idx == 0 else ppbs[idx - 1] + 1
        max_suffix = max_seq_len - m_min * page_size
        if max_suffix < 1:
            continue
        for i, b in enumerate(prefill_buckets):
            s_min = prefill_buckets[i - 1] + 1 if i else 1
            if s_min <= max_suffix:
                shapes.append((b, ppb))
    return shapes


# ---------------------------------------------------------------------------
# Jitted program bodies (module-level factories — audited by `rbt check`
# exactly like the dense engine's; runbooks_tpu/analysis/program.py traces
# these same bodies abstractly).
# ---------------------------------------------------------------------------

def make_paged_prefill_fn(cfg: ModelConfig, cache_len: int,
                          page_size: int, num_pages: int):
    """Batched paged prefill + first-token sample, one dispatch per
    admission group. Rows prefill into fresh scratch rows (exactly the
    dense prefill's discipline); a shared prefix is GATHERED from its
    physical pages into positions [0, prefix_len) of each scratch row
    first, and afterwards only the SUFFIX tokens scatter back out to the
    row's private pages — shared pages are never written. The program is
    keyed on (rows, suffix bucket, prefix-page bucket) shapes; padding
    rows and pad tokens scatter harmlessly to the trash page."""
    n_flat = (num_pages + 1) * page_size
    trash_flat = num_pages * page_size      # token 0 of the trash page
    scratch_trash = cache_len - 1           # scratch rows' trash slot
    L, kvh, d = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim

    def paged_prefill_fn(params, pool, tokens, positions, dest_pages,
                         last_pos, rng, temps, top_ks, top_ps,
                         prefix_pages=None, prefix_len=None,
                         apool=None, aslots=None, gmask=None):
        rows, _bucket = tokens.shape
        ad = cfg.activation_dtype
        quantized = pool.k.dtype == jnp.int8
        flat_k = pool.k.reshape(L, n_flat, kvh, d)
        flat_v = pool.v.reshape(L, n_flat, kvh, d)
        flat_ks = (pool.k_scale.reshape(L, n_flat, kvh)
                   if quantized else None)
        flat_vs = (pool.v_scale.reshape(L, n_flat, kvh)
                   if quantized else None)

        row_shape = (L, rows, cache_len, kvh, d)
        k1 = jnp.zeros(row_shape, ad)
        v1 = jnp.zeros(row_shape, ad)
        if prefix_pages is not None and prefix_pages.shape[1] > 0:
            # Gather the shared prefix out of its physical pages into
            # the scratch rows, so the suffix forward attends it exactly
            # as the dense splice path would. Pages beyond a row's real
            # prefix_len are trash-padded; their garbage scatters to the
            # scratch trash slot, which no query ever attends.
            ppw = prefix_pages.shape[1] * page_size
            t = jnp.arange(ppw, dtype=jnp.int32)
            fidx = (prefix_pages[:, t // page_size] * page_size
                    + t % page_size)                      # [rows, ppw]
            gk = flat_k[:, fidx]                  # [L, rows, ppw, kvh, d]
            gv = flat_v[:, fidx]
            if quantized:
                from runbooks_tpu.ops.quantization import dequantize_kv

                gk = dequantize_kv(gk, flat_ks[:, fidx], ad)
                gv = dequantize_kv(gv, flat_vs[:, fidx], ad)
            else:
                gk = gk.astype(ad)
                gv = gv.astype(ad)
            sp = jnp.where(t[None, :] < prefix_len[:, None],
                           t[None, :], scratch_trash)     # [rows, ppw]
            r_idx = jnp.arange(rows, dtype=jnp.int32)[:, None]
            k1 = k1.at[:, r_idx, sp].set(gk)
            v1 = v1.at[:, r_idx, sp].set(gv)
        cache1 = KVCache(k=k1, v=v1, index=jnp.zeros((), jnp.int32))
        adapters = None if apool is None else (apool, aslots)
        logits, cache1 = forward(cfg, params, tokens,
                                 positions=positions, cache=cache1,
                                 adapters=adapters)

        # Scatter the suffix K/V to the rows' private pages, by the same
        # positions operand the forward wrote them at. Pad tokens sit at
        # the scratch trash position -> routed to the trash page.
        wpos = jnp.clip(positions, 0, cache_len - 1)
        idx5 = wpos[None, :, :, None, None]
        sk = jnp.take_along_axis(cache1.k, idx5, axis=2)
        sv = jnp.take_along_axis(cache1.v, idx5, axis=2)
        if quantized:
            from runbooks_tpu.ops.quantization import quantize_kv

            sk, sks = quantize_kv(sk)
            sv, svs = quantize_kv(sv)
        valid = positions < scratch_trash
        page = jnp.take_along_axis(
            dest_pages,
            jnp.clip(wpos // page_size, 0, dest_pages.shape[1] - 1),
            axis=1)                                       # [rows, bucket]
        fi = jnp.where(valid, page * page_size + wpos % page_size,
                       trash_flat)
        flat_k = flat_k.at[:, fi].set(sk)
        flat_v = flat_v.at[:, fi].set(sv)
        if quantized:
            flat_ks = flat_ks.at[:, fi].set(sks)
            flat_vs = flat_vs.at[:, fi].set(svs)

        rng, sub = jax.random.split(rng)
        last_logits = jnp.take_along_axis(
            logits, last_pos[:, None, None], axis=1)[:, 0]
        first = sample(last_logits, sub, temps, top_ks, top_ps,
                       gmask=gmask)
        new_pool = PagePool(
            k=flat_k.reshape(pool.k.shape),
            v=flat_v.reshape(pool.v.shape),
            k_scale=(flat_ks.reshape(pool.k_scale.shape)
                     if quantized else None),
            v_scale=(flat_vs.reshape(pool.v_scale.shape)
                     if quantized else None))
        return first, new_pool, rng

    return paged_prefill_fn


def make_paged_decode_fn(cfg: ModelConfig, chunk: int, max_len: int,
                         page_size: int, view_pages: int, num_pages: int,
                         weight_layouts=None):
    """``chunk`` decode steps over paged KV in one jit call. The slots'
    pages are gathered ONCE into a contiguous [slots, view_pages*page_size
    + 1] view (last slot = view trash for parked rows); the scan attends
    the view and scatters each newly written token's K/V back to its
    physical page, so the pool is exact when the chunk returns. Liveness
    (EOS / budget / out-of-room) tracks on device exactly as the dense
    decode does (advance_rows) — the host takes (tokens, valid)
    identically, and the final carry is returned as the dense one is.
    `weight_layouts`: as make_decode_fn's."""
    n_flat = (num_pages + 1) * page_size
    trash_flat = num_pages * page_size
    V = view_pages * page_size
    L, kvh, d = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim

    def paged_decode_fn(params, pool, page_tables, tokens, positions, rng,
                        temperature, top_k, top_p, eos_ids, remaining,
                        active, apool=None, aslots=None, gmask=None):
        # gmask [B, vocab]: chunk-start allowed-token rows, same
        # first-step-exact contract as the dense decode (the host takes
        # one token per chunk for constrained slots — _take_chunk).
        B = tokens.shape[0]
        quantized = pool.k.dtype == jnp.int8
        flat_k = pool.k.reshape(L, n_flat, kvh, d)
        flat_v = pool.v.reshape(L, n_flat, kvh, d)
        flat_ks = (pool.k_scale.reshape(L, n_flat, kvh)
                   if quantized else None)
        flat_vs = (pool.v_scale.reshape(L, n_flat, kvh)
                   if quantized else None)
        t = jnp.arange(V, dtype=jnp.int32)
        fidx = (page_tables[:, t // page_size] * page_size
                + t % page_size)                             # [B, V]
        pad5 = [(0, 0), (0, 0), (0, 1), (0, 0), (0, 0)]
        view_cache = KVCache(
            k=jnp.pad(flat_k[:, fidx], pad5),
            v=jnp.pad(flat_v[:, fidx], pad5),
            index=jnp.zeros((), jnp.int32),
            k_scale=(jnp.pad(flat_ks[:, fidx], pad5[:-1])
                     if quantized else None),
            v_scale=(jnp.pad(flat_vs[:, fidx], pad5[:-1])
                     if quantized else None))
        rng, step_rng = jax.random.split(rng)
        keys = jax.random.split(step_rng, chunk)
        b_idx = jnp.arange(B, dtype=jnp.int32)
        adapters = None if apool is None else (apool, aslots)

        def body(carry, key):
            fk, fv, fks, fvs, cache, tok, pos, alive, left = carry
            p = jnp.where(alive, pos, V)   # park at the view trash slot
            logits, cache = forward(cfg, params, tok[:, None],
                                    positions=p[:, None], cache=cache,
                                    adapters=adapters,
                                    weight_layouts=weight_layouts)
            nxt = sample(logits[:, -1], key, temperature, top_k, top_p,
                         gmask=gmask)
            nxt = jnp.where(alive, nxt, tok)
            # Write-back: the token the forward just wrote at p, view ->
            # physical page. Parked rows write the trash page. Shared
            # pages are structurally out of reach: alive positions are
            # >= the prompt length, past every shared (full prompt) page.
            i4 = p[None, :, None, None]
            wk = jnp.take_along_axis(cache.k, i4[..., None], axis=2)[:, :, 0]
            wv = jnp.take_along_axis(cache.v, i4[..., None], axis=2)[:, :, 0]
            page = page_tables[
                b_idx, jnp.clip(p // page_size, 0,
                                page_tables.shape[1] - 1)]
            fi = jnp.where(alive, page * page_size + p % page_size,
                           trash_flat)
            fk = fk.at[:, fi].set(wk)
            fv = fv.at[:, fi].set(wv)
            if quantized:
                wks = jnp.take_along_axis(cache.k_scale, i4,
                                          axis=2)[:, :, 0]
                wvs = jnp.take_along_axis(cache.v_scale, i4,
                                          axis=2)[:, :, 0]
                fks = fks.at[:, fi].set(wks)
                fvs = fvs.at[:, fi].set(wvs)
            out = (nxt, alive)
            pos, alive, left = advance_rows(nxt, pos, alive, left, eos_ids,
                                            max_len)
            return (fk, fv, fks, fvs, cache, nxt, pos, alive, left), out

        init = (flat_k, flat_v, flat_ks, flat_vs, view_cache, tokens,
                positions, active, remaining)
        (fk, fv, fks, fvs, _, *carry), (toks, valid) = jax.lax.scan(
            body, init, keys)
        new_pool = PagePool(
            k=fk.reshape(pool.k.shape), v=fv.reshape(pool.v.shape),
            k_scale=(fks.reshape(pool.k_scale.shape)
                     if quantized else None),
            v_scale=(fvs.reshape(pool.v_scale.shape)
                     if quantized else None))
        return toks, valid, tuple(carry), new_pool, rng

    return paged_decode_fn


def make_paged_verify_fn(cfg: ModelConfig, draft_tokens: int,
                         page_size: int, view_pages: int, num_pages: int):
    """Speculative draft-verify over paged KV: one ``[B, K+1]`` forward
    (carry-in token + up to K drafts per slot) against the gathered
    contiguous view, with every live position's K/V scattered back to
    its physical page (docs/speculative-decoding.md). The host rolls
    back rejected tokens by not advancing the slot's in-page cursor —
    a shared page is never a write target (live positions are >= the
    prompt length, past every shared full-prompt page), so rollback can
    never touch, free, or corrupt a radix/CoW page. Verdict semantics
    are the dense ``make_verify_fn``'s exactly
    (ops/sampling.speculative_verify)."""
    K = draft_tokens
    n_flat = (num_pages + 1) * page_size
    trash_flat = num_pages * page_size
    V = view_pages * page_size
    L, kvh, d = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim

    def paged_verify_fn(params, pool, page_tables, tokens, positions,
                        draft_len, rng, temperature, top_k, top_p,
                        active, apool=None, aslots=None, gmask=None):
        quantized = pool.k.dtype == jnp.int8
        flat_k = pool.k.reshape(L, n_flat, kvh, d)
        flat_v = pool.v.reshape(L, n_flat, kvh, d)
        flat_ks = (pool.k_scale.reshape(L, n_flat, kvh)
                   if quantized else None)
        flat_vs = (pool.v_scale.reshape(L, n_flat, kvh)
                   if quantized else None)
        t = jnp.arange(V, dtype=jnp.int32)
        fidx = (page_tables[:, t // page_size] * page_size
                + t % page_size)                             # [B, V]
        pad5 = [(0, 0), (0, 0), (0, 1), (0, 0), (0, 0)]
        view_cache = KVCache(
            k=jnp.pad(flat_k[:, fidx], pad5),
            v=jnp.pad(flat_v[:, fidx], pad5),
            index=jnp.zeros((), jnp.int32),
            k_scale=(jnp.pad(flat_ks[:, fidx], pad5[:-1])
                     if quantized else None),
            v_scale=(jnp.pad(flat_vs[:, fidx], pad5[:-1])
                     if quantized else None))
        offs = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
        live = active[:, None] & (offs <= draft_len[:, None])
        # Park dead lanes at the view trash slot V (the padded row the
        # gather appended) — same parking the paged decode scan uses.
        pos = jnp.where(live, positions[:, None] + offs, V)
        adapters = None if apool is None else (apool, aslots)
        logits, vc = forward(cfg, params, tokens, positions=pos,
                             cache=view_cache, adapters=adapters)
        # Write-back: every live position's freshly written K/V, view ->
        # physical page; parked lanes land in the pool trash page.
        idx5 = pos[None, :, :, None, None]
        wk = jnp.take_along_axis(vc.k, idx5, axis=2)   # [L, B, K+1, kvh, d]
        wv = jnp.take_along_axis(vc.v, idx5, axis=2)
        page = jnp.take_along_axis(
            page_tables,
            jnp.clip(pos // page_size, 0, page_tables.shape[1] - 1),
            axis=1)                                    # [B, K+1]
        fi = jnp.where(live, page * page_size + pos % page_size,
                       trash_flat)
        flat_k = flat_k.at[:, fi].set(wk)
        flat_v = flat_v.at[:, fi].set(wv)
        if quantized:
            i4 = pos[None, :, :, None]
            wks = jnp.take_along_axis(vc.k_scale, i4, axis=2)
            wvs = jnp.take_along_axis(vc.v_scale, i4, axis=2)
            flat_ks = flat_ks.at[:, fi].set(wks)
            flat_vs = flat_vs.at[:, fi].set(wvs)
        rng, sub = jax.random.split(rng)
        accept, resid, full = speculative_verify(
            logits, tokens[:, 1:], sub, temperature, top_k, top_p,
            gmask=gmask)
        new_pool = PagePool(
            k=flat_k.reshape(pool.k.shape),
            v=flat_v.reshape(pool.v.shape),
            k_scale=(flat_ks.reshape(pool.k_scale.shape)
                     if quantized else None),
            v_scale=(flat_vs.reshape(pool.v_scale.shape)
                     if quantized else None))
        return accept, resid, full, new_pool, rng

    return paged_verify_fn


def make_kv_swap_out_fn():
    """One radix page, pool -> host: gather page ``page``'s K/V (plus
    scales when quantized) out of the pool so the host can pull and
    store it. The page index is a TRACED operand, so every swap-out of
    any page is the same compiled program — one warmup call covers all
    steady-state swap traffic (the PR-14 adapter page-in discipline).
    The pool is donated and returned unchanged (input-output aliasing:
    zero copy), keeping the caller's cache-threading identical to every
    other paged program."""

    def kv_swap_out_fn(pool, page):
        quantized = pool.k.dtype == jnp.int8
        out = (pool.k[:, page], pool.v[:, page],
               pool.k_scale[:, page] if quantized else None,
               pool.v_scale[:, page] if quantized else None)
        return out, pool

    return kv_swap_out_fn


def make_kv_swap_in_fn():
    """One radix page, host -> pool: splice a host-resident page's K/V
    back into physical page ``page`` of the donated pool, in place.
    Payload operands arrive as plain (uncommitted) numpy arrays — the
    HostPagePool buffers themselves — and the page index as np.int32,
    at warmup AND at runtime: committed device arrays would key a
    different jit entry and compile on the serving thread (the
    lora_pool lesson)."""

    def kv_swap_in_fn(pool, page, k_page, v_page, k_scale=None,
                      v_scale=None):
        quantized = pool.k.dtype == jnp.int8
        k = pool.k.at[:, page].set(k_page.astype(pool.k.dtype))
        v = pool.v.at[:, page].set(v_page.astype(pool.v.dtype))
        ks = (pool.k_scale.at[:, page].set(k_scale) if quantized
              else None)
        vs = (pool.v_scale.at[:, page].set(v_scale) if quantized
              else None)
        return PagePool(k=k, v=v, k_scale=ks, v_scale=vs)

    return kv_swap_in_fn


# ---------------------------------------------------------------------------
# Host-side paging state
# ---------------------------------------------------------------------------

class PagedKVManager:
    """Allocator + radix tree + per-slot page tables for one engine.
    Single-threaded like the engine that owns it; the ints /metrics
    reads are safe to read racily."""

    def __init__(self, num_pages: int, page_size: int, max_slots: int,
                 max_pages_per_slot: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_pages_per_slot = max_pages_per_slot
        self.allocator = PageAllocator(num_pages)
        self.radix = RadixTree(page_size, self.allocator)
        self.trash_page = num_pages
        self.page_table = np.full((max_slots, max_pages_per_slot),
                                  self.trash_page, np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        self.slot_shared = np.zeros(max_slots, np.int32)  # leading shared
        self.pages_reused_total = 0   # radix hits, counted PER PAGE
        # Engine callback for promoting host-resident matches at
        # admission: (host_slot, dest_page) -> bool. None until the
        # paged engine wires the host tier.
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.swap_in = None
        self.pages_swapped_in = 0     # cumulative host -> HBM promotions

    def plan(self, prompt_tokens, max_tokens: int,
             max_seq_len: int, ns=None) -> Tuple[List[_RadixNode], int]:
        """(shared_nodes, private_needed) for admitting this prompt.
        Shared = the radix tree's longest full-page match across BOTH
        tiers (HBM pages and host-resident copies — admit() swaps the
        latter back in), capped so at least one prompt token remains to
        prefill (sampling needs a real suffix logit). Private pages
        reserve the whole generation up front — ceil(min(prompt +
        max_tokens, max_seq_len) / page_size) minus the shared pages —
        so an admitted request can never die mid-generation to page
        exhaustion (admission and explicit QoS preemption are the only
        backpressure points: no corruption)."""
        ps = self.page_size
        n = len(prompt_tokens)
        shareable = ((n - 1) // ps) * ps
        shared = self.radix.match_nodes(prompt_tokens[:shareable], ns=ns)
        reserve = min(n + max_tokens, max_seq_len)
        total_pages = -(-reserve // ps)
        return shared, max(total_pages - len(shared), 0)

    def admit(self, slot: int, shared: List[_RadixNode],
              private_n: int) -> Optional[List[int]]:
        """Commit an admission: evict unreferenced prefix pages if the
        free list is short, allocate the private pages, take references
        on the shared ones — swapping host-resident matches back into
        fresh HBM pages first, so a returning session pays a device_put
        instead of re-prefilling its history — and build the slot's
        page table. Returns the private pages, or None when the pool
        cannot satisfy the plan (caller leaves the request queued —
        queue backpressure, not corruption). On a swap-in failure the
        whole admission rolls back ref-for-ref and the failed node
        drops from the tree, so the next plan's shorter match simply
        recomputes those tokens — degrade, never crash or leak."""
        # Pin the HBM-resident matches BEFORE evicting: the planned
        # shared pages may be tree-only (refcount 1) and would
        # otherwise be legal eviction victims for their own admission.
        hbm_pins = [nd.page for nd in shared if nd.page >= 0]
        self.allocator.incref(hbm_pins)
        n_promote = sum(1 for nd in shared if nd.page < 0)
        need = private_n + n_promote
        if need > self.allocator.free_count:
            self.radix.evict(need - self.allocator.free_count)
        fresh = self.allocator.alloc(need)
        if fresh is None or any(nd.page < 0 and nd.host_slot < 0
                                for nd in shared):
            # Pool can't satisfy the plan — or eviction's own host-tier
            # LRU pass dropped one of the matched host nodes (possible
            # only under extreme host pressure; the match refreshed
            # their recency, so they are the LAST candidates). Roll
            # back fully and let the caller re-plan.
            if fresh is not None:
                self.allocator.decref(fresh)
            self.allocator.decref(hbm_pins)
            return None
        pages: List[int] = []
        promoted: List[int] = []
        fi = 0
        failed: Optional[_RadixNode] = None
        for nd in shared:
            if nd.page >= 0:
                pages.append(nd.page)
                continue
            pg = fresh[fi]
            if self.swap_in is None or not self.swap_in(nd.host_slot, pg):
                failed = nd
                break
            # The fresh page's allocator ref transfers to the tree (it
            # owned the host copy); the slot's share ref goes on top —
            # refcount 2, exactly an HBM-resident shared page's shape.
            self.radix.host.free(nd.host_slot)
            nd.host_slot = -1
            nd.page = int(pg)
            self.radix.host_nodes -= 1
            self.radix.nodes += 1
            self.allocator.incref([pg])
            self.pages_swapped_in += 1
            promoted.append(pg)
            pages.append(pg)
            fi += 1
        if failed is not None:
            # Swap-in failed mid-promotion: drop the failed node (its
            # HBM descendants, if any, only lose their TREE refs — the
            # pins below still hold them until the final decref), undo
            # the slot refs taken so far (already-promoted nodes keep
            # their new HBM residency: that work is not wasted), and
            # free the unused fresh pages.
            self.radix._drop_subtree(failed)
            self.allocator.decref(promoted)
            self.allocator.decref(fresh[fi:])
            self.allocator.decref(hbm_pins)
            return None
        priv = fresh[fi:]
        pages.extend(priv)
        self.slot_pages[slot] = pages
        self.slot_shared[slot] = len(shared)
        self.page_table[slot, :] = self.trash_page
        self.page_table[slot, :len(pages)] = pages
        self.pages_reused_total += len(shared)
        return priv

    def release(self, slot: int, written_tokens=None, ns=None) -> None:
        """Drop the slot's page references. With ``written_tokens`` (the
        finished request's prompt + generated tokens, trimmed to what
        the cache actually holds), first adopt the completed full pages
        into the radix tree — under the request's adapter namespace, so
        a tenant's pages only ever serve the SAME adapter's prompts —
        so the next prompt sharing this prefix (including the next turn
        of the same chat) reuses them."""
        pages = self.slot_pages[slot]
        if not pages:
            return
        if written_tokens is not None:
            self.radix.insert(written_tokens, pages, ns=ns)
        self.allocator.decref(pages)
        self.slot_pages[slot] = []
        self.slot_shared[slot] = 0
        self.page_table[slot, :] = self.trash_page

    def occupancy(self) -> dict:
        occ = {
            "pages_total": self.num_pages,
            "pages_free": self.allocator.free_count,
            "pages_used": self.allocator.used_count,
            "pages_shared": self.radix.nodes,
            "pages_reused_total": self.pages_reused_total,
            "pages_evicted_total": self.radix.pages_evicted,
        }
        host = self.radix.host
        if host is not None:
            occ.update({
                "host_pages_total": host.num_pages,
                "host_pages_used": host.used_count,
                "host_pages_free": host.free_count,
                "host_resident_pages": self.radix.host_nodes,
                "host_bytes": host.nbytes,
                "swap_out_pages_total": self.radix.pages_swapped_out,
                "swap_in_pages_total": self.pages_swapped_in,
                "swap_dropped_pages_total": self.radix.pages_swap_dropped,
                "host_pages_evicted_total": self.radix.host_pages_evicted,
            })
        return occ


# ---------------------------------------------------------------------------
# The paged engine
# ---------------------------------------------------------------------------

class PagedInferenceEngine(InferenceEngine):
    """InferenceEngine over a paged pool instead of dense slot rows.

    Same request lifecycle, queueing, deadlines, and latency accounting
    as the dense engine (inherited); what changes is storage and
    admission: slots hold page tables into a shared pool, admission
    gates on page availability (pages, not slots, are the scarce
    resource), and every finished request's prompt pages feed the radix
    tree for many-user prefix reuse (docs/paged-kv.md)."""

    kv_paging = "paged"

    def _check_mesh(self, cfg: ModelConfig, mesh) -> None:
        # Precise mesh-geometry validation: each error names the one
        # constraint that failed (docs/troubleshooting.md). Anything
        # that passes here serves correctly — the pool shards its
        # kv-heads axis over `tensor` and replicates over the data/
        # fsdp axes (page identity is global: the page tables, the
        # allocator, and the radix tree stay replicated host state).
        if not isinstance(mesh, jax.sharding.Mesh):
            raise ValueError(
                f"mesh must be a jax.sharding.Mesh, got "
                f"{type(mesh).__name__}")
        tensor = int(mesh.shape.get("tensor", 1))
        if tensor > 1 and cfg.num_kv_heads % tensor:
            raise ValueError(
                f"kv-heads not divisible by mesh_tensor: the paged "
                f"pool shards num_kv_heads={cfg.num_kv_heads} over "
                f"tensor={tensor}; pick mesh_tensor dividing the "
                f"kv-head count (docs/paged-kv.md)")
        # stage > 1 is rejected by the dense engine's constructor
        # (pipeline parallelism is a training-path feature).

    # -- storage -------------------------------------------------------

    def _init_cache(self) -> None:
        ps = self.page_size = self.options.page_size
        if self.max_seq_len % ps:
            raise ValueError(
                f"page_size {ps} must divide max_seq_len "
                f"{self.max_seq_len} (static page tables assume whole "
                "pages per slot)")
        self.pages_per_slot = self.max_seq_len // ps
        self.num_pages = (self.options.num_pages
                          if self.options.num_pages is not None
                          else self.max_slots * self.pages_per_slot)
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold even one "
                f"max-length sequence ({self.pages_per_slot} pages)")
        self.pager = PagedKVManager(self.num_pages, ps, self.max_slots,
                                    self.pages_per_slot)
        # guarded-by: engine worker thread (single-threaded serving loop)
        self.host_pool: Optional[HostPagePool] = None
        self._wire_host_tier()
        self.cache = self._shard_pool(
            PagePool.create(self.cfg, self.num_pages, ps,
                            quantize_kv=self.quantize_kv))

    def _wire_host_tier(self) -> None:
        """(Re)attach the host swap tier to a fresh pager. The host pool
        reallocates too: its copies pair with radix nodes of the pager
        being replaced, so carrying them over would resurrect pages of
        a discarded tree. No-op when kv_host_pages is 0 — eviction then
        drops pages exactly as before the host tier existed."""
        if self.options.kv_host_pages <= 0:
            return
        self.host_pool = HostPagePool(self.cfg,
                                      self.options.kv_host_pages,
                                      self.page_size,
                                      quantize_kv=self.quantize_kv)
        self.pager.radix.host = self.host_pool
        self.pager.radix.swap_out = self._kv_swap_out
        self.pager.swap_in = self._kv_swap_in

    def _shard_pool(self, pool: PagePool) -> PagePool:
        """Lay the pool out under the serving mesh: kv-heads (axis 3 of
        the 5-d k/v, axis 3 of the 4-d scales) shard over `tensor`;
        every other axis replicates. The page axis must NOT shard — page
        ids are global (one page table serves every shard), and the
        jitted bodies' flat [L, (num_pages+1)*page_size, kvh, d] reshape
        preserves the kv-head axis, so gathers/scatters index only the
        replicated flat-token axis and GSPMD propagates the head
        sharding straight through them."""
        if self.mesh is None:
            return jax.device_put(pool, self._home())
        from jax.sharding import NamedSharding

        from runbooks_tpu.parallel.sharding import spec_for_array

        def put(a):
            if a is None:
                return None
            logical = (None, None, None, "act_heads", None)[:a.ndim]
            return jax.device_put(a, NamedSharding(
                self.mesh, spec_for_array(a.shape, logical, self.mesh)))

        return PagePool(k=put(pool.k), v=put(pool.v),
                        k_scale=put(pool.k_scale),
                        v_scale=put(pool.v_scale))

    def reset(self) -> None:
        """Crash recovery: donated pool buffers may be invalid, so the
        pool reallocates and ALL paging state resets — the radix tree's
        pages lived in the doomed pool, so its content goes too."""
        self._reset_slots()
        self.pager = PagedKVManager(self.num_pages, self.page_size,
                                    self.max_slots, self.pages_per_slot)
        self._wire_host_tier()
        self.cache = self._shard_pool(
            PagePool.create(self.cfg, self.num_pages, self.page_size,
                            quantize_kv=self.quantize_kv))

    # -- programs ------------------------------------------------------

    def _init_programs(self) -> None:
        cfg = self.cfg
        cache_len = self.max_seq_len + 1
        self._paged_prefill = jax.jit(
            make_paged_prefill_fn(cfg, cache_len, self.page_size,
                                  self.num_pages),
            donate_argnums=(1,))
        obs_device.PROGRAMS.register("serve", "paged_prefill",
                                     self._paged_prefill)
        self.view_page_buckets = view_page_buckets_for(self.max_seq_len,
                                                       self.page_size)
        self._decode_fns: dict = {}

        def make_decode(view_pages: int, weight_layouts=None):
            return make_paged_decode_fn(cfg, self.decode_chunk,
                                        self.max_seq_len, self.page_size,
                                        view_pages, self.num_pages,
                                        weight_layouts)

        self._make_decode = make_decode

        def decode_for(view_pages: int):
            if view_pages not in self._decode_fns:
                self._decode_fns[view_pages] = self._jit_decode(
                    make_decode(view_pages, self._stack_layouts))
                obs_device.PROGRAMS.register(
                    "serve", f"decode_p{view_pages}",
                    self._decode_fns[view_pages])
            return self._decode_fns[view_pages]

        self._decode_for = decode_for
        self._verify_fns: dict = {}

        def verify_for(view_pages: int):
            if view_pages not in self._verify_fns:
                self._verify_fns[view_pages] = jax.jit(
                    make_paged_verify_fn(cfg, self.draft_tokens,
                                         self.page_size, view_pages,
                                         self.num_pages),
                    donate_argnums=(1,))
                obs_device.PROGRAMS.register(
                    "serve", f"verify_p{view_pages}",
                    self._verify_fns[view_pages])
            return self._verify_fns[view_pages]

        self._verify_for = verify_for
        if self.options.kv_host_pages > 0:
            self._swap_out_prog = jax.jit(make_kv_swap_out_fn(),
                                          donate_argnums=(0,))
            obs_device.PROGRAMS.register("serve", "kv_swap_out",
                                         self._swap_out_prog)
            self._swap_in_prog = jax.jit(make_kv_swap_in_fn(),
                                         donate_argnums=(0,))
            obs_device.PROGRAMS.register("serve", "kv_swap_in",
                                         self._swap_in_prog)

    # -- host swap tier (docs/paged-kv.md "Host tier") -----------------

    def _kv_swap_out(self, page: int) -> Optional[int]:
        """RadixTree eviction callback: copy one HBM page into a host
        slot. Returns the host slot, or None to degrade to dropping
        (host tier still full after its own LRU pass, or the injected
        swapfail fault) — the tree then drops the node exactly as the
        host-less path would."""
        if self._swap_fault_hit():
            return None
        h = self.host_pool.alloc()
        if h is None:
            self.pager.radix.evict_host(1)
            h = self.host_pool.alloc()
            if h is None:
                return None
        t0 = time.perf_counter()
        with self._mesh_ctx():
            out, self.cache = self._swap_out_prog(self.cache,
                                                  np.int32(page))
            # rbt-check: ignore[device-sync] swap-out boundary — the page's bytes must land in host RAM before the HBM page frees
            payload = tuple(np.asarray(x) for x in out if x is not None)
        self.host_pool.store(h, *payload)
        obs_metrics.REGISTRY.observe(
            "serve_kv_swap_seconds", time.perf_counter() - t0,
            direction="out",
            help_text="Host-tier page copy wall time (dispatch + host "
                      "sync), labeled by direction.")
        return h

    def _kv_swap_in(self, host_slot: int, page: int) -> bool:
        """PagedKVManager promotion callback: splice one host-resident
        page back into fresh HBM page ``page``. False = degrade to
        recompute (injected swapfail fault): the manager aborts the
        admission leak-free and the next plan simply prefills those
        tokens."""
        if self._swap_fault_hit():
            return False
        payload = self.host_pool.load(host_slot)
        t0 = time.perf_counter()
        with self._mesh_ctx():
            self.cache = self._swap_in_prog(self.cache, np.int32(page),
                                            *payload)
        obs_metrics.REGISTRY.observe(
            "serve_kv_swap_seconds", time.perf_counter() - t0,
            direction="in",
            help_text="Host-tier page copy wall time (dispatch + host "
                      "sync), labeled by direction.")
        return True

    def _view_pages_for(self, max_pos: int) -> int:
        """Smallest view-page bucket whose token extent covers every
        position this chunk can write."""
        for vp in self.view_page_buckets:
            if max_pos <= vp * self.page_size:
                return vp
        return self.view_page_buckets[-1]

    def warmup(self, rows: Optional[tuple] = None,
               prefix_build: bool = False) -> None:
        """Compile the full paged program set ahead of traffic: every
        reachable (suffix bucket, prefix-page bucket) x row count
        prefill, plus one decode per view-page bucket. Unlike the dense
        engine's prefix path (whose plen-keyed splice shapes appear at
        runtime and warm in the background), the paged prefix-shape set
        is static — so warmup covers it completely and a radix hit can
        NEVER compile on the serving thread. prefix_build is accepted
        for interface compatibility and ignored (prefix registration
        rides the normal admission path here)."""
        del prefix_build
        if rows is None:
            rows = (1, self.max_slots) if self.max_slots > 1 else (1,)
        row_set = list(dict.fromkeys(min(r, self.max_slots)
                                     for r in rows))
        run = WarmupRun()
        sentinel = run.sentinel
        shapes = paged_prefill_shapes(self.prefill_buckets,
                                      self.pages_per_slot, self.page_size,
                                      self.max_seq_len)
        n_prefill = 0
        trash = self.pager.trash_page
        with sentinel.expected():
            if self.adapters is not None:
                # The pool's lane-splice program (serve/lora_pool.py):
                # adapter loads under traffic must never compile.
                self.adapters.warm()
            for bucket, ppb in shapes:
                for r in row_set:
                    tokens = np.zeros((r, bucket), np.int32)
                    positions = np.full((r, bucket), self._pad_slot,
                                        np.int32)
                    dest = np.full((r, self.pages_per_slot), trash,
                                   np.int32)
                    args = (jnp.asarray(tokens), jnp.asarray(positions),
                            jnp.asarray(dest), jnp.zeros(r, jnp.int32),
                            self._commit_key(jax.random.key(0)),
                            jnp.zeros(r, jnp.float32),
                            jnp.zeros(r, jnp.int32),
                            jnp.ones(r, jnp.float32))
                    if ppb:
                        args = args + (
                            jnp.full((r, ppb), trash, jnp.int32),
                            jnp.zeros(r, jnp.int32))
                    akw = {**self._adapter_kwargs(np.full(r, -1,
                                                          np.int32)),
                           **self._grammar_warm_kwargs(
                               (r, self.cfg.vocab_size))}
                    with self._mesh_ctx():
                        _, self.cache, _ = run.program(
                            "paged_prefill", f"b{bucket}r{r}p{ppb}",
                            self._paged_prefill, self.params, self.cache,
                            *args, **akw)
                    n_prefill += 1
            zeros = np.zeros(self.max_slots, np.int32)
            tables = np.full((self.max_slots, self.pages_per_slot), trash,
                             np.int32)
            akw = {**self._decode_kwargs(),
                   **self._grammar_warm_kwargs(
                       (self.max_slots, self.cfg.vocab_size))}
            for vp in self.view_page_buckets:
                # No row alive.
                args = (jnp.asarray(tables),
                        *self._place_blocks(
                            np.zeros_like(self._slot_ints),
                            np.zeros_like(self._slot_floats)),
                        self._commit_key(jax.random.key(0)))
                with self._mesh_ctx():
                    _, _, self.cache, _ = run.program(
                        f"decode_p{vp}", f"p{vp}", self._decode_for(vp),
                        self.params, self.cache, *args, **akw)
            n_verify = 0
            if self.options.speculative != "off":
                vtok = np.zeros((self.max_slots, self.draft_tokens + 1),
                                np.int32)
                akw = {**self._adapter_kwargs(),
                       **self._grammar_warm_kwargs(
                           (self.max_slots, self.draft_tokens + 1,
                            self.cfg.vocab_size))}
                for vp in self.view_page_buckets:
                    args = (jnp.asarray(tables), jnp.asarray(vtok),
                            jnp.asarray(zeros), jnp.asarray(zeros),
                            self._commit_key(jax.random.key(0)),
                            jnp.zeros(self.max_slots, jnp.float32),
                            jnp.zeros(self.max_slots, jnp.int32),
                            jnp.ones(self.max_slots, jnp.float32),
                            jnp.zeros(self.max_slots, bool))
                    with self._mesh_ctx():
                        _, _, _, self.cache, _ = run.program(
                            f"verify_p{vp}", f"p{vp}",
                            self._verify_for(vp), self.params, self.cache,
                            *args, **akw)
                    n_verify += 1
            n_swap = 0
            if self.options.kv_host_pages > 0:
                # Swap splices warm against the trash page: the gather
                # reads garbage and the splice writes a page nothing
                # references — harmless, and EXACTLY the runtime operand
                # signature (np.int32 page index, plain np host-page
                # payloads; committed device operands would key a
                # different jit entry — the lora_pool lesson).
                pg = np.int32(self.pager.trash_page)
                with self._mesh_ctx():
                    out, self.cache = run.program(
                        "kv_swap_out", "page", self._swap_out_prog,
                        self.cache, pg)
                    payload = tuple(np.asarray(x) for x in out
                                    if x is not None)
                with self._mesh_ctx():
                    self.cache = run.program(
                        "kv_swap_in", "page", self._swap_in_prog,
                        self.cache, pg, *payload)
                n_swap = 2
        census = obs_device.PROGRAMS.census("serve")
        self.warmup_census = {
            "prefill_programs": n_prefill,
            "prefill_buckets": list(self.prefill_buckets),
            "prefix_page_buckets":
                [0] + prefix_page_buckets(self.pages_per_slot),
            "rows": row_set,
            "decode_views": list(self.view_page_buckets),
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "verify_programs": n_verify,
            "swap_programs": n_swap,
            "kv_host_pages": self.options.kv_host_pages,
            "speculative": self.options.speculative,
            "draft_tokens": self.draft_tokens,
            "adapter_pool": (self.adapters.pool_size
                             if self.adapters is not None else 0),
            "lora_rank": (self.adapters.rank
                          if self.adapters is not None else None),
            "grammar": self.options.grammar,
            "grammar_cache_size": (self._grammar_cache.capacity
                                   if self._grammar_cache is not None
                                   else None),
            "decode_chunk": self.decode_chunk,
            **run.finish(self.cache),
            "weight_layout": self.weight_layout,
            "programs": [{"name": c["name"], "programs": c["programs"]}
                         for c in census],
        }
        print(
            f"serve: paged warmup census: {n_prefill} prefill programs "
            f"({len(shapes)} (bucket, prefix-pages) shapes x rows "
            f"{row_set}), {len(self.view_page_buckets)} decode views "
            f"(pages {self.view_page_buckets}), "
            f"{self.num_pages}x{self.page_size} pool, "
            f"{n_verify} verify programs, {n_swap} swap programs; "
            f"{self.warmup_census['compiles']} compiles in "
            f"{self.warmup_census['compile_seconds']}s, "
            f"{self.warmup_census['cache_hits']} from the persistent "
            f"cache; weight layout {self.weight_layout}; "
            f"phases {self.warmup_census['phases']}", flush=True)
        if not self._marked_steady:
            self._marked_steady = True
            sentinel.mark_steady("serve")
        self.reset()

    # -- prefix surface (radix-backed) ---------------------------------

    def _usable_prefix_len(self, tokens) -> int:
        """Full-page token count a registration/lookup can share, leaving
        at least one token inside the context window to prefill."""
        n = min(len(tokens), self.max_seq_len - 1)
        return (n // self.page_size) * self.page_size

    def register_prefix(self, tokens: List[int], warmup: bool = True) -> int:
        """Seed the radix tree with a prompt prefix (e.g. a deployment's
        system prompt) by running it through the NORMAL admission path:
        a one-token synthetic generation prefills the tokens into pages,
        and the finish hook adopts the full pages into the tree. Zero
        dedicated programs, zero compiles beyond the warmed set. Returns
        the shareable (full-page) length, 0 if too short."""
        del warmup  # every paged shape is compiled by warmup() already
        plen = self._usable_prefix_len(tokens)
        if plen < self.page_size:
            return 0
        toks = [int(t) for t in tokens[:self.max_seq_len - 1]]
        if len(self.pager.radix.match(toks[:plen])) * self.page_size \
                >= plen:
            return plen  # already fully resident
        req = Request(prompt_tokens=toks, max_tokens=1, temperature=0.0)
        self.validate(req)
        req._submitted = time.monotonic()
        # Engine-internal work driven by the worker thread itself:
        # bypass submit()'s public admission bound — a full queue must
        # not turn registration into a 429 (the dense engine's
        # register_prefix cannot fail under load either).
        self.queue.append(req)
        # Synchronous: the caller runs on the engine's thread (the
        # worker's prefix-job path). Other queued traffic keeps being
        # served by these steps.
        try:
            for _ in range(self.max_seq_len * 4):
                if req.finished:
                    break
                self.step()
        except Exception as exc:  # noqa: BLE001
            # The donated cache may now be invalid and page refs
            # half-applied — the worker must doom in-flight requests and
            # reset(), not swallow this per-job (serve/api.py).
            raise EngineStepFailed(
                "jitted step failed during paged prefix "
                "registration") from exc
        if req.finished:
            return plen
        # Timed out behind sustained traffic: withdraw the synthetic
        # request so a late completion cannot adopt pages after we
        # reported failure.
        try:
            self.queue.remove(req)
        except ValueError:
            pass
        return 0

    def register_prefix_from_slot(self, slot: int,
                                  tokens: List[int]) -> int:
        """No-op: the finish hook already adopted the slot's completed
        pages into the radix tree — multi-turn reuse needs no explicit
        lift-out on the paged engine."""
        return 0

    def has_prefix(self, tokens: List[int]) -> bool:
        plen = self._usable_prefix_len(tokens)
        return (plen >= self.page_size
                and len(self.pager.radix.match(tokens[:plen]))
                * self.page_size >= plen)

    def prefix_warmup_shapes(self, plen: int) -> List[tuple]:
        return []  # warmup() compiled the full static set

    def warm_prefix_shape(self, key: tuple, bucket: int, rows: int,
                          buffers: Optional[tuple] = None):
        return buffers  # nothing to warm at runtime

    # -- admission -----------------------------------------------------

    def _admit(self, exclude_slots=()) -> None:
        blocked = self._admit_pass(exclude_slots)
        if (self.options.preemption == "swap" and blocked
                and self._maybe_preempt(exclude_slots)):
            # The victim's slot and pages freed at this step boundary:
            # a second pass admits the better-class head NOW instead of
            # a step later (TTFT under overload is the point).
            self._admit_pass(exclude_slots)

    def _admit_pass(self, exclude_slots=()) -> bool:
        """One admission sweep over the free slots. Returns True when
        the queue head is left blocked on CAPACITY (no free slot, page
        exhaustion, or adapter-lane exhaustion) rather than on this
        tick's prefill budget — the signal _admit's preemption pass
        keys on (a budget-blocked head admits next step by itself;
        preempting for it would churn)."""
        budget = self.prefill_budget
        admitted: List[tuple] = []
        budget_blocked = False
        for slot in self._free_slots(exclude_slots):
            if not self.queue:
                break
            head = self.queue[0]
            # Radix lookups are namespaced by adapter: a tenant's pages
            # only ever match the SAME adapter's prompts (the K/V values
            # differ per adapter even for identical tokens). A preempted
            # head plans against prompt + written outputs — its own
            # adopted pages — so resume rides the shared-prefix path.
            eff = self._admit_tokens(head)
            shared, private_n = self.pager.plan(
                eff, self._admit_budget(head), self.max_seq_len,
                ns=head.adapter)
            suffix = len(eff) - len(shared) * self.page_size
            need = self._bucket_for(suffix)
            if admitted and need > budget:
                budget_blocked = True
                break
            if not self._acquire_adapter(head):
                # Adapter-pool exhaustion: same backpressure as page
                # exhaustion below — the head waits, the queue backs up,
                # submit() sheds with 429.
                break
            if head.finished:       # adapter artifact failed to load
                self.queue.pop(0)
                continue
            priv = self.pager.admit(slot, shared, private_n)
            if priv is None:
                # Page pressure even after evicting unreferenced prefix
                # pages: the head waits (FIFO — no starvation of big
                # requests) and the queue backs up until submit() sheds
                # with 429. Never admit a request the pool cannot hold.
                # (The adapter lane pin above persists on the request
                # and is reused when pages free up.)
                break
            req = self.queue.pop(0)
            req._admitted = time.monotonic()
            obs_metrics.REGISTRY.observe(
                "serve_queue_wait_seconds",
                req._admitted - req._submitted,
                help_text="Admission-queue wait (engine.submit, on the "
                          "worker's thread, to slot assignment; the wait "
                          "for the worker before it is "
                          "serve_pending_wait_seconds).")
            if record_enabled():
                trace_complete("queue_wait",
                               req._admitted - req._submitted,
                               request_id=req.request_id, slot=slot)
            budget -= need
            admitted.append((slot, req, len(shared)))
        if admitted:
            by_group: dict = {}
            for slot, req, nshared in admitted:
                b = self._bucket_for(len(self._admit_tokens(req))
                                     - nshared * self.page_size)
                ppb = page_bucket(nshared, self.pages_per_slot)
                by_group.setdefault((b, ppb), []).append((slot, req))
            for (bucket, ppb), group in by_group.items():
                self._prefill_group_paged(bucket, ppb, group)
        return bool(self.queue) and not budget_blocked

    # -- QoS preemption (docs/paged-kv.md "Preemption") ----------------

    def _maybe_preempt(self, exclude_slots=()) -> bool:
        """Preempt ONE active slot whose class is strictly worse than
        the queue head's: worst class first, most-recently-admitted
        within a class (least sunk work lost). One victim per step
        bounds preemption churn — a storm can displace at most one
        slot per step boundary, and only while a better-class request
        is actually waiting. Returns True when a slot was preempted."""
        head_rank = PRIORITY_RANK[self.queue[0].priority]
        cands = [
            (PRIORITY_RANK[self.slot_req[s].priority],
             self.slot_req[s]._admitted, s)
            for s in range(self.max_slots)
            if self.active[s] and self.slot_req[s] is not None
            and s not in exclude_slots
            and PRIORITY_RANK[self.slot_req[s].priority] > head_rank]
        if not cands:
            return False
        _, _, victim = max(cands)
        self._preempt_slot(victim)
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Displace one active slot at a step boundary. The written
        extent (prompt + outputs[:-1] — the last sampled token is never
        written; engine.py's cache invariant) adopts into the radix
        tree exactly like a finished request's pages, so the state
        survives in the HBM/host hierarchy; the request re-queues with
        its generated tokens intact and resumes later via a radix match
        on its own history (engine.py _activate_slot's resume branch) —
        no token loss, finish_reason unchanged. The adapter lane stays
        pinned: releasing it could park the resume behind the very
        traffic that preempted it."""
        req = self.slot_req[slot]
        assert req is not None
        # Its last tokens first: they may still wait for the next dispatch.
        self.deliver_parked()
        self._dev_blocks = None
        m = len(req.output_tokens)
        written = len(req.prompt_tokens) + max(0, m - 1)
        toks = (req.prompt_tokens + req.output_tokens)[:written]
        self.pager.release(slot, written_tokens=toks, ns=req.adapter)
        self.active[slot] = False
        self.slot_req[slot] = None
        self.adapter_slots[slot] = -1
        if self._spec_index is not None:
            self._spec_index.clear(slot)
        req._slot = -1
        req._preempted = True
        self.preemptions += 1
        # Requeue at the tail of the request's own class, bypassing
        # submit()'s admission bounds — shedding a preempted request
        # would lose its generated tokens, the one thing preemption
        # exists to avoid.
        self._queue_insert(req)

    def _prefill_group_paged(self, bucket: int, ppb: int,
                             group: List[tuple]) -> None:
        """One batched paged prefill for same-(suffix bucket, prefix-page
        bucket) admissions. Rows within the group may share DIFFERENT
        prefixes (or different lengths within the bucket) — the per-row
        prefix-page and prefix-length operands carry each row's own
        match, which is what makes this many-user sharing rather than
        the dense path's one-prefix-per-dispatch."""
        n = len(group)
        ps = self.page_size
        self.prefix_lookups += n
        rows = 1 if n == 1 else self.max_slots

        def operands():
            tokens = np.zeros((rows, bucket), np.int32)
            positions = np.full((rows, bucket), self._pad_slot, np.int32)
            trash = self.pager.trash_page
            dest_pages = np.full((rows, self.pages_per_slot), trash,
                                 np.int32)
            prefix_pages = (np.full((rows, ppb), trash, np.int32)
                            if ppb else None)
            prefix_len = np.zeros(rows, np.int32) if ppb else None
            last_pos = np.zeros(rows, np.int32)
            temps = np.zeros(rows, np.float32)
            top_ks = np.zeros(rows, np.int32)
            top_ps = np.ones(rows, np.float32)
            aslots = np.full(rows, -1, np.int32)
            for i, (slot, req) in enumerate(group):
                aslots[i] = req._adapter_lane
                nshared = int(self.pager.slot_shared[slot])
                plen = nshared * ps
                # Preemption-resume rows prefill the request's own written
                # history past its adopted pages (engine.py
                # _admit_tokens); fresh rows see eff == prompt_tokens
                # unchanged.
                eff = self._admit_tokens(req)
                m = len(eff) - plen
                tokens[i, :m] = eff[plen:]
                positions[i, :m] = np.arange(plen, plen + m)
                dest_pages[i] = self.pager.page_table[slot]
                if ppb:
                    prefix_pages[i, :nshared] = \
                        self.pager.slot_pages[slot][:nshared]
                    prefix_len[i] = plen
                last_pos[i] = m - 1
                temps[i] = req.temperature
                top_ks[i] = req.top_k
                top_ps[i] = req.top_p
                if nshared:
                    self.prefix_hits += 1
                    self.prefix_tokens_reused += plen
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(dest_pages), jnp.asarray(last_pos),
                    self.rng, jnp.asarray(temps), jnp.asarray(top_ks),
                    jnp.asarray(top_ps))
            if ppb:
                args = args + (jnp.asarray(prefix_pages),
                               jnp.asarray(prefix_len))
            kwargs = {**self._adapter_kwargs(aslots),
                      **self._grammar_prefill_kwargs(group, rows)}
            return args, kwargs, positions

        def program(args, kwargs):
            first, self.cache, self.rng = self._paged_prefill(
                self.params, self.cache, *args, **kwargs)
            return (first,)

        self._prefill_dispatch(bucket, rows, ppb * ps, group, operands,
                               program)

    # -- lifecycle hooks ----------------------------------------------

    def _on_slot_finished(self, slot: int, req: Request) -> None:
        """Adopt the finished request's fully written pages into the
        radix tree, then drop the slot's references. Only pages the
        cache ACTUALLY holds are insertable: the last sampled token is
        never written (the next chunk would have written it), so the
        written extent is prompt + outputs - 1 — inserting past it would
        share a page whose tail is garbage."""
        m = len(req.output_tokens)
        written = len(req.prompt_tokens) + max(0, m - 1)
        toks = (req.prompt_tokens + req.output_tokens)[:written]
        self.pager.release(slot, written_tokens=toks, ns=req.adapter)
        super()._on_slot_finished(slot, req)  # spec index + adapter lane

    # -- decode --------------------------------------------------------

    # The decode chunk and the verify step are the dense engine's
    # (engine.py _decode_chunk_step, _verify_dispatch); only the program
    # key (a page count) and the page-table operand differ (inactive rows
    # park at the view's trash slot, whose writes land in the trash page).

    def _view_key(self, max_pos: int) -> tuple:
        vp = self._view_pages_for(max_pos)
        return vp, vp * self.page_size

    def _table_operands(self) -> tuple:
        return (self.pager.page_table,)

    # -- observability -------------------------------------------------

    def kv_occupancy(self) -> dict:
        """Page-level pool occupancy. occupancy_ratio here is pages
        used / pages total — physical pressure on the pool (the dense
        engine reports logical tokens / dense reservation; at equal HBM
        the paged ratio is what admission actually gates on)."""
        ps = self.page_size
        occ = self.pager.occupancy()
        tokens = (int(self.lengths[self.active].sum())
                  if self.active.any() else 0)
        capacity = self.num_pages * ps
        # nbytes is LOGICAL (global) bytes; under a serving mesh each
        # chip holds only its kv-head shard of the pool, so both views
        # are reported — per-device is what admission headroom and OOMs
        # actually see (docs/observability.md).
        pool_bytes = self.cache.nbytes
        arrays = [a for a in (self.cache.k, self.cache.v,
                              self.cache.k_scale, self.cache.v_scale)
                  if a is not None]
        pool_local = sum(obs_device.shard_local_nbytes(a) for a in arrays)
        bpp = pool_bytes // (self.num_pages + 1)
        return {"slots_total": self.max_slots,
                "slots_active": int(self.active.sum()),
                "kv_tokens": tokens,
                "kv_capacity_tokens": capacity,
                "occupancy_ratio": (occ["pages_used"] / self.num_pages
                                    if self.num_pages else 0.0),
                "paged": True,
                "page_size": ps,
                "bytes_per_page": bpp,
                "kv_pool_bytes": pool_bytes,
                "kv_pool_bytes_per_device": pool_local,
                "bytes_per_page_per_device":
                    pool_local // (self.num_pages + 1),
                "kv_bytes_shared": occ["pages_shared"] * bpp,
                "kv_bytes_private":
                    (occ["pages_used"] - occ["pages_shared"]) * bpp,
                **occ}
