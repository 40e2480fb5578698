"""HBM-paged LoRA adapter pool for multi-tenant batched serving.

One engine, one set of base weights, many tenants: the pool holds up to
``pool_size`` LoRA adapters resident in HBM as a stacked pytree
(ops/lora.py — lane ``pool_size`` is the all-zero trash lane base-only
rows gather), pages adapters in from artifact storage on demand, and
evicts by LRU among lanes no in-flight request references. The same
allocator discipline as serve/paging.py's PageAllocator: refcounts pin
what live slots use, admission is the only backpressure point (a
non-resident adapter whose pool has no evictable lane leaves its request
queued — the queue backs up until submit() sheds with a typed 429), and
nothing is ever torn out from under a running request.

Compile discipline (docs/multi-tenant-lora.md): the pool's geometry
(pool_size, rank bucket, target set) is static, the lane index is a
traced operand, and the HBM splice is ONE jitted program warmed at
engine warmup — so a steady adapter-swapping loop performs loads and
evictions with ZERO XLA compiles (the sentinel-audited invariant every
other engine program obeys).

Artifact format — exactly what a LoRA training run leaves behind
(train/trainer.py): a directory with ``checkpoints/`` holding the
TrainState whose params are the LoRA tree ({target: {"a": [L, in, r],
"b": [L, r, out]}}) and ``lora.json`` carrying {rank, alpha, targets}.
``save_adapter`` writes the same layout for tests/tools. Each adapter's
own alpha/rank scale is folded into its B at load (load_adapter_tree),
so heterogeneous alphas batch together without per-row scale operands;
ranks below the pool's bucket zero-pad exactly.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from runbooks_tpu.models.config import ModelConfig
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.ops.lora import (
    init_adapter_pool,
    make_pool_write_fn,
    nest_targets,
    target_dims,
)

ADAPTER_META = "lora.json"


class AdapterLoadError(ValueError):
    """A named adapter artifact cannot be loaded into the pool (missing
    checkpoint, rank above the pool bucket, target/shape mismatch).
    Callers surface it per-request (HTTP 400 at validation, finish_reason
    "error" if it only fails at admission) — it must never crash the
    engine loop."""


def save_adapter(path: str, lora_tree, rank: int, alpha: float,
                 targets=None) -> None:
    """Write a serving-loadable adapter artifact (the trainer's layout:
    checkpoints/ + lora.json). For tests, tooling, and exporting adapters
    trained elsewhere."""
    from runbooks_tpu.train.checkpoint import CheckpointManager

    os.makedirs(path, exist_ok=True)
    mgr = CheckpointManager(path)
    try:
        mgr.save(0, {"params": lora_tree}, force=True)
        mgr.wait()
    finally:
        mgr.close()
    meta = {"rank": int(rank), "alpha": float(alpha)}
    if targets is not None:
        meta["targets"] = list(targets)
    with open(os.path.join(path, ADAPTER_META), "w") as f:
        json.dump(meta, f)


def read_adapter_meta(path: str) -> dict:
    """lora.json contents ({} when absent — rank then infers from the
    checkpoint shapes and alpha defaults to train/lora.py's 16.0)."""
    meta_path = os.path.join(path, ADAPTER_META)
    if not os.path.exists(meta_path):
        return {}
    try:
        with open(meta_path) as f:
            return dict(json.load(f))
    except (OSError, ValueError) as exc:
        raise AdapterLoadError(
            f"adapter {path!r}: unreadable {ADAPTER_META}: {exc}") from exc


def adapter_artifact_ok(path: str) -> Optional[str]:
    """Cheap pre-admission artifact probe: None when ``path`` looks like
    a loadable adapter dir, else the reason it is not (the 400 message).
    Existence only — the full shape validation happens at load."""
    if not os.path.isdir(path):
        return f"adapter {path!r}: no such directory"
    if not os.path.isdir(os.path.join(path, "checkpoints")):
        return (f"adapter {path!r}: no checkpoints/ directory (expected "
                "a LoRA training artifact — train/trainer.py layout)")
    return None


def load_adapter_tree(path: str, cfg: ModelConfig, targets, rank: int):
    """Load one adapter artifact into the pool's device layout: a nested
    {"attn"/"mlp": {target: {"a": [L, d_in, rank], "b": [L, rank,
    d_out]}}} tree covering EVERY pool target — targets the adapter did
    not train are zero (a recycled lane must not leak the previous
    tenant's deltas), trained targets are rank-padded and alpha/rank
    scale-folded. Raises AdapterLoadError on any mismatch."""
    err = adapter_artifact_ok(path)
    if err is not None:
        raise AdapterLoadError(err)
    from runbooks_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(path)
    try:
        try:
            full = mgr.restore(None)
        except Exception as exc:  # noqa: BLE001 — corrupt artifact
            raise AdapterLoadError(
                f"adapter {path!r}: checkpoint restore failed: "
                f"{exc!r}") from exc
    finally:
        mgr.close()
    lora = (full.get("params") if isinstance(full, dict)
            else getattr(full, "params", None))
    if not isinstance(lora, dict) or not lora:
        raise AdapterLoadError(
            f"adapter {path!r}: checkpoint holds no LoRA params tree")
    # Structural validation BEFORE any indexing: a per-request adapter
    # must never crash the engine loop (the class contract), so a
    # malformed artifact — target values that are not {"a", "b"} trees —
    # raises the typed error, not a raw KeyError/IndexError that would
    # escape _acquire_adapter into the worker's crash handler.
    for t, ab in lora.items():
        if not (isinstance(ab, dict) and "a" in ab and "b" in ab
                and np.ndim(ab["a"]) >= 2 and np.ndim(ab["b"]) >= 2):
            raise AdapterLoadError(
                f"adapter {path!r}: target {t} is not an {{a, b}} LoRA "
                "pair (expected the train/lora.py artifact layout)")
    meta = read_adapter_meta(path)
    extra = sorted(set(lora) - set(targets))
    if extra:
        raise AdapterLoadError(
            f"adapter {path!r} trains target(s) {extra} the pool does "
            f"not inject; serve with lora_targets covering them "
            f"(pool targets: {sorted(targets)})")
    first = next(iter(lora.values()))
    a_rank = int(np.shape(first["a"])[-1])
    alpha = float(meta.get("alpha", 16.0))
    a_meta_rank = int(meta.get("rank", a_rank))
    if a_meta_rank != a_rank:
        raise AdapterLoadError(
            f"adapter {path!r}: {ADAPTER_META} rank {a_meta_rank} does "
            f"not match checkpoint rank {a_rank}")
    # Everything below runs in NumPy on the host, with ONE device_put
    # per leaf at the end — two reasons, both compile-sentinel
    # discipline (the load path runs under live traffic):
    # (1) eager jax pad/scale/astype ops would XLA-compile tiny
    #     programs on the first post-warmup load;
    # (2) orbax restores COMMITTED device arrays, and committedness
    #     propagates into the pool-write operands, keying fresh jit
    #     entries (re-COMPILING the warmed lane splice).
    # Leaves stay float32 — the write program casts to the pool dtype
    # inside the already-compiled splice (ops/lora.make_pool_write_fn).
    flat = {}
    for t in targets:
        d_in, d_out = target_dims(cfg, t)
        if t in lora:
            a = np.asarray(lora[t]["a"], np.float32)
            b = np.asarray(lora[t]["b"], np.float32)
            want_a = (cfg.num_layers, d_in, a_rank)
            want_b = (cfg.num_layers, a_rank, d_out)
            if tuple(a.shape) != want_a or tuple(b.shape) != want_b:
                raise AdapterLoadError(
                    f"adapter {path!r}: target {t} shapes "
                    f"a{tuple(a.shape)}/b{tuple(b.shape)} do not match "
                    f"model {cfg.name!r} (want a{want_a}/b{want_b})")
            if a_rank > rank:
                raise AdapterLoadError(
                    f"adapter rank {a_rank} exceeds the pool's rank "
                    f"bucket {rank}; raise lora_rank on the serving "
                    "config (a static program shape — all lanes share "
                    "it)")
            if a_rank < rank:
                a = np.pad(a, [(0, 0), (0, 0), (0, rank - a_rank)])
                b = np.pad(b, [(0, 0), (0, rank - a_rank), (0, 0)])
            b = b * (float(alpha) / float(a_rank))
            flat[t] = {"a": jnp.asarray(a), "b": jnp.asarray(b)}
        else:
            flat[t] = {"a": jnp.asarray(
                np.zeros((cfg.num_layers, d_in, rank), np.float32)),
                "b": jnp.asarray(
                np.zeros((cfg.num_layers, rank, d_out), np.float32))}
    return flat and nest_targets(flat)


def load_merge_adapter(path: str, cfg: ModelConfig, base_params):
    """Baseline single-adapter path: fold one adapter artifact into the
    base weights at load time (train/lora.py apply_lora — exactly what
    the trainer's merge would produce). The parity oracle for the pooled
    batched path, and the zero-overhead way to serve ONE tenant."""
    err = adapter_artifact_ok(path)
    if err is not None:
        raise AdapterLoadError(err)
    from runbooks_tpu.train.checkpoint import CheckpointManager
    from runbooks_tpu.train.lora import LoraConfig, apply_lora

    mgr = CheckpointManager(path)
    try:
        full = mgr.restore(None)
    finally:
        mgr.close()
    lora = (full.get("params") if isinstance(full, dict)
            else getattr(full, "params", None))
    if not isinstance(lora, dict) or not lora:
        raise AdapterLoadError(
            f"adapter {path!r}: checkpoint holds no LoRA params tree")
    lora = jax.tree.map(jnp.asarray, lora)
    meta = read_adapter_meta(path)
    rank = int(meta.get("rank",
                        np.shape(next(iter(lora.values()))["a"])[-1]))
    lcfg = LoraConfig(rank=rank, alpha=float(meta.get("alpha", 16.0)),
                      targets=tuple(lora))
    return jax.jit(lambda p, ab: apply_lora(p, ab, lcfg))(base_params,
                                                          lora)


class AdapterPool:
    """Host-side manager for the HBM-resident adapter pool. Driven from
    the single engine worker thread like the engine itself; the counters
    /metrics reads are plain ints, safe to read racily. ``requests`` is
    additionally lock-guarded because submit() (HTTP handler threads)
    counts into it while the worker thread swaps lanes."""

    def __init__(self, cfg: ModelConfig, pool_size: int, rank: int,
                 targets: Tuple[str, ...], root: Optional[str] = None,
                 loader=None):
        self.cfg = cfg
        self.pool_size = pool_size
        self.rank = rank
        self.targets = tuple(targets)
        if cfg.moe_num_experts and any(t.startswith("mlp.")
                                       for t in self.targets):
            raise ValueError(
                "adapter pools cannot inject mlp targets on an MoE "
                "model (the expert FFN has no single target matrix); "
                "restrict lora_targets to attention")
        # Fail at construction on targets the architecture lacks.
        for t in self.targets:
            target_dims(cfg, t)
        self.root = root
        self._loader = loader or (lambda path: load_adapter_tree(
            path, self.cfg, self.targets, self.rank))
        self.tree = init_adapter_pool(cfg, self.pool_size, self.rank,
                                      self.targets)
        self._write = jax.jit(make_pool_write_fn(), donate_argnums=(0,))
        self._lane_name: List[Optional[str]] = [None] * self.pool_size
        self._lane_ref = [0] * self.pool_size          # pinned by slots
        self._lane_used = [0] * self.pool_size         # LRU clock stamps
        self._clock = 0
        self._by_name: Dict[str, int] = {}
        self.loads = 0        # artifact reads -> HBM splices
        self.evictions = 0    # resident adapters displaced
        self.hits = 0         # acquires served from residency
        self._req_lock = threading.Lock()
        self.requests: Dict[str, int] = {}   # guarded-by: _req_lock

    # -- observability -------------------------------------------------

    @property
    def resident_count(self) -> int:
        return sum(1 for n in self._lane_name if n is not None)

    def resident(self) -> List[str]:
        return [n for n in self._lane_name if n is not None]

    def stats(self) -> dict:
        with self._req_lock:
            requests = dict(self.requests)
        return {"pool_size": self.pool_size, "rank": self.rank,
                "resident": self.resident(), "loads": self.loads,
                "evictions": self.evictions, "hits": self.hits,
                "requests": requests}

    def count_request(self, name: str) -> None:
        with self._req_lock:
            self.requests[name] = self.requests.get(name, 0) + 1

    def request_counts(self) -> Dict[str, int]:
        with self._req_lock:
            return dict(self.requests)

    def pool_bytes(self) -> int:
        return sum(int(x.nbytes) for x in jax.tree.leaves(self.tree))

    # -- name resolution -----------------------------------------------

    def resolve(self, name: str) -> str:
        """Adapter name -> artifact path: absolute paths pass through,
        relative names join the configured adapter root (Server param
        ``adapter_dir``)."""
        if os.path.isabs(name) or self.root is None:
            return name
        return os.path.join(self.root, name)

    def can_resolve(self, name: str) -> Optional[str]:
        """Pre-admission check for submit()-time 400s: None when the
        adapter is resident or its artifact looks loadable."""
        if name in self._by_name:
            return None
        return adapter_artifact_ok(self.resolve(name))

    # -- residency -----------------------------------------------------

    def _touch(self, lane: int) -> None:
        self._clock += 1
        self._lane_used[lane] = self._clock

    def _victim_lane(self) -> Optional[int]:
        """Lane to (re)use: an empty lane first, else the LRU lane no
        in-flight request pins. None = every lane pinned (the caller
        leaves the request queued — admission backpressure, exactly the
        paged engine's pages-exhausted discipline)."""
        for lane, name in enumerate(self._lane_name):
            if name is None:
                return lane
        candidates = [lane for lane in range(self.pool_size)
                      if self._lane_ref[lane] == 0]
        if not candidates:
            return None
        return min(candidates, key=lambda lane: self._lane_used[lane])

    def acquire(self, name: str) -> Optional[int]:
        """Pin ``name``'s lane for one request, paging the adapter in
        from artifact storage if it is not resident. Returns the lane,
        or None when the pool is exhausted (every lane pinned). Raises
        AdapterLoadError when the artifact itself cannot load."""
        lane = self._by_name.get(name)
        if lane is not None:
            self.hits += 1
            self._lane_ref[lane] += 1
            self._touch(lane)
            return lane
        lane = self._victim_lane()
        if lane is None:
            return None
        adapter = self._loader(self.resolve(name))
        old = self._lane_name[lane]
        if old is not None:
            self.evictions += 1
            del self._by_name[old]
        # One compiled splice program regardless of lane or tenant
        # (warmed by engine warmup); donated pool -> in-place update.
        self.tree = self._write(self.tree, adapter, jnp.int32(lane))
        self._lane_name[lane] = name
        self._by_name[name] = lane
        self._lane_ref[lane] = 1
        self._touch(lane)
        self.loads += 1
        return lane

    def release(self, lane: int) -> None:
        if lane < 0:
            return
        if self._lane_ref[lane] <= 0:
            raise RuntimeError(f"release of unpinned adapter lane {lane}")
        self._lane_ref[lane] -= 1

    def reset_refs(self) -> None:
        """Crash recovery (engine.reset()): every in-flight request was
        doomed, so no lane is pinned anymore. Residency survives — the
        pool tree is never donated to the engine's jitted steps, so its
        buffers are valid even after a failed step."""
        self._lane_ref = [0] * self.pool_size

    def warm(self) -> None:
        """Compile the lane-splice program ahead of traffic (engine
        warmup calls this inside the sentinel's expected() window): a
        first adapter load under traffic must swap lanes, never compile.
        Writes zeros into lane 0 — pre-traffic every lane is zero, so
        content is unchanged. The zero operands are float32 np-backed
        arrays, EXACTLY the signature load_adapter_tree produces (the
        splice casts to the pool dtype internally), so runtime loads hit
        this one compiled program."""
        zero = jax.tree.map(lambda x: jnp.asarray(np.zeros(
            (x.shape[0],) + x.shape[2:], np.float32)), self.tree)
        with obs_device.SENTINEL.expected():
            self.tree = self._write(self.tree, zero, jnp.int32(0))
