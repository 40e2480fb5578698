"""Device-level observability: what happens below the dispatch boundary.

PR-5/6 built the host- and fleet-side telemetry planes; this module covers
the three device-side blind spots that dominate at-scale failures
(arXiv:2011.03641 §"compilation", arXiv:1909.09756 §startup — PAPERS.md):

- **Recompilation sentinel** (:data:`SENTINEL`): a process-wide compile
  tracker fed by ``jax.monitoring`` duration events. Every backend compile
  counts into ``xla_compilations_total`` / ``xla_compile_seconds``; once a
  component declares itself *steady* (the serve engine after warmup, the
  trainer after its first step), any further compile outside an
  :meth:`CompileSentinel.expected` block is a serve-time stall — it fires a
  loud log line, ``xla_unexpected_compiles_total``, and a trace instant.
  The engine's whole compile discipline ("no recompiles at serve time",
  serve/engine.py) stops being a comment and becomes a measured counter.

- **HBM / memory accounting**: per-device ``memory_stats()`` gauges
  (``device_memory_*``) plus a ``jax.live_arrays()`` census that attributes
  bytes to caller-named groups (weights / KV cache / optimizer state /
  other). On CPU ``memory_stats()`` is absent — the census alone still
  answers "what is holding the bytes".

- **Roofline attribution**: per-compiled-program ``cost_analysis()`` FLOPs
  and HBM bytes (captured from the *lowering*, no second backend compile),
  rolled into arithmetic intensity and a compute- vs bandwidth-bound
  classification against the chip's peak FLOP/s and HBM bandwidth
  (utils/hw.py). The engine's "decode is HBM-bound" claim becomes the
  ``xla_program_bandwidth_bound`` gauge; analytic MFU cross-checks the
  wall-clock MFU the trainer/bench report.

Everything degrades gracefully off-TPU; see docs/observability.md
("Device-level metrics") for the catalog and PromQL.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from runbooks_tpu.obs import metrics as obs_metrics
from runbooks_tpu.obs import trace as obs_trace

# The jax.monitoring event one backend (XLA) compile emits; its value is
# the compile wall time in seconds.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Compile times run from ~10 ms (tiny CPU programs) to minutes (pod-scale
# train steps); the default latency buckets top out at 30 s.
_COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                    10.0, 30.0, 60.0, 120.0, 300.0)

# Counted (no value) when a compile request is served from the persistent
# compilation cache instead of compiling.
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# ---------------------------------------------------------------------------
# Recompilation sentinel
# ---------------------------------------------------------------------------

class CompileSentinel:
    """Process-wide compiled-program tracker + post-warmup compile alarm.

    ``install()`` hooks ``jax.monitoring``; every backend compile then
    counts into the registry. Components call ``mark_steady(name)`` when
    their compile phase is over (warmup done / first step folded); from
    then on a compile outside an ``expected()`` block increments
    ``xla_unexpected_compiles_total``, prints a loud line, and emits a
    trace instant — on a serving path that compile just stalled every
    in-flight request for its duration.

    ``expected()`` is thread-local: JAX compiles on the thread that traced
    the call, so the engine worker's intentional background prefix warms
    (serve/api.py ``_warm_one``) and the trainer's checkpoint machinery
    wrap themselves without masking compiles from other threads.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False                 # guarded-by: _lock
        self._degraded: Optional[str] = None    # guarded-by: _lock
        # component -> number of live claimants. Counted, not boolean:
        # two engines in one process both mark "serve"; the first one
        # stopping must not blind the sentinel for the survivor.
        self._steady: Dict[str, int] = {}       # guarded-by: _lock
        self._local = threading.local()
        self.total = 0                          # guarded-by: _lock
        self.unexpected = 0                     # guarded-by: _lock
        self.compile_seconds = 0.0              # guarded-by: _lock
        # Compile requests answered by the persistent compilation cache
        # (utils/jax_cache.py) — the warm-restart evidence warmup reports.
        self.cache_hits = 0                     # guarded-by: _lock
        # Ring of the most recent unexpected-compile records (operators
        # read it via /debug/programs; tests assert on it).
        self.last_unexpected: List[dict] = []   # guarded-by: _lock

    # -- lifecycle ------------------------------------------------------

    def install(self) -> bool:
        """Idempotently hook jax.monitoring. Returns True when the
        monitoring feed is live; False when this jax build has no usable
        monitoring API (the sentinel then still serves the census and
        steady bookkeeping, it just cannot observe compiles)."""
        with self._lock:
            if self._installed:
                return self._degraded is None
            self._installed = True
            # Zero-init both counters: a PromQL increase()/rate() alert
            # needs the series to exist BEFORE the first onset, and the
            # healthy state (zero unexpected compiles) must be a visible
            # 0, not an absent series.
            reg = obs_metrics.REGISTRY
            reg.inc("xla_compilations_total", 0.0,
                    help_text="Backend (XLA) compiles in this process.")
            reg.inc("xla_unexpected_compiles_total", 0.0,
                    help_text="Compiles after a component marked steady — "
                              "each one stalled live work for its "
                              "duration.")
            try:
                import jax.monitoring

                jax.monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                jax.monitoring.register_event_listener(self._on_event)
            except Exception as exc:  # noqa: BLE001 — degrade, don't crash
                self._degraded = repr(exc)
                print(f"device-obs: jax.monitoring unavailable ({exc!r}); "
                      "compile sentinel degraded to census-only",
                      flush=True)
                return False
            return True

    def mark_steady(self, component: str) -> None:
        """Declare `component`'s compile phase over: compiles from here on
        are stalls unless wrapped in expected(). Each mark pairs with one
        clear_steady (refcounted per component)."""
        with self._lock:
            self._steady[component] = self._steady.get(component, 0) + 1

    def clear_steady(self, component: Optional[str] = None) -> None:
        """Withdraw one steadiness claim (run ended / engine stopped).
        None force-clears every component (tests)."""
        with self._lock:
            if component is None:
                self._steady.clear()
            elif component in self._steady:
                self._steady[component] -= 1
                if self._steady[component] <= 0:
                    del self._steady[component]

    def steady_components(self) -> List[str]:
        with self._lock:
            return sorted(self._steady)

    def recent_unexpected(self) -> List[dict]:
        """Snapshot of the last-unexpected ring. The live list mutates
        under the lock on whichever thread compiles; callers (the
        /debug/programs handler serializing during a compile storm) must
        not iterate the shared object."""
        with self._lock:
            return [dict(r) for r in self.last_unexpected]

    @contextlib.contextmanager
    def expected(self):
        """Mark compiles on THIS thread as intentional (warmup sweeps,
        background prefix warms, checkpoint plumbing)."""
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        try:
            yield
        finally:
            self._local.depth = depth

    # -- event feed -----------------------------------------------------

    def _on_event(self, name: str, **kw) -> None:
        if name == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def _on_duration(self, name: str, value: float, **kw) -> None:
        if name != COMPILE_EVENT:
            return
        reg = obs_metrics.REGISTRY
        with self._lock:
            self.total += 1
            self.compile_seconds += float(value)
            steady = sorted(self._steady)
        reg.inc("xla_compilations_total",
                help_text="Backend (XLA) compiles in this process.")
        reg.observe("xla_compile_seconds", float(value),
                    buckets=_COMPILE_BUCKETS,
                    help_text="Backend compile wall time per program.")
        if not steady or getattr(self._local, "depth", 0):
            return
        with self._lock:
            self.unexpected += 1
            record = {"seconds": round(float(value), 3),
                      "steady": steady, "time": time.time()}
            self.last_unexpected.append(record)
            del self.last_unexpected[:-16]
        reg.inc("xla_unexpected_compiles_total",
                help_text="Compiles after a component marked steady — "
                          "each one stalled live work for its duration.")
        print(f"device-obs: UNEXPECTED XLA COMPILE ({value:.2f}s) after "
              f"steady mark ({','.join(steady)}) — a compile here stalls "
              "every in-flight request/step for its duration; see "
              "docs/troubleshooting.md (xla_unexpected_compiles_total)",
              flush=True)
        obs_trace.instant("unexpected_compile",
                          seconds=round(float(value), 3),
                          steady=",".join(steady))


SENTINEL = CompileSentinel()


# ---------------------------------------------------------------------------
# Compiled-program census + roofline costs
# ---------------------------------------------------------------------------

class ProgramTracker:
    """Census of the jitted entry points each component runs, with their
    live compiled-variant counts (``fn._cache_size()``) and per-shape
    roofline costs. The registry view is the ``xla_programs`` /
    ``xla_program_*`` gauge families; /debug/programs renders the same
    data as a table."""

    def __init__(self):
        self._lock = threading.Lock()
        # (component, name) ->
        #   {"fn_ref": weakref-to-jitted-fn | None, "costs": {sig: cost}}
        self._programs: Dict[Tuple[str, str], dict] = {}  # guarded-by: _lock
        # (registry id, component) -> program names last exported there,
        # so set_gauges can DROP series whose program died/re-registered
        # instead of leaving a dead model's numbers on the exposition.
        self._exported: Dict[Tuple[int, Optional[str]], set] = {}  # guarded-by: _lock

    @staticmethod
    def _make_ref(fn: Any):
        if fn is None:
            return None
        try:
            # WEAK reference on purpose: a jitted fn's closure pins its
            # owner (the engine's decode fns capture the engine — params
            # and KV pool included). A strong ref here would keep a
            # discarded engine's HBM alive until process exit.
            return weakref.ref(fn)
        except TypeError:
            return lambda: fn

    def register(self, component: str, name: str, fn: Any) -> None:
        """(Re-)register a jitted entry point. Registration RESETS the
        recorded costs: a rebuilt engine / fresh run may carry a
        different model config behind the same program name, and serving
        the previous model's FLOPs for it would silently falsify the
        roofline gauges. The owner re-records at its warmup."""
        with self._lock:
            self._programs[(component, name)] = {
                "fn_ref": self._make_ref(fn), "costs": {}}

    def record_cost(self, component: str, name: str, shape_sig: str,
                    cost: Optional[dict]) -> None:
        if cost is None:
            return
        with self._lock:
            entry = self._programs.setdefault(
                (component, name), {"fn_ref": None, "costs": {}})
            entry["costs"][shape_sig] = dict(cost)

    def has_cost(self, component: str, name: str, shape_sig: str) -> bool:
        with self._lock:
            entry = self._programs.get((component, name))
            return bool(entry and shape_sig in entry["costs"])

    def census(self, component: Optional[str] = None) -> List[dict]:
        out = []
        doomed = []
        with self._lock:
            items = sorted(self._programs.items())
        for (comp, name), entry in items:
            fn = entry["fn_ref"]() if entry["fn_ref"] is not None else None
            if entry["fn_ref"] is not None and fn is None:
                # The owning engine/run was garbage-collected: its
                # programs are gone, so the census row is too.
                doomed.append((comp, name))
                continue
            if component is not None and comp != component:
                continue
            variants = None
            try:
                if fn is not None and hasattr(fn, "_cache_size"):
                    variants = int(fn._cache_size())
            except Exception:  # noqa: BLE001 — census must not crash
                variants = None
            out.append({"component": comp, "name": name,
                        "programs": variants,
                        "costs": {k: dict(v)
                                  for k, v in entry["costs"].items()}})
        if doomed:
            with self._lock:
                for key in doomed:
                    entry = self._programs.get(key)
                    if entry is not None and entry["fn_ref"] is not None \
                            and entry["fn_ref"]() is None:
                        del self._programs[key]
        return out

    def set_gauges(self, registry: Optional[obs_metrics.Registry] = None,
                   component: Optional[str] = None) -> None:
        """Mirror the census into the registry (call at scrape time).

        Each program's series are dropped before being re-set, and
        programs gone from the census (engine rebuilt / garbage-
        collected) have their series dropped entirely — a dead model's
        FLOPs must not keep rendering as live gauges."""
        reg = registry if registry is not None else obs_metrics.REGISTRY
        census = self.census(component)
        live = {(e["component"], e["name"]) for e in census}
        key = (id(reg), component)
        with self._lock:
            gone = self._exported.get(key, set()) - live
            self._exported[key] = live
        for comp, name in gone:
            reg.drop_series(component=comp, program=name)
        for entry in census:
            labels = {"component": entry["component"],
                      "program": entry["name"]}
            # Clear stale values first: a re-registered program with no
            # recorded costs yet must not show its predecessor's numbers.
            reg.drop_series(**labels)
            if entry["programs"] is not None:
                reg.set_gauge("xla_programs", entry["programs"],
                              help_text="Live compiled variants per jitted "
                                        "entry point.", **labels)
            costs = entry["costs"]
            if not costs:
                continue
            # One gauge per program: the largest shape is the one that
            # bounds memory/time (warmup walks shapes smallest-last only
            # for prefill rows; max-flops is the stable choice).
            cost = max(costs.values(), key=lambda c: c.get("flops", 0.0))
            reg.set_gauge("xla_program_flops", cost.get("flops", 0.0),
                          help_text="Analytic FLOPs per invocation "
                                    "(cost_analysis).", **labels)
            reg.set_gauge("xla_program_hbm_bytes",
                          cost.get("hbm_bytes", 0.0),
                          help_text="Analytic bytes accessed per "
                                    "invocation (cost_analysis).", **labels)
            if cost.get("arithmetic_intensity") is not None:
                reg.set_gauge("xla_program_arithmetic_intensity",
                              cost["arithmetic_intensity"],
                              help_text="FLOPs per byte accessed.",
                              **labels)
            if cost.get("bound"):
                reg.set_gauge("xla_program_bandwidth_bound",
                              int(cost["bound"] == "bandwidth"),
                              help_text="1 when the program sits left of "
                                        "the roofline ridge (HBM-bound).",
                              **labels)


PROGRAMS = ProgramTracker()


def cost_analysis_of(fn, *args, **kwargs) -> Optional[dict]:
    """FLOPs / bytes-accessed for one jitted call at these arg shapes,
    from the *lowering's* cost analysis — tracing only, no second backend
    compile (donated buffers are safe: nothing executes). Returns None
    when the backend offers no analysis (some plugin backends)."""
    try:
        lowered = fn.lower(*args, **kwargs)
        analysis = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — optional telemetry, never fatal
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    flops = float(analysis.get("flops", 0.0) or 0.0)
    hbm = float(analysis.get("bytes accessed", 0.0) or 0.0)
    return {"flops": flops, "hbm_bytes": hbm}


def classify_roofline(flops: float, hbm_bytes: float,
                      peak_flops: Optional[float] = None,
                      hbm_bytes_per_sec: Optional[float] = None) -> dict:
    """Roofline classification of one program: arithmetic intensity
    (FLOPs/byte) against the ridge point (peak FLOP/s ÷ HBM bandwidth).
    Left of the ridge the program cannot saturate the MXU no matter how
    good the schedule — it is **bandwidth**-bound; right of it, compute-
    bound. Peaks default to the current device's; off-TPU there are none,
    and the fields that need one (ridge, bound, min_seconds) are absent."""
    ai = flops / hbm_bytes if hbm_bytes > 0 else float("inf")
    out = {"arithmetic_intensity": round(ai, 3)}
    if peak_flops is None or hbm_bytes_per_sec is None:
        peaks = device_peaks()
        if peaks is None:
            return out
        peak_flops, hbm_bytes_per_sec = peaks
    ridge = peak_flops / hbm_bytes_per_sec
    out["ridge"] = round(ridge, 3)
    out["bound"] = "bandwidth" if ai < ridge else "compute"
    # Best achievable time: max of the compute and the memory roofline.
    out["min_seconds"] = max(flops / peak_flops,
                             hbm_bytes / hbm_bytes_per_sec)
    return out


def device_peaks() -> Optional[Tuple[float, float]]:
    """(peak FLOP/s, HBM bytes/s) across ALL local devices; None off-TPU
    (utils/hw.chip_peaks — an unknown TPU raises there). Whole-process
    totals on purpose: cost_analysis FLOPs cover the whole (SPMD) module,
    and the trainer's wall-clock MFU normalizes by chip peak × device count
    (train/trainer.py) — analytic MFU must use the same convention or the
    cross-check can never agree on a multi-chip mesh. The ridge (peak ÷
    bandwidth) is per-chip either way, since both totals scale by the
    device count."""
    import jax

    from runbooks_tpu.utils.hw import chip_peaks

    devices = jax.devices()
    peaks = chip_peaks(devices[0])
    if peaks is None:
        return None
    return peaks[0] * len(devices), peaks[1] * len(devices)


def program_cost(component: str, name: str, shape_sig: str, fn,
                 *args, **kwargs) -> Optional[dict]:
    """Capture-and-record one program shape's roofline cost (idempotent
    per shape signature — re-warms skip the re-trace). Returns the cost
    dict (with classification folded in) or None."""
    if PROGRAMS.has_cost(component, name, shape_sig):
        return None
    cost = cost_analysis_of(fn, *args, **kwargs)
    if cost is None:
        return None
    cost.update(classify_roofline(cost["flops"], cost["hbm_bytes"]))
    PROGRAMS.record_cost(component, name, shape_sig, cost)
    return cost


# ---------------------------------------------------------------------------
# HBM / memory accounting
# ---------------------------------------------------------------------------

def device_memory_stats() -> List[dict]:
    """Per-device allocator stats. TPU/GPU backends report bytes in use /
    peak / limit; CPU's ``memory_stats()`` returns None — the entry then
    carries only identity, and callers fall back to the live-array census
    (the documented CPU degradation path)."""
    import jax

    out: List[dict] = []
    for d in jax.devices():
        entry: dict = {"device": str(getattr(d, "id", "?")),
                       "kind": getattr(d, "device_kind", ""),
                       "platform": getattr(d, "platform", "")}
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — not all backends implement it
            stats = None
        if stats:
            in_use = stats.get("bytes_in_use")
            limit = (stats.get("bytes_limit")
                     or stats.get("bytes_reservable_limit"))
            peak = stats.get("peak_bytes_in_use")
            if in_use is not None:
                entry["bytes_in_use"] = int(in_use)
            if peak is not None:
                entry["peak_bytes_in_use"] = int(peak)
            if limit:
                entry["bytes_limit"] = int(limit)
                if in_use is not None:
                    entry["headroom_bytes"] = int(limit) - int(in_use)
        out.append(entry)
    return out


def set_memory_gauges(registry: Optional[obs_metrics.Registry] = None
                      ) -> List[dict]:
    """Mirror device_memory_stats() into ``device_memory_*`` gauges
    (labeled per device) and return the entries. Devices without stats
    set nothing — an absent series IS the CPU-degradation signal."""
    reg = registry if registry is not None else obs_metrics.REGISTRY
    entries = device_memory_stats()
    for e in entries:
        if "bytes_in_use" not in e:
            continue
        labels = {"device": e["device"]}
        reg.set_gauge("device_memory_bytes_in_use", e["bytes_in_use"],
                      help_text="Allocator bytes currently in use "
                                "(memory_stats).", **labels)
        if "peak_bytes_in_use" in e:
            reg.set_gauge("device_memory_peak_bytes",
                          e["peak_bytes_in_use"],
                          help_text="Allocator high-water mark.", **labels)
        if "bytes_limit" in e:
            reg.set_gauge("device_memory_bytes_limit", e["bytes_limit"],
                          help_text="Allocator byte limit (HBM capacity "
                                    "share).", **labels)
            reg.set_gauge("device_memory_headroom_bytes",
                          e.get("headroom_bytes", 0),
                          help_text="bytes_limit - bytes_in_use.", **labels)
    return entries


def shard_local_nbytes(arr) -> int:
    """Per-device bytes one device holds of ``arr`` under its sharding.
    Pure metadata (``sharding.shard_shape`` — no device sync, no
    transfer): a [H, D] weight sharded 2-way over its head axis reports
    half its logical ``nbytes``; replicated and single-device arrays
    report the full amount. Falls back to logical bytes when the
    sharding doesn't expose shard shapes (committed host arrays etc.)."""
    try:
        shape = arr.sharding.shard_shape(tuple(arr.shape))
        out = int(getattr(arr.dtype, "itemsize", 1))
        for d in shape:
            out *= int(d)
        return out
    except Exception:  # noqa: BLE001 — metadata probe only
        return int(getattr(arr, "nbytes", 0))


def _tree_array_ids(tree: Any) -> set:
    """ids of the jax.Array leaves of an arbitrary pytree (QuantizedArray,
    KVCache etc. are registered pytrees, so tree.leaves walks them)."""
    import jax

    ids = set()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            ids.add(id(leaf))
    return ids


def live_array_census(groups: Optional[Dict[str, Any]] = None) -> dict:
    """Attribute every live jax.Array's bytes to caller-named groups.

    ``groups`` maps a name ("weights", "kv_cache", "optimizer", …) to a
    pytree whose leaves should be charged to it; anything live that
    belongs to no group lands in ``other``. Bytes are logical
    (``nbytes``); a group's number is exact, the categories + ``other``
    sum to ``total_bytes`` by construction. Deleted (donated-away)
    arrays are skipped — they hold no memory.

    ``by_category_per_device`` / ``total_per_device_bytes`` carry the
    same attribution in PER-DEVICE bytes (shard_local_nbytes): under a
    serving mesh a sharded weight or KV pool costs each chip only its
    shard, and per-chip HBM — not the logical total — is what fits or
    OOMs. On one device (or fully replicated) the two views agree."""
    import jax

    group_ids = {name: _tree_array_ids(tree)
                 for name, tree in (groups or {}).items()}
    by_group = {name: 0 for name in group_ids}
    by_group_local = {name: 0 for name in group_ids}
    by_group_counts = {name: 0 for name in group_ids}
    total = 0
    total_local = 0
    count = 0
    for arr in jax.live_arrays():
        try:
            if arr.is_deleted():
                continue
            nbytes = int(arr.nbytes)
        except Exception:  # noqa: BLE001 — racing a deletion
            continue
        local = shard_local_nbytes(arr)
        total += nbytes
        total_local += local
        count += 1
        aid = id(arr)
        for name, ids in group_ids.items():
            if aid in ids:
                by_group[name] += nbytes
                by_group_local[name] += local
                by_group_counts[name] += 1
                break
    categorized = sum(by_group.values())
    by_group["other"] = total - categorized
    by_group_local["other"] = total_local - sum(by_group_local.values())
    by_group_counts["other"] = count - sum(by_group_counts.values())
    return {"total_bytes": total, "arrays": count,
            "total_per_device_bytes": total_local,
            "by_category": by_group,
            "by_category_per_device": by_group_local,
            "array_counts": by_group_counts}


def memory_snapshot(groups: Optional[Dict[str, Any]] = None) -> dict:
    """One self-contained memory picture: device allocator stats + the
    live-array attribution census. This is what GET /debug/memory returns
    and what /debug/profile bundles beside the XLA trace."""
    return {"devices": device_memory_stats(),
            "live_arrays": live_array_census(groups)}
