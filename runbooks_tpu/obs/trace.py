"""Lightweight JSONL trace spans (Chrome ``trace_event`` compatible).

One span mechanism, three sinks. ``RBT_TRACE=1`` turns FILE emission on;
independent of that switch, every ``span`` also tees into the in-memory
flight-recorder ring (obs/flight.py, always on unless ``RBT_FLIGHT=0``)
so the recent timeline survives for ``/debug/flight``, tail sampling, and
incident bundles; and while a profiler capture of ``obs.profile.PROFILER``
is in flight every span also opens a ``jax.profiler.TraceAnnotation``, so
the capture holds the program's own spans on the profiler's clock beside
the device planes. ``obs/profile.py`` arms and disarms that sink through
``set_annotator`` — this module imports no JAX (the gateway imports it).
With every sink off a span is a near-zero-cost no-op (one attribute test,
one env lookup + one shared null context manager per span, so the
instrumented hot loops — trainer steps, engine ticks, reconciles — pay
nothing when recording is off).

``fine`` is ``span`` for phases too frequent for the ring (the children of
an engine tick): a capture and ``RBT_TRACE=1`` record them, the ring never
does, so its depth in minutes stays what it was.

File format: the Chrome/Perfetto "JSON Array Format" with one event per
line — an opening ``[`` line, then ``{...},`` per event. The spec allows
the closing ``]`` to be omitted, so the file is loadable in Perfetto /
chrome://tracing at any moment (including mid-run or after a crash), and
each line (minus the trailing comma) is a complete JSON object — greppable
and streamable like any JSONL log.

Multi-pod merges: events carry a *trace pid* derived from host+pid (not
the bare OS pid), so concatenating trace files from a gateway and N
replica pods cannot collide two processes onto one Perfetto track; each
file generation opens with ``process_name``/``thread_name`` metadata
events (``ph: "M"``) naming the component, host, and real pid.

Default output: ``{artifacts}/trace.jsonl`` (the container contract's
durable mount); ``configure(path)`` repoints it (the trainer does, per
run). Writes are lock-serialized line appends, so concurrent spans from
the engine worker, checkpoint threads, and reconcilers interleave without
tearing.

Rotation: a long-running traced server would otherwise grow the file
without bound. When the file exceeds ``RBT_TRACE_MAX_MB`` (default 256)
it rolls to ``<path>.1`` (one generation kept, the previous ``.1``
replaced) and a fresh file starts with its own ``[`` header — both
generations stay independently Perfetto-loadable and line-parseable.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import socket
import threading
import time
import uuid
from typing import Optional, Tuple

from runbooks_tpu.obs import flight


def trace_enabled() -> bool:
    """Read the switch per call (not cached at import): tests and operators
    flip RBT_TRACE around individual runs."""
    return os.environ.get("RBT_TRACE", "") == "1"


# jax.profiler.TraceAnnotation while a capture of obs.profile.PROFILER is
# in flight, None otherwise. Set and cleared by Profiler.start/stop only.
_ANNOTATE = None


def set_annotator(factory) -> None:
    """Arm (a ``TraceAnnotation``-like class) or disarm (None) the
    profiler sink. obs/profile.py owns the one call site of each."""
    global _ANNOTATE
    _ANNOTATE = factory


def record_enabled() -> bool:
    """True when span events go ANYWHERE (profiler capture, trace file or
    flight ring) — the gate hot paths use before materializing span
    attributes (request-id lists etc.)."""
    return (_ANNOTATE is not None or trace_enabled()
            or flight.recording())


def fine_enabled() -> bool:
    """The same gate for ``fine`` spans, which never enter the ring."""
    return _ANNOTATE is not None or trace_enabled()


def _annotation(name: str, args: dict):
    """An entered TraceAnnotation for the capture in flight, or None.
    Its arguments become event stats: lists are joined with spaces (the
    annotation encodes ``name#k=v,k=v#``, so a comma would split a
    value)."""
    factory = _ANNOTATE
    if factory is None:
        return None
    flat = {k: (" ".join(map(str, v)) if isinstance(v, (list, tuple))
                else v) for k, v in args.items()}
    try:
        ann = factory(name, **flat)
        ann.__enter__()
    except Exception:  # noqa: BLE001 — tracing never takes the work down
        return None
    return ann


class _NullSpan:
    """Shared no-op context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL = _NullSpan()


# -- trace pid (multi-pod merge safety) -------------------------------------

_TRACE_PID: Optional[Tuple[int, int]] = None  # (os pid, derived trace pid)


def trace_pid() -> int:
    """A stable 31-bit pid derived from host+pid: unique enough that
    merged traces from many pods don't collapse processes onto one
    Perfetto track (two hosts routinely share os pids like 1). Fork-safe
    (re-derived when os.getpid() changes)."""
    global _TRACE_PID
    pid = os.getpid()
    if _TRACE_PID is None or _TRACE_PID[0] != pid:
        digest = hashlib.sha1(
            f"{socket.gethostname()}:{pid}".encode()).digest()
        _TRACE_PID = (pid,
                      (int.from_bytes(digest[:4], "big") & 0x7FFFFFFF) or 1)
    return _TRACE_PID[1]


def _tid() -> int:
    return threading.get_ident() & 0x7FFFFFFF


def _max_trace_bytes() -> int:
    """Rotation threshold from RBT_TRACE_MAX_MB (default 256; fractional
    values allowed — tests rotate at a few hundred bytes). Read per open,
    not per write."""
    try:
        return int(float(os.environ.get("RBT_TRACE_MAX_MB", "256")) * 2**20)
    except ValueError:
        return 256 * 2**20


class _Writer:
    def __init__(self):
        self._lock = threading.Lock()
        self._path: Optional[str] = None   # guarded-by: _lock
        self._file = None                  # guarded-by: _lock
        self._bytes = 0                    # guarded-by: _lock
        self._max_bytes = 0                # guarded-by: _lock
        self._meta_tids: set = set()       # guarded-by: _lock

    def configure(self, path: Optional[str]) -> None:
        with self._lock:
            if self._file is not None and path != self._path:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
            self._path = path

    def path(self) -> Optional[str]:
        with self._lock:
            if self._path is not None:
                return self._path
        from runbooks_tpu.utils import contract

        return os.path.join(contract.artifacts_dir(), "trace.jsonl")

    def _write_line_locked(self, obj: dict) -> None:  # guarded-by: _lock
        line = json.dumps(obj, separators=(",", ":"))
        self._file.write(line + ",\n")
        self._bytes += len(line) + 2

    def _write_meta_locked(self, tid: Optional[int]) -> None:  # guarded-by: _lock
        """Perfetto metadata for this file generation: one process_name
        naming component@host + the real pid, then one thread_name per
        tid seen — merged multi-pod traces stay attributable even though
        events carry the derived trace pid."""
        ident = flight.identity()
        ts = round(time.time() * 1e6, 1)  # tolerated on M events; keeps
        # every line uniform for line-oriented consumers
        if not self._meta_tids:
            self._write_line_locked({
                "name": "process_name", "ph": "M", "ts": ts,
                "pid": trace_pid(), "tid": 0,
                "args": {"name": f"{ident['component']}@{ident['host']} "
                                 f"pid={ident['pid']}"}})
            self._meta_tids.add(0)
        if tid is not None and tid not in self._meta_tids:
            self._write_line_locked({
                "name": "thread_name", "ph": "M", "ts": ts,
                "pid": trace_pid(), "tid": tid,
                "args": {"name": f"{ident['component']}-{tid}"}})
            self._meta_tids.add(tid)

    def write(self, event: dict) -> None:
        with self._lock:
            if self._file is None:
                path = self._path
                if path is None:
                    from runbooks_tpu.utils import contract

                    path = os.path.join(contract.artifacts_dir(),
                                        "trace.jsonl")
                    self._path = path
                try:
                    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                    size = (os.path.getsize(path)
                            if os.path.exists(path) else 0)
                    self._file = open(path, "a", buffering=1)
                    if size == 0:
                        self._file.write("[\n")
                        size = 2
                    self._bytes = size
                    self._max_bytes = _max_trace_bytes()
                    self._meta_tids = set()
                except OSError:
                    # Tracing must never take down the workload: an
                    # unwritable path drops this event. The CONFIGURED
                    # path is kept (resetting it would silently reroute
                    # the rest of the run's spans to the contract-default
                    # location); the next write retries the open — e.g. a
                    # not-yet-mounted artifacts volume heals in place.
                    return
            try:
                self._write_meta_locked(event.get("tid"))
                self._write_line_locked(event)
                if self._bytes >= self._max_bytes:
                    self._rotate_locked()
            except OSError:
                pass

    def _rotate_locked(self) -> None:  # guarded-by: _lock
        """Size cap hit: roll the live file to <path>.1 (replacing the
        previous generation) and start fresh. Caller holds the lock; the
        open failure mode matches write() — drop and retry later."""
        path = self._path
        try:
            self._file.close()
        except OSError:
            pass
        self._file = None
        if path is None:
            return
        try:
            os.replace(path, path + ".1")
            self._file = open(path, "a", buffering=1)
            self._file.write("[\n")
            self._bytes = 2
            self._meta_tids = set()
        except OSError:
            self._file = None

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None


_WRITER = _Writer()


def configure(path: Optional[str]) -> None:
    """Repoint trace output (e.g. the trainer sets
    ``{artifacts}/trace.jsonl`` for its run). None reverts to the
    contract default."""
    _WRITER.configure(path)


def close() -> None:
    """Flush and close the trace file (end of a run; the next span
    reopens in append mode)."""
    _WRITER.close()


def write_event(event: dict) -> None:
    """Write one already-built event to the trace file REGARDLESS of
    RBT_TRACE — the tail-sampling promotion path (obs/flight.py) uses it
    to land an interesting request's ring timeline on disk."""
    _WRITER.write(event)


def _emit(event: dict, ring: bool = True) -> None:
    """Route one event: the trace file when file tracing is on, the
    flight ring whenever the recorder is (``fine`` spans skip it)."""
    if trace_enabled():
        _WRITER.write(event)
    if ring and flight.recording():
        flight.RING.record(event)


class _Span:
    """One complete event (``ph: "X"``): records wall-clock start and
    monotonic duration, emitted at exit; inside a profiler capture the
    same interval is a TraceAnnotation on this thread's line."""

    __slots__ = ("name", "args", "_ring", "_ts", "_t0", "_ann")

    def __init__(self, name: str, args: dict, ring: bool = True):
        self.name = name
        self.args = args
        self._ring = ring

    def set(self, **args) -> None:
        """Attributes known only once the phase ran (a count of what it
        handled): added to the event and to the annotation."""
        self.args.update(args)
        if self._ann is not None:
            try:
                self._ann.set_metadata(**args)
            except Exception:  # noqa: BLE001
                pass

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        self._ts = time.time() * 1e6          # trace_event ts is in µs
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = (time.perf_counter() - self._t0) * 1e6
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if not (trace_enabled() or (self._ring and flight.recording())):
            return False   # the capture was the only sink
        event = {
            "name": self.name,
            "ph": "X",
            "ts": round(self._ts, 1),
            "dur": round(dur, 1),
            "pid": trace_pid(),
            "tid": _tid(),
        }
        if self.args:
            event["args"] = self.args
        if exc_type is not None:
            event.setdefault("args", {})["error"] = exc_type.__name__
        _emit(event, self._ring)
        return False


def span(name: str, /, **args):
    """Context manager tracing one phase: ``with span("prefill",
    bucket=128): ...``. Emits a Chrome complete event to the trace file
    (RBT_TRACE=1) and/or the flight ring (RBT_FLIGHT, default on), and a
    TraceAnnotation into a profiler capture in flight; otherwise returns
    a shared no-op (no allocation beyond the env reads). ``name`` is
    positional-only so span attributes may freely use "name" as a key
    (e.g. reconcile spans labeling the object name)."""
    if not record_enabled():
        return _NULL
    return _Span(name, args)


def fine(name: str, /, **args):
    """``span`` for a phase inside an engine tick or a trainer step: a
    profiler capture and the trace file record it, the flight ring does
    not. The shared no-op outside both, whether the ring is on or off."""
    if not fine_enabled():
        return _NULL
    return _Span(name, args, ring=False)


def complete(name: str, duration_s: float, /, **args) -> None:
    """Emit a completed span for an interval measured elsewhere, ending
    now (``ph: "X"`` with ts backdated by the duration). Used for
    request-scoped phases whose start predates the code that knows their
    name — e.g. a request's queue wait, measured by the engine at
    admission time."""
    if record_enabled():
        _complete(name, duration_s, args, ring=True)


def _complete(name: str, duration_s: float, args: dict, ring: bool) -> None:
    dur = max(float(duration_s), 0.0) * 1e6
    event = {
        "name": name,
        "ph": "X",
        "ts": round(time.time() * 1e6 - dur, 1),
        "dur": round(dur, 1),
        "pid": trace_pid(),
        "tid": _tid(),
    }
    if args:
        event["args"] = args
    _emit(event, ring)


class PhaseSeconds:
    """Seconds of set-up summed by phase (``startup.weights``,
    ``warmup.trace``): the object the entry points print on their
    start-up and warm-up lines and serve as ``warmup_census["phases"]``.
    No capture runs during set-up, so the durations are kept here; each
    addition is also a ``fine`` event ending now, so that ``RBT_TRACE=1``
    lays set-up on the run's own timeline."""

    def __init__(self):
        self._seconds: dict = {}

    def add(self, name: str, seconds: float, /, **args) -> None:
        self._seconds[name] = self._seconds.get(name, 0.0) + seconds
        if trace_enabled():
            _complete(name, seconds, args, ring=False)

    @contextlib.contextmanager
    def timed(self, name: str, /, **args):
        """Add the time the body took."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0, **args)

    def snapshot(self) -> dict:
        return {k: round(v, 3) for k, v in self._seconds.items()}


# The process's start-up phases (``startup.*``), filled by the entry point
# that runs it (serve/api.main, train/trainer.main) and by nothing else.
STARTUP = PhaseSeconds()


def process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process: what an entry point
    records as ``startup.imports`` once its imports are done. None where
    /proc does not say (the phase is then left out)."""
    try:
        with open("/proc/self/stat") as f:
            # Field 22, counted after the parenthesised command name.
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return None


def make_instant(name: str, /, **args) -> dict:
    """Build (without emitting) an instant event — the tail-sampling
    promoter appends one as the promotion marker."""
    event = {
        "name": name,
        "ph": "i",
        "s": "p",
        "ts": round(time.time() * 1e6, 1),
        "pid": trace_pid(),
        "tid": _tid(),
    }
    if args:
        event["args"] = args
    return event


def instant(name: str, /, **args) -> None:
    """Point-in-time marker (``ph: "i"``): checkpoint landed, preemption
    signal caught, profile started."""
    if not record_enabled():
        return
    ann = _annotation(name, args)
    if ann is not None:       # a zero-length event on the profiler's clock
        ann.__exit__(None, None, None)
    _emit(make_instant(name, **args))


# ---------------------------------------------------------------------------
# Request scope (shared by the serve API and the gateway — the gateway
# must not import serve/api, which pulls the JAX engine stack).
# ---------------------------------------------------------------------------

# W3C trace context (https://www.w3.org/TR/trace-context/):
# version-traceid-parentid-flags, all lowercase hex.
_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")
# Client-supplied ids flow into response headers, logs, and trace JSON:
# strip anything that could split a header or forge a log line.
_RID_UNSAFE_RE = re.compile(r"[^A-Za-z0-9._:/-]")


def request_scope(headers) -> Tuple[str, Optional[str]]:
    """(request_id, traceparent_out) for one HTTP request.

    X-Request-Id is accepted verbatim (sanitized); a W3C ``traceparent``
    is also honored — its trace-id becomes the request id when no
    explicit one came, and the response carries a child ``traceparent``
    (same trace-id, fresh parent-id) so an upstream tracer can stitch
    the hop. With neither header, an id is generated. The id rides the
    queue/prefill/decode trace spans (obs/trace.py) and the access log,
    so one Perfetto trace follows one request across the engine — and,
    through the gateway's forwarded headers, across pods."""
    rid = headers.get("X-Request-Id") if headers else None
    tp_out = None
    tp = (headers.get("traceparent", "") if headers else "").strip().lower()
    m = _TRACEPARENT_RE.match(tp)
    if m:
        tp_out = (f"{m.group(1)}-{m.group(2)}-"
                  f"{uuid.uuid4().hex[:16]}-{m.group(4)}")
        if not rid:
            rid = m.group(2)
    if rid:
        rid = _RID_UNSAFE_RE.sub("", str(rid))[:128]
    if not rid:
        rid = f"req-{uuid.uuid4().hex[:16]}"
    return rid, tp_out


def mint_traceparent() -> str:
    """A fresh root W3C traceparent (sampled flag set) — the gateway
    mints one when the client supplied none, so every upstream hop
    carries a stitchable trace context."""
    return f"00-{uuid.uuid4().hex}-{uuid.uuid4().hex[:16]}-01"
