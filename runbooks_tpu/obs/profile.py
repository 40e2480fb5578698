"""On-demand TPU/XLA profiler capture (``jax.profiler`` trace).

Two triggers, both writing XProf/TensorBoard-loadable traces under
``{artifacts}/profiles/``:

- Serve API: ``POST /debug/profile?seconds=N`` captures N seconds of live
  traffic (serve/api.py wires it; returns the capture directory).
- Trainer: ``RBT_PROFILE_AT_STEP=n[:k]`` captures k steps (default 1)
  starting at step n — an env-only knob, so an operator can profile a
  misbehaving run by editing the Job env without touching the validated
  spec. (The spec-level ``profile_start``/``profile_stop`` window from the
  TrainJobConfig still works; this is the on-demand path.)

The net-new capability vs the reference, which has no profiling hooks at
all (SURVEY.md §5.1): answering "is this run input-bound or compute-bound"
from a trace instead of a debugger.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional, Tuple


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (jax.profiler supports one trace at
    a time per process); serve/api.py maps this to HTTP 409."""


class Profiler:
    """Thread-safe single-capture guard over jax.profiler start/stop."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None  # guarded-by: _lock

    @property
    def active_dir(self) -> Optional[str]:
        with self._lock:
            return self._active_dir

    def start(self, log_dir: str, python_tracer: bool = False) -> str:
        """Begin a capture. While it runs, every obs.trace span is also a
        TraceAnnotation in it (host spans on the profiler's clock, beside
        the device planes). The Python tracer is off unless asked for:
        with the program's own spans in the trace its frames are no longer
        the only host names, and it slows the threads it instruments."""
        import jax

        from runbooks_tpu.obs import trace as obs_trace

        with self._lock:
            if self._active_dir is not None:
                raise ProfilerBusy(
                    f"a profile capture is already writing to "
                    f"{self._active_dir}")
            os.makedirs(log_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python_tracer else 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
            self._active_dir = log_dir
            obs_trace.set_annotator(jax.profiler.TraceAnnotation)
        # Inside the capture and in the ring / trace.jsonl at once: the one
        # event that carries both clocks, so an operator can lay the
        # wall-clock events of those sinks over this capture.
        obs_trace.instant("profile.start", dir=log_dir,
                          unix_ns=time.time_ns())
        return log_dir

    def stop(self) -> Optional[str]:
        import jax

        from runbooks_tpu.obs import trace as obs_trace

        with self._lock:
            if self._active_dir is None:
                return None
            obs_trace.set_annotator(None)
            try:
                jax.profiler.stop_trace()
            finally:
                log_dir, self._active_dir = self._active_dir, None
        obs_trace.instant("profile.stop", dir=log_dir)
        # Self-contained bundle: snapshot the device memory state
        # (memory_stats() + live-array census) beside the XLA trace, so
        # "what was resident while this trace ran" travels with the
        # capture instead of needing a live process to ask.
        try:
            import json

            from runbooks_tpu.obs import device as obs_device

            with open(os.path.join(log_dir, "memory.json"), "w") as f:
                json.dump(obs_device.memory_snapshot(), f, indent=2)
        except Exception as exc:  # noqa: BLE001 — the trace still stands
            print(f"profile: memory snapshot failed: {exc!r}", flush=True)
        return log_dir

    def capture(self, log_dir: str, seconds: float,
                python_tracer: bool = False) -> str:
        """Blocking timed capture: start, sleep, stop. Call off the event
        loop (the serve API runs it in an executor)."""
        self.start(log_dir, python_tracer)
        try:
            time.sleep(max(seconds, 0.0))
        finally:
            self.stop()
        return log_dir


PROFILER = Profiler()


def profiles_dir(artifacts: Optional[str] = None) -> str:
    from runbooks_tpu.utils import contract

    return os.path.join(artifacts or contract.artifacts_dir(), "profiles")


def capture_dir(artifacts: Optional[str] = None,
                tag: Optional[str] = None) -> str:
    """A fresh capture directory: profiles/<utc-stamp>[-tag]."""
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    name = f"{stamp}-{tag}" if tag else stamp
    return os.path.join(profiles_dir(artifacts), name)


def parse_profile_at_step(
        spec: Optional[str] = None) -> Optional[Tuple[int, int]]:
    """``RBT_PROFILE_AT_STEP=n[:k]`` -> (start_step, num_steps). k defaults
    to 1. Malformed values raise at parse time (before training state
    exists), like RBT_FAULT_INJECT."""
    if spec is None:
        spec = os.environ.get("RBT_PROFILE_AT_STEP", "")
    if not spec:
        return None
    step, _, count = spec.partition(":")
    try:
        n = int(step)
        k = int(count) if count else 1
    except ValueError:
        raise ValueError(
            f"RBT_PROFILE_AT_STEP={spec!r}: expected n or n:k "
            "(capture k steps starting at step n)") from None
    if n < 0 or k < 1:
        raise ValueError(
            f"RBT_PROFILE_AT_STEP={spec!r}: step must be >= 0, count >= 1")
    return n, k
