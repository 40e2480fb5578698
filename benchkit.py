"""Outer bench harness: run the real benchmark in a subprocess.

The orchestrator is stdlib-only and never imports jax, so it never holds
the chip: a chip belongs to one process at a time, and a parent that has
touched JAX would make the ``--inner`` child fail or hang. The inner run
uses whatever backend JAX finds; when that is not a TPU the inner script
fails and so does this harness — there is no fallback that hides the
device. ``RBT_BENCH_FORCE_CPU=1`` is the only way to a CPU run, which
reports counts and correctness, never a rate or utilization under a device
metric's name.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys


class BenchFailed(RuntimeError):
    """The inner benchmark did not produce a result."""


def apply_cpu_env(env=None, n_devices: int = 1):
    """Pin an environment mapping to CPU with n virtual devices. The one
    place the pinning recipe lives (used by the bench orchestrator and
    tests/conftest.py); mutates and returns ``env`` (default: os.environ).

    An existing device-count flag is REPLACED, not kept — though it only
    takes effect if the CPU backend has not been initialized yet."""
    env = os.environ if env is None else env
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    count_flag = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       count_flag, flags)
    else:
        flags = (flags + " " + count_flag).strip()
    env["XLA_FLAGS"] = flags
    return env


def cpu_env(n_devices: int = 1) -> dict:
    """A copy of os.environ pinned to CPU (for subprocesses)."""
    return apply_cpu_env(dict(os.environ), n_devices)


def work_dir(name: str) -> str:
    """A bench's scratch/artifacts directory: one fixed, git-ignored path
    inside the checkout, emptied on entry. Never a temp dir, a pid or a
    timestamp — a run that resumes or restarts must find the same path."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench_work", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def bench_device():
    """(device, on_tpu) for an ``--inner`` run. Exits non-zero when the
    backend is not a TPU unless RBT_BENCH_FORCE_CPU=1 — a bench that finds
    no chip fails, it does not quietly measure the CPU. Imports jax: call
    from the inner process only."""
    import jax

    from runbooks_tpu.utils.hw import on_tpu

    if not on_tpu() and os.environ.get("RBT_BENCH_FORCE_CPU") != "1":
        raise SystemExit(
            f"bench: JAX backend is {jax.default_backend()!r}, not a TPU; "
            "set RBT_BENCH_FORCE_CPU=1 for a CPU functional run")
    return jax.devices()[0], on_tpu()


def emit(result: dict) -> None:
    """Print one result line naming the device it ran on. Off-TPU the
    result is nested under ``cpu_functional_run``: its counts are valid,
    but none of its times or rates may be read as a device metric."""
    from runbooks_tpu.utils.hw import device_identity

    ident = device_identity()
    if ident["platform"] != "tpu":
        result = {"cpu_functional_run": result}
    print(json.dumps({**result, **ident}))


def measure_outer(script: str, extra_env: dict | None = None) -> dict:
    """Run ``script --inner`` once (with ``extra_env`` on top of the
    environment) and return its JSON result line. Raises BenchFailed (with
    the inner stderr tail) on a non-zero exit, a timeout or missing JSON —
    the caller exits non-zero."""
    timeout = float(os.environ.get("RBT_BENCH_TIMEOUT", 1200))
    env = {**os.environ, **(extra_env or {})}
    if env.get("RBT_BENCH_FORCE_CPU") == "1":
        # A multi-chip bench axis (RBT_BENCH_MESH_TENSOR) still needs that
        # many devices on CPU — virtualize them.
        apply_cpu_env(env, max(1, int(env.get("RBT_BENCH_MESH_TENSOR", 1))))
    try:
        proc = subprocess.run(
            [sys.executable, script, "--inner"], env=env, timeout=timeout,
            capture_output=True, text=True)
    except subprocess.TimeoutExpired as exc:
        tail = exc.stderr or ""
        if isinstance(tail, bytes):
            tail = tail.decode("utf-8", "replace")
        raise BenchFailed(
            f"{script}: timeout after {timeout:.0f}s: {tail[-1500:]}") \
            from exc
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        raise BenchFailed(
            f"{script}: rc={proc.returncode}: {proc.stderr[-1500:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    raise BenchFailed(f"{script}: no JSON in stdout: {proc.stdout[-1500:]}")


def run_outer(script: str) -> None:
    """Print ``script``'s result line; exit 1 with the error when the inner
    run failed."""
    try:
        print(json.dumps(measure_outer(script)))
    except BenchFailed as exc:
        print(f"bench failed: {exc}", file=sys.stderr)
        raise SystemExit(1) from exc
