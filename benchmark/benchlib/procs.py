"""Children of the harness: the serving or training process that holds the
chip, then the checker. One at a time; every one is stopped on every exit
path. Each output line is stamped with the host clock on arrival."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import threading
import time

_CHILDREN: list = []


def _as_json(text: str):
    """The object an output line holds, or None."""
    if not text.startswith("{"):
        return None
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        return None


class Child:
    def __init__(self, name: str, argv: list, env: dict, cwd: str,
                 log_dir: str):
        os.makedirs(log_dir, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(log_dir, name + ".log")
        self.lines: list = []          # (monotonic arrival, text)
        self._cond = threading.Condition()
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            argv, env=env, cwd=cwd, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, start_new_session=True)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        _CHILDREN.append(self)

    def _read(self) -> None:
        with open(self.log_path, "w") as log:
            for raw in self.proc.stdout:
                now = time.monotonic()
                text = raw.decode(errors="replace").rstrip("\n")
                log.write(text + "\n")
                log.flush()
                with self._cond:
                    self.lines.append((now, text))
                    self._cond.notify_all()
        with self._cond:
            self._cond.notify_all()

    def json_lines(self) -> list:
        """(arrival, object) for every output line that is a JSON object."""
        out = []
        with self._cond:
            snapshot = list(self.lines)
        for t, text in snapshot:
            obj = _as_json(text)
            if obj is not None:
                out.append((t, obj))
        return out

    def wait_json(self, pred, timeout: float):
        """First JSON line satisfying pred, as (arrival, object); None when
        the child exits or the time runs out first."""
        deadline = time.monotonic() + timeout
        seen = 0
        while True:
            with self._cond:
                snapshot = self.lines[seen:]
                seen += len(snapshot)
            for t, text in snapshot:
                obj = _as_json(text)
                if obj is not None and pred(obj):
                    return t, obj
            if self.proc.poll() is not None and not self._reader.is_alive():
                return None
            left = deadline - time.monotonic()
            if left <= 0:
                return None
            with self._cond:
                if len(self.lines) == seen:
                    self._cond.wait(min(left, 0.5))

    def alive(self) -> bool:
        return self.proc.poll() is None

    def tail(self, n: int = 25) -> str:
        with self._cond:
            return "\n".join(text for _, text in self.lines[-n:])

    def stop(self, grace_s: float = 90.0) -> int:
        """SIGTERM, wait, SIGKILL the whole group if it lingers."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace_s)
            except subprocess.TimeoutExpired:
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(30)
        self._reader.join(10)
        return self.proc.returncode

    def wait(self, timeout: float) -> int:
        try:
            self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            return self.stop(5)
        self._reader.join(10)
        return self.proc.returncode


def stop_all() -> None:
    for child in _CHILDREN:
        if child.alive():
            try:
                os.killpg(child.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            child.proc.wait(30)


def child_env(root: str, extra: dict = None) -> dict:
    """The environment of a child: the program importable from the
    checkout, unbuffered, the compile cache where JAX_COMPILATION_CACHE_DIR
    says or else at the fixed <checkout>/.jax_cache (the program's own
    default). BENCH_RUN is the driver's, and is not passed on."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env
