"""Bring-up guards (ISSUE 21): nothing may make a run pass without the
device, and the compile cache is placed from outside.

Covers: enable_compilation_cache's three placements and a real two-process
warm read on CPU; benchkit failing when the inner run fails (and carrying no
fallback); chip_smoke.py parsing, refusing a CPU at full settings, and the
exact form of its result line. The
unknown-device_kind check lives with the roofline tests
(tests/test_device_obs.py::test_no_peaks_off_tpu).
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import benchkit
from runbooks_tpu.utils import jax_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config(monkeypatch):
    """Run with the suite's RBT_JAX_CACHE=0 lifted, record every
    jax.config.update, and restore the cache directory afterwards so later
    tests in this process never read a warm cache."""
    monkeypatch.delenv("RBT_JAX_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    calls = {}
    real_update = jax.config.update

    def spy(name, value):
        calls[name] = value
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    yield calls
    real_update("jax_compilation_cache_dir", before)


def test_cache_dir_from_env_sets_nothing(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_cache.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in cache_config
    assert cache_config["jax_persistent_cache_min_compile_time_secs"] == 0.2


def test_cache_dir_default_is_fixed_in_checkout(cache_config, monkeypatch,
                                                tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax_cache, "DEFAULT_CACHE_DIR",
                        str(tmp_path / ".jax_cache"))
    assert jax_cache.enable_compilation_cache() == str(tmp_path / ".jax_cache")
    assert cache_config["jax_compilation_cache_dir"] == \
        str(tmp_path / ".jax_cache")
    assert os.path.isdir(tmp_path / ".jax_cache")
    # The real default: one fixed path under the repo root, git-ignored.
    monkeypatch.undo()
    assert jax_cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read()


def test_cache_disabled_and_failure_not_swallowed(cache_config, monkeypatch,
                                                  tmp_path):
    monkeypatch.setenv("RBT_JAX_CACHE", "0")
    assert jax_cache.enable_compilation_cache() is None
    assert cache_config == {}
    # A cache that cannot be set up raises instead of "disabling" itself.
    monkeypatch.delenv("RBT_JAX_CACHE")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("")
    monkeypatch.setattr(jax_cache, "DEFAULT_CACHE_DIR",
                        str(blocker / ".jax_cache"))
    with pytest.raises(OSError):
        jax_cache.enable_compilation_cache()


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.utils.jax_cache import enable_compilation_cache
enable_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
obs_device.SENTINEL.install()
jax.jit(lambda x: jnp.tanh(x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
print(obs_device.SENTINEL.cache_hits)
"""


def test_cache_works_on_cpu_across_processes(tmp_path):
    """The CPU opt-out is gone: on jaxlib 0.9.0 a second process reads what
    the first one wrote (the sentinel's cache_hits counter sees it)."""
    env = {**benchkit.cpu_env(), "PYTHONPATH": REPO,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    env.pop("RBT_JAX_CACHE", None)
    hits = [int(subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, check=True,
        capture_output=True, text=True, timeout=120).stdout.split()[-1])
        for _ in range(2)]
    assert hits[0] == 0 and hits[1] >= 1, hits


def test_benchkit_fails_when_inner_fails(tmp_path, monkeypatch):
    bad = tmp_path / "bad_bench.py"
    bad.write_text("import sys\nprint('backend exploded', file=sys.stderr)\n"
                   "sys.exit(1)\n")
    with pytest.raises(benchkit.BenchFailed, match="backend exploded"):
        benchkit.measure_outer(str(bad))
    with pytest.raises(SystemExit) as exc:
        benchkit.run_outer(str(bad))
    assert exc.value.code == 1
    good = tmp_path / "good_bench.py"
    good.write_text("print('{\"value\": 3}')\n")
    assert benchkit.measure_outer(str(good)) == {"value": 3}
    # No preflight, retry or fallback left to hide a missing device.
    source = open(benchkit.__file__).read().lower()
    for gone in ("relay", "socket", "fallback_metric", "retry",
                 '"platform": "none"'):
        assert gone not in source, gone


def test_chip_smoke_parses_and_refuses_cpu():
    smoke = os.path.join(REPO, "chip_smoke.py")
    out = subprocess.run([sys.executable, smoke, "--help"], check=True,
                         capture_output=True, text=True, timeout=60).stdout
    assert "--chips" in out and "--tiny" in out
    # Full settings on a CPU: the identity gate fails before any server or
    # trainer starts, exit code non-zero, and no result line on stdout.
    proc = subprocess.run([sys.executable, smoke], env=benchkit.cpu_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    last = proc.stdout.strip().splitlines()[-1]
    assert not last.startswith("{") or "ok" not in json.loads(last)


def test_chip_smoke_result_line_is_exactly_ok_and_device(
        tmp_path, monkeypatch, capsys):
    """The checker reads the last stdout line of a passing run: one JSON
    object with the keys ok and device and nothing else (the set-up facts
    go on the line before). Phases are stubbed; no child starts."""
    import chip_smoke

    def agree(sz, chips, tiny, hw, facts):
        facts["device"] = {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": chips}
        facts["agree"] = {"stub": True}

    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "logs"))
    monkeypatch.setattr(chip_smoke, "agree_phase", agree)
    monkeypatch.setattr(chip_smoke, "serve_phase", lambda *a: None)
    monkeypatch.setattr(chip_smoke, "train_phase", lambda *a: None)
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert lines[-2].startswith("chip_smoke: summary {")
    # A failed phase: exit 1 and no result line at all.
    monkeypatch.setattr(chip_smoke, "train_phase", lambda *a: chip_smoke.check(
        False, "stub failure"))
    assert chip_smoke.main([]) == 1
    assert not capsys.readouterr().out.strip().splitlines()[-1].startswith("{")
