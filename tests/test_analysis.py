"""Static analyzer (`rbt check`) tests: every lint rule and every
program-contract check proven to FIRE on a seeded violation and to stay
QUIET on clean code, plus the tier-1 gate that the repo itself audits
clean (docs/static-analysis.md).
"""

import json
import os
import textwrap

import pytest

from runbooks_tpu.analysis.findings import (
    Finding,
    Suppression,
    apply_baseline,
    load_baseline,
)
from runbooks_tpu.analysis.lint import lint_source


def _lint(src: str, rel: str = "runbooks_tpu/some/module.py"):
    return lint_source(textwrap.dedent(src), rel)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

LOCKED_CLASS = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._jobs = []  # guarded-by: _lock

        def add(self, j):
            with self._lock:
                self._jobs.append(j)
"""


def test_lock_discipline_fires_on_unguarded_access():
    findings = _lint(LOCKED_CLASS + """
        def steal(self):
            return list(self._jobs)
    """)
    assert _rules(findings) == ["lock-discipline"]
    assert "_jobs" in findings[0].message
    assert "with self._lock" in findings[0].message


def test_lock_discipline_quiet_when_guarded():
    assert _lint(LOCKED_CLASS) == []


def test_lock_discipline_init_exempt():
    # __init__ assigns guarded attrs before any other thread exists.
    assert _lint(LOCKED_CLASS) == []


def test_lock_discipline_nested_with_and_release():
    findings = _lint(LOCKED_CLASS + """
        def late(self):
            with self._lock:
                ok = self._jobs
            return self._jobs  # lock released above
    """)
    assert _rules(findings) == ["lock-discipline"]


def test_lock_discipline_lock_held_helper_annotation():
    findings = _lint(LOCKED_CLASS + """
        def _drain_locked(self):  # guarded-by: _lock
            self._jobs.clear()
    """)
    assert findings == []


def test_lock_discipline_inline_ignore_with_reason():
    findings = _lint(LOCKED_CLASS + """
        def peek(self):
            # rbt-check: ignore[lock-discipline] len() is GIL-atomic here
            return len(self._jobs)
    """)
    assert findings == []


def test_unannotated_attrs_not_audited():
    findings = _lint("""
        import threading

        class Free:
            def __init__(self):
                self._lock = threading.Lock()
                self._jobs = []

            def steal(self):
                return list(self._jobs)
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# async-blocking
# ---------------------------------------------------------------------------

def test_async_blocking_fires_on_time_sleep():
    findings = _lint("""
        import time

        async def handler(request):
            time.sleep(1)
    """)
    assert _rules(findings) == ["async-blocking"]
    assert "time.sleep" in findings[0].message


@pytest.mark.parametrize("call", [
    "fut.result()",
    "worker._thread.join()",
    "subprocess.run(cmd)",
    "requests.get(url)",
    "urllib.request.urlopen(url)",
])
def test_async_blocking_fires_on(call):
    findings = _lint(f"""
        async def handler(fut, worker, cmd, url):
            {call}
    """)
    assert _rules(findings) == ["async-blocking"]


def test_async_blocking_quiet_on_clean_async():
    findings = _lint("""
        import asyncio

        async def handler(request, fut):
            await asyncio.sleep(1)
            await asyncio.wrap_future(fut)
            return "-".join(["a", "b"])
    """)
    assert findings == []


def test_async_blocking_nested_sync_def_exempt():
    # A sync def nested in a coroutine runs in an executor/thread.
    findings = _lint("""
        import time

        async def handler(loop):
            def blocking():
                time.sleep(1)
            await loop.run_in_executor(None, blocking)
    """)
    assert findings == []


def test_async_blocking_nested_async_def_reported_once():
    # The nested coroutine gets its own visitor pass; the outer pass
    # must not descend into it too (double-reporting would let one
    # baseline suppression silently cover both copies).
    findings = _lint("""
        import time

        async def outer():
            async def inner():
                time.sleep(1)
            await inner()
    """)
    assert _rules(findings) == ["async-blocking"]


def test_sync_def_not_audited_for_blocking():
    findings = _lint("""
        import time

        def worker():
            time.sleep(1)
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# device-sync
# ---------------------------------------------------------------------------

HOT_SYNC = """
    import numpy as np

    def step(self, x):
        return np.asarray(x)
"""


def test_device_sync_fires_on_hot_paths():
    for rel in ("runbooks_tpu/serve/engine.py", "runbooks_tpu/train/step.py"):
        findings = _lint(HOT_SYNC, rel)
        assert _rules(findings) == ["device-sync"], rel


@pytest.mark.parametrize("call", [
    "x.item()", "x.block_until_ready()", "jax.block_until_ready(x)",
    "jax.device_get(x)",
])
def test_device_sync_variants(call):
    findings = _lint(f"""
        import jax

        def step(x):
            return {call}
    """, "runbooks_tpu/serve/engine.py")
    assert _rules(findings) == ["device-sync"]


def test_device_sync_quiet_off_hot_path():
    assert _lint(HOT_SYNC, "runbooks_tpu/train/trainer.py") == []


def test_device_sync_inline_ignore():
    findings = _lint("""
        import numpy as np

        def step(self, x):
            # rbt-check: ignore[device-sync] dispatch boundary
            return np.asarray(x)
    """, "runbooks_tpu/serve/engine.py")
    assert findings == []


# ---------------------------------------------------------------------------
# rng-layout
# ---------------------------------------------------------------------------

RNG_JIT = """
    import jax

    def make(shardings):
        def init_fn(rng):
            return jax.random.normal(rng, (4, 4))
        return jax.jit(init_fn, out_shardings=shardings)
"""


def test_rng_layout_fires_outside_scope():
    findings = _lint(RNG_JIT)
    assert _rules(findings) == ["rng-layout"]
    assert "layout_invariant_init" in findings[0].message


def test_rng_layout_quiet_inside_scope():
    findings = _lint("""
        import jax

        def make(shardings):
            def init_fn(rng):
                return jax.random.normal(rng, (4, 4))
            with layout_invariant_init():
                return jax.jit(init_fn, out_shardings=shardings)
    """)
    assert findings == []


def test_rng_layout_quiet_without_out_shardings():
    findings = _lint("""
        import jax

        def make():
            def init_fn(rng):
                return jax.random.normal(rng, (4, 4))
            return jax.jit(init_fn)
    """)
    assert findings == []


def test_rng_layout_quiet_for_non_rng_body():
    findings = _lint("""
        import jax

        def make(shardings):
            def step_fn(x):
                return x + 1
            return jax.jit(step_fn, out_shardings=shardings)
    """)
    assert findings == []


def test_rng_layout_covers_init_callees():
    findings = _lint("""
        import jax

        def make(cfg, shardings):
            def init_fn(rng):
                return init_params(cfg, rng)
            return jax.jit(init_fn, out_shardings=shardings)
    """)
    assert _rules(findings) == ["rng-layout"]


# ---------------------------------------------------------------------------
# bare-except / swallowed-error / ignore-reason
# ---------------------------------------------------------------------------

def test_bare_except_fires():
    findings = _lint("""
        def f():
            try:
                g()
            except:
                return None
    """)
    assert _rules(findings) == ["bare-except"]


def test_swallowed_error_fires_on_silent_broad_except():
    findings = _lint("""
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert _rules(findings) == ["swallowed-error"]


def test_swallowed_error_quiet_with_justifying_comment():
    for handler in ("    except Exception:  # probe only\n        pass\n",
                    "    except Exception:\n        pass  # probe only\n"):
        src = "def f():\n    try:\n        g()\n" + handler
        findings = lint_source(src, "runbooks_tpu/some/module.py")
        assert findings == [], handler


def test_swallowed_error_quiet_when_handled():
    findings = _lint("""
        def f():
            try:
                g()
            except Exception as exc:
                log(exc)
    """)
    assert findings == []


def test_narrow_except_not_audited():
    findings = _lint("""
        def f():
            try:
                g()
            except OSError:
                pass
    """)
    assert findings == []


def test_ignore_without_reason_is_flagged():
    findings = _lint("""
        import time

        async def handler():
            time.sleep(1)  # rbt-check: ignore[async-blocking]
    """)
    assert _rules(findings) == ["ignore-reason"]


def test_syntax_error_reported_not_raised():
    findings = lint_source("def f(:\n", "runbooks_tpu/x.py")
    assert _rules(findings) == ["syntax"]


# ---------------------------------------------------------------------------
# findings model: baseline suppression
# ---------------------------------------------------------------------------

def _finding(rule="lock-discipline", path="runbooks_tpu/a.py",
             message="self._x accessed outside lock"):
    return Finding(rule=rule, path=path, line=3, message=message)


def test_apply_baseline_suppresses_and_reports_stale():
    hit = Suppression(rule="lock-discipline", path="runbooks_tpu/a.py",
                      reason="intentional")
    stale = Suppression(rule="device-sync", path="runbooks_tpu/b.py",
                        reason="fixed long ago")
    active, suppressed, stale_out = apply_baseline(
        [_finding(), _finding(rule="bare-except")], [hit, stale])
    assert _rules(active) == ["bare-except"]
    assert _rules(suppressed) == ["lock-discipline"]
    assert stale_out == [stale]


def test_baseline_contains_scopes_suppression():
    s = Suppression(rule="lock-discipline", path="runbooks_tpu/a.py",
                    reason="r", contains="_y")
    active, suppressed, _ = apply_baseline([_finding()], [s])
    assert len(active) == 1 and not suppressed


def test_load_baseline_rejects_reasonless_entries(tmp_path):
    p = tmp_path / "check_baseline.json"
    p.write_text(json.dumps(
        {"suppressions": [{"rule": "x", "path": "y"}]}))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(p))


def test_load_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == []


# ---------------------------------------------------------------------------
# program contracts (synthetic seeded violations)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jnp():
    return pytest.importorskip("jax.numpy")


def _audit(fn, *args):
    import jax

    from runbooks_tpu.analysis.program import AuditSettings, audit_jaxpr

    closed = jax.make_jaxpr(fn)(*args)
    settings = AuditSettings(f32_upcast_bytes=1 << 12,
                             const_bytes=1 << 12)
    return audit_jaxpr(closed, "test/prog", settings)


def test_program_callback_fires(jnp):
    import jax

    def f(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    findings, flags = _audit(f, jnp.zeros((4,), jnp.float32))
    assert "program-callback" in _rules(findings)
    assert flags["callbacks"] >= 1


def test_program_dtype_fires_on_large_bf16_upcast(jnp):
    def f(x):
        return x.astype(jnp.float32) * 2.0  # 64*64*4 B > 4 KiB threshold

    findings, flags = _audit(f, jnp.zeros((64, 64), jnp.bfloat16))
    assert "program-dtype" in _rules(findings)
    assert flags["f32_upcasts"] == 1


def test_program_dtype_quiet_on_small_accumulator(jnp):
    def f(x):
        # A scalar-ish LSE/norm accumulator: upcast under the threshold.
        return x.astype(jnp.float32).sum()

    findings, flags = _audit(f, jnp.zeros((8,), jnp.bfloat16))
    assert findings == []
    assert flags["f32_upcasts"] == 0


def test_program_dtype_quiet_on_f32_inputs(jnp):
    def f(x):
        return x.astype(jnp.float32) * 2.0

    findings, _ = _audit(f, jnp.zeros((64, 64), jnp.float32))
    assert findings == []


def test_program_const_fires_on_big_embedded_constant(jnp):
    import numpy as np

    table = jnp.asarray(np.ones((64, 64), np.float32))  # 16 KiB closure

    def f(x):
        return x + table

    findings, flags = _audit(f, jnp.zeros((64, 64), jnp.float32))
    assert "program-const" in _rules(findings)
    assert flags["const_bytes_max"] >= 64 * 64 * 4


def test_program_clean_fn_is_quiet(jnp):
    def f(x, w):
        return x @ w

    findings, flags = _audit(f, jnp.zeros((8, 8), jnp.bfloat16),
                             jnp.zeros((8, 8), jnp.bfloat16))
    assert findings == []
    assert flags == {"callbacks": 0, "f32_upcasts": 0,
                     "const_bytes_max": 0}


def test_program_callback_found_inside_scan(jnp):
    import jax

    def f(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c)
            return c + 1, c
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    findings, _ = _audit(f, jnp.zeros((), jnp.float32))
    assert "program-callback" in _rules(findings)


# ---------------------------------------------------------------------------
# census drift
# ---------------------------------------------------------------------------

def _census(sigs=3, flags=None):
    return {"settings": {"config": "debug"},
            "programs": [{"component": "serve", "name": "prefill",
                          "signatures": sigs,
                          "flags": flags or {"callbacks": 0,
                                             "f32_upcasts": 0,
                                             "const_bytes_max": 0}}]}


def test_diff_census_missing_baseline():
    from runbooks_tpu.analysis.program import diff_census

    findings = diff_census(_census(), None, "config/program_baseline.json")
    assert _rules(findings) == ["program-census-drift"]
    assert "missing" in findings[0].message


def test_diff_census_clean_on_match():
    from runbooks_tpu.analysis.program import diff_census

    assert diff_census(_census(), _census(), "b.json") == []


def test_diff_census_flags_signature_growth():
    from runbooks_tpu.analysis.program import diff_census

    findings = diff_census(_census(sigs=5), _census(sigs=3), "b.json")
    assert _rules(findings) == ["program-census-drift"]
    assert "drifted" in findings[0].message


def test_diff_census_flags_new_and_vanished_programs():
    from runbooks_tpu.analysis.program import diff_census

    grown = _census()
    grown["programs"].append({"component": "serve", "name": "decode_v2",
                              "signatures": 1, "flags": None})
    findings = diff_census(grown, _census(), "b.json")
    assert any("new program" in f.message for f in findings)
    findings = diff_census(_census(), grown, "b.json")
    assert any("vanished" in f.message for f in findings)


# ---------------------------------------------------------------------------
# the repo itself: `rbt check --strict` is clean, abstract, and cheap
# ---------------------------------------------------------------------------

def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_repo_audits_clean_with_zero_compiles():
    """The tier-1 gate behind `make check`: the repo at HEAD has no
    active findings, no stale suppressions, and the program audit
    performs ZERO XLA backend compiles (sentinel-verified abstract
    tracing)."""
    from runbooks_tpu.analysis.check import run_check

    report = run_check(_repo_root())
    assert report.active == [], "\n".join(f.render() for f in report.active)
    assert report.stale == []
    assert report.compiles == 0
    assert report.exit_code(strict=True) == 0
    # The committed baseline covers exactly the audited program set.
    names = {(p["component"], p["name"])
             for p in report.census["programs"]}
    assert ("serve", "prefill") in names
    assert ("train", "train_step") in names
    assert ("train", "lora_step") in names
    assert ("serve", "hybrid_prefill") in names
    assert ("serve", "hybrid_decode") in names


def test_hybrid_programs_are_audited_and_the_rest_of_the_census_stands():
    """A layer pattern's prefill and decode programs are traced with the
    recurrent cache leaves beside k / v; every row the census had before
    them (PR 25's committed baseline, by digest) is what it was, but that
    since PR 45 the four prefill rows count the 9 shapes admission can
    dispatch at the default budget, not 5 buckets x 2 row counts
    (serve/engine.dispatch_shapes)."""
    import hashlib
    import json

    from runbooks_tpu.analysis.program import (
        AuditSettings,
        _engine_specs,
        load_program_baseline,
    )

    specs = {s["name"]: s for s in _engine_specs(AuditSettings())}
    for name in ("hybrid_prefill", "hybrid_decode"):
        pool = specs[name]["args"][1]
        assert pool.state.shape[0] == 3 and pool.state.dtype == "float32"
        assert pool.conv.shape[2] == 3 and pool.k.shape[0] == 1
        assert isinstance(specs[name]["args"][0]["linear_layers"], list)
    assert specs["prefill"]["args"][1].state is None
    base = load_program_baseline(os.path.join(
        _repo_root(), "config", "program_baseline.json"))
    old = [p for p in base["programs"]
           if not p["name"].startswith(("hybrid_", "sparse_latent_",
                                        "window_full_"))]
    assert len(old) == 31 and len(base["programs"]) == 37
    assert hashlib.sha256(json.dumps(old, sort_keys=True).encode()
                          ).hexdigest() == (
        "ea12e82ea1a1035e8061ef7dbb0f43df71f6b6fa942d142d5a65d364bca2e4f1")


def test_sparse_latent_programs_are_audited():
    """PR 30's two programs are traced with the latent leaf in place of
    k / v, a leading-layer stack and a share of the experts; the counts
    are among their results."""
    from runbooks_tpu.analysis.program import AuditSettings, _engine_specs

    specs = {s["name"]: s for s in _engine_specs(AuditSettings())}
    for name in ("sparse_latent_prefill", "sparse_latent_decode"):
        params, pool = specs[name]["args"][:2]
        assert pool.k.shape[0] == 0 and pool.latent.shape[0] == 3
        assert pool.latent.shape[-1] == 80 and pool.state is None
        assert params["layers"]["moe"]["wi_gate"].shape[:2] == (2, 8)
        assert params["layers"]["moe"]["router"].shape == (2, 128, 16)
        assert params["leading_layers"]["mlp"]["wi_gate"].shape[0] == 1
    assert specs["prefill"]["args"][1].latent is None


def test_window_full_programs_are_audited():
    """PR 32's two programs are traced with the ring leaves beside K/V of
    another head count and width, a stack a position of the period and a
    share of the experts."""
    from runbooks_tpu.analysis.program import AuditSettings, _engine_specs

    specs = {s["name"]: s for s in _engine_specs(AuditSettings())}
    for name in ("window_full_prefill", "window_full_decode"):
        params, pool = specs[name]["args"][:2]
        assert pool.ring_k.shape == (3, 2, 16, 4, 24)
        assert pool.ring_v.shape == (3, 2, 16, 4, 16)
        assert pool.k.shape == (2, 2, 257, 2, 24) and pool.v.shape[-1] == 16
        assert len(params["window_layers"]) == 3
        assert params["window_layers"][0]["attn"]["sink"].shape == (1, 8)
        assert params["window_layers"][0]["moe"]["wi_gate"].shape[:2] == (
            1, 8)
        assert "sink" not in params["layers"]["attn"]
    assert specs["prefill"]["args"][1].ring_k is None


def test_program_baseline_roundtrip(tmp_path):
    """--write-baseline then re-check: drift gate green immediately
    after regeneration, red after tampering."""
    from runbooks_tpu.analysis.program import (
        diff_census,
        load_program_baseline,
        write_program_baseline,
    )

    path = str(tmp_path / "program_baseline.json")
    census = _census()
    write_program_baseline(path, census)
    assert diff_census(census, load_program_baseline(path), path) == []
    tampered = load_program_baseline(path)
    tampered["programs"][0]["signatures"] += 1
    assert diff_census(census, tampered, path) != []


def test_cli_check_strict_exits_zero(capsys, monkeypatch):
    from runbooks_tpu.cli.main import main

    monkeypatch.chdir(_repo_root())
    rc = main(["check", "--strict", "--no-programs"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 active" in out


def test_cli_check_json_reports_census(capsys, monkeypatch):
    from runbooks_tpu.cli.main import main

    monkeypatch.chdir(_repo_root())
    rc = main(["check", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["active"] == []
    assert data["compiles"] == 0
    assert len(data["census"]["programs"]) >= 6


def test_cli_check_nonzero_on_seeded_violation(tmp_path, capsys,
                                               monkeypatch):
    """A fresh violation fails the gate: seeded repo with one blocking
    call in an async handler -> exit 1 and the finding rendered."""
    from runbooks_tpu.cli.main import main

    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "runbooks_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import time\n\n\nasync def handler():\n    time.sleep(1)\n")
    monkeypatch.chdir(tmp_path)
    rc = main(["check", "--no-programs"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "async-blocking" in out


def test_monitoring_outage_is_not_a_vacuous_pass(monkeypatch, capsys):
    """When jax.monitoring is unavailable the zero-compile assertion
    cannot be verified: the report says so and `rbt check` prints
    UNVERIFIED instead of a confident 0."""
    from runbooks_tpu.analysis.check import run_check
    from runbooks_tpu.cli.main import main
    from runbooks_tpu.obs import device as obs_device

    monkeypatch.setattr(obs_device.SENTINEL, "install", lambda: False)
    report = run_check(_repo_root(), lint=False)
    assert report.monitoring is False
    monkeypatch.chdir(_repo_root())
    assert main(["check", "--no-lint"]) == 0  # findings still gate
    assert "UNVERIFIED" in capsys.readouterr().out


def test_strict_flags_stale_suppression(tmp_path, monkeypatch, capsys):
    """A suppression whose violation was fixed must be removed: --strict
    exits 2 on it, non-strict stays green."""
    from runbooks_tpu.cli.main import main

    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    (tmp_path / "runbooks_tpu").mkdir()
    cfg = tmp_path / "config"
    cfg.mkdir()
    (cfg / "check_baseline.json").write_text(json.dumps({
        "suppressions": [{"rule": "async-blocking",
                          "path": "runbooks_tpu/gone.py",
                          "reason": "was fixed; entry forgotten"}]}))
    monkeypatch.chdir(tmp_path)
    assert main(["check", "--no-programs"]) == 0
    assert main(["check", "--no-programs", "--strict"]) == 2
    assert "stale suppression" in capsys.readouterr().out
