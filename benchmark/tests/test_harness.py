"""The harness end to end on the CPU at a toy size: the result line, no
chip -> no line, and a broken timed path -> `correct` false."""

import json
import os
import subprocess
import sys

import pytest

import run

FIX = os.path.join(os.path.dirname(__file__), "fixtures")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checked"}


def fake_tpu(ident, chips, child):
    """Skip the harness's look for a chip (and only that)."""
    return {"platform": ident["platform"], "kind": "TPU v5 lite",
            "count": int(ident["device_count"])}


def drive(capsys, monkeypatch, workload, seconds="2", argv=None):
    monkeypatch.setattr(run, "require_tpu", fake_tpu)
    if argv is not None:
        monkeypatch.setattr(run, "SERVE_ARGV", argv)
        monkeypatch.setattr(run, "TRAIN_ARGV", argv)
    rc = run.main(["--workload", workload, "--seed", str(2 ** 31 + 5),
                   "--seconds", seconds, "--trace", "0",
                   "--bench-root", FIX])
    out, err = capsys.readouterr()
    return rc, out.strip().splitlines(), err


@pytest.mark.parametrize("workload,metrics", [
    ("tiny_chat", {"ttft_p50_ms", "tpot_p90_ms", "setup_s"}),
    ("tiny_doc", {"serve_tok_s", "setup_s"}),
    ("tiny_lora", {"train_tok_s", "setup_s"}),
])
def test_result_line_has_exactly_the_contracts_keys(capsys, monkeypatch,
                                                    workload, metrics):
    rc, lines, err = drive(capsys, monkeypatch, workload)
    assert rc == 0
    result = json.loads(lines[-1])
    assert set(result) == RESULT_KEYS
    assert set(result["metrics"]) == metrics
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # Every number compared is printed beside its limit, on stdout as it
    # is read, as the last lines of stderr, and last in the result.
    assert any(ln.startswith("check:") and "limit" in ln for ln in lines)
    assert list(result)[-1] == "checked" and len(result["checked"]) >= 5
    assert all(set(n) == {"value", "limit"}
               for n in result["checked"].values())
    last = err.strip().splitlines()[-len(result["checked"]):]
    assert [ln.split()[1] for ln in last] == list(result["checked"])


def test_broken_timed_path_comes_out_not_correct(capsys, monkeypatch):
    rc, lines, err = drive(capsys, monkeypatch, "tiny_chat", argv=[
        sys.executable, os.path.join(FIX, "broken_server.py")])
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert any("served_logit_gap_max" in ln and "OUT" in ln for ln in lines)


def test_step_that_returns_its_state_unchanged_is_not_correct(capsys,
                                                              monkeypatch):
    monkeypatch.setenv("RBT_DEVICE_OBS", "0")
    rc, lines, err = drive(capsys, monkeypatch, "tiny_lora", argv=[
        sys.executable, os.path.join(FIX, "broken_trainer.py")])
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert any("param_change_norm_gap" in ln and "OUT" in ln for ln in lines)
    assert any("first_grad_difference" in ln and "OUT" in ln for ln in lines)


def test_no_chip_exits_non_zero_and_prints_no_line():
    """The real command, nothing patched: the server starts on the CPU,
    says so on its start-up line, and the run ends without a result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(run.BENCH_DIR, "run.py"),
         "--workload", "tiny_chat", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--bench-root", FIX],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "RBT_JAX_CACHE": "0"})
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "not a TPU" in proc.stderr


def test_a_number_held_to_no_limit_has_the_limit_null_in_the_line(capsys):
    """`limits_left_out` of a configuration: inf in the checker's verdict,
    null in the result line, which has to stay plain JSON."""
    import checker

    run.result_line(True, 3, 0, {}, {}, [
        checker.number("served_logit_gap_max", 2.58, float("inf")),
        checker.number("served_logit_gap_mean", 0.126, 0.28)])
    out, err = capsys.readouterr()
    last = out.strip().splitlines()[-1]
    assert "Infinity" not in last
    assert json.loads(last)["checked"] == {
        "served_logit_gap_max": {"value": 2.58, "limit": None},
        "served_logit_gap_mean": {"value": 0.126, "limit": 0.28}}
    assert err.strip().splitlines()[-2:] == [
        "check: served_logit_gap_max 2.58 (limit inf) ok",
        "check: served_logit_gap_mean 0.126 (limit 0.28) ok"]
