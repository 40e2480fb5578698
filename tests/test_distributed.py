"""Multi-process jax.distributed rendezvous test (SURVEY §4 implication (d)).

Spawns N local processes with the EXACT env shape the operator's fan-out
injects into slice pods (cloud/resources.py:distributed_env — coordinator
address, process count, pod-index-derived process id), then asserts the
runtime forms, cross-process collectives work, and a global-mesh train step
runs. This is the piece the reference never had (no trainer rendezvous at
all — SURVEY §2a) and round 1 never executed.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from runbooks_tpu.cloud.resources import (
    JAX_COORDINATOR_PORT,
    distributed_env,
    parse_tpu,
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_distributed_env_shape():
    """The operator injects exactly what distributed.initialize consumes."""
    slice_ = parse_tpu({"type": "v5e", "topology": "4x4"})  # 2-host slice
    env = distributed_env("job", "svc", "ns", slice_)
    by_name = {e["name"]: e for e in env}
    assert by_name["JAX_COORDINATOR_ADDRESS"]["value"] == (
        f"job-0.svc.ns.svc.cluster.local:{JAX_COORDINATOR_PORT}")
    assert by_name["JAX_NUM_PROCESSES"]["value"] == str(slice_.hosts)
    # Process id comes from the indexed-Job completion index annotation.
    ref = by_name["JAX_PROCESS_ID"]["valueFrom"]["fieldRef"]["fieldPath"]
    assert "job-completion-index" in ref


@pytest.mark.slow
def test_two_process_rendezvous_psum_and_train_step(tmp_path):
    nproc = 2
    port = _free_port()
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        # The operator's env contract, localhost flavor (the fieldRef that
        # resolves the pod index becomes a literal process id here).
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = str(nproc)
        env["JAX_PROCESS_ID"] = str(pid)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          "distworker.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    assert all(o["ok"] for o in outs)
    # 2 processes x 2 virtual devices each = 4 global devices.
    assert all(o["world_devices"] == 4 for o in outs)
    assert sorted(o["process"] for o in outs) == [0, 1]
    assert [o["primary"] for o in sorted(outs, key=lambda o: o["process"])] \
        == [True, False]
    # SPMD: every process computes the identical global loss.
    assert outs[0]["loss"] == outs[1]["loss"]
