"""Test harness: force an 8-device virtual CPU platform before JAX initializes.

Multi-chip TPU hardware is not available in CI; all sharding logic is tested on
a virtual 8-device CPU mesh. Mirrors the reference's strategy of testing the
whole operator loop without cloud dependencies (SURVEY.md §4: envtest + kind
cloud).
"""

import os
import sys

# Must be set before jax is imported anywhere: tier-1 runs on 8 virtual CPU
# devices (JAX_PLATFORMS=cpu + --xla_force_host_platform_device_count=8;
# the recipe lives in benchkit.apply_cpu_env). Set RBT_TEST_PLATFORM to
# run the suite on another platform.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchkit import apply_cpu_env  # noqa: E402

if os.environ.get("RBT_TEST_PLATFORM", "cpu") == "cpu":
    apply_cpu_env(n_devices=8)
else:
    os.environ["JAX_PLATFORMS"] = os.environ["RBT_TEST_PLATFORM"]

# Tier-1 neither writes a compilation cache into the checkout nor depends
# on one a previous run left there (utils/jax_cache.py); the cache's own
# tests opt back in.
os.environ.setdefault("RBT_JAX_CACHE", "0")

import jax  # noqa: E402

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # pytest loads installed plugins BEFORE conftest, and one that imports
    # jax latches the ambient JAX_PLATFORMS at import time, making the env
    # override above a no-op (on a chip machine the suite would then take
    # the chip). The config update works until a backend is initialized.
    jax.config.update("jax_platforms", "cpu")

# Exact-math tests: JAX's *default* matmul precision may round inputs to
# bf16 even for f32 arrays, which makes results shape-dependent (full matmul
# vs sliced matmul accumulate differently). Pin highest precision in tests;
# production code on TPU keeps the fast default (bf16 on the MXU).
jax.config.update("jax_default_matmul_precision", "highest")

import tempfile  # noqa: E402

import pytest  # noqa: E402

# Keep the container contract's /content out of test runs: always-on
# paths (flight-recorder tail sampling, incident capture) default their
# output under contract.artifacts_dir(), and a test that exercises them
# without monkeypatching RBT_CONTENT_DIR must land in a throwaway dir,
# never in a real /content (tests may run as root, where the mkdir
# would succeed).
os.environ.setdefault(
    "RBT_CONTENT_DIR", tempfile.mkdtemp(prefix="rbt-test-content-"))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _reset_fleet_history():
    """The fleet history (obs/history.py HISTORY) is process-wide state
    written by every FleetScraper sweep and read by the burn-rate SLO
    evaluator and the autoscaler's windowed p90 — one test's appended
    rings must never leak a computable window into another test's
    reconciles (the windows key off REAL wall-clock time, so leakage
    would be order- and wall-time-dependent flakiness)."""
    from runbooks_tpu.obs.history import HISTORY

    HISTORY.reset()
    yield
    HISTORY.reset()
