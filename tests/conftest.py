"""Test harness config.

SURVEY.md §4 lesson: distributed tests run on a CPU-simulated multi-device
mesh — the TPU analogue of the reference's multiprocess-on-one-host trick
(test_dist_base.py:783). Must set XLA flags before jax import.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite is the CPU tier wherever it runs
# Unset, the compile cache lives in <checkout>/.jax_cache, and the chip tool
# copies the checkout as it stands on disk: keep the suite's entries out of
# it, at a fixed path (the path is part of the cache key) with the same
# admission threshold the package default uses.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu", "xla"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
# Lazy-graph IR verifier (analysis/verify_graph.py): default ON for the whole
# suite via the flags env pickup — every flush in every test re-checks the
# wiring/leaf-table/donation/signature invariants, so a record-time
# bookkeeping slip fails as a structured GraphInvariantError at its flush
# instead of as a wrong cached executable three tests later. Production
# default stays off (one flag probe per flush, pinned by a tripwire).
os.environ.setdefault("FLAGS_lazy_verify", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


# Smoke tier: `pytest -m smoke` runs a <60s cross-section (tensor ops,
# autograd engine, lazy batching, regression pins) — the always-run gate;
# the full suite is the per-round regression sweep.
_SMOKE_MODULES = {
    "test_tensor_ops", "test_autograd", "test_lazy", "test_regressions",
    "test_lazy_donation",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: fast cross-section of the suite (<60s total)"
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow') to hold its "
        "time budget; redundant grid points and heavy cross-feature "
        "composes whose core contract is already pinned by a tier-1 test",
    )
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / fault-tolerance tests (CPU-fast, tier-1)",
    )
    config.addinivalue_line(
        "markers",
        "multichip: N-device tests on the virtual CPU mesh (8-device DP "
        "perf/parity); auto-skipped when the environment provides fewer "
        "devices — the same skip discipline as the multiprocess-env tests",
    )
    config.addinivalue_line(
        "markers",
        "chaos: multi-process chaos-injection recovery tests (kill/hang a "
        "rank mid-run, assert bounded-time coordinated recovery); each "
        "worker is a fresh interpreter importing jax, so the suite needs a "
        "real multi-process budget — auto-skipped on the CPU tier unless "
        "PADDLE_TPU_CHAOS=1 opts in",
    )


def _chaos_world_available() -> bool:
    """The chaos suite spawns whole fresh-interpreter worlds (jax import per
    worker). The JAX_PLATFORMS=cpu CI tier lacks that process budget, so
    chaos runs only on explicit opt-in."""
    if os.environ.get("PADDLE_TPU_CHAOS") == "1":
        return True
    return os.environ.get("JAX_PLATFORMS", "cpu") != "cpu"


def pytest_collection_modifyitems(config, items):
    n_devices = jax.device_count()
    for item in items:
        if item.module.__name__ in _SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)
        if item.get_closest_marker("multichip") is not None and n_devices < 8:
            item.add_marker(pytest.mark.skip(
                reason=f"multichip tests need 8 devices, have {n_devices}"
            ))
        if item.get_closest_marker("chaos") is not None and not _chaos_world_available():
            item.add_marker(pytest.mark.skip(
                reason="chaos tests spawn fresh multi-process worlds; the "
                "JAX_PLATFORMS=cpu tier lacks the process budget "
                "(set PADDLE_TPU_CHAOS=1 to opt in)"
            ))
