"""Test harness: run JAX on a virtual 8-device CPU mesh.

Must set env before the first `import jax` anywhere in the test process so
multi-chip sharding tests (parallel/) exercise real collectives without TPU
hardware. The benchmark (`benchmark/run.py`) does NOT import this and runs on
the real chip.
"""

import os
import sys
import pathlib
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the env may pin a TPU platform

# the canary prober's background loop writes sentinel blobs through real
# gateway paths — nondeterministic traffic inside timing-sensitive tests.
# Default it off for the suite; the flight-recorder tests drive probes
# explicitly via run_once() (and may re-enable the loop themselves).
os.environ.setdefault("WEEDTPU_CANARY_INTERVAL", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# tier-1 compiles hundreds of throwaway CPU programs: keep them (and the
# servers tests spawn as children) out of the persistent compile cache
# the package places inside the checkout (seaweedfs_tpu/__init__.py) —
# the chip tool copies the tree as it stands on disk
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

REFERENCE_ROOT = pathlib.Path("/root/reference")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: timing-sensitive tests excluded from tier-1 "
        "(-m 'not slow')")


# -- tier-1 timing guard ---------------------------------------------------
# The tier-1 gate runs under a hard 870s timeout; a suite that creeps
# toward it fails suddenly and opaquely one PR later.  When a run
# exceeds 80% of the budget, print the 10 slowest tests so the
# offender is named while there is still headroom to fix it.

TIER1_BUDGET_S = 870.0
_suite_start = time.time()
_test_durations: list = []


def pytest_runtest_logreport(report):
    if report.when == "call":
        _test_durations.append((report.duration, report.nodeid))


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    elapsed = time.time() - _suite_start
    if elapsed <= 0.8 * TIER1_BUDGET_S or not _test_durations:
        return
    tr = terminalreporter
    tr.write_sep("=", "tier-1 timing guard")
    tr.write_line(
        f"suite wall time {elapsed:.0f}s exceeds 80% of the "
        f"{TIER1_BUDGET_S:.0f}s tier-1 budget — trim before the "
        f"timeout does it for you. 10 slowest tests:")
    for dur, nodeid in sorted(_test_durations, reverse=True)[:10]:
        tr.write_line(f"  {dur:8.2f}s  {nodeid}")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_resilience_state():
    """Per-peer circuit breakers and the chaos fault registry are
    process-global (keyed by netloc); without a reset, a test that
    killed a server could leave its port's breaker open for the next
    test that happens to draw the same free port."""
    yield
    from seaweedfs_tpu.maintenance import faults
    from seaweedfs_tpu.utils import resilience
    resilience.reset_breakers()
    resilience.reset_latency_trackers()
    faults.clear_net()


@pytest.fixture(scope="session")
def device_mesh_devices():
    """The ONE backend-selection seam for every sharding test: under
    tier-1 (JAX_PLATFORMS=cpu — forced above) this is the virtual
    8-device CPU mesh; on a machine with real accelerators attached and
    the force lifted, the real devices.  It ASSERTS instead of skipping:
    a CPU run that silently skipped the sharding suite is exactly how a
    mesh regression would ship."""
    devs = jax.devices()
    assert len(devs) >= 8, (
        f"sharding suite needs >= 8 devices, got {len(devs)} — the "
        f"conftest XLA_FLAGS force failed; do NOT skip mesh tests")
    return devs


@pytest.fixture(scope="session")
def unit_mesh(device_mesh_devices):
    """8-way 1D mesh on the unit axis (FleetUnitEncoder shape)."""
    from seaweedfs_tpu.parallel import mesh as pmesh
    return pmesh.make_mesh(8, ("unit",))


@pytest.fixture(scope="session")
def column_mesh(device_mesh_devices):
    """8-way 1D mesh on the byte-column axis (ShardedRSEncoder shape)."""
    from seaweedfs_tpu.parallel import mesh as pmesh
    return pmesh.make_mesh(8, ("data",))


def reference_fixture(relpath: str) -> pathlib.Path | None:
    """Path to a binary test fixture inside the read-only reference checkout,
    or None when the reference isn't mounted (tests then skip the golden
    cross-checks and rely on self-generated fixtures)."""
    p = REFERENCE_ROOT / relpath
    return p if p.exists() else None
