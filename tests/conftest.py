"""Test harness setup.

Multi-chip behavior is tested on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``) — the TPU-pod analogue of the
reference's "MPI ranks as local processes" strategy
(/root/reference/README.md:182-198). The env vars must be set before the
first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tier-1 never uses an accelerator
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Keep CPU test jobs from oversubscribing the machine.
os.environ.setdefault("XLA_PYTHON_CLIENT_PREALLOCATE", "false")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Tier-1 exercises the native core throughout: (re)build it up front when
# the cached _lib/*.so was built from other native/*.cc|*.h contents
# (`make native` runs the same stale-aware entry). One clean compile here
# beats N test processes racing the lazy first-import build.
from ddstore_tpu import _build  # noqa: E402

_build.build()


def pytest_report_header(config):
    """Point at the one-command local reproduction for the static
    analyzer's tier-1 gate (tests/test_static_analysis.py): a lint
    failure in CI is `make lint` here, no pytest invocation needed."""
    from ddstore_tpu.analysis import baseline_path
    return (f"ddlint: `make lint` reproduces the static-analysis gate; "
            f"baseline at {os.path.relpath(baseline_path())}")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def small_tiles(monkeypatch):
    """Cuts the flash forward's sub-tiles to ``tile`` (rows, columns), so
    that a small call holds several of them a strip:
    ``small_tiles((64, 64))``."""
    def cut(tile):
        from ddstore_tpu.ops import attention
        monkeypatch.setattr(attention, "_one_pass_tile", lambda d, bq, bk: (
            min(tile[0], bq), min(tile[1], bk)))
    return cut
