"""Test fixtures.

We request 4 host devices (NOT 512 — the 512-device config belongs
exclusively to launch/dryrun.py, which sets it before its own jax init):
the PGAS/collective/dist tests need a real multi-device mesh to mean
anything, and 4 keeps every smoke test fast.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import pytest  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))


@pytest.fixture(scope="session")
def mesh4():
    """1-D 4-rank PGAS mesh."""
    return jax.make_mesh((4,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))


@pytest.fixture(scope="session")
def mesh22():
    """2-D (data=2, model=2) mesh for dist tests."""
    return jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
