"""Test configuration: force a virtual 8-device CPU mesh.

Tests run on the CPU whatever the machine holds: the platform is pinned
after importing jax but before any backend initialization. The chip is
reached only by ``chip_smoke.py`` (and ``bench.py``), one process per
chip.
"""

import os
import sys

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def pytest_sessionstart(session):
    assert jax.default_backend() == "cpu", (
        f"tests must run on CPU, got {jax.default_backend()}"
    )
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
