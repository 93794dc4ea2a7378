"""Multi-device test leg: 8 fake host-platform devices.

XLA reads ``--xla_force_host_platform_device_count`` when the backend first
initializes — it cannot be applied after ``import jax`` has touched devices —
so this leg runs as a SEPARATE pytest invocation that opts in via env var:

    REPRO_MULTIDEVICE=1 PYTHONPATH=src python -m pytest tests/multidevice -q

The main suite (plain ``pytest``) keeps running on the real single CPU
device: without the opt-in the flag is never set, and everything under this
directory is skipped when fewer than 8 devices exist.  CI wires the two as
distinct jobs (see .github/workflows/ci.yml, ``test-multidevice``).
"""
import os

if os.environ.get("REPRO_MULTIDEVICE") == "1":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402  (after the device-count env setup)
import pytest  # noqa: E402

from repro.parallel.mesh import make_mesh  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))


def pytest_collection_modifyitems(config, items):
    if len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(
        reason="needs >= 8 devices; run REPRO_MULTIDEVICE=1 python -m pytest tests/multidevice"
    )
    for item in items:
        if str(item.fspath).startswith(_HERE):
            item.add_marker(skip)


@pytest.fixture(scope="session")
def mesh81():
    """(8, 1) ('data', 'model') — every fake device on the data axis."""
    return make_mesh((8, 1), ("data", "model"))


@pytest.fixture(scope="session")
def mesh42():
    """(4, 2) ('data', 'model') — data sharding alongside a model axis."""
    return make_mesh((4, 2), ("data", "model"))
