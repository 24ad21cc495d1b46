"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of testing collective semantics without a
real cluster (SURVEY.md §4): multi-device via
``--xla_force_host_platform_device_count``, multi-process via the launcher
on localhost (tests/parallel).
"""

import os
import sys

# Both must be set before the first backend initialization; every worker the
# launcher spawns inherits them.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def hvd_single():
    """An initialized single-process Horovod runtime, torn down after."""
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()
