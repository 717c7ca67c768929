"""Test harness configuration.

The reference simulates a cluster by forkserver-spawning N processes over
NCCL/Gloo on localhost (tests/unit/common.py:92-199).  The TPU-native
analogue: a *virtual 8-device mesh* on the XLA host platform via
``--xla_force_host_platform_device_count=8`` — same process, real SPMD
partitioning, real collectives (compiled), no hardware needed.  Real-TPU tests
are marked ``tpu`` and skipped on the simulated mesh.
"""
import os

# Must be set before jax is imported.  Unit tests run on the virtual
# 8-device host mesh, with the Pallas kernels in interpret mode — asked for
# here by name, never inferred from the devices (ops/pallas/common.py).
# Real-TPU tests (tpu marker) run in a SEPARATE pytest process with the
# machine's default platform:  DS_TPU_REAL_TESTS=1 pytest -m tpu tests/
_REAL_TPU = os.environ.get("DS_TPU_REAL_TESTS") == "1"
if not _REAL_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "1"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

import pytest  # noqa: E402


# markers are declared once, in pyproject.toml [tool.pytest.ini_options]

# the benchmark tests' own set-asides, which their conftest.py may not take
pytest_plugins = ("tests.benchmark.pinned_sets", "tests.benchmark.pinned_tail",
                  "tests.benchmark.pinned_thirteenth",
                  "tests.benchmark.pinned_fourteenth",
                  "tests.benchmark.pinned_fifteenth")


def pytest_collection_modifyitems(config, items):
    # The unit suite pins itself to the virtual CPU mesh above; tpu-marked
    # tests need real hardware: DS_TPU_REAL_TESTS=1 pytest -m tpu tests/
    # (a separate process — jax backends can't be re-picked once initialized).
    if jax.devices()[0].platform == "cpu":
        skip_tpu = pytest.mark.skip(reason="requires real TPU: run "
                                    "DS_TPU_REAL_TESTS=1 pytest -m tpu tests/")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip_tpu)
    else:
        skip_cpu = pytest.mark.skip(reason="virtual-mesh test (needs 8 "
                                    "devices); run without DS_TPU_REAL_TESTS")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip_cpu)


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Each test gets a clean global-mesh slate (analogue of destroying
    process groups between DistributedTest cases)."""
    yield
    from deepspeed_tpu.parallel import mesh

    mesh.reset_mesh()
