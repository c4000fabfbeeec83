"""Test harness configuration.

Mirrors the reference's test strategy (SURVEY.md §4): correctness tests run
against eager references with tolerances; multi-device tests run on a virtual
8-device CPU mesh (the TPU stand-in for the reference's multiprocessing-spawn
multi-GPU tests, tests/comm/conftest.py); Pallas kernels run in interpret mode
off-TPU (the stand-in for the reference's fake backends).

Resource gating mirrors the reference's gpu_2/gpu_4/gpu_8 markers
(tests/conftest.py:140-212): `devices_8` marks tests needing the 8-device
mesh.
"""

import os

# Must happen before jax initializes a backend.  Set
# FLASHINFER_TPU_TEST_ON_TPU=1 to run the suite against real hardware
# (enables the tpu_only smoke tests; the devices_8 mesh tests then skip).
_ON_TPU = os.environ.get("FLASHINFER_TPU_TEST_ON_TPU", "0") == "1"
if not _ON_TPU:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Persistent XLA cache for the CPU suite (a full run compiles
    # thousands of executables per worker), by the program's own rule
    # (flashinfer_tpu/env.py xla_cache_dir): JAX_COMPILATION_CACHE_DIR
    # where it is set, else the checkout's one fixed git-ignored
    # directory.  Env-var form on purpose: no package import at
    # collection time.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".xla_cache"))
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "devices_8: test requires the 8-device virtual mesh"
    )
    config.addinivalue_line("markers", "tpu_only: test requires real TPU hardware")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow') — multi-minute "
        "subprocess benches and similar",
    )
    config.addinivalue_line(
        "markers",
        "quick: ~10-minute representative tier — one test per public "
        "surface, the reviewer-reproducible surface proof "
        "(`python -m pytest tests/ -m quick`; runner line in ROADMAP.md)",
    )


def pytest_collection_modifyitems(config, items):
    n = len(jax.devices())
    for item in items:
        if item.get_closest_marker("devices_8") and n < 8:
            item.add_marker(pytest.mark.skip(reason=f"needs 8 devices, have {n}"))
        if item.get_closest_marker("tpu_only") and jax.default_backend() != "tpu":
            item.add_marker(pytest.mark.skip(reason="needs real TPU"))


@pytest.fixture
def mesh8():
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    with Mesh(devs, ("dp", "tp")) as m:
        yield m
