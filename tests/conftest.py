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


def _sharers_of_no_rows(segs):
    """An engine schedule (``SchedSeg``s) with a sharer of NO rows put behind
    every prompt chunk that is alone in its group.  The planner then meets a
    group of two and keeps the chunk at level 0 under its host-built mask:
    the plans of the planner as it was before a lone chunk rode level 1
    whole (ISSUE 44), array for array.  The engine's own schedules cannot
    say this: a chunk that shares a cached run starts at its end, where the
    mask cuts nothing, so this is what holds level 0's masked units."""
    import dataclasses

    out = []
    for s in segs:
        out.append(s)
        if not s.decoding and sum(o.group == s.group for o in segs) == 1:
            out.append(dataclasses.replace(
                s, row0=s.row0 + s.n, n=0, kv_after=0))
    return out


@pytest.fixture
def sharers_of_no_rows():
    return _sharers_of_no_rows


@pytest.fixture
def chunks_at_level0(monkeypatch):
    """The engine's planner with every lone chunk kept at level 0
    (:func:`_sharers_of_no_rows` in front of it; a window plan has no
    level 0 and passes through)."""
    from flashinfer_tpu.serve import engine_kernels as ek

    plan = ek.build_engine_work_units

    def kept(segs, **kw):
        if kw.get("window_left", -1) < 0:
            segs = _sharers_of_no_rows(segs)
        return plan(segs, **kw)

    monkeypatch.setattr(ek, "build_engine_work_units", kept)


@pytest.fixture(params=[False, True], ids=["level1_whole", "level0_kept"])
def level0_kept(request):
    """Runs a test twice: with the planner as it is, and under
    :func:`chunks_at_level0`.  True in the second run."""
    if request.param:
        request.getfixturevalue("chunks_at_level0")
    return request.param


@pytest.fixture
def mesh8():
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    with Mesh(devs, ("dp", "tp")) as m:
        yield m


@pytest.fixture
def tiny_sharded_step():
    """``(step, x0, layer_ws, head, head_s, state)``: a planned
    ``ShardedServingStep`` of one layer on a 1x1 mesh, with host arrays
    for its arguments (``state`` = caches, page table, lens, key)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from flashinfer_tpu.parallel.plan import (
        ShardedServingStep, ShardingPlan, split_shard_weights_for_spec)
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.serve.shard import Int8ShardSpec

    spec = Int8ShardSpec(bs=2, hidden=64, hq=2, hkv=1, hd=32, inter=128,
                         vocab_shard=64, page_size=8, use_pallas=False)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)

    def qw(k, shape):
        wq, ws = quantize_int8(jax.random.normal(k, shape) / 8.0, axis=0)
        return wq, ws.reshape(1, -1)

    layer_ws = split_shard_weights_for_spec([(
        *qw(ks[0], (64, spec.qdim + 2 * spec.kvdim)),
        *qw(ks[1], (spec.qdim, 64)), *qw(ks[2], (64, 256)),
        *qw(ks[3], (128, 64)), jnp.ones((64,)), jnp.ones((64,)))], spec)
    head, head_s = qw(ks[4], (64, 64))
    x0 = jax.random.normal(ks[5], (2, 64), jnp.bfloat16)
    state = [[(jnp.zeros((4, 1, 8, 32), jnp.int8),
               jnp.zeros((4, 1, 8, 32), jnp.int8))],
             jnp.arange(4, dtype=jnp.int32).reshape(2, 2),
             jnp.array([3, 5], jnp.int32), jax.random.PRNGKey(2)]
    step = ShardedServingStep()
    step.plan(spec, ShardingPlan(Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))),
        num_layers=1)
    return step, x0, layer_ws, head, head_s, state
