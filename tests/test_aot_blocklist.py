"""AOT prewarm, tactics blocklist, SVDQuant GEMM tests."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flashinfer_tpu as fi


def test_prewarm_compiles(monkeypatch, tmp_path):
    monkeypatch.setenv("FLASHINFER_TPU_CACHE_DIR", str(tmp_path))
    from flashinfer_tpu import env
    from flashinfer_tpu.aot import prewarm

    placed = jax.config.jax_compilation_cache_dir
    n = prewarm(shapes=[(8, 2, 64)], batch_sizes=(8,), verbose=False)
    assert n == 2  # one decode config + one prefill config
    # prewarm turns the XLA cache on where JAX_COMPILATION_CACHE_DIR put
    # it (tests/conftest.py sets it) and sets no other directory in code:
    # FLASHINFER_TPU_CACHE_DIR places the library's own state only
    assert jax.config.jax_compilation_cache_dir == placed \
        == str(env.xla_cache_dir())
    assert not (tmp_path / "xla_cache").exists()


def test_blocklist(monkeypatch, tmp_path):
    from flashinfer_tpu import tactics_blocklist as tb

    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps([{"op": "flash", "tactic": [256, 512]}]))
    monkeypatch.setenv("FLASHINFER_TPU_TACTICS_BLOCKLIST", str(bl))
    assert tb.blocked("flash", (256, 512))
    assert not tb.blocked("flash", (128, 128))
    assert tb.filter_candidates("flash", [(256, 512), (128, 128)]) == [(128, 128)]
    # everything blocked -> keep first (never empty)
    assert tb.filter_candidates("flash", [(256, 512)]) == [(256, 512)]


def test_autotuner_respects_blocklist(monkeypatch, tmp_path):
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps([{"op": "myop", "tactic": [64]}]))
    monkeypatch.setenv("FLASHINFER_TPU_TACTICS_BLOCKLIST", str(bl))
    monkeypatch.setenv("FLASHINFER_TPU_CACHE_DIR", str(tmp_path))
    import flashinfer_tpu.autotuner as at

    at.AutoTuner._instance = None
    tuner = at.AutoTuner.get()
    got = tuner.choose_one("myop", (1,), [(64,), (128,)], lambda c: lambda: None)
    assert got == (128,)  # blocked default candidate skipped
    at.AutoTuner._instance = None


def test_mm_svdquant_recovers_low_rank_error():
    """With the LoRA factors set to the SVD of the quant error, svdquant
    beats plain fp4 matmul accuracy."""
    rng = np.random.default_rng(0)
    m, k, n, r = 16, 64, 32, 8
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = rng.normal(size=(k, n)).astype(np.float32)
    wp, ws = fi.quantize_fp4(jnp.asarray(w.T))
    wp_k, ws_k = jnp.swapaxes(wp, 0, 1), jnp.swapaxes(ws, 0, 1)
    from flashinfer_tpu.quantization import dequantize_fp4

    w_deq = np.asarray(
        dequantize_fp4(wp, ws, out_dtype=jnp.float32)
    ).T
    err = w - w_deq
    U, S, Vt = np.linalg.svd(err, full_matrices=False)
    down = jnp.asarray(U[:, :r] * S[:r])
    up = jnp.asarray(Vt[:r])

    from flashinfer_tpu.gemm import mm_svdquant

    out = mm_svdquant(x, wp_k, ws_k, down, up, out_dtype=jnp.float32)
    ref = np.asarray(x) @ w
    plain = np.asarray(x) @ w_deq
    err_svdq = np.abs(np.asarray(out) - ref).mean()
    err_plain = np.abs(plain - ref).mean()
    assert err_svdq < err_plain * 0.9, (err_svdq, err_plain)


def test_cli_prewarm():
    # tiny prewarm via module flag isn't exposed; just check command exists
    from flashinfer_tpu.__main__ import main

    with pytest.raises(SystemExit) as e:
        main(["prewarm", "--help"])
    assert e.value.code == 0


# ---- compile-hang quarantine (compile_guard.py) --------------------------


def test_compile_guard_pass_and_quarantine(tmp_path, monkeypatch):
    import json, os, time
    from flashinfer_tpu import compile_guard as cg

    monkeypatch.setenv("FLASHINFER_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("FLASHINFER_TPU_COMPILE_GUARD", "1")
    cg._seen_ok.clear()

    calls = []
    out = cg.guarded("demo_op", ("k", 1), lambda: calls.append(1) or 7)
    assert out == 7 and calls == [1]
    # marker cleared on success, fingerprint remembered
    assert not list((tmp_path / "quarantine" / "pending").glob("*.json"))
    fp = cg.fingerprint("demo_op", ("k", 1))
    assert fp in cg._seen_ok

    # quarantined variant raises without running the thunk
    cg._seen_ok.clear()
    cg.quarantine(fp, "demo_op", "test")
    import pytest as _pytest

    with _pytest.raises(cg.KernelQuarantined):
        cg.guarded("demo_op", ("k", 1), lambda: calls.append(2))
    assert calls == [1]
    # clear() lifts it
    assert cg.clear(fp) == 1
    assert cg.guarded("demo_op", ("k", 1), lambda: 9) == 9


def test_compile_guard_stale_marker_sweep(tmp_path, monkeypatch):
    """A pending marker from a dead process older than the hang threshold is
    promoted to the quarantine list — one wedge costs one kernel slot."""
    import json, time
    from flashinfer_tpu import compile_guard as cg

    monkeypatch.setenv("FLASHINFER_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("FLASHINFER_TPU_COMPILE_GUARD", "1")
    cg._seen_ok.clear()

    fp = cg.fingerprint("wedgy_op", ("shape", 2))
    d = tmp_path / "quarantine" / "pending"
    d.mkdir(parents=True)
    (d / f"{fp}.json").write_text(json.dumps(
        {"op": "wedgy_op", "pid": 2**22 + 12345,  # certainly dead
         "ts": time.time() - 2 * cg.HANG_THRESHOLD_S}
    ))
    import pytest as _pytest

    with _pytest.raises(cg.KernelQuarantined):
        cg.guarded("wedgy_op", ("shape", 2), lambda: 1)
    q = json.loads((tmp_path / "quarantine" / "kernels.json").read_text())
    assert fp in q
    # a *young* dead marker is NOT quarantined (interrupted run, not a hang)
    fp2 = cg.fingerprint("fine_op", ())
    (d / f"{fp2}.json").write_text(json.dumps(
        {"op": "fine_op", "pid": 2**22 + 12345, "ts": time.time() - 5}
    ))
    assert cg.guarded("fine_op", (), lambda: 3) == 3
