"""Artifact bundles: pack/unpack round-trip, checksum enforcement, status.

Reference parity target: ``/root/reference/flashinfer/artifacts.py``
(cubin artifactory) re-designed as checksummed XLA-cache + tactics
bundles (``flashinfer_tpu/artifacts.py`` module docstring).
"""

import json
import tarfile

import pytest


@pytest.fixture()
def fake_cache(tmp_path, monkeypatch):
    from flashinfer_tpu import env

    root = tmp_path / "cache"
    (root / "xla_cache").mkdir(parents=True)
    (root / "xla_cache" / "exec_abc.bin").write_bytes(b"\x00" * 64)
    (root / "autotuner").mkdir()
    (root / "autotuner" / "tactics.json").write_text(
        json.dumps({"meta": {}, "tactics": {"k": 1}})
    )
    monkeypatch.setenv("FLASHINFER_TPU_CACHE_DIR", str(root))
    # the XLA executables live where JAX's own variable says, not under
    # FLASHINFER_TPU_CACHE_DIR
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root / "xla_cache"))
    assert env.cache_dir() == root
    assert env.xla_cache_dir() == root / "xla_cache"
    return root


def test_pack_unpack_round_trip(fake_cache, tmp_path):
    from flashinfer_tpu import artifacts

    bundle = artifacts.pack_artifacts(tmp_path / "b.tgz")
    assert bundle.is_file()
    # manifest covers every member incl. shipped tuning configs
    with tarfile.open(bundle) as tar:
        names = set(tar.getnames())
    assert "xla_cache/exec_abc.bin" in names
    assert "autotuner/tactics.json" in names
    assert artifacts.CheckSumHash.MANIFEST in names
    assert any(n.startswith("tuning_configs/") for n in names)

    dest = tmp_path / "restored"
    n = artifacts.unpack_artifacts(bundle, cache_dir=dest)
    assert n >= 3
    assert (dest / "xla_cache" / "exec_abc.bin").read_bytes() == b"\x00" * 64
    assert (dest / "autotuner" / "tactics.json").is_file()


def test_unpack_rejects_tampered_bundle(fake_cache, tmp_path):
    from flashinfer_tpu import artifacts

    bundle = artifacts.pack_artifacts(tmp_path / "b.tgz")
    # flip a byte inside the gzip stream -> either checksum failure or a
    # tar/gzip read error; both must refuse to seed the cache
    data = bytearray(bundle.read_bytes())
    data[len(data) // 2] ^= 0xFF
    bad = tmp_path / "bad.tgz"
    bad.write_bytes(bytes(data))
    with pytest.raises(Exception):
        artifacts.unpack_artifacts(bad, cache_dir=tmp_path / "x")


def test_unpack_rejects_manifestless_and_truncated(fake_cache, tmp_path):
    from flashinfer_tpu import artifacts

    # plain tar with no manifest -> ValueError (documented contract)
    plain = tmp_path / "plain.tgz"
    with tarfile.open(plain, "w:gz") as tar:
        tar.add(fake_cache / "autotuner" / "tactics.json",
                arcname="autotuner/tactics.json")
    with pytest.raises(ValueError, match="missing"):
        artifacts.unpack_artifacts(plain, cache_dir=tmp_path / "a")

    # manifest present but a listed member dropped -> ValueError
    bundle = artifacts.pack_artifacts(tmp_path / "b.tgz")
    filtered = tmp_path / "filtered.tgz"
    with tarfile.open(bundle) as src, tarfile.open(filtered, "w:gz") as dst:
        for m in src.getmembers():
            if m.name.startswith("xla_cache/"):
                continue  # drop the executables, keep the manifest
            dst.addfile(m, src.extractfile(m))
    with pytest.raises(ValueError, match="missing from the bundle"):
        artifacts.unpack_artifacts(filtered, cache_dir=tmp_path / "c")


def test_bundle_tuning_configs_reach_autotuner(fake_cache, tmp_path,
                                               monkeypatch):
    """A bundle-installed tuning table must be served by AutoTuner.lookup
    (the fleet-distribution path: cache-dir copy overrides package)."""
    import json as _json

    from flashinfer_tpu import artifacts, autotuner

    monkeypatch.setattr(autotuner, "_device_config_key", lambda: "fakechip")
    (fake_cache / "tuning_configs").mkdir()
    (fake_cache / "tuning_configs" / "fakechip.json").write_text(
        _json.dumps({"tactics": {
            # a registered knob reaches lookup; an unregistered one is
            # dropped by the validating loader (the L006 runtime belt)
            "rmsnorm.row_block|1_2": 7,
            "some_renamed_op.knob|1_2": 7,
        }})
    )
    t = autotuner.AutoTuner()
    assert t.lookup("rmsnorm.row_block", (1, 2)) == 7
    assert t.lookup("some_renamed_op.knob", (1, 2), default="dropped") \
        == "dropped"


def test_status_and_listing(fake_cache):
    from flashinfer_tpu import artifacts

    status = dict(artifacts.get_artifacts_status())
    assert status["xla_cache"] is True
    assert status["autotuner"] is True
    assert artifacts.get_available_cubin_files() == ("exec_abc.bin",)
    sums = artifacts.get_checksums(["autotuner"])
    assert list(sums) == ["autotuner/tactics.json"]
    subs = {s for s, _ in artifacts.get_subdir_file_list()}
    assert {"xla_cache", "autotuner"} <= subs


def test_clear_artifacts(fake_cache):
    from flashinfer_tpu import artifacts

    artifacts.clear_cubin(cache_dir=fake_cache)
    assert not (fake_cache / "xla_cache").exists()
    assert not (fake_cache / "autotuner").exists()
    # shipped tuning configs untouched
    assert artifacts.get_available_header_files()


def test_xla_cache_is_placed_from_outside(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the program uses it and
    sets no other in code; unset, one fixed git-ignored directory of the
    checkout (the path is part of the cache key)."""
    from pathlib import Path

    import jax

    from flashinfer_tpu import env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    env.enable_compilation_cache()
    assert env.xla_cache_dir() == Path("/placed/from/outside")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = Path(env.__file__).resolve().parent.parent
    assert env.xla_cache_dir() == repo / ".xla_cache"
    assert ".xla_cache/" in (repo / ".gitignore").read_text().split()


def test_temp_env_var(monkeypatch):
    import os

    from flashinfer_tpu import artifacts

    monkeypatch.delenv("FI_TPU_TEST_VAR", raising=False)
    with artifacts.temp_env_var("FI_TPU_TEST_VAR", "1"):
        assert os.environ["FI_TPU_TEST_VAR"] == "1"
    assert "FI_TPU_TEST_VAR" not in os.environ
