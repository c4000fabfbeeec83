"""Compiled-artifact bundles — the TPU re-design of the reference's cubin
artifactory (``/root/reference/flashinfer/artifacts.py:131-335``).

The reference ships pre-compiled device binaries (cubins) from an
artifactory: ``ArtifactPath`` names the paths, ``CheckSumHash`` pins
sha256 sums, ``download_artifacts()`` fetches them and
``get_artifacts_status()`` audits presence.  On TPU the equivalent
"pre-compiled device binary" is an **XLA persistent-cache entry** (a
serialized Mosaic/XLA executable keyed by HLO hash) plus the **tuned
tactic tables** that select kernel schedules.  Both are host-portable
across machines with the same chip generation and jax version, so the
artifact story becomes pack/unpack of a checksummed bundle:

- :func:`build_artifacts` — populate the local cache by compiling the
  serving-critical kernel set (aot.prewarm) — the zero-egress analogue of
  "download" (artifacts are *built once* then shipped).
- :func:`pack_artifacts` / :func:`unpack_artifacts` — tar the cache +
  tactics into a bundle with a sha256 manifest, and restore it on an
  air-gapped or fleet host (checksum-verified, like the reference's
  ``get_checksums``).
- :func:`get_artifacts_status` — presence audit, reference-shaped
  ``tuple[tuple[str, bool], ...]``.
- :func:`clear_artifacts` — the ``clear_cubin()`` analogue.

``download_artifacts()`` is kept as a reference-named alias: it unpacks
``$FLASHINFER_TPU_ARTIFACT_BUNDLE`` if set (the fleet-distribution hook),
else builds locally.
"""

from __future__ import annotations

import hashlib
import json
import os
import tarfile
from pathlib import Path
from typing import Optional, Tuple

from flashinfer_tpu import env


class ArtifactPath:
    """Bundle subdirectories (reference ArtifactPath names cubin dirs)."""

    XLA_CACHE: str = "xla_cache"          # serialized executables
    TACTICS: str = "autotuner"            # user-tuned tactic cache
    TUNING_CONFIGS: str = "tuning_configs"  # shipped per-chip tables


_MANIFEST = "MANIFEST.sha256.json"


def _tuning_configs_dir() -> Path:
    return Path(__file__).parent / "tuning_configs"


def _sha256(p: Path) -> str:
    h = hashlib.sha256()
    with open(p, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _roots(cache_dir: Optional[str]) -> Tuple[Path, Path]:
    """(library state root, XLA executable directory).  An explicit
    ``cache_dir`` is a self-contained bundle root with the executables
    under it; the live places are ``env.cache_dir()`` and
    ``env.xla_cache_dir()`` (JAX's own ``JAX_COMPILATION_CACHE_DIR``
    or the checkout's fixed directory — env.py)."""
    if cache_dir:
        return Path(cache_dir), Path(cache_dir) / ArtifactPath.XLA_CACHE
    return env.cache_dir(), env.xla_cache_dir()


def _bundle_members(cache_root: Path, xla_root: Path):
    """Yield (arcname, path) for every file the bundle carries."""
    for sub, root in (
        (ArtifactPath.XLA_CACHE, xla_root),
        (ArtifactPath.TACTICS, cache_root / ArtifactPath.TACTICS),
    ):
        if not root.is_dir():
            continue
        for p in sorted(root.rglob("*")):
            if p.is_file():
                yield f"{sub}/{p.relative_to(root)}", p
    # tuning tables: a bundle-installed copy in the cache dir is the
    # NEWER table (autotuner._load lets it override the package copy) —
    # re-packing must relay it, not the stale package file
    by_stem = {}
    for root in (_tuning_configs_dir(),
                 cache_root / ArtifactPath.TUNING_CONFIGS):
        if root.is_dir():
            for p in sorted(root.glob("*.json")):
                by_stem[p.name] = p
    for name, p in sorted(by_stem.items()):
        yield f"{ArtifactPath.TUNING_CONFIGS}/{name}", p


def build_artifacts(verbose: bool = True) -> None:
    """Compile the serving-critical kernel set into the persistent cache
    (the zero-egress ``download_artifacts`` body: artifacts are built,
    not fetched).  Reference: ``download_artifacts`` artifacts.py:277."""
    from flashinfer_tpu import aot

    env.enable_compilation_cache()
    aot.prewarm(verbose=verbose)


def pack_artifacts(out_path: str, cache_dir: Optional[str] = None) -> Path:
    """Tar the compilation cache + tactic tables with a sha256 manifest.

    The bundle is valid for hosts with the same chip generation and jax
    version (the autotuner additionally validates device_kind metadata on
    load, so a mismatched bundle degrades to defaults, never misapplies).
    """
    out = Path(out_path)
    manifest = {}
    with tarfile.open(out, "w:gz") as tar:
        for arcname, p in _bundle_members(*_roots(cache_dir)):
            manifest[arcname] = _sha256(p)
            tar.add(p, arcname=arcname)
        mbytes = json.dumps(manifest, indent=1, sort_keys=True).encode()
        import io

        info = tarfile.TarInfo(_MANIFEST)
        info.size = len(mbytes)
        tar.addfile(info, io.BytesIO(mbytes))
    return out


def unpack_artifacts(bundle_path: str,
                     cache_dir: Optional[str] = None) -> int:
    """Restore a bundle into the local cache, verifying every checksum
    (reference ``get_checksums`` role).  Returns the file count.

    Raises ``ValueError`` on any integrity failure (checksum mismatch,
    missing manifest entry, unsafe path) and writes NOTHING in that case
    — a damaged bundle must not partially seed the executable cache.
    (This is corruption/truncation DETECTION, not tamper-proofing: the
    manifest travels inside the bundle, so an adversary who can rewrite
    the bundle can re-sign it; distribute bundles over channels with
    their own authenticity guarantees.)
    """
    root, xla_root = _roots(cache_dir)

    def _members(tar, manifest):
        for member in tar.getmembers():
            if not member.isfile() or member.name == _MANIFEST:
                continue
            rel = Path(member.name)
            # refuse path escapes; tarfile data filter exists only on
            # newer pythons, so normalize by hand
            if rel.is_absolute() or ".." in rel.parts:
                raise ValueError(f"unsafe member path {member.name!r}")
            if member.name not in manifest:
                raise ValueError(f"{member.name}: not in manifest")
            yield member, rel

    # pass 1: stream every member through sha256 — nothing is written
    # until the WHOLE bundle has verified (O(chunk) memory, not O(bundle))
    seen = set()
    with tarfile.open(bundle_path, "r:gz") as tar:
        if _MANIFEST not in tar.getnames():
            raise ValueError(f"{bundle_path}: missing {_MANIFEST}")
        manifest = json.loads(tar.extractfile(_MANIFEST).read().decode())
        for member, _rel in _members(tar, manifest):
            h = hashlib.sha256()
            f = tar.extractfile(member)
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
            if h.hexdigest() != manifest[member.name]:
                raise ValueError(f"{member.name}: checksum mismatch")
            seen.add(member.name)
    dropped = set(manifest) - seen
    if dropped:
        raise ValueError(
            f"{bundle_path}: manifest entries missing from the bundle "
            f"(truncated/repacked?): {sorted(dropped)[:5]}"
        )
    # pass 2: extract (the autotuner reads bundle-installed
    # tuning_configs from the cache dir too — autotuner._load second
    # root — overriding the package copy)
    n = 0
    root.mkdir(parents=True, exist_ok=True)
    with tarfile.open(bundle_path, "r:gz") as tar:
        manifest = json.loads(tar.extractfile(_MANIFEST).read().decode())
        for member, rel in _members(tar, manifest):
            if rel.parts[0] == ArtifactPath.XLA_CACHE:
                dest = xla_root.joinpath(*rel.parts[1:])
            else:
                dest = root / rel
            dest.parent.mkdir(parents=True, exist_ok=True)
            f = tar.extractfile(member)
            with open(dest, "wb") as out:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    out.write(chunk)
            n += 1
    return n


def get_artifacts_status() -> Tuple[Tuple[str, bool], ...]:
    """Presence audit, reference-shaped (artifacts.py:318).

    Deliberately queries NO device: this must answer on a host whose
    accelerator is absent or wedged (it is part of the recovery
    tooling), so the tuning-config rows list each available stem rather
    than resolving the current chip."""
    root, xla_root = _roots(None)
    status = [
        (ArtifactPath.XLA_CACHE,
         any(xla_root.rglob("*")) if xla_root.is_dir() else False),
        (ArtifactPath.TACTICS,
         (root / ArtifactPath.TACTICS / "tactics.json").is_file()),
    ]
    # glob on a missing directory yields nothing, so no existence check
    stems = sorted(
        {p.stem for p in _tuning_configs_dir().glob("*.json")}
        | {p.stem
           for p in (root / ArtifactPath.TUNING_CONFIGS).glob("*.json")}
    )
    if stems:
        for s in stems:
            status.append((f"{ArtifactPath.TUNING_CONFIGS}/{s}", True))
    else:
        status.append((ArtifactPath.TUNING_CONFIGS, False))
    return tuple(status)


def clear_artifacts(cache_dir: Optional[str] = None) -> None:
    """Remove cached executables + user tactics (``clear_cubin`` role,
    artifacts.py:335).  Shipped tuning_configs are package data and are
    NOT touched."""
    import shutil

    root, xla_root = _roots(cache_dir)
    for d in (xla_root, root / ArtifactPath.TACTICS):
        if d.is_dir():
            shutil.rmtree(d)


def download_artifacts() -> None:
    """Reference-named entry (artifacts.py:277): unpack the bundle named
    by ``$FLASHINFER_TPU_ARTIFACT_BUNDLE`` if set, else build locally."""
    bundle = os.environ.get("FLASHINFER_TPU_ARTIFACT_BUNDLE")
    if bundle:
        unpack_artifacts(bundle)
    else:
        build_artifacts()


# ---------------------------------------------------------------------------
# Reference-named surface (artifacts.py) on the bundle model
# ---------------------------------------------------------------------------

import contextlib
from contextlib import contextmanager  # noqa: F401  (reference re-export)
from concurrent.futures import (  # noqa: F401  (reference re-export)
    ThreadPoolExecutor, as_completed,
)
from dataclasses import dataclass  # noqa: F401  (reference re-export)
from typing import Generator  # noqa: F401  (reference re-export)

# Reference module constants (artifacts.py): the repository URL becomes
# the bundle env hook, the cubin dir the local cache root.
FLASHINFER_CUBINS_REPOSITORY = os.environ.get(
    "FLASHINFER_TPU_ARTIFACT_BUNDLE", ""
)
FLASHINFER_CUBIN_DIR = str(env.cache_dir())


def safe_urljoin(base: str, part: str) -> str:
    """Reference path-join helper; bundles are local paths here."""
    return os.path.join(base, part)


def download_file(src: str, dest: str) -> str:
    """Reference single-file fetch -> local copy (zero-egress env)."""
    import shutil

    Path(dest).parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(src, dest)
    return dest


def verify_cubin(path: str, sha256: str) -> bool:
    """Checksum check under the reference's name."""
    return _sha256(Path(path)) == sha256


@contextlib.contextmanager
def temp_env_var(key: str, value: str):
    """Reference helper (artifacts.py:46) — unchanged semantics."""
    prev = os.environ.get(key)
    os.environ[key] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev


def get_subdir_file_list():
    """(subdir, file) pairs the bundle would carry (artifacts.py:227)."""
    for arcname, _ in _bundle_members(*_roots(None)):
        sub, _, rest = arcname.partition("/")
        yield sub, rest


def get_available_cubin_files(*_a, **_k) -> Tuple[str, ...]:
    """Reference lists cubins present for a path (artifacts.py:58); here:
    serialized XLA executables in the local cache."""
    d = env.xla_cache_dir()
    if not d.is_dir():
        return ()
    return tuple(sorted(p.name for p in d.rglob("*") if p.is_file()))


def get_available_header_files(*_a, **_k) -> Tuple[str, ...]:
    """Headers have no TPU meaning (no JIT-compiled C++ on this path);
    the shipped tuning tables are the closest 'interface' files."""
    return tuple(sorted(p.name for p in _tuning_configs_dir().glob("*.json")))


class CheckSumHash:
    """Reference pins static cubin checksums (artifacts.py:152); TPU
    bundles carry their manifest INSIDE the tarball (``MANIFEST`` name
    here), so the class only names the manifest file."""

    MANIFEST: str = _MANIFEST


def get_checksums(subdirs=None):
    """Live checksums of the local artifact set (artifacts.py:198)."""
    want = set(subdirs) if subdirs else None
    out = {}
    for arcname, p in _bundle_members(*_roots(None)):
        sub = arcname.partition("/")[0]
        if want is None or sub in want:
            out[arcname] = _sha256(p)
    return out


clear_cubin = clear_artifacts  # reference name (artifacts.py:335)
