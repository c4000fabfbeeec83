"""CLI: ``python -m flashinfer_tpu <cmd>``.

TPU re-design of the reference CLI (``flashinfer/__main__.py:63-462``).
Command mapping: cubin/jit-cache management collapses into the XLA
persistent compilation cache (``env.xla_cache_dir()``) + the library's
own state (native planner, tactics) under ``FLASHINFER_TPU_CACHE_DIR``.

Commands: collect-env | show-config | clear-cache | module-status |
list-modules | tuner-status.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys


def cmd_collect_env(_args) -> int:
    from flashinfer_tpu.collect_env import main as ce

    ce()
    return 0


def cmd_show_config(_args) -> int:
    from flashinfer_tpu import env

    print(f"cache_dir        : {env.cache_dir()}")
    print(f"xla_cache_dir    : {env.xla_cache_dir()}")
    print(f"dump_dir         : {env.dump_dir()}")
    print(f"log_level        : {env.log_level()}")
    print(f"backend_override : {env.backend_override()}")
    print(f"force_interpret  : {env.force_interpret()}")
    d = env.cache_dir()
    if d.exists():
        n = sum(1 for _ in d.rglob("*") if _.is_file())
        sz = sum(f.stat().st_size for f in d.rglob("*") if f.is_file())
        print(f"cache contents   : {n} files, {sz / 1e6:.1f} MB")
    return 0


def cmd_clear_cache(_args) -> int:
    from flashinfer_tpu import env

    d = env.cache_dir()
    if d.exists():
        shutil.rmtree(d)
        print(f"cleared {d}")
    else:
        print(f"nothing to clear at {d}")
    return 0


def cmd_module_status(_args) -> int:
    from flashinfer_tpu import native

    lib = native.get_lib()
    print(f"native planner  : {'built+loaded' if lib else 'numpy fallback'}")
    from flashinfer_tpu import env

    xc = env.xla_cache_dir()
    n = sum(1 for _ in xc.rglob("*") if _.is_file()) if xc.exists() else 0
    print(f"xla compile cache: {n} entries ({xc})")
    from flashinfer_tpu import compile_guard

    reg = compile_guard.compile_status()
    q = compile_guard._load_qlist()
    print(f"kernel compiles  : {len(reg)} recorded, {len(q)} quarantined")
    for fp, info in sorted(reg.items(), key=lambda kv: -kv[1].get("ts", 0))[:10]:
        print(f"  {fp}  {info['op']:<24} {info['compile_s']:7.2f}s  {info['status']}")
    return 0


def cmd_list_modules(_args) -> int:
    mods = [
        "decode (single + BatchDecodeWithPagedKVCacheWrapper)",
        "prefill (single + BatchPrefill{Paged,Ragged}KVCacheWrapper)",
        "attention (BatchAttention holistic, POD, attention sinks)",
        "mla (BatchMLAPagedAttentionWrapper)",
        "cascade (MultiLevelCascadeAttentionWrapper, merge ops)",
        "sparse (BlockSparse, VariableBlockSparse)",
        "page (append_paged_kv_cache, MLA append)",
        "rope / norm / activation",
        "sampling + logits_processor pipeline",
        "gemm (mm/bmm bf16/fp8/int8, grouped, SegmentGEMMWrapper)",
        "quantization (packbits, fp8/int8)",
        "fused_moe (routing, fused_moe, EP)",
        "comm (Mapping, allreduce fusion) / parallel (ulysses, ring, dcp)",
        "topk",
    ]
    for m in mods:
        print(f"  {m}")
    return 0


def cmd_replay(args) -> int:
    """Re-invoke a dumped API call (reference CLI ``replay``,
    flashinfer/__main__.py:462): loads ``arg*.npy`` / ``kw_*.npy`` from a
    FLASHINFER_TPU_LOGLEVEL=10 dump directory and calls the op again."""
    import re
    from pathlib import Path

    import numpy as np

    import flashinfer_tpu as fi

    d = Path(args.dump_dir)
    if not d.is_dir():
        print(f"no such dump dir: {d}")
        return 1
    op_name = re.sub(r"_\d+$", "", d.name)
    fn = getattr(fi, op_name, None)
    if fn is None:
        print(f"unknown op {op_name!r} (dir name must be <op>_<callidx>)")
        return 1
    import json

    meta = {}
    meta_f = d / "meta.json"
    if meta_f.exists():
        meta = json.loads(meta_f.read_text())

    def load(f: Path):
        arr = np.load(f)
        orig = meta.get(f.stem)
        if orig:  # bf16/fp8 stored as f32 with the original dtype recorded
            import jax.numpy as jnp

            arr = jnp.asarray(arr).astype(orig)
        return arr

    pos = {}
    kws = {}
    for f in sorted(d.glob("*.npy")):
        m = re.fullmatch(r"arg(\d+)", f.stem)
        if m:
            pos[int(m.group(1))] = load(f)
        elif f.stem.startswith("kw_"):
            kws[f.stem[3:]] = load(f)
    for key, val in meta.get("scalars", {}).items():
        m = re.fullmatch(r"arg(\d+)", key)
        if m:
            pos[int(m.group(1))] = val
        elif key.startswith("kw_"):
            kws[key[3:]] = val
    if meta.get("skipped"):
        print(f"cannot replay: args were not dumpable: {meta['skipped']}")
        return 1
    if sorted(pos) != list(range(len(pos))):
        print(f"cannot replay: positional dump gap (have {sorted(pos)})")
        return 1
    args_list = [pos[i] for i in sorted(pos)]
    out = fn(*args_list, **kws)
    import jax

    jax.block_until_ready(out)
    leaves = jax.tree_util.tree_leaves(out)
    print(
        f"replayed {op_name} with {len(args_list)} args, {len(kws)} kwargs -> "
        + ", ".join(f"{getattr(l, 'shape', l)}" for l in leaves[:4])
    )
    return 0


def cmd_prewarm(_args) -> int:
    from flashinfer_tpu.aot import prewarm

    n = prewarm()
    print(f"prewarmed {n} configs into the persistent compile cache")
    return 0


def cmd_tuner_status(_args) -> int:
    from flashinfer_tpu.autotuner import AutoTuner

    t = AutoTuner.get()
    t._load()
    print(f"cache file : {t._cache_path()}")
    print(f"entries    : {len(t._cache)}")
    for k, v in sorted(t._cache.items()):
        print(f"  {k} -> {v}")
    return 0


def cmd_tune(args) -> int:
    """Profile the serving-critical op families on the live chip and write
    the tactics straight into tuning_configs/<chip>.json after every
    stage — the production path the recovery watchdog invokes after the
    hardware tier (no manual merge step)."""
    from flashinfer_tpu.tune import run_tuning_workload

    path = run_tuning_workload(
        stages=args.stage or None, merge_stem=args.stem,
        log=lambda m: print(m, flush=True),
    )
    print(f"tuning config written: {path}")
    return 0


def cmd_artifacts(args) -> int:
    """Artifact bundles (reference artifacts.py role): pack the local
    compile cache + tactic tables into a checksummed tarball, restore
    one, or audit presence."""
    from flashinfer_tpu import artifacts

    if args.action == "pack":
        out = artifacts.pack_artifacts(args.path or "flashinfer_tpu_artifacts.tgz")
        print(f"packed -> {out}")
    elif args.action == "unpack":
        if not args.path:
            print("unpack requires a bundle path", file=sys.stderr)
            return 2
        n = artifacts.unpack_artifacts(args.path)
        print(f"restored {n} files into {artifacts.env.cache_dir()}")
    else:
        for name, present in artifacts.get_artifacts_status():
            print(f"{'present' if present else 'MISSING':8s} {name}")
    return 0


def cmd_probe(args) -> int:
    """Chip-health probe: compile a trivial kernel in a subprocess under a
    timeout (the post-wedge recovery detector)."""
    from flashinfer_tpu import compile_guard

    r = compile_guard.probe(timeout_s=args.timeout)
    print(json.dumps(r, indent=1))
    return 0 if r["healthy"] else 1


def cmd_quarantine(args) -> int:
    from flashinfer_tpu import compile_guard

    if args.clear is not None:
        n = compile_guard.clear(args.clear or None)
        print(f"cleared {n} quarantine entries")
        return 0
    q = compile_guard._load_qlist()
    print(f"quarantine file: {compile_guard._qlist_path()}")
    print(f"entries        : {len(q)}")
    for fp, info in sorted(q.items()):
        print(f"  {fp}  op={info.get('op')}  reason={info.get('reason')}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="flashinfer_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)
    for name, fn in [
        ("collect-env", cmd_collect_env),
        ("show-config", cmd_show_config),
        ("clear-cache", cmd_clear_cache),
        ("module-status", cmd_module_status),
        ("list-modules", cmd_list_modules),
        ("tuner-status", cmd_tuner_status),
        ("prewarm", cmd_prewarm),
    ]:
        sp = sub.add_parser(name)
        sp.set_defaults(fn=fn)
    sp = sub.add_parser("replay")
    sp.add_argument("dump_dir", help="a <op>_<idx> dir from LOGLEVEL=10 dumps")
    sp.set_defaults(fn=cmd_replay)
    sp = sub.add_parser("probe")
    sp.add_argument("--timeout", type=float, default=240.0)
    sp.set_defaults(fn=cmd_probe)
    sp = sub.add_parser("artifacts")
    sp.add_argument("action", choices=["status", "pack", "unpack"])
    sp.add_argument("path", nargs="?")
    sp.set_defaults(fn=cmd_artifacts)
    sp = sub.add_parser("tune")
    sp.add_argument(
        "--stage", action="append",
        choices=["norm", "decode", "prefill", "moe", "mla", "flash"],
        help="run only these stages (default: all, wedge-safe order)",
    )
    sp.add_argument(
        "--stem", default=None,
        help="tuning_configs file stem (default: from device_kind)",
    )
    sp.set_defaults(fn=cmd_tune)
    sp = sub.add_parser("quarantine")
    sp.add_argument(
        "--clear", nargs="?", const="", default=None,
        help="clear one fingerprint (or all with no value)",
    )
    sp.set_defaults(fn=cmd_quarantine)
    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
