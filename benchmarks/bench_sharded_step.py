"""Mesh-axis sweep harness for the compile-once sharded serving step.

The multi-chip autotune surface for ISSUE 9's tentpole: sweeps every
valid (dp, tp) split of the visible device count for the sharded
serving step (``parallel/plan.py``), A/B-ing the fused one-program step
against the per-op sharded loop at each split, and prints the ICI cost
model's predicted step time next to every measurement — each run
doubles as a predicted-vs-measured check on
``costmodel.predict_step_seconds`` / the ``parallel.*`` knob seeds.

Rows are roofline-stamped by the shared cost model
(``obs.costmodel.serving_step_sharded`` — HBM + MXU + the collective
ICI dimension) and carry BOTH configuration identities:
``mesh_axes`` (dp/tp shape) and ``step_mode`` (fused | per_op), so no
split's rows ever compete with another's banked history
(``obs.bench_audit``).

Usage::

    python benchmarks/bench_sharded_step.py             # on-mesh sweep
    python benchmarks/bench_sharded_step.py --smoke     # 8-virtual-dev CPU
    python benchmarks/bench_sharded_step.py --emit-config > parallel.json

``--emit-config`` prints a ready-to-paste ``"parallel"`` section for
``flashinfer_tpu/tuning_configs/<gen>.json`` with the fused-step
winner's axis split — the step that graduates the shipped section from
``"seed": true`` (ICI-cost-model-derived) to measured.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # runnable from any cwd (sys.path[0] is benchmarks/)
    sys.path.insert(0, _REPO)

_AUDITOR = None


def _emit_row(**kw):
    """One measurement, RowAuditor-stamped, parseable by orchestrators."""
    global _AUDITOR
    try:
        from flashinfer_tpu.obs import bench_audit

        if _AUDITOR is None:
            _AUDITOR = bench_audit.RowAuditor(
                bench_audit.load_banked_history(
                    os.path.join(_REPO, "BENCH_BANKED.md")))
        _AUDITOR.stamp(kw)
    except Exception as e:  # noqa: BLE001 - the audit must never cost a row
        print(f"# row audit failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    print("ROW " + json.dumps(kw), flush=True)
    return kw


def _axis_splits(world: int, hq: int, hkv: int):
    """Every (dp, tp) with dp*tp == world and tp tiling both head
    counts — the sweep grid."""
    out = []
    for tp in range(1, world + 1):
        if world % tp == 0 and hq % tp == 0 and hkv % tp == 0:
            out.append((world // tp, tp))
    return out


def sweep(smoke: bool, emit_config: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.obs import costmodel, hwspec, roofline
    from flashinfer_tpu.parallel.plan import (
        ShardingPlan, build_sharded_fused_step,
        build_sharded_per_op_step, split_shard_weights_for_spec,
        validate_dp_page_table)
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.serve.shard import Int8ShardSpec
    from flashinfer_tpu.utils import is_tpu
    from jax.sharding import Mesh

    if smoke:
        bs, ctx, PS = 4, 128, 16
        hidden, hq, hkv, hd, inter, vocab = 512, 8, 4, 128, 1024, 1024
        L = 2
    else:
        bs, ctx, PS = 64, 4096, 16
        hidden, hq, hkv, hd, inter, vocab = 8192, 64, 8, 128, 28672, 128256
        L = 8
    world = len(jax.devices())
    spec_hw = hwspec.current_spec()
    key = jax.random.PRNGKey(0)

    def qw(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=0)
        return wq, ws.reshape(1, -1)

    spec = Int8ShardSpec(bs=bs, hidden=hidden, hq=hq, hkv=hkv, hd=hd,
                         inter=inter, vocab_shard=vocab, page_size=PS,
                         use_pallas=is_tpu())
    qdim, kvdim = spec.qdim, spec.kvdim
    ks = jax.random.split(key, 6 * L + 2)
    layer_ws = split_shard_weights_for_spec([(
        *qw(ks[6 * i], (hidden, qdim + 2 * kvdim)),
        *qw(ks[6 * i + 1], (qdim, hidden)),
        *qw(ks[6 * i + 2], (hidden, 2 * inter)),
        *qw(ks[6 * i + 3], (inter, hidden)),
        jax.random.normal(ks[6 * i + 4], (hidden,)) * 0.02 + 1.0,
        jax.random.normal(ks[6 * i + 5], (hidden,)) * 0.02 + 1.0,
    ) for i in range(L)], spec)
    head, head_s = qw(jax.random.fold_in(key, 999), (hidden, vocab))
    pages_per_req = ctx // PS
    num_pages = bs * pages_per_req
    lens0 = np.full((bs,), ctx - 1, np.int32)
    x0 = jax.random.normal(jax.random.fold_in(key, 7), (bs, hidden),
                           jnp.bfloat16)
    shape = dict(hidden=hidden, hq=hq, hkv=hkv, hd=hd, inter=inter,
                 vocab_shard=vocab, page_size=PS, weight_bytes=1,
                 kv_bytes=1)

    def mk_caches():
        return [(jax.random.randint(
                    jax.random.fold_in(ks[-2], i),
                    (num_pages, hkv, PS, hd), -127, 127, jnp.int8),
                 jax.random.randint(
                    jax.random.fold_in(ks[-1], i),
                    (num_pages, hkv, PS, hd), -127, 127, jnp.int8))
                for i in range(L)]

    def wall(stepfn, pt0, warm=2, steps=8, repeats=3):
        best = float("inf")
        for _ in range(repeats):
            caches = mk_caches()
            p, l = jnp.asarray(pt0), jnp.asarray(lens0)
            sk = jax.random.PRNGKey(3)
            for _ in range(warm):
                tok, caches, p, l, sk = stepfn(
                    x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])
            t0 = time.perf_counter()
            for _ in range(steps):
                tok, caches, p, l, sk = stepfn(
                    x0, layer_ws, caches, head, head_s, p, l, sk)
            float(tok[0])
            best = min(best, (time.perf_counter() - t0) / steps)
        return best

    splits = [s for s in _axis_splits(world, hq, hkv) if bs % s[0] == 0]
    print(f"# sweeping {len(splits)} axis split(s) of {world} device(s):"
          f" {splits}", file=sys.stderr)
    best_fused = None
    for dp, tp in splits:
        devs = np.array(jax.devices()[:world]).reshape(dp, tp)
        plan = ShardingPlan(Mesh(devs, ("dp", "tp")))
        bs_l, pages_l = bs // dp, num_pages // dp
        rng = np.random.default_rng(0)
        pt0 = np.stack([
            rng.permutation(pages_l)[:pages_per_req]
            + (b // bs_l) * pages_l for b in range(bs)]).astype(np.int32)
        validate_dp_page_table(pt0, num_pages, plan)
        cost = costmodel.serving_step_sharded(bs, ctx, L, dp=dp, tp=tp,
                                              **shape)
        pred = costmodel.predict_step_seconds(
            cost, hbm_tbps=spec_hw.hbm_tbps,
            peak_tflops=spec_hw.peak_tflops(cost.dtype),
            ici_gbps=spec_hw.ici_gbps)
        for name, build in (
            ("fused", lambda: build_sharded_fused_step(
                spec, plan, num_layers=L)),
            ("per_op", lambda: build_sharded_per_op_step(spec, plan)),
        ):
            try:
                t = wall(build(), pt0)
            except Exception as e:  # noqa: BLE001 - one split must not
                print(f"# {plan.mesh_axes}/{name} FAILED "  # cost the rest
                      f"{type(e).__name__}: "
                      f"{(str(e).splitlines() or ['?'])[0][:120]}",
                      file=sys.stderr)
                continue
            row = _emit_row(**roofline.stamp_row(
                dict(phase="serving_sharded", model="llama70b_int8",
                     variant=name, bs=bs, ctx=ctx, layers=L,
                     us_step=round(t * 1e6, 1),
                     pred_us=round(pred * 1e6, 1)),
                cost, t, spec_hw, step_mode=name,
                mesh_axes=plan.mesh_axes))
            print(f"# {plan.mesh_axes:10s} {name:7s} "
                  f"{t * 1e6:10.1f} us/step (pred {pred * 1e6:9.1f}) "
                  f"quality={row.get('quality')}", file=sys.stderr)
            if name == "fused" and (best_fused is None
                                    or t < best_fused[0]):
                best_fused = (t, dp, tp)

    if emit_config and best_fused is not None:
        _, dp, tp = best_fused
        key_str = f"{world}_{hidden}_{hq}_{hkv}"
        section = {"parallel": {
            "comment": f"Measured winner of benchmarks/"
                       f"bench_sharded_step.py on {spec_hw.name} "
                       f"({world} devices).",
            "tactics": {
                f"parallel.tp|{key_str}": tp,
                f"parallel.dp|{key_str}": dp,
                f"parallel.ep|{key_str}": 1,
            },
        }}
        print(json.dumps(section, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dims on an 8-virtual-device CPU mesh")
    ap.add_argument("--emit-config", action="store_true",
                    help="print a tuning_configs 'parallel' section "
                         "with the measured winner")
    args = ap.parse_args()
    if args.smoke and "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sweep(args.smoke, args.emit_config)
    return 0


if __name__ == "__main__":
    sys.exit(main())
