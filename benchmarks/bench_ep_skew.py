"""Exact-EP vs capacity-EP under routing skew (round-5 verdict item 5).

Runs the three fused_moe_ep dispatch modes on an 8-virtual-device CPU
mesh over routing distributions from uniform to pathological, and
reports per mode:

- wall ms/step (median; CPU-mesh — NOT hardware numbers, labeled so),
- exact-mode ROUND COUNT (exact property of the routing, analytically
  recomputed from the same bucket math the kernel uses — valid on any
  backend),
- capacity-mode DROP FRACTION (exact property, measured via
  return_dropped),
- per-rank bytes moved per step (analytic: allgather moves
  T_global * H * itemsize; alltoall moves rounds * ep * cap * H *
  itemsize each way plus the id buckets).

Usage: python benchmarks/bench_ep_skew.py [--json]
The results table is banked in BENCH_BANKED.md behind the
mode-selection guidance in fused_moe_ep's docstring.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_routing(kind: str, T: int, K: int, E: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        ids = rng.integers(0, E, (T, K))
    elif kind.startswith("zipf"):
        a = float(kind.split("-")[1])
        ids = (rng.zipf(a, (T, K)) - 1) % E
    elif kind == "hot90":
        # 90% of routes hit expert 0 (worst-case hot expert)
        ids = rng.integers(0, E, (T, K))
        hot = rng.random((T, K)) < 0.9
        ids = np.where(hot, 0, ids)
    else:
        raise ValueError(kind)
    return jnp.asarray(ids, jnp.int32)


def exact_rounds(ids: np.ndarray, ep: int, E: int, T_local: int, K: int,
                 cf: float) -> int:
    """Trip count of the alltoall_exact while_loop, recomputed from the
    same bucket math (_route_buckets): cap per (src, dst) bucket, rounds
    = ceil(max bucket load / cap) agreed via pmax."""
    e_local = E // ep
    cap = max(int(np.ceil(cf * T_local * K / ep)), 1)
    worst = 0
    for src in range(ep):
        sl = ids[src * T_local:(src + 1) * T_local].reshape(-1)
        dst = sl // e_local
        counts = np.bincount(dst, minlength=ep)
        worst = max(worst, int(counts.max()))
    return -(-worst // cap)


def run(args):
    ep, T_local, K, H, I = 8, 128, 2, 256, 512
    E = 16
    cf = 2.0
    T = ep * T_local
    mesh = Mesh(np.asarray(jax.devices()[:ep]), ("ep",))
    from flashinfer_tpu.fused_moe import fused_moe_ep

    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(k0, (T, H), jnp.float32)
    w_gu = jax.random.normal(k1, (E, H, 2 * I), jnp.float32) * 0.05
    w_dn = jax.random.normal(k2, (E, I, H), jnp.float32) * 0.05
    wts = jnp.full((T, K), 1.0 / K, jnp.float32)

    rows = []
    for kind in ("uniform", "zipf-1.5", "zipf-1.1", "hot90"):
        ids = make_routing(kind, T, K, E)
        ids_np = np.asarray(ids)
        rounds = exact_rounds(ids_np, ep, E, T_local, K, cf)
        cap = max(int(np.ceil(cf * T_local * K / ep)), 1)
        for mode in ("allgather", "alltoall", "alltoall_exact"):
            fn = jax.jit(shard_map(
                functools.partial(
                    fused_moe_ep, num_experts=E, axis="ep", dispatch=mode,
                    capacity_factor=cf, return_dropped=True,
                ),
                mesh=mesh,
                in_specs=(P("ep"), P("ep"), P("ep"), P("ep"), P("ep")),
                out_specs=(P("ep"), P("ep")),
                check_vma=False,
            ))
            out, dropped = fn(hidden, w_gu, w_dn, wts, ids)
            jax.block_until_ready(out)
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                out, dropped = fn(hidden, w_gu, w_dn, wts, ids)
                jax.block_until_ready(out)
                times.append((time.perf_counter() - t0) * 1e3)
            drop_frac = float(np.asarray(dropped).sum()) / (T * K)
            itemsize = 4
            if mode == "allgather":
                bytes_rank = T * H * itemsize  # gathered tokens
                r = 1
            else:
                r = rounds if mode == "alltoall_exact" else 1
                # dispatch + combine, ep buckets of cap tokens each way
                bytes_rank = 2 * r * ep * cap * H * itemsize
            rows.append(dict(
                skew=kind, mode=mode, ms=float(np.median(times)),
                rounds=(r if mode != "allgather" else 0),
                drop_frac=round(drop_frac, 4),
                mbytes_per_rank=round(bytes_rank / 1e6, 2),
            ))
            print(json.dumps(rows[-1]))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    run(args)
