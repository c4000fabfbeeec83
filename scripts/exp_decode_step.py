"""On-chip decode-step timing harness (and a cautionary tale).

Original purpose: explain why gdn_decode_step benched ~88x slower than
kda_decode_step (BENCH_SWEEP 2026-07-31: 1837 us vs 20.9 us for the same
state traffic), with a broadcast-lowering hypothesis and three
formulation variants (base / twostep / fused -- note the fused variant's
state update still carries the [B,H,1,1] broadcast, so it never isolated
the broadcast hypothesis cleanly).

ACTUAL FINDING: the variants are equivalent -- the 1.8 ms readings were
a MEASUREMENT ARTIFACT (multi-second degraded windows
poisoning whole median-of-repeats measurements; they migrated between
variants run to run).  With the escalating min-floor timer in
``testing.utils.bench_fn_device`` all gdn variants measure ~17 us
(~59% of HBM roofline) and selective_state_update measures ~7.8 us
(~98% of roofline), stable across processes.  The script survives as
the validation harness for that timer: all five rows printing stable,
physical numbers is the regression check.

Run: python scripts/exp_decode_step.py   (real chip; ~2 min)
"""
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from flashinfer_tpu.testing import bench_fn_device  # noqa: E402

B, H, dk, dv = 4, 16, 128, 128
key = jax.random.PRNGKey(0)
s0 = jax.random.normal(key, (B, H, dk, dv), jnp.float32)
q = jax.random.normal(jax.random.fold_in(key, 1), (B, H, dk)) * 0.3
k = jax.random.normal(jax.random.fold_in(key, 2), (B, H, dk)) * 0.3
v = jax.random.normal(jax.random.fold_in(key, 3), (B, H, dv))
alpha = jnp.exp(-0.05 * jax.random.uniform(jax.random.fold_in(key, 4),
                                           (B, H)))
beta = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 5),
                                        (B, H)))


def step(s, a4, kf, vf, qf, b4):
    s = a4 * s
    pred = jnp.einsum("bhkv,bhk->bhv", s, kf)
    s = s + b4 * jnp.einsum("bhk,bhv->bhkv", kf, vf - pred)
    o = jnp.einsum("bhkv,bhk->bhv", s, qf)
    return o, s


def base(s, qq, kk, vv, aa, bb):
    return step(s, aa[..., None, None], kk, vv, qq, bb[..., None, None])


def twostep(s, qq, kk, vv, aa, bb):
    a4 = jnp.broadcast_to(aa[..., None], (B, H, dk))[..., None]
    b4 = jnp.broadcast_to(bb[..., None], (B, H, dk))[..., None]
    return step(s, a4, kk, vv, qq, b4)


def fused(s, qq, kk, vv, aa, bb):
    # never scale the state: o = a*(q.S) + correction, S' = a*S + ...
    # requires the same state write anyway -- here decay rides the
    # [B,H,dk] k/q operands (lane-dim-free broadcasts only)
    a_k = aa[..., None]  # [B,H,1] -> broadcasts along dk (minor dim only)
    pred = jnp.einsum("bhkv,bhk->bhv", s, kk) * a_k[..., 0:1]
    upd = jnp.einsum("bhk,bhv->bhkv", bb[..., None] * kk, vv - pred)
    s_new = aa[..., None, None] * s + upd
    o = jnp.einsum("bhkv,bhk->bhv", s_new, qq)
    return o, s_new


for name, fn in (("base", base), ("twostep", twostep), ("fused", fused)):
    t = bench_fn_device(fn, s0, q, k, v, alpha, beta, repeats=5)
    gb = 2 * B * H * dk * dv * 4 / 1e9
    print(f"{name:8s}: {t*1e6:9.1f} us   {gb/t:7.1f} GB/s")


# ---- mamba selective_state_update variants (1629 us banked; ~0.5% rf) ----
H24, dim, ds, G = 24, 64, 128, 1
st = jax.random.normal(key, (B, H24, dim, ds), jnp.float32)
xd = jax.random.normal(jax.random.fold_in(key, 31), (B, H24, dim))
dtd = jax.random.normal(jax.random.fold_in(key, 32), (B, H24, dim))
Ad = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 33),
                                (H24, dim, ds)))
Bd = jax.random.normal(jax.random.fold_in(key, 34), (B, G, ds))
Cd = jax.random.normal(jax.random.fold_in(key, 35), (B, G, ds))


def ssu_base(s, xf, dtf, Af, Bf, Cf):
    rep = H24 // G
    Br = jnp.repeat(Bf, rep, axis=1)
    Cr = jnp.repeat(Cf, rep, axis=1)
    dA = jnp.exp(dtf[..., None] * Af[None])
    dBx = (dtf * xf)[..., None] * Br[:, :, None, :]
    ns = s * dA + dBx
    y = jnp.einsum("bhds,bhs->bhd", ns, Cr)
    return y, ns


def ssu_vpu(s, xf, dtf, Af, Bf, Cf):
    # no repeat (broadcast G->H via reshape), no MXU matvec (VPU reduce),
    # y split so the B-term never needs the materialized state
    rep = H24 // G
    Br = jnp.broadcast_to(Bf[:, :, None, :], (B, G, rep, ds)
                          ).reshape(B, H24, ds)
    Cr = jnp.broadcast_to(Cf[:, :, None, :], (B, G, rep, ds)
                          ).reshape(B, H24, ds)
    dA = jnp.exp(dtf[..., None] * Af[None])
    sd = s * dA
    y1 = (sd * Cr[:, :, None, :]).sum(-1)
    bc = (Br * Cr).sum(-1)  # [B, H]
    y = y1 + (dtf * xf) * bc[..., None]
    ns = sd + (dtf * xf)[..., None] * Br[:, :, None, :]
    return y, ns


for name, fn in (("ssu_base", ssu_base), ("ssu_vpu", ssu_vpu)):
    t = bench_fn_device(fn, st, xd, dtd, Ad, Bd, Cd, repeats=5)
    gb = 2 * B * H24 * dim * ds * 4 / 1e9
    print(f"{name:8s}: {t*1e6:9.1f} us   {gb/t:7.1f} GB/s")
