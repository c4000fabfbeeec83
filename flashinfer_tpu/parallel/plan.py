"""Mesh-aware compile-once SHARDED serving step.

The multi-chip half of the plan/run story (reference analogue: one
captured multi-GPU program instead of a Python loop over per-op sharded
calls).  The per-op parallel layer (``parallel/attention.py``
Ulysses/Ring, ``fused_moe_ep``, ``comm/`` fusions) speaks mesh axes; this
module adds a :class:`ShardingPlan` that derives explicit
``NamedSharding``s for every serving-state leaf, and one step that runs
the WHOLE sharded program under the mesh with donated KV buffers.

Components:

- :class:`ShardingPlan` — a ``jax.sharding.Mesh`` plus named (dp, tp,
  ep) axes, and the sharding table for every serving-state leaf:
  replicated small state (norms, scales of row-sharded linears, PRNG
  key), TP-sharded weights/heads (column-shard q/k/v/gate/up/lm_head,
  row-shard o/down, KV-head axis of the paged caches), DP-sharded batch
  state (activations, page tables, kv_lens, the page axis of the caches).
- :class:`ShardedServingStep` / :func:`build_sharded_fused_step` — the
  int8 shard pipeline (``serve/shard.py``) at GLOBAL model dims compiled
  ONCE as a ``shard_map`` over the mesh: a per-device body with explicit
  collectives (int32-psum TP reductions, pmax-amax activation
  quantization, logits all-gather epilogue).  There is one mode: JAX
  refuses to partition a Mosaic kernel on its own, so a step that holds
  Pallas ops lowers only inside a ``shard_map``.

Numerics contract (pinned by tests/test_sharded_step.py): the int8
shard pipeline's TP reductions accumulate in int32 (order-free), so the
sharded step and the unsharded ``serve/shard.py`` step sample
token-for-token identical sequences.

DP paged-KV contract: the page axis of every cache shards over dp, so
all pages of a request must live in its dp block —
``page_table[b] // (num_pages // dp) == b // (batch // dp)`` for every
entry (:func:`validate_dp_page_table`; the per-replica block-pool
layout a dp-sharded serving engine allocates naturally).

Everything here is testable off-hardware on a CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and ran on four
v5e chips through ``chip_smoke.py --chips 4``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from flashinfer_tpu.api_logging import flashinfer_api
from flashinfer_tpu.serve.shard import Int8ShardSpec


# ---------------------------------------------------------------------------
# The plan: mesh + named axes -> NamedShardings per serving-state leaf
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """A mesh plus the serving axis names.

    ``dp`` shards the batch (requests, page pools), ``tp`` the heads /
    hidden projections / vocab, ``ep`` (optional; MoE steps) the expert
    axis.  Axes named here must exist in the mesh; absent ``ep`` means
    experts replicate (or fold into tp, the ``fused_moe_ep`` default)."""

    mesh: Mesh
    dp: str = "dp"
    tp: str = "tp"
    ep: Optional[str] = None

    def __post_init__(self):
        names = tuple(self.mesh.axis_names)
        for axis in (self.dp, self.tp) + ((self.ep,) if self.ep else ()):
            if axis not in names:
                raise ValueError(
                    f"axis {axis!r} not in mesh axes {names}; a "
                    "ShardingPlan names only axes its mesh carries")

    # ---- sizes / identity -----------------------------------------------
    def _axis_size(self, axis: Optional[str]) -> int:
        if axis is None:
            return 1
        return self.mesh.shape[axis]

    @property
    def dp_size(self) -> int:
        return self._axis_size(self.dp)

    @property
    def tp_size(self) -> int:
        return self._axis_size(self.tp)

    @property
    def ep_size(self) -> int:
        return self._axis_size(self.ep)

    @property
    def mesh_axes(self) -> str:
        """The mesh's shape as a string (``"dp2.tp4"``): configuration
        a record carries beside its numbers."""
        parts = [f"dp{self.dp_size}", f"tp{self.tp_size}"]
        if self.ep:
            parts.append(f"ep{self.ep_size}")
        return ".".join(parts)

    # ---- shardings -------------------------------------------------------
    def named(self, *axes) -> NamedSharding:
        """NamedSharding over this plan's mesh (axes as in a
        PartitionSpec: strings, None, or nothing for replicated)."""
        return NamedSharding(self.mesh, P(*axes))

    @property
    def replicated(self) -> NamedSharding:
        return self.named()

    def cache_sharding(self) -> NamedSharding:
        """Paged KV cache [num_pages, kv_heads, page_size, head_dim]:
        page pool over dp (per-replica block pools), KV heads over tp."""
        return self.named(self.dp, self.tp, None, None)

    def shard_layer_shardings(self) -> Dict[str, NamedSharding]:
        """Sharding per leaf of one decoder layer's weight dict (the
        :func:`split_shard_weights` format): column-shard q/k/v/gate/up
        (+ their per-output-channel scales), row-shard o/down (whose
        scales span the full out dim and replicate), replicate norms."""
        col = self.named(None, self.tp)
        row = self.named(self.tp, None)
        repl2 = self.named(None, None)
        repl1 = self.named(None)
        return dict(
            q_proj=col, q_scale=col, k_proj=col, k_scale=col,
            v_proj=col, v_scale=col,
            o_proj=row, o_scale=repl2,
            gate_proj=col, gate_scale=col, up_proj=col, up_scale=col,
            down_proj=row, down_scale=repl2,
            input_norm=repl1, post_norm=repl1,
        )

    def shard_step_shardings(self, num_layers: int):
        """(in_shardings, out_shardings) for the sharded shard-pipeline
        step signature ``(x0, layer_ws, caches, head, head_s, pt, lens,
        skey) -> (tok, caches, pt, lens, skey)``.  Sampled tokens come
        back REPLICATED (the epilogue gathers the vocab-sharded logits
        so every device samples the same tokens)."""
        layer = self.shard_layer_shardings()
        cache = self.cache_sharding()
        in_sh = (
            self.named(self.dp, None),            # x0 [bs, hidden]
            [dict(layer) for _ in range(num_layers)],
            [(cache, cache) for _ in range(num_layers)],
            self.named(None, self.tp),            # head [hidden, vocab]
            self.named(None, self.tp),            # head_s [1, vocab]
            self.named(self.dp, None),            # page_table [bs, ppr]
            self.named(self.dp),                  # kv_lens [bs]
            self.replicated,                      # PRNG key
        )
        out_sh = (
            self.replicated,                      # tokens [bs]
            [(cache, cache) for _ in range(num_layers)],
            self.named(self.dp, None),
            self.named(self.dp),
            self.replicated,
        )
        return in_sh, out_sh

    def spec_tree(self, shardings):
        """The PartitionSpec pytree of a NamedSharding pytree (the
        shard_map in_specs/out_specs form of the same table)."""
        return jax.tree_util.tree_map(
            lambda s: s.spec, shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))


def shard_check(spec: Int8ShardSpec, plan: ShardingPlan) -> None:
    """Divisibility contract of the GLOBAL-dims spec against the mesh:
    heads/inter/vocab over tp, batch over dp."""
    bad = []
    if spec.hq % plan.tp_size:
        bad.append(f"hq {spec.hq} % tp {plan.tp_size}")
    if spec.hkv % plan.tp_size:
        bad.append(f"hkv {spec.hkv} % tp {plan.tp_size}")
    if spec.inter % plan.tp_size:
        bad.append(f"inter {spec.inter} % tp {plan.tp_size}")
    if spec.vocab_shard % plan.tp_size:
        bad.append(f"vocab {spec.vocab_shard} % tp {plan.tp_size}")
    if spec.bs % plan.dp_size:
        bad.append(f"bs {spec.bs} % dp {plan.dp_size}")
    if bad:
        raise ValueError(
            "spec does not tile the mesh: " + ", ".join(bad))


def validate_dp_page_table(page_table, num_pages: int,
                           plan: ShardingPlan) -> None:
    """Host-side check of the DP paged-KV contract: request b's pages
    must all live in b's dp block of the page pool (each dp replica owns
    a contiguous ``num_pages // dp`` slab).  Raises with the offending
    request; a violated contract would silently read another replica's
    pages inside the ``shard_map``."""
    pt = np.asarray(page_table)
    dp = plan.dp_size
    if dp == 1:
        return
    bs = pt.shape[0]
    if bs % dp or num_pages % dp:
        raise ValueError(
            f"batch {bs} / num_pages {num_pages} must divide dp {dp}")
    bs_l, pages_l = bs // dp, num_pages // dp
    blocks = pt // pages_l
    want = np.repeat(np.arange(dp), bs_l)[:, None]
    if not np.array_equal(blocks, np.broadcast_to(want, pt.shape)):
        b = int(np.argwhere(blocks != want)[0][0])
        raise ValueError(
            f"request {b}'s pages leave its dp block (block ids "
            f"{sorted(set(blocks[b].tolist()))}, expected "
            f"{int(want[b, 0])}): a dp-sharded page pool allocates each "
            "replica's requests from its own page slab")


def split_shard_weights_for_spec(layer_ws,
                                 spec: Int8ShardSpec
                                 ) -> List[Dict[str, jax.Array]]:
    """Convert ``serve/shard.py``'s fused per-layer 10-tuples
    ``(wqkv, sqkv, wo, so, wgu, sgu, wd, sd, n1, n2)`` into the named
    per-projection dict the sharded step shards (the spec names the
    column boundaries: fused [q | k | v] and [gate | up] blocks split
    apart so each projection's columns tile over tp as whole heads).
    Column-exact: ``mm(x, concat(a, b)) == concat(mm(x, a), mm(x, b))``,
    so the split changes no numerics."""
    qdim, kvdim, inter = spec.qdim, spec.kvdim, spec.inter
    out = []
    for wqkv, sqkv, wo, so, wgu, sgu, wd, sd, n1, n2 in layer_ws:
        out.append(dict(
            q_proj=wqkv[:, :qdim], q_scale=sqkv[:, :qdim],
            k_proj=wqkv[:, qdim:qdim + kvdim],
            k_scale=sqkv[:, qdim:qdim + kvdim],
            v_proj=wqkv[:, qdim + kvdim:],
            v_scale=sqkv[:, qdim + kvdim:],
            o_proj=wo, o_scale=so,
            gate_proj=wgu[:, :inter], gate_scale=sgu[:, :inter],
            up_proj=wgu[:, inter:], up_scale=sgu[:, inter:],
            down_proj=wd, down_scale=sd,
            input_norm=n1, post_norm=n2,
        ))
    return out


# ---------------------------------------------------------------------------
# The sharded shard-pipeline step bodies
# ---------------------------------------------------------------------------


class _ShardMapComm:
    """The per-device body's collectives, spelled to land bit-identically
    with the unsharded program — TP matmul reductions psum in int32
    BEFORE the f32 scale multiply (integer addition is order-free), activation
    quantization pmaxes the local amax so every shard applies the
    global scale, and the sampling epilogue all-gathers the
    vocab/batch-sharded logits so every device samples the same
    tokens."""

    def __init__(self, spec: Int8ShardSpec, plan: ShardingPlan):
        self.spec = spec
        self.plan = plan
        self.hq_l = spec.hq // plan.tp_size
        self.hkv_l = spec.hkv // plan.tp_size
        self.qdim_l = self.hq_l * spec.hd

    def local_pages(self, pt, kcl):
        # global page ids -> this dp shard's slab-local ids (the
        # validate_dp_page_table contract); kcl is the LOCAL cache
        # shard, so its page axis is the slab length
        if self.plan.dp_size == 1:
            return pt
        rank = jax.lax.axis_index(self.plan.dp)
        return pt - rank * kcl.shape[0]

    def quantize_tp(self, x):
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        amax = jax.lax.pmax(amax, self.plan.tp)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    def mm_row(self, a8, w, name, scale_name, a_scale):
        acc = jnp.dot(a8, w[name], preferred_element_type=jnp.int32)
        acc = jax.lax.psum(acc, self.plan.tp)
        out = (acc.astype(jnp.float32) * a_scale
               * jnp.asarray(w[scale_name], jnp.float32))
        return out.astype(jnp.bfloat16)

    def gather_logits(self, logits):
        full = jax.lax.all_gather(logits, self.plan.tp, axis=1, tiled=True)
        if self.plan.dp_size > 1:
            full = jax.lax.all_gather(full, self.plan.dp, axis=0,
                                      tiled=True)
        return full



def _sharded_layer(x, w: Dict[str, jax.Array], kcl, vcl, pt, lens,
                   spec: Int8ShardSpec, comm):
    """One decoder layer of the int8 shard pipeline over split-named
    weights — the same math as ``serve/shard.py shard_layer`` (paged
    int8-KV append included), with the TP-sensitive steps routed
    through the `comm` strategy."""
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.gemm import mm_int8
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.ops import paged_decode_attention
    from flashinfer_tpu.ops.xla_ref import xla_paged_decode
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.rope import apply_rope_pos_ids

    bs = x.shape[0]
    PS = spec.page_size
    h = rmsnorm(x, w["input_norm"].astype(x.dtype))
    hq8, hs = quantize_int8(h)  # rows span the (unsharded) hidden axis
    q = mm_int8(hq8, w["q_proj"], hs, w["q_scale"]) \
        .reshape(bs, comm.hq_l, spec.hd)
    k = mm_int8(hq8, w["k_proj"], hs, w["k_scale"]) \
        .reshape(bs, comm.hkv_l, spec.hd)
    v = mm_int8(hq8, w["v_proj"], hs, w["v_scale"]) \
        .reshape(bs, comm.hkv_l, spec.hd)
    q, k = apply_rope_pos_ids(q, k, lens)
    pt_l = comm.local_pages(pt, kcl)
    pages = jnp.take_along_axis(pt_l, lens[:, None] // PS, axis=1)[:, 0]
    slots = lens % PS
    k8 = jnp.clip(jnp.round(k.astype(jnp.float32) / spec.k_scale),
                  -127, 127).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(v.astype(jnp.float32) / spec.v_scale),
                  -127, 127).astype(jnp.int8)
    kcl = kcl.at[pages, :, slots, :].set(k8)
    vcl = vcl.at[pages, :, slots, :].set(v8)
    attn_fn = paged_decode_attention if spec.use_pallas \
        else xla_paged_decode
    attn = attn_fn(
        q.astype(jnp.bfloat16), kcl, vcl, pt_l, lens + 1,
        sm_scale=spec.hd ** -0.5 * spec.k_scale, kv_layout="HND",
    ) * spec.v_scale
    a8, as_ = comm.quantize_tp(
        attn.reshape(bs, comm.qdim_l).astype(x.dtype))
    x = x + comm.mm_row(a8, w, "o_proj", "o_scale", as_)
    h2 = rmsnorm(x, w["post_norm"].astype(x.dtype))
    g8, gs = quantize_int8(h2)
    mlp = silu_and_mul(jnp.concatenate(
        [mm_int8(g8, w["gate_proj"], gs, w["gate_scale"]),
         mm_int8(g8, w["up_proj"], gs, w["up_scale"])], -1))
    m8, ms = comm.quantize_tp(mlp)
    x = (x + comm.mm_row(m8, w, "down_proj", "down_scale", ms)) \
        .astype(x.dtype)
    return x, kcl, vcl


def _sharded_epilogue(x, head, head_s, skey, spec: Int8ShardSpec, comm):
    """lm_head shard + top-k sampling over the gathered logits — the
    ``serve/shard.py head_and_sample`` math; every device ends with the
    same tokens and the same folded key."""
    from flashinfer_tpu.gemm import mm_int8
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.sampling import (sampling_from_logits,
                                         top_k_mask_logits)

    hq8, hs = quantize_int8(
        rmsnorm(x, jnp.ones((spec.hidden,), x.dtype)))
    logits = mm_int8(hq8, head, hs, head_s, out_dtype=jnp.float32)
    logits = comm.gather_logits(logits)
    tok = sampling_from_logits(top_k_mask_logits(logits, spec.top_k),
                               skey)
    return tok, jax.random.fold_in(skey, tok[0])


def _step_math(x0, layer_ws, caches, head, head_s, pt, lens, skey,
               spec: Int8ShardSpec, comm):
    """One whole serving step (layers + sampling epilogue)."""
    x = x0
    new_caches = []
    for w, (kcl, vcl) in zip(layer_ws, caches):
        x, kcl, vcl = _sharded_layer(x, w, kcl, vcl, pt, lens, spec,
                                     comm)
        new_caches.append((kcl, vcl))
    tok, skey = _sharded_epilogue(x, head, head_s, skey, spec, comm)
    return tok, new_caches, pt, lens, skey


class _CountingStep:
    """One step body as a jitted ``shard_map`` over the plan's mesh: it
    counts its own traces (the compile-once pin's instrument) and keeps
    its donated state on the plan's layout."""

    def __init__(self, body, plan: ShardingPlan, num_layers: int,
                 donate: bool):
        in_sh, out_sh = plan.shard_step_shardings(num_layers)
        self.num_traces = 0
        self._state_shardings = (in_sh[2], in_sh[5], in_sh[6], in_sh[7])

        def _counted(*args):
            self.num_traces += 1  # trace-time only
            return body(*args)

        mapped = jax.shard_map(
            _counted, mesh=plan.mesh, in_specs=plan.spec_tree(in_sh),
            out_specs=plan.spec_tree(out_sh), check_vma=False)
        self._fn = jax.jit(mapped, in_shardings=in_sh,
                           out_shardings=out_sh,
                           donate_argnums=(2, 5, 6, 7) if donate else ())

    @property
    def jitted(self):
        """The underlying jitted callable (for .lower() inspection —
        the donation-aliasing pin in tests)."""
        return self._fn

    def __call__(self, x0, layer_ws, caches, head, head_s, pt, lens, skey):
        # a state no device of the mesh holds yet and the state a step
        # returned are two argument types to ``jax.jit`` (the type names
        # the mesh an array is committed to): without this, call 2 traces
        # and compiles the whole step again.  For a state that is on the
        # layout already this hands back the same buffers.
        caches, pt, lens, skey = jax.device_put(
            (caches, pt, lens, skey), self._state_shardings)
        return self._fn(x0, layer_ws, caches, head, head_s, pt, lens, skey)


def build_sharded_fused_step(spec: Int8ShardSpec, plan: ShardingPlan, *,
                             num_layers: int, donate: bool = True):
    """The compile-once SHARDED shard step: ONE XLA program per serving
    step over the whole mesh, a ``shard_map`` of the per-device body with
    explicit collectives.

    ``spec`` carries GLOBAL model dims (the whole 70B, not the per-chip
    shard); the plan's shardings slice it per device.  Signature is
    ``serve/shard.py build_fused_step``'s with split-named layer dicts
    (:func:`split_shard_weights_for_spec`): ``step(x0, layer_ws, caches,
    head, head_s, pt, lens, skey) -> (tok, caches, pt, lens, skey)``;
    caches / page table / lens / PRNG key are donated.  Returns a
    :class:`_CountingStep` (callable; ``num_traces`` pins compile-once)."""
    shard_check(spec, plan)
    body = functools.partial(_step_math, spec=spec,
                             comm=_ShardMapComm(spec, plan))
    return _CountingStep(body, plan, num_layers, donate)


class ShardedServingStep:
    """plan/run lifecycle over :func:`build_sharded_fused_step`.

    >>> splan = ShardingPlan(mesh, dp="dp", tp="tp")
    >>> step = ShardedServingStep()
    >>> step.plan(spec, splan, num_layers=L)          # compile once
    >>> tok, caches, pt, lens, skey = step.run(
    ...     x0, layer_ws, caches, head, head_s, pt, lens, skey)

    ``num_traces`` pins compile-once; a trace beyond the first under a
    live plan increments the ``serve.step_retraces`` obs counter (the
    same catalog contract as the engine's rungs)."""

    _STATE_NAMES = ("x0", "layer_ws", "caches", "head", "head_s",
                    "page_table", "kv_lens", "key")

    def __init__(self):
        self._plan: Optional[ShardingPlan] = None
        self._step: Optional[_CountingStep] = None
        self._last_sig = None

    @property
    def num_traces(self) -> int:
        return 0 if self._step is None else self._step.num_traces

    @property
    def sharding_plan(self) -> Optional[ShardingPlan]:
        return self._plan

    @property
    def mesh_axes(self) -> str:
        return self._plan.mesh_axes if self._plan else ""

    def plan(self, spec: Int8ShardSpec, plan: ShardingPlan, *,
             num_layers: int, donate: bool = True) -> None:
        from flashinfer_tpu import obs

        replan = self._step is not None
        self._plan = plan
        self._step = build_sharded_fused_step(
            spec, plan, num_layers=num_layers, donate=donate)
        self._last_sig = None
        # the sharded plan's frozen statics for retrace-cause
        # attribution: shard spec + mesh identity + step shape
        obs.record_plan(self, replan=replan, statics=dict(
            spec=spec, mesh_axes=plan.mesh_axes,
            num_layers=int(num_layers), donate=bool(donate)))

    @flashinfer_api(name="parallel.sharded_step")
    def run(self, x0, layer_ws, caches, head, head_s, pt, lens, skey):
        from flashinfer_tpu import obs

        if self._step is None:
            raise RuntimeError("plan() must be called before run()")
        tick = obs.steploop_begin("ShardedServingStep")
        signed = (x0, layer_ws, caches, head, head_s, pt, lens, skey)
        sig = obs.state_signature(signed, names=self._STATE_NAMES)
        if tick is not None:
            tick.mark("signature")
        before = self._step.num_traces
        t0 = time.perf_counter() if sig is not None else 0.0
        out = self._step(x0, layer_ws, caches, head, head_s, pt, lens,
                         skey)
        if tick is not None:
            tick.dispatched()
        if self._step.num_traces > before:
            if sig is not None:
                obs.record_span(f"{type(self).__name__}.trace_and_compile",
                                "compile", t0, time.perf_counter(),
                                wrapper=type(self).__name__,
                                trace_index=self._step.num_traces)
            if self._step.num_traces > 1:
                obs.counter_inc("serve.step_retraces",
                                wrapper=type(self).__name__)
                if sig is not None:
                    obs.record_retrace(
                        type(self).__name__,
                        obs.diff_state_sigs(self._last_sig, sig, signed))
        if sig is not None:
            self._last_sig = sig
        if tick is not None:
            jax.block_until_ready(out[0])  # completion probe (gate-ON)
            tick.done()
            tick.commit(tokens=int(x0.shape[0]))
        return out


# ---------------------------------------------------------------------------
# Axis selection (the parallel.* autotune knobs)
# ---------------------------------------------------------------------------


def default_tp(world_size: int, num_qo_heads: int,
               num_kv_heads: int) -> int:
    """Largest tp that tiles both head counts and the world size —
    the all-tp default (serving decode is TP-dominant; dp absorbs the
    remainder)."""
    return max(math.gcd(world_size,
                        math.gcd(num_qo_heads, num_kv_heads)), 1)


def plan_axes(world_size: int, *, hidden: int, num_qo_heads: int,
              num_kv_heads: int) -> Tuple[int, int, int]:
    """(dp, tp, ep) axis sizes for a serving mesh: the registered
    ``parallel.dp`` / ``parallel.tp`` / ``parallel.ep`` autotune knobs
    (shape key ``world_hidden_hq_hkv``), falling back to the all-tp
    default.  Invalid combinations (product != world, head counts not
    tiled) fall back too — a stale config entry must not build an
    uncompilable mesh."""
    from flashinfer_tpu.autotuner import AutoTuner

    key = (int(world_size), int(hidden), int(num_qo_heads),
           int(num_kv_heads))
    t = AutoTuner.get()
    tp = int(t.lookup("parallel.tp", key,
                      default=default_tp(world_size, num_qo_heads,
                                         num_kv_heads)))
    dp = int(t.lookup("parallel.dp", key,
                      default=max(world_size // max(tp, 1), 1)))
    ep = int(t.lookup("parallel.ep", key, default=1))
    # ep factors the tp axis (the Mapping moe_tp*moe_ep == tp contract)
    ok = (dp >= 1 and tp >= 1 and ep >= 1 and dp * tp == world_size
          and num_qo_heads % tp == 0 and num_kv_heads % tp == 0
          and tp % ep == 0)
    if not ok:
        tp = default_tp(world_size, num_qo_heads, num_kv_heads)
        dp, ep = world_size // tp, 1
    return dp, tp, ep


def make_serving_mesh(world_size: Optional[int] = None, *, hidden: int,
                      num_qo_heads: int, num_kv_heads: int,
                      devices=None) -> ShardingPlan:
    """Build a (dp, tp) serving mesh over the visible devices with
    knob-selected axis sizes (``chip_smoke.py --chips 4`` builds its
    mesh with it)."""
    devices = list(devices if devices is not None else jax.devices())
    if world_size is None:
        world_size = len(devices)
    dp, tp, _ = plan_axes(world_size, hidden=hidden,
                          num_qo_heads=num_qo_heads,
                          num_kv_heads=num_kv_heads)
    devs = np.array(devices[:world_size]).reshape(dp, tp)
    return ShardingPlan(Mesh(devs, ("dp", "tp")))
