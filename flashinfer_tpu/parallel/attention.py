"""Ulysses (all-to-all over heads) and Ring (KV-rotation) attention.

Re-design of the reference SP layer (``flashinfer/parallel_attention/``):

- **Ulysses** (parallel_wrapper.py:10 ``all_to_all``): sequence-sharded
  activations are all-to-all'd so each rank holds *all* tokens of a *subset
  of heads*, attention runs locally, then the inverse all-to-all restores
  sequence sharding.  The reference builds this from NCCL all-to-all; here
  it is ``jax.lax.all_to_all`` over a mesh axis — XLA lowers it onto ICI.

- **Ring** (parallel_wrapper.py:216-242): KV chunks rotate around the ring
  (``jax.lax.ppermute``) while each rank accumulates partial attention
  states, merged with the online-softmax LSE algebra from ops/merge.py —
  the same attention-state math the reference uses
  (recursive_attention.rst).  O(seq) memory per rank; the long-context
  workhorse.

Both are *per-shard* functions to call inside ``shard_map`` with the
context-parallel axis in scope; ``ParallelAttention`` packages the
shard_map for convenience (mirroring the reference's wrapper class).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flashinfer_tpu.ops.flash_attention import flash_attention
from flashinfer_tpu.ops.merge import merge_state
from flashinfer_tpu.ops.xla_ref import xla_ragged_attention
from flashinfer_tpu.utils import get_sm_scale, is_tpu


def _attn(q, k, v, q_pos, kv_pos, *, causal, sm_scale, use_pallas):
    """Local attention chunk -> (out, lse); positions carry global offsets."""
    T, S = q.shape[0], k.shape[0]
    seg_q = jnp.zeros((T,), jnp.int32)
    seg_kv = jnp.zeros((S,), jnp.int32)
    fn = flash_attention if use_pallas else xla_ragged_attention
    return fn(
        q, k, v, seg_q, seg_kv, q_pos, kv_pos,
        causal=causal, sm_scale=sm_scale, return_lse=True,
    )


def ulysses_attention(
    q: jax.Array,  # [seq_local, num_qo_heads, head_dim]
    k: jax.Array,  # [seq_local, num_kv_heads, head_dim]
    v: jax.Array,
    axis: str = "cp",
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all sequence parallel attention (call inside shard_map).

    Requires num heads divisible by the axis size."""
    cp = jax.lax.axis_size(axis)
    if q.shape[1] % cp or k.shape[1] % cp:
        raise ValueError(
            f"ulysses needs qo heads ({q.shape[1]}) and kv heads "
            f"({k.shape[1]}) divisible by the {axis!r} axis size {cp}; "
            "use ring attention (mode='ring') for GQA head counts below "
            "the axis size"
        )
    sm_scale = get_sm_scale(q.shape[-1], sm_scale)
    # [seq/cp, H, D] -> [seq, H/cp, D]
    qg = jax.lax.all_to_all(q, axis, split_axis=1, concat_axis=0, tiled=True)
    kg = jax.lax.all_to_all(k, axis, split_axis=1, concat_axis=0, tiled=True)
    vg = jax.lax.all_to_all(v, axis, split_axis=1, concat_axis=0, tiled=True)
    seq = qg.shape[0]
    pos = jnp.arange(seq, dtype=jnp.int32)
    fn = flash_attention if is_tpu() else xla_ragged_attention
    out = fn(
        qg, kg, vg,
        jnp.zeros((seq,), jnp.int32), jnp.zeros((seq,), jnp.int32), pos, pos,
        causal=causal, sm_scale=sm_scale,
    )
    # [seq, H/cp, D] -> [seq/cp, H, D]
    return jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=1, tiled=True)


def ring_attention(
    q: jax.Array,  # [chunk, num_qo_heads, head_dim]  (this rank's seq chunk)
    k: jax.Array,  # [chunk, num_kv_heads, head_dim]
    v: jax.Array,
    axis: str = "cp",
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention with LSE-merged partials (call inside shard_map).

    Sequence is chunked contiguously: rank r holds tokens
    ``[r*chunk, (r+1)*chunk)``.  Each of the cp steps computes a partial
    against the currently-held KV chunk and rotates KV to the next rank
    (bidirectional-bandwidth zigzag scheduling is a later optimization)."""
    cp = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    chunk = q.shape[0]
    sm_scale = get_sm_scale(q.shape[-1], sm_scale)
    use_pallas = is_tpu()
    q_pos = me * chunk + jnp.arange(chunk, dtype=jnp.int32)

    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def step(carry, i):
        k_cur, v_cur, acc, lse = carry
        src = jax.lax.rem(me - i + cp, cp)  # owner of the current kv chunk
        kv_pos = src * chunk + jnp.arange(chunk, dtype=jnp.int32)
        o_i, lse_i = _attn(
            q, k_cur, v_cur, q_pos, kv_pos,
            causal=causal, sm_scale=sm_scale, use_pallas=use_pallas,
        )
        acc, lse = merge_state(acc, lse, o_i, lse_i)
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return (k_nxt, v_nxt, acc, lse), None

    # carry the merge accumulator in f32 across all cp steps (casting to
    # q.dtype per step would re-round to bf16 each rotation and degrade
    # precision with cp size); single cast on exit
    acc0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((chunk, q.shape[1]), -1e30, jnp.float32)
    (k_f, v_f, acc, lse), _ = jax.lax.scan(
        step, (k, v, acc0, lse0), jnp.arange(cp)
    )
    return acc.astype(q.dtype)


class ParallelAttention:
    """Mesh-packaged SP attention (mirrors reference ``ParallelAttention``,
    parallel_attention.py:12): pick ``mode="ulysses"`` or ``"ring"``, get a
    jitted callable over sequence-sharded [seq, H, D] global arrays."""

    def __init__(
        self,
        mesh,
        axis: str = "cp",
        mode: str = "ulysses",
        causal: bool = False,
        sm_scale: Optional[float] = None,
    ):
        if mode not in ("ulysses", "ring"):
            raise ValueError(f"unknown parallel attention mode {mode!r}")
        self.mesh = mesh
        self.axis = axis
        self.mode = mode
        fn = ulysses_attention if mode == "ulysses" else ring_attention

        def local(q, k, v):
            return fn(q, k, v, axis, causal=causal, sm_scale=sm_scale)

        spec = P(axis, None, None)
        self._call = jax.jit(
            jax.shard_map(
                local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )
        )

    def run(self, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        return self._call(q, k, v)

    __call__ = run
