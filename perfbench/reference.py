"""The plain reference: the decoder block as published (Mistral / Llama
style: token embedding, RMSNorm, rotary position embedding in the
rotate-half form at ``rope_theta``, causal grouped-query attention,
SwiGLU, untied head, no biases), in straightforward ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  It
is given token ids and weights (the benchmark's own, ``weights.py``) one
layer at a time, so that only one layer's float32 copy exists at once, and
returns what the block defines: each layer's K rows (after RoPE) and V
rows, which are what a KV cache has to hold, and the logits.

No kernels, no cache, no batching: one sequence at a time, full causal
attention over the whole sequence.  ``precision`` is for the control only
(``check.py``): ``"int8"`` computes every linear in 8-bit integers, the
step below the bf16 the configurations state: the weight rounded to 8-bit
codes per output channel and its input to 8-bit codes per row (symmetric,
dynamic), which is what an int8 matrix unit multiplies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LINEAR = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
          "down_proj")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, H, D]; rotate-half convention (the published implementation:
    the first and second halves of a head form the rotated pairs)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def int8_round(w, axis=0):
    """Symmetric 8-bit codes with one scale per slice along ``axis`` (0: per
    output channel of a weight; -1: per row of an activation), back in
    float32."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _mm(x, w, precision):
    """x @ w; in the int8 control the input is rounded per row too (the
    weight already is, :func:`_f32`)."""
    return (int8_round(x, -1) if precision == "int8" else x) @ w


def _f32(layer, precision):
    out = {k: v.astype(jnp.float32) for k, v in layer.items()}
    if precision == "int8":
        for name in LINEAR:
            out[name] = int8_round(out[name])
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return out


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "theta", "eps", "precision"))
def layer_forward(x, layer, *, heads, kv_heads, head_dim, theta, eps,
                  precision="float32"):
    """One decoder layer over one sequence.  x [T, hidden] float32.  Rows
    past the sequence's end may be padding: the causal mask keeps them out
    of every row before them.  Returns (x_out, k [T, Hkv, D],
    v [T, Hkv, D])."""
    with jax.default_matmul_precision("highest"):
        w = _f32(layer, precision)
        t = x.shape[0]
        pos = jnp.arange(t)
        h = rmsnorm(x, w["input_norm"], eps)
        q = _mm(h, w["q_proj"], precision).reshape(t, heads, head_dim)
        k = _mm(h, w["k_proj"], precision).reshape(t, kv_heads, head_dim)
        v = _mm(h, w["v_proj"], precision).reshape(t, kv_heads, head_dim)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        g = heads // kv_heads
        qg = q.reshape(t, kv_heads, g, head_dim)
        s = jnp.einsum("tngd,snd->ngts", qg, k)
        s = s / jnp.sqrt(jnp.float32(head_dim))
        causal = pos[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("ngts,snd->tngd", p, v).reshape(t, heads * head_dim)
        x = x + _mm(o, w["o_proj"], precision)
        h2 = rmsnorm(x, w["post_norm"], eps)
        mlp = jax.nn.silu(_mm(h2, w["gate_proj"], precision)) \
            * _mm(h2, w["up_proj"], precision)
        x = x + _mm(mlp, w["down_proj"], precision)
        return x, k, v


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head_forward(x, final_norm, lm_head, *, eps, precision="float32"):
    with jax.default_matmul_precision("highest"):
        w = lm_head.astype(jnp.float32)
        if precision == "int8":
            w = int8_round(w)
        return _mm(rmsnorm(x, final_norm.astype(jnp.float32), eps), w,
                   precision)


def forward(seqs, m, embed, layer_of, *, layers, on_rows=None,
            precision="float32", head=None, logit_rows=None):
    """Run ``layers`` layers over each (padded) token sequence of ``seqs``.

    ``m`` is the model dict (published key names), ``embed`` the embedding
    table, ``layer_of(i)`` returns layer i's weights (made on demand: one
    layer exists at a time, and serves every sequence before the next is
    made).  ``on_rows(s, i, k, v)`` is handed sequence s's K rows (after
    RoPE) and V rows of layer i, ``[T, Hkv, D]`` float32, as they are made.
    Returns ``logits[s]``, float32 ``[len(logit_rows[s]), vocab]`` for the
    positions asked, when ``head = (final_norm, lm_head)`` is given."""
    kw = dict(heads=m["num_attention_heads"],
              kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
              theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]),
              precision=precision)
    xs = [embed[jnp.asarray(s, jnp.int32)].astype(jnp.float32) for s in seqs]
    for li in range(layers):
        layer = layer_of(li)
        for si, x in enumerate(xs):
            xs[si], k, v = layer_forward(x, layer, **kw)
            if on_rows is not None:
                on_rows(si, li, k, v)
        del layer
    if head is None:
        return None
    return [head_forward(x[jnp.asarray(r, jnp.int32)], head[0], head[1],
                         eps=kw["eps"], precision=precision)
            for x, r in zip(xs, logit_rows)]
