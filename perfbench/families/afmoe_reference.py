"""The plain reference of the ``afmoe`` block (Arcee Trinity: sliding-window
and full grouped-query attention in a published pattern, rotation on the
window layers only, per-head RMSNorm of queries and keys, a sigmoid gate on
the attention output, four norms a layer, leading dense MLPs and then a
sigmoid-routed expert layer with a shared expert, a scaled embedding and an
untied head), in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  It is
given token ids and the benchmark's own seeded weights one layer at a time
(``afmoe.layer_params``) and returns what the block defines: per layer the
key and the value of every position, and the logits.

The equations (``n*`` RMSNorms with their own weights, eps ``rms_norm_eps``;
no biases):

* ends: ``x = E[tok] * sqrt(hidden_size)``; ``logits = n_f(x_L) W_head``;
* layer: ``x <- x + n_post_attn(Attn(n_in(x)))``; ``x <- x +
  n_post_mlp(F(n_pre_mlp(x)))``;
* ``Attn(h)``: ``q = h W_q`` -> heads x head_dim, ``k = h W_k``, ``v = h
  W_v`` -> K/V heads x head_dim, ``g = h W_g`` (as wide as ``q``); ``q`` and
  ``k`` RMS-normed over the head_dim of each head, one weight each for all
  heads; on ``sliding_attention`` layers ONLY, ``q`` and ``k`` rotated at the
  token's position: dims ``(i, i + head_dim / 2)`` are a pair, angle ``pos *
  theta^(-2i / head_dim)``; on ``full_attention`` layers no position signal at
  all; query head ``j`` reads K/V head ``j // (heads / kv_heads)``; softmax of
  ``q . k / sqrt(head_dim)`` over keys ``s <= t`` and, on sliding layers, ``s >
  t - sliding_window`` (``sliding_window`` keys, the query's own among them);
  ``out = (o * sigmoid(g)) W_o``.  THE CACHE ROWS ARE ``k`` (normed, and
  rotated where the layer rotates) AND ``v``;
* ``F`` in the first ``num_dense_layers`` layers: ``W_down (silu(a) * b)``,
  ``[a | b] = u W_gate_up``; after them ``s = sigmoid(u W_r)`` over ALL the
  published experts; the ``num_experts_per_tok`` largest of ``s + bias`` (the
  bias selects only); weights ``s[chosen] / (sum(s[chosen]) + 1e-20) *
  route_scale``; expert ``e`` adds its weight times its SiLU-gated MLP; the
  shared expert is the same form for every token.

Departure, because of the cut (the guide's section 4), none in the
mathematics: the reference is GIVEN the experts this chip holds (``first``,
the stack's count) and leaves out what the absent ones would add, without
renormalising, exactly as the program does.

One sequence at a time, no cache, no batching; attention a block of query
rows at a time against the keys it can see, so a 16k sequence fits.
``precision="int8"`` is for the control only: every linear (not the router,
which the configuration states in float32) has its weight rounded to 8-bit
codes per output channel and its input per row.  ``precision=
"float32-nowindow"`` is the other control (``--control``): the sliding layers
attend the whole context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128   # query rows a pass of the scores
# what the int8 control rounds (the held experts' stacks are rounded where
# they are used, one expert at a time)
LINEAR = ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj", "gate_up",
          "down", "shared_gate_up", "shared_down")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate ``x`` ``[T, heads, D]`` at ``pos`` ``[T]``: dims ``(i, i + D /
    2)`` by the angle ``pos * theta^(-2i / D)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def int8_round(w, axis):
    """Symmetric 8-bit codes, one scale per slice along ``axis``, back in
    float32 (``-2``: per output channel of a ``[.., in, out]`` weight;
    ``-1``: per row of an activation)."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _w(layer, name, precision):
    w = layer[name].astype(jnp.float32)
    return int8_round(w, -2) if precision == "int8" and name in LINEAR else w


def _mm(x, w, precision):
    return (int8_round(x, -1) if precision == "int8" else x) @ w


def swiglu(u, w1, w2, precision):
    a, b = jnp.split(_mm(u, w1, precision), 2, -1)
    return _mm(jax.nn.silu(a) * b, w2, precision)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "rotate", "theta", "eps",
    "precision"))
def attention(x, n, layer, *, heads, kv_heads, head_dim, window, rotate,
              theta, eps, precision="float32"):
    """The attention sublayer over one sequence, ``x`` ``[T, hidden]``
    float32; query rows at and past ``n`` are not computed (nothing reads
    them: the mask is causal).  ``window`` 0: a full layer.  Returns (x +
    n_post_attn(Attn), keys ``[T, kv_heads x head_dim]``, values the same)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        pos = jnp.arange(t)
        h = rmsnorm(x, _w(layer, "input_norm", precision), eps)
        q = rmsnorm(_mm(h, _w(layer, "q_proj", precision), precision)
                    .reshape(t, heads, head_dim),
                    _w(layer, "q_norm", precision), eps)
        k = rmsnorm(_mm(h, _w(layer, "k_proj", precision), precision)
                    .reshape(t, kv_heads, head_dim),
                    _w(layer, "k_norm", precision), eps)
        v = _mm(h, _w(layer, "v_proj", precision), precision).reshape(
            t, kv_heads, head_dim)
        g = _mm(h, _w(layer, "gate_proj", precision), precision)
        if rotate:
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        group = heads // kv_heads
        qb = min(QUERY_BLOCK, t)          # t is a multiple of it
        # the keys a block of query rows can see: all of them in a full
        # layer, the window before its first row up to its last row else
        span = min(t, window + qb - 1) if window else t
        scale = head_dim ** -0.5

        def block(b, out):
            r0 = b * qb
            rows = r0 + jnp.arange(qb)
            k0 = jnp.clip(r0 + qb - span, 0, t - span)
            cols = k0 + jnp.arange(span)
            kk = jax.lax.dynamic_slice_in_dim(k, k0, span)
            vv = jax.lax.dynamic_slice_in_dim(v, k0, span)
            qq = jax.lax.dynamic_slice_in_dim(q, r0, qb).reshape(
                qb, kv_heads, group, head_dim)
            s = jnp.einsum("qngd,snd->ngqs", qq, kk) * scale
            sees = cols[None, :] <= rows[:, None]
            if window:
                sees &= cols[None, :] > rows[:, None] - window
            p = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
            o = jnp.einsum("ngqs,snd->qngd", p, vv).reshape(
                qb, heads * head_dim)
            return jax.lax.dynamic_update_slice_in_dim(out, o, r0, 0)

        o = jax.lax.fori_loop(
            0, (n + qb - 1) // qb, block,
            jnp.zeros((t, heads * head_dim), jnp.float32))
        a = _mm(o * jax.nn.sigmoid(g), _w(layer, "o_proj", precision),
                precision)
        x = x + rmsnorm(a, _w(layer, "post_attn_norm", precision), eps)
        return (x, k.reshape(t, kv_heads * head_dim),
                v.reshape(t, kv_heads * head_dim))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def dense_ffn(x, layer, *, eps, precision="float32"):
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(x, _w(layer, "pre_mlp_norm", precision), eps)
        f = swiglu(u, _w(layer, "gate_up", precision),
                   _w(layer, "down", precision), precision)
        return x + rmsnorm(f, _w(layer, "post_mlp_norm", precision), eps)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "eps", "precision"))
def expert_ffn(x, layer, *, top_k, first, scale, eps, precision="float32"):
    """The expert layer over one sequence: what the HELD experts add
    (``layer["w_gate_up"]`` ``[held, hidden, 2 x width]`` are experts
    ``first .. first + held - 1``) and the shared expert, normed and added.
    Returns (x + n_post_mlp(F), the gap between the ``top_k``-th and the next
    biased score per token)."""
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(x, _w(layer, "pre_mlp_norm", precision), eps)
        s = jax.nn.sigmoid(u @ layer["router"].astype(jnp.float32))
        top, ids = jax.lax.top_k(s + layer["e_bias"].astype(jnp.float32),
                                 top_k + 1)
        gap = top[:, top_k - 1] - top[:, top_k]
        ids = ids[:, :top_k]
        chosen = jnp.take_along_axis(s, ids, 1)
        weights = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) \
            * scale
        held = layer["w_gate_up"].shape[0]
        # [T, held]: the weight each held expert has for each token, 0 where
        # it was not among the chosen
        onehot = (ids[:, :, None] - first) == jnp.arange(held)[None, None, :]
        per_expert = jnp.sum(jnp.where(onehot, weights[:, :, None], 0.0), 1)

        def one(acc, e):
            w1, w2, we = e
            w1, w2 = w1.astype(jnp.float32), w2.astype(jnp.float32)
            if precision == "int8":
                w1, w2 = int8_round(w1, -2), int8_round(w2, -2)
            return acc + we[:, None] * swiglu(u, w1, w2, precision), None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(u),
            (layer["w_gate_up"], layer["w_down"], per_expert.T))
        shared = swiglu(u, _w(layer, "shared_gate_up", precision),
                        _w(layer, "shared_down", precision), precision)
        f = routed + shared
        return x + rmsnorm(f, _w(layer, "post_mlp_norm", precision), eps), gap


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head_forward(x, final_norm, lm_head, *, eps, precision="float32"):
    with jax.default_matmul_precision("highest"):
        w = lm_head.astype(jnp.float32)
        if precision == "int8":
            w = int8_round(w, -2)
        return _mm(rmsnorm(x, final_norm.astype(jnp.float32), eps), w,
                   precision)


def forward(seqs, needs, m, ends, layer_of, *, first, on_rows=None,
            precision="float32", logit_rows=None, on_gap=None):
    """Run the layers over each token sequence of ``seqs``; ``needs[s]`` is
    how many leading query rows of it anything reads (cache rows or logits).

    ``m`` is the model dict (published key names), ``ends`` holds ``embed``,
    ``lm_head`` and ``final_norm``, ``layer_of(i)`` layer i's weights, made
    on demand so that one layer exists at a time.  ``on_rows(s, i, {"k",
    "v"})`` is handed the layer's cache rows ``[T, kv_heads x head_dim]``;
    ``on_gap(s, i, gap [T])`` the router's margin of an expert layer.
    Returns ``logits[s]``, float32 ``[len(logit_rows[s]), vocab]``."""
    eps = float(m["rms_norm_eps"])
    hidden = int(m["hidden_size"])
    window_off = precision == "float32-nowindow"
    # the window control shares every other equation with the float32 pass
    rest = "float32" if window_off else precision
    xs = [ends["embed"][jnp.asarray(seq, jnp.int32)].astype(jnp.float32)
          * hidden ** 0.5 for seq in seqs]
    for li, kind in enumerate(m["layer_types"][:m["num_hidden_layers"]]):
        layer = layer_of(li)
        sliding = kind == "sliding_attention"
        for si, x in enumerate(xs):
            x, k, v = attention(
                x, jnp.int32(needs[si]), layer,
                heads=int(m["num_attention_heads"]),
                kv_heads=int(m["num_key_value_heads"]),
                head_dim=int(m["head_dim"]),
                window=int(m["sliding_window"])
                if sliding and not window_off else 0,
                rotate=sliding, theta=float(m["rope_theta"]), eps=eps,
                precision=rest)
            if li < int(m["num_dense_layers"]):
                x = dense_ffn(x, layer, eps=eps, precision=rest)
            else:
                x, gap = expert_ffn(
                    x, layer, top_k=int(m["num_experts_per_tok"]),
                    first=int(first), scale=float(m["route_scale"]), eps=eps,
                    precision=rest)
                if on_gap is not None:
                    on_gap(si, li, gap)
            xs[si] = x
            if on_rows is not None:
                on_rows(si, li, {"k": k, "v": v})
        del layer
    if logit_rows is None:
        return None
    return [head_forward(x[jnp.asarray(r, jnp.int32)], ends["final_norm"],
                         ends["lm_head"], eps=eps, precision=rest)
            for x, r in zip(xs, logit_rows)]
