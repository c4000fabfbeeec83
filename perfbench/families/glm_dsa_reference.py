"""The plain reference of the ``glm_moe_dsa`` block (GLM-5: multi-latent
attention, DeepSeek sparse attention's lightning indexer with top-k selection
per query token, a sigmoid-routed expert layer with a shared expert after the
leading dense layers, untied ends), in straightforward ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  It is
given token ids and the benchmark's own seeded weights one layer at a time
(``glm_dsa.layer_params``) and returns what the block defines: per layer the
latent row ``[c_kv | k_pe]`` and the indexer's key ``k_I`` of every position,
and the logits.

The equations (``n`` is an RMSNorm with its own weight; no biases but the
index key's LayerNorm):

* layer: ``x <- x + Attn(n(x))``; ``x <- x + FFN(n(x))``;
* latents: ``c_q = n(h W_qa)``; ``q = c_q W_qb`` -> heads x (nope + rope), the
  rope part rotated at the token's position (interleaved pairs ``(2i, 2i +
  1)``, angle ``pos * theta^(-2i / rope)``); ``[c_kv | k_pe] = h W_kva``;
  ``c_kv = n(c_kv)``; ``k_pe`` rotated, shared by all heads;
* attention in the EXPANDED form, as the paper states it (the program runs the
  absorbed one, so the two share no algebra): per head ``[k_nope | v] = c_kv
  W_kvb``; ``k = [k_nope | k_pe]``; causal softmax of ``q . k / sqrt(nope +
  rope)`` over the SELECTED positions only; output heads x v -> ``W_o``;
* indexer: ``q_I = c_q W_Iq`` -> index heads x index dim; ``k_I =
  LayerNorm(h W_Ik)``; the first ``rope`` dims of both rotated as above; ``w
  = (h W_Iw) * heads_I^-1/2 * dim_I^-1/2``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])``, ``s <= t``; ``S_t`` = the ``index_topk`` positions
  of row ``t`` with the highest scores, ties to the lower position (every ``s
  <= t`` while ``t < index_topk``): a full score matrix, its ``index_topk``-th
  highest value and a mask, a block of query rows at a time;
* dense FFN: ``W_down (silu(g) * v)``, ``[g | v] = u W_gate_up``;
* expert layer: ``s = sigmoid(u W_r)`` over ALL the published experts; the
  ``num_experts_per_tok`` largest of ``s + bias`` (``n_group`` 1: no group
  stage); weights ``s[chosen] / sum(s[chosen]) * routed_scaling_factor``;
  expert ``e`` adds its weight times its SiLU-gated MLP; the shared expert is
  the same form for every token;
* ends: ``x = E[tok]``; ``logits = n(x_L) W_head`` (untied).

Departures, each because of the cut (the guide's section 4), none in the
mathematics: the reference is GIVEN the experts this chip holds (``first``,
the stack's count) and leaves out what the absent ones would add, without
renormalising, exactly as the program does; the vocabulary is the slice the
configuration holds.  The published implementation's Hadamard rotation and
FP8 quantisation of the indexer are left out (the configuration's file says
why), and multi-token prediction is not served.

One sequence at a time, no cache, no batching.  ``precision="int8"`` is for
the control only: every linear (not the router, the index head weights or the
scores, which the configuration states in float32) has its weight rounded to
8-bit codes per output channel and its input per row.
``precision="float32-dense"`` is the other control (``--control``): selection
off, plain causal attention over every cached position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128   # query rows a pass of the score matrix
# what the int8 control rounds (the held experts' stacks are rounded where
# they are used, one expert at a time)
LINEAR = ("q_a", "q_b", "kv_a", "kv_b", "o_proj", "idx_wq", "idx_wk",
          "gate_up", "down", "shared_gate_up", "shared_down")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layernorm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, pos, theta, dims):
    """Rotate the first ``dims`` of ``x`` ``[T, ..., D]`` at ``pos`` ``[T]``:
    pairs ``(2i, 2i + 1)`` by the angle ``pos * theta^(-2i / dims)``."""
    inv = theta ** (-jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dims // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0:dims:2], x[..., 1:dims:2]
    rot = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate(
        [rot.reshape(x.shape[:-1] + (dims,)), x[..., dims:]], -1)


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
    g, v = jnp.split(_mm(u, w1, precision), 2, -1)
    return _mm(jax.nn.silu(g) * v, w2, precision)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope_dim", "ckv", "v_dim", "idx_heads", "idx_dim",
    "topk", "theta", "eps", "idx_eps", "near", "precision"))
def attention(x, n, layer, *, heads, nope, rope_dim, ckv, v_dim, idx_heads,
              idx_dim, topk, theta, eps, idx_eps, near=0.0,
              precision="float32"):
    """The attention sublayer over one sequence, ``x`` ``[T, hidden]`` float32;
    query rows at and past ``n`` are not computed (nothing reads them: the
    mask is causal).  Returns (x + Attn, latent rows ``[T, ckv + rope]``,
    index keys ``[T, idx_dim]``, per query row how many of its index scores
    lie within ``near`` of its ``topk``-th highest, that one included (0
    where it has no more than ``topk`` positions: nothing is dropped))."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        pos = jnp.arange(t)
        h = rmsnorm(x, _w(layer, "input_norm", precision), eps)
        c_q = rmsnorm(_mm(h, _w(layer, "q_a", precision), precision),
                      _w(layer, "q_a_norm", precision), eps)
        q = _mm(c_q, _w(layer, "q_b", precision), precision).reshape(
            t, heads, nope + rope_dim)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], pos, theta, rope_dim)], -1)
        kv = _mm(h, _w(layer, "kv_a", precision), precision)
        c_kv = rmsnorm(kv[:, :ckv], _w(layer, "kv_a_norm", precision), eps)
        k_pe = rope(kv[:, ckv:], pos, theta, rope_dim)
        kv_up = _mm(c_kv, _w(layer, "kv_b", precision), precision).reshape(
            t, heads, nope + v_dim)
        k = jnp.concatenate(
            [kv_up[..., :nope],
             jnp.broadcast_to(k_pe[:, None], (t, heads, rope_dim))], -1)
        v = kv_up[..., nope:]

        q_i = rope(_mm(c_q, _w(layer, "idx_wq", precision), precision)
                   .reshape(t, idx_heads, idx_dim), pos, theta, rope_dim)
        k_i = rope(layernorm(
            _mm(h, _w(layer, "idx_wk", precision), precision),
            _w(layer, "idx_k_norm", precision),
            _w(layer, "idx_k_bias", precision), idx_eps), pos, theta,
            rope_dim)
        w_i = (h @ _w(layer, "idx_wp", precision)) \
            * (idx_heads ** -0.5 * idx_dim ** -0.5)

        scale = (nope + rope_dim) ** -0.5
        qb = min(QUERY_BLOCK, t)   # t is a multiple of it
        k_sel = min(topk, t)

        def block(b, carry):
            out, closes = carry
            r0 = b * qb
            rows = r0 + jnp.arange(qb)
            causal = pos[None, :] <= rows[:, None]
            qi = jax.lax.dynamic_slice_in_dim(q_i, r0, qb)
            wi = jax.lax.dynamic_slice_in_dim(w_i, r0, qb)
            s_i = jnp.einsum("qjd,sd->qjs", qi, k_i)
            index = jnp.where(
                causal, jnp.einsum("qjs,qj->qs", jax.nn.relu(s_i), wi),
                -jnp.inf)
            kth = jax.lax.top_k(index, k_sel)[0][:, -1]
            drops = jnp.sum(causal, -1) > k_sel
            close = jnp.where(
                drops, jnp.sum(causal & (jnp.abs(index - kth[:, None])
                                         <= near), -1), 0)
            # above the k-th highest, and of those equal to it (in float32
            # only where every index head's product is negative and the
            # score is exactly 0) the lowest positions, k in all
            above = index > kth[:, None]
            equal = causal & (index == kth[:, None])
            room = k_sel - jnp.sum(above, -1, keepdims=True)
            selected = above | (equal & (jnp.cumsum(equal, -1) <= room))
            if precision == "float32-dense":
                selected = causal
            qq = jax.lax.dynamic_slice_in_dim(q, r0, qb)
            s = jnp.einsum("qhd,shd->hqs", qq, k) * scale
            p = jax.nn.softmax(jnp.where(selected[None], s, -jnp.inf), -1)
            o = jnp.einsum("hqs,shv->qhv", p, v).reshape(qb, heads * v_dim)
            return (jax.lax.dynamic_update_slice_in_dim(out, o, r0, 0),
                    jax.lax.dynamic_update_slice_in_dim(closes, close, r0, 0))

        o, closes = jax.lax.fori_loop(
            0, (n + qb - 1) // qb, block,
            (jnp.zeros((t, heads * v_dim), jnp.float32),
             jnp.zeros((t,), jnp.int32)))
        x = x + _mm(o, _w(layer, "o_proj", precision), precision)
        return x, jnp.concatenate([c_kv, k_pe], -1), k_i, closes


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def dense_ffn(x, layer, *, eps, precision="float32"):
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(x, _w(layer, "post_norm", precision), eps)
        return x + swiglu(u, _w(layer, "gate_up", precision),
                          _w(layer, "down", precision), precision)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "eps", "precision"))
def expert_ffn(x, layer, *, top_k, first, scaling, eps, precision="float32"):
    """The expert layer over one sequence: what the HELD experts add
    (``layer["w_gate_up"]`` ``[held, hidden, 2 x width]`` are experts
    ``first .. first + held - 1``) and the shared expert.  Returns (x + FFN,
    the gap between the ``top_k``-th and the next biased score per token)."""
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(x, _w(layer, "post_norm", precision), eps)
        s = jax.nn.sigmoid(u @ layer["router"].astype(jnp.float32))
        top, ids = jax.lax.top_k(s + layer["e_bias"].astype(jnp.float32),
                                 top_k + 1)
        gap = top[:, top_k - 1] - top[:, top_k]
        ids = ids[:, :top_k]
        chosen = jnp.take_along_axis(s, ids, 1)
        weights = chosen / jnp.sum(chosen, -1, keepdims=True) * scaling
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
        return x + routed + shared, gap


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def head_forward(x, final_norm, lm_head, *, eps, precision="float32"):
    with jax.default_matmul_precision("highest"):
        w = lm_head.astype(jnp.float32)
        if precision == "int8":
            w = int8_round(w, -2)
        return _mm(rmsnorm(x, final_norm.astype(jnp.float32), eps), w,
                   precision)


def forward(seqs, needs, m, ends, layer_of, *, ffn, first, on_rows=None,
            precision="float32", logit_rows=None, on_margins=None,
            near=0.0):
    """Run the layers (``ffn[i]``: ``"dense"`` | ``"moe"``) over each token
    sequence of ``seqs``; ``needs[s]`` is how many leading query rows of it
    anything reads (cache rows or logits).

    ``m`` is the model dict (published key names), ``ends`` holds ``embed``,
    ``lm_head`` and ``final_norm`` (the slice held), ``layer_of(i)`` layer
    i's weights, made on demand so that one layer exists at a time.
    ``on_rows(s, i, {"ckv", "kpe", "kidx"})`` is handed the layer's cache
    rows ``[T, ...]``; ``on_margins(s, i, close [T], router gap [T] or
    None)`` how many index scores of each query row lie within ``near`` of
    its ``index_topk``-th, and the router's margin.  Returns ``logits[s]``,
    float32 ``[len(logit_rows[s]), vocab]``."""
    eps = float(m["rms_norm_eps"])
    ckv = int(m["kv_lora_rank"])
    attn_kw = dict(
        heads=int(m["num_attention_heads"]), nope=int(m["qk_nope_head_dim"]),
        rope_dim=int(m["qk_rope_head_dim"]), ckv=ckv,
        v_dim=int(m["v_head_dim"]), idx_heads=int(m["index_n_heads"]),
        idx_dim=int(m["index_head_dim"]), topk=int(m["index_topk"]),
        theta=float(m["rope_parameters"]["rope_theta"]), eps=eps,
        idx_eps=float(m["index_norm_eps"]), near=float(near),
        precision=precision)
    # the dense control shares every other equation with the float32 pass
    rest = "float32" if precision == "float32-dense" else precision
    xs = [ends["embed"][jnp.asarray(seq, jnp.int32)].astype(jnp.float32)
          for seq in seqs]
    for li, kind in enumerate(ffn):
        layer = layer_of(li)
        for si, x in enumerate(xs):
            x, latent, k_i, close = attention(x, jnp.int32(needs[si]), layer,
                                              **attn_kw)
            if kind == "dense":
                x, rgap = dense_ffn(x, layer, eps=eps, precision=rest), None
            else:
                x, rgap = expert_ffn(
                    x, layer, top_k=int(m["num_experts_per_tok"]),
                    first=int(first),
                    scaling=float(m["routed_scaling_factor"]), eps=eps,
                    precision=rest)
            xs[si] = x
            if on_rows is not None:
                on_rows(si, li, {"ckv": latent[:, :ckv],
                                 "kpe": latent[:, ckv:], "kidx": k_i})
            if on_margins is not None:
                on_margins(si, li, close, rgap)
        del layer
    if logit_rows is None:
        return None
    return [head_forward(x[jnp.asarray(r, jnp.int32)], ends["final_norm"],
                         ends["lm_head"], eps=eps, precision=rest)
            for x, r in zip(xs, logit_rows)]
