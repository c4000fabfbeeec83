"""The plain reference of the ``granitemoehybrid`` block (granite-4.0-h:
Mamba-2 and no-rope attention mixers in a published pattern, a routed expert
layer with a shared expert in every layer, tied and scaled ends), in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  It is
given token ids and the benchmark's own seeded weights one layer at a time
(``granite_hybrid.layer_params``), and returns what the block defines: the
K and V rows of an attention layer, the convolution tail and the state ``S``
of a Mamba layer after a stated number of tokens, and the logits.

The equations (``n`` is an RMSNorm with its own weight; no biases but the
convolution's):

* ends: ``x = embedding_multiplier * E[tok]``;
  ``logits = n(x_L) E^T / logits_scaling`` (tied head);
* layer: ``x <- x + residual_multiplier * mixer(n(x))``;
  ``x <- x + residual_multiplier * (MoE(u) + Shared(u))``, ``u = n(x)``;
* attention: grouped-query, NO positional rotation, causal softmax of
  ``q . k * attention_multiplier``;
* Mamba-2: ``[z | xBC | dt] = h W_in``; ``xBC_t <- silu(b + sum_j w_j
  xBC_{t-3+j})`` (depthwise, causal); ``[x | B | C] = xBC``;
  ``D_t = softplus(dt_t + dt_bias)``; ``a_t = exp(D_t A)``, ``A =
  -exp(A_log)``, one scalar a head; ``S_t = a_t S_{t-1} + D_t x_t (x) B_t``;
  ``y_t = S_t C_t + D x_t``; ``g_t = RMSNorm(y_t silu(z_t))`` over the whole
  inner width (gate first, then one norm); output ``g_t W_out``.  The scan is
  SEQUENTIAL over time (``lax.scan``, one token a step): the published
  ``mamba_chunk_size`` is an implementation choice and has no place here;
* expert layer: ``r = u W_r`` over ALL the published experts; the
  ``num_experts_per_tok`` largest; ``w = softmax`` over those logits; expert
  ``e`` adds ``w_e W2_e (silu(g) * v)``, ``[g | v] = u W1_e``; ``Shared`` is
  the same form for every token.

Departures, each because of the cut (the guide's section 4), none in the
mathematics: the reference is GIVEN the experts this chip holds (``first``,
``count``) and leaves out what the absent ones would add, exactly as the
program does; the vocabulary is the slice the configuration holds.

One sequence at a time, no cache, no batching.  ``precision="int8"`` is for
the control only: every linear (not the router and not the state, which the
configuration states in float32) has its weight rounded to 8-bit codes per
output channel and its input per row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MAMBA_LINEAR = ("in_proj", "out_proj")
ATTN_LINEAR = ("q_proj", "k_proj", "v_proj", "o_proj")
MOE_LINEAR = ("w_gate_up", "w_down", "shared_gate_up", "shared_down")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def int8_round(w, axis):
    """Symmetric 8-bit codes, one scale per slice along ``axis``, back in
    float32 (``-2``: per output channel of a ``[.., in, out]`` weight;
    ``-1``: per row of an activation)."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _mm(x, w, precision):
    return (int8_round(x, -1) if precision == "int8" else x) @ w


def _f32(layer, precision):
    out = {k: v.astype(jnp.float32) for k, v in layer.items()}
    if precision == "int8":
        for name in MAMBA_LINEAR + ATTN_LINEAR + MOE_LINEAR:
            if name in out:
                out[name] = int8_round(out[name], -2)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return out


def swiglu(u, w1, w2, precision):
    gv = _mm(u, w1, precision)
    g, v = jnp.split(gv, 2, -1)
    return _mm(jax.nn.silu(g) * v, w2, precision)


def moe(u, w, *, top_k, first, precision):
    """What the held experts add, and the shared expert.  ``w["router"]`` is
    ``[hidden, all experts]``; ``w["w_gate_up"]`` ``[held, hidden, 2 x
    width]`` are experts ``first .. first + held - 1``.  Returns (the sum,
    the gap between the k-th and the next router logit per token)."""
    logits = u @ w["router"]
    top, ids = jax.lax.top_k(logits, top_k + 1)
    gap = top[:, top_k - 1] - top[:, top_k]
    weights = jax.nn.softmax(top[:, :top_k], -1)
    ids = ids[:, :top_k]
    held = w["w_gate_up"].shape[0]
    # [T, held]: the weight each held expert has for each token, 0 where it
    # was not among the chosen
    onehot = (ids[:, :, None] - first) == jnp.arange(held)[None, None, :]
    per_expert = jnp.sum(jnp.where(onehot, weights[:, :, None], 0.0), 1)

    def one(acc, e):
        w1, w2, we = e
        return acc + we[:, None] * swiglu(u, w1, w2, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (w["w_gate_up"], w["w_down"], per_expert.T))
    return routed + swiglu(u, w["shared_gate_up"], w["shared_down"],
                           precision), gap


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "scale", "eps", "res", "top_k", "first",
    "precision"))
def attention_layer(x, layer, *, heads, kv_heads, head_dim, scale, eps, res,
                    top_k, first, precision="float32"):
    """One attention layer over one sequence, x [T, hidden] float32.
    Returns (x_out, k [T, Hkv, D], v, router gap [T])."""
    with jax.default_matmul_precision("highest"):
        w = _f32(layer, precision)
        t = x.shape[0]
        h = rmsnorm(x, w["input_norm"], eps)
        q = _mm(h, w["q_proj"], precision).reshape(t, heads, head_dim)
        k = _mm(h, w["k_proj"], precision).reshape(t, kv_heads, head_dim)
        v = _mm(h, w["v_proj"], precision).reshape(t, kv_heads, head_dim)
        qg = q.reshape(t, kv_heads, heads // kv_heads, head_dim)
        s = jnp.einsum("tngd,snd->ngts", qg, k) * scale
        pos = jnp.arange(t)
        causal = pos[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("ngts,snd->tngd", p, v).reshape(t, heads * head_dim)
        x = x + res * _mm(o, w["o_proj"], precision)
        add, gap = moe(rmsnorm(x, w["post_norm"], eps), w, top_k=top_k,
                       first=first, precision=precision)
        return x + res * add, k, v, gap


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_head", "d_state", "n_groups", "eps", "res", "top_k",
    "first", "precision"))
def mamba_layer(x, n, layer, *, n_heads, d_head, d_state, n_groups, eps, res,
                top_k, first, precision="float32"):
    """One Mamba-2 layer over one sequence, x [T, hidden] float32; ``n`` is
    how many leading tokens are the sequence (rows past it are padding and
    leave the state alone).  Returns (x_out, the convolution's last d_conv-1
    inputs before position n [d_conv - 1, conv width], S after n tokens
    [heads, d_head, d_state], router gap [T])."""
    with jax.default_matmul_precision("highest"):
        w = _f32(layer, precision)
        t = x.shape[0]
        inner = n_heads * d_head
        gs = n_groups * d_state
        h = rmsnorm(x, w["input_norm"], eps)
        zxd = _mm(h, w["in_proj"], precision)
        z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * gs], -1)
        d_conv = w["conv_w"].shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((d_conv - 1, xbc.shape[1]), xbc.dtype), xbc])
        tail = jax.lax.dynamic_slice_in_dim(padded, n, d_conv - 1, 0)
        conv = w["conv_b"] + sum(w["conv_w"][j] * padded[j:j + t]
                                 for j in range(d_conv))
        xs, b, c = jnp.split(jax.nn.silu(conv), [inner, inner + gs], -1)
        xs = xs.reshape(t, n_heads, d_head)
        rep = n_heads // n_groups
        b = jnp.repeat(b.reshape(t, n_groups, d_state), rep, 1)
        c = jnp.repeat(c.reshape(t, n_groups, d_state), rep, 1)
        delta = jax.nn.softplus(dt + w["dt_bias"])            # [T, heads]
        delta = jnp.where((jnp.arange(t) < n)[:, None], delta, 0.0)
        decay = jnp.exp(delta * -jnp.exp(w["A_log"]))

        def step(s, inp):
            a_t, d_t, x_t, b_t, c_t = inp
            s = a_t[:, None, None] * s \
                + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return s, jnp.einsum("hds,hs->hd", s, c_t)

        s_n, y = jax.lax.scan(
            step, jnp.zeros((n_heads, d_head, d_state), jnp.float32),
            (decay, delta, xs, b, c))
        y = (y + w["D"][:, None] * xs).reshape(t, inner)
        g = rmsnorm(y * jax.nn.silu(z), w["ssm_norm"], eps)
        x = x + res * _mm(g, w["out_proj"], precision)
        add, gap = moe(rmsnorm(x, w["post_norm"], eps), w, top_k=top_k,
                       first=first, precision=precision)
        return x + res * add, tail, s_n, gap


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def head_forward(x, final_norm, embed, *, eps, scaling, precision="float32"):
    with jax.default_matmul_precision("highest"):
        w = embed.astype(jnp.float32).T
        if precision == "int8":
            w = int8_round(w, -2)
        return _mm(rmsnorm(x, final_norm.astype(jnp.float32), eps), w,
                   precision) / scaling


def forward(seqs, lengths, m, embed, layer_of, *, kinds, first, on_rows=None,
            precision="float32", final_norm=None, logit_rows=None,
            on_gap=None):
    """Run the layers ``kinds`` (``"mamba"`` | ``"attention"``) over each
    (padded) token sequence of ``seqs``; ``lengths[s]`` is how many of its
    tokens are real (the state of a Mamba layer is taken there).

    ``m`` is the model dict (published key names), ``embed`` the embedding
    table (the slice held), ``layer_of(i)`` layer i's weights, made on demand
    so that one layer exists at a time.  ``on_rows(s, i, rows)`` is handed
    ``{"k", "v"}`` ``[T, Hkv, D]`` of an attention layer, or ``{"conv": [1,
    (d_conv - 1) x width], "ssm": [1, heads x d_head x d_state]}`` of a Mamba
    layer (one row each: the state is one thing, not a row a position).
    ``on_gap(s, i, gap [T])``: the router's margin at each token.  Returns
    ``logits[s]``, float32 ``[len(logit_rows[s]), vocab]``."""
    moe_kw = dict(eps=float(m["rms_norm_eps"]),
                  res=float(m["residual_multiplier"]),
                  top_k=int(m["num_experts_per_tok"]), first=int(first),
                  precision=precision)
    xs = [float(m["embedding_multiplier"])
          * embed[jnp.asarray(s, jnp.int32)].astype(jnp.float32)
          for s in seqs]
    for li, kind in enumerate(kinds):
        layer = layer_of(li)
        for si, x in enumerate(xs):
            if kind == "attention":
                xs[si], k, v, gap = attention_layer(
                    x, layer, heads=m["num_attention_heads"],
                    kv_heads=m["num_key_value_heads"],
                    head_dim=m["hidden_size"] // m["num_attention_heads"],
                    scale=float(m["attention_multiplier"]), **moe_kw)
                rows = {"k": k, "v": v}
            else:
                xs[si], tail, s_n, gap = mamba_layer(
                    x, jnp.int32(lengths[si]), layer,
                    n_heads=m["mamba_n_heads"], d_head=m["mamba_d_head"],
                    d_state=m["mamba_d_state"], n_groups=m["mamba_n_groups"],
                    **moe_kw)
                rows = {"conv": tail.reshape(1, -1),
                        "ssm": s_n.reshape(1, -1)}
            if on_rows is not None:
                on_rows(si, li, rows)
            if on_gap is not None:
                on_gap(si, li, gap)
        del layer
    if final_norm is None:
        return None
    return [head_forward(x[jnp.asarray(r, jnp.int32)], final_norm, embed,
                         eps=moe_kw["eps"], scaling=float(m["logits_scaling"]),
                         precision=precision)
            for x, r in zip(xs, logit_rows)]
