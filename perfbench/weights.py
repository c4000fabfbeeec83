"""The benchmark's own seeded weights.

The benchmark, not the program, makes the weights: on the device, from
``--seed``, in the type they are served in (bf16), in ONE jitted call
(:func:`make_params`).  The plain reference (``reference.py``) takes nothing
the program made; it regenerates one layer at a time with
:func:`layer_params` from the same key, which is the same arithmetic on the
same device and so the same bits.

The tree has the layout the engine's caller supplies (``embed``,
``final_norm``, ``lm_head``, ``layers[i]{input_norm, q_proj, k_proj, v_proj,
o_proj, post_norm, gate_proj, up_proj, down_proj}``), with linear weights
stored ``[in, out]``.  Linear weights are N(0, 0.02^2); norm weights are
1 + 0.1 N(0, 1), so a dropped norm weight shows in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LINEAR = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
          "down_proj")


def root_key(seed):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _normal(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm(key, n, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def linear_shapes(m):
    """{name: (in, out)} of one layer's linear weights, from a model
    dict with the published key names."""
    h, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    i = m["intermediate_size"]
    return {"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv),
            "o_proj": (q, h), "gate_proj": (h, i), "up_proj": (h, i),
            "down_proj": (i, h)}


def _layer(key, shapes, hidden, dtype):
    ks = jax.random.split(key, len(LINEAR) + 2)
    out = {n: _normal(k, shapes[n], dtype) for n, k in zip(LINEAR, ks)}
    out["input_norm"] = _norm(ks[-2], hidden, dtype)
    out["post_norm"] = _norm(ks[-1], hidden, dtype)
    return out


def _ends(key, hidden, vocab, dtype):
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _normal(ke, (vocab, hidden), dtype),
            "lm_head": _normal(kh, (hidden, vocab), dtype),
            "final_norm": _norm(kn, hidden, dtype)}


def _frozen(m):
    return (tuple(sorted(linear_shapes(m).items())), m["hidden_size"],
            m["vocab_size"], m["num_hidden_layers"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _all(key, frozen, dtype):
    shapes, hidden, vocab, layers = frozen
    shapes = dict(shapes)
    out = _ends(key, hidden, vocab, dtype)
    out["layers"] = [_layer(jax.random.fold_in(key, li), shapes, hidden,
                            dtype) for li in range(layers)]
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _one_layer(key, li, frozen, dtype):
    shapes, hidden, _, _ = frozen
    return _layer(jax.random.fold_in(key, li), dict(shapes), hidden, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _only_ends(key, frozen, dtype):
    _, hidden, vocab, _ = frozen
    return _ends(key, hidden, vocab, dtype)


def make_params(seed, m, dtype=jnp.bfloat16):
    """The whole tree, one jitted call."""
    return _all(root_key(seed), _frozen(m), dtype)


def layer_params(seed, m, li, dtype=jnp.bfloat16):
    """Layer ``li`` alone: the same bits :func:`make_params` gave it."""
    return _one_layer(root_key(seed), jnp.int32(li), _frozen(m), dtype)


def ends_params(seed, m, dtype=jnp.bfloat16):
    return _only_ends(root_key(seed), _frozen(m), dtype)
