"""Operations and bytes a step needs, computed from shapes.

These are the benchmark's own yardstick: a later PR cannot change them.
``m`` is a model dict with the published key names; everything served is
``itemsize`` bytes wide (2: bf16 weights and K/V).  Each function counts
the LEAST the algorithm needs, so a share built on it is a lower bound over
a measured time and cannot pass 100%.

The functions of ``(m, facts)`` at the end are what readers call by name
through the family (``readers/roofline.py``: ``count``; ``readers/
steploop.py``: ``decode_min_bytes``).  ``facts`` is one step's facts as
``ctx["steps"]`` holds them: what ``step()`` returned (``tokens``,
``sampled``, ``prefill_tokens``, ...) and what the harness noted beside it
(``ctx_tokens``, the context tokens scheduled).
"""

from __future__ import annotations


def layer_weight_elems(m):
    """Linear weights of one decoder layer (norms are negligible and left
    out, which keeps the count a lower bound)."""
    h, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return h * (2 * q + 2 * kv + 3 * m["intermediate_size"])


def head_elems(m):
    return m["hidden_size"] * m["vocab_size"]


def kv_bytes_per_token(m, itemsize=2):
    """K and V rows of every layer for one token."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * itemsize)


def weight_bytes(m, itemsize=2):
    """Everything resident: layers, embedding and head."""
    return itemsize * (m["num_hidden_layers"] * layer_weight_elems(m)
                       + 2 * head_elems(m))


def decode_step_min_bytes(m, ctx_tokens, itemsize=2):
    """Least HBM bytes a decode-only step must read: every layer's linear
    weights and the head once, and the K/V of the ``ctx_tokens`` context
    tokens it attends over (summed over its lanes).  Embedding rows,
    activations and what it writes are left out."""
    return (itemsize * (m["num_hidden_layers"] * layer_weight_elems(m)
                        + head_elems(m))
            + ctx_tokens * kv_bytes_per_token(m, itemsize))


def step_min_flops(m, tokens, attended_pairs, sampled):
    """Least floating-point operations of a step over ``tokens`` rows that
    attend ``attended_pairs`` (query, key) pairs and sample ``sampled``
    lanes: 2 per multiply-add in the linears and the head, 4 per pair and
    head dimension (QK^T and PV) in attention."""
    return (2 * tokens * m["num_hidden_layers"] * layer_weight_elems(m)
            + 4 * attended_pairs * m["num_hidden_layers"]
            * m["num_attention_heads"] * m["head_dim"]
            + 2 * sampled * head_elems(m))


def decode_min_bytes(m, facts):
    """:func:`decode_step_min_bytes` of one step's facts."""
    return decode_step_min_bytes(m, facts["ctx_tokens"])


def dense_flops(m, facts):
    """Least operations of a step's projections and MLP (the scopes
    ``attn.qkv``, ``attn.out``, ``mlp``): 2 per multiply-add of every linear
    weight, for each of the step's real token rows (not the rung's padded
    ones)."""
    return 2 * facts["tokens"] * m["num_hidden_layers"] * layer_weight_elems(m)


def dense_bytes(m, facts, itemsize=2):
    """Least HBM bytes of the same parts: every layer's linear weights read
    once, however few rows the step has."""
    return itemsize * m["num_hidden_layers"] * layer_weight_elems(m)
