"""A second family, for ``tests/test_family.py`` only: it arrives as new
files (this one, ``configs/toy-packed.json``, ``layers/
toy.dense_roofline_share.json``) and entries in ``BENCHMARK.json``, and the
harness serves, checks and reads it without an edit to a file that exists.

The engine serves one block today, so the toy's block is that one; what
differs is everything the harness must not assume: its cache rows are one
kind, ``kv`` (K and V side by side), in two units a layer (the KV heads
halved), its limits, its rows number, a number of its own, its counts and
its rehearsal sizes are its own."""

from __future__ import annotations

import sys

import numpy as np

from families import llama_dense
from families.llama_dense import (ends_params, layer_params,  # noqa: F401
                                  make_params, model_config)

TINY = {"hidden_size": 128, "intermediate_size": 384, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 384}

ROWS_NUMBER = "packed_rel_rms"
UNITS_HELD = 3
CONTROLS = ("int8",)
# The toy's own: set for the CPU rehearsal at TINY widths, never read on a chip.
LIMITS = {"packed_rel_rms": 0.02, "token_below_topk": 0.5,
          "samples_without_logits": 0}

counts = sys.modules[__name__]   # the counts below are this module's


def _pack(k, v):
    """[..., Hkv, D] K and V -> the two halves of the heads, each with K and
    V side by side."""
    h = k.shape[-2] // 2
    return [np.concatenate([np.asarray(k[..., s, :]), np.asarray(v[..., s, :])],
                           -1) for s in (slice(0, h), slice(h, None))]


def state_rows(eng, req, n):
    """{"kv": [2 x layers, n, Hkv / 2, 2 D]}."""
    rows = llama_dense.state_rows(eng, req, n)
    units = []
    for k, v in zip(rows["k"], rows["v"]):
        units += _pack(k, v)
    return {"kv": np.stack(units)}


def reference_forward(seqs, config, seed, *, on_rows, precision, logit_rows):
    def on_layer(si, li, rows):
        import jax.numpy as jnp

        for half, packed in enumerate(_pack(rows["k"], rows["v"])):
            on_rows(si, 2 * li + half, {"kv": jnp.asarray(packed)})

    return llama_dense.reference_forward(
        seqs, config, seed, on_rows=on_layer, precision=precision,
        logit_rows=logit_rows)


def more_numbers(samples, config, seed, logits):
    """A number of the family's own: samples whose reference logits do not
    cover their served tokens."""
    return {"samples_without_logits": sum(
        lg.shape != (len(s["served"]), config["vocab_size"])
        for lg, s in zip(logits, samples))}


def _elems(m):
    h, hd = m["hidden_size"], m["head_dim"]
    return h * (2 * m["num_attention_heads"] * hd
                + 2 * m["num_key_value_heads"] * hd
                + 3 * m["intermediate_size"])


def decode_min_bytes(m, facts):
    return 2 * (m["num_hidden_layers"] * _elems(m)
                + m["hidden_size"] * m["vocab_size"])


def dense_flops(m, facts):
    return 2 * facts["tokens"] * m["num_hidden_layers"] * _elems(m)


def dense_bytes(m, facts):
    return 2 * m["num_hidden_layers"] * _elems(m)
