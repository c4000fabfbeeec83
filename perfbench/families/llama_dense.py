"""The family of the Llama-style dense block (token embedding, RMSNorm,
rotate-half RoPE, causal grouped-query attention, SwiGLU, untied head, no
biases): what the harness knows about that block, in one place.

A configuration file names its family (``"family": "<name>"``; one without
the key is this one), and ``run.py``, ``check.py`` and the readers ask
everything about the block of ``families/<name>.py``, found by name.  What a
family module supplies is listed in ``README.md`` ("A family").  This one
points at the files that were there before the seam: ``weights.py``,
``reference.py`` and ``counts.py``.
"""

from __future__ import annotations

import counts  # noqa: F401  (the family's counts: readers call counts.<name>(config, facts))
import reference
from weights import ends_params, layer_params, make_params  # noqa: F401

# The CPU rehearsal's widths (--tiny): never a measurement.
TINY = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 32,
        "vocab_size": 512}

# The check's family-owned numbers.  A unit is what one set of cache rows is
# compared over: here a layer, with its K rows (after RoPE) and V rows.
# Rows of layer l depend on every kernel of the layers before it (prefill
# launches, decode launch, cascade merge, norm, MLP), so the first UNITS_HELD
# layers cross them all; deeper rows add only the amplification of rounding
# by a random-weight network, and are printed but not held to the limit.
ROWS_NUMBER = "kv_rel_rms"
UNITS_HELD = 4
# The control is the reference computed in int8, the step below the
# configurations' bf16, put in the program's place (``--control int8``).
CONTROLS = ("int8",)
# Limits.  Each stands between the largest reading of sound runs and the
# smallest of the control, both read on the chip at the cells' own sizes;
# PERF.md section 2 lists every reading.
#
# kv_rel_rms: sound runs read 0.0168-0.0172 (7B, both cells) and
# 0.0188-0.0191 (Nemo) over 13 and 12 seeds (bf16 storage and bf16 activations
# against float32: 0.0026 at layer 0, growing by attention and MLP rounding to
# layer 3); the int8 control reads 0.0941-0.0946 (7B) and 0.1035-0.1038 (Nemo)
# on four seeds each (0.0126 at layer 0).  The limit is near the geometric
# middle: 2.1 times the largest sound reading, and the smallest control
# reading is 2.4 times it.
#
# token_below_topk: held against the fault it is there to catch, a token
# altered or drawn outside the reference's top k (a broken sampler, head or
# last layer reads several logits: the logits' spread is ~1.3, and the top 40
# of 32,768 start ~3 spreads up).  Sound runs read 0.00-0.144 logits (bf16
# rounding of 16 layers moves a logit by a few hundredths, and a served token
# is inside the program's own top 40; a widest gap swings by its nature); the
# limit is about three times their largest.  The int8 control reads 0.74-1.08,
# so it fails this number too.
LIMITS = {"kv_rel_rms": 0.04, "token_below_topk": 0.4}


def model_config(config):
    """The program's model object for ``EngineConfig.from_knobs`` and
    ``ServingEngine``."""
    import jax.numpy as jnp
    from flashinfer_tpu.models.llama import LlamaConfig

    if config["torch_dtype"] != "bfloat16":
        raise ValueError("the engine is served in bfloat16 here")
    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_qo_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)


def state_rows(eng, req, n):
    """{"k": [layers, n, Hkv, D], "v": the same}: the first ``n`` K/V rows
    the engine's paged cache holds for ``req``, read through its page list,
    on the host."""
    import jax.numpy as jnp
    import numpy as np

    pages = jnp.asarray(req.pages, jnp.int32)
    out = {"k": [], "v": []}
    for pair in eng.caches:
        for kind, c in zip(("k", "v"), pair):
            w = c[pages]  # [npages, Hkv, PS, D]
            w = jnp.swapaxes(w, 1, 2).reshape(-1, c.shape[1], c.shape[3])
            out[kind].append(np.asarray(w[:n]))
    return {kind: np.stack(rows) for kind, rows in out.items()}


def reference_forward(seqs, config, seed, *, on_rows, precision, logit_rows):
    """The plain reference (``reference.py``) over padded token sequences,
    on the benchmark's own weights regenerated from ``seed`` one layer at a
    time.  ``on_rows(s, unit, {"k": [T, Hkv, D], "v": ...})`` is handed
    sequence s's rows of each layer as they are made; returns the float32
    logits at ``logit_rows[s]``."""
    ends = ends_params(seed, config)
    return reference.forward(
        seqs, config, ends["embed"],
        lambda li: layer_params(seed, config, li),
        layers=config["num_hidden_layers"],
        on_rows=lambda si, li, k, v: on_rows(si, li, {"k": k, "v": v}),
        precision=precision, head=(ends["final_norm"], ends["lm_head"]),
        logit_rows=logit_rows)
