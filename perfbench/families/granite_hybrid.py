"""The family of the ``granitemoehybrid`` block (granite-4.0-h): Mamba-2 and
no-rope attention mixers in the published pattern, a routed expert layer
with a shared expert in every layer, tied and scaled ends.  What the harness
knows about that block, in one place (``README.md``, "A family").

The configuration's cut (``configs/granite-4.0-h-small.json``): this chip
holds the first ``num_hidden_layers`` layers of ``layer_types``, experts
``local_experts_first .. + num_local_experts - 1`` of each layer's
``num_local_experts_published`` (the router keeps its published width), and
the first ``vocab_size`` rows of the vocabulary.  The program's expert layer
and the reference are given the same share, and what the absent experts
would add is left out in both.

Units of the check are layers.  An attention layer's rows are its K and V
rows (``k``, ``v``: ``[n, Hkv, D]``); a Mamba layer has no row a position,
it has one state, so its "rows" are ONE row each: the convolution's tail
(``conv``: its last ``d_conv - 1`` inputs) and ``S`` (``ssm``), both AFTER
the request's ``n`` cached tokens.  ``check.py`` pads every sequence to a
bucket with token 0 and does not say where it ends, and a state, unlike a
row, depends on that: :func:`state_rows` notes ``n`` under the sequence's
own tokens (``_LENGTHS``) and :func:`reference_forward` finds it there (a
sequence without an entry is a finished request, whose states nobody
compares, and runs to its padded end).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from families import granite_hybrid_reference as reference

# The CPU rehearsal's widths (--tiny): never a measurement.
TINY = {"hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256,
        "vocab_size_published": 512, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba", "mamba"],
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "intermediate_size": 64,
        "shared_intermediate_size": 128, "num_local_experts": 4,
        "num_local_experts_published": 8, "local_experts_first": 0,
        "num_experts_per_tok": 3, "attention_multiplier": 0.03125}

# The check's family-owned numbers.  A layer's state depends on every
# mixer, norm and expert layer before it.  All ten units are held: the
# deviation grows evenly to 0.023-0.028 at unit 9, far from the control's,
# so nothing is gained by printing the last four only (the dense block holds
# 4 of 16 because there the sound reading reaches the control's by layer 15).
ROWS_NUMBER = "state_rel_rms"
UNITS_HELD = 10
# The control is the reference with every linear in int8 (not the router and
# not the state, which the configuration states in float32).
CONTROLS = ("int8",)
# Limits.  Each stands between the largest reading of sound runs and the
# reading of the control, both on the chip at the cell's own sizes (my chip
# runs, PR 28; PERF.md section 2).
#
# state_rel_rms, the worst of units 0-9: sound runs read 0.02318, 0.02328,
# 0.02356, 0.02382, 0.02408, 0.02765, 0.02780, 0.02957 over 8 seeds (unit 0, a Mamba
# layer's tail and S, 0.0031; growing by about 0.003 a layer through the
# attention layer's K/V rows at unit 5, 0.0170-0.0181, to unit 9: bf16
# weights, activations and tail against float32, the scan's contractions in
# one bf16 pass); the int8 control reads 0.1741 (per unit 0.0132, 0.0524,
# 0.0748, 0.0962, 0.1177, 0.1244, 0.1323, 0.1464, 0.1652, 0.1741; seed
# 1960000082, whose sound reading is 0.02408).  The limit is the geometric
# middle: 2.4 times the largest sound reading, and the control is 2.5 times
# it.  (With units 0-5 held, as first written, the readings were
# 0.0170-0.0181 and 0.1227-0.1244, and the limit 0.045.)  How much of the
# sound reading is swapped experts: the reference finds
# 4.7-5.5% of its router choices (token x layer) with the 10th and the 11th
# logit within 0.004, about what bf16 rounding of the router's input moves a
# logit by, so up to one token in twenty has an expert swapped in a layer;
# the swapped pair have nearly the same weight (about 0.1 of one of ten
# experts' outputs changes), and the per-unit readings grow as evenly as the
# dense block's do (0.0026 to 0.017 over four layers there), so the swaps
# are a small part of it and no step is visible where they would set in.
#
# token_below_topk: the logits' spread is 0.08 here (sqrt(4096) x 0.02 / 16:
# the scaled, tied head on random weights), so the numbers are small: sound
# runs read 0.0016-0.0048 logits over the 8 seeds, the control's own 40th
# token lies 0.0443 and 0.0445 (two seeds) below the reference's cutoff.  The
# limit is 4 times the largest sound reading and under half the control's.
LIMITS = {"state_rel_rms": 0.07, "token_below_topk": 0.02}

counts = sys.modules[__name__]   # the counts at the end are this module's

_LENGTHS = {}   # tuple(tokens) -> n, noted by state_rows for the reference


def kinds(m):
    """The kinds of the layers held here, in order."""
    return tuple(m["layer_types"][:m["num_hidden_layers"]])


def model_config(config):
    """The program's model object for ``EngineConfig.from_knobs`` and
    ``ServingEngine``."""
    from flashinfer_tpu.models.granite_hybrid import GraniteHybridConfig

    return GraniteHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=kinds(config),
        num_qo_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_n_groups=config["mamba_n_groups"],
        num_experts=config["num_local_experts_published"],
        experts_per_tok=config["num_experts_per_tok"],
        intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        local_experts=(config["local_experts_first"],
                       config["num_local_experts"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        attention_multiplier=float(config["attention_multiplier"]),
        rms_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# The benchmark's own seeded weights (bf16, made on the device)
# ---------------------------------------------------------------------------


def _shapes(m, kind):
    """{name: shape} of one layer: linear weights ``[in, out]``, the held
    experts stacked ``[held, in, out]`` with gate and up side by side."""
    h, held = m["hidden_size"], m["num_local_experts"]
    out = {"input_norm": (h,), "post_norm": (h,),
           "router": (h, m["num_local_experts_published"]),
           "w_gate_up": (held, h, 2 * m["intermediate_size"]),
           "w_down": (held, m["intermediate_size"], h),
           "shared_gate_up": (h, 2 * m["shared_intermediate_size"]),
           "shared_down": (m["shared_intermediate_size"], h)}
    if kind == "attention":
        hd = h // m["num_attention_heads"]
        q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
        out.update(q_proj=(h, q), k_proj=(h, kv), v_proj=(h, kv),
                   o_proj=(q, h))
    else:
        inner = m["mamba_n_heads"] * m["mamba_d_head"]
        conv = inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
        out.update(in_proj=(h, inner + conv + m["mamba_n_heads"]),
                   conv_w=(m["mamba_d_conv"], conv), conv_b=(conv,),
                   dt_bias=(m["mamba_n_heads"],), A_log=(m["mamba_n_heads"],),
                   D=(m["mamba_n_heads"],), ssm_norm=(inner,),
                   out_proj=(inner, h))
    return out


def _weight(key, name, shape):
    """Linear N(0, 0.02^2); norm weights and D 1 + 0.1 N, so a dropped one
    shows; the convolution U(-1/2, 1/2) and a small bias; ``dt_bias`` with
    softplus of it log-uniform in [0.001, 0.1] and ``A_log`` = log U(1, 16),
    Mamba-2's own initialisation, so that the state decays as a trained
    layer's does."""
    f32 = jnp.float32
    if name.endswith("norm") or name == "D":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name == "conv_w":
        w = jax.random.uniform(key, shape, f32, -0.5, 0.5)
    elif name == "conv_b":
        w = 0.1 * jax.random.normal(key, shape, f32)
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    else:
        w = 0.02 * jax.random.normal(key, shape, f32)
    return w.astype(jnp.bfloat16)


def _layer(key, shapes):
    names = sorted(shapes)
    return {n: _weight(k, n, shapes[n])
            for n, k in zip(names, jax.random.split(key, len(names)))}


def _ends(key, hidden, vocab):
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": _weight(ke, "embed", (vocab, hidden)),
            "final_norm": _weight(kn, "final_norm", (hidden,))}


def root_key(seed):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _frozen(m):
    return (tuple(tuple(sorted(_shapes(m, k).items())) for k in kinds(m)),
            m["hidden_size"], m["vocab_size"])


@functools.partial(jax.jit, static_argnums=(1,))
def _all(key, frozen):
    layers, hidden, vocab = frozen
    out = _ends(key, hidden, vocab)
    out["layers"] = [_layer(jax.random.fold_in(key, li), dict(shapes))
                     for li, shapes in enumerate(layers)]
    return out


@functools.partial(jax.jit, static_argnums=(2,))
def _one_layer(key, li, shapes):
    return _layer(jax.random.fold_in(key, li), dict(shapes))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _only_ends(key, hidden, vocab):
    return _ends(key, hidden, vocab)


def make_params(seed, m):
    """The whole tree (``embed`` is the tied head), one jitted call."""
    return _all(root_key(seed), _frozen(m))


def layer_params(seed, m, li):
    """Layer ``li`` alone: the same bits :func:`make_params` gave it."""
    return _one_layer(root_key(seed), jnp.int32(li), _frozen(m)[0][li])


def ends_params(seed, m):
    return _only_ends(root_key(seed), m["hidden_size"], m["vocab_size"])


# ---------------------------------------------------------------------------
# What the engine holds, and the reference
# ---------------------------------------------------------------------------


class _ByUnit(dict):
    """{unit: rows} of one kind, with the ``shape`` ``check.py`` reads the
    number of positions from: units of another kind have no entry."""

    def __init__(self, rows, units, n):
        super().__init__(rows)
        self.shape = (units, n)


def state_rows(eng, req, n):
    """What the engine holds for ``req`` after its first ``n`` positions:
    ``k`` / ``v`` ``[n, Hkv, D]`` at the attention layers (read through the
    page list), ``conv`` / ``ssm`` (one row each) at the Mamba layers (read
    at the request's slot), on the host."""
    pages = jnp.asarray(req.pages, jnp.int32)
    out = {"k": {}, "v": {}, "conv": {}, "ssm": {}}
    for li, (kind, cache) in enumerate(zip(eng.cfg.layer_types, eng.caches)):
        if kind == "attention":
            for name, c in zip(("k", "v"), cache):
                w = jnp.swapaxes(c[pages], 1, 2).reshape(
                    -1, c.shape[1], c.shape[3])
                out[name][li] = np.asarray(w[:n].astype(jnp.float32))
        else:
            for name, c in zip(("conv", "ssm"), cache):
                out[name][li] = np.asarray(
                    c[req.slot].astype(jnp.float32)).reshape(1, -1)
    _LENGTHS[tuple(req.seq()[:n])] = n
    units = len(eng.caches)
    return {name: _ByUnit(rows, units, n) for name, rows in out.items()}


def _length(seq):
    """How many of a padded sequence's tokens are real: what
    :func:`state_rows` noted for it, else all of them."""
    for n in sorted(set(_LENGTHS.values()), reverse=True):
        if _LENGTHS.get(tuple(seq[:n])) == n:
            return n
    return len(seq)


NEAR_TIE = 4e-3   # router logits: bf16 rounding of u moves one by about this


def reference_forward(seqs, config, seed, *, on_rows, precision, logit_rows):
    """The plain reference (``granite_hybrid_reference.py``) over padded
    token sequences, on the benchmark's own weights regenerated from
    ``seed`` one layer at a time, given the same experts and the same
    vocabulary slice.  ``on_rows(s, unit, rows)`` is handed sequence s's
    rows of each layer as they are made; returns the float32 logits at
    ``logit_rows[s]``.  Says how many of the router's choices were near
    ties (the k-th and the next logit within ``NEAR_TIE``): those are the
    tokens whose expert bf16 rounding can swap."""
    ends = ends_params(seed, config)
    lengths = [_length(s) for s in seqs]
    ties = [0, 0]

    def on_gap(si, li, gap):
        real = np.asarray(gap)[:lengths[si]]
        ties[0] += int((real < NEAR_TIE).sum())
        ties[1] += real.size

    logits = reference.forward(
        seqs, lengths, config, ends["embed"],
        lambda li: layer_params(seed, config, li), kinds=kinds(config),
        first=config["local_experts_first"], on_rows=on_rows,
        precision=precision, final_norm=ends["final_norm"],
        logit_rows=logit_rows, on_gap=on_gap)
    print(f"[perfbench] reference ({precision}): {ties[0]} of {ties[1]} "
          f"router choices (token x layer) had the "
          f"{config['num_experts_per_tok']}th and the next logit within "
          f"{NEAR_TIE}", flush=True)
    return logits


# ---------------------------------------------------------------------------
# Counts: the least bytes and operations of one step, from shapes and the
# step's facts (``ctx["steps"]``: what ``step()`` returned and what the
# harness noted beside it).  bf16 weights and rows: 2 bytes.
# ---------------------------------------------------------------------------


def _expert_elems(m):
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _outside_experts_elems(m):
    """Linear weights of the layers held here outside their routed experts
    (mixer, shared expert, router), and the tied embedding once."""
    h = m["hidden_size"]
    total = h * m["vocab_size"]
    for kind in kinds(m):
        sh = _shapes(m, kind)
        names = ("q_proj", "k_proj", "v_proj", "o_proj") \
            if kind == "attention" else ("in_proj", "out_proj")
        total += sum(int(np.prod(sh[n])) for n in names
                     + ("router", "shared_gate_up", "shared_down"))
    return total


def state_bytes_per_request(m):
    """One request's recurrent state over the Mamba layers held: ``S`` in
    float32 and the convolution's tail in bf16."""
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    conv = inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    per_layer = 4 * inner * m["mamba_d_state"] \
        + 2 * (m["mamba_d_conv"] - 1) * conv
    return per_layer * sum(k != "attention" for k in kinds(m))


def kv_bytes_per_token(m):
    hd = m["hidden_size"] // m["num_attention_heads"]
    return 2 * 2 * m["num_key_value_heads"] * hd \
        * sum(k == "attention" for k in kinds(m))


def decode_min_bytes(m, facts):
    """Least HBM bytes of a decode-only step: the weights outside the
    experts and the head once, the experts that got a row, each lane's
    recurrent state read and written, and the K/V of the context."""
    return (2 * _outside_experts_elems(m)
            + moe_expert_bytes(m, facts)
            + 2 * facts["ssm_decode_lanes"] * state_bytes_per_request(m)
            + facts["ctx_tokens"] * kv_bytes_per_token(m))


def ssm_scan_flops(m, facts):
    """Least operations of the convolution and the recurrence (scopes
    ``ssm.conv`` + ``ssm.scan``): per token and Mamba layer, 2 per tap of
    the convolution and, per element of ``S``, 3 for its update (decay,
    outer product, add) and 2 for ``S C``."""
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    conv = inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    per_token = 5 * inner * m["mamba_d_state"] + 2 * m["mamba_d_conv"] * conv
    return facts["tokens"] * per_token \
        * sum(k != "attention" for k in kinds(m))


def ssm_state_bytes(m, facts):
    """Least HBM bytes of the same parts: each scheduled segment's slot
    (tail and ``S``) read and written once (the facts count the one-token
    segments; the longer ones are at least one where there is any), and per
    token the rows in (``xBC``, ``dt``) and out (``y``) in bf16."""
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    conv = inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    layers = sum(k != "attention" for k in kinds(m))
    rows = 2 * (conv + m["mamba_n_heads"] + inner) * layers
    segments = facts["ssm_decode_lanes"] + (facts["ssm_prefill_tokens"] > 0)
    return 2 * segments * state_bytes_per_request(m) \
        + facts["tokens"] * rows


def moe_expert_flops(m, facts):
    """Least operations of the grouped matmuls (scope ``moe.experts``): 2
    per multiply-add of an expert's three matrices, for each row routed to
    an expert held here."""
    return 2 * _expert_elems(m) * facts["moe_routes_local"]


def moe_expert_bytes(m, facts):
    """Least HBM bytes of the same: the weights of the (layer, expert)
    pairs that got a row, once."""
    return 2 * _expert_elems(m) * facts["moe_experts_hit"]
