"""The family of the ``glm_moe_dsa`` block (GLM-5): multi-latent attention
over a latent paged cache, the lightning indexer's key cache beside it,
top-``index_topk`` selection per query token, a sigmoid-routed expert layer
with a shared expert after the leading dense layers, untied ends.  What the
harness knows about that block, in one place (``README.md``, "A family").

The configuration's cut (``configs/glm-5.json``): this chip holds the layers
``layers_held`` (one of the ``first_k_dense_replace`` leading dense layers and
the expert layers that follow them), experts ``local_experts_first .. +
n_routed_experts - 1`` of each expert layer's ``n_routed_experts_published``
(the router keeps its published width), and the first ``vocab_size`` rows of
the vocabulary.  The program's expert layer and the reference are given the
same share, and what the absent experts would add is left out in both.

Units of the check are layers.  A layer's rows are what its two paged arrays
hold a position: the latent row, as ``ckv`` and ``kpe``, and the indexer's
key ``kidx``.  ``check.py`` pads every sequence to a power of two; attention
here is causal, so nothing past the last row anybody reads changes a result,
and :func:`reference_forward` cuts each sequence to the 2,048 rows' multiple
that covers its cache rows and logit rows (five shapes at the most, not
double the work for a 16.4k sequence padded to 32k).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from families import glm_dsa_reference as reference
# at import, so that a program without the block fails before any weight is
# made (a parent tree given this family exits at once, and not zero)
from flashinfer_tpu.models.glm_dsa import GlmDsaConfig

# The CPU rehearsal's widths (--tiny): never a measurement.
TINY = {"hidden_size": 128, "num_attention_heads": 4, "q_lora_rank": 64,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
        "qk_head_dim": 32, "v_head_dim": 16, "index_n_heads": 4,
        "index_head_dim": 32, "index_topk": 24, "intermediate_size": 256,
        "moe_intermediate_size": 64, "n_routed_experts": 4,
        "n_routed_experts_published": 8, "local_experts_first": 0,
        "num_experts_per_tok": 3, "vocab_size": 256,
        "vocab_size_published": 512, "num_hidden_layers": 2,
        "layers_held": [0, 3], "unit_rows": 16}

# The check's family-owned numbers.  All six layers are held: a layer's rows
# depend on every attention, selection and expert layer before it.
ROWS_NUMBER = "latent_rel_rms"
UNITS_HELD = 6
# The control is the reference with every linear in int8 (not the router, the
# index head weights or the scores, which the configuration states in
# float32).  The other control is selection off ("float32-dense": plain
# causal attention).  Both run through ``--control <name>``.
CONTROLS = ("int8", "float32-dense")
# Limits.  Each stands between the largest reading of sound runs and the
# reading of the control, both on the chip at the cell's own sizes (my chip
# runs, PR 35; PERF.md section 2).
#
# latent_rel_rms, the worst of units 0-5: sound runs read 0.0927-0.1041 over
# 13 seeds (unit 0, the first layer's rows, 0.0029: bf16 projections, norms
# and rotation against float32; unit 1 0.063-0.067, then +0.01 a layer).  The
# step from unit 0 to unit 1 is SWAPPED SELECTIONS, not arithmetic: with
# random weights the index scores of a 16k context lie 3e-4 apart around the
# 2,048th, bf16 rounding of q_I and of the cached k_I moves a score by ~4e-3,
# and the reference counts a mean of 21-22 scores within that of a row's
# 2,048th, so ~10 of a query's 2,048 rows are other rows than the
# reference's (0.5%); attention over random weights is nearly flat, so they
# weigh as any row does, sqrt(2 x 10 / 2048) = 10% of the attention's
# output, which is as large as the embedding it is added to.  The int8
# control reads 0.1998 (per unit 0.0130, 0.1460, 0.1614, 0.1744, 0.1861,
# 0.1998; seed 3500000002, whose sound reading is 0.0996): twice the swaps.
# Selection OFF (the reference with plain causal attention, then run by hand in
# the control's place) reads 0.9104 (seed 3500000003).  The limit is the
# geometric middle of the largest sound reading and the int8 control's.
#
# token_below_topk: the logits' spread is 1.6 (sqrt(6144) x 0.02 on a normed
# row).  Sound runs read 0.28, 0.30, 0.37, 0.44, 0.45, 0.46, 0.46, 0.50,
# 0.56, 0.58, 0.78 logits (11 seeds, ~340 served tokens each: a maximum, so
# it has a tail); the int8 control's own 40th token lies 1.76 below the
# reference's cutoff, selection off 7.17.  The limit is the geometric middle
# of 0.78 and 1.76.
LIMITS = {"latent_rel_rms": 0.145, "token_below_topk": 1.2}

counts = sys.modules[__name__]   # the counts at the end are this module's

CUT_ROWS = 2048   # a sequence is cut to a multiple of this many rows


def ffn_kinds(m):
    """``"dense"`` | ``"moe"`` of the layers held here, in order."""
    return tuple("dense" if li < m["first_k_dense_replace"] else "moe"
                 for li in m["layers_held"][:m["num_hidden_layers"]])


def model_config(config):
    """The program's model object for ``EngineConfig.from_knobs`` and
    ``ServingEngine``."""
    return GlmDsaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_ffn=ffn_kinds(config),
        num_qo_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["n_routed_experts_published"],
        experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        local_experts=(config["local_experts_first"],
                       config["n_routed_experts"]),
        rms_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
        scan_chunk=int(config.get("unit_rows", 128)))


# ---------------------------------------------------------------------------
# The benchmark's own seeded weights (bf16, made on the device)
# ---------------------------------------------------------------------------


def _shapes(m, ffn):
    """{name: shape} of one layer: linear weights ``[in, out]``, ``kv_b`` in
    the published form ``[ckv, heads x (nope + v)]``, the held experts
    stacked ``[held, in, out]`` with gate and up side by side."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    out = {"input_norm": (h,), "post_norm": (h,),
           "q_a": (h, m["q_lora_rank"]), "q_a_norm": (m["q_lora_rank"],),
           "q_b": (m["q_lora_rank"], heads * qk),
           "kv_a": (h, m["kv_lora_rank"] + m["qk_rope_head_dim"]),
           "kv_a_norm": (m["kv_lora_rank"],),
           "kv_b": (m["kv_lora_rank"],
                    heads * (m["qk_nope_head_dim"] + m["v_head_dim"])),
           "o_proj": (heads * m["v_head_dim"], h),
           "idx_wq": (m["q_lora_rank"],
                      m["index_n_heads"] * m["index_head_dim"]),
           "idx_wk": (h, m["index_head_dim"]),
           "idx_k_norm": (m["index_head_dim"],),
           "idx_k_bias": (m["index_head_dim"],),
           "idx_wp": (h, m["index_n_heads"])}
    if ffn == "dense":
        out.update(gate_up=(h, 2 * m["intermediate_size"]),
                   down=(m["intermediate_size"], h))
    else:
        held, w = m["n_routed_experts"], m["moe_intermediate_size"]
        out.update(router=(h, m["n_routed_experts_published"]),
                   e_bias=(m["n_routed_experts_published"],),
                   w_gate_up=(held, h, 2 * w), w_down=(held, w, h),
                   shared_gate_up=(h, 2 * w), shared_down=(w, h))
    return out


def _weight(key, name, shape):
    """Linear N(0, 0.02^2) in bf16; norm weights 1 + 0.1 N, so a dropped one
    shows; the index key's LayerNorm bias 0.1 N; the router's correction
    bias 0.1 N in float32.  At the published widths that gives attention
    logits and index scores a spread of order 1 (PERF.md section 2 has the
    spreads read), so which rows are selected matters to the result."""
    f32 = jnp.float32
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name in ("idx_k_bias", "e_bias"):
        w = 0.1 * jax.random.normal(key, shape, f32)
        if name == "e_bias":
            return w
    else:
        w = 0.02 * jax.random.normal(key, shape, f32)
    return w.astype(jnp.bfloat16)


def _layer(key, shapes):
    names = sorted(shapes)
    return {n: _weight(k, n, shapes[n])
            for n, k in zip(names, jax.random.split(key, len(names)))}


def _ends(key, hidden, vocab):
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _weight(ke, "embed", (vocab, hidden)),
            "lm_head": _weight(kh, "lm_head", (hidden, vocab)),
            "final_norm": _weight(kn, "final_norm", (hidden,))}


def root_key(seed):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _frozen(m):
    return (tuple(tuple(sorted(_shapes(m, f).items())) for f in ffn_kinds(m)),
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
    """The whole tree (embedding and head untied), one jitted call."""
    return _all(root_key(seed), _frozen(m))


def layer_params(seed, m, li):
    """Layer ``li`` alone: the same bits :func:`make_params` gave it."""
    return _one_layer(root_key(seed), jnp.int32(li), _frozen(m)[0][li])


def ends_params(seed, m):
    return _only_ends(root_key(seed), m["hidden_size"], m["vocab_size"])


# ---------------------------------------------------------------------------
# What the engine holds, and the reference
# ---------------------------------------------------------------------------


def state_rows(eng, req, n):
    """What the engine holds for ``req`` after its first ``n`` positions, on
    the host: ``ckv`` ``[layers, n, ckv]``, ``kpe`` ``[layers, n, rope]``
    (the two parts of a latent row) and ``kidx`` ``[layers, n, index dim]``,
    each read through the request's page list."""
    pages = jnp.asarray(req.pages, jnp.int32)
    ckv = eng.cfg.kv_lora_rank
    lat, kidx = [], []
    for latent, keys in eng.caches:
        lat.append(np.asarray(latent[pages].reshape(
            -1, latent.shape[-1])[:n].astype(jnp.float32)))
        kidx.append(np.asarray(keys[pages].reshape(
            -1, keys.shape[-1])[:n].astype(jnp.float32)))
    lat = np.stack(lat)
    return {"ckv": lat[..., :ckv], "kpe": lat[..., ckv:],
            "kidx": np.stack(kidx)}


NEAR_INDEX = 4e-3    # index scores: about what bf16 rounding moves one by
NEAR_ROUTER = 2e-3   # router scores (sigmoid of a bf16-rounded input)


def reference_forward(seqs, config, seed, *, on_rows, precision, logit_rows):
    """The plain reference (``glm_dsa_reference.py``) over padded token
    sequences, on the benchmark's own weights regenerated from ``seed`` one
    layer at a time, given the same experts and the same vocabulary slice.
    ``on_rows(s, unit, rows)`` is handed sequence s's rows of each layer as
    they are made; returns the float32 logits at ``logit_rows[s]``.  Says how
    near the selections and the router's choices were to a swap: how many of
    a query row's index scores lie within a bf16 rounding of its k-th highest
    (the rows of its selected set that rounding can swap), and how many
    router choices had the k-th and the next score within one."""
    ends = ends_params(seed, config)
    needs, cut = [], []
    for seq, rows in zip(seqs, logit_rows):
        # check.py pads with token 0 and does not say where a sequence ends;
        # the traffic draws ids from 1 up, so the last nonzero id is the end
        # of what the engine was fed, or a row before it
        real = len(seq) - int(np.argmax(np.asarray(seq)[::-1] != 0))
        need = max([real] + [r + 1 for r in rows])
        needs.append(need)
        cut.append(min(len(seq), -(-need // CUT_ROWS) * CUT_ROWS))
    topk = config["index_topk"]
    close = [0, 0]      # scores near the k-th, query rows that drop any
    router = [0, 0]

    def on_margins(si, li, near_kth, router_gap):
        c = np.asarray(near_kth)[topk:needs[si]]
        close[0] += int(c.sum())
        close[1] += c.size
        if router_gap is not None:
            r = np.asarray(router_gap)[:needs[si]]
            router[0] += int((r < NEAR_ROUTER).sum())
            router[1] += r.size

    logits = reference.forward(
        [list(s[:c]) for s, c in zip(seqs, cut)], needs, config, ends,
        lambda li: layer_params(seed, config, li), ffn=ffn_kinds(config),
        first=config["local_experts_first"], on_rows=on_rows,
        precision=precision, logit_rows=logit_rows, on_margins=on_margins,
        near=NEAR_INDEX)
    print(f"[perfbench] reference ({precision}): over {close[1]} selections "
          f"(query row past {topk} x layer) a mean of "
          f"{close[0] / max(close[1], 1):.1f} index scores lie within "
          f"{NEAR_INDEX} of the row's {topk}th (rows a rounding can swap); "
          f"{router[0]} of {router[1]} router choices (token x layer) had "
          f"the {config['num_experts_per_tok']}th and the next score within "
          f"{NEAR_ROUTER}", flush=True)
    return logits


# ---------------------------------------------------------------------------
# Counts: the least bytes and operations of one step, from shapes and the
# step's facts (``ctx["steps"]``: what ``step()`` returned and what the
# harness noted beside it).  bf16 weights and rows: 2 bytes.  The sparse
# path's facts are per layer (every layer does the same), so they are
# multiplied by the layers held.
# ---------------------------------------------------------------------------


def _layers(m):
    return len(ffn_kinds(m))


def _expert_elems(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _attention_elems(m):
    """Linear weights of one layer's attention (scopes ``attn.qkv`` +
    ``attn.out``): the five latent projections, ``kv_b`` once (its key half
    folded into the query, its value half into the output), ``o_proj``."""
    sh = _shapes(m, "dense")
    return sum(int(np.prod(sh[n]))
               for n in ("q_a", "q_b", "kv_a", "kv_b", "o_proj"))


def _dense_elems(m):
    """What ``attn.qkv`` + ``attn.out`` + ``mlp`` multiply by: every held
    layer's attention and a dense layer's MLP."""
    mlp = 3 * m["hidden_size"] * m["intermediate_size"]
    return _layers(m) * _attention_elems(m) \
        + mlp * sum(f == "dense" for f in ffn_kinds(m))


def dense_flops(m, facts):
    """Least operations of the projections and the dense MLP: 2 per
    multiply-add of their linear weights, for each of the step's real token
    rows."""
    return 2 * facts["tokens"] * _dense_elems(m)


def dense_bytes(m, facts):
    """Least HBM bytes of the same: those weights read once."""
    return 2 * _dense_elems(m)


def sparse_attn_flops(m, facts):
    """Least operations of the sparse attention proper (scope ``attn.core``
    outside the indexer and the selection): per selected (query, row) pair
    and head, 2 per element of the row for the score (ckv + rope) and 2 per
    element of the value (ckv)."""
    per_pair = 2 * m["num_attention_heads"] \
        * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return facts["attn_pairs_selected"] * per_pair * _layers(m)


def sparse_attn_bytes(m, facts):
    """Least HBM bytes of the same: the latent rows some query of the step
    can select (a request's cached rows, or queries x index_topk where that
    is fewer), read once."""
    return 2 * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) \
        * facts["latent_rows_read"] * _layers(m)


def index_flops(m, facts):
    """Least operations of the indexer's scores (scope ``attn.index``): per
    causal (query, cached key) pair and index head, 2 per element of the
    key; the index projections are left out (a lower bound)."""
    return 2 * facts["attn_pairs_causal"] * m["index_n_heads"] \
        * m["index_head_dim"] * _layers(m)


def index_bytes(m, facts):
    """Least HBM bytes of the same: the cached index keys of the step's
    requests, read once."""
    return 2 * m["index_head_dim"] * facts["index_rows_scored"] * _layers(m)


def moe_expert_flops(m, facts):
    """Least operations of the grouped matmuls (scope ``moe.experts``): 2
    per multiply-add of an expert's three matrices, for each row routed to
    an expert held here."""
    return 2 * _expert_elems(m) * facts["moe_routes_local"]


def moe_expert_bytes(m, facts):
    """Least HBM bytes of the same: the weights of the (layer, expert)
    pairs that got a row, once."""
    return 2 * _expert_elems(m) * facts["moe_experts_hit"]
