"""The family of the ``afmoe`` block (Arcee Trinity-Mini): sliding-window and
full grouped-query attention layers three to one, rotation on the window
layers only, per-head RMSNorm of queries and keys, a sigmoid gate on the
attention output, four norms a layer, two leading dense MLPs and then a
sigmoid-routed expert layer with a shared expert, a scaled embedding and an
untied head.  What the harness knows about that block, in one place
(``README.md``, "A family").

The configuration's cut (``configs/trinity-mini.json``): this chip holds every
layer, the whole vocabulary and, of each expert layer's
``num_experts_published`` experts, ``local_experts_first .. + num_experts -
1`` (the router keeps its published width).  The program's expert layer and
the reference are given the same share, and what the absent experts would add
is left out in both.

Units of the check are layers; a layer's rows are the key and the value of
every cached position (``k``, ``v``: ``[n, kv_heads x head_dim]``).  A WINDOW
layer's pages behind the window are released by the engine
(``serve/window_pool.py``), so the rows of positions below ``page_floor(n -
sliding_window)`` are gone by design: :func:`state_rows` and the reference's
rows both hold zeros there, and every row the program still needs is
compared.  ``check.py`` pads every sequence to a power of two; attention is
causal, so nothing past the last row anybody reads changes a result, and
:func:`reference_forward` cuts each sequence to the 2,048 rows' multiple that
covers its cache rows and logit rows.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from families import afmoe_reference as reference
# at import, so that a program without the block fails before any weight is
# made (a parent tree given this family exits at once, and not zero)
from flashinfer_tpu.models.afmoe import AfmoeConfig

# The CPU rehearsal's widths (--tiny): never a measurement.
TINY = {"hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 32,
        "intermediate_size": 256, "moe_intermediate_size": 64,
        "num_experts": 4, "num_experts_published": 8,
        "local_experts_first": 0, "num_experts_per_tok": 3,
        "num_dense_layers": 1, "vocab_size": 256, "num_hidden_layers": 4,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention"]}

# The check's family-owned numbers.
ROWS_NUMBER = "kv_rel_rms"
# Units are the 32 layers, all held: the deviation grows evenly with depth
# (readings below), with no step where it stops being arithmetic.
UNITS_HELD = 32
# The controls (``--control <name>``): the reference with every linear in int8
# (not the router, which the configuration states in float32), and window off
# ("float32-nowindow": the sliding layers attend the whole context).
CONTROLS = ("int8", "float32-nowindow")
# Limits, from readings on the chip at the cell's own sizes (PERF.md section 2
# has every reading; tests/data/afmoe_token_gaps.json holds the token
# readings and tests/test_check.py re-derives the token limits from them).
#
# ``kv_rel_rms`` (my chip runs, PR 37; untouched since): sound 0.0732, 0.0747,
# 0.0751, 0.0761, 0.0763, 0.0768, 0.0775, 0.0779, 0.0791, 0.0794, 0.0814 (11
# seeds; 0.0781 on a 12th with these limits), int8 0.1478 (and 0.1430 on that
# 12th), window off 1.2374; the limit is the geometric middle of 0.0814 and
# 0.1478 (room 1.35x each side).  It is what tells int8 from bf16 here.
#
# ``token_below_topk`` is the widest gap over the few hundred tokens a run
# samples: a maximum of a heavy-tailed quantity (15% of router choices are
# near-ties, so about one token in seven has a swapped expert a layer), which
# spreads 8x over the seeds on record and cannot tell int8 from bf16 (int8
# 0.450, 0.696, INSIDE the sound readings).  Until PR 47 it stood at 0.44,
# 1.27x over the largest of PR 37's twelve readings, and refused sound runs
# (0.4535, PR 38; 0.6925, the ledger's PR 42; the accepted parent twice in PR
# 46's check).  It is the GROSS-FAULT limit, for a broken head, sampler or
# window: the geometric middle of the largest sound reading on record and the
# smallest window-off reading, asked to leave 2x over every sound reading and
# 1.5x under every window-off one (ISSUE 47; ``tests/gap_readings.py derive``).
#
# Readings (the widest gap a run): before PR 47 (PR 37, 38, 44 and the
# ledger's PR 42: 31 readings) 0.084 ... 0.4056, 0.4535, 0.6925 the largest.
# PR 47 (my chip runs, the accepted program of d0b4f0b; 20 untraced runs,
# seeds 3800000603 and 1041883974, the two on record that tripped, and
# 4700000101-118, and 3 traced, 4700000119-121; every sampled token's gap
# kept): 0.0955, 0.1097, 0.1305, 0.1321, 0.1485, 0.1527, 0.1587, 0.1605,
# 0.1756, 0.1894, 0.2097, 0.2099, 0.2190, 0.2198, 0.2267, 0.2463, 0.2477,
# 0.2637, 0.3105, 0.3401, 0.3469, 0.3622, 0.3688 over 200-522 tokens a run.
# Pooled, 7,660 tokens: 407 outside the reference's top 40 at all (5.3%), 97
# over 0.1, 27 over 0.2, 7 over 0.3, none over 0.44; the widest lie evenly
# over requests, positions, prompt lengths and in-flight against finished,
# the 30 widest at ranks 83-175 of 200,192: rounding and swapped experts at
# the cutoff, no other distribution (PERF.md section 2; first tokens read
# NARROWER: 3 of 171 outside).  Window off (``--control float32-nowindow``,
# its own 40th-best token's gap at every position): 4.5161 (PR 37), 4.7271,
# 5.4270 (PR 47).  The middle of 0.6925 and 4.5161 is 1.77, written 1.8: 2.6x
# over the largest sound reading on record (4.9x over PR 47's largest), 2.5x
# under the smallest window-off reading.
#
# One number that repeats beside it (ISSUE 47: the statistic of the gaps with
# the largest window-off ratio whose sound spread is under 2x, at the
# middle, given 1.5x of room) is NOT held: none earns it.  Over PR 47's 23
# sound runs, largest / smallest; window off and int8 are the smaller of two
# seeds' readings of tokens DRAWN from the control's logits as the traffic
# samples (temperature 0.8, top k 40, top p 0.95; ``check.draw_tokens``),
# over the largest sound reading:
#   token_gap_p99             0.0579-0.1876  3.2x   window off 21x   int8 1.4x
#   token_gap_p90             0 in every run  -     (2.99)           (0.011)
#   token_outside_topk_share  0.0318-0.0898  2.8x   window off 11x   int8 1.3x
#   token_over_0p1_share      0-0.0310        -     window off 31x   int8 1.4x
#   token_over_0p2_share      0-0.0096        -     window off 100x  int8 2.2x
#   token_gap_mean_positive   0.0014-0.0082  5.9x   window off 229x  int8 1.5x
# A run samples 200-500 tokens, 10-30 of them outside the top 40: the spread
# is the counting noise of that few, not the program's.  Every statistic is
# printed and kept all the same (``check: gaps of ...``, ``--keep-gaps``), and
# a run that sampled ~1,500 tokens would bring the share outside the top 40
# under 1.5x (PERF.md section 7).  int8 is left to ``kv_rel_rms`` (PR 47:
# sound 0.0706-0.0829, int8 0.1425, 0.1483).
LIMITS = {"kv_rel_rms": 0.11, "token_below_topk": 1.8}

counts = sys.modules[__name__]   # the counts at the end are this module's

CUT_ROWS = 2048   # a sequence is cut to a multiple of this many rows


def model_config(config):
    """The program's model object for ``EngineConfig.from_knobs`` and
    ``ServingEngine``."""
    return AfmoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        num_qo_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        attention_window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        num_dense_layers=config["num_dense_layers"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts_published"],
        experts_per_tok=config["num_experts_per_tok"],
        route_scale=float(config["route_scale"]),
        local_experts=(config["local_experts_first"], config["num_experts"]),
        rms_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# The benchmark's own seeded weights (bf16, made on the device)
# ---------------------------------------------------------------------------


def _shapes(m, dense):
    """{name: shape} of one layer: linear weights ``[in, out]``, the held
    experts stacked ``[held, in, out]`` with gate and up side by side."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    out = {"input_norm": (h,), "post_attn_norm": (h,), "pre_mlp_norm": (h,),
           "post_mlp_norm": (h,), "q_proj": (h, q), "k_proj": (h, kv),
           "v_proj": (h, kv), "gate_proj": (h, q), "o_proj": (q, h),
           "q_norm": (d,), "k_norm": (d,)}
    if dense:
        out.update(gate_up=(h, 2 * m["intermediate_size"]),
                   down=(m["intermediate_size"], h))
    else:
        held, w = m["num_experts"], m["moe_intermediate_size"]
        out.update(router=(h, m["num_experts_published"]),
                   e_bias=(m["num_experts_published"],),
                   w_gate_up=(held, h, 2 * w), w_down=(held, w, h),
                   shared_gate_up=(h, 2 * w), shared_down=(w, h))
    return out


def _dense_layers(m):
    return tuple(li < m["num_dense_layers"]
                 for li in range(m["num_hidden_layers"]))


def _weight(key, name, shape):
    """Linear N(0, 0.02^2) in bf16; norm weights 1 + 0.1 N, so a dropped one
    shows; the router's selection bias 0.1 N in float32."""
    f32 = jnp.float32
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name == "e_bias":
        return 0.1 * jax.random.normal(key, shape, f32)
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
    return (tuple(tuple(sorted(_shapes(m, d).items()))
                  for d in _dense_layers(m)),
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

_LENGTHS = {}   # token sequence handed to state_rows -> its length


def _gone_below(n, window, page_size):
    """Rows of a window layer below this position are released:
    ``page_floor(n - window)``."""
    return max(n - window, 0) // page_size * page_size


def state_rows(eng, req, n):
    """What the engine holds for ``req`` after its first ``n`` positions:
    ``k`` / ``v`` ``[layers, n, kv_heads x head_dim]`` in the cache's own
    type on the host, a full layer's through the request's page list, a
    window layer's through its window page list with zeros at the released
    positions (module doc)."""
    ps = eng.config.page_size
    gone = _gone_below(n, eng.cfg.attention_window, ps)
    if gone != req.wfirst * ps:
        raise RuntimeError(
            f"{req.rid}: window pages start at position {req.wfirst * ps}, "
            f"the rule says {gone} for {n} rows")
    lists = {"attention": (jnp.asarray(req.pages, jnp.int32), 0),
             "window": (jnp.asarray(req.wpages, jnp.int32), gone)}
    out = {"k": [], "v": []}
    for kind, cache in zip(eng.cfg.layer_kinds(), eng.caches):
        pages, at = lists[kind]
        for name, c in zip(("k", "v"), cache):
            rows = jnp.swapaxes(c[pages], 1, 2).reshape(
                -1, c.shape[1] * c.shape[3])[:n - at]
            out[name].append(np.asarray(
                jnp.pad(rows, ((at, 0), (0, 0)))))
    _LENGTHS[tuple(req.seq()[:n])] = (n, ps)
    return {name: np.stack(rows) for name, rows in out.items()}


def _length(seq):
    """(how many of a padded sequence's tokens the engine held, its page
    size) as :func:`state_rows` noted them, else None: a finished request,
    whose rows nobody compares."""
    for n, ps in sorted(set(_LENGTHS.values()), reverse=True):
        if _LENGTHS.get(tuple(seq[:n])) == (n, ps):
            return n, ps
    return None


NEAR_ROUTER = 2e-3   # router scores (sigmoid of a bf16-rounded input)


def reference_forward(seqs, config, seed, *, on_rows, precision, logit_rows):
    """The plain reference (``afmoe_reference.py``) over padded token
    sequences, on the benchmark's own weights regenerated from ``seed`` one
    layer at a time, given the same experts.  ``on_rows(s, unit, rows)`` is
    handed sequence s's rows of each layer as they are made, a sliding
    layer's with zeros where the engine has released its pages; returns the
    float32 logits at ``logit_rows[s]``.  Says how many router choices had
    the k-th and the next score within a bf16 rounding of each other."""
    ends = ends_params(seed, config)
    needs, cut, held = [], [], []
    for seq, rows in zip(seqs, logit_rows):
        # check.py pads with token 0 and does not say where a sequence ends;
        # the traffic draws ids from 1 up, so the last nonzero id is the end
        # of what the engine was fed, or a row before it
        real = len(seq) - int(np.argmax(np.asarray(seq)[::-1] != 0))
        need = max([real] + [r + 1 for r in rows])
        needs.append(need)
        cut.append(min(len(seq), -(-need // CUT_ROWS) * CUT_ROWS))
        held.append(_length(list(seq)))
    router = [0, 0]
    sliding = [t == "sliding_attention" for t in config["layer_types"]]

    def rows_as_held(si, li, rows):
        if sliding[li] and held[si] is not None:
            n, ps = held[si]
            gone = _gone_below(n, config["sliding_window"], ps)
            keep = (jnp.arange(rows["k"].shape[0]) >= gone)[:, None]
            rows = {kind: jnp.where(keep, r, 0.0)
                    for kind, r in rows.items()}
        on_rows(si, li, rows)

    def on_gap(si, li, gap):
        g = np.asarray(gap)[:needs[si]]
        router[0] += int((g < NEAR_ROUTER).sum())
        router[1] += g.size

    logits = reference.forward(
        [list(s[:c]) for s, c in zip(seqs, cut)], needs, config, ends,
        lambda li: layer_params(seed, config, li),
        first=config["local_experts_first"], on_rows=rows_as_held,
        precision=precision, logit_rows=logit_rows, on_gap=on_gap)
    print(f"[perfbench] reference ({precision}): {router[0]} of {router[1]} "
          f"router choices (token x layer) had the "
          f"{config['num_experts_per_tok']}th and the next score within "
          f"{NEAR_ROUTER}", flush=True)
    return logits


# ---------------------------------------------------------------------------
# Counts: the least bytes and operations of one step, from shapes and the
# step's facts (``ctx["steps"]``: what ``step()`` returned and what the
# harness noted beside it).  bf16 weights and rows: 2 bytes.  The attention
# facts are per layer of a kind (every layer of a kind does the same), so
# they are multiplied by the layers of that kind.
# ---------------------------------------------------------------------------


def _kind_layers(m, kind):
    return sum(t == kind
               for t in m["layer_types"][:m["num_hidden_layers"]])


def _expert_elems(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _dense_elems(m):
    """What ``attn.qkv`` + ``attn.out`` + ``mlp`` multiply by: every layer's
    five attention projections and the leading layers' MLPs."""
    sh = _shapes(m, True)
    attn = sum(int(np.prod(sh[n])) for n in
               ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"))
    mlp = 3 * m["hidden_size"] * m["intermediate_size"]
    return m["num_hidden_layers"] * attn + mlp * sum(_dense_layers(m))


def dense_flops(m, facts):
    """Least operations of the projections and the dense MLPs: 2 per
    multiply-add of their linear weights, for each of the step's real token
    rows."""
    return 2 * facts["tokens"] * _dense_elems(m)


def dense_bytes(m, facts):
    """Least HBM bytes of the same: those weights read once."""
    return 2 * _dense_elems(m)


def _pair_flops(m):
    """Per attended (query, key) pair: 2 per element of the key for the
    score and 2 per element of the value, in every query head."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def _row_bytes(m):
    """One cached position of one layer: its key and its value."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def window_attn_flops(m, facts):
    """Least operations of the window layers' attention (scope
    ``attn.window``): the pairs inside the window, in each sliding layer."""
    return facts["attn_pairs_window"] * _pair_flops(m) \
        * _kind_layers(m, "sliding_attention")


def window_attn_bytes(m, facts):
    """Least HBM bytes of the same: the cached rows the step's windows open
    on, once a sliding layer."""
    return facts["window_rows_read"] * _row_bytes(m) \
        * _kind_layers(m, "sliding_attention")


def full_attn_flops(m, facts):
    """Least operations of the full layers' attention (scope ``attn.full``):
    every causal pair, in each full layer."""
    return facts["attn_pairs_full"] * _pair_flops(m) \
        * _kind_layers(m, "full_attention")


def full_attn_bytes(m, facts):
    """Least HBM bytes of the same: the whole context of the step's
    requests, once a full layer."""
    return facts["full_rows_read"] * _row_bytes(m) \
        * _kind_layers(m, "full_attention")


def moe_expert_flops(m, facts):
    """Least operations of the grouped matmuls (scope ``moe.experts``): 2
    per multiply-add of an expert's three matrices, for each row routed to
    an expert held here."""
    return 2 * _expert_elems(m) * facts["moe_routes_local"]


def moe_expert_bytes(m, facts):
    """Least HBM bytes of the same: the weights of the (layer, expert)
    pairs that got a row, once."""
    return 2 * _expert_elems(m) * facts["moe_experts_hit"]
