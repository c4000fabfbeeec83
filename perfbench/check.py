"""The comparison that decides ``correct``.

What is compared is what the timed window itself left behind:

* ``kv_rel_rms`` - the K/V rows that the window's steps wrote into the
  engine's paged cache, for requests still in flight when the window closed
  (read out through their page lists by the harness), against the rows the
  plain float32 reference (``reference.py``) computes for the same token
  sequence.  Rows of layer l depend on every kernel of the layers before it
  (prefill launches, decode launch, cascade merge, norm, MLP), so the first
  ``CHECK_LAYERS`` layers cross them all; deeper rows add only the
  amplification of rounding by a random-weight network, and are printed but
  not held to the limit.  The number is the worst, over the sampled requests
  and those layers, of ``||rows - reference|| / ||reference||`` (Frobenius,
  K and V together).
* ``token_below_topk`` - every token the window served for the sampled
  requests (in flight and finished), against the reference's logits at the
  position that produced it: how far the served token's logit lies below the
  reference's k-th best, k being the traffic's ``top_k`` (k = 1 is the
  greedy rule: the gap below the reference's best).  A sampler, a head or a
  last layer that is broken serves tokens far outside the reference's top k;
  rounding moves a token by a fraction of a logit.  The number is the widest
  such gap.
* exact counts, limit 0: a row that is not finite, a token outside the
  vocabulary, a trace or a compile inside the window.

The limits and the readings they were set from are below, beside the check.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights

CHECK_LAYERS = 4

# Limits.  Each stands between the largest reading of sound runs and the
# smallest of the control (the reference computed in int8, the step below
# the configurations' bf16, put in the program's place), both read on the
# chip at the cells' own sizes; PERF.md section 2 lists every reading.
#
# kv_rel_rms: sound runs read 0.0168-0.0172 (7B, both cells) and
# 0.0188-0.0191 (Nemo) over 13 and 12 seeds (bf16 storage and bf16 activations
# against float32: 0.0026 at layer 0, growing by attention and MLP rounding to
# layer 3); the int8 control reads 0.0941-0.0946 (7B) and 0.1035-0.1038 (Nemo)
# on four seeds each (0.0126 at layer 0).  The limit is near the geometric
# middle: 2.1 times the largest sound reading, and the smallest control
# reading is 2.4 times it.
KV_REL_RMS_LIMIT = 0.04
# token_below_topk: held against the fault it is there to catch, a token
# altered or drawn outside the reference's top k (a broken sampler, head or
# last layer reads several logits: the logits' spread is ~1.3, and the top 40
# of 32,768 start ~3 spreads up).  Sound runs read 0.00-0.144 logits (bf16
# rounding of 16 layers moves a logit by a few hundredths, and a served token
# is inside the program's own top 40; a widest gap swings by its nature); the
# limit is about three times their largest.  The int8 control reads 0.74-1.08,
# so it fails this number too.
TOKEN_BELOW_TOPK_LIMIT = 0.4

LIMITS = {"kv_rel_rms": KV_REL_RMS_LIMIT,
          "token_below_topk": TOKEN_BELOW_TOPK_LIMIT,
          "rows_not_finite": 0, "tokens_outside_vocab": 0,
          "traces_in_window": 0, "compiles_in_window": 0}


def bucket(n, floor=32):
    """Sequences are padded to a power of two so that the reference
    compiles a handful of shapes, not one per request."""
    b = floor
    while b < n:
        b *= 2
    return b


def _pad(tokens):
    return list(tokens) + [0] * (bucket(len(tokens)) - len(tokens))


def _rel(a, ref):
    return float(jnp.sqrt(jnp.sum((a - ref) ** 2) / jnp.sum(ref ** 2)))


def run_reference(samples, m, seed, *, precision, on_rows):
    """One pass of the reference over the samples' sequences.  Returns the
    logits at each sample's ``logit_rows``."""
    ends = weights.ends_params(seed, m)
    out = reference.forward(
        [_pad(s["tokens"]) for s in samples], m, ends["embed"],
        lambda li: weights.layer_params(seed, m, li),
        layers=m["num_hidden_layers"], on_rows=on_rows, precision=precision,
        head=(ends["final_norm"], ends["lm_head"]),
        logit_rows=[s["logit_rows"] for s in samples])
    return out


def _below_topk(logits, tokens, k):
    """Per position: reference's k-th best logit minus the logit of
    ``tokens`` (negative or zero inside the top k)."""
    kth = jax.lax.top_k(logits, k)[0][:, -1]
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], 1)[:, 0]
    return kth - got


def compare(samples, m, seed, *, top_k, control=False, say=print):
    """The numbers of one run.  Each sample is a dict:

        tokens      the sequence the engine was fed: prompt + served tokens
                    but the last (teacher forcing)
        prog_rows   None, or the engine's cache rows for it,
                    [layers, 2, n, Hkv, D] (any float type, host or device)
        logit_rows  positions whose logits produced a served token
        served      the tokens served at those positions

    Returns ({number: value}, {control number: value} or None)."""
    layers = m["num_hidden_layers"]
    k = max(int(top_k), 1)
    per_layer = np.zeros(layers)
    keep = {}  # (sample, layer) -> reference rows, for the control
    bad_rows = 0

    def on_ref(si, li, kr, vr):
        nonlocal bad_rows
        s = samples[si]
        if s["prog_rows"] is None:
            return
        n = s["prog_rows"].shape[2]
        ref = jnp.stack([kr[:n], vr[:n]])
        got = jnp.asarray(s["prog_rows"][li]).astype(jnp.float32)
        bad_rows += int(jnp.sum(~jnp.isfinite(got)))
        per_layer[li] = max(per_layer[li], _rel(got, ref))
        if control and li < CHECK_LAYERS:
            keep[si, li] = ref

    logits = run_reference(samples, m, seed, precision="float32",
                           on_rows=on_ref)
    gaps = [np.asarray(_below_topk(lg, s["served"], k))
            for lg, s in zip(logits, samples) if len(s["served"])]
    gap = float(np.max(np.concatenate(gaps))) if gaps else 0.0
    n_tok = int(sum(len(g) for g in gaps))
    with_rows = [s["prog_rows"].shape[2] for s in samples
                 if s["prog_rows"] is not None]
    say(f"check: K/V rows of {len(with_rows)} request(s), {sum(with_rows)} "
        f"positions; relative RMS deviation per layer "
        f"{[round(float(x), 5) for x in per_layer]}")
    say(f"check: {n_tok} served tokens of {len(gaps)} request(s); widest gap "
        f"below the reference's top-{k} cutoff {gap:.4f} logits")
    numbers = {"kv_rel_rms": float(per_layer[:CHECK_LAYERS].max()),
               "token_below_topk": gap, "rows_not_finite": bad_rows}
    ctl = None
    if control:
        ctl_layer = np.zeros(CHECK_LAYERS)

        def on_ctl(si, li, kr, vr):
            if (si, li) in keep:
                ref = keep[si, li]
                n = ref.shape[1]
                ctl_layer[li] = max(ctl_layer[li],
                                    _rel(jnp.stack([kr[:n], vr[:n]]), ref))

        clogits = run_reference(samples, m, seed, precision="int8",
                                on_rows=on_ctl)
        cg = []
        for lg, cl, s in zip(logits, clogits, samples):
            if len(s["served"]):
                kth_tok = jax.lax.top_k(cl, k)[1][:, -1]
                cg.append(np.asarray(_below_topk(lg, kth_tok, k)))
        ctl = {"kv_rel_rms": float(ctl_layer.max()),
               "token_below_topk": float(np.max(np.concatenate(cg)))
               if cg else 0.0}
        say(f"control (reference in int8 weights): relative RMS deviation "
            f"per layer {[round(float(x), 5) for x in ctl_layer]}; widest "
            f"gap of its own top-{k} token {ctl['token_below_topk']:.4f}")
    return numbers, ctl


def verdict(numbers, say=print):
    """True when every number is within its limit; prints each beside it."""
    ok = True
    for name, value in numbers.items():
        limit = LIMITS[name]
        good = np.isfinite(value) and value <= limit
        ok &= bool(good)
        say(f"compared {name}: {value:.6g} (limit {limit:g}) "
            f"{'ok' if good else 'NOT OK'}")
    return ok
