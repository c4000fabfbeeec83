"""The comparison that decides ``correct``.

What is compared is what the timed window itself left behind:

* the rows number (the family names it: ``kv_rel_rms`` for K/V rows) - the
  rows that the window's steps wrote into the engine's cache, for requests
  still in flight when the window closed (read out by the family's
  ``state_rows``), against the rows the family's plain float32 reference
  computes for the same token sequence.  Rows come per comparable unit (a
  layer, or one of a layer's attentions) as named kinds (``k`` and ``v``, or
  a latent row), and the family says how many leading units are held to the
  limit: deeper ones are printed, not held.  The number is the worst, over
  the sampled requests and those units, of ``||rows - reference|| /
  ||reference||`` (Frobenius, all kinds of a unit together).
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

This file keeps what is common to every block: padding to buckets, the
relative-RMS rule, ``token_below_topk``, the exact counts, the control's
plumbing and the verdict.  The block's reference, the number of units held,
the limits with the readings they were set from, and any further number of
its own (``more_numbers``) are the family's (``families/<family>.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Exact comparisons, whatever the block: the limit is 0.
EXACT = ("rows_not_finite", "tokens_outside_vocab", "traces_in_window",
         "compiles_in_window")


def limits(family):
    """{number: limit}: the family's, and 0 for the exact counts."""
    return {**family.LIMITS, **dict.fromkeys(EXACT, 0)}


def bucket(n, floor=32):
    """Sequences are padded to a power of two so that the reference
    compiles a handful of shapes, not one per request."""
    b = floor
    while b < n:
        b *= 2
    return b


def _pad(tokens):
    return list(tokens) + [0] * (bucket(len(tokens)) - len(tokens))


def _rel(got, ref):
    """``||got - ref|| / ||ref||`` over all the kinds of one unit's rows."""
    num = sum(jnp.sum((got[kind] - ref[kind]) ** 2) for kind in ref)
    return float(jnp.sqrt(num / sum(jnp.sum(r ** 2) for r in ref.values())))


def run_reference(samples, family, m, seed, *, precision, on_rows):
    """One pass of the family's reference over the samples' sequences.
    Returns the logits at each sample's ``logit_rows``."""
    return family.reference_forward(
        [_pad(s["tokens"]) for s in samples], m, seed, on_rows=on_rows,
        precision=precision, logit_rows=[s["logit_rows"] for s in samples])


def _below_topk(logits, tokens, k):
    """Per position: reference's k-th best logit minus the logit of
    ``tokens`` (negative or zero inside the top k)."""
    kth = jax.lax.top_k(logits, k)[0][:, -1]
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], 1)[:, 0]
    return kth - got


def _rows_len(prog_rows):
    return next(iter(prog_rows.values())).shape[1]


def compare(samples, family, m, seed, *, top_k, control=False, say=print):
    """The numbers of one run.  Each sample is a dict:

        tokens      the sequence the engine was fed: prompt + served tokens
                    but the last (teacher forcing)
        prog_rows   None, or the engine's cache rows for it as the family's
                    ``state_rows`` gives them, {kind: [units, n, ...]} (any
                    float type, host or device)
        logit_rows  positions whose logits produced a served token
        served      the tokens served at those positions

    Returns ({number: value}, {control number: value} or None)."""
    held = family.UNITS_HELD
    k = max(int(top_k), 1)
    per_unit = {}
    keep = {}  # (sample, unit) -> reference rows, for the control
    bad_rows = 0

    def on_ref(si, unit, rows):
        nonlocal bad_rows
        s = samples[si]
        if s["prog_rows"] is None:
            return
        n = _rows_len(s["prog_rows"])
        ref = {kind: r[:n] for kind, r in rows.items()}
        got = {kind: jnp.asarray(s["prog_rows"][kind][unit])
               .astype(jnp.float32) for kind in ref}
        bad_rows += sum(int(jnp.sum(~jnp.isfinite(g))) for g in got.values())
        per_unit[unit] = max(per_unit.get(unit, 0.0), _rel(got, ref))
        if control and unit < held:
            keep[si, unit] = ref

    logits = run_reference(samples, family, m, seed, precision="float32",
                           on_rows=on_ref)
    gaps = [np.asarray(_below_topk(lg, s["served"], k))
            for lg, s in zip(logits, samples) if len(s["served"])]
    gap = float(np.max(np.concatenate(gaps))) if gaps else 0.0
    n_tok = int(sum(len(g) for g in gaps))
    with_rows = [s["prog_rows"] for s in samples
                 if s["prog_rows"] is not None]
    say(f"check: cache rows ({', '.join(with_rows[0]) if with_rows else '-'})"
        f" of {len(with_rows)} request(s), "
        f"{sum(_rows_len(r) for r in with_rows)} positions; relative RMS "
        f"deviation per unit "
        f"{[round(per_unit[u], 5) for u in sorted(per_unit)]}")
    say(f"check: {n_tok} served tokens of {len(gaps)} request(s); widest gap "
        f"below the reference's top-{k} cutoff {gap:.4f} logits")
    numbers = {family.ROWS_NUMBER: max((v for u, v in per_unit.items()
                                        if u < held), default=0.0),
               "token_below_topk": gap, "rows_not_finite": bad_rows}
    if hasattr(family, "more_numbers"):
        numbers.update(family.more_numbers(samples, m, seed, logits))
    ctl = None
    if control:
        ctl_unit = {}

        def on_ctl(si, unit, rows):
            if (si, unit) in keep:
                ref = keep[si, unit]
                got = {kind: rows[kind][:len(r)] for kind, r in ref.items()}
                ctl_unit[unit] = max(ctl_unit.get(unit, 0.0), _rel(got, ref))

        clogits = run_reference(samples, family, m, seed,
                                precision=family.CONTROL_PRECISION,
                                on_rows=on_ctl)
        cg = []
        for lg, cl, s in zip(logits, clogits, samples):
            if len(s["served"]):
                kth_tok = jax.lax.top_k(cl, k)[1][:, -1]
                cg.append(np.asarray(_below_topk(lg, kth_tok, k)))
        ctl = {family.ROWS_NUMBER: max(ctl_unit.values(), default=0.0),
               "token_below_topk": float(np.max(np.concatenate(cg)))
               if cg else 0.0}
        say(f"control (reference in {family.CONTROL_PRECISION}): relative "
            f"RMS deviation per unit "
            f"{[round(ctl_unit[u], 5) for u in sorted(ctl_unit)]}; widest "
            f"gap of its own top-{k} token {ctl['token_below_topk']:.4f}")
    return numbers, ctl


def verdict(numbers, limit_of, say=print):
    """True when every number is within its limit (``limit_of``:
    :func:`limits` of the family); prints each beside it."""
    ok = True
    for name, value in numbers.items():
        limit = limit_of[name]
        good = np.isfinite(value) and value <= limit
        ok &= bool(good)
        say(f"compared {name}: {value:.6g} (limit {limit:g}) "
            f"{'ok' if good else 'NOT OK'}")
    return ok
