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
  such gap.  It is a maximum over a few hundred draws and swings from seed to
  seed, so the check also says what it saw (printed, held by nothing): per
  token the request, whether it was in flight or finished, the prompt's
  length, the token's index in the answer, its position, its RANK in the
  reference's logits and the reference's best-minus-k-th there
  (:func:`token_table`), the five widest, and the statistics of the gaps
  over the run (``TOKEN_STATS``).  A family holds one of those statistics by
  naming it in its ``LIMITS``.
* exact counts, limit 0: a row that is not finite, a token outside the
  vocabulary, a trace or a compile inside the window.

This file keeps what is common to every block: padding to buckets, the
relative-RMS rule, ``token_below_topk`` and the other statistics of the
tokens' gaps, the exact counts, the controls' plumbing and the verdict.  The
block's reference, the number of units held, the limits with the readings
they were set from, and any further number of its own (``more_numbers``) are
the family's (``families/<family>.py``).
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


def _share_over(x):
    return lambda g: float(np.mean(g > x))


def _percentile(q):
    """Nearest rank, and 0 where that token lies inside the top k."""
    return lambda g: max(float(np.percentile(g, q, method="inverted_cdf")),
                         0.0)


# Statistics of one run's gaps (``g``: per served token, the reference's k-th
# best logit minus the token's; at or below 0 inside the top k).  Every one
# is printed or kept in every run; a family's ``LIMITS`` names those it
# holds.  ``token_below_topk`` is the widest gap.
TOKEN_STATS = {
    "token_below_topk": lambda g: float(np.max(g)),
    "token_gap_p99": _percentile(99),
    "token_gap_p90": _percentile(90),
    "token_outside_topk_share": _share_over(0.0),
    "token_over_0p1_share": _share_over(0.1),
    "token_over_0p2_share": _share_over(0.2),
    "token_gap_mean_positive": lambda g: float(np.mean(np.maximum(g, 0.0))),
}
WIDEST_SAID = 5
TOKEN_COLUMNS = ("request", "in_flight", "prompt_len", "index", "position",
                 "gap", "rank", "spread")


def token_stats(gaps):
    """{statistic: value} of one run's gaps; all 0 for a run with no token."""
    g = np.asarray(gaps, np.float64)
    return {name: (f(g) if g.size else 0.0)
            for name, f in TOKEN_STATS.items()}


def token_table(samples, logits, k, tokens=None):
    """One entry a served token of the samples (or of ``tokens``, a list a
    sample, in the served tokens' places), as the columns ``TOKEN_COLUMNS``:
    ``request``, ``in_flight`` (its cache rows were compared too),
    ``prompt_len``, ``index`` in the answer (0: the first token), ``position``
    (of the row whose logits produced it), ``gap`` (the reference's k-th best
    logit minus the token's), ``rank`` of the token in the reference's
    logits (1: its best; ``k + 1``: the first outside) and ``spread``, the
    reference's best minus its k-th best there."""
    cols = {name: [] for name in TOKEN_COLUMNS}
    for lg, s, toks in zip(logits, samples,
                           tokens or [s["served"] for s in samples]):
        if not len(toks):
            continue
        top = jax.lax.top_k(lg, k)[0]
        got = jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], 1)[:, 0]
        n = len(toks)
        cols["request"] += [s.get("rid", "")] * n
        cols["in_flight"] += [s["prog_rows"] is not None] * n
        cols["prompt_len"] += [int(s["logit_rows"][0]) + 1] * n
        cols["index"] += list(range(n))
        cols["position"] += [int(r) for r in s["logit_rows"]]
        cols["gap"] += np.asarray(top[:, -1] - got, np.float64).tolist()
        cols["rank"] += (1 + np.asarray(jnp.sum(lg > got[:, None], 1))
                         ).tolist()
        cols["spread"] += np.asarray(top[:, 0] - top[:, -1],
                                     np.float64).tolist()
    return cols


def say_tokens(table, k, say, whose="served", tag="check"):
    """The two lines that tell what the token rule saw."""
    g = np.asarray(table["gap"])
    if not g.size:
        return
    p50, p90, p99 = (float(np.percentile(g, q, method="inverted_cdf"))
                     for q in (50, 90, 99))
    say(f"{tag}: gaps of the {g.size} {whose} tokens below the top-{k} "
        f"cutoff: {100 * np.mean(g > 0):.2f}% outside the top {k} at all; "
        f"percentiles 50 / 90 / 99: {p50:.4f} / {p90:.4f} / {p99:.4f}")
    widest = np.argsort(-g, kind="stable")[:WIDEST_SAID]
    say(f"{tag}: widest gaps (request, in flight or finished, prompt length, "
        "index in the answer, position, rank in the reference, gap, the "
        "reference's best minus its k-th): " + "; ".join(
            f"{table['request'][i]}, "
            f"{'in flight' if table['in_flight'][i] else 'finished'}, "
            f"{table['prompt_len'][i]}, {table['index'][i]}, "
            f"{table['position'][i]}, {table['rank'][i]}, {g[i]:.4f}, "
            f"{table['spread'][i]:.4f}" for i in widest))


def draw_tokens(logits, sampling, rng):
    """One token a row of ``logits`` ``[n, vocab]`` as the traffic's sampling
    draws it (temperature, softmax, top k, top p, in the engine's order),
    with the host generator ``rng``."""
    vocab = logits.shape[-1]
    k = int(sampling.get("top_k", 0)) or vocab
    vals, ids = jax.lax.top_k(logits, min(k, vocab))
    vals, ids = np.asarray(vals, np.float64), np.asarray(ids)
    temp = float(sampling.get("temperature", 1.0))
    if temp <= 0.0 or k == 1:
        return ids[:, 0].tolist()
    p = np.exp((vals - vals[:, :1]) / temp)
    p /= p.sum(-1, keepdims=True)
    before = np.cumsum(p, -1) - p          # sorted best first
    p = np.where(before < float(sampling.get("top_p", 1.0)), p, 0.0)
    cum = np.cumsum(p / p.sum(-1, keepdims=True), -1)
    pick = (cum < rng.random((len(p), 1))).sum(-1)
    return ids[np.arange(len(p)), np.minimum(pick, p.shape[1] - 1)].tolist()


def controls_of(family, asked):
    """The names of the controls asked for: nothing or ``"0"`` is none,
    ``True`` or ``"1"`` the family's first (the precision below the
    configuration's), a string names one or, with commas, several of
    ``family.CONTROLS``."""
    if not asked or asked == "0":
        return ()
    if asked is True or asked == "1":
        return family.CONTROLS[:1]
    names = tuple(n for n in str(asked).split(",") if n)
    unknown = [n for n in names if n not in family.CONTROLS]
    if unknown:
        raise ValueError(f"no control {unknown}; the family's controls are "
                         f"{list(family.CONTROLS)}")
    return names


def compare(samples, family, m, seed, *, top_k, control=False, sampling=None,
            kept=None, say=print):
    """The numbers of one run.  Each sample is a dict:

        tokens      the sequence the engine was fed: prompt + served tokens
                    but the last (teacher forcing)
        prog_rows   None, or the engine's cache rows for it as the family's
                    ``state_rows`` gives them, {kind: [units, n, ...]} (any
                    float type, host or device)
        logit_rows  positions whose logits produced a served token
        served      the tokens served at those positions

    ``control`` asks for controls (:func:`controls_of`); ``sampling`` is the
    traffic's (a control's tokens are drawn under it; without it, ``top_k``
    alone); ``kept``, a dict, is filled with every sampled token's entry
    (``tokens``), the statistics (``stats``) and each control's.

    Returns ({number: value}, {control: {number: value}} or None)."""
    held = family.UNITS_HELD
    k = max(int(top_k), 1)
    names = controls_of(family, control)
    per_unit = {}
    keep = {}  # (sample, unit) -> reference rows, for the controls
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
        if names and unit < held:
            keep[si, unit] = ref

    logits = run_reference(samples, family, m, seed, precision="float32",
                           on_rows=on_ref)
    table = token_table(samples, logits, k)
    stats = token_stats(table["gap"])
    gap = stats["token_below_topk"]
    with_rows = [s["prog_rows"] for s in samples
                 if s["prog_rows"] is not None]
    say(f"check: cache rows ({', '.join(with_rows[0]) if with_rows else '-'})"
        f" of {len(with_rows)} request(s), "
        f"{sum(_rows_len(r) for r in with_rows)} positions; relative RMS "
        f"deviation per unit "
        f"{[round(per_unit[u], 5) for u in sorted(per_unit)]}")
    say(f"check: {len(table['gap'])} served tokens of "
        f"{len(set(table['request']))} request(s); widest gap "
        f"below the reference's top-{k} cutoff {gap:.4f} logits")
    say_tokens(table, k, say)
    numbers = {family.ROWS_NUMBER: max((v for u, v in per_unit.items()
                                        if u < held), default=0.0),
               "token_below_topk": gap}
    # the further statistics of the gaps that the family holds
    numbers.update({n: stats[n] for n in family.LIMITS
                    if n in TOKEN_STATS and n not in numbers})
    numbers["rows_not_finite"] = bad_rows
    if hasattr(family, "more_numbers"):
        numbers.update(family.more_numbers(samples, m, seed, logits))
    if kept is not None:
        kept.update(top_k=k, tokens=table, stats=stats, controls={})
    ctl = {} if names else None
    for name in names:
        ctl_unit = {}

        def on_ctl(si, unit, rows):
            if (si, unit) in keep:
                ref = keep[si, unit]
                got = {kind: rows[kind][:len(r)] for kind, r in ref.items()}
                ctl_unit[unit] = max(ctl_unit.get(unit, 0.0), _rel(got, ref))

        clogits = run_reference(samples, family, m, seed, precision=name,
                                on_rows=on_ctl)
        # today's rule for the widest gap: the control's OWN k-th best token
        # at every position, the worst of its top k
        own = [np.asarray(_below_topk(lg, jax.lax.top_k(cl, k)[1][:, -1], k))
               for lg, cl, s in zip(logits, clogits, samples)
               if len(s["served"])]
        # and as a served run would read: one token a position drawn from the
        # control's logits under the traffic's sampling, from the seed
        rng = np.random.default_rng([int(seed), 0xC071])
        drawn = [draw_tokens(cl, sampling or {"top_k": k}, rng)
                 if len(s["served"]) else []
                 for cl, s in zip(clogits, samples)]
        dtable = token_table(samples, logits, k, tokens=drawn)
        dstats = token_stats(dtable["gap"])
        ctl[name] = {
            family.ROWS_NUMBER: max(ctl_unit.values(), default=0.0),
            "token_below_topk": float(np.max(np.concatenate(own)))
            if own else 0.0,
            **{f"drawn.{n}": v for n, v in dstats.items()}}
        say(f"control (reference in {name}): relative RMS deviation per unit "
            f"{[round(ctl_unit[u], 5) for u in sorted(ctl_unit)]}; widest "
            f"gap of its own top-{k} token "
            f"{ctl[name]['token_below_topk']:.4f}")
        say_tokens(dtable, k, say, whose=f"drawn ({name})", tag="control")
        if kept is not None:
            kept["controls"][name] = {
                "numbers": ctl[name], "tokens": dtable,
                "own_kth_gap": np.concatenate(own).tolist() if own else []}
    return numbers, ctl


def control_verdict(ctl, limit_of, say=print):
    """A control's numbers beside the limits a run is held to: the rows
    number, ``token_below_topk`` by its own rule, and the statistics of its
    drawn tokens that the family holds.  Returns {control: [numbers it
    fails]}: a control has to fail one."""
    failed = {}
    for name, numbers in ctl.items():
        failed[name] = []
        for n, value in numbers.items():
            limit = limit_of.get(n.removeprefix("drawn."))
            if limit is None or n == "drawn.token_below_topk":
                continue
            good = np.isfinite(value) and value <= limit
            if not good:
                failed[name].append(n)
            say(f"control {name} compared {n}: {value:.6g} (limit {limit:g}) "
                f"{'inside' if good else 'NOT OK'}")
        say(f"control {name}: would read correct " + (
            "false, by " + ", ".join(failed[name]) if failed[name]
            else "true: NOT TOLD from the program"))
    return failed


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
