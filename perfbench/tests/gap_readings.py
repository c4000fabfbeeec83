"""From the gaps a run keeps (``run.py --keep-gaps``) to the readings a
family's token limits are set from, and the rule that sets them.

    python3 perfbench/tests/gap_readings.py <out.json> <on_record.json> \
        <kept gaps files...>

writes the committed readings (``data/afmoe_token_gaps.json``): per run the
statistics of its gaps (``check.TOKEN_STATS``), how many tokens lie over each
threshold, and its ``TAIL`` widest gaps with their labels; a control's runs
the same (``CONTROL_TAIL`` gaps) for the tokens drawn from its logits.
``table`` and ``derive`` are the criterion of ISSUE 47, step 4, as code, so that
``test_check.py`` re-derives each committed limit from the committed readings
and the next writer can hold a limit against its readings without a chip.
"""

from __future__ import annotations

import json
import math
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

import check  # noqa: E402

TAIL = 50
CONTROL_TAIL = 10
# what is committed of a tail's entries (``--keep-gaps`` keeps every column)
TAIL_COLUMNS = ("in_flight", "prompt_len", "index", "position", "gap", "rank")
OVER = (0.0, 0.1, 0.2, 0.3, 0.44, 0.6, 0.8, 1.0)
WIDEST = "token_below_topk"
# a candidate for the number that repeats: every statistic but the widest gap
CANDIDATES = tuple(n for n in check.TOKEN_STATS if n != WIDEST)


def tail(tokens, n=TAIL):
    """The ``n`` widest gaps of a token table, as columns, widest first."""
    order = np.argsort(-np.asarray(tokens["gap"]), kind="stable")[:n]
    out = {name: [tokens[name][i] for i in order] for name in TAIL_COLUMNS}
    out["gap"] = [round(float(x), 4) for x in out["gap"]]
    out["in_flight"] = [int(x) for x in out["in_flight"]]
    return out


def reading(tokens, n=TAIL):
    """What is committed of one token table."""
    g = np.asarray(tokens["gap"])
    return {"tokens": int(g.size), "stats": check.token_stats(g),
            "over": {f"{x:g}": int((g > x).sum()) for x in OVER},
            "first_tokens": int(sum(i == 0 for i in tokens["index"])),
            "in_flight_tokens": int(sum(map(bool, tokens["in_flight"]))),
            "tail": tail(tokens, n)}


def summarize(kept_runs, on_record):
    """The committed file from the kept gaps of runs (``--keep-gaps``; a run
    with controls gives its sound reading and each control's) and the
    readings of earlier PRs (``on_record``: ``top_k`` and widest gaps only,
    ``sound_widest`` and ``<control>_widest``)."""
    out = {"top_k": on_record["top_k"], "on_record": on_record,
           "sound": [], "controls": {}}
    for kept in sorted(kept_runs, key=lambda r: r["seed"]):
        if kept["top_k"] != out["top_k"]:
            raise ValueError(f"seed {kept['seed']}: top_k {kept['top_k']}")
        head = {"seed": kept["seed"], "workload": kept["workload"]}
        rows = {n: v for n, v in kept["numbers"].items()
                if n.endswith("_rel_rms")}
        out["sound"].append({**head, **rows, **reading(kept["tokens"])})
        for name, ctl in kept["controls"].items():
            own = {n: v for n, v in ctl["numbers"].items()
                   if not n.startswith("drawn.")}
            out["controls"].setdefault(name, []).append(
                {**head, **own, **reading(ctl["tokens"], CONTROL_TAIL)})
    return out


def two_digits(x):
    """Rounded to two significant digits, as a limit is written."""
    return float(f"{x:.2g}")


def middle(low, high):
    return math.sqrt(low * high)


def table(data, off, lower="int8"):
    """Per candidate: the sound runs' smallest and largest reading and their
    spread (largest / smallest; inf where a run reads 0), and the ratio of
    the smallest reading of each control (``off``: the mechanism switched
    off; ``lower``: the precision below) to the largest sound reading."""
    rows = {}
    if not (data["sound"] and off in data["controls"]
            and lower in data["controls"]):
        return rows     # no run kept its gaps: no candidate has a reading
    for name in CANDIDATES:
        sound = [r["stats"][name] for r in data["sound"]]
        lo, hi = min(sound), max(sound)
        row = {"sound_min": lo, "sound_max": hi,
               "spread": hi / lo if lo > 0 else math.inf}
        for ctl in (off, lower):
            least = min(r["stats"][name] for r in data["controls"][ctl])
            row[ctl] = least
            row[f"{ctl}_ratio"] = least / hi if hi > 0 else math.inf
        rows[name] = row
    return rows


def derive(data, off, lower="int8"):
    """{number: limit} by ISSUE 47's criterion.

    The widest gap is the gross-fault limit: the geometric middle of the
    largest sound reading on record (earlier PRs' and the committed runs')
    and the smallest reading of the mechanism-off control (its own k-th best
    token's gap, on record and committed), refused unless it leaves 2x over
    every sound reading and 1.5x under every control reading.

    One number that repeats is held beside it if and only if the readings
    earn it: of the candidates whose sound spread is under 2x, the one with
    the largest ratio of the control's smallest reading (tokens drawn from
    its logits as the traffic samples) to the largest sound reading, at the
    geometric middle, if that leaves 1.5x on both sides."""
    sound = [r["stats"][WIDEST] for r in data["sound"]] \
        + list(data["on_record"]["sound_widest"])
    ctl = [r[WIDEST] for r in data["controls"].get(off, [])] \
        + list(data["on_record"][f"{off}_widest"])
    limit = two_digits(middle(max(sound), min(ctl)))
    if limit < 2 * max(sound) or 1.5 * limit > min(ctl):
        raise ValueError(f"no gross-fault limit between {max(sound)} and "
                         f"{min(ctl)}")
    out = {WIDEST: limit}
    rows = table(data, off, lower)
    steady = {n: r for n, r in rows.items() if r["spread"] < 2}
    if steady:
        name = max(steady, key=lambda n: steady[n][f"{off}_ratio"])
        row = steady[name]
        if row[f"{off}_ratio"] >= 1.5 ** 2:
            out[name] = two_digits(middle(row["sound_max"], row[off]))
    return out


def main(argv):
    out, on_record, files = argv[0], argv[1], argv[2:]
    on_record = json.loads(pathlib.Path(on_record).read_text())
    kept = [json.loads(pathlib.Path(f).read_text()) for f in files]
    data = summarize(kept, on_record)
    pathlib.Path(out).write_text(json.dumps(data, separators=(",", ":")))
    off = next(n[:-len("_widest")] for n in on_record
               if n.endswith("_widest") and n not in ("sound_widest",
                                                      "int8_widest"))
    print(json.dumps({"limits": derive(data, off),
                      "candidates": table(data, off)}, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
