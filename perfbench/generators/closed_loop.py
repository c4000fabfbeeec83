"""Closed loop: ``clients`` callers with zero think time, each sending its
next request the moment its last one is seen finished.

Parameters (``traffic/<name>.json``):

    clients             number of callers
    prompt_len          [lo, hi] tokens, log-uniform
    output_len          [lo, hi] tokens, log-uniform
    n_strata            how many equally spaced quantiles of each range
    shared_prefix_len   tokens every prompt opens with (0: nothing shared);
                        ``prompt_len`` is then the unique suffix
    shared_prefix_count how many distinct such prefixes (dealt round-robin)
    order_seed          optional: what orders the lengths (see below)

Lengths are STRATIFIED, not sampled: the ``n_strata`` quantiles of each
range are paired by a seeded shuffle into one deck, and each new request
takes the next card, cycling.  Every seed therefore sends the same
multiset of lengths in another order with other token ids, which is what
lets a tail over a few dozen requests repeat from seed to seed.

In a closed loop with zero think time the ORDER of the lengths is itself
work: which requests meet in one step's prefill budget decides who waits a
step.  (The engine's own scheduler driven through this loop with the device
step stubbed, 12 seeds, same multiset in seeded order: the median request
got its first token after one step under 6 seeds and after two under 5;
PERF.md, PR 23.)  So a mix that gives ``order_seed`` has its lengths, and
the first requests' shortened outputs, dealt in that one order under every
``--seed``; the seed then changes what it should, the token ids (and, in
the harness, the weights), and every run steps through the same schedule.
Without ``order_seed`` the order follows the seed.

The first request of each client gets an output length drawn uniformly
from 1 to the one it was dealt, so the window opens on lanes at mixed
phases and not on one synchronised burst.
"""

from __future__ import annotations

import math

import numpy as np


def strata(lo, hi, n):
    """``n`` equally spaced quantiles of the log-uniform range [lo, hi]."""
    lo, hi = int(lo), int(hi)
    if not 1 <= lo <= hi:
        raise ValueError(f"bad range [{lo}, {hi}]")
    return [int(round(math.exp(math.log(lo) + (i + 0.5) / n
                               * (math.log(hi) - math.log(lo)))))
            for i in range(n)]


class Generator:
    def __init__(self, spec, *, seed, vocab, max_seq_tokens):
        self.clients = int(spec["clients"])
        n = int(spec["n_strata"])
        self.rng = np.random.default_rng([int(seed), 0x7AFF1C])  # token ids
        self.order = np.random.default_rng(
            [int(spec.get("order_seed", seed)), 0x0DE4])       # lengths
        self.vocab = int(vocab)
        prompts = strata(*spec["prompt_len"], n)
        outputs = strata(*spec["output_len"], n)
        self.order.shuffle(prompts)
        self.order.shuffle(outputs)
        self.deck = list(zip(prompts, outputs))
        self.prefix_len = int(spec.get("shared_prefix_len", 0))
        count = int(spec.get("shared_prefix_count", 1))
        self.prefixes = [self._tokens(self.prefix_len) for _ in range(count)] \
            if self.prefix_len else []
        longest = self.prefix_len + max(prompts) + max(outputs)
        if longest > max_seq_tokens:
            raise ValueError(
                f"traffic reaches {longest} tokens, the engine holds "
                f"{max_seq_tokens} per request: no operation may fail")
        self.dealt = 0
        self.owner = {}  # rid -> client

    def _tokens(self, n):
        return self.rng.integers(1, self.vocab, n).tolist()

    def _deal(self, client, first):
        plen, olen = self.deck[self.dealt % len(self.deck)]
        if first:
            olen = int(self.order.integers(1, olen + 1))
        prompt = self._tokens(plen)
        if self.prefixes:
            prompt = self.prefixes[self.dealt % len(self.prefixes)] + prompt
        rid = f"c{client}.{self.dealt}"
        self.dealt += 1
        self.owner[rid] = client
        return {"rid": rid, "client": client, "prompt": prompt,
                "output_len": olen}

    def due(self, now, finished):
        """Requests to submit now.  ``finished`` is None on the first call
        (every client sends its first request) and afterwards the ids seen
        finished since the last call (their clients send the next)."""
        if finished is None:
            return [self._deal(c, True) for c in range(self.clients)]
        return [self._deal(self.owner.pop(rid), False) for rid in finished]
