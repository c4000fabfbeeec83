"""Closed loop for a mix that only a long-context engine holds: the callers,
strata, deck, order and first-request rule of ``closed_loop.py``, which this
generator IS but for where it refuses a mix that does not fit.

``closed_loop`` refuses at construction a mix whose longest request passes
the engine's ``max_seq_tokens``, and ``tests/test_traffic.py`` builds every
committed mix against an engine of 2,048 tokens; prompts of 16k are no fault
of the mix there.  Whether a mix fits is a property of the cell (the mix and
the configuration's engine), so here the same refusal, with the same words,
comes when the callers send their first requests (``due(now, None)``: the
harness's first call, before any step), where the engine is the cell's.
"""

from __future__ import annotations

from generators import closed_loop


class Generator(closed_loop.Generator):
    def __init__(self, spec, *, seed, vocab, max_seq_tokens):
        super().__init__(spec, seed=seed, vocab=vocab,
                         max_seq_tokens=float("inf"))
        self.max_seq_tokens = int(max_seq_tokens)

    def due(self, now, finished):
        if finished is None:
            longest = self.prefix_len + max(p + o for p, o in self.deck)
            if longest > self.max_seq_tokens:
                raise ValueError(
                    f"traffic reaches {longest} tokens, the engine holds "
                    f"{self.max_seq_tokens} per request: no operation may "
                    "fail")
        return super().due(now, finished)
