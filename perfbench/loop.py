"""The one loop around the engine's ``step()``:

    submit due requests -> eng.step() -> stamp the clock ->
    for each request whose out_tokens grew, record the token time

It knows the engine only through ``submit(req)``, ``step() -> facts`` and
the request objects' ``out_tokens`` / ``state`` / ``kv_len`` / ``pages``,
so ``tests/test_loop.py`` drives it with a fake engine and a fake clock.
``step()`` ends with the sampled tokens on the host, so the stamp after it
needs no extra sync.
"""

from __future__ import annotations

import contextlib
import time


class Driver:
    def __init__(self, eng, gen, make_request, *, vocab,
                 clock=time.perf_counter, per_step=None, around_step=None):
        self.eng, self.gen, self.make_request = eng, gen, make_request
        self.vocab, self.clock = vocab, clock
        self.per_step = per_step        # traced runs: facts of live requests
        self.around_step = around_step  # traced runs: a context manager
        self.records = {}   # rid -> record (see metrics.py)
        self.live = {}      # rid -> the engine's request object
        self.steps = []     # one facts dict per engine step
        self.finished = None  # ids seen finished since the last submit
        self.last_stamp = clock()

    def submit_due(self):
        now = self.clock()
        for spec in self.gen.due(now, self.finished):
            rec = {"rid": spec["rid"], "client": spec["client"],
                   "prompt": spec["prompt"], "asked": spec["output_len"],
                   "t_submit": now, "token_times": [], "tokens": [],
                   "t_end": None, "failed": False, "hit_tokens": 0,
                   "preemptions": 0}
            self.records[spec["rid"]] = rec
            req = self.make_request(spec)
            try:
                self.eng.submit(req)
            except ValueError as e:   # refused: it failed, and it ended
                rec["failed"], rec["t_end"] = True, now
                rec["refused"] = str(e)
                continue
            self.live[spec["rid"]] = req
        self.finished = []

    def step(self):
        """One engine step and its bookkeeping.  Returns the step's facts."""
        self.submit_due()
        t0 = self.clock()
        with (self.around_step() if self.around_step
              else contextlib.nullcontext()):
            facts = dict(self.eng.step())
        now = self.clock()
        facts["t0"], facts["t1"] = t0, now
        if self.per_step is not None:
            facts.update(self.per_step(self.live.values()))
        for rid, req in list(self.live.items()):
            rec = self.records[rid]
            new = len(req.out_tokens) - len(rec["token_times"])
            if new > 0:
                rec["token_times"].extend([now] * new)
                rec["tokens"] = list(req.out_tokens)
            if req.state == "finished":
                rec["t_end"] = now
                rec["hit_tokens"] = req.hit_tokens
                rec["preemptions"] = req.preemptions
                rec["failed"] = (
                    len(req.out_tokens) != rec["asked"]
                    or not all(0 <= t < self.vocab for t in req.out_tokens))
                del self.live[rid]
                self.finished.append(rid)
        self.steps.append(facts)
        self.last_stamp = now
        return facts

    def fill(self, steps):
        """Set-up: submit every client's first request and run exactly
        ``steps`` engine steps (the traffic mix's ``fill_steps``).  The
        clients start together, and the loop needs that long to work off the
        synchronised start; a fixed count keeps the set-up's cost the same
        whatever the scheduler does with it.  Every client has to hold a
        first token by then."""
        self.submit_due()
        first = [self.records[rid] for rid in self.live]
        for _ in range(steps):
            self.step()
        late = [r["rid"] for r in first if not r["token_times"]]
        if late:
            raise RuntimeError(
                f"after {steps} fill steps {len(late)} client(s) still wait "
                f"for a first token ({late[:4]}): fill_steps is too short")
        return steps

    def window(self, seconds, hooks=()):
        """Measure for ``seconds``: the window opens at the stamp of the
        last step before it and closes at the stamp of the first step that
        ends at or after ``seconds``.  ``hooks`` are called with the
        elapsed time before each step (traced runs start and stop the
        profiler there).  Returns (t_open, t_close, first step index)."""
        t_open, first = self.last_stamp, len(self.steps)
        while True:
            for h in hooks:
                h(self.last_stamp - t_open)
            self.step()
            if self.last_stamp - t_open >= seconds:
                return t_open, self.last_stamp, first
