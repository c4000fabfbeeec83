"""Closed-loop bookkeeping on a fake engine and a fake clock."""

import traffic
import loop
import metrics


class Req:
    def __init__(self, rid, prompt, n):
        self.rid, self.prompt, self.max_new_tokens = rid, prompt, n
        self.out_tokens, self.state = [], "waiting"
        self.hit_tokens = self.preemptions = self.kv_len = 0
        self.pages = []


class FakeEngine:
    """Every request gets its first token on the second step after it was
    submitted, then one token per step; each step takes one second."""

    def __init__(self, clock):
        self.clock, self.reqs, self.age = clock, [], {}

    def submit(self, r):
        if len(r.prompt) > 60:
            raise ValueError("too long")
        self.reqs.append(r)
        self.age[r.rid] = 0

    def step(self):
        self.clock.t += 1.0
        sampled = 0
        for r in list(self.reqs):
            self.age[r.rid] += 1
            r.state = "running"
            if self.age[r.rid] >= 2:
                r.out_tokens.append(7)
                sampled += 1
                if len(r.out_tokens) >= r.max_new_tokens:
                    r.state = "finished"
                    self.reqs.remove(r)
        return {"rung": 8, "tokens": max(sampled, 1), "sampled": sampled,
                "finished": 0}


class Clock:
    t = 100.0

    def __call__(self):
        return self.t


def test_closed_loop_bookkeeping():
    clock = Clock()
    spec = {"kind": "closed_loop", "clients": 3, "prompt_len": [8, 100],
            "output_len": [2, 6], "n_strata": 6}
    gen = traffic.make(spec, seed=1, vocab=512, max_seq_tokens=160)
    d = loop.Driver(FakeEngine(clock), gen,
                    lambda s: Req(s["rid"], s["prompt"], s["output_len"]),
                    vocab=512, clock=clock)
    assert d.fill(2) == 2                 # first tokens on the second step
    t_open, t_close, first = d.window(10.0)
    assert (t_open, t_close, first) == (102.0, 112.0, 2)
    recs = list(d.records.values())
    # a client's next request is submitted the moment its last is seen
    # finished: never more than `clients` requests alive
    assert len(d.live) <= 3
    for r in recs:
        if r.get("refused"):
            assert r["failed"] and r["t_end"] == r["t_submit"]
            continue
        if r["token_times"]:
            assert r["token_times"][0] - r["t_submit"] == 2.0   # TTFT
            gaps = [b - a for a, b in zip(r["token_times"],
                                          r["token_times"][1:])]
            assert all(g == 1.0 for g in gaps)
        if r["t_end"] is not None:
            assert len(r["tokens"]) == r["asked"] and not r["failed"]
    vals, n = metrics.end_to_end(recs, t_open, t_close)
    assert vals["itl_p95_ms"][0] == 1000.0
    assert vals["ttft_p50_ms"][0] == 2000.0
    assert n["attempted"] >= 3
    # the refused prompts (> 60 tokens) count as failed, and their client
    # still gets its next request
    assert n["failed"] == sum(bool(r.get("refused")) and
                              metrics.in_window(r["t_end"], t_open, t_close)
                              for r in recs)
    assert any(r.get("refused") for r in recs)
