"""From the profiler's ``.xplane.pb`` to device busy/idle, per-operation
time and the longest idle gaps.

``load`` reads the file with nothing but JAX (``jax.profiler.ProfileData``)
into plain lists, ``reduce`` is arithmetic over those lists (tested on a
small recording, ``tests/data/``), and ``Tracer`` is the harness's handle on
the profiler: it wraps ``steps`` steady steps in the middle of the window,
each inside a ``perfbench.step`` annotation written from this file, so that
an idle gap is attributed to "inside step()" or to the harness's own loop,
and it notes WHICH steps those were, so that a reader can put the facts of
the same steps (tokens, lanes, context) beside their device time.

What a v5e trace looks like (PR 23's chip runs): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed HLO
operation, fusions and ``while`` bodies nested inside their parents, and a
plane ``/host:CPU`` with one line per thread.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil

STEP = "perfbench.step"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def op_name(event_name):
    """A device event is named by its whole HLO instruction
    (``%fused_paged_prefill.16 = (bf16[...]) custom-call(...)``): keep the
    instruction's name, without ``%`` and without the ``.16`` that numbers
    its instances, so the layers' copies of one operation add up."""
    name = re.match(r"%?([^\s=(]+)", event_name).group(1)
    return re.sub(r"\.\d+$", "", name)


def load(path):
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}] from
    an ``.xplane.pb``, or from an XSpace text proto (``.pbtxt``: the small
    recordings under ``tests/data/``)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".pbtxt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(str(path))
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def union(intervals):
    """Merge (start, end) pairs; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """{name: seconds} of SELF time: an event's duration less that of the
    events nested directly inside it, so a ``while`` or a fusion parent is
    not counted again on top of its children."""
    out = {}
    stack = []  # [name, end, self_ns]

    def close():
        name, _, own = stack.pop()
        out[name] = out.get(name, 0.0) + own / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close()
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(planes):
    """The reduction.  Returns None where the trace holds no device plane
    with operations.  Otherwise a dict:

        window_s     first ``perfbench.step`` start to last one's end
                     (the device's first to last operation where the host
                     plane has no such span)
        busy_s       union of the device's operation intervals inside the
                     window, averaged over the device planes
        steps        perfbench.step spans counted
        op_s         {operation name (:func:`op_name`): self seconds}, summed
                     over the planes
        device_ops   the ten largest of those, [[name, seconds], ...]
        idle_gaps    the device's idle time inside the window by what the
                     host's main thread was doing, [[label, seconds], ...]
    """
    dev = []
    for p in planes:
        if p["name"].startswith(DEVICE_PREFIX):
            ops = [ln for ln in p["lines"] if ln["name"] == OPS_LINE]
            if ops and ops[0]["events"]:
                dev.append(ops[0]["events"])
    if not dev:
        return None
    host_lines = [ln for p in planes if p["name"] == HOST_PLANE
                  for ln in p["lines"]]
    main = next((ln for ln in host_lines
                 if any(e[0] == STEP for e in ln["events"])), None)
    spans = sorted((s, s + d) for n, s, d in main["events"] if n == STEP) \
        if main else []
    if spans:
        lo, hi = spans[0][0], spans[-1][1]
    else:
        lo = min(s for evs in dev for _, s, _ in evs)
        hi = max(s + d for evs in dev for _, s, d in evs)
    busy, op_s, gaps = 0.0, {}, {}
    for evs in dev:
        inside = [(n, s, d) for n, s, d in evs if s + d > lo and s < hi]
        merged = _clip(union([(s, s + d) for _, s, d in inside]), lo, hi)
        busy += sum(e - s for s, e in merged) / 1e9
        for n, t in self_times([(op_name(n), s, d)
                                for n, s, d in inside]).items():
            op_s[n] = op_s.get(n, 0.0) + t
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = _host_label(main, spans, g0, g1)
                gaps[label] = gaps.get(label, 0.0) + (g1 - g0) / 1e9
    top = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / len(dev),
            "steps": len(spans), "op_s": op_s,
            "device_ops": [[n, t] for n, t in top[:10]],
            "idle_gaps": [[n, t / len(dev)] for n, t in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]}


def _host_label(main, spans, g0, g1):
    """What the host's main thread was doing while the device idled over
    [g0, g1]: the most specific (shortest) host event covering the gap's
    middle, said to be inside ``step()`` or in the harness's own loop."""
    mid = (g0 + g1) / 2
    where = "step()" if any(s <= mid < e for s, e in spans) \
        else "perfbench loop"
    if main is None:
        return where
    cover = [(d, n) for n, s, d in main["events"]
             if n != STEP and s <= mid < s + d]
    return f"{where}: {min(cover)[1]}" if cover else where


class Tracer:
    """Wraps ``steps`` engine steps, from ``at`` of the way into the window,
    in the JAX profiler (Python call tracing off: a step makes thousands of
    calls), each step inside a ``perfbench.step`` annotation.  Every step
    of the run goes through :meth:`around_step`, from the first, so the count
    of its calls is the step's index in the loop's list of steps, and
    ``traced`` holds the indices of the steps that were wrapped."""

    def __init__(self, directory, steps, at):
        self.dir, self.steps, self.at = str(directory), steps, at
        self.state, self.calls, self.traced = "waiting", 0, []
        shutil.rmtree(self.dir, ignore_errors=True)

    def tick(self, elapsed, seconds):
        import jax

        if self.state == "waiting" and elapsed >= self.at * seconds:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state = "tracing"
        elif self.state == "tracing" and len(self.traced) >= self.steps:
            self.close()

    @contextlib.contextmanager
    def around_step(self):
        import jax

        index, self.calls = self.calls, self.calls + 1
        if self.state != "tracing":
            yield
            return
        with jax.profiler.TraceAnnotation(STEP):
            yield
        self.traced.append(index)

    def close(self):
        import jax

        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"

    def path(self):
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return found[0] if found else None

    def reduce(self, say=print, first=0):
        """The reduction of the recording (:func:`reduce`), and under
        ``step_indices`` which steps it covers, counted from step ``first``
        of the run (the window's first): None where the recording's
        ``perfbench.step`` spans are not the steps this object wrapped."""
        path = self.path()
        if self.state != "done" or path is None:
            say("trace: the profiler wrote nothing")
            return None
        planes = load(path)
        for p in planes:
            say(f"trace plane {p['name']}: " + ", ".join(
                f"{ln['name']} ({len(ln['events'])})" for ln in p["lines"]))
        out = reduce(planes)
        if out is None:
            say("trace: no device plane with operations")
        else:
            out["step_indices"] = [i - first for i in self.traced] \
                if len(self.traced) == out["steps"] else None
            say(f"trace: {out['steps']} steps (of the window: "
                f"{out['step_indices']}), window {out['window_s']:.4f} "
                f"s, device busy {out['busy_s']:.4f} s; "
                f"{os.path.getsize(path)} bytes at {path}")
        return out

    def keep(self, directory):
        """Copy the recording, and a readable summary of it, for a look by
        hand (``--keep-trace``)."""
        import json

        path = self.path()
        if not path:
            return
        os.makedirs(directory, exist_ok=True)
        shutil.copy(path, directory)
        summary = []
        for p in load(path):
            for ln in p["lines"]:
                tot = {}
                for n, _, d in ln["events"]:
                    c = tot.setdefault(n, [0, 0.0])
                    c[0] += 1
                    c[1] += d / 1e9
                top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:60]
                summary.append({"plane": p["name"], "line": ln["name"],
                                "events": len(ln["events"]),
                                "first_ns": min((s for _, s, _ in
                                                 ln["events"]), default=None),
                                "top": [[n, c, t] for n, (c, t) in top]})
        with open(os.path.join(directory, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
