"""Reads the device time of the step program's named parts from the
profiler's recording.

The engine's step body wraps its parts in ``jax.named_scope`` (``embed``;
per layer ``attn.qkv``, ``attn.kv_write``, ``attn.core``, ``attn.out``,
``mlp``; ``head``; ``sample``), and a scope is a component of the HLO
``op_name`` of every operation under it
(``jit(_body)/attn.kv_write/cond/branch_0_fun/attn.core/...``).  A v5e
recording (PR 24's first kept trace) keeps that path in the stat ``tf_op``
of an operation's event METADATA, with the operation's kind after a colon
(``jit(_body)/attn.qkv/dot_general:``).  ``jax.profiler.ProfileData`` shows
an event's own stats and not its metadata's, and ``trace_reduce.load`` drops
both, so this reader takes the events from ``ProfileData`` and the paths from
the file's bytes (:func:`op_paths`, a reader of the few protobuf fields it
needs), joined by the event's name.  The recording is the one the harness
made, under ``.perfbench_trace/`` at the root of the checkout.

An event belongs to the LAST component of its path that is a known scope
(the ``cond`` that picks the fused-ingest branch sits in ``attn.kv_write``
and holds ``attn.core`` inside it); an event with no such component, or
with no path at all (copies the compiler put in), is ``unscoped``.  Time is
SELF time inside the traced steps, as in ``trace_reduce.reduce``, so the
parts, the attention kernels and ``unscoped`` add up to the device's busy
time.

Parameters: ``scopes`` (the parts to add up, ``unscoped`` among them) and
optionally ``exclude`` (regular expressions on the operation's name: the
attention kernels lie in ``attn.core`` and have metrics of their own).
Reads nothing where the trace has no device plane or no operation lies in
a known scope."""

from __future__ import annotations

import functools
import glob
import os
import pathlib
import re

import trace_reduce as tr

SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
          "mlp", "head", "sample")
STAT = "tf_op"
TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".perfbench_trace"


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int,
    a length-delimited field as a memoryview, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire}")


def op_paths(serialized):
    """{event name: op_name path} over the device planes of a serialized
    XSpace.  XSpace.planes = 1; XPlane: name 2, event_metadata 4 and
    stat_metadata 5 (maps: key 1, value 2); XEventMetadata: name 2, stats
    5; XStatMetadata: name 2; XStat: metadata_id 1, str_value 5,
    ref_value 7 (the id of a stat metadata whose name is the string)."""
    out = {}
    for num, plane in _fields(memoryview(serialized)):
        if num != 1:
            continue
        top = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in top if n == 2), "")
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        stat_names = {}
        for n, entry in top:
            if n == 5:
                e = dict(_fields(entry))
                stat_names[e.get(1, 0)] = bytes(
                    dict(_fields(e[2])).get(2, b"")).decode()
        for n, entry in top:
            if n != 4:
                continue
            md = list(_fields(dict(_fields(entry))[2]))
            for m, stat in md:
                if m != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == STAT:
                    path = bytes(st[5]).decode() if 5 in st \
                        else stat_names.get(st.get(7), "")
                    ev = next(bytes(v).decode() for k, v in md if k == 2)
                    out[ev] = path.rsplit(":", 1)[0]
    return out


def scope_of(path):
    """The last known scope among the components of an ``op_name``."""
    for part in reversed((path or "").split("/")):
        if part in SCOPES:
            return part
    return "unscoped"


def load(path):
    """(device planes' events [(name, start_ns, dur_ns, op_name path or
    None)], the host's ``perfbench.step`` spans) of an ``.xplane.pb`` or
    of an XSpace text proto (``.pbtxt``, the recordings under
    ``tests/data/``)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".pbtxt"):
        with open(path) as f:
            raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    else:
        with open(path, "rb") as f:
            raw = f.read()
    paths = op_paths(raw)
    dev, spans = [], []
    for p in ProfileData.from_serialized_xspace(raw).planes:
        for ln in p.lines:
            if p.name.startswith(tr.DEVICE_PREFIX) \
                    and ln.name == tr.OPS_LINE:
                dev.append([(e.name, float(e.start_ns),
                             float(e.duration_ns), paths.get(e.name))
                            for e in ln.events])
            elif p.name == tr.HOST_PLANE:
                spans += [(float(e.start_ns),
                           float(e.start_ns + e.duration_ns))
                          for e in ln.events if e.name == tr.STEP]
    return [d for d in dev if d], sorted(spans)


def scope_seconds(dev, spans, exclude=()):
    """{scope: self seconds inside the traced steps}, summed over the
    device planes; an operation whose name matches ``exclude`` is booked
    under ``excluded``.  None where no operation lies in a known scope (a
    program without them): all of its time would read ``unscoped``."""
    if not any(scope_of(e[3]) != "unscoped" for evs in dev for e in evs):
        return None
    pats = [re.compile(p) for p in exclude]
    lo = spans[0][0] if spans else float("-inf")
    hi = spans[-1][1] if spans else float("inf")
    out = {}
    for evs in dev:
        labelled = [
            ("excluded" if any(p.search(tr.op_name(n)) for p in pats)
             else scope_of(path), s, d)
            for n, s, d, path in evs if s + d > lo and s < hi]
        for scope, t in tr.self_times(labelled).items():
            out[scope] = out.get(scope, 0.0) + t
    return out


@functools.lru_cache(maxsize=2)
def _seconds(path, _mtime, exclude):
    """One load and one reduction per recording, for all the metrics."""
    return scope_seconds(*load(path), exclude)


def seconds(ctx, spec):
    """Self seconds, over the traced steps, of the operations under
    ``spec["scopes"]`` (less ``spec["exclude"]``); None where there is
    nothing to read."""
    t = ctx["trace"]
    found = glob.glob(str(TRACE_DIR / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not t or not t["steps"] or not found:
        return None
    secs = _seconds(found[0], os.path.getmtime(found[0]),
                    tuple(spec.get("exclude", ())))
    if secs is None:
        return None
    return sum(secs.get(s, 0.0) for s in spec["scopes"])


def read(ctx, spec):
    secs = seconds(ctx, spec)
    return None if secs is None else 1e3 * secs / ctx["trace"]["steps"]
