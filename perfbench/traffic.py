"""Traffic mixes are data: ``traffic/<name>.json`` holds the parameters and
its ``kind`` names the one general generator that reads them,
``generators/<kind>.py``.  A new mix of an existing kind is a new JSON
file; a new kind is a new generator file.  Nothing here names a mix."""

from __future__ import annotations

import importlib
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def load(name):
    path = HERE / "traffic" / f"{name}.json"
    with open(path) as f:
        spec = json.load(f)
    if "kind" not in spec:
        raise ValueError(f"{path}: no 'kind'")
    return spec


def make(spec, *, seed, vocab, max_seq_tokens):
    """The generator object for one run.  Its protocol (see
    ``generators/closed_loop.py``): ``due(now, finished)`` returns the
    requests to submit now, given the ids of requests seen finished since
    the last call (``None`` on the first call)."""
    mod = importlib.import_module(f"generators.{spec['kind']}")
    return mod.Generator(spec, seed=seed, vocab=vocab,
                         max_seq_tokens=max_seq_tokens)
