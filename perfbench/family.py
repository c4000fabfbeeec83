"""A configuration names its family (``"family"`` in ``configs/<name>.json``)
and the family names the one module that knows the block,
``families/<family>.py``: the program's model object, the seeded weights,
what the engine's cache holds for a request, the plain reference, the
check's limits, the counts and the rehearsal's sizes (``README.md``, "A
family"; optionally ``SAMPLE``, how many requests in flight and how many
finished ones the check takes, where the harness's 4 + 4 would not end inside
a run's time limit).  A new block is a new family file; nothing here names one but the
default, which the two configurations that came before the key keep."""

from __future__ import annotations

import importlib

DEFAULT = "llama_dense"
SUPPLIES = ("model_config", "make_params", "layer_params", "ends_params",
            "state_rows", "reference_forward", "ROWS_NUMBER", "UNITS_HELD",
            "CONTROLS", "LIMITS", "counts", "TINY")


def load(config):
    """The family module of a configuration dict."""
    name = config.get("family", DEFAULT)
    mod = importlib.import_module(f"families.{name}")
    missing = [n for n in SUPPLIES if not hasattr(mod, n)]
    if missing:
        raise ValueError(f"families/{name}.py lacks {missing}")
    return mod
