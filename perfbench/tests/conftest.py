"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q``
(the repo's tier-1 command collects only ``tests/``)."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE.parent.parent, HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
