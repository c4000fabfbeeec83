"""The granite_hybrid family in the harness: its cell rehearses to the end on
the CPU at the family's tiny widths (about two minutes), with its own rows,
units, counts and numbers, and the control reads worse than the program."""

import json
import os
import pathlib
import subprocess
import sys

PERFBENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERFBENCH.parent


def test_the_cell_rehearses_to_the_end(tmp_path):
    p = subprocess.run(
        [sys.executable, str(PERFBENCH / "run.py"), "--workload",
         "granite-4.0-h-small.chat", "--seed", str(2**31 + 7), "--seconds",
         "3", "--trace", "1", "--tiny", "--control", "1"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / ".xla_cache")})
    out = p.stdout.splitlines()
    last = json.loads(out[-1])
    assert last["rehearsal"] == "passed", p.stdout[-3000:] + p.stderr[-3000:]
    would = last["would_be"]
    assert would["failed"] == 0 and would["attempted"] > 0
    # K/V rows of the attention layer, tail and S of the Mamba layers
    rows = next(ln for ln in out if "check: cache rows" in ln)
    assert "cache rows (k, v, conv, ssm)" in rows
    per_unit = json.loads(rows.split("per unit ")[1])
    assert len(per_unit) == 3 and all(0 < x < 0.02 for x in per_unit)
    compared = would["compared"]
    assert list(compared)[0] == "state_rel_rms"
    ctl = json.loads(next(ln for ln in out if "control numbers: " in ln)
                     .split("control numbers: ")[1])
    assert ctl["int8"]["state_rel_rms"] \
        > 1.5 * compared["state_rel_rms"]["value"]
    assert any("router choices (token x layer)" in ln for ln in out)
    # the counters of the new layers are read; the CPU has no device trace
    m = would["metrics"]
    assert 40 < m["moe.local_route_share"]["value"] < 60
    assert m["cache.state_slots_peak"]["value"] >= 2
    assert m["moe.expert_rows_max"]["value"] > 0
    assert m["sched.prefix_hit_share"]["value"] == 0.0
    assert any("kernel.ssm_scan_roofline: nothing to read" in ln
               for ln in out)
