"""The harness refuses a CPU without ``--tiny`` and an unknown
``device_kind``; a run with the timed path broken underneath comes out not
correct.  (The second drives a whole tiny run on the CPU: about two
minutes.)"""

import json
import os
import subprocess
import sys
import pathlib

import pytest

import family
import run

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent


def _cell():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)["workloads"][0]["name"]


def test_a_cpu_without_tiny_is_refused(capsys):
    assert run.main(["--workload", _cell(), "--seed", "1"]) == 2
    out = capsys.readouterr()
    assert "no TPU" in out.err and "{" not in out.out


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.main(["--workload", "no-such-cell"])


def test_an_unknown_device_kind_is_refused(monkeypatch, capsys):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    assert run.main(["--workload", _cell(), "--seed", "1"]) == 2
    assert "no peaks" in capsys.readouterr().err


def test_every_metric_of_the_benchmark_has_its_files():
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        with open(ROOT / "perfbench" / "layers" / f"{m['name']}.json") as f:
            spec = json.load(f)
        assert (ROOT / "perfbench" / "readers"
                / f"{spec['reader']}.py").exists()
        for k in ("layer", "unit", "moves", "source"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for c in bench["configs"]:
        with open(ROOT / c["file"]) as f:
            name = json.load(f).get("family", family.DEFAULT)
        assert (ROOT / "perfbench" / "families" / f"{name}.py").exists()
    for w in bench["workloads"]:
        assert (ROOT / "perfbench" / "traffic"
                / f"{w['traffic']}.json").exists()


BROKEN = """
import sys
sys.argv = ["run.py"]
sys.path[:0] = [{root!r}, {root!r} + "/perfbench"]
import run
from flashinfer_tpu.serve import ServingEngine
real = ServingEngine.step
def step(self):
    facts = real(self)
    for r in self._running:            # a token altered where it is produced
        if r.out_tokens:
            r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab_size
    return facts
ServingEngine.step = step
sys.exit(run.main(["--workload", {cell!r}, "--seed", "5", "--seconds", "3",
                   "--tiny"]))
"""


def test_a_broken_timed_path_is_not_correct():
    """Skips the harness's look for a chip (``--tiny``) and drives the rest
    of a run with every served token altered where the engine appends it:
    the cache rows still follow the altered tokens, so ``kv_rel_rms`` holds,
    and ``token_below_topk`` has to catch it."""
    code = BROKEN.format(root=str(ROOT), cell=_cell())
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "FAILED", p.stdout[-2000:]
    assert last["would_be"]["correct"] is False
    lines = [ln for ln in p.stdout.splitlines() if "compared " in ln]
    assert any("token_below_topk" in ln and "NOT OK" in ln for ln in lines)
    assert any("kv_rel_rms" in ln and " ok" in ln for ln in lines)
    assert p.returncode == 1


class _Flying:
    def __init__(self, rid, kv_len, served):
        self.rid, self.kv_len, self.state = rid, kv_len, "running"
        self.out_tokens, self.preemptions = [7] * served, 0


class _Driver:
    """Six requests in flight, ``f0`` the longest, at a window's close."""

    def __init__(self):
        self.live = {f"f{i}": _Flying(f"f{i}", 40 - i + 2, 3)
                     for i in range(6)}
        self.records = {rid: {"prompt": [1] * (r.kv_len - 2)}
                        for rid, r in self.live.items()}


@pytest.mark.parametrize("sample", [run.SAMPLE, (2, 1), (1, 1), (8, 8)])
def test_the_checks_sample_is_as_many_as_asked_with_the_longest(sample):
    import numpy as np

    ended = [{"rid": f"e{i}", "prompt": [1] * (30 - i), "tokens": [7, 7]}
             for i in range(5)] + [{"rid": "none", "prompt": [1] * 99,
                                    "tokens": []}]
    got = run.pick_samples(_Driver(), np.random.default_rng(3),
                           lambda req, n: {"k": n}, ended, sample)
    flying = [s for s in got if s["prog_rows"] is not None]
    done = [s for s in got if s["prog_rows"] is None]
    assert len(flying) == min(sample[0], 6) and len(done) == min(sample[1], 5)
    assert flying[0]["rid"] == "f0" and done[0]["rid"] == "e0"
    assert len({s["rid"] for s in got}) == len(got)
    for s in flying:   # the rows the engine holds: prompt + served - 1
        assert len(s["tokens"]) == s["prog_rows"]["k"]
        assert s["logit_rows"][0] == len(s["tokens"]) - len(s["served"])


def test_only_the_long_context_family_takes_a_smaller_sample():
    """``minicpm_sala``'s reference over 16k-65k rows a request would not end
    inside a run's time limit over the harness's 4 + 4 (PERF.md section 2)."""
    assert run.SAMPLE == (4, 4)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(ROOT / c["file"]) as f:
            fam = family.load(json.load(f))
        want = (2, 1) if fam.__name__ == "families.minicpm_sala" else None
        assert getattr(fam, "SAMPLE", None) == want, fam.__name__
