"""The seam between the harness and a block: a second family arrives as new
files alone and rehearses to the end; the default family's weights are the
bits they were before the seam.  (The first drives a whole tiny run on the
CPU: about two minutes.)"""

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import family

PERFBENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERFBENCH.parent
NEW = PERFBENCH / "tests" / "data" / "second_family"


def _digest(tree):
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf.astype("float32")).tobytes())
    return h.hexdigest()[:16]


# Taken on commit 780b73a, before the seam, from weights.make_params /
# layer_params(.., 1) / ends_params at tiny.json's model sizes, on the CPU.
PARENT = {5: ("f5b383df17e85ae8", "ad9ca5fe0371826f", "1ba54e0b8a57b60d"),
          2**31 + 5: ("522d6d7e5cc4f73f", "08c17482276d5252",
                      "3a3e5bd7beb07fd1")}


@pytest.mark.parametrize("seed", sorted(PARENT))
def test_the_same_seed_still_gives_the_same_bits(seed):
    fam = family.load({})
    assert fam.__name__ == "families.llama_dense"
    m = dict(fam.TINY, rope_theta=1e6, rms_norm_eps=1e-5,
             torch_dtype="bfloat16")
    assert (_digest(fam.make_params(seed, m)),
            _digest(fam.layer_params(seed, m, 1)),
            _digest(fam.ends_params(seed, m))) == PARENT[seed]


def test_a_family_that_lacks_a_part_is_refused(tmp_path, monkeypatch):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half_done.py").write_text("TINY = {}\n")
    import families

    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(tmp_path / "families")])
    with pytest.raises(ValueError, match="lacks"):
        family.load({"family": "half_done"})


def _hashes(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".xla_cache" not in p.parts
            and ".perfbench_trace" not in p.parts}


def test_a_second_family_arrives_as_new_files(tmp_path):
    """Copies the benchmark to a temporary root, adds there the toy family's
    three files and its entries in ``BENCHMARK.json``, and rehearses its
    cell: cache rows of another kind in two units a layer, its own limits,
    its own number, its own sizes, its roofline metric over the general
    reader - with no edit to a file that was copied."""
    shutil.copytree(PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _hashes(tmp_path / "perfbench")
    shutil.copytree(NEW, tmp_path / "perfbench", dirs_exist_ok=True)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    with open(NEW / "layers" / "toy.dense_roofline_share.json") as f:
        spec = json.load(f)
    bench["configs"].append({
        "name": "toy-packed", "source": "perfbench/tests/test_family.py",
        "file": "perfbench/configs/toy-packed.json", "reduced": [],
        "why": "a second family by new files alone"})
    bench["workloads"].append({
        "name": "toy.chat", "config": "toy-packed", "traffic": "chat",
        "chips": 1, "why": "rehearsal only"})
    bench["per_layer"].append({
        "name": "toy.dense_roofline_share", "workloads": ["toy.chat"],
        **{k: spec[k] for k in ("unit", "source", "layer", "moves")},
        "better": "higher"})
    lanes = next(m for m in bench["per_layer"]
                 if m["name"] == "sched.lanes_mean")
    lanes["workloads"].append("toy.chat")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    p = subprocess.run(
        [sys.executable, str(tmp_path / "perfbench" / "run.py"),
         "--workload", "toy.chat", "--seed", "7", "--seconds", "3",
         "--trace", "1", "--tiny", "--control", "1"],
        capture_output=True, text=True, timeout=900, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / ".xla_cache")})
    out = p.stdout.splitlines()
    last = json.loads(out[-1])
    assert last["rehearsal"] == "passed", p.stdout[-3000:] + p.stderr[-3000:]
    assert p.returncode == 0
    # its own rows, units, sizes, limits and number
    rows = next(ln for ln in out if "check: cache rows" in ln)
    assert "cache rows (kv)" in rows
    per_unit = json.loads(rows.split("per unit ")[1])
    assert len(per_unit) == 6 and all(0 < x < 0.02 for x in per_unit)
    compared = last["would_be"]["compared"]
    assert list(compared)[0] == "packed_rel_rms"
    assert compared["packed_rel_rms"]["value"] == max(per_unit[:3]) \
        or compared["packed_rel_rms"]["value"] == pytest.approx(
            max(per_unit[:3]), abs=1e-5)
    assert compared["packed_rel_rms"]["limit"] == 0.02
    assert compared["samples_without_logits"] == {"value": 0, "limit": 0}
    assert "kv_rel_rms" not in compared
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")
    # the control (the toy's reference in int8) reads well above the program
    ctl = json.loads(next(ln for ln in out if "control numbers: " in ln)
                     .split("control numbers: ")[1])
    assert ctl["int8"]["packed_rel_rms"] \
        > 3 * compared["packed_rel_rms"]["value"]
    # the CPU has no device trace: the roofline metric reads nothing, the
    # cell's other metric is there
    assert any("toy.dense_roofline_share: nothing to read" in ln
               for ln in out)
    assert "sched.lanes_mean" in last["would_be"]["metrics"]
    after = _hashes(tmp_path / "perfbench")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/toy-packed.json", "families/toy_packed.py",
        "layers/toy.dense_roofline_share.json"]
