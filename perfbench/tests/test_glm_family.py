"""The glm_dsa family in the harness: it supplies everything ``family.py``
asks, its counts are positive and below what a toy step did, its reference's
tie rule and selection-off control behave, and its cell rehearses to the end
on the CPU at the family's tiny widths."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np

PERFBENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERFBENCH.parent


def _config():
    with open(PERFBENCH / "configs" / "glm-5.json") as f:
        return json.load(f)


def test_the_family_is_whole_and_the_file_holds_the_cut():
    import family

    config = _config()
    fam = family.load(config)
    assert fam.ROWS_NUMBER == "latent_rel_rms" and fam.UNITS_HELD == 6
    assert set(fam.LIMITS) == {"latent_rel_rms", "token_below_topk"}
    assert fam.ffn_kinds(config) == ("dense",) + ("moe",) * 5
    mcfg = fam.model_config(config)
    assert mcfg.local_experts == (0, 16) and mcfg.num_experts == 256
    assert mcfg.latent_width == 576 and mcfg.index_topk == 2048
    assert mcfg.attention_scale == 1 / 16
    # the cut of ISSUE 35's table: 4,727M parameters, 9.45 GB of bf16
    elems = config["hidden_size"] * config["vocab_size"] * 2
    for ffn in fam.ffn_kinds(config):
        elems += sum(int(np.prod(s)) for s in fam._shapes(config, ffn).values())
    assert 9.44e9 < 2 * elems < 9.47e9
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "glm-5")
    assert set(entry["reduced"]) == set(config["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == "glm-5.longctx")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5", "longctx", 1)


def test_counts_are_positive_and_below_what_a_step_does():
    """The counts of a full step at the cell's widths: positive, and each a
    lower bound: below the operations and bytes a step of 1,016 + 6 rows
    launches (whole windows scored, whole lists gathered)."""
    import family

    config = _config()
    c = family.load(config).counts
    chunk, lanes, ctx, topk, window = 1016, 6, 9000, 2048, 16640
    rows = chunk + lanes
    facts = {"tokens": rows,
             "attn_pairs_causal": chunk * ctx + lanes * ctx,
             "attn_pairs_selected": rows * topk,
             "index_rows_scored": 7 * ctx, "latent_rows_read":
             ctx + lanes * topk, "moe_routes_local": rows * 8 * 5 // 16,
             "moe_experts_hit": 80}
    layers = 6
    launched = {
        "index_flops": 2 * (8 * 128 + 8) * window * 32 * 128 * layers,
        "index_bytes": 2 * 128 * 9 * window * layers,
        "sparse_attn_flops": 2 * (8 * 128 + 8) * topk * 64 * 1280 * layers,
        "sparse_attn_bytes": 2 * 640 * (8 * 128 + 8) * topk * layers,
        "moe_expert_flops": 2 * 3 * 6144 * 2048 * rows * 8 * 5,
        "moe_expert_bytes": 2 * 3 * 6144 * 2048 * 80 + 1,
        "dense_flops": 2 * 1024 * 1.3e9, "dense_bytes": 2 * 1.3e9}
    for name, most in launched.items():
        value = getattr(c, name)(config, facts)
        assert 0 < value < most, (name, value, most)
    # 285 MFLOP a token a layer of sparse attention at 2,048 rows (ISSUE 35)
    per = c.sparse_attn_flops(config, facts) / rows / layers
    assert 2.8e8 < per < 2.9e8


def test_the_cell_rehearses_to_the_end(tmp_path):
    p = subprocess.run(
        [sys.executable, str(PERFBENCH / "run.py"), "--workload",
         "glm-5.longctx", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--tiny"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / ".xla_cache")})
    out = p.stdout.splitlines()
    last = json.loads(out[-1])
    assert last["rehearsal"] == "passed", p.stdout[-3000:] + p.stderr[-3000:]
    would = last["would_be"]
    assert would["failed"] == 0 and would["attempted"] > 0
    rows = next(ln for ln in out if "check: cache rows" in ln)
    assert "cache rows (ckv, kpe, kidx)" in rows
    per_unit = json.loads(rows.split("per unit ")[1])
    assert len(per_unit) == 2 and all(0 < x < 0.06 for x in per_unit)
    assert list(would["compared"])[0] == "latent_rel_rms"
    assert any("selections (query row past 24 x layer)" in ln for ln in out)
    assert any("router choices (token x layer)" in ln for ln in out)
    m = would["metrics"]
    # 4 of the toy's 8 experts are held; the random correction bias tilts it
    assert 30 < m["moe.local_route_share"]["value"] < 70
    assert 30 < m["attn.selected_share"]["value"] < 100
    assert m["sched.prefix_hit_share"]["value"] == 0.0
    assert any("kernel.sparse_mla_roofline: nothing to read" in ln
               for ln in out)


def test_the_long_mix_is_refused_where_its_engine_is_too_small():
    """``closed_loop_ctx`` is ``closed_loop`` (the same deck under the same
    seeds) but refuses a mix that does not fit when the callers first send,
    not when it is built."""
    import pytest

    import traffic

    spec = traffic.load("longctx")
    assert spec["kind"] == "closed_loop_ctx" and spec["clients"] == 8
    assert spec["prompt_len"] == [4096, 16384]
    assert spec["output_len"] == [16, 128]
    small = traffic.make(spec, seed=0, vocab=512, max_seq_tokens=2048)
    with pytest.raises(ValueError, match="no operation may fail"):
        small.due(0.0, None)
    fits = traffic.make(spec, seed=3, vocab=512, max_seq_tokens=16640)
    plain = traffic.make(dict(spec, kind="closed_loop"), seed=3, vocab=512,
                         max_seq_tokens=16640)
    assert fits.deck == plain.deck and len(fits.deck) == 32
    first = fits.due(0.0, None)
    assert [(r["rid"], len(r["prompt"]), r["output_len"]) for r in first] \
        == [(r["rid"], len(r["prompt"]), r["output_len"])
            for r in plain.due(0.0, None)]
    assert max(p + o for p, o in fits.deck) <= 16640
