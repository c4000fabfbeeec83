"""The afmoe family in the harness: it supplies everything ``family.py``
asks, the file holds the cut, its counts are positive and below what a step
launches, its reference's window and rotation controls behave, and its cell
rehearses to the end on the CPU at the family's tiny widths."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np

PERFBENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERFBENCH.parent


def _config():
    with open(PERFBENCH / "configs" / "trinity-mini.json") as f:
        return json.load(f)


def fam_limits():
    import family

    return family.load(_config()).LIMITS


def test_the_family_is_whole_and_the_file_holds_the_cut():
    import family

    config = _config()
    fam = family.load(config)
    assert fam.ROWS_NUMBER == "kv_rel_rms" and fam.UNITS_HELD == 32
    assert set(fam.LIMITS) == {"kv_rel_rms", "token_below_topk"}
    mcfg = fam.model_config(config)
    assert mcfg.local_experts == (0, 16) and mcfg.num_experts == 128
    assert mcfg.layer_kinds() == (("window",) * 3 + ("attention",)) * 8
    assert mcfg.attention_window == 2048 and mcfg.num_dense_layers == 2
    # the cut of ISSUE 37: 4.98B parameters, 9.97 GB of bf16, no width cut
    elems = config["hidden_size"] * config["vocab_size"] * 2
    for li in range(32):
        elems += sum(int(np.prod(s))
                     for s in fam._shapes(config, li < 2).values())
    assert 9.96e9 < 2 * elems < 9.98e9
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert set(entry["reduced"]) == set(config["reduced"]) == {"num_experts"}
    assert config["num_experts"] == 16 \
        and config["num_experts_published"] == 128
    cell = next(w for w in bench["workloads"]
                if w["name"] == "trinity-mini.longctx")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "longctx", 1)
    # the engine block: 8 x 1,040 full-layer pages and the scratch page
    eng = config["engine"]
    assert eng["num_pages"] == 8 * (eng["max_seq_tokens"] // 16) + 1


def test_counts_are_positive_and_below_what_a_step_does():
    """The counts of a full step at the cell's widths: positive, and each a
    lower bound: below the operations and bytes a step of 1,016 + 6 rows
    launches (whole tiles, whole chunks)."""
    import family

    config = _config()
    c = family.load(config).counts
    chunk, lanes, ctx, window = 1016, 6, 9000, 2048
    rows = chunk + lanes
    facts = {"tokens": rows,
             "attn_pairs_causal": rows * ctx, "attn_pairs_full": rows * ctx,
             "attn_pairs_window": rows * window,
             "full_rows_read": 7 * ctx,
             "window_rows_read": (window - 1 + chunk) + lanes * window,
             "moe_routes_local": rows * 8 * 30 // 8, "moe_experts_hit": 480}
    launched = {
        "window_attn_flops": 4 * 1024 * (window + 256) * 32 * 128 * 24 * 1.2,
        "window_attn_bytes": 7 * (window + 1024 + 256) * 2048 * 24,
        "full_attn_flops": 4 * 1152 * ctx * 32 * 128 * 8 * 1.2,
        "full_attn_bytes": 8 * ctx * 2048 * 8 * 1.2,
        "moe_expert_flops": 2 * 3 * 2048 * 1024 * rows * 8 * 30,
        "moe_expert_bytes": 2 * 3 * 2048 * 1024 * 480 + 1,
        "dense_flops": 2 * 1024 * 0.95e9, "dense_bytes": 2 * 0.95e9}
    for name, most in launched.items():
        value = getattr(c, name)(config, facts)
        assert 0 < value < most, (name, value, most)
    # 16,384 operations an attended pair a layer; 2 KiB a cached row a layer
    assert c.full_attn_flops(config, facts) == 16384 * rows * ctx * 8
    assert c.window_attn_bytes(config, facts) \
        == 2048 * facts["window_rows_read"] * 24


def test_the_reference_window_and_rotation():
    """One layer of the reference at toy widths: a sliding layer's rows past
    the window do not reach a query, a full layer's do, only the sliding
    layer's keys depend on the position, and "float32-nowindow" turns the
    window off."""
    import jax
    import jax.numpy as jnp

    import family

    config = dict(_config())
    fam = family.load(config)
    config.update(fam.TINY, sliding_window=8)
    ref = fam.reference
    layer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         fam.layer_params(3, config, 0))
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 128), jnp.float32)
    kw = dict(heads=4, kv_heads=2, head_dim=32, theta=1e4, eps=1e-5)

    def run(xs, window, rotate):
        return ref.attention(xs, jnp.int32(32), layer, window=window,
                             rotate=rotate, **kw)

    far = x.at[3].add(1.0)        # position 3 is outside row 31's window
    out = run(x, 8, True)[0]
    out_far = run(far, 8, True)[0]
    assert float(jnp.max(jnp.abs(out[31] - out_far[31]))) == 0.0
    assert float(jnp.max(jnp.abs(out[10] - out_far[10]))) > 1e-3
    full = run(x, 0, False)[0]
    assert float(jnp.max(jnp.abs(full[31] - run(far, 0, False)[0][31]))) \
        > 1e-4
    # rotation: the same token at two positions has one key in a full
    # layer, two in a sliding one
    twice = jnp.tile(x[:1], (32, 1))
    _, k_rot, _ = run(twice, 8, True)
    _, k_flat, _ = run(twice, 0, False)
    assert float(jnp.max(jnp.abs(k_flat[5] - k_flat[20]))) < 1e-6
    assert float(jnp.max(jnp.abs(k_rot[5] - k_rot[20]))) > 1e-2


def test_the_cell_rehearses_to_the_end(tmp_path):
    p = subprocess.run(
        [sys.executable, str(PERFBENCH / "run.py"), "--workload",
         "trinity-mini.longctx", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--tiny", "--control", "float32-nowindow",
         "--keep-gaps", str(tmp_path / "gaps.json")],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / ".xla_cache")})
    out = p.stdout.splitlines()
    last = json.loads(out[-1])
    assert last["rehearsal"] == "passed", p.stdout[-3000:] + p.stderr[-3000:]
    would = last["would_be"]
    assert would["failed"] == 0 and would["attempted"] > 0
    rows = next(ln for ln in out if "check: cache rows" in ln)
    assert "cache rows (k, v)" in rows
    per_unit = json.loads(rows.split("per unit ")[1])
    # which requests are in flight when the 2 s close follows the machine's
    # speed; where one of them has a swapped expert (3 of 8 at these widths)
    # the layers after it read 0.07-0.08, else 0.01-0.015
    assert len(per_unit) == 4 and all(0 < x < 0.2 for x in per_unit)
    assert list(would["compared"])[0] == "kv_rel_rms"
    assert set(would["compared"]) == set(fam_limits()) | {
        "rows_not_finite", "tokens_outside_vocab", "traces_in_window",
        "compiles_in_window"}
    # the window-off control by its name: the first layer's rows do not
    # depend on the window, the later ones read far over the program's, and
    # every sampled token's gap is kept, the control's drawn tokens' too
    ctl = json.loads(next(ln for ln in out if "control numbers: " in ln)
                     .split("control numbers: ")[1])["float32-nowindow"]
    assert ctl["kv_rel_rms"] > 2 * would["compared"]["kv_rel_rms"]["value"]
    assert any("control float32-nowindow: would read correct false" in ln
               for ln in out)
    with open(tmp_path / "gaps.json") as f:
        kept = json.load(f)
    served = next(ln for ln in out if " served tokens of " in ln)
    assert f"check: {len(kept['tokens']['gap'])} served tokens" in served
    assert max(kept["tokens"]["gap"]) \
        == would["compared"]["token_below_topk"]["value"]
    assert kept["controls"]["float32-nowindow"]["numbers"] == ctl
    assert len(kept["controls"]["float32-nowindow"]["tokens"]["gap"]) \
        == len(kept["tokens"]["gap"])
    assert any("router choices (token x layer)" in ln for ln in out)
    m = would["metrics"]
    # 4 of the toy's 8 experts are held; the random selection bias tilts it
    assert 30 < m["moe.local_route_share"]["value"] < 70
    # the toy's window is 32 and its prompts reach 100
    assert 30 < m["attn.window_pair_share"]["value"] < 100
    assert 0 < m["cache.window_pages_peak_share"]["value"] <= 100
    assert m["sched.prefix_hit_share"]["value"] == 0.0
    assert 0 < m["kernel.prefill_units_useful_share"]["value"] <= 100
    assert any("kernel.window_attn_roofline: nothing to read" in ln
               for ln in out)
