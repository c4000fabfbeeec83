"""The minicpm_sala family in the harness: it supplies everything
``family.py`` asks, its configuration file holds the catalog row and the cut,
its counts are positive and below what a step launches, and the two mixes
ISSUE 45 named hold its parameters.  (The cell's rehearsal, ``run.py --tiny``,
is run by hand: it compiles four rungs.)"""

import json
import pathlib

import numpy as np

PERFBENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERFBENCH.parent
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _config():
    with open(PERFBENCH / "configs" / "minicpm-sala.json") as f:
        return json.load(f)


def test_the_family_is_whole_and_the_file_holds_the_cut():
    import family

    config = _config()
    fam = family.load(config)
    assert fam.ROWS_NUMBER == "state_rel_rms" and fam.UNITS_HELD == 16
    assert set(fam.LIMITS) == {"state_rel_rms", "token_below_topk"}
    kinds = fam.mixers(config)
    assert kinds.count("minicpm4") == 4 and kinds.count("lightning-attn") == 12
    assert fam.published(config) == tuple(range(9, 25))
    assert list(kinds) == config["mixer_types_published"][9:25]
    mcfg = fam.model_config(config)
    assert mcfg.layer_kinds().count("latent") == 4
    assert (mcfg.topk, mcfg.block_size, mcfg.local_blocks, mcfg.block_pages) \
        == (64, 64, 32, 4)
    assert abs(mcfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    # ISSUE 45's arithmetic: 5,039M parameters, 10.08 GB of bf16
    elems = config["hidden_size"] * config["vocab_size"] * 2
    per = {k: sum(int(np.prod(s)) for n, s in fam._shapes(config, k).items()
                  if not n.endswith("norm"))
           for k in ("minicpm4", "lightning-attn")}
    assert round(per["lightning-attn"] / 1e6, 1) == 285.2
    assert round(per["minicpm4"] / 1e6, 1) == 253.8
    elems += sum(per[k] for k in kinds)
    assert 10.07e9 < 2 * elems < 10.09e9
    assert fam.state_bytes_per_request(config) == 12 * 2 * 2 ** 20
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "minicpm-sala")
    assert set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "mixer_types"}
    cell = next(w for w in bench["workloads"]
                if w["name"] == "minicpm-sala.longctx-64k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala", "longctx-64k", 1)
    assert len(bench["workloads"]) == 8 \
        and all(w["chips"] == 1 for w in bench["workloads"])


def test_the_file_holds_every_catalog_key():
    if not CATALOG.exists():
        import pytest

        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    config = _config()
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert config["mixer_types_published"] == row["config"]["mixer_types"]


def test_counts_are_positive_and_below_what_a_step_does():
    """The counts of a full step at the cell's widths (a 1,016-token chunk
    at ~40k beside 6 lanes): positive, and each a lower bound: below what the
    step launches (whole compressed windows scored, tiles' unions read)."""
    import family

    config = _config()
    c = family.load(config).counts
    chunk, lanes, ctx = 1016, 6, 40000
    rows = chunk + lanes
    facts = {"tokens": rows, "ctx_tokens": 7 * ctx,
             "attn_pairs_causal": rows * ctx,
             "attn_pairs_selected": rows * (63 * 64 + 32),
             "index_rows_scored": 7 * ctx // 16,
             "attn_blocks_selected": rows * 64 * 2 * 4,
             "attn_blocks_read": (chunk * 270 + lanes * 64) * 2 * 4,
             "kv_rows_read": (chunk * 270 + lanes * 64) * 2 * 4 * 64,
             "ssm_decode_lanes": lanes, "ssm_prefill_tokens": chunk}
    launched = {
        "index_flops": 2 * rows * 4096 * 32 * 128 * 4,
        "index_bytes": 2 * 256 * 9 * 4096 * 4,
        "sparse_attn_flops": 4 * facts["kv_rows_read"] // 2 * 16 * 128 * 2,
        "sparse_attn_bytes": facts["kv_rows_read"] // 8 * 512 + 1,
        "ssm_scan_flops": 12 * 32 * 9 * (2 * 128 * 128 * 128 * 4),
        "ssm_state_bytes": 2 * 8 * 12 * 2 * 2 ** 20 + rows * 12 * 2 * 6 * 4096,
        "dense_flops": 2 * 1024 * 3.5e9, "dense_bytes": 2 * 3.5e9}
    for name, most in launched.items():
        value = getattr(c, name)(config, facts)
        assert 0 < value < most, (name, value, most)
    # ISSUE 45: 4 x 68 GFLOP of selected attention a 1,024-row step
    assert 2.6e11 < c.sparse_attn_flops(config, facts) < 2.8e11
    # a decode-only step reads every weight but the embedding's rows once
    # (9.48 GB), the lanes' states twice and what the lanes select
    assert 9.6e9 < c.decode_min_bytes(
        config, dict(facts, ctx_tokens=6 * ctx)) < 10.2e9


def test_the_two_mixes_hold_the_issues_parameters():
    import traffic

    spec = traffic.load("longctx-64k")
    assert {k: spec[k] for k in ("kind", "clients", "prompt_len",
                                 "output_len", "n_strata",
                                 "shared_prefix_len", "order_seed")} == {
        "kind": "closed_loop_ctx", "clients": 8,
        "prompt_len": [16384, 65536], "output_len": [64, 512],
        "n_strata": 32, "shared_prefix_len": 0, "order_seed": 0}
    assert spec["fill_steps"] % 8 == 0 and 280 <= spec["fill_steps"] <= 320
    gen = traffic.make(spec, seed=7, vocab=512, max_seq_tokens=65536)
    prompts = [p for p, _ in gen.deck]
    assert len(gen.deck) == 32 and max(prompts) == 64132
    assert round(float(np.mean(prompts))) == 35453
    assert max(p + o for p, o in gen.deck) <= 65536
    spec = traffic.load("prefix-sessions")
    assert {k: spec[k] for k in spec if k != "why"} == {
        "kind": "closed_loop", "clients": 32, "shared_prefix_len": 1024,
        "shared_prefix_count": 4, "prompt_len": [64, 256],
        "output_len": [16, 64], "n_strata": 64, "order_seed": 0,
        "fill_steps": 80}


def test_a_sparse_layers_qk_norms_are_sharpened_and_the_sizes_stay_tiny():
    import family

    config = _config()
    fam = family.load(config)
    tiny = dict(config, **fam.TINY)
    kinds = fam.mixers(tiny)
    for li, kind in enumerate(kinds):
        layer = fam.layer_params(3, tiny, li)
        want = fam.SHARP_QK if kind == "minicpm4" else 1.0
        for name in ("q_norm", "k_norm"):
            assert abs(float(np.mean(np.asarray(
                layer[name], np.float32))) - want) < 0.1, (li, name)
        assert abs(float(np.mean(np.asarray(
            layer["input_norm"], np.float32))) - 1.0) < 0.1
    # the whole tree and a layer alone are the same bits
    whole = fam.make_params(3, tiny)["layers"][1]
    alone = fam.layer_params(3, tiny, 1)
    assert all(np.array_equal(np.asarray(whole[n], np.float32),
                              np.asarray(alone[n], np.float32))
               for n in alone)
    # the unit, tile and chunk sizes are the rehearsal's only at its widths
    assert fam.model_config(tiny).scan_chunk == fam.TINY_SIZES["scan_chunk"]
    full = fam.model_config(config)
    assert (full.scan_chunk, full.tile_tokens, full.chunk_blocks) == (
        128, 8, 8)
    assert not {"unit_rows", "tile_tokens", "chunk_blocks"} & set(config)


def test_selection_off_attends_every_causal_pair_and_moves_the_rows():
    """``precision="float32-dense"`` (the control run by hand) is the same
    reference with a plain causal softmax in the selection's place: it counts
    every causal pair, leaves the first sparse layer's own rows alone and
    moves every layer's after it."""
    import family

    config = _config()
    fam = family.load(config)
    tiny = dict(config, **fam.TINY)       # topk 2, 1 newest block forced
    n = 256                               # 4 blocks: a late query drops 2
    seq = np.random.default_rng(0).integers(1, tiny["vocab_size"], n).tolist()
    ends = fam.ends_params(5, tiny)
    got = {}
    for precision in ("float32", "float32-dense"):
        rows, pairs = {}, []
        fam.reference.forward(
            [seq], [n], tiny, ends, lambda li: fam.layer_params(5, tiny, li),
            mixers=fam.mixers(tiny), published=fam.published(tiny),
            on_rows=lambda si, li, r: rows.update({li: r}),
            on_pairs=lambda si, li, p: pairs.append(int(p)),
            precision=precision, logit_rows=[[n - 1]])
        got[precision] = rows, pairs
    (sel, sel_pairs), (off, off_pairs) = got["float32"], got["float32-dense"]
    assert off_pairs == [n * (n + 1) // 2] and sel_pairs[0] < off_pairs[0]
    sparse = fam.mixers(tiny).index("minicpm4")
    assert np.array_equal(np.asarray(sel[sparse]["k"]),
                          np.asarray(off[sparse]["k"]))
    after = np.asarray(sel[sparse + 1]["state"]), \
        np.asarray(off[sparse + 1]["state"])
    assert np.linalg.norm(after[0] - after[1]) > 1e-3 * np.linalg.norm(
        after[0])
