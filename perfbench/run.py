#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one chip, no child process.  The LAST line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, in a traced run ``breakdown``, and last ``compared``: each number
of the check beside its limit, which are also the last lines of standard
error).  Everything else worth
reading is on earlier lines that start with ``[perfbench]``.  Where JAX
finds no TPU the run fails with no result line; ``--tiny`` is the CPU
rehearsal only, and its last line says ``"rehearsal"``.

This file names no cell, no configuration, no traffic mix, no metric and no
block: they are data (``BENCHMARK.json``, ``configs/``, ``traffic/``,
``layers/``) and the configuration's family (``families/``), found by name.
See ``README.md``.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import pathlib
import sys
import time

T_PROCESS = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import family  # noqa: E402  (perfbench's own, pure Python; found through HERE)
import loop  # noqa: E402
import metrics  # noqa: E402
import traffic  # noqa: E402

TRACE_STEPS = 5          # steady steps wrapped in the profiler
TRACE_AT = 0.4           # ... starting at this share of the window
# The check's sample: requests in flight whose cache rows are compared, and
# further finished requests whose tokens are.  A family whose reference is
# slow over its cell's contexts gives its own ``SAMPLE``, so that a whole run
# ends well inside its time limit (PERF.md section 2).
SAMPLE = (4, 4)


def say(msg):
    print(f"[perfbench] {msg}", flush=True)


class CacheCounter:
    """Hits and writes of JAX's persistent compilation cache."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def load_cell(name):
    """(cell, model+engine+sampling config, traffic spec, metric lists)."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no workload {name!r}; there are "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfile = {c["name"]: c["file"] for c in bench["configs"]}[cell["config"]]
    with open(ROOT / cfile) as f:
        config = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return (cell, config, traffic.load(cell["traffic"]),
            mine(bench["end_to_end"]), mine(bench["per_layer"]))


def apply_tiny(config, spec, fam):
    with open(HERE / "tiny.json") as f:
        tiny = json.load(f)
    config = dict(config, **fam.TINY)
    config["engine"] = tiny["engine"]
    return config, dict(spec, **tiny["traffic"])


def warm_rungs(eng, ecfg, vocab, rng, make_request, cache):
    """Serve throw-away requests so that every rung the ladder has is
    traced and compiled before the window: one anchor request keeps one
    decode lane alive, and beside it one prompt per rung of just the size
    that lands the step on that rung."""
    rungs = ecfg.rungs()
    # The anchor's prompt is one page long: a lone fresh prefill whose length
    # is not a multiple of 16 and falls short of its rung trips an assertion
    # in the engine's ingest planner (PERF.md, PR 23), and the cells' traffic
    # never makes such a step (a decode lane is always running).
    first = min(r for r in rungs if r >= 16)
    todo = [r for r in rungs if r != first]
    anchor = make_request({"rid": "warm.anchor",
                           "prompt": rng.integers(1, vocab, 16).tolist(),
                           "output_len": 1 + len(todo)})
    eng.submit(anchor)
    seen = {}

    def step(label):
        t0, h0, w0 = time.perf_counter(), cache.hits, cache.writes
        facts = eng.step()
        seen.setdefault(facts["rung"], time.perf_counter() - t0)
        say(f"warm-up {label}: rung {facts['rung']}, {facts['tokens']} "
            f"tokens, {time.perf_counter() - t0:.1f} s (compile cache: "
            f"{cache.hits - h0} hit(s), {cache.writes - w0} written)")

    step("anchor")
    for rung in todo:
        below = max([r for r in rungs if r < rung], default=0)
        chunk = min(ecfg.prefill_budget_tokens, rung - 1)
        if 1 + chunk <= below:
            raise RuntimeError(f"no step of one lane and one chunk lands on "
                               f"rung {rung}")
        eng.submit(make_request(
            {"rid": f"warm.{rung}",
             "prompt": rng.integers(1, vocab, chunk).tolist(),
             "output_len": 1}))
        step(f"for rung {rung}")
    while eng.has_work():
        step("drain")
    eng.harvest_finished()
    missing = [r for r in rungs if r not in seen]
    if missing:
        raise RuntimeError(f"warm-up did not reach rungs {missing}")
    return seen


def pick_samples(driver, rng, state_rows, ended, sample=SAMPLE):
    """What the check compares: ``sample[0]`` requests in flight at the
    window's close with their cache rows (the longest among them), and
    ``sample[1]`` finished requests of the window with their served tokens
    (the longest among them); the rest drawn from the seed."""
    def pick(cands, size, n):
        cands = sorted(cands, key=size, reverse=True)
        if len(cands) <= n:
            return cands
        rest = cands[1:]
        idx = rng.choice(len(rest), n - 1, replace=False)
        return [cands[0]] + [rest[i] for i in sorted(idx)]

    samples = []
    flying = [r for r in driver.live.values()
              if r.state == "running" and r.kv_len > 0]
    for req in pick(flying, lambda r: r.kv_len, sample[0]):
        rec = driver.records[req.rid]
        seq = rec["prompt"] + list(req.out_tokens)
        n, plen, k = req.kv_len, len(rec["prompt"]), len(req.out_tokens)
        if n > len(seq) or (k and not req.preemptions
                            and n != plen + k - 1):
            raise RuntimeError(
                f"{req.rid}: {n} rows materialized for a {plen}-token "
                f"prompt and {k} served tokens")
        samples.append({
            "rid": req.rid, "tokens": seq[:n],
            "prog_rows": state_rows(req, n),
            "logit_rows": [plen - 1 + j for j in range(k)],
            "served": list(req.out_tokens)})
    done = [r for r in ended if r["tokens"] and not r.get("refused")]
    for rec in pick(done, lambda r: len(r["prompt"]) + len(r["tokens"]),
                    sample[1]):
        plen, k = len(rec["prompt"]), len(rec["tokens"])
        samples.append({
            "rid": rec["rid"],
            "tokens": rec["prompt"] + rec["tokens"][:-1],
            "prog_rows": None,
            "logit_rows": [plen - 1 + j for j in range(k)],
            "served": list(rec["tokens"])})
    return samples


def read_layers(metrics_list, ctx):
    """Per-layer metrics: each has a file ``layers/<name>.json`` that names
    its reader (``readers/<reader>.py``) and the reader's parameters.  A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in metrics_list:
        with open(HERE / "layers" / f"{m['name']}.json") as f:
            spec = json.load(f)
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(ctx, spec)
        if value is None:
            say(f"layer metric {m['name']}: nothing to read")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal sizes; never a result")
    ap.add_argument("--control", default="",
                    help="also read the check's numbers for a control, the "
                         "family's reference in the program's place: a name "
                         "of the family's CONTROLS (int8; the family's "
                         "mechanism switched off), several with commas; 1 "
                         "is the first, int8; not used by the driver")
    ap.add_argument("--keep-gaps", default="",
                    help="write every sampled token's gap, rank and labels, "
                         "and each control's, to this JSON file")
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's files into this directory")
    args = ap.parse_args(argv)

    cell, config, spec, e2e, per_layer = load_cell(args.workload)
    fam = family.load(config)
    import check

    try:
        check.controls_of(fam, args.control)
    except ValueError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    if args.tiny:
        config, spec = apply_tiny(config, spec, fam)
    if args.trace:
        # read lazily by the program, so setting them here is early enough
        os.environ["FLASHINFER_TPU_STEPLOOP"] = "1"
        os.environ["FLASHINFER_TPU_METRICS"] = "1"
        os.environ.setdefault("FLASHINFER_TPU_STEPLOOP_CAP", "262144")

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        print(f"perfbench: JAX found no TPU ({device}); a cell runs on the "
              "chip and does not fall back. --tiny rehearses on the CPU.",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"perfbench: the cell needs {cell['chips']} chip(s), JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 2
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f).get(device["kind"])
    if on_tpu and peaks is None:
        print(f"perfbench: no peaks for device kind {device['kind']!r} in "
              "perfbench/peaks.json", file=sys.stderr)
        return 2

    import numpy as np

    from flashinfer_tpu import env
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)
    from flashinfer_tpu.utils import resolve_backend, use_interpret

    if on_tpu and (use_interpret() or resolve_backend("auto") != "pallas"):
        print("perfbench: FLASHINFER_TPU_INTERPRET / FLASHINFER_TPU_BACKEND "
              "steer the kernels off Mosaic; unset them", file=sys.stderr)
        return 2
    env.enable_compilation_cache()
    cache = CacheCounter()
    say(f"device {device}; jax {jax.__version__}; compile cache at "
        f"{env.xla_cache_dir()}; cell {cell['name']} seed {args.seed} "
        f"window {args.seconds:g} s trace {args.trace}")

    # ---- set-up -----------------------------------------------------------
    t0 = time.perf_counter()
    params = fam.make_params(args.seed, config)
    jax.block_until_ready(params)
    say(f"weights from seed {args.seed}: {time.perf_counter() - t0:.1f} s")
    mcfg = fam.model_config(config)
    # The engine's own sampling seed stays fixed: the engine bakes it into
    # the compiled step as a constant, so a seed that moved with --seed
    # would recompile every rung in every run.  The weights, the prompts and
    # the order of lengths are what --seed changes.
    ecfg = EngineConfig.from_knobs(
        mcfg, **config["engine"], seed=0,
        sampling=SamplingConfig(**config["sampling"]))
    say(f"engine: backend {ecfg.attention_backend}, rungs {ecfg.rungs()}, "
        f"max_batch {ecfg.max_batch}, prefill budget "
        f"{ecfg.prefill_budget_tokens}, page {ecfg.page_size}, pages "
        f"{ecfg.num_pages}, fused_ingest {ecfg.fused_ingest}, kv_offload "
        f"{ecfg.kv_offload}/{ecfg.spill_policy}")
    if on_tpu and ecfg.attention_backend != "kernel":
        raise RuntimeError("the cell is not on the kernel tier")
    eng = ServingEngine(mcfg, params, ecfg)

    def make_request(s):
        return EngineRequest(s["rid"], list(s["prompt"]),
                             max_new_tokens=int(s["output_len"]))

    vocab = config["vocab_size"]
    warm = warm_rungs(eng, ecfg, vocab,
                      np.random.default_rng([args.seed, 0x3A93]),
                      make_request, cache)
    gen = traffic.make(spec, seed=args.seed, vocab=vocab,
                       max_seq_tokens=ecfg.max_seq_tokens)

    usable = ecfg.num_pages - 1

    def per_step(live):
        live = [r for r in live if r.state != "waiting"]
        pages = set()
        for r in live:
            pages.update(r.pages)
        return {"live_pages_share": 100.0 * len(pages) / usable,
                "ctx_tokens": sum(r.kv_len for r in live)}

    tracer = None
    if args.trace:
        import trace_reduce

        tracer = trace_reduce.Tracer(ROOT / ".perfbench_trace", TRACE_STEPS,
                                     TRACE_AT)
    driver = loop.Driver(
        eng, gen, make_request, vocab=vocab,
        per_step=per_step if args.trace else None,
        around_step=tracer.around_step if tracer else None)
    n_fill = driver.fill(int(spec["fill_steps"]))
    setup_s = time.perf_counter() - T_PROCESS
    say(f"set-up {setup_s:.1f} s: every rung warm {sorted(warm)}, "
        f"{gen.clients} clients ran {n_fill} fill step(s)")

    # ---- window -----------------------------------------------------------
    if args.trace:
        from flashinfer_tpu.obs import steploop

        steploop.reset()
    traces0, writes0, hits0 = eng.num_traces, cache.writes, cache.hits
    t_open, t_close, first = driver.window(
        args.seconds,
        hooks=[lambda el: tracer.tick(el, args.seconds)] if tracer else ())
    if tracer:
        tracer.close()
    steps = driver.steps[first:]
    in_window = {"traces_in_window": eng.num_traces - traces0,
                 "compiles_in_window": cache.writes - writes0}
    stats = devices[0].memory_stats() if on_tpu else None
    mem_peak = int(stats["peak_bytes_in_use"]) if stats else 0
    values, counts = metrics.end_to_end(driver.records.values(), t_open,
                                        t_close)
    say(f"window {counts['window_s']:.3f} s, {len(steps)} steps, rungs used "
        f"{sorted({s['rung'] for s in steps})}, {counts['tokens']} tokens, "
        f"{counts['ttft_samples']} first tokens, {counts['gap_samples']} "
        f"gaps, {counts['attempted']} requests ended, {counts['failed']} "
        f"failed; cache hits in window {cache.hits - hits0}; HBM peak "
        f"{mem_peak} bytes")
    say("window values: " + json.dumps({k: v[0] for k, v in values.items()}))
    ended = metrics.ended_in_window(driver.records.values(), t_open, t_close)
    lens = sorted((len(r["prompt"]), r["asked"]) for r in ended)
    say(f"lengths ended (prompt, output): {lens}")
    # ---- layers (traced runs) --------------------------------------------
    ctx = None
    if args.trace:
        ledger = [r for r in steploop.ledger().records() if not r["idle"]]
        busy = [s for s in steps if s["tokens"]]
        if len(ledger) != len(busy):
            raise RuntimeError(f"{len(ledger)} ledger records for "
                               f"{len(busy)} steps")
        for s, rec in zip(busy, ledger):
            s["ledger"] = rec
        ctx = {"steps": steps, "requests": ended, "model": config,
               "family": fam, "values": values, "say": say,
               "peaks": peaks, "trace": tracer.reduce(say, first),
               "admitted": [r for r in driver.records.values()
                            if metrics.in_window(r["t_submit"],
                                                 t_open, t_close)
                            and not r.get("refused")]}
        for r in ctx["admitted"]:   # still in flight: read the live object
            req = driver.live.get(r["rid"])
            if req is not None:
                r["hit_tokens"] = req.hit_tokens
                r["preemptions"] = req.preemptions

    # A run that reads far off has, so far, lost seconds in a few steps
    # (PERF.md section 2): say which, and in a traced run in which of the
    # step's phases, so the next one can be explained.
    def before(i):
        return steps[i - 1]["t1"] if i else t_open

    def told(i):
        s = steps[i]
        rec = s.get("ledger", {})
        spans = {**rec.get("phases", {}), **rec.get("spans", {})}
        top = sorted(spans.items(), key=lambda kv: -kv[1])[:3]
        return (f"{i}, {1e3 * (s['t1'] - s['t0']):.1f}, "
                f"{1e3 * (s['t0'] - before(i)):.1f}, {s['rung']}, "
                f"{s['tokens']}"
                + "".join(f", {n} {1e3 * t:.1f}" for n, t in top))

    slow = sorted(range(len(steps)), reverse=True,
                  key=lambda i: steps[i]["t1"] - before(i))[:3]
    say("longest steps of the window (index, ms in step(), ms in the loop "
        "before it, rung, tokens; traced: its longest phases, ms): "
        + "; ".join(map(told, slow)))

    # ---- check (outside the window, after the program's state is freed) ---
    samples = pick_samples(driver, np.random.default_rng([args.seed, 0xC4EC]),
                           lambda req, n: fam.state_rows(eng, req, n),
                           ended, getattr(fam, "SAMPLE", SAMPLE))
    bad_tokens = sum(not 0 <= t < vocab
                     for r in driver.records.values() for t in r["tokens"])
    eng.caches = None
    del eng, params, driver.eng
    gc.collect()
    t0 = time.perf_counter()
    kept = {"workload": cell["name"], "seed": args.seed,
            "seconds": args.seconds} if args.keep_gaps else None
    numbers, ctl = check.compare(samples, fam, config, args.seed,
                                 top_k=config["sampling"]["top_k"],
                                 control=args.control,
                                 sampling=config["sampling"], kept=kept,
                                 say=say)
    numbers.update(tokens_outside_vocab=bad_tokens, **in_window)
    limit_of = check.limits(fam)
    correct = check.verdict(numbers, limit_of, say)
    if ctl is not None:
        say("control numbers: " + json.dumps(ctl))
        check.control_verdict(ctl, limit_of, say)
    if kept is not None:
        kept["numbers"] = numbers
        path = ROOT / args.keep_gaps
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(kept))
    say(f"check took {time.perf_counter() - t0:.1f} s (not in setup_s)")

    # ---- the result line ---------------------------------------------------
    values["setup_s"] = (setup_s, "s")
    if args.trace:
        line_metrics = read_layers(per_layer, ctx)
    else:
        missing = [m["name"] for m in e2e if m["name"] not in values]
        if missing:
            raise RuntimeError(f"the window gave no sample for {missing}")
        line_metrics = {m["name"]: {"value": values[m["name"]][0],
                                    "unit": m["unit"]} for m in e2e}
    dev = dict(device, memory_peak_bytes=mem_peak)
    result = {"correct": bool(correct), "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": line_metrics,
              "device": dev}
    if args.trace and ctx["trace"]:
        dev["busy_s"] = ctx["trace"]["busy_s"]
        dev["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"][:10],
                               "idle_gaps": ctx["trace"]["idle_gaps"][:10]}
    if args.keep_trace and tracer:
        tracer.keep(ROOT / args.keep_trace)
    result["compared"] = {n: {"value": v, "limit": limit_of[n]}
                          for n, v in numbers.items()}
    if on_tpu:
        print(json.dumps(result), flush=True)
    else:
        print(json.dumps({"rehearsal": "passed" if correct else "FAILED",
                          "would_be": result}), flush=True)
    for n, c in result["compared"].items():
        print(f"compared {n}: {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr)
    return 0 if correct or on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
