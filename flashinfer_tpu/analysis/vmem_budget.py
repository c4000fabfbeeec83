"""L009 — tuning-config block shapes that cannot fit in VMEM.

L006 checks that every ``tuning_configs/*.json`` entry names a
registered knob with a well-formed value; this pass extends it with
the SEMANTIC check: plug the knob's values into the launcher's own
``scratch_shapes`` / BlockSpec expressions, evaluate the VMEM bytes
symbolically, and flag entries whose blocks exceed the launch's VMEM
budget.  A config that cannot compile fails at the worst possible time
— a Mosaic error on the serving host when the shipped tactic table
finally matches a live shape — when the arithmetic was fully decidable
at lint time from (knob value, shape key, kernel source).

Per-knob launch bindings live in ``KNOB_LAUNCHES``: which launcher
function owns the pallas_call, which env names the knob's components
and the shape key's fields bind to.  The evaluator then walks the
launcher body executing simple arithmetic assignments
(``chunk_tokens = pages_per_chunk * page_size``, ``bq = min(block_q,
round_up(total_q, 16))``) and sizes every ``pltpu.VMEM`` scratch plus
every explicitly-shaped BlockSpec block (x2 for the pipeline's double
buffering).  Anything unevaluable contributes zero — the estimate is a
LOWER bound, so a finding is a proof, never a guess.

The budget is the launcher's own declared ``vmem_limit_bytes`` when
statically present (Mosaic enforces it on every platform), else the
per-generation ceiling in ``VMEM_CAPS``.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
from typing import Dict, List, Optional, Sequence

from flashinfer_tpu.analysis.core import (Finding, FunctionInfo,
                                          PallasCallSite, Project,
                                          expr_basename)
from flashinfer_tpu.analysis.tuning_schema import (_config_paths,
                                                   _key_line, _tables)
from flashinfer_tpu.obs.hwspec import VMEM_CAPS

CODE = "L009"

# Per-generation VMEM ceilings (bytes) used when a launch declares no
# vmem_limit_bytes: imported from the chip-spec registry above
# (obs/hwspec.py is plain data with no env/backend reads at import, so
# this lint path stays accelerator-free).  Provenance lives with the
# specs — compile budgets, not datasheet capacities.
_DEFAULT_CAP = 128 * 1024 * 1024

_DTYPE_SIZES = {
    "float64": 8, "int64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "fp8": 1, "e4m3": 1,
}


@dataclasses.dataclass(frozen=True)
class KnobLaunch:
    """How one registered knob binds into its launcher's namespace.

    ``value_names``: env name for each component of the tactic value.
    ``shape_names``: env name for each ``_``-separated field of the
    config key's shape part (None = unused; ``"__dtype__"`` = the
    field is a dtype string setting the default element size).
    ``aliases``: extra env names copied from already-bound ones
    (launcher locals the shape key doesn't spell, e.g. head_dim_vo)."""

    knob: str
    launcher: str
    value_names: Sequence[str]
    shape_names: Sequence[Optional[str]]
    aliases: Dict[str, str] = dataclasses.field(default_factory=dict)


# Sibling registries, same no-silent-skip rule:
# pallas_contract.PLANNER_KERNELS (the L007 plan-array contract) and
# obs/costmodel.COST_LAUNCH_BINDINGS (the L016 parity scenario that
# proves a priced launcher's traffic against its cost family).  A
# launcher whose candidates this registry's L009 proof gates should
# also carry a parity binding — the proof says a tactic FITS, the
# binding says the model PRICING it is honest (L017 checks both).
KNOB_LAUNCHES: Dict[str, KnobLaunch] = {}


def register_knob_launch(kl: KnobLaunch) -> None:
    KNOB_LAUNCHES[kl.knob] = kl


# Knobs with NO KNOB_LAUNCHES binding, waived EXPLICITLY with a reason
# (L013 `registry_coverage`): a registered knob must either carry a
# VMEM-proof binding above or state here why none is needed — the PR 4
# silent-skip extension point closed.  A waiver with an empty reason is
# itself an L013 finding (the L000 rule, applied to registries).
KNOB_WAIVERS: Dict[str, str] = {}


def waive_knob_launch(knob: str, reason: str) -> None:
    KNOB_WAIVERS[knob] = reason


# host-side / scheduler-only knobs: no VMEM launch by design
waive_knob_launch(
    "parallel.dp",
    "mesh axis size — host-side sharding topology, no VMEM launch; "
    "plan_axes falls back on invalid combinations before a mesh exists")
waive_knob_launch(
    "parallel.tp",
    "mesh axis size — host-side sharding topology, no VMEM launch; "
    "plan_axes falls back on invalid combinations before a mesh exists")
waive_knob_launch(
    "parallel.ep",
    "mesh axis factor — host-side sharding topology, no VMEM launch; "
    "plan_axes falls back on invalid combinations before a mesh exists")
waive_knob_launch(
    "engine.block_size",
    "scheduler static (KV page size): feeds EngineKernelGeom, whose "
    "launches are proved via the fused_prefill.blocks / decode.splits "
    "bindings the geometry is clamped to")
waive_knob_launch(
    "engine.prefill_budget_tokens",
    "scheduler budget — host-side admission pricing via "
    "predict_step_seconds, no launch")
waive_knob_launch(
    "engine.max_batch",
    "scheduler static (batch slots / rung-ladder floor) — host-side, "
    "no launch of its own")
waive_knob_launch(
    "engine.kv_offload",
    "host-RAM tier attach switch — host-side page copies only, no "
    "VMEM launch by design")
waive_knob_launch(
    "engine.spill_policy",
    "preemption-resume policy enum — host-side decision, no VMEM "
    "launch by design")
waive_knob_launch(
    "engine.host_gib",
    "host-RAM capacity budget (HostKVStore LRU bound) — host-side, "
    "no VMEM launch by design")
# kernel knobs whose tactic can never launch an infeasible shape
waive_knob_launch(
    "rmsnorm.row_block",
    "scratchless row-block elementwise kernel; the resolver clamps "
    "the tactic to the operand's rows (norm.py _tuned_row_block), so "
    "an oversized entry is clamped, never launched")
waive_knob_launch(
    "fused_add_rmsnorm.row_block",
    "scratchless row-block elementwise kernel; the resolver clamps "
    "the tactic to the operand's rows (norm.py _tuned_row_block), so "
    "an oversized entry is clamped, never launched")
waive_knob_launch(
    "paged_decode.prefetch",
    "string mode knob (static/off cross-step prefetch) — no shape "
    "arithmetic, no VMEM-bearing value")
waive_knob_launch(
    "mla_decode.layout",
    "scratch-LAYOUT enum (split/packed) over a fixed scratch budget — "
    "the layout choice moves no bytes")


# fkey: (batch, tq_pad, num_qo_heads, num_kv_heads, head_dim,
# page_size) — prefill.py fused_key
register_knob_launch(KnobLaunch(
    knob="fused_prefill.blocks",
    launcher="fused_paged_prefill",
    value_names=("block_q", "pages_per_chunk"),
    shape_names=(None, "total_q", "H", "Hkv", "D", "page_size"),
))

# key: (tq_pow2, tkv_pow2, num_qo_heads, num_kv_heads, head_dim,
# dtype, causal) — prefill.py flash_block_key
register_knob_launch(KnobLaunch(
    knob="flash_attention.blocks",
    launcher="flash_attention",
    value_names=("block_q", "block_kv"),
    shape_names=("total_q", "total_kv", "num_qo_heads", "num_kv_heads",
                 "head_dim", "__dtype__", None),
    aliases={"head_dim_vo": "head_dim"},
))

# key: (batch, max_pages, num_qo_heads, num_kv_heads, head_dim,
# page_size, pages_per_chunk, dtype) — ops/paged_decode.py
# decode_split_tactic_key.  The tactic VALUE (num_splits) never enters
# the scratch arithmetic — the split kernel's VMEM footprint is the
# double-buffered (pages_per_chunk, Hkv, PS, D) chunk pair, which the
# key's own fields size — so this binding is the feasibility gate
# plan-time selection composes with (decode.py _split_vmem_feasible):
# a split tactic whose chunk scratch can't compile is pruned before it
# is ever considered.
register_knob_launch(KnobLaunch(
    knob="decode.splits",
    launcher="paged_decode_attention_split",
    value_names=("num_splits",),
    shape_names=("batch", "max_pages", "num_qo_heads", "num_kv_heads",
                 "head_dim", "page_size", "pages_per_chunk",
                 "__dtype__"),
))

# key: (batch, max_pages, num_qo_heads, num_kv_heads, head_dim,
# page_size, dtype) — ops/paged_decode.py decode_tactic_key.  The tactic
# VALUE is the pages-per-chunk itself, which directly sizes the
# double-buffered (2, ppc, Hkv, PS, D) K+V chunk-pair scratch of the
# head-fused HND launch — the unsplit twin of decode.splits (whose key
# carries ppc as a SHAPE field instead).  The launcher's runtime 8 MiB
# clamp lives in paged_decode_attention, upstream of this launch, so
# the proof evaluates the RAW shipped value: an entry this binding
# rejects would only ever run clamped, i.e. the tactic silently would
# not be what the config promised — exactly what L009 must say.
register_knob_launch(KnobLaunch(
    knob="paged_decode.pages_per_chunk",
    launcher="_paged_decode_hnd_launch",
    value_names=("pages_per_chunk",),
    shape_names=("batch", None, "num_qo_heads", "num_kv_heads",
                 "head_dim", "page_size", "__dtype__"),
))

# key: (m, k, n, dtype) — ops/moe_gmm.py tune_tiles / fused_moe.  The
# (tm, tn, tk) tactic sizes the lhs/rhs/out blocks and the f32/int32
# accumulator scratch of the one gmm pallas_call; the quantized-path
# extra scale blocks are tiny and branch-gated, so the evaluator's
# min-merge keeps the estimate a lower bound (L009 semantics).
register_knob_launch(KnobLaunch(
    knob="moe_gmm.tiles",
    launcher="gmm",
    value_names=("tm", "tn", "tk"),
    shape_names=("m", "k", "n", "__dtype__"),
))

# key: (hidden, hq, hkv, hd) — serve/engine.py EngineConfig.from_knobs.
# The engine's KERNEL attention tier launches through
# fused_paged_prefill (both cascade levels) and
# paged_decode_attention_split; the tactic value is the backend NAME
# (string), which never enters scratch arithmetic, and the engine's
# block_q/pages_per_chunk launch statics are derived at engine build
# (serve/engine_kernels.py EngineKernelGeom), so this binding registers
# the launch without a standalone VMEM proof — the compile-feasibility
# gate rides the fused_prefill.blocks and decode.splits bindings the
# engine's geometry is clamped to (the same 512-token chunk / 8 MiB
# double-buffer clamps those knobs' evaluations prove).
register_knob_launch(KnobLaunch(
    knob="engine.attention_backend",
    launcher="fused_paged_prefill",
    value_names=("attention_backend",),
    shape_names=("hidden", "H", "Hkv", "D"),
))

# key: (batch, tq_pad, num_qo_heads, num_kv_heads, head_dim, page_size)
# — prefill.py fused_key, shared with fused_prefill.blocks.  The tactic
# value is the mode STRING ("on"/"off"), which never enters scratch
# arithmetic, and the ingest launcher's block_q/pages_per_chunk arrive
# from the fused_prefill.blocks tactic for the same key — so this
# binding registers the launch (the ISSUE 14 satellite contract) while
# the compile-feasibility proof rides the fused_prefill.blocks
# evaluation of the shared chunk/tile shapes.
register_knob_launch(KnobLaunch(
    knob="prefill.fused_ingest",
    launcher="fused_paged_prefill_ingest",
    value_names=("fused_ingest",),
    shape_names=(None, "total_q", "H", "Hkv", "D", "page_size"),
))


class _Unevaluable(Exception):
    pass


class _Evaluator:
    """Tiny arithmetic interpreter over a known-int environment."""

    _FNS = {
        "min": min, "max": max, "abs": abs, "sum": sum, "int": int,
        "round_up": lambda x, m: -(-x // m) * m,
        "cdiv": lambda a, b: -(-a // b),
        "next_power_of_two": lambda x: 1 << max(int(x) - 1, 0).bit_length(),
    }

    def __init__(self, env: Dict[str, int], default_itemsize: int,
                 dtype_declared: bool = False):
        self.env = dict(env)
        self.default_itemsize = default_itemsize
        # blocks pipeline the operands the config key was looked up
        # with: when the key DECLARED their dtype the default is a
        # proof for them; otherwise (and for `.dtype`-attribute scratch
        # like an int8 KV cache) only 1 byte/element keeps the
        # estimate a lower bound
        self.block_itemsize = default_itemsize if dtype_declared else 1

    def eval(self, expr: ast.expr):
        if isinstance(expr, ast.Constant):
            if isinstance(expr.value, (int, float)) \
                    and not isinstance(expr.value, bool):
                return expr.value
            raise _Unevaluable
        if isinstance(expr, ast.Name):
            if expr.id in self.env and self.env[expr.id] is not None:
                return self.env[expr.id]
            raise _Unevaluable
        if isinstance(expr, ast.BinOp):
            lo, hi = self.eval(expr.left), self.eval(expr.right)
            ops = {ast.Add: lambda: lo + hi, ast.Sub: lambda: lo - hi,
                   ast.Mult: lambda: lo * hi,
                   ast.FloorDiv: lambda: lo // hi,
                   ast.Div: lambda: lo / hi, ast.Mod: lambda: lo % hi,
                   ast.Pow: lambda: lo ** hi,
                   ast.LShift: lambda: lo << hi,
                   ast.RShift: lambda: lo >> hi}
            fn = ops.get(type(expr.op))
            if fn is None or (type(expr.op) in (ast.FloorDiv, ast.Div,
                                                ast.Mod) and not hi):
                raise _Unevaluable
            return fn()
        if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
            return -self.eval(expr.operand)
        if isinstance(expr, ast.Call):
            fn = self._FNS.get(expr_basename(expr.func))
            if fn is None or expr.keywords:
                raise _Unevaluable
            try:
                return fn(*[self.eval(a) for a in expr.args])
            except (TypeError, ValueError, ZeroDivisionError):
                raise _Unevaluable
        if isinstance(expr, ast.IfExp):
            # undecidable test: the smaller branch keeps the total a
            # lower bound, so "cannot fit" stays a proof
            return min(self.eval(expr.body), self.eval(expr.orelse))
        raise _Unevaluable

    def itemsize(self, expr: Optional[ast.expr]) -> int:
        # anything not a literal dtype name charges the MINIMUM real
        # itemsize (1): `k_cache.dtype` may be the int8 low-precision
        # cache even when the op computes in bf16, and over-charging
        # would turn "cannot fit" from a proof into a guess
        if expr is None:
            return 1
        return _DTYPE_SIZES.get(expr_basename(expr), 1)

    def run_body(self, fn: ast.AST) -> None:
        """Execute evaluable straight-line assignments in source order.
        Writes under a conditional (If branch, loop body that may run
        zero times, Try) min-MERGE into the environment — which branch
        runs is undecidable here, and only the smallest value on any
        path keeps "cannot fit" a proof."""
        self._exec_block(fn.body, self.env)

    def _exec_block(self, stmts, env: Dict[str, int]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                saved, self.env = self.env, env
                try:
                    t = stmt.targets[0]
                    if isinstance(t, ast.Name):
                        try:
                            env[t.id] = self.eval(stmt.value)
                        except _Unevaluable:
                            pass
                    elif isinstance(t, ast.Tuple) and all(
                            isinstance(e, ast.Name) for e in t.elts) \
                            and isinstance(stmt.value, ast.Tuple) \
                            and len(stmt.value.elts) == len(t.elts):
                        for e, v in zip(t.elts, stmt.value.elts):
                            try:
                                env[e.id] = self.eval(v)
                            except _Unevaluable:
                                pass
                finally:
                    self.env = saved
            elif isinstance(stmt, (ast.If, ast.For, ast.While,
                                   ast.With, ast.Try)):
                outs = []
                for attr in ("body", "orelse", "finalbody"):
                    block = getattr(stmt, attr, []) or []
                    if block:
                        branch = dict(env)
                        self._exec_block(block, branch)
                        outs.append(branch)
                for branch in outs:
                    for k, v in branch.items():
                        env[k] = min(env[k], v) if k in env else v

    # -- buffer sizing ----------------------------------------------------

    def _shape_bytes(self, shape_expr: ast.expr, itemsize: int) -> int:
        if not isinstance(shape_expr, (ast.Tuple, ast.List)):
            raise _Unevaluable
        total = itemsize
        for dim in shape_expr.elts:
            if isinstance(dim, ast.Constant) and dim.value is None:
                continue  # squeezed block dim
            total *= int(self.eval(dim))
        return total

    def scratch_bytes(self, expr: ast.expr) -> int:
        """pltpu.VMEM((shape), dtype) -> bytes; semaphores/SMEM -> 0."""
        if not isinstance(expr, ast.Call):
            return 0
        base = expr_basename(expr.func)
        if base != "VMEM":
            return 0
        if not expr.args:
            return 0
        try:
            return self._shape_bytes(expr.args[0], self.itemsize(
                expr.args[1] if len(expr.args) > 1 else None))
        except _Unevaluable:
            return 0

    def block_bytes(self, spec: ast.expr) -> int:
        """BlockSpec((block shape), index_map) -> bytes (0 for ANY /
        unshaped specs), x2 for the grid pipeline's double buffering."""
        if not (isinstance(spec, ast.Call)
                and expr_basename(spec.func) == "BlockSpec"):
            return 0
        for k in spec.keywords:
            if k.arg == "memory_space":
                return 0  # ANY/SMEM residents are not VMEM blocks
        if not spec.args:
            return 0
        try:
            return 2 * self._shape_bytes(spec.args[0],
                                         self.block_itemsize)
        except _Unevaluable:
            return 0


def _site_of(project: Project,
             launcher: FunctionInfo) -> Optional[PallasCallSite]:
    for site in project.pallas_sites:
        if site.enclosing is not None \
                and site.enclosing.node is launcher.node:
            return site
    return None


def _estimate(project: Project, kl: KnobLaunch, value, shape_fields):
    """(bytes, budget, detail) for one config entry, or None when the
    launcher/launch cannot be resolved in the analyzed set."""
    launcher = project.resolve_function(kl.launcher)
    if launcher is None:
        return None
    site = _site_of(project, launcher)
    if site is None:
        return None
    env: Dict[str, int] = {}
    itemsize = 2  # bf16 default — the serving dtype
    dtype_declared = False
    vals = value if isinstance(value, (list, tuple)) else [value]
    if len(vals) != len(kl.value_names):
        return None  # arity errors are L006's finding
    for name, v in zip(kl.value_names, vals):
        if isinstance(v, int):
            env[name] = v
    for name, field in zip(kl.shape_names, shape_fields):
        if name is None:
            continue
        if name == "__dtype__":
            if field in _DTYPE_SIZES:
                itemsize = _DTYPE_SIZES[field]
                dtype_declared = True
        else:
            try:
                env[name] = int(field)
            except (TypeError, ValueError):
                pass
    ev = _Evaluator(env, itemsize, dtype_declared=dtype_declared)
    for dst, src in kl.aliases.items():
        if src is not None and src in ev.env:
            ev.env[dst] = ev.env[src]
    ev.run_body(launcher.node)
    total = 0
    for e in site.scratch_exprs or ():
        total += ev.scratch_bytes(e)
    for spec in list(site.in_spec_exprs or []) + list(
            site.out_spec_exprs or []):
        total += ev.block_bytes(spec)
    if total <= 0:
        return None
    budget = site.vmem_limit_bytes
    return total, budget, launcher


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for path in _config_paths(project):
        stem = os.path.splitext(os.path.basename(path))[0]
        platform_cap = VMEM_CAPS.get(stem, _DEFAULT_CAP)
        try:
            with open(path) as fh:
                src = fh.read()
            data = json.loads(src)
        except (OSError, json.JSONDecodeError):
            continue  # L006 already reports unreadable configs
        if not isinstance(data, dict):
            continue
        for _section, table in _tables(data).items():
            if not isinstance(table, dict):
                continue
            for key, value in table.items():
                op_name, sep, shape = key.partition("|")
                kl = KNOB_LAUNCHES.get(op_name)
                if kl is None or not sep:
                    continue
                est = _estimate(project, kl, value, shape.split("_"))
                if est is None:
                    continue
                total, declared, launcher = est
                budget = declared if declared is not None \
                    else platform_cap
                if total > budget:
                    findings.append(Finding(
                        CODE, path, _key_line(src, key), key,
                        f"blocks {value} need >= {total // 1024} KiB of "
                        f"VMEM in '{kl.launcher}' "
                        f"({launcher.file.basename}:"
                        f"{launcher.node.lineno}) but the launch "
                        + (f"declares vmem_limit_bytes="
                           f"{declared // (1024 * 1024)} MiB"
                           if declared is not None else
                           f"budget on {stem} is "
                           f"{budget // (1024 * 1024)} MiB")
                        + " — this tactic can never compile; shrink the "
                        "blocks or delete the entry"))
    return findings
