"""Runtime kernel-parameter autotuner.

TPU re-design of the reference autotuner (``flashinfer/autotuner/
autotuner.py:560-1419`` — TunableRunner interface, ``autotune()`` context,
profiling cache with hardware/version metadata validation).  GPU "tactics"
(kernel template choices) map to Pallas launch parameters: block sizes for
the flash kernel, pages-per-chunk for the decode kernels.  Outside an
``autotune()`` context, cached or default parameters are used with zero
profiling overhead; inside, every new (op, bucketed-shape) key is profiled
once across its candidate set and persisted to a JSON cache keyed by
device kind + library version (invalid on mismatch, like the reference's
metadata validation, autotuner.py:297-382).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import dataclasses

from flashinfer_tpu import env
from flashinfer_tpu.version import __version__


# ---------------------------------------------------------------------------
# Knob registry: the autotuner's first-class tactic surface.  Every op that
# consults the tuner (lookup / choose_one) registers its knob here — name
# and legal value shape — so (a) the
# shipped tuning_configs/*.json files are lint-checkable (analysis pass
# L006 `tuning_schema` rejects stale/misspelled keys at CI time) and (b) a
# corrupt or hand-edited config entry is ignored instead of crashing a
# kernel launch with a nonsense block shape.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KnobSpec:
    """One tunable launch parameter.

    ``arity=0`` means a scalar value; ``arity=n`` a list/tuple of n ints
    (JSON lists round-trip to tuples at lookup).  ``choices`` restricts
    string-valued knobs to an enum."""

    op_name: str
    arity: int = 0
    kind: str = "int"  # "int" | "str"
    choices: Optional[Tuple[str, ...]] = None
    description: str = ""

    def validate(self, value) -> Optional[str]:
        """Error message if `value` is not a legal tactic, else None."""
        if self.arity == 0:
            if self.kind == "str":
                if not isinstance(value, str):
                    return f"expected a string, got {value!r}"
                if self.choices and value not in self.choices:
                    return (f"{value!r} not in allowed choices "
                            f"{list(self.choices)}")
                return None
            if not isinstance(value, int) or isinstance(value, bool) \
                    or value <= 0:
                return f"expected a positive int, got {value!r}"
            return None
        if not isinstance(value, (list, tuple)) or len(value) != self.arity:
            return (f"expected a list of {self.arity} positive ints, "
                    f"got {value!r}")
        for v in value:
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                return (f"expected a list of {self.arity} positive ints, "
                        f"got {value!r}")
        return None


KNOWN_KNOBS: Dict[str, KnobSpec] = {}


def register_knob(op_name: str, *, arity: int = 0, kind: str = "int",
                  choices: Optional[Sequence[str]] = None,
                  description: str = "") -> KnobSpec:
    spec = KnobSpec(op_name, arity, kind,
                    tuple(choices) if choices else None, description)
    KNOWN_KNOBS[op_name] = spec
    return spec


# The registered surface (one entry per choose_one/lookup op name in the
# tree; L006 cross-checks tuning_configs/*.json against this table).
register_knob("rmsnorm.row_block",
              description="rmsnorm Pallas kernel row-block size")
register_knob("fused_add_rmsnorm.row_block",
              description="fused_add_rmsnorm Pallas kernel row-block size")
register_knob("paged_decode.pages_per_chunk",
              description="decode kernel KV pages per DMA chunk")
register_knob("paged_decode.prefetch", kind="str",
              choices=("static", "off"),
              description="decode kernel cross-step prefetch mode")
register_knob("decode.splits",
              description="split-KV decode partition factor per request "
                          "(1 = unsplit; plan-time, overrides the "
                          "cost-model choice — see docs/performance.md)")
register_knob("fused_prefill.blocks", arity=2,
              description="fused work-unit prefill (block_q, "
                          "pages_per_chunk) — the qo-tile/kv-chunk "
                          "shapes of the pipelined mainloop")
register_knob("prefill.fused_ingest", kind="str",
              choices=("off", "on"),
              description="fused prefill INGEST mode (ISSUE 14): 'on' "
                          "folds RoPE + KV-quantize-append into the "
                          "work-unit prefill mainloop "
                          "(ops/paged_prefill.fused_paged_prefill_"
                          "ingest) where geometry allows; absent "
                          "entries default via costmodel."
                          "predict_prefill_ingest_win (>2% predicted "
                          "win required, the choose_decode_splits "
                          "pattern)")
register_knob("flash_attention.blocks", arity=2,
              description="ragged flash kernel (block_q, block_kv) "
                          "grid blocks")
register_knob("moe_gmm.tiles", arity=3,
              description="MoE grouped-matmul (tm, tk, tn) tile shape")
register_knob("mla_decode.layout", kind="str",
              choices=("split", "packed"),
              description="MLA decode scratch layout")
# sharded serving mesh axes (parallel/plan.py plan_axes, shape key
# world_hidden_hq_hkv): dp x tp must equal the world size and tp must
# tile both head counts — invalid entries fall back to the all-tp
# default instead of building an uncompilable mesh
register_knob("parallel.dp",
              description="serving mesh data-parallel axis size "
                          "(batch + page-pool sharding)")
register_knob("parallel.tp",
              description="serving mesh tensor-parallel axis size "
                          "(heads/inter/vocab sharding; must tile "
                          "num_qo_heads and num_kv_heads)")
register_knob("parallel.ep",
              description="expert-parallel factor of the tp axis for "
                          "MoE serving steps (1 = dense; must divide "
                          "parallel.tp — the Mapping moe_ep contract)")
# continuous-batching engine scheduler statics (serve/engine.py,
# EngineConfig.from_knobs; shape key = (hidden, hq, hkv, hd) of the
# served model) — the shape ladder the engine compiles is derived from
# these (powers of two from max_batch; the top rung is max_batch +
# prefill_budget_tokens rounded up to the prefill q tile), so each chip
# generation can trade batch width against the chunked-prefill budget
register_knob("engine.block_size",
              description="serving-engine KV block (page) size in "
                          "tokens — the block-pool / prefix-cache "
                          "sharing granularity (full blocks hash into "
                          "the prefix trie)")
register_knob("engine.prefill_budget_tokens",
              description="chunked-prefill token budget per engine "
                          "step — bounds prefill's latency "
                          "interference on decode lanes; the marginal "
                          "chunk is additionally priced by "
                          "costmodel.predict_step_seconds against "
                          "EngineConfig.slo_step_seconds")
register_knob("engine.max_batch",
              description="serving-engine batch slots (concurrent "
                          "running requests); the decode floor of "
                          "the compile-once rung ladder, and with the "
                          "prefill budget its top rung")
# tiered-KV statics (serve/kv_tier.py; same (hidden, hq, hkv, hd)
# shape key as the other engine.* knobs): whether the host-RAM tier
# below the block pool is attached, how preemption resumes, and how
# much host RAM the tier may hold — each chip generation trades its
# HBM GiB budget against host capacity + restore bandwidth here
register_knob("engine.kv_offload", kind="str",
              choices=("off", "host"),
              description="serving-engine KV offload tier: 'off' = "
                          "device-only (PR 11 behavior), 'host' = "
                          "attach a HostKVStore so preempted/idle "
                          "requests spill their page runs to host RAM "
                          "and restore bit-exactly on resume — "
                          "effective KV capacity exceeds hwspec "
                          "hbm_gib")
register_knob("engine.spill_policy", kind="str",
              choices=("recompute", "spill", "auto"),
              description="preemption resume policy: 'recompute' = "
                          "fold + re-prefill (PR 11), 'spill' = "
                          "always offload to the host tier, 'auto' = "
                          "per-victim cost-model comparison (restore "
                          "bytes over the HBM roofline vs recompute "
                          "FLOPs via predict_step_seconds — the "
                          "choose_decode_splits pattern)")
register_knob("engine.host_gib",
              description="host-RAM KV store capacity in GiB "
                          "(HostKVStore; LRU-evicts spilled entries "
                          "over this budget, downgrading their resume "
                          "to recompute — counted, never silent)")
register_knob("engine.attention_backend", kind="str",
              choices=("reference", "kernel"),
              description="serving-engine attention tier: 'reference' "
                          "= the dense XLA oracle form (bitwise-"
                          "provable, interpret-mode correctness "
                          "anchor), 'kernel' = the Pallas work-unit "
                          "lowering (serve/engine_kernels.py — PR 3 "
                          "prefill mainloop + PR 6 split-KV decode "
                          "composed by the cascade merge)")


def validate_tactic(op_name: str, value) -> Optional[str]:
    """Error message if (op_name, value) is not a registered legal
    tactic; None when valid.  Unknown op names are errors — that is the
    stale-config bug class L006 exists to catch."""
    spec = KNOWN_KNOBS.get(op_name)
    if spec is None:
        return (f"unknown autotuner knob {op_name!r} (registered: "
                f"{sorted(KNOWN_KNOBS)})")
    return spec.validate(value)


def _device_config_key() -> Optional[str]:
    """Normalize ``device_kind`` to a shipped-config file stem.

    The reference ships per-GPU tuned configs (``flashinfer/tuning_configs/``
    keyed by SM arch); the TPU analogue keys on generation: v5e / v5p / v4 /
    v6e."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    if "lite" in kind and "v5" in kind:
        return "v5e"
    if "v6" in kind or "trillium" in kind:
        return "v6e"
    if "v5" in kind:
        return "v5p"
    if "v4" in kind:
        return "v4"
    return None


def _flatten_config(data: dict) -> Dict[str, Any]:
    """Merge a shipped config file's tactic tables.

    Schema: a top-level ``"tactics"`` dict plus any number of named
    SECTIONS — dicts carrying their own ``"tactics"`` (and optionally
    ``"seed": true`` for entries derived off-chip, plus a ``"comment"``).
    Sections group an op family's entries (the ``"prefill"`` section
    feeds the pipelined prefill path; see docs/performance.md) and merge
    after the flat table, so a section entry wins on key collision.
    Entries that fail :func:`validate_tactic` are dropped — a stale or
    hand-mangled config key must not reach a kernel launch (L006 catches
    it at lint time; this is the runtime belt to that suspender)."""
    out: Dict[str, Any] = {}
    tables = [data.get("tactics", {})]
    tables += [sec["tactics"] for key, sec in sorted(data.items())
               if isinstance(sec, dict) and key != "tactics"
               and isinstance(sec.get("tactics"), dict)]
    for table in tables:
        for key, val in table.items():
            op_name = key.split("|", 1)[0]
            if validate_tactic(op_name, val) is None:
                out[key] = val
    return out


class AutoTuner:
    _instance: Optional["AutoTuner"] = None
    _lock = threading.Lock()

    def __init__(self):
        self._cache: Dict[str, Any] = {}
        self._shipped: Dict[str, Any] = {}
        self._loaded = False
        self._tuning_enabled = False

    @classmethod
    def get(cls) -> "AutoTuner":
        with cls._lock:
            if cls._instance is None:
                cls._instance = AutoTuner()
            return cls._instance

    @property
    def tuning_enabled(self) -> bool:
        return self._tuning_enabled

    # ---- persistence -----------------------------------------------------
    def _meta(self) -> Dict[str, str]:
        import jax

        return {
            "version": __version__,
            "device": jax.devices()[0].device_kind,
            "backend": jax.default_backend(),
        }

    def _cache_path(self) -> Path:
        return env.cache_dir() / "autotuner" / "tactics.json"

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        # shipped per-generation defaults (reference tuning_configs/ role):
        # loaded first, overridden by anything the user's own tuning cached.
        # Shape-keyed, version-independent — a library upgrade keeps them.
        try:
            stem = _device_config_key()
        except Exception:
            stem = None
        if stem is not None:
            # package copy first, then a bundle-installed copy in the
            # cache dir (artifacts.unpack_artifacts target) — the
            # bundle is the newer/fleet-specific table, so it wins.
            # Per-file try: a corrupt package JSON must not block the
            # bundle copy the fleet explicitly distributed (or vice
            # versa)
            for root in (
                Path(__file__).parent / "tuning_configs",
                env.cache_dir() / "tuning_configs",
            ):
                try:
                    p = root / f"{stem}.json"
                    if p.is_file():
                        self._shipped.update(
                            _flatten_config(json.loads(p.read_text()))
                        )
                except Exception:
                    pass
        p = self._cache_path()
        try:
            data = json.loads(p.read_text())
            if data.get("meta") == self._meta():
                self._cache = data.get("tactics", {})
        except Exception:
            pass

    def _save(self) -> None:
        from flashinfer_tpu.utils import atomic_write_text

        atomic_write_text(
            self._cache_path(),
            json.dumps({"meta": self._meta(), "tactics": self._cache}, indent=1),
        )

    # ---- tuning ----------------------------------------------------------
    def lookup(self, op_name: str, shape_key: Sequence, default: Any = None) -> Any:
        """Non-profiling fetch: user cache -> shipped config -> default.

        For call sites (e.g. plan()) where profiling is impossible because
        live tensors don't exist yet; ``choose_one`` is the profiling path."""
        from flashinfer_tpu.tactics_blocklist import blocked

        self._load()
        key = f"{op_name}|{'_'.join(map(str, shape_key))}"
        for store in (self._cache, self._shipped):
            if key in store and not blocked(op_name, store[key]):
                val = store[key]
                return tuple(val) if isinstance(val, list) else val
        return default

    def choose_one(
        self,
        op_name: str,
        shape_key: Sequence,
        candidates: Sequence[Any],
        runner: Callable[[Any], Callable[[], Any]],
        default: Any = None,
        module: Any = None,  # kernel module for wedge-quarantine fingerprints
    ) -> Any:
        """Pick the best candidate for (op, shape_key).

        ``runner(candidate)`` returns a nullary callable executing the op
        with that candidate; it is timed with ``block_until_ready``.
        Mirrors ``AutoTuner.choose_one`` (reference autotuner.py:1419)."""
        from flashinfer_tpu.tactics_blocklist import blocked, filter_candidates

        self._load()
        candidates = filter_candidates(op_name, list(candidates))
        key = f"{op_name}|{'_'.join(map(str, shape_key))}"
        if key in self._cache:
            val = self._cache[key]
            # a later-blocklisted cached tactic must not be served
            if not blocked(op_name, val):
                return tuple(val) if isinstance(val, list) else val
            del self._cache[key]
        if not self._tuning_enabled:
            if key in self._shipped and not blocked(op_name, self._shipped[key]):
                val = self._shipped[key]
                return tuple(val) if isinstance(val, list) else val
            return default if default is not None else candidates[0]

        import jax

        from flashinfer_tpu.compile_guard import trace_state_clean

        # called under a jit trace (op embedded in a user model):
        # wall-clock profiling is meaningless there and must not
        # poison the persistent cache
        if not trace_state_clean():
            return default if default is not None else candidates[0]

        from flashinfer_tpu import compile_guard

        best, best_t = None, float("inf")
        for cand in candidates:
            try:
                f = runner(cand)
                # first call runs under the wedge-quarantine marker (a hang
                # while profiling this tactic blocklists it for the next
                # process); the extra warm call keeps compile time and
                # first-run allocator noise out of every timing rep
                # module-inclusive fingerprint: a kernel edit (the fix for a
                # wedge) must automatically clear a tuning-time quarantine
                compile_guard.guarded(
                    op_name, (tuple(map(str, shape_key)), cand), f,
                    module=module,
                )
                jax.block_until_ready(f())
                dt = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(5):
                        out = f()
                    jax.block_until_ready(out)
                    dt = min(dt, (time.perf_counter() - t0) / 5)
            except Exception:
                continue
            if dt < best_t:
                best, best_t = cand, dt
        if best is None:
            best = default if default is not None else candidates[0]
        self._cache[key] = list(best) if isinstance(best, tuple) else best
        self._save()
        return best


@contextlib.contextmanager
def autotune(enable: bool = True):
    """Enable profiling-based tactic selection inside the context
    (reference ``with autotune():`` surface)."""
    t = AutoTuner.get()
    prev = t._tuning_enabled
    t._tuning_enabled = enable
    try:
        yield t
    finally:
        t._tuning_enabled = prev


# ---------------------------------------------------------------------------
# Reference autotuner profile-API surface (flashinfer/autotuner.py).  The
# reference tunes against FAKE tensors described by specs/profiles; this
# tuner times REAL tensors at call sites, so these classes are lightweight
# records that carry the same information into AutoTuner.choose_one keys.
# ---------------------------------------------------------------------------

import dataclasses as _dc
from typing import Callable as _Callable, Tuple as _Tuple


@_dc.dataclass
class Dim:
    """A tensor dimension (reference autotuner.Dim)."""

    value: int = 0


class StaticDim(Dim):
    """Fixed-size dimension."""


@_dc.dataclass
class DynamicDim(Dim):
    """Bucketed dynamic dimension (reference DynamicDim): tuning runs per
    bucket; this package buckets via next-power-of-two shape keys."""

    min: int = 1
    opt: int = 1
    max: int = 1


@_dc.dataclass
class DynamicTensorSpec:
    """Which input dims vary + their bucketing (reference
    DynamicTensorSpec)."""

    input_idx: _Tuple = ()
    dim_idx: _Tuple = ()
    gen_tuning_buckets: object = ()
    map_to_tuning_buckets: object = None


@_dc.dataclass
class ConstraintSpec:
    """Derived-dimension constraint (reference ConstraintSpec)."""

    input_idx: int = 0
    dim_idx: int = 0
    infer_shape: object = None


@_dc.dataclass
class OptimizationProfile:
    """One tuning bucket's concrete shapes (reference
    OptimizationProfile)."""

    shapes: _Tuple = ()


@_dc.dataclass(frozen=True)
class ProfilingCacheKey:
    """Cache key record (reference ProfilingCacheKey); this tuner's keys
    are the `op|shape` strings in tactics.json."""

    op_name: str = ""
    shape_key: str = ""


class FakeTensor:
    """Shape/dtype-only tensor stand-in (reference FakeTensor, used to
    describe profiles without allocating)."""

    def __init__(self, shape=(), dtype=None):
        self.shape = tuple(shape)
        self.dtype = dtype


class TuningConfig:
    """Bundle of dynamic specs + constraints (reference TuningConfig)."""

    def __init__(self, dynamic_tensor_specs=(), constraint_specs=(),
                 **_unused):
        self.dynamic_tensor_specs = tuple(dynamic_tensor_specs)
        self.constraint_specs = tuple(constraint_specs)


class TunableRunner:
    """Base class for tunable op runners (reference TunableRunner): a
    runner exposes candidate tactics and a forward; AutoTuner.choose_one
    times them on the live shapes."""

    def get_valid_tactics(self, inputs, profile) -> list:
        return [-1]

    def forward(self, inputs, tactic: int = -1):
        raise NotImplementedError


class AutoTunerStatistics:
    """Tuning-run counters (reference AutoTunerStatistics)."""

    def __init__(self):
        self.cache_hits = 0
        self.cache_misses = 0
        self.tuned_ops = {}


def autotuner_initializer_empty(shape, dtype):
    import jax.numpy as jnp

    return jnp.empty(shape, dtype)


def autotuner_initializer_ones(shape, dtype):
    import jax.numpy as jnp

    return jnp.ones(shape, dtype)


def autotuner_initializer_rand(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.random.uniform(jax.random.PRNGKey(0), shape).astype(dtype)


def autotuner_initializer_zeros(shape, dtype):
    import jax.numpy as jnp

    return jnp.zeros(shape, dtype)


def autotuner_initializer_randn(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)


def autotuner_initializer_rand_scaled(shape, dtype, scale: float = 1.0):
    return autotuner_initializer_rand(shape, dtype) * scale


def round_to_nearest_bucket(value: int, buckets) -> int:
    """Snap a dynamic dim to its tuning bucket (reference
    round_to_nearest_bucket): smallest bucket >= value, else the max."""
    bs = sorted(int(b) for b in buckets)
    for b in bs:
        if value <= b:
            return b
    return bs[-1] if bs else value


def make_bucket_mapper(buckets):
    """Bucket-mapping closure (reference make_bucket_mapper)."""
    frozen = tuple(sorted(int(b) for b in buckets))

    def mapper(value: int) -> int:
        return round_to_nearest_bucket(value, frozen)

    return mapper


_AUTOTUNE_PROCESS_GROUP = None


def set_autotune_process_group(group) -> None:
    """Reference: a torch.distributed group for sharing tuning results;
    the mesh-wide analogue is the shared tactics.json file, so the group
    handle is recorded but unused."""
    global _AUTOTUNE_PROCESS_GROUP
    _AUTOTUNE_PROCESS_GROUP = group


def get_autotune_process_group():
    return _AUTOTUNE_PROCESS_GROUP


def is_in_profile_measurement() -> bool:
    """True while the tuner is timing candidates (reference
    is_in_profile_measurement) — this tuner times inline, so this is
    simply whether tuning is enabled."""
    return AutoTuner.get().tuning_enabled
