"""Chip-spec registry: the single source of truth for hardware ceilings.

Every number the repo used to scatter (bench.py's ``HBM_PEAK_TBPS``
table, per-phase ``hbm_gbps`` recomputations, the analysis pass's
literal ``VMEM_CAPS``) lives here once, so the roofline attribution in
:mod:`~flashinfer_tpu.obs.roofline` and the VMEM-budget lint (L009)
can never disagree about what the hardware can do.

Import contract: **plain data, no side effects**.  This module reads no
env vars and touches no backend at import time — ``analysis/
vmem_budget.py`` imports ``VMEM_CAPS`` from here and must stay usable
in a lint process with no accelerator.  Detection (:func:`detect_chip`
/ :func:`current_spec`) reads ``FLASHINFER_TPU_CHIP`` and the jax
device kind lazily, per call.  A chip that is not in the table is an
error, never a default: plan-time choosers read :func:`current_spec`
for the VMEM budget and the peaks, so a wrong spec is wrong program
behaviour, not just wrong reporting.

Provenance of the numbers:

- HBM peak TB/s: the values bench.py has banked against since round 1
  (v5e 0.819 validated by the 87.6-90.9% decode rows — a wrong peak
  would put measurements over 100%).
- MXU peak TFLOP/s by dtype: published per-chip peaks (v5e 197 bf16 /
  394 int8 — the "197 TFLOP/s chip" every VERDICT MFU number divides
  by; v5p 459/918; v4 275 bf16, no int8 MXU mode → bf16 rate; v6e 918/
  1836).  ``fp8`` maps to the int8 rate where no native fp8 mode
  exists — same MXU width.
- VMEM bytes: compile-budget ceilings, not datasheet capacities —
  v5e 64 MiB is validated by this repo's own kernels (they request
  vmem_limit_bytes=64 MiB and compile for a v5e); v5p carries 2x per
  tuning_configs/v5p.json; v4/v6e conservatively inherit the v5e
  bound.
- ICI GB/s: per-chip aggregate interconnect bandwidth (v4 2400 Gbps,
  v5e 1600, v5p 4800, v6e 3584 — /8 to bytes), for sizing the
  all-reduce terms the single-chip bench excludes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak ceilings for one TPU generation."""

    name: str
    hbm_tbps: float  # peak HBM bandwidth, TB/s
    mxu_tflops: Mapping[str, float]  # dtype -> peak TFLOP/s
    vmem_bytes: int  # compile-budget VMEM ceiling (see module doc)
    ici_gbps: float  # per-chip aggregate ICI bandwidth, GB/s
    hbm_gib: float  # HBM capacity, GiB (fits-in-memory sizing)

    def peak_tflops(self, dtype: str = "bf16") -> float:
        """Peak MXU TFLOP/s for `dtype` (normalized; unknown dtypes
        fall back to the conservative bf16 rate)."""
        return self.mxu_tflops.get(normalize_dtype(dtype),
                                   self.mxu_tflops["bf16"])

    def ridge_intensity(self, dtype: str = "bf16") -> float:
        """The roofline ridge point in FLOPs/byte: arithmetic
        intensities below this are memory-bound on this chip."""
        return self.peak_tflops(dtype) / self.hbm_tbps


_DTYPE_ALIASES = {
    "bfloat16": "bf16", "bf16": "bf16", "float32": "bf16", "f32": "bf16",
    "float16": "bf16", "fp16": "bf16",
    "int8": "int8", "i8": "int8",
    "fp8": "fp8", "float8_e4m3fn": "fp8", "float8_e5m2": "fp8",
    "e4m3": "fp8", "e5m2": "fp8",
}


def normalize_dtype(dtype: str) -> str:
    return _DTYPE_ALIASES.get(str(dtype).lower(), "bf16")


CHIP_SPECS: Dict[str, ChipSpec] = {
    "v4": ChipSpec(
        name="v4", hbm_tbps=1.228,
        mxu_tflops={"bf16": 275.0, "int8": 275.0, "fp8": 275.0},
        vmem_bytes=64 * 1024 * 1024, ici_gbps=300.0, hbm_gib=32.0,
    ),
    "v5e": ChipSpec(
        name="v5e", hbm_tbps=0.819,
        mxu_tflops={"bf16": 197.0, "int8": 394.0, "fp8": 394.0},
        vmem_bytes=64 * 1024 * 1024, ici_gbps=200.0, hbm_gib=16.0,
    ),
    "v5p": ChipSpec(
        name="v5p", hbm_tbps=2.765,
        mxu_tflops={"bf16": 459.0, "int8": 918.0, "fp8": 918.0},
        vmem_bytes=128 * 1024 * 1024, ici_gbps=600.0, hbm_gib=95.0,
    ),
    "v6e": ChipSpec(
        name="v6e", hbm_tbps=1.64,
        mxu_tflops={"bf16": 918.0, "int8": 1836.0, "fp8": 1836.0},
        vmem_bytes=64 * 1024 * 1024, ici_gbps=448.0, hbm_gib=32.0,
    ),
}

# device_kind substrings / user shorthands -> canonical spec name.
# "v5" alone is v5 lite (the device_kind bench.py's matcher saw).
CHIP_ALIASES: Dict[str, str] = {
    "v5": "v5e", "v5litepod": "v5e", "v5e": "v5e",
    "v5p": "v5p", "v4": "v4", "v6e": "v6e", "v6": "v6e",
    "trillium": "v6e",
}

# The chip a process with NO accelerator plans and prices for: the CPU
# test suite, lint, and reports read off-chip.  Never given to a TPU.
DEFAULT_CHIP = "v5e"

# Plain per-generation VMEM compile-budget dict: what analysis/
# vmem_budget.py (L009) imports.  Kept as a dict of ints (not specs) so
# the lint path stays trivially serializable and import-light.
VMEM_CAPS: Dict[str, int] = {
    name: s.vmem_bytes for name, s in CHIP_SPECS.items()
}


def spec(name: str) -> ChipSpec:
    """Spec by canonical name, alias, or device-kind-ish string
    (``"TPU v5 lite"`` -> v5e).  An unknown name raises KeyError:
    giving a chip we do not know another chip's peaks would make every
    number attributed against it wrong in silence."""
    key = str(name).lower().replace(" ", "")
    if key in CHIP_SPECS:
        return CHIP_SPECS[key]
    if key in CHIP_ALIASES:
        return CHIP_SPECS[CHIP_ALIASES[key]]
    # substring match, longest alias first (so "v5p" beats "v5")
    for alias, canon in sorted(CHIP_ALIASES.items(),
                               key=lambda kv: -len(kv[0])):
        if alias in key:
            return CHIP_SPECS[canon]
    raise KeyError(
        f"unknown chip {name!r}: not in hwspec.CHIP_SPECS / CHIP_ALIASES "
        f"({sorted(CHIP_SPECS)}) — add its spec, with the source of its "
        "peaks, before running on it")


def spec_for_peak_tbps(peak: float,
                       rel_tol: float = 0.02) -> Optional[ChipSpec]:
    """Map a banked row's ``peak`` field (HBM TB/s) back to its chip —
    pre-roofline rows carry only that number.  None when nothing is
    within `rel_tol`."""
    try:
        peak = float(peak)
    except (TypeError, ValueError):
        return None
    for s in CHIP_SPECS.values():
        if peak > 0 and abs(s.hbm_tbps - peak) <= rel_tol * s.hbm_tbps:
            return s
    return None


def detect_chip(device_kind: Optional[str] = None) -> str:
    """Canonical chip name: ``FLASHINFER_TPU_CHIP`` env override first
    (works off-accelerator and in CI), else the jax device kind.  A
    device that is no TPU (the CPU the tests run on) plans for
    DEFAULT_CHIP; a TPU whose kind is not in the table raises, and so
    does a backend that fails to start.  Env read and backend touch
    happen HERE, per call — never at import."""
    import os

    override = os.environ.get("FLASHINFER_TPU_CHIP")
    if override:
        return spec(override).name
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    key = str(device_kind).lower().replace(" ", "")
    if "tpu" not in key and not any(a in key for a in CHIP_ALIASES):
        return DEFAULT_CHIP
    return spec(key).name


def current_spec() -> ChipSpec:
    """The spec roofline attribution should run against right now."""
    return CHIP_SPECS[detect_chip()]


def registry_table() -> Tuple[Tuple[str, ...], ...]:
    """(header, *rows) for docs / ``obs perf`` human output."""
    rows = [("chip", "HBM TB/s", "bf16 TFLOP/s", "int8 TFLOP/s",
             "VMEM MiB", "ICI GB/s", "HBM GiB")]
    for name in sorted(CHIP_SPECS):
        s = CHIP_SPECS[name]
        rows.append((
            name, f"{s.hbm_tbps:g}", f"{s.mxu_tflops['bf16']:g}",
            f"{s.mxu_tflops['int8']:g}",
            f"{s.vmem_bytes // (1024 * 1024)}", f"{s.ici_gbps:g}",
            f"{s.hbm_gib:g}",
        ))
    return tuple(rows)
