"""Analytic FLOP / HBM-byte cost model per op family.

The counting half of roofline attribution (Williams et al., CACM 2009):
each public op family gets one formula for total FLOPs and HBM bytes
moved (read/write split), computed from the *plan objects the library
already builds* — fused-prefill work-unit stats report both *launched*
work (what the MXU actually executed, padding included) and *effective*
work (the attended tokens a perfect packing would compute).
:mod:`~flashinfer_tpu.obs.roofline` joins a :class:`Cost` with a
measured wall time and a :class:`~flashinfer_tpu.obs.hwspec.ChipSpec`.

Conventions (pinned by ``tests/test_obs_roofline.py`` against brute-force
tiny-shape counts):

- a multiply-add is 2 FLOPs (matching ``testing.utils.attention_flops``
  and every banked TFLOP/s number);
- bytes are *algorithmic* HBM traffic: every operand read once, every
  output written once, caches at their storage width (quantized-KV
  cost halves/quarters with the byte width) — re-fetch inefficiency is
  what the measured-vs-roofline gap exposes, so it must not be modeled
  away here;
- elementwise/sampling ops count 2 FLOPs/element so intensity stays
  honest-tiny (they are bandwidth attributions, not MFU claims).

Import contract: pure Python, no jax / no env reads — ``obs perf``
runs in CI lint processes, and the zero-overhead test pins that merely
importing ``flashinfer_tpu`` and running ops never loads this module.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Cost:
    """Launched (+ optionally effective) work for one op invocation."""

    flops: float
    bytes_read: float
    bytes_written: float
    # effective (useful) work after padding/pruning waste; None == all
    # launched work was useful
    flops_effective: Optional[float] = None
    dtype: str = "bf16"  # compute dtype -> which MXU peak applies
    op: str = ""
    # per-chip ICI wire bytes (the collective cost family) — a THIRD
    # roofline dimension alongside HBM bytes and FLOPs, priced against
    # hwspec.ici_gbps by obs.roofline.  0 for every single-chip op.
    ici_bytes: float = 0.0

    @property
    def bytes_total(self) -> float:
        return self.bytes_read + self.bytes_written

    @property
    def effective_flops(self) -> float:
        return self.flops if self.flops_effective is None \
            else self.flops_effective

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, FLOPs per HBM byte (launched work)."""
        return self.flops / self.bytes_total if self.bytes_total else 0.0

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(
            flops=self.flops + other.flops,
            bytes_read=self.bytes_read + other.bytes_read,
            bytes_written=self.bytes_written + other.bytes_written,
            flops_effective=self.effective_flops + other.effective_flops
            if (self.flops_effective is not None
                or other.flops_effective is not None) else None,
            dtype=self.dtype, op=self.op or other.op,
            ici_bytes=self.ici_bytes + other.ici_bytes,
        )


def attended_tokens(qo_len: int, kv_len: int, causal: bool = False,
                    window_left: int = -1) -> int:
    """Number of attended (q, kv) pairs for one request — THE counted
    term of every attention formula (bottom-right causal alignment,
    matching testing.utils.attention_ref)."""
    total = 0
    off = kv_len - qo_len
    for qi in range(qo_len):
        hi = min(qi + off, kv_len - 1) if causal else kv_len - 1
        lo = max(qi + off - window_left, 0) if window_left >= 0 else 0
        if hi >= lo:
            total += hi - lo + 1
    return total


def _attended_closed(qo_len: int, kv_len: int, causal: bool) -> float:
    # closed form of attended_tokens for window_left=-1 (the bench
    # shapes) — O(1) so stamping a 16-cell sweep costs nothing
    if causal and qo_len > 1:
        return qo_len * (kv_len - qo_len) + (qo_len * (qo_len + 1)) // 2
    return qo_len * kv_len


def attention(qo_len: int, kv_len: int, num_qo_heads: int,
              num_kv_heads: int, head_dim_qk: int,
              head_dim_vo: Optional[int] = None, *, causal: bool = False,
              batch: int = 1, q_bytes: int = 2, kv_bytes: int = 2,
              out_bytes: int = 2, dtype: str = "bf16") -> Cost:
    """Generic (ragged/flash/single/decode) attention: QK^T + PV FLOPs,
    q+k+v read / o written once.  ``kv_bytes`` carries the quantized-KV
    byte width (int8 cache -> 1, fp8 -> 1)."""
    dvo = head_dim_qk if head_dim_vo is None else head_dim_vo
    att = _attended_closed(qo_len, kv_len, causal)
    return Cost(
        flops=2.0 * batch * att * num_qo_heads * (head_dim_qk + dvo),
        bytes_read=float(batch) * (
            qo_len * num_qo_heads * head_dim_qk * q_bytes
            + kv_len * num_kv_heads * (head_dim_qk + dvo) * kv_bytes),
        bytes_written=float(batch) * qo_len * num_qo_heads * dvo
        * out_bytes,
        dtype=dtype, op="attention",
    )


def paged_decode(batch: int, ctx: int, num_qo_heads: int,
                 num_kv_heads: int, head_dim: int, *, kv_bytes: int = 2,
                 q_bytes: int = 2, dtype: str = "bf16") -> Cost:
    """Batched paged-KV decode: one query token per request streams the
    whole cache — the bandwidth-bound headline op."""
    c = attention(1, ctx, num_qo_heads, num_kv_heads, head_dim,
                  causal=False, batch=batch, q_bytes=q_bytes,
                  kv_bytes=kv_bytes, dtype=dtype)
    return dataclasses.replace(c, op="paged_decode")


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def split_chunk_pages(page_size: int, num_kv_heads: int, head_dim: int,
                      itemsize: int = 2) -> int:
    """Pages-per-chunk of the split-KV decode path — MUST equal
    ``ops/paged_decode.split_pages_per_chunk`` (duplicated because this
    module stays jax-free by import contract; equality is pinned by
    tests/test_split_decode.py)."""
    ppc = max(1, min(512 // page_size, 16))
    per_page = 4 * num_kv_heads * page_size * head_dim * itemsize
    return max(1, min(ppc, (8 << 20) // per_page))


def decode_split_breakdown(
    batch: int, ctx: int, num_qo_heads: int, num_kv_heads: int,
    head_dim: int, *, num_splits: int, page_size: int = 16,
    pages_per_chunk: Optional[int] = None, kv_bytes: int = 2,
    q_bytes: int = 2, out_bytes: int = 2, lse_lanes: int = 128,
) -> Dict[str, float]:
    """Traffic/shape breakdown of split-KV decode at one split factor —
    the terms :func:`decode_split` sums and the bench stamp's
    ``merge_bytes`` field.  Mirrors ``build_decode_split_units``
    geometry exactly: per-request span ``per = ceil(pages/S)`` rounded
    up to whole DMA chunks, so sub-chunk splits degenerate into empty
    units (their kernel grid steps still write identity partials, which
    the merge traffic charges).

    Keys: ``kv_bytes`` (cache streamed once — splits are disjoint),
    ``q_bytes`` (one padded-group q-block fetch per unit),
    ``merge_bytes`` (f32 partial out+lse written by the kernel and read
    back by merge_states; 0 at S=1), ``out_bytes`` (merged output),
    ``units_real``/``units_total``, ``max_chunks_per_unit``,
    ``kv_tokens_launched`` (whole-chunk walks incl. the masked tail)."""
    S = int(num_splits)
    ppc = pages_per_chunk if pages_per_chunk else split_chunk_pages(
        page_size, num_kv_heads, head_dim, kv_bytes)
    chunk_tokens = ppc * page_size
    pages = _cdiv(max(ctx, 1), page_size)
    per = _cdiv(_cdiv(max(pages, 1), S), ppc) * ppc
    units_real = 0
    max_chunks = 0
    kv_launched = 0
    for s in range(S):
        start = s * per * page_size
        uk = min(start + per * page_size, ctx) - start
        if uk <= 0:
            continue
        units_real += 1
        c = _cdiv(uk, chunk_tokens)
        max_chunks = max(max_chunks, c)
        kv_launched += c * chunk_tokens
    group = num_qo_heads // max(num_kv_heads, 1)
    gp = _cdiv(max(group, 1), 8) * 8
    partial_elems = (float(batch) * S * num_kv_heads * gp
                     * (head_dim + lse_lanes))
    return {
        "kv_bytes": float(batch) * ctx * num_kv_heads * head_dim * 2
        * kv_bytes,
        "q_bytes": float(batch) * units_real * num_kv_heads * gp
        * head_dim * q_bytes,
        "merge_bytes": 2.0 * 4.0 * partial_elems if S > 1 else 0.0,
        "out_bytes": float(batch) * num_qo_heads * head_dim * out_bytes,
        "units_real": units_real,
        "units_total": S,
        "max_chunks_per_unit": max_chunks,
        "kv_tokens_launched": float(batch) * kv_launched,
    }


def decode_split(batch: int, ctx: int, num_qo_heads: int,
                 num_kv_heads: int, head_dim: int, *, num_splits: int,
                 page_size: int = 16,
                 pages_per_chunk: Optional[int] = None,
                 kv_bytes: int = 2, q_bytes: int = 2,
                 dtype: str = "bf16") -> Cost:
    """Split-KV paged decode: S partial passes + the merge reduction.
    At ``num_splits=1`` this is exactly :func:`paged_decode` (no
    partials exist).  Launched FLOPs count the whole-chunk KV walk
    (masked tails included); effective FLOPs count the attended
    tokens — the launched/effective gap is the split padding waste."""
    if num_splits <= 1:
        return dataclasses.replace(
            paged_decode(batch, ctx, num_qo_heads, num_kv_heads,
                         head_dim, kv_bytes=kv_bytes, q_bytes=q_bytes,
                         dtype=dtype),
            op="decode_split")
    bd = decode_split_breakdown(
        batch, ctx, num_qo_heads, num_kv_heads, head_dim,
        num_splits=num_splits, page_size=page_size,
        pages_per_chunk=pages_per_chunk, kv_bytes=kv_bytes,
        q_bytes=q_bytes)
    per_tok = 2.0 * num_qo_heads * (head_dim + head_dim)
    merge_elems = bd["merge_bytes"] / (2.0 * 4.0)
    return Cost(
        flops=bd["kv_tokens_launched"] * per_tok + 2.0 * merge_elems,
        flops_effective=float(batch) * ctx * per_tok,
        bytes_read=bd["kv_bytes"] + bd["q_bytes"]
        + bd["merge_bytes"] / 2.0,
        bytes_written=bd["merge_bytes"] / 2.0 + bd["out_bytes"],
        dtype=dtype, op="decode_split",
    )


# per-grid-step fixed overhead of the split predictor's stall model
# (DMA issue + epilogue per work unit) — a committed estimate pending
# on-chip calibration; the DECISIONS it drives (S>1 on short-ctx/
# large-batch, S=1 on long-ctx) are pinned by tests/test_split_decode.py
DECODE_UNIT_OVERHEAD_S = 0.3e-6


def predict_decode_seconds(batch: int, ctx: int, num_qo_heads: int,
                           num_kv_heads: int, head_dim: int, *,
                           num_splits: int, hbm_tbps: float,
                           page_size: int = 16,
                           pages_per_chunk: Optional[int] = None,
                           kv_bytes: int = 2) -> float:
    """Predicted wall time of one decode step at a candidate split
    factor: roofline transfer time of the algorithmic traffic, plus a
    cold-start stall term — each multi-chunk work unit (and each
    request of the unsplit kernel) exposes one chunk's DMA before its
    double buffer fills, while an all-single-chunk unit stream is
    cross-unit prefetched and exposes none — plus a per-unit fixed
    overhead.  This is the invert-the-cost-model selection rule
    (ROADMAP item 5): the same physics ``obs perf`` attributes with,
    used *forward* at plan time."""
    ppc = pages_per_chunk if pages_per_chunk else split_chunk_pages(
        page_size, num_kv_heads, head_dim, kv_bytes)
    cost = decode_split(
        batch, ctx, num_qo_heads, num_kv_heads, head_dim,
        num_splits=num_splits, page_size=page_size, pages_per_chunk=ppc,
        kv_bytes=kv_bytes)
    bd = decode_split_breakdown(
        batch, ctx, num_qo_heads, num_kv_heads, head_dim,
        num_splits=num_splits, page_size=page_size, pages_per_chunk=ppc,
        kv_bytes=kv_bytes)
    bw = hbm_tbps * 1e12
    chunk_bytes = (min(ppc * page_size, max(ctx, 1)) * num_kv_heads
                   * head_dim * 2 * kv_bytes)
    if num_splits <= 1:
        exposed = batch  # one cold start per request
        units = batch
    elif bd["max_chunks_per_unit"] <= 1:
        exposed = 0  # cross-unit double buffer: no cold start anywhere
        units = batch * bd["units_total"]
    else:
        exposed = batch * bd["units_real"]
        units = batch * bd["units_total"]
    return (cost.bytes_total / bw + exposed * chunk_bytes / bw
            + units * DECODE_UNIT_OVERHEAD_S)


def _quarantined(op_name: str, tactic) -> bool:
    """True when the bring-up quarantine blocklists (op, tactic) — the
    ISSUE 20 wedge-attribution plumbing.  Lazy import and never-raise:
    the import contract above stays intact (tactics_blocklist only
    loads when a chooser actually runs), and a broken quarantine file
    must not take the chooser down with it."""
    try:
        from flashinfer_tpu import tactics_blocklist

        return tactics_blocklist.blocked(op_name, tactic)
    except Exception:
        return False


def choose_decode_splits(batch: int, ctx: int, num_qo_heads: int,
                         num_kv_heads: int, head_dim: int, *,
                         hbm_tbps: float, page_size: int = 16,
                         pages_per_chunk: Optional[int] = None,
                         kv_bytes: int = 2,
                         candidates: Tuple[int, ...] = (1, 2, 4, 8),
                         feasible=None) -> Tuple[int, Dict[int, dict]]:
    """Plan-time split-factor selection: predict each candidate S with
    :func:`predict_decode_seconds`, drop candidates ``feasible``
    rejects (the L009 VMEM-feasibility evaluator at the decode.py call
    site), and return ``(best_S, table)`` where ``table[S]`` carries
    the predicted seconds / bytes / intensity evidence.  A larger S
    must beat the incumbent by >2% predicted time — on ties (e.g. a
    sub-chunk split degenerating to the same real partition) the
    smaller S wins, so S=1 stays the default wherever splitting has
    nothing to remove.

    Candidates the bring-up quarantine names (a smoke-ladder rung that
    wedged the chip on this (op, tactic) pair — ISSUE 20) are pruned
    the same way ``feasible`` rejections are: S=1 always survives, so
    a fully quarantined sweep degrades to unsplit, never to a wedge."""
    best, best_t = 1, None
    table: Dict[int, dict] = {}
    for S in sorted(set(int(s) for s in candidates)):
        if S < 1:
            continue
        if S > 1 and feasible is not None and not feasible(S):
            continue
        if S > 1 and _quarantined("decode.splits", S):
            continue
        cost = decode_split(
            batch, ctx, num_qo_heads, num_kv_heads, head_dim,
            num_splits=S, page_size=page_size,
            pages_per_chunk=pages_per_chunk, kv_bytes=kv_bytes)
        t = predict_decode_seconds(
            batch, ctx, num_qo_heads, num_kv_heads, head_dim,
            num_splits=S, hbm_tbps=hbm_tbps, page_size=page_size,
            pages_per_chunk=pages_per_chunk, kv_bytes=kv_bytes)
        table[S] = {
            "seconds": t, "bytes": cost.bytes_total,
            "intensity": cost.intensity,
        }
        if best_t is None or t < best_t * 0.98:
            best, best_t = S, t
    return best, table


def mla_decode(batch: int, ctx: int, num_heads: int, *,
               latent_dim: int = 512, rope_dim: int = 64,
               lane_pad: int = 128, cache_bytes: int = 2,
               q_bytes: int = 2, out_bytes: int = 2,
               dtype: str = "bf16") -> Cost:
    """MLA absorbed decode (DeepSeek ckv 512 + kpe 64): the latent cache
    is read ONCE for all heads (the MLA memory win); the TPU kpe layout
    is lane-padded to `lane_pad` columns, so cache bytes charge
    ``latent_dim + lane_pad`` per token — the padding is real HBM
    traffic.  FLOPs count the live dims only: q.k over
    (latent+rope) and p.v over latent."""
    att = float(batch) * ctx * num_heads
    return Cost(
        flops=2.0 * att * ((latent_dim + rope_dim) + latent_dim),
        bytes_read=(
            float(batch) * ctx * (latent_dim + lane_pad) * cache_bytes
            + batch * num_heads * (latent_dim + rope_dim) * q_bytes),
        bytes_written=float(batch) * num_heads * latent_dim * out_bytes,
        dtype=dtype, op="mla_decode",
    )


def fused_prefill_from_stats(
    stats: Mapping[str, int], *, block_q: int, pages_per_chunk: int,
    page_size: int, num_qo_heads: int, num_kv_heads: int, head_dim: int,
    total_q: int, q_bytes: int = 2, kv_bytes: int = 2,
    out_bytes: int = 2, dtype: str = "bf16",
) -> Cost:
    """Launched + effective work of the pipelined work-unit prefill,
    straight from the plan's post-pruning/post-packing ``stats`` (PR 3:
    ``mxu_cells_total`` = every (q-row, kv-col) MXU position the real
    units execute; ``mxu_cells_valid`` = the in-bounds ones).  The gap
    IS the padding waste ``plan.padding_waste_pct`` histograms."""
    chunk_tokens = pages_per_chunk * page_size
    per_cell = 2.0 * num_qo_heads * (head_dim + head_dim)
    return Cost(
        flops=stats["mxu_cells_total"] * per_cell,
        flops_effective=stats["mxu_cells_valid"] * per_cell,
        # q is fetched once per packed tile (the double-buffered qslot
        # stream); k+v stream once per unit chunk
        bytes_read=(
            stats["tiles"] * block_q * num_qo_heads * head_dim * q_bytes
            + stats["units"] * chunk_tokens * num_kv_heads
            * (head_dim + head_dim) * kv_bytes),
        bytes_written=float(total_q) * num_qo_heads * head_dim
        * out_bytes,
        dtype=dtype, op="fused_prefill",
    )


def paged_prefill(batch: int, qo_len: int, kv_len: int,
                  num_qo_heads: int, num_kv_heads: int, head_dim: int,
                  *, causal: bool = True, stats: Optional[Mapping] = None,
                  block_q: Optional[int] = None,
                  pages_per_chunk: Optional[int] = None,
                  page_size: int = 16, q_bytes: int = 2,
                  kv_bytes: int = 2, dtype: str = "bf16") -> Cost:
    """Batch chunked paged prefill.  With live plan ``stats`` (the
    fused backend) the launched work comes from the work-unit grid and
    the effective work from the attended tokens; without (gather
    fallback, or a banked row reconstructed from config alone) the
    cost is effective-only."""
    eff = attention(qo_len, kv_len, num_qo_heads, num_kv_heads,
                    head_dim, causal=causal, batch=batch,
                    q_bytes=q_bytes, kv_bytes=kv_bytes, dtype=dtype)
    if stats is None or block_q is None or pages_per_chunk is None:
        return dataclasses.replace(eff, op="paged_prefill")
    launched = fused_prefill_from_stats(
        stats, block_q=block_q, pages_per_chunk=pages_per_chunk,
        page_size=page_size, num_qo_heads=num_qo_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        total_q=batch * qo_len, q_bytes=q_bytes, kv_bytes=kv_bytes,
        dtype=dtype)
    return dataclasses.replace(launched, flops_effective=eff.flops,
                               op="paged_prefill")


def prefill_ingest_breakdown(
    total_q: int, total_kv: int, num_qo_heads: int, num_kv_heads: int,
    head_dim: int, *, q_bytes: int = 2, kv_bytes: int = 2,
    cache_bytes: int = 2, out_bytes: int = 2,
) -> Dict[str, float]:
    """Algorithmic HBM traffic of the prefill INGEST fusion vs the
    separate-op composition (ISSUE 14): per the module convention these
    are algorithmic bytes — every operand read once, outputs written
    once — so the avoided terms are exactly the round trips the fusion
    structurally removes, independent of kernel re-streaming (which the
    measured-vs-roofline gap exposes separately).

    Separate path (rope -> quantize-append -> attention re-read):

    - rope_q: read raw q + write rotated q            = 2 Q
    - rope_k: read raw k + write rotated k            = 2 K
    - append: read rotated k + raw v, write cache     = K + V + Kc + Vc
    - attention: read rotated q + cache k/v, write o  = Q + Kc + Vc + O

    Fused path: read raw q + raw k + raw v, write cache + o.

    Avoided = 2 Q + 2 K + Kc + Vc — the rope round trips and the
    attention's cache re-read (the quantize-append write survives: the
    cache must exist for decode either way)."""
    Q = float(total_q) * num_qo_heads * head_dim * q_bytes
    K = float(total_kv) * num_kv_heads * head_dim * kv_bytes
    V = K
    Kc = float(total_kv) * num_kv_heads * head_dim * cache_bytes
    Vc = Kc
    O = float(total_q) * num_qo_heads * head_dim * out_bytes
    separate = 3 * Q + 3 * K + V + 2 * Kc + 2 * Vc + O
    fused = Q + K + V + Kc + Vc + O
    return {
        "separate_bytes": separate,
        "fused_bytes": fused,
        "bytes_avoided": separate - fused,
        "avoided_fraction": (separate - fused) / separate if separate
        else 0.0,
        # per-launch traffic of the separate composition (the chooser
        # prices these as SEQUENTIAL memory passes — rope and append
        # are elementwise and cannot hide under attention's MXU floor)
        "rope_bytes": 2 * Q + 2 * K,
        "append_bytes": K + V + Kc + Vc,
        "attention_bytes": Q + Kc + Vc + O,
    }


def prefill_ingest(
    total_q: int, total_kv: int, num_qo_heads: int, num_kv_heads: int,
    head_dim: int, *, causal: bool = True,
    stats: Optional[Mapping] = None, block_q: Optional[int] = None,
    pages_per_chunk: Optional[int] = None, page_size: int = 16,
    q_bytes: int = 2, kv_bytes: int = 2, cache_bytes: int = 2,
    out_bytes: int = 2, dtype: str = "bf16",
) -> Cost:
    """The fused prefill-ingest launch's cost: attention FLOPs (plus
    the ~6 FLOP/element rotation and 2 FLOP/element quantize riding
    in-register) over ONE raw q/k/v read + one quantized-page write +
    the output.  With live plan ``stats`` the launched work comes from
    the real work-unit grid (``fused_prefill_from_stats`` MXU cells;
    raw chunks stream once per unit, finished pages write once per
    ``ingest_chunks`` owner) and effective work is the attended pairs;
    without, the cost is the algorithmic fused-path traffic."""
    att = attention(total_q, total_kv, num_qo_heads, num_kv_heads,
                    head_dim, causal=causal, q_bytes=q_bytes,
                    kv_bytes=kv_bytes, out_bytes=out_bytes, dtype=dtype)
    rope_flops = 6.0 * (total_q * num_qo_heads
                        + total_kv * num_kv_heads) * head_dim
    quant_flops = 2.0 * 2.0 * total_kv * num_kv_heads * head_dim
    bd = prefill_ingest_breakdown(
        total_q, total_kv, num_qo_heads, num_kv_heads, head_dim,
        q_bytes=q_bytes, kv_bytes=kv_bytes, cache_bytes=cache_bytes,
        out_bytes=out_bytes)
    cache_w = 2.0 * total_kv * num_kv_heads * head_dim * cache_bytes
    out_w = float(total_q) * num_qo_heads * head_dim * out_bytes
    if stats is not None and block_q and pages_per_chunk:
        chunk_tokens = pages_per_chunk * page_size
        per_cell = 2.0 * num_qo_heads * (head_dim + head_dim)
        flops = (stats["mxu_cells_total"] * per_cell + rope_flops
                 + quant_flops)
        # effective follows the fused_prefill_from_stats convention:
        # the in-bounds MXU cells (plus the rotate/quantize work,
        # which is useful on every real row) — never att.flops, whose
        # causal accounting can exceed a tightly-pruned launch
        effective = (stats["mxu_cells_valid"] * per_cell + rope_flops
                     + quant_flops)
        reads = (
            stats["tiles"] * block_q * num_qo_heads * head_dim * q_bytes
            + stats["units"] * chunk_tokens * num_kv_heads
            * (head_dim + head_dim) * kv_bytes)
        return Cost(
            flops=flops, flops_effective=min(effective, flops),
            bytes_read=reads, bytes_written=cache_w + out_w,
            dtype=dtype, op="prefill_ingest")
    return Cost(
        flops=att.flops + rope_flops + quant_flops,
        flops_effective=att.flops,
        bytes_read=bd["fused_bytes"] - cache_w - out_w,
        bytes_written=cache_w + out_w,
        dtype=dtype, op="prefill_ingest")


def prefill_ingest_separate(
    total_q: int, total_kv: int, num_qo_heads: int, num_kv_heads: int,
    head_dim: int, *, causal: bool = True, q_bytes: int = 2,
    kv_bytes: int = 2, cache_bytes: int = 2, out_bytes: int = 2,
    dtype: str = "bf16",
) -> Cost:
    """The separate-op composition (rope → quantize-append → attention)
    priced at the SAME ``prefill_ingest`` op family as the fused launch
    — the A/B's separate-mode rows.  FLOPs are identical (the same
    rotate/quantize/attend work executes, just split over three
    launches); bytes are the three-pass traffic
    :func:`prefill_ingest_breakdown` itemizes (``separate_bytes``), so
    a separate-mode row's roofline fraction rates the composition
    against what it actually moved, not attention alone."""
    att = attention(total_q, total_kv, num_qo_heads, num_kv_heads,
                    head_dim, causal=causal, q_bytes=q_bytes,
                    kv_bytes=kv_bytes, out_bytes=out_bytes, dtype=dtype)
    rope_flops = 6.0 * (total_q * num_qo_heads
                        + total_kv * num_kv_heads) * head_dim
    quant_flops = 2.0 * 2.0 * total_kv * num_kv_heads * head_dim
    Q = float(total_q) * num_qo_heads * head_dim * q_bytes
    K = float(total_kv) * num_kv_heads * head_dim * kv_bytes
    V = K
    Kc = float(total_kv) * num_kv_heads * head_dim * cache_bytes
    Vc = Kc
    O = float(total_q) * num_qo_heads * head_dim * out_bytes
    # rope reads Q+K / writes Q+K; append reads K+V / writes Kc+Vc;
    # attention reads Q+Kc+Vc / writes O  (sum == separate_bytes)
    return Cost(
        flops=att.flops + rope_flops + quant_flops,
        flops_effective=att.flops,
        bytes_read=2 * Q + 2 * K + V + Kc + Vc,
        bytes_written=Q + K + Kc + Vc + O,
        dtype=dtype, op="prefill_ingest")


def predict_prefill_ingest_win(
    total_q: int, total_kv: int, num_qo_heads: int, num_kv_heads: int,
    head_dim: int, *, hbm_tbps: float, peak_tflops: float = 0.0,
    causal: bool = True, q_bytes: int = 2, kv_bytes: int = 2,
    cache_bytes: int = 2, feasible=None,
) -> Tuple[bool, Dict[str, float]]:
    """Plan-time fused-ingest selection (the ``choose_decode_splits``
    pattern, ISSUE 14): roofline-forward seconds of the separate-op
    composition vs the fused launch; fused must beat separate by >2%
    predicted time or the knob default stays OFF — ties and noise keep
    the proven composition.

    The separate path is THREE sequential launches — rope and
    quantize-append are elementwise memory passes that cannot hide
    under the attention launch's MXU floor — so it is priced as
    ``rope_bytes/bw + append_bytes/bw + max(attention_bytes/bw,
    t_flops)``, while the fused launch overlaps everything under one
    roofline (the rotation/quantize FLOPs ride the VPU inside the DMA
    shadow).  Compute-bound shapes therefore still show the win of the
    two deleted memory passes; tiny shapes where everything rounds to
    noise keep the proven composition via the 2% bar.  ``feasible``
    is the L009 VMEM-feasibility evaluator of the fused launch at the
    caller's shape (the ``choose_decode_splits`` prune applied to a
    two-candidate choice): when it rejects, the fused candidate is
    pruned before the roofline race and the proven separate
    composition wins unconditionally.  Returns ``(use_fused,
    evidence_table)``."""
    bd = prefill_ingest_breakdown(
        total_q, total_kv, num_qo_heads, num_kv_heads, head_dim,
        q_bytes=q_bytes, kv_bytes=kv_bytes, cache_bytes=cache_bytes)
    if feasible is not None and not feasible():
        # fused scratch does not fit VMEM at this shape: candidate
        # pruned pre-pricing, evidence records why OFF was forced
        return False, {
            "separate_s": 0.0, "fused_s": 0.0,
            "bytes_avoided": bd["bytes_avoided"],
            "avoided_fraction": bd["avoided_fraction"],
            "pruned_infeasible": 1.0,
        }
    if _quarantined("prefill.fused_ingest", "on"):
        # a bring-up smoke-ladder rung wedged the chip on the fused
        # launch (ISSUE 20): the proven separate composition wins
        # unconditionally until the quarantine is lifted
        return False, {
            "separate_s": 0.0, "fused_s": 0.0,
            "bytes_avoided": bd["bytes_avoided"],
            "avoided_fraction": bd["avoided_fraction"],
            "pruned_quarantined": 1.0,
        }
    att = attention(total_q, total_kv, num_qo_heads, num_kv_heads,
                    head_dim, causal=causal)
    bw = hbm_tbps * 1e12
    t_flops = (att.flops / (peak_tflops * 1e12)) if peak_tflops > 0 \
        else 0.0
    t_sep = (bd["rope_bytes"] / bw + bd["append_bytes"] / bw
             + max(bd["attention_bytes"] / bw, t_flops))
    t_fused = max(bd["fused_bytes"] / bw, t_flops)
    use = t_fused < t_sep * 0.98
    return use, {
        "separate_s": t_sep, "fused_s": t_fused,
        "bytes_avoided": bd["bytes_avoided"],
        "avoided_fraction": bd["avoided_fraction"],
    }


def moe_gmm(tokens: int, num_experts: int, hidden: int, inter: int,
            top_k: int, *, weight_bytes: int = 2, act_bytes: int = 2,
            experts_loaded: Optional[int] = None,
            dtype: str = "bf16") -> Cost:
    """Fused MoE (gate/up + down GEMMs over routed tokens).  Per-expert
    token loads: each ACTIVE expert's weight block is streamed once per
    launch (``experts_loaded``, default every expert hot — the bench
    regime where tokens*top_k >> experts); routed activations are
    gathered in and scattered out per (token, choice)."""
    if experts_loaded is None:
        experts_loaded = min(num_experts, tokens * top_k)
    per_tok = hidden * 2 * inter + inter * hidden  # both GEMMs, madd=2
    return Cost(
        flops=2.0 * tokens * top_k * per_tok,
        bytes_read=(
            float(experts_loaded) * (hidden * 2 * inter + inter * hidden)
            * weight_bytes
            + tokens * hidden * act_bytes  # x in
            + tokens * top_k * (hidden + 2 * inter) * act_bytes),
        bytes_written=(
            float(tokens) * top_k * hidden * act_bytes  # expert outs
            + tokens * hidden * act_bytes),  # combined y
        dtype=dtype, op="moe_gmm",
    )


def gemm(m: int, n: int, k: int, *, a_bytes: int = 2, b_bytes: int = 2,
         out_bytes: int = 2, dtype: str = "bf16") -> Cost:
    return Cost(
        flops=2.0 * m * n * k,
        bytes_read=float(m) * k * a_bytes + float(k) * n * b_bytes,
        bytes_written=float(m) * n * out_bytes, dtype=dtype, op="gemm",
    )


def sampling(batch: int, vocab: int, *, probs_bytes: int = 4) -> Cost:
    """Categorical sampling / filtering over the full distribution:
    one pass over [batch, vocab] probs, a few tokens out."""
    return Cost(
        flops=2.0 * batch * vocab,
        bytes_read=float(batch) * vocab * probs_bytes,
        bytes_written=float(batch) * 4, op="sampling",
    )


def topk(batch: int, vocab: int, k: int = 0, *,
         score_bytes: int = 4) -> Cost:
    """Exact top-k over [batch, vocab] scores: the lower-bound traffic
    is one read of the score matrix + k indices/values out."""
    return Cost(
        flops=2.0 * batch * vocab,
        bytes_read=float(batch) * vocab * score_bytes,
        bytes_written=float(batch) * max(k, 1) * 8, op="topk",
    )


def elementwise(elements: int, *, reads_per: int = 1, writes_per: int = 1,
                bytes_per: int = 2, flops_per: float = 2.0,
                op: str = "elementwise") -> Cost:
    """Gated activations / masks / casts: pure bandwidth."""
    return Cost(
        flops=flops_per * elements,
        bytes_read=float(elements) * reads_per * bytes_per,
        bytes_written=float(elements) * writes_per * bytes_per, op=op,
    )


def norm(tokens: int, hidden: int, *, bytes_per: int = 2,
         fused_residual: bool = False) -> Cost:
    """RMS-norm family: read x (+ residual) + weight, write out
    (+ residual); ~4 FLOPs/element (square, sum, rsqrt-mul, scale)."""
    n = tokens * hidden
    extra = n if fused_residual else 0
    return Cost(
        flops=4.0 * n,
        bytes_read=float(n + extra + hidden) * bytes_per,
        bytes_written=float(n + extra) * bytes_per, op="norm",
    )


def rope(tokens: int, num_heads: int, head_dim: int, *,
         bytes_per: int = 2, quantize_out_bytes: Optional[int] = None
         ) -> Cost:
    """Rotary embedding over q/k rows: read + write each element, ~6
    FLOPs/element (two muls + add per rotated pair, cos/sin amortized);
    the fp8-quantizing variants write at the narrow width."""
    n = tokens * num_heads * head_dim
    wb = bytes_per if quantize_out_bytes is None else quantize_out_bytes
    return Cost(flops=6.0 * n, bytes_read=float(n) * bytes_per,
                bytes_written=float(n) * wb, op="rope")


def page_append(tokens: int, num_kv_heads: int, head_dim: int, *,
                kv_bytes: int = 2, in_bytes: int = 2) -> Cost:
    """append_paged_kv_cache: read the new k+v rows, scatter them into
    the paged cache at the cache's storage width."""
    n = tokens * num_kv_heads * head_dim * 2  # k and v
    return Cost(flops=2.0 * n, bytes_read=float(n) * in_bytes,
                bytes_written=float(n) * kv_bytes, op="page_append")


# -- linear-attention / SSM families (bench.py phase_scans) ---------------


def state_decode(batch: int, num_heads: int, dk: int, dv: int, *,
                 state_bytes: int = 4) -> Cost:
    """One decode step of a state-space / linear-attention model: the
    [heads, dk, dv] f32 state is read + written once per token (the
    bandwidth bound the no-kernel verdicts divide by)."""
    n = batch * num_heads * dk * dv
    return Cost(flops=4.0 * n, bytes_read=float(n) * state_bytes,
                bytes_written=float(n) * state_bytes, op="state_decode")


def ssd_prefill(batch: int, seqlen: int, num_heads: int, head_dim: int,
                state_dim: int, *, chunk: int = 64,
                act_bytes: int = 4) -> Cost:
    """Mamba-2 chunked SSD prefill: intra-chunk scores [Q,Q] via C.B
    plus the state outer products (the bench.py formula, now shared)."""
    flops = (2.0 * batch * seqlen * chunk * num_heads
             * (state_dim + head_dim)
             + 2.0 * batch * seqlen * num_heads * head_dim * state_dim)
    n_io = batch * seqlen * num_heads * head_dim
    return Cost(flops=flops, bytes_read=float(n_io) * act_bytes * 2,
                bytes_written=float(n_io) * act_bytes, op="ssd_prefill")


def gated_delta_prefill(batch: int, seqlen: int, num_heads: int,
                        dk: int, dv: int, *, act_bytes: int = 4) -> Cost:
    """GDN / KDA chunked prefill: state in/out matmuls per token."""
    n_io = batch * seqlen * num_heads * (dk + dv)
    return Cost(flops=2.0 * batch * seqlen * num_heads * (dk * dv * 2),
                bytes_read=float(n_io) * act_bytes,
                bytes_written=float(batch) * seqlen * num_heads * dv
                * act_bytes, op="gated_delta_prefill")


# -- serving decode step (bench.py phase_serving int8 shard pipeline) -----

# dims of the per-chip tp=8 70B shard bench.py measures; keyed by the
# row's `model` field so `obs perf` can attribute banked rows that
# predate cost stamping
SERVING_SHAPES: Dict[str, Dict[str, int]] = {
    "llama70b_tp8shard_int8": dict(
        hidden=8192, hq=8, hkv=1, hd=128, inter=3584, vocab_shard=16032,
        page_size=16, weight_bytes=1, kv_bytes=1,
    ),
}

SERVING_PHASES = ("norm_rope", "attention", "kv_append", "moe_or_mlp",
                  "lm_head", "sampling")


def serving_phase_costs(bs: int, ctx: int, layers: int, *, hidden: int,
                        hq: int, hkv: int, hd: int, inter: int,
                        vocab_shard: int, page_size: int = 16,
                        weight_bytes: int = 1, kv_bytes: int = 1,
                        act_bytes: int = 2) -> Dict[str, Cost]:
    """Per-step cost of each serving-loop phase (the SAME names the
    ``overhead_decomposition`` row and profiler scopes use), aggregated
    over `layers`.  int8-weight GEMMs -> dtype int8."""
    qdim, kvdim = hq * hd, hkv * hd
    L = float(layers)

    def lg(m, n, k):  # one int8 GEMM per layer, activations int8 in
        return dataclasses.replace(
            gemm(m, n, k, a_bytes=1, b_bytes=weight_bytes,
                 out_bytes=act_bytes), dtype="int8")

    attn = (lg(bs, qdim + 2 * kvdim, hidden) + lg(bs, hidden, qdim)
            + dataclasses.replace(
                paged_decode(bs, ctx, hq, hkv, hd, kv_bytes=kv_bytes),
                dtype="int8"))
    mlp = lg(bs, 2 * inter, hidden) + lg(bs, hidden, inter)
    nr = (norm(bs, hidden) + norm(bs, hidden)
          + rope(bs, hq + hkv, hd))
    costs = {
        "norm_rope": _scale(nr, L),
        "attention": _scale(attn, L),
        "kv_append": _scale(
            page_append(bs, hkv, hd, kv_bytes=kv_bytes), L),
        "moe_or_mlp": _scale(mlp, L),
        "lm_head": dataclasses.replace(
            norm(bs, hidden) + lg(bs, vocab_shard, hidden),
            dtype="int8"),
        "sampling": sampling(bs, vocab_shard),
    }
    return costs


def _scale(c: Cost, k: float) -> Cost:
    return dataclasses.replace(
        c, flops=c.flops * k, bytes_read=c.bytes_read * k,
        bytes_written=c.bytes_written * k,
        flops_effective=None if c.flops_effective is None
        else c.flops_effective * k,
        ici_bytes=c.ici_bytes * k)


def serving_step(bs: int, ctx: int, layers: int, *,
                 include_kv_append: bool = True,
                 include_sampling: bool = True, **shape) -> Cost:
    """Whole decode step of the int8 shard pipeline: sum of phases
    (the slope-fit row excludes kv_append + sampling, mirroring what
    it measures)."""
    phases = serving_phase_costs(bs, ctx, layers, **shape)
    total = None
    for name in SERVING_PHASES:
        if name == "kv_append" and not include_kv_append:
            continue
        if name == "sampling" and not include_sampling:
            continue
        total = phases[name] if total is None else total + phases[name]
    return dataclasses.replace(total, dtype="int8", op="serving_step")


def engine_step(num_tokens: int, batch: int, layers: int, *, hidden: int,
                inter: int, hq: int, hkv: int, hd: int, vocab: int,
                kv_tokens: float, kv_rows: Optional[float] = None,
                kv_bytes: int = 2, weight_bytes: int = 2,
                act_bytes: int = 2, dtype: str = "bf16",
                kv_pairs_launched: Optional[float] = None,
                kv_rows_launched: Optional[float] = None) -> Cost:
    """One continuous-batching ENGINE step (serve/engine.py): mixed
    decode + chunked-prefill tokens on one flat axis.

    Counted terms, per layer x ``layers``:

    - projections / MLP / norms / rope / KV append over ``num_tokens``
      flat tokens (q/k/v, o, gate/up/down GEMMs; weights stream once
      per step);
    - attention FLOPs over ``kv_tokens`` attended (query, kv) pairs —
      the scheduler passes the EXACT per-token window sums (a decode
      lane contributes ``kv_len + 1``, a prefill chunk
      ``chunk*kv_before + chunk(chunk+1)/2``), so admission pricing
      sees real traffic, not a shape bound;
    - attention KV BYTES over ``kv_rows`` streamed cache rows (default
      ``kv_tokens``).  A caller that dedupes shared-prefix reads — the
      cascade level-0 group gather reads a shared page run ONCE per
      group instead of once per request — passes the deduped row count
      here, making the prefix-cache HBM win visible to ``obs perf``.
      FLOPs are never deduped (every query still multiplies the shared
      keys).

    Plus the lm_head + per-lane sampling epilogue over ``batch`` lanes.
    The engine's FLOPs-avoided metering prices skipped prefill spans
    with this same formula (``ServingEngine._prefill_cost_flops``).

    Launched-vs-effective (the KERNEL attention backend): when the
    engine runs the Pallas work-unit tier, ``kv_pairs_launched`` /
    ``kv_rows_launched`` carry the REAL unit stats — the work units
    each launch runs (a prefill-level launch runs its plan's real units,
    never the pad units of the rung's cap), each a whole tile against a
    whole chunk-aligned page walk (``ServingEngine.unit_stats``).  A
    plan holds a unit only where its launch has work: a span with
    nothing to attend adds none to a tile that holds another (a tile of
    such spans keeps ONE zero-write unit, priced like any other), and a
    decode lane alone in its group is priced as DECODE pages over its
    whole context (``decode_lanes_whole``), no longer as one-row prefill
    units over its prompt plus decode pages over the suffix.  A prompt
    chunk alone in its group is priced as the same units it always was,
    run by level 1's launch over its whole context
    (``prefill_tokens_whole``: its rows inside the span) in place of
    level 0's.  The
    attention term then prices ``flops`` from launched pairs and
    ``bytes`` from launched rows, with ``flops_effective`` holding the
    exact attended-pair work, so ``obs perf`` exposes the tier's true
    padding waste instead of the dense window the reference tier
    attends through.  Left ``None``
    (the reference tier, and every pre-graduation caller) the formula
    is unchanged: launched == effective attended pairs."""
    qdim, kvdim = hq * hd, hkv * hd
    L = float(layers)
    if kv_rows is None:
        kv_rows = kv_tokens

    def g(m, n, k):
        return gemm(m, n, k, a_bytes=act_bytes, b_bytes=weight_bytes,
                    out_bytes=act_bytes, dtype=dtype)

    per_layer = (g(num_tokens, qdim + 2 * kvdim, hidden)
                 + g(num_tokens, hidden, qdim)
                 + g(num_tokens, 2 * inter, hidden)
                 + g(num_tokens, hidden, inter)
                 + norm(num_tokens, hidden, bytes_per=act_bytes)
                 + norm(num_tokens, hidden, bytes_per=act_bytes)
                 + rope(num_tokens, hq + hkv, hd, bytes_per=act_bytes)
                 + page_append(num_tokens, hkv, hd, kv_bytes=kv_bytes))
    attn_pairs = (kv_tokens if kv_pairs_launched is None
                  else kv_pairs_launched)
    attn_rows = kv_rows if kv_rows_launched is None else kv_rows_launched
    attn = Cost(
        flops=2.0 * attn_pairs * hq * (2 * hd),
        bytes_read=(num_tokens * hq * hd * act_bytes
                    + attn_rows * hkv * (2 * hd) * kv_bytes),
        bytes_written=float(num_tokens) * hq * hd * act_bytes,
        flops_effective=(None if kv_pairs_launched is None
                         else 2.0 * kv_tokens * hq * (2 * hd)),
        dtype=dtype, op="engine_attention",
    )
    total = _scale(per_layer, L) + _scale(attn, L)
    total = total + g(batch, vocab, hidden) + sampling(batch, vocab)
    return dataclasses.replace(total, dtype=dtype, op="engine_step")


# -- tiered-KV family (serve/kv_tier.py: host offload + disagg handoff) ---


def kv_page_bytes(pages: int, *, page_size: int, num_kv_heads: int,
                  head_dim: int, layers: int, kv_bytes: int = 2) -> float:
    """Payload bytes of one request's KV page run across all layers —
    the counted term of every tier movement (spill, restore, migrate):
    K and V planes, ``pages * page_size`` rows of ``num_kv_heads *
    head_dim`` lanes at the cache's storage width (quantized caches
    move at 1 byte/element — the compressed wire/host format)."""
    return (2.0 * layers * pages * page_size * num_kv_heads * head_dim
            * kv_bytes)


def kv_page_io(pages: int, *, page_size: int, num_kv_heads: int,
               head_dim: int, layers: int, kv_bytes: int = 2,
               direction: str = "spill", dtype: str = "bf16") -> Cost:
    """One host-tier page movement (``HostKVStore``): ``spill`` reads
    the page run out of HBM (the host-RAM write is not HBM traffic),
    ``restore`` writes it back.  Zero FLOPs — the tier moves bytes, it
    computes nothing — so attribution is pure bandwidth.  The cost
    family of the ``engine.kv_spill`` / ``engine.kv_restore`` ops."""
    if direction not in ("spill", "restore"):
        raise ValueError(f"direction must be spill|restore, "
                         f"got {direction!r}")
    payload = kv_page_bytes(pages, page_size=page_size,
                            num_kv_heads=num_kv_heads, head_dim=head_dim,
                            layers=layers, kv_bytes=kv_bytes)
    return Cost(
        flops=0.0,
        bytes_read=payload if direction == "spill" else 0.0,
        bytes_written=payload if direction == "restore" else 0.0,
        dtype=dtype, op="kv_page_io",
    )


def kv_migrate(tokens: Optional[int] = None, *, pages: Optional[int] = None,
               page_size: int = 16, num_kv_heads: int, head_dim: int,
               layers: int, kv_bytes: int = 2, hops: int = 1,
               dtype: str = "bf16") -> Cost:
    """One prefill-pool -> decode-pool KV handoff (the disaggregated
    serving collective, ``engine.kv_migrate``): the finished prefill's
    page run crosses the ICI once per hop — point-to-point, so wire
    bytes equal the payload (no ring (p-1)/p discount; a multi-hop
    route multiplies).  The HBM legs are real on both ends: the source
    chip reads the run out, the destination writes it in.  Per-request
    page-run x kv-byte-width wire formula — what
    ``roofline.predict_serving_ici`` prices per chip generation and the
    ``serving_disagg`` bench phase stamps on migration rows
    (``bound == "ici"`` wherever the interconnect is the deepest
    floor, which it is on every registered chip)."""
    if pages is None:
        if tokens is None:
            raise ValueError("kv_migrate needs tokens or pages")
        pages = _cdiv(max(int(tokens), 1), page_size)
    payload = kv_page_bytes(pages, page_size=page_size,
                            num_kv_heads=num_kv_heads, head_dim=head_dim,
                            layers=layers, kv_bytes=kv_bytes)
    return Cost(
        flops=0.0, bytes_read=payload, bytes_written=payload,
        ici_bytes=payload * max(int(hops), 1),
        dtype=dtype, op="kv_migrate",
    )


# -- ICI collective family (the sharded serving step's third dimension) ----

# wire bytes each chip moves per payload byte for the canonical ring
# algorithms over p ranks (scaling-book formulas; p=1 moves nothing):
# allreduce = reduce-scatter + all-gather = 2(p-1)/p; gather/scatter
# each (p-1)/p; all_to_all keeps 1/p local and sends the rest.
_COLLECTIVE_WIRE_FACTOR = {
    "allreduce": 2.0, "allgather": 1.0, "reducescatter": 1.0,
    "alltoall": 1.0,
}


def collective(kind: str, payload_bytes: float, axis_size: int, *,
               op: str = "") -> Cost:
    """Per-chip cost of ONE collective over `axis_size` ranks.

    Only the ICI dimension is charged: the payload's HBM staging traffic
    already belongs to the producing op's write and the consuming op's
    read (charging it again here would double-count the phase's HBM
    bytes), and reduction adds are ~1 FLOP/element — noise against the
    GEMMs they join, so FLOPs stay 0 to keep MFU honest."""
    p = max(int(axis_size), 1)
    factor = _COLLECTIVE_WIRE_FACTOR[kind]
    wire = factor * (p - 1) / p * float(payload_bytes) if p > 1 else 0.0
    return Cost(flops=0.0, bytes_read=0.0, bytes_written=0.0,
                ici_bytes=wire, op=op or kind)


def tp_allreduce(tokens: int, hidden: int, tp_size: int, *,
                 act_bytes: int = 2) -> Cost:
    """One TP partial-sum combine of a [tokens, hidden] activation (the
    o_proj / down_proj epilogue — 2 of these per decoder layer)."""
    return collective("allreduce", float(tokens) * hidden * act_bytes,
                      tp_size, op="tp_allreduce")


def ep_all_to_all(tokens: int, hidden: int, top_k: int, ep_size: int, *,
                  act_bytes: int = 2) -> Cost:
    """EP token exchange for one MoE layer: dispatch + combine, each an
    all_to_all of the routed (token, choice) activations — the
    ``fused_moe_ep`` "alltoall" mode's O(T*K*hidden) traffic (balanced
    routing; capacity overflow rounds add multiples of this)."""
    payload = float(tokens) * max(top_k, 1) * hidden * act_bytes
    a2a = collective("alltoall", payload, ep_size, op="ep_all_to_all")
    return dataclasses.replace(a2a, ici_bytes=2.0 * a2a.ici_bytes,
                               op="ep_all_to_all")


def sampling_gather(batch_local: int, vocab: int, tp_size: int, *,
                    dp_size: int = 1, logits_bytes: int = 4) -> Cost:
    """The sampling epilogue's gathers, per chip: the replicated-
    sampler contract (parallel/plan.py) all-gathers the vocab-sharded
    logits over tp AND the batch-sharded logits over dp, so every chip
    holds the FULL [batch, vocab] f32 distribution before sampling
    (this jax's threefry is not partitionable — a sharded sampler
    would fork the random stream).  ``batch_local`` is the per-dp-shard
    batch; the dp leg gathers all ``batch_local * dp`` rows."""
    g_tp = collective("allgather",
                      float(batch_local) * vocab * logits_bytes,
                      tp_size, op="sampling_gather")
    g_dp = collective("allgather",
                      float(batch_local) * dp_size * vocab * logits_bytes,
                      dp_size, op="sampling_gather")
    return dataclasses.replace(g_tp + g_dp, op="sampling_gather")


# GLOBAL dims of the sharded serving pipeline (the whole model, not the
# per-chip shard): tp8 of this entry IS SERVING_SHAPES'
# "llama70b_tp8shard_int8" (hq 64/8=8, hkv 8/8=1, inter 28672/8=3584,
# vocab 128256/8=16032 — pinned by tests/test_sharded_step.py)
SHARDED_SERVING_SHAPES: Dict[str, Dict[str, int]] = {
    "llama70b_int8": dict(
        hidden=8192, hq=64, hkv=8, hd=128, inter=28672,
        vocab_shard=128256, page_size=16, weight_bytes=1, kv_bytes=1,
    ),
}


def serving_phase_costs_sharded(
    bs: int, ctx: int, layers: int, *, dp: int = 1, tp: int = 1,
    ep: int = 1, moe_top_k: int = 0, hidden: int, hq: int, hkv: int,
    hd: int, inter: int, vocab_shard: int, page_size: int = 16,
    weight_bytes: int = 1, kv_bytes: int = 1, act_bytes: int = 2,
) -> Dict[str, Cost]:
    """PER-CHIP cost of each serving phase on a (dp, tp[, ep]) mesh,
    from GLOBAL model dims: the single-chip formulas at the local shard
    dims (batch/dp, heads+inter+vocab/tp — exactly the per-chip shard
    bench.py measures at tp8), plus the collective family per phase:

    - ``attention``  += one TP allreduce per layer (o_proj combine);
    - ``moe_or_mlp`` += one TP allreduce per layer (down combine) and,
      when ``moe_top_k > 0`` and ``ep > 1``, the EP all-to-all pair;
    - ``sampling``   += the vocab all-gather (+ dp token exchange).

    ``tp=dp=1`` degenerates exactly to :func:`serving_phase_costs` —
    the single-chip model is the mesh model's fixed point."""
    if hq % tp or hkv % tp or inter % tp or vocab_shard % tp or bs % dp:
        raise ValueError(
            f"global dims (hq {hq}, hkv {hkv}, inter {inter}, vocab "
            f"{vocab_shard}, bs {bs}) do not tile (dp {dp}, tp {tp})")
    bs_l = bs // dp
    costs = serving_phase_costs(
        bs_l, ctx, layers, hidden=hidden, hq=hq // tp, hkv=hkv // tp,
        hd=hd, inter=inter // tp, vocab_shard=vocab_shard // tp,
        page_size=page_size, weight_bytes=weight_bytes,
        kv_bytes=kv_bytes, act_bytes=act_bytes)
    L = float(layers)
    ar = _scale(tp_allreduce(bs_l, hidden, tp, act_bytes=act_bytes), L)
    costs["attention"] = costs["attention"] + ar
    costs["moe_or_mlp"] = costs["moe_or_mlp"] + ar
    if moe_top_k > 0 and ep > 1:
        costs["moe_or_mlp"] = costs["moe_or_mlp"] + _scale(
            ep_all_to_all(bs_l, hidden, moe_top_k, ep,
                          act_bytes=act_bytes), L)
    costs["sampling"] = costs["sampling"] + sampling_gather(
        bs_l, vocab_shard, tp, dp_size=dp)
    return costs


def serving_step_sharded(bs: int, ctx: int, layers: int, *, dp: int = 1,
                         tp: int = 1, ep: int = 1, moe_top_k: int = 0,
                         **shape) -> Cost:
    """Whole per-chip sharded decode step: phase sum with the
    collective ICI bytes folded in (nothing excluded — the fused
    sharded step dispatches kv_append and sampling too).  The cost
    family of the ``parallel.sharded_step`` public op."""
    phases = serving_phase_costs_sharded(
        bs, ctx, layers, dp=dp, tp=tp, ep=ep, moe_top_k=moe_top_k,
        **shape)
    total = None
    for name in SERVING_PHASES:
        total = phases[name] if total is None else total + phases[name]
    return dataclasses.replace(total, dtype="int8",
                               op="serving_step_sharded")


def predict_step_seconds(cost: Cost, *, hbm_tbps: float,
                         peak_tflops: float, ici_gbps: float) -> float:
    """Roofline-forward prediction of one step's wall time on one chip
    of a mesh: HBM and MXU floors overlap (the deeper one binds), the
    ICI floor adds serially — collectives on the serving critical path
    overlap poorly with the dependent compute that waits on them (the
    conservative no-overlap model; same physics ``obs perf`` attributes
    with, used forward like ``predict_decode_seconds``)."""
    t_mem = cost.bytes_total / (hbm_tbps * 1e12)
    t_comp = cost.flops / (peak_tflops * 1e12)
    t_ici = cost.ici_bytes / (ici_gbps * 1e9) if ici_gbps > 0 else 0.0
    return max(t_mem, t_comp) + t_ici


# -- @flashinfer_api coverage (obs doctor) --------------------------------

# decorated public op -> cost-model family (a function in this module).
# `obs doctor` lists API_OPS absent here, mirroring L005's obs-coverage
# idea: a new public op cannot silently ship roofline-unattributable.
API_OP_COSTS: Dict[str, str] = {
    "silu_and_mul": "elementwise", "gelu_and_mul": "elementwise",
    "gelu_tanh_and_mul": "elementwise",
    "rmsnorm": "norm", "gemma_rmsnorm": "norm",
    "fused_add_rmsnorm": "norm", "gemma_fused_add_rmsnorm": "norm",
    "apply_rope": "rope", "apply_llama31_rope": "rope",
    "rope_quantize_fp8": "rope", "mla_rope_quantize_fp8": "rope",
    "rope_quantize_fp8_append_paged_kv_cache": "rope",
    "append_paged_kv_cache": "page_append",
    "single_decode_with_kv_cache": "attention",
    "single_prefill_with_kv_cache": "attention",
    "sampling_from_probs": "sampling", "sampling_from_logits": "sampling",
    "top_p_sampling_from_probs": "sampling",
    "top_k_sampling_from_probs": "sampling",
    "min_p_sampling_from_probs": "sampling",
    "top_k_top_p_sampling_from_probs": "sampling",
    # the mesh step: the phase-sum model (norm_rope + attention +
    # kv_append + moe_or_mlp + lm_head + sampling) + the collective ICI
    # family (tp allreduces, optional EP all-to-all, sampling gather)
    "parallel.sharded_step": "serving_step_sharded",
    # the continuous-batching engine step: mixed decode + chunked
    # prefill on one flat axis, exact attended-pair accounting and a
    # deduped shared-prefix KV-row term (the cascade level-0 gather)
    "engine.step": "engine_step",
    # the tiered-KV subsystem (serve/kv_tier.py): host-RAM page
    # movements are pure-bandwidth page-run formulas; the disagg
    # handoff adds the point-to-point ICI wire leg
    "engine.kv_spill": "kv_page_io",
    "engine.kv_restore": "kv_page_io",
    "engine.kv_migrate": "kv_migrate",
}


def uncovered_api_ops() -> Tuple[str, ...]:
    """Decorated public ops with no cost-model family (doctor check).

    Delegates to the L013 ``registry_coverage`` pass — the ONE
    implementation of the coverage rule, shared by ``obs doctor`` and
    the static analyzer (ISSUE 15): the lint gate and the doctor can
    never disagree about what "covered" means.  The fallback mirrors
    the delegated implementation so this obs-internal surface survives
    a broken ANALYSIS package (importing the pass runs the full
    package init); the pass remains the enforcement point."""
    try:
        from flashinfer_tpu.analysis.registry_coverage import \
            uncovered_api_ops as _impl
    except Exception:
        from flashinfer_tpu.obs.catalog import API_OPS

        return tuple(sorted(API_OPS - set(API_OP_COSTS)))
    return _impl()


# -- banked-row reconstruction (obs perf on pre-roofline history) ---------

# fixed configs of bench.py's phases that rows don't spell out
# (Llama-3 GQA 32/8/128, DeepSeek MLA 128 heads 512+64, Mixtral 8x7B,
# the scans dims) — used ONLY for rows banked before cost stamping;
# new rows carry their cost fields inline.
_BENCH_DECODE = dict(num_qo_heads=32, num_kv_heads=8, head_dim=128)
_BENCH_PREFILL = dict(HQ=32, HKV=8, D=128)
_BENCH_MOE = dict(num_experts=8, hidden=4096, inter=14336, top_k=2)
_BENCH_SCANS = dict(H=24, dim=64, ds=128, Hg=16, dk=128, dv=128)


def _row_seconds(row: Mapping) -> Optional[float]:
    """Wall time of the measurement a row's stamp refers to."""
    for f in ("us", "us_step", "us_step_80l", "kernel_us"):
        v = row.get(f)
        if isinstance(v, (int, float)) and v > 0:
            return float(v) * 1e-6
    return None


def cost_from_stamped_row(row: Mapping) -> Optional[Tuple[Cost, float]]:
    """(Cost, seconds) straight from a row that obs.roofline already
    stamped (new-generation banked rows are self-describing): launched
    flops + read/write bytes, the optional ``flops_effective`` waste
    split, and the compute dtype — no shape reconstruction needed."""
    try:
        flops = float(row["flops"])
        br = float(row["bytes_read"])
        bw = float(row["bytes_written"])
    except (KeyError, TypeError, ValueError):
        return None
    seconds = _row_seconds(row)
    if seconds is None:
        return None
    eff = row.get("flops_effective")
    ici = row.get("ici_bytes")
    return Cost(
        flops=flops, bytes_read=br, bytes_written=bw,
        flops_effective=float(eff) if isinstance(eff, (int, float))
        else None,
        dtype=str(row.get("dtype", "bf16")), op=str(row.get("phase", "")),
        ici_bytes=float(ici) if isinstance(ici, (int, float)) else 0.0,
    ), seconds


def cost_for_bench_row(row: Mapping) -> Optional[Tuple[Cost, float]]:
    """(Cost, seconds) for a bench row: the row's own roofline stamp
    when present (:func:`cost_from_stamped_row`), else reconstructed
    from the row's configuration via the fixed bench shapes below.
    None when the phase has no model (the CI selftest stub) or the row
    is malformed."""
    stamped = cost_from_stamped_row(row)
    if stamped is not None:
        return stamped
    phase = row.get("phase")
    try:
        if phase == "decode":
            return (paged_decode(int(row["bs"]), int(row["ctx"]),
                                 **_BENCH_DECODE),
                    float(row["us"]) * 1e-6)
        if phase == "prefill":
            p = _BENCH_PREFILL
            if row.get("kind") == "ragged_flash":
                T = int(row["qlen"])
                c = attention(T, T, p["HQ"], p["HKV"], p["D"],
                              causal=True)
            else:
                c = paged_prefill(int(row["bs"]), int(row["qlen"]),
                                  int(row["ctx"]), p["HQ"], p["HKV"],
                                  p["D"], causal=True)
            return c, float(row["us"]) * 1e-6
        if phase == "mla":
            return (mla_decode(int(row["bs"]), int(row["ctx"]),
                               int(row.get("heads", 128))),
                    float(row["us"]) * 1e-6)
        if phase == "moe":
            int8 = "int8" in str(row.get("variant", ""))
            return (moe_gmm(int(row["tokens"]), **_BENCH_MOE,
                            weight_bytes=1 if int8 else 2,
                            dtype="int8" if int8 else "bf16"),
                    float(row["us"]) * 1e-6)
        if phase == "sampling":
            return (sampling(int(row["bs"]), int(row["vocab"])),
                    float(row["kernel_us"]) * 1e-6)
        if phase == "topk":
            return (topk(int(row["bs"]), int(row["vocab"]),
                         int(row.get("k", 0))),
                    float(row["us"]) * 1e-6)
        if phase == "scans":
            return _scans_row_cost(row)
        if phase == "serving":
            return _serving_row_cost(row)
    except (KeyError, TypeError, ValueError):
        return None
    return None


def _scans_row_cost(row: Mapping) -> Optional[Tuple[Cost, float]]:
    op, B = str(row.get("op", "")), int(row["B"])
    s = _BENCH_SCANS
    t = float(row["us"]) * 1e-6
    if op == "mamba_decode":
        return state_decode(B, s["H"], s["dim"], s["ds"]), t
    if op in ("gdn_decode", "kda_decode"):
        return state_decode(B, s["Hg"], s["dk"], s["dv"]), t
    L = int(row["L"])
    if op.startswith("mamba_prefill"):
        chunk = 128 if op.endswith("pallas") else 64
        return ssd_prefill(B, L, s["H"], s["dim"], s["ds"],
                           chunk=chunk), t
    if op.startswith(("gdn_prefill", "kda_prefill")):
        return gated_delta_prefill(B, L, s["Hg"], s["dk"], s["dv"]), t
    return None


def _serving_row_cost(row: Mapping) -> Optional[Tuple[Cost, float]]:
    shape = SERVING_SHAPES.get(str(row.get("model", "")))
    if shape is None:
        return None
    bs, ctx = int(row["bs"]), int(row["ctx"])
    if row.get("mode") == "e2e_measured":
        return (serving_step(bs, ctx, int(row["layers"]), **shape),
                float(row["us_step"]) * 1e-6)
    if "us_step_80l" in row:
        return (serving_step(bs, ctx, 80, include_kv_append=False,
                             include_sampling=False, **shape),
                float(row["us_step_80l"]) * 1e-6)
    return None


# ---------------------------------------------------------------------------
# Cost-launch bindings: the L016 cost-parity registry (launcher -> family)
# ---------------------------------------------------------------------------
#
# Registration contract (the extension point every newly PRICED kernel
# must feed — the costmodel side of the ``PLANNER_KERNELS`` /
# ``KNOB_LAUNCHES`` triple; see analysis/pallas_contract.py and
# analysis/vmem_budget.py for the other two):
#
# A :class:`CostLaunchBinding` ties one Pallas *launcher* (the function
# whose ``pl.pallas_call`` the analyzer resolves) to the cost-model
# *family* that prices it, plus ONE concrete scenario under which the
# L016 ``cost_parity`` pass replays the kernel symbolically and proves
# the formula's bytes/FLOPs against the DMA traffic and MXU dots the
# kernel body actually issues.  Scenarios must (a) make every grid
# trip count and BlockSpec dimension evaluable from ``scenario``
# alone, (b) keep the grid's final axis >= 3 trips so warmup /
# steady-state / epilogue steps are all distinguished (the
# double-buffer warmup is counted once, not per step), and (c) keep
# every in-kernel unrolled loop within the model's unroll ceiling.
# ``adapter`` returns the family's EXPECTED totals for exactly the
# traffic the launch itself moves — terms belonging to sibling
# launches (e.g. the split-decode merge pass) are excluded, and the
# exclusion must be justified in ``notes``.  A deviation beyond
# ``compare``'s tolerance is a machine-proved cost-model drift:
# fix the formula or the kernel, NEVER baseline it (L016 is in the
# analyzer's unbaselineable set, like L014 races).


@dataclasses.dataclass(frozen=True)
class CostLaunchBinding:
    """One launcher's parity contract against its pricing family.

    ``launcher``/``family`` are names (resolved by the pass /
    checked by L017), the callables are scenario -> concrete values:

    - ``vmem_shapes(scenario)``: kernel-visible shape of every VMEM
      ref the kernel's DMAs or dots touch, keyed by KERNEL param (or
      scratch-unpack) name.  Cross-checked against the launch's
      ``scratch_shapes`` exprs via the L009 evaluator where
      ``scratch_names`` maps a name to its scratch index — a
      disagreement is its own L016 finding (binding drift).
    - ``adapter(scenario)``: expected totals per compared category
      (``bytes_read`` / ``bytes_written`` / ``bytes_total`` /
      ``flops``), computed by calling the family formula.
    - ``compare``: category -> relative tolerance (0.0 = exact).
    - ``implicit_fallback(scenario)``: declared BlockSpec-machinery
      bytes, used ONLY for the spec side(s) the analyzer cannot
      statically resolve (flag-conditional spec lists); sides the
      analyzer CAN resolve are always machine-derived and the
      declaration is ignored.  ``notes`` must say why resolution
      fails.
    """

    launcher: str
    family: str
    scenario: Mapping[str, object]
    statics: Mapping[str, object]
    seeds: Mapping[str, int]
    vmem_shapes: object  # Callable[[Mapping], Dict[str, tuple]]
    adapter: object  # Callable[[Mapping], Dict[str, float]]
    compare: Mapping[str, float]
    itemsizes: Mapping[str, int] = dataclasses.field(
        default_factory=dict)
    default_itemsize: int = 2
    spec_itemsizes: Mapping[str, int] = dataclasses.field(
        default_factory=dict)
    scratch_names: Mapping[str, int] = dataclasses.field(
        default_factory=dict)
    implicit_fallback: Optional[object] = None
    notes: str = ""


COST_LAUNCH_BINDINGS: Dict[str, CostLaunchBinding] = {}


def register_cost_launch(binding: CostLaunchBinding) -> CostLaunchBinding:
    COST_LAUNCH_BINDINGS[binding.launcher] = binding
    return binding


# -- knob -> chooser coverage (L017) ----------------------------------------
#
# Every KNOWN_KNOBS surface must either be resolved by a registered
# plan-time chooser (a ``choose_*`` / ``predict_*_win`` function that
# prunes candidates through the real L009 VMEM evaluator before
# pricing them) or carry a REASONED waiver below.  A waiver that
# shadows a registered chooser, or names a knob that no longer
# exists, is itself an L017 finding — same staleness rules as the
# L013 KNOB_WAIVERS idiom.

KNOB_CHOOSERS: Dict[str, str] = {}
CHOOSER_WAIVERS: Dict[str, str] = {}


def register_knob_chooser(knob: str, chooser: str) -> None:
    KNOB_CHOOSERS[knob] = chooser


def waive_chooser(knob: str, reason: str) -> None:
    CHOOSER_WAIVERS[knob] = reason


register_knob_chooser("decode.splits", "choose_decode_splits")
register_knob_chooser("prefill.fused_ingest", "predict_prefill_ingest_win")

waive_chooser("paged_decode.pages_per_chunk",
              "resolved by the shared split_chunk_pages formula "
              "(512/page clamp + 8 MiB double-buffer scratch bound), "
              "a geometry derivation, not a priced choice")
waive_chooser("paged_decode.prefetch",
              "boolean pipeline toggle whose safety is proven by the "
              "L014 race model; perf delta is A/B'd on-chip, no "
              "analytic candidate race exists")
waive_chooser("fused_prefill.blocks",
              "(block_q, pages_per_chunk) is tuned by the offline "
              "banked sweep (scripts/exp_prefill_blocks.py) and "
              "gated by the L009 VMEM proof of the launch binding; "
              "no plan-time pricing loop")
waive_chooser("flash_attention.blocks",
              "offline-swept grid blocks, L009-gated via its "
              "KNOB_LAUNCHES binding; not priced at plan time")
waive_chooser("moe_gmm.tiles",
              "chosen by tune_tiles MEASURED profiling with the "
              "VMEM-ceiling candidate filter — measurement beats the "
              "model where both exist (docs/performance.md)")
waive_chooser("mla_decode.layout",
              "dictated by the latent-cache layout contract of the "
              "serving cache, not a priced per-shape choice")
waive_chooser("rmsnorm.row_block",
              "bandwidth-bound elementwise kernel: row block is a "
              "VMEM-fit resolution (L009 launch binding), every "
              "fitting value moves the same bytes")
waive_chooser("fused_add_rmsnorm.row_block",
              "same as rmsnorm.row_block: VMEM-fit resolution of a "
              "bandwidth-bound elementwise kernel")
waive_chooser("parallel.dp",
              "mesh topology knob: validity (dp x tp == world) and "
              "capacity math live in parallel/plan.py; no kernel "
              "candidate set to price")
waive_chooser("parallel.tp",
              "mesh topology knob, see parallel.dp")
waive_chooser("parallel.ep",
              "mesh topology knob (must divide parallel.tp), see "
              "parallel.dp")
waive_chooser("engine.block_size",
              "page-pool sharing granularity: a capacity/prefix-"
              "cache trade priced by serving capacity math, not a "
              "kernel-candidate race")
waive_chooser("engine.prefill_budget_tokens",
              "the marginal chunk is priced ONLINE by "
              "predict_step_seconds against slo_step_seconds in the "
              "engine scheduler; the static is a ceiling, not a "
              "candidate choice")
waive_chooser("engine.max_batch",
              "compile-once rung-ladder width: a memory-capacity "
              "ceiling from the HBM budget, not a priced choice")
waive_chooser("engine.kv_offload",
              "deployment capacity toggle (host tier attached or "
              "not); spill pricing happens per-victim under "
              "engine.spill_policy")
waive_chooser("engine.spill_policy",
              "'auto' performs the per-victim restore-vs-recompute "
              "cost comparison inline in the engine (via "
              "predict_step_seconds); the knob selects the policy, "
              "the pricing is not a choose_* surface")
waive_chooser("engine.host_gib",
              "host-RAM capacity budget; LRU eviction over it is "
              "counted, there is no candidate set to price")
waive_chooser("engine.attention_backend",
              "correctness-tier dispatch (reference oracle vs Pallas "
              "kernels); the kernel tier's internal choices are "
              "priced by decode.splits / prefill.fused_ingest")


# -- the five priced kernel families ----------------------------------------


def _gmm_vmem_shapes(sc):
    tm, tk, tn = sc["tm"], sc["tk"], sc["tn"]
    return {"lhs_ref": (tm, tk), "rhs_ref": (tk, tn),
            "out_ref": (tm, tn), "acc_ref": (tm, tn)}


def _gmm_adapter(sc):
    c = gemm(sc["m"], sc["n"], sc["k"])
    return {"bytes_read": c.bytes_read,
            "bytes_written": c.bytes_written, "flops": c.flops}


def _gmm_implicit(sc):
    # in_specs is extended under the quantized flag, so the analyzer
    # cannot statically resolve the list; the bf16 scenario's two
    # operands are declared here: lhs re-streamed per k-tile sweep
    # (tiles_n == 1 in the scenario so lhs streams once), rhs panels
    # once per (group, k) visit.
    return {"bytes_read": float(sc["m"]) * sc["k"] * 2
            + float(sc["k"]) * sc["n"] * 2}


register_cost_launch(CostLaunchBinding(
    launcher="gmm",
    family="gemm",
    scenario=dict(tiles_n=1, num_tiles=1, tiles_k=2, tm=128, tk=512,
                  tn=128, m=128, k=1024, n=128),
    statics=dict(tm=128, tiles_k=2, quantized=False),
    seeds=dict(offsets_s=0, tile_group_s=0, tile_m_s=0),
    vmem_shapes=_gmm_vmem_shapes,
    adapter=_gmm_adapter,
    compare={"bytes_read": 0.0, "bytes_written": 0.0, "flops": 0.0},
    itemsizes={"acc_ref": 4},
    spec_itemsizes={"out0": 2},
    scratch_names={"acc_ref": 0},
    implicit_fallback=_gmm_implicit,
    notes="One expert tile, one n-tile, two k-tiles of the bf16 "
          "grouped matmul: exactly one gemm(m, n, k) with every "
          "operand streamed once, so parity is exact (tol 0). The "
          "masked-partial-store epilogue re-reads the resident out "
          "block in VMEM, not HBM.",
))


def _paged_decode_vmem_shapes(sc):
    ppc, hkv = sc["pages_per_chunk"], sc["num_kv_heads"]
    ps, d, gp = sc["page_size"], sc["head_dim"], sc["gp"]
    return {"k_buf": (2, ppc, hkv, ps, d), "v_buf": (2, ppc, hkv, ps, d),
            "q_ref": (hkv, gp, d), "o_ref": (hkv, gp, d),
            "lse_ref": (hkv, gp, 128)}


def _paged_decode_adapter(sc):
    c = paged_decode(sc["batch"], sc["ctx"], sc["num_qo_heads"],
                     sc["num_kv_heads"], sc["head_dim"])
    return {"bytes_read": c.bytes_read, "flops": c.flops,
            "bytes_total": c.bytes_total}


register_cost_launch(CostLaunchBinding(
    launcher="_paged_decode_hnd_launch",
    family="paged_decode",
    scenario=dict(batch=4, ctx=512, num_qo_heads=16, num_kv_heads=2,
                  group=8, gp=8, head_dim=128, page_size=16,
                  pages_per_chunk=8),
    statics=dict(page_size=16, ppc=8, sm_scale=1.0,
                 logits_soft_cap=0.0, window_left=-1, num_kv_heads=2,
                 cross_step_prefetch=False, compute_dtype="bfloat16"),
    seeds=dict(pages_ref=0, kvlen_ref=512, base_smem=0),
    vmem_shapes=_paged_decode_vmem_shapes,
    adapter=_paged_decode_adapter,
    compare={"bytes_read": 0.0, "flops": 0.0, "bytes_total": 0.02},
    itemsizes={"lse_ref": 4},
    spec_itemsizes={"in0": 2, "out0": 2, "out1": 4},
    scratch_names={"k_buf": 0, "v_buf": 1},
    notes="Full-cache HND decode at 4 requests x 512 ctx: reads and "
          "FLOPs are exact; bytes_total carries a 2% band because "
          "the kernel also writes the f32 LSE block (B*Hkv*Gp*128*4 "
          "= +1.5% here) which the algorithmic formula folds into "
          "the outputs-written-once convention (LSE is consumed by "
          "the cascade merge, not a decode deliverable).",
))


def _decode_split_vmem_shapes(sc):
    ppc, hkv = sc["pages_per_chunk"], sc["num_kv_heads"]
    ps, d, gp = sc["page_size"], sc["head_dim"], sc["gp"]
    return {"k_buf": (2, ppc, hkv, ps, d), "v_buf": (2, ppc, hkv, ps, d),
            "q_ref": (hkv, gp, d), "o_ref": (hkv, gp, d),
            "lse_ref": (hkv, gp, 128)}


def _decode_split_adapter(sc):
    bd = decode_split_breakdown(
        sc["batch"], sc["ctx"], sc["num_qo_heads"],
        sc["num_kv_heads"], sc["head_dim"],
        num_splits=sc["num_splits"], page_size=sc["page_size"],
        pages_per_chunk=sc["pages_per_chunk"])
    per_tok = 2.0 * sc["num_qo_heads"] * 2 * sc["head_dim"]
    return {"bytes_read": bd["kv_bytes"] + bd["q_bytes"],
            "bytes_written": bd["merge_bytes"] / 2.0,
            "flops": bd["kv_tokens_launched"] * per_tok}


register_cost_launch(CostLaunchBinding(
    launcher="paged_decode_attention_split",
    family="decode_split",
    scenario=dict(batch=4, ctx=256, num_splits=2, num_units=8,
                  num_qo_heads=16, num_kv_heads=2, group=8, gp=8,
                  head_dim=128, page_size=16, pages_per_chunk=8),
    statics=dict(page_size=16, ppc=8, sm_scale=1.0,
                 logits_soft_cap=0.0, window_left=-1, num_kv_heads=2,
                 single_chunk=True),
    seeds=dict(pages_ref=0, kvlen_ref=256, req_ref=0, page0_ref=0,
               uklen_ref=128),
    vmem_shapes=_decode_split_vmem_shapes,
    adapter=_decode_split_adapter,
    compare={"bytes_read": 0.0, "bytes_written": 0.0, "flops": 0.0},
    itemsizes={"o_ref": 4, "lse_ref": 4},
    spec_itemsizes={"in0": 2, "out0": 4, "out1": 4},
    scratch_names={"k_buf": 0, "v_buf": 1},
    notes="4 requests x 256 ctx split 2 ways = 8 single-chunk work "
          "units. The kernel's share of decode_split is exact (tol "
          "0): reads = kv_bytes + q_bytes, writes = merge_bytes/2 "
          "(the f32 partial out+lse), flops = the whole-chunk KV "
          "walk. The OTHER half of the family's totals — the "
          "merge_bytes/2 read-back, the merged out_bytes write and "
          "the 2*merge_elems reduction FLOPs — belongs to the "
          "merge_states launch and is excluded here.",
))


def _fused_prefill_stats(sc, u):
    chunk = sc["ppc"] * sc["page_size"]
    cells = u * sc["bq"] * chunk
    return {"tiles": u, "units": u, "mxu_cells_total": cells,
            "mxu_cells_valid": cells}


def _fused_prefill_vmem_shapes(sc):
    bq, g, d = sc["bq"], sc["group"], sc["head_dim"]
    chunk = sc["ppc"] * sc["page_size"]
    return {"qbuf": (2, bq, g, d), "kbuf": (2, chunk, d),
            "vbuf": (2, chunk, d), "obuf": (bq, g, d),
            "acc_ref": (bq * g, d), "m_ref": (bq * g, 128),
            "l_ref": (bq * g, 128), "lsebuf": (bq, g, 128)}


def _fused_prefill_adapter(sc):
    u = sc["n_units"]  # the launch runs the plan's real units only
    c = fused_prefill_from_stats(
        _fused_prefill_stats(sc, u), block_q=sc["bq"],
        pages_per_chunk=sc["ppc"], page_size=sc["page_size"],
        num_qo_heads=sc["num_qo_heads"], num_kv_heads=sc["Hkv"],
        head_dim=sc["head_dim"], total_q=u * sc["bq"])
    return {"bytes_read": c.bytes_read,
            "bytes_written": c.bytes_written, "flops": c.flops}


def _fused_prefill_implicit(sc):
    # every q/k/v/o operand is ANY (manual DMA); the spec lists are
    # extended under has_mask / return_lse / trace_events flags (all
    # pinned off by the scenario), hence statically unresolvable.
    return {"bytes_read": 0.0, "bytes_written": 0.0}


register_cost_launch(CostLaunchBinding(
    launcher="fused_paged_prefill",
    family="fused_prefill_from_stats",
    scenario=dict(Hkv=2, n_units=4, num_qo_heads=16, bq=128,
                  group=8, head_dim=128, page_size=16, ppc=8),
    statics=dict(bq=128, ppc=8, page_size=16, group=8, sm_scale=1.0,
                 logits_soft_cap=0.0, window_left=-1, causal=True,
                 has_mask=False, return_lse=False, trace_events=False),
    seeds=dict(qstart_ref=0, rowlo_ref=0, rowhi_ref=128, qpos0_ref=0,
               kvstart_ref=0, kvlen_ref=128, first_ref=1, wout_ref=1,
               qslot_ref=0, code_ref=0, pages_ref=0),
    vmem_shapes=_fused_prefill_vmem_shapes,
    adapter=_fused_prefill_adapter,
    compare={"bytes_read": 0.0, "bytes_written": 0.0, "flops": 0.0},
    itemsizes={"acc_ref": 4, "m_ref": 4, "l_ref": 4, "lsebuf": 4},
    implicit_fallback=_fused_prefill_implicit,
    notes="4 real work units, each its own q tile and single full "
          "KV chunk (first=wout=1, CODE_FULL).  The unit axis's trip "
          "count is the plan's n_units whatever length the plan "
          "arrays are padded to, so pad units are not run and the "
          "family prices none: the stats adapter's tiles/units/cells "
          "mirror the launch exactly, parity is exact (tol 0) on "
          "reads, writes and MXU FLOPs.",
))


def _prefill_ingest_vmem_shapes(sc):
    bq, g, d = sc["bq"], sc["group"], sc["head_dim"]
    chunk = sc["ppc"] * sc["page_size"]
    return {"qbuf": (2, bq, g, d), "kbuf": (2, chunk, d),
            "vbuf": (2, chunk, d), "obuf": (bq, g, d),
            "kqbuf": (chunk, d), "vqbuf": (chunk, d),
            "acc_ref": (bq * g, d), "m_ref": (bq * g, 128),
            "l_ref": (bq * g, 128), "lsebuf": (bq, g, 128)}


def _prefill_ingest_adapter(sc):
    u = sc["num_units"]
    chunk = sc["ppc"] * sc["page_size"]
    total_q, total_kv = u * sc["bq"], u * chunk
    hq, hkv, d = sc["num_qo_heads"], sc["Hkv"], sc["head_dim"]
    c = prefill_ingest(
        total_q, total_kv, hq, hkv, d, stats=_fused_prefill_stats(sc, u),
        block_q=sc["bq"], pages_per_chunk=sc["ppc"],
        page_size=sc["page_size"])
    rope_flops = 6.0 * (total_q * hq + total_kv * hkv) * d
    quant_flops = 2.0 * 2.0 * total_kv * hkv * d
    return {"bytes_read": c.bytes_read,
            "bytes_written": c.bytes_written,
            "flops": c.flops - rope_flops - quant_flops}


register_cost_launch(CostLaunchBinding(
    launcher="fused_paged_prefill_ingest",
    family="prefill_ingest",
    scenario=dict(Hkv=2, num_units=4, num_qo_heads=16, bq=128,
                  group=8, head_dim=128, page_size=16, ppc=8),
    statics=dict(bq=128, ppc=8, page_size=16, group=8, head_dim=128,
                 sm_scale=1.0, logits_soft_cap=0.0, window_left=-1,
                 causal=True, has_mask=False, return_lse=False,
                 attend=True, rope_scale=1.0, rope_theta=10000.0,
                 rope_interleave=False, kv_quant="none", k_scale=1.0,
                 v_scale=1.0),
    seeds=dict(qstart_ref=0, rowlo_ref=0, rowhi_ref=128, qpos0_ref=0,
               kvstart_ref=0, kvlen_ref=128, first_ref=1, wout_ref=1,
               qslot_ref=0, code_ref=0, pages_ref=0, kvbase_ref=0,
               posoff_ref=0, wkv_ref=1),
    vmem_shapes=_prefill_ingest_vmem_shapes,
    adapter=_prefill_ingest_adapter,
    compare={"bytes_read": 0.0, "bytes_written": 0.0, "flops": 0.0},
    itemsizes={"acc_ref": 4, "m_ref": 4, "l_ref": 4, "lsebuf": 4},
    implicit_fallback=_fused_prefill_implicit,
    notes="4 single-chunk work units owning their cache pages "
          "(wkv=1): raw q/k/v stream in once, quantized pages write "
          "out once, so the stats-mode prefill_ingest reads/writes "
          "are exact (tol 0) — this is the binding whose read side "
          "deletes if the fused-ingest 'avoided Kc re-read' term "
          "regresses.  FLOPs compare MXU dots only: the family's "
          "rope (6/elt) and quantize (4/elt) terms are VPU work the "
          "MXU dot walk never sees, subtracted in the adapter.",
))
