"""AutoChunk: an activation-memory planner for the chunk knobs.

FastFold's AutoChunk "automatically determines the chunk strategy" instead of
hand-tuned constants. This module is that planner for our stack: given the
static tensor shapes of a forward pass, the compute dtype, and the per-chip
HBM budget (``launch.mesh.HBM_BYTES``), it picks

  * ``inference_chunk`` — paper-§V.C group chunking of the attention sites,
  * ``opm_chunk``       — Outer-Product-Mean j-chunking (materialized path),
  * ``attn_kv_tile``    — KV tile of the fused flash-attention kernel
                          (forward tile and backward recompute block),
  * ``tri_k_tile``      — tile of the fused triangle-mult kernel (Pallas k
                          accumulation tile / XLA j block / bwd recompute),
  * ``opm_s_tile``      — tile of the fused outer-product-mean kernel
                          (Pallas s tile / XLA j block / bwd recompute),

as the LEAST-chunked settings whose modeled peak activation bytes fit the
budget (0 = knob off / kernel default — selected whenever the unchunked plan
fits). Chunk knobs serialize compute, so the preference order when shrinking
is: kernel tiles first (near-free: still one sweep over the data — KV tile,
then triangle/OPM tiles), then OPM j-chunk (scan), then inference_chunk
(whole attention sites serialized).

Contract:
  * Planning is pure Python over static shapes — it runs at trace time
    (``alphafold_forward``), never inside the computation.
  * The returned plan never exceeds the budget when ANY candidate fits;
    ``fits=False`` flags that even the smallest plan is over budget (the
    caller decides — e.g. raise the DAP degree, paper Table V).
  * Hand-set (nonzero) knobs are respected: they are pinned during planning
    and never overwritten by ``resolve_evoformer_config``.

The memory model is the roofline-style dominant-term model used by
``bench_inference`` (paper §III.B: the cubic N_r^3*H attention transient),
not a byte-exact simulator: every term is the size of one live dominant
buffer, and the total is the peak of the block's phases.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax.numpy as jnp

from repro.kernels.ops import (
    _DEFAULT_KV_TILE,
    _DEFAULT_OPM_TILE,
    _DEFAULT_TRI_TILE,
)
from repro.kernels.flash_attention import LANE, _pad_to
from repro.launch.mesh import HBM_BYTES


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _eff_chunk(total: int, chunk: int) -> int:
    """Effective processed-at-once extent for a tile knob (0 = whole). Tiles
    (the attention KV tile) need no divisibility — the kernel pads + masks."""
    if chunk and 0 < chunk < total:
        return chunk
    return total


def _eff_div_chunk(total: int, chunk: int) -> int:
    """Effective extent for a CHUNK knob. Mirrors the runtime exactly:
    ``_gated_attention`` and ``outer_product_mean`` silently run UNCHUNKED
    when the chunk does not divide the extent (``g % chunk != 0``), so a
    non-dividing chunk must be modeled as the whole extent — otherwise a
    plan could claim fits=True and then run unchunked over budget."""
    if chunk and 0 < chunk < total and total % chunk == 0:
        return chunk
    return total


# ---------------------------------------------------------------------------
# Memory model
# ---------------------------------------------------------------------------


def attention_transient_bytes(
    groups: int,
    heads: int,
    seq: int,
    head_dim: int,
    *,
    kv_len: int | None = None,
    kv_tile: int = 0,
    fused: bool = True,
    dtype_bytes: int = 2,
) -> int:
    """Peak transient of one gated-attention site over ``groups`` rows.

    fused (flash kernel): q/k/v/out in compute dtype plus the fp32
    (groups, heads, seq, kv_tile) recompute block of the backward scan — the
    largest live buffer on the fused path; it scales with the KV tile, not
    with kv_len^2.

    scores-materialized: two (groups, heads, seq, kv_len) copies
    (scores + probs) — the paper's cubic transient when groups ~ seq.
    """
    kv = kv_len if kv_len is not None else seq
    qkvo = 4 * groups * seq * heads * head_dim * dtype_bytes
    if fused:
        tile = _eff_chunk(kv, kv_tile or _DEFAULT_KV_TILE)
        block = groups * heads * seq * tile * 4          # fp32 p/ds block
        lse = groups * heads * seq * 4
        return qkvo + block + lse
    return qkvo + 2 * groups * heads * seq * kv * dtype_bytes


def triangle_transient_bytes(
    rows_loc: int,
    n_res: int,
    c_mult: int,
    *,
    tile: int = 0,
    fused: bool = True,
    dtype_bytes: int = 2,
) -> int:
    """Peak transient of one triangular multiplicative update over
    ``rows_loc`` local pair rows.

    fused (ops.fused_triangle_mult): the merged a/gate projections plus the
    gathered (r, k, c) right operand in compute dtype, plus the fp32
    j-block product of the kernel's sweep / the backward's recompute scan —
    bounded by the tile, not by r.

    materialized: same operands plus the full (rows_loc, r, c) fp32 product
    the LayerNorm reads.
    """
    operands = c_mult * dtype_bytes * (4 * rows_loc * n_res
                                       + n_res * n_res)
    if fused:
        blk = _eff_chunk(n_res, tile or _DEFAULT_TRI_TILE)
        return operands + rows_loc * blk * c_mult * 4
    return operands + rows_loc * n_res * c_mult * 4


def opm_transient_bytes(
    rows_loc: int,
    n_res: int,
    n_seq: int,
    c_opm: int,
    *,
    tile: int = 0,
    opm_chunk: int = 0,
    fused: bool = True,
    dtype_bytes: int = 2,
) -> int:
    """Peak transient of the Outer-Product-Mean over ``rows_loc`` local pair
    rows: the gathered right projection plus the fp32 (rows_loc, j, c, c)
    outer-product block — j bounded by the fused op's tile (s/j sweep) or,
    on the materialized path, by the opm_chunk scan (full r when off)."""
    gathered = n_seq * n_res * c_opm * dtype_bytes
    if fused:
        jc = _eff_chunk(n_res, tile or _DEFAULT_OPM_TILE)
    else:
        jc = _eff_div_chunk(n_res, opm_chunk)
    return gathered + rows_loc * jc * c_opm * c_opm * 4


def evoformer_peak_bytes(
    cfg,
    *,
    batch: int,
    n_seq: int,
    n_res: int,
    dap: int = 1,
    fused: bool = True,
    staged: bool = False,
    inference_chunk: int = 0,
    opm_chunk: int = 0,
    attn_kv_tile: int = 0,
    tri_k_tile: int = 0,
    opm_s_tile: int = 0,
) -> dict:
    """Dominant per-device activation terms (bytes) of one Evoformer block.

    cfg: EvoformerConfig (duck-typed: d_msa, d_pair, msa_heads, pair_heads,
    head_dim, opm_dim, tri_mult_dim, transition_factor, compute_dtype).
    ``staged``: the fused leg is the Pallas kernel, and every attention call
    is counted with each head padded to 128 lanes (``ops._attn_tiles``): 4x
    the bytes of a 32-wide head, 16x those of the extra-MSA stack's 8-wide
    heads over 5120 rows. Only the backward kernel still stages that way;
    the forward reads q, k, v and writes its output at the heads' width, so
    for a forward-only pass the term overcounts. The XLA leg stages the
    heads at their width. Returns a dict of named terms; ``sum(values())``
    is the modeled peak.
    """
    dt = jnp.dtype(cfg.compute_dtype).itemsize
    s_loc = _ceil_div(n_seq, dap)
    r_loc = _ceil_div(n_res, dap)

    terms = {
        # A few live copies of each representation (input, LN'ed, update).
        "msa_rep": 3 * batch * s_loc * n_res * cfg.d_msa * dt,
        "pair_rep": 3 * batch * r_loc * n_res * cfg.d_pair * dt,
        # Gathered (B, H, r, r) pair-bias tensors — not chunkable.
        "pair_bias": batch * max(cfg.msa_heads, cfg.pair_heads)
        * n_res * n_res * dt,
        # Triangular mult: projections + gathered operand + the product
        # block (fp32 full row when materialized, tile-bounded when fused).
        "tri_mult": batch * triangle_transient_bytes(
            r_loc, n_res, cfg.tri_mult_dim, tile=tri_k_tile, fused=fused,
            dtype_bytes=dt),
    }
    # Attention: MSA row (groups = local MSA rows) and triangle (groups =
    # local pair rows) phases don't overlap — take the max.
    def width(head_dim):
        return _pad_to(head_dim, LANE) if fused and staged else head_dim

    attn_row = attention_transient_bytes(
        batch * _eff_div_chunk(s_loc, inference_chunk), cfg.msa_heads, n_res,
        width(cfg.d_msa // cfg.msa_heads), kv_tile=attn_kv_tile, fused=fused,
        dtype_bytes=dt)
    attn_tri = attention_transient_bytes(
        batch * _eff_div_chunk(r_loc, inference_chunk), cfg.pair_heads, n_res,
        width(cfg.head_dim), kv_tile=attn_kv_tile, fused=fused,
        dtype_bytes=dt)
    terms["attention"] = max(attn_row, attn_tri)
    # Outer Product Mean: gathered right projection + the fp32 outer-product
    # block (opm_s_tile-bounded when fused, opm_chunk scan otherwise).
    terms["opm"] = batch * opm_transient_bytes(
        r_loc, n_res, n_seq, cfg.opm_dim, tile=opm_s_tile,
        opm_chunk=opm_chunk, fused=fused, dtype_bytes=dt)
    # The MSA transition's (B, s, r, f·c) hidden: 0.67 GB over the extra
    # stack's 5120 rows at r 256, c 64.
    terms["msa_transition"] = (batch * s_loc * n_res
                               * cfg.transition_factor * cfg.d_msa * dt)
    return terms


def modeled_evoformer_peak(
    cfg,
    *,
    batch: int,
    n_seq: int,
    n_res: int,
    dap: int = 1,
    fused: bool = True,
) -> int:
    """Total modeled peak (sum of ``evoformer_peak_bytes`` terms) with the
    cfg's OWN chunk/tile knobs — the single number the ``PeakBytesWithin``
    contract (repro/analysis) cross-validates against what XLA's
    ``memory_analysis()`` says the compiled program actually allocates."""
    return sum(evoformer_peak_bytes(
        cfg, batch=batch, n_seq=n_seq, n_res=n_res, dap=dap, fused=fused,
        inference_chunk=cfg.inference_chunk, opm_chunk=cfg.opm_chunk,
        attn_kv_tile=getattr(cfg, "attn_kv_tile", 0),
        tri_k_tile=getattr(cfg, "tri_k_tile", 0),
        opm_s_tile=getattr(cfg, "opm_s_tile", 0)).values())


# ---------------------------------------------------------------------------
# Evoformer planner
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ChunkPlan:
    inference_chunk: int = 0
    opm_chunk: int = 0
    attn_kv_tile: int = 0
    est_bytes: int = 0
    budget_bytes: int = 0
    fits: bool = True
    # Appended fields (keep positional compatibility with older callers):
    # tiles of the fused triangle-mult / outer-product-mean kernels
    # (0 = kernel default — already tile-bounded).
    tri_k_tile: int = 0
    opm_s_tile: int = 0

    def describe(self) -> str:
        return (f"ic={self.inference_chunk} oc={self.opm_chunk} "
                f"kt={self.attn_kv_tile} tt={self.tri_k_tile} "
                f"ot={self.opm_s_tile} est={self.est_bytes >> 20}MB "
                f"budget={self.budget_bytes >> 20}MB fits={self.fits}")


_IC_CANDIDATES = (0, 256, 128, 64, 32, 16, 8, 4, 2, 1)
_OC_CANDIDATES = (0, 1024, 512, 256, 128, 64, 32, 16, 8)
_KT_CANDIDATES = (0, 256, 128)
_TT_CANDIDATES = (0, 64, 32, 16)    # triangle tile below its default 128
_OT_CANDIDATES = (0, 64, 32, 16)    # OPM tile below its default 128


def _knob_candidates(fixed: int, options, limit: int):
    if fixed:
        return (fixed,)
    return tuple(o for o in options if o == 0 or o < limit) or (0,)


def _div_candidates(fixed: int, options, *totals):
    """Candidates for a CHUNK knob: 0 (off) plus values that divide at least
    one of the chunked extents (non-dividing chunks are runtime no-ops — see
    _eff_div_chunk), augmented with total/k divisors so non-power-of-two
    extents still get effective options."""
    if fixed:
        return (fixed,)
    cands: set[int] = set()
    for total in totals:
        cands |= {o for o in options if 0 < o < total and total % o == 0}
        cands |= {total // k for k in (2, 4, 8, 16, 32, 64)
                  if total % k == 0 and 1 <= total // k < total}
    return (0,) + tuple(sorted(cands, reverse=True))


def plan_evoformer_chunks(
    cfg,
    *,
    batch: int,
    n_seq: int,
    n_res: int,
    budget_bytes: int = HBM_BYTES,
    dap: int = 1,
    fused: bool = True,
    staged: bool = False,
) -> ChunkPlan:
    """Pick the least-chunked (inference_chunk, opm_chunk, attn_kv_tile)
    whose modeled peak fits ``budget_bytes``. Nonzero knobs already set on
    ``cfg`` are pinned. Never exceeds the budget when any candidate fits;
    otherwise returns the minimal-memory plan with ``fits=False``."""
    s_loc = _ceil_div(n_seq, dap)
    r_loc = _ceil_div(n_res, dap)
    groups = max(s_loc, r_loc)
    ics = _div_candidates(cfg.inference_chunk, _IC_CANDIDATES, s_loc, r_loc)
    ocs = _div_candidates(cfg.opm_chunk, _OC_CANDIDATES, n_res)
    kts = _knob_candidates(getattr(cfg, "attn_kv_tile", 0), _KT_CANDIDATES,
                           n_res if fused else 1)
    lim = n_res if fused else 1
    tts = _knob_candidates(getattr(cfg, "tri_k_tile", 0), _TT_CANDIDATES, lim)
    ots = _knob_candidates(getattr(cfg, "opm_s_tile", 0), _OT_CANDIDATES, lim)

    def est(ic, oc, kt, tt, ot) -> int:
        return sum(evoformer_peak_bytes(
            cfg, batch=batch, n_seq=n_seq, n_res=n_res, dap=dap, fused=fused,
            staged=staged, inference_chunk=ic, opm_chunk=oc, attn_kv_tile=kt,
            tri_k_tile=tt, opm_s_tile=ot).values())

    def serialization_cost(ic, oc, kt, tt, ot):
        # Lexicographic preference: avoid/maximize inference_chunk first
        # (whole sites serialized), then opm_chunk (scan), then the kernel
        # tiles (near-free: still one sweep each).
        return (
            _ceil_div(groups, ic) if ic else 0,
            _ceil_div(n_res, oc) if oc else 0,
            _ceil_div(n_res, kt) if kt else 0,
            _ceil_div(n_res, tt) if tt else 0,
            _ceil_div(n_res, ot) if ot else 0,
        )

    best = None          # least serialization among fitting plans
    smallest = None      # minimal est_bytes overall (fallback)
    for ic in ics:
        for oc in ocs:
            for kt in kts:
                for tt in tts:
                    for ot in ots:
                        e = est(ic, oc, kt, tt, ot)
                        key = serialization_cost(ic, oc, kt, tt, ot)
                        if smallest is None or e < smallest[0]:
                            smallest = (e, ic, oc, kt, tt, ot)
                        if e <= budget_bytes and (best is None
                                                  or key < best[0]):
                            best = (key, e, ic, oc, kt, tt, ot)
    if best is not None:
        _, e, ic, oc, kt, tt, ot = best
        return ChunkPlan(ic, oc, kt, e, budget_bytes, True, tt, ot)
    e, ic, oc, kt, tt, ot = smallest
    return ChunkPlan(ic, oc, kt, e, budget_bytes, False, tt, ot)


def apply_plan(cfg, plan: ChunkPlan):
    """EvoformerConfig with the plan's knobs filled in (hand-set nonzero
    knobs on cfg win — the planner already pinned them)."""
    return dataclasses.replace(
        cfg,
        inference_chunk=cfg.inference_chunk or plan.inference_chunk,
        opm_chunk=cfg.opm_chunk or plan.opm_chunk,
        attn_kv_tile=cfg.attn_kv_tile or plan.attn_kv_tile,
        tri_k_tile=getattr(cfg, "tri_k_tile", 0) or plan.tri_k_tile,
        opm_s_tile=getattr(cfg, "opm_s_tile", 0) or plan.opm_s_tile,
    )


def resolve_evoformer_config(
    cfg,
    *,
    batch: int,
    n_seq: int,
    n_res: int,
    dap: int = 1,
    budget_bytes: int | None = None,
):
    """AutoChunk entry point used by ``alphafold_forward``: returns cfg with
    every knob left at 0 replaced by the planned value (no-op when
    ``cfg.auto_chunk`` is False or everything already fits unchunked).
    ``budget_bytes=None`` resolves the current ExecutionPlan's
    MemoryPolicy.hbm_budget, falling back to the hardware HBM_BYTES."""
    if not getattr(cfg, "auto_chunk", False):
        return cfg
    if budget_bytes is None:
        from repro.exec.plan import current_plan

        budget_bytes = current_plan().memory.hbm_budget or HBM_BYTES
    from repro.kernels import ops

    fused = ops.fused_attention_supported(
        (batch, n_seq, n_res, cfg.msa_heads, cfg.d_msa // cfg.msa_heads),
        kv_len=n_res, dtype=cfg.compute_dtype)
    plan = plan_evoformer_chunks(
        cfg, batch=batch, n_seq=n_seq, n_res=n_res,
        budget_bytes=budget_bytes, dap=dap, fused=fused,
        staged=ops._use_pallas(ops.kernel_leg("attention")))
    return apply_plan(cfg, plan)


# ---------------------------------------------------------------------------
# Decoder / serving planner
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DecoderPlan:
    attn_q_block: int
    attn_kv_block: int
    est_bytes: int
    budget_bytes: int
    fits: bool


def decoder_attention_bytes(cfg, *, n_slots: int, max_seq: int,
                            q_block: int, kv_block: int,
                            seq_len: int | None = None) -> int:
    """Dominant serving-time bytes: the batched KV cache + the prefill
    flash-attention probs block + logits. cfg is a ModelConfig.
    ``seq_len`` bounds the prefill-phase terms to the actual prompt length
    (admission queries); None models the worst case (= max_seq)."""
    hd = cfg.resolved_head_dim
    dt = 1 if getattr(cfg, "kv_cache_int8", False) else 2
    s = min(seq_len or max_seq, max_seq)
    cache = cfg.n_layers * n_slots * max_seq * 2 * cfg.n_kv * hd * dt
    qb = min(q_block or s, s)
    kvb = min(kv_block or s, s)
    probs = cfg.n_heads * qb * kvb * 4              # fp32 block in the scan
    acts = 3 * s * cfg.n_heads * hd * 2
    logits = n_slots * cfg.vocab * 4
    return cache + probs + acts + logits


@dataclass(frozen=True)
class AdmissionCheck:
    """Result of a serving-engine admission query (see
    ``check_decoder_admission``)."""

    fits: bool
    est_bytes: int
    budget_bytes: int
    seq_len: int

    def describe(self) -> str:
        return (f"seq_len={self.seq_len} est={self.est_bytes >> 20}MB "
                f"budget={self.budget_bytes >> 20}MB fits={self.fits}")


_MIN_BLOCK = 32   # the most-shrunk attention block plan_decoder_blocks tries


def check_decoder_admission(cfg, *, n_slots: int, max_seq: int,
                            seq_len: int | None = None,
                            budget_bytes: int = HBM_BYTES) -> AdmissionCheck:
    """Admission query for the serving engine: can a request of
    ``seq_len`` tokens run in an engine of (n_slots, max_seq) within
    ``budget_bytes``? The engine can always degrade its attention blocks
    (but not the KV-cache extent), so a request is admissible iff even the
    most-shrunk block plan fits its plan's budget. Pure Python over static
    shapes — safe to call per submit()."""
    s = min(seq_len or max_seq, max_seq)
    est = decoder_attention_bytes(
        cfg, n_slots=n_slots, max_seq=max_seq,
        q_block=min(_MIN_BLOCK, s), kv_block=min(_MIN_BLOCK, s),
        seq_len=s)
    return AdmissionCheck(est <= budget_bytes, est, budget_bytes, s)


def plan_decoder_blocks(cfg, *, n_slots: int, max_seq: int,
                        budget_bytes: int = HBM_BYTES):
    """Serving-engine AutoChunk: keep the configured attention blocks when
    they fit the HBM budget, otherwise shrink — KV block first, then the q
    block. Returns (ModelConfig, DecoderPlan)."""
    q_opts = [cfg.attn_q_block] + [b for b in (256, 128, 64, 32)
                                   if not cfg.attn_q_block
                                   or b < cfg.attn_q_block]
    kv_opts = [cfg.attn_kv_block] + [b for b in (512, 256, 128, 64, 32)
                                     if not cfg.attn_kv_block
                                     or b < cfg.attn_kv_block]
    best = None
    for qb in q_opts:              # outer: shrink q last
        for kvb in kv_opts:        # inner: shrink kv first
            e = decoder_attention_bytes(cfg, n_slots=n_slots,
                                        max_seq=max_seq, q_block=qb,
                                        kv_block=kvb)
            if best is None or e < best[0]:
                best = (e, qb, kvb)
            if e <= budget_bytes:
                plan = DecoderPlan(qb, kvb, e, budget_bytes, fits=True)
                return dataclasses.replace(
                    cfg, attn_q_block=qb, attn_kv_block=kvb), plan
    e, qb, kvb = best
    plan = DecoderPlan(qb, kvb, e, budget_bytes, fits=False)
    return dataclasses.replace(cfg, attn_q_block=qb, attn_kv_block=kvb), plan
