"""Public, shape-polymorphic entry points for the Pallas kernels.

Each op:
  * reshapes arbitrary leading dims down to the kernel's canonical layout,
  * runs the Pallas kernel on TPU (the target) for attention, triangle and
    OPM; the element-wise/softmax/LN ops run their XLA leg there by default
    (``kernel_leg``), their kernels only when a plan names them. On other
    backends each op runs an XLA-native leg with identical semantics (the jnp
    oracle for the element-wise/softmax/LN ops, the online-softmax lax.scan
    for fused attention) — interpret-mode Pallas is a per-grid-cell loop that
    only runs when the plan asks for interpret mode (the kernel-validation CI
    leg),
  * carries a ``jax.custom_vjp``: fused attention pairs the forward with the
    fused Pallas backward (``flash_attention_bwd_pallas``) on the Pallas leg
    and with the jnp KV-scan recompute backward elsewhere; the remaining ops
    use analytic jnp backwards that XLA fuses,
  * falls back to the pure-jnp oracle (ref.py) when the shape is outside the
    kernel envelope or kernels are globally disabled,
  * runs under ``jax.named_scope("ops.<family>")`` (``_scoped``): the public
    wrapper and the custom_vjp rules, so every HLO op of the family, on
    whichever leg, forward, backward and remat recompute alike, carries the
    family in its ``op_name`` metadata. The device trace attributes time by
    those names; the scope strings are that interface.

Toggle: every leg choice is read from the context-local ExecutionPlan
(``repro.exec.plan.current_plan()`` / ``with use_plan(plan):``) at *trace*
time — ``KernelPolicy(enabled=False)`` (the old REPRO_DISABLE_KERNELS)
forces the oracle paths everywhere, per-op legs pin one op family, and the
attention-backward choice (the old mutable ``FORCE_SCAN_ATTN_BWD``) is baked
into each op call's trace so it scopes correctly under ``use_plan``. Legacy
env vars are honored only through ``ExecutionPlan.from_env()``
(repro/exec/envcompat.py), which is what ``current_plan()`` falls back to
outside any ``use_plan`` scope.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.exec.plan import current_plan
from repro.kernels import ref
from repro.kernels.fused_elementwise import (
    bias_dropout_add_pallas,
    bias_sigmoid_mul_pallas,
)
from repro.kernels.fused_softmax import fused_softmax_pallas
from repro.kernels.layer_norm import layer_norm_pallas

# Kernel envelope: last-dim sizes beyond this would blow the VMEM tile budget
# on the v5e target (ROW_TILE rows * C * 4 B fp32 + headroom in ~16 MB VMEM).
_MAX_SOFTMAX_C = 16384
_MAX_NORM_C = 32768


def _scoped(family: str):
    """Decorator: run the function under ``jax.named_scope("ops." +
    family)``. Scopes are trace-time metadata: no op, no numerics change.
    A fresh scope per call: the object ``jax.named_scope`` returns keeps
    the outer name stack on itself, so one shared object is not
    re-entrant."""
    name = "ops." + family

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return wrapped
    return deco


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Row-wise op families: ``auto`` runs them on their XLA leg on TPU too.
_ROW_OPS = ("softmax", "layer_norm", "elementwise")


def kernel_leg(op: str) -> str:
    """Resolved execution leg for an op family under the current plan:
    'pallas' | 'interpret' | 'xla' | 'oracle'. An explicit per-op leg on
    KernelPolicy wins. 'auto' resolves on TPU (the target) to the Pallas
    kernel for attention, triangle and OPM, and to the XLA leg for the
    row-wise families (``_ROW_OPS``) under every parallel backend: their
    Pallas kernels move one 8-row tile (2-4 KB of bf16) per grid step, so a
    fixed cost per step, not HBM bandwidth, sets their time, while XLA fuses
    the same float32 math into the neighbouring ops. On one v5e the
    full-width train step ran 7.455 s with them on Pallas and 5.166 s on XLA
    (PERF.md section 5); under the 'gspmd' backend the XLA leg is also what
    GSPMD partitions on the global arrays these ops see. Off TPU 'auto'
    resolves to the op's XLA-native leg — interpret-mode Pallas (a
    per-grid-cell loop) only under ``KernelPolicy.interpret`` (the
    kernel-validation CI leg), which is both faster on CPU and safe to lower
    inside large SPMD dry-runs. ``enabled=False`` sends every 'auto' op to
    its jnp oracle."""
    pol = current_plan().kernels
    leg = getattr(pol, op)
    if leg != "auto":
        return leg
    if not pol.enabled:
        return "oracle"
    if jax.default_backend() == "tpu":
        return "xla" if op in _ROW_OPS else "pallas"
    return "interpret" if pol.interpret else "xla"


def _use_pallas(leg: str) -> bool:
    """Whether a resolved leg executes the Pallas kernel (off-TPU both
    'pallas' and 'interpret' run it in interpret mode — there is no compiled
    Pallas backend to target there). For the element-wise/softmax/LN ops the
    'xla' leg IS the float32 jnp oracle (ref.py), which XLA fuses into its
    neighbours and which ``auto`` selects on TPU (``kernel_leg``); their
    Pallas kernels run only when a plan names 'pallas' or 'interpret'. So
    this is their whole routing decision."""
    return leg in ("pallas", "interpret")


def _interpret_for(leg: str) -> bool:
    """Interpret flag for a kernel launch: an explicit 'interpret' leg runs
    interpret mode even ON TPU (kernel-numerics debugging); everything else
    interprets only off-TPU, where no compiled Pallas backend exists."""
    return leg == "interpret" or _interpret()


# ---------------------------------------------------------------------------
# fused softmax
# ---------------------------------------------------------------------------


def _softmax_impl(scale, has_bias, has_mask, x, bias, mask):
    n, h, r, c = x.shape
    leg = kernel_leg("softmax")
    if not _use_pallas(leg) or c > _MAX_SOFTMAX_C:
        return ref.softmax_ref(x, bias if has_bias else None,
                               mask if has_mask else None, scale)
    return fused_softmax_pallas(
        x, bias if has_bias else None, mask if has_mask else None,
        scale=scale, has_bias=has_bias, has_mask=has_mask,
        interpret=_interpret_for(leg),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _softmax_op(scale, has_bias, has_mask, x, bias, mask):
    return _softmax_impl(scale, has_bias, has_mask, x, bias, mask)


@_scoped("softmax")
def _softmax_fwd(scale, has_bias, has_mask, x, bias, mask):
    y = _softmax_impl(scale, has_bias, has_mask, x, bias, mask)
    return y, (y, None if bias is None else bias.shape,
               None if mask is None else mask.shape)


@_scoped("softmax")
def _softmax_bwd(scale, has_bias, has_mask, res, g):
    y, bias_shape, mask_shape = res
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dot = jnp.sum(gf * yf, axis=-1, keepdims=True)
    dlogits = yf * (gf - dot)  # grad wrt (scale*x + bias + mask)
    dx = (dlogits * scale).astype(y.dtype)
    dbias = None
    if has_bias:
        b = bias_shape[0]
        n = y.shape[0]
        dbias = dlogits.reshape((b, n // b) + dlogits.shape[1:]).sum(axis=1)
    dmask = None
    if has_mask:
        dmask = dlogits.sum(axis=(1, 2))
    return dx, dbias, dmask


_softmax_op.defvjp(_softmax_fwd, _softmax_bwd)


@_scoped("softmax")
def fused_softmax(
    x: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    scale: float = 1.0,
    *,
    allow_flatten: bool = True,
) -> jax.Array:
    """softmax(scale*x + bias + mask) over the last axis.

    x: (..., H, R, C) — leading dims are flattened into N for the kernel.
    bias: (H, R, C) or (B, H, R, C), N % B == 0 (each bias batch element is
          shared by N/B consecutive rows), or None.
    mask: additive, shape (..., C) matching x's leading dims, or None.

    5D form (group attention, Evoformer): x (B, G, H, R, C) with bias
    (B, H, R, C) shared across G and mask (B, G, C). When the Pallas leg is
    inactive — or the caller passes ``allow_flatten=False`` because the
    (B, G) dims are mesh-sharded GLOBAL dims (GspmdDist) — this form
    computes WITHOUT flattening: reshaping (B, G) together would merge two
    mesh-sharded dims and force GSPMD to all-gather the whole representation
    (§Perf alphafold iter 3).
    """
    if x.ndim == 5 and not (allow_flatten
                            and _use_pallas(kernel_leg("softmax"))
                            and x.shape[-1] <= _MAX_SOFTMAX_C):
        acc = x.astype(jnp.float32) * scale
        if bias is not None:
            acc = acc + bias.astype(jnp.float32)[:, None]
        if mask is not None:
            acc = acc + mask.astype(jnp.float32)[:, :, None, None, :]
        return jax.nn.softmax(acc, axis=-1).astype(x.dtype)
    if x.ndim == 5:
        b, g, h, r, c = x.shape
        xb = x.reshape((b * g, h, r, c))
        mb = mask.reshape((-1, c)) if mask is not None else None
        out = _softmax_op(scale, bias is not None, mask is not None, xb,
                          bias, mb)
        return out.reshape(x.shape)
    *lead, h, r, c = x.shape
    if bias is not None and bias.ndim == 3:
        bias = bias[None]
    xb = x.reshape((-1, h, r, c))
    mb = mask.reshape((-1, c)) if mask is not None else None
    out = _softmax_op(scale, bias is not None, mask is not None, xb, bias, mb)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# fused flash attention (online softmax over KV tiles; scores never in HBM)
# ---------------------------------------------------------------------------

# Envelope: head dim beyond 256 blows the (kv_tile, d_pad) VMEM working set;
# KV lengths beyond 16k belong to the decoder-LM blockwise path instead.
_MAX_ATTN_D = 256
_MAX_ATTN_S = 16384
_DEFAULT_KV_TILE = 512   # forward KV tile / backward recompute block default


def _attn_envelope_ok(q_shape, kv_len: int | None = None, dtype=None) -> bool:
    """Shape/dtype envelope of the fused attention legs (no plan consult —
    callers with a baked leg use this directly)."""
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    d = q_shape[-1]
    skv = q_shape[-3] if kv_len is None else kv_len
    return d <= _MAX_ATTN_D and skv <= _MAX_ATTN_S


def fused_attention_supported(q_shape, kv_len: int | None = None,
                              dtype=None) -> bool:
    """True when ops.fused_attention will take a fused flash leg (the Pallas
    kernel on TPU, the XLA-native online-softmax leg elsewhere) for this
    shape under the current plan — callers keeping a scores-materialized A/B
    path (the evoformer's KernelPolicy(enabled=False) leg) branch on this.
    The same envelope gates the fused Pallas *backward* (``ops._attn_bwd``):
    forward and backward always agree on which leg owns a shape, so the
    saved (q, k, v, out, lse) residuals are interchangeable. q_shape is the
    4D (N, Sq, H, D) or 5D (B, G, S, H, D) query shape."""
    if kernel_leg("attention") == "oracle":
        return False
    return _attn_envelope_ok(q_shape, kv_len=kv_len, dtype=dtype)


def _attn_tiles(sq: int, skv: int, d: int, kv_tile: int):
    from repro.kernels.flash_attention import LANE, _pad_to

    d_pad = _pad_to(d, LANE)
    # 16-row q tiles: bf16's min sublane tile (f32 needs 8; 16 covers both).
    q_tile = min(128, _pad_to(sq, 16))
    kv = kv_tile or _DEFAULT_KV_TILE
    kv = min(_pad_to(kv, LANE), _pad_to(skv, LANE))
    return q_tile, kv, d_pad


def _pad_nhsd(x, s_to: int, d_to: int):
    """Zero-pad a (N, H, S, D) kernel-layout tensor to (N, H, s_to, d_to)."""
    _, _, ss, dd = x.shape
    if ss == s_to and dd == d_to:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, s_to - ss), (0, d_to - dd)))


def _attn_stage_padded(kv_tile, q, k, v, bias, mask):
    """Shared fwd/bwd staging into the padded Pallas kernel layout — one
    source of truth so the backward kernel always sees tiles padded under
    the same rules as the forward that saved its residuals. Returns
    (qt, kt, vt, bt, mt, q_tile, kv_t, sq_pad, skv_pad) with q/k/v
    transposed to (N, H, S, D) and S/D padded to the tile grid."""
    from repro.kernels.flash_attention import _pad_to

    n, sq, h, d = q.shape
    skv = k.shape[1]
    q_tile, kv_t, d_pad = _attn_tiles(sq, skv, d, kv_tile)
    sq_pad = _pad_to(sq, q_tile)
    skv_pad = _pad_to(skv, kv_t)
    qt = _pad_nhsd(q.transpose(0, 2, 1, 3), sq_pad, d_pad)
    kt = _pad_nhsd(k.transpose(0, 2, 1, 3), skv_pad, d_pad)
    vt = _pad_nhsd(v.transpose(0, 2, 1, 3), skv_pad, d_pad)
    bt = None
    if bias is not None:
        bt = jnp.pad(bias, ((0, 0), (0, 0), (0, sq_pad - sq),
                            (0, skv_pad - skv)))
    mt = None
    if mask is not None:
        mt = jnp.pad(mask, ((0, 0), (0, skv_pad - skv)))[:, None, :]
    return qt, kt, vt, bt, mt, q_tile, kv_t, sq_pad, skv_pad


def _attn_fwd_impl(scale, has_bias, has_mask, kv_tile, leg, q, k, v, bias,
                   mask):
    """Returns (out (N, Sq, H, D), lse (N, H, Sq)). ``leg`` is the kernel
    leg resolved (from the plan) when the op was called — baked into the
    trace so forward, residuals, and backward always agree."""
    n, sq, h, d = q.shape
    skv = k.shape[1]
    bias = bias if has_bias else None
    mask = mask if has_mask else None
    if leg == "oracle" or not _attn_envelope_ok(q.shape, kv_len=skv,
                                               dtype=q.dtype):
        return ref.attention_ref(q, k, v, bias, mask, scale)
    if not _use_pallas(leg):
        # XLA-native online-softmax leg (non-TPU backends): same math, same
        # (out, lse) residuals, lax.scan over KV tiles instead of the kernel
        # grid — interpret-mode Pallas is ~2x this path on CPU smoke shapes.
        from repro.kernels.flash_attention import flash_attention_xla

        kvb = min(kv_tile or _DEFAULT_KV_TILE, skv)
        return flash_attention_xla(q, k, v, bias, mask, scale=scale,
                                   kv_tile=kvb)
    from repro.kernels.flash_attention import flash_attention_pallas

    qt, kt, vt, bt, mt, q_tile, kv_t, sq_pad, skv_pad = _attn_stage_padded(
        kv_tile, q, k, v, bias, mask)
    out, lse = flash_attention_pallas(
        qt, kt, vt, bt, mt, scale=scale, kv_len=skv, q_tile=q_tile,
        kv_tile=kv_t, has_bias=bias is not None, has_mask=mask is not None,
        interpret=_interpret_for(leg),
    )
    return out[:, :, :sq, :d].transpose(0, 2, 1, 3), lse[:, :, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _attn_op(scale, has_bias, has_mask, kv_tile, leg, bwd, q, k, v, bias,
             mask):
    out, _ = _attn_fwd_impl(scale, has_bias, has_mask, kv_tile, leg, q, k, v,
                            bias, mask)
    return out


@_scoped("attention")
def _attn_fwd(scale, has_bias, has_mask, kv_tile, leg, bwd, q, k, v, bias,
              mask):
    out, lse = _attn_fwd_impl(scale, has_bias, has_mask, kv_tile, leg, q, k,
                              v, bias, mask)
    # Flash recompute residuals: only (q, k, v, out, lse) + the (already
    # HBM-resident) bias/mask inputs — never the (N, H, Sq, Skv) probs.
    return out, (q, k, v, bias, mask, out, lse)


def _attn_bwd_pallas(scale, has_bias, has_mask, kv_tile, leg, res, g):
    """Fused Pallas backward: dq/dk/dv (and the bias/mask reductions) are
    computed tile-by-tile in VMEM by flash_attention_bwd_pallas from the
    saved (q, k, v, out, lse) — the fp32 (N, H, Sq, kv_block) recompute
    transient of the jnp KV-scan backward never reaches HBM. Same envelope
    as the forward kernel; the scan below stays as the oracle leg."""
    q, k, v, bias, mask, out, lse = res
    n, sq, h, d = q.shape
    skv = k.shape[1]
    from repro.kernels.flash_attention import flash_attention_bwd_pallas

    qt, kt, vt, bt, mt, q_tile, kv_t, sq_pad, skv_pad = _attn_stage_padded(
        kv_tile, q, k, v, bias, mask)
    gf = g.astype(jnp.float32)
    delta = jnp.einsum("nqhd,nqhd->nhq", gf, out.astype(jnp.float32))
    dot = _pad_nhsd(g.astype(q.dtype).transpose(0, 2, 1, 3), sq_pad,
                    qt.shape[-1])
    lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_pad - sq)))
    delta_p = jnp.pad(delta, ((0, 0), (0, 0), (0, sq_pad - sq)))
    dq, dk, dv, dbias, dmask_h = flash_attention_bwd_pallas(
        qt, kt, vt, dot, lse_p, delta_p, bt, mt, scale=scale, kv_len=skv,
        q_tile=q_tile, kv_tile=kv_t, has_bias=has_bias, has_mask=has_mask,
        interpret=_interpret_for(leg),
    )
    dq = dq[:, :, :sq, :d].transpose(0, 2, 1, 3).astype(q.dtype)
    dk = dk[:, :, :skv, :d].transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv[:, :, :skv, :d].transpose(0, 2, 1, 3).astype(v.dtype)
    db = None
    if has_bias:
        db = dbias[:, :, :sq, :skv].astype(bias.dtype)
    dm = None
    if has_mask:
        dm = dmask_h.sum(axis=1)[:, :skv].astype(mask.dtype)
    return dq, dk, dv, db, dm


@_scoped("attention")
def _attn_bwd(scale, has_bias, has_mask, kv_tile, leg, bwd, res, g):
    """Recompute backward. On the Pallas leg (TPU, or forced interpret) and
    in-envelope shapes: the fused flash_attention_bwd_pallas kernel. Oracle
    leg: scan over KV blocks, rebuilding the probs block from (q, k, lse) —
    peak transient is (N, H, Sq, kv_block), never the full scores tensor
    (mirrors layers/attention._flash_bwd, plus bias/mask). ``leg``/``bwd``
    were resolved from the plan when the op was *called*, so a use_plan
    scope around the op call governs this backward even though it is traced
    later (KernelPolicy.attn_bwd='scan' pins the scan for A/B)."""
    q, k, v, bias, mask, out, lse = res
    if (_use_pallas(leg) and bwd != "scan"
            and _attn_envelope_ok(q.shape, kv_len=k.shape[1],
                                  dtype=q.dtype)):
        return _attn_bwd_pallas(scale, has_bias, has_mask, kv_tile, leg,
                                res, g)
    n, sq, h, d = q.shape
    skv = k.shape[1]
    kvb = min(kv_tile or _DEFAULT_KV_TILE, skv)
    nkv = -(-skv // kvb)
    skv_pad = nkv * kvb
    from repro.kernels.flash_attention import (
        apply_block_bias_mask, stage_kv_blocks)

    xs = stage_kv_blocks(k, v, bias if has_bias else None,
                         mask if has_mask else None, kvb)

    gf = g.astype(jnp.float32)
    delta = jnp.einsum("nqhd,nqhd->nhq", gf, out.astype(jnp.float32))

    def kv_step(dq, blk):
        k_j, v_j = blk["k"], blk["v"]
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k_j,
                       preferred_element_type=jnp.float32) * scale
        s = apply_block_bias_mask(s, blk, n)
        p = jnp.exp(s - lse[..., None])                    # (N, H, Sq, kvb)
        dv_j = jnp.einsum("nhqk,nqhd->nkhd", p, gf)
        dp = jnp.einsum("nqhd,nkhd->nhqk", gf, v_j.astype(jnp.float32))
        ds = p * (dp - delta[..., None])                   # d(logits)
        dq = dq + jnp.einsum("nhqk,nkhd->nqhd", ds,
                             k_j.astype(jnp.float32)) * scale
        dk_j = jnp.einsum("nhqk,nqhd->nkhd", ds,
                          q.astype(jnp.float32)) * scale
        ys = {"dk": dk_j, "dv": dv_j}
        if has_bias:
            nb = bias.shape[0]
            ys["db"] = ds.reshape((nb, n // nb) + ds.shape[1:]).sum(axis=1)
        if has_mask:
            ys["dm"] = ds.sum(axis=(1, 2))
        return dq, ys

    dq0 = jnp.zeros((n, sq, h, d), jnp.float32)
    dq, ys = jax.lax.scan(kv_step, dq0, xs)
    dk = ys["dk"].swapaxes(0, 1).reshape(n, skv_pad, h, d)[:, :skv]
    dv = ys["dv"].swapaxes(0, 1).reshape(n, skv_pad, h, v.shape[-1])[:, :skv]
    dbias = None
    if has_bias:
        dbias = (ys["db"].transpose(1, 2, 3, 0, 4)
                 .reshape(bias.shape[0], h, sq, skv_pad)[..., :skv]
                 .astype(bias.dtype))
    dmask = None
    if has_mask:
        dmask = (ys["dm"].swapaxes(0, 1).reshape(n, skv_pad)[:, :skv]
                 .astype(mask.dtype))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias, dmask)


_attn_op.defvjp(_attn_fwd, _attn_bwd)


@_scoped("attention")
def fused_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    scale: float | None = None,
    kv_tile: int = 0,
) -> jax.Array:
    """Flash-style fused gated attention: softmax(scale*qk^T + bias + mask)@v
    with online softmax over KV tiles — the scores tensor never reaches HBM.

    4D form: q (N, Sq, H, D); k, v (N, Skv, H, D); bias (B, H, Sq, Skv) with
        N % B == 0 (or (H, Sq, Skv) as B=1); mask (N, Skv) additive fp32.
    5D form (Evoformer group attention): q, k, v (B, G, S, H, D) with bias
        (B, H, S, S) shared across G and mask (B, G, S) additive. The (B, G)
        dims are flattened for the kernel — callers whose (B, G) dims are
        *mesh-sharded* must hand LOCAL blocks to this function (the
        ``dist.sharded_attention`` hook in core/dist.py: shard_map under
        GSPMD), or the flatten merges two sharded dims and forces an
        all-gather of the whole representation.

    ``scale`` defaults to 1/sqrt(D). ``kv_tile`` (0 = default 512) bounds the
    forward KV tile and the backward recompute block/tile — AutoChunk
    (repro.memory.autochunk) plans it from the HBM budget.

    custom_vjp: forward saves only (q, k, v, out, lse); the backward rebuilds
    the probs from them. On the Pallas leg the fused
    ``flash_attention_bwd_pallas`` kernel computes dq/dk/dv and the
    bias/mask reductions tile-by-tile in VMEM (same envelope as the forward:
    D <= 256, Skv <= 16384, fp32/bf16); elsewhere a jnp KV-block scan with a
    (N, H, Sq, kv_block) fp32 transient is the oracle leg
    (``KernelPolicy.attn_bwd='scan'`` pins it for A/B). Mask values must be
    finite (~-1e9, not -inf). Out-of-envelope shapes and
    KernelPolicy(enabled=False) fall back to the scores-materialized oracle
    (ref.attention_ref) under the same VJP. Leg choices are resolved from
    ``current_plan()`` here, once, and baked into the trace.
    """
    leg = kernel_leg("attention")
    bwd = current_plan().kernels.attn_bwd
    d = q.shape[-1]
    assert k.shape[-1] == d and v.shape[-1] == d, (q.shape, k.shape, v.shape)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if q.ndim == 5:
        b, grp, sq, h, _ = q.shape
        skv = k.shape[2]
        qf = q.reshape(b * grp, sq, h, d)
        kf = k.reshape(b * grp, skv, h, d)
        vf = v.reshape(b * grp, skv, h, d)
        mb = mask.reshape(b * grp, skv) if mask is not None else None
        out = _attn_op(scale, bias is not None, mask is not None, kv_tile,
                       leg, bwd, qf, kf, vf, bias, mb)
        return out.reshape(q.shape)
    if bias is not None and bias.ndim == 3:
        bias = bias[None]
    return _attn_op(scale, bias is not None, mask is not None, kv_tile,
                    leg, bwd, q, k, v, bias, mask)


# ---------------------------------------------------------------------------
# fused triangle multiplicative update + outer-product-mean (pair stack)
# ---------------------------------------------------------------------------

# Envelope: the triangle epilogue keeps (i_t*j_t, C) operands in VMEM —
# bound C (triangle channel). The OPM bound on its channel is set by its
# (C, i_t·C, j_t) fp32 accumulator fitting the kernels' scoped VMEM
# (triangle.VMEM_LIMIT_BYTES): c=32 → 4 MiB, c=64 → 16 MiB at i_t=8,
# j_t=128; c=128 would need 64 MiB.
_MAX_TRI_C = 1024
_MAX_OPM_C = 64
# Default j output block of the XLA legs and the backward recompute scans
# (the HBM-visible transient the AutoChunk planner models). The Pallas
# kernels' k/s accumulation tile default is kernels/triangle.py
# DEFAULT_PALLAS_TILE.
_DEFAULT_TRI_TILE = 128
_DEFAULT_OPM_TILE = 128


def _tri_dtype_ok(dtype) -> bool:
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16))


def fused_triangle_supported(c: int, d: int, dtype=None) -> bool:
    """True when ops.fused_triangle_mult takes a fused leg (Pallas on TPU /
    interpret, the XLA j-block scan elsewhere) for this channel size/dtype
    under the current plan. Callers keeping the materialized A/B path (the
    Evoformer's KernelPolicy(enabled=False) leg, or the per-op
    ``triangle='oracle'`` pin of the ci.sh triangle-oracle preset) branch
    on this."""
    if kernel_leg("triangle") == "oracle":
        return False
    if dtype is not None and not _tri_dtype_ok(dtype):
        return False
    return c <= _MAX_TRI_C and d <= _MAX_TRI_C


def fused_opm_supported(c: int, d: int, dtype=None) -> bool:
    """Same contract as fused_triangle_supported, for the outer-product-mean
    (c is the OPM channel — the kernel tile holds c² lanes); routed by the
    plan's ``opm`` leg."""
    if kernel_leg("opm") == "oracle":
        return False
    if dtype is not None and not _tri_dtype_ok(dtype):
        return False
    return c <= _MAX_OPM_C and d <= _MAX_TRI_C


def _tri_fwd_impl(eps, tile, leg, a_lin, ga, mask, b_full, gamma, beta,
                  w_out, b_out, g_lin, g_bias):
    from repro.kernels import triangle as tri

    if _use_pallas(leg):
        return tri.fused_triangle_pallas(
            a_lin, ga, mask, b_full, gamma, beta, w_out, b_out, g_lin,
            g_bias, eps=eps, k_tile=tile, interpret=_interpret_for(leg))
    a = tri.triangle_gate_a(a_lin, ga, mask)
    return tri.fused_triangle_xla(
        a, b_full, g_lin, gamma, beta, w_out, b_out, g_bias, eps=eps,
        j_block=tile or _DEFAULT_TRI_TILE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _tri_op(eps, tile, leg, a_lin, ga, mask, b_full, gamma, beta, w_out,
            b_out, g_lin, g_bias):
    out, _, _ = _tri_fwd_impl(eps, tile, leg, a_lin, ga, mask, b_full, gamma,
                              beta, w_out, b_out, g_lin, g_bias)
    return out


@_scoped("triangle")
def _tri_fwd(eps, tile, leg, a_lin, ga, mask, b_full, gamma, beta, w_out,
             b_out, g_lin, g_bias):
    out, mean, inv = _tri_fwd_impl(eps, tile, leg, a_lin, ga, mask, b_full,
                                   gamma, beta, w_out, b_out, g_lin, g_bias)
    # Recompute residuals: inputs + per-tile LN stats + the (already
    # HBM-resident) output — never the (B, I, J, C) product. `out` gives the
    # output-gate cotangent directly (g·out·(1-s), see triangle_mult_bwd).
    return out, (a_lin, ga, mask, b_full, gamma, beta, w_out, b_out, g_lin,
                 g_bias, mean, inv, out)


@_scoped("triangle")
def _tri_bwd(eps, tile, leg, res, g):
    from repro.kernels.triangle import triangle_mult_bwd

    return triangle_mult_bwd(eps, tile or _DEFAULT_TRI_TILE, res, g)


_tri_op.defvjp(_tri_fwd, _tri_bwd)


@_scoped("triangle")
def fused_triangle_mult(
    a_lin: jax.Array,
    ga: jax.Array,
    mask: jax.Array,
    b_full: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    w_out: jax.Array,
    b_out: jax.Array,
    g_lin: jax.Array,
    g_bias: jax.Array,
    *,
    eps: float = 1e-5,
    tile: int = 0,
) -> jax.Array:
    """Fused triangular multiplicative update:
    ``sigmoid(g_lin + g_bias) * (LN_c(sum_k (a_lin·σ(ga)·mask) ⊙ b_full) @
    w_out + b_out)`` in one sweep — the k-tiled product, input gating, pair
    mask, output LayerNorm and the bias_sigmoid_mul output gate never
    materialize intermediates at full (B, I, J, C) size.

    Shapes: a_lin/ga (B, I, K, C); mask (B, I, K); b_full (B, J, K, C)
    (gated+masked right operand — gathered under DAP; callers whose I dim is
    mesh-sharded go through ``dist.sharded_triangle`` so the kernel sees
    local blocks); gamma/beta (C,); w_out (C, D); b_out/g_bias (D,);
    g_lin (B, I, J, D). ``tile`` is the Pallas k tile / XLA j block /
    backward recompute block (0 = leg default: Pallas 128, XLA/backward
    128) — AutoChunk plans it as ``tri_k_tile``.

    custom_vjp: forward saves inputs + per-tile (mean, inv) LN stats; the
    backward rebuilds the product per j block (kernels/triangle.py).
    Out-of-envelope dtypes/channels, KernelPolicy(enabled=False), and the
    per-op ``triangle='oracle'`` leg fall back to ref.triangle_mult_ref.
    """
    if not fused_triangle_supported(a_lin.shape[-1], w_out.shape[-1],
                                    a_lin.dtype):
        return ref.triangle_mult_ref(a_lin, ga, mask, b_full, gamma, beta,
                                     w_out, b_out, g_lin, g_bias, eps)
    return _tri_op(eps, tile, kernel_leg("triangle"), a_lin, ga, mask,
                   b_full, gamma, beta, w_out, b_out, g_lin, g_bias)


def _opm_fwd_impl(tile, leg, a, b_full, mask_a, mask_b, w, bias):
    from repro.kernels import triangle as tri

    if _use_pallas(leg):
        return tri.fused_opm_pallas(a, b_full, mask_a, mask_b, w, bias,
                                    s_tile=tile,
                                    interpret=_interpret_for(leg))
    return tri.fused_opm_xla(a, b_full, mask_a, mask_b, w, bias,
                             j_block=tile or _DEFAULT_OPM_TILE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _opm_op(tile, leg, a, b_full, mask_a, mask_b, w, bias):
    return _opm_fwd_impl(tile, leg, a, b_full, mask_a, mask_b, w, bias)


@_scoped("opm")
def _opm_fwd(tile, leg, a, b_full, mask_a, mask_b, w, bias):
    out = _opm_fwd_impl(tile, leg, a, b_full, mask_a, mask_b, w, bias)
    # Residuals: inputs + the (already HBM-resident) output — `out` turns
    # the mask-norm cotangent into a cheap (B, I, J, D) contraction instead
    # of a full ov·(g@wᵀ) reduction over c² (see opm_bwd).
    return out, (a, b_full, mask_a, mask_b, w, bias, out)


@_scoped("opm")
def _opm_bwd(tile, leg, res, g):
    from repro.kernels.triangle import opm_bwd

    return opm_bwd(tile or _DEFAULT_OPM_TILE, res, g)


_opm_op.defvjp(_opm_fwd, _opm_bwd)


@_scoped("opm")
def fused_outer_product_mean(
    a: jax.Array,
    b_full: jax.Array,
    mask_a: jax.Array,
    mask_b: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    *,
    tile: int = 0,
) -> jax.Array:
    """Fused outer-product-mean: s-tiled accumulation of
    ``sum_s a_si ⊗ b_sj`` with the fp32 mask-normalization and the c²→d
    projection fused, so the (B, I, J, C, C) transient never reaches HBM at
    full size.

    Shapes: a (B, S, I, C), b_full (B, S, J, C) masked projections (b
    gathered under DAP — mesh-sharded I goes through ``dist.sharded_opm``);
    mask_a (B, S, I), mask_b (B, S, J); w (C*C, D), bias (D,). ``tile`` is
    the Pallas s tile / XLA j block / backward recompute block (0 = leg
    default: Pallas 128, XLA/backward 128) — AutoChunk plans it as
    ``opm_s_tile``.

    custom_vjp: forward saves only the inputs (the mask-norm is recomputed);
    the backward rebuilds the normalized outer product per j block.
    Fallbacks mirror fused_triangle_mult (ref.outer_product_mean_ref).
    """
    if not fused_opm_supported(a.shape[-1], w.shape[-1], a.dtype):
        return ref.outer_product_mean_ref(a, b_full, mask_a, mask_b, w, bias)
    return _opm_op(tile, kernel_leg("opm"), a, b_full, mask_a, mask_b, w,
                   bias)


# ---------------------------------------------------------------------------
# layer norm
# ---------------------------------------------------------------------------


def _ln_impl(eps, x, gamma, beta):
    # The public layer_norm wrapper routes the oracle leg (Pallas inactive /
    # over-envelope C) before flattening; only the kernel leg reaches here.
    return layer_norm_pallas(x, gamma, beta, eps=eps,
                             interpret=_interpret_for(kernel_leg("layer_norm")))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ln_op(eps, x, gamma, beta):
    return _ln_impl(eps, x, gamma, beta)


@_scoped("layer_norm")
def _ln_fwd(eps, x, gamma, beta):
    return _ln_impl(eps, x, gamma, beta), (x, gamma)


@_scoped("layer_norm")
def _ln_bwd(eps, res, g):
    x, gamma = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * inv
    lead = tuple(range(x.ndim - 1))
    dgamma = jnp.sum(gf * xhat, axis=lead)
    dbeta = jnp.sum(gf, axis=lead)
    gg = gf * gamma.astype(jnp.float32)
    dx = inv * (
        gg
        - jnp.mean(gg, axis=-1, keepdims=True)
        - xhat * jnp.mean(gg * xhat, axis=-1, keepdims=True)
    )
    return dx.astype(x.dtype), dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


_ln_op.defvjp(_ln_fwd, _ln_bwd)


@_scoped("layer_norm")
def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    """LayerNorm over the last axis; any leading shape.

    The Pallas leg is rank-polymorphic for 2D-4D inputs (grid over the
    leading dims, no row-flatten) so mesh-sharded (B, G, ...) leading dims
    stay unmerged under GSPMD — same contract as the oracle leg. Only 1D /
    5D+ shapes (outside the Evoformer layouts) reshape."""
    c = x.shape[-1]
    if not _use_pallas(kernel_leg("layer_norm")) or c > _MAX_NORM_C:
        # Oracle path without flattening (see bias_sigmoid_mul): keeps
        # mesh-sharded leading dims unmerged under GSPMD.
        return ref.layer_norm_ref(x, gamma, beta, eps)
    if 2 <= x.ndim <= 4:
        return _ln_op(eps, x, gamma, beta)
    xb = x.reshape((-1, c))
    return _ln_op(eps, xb, gamma, beta).reshape(x.shape)


# ---------------------------------------------------------------------------
# bias + sigmoid + mul (gating)
# ---------------------------------------------------------------------------


def _bsm_impl(g, bg, v):
    # The public bias_sigmoid_mul wrapper routes the oracle leg before
    # flattening; only the kernel leg reaches here.
    return bias_sigmoid_mul_pallas(
        g, bg, v, interpret=_interpret_for(kernel_leg("elementwise")))


@jax.custom_vjp
def _bsm_op(g, bg, v):
    return _bsm_impl(g, bg, v)


@_scoped("bias_sigmoid_mul")
def _bsm_fwd(g, bg, v):
    return _bsm_impl(g, bg, v), (g, bg, v)


@_scoped("bias_sigmoid_mul")
def _bsm_bwd(res, grad):
    g, bg, v = res
    gradf = grad.astype(jnp.float32)
    s = jax.nn.sigmoid(g.astype(jnp.float32) + bg.astype(jnp.float32))
    dv = (gradf * s).astype(v.dtype)
    dg_f = gradf * v.astype(jnp.float32) * s * (1.0 - s)
    dg = dg_f.astype(g.dtype)
    dbg = dg_f.sum(axis=tuple(range(g.ndim - 1))).astype(bg.dtype)
    return dg, dbg, dv


_bsm_op.defvjp(_bsm_fwd, _bsm_bwd)


@_scoped("bias_sigmoid_mul")
def bias_sigmoid_mul(g: jax.Array, bg: jax.Array, v: jax.Array) -> jax.Array:
    """sigmoid(g + bg) * v; g and v share shape (..., C), bg is (C,).

    Rank-polymorphic Pallas leg for 2D-4D inputs (grid over the leading
    dims): no row-flatten, so mesh-sharded leading dims stay unmerged under
    GSPMD — matching the oracle leg."""
    c = g.shape[-1]
    if not _use_pallas(kernel_leg("elementwise")) or c > _MAX_NORM_C:
        # Oracle path without flattening: reshaping (B, G, ...) to rows would
        # merge mesh-sharded dims under GSPMD and force a resharding copy of
        # the whole tensor (same note as fused_softmax 5D / bias_dropout_add).
        return ref.bias_sigmoid_mul_ref(g, bg, v)
    if 2 <= g.ndim <= 4:
        return _bsm_op(g, bg, v)
    out = _bsm_op(g.reshape((-1, c)), bg, v.reshape((-1, c)))
    return out.reshape(v.shape)


# ---------------------------------------------------------------------------
# bias + dropout + add (residual)
# ---------------------------------------------------------------------------


def _bda_impl(rate, x, b, residual, keep):
    c = x.shape[-1]
    leg = kernel_leg("elementwise")
    if not _use_pallas(leg) or c > _MAX_NORM_C:
        return ref.bias_dropout_add_ref(x, b, residual,
                                        keep if rate > 0.0 else None, rate)
    return bias_dropout_add_pallas(x, b, residual, keep, rate=rate,
                                   interpret=_interpret_for(leg))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bda_op(rate, x, b, residual, keep):
    return _bda_impl(rate, x, b, residual, keep)


@_scoped("bias_dropout_add")
def _bda_fwd(rate, x, b, residual, keep):
    return _bda_impl(rate, x, b, residual, keep), (keep,)


@_scoped("bias_dropout_add")
def _bda_bwd(rate, res, g):
    (keep,) = res
    gf = g.astype(jnp.float32)
    if rate > 0.0:
        dx_f = gf * keep / (1.0 - rate)
    else:
        dx_f = gf
    return (dx_f.astype(g.dtype), dx_f.sum(axis=0).astype(g.dtype), g,
            jnp.zeros_like(keep))


_bda_op.defvjp(_bda_fwd, _bda_bwd)


@_scoped("bias_dropout_add")
def bias_dropout_add(
    x: jax.Array,
    b: jax.Array | None,
    residual: jax.Array,
    rate: float = 0.0,
    rng: jax.Array | None = None,
    shared_axes: tuple[int, ...] = (),
) -> jax.Array:
    """residual + dropout(x + b, rate); rng=None or rate=0 disables dropout.

    ``b=None`` means no bias term (the Evoformer residual adds — the update's
    output projection already carries its bias).

    ``shared_axes``: axes of ``x`` along which the dropout mask is SHARED
    (AlphaFold row/column dropout: one Bernoulli draw at the reduced shape,
    broadcast along the named axes). The scale/mask/add still run in one
    fused HBM pass.
    """
    c = x.shape[-1]
    if b is None and (rng is None or rate == 0.0):
        # Pure residual add: no bias operand, no dropout mask. XLA fuses the
        # fp32-accumulate add chain into one HBM pass on its own; running the
        # kernel here would stream an all-ones keep mask and a zero bias for
        # nothing. Same math as the kernel epilogue (fp32 add, cast back).
        return (x.astype(jnp.float32)
                + residual.astype(jnp.float32)).astype(residual.dtype)
    keep_full = None
    eff_rate = 0.0
    if rng is not None and rate > 0.0:
        shape = list(x.shape)
        for ax in shared_axes:
            shape[ax] = 1
        keep_full = jnp.broadcast_to(
            jax.random.bernoulli(rng, 1.0 - rate, tuple(shape)), x.shape
        ).astype(jnp.float32)
        eff_rate = rate
    if b is None:
        b = jnp.zeros((c,), x.dtype)
    if not _use_pallas(kernel_leg("elementwise")) or c > _MAX_NORM_C:
        # Oracle path without flattening: reshaping (B, G, ...) to rows would
        # merge mesh-sharded dims under GSPMD (same note as fused_softmax 5D).
        return ref.bias_dropout_add_ref(x, b, residual, keep_full, eff_rate)
    xb = x.reshape((-1, c))
    rb = residual.reshape((-1, c))
    keep = (keep_full.reshape((-1, c)) if keep_full is not None
            else jnp.ones_like(xb, dtype=jnp.float32))
    out = _bda_op(eff_rate, xb, b, rb, keep)
    return out.reshape(residual.shape)
