"""Work of the algorithm, counted from shapes: floating-point operations
(2 per multiply-add of a matrix product) and the bytes a kernel has to move
at the least. Padding to tiles and recomputation are not counted, so a later
kernel that pads or recomputes less is judged against the same work.

Hand count for one pass of the published model (``d_msa`` 256, ``d_pair``
128, 8 MSA heads, 4 pair heads, head dim 32, OPM 32, triangle 128,
transition x4, 48 blocks) at n_res r = 256, n_seq s = 128, batch 1, per
block, in GFLOP:

    MSA row attention  bias 0.13, qkv 12.88, gate 4.29, QK 4.29, PV 4.29,
                       out 4.29                                     30.2
    MSA column attn    qkv 12.88, gate 4.29, QK 2.15, PV 2.15, out 4.29 25.8
    MSA transition     2 x 17.18                                    34.4
    outer product mean proj 1.07, outer 17.18, out 17.18           35.4
    triangle mult x2   proj 4.29, gate 4.29, gate_out 2.15,
                       product 4.29, out 2.15                   2 x 17.2
    triangle attn x2   bias 0.07, qkv 6.44, gate 2.15, QK 4.29,
                       PV 4.29, out 2.15                        2 x 19.4
    pair transition    2 x 8.59                                     17.2
                                                    per block  ~216.1

48 blocks give 10.37 TFLOP; the embeddings, the structure module's 8 IPA
iterations and the heads add ~0.03, so one pass is ~10.4 TFLOP. A fold is
``n_recycle + 1`` passes. A train step is ``n_recycle`` passes without
gradient, the last pass forward, and its backward at twice the forward:
``n_recycle + 3`` pass-equivalents, ~62 TFLOP at the shapes above.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _attention(g, s, d_in, heads, hd, d_out, batch):
    proj = 2 * g * s * d_in * 4 * heads * hd          # q, k, v and the gate
    core = 2 * 2 * g * heads * s * s * hd             # QK^T and PV
    out = 2 * g * s * heads * hd * d_out
    return batch * (proj + core + out)


def block_flops(d: dict, r: int, s: int, b: int = 1) -> int:
    dm, dz, hd = d["d_msa"], d["d_pair"], d["head_dim"]
    hm, hz, f = d["msa_heads"], d["pair_heads"], d["transition_factor"]
    c_opm, c_tri = d["opm_dim"], d["tri_mult_dim"]
    msa_row = 2 * b * r * r * dz * hm + _attention(s, r, dm, hm, hd, dm, b)
    msa_col = _attention(r, s, dm, hm, hd, dm, b)
    msa_trans = 2 * 2 * b * s * r * dm * f * dm
    opm = b * (2 * s * r * dm * 2 * c_opm + 2 * r * r * s * c_opm ** 2
               + 2 * r * r * c_opm ** 2 * dz)
    tri_mult = b * (2 * 2 * r * r * dz * 2 * c_tri + 2 * r * r * dz * dz
                    + 2 * r ** 3 * c_tri + 2 * r * r * c_tri * dz)
    tri_attn = 2 * b * r * r * dz * hz + _attention(r, r, dz, hz, hd, dz, b)
    pair_trans = 2 * 2 * b * r * r * dz * f * dz
    return (msa_row + msa_col + msa_trans + opm + 2 * tri_mult
            + 2 * tri_attn + pair_trans)


def structure_flops(d: dict, r: int, b: int = 1) -> int:
    cs, cz, h, c = d["c_s"], d["d_pair"], d["ipa_heads"], d["ipa_c_hidden"]
    qp, vp = d["ipa_qk_points"], d["ipa_v_points"]
    proj = 2 * r * cs * (3 * h * c + h * (2 * qp + vp) * 3)
    logits = 2 * r * r * cz * h + 2 * h * r * r * c + 2 * r * r * h * qp * 3
    values = 2 * h * r * r * (c + cz + vp * 3)
    out = 2 * r * (h * c + h * cz + h * vp * 4) * cs
    trans = 3 * 2 * r * cs * cs + 2 * r * cs * 6
    return b * (2 * r * cs * cs
                + d["structure_iterations"] * (proj + logits + values + out
                                               + trans))


def pass_flops(d: dict, r: int, s: int, b: int = 1) -> int:
    """One recycling pass: embeddings, the Evoformer stack, the structure
    module and the heads."""
    dm, dz = d["d_msa"], d["d_pair"]
    embed = b * (2 * s * r * 23 * dm + 2 * r * 21 * (dm + 2 * dz)
                 + 2 * r * r * (65 + d["recycle_bins"]) * dz)
    heads = b * (2 * r * dm * d["c_s"] + 2 * s * r * dm * 23
                 + 2 * r * r * dz * 64)
    return (embed + d["n_blocks"] * block_flops(d, r, s, b)
            + structure_flops(d, r, b) + heads)


def fold_flops(d: dict, shapes: dict) -> int:
    return (d["n_recycle"] + 1) * pass_flops(d, shapes["n_res"],
                                             shapes["n_seq"], shapes["batch"])


def train_step_flops(d: dict, shapes: dict) -> int:
    return (d["n_recycle"] + 3) * pass_flops(d, shapes["n_res"],
                                             shapes["n_seq"], shapes["batch"])


# ---------------------------------------------------------------------------
# kernels: (flops, bytes) of one call, per device under DAP
# ---------------------------------------------------------------------------


def attention_call(n, s, heads, hd, bias_b, backward=False, dap=1):
    """Flash attention on (N, S, H, D) bf16 q, k, v with an fp32 additive
    key mask (N, S) and, when ``bias_b``, a bf16 (bias_b, H, S, S) pair bias
    that every device reads whole. Forward: QK^T and PV, reading q, k, v,
    mask and bias and writing the output. Backward: dV, dP, dQ, dK (the
    recompute of QK^T is not counted), reading q, k, v, out, dout, the fp32
    log-sum-exp, mask and bias and writing dq, dk, dv and dbias."""
    n_loc = n / dap
    qkv = n_loc * s * heads * hd * BF16
    mask = n_loc * s * F32
    bias = bias_b * heads * s * s * BF16
    flops = 4 * n_loc * heads * s * s * hd
    if not backward:
        return flops, 4 * qkv + mask + bias
    lse = n_loc * heads * s * F32
    return 2 * flops, 8 * qkv + lse + mask + 2 * bias


def attention_sites(d: dict, shapes: dict, backward=False, dap=1):
    """[(flops, bytes, calls)] of one pass through the four attention sites
    of every block: one call per site, block and device."""
    r, s, b = shapes["n_res"], shapes["n_seq"], shapes["batch"]
    hm, hz, hd = d["msa_heads"], d["pair_heads"], d["head_dim"]
    n = d["n_blocks"] * dap
    return [(*attention_call(b * s, r, hm, hd, b, backward, dap), n),
            (*attention_call(b * r, s, hm, hd, 0, backward, dap), n),
            (*attention_call(b * r, r, hz, hd, b, backward, dap), 2 * n)]


def triangle_call(d: dict, shapes: dict, dap=1):
    """Fused triangle multiplication on one device: a (I, K, C) gated and
    masked against the gathered b (J, K, C), LN over C, C -> D projection
    and output gate. Reads a, its gate logits, the fp32 mask, b and the
    (I, J, D) gate logits; writes (I, J, D); all bf16 but the mask."""
    r, b = shapes["n_res"], shapes["batch"]
    c, dz = d["tri_mult_dim"], d["d_pair"]
    i = r / dap
    flops = b * (2 * i * r * r * c + 2 * i * r * c * dz)
    byts = b * (2 * i * r * c * BF16 + i * r * F32 + r * r * c * BF16
                + 2 * i * r * dz * BF16)
    return flops, byts


def _times(calls, n):
    return [(f, b, c * n) for f, b, c in calls]


def attention_train(d, sh, dap):
    """Forward in every pass (the recycles and the gradient pass), backward
    in the gradient pass."""
    return (_times(attention_sites(d, sh, False, dap), d["n_recycle"] + 1)
            + attention_sites(d, sh, True, dap))


def attention_fold(d, sh, dap):
    return _times(attention_sites(d, sh, False, dap), d["n_recycle"] + 1)


def triangle_fold(d, sh, dap):
    """Both triangle updates of every block in every pass, on every
    device."""
    return [(*triangle_call(d, sh, dap),
             (d["n_recycle"] + 1) * 2 * d["n_blocks"] * dap)]


# [(flops, bytes, calls)] per unit of the cell (a train step, a fold), by
# the name a metric file gives; each call on one device.
KERNEL_WORK = {"attention_train": attention_train,
               "attention_fold": attention_fold,
               "triangle_fold": triangle_fold}

# flops per unit, summed over the devices.
MODEL_FLOPS = {"train_step": train_step_flops, "fold": fold_flops}


def least_time(calls, peak: dict) -> tuple[float, str]:
    """Seconds the chip needs at the least for the calls, each bound by the
    larger of its flops over the bf16 peak and its bytes over HBM bandwidth,
    and which bound holds most of that time."""
    by = {"compute": 0.0, "bytes": 0.0}
    for f, b, c in calls:
        tf, tb = f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"]
        by["compute" if tf >= tb else "bytes"] += c * max(tf, tb)
    return by["compute"] + by["bytes"], max(by, key=by.get)
