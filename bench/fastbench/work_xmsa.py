"""Work of AlphaFold-2 ``model_3``'s fold, counted from shapes as
``work.py`` counts the trunk's: 2 flops per multiply-add of a matrix
product, no padding, no recomputation. Global column attention is counted as
what it computes (one query per column against one shared key and value
head), not as full column attention.

Hand count of one extra-MSA block pass (``extra_msa_channel`` c 64, 8 heads
of 8, OPM 32, the trunk's pair side) at n_res r = 256 and s = 5120 extra
rows, batch 1, in GFLOP:

    row attention      bias 0.13, qkv 32.21, gate 10.74, QK 42.95,
                       PV 42.95, out 10.74                           139.7
    global column      query 0.002, kv 2.68, gate 10.74, QK 0.17,
                       PV 0.17, out 10.74                             24.5
    MSA transition     2 x 42.95                                      85.9
    outer product mean proj 10.74, outer 687.19, out 17.18           715.1
    pair side          triangle mult 2 x 17.18, triangle attention
                       2 x 19.40, pair transition 17.18               90.3
                                                          per block ~1055.6

Four blocks and the embedding (25 -> 64 over the extra rows, 4.2) give
~4.23 TFLOP a pass, and 16.91 TFLOP a fold of 4 passes, beside the trunk's
113.92 at r 256 with 512 MSA rows (``work.fold_flops``).
"""
from __future__ import annotations

from fastbench import work

N_EXTRA_FEAT = 25


def extra_block_flops(d: dict, r: int, s: int, b: int = 1) -> int:
    """One extra-MSA block over s extra rows."""
    c, h, hd = (d["extra_msa_channel"], d["extra_msa_heads"],
                d["extra_msa_head_dim"])
    dz, hz, hdz = d["d_pair"], d["pair_heads"], d["head_dim"]
    f, c_opm, c_tri = d["transition_factor"], d["opm_dim"], d["tri_mult_dim"]
    row = 2 * b * r * r * dz * h + work._attention(s, r, c, h, hd, c, b)
    col = b * (2 * r * c * h * hd               # query from the column mean
               + 2 * s * r * c * 2 * hd         # the shared key and value
               + 2 * s * r * c * h * hd         # gate
               + 2 * 2 * r * h * s * hd         # QK^T and PV
               + 2 * s * r * h * hd * c)        # output projection
    trans = 2 * 2 * b * s * r * c * f * c
    opm = b * (2 * s * r * c * 2 * c_opm + 2 * r * r * s * c_opm ** 2
               + 2 * r * r * c_opm ** 2 * dz)
    tri_mult = b * (2 * 2 * r * r * dz * 2 * c_tri + 2 * r * r * dz * dz
                    + 2 * r ** 3 * c_tri + 2 * r * r * c_tri * dz)
    tri_attn = 2 * b * r * r * dz * hz + work._attention(r, r, dz, hz, hdz,
                                                         dz, b)
    pair_trans = 2 * 2 * b * r * r * dz * f * dz
    return (row + col + trans + opm + 2 * tri_mult + 2 * tri_attn
            + pair_trans)


def extra_pass_flops(d: dict, shapes: dict) -> int:
    """The extra MSA's embedding and stack in one recycling pass."""
    r, s, b = shapes["n_res"], shapes["n_extra_seq"], shapes["batch"]
    embed = 2 * b * s * r * N_EXTRA_FEAT * d["extra_msa_channel"]
    return embed + d["extra_msa_stack_num_block"] * extra_block_flops(d, r,
                                                                      s, b)


def fold_model3_flops(d: dict, shapes: dict) -> int:
    """A fold: the trunk's (``work.fold_flops``) and the extra stack's, in
    each of the ``n_recycle + 1`` passes."""
    return (work.fold_flops(d, shapes)
            + (d["n_recycle"] + 1) * extra_pass_flops(d, shapes))


def attention_fold_model3(d: dict, shapes: dict, dap: int = 1):
    """[(flops, bytes, calls)] of the flash kernel in a fold: the trunk's
    four sites (``work.attention_fold``) and, in each extra block, the row
    attention at 8 heads of 8 over the extra rows and the two triangle
    attentions."""
    r, s, b = shapes["n_res"], shapes["n_extra_seq"], shapes["batch"]
    n = (d["n_recycle"] + 1) * d["extra_msa_stack_num_block"] * dap
    row = work.attention_call(b * s, r, d["extra_msa_heads"],
                              d["extra_msa_head_dim"], b, dap=dap)
    tri = work.attention_call(b * r, r, d["pair_heads"], d["head_dim"], b,
                              dap=dap)
    return work.attention_fold(d, shapes, dap) + [(*row, n), (*tri, 2 * n)]


def triangle_fold_model3(d: dict, shapes: dict, dap: int = 1):
    """[(flops, bytes, calls)] of the triangle kernel in a fold: the trunk's
    two updates a block (``work.triangle_fold``) and the same two in each
    extra block, whose pair side is the trunk's."""
    n = (d["n_recycle"] + 1) * 2 * d["extra_msa_stack_num_block"] * dap
    return (work.triangle_fold(d, shapes, dap)
            + [(*work.triangle_call(d, shapes, dap), n)])


# flops per unit, summed over the devices, by the name a metric file gives.
MODEL_FLOPS = {"fold_model3": fold_model3_flops}

# [(flops, bytes, calls)] per unit, each call on one device.
KERNEL_WORK = {"attention_fold_model3": attention_fold_model3,
               "triangle_fold_model3": triangle_fold_model3}
