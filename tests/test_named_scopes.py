"""Named scopes in the compiled train step: every kernel family
(``ops.<family>``, kernels/ops.py) and every Evoformer sub-module
(``evoformer.<name>``) appears in the ``op_name`` metadata of the ops it
emits, forward and backward, on every kernel leg. The device trace
attributes time by these names, so they are an interface: a renamed or
dropped scope fails here."""
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.configs.alphafold import SMOKE
from repro.data import protein_batches
from repro.exec.plan import ExecutionPlan, KernelPolicy
from repro.exec.session import FastFold
from repro.train.loop import make_train_step

ROW_FAMILIES = {"layer_norm", "bias_dropout_add", "bias_sigmoid_mul"}
SUBMODULES = {
    "msa_row_attention", "msa_col_attention", "msa_transition",
    "outer_product_mean", "triangle_mult_outgoing", "triangle_mult_incoming",
    "triangle_attention_starting", "triangle_attention_ending",
    "pair_transition",
}
# The oracle leg runs the Evoformer's scores-materialized and materialized
# triangle/OPM paths: softmax instead of the fused attention, triangle and
# OPM families.
LEGS = {
    "interpret": (KernelPolicy(interpret=True),
                  ROW_FAMILIES | {"attention", "triangle", "opm"}),
    "xla": (KernelPolicy(), ROW_FAMILIES | {"attention", "triangle", "opm"}),
    "oracle": (KernelPolicy(enabled=False), ROW_FAMILIES | {"softmax"}),
}
# One block, no recycle, one structure iteration: every scope is still
# reached, in a smaller program to compile.
CFG = replace(SMOKE, n_recycle=0,
              evoformer=replace(SMOKE.evoformer, n_blocks=1),
              structure=replace(SMOKE.structure, n_iterations=1))
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(policy) -> list:
    """The ``op_name`` metadata of the compiled train step's ops: full name
    paths, the backward's under ``transpose(jvp(...))``."""
    ff = FastFold(CFG, ExecutionPlan(kernels=policy))
    init_state, step = make_train_step(ff.loss_fn, base_lr=1e-3,
                                       warmup_steps=1, total_steps=10)
    state = jax.eval_shape(lambda: init_state(ff.init(jax.random.PRNGKey(0))))
    pb = next(protein_batches(batch=1, n_seq=4, n_res=8, seed=0))
    batch = {k: jnp.asarray(getattr(pb, k)) for k in
             ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
              "pseudo_beta", "bert_mask", "true_msa")}
    compiled = jax.jit(step).lower(state, batch,
                                   jax.random.PRNGKey(1)).compile()
    return _OP_NAME.findall(compiled.as_text())


@pytest.mark.parametrize("leg", sorted(LEGS))
def test_train_step_names_families_and_submodules(leg):
    policy, families = LEGS[leg]
    names = _op_names(policy)
    fwd = [n for n in names if "transpose(" not in n]
    bwd = [n for n in names if "transpose(" in n]
    for fam in sorted(families):
        scope = f"ops.{fam}"
        assert any(scope in n for n in fwd), f"{scope}: no forward op"
        assert any(scope in n for n in bwd), f"{scope}: no backward op"
    reached = {m for n in names for m in re.findall(r"ops\.(\w+)", n)}
    assert reached == families, reached
    for sub in sorted(SUBMODULES):
        scope = f"evoformer.{sub}"
        assert any(scope in n for n in fwd), f"{scope}: no forward op"
        assert any(scope in n for n in bwd), f"{scope}: no backward op"
    for scope in ("alphafold.embed", "alphafold.recycle", "alphafold.heads",
                  "alphafold.loss", "structure.module", "train.optimizer"):
        assert any(scope in n for n in names), scope


def test_fold_names_the_extra_msa_stack():
    """The extra-MSA stack's embedding and stack, its global column
    attention and the ``global_attention`` family appear in the compiled
    fold, and its blocks name their sub-modules inside the stack's scope."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench"))
    from fastbench import scopes

    extra = replace(CFG.evoformer, d_msa=16, msa_heads=2,
                    global_column=True)
    ff = FastFold(replace(CFG, extra_msa=extra), ExecutionPlan())
    params = jax.eval_shape(ff.init, jax.random.PRNGKey(0))
    pb = next(protein_batches(batch=1, n_seq=4, n_res=8, seed=0,
                              n_extra_seq=6))
    batch = {k: jnp.asarray(getattr(pb, k)) for k in
             ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
              "extra_msa", "extra_msa_mask", "extra_has_deletion",
              "extra_deletion_value")}
    names = _OP_NAME.findall(ff.lower("forward", params, batch).compile()
                             .as_text())
    for scope in ("alphafold.extra_msa_embed", "alphafold.extra_msa_stack",
                  "evoformer.msa_col_global_attention",
                  "ops.global_attention"):
        assert any(scope in n for n in names), scope
    inside = [n for n in names if "alphafold.extra_msa_stack" in n]
    assert any("evoformer.msa_row_attention" in n for n in inside)
    assert any("evoformer.outer_product_mean" in n for n in inside)
    assert "global_attention" in {scopes.family(n) for n in inside}
    # Every op of the global column attention lies inside the stack's scope,
    # which the trace's reader counts. (The reducers' own regions carry a
    # shorter path; they are never device ops of their own.)
    ops = [n for n in names if n.startswith("jit(")]
    assert not any("evoformer.msa_col_global_attention" in n for n in ops
                   if "alphafold.extra_msa_stack" not in n)
