"""The extra-MSA stack (AlphaFold-2 SI Alg. 18-19): ``ops.global_attention``
against its oracle, the block variant and the stack inside the fold, and the
trunk unchanged while the stack is off."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.alphafold import SMOKE
from repro.core.alphafold import alphafold_forward, init_alphafold
from repro.core.evoformer import extra_msa_stack
from repro.data import protein_batches
from repro.exec.plan import ExecutionPlan, KernelPolicy, use_plan
from repro.kernels import ops, ref

EXTRA = dataclasses.replace(SMOKE.evoformer, d_msa=16, msa_heads=2, n_blocks=2,
                            global_column=True)
CFG = dataclasses.replace(SMOKE, extra_msa=EXTRA)
TRUNK_KEYS = ("msa", "msa_mask", "residue_index", "aatype", "seq_mask")
EXTRA_KEYS = ("extra_msa", "extra_msa_mask", "extra_has_deletion",
              "extra_deletion_value")


def _batch(n_extra=24, keys=TRUNK_KEYS + EXTRA_KEYS):
    pb = next(protein_batches(batch=1, n_seq=8, n_res=16, seed=3,
                              n_extra_seq=n_extra))
    b = {k: jnp.asarray(getattr(pb, k)) for k in keys}
    # ragged: the last 3 residues and the last 5 extra rows are padding
    res = (jnp.arange(16) < 13).astype(jnp.float32)
    b["seq_mask"] = res[None]
    b["msa_mask"] = b["msa_mask"] * res[None, None, :]
    if "extra_msa_mask" in b:
        rows = (jnp.arange(n_extra) < n_extra - 5).astype(jnp.float32)
        b["extra_msa_mask"] = rows[None, :, None] * res[None, None, :]
    return b


def _perturbed(params, key):
    """Every leaf drawn afresh, so that no zero-initialised projection
    hides a path."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        0.3 * jax.random.normal(k, x.shape, x.dtype) / np.sqrt(x.shape[-1])
        + (1.0 if path[-1].key == "gamma" else 0.0)
        for (path, x), k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# ops.global_attention
# ---------------------------------------------------------------------------


def _ga_inputs(dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    n, s, h, d = 6, 40, 4, 8
    q = jax.random.normal(ks[0], (2, n, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (2, n, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, n, s, d)).astype(dtype)
    lens = jnp.array([[40, 31, 7, 1, 0, 25], [12, 40, 0, 3, 39, 40]])
    mask = jnp.where(jnp.arange(s) < lens[..., None], 0.0, -1e9)
    return q, k, v, mask.astype(jnp.float32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_global_attention_matches_oracle(dtype, tol):
    q, k, v, mask = _ga_inputs(dtype)
    got = ops.global_attention(q, k, v, mask=mask, scale=0.3)
    want = ref.global_attention_ref(q, k, v, mask, 0.3)
    assert got.dtype == dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_global_attention_oracle_is_plain_softmax_attention():
    q, k, v, mask = _ga_inputs(jnp.float32, key=1)
    got = np.asarray(ref.global_attention_ref(q, k, v, mask, 0.5))
    qn, kn, vn, mn = (np.asarray(x, np.float64) for x in (q, k, v, mask))
    logits = np.einsum("bnhd,bnsd->bnhs", qn, kn) * 0.5 + mn[:, :, None, :]
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    some = np.asarray(mask).max(-1) == 0      # groups with a key unmasked
    np.testing.assert_allclose(got[some],
                               np.einsum("bnhs,bnsd->bnhd", w, vn)[some],
                               atol=1e-5)
    # a group with every key masked attends uniformly, and stays finite
    # (in float32 the -1e9 swamps the logits)
    col = got[0, 4]
    np.testing.assert_allclose(col, np.broadcast_to(vn[0, 4].mean(0),
                                                    col.shape), atol=1e-5)


def test_global_attention_follows_the_attention_leg():
    q, k, v, mask = _ga_inputs(jnp.bfloat16)
    with use_plan(ExecutionPlan(kernels=KernelPolicy(enabled=False))):
        got = ops.global_attention(q, k, v, mask=mask)
    want = ref.global_attention_ref(q, k, v, mask, 8 ** -0.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_global_attention_gradients_are_finite_on_masked_columns():
    q, k, v, mask = _ga_inputs(jnp.float32)
    g = jax.grad(lambda q, k, v: jnp.sum(ops.global_attention(
        q, k, v, mask=mask) ** 2), argnums=(0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(x).all()) for x in g)


# ---------------------------------------------------------------------------
# the extra stack off: the trunk as it was
# ---------------------------------------------------------------------------


def test_params_with_the_stack_off_are_the_trunks():
    trunk = init_alphafold(jax.random.PRNGKey(0), SMOKE)
    full = init_alphafold(jax.random.PRNGKey(0), CFG)
    assert set(full) - set(trunk) == {"extra_msa_embed", "extra_msa_stack"}
    # the trunk's leaves keep their values: the extra leaves draw their
    # keys after every trunk leaf
    jax.tree.map(np.testing.assert_array_equal, trunk,
                 {k: full[k] for k in trunk})
    assert "wq" not in trunk["evoformer"]["msa_col"]["attn"]
    # SMOKE's trunk: 122 leaves, 78,561 numbers
    assert len(jax.tree.leaves(trunk)) == 122
    assert sum(x.size for x in jax.tree.leaves(trunk)) == 78561


def test_fold_with_the_stack_off_ignores_extra_inputs():
    params = _perturbed(init_alphafold(jax.random.PRNGKey(0), SMOKE),
                        jax.random.PRNGKey(1))
    fold = jax.jit(lambda p, b: alphafold_forward(p, b, SMOKE))
    plain = fold(params, _batch(keys=TRUNK_KEYS))
    with_extra = fold(params, _batch())
    for k in ("distogram_logits", "msa_logits", "coords"):
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(with_extra[k]))
    names = re.findall(r'op_name="([^"]*)"',
                       fold.lower(params, _batch()).compile().as_text())
    assert names and not any("extra_msa" in n or "global_attention" in n
                             for n in names)


# ---------------------------------------------------------------------------
# the extra stack on
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return _perturbed(init_alphafold(jax.random.PRNGKey(0), CFG),
                      jax.random.PRNGKey(2))


def test_block_variant_and_embedding_shapes(params):
    col = params["extra_msa_stack"]["msa_col"]["attn"]
    assert col["wq"]["w"].shape == (2, 16, 16)       # 2 heads of 8 from c 16
    assert col["wkv"]["w"].shape == (2, 16, 16)      # one key + one value
    assert params["extra_msa_stack"]["msa_row"]["attn"]["wqkv"]["w"].shape \
        == (2, 16, 48)
    assert params["extra_msa_embed"]["w"].shape == (25, 16)


def test_extra_stack_updates_the_pair_and_follows_the_oracle_leg(params):
    batch = _batch()
    fold = jax.jit(lambda p, b: alphafold_forward(p, b, CFG))
    got = fold(params, batch)
    without = jax.jit(lambda p, b: alphafold_forward(
        p, b, dataclasses.replace(CFG, extra_msa=None)))(params, batch)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(got))
    gap = jnp.linalg.norm(got["pair"].astype(jnp.float32)
                          - without["pair"].astype(jnp.float32))
    assert float(gap) > 1e-2 * float(jnp.linalg.norm(
        without["pair"].astype(jnp.float32)))
    with use_plan(ExecutionPlan(kernels=KernelPolicy(enabled=False))):
        oracle = jax.jit(lambda p, b: alphafold_forward(p, b, CFG))(params,
                                                                    batch)
    np.testing.assert_allclose(np.asarray(got["distogram_logits"]),
                               np.asarray(oracle["distogram_logits"]),
                               atol=0.1, rtol=0.1)


def test_padded_extra_rows_do_not_change_the_pair(params):
    """Rows masked out of the extra MSA reach the pair only through the
    masked outer product mean and masked column attention: changing them
    leaves the stack's output as it was."""
    batch = _batch()
    p = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    cfg = dataclasses.replace(EXTRA, compute_dtype=jnp.float32)
    extra = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 16, 16))
    pair = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 16, 16))
    seq_mask = batch["seq_mask"]
    pair_mask = seq_mask[:, :, None] * seq_mask[:, None, :]
    run = jax.jit(lambda e: extra_msa_stack(
        p["extra_msa_stack"], e, pair, batch["extra_msa_mask"], seq_mask,
        pair_mask, cfg=cfg))
    changed = extra.at[:, -5:].set(7.0)
    a, b = run(extra), run(changed)
    np.testing.assert_allclose(np.asarray(a)[0, :13, :13],
                               np.asarray(b)[0, :13, :13], atol=1e-4)


def test_extra_stack_needs_the_global_variant(params):
    with pytest.raises(ValueError, match="global_column"):
        extra_msa_stack(params["extra_msa_stack"], None, None, None, None,
                        None, cfg=SMOKE.evoformer)


def test_synthetic_extra_rows_leave_the_trunk_features_unchanged():
    plain = next(protein_batches(batch=2, n_seq=4, n_res=12, seed=7))
    extra = next(protein_batches(batch=2, n_seq=4, n_res=12, seed=7,
                                 n_extra_seq=30))
    assert plain.extra_msa is None
    for k in ("msa", "aatype", "pseudo_beta", "bert_mask"):
        np.testing.assert_array_equal(getattr(plain, k), getattr(extra, k))
    assert extra.extra_msa.shape == (2, 30, 12)
    assert extra.extra_msa.max() < 20 and extra.extra_msa_mask.all()
    # deletion_value = 2/pi * arctan(count / 3), zero where no deletion
    dv, hd = extra.extra_deletion_value, extra.extra_has_deletion
    assert ((dv > 0) == (hd > 0)).all() and hd.any() and (dv < 1).all()
