"""The system under test, built from a configuration file: the
``AlphaFoldConfig``, the ``FastFold`` facade with its execution plan, the
DAP mesh, and the shardings of the inputs. This module is the only one of
the benchmark that imports the program."""
from __future__ import annotations

import os
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def alphafold_config(cfg: dict):
    import jax.numpy as jnp

    from repro.core.alphafold import AlphaFoldConfig
    from repro.core.evoformer import EvoformerConfig
    from repro.core.structure import StructureConfig

    if cfg["compute_dtype"] != "bfloat16":
        raise ValueError(f"compute_dtype {cfg['compute_dtype']!r}: the "
                         f"program computes in bfloat16")
    return AlphaFoldConfig(
        evoformer=EvoformerConfig(
            d_msa=cfg["d_msa"], d_pair=cfg["d_pair"],
            msa_heads=cfg["msa_heads"], pair_heads=cfg["pair_heads"],
            head_dim=cfg["head_dim"], opm_dim=cfg["opm_dim"],
            tri_mult_dim=cfg["tri_mult_dim"],
            transition_factor=cfg["transition_factor"],
            dropout_msa=cfg["dropout_msa"], dropout_pair=cfg["dropout_pair"],
            n_blocks=cfg["n_blocks"], compute_dtype=jnp.bfloat16),
        structure=StructureConfig(
            c_s=cfg["c_s"], c_z=cfg["d_pair"], n_heads=cfg["ipa_heads"],
            c_hidden=cfg["ipa_c_hidden"], n_qk_points=cfg["ipa_qk_points"],
            n_v_points=cfg["ipa_v_points"],
            n_iterations=cfg["structure_iterations"],
            trans_scale=cfg["trans_scale"]),
        n_recycle=cfg["n_recycle"], recycle_bins=cfg["recycle_bins"],
        compute_dtype=jnp.bfloat16)


def fastfold(cfg: dict, plan=None):
    from repro.exec.plan import ExecutionPlan
    from repro.exec.session import FastFold

    return FastFold(alphafold_config(cfg), plan or ExecutionPlan())


def check_layout(ff, params_shape) -> None:
    """The benchmark makes the weights itself; refuse a program whose
    parameter tree differs from the reference's in structure or shape."""
    import jax

    want = jax.tree.map(lambda x: (x.shape, x.dtype), params_shape)
    got = jax.tree.map(lambda x: (x.shape, x.dtype),
                       jax.eval_shape(ff.init, jax.random.PRNGKey(0)))
    if want != got:
        raise RuntimeError("the program's parameter tree differs from the "
                           "benchmark's reference layout")


def train_step(ff, opt: dict):
    """``make_train_step(ff.loss_fn, ...)`` with the configuration's
    optimizer; returns (init_state, step)."""
    from repro.train.loop import make_train_step

    if opt["name"] != "adam" or opt.get("weight_decay", 0.0):
        raise ValueError(f"unsupported optimizer {opt}")
    return make_train_step(
        ff.loss_fn, optimizer="adamw", base_lr=opt["learning_rate"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        clip_norm=opt["clip_norm"])


def dap_mesh(devices, dap: int):
    """A (1, dap) mesh, ('data', 'model'), over the given devices."""
    import jax

    auto = jax.sharding.AxisType.Auto
    return jax.sharding.Mesh(np.array(devices[:dap]).reshape(1, dap),
                             ("data", "model"), axis_types=(auto, auto))


def dap_plan(mesh):
    from repro.exec.plan import ExecutionPlan

    return ExecutionPlan().with_parallel(backend="gspmd", mesh=mesh)


def batch_shardings(mesh, batch: dict) -> dict:
    """DAP input layout: MSA-shaped inputs split on the sequence axis s,
    everything else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.dist import dap_msa_spec

    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(*dap_msa_spec(mesh, "s")[:3]))
    return {k: rows if np.ndim(v) == 3 and k != "pseudo_beta" else rep
            for k, v in batch.items()}
