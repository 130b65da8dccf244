"""The system under test for AlphaFold-2 ``model_3``: ``program.py``'s
configuration with the extra-MSA stack on (``AlphaFoldConfig.extra_msa``,
a block config of the global-column variant). A program without the extra
stack is refused here, before anything is compiled: the cell never folds
without it."""
from __future__ import annotations

import dataclasses

from fastbench import program


def require_extra_stack(config_cls) -> None:
    """Refuse a program whose AlphaFold configuration has no extra-MSA
    stack."""
    if "extra_msa" not in {f.name for f in dataclasses.fields(config_cls)}:
        raise RuntimeError(
            "the program under test has no extra-MSA stack "
            "(AlphaFoldConfig.extra_msa): this cell folds AlphaFold-2 "
            "model_3, whose 4 extra-MSA blocks it cannot run")


def alphafold_config(cfg: dict):
    from repro.core.alphafold import AlphaFoldConfig

    require_extra_stack(AlphaFoldConfig)
    base = program.alphafold_config(cfg)
    extra = dataclasses.replace(
        base.evoformer, d_msa=cfg["extra_msa_channel"],
        msa_heads=cfg["extra_msa_heads"],
        n_blocks=cfg["extra_msa_stack_num_block"], global_column=True)
    if extra.msa_head_dim != cfg["extra_msa_head_dim"]:
        raise ValueError(
            f"extra_msa_head_dim {cfg['extra_msa_head_dim']} is not "
            f"extra_msa_channel / extra_msa_heads = {extra.msa_head_dim}, "
            f"the width the program's MSA attention takes")
    return dataclasses.replace(base, extra_msa=extra)


def fastfold(cfg: dict, plan=None, extra: bool = True):
    """The ``FastFold`` facade for ``cfg``; ``extra=False`` leaves the extra
    stack out (a planted fault: the trunk alone, its weights and inputs
    ignored)."""
    from repro.exec.plan import ExecutionPlan
    from repro.exec.session import FastFold

    af = alphafold_config(cfg)
    if not extra:
        af = dataclasses.replace(af, extra_msa=None)
    return FastFold(af, plan or ExecutionPlan())
