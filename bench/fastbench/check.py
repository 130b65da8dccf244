"""The numbers that decide ``correct``, each held to the limit the cell's
file (``workloads/<cell>.json``) gives it.

Training (three optimizer steps of the timed program against the
reference's three, from the same weights, batches and dropout keys):

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: for the first step's clipped gradient as the optimizer got
  it, the worst leaf's gap between the two norms, over the reference's norm
  of that leaf or of the median leaf, whichever is larger;
- ``update_gap``: the same for the change of the parameters over the three
  steps, leaving out the leaves whose reference gradient is below a
  thousandth of the median leaf's (Adam moves them by round-off alone).

Folding (a sample of the window's folds against the reference's fold of the
same batch), relative L2 gaps over the unpadded residues and MSA rows:
``distogram_gap``, ``msa_logits_gap`` and ``coords_gap``.
"""
from __future__ import annotations

import math

import numpy as np

ROUND_OFF_LEAF = 1e-3


def leaf_gap(got, want, include=None) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if include is not None:
        got, want = got[include], want[include]
    floor = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / floor))


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: ``losses`` (one per step), ``grad_norms`` and
    ``change_norms`` (one per parameter leaf)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moved = g_ref >= ROUND_OFF_LEAF * np.median(g_ref)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": leaf_gap(prog["grad_norms"], g_ref),
        "update_gap": leaf_gap(prog["change_norms"], ref["change_norms"],
                               moved),
    }


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def fold_numbers(prog: dict, ref: dict, batch: dict) -> dict:
    """Relative L2 gaps of one fold over its unpadded region."""
    res = batch["seq_mask"][0] > 0
    rows = batch["msa_mask"][0].max(axis=1) > 0
    dist = lambda o: np.asarray(o["distogram_logits"])[0][res][:, res]  # noqa
    msa = lambda o: np.asarray(o["msa_logits"])[0][rows][:, res]  # noqa
    xyz = lambda o: np.asarray(o["coords"])[0][res]  # noqa
    return {"distogram_gap": rel_l2(dist(prog), dist(ref)),
            "msa_logits_gap": rel_l2(msa(prog), msa(ref)),
            "coords_gap": rel_l2(xyz(prog), xyz(ref))}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number finite and at
    most its limit, and no number without a limit."""
    checks = {}
    ok = set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        value = numbers.get(name, float("nan"))
        limit = limits.get(name, float("nan"))
        checks[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, checks


def worst(checks_list: list[dict]) -> dict:
    """Several folds' checks folded into one: the largest of each number."""
    out = {}
    for checks in checks_list:
        for k, v in checks.items():
            if k not in out or not v <= out[k]:
                out[k] = v
    return out
