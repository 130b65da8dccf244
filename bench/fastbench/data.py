"""Inputs from the seed: synthetic protein families as padded crops.

One generator for every AlphaFold traffic mix. A mix file gives the padded
shapes (``n_res``, ``n_seq``, ``batch``), the ranges the real residue count
and MSA depth are drawn from (``real_res``, ``real_seq``, both inclusive and
below the padded shapes, so every mask ends mid-tile), the BERT mask rate,
and how many distinct batches the feed cycles through (``feed_batches``).
Padding does not change the work: every seed computes at the padded shapes.

A family: a backbone drawn as a random walk of 3.8 Å steps (the CA trace
the losses read), a target sequence, and MSA rows that copy the target at a
per-position conservation level and substitute elsewhere, so the MSA carries
co-evolution signal. Row 0 is the target.
"""
from __future__ import annotations

import numpy as np

N_MSA_TOK = 23   # 20 amino acids, unknown, gap, mask
N_AA = 21
MASK_TOKEN = N_MSA_TOK - 1
KEYS = ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
        "pseudo_beta", "bert_mask", "true_msa")


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """Any non-negative whole number, however large, plus salt words."""
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return np.random.SeedSequence([seed, *salt])


def jax_seed(seed: int, salt: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey``, drawn from the seed."""
    return int(seed_sequence(seed, salt).generate_state(1, np.uint32)[0]
               >> 1)


def protein_batch(rng: np.random.Generator, mix: dict) -> dict:
    """One padded-crop batch of numpy arrays."""
    b, s, r = mix["batch"], mix["n_seq"], mix["n_res"]
    lo, hi = mix["real_res"]
    n_res = rng.integers(lo, hi + 1, size=b)
    lo, hi = mix["real_seq"]
    n_seq = rng.integers(lo, hi + 1, size=b)
    aatype = rng.integers(0, 20, size=(b, r)).astype(np.int32)
    steps = rng.normal(size=(b, r, 3))
    steps /= np.linalg.norm(steps, axis=-1, keepdims=True) + 1e-8
    coords = np.cumsum(3.8 * steps, axis=1).astype(np.float32)
    conservation = rng.beta(2.0, 2.0, size=(b, 1, r))
    mutate = rng.random((b, s, r)) > conservation
    subs = rng.integers(0, 20, size=(b, s, r))
    msa = np.where(mutate, subs, aatype[:, None, :]).astype(np.int32)
    msa[:, 0] = aatype
    res_on = (np.arange(r)[None, :] < n_res[:, None]).astype(np.float32)
    seq_on = (np.arange(s)[None, :] < n_seq[:, None]).astype(np.float32)
    msa_mask = seq_on[:, :, None] * res_on[:, None, :]
    bert = (rng.random((b, s, r)) < mix["mask_rate"]).astype(np.float32)
    bert *= msa_mask
    return {
        "msa": np.where(bert > 0, MASK_TOKEN, msa).astype(np.int32),
        "msa_mask": msa_mask,
        "residue_index": np.tile(np.arange(r, dtype=np.int32), (b, 1)),
        "aatype": aatype,
        "seq_mask": res_on,
        "pseudo_beta": coords,
        "bert_mask": bert,
        "true_msa": msa,
    }


def feed(seed: int, mix: dict) -> list[dict]:
    """The ``feed_batches`` distinct batches a run cycles through."""
    rng = np.random.default_rng(seed_sequence(seed, 1))
    return [protein_batch(rng, mix) for _ in range(mix["feed_batches"])]
