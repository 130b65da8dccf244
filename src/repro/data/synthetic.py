"""Synthetic-but-faithful data pipelines.

Real AlphaFold preprocessing (jackhmmer/hhblits database search) is CPU-side
and out of scope (cf. ParaFold); we generate features with the *exact shapes,
dtypes and semantics* the model contract requires, deterministically from a
seed, so training/benchmark results are reproducible.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator

import numpy as np


@dataclass(frozen=True)
class LMBatch:
    tokens: np.ndarray   # (B, S) int32
    targets: np.ndarray  # (B, S) int32 (next-token)
    mask: np.ndarray     # (B, S) float32 loss mask


def lm_batches(
    *, vocab: int, batch: int, seq: int, seed: int = 0
) -> Iterator[LMBatch]:
    """Zipf-distributed token stream with a deterministic generator — matches
    the rank-frequency profile of natural-language corpora closely enough for
    throughput/loss-curve work."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks**1.1
    probs /= probs.sum()
    while True:
        toks = rng.choice(vocab, size=(batch, seq + 1), p=probs).astype(np.int32)
        yield LMBatch(
            tokens=toks[:, :-1],
            targets=toks[:, 1:],
            mask=np.ones((batch, seq), np.float32),
        )


N_AA = 21          # 20 amino acids + gap/unknown
N_MSA_TOK = 23     # AlphaFold MSA alphabet (aa + gap + mask)


@dataclass(frozen=True)
class ProteinBatch:
    """AlphaFold featurization contract (the subset the model consumes)."""
    msa: np.ndarray           # (B, N_s, N_r) int32 in [0, N_MSA_TOK)
    msa_mask: np.ndarray      # (B, N_s, N_r) float32
    residue_index: np.ndarray # (B, N_r) int32
    aatype: np.ndarray        # (B, N_r) int32 in [0, N_AA)
    seq_mask: np.ndarray      # (B, N_r) float32
    pseudo_beta: np.ndarray   # (B, N_r, 3) float32 ground-truth CB coords
    bert_mask: np.ndarray     # (B, N_s, N_r) float32: positions masked for the
                              # masked-MSA objective
    true_msa: np.ndarray      # (B, N_s, N_r) int32 unmasked MSA
    # Extra MSA (AlphaFold's extra_msa stack input), None unless asked for:
    extra_msa: np.ndarray | None = None             # (B, N_e, N_r) int32
    extra_msa_mask: np.ndarray | None = None        # (B, N_e, N_r) float32
    extra_has_deletion: np.ndarray | None = None    # (B, N_e, N_r) float32
    extra_deletion_value: np.ndarray | None = None  # (B, N_e, N_r) float32


def extra_msa_features(rng: np.random.Generator, aatype: np.ndarray,
                       n_extra: int) -> dict:
    """Extra-MSA rows for targets ``aatype`` (B, N_r): each row copies the
    target at a per-position conservation level and substitutes elsewhere,
    as the MSA does, and carries deletion features as AlphaFold computes
    them from per-position deletion counts: ``has_deletion`` (count > 0)
    and ``deletion_value`` = 2/pi * arctan(count / 3)."""
    b, r = aatype.shape
    conservation = rng.beta(2.0, 2.0, size=(b, 1, r))
    mutate = rng.random((b, n_extra, r)) > conservation
    subs = rng.integers(0, 20, size=(b, n_extra, r))
    rows = np.where(mutate, subs, aatype[:, None, :]).astype(np.int32)
    deletions = rng.poisson(0.2, size=(b, n_extra, r))
    return {
        "extra_msa": rows,
        "extra_msa_mask": np.ones((b, n_extra, r), np.float32),
        "extra_has_deletion": (deletions > 0).astype(np.float32),
        "extra_deletion_value": (2.0 / np.pi * np.arctan(deletions / 3.0)
                                 ).astype(np.float32),
    }


def protein_batches(
    *, batch: int, n_seq: int, n_res: int, seed: int = 0,
    mask_rate: float = 0.15, n_extra_seq: int = 0,
) -> Iterator[ProteinBatch]:
    """Synthetic homologous-family generator: a ground-truth backbone is drawn
    as a self-avoiding-ish random walk; MSA rows are the target sequence with
    position-dependent mutation rates, so co-evolution signal exists for the
    model to learn (loss decreases measurably within a few hundred steps).
    ``n_extra_seq`` > 0 adds that many extra-MSA rows
    (``extra_msa_features``), drawn last in each batch, so the first
    batch's other features do not change with it."""
    rng = np.random.default_rng(seed)
    while True:
        aatype = rng.integers(0, 20, size=(batch, n_res)).astype(np.int32)
        # Backbone: cumulative random unit steps, ~3.8 A spacing like CA traces.
        steps = rng.normal(size=(batch, n_res, 3))
        steps /= np.linalg.norm(steps, axis=-1, keepdims=True) + 1e-8
        coords = np.cumsum(3.8 * steps, axis=1).astype(np.float32)
        # MSA rows: mutate the target with per-position conservation levels.
        conservation = rng.beta(2.0, 2.0, size=(batch, 1, n_res))
        mutate = rng.random((batch, n_seq, n_res)) > conservation
        subs = rng.integers(0, 20, size=(batch, n_seq, n_res))
        msa = np.where(mutate, subs, aatype[:, None, :]).astype(np.int32)
        msa[:, 0] = aatype  # row 0 is the target sequence
        bert_mask = (rng.random((batch, n_seq, n_res)) < mask_rate).astype(np.float32)
        masked_msa = np.where(bert_mask > 0, N_MSA_TOK - 1, msa).astype(np.int32)
        extra = (extra_msa_features(rng, aatype, n_extra_seq)
                 if n_extra_seq else {})
        yield ProteinBatch(
            msa=masked_msa,
            msa_mask=np.ones((batch, n_seq, n_res), np.float32),
            residue_index=np.tile(np.arange(n_res, dtype=np.int32), (batch, 1)),
            aatype=aatype,
            seq_mask=np.ones((batch, n_res), np.float32),
            pseudo_beta=coords,
            bert_mask=bert_mask,
            true_msa=msa,
            **extra,
        )
