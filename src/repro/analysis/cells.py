"""The contract matrix: (config, ExecutionPlan preset, mesh) cells.

Each cell lowers + compiles one program the repo's invariants were won on —
the 2-block Evoformer stack under GspmdDist (all four attention sites,
forward and backward), the shard-mapped fused triangle/OPM ops, the reduced
2-block AlphaFold train-loss dry-run, and the paper-faithful DAP shard_map
stack (whose jaxpr is also counted primitive-by-primitive) — and evaluates
the contracts from repro/analysis/contracts.py against the artifact.

Shapes are the distributed suite's (small enough to compile on the CPU CI
host in seconds, sharded the same way production is). The per-cell
``PeakBytesWithin`` factors and ``CollectiveBudget`` budgets are calibrated
against the checked-in BENCH_contracts.json baseline: the factor brackets
the measured modeled/compiled ratio with ~2x headroom, so a regression that
doubles the compiled peak (a rematerialized transient, a lost tiling) or
doubles the collective count trips the gate while XLA-version jitter does
not. This module imports jax — the runner (`__main__.py`) parses args and
forces the host device count BEFORE importing it.

NOTE: launch/dryrun.py force-sets a 512-device XLA flag at import time;
this module deliberately builds its own reduced AlphaFold cell instead of
importing it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.analysis.contracts import (
    CollectiveBudget,
    CompiledArtifact,
    NoInvoluntaryRemat,
    NoMergedAllGather,
    PeakBytesWithin,
    check_all,
)
from repro.core.dist import GspmdDist
from repro.core.evoformer import (
    EvoformerConfig,
    evoformer_stack,
    init_evoformer_stack,
)
from repro.exec.plan import preset, use_plan
from repro.kernels import ops
from repro.launch.mesh import _mesh
from repro.memory.autochunk import (
    modeled_evoformer_peak,
    opm_transient_bytes,
    triangle_transient_bytes,
)

# Evoformer cell config/shapes == the distributed suite's (s and r divide
# every tested model-axis size; compiles in seconds on CPU).
CFG = EvoformerConfig(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2,
                      head_dim=8, opm_dim=8, tri_mult_dim=16, n_blocks=2)
B, S, R = 2, 8, 16

# Per-cell PeakBytesWithin factors, calibrated on the BENCH_contracts.json
# baseline (see module docstring). The AutoChunk model is a dominant-term
# activation model: at these CI shapes parameters/outputs are a visible
# fraction of XLA's peak and backward passes double-count nothing, so the
# bracket is a factor, not a percentage. Forward cells sit closest to the
# model; grad cells and the full AlphaFold dry-run (structure module + heads
# outside the model) get looser brackets.
PEAK_FACTORS = {
    "evoformer_fwd": 4.0,      # measured ratio 1.16-1.37 (oracle/default)
    "evoformer_grad": 48.0,    # fwd-activation model vs full bwd: 19-22x
    "triangle_opm": 4.0,       # measured 0.67-0.76 (model slightly high)
    "alphafold_dryrun": 32.0,  # model covers the Evoformer only: 9.9-10.0x
    "dap_stack": 4.0,          # measured 0.67-1.21
}

# Per-cell static collective budgets (ops per traced block — the layer scan
# body is traced once, so the HLO count IS the per-block count). Calibrated
# the same way: measured count + ~2x headroom. Paper Table III's DAP budget
# is 4 all_to_all + a handful of row gathers per block; GSPMD adds resharding
# collectives around the shard_mapped kernels.
COLLECTIVE_BUDGETS = {
    "evoformer_fwd": 48,        # measured 19-22 static ops
    "evoformer_grad": 256,      # measured 142-168 (bwd resharding)
    "triangle_opm": 8,          # measured 1
    "alphafold_dryrun": 384,    # measured 238-266
    "dap_stack": 32,            # measured 15
    "dap_jaxpr": 32,            # measured 15 explicit primitives
}


@dataclass
class CellResult:
    artifact: CompiledArtifact
    contracts: tuple
    modeled_bytes: int | None = None


def _mesh_ctx(mesh):
    return jax.set_mesh(mesh)


def _compile_artifact(name: str, fn, *args) -> CompiledArtifact:
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    peak = None
    if mem is not None:
        peak = int(getattr(mem, "peak_memory_in_bytes", 0)
                   or getattr(mem, "temp_size_in_bytes", 0)) or None
    return CompiledArtifact(name, compiled.as_text(), peak)


def _fused_under_plan() -> bool:
    """Whether the current plan routes the Evoformer through the fused
    kernels (the AutoChunk model's ``fused`` axis) — same probe
    resolve_evoformer_config uses, at this cell's attention shape."""
    return ops.fused_attention_supported(
        (B, S, R, CFG.msa_heads, CFG.head_dim), kv_len=R,
        dtype=CFG.compute_dtype)


def _evo_inputs():
    msa = jax.random.normal(jax.random.PRNGKey(1), (B, S, R, CFG.d_msa))
    pair = jax.random.normal(jax.random.PRNGKey(2), (B, R, R, CFG.d_pair))
    masks = (jnp.ones((B, S, R)), jnp.ones((B, R)), jnp.ones((B, R, R)))
    return msa, pair, masks


# Legit rank-3+ all-gathers in these programs all lead with B (=2); a lead of
# B*S or B*R is the flatten-forced-gather signature. min_rank=3 covers both
# the attention (rank-4) and triangle/OPM (rank-3) merge shapes.
_EVO_MERGED = frozenset({B * S, B * R})


def _evo_contracts(cell: str, modeled: int | None):
    cs = [NoMergedAllGather(_EVO_MERGED, min_rank=3),
          NoInvoluntaryRemat(),
          CollectiveBudget(COLLECTIVE_BUDGETS[cell])]
    if modeled is not None:
        cs.append(PeakBytesWithin(modeled, PEAK_FACTORS[cell]))
    return tuple(cs)


def cell_evoformer_fwd(pname: str, mesh) -> list[CellResult]:
    """2-block Evoformer forward under GspmdDist — the four attention sites
    + both triangle updates + OPM, shard-mapped over the model axis."""
    n_model = mesh.shape["model"]
    msa, pair, masks = _evo_inputs()
    params = init_evoformer_stack(jax.random.PRNGKey(0), CFG)
    dist = GspmdDist(mesh=mesh, axis="model")
    with use_plan(preset(pname)), _mesh_ctx(mesh):
        art = _compile_artifact(
            f"evoformer_fwd/{pname}",
            lambda p: evoformer_stack(p, msa, pair, *masks, dist=dist,
                                      cfg=CFG, remat=False), params)
        modeled = modeled_evoformer_peak(CFG, batch=B, n_seq=S, n_res=R,
                                         dap=n_model,
                                         fused=_fused_under_plan())
    return [CellResult(art, _evo_contracts("evoformer_fwd", modeled),
                       modeled)]


def cell_evoformer_grad(pname: str, mesh) -> list[CellResult]:
    """Same stack, jit(grad(...)): the backward's recompute regions are where
    sharding propagation historically lost the group dim."""
    n_model = mesh.shape["model"]
    msa, pair, masks = _evo_inputs()
    params = init_evoformer_stack(jax.random.PRNGKey(0), CFG)
    dist = GspmdDist(mesh=mesh, axis="model")

    def loss(p):
        m, z = evoformer_stack(p, msa, pair, *masks, dist=dist, cfg=CFG,
                               remat=False)
        return jnp.sum(m ** 2) + jnp.sum(z ** 2)

    with use_plan(preset(pname)), _mesh_ctx(mesh):
        art = _compile_artifact(f"evoformer_grad/{pname}", jax.grad(loss),
                                params)
        modeled = modeled_evoformer_peak(CFG, batch=B, n_seq=S, n_res=R,
                                         dap=n_model,
                                         fused=_fused_under_plan())
    return [CellResult(art, _evo_contracts("evoformer_grad", modeled),
                       modeled)]


def cell_triangle_opm(pname: str, mesh) -> list[CellResult]:
    """Shard-mapped fused triangle-mult (fwd + grad) and OPM (fwd) as the
    distributed suite drives them; the three programs' HLO is checked as one
    artifact with peak = the max over the three."""
    B2, I, K, C, D, S2 = 2, 16, 16, 16, 12, 8
    c_opm = 8
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    a_lin = jax.random.normal(ks[0], (B2, I, K, C))
    ga = jax.random.normal(ks[1], (B2, I, K, C))
    mask = jax.random.bernoulli(ks[2], 0.7, (B2, I, K)).astype(jnp.float32)
    b_full = jax.random.normal(ks[3], (B2, I, K, C))
    gamma = jax.random.normal(ks[4], (C,))
    beta = jax.random.normal(ks[5], (C,))
    w_out = jax.random.normal(ks[6], (C, D))
    b_out = jax.random.normal(ks[7], (D,))
    g_lin = jax.random.normal(ks[8], (B2, I, I, D))
    g_bias = jax.random.normal(ks[9], (D,))
    oa = jax.random.normal(ks[10], (B2, S2, I, c_opm))
    ob = jax.random.normal(ks[11], (B2, S2, I, c_opm))
    oma = jnp.ones((B2, S2, I))
    omb = jnp.ones((B2, S2, I))
    ow = jax.random.normal(ks[2], (c_opm * c_opm, D))
    obias = jax.random.normal(ks[3], (D,))

    dist = GspmdDist(mesh=mesh, axis="model")

    def tri(a, b):
        return dist.sharded_triangle(a, ga, mask, b, gamma, beta, w_out,
                                     b_out, g_lin, g_bias, tile=4)

    def opm(a, b):
        return dist.sharded_opm(a, b, oma, omb, ow, obias, tile=4)

    with use_plan(preset(pname)), _mesh_ctx(mesh):
        arts = [
            _compile_artifact("tri_fwd", tri, a_lin, b_full),
            _compile_artifact(
                "tri_grad",
                jax.grad(lambda a, b: jnp.sum(tri(a, b) ** 2),
                         argnums=(0, 1)), a_lin, b_full),
            _compile_artifact("opm_fwd", opm, oa, ob),
        ]
        fused = preset(pname).kernels.enabled
    peaks = [a.peak_bytes for a in arts if a.peak_bytes]
    art = CompiledArtifact(f"triangle_opm/{pname}",
                           "\n".join(a.hlo_text for a in arts),
                           max(peaks) if peaks else None)
    modeled = max(
        B2 * triangle_transient_bytes(I, K, C, tile=4, fused=fused,
                                      dtype_bytes=4),
        B2 * opm_transient_bytes(I, I, S2, c_opm, tile=4, fused=fused,
                                 dtype_bytes=4),
    )
    contracts = [NoMergedAllGather(frozenset({B2 * I}), min_rank=3),
                 NoInvoluntaryRemat(),
                 CollectiveBudget(COLLECTIVE_BUDGETS["triangle_opm"]),
                 PeakBytesWithin(modeled, PEAK_FACTORS["triangle_opm"])]
    return [CellResult(art, tuple(contracts), modeled)]


def cell_alphafold_dryrun(pname: str, mesh) -> list[CellResult]:
    """Reduced 2-block AlphaFold train-loss gradient under GspmdDist — the
    GSPMD dry-run's program shape (embedders + recycling + Evoformer +
    structure module + heads), built here directly so the 512-device
    launch/dryrun module is never imported."""
    from repro.configs.alphafold import SMOKE
    from repro.core.alphafold import alphafold_train_loss, init_alphafold
    from repro.data import protein_batches
    from repro.memory.autochunk import resolve_evoformer_config

    n_model = mesh.shape["model"]
    pb = next(protein_batches(batch=B, n_seq=S, n_res=R, seed=0))
    batch = {k: jnp.asarray(getattr(pb, k)) for k in
             ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
              "pseudo_beta", "bert_mask", "true_msa")}
    params = init_alphafold(jax.random.PRNGKey(0), SMOKE)
    dist = GspmdDist(mesh=mesh, axis="model")

    def loss(p):
        out = alphafold_train_loss(p, batch, SMOKE,
                                   rng=jax.random.PRNGKey(1), dist=dist)
        return out[0] if isinstance(out, tuple) else out

    with use_plan(preset(pname)), _mesh_ctx(mesh):
        art = _compile_artifact(f"alphafold_dryrun/{pname}", jax.grad(loss),
                                params)
        evo_cfg = resolve_evoformer_config(SMOKE.evoformer, batch=B,
                                           n_seq=S, n_res=R, dap=n_model)
        modeled = modeled_evoformer_peak(evo_cfg, batch=B, n_seq=S, n_res=R,
                                         dap=n_model,
                                         fused=_fused_under_plan())
    return [CellResult(art, _evo_contracts("alphafold_dryrun", modeled),
                       modeled)]


# jax collective primitive names (jaxpr view of the same budget).
_JAXPR_COLLECTIVES = frozenset({
    "all_to_all", "all_gather", "psum", "psum_scatter", "reduce_scatter",
    "ppermute", "all_reduce", "collective_permute",
})


def count_jaxpr_collectives(jaxpr) -> dict[str, int]:
    """Static collective-primitive counts over a (Closed)Jaxpr, recursing
    into every sub-jaxpr (scan/shard_map/cond bodies are traced once, so —
    like the HLO count — this is a per-block number)."""
    counts: dict[str, int] = {}

    def sub_jaxprs(value):
        if hasattr(value, "jaxpr") and hasattr(value, "consts"):
            yield value.jaxpr                    # ClosedJaxpr
        elif hasattr(value, "eqns"):
            yield value                          # Jaxpr
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from sub_jaxprs(v)

    def walk(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in _JAXPR_COLLECTIVES:
                counts[name] = counts.get(name, 0) + 1
            for v in eqn.params.values():
                for sj in sub_jaxprs(v):
                    walk(sj)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return counts


def cell_dap_stack(pname: str, mesh) -> list[CellResult]:
    """Paper-faithful DAP shard_map stack: the compiled artifact carries the
    HLO/memory contracts; a second artifact counts the jaxpr's explicit
    collective primitives against the paper-Table-III budget."""
    from repro.core.dap import dap_evoformer_stack, shard_dap_inputs

    n_model = mesh.shape["model"]
    msa, pair, masks = _evo_inputs()
    params = init_evoformer_stack(jax.random.PRNGKey(0), CFG)
    with use_plan(preset(pname)), _mesh_ctx(mesh):
        fn = dap_evoformer_stack(mesh, CFG, remat=False)
        args = shard_dap_inputs(mesh, msa, pair, *masks)
        art = _compile_artifact(f"dap_stack/{pname}", fn, params, *args)
        jaxpr_counts = count_jaxpr_collectives(
            jax.make_jaxpr(fn)(params, *args))
        modeled = modeled_evoformer_peak(CFG, batch=B, n_seq=S, n_res=R,
                                         dap=n_model,
                                         fused=_fused_under_plan())
    jaxpr_art = CompiledArtifact(f"dap_jaxpr/{pname}",
                                 collective_counts=jaxpr_counts)
    return [
        CellResult(art, _evo_contracts("dap_stack", modeled), modeled),
        CellResult(jaxpr_art,
                   (CollectiveBudget(COLLECTIVE_BUDGETS["dap_jaxpr"]),)),
    ]


CELLS = (cell_evoformer_fwd, cell_evoformer_grad, cell_triangle_opm,
         cell_alphafold_dryrun, cell_dap_stack)


def run_matrix(preset_names=("default", "oracle"), cells=CELLS):
    """Evaluate every cell under every preset. Returns (violations, rows):
    rows are the BENCH_contracts.json records (modeled vs compiled peak,
    static collective counts, contract verdicts) in a stable order."""
    mesh = _mesh((1, len(jax.devices())), ("data", "model"))
    violations, rows = [], []
    for pname in preset_names:
        for cell in cells:
            for res in cell(pname, mesh):
                v = check_all(res.contracts, res.artifact)
                violations.extend(v)
                peak = res.artifact.peak_bytes
                rows.append({
                    "cell": res.artifact.name,
                    "preset": pname,
                    "modeled_bytes": res.modeled_bytes,
                    "compiled_peak_bytes": peak,
                    "ratio": (round(peak / res.modeled_bytes, 3)
                              if peak and res.modeled_bytes else None),
                    "collectives": dict(sorted(
                        res.artifact.counts().items())),
                    "contracts": [c.name for c in res.contracts],
                    "violations": [x.render() for x in v],
                })
    return violations, rows
