"""The benchmark's cells cut to a size a test run holds on the CPU, and
runners of the training and fold modes that skip the harness's look for a
chip."""
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fastbench import check, manifest, runtime  # noqa: E402

SEED = 3000000011
TINY = dict(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2, head_dim=8,
            opm_dim=8, tri_mult_dim=16, n_blocks=2, c_s=32, ipa_heads=4,
            ipa_c_hidden=8, ipa_qk_points=2, ipa_v_points=2,
            structure_iterations=2, n_recycle=1, crop_size=32,
            max_msa_clusters=16)


def tiny(name: str):
    """Cell ``name`` at TINY widths, 32 residues and 16 MSA rows; its
    limits as the cell's file gives them."""
    c = manifest.cell(name)
    return dataclasses.replace(
        c, config=dict(c.config, **TINY),
        traffic=dict(c.traffic, n_res=32, n_seq=16, real_res=[25, 31],
                     real_seq=[10, 15]))


def run_train(cell, system):
    """One training run of ``system`` on the CPU: (correct, checks)."""
    import jax

    from fastbench.modes import RunContext, train

    ctx = RunContext(seed=SEED, seconds=0.5, trace=False, cell=cell,
                     devices=jax.devices()[:1], t0=time.perf_counter(),
                     counter=runtime.CompileCounter())
    out = train.run(ctx, system=system)
    ok, checks = check.judge(out.numbers, cell.check["limits"])
    return ok and out.failed == 0, checks


def broken_train(fault):
    """The program's training system with ``fault`` wrapped round its
    step."""
    from fastbench.modes import train

    def system(cfg, dims):
        init_state, step, check_layout = train.program_system(cfg, dims)
        return init_state, fault(step), check_layout
    return system


def run_fold(cell, system):
    """One fold run of ``system`` on the CPU, on as many devices as the
    cell asks for: (correct, checks)."""
    import jax

    from fastbench.modes import RunContext, fold

    ctx = RunContext(seed=SEED, seconds=0.3, trace=False, cell=cell,
                     devices=jax.devices()[:cell.chips],
                     t0=time.perf_counter(),
                     counter=runtime.CompileCounter())
    out = fold.run(ctx, system=system)
    ok, checks = check.judge(out.numbers, cell.check["limits"])
    return ok and out.failed == 0, checks


def broken_fold(fault):
    """The program's fold system with ``fault`` wrapped round the function
    that compiles the fold."""
    from fastbench.modes import fold

    def system(cfg, mesh):
        compile_fold, check_layout = fold.program_system(cfg, mesh)
        return fault(compile_fold), check_layout
    return system


def control_fold(cell) -> dict:
    """The float8 control's fold numbers at ``cell``'s size on one CPU
    device, the reference in float8 against the float32 reference: the
    least of each number over the batches of the run's feed, whichever of
    them a run samples."""
    import jax

    from fastbench import data, reference
    from fastbench.modes import fold

    dims = reference.Dims.from_config(cell.config)
    wkey = jax.random.PRNGKey(data.jax_seed(SEED, fold.WEIGHT_SALT))
    feed = data.feed(SEED, cell.traffic)
    dev = jax.devices()[0]
    ref = fold.reference_folds(dims, wkey, feed, range(len(feed)), dev,
                               reference.FP32)
    each = list(fold.control_numbers(dims, wkey, feed, ref, dev).values())
    return {k: min(n[k] for n in each) for k in each[0]}
