"""repro.analysis: repro-lint rules, compiled-program contracts, the runner.

Every rule and contract is proven BOTH ways: it fires on a deliberately-bad
fixture and stays quiet on the good twin (and on HEAD). The lint/contract
halves are pure (no jax); the integration tests drive the real runner and a
naive-shard merged-all-gather program in subprocesses, test_distributed
style."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.contracts import (
    CollectiveBudget,
    CompiledArtifact,
    NoInvoluntaryRemat,
    NoMergedAllGather,
    PeakBytesWithin,
    assert_no_merged_allgather,
    check_all,
    find_gather_then_slice,
    find_merged_allgathers,
)
from repro.analysis.lint import lint_source, lint_tree
from repro.roofline.analysis import count_collective_ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
REPRO = os.path.join(SRC, "repro")


def rules_of(src: str, relpath: str) -> list[str]:
    return [f.rule for f in lint_source(textwrap.dedent(src), relpath)]


# ---------------------------------------------------------------------------
# repro-lint rules: each fires on the bad fixture, not on the good twin
# ---------------------------------------------------------------------------


def test_r001_env_access_fires():
    assert rules_of("import os\nos.environ['X'] = '1'\n",
                    "core/foo.py") == ["R001"]
    assert rules_of("import os\nv = os.getenv('X')\n",
                    "serving/engine.py") == ["R001"]
    assert rules_of("import os as _o\n_o.environ.get('X')\n",
                    "core/foo.py") == ["R001"]


def test_r001_catches_aliased_from_import():
    # The cases the old ci.sh grep for "os.environ" missed entirely.
    assert "R001" in rules_of("from os import environ\n", "core/foo.py")
    assert "R001" in rules_of(
        "from os import getenv as ge\nv = ge('X')\n", "core/foo.py")


def test_r001_exempts_envcompat():
    src = "import os\nos.environ['XLA_FLAGS'] = 'x'\nos.getenv('Y')\n"
    assert rules_of(src, "exec/envcompat.py") == []
    assert rules_of(src, "exec/other.py") == ["R001", "R001"]


def test_r002_bare_except_fires():
    bad = """
    try:
        f()
    except Exception:
        pass
    """
    assert rules_of(bad, "serving/engine.py") == ["R002"]
    assert rules_of("try:\n    f()\nexcept:\n    pass\n",
                    "core/foo.py") == ["R002"]


def test_r002_allows_named_and_resilience():
    named = """
    try:
        f()
    except Exception as err:
        raise RuntimeError("x") from err
    """
    assert rules_of(named, "serving/engine.py") == []
    assert rules_of("try:\n    f()\nexcept Exception:\n    pass\n",
                    "resilience/inject.py") == []


def test_r003_wallclock_and_random_fire_in_traced_code():
    assert rules_of("import time\nt = time.time()\n",
                    "core/evoformer.py") == ["R003"]
    assert rules_of("import random\nx = random.random()\n",
                    "kernels/ops.py") == ["R003"]
    assert rules_of("import numpy as np\nx = np.random.normal()\n",
                    "memory/autochunk.py") == ["R003"]
    assert rules_of("import datetime\nt = datetime.datetime.now()\n",
                    "train/loop.py") == ["R003"]


def test_r003_scoped_to_traced_modules_and_allows_jax_random():
    # launch/resilience/benchmark code may read clocks and host RNGs.
    assert rules_of("import time\nt = time.time()\n",
                    "launch/dryrun.py") == []
    assert rules_of("import random\nrandom.seed(0)\n",
                    "resilience/inject.py") == []
    # jax.random is the sanctioned in-trace RNG.
    assert rules_of("import jax\nk = jax.random.split(key)\n",
                    "core/evoformer.py") == []


def test_r004_r005_scores_materialized_attention_fires():
    bad = """
    import jax
    import jax.numpy as jnp
    def attend(q, k, v):
        scores = jnp.einsum("bgihd,bgjhd->bghij", q, k)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bghij,bgjhd->bgihd", probs, v)
    """
    got = rules_of(bad, "core/evoformer.py")
    assert got == ["R004", "R005", "R004"], got
    # The same source outside the pair-stack modules is not in scope.
    assert rules_of(bad, "models/decoder.py") == []


def test_suppressions():
    line = ('import jax.numpy as jnp\n'
            'o = jnp.einsum("ij,jk->ik", a, b)'
            '  # repro-lint: disable=R004\n')
    assert rules_of(line, "core/evoformer.py") == []
    above = ('import jax.numpy as jnp\n'
             '# repro-lint: disable=R004 -- sanctioned fallback\n'
             'o = jnp.einsum("ij,jk->ik", a, b)\n')
    assert rules_of(above, "core/evoformer.py") == []
    multiline = ('import jax.numpy as jnp\n'
                 'o = jnp.einsum("ij,jk->ik", a,\n'
                 '               b)  # repro-lint: disable=R004\n')
    assert rules_of(multiline, "core/evoformer.py") == []
    whole_file = ('# repro-lint: disable-file=R004\n'
                  'import jax.numpy as jnp\n'
                  'o = jnp.einsum("ij,jk->ik", a, b)\n')
    assert rules_of(whole_file, "core/evoformer.py") == []
    # Suppressing a different rule does not silence this one.
    wrong = ('import jax.numpy as jnp\n'
             'o = jnp.einsum("ij,jk->ik", a, b)'
             '  # repro-lint: disable=R005\n')
    assert rules_of(wrong, "core/evoformer.py") == ["R004"]


def test_r006_print_and_stdout_fire_in_library_modules():
    assert rules_of("print('debug')\n", "serving/engine.py") == ["R006"]
    assert rules_of("import sys\nsys.stdout.write('x')\n",
                    "train/loop.py") == ["R006"]
    assert rules_of("import sys\nsys.stderr.writelines(['x'])\n",
                    "core/foo.py") == ["R006"]


def test_r006_quiet_twin_and_exempt_scopes():
    # Telemetry/report/CLI scopes may print; __main__ entrypoints too.
    for rel in ("obs/report.py", "obs/trace.py", "analysis/lint.py",
                "launch/serve.py", "analysis/__main__.py",
                "serving/__main__.py"):
        assert rules_of("print('report line')\n", rel) == []
    # The quiet twin: writes to ordinary file objects are not stdout.
    quiet = ("def dump(fh, log):\n"
             "    fh.write('x')\n"
             "    log.writelines(['x'])\n")
    assert rules_of(quiet, "serving/engine.py") == []


def test_r006_suppression():
    line = "print('sanctioned')  # repro-lint: disable=R006\n"
    assert rules_of(line, "serving/engine.py") == []
    wrong = "print('sanctioned')  # repro-lint: disable=R003\n"
    assert rules_of(wrong, "serving/engine.py") == ["R006"]


def test_lint_tree_clean_on_head():
    findings = lint_tree(REPRO)
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# contracts: pure HLO finders on crafted artifacts
# ---------------------------------------------------------------------------

MERGED_AG_HLO = """
ENTRY %main (p0: f32[2,8,16,8]) -> f32[16,16,8] {
  %p0 = f32[2,8,16,8]{3,2,1,0} parameter(0)
  %r = f32[16,16,8]{2,1,0} reshape(%p0)
  %ag = f32[16,16,8]{2,1,0} all-gather(%r), dimensions={0}
  ROOT %out = f32[16,16,8]{2,1,0} add(%ag, %ag)
}
"""

CLEAN_AG_HLO = """
ENTRY %main (p0: f32[2,4,16,8]) -> f32[2,8,16,8] {
  %p0 = f32[2,4,16,8]{3,2,1,0} parameter(0)
  %ag = f32[2,8,16,8]{3,2,1,0} all-gather(%p0), dimensions={1}
  ROOT %out = f32[2,8,16,8]{3,2,1,0} add(%ag, %ag)
}
"""


def test_find_merged_allgathers():
    assert find_merged_allgathers(MERGED_AG_HLO, {16}, 3) == [[16, 16, 8]]
    assert find_merged_allgathers(CLEAN_AG_HLO, {16}, 3) == []
    # rank gate: a merged lead below min_rank does not count
    assert find_merged_allgathers(MERGED_AG_HLO, {16}, 4) == []
    # async form counts once, at the -start
    async_hlo = "%ag = f32[16,8,4]{2,1,0} all-gather-start(%x)\n"
    assert find_merged_allgathers(async_hlo, {16}, 3) == [[16, 8, 4]]
    with pytest.raises(AssertionError):
        assert_no_merged_allgather(MERGED_AG_HLO, {16}, 3)
    assert_no_merged_allgather(CLEAN_AG_HLO, {16}, 3)


GATHER_SLICE_HLO = """
ENTRY %main (p0: f32[2,4,8]) -> f32[2,4,8] {
  %p0 = f32[2,4,8]{2,1,0} parameter(0)
  %ag = f32[2,8,8]{2,1,0} all-gather(%p0), dimensions={1}
  %idx = s32[] partition-id()
  ROOT %ds = f32[2,4,8]{2,1,0} dynamic-slice(%ag, %idx), dynamic_slice_sizes={2,4,8}
}
"""


def test_find_gather_then_slice():
    pairs = find_gather_then_slice(GATHER_SLICE_HLO)
    assert len(pairs) == 1 and pairs[0][0] == "ag"
    # a gather consumed by compute (not a slice) is fine
    assert find_gather_then_slice(CLEAN_AG_HLO) == []
    # computation boundaries reset the gathered set
    split = GATHER_SLICE_HLO.replace("%idx", "}\n%idx")
    assert find_gather_then_slice(split) == []


def test_count_collective_ops_static():
    hlo = """
  %a = f32[4,4]{1,0} all-gather(%x), dimensions={0}
  %b = f32[4,4]{1,0} all-reduce(%y), to_apply=%sum
  %c = (f32[4,4], f32[4,4]) all-gather-start(%z)
  %d = f32[4,4]{1,0} all-gather-done(%c)
  %e = f32[4,4]{1,0} all-to-all(%w)
"""
    counts = count_collective_ops(hlo)
    # -start counts once; -done re-states the same gather, not a new one
    assert counts == {"all-gather": 2, "all-reduce": 1, "all-to-all": 1}


def test_contract_objects():
    art = CompiledArtifact("cell/x", MERGED_AG_HLO, peak_bytes=1000)
    v = check_all([NoMergedAllGather(frozenset({16}), 3)], art)
    assert len(v) == 1 and v[0].contract == "NoMergedAllGather"
    assert "cell/x" in v[0].render()

    assert NoInvoluntaryRemat().check(
        CompiledArtifact("c", GATHER_SLICE_HLO))
    assert not NoInvoluntaryRemat().check(
        CompiledArtifact("c", CLEAN_AG_HLO))

    budget = CollectiveBudget(max_per_block=1)
    assert not budget.check(CompiledArtifact("c", CLEAN_AG_HLO))
    over = CompiledArtifact("c", collective_counts={"all-gather": 5})
    assert budget.check(over)
    assert not CollectiveBudget(max_per_block=3, blocks=2).check(over)


def test_peak_bytes_within_two_sided():
    ok = CompiledArtifact("c", peak_bytes=1500)
    assert not PeakBytesWithin(modeled_bytes=1000, factor=2.0).check(ok)
    # compiled way above modeled: the model is lying low (over-admission)
    high = CompiledArtifact("c", peak_bytes=5000)
    assert PeakBytesWithin(1000, 2.0).check(high)
    # compiled way below modeled: the model cries wolf (over-serialization)
    low = CompiledArtifact("c", peak_bytes=100)
    assert PeakBytesWithin(1000, 2.0).check(low)
    # a backend with no memory_analysis is itself a violation
    assert PeakBytesWithin(1000, 2.0).check(
        CompiledArtifact("c", peak_bytes=None))


# ---------------------------------------------------------------------------
# integration: the runner + a real naive-shard program, in subprocesses
# ---------------------------------------------------------------------------


def run_sub(argv, devices=None, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, *argv], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=900)


def test_runner_lint_clean_on_head():
    out = run_sub(["-m", "repro.analysis", "--lint-only"])
    assert out.returncode == 0, out.stdout + out.stderr
    assert "repro-lint: clean" in out.stdout


def test_runner_fails_on_bad_tree(tmp_path):
    (tmp_path / "core").mkdir()
    (tmp_path / "core" / "bad.py").write_text(textwrap.dedent("""
        import os, time
        FLAG = os.environ.get("REPRO_X")
        def traced():
            t = time.time()
            try:
                return t
            except Exception:
                pass
    """))
    out = run_sub(["-m", "repro.analysis", "--lint-only",
                   "--lint-root", str(tmp_path)])
    assert out.returncode == 1, out.stdout + out.stderr
    for rule in ("R001", "R002", "R003"):
        assert rule in out.stdout, (rule, out.stdout)


NAIVE_SHARD_SCRIPT = r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.contracts import find_merged_allgathers
from repro.launch.mesh import _mesh

B, G, S, D = 2, 8, 16, 8
mesh = _mesh((1, 2), ("data", "model"))
# The pre-PR-2 bug shape: the (B, G) pair already flattened into one merged
# lead of B*G=16, sharded across the model axis. Any consumer that needs
# the full representation forces GSPMD to all-gather the merged dim whole.
x = jax.random.normal(jax.random.PRNGKey(0), (B * G, S, D))

def naive(x):
    x = jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P("model", None, None)))
    y = jax.lax.with_sharding_constraint(
        x * 2.0, NamedSharding(mesh, P(None, None, None)))
    return y + 1.0

with jax.set_mesh(mesh):
    hlo = jax.jit(naive).lower(x).compile().as_text()
bad = find_merged_allgathers(hlo, {B * G}, min_rank=3)
assert bad, "expected the naive flatten-then-shard to force a merged-lead " \
    "all-gather, found none:\n" + hlo
print("NAIVE_SHARD_CONTRACT_FIRES", bad[0])
"""


def test_merged_allgather_contract_fires_on_naive_shard():
    """The NoMergedAllGather finder catches a real compiled program that
    merges a mesh-sharded group dim — the exact regression the contract
    guards, rebuilt via a naive flatten on a 2-device host mesh."""
    out = run_sub(["-c", NAIVE_SHARD_SCRIPT], devices=2)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "NAIVE_SHARD_CONTRACT_FIRES" in out.stdout


def test_runner_contract_cell_clean_on_head(tmp_path):
    """One real contract cell end-to-end through `python -m repro.analysis`
    (ci.sh leg 7 runs the full matrix; this keeps tier-1 to a single
    compile). A filtered run must not touch the checked-in baseline."""
    out = run_sub(["-m", "repro.analysis", "--contracts-only",
                   "--presets", "default", "--cells", "evoformer_fwd",
                   "--devices", "2"], cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "contract evoformer_fwd/default: ok" in out.stdout
    assert not (tmp_path / "BENCH_contracts.json").exists()


def test_bench_contracts_baseline_in_sync():
    """The checked-in BENCH_contracts.json matches what the runner would
    write: full default+oracle matrix, every cell contract-clean, ratios
    recorded."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_contracts.json")
    with open(path) as fh:
        payload = json.load(fh)
    assert payload["presets"] == ["default", "oracle"]
    cells = payload["cells"]
    names = {row["cell"] for row in cells}
    for cell in ("evoformer_fwd", "evoformer_grad", "triangle_opm",
                 "alphafold_dryrun", "dap_stack", "dap_jaxpr"):
        for pname in ("default", "oracle"):
            assert f"{cell}/{pname}" in names, (cell, pname)
    for row in cells:
        assert row["violations"] == [], row
        if row["modeled_bytes"] and row["compiled_peak_bytes"]:
            assert row["ratio"] > 0
