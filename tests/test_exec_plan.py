"""ExecutionPlan semantics (repro/exec): env-compat round-trip + late
binding (the old import-time KERNELS_ENABLED bug), nested use_plan scoping,
the hashability/jit-cache contract, leg-numerics parity, MemoryPolicy
overrides, AsyncPolicy gating, per-request serving plans, the FastFold
facade, and the no-env-access-outside-envcompat gate."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import duality
from repro.exec.plan import (
    ExecutionPlan,
    KernelPolicy,
    MemoryPolicy,
    current_plan,
    preset,
    use_plan,
)
from repro.kernels import ops

_LEGACY_VARS = ("REPRO_PLAN", "REPRO_DISABLE_KERNELS",
                "REPRO_PALLAS_INTERPRET", "REPRO_FORCE_TRIANGLE_ORACLE",
                "REPRO_FORCE_SCAN_ATTN_BWD")


@pytest.fixture
def clean_env(monkeypatch):
    for v in _LEGACY_VARS:
        monkeypatch.delenv(v, raising=False)
    return monkeypatch


# ---------------------------------------------------------------------------
# env compat
# ---------------------------------------------------------------------------


def test_from_env_round_trips_all_legacy_flags(clean_env):
    assert ExecutionPlan.from_env() == ExecutionPlan()
    clean_env.setenv("REPRO_DISABLE_KERNELS", "1")
    clean_env.setenv("REPRO_PALLAS_INTERPRET", "1")
    clean_env.setenv("REPRO_FORCE_TRIANGLE_ORACLE", "1")
    clean_env.setenv("REPRO_FORCE_SCAN_ATTN_BWD", "1")
    k = ExecutionPlan.from_env().kernels
    assert k == KernelPolicy(enabled=False, interpret=True, triangle="oracle",
                             opm="oracle", attn_bwd="scan")


def test_from_env_plan_presets_and_composition(clean_env):
    clean_env.setenv("REPRO_PLAN", "triangle-oracle")
    k = ExecutionPlan.from_env().kernels
    assert (k.triangle, k.opm, k.enabled) == ("oracle", "oracle", True)
    # legacy flags layer ON TOP of the preset
    clean_env.setenv("REPRO_PALLAS_INTERPRET", "1")
    k = ExecutionPlan.from_env().kernels
    assert (k.triangle, k.interpret) == ("oracle", True)
    clean_env.setenv("REPRO_PLAN", "no-such-preset")
    with pytest.raises(KeyError):
        ExecutionPlan.from_env()


def test_env_flags_bind_at_plan_construction_not_import(clean_env):
    """Regression for the import-order bug: KERNELS_ENABLED used to be read
    from the environment at import time, so setting REPRO_DISABLE_KERNELS
    *after* `import repro.kernels.ops` silently did nothing. Now the flag is
    read when the plan is constructed — long after every import."""
    assert current_plan().kernels.enabled
    assert ops.fused_attention_supported((2, 8, 2, 4))
    clean_env.setenv("REPRO_DISABLE_KERNELS", "1")   # post-import!
    assert not current_plan().kernels.enabled
    assert not ops.fused_attention_supported((2, 8, 2, 4))
    assert not ops.fused_triangle_supported(16, 12, jnp.float32)
    clean_env.delenv("REPRO_DISABLE_KERNELS")
    assert ops.fused_attention_supported((2, 8, 2, 4))


# ---------------------------------------------------------------------------
# scoping
# ---------------------------------------------------------------------------


def test_nested_use_plan_scopes_restore(clean_env):
    outer = preset("interpret")
    inner = preset("oracle")
    base = current_plan()
    with use_plan(outer):
        assert current_plan() is outer
        with use_plan(inner):
            assert current_plan() is inner
        assert current_plan() is outer
    assert current_plan() == base


def test_use_plan_scope_restores_on_exception(clean_env):
    base = current_plan()
    with pytest.raises(RuntimeError):
        with use_plan(preset("oracle")):
            raise RuntimeError("boom")
    assert current_plan() == base


def test_use_plan_rejects_non_plan():
    with pytest.raises(TypeError):
        with use_plan("oracle"):
            pass


def test_kernel_policy_validates_legs():
    with pytest.raises(ValueError):
        KernelPolicy(triangle="pallass")
    with pytest.raises(ValueError):
        KernelPolicy(attn_bwd="oracle")


# ---------------------------------------------------------------------------
# the leg rule on TPU
# ---------------------------------------------------------------------------


_ROW_FAMILIES = ("softmax", "layer_norm", "elementwise")
_KERNEL_FAMILIES = ("attention", "triangle", "opm")


def _tpu_rule_plans():
    """name -> (plan, expected leg per op family) for the TPU leg rule."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    default = {**{op: "xla" for op in _ROW_FAMILIES},
               **{op: "pallas" for op in _KERNEL_FAMILIES}}
    explicit = KernelPolicy(softmax="pallas", layer_norm="pallas",
                            elementwise="pallas")
    return {
        "default": (ExecutionPlan(), default),
        "gspmd": (ExecutionPlan().with_parallel(backend="gspmd", mesh=mesh),
                  default),
        "shard_map": (ExecutionPlan().with_parallel(backend="shard_map"),
                      default),
        "explicit_pallas": (ExecutionPlan(kernels=explicit),
                            dict.fromkeys(default, "pallas")),
        "disabled": (ExecutionPlan(kernels=KernelPolicy(enabled=False)),
                     dict.fromkeys(default, "oracle")),
    }


@pytest.mark.parametrize("op", _ROW_FAMILIES + _KERNEL_FAMILIES)
@pytest.mark.parametrize("plan_name", ["default", "gspmd", "shard_map",
                                       "explicit_pallas", "disabled"])
def test_kernel_leg_rule_on_tpu(clean_env, plan_name, op):
    """On TPU ``auto`` sends the row-wise families to XLA and attention,
    triangle and OPM to their Pallas kernels, under every parallel backend;
    an explicit 'pallas' leg still reaches the row-wise kernels and
    ``enabled=False`` still gives the oracle."""
    plan, want = _tpu_rule_plans()[plan_name]
    clean_env.setattr(jax, "default_backend", lambda: "tpu")
    with use_plan(plan):
        assert ops.kernel_leg(op) == want[op]


# ---------------------------------------------------------------------------
# hashability / jit-cache contract + leg numerics
# ---------------------------------------------------------------------------


def test_two_plans_two_jit_cache_entries_identical_numerics(clean_env):
    """Two different plans on identical shapes produce distinct jit cache
    entries (the hashability contract) and identical numerics for the
    pallas/xla-vs-oracle attention legs; an equal plan (fresh instance) must
    NOT retrace."""
    traces = []

    @functools.partial(jax.jit, static_argnums=0)
    def run(plan, q):
        traces.append(plan)
        with use_plan(plan):
            return ops.fused_attention(q, q, q)

    q = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 2, 8))
    default, oracle = ExecutionPlan(), preset("oracle")
    assert hash(default) == hash(ExecutionPlan())
    assert hash(default) != hash(oracle)

    y_fused = run(default, q)
    y_again = run(ExecutionPlan(), q)       # equal plan -> cache hit
    assert len(traces) == 1
    y_oracle = run(oracle, q)
    assert len(traces) == 2                 # distinct plan -> new entry
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_again),
                               atol=0)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_oracle),
                               atol=1e-6)


def test_triangle_opm_legs_identical_under_plan_scopes(clean_env):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    B, S, I, C, D = 1, 4, 6, 8, 10
    a = jax.random.normal(ks[0], (B, S, I, C))
    b = jax.random.normal(ks[1], (B, S, I, C))
    ma = jnp.ones((B, S, I))
    mb = jnp.ones((B, S, I))
    w = jax.random.normal(ks[2], (C * C, D))
    bias = jax.random.normal(ks[3], (D,))
    outs = {}
    for name in ("default", "oracle", "triangle-oracle"):
        with use_plan(preset(name)):
            outs[name] = ops.fused_outer_product_mean(a, b, ma, mb, w, bias)
    for name in ("oracle", "triangle-oracle"):
        np.testing.assert_allclose(np.asarray(outs["default"]),
                                   np.asarray(outs[name]), atol=2e-5)


def test_attn_bwd_choice_baked_at_call_time(clean_env):
    """KernelPolicy.attn_bwd is resolved when the op is CALLED, so a
    use_plan scope around the op call governs the backward even though jax
    traces the custom_vjp bwd after the scope exits — and the two backward
    legs agree numerically."""
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 2, 8))

    def loss_default(q_):
        return jnp.sum(ops.fused_attention(q_, q_, q_, kv_tile=8) ** 2)

    def loss_scan(q_):
        with use_plan(current_plan().with_kernels(attn_bwd="scan")):
            return jnp.sum(ops.fused_attention(q_, q_, q_, kv_tile=8) ** 2)

    g_default = jax.grad(loss_default)(q)
    g_scan = jax.grad(loss_scan)(q)
    np.testing.assert_allclose(np.asarray(g_default), np.asarray(g_scan),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# MemoryPolicy / AsyncPolicy
# ---------------------------------------------------------------------------


def test_memory_policy_overrides_evoformer_knobs():
    from repro.core.evoformer import EvoformerConfig

    cfg = EvoformerConfig()
    pol = MemoryPolicy(attn_kv_tile=64, tri_k_tile=32, auto_chunk=False)
    out = pol.apply(cfg)
    assert (out.attn_kv_tile, out.tri_k_tile, out.auto_chunk) == (64, 32,
                                                                  False)
    assert out.opm_chunk == cfg.opm_chunk
    assert MemoryPolicy().apply(cfg) is cfg  # no overrides -> same object


def test_async_policy_gates_overlap_window(clean_env):
    x = jnp.ones((3,))
    y = jnp.ones((4,))
    with use_plan(ExecutionPlan().with_async(overlap_windows=False)):
        cx, ix = duality.overlap_window(x, y)
        assert cx is x and ix is y           # pure passthrough, no barrier
    cx, ix = duality.overlap_window(x, y)
    assert cx is not x                       # barrier emitted new values
    np.testing.assert_allclose(np.asarray(cx), np.asarray(x))


# ---------------------------------------------------------------------------
# serving: per-request plans
# ---------------------------------------------------------------------------


def test_serving_engine_mixed_plan_traffic(clean_env):
    from repro.configs import get_config
    from repro.models.decoder import init_model
    from repro.serving.engine import ServingEngine

    cfg = get_config("qwen2-1.5b", reduced_variant=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=(5 + i,)) for i in range(3)]

    eng_ref = ServingEngine(params, cfg, n_slots=3, max_seq=32)
    want = [eng_ref.submit(p, max_new_tokens=4) for p in prompts]
    eng_ref.run()

    # Same traffic, middle request on the oracle-leg canary plan: runs in
    # the same engine (two decode groups per step) with identical greedy
    # output — no global toggles, no cross-request leakage.
    eng = ServingEngine(params, cfg, n_slots=3, max_seq=32)
    canary = preset("oracle")
    got = [eng.submit(p, max_new_tokens=4,
                      plan=canary if i == 1 else None)
           for i, p in enumerate(prompts)]
    eng.run()
    assert got[1].plan == canary
    assert len(eng._decode_fns) == 2         # one jit wrapper per plan
    for w, g in zip(want, got):
        assert w.generated == g.generated


# ---------------------------------------------------------------------------
# FastFold facade
# ---------------------------------------------------------------------------


def test_fastfold_facade_forward_train_serve(clean_env):
    from repro.configs.alphafold import SMOKE
    from repro.data import protein_batches
    from repro.exec.session import FastFold

    ff = FastFold(SMOKE)
    params = ff.init(jax.random.PRNGKey(0))
    pb = next(protein_batches(batch=1, n_seq=4, n_res=8, seed=0))
    batch = {k: jnp.asarray(getattr(pb, k)) for k in
             ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
              "pseudo_beta", "bert_mask", "true_msa")}
    out = ff.forward(params, batch)
    assert out["coords"].shape == (1, 8, 3)
    loss, metrics = ff.train_loss(params, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    # per-request plan override through the serving entry point
    outs = ff.serve(params, [batch, batch],
                    plans=[None, preset("oracle")])
    np.testing.assert_allclose(np.asarray(outs[0]["coords"]),
                               np.asarray(outs[1]["coords"]), atol=1e-4)
    with pytest.raises(ValueError):
        ff.serve(params, [batch], plans=[None, None])


# ---------------------------------------------------------------------------
# the env gate, enforced in tier-1 too
# ---------------------------------------------------------------------------


def test_plan_json_round_trip_all_presets():
    """to_json/from_json round-trips every preset to an equal AND
    equal-hash plan — a deserialized plan must hit the same jit cache
    entries as the original (the hashability contract, extended across
    process boundaries)."""
    from repro.exec.plan import PRESETS

    for name, plan in PRESETS.items():
        back = ExecutionPlan.from_json(plan.to_json())
        assert back == plan, name
        assert hash(back) == hash(plan), name
        # canonical form: equal plans serialize to equal strings
        assert back.to_json() == plan.to_json(), name
        d = plan.to_dict()
        assert set(d) == {"kernels", "parallel", "memory", "duality"}, name
        assert ExecutionPlan.from_dict(d) == plan, name


def test_plan_serialization_validates_and_rejects_mesh():
    degraded = preset("default").degrade()
    assert ExecutionPlan.from_json(degraded.to_json()) == degraded
    # from_dict goes through the policies' __post_init__ validation
    bad = preset("default").to_dict()
    bad["kernels"]["triangle"] = "quantum"
    with pytest.raises(ValueError, match="triangle"):
        ExecutionPlan.from_dict(bad)
    # a live mesh is a device handle, not data
    meshy = preset("default").with_parallel(backend="gspmd", mesh=object())
    with pytest.raises(ValueError, match="mesh"):
        meshy.to_dict()


def test_no_env_access_outside_envcompat():
    """Env access under src/repro is confined to the single compat module
    (exec/envcompat.py) — repro-lint rule R001, the same gate ci.sh leg 7
    runs. Strictly stronger than the old `os.environ` string scan: the AST
    pass also catches `from os import environ` and `os.getenv` aliases."""
    from repro.analysis.lint import lint_tree

    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    offenders = [f.render() for f in lint_tree(root) if f.rule == "R001"]
    assert not offenders, (
        f"env access outside exec/envcompat.py (R001): {offenders}")
