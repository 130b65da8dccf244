"""ExecutionPlan: one first-class, frozen, hashable policy object for the
whole execution stack — kernels, parallelism, memory, and async overlap.

FastFold's value is the *composition* of its levers (DAP, fused kernels,
AutoChunk, Duality Async). Before this module each lever was toggled through
a different side channel (env vars read at import, mutable module globals,
hand-threaded kwargs); now every A/B leg, CI preset, benchmark cell, and
per-request serving scenario is a data value:

    from repro.exec import ExecutionPlan, KernelPolicy, use_plan

    plan = ExecutionPlan(kernels=KernelPolicy(triangle="oracle"))
    with use_plan(plan):
        out = alphafold_forward(params, batch, cfg)   # triangle ops -> oracle

Policy matrix (op x leg x backend) — how ``KernelPolicy`` legs resolve for
each op family in ``kernels/ops.py`` (``auto`` is the default everywhere):

    op          "auto" on TPU   "auto" off-TPU            explicit legs
    ----------  --------------  ------------------------  -------------------
    attention   Pallas kernel   XLA online-softmax scan   pallas | interpret |
                                (interpret=True: Pallas     xla | oracle
                                 interpret mode)
    triangle    Pallas kernel   XLA j-block scan          pallas | interpret |
    opm         Pallas kernel   XLA reassociated GEMMs      xla | oracle
    softmax     jnp oracle      jnp oracle                pallas | interpret |
    layer_norm  jnp oracle      jnp oracle                  xla | oracle
    elementwise jnp oracle      jnp oracle                (xla == oracle for
                (its XLA leg)   (its XLA leg)              these op families)
    attn_bwd    fused Pallas    jnp KV-scan recompute     auto | scan
                backward

  * ``enabled=False`` forces the jnp oracle for every op whose leg is
    ``auto`` (the old ``REPRO_DISABLE_KERNELS=1``); the scores-materialized
    Evoformer paths ride the same switch via ``fused_*_supported``.
  * ``interpret=True`` runs interpret-mode Pallas instead of the XLA legs on
    non-TPU backends (the old ``REPRO_PALLAS_INTERPRET=1`` validation leg).
  * ``"oracle"`` on a per-op leg pins just that op family to its jnp oracle
    (``triangle="oracle", opm="oracle"`` is the old
    ``REPRO_FORCE_TRIANGLE_ORACLE=1``).
  * ``attn_bwd="scan"`` pins the attention backward to the jnp KV-scan
    recompute (the old mutable ``ops.FORCE_SCAN_ATTN_BWD``). The choice is
    baked into the op's trace at *call* time, so it scopes correctly under
    ``use_plan`` even though the backward is traced later.
  * Off-TPU, an explicit ``"pallas"`` runs the kernel in interpret mode
    (there is no compiled Pallas backend to target).
  * On TPU ``"auto"`` resolves softmax / layer_norm / elementwise to their
    XLA leg under every parallel backend (local, shard_map, gspmd). Their
    Pallas kernels move one 8-row tile (2-4 KB of bf16) per grid step, so a
    fixed cost per step, not HBM bandwidth, sets their time; XLA fuses the
    same float32 row math into the neighbouring ops. On one v5e the
    full-width train step ran 7.455 s with them on Pallas and 5.166 s on XLA
    (PERF.md section 5). The XLA leg is also what GSPMD can partition on the
    global arrays these ops see under the gspmd backend (attention, triangle
    and OPM run their kernels inside the dist backend's shard_map). An
    explicit ``"pallas"`` still runs the kernels, for A/B runs.

``ParallelPolicy`` subsumes the hand-threaded ``dist=`` kwarg (the backend is
built once via ``make_dist()``), ``MemoryPolicy`` subsumes ``hbm_budget=``
plus per-knob AutoChunk overrides, and ``AsyncPolicy`` gates the Duality
overlap windows (``core/duality.overlap_window`` becomes a passthrough when
disabled).

Scoping: ``current_plan()`` returns the innermost ``use_plan`` scope's plan;
outside any scope it falls back to ``ExecutionPlan.from_env()`` — the single
env-var compatibility shim (``repro/exec/envcompat.py``), evaluated at
*plan-construction* time, never at import. Plans are consulted at trace
time only, so a jitted function traced under one plan must not be reused
under another: bind the plan per jit wrapper (what the ``FastFold`` facade
and the ServingEngine do), or pass the plan as a static jit argument — the
hashability contract exists exactly so two different plans produce two
distinct jit cache entries.

This module is import-light by design (no jax): launchers import it to set
process flags before jax initializes.
"""
from __future__ import annotations

import dataclasses
import json
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any

_LEGS = ("auto", "pallas", "interpret", "xla", "oracle")
_ATTN_BWD_LEGS = ("auto", "scan")
_DIST_BACKENDS = ("local", "shard_map", "gspmd")


@dataclass(frozen=True)
class KernelPolicy:
    """Per-op kernel leg selection (see the policy matrix in the module
    docstring). ``enabled``/``interpret`` steer every ``auto`` op; a per-op
    field pins that op family regardless of the global switches."""

    enabled: bool = True          # False: "auto" ops -> jnp oracles
    interpret: bool = False       # off-TPU "auto" ops -> interpret-mode Pallas
    attention: str = "auto"
    triangle: str = "auto"
    opm: str = "auto"
    softmax: str = "auto"
    layer_norm: str = "auto"
    elementwise: str = "auto"     # bias_sigmoid_mul / bias_dropout_add
    attn_bwd: str = "auto"        # "scan": pin the jnp KV-scan recompute bwd

    def __post_init__(self):
        for op in ("attention", "triangle", "opm", "softmax", "layer_norm",
                   "elementwise"):
            leg = getattr(self, op)
            if leg not in _LEGS:
                raise ValueError(f"KernelPolicy.{op}={leg!r}: not in {_LEGS}")
        if self.attn_bwd not in _ATTN_BWD_LEGS:
            raise ValueError(
                f"KernelPolicy.attn_bwd={self.attn_bwd!r}: "
                f"not in {_ATTN_BWD_LEGS}")


@dataclass(frozen=True)
class ParallelPolicy:
    """Distribution backend + mesh axes — subsumes the ``dist=`` kwarg.

    ``backend``: 'local' (single device, identity collectives),
    'shard_map' (paper-faithful DAP with explicit collectives — valid only
    inside a shard_map over ``axis``), or 'gspmd' (production path;
    ``mesh`` must carry the jax Mesh). ``make_dist()`` builds the matching
    core/dist.py backend."""

    backend: str = "local"
    axis: str = "model"
    mesh: Any = None              # jax.sharding.Mesh (hashable) for 'gspmd'

    def __post_init__(self):
        if self.backend not in _DIST_BACKENDS:
            raise ValueError(f"ParallelPolicy.backend={self.backend!r}: "
                             f"not in {_DIST_BACKENDS}")

    def make_dist(self):
        from repro.core.dist import dist_from_policy

        return dist_from_policy(self)


@dataclass(frozen=True)
class MemoryPolicy:
    """HBM budget + AutoChunk knob overrides — subsumes ``hbm_budget=``.

    ``hbm_budget=None`` means the hardware default (launch.mesh.HBM_BYTES).
    Nonzero chunk/tile knobs override the EvoformerConfig's values (and are
    then pinned through the AutoChunk planner); ``auto_chunk`` overrides the
    config's planner opt-in when not None."""

    hbm_budget: int | None = None
    auto_chunk: bool | None = None
    inference_chunk: int = 0
    opm_chunk: int = 0
    attn_kv_tile: int = 0
    tri_k_tile: int = 0
    opm_s_tile: int = 0

    _KNOBS = ("inference_chunk", "opm_chunk", "attn_kv_tile", "tri_k_tile",
              "opm_s_tile")

    def apply(self, evo_cfg):
        """EvoformerConfig with this policy's overrides applied (returns the
        input unchanged when nothing overrides)."""
        updates = {k: getattr(self, k) for k in self._KNOBS
                   if getattr(self, k)}
        if self.auto_chunk is not None:
            updates["auto_chunk"] = self.auto_chunk
        if not updates:
            return evo_cfg
        return dataclasses.replace(evo_cfg, **updates)


@dataclass(frozen=True)
class AsyncPolicy:
    """Duality-Async enablement: when ``overlap_windows`` is False,
    ``core/duality.overlap_window`` is a plain passthrough (no optimization
    barrier), letting A/B cells measure the paper's §IV.C overlap."""

    overlap_windows: bool = True


# Rung 1 of the degradation ladder: the minimal-transient chunk/tile knobs
# (the most serialized settings the AutoChunk candidate sets ever pick).
_DEGRADED_MEMORY = dict(inference_chunk=1, opm_chunk=8, attn_kv_tile=32,
                        tri_k_tile=16, opm_s_tile=16)


@dataclass(frozen=True)
class ExecutionPlan:
    """The composed execution policy. Frozen and hashable: equal plans hash
    equal (jit caching with the plan as a static argument works), distinct
    plans are distinct cache keys."""

    kernels: KernelPolicy = field(default_factory=KernelPolicy)
    parallel: ParallelPolicy = field(default_factory=ParallelPolicy)
    memory: MemoryPolicy = field(default_factory=MemoryPolicy)
    duality: AsyncPolicy = field(default_factory=AsyncPolicy)

    # -- convenience builders ------------------------------------------------

    def replace(self, **kw) -> "ExecutionPlan":
        return dataclasses.replace(self, **kw)

    def with_kernels(self, **kw) -> "ExecutionPlan":
        return self.replace(kernels=dataclasses.replace(self.kernels, **kw))

    def with_parallel(self, **kw) -> "ExecutionPlan":
        return self.replace(parallel=dataclasses.replace(self.parallel, **kw))

    def with_memory(self, **kw) -> "ExecutionPlan":
        return self.replace(memory=dataclasses.replace(self.memory, **kw))

    def with_async(self, **kw) -> "ExecutionPlan":
        return self.replace(duality=dataclasses.replace(self.duality, **kw))

    def degrade(self) -> "ExecutionPlan | None":
        """Next rung of the graceful-degradation ladder (the serving
        engine's OOM fallback): (1) tighten every MemoryPolicy chunk/tile
        knob to its minimal-transient setting (serializes compute, keeps
        the kernel legs), then (2) drop to the jnp oracle kernel leg.
        Returns ``None`` when fully degraded. Each rung is a plain frozen
        plan — distinct hash, own jit cache entry — so fault-driven
        fallbacks compose with ``use_plan`` scoping like any other plan."""
        tight = dataclasses.replace(self.memory, **_DEGRADED_MEMORY)
        if self.memory != tight:
            return self.replace(memory=tight)
        if self.kernels.enabled:
            return self.with_kernels(enabled=False)
        return None

    @classmethod
    def from_env(cls) -> "ExecutionPlan":
        """Legacy-flag compatibility: build the plan the process env asks
        for. The ONLY env-var pathway left in the codebase — evaluated at
        plan-construction time (never at import), so flags set after import
        take effect (see repro/exec/envcompat.py)."""
        from repro.exec import envcompat

        return envcompat.plan_from_env()

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        """Plain-JSON form of the full plan (every telemetry event and
        BENCH_serving.json row records this, not a process-salted hash).
        A live ``ParallelPolicy.mesh`` is a device handle, not data — plans
        carrying one don't serialize."""
        if self.parallel.mesh is not None:
            raise ValueError(
                "ExecutionPlan.to_dict: ParallelPolicy.mesh holds a live "
                "device mesh; serialize the mesh-free plan and rebind the "
                "mesh on load")
        return {
            "kernels": dataclasses.asdict(self.kernels),
            "parallel": {"backend": self.parallel.backend,
                         "axis": self.parallel.axis},
            "memory": dataclasses.asdict(self.memory),
            "duality": dataclasses.asdict(self.duality),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ExecutionPlan":
        """Inverse of ``to_dict`` — round-trips to an equal (and equal-hash)
        plan, so a deserialized plan hits the same jit cache entries.
        Policy ``__post_init__`` validation applies (bad legs raise)."""
        return cls(
            kernels=KernelPolicy(**d.get("kernels", {})),
            parallel=ParallelPolicy(**d.get("parallel", {})),
            memory=MemoryPolicy(**d.get("memory", {})),
            duality=AsyncPolicy(**d.get("duality", {})),
        )

    def to_json(self) -> str:
        """Canonical JSON (sorted keys): equal plans serialize to equal
        strings, making the string itself a stable cross-process cache/
        interning key — what python ``hash()`` (per-process salted) is not."""
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ExecutionPlan":
        return cls.from_dict(json.loads(s))

    def describe(self) -> str:
        k = self.kernels
        per_op = ",".join(
            f"{op}={getattr(k, op)}" for op in
            ("attention", "triangle", "opm", "softmax", "layer_norm",
             "elementwise") if getattr(k, op) != "auto")
        return (f"kernels(enabled={k.enabled} interpret={k.interpret}"
                f"{' ' + per_op if per_op else ''} attn_bwd={k.attn_bwd}) "
                f"parallel({self.parallel.backend}) "
                f"memory(budget={self.memory.hbm_budget}) "
                f"async(overlap={self.duality.overlap_windows})")


# ---------------------------------------------------------------------------
# Named presets (the ci.sh legs; REPRO_PLAN=<name> selects one, see envcompat)
# ---------------------------------------------------------------------------

PRESETS: dict[str, ExecutionPlan] = {
    # Leg 1: kernels enabled — Pallas on TPU, XLA-native legs elsewhere.
    "default": ExecutionPlan(),
    # Leg 2: every op pinned to its jnp oracle (scores-materialized paths).
    "oracle": ExecutionPlan(kernels=KernelPolicy(enabled=False)),
    # Leg 3: interpret-mode Pallas validation off-TPU.
    "interpret": ExecutionPlan(kernels=KernelPolicy(interpret=True)),
    # Leg 4: only the pair-stack kernels pinned to their oracles.
    "triangle-oracle": ExecutionPlan(
        kernels=KernelPolicy(triangle="oracle", opm="oracle")),
}


def preset(name: str) -> ExecutionPlan:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(
            f"unknown plan preset {name!r}; choose from {sorted(PRESETS)}"
        ) from None


# ---------------------------------------------------------------------------
# Context-local plan scoping
# ---------------------------------------------------------------------------

_PLAN: ContextVar[ExecutionPlan | None] = ContextVar("repro_execution_plan",
                                                     default=None)


def current_plan() -> ExecutionPlan:
    """The innermost ``use_plan`` scope's plan, else the env-compat plan.
    Consulted by kernels/ops.py, core/duality.py, alphafold_forward, the
    ServingEngine, … at trace time."""
    plan = _PLAN.get()
    if plan is not None:
        return plan
    return ExecutionPlan.from_env()


@contextmanager
def use_plan(plan: ExecutionPlan):
    """Scope ``plan`` as the current execution plan (re-entrant; nested
    scopes restore the outer plan on exit). Plans steer *tracing*: enter the
    scope around the traced call (or inside the traced function), and never
    share one jit wrapper across plans."""
    if not isinstance(plan, ExecutionPlan):
        raise TypeError(f"use_plan expects an ExecutionPlan, got {plan!r}")
    token = _PLAN.set(plan)
    try:
        yield plan
    finally:
        _PLAN.reset(token)
