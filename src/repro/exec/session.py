"""FastFold facade: bind ``(AlphaFoldConfig, ExecutionPlan)`` once, use it
everywhere.

    from repro.exec import ExecutionPlan, FastFold

    ff = FastFold(SMOKE, ExecutionPlan())
    params = ff.init(jax.random.PRNGKey(0))
    out = ff.forward(params, batch)                 # folding inference
    loss, metrics = ff.train_loss(params, batch, rng)
    outs = ff.serve(params, [batch_a, batch_b])     # per-request plans ok

The facade owns one jit wrapper per (plan, mode), so two plans can never
share a trace (the plan steers trace-time branches); the bound
ParallelPolicy provides the dist backend and, for the GSPMD backend, the
mesh scope around every call. ``examples/quickstart.py``,
``examples/train_alphafold_mini.py``, and the launch scripts drive the model
through this class instead of hand-threading ``dist=`` / ``hbm_budget=``.
"""
from __future__ import annotations

import contextlib

import jax

from repro.exec.plan import ExecutionPlan, current_plan, use_plan


def _mesh_scope(plan: ExecutionPlan):
    """Mesh context for the plan's dist backend (GSPMD needs the mesh active
    around trace and execution; the other backends need nothing)."""
    mesh = plan.parallel.mesh
    if plan.parallel.backend == "gspmd" and mesh is not None:
        return jax.set_mesh(mesh)
    return contextlib.nullcontext()


class FastFold:
    """AlphaFold bound to one ExecutionPlan (overridable per call)."""

    def __init__(self, config, plan: ExecutionPlan | None = None):
        self.config = config
        self.plan = plan if plan is not None else current_plan()
        self._jitted: dict = {}

    # -- params -------------------------------------------------------------

    def init(self, key):
        from repro.core.alphafold import init_alphafold

        with use_plan(self.plan):
            return init_alphafold(key, self.config)

    # -- composition hook ---------------------------------------------------

    @property
    def loss_fn(self):
        """Plain ``(params, batch, rng) -> alphafold_train_loss`` under the
        bound plan — hand this to train.loop.make_train_step (which jits the
        whole step itself)."""
        from repro.core.alphafold import alphafold_train_loss

        def fn(params, batch, rng):
            with use_plan(self.plan):
                return alphafold_train_loss(
                    params, batch, self.config, rng=rng,
                    dist=self.plan.parallel.make_dist())

        return fn

    # -- jitted entry points ------------------------------------------------

    def _get_jitted(self, kind: str, plan: ExecutionPlan, train: bool = False):
        key = (kind, plan, train)
        fn = self._jitted.get(key)
        if fn is not None:
            return fn
        from repro.core.alphafold import alphafold_forward, \
            alphafold_train_loss

        if kind == "forward":
            def impl(params, batch, rng):
                with use_plan(plan):
                    return alphafold_forward(
                        params, batch, self.config, rng=rng, train=train,
                        dist=plan.parallel.make_dist())
        else:
            def impl(params, batch, rng):
                with use_plan(plan):
                    return alphafold_train_loss(
                        params, batch, self.config, rng=rng,
                        dist=plan.parallel.make_dist())
        fn = jax.jit(impl)
        self._jitted[key] = fn
        return fn

    def lower(self, kind: str, params, batch, rng=None, *,
              plan: ExecutionPlan | None = None, train: bool = False):
        """AOT-lower the jitted ``"forward"`` or ``"train_loss"`` entry under
        the plan's mesh scope. ``.compile()`` on the result gives the program
        (``memory_analysis()``, ``as_text()``), callable as
        ``(params, batch, rng)``."""
        if kind not in ("forward", "train_loss"):
            raise ValueError(f"FastFold.lower: unknown entry {kind!r}")
        plan = plan if plan is not None else self.plan
        with _mesh_scope(plan):
            return self._get_jitted(kind, plan, train).lower(params, batch,
                                                             rng)

    def forward(self, params, batch, *, rng=None, train: bool = False,
                plan: ExecutionPlan | None = None):
        """Full folding forward (recycling included) under the bound plan
        (or a per-call override)."""
        plan = plan if plan is not None else self.plan
        with _mesh_scope(plan):
            return self._get_jitted("forward", plan, train)(params, batch,
                                                            rng)

    def train_loss(self, params, batch, rng=None, *,
                   plan: ExecutionPlan | None = None):
        plan = plan if plan is not None else self.plan
        with _mesh_scope(plan):
            return self._get_jitted("train_loss", plan)(params, batch, rng)

    def serve(self, params, batches, *, plans=None):
        """Folding-inference service entry: run each request batch through
        ``forward``. ``plans`` (optional, same length) overrides the plan per
        request — e.g. an oracle-leg canary beside production pallas-leg
        requests — with one jit cache entry per distinct plan."""
        batches = list(batches)
        if plans is None:
            plans = [None] * len(batches)
        if len(plans) != len(batches):
            raise ValueError(
                f"serve: {len(batches)} batches but {len(plans)} plans")
        return [self.forward(params, b, plan=p)
                for b, p in zip(batches, plans)]
