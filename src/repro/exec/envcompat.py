"""The single environment-variable compatibility module.

Every legacy process-global toggle maps onto one ExecutionPlan field here —
and ONLY here: ``os.environ`` is not read (or written) anywhere else under
``src/repro`` (ci.sh greps for it). Plans are built at *construction* time,
never at import, so flags exported after ``import repro...`` still take
effect (the old ``ops.KERNELS_ENABLED`` was read once at import and went
stale — the regression test for that lives in tests/test_exec_plan.py).

Recognized variables:

  REPRO_PLAN=<preset>              start from a named preset
                                   (default | oracle | interpret |
                                    triangle-oracle) — the ci.sh legs.
  REPRO_DISABLE_KERNELS=1          -> KernelPolicy.enabled = False
  REPRO_PALLAS_INTERPRET=1         -> KernelPolicy.interpret = True
  REPRO_FORCE_TRIANGLE_ORACLE=1    -> KernelPolicy.triangle = opm = "oracle"
  REPRO_FORCE_SCAN_ATTN_BWD=1      -> KernelPolicy.attn_bwd = "scan"
  REPRO_FAULT_SEED=<int>           -> default seed of resilience.FaultInjector
                                   (not a plan field; read via fault_seed())
  JAX_COMPILATION_CACHE_DIR=<dir>  -> persistent compile cache of the entry
                                   points (read via compilation_cache_dir())

Legacy flags layer on top of the preset, so e.g.
``REPRO_PLAN=interpret REPRO_FORCE_TRIANGLE_ORACLE=1`` composes.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib

_ENV_VARS = (
    "REPRO_PLAN",
    "REPRO_DISABLE_KERNELS",
    "REPRO_PALLAS_INTERPRET",
    "REPRO_FORCE_TRIANGLE_ORACLE",
    "REPRO_FORCE_SCAN_ATTN_BWD",
)

# The checkout this package runs from: src/repro/exec/envcompat.py -> root.
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

# Memoized on the observed env values — re-reads the environment on every
# call (cheap), rebuilds the plan only when a relevant variable changed.
_cache: dict[tuple, object] = {}


def _flag(name: str) -> bool:
    return os.environ.get(name, "0") == "1"


def plan_from_env():
    """ExecutionPlan for the current process environment (see module doc)."""
    from repro.exec import plan as planmod

    key = tuple(os.environ.get(v) for v in _ENV_VARS)
    hit = _cache.get(key)
    if hit is not None:
        return hit

    p = planmod.preset(os.environ.get("REPRO_PLAN", "default"))
    kern = p.kernels
    if _flag("REPRO_DISABLE_KERNELS"):
        kern = dataclasses.replace(kern, enabled=False)
    if _flag("REPRO_PALLAS_INTERPRET"):
        kern = dataclasses.replace(kern, interpret=True)
    if _flag("REPRO_FORCE_TRIANGLE_ORACLE"):
        kern = dataclasses.replace(kern, triangle="oracle", opm="oracle")
    if _flag("REPRO_FORCE_SCAN_ATTN_BWD"):
        kern = dataclasses.replace(kern, attn_bwd="scan")
    if kern is not p.kernels:
        p = p.replace(kernels=kern)
    _cache[key] = p
    return p


def fault_seed() -> int | None:
    """Default FaultInjector seed from REPRO_FAULT_SEED (None when unset) —
    the resilience CI leg pins a process-wide fault schedule through here,
    keeping os.environ access confined to this module."""
    v = os.environ.get("REPRO_FAULT_SEED")
    return int(v) if v else None


def force_host_device_count(n: int) -> None:
    """Set the XLA host-platform device-count flag. Must run before jax
    initializes its backends — launchers (launch/dryrun.py, the benchmark
    subprocess scripts) call this instead of touching os.environ, keeping
    env access confined to this module. This package imports no jax, so
    importing it never triggers backend init."""
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"


def compilation_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache for the entry points:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else a fixed
    ``.jax_cache`` inside the checkout. The path is part of the cache key, so
    it never depends on a temp dir, pid or clock."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
