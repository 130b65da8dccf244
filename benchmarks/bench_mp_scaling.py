"""Paper Fig. 10 — model-parallel scaling, DAP vs TP.

Measures real wall-clock of one Evoformer-stack forward+backward on 1/2/4
host devices (reduced config — CPU wall time gives *relative* scaling, the
quantity Fig. 10 plots). DAP runs at every degree; TP is capped at
pair_heads=2 for this config, reproducing the paper's TP scaling limit.
"""
import os
import subprocess
import sys

from benchmarks.common import csv_row

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import time, jax, jax.numpy as jnp
NDEV = {ndev}
MODE = "{mode}"
from repro.core.evoformer import EvoformerConfig, init_evoformer_stack, evoformer_stack
from repro.core.dap import dap_evoformer_stack, shard_dap_inputs
from repro.core.tp import tp_evoformer_stack
cfg = EvoformerConfig(d_msa=64, d_pair=32, msa_heads=4, pair_heads=2, head_dim=16,
                      opm_dim=16, tri_mult_dim=32, n_blocks=2)
params = init_evoformer_stack(jax.random.PRNGKey(0), cfg)
B,s,r = 1,16,32
msa = jax.random.normal(jax.random.PRNGKey(1),(B,s,r,cfg.d_msa))
pair = jax.random.normal(jax.random.PRNGKey(2),(B,r,r,cfg.d_pair))
masks = (jnp.ones((B,s,r)), jnp.ones((B,r)), jnp.ones((B,r,r)))
if MODE == "local":
    fwd = lambda p, *a: evoformer_stack(p, *a, cfg=cfg, remat=False)
    args = (msa, pair) + masks
else:
    mesh = jax.make_mesh((1, NDEV), ("data","model"),
                         axis_types=(jax.sharding.AxisType.Auto,)*2)
    if MODE == "dap":
        fwd = dap_evoformer_stack(mesh, cfg, remat=False)
        args = shard_dap_inputs(mesh, msa, pair, *masks)
    else:
        fwd = tp_evoformer_stack(mesh, cfg, remat=False)
        args = (msa, pair) + masks
def loss(p, *a):
    m, z = fwd(p, *a)
    return jnp.sum(m**2) + jnp.sum(z**2)
step = jax.jit(jax.grad(loss))
out = step(params, *args); jax.block_until_ready(out)
ts = []
for _ in range(6):
    t0 = time.perf_counter()
    out = step(params, *args); jax.block_until_ready(out)
    ts.append(time.perf_counter()-t0)
ts.sort()
print("TIME_US", ts[len(ts)//2]*1e6)
"""


def measure(mode: str, ndev: int) -> float:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # a CPU-device model, never the TPU
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(ndev=ndev, mode=mode)],
        env=env, capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        return float("nan")
    for ln in out.stdout.splitlines():
        if ln.startswith("TIME_US"):
            return float(ln.split()[1])
    return float("nan")


def run():
    base = measure("local", 1)
    csv_row("mp_scaling_1dev_baseline", base, "single device fwd+bwd")
    for ndev in (2, 4):
        t = measure("dap", ndev)
        eff = base / (t * ndev) if t == t else 0.0
        csv_row(f"mp_scaling_DAP_{ndev}dev", t,
                f"parallel_efficiency={eff:.2f}")
    t = measure("tp", 2)
    eff = base / (t * 2) if t == t else 0.0
    csv_row("mp_scaling_TP_2dev", t,
            f"parallel_efficiency={eff:.2f} (TP capped at pair heads)")


if __name__ == "__main__":
    run()
