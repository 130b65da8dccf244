"""Paper §IV.C (Fig. 7) — Duality Async Operation / comm-compute overlap.

In XLA the duality pair becomes scheduling freedom (DESIGN.md §2). This bench
compiles the DAP Evoformer and reports, from the scheduled HLO, how many
collectives are async start/done pairs with independent compute inside the
window — the machine-checkable form of the paper's overlap claim. (XLA:CPU
schedules collectives synchronously; the structural placement — swap-back
launched before the pair stack — is still verified via op ordering.)
"""
import os
import re
import subprocess
import sys

from benchmarks.common import csv_row

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import jax, jax.numpy as jnp, re
from repro.core.evoformer import EvoformerConfig, init_evoformer_stack
from repro.core.dap import dap_evoformer_stack, shard_dap_inputs
from repro.core.duality import overlap_report
cfg = EvoformerConfig(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2, head_dim=8,
                      opm_dim=8, tri_mult_dim=16, n_blocks=1)
params = init_evoformer_stack(jax.random.PRNGKey(0), cfg)
B,s,r = 1,8,16
msa = jax.random.normal(jax.random.PRNGKey(1),(B,s,r,cfg.d_msa))
pair = jax.random.normal(jax.random.PRNGKey(2),(B,r,r,cfg.d_pair))
masks = (jnp.ones((B,s,r)), jnp.ones((B,r)), jnp.ones((B,r,r)))
mesh = jax.make_mesh((1,4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
fn = jax.jit(dap_evoformer_stack(mesh, cfg, remat=False))
args = shard_dap_inputs(mesh, msa, pair, *masks)
txt = fn.lower(params, *args).compile().as_text()
rep = overlap_report(txt)
print("OVERLAP", rep)
# structural check: the msa swap-back a2a is emitted before the triangular
# multiplication dots that are independent of it.
lines = txt.splitlines()
a2a_lines = [i for i,l in enumerate(lines) if "all-to-all" in l]
dot_lines = [i for i,l in enumerate(lines) if " dot(" in l]
window = sum(1 for a in a2a_lines if any(a < d for d in dot_lines))
print("PLACEMENT", {"a2a_ops": len(a2a_lines),
                    "a2a_with_compute_after": window})
"""


def run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # a CPU-device model, never the TPU
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        csv_row("duality_overlap", 0, "FAILED " + out.stderr[-200:])
        return
    for ln in out.stdout.strip().splitlines():
        tag, rest = ln.split(" ", 1)
        csv_row(f"duality_{tag.lower()}", 0, rest.replace(",", ";"))


if __name__ == "__main__":
    run()
