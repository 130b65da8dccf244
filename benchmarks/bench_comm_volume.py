"""Paper Table III — communication volume per Evoformer block, DAP vs TP.

Analytic volumes for the paper's training shapes, plus *measured* collective
schedules parsed from the compiled HLO of both implementations (subprocess on
4 fake host devices).
"""
import os
import subprocess
import sys

from benchmarks.common import csv_row

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MEASURE = r"""
import re, jax, jax.numpy as jnp
from repro.core.evoformer import EvoformerConfig, init_evoformer_stack, evoformer_stack
from repro.core.dap import dap_evoformer_stack, shard_dap_inputs
from repro.core.tp import tp_evoformer_stack
from repro.roofline import analysis
cfg = EvoformerConfig(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2, head_dim=8,
                      opm_dim=8, tri_mult_dim=16, n_blocks=1)
params = init_evoformer_stack(jax.random.PRNGKey(0), cfg)
B,s,r = 1,8,16
msa = jax.random.normal(jax.random.PRNGKey(1),(B,s,r,cfg.d_msa))
pair = jax.random.normal(jax.random.PRNGKey(2),(B,r,r,cfg.d_pair))
masks = (jnp.ones((B,s,r)), jnp.ones((B,r)), jnp.ones((B,r,r)))
mesh2 = jax.make_mesh((1,2), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
mesh4 = jax.make_mesh((1,4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
# DAP fwd
fn = jax.jit(dap_evoformer_stack(mesh4, cfg, remat=False))
args = shard_dap_inputs(mesh4, msa, pair, *masks)
txt = fn.lower(params, *args).compile().as_text()
st = analysis.parse_collectives(txt, 4)
print("DAP_FWD", {k: int(v) for k, v in st.counts.items()},
      int(sum(st.payload_bytes.values())))
# TP fwd (2-way: pair heads = 2)
fn = jax.jit(tp_evoformer_stack(mesh2, cfg, remat=False))
txt = fn.lower(params, msa, pair, *masks).compile().as_text()
st = analysis.parse_collectives(txt, 2)
print("TP_FWD", {k: int(v) for k, v in st.counts.items()},
      int(sum(st.payload_bytes.values())))
"""


def analytic(n_r, n_s, h_m=256, h_z=128, n_dev=4, bf=2):
    """Paper Table III volumes (forward), bytes per device."""
    msa = n_s * n_r * h_m * bf
    pair = n_r * n_r * h_z * bf
    # TP: 6 AllReduce of full activations (ring: 2x payload)
    tp = 6 * 2 * (4 * msa + 2 * pair) / 6  # avg of msa/pair module payloads
    tp = 2 * (3 * msa + 3 * pair)          # 3 msa-sized + 3 pair-sized
    # DAP: 2 msa a2a (1/N of local shard moves) + 3 pair a2a + gathers
    a2a = (2 * msa + 3 * pair) / n_dev * (n_dev - 1) / n_dev
    gathers = (pair / h_z * 8          # msa-row bias (H_m heads -> 8)
               + n_s * n_r * 32 * bf   # OPM right proj (c=32)
               + 2 * n_r * n_r * 128 * bf  # tri-mult right (c=128)
               + 2 * pair / h_z * 4)   # 2 tri-attn biases (H_z heads -> 4)
    dap = a2a + gathers * (n_dev - 1) / n_dev
    return tp, dap


def run():
    for name, (n_r, n_s) in (("initial", (256, 128)), ("finetune", (384, 512))):
        tp, dap = analytic(n_r, n_s)
        csv_row(f"commvol_{name}_TP_fwd_bytes", tp,
                f"analytic per-device, paper: 12xAllReduce/blk (6 fwd)")
        csv_row(f"commvol_{name}_DAP_fwd_bytes", dap,
                f"analytic per-device, ratio TP/DAP={tp / dap:.2f}x")

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # a CPU-device model, never the TPU
    out = subprocess.run([sys.executable, "-c", MEASURE], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        csv_row("commvol_measured", 0, "FAILED: " + out.stderr[-200:])
        return
    for line in out.stdout.strip().splitlines():
        tag, rest = line.split(" ", 1)
        csv_row(f"commvol_measured_{tag}", 0, rest.replace(",", ";"))


if __name__ == "__main__":
    run()
