"""Diagnostic: compile one (arch, shape [, overrides]) and dump the top
byte/flop-contributing HLO ops with their loop scales — the 'profile' the
§Perf hypothesis loop reads (there is no wall-clock profiler for the TPU
target on this host; the lowered IR is the evidence).

  PYTHONPATH=src python -m benchmarks.diag_hlo --arch deepseek-v2-236b \
      --shape train_4k --top 25 [--set attn_q_block=0]
"""
import argparse
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

INNER = r"""
# dryrun sets the 512-device XLA flag (via exec/envcompat) before jax init;
# the materialized-path HLO comes from a use_plan("oracle") scope, not env.
import re, jax, dataclasses
from repro.launch import dryrun
from repro.exec.plan import preset, use_plan
from repro.roofline import analysis as A

arch, shape_name, top_n = {arch!r}, {shape!r}, {top}
overrides = {overrides!r}
mesh = dryrun.make_production_mesh()
if arch.startswith("alphafold"):
    fn, args, in_sh, out_sh = dryrun.build_alphafold(arch.split("-")[1], mesh,
                                                     evo_overrides=overrides)
    kind = "train"
else:
    cfg = dryrun.get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = dryrun.INPUT_SHAPES[shape_name]
    kind = shape.kind
    fn, args, in_sh, out_sh = dryrun.BUILDERS[kind](cfg, shape, mesh)
with jax.set_mesh(mesh), use_plan(preset("oracle")):
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
txt = compiled.as_text()
comps = A._split_computations(txt)
scales = A._execution_scales(comps)
fused = set()
for lines in comps.values():
    for ln in lines:
        if " fusion(" in ln:
            for m in re.finditer(r"calls=%?([\w.\-]+)", ln):
                fused.add(m.group(1))
fe = {{n: A._fusion_param_effective(comps[n]) for n in fused if n in comps}}
fo = {{n: A._fusion_root_out_bytes(comps[n]) for n in fused if n in comps}}
rows_b, rows_f = [], []
for name, lines in comps.items():
    sc = max(scales.get(name, 1.0), 1.0)
    st = A._symbols(lines)
    isfused = name in fused or name.startswith("fused")
    for ln in lines:
        if " dot(" in ln:
            f = A._dot_flops(ln, st) * sc
            if f > 0:
                rows_f.append((f, sc, name, ln.strip()[:110]))
        if isfused or any(op in ln for op in A._SKIP_BYTE_OPS) or "=" not in ln:
            continue
        b = A._op_bytes(ln, st, fe, fo) * sc
        if b > 0:
            rows_b.append((b, sc, name, ln.strip()[:110]))
print("==== TOP BYTES ====")
for b, sc, name, ln in sorted(rows_b, reverse=True)[:top_n]:
    print(f"{{b/2**30:9.1f}}GB x{{sc:7.0f}} {{name[:30]:30s}} {{ln}}")
print("==== TOP FLOPS ====")
for f, sc, name, ln in sorted(rows_f, reverse=True)[:top_n]:
    print(f"{{f/1e12:9.2f}}TF x{{sc:7.0f}} {{name[:30]:30s}} {{ln}}")
print("==== COLLECTIVE PAYLOADS ====")
st = A.parse_collectives(txt, mesh.shape["model"])
for k, v in sorted(st.payload_bytes.items(), key=lambda kv: -kv[1]):
    print(f"{{v/2**30:9.1f}}GB payload {{k}} (count {{st.counts[k]}})")
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (int/bool)")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=")
        overrides[k] = (v == "True") if v in ("True", "False") else int(v)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # a CPU-device model, never the TPU
    out = subprocess.run(
        [sys.executable, "-c", INNER.format(arch=args.arch, shape=args.shape,
                                            top=args.top,
                                            overrides=overrides)],
        env=env, text=True, timeout=7200)
    sys.exit(out.returncode)


if __name__ == "__main__":
    main()
