"""Quickstart: the public API in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py

1. Build a reduced AlphaFold behind the FastFold facade — one object binding
   (AlphaFoldConfig, ExecutionPlan) — and run folding inference.
2. Run one DAP-style training step through the same facade.
3. Serve mixed-plan folding traffic (an oracle-leg canary request beside the
   production-leg request) from the one bound session.
4. Build an assigned LLM arch and generate tokens through the serving engine.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.alphafold import SMOKE
from repro.data import protein_batches
from repro.exec import ExecutionPlan, FastFold
from repro.launch.cache import enable_compilation_cache
from repro.models.decoder import init_model
from repro.serving.engine import ServingEngine
from repro.train.loop import make_train_step

enable_compilation_cache()

# --- 1. AlphaFold inference -------------------------------------------------
print("== AlphaFold (reduced) folding inference ==")
ff = FastFold(SMOKE, ExecutionPlan())       # config + execution policy, once
params = ff.init(jax.random.PRNGKey(0))
pb = next(protein_batches(batch=1, n_seq=8, n_res=16, seed=0))
batch = {k: jnp.asarray(getattr(pb, k)) for k in
         ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
          "pseudo_beta", "bert_mask", "true_msa")}
out = ff.forward(params, batch)             # recycling included
print("predicted CA coords:", out["coords"].shape,
      "distogram:", out["distogram_logits"].shape)

# --- 2. one training step ----------------------------------------------------
print("== one AlphaFold training step ==")
init_state, train_step = make_train_step(ff.loss_fn, base_lr=1e-3)
state = init_state(params)
state, metrics = jax.jit(train_step)(state, batch, jax.random.PRNGKey(1))
print({k: round(float(v), 3) for k, v in metrics.items()})

# --- 3. mixed-plan folding serving -------------------------------------------
print("== mixed-plan folding requests (production + oracle canary) ==")
canary_plan = ff.plan.with_kernels(enabled=False)   # jnp-oracle leg
outs = ff.serve(params, [batch, batch], plans=[None, canary_plan])
drift = float(jnp.max(jnp.abs(outs[0]["coords"] - outs[1]["coords"])))
print(f"production vs oracle-canary coords drift: {drift:.2e}")

# --- 4. LLM serving (assigned architecture) ----------------------------------
print("== qwen2 (reduced) serving ==")
cfg = get_config("qwen2-1.5b", reduced_variant=True)
lm_params = init_model(jax.random.PRNGKey(0), cfg)
engine = ServingEngine(lm_params, cfg, n_slots=2, max_seq=64)
prompt = np.random.default_rng(0).integers(0, cfg.vocab, size=(8,))
req = engine.submit(prompt, max_new_tokens=8, temperature=0.8)
engine.run()
print("prompt:", prompt.tolist())
print("generated:", req.generated)
