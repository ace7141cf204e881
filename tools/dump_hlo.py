"""Dump the ResNet50-224 optimized train-step HLO + cost summary.

Run from the repo root: ``python -m tools.dump_hlo``.  Writes the HLO
text to /tmp/hlo_opt.txt and prints the backend cost rows.

The HLO comes through the ONE extraction path
(``tools/graftaudit/extract.py``): fit() populates the trace cache, and
the recorded train-step call is re-lowered via ``audit_lower`` — the
program production actually ran, with its declared donation, not a
hand-reconstructed ``.lower()`` with a fresh RNG key.
"""
import json

import jax.numpy as jnp

from deeplearning4j_tpu.models import available_bench_model
from tools.graftaudit.extract import iter_trace_cache_hlo

model, (x, y) = available_bench_model(batch=256, image=224)
x, y = jnp.asarray(x), jnp.asarray(y)
model.fit(x, y)                       # records the real train-step call
exs = list(iter_trace_cache_hlo(kinds=("train_step",)))
assert exs, "no train_step in the trace cache after fit()"
ex = exs[-1]
with open("/tmp/hlo_opt.txt", "w") as f:
    f.write(ex.hlo_text)
ca = ex.cost_analysis()
flops = ca.get("flops", 0)
print(json.dumps({k: v for k, v in ca.items()
                  if k in ("flops", "bytes accessed", "optimal_seconds",
                           "bytes accessed0{}", "bytes accessedout{}")},
                 indent=0))
print("flops/step TFLOP:", flops / 1e12)
