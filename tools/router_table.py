"""How a seeded router spreads its picks, layer by layer, at the published
widths of ``sd15_mellum2_expand`` on the CPU (no chip; ten minutes and
20 GB): under the harness's token table (variance 1/features,
``components/unet_clip_vae_lm.py``) and under the cell's (variance 1,
``components/unet_clip_vae_lm_table.py``), the share of a layer's picks
that its 8 busiest of 64 experts take, the distinct experts four late
tokens choose (even routing: 26.5 of 32 picks) and the experts that got
any pick. The table in that components file and in PERF.md section 6
(PR 45) is this script's output.

    python3 tools/router_table.py      (from a scratch directory)
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from benchmarks.harness import files, weights       # noqa: E402
from stable_diffusion_webui_distributed_tpu.models import lm  # noqa: E402

POSITIONS = 1280
bench = files.Bench(REPO)
config = bench.config("sd15_mellum2_expand")
family = files.resolve_family(config)
ref = bench.reference(config)
cfg = family.expander
rows_out = {}
for name in ("unet_clip_vae_lm", "unet_clip_vae_lm_table"):
    components = bench.load("components", name)
    module, args = components.component_inits(family)["expander"]
    params = weights.fill(weights.param_shapes(module, args), jnp.bfloat16,
                          45, getattr(components, "leaf_rule", None))
    ids, _ = ref.inputs(family, 45, POSITIONS + 160)
    ids = ids[:POSITIONS]
    model = lm.DecoderLM(cfg, dtype=jnp.float32)
    cache = lm.empty_cache(cfg, POSITIONS, jnp.float32)
    _, _, routed = jax.jit(lambda p, t, c: model.apply(
        {"params": p}, t, jnp.int32(0), jnp.int32(POSITIONS), c))(
            params, ids, cache)
    chosen = np.asarray(routed[0])          # (layers, rows, k)
    table = []
    for layer in range(chosen.shape[0]):
        picks = np.bincount(chosen[layer].ravel(), minlength=cfg.num_experts)
        busiest = np.sort(picks)[::-1][:8].sum() / picks.sum()
        late = chosen[layer, -256:].reshape(64, -1)     # four tokens a row
        distinct = np.mean([len(set(r)) for r in late])
        table.append([layer, round(100 * float(busiest), 1),
                      round(float(distinct), 2), int((picks > 0).sum())])
        print(name, table[-1], flush=True)
    rows_out[name] = table
    del params
os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
with open(os.path.join(REPO, "chiprun_out", "router_table.json"), "w") as fh:
    json.dump(rows_out, fh, indent=1)
