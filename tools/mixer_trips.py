"""What one residual-stream mixer costs a decode step: XLA's form against
the two Pallas kernels (PERF.md section 6, PR 36).

    chiprun -- python3 tools/mixer_trips.py [--steps 512]

A layer's two mixers (``models/lm.py:StreamMixer`` and ``written`` around
a sublayer that hands its normed input on) at the published shape (four streams of
3 584, ``phi`` 14 336 x 24 stored bf16, twenty Sinkhorn iterations) run
``--steps`` times in a device-side scan, each step on the streams the step
before left, once in the form ``ops/stream_mixer.py:choose`` gives a decode
step on the chip (one kernel before the sublayer, one after) and once with
the choice held to the XLA form. A row says microseconds a mixer (the
scan's time over ``2 * steps``) and the largest difference of the streams
from the XLA form's after the first step and after the last. Written to
``chiprun_out/mixer_trips.json``; a CPU is refused: a time comes from the
chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "mixer_trips.json")
REPEATS = 5


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=512)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    sys.path.insert(0, REPO)
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.models import configs, lm
    from stable_diffusion_webui_distributed_tpu.ops import stream_mixer

    if jax.default_backend() != "tpu":
        print("mixer_trips.py times the chip: no TPU here", file=sys.stderr)
        return 1
    cfg = configs.sd15_xing4_expander().expander
    n, hidden = cfg.residual_streams, cfg.hidden_size

    class TwoMixers(nn.Module):
        @nn.compact
        def __call__(self, x):
            for hc in ("attn_hc", "mlp_hc"):
                mixed = lm.StreamMixer(cfg, name=hc)(x)
                # the sublayer: its input at unit scale, so nothing grows
                x = lm.written(x, mixed, mixed.read * jax.lax.rsqrt(
                    jnp.mean(jnp.square(mixed.read)) + 1e-6))
            return x

    module = TwoMixers()
    start = jax.random.normal(jax.random.key(args.seed), (1, n, hidden))
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16),
        module.init(jax.random.key(args.seed + 1),
                    jnp.zeros((4, n, hidden)))["params"])

    def scan_of(steps):
        def run(params, x):
            variables = {"params": params,
                         "mixers": lm.mixer_operands(params)}
            return jax.lax.scan(
                lambda x, _: (module.apply(variables, x), None), x, None,
                length=steps)[0]
        return jax.jit(run)

    chosen = stream_mixer.choose
    rows, results = [], {}
    for form in (stream_mixer.LOOP, stream_mixer.KERNEL):
        if form == stream_mixer.LOOP:   # hold the choice to XLA's form
            stream_mixer.choose = lambda *a, **k: stream_mixer.LOOP
        try:
            one, many = scan_of(1), scan_of(args.steps)
            results[form] = (jax.block_until_ready(one(params, start)),
                             jax.block_until_ready(many(params, start)))
        finally:
            stream_mixer.choose = chosen
        times = []
        for _ in range(REPEATS):
            began = time.perf_counter()
            jax.block_until_ready(many(params, start))
            times.append(time.perf_counter() - began)
        rows.append({"form": form, "steps": args.steps,
                     "us_a_mixer": 1e6 * statistics.median(times)
                     / (2 * args.steps),
                     "us_a_mixer_runs": [1e6 * t / (2 * args.steps)
                                         for t in times]})
    for row, (first, last) in zip(rows, results.values()):
        want_first, want_last = results[stream_mixer.LOOP]
        row["max_diff_first_step"] = float(jnp.max(jnp.abs(
            first - want_first)))
        row["max_diff_last_step"] = float(jnp.max(jnp.abs(last - want_last)))
        row["max_abs_last_step"] = float(jnp.max(jnp.abs(want_last)))
    report = {"device": jax.devices()[0].device_kind, "streams": n,
              "hidden": hidden, "iters": cfg.sinkhorn_iters, "rows": rows}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as out:
        json.dump(report, out, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
