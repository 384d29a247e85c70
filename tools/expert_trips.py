"""What one expert layer's call costs a decode step: XLA's loops against the
expert kernel, its ring of reads (PERF.md section 6, PR 34, PR 46, PR 70).

    chiprun -- python3 tools/expert_trips.py [--rows 1 4 8]
        [--budgets 24] [--buffers 2] [--parent .parent]
        [--shapes xing4 ...]

For each published expert shape (Laguna-S-2.1's 3072 x 1024 and
Qwen3-Next's 2048 x 512, 128 of ``num_experts`` held, 10 a token; Xing4.0's
3584 x 1024, 16 of 64 held, 4 a token; LFM2's 2048 x 1536, all 64 held, 4 a
token; Mellum2's 2304 x 896, all 64 held, 8 a token; kanana-2's 2048 x 768,
all 128 held, 6 a token; GigaChat3.5's 7168 x 2048, 16 of 256 held, 8 a
token; LongCat-Flash's 6144 x 2048, 16 held of 512 and 256 identity experts
behind them, 12 a token; granite-4.0-h-small's 4096 x 768, 36 of 72 held,
10 a token; bf16) and each ``--rows`` (the sequences a step carries) one
expert layer's routed sum runs ``--steps`` times in a device-side scan, each
step on its own seeded draw of every row's experts among the layer's (the
rows draw independently, so a step holds nothing at the cells' own rates)
and fed the step before's result: once through ``ops/moe.py``'s XLA product
(the loop over a token's chosen experts at one row, the grouped product at
several), once through the kernel of the checkout at ``--parent`` (a copy
of another commit: its ``ops/moe_kernel.py`` alone is loaded) where that
is given, and once through ``ops/moe_kernel.py`` at each combination of
``--budgets`` (MiB of VMEM for the ring) and ``--buffers`` (its slots):
the blocks follow from the two (``moe_kernel.ring``). A row says microseconds a call (a
step), how far that is over the call's bytes at 784 GB/s (the kernel's own
rate on the margin between two expert sizes, PR 34), the share of steps in
which nothing chosen was held, microseconds a DISTINCT chosen-and-held
expert (a trip), GB/s over the bytes those experts' kernels hold, and the
kernel's largest difference from XLA's product over the steps' results.
Written to ``chiprun_out/expert_trips.json``; a CPU is refused: a time
comes from the chip.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "expert_trips.json")
#: (name, d, f, experts of the layer, held here, chosen a token)
SHAPES = (("laguna", 3072, 1024, 256, 128, 10),
          ("qwen3next", 2048, 512, 512, 128, 10),
          ("xing4", 3584, 1024, 64, 16, 4),
          ("lfm2", 2048, 1536, 64, 64, 4),
          ("mellum2", 2304, 896, 64, 64, 8),
          ("kanana2", 2048, 768, 128, 128, 6),
          ("gigachat35", 7168, 2048, 256, 16, 8),
          ("longcat_flash", 6144, 2048, 768, 16, 12),
          ("granite_h", 4096, 768, 72, 36, 10))
#: bytes a second the kernel streams on the margin between two expert
#: sizes (PERF.md section 5, PR 34): what a call's bytes are held against
MARGINAL_GB_S = 784.0
REPEATS = 5


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=256)
    parser.add_argument("--budgets", type=int, nargs="*", default=[24])
    parser.add_argument("--buffers", type=int, nargs="*", default=[2])
    parser.add_argument("--rows", type=int, nargs="*", default=[1])
    parser.add_argument("--shapes", nargs="*",
                        default=[shape[0] for shape in SHAPES])
    parser.add_argument("--parent", default="",
                        help="a checkout whose kernel is timed beside")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from stable_diffusion_webui_distributed_tpu.ops import moe, moe_kernel

    if jax.default_backend() != "tpu":
        print("expert_trips.py times the chip: no TPU here", file=sys.stderr)
        return 1
    parent = None
    if args.parent:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "parent_moe_kernel", os.path.join(
                args.parent, "stable_diffusion_webui_distributed_tpu",
                "ops", "moe_kernel.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    def layer(name, d, f, experts, held, k, tokens):
        keys = jax.random.split(jax.random.key(args.seed), 5)

        def kernel(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.bfloat16)
                    * fan_in ** -0.5)

        w = (kernel(keys[0], (held, d, f), d),
             kernel(keys[1], (held, d, f), d),
             kernel(keys[2], (held, f, d), f))
        x0 = jax.random.normal(keys[3], (tokens, d), jnp.bfloat16)
        logits = jax.random.normal(keys[4], (args.steps * tokens, experts))
        routing = moe.Routing(*(
            a.reshape(args.steps, tokens, k)
            for a in moe.route(logits, k, renormalise=True, scale=1.0)))
        chosen = np.asarray(routing.experts)
        trips = [len(set(step[step < held])) for step in chosen]
        held_trips = sum(trips)
        none_held = trips.count(0) / len(trips)

        def product(use_kernel, x, route, w):
            if tokens == 1:
                return moe._chosen(x, route, *w, 0, kernel=use_kernel)
            if use_kernel:
                return moe._block(x, route, *w, 0)
            return moe._grouped(x, route, *w, 0, experts)

        def run(use_kernel):
            def steps(x, w, routing):
                def step(x, route):
                    out = product(use_kernel, x, moe.Routing(*route), w)
                    return (0.9 * x + 0.1 * out).astype(x.dtype), out

                return jax.lax.scan(step, x, tuple(routing))[1]

            return jax.jit(steps)

        def time_it(fn):
            result = jax.block_until_ready(fn(x0, w, routing))
            seconds = []
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x0, w, routing))
                seconds.append(time.perf_counter() - t0)
            return result, statistics.median(seconds)

        expert_bytes = 3 * d * f * 2
        rows = []
        want, seconds = time_it(run(False))

        def row(path, seconds, **more):
            us = 1e6 * seconds / args.steps
            return dict(
                shape=name, rows=tokens, path=path, steps=args.steps,
                held_trips=held_trips, none_held_share=none_held,
                us_a_step=us,
                us_over_its_bytes=us - held_trips * expert_bytes
                / args.steps / (1e3 * MARGINAL_GB_S),
                us_a_trip=1e6 * seconds / held_trips,
                gb_s=held_trips * expert_bytes / seconds / 1e9, **more)

        def kernel_row(path, **more):
            got, seconds = time_it(run(True))
            return row(
                path, seconds,
                max_abs_diff=float(jnp.max(jnp.abs(got - want))),
                max_abs=float(jnp.max(jnp.abs(want))), **more)

        rows.append(row("loop" if tokens == 1 else "grouped", seconds))
        if parent is not None and parent.f_tile(d, f, 2) is not None:
            moe.moe_kernel = parent
            rows.append(kernel_row("parent_kernel",
                                   f_tile=parent.f_tile(d, f, 2)))
            moe.moe_kernel = moe_kernel
        for budget, buffers in itertools.product(args.budgets,
                                                 args.buffers):
            moe_kernel._WEIGHT_VMEM = budget * 2 ** 20
            moe_kernel._RING_BUFFERS = buffers
            blocks = moe_kernel.ring(d, f, 2)
            if blocks is None:
                continue
            rows.append(kernel_row(
                "kernel", budget_mib=budget,
                ring_mib=blocks.vmem_bytes(d, 2) / 2 ** 20,
                **blocks._asdict()))
        return rows

    rows = []
    for shape in SHAPES:
        if shape[0] not in args.shapes:
            continue
        for tokens in args.rows:
            made = layer(*shape, tokens)
            for r in made:
                print(json.dumps(r), flush=True)
            rows += made
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as out:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows},
                  out, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
