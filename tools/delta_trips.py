"""What one forked step of the gated delta rule costs: XLA's element-wise
form against the Pallas kernel (PERF.md section 6, PR 62).

    chiprun -- python3 tools/delta_trips.py [--steps 256] [--layers 4]
        [--shapes 4x64x128x128 ...] [--blocks 8 16 32]

``--layers`` states of one ``(B, H, K, V)`` shape (a model's delta layers:
together they are over what stays on chip between steps) are stepped
``--steps`` times in a device-side scan, each step on the states the step
before left and on keys, queries and values of its own, once by
``ops/delta_rule.py:recurrent_step_each`` and once by
``ops/delta_kernel.py`` (at each of ``--blocks`` heads a grid step where
given, else at ``delta_kernel.head_block``'s). A row says microseconds a
call (the scan's time over ``layers * steps``), the GB/s that is for one
read and one write of a state, and the largest difference of the states
and of the read-outs from the element-wise form's after the first step and
after the last. Written to ``chiprun_out/delta_trips.json``; a CPU is
refused: a time comes from the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "delta_trips.json")
REPEATS = 5


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=256)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--shapes", nargs="+", default=["4x64x128x128"])
    parser.add_argument("--blocks", type=int, nargs="*", default=[])
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from stable_diffusion_webui_distributed_tpu.ops import (
        delta_kernel, delta_rule,
    )

    if jax.default_backend() != "tpu":
        print("delta_trips.py times the chip: no TPU here", file=sys.stderr)
        return 1

    def operands(shape):
        b, h, k_dim, v_dim = shape
        keys = jax.random.split(jax.random.key(args.seed), 6)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

        steps = (args.steps, args.layers, b, h)
        state = jax.random.normal(keys[0], (args.layers,) + shape)
        rows = (unit(jax.random.normal(keys[1], steps + (k_dim,)))
                * k_dim ** -0.5,
                unit(jax.random.normal(keys[2], steps + (k_dim,))),
                jax.random.normal(keys[3], steps + (v_dim,)),
                -0.05 * jax.random.uniform(keys[4], steps),
                2.0 * jax.nn.sigmoid(jax.random.normal(keys[5], steps)))
        return state, rows

    def scan_of(step, steps):
        def run(state, rows):
            def one(state, row):
                outs, after = zip(*(step(state[i], *(x[i] for x in row))
                                    for i in range(args.layers)))
                return jnp.stack(after), jnp.stack(outs)

            state, outs = jax.lax.scan(
                one, state, jax.tree_util.tree_map(lambda x: x[:steps],
                                                   rows))
            return state, outs[-1]
        return jax.jit(run, donate_argnums=(0,))

    def timed(step, state, rows):
        one, many = scan_of(step, 1), scan_of(step, args.steps)
        first = jax.block_until_ready(one(state + 0.0, rows))
        began = time.perf_counter()
        last = jax.block_until_ready(many(state + 0.0, rows))
        compile_s = time.perf_counter() - began
        times = []
        for _ in range(REPEATS):
            fresh = jax.block_until_ready(state + 0.0)
            began = time.perf_counter()
            jax.block_until_ready(many(fresh, rows))
            times.append(time.perf_counter() - began)
        return first, last, times, compile_s

    report_rows = []
    for text in args.shapes:
        shape = tuple(int(n) for n in text.split("x"))
        state, rows = operands(shape)
        calls = args.layers * args.steps
        moved = 2 * 4 * state[0].size
        forms = [("elementwise", None, delta_rule.recurrent_step_each)]
        chosen = delta_kernel.head_block
        for block in args.blocks or [chosen(*shape[1:])]:
            if block is not None:
                forms.append(("kernel", block,
                              delta_kernel.recurrent_step_each))
        want = None
        for form, block, step in forms:
            if block is not None:
                delta_kernel.head_block = lambda *a, block=block: block
            try:
                first, last, times, compile_s = timed(step, state, rows)
            finally:
                delta_kernel.head_block = chosen
            if want is None:
                want = (first, last)
            us = 1e6 * statistics.median(times) / calls
            diffs = [float(jnp.max(jnp.abs(a - b)))
                     for got, ref in zip((first, last), want)
                     for a, b in zip(got, ref)]
            report_rows.append({
                "shape": text, "form": form, "heads_a_grid_step": block,
                "steps": args.steps, "layers": args.layers,
                "us_a_call": us, "gb_per_s": moved / us / 1e3,
                "us_a_call_runs": [1e6 * t / calls for t in times],
                "first_run_s": compile_s,
                "max_diff_first_step": {"state": diffs[0], "o": diffs[1]},
                "max_diff_last_step": {"state": diffs[2], "o": diffs[3]},
                "max_abs_last_state": float(jnp.max(jnp.abs(want[1][0])))})
            print(json.dumps(report_rows[-1]), flush=True)
    report = {"device": jax.devices()[0].device_kind, "rows": report_rows}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as out:
        json.dump(report, out, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
