"""What one expert layer's routing costs a decode step: XLA's chain against
the routing kernel (PERF.md section 6, PR 68).

    chiprun -- python3 tools/route_trips.py [--steps 500]

Each of the eight published routers at the rows its cell decodes (one
token, or four sequences a step) runs ``--steps`` times in a device-side
scan under the profiler, once as ``ops/moe.py:route`` with the chain behind
it (``_chosen`` or ``_block`` up to the expert kernel's door, then
``load_counts`` and the identity experts' two) and once as
``ops/route_kernel.py:routing``. A row says the microseconds a step of the
form's own device ops (the trace's per-op table; the scan's bookkeeping and
the sums that feed a step's result into the next left out), how many
launches those are, and whether the two forms agreed on five seeds (the
last with logits of whole numbers: exact ties): the picks and the expert
kernel's operands EQUAL, the weights to 1e-6. Neither form has a Linear's
copy in flight here: in the decode scan part of the chain hid under one
(PERF.md section 6, PR 68). Written to ``chiprun_out/route_trips.json``; a
CPU is refused: a time comes from the chip. Exit code 1 where a pick
differed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "route_trips.json")
#: (factory of models/configs.py, rows of a decode step in its cell)
ROUTERS = [("sd15_laguna_expander", 1), ("sd15_qwen3next_expander", 1),
           ("sd15_xing4_expander", 1), ("sd15_lfm2_expander", 1),
           ("sd15_mellum2_expander", 4), ("sd15_kanana2_expander", 4),
           ("sd15_gigachat35_expander", 4),
           ("sd15_longcat_flash_expander", 4)]
#: ops of the timing harness, not of a form
HARNESS = ("reduce_sum", "broadcast_add_fusion", "add_reduce", "while")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=500)
    args = parser.parse_args()

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import trace_reduce
    from stable_diffusion_webui_distributed_tpu.models import configs
    from stable_diffusion_webui_distributed_tpu.ops import (
        moe, moe_kernel, route_kernel,
    )

    if jax.default_backend() != "tpu":
        print("route_trips.py times the chip: no TPU here", file=sys.stderr)
        return 1

    def forms(cfg, rows, bias, valid):
        first, count = cfg.experts
        router = dict(renormalise=cfg.norm_topk_prob,
                      scale=cfg.routed_scaling_factor,
                      scoring=cfg.router_scoring, eps=cfg.norm_topk_eps)
        k = cfg.num_experts_per_tok

        def kernel(logits):
            return tuple(part for part in route_kernel.routing(
                logits, bias, valid, k=k, first=first, count=count,
                zero_experts=cfg.zero_experts, **router)
                if part is not None)

        def chain(logits):
            routing = moe.route(
                logits, k, bias=None if bias is None
                else bias.astype(jnp.float32), **router)
            door = {}
            with mock.patch.object(
                    moe_kernel, "chosen_experts",
                    lambda x, ids, weights, held, *a, **kw: door.update(
                        ids=ids, weights=weights, held=held) or x):
                x = jnp.zeros((rows, 8), jnp.float32)
                kernels = (jnp.zeros((count, 8, 8)),) * 3
                if rows == 1:
                    moe._chosen(x, routing, *kernels, first, kernel=True)
                else:
                    moe._block(x, routing, *kernels, first)
            out = (door["ids"], door["weights"],
                   jnp.reshape(door["held"], (1,)).astype(jnp.int32),
                   routing.experts) + moe.load_counts(routing, first, count,
                                                      valid)
            if cfg.zero_experts:
                out += (moe.identity_part(jnp.ones((rows, 1)), routing,
                                          cfg.real_experts),
                        moe.identity_picks(routing, cfg.real_experts, valid))
            return out

        return {"xla": chain, "kernel": kernel}

    def differ(got, want):
        """Why the kernel's step is not the chain's; "" where it is."""
        held = int(want[2][0])
        try:
            assert int(got[2][0]) == held, "held"
            np.testing.assert_array_equal(got[3], want[3], "picks")
            np.testing.assert_array_equal(got[0][:held], want[0][:held],
                                          "slots")
            np.testing.assert_allclose(got[1], want[1], rtol=1e-6, atol=0)
            for ours, theirs in zip(got[4:], want[4:]):
                if ours.dtype == jnp.float32:
                    np.testing.assert_allclose(ours, theirs, rtol=1e-6)
                else:
                    np.testing.assert_array_equal(ours, theirs)
        except AssertionError as error:
            return str(error)[:300]
        return ""

    def profiled(form, logits):
        def run(logits):
            def step(carry, _):
                # each step routes what the one before left: nothing hoists
                return sum(jnp.sum(part).astype(jnp.float32)
                           for part in form(logits + carry)) * 1e-12, None
            return jax.lax.scan(step, jnp.float32(0), None,
                                length=args.steps)[0]

        run = jax.jit(run)
        run(logits).block_until_ready()
        folder = tempfile.mkdtemp()
        with jax.profiler.trace(folder):
            run(logits).block_until_ready()
        table = trace_reduce.reduce(
            trace_reduce.find_xplane(folder))["op_table"]
        own = [row for row in table if row["calls"] >= args.steps
               and not row["name"].startswith(HARNESS)]
        return (1e6 * sum(row["seconds"] for row in own) / args.steps,
                sum(row["calls"] for row in own) // args.steps)

    rows_out = []
    for factory, rows in ROUTERS:
        cfg = getattr(configs, factory)().expander
        bias = (0.1 * jax.random.normal(
            jax.random.key(9), (cfg.num_experts,))).astype(
                jnp.bfloat16) if cfg.router_bias else None
        valid = jnp.arange(rows) < max(1, rows - 1)
        both = forms(cfg, rows, bias, valid)
        differed = []
        for seed in range(5):
            logits = 2.0 * jax.random.normal(
                jax.random.key(seed), (rows, cfg.num_experts), jnp.float32)
            if seed == 4:
                logits = jnp.round(logits)
            why = differ(jax.jit(both["kernel"])(logits),
                         jax.jit(both["xla"])(logits))
            if why:
                differed.append([seed, why])
        row = {"router": factory, "rows": rows, "experts": cfg.num_experts,
               "k": cfg.num_experts_per_tok, "differed": differed}
        logits = jax.random.normal(jax.random.key(1),
                                   (rows, cfg.num_experts), jnp.float32)
        for name, form in both.items():
            row[name + "_us_a_step"], row[name + "_launches"] = profiled(
                form, logits)
        print(json.dumps(row), flush=True)
        rows_out.append(row)
    report = {"device": jax.devices()[0].device_kind, "steps": args.steps,
              "rows": rows_out}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as out:
        json.dump(report, out, indent=1)
    return 1 if any(row["differed"] for row in rows_out) else 0


if __name__ == "__main__":
    sys.exit(main())
