"""How many launches a decoded token costs, by class, without the chip.

    python3 tools/decode_hlo.py --expander sd15_xing4_expander \
        --classes xing4_decode [--layers 4]

Compiles the prompt expander's decode chunk (models/lm.py
``decode_chunk_fn``, bf16 shapes, a 1 024-slot cache) for a described v5e,
as tests/test_chip_compile.py does, and counts the fusions and custom
calls of the optimised HLO by the classes of a ``benchmarks/op_classes``
file: every one of them is a launch of its own in every step of the scan,
0.3 us or more on a v5e however little it computes. Where a step is bound
by launches and not by bytes (the residual streams' mixers: PERF.md
section 6, PR 35) the count moves before any chip time is spent. A loop
nested in the step is counted once whatever its trips. Since PR 36 a mixer
of a decode step is 2 launches, its two kernels (``hc`` 80 of a step's 927
at 20 layers); in XLA's form it was 22 outside Sinkhorn's loop and 4 an
iteration inside, about a hundred. ``expert_a_layer`` spreads the
``expert`` class over the layers its scopes name (``/layers_N/``), in layer
order: since PR 68 an expert layer of a decode step is 3 to 5 (the router's
product, the routing kernel, the expert kernel, and what else rides in the
class: a shared expert's gate's multiply, the identity experts' scaling),
where XLA's chain of ``top_k``, sorts and gathers made it 19 or 20. The
scope of an op the compiler hoisted
out of the loop still says ``while/body``: where that matters read the
``--keep`` text by computation. It says nothing about a time.
``--layers`` cuts the depth for a faster answer; ``--keep`` writes the HLO
text there. Run it from a scratch directory.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def count_launches(text: str, rules: list, classify) -> tuple[dict, list]:
    """(launches of a step by class, the ``expert`` class's by layer in
    layer order) of the optimised HLO ``text``: the fusions and custom
    calls whose scope lies in the scan's body."""
    launches = collections.Counter()
    by_layer = collections.Counter()
    for line in text.splitlines():
        kind = re.search(r" (fusion|custom-call)\(", line)
        scope = re.search(r'op_name="([^"]*)"', line)
        if not kind or not scope or "/while/body/" not in scope.group(1):
            continue
        name = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = ", line)
        row = {"scope": scope.group(1), "category": "",
               "name": name.group(1) if name else ""}
        took = classify(row, rules)
        launches[took] += 1
        layer = re.search(r"/layers_(\d+)/", row["scope"])
        if took == "expert" and layer:
            by_layer[int(layer.group(1))] += 1
    return dict(sorted(launches.items())), [
        n for _, n in sorted(by_layer.items())]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--expander", default="sd15_xing4_expander",
                        help="a factory of models/configs.py")
    parser.add_argument("--classes", default="xing4_decode",
                        help="a file of benchmarks/op_classes")
    parser.add_argument("--layers", type=int, default=0)
    parser.add_argument("--keep", default="")
    args = parser.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import files
    from stable_diffusion_webui_distributed_tpu.models import configs, lm

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    # the program asks the backend which products and forms to take
    jax.default_backend = lambda: "tpu"
    cfg = getattr(configs, args.expander)().expander
    if args.layers:
        cfg = dataclasses.replace(
            cfg, layer_types=cfg.layer_types[:args.layers],
            num_heads_per_layer=cfg.num_heads_per_layer[:args.layers])
    module = lm.DecoderLM(cfg, dtype=jnp.bfloat16)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def cache(capacity, place):
        return {name: [place(shape, lm.buffer_dtype(name, jnp.bfloat16))
                       for shape in rows]
                for name, rows in lm.cache_shapes(cfg, capacity).items()}

    scalar = on_chip((), jnp.int32)
    shapes = jax.eval_shape(
        lambda *a: module.init(jax.random.key(0), *a),
        jax.ShapeDtypeStruct((4,), jnp.int32), scalar, scalar,
        cache(8, jax.ShapeDtypeStruct))["params"]
    params = jax.tree_util.tree_map(
        lambda x: on_chip(x.shape, jnp.bfloat16), shapes)
    text = jax.jit(lm.decode_chunk_fn(module, 32), donate_argnums=(1,)).lower(
        params, cache(1024, on_chip), scalar, scalar,
        on_chip((), jax.random.key(0).dtype),
        on_chip((), jnp.float32)).compile().as_text()
    if args.keep:
        with open(args.keep, "w") as out:
            out.write(text)

    bench = files.Bench(REPO)
    rules = bench.read("op_classes", args.classes + ".json")["classes"]
    classify = bench.load("readers", "op_class_ms").classify
    launches, by_layer = count_launches(text, rules, classify)
    print(json.dumps({
        "expander": args.expander, "layers": cfg.num_layers,
        "launches_a_step": launches, "expert_a_layer": by_layer,
        "all": sum(launches.values())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
