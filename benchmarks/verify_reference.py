"""How close the program's UNet comes to the plain reference, on the chip,
at the configuration's published widths.

    python3 benchmarks/verify_reference.py --config sd15

One epsilon-prediction at batch 1 on a latent the reference can hold, from
the configuration's seeded weights as stored (bfloat16): the program's flax
module at its serving policy, the same module with dynamic int8 linears (the
precision the tolerance must reject), and the reference in float32. Not part
of a benchmark run: each side is one more UNet compile. The result goes to
benchmarks/reference/<config>.json by hand, with the device it came from.
Exit code 1 when the program misses the tolerance or int8 passes it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: relative RMS of the program's output against the reference's. bfloat16
#: (unit roundoff 3.9e-3) through these UNets' ~70 sequential blocks reads
#: 1.5e-2 on the chip; the same tree through dynamic int8 linears reads
#: 3.9e-2 to 4.7e-2 and must fail (benchmarks/reference/<config>.json)
TOLERANCE = 2.5e-2

def relative_rms(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def compare(family, policy, seed: int, latent: int) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from benchmarks.reference import unet_ref
    from stable_diffusion_webui_distributed_tpu.models.unet import UNet

    module, args = weights.component_inits(family)["unet"]
    params = weights.fill(weights.param_shapes(module, args),
                          policy.param_dtype, seed)
    cfg = family.unet
    keys = jax.random.split(jax.random.key(seed), 3)
    inputs = [jax.random.normal(keys[0], (1, latent, latent,
                                          cfg.in_channels), jnp.float32),
              jnp.full((1,), 500.0, jnp.float32),
              jax.random.normal(keys[1], (1, 77, cfg.cross_attention_dim),
                                jnp.float32)]
    if cfg.addition_embed_dim:
        inputs.append(jax.random.normal(
            keys[2], (1, cfg.projection_input_dim), jnp.float32))

    def program(**kw):
        unet = UNet(cfg, dtype=policy.compute_dtype,
                    attention_impl=policy.attention_impl, **kw)
        return jax.jit(lambda p, *a: unet.apply({"params": p}, *a))

    out = {}
    t0 = time.perf_counter()
    want = jax.jit(lambda p, *a: unet_ref.unet_forward(cfg, p, *a))(
        params, *inputs).block_until_ready()
    out["reference_seconds_with_compile"] = time.perf_counter() - t0
    got = program()(params, *inputs)
    int8 = program(quant_linears=True)(params, *inputs)
    out["program_vs_reference_relative_rms"] = relative_rms(got, want)
    out["int8_vs_reference_relative_rms"] = relative_rms(int8, want)
    out["reference_rms"] = float(jnp.sqrt(jnp.mean(want ** 2)))
    out["finite"] = bool(jnp.isfinite(got).all() & jnp.isfinite(want).all())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--latent", type=int, default=0,
                    help="latent edge (default: the file's, else 32)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax

    from benchmarks.harness import device, files

    bench = files.Bench(ROOT)
    config = bench.config(args.config)
    family = files.resolve_family(config)
    policy = files.resolve_policy(config)
    latent = args.latent or int(config.get("reference_latent", 32))
    with jax.default_matmul_precision("highest"):
        result = compare(family, policy, int(config["weight_seed"]), latent)
    passed = (result["finite"]
              and result["program_vs_reference_relative_rms"] < TOLERANCE
              < result["int8_vs_reference_relative_rms"])
    result.update(config=args.config, latent=latent, batch=1,
                  tolerance_relative_rms=TOLERANCE, passed=passed,
                  device=device.record())
    print(json.dumps(result), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
