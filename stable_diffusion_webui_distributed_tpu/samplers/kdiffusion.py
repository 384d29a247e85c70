"""k-diffusion samplers as scan-step functions.

Design: a sampler is ``(carry, step_index) -> (carry, ())`` so the pipeline
can ``lax.scan`` any contiguous chunk of steps and check the interrupt flag
between chunks — reproducing the reference's 0.5 s interrupt poll
(/root/reference/scripts/spartan/worker.py:440-448) under XLA compilation.

Stochastic (ancestral) steps draw noise keyed per image *and* per step from
the image's own PRNG key, never from batch position — so a sub-batch sharded
to any device/slice reproduces the exact images of a single-device run (the
seed contract of runtime/rng.py; reference seed fan-out semantics at
/root/reference/scripts/distributed.py:297-305).

Sampler names mirror webui's (the reference's speed table rows,
worker.py:75-94): "Euler a", "Euler", "Heun", "DDIM", "DPM++ 2M",
"DPM++ 2M Karras", "DPM2", "DPM2 a", "LMS".
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from stable_diffusion_webui_distributed_tpu.runtime.kept import KeptTable
from stable_diffusion_webui_distributed_tpu.samplers import schedules as sched
from stable_diffusion_webui_distributed_tpu.serving.metrics import PLAN

# denoise_fn(x, sigma_scalar, step_index) -> denoised x0 prediction, same
# shape as x. ``step_index`` lets conditioners gate by progress fraction
# (ControlNet guidance_start/end) without re-deriving it from sigma.
DenoiseFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """A named sampler = step algorithm + sigma schedule + stochasticity."""

    algorithm: str           # euler | euler_a | heun | dpmpp_2m | dpm2 | dpm2_a | lms
    schedule: str = "default"  # key into schedules.SCHEDULES
    ancestral: bool = False
    # Extra model evaluations per step (Heun/DPM2 are 2nd order).
    evals_per_step: int = 1
    # Adaptive step sizing (DPM adaptive): the engine routes these through
    # the host-side PID loop (sample_dpm_adaptive) instead of the fixed
    # sigma-ladder scan; ``algorithm`` then names the fixed-grid FALLBACK
    # used by consumers without a host loop.
    adaptive: bool = False


SAMPLERS = {
    "Euler a": SamplerSpec("euler_a", ancestral=True),
    "Euler": SamplerSpec("euler"),
    "Heun": SamplerSpec("heun", evals_per_step=2),
    "DDIM": SamplerSpec("euler", schedule="ddim"),
    "LMS": SamplerSpec("lms"),
    "DPM2": SamplerSpec("dpm2", evals_per_step=2),
    "DPM2 a": SamplerSpec("dpm2_a", ancestral=True, evals_per_step=2),
    "DPM++ 2M": SamplerSpec("dpmpp_2m"),
    "DPM++ 2M Karras": SamplerSpec("dpmpp_2m", schedule="karras"),
    "DPM++ 2S a": SamplerSpec("dpmpp_2s_a", ancestral=True,
                              evals_per_step=2),
    "DPM++ 2S a Karras": SamplerSpec("dpmpp_2s_a", schedule="karras",
                                     ancestral=True, evals_per_step=2),
    "DPM++ SDE": SamplerSpec("dpmpp_sde", ancestral=True, evals_per_step=2),
    "DPM++ SDE Karras": SamplerSpec("dpmpp_sde", schedule="karras",
                                    ancestral=True, evals_per_step=2),
    "Euler a Karras": SamplerSpec("euler_a", schedule="karras", ancestral=True),
    "Euler Karras": SamplerSpec("euler", schedule="karras"),
    # PLMS (ldm's pseudo linear multistep): Adams-Bashforth on the eps
    # estimate over the DDIM leading-timestep grid, pseudo-improved-Euler
    # warmup (2 evals on the first step only).
    "PLMS": SamplerSpec("plms", schedule="ddim"),
    # DPM fast: 2nd-order DPM-Solver on the uniform log-sigma grid
    # k-diffusion's sample_dpm_fast walks. Multistep (history-based) so the
    # model-eval budget stays ~= the requested step count — DPM fast's
    # defining property (its NFE ~ n; a probe-based solver would double it).
    "DPM fast": SamplerSpec("dpm_fast", schedule="exponential"),
    # DPM adaptive: k-diffusion's PID-controlled adaptive-step DPM-Solver
    # (order 2/3 embedded pair; the step slider is ignored, like webui).
    # Data-dependent step counts can't live in a compiled fixed-shape scan,
    # so the engine runs it as a HOST loop over one compiled "attempt"
    # (sample_dpm_adaptive below): the solver math + error norm execute in
    # a single XLA call per attempt with sigma as data — one compile total
    # — and only the scalar error returns for the host PID decision. The
    # ``dpm_solver_3`` algorithm here is the fixed-grid fallback for
    # consumers without a host loop. Speed-table row (-61.4%, eta.py)
    # reflects the heavy NFE.
    "DPM adaptive": SamplerSpec("dpm_solver_3", schedule="exponential",
                                evals_per_step=3, adaptive=True),
}


def resolve_sampler(name: str) -> SamplerSpec:
    """Look up a webui sampler name; unknown names fall back to Euler a —
    the same degraded-capability fallback the reference applies on a remote's
    404 "Sampler not found" (worker.py:457-467)."""
    if name in SAMPLERS:
        return SAMPLERS[name]
    base = name.replace(" Karras", "")
    if base in SAMPLERS and "Karras" in name:
        return dataclasses.replace(SAMPLERS[base], schedule="karras")
    return SAMPLERS["Euler a"]


class Carry(NamedTuple):
    """Scan carry: latent + a 3-deep history of per-step estimates.

    ``old_denoised`` is the newest history entry (``denoised`` for
    DPM++ 2M-family, the eps estimate ``d`` for LMS/PLMS); ``hist2``/
    ``hist3`` are one/two steps older — only PLMS's order-4 multistep reads
    that deep. ``n_hist`` counts valid entries (0 at the first step)."""

    x: jax.Array
    old_denoised: jax.Array  # zeros until step 1
    have_old: jax.Array      # bool scalar
    hist2: jax.Array         # zeros until step 2
    hist3: jax.Array         # zeros until step 3
    n_hist: jax.Array        # int32 scalar


def _ancestral_split(sigma, sigma_next, eta: float = 1.0):
    """(sigma_down, sigma_up) for ancestral steps (k-diffusion formula)."""
    var_frac = (sigma**2 - sigma_next**2) / jnp.maximum(sigma**2, 1e-20)
    sigma_up = jnp.minimum(
        sigma_next, eta * jnp.sqrt(jnp.maximum(sigma_next**2 * var_frac, 0.0))
    )
    sigma_down = jnp.sqrt(jnp.maximum(sigma_next**2 - sigma_up**2, 0.0))
    return sigma_down, sigma_up


def _step_noise(keys: jax.Array, step: jax.Array, shape, dtype) -> jax.Array:
    """Per-image, per-step noise: fold the step index into each image key.

    ``keys`` is a (B,) key array (one key per image, derived from that
    image's seed); batch position never enters, so sharding is seed-exact.
    """
    def one(k):
        return jax.random.normal(jax.random.fold_in(k, step), shape[1:], dtype)

    return jax.vmap(one)(keys)


def make_sampler_step(
    spec: SamplerSpec,
    denoise_fn: DenoiseFn,
    sigmas: jax.Array,        # (steps+1,) f32
    image_keys: jax.Array,    # (B,) PRNG keys, one per image
) -> Callable[[Carry, jax.Array], Tuple[Carry, Tuple]]:
    """Build the scan-step function for ``spec`` over a fixed sigma ladder."""

    algo = spec.algorithm

    def to_d(x, sigma, denoised):
        return (x - denoised) / jnp.maximum(sigma, 1e-10)

    # Scanned by run_steps via lax.scan; that call site is in another
    # function, out of the analyzer's lexical reach, hence the marker.
    # sdtpu-lint: traced
    def step(carry: Carry, i: jax.Array) -> Tuple[Carry, Tuple]:
        x = carry.x
        sigma = sigmas[i]
        sigma_next = sigmas[i + 1]
        denoised = denoise_fn(x, sigma, i)
        d = to_d(x, sigma, denoised)

        if algo == "euler":
            x_new = x + d * (sigma_next - sigma)

        elif algo == "euler_a":
            sigma_down, sigma_up = _ancestral_split(sigma, sigma_next)
            x_new = x + d * (sigma_down - sigma)
            noise = _step_noise(image_keys, i, x.shape, x.dtype)
            x_new = x_new + noise * sigma_up

        elif algo == "heun":
            x_eul = x + d * (sigma_next - sigma)

            def second_order(_):
                denoised2 = denoise_fn(x_eul, jnp.maximum(sigma_next, 1e-10),
                                       i)
                d2 = to_d(x_eul, sigma_next, denoised2)
                return x + (d + d2) / 2 * (sigma_next - sigma)

            x_new = jax.lax.cond(sigma_next > 0, second_order,
                                 lambda _: x_eul, operand=None)

        elif algo in ("dpm2", "dpm2_a"):
            if algo == "dpm2_a":
                sigma_down, sigma_up = _ancestral_split(sigma, sigma_next)
            else:
                sigma_down, sigma_up = sigma_next, jnp.float32(0.0)

            def second_order(_):
                # midpoint in log-sigma space (k-diffusion sample_dpm_2)
                sigma_mid = jnp.exp(
                    (jnp.log(jnp.maximum(sigma, 1e-10))
                     + jnp.log(jnp.maximum(sigma_down, 1e-10))) / 2
                )
                x_mid = x + d * (sigma_mid - sigma)
                denoised2 = denoise_fn(x_mid, sigma_mid, i)
                d2 = to_d(x_mid, sigma_mid, denoised2)
                return x + d2 * (sigma_down - sigma)

            x_new = jax.lax.cond(sigma_down > 0, second_order,
                                 lambda _: x + d * (sigma_down - sigma),
                                 operand=None)
            if algo == "dpm2_a":
                noise = _step_noise(image_keys, i, x.shape, x.dtype)
                x_new = x_new + noise * sigma_up

        elif algo == "dpmpp_2s_a":
            # k-diffusion sample_dpmpp_2s_ancestral: single-step 2nd order
            # in log-sigma space, then ancestral noise.
            sigma_down, sigma_up = _ancestral_split(sigma, sigma_next)

            def second_order(_):
                t = -jnp.log(jnp.maximum(sigma, 1e-10))
                t_next = -jnp.log(jnp.maximum(sigma_down, 1e-10))
                h = t_next - t
                s_mid = t + 0.5 * h
                sig_mid = jnp.exp(-s_mid)
                x_2 = (sig_mid / sigma) * x - jnp.expm1(-0.5 * h) * denoised
                denoised_2 = denoise_fn(x_2, sig_mid, i)
                return (sigma_down / sigma) * x \
                    - jnp.expm1(-h) * denoised_2

            x_new = jax.lax.cond(sigma_down > 0, second_order,
                                 lambda _: x + d * (sigma_down - sigma),
                                 operand=None)
            noise = _step_noise(image_keys, i, x.shape, x.dtype)
            x_new = x_new + noise * sigma_up

        elif algo == "dpmpp_sde":
            # k-diffusion sample_dpmpp_sde (eta=1, r=1/2): two-stage SDE
            # solver with fresh noise at the midpoint and the endpoint.
            def sde_step(_):
                t = -jnp.log(jnp.maximum(sigma, 1e-10))
                t_next = -jnp.log(jnp.maximum(sigma_next, 1e-10))
                h = t_next - t
                s_mid = t + 0.5 * h
                sig_mid = jnp.exp(-s_mid)
                # stage 1: ancestral sub-step to the midpoint
                sd1, su1 = _ancestral_split(sigma, sig_mid)
                s1 = -jnp.log(jnp.maximum(sd1, 1e-10))
                x_2 = (sd1 / sigma) * x - jnp.expm1(t - s1) * denoised
                noise_mid = _step_noise(image_keys, 500_000 + i,
                                        x.shape, x.dtype)
                x_2 = x_2 + noise_mid * su1
                denoised_2 = denoise_fn(x_2, sig_mid, i)
                # stage 2: combine and step to sigma_next
                sd2, su2 = _ancestral_split(sigma, sigma_next)
                s2 = -jnp.log(jnp.maximum(sd2, 1e-10))
                denoised_d = denoised_2  # fac = 1/(2r) = 1 -> pure stage-2
                x_n = (sd2 / sigma) * x - jnp.expm1(t - s2) * denoised_d
                noise_end = _step_noise(image_keys, i, x.shape, x.dtype)
                return x_n + noise_end * su2

            x_new = jax.lax.cond(sigma_next > 0, sde_step,
                                 lambda _: x + d * (sigma_next - sigma),
                                 operand=None)

        elif algo == "dpmpp_2m":
            t = -jnp.log(jnp.maximum(sigma, 1e-10))
            t_next = -jnp.log(jnp.maximum(sigma_next, 1e-10))
            h = t_next - t
            sigma_prev = sigmas[jnp.maximum(i - 1, 0)]
            t_prev = -jnp.log(jnp.maximum(sigma_prev, 1e-10))
            h_last = t - t_prev
            r = h_last / jnp.maximum(h, 1e-10)
            denoised_d = (1 + 1 / (2 * r)) * denoised \
                - (1 / (2 * r)) * carry.old_denoised
            use_multistep = jnp.logical_and(carry.have_old, sigma_next > 0)
            eff = jnp.where(use_multistep, denoised_d, denoised)
            ratio = sigma_next / jnp.maximum(sigma, 1e-10)
            x_new = ratio * x - jnp.expm1(-h) * eff
            # terminal step (sigma_next == 0): x collapses to denoised
            x_new = jnp.where(sigma_next > 0, x_new, denoised)

        elif algo == "lms":
            # order-2 Adams-Bashforth on d (k-diffusion LMS truncated to
            # order 2: identical at step 0, very close thereafter). The carry
            # history slot holds the PREVIOUS step's d for this algorithm.
            d_prev = carry.old_denoised
            h = sigma_next - sigma
            h_last = sigma - sigmas[jnp.maximum(i - 1, 0)]
            r = h / jnp.where(h_last == 0, 1.0, h_last)
            d_eff = jnp.where(carry.have_old,
                              d + 0.5 * r * (d - d_prev), d)
            x_new = x + d_eff * h

        elif algo == "plms":
            # ldm's pseudo linear multistep (the webui PLMS sampler):
            # Adams-Bashforth on the eps estimate, ramping order 2->4 as
            # history fills; the first step probes sigma_next for a pseudo
            # improved-Euler estimate. Terminal step uses plain d (exact).
            h = sigma_next - sigma

            def warmup(_):
                sn = jnp.maximum(sigma_next, 1e-10)
                x_eul = x + d * h
                denoised2 = denoise_fn(x_eul, sn, i)
                return (d + to_d(x_eul, sn, denoised2)) / 2

            def multistep(_):
                d1, d2_, d3 = carry.old_denoised, carry.hist2, carry.hist3
                o2 = (3 * d - d1) / 2
                o3 = (23 * d - 16 * d1 + 5 * d2_) / 12
                o4 = (55 * d - 59 * d1 + 37 * d2_ - 9 * d3) / 24
                n = carry.n_hist
                return jnp.where(n >= 3, o4, jnp.where(n == 2, o3, o2))

            d_prime = jax.lax.cond(carry.n_hist > 0, multistep, warmup,
                                   operand=None)
            d_prime = jnp.where(sigma_next > 0, d_prime, d)
            x_new = x + d_prime * h

        elif algo == "dpm_fast":
            # Multistep 2nd-order DPM-Solver in the VE eps parameterization:
            # slope of eps estimated from the PREVIOUS step's d (1 model
            # eval per step). First step is solver-1 (== Euler); terminal
            # step collapses to the denoised prediction (exact).
            t = -jnp.log(jnp.maximum(sigma, 1e-10))
            sn = jnp.maximum(sigma_next, 1e-10)
            h = -jnp.log(sn) - t
            sigma_prev = sigmas[jnp.maximum(i - 1, 0)]
            h_last = t + jnp.log(jnp.maximum(sigma_prev, 1e-10))
            i0 = sigma - sigma_next
            i1 = sigma - sigma_next - h * sigma_next
            d_prev = carry.old_denoised
            c1 = (d - d_prev) / jnp.maximum(h_last, 1e-10)
            c1 = jnp.where(carry.have_old, c1, jnp.zeros_like(c1))
            x_new = x - i0 * d - i1 * c1
            x_new = jnp.where(sigma_next > 0, x_new, denoised)

        elif algo in ("dpm_solver_2", "dpm_solver_3"):
            # Single-step DPM-Solver, order 2 (midpoint) or 3 (thirds), in
            # the VE eps parameterization (Lu et al. 2022; k-diffusion's
            # dpm_solver_2_step/3_step walk the same exponential-integrator
            # updates). Exact integrals of the Taylor terms over the step:
            #   I0 = ∫σ ds = σ−σ', I1 = ∫(s−t)σ ds = σ−σ'−hσ',
            #   I2 = ∫(s−t)²σ ds = 2·I1 − h²σ'   (with t = −log σ).
            def solver(_):
                sn = jnp.maximum(sigma_next, 1e-10)
                t = -jnp.log(jnp.maximum(sigma, 1e-10))
                h = -jnp.log(sn) - t
                i0 = sigma - sigma_next
                i1 = sigma - sigma_next - h * sigma_next
                if algo == "dpm_solver_2":
                    a = 0.5 * h
                    sig1 = jnp.exp(-(t + a))
                    u1 = x + d * (sig1 - sigma)  # Euler probe to midpoint
                    d1 = to_d(u1, sig1, denoise_fn(u1, sig1, i))
                    c1 = (d1 - d) / a            # eps' estimate
                    return x - i0 * d - i1 * c1
                # order 3: probes at r1=1/3, r2=2/3; quadratic fit in s
                a = h / 3.0
                b = 2.0 * h / 3.0
                sig1 = jnp.exp(-(t + a))
                sig2 = jnp.exp(-(t + b))
                u1 = x + d * (sig1 - sigma)
                d1 = to_d(u1, sig1, denoise_fn(u1, sig1, i))
                # 2nd-order probe to s2 using the midstep slope
                i0b = sigma - sig2
                i1b = sigma - sig2 - b * sig2
                u2 = x - i0b * d - i1b * (d1 - d) / a
                d2_ = to_d(u2, sig2, denoise_fn(u2, sig2, i))
                denom = a * b * (b - a)
                c1 = (b * b * (d1 - d) - a * a * (d2_ - d)) / denom
                c2 = (a * (d2_ - d) - b * (d1 - d)) / denom
                i2 = 2.0 * i1 - h * h * sigma_next
                return x - i0 * d - i1 * c1 - i2 * c2

            x_new = jax.lax.cond(sigma_next > 0, solver,
                                 lambda _: denoised, operand=None)

        else:  # pragma: no cover
            raise ValueError(f"unknown sampler algorithm {algo}")

        history = d if algo in ("lms", "plms", "dpm_fast") else denoised
        return Carry(x_new, history, jnp.bool_(True),
                     carry.old_denoised, carry.hist2,
                     carry.n_hist + 1), ()

    return step


def init_carry(x: jax.Array) -> Carry:
    # the history leaves must be DISTINCT buffers, not one shared zeros
    # array: the engine donates the whole carry into each chunk dispatch,
    # and XLA rejects donating the same buffer twice
    return Carry(x, jnp.zeros_like(x), jnp.bool_(False), jnp.zeros_like(x),
                 jnp.zeros_like(x), jnp.int32(0))


def run_steps(
    step_fn, carry: Carry, start: int, stop: int
) -> Carry:
    """Scan a contiguous chunk [start, stop) of sampler steps."""
    idx = jnp.arange(start, stop)
    carry, _ = jax.lax.scan(step_fn, carry, idx)
    return carry


class Ladder(NamedTuple):
    """A kept sigma ladder, ``steps + 1`` float32: the device array the
    executables close over and the host copy of the same bits (read-only),
    for whoever searches it or reads one sigma as a Python float."""

    sigmas: jax.Array
    host: np.ndarray
    #: the key's ``NoiseSchedule``: held so that its ``id`` stays its own
    schedule: sched.NoiseSchedule


#: Ladders by (schedule name, the engine's ``NoiseSchedule`` object, steps).
#: ``NoiseSchedule`` wraps an ``ndarray`` and does not hash, so the key has
#: the object's ``id`` and the entry holds the object.
_LADDERS = KeptTable(64)


def ladder(spec: SamplerSpec, schedule: sched.NoiseSchedule,
           steps: int) -> Tuple[Ladder, bool]:
    """``(ladder, hit)``. The first call for a key builds the ladder (the
    schedule's own ops, one fetch); a later one runs no device op. The
    arrays are shared by every request: index them, never donate them."""
    def build() -> Ladder:
        sigmas = jnp.asarray(sched.SCHEDULES[spec.schedule](schedule, steps))
        host = np.asarray(sigmas)
        host.setflags(write=False)
        return Ladder(sigmas, host, schedule)

    entry, hit = _LADDERS.get((spec.schedule, id(schedule), int(steps)),
                              build)
    PLAN.record("ladder", hit)
    return entry, hit


def build_sigmas(spec: SamplerSpec, schedule: sched.NoiseSchedule,
                 steps: int) -> jax.Array:
    return ladder(spec, schedule, steps)[0].sigmas


# --------------------------------------------------------------------------
# DPM adaptive: host-side PID step control over a compiled attempt
# --------------------------------------------------------------------------

class PIDStepController:
    """k-diffusion's PIDStepSizeController: proposes/accepts log-sigma step
    sizes from the embedded-pair error estimate. Pure host arithmetic."""

    def __init__(self, h: float, pcoeff: float, icoeff: float, dcoeff: float,
                 order: float, accept_safety: float, eps: float = 1e-8):
        import math

        self._atan = math.atan
        self.h = h
        self.b1 = (pcoeff + icoeff + dcoeff) / order
        self.b2 = -(pcoeff + 2 * dcoeff) / order
        self.b3 = dcoeff / order
        self.accept_safety = accept_safety
        self.eps = eps
        self.errs: list = []

    def _limiter(self, x: float) -> float:
        return 1.0 + self._atan(x - 1.0)

    def propose_step(self, error: float) -> bool:
        inv_error = 1.0 / (float(error) + self.eps)
        if not self.errs:
            self.errs = [inv_error, inv_error, inv_error]
        self.errs[0] = inv_error
        factor = (self.errs[0] ** self.b1 * self.errs[1] ** self.b2
                  * self.errs[2] ** self.b3)
        factor = self._limiter(factor)
        accept = factor >= self.accept_safety
        if accept:
            self.errs[2] = self.errs[1]
            self.errs[1] = self.errs[0]
        self.h *= factor
        return accept


def make_adaptive_attempt(denoise_fn: DenoiseFn):
    """One adaptive attempt as a single traceable function of
    ``(x, x_prev, s, h, rtol, atol)`` with s/h as DATA — jit it once and
    every PID-proposed step reuses the executable.

    Computes k-diffusion's embedded order-2/3 DPM-Solver pair in the eps
    parameterization over t = -log(sigma) (its dpm_solver_2_step with
    r1=1/3 shares both model evals with dpm_solver_3_step, so an attempt
    is exactly 3 UNet calls) and the scaled-RMS error between them.
    Returns (x_low, x_high, error_scalar)."""

    def attempt(x, x_prev, s, h, rtol, atol):
        sig_s = jnp.exp(-s)
        den = denoise_fn(x, sig_s, jnp.int32(0))
        eps = (x - den) / sig_s
        # shared probe at s + h/3 (r1 = 1/3)
        sig1 = jnp.exp(-(s + h / 3.0))
        u1 = x - sig1 * jnp.expm1(h / 3.0) * eps
        den1 = denoise_fn(u1, sig1, jnp.int32(0))
        eps_r1 = (u1 - den1) / sig1
        sig_t = jnp.exp(-(s + h))
        # order-2 estimate (dpm_solver_2_step, r1=1/3)
        x_low = x - sig_t * jnp.expm1(h) * eps \
            - sig_t * 1.5 * jnp.expm1(h) * (eps_r1 - eps)
        # order-3 estimate (dpm_solver_3_step, r1=1/3, r2=2/3)
        r2h = 2.0 * h / 3.0
        sig2 = jnp.exp(-(s + r2h))
        u2 = x - sig2 * jnp.expm1(r2h) * eps \
            - sig2 * 2.0 * (jnp.expm1(r2h) / r2h - 1.0) * (eps_r1 - eps)
        den2 = denoise_fn(u2, sig2, jnp.int32(0))
        eps_r2 = (u2 - den2) / sig2
        x_high = x - sig_t * jnp.expm1(h) * eps \
            - sig_t * 1.5 * (jnp.expm1(h) / h - 1.0) * (eps_r2 - eps)
        delta = jnp.maximum(atol, rtol * jnp.maximum(jnp.abs(x_low),
                                                     jnp.abs(x_prev)))
        error = jnp.sqrt(jnp.mean(jnp.square((x_low - x_high) / delta)))
        return x_low, x_high, error

    return attempt


def sample_dpm_adaptive(attempt_fn, x: jax.Array, sigma_max: float,
                        sigma_min: float, *, rtol: float = 0.05,
                        atol: float = 0.0078, h_init: float = 0.05,
                        pcoeff: float = 0.0, icoeff: float = 1.0,
                        dcoeff: float = 0.0, accept_safety: float = 0.81,
                        order: int = 3, max_attempts: int = 1000,
                        should_stop=None, on_accept=None):
    """k-diffusion ``sample_dpm_adaptive`` (eta=0) with the solver compiled:
    the host runs ONLY the PID controller; each attempt is one call of
    ``attempt_fn`` (see make_adaptive_attempt; pass it jitted).

    Integrates t = -log(sigma) from sigma_max to sigma_min and returns
    (x_at_sigma_min, info) — like k-diffusion, there is no terminal
    collapse to the denoised prediction. ``should_stop()`` is polled
    between attempts (interrupt contract); ``on_accept(x, sigma, n)`` may
    transform x after each accepted step (inpaint region pinning)."""
    import math

    t_end = -math.log(sigma_min)
    s = float(-math.log(sigma_max))
    x_prev = x
    pid = PIDStepController(abs(h_init), pcoeff, icoeff, dcoeff,
                            order, accept_safety)
    info = {"steps": 0, "nfe": 0, "n_accept": 0, "n_reject": 0,
            "completed": False}
    while s < t_end - 1e-5:
        if should_stop is not None and should_stop():
            break
        if info["steps"] >= max_attempts:  # runaway-tolerance backstop
            break
        t = min(t_end, s + pid.h)
        x_low, x_high, error = attempt_fn(
            x, x_prev, jnp.float32(s), jnp.float32(t - s),
            jnp.float32(rtol), jnp.float32(atol))
        info["steps"] += 1
        info["nfe"] += 3
        if pid.propose_step(float(error)):
            x_prev = x_low
            x = x_high
            s = t
            info["n_accept"] += 1
            if on_accept is not None:
                x = on_accept(x, math.exp(-s), info["n_accept"])
        else:
            info["n_reject"] += 1
    info["completed"] = s >= t_end - 1e-5
    return x, info
