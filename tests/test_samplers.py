"""Sampler tests: schedule shapes/monotonicity, convergence on an analytic
denoiser, seed-exact sharding of ancestral noise, chunked == unchunked."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stable_diffusion_webui_distributed_tpu.runtime import rng
from stable_diffusion_webui_distributed_tpu.runtime.kept import KeptTable
from stable_diffusion_webui_distributed_tpu.samplers import (
    kdiffusion as kd,
    schedules as sched,
)
from stable_diffusion_webui_distributed_tpu.serving.metrics import PLAN

SCHEDULE = sched.sd_schedule()


def keys_for(seed, n):
    return jax.vmap(lambda i: rng.key_for_image(seed, i))(jnp.arange(n))


class TestSchedules:
    def test_trained_sigma_range(self):
        # SD's scaled-linear schedule: sigma_min ~0.03, sigma_max ~14.6.
        assert 0.02 < SCHEDULE.sigma_min < 0.04
        assert 14.0 < SCHEDULE.sigma_max < 15.5

    @pytest.mark.parametrize("name", ["default", "karras", "ddim", "exponential"])
    def test_ladder_shape_and_monotone(self, name):
        s = sched.SCHEDULES[name](SCHEDULE, 20)
        assert s.shape == (21,)
        assert s[-1] == 0.0
        assert np.all(np.diff(s) < 0), f"{name} not strictly decreasing"

    def test_sigma_t_roundtrip(self):
        t = SCHEDULE.sigma_to_t(jnp.float32(1.0))
        back = SCHEDULE.t_to_sigma(t)
        np.testing.assert_allclose(float(back), 1.0, rtol=1e-3)


class TestSamplerMath:
    """Analytic check: with denoise_fn(x, sigma) = x0 (a perfect denoiser for
    a point distribution at x0), every deterministic sampler must land on x0
    from any start, and ancestral ones must land near it."""

    X0 = 3.7

    def _run(self, name, steps=12, x0=None):
        spec = kd.resolve_sampler(name)
        x0 = self.X0 if x0 is None else x0

        def denoise(x, sigma, step):
            return jnp.full_like(x, x0)

        sigmas = kd.build_sigmas(spec, SCHEDULE, steps)
        keys = keys_for(7, 2)
        step = kd.make_sampler_step(spec, denoise, sigmas, keys)
        x = jnp.full((2, 4, 4, 1), 10.0) * sigmas[0] / 10.0  # scaled start
        carry = kd.run_steps(step, kd.init_carry(x), 0, steps)
        return np.asarray(carry.x)

    @pytest.mark.parametrize(
        "name", ["Euler", "DDIM", "Heun", "DPM++ 2M", "DPM++ 2M Karras",
                 "LMS", "DPM2", "PLMS", "DPM fast", "DPM adaptive"])
    def test_deterministic_converges_exactly(self, name):
        out = self._run(name)
        np.testing.assert_allclose(out, self.X0, rtol=1e-4, atol=1e-4)

    # Euler's loose bound is the 1st-order contrast anchor (the ladder tail
    # is stiff for x ∝ sigma^0.3). PLMS's constant-coefficient
    # Adams-Bashforth roughly halves Euler's error (as ldm's does on stiff
    # tails); the DPM solvers must track the exact solution 100x+ tighter.
    @pytest.mark.parametrize("name,rel_tol", [
        ("Euler", 0.80), ("PLMS", 0.40), ("DPM fast", 0.15),
        ("DPM adaptive", 0.005)])
    def test_order_of_accuracy_on_analytic_ode(self, name, rel_tol):
        """Integrate dx/dsigma = x(1-k)/sigma (denoiser x0 = k*x), whose
        exact solution is x ∝ sigma^(1-k). Higher-order samplers must track
        it far better than Euler at the same step count; stop one step
        before the terminal sigma=0 (where every sampler is exact anyway).
        """
        k = 0.7
        spec = kd.resolve_sampler(name)

        def denoise(x, sigma, step):
            return x * k

        steps = 12
        sigmas = kd.build_sigmas(spec, SCHEDULE, steps)
        keys = keys_for(3, 1)
        step = kd.make_sampler_step(spec, denoise, sigmas, keys)
        x = jnp.full((1, 2, 2, 1), float(sigmas[0]))
        carry = kd.run_steps(step, kd.init_carry(x), 0, steps - 1)
        got = float(np.asarray(carry.x).mean())
        exact = float(sigmas[0]) * (float(sigmas[steps - 1])
                                    / float(sigmas[0])) ** (1 - k)
        assert abs(got - exact) / exact < rel_tol, (got, exact)

    @pytest.mark.parametrize(
        "name", ["Euler a", "DPM2 a", "DPM++ 2S a", "DPM++ SDE",
                 "DPM++ 2S a Karras", "DPM++ SDE Karras"])
    def test_ancestral_converges(self, name):
        # Ancestral noise is annealed by sigma_up -> 0 at the end; the final
        # x must be exactly x0 because the terminal step has sigma_next=0.
        out = self._run(name)
        np.testing.assert_allclose(out, self.X0, rtol=1e-3, atol=1e-3)

    def test_unknown_name_falls_back_to_euler_a(self):
        spec = kd.resolve_sampler("No Such Sampler")
        assert spec.algorithm == "euler_a"  # reference worker.py:457-467


class TestDpmAdaptive:
    """The host-side PID loop (kd.sample_dpm_adaptive): k-diffusion's
    adaptive controller over the compiled embedded order-2/3 pair."""

    def _attempt(self, denoise):
        return jax.jit(kd.make_adaptive_attempt(denoise))

    def test_exact_on_point_denoiser(self):
        # denoised == const: the exponential integrator is exact, every
        # attempt is accepted, and x lands on the analytic solution
        # x(sigma) = x0 + (x_start - x0) * sigma/sigma_start.
        x0 = 2.5

        def denoise(x, sigma, step):
            return jnp.full_like(x, x0)

        smax, smin = float(SCHEDULE.sigma_max), float(SCHEDULE.sigma_min)
        x = jnp.full((2, 4, 4, 1), x0 + smax)  # offset = sigma_max
        out, info = kd.sample_dpm_adaptive(self._attempt(denoise), x,
                                           smax, smin)
        exact = x0 + smin  # offset decays proportionally to sigma
        np.testing.assert_allclose(np.asarray(out), exact, rtol=1e-3,
                                   atol=1e-3)
        assert info["n_reject"] == 0
        assert info["nfe"] == 3 * info["steps"]
        # the PID grows h on exact solves: far fewer steps than a dense
        # fixed ladder would need to cross ~6 decades of sigma
        assert info["n_accept"] < 200

    def test_tracks_analytic_ode_tightly(self):
        # same ODE family as test_order_of_accuracy_on_analytic_ode
        k = 0.7

        def denoise(x, sigma, step):
            return x * k

        smax, smin = float(SCHEDULE.sigma_max), 0.1
        x = jnp.full((1, 2, 2, 1), smax)
        out, info = kd.sample_dpm_adaptive(self._attempt(denoise), x,
                                           smax, smin)
        exact = smax * (smin / smax) ** (1 - k)
        got = float(np.asarray(out).mean())
        assert abs(got - exact) / exact < 0.05, (got, exact, info)
        # tightening rtol/atol must tighten the result (the controller
        # actually controls): an order tighter tolerance, ~2x+ less error
        out2, info2 = kd.sample_dpm_adaptive(
            self._attempt(denoise), x, smax, smin, rtol=0.005, atol=8e-4)
        got2 = float(np.asarray(out2).mean())
        assert abs(got2 - exact) < abs(got - exact) / 2, (got, got2, info2)
        assert info2["n_accept"] > info["n_accept"]

    def test_interrupt_stops_between_attempts(self):
        def denoise(x, sigma, step):
            return jnp.zeros_like(x)

        calls = []
        x = jnp.full((1, 2, 2, 1), 10.0)
        out, info = kd.sample_dpm_adaptive(
            self._attempt(denoise), x, 10.0, 0.1,
            should_stop=lambda: len(calls) >= 2 or calls.append(None))
        assert info["steps"] == 2  # stopped after two attempts

    def test_on_accept_transforms_every_accepted_step(self):
        def denoise(x, sigma, step):
            return jnp.zeros_like(x)

        seen = []

        def on_accept(x, sigma, n):
            seen.append((n, sigma))
            return x

        _, info = kd.sample_dpm_adaptive(
            self._attempt(denoise), jnp.full((1, 2, 2, 1), 10.0),
            10.0, 0.5, on_accept=on_accept)
        assert [n for n, _ in seen] == list(range(1, info["n_accept"] + 1))
        assert all(s2 < s1 for (_, s1), (_, s2) in zip(seen, seen[1:]))

    def test_spec_is_marked_adaptive(self):
        assert kd.resolve_sampler("DPM adaptive").adaptive
        assert not kd.resolve_sampler("Euler a").adaptive


class TestShardingContract:
    """Ancestral noise must depend only on the image's key — never on batch
    position — so sub-batches reproduce the full batch exactly."""

    def test_subbatch_equals_fullbatch_ancestral(self):
        spec = kd.resolve_sampler("Euler a")
        shape = (4, 4, 1)

        def denoise(x, sigma, step):
            # any x-dependent denoiser; keeps the test honest
            return x * 0.9 / (1.0 + sigma)

        sigmas = kd.build_sigmas(spec, SCHEDULE, 8)
        full_keys = keys_for(123, 6)
        x_full = rng.batch_noise(123, 0, 0.0, 0, 6, shape) * sigmas[0]
        step = kd.make_sampler_step(spec, denoise, sigmas, full_keys)
        out_full = np.asarray(
            kd.run_steps(step, kd.init_carry(x_full), 0, 8).x
        )

        # images [2, 5) as an independent sub-batch (another "worker")
        sub_keys = jax.vmap(lambda i: rng.key_for_image(123, i))(
            jnp.arange(2, 5))
        x_sub = rng.batch_noise(123, 0, 0.0, 2, 3, shape) * sigmas[0]
        step_sub = kd.make_sampler_step(spec, denoise, sigmas, sub_keys)
        out_sub = np.asarray(
            kd.run_steps(step_sub, kd.init_carry(x_sub), 0, 8).x
        )
        np.testing.assert_array_equal(out_full[2:5], out_sub)


class TestChunking:
    def test_chunked_equals_unchunked(self):
        """Interrupt chunking must not change results (worker.py:440-448
        semantics: polling is invisible to the computation)."""
        spec = kd.resolve_sampler("Euler a")

        def denoise(x, sigma, step):
            return x / (1.0 + sigma)

        sigmas = kd.build_sigmas(spec, SCHEDULE, 10)
        keys = keys_for(9, 2)
        x = rng.batch_noise(9, 0, 0.0, 0, 2, (4, 4, 1)) * sigmas[0]
        step = kd.make_sampler_step(spec, denoise, sigmas, keys)

        whole = kd.run_steps(step, kd.init_carry(x), 0, 10)
        c = kd.init_carry(x)
        for lo, hi in [(0, 3), (3, 7), (7, 10)]:
            c = kd.run_steps(step, c, lo, hi)
        np.testing.assert_array_equal(np.asarray(whole.x), np.asarray(c.x))


def fresh_ladder(spec, schedule, steps):
    """The ladder as every request built it before it was kept."""
    return jnp.asarray(sched.SCHEDULES[spec.schedule](schedule, steps))


def bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


class TestKeptLadder:
    """samplers/kdiffusion.py ``ladder``: built once per (schedule name,
    NoiseSchedule object, steps) by the code that built it every request,
    then served with no device op."""

    @pytest.mark.parametrize("steps", [1, 20, 30])
    @pytest.mark.parametrize("name", sorted(kd.SAMPLERS))
    def test_kept_is_a_fresh_build_bit_for_bit(self, name, steps):
        spec = kd.resolve_sampler(name)
        kept, _ = kd.ladder(spec, SCHEDULE, steps)
        fresh = bits(fresh_ladder(spec, SCHEDULE, steps))
        assert fresh[0] == np.float32 and fresh[1] == (steps + 1,)
        assert bits(kept.sigmas) == fresh
        assert bits(kept.host) == fresh
        assert not kept.host.flags.writeable
        assert kd.build_sigmas(spec, SCHEDULE, steps) is kept.sigmas

    @pytest.mark.parametrize("name", ["Euler a", "DPM++ 2M Karras", "DDIM",
                                      "DPM fast"])
    def test_second_call_is_the_same_objects_and_builds_nothing(
            self, name, monkeypatch):
        spec = kd.resolve_sampler(name)
        schedule = sched.sd_schedule()
        before = PLAN.summary()["ladder"]
        first, hit = kd.ladder(spec, schedule, 17)
        assert not hit

        def boom(*a, **k):
            raise AssertionError("a kept ladder was built again")

        for key in sched.SCHEDULES:
            monkeypatch.setitem(sched.SCHEDULES, key, boom)
        monkeypatch.setattr(sched.NoiseSchedule, "t_to_sigma", boom)
        second, hit = kd.ladder(spec, schedule, 17)
        assert hit
        assert second.sigmas is first.sigmas and second.host is first.host
        assert kd.build_sigmas(spec, schedule, 17) is first.sigmas
        after = PLAN.summary()["ladder"]
        assert after["builds"] - before["builds"] == 1
        assert after["hits"] - before["hits"] == 2

    def test_two_noise_schedules_do_not_share_an_entry(self):
        spec = kd.resolve_sampler("Euler a")
        a, b = sched.sd_schedule(), sched.sd_schedule(beta_end=0.02)
        twin = sched.sd_schedule()          # a's numbers, another object
        (la, hit_a), (lb, hit_b), (lt, hit_t) = (
            kd.ladder(spec, s, 9) for s in (a, b, twin))
        assert (hit_a, hit_b, hit_t) == (False, False, False)
        assert la.schedule is a and lb.schedule is b and lt.schedule is twin
        assert bits(la.host) != bits(lb.host)
        assert bits(lt.host) == bits(la.host) and lt.host is not la.host
        assert bits(lb.sigmas) == bits(fresh_ladder(spec, b, 9))
        # and the step count and the schedule's name key apart too
        assert kd.ladder(spec, a, 10)[1] is False
        assert kd.ladder(kd.resolve_sampler("Euler a Karras"), a, 9)[1] \
            is False
        assert kd.ladder(spec, a, 9) == (la, True)

    def test_two_threads_build_one_new_key_once(self, monkeypatch):
        import threading
        import time

        built = []
        real = sched.SCHEDULES["default"]

        def slow(schedule, steps):
            built.append(steps)
            time.sleep(0.05)
            return real(schedule, steps)

        monkeypatch.setitem(sched.SCHEDULES, "default", slow)
        spec, schedule = kd.resolve_sampler("Euler a"), sched.sd_schedule()
        gate, got = threading.Barrier(2), []

        def ask():
            gate.wait()
            got.append(kd.ladder(spec, schedule, 13))

        threads = [threading.Thread(target=ask) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert built == [13]
        assert sorted(hit for _, hit in got) == [False, True]
        assert got[0][0].sigmas is got[1][0].sigmas

    @pytest.mark.parametrize("limit", [2, 3])
    def test_table_is_bounded_and_drops_the_least_recently_used(
            self, limit):
        table = KeptTable(limit)
        for k in range(limit):
            assert table.get(k, lambda k=k: [k]) == ([k], False)
        assert table.get(0, list)[1] is True        # 0 is now the newest
        assert table.get("new", list) == ([], False)
        assert len(table) == limit
        assert table.get(0, list)[1] is True
        assert table.get(1, list) == ([], False)    # 1 went, and is rebuilt
