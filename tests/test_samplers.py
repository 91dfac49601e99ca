"""Samplers + schedules: convergence with an ideal denoiser, determinism,
schedule invariants, CFG wrapper."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import samplers as smp
from comfyui_distributed_tpu.models import schedules as sch


@pytest.fixture(scope="module")
def ds():
    return sch.make_discrete_schedule()


class TestSchedules:
    def test_discrete_table_shape(self, ds):
        assert ds.sigmas.shape == (1000,)
        assert ds.sigma_min > 0
        assert 10 < ds.sigma_max < 200  # SD scaled-linear is ~14.6

    def test_sigma_t_round_trip(self, ds):
        t = ds.t_from_sigma(np.asarray([1.0, 5.0]))
        back = ds.sigma_from_t(t)
        assert np.allclose(back, [1.0, 5.0], rtol=1e-3)

    @pytest.mark.parametrize("name", sch.SCHEDULER_NAMES)
    def test_all_schedulers_valid(self, ds, name):
        for steps in (1, 4, 20):
            sig = sch.compute_sigmas(ds, name, steps)
            assert sig[-1] == 0.0
            assert np.all(np.diff(sig) < 1e-7), f"{name} not descending: {sig}"
            assert sig[0] > 0

    def test_karras_endpoints(self, ds):
        sig = sch.karras_scheduler(ds, 10)
        assert np.isclose(sig[0], ds.sigma_max, rtol=1e-5)
        assert np.isclose(sig[-2], ds.sigma_min, rtol=1e-5)

    def test_denoise_truncation(self, ds):
        full = sch.compute_sigmas(ds, "normal", 20)
        part = sch.compute_sigmas(ds, "normal", 10, denoise=0.5)
        assert len(part) == 11
        assert part[0] < full[0]  # starts mid-schedule (img2img semantics)

    def test_unknown_scheduler_raises(self, ds):
        with pytest.raises(ValueError):
            sch.compute_sigmas(ds, "nope", 10)


def ideal_model(x0):
    """Perfect denoiser for a point-mass distribution at x0: always returns
    x0.  Every correct sampler must converge to x0 as sigma -> 0."""
    def model(x, sigma, **kw):
        return jnp.broadcast_to(x0, x.shape)
    return model


class TestSamplers:
    @pytest.mark.parametrize("name", smp.SAMPLER_NAMES)
    def test_converges_to_target(self, ds, name):
        x0 = jnp.asarray(np.random.default_rng(3).standard_normal(
            (2, 4, 4, 3)).astype(np.float32))
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 12))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2, dtype=jnp.uint32))
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, x0.shape) * sigmas[0]
        sampler = smp.get_sampler(name)
        out = sampler(ideal_model(x0), x, sigmas, keys=keys)
        # dpm_fast/dpm_adaptive end at sigma_min, not 0 (k-diffusion /
        # ComfyUI parity): residual is O(sigma_min * |noise|)
        atol = 0.12 if name in ("dpm_fast", "dpm_adaptive") else 1e-3
        assert np.allclose(np.asarray(out), np.asarray(x0), atol=atol), name

    @pytest.mark.parametrize("name", ["euler_ancestral", "dpmpp_2m_sde",
                                      "lcm", "dpmpp_sde", "dpmpp_3m_sde",
                                      "ddpm", "er_sde", "seeds_2",
                                      "seeds_3"])
    def test_stochastic_requires_keys(self, ds, name):
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 4))
        x = jnp.zeros((1, 2, 2, 1))
        with pytest.raises(ValueError):
            smp.get_sampler(name)(ideal_model(x), x, sigmas)

    def test_deterministic_given_keys(self, ds):
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 6))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 4, 2)) * sigmas[0]
        x0 = jnp.ones((3, 4, 4, 2)) * 0.3
        a = smp.sample_euler_ancestral(ideal_model(x0), x, sigmas, keys=keys)
        b = smp.sample_euler_ancestral(ideal_model(x0), x, sigmas, keys=keys)
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_different_keys_differ_midrun(self, ds):
        """Distinct per-sample keys give distinct trajectories (replica
        independence) — checked at nonzero final sigma so ancestral noise
        isn't annihilated."""
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 8))[:5]  # stop early
        keys_a = jax.vmap(jax.random.PRNGKey)(jnp.asarray([1, 2], jnp.uint32))
        keys_b = jax.vmap(jax.random.PRNGKey)(jnp.asarray([3, 4], jnp.uint32))
        x = jnp.zeros((2, 4, 4, 1)) + sigmas[0]
        x0 = jnp.zeros((2, 4, 4, 1))
        a = smp.sample_euler_ancestral(ideal_model(x0), x, sigmas, keys=keys_a)
        b = smp.sample_euler_ancestral(ideal_model(x0), x, sigmas, keys=keys_b)
        assert not np.allclose(np.asarray(a), np.asarray(b))

    def test_samplers_jit_compile(self, ds):
        """The whole sampler must be jittable (scan-based, no python loop)."""
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 5))
        x0 = jnp.ones((1, 4, 4, 2)) * 0.5

        @jax.jit
        def run(x):
            return smp.sample_dpmpp_2m(ideal_model(x0), x, sigmas)

        x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 4, 2)) * sigmas[0]
        out = run(x)
        assert np.allclose(np.asarray(out), 0.5, atol=1e-3)

    def test_unknown_sampler_raises(self):
        with pytest.raises(ValueError):
            smp.get_sampler("plms9000")


class TestPerStepInterrupt:
    """With DTPU_INTERRUPT_POLL=1, /interrupt stops a sample already
    inside the compiled scan, not just between nodes."""

    @pytest.fixture(autouse=True)
    def _clean_flag(self, monkeypatch):
        from comfyui_distributed_tpu.runtime import interrupt as itr
        monkeypatch.setenv("DTPU_INTERRUPT_POLL", "1")
        itr.clear_interrupt()
        yield
        itr.clear_interrupt()

    def _run(self, ds, steps=20, sampler="euler"):
        x0 = jnp.zeros((1, 4, 4, 3), jnp.float32)
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", steps))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(1, dtype=jnp.uint32))
        x = jnp.ones(x0.shape, jnp.float32) * sigmas[0]
        fn = smp.get_sampler(sampler)
        return x, fn(ideal_model(x0), x, sigmas, keys=keys)

    @pytest.mark.parametrize("name", smp.SAMPLER_NAMES)
    def test_interrupt_skips_all_steps(self, ds, name):
        """Flag set -> every scan iteration skips the model call; the
        latent comes back untouched (the partial-result semantics).
        Parametrized over ALL samplers: dpmpp_2m/_sde once had their own
        scans bypassing the polling _scan_sampler."""
        from comfyui_distributed_tpu.runtime import interrupt as itr
        itr.request_interrupt()
        x_in, out = self._run(ds, sampler=name)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x_in))

    def test_clear_resumes_normal_sampling(self, ds):
        x_in, out = self._run(ds)
        # ideal denoiser: converges to 0, far from the initial latent
        assert not np.allclose(np.asarray(out), np.asarray(x_in))
        np.testing.assert_allclose(np.asarray(out),
                                   np.zeros_like(np.asarray(out)), atol=1e-3)

    def test_mid_run_interrupt_returns_partial(self, ds):
        """The model sets the flag on its 3rd call (host callback): every
        later scan iteration must skip, so the result is exactly the
        3-step partial — deterministic proof the poll stops a sample
        mid-scan within one step."""
        from comfyui_distributed_tpu.runtime import interrupt as itr

        steps = 20
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", steps))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(1, dtype=jnp.uint32))
        x = jnp.ones((1, 4, 4, 3), jnp.float32) * sigmas[0]
        calls = []

        def model(xin, sigma, **kw):
            def cb(_x_seq):
                calls.append(1)
                if len(calls) == 3:
                    itr.request_interrupt()
                return np.float32(0.0)
            z = jax.pure_callback(cb, jax.ShapeDtypeStruct((), np.float32),
                                  xin.reshape(-1)[0])
            return jnp.zeros_like(xin) + z   # ideal denoiser to x0 = 0

        out = np.asarray(smp.sample_euler(model, x, sigmas, keys=keys))
        # euler to x0=0: x_{k+1} = x_k * s_{k+1}/s_k, stopped after 3 steps
        expect = np.asarray(x) * float(sigmas[3] / sigmas[0])
        np.testing.assert_allclose(out, expect, rtol=1e-4)
        assert len(calls) == 3   # steps 4..20 never called the model

    def test_preset_interrupt_never_calls_model_uni_pc(self, ds):
        """uni_pc's priming call runs OUTSIDE the scan: it must honor the
        poll too — an already-interrupted run pays ZERO model calls (the
        latent-untouched check alone can't see a wasted forward)."""
        from comfyui_distributed_tpu.runtime import interrupt as itr

        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 6))
        x = jnp.ones((1, 4, 4, 3), jnp.float32) * sigmas[0]
        calls = []

        def model(xin, sigma, **kw):
            z = jax.pure_callback(
                lambda _: (calls.append(1), np.float32(0.0))[1],
                jax.ShapeDtypeStruct((), np.float32), xin.reshape(-1)[0])
            return jnp.zeros_like(xin) + z

        itr.request_interrupt()
        out = np.asarray(smp.sample_uni_pc(model, x, sigmas))
        np.testing.assert_allclose(out, np.asarray(x))
        assert calls == []


class TestCFG:
    def test_cfg_interpolates(self):
        calls = []

        def model(x, sigma, context=None):
            calls.append(x.shape[0])
            # each batch row's "denoised" depends on its own context row
            per_row = jnp.mean(context, axis=(1, 2)).reshape(-1, 1, 1, 1)
            return jnp.ones_like(x) * per_row

        cond = jnp.ones((1, 2, 4)) * 2.0
        uncond = jnp.zeros((1, 2, 4))
        x = jnp.zeros((1, 4, 4, 2))
        wrapped = smp.cfg_denoiser(model, cond, uncond, cfg_scale=6.0)
        out = wrapped(x, jnp.asarray(1.0))
        # d_uncond=0, d_cond=2 -> 0 + (2-0)*6 = 12
        assert np.allclose(np.asarray(out), 12.0)
        assert calls == [2]  # one doubled-batch call

    def test_cfg_scale_one_single_call(self):
        def model(x, sigma, context=None):
            return jnp.ones_like(x) * context.shape[0]
        wrapped = smp.cfg_denoiser(model, jnp.ones((2, 2, 4)),
                                   jnp.zeros((2, 2, 4)), cfg_scale=1.0)
        out = wrapped(jnp.zeros((2, 4, 4, 1)), jnp.asarray(1.0))
        assert np.allclose(np.asarray(out), 2.0)  # context not doubled


class TestDenoiserPredictionTypes:
    """make_denoiser conventions: a model predicting the TRUE quantity
    (eps or v, VP parameterization) must denoise exactly back to x0."""

    def _setup(self, ds, sigma_val):
        rng = np.random.default_rng(11)
        x0 = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
        noise = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
        sigma = jnp.float32(sigma_val)
        x = x0 + sigma * noise
        return x0, noise, sigma, x

    @pytest.mark.parametrize("sigma_val", [0.5, 2.0, 7.0])
    def test_eps_prediction_recovers_x0(self, ds, sigma_val):
        from comfyui_distributed_tpu.models.denoiser import make_denoiser
        x0, noise, sigma, x = self._setup(ds, sigma_val)

        def apply_fn(params, xin, ts, ctx, y=None, control=None):
            return noise                     # the true eps

        den = make_denoiser(apply_fn, {}, ds, prediction_type="eps")
        np.testing.assert_allclose(np.asarray(den(x, sigma)),
                                   np.asarray(x0), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("sigma_val", [0.5, 2.0, 7.0])
    def test_v_prediction_recovers_x0(self, ds, sigma_val):
        """VP v-target: v = alpha*eps - sigma_vp*x0 with
        alpha = 1/sqrt(sigma^2+1), sigma_vp = sigma*alpha (the SD2.x
        768-v parameterization)."""
        from comfyui_distributed_tpu.models.denoiser import make_denoiser
        x0, noise, sigma, x = self._setup(ds, sigma_val)
        alpha = 1.0 / jnp.sqrt(sigma ** 2 + 1.0)
        v_true = alpha * noise - (sigma * alpha) * x0

        def apply_fn(params, xin, ts, ctx, y=None, control=None):
            return v_true                    # the true v

        den = make_denoiser(apply_fn, {}, ds, prediction_type="v")
        np.testing.assert_allclose(np.asarray(den(x, sigma)),
                                   np.asarray(x0), rtol=1e-4, atol=1e-4)


class TestLoopOracles:
    """The scan/carry mechanics of the multistep and 2-call samplers vs
    straightforward per-step Python loops (where multistep bugs live):
    same model, same keys, same noise streams — allclose required.  The
    LMS loop integrates its coefficients with scipy.integrate.quad
    (k-diffusion's method), independently validating the in-graph
    Gauss-Legendre quadrature."""

    def _setup(self, ds, steps=7, b=2):
        import numpy as _np
        sigmas = np.asarray(sch.compute_sigmas(ds, "karras", steps),
                            _np.float64)
        rng = _np.random.default_rng(5)
        x = rng.standard_normal((b, 4, 4, 3)).astype(_np.float32) \
            * sigmas[0]
        keys = smp.sample_keys(_np.arange(b, dtype=_np.uint64) + 9)

        def model(xx, s, **kw):
            # nonlinear, sigma-dependent denoiser: exposes wrong-step
            # bugs an ideal (constant) model hides
            return jnp.tanh(xx) * (1.0 / (1.0 + s))

        return sigmas, jnp.asarray(x), keys, model

    @staticmethod
    def _anc(s, s_next, eta=1.0):
        import math
        su = min(s_next, eta * math.sqrt(
            max(s_next ** 2 * (s ** 2 - s_next ** 2) / s ** 2, 0.0)))
        sd = math.sqrt(max(s_next ** 2 - su ** 2, 0.0))
        return sd, su

    def test_dpmpp_sde_matches_loop(self, ds):
        import math
        sigmas, x0, keys, model = self._setup(ds)
        out = smp.sample_dpmpp_sde(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)), keys=keys)
        noise_fn = smp.make_noise_fn(keys)
        x = np.asarray(x0, np.float64)
        r, fac = 0.5, 1.0
        for i in range(len(sigmas) - 1):
            s, s_next = sigmas[i], sigmas[i + 1]
            den = np.asarray(model(jnp.asarray(x, jnp.float32), s),
                             np.float64)
            if s_next == 0:
                x = x + (x - den) / s * (s_next - s)
                continue
            t = -math.log(s)
            h = -math.log(s_next) - t
            s_mid = math.exp(-(t + h * r))
            sd1, su1 = self._anc(s, s_mid)
            x2 = (sd1 / s) * (x - den) + den \
                + np.asarray(noise_fn(2 * i, x.shape[1:]), np.float64) * su1
            den2 = np.asarray(model(jnp.asarray(x2, jnp.float32), s_mid),
                              np.float64)
            sd2, su2 = self._anc(s, s_next)
            dd = (1 - fac) * den + fac * den2
            x = (sd2 / s) * (x - dd) + dd \
                + np.asarray(noise_fn(2 * i + 1, x.shape[1:]),
                             np.float64) * su2
        np.testing.assert_allclose(np.asarray(out), x, rtol=2e-4,
                                   atol=2e-4)

    def test_dpmpp_3m_sde_matches_loop(self, ds):
        import math
        sigmas, x0, keys, model = self._setup(ds, steps=9)
        out = smp.sample_dpmpp_3m_sde(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)), keys=keys)
        noise_fn = smp.make_noise_fn(keys)
        x = np.asarray(x0, np.float64)
        eta = 1.0
        den_1 = den_2 = None
        h_1 = h_2 = None
        for i in range(len(sigmas) - 1):
            s, s_next = sigmas[i], sigmas[i + 1]
            den = np.asarray(model(jnp.asarray(x, jnp.float32), s),
                             np.float64)
            if s_next == 0:
                x = den
                continue
            h = math.log(s) - math.log(s_next)
            h_eta = h * (eta + 1.0)
            x = math.exp(-h_eta) * x - math.expm1(-h_eta) * den
            phi_2 = math.expm1(-h_eta) / h_eta + 1.0
            if h_2 is not None:
                r0, r1 = h_1 / h, h_2 / h
                d1_0 = (den - den_1) / r0
                d1_1 = (den_1 - den_2) / r1
                d1 = d1_0 + (d1_0 - d1_1) * r0 / (r0 + r1)
                d2 = (d1_0 - d1_1) / (r0 + r1)
                phi_3 = phi_2 / h_eta - 0.5
                x = x + phi_2 * d1 - phi_3 * d2
            elif h_1 is not None:
                x = x + phi_2 * ((den - den_1) / (h_1 / h))
            amt = s_next * math.sqrt(max(-math.expm1(-2 * h * eta), 0.0))
            x = x + np.asarray(noise_fn(i, x.shape[1:]), np.float64) * amt
            den_1, den_2 = den, den_1
            h_1, h_2 = h, h_1
        np.testing.assert_allclose(np.asarray(out), x, rtol=2e-4,
                                   atol=2e-4)

    def test_lms_matches_scipy_quad_loop(self, ds):
        from scipy import integrate
        sigmas, x0, keys, model = self._setup(ds, steps=8)
        out = smp.sample_lms(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)))

        def coeff(order, t, i, j):
            def fn(tau):
                prod = 1.0
                for k in range(order):
                    if j == k:
                        continue
                    prod *= (tau - t[i - k]) / (t[i - j] - t[i - k])
                return prod
            return integrate.quad(fn, t[i], t[i + 1], epsrel=1e-6)[0]

        x = np.asarray(x0, np.float64)
        dhist = []
        for i in range(len(sigmas) - 1):
            den = np.asarray(model(jnp.asarray(x, jnp.float32), sigmas[i]),
                             np.float64)
            d = (x - den) / sigmas[i]
            dhist.append(d)
            if len(dhist) > 4:
                dhist.pop(0)
            cur = min(i + 1, 4)
            cs = [coeff(cur, sigmas, i, j) for j in range(cur)]
            x = x + sum(c * dd for c, dd in zip(cs, reversed(dhist)))
        np.testing.assert_allclose(np.asarray(out), x, rtol=2e-4,
                                   atol=2e-4)

    @staticmethod
    def _unipc_loop(sigmas, x0, model, variant):
        """Per-step Python UniPC loop with numpy solves (order ramp at
        both ends, corrector-eval reuse, predictor-only final step on a
        window ending above sigma 0)."""
        import math

        def m_of(xx, s):
            return np.asarray(model(jnp.asarray(xx, jnp.float32), s),
                              np.float64)

        n = len(sigmas) - 1
        x = np.asarray(x0, np.float64)
        m_list = [m_of(x, sigmas[0])]          # priming call
        for i in range(n):
            s, s_next = sigmas[i], sigmas[i + 1]
            m0 = m_list[-1]
            if s_next == 0:
                x = m0
                continue
            last_nonzero = i == n - 1          # window ending above 0
            order = min(i + 1, 3, n - i)
            lam0, lam_t = -math.log(s), -math.log(s_next)
            h = lam_t - lam0
            hh = -h
            h_phi_1 = math.expm1(hh)
            B_h = hh if variant == "bh1" else math.expm1(hh)
            rks, d1s = [], []
            for k in range(1, order):
                lam_k = -math.log(sigmas[i - k])
                rk = (lam_k - lam0) / h
                rks.append(rk)
                d1s.append((m_list[-1 - k] - m0) / rk)
            rks.append(1.0)
            b, h_phi_k, fact = [], h_phi_1 / hh - 1.0, 1.0
            for j in range(1, order + 1):
                b.append(h_phi_k * fact / B_h)
                fact *= j + 1
                h_phi_k = h_phi_k / hh - 1.0 / fact
            R = np.vander(np.asarray(rks), order, increasing=True).T
            x_t_ = (s_next / s) * x - h_phi_1 * m0
            if order == 1:
                x_pred = x_t_
            elif order == 2:
                x_pred = x_t_ - B_h * (0.5 * d1s[0])
            else:
                rhos_p = np.linalg.solve(R[:-1, :-1], np.asarray(b[:-1]))
                x_pred = x_t_ - B_h * sum(
                    rhos_p[k] * d1s[k] for k in range(order - 1))
            if last_nonzero:
                # reference: use_corrector=False on the last step of a
                # window ending above sigma 0 (predictor-only)
                x = x_pred
                continue
            m_t = m_of(x_pred, s_next)
            d1_t = m_t - m0
            if order == 1:
                corr = 0.5 * d1_t
            else:
                rhos_c = np.linalg.solve(R, np.asarray(b))
                corr = rhos_c[-1] * d1_t + sum(
                    rhos_c[k] * d1s[k] for k in range(order - 1))
            x = x_t_ - B_h * corr
            m_list.append(m_t)
        return x

    @pytest.mark.parametrize("variant", ["bh1", "bh2"])
    def test_uni_pc_matches_loop(self, ds, variant):
        """UniPC vs the Python loop oracle on a full schedule (ends at
        sigma 0)."""
        sigmas, x0, keys, model = self._setup(ds, steps=8)
        name = "uni_pc" if variant == "bh1" else "uni_pc_bh2"
        out = smp.get_sampler(name)(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)))
        ref = self._unipc_loop(sigmas, x0, model, variant)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-4,
                                   atol=3e-4)

    @pytest.mark.parametrize("variant", ["bh1", "bh2"])
    def test_uni_pc_truncated_window_matches_loop(self, ds, variant):
        """A schedule ending ABOVE sigma 0 (img2img-style window): the
        last update must be predictor-only (reference use_corrector=False
        on the final step)."""
        sigmas_full, x0, keys, model = self._setup(ds, steps=7)
        sigmas = sigmas_full[:-1]              # drop the trailing 0
        name = "uni_pc" if variant == "bh1" else "uni_pc_bh2"
        out = smp.get_sampler(name)(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)))
        ref = self._unipc_loop(sigmas, x0, model, variant)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-4,
                                   atol=3e-4)


class TestMultiCondCFG:
    """cfg_denoiser_multi (regional prompting): mask-weighted blend of
    per-entry denoised predictions before the CFG combine."""

    @staticmethod
    def _model():
        def model(x, sigma, context=None):
            per_row = jnp.mean(context, axis=(1, 2)).reshape(-1, 1, 1, 1)
            return jnp.ones_like(x) * per_row
        return model

    def test_mask_blend_and_cfg(self):
        B, h, w = 1, 4, 4
        cond_a = jnp.full((B, 7, 8), 1.0)
        cond_b = jnp.full((B, 7, 8), 3.0)
        unc = jnp.zeros((B, 7, 8))
        mask_a = jnp.zeros((1, h, w, 1)).at[:, :, :2].set(1.0)
        mask_b = 1.0 - mask_a
        f = smp.cfg_denoiser_multi(
            self._model(), [(cond_a, mask_a, 1.0), (cond_b, mask_b, 1.0)],
            unc, 2.0)
        out = np.asarray(f(jnp.zeros((B, h, w, 3)), jnp.asarray(1.0)))
        # left half: den_cond=1 -> 0 + (1-0)*2 = 2; right: 3 -> 6
        np.testing.assert_allclose(out[:, :, :2], 2.0, atol=1e-5)
        np.testing.assert_allclose(out[:, :, 2:], 6.0, atol=1e-5)

    def test_strengths_weight_overlap(self):
        """Overlapping masks: weighted mean by strength*mask."""
        B, h, w = 1, 2, 2
        cond_a = jnp.full((B, 7, 8), 2.0)
        cond_b = jnp.full((B, 7, 8), 6.0)
        unc = jnp.zeros((B, 7, 8))
        f = smp.cfg_denoiser_multi(
            self._model(), [(cond_a, None, 3.0), (cond_b, None, 1.0)],
            unc, 1.0)   # cfg=1: pure cond blend, no uncond row
        out = np.asarray(f(jnp.zeros((B, h, w, 3)), jnp.asarray(1.0)))
        np.testing.assert_allclose(out, (3 * 2 + 1 * 6) / 4.0, atol=1e-5)

    def test_single_entry_equals_plain_cfg(self):
        B, h, w = 2, 4, 4
        cond = jnp.full((B, 7, 8), 1.5)
        unc = jnp.zeros((B, 7, 8))
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (B, h, w, 3)).astype(np.float32))
        a = smp.cfg_denoiser(self._model(), cond, unc, 3.0)(
            x, jnp.asarray(1.0))
        b = smp.cfg_denoiser_multi(self._model(), [(cond, None, 1.0)],
                                   unc, 3.0)(x, jnp.asarray(1.0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_timestep_range_gates_entries(self, ds):
        """ComfyUI prompt scheduling: an entry contributes only while
        sigma is inside its range; outside it the other entry takes
        over completely."""
        def model(x, sigma, context=None):
            per_row = jnp.mean(context, axis=(1, 2)).reshape(-1, 1, 1, 1)
            return jnp.ones_like(x) * per_row

        cond_a = jnp.full((1, 7, 8), 1.0)
        cond_b = jnp.full((1, 7, 8), 3.0)
        unc = jnp.zeros((1, 7, 8))
        # a active for sigma in [5, inf); b active for sigma in [0, 5]
        f = smp.cfg_denoiser_multi(
            model, [(cond_a, None, 1.0, (1e9, 5.0)),
                    (cond_b, None, 1.0, (5.0, 0.0))], unc, 1.0)
        hi = np.asarray(f(jnp.zeros((1, 2, 2, 3)), jnp.asarray(9.0)))
        lo = np.asarray(f(jnp.zeros((1, 2, 2, 3)), jnp.asarray(1.0)))
        np.testing.assert_allclose(hi, 1.0, atol=1e-5)   # only a
        np.testing.assert_allclose(lo, 3.0, atol=1e-5)   # only b
        # at the boundary both are active: equal-weight mean
        mid = np.asarray(f(jnp.zeros((1, 2, 2, 3)), jnp.asarray(5.0)))
        np.testing.assert_allclose(mid, 2.0, atol=1e-5)


class TestDdpmIpndmOracles:
    _setup = TestLoopOracles._setup   # shared fixture-free helper
    def test_ddpm_matches_loop(self, ds):
        import math
        sigmas, x0, keys, model = self._setup(ds, steps=7)
        out = smp.sample_ddpm(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)), keys=keys)
        noise_fn = smp.make_noise_fn(keys)
        x = np.asarray(x0, np.float64)
        for i in range(len(sigmas) - 1):
            s, s_next = sigmas[i], sigmas[i + 1]
            den = np.asarray(model(jnp.asarray(x, jnp.float32), s),
                             np.float64)
            eps = (x - den) / s
            xs = x / math.sqrt(1.0 + s * s)
            ac = 1.0 / (s * s + 1.0)
            ac_prev = 1.0 / (s_next * s_next + 1.0)
            alpha = ac / ac_prev
            mu = math.sqrt(1.0 / alpha) * (
                xs - (1.0 - alpha) * eps / math.sqrt(1.0 - ac))
            if s_next > 0:
                std = math.sqrt((1.0 - alpha) * (1.0 - ac_prev)
                                / (1.0 - ac))
                mu = mu + np.asarray(noise_fn(i, x.shape[1:]),
                                     np.float64) * std
                x = mu * math.sqrt(1.0 + s_next * s_next)
            else:
                x = mu
        np.testing.assert_allclose(np.asarray(out), x, rtol=2e-4,
                                   atol=2e-4)

    def test_ipndm_matches_loop(self, ds):
        sigmas, x0, keys, model = self._setup(ds, steps=8)
        out = smp.sample_ipndm(model, x0, jnp.asarray(
            np.asarray(sigmas, np.float32)))
        coeffs = ((1.0,), (3 / 2, -1 / 2), (23 / 12, -16 / 12, 5 / 12),
                  (55 / 24, -59 / 24, 37 / 24, -9 / 24))
        x = np.asarray(x0, np.float64)
        hist = []
        for i in range(len(sigmas) - 1):
            s, s_next = sigmas[i], sigmas[i + 1]
            den = np.asarray(model(jnp.asarray(x, jnp.float32), s),
                             np.float64)
            d = (x - den) / s
            order = min(i + 1, 4)
            cs = coeffs[order - 1]
            upd = cs[0] * d
            for k in range(1, order):
                upd = upd + cs[k] * hist[-k]
            x = x + (s_next - s) * upd
            hist.append(d)
        np.testing.assert_allclose(np.asarray(out), x, rtol=2e-4,
                                   atol=2e-4)

    def test_rescale_cfg_math(self):
        """RescaleCFG vs a direct numpy port of the reference patch;
        multiplier=0 must equal plain CFG exactly."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
        dc = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
        du = jnp.asarray(rng.standard_normal((2, 4, 4, 3)), jnp.float32)
        sigma, scale, mult = 3.0, 7.0, 0.6
        out = np.asarray(smp._rescale_cfg(x, jnp.asarray(sigma), dc, du,
                                          scale, mult))
        xn, dcn, dun = (np.asarray(a, np.float64) for a in (x, dc, du))
        s2 = sigma * sigma
        xs = xn / (s2 + 1.0)
        root = np.sqrt(s2 + 1.0)
        v_c = (xs - (xn - dcn)) * root / sigma
        v_u = (xs - (xn - dun)) * root / sigma
        v_cfg = v_u + (v_c - v_u) * scale
        ro_pos = v_c.std(axis=(1, 2, 3), keepdims=True)
        ro_cfg = v_cfg.std(axis=(1, 2, 3), keepdims=True)
        v_fin = mult * (v_cfg * ro_pos / ro_cfg) + (1 - mult) * v_cfg
        ref = xn - (xs - v_fin * sigma / root)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        # multiplier path off == plain CFG
        cond = jnp.full((2, 7, 8), 1.5)
        unc = jnp.zeros((2, 7, 8))

        def model(xx, s, context=None):
            per = jnp.mean(context, axis=(1, 2)).reshape(-1, 1, 1, 1)
            return xx * 0.1 + per

        a = smp.cfg_denoiser_multi(model, [(cond, None, 1.0)], unc, scale,
                                   cfg_rescale=0.0)(x, jnp.asarray(sigma))
        b = smp.cfg_denoiser(model, cond, unc, scale)(x, jnp.asarray(sigma))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestNewSamplersRound4:
    """heunpp2 / ipndm_v / deis / dpm_fast / dpm_adaptive specifics
    beyond the all-sampler parametrized suites."""

    def test_ab_vs_coeffs_order2_closed_form(self):
        """Variable-step AB order-2 weights must equal the classic
        step-ratio formula c0=(2+r)/2, c1=-r/2 with r=h_n/h_{n-1}."""
        t_prev, t_cur, t_next = 10.0, 6.0, 3.0    # descending sigmas
        h_n = t_next - t_cur
        h_p = t_cur - t_prev
        c = smp._ab_vs_coeffs([jnp.float32(t_cur), jnp.float32(t_prev)],
                              jnp.float32(t_cur), jnp.float32(t_next))
        r = h_n / h_p
        np.testing.assert_allclose(float(c[0]), (2 + r) / 2, rtol=1e-6)
        np.testing.assert_allclose(float(c[1]), -r / 2, rtol=1e-6)

    def test_ab_vs_uniform_reduces_to_ipndm_table(self):
        """On a uniform grid the variable-step weights collapse to the
        classic Adams-Bashforth table (_IPNDM_COEFFS)."""
        ts = [jnp.float32(v) for v in (4.0, 5.0, 6.0, 7.0)]  # newest first
        c = smp._ab_vs_coeffs(ts, jnp.float32(4.0), jnp.float32(3.0))
        np.testing.assert_allclose([float(v) for v in c],
                                   smp._IPNDM_COEFFS[3], rtol=1e-5)
        # and ipndm_v == ipndm exactly on a uniform schedule
        x0 = jnp.full((1, 4, 4, 2), 0.4, jnp.float32)
        sigmas = jnp.linspace(8.0, 0.0, 9)
        x = jnp.ones_like(x0) * sigmas[0]
        a = smp.sample_ipndm(ideal_model(x0), x, sigmas)
        b = smp.sample_ipndm_v(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_heunpp2_final_step_is_euler(self, ds):
        """A 1-step schedule must reduce heunpp2 to plain Euler."""
        x0 = jnp.full((1, 4, 4, 2), 0.3, jnp.float32)
        sigmas = jnp.asarray([5.0, 0.0], jnp.float32)
        x = jnp.ones_like(x0) * 5.0
        a = smp.sample_heunpp2(ideal_model(x0), x, sigmas)
        b = smp.sample_euler(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_dpm_fast_exact_on_linear_ode(self, ds):
        """Ideal denoiser: the trajectory is exactly x0 + sigma*c;
        DPM-Solver's expm1 updates integrate that ODE EXACTLY at every
        order, so dpm_fast must land on x0 + sigma_min*c to fp32."""
        x0 = jnp.zeros((1, 4, 4, 2), jnp.float32)
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 7))
        c = 1.0 / float(sigmas[0])
        x = jnp.ones_like(x0) * sigmas[0] * c
        out = smp.sample_dpm_fast(ideal_model(x0), x, sigmas)
        sig_min = float(sigmas[-2])
        np.testing.assert_allclose(np.asarray(out),
                                   np.full_like(np.asarray(out),
                                                sig_min * c),
                                   rtol=1e-4, atol=1e-5)

    def test_dpm_adaptive_converges_and_bounds_iters(self, ds):
        calls = []

        def counting_model(x, sigma, **kw):
            def cb(_):
                calls.append(1)
                return np.float32(0.0)
            z = jax.pure_callback(cb, jax.ShapeDtypeStruct((), np.float32),
                                  x.reshape(-1)[0])
            return jnp.zeros_like(x) + z

        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 10))
        x = jnp.ones((1, 4, 4, 2), jnp.float32) * sigmas[0]
        out = smp.sample_dpm_adaptive(counting_model, x, sigmas)
        assert np.all(np.abs(np.asarray(out)) < 0.12)
        assert 0 < len(calls) < 3 * 512   # PID accepted its way through

    def test_deis_three_history_converges_tight(self, ds):
        x0 = jnp.full((2, 4, 4, 3), -0.2, jnp.float32)
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 10))
        x = jnp.zeros_like(x0) + sigmas[0]
        out = smp.sample_deis(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0),
                                   atol=1e-3)


class TestSchedulerNodesRound4:
    """Scheduler node suite: Exponential/Polyexponential/VP/Laplace/
    Beta/AYS/SDTurbo + SplitSigmasDenoise."""

    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def _ctx(self):
        from comfyui_distributed_tpu.ops.base import OpContext
        return OpContext()

    def test_exponential_and_poly(self):
        octx = self._ctx()
        (e,) = self._op("ExponentialScheduler").execute(octx, 8, 10.0,
                                                        0.1)
        assert e.shape == (9,) and e[-1] == 0.0
        np.testing.assert_allclose(e[0], 10.0, rtol=1e-5)
        np.testing.assert_allclose(e[-2], 0.1, rtol=1e-5)
        # exponential == polyexponential at rho=1; rho=2 bends the ramp
        (p1,) = self._op("PolyexponentialScheduler").execute(
            octx, 8, 10.0, 0.1, 1.0)
        np.testing.assert_array_equal(e, p1)
        (p2,) = self._op("PolyexponentialScheduler").execute(
            octx, 8, 10.0, 0.1, 2.0)
        assert p2[4] < p1[4]        # rho>1 front-loads low sigmas
        # exact log-linear ramp: e[i] = exp(lerp(log 10, log 0.1, i/7))
        expect = np.exp(np.linspace(np.log(10.0), np.log(0.1), 8))
        np.testing.assert_allclose(e[:-1], expect, rtol=1e-5)

    def test_vp_and_laplace(self):
        octx = self._ctx()
        (v,) = self._op("VPScheduler").execute(octx, 10, 19.9, 0.1,
                                               0.001)
        assert v.shape == (11,) and v[-1] == 0.0
        assert np.all(np.diff(v[:-1]) < 0)
        (la,) = self._op("LaplaceScheduler").execute(octx, 10, 14.6,
                                                     0.03, 0.0, 0.5)
        assert la.shape == (11,) and la[-1] == 0.0
        assert la[0] <= 14.6 and la[-2] >= 0.03

    def test_beta_node_matches_scheduler(self, ds):
        octx = self._ctx()

        class _M:
            schedule = ds
        (b,) = self._op("BetaSamplingScheduler").execute(octx, _M(), 9,
                                                         0.6, 0.6)
        np.testing.assert_array_equal(
            b, np.asarray(sch.beta_scheduler(ds, 9, 0.6, 0.6),
                          np.float32))

    def test_ays_tables_and_denoise(self):
        octx = self._ctx()
        (s10,) = self._op("AlignYourStepsScheduler").execute(octx, "SD1",
                                                             10, 1.0)
        np.testing.assert_allclose(
            s10[:-1], sch.AYS_TABLES["SD1"][:-1], rtol=1e-5)
        assert s10[-1] == 0.0
        (s20,) = self._op("AlignYourStepsScheduler").execute(octx,
                                                             "SDXL", 20,
                                                             1.0)
        assert s20.shape == (21,)
        assert np.all(np.diff(s20[:-1]) < 0)
        (half,) = self._op("AlignYourStepsScheduler").execute(octx,
                                                              "SD1", 10,
                                                              0.5)
        assert half.shape == (6,)
        np.testing.assert_allclose(half[:-1], s10[5:-1], rtol=1e-6)
        with pytest.raises(ValueError):
            self._op("AlignYourStepsScheduler").execute(octx, "nope", 10,
                                                        1.0)

    def test_sd_turbo(self, ds):
        octx = self._ctx()

        class _M:
            schedule = ds
        (s1,) = self._op("SDTurboScheduler").execute(octx, _M(), 1, 1.0)
        assert s1.shape == (2,) and s1[-1] == 0.0
        np.testing.assert_allclose(s1[0], ds.sigmas[999], rtol=1e-6)
        (s4,) = self._op("SDTurboScheduler").execute(octx, _M(), 4, 1.0)
        assert s4.shape == (5,)
        np.testing.assert_allclose(
            s4[:-1], ds.sigmas[[999, 899, 799, 699]], rtol=1e-6)
        # denoise 0.5: starts mid-schedule (img2img for turbo)
        (sd,) = self._op("SDTurboScheduler").execute(octx, _M(), 2, 0.5)
        np.testing.assert_allclose(sd[0], ds.sigmas[499], rtol=1e-6)

    def test_split_sigmas_denoise(self):
        octx = self._ctx()
        sig = np.asarray([10, 8, 6, 4, 2, 0], np.float32)
        hi, lo = self._op("SplitSigmasDenoise").execute(octx, sig, 0.4)
        assert lo.shape == (3,)          # 2 of 5 steps kept
        np.testing.assert_array_equal(lo, sig[3:])
        np.testing.assert_array_equal(hi, sig[:4])
        hi1, lo1 = self._op("SplitSigmasDenoise").execute(octx, sig, 1.0)
        np.testing.assert_array_equal(lo1, sig)


class TestLatentArithmeticNodes:
    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def test_add_subtract_multiply_interpolate(self):
        from comfyui_distributed_tpu.ops.base import OpContext
        octx = OpContext()
        a = {"samples": np.full((1, 4, 4, 4), 2.0, np.float32),
             "fanout": 1, "local_batch": 1}
        b = {"samples": np.full((1, 4, 4, 4), 0.5, np.float32)}
        (add,) = self._op("LatentAdd").execute(octx, a, b)
        np.testing.assert_allclose(add["samples"], 2.5)
        (sub,) = self._op("LatentSubtract").execute(octx, a, b)
        np.testing.assert_allclose(sub["samples"], 1.5)
        (mul,) = self._op("LatentMultiply").execute(octx, a, 0.25)
        np.testing.assert_allclose(mul["samples"], 0.5)
        # interpolate: ratio 1 -> exactly a; ratio 0 -> exactly b
        (i1,) = self._op("LatentInterpolate").execute(octx, a, b, 1.0)
        np.testing.assert_allclose(i1["samples"], 2.0, rtol=1e-5)
        (i0,) = self._op("LatentInterpolate").execute(octx, a, b, 0.0)
        np.testing.assert_allclose(i0["samples"], 0.5, rtol=1e-5)
        # parallel directions: magnitudes lerp
        (ih,) = self._op("LatentInterpolate").execute(octx, a, b, 0.5)
        np.testing.assert_allclose(ih["samples"], 1.25, rtol=1e-5)


class TestCFGPlusPlus:
    def test_reduces_to_euler_without_cfg_wrapper(self, ds):
        """A bare model has no uncond side-channel: CFG++ falls back to
        the denoised anchor and the update equals plain euler exactly
        (x' = den + s_next*(x-den)/s == x + d*(s_next - s))."""
        x0 = jnp.full((1, 4, 4, 2), 0.3, jnp.float32)
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 6))
        x = jnp.ones_like(x0) * sigmas[0]
        a = smp.sample_euler_cfg_pp(ideal_model(x0), x, sigmas)
        b = smp.sample_euler(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_uses_the_uncond_direction_under_cfg(self, ds):
        """With a CFG wrapper whose cond and uncond denoise to different
        targets, the step direction must come from the UNCOND (the
        reference's post-cfg uncond_denoised), not the CFG result."""
        cond_t = jnp.full((1, 4, 4, 2), 0.5, jnp.float32)
        unc_t = jnp.full((1, 4, 4, 2), -0.5, jnp.float32)

        def raw(x, sigma, context=None, **kw):
            # rows: [cond; uncond] — pretend contexts select targets
            B = x.shape[0] // 2
            return jnp.concatenate(
                [jnp.broadcast_to(cond_t, (B,) + cond_t.shape[1:]),
                 jnp.broadcast_to(unc_t, (B,) + unc_t.shape[1:])])

        cfg = smp.cfg_denoiser(raw, jnp.zeros((1, 7, 8)),
                               jnp.zeros((1, 7, 8)), 3.0)
        sigmas = jnp.asarray([4.0, 2.0], jnp.float32)
        x = jnp.zeros((1, 4, 4, 2), jnp.float32) + 4.0
        out = smp.sample_euler_cfg_pp(cfg, x, sigmas)
        den = np.asarray(unc_t + (cond_t - unc_t) * 3.0)  # CFG result
        expect = den + (np.asarray(x) - np.asarray(unc_t)) / 4.0 * 2.0
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)

    def test_ancestral_variant_stochastic_contract(self, ds):
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 4))
        x = jnp.zeros((1, 2, 2, 1))
        with pytest.raises(ValueError):
            smp.sample_euler_ancestral_cfg_pp(ideal_model(x), x, sigmas)


class TestCFGPlusPlusGuiderCoverage:
    def test_ancestral_eta0_equals_euler_cfg_pp(self, ds):
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 6))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(1,
                                                       dtype=jnp.uint32))
        x0 = jnp.full((1, 4, 4, 2), 0.4, jnp.float32)
        x = jnp.ones_like(x0) * sigmas[0]
        a = smp.sample_euler_ancestral_cfg_pp(ideal_model(x0), x,
                                              sigmas, keys=keys,
                                              eta=0.0)
        b = smp.sample_euler_cfg_pp(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_dual_and_perp_wrappers_expose_uncond(self):
        cond_t = jnp.full((1, 4, 4, 2), 0.5, jnp.float32)
        unc_t = jnp.full((1, 4, 4, 2), -0.5, jnp.float32)

        def raw3(x, sigma, context=None, **kw):
            B = x.shape[0] // 3
            t = lambda v: jnp.broadcast_to(v, (B,) + v.shape[1:])  # noqa
            return jnp.concatenate([t(cond_t), t(jnp.zeros_like(cond_t)),
                                    t(unc_t)])

        c = jnp.zeros((1, 7, 8))
        dual = smp.cfg_denoiser_dual(raw3, c, c, c, 2.0, 1.5)
        dual(jnp.zeros((1, 4, 4, 2)), jnp.asarray(3.0))
        np.testing.assert_allclose(np.asarray(dual.last_uncond),
                                   np.asarray(unc_t))
        perp = smp.cfg_denoiser_perp_neg(raw3, c, c, c, 2.0, 1.0)
        perp(jnp.zeros((1, 4, 4, 2)), jnp.asarray(3.0))
        np.testing.assert_allclose(np.asarray(perp.last_uncond),
                                   np.asarray(unc_t))


class TestRound5SamplerLongTail:
    """res_multistep / gradient_estimation / er_sde / sa_solver /
    seeds_2 / seeds_3 (VERDICT r4 #7) — behavioral contracts beyond the
    all-sampler parametrized suites."""

    def _setup(self, ds, steps=8, b=2):
        x0 = jnp.asarray(np.random.default_rng(9).standard_normal(
            (b, 4, 4, 2)).astype(np.float32)) * 0.4
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", steps))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(b, dtype=jnp.uint32))
        x = jax.random.normal(jax.random.PRNGKey(2), x0.shape) * sigmas[0]
        return x0, sigmas, keys, x

    def test_gradient_estimation_equals_euler_for_ideal_model(self, ds):
        """For a constant-x0 denoiser the step directions coincide, so
        the gamma-extrapolation is exact and the trajectory IS euler."""
        x0, sigmas, keys, x = self._setup(ds)
        a = smp.sample_gradient_estimation(ideal_model(x0), x, sigmas)
        b = smp.sample_euler(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_res_multistep_second_order_beats_euler(self, ds):
        """On a sigma-curved denoiser (denoised bends with sigma) the
        2nd-order multistep lands closer to the true limit than euler at
        the same step count."""
        x0 = jnp.full((1, 4, 4, 2), 0.5, jnp.float32)

        def curved(x, sigma, **kw):
            s = jnp.reshape(sigma, (-1,) + (1,) * (x.ndim - 1))
            return x0 * (1.0 + 0.3 * jnp.tanh(s))

        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 6))
        x = jnp.ones_like(x0) * sigmas[0]
        # the true sigma->0 limit of the curved target is x0
        err_res = np.abs(np.asarray(
            smp.sample_res_multistep(curved, x, sigmas)) - 0.5).max()
        err_euler = np.abs(np.asarray(
            smp.sample_euler(curved, x, sigmas)) - 0.5).max()
        assert err_res <= err_euler + 1e-6, (err_res, err_euler)

    def test_sa_solver_corrector_beats_predictor_only(self, ds):
        """The PECE corrector evaluation must tighten the same curved
        trajectory vs the predictor-only res_multistep path."""
        x0 = jnp.full((1, 4, 4, 2), 0.5, jnp.float32)

        def curved(x, sigma, **kw):
            s = jnp.reshape(sigma, (-1,) + (1,) * (x.ndim - 1))
            return x0 * (1.0 + 0.3 * jnp.tanh(s))

        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 6))
        x = jnp.ones_like(x0) * sigmas[0]
        err_sa = np.abs(np.asarray(
            smp.sample_sa_solver(curved, x, sigmas)) - 0.5).max()
        err_res = np.abs(np.asarray(
            smp.sample_res_multistep(curved, x, sigmas)) - 0.5).max()
        assert err_sa <= err_res + 1e-6, (err_sa, err_res)

    @pytest.mark.parametrize("name", ["seeds_2", "seeds_3", "er_sde"])
    def test_stochastic_deterministic_given_keys(self, ds, name):
        x0, sigmas, keys, x = self._setup(ds)
        fn = smp.get_sampler(name)
        a = fn(ideal_model(x0), x, sigmas, keys=keys)
        b = fn(ideal_model(x0), x, sigmas, keys=keys)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("name", ["seeds_2", "seeds_3"])
    def test_seeds_eta_zero_is_deterministic_no_keys(self, ds, name):
        """eta=0 degenerates to the deterministic exponential RK — no
        keys needed, and repeated runs are bit-identical."""
        x0, sigmas, _, x = self._setup(ds)
        fn = smp.get_sampler(name)
        a = fn(ideal_model(x0), x, sigmas, eta=0.0)
        b = fn(ideal_model(x0), x, sigmas, eta=0.0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(x0),
                                   atol=1e-3)

    @pytest.mark.parametrize("name", ["seeds_2", "seeds_3", "er_sde"])
    def test_distinct_keys_distinct_trajectories(self, ds, name):
        """Per-sample noise streams: different keys diverge mid-run
        (stopped before sigma 0 so the noise isn't annihilated)."""
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 8))[:5]
        keys_a = jax.vmap(jax.random.PRNGKey)(jnp.asarray([1, 2],
                                                          jnp.uint32))
        keys_b = jax.vmap(jax.random.PRNGKey)(jnp.asarray([3, 4],
                                                          jnp.uint32))
        x = jnp.zeros((2, 4, 4, 1)) + sigmas[0]
        x0 = jnp.zeros((2, 4, 4, 1))
        fn = smp.get_sampler(name)
        a = fn(ideal_model(x0), x, sigmas, keys=keys_a)
        b = fn(ideal_model(x0), x, sigmas, keys=keys_b)
        assert not np.allclose(np.asarray(a), np.asarray(b))

    def test_ksampler_runs_the_long_tail_end_to_end(self, ds):
        """The registry path (static-key jit cache, CFG wrapper, noise
        plumbing) accepts every new sampler name."""
        from comfyui_distributed_tpu.models import registry
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        import os
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
        try:
            pipe = registry.load_pipeline("longtail.ckpt")
            pos = Conditioning(context=pipe.encode_prompt(["x"])[0])
            lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
            for name in ("res_multistep", "gradient_estimation", "er_sde",
                         "sa_solver", "seeds_2", "seeds_3"):
                (out,) = get_op("KSampler").execute(
                    OpContext(), pipe, 3, 2, 3.0, name, "normal", pos,
                    pos, lat, 1.0)
                assert np.isfinite(np.asarray(out["samples"])).all(), name
        finally:
            os.environ.pop("DTPU_DEFAULT_FAMILY", None)
            registry.clear_pipeline_cache()


class TestCfgPpLongTailVariants:
    """res_multistep_cfg_pp / _ancestral(_cfg_pp) / dpmpp_2m_cfg_pp:
    exact reductions + the uncond side-channel engaging."""

    def _x(self, ds, steps=8):
        x0 = jnp.full((1, 4, 4, 2), 0.4, jnp.float32)
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", steps))
        x = jnp.ones_like(x0) * sigmas[0]
        return x0, sigmas, x

    def test_cfg_pp_variants_reduce_to_plain_for_bare_model(self, ds):
        x0, sigmas, x = self._x(ds)
        a = smp.sample_res_multistep_cfg_pp(ideal_model(x0), x, sigmas)
        b = smp.sample_res_multistep(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
        c = smp.sample_dpmpp_2m_cfg_pp(ideal_model(x0), x, sigmas)
        d = smp.sample_dpmpp_2m(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(c), np.asarray(d),
                                   rtol=1e-5, atol=1e-6)

    def test_ancestral_eta_zero_equals_deterministic(self, ds):
        x0, sigmas, x = self._x(ds)
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(1, dtype=jnp.uint32))
        a = smp.sample_res_multistep_ancestral(ideal_model(x0), x,
                                               sigmas, keys=keys, eta=0.0)
        b = smp.sample_res_multistep(ideal_model(x0), x, sigmas)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_cfg_pp_reads_the_uncond_side_channel(self, ds):
        """Under a CFG wrapper with distinct cond/uncond targets the
        CFG++ variant departs from the plain sampler."""
        cond_t = jnp.full((1, 4, 4, 2), 0.5, jnp.float32)
        unc_t = jnp.full((1, 4, 4, 2), -0.5, jnp.float32)

        def raw(x, sigma, context=None, **kw):
            B = x.shape[0] // 2
            return jnp.concatenate(
                [jnp.broadcast_to(cond_t, (B,) + cond_t.shape[1:]),
                 jnp.broadcast_to(unc_t, (B,) + unc_t.shape[1:])])

        cfg = smp.cfg_denoiser(raw, jnp.zeros((1, 7, 8)),
                               jnp.zeros((1, 7, 8)), 3.0)
        # STOP at a nonzero sigma: the stub denoises to a constant, so
        # the final x=denoised step would erase the trajectory split
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "karras", 6))[:4]
        x = jnp.zeros((1, 4, 4, 2), jnp.float32) + sigmas[0]
        for pp, plain in ((smp.sample_res_multistep_cfg_pp,
                           smp.sample_res_multistep),
                          (smp.sample_dpmpp_2m_cfg_pp,
                           smp.sample_dpmpp_2m)):
            a = pp(cfg, x, sigmas)
            b = plain(cfg, x, sigmas)
            assert not np.allclose(np.asarray(a), np.asarray(b)), pp

    def test_ancestral_keyed_noise_contract(self, ds):
        sigmas = jnp.asarray(sch.compute_sigmas(ds, "normal", 8))[:5]
        ka = jax.vmap(jax.random.PRNGKey)(jnp.asarray([1, 2], jnp.uint32))
        kb = jax.vmap(jax.random.PRNGKey)(jnp.asarray([3, 4], jnp.uint32))
        x = jnp.zeros((2, 4, 4, 1)) + sigmas[0]
        x0 = jnp.zeros((2, 4, 4, 1))
        fn = smp.sample_res_multistep_ancestral_cfg_pp
        a = fn(ideal_model(x0), x, sigmas, keys=ka)
        b = fn(ideal_model(x0), x, sigmas, keys=kb)
        assert not np.allclose(np.asarray(a), np.asarray(b))
        with pytest.raises(ValueError):
            fn(ideal_model(x0), x, sigmas)
