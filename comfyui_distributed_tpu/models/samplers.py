"""k-diffusion-family samplers, written for XLA.

The reference drives ComfyUI's ``common_ksampler`` (reference
``distributed_upscale.py:521``; KSampler node in
``workflows/distributed-txt2img.json`` with widgets
``[seed, control, steps, cfg, sampler_name, scheduler, denoise]``).  These are
native implementations with the same sampler-name surface, built TPU-first:

- every sampler is a pure function stepping a ``lax.scan`` over the sigma
  sequence — one traced step, no Python loop in the compiled program;
- per-sample PRNG: callers pass per-sample keys (shape ``[B, 2]``); step
  noise is ``fold_in(key, step)`` so replica/batch streams stay independent
  and reproducible (seed-offset parity with reference
  ``distributed.py:1491-1514`` lives in the keys, not here);
- the model is called once per step on the full batch (CFG doubling happens
  inside the denoiser wrapper) — large batched matmuls for the MXU.

Model convention: ``model(x, sigma) -> denoised`` (x0-prediction), k-diffusion
style, where ``x`` is NHWC latent and ``sigma`` a scalar.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.parallel import sharding as shd

Model = Callable[..., jax.Array]  # model(x, sigma, **extra) -> denoised


def seed_words(seeds) -> Tuple[Any, Any]:
    """Per-sample seeds as their low and high 32-bit words (``uint32[B]``
    each): what ``fold_keys`` takes.  Host seeds (numpy / python ints) are
    64-bit, as the reference's seed widget is, and both words come back as
    numpy: nothing touches the device.  A ``jax.Array`` (a tracer too) is
    treated as 32-bit (x64 is disabled under jit): it is its own low word,
    the high word is zero."""
    if isinstance(seeds, jax.Array):
        return seeds, np.zeros(seeds.shape, np.uint32)
    s = np.asarray(seeds, dtype=np.uint64)
    return ((s & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (s >> np.uint64(32)).astype(np.uint32))


def fold_keys(lo, hi, idx) -> jax.Array:
    """``[B, 2]`` PRNG keys from ``seed_words`` and per-sample fold
    indices: the high word is folded in separately, so seeds differing by
    2^32 stay distinct, then the index, so rows sharing a seed still get
    distinct streams.  Integer arithmetic only: the same bits eagerly and
    under a jit (``DiffusionPipeline.sampler_inputs`` calls it there)."""
    lo, hi, idx = (jnp.asarray(v).astype(jnp.uint32) for v in (lo, hi, idx))
    return jax.vmap(lambda l, h, i: jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(l), h), i))(lo, hi, idx)


def sample_keys(seeds, idx=None) -> jax.Array:
    """Per-sample PRNG keys from per-sample seeds: fold a per-sample index
    into each seed so rows sharing a seed still get distinct streams.

    ``idx`` defaults to the global batch position; the distributed layer
    passes *replica-local* indices instead, so two replicas given the same
    seed produce identical sub-batches (reference parity: a run without a
    DistributedSeed node yields duplicate images on every participant).

    Eager: a dozen tiny programs a call.  A request's denoise gets its
    keys from ``DiffusionPipeline.sampler_inputs`` instead (the same
    ``fold_keys`` inside the one program that makes its other inputs)."""
    lo, hi = seed_words(seeds)
    if idx is None:
        idx = np.arange(lo.shape[0], dtype=np.uint32)
    return fold_keys(lo, hi, idx)


def make_noise_fn(keys: jax.Array) -> Callable[[jax.Array, Tuple[int, ...]], jax.Array]:
    """Per-sample step-noise generator: ``noise(step, shape)`` returns
    ``[B, *shape]`` with each sample drawn from ``fold_in(keys[b], step)``."""
    def noise(step: jax.Array, sample_shape: Tuple[int, ...]) -> jax.Array:
        def one(k):
            return jax.random.normal(jax.random.fold_in(k, step), sample_shape)
        return jax.vmap(one)(keys)
    return noise


def make_noise_fn_rowwise(keys: jax.Array) -> Callable:
    """Row-wise variant of :func:`make_noise_fn` for the continuous-
    batching step executor: ``steps`` is a PER-SAMPLE ``[B]`` vector (a
    padded batch's slots sit at different iteration indices), each row
    drawing from ``fold_in(keys[b], steps[b])``.  With a broadcast
    scalar step this is bit-identical to ``make_noise_fn`` — the same
    fold-in, vmapped over the same keys."""
    def noise(steps: jax.Array, sample_shape: Tuple[int, ...]) -> jax.Array:
        def one(k, st):
            return jax.random.normal(jax.random.fold_in(k, st),
                                     sample_shape)
        return jax.vmap(one)(keys, jnp.broadcast_to(
            jnp.asarray(steps), (keys.shape[0],)))
    return noise


def _broadcast_sigma(sigma: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.reshape(sigma, (-1,) + (1,) * (x.ndim - 1))


def _ancestral_sigmas(sigma: jax.Array, sigma_next: jax.Array,
                      eta: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """sigma_down/sigma_up split for ancestral samplers."""
    sigma_up = jnp.minimum(
        sigma_next,
        eta * jnp.sqrt(jnp.maximum(
            sigma_next ** 2 * (sigma ** 2 - sigma_next ** 2)
            / jnp.maximum(sigma ** 2, 1e-20), 0.0)))
    sigma_down = jnp.sqrt(jnp.maximum(sigma_next ** 2 - sigma_up ** 2, 0.0))
    return sigma_down, sigma_up


def _to_d(x: jax.Array, sigma: jax.Array, denoised: jax.Array) -> jax.Array:
    return (x - denoised) / jnp.maximum(sigma, 1e-20)


def _interrupt_stop(operand) -> jax.Array:
    """Traced poll of the process-global interrupt flag.

    io_callback, not pure_callback: the poll reads mutable host state,
    and an effectful callback can't be CSE'd/elided when the operand
    repeats (it does once interrupted — the carry goes constant).
    Ordering comes from the data-derived ``operand``, so ordered=False
    keeps it compatible with sharded (SPMD) sampling.  The ONE copy of
    this subtle idiom — the scan body and uni_pc's priming call both use
    it."""
    import numpy as _np

    from jax.experimental import io_callback

    from comfyui_distributed_tpu.runtime import interrupt as itr
    return io_callback(itr.poll, jax.ShapeDtypeStruct((), _np.bool_),
                       operand)


def _scan_sampler(step_fn, x, sigmas, carry_init=None):
    """Run ``step_fn`` over consecutive sigma pairs with lax.scan.

    Memory contract (buffer donation): ``x`` rides the scan as the carry,
    and the registry jits the enclosing denoise loop with the latent
    argument donated (``registry.sample``: ``donate_argnums`` on the
    core) — XLA aliases the carry onto the caller's input buffer, so the
    loop holds ONE latent-sized buffer per carry slot instead of
    input + carry.  Samplers must keep the latent flowing THROUGH the
    carry (never closing over ``x`` from an outer scope) or the aliasing
    breaks and peak memory doubles; history slots (``carry_init``) are
    extra buffers by design (multistep samplers need them).

    Per-step interrupt (``DTPU_INTERRUPT_POLL=1``; reference parity with
    ComfyUI's in-sampler interrupt, off by default because a program
    with a host callback is never persisted to the compile cache): each
    iteration polls the process-global flag
    (:mod:`comfyui_distributed_tpu.runtime.interrupt`) via a host callback
    and, once set, skips the model call — the scan still runs its remaining
    (now trivial) iterations and returns the partially-denoised latent.
    The poll's operand is a carry-derived scalar purely to sequence the
    callback after the previous step."""
    from comfyui_distributed_tpu.runtime import interrupt as itr

    pairs = jnp.stack([sigmas[:-1], sigmas[1:]], axis=1)
    steps = jnp.arange(pairs.shape[0])
    poll = itr.polling_enabled()

    def body(carry, inp):
        step, (s, s_next) = inp
        if not poll:
            return step_fn(carry, step, s, s_next)
        stop = _interrupt_stop(carry[0].reshape(-1)[0])
        new_carry = jax.lax.cond(
            stop,
            lambda c: c,
            lambda c: step_fn(c, step, s, s_next)[0],
            carry)
        return new_carry, None

    carry = (x, carry_init) if carry_init is not None else (x, None)
    (x_final, _), _ = jax.lax.scan(body, carry, (steps, pairs))
    return x_final


# --- extracted single-step callables (continuous batching) -------------------
#
# The step-granular batch executor (workflow/batch_executor.py) advances
# a padded batch ONE sigma pair at a time, with every slot at its own
# iteration index — so these samplers' per-step math is extracted into
# standalone ``<name>_step(model, x, sigma, sigma_next, step_i, keys)``
# callables that accept PER-SAMPLE ``[B]`` sigma/step vectors (scalars
# still work: ``_broadcast_sigma`` reshapes either form identically).
# The scan samplers below are expressed THROUGH these callables, so the
# serial loop and the continuous-batching loop execute literally the
# same per-step expressions — the bit-exactness guarantee is structural,
# not a parallel implementation kept in sync by hand.  Only samplers
# whose step is stateless across iterations (no multistep history
# carry) are extracted; SAMPLER_STEPS is the executor's whitelist.

def euler_step(model: Model, x: jax.Array, sigma: jax.Array,
               sigma_next: jax.Array, step_i: jax.Array = 0,
               keys: Optional[jax.Array] = None,
               extra_args: Optional[Dict[str, Any]] = None) -> jax.Array:
    """One Euler (== deterministic DDIM) step; ``keys``/``step_i`` are
    accepted for signature uniformity and unused (no step noise)."""
    extra = extra_args or {}
    denoised = model(x, sigma, **extra)
    d = _to_d(x, _broadcast_sigma(jnp.asarray(sigma, jnp.float32), x),
              denoised)
    return x + d * _broadcast_sigma(
        jnp.asarray(sigma_next, jnp.float32)
        - jnp.asarray(sigma, jnp.float32), x)


def euler_ancestral_step(model: Model, x: jax.Array, sigma: jax.Array,
                         sigma_next: jax.Array, step_i: jax.Array,
                         keys: jax.Array,
                         extra_args: Optional[Dict[str, Any]] = None,
                         eta: float = 1.0) -> jax.Array:
    """One ancestral Euler step: deterministic move to sigma_down, then
    per-sample ``fold_in(keys[b], step_i[b])`` noise at sigma_up."""
    extra = extra_args or {}
    s = jnp.asarray(sigma, jnp.float32)
    s_next = jnp.asarray(sigma_next, jnp.float32)
    denoised = model(x, s, **extra)
    sd, su = _ancestral_sigmas(s, s_next, eta)
    d = _to_d(x, _broadcast_sigma(s, x), denoised)
    x = x + d * _broadcast_sigma(sd - s, x)
    noise = make_noise_fn_rowwise(keys)(step_i, x.shape[1:])
    return x + noise * _broadcast_sigma(su, x)


# sampler name -> extracted step callable; THE eligibility surface for
# the continuous-batching executor (constants.CB_SAFE_SAMPLERS mirrors
# the keys so the registry-drift story stays in one obvious place)
SAMPLER_STEPS: Dict[str, Callable] = {
    "euler": euler_step,
    "ddim": euler_step,
    "euler_ancestral": euler_ancestral_step,
}


def get_sampler_step(name: str) -> Callable:
    if name not in SAMPLER_STEPS:
        raise ValueError(
            f"sampler {name!r} has no extracted step callable; "
            f"continuous batching supports: {sorted(SAMPLER_STEPS)}")
    return SAMPLER_STEPS[name]


# --- samplers ---------------------------------------------------------------

def sample_euler(model: Model, x: jax.Array, sigmas: jax.Array,
                 extra_args: Optional[Dict[str, Any]] = None,
                 keys: Optional[jax.Array] = None) -> jax.Array:
    """Euler (= DDIM with eta=0 in this parameterization: the update
    ``x0 + s_next * (x - x0)/s`` is exactly the deterministic DDIM step)."""
    extra = extra_args or {}

    def step(carry, step_i, s, s_next):
        x, _ = carry
        x = euler_step(model, x, s, s_next, step_i, keys,
                       extra_args=extra)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


sample_ddim = sample_euler  # deterministic DDIM == euler in sigma space


def _last_uncond(model: Model, denoised: jax.Array) -> jax.Array:
    """CFG++ side-channel: the cfg denoiser stashes its uncond denoised
    on itself each call (a traced value read back within the same trace
    step); a bare model (no CFG wrapper) falls back to the denoised."""
    return getattr(model, "last_uncond", denoised)


def sample_euler_cfg_pp(model: Model, x: jax.Array, sigmas: jax.Array,
                        extra_args: Optional[Dict[str, Any]] = None,
                        keys: Optional[jax.Array] = None) -> jax.Array:
    """Euler CFG++ (the reference's euler_cfg_pp): the step direction
    comes from the UNCOND denoised while the anchor is the CFG result —
    ``x' = denoised + sigma_next * (x - uncond_denoised) / sigma``."""
    extra = extra_args or {}

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        d = _to_d(x, s, _last_uncond(model, denoised))
        x = denoised + d * s_next
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_euler_ancestral_cfg_pp(
        model: Model, x: jax.Array, sigmas: jax.Array,
        extra_args: Optional[Dict[str, Any]] = None,
        keys: Optional[jax.Array] = None,
        eta: float = 1.0) -> jax.Array:
    """Ancestral Euler CFG++ (euler_ancestral_cfg_pp)."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("euler_ancestral_cfg_pp requires per-sample "
                         "keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        sd, su = _ancestral_sigmas(s, s_next, eta)
        d = _to_d(x, s, _last_uncond(model, denoised))
        x = denoised + d * sd
        x = x + noise_fn(step_i, sample_shape) * su
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_euler_ancestral(model: Model, x: jax.Array, sigmas: jax.Array,
                           extra_args: Optional[Dict[str, Any]] = None,
                           keys: Optional[jax.Array] = None,
                           eta: float = 1.0) -> jax.Array:
    extra = extra_args or {}
    if keys is None:
        raise ValueError("euler_ancestral requires per-sample keys")

    def step(carry, step_i, s, s_next):
        x, _ = carry
        x = euler_ancestral_step(model, x, s, s_next, step_i, keys,
                                 extra_args=extra, eta=eta)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_heun(model: Model, x: jax.Array, sigmas: jax.Array,
                extra_args: Optional[Dict[str, Any]] = None,
                keys: Optional[jax.Array] = None) -> jax.Array:
    extra = extra_args or {}

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        d = _to_d(x, s, denoised)
        x_euler = x + d * (s_next - s)

        def heun_branch(_):
            denoised2 = model(x_euler, s_next, **extra)
            d2 = _to_d(x_euler, s_next, denoised2)
            return x + (d + d2) / 2 * (s_next - s)

        x = jax.lax.cond(s_next > 0, heun_branch, lambda _: x_euler, None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_dpm_2(model: Model, x: jax.Array, sigmas: jax.Array,
                 extra_args: Optional[Dict[str, Any]] = None,
                 keys: Optional[jax.Array] = None) -> jax.Array:
    """DPM-Solver-2 (midpoint in log-sigma)."""
    extra = extra_args or {}

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        d = _to_d(x, s, denoised)

        def mid_branch(_):
            s_mid = jnp.exp((jnp.log(s) + jnp.log(jnp.maximum(s_next, 1e-20))) / 2)
            x_mid = x + d * (s_mid - s)
            denoised2 = model(x_mid, s_mid, **extra)
            d2 = _to_d(x_mid, s_mid, denoised2)
            return x + d2 * (s_next - s)

        x = jax.lax.cond(s_next > 0, mid_branch,
                         lambda _: x + d * (s_next - s), None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_dpm_2_ancestral(model: Model, x: jax.Array, sigmas: jax.Array,
                           extra_args: Optional[Dict[str, Any]] = None,
                           keys: Optional[jax.Array] = None,
                           eta: float = 1.0) -> jax.Array:
    extra = extra_args or {}
    if keys is None:
        raise ValueError("dpm_2_ancestral requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        sd, su = _ancestral_sigmas(s, s_next, eta)
        d = _to_d(x, s, denoised)

        def mid_branch(_):
            s_mid = jnp.exp((jnp.log(s) + jnp.log(jnp.maximum(sd, 1e-20))) / 2)
            x_mid = x + d * (s_mid - s)
            denoised2 = model(x_mid, s_mid, **extra)
            d2 = _to_d(x_mid, s_mid, denoised2)
            x2 = x + d2 * (sd - s)
            return x2 + noise_fn(step_i, sample_shape) * su

        x = jax.lax.cond(sd > 0, mid_branch,
                         lambda _: x + d * (s_next - s), None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_dpmpp_2s_ancestral(model: Model, x: jax.Array, sigmas: jax.Array,
                              extra_args: Optional[Dict[str, Any]] = None,
                              keys: Optional[jax.Array] = None,
                              eta: float = 1.0) -> jax.Array:
    """DPM-Solver++(2S) ancestral."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("dpmpp_2s_ancestral requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]

    def t_of(s):
        return -jnp.log(jnp.maximum(s, 1e-20))

    def s_of(t):
        return jnp.exp(-t)

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        sd, su = _ancestral_sigmas(s, s_next, eta)

        def solver_branch(_):
            t, t_next = t_of(s), t_of(sd)
            r = 1 / 2
            h = t_next - t
            s_mid = s_of(t + r * h)
            x_2 = (s_mid / s) * x - jnp.expm1(-h * r) * denoised
            denoised_2 = model(x_2, s_mid, **extra)
            x_out = (sd / s) * x - jnp.expm1(-h) * denoised_2
            return x_out + noise_fn(step_i, sample_shape) * su

        def euler_branch(_):
            d = _to_d(x, s, denoised)
            return x + d * (s_next - s)

        x = jax.lax.cond(sd > 0, solver_branch, euler_branch, None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_dpmpp_2m(model: Model, x: jax.Array, sigmas: jax.Array,
                    extra_args: Optional[Dict[str, Any]] = None,
                    keys: Optional[jax.Array] = None) -> jax.Array:
    """DPM-Solver++(2M): multistep, carries the previous denoised."""
    extra = extra_args or {}
    n = sigmas.shape[0] - 1
    sig = sigmas

    def t_of(s):
        return -jnp.log(jnp.maximum(s, 1e-20))

    def step(carry, step_i, s, s_next):
        x, old_denoised = carry
        denoised = model(x, s, **extra)
        t, t_next = t_of(s), t_of(jnp.maximum(s_next, 1e-20))
        h = t_next - t
        s_prev = sig[jnp.maximum(step_i - 1, 0)]
        h_last = t_of(s) - t_of(s_prev)

        def multistep(_):
            r = h_last / h
            denoised_d = (1 + 1 / (2 * r)) * denoised - (1 / (2 * r)) * old_denoised
            return denoised_d

        use_ms = jnp.logical_and(step_i > 0, s_next > 0)
        denoised_d = jax.lax.cond(use_ms, multistep, lambda _: denoised, None)
        x_new = (jnp.maximum(s_next, 0.0) / s) * x - jnp.expm1(-h) * denoised_d
        x = jnp.where(s_next > 0, x_new, denoised_d)
        return (x, denoised), None

    return _scan_sampler(step, x, sigmas, carry_init=jnp.zeros_like(x))


def sample_dpmpp_2m_sde(model: Model, x: jax.Array, sigmas: jax.Array,
                        extra_args: Optional[Dict[str, Any]] = None,
                        keys: Optional[jax.Array] = None,
                        eta: float = 1.0) -> jax.Array:
    """DPM-Solver++(2M) SDE, midpoint noise schedule."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("dpmpp_2m_sde requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]
    sig = sigmas
    n = sigmas.shape[0] - 1

    def step(carry, step_i, s, s_next):
        x, (old_denoised, h_last) = carry
        denoised = model(x, s, **extra)

        def final(_):
            return denoised, (denoised, h_last)

        def sde_step(_):
            t, t_next = -jnp.log(s), -jnp.log(s_next)
            h = t_next - t
            x_out = (s_next / s) * jnp.exp(-h * eta) * x \
                + (-jnp.expm1(-h * (1 + eta))) * denoised

            def with_ms(xo):
                # 'midpoint' solver variant — ComfyUI's default for this
                # sampler name (heun variant differs numerically)
                r = h_last / h
                xo = xo + 0.5 * (-jnp.expm1(-h * (1 + eta))) \
                    * (1 / r) * (denoised - old_denoised)
                return xo

            x_out = jax.lax.cond(step_i > 0, with_ms, lambda xo: xo, x_out)
            noise_amt = s_next * jnp.sqrt(jnp.maximum(-jnp.expm1(-2 * eta * h), 0.0))
            x_out = x_out + noise_fn(step_i, sample_shape) * noise_amt
            return x_out, (denoised, h)

        x, new_carry = jax.lax.cond(s_next > 0, sde_step, final, None)
        return (x, new_carry), None

    return _scan_sampler(
        step, x, sigmas,
        carry_init=(jnp.zeros_like(x), jnp.asarray(1.0, x.dtype)))


def sample_dpmpp_sde(model: Model, x: jax.Array, sigmas: jax.Array,
                     extra_args: Optional[Dict[str, Any]] = None,
                     keys: Optional[jax.Array] = None,
                     eta: float = 1.0, r: float = 1.0 / 2) -> jax.Array:
    """DPM-Solver++ (stochastic): 2S with an ancestral noise split at BOTH
    the midpoint and the full step (two model calls, two independent noise
    draws per step; the per-sample streams use fold-ins 2i / 2i+1)."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("dpmpp_sde requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]
    fac = 1.0 / (2.0 * r)

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)

        def euler_branch(_):
            d = _to_d(x, s, denoised)
            return x + d * (s_next - s)

        def sde_branch(_):
            t = -jnp.log(s)
            h = -jnp.log(jnp.maximum(s_next, 1e-20)) - t
            s_mid = jnp.exp(-(t + h * r))
            # step 1: to the midpoint, ancestral split s -> s_mid.
            # exp(t - t_of(sd)) = sd/s, so the k-diffusion update
            # (sd/s)*x - expm1(log(sd/s))*denoised reduces to the
            # interpolation below
            sd1, su1 = _ancestral_sigmas(s, s_mid, eta)
            x_2 = (sd1 / s) * (x - denoised) + denoised
            x_2 = x_2 + noise_fn(step_i * 2, sample_shape) * su1
            denoised_2 = model(x_2, s_mid, **extra)
            # step 2: full step with the blended denoised
            sd2, su2 = _ancestral_sigmas(s, s_next, eta)
            denoised_d = (1 - fac) * denoised + fac * denoised_2
            x_out = (sd2 / s) * (x - denoised_d) + denoised_d
            return x_out + noise_fn(step_i * 2 + 1, sample_shape) * su2

        x = jax.lax.cond(s_next > 0, sde_branch, euler_branch, None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_dpmpp_3m_sde(model: Model, x: jax.Array, sigmas: jax.Array,
                        extra_args: Optional[Dict[str, Any]] = None,
                        keys: Optional[jax.Array] = None,
                        eta: float = 1.0) -> jax.Array:
    """DPM-Solver++(3M) SDE: multistep, carries the TWO previous denoiseds
    and step sizes; order ramps 1 -> 2 -> 3 over the first steps."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("dpmpp_3m_sde requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]

    def step(carry, step_i, s, s_next):
        x, (den_1, den_2, h_1, h_2) = carry
        denoised = model(x, s, **extra)

        def final(_):
            return denoised, (den_1, den_2, h_1, h_2)

        def sde_step(_):
            h = -jnp.log(s_next) + jnp.log(s)
            h_eta = h * (eta + 1.0)
            x_out = jnp.exp(-h_eta) * x - jnp.expm1(-h_eta) * denoised
            phi_2 = jnp.expm1(-h_eta) / h_eta + 1.0

            def order1(_):
                return x_out

            def order2(_):
                rr = h_1 / h
                d = (denoised - den_1) / rr
                return x_out + phi_2 * d

            def order3(_):
                r0, r1 = h_1 / h, h_2 / h
                d1_0 = (denoised - den_1) / r0
                d1_1 = (den_1 - den_2) / r1
                d1 = d1_0 + (d1_0 - d1_1) * r0 / (r0 + r1)
                d2 = (d1_0 - d1_1) / (r0 + r1)
                phi_3 = phi_2 / h_eta - 0.5
                return x_out + phi_2 * d1 - phi_3 * d2

            x_out = jax.lax.switch(jnp.minimum(step_i, 2),
                                   [order1, order2, order3], None)
            if eta:
                amt = s_next * jnp.sqrt(
                    jnp.maximum(-jnp.expm1(-2.0 * h * eta), 0.0))
                x_out = x_out + noise_fn(step_i, sample_shape) * amt
            return x_out, (denoised, den_1, h, h_1)

        x, new_carry = jax.lax.cond(s_next > 0, sde_step, final, None)
        return (x, new_carry), None

    zero = jnp.zeros_like(x)
    one = jnp.asarray(1.0, x.dtype)
    return _scan_sampler(step, x, sigmas,
                         carry_init=(zero, zero, one, one))


# 4-point Gauss-Legendre on [-1, 1]: exact for polynomials to degree 7 —
# the LMS coefficient integrand is degree <= 3, so the quadrature is exact
# (matching k-diffusion's adaptive quad without host-side scipy, which
# cannot run under jit where sigmas are traced)
_GL4_NODES = (-0.8611363115940526, -0.3399810435848563,
              0.3399810435848563, 0.8611363115940526)
_GL4_WEIGHTS = (0.3478548451374538, 0.6521451548625461,
                0.6521451548625461, 0.3478548451374538)


def _lms_coeff(order: int, sig_hist, s, s_next):
    """∫_{s}^{s_next} Π_{k≠j} (τ - σ[i-k])/(σ[i-j] - σ[i-k]) dτ for each j
    in range(order).  ``sig_hist[k]`` = σ[i-k] (k = 0..order-1)."""
    half = (s_next - s) / 2.0
    mid = (s_next + s) / 2.0
    coeffs = []
    for j in range(order):
        total = 0.0
        for node, w in zip(_GL4_NODES, _GL4_WEIGHTS):
            tau = mid + half * node
            prod = 1.0
            for k in range(order):
                if k == j:
                    continue
                prod = prod * (tau - sig_hist[k]) \
                    / (sig_hist[j] - sig_hist[k])
            total = total + w * prod
        coeffs.append(half * total)
    return coeffs


def sample_lms(model: Model, x: jax.Array, sigmas: jax.Array,
               extra_args: Optional[Dict[str, Any]] = None,
               keys: Optional[jax.Array] = None,
               order: int = 4) -> jax.Array:
    """Linear multistep (Adams-Bashforth over the sigma axis): carries a
    ring of the last ``order`` derivative estimates; the Lagrange-basis
    integrals are computed in-graph by exact Gauss-Legendre quadrature."""
    extra = extra_args or {}
    sig = sigmas
    order = max(1, min(int(order), 4))

    def step(carry, step_i, s, s_next):
        x, d_hist = carry                      # d_hist[k] = d at step i-k
        denoised = model(x, s, **extra)
        d = _to_d(x, s, denoised)
        # shift the ring: newest first
        d_hist = jnp.concatenate([d[None], d_hist[:-1]], axis=0)
        sig_hist = [sig[jnp.maximum(step_i - k, 0)] for k in range(order)]

        def make_branch(cur_order):
            def branch(_):
                cs = _lms_coeff(cur_order, sig_hist[:cur_order], s, s_next)
                upd = x
                for j in range(cur_order):
                    upd = upd + cs[j] * d_hist[j]
                return upd
            return branch

        branches = [make_branch(o + 1) for o in range(order)]
        x = jax.lax.switch(jnp.minimum(step_i, order - 1), branches, None)
        return (x, d_hist), None

    d0 = jnp.zeros((order,) + x.shape, x.dtype)
    return _scan_sampler(step, x, sigmas, carry_init=d0)


def _unipc_rb(order: int, h: jax.Array, lam0, lam_hist, variant: str):
    """UniPC's R matrix / b vector (x0-prediction, so ``hh = -h``) and the
    r_k ratios for the D1 differences.  ``lam_hist[k]`` = lambda k steps
    back (k >= 1).  Returns (rks, b, B_h, h_phi_1)."""
    hh = -h
    h_phi_1 = jnp.expm1(hh)
    B_h = hh if variant == "bh1" else jnp.expm1(hh)
    rks = [(lam_hist[k] - lam0) / h for k in range(1, order)] + [1.0]
    b = []
    h_phi_k = h_phi_1 / hh - 1.0
    factorial_i = 1.0
    for i in range(1, order + 1):
        b.append(h_phi_k * factorial_i / B_h)
        factorial_i *= i + 1
        h_phi_k = h_phi_k / hh - 1.0 / factorial_i
    return rks, b, B_h, h_phi_1


def _make_unipc(variant: str):
    def sample(model: Model, x: jax.Array, sigmas: jax.Array,
               extra_args: Optional[Dict[str, Any]] = None,
               keys: Optional[jax.Array] = None) -> jax.Array:
        """UniPC (unified predictor-corrector, order 3, x0-prediction):
        multistep like dpmpp_2m but each step also CORRECTS using the
        model evaluated at the predicted point — that evaluation is then
        reused as the next step's current output, so the cost stays one
        model call per step (plus one priming call before the scan).
        ``lower_order_final`` semantics: order ramps 1->2->3 at the start
        and back down near the end."""
        extra = extra_args or {}
        sig = sigmas
        n = int(sigmas.shape[0]) - 1

        def lam_at(i):
            return -jnp.log(jnp.maximum(sig[jnp.maximum(i, 0)], 1e-20))

        # priming call under the same interrupt poll as the scan steps
        # (without it, an already-interrupted run would still pay one
        # full model forward before the scan's own polls kick in)
        from comfyui_distributed_tpu.runtime import interrupt as itr
        if itr.polling_enabled():
            stop0 = _interrupt_stop(x.reshape(-1)[0])
            m_init = jax.lax.cond(
                stop0, lambda _: jnp.zeros_like(x),
                lambda _: model(x, sigmas[0], **extra), None)
        else:
            m_init = model(x, sigmas[0], **extra)

        def step(carry, step_i, s, s_next):
            x, (m0, m1, m2) = carry
            lam0 = -jnp.log(s)
            lam_hist = [None, lam_at(step_i - 1), lam_at(step_i - 2)]
            m_hist = [m0, m1, m2]

            def final(_):
                # sigma 0: the corrector-free limit of the reference's
                # last step toward t~0 is exactly x = m0
                return m0, (m0, m0, m1)

            def full(_):
                lam_t = -jnp.log(s_next)
                h = lam_t - lam0

                def order_branch(order):
                    # model-free per-order coefficients: the single model
                    # call happens OUTSIDE the switch (tracing the UNet
                    # in every branch would ~4x the compiled program)
                    def branch(_):
                        rks, b, B_h, h_phi_1 = _unipc_rb(
                            order, h, lam0, lam_hist, variant)
                        d1s = [(m_hist[k] - m0) / rks[k - 1]
                               for k in range(1, order)]
                        x_t_ = (s_next / s) * x - h_phi_1 * m0
                        # predictor (UniP)
                        if order == 1:
                            x_pred = x_t_
                        elif order == 2:
                            # ComfyUI hardcodes rhos_p=[0.5] at order 2
                            x_pred = x_t_ - B_h * (0.5 * d1s[0])
                        else:
                            rr = jnp.stack([
                                jnp.stack([jnp.ones_like(rks[0]),
                                           jnp.ones_like(rks[0])]),
                                jnp.stack([rks[0], rks[1]])])
                            bb = jnp.stack([b[0], b[1]])
                            rhos_p = jnp.linalg.solve(rr, bb)
                            x_pred = x_t_ - B_h * (rhos_p[0] * d1s[0]
                                                   + rhos_p[1] * d1s[1])
                        # corrector coefficients (UniC): x_corr =
                        # x_t_ - B_h*(corr_base + rho_last*(m_t - m0))
                        if order == 1:
                            corr_base = jnp.zeros_like(x)
                            rho_last = jnp.asarray(0.5, x.dtype)
                        else:
                            rows = []
                            for i in range(order):
                                rows.append(jnp.stack(
                                    [jnp.asarray(rk) ** i for rk in rks]))
                            rhos_c = jnp.linalg.solve(jnp.stack(rows),
                                                      jnp.stack(b))
                            corr_base = jnp.zeros_like(x)
                            for k in range(order - 1):
                                corr_base = corr_base + rhos_c[k] * d1s[k]
                            rho_last = rhos_c[-1]
                        return x_pred, x_t_, B_h, corr_base, rho_last
                    return branch

                # order = min(history, 3, steps-left) — the UniPC
                # lower_order_final ramp at both ends
                sel = jnp.minimum(jnp.minimum(step_i + 1, 3),
                                  n - step_i) - 1
                x_pred, x_t_, B_h, corr_base, rho_last = jax.lax.switch(
                    sel, [order_branch(1), order_branch(2),
                          order_branch(3)], None)
                # the ONE model call; the reference skips the corrector
                # (and its evaluation) on the last step of a window that
                # ends above sigma 0
                is_last = step_i == n - 1
                m_t = jax.lax.cond(
                    is_last, lambda _: m0,
                    lambda _: model(x_pred, s_next, **extra), None)
                x_corr = x_t_ - B_h * (corr_base + rho_last * (m_t - m0))
                x_out = jnp.where(is_last, x_pred, x_corr)
                return x_out, (m_t, m0, m1)

            x, new_m = jax.lax.cond(s_next > 0, full, final, None)
            return (x, new_m), None

        zero = jnp.zeros_like(x)
        return _scan_sampler(step, x, sigmas,
                             carry_init=(m_init, zero, zero))

    sample.__name__ = f"sample_uni_pc_{variant}"
    return sample


sample_uni_pc = _make_unipc("bh1")
sample_uni_pc_bh2 = _make_unipc("bh2")


def sample_ddpm(model: Model, x: jax.Array, sigmas: jax.Array,
                extra_args: Optional[Dict[str, Any]] = None,
                keys: Optional[jax.Array] = None) -> jax.Array:
    """Classic DDPM ancestral step in sigma space (ComfyUI's ddpm): the
    posterior-mean update runs in the VP-scaled frame x/sqrt(1+sigma^2),
    rescaled back between steps."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("ddpm requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        eps = _to_d(x, s, denoised)             # noise estimate
        xs = x / jnp.sqrt(1.0 + s ** 2)         # VP-scaled frame
        ac = 1.0 / (s * s + 1.0)                # alpha_cumprod
        ac_prev = 1.0 / (jnp.maximum(s_next, 0.0) ** 2 + 1.0)
        alpha = ac / ac_prev
        mu = jnp.sqrt(1.0 / alpha) * (
            xs - (1.0 - alpha) * eps / jnp.sqrt(1.0 - ac))
        std = jnp.sqrt(jnp.maximum(
            (1.0 - alpha) * (1.0 - ac_prev) / (1.0 - ac), 0.0))
        mu = jnp.where(s_next > 0,
                       mu + noise_fn(step_i, sample_shape) * std, mu)
        x = jnp.where(s_next > 0, mu * jnp.sqrt(1.0 + s_next ** 2), mu)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


# Adams-Bashforth coefficients for uniform steps, order 1..4 (the
# classic iPNDM table)
_IPNDM_COEFFS = (
    (1.0,),
    (3.0 / 2, -1.0 / 2),
    (23.0 / 12, -16.0 / 12, 5.0 / 12),
    (55.0 / 24, -59.0 / 24, 37.0 / 24, -9.0 / 24),
)


def sample_ipndm(model: Model, x: jax.Array, sigmas: jax.Array,
                 extra_args: Optional[Dict[str, Any]] = None,
                 keys: Optional[jax.Array] = None,
                 max_order: int = 4) -> jax.Array:
    """iPNDM: Adams-Bashforth multistep over the derivative history with
    the classic fixed coefficient table (order ramps 1 -> 4)."""
    extra = extra_args or {}
    max_order = max(1, min(int(max_order), 4))

    def step(carry, step_i, s, s_next):
        x, d_hist = carry                      # d_hist[k] = d at i-1-k
        denoised = model(x, s, **extra)
        d = _to_d(x, s, denoised)
        dt = s_next - s

        def make_branch(order):
            def branch(_):
                cs = _IPNDM_COEFFS[order - 1]
                upd = cs[0] * d
                for k in range(1, order):
                    upd = upd + cs[k] * d_hist[k - 1]
                return x + dt * upd
            return branch

        branches = [make_branch(o + 1) for o in range(max_order)]
        x = jax.lax.switch(jnp.minimum(step_i, max_order - 1), branches,
                           None)
        d_hist = jnp.concatenate([d[None], d_hist[:-1]], axis=0)
        return (x, d_hist), None

    d0 = jnp.zeros((max(max_order - 1, 1),) + x.shape, x.dtype)
    return _scan_sampler(step, x, sigmas, carry_init=d0)


def sample_heunpp2(model: Model, x: jax.Array, sigmas: jax.Array,
                   extra_args: Optional[Dict[str, Any]] = None,
                   keys: Optional[jax.Array] = None) -> jax.Array:
    """Heun++ (MEDS, arXiv:2305.14267 — k-diffusion's heunpp2): Euler on
    the final step, weighted Heun on the second-to-last, and a 3-eval
    weighted combination elsewhere.  Branches select by position in the
    schedule (traced comparisons under lax.cond — no dynamic shapes)."""
    extra = extra_args or {}
    s_end = sigmas[-1]
    s0 = sigmas[0]
    sig_ext = jnp.concatenate([sigmas, sigmas[-1:]])

    def step(carry, step_i, s, s_next):
        x, _ = carry
        s2 = sig_ext[step_i + 2]
        denoised = model(x, s, **extra)
        d = _to_d(x, s, denoised)
        dt = s_next - s
        x_euler = x + d * dt

        def heun_branch(_):
            x_2 = x_euler
            d_2 = _to_d(x_2, s_next, model(x_2, s_next, **extra))
            w = 2.0 * s0
            w2 = s_next / w
            return x + (d * (1.0 - w2) + d_2 * w2) * dt

        def heunpp_branch(_):
            x_2 = x_euler
            d_2 = _to_d(x_2, s_next, model(x_2, s_next, **extra))
            x_3 = x_2 + d_2 * (s2 - s_next)
            d_3 = _to_d(x_3, s2, model(x_3, s2, **extra))
            w = 3.0 * s0
            w2 = s_next / w
            w3 = s2 / w
            return x + (d * (1.0 - w2 - w3) + d_2 * w2 + d_3 * w3) * dt

        x_out = jax.lax.cond(
            s_next == s_end, lambda _: x_euler,
            lambda _: jax.lax.cond(s2 == s_end, heun_branch,
                                   heunpp_branch, None), None)
        return (x_out, None), None

    return _scan_sampler(step, x, sigmas)


def _ab_vs_coeffs(nodes, t_cur, t_next):
    """Variable-step Adams-Bashforth weights: c_j = mean over
    [t_cur, t_next] of the Lagrange basis L_j on ``nodes`` (newest
    first).  2-point Gauss-Legendre is exact for the <=cubic basis, so
    the classic iPNDM-v / DEIS(tab) step-ratio formulas fall out
    without hand-tabulated coefficients (uniform steps reduce to the
    _IPNDM_COEFFS table)."""
    mid = (t_cur + t_next) / 2.0
    half = (t_next - t_cur) / 2.0
    qs = (mid - half / jnp.sqrt(3.0), mid + half / jnp.sqrt(3.0))

    def basis(j, t):
        out = 1.0
        for m, tm in enumerate(nodes):
            if m != j:
                out = out * (t - tm) / (nodes[j] - tm)
        return out

    return [(basis(j, qs[0]) + basis(j, qs[1])) / 2.0
            for j in range(len(nodes))]


def _make_ab_variable(max_order: int):
    """Variable-step multistep sampler over the derivative history —
    the shared core of ipndm_v (order 4) and DEIS 'tab' mode (order 3):
    both integrate the Lagrange interpolation of d = (x - x0)/sigma
    over the sigma step."""
    def sampler(model: Model, x: jax.Array, sigmas: jax.Array,
                extra_args: Optional[Dict[str, Any]] = None,
                keys: Optional[jax.Array] = None) -> jax.Array:
        extra = extra_args or {}

        def step(carry, step_i, s, s_next):
            x, d_hist = carry
            denoised = model(x, s, **extra)
            d = _to_d(x, s, denoised)
            dt = s_next - s

            def make_branch(order):
                def branch(_):
                    nodes = [s] + [
                        sigmas[jnp.maximum(step_i - k, 0)]
                        for k in range(1, order)]
                    cs = _ab_vs_coeffs(nodes, s, s_next)
                    upd = cs[0] * d
                    for k in range(1, order):
                        upd = upd + cs[k] * d_hist[k - 1]
                    return x + dt * upd
                return branch

            branches = [make_branch(o + 1) for o in range(max_order)]
            x = jax.lax.switch(jnp.minimum(step_i, max_order - 1),
                               branches, None)
            d_hist = jnp.concatenate([d[None], d_hist[:-1]], axis=0)
            return (x, d_hist), None

        d0 = jnp.zeros((max(max_order - 1, 1),) + x.shape, x.dtype)
        return _scan_sampler(step, x, sigmas, carry_init=d0)

    return sampler


sample_ipndm_v = _make_ab_variable(4)
sample_deis = _make_ab_variable(3)


def _dpm_eps(model, x, s, extra):
    return _to_d(x, s, model(x, s, **extra))


def _dpm1_step(model, x, t, t_next, extra):
    """DPM-Solver-1 in t = -log sigma (sigma(t) = exp(-t))."""
    h = t_next - t
    eps = _dpm_eps(model, x, jnp.exp(-t), extra)
    return x - jnp.exp(-t_next) * jnp.expm1(h) * eps


def _dpm2_step(model, x, t, t_next, extra, r1=0.5):
    h = t_next - t
    eps = _dpm_eps(model, x, jnp.exp(-t), extra)
    s1 = t + r1 * h
    u1 = x - jnp.exp(-s1) * jnp.expm1(r1 * h) * eps
    eps_r1 = _dpm_eps(model, u1, jnp.exp(-s1), extra)
    return (x - jnp.exp(-t_next) * jnp.expm1(h) * eps
            - jnp.exp(-t_next) / (2.0 * r1) * jnp.expm1(h)
            * (eps_r1 - eps))


def _dpm3_step(model, x, t, t_next, extra, r1=1.0 / 3, r2=2.0 / 3):
    h = t_next - t
    eps = _dpm_eps(model, x, jnp.exp(-t), extra)
    s1 = t + r1 * h
    s2 = t + r2 * h
    u1 = x - jnp.exp(-s1) * jnp.expm1(r1 * h) * eps
    eps_r1 = _dpm_eps(model, u1, jnp.exp(-s1), extra)
    u2 = (x - jnp.exp(-s2) * jnp.expm1(r2 * h) * eps
          - jnp.exp(-s2) * (r2 / r1)
          * (jnp.expm1(r2 * h) / (r2 * h) - 1.0) * (eps_r1 - eps))
    eps_r2 = _dpm_eps(model, u2, jnp.exp(-s2), extra)
    return (x - jnp.exp(-t_next) * jnp.expm1(h) * eps
            - jnp.exp(-t_next) / r2 * (jnp.expm1(h) / h - 1.0)
            * (eps_r2 - eps))


def sample_dpm_fast(model: Model, x: jax.Array, sigmas: jax.Array,
                    extra_args: Optional[Dict[str, Any]] = None,
                    keys: Optional[jax.Array] = None) -> jax.Array:
    """DPM-Solver fast (k-diffusion): the NFE budget len(sigmas)-1
    splits into third-order solver steps on a uniform t = -log sigma
    grid (orders [3..3, 2, 1] / [3..3, rem]).  The schedule endpoints
    come from the caller's sigmas (sigma_min falls back past a trailing
    0 like ComfyUI's wrapper); the solver places its own grid, so only
    the ENDPOINTS and COUNT of ``sigmas`` matter.  Deterministic; runs
    unrolled (static order list), so no per-step interrupt poll."""
    extra = extra_args or {}
    nfe = int(sigmas.shape[0]) - 1
    if nfe < 1:
        return x
    sig_min = jnp.where(sigmas[-1] > 0, sigmas[-1], sigmas[-2])
    t_start = -jnp.log(sigmas[0])
    t_end = -jnp.log(sig_min)
    m = nfe // 3 + 1
    ts = [t_start + (t_end - t_start) * (i / m) for i in range(m + 1)]
    if nfe % 3 == 0:
        orders = [3] * (m - 2) + [2, 1]
    else:
        orders = [3] * (m - 1) + [nfe % 3]
    steps = {1: _dpm1_step, 2: _dpm2_step, 3: _dpm3_step}
    from comfyui_distributed_tpu.runtime import interrupt as itr
    poll = itr.polling_enabled()
    stop = jnp.asarray(False)
    for i, order in enumerate(orders):
        if poll:
            # same per-step interrupt contract as _scan_sampler, chained
            # through the unrolled solver steps
            stop = jnp.logical_or(stop,
                                  _interrupt_stop(x.reshape(-1)[0]))
            x = jax.lax.cond(
                stop, lambda c: c,
                lambda c, _i=i, _o=order: steps[_o](model, c, ts[_i],
                                                    ts[_i + 1], extra),
                x)
        else:
            x = steps[order](model, x, ts[i], ts[i + 1], extra)
    return x


def sample_dpm_adaptive(model: Model, x: jax.Array, sigmas: jax.Array,
                        extra_args: Optional[Dict[str, Any]] = None,
                        keys: Optional[jax.Array] = None,
                        order: int = 3, rtol: float = 0.05,
                        atol: float = 0.0078, h_init: float = 0.05,
                        pcoeff: float = 0.0, icoeff: float = 1.0,
                        dcoeff: float = 0.0,
                        accept_safety: float = 0.81,
                        max_iters: int = 512) -> jax.Array:
    """DPM-Solver-12/23 adaptive (k-diffusion's dpm_adaptive): embedded
    2nd/3rd-order solver pair in t = -log sigma with a PID step-size
    controller — TPU-shaped as a lax.while_loop (data-dependent trip
    count is the whole point; ``max_iters`` bounds a pathological
    controller).  Only the ENDPOINTS of ``sigmas`` matter; the
    controller places its own steps.  The eps evaluations are shared
    between the embedded orders (3 NFE per attempt, like k-diffusion's
    eps_cache)."""
    extra = extra_args or {}
    if int(sigmas.shape[0]) < 2:
        return x
    sig_min = jnp.where(sigmas[-1] > 0, sigmas[-1], sigmas[-2])
    t_start = -jnp.log(sigmas[0])
    t_end = -jnp.log(sig_min)
    b1 = (pcoeff + icoeff + dcoeff) / order
    b2 = -(pcoeff + 2.0 * dcoeff) / order
    b3 = dcoeff / order
    n_sqrt = float(x.size) ** 0.5
    from comfyui_distributed_tpu.runtime import interrupt as itr
    poll = itr.polling_enabled()

    def cond(carry):
        x_, x_prev, s, h, errs, it, stopped = carry
        return jnp.logical_and(
            jnp.logical_and(s < t_end - 1e-5, it < max_iters),
            jnp.logical_not(stopped))

    def body(carry):
        if poll:
            # per-step interrupt: poll BEFORE the attempt; a set flag
            # ends the loop without paying the 3 model evals
            stopped = _interrupt_stop(carry[0].reshape(-1)[0])
            return jax.lax.cond(
                stopped,
                lambda c: (*c[:6], jnp.asarray(True)),
                _attempt, carry)
        return _attempt(carry)

    def _attempt(carry):
        x_, x_prev, s, h, errs, it, stopped = carry
        t = jnp.minimum(t_end, s + h)
        hh = t - s
        # shared-eps embedded pair (k-diffusion r1=1/3 cache sharing)
        r1, r2 = 1.0 / 3, 2.0 / 3
        eps = _dpm_eps(model, x_, jnp.exp(-s), extra)
        s1 = s + r1 * hh
        s2 = s + r2 * hh
        u1 = x_ - jnp.exp(-s1) * jnp.expm1(r1 * hh) * eps
        eps_r1 = _dpm_eps(model, u1, jnp.exp(-s1), extra)
        x_low = (x_ - jnp.exp(-t) * jnp.expm1(hh) * eps
                 - jnp.exp(-t) / (2.0 * r1) * jnp.expm1(hh)
                 * (eps_r1 - eps))
        u2 = (x_ - jnp.exp(-s2) * jnp.expm1(r2 * hh) * eps
              - jnp.exp(-s2) * (r2 / r1)
              * (jnp.expm1(r2 * hh) / (r2 * hh) - 1.0) * (eps_r1 - eps))
        eps_r2 = _dpm_eps(model, u2, jnp.exp(-s2), extra)
        x_high = (x_ - jnp.exp(-t) * jnp.expm1(hh) * eps
                  - jnp.exp(-t) / r2 * (jnp.expm1(hh) / hh - 1.0)
                  * (eps_r2 - eps))
        # elementwise tolerance (k-diffusion): low-magnitude regions get
        # their own |x|-scaled delta, not the tensor-global max
        delta = jnp.maximum(
            atol, rtol * jnp.maximum(jnp.abs(x_low), jnp.abs(x_prev)))
        error = jnp.sqrt(jnp.sum(((x_low - x_high) / delta) ** 2)) \
            / n_sqrt
        e0 = 1.0 / (1e-8 + error)
        # k-diffusion seeds the whole PID history with the FIRST step's
        # inverse error (errs = [inv_error]*3), so nonzero pcoeff/dcoeff
        # see a neutral history, not a placeholder
        e1 = jnp.where(it == 0, e0, errs[0])
        e2 = jnp.where(it == 0, e0, errs[1])
        factor = e0 ** b1 * e1 ** b2 * e2 ** b3
        factor = 1.0 + jnp.arctan(factor - 1.0)     # k-diffusion limiter
        accept = factor >= accept_safety
        x_new = jnp.where(accept, x_high, x_)
        x_prev_new = jnp.where(accept, x_low, x_prev)
        s_new = jnp.where(accept, t, s)
        # accept shifts the history; reject keeps it (incl. the it==0
        # seeding, which persists either way in k-diffusion)
        errs_new = jnp.where(accept, jnp.stack([e0, e1]),
                             jnp.stack([e1, e2]))
        return (x_new, x_prev_new, s_new, h * factor, errs_new, it + 1,
                stopped)

    errs0 = jnp.full((2,), 1.0 / 1e-8, jnp.float32)
    out = jax.lax.while_loop(
        cond, body, (x, x, t_start, jnp.asarray(h_init, jnp.float32),
                     errs0, jnp.asarray(0, jnp.int32),
                     jnp.asarray(False)))
    return out[0]


def sample_lcm(model: Model, x: jax.Array, sigmas: jax.Array,
               extra_args: Optional[Dict[str, Any]] = None,
               keys: Optional[jax.Array] = None) -> jax.Array:
    """Latent consistency sampling: jump to x0, re-noise to next sigma."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("lcm requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)
        x = jnp.where(s_next > 0,
                      denoised + noise_fn(step_i, sample_shape) * s_next,
                      denoised)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def _phi1(neg_h: jax.Array) -> jax.Array:
    """phi_1(z) = expm1(z)/z, z = -h (h > 0 in the descending-sigma
    half-log-SNR parameterization used by every solver here)."""
    return jnp.expm1(neg_h) / neg_h


def _phi2(neg_h: jax.Array) -> jax.Array:
    """phi_2(z) = (phi_1(z) - 1)/z."""
    return (_phi1(neg_h) - 1.0) / neg_h


def sample_res_multistep(model: Model, x: jax.Array, sigmas: jax.Array,
                         extra_args: Optional[Dict[str, Any]] = None,
                         keys: Optional[jax.Array] = None) -> jax.Array:
    """RES second-order exponential multistep (Refined Exponential
    Solver, arXiv:2308.02157 — the ecosystem's ``res_multistep``),
    deterministic variant: one model call per step, the previous
    denoised extrapolates via phi-weighted Adams-Bashforth coefficients
    (first step falls back to the first-order exponential update).
    One shared body serves all four variants (``_res_multistep_core``)."""
    return _res_multistep_core(model, x, sigmas, extra_args, keys,
                               eta=0.0, cfg_pp=False)


def _res_multistep_core(model: Model, x: jax.Array, sigmas: jax.Array,
                        extra_args: Optional[Dict[str, Any]],
                        keys: Optional[jax.Array], eta: float,
                        cfg_pp: bool) -> jax.Array:
    """Shared RES multistep body: deterministic (eta=0) or ancestral
    (sigma_down/up split + per-step noise), optionally CFG++ (the step's
    exponential decay anchors on the uncond denoised — the same
    ``last_uncond`` side-channel the euler CFG++ samplers read)."""
    extra = extra_args or {}
    if eta > 0 and keys is None:
        raise ValueError("res_multistep_ancestral requires per-sample "
                         "keys")
    noise_fn = make_noise_fn(keys) if eta > 0 else None
    sample_shape = x.shape[1:]
    sig = sigmas

    def step(carry, step_i, s, s_next):
        x, old_denoised = carry
        denoised = model(x, s, **extra)
        anchor = _last_uncond(model, denoised) if cfg_pp else denoised
        sd, su = (_ancestral_sigmas(s, s_next, eta) if eta > 0
                  else (s_next, jnp.asarray(0.0, x.dtype)))
        t = -jnp.log(s)
        t_next = -jnp.log(jnp.maximum(sd, 1e-20))
        h = t_next - t
        t_old = -jnp.log(sig[jnp.maximum(step_i - 1, 0)])
        c2 = jnp.where(step_i > 0, (t_old - t) / h, -1.0)
        b2 = _phi2(-h) / c2
        # first-order part: plain = e^-h x - expm1(-h) D; cfg_pp anchors
        # the exponential decay on the UNCOND (D + e^-h (x - anchor) —
        # euler_cfg_pp's update in exponential form); both reduce to the
        # same thing for a bare model.  The 2nd-order correction
        # h*b2*(D_old - D) is identical algebra either way:
        # h*(b1 D + b2 D_old) == -expm1(-h) D + h b2 (D_old - D).
        base = (denoised + jnp.exp(-h) * (x - anchor)) if cfg_pp \
            else (jnp.exp(-h) * x - jnp.expm1(-h) * denoised)
        x_ms = base + h * b2 * (old_denoised - denoised)
        x_new = jnp.where(step_i > 0, x_ms, base)
        if eta > 0:
            x_new = x_new + noise_fn(step_i, sample_shape) * su
        x = jnp.where(s_next > 0, x_new, denoised)
        return (x, denoised), None

    return _scan_sampler(step, x, sigmas, carry_init=jnp.zeros_like(x))


def sample_res_multistep_cfg_pp(model: Model, x: jax.Array,
                                sigmas: jax.Array,
                                extra_args: Optional[Dict[str, Any]] = None,
                                keys: Optional[jax.Array] = None
                                ) -> jax.Array:
    """res_multistep with the CFG++ anchor (uncond denoised drives the
    exponential decay; reduces to res_multistep for a bare model)."""
    return _res_multistep_core(model, x, sigmas, extra_args, keys,
                               eta=0.0, cfg_pp=True)


def sample_res_multistep_ancestral(model: Model, x: jax.Array,
                                   sigmas: jax.Array,
                                   extra_args: Optional[Dict[str, Any]] = None,
                                   keys: Optional[jax.Array] = None,
                                   eta: float = 1.0) -> jax.Array:
    """Ancestral res_multistep: the multistep update targets sigma_down
    and fresh noise tops back up to sigma_next."""
    return _res_multistep_core(model, x, sigmas, extra_args, keys,
                               eta=eta, cfg_pp=False)


def sample_res_multistep_ancestral_cfg_pp(
        model: Model, x: jax.Array, sigmas: jax.Array,
        extra_args: Optional[Dict[str, Any]] = None,
        keys: Optional[jax.Array] = None, eta: float = 1.0) -> jax.Array:
    """Ancestral res_multistep with the CFG++ anchor."""
    return _res_multistep_core(model, x, sigmas, extra_args, keys,
                               eta=eta, cfg_pp=True)


def sample_dpmpp_2m_cfg_pp(model: Model, x: jax.Array, sigmas: jax.Array,
                           extra_args: Optional[Dict[str, Any]] = None,
                           keys: Optional[jax.Array] = None) -> jax.Array:
    """DPM-Solver++(2M) with the CFG++ anchor: the multistep
    extrapolation uses the CFG denoised, the exponential decay anchors
    on the uncond (``denoised + e^-h * (x - uncond)``) — reduces to
    dpmpp_2m exactly for a bare model."""
    extra = extra_args or {}
    sig = sigmas

    def t_of(s):
        return -jnp.log(jnp.maximum(s, 1e-20))

    def step(carry, step_i, s, s_next):
        x, old_denoised = carry
        denoised = model(x, s, **extra)
        anchor = _last_uncond(model, denoised)
        t, t_next = t_of(s), t_of(jnp.maximum(s_next, 1e-20))
        h = t_next - t
        s_prev = sig[jnp.maximum(step_i - 1, 0)]
        h_last = t_of(s) - t_of(s_prev)

        def ms_term(_):
            r = h_last / h
            return -jnp.expm1(-h) * (1.0 / (2.0 * r)) \
                * (denoised - old_denoised)

        extra_ms = jax.lax.cond(step_i > 0, ms_term,
                                lambda _: jnp.zeros_like(denoised), None)
        # D + e^-h (x - anchor): euler_cfg_pp's exponential-decay-on-
        # uncond form; adding the standard 2M correction term reduces
        # EXACTLY to dpmpp_2m for a bare model (anchor == D):
        # D(1 - e^-h) + e^-h x - expm1(-h)(1/2r)(D - D_old)
        #   == e^-h x - expm1(-h) D_d
        x_new = denoised + jnp.exp(-h) * (x - anchor) + extra_ms
        x = jnp.where(s_next > 0, x_new, denoised)
        return (x, denoised), None

    return _scan_sampler(step, x, sigmas, carry_init=jnp.zeros_like(x))


def sample_gradient_estimation(model: Model, x: jax.Array,
                               sigmas: jax.Array,
                               extra_args: Optional[Dict[str, Any]] = None,
                               keys: Optional[jax.Array] = None,
                               ge_gamma: float = 2.0) -> jax.Array:
    """Gradient-estimation sampler (the ecosystem's
    ``gradient_estimation``): euler steps whose direction extrapolates
    the previous step's, ``d_bar = gamma*d + (1-gamma)*d_old`` — for an
    ideal (constant-x0) denoiser the directions coincide and the
    trajectory equals euler exactly."""
    extra = extra_args or {}

    def step(carry, step_i, s, s_next):
        x, old_d = carry
        denoised = model(x, s, **extra)
        d = _to_d(x, s, denoised)
        d_bar = jnp.where(step_i > 0,
                          ge_gamma * d + (1.0 - ge_gamma) * old_d, d)
        x = x + d_bar * (s_next - s)
        return (x, d), None

    return _scan_sampler(step, x, sigmas, carry_init=jnp.zeros_like(x))


def sample_er_sde(model: Model, x: jax.Array, sigmas: jax.Array,
                  extra_args: Optional[Dict[str, Any]] = None,
                  keys: Optional[jax.Array] = None,
                  s_noise: float = 1.0, max_stage: int = 3) -> jax.Array:
    """Extended Reverse-time SDE solver, VE ER-SDE-Solver-3
    (arXiv:2309.06169 — the ecosystem's ``er_sde``): stage ramps 1->3
    over the first steps; the noise-scale function lambda(sigma) =
    sigma*(exp(sigma^0.3)+10) and its integrals (200-point midpointless
    Riemann sum, static shapes) drive the higher-order corrections."""
    extra = extra_args or {}
    if keys is None:
        raise ValueError("er_sde requires per-sample keys")
    noise_fn = make_noise_fn(keys)
    sample_shape = x.shape[1:]
    sig = sigmas
    n_int = 200

    def scaler(sigma):
        return sigma * (jnp.exp(sigma ** 0.3) + 10.0)

    def step(carry, step_i, s, s_next):
        x, (old_den, old_den_d) = carry
        denoised = model(x, s, **extra)
        r = scaler(jnp.maximum(s_next, 1e-20)) / scaler(s)
        x1 = r * x + (1.0 - r) * denoised
        # stage 2: first divided difference of the denoised
        s_prev = sig[jnp.maximum(step_i - 1, 0)]
        den_d = (denoised - old_den) \
            / jnp.where(step_i > 0, s - s_prev, 1.0)
        dt = s_next - s
        pos = s_next + jnp.arange(n_int, dtype=x.dtype) * (-dt / n_int)
        int1 = jnp.sum(1.0 / scaler(jnp.maximum(pos, 1e-20))) \
            * (-dt / n_int)
        x2 = x1 + (dt + int1 * scaler(jnp.maximum(s_next, 1e-20))) * den_d
        # stage 3: second divided difference
        s_prev2 = sig[jnp.maximum(step_i - 2, 0)]
        den_u = (den_d - old_den_d) \
            / jnp.where(step_i > 1, (s - s_prev2) / 2.0, 1.0)
        int2 = jnp.sum((pos - s) / scaler(jnp.maximum(pos, 1e-20))) \
            * (-dt / n_int)
        x3 = x2 + ((dt ** 2) / 2.0
                   + int2 * scaler(jnp.maximum(s_next, 1e-20))) * den_u
        stage = jnp.minimum(step_i + 1, max_stage)
        x_new = jnp.where(stage >= 3, x3, jnp.where(stage >= 2, x2, x1))
        noise_amt = jnp.sqrt(jnp.maximum(s_next ** 2 - (s * r) ** 2, 0.0))
        x_new = x_new + noise_fn(step_i, sample_shape) * s_noise * noise_amt
        x = jnp.where(s_next > 0, x_new, denoised)
        return (x, (denoised, den_d)), None

    return _scan_sampler(
        step, x, sigmas,
        carry_init=(jnp.zeros_like(x), jnp.zeros_like(x)))


def sample_sa_solver(model: Model, x: jax.Array, sigmas: jax.Array,
                     extra_args: Optional[Dict[str, Any]] = None,
                     keys: Optional[jax.Array] = None) -> jax.Array:
    """SA-Solver (Stochastic Adams, arXiv:2309.05019 — the ecosystem's
    ``sa_solver``), deterministic tau=0 PECE variant at order 2: the
    RES-style Adams-Bashforth predictor takes a trial step, the model
    evaluates AT the target sigma, and the exponential trapezoidal
    Adams-Moulton corrector (weights phi_1 - phi_2 / phi_2) recombines
    — two model calls per step."""
    extra = extra_args or {}
    sig = sigmas

    def step(carry, step_i, s, s_next):
        x, old_denoised = carry
        denoised = model(x, s, **extra)

        def pece(_):
            t = -jnp.log(s)
            t_next = -jnp.log(s_next)
            h = t_next - t
            t_old = -jnp.log(sig[jnp.maximum(step_i - 1, 0)])
            c2 = jnp.where(step_i > 0, (t_old - t) / h, -1.0)
            phi1, phi2 = _phi1(-h), _phi2(-h)
            b2 = phi2 / c2
            b1 = phi1 - b2
            x_pred = jnp.exp(-h) * x \
                + h * (b1 * denoised + b2 * old_denoised)
            x_pred = jnp.where(step_i > 0, x_pred,
                               jnp.exp(-h) * x + h * phi1 * denoised)
            denoised_p = model(x_pred, s_next, **extra)
            return jnp.exp(-h) * x + h * ((phi1 - phi2) * denoised
                                          + phi2 * denoised_p)

        x = jax.lax.cond(s_next > 0, pece, lambda _: denoised, None)
        return (x, denoised), None

    return _scan_sampler(step, x, sigmas, carry_init=jnp.zeros_like(x))


def sample_seeds_2(model: Model, x: jax.Array, sigmas: jax.Array,
                   extra_args: Optional[Dict[str, Any]] = None,
                   keys: Optional[jax.Array] = None,
                   eta: float = 1.0, s_noise: float = 1.0,
                   r: float = 0.5) -> jax.Array:
    """SEEDS-2 (Stochastic Explicit Exponential Derivative-free Solver,
    arXiv:2305.14267 — the ecosystem's ``seeds_2``): 2-stage exponential
    solver in the eta-augmented half-log-SNR time ``h_eta = h*(1+eta)``,
    with Brownian increments coupled across the midpoint and full step
    (independent per-sample fold-ins 2i / 2i+1); eta=0 degenerates to
    the deterministic exponential midpoint method."""
    extra = extra_args or {}
    inject = eta > 0 and s_noise > 0
    if inject and keys is None:
        raise ValueError("seeds_2 requires per-sample keys when eta > 0")
    noise_fn = make_noise_fn(keys) if inject else None
    sample_shape = x.shape[1:]
    fac = 1.0 / (2.0 * r)

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)

        def solver(_):
            t = -jnp.log(s)
            t_next = -jnp.log(s_next)
            h = t_next - t
            h_eta = h * (eta + 1.0)
            sigma_mid = jnp.exp(-(t + r * h))
            coeff_1 = jnp.expm1(-r * h_eta)
            coeff_2 = jnp.expm1(-h_eta)
            # stage 1: to the midpoint
            x_2 = (coeff_1 + 1.0) * x - coeff_1 * denoised
            if inject:
                nc1 = jnp.sqrt(-jnp.expm1(-2.0 * r * h * eta))
                n1 = noise_fn(step_i * 2, sample_shape)
                x_2 = x_2 + sigma_mid * nc1 * n1 * s_noise
            denoised_2 = model(x_2, sigma_mid, **extra)
            # stage 2: full step with the blended denoised
            denoised_d = (1.0 - fac) * denoised + fac * denoised_2
            x_out = (coeff_2 + 1.0) * x - coeff_2 * denoised_d
            if inject:
                nc2 = jnp.sqrt(jnp.maximum(
                    jnp.expm1(-2.0 * r * h * eta)
                    - jnp.expm1(-2.0 * h * eta), 0.0))
                n2 = noise_fn(step_i * 2 + 1, sample_shape)
                x_out = x_out + s_next * (nc2 * n1 + nc1 * n2) * s_noise
            return x_out

        x = jax.lax.cond(s_next > 0, solver, lambda _: denoised, None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


def sample_seeds_3(model: Model, x: jax.Array, sigmas: jax.Array,
                   extra_args: Optional[Dict[str, Any]] = None,
                   keys: Optional[jax.Array] = None,
                   eta: float = 1.0, s_noise: float = 1.0,
                   r_1: float = 1.0 / 3, r_2: float = 2.0 / 3) -> jax.Array:
    """SEEDS-3 (arXiv:2305.14267 — the ecosystem's ``seeds_3``):
    3-stage exponential solver at stage fractions r_1/r_2 of the
    eta-augmented step, noise coupled down the stage chain (fold-ins
    3i, 3i+1, 3i+2); eta=0 degenerates to a deterministic 3-stage
    exponential Runge-Kutta."""
    extra = extra_args or {}
    inject = eta > 0 and s_noise > 0
    if inject and keys is None:
        raise ValueError("seeds_3 requires per-sample keys when eta > 0")
    noise_fn = make_noise_fn(keys) if inject else None
    sample_shape = x.shape[1:]

    def step(carry, step_i, s, s_next):
        x, _ = carry
        denoised = model(x, s, **extra)

        def solver(_):
            t = -jnp.log(s)
            t_next = -jnp.log(s_next)
            h = t_next - t
            h_eta = h * (eta + 1.0)
            sigma_1 = jnp.exp(-(t + r_1 * h))
            sigma_2 = jnp.exp(-(t + r_2 * h))
            coeff_1 = jnp.expm1(-r_1 * h_eta)
            coeff_2 = jnp.expm1(-r_2 * h_eta)
            coeff_3 = jnp.expm1(-h_eta)
            if inject:
                nc1 = jnp.sqrt(-jnp.expm1(-2.0 * r_1 * h * eta))
                nc2 = jnp.sqrt(jnp.maximum(
                    jnp.expm1(-2.0 * r_1 * h * eta)
                    - jnp.expm1(-2.0 * r_2 * h * eta), 0.0))
                nc3 = jnp.sqrt(jnp.maximum(
                    jnp.expm1(-2.0 * r_2 * h * eta)
                    - jnp.expm1(-2.0 * h * eta), 0.0))
                n1 = noise_fn(step_i * 3, sample_shape)
                n2 = noise_fn(step_i * 3 + 1, sample_shape)
                n3 = noise_fn(step_i * 3 + 2, sample_shape)
            # stage 1
            x_2 = (coeff_1 + 1.0) * x - coeff_1 * denoised
            if inject:
                x_2 = x_2 + sigma_1 * nc1 * n1 * s_noise
            denoised_2 = model(x_2, sigma_1, **extra)
            # stage 2
            x_3 = (coeff_2 + 1.0) * x - coeff_2 * denoised \
                + (r_2 / r_1) * (coeff_2 / (r_2 * h_eta) + 1.0) \
                * (denoised_2 - denoised)
            if inject:
                x_3 = x_3 + sigma_2 * (nc2 * n1 + nc1 * n2) * s_noise
            denoised_3 = model(x_3, sigma_2, **extra)
            # stage 3
            x_out = (coeff_3 + 1.0) * x - coeff_3 * denoised \
                + (1.0 / r_2) * (coeff_3 / h_eta + 1.0) \
                * (denoised_3 - denoised)
            if inject:
                x_out = x_out + s_next * (nc3 * n1 + nc2 * n2
                                          + nc1 * n3) * s_noise
            return x_out

        x = jax.lax.cond(s_next > 0, solver, lambda _: denoised, None)
        return (x, None), None

    return _scan_sampler(step, x, sigmas)


SAMPLERS: Dict[str, Callable] = {
    "euler": sample_euler,
    "ddim": sample_ddim,
    "euler_cfg_pp": sample_euler_cfg_pp,
    "euler_ancestral": sample_euler_ancestral,
    "euler_ancestral_cfg_pp": sample_euler_ancestral_cfg_pp,
    "heun": sample_heun,
    "dpm_2": sample_dpm_2,
    "dpm_2_ancestral": sample_dpm_2_ancestral,
    "dpmpp_2s_ancestral": sample_dpmpp_2s_ancestral,
    "dpmpp_sde": sample_dpmpp_sde,
    "dpmpp_2m": sample_dpmpp_2m,
    "dpmpp_2m_sde": sample_dpmpp_2m_sde,
    "dpmpp_3m_sde": sample_dpmpp_3m_sde,
    "lms": sample_lms,
    "ddpm": sample_ddpm,
    "ipndm": sample_ipndm,
    "ipndm_v": sample_ipndm_v,
    "deis": sample_deis,
    "heunpp2": sample_heunpp2,
    "dpm_fast": sample_dpm_fast,
    "dpm_adaptive": sample_dpm_adaptive,
    "lcm": sample_lcm,
    "uni_pc": sample_uni_pc,
    "uni_pc_bh2": sample_uni_pc_bh2,
    "res_multistep": sample_res_multistep,
    "res_multistep_cfg_pp": sample_res_multistep_cfg_pp,
    "res_multistep_ancestral": sample_res_multistep_ancestral,
    "res_multistep_ancestral_cfg_pp": sample_res_multistep_ancestral_cfg_pp,
    "dpmpp_2m_cfg_pp": sample_dpmpp_2m_cfg_pp,
    "gradient_estimation": sample_gradient_estimation,
    "er_sde": sample_er_sde,
    "sa_solver": sample_sa_solver,
    "seeds_2": sample_seeds_2,
    "seeds_3": sample_seeds_3,
}

SAMPLER_NAMES = tuple(SAMPLERS.keys())


def get_sampler(name: str) -> Callable:
    if name not in SAMPLERS:
        raise ValueError(f"unknown sampler {name!r}; available: {SAMPLER_NAMES}")
    return SAMPLERS[name]


def cfg_denoiser(model: Model, cond: Any, uncond: Any,
                 cfg_scale: float) -> Model:
    """Classifier-free guidance wrapper: one doubled-batch model call per step
    (cond rows then uncond rows) so the MXU sees a single large matmul —
    the TPU-friendly layout of what ComfyUI does per-sample."""
    return cfg_denoiser_multi(model, [(cond, None, 1.0)], uncond, cfg_scale)


def _norm_entries(entries):
    """(ctx, mask, strength[, sigma_range]) -> uniform 4-tuples."""
    return [e if len(e) == 4 else (*e, None) for e in entries]


def _mask_blend(entries, parts, sigma):
    """sum_i(w_i * den_i) / max(sum_i(w_i), eps), w_i = strength_i *
    mask_i * active_i(sigma) — the per-entry denoised blend both CFG
    sides use.  ``active_i``: ComfyUI's timestep-range gate (a traced
    elementwise select on the step's sigma; entries outside their range
    contribute nothing that step)."""
    acc = None
    wsum = None
    for (c, m, s, srange), p in zip(entries, parts):
        w = jnp.full((1, 1, 1, 1), float(s), p.dtype) if m is None \
            else jnp.asarray(m, p.dtype) * float(s)
        if srange is not None:
            s_start, s_end = float(srange[0]), float(srange[1])
            sig = jnp.max(jnp.asarray(sigma))
            active = jnp.logical_and(sig <= s_start, sig >= s_end)
            w = w * active.astype(p.dtype)
        term = p * w
        wb = jnp.broadcast_to(w, p.shape[:-1] + (1,))
        acc = term if acc is None else acc + term
        wsum = wb if wsum is None else wsum + wb
    return acc / jnp.maximum(wsum, 1e-9)


def cfg_denoiser_multi(model: Model, conds, uncond: Any,
                       cfg_scale: float,
                       cfg_rescale: float = 0.0) -> Model:
    """Area/mask conditioning (ComfyUI's multi-entry cond lists): every
    entry of BOTH CFG sides is evaluated in ONE stacked model call
    ([cond_1..cond_N, uncond_1..uncond_M] rows — still a single large
    matmul for the MXU), then each side's denoised predictions blend by
    their latent-resolution masks and strengths (``_mask_blend``) before
    the CFG combine.

    ``conds`` (and optionally ``uncond``): list of ``(context [B,T,C],
    mask [.,h,w,1] or None, strength[, sigma_range])``; a plain
    ``uncond`` array is a single unmasked entry.  Masks/strengths/ranges
    are trace-time constants of the compiled program (static shapes, no
    dynamic control flow); a region covered by no mask gets ~zero
    prediction — cover the canvas, like ComfyUI (its uncovered regions
    behave the same way)."""
    conds = _norm_entries(conds)
    unconds = _norm_entries(uncond) if isinstance(uncond, (list, tuple)) \
        else [(uncond, None, 1.0, None)]
    n, nu = len(conds), len(unconds)

    def wrapped(x, sigma, **extra):
        use_uncond = cfg_scale != 1.0
        reps = n + (nu if use_uncond else 0)
        if reps == 1 and conds[0][1] is None and conds[0][3] is None:
            den = model(x, sigma, context=conds[0][0], **extra)
            wrapped.last_uncond = den      # cfg==1: no separate uncond
            return den
        # CFG row-stack: a batch-dim concat whose concat dim picks up a
        # mesh axis hits the same XLA CPU SPMD miscompile as the UNet
        # skip concat (tp-concat-cpu-miscompile) — shd.stack_rows /
        # shd.unstack_rows keep the stack/split seams off shard
        # boundaries (inert without an engaged tensor axis)
        x_rep = shd.stack_rows([x] * reps)
        ctx = shd.stack_rows(
            [c for c, _, _, _ in conds]
            + ([c for c, _, _, _ in unconds] if use_uncond else []))
        # per-sample sigma (continuous batching: a padded batch's slots
        # sit at different sigmas) tiles in lockstep with the CFG-stacked
        # rows; scalar sigma broadcasts exactly as before
        sigma_rep = sigma
        if getattr(sigma, "ndim", 0):
            sigma_rep = shd.stack_rows([jnp.asarray(sigma)] * reps)
        out = model(x_rep, sigma_rep, context=ctx, **extra)
        parts = shd.unstack_rows(out, reps)
        den_cond = _mask_blend(conds, parts[:n], sigma)
        if not use_uncond:
            wrapped.last_uncond = den_cond
            return den_cond
        d_uncond = _mask_blend(unconds, parts[n:], sigma)
        # side-channel for CFG++ samplers: the UNCOND denoised of THIS
        # call (a traced value read back within the same trace step)
        wrapped.last_uncond = d_uncond
        if cfg_rescale:
            return _rescale_cfg(x, sigma, den_cond, d_uncond, cfg_scale,
                                cfg_rescale)
        return d_uncond + (den_cond - d_uncond) * cfg_scale
    return wrapped


def cfg_denoiser_dual(model: Model, cond: jax.Array, middle: jax.Array,
                      uncond: jax.Array, cfg1: float, cfg2: float,
                      cfg_rescale: float = 0.0) -> Model:
    """Dual-CFG guidance (ComfyUI's DualCFGGuider / the InstructPix2Pix
    combine): one tripled-batch model call per step ([cond, middle,
    uncond] rows — still a single large matmul for the MXU), combined as

        result = (uncond + cfg2 * (middle - uncond)) + cfg1 * (cond - middle)

    i.e. the middle conditioning is CFG'd against the negative at
    ``cfg2``, then the positive steers against the middle at ``cfg1`` —
    reference semantics: ComfyUI ``nodes_custom_sampler.Guider_DualCFG``.
    A RescaleCFG patch applies to the middle/negative combine (ComfyUI:
    the sampler_cfg_function rides ``cfg_function`` there)."""
    def wrapped(x, sigma, **extra):
        # seam-safe CFG stack/split (tp-concat-cpu-miscompile; see
        # cfg_denoiser_multi)
        x_rep = shd.stack_rows([x, x, x])
        ctx = shd.stack_rows([cond, middle, uncond])
        out = model(x_rep, sigma, context=ctx, **extra)
        pos, mid, neg = shd.unstack_rows(out, 3)
        wrapped.last_uncond = neg       # CFG++ side-channel
        if cfg_rescale:
            base = _rescale_cfg(x, sigma, mid, neg, cfg2, cfg_rescale)
        else:
            base = neg + (mid - neg) * cfg2
        return base + (pos - mid) * cfg1
    return wrapped


def _gaussian_blur_nhwc(x: jax.Array, ksize: int = 9,
                        sigma: float = 2.0) -> jax.Array:
    """Separable gaussian blur with reflect padding (the SAG reference's
    gaussian_blur_2d), [B, H, W, C]."""
    r = ksize // 2
    xs = jnp.arange(-r, r + 1, dtype=jnp.float32)
    k = jnp.exp(-(xs ** 2) / max(2.0 * sigma * sigma, 1e-8))
    k = (k / k.sum()).astype(x.dtype)
    h = jnp.pad(x, ((0, 0), (r, r), (0, 0), (0, 0)), mode="reflect")
    x = sum(k[i] * h[:, i:i + x.shape[1]] for i in range(ksize))
    h = jnp.pad(x, ((0, 0), (0, 0), (r, r), (0, 0)), mode="reflect")
    return sum(k[i] * h[:, :, i:i + x.shape[2]] for i in range(ksize))


def cfg_denoiser_sag(model_capture: Model, model_plain: Model,
                     cond: jax.Array, uncond: jax.Array,
                     cfg_scale: float, sag_scale: float,
                     blur_sigma: float, mid_hw: tuple,
                     cfg_rescale: float = 0.0) -> Model:
    """Self-Attention Guidance (Hong et al.; the reference ecosystem's
    SelfAttentionGuidance patch): per step, the stacked CFG call also
    captures the mid-block self-attention weights; tokens the UNCOND
    pass attends strongly (mean over heads, summed over queries > 1)
    mark where the uncond denoised image gets gaussian-blurred, the
    degraded latent is re-noised and denoised once more under the
    uncond prompt, and the result steers away from what degradation
    would produce:

        out = cfg(cond, uncond) + sag_scale * (degraded - den_degraded)

    (the reference's post-CFG combine; in eps-space this is the paper's
    s*(eps(x̂) - eps(x)) direction).  3 UNet evals per step, like the
    reference."""
    mh, mw = mid_hw

    def wrapped(x, sigma, **extra):
        B = x.shape[0]
        # seam-safe CFG stack/split (tp-concat-cpu-miscompile; see
        # cfg_denoiser_multi)
        x_rep = shd.stack_rows([x, x])
        ctx = shd.stack_rows([cond, uncond])
        out, probs = model_capture(x_rep, sigma, context=ctx, **extra)
        den_cond, den_unc = shd.unstack_rows(out, 2)
        wrapped.last_uncond = den_unc   # CFG++ side-channel
        # probs [2B, heads, N, N]: uncond rows second; mean over heads,
        # sum over the QUERY axis -> per-key attention mass
        a = probs[B:].mean(axis=1).sum(axis=1)          # [B, N]
        mask = (a > 1.0).astype(x.dtype)
        mask = mask.reshape(B, mh, mw, 1)
        mask = jax.image.resize(mask, (B, x.shape[1], x.shape[2], 1),
                                method="nearest")
        blurred = _gaussian_blur_nhwc(den_unc, 9, float(blur_sigma))
        degraded = blurred * mask + den_unc * (1.0 - mask)
        # re-noise the degraded estimate to the current level and run
        # one more UNCOND denoise on it
        degraded_noised = degraded + x - den_unc
        extra_1 = dict(extra)
        for k2 in ("y", "objs"):    # per-block extras: take the uncond
            if extra_1.get(k2) is not None:     # block's rows
                extra_1[k2] = extra_1[k2][B:2 * B]
        den_sag = model_plain(degraded_noised, sigma, context=uncond,
                              **extra_1)
        if cfg_rescale:
            cfg_out = _rescale_cfg(x, sigma, den_cond, den_unc,
                                   cfg_scale, cfg_rescale)
        else:
            cfg_out = den_unc + (den_cond - den_unc) * cfg_scale
        return cfg_out + (degraded - den_sag) * sag_scale
    return wrapped


def cfg_denoiser_perp_neg(model: Model, cond: jax.Array,
                          empty: jax.Array, uncond: jax.Array,
                          cfg_scale: float, neg_scale: float,
                          cfg_rescale: float = 0.0) -> Model:
    """Perp-Neg guidance (Armandpour et al.; ComfyUI's PerpNeg /
    PerpNegGuider): one tripled-batch call with rows [cond, empty,
    uncond]; the negative's component PERPENDICULAR to the positive
    direction (both relative to the empty prompt) is subtracted at
    ``neg_scale`` — the parallel component, which CFG would misread as
    "less positive", is discarded:

        pos  = den_cond - den_empty
        neg  = den_unc - den_empty
        perp = neg - (<neg, pos>/|pos|^2) pos       (per sample)
        out  = den_empty + cfg * (pos - neg_scale * perp)

    Projections reduce per-SAMPLE (the reference ecosystem's global-sum
    reduction cross-talks a batch; x0-space is equivalent to its
    eps-space math — the shared -sigma factor cancels in the
    projection).  A RescaleCFG patch re-stds the combine toward the
    cond prediction like the plain CFG path."""
    def wrapped(x, sigma, **extra):
        # seam-safe CFG stack/split (tp-concat-cpu-miscompile; see
        # cfg_denoiser_multi)
        x_rep = shd.stack_rows([x, x, x])
        ctx = shd.stack_rows([cond, empty, uncond])
        out = model(x_rep, sigma, context=ctx, **extra)
        den_cond, den_empty, den_unc = shd.unstack_rows(out, 3)
        wrapped.last_uncond = den_unc   # CFG++ side-channel
        pos = den_cond - den_empty
        neg = den_unc - den_empty
        axes = tuple(range(1, x.ndim))
        dot = jnp.sum(neg * pos, axis=axes, keepdims=True)
        sq = jnp.maximum(jnp.sum(pos * pos, axis=axes, keepdims=True),
                         1e-12)
        perp = neg - (dot / sq) * pos
        direction = pos - neg_scale * perp
        if cfg_rescale:
            return _rescale_cfg(x, sigma, den_empty + direction,
                                den_empty, cfg_scale, cfg_rescale)
        return den_empty + cfg_scale * direction
    return wrapped


def _rescale_cfg(x: jax.Array, sigma: jax.Array, den_cond: jax.Array,
                 den_uncond: jax.Array, cfg_scale: float,
                 multiplier: float) -> jax.Array:
    """RescaleCFG (Lin et al., "Common Diffusion Noise Schedules..."):
    re-std the CFG combination toward the cond prediction's statistics in
    v-space, blended by ``multiplier`` — tames the over-saturation of
    high CFG, especially on v-prediction models.  Port of the reference
    ecosystem's RescaleCFG patch (x0 predictions in, x0 out)."""
    s = _broadcast_sigma(jnp.asarray(sigma, x.dtype), x)
    s2 = s * s
    xs = x / (s2 + 1.0)
    root = jnp.sqrt(s2 + 1.0)
    v_cond = (xs - (x - den_cond)) * root / s
    v_unc = (xs - (x - den_uncond)) * root / s
    v_cfg = v_unc + (v_cond - v_unc) * cfg_scale
    axes = tuple(range(1, x.ndim))
    ro_pos = jnp.std(v_cond, axis=axes, keepdims=True)
    ro_cfg = jnp.std(v_cfg, axis=axes, keepdims=True)
    v_res = v_cfg * (ro_pos / jnp.maximum(ro_cfg, 1e-9))
    v_fin = multiplier * v_res + (1.0 - multiplier) * v_cfg
    return x - (xs - v_fin * s / root)
