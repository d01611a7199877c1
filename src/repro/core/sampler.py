"""Diffusion samplers: DDPM ancestral, DDIM / DPM-Solver-1 (paper Lemma 1),
and the noise schedules they share. All in VP (variance-preserving)
parameterization: alpha_t = sqrt(alpha_bar_t), sigma_t = sqrt(1 - alpha_bar_t),
lambda_t = log(alpha_t / sigma_t)  (log-SNR/2). Beside them the rectified-
flow Euler sampler of SD3 (:class:`FlowSchedule`); :func:`timesteps` and
:func:`step` pick the update from the schedule's type.

The paper's Lemma 1 (DPM-Solver-1 == DDIM):
    x_{t_m} = (alpha_{t_m}/alpha_{t_{m-1}}) x_{t_{m-1}}
              - sigma_{t_m} (e^{h_m} - 1) eps_theta(x_{t_{m-1}}, t_{m-1}),
    h_m = lambda_{t_m} - lambda_{t_{m-1}}.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class NoiseSchedule:
    """Discrete schedule over T training steps with continuous accessors."""
    T: int
    alpha_bar: jnp.ndarray        # [T+1]; alpha_bar[0] = 1 (t=0 is data)
    betas: jnp.ndarray            # [T+1]; betas[0] = 0

    def alpha(self, t):
        return jnp.sqrt(self._ab(t))

    def sigma(self, t):
        return jnp.sqrt(1.0 - self._ab(t))

    def lam(self, t):
        ab = self._ab(t)
        return 0.5 * (jnp.log(ab) - jnp.log1p(-ab))

    def _ab(self, t):
        """Linear interpolation of alpha_bar at (possibly fractional) t."""
        t = jnp.asarray(t, jnp.float32)
        lo = jnp.clip(jnp.floor(t).astype(jnp.int32), 0, self.T)
        hi = jnp.clip(lo + 1, 0, self.T)
        w = t - lo
        return (1 - w) * self.alpha_bar[lo] + w * self.alpha_bar[hi]


# a pytree, so that jitted steps take the schedule table as an argument
jax.tree_util.register_dataclass(NoiseSchedule,
                                 data_fields=["alpha_bar", "betas"],
                                 meta_fields=["T"])


def linear_schedule(T: int = 1000, beta_min: float = 1e-4, beta_max: float = 2e-2) -> NoiseSchedule:
    betas = jnp.concatenate([jnp.zeros((1,)), jnp.linspace(beta_min, beta_max, T)])
    alpha_bar = jnp.cumprod(1.0 - betas)
    return NoiseSchedule(T, alpha_bar, betas)


def cosine_schedule(T: int = 1000, s: float = 8e-3) -> NoiseSchedule:
    t = jnp.arange(T + 1) / T
    f = jnp.cos((t + s) / (1 + s) * jnp.pi / 2) ** 2
    alpha_bar = jnp.clip(f / f[0], 1e-5, 1.0)
    ab_prev = jnp.concatenate([jnp.ones((1,)), alpha_bar[:-1]])
    betas = jnp.clip(1 - alpha_bar / ab_prev, 0.0, 0.999)
    return NoiseSchedule(T, alpha_bar, betas)


def ddim_timesteps(T: int, M: int, warmup_offset: int = 0) -> jnp.ndarray:
    """M+1 decreasing timesteps t_0=T .. t_M=0 (paper Lemma 1 grid)."""
    return jnp.round(jnp.linspace(T, 0, M + 1)).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class FlowSchedule:
    """Rectified flow (arXiv:2403.03206): x_sigma = (1 - sigma) x_0 +
    sigma * noise, and the model predicts the velocity noise - x_0 at the
    timestep T * sigma. The sigma grid is that of diffusers'
    ``FlowMatchEulerDiscreteScheduler`` with a static ``shift``."""
    T: int = 1000
    shift: float = 1.0

    def shifted(self, sigma):
        return self.shift * sigma / (1 + (self.shift - 1) * sigma)

    def sigmas(self, M: int) -> np.ndarray:
        """M + 1 decreasing sigmas, the last 0: M points evenly spaced from
        1 to the training grid's smallest (shifted) sigma, shifted again,
        as ``set_timesteps`` spaces them. float64 arithmetic, float32 out."""
        sigma_min = self.shifted(1.0 / self.T)
        s = self.shifted(np.linspace(1.0, sigma_min, M))
        return np.append(s, 0.0).astype(np.float32)


jax.tree_util.register_dataclass(FlowSchedule, data_fields=[],
                                 meta_fields=["T", "shift"])


def timesteps(sched, M: int) -> jnp.ndarray:
    """The fine grid of M steps: M + 1 decreasing model timesteps, the last
    0 (DDIM's integer grid, or T * sigma of a flow schedule, float32)."""
    if isinstance(sched, FlowSchedule):
        return jnp.asarray(sched.T * sched.sigmas(M))
    return ddim_timesteps(sched.T, M)


# ----------------------------------------------------------------------
# classifier-free guidance (DESIGN.md §12)
# ----------------------------------------------------------------------

def cfg_combine(eps_c, eps_u, scale):
    """The CFG combiner: ``eps_u + w * (eps_c - eps_u)`` in fp32, cast back
    to eps_c's dtype. The ONE place the guidance formula lives — the fused-
    batch reference (:func:`repro.models.diffusion.dit.forward_cfg`), the
    emulated engine, the SPMD guidance bodies and the serving engine all
    route through it, so the rule cannot drift between executors. ``scale``
    may be a python float or a per-lane array broadcastable to eps_c."""
    ec = eps_c.astype(jnp.float32)
    eu = eps_u.astype(jnp.float32)
    return (eu + scale * (ec - eu)).astype(eps_c.dtype)


def cfg_delta(eps_c, eps_u):
    """The guidance direction ``eps_c - eps_u`` (fp32): what interleaved
    guidance caches. The class direction drifts far more slowly across
    fine steps than eps_u itself (which tracks the noisy latent), so
    reusing the DELTA keeps the reuse error ``(w-1) * dDelta`` small even
    at production guidance weights."""
    return eps_c.astype(jnp.float32) - eps_u.astype(jnp.float32)


def cfg_apply_delta(eps_c, delta, scale):
    """Interleaved reuse combiner: ``eps_c + (w-1) * delta`` — exactly
    :func:`cfg_combine` when ``delta`` is this step's true eps_c - eps_u."""
    ec = eps_c.astype(jnp.float32)
    return (ec + (scale - 1.0) * delta).astype(eps_c.dtype)


# ----------------------------------------------------------------------
# single steps
# ----------------------------------------------------------------------

def ddim_step(sched: NoiseSchedule, x, eps, t_from, t_to):
    """One Lemma-1 update from t_{m-1}=t_from to t_m=t_to (t_to < t_from).

    Called eagerly, the update is one compiled program; inside traced code
    (``lax.scan``, ``shard_map`` bodies) it is traced inline. The
    timesteps are scalars or per-lane arrays over x's leading axes (``[G]``
    against ``[G,1,H,W,C]``); they broadcast over x's remaining axes."""
    args = (sched, x, eps, t_from, t_to)
    if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
        return _ddim_update(*args)
    return _ddim_program(*args)


def _ddim_update(sched, x, eps, t_from, t_to):
    a_from, a_to = sched.alpha(t_from), sched.alpha(t_to)
    s_from, s_to = sched.sigma(t_from), sched.sigma(t_to)
    # sigma_to * (e^{h} - 1) == a_to*s_from/a_from - s_to  exactly (VP param);
    # this form is finite at the t_to = 0 endpoint where lambda -> +inf.
    coef = a_to * s_from / a_from - s_to
    ratio = a_to / a_from
    lanes = coef.shape + (1,) * (x.ndim - coef.ndim)
    x32 = x.astype(jnp.float32)
    out = (ratio.reshape(lanes) * x32
           - coef.reshape(lanes) * eps.astype(jnp.float32))
    return out.astype(x.dtype)


# With XLA's fusion pass off every operation rounds to f32 as it does run
# op by op: fused, the CPU backend contracts a product and the difference
# that takes it into one FMA, an ULP away. (The TPU compiler names its
# passes otherwise and fuses as before.)
_ddim_program = jax.jit(_ddim_update,
                        compiler_options={"xla_disable_hlo_passes": "fusion"})


def step(sched, x, out, t_from, t_to):
    """One update of the fine grid from t_from to t_to with the model's
    output: DDIM (out = eps) on a :class:`NoiseSchedule`, Euler (out =
    velocity) on a :class:`FlowSchedule`."""
    if isinstance(sched, FlowSchedule):
        return flow_step(sched, x, out, t_from, t_to)
    return ddim_step(sched, x, out, t_from, t_to)


def flow_step(sched: FlowSchedule, x, v, t_from, t_to):
    """The Euler update of rectified flow, x + (sigma_to - sigma_from) * v.
    One compiled program called eagerly, traced inline inside traced code,
    and broadcast over lanes, as :func:`ddim_step`."""
    args = (sched, x, v, t_from, t_to)
    if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
        return _flow_update(*args)
    return _flow_program(*args)


def _flow_update(sched, x, v, t_from, t_to):
    dt = (jnp.asarray(t_to, jnp.float32)
          - jnp.asarray(t_from, jnp.float32)) / sched.T
    lanes = dt.shape + (1,) * (x.ndim - dt.ndim)
    out = x.astype(jnp.float32) + dt.reshape(lanes) * v.astype(jnp.float32)
    return out.astype(x.dtype)


_flow_program = jax.jit(_flow_update)


def ddpm_step(sched: NoiseSchedule, x, eps, t, noise):
    """Ancestral DDPM step t -> t-1 (stochastic)."""
    t = jnp.asarray(t, jnp.int32)
    beta = sched.betas[t]
    ab = sched.alpha_bar[t]
    alpha = 1.0 - beta
    x32 = x.astype(jnp.float32)
    mean = (x32 - beta / jnp.sqrt(1 - ab) * eps.astype(jnp.float32)) / jnp.sqrt(alpha)
    sigma = jnp.sqrt(beta)
    out = jnp.where(t > 1, mean + sigma * noise, mean)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# full trajectories (single device / oracle "Origin" path)
# ----------------------------------------------------------------------

def ddim_sample(eps_fn: Callable, sched: NoiseSchedule, x_T, M: int,
                collect: bool = False):
    """eps_fn(x, t_scalar) -> eps. Returns x_0 (and trajectory if collect)."""
    ts = ddim_timesteps(sched.T, M)

    def body(x, m):
        t_from, t_to = ts[m], ts[m + 1]
        eps = eps_fn(x, t_from)
        return ddim_step(sched, x, eps, t_from, t_to), (x if collect else None)

    x, traj = jax.lax.scan(body, x_T, jnp.arange(M))
    return (x, traj) if collect else x


def ddpm_sample(eps_fn: Callable, sched: NoiseSchedule, x_T, rng):
    def body(carry, t):
        x, rng = carry
        rng, k = jax.random.split(rng)
        eps = eps_fn(x, t)
        noise = jax.random.normal(k, x.shape, jnp.float32)
        return (ddpm_step(sched, x, eps, t, noise), rng), None

    (x, _), _ = jax.lax.scan(body, (x_T, rng), jnp.arange(sched.T, 0, -1))
    return x


# ----------------------------------------------------------------------
# diffusion training objective (eps-prediction)
# ----------------------------------------------------------------------

def diffusion_loss(eps_fn: Callable, sched: NoiseSchedule, x0, rng):
    """Standard eps-matching loss: E_t,eps ||eps_theta(x_t, t) - eps||^2."""
    B = x0.shape[0]
    kt, ke = jax.random.split(rng)
    t = jax.random.randint(kt, (B,), 1, sched.T + 1)
    eps = jax.random.normal(ke, x0.shape, jnp.float32)
    ab = sched.alpha_bar[t].reshape((B,) + (1,) * (x0.ndim - 1))
    xt = jnp.sqrt(ab) * x0 + jnp.sqrt(1 - ab) * eps
    pred = eps_fn(xt.astype(x0.dtype), t)
    return jnp.mean(jnp.square(pred.astype(jnp.float32) - eps))
