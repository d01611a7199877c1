"""Sampler + schedule numerics, incl. empirical Theorem 1/2 order checks on a
closed-form score model (cheap; the trained-DiT versions live in
benchmarks/bench_redundancy.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sampler as sl


def test_schedules_monotone():
    for sched in (sl.linear_schedule(1000), sl.cosine_schedule(1000)):
        ab = np.asarray(sched.alpha_bar)
        assert ab[0] == pytest.approx(1.0)
        assert np.all(np.diff(ab) <= 1e-9)
        assert ab[-1] < 0.05


def test_alpha_sigma_vp_identity():
    sched = sl.linear_schedule(1000)
    t = jnp.linspace(0, 1000, 77)
    a, s = sched.alpha(t), sched.sigma(t)
    np.testing.assert_allclose(np.asarray(a ** 2 + s ** 2), 1.0, rtol=1e-5)


def test_ddim_full_steps_deterministic_and_finite():
    sched = sl.linear_schedule(100)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8))
    eps_fn = lambda x, t: 0.1 * x
    out1 = sl.ddim_sample(eps_fn, sched, x, M=100)
    out2 = sl.ddim_sample(eps_fn, sched, x, M=100)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert np.all(np.isfinite(np.asarray(out1)))


def test_ddpm_runs_finite():
    sched = sl.linear_schedule(50)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4))
    out = sl.ddpm_sample(lambda x, t: 0.1 * x, sched, x, jax.random.PRNGKey(1))
    assert np.all(np.isfinite(np.asarray(out)))


def _ddim_op_by_op(sched, x, eps, t_from, t_to):
    """The DDIM update as separate eager operations, the timesteps laid
    over x's leading axes."""
    lanes = jnp.shape(t_from) + (1,) * (x.ndim - jnp.ndim(t_from))
    t_from, t_to = jnp.reshape(t_from, lanes), jnp.reshape(t_to, lanes)
    a_from, a_to = sched.alpha(t_from), sched.alpha(t_to)
    s_from, s_to = sched.sigma(t_from), sched.sigma(t_to)
    coef = a_to * s_from / a_from - s_to
    out = (a_to / a_from) * x.astype(jnp.float32) \
        - coef * eps.astype(jnp.float32)
    return out.astype(x.dtype)


@pytest.mark.parametrize("case", ["scalar", "lanes", "endpoint"])
def test_compiled_ddim_step_is_the_op_by_op_update(case):
    """One compiled program per update, bitwise the eager formula: a scalar
    step, per-lane [G] timesteps over a [G,1,H,W,C] lane stack, and the
    t_to = 0 endpoint; a second call at the same shapes reuses the
    program."""
    sched = sl.linear_schedule(1000)
    ts = sl.ddim_timesteps(sched.T, 20)
    kx, ke = jax.random.split(jax.random.PRNGKey(3))
    if case == "lanes":
        shape, dtype = (5, 1, 6, 8, 4), jnp.bfloat16
        t_from, t_to = ts[jnp.array([0, 3, 9, 15, 19])], \
            ts[jnp.array([1, 5, 10, 17, 20])]
    else:
        shape, dtype = (1, 6, 8, 4), jnp.float32
        m = 19 if case == "endpoint" else 4
        t_from, t_to = ts[m], ts[m + 1]
    assert (int(jnp.min(t_to)) == 0) == (case != "scalar")

    def draw(i):
        x = jax.random.normal(jax.random.fold_in(kx, i), shape)
        eps = jax.random.normal(jax.random.fold_in(ke, i), shape)
        return x.astype(dtype), eps.astype(dtype)

    programs = sl._ddim_program._cache_size()
    for i in range(2):
        x, eps = draw(i)
        got = sl.ddim_step(sched, x, eps, t_from, t_to)
        if i == 0:
            programs = sl._ddim_program._cache_size()
        want = _ddim_op_by_op(sched, x, eps, t_from, t_to)
        assert got.dtype == dtype and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        assert np.all(np.isfinite(np.asarray(got, np.float32)))
    assert sl._ddim_program._cache_size() == programs


def test_theorem1_redundancy_order():
    """|x_{t_m} - x_{t_{m+1}}| max-step-difference scales ~ 1/M (Thm. 1)."""
    sched = sl.linear_schedule(1000)
    x_T = jax.random.normal(jax.random.PRNGKey(0), (1, 16))
    eps_fn = lambda x, t: jnp.tanh(x)              # bounded model output

    def max_diff(M):
        _, traj = sl.ddim_sample(eps_fn, sched, x_T, M=M, collect=True)
        d = jnp.abs(jnp.diff(traj, axis=0))
        return float(jnp.max(d))

    Ms = [25, 50, 100, 200]
    diffs = [max_diff(M) for M in Ms]
    # fit slope in log-log; O(1/M) => slope ~ -1 (tolerate [-1.35, -0.6])
    slope = np.polyfit(np.log(Ms), np.log(diffs), 1)[0]
    assert -1.35 < slope < -0.6, (slope, diffs)


def test_theorem2_mixed_rate_alignment():
    """Device j with 2x steps of device i: gap at shared timesteps O(1/M)."""
    sched = sl.linear_schedule(1000)
    x_T = jax.random.normal(jax.random.PRNGKey(1), (1, 16))
    eps_fn = lambda x, t: jnp.tanh(x)

    def gap(M):
        ts_f = sl.ddim_timesteps(sched.T, M)       # fine (device j)
        ts_c = ts_f[::2]                           # coarse (device i), M/2 steps
        xf = xc = x_T
        gaps = []
        for m in range(M // 2):
            for s in range(2):
                tf, tt = ts_f[2 * m + s], ts_f[2 * m + s + 1]
                xf = sl.ddim_step(sched, xf, eps_fn(xf, tf), tf, tt)
            tc_f, tc_t = ts_c[m], ts_c[m + 1]
            xc = sl.ddim_step(sched, xc, eps_fn(xc, tc_f), tc_f, tc_t)
            gaps.append(float(jnp.max(jnp.abs(xf - xc))))
        return max(gaps)

    Ms = [40, 80, 160]
    gaps = [gap(M) for M in Ms]
    slope = np.polyfit(np.log(Ms), np.log(gaps), 1)[0]
    assert slope < -0.6, (slope, gaps)             # decays at least ~1/M


def test_diffusion_loss_finite_and_positive():
    sched = sl.linear_schedule(100)
    x0 = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8, 3))
    loss = sl.diffusion_loss(lambda x, t: jnp.zeros_like(x), sched, x0,
                             jax.random.PRNGKey(1))
    assert float(loss) == pytest.approx(1.0, rel=0.2)   # ||eps||^2 ~ 1
