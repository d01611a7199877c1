"""In-process tests for core/comm.py exchange primitives: padded vs
broadcast uneven all-gather equivalence at N=1 and under uneven tails,
plus the analytic gather-cost helper (simulator satellite fix).

Deterministic cases always run; hypothesis widens the size space when the
``test`` extra is installed. The mesh spans jax.devices() (the CI matrix
forces 1 or 4 host devices via STADI_HOST_DEVICES, honored by
tests/conftest.py), so the N=1 degenerate case is exercised in the
single-device leg and true multi-rank uneven tails in the 4-device leg.
jit programs are cached per sizes tuple so repeated examples reuse
compilations."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

N_DEV = len(jax.devices())


def _mesh():
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()), ("dev",))


@functools.lru_cache(maxsize=None)
def _gather_fns(sizes):
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()

    def f_pad(xl):
        return comm.uneven_all_gather_padded(xl[0], sizes, "dev")

    def f_bc(xl):
        return comm.uneven_all_gather_broadcast(xl[0], sizes, "dev")

    return tuple(jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("dev"),
                                      out_specs=P(None), check_vma=False))
                 for f in (f_pad, f_bc))


def _run_case(sizes, width=5, seed=0):
    sizes = tuple(int(s) for s in sizes)
    mx = max(sizes)
    rng = np.random.default_rng(seed)
    slabs = [rng.normal(size=(s, width)).astype(np.float32) for s in sizes]
    oracle = np.concatenate(slabs, 0)
    padded = np.stack([np.pad(s, ((0, mx - s.shape[0]), (0, 0)))
                       for s in slabs])
    x = jnp.asarray(padded)                       # [N, mx, width]
    f_pad, f_bc = _gather_fns(sizes)
    np.testing.assert_allclose(np.asarray(f_pad(x)), oracle, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(f_bc(x)), oracle, rtol=1e-6)


def test_single_rank_identity():
    """N=1: both strategies must return the local slab verbatim."""
    if N_DEV != 1:
        pytest.skip(f"needs exactly 1 device, have {N_DEV}")
    _run_case((4,))
    _run_case((1,))


@pytest.mark.parametrize("seed,tail", [(0, 1), (1, 3), (2, 6)])
def test_uneven_tail_vs_equal_heads(seed, tail):
    """The classic uneven-tail layout: all ranks equal except the last."""
    sizes = (4,) * (N_DEV - 1) + (tail,)
    _run_case(sizes, seed=seed)


def test_fully_uneven_sizes():
    sizes = tuple(([3, 1, 4, 2, 5, 1, 2, 6])[:N_DEV])
    _run_case(sizes, seed=9)


def test_zero_size_rank_contributes_nothing():
    """A rank with 0 valid rows (excluded device) is sliced away."""
    if N_DEV < 2:
        pytest.skip("needs >= 2 devices for a zero-size rank")
    sizes = (3,) + (0,) * (N_DEV - 1)
    _run_case(sizes)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(sizes=st.lists(st.integers(1, 6), min_size=N_DEV,
                          max_size=N_DEV),
           seed=st.integers(0, 3))
    def test_padded_equals_broadcast_equals_oracle(sizes, seed):
        """Paper §V-A equivalence under arbitrary uneven tails (any N)."""
        _run_case(tuple(sizes), seed=seed)


# ----------------------------------------------------------------------
# analytic gather cost (simulator satellite fix)
# ----------------------------------------------------------------------

def test_uneven_all_gather_rows():
    assert comm.uneven_all_gather_rows([8, 8]) == 8
    assert comm.uneven_all_gather_rows([12, 4]) == 12
    assert comm.uneven_all_gather_rows([5, 0, 3]) == 5    # 0-row excluded
    assert comm.uneven_all_gather_rows([16]) == 0         # N=1: no traffic
    assert comm.uneven_all_gather_rows([16, 0]) == 0
    assert comm.uneven_all_gather_rows([]) == 0
    assert comm.uneven_all_gather_rows([2, 2, 2, 2]) == 6


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(sizes=st.lists(st.integers(0, 32), min_size=1, max_size=8))
    def test_uneven_all_gather_rows_bounds(sizes):
        """Wire rows never exceed (N-1) * max; never charge a lone rank."""
        rows = comm.uneven_all_gather_rows(sizes)
        active = [s for s in sizes if s > 0]
        if len(active) <= 1:
            assert rows == 0
        else:
            assert rows == (len(active) - 1) * max(active)
            assert rows < len(active) * max(active)
