"""Jitted/batched Algorithm 1 (core.monotonic_jax + kernels.polyblock_project)
vs the host NumPy reference, plus the vectorized Algorithm 2 formulation.

No hypothesis dependency: these must run even where the property-test
modules skip."""
import numpy as np
import pytest

from repro.core import (
    WirelessConfig,
    grid_oracle,
    precompute_gamma,
    solve_pairs,
    solve_pairs_jit,
    swap_matching,
    swap_matching_loop,
)
from repro.core.matching import is_two_sided_exchange_stable, prepare_utility
from repro.core.wireless import total_energy

CFG = WirelessConfig()


def _random_batch(seed=0, k=4, n=48, scale=3.0):
    rng = np.random.default_rng(seed)
    h2 = rng.exponential(size=(k, n)) * scale
    beta = rng.integers(5, 60, n).astype(float)
    return beta, h2


def _rel(a, b):
    return np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-30))


@pytest.mark.parametrize("backend", ["mixed", "newton", "bisect"])
def test_jitted_matches_numpy(backend):
    """Acceptance contract: 1e-6 relative on tau/p/time_s for feasible pairs."""
    beta, h2 = _random_batch(seed=1)
    ref = solve_pairs(beta[None, :], h2, CFG)
    jit = solve_pairs_jit(beta[None, :], h2, CFG, backend=backend)
    np.testing.assert_array_equal(ref.feasible, jit.feasible)
    np.testing.assert_array_equal(ref.iterations, jit.iterations)
    f = ref.feasible
    assert f.any()
    for field in ("tau", "p", "time_s", "energy_j"):
        assert _rel(getattr(ref, field)[f], getattr(jit, field)[f]) < 1e-6, field
    # infeasible pairs keep the sentinel contract
    assert np.all(np.isinf(jit.time_s[~f]))
    assert np.all(np.isnan(jit.tau[~f]))


def test_jitted_matches_grid_oracle():
    """Spot-check the jitted solver against the brute-force oracle."""
    rng = np.random.default_rng(7)
    h2 = rng.exponential(size=8) * 4
    beta = rng.integers(5, 60, 8).astype(float)
    res = solve_pairs_jit(beta, h2, CFG)
    for i in range(8):
        oracle = grid_oracle(float(beta[i]), float(h2[i]), CFG)
        if not res.feasible[i]:
            assert oracle == np.inf
        else:
            assert res.time_s[i] <= oracle * 1.02 + 1e-6


def test_jitted_energy_budget_and_bounds():
    beta, h2 = _random_batch(seed=2, n=64)
    res = solve_pairs_jit(beta[None, :], h2, CFG)
    f = res.feasible
    e = total_energy(res.tau[f], res.p[f], np.broadcast_to(beta, h2.shape)[f],
                     h2[f], CFG)
    assert np.all(e <= CFG.e_max_j * (1 + 1e-6))
    assert np.all((res.tau[f] > 0) & (res.tau[f] <= 1))
    assert np.all((res.p[f] > 0) & (res.p[f] <= 1))


def test_jitted_unconstrained_corner():
    """theta = 1 corner: a huge budget makes (1, 1) optimal."""
    cfg = WirelessConfig(e_max_j=100.0)
    res = solve_pairs_jit(np.array([10.0]), np.array([10.0]), cfg)
    assert res.feasible[0]
    assert res.tau[0] == pytest.approx(1.0)
    assert res.p[0] == pytest.approx(1.0)


def test_whole_horizon_precompute_matches_per_round():
    """precompute_gamma == stacking per-round host solves (the tensor is
    selection-independent, so one batched call covers the horizon)."""
    rng = np.random.default_rng(3)
    rounds, k, n = 5, 4, 12
    beta = rng.integers(5, 60, n).astype(float)
    h2_all = rng.exponential(size=(rounds, k, n)) * 3
    batch = precompute_gamma(beta, h2_all, CFG)
    assert batch.time_s.shape == (rounds, k, n)
    for t in range(rounds):
        ref = solve_pairs(beta[None, :], h2_all[t], CFG)
        np.testing.assert_array_equal(ref.feasible, batch.feasible[t])
        f = ref.feasible
        assert _rel(ref.time_s[f], batch.time_s[t][f]) < 1e-6


def test_projection_backends_agree():
    """ref (NumPy bisection) vs fused jnp vs Pallas kernel (f32, interpret
    on the CPU) on the same vertex batch."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.feasibility import is_infeasible
    from repro.kernels.polyblock_project.ops import polyblock_project

    rng = np.random.default_rng(11)
    n = 256
    v = np.stack([rng.uniform(0.05, 1, n), rng.uniform(0.05, 1, n)], -1)
    beta = rng.integers(5, 60, n).astype(float)
    h2 = rng.exponential(size=n) * 3
    e_max = np.full(n, CFG.e_max_j)
    keep = ~is_infeasible(h2, CFG, e_max)  # bisection-to-TINY pairs excluded
    v, beta, h2, e_max = v[keep], beta[keep], h2[keep], e_max[keep]

    ref = polyblock_project(v, beta, h2, e_max, CFG, backend="ref")
    with jax.enable_x64(True):
        args = [jnp.asarray(x) for x in (v, beta, h2, e_max)]
        jit = np.asarray(polyblock_project(*args, CFG, backend="bisect"))
        newt = np.asarray(polyblock_project(*args, CFG, backend="newton"))
        mixed = np.asarray(polyblock_project(*args, CFG, backend="mixed"))
    pal = np.asarray(polyblock_project(v, beta, h2, e_max, CFG,
                                       backend="pallas", interpret=True))
    assert _rel(ref, jit) < 1e-12          # same arithmetic, same order
    assert _rel(ref, newt) < 1e-6          # Newton converges to the same root
    assert _rel(ref, mixed) < 1e-6         # f32 bulk, f64 polish (§13)
    assert _rel(newt, mixed) < 1e-9        # polish pins to the f64 Newton root
    assert _rel(ref, pal) < 1e-4           # kernel runs float32


def test_swap_matching_vectorized_equals_loop():
    """The vectorized pairwise-delta formulation replicates the reference
    proposal loop exactly: same assignment, same swap count, stable result."""
    for seed in range(40):
        rng = np.random.default_rng(seed)
        k = int(rng.integers(2, 9))
        n_sel = int(rng.integers(1, k + 1))
        gamma = rng.exponential(size=(k, n_sel)) * 5
        feas = rng.uniform(size=(k, n_sel)) > rng.uniform(0, 0.8)
        init = rng.permutation(k)[:n_sel]
        vec = swap_matching(gamma, feas, initial=init)
        ref = swap_matching_loop(gamma, feas, initial=init)
        gamma_u = prepare_utility(gamma, feas)
        assert is_two_sided_exchange_stable(gamma_u, vec.assignment)
        np.testing.assert_array_equal(vec.assignment, ref.assignment)
        assert vec.n_swaps == ref.n_swaps
        assert vec.utilities.sum() == ref.utilities.sum()


def test_swap_matching_zero_rounds_guard():
    """max_rounds=0 must return the initial matching, not crash on an
    unbound loop variable (regression)."""
    gamma = np.ones((3, 3))
    feas = np.ones((3, 3), bool)
    init = np.array([2, 0, 1])
    for fn in (swap_matching, swap_matching_loop):
        res = fn(gamma, feas, initial=init, max_rounds=0)
        np.testing.assert_array_equal(res.assignment, init)
        assert res.n_swaps == 0
        assert res.n_rounds == 0
