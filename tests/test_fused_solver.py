"""Differential suite for the fused-stage Algorithm 1 driver
(`core.monotonic_jax.solve_pairs_fused`) and the fully fused Pallas kernel
(`kernels.polyblock_fused`) against the step driver (`solve_pairs_jit`).

Set REPRO_DIFF_BACKEND=pallas to run the driver grid with the single-kernel
solve (interpret mode, the CPU test path) — the CI differential job does exactly that,
mirroring tests/test_scan_equivalence.py.
"""
import os

import numpy as np
import pytest

from repro.core import WirelessConfig, solve_pairs_fused, solve_pairs_jit
from repro.core.feasibility import is_infeasible

CFG = WirelessConfig()

# The step driver with the backend that replays each fused backend's
# projection arithmetic exactly: bisection backends mirror "bisect";
# Newton-family backends ("newton", "mixed", and the CPU default None)
# converge to the same root as "newton" at ~1e-12 relative.
_REF_OF = {"bisect": "bisect", "pallas": "bisect"}

BACKENDS = ["mixed", "bisect"]
_env = os.environ.get("REPRO_DIFF_BACKEND")
if _env and _env not in BACKENDS:
    BACKENDS.append(_env)


def _random_batch(seed=0, k=4, n=96, scale=3.0):
    rng = np.random.default_rng(seed)
    h2 = rng.exponential(size=(k, n)) * scale
    beta = rng.integers(5, 60, n).astype(float)
    return beta, h2


def _rel(a, b):
    return np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-30))


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_driver_matches_step(backend):
    """Acceptance contract: <= 1e-6 relative on tau/p/time_s/energy_j for
    feasible pairs, identical feasibility and iteration counts."""
    beta, h2 = _random_batch(seed=1)
    ref = solve_pairs_jit(beta[None, :], h2, CFG,
                          backend=_REF_OF.get(backend, "newton"))
    fused = solve_pairs_fused(beta[None, :], h2, CFG, backend=backend)
    np.testing.assert_array_equal(ref.feasible, fused.feasible)
    np.testing.assert_array_equal(ref.iterations, fused.iterations)
    f = ref.feasible
    assert f.any() and not f.all()
    for field in ("tau", "p", "time_s", "energy_j"):
        assert _rel(getattr(ref, field)[f], getattr(fused, field)[f]) < 1e-6, field
    # infeasible pairs keep the sentinel contract
    assert np.all(np.isinf(fused.time_s[~f]))
    assert np.all(np.isnan(fused.tau[~f]))


@pytest.mark.parametrize("seed", range(4))
def test_fused_driver_differential_grid(seed):
    """The CI differential grid: varied channel scales so retirement
    schedules differ across rows (the compaction stages see ragged
    active sets)."""
    beta, h2 = _random_batch(seed=seed, n=64, scale=[0.5, 2.0, 8.0, 30.0][seed])
    ref = solve_pairs_jit(beta[None, :], h2, CFG)
    fused = solve_pairs_fused(beta[None, :], h2, CFG)
    np.testing.assert_array_equal(ref.feasible, fused.feasible)
    f = ref.feasible
    if f.any():
        assert _rel(ref.time_s[f], fused.time_s[f]) < 1e-6


def test_fused_driver_horizon_tensor():
    """Whole-horizon (rounds, K, N) input: shape preserved, per-round
    slices match the step driver."""
    rng = np.random.default_rng(5)
    rounds, k, n = 6, 4, 24
    beta = rng.integers(5, 60, n).astype(float)
    h2_all = rng.exponential(size=(rounds, k, n)) * 3
    ref = solve_pairs_jit(beta[None, None, :], h2_all, CFG)
    fused = solve_pairs_fused(beta[None, None, :], h2_all, CFG)
    assert fused.time_s.shape == (rounds, k, n)
    np.testing.assert_array_equal(ref.feasible, fused.feasible)
    f = ref.feasible
    assert _rel(ref.time_s[f], fused.time_s[f]) < 1e-6


def test_fused_driver_all_infeasible_and_tiny():
    """Degenerate batches: an all-infeasible batch and a 1-pair batch
    must not trip the staged compaction (empty active set at stage 0)."""
    res = solve_pairs_fused(np.array([40.0]), np.array([1e-9]),
                            WirelessConfig(e_max_j=1e-6))
    assert not res.feasible[0] and np.isinf(res.time_s[0])
    one = solve_pairs_fused(np.array([10.0]), np.array([10.0]), CFG)
    assert one.feasible[0] and np.isfinite(one.time_s[0])


def test_fused_pallas_kernel_f64_bit_identical():
    """The fully fused kernel in f64 interpret mode replays the jnp
    "bisect" step driver bit-for-bit: same vertex trajectory, same
    eq. (26) retirements, identical floats out (DESIGN.md §13)."""
    pytest.importorskip("jax")
    beta, h2 = _random_batch(seed=7, n=48)
    ref = solve_pairs_jit(beta[None, :], h2, CFG, backend="bisect")
    res = solve_pairs_fused(beta[None, :], h2, CFG, backend="pallas")
    np.testing.assert_array_equal(ref.feasible, res.feasible)
    np.testing.assert_array_equal(ref.iterations, res.iterations)
    f = ref.feasible
    assert f.any()
    for field in ("tau", "p", "time_s"):
        np.testing.assert_array_equal(getattr(ref, field)[f],
                                      getattr(res, field)[f], err_msg=field)


def test_fused_pallas_kernel_f32_study():
    """fp32-accumulation study (DESIGN.md §13): the f32 kernel keeps the
    iteration trajectory of the f64 solve and lands within 1e-4 relative
    (this batch has no eps-boundary retirements; that case is pinned in
    test_kernels.py::test_polyblock_fused_solve_interpret_vs_oracle)."""
    jax = pytest.importorskip("jax")

    from repro.kernels.polyblock_fused.ops import polyblock_solve_fused

    beta, h2 = _random_batch(seed=9, n=48)
    bf, hf = np.broadcast_to(beta, h2.shape).reshape(-1), h2.reshape(-1)
    keep = ~is_infeasible(hf, CFG, np.full(hf.size, CFG.e_max_j))
    bf, hf = bf[keep], hf[keep]
    assert keep.any()
    with jax.enable_x64(True):
        t64, p64, s64, i64 = polyblock_solve_fused(
            bf, hf, CFG.e_max_j, CFG, interpret=True, dtype=np.float64)
    t32, p32, s32, i32 = polyblock_solve_fused(
        bf, hf, CFG.e_max_j, CFG, interpret=True, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(i64), np.asarray(i32))
    for a, b in ((t64, t32), (p64, p32), (s64, s32)):
        assert _rel(np.asarray(a), np.asarray(b, np.float64)) < 1e-4


def test_fused_pallas_kernel_tile_independence():
    """Result must not depend on the (bm, 128) tiling or on how much
    padding the wrapper adds."""
    pytest.importorskip("jax")
    from repro.kernels.polyblock_fused.ops import polyblock_solve_fused

    beta, h2 = _random_batch(seed=11, n=80)
    bf, hf = np.broadcast_to(beta, h2.shape).reshape(-1), h2.reshape(-1)
    keep = ~is_infeasible(hf, CFG, np.full(hf.size, CFG.e_max_j))
    bf, hf = bf[keep][:130], hf[keep][:130]      # ragged: 2 tiles + padding
    outs = [polyblock_solve_fused(bf, hf, CFG.e_max_j, CFG, interpret=True,
                                  dtype=np.float32, bm=bm) for bm in (1, 4, 8)]
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
