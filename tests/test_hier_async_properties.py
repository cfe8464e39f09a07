"""Property suite for the two-tier async hierarchy (`fl.hier_async`).

Hypothesis drives the PURE pieces the engine is assembled from —
staleness weighting, the shared `commit_event` rule reused at both tiers
(device-indexed at the cell tier, cell-indexed at the global tier), and
the virtual-clock recursion — over adversarial inputs; deterministic
tests then pin the same invariants on the real engine's recorded traces
under the churn scenario, and on the coupled cross-cell fading process.

Imports `given`/`st` via the `_hyp` shim: without hypothesis only the
`@given` tests skip (each with a reason), the deterministic ones run.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import WirelessConfig
from repro.fl.async_loop import commit_event
from repro.fl.hierarchical import HierSimConfig, run_hier_many
from repro.fl.server import AsyncAggregation, staleness_weight
from repro.scenarios import FadingProcess, sample_coupled_fading, \
    sample_fading

# Smallest drawn remaining time: 1e-3 rounded to float32, since a width-32
# float strategy takes only bounds that float32 represents exactly.
_F32_MS = float(np.float32(1e-3))

# --------------------------------------------------------------------------
# staleness weights: exact fresh-commit identity + normalization
# --------------------------------------------------------------------------

EXPONENTS = st.floats(min_value=0.0, max_value=4.0,
                      allow_nan=False, allow_infinity=False)


@settings(max_examples=100, deadline=None)
@given(exponent=EXPONENTS)
def test_staleness_fresh_commit_weight_exactly_one(exponent):
    """f(0) == 1.0 EXACTLY for every exponent — both tiers rely on this
    for the bit-exact sync limit (a fresh commit's eq.-34 weight must be
    beta * 1.0 == beta, no rounding)."""
    w = staleness_weight(jnp.int32(0), jnp.float32(exponent))
    assert float(w) == 1.0
    # ... and clamped below zero staleness too (never-dispatched slots).
    assert float(staleness_weight(jnp.int32(-3), jnp.float32(exponent))) == 1.0


@settings(max_examples=100, deadline=None)
@given(stale=st.lists(st.integers(min_value=0, max_value=10_000),
                      min_size=1, max_size=32),
       exponent=EXPONENTS)
def test_staleness_weights_normalized_and_monotone(stale, exponent):
    """Two-tier staleness weights live in (0, 1] and never increase with
    staleness: w(s) = (1+s)^-a."""
    s = jnp.asarray(sorted(stale), jnp.int32)
    w = np.asarray(staleness_weight(s, jnp.float32(exponent)), np.float64)
    assert ((w > 0.0) & (w <= 1.0)).all()
    assert (np.diff(w) <= 1e-12).all()


# --------------------------------------------------------------------------
# the shared commit rule, exercised at the GLOBAL tier's shapes:
# rem/active are cell-indexed (C,), buffer bounded by the cell count
# --------------------------------------------------------------------------

@settings(max_examples=200, deadline=None)
@given(data=st.data(),
       n_cells=st.integers(min_value=1, max_value=12))
def test_global_commit_bounded_by_cell_count_buffer(data, n_cells):
    """Global-tier commit events never exceed the cell-count buffer
    bound (ties at the commit horizon may overshoot `buffer`, but never
    the C slots — exactly the tie-commit behavior the uniform-clock sync
    limit relies on), commit only in-flight cells, and the event latency
    is the exact remaining time of some in-flight cell (or 0 when the
    sky is empty)."""
    rem = np.asarray(data.draw(st.lists(
        st.floats(min_value=_F32_MS, max_value=1e3, allow_nan=False,
                  allow_infinity=False, width=32),
        min_size=n_cells, max_size=n_cells)), np.float32)
    active = np.asarray(data.draw(st.lists(
        st.booleans(), min_size=n_cells, max_size=n_cells)))
    buffer = data.draw(st.integers(min_value=1, max_value=n_cells))
    delta, commit = commit_event(jnp.asarray(rem), jnp.asarray(active),
                                 jnp.int32(buffer), n_cells)
    delta, commit = float(delta), np.asarray(commit)
    assert commit.sum() <= min(n_cells, active.sum())
    assert (commit <= active).all()
    assert delta >= 0.0
    if active.any():
        assert commit.sum() >= 1          # something always commits
        assert delta in rem[active].astype(np.float64).tolist()
        # everything that arrived by the commit horizon commits (up to
        # the k-slot rank cap the engine enforces with k == n_cells)
        arrived = active & (rem <= np.float32(delta))
        assert commit.sum() == min(arrived.sum(), n_cells)
    else:
        assert commit.sum() == 0 and delta == 0.0


@settings(max_examples=100, deadline=None)
@given(data=st.data())
def test_virtual_clocks_non_decreasing_any_trace(data):
    """The per-cell virtual-clock recursion rem' = rem - delta keeps
    every in-flight remainder non-negative and the committed-time axis
    cumsum(delta) non-decreasing, for ANY dispatch/active pattern —
    churn and slowdowns only change the dispatched times, never the
    recursion."""
    n = data.draw(st.integers(min_value=1, max_value=8))
    events = data.draw(st.integers(min_value=1, max_value=20))
    rem = np.zeros(n, np.float32)
    active = np.zeros(n, bool)
    clock = 0.0
    for _ in range(events):
        free = ~active
        dispatch = np.asarray(data.draw(st.lists(
            st.booleans(), min_size=n, max_size=n))) & free
        times = np.asarray(data.draw(st.lists(
            st.floats(min_value=_F32_MS, max_value=1e3, allow_nan=False,
                      allow_infinity=False, width=32),
            min_size=n, max_size=n)), np.float32)
        active = active | dispatch
        rem = np.where(dispatch, times, rem)
        buffer = data.draw(st.integers(min_value=1, max_value=n))
        delta, commit = commit_event(jnp.asarray(rem), jnp.asarray(active),
                                     jnp.int32(buffer), n)
        delta, commit = np.float32(delta), np.asarray(commit)
        assert delta >= 0.0               # the clock never runs backward
        clock_next = clock + float(delta)
        assert clock_next >= clock
        clock = clock_next
        active = active & ~commit
        rem = np.where(active, rem - delta, np.float32(0.0))
        assert (rem >= 0.0).all()         # no in-flight upload overshoots


# --------------------------------------------------------------------------
# deterministic: the real engine's traces satisfy the same invariants
# --------------------------------------------------------------------------

_CFG = dict(dataset="mnist", rounds=8, n_cells=3, devices_per_cell=6,
            subchannels_per_cell=2, n_samples=96, batch=16, local_steps=2,
            eval_every=2, scenario="churn")


@pytest.fixture(scope="module")
def churn_hist():
    cfg = HierSimConfig(**_CFG, aggregation=AsyncAggregation(buffer=1),
                        global_aggregation=AsyncAggregation(buffer=1))
    return run_hier_many([cfg])[0]


def test_engine_commit_bounds_under_churn(churn_hist):
    h = churn_hist
    c_n = _CFG["n_cells"]
    assert (h.async_trace["cell_committed"].sum(axis=1) <= c_n).all()
    assert (h.async_trace["g_pending"] <= c_n).all()
    assert not h.async_trace["overflow"].any()
    # commits only ever devices with an uncommitted dispatch
    n = c_n * _CFG["devices_per_cell"]
    in_flight = np.zeros(n, bool)
    for e in range(_CFG["rounds"]):
        in_flight |= h.tx_trace[e]
        assert (h.commit_trace[e] <= in_flight).all(), e
        in_flight &= ~h.commit_trace[e]


def test_engine_clocks_non_decreasing_under_churn(churn_hist):
    h = churn_hist
    assert (h.latency_all >= 0).all()
    assert (np.diff(np.cumsum(h.latency_all)) >= 0).all()
    assert (h.async_trace["latency_cells"] >= 0).all()
    assert (h.age_trace >= 1).all()


# --------------------------------------------------------------------------
# coupled cross-cell fading: marginals survive the mixture
# --------------------------------------------------------------------------

_WCFG = WirelessConfig(n_devices=24, n_subchannels=4)


def test_coupled_fading_zero_coupling_bitwise_uncoupled():
    """coupling=0 must consume the rng stream exactly as C independent
    per-cell draws — the anchor that keeps C=1 hierarchies on the flat
    world stream."""
    proc = FadingProcess(kind="ar1", rho=0.8)
    a = sample_coupled_fading(np.random.default_rng(7), _WCFG, proc,
                              rounds=20, n_cells=3, coupling=0.0)
    rng = np.random.default_rng(7)
    b = np.stack([sample_fading(rng, _WCFG, proc, 20) for _ in range(3)])
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kind,rho", [("iid", 0.0), ("ar1", 0.6),
                                      ("ar1", 0.95)])
@pytest.mark.parametrize("coupling", [0.25, 0.7, 1.0])
def test_coupled_fading_preserves_exp1_marginals(kind, rho, coupling):
    """The cross-cell mixture sqrt(c)*shared + sqrt(1-c)*local of two
    independent CN(0,1) AR(1) streams with the same rho is again CN(0,1)
    AR(1), so per-cell power gains keep Exp(1) marginals (mean 1, var 1)
    at ANY coupling."""
    proc = FadingProcess(kind=kind, rho=rho)
    g2 = sample_coupled_fading(np.random.default_rng(11), _WCFG, proc,
                               rounds=400, n_cells=4, coupling=coupling)
    assert g2.shape == (4, 400, 4, 24)
    assert (g2 >= 0).all()
    for c in range(4):
        assert abs(g2[c].mean() - 1.0) < 0.05
        assert abs(g2[c].var() - 1.0) < 0.12


def test_coupled_fading_correlates_cells():
    """Coupling is real: the cross-cell correlation of the power gains
    increases with the coupling coefficient (and is ~0 uncoupled)."""
    proc = FadingProcess(kind="ar1", rho=0.7)

    def xcorr(coupling):
        g2 = sample_coupled_fading(np.random.default_rng(3), _WCFG, proc,
                                   rounds=300, n_cells=2, coupling=coupling)
        a, b = g2[0].ravel(), g2[1].ravel()
        return np.corrcoef(a, b)[0, 1]

    lo, mid, hi = xcorr(0.0), xcorr(0.5), xcorr(0.95)
    assert abs(lo) < 0.05
    assert lo < mid < hi
    assert hi > 0.6


def test_coupled_fading_validates_coupling():
    proc = FadingProcess(kind="iid")
    for bad in (-0.1, 1.01):
        with pytest.raises(ValueError):
            sample_coupled_fading(np.random.default_rng(0), _WCFG, proc,
                                  rounds=4, n_cells=2, coupling=bad)
