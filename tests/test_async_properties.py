"""Property tests for the buffered async server (hypothesis-guarded,
following the tests/test_scenario_properties.py convention: each @given
test skips individually without hypothesis, via the tests/_hyp.py shim).

Pins the protocol contracts documented in src/repro/fl/async_loop.py and
src/repro/fl/server.py:

  * staleness weights: f(0) == 1.0 EXACTLY (the bit-exact sync anchor),
    f in (0, 1], non-increasing in staleness, constant preset == 1.0;
  * commit weights normalize to 1 inside the weighted mean whenever
    anything commits (`masked_weighted_mean` divides by the mass);
  * `commit_event`: commits only in-flight devices, at most K per event,
    never negative latency; a buffer >= the in-flight count commits
    everything at the max remaining time (the sync barrier);
  * virtual clocks: for ANY dispatch pattern and clock trace the server
    time is non-decreasing, an upload never commits before its full
    Γ-time has elapsed, and the device-indexed event buffer (one slot
    per device) cannot overflow.

The check bodies live in module-level `_check_*` helpers so they can be
driven without hypothesis too (see the deterministic tests at the end,
which run a small pinned corpus through the same helpers).
"""
import numpy as np
import pytest

from _hyp import given, settings, st  # per-test skip without hypothesis

from repro.fl import (
    AGGREGATION_PRESETS,
    AsyncAggregation,
    aggregate_buffered,
    get_aggregation,
    masked_weighted_mean,
    staleness_weight,
)
from repro.fl.async_loop import commit_event


# ---------------------------------------------------------------------------
# check bodies (hypothesis-independent)
# ---------------------------------------------------------------------------

def _check_staleness_weight(stales, exponent):
    import jax.numpy as jnp

    s = jnp.asarray(stales, jnp.int32)
    w = np.asarray(staleness_weight(s, jnp.float32(exponent)))
    assert w.dtype == np.float32
    assert (w[np.asarray(stales) == 0] == 1.0).all()      # EXACT sync anchor
    assert ((w > 0) & (w <= 1.0)).all()
    order = np.argsort(stales)
    assert (np.diff(w[order]) <= 1e-7).all()              # non-increasing
    if exponent == 0.0:                                   # "const" preset
        assert (w == 1.0).all()


def _check_weight_normalization(weights):
    import jax.numpy as jnp

    w = jnp.asarray(weights, jnp.float32)
    ones = jnp.ones((len(weights), 1), jnp.float32)
    mean = float(masked_weighted_mean(ones, w)[0])
    wsum = float(w.sum())
    if wsum >= 1e-28:
        assert abs(mean - 1.0) < 1e-5    # weights normalize to 1
    elif wsum == 0.0:
        assert mean == 0.0               # zero mass contributes nothing
    else:
        # Sub-guard mass (< the 1e-30 zero-division guard): the mean
        # shrinks toward 0 instead of amplifying noise.
        assert 0.0 <= mean <= 1.0 + 1e-5


def _check_commit_event(rem, active, buffer, k):
    import jax.numpy as jnp

    rem = jnp.asarray(rem, jnp.float32)
    active_j = jnp.asarray(active)
    delta, commit = commit_event(rem, active_j, jnp.int32(buffer), k)
    delta = float(delta)
    commit = np.asarray(commit)
    active = np.asarray(active)
    assert delta >= 0.0
    assert not (commit & ~active).any()          # commits only in flight
    assert commit.sum() <= k                     # server drains <= K/event
    if not active.any():
        assert delta == 0.0 and not commit.any()
        return
    rem_np = np.asarray(rem)
    if buffer >= active.sum():
        # Full buffer == the sync barrier: everything commits at max rem.
        assert delta == rem_np[active].max()
        assert (commit == active).all() or active.sum() > k
    # Every commit had arrived by the commit time; every arrival beyond
    # the K cap stays pending.
    assert (rem_np[commit] <= delta).all()
    uncommitted_arrived = active & ~commit & (rem_np <= delta)
    assert uncommitted_arrived.sum() == 0 or commit.sum() == k


def _check_virtual_clocks(n, k, buffer, dispatch_wants, upload_times):
    """Run an arbitrary dispatch/clock schedule through `commit_event`
    and verify the event-timeline invariants."""
    import jax.numpy as jnp

    rem = jnp.zeros(n, jnp.float32)
    active = np.zeros(n, bool)
    started = np.full(n, np.nan)
    t_len = np.full(n, np.nan)
    t_now = 0.0
    for want, times in zip(dispatch_wants, upload_times):
        # The engine gates dispatch on free-ness and has <= min(K, N)
        # transmit slots; mimic both.
        req = np.asarray(want) & ~active
        ids = np.where(req)[0][: min(k, n)]
        dispatch = np.zeros(n, bool)
        dispatch[ids] = True
        # One slot per device: a dispatch can never land on an occupied
        # slot, so the buffer structurally cannot overflow.
        assert not (dispatch & active).any()
        active |= dispatch
        assert active.sum() <= n
        started[dispatch] = t_now
        t_len[dispatch] = np.asarray(times)[dispatch]
        rem = jnp.where(jnp.asarray(dispatch),
                        jnp.asarray(times, jnp.float32), rem)
        delta, commit = commit_event(rem, jnp.asarray(active),
                                     jnp.int32(buffer), k)
        delta = float(delta)
        commit = np.asarray(commit)
        assert delta >= 0.0                       # server clock monotone
        t_now += delta
        # An upload never commits before its full Γ-time has elapsed
        # (tolerance: float32 remaining-time decrements).
        for i in np.where(commit)[0]:
            assert t_now - started[i] >= t_len[i] - 1e-3 * (1.0 + t_len[i])
        active &= ~commit
        rem = jnp.where(jnp.asarray(active), rem - delta, jnp.float32(0.0))
    return t_now


# ---------------------------------------------------------------------------
# hypothesis drivers
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 500), min_size=1, max_size=32),
       st.floats(0.0, 4.0))
def test_staleness_weight_properties(stales, exponent):
    _check_staleness_weight(stales, exponent)


@given(st.lists(st.floats(0.0, 100.0), min_size=1, max_size=16))
def test_commit_weights_normalize_to_one(weights):
    _check_weight_normalization(weights)


@given(st.data())
@settings(max_examples=50, deadline=None)
def test_commit_event_protocol(data):
    n = data.draw(st.integers(1, 12))
    k = data.draw(st.integers(1, 6))
    buffer = data.draw(st.integers(1, n + 3))
    rem = data.draw(st.lists(st.floats(0.001, 50.0), min_size=n, max_size=n))
    active = data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
    _check_commit_event(rem, active, buffer, k)


@given(st.data())
@settings(max_examples=25, deadline=None)
def test_virtual_clocks_for_any_trace(data):
    n = data.draw(st.integers(2, 10))
    k = data.draw(st.integers(1, 4))
    buffer = data.draw(st.integers(1, n))
    rounds = data.draw(st.integers(1, 10))
    wants = data.draw(st.lists(
        st.lists(st.booleans(), min_size=n, max_size=n),
        min_size=rounds, max_size=rounds))
    times = data.draw(st.lists(
        st.lists(st.floats(0.01, 8.0), min_size=n, max_size=n),
        min_size=rounds, max_size=rounds))
    _check_virtual_clocks(n, k, buffer, wants, times)


# ---------------------------------------------------------------------------
# deterministic pinned corpus (runs with or without hypothesis)
# ---------------------------------------------------------------------------

def test_pinned_corpus_through_check_bodies(rng):
    """A small seeded corpus through the same helpers, so the protocol
    contracts stay exercised on boxes without hypothesis."""
    _check_staleness_weight([0, 1, 2, 5, 100], 0.5)
    _check_staleness_weight([0, 3, 7], 0.0)
    _check_weight_normalization([0.0, 2.5, 40.0])
    _check_weight_normalization([0.0, 0.0])
    for _ in range(25):
        n = int(rng.integers(1, 12))
        k = int(rng.integers(1, 6))
        _check_commit_event(rng.uniform(0.01, 50.0, n),
                            rng.random(n) < 0.6,
                            int(rng.integers(1, n + 3)), k)
    for _ in range(5):
        n, k = int(rng.integers(2, 10)), int(rng.integers(1, 4))
        rounds = int(rng.integers(1, 10))
        _check_virtual_clocks(
            n, k, int(rng.integers(1, n)),
            [rng.random(n) < 0.5 for _ in range(rounds)],
            [rng.uniform(0.01, 8.0, n) for _ in range(rounds)])


def test_staleness_zero_is_exactly_one():
    import jax.numpy as jnp

    w = staleness_weight(jnp.zeros(4, jnp.int32), jnp.float32(0.7))
    assert (np.asarray(w) == 1.0).all()


def test_aggregation_spec_resolution():
    assert get_aggregation("sync") is None
    assert get_aggregation("async") == AsyncAggregation()
    assert get_aggregation("async_const").stale_exponent() == 0.0
    assert get_aggregation("async_full").resolve_buffer(20, 4) == 20
    assert AsyncAggregation().resolve_buffer(20, 4) == 2       # K // 2
    assert AsyncAggregation().resolve_buffer(20, 1) == 1       # floor 1
    assert AsyncAggregation(buffer=3).resolve_buffer(20, 4) == 3
    for b in (4, 7):                  # >= K silently means "sync barrier"
        with pytest.raises(ValueError):
            AsyncAggregation(buffer=b).resolve_buffer(20, 4)
    assert AsyncAggregation(buffer=1).resolve_buffer(20, 1) == 1  # K=1 exempt
    spec = get_aggregation(AsyncAggregation(buffer=3))
    assert spec is not None and spec.buffer == 3
    assert set(AGGREGATION_PRESETS) == {"async", "async_const", "async_full"}
    with pytest.raises(ValueError):
        get_aggregation("nope")


def test_aggregate_buffered_endpoints():
    """server_lr == 1 must be bitwise eq.-34; an empty commit must be
    bitwise identity; intermediate step sizes land strictly between."""
    import jax
    import jax.numpy as jnp

    from repro.fl import aggregate

    # Both sides compiled, as in the engines: eager op-by-op dispatch may
    # sum the weighted mean in a different order than the fused program.
    aggregate_buffered_jit = jax.jit(aggregate_buffered)
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
    c = {"w": jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)}
    w = jnp.asarray([1.0, 0.0, 2.0, 0.5, 0.0], jnp.float32)
    sync = aggregate(g, c, w)
    full_step = aggregate_buffered_jit(g, c, w, jnp.float32(1.0))
    np.testing.assert_array_equal(np.asarray(sync["w"]),
                                  np.asarray(full_step["w"]))
    nothing_committed = aggregate_buffered_jit(
        g, c, jnp.zeros(5, jnp.float32), jnp.float32(1.0))
    np.testing.assert_array_equal(np.asarray(g["w"]),
                                  np.asarray(nothing_committed["w"]))
    # Strictly between the endpoints the commit moves the model partway.
    mixed = aggregate_buffered_jit(g, c, w, jnp.float32(0.4))
    assert not np.array_equal(np.asarray(mixed["w"]), np.asarray(g["w"]))
    assert not np.array_equal(np.asarray(mixed["w"]), np.asarray(sync["w"]))
    with pytest.raises(ValueError):
        AsyncAggregation(server_lr=0.0)
    with pytest.raises(ValueError):
        AsyncAggregation(server_lr=1.5)
