"""Differential harness: the two-tier buffered async hierarchy vs its limits.

The city-scale engine (`fl.hier_async`, DESIGN.md §15) is pinned from
three directions:

  * degenerate SYNC limit — full buffers at BOTH tiers make every cell
    commit its whole dispatch at its own event and every cell flight
    commit at the same global event, so the two-tier event loop must
    reproduce the synchronous hierarchy (`engine="scan"`) BIT-EXACTLY
    across the policy x scenario matrix; uniform per-device clocks
    collapse ANY buffer pair to the same limit;
  * degenerate FLAT limit — a hierarchy of ONE cell has a single global
    slot whose commits mirror the cell commits one-for-one, so every
    trace must equal the flat `engine="async"` path bit-for-bit;
  * program identity — vmapped grid members == solo runs, sharded ==
    unsharded, and the segmented carry (`build_hier_async_runner(
    segmented=True)`) chains into exactly the one-scan trajectory.

Set REPRO_DIFF_BACKEND=pallas to solve Γ through the interpret-mode
Pallas projection backend (CI's hier-async-differential job runs the
default).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RoundPolicy
from repro.fl import AsyncAggregation, SimConfig, run_many
from repro.fl.hier_async import build_hier_async_runner, init_hier_async_carry
from repro.fl.hierarchical import (
    HierSimConfig,
    _apply_hier_dynamics,
    _hier_scan_inputs,
    _prepare_hier,
    _solve_hier_horizons,
    run_hier_many,
    run_hierarchical,
)
from repro.fl.sim import _eval_rounds, _group_trainer_and_policies

RA_BACKEND = os.environ.get("REPRO_DIFF_BACKEND") or None

_SMALL = dict(rounds=6, n_cells=2, devices_per_cell=8, subchannels_per_cell=3,
              n_samples=96, batch=16, local_steps=2, eval_every=2)

# The pinned RoundPolicy x scenario matrix (>= 8 combos): the proposed
# policy across the scenario presets, plus baseline policies crossed with
# the stressful ones.
POLICY_SCENARIOS = [
    ("alg3", "mo", "matching", "static"),
    ("alg3", "mo", "matching", "corr_fading"),
    ("alg3", "mo", "matching", "mobility"),
    ("alg3", "mo", "matching", "churn"),
    ("alg3", "mo", "matching", "urban"),
    ("aou_topk", "mo", "matching", "churn"),
    ("random", "fix", "random", "urban"),
    ("cluster", "mo", "random", "churn"),
    ("fixed", "fix", "matching", "urban"),
    ("random", "mo", "matching", "harvest"),
]


def _cfg(**kw):
    base = dict(_SMALL, dataset="mnist")
    base.update(kw)
    return HierSimConfig(**base)


def _assert_bit_exact(sync, asy):
    """The sync-limit contract: EVERYTHING the sync hierarchy records
    must match bit-for-bit, every cell dispatch must commit at its own
    event, and every cell flight must commit at the same global event."""
    np.testing.assert_array_equal(sync.tx_trace, asy.tx_trace)
    np.testing.assert_array_equal(sync.age_trace, asy.age_trace)
    np.testing.assert_array_equal(sync.latency_all, asy.latency_all)
    np.testing.assert_array_equal(sync.energy_all, asy.energy_all)
    np.testing.assert_array_equal(sync.global_loss, asy.global_loss)
    np.testing.assert_array_equal(sync.accuracy, asy.accuracy)
    np.testing.assert_array_equal(sync.n_selected, asy.n_selected)
    np.testing.assert_array_equal(sync.n_transmitted, asy.n_transmitted)
    np.testing.assert_array_equal(asy.commit_trace, sync.tx_trace)
    assert not asy.async_trace["overflow"].any()
    assert asy.async_trace["n_pending"].max() == 0
    assert asy.async_trace["g_pending"].max() == 0


def _assert_hist_equal(a, b):
    """Full bitwise trace identity between two async hierarchy runs."""
    np.testing.assert_array_equal(a.tx_trace, b.tx_trace)
    np.testing.assert_array_equal(a.commit_trace, b.commit_trace)
    np.testing.assert_array_equal(a.age_trace, b.age_trace)
    np.testing.assert_array_equal(a.latency_all, b.latency_all)
    np.testing.assert_array_equal(a.energy_all, b.energy_all)
    np.testing.assert_array_equal(a.global_loss, b.global_loss)
    np.testing.assert_array_equal(a.async_trace["n_pending"],
                                  b.async_trace["n_pending"])
    np.testing.assert_array_equal(a.async_trace["cell_committed"],
                                  b.async_trace["cell_committed"])
    np.testing.assert_array_equal(a.async_trace["latency_cells"],
                                  b.async_trace["latency_cells"])


# --------------------------------------------------------------------------
# (a) full buffers at both tiers == the sync hierarchy
# --------------------------------------------------------------------------

@pytest.mark.parametrize("ds,ra,sa,scenario", POLICY_SCENARIOS,
                         ids=[f"{d}-{r}-{s}-{sc}"
                              for d, r, s, sc in POLICY_SCENARIOS])
def test_hier_async_full_buffers_bit_exact_vs_scan(ds, ra, sa, scenario):
    """engine="async" with the full-buffer barrier at BOTH tiers ==
    engine="scan", bit-for-bit, across the policy x scenario matrix."""
    cfg = _cfg(policy=RoundPolicy(ds=ds, ra=ra, sa=sa), scenario=scenario)
    sync = run_hier_many([cfg], engine="scan", ra_backend=RA_BACKEND)[0]
    asy = run_hier_many([cfg], engine="async", ra_backend=RA_BACKEND)[0]
    _assert_bit_exact(sync, asy)


def test_hier_async_full_buffers_any_staleness_bit_exact():
    """With full buffers no commit is ever stale at either tier, so the
    staleness presets cannot perturb the limit (f(0) == 1.0 exactly)."""
    cfg = _cfg(scenario="churn")
    sync = run_hier_many([cfg], engine="scan", ra_backend=RA_BACKEND)[0]
    for agg, g_agg in (
            (AsyncAggregation(buffer="full", staleness="poly"),
             AsyncAggregation(buffer="full", staleness="poly")),
            ("async_full", "async_full"),
            (AsyncAggregation(buffer="full", staleness="const",
                              exponent=0.0), "sync")):
        asy = run_hier_many(
            [_cfg(scenario="churn", aggregation=agg,
                  global_aggregation=g_agg)],
            ra_backend=RA_BACKEND)[0]
        _assert_bit_exact(sync, asy)


def test_hier_uniform_clocks_any_buffers_degenerate_to_sync(monkeypatch):
    """With uniform per-device clocks every upload of an event ties at
    the cell tier AND every cell flight ties at the global tier, so ANY
    buffer pair commits everything together — the two-tier event loop
    collapses to the synchronous barrier even at buffer=1/g_buffer=1.
    Uniform clocks are forced by flattening the solved Γ to a constant
    (slowdown-free scenario: `apply_dynamics` re-stretching IS
    non-uniform clocks)."""
    from repro.fl import hierarchical as hier_mod

    orig = hier_mod._solve_hier_horizons

    def flat_gamma(preps, backend, **kw):
        ras_list, secs = orig(preps, backend, **kw)
        flat = []
        for ras in ras_list:
            flat.append([
                type(ra)(tau=ra.tau, p=ra.p,
                         time_s=np.where(ra.feasible, 1.0, np.inf),
                         energy_j=ra.energy_j, feasible=ra.feasible,
                         iterations=ra.iterations)
                for ra in ras])
        return flat, secs

    monkeypatch.setattr(hier_mod, "_solve_hier_horizons", flat_gamma)
    cfg = _cfg(scenario="static")
    sync = run_hier_many([cfg], engine="scan", ra_backend=RA_BACKEND)[0]
    asy = run_hier_many(
        [_cfg(scenario="static",
              aggregation=AsyncAggregation(buffer=1),
              global_aggregation=AsyncAggregation(buffer=1))],
        ra_backend=RA_BACKEND)[0]
    _assert_bit_exact(sync, asy)


# --------------------------------------------------------------------------
# (b) a hierarchy of one cell == the flat async engine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("aggregation,scenario", [
    ("async", "urban"), ("async_const", "churn"),
    (AsyncAggregation(buffer=1, staleness="poly", exponent=1.0), "churn"),
])
def test_single_cell_hierarchy_bit_exact_vs_flat_async(aggregation, scenario):
    """C=1 collapses the global tier to a single slot committing in
    lockstep with the cell server, so every trace — dispatches, commits,
    clocks, losses — must equal the flat `engine="async"` path
    bit-for-bit."""
    flat = SimConfig(dataset="mnist", n_devices=8, n_subchannels=3,
                     rounds=6, n_samples=96, batch=16, local_steps=2,
                     eval_every=2, aggregation=aggregation,
                     scenario=scenario)
    hier = _cfg(n_cells=1, aggregation=aggregation, scenario=scenario)
    hf = run_many([flat], engine="async", ra_backend=RA_BACKEND)[0]
    hh = run_hier_many([hier], engine="async", ra_backend=RA_BACKEND)[0]
    for name in ("global_loss", "accuracy", "latency_all", "energy_all",
                 "tx_trace", "age_trace", "commit_trace", "cum_time_s",
                 "n_selected", "n_transmitted"):
        np.testing.assert_array_equal(getattr(hf, name), getattr(hh, name),
                                      err_msg=name)
    for k in ("n_pending", "rem_dispatch", "overflow"):
        np.testing.assert_array_equal(hf.async_trace[k], hh.async_trace[k],
                                      err_msg=k)
    # The lone global slot flies exactly when the cell commits something.
    cell_commits = hh.commit_trace.any(axis=1)
    np.testing.assert_array_equal(
        hh.async_trace["cell_committed"][:, 0], cell_commits)


def test_single_cell_sync_hierarchy_matches_flat_scan():
    """The C=1 anchor of the anchor: the sync hierarchy itself consumes
    the flat world stream bit-identically at one cell."""
    flat = SimConfig(dataset="mnist", n_devices=8, n_subchannels=3,
                     rounds=6, n_samples=96, batch=16, local_steps=2,
                     eval_every=2, scenario="urban")
    hier = _cfg(n_cells=1, scenario="urban")
    hf = run_many([flat], engine="scan", ra_backend=RA_BACKEND)[0]
    hh = run_hier_many([hier], engine="scan", ra_backend=RA_BACKEND)[0]
    for name in ("global_loss", "accuracy", "latency_all", "energy_all",
                 "tx_trace", "age_trace"):
        np.testing.assert_array_equal(getattr(hf, name), getattr(hh, name),
                                      err_msg=name)


# --------------------------------------------------------------------------
# (c) program identity: vmap == solo, shard == vmap
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_hier_async_vmap_matches_solo():
    """run_hier_many's vmapped two-tier engine == per-cell solo runs,
    bit-exact, across a seed x aggregation grid (one compiled program
    per shape — the four commit-policy operands are traced data)."""
    cfgs = [_cfg(seed=s, scenario="churn", aggregation=a,
                 global_aggregation=g)
            for s in (0, 1) for a, g in (("async", "async"),
                                         ("async_const", "sync"),
                                         ("sync", "async"))]
    vmapped = run_hier_many(cfgs, engine="async", ra_backend=RA_BACKEND)
    for c, v in zip(cfgs, vmapped):
        solo = run_hier_many([c], engine="async", ra_backend=RA_BACKEND)[0]
        _assert_hist_equal(v, solo)


@pytest.mark.slow
def test_hier_async_sharded_dispatch_matches_vmap():
    """shard=True on 2 forced host devices == unsharded vmap, bit-for-bit
    (separate process: device count must be set before JAX initializes)."""
    code = """
import numpy as np
from repro.fl.hierarchical import HierSimConfig, run_hier_many
cfgs = [HierSimConfig(dataset="mnist", rounds=4, n_cells=2,
                      devices_per_cell=6, subchannels_per_cell=2,
                      n_samples=48, batch=8, local_steps=2, eval_every=2,
                      seed=s, scenario="churn", aggregation="async")
        for s in (0, 1, 2)]
sh = run_hier_many(cfgs, engine="async", shard=True)
un = run_hier_many(cfgs, engine="async", shard=False)
for a, b in zip(sh, un):
    assert np.array_equal(a.tx_trace, b.tx_trace)
    assert np.array_equal(a.commit_trace, b.commit_trace)
    assert np.array_equal(a.global_loss, b.global_loss)
    assert np.array_equal(a.latency_all, b.latency_all)
print("HIER_SHARD_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "HIER_SHARD_OK" in proc.stdout


# --------------------------------------------------------------------------
# (d) segmented carry: chained segments == one unsegmented scan
# --------------------------------------------------------------------------

def test_hier_segmented_carry_matches_one_scan():
    """`build_hier_async_runner(segmented=True)` must chain the full
    15-slot two-tier carry across segments so that serving the grid in
    pieces is bit-identical to one unsegmented scan on EVERY ys trace —
    the property that lets the sustained-service harness stream the
    city."""
    cfg = _cfg(rounds=8, scenario="urban", aggregation="async",
               global_aggregation=AsyncAggregation(buffer=1))
    prep = _prepare_hier(cfg)
    ras_list, _ = _solve_hier_horizons([prep], RA_BACKEND)
    ras = _apply_hier_dynamics(prep, ras_list[0])
    model, trainer, policies, _ = _group_trainer_and_policies([cfg])
    data = _hier_scan_inputs(prep, ras, int(prep.x.shape[2]))
    spec = AsyncAggregation(buffer=None, staleness="poly")
    g_spec = AsyncAggregation(buffer=1)
    data["buffer"] = jnp.int32(spec.resolve_buffer(
        cfg.devices_per_cell, cfg.subchannels_per_cell))
    data["stale_exp"] = jnp.float32(spec.stale_exponent())
    data["server_lr"] = jnp.float32(spec.server_lr)
    data["g_buffer"] = jnp.int32(g_spec.resolve_buffer(cfg.n_cells,
                                                       cfg.n_cells))
    data["g_stale_exp"] = jnp.float32(g_spec.stale_exponent())
    data["g_server_lr"] = jnp.float32(g_spec.server_lr)
    eval_mask = np.zeros(cfg.rounds, bool)
    eval_mask[_eval_rounds(cfg.rounds, cfg.eval_every)] = True

    whole = jax.jit(build_hier_async_runner(
        model, trainer, policies, n_cells=cfg.n_cells,
        k=cfg.subchannels_per_cell, n=cfg.devices_per_cell,
        rounds=cfg.rounds, eval_mask=eval_mask))(data)

    seg_len = 4
    seg_run = jax.jit(build_hier_async_runner(
        model, trainer, policies, n_cells=cfg.n_cells,
        k=cfg.subchannels_per_cell, n=cfg.devices_per_cell,
        rounds=seg_len, eval_mask=np.ones(seg_len, bool),
        segmented=True))
    carry = init_hier_async_carry(data["params0"], data["key0"],
                                  cfg.n_cells, cfg.devices_per_cell)
    chunks = []
    per_round = ("gamma", "feas", "energy", "sel_perms", "assign_perms")
    for t0 in range(0, cfg.rounds, seg_len):
        seg = dict(data, t0=jnp.int32(t0),
                   **{k: data[k][t0:t0 + seg_len] for k in per_round})
        carry, ys = seg_run(seg, carry)
        chunks.append(jax.tree_util.tree_map(np.asarray, ys))
    chained = jax.tree_util.tree_map(
        lambda *leaves: np.concatenate(leaves), *chunks)

    whole = jax.tree_util.tree_map(np.asarray, whole)
    assert set(chained) == set(whole)
    for name in whole:
        if name in ("loss", "acc", "gnorm"):
            # Segment eval masks differ (every event) from the whole
            # run's eval_every sampling; compare where BOTH evaluated.
            ev = eval_mask
            np.testing.assert_array_equal(whole[name][ev],
                                          chained[name][ev], err_msg=name)
        else:
            np.testing.assert_array_equal(whole[name], chained[name],
                                          err_msg=name)


# --------------------------------------------------------------------------
# satellites: eval-trace gap + async-beats-sync under churn
# --------------------------------------------------------------------------

def test_hierarchical_eval_every_full_traces():
    """The PR-2 `cum_time_s` lesson, hierarchical edition: under
    eval_every=5, latency/energy/tx/age must still be recorded for EVERY
    round (bit-equal to the eval_every=1 run), loss/accuracy sampled at
    the eval rounds, and cum_time_s accumulated over ALL rounds."""
    dense = run_hierarchical(_cfg(rounds=10, eval_every=1, scenario="urban"),
                             engine="scan", ra_backend=RA_BACKEND)
    sparse = run_hierarchical(_cfg(rounds=10, eval_every=5, scenario="urban"),
                              engine="scan", ra_backend=RA_BACKEND)
    np.testing.assert_array_equal(sparse["eval_rounds"], [0, 5, 9])
    for name in ("latency", "energy", "tx", "age"):
        assert sparse[name].shape[0] == 10, name
        np.testing.assert_array_equal(sparse[name], dense[name],
                                      err_msg=name)
    np.testing.assert_array_equal(sparse["loss"],
                                  dense["loss"][[0, 5, 9]])
    np.testing.assert_array_equal(sparse["accuracy"],
                                  dense["accuracy"][[0, 5, 9]])
    np.testing.assert_allclose(sparse["cum_time_s"],
                               np.cumsum(dense["latency"])[[0, 5, 9]])
    # Same contract through the async engine.
    asparse = run_hierarchical(
        _cfg(rounds=10, eval_every=5, scenario="urban",
             aggregation="async"), ra_backend=RA_BACKEND)
    for name in ("latency", "energy", "tx", "age", "committed"):
        assert asparse[name].shape[0] == 10, name


@pytest.mark.parametrize("g_buffer", [1, "full"])
def test_hier_async_cum_time_monotonic_under_churn(g_buffer):
    """The two-tier buffered servers never wait longer than the two-tier
    eq.-9 barrier: async cumulative simulated time <= sync under the
    straggler scenario, for partial cell buffers at either global
    policy."""
    for seed in (0, 1):
        cfg = _cfg(rounds=10, seed=seed, scenario="churn")
        sync = run_hier_many([cfg], engine="scan", ra_backend=RA_BACKEND)[0]
        asy = run_hier_many(
            [_cfg(rounds=10, seed=seed, scenario="churn",
                  aggregation=AsyncAggregation(buffer=1),
                  global_aggregation=AsyncAggregation(buffer=g_buffer))],
            ra_backend=RA_BACKEND)[0]
        assert asy.cum_time_s[-1] <= sync.cum_time_s[-1]
        assert (asy.latency_all >= 0).all()
        assert not asy.async_trace["overflow"].any()


def test_hier_engine_validation():
    with pytest.raises(ValueError):
        run_hierarchical(_cfg(), engine="warp")
    with pytest.raises(ValueError):
        run_hier_many([_cfg()], engine="loop")
    with pytest.raises(ValueError):
        run_hier_many([_cfg(aggregation="warp")])
    with pytest.raises(ValueError):
        _prepare_hier(_cfg(cell_coupling=1.5))
