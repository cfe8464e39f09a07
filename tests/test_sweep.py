"""Sweep-harness tests (repro.experiments + the run_many sweep substrate).

Pins the harness's core contract — a sweep cell's trajectory is IDENTICAL
to a solo `run_simulation` call — plus the grouping machinery behind it:
policy-only variants share one prepared world and one Γ solve, different
(N, K) shapes land in different compiled-program groups, and artifacts
version monotonically.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import RoundPolicy, policy_grid
from repro.experiments import (
    SweepSpec,
    load_latest,
    load_record,
    mean_subchannel_utilization,
    rounds_to_target,
    run_sweep,
    time_to_target_s,
)
from repro.experiments.store import latest_dir, next_version_dir, write_record
from repro.fl import SimConfig, run_simulation
from repro.fl.sim import _prep_key, _scan_group_key

TINY = dict(n_samples=64, batch=8, eval_every=2, local_steps=2)


# --------------------------------------------------------------------------
# spec expansion
# --------------------------------------------------------------------------

def test_policy_grid_order_and_validation():
    grid = policy_grid(ds=("alg3", "random"), ra=("mo", "fix"))
    assert [(p.ds, p.ra) for p in grid] == [
        ("alg3", "mo"), ("alg3", "fix"), ("random", "mo"), ("random", "fix")]
    assert policy_grid(ds="cluster")[0] == RoundPolicy(ds="cluster")
    with pytest.raises(ValueError):
        policy_grid(ds="nope")


def test_spec_expansion_stable_ids():
    spec = SweepSpec(name="t", datasets="mnist", ds=("alg3", "random"),
                     seeds=(0, 1), rounds=4, n_devices=(8, 10),
                     n_subchannels=3, overrides={"n_samples": 32})
    cells = spec.cells()
    assert spec.n_cells == len(cells) == 8
    # dataset > (N, K) > policy > seed, ids stable and unique
    assert cells[0].cell_id == "mnist-N8-K3-alg3.mo.matching-s0"
    assert cells[1].cell_id == "mnist-N8-K3-alg3.mo.matching-s1"
    assert cells[2].cell_id == "mnist-N8-K3-random.mo.matching-s0"
    assert cells[4].cell_id == "mnist-N10-K3-alg3.mo.matching-s0"
    assert len({c.cell_id for c in cells}) == 8
    assert all(c.config.n_samples == 32 for c in cells)
    # round-trips through JSON
    assert SweepSpec.from_json(spec.to_json()) == spec


def test_spec_rejects_bad_inputs():
    with pytest.raises(ValueError):
        SweepSpec(name="bad/name")
    with pytest.raises(ValueError):
        SweepSpec(name="t", overrides={"n_devices": 5})   # grid axis
    with pytest.raises(ValueError):
        SweepSpec(name="t", overrides={"typo_field": 1})
    with pytest.raises(ValueError):
        SweepSpec(name="t", ds="unknown-scheme")
    with pytest.raises(ValueError):
        SweepSpec(name="t", aggregation="warp")
    with pytest.raises(ValueError):                       # grid axis too
        SweepSpec(name="t", overrides={"aggregation": "async"})


def test_spec_aggregation_axis():
    """The aggregation axis expands between scenario and policy, keeps
    "sync" ids unchanged (committed artifacts stay addressable), and
    round-trips through JSON."""
    spec = SweepSpec(name="t", ds="alg3", seeds=(0,), rounds=4,
                     n_devices=8, n_subchannels=3,
                     aggregation=("sync", "async"))
    cells = spec.cells()
    assert spec.n_cells == len(cells) == 2
    assert cells[0].cell_id == "mnist-N8-K3-alg3.mo.matching-s0"
    assert cells[1].cell_id == "mnist-N8-K3-async-alg3.mo.matching-s0"
    assert cells[0].config.aggregation == "sync"
    assert cells[1].config.aggregation == "async"
    assert SweepSpec.from_json(spec.to_json()) == spec


# --------------------------------------------------------------------------
# grouping: shapes, worlds, Γ reuse
# --------------------------------------------------------------------------

def test_scan_group_keys_mixed_shapes():
    base = SimConfig(rounds=4, **TINY)
    same = [dataclasses.replace(base, seed=1),
            dataclasses.replace(base, policy=RoundPolicy(ds="random")),
            dataclasses.replace(base, policy=RoundPolicy(ra="fix")),
            dataclasses.replace(base, radius_m=300.0)]
    for c in same:     # policy/seed/wireless-data variants share a program
        assert _scan_group_key(c) == _scan_group_key(base)
    diff = [dataclasses.replace(base, n_devices=32),
            dataclasses.replace(base, n_subchannels=8),
            dataclasses.replace(base, rounds=6),
            dataclasses.replace(base, dataset="sst2"),
            dataclasses.replace(base, eval_every=4)]
    for c in diff:     # shape changes compile separately
        assert _scan_group_key(c) != _scan_group_key(base)


def test_prep_key_shares_worlds_only_across_policies():
    base = SimConfig(rounds=4, **TINY)
    assert _prep_key(base) == _prep_key(
        dataclasses.replace(base, policy=RoundPolicy(ds="fixed", ra="fix")))
    # ... and across aggregation disciplines: sync vs async cells of one
    # seed share the sampled world and Γ solve (the differential setup).
    assert _prep_key(base) == _prep_key(
        dataclasses.replace(base, aggregation="async"))
    assert _prep_key(base) != _prep_key(dataclasses.replace(base, seed=1))
    assert _prep_key(base) != _prep_key(
        dataclasses.replace(base, n_devices=32))


def test_gamma_solved_once_per_world(monkeypatch):
    """A policy grid over one seed pays ONE Γ solve (and mixed shapes/seeds
    pay one each — no cross-world aliasing)."""
    import repro.fl.sim as sim

    calls = []
    real = sim.solve_pairs_fused    # the ra_solver="fused" default path

    def counting(beta, h2, wcfg, e_max=None, **kw):
        calls.append(np.asarray(h2).size)
        return real(beta, h2, wcfg, e_max, **kw)

    monkeypatch.setattr(sim, "solve_pairs_fused", counting)
    base = SimConfig(rounds=3, n_devices=6, n_subchannels=2, **TINY)
    cfgs = [dataclasses.replace(base, policy=RoundPolicy(ds=d))
            for d in ("alg3", "random", "cluster")]
    sim.run_many(cfgs, engine="loop")
    # One batched call, sized for ONE horizon (not 3x): policy variants
    # share the world's solve.
    assert len(calls) == 1
    assert calls[0] == 3 * 2 * 6
    calls.clear()
    sim.run_many(cfgs + [dataclasses.replace(base, seed=1)], engine="loop")
    # Still one flattened call, but now two worlds' pairs deep.
    assert len(calls) == 1 and calls[0] == 2 * (3 * 2 * 6)


# --------------------------------------------------------------------------
# cell results identical to solo runs
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_sweep_cells_bit_identical_to_solo(tmp_path):
    spec = SweepSpec(name="eq", datasets="mnist", ds=("alg3", "random"),
                     seeds=(0, 1), rounds=5, n_devices=8, n_subchannels=3,
                     target_loss=5.0, overrides=TINY)
    res = run_sweep(spec, results_root=tmp_path, figures=True)
    assert len(res.histories) == 4
    for cell, hist in zip(res.cells, res.histories):
        solo = run_simulation(cell.config, engine="scan")
        assert np.array_equal(hist.tx_trace, solo.tx_trace), cell.cell_id
        assert np.array_equal(hist.age_trace, solo.age_trace)
        assert np.array_equal(hist.global_loss, solo.global_loss)
        assert np.array_equal(hist.accuracy, solo.accuracy)
    # artifact round-trip agrees with the in-memory record
    rec = load_record(res.out_dir)
    assert rec["n_cells"] == 4
    ids = [c["id"] for c in rec["cells"]]
    assert ids == [c.cell_id for c in res.cells]
    for c in rec["cells"]:
        m = c["metrics"]
        assert 0.0 <= m["mean_subchannel_utilization"] <= 1.0
        assert m["rounds_to_target"] is None or m["rounds_to_target"] >= 1
    figs = sorted(p.name for p in (res.out_dir / "figures").iterdir())
    assert figs == ["convergence_rounds_mnist.svg", "convergence_time_mnist.svg",
                    "latency_cdf_mnist.svg", "utilization_mnist.svg"]


@pytest.mark.slow
def test_mixed_shape_grid_matches_solo():
    """Mixed N/K grids split into per-shape groups with no cross-group
    contamination: every cell still reproduces its solo trajectory."""
    spec = SweepSpec(name="mix", datasets="mnist", ds="alg3", seeds=0,
                     rounds=4, n_devices=(6, 9), n_subchannels=(2, 3),
                     overrides=TINY)
    res = run_sweep(spec, write=False)
    assert len(res.histories) == 4
    shapes = {(c.config.n_devices, c.config.n_subchannels) for c in res.cells}
    assert shapes == {(6, 2), (6, 3), (9, 2), (9, 3)}
    for cell, hist in zip(res.cells, res.histories):
        solo = run_simulation(cell.config, engine="scan")
        assert np.array_equal(hist.tx_trace, solo.tx_trace), cell.cell_id
        assert np.array_equal(hist.global_loss, solo.global_loss)


@pytest.mark.slow
def test_sharded_dispatch_matches_vmap():
    """shard=auto on 2 forced host devices == unsharded vmap, bit-for-bit
    (separate process: device count must be set before JAX initializes)."""
    code = """
import numpy as np
from repro.core import RoundPolicy
from repro.fl import SimConfig, run_many
cfgs = [SimConfig(dataset="mnist", rounds=4, n_devices=6, n_subchannels=2,
                  n_samples=48, batch=8, eval_every=2, seed=0,
                  policy=RoundPolicy(ds=d))
        for d in ("alg3", "random", "fixed")]
sh = run_many(cfgs, engine="scan", shard=True)
un = run_many(cfgs, engine="scan", shard=False)
for a, b in zip(sh, un):
    assert np.array_equal(a.tx_trace, b.tx_trace)
    assert np.array_equal(a.global_loss, b.global_loss)
print("SHARD_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD_OK" in proc.stdout


# --------------------------------------------------------------------------
# figures: faceting never pools heterogeneous configs
# --------------------------------------------------------------------------

def _toy_record(keys):
    """Minimal record with one cell per (dataset, N, K, ra, sa, ds, seed)."""
    cells = []
    for d, n, k, ra, sa, ds, seed in keys:
        cells.append({
            "id": f"{d}-N{n}-K{k}-{ds}.{ra}.{sa}-s{seed}",
            "dataset": d, "n_devices": n, "n_subchannels": k, "seed": seed,
            "policy": {"ds": ds, "ra": ra, "sa": sa,
                       "label": f"{ds}+{ra}+{sa}"},
            "metrics": {"mean_subchannel_utilization": 0.5},
            "curves": {"round": [0, 1], "global_loss": [2.0, 1.0],
                       "accuracy": [0.1, 0.2], "cum_time_s": [1.0, 2.0]},
            "trace": {"latency_s": [1.0, 1.0], "utilization": [0.5, 0.5]},
        })
    return {"schema": 1, "cells": cells}


def test_facets_split_heterogeneous_records(tmp_path):
    from repro.experiments import facets, render_gallery

    rec = _toy_record([
        ("mnist", 8, 2, "mo", "matching", "alg3", 0),
        ("mnist", 8, 2, "mo", "matching", "random", 0),
        ("mnist", 16, 4, "mo", "matching", "alg3", 0),   # second shape
        ("mnist", 8, 2, "fix", "random", "alg3", 0),     # second (ra, sa)
    ])
    fs = facets(rec)
    assert len(fs) == 3           # (8,2,mo,matching), (16,4,...), (8,2,fix,random)
    assert {f.suffix for f in fs} == {
        "mnist-N8-K2-mo.matching", "mnist-N16-K4-mo.matching",
        "mnist-N8-K2-fix.random"}
    paths = render_gallery(rec, tmp_path)
    assert len(paths) == 12       # 4 figures per facet, no pooling
    # homogeneous record keeps the short suffix (committed artifact names)
    homo = _toy_record([("mnist", 8, 2, "mo", "matching", "alg3", s)
                        for s in (0, 1)])
    assert [f.suffix for f in facets(homo)] == ["mnist"]


def test_fig_time_to_target_refuses_pooling(tmp_path):
    """The sync-vs-async headline figure averages SEEDS only: records
    varying ra/sa (or shape/dataset) within the chosen ds render nothing
    rather than pooling configurations that were never co-simulated."""
    from repro.experiments import fig_time_to_target

    def cell(agg, ra, seed, t2t):
        return {"dataset": "mnist", "n_devices": 8, "n_subchannels": 3,
                "scenario": "static", "aggregation": agg, "seed": seed,
                "policy": {"ds": "alg3", "ra": ra, "sa": "matching",
                           "label": "x"},
                "metrics": {"time_to_target_s": t2t},
                "curves": {}, "trace": {}}

    homogeneous = {"cells": [cell("sync", "mo", 0, 10.0),
                             cell("sync", "mo", 1, 12.0),
                             cell("async", "mo", 0, 2.0),
                             cell("async", "mo", 1, 3.0)]}
    assert fig_time_to_target(homogeneous, tmp_path) is not None
    mixed_ra = {"cells": homogeneous["cells"]
                + [cell("sync", "fix", 0, 99.0)]}
    assert fig_time_to_target(mixed_ra, tmp_path) is None
    sync_only = {"cells": [cell("sync", "mo", 0, 10.0)]}
    assert fig_time_to_target(sync_only, tmp_path) is None


def test_group_mean_curves_refuses_ambiguity():
    from repro.experiments import group_mean_curves

    rec = _toy_record([
        ("mnist", 8, 2, "mo", "matching", "alg3", 0),
        ("mnist", 16, 2, "mo", "matching", "alg3", 0),
    ])
    with pytest.raises(ValueError, match="n_devices"):
        group_mean_curves(rec)
    out = group_mean_curves(rec, n_devices=8)
    assert list(out) == ["alg3+mo+matching"]
    np.testing.assert_allclose(out["alg3+mo+matching"][1], [2.0, 1.0])


# --------------------------------------------------------------------------
# metrics + store
# --------------------------------------------------------------------------

def _fake_history(losses, rounds, lat):
    from repro.fl.sim import SimHistory
    ev = np.asarray(rounds)
    lat = np.asarray(lat, float)
    return SimHistory(
        label="t", rounds=ev, global_loss=np.asarray(losses, float),
        accuracy=np.zeros(len(ev)), latency_s=lat[ev],
        cum_time_s=np.cumsum(lat)[ev], n_selected=np.zeros(len(ev)),
        n_transmitted=np.zeros(len(ev)), energy_j=np.zeros(len(ev)),
        deficits=np.zeros(len(ev)), grad_sq_norms=np.zeros(len(ev)),
        beta=np.ones(4), wall_s=0.0, latency_all=lat,
        energy_all=np.zeros(len(lat)),
        tx_trace=np.array([[1, 1, 0, 0]] * len(lat), bool),
        age_trace=np.ones((len(lat), 4), np.int64))


def test_derived_metrics():
    h = _fake_history(losses=[3.0, 1.9, 1.2], rounds=[0, 2, 4],
                      lat=[2.0, 1.0, 2.0, 1.0, 4.0])
    assert rounds_to_target(h, 2.0) == 3          # eval round 2, 1-based
    assert rounds_to_target(h, 0.5) is None
    assert time_to_target_s(h, 2.0) == pytest.approx(5.0)  # cumsum at t=2
    assert mean_subchannel_utilization(h, 2) == pytest.approx(1.0)
    assert mean_subchannel_utilization(h, 4) == pytest.approx(0.5)


def test_utilization_fallback_is_explicit_and_weighted():
    """Without a full tx_trace, utilization is eval-sampled: the silent
    per-round pretence raises, and the mean weights each eval point by
    its block span instead of double-counting the always-sampled tail."""
    from repro.experiments import eval_spacing_weights, per_round_utilization

    # eval_every=5, horizon 20: eval rounds 0, 5, 10, 15, 19.
    rounds = [0, 5, 10, 15, 19]
    h = _fake_history(losses=[3.0] * 5, rounds=rounds, lat=[1.0] * 20)
    h = dataclasses.replace(h, tx_trace=None,
                            n_transmitted=np.array([0, 4, 4, 4, 4], float))
    with pytest.raises(ValueError, match="allow_eval_sampled"):
        per_round_utilization(h, 4)
    u = per_round_utilization(h, 4, allow_eval_sampled=True)
    assert np.array_equal(u, [0.0, 1.0, 1.0, 1.0, 1.0])
    w = eval_spacing_weights(h.rounds)
    assert np.array_equal(w, [1, 5, 5, 5, 4])     # blocks cover all 20 rounds
    assert w.sum() == 20
    # plain mean over eval points would be 0.8; round-0 carries a 1-round
    # block, so the block-weighted mean is 19/20.
    assert mean_subchannel_utilization(h, 4) == pytest.approx(19 / 20)
    # full-trace histories are untouched by the fallback change
    full = _fake_history(losses=[3.0] * 5, rounds=rounds, lat=[1.0] * 20)
    assert mean_subchannel_utilization(full, 2) == pytest.approx(1.0)


def test_store_versioning(tmp_path):
    d1 = next_version_dir(tmp_path, "s")
    d2 = next_version_dir(tmp_path, "s")
    assert (d1.name, d2.name) == ("v0001", "v0002")
    write_record({"schema": 1, "cells": []}, d2)
    assert latest_dir(tmp_path, "s") == d2
    assert load_latest(tmp_path, "s") == {"schema": 1, "cells": []}
    assert load_latest(tmp_path, "never-ran") is None
    bad = d1 / "sweep.json"
    bad.write_text(json.dumps({"schema": 99}))
    with pytest.raises(ValueError):
        load_record(d1)
