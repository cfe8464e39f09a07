"""The engine's call-independent sub-programs keep their trace caches from
call to call: the leader step (`fl.engine_common`) and one client's local
training (`fl.client`) are traced once per process per (static arguments,
shapes), so a later `run_many` call re-traces only the round loop's glue.
The recorder's `engine.subprogram_traces` counts each miss.  A cached
call gives the same histories, bit for bit, as a call on cleared caches,
and a change of any training setting builds a new trainer."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import RoundPolicy
from repro.fl import SimConfig, run_many
import repro.fl.engine_common as engine_common
import repro.fl.sim as sim

SCHEMES = ("alg3", "random", "fixed", "cluster")
TINY = dict(dataset="mnist", rounds=3, n_devices=16, n_subchannels=2,
            n_samples=500, batch=8, local_steps=2, seed=5)
TIMERS = ("wall_s", "plan_wall_s")
COUNTER = "engine.subprogram_traces"


@pytest.fixture(autouse=True)
def recorder_off():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def _clear_caches():
    jax.clear_caches()
    sim._model_and_trainer.cache_clear()
    engine_common._jitted.cache_clear()


def _cfgs(**over):
    """One config per scheme.  Worlds that differ only in `radius_m` share
    one group key and the seed's data sizes, hence the engine's shapes."""
    return [SimConfig(**dict(TINY, **over), policy=RoundPolicy(ds=d, ra="fix"))
            for d in SCHEMES]


def _dispatch_counts(cfgs):
    """Run `cfgs` with the recorder on: (histories, the dispatch's counts)."""
    tracing.reset()
    tracing.enable()
    hists = run_many(cfgs, engine="scan")
    tracing.disable()
    dispatch = [s for s in tracing.spans() if s.name == "engine.dispatch"]
    assert len(dispatch) == 1
    assert not any(COUNTER in s.counts for s in tracing.spans()
                   if s is not dispatch[0])
    return hists, dispatch[0].counts.get(COUNTER, 0)


def _assert_same(hists_a, hists_b):
    for a, b in zip(hists_a, hists_b, strict=True):
        for f in dataclasses.fields(a):
            if f.name not in TIMERS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, f.name)),
                    np.asarray(getattr(b, f.name)), err_msg=f.name)


def test_later_call_traces_no_subprogram_and_matches_fresh_caches():
    _clear_caches()
    _, first = _dispatch_counts(_cfgs(radius_m=500.0))
    cached, second = _dispatch_counts(_cfgs(radius_m=300.0))
    # Four leader variants and one client step, then none.
    assert first == len(SCHEMES) + 1
    assert second == 0

    _clear_caches()
    fresh, again = _dispatch_counts(_cfgs(radius_m=300.0))
    assert again == len(SCHEMES) + 1
    _assert_same(cached, fresh)
    # The worlds differ: the second call did not replay the first.
    assert not np.array_equal(cached[0].tx_trace,
                              run_many(_cfgs(radius_m=500.0),
                                       engine="scan")[0].tx_trace)


@pytest.mark.parametrize("field,value", [
    ("lr", 0.05), ("local_steps", 3), ("batch", 16), ("optimizer", "adam")])
def test_changed_training_setting_builds_a_new_trainer(field, value):
    base = _cfgs(radius_m=400.0)
    changed = _cfgs(radius_m=400.0, **{field: value})
    base_hists, _ = _dispatch_counts(base)
    cached, count = _dispatch_counts(changed)
    # The leader variants are reused; the client step is traced anew.
    assert count == 1

    _clear_caches()
    fresh, _ = _dispatch_counts(changed)
    _assert_same(cached, fresh)
    assert not np.array_equal(cached[0].global_loss, base_hists[0].global_loss)


def test_trainer_kept_per_settings_and_factory(monkeypatch):
    """One model and trainer per training settings; a trainer or model
    factory patched in (as the benchmark's planted faults do) is not
    hidden by a trainer an earlier call cached."""
    cfg = _cfgs()[:1]
    model, trainer, _, _ = sim._group_trainer_and_policies(cfg)
    again = sim._group_trainer_and_policies(_cfgs(seed=9, radius_m=1.0)[:1])
    assert again[0] is model and again[1] is trainer

    real = sim.make_local_trainer
    monkeypatch.setattr(sim, "make_local_trainer",
                        lambda *a, **kw: real(*a, **kw))
    patched = sim._group_trainer_and_policies(cfg)[1]
    assert patched is not trainer
    monkeypatch.undo()
    assert sim._group_trainer_and_policies(cfg)[1] is trainer

    real_model = sim.get_small_model
    monkeypatch.setattr(sim, "get_small_model", lambda d: real_model(d))
    assert sim._group_trainer_and_policies(cfg)[1] is not trainer


def test_leader_jit_follows_the_module_global(monkeypatch):
    """The branches resolve `engine_common.leader_round` at each call, so a
    leader patched in its place is jitted and traced on its own."""
    real = engine_common.leader_round
    calls = []

    def leader_round(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    run_many(_cfgs(), engine="scan")
    monkeypatch.setattr(engine_common, "leader_round", leader_round)
    _, count = _dispatch_counts(_cfgs())
    assert len(calls) == len(SCHEMES) and count == len(SCHEMES)
