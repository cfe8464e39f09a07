"""The span recorder (`repro.tracing`): nothing is recorded while it is off,
spans nest and carry their counts and compile seconds while it is on, the
planner's `run_many` yields one span tree per call, and the histories are
the same bit for bit with the recorder on and off, on every dispatch
branch (solo, vmap, shard_map)."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import RoundPolicy
from repro.fl import SimConfig, run_many

# Sizes of the benchmark's tiny sweep (bench/tests/tiny.py), cut further.
TINY = dict(dataset="mnist", rounds=4, n_devices=16, n_subchannels=4,
            n_samples=96, batch=8, eval_every=2, seed=3)
TIMERS = ("wall_s", "plan_wall_s")
EVENTS = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")


@pytest.fixture(autouse=True)
def recorder_off():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def _cfgs(schemes):
    return [SimConfig(**TINY, policy=RoundPolicy(ds=d)) for d in schemes]


def _assert_same(hists_a, hists_b):
    """Every field of every history equal, bit for bit, but the timers."""
    for a, b in zip(hists_a, hists_b, strict=True):
        for f in dataclasses.fields(a):
            if f.name in TIMERS:
                continue
            x, y = getattr(a, f.name), getattr(b, f.name)
            if isinstance(x, dict):
                assert x.keys() == y.keys(), f.name
                for k in x:
                    np.testing.assert_array_equal(x[k], y[k], err_msg=f.name)
            else:
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                              err_msg=f.name)


# --------------------------------------------------------------------------
# the recorder alone
# --------------------------------------------------------------------------

def test_off_records_nothing():
    assert tracing.span("a") is tracing.span("b", x=1) is tracing._NULL
    with tracing.span("a"):
        tracing.count("n", 5)
    with tracing.timed("c") as clock:   # the clock alone: nothing recorded
        tracing.count("n")
    assert clock.seconds >= 0.0 and clock.id is None and clock.counts == {}
    assert tracing.spans() == []


def test_nested_spans_counts_and_compile_seconds():
    tracing.enable()
    with tracing.span("outer", cells=2) as outer:
        tracing.count("n")
        with tracing.span("inner") as inner:
            tracing.count("n", 2)
            tracing.count("m")
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
        with tracing.timed("sibling") as sib:
            pass
    with tracing.span("next_root") as nxt:
        pass
    tracing.count("dropped")            # no open span: nothing to add to
    got = tracing.spans()
    assert [s.name for s in got] == ["outer", "inner", "sibling", "next_root"]
    assert len({s.id for s in got}) == 4
    assert (outer.parent, outer.root) == (None, outer.id)
    assert (inner.parent, inner.root) == (outer.id, outer.id)
    assert (sib.parent, sib.root) == (outer.id, outer.id)
    assert (nxt.parent, nxt.root) == (None, nxt.id)
    assert outer.attrs == {"cells": 2}
    assert outer.counts == {"n": 1}
    assert inner.counts == {"n": 2, "m": 1}
    assert set(inner.compile_s) == set(EVENTS)
    assert all(v > 0.0 for v in inner.compile_s.values())
    assert outer.compile_s == {} and sib.compile_s == {}
    assert outer.start <= inner.start <= inner.end <= sib.start <= outer.end
    assert sib.seconds == sib.end - sib.start
    tracing.reset()
    assert tracing.spans() == []


def test_disable_unregisters_the_listener():
    tracing.enable()
    tracing.enable()                    # a second enable adds no listener
    with tracing.span("s") as s:
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.5)
        jax.monitoring.record_event_duration_secs("/jax/other", 9.0)
        tracing.disable()
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 1.0)
    assert s.compile_s == {"backend_compile": 0.5}
    assert tracing.span("after") is tracing._NULL


def test_nested_compile_events_count_once():
    """A compile event that fires inside another (a jit traced while an
    outer one is traced or lowered) is already in the outer's seconds."""
    ev = "/jax/core/compile/{}_duration".format
    tracing.enable()
    with tracing.span("s") as s:
        jax.monitoring.record_event_duration_secs(ev("jaxpr_trace"), 1e-3)
        jax.monitoring.record_event_duration_secs(ev("jaxpr_trace"), 0.5)
        jax.monitoring.record_event_duration_secs(ev("jaxpr_trace"), 1e-9)
        jax.monitoring.record_event_duration_secs(
            ev("jaxpr_to_mlir_module"), 1e-9)
    assert s.compile_s == {"jaxpr_trace": 0.5 + 1e-9,
                           "jaxpr_to_mlir_module": 1e-9}
    with tracing.span("real") as real:   # a jit traced inside another
        jax.jit(lambda x: jax.jit(lambda y: y * 2.0)(x) + 1.0)(
            jnp.ones(5)).block_until_ready()
    assert sum(real.compile_s.values()) <= real.seconds


# --------------------------------------------------------------------------
# the planner's spans
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def runs():
    """Each dispatch branch of one process run with the recorder off and
    on: {branch: (histories off, histories on, spans on)}."""
    out = {}
    for branch, schemes in (("solo", ("alg3",)),
                            ("vmap", ("alg3", "random", "fixed"))):
        tracing.disable()
        off = run_many(_cfgs(schemes), engine="scan")
        tracing.reset()
        tracing.enable()
        on = run_many(_cfgs(schemes), engine="scan")
        tracing.disable()
        out[branch] = (off, on, tracing.spans())
        tracing.reset()
    return out


@pytest.mark.parametrize("branch", ["solo", "vmap"])
def test_histories_bit_identical_on_and_off(runs, branch):
    off, on, _ = runs[branch]
    _assert_same(on, off)


@pytest.mark.parametrize("branch", ["solo", "vmap"])
def test_run_many_span_tree(runs, branch):
    _, hists, spans = runs[branch]
    by_id = {s.id: s for s in spans}
    root = spans[0]
    assert root.name == "sim.run_many" and root.parent is None
    assert root.attrs == {"cells": len(hists)}
    assert all(s.root == root.id and s.end is not None for s in spans)

    def children(span):
        return [s for s in spans if s.parent == span.id]

    top = [s.name for s in children(root)]
    assert top == ["sim.prepare", "gamma.solve", "engine.dispatch"]
    prep, gamma, dispatch = children(root)
    assert [s.name for s in children(prep)] == ["prepare.dataset"]
    assert gamma.attrs["pairs"] == TINY["rounds"] * TINY["n_subchannels"] * \
        TINY["n_devices"]
    stages = children(gamma)
    assert stages and {s.name for s in stages} == {"gamma.stage"}
    syncs = sum(s.counts.get("gamma.host_syncs", 0) for s in [gamma] + stages)
    assert syncs >= 1
    assert all(s.counts == {"gamma.host_syncs": 1} for s in stages)
    assert [s.name for s in children(dispatch)] == ["engine.run"]
    # The jitted call's trace, lowering and compile, by JAX's own events.
    assert set(dispatch.compile_s) == set(EVENTS)
    assert all(v > 0.0 for v in dispatch.compile_s.values())
    assert sum(dispatch.compile_s.values()) <= dispatch.seconds
    assert all(by_id[s.parent].start <= s.start <= s.end <= by_id[s.parent].end
               for s in spans if s.parent is not None)
    # One timer for one interval: the Γ group's planning wall is the span's.
    assert hists[0].plan_wall_s == gamma.seconds


def test_subprogram_traces_counted_on_the_dispatch():
    """`engine.subprogram_traces`, counted where JAX traces the engine's
    jitted sub-programs: nothing recorded while the recorder is off; on,
    one per leader variant and one for the client step, all on the
    `engine.dispatch` that traced them."""
    from repro.fl import engine_common, sim

    def clear():
        jax.clear_caches()
        sim._model_and_trainer.cache_clear()
        engine_common._jitted.cache_clear()

    cfgs = [SimConfig(**TINY, policy=RoundPolicy(ds=d, ra="fix"))
            for d in ("alg3", "random")]
    clear()
    run_many(cfgs, engine="scan")
    assert tracing.spans() == []
    clear()
    tracing.enable()
    run_many(cfgs, engine="scan")
    tracing.disable()
    holders = [s for s in tracing.spans()
               if "engine.subprogram_traces" in s.counts]
    assert [s.name for s in holders] == ["engine.dispatch"]
    assert holders[0].counts == {"engine.subprogram_traces": 3}


def test_hierarchy_shares_the_engine_and_gamma_spans():
    """`run_hier_many` dispatches through `fl.sim._dispatch_group` and
    solves Γ with `solve_pairs_fused`: their spans are recorded there too,
    each a root of its own (there is no `sim.run_many` above them), and
    the histories are the same bit for bit with the recorder on and off."""
    from repro.fl.hierarchical import HierSimConfig, run_hier_many
    cfg = HierSimConfig(dataset="mnist", rounds=4, n_cells=2,
                        devices_per_cell=8, subchannels_per_cell=3,
                        n_samples=96, batch=16, local_steps=2, eval_every=2)
    off = run_hier_many([cfg], engine="scan")
    tracing.enable()
    on = run_hier_many([cfg], engine="scan")
    tracing.disable()
    _assert_same(on, off)
    spans = tracing.spans()
    dispatch = [s for s in spans if s.name == "engine.dispatch"]
    assert len(dispatch) == 1 and dispatch[0].parent is None
    assert [s.name for s in spans if s.parent == dispatch[0].id] == [
        "engine.run"]
    stages = [s for s in spans if s.name == "gamma.stage"]
    assert stages and all(s.parent is None for s in stages)
    assert not any(s.name.startswith("sim.") for s in spans)


SHARD_CODE = """
import dataclasses
import numpy as np
from repro import tracing
from repro.core import RoundPolicy
from repro.fl import SimConfig, run_many
cfgs = lambda: [SimConfig(dataset="mnist", rounds=4, n_devices=6,
                          n_subchannels=2, n_samples=48, batch=8,
                          eval_every=2, seed=0, policy=RoundPolicy(ds=d))
                for d in ("alg3", "random", "fixed")]
off = run_many(cfgs(), engine="scan", shard=True)
tracing.enable()
on = run_many(cfgs(), engine="scan", shard=True)
tracing.disable()
names = [s.name for s in tracing.spans() if s.name.startswith("engine.")]
assert names == ["engine.dispatch", "engine.run"], names
for a, b in zip(on, off):
    for f in dataclasses.fields(a):
        if f.name in ("wall_s", "plan_wall_s"):
            continue
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, dict):
            for k in x:
                assert np.array_equal(x[k], y[k]), (f.name, k)
        else:
            assert np.array_equal(np.asarray(x), np.asarray(y)), f.name
print("SHARD_TRACING_OK")
"""


def test_shard_map_branch_bit_identical_on_and_off():
    """The shard_map branch on 2 forced host devices (its own process: the
    device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", SHARD_CODE], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD_TRACING_OK" in proc.stdout
