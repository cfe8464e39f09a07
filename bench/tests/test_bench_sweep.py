"""The `sweep` path end to end on the CPU at a tiny size: a sound run is
correct; the control (the reference one precision below) and each
fault planted in the program underneath are not."""
import time
from types import SimpleNamespace

import pytest

from bench import checks, harness
from bench.tests import faults, tiny

CELL = "mnist-mlp.grid4-n4096k4"


def test_sound_run_is_correct():
    r = tiny.run_tiny(CELL, seed=2**31 + 11)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_control_is_not_correct():
    files = tiny.tiny_files(CELL)
    run = harness.Run(SimpleNamespace(workload=CELL, seed=5, seconds=0.5,
                                      trace=0), files, time.perf_counter())
    run.control = True
    path = harness.load_module(
        harness.BENCH / "paths" / f"{files['traffic']['path']}.py")
    try:
        path.run(run, dict(tiny.DEVICE))
    finally:
        run.restore()
    ok, chk = checks.verdict(run.control_values,
                             files["traffic"]["check"]["limits"])
    assert not ok, chk


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch)
    r = tiny.run_tiny(CELL, seed=9)
    assert not r["correct"], r["checks"]
