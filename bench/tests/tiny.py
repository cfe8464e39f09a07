"""Drive a whole benchmark run on the CPU at a tiny size, for the tests:
the harness's look for a chip is skipped (the device is described, not
found), and every other step of a run is the real one."""
from __future__ import annotations

import json
import time
from types import SimpleNamespace

from bench import harness

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

TINY = {
    "mnist-mlp.grid4-n4096k4": (
        {"n_devices": 16, "n_subchannels": 4, "rounds": 12,
         "trace_after_calls": 1, "trace_seconds": 0.2,
         "check": {"gamma_pairs": 64}},
        {"n_samples": 600}),
}


def tiny_files(workload: str) -> dict:
    files = harness.cell_files(workload)
    tr_over, cfg_over = TINY[workload]
    tr = dict(files["traffic"])
    for k, v in tr_over.items():
        tr[k] = {**tr[k], **v} if isinstance(v, dict) and k in tr else v
    files["traffic"] = tr
    files["config"] = {**files["config"], **cfg_over}
    return files


def run_tiny(workload: str, seed: int = 3, seconds: float = 0.5,
             trace: int = 0, files: dict | None = None) -> dict:
    """One run of the cell at the tiny size; returns the result line."""
    files = files or tiny_files(workload)
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace)
    run = harness.Run(args, files, time.perf_counter())
    path = harness.load_module(
        harness.BENCH / "paths" / f"{files['traffic']['path']}.py")
    try:
        return json.loads(path.run(run, dict(DEVICE)))
    finally:
        run.restore()
