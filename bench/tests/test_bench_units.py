"""CPU tests of the benchmark's yardstick: FLOP counters, window
arithmetic, the peak table, the trace reduction, finding every file by
name, the plain references, and the refusal to run without a TPU."""
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, trace_reduce, window

ROOT = harness.ROOT
MANIFEST = harness.load_json(ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("model,macs", [("mnist_mlp", 135_680)])
def test_forward_macs_match_hand_counts(model, macs):
    mod = harness.load_module(harness.BENCH / "flops" / f"{model}.py")
    assert mod.forward_macs() == macs
    assert mod.forward_flops() == 2 * macs


def test_sweep_window_arithmetic():
    calls = [(0.0, 4.0), (4.0, 8.0)]
    m = window.sweep(calls, [100, 100])
    assert m["sim_rounds_per_s"] == pytest.approx(25.0)
    s = window.sweep([(0.0, 4.0), (4.0, 10.0)], [100, 100])
    assert s["sim_rounds_per_s"] < m["sim_rounds_per_s"]
    with pytest.raises(ValueError):
        window.sweep(calls, [100])


def test_peaks_table():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in harness.load_json(harness.BENCH / "peaks.json")["source"]
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_file_is_found_by_name(cell):
    files = harness.cell_files(cell)
    assert files["config"]["name"] == files["cell"]["config"]
    path = harness.load_module(
        harness.BENCH / "paths" / f"{files['traffic']['path']}.py")
    assert callable(path.run)
    harness.load_module(harness.BENCH / "flops" / f"{files['config']['model']}.py")
    names = {m["name"] for m in files["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert files["per_layer"]
    run = harness.Run(SimpleNamespace(seed=1, seconds=1, trace=1), files, 0.0)
    run.load_readers()
    for spec in files["per_layer"]:
        assert callable(run.readers[spec["name"]].read)
    for dotted, _, _ in run.wraps():
        owner = dotted.rsplit(".", 1)[0]
        assert harness.resolve(owner) is not None or harness.resolve(dotted)


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError):
        harness.cell_files("no-such-cell")


class _Ev(SimpleNamespace):
    pass


def test_trace_reduce_on_built_planes():
    ops = SimpleNamespace(name="XLA Ops", events=[
        _Ev(name="fusion.1", start_ns=0, duration_ns=2e9),
        _Ev(name="fusion.2", start_ns=1e9, duration_ns=2e9),
        _Ev(name="fusion.1", start_ns=6e9, duration_ns=1e9)])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[ops])
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
        name="python", events=[_Ev(name="sweep.prepare", start_ns=2.5e9,
                                   duration_ns=4e9),
                               _Ev(name="other", start_ns=0, duration_ns=8e9)])])
    r = trace_reduce.reduce_planes([dev, host], frozenset({"sweep.prepare"}))
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["window_s"] == pytest.approx(8.0)
    assert dict((k, v) for k, v in r["breakdown"]["device_ops"]) == {
        "fusion.1": pytest.approx(3.0), "fusion.2": pytest.approx(2.0)}
    assert dict((k, v) for k, v in r["breakdown"]["idle_gaps"]) == {
        "sweep.prepare": pytest.approx(3.0), "host.other": pytest.approx(1.0)}


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.3" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1500000000 duration_ps: 2500000000 } }
  event_metadata { key: 1 value { id: 1 name: "sweep.gamma" } } }
"""


def test_trace_reduce_reads_an_xplane_file(tmp_path):
    from jax.profiler import ProfileData
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    r = trace_reduce.reduce_file(f, frozenset({"sweep.gamma"}))
    assert r["busy_s"] == pytest.approx(3e-3)
    assert r["window_s"] == pytest.approx(4e-3)
    assert dict(r["breakdown"]["idle_gaps"]) == {"sweep.gamma": pytest.approx(1e-3)}
    assert [k for k, _ in r["breakdown"]["device_ops"]] == ["fusion.1", "convolution.3"]


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_reference_gamma_matches_the_program_solver():
    """The copied NumPy Algorithm 1 gives the program's host solver's
    answer; float32 (the control) does not."""
    from bench.reference import gamma as ref
    from repro.core.monotonic import solve_pairs
    from repro.core.wireless import WirelessConfig
    cfg = harness.load_json(harness.BENCH / "configs" / "mnist-mlp-table1.json")
    wc = WirelessConfig(n_devices=64, n_subchannels=16, model_bits=1e6,
                        e_max_j=0.02)
    rng = np.random.default_rng(5)
    n = 1024
    beta = rng.integers(400, 1500, n).astype(float)
    d = 500 * np.sqrt(rng.uniform(size=n))          # uniform on the disc
    h2 = (wc.pt_w * rng.exponential(size=n) * wc.eta * d ** -3.76
          / wc.noise_w)
    e = np.full(n, 0.02)
    want = solve_pairs(beta, h2, wc, e)
    ph = ref.Physics(cfg["wireless"])
    tau, p, t, _, f = ref.solve_pairs(ph, beta, h2, e)
    assert np.array_equal(f, want.feasible) and f.any() and not f.all()
    np.testing.assert_array_equal(t[f], want.time_s[f])
    np.testing.assert_array_equal(tau[f], want.tau[f])
    tau32, p32, _, _, _ = ref.solve_pairs(ph, beta, h2, e, dtype=np.float32)
    assert max(np.max(np.abs(tau32[f] - tau[f]) / tau[f]),
               np.max(np.abs(p32[f] - p[f]) / p[f])) > 1e-5
