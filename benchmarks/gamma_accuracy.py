"""Accuracy of each Γ path on the platform JAX runs on.

Measures what decides which Γ path is the default (ROADMAP S2):

  ops    the float32 accuracy of the transcendentals Algorithm 1 uses,
         evaluated inside a Pallas kernel and in plain XLA, against float64
         NumPy on the same float32 inputs;
  gamma  each Γ path against NumPy `core.monotonic` on a seeded sample of
         Table-I channel pairs (N=4096 x K=4 x 25 rounds, as in
         `chip_smoke.py`): the jnp drivers "mixed", "bisect" and "newton"
         (emulated float64 on a TPU), and the float32 single-kernel solve
         compiled (TPU only) and in interpret mode; on a TPU also the
         compiled kernel against the interpret-mode one.

    PYTHONPATH=src python -m benchmarks.gamma_accuracy        # on the chip
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.gamma_accuracy \\
        --pairs 64

Prints one line per measurement, then one JSON object with all of them
(also written to --out when given).  A diagnostic, not a gate: it exits 0
whatever the numbers.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.compile_cache import enable_compile_cache
from repro.core import (WirelessConfig, sample_channel_gains,
                        sample_topology, solve_pairs_fused)
from repro.core.feasibility import is_infeasible
from repro.core.monotonic import solve_pairs
from repro.kernels.polyblock_fused.ops import polyblock_solve_fused

SEED = 0
N, K, ROUNDS = 4096, 4, 25
FIELDS = ("tau", "p", "time_s")

# name: (float32 function, float64 reference, inputs from (rng, size))
OPS = {
    "log": (jnp.log, np.log,
            lambda r, n: (np.geomspace(1e-6, 1e6, n),)),
    "log1p": (jnp.log1p, np.log1p,
              lambda r, n: (np.geomspace(1e-6, 1e6, n),)),
    "exp": (jnp.exp, np.exp, lambda r, n: (np.linspace(-80.0, 17.0, n),)),
    "recip": (lambda x: 1.0 / x, lambda x: 1.0 / x,
              lambda r, n: (np.geomspace(1e-6, 1e6, n),)),
    "div": (lambda x, y: x / y, lambda x, y: x / y,
            lambda r, n: (r.uniform(1e-3, 1e3, n), r.uniform(1e-3, 1e3, n))),
}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(np.abs(want), 1e-30)


def _in_pallas(fn, *xs, interpret):
    def kernel(*refs):
        *ins, out = refs
        out[...] = fn(*(r[...] for r in ins))

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(xs[0].shape, jnp.float32),
        interpret=interpret)(*xs)


def op_accuracy(size: int = 64 * 128) -> dict:
    """Max relative error of each float32 op, in a Pallas kernel (compiled
    on a TPU, interpret mode elsewhere) and in plain XLA."""
    interpret = jax.default_backend() != "tpu"
    out = {}
    for name, (f32, f64, build) in OPS.items():
        xs = [np.asarray(x, np.float32).reshape(-1, 128)
              for x in build(np.random.default_rng(SEED), size)]
        want = f64(*(x.astype(np.float64) for x in xs))
        dev = [jnp.asarray(x) for x in xs]
        row = {}
        for where, got in (
                ("pallas", _in_pallas(f32, *dev, interpret=interpret)),
                ("xla", jax.jit(f32)(*dev))):
            err = _rel(got, want)
            i = int(np.argmax(err))
            row[where] = {"max_rel": float(err.max()),
                          "at": float(xs[0].reshape(-1)[i])}
        out[name] = row
        print(f"op {name:6s} pallas max rel {row['pallas']['max_rel']:.3e} "
              f"(at x={row['pallas']['at']:.4g})  xla max rel "
              f"{row['xla']['max_rel']:.3e}", flush=True)
    return out


def sample_pairs(n_pairs: int):
    """A seeded sample of Proposition-1 feasible Table-I pairs."""
    cfg = WirelessConfig(n_devices=N, n_subchannels=K)
    rng = np.random.default_rng(SEED)
    topo = sample_topology(rng, cfg)
    h2 = np.stack([sample_channel_gains(rng, cfg, topo)
                   for _ in range(ROUNDS)])
    beta = rng.integers(5, 60, N).astype(float)
    beta_all = np.broadcast_to(beta, h2.shape).reshape(-1)
    h2 = h2.reshape(-1)
    feas = ~is_infeasible(h2, cfg, np.full(h2.shape, cfg.e_max_j))
    idx = np.sort(np.random.default_rng(SEED + 1).choice(
        np.flatnonzero(feas), n_pairs, replace=False))
    return beta_all[idx], h2[idx], cfg


def _compare(got: dict, ref: dict) -> dict:
    same = got["iterations"] == ref["iterations"]
    row = {"iters_equal": float(same.mean())}
    for f in FIELDS:
        err = _rel(got[f], ref[f])
        row[f"{f}_max_rel"] = float(err.max())
        row[f"{f}_max_rel_iters_equal"] = float(err[same].max(initial=0.0))
    row["abs_dtime_iters_differ"] = float(
        np.abs(got["time_s"] - ref["time_s"])[~same].max(initial=0.0))
    return row


def _kernel(beta, h2, cfg, interpret):
    tau, p, time_s, it = polyblock_solve_fused(
        beta, h2, cfg.e_max_j, cfg, interpret=interpret, dtype=np.float32)
    return {"tau": np.asarray(tau, np.float64),
            "p": np.asarray(p, np.float64),
            "time_s": np.asarray(time_s, np.float64),
            "iterations": np.asarray(it, np.int64)}


def gamma_accuracy(n_pairs: int = 512,
                   backends=("mixed", "bisect", "newton")) -> dict:
    beta, h2, cfg = sample_pairs(n_pairs)
    r = solve_pairs(beta, h2, cfg)
    ref = {f: getattr(r, f) for f in FIELDS + ("iterations",)}
    runs = {}
    for b in backends:
        s = solve_pairs_fused(beta, h2, cfg, backend=b)
        runs[b] = {f: getattr(s, f) for f in FIELDS + ("iterations",)}
    runs["kernel_f32_interpret"] = _kernel(beta, h2, cfg, True)
    if jax.default_backend() == "tpu":
        runs["kernel_f32_compiled"] = _kernel(beta, h2, cfg, False)
    out = {name: _compare(got, ref) for name, got in runs.items()}
    if "kernel_f32_compiled" in runs:
        out["kernel_compiled_vs_interpret"] = _compare(
            runs["kernel_f32_compiled"], runs["kernel_f32_interpret"])
    for name, row in out.items():
        print(f"gamma {name}: iters equal {row['iters_equal']:.4f} | "
              + " | ".join(f"{f} max rel {row[f + '_max_rel']:.3e}"
                           for f in FIELDS)
              + f" | |dT| where iters differ "
                f"{row['abs_dtime_iters_differ']:.3e}", flush=True)
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pairs", type=int, default=512)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"platform {dev.platform} {dev.device_kind}, jax {jax.__version__}",
          flush=True)
    record = {"platform": dev.platform, "device_kind": dev.device_kind,
              "pairs": args.pairs, "ops": op_accuracy(),
              "gamma": gamma_accuracy(args.pairs)}
    text = json.dumps(record)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return record


if __name__ == "__main__":
    main()
    sys.exit(0)
