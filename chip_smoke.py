#!/usr/bin/env python3
"""Smoke run of the wireless-FL controller on a TPU.  Not a benchmark.

Drives the main path once, in this one process, through the entry points
a user calls, and checks each result against the repository's own
references:

  gamma    whole-horizon Γ (`precompute_gamma`) at N=4096 x K=4 x 25 rounds
           on the default path (the "mixed" jnp driver, plain XLA: no
           program it compiles holds a Pallas call) vs NumPy
           `core.monotonic` on a seeded 512-pair sample, <= 1e-6 relative;
  scan     `run_simulation(engine="scan")` vs `engine="loop"` on the
           paper's MNIST MLP (784-128-256-10) at N=64, K=16;
  async    `engine="async"` with a full buffer vs the scan run, then a
           buffered-async run of a few hundred events;
  hier     `run_hier_many`, 2 cells, async at both tiers;
  service  the `repro.service.run --smoke` preset for a few segments.

With `--chips 4` it runs only the multi-device comparisons, each sharded
vs unsharded with the sharded work placed on all four chips:
`solve_pairs_fused` at the Γ size above, bit for bit; and `run_many` over
8 seeds with the default policy (so Γ is solved, sharded, inside it),
bit for bit on every history field against unsharded `run_many` over the
same seeds two at a time (the batch each chip holds).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips

Exits nonzero on any failure, and when JAX finds no TPU.  The last line
of stdout is one JSON object naming the device.  Times printed are wall
seconds of one run, compilation included.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
GAMMA_N, GAMMA_K, GAMMA_ROUNDS = 4096, 4, 25
GAMMA_SAMPLE = 512
GAMMA_RTOL = 1e-6     # the jnp drivers' contract vs NumPy (DESIGN.md §6)
# Loop, scan and async compile the same training arithmetic into
# differently fused programs.  On one v5e the eval losses then differ
# after 20 rounds by at most 9.257e-5 (scan vs loop) and 7.679e-5 (async
# vs scan) relative (PERF.md); the bound is about 10x those readings.
# The selection traces, which decide the control plane, must be exact.
LOSS_RTOL = 1e-3
HISTORY_FIELDS = ("tx_trace", "age_trace", "latency_all", "energy_all",
                  "latency_s", "cum_time_s", "n_selected", "n_transmitted",
                  "energy_j", "deficits", "global_loss", "accuracy",
                  "grad_sq_norms")
IR_DIR = Path(__file__).resolve().parent / ".smoke_ir"


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(np.abs(want), 1e-30)


def _compiled_programs(fn):
    """Run fn while JAX writes the StableHLO of every program it compiles
    (a compile-cache hit included) to IR_DIR; returns fn's result and
    {file name: program text}."""
    import jax

    shutil.rmtree(IR_DIR, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", str(IR_DIR))
    try:
        out = fn()
    finally:
        jax.config.update("jax_dump_ir_to", "")
    texts = {f.name: f.read_text() for f in sorted(IR_DIR.glob("*.mlir"))}
    shutil.rmtree(IR_DIR, ignore_errors=True)
    return out, texts


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return np.array_equal(a, b, equal_nan=a.dtype.kind == "f")


def _gamma_inputs():
    from repro.core import (WirelessConfig, sample_channel_gains,
                            sample_topology)

    cfg = WirelessConfig(n_devices=GAMMA_N, n_subchannels=GAMMA_K)
    rng = np.random.default_rng(SEED)
    topo = sample_topology(rng, cfg)
    h2 = np.stack([sample_channel_gains(rng, cfg, topo)
                   for _ in range(GAMMA_ROUNDS)])
    beta = rng.integers(5, 60, GAMMA_N).astype(float)
    return cfg, beta, h2


def phase_gamma():
    from repro.core import precompute_gamma, solve_pairs_fused
    from repro.core.monotonic import solve_pairs

    cfg, beta, h2 = _gamma_inputs()
    n_pairs = h2.size

    # The default Γ path on every platform is the "mixed" jnp driver:
    # plain XLA, so none of the programs the first call compiles may hold
    # a Pallas call (`tpu_custom_call`), and nothing can run in interpret
    # mode.
    t0 = time.perf_counter()
    ra, programs = _compiled_programs(
        lambda: precompute_gamma(beta, h2, cfg))
    first = time.perf_counter() - t0
    stages = [n for n in programs if "fused_stage" in n]
    log(f"gamma: first call compiled {len(programs)} programs, "
        f"{len(stages)} of them Γ stages")
    require(stages, "no Γ stage program was compiled")
    pallas = [n for n, t in programs.items() if "tpu_custom_call" in t]
    require(not pallas, f"the default Γ path holds a Pallas call: {pallas}")
    t0 = time.perf_counter()
    ra2 = precompute_gamma(beta, h2, cfg)
    second = time.perf_counter() - t0
    feas = ra.feasible.reshape(-1)
    log(f"gamma: {n_pairs} pairs, {int(feas.sum())} feasible, "
        f"first call {first:.2f}s (compile included), second {second:.2f}s")
    for f in ("tau", "p", "time_s", "iterations"):
        require(np.array_equal(getattr(ra, f), getattr(ra2, f),
                               equal_nan=True),
                f"gamma not deterministic: {f}")

    rng = np.random.default_rng(SEED + 1)
    idx = np.sort(rng.choice(np.flatnonzero(feas), GAMMA_SAMPLE,
                             replace=False))
    beta_s = np.broadcast_to(beta, h2.shape).reshape(-1)[idx]
    h2_s = h2.reshape(-1)[idx]
    flat = {f: getattr(ra, f).reshape(-1)[idx]
            for f in ("tau", "p", "time_s", "iterations")}

    # Same sample through the mixed driver by name: bit-identical, so the
    # default path above was that driver (pairs are solved independently).
    named = solve_pairs_fused(beta_s, h2_s, cfg, backend="mixed")
    for f in flat:
        require(np.array_equal(getattr(named, f), flat[f]),
                f"default Γ path differs from backend='mixed' on {f}")

    ref = solve_pairs(beta_s, h2_s, cfg)
    require(ref.feasible.all(), "reference calls a sampled pair infeasible")
    same = float((ref.iterations == flat["iterations"]).mean())
    worst = max(float(_rel(flat[f], getattr(ref, f)).max())
                for f in ("tau", "p", "time_s"))
    log(f"gamma vs NumPy reference ({GAMMA_SAMPLE} pairs): max rel diff "
        f"{worst:.3e} over tau, p, time_s; iteration counts equal on "
        f"{same:.4f}")
    require(worst <= GAMMA_RTOL, f"relative diff {worst:.3e} > {GAMMA_RTOL}")


def _same_traces(a, b, what):
    for f in ("tx_trace", "age_trace"):
        require(np.array_equal(getattr(a, f), getattr(b, f)),
                f"{what}: {f} differs")
    la, lb = np.asarray(a.global_loss), np.asarray(b.global_loss)
    require(np.isfinite(la).all() and np.isfinite(lb).all(),
            f"{what}: non-finite loss")
    rel = float(_rel(la, lb).max())
    log(f"{what}: tx/age traces equal, final loss {la[-1]:.6f} vs "
        f"{lb[-1]:.6f}, max |d loss| {float(np.max(np.abs(la - lb))):.3e} "
        f"({rel:.3e} relative)")
    require(rel <= LOSS_RTOL,
            f"{what}: loss differs by {rel:.3e} > {LOSS_RTOL} relative")


def _sim_cfg(**kw):
    from repro.fl import SimConfig

    base = dict(dataset="mnist", n_devices=64, n_subchannels=16, rounds=20,
                eval_every=5, seed=SEED)
    return SimConfig(**{**base, **kw})


def phase_engines():
    from repro.fl import run_simulation

    cfg = _sim_cfg()
    walls = {}
    hists = {}
    for engine in ("loop", "scan", "async"):
        t0 = time.perf_counter()
        hists[engine] = run_simulation(cfg, engine=engine)
        walls[engine] = time.perf_counter() - t0
    log("engines wall (compile included): " +
        ", ".join(f"{k} {v:.2f}s" for k, v in walls.items()))
    _same_traces(hists["scan"], hists["loop"], "scan vs loop")
    _same_traces(hists["async"], hists["scan"], "full-buffer async vs scan")


def phase_buffered_async():
    from repro.fl import run_simulation

    cfg = _sim_cfg(rounds=300, eval_every=50, aggregation="async",
                   scenario="churn")
    t0 = time.perf_counter()
    h = run_simulation(cfg, engine="async")
    wall = time.perf_counter() - t0
    loss = np.asarray(h.global_loss)
    commits = int(np.asarray(h.commit_trace).sum())
    log(f"buffered async: {cfg.rounds} events, {commits} commits, loss "
        f"{loss[0]:.4f} -> {loss[-1]:.4f}, {wall:.2f}s")
    require(np.isfinite(loss).all(), "buffered async: non-finite loss")
    require(commits > 0, "buffered async: nothing committed")
    require(loss[-1] < loss[0], "buffered async: loss did not fall")


def phase_hier():
    from repro.fl.hierarchical import HierSimConfig, run_hier_many

    cfg = HierSimConfig(dataset="mnist", n_cells=2, devices_per_cell=32,
                        subchannels_per_cell=8, rounds=40, eval_every=10,
                        seed=SEED, scenario="churn", aggregation="async",
                        global_aggregation="async")
    t0 = time.perf_counter()
    (h,) = run_hier_many([cfg], engine="async")
    wall = time.perf_counter() - t0
    loss = np.asarray(h.global_loss)
    commits = int(np.asarray(h.commit_trace).sum())
    log(f"hierarchy: 2 cells x 32 devices, {cfg.rounds} events, "
        f"{commits} commits, loss {loss[0]:.4f} -> {loss[-1]:.4f}, "
        f"{wall:.2f}s")
    require(np.isfinite(loss).all(), "hierarchy: non-finite loss")
    require(commits > 0, "hierarchy: nothing committed")


def phase_service():
    from repro.service.harness import SustainedService
    from repro.service.run import build_service_config, parse_args

    args = parse_args(["--smoke"])
    cfg = build_service_config(args)
    t0 = time.perf_counter()
    rec = SustainedService(cfg).serve(args.segments)
    wall = time.perf_counter() - t0
    s = rec["summary"]
    loss = np.asarray(rec["steady_state"]["global_loss"])
    log(f"service smoke: {s['events']} events over {args.segments} "
        f"segments, p99 commit latency {s['latency_s']['p99']:.3f}s, "
        f"{wall:.2f}s")
    require(s["events"] == args.segments * args.segment_events,
            "service: events lost")
    require(np.isfinite(loss).all(), "service: non-finite loss")


def _device_counters():
    """Per device: (allocations so far, peak bytes) as the runtime reports
    them; used to see which devices a sharded call touched."""
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append((st.get("num_allocs", -1), st.get("peak_bytes_in_use", -1)))
    return out


def _touched_all(before, after, what):
    touched = [a != b for a, b in zip(before, after)]
    log(f"{what}: devices touched {touched} (allocs, peak bytes) "
        f"{before} -> {after}")
    require(all(touched), f"{what}: sharded work not on every device")


def phase_four_chips():
    import jax

    from repro.core import solve_pairs_fused
    from repro.fl import run_many

    require(jax.device_count() == 4,
            f"--chips 4 needs 4 devices, found {jax.device_count()}")

    cfg, beta, h2 = _gamma_inputs()
    c0 = _device_counters()
    t0 = time.perf_counter()
    sh = solve_pairs_fused(beta[None, None, :], h2, cfg, shard=True)
    t_sh = time.perf_counter() - t0
    c1 = _device_counters()
    t0 = time.perf_counter()
    un = solve_pairs_fused(beta[None, None, :], h2, cfg, shard=False)
    t_un = time.perf_counter() - t0
    for f in ("feasible", "iterations", "tau", "p", "time_s", "energy_j"):
        require(np.array_equal(getattr(sh, f), getattr(un, f),
                               equal_nan=True), f"sharded gamma differs: {f}")
    log(f"gamma sharded == unsharded bit for bit ({h2.size} pairs; "
        f"{t_sh:.2f}s vs {t_un:.2f}s, compile included)")
    _touched_all(c0, c1, "sharded gamma")

    # Default policy, so run_many solves Γ (sharded when asked) as well.
    cfgs = [_sim_cfg(seed=s) for s in range(8)]
    log("run_many 8 seeds, sharded ...")
    c0 = _device_counters()
    t0 = time.perf_counter()
    hs = run_many(cfgs, engine="scan", shard=True)
    t_sh = time.perf_counter() - t0
    c1 = _device_counters()
    _touched_all(c0, c1, "sharded run_many")
    # Each chip holds 2 of the 8 runs, so the reference is unsharded
    # run_many over the same seeds two at a time.  (One unsharded batch of
    # 8 compiles to another program, whose losses differ by ~2.7e-4
    # relative on one chip alone: PERF.md.)
    log(f"run_many sharded {t_sh:.2f}s (compile included); unsharded in "
        f"batches of 2 ...")
    t0 = time.perf_counter()
    hp = [h for i in range(0, len(cfgs), 2)
          for h in run_many(cfgs[i:i + 2], engine="scan", shard=False)]
    t_un = time.perf_counter() - t0
    for a, b in zip(hs, hp):
        require(np.isfinite(np.asarray(a.global_loss)).all(),
                "run_many: non-finite loss")
        for f in HISTORY_FIELDS:
            require(_same(getattr(a, f), getattr(b, f)),
                    f"sharded run_many differs from unsharded batches of "
                    f"2: {f}")
    log(f"run_many sharded == unsharded batches of 2, bit for bit on every "
        f"field ({t_un:.2f}s unsharded, compile included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip (default); "
                         "4: the sharded paths against unsharded")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              f"this smoke run needs the chip", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"smoke run, not a benchmark: {devs[0].platform} "
        f"{devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache}")

    phases = ([("four_chips", phase_four_chips)] if args.chips == 4 else
              [("gamma", phase_gamma), ("engines", phase_engines),
               ("buffered_async", phase_buffered_async),
               ("hier", phase_hier), ("service", phase_service)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        log(f"phase {name} ok ({time.perf_counter() - t0:.2f}s wall)")

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
