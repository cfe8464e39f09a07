"""Control-plane benchmark: per-round NumPy Algorithm 1 vs the batched
jitted whole-horizon solver (core.monotonic_jax), plus the fused scan round
loop vs the host loop (fl.sim engines, DESIGN.md §8).

Emits a CSV table like the other benchmark modules and, when given
`json_path` (benchmarks/run.py --json), writes BENCH_control_plane.json so
the perf trajectory is machine-readable across PRs.  Acceptance rows:

  * `horizon/N512` — the whole-horizon (100 x 4 x 512) Γ solve must be
    >= 10x faster than the per-round NumPy loop, agreeing within 1e-6
    relative on feasible time_s;
  * `run_many/scan` — an 8-seed sweep through the scan+vmap engine must be
    >= 3x faster wall-clock than the host round loop (best of
    SWEEP_REPS runs per engine; FIX-RA keeps Algorithm 1 — measured by the
    horizon row, identical work for both engines — out of this one);
  * `sweep/grid8` — an 8-config policy x seed grid (the experiment
    harness's workload, DESIGN.md §10) through ONE grouped run_many scan
    dispatch must be >= 2x faster than the equivalent loop of solo
    `run_simulation(engine="scan")` calls (the grid compiles one
    lax.switch program and shares worlds/Γ across policy variants; the
    solo loop pays per-call compilation and preparation);
  * `scenario_traces` — whole-horizon environment generation + Γ solve
    (+ churn fold-in) for the `urban` stress preset vs `static`
    (DESIGN.md §11): the scenario layer's overhead over the legacy
    static world, measured end-to-end at control-plane scale.  Not an
    acceptance gate — trace generation is host-side numpy and runs once
    per world — but recorded so regressions in the dynamic path show up
    in the perf trajectory.
  * `async_event_loop` — the buffered event engine (DESIGN.md §12) on
    the same 8-seed sweep as `run_many/scan`: events/sec vs the sync
    engine's rounds/sec (an event carries the extra buffer state in its
    scan carry, so the ratio records the async engine's overhead), plus
    a one-rep full-buffer run pinning the degenerate limit's transmitted
    sets against the scan engine at benchmark scale.  Recorded, not
    gated.
  * `sustained_service` — the segment-chained streaming deployment of
    the buffered event engine (DESIGN.md §14): one warm-up segment, then
    4 closed-loop segments of 100 events at the sweep cell shape
    (N=64, K=16, churn scenario).  Gate: sustained throughput
    >= 55 events/s; p50/p99 commit latency and SLO attainment against a
    2 s budget are recorded alongside.
  * `hier_async` — the two-tier buffered async hierarchy (DESIGN.md §15)
    vs the sync hierarchy scan at city scale (8 cells x 32 devices = 256
    devices, churn scenario).  Gates: wall throughput >= 0.45x the sync
    hier scan's (the async event carries both tiers' buffer state; the
    measured ratio is ~0.63x — see the calibration note at HIER_CFG),
    simulated p99 commit latency <= 0.5x the sync hierarchy's p99 round
    latency (the async engine's actual win: no tier waits for its
    slowest member), and the full-buffer degenerate-limit anchor at
    bench scale.
  * `polyblock_fused` — the staged fused Γ driver (`solve_pairs_fused`,
    mixed-precision projections) vs the step driver (`solve_pairs_jit`,
    the previous whole-horizon path) at N in {512, 4096, 32768} devices
    x K=4 sub-channels.  Timed as min over FUSED_REPS *interleaved*
    rounds (A,B,A,B,... — back-to-back mins, not per-solver batches, so
    a background hiccup hits both solvers equally on a noisy shared
    box).  Gates: >= 1.8x at N=4096 with <= 1e-6 max relative time_s
    difference, and `roofline_pct` (measured against the analytic
    op/byte bound of `launch.analytic.polyblock_solve_cost`) >= 3% — an
    absolute tripwire that catches a slow solver even when both measured
    paths degrade together.
"""
from __future__ import annotations

import json
import platform
import time

import numpy as np

from repro.core import (
    RoundPolicy,
    WirelessConfig,
    sample_channel_gains,
    sample_topology,
    solve_pairs,
    solve_pairs_fused,
    solve_pairs_jit,
)
from repro.fl import (
    HierSimConfig,
    SimConfig,
    run_hier_many,
    run_many,
    run_simulation,
)
from repro.launch.analytic import polyblock_solve_cost, roofline_pct
from repro.scenarios import apply_dynamics, generate_traces
from repro.service import ServiceConfig, SustainedService

from .common import emit

K = 4
HORIZON_ROUNDS = 100
HORIZON_N = 512

# The step driver is timed with the projection its gates and the "step"
# cost model (launch/analytic.py) were calibrated on; the drivers'
# default is "mixed".
STEP_BACKEND = "newton"

FUSED_NS = (512, 4096, 32768)
FUSED_GATE_N = 4096
FUSED_REPS = 11
# Relative-speedup target carries ~10% margin below the measured floor,
# matching the other gates (scan 3.3x vs 3.0, horizon 14x vs 10): the
# step/fused ratio is host-dependent (2.05x on the original 2-core box,
# 1.95-2.0x converged on the current 1-core host), so the absolute
# roofline tripwire below is the gate that catches a genuinely slow
# solver; the ratio gate only guards against the fused path regressing
# relative to the step driver.
FUSED_TARGET_SPEEDUP = 1.8
FUSED_TARGET_REL = 1e-6
FUSED_TARGET_ROOFLINE_PCT = 3.0

SCN_ROUNDS = 100
SCN_N = 128
SCN_REPS = 2

SWEEP_SEEDS = 8
SWEEP_REPS = 3
SWEEP_CFG = dict(dataset="mnist", rounds=100, n_devices=64, n_subchannels=16,
                 n_samples=128, batch=16, eval_every=20, local_steps=1)

SERVICE_SEGMENTS = 4
SERVICE_SEGMENT_EVENTS = 100
SERVICE_EVAL_EVERY = 20
SERVICE_BUDGET_S = 2.0
SERVICE_TARGET_EV_PER_S = 55.0

HIER_CELLS = 8
HIER_REPS = 2
HIER_CFG = dict(dataset="mnist", n_cells=HIER_CELLS, devices_per_cell=32,
                subchannels_per_cell=8, rounds=50, n_samples=128, batch=16,
                eval_every=10, local_steps=1, scenario="churn")
# Honest calibration (DESIGN.md §15): a two-tier async event carries BOTH
# tiers' buffer state in its scan carry, so its wall throughput sits below
# the sync hierarchy scan's (0.63x measured at N=256 / 8 cells on this
# class of host — the same per-event overhead the flat async_event_loop
# row records).  The async win is SIMULATED time — no tier ever waits for
# its slowest member — pinned by the results/hier_async artifact and by
# the deterministic p99 gate below.  Gates: wall-throughput ratio floor
# with ~30% margin under the measured value, simulated p99 commit latency
# at most half the sync hierarchy's p99 round latency (deterministic
# given the config, measured 0.17x), and the full-buffer anchor.
HIER_TARGET_THROUGHPUT_RATIO = 0.45
HIER_TARGET_P99_RATIO = 0.5

GRID_DS = ("alg3", "random", "fixed", "cluster")
GRID_SEEDS = 2
GRID_REPS = 2
GRID_CFG = dict(dataset="mnist", rounds=60, n_devices=20, n_subchannels=4,
                n_samples=128, batch=16, eval_every=20, local_steps=1)


def _setup(n, rounds, seed=0):
    cfg = WirelessConfig(n_devices=n, n_subchannels=K)
    rng = np.random.default_rng(seed)
    topo = sample_topology(rng, cfg)
    h2 = np.stack([sample_channel_gains(rng, cfg, topo) for _ in range(rounds)])
    beta = rng.integers(5, 60, n).astype(float)
    return cfg, beta, h2


def _agreement(ref_time, jit, mask):
    return float(np.max(np.abs(ref_time[mask] - jit.time_s[mask])
                        / np.abs(ref_time[mask])))


def run(json_path: str | None = None):
    rows = []
    record = {
        "bench": "control_plane",
        "host": platform.machine(),
        "settings": {"K": K, "rounds": HORIZON_ROUNDS, "N": HORIZON_N},
        "solve_pairs_micro": {},
    }

    # ---- micro: one-round solve at growing N (NumPy vs jitted) ------------
    for n in (32, 512, 4096):
        cfg, beta, h2 = _setup(n, 1)
        t0 = time.perf_counter()
        ref = solve_pairs(beta[None, :], h2[0], cfg)
        t_np = time.perf_counter() - t0
        # warm the jit caches
        solve_pairs_jit(beta[None, :], h2[0], cfg, backend=STEP_BACKEND)
        t0 = time.perf_counter()
        jit = solve_pairs_jit(beta[None, :], h2[0], cfg, backend=STEP_BACKEND)
        t_jit = time.perf_counter() - t0
        agree = _agreement(ref.time_s, jit, ref.feasible)
        rows.append([f"solve_pairs/np/N{n}", round(t_np * 1e6, 1), f"{K}x{n} pairs"])
        rows.append([f"solve_pairs/jit/N{n}", round(t_jit * 1e6, 1),
                     f"{t_np / t_jit:.1f}x, agree={agree:.1e}"])
        record["solve_pairs_micro"][f"N{n}"] = {
            "numpy_us": t_np * 1e6, "jit_us": t_jit * 1e6,
            "speedup": t_np / t_jit, "max_rel_diff": agree,
        }

    # ---- acceptance: whole-horizon Gamma precompute (always full scale) ---
    rounds = HORIZON_ROUNDS
    cfg, beta, h2_all = _setup(HORIZON_N, rounds)
    solve_pairs_jit(beta[None, None, :], h2_all, cfg,
                    backend=STEP_BACKEND)                    # warm/compile
    t0 = time.perf_counter()
    jit = solve_pairs_jit(beta[None, None, :], h2_all, cfg,
                          backend=STEP_BACKEND)
    t_jit = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref_time = np.stack(
        [solve_pairs(beta[None, :], h2_all[t], cfg).time_s
         for t in range(rounds)])
    t_np = time.perf_counter() - t0
    agree = _agreement(ref_time, jit, jit.feasible)
    speedup = t_np / t_jit
    rows.append([f"horizon/np_loop/N{HORIZON_N}", round(t_np * 1e6, 1),
                 f"{rounds} rounds"])
    rows.append([f"horizon/jit/N{HORIZON_N}", round(t_jit * 1e6, 1),
                 f"{speedup:.1f}x, agree={agree:.1e}"])
    record["horizon"] = {
        "rounds": rounds, "N": HORIZON_N, "K": K,
        "numpy_loop_s": t_np, "jit_s": t_jit,
        "speedup": speedup, "max_rel_diff": agree,
        "target_speedup": 10.0, "meets_target": bool(speedup >= 10.0),
    }

    # ---- acceptance: fused staged Γ driver vs the step driver -------------
    record["polyblock_fused"] = {}
    for n in FUSED_NS:
        cfg, beta, h2 = _setup(n, 1, seed=3)
        # warm both jits
        solve_pairs_jit(beta[None, :], h2[0], cfg, backend=STEP_BACKEND)
        fused = solve_pairs_fused(beta[None, :], h2[0], cfg)
        step = solve_pairs_jit(beta[None, :], h2[0], cfg, backend=STEP_BACKEND)
        t_step, t_fused = [], []
        for _ in range(FUSED_REPS):                          # interleaved
            t0 = time.perf_counter()
            step = solve_pairs_jit(beta[None, :], h2[0], cfg,
                                   backend=STEP_BACKEND)
            t_step.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            fused = solve_pairs_fused(beta[None, :], h2[0], cfg)
            t_fused.append(time.perf_counter() - t0)
        ts, tf = min(t_step), min(t_fused)
        agree = _agreement(step.time_s, fused, step.feasible)
        iters_eq = bool(np.array_equal(step.iterations, fused.iterations))
        speedup = ts / tf
        pct = roofline_pct(tf, polyblock_solve_cost(K * n, solver="fused"))
        rows.append([f"polyblock/step/N{n}", round(ts * 1e6, 1),
                     f"{K}x{n} pairs"])
        rows.append([f"polyblock/fused/N{n}", round(tf * 1e6, 1),
                     f"{speedup:.2f}x, agree={agree:.1e}, "
                     f"roofline={pct:.1f}%"])
        gated = n == FUSED_GATE_N
        record["polyblock_fused"][f"N{n}"] = {
            "pairs": K * n, "reps": FUSED_REPS,
            "step_s": ts, "fused_s": tf,
            "step_s_all": t_step, "fused_s_all": t_fused,
            "speedup": speedup, "max_rel_diff": agree,
            "iterations_equal": iters_eq,
            "roofline_pct": pct,
            "target_rel": FUSED_TARGET_REL,
            "meets_rel": bool(agree <= FUSED_TARGET_REL),
            **({"target_speedup": FUSED_TARGET_SPEEDUP,
                "target_roofline_pct": FUSED_TARGET_ROOFLINE_PCT,
                "meets_target": bool(speedup >= FUSED_TARGET_SPEEDUP
                                     and agree <= FUSED_TARGET_REL
                                     and pct >= FUSED_TARGET_ROOFLINE_PCT)}
               if gated else {}),
        }

    # ---- acceptance: fused scan round loop vs host loop, 8-seed sweep -----
    cfgs = [SimConfig(seed=s, policy=RoundPolicy(ra="fix"), **SWEEP_CFG)
            for s in range(SWEEP_SEEDS)]
    times = {"scan": [], "loop": []}
    hists = {}
    for _ in range(SWEEP_REPS):
        for engine in ("scan", "loop"):
            t0 = time.perf_counter()
            hists[engine] = run_many(cfgs, engine=engine)
            times[engine].append(time.perf_counter() - t0)
    tx_agree = all(
        np.array_equal(a.tx_trace, b.tx_trace)
        for a, b in zip(hists["scan"], hists["loop"]))
    t_scan, t_loop = min(times["scan"]), min(times["loop"])
    sweep_speedup = t_loop / t_scan
    rows.append([f"run_many/loop/seeds{SWEEP_SEEDS}", round(t_loop * 1e6, 1),
                 f"{SWEEP_CFG['rounds']} rounds, N={SWEEP_CFG['n_devices']}"])
    rows.append([f"run_many/scan/seeds{SWEEP_SEEDS}", round(t_scan * 1e6, 1),
                 f"{sweep_speedup:.1f}x, tx_agree={tx_agree}"])
    record["run_many_scan"] = {
        "seeds": SWEEP_SEEDS, "reps": SWEEP_REPS, **SWEEP_CFG,
        "loop_s": t_loop, "scan_s": t_scan,
        "loop_s_all": times["loop"], "scan_s_all": times["scan"],
        "speedup": sweep_speedup, "tx_traces_agree": bool(tx_agree),
        "target_speedup": 3.0, "meets_target": bool(sweep_speedup >= 3.0),
    }

    # ---- async event engine: events/sec vs sync rounds/sec ----------------
    acfgs = [SimConfig(seed=s, policy=RoundPolicy(ra="fix"),
                       aggregation="async", **SWEEP_CFG)
             for s in range(SWEEP_SEEDS)]
    t_async = []
    for _ in range(SWEEP_REPS):
        t0 = time.perf_counter()
        run_many(acfgs, engine="async")
        t_async.append(time.perf_counter() - t0)
    ta = min(t_async)
    events = SWEEP_SEEDS * SWEEP_CFG["rounds"]
    # Degenerate-limit anchor at benchmark scale: full buffer == scan.
    fcfgs = [SimConfig(seed=s, policy=RoundPolicy(ra="fix"),
                       aggregation="async_full", **SWEEP_CFG)
             for s in range(SWEEP_SEEDS)]
    fhists = run_many(fcfgs, engine="async")
    anchor = all(np.array_equal(f.tx_trace, h.tx_trace)
                 for f, h in zip(fhists, hists["scan"]))
    ev_per_s = events / ta
    sync_r_per_s = events / t_scan
    rows.append([f"async_event_loop/seeds{SWEEP_SEEDS}", round(ta * 1e6, 1),
                 f"{ev_per_s:.1f} ev/s vs {sync_r_per_s:.1f} sync r/s, "
                 f"anchor={anchor}"])
    record["async_event_loop"] = {
        "seeds": SWEEP_SEEDS, "reps": SWEEP_REPS, **SWEEP_CFG,
        "async_s": ta, "async_s_all": t_async,
        "events_per_s": ev_per_s, "sync_rounds_per_s": sync_r_per_s,
        "events_per_sync_round": ev_per_s / sync_r_per_s,
        "full_buffer_anchor_tx_agree": bool(anchor),
    }

    # ---- acceptance: sustained service, segment-chained async stream -----
    svc_sim = SimConfig(seed=0, policy=RoundPolicy(ra="fix"),
                        aggregation="async", scenario="churn", **SWEEP_CFG)
    svc = SustainedService(ServiceConfig(
        sim=svc_sim,
        segment_events=SERVICE_SEGMENT_EVENTS,
        eval_every_events=SERVICE_EVAL_EVERY,
        target_rate_events_per_s=None,               # closed loop: capacity
        latency_budget_s=SERVICE_BUDGET_S,
        warmup_segments=1))
    summ = svc.serve(SERVICE_SEGMENTS)["summary"]
    svc_ev_s = summ["throughput_events_per_s"]
    rows.append([f"sustained_service/N{SWEEP_CFG['n_devices']}",
                 round(summ["events"] / svc_ev_s * 1e6, 1),
                 f"{svc_ev_s:.1f} ev/s, "
                 f"p99={summ['latency_s']['p99'] * 1e3:.0f}ms, "
                 f"slo={summ['slo']['attained']:.0%}"])
    record["sustained_service"] = {
        "segments": SERVICE_SEGMENTS,
        "segment_events": SERVICE_SEGMENT_EVENTS,
        "eval_every_events": SERVICE_EVAL_EVERY,
        "events_measured": summ["events"],
        **{k: SWEEP_CFG[k] for k in ("dataset", "n_devices", "n_subchannels",
                                     "n_samples", "batch", "local_steps")},
        "scenario": "churn",
        "closed_loop": True,
        "events_per_s": svc_ev_s,
        "p50_latency_s": summ["latency_s"]["p50"],
        "p99_latency_s": summ["latency_s"]["p99"],
        "slo_budget_s": SERVICE_BUDGET_S,
        "slo_attained": summ["slo"]["attained"],
        "mean_pending": summ["buffer"]["mean_pending"],
        "target_events_per_s": SERVICE_TARGET_EV_PER_S,
        "meets_target": bool(svc_ev_s >= SERVICE_TARGET_EV_PER_S),
    }

    # ---- acceptance: two-tier async hierarchy vs the sync hier scan -------
    h_sync = HierSimConfig(policy=RoundPolicy(ra="fix"), **HIER_CFG)
    h_async = HierSimConfig(policy=RoundPolicy(ra="fix"),
                            aggregation="async", global_aggregation="async",
                            **HIER_CFG)
    h_times = {"scan": [], "async": []}
    h_hists = {}
    for _ in range(HIER_REPS):
        for eng, hcfg in (("scan", h_sync), ("async", h_async)):
            t0 = time.perf_counter()
            h_hists[eng] = run_hier_many([hcfg], engine=eng)[0]
            h_times[eng].append(time.perf_counter() - t0)
    t_hs, t_ha = min(h_times["scan"]), min(h_times["async"])
    hier_n = HIER_CELLS * HIER_CFG["devices_per_cell"]
    hier_r_per_s = HIER_CFG["rounds"] / t_hs
    hier_ev_per_s = HIER_CFG["rounds"] / t_ha
    hier_ratio = hier_ev_per_s / hier_r_per_s
    hier_p99_async = float(np.percentile(h_hists["async"].latency_all, 99))
    hier_p99_sync = float(np.percentile(h_hists["scan"].latency_all, 99))
    hier_p99_ratio = hier_p99_async / hier_p99_sync
    # Degenerate-limit anchor at bench scale: full buffers at BOTH tiers
    # reproduce the sync hierarchy's transmitted sets bit-exactly.
    h_full = HierSimConfig(policy=RoundPolicy(ra="fix"),
                           aggregation="async_full",
                           global_aggregation="async_full", **HIER_CFG)
    h_anchor = bool(np.array_equal(
        run_hier_many([h_full], engine="async")[0].tx_trace,
        h_hists["scan"].tx_trace))
    hier_meets = bool(hier_ratio >= HIER_TARGET_THROUGHPUT_RATIO
                      and hier_p99_ratio <= HIER_TARGET_P99_RATIO
                      and h_anchor)
    rows.append([f"hier_sync_scan/N{hier_n}x{HIER_CELLS}cells",
                 round(t_hs * 1e6, 1),
                 f"{hier_r_per_s:.1f} r/s, p99={hier_p99_sync:.2f}s sim"])
    rows.append([f"hier_async/N{hier_n}x{HIER_CELLS}cells",
                 round(t_ha * 1e6, 1),
                 f"{hier_ev_per_s:.1f} ev/s ({hier_ratio:.2f}x sync), "
                 f"p99={hier_p99_async:.2f}s sim, anchor={h_anchor}"])
    record["hier_async"] = {
        "n_cells": HIER_CELLS, "reps": HIER_REPS,
        **{k: HIER_CFG[k] for k in ("rounds", "devices_per_cell",
                                    "subchannels_per_cell", "n_samples",
                                    "batch", "local_steps", "scenario")},
        "n_devices_total": hier_n,
        "sync_scan_s": t_hs, "async_s": t_ha,
        "sync_scan_s_all": h_times["scan"], "async_s_all": h_times["async"],
        "sync_rounds_per_s": hier_r_per_s, "events_per_s": hier_ev_per_s,
        "throughput_ratio": hier_ratio,
        "p99_commit_latency_s": hier_p99_async,
        "p99_sync_round_latency_s": hier_p99_sync,
        "p99_latency_ratio": hier_p99_ratio,
        "full_buffer_anchor_tx_agree": h_anchor,
        "target_throughput_ratio": HIER_TARGET_THROUGHPUT_RATIO,
        "target_p99_ratio": HIER_TARGET_P99_RATIO,
        "meets_target": hier_meets,
    }

    # ---- acceptance: 8-config policy x seed grid vs solo-call loop --------
    grid = [SimConfig(seed=s, policy=RoundPolicy(ds=d, ra="fix"), **GRID_CFG)
            for d in GRID_DS for s in range(GRID_SEEDS)]
    t_grid, t_solo = [], []
    grid_hists = solo_hists = None
    for _ in range(GRID_REPS):
        t0 = time.perf_counter()
        grid_hists = run_many(grid, engine="scan")
        t_grid.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        solo_hists = [run_simulation(c, engine="scan") for c in grid]
        t_solo.append(time.perf_counter() - t0)
    grid_agree = all(
        np.array_equal(a.tx_trace, b.tx_trace)
        and np.array_equal(a.global_loss, b.global_loss)
        for a, b in zip(grid_hists, solo_hists))
    tg, ts = min(t_grid), min(t_solo)
    grid_speedup = ts / tg
    rows.append([f"sweep/solo_loop/{len(grid)}cfg", round(ts * 1e6, 1),
                 f"{GRID_CFG['rounds']} rounds, N={GRID_CFG['n_devices']}"])
    rows.append([f"sweep/grid/{len(grid)}cfg", round(tg * 1e6, 1),
                 f"{grid_speedup:.1f}x, agree={grid_agree}"])
    record["sweep_grid"] = {
        "policies": list(GRID_DS), "seeds": GRID_SEEDS, "reps": GRID_REPS,
        **GRID_CFG,
        "solo_loop_s": ts, "grid_s": tg,
        "solo_loop_s_all": t_solo, "grid_s_all": t_grid,
        "speedup": grid_speedup, "results_agree": bool(grid_agree),
        "target_speedup": 2.0, "meets_target": bool(grid_speedup >= 2.0),
    }

    # ---- scenario layer: trace-gen + solve overhead vs the static world ---
    wcfg = WirelessConfig(n_devices=SCN_N, n_subchannels=K)
    rng = np.random.default_rng(0)
    beta = rng.integers(5, 60, SCN_N).astype(float)
    emax0 = np.full((SCN_ROUNDS, SCN_N), wcfg.e_max_j)
    solve_pairs_jit(beta[None, None, :],
                    generate_traces(0, wcfg, "static", SCN_ROUNDS).h2_all,
                    wcfg, emax0[:, None, :],
                    backend=STEP_BACKEND)                   # warm/compile
    scn_rec = {}
    for name in ("static", "urban"):
        t_gen, t_solve = [], []
        for _ in range(SCN_REPS):
            t0 = time.perf_counter()
            tr = generate_traces(0, wcfg, name, SCN_ROUNDS)
            t_gen.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            ra = solve_pairs_jit(beta[None, None, :], tr.h2_all, wcfg,
                                 np.broadcast_to(tr.e_max_j[:, None, :],
                                                 tr.h2_all.shape),
                                 backend=STEP_BACKEND)
            apply_dynamics(ra, tr.avail, tr.slowdown, beta, wcfg)
            t_solve.append(time.perf_counter() - t0)
        scn_rec[name] = {"trace_gen_s": min(t_gen), "solve_s": min(t_solve),
                         "total_s": min(t_gen) + min(t_solve)}
        rows.append([f"scenario/{name}/N{SCN_N}",
                     round(scn_rec[name]["total_s"] * 1e6, 1),
                     f"{SCN_ROUNDS} rounds, gen={min(t_gen)*1e3:.1f}ms"])
    overhead = scn_rec["urban"]["total_s"] / scn_rec["static"]["total_s"]
    rows[-1][2] += f", {overhead:.2f}x vs static"
    record["scenario_traces"] = {
        "rounds": SCN_ROUNDS, "N": SCN_N, "K": K, "reps": SCN_REPS,
        **{f"{k}_{m}": v for k, d in scn_rec.items() for m, v in d.items()},
        "overhead_vs_static": overhead,
    }

    emit("control_plane", ["us_per_call", "derived"], rows)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(record, fh, indent=2)
        print(f"# wrote {json_path}")
    return record


if __name__ == "__main__":
    run("BENCH_control_plane.json")
