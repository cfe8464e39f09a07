"""Entry path `sweep`: the planner's `fl.run_many(..., engine="scan")`, one
world per call with one config per selection scheme of the traffic file.

Every call plans a fresh world, its seed drawn from the run's seed and the
call's index, as a planner's calls do: each pays its world's generation,
its Γ solve, the engine's re-trace and its cache loads
(`compile_ms.sweep`).  Every world holds the same data sizes in another
order (`data_sizes: stratified`), so the engine keeps one shape.  Set-up
makes one call on a world of its own, and solves Γ for the worlds of the
window's first `gamma_warm_calls` calls, whose active-set bucket shapes
depend on the channels, so that none of them compiles in the window.
Calls run back to back; every call that starts before the deadline
counts, and the window ends when the last one ends.
"""
from __future__ import annotations

import sys
import time

import numpy as np

# The control: the reference in the precision below the configuration's,
# float32 for the float64 Γ and bfloat16 for the float32 training.
CONTROL = {"gamma_dtype": np.float32, "train_dtype": "bfloat16"}


class Capture:
    """Keeps, for the call that the seeded reservoir picks, the worlds
    (`_prepare` results), the engine inputs, the final parameters and the
    histories.

    The engine's runner keeps its final carry to itself, so the dispatch
    is handed a runner that also returns the carry of its outermost
    `lax.scan` (the round loop): one more output of the same program."""

    def __init__(self, run, sim_mod, rng):
        self.rng, self.calls, self.kept, self.current = rng, 0, None, None
        solve, dispatch = sim_mod._solve_horizons, sim_mod._dispatch_group

        def solve_horizons(preps, *a, **kw):
            ras, secs = solve(preps, *a, **kw)
            self.current["preps"], self.current["ras"] = preps, ras
            return ras, secs

        def dispatch_group(run, datas, shard):
            self.current["datas"] = [
                {k: d[k] for k in ("gamma", "feas", "sel_perms",
                                   "assign_perms", "policy_idx", "params0")}
                for d in datas]
            ys = dict(dispatch(with_final_carry(run), datas, shard))
            self.current["params"] = ys.pop(FINAL)
            return ys
        run.patch(sim_mod, "_solve_horizons", solve_horizons)
        run.patch(sim_mod, "_dispatch_group", dispatch_group)

    def begin(self):
        self.current = {}

    def end(self, hists, keep: bool):
        """Reservoir of one over the window's calls, drawn from the seed."""
        if keep:
            self.calls += 1
            self.current["hists"] = hists
            if self.rng.integers(self.calls) == 0:
                self.kept = self.current
        self.current = None


FINAL = "bench_final_params"


def with_final_carry(run):
    """`run`, also returning the parameters of the final carry of its
    outermost `lax.scan` under the key `FINAL`."""
    import jax

    def run_with_carry(data):
        scan, box = jax.lax.scan, []

        def outer_scan(*a, **kw):
            jax.lax.scan = scan              # nested scans run as they are
            carry, ys = scan(*a, **kw)
            box.append(carry)
            return carry, ys
        jax.lax.scan = outer_scan
        try:
            ys = run(data)
        finally:
            jax.lax.scan = scan
        return dict(ys, **{FINAL: box[0][0]})
    return run_with_carry


def configs(run, salt: int, call: int):
    """One config per scheme, on the world of (seed, salt, call)."""
    from bench import world as w
    tr = run.traffic
    seed = w.world_seed(run.seed, salt, call)
    from repro.core import RoundPolicy
    return [w.sim_config(run.config, tr, seed,
                         policy=RoundPolicy(ds=ds, **tr["policy"]))
            for ds in tr["schemes"]]


def run(run, device) -> str:
    from bench import harness, world, window, checks
    import repro.fl.sim as sim_mod
    from repro.fl import run_many

    cfg, tr = run.config, run.traffic
    world.install(run)
    if run.trace:
        run.load_readers()
        run.instrument()
        for dotted in run.missing():
            print(f"bench: {dotted} not found; its metrics read nothing",
                  file=sys.stderr)
    run.listen_compiles()
    for i in range(tr["gamma_warm_calls"]):
        sim_mod._solve_horizons([sim_mod._prepare(configs(run, 1, i)[0])],
                                None)
    cap = Capture(run, sim_mod, np.random.default_rng([run.seed, 11]))

    cap.begin()                            # set-up: one call, its own world
    run_many(configs(run, 2, 0), engine="scan")
    cap.end(None, keep=False)
    setup_s = time.perf_counter() - run.t_start

    calls, rounds, dispatched = [], [], 0
    w0 = time.perf_counter()
    while not calls or time.perf_counter() - w0 < run.seconds:
        if run.trace and len(calls) == tr["trace_after_calls"]:
            run.trace_start()
        cfgs = configs(run, 1, len(calls))
        cap.begin()
        t0 = time.perf_counter()
        hists = run_many(cfgs, engine="scan")
        calls.append((t0, time.perf_counter()))
        cap.end(hists, keep=True)
        rounds.append(sum(c.rounds for c in cfgs))
        dispatched += sum(int(h.tx_trace.sum()) for h in hists)
        if run.tracing and calls[-1][1] - run._trace_t[0] >= tr["trace_seconds"]:
            run.trace_stop()
    if run.tracing:
        run.trace_stop()
    run.window = (w0, calls[-1][1])
    e2e = window.sweep(calls, rounds)
    print(f"bench: {run.compile_s_in_window():.3f} s of compiles or cache "
          f"loads inside the window", file=sys.stderr)
    mem = harness.memory_peak_bytes(run.cell["chips"])

    run.counters["calls"] = len(calls)
    run.counters["sim_rounds"] = sum(rounds)
    t1 = cfg["table1"]
    evals = len(calls) * len(tr["schemes"]) * len(
        eval_rounds(tr["rounds"], tr.get("eval_every", 1)))
    fwd = harness.flops_model(cfg).forward_flops()
    run.counters["model_flops"] = fwd * (
        3 * dispatched * cfg["local_steps"] * t1["batch"]
        + evals * cfg["n_samples"])
    run.counters["peak_flops"] = harness.peaks(device["kind"])["bf16_flops_per_s"]

    kept = cap.kept
    cap = None
    ref = reference_readings(run, kept)
    got = program_readings(run, kept)
    values = gaps(got, ref)
    print(f"bench: update gap by leaf: {leaf_gaps(got, ref)}", file=sys.stderr)
    print("bench: Γ pair gaps: p99 {gamma_p99_rel_err!r} max "
          "{gamma_max_rel_err!r} mean {gamma_mean_rel_err!r}".format(**values),
          file=sys.stderr)
    ok, chk = checks.verdict(values, tr["check"]["limits"])
    if run.control:
        run.control_values = gaps(reference_readings(run, kept, **CONTROL), ref)

    dev = dict(device, memory_peak_bytes=mem)
    breakdown = None
    if run.trace:
        metrics = {}
        for spec in run.per_layer_specs:
            v = run.readers[spec["name"]].read(run)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        if run.trace_result:
            dev.update(busy_s=run.trace_result["busy_s"],
                       window_s=run.trace_result["window_s"])
            breakdown = run.trace_result["breakdown"]
    else:
        metrics = {"sim_rounds_per_s": {"value": e2e["sim_rounds_per_s"],
                                        "unit": "rounds/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    harness.print_checks(chk)
    return harness.result_line(correct=ok, attempted=len(calls), failed=0,
                               metrics=metrics, device=dev, checks=chk,
                               breakdown=breakdown)


def eval_rounds(rounds: int, every: int) -> list[int]:
    """Rounds a simulation evaluates at: every `every`-th and the last."""
    return [t for t in range(rounds) if t % every == 0 or t == rounds - 1]


def _sample(run, prep):
    from bench import checks
    rng = np.random.default_rng([run.seed, 13])
    return checks.sample_pairs(rng, prep.h2_all.shape,
                               run.traffic["check"]["gamma_pairs"])


def program_readings(run, kept) -> dict:
    """What the kept call produced: the engine's Γ at the sampled pairs,
    and each scheme's decisions, eval losses and parameters' change."""
    from bench import checks
    prep, ra = kept["preps"][0], kept["ras"][0]
    idx = _sample(run, prep)
    d0 = {k: np.asarray(v) for k, v in kept["datas"][0].items()}
    raw = tuple(np.asarray(a).reshape(-1)[idx]
                for a in (ra.time_s, ra.tau, ra.p, ra.feasible))
    import jax
    from bench.reference import train
    sims = []
    for i, h in enumerate(kept["hists"]):
        change = jax.tree_util.tree_map(
            lambda a, b: np.asarray(a[i], np.float32) - np.asarray(b, np.float32),
            kept["params"], kept["datas"][i]["params0"])
        sims.append({"decisions": (h.tx_trace, h.age_trace,
                                   h.latency_all.astype(np.float32)),
                     "losses": list(h.global_loss),
                     "norms": train.leaf_norms(change)})
    gamma = {"raw": raw,
             "input": (d0["gamma"].reshape(-1)[idx], d0["feas"].reshape(-1)[idx]),
             "expected_input": checks.engine_input(
                 run.config, prep.h2_all.shape, raw, prep.beta, prep.avail,
                 prep.slowdown, idx)}
    return {"gamma": gamma, "sims": sims}


def reference_readings(run, kept, *, gamma_dtype=np.float64,
                       train_dtype=None) -> dict:
    """The plain reference on the kept call's world: NumPy Γ at the sampled
    pairs, and for each scheme the synchronous rounds (decisions on the
    engine's own Γ inputs, FedAvg training, eval every eval round, and the
    parameters' change over the whole horizon).
    Float32 Γ and bfloat16 training are the control."""
    import jax
    import jax.numpy as jnp
    from bench import checks
    from bench.reference import engines, train
    cfg, tr = run.config, run.traffic
    prep = kept["preps"][0]
    idx = _sample(run, prep)
    raw = checks.gamma_sample(cfg, prep.h2_all, prep.emax_all, prep.beta, idx,
                              dtype=gamma_dtype)
    fed = checks.engine_input(cfg, prep.h2_all.shape, raw, prep.beta,
                              prep.avail, prep.slowdown, idx)
    gamma = {"raw": raw, "input": fed, "expected_input": fed}
    t1 = cfg["table1"]
    trainer = train.Trainer(cfg["arch"], optimizer=t1["optimizer"], lr=t1["lr"],
                            batch=t1["batch"], local_steps=cfg["local_steps"],
                            dtype=train_dtype or jnp.float32)
    data = {"x_all": prep.x_all, "y_all": prep.y_all, "m_all": prep.m_all,
            "x_full": jnp.asarray(prep.ds.x), "y_full": jnp.asarray(prep.ds.y)}
    sims = []
    for ds, d in zip(tr["schemes"], kept["datas"]):
        inp = {k: np.asarray(v) for k, v in d.items()}
        inp.update(beta=prep.beta, clusters=prep.clusters,
                   fixed_ids=prep.fixed_ids)
        key, k_init = jax.random.split(jax.random.PRNGKey(prep.cfg.seed))
        params = trainer.cast(train.init_params(cfg["arch"], k_init))
        ev = eval_rounds(tr["rounds"], tr["eval_every"])
        dec, losses, final = engines.replay_sync(
            inp, data, ds=ds, k=tr["n_subchannels"], trainer=trainer,
            params=params, key=key, eval_rounds=set(ev))
        change = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            final, params)
        sims.append({"decisions": (dec["transmitted"], dec["age"],
                                   dec["latency"].astype(np.float32)),
                     "losses": [losses[t] for t in ev],
                     "norms": train.leaf_norms(change)})
    return {"gamma": gamma, "sims": sims}


def gaps(got: dict, ref: dict) -> dict:
    from bench import checks
    out = checks.gamma_gaps(got["gamma"], ref["gamma"])
    mism, lp, lr = 0, [], []
    for g, r in zip(got["sims"], ref["sims"]):
        for a, b in zip(g["decisions"], r["decisions"]):
            mism += int(np.sum(np.any((np.asarray(a) != np.asarray(b))
                                      .reshape(len(a), -1), axis=1)))
        lp += g["losses"]
        lr += r["losses"]
    out["decision_mismatch"] = mism
    out["loss_rel_err"] = checks.loss_rel_err(lp, lr)
    out["update_gap"] = max(checks.update_gap(g["norms"], r["norms"])
                            for g, r in zip(got["sims"], ref["sims"]))
    return out


def leaf_gaps(got: dict, ref: dict) -> dict:
    """Each leaf's gap of `update_gap`, in the scheme where it is worst."""
    out = {}
    for g, r in zip(got["sims"], ref["sims"]):
        med = float(np.median(list(r["norms"].values())))
        for leaf, rn in r["norms"].items():
            out[leaf] = max(out.get(leaf, 0.0),
                            abs(g["norms"][leaf] - rn) / max(rn, med))
    return out
