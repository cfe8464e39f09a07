"""Reproduce the paper's headline comparison from ONE declarative sweep.

Runs the Fig.-3-style device-selection comparison — the proposed Algorithm 3
vs the random / fixed / cluster baselines (all with MO-RA + M-SA) — over
several seeds through the vmapped scan engine, then writes:

  results/<name>/v####/sweep.json     versioned metrics + curves artifact
  results/<name>/v####/figures/*.svg  convergence curves (vs round and vs
                                      simulated time), sub-channel
                                      utilization bars, latency CDF

  PYTHONPATH=src python examples/reproduce_figures.py              # reduced
  PYTHONPATH=src python examples/reproduce_figures.py --full       # paper scale
  PYTHONPATH=src python examples/reproduce_figures.py --smoke      # CI smoke
  PYTHONPATH=src python examples/reproduce_figures.py --engine loop  # reference

Scenario robustness (DESIGN.md §11): pass one or more --scenario presets
(static / corr_fading / mobility / churn / harvest / urban) to cross the
policy grid with time-varying environments — the whole policy x scenario
x seed grid still dispatches as one compiled scan program:

  PYTHONPATH=src python examples/reproduce_figures.py \
      --name scenario_robustness --scenario static --scenario corr_fading

Sync-vs-async server disciplines (DESIGN.md §12): pass one or more
--aggregation presets (sync / async / async_const / async_full) to add
the server-aggregation axis — async cells share the sync cells' worlds
and Γ solves and route through the buffered event engine, and the
gallery gains the time-to-target comparison figure:

  PYTHONPATH=src python examples/reproduce_figures.py \
      --name async_vs_sync --ds alg3 \
      --aggregation sync --aggregation async \
      --scenario static --scenario churn --scenario urban

Every run appends a NEW version directory; RESULTS.md documents the
gallery generated from these artifacts.
"""
import argparse

from repro.core import PAPER_BASELINE_DS
from repro.experiments import SweepSpec, run_sweep


def build_spec(args: argparse.Namespace) -> SweepSpec:
    scenarios = tuple(args.scenario) if args.scenario else ("static",)
    aggregation = tuple(args.aggregation) if args.aggregation else ("sync",)
    if args.smoke:       # CI: 2 policies x 2 seeds, minutes on 2 CPU cores
        return SweepSpec(
            name=args.name, datasets="mnist",
            ds=tuple(args.ds) if args.ds else ("alg3", "random"),
            scenarios=scenarios, aggregation=aggregation,
            seeds=(0, 1), rounds=12, n_devices=12, n_subchannels=4,
            target_loss=args.target_loss,
            overrides={"n_samples": 128, "batch": 16, "eval_every": 3,
                       "local_steps": 2})
    if args.full:        # paper scale (Table I / Sec. VI)
        return SweepSpec(
            name=args.name, datasets="mnist",
            ds=tuple(args.ds) if args.ds else PAPER_BASELINE_DS,
            scenarios=scenarios, aggregation=aggregation,
            seeds=tuple(range(args.seeds)), rounds=300,
            n_devices=20, n_subchannels=4, target_loss=args.target_loss)
    # default: reduced scale, same scheme ordering (DESIGN.md §2)
    return SweepSpec(
        name=args.name, datasets="mnist",
        ds=tuple(args.ds) if args.ds else PAPER_BASELINE_DS,
        scenarios=scenarios, aggregation=aggregation,
        seeds=tuple(range(args.seeds)), rounds=60,
        n_devices=20, n_subchannels=4, target_loss=args.target_loss,
        overrides={"n_samples": 500, "eval_every": 5})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--name", default="fig3_convergence",
                    help="sweep/artifact name under --results-root")
    ap.add_argument("--results-root", default="results")
    ap.add_argument("--seeds", type=int, default=2,
                    help="number of world seeds (0..seeds-1)")
    ap.add_argument("--target-loss", type=float, default=1.0,
                    help="rounds/time-to-target threshold")
    ap.add_argument("--engine", choices=("scan", "loop"), default="scan")
    ap.add_argument("--full", action="store_true", help="paper scale")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid (2 policies x 2 seeds)")
    ap.add_argument("--scenario", action="append", default=None,
                    metavar="PRESET",
                    help="environment scenario preset (repeatable; adds a "
                         "scenario axis to the grid — see repro.scenarios)")
    ap.add_argument("--aggregation", action="append", default=None,
                    metavar="PRESET",
                    help="server-aggregation preset (repeatable; sync / "
                         "async / async_const / async_full — async cells "
                         "run the buffered event engine, DESIGN.md §12)")
    ap.add_argument("--ds", action="append", default=None, metavar="SCHEME",
                    help="device-selection scheme axis override "
                         "(repeatable; default: the per-mode policy grid)")
    args = ap.parse_args()

    spec = build_spec(args)
    print(f"sweep {spec.name!r}: {spec.n_cells} cells "
          f"({len(spec.policies)} policies x {len(spec.scenarios)} scenarios "
          f"x {len(spec.aggregation)} aggregations x {len(spec.seeds)} "
          f"seeds), {spec.rounds} rounds, engine={args.engine}")
    res = run_sweep(spec, engine=args.engine,
                    results_root=args.results_root, figures=True)
    print(f"wrote {res.out_dir}/sweep.json "
          f"(+ figures/) in {res.record['wall_s']:.1f}s")

    print(f"\n{'policy':34s} {'final loss':>10s} {'rounds→{:g}'.format(spec.target_loss):>10s} "
          f"{'util':>6s} {'cum lat (s)':>12s}")
    rows: dict[str, list[dict]] = {}
    many_sc = len(spec.scenarios) > 1
    many_ag = len(spec.aggregation) > 1
    for c in res.record["cells"]:
        label = c["policy"]["label"]
        if many_sc:   # never pool metrics across environments
            label = f"{label} @{c['scenario']}"
        if many_ag:   # ... nor across server disciplines
            label = f"{label} [{c['aggregation']}]"
        rows.setdefault(label, []).append(c["metrics"])
    for label, ms in rows.items():
        import numpy as np
        r2t = [m["rounds_to_target"] for m in ms]
        r2t_s = ("-" if any(r is None for r in r2t)
                 else f"{np.mean(r2t):.1f}")
        print(f"{label:34s} {np.mean([m['final_loss'] for m in ms]):10.4f} "
              f"{r2t_s:>10s} "
              f"{np.mean([m['mean_subchannel_utilization'] for m in ms]):6.2f} "
              f"{np.mean([m['cumulative_latency_s'] for m in ms]):12.1f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
