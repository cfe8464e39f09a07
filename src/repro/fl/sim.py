"""End-to-end FLOWN simulation harness (reproduces paper Sec. VI).

Couples the control plane (Stackelberg round planning over a simulated
wireless network) with the learning plane (real JAX training of the paper's
models on seeded synthetic datasets).  One `run_simulation` call produces
the trajectory behind one curve of Figs. 3-9.

Control-plane scheduling is *hoisted out of the training loop*: Γ (the
Algorithm-1 minimum-time matrix) is selection-independent, so every round's
channel realization is pre-sampled and the full-horizon (rounds x K x N)
tensor is solved in one batched jitted call (`core.monotonic_jax`) before
the first training step.  `run_many` extends the same trick across
simulations: all configured runs' horizons are flattened into a single
solver batch, so planning cost is amortized over seeds/sweeps (Figs. 5-9
sweep many configs) and the learning plane never waits on the host solver
mid-run.  DESIGN.md §6.

Three round-loop engines (DESIGN.md §8, §12):

  engine="loop"  -- the host loop: per-round `plan_round` (NumPy leader)
                    interleaved with jitted training calls;
  engine="scan"  -- the device-resident loop: the jnp leader plane
                    (`core.leader_jax`) fused with training inside ONE
                    `lax.scan` over rounds, and — in `run_many` — `vmap`ped
                    across the seeds of a sweep so a Fig. 5-9 curve family
                    is a single compiled program;
  engine="async" -- the buffered event-timeline loop (`fl.async_loop`):
                    the eq.-9 round barrier is replaced by per-device
                    virtual clocks driven by the same precomputed Γ +
                    scenario traces, with the server committing
                    staleness-weighted updates as they land
                    (`SimConfig.aggregation` names the commit policy;
                    cells with an async aggregation route here
                    automatically from the other engines).

All engines consume identical pre-sampled randomness (`RoundRandomness`
permutations drawn in `_prepare`), so their transmitted sets, AoU
trajectories, and latencies coincide exactly; the differential harness
tests/test_scan_equivalence.py pins this for every RoundPolicy, and
tests/test_async_equivalence.py pins the async engine's degenerate
(full-buffer) limit bit-exactly against the scan engine.

Scenario layer (DESIGN.md §11): the wireless environment of a simulation
is a named `repro.scenarios.Scenario` — temporally correlated fading,
device mobility, churn/stragglers, and energy-harvesting budgets generated
as whole-horizon traces by `_prepare` (the `static` preset replays the
legacy inline sampling bit-exactly).  Traces enter through the SAME three
tensors both engines already consume — the channel horizon `h2_all`
(fading x mobility), the solver's per-element energy budgets
(harvesting), and the solved `RAResult` (churn availability folds into
the Prop-1 mask, straggler slowdowns into the eq.-1 compute share of Γ,
via `scenarios.apply_dynamics`) — so the loop/scan/vmap/shard paths stay
differentially equivalent under every scenario with zero engine changes.

Sweep extensions (DESIGN.md §10): configs that differ only in
`policy.ds`/`policy.sa` share ONE `_Prepared` world (same seed => same
data/topology/channels) and ONE whole-horizon Γ solve, and the scan engine
batches them into a single compiled program — `leader_round` branches become
a `lax.switch` on a per-element policy index, so a policy x seed grid is one
XLA program with a (policy x seed) batch axis.  When more than one local
device is visible, that batch axis is sharded across devices via
`shard_map` (`run_many(..., shard=...)`); on one device it stays a `vmap`.
The declarative front-end over this path lives in `repro.experiments`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (
    RAResult,
    RoundPolicy,
    RoundRandomness,
    WirelessConfig,
    init_aou,
    make_clusters,
    participation_deficit,
    plan_round,
    solve_pairs_fused,
    solve_pairs_jit,
)
from ..core.monotonic import fixed_ra
from ..scenarios import (
    Scenario,
    apply_dynamics,
    compose_gains,
    get_scenario,
    sample_churn,
    sample_distances,
    sample_energy,
    sample_fading,
)
from ..data.fl_datasets import (
    Dataset,
    FLPartition,
    make_dataset,
    partition_dirichlet,
    partition_imbalanced_iid,
)
from .. import tracing
from ..models.small import SmallModel, get_small_model
from ..train.optimizer import make_optimizer
from .async_loop import build_async_runner
from .client import make_local_trainer
from .engine_common import (
    make_eval_fn,
    make_leader_branches,
    make_xs,
    run_leader,
    train_clients,
)
from .server import AsyncAggregation, aggregate, get_aggregation

__all__ = ["SimConfig", "SimHistory", "run_simulation", "run_many", "TABLE1"]

# Table I per-dataset settings: (model_bits, e_max, lr, batch, optimizer).
TABLE1 = {
    "mnist": dict(model_bits=1e6, e_max=0.02, lr=0.01, batch=32, optimizer="sgd"),
    "cifar10": dict(model_bits=5e6, e_max=0.1, lr=0.001, batch=512, optimizer="adam"),
    "sst2": dict(model_bits=5e6, e_max=0.1, lr=0.01, batch=128, optimizer="sgd"),
}


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """One Sec.-VI simulation: dataset + network size + scheme policy +
    seed (Table-I learning settings default per dataset; override fields
    are None = "use Table I")."""

    dataset: str = "mnist"
    n_devices: int = 20
    n_subchannels: int = 4
    rounds: int = 100
    policy: RoundPolicy = RoundPolicy()
    seed: int = 0
    n_samples: int | None = None       # dataset size (None -> dataset default)
    local_steps: int = 4
    radius_m: float = 500.0
    pt_dbm: float = 10.0
    e_max_j: float | None = None       # None -> Table I per-dataset value
    lr: float | None = None
    batch: int | None = None
    optimizer: str | None = None
    eval_every: int = 1
    track_gradnorm: bool = False       # needed for the Prop-3 bound benchmark
    partition: str = "iid"             # "iid" (paper) | "dirichlet" (non-IID ext.)
    dirichlet_alpha: float = 0.5
    scenario: str | Scenario = "static"  # environment preset name or Scenario
    # Server aggregation discipline: "sync" (eq. 34, round barrier) or an
    # async preset name / `AsyncAggregation` spec (buffered staleness-
    # weighted commits; routes the cell through engine="async").
    aggregation: str | AsyncAggregation = "sync"

    def wireless(self) -> WirelessConfig:
        t1 = TABLE1[self.dataset]
        return WirelessConfig(
            n_devices=self.n_devices,
            n_subchannels=self.n_subchannels,
            radius_m=self.radius_m,
            pt_dbm=self.pt_dbm,
            model_bits=t1["model_bits"],
            e_max_j=self.e_max_j if self.e_max_j is not None else t1["e_max"],
        )


@dataclasses.dataclass
class SimHistory:
    """One finished simulation's trajectory: eval-round curves (loss,
    accuracy, eq.-9 latency, cumulative convergence time) plus full
    per-round traces (`*_all`, `tx_trace`, `age_trace`) used by the
    differential harness and the sweep metrics."""

    label: str
    rounds: np.ndarray
    global_loss: np.ndarray
    accuracy: np.ndarray
    latency_s: np.ndarray          # per-round latency (eq. 9) at eval rounds
    cum_time_s: np.ndarray         # convergence time: cumsum over ALL rounds,
                                   # sampled at eval rounds
    n_selected: np.ndarray
    n_transmitted: np.ndarray
    energy_j: np.ndarray           # total energy spent per round (eval rounds)
    deficits: np.ndarray           # Prop-3 participation deficits
    grad_sq_norms: np.ndarray      # ||grad F||^2 per round (0 if untracked)
    beta: np.ndarray
    wall_s: float
    plan_wall_s: float = 0.0       # control-plane share (Γ precompute)
    # Full per-round traces (every round, not just eval rounds).  The
    # differential harness compares these across engines; cum_time_s above
    # is their cumsum sampled at eval rounds.
    latency_all: np.ndarray | None = None   # (rounds,)
    energy_all: np.ndarray | None = None    # (rounds,)
    tx_trace: np.ndarray | None = None      # (rounds, N) bool
    age_trace: np.ndarray | None = None     # (rounds, N) post-update AoU
    # Async-engine extras (None on sync runs).  For engine="async",
    # `tx_trace` records DISPATCHES and `commit_trace` the server-side
    # commits; `async_trace` holds the event-loop invariant traces
    # (n_pending / overflow / rem_dispatch) the property tests consume.
    commit_trace: np.ndarray | None = None  # (rounds, N) bool
    async_trace: dict | None = None


def _eval_rounds(rounds: int, eval_every: int) -> list[int]:
    return [t for t in range(rounds)
            if t % eval_every == 0 or t == rounds - 1]


def _pad_partition(ds: Dataset, part: FLPartition, bmax: int | None = None):
    """Pad per-device data to (N, Bmax, ...) + mask for vmapped training."""
    bmax = int(part.beta.max()) if bmax is None else bmax
    n = part.n_devices
    x = np.zeros((n, bmax) + ds.x.shape[1:], dtype=ds.x.dtype)
    y = np.zeros((n, bmax), dtype=ds.y.dtype)
    m = np.zeros((n, bmax), dtype=np.float32)
    for i, idx in enumerate(part.indices):
        x[i, : len(idx)] = ds.x[idx]
        y[i, : len(idx)] = ds.y[idx]
        m[i, : len(idx)] = 1.0
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(m)


def _sample_dataset(cfg: SimConfig, rng: np.random.Generator):
    """The world stream's dataset phase: dataset draw, device partition,
    padded client buffers.  This is the rng PREFIX of `_prepare` — it
    never consults the scenario — and it is reused verbatim by the
    sustained service (`repro.service`), whose open-ended world replays
    the same phase before handing the stream to `ScenarioStream`."""
    ds_kw = {} if cfg.n_samples is None else {"n": cfg.n_samples}
    ds = make_dataset(cfg.dataset, rng, **ds_kw)
    if cfg.partition == "dirichlet":
        part = partition_dirichlet(rng, ds.y, cfg.n_devices,
                                   cfg.dirichlet_alpha)
    else:
        part = partition_imbalanced_iid(rng, ds.n, cfg.n_devices)
    beta = part.beta.astype(np.float64)
    x_all, y_all, m_all = _pad_partition(ds, part)
    return ds, part, beta, x_all, y_all, m_all


@dataclasses.dataclass
class _Prepared:
    """Everything sampled ahead of the training loop for one simulation."""

    cfg: SimConfig
    wcfg: WirelessConfig
    rng: np.random.Generator
    ds: Dataset
    part: FLPartition
    beta: np.ndarray
    x_all: Any
    y_all: Any
    m_all: Any
    h2_all: np.ndarray             # (rounds, K, N) pre-sampled channel gains
    clusters: np.ndarray
    fixed_ids: np.ndarray
    sel_perms: np.ndarray          # (rounds, N) injected device permutations
    assign_perms: np.ndarray       # (rounds, K) injected channel permutations
    # Scenario traces (DESIGN.md §11): the whole-horizon environment.
    distances: np.ndarray          # (rounds, N) mobility distance trace
    avail: np.ndarray              # (rounds, N) bool churn availability
    slowdown: np.ndarray           # (rounds, N) straggler compute multipliers
    emax_all: np.ndarray           # (rounds, N) per-round energy budgets


def _prepare(cfg: SimConfig, _data_cache: dict | None = None) -> _Prepared:
    """Sample data + the whole-horizon scenario environment up front.

    The scenario processes replace the legacy inline topology / channel
    sampling at the SAME positions of the world rng stream (distances
    where `sample_topology` drew, fading where `sample_channel_gains`
    drew), and the scenario-only processes (churn, energy) draw strictly
    AFTER the legacy stream — so the `static` preset consumes the
    bit-identical stream and reproduces legacy trajectories exactly
    (tests/test_scenarios.py pins this).

    `_data_cache` (threaded in by `run_many`) shares the dataset phase —
    dataset, partition, padded client buffers — across worlds that differ
    only in scenario: the rng prefix through the partition draw never
    consults the scenario, so the cache stores the generator state at the
    branch point and replaying it is bit-identical to resampling.
    """
    with tracing.span("sim.prepare"):
        rng = np.random.default_rng(cfg.seed)
        wcfg = cfg.wireless()
        scn = get_scenario(cfg.scenario)

        data_key = (cfg.dataset, cfg.n_samples, cfg.partition,
                    cfg.dirichlet_alpha, cfg.n_devices, cfg.seed)
        with tracing.span("prepare.dataset"):
            if _data_cache is not None and data_key in _data_cache:
                (ds, part, beta, x_all, y_all, m_all,
                 state) = _data_cache[data_key]
                rng.bit_generator.state = state
            else:
                (ds, part, beta, x_all, y_all,
                 m_all) = _sample_dataset(cfg, rng)
                if _data_cache is not None:
                    _data_cache[data_key] = (ds, part, beta, x_all, y_all,
                                             m_all, rng.bit_generator.state)

        distances = sample_distances(rng, wcfg, scn.mobility, cfg.rounds)
        clusters = make_clusters(cfg.n_devices, cfg.n_subchannels, rng)
        fixed_ids = rng.permutation(cfg.n_devices)[: cfg.n_subchannels]
        g2_all = sample_fading(rng, wcfg, scn.fading, cfg.rounds)
        h2_all = compose_gains(g2_all, distances, wcfg)
        # One randomness stream for BOTH engines (DESIGN.md §8): every
        # round's leader-plane permutations are drawn here, never inside
        # the loop.
        sel_perms = np.stack([rng.permutation(cfg.n_devices)
                              for _ in range(cfg.rounds)])
        assign_perms = np.stack([rng.permutation(cfg.n_subchannels)
                                 for _ in range(cfg.rounds)])
        avail, slowdown = sample_churn(rng, scn.churn, cfg.rounds,
                                       cfg.n_devices)
        emax_all = sample_energy(rng, wcfg, scn.energy, cfg.rounds)

        return _Prepared(cfg=cfg, wcfg=wcfg, rng=rng, ds=ds, part=part,
                         beta=beta, x_all=x_all, y_all=y_all, m_all=m_all,
                         h2_all=h2_all, clusters=clusters, fixed_ids=fixed_ids,
                         sel_perms=sel_perms, assign_perms=assign_perms,
                         distances=distances, avail=avail, slowdown=slowdown,
                         emax_all=emax_all)


def _solve_horizons(
    preps: Sequence[_Prepared], backend: str | None,
    solver: str = "fused", shard: bool | None = None,
) -> tuple[list[RAResult], list[float]]:
    """Algorithm 1 for every round of every prepared simulation, batched.

    All MO-RA horizons are flattened into ONE jitted solver call per
    wireless-constant group (the solver is elementwise over pairs, so
    heterogeneous seeds/radii/budgets concatenate freely); FIX-RA horizons
    are a closed form, evaluated per config.  Energy budgets are the
    scenario's per-round per-device trace (`_Prepared.emax_all`,
    constant = the legacy e_max_j under a static energy process), fed as
    the solver's per-element e_max operand.  Returns the per-sim RAResults
    and each sim's share of planning wall time (group time split
    proportionally to its pair count).

    solver: "fused" (default — `solve_pairs_fused`, staged whole-loop jit
    with optional device-axis row sharding via `shard`) or "step"
    (`solve_pairs_jit`, the per-iteration phase-split driver).  shard is
    forwarded to the fused driver only (the step driver has no row-shard
    path); None auto-shards when more than one local device is visible.

    Sims sharing a `_Prepared` world (policy-only variants deduped by
    `run_many`) and the same `policy.ra` have identical Γ by construction:
    they are solved ONCE and the duplicates alias the representative's
    RAResult (read-only downstream), at zero attributed planning time.
    """
    out: list[RAResult | None] = [None] * len(preps)
    secs = [0.0] * len(preps)

    # Γ dedup: channel horizon identity (shared _Prepared) + RA scheme.
    dup_of: list[int | None] = [None] * len(preps)
    rep_idx: dict[tuple[int, str], int] = {}
    for i, p in enumerate(preps):
        key = (id(p.h2_all), p.cfg.policy.ra)
        if key in rep_idx:
            dup_of[i] = rep_idx[key]
        else:
            rep_idx[key] = i

    # The solver is elementwise over pairs with e_max as a per-element
    # operand, but the remaining wireless constants (model_bits, P_t, B,
    # CPU model, ...) are baked into the closed forms — group by them.
    def solver_key(wcfg: WirelessConfig) -> WirelessConfig:
        return dataclasses.replace(
            wcfg, n_devices=0, n_subchannels=0, radius_m=0.0, e_max_j=0.0,
            min_dist_m=1.0)

    groups: dict[WirelessConfig, list[int]] = {}
    for i, p in enumerate(preps):
        if p.cfg.policy.ra == "mo" and dup_of[i] is None:
            groups.setdefault(solver_key(p.wcfg), []).append(i)

    for mo in groups.values():
        h2_cat = np.concatenate([preps[i].h2_all.reshape(-1) for i in mo])
        beta_cat = np.concatenate([
            np.broadcast_to(preps[i].beta[None, None, :],
                            preps[i].h2_all.shape).reshape(-1)
            for i in mo])
        emax_cat = np.concatenate([
            np.broadcast_to(preps[i].emax_all[:, None, :],
                            preps[i].h2_all.shape).reshape(-1)
            for i in mo])
        group_pairs = h2_cat.size
        with tracing.timed("gamma.solve", pairs=group_pairs) as clock:
            if solver == "fused":
                ra_flat = solve_pairs_fused(beta_cat, h2_cat,
                                            preps[mo[0]].wcfg, emax_cat,
                                            backend=backend, shard=shard)
            else:
                ra_flat = solve_pairs_jit(beta_cat, h2_cat, preps[mo[0]].wcfg,
                                          emax_cat, backend=backend)
        group_s = clock.seconds
        off = 0
        for i in mo:
            shp = preps[i].h2_all.shape
            sz = preps[i].h2_all.size
            sl = slice(off, off + sz)
            out[i] = RAResult(
                tau=ra_flat.tau[sl].reshape(shp),
                p=ra_flat.p[sl].reshape(shp),
                time_s=ra_flat.time_s[sl].reshape(shp),
                energy_j=ra_flat.energy_j[sl].reshape(shp),
                feasible=ra_flat.feasible[sl].reshape(shp),
                iterations=ra_flat.iterations[sl].reshape(shp),
            )
            secs[i] = group_s * sz / group_pairs
            off += sz

    for i, p in enumerate(preps):
        if out[i] is None and dup_of[i] is None:
            t0 = time.perf_counter()
            out[i] = fixed_ra(p.beta[None, None, :], p.h2_all, p.wcfg,
                              np.broadcast_to(p.emax_all[:, None, :],
                                              p.h2_all.shape))
            secs[i] = time.perf_counter() - t0
    for i, rep in enumerate(dup_of):
        if rep is not None:
            out[i] = out[rep]
    return out, secs


def _slice_ra(ra: RAResult, t: int) -> RAResult:
    return RAResult(tau=ra.tau[t], p=ra.p[t], time_s=ra.time_s[t],
                    energy_j=ra.energy_j[t], feasible=ra.feasible[t],
                    iterations=ra.iterations[t])


# ---------------------------------------------------------------------------
# engine="loop": the host round loop
# ---------------------------------------------------------------------------

def _run_prepared(prep: _Prepared, ra_all: RAResult, plan_wall_s: float) -> SimHistory:
    cfg, wcfg, rng, beta = prep.cfg, prep.wcfg, prep.rng, prep.beta
    t_start = time.perf_counter()
    t1 = TABLE1[cfg.dataset]

    # ---- model + trainer --------------------------------------------------
    model: SmallModel = get_small_model(cfg.dataset)
    key = jax.random.PRNGKey(cfg.seed)
    key, k_init = jax.random.split(key)
    params = model.init(k_init)
    opt = make_optimizer(cfg.optimizer or t1["optimizer"], cfg.lr or t1["lr"])
    trainer = make_local_trainer(
        model.loss, opt, batch_size=cfg.batch or t1["batch"],
        local_steps=cfg.local_steps, loss_per_example=model.loss_per_example,
    )
    x_full, y_full = jnp.asarray(prep.ds.x), jnp.asarray(prep.ds.y)
    eval_loss = jax.jit(model.loss)
    eval_acc = jax.jit(model.accuracy)
    grad_norm_sq = jax.jit(
        lambda p: sum(
            jnp.sum(jnp.square(g))
            for g in jax.tree_util.tree_leaves(jax.grad(model.loss)(p, x_full, y_full))
        )
    )

    aou = init_aou(cfg.n_devices)
    k_slots = cfg.n_subchannels
    eval_at = set(_eval_rounds(cfg.rounds, cfg.eval_every))
    hist: dict[str, list] = {k: [] for k in (
        "round", "loss", "acc", "nsel", "ntx", "deficit", "gnorm")}
    # Per-round traces recorded EVERY round: convergence time (the paper's
    # headline metric) must accumulate unsampled rounds too, and the
    # differential harness compares full trajectories across engines.
    lat_all = np.zeros(cfg.rounds)
    energy_all = np.zeros(cfg.rounds)
    tx_trace = np.zeros((cfg.rounds, cfg.n_devices), dtype=bool)
    age_trace = np.zeros((cfg.rounds, cfg.n_devices), dtype=np.int64)

    for t in range(cfg.rounds):
        plan = plan_round(
            aou, beta, prep.h2_all[t], wcfg, rng,
            policy=cfg.policy, round_idx=t, clusters=prep.clusters,
            fixed_ids=prep.fixed_ids, ra=_slice_ra(ra_all, t),
            randomness=RoundRandomness(sel_perm=prep.sel_perms[t],
                                       assign_perm=prep.assign_perms[t]),
        )
        aou = plan.aou_next
        lat_all[t] = plan.latency_s
        energy_all[t] = float(plan.energy_per_device.sum())
        tx_trace[t] = plan.transmitted
        age_trace[t] = aou.age

        # ---- learning plane: train the transmitting devices. -------------
        tx_ids = np.where(plan.transmitted)[0]
        slot_ids = np.zeros(k_slots, dtype=np.int64)
        slot_w = np.zeros(k_slots, dtype=np.float32)
        slot_ids[: len(tx_ids)] = tx_ids
        slot_w[: len(tx_ids)] = beta[tx_ids]

        if len(tx_ids) > 0:
            key, k_round = jax.random.split(key)
            keys = jax.random.split(k_round, k_slots)
            client_params = trainer(
                params, prep.x_all[slot_ids], prep.y_all[slot_ids],
                prep.m_all[slot_ids], keys
            )
            params = aggregate(params, client_params, jnp.asarray(slot_w))

        # ---- bookkeeping ---------------------------------------------------
        if t in eval_at:
            hist["round"].append(t)
            hist["loss"].append(float(eval_loss(params, x_full, y_full)))
            hist["acc"].append(float(eval_acc(params, x_full, y_full)))
            hist["nsel"].append(int(plan.selected.sum()))
            hist["ntx"].append(int(plan.transmitted.sum()))
            hist["deficit"].append(participation_deficit(beta, plan.transmitted))
            hist["gnorm"].append(float(grad_norm_sq(params)) if cfg.track_gradnorm else 0.0)

    ev = np.asarray(hist["round"])
    return SimHistory(
        label=cfg.policy.label,
        rounds=ev,
        global_loss=np.asarray(hist["loss"]),
        accuracy=np.asarray(hist["acc"]),
        latency_s=lat_all[ev],
        cum_time_s=np.cumsum(lat_all)[ev],
        n_selected=np.asarray(hist["nsel"]),
        n_transmitted=np.asarray(hist["ntx"]),
        energy_j=energy_all[ev],
        deficits=np.asarray(hist["deficit"]),
        grad_sq_norms=np.asarray(hist["gnorm"]),
        beta=beta,
        wall_s=time.perf_counter() - t_start + plan_wall_s,
        plan_wall_s=plan_wall_s,
        latency_all=lat_all,
        energy_all=energy_all,
        tx_trace=tx_trace,
        age_trace=age_trace,
    )


# ---------------------------------------------------------------------------
# engine="scan": the device-resident round loop (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _scan_inputs(prep: _Prepared, ra: RAResult, bmax: int,
                 policy_idx: int = 0) -> dict:
    """Per-cell device arrays consumed by the scanned round loop.

    Leader-plane operands are cast to float32 (the learning plane's dtype);
    equality of the two engines' decisions survives the cast because every
    comparison is between continuous channel draws (documented in
    DESIGN.md §8).  `bmax` pads client data to the group-wide max so cells
    stack for vmap; `policy_idx` selects this cell's leader branch in the
    runner's `lax.switch` (0 for single-policy groups).
    """
    cfg = prep.cfg
    if bmax == prep.x_all.shape[1]:        # single-sim / homogeneous group
        x_all, y_all, m_all = prep.x_all, prep.y_all, prep.m_all
    else:
        x_all, y_all, m_all = _pad_partition(prep.ds, prep.part, bmax)
    key = jax.random.PRNGKey(cfg.seed)
    key, k_init = jax.random.split(key)
    model = get_small_model(cfg.dataset)
    return dict(
        params0=model.init(k_init),
        policy_idx=jnp.int32(policy_idx),
        key0=key,
        beta=jnp.asarray(prep.beta, jnp.float32),
        x_all=x_all, y_all=y_all, m_all=m_all,
        x_full=jnp.asarray(prep.ds.x), y_full=jnp.asarray(prep.ds.y),
        clusters=jnp.asarray(prep.clusters, jnp.int32),
        fixed_ids=jnp.asarray(prep.fixed_ids, jnp.int32),
        gamma=jnp.asarray(ra.time_s, jnp.float32),
        feas=jnp.asarray(ra.feasible),
        energy=jnp.asarray(np.where(np.isfinite(ra.energy_j),
                                    ra.energy_j, 0.0), jnp.float32),
        sel_perms=jnp.asarray(prep.sel_perms, jnp.int32),
        assign_perms=jnp.asarray(prep.assign_perms, jnp.int32),
    )


def _build_scan_runner(cfg: SimConfig, model: SmallModel, trainer,
                       policies: Sequence[tuple[str, str]] | None = None):
    """One fused `lax.scan` over rounds: leader plane + learning plane.

    carry = (params, key, age); xs = per-round Γ slices + injected
    permutations.  Returns the raw traceable fn(data) -> ys so the caller
    can `jit` it directly or `jit(vmap(...))` it across stacked cells.

    `policies` lists the distinct (ds, sa) leader variants of the group; a
    multi-policy group dispatches on `data["policy_idx"]` through
    `lax.switch`, so one compiled program covers a whole policy x seed grid
    (under `vmap` the switch lowers to a select — every branch runs on the
    batch, which is cheap next to the training plane and buys one XLA
    compilation instead of one per policy; DESIGN.md §10).
    """
    k, n = cfg.n_subchannels, cfg.n_devices
    rounds, eval_every = cfg.rounds, cfg.eval_every
    n_clusters = int(math.ceil(n / k))
    ndev = jnp.arange(n)
    kslot = jnp.arange(k)
    f0 = jnp.float32(0.0)
    if policies is None:
        policies = [(cfg.policy.ds, cfg.policy.sa)]

    def run(data):
        branches = make_leader_branches(policies, data, k=k, n=n,
                                        n_clusters=n_clusters)
        ev = make_eval_fn(model, data, cfg.track_gradnorm)

        def body(carry, x):
            params, key, age = carry

            # ---- leader plane (Algorithms 2-3 + AoU), pure jnp ------------
            lead = run_leader(branches, data["policy_idx"], age,
                              x["feas"], x)
            tx = lead["transmitted"]
            ch_g = jnp.where(tx, lead["channel_of"], 0)
            t_dev = x["gamma"][ch_g, ndev]
            latency = jnp.where(
                tx.any(), jnp.max(jnp.where(tx, t_dev, -jnp.inf)), f0)
            energy = jnp.sum(jnp.where(tx, x["energy"][ch_g, ndev], f0))

            # ---- learning plane: train the transmitting devices -----------
            tx_ids = jnp.nonzero(tx, size=k, fill_value=0)[0]
            cnt = tx.sum()
            slot_w = jnp.where(kslot < cnt, data["beta"][tx_ids], f0)

            def do_train(ops):
                p, kk = ops
                cp, kk = train_clients(trainer, data, k, p, kk, tx_ids)
                return aggregate(p, cp, slot_w), kk

            params, key = jax.lax.cond(
                cnt > 0, do_train, lambda ops: ops, (params, key))

            # ---- bookkeeping: evaluate only at eval rounds ----------------
            loss, acc, gnorm = jax.lax.cond(
                x["eval_mask"], ev, lambda p: (f0, f0, f0), params)

            ys = dict(loss=loss, acc=acc, gnorm=gnorm, latency=latency,
                      energy=energy, selected=lead["selected"],
                      transmitted=tx, age=lead["age_next"])
            return (params, key, lead["age_next"]), ys

        # One source of truth for eval rounds: the same helper the history
        # builders index with (an unbatched xs leaf, so the eval cond stays
        # a real branch under vmap).
        eval_mask = np.zeros(rounds, bool)
        eval_mask[_eval_rounds(rounds, eval_every)] = True
        carry0 = (data["params0"], data["key0"], jnp.ones(n, jnp.int32))
        _, ys = jax.lax.scan(body, carry0, make_xs(data, rounds, eval_mask))
        return ys

    return run


def _history_from_scan(cfg: SimConfig, beta: np.ndarray, ys: dict,
                       wall_s: float, plan_wall_s: float) -> SimHistory:
    lat_all = np.asarray(ys["latency"], np.float64)
    energy_all = np.asarray(ys["energy"], np.float64)
    tx = np.asarray(ys["transmitted"])
    sel = np.asarray(ys["selected"])
    age = np.asarray(ys["age"], np.int64)
    ev = np.asarray(_eval_rounds(cfg.rounds, cfg.eval_every))
    return SimHistory(
        label=cfg.policy.label,
        rounds=ev,
        global_loss=np.asarray(ys["loss"], np.float64)[ev],
        accuracy=np.asarray(ys["acc"], np.float64)[ev],
        latency_s=lat_all[ev],
        cum_time_s=np.cumsum(lat_all)[ev],
        n_selected=sel[ev].sum(axis=1),
        n_transmitted=tx[ev].sum(axis=1),
        energy_j=energy_all[ev],
        deficits=np.asarray([participation_deficit(beta, tx[t]) for t in ev]),
        grad_sq_norms=np.asarray(ys["gnorm"], np.float64)[ev],
        beta=beta,
        wall_s=wall_s,
        plan_wall_s=plan_wall_s,
        latency_all=lat_all,
        energy_all=energy_all,
        tx_trace=tx,
        age_trace=age,
    )


def _scan_group_key(cfg: SimConfig) -> SimConfig:
    """Configs identical up to seed/wireless-data/policy/scenario fields
    share one compiled scan program: policy.ra only selects which
    precomputed Γ is fed in, policy.ds/sa select a `lax.switch` leader
    branch inside the shared program (DESIGN.md §10), and a scenario only
    changes the DATA flowing through the fixed-shape traces (channel
    horizon, Prop-1 mask, budgets), never the program — so a policy x
    scenario x seed grid is ONE compiled dispatch (DESIGN.md §11).  The
    aggregation spec normalizes away too: the async engine's buffer size
    and staleness exponent are traced operands (DESIGN.md §12), so an
    aggregation axis varies data, not programs — run_many partitions
    sync-mode from async-mode cells BEFORE grouping (different carries)."""
    return dataclasses.replace(
        cfg, seed=0, radius_m=0.0, pt_dbm=0.0, e_max_j=None,
        policy=RoundPolicy(), scenario="static", aggregation="sync")


def _prep_key(cfg: SimConfig) -> SimConfig:
    """Configs identical up to the policy sample the same `_Prepared` world:
    dataset, partition, scenario traces (topology, channel horizon, churn,
    budgets), and injected permutations are all drawn from `seed` before
    the policy is ever consulted.  The scenario stays in the key — it IS
    part of the world.  The aggregation discipline does not: sync and
    async variants of one world share its samples and its Γ solve, which
    is exactly what makes the sync-vs-async comparison differential."""
    return dataclasses.replace(cfg, policy=RoundPolicy(), aggregation="sync")


@functools.lru_cache(maxsize=16)
def _model_and_trainer(make_trainer, get_model, make_opt, dataset: str,
                       optimizer: str, lr: float, batch: int,
                       local_steps: int):
    """The model and trainer of one set of training settings, kept for
    the process so that the trainer's jitted client step keeps its trace
    cache from call to call.  The arguments are everything the trainer
    closes over, the factories included: one patched in their place
    builds its own."""
    model = get_model(dataset)
    trainer = make_trainer(
        model.loss, make_opt(optimizer, lr), batch_size=batch,
        local_steps=local_steps, loss_per_example=model.loss_per_example,
        jit=False)
    return model, trainer


def _group_trainer_and_policies(cfgs: Sequence[SimConfig]):
    """Shared scan/async group setup: model, un-jitted trainer (the group
    program jits around it), and the group's distinct (ds, sa) leader
    variants in first-appearance order with each cell's branch index."""
    cfg = cfgs[0]
    t1 = TABLE1[cfg.dataset]
    model, trainer = _model_and_trainer(
        make_local_trainer, get_small_model, make_optimizer, cfg.dataset,
        cfg.optimizer or t1["optimizer"], cfg.lr or t1["lr"],
        cfg.batch or t1["batch"], cfg.local_steps)
    policies: list[tuple[str, str]] = []
    pol_idx = []
    for c in cfgs:
        key = (c.policy.ds, c.policy.sa)
        if key not in policies:
            policies.append(key)
        pol_idx.append(policies.index(key))
    return model, trainer, policies, pol_idx


def _check_f32_priorities(preps: Sequence[_Prepared]) -> None:
    # The device-resident leaders rank float32 age*beta products
    # (core.leader_jax.priority_order); they are integer-exact — and hence
    # tie/order identical to the host's f64 ranking — only below 2^24.
    # Ages are bounded by rounds + 1.
    for p in preps:
        worst = (p.cfg.rounds + 1) * float(p.beta.max())
        if worst >= 2 ** 24:
            raise ValueError(
                f"scan engine: age*beta products may reach {worst:.3g} >= "
                f"2^24, where float32 priorities lose host equivalence — "
                f"use engine='loop' or shrink rounds/data sizes")


def _dispatch_group(run, datas: list[dict], shard: bool):
    """Dispatch one static-shape group: solo jit, jit(vmap), or — with
    more than one visible local device — `shard_map` over a 1-D batch
    mesh (padded to a device-count multiple by repeating cell 0; pad rows
    are dropped by the caller).  Returns the blocked-on ys.

    Recorded, the `engine.dispatch` span's `compile_s` splits the jitted
    call's trace, lowering and compile-or-load by JAX's own events, and
    `engine.run` is the wait for the results."""
    n_dev = jax.local_device_count()
    with tracing.span("engine.dispatch", cells=len(datas)):
        if len(datas) == 1:
            ys = jax.jit(run)(datas[0])
        elif shard and n_dev > 1:
            from jax.sharding import Mesh, PartitionSpec

            pad = (-len(datas)) % n_dev
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *(list(datas) + [datas[0]] * pad))
            mesh = Mesh(np.asarray(jax.local_devices()), ("batch",))
            sharded = jax.shard_map(jax.vmap(run), mesh=mesh,
                                    in_specs=PartitionSpec("batch"),
                                    out_specs=PartitionSpec("batch"),
                                    check_vma=False)
            ys = jax.jit(sharded)(stacked)
        else:
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *datas)
            ys = jax.jit(jax.vmap(run))(stacked)
        with tracing.span("engine.run"):
            jax.block_until_ready(ys)
    return ys


def _run_group_scan(cfgs: Sequence[SimConfig], preps: Sequence[_Prepared],
                    ras: Sequence[RAResult], plan_walls: Sequence[float],
                    shard: bool = False) -> list[SimHistory]:
    """Run one static-shape group of simulations through the scan engine.

    Members differing in seed/wireless data/policy stack into one batch:
    a single `jit(vmap(run))` program (distinct ds/sa pairs become
    `lax.switch` branches selected per batch element).  With `shard=True`
    and more than one visible local device, the batch axis is additionally
    sharded across devices via `shard_map` — the batch is padded to a
    device-count multiple by repeating cell 0 and the pad rows are dropped
    from the histories (per-cell programs are independent, so padding
    cannot perturb real cells).
    """
    cfg = cfgs[0]
    model, trainer, policies, pol_idx = _group_trainer_and_policies(cfgs)
    run = _build_scan_runner(cfg, model, trainer, policies)
    _check_f32_priorities(preps)

    t_start = time.perf_counter()
    bmax = max(int(p.part.beta.max()) for p in preps)
    datas = [_scan_inputs(p, ra, bmax, i)
             for p, ra, i in zip(preps, ras, pol_idx)]
    ys = _dispatch_group(run, datas, shard)
    wall_each = (time.perf_counter() - t_start) / len(datas)

    out = []
    for i, (c, p, w) in enumerate(zip(cfgs, preps, plan_walls)):
        ys_i = ys if len(datas) == 1 else jax.tree_util.tree_map(
            lambda leaf: leaf[i], ys)
        out.append(_history_from_scan(c, p.beta, ys_i, wall_each + w, w))
    return out


# ---------------------------------------------------------------------------
# engine="async": the buffered event-timeline loop (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _async_spec(cfg: SimConfig) -> AsyncAggregation:
    """The cell's commit policy.  A "sync" cell forced through the event
    engine runs the degenerate full-buffer barrier, which reproduces the
    scan engine bit-exactly — the differential anchor."""
    spec = get_aggregation(cfg.aggregation)
    if spec is None:
        spec = AsyncAggregation(buffer="full", staleness="const")
    return spec


def _history_from_async(cfg: SimConfig, beta: np.ndarray, ys: dict,
                        wall_s: float, plan_wall_s: float) -> SimHistory:
    hist = _history_from_scan(cfg, beta, ys, wall_s, plan_wall_s)
    hist.commit_trace = np.asarray(ys["committed"])
    hist.async_trace = dict(
        n_pending=np.asarray(ys["n_pending"], np.int64),
        overflow=np.asarray(ys["overflow"]),
        rem_dispatch=np.asarray(ys["rem_dispatch"], np.float64),
    )
    return hist


def _run_group_async(cfgs: Sequence[SimConfig], preps: Sequence[_Prepared],
                     ras: Sequence[RAResult], plan_walls: Sequence[float],
                     shard: bool = False) -> list[SimHistory]:
    """Run one static-shape group through the buffered event-timeline
    engine (`fl.async_loop`).  Grouping/batching/sharding mirror the scan
    engine exactly; each cell's commit batch size and staleness exponent
    enter as traced operands, so a whole aggregation axis shares one
    compiled event program per shape.
    """
    cfg = cfgs[0]
    model, trainer, policies, pol_idx = _group_trainer_and_policies(cfgs)
    eval_mask = np.zeros(cfg.rounds, bool)
    eval_mask[_eval_rounds(cfg.rounds, cfg.eval_every)] = True
    run = build_async_runner(
        model, trainer, policies, k=cfg.n_subchannels, n=cfg.n_devices,
        rounds=cfg.rounds, eval_mask=eval_mask,
        track_gradnorm=cfg.track_gradnorm)
    _check_f32_priorities(preps)

    t_start = time.perf_counter()
    bmax = max(int(p.part.beta.max()) for p in preps)
    datas = []
    for c, p, ra, i in zip(cfgs, preps, ras, pol_idx):
        d = _scan_inputs(p, ra, bmax, i)
        spec = _async_spec(c)
        d["buffer"] = jnp.int32(
            spec.resolve_buffer(cfg.n_devices, cfg.n_subchannels))
        d["stale_exp"] = jnp.float32(spec.stale_exponent())
        d["server_lr"] = jnp.float32(spec.server_lr)
        datas.append(d)
    ys = _dispatch_group(run, datas, shard)
    wall_each = (time.perf_counter() - t_start) / len(datas)

    out = []
    for i, (c, p, w) in enumerate(zip(cfgs, preps, plan_walls)):
        ys_i = ys if len(datas) == 1 else jax.tree_util.tree_map(
            lambda leaf: leaf[i], ys)
        out.append(_history_from_async(c, p.beta, ys_i, wall_each + w, w))
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def run_many(cfgs: Sequence[SimConfig], *,
             ra_backend: str | None = None,
             ra_solver: str = "fused",
             engine: str = "loop",
             shard: bool | None = None) -> list[SimHistory]:
    """Run several simulations, sharing ONE batched whole-horizon Γ solve.

    The control-plane cost of a sweep (multiple seeds / radii / budgets /
    policies, Figs. 3-9) collapses into a single device batch; each
    simulation then replays its precomputed per-round slices — through
    `plan_round` on the host (engine="loop"), or through the fused
    `lax.scan` round loop (engine="scan"), where configs differing only in
    seed / wireless data / policy.ds / policy.sa are additionally batched
    into one compiled program (DESIGN.md §8, §10).

    Configs identical up to the policy also share one `_Prepared` world
    (dataset, topology, channel horizon, injected permutations — all drawn
    before the policy is consulted) and one Γ solve per RA scheme, so a
    policy grid over S seeds samples and solves S worlds, not S x P.

    Args:
      cfgs: the simulations to run; results are returned in the same order.
      ra_backend: projection backend for the Γ solver (None = default;
        see `kernels.polyblock_project.ops`).
      ra_solver: "fused" (default — staged whole-loop Γ driver with
        device-axis row sharding when `shard` allows) or "step" (the
        per-iteration phase-split driver); see `core.monotonic_jax`.
      engine: "loop" (host round loop), "scan" (device-resident), or
        "async" (buffered event-timeline loop, DESIGN.md §12).  Cells
        whose `SimConfig.aggregation` names an async commit policy route
        through the async engine REGARDLESS of this argument (the sync
        engines cannot express buffered commits); engine="async" forces
        every cell through the event engine, where "sync"-aggregation
        cells run the degenerate full-buffer barrier and reproduce the
        scan engine bit-exactly.
      shard: shard the scan/async engines' batch axis — and the fused Γ
        solve's row axis — across local devices via `shard_map`.  None
        (default) auto-enables sharding when more than one local device
        is visible; False forces single-device `vmap`; True asks for
        sharding (a no-op on one device).  Ignored by engine="loop"
        (the Γ solve still shards).
    """
    if engine not in ("loop", "scan", "async"):
        raise ValueError(f"unknown engine: {engine}")
    if ra_solver not in ("fused", "step"):
        raise ValueError(f"unknown ra_solver: {ra_solver}")
    with tracing.span("sim.run_many", cells=len(cfgs)):
        if shard is None:
            shard = jax.local_device_count() > 1
        # Per-cell execution mode: an async aggregation spec overrides the
        # requested sync engine (and validates eagerly, before any sampling).
        modes = ["async" if engine == "async" or get_aggregation(c.aggregation)
                 is not None else engine for c in cfgs]

        # One _Prepared world per policy-free config: policy-only variants
        # share data/topology/channels by construction (and hence Γ, below).
        # Scenario-only variants are distinct worlds but still share the
        # dataset phase (dataset/partition/padded buffers) via `data_cache` —
        # the rng prefix up to the partition draw is scenario-independent.
        preps_by_key: dict[SimConfig, _Prepared] = {}
        data_cache: dict = {}
        preps: list[_Prepared] = []
        for c in cfgs:
            key = _prep_key(c)
            if key not in preps_by_key:
                preps_by_key[key] = _prepare(c, data_cache)
            shared = preps_by_key[key]
            preps.append(shared if shared.cfg == c
                         else dataclasses.replace(shared, cfg=c))

        ras, plan_walls = _solve_horizons(preps, ra_backend,
                                          solver=ra_solver, shard=shard)
        # Scenario dynamics (DESIGN.md §11): churn availability knocks out
        # Prop-1 feasibility, straggler slowdowns stretch the eq.-1 compute
        # share of Γ — folded into the whole-horizon RAResult ONCE, before
        # either engine runs, so loop and scan consume identical tensors.
        # Γ-deduped sims alias one RAResult and one world, so the transform is
        # applied per unique object and re-aliased.
        transformed: dict[int, RAResult] = {}
        for i, (p, ra) in enumerate(zip(preps, ras)):
            if id(ra) not in transformed:
                transformed[id(ra)] = apply_dynamics(
                    ra, p.avail, p.slowdown, p.beta, p.wcfg)
            ras[i] = transformed[id(ra)]
        out: list[SimHistory | None] = [None] * len(cfgs)
        for i, mode in enumerate(modes):
            if mode == "loop":
                out[i] = _run_prepared(preps[i], ras[i], plan_walls[i])

        # Sync-mode and async-mode cells never share a program (different scan
        # carries), so group within each mode; inside a mode the aggregation
        # spec is data (buffer / exponent operands), not program shape.
        groups: dict[tuple[str, SimConfig], list[int]] = {}
        for i, (c, mode) in enumerate(zip(cfgs, modes)):
            if mode != "loop":
                groups.setdefault((mode, _scan_group_key(c)), []).append(i)
        for (mode, _), idx in groups.items():
            run_group = _run_group_scan if mode == "scan" else _run_group_async
            hists = run_group([cfgs[i] for i in idx],
                              [preps[i] for i in idx],
                              [ras[i] for i in idx],
                              [plan_walls[i] for i in idx],
                              shard=shard)
            for i, h in zip(idx, hists):
                out[i] = h
        return out


def run_simulation(cfg: SimConfig, *, ra_backend: str | None = None,
                   ra_solver: str = "fused",
                   engine: str = "loop") -> SimHistory:
    """Run ONE simulation (the trajectory behind one curve of Figs. 3-9).

    Equivalent to ``run_many([cfg])[0]``: the whole channel horizon is
    pre-sampled and Γ solved in one batched Algorithm-1 call, then the
    round loop runs on the chosen engine ("loop" = host, "scan" =
    device-resident `lax.scan`, "async" = buffered event timeline; all
    consume identical randomness and pre-solved traces — DESIGN.md §8,
    §12).
    """
    return run_many([cfg], ra_backend=ra_backend, ra_solver=ra_solver,
                    engine=engine)[0]
