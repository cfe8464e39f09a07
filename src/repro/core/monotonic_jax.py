"""Device-resident Algorithm 1: jitted, batched polyblock outer approximation.

Port of `core.monotonic.solve_pairs` to JAX (DESIGN.md §6).  The host
implementation re-enters Python for every polyblock iteration of every
planning round; this one solves an arbitrary batch — including the
whole-horizon (rounds x K x N) Γ tensor, which `stackelberg.plan_round`
notes is selection-independent — as a sequence of jitted steps over
fixed-shape device arrays:

  verts/vproj : (rows, m, 2)  vertex set + boundary projections per pair
  vfval       : (rows, m)     f of eq. (21) at each projection
  valid/active: bool masks replacing the host path's ragged retirement

Structural optimizations over a naive port (all result-preserving — the
iteration trajectory replays the host algorithm's structure exactly, so
`iterations` matches the reference pair-for-pair):

  * feasibility pre-filter — Proposition-1 infeasible pairs (the majority at
    realistic radii) never enter the vertex store at all;
  * phase-split steps with active-set compaction — pairs retire after very
    few iterations (the empirical distribution is p50 ~ 2, max ~ 24 at
    Table-I settings), so the driver runs the cheap selection half-step,
    syncs the active mask, compacts surviving pairs into a smaller bucket,
    and only then pays for the expensive child projections.  Bucket sizes
    come from the {1, 1.25, 1.5, 1.75} x 2^k ladder so padding slack stays
    under 25% while jit caches stay warm across calls;
  * lazy vertex store — the store starts at 8 columns and doubles toward
    max_iter + 3 only for the rare stragglers, by which point compaction
    has shrunk the row count, so eq. (24)'s per-pair vertex replacement is
    a fully vectorized masked select over a narrow store (XLA CPU would
    execute a row scatter as a serial loop).

The projection (eqs. 27-29) dispatches through `kernels.polyblock_project`:
fp32-bulk/fp64-polish Newton ("mixed", the default on every platform),
warm-started safeguarded log-space Newton ("newton" — same root as the
reference 60-step bisection to ~1e-9 relative with 4x fewer transcendental
evaluations on the CPU), exact mirrored bisection ("bisect"), or the
float32 Pallas kernel ("pallas", opt-in).  Everything else runs float64 under a scoped
`jax.enable_x64(True)`, so results match the NumPy path to ~1e-7 relative
(1e-6 contract, tests/test_monotonic_jax.py) without enabling x64
globally for the learning plane.

At the acceptance scale (100 rounds x K=4 x N=512 on a 2-core CPU
container) the whole-horizon solve is ~11x faster than the per-round host
loop; benchmarks/control_plane.py records the trajectory in
BENCH_control_plane.json.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# The masked-select store rewrite intentionally produces fresh buffers for
# the four (rows, m, ...) store arrays, so XLA cannot reuse their donated
# inputs and warns once per compiled bucket shape. Expected; silence it so
# every simulation run doesn't print compiler noise.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

from .. import tracing
from ..kernels.polyblock_project.ops import (polyblock_project,
                                             project_newton_mixed)
from .feasibility import is_infeasible
from .monotonic import RAResult
from .wireless import WirelessConfig, total_energy, total_time

__all__ = ["solve_pairs_jit", "solve_pairs_fused", "precompute_gamma"]

# State tuple layout for one bucket of pairs (rows = bucket size, m = the
# current lazy vertex-slot capacity).
_BETA, _H2, _EMAX, _VERTS, _VPROJ, _VFVAL, _VALID, _ACTIVE = range(8)
_PREV, _BESTF, _BESTP, _ITERS, _NVALID, _IDX = range(8, 14)


def _bucket(n: int, lo: int = 128) -> int:
    """Smallest size in the {1, 1.25, 1.5, 1.75} x 2^k ladder that fits n:
    bounded padding slack (<= 25%), bounded number of distinct shapes for
    the jit cache."""
    b = lo
    while True:
        for quarters in (4, 5, 6, 7):
            s = (b * quarters) >> 2
            if n <= s:
                return s
        b <<= 1


def _project(v, beta, h2, e_max, cfg, backend, n_bisect):
    return polyblock_project(v, beta, h2, e_max, cfg,
                             n_bisect=n_bisect, backend=backend)


@partial(jax.jit, static_argnames=("cfg", "m", "backend", "n_bisect"))
def _init_state(beta, h2, e_max, n_real, *, cfg, m, backend, n_bisect):
    b = beta.shape[0]
    active = jnp.arange(b) < n_real
    v0 = jnp.ones((b, 2), h2.dtype)
    if backend == "mixed":
        # Cold start (no parent hint yet), but the regime-split warm start
        # in project_newton_mixed already lands near-exact on the rows that
        # used to need 6 contraction steps.
        pj0 = project_newton_mixed(v0, beta, h2, e_max, cfg, n_f32=4)
    else:
        pj0 = _project(v0, beta, h2, e_max, cfg, backend, n_bisect)
    f0 = -total_time(pj0[:, 0], pj0[:, 1], beta, h2, cfg)
    verts = jnp.zeros((b, m, 2), h2.dtype).at[:, 0].set(v0)
    vproj = jnp.zeros((b, m, 2), h2.dtype).at[:, 0].set(pj0)
    vfval = jnp.full((b, m), -jnp.inf, h2.dtype).at[:, 0].set(f0)
    valid = jnp.zeros((b, m), bool).at[:, 0].set(True)
    return (beta, h2, e_max, verts, vproj, vfval, valid, active,
            jnp.full(b, jnp.inf, h2.dtype), f0, pj0,
            jnp.zeros(b, jnp.int32), jnp.ones(b, jnp.int32),
            jnp.zeros(b, jnp.int32))


def _select_impl(state, eps):
    """Polyblock selection half-step (paper steps 9-10): pick each pair's
    best vertex, update the incumbent, retire pairs that meet eq. (26).
    Split from the projection half so the driver can compact the active set
    *before* paying for child projections."""
    (beta, h2, e_max, verts, vproj, vfval, valid, active,
     prev_best, best_f, best_proj, iters, nvalid, _) = state

    fv = jnp.where(valid, vfval, -jnp.inf)
    idx = jnp.argmax(fv, axis=1).astype(jnp.int32)      # paper step 9
    fbest = jnp.take_along_axis(fv, idx[:, None].astype(jnp.int64), 1)[:, 0]

    improved = fbest > best_f
    sel_proj = jnp.take_along_axis(
        vproj, idx[:, None, None].astype(jnp.int64), 1)[:, 0]
    best_f = jnp.where(improved, fbest, best_f)
    best_proj = jnp.where(improved[:, None], sel_proj, best_proj)

    done = jnp.abs(fbest - prev_best) <= eps            # eq. (26)
    prev_best = fbest
    active = active & ~done
    iters = iters + active.astype(jnp.int32)

    return (beta, h2, e_max, verts, vproj, vfval, valid, active,
            prev_best, best_f, best_proj, iters, nvalid, idx)


def _children_impl(state, cfg, backend, n_bisect):
    """Polyblock refinement half-step (paper steps 11-13): split the chosen
    vertex into its two children (eq. 23), project both in one batch, and
    write them into the store (eq. 24)."""
    (beta, h2, e_max, verts, vproj, vfval, valid, active,
     prev_best, best_f, best_proj, iters, nvalid, idx) = state
    b, m = vfval.shape

    v = jnp.take_along_axis(verts, idx[:, None, None].astype(jnp.int64), 1)[:, 0]
    phi = jnp.take_along_axis(vproj, idx[:, None, None].astype(jnp.int64), 1)[:, 0]
    # Children (eq. 23): v - (v_i - phi_i) e_i, both projected in one batch.
    child1 = jnp.stack([phi[:, 0], v[:, 1]], axis=-1)
    child2 = jnp.stack([v[:, 0], phi[:, 1]], axis=-1)
    ch = jnp.concatenate([child1, child2], axis=0)
    beta2 = jnp.concatenate([beta, beta])
    h2x2 = jnp.concatenate([h2, h2])
    if backend == "mixed":
        # The parent's projection ratio zeta = phi/v is a lower bound on
        # both children's roots (energy is increasing in tau and p), so it
        # warm-starts the fp32 bulk — which then needs only 2 contraction
        # steps plus a single fp64 Halley polish, vs the cold call's 4+2
        # (see project_newton_mixed; only _init_state's projection of
        # (1, 1) runs cold).
        zeta = phi[:, 0] / jnp.maximum(v[:, 0], 1e-300)
        pj = project_newton_mixed(
            ch, beta2, h2x2, jnp.concatenate([e_max, e_max]), cfg,
            n_f32=2, n_f64=1, x0_hint=jnp.concatenate([zeta, zeta]))
    else:
        pj = _project(ch, beta2, h2x2, jnp.concatenate([e_max, e_max]),
                      cfg, backend, n_bisect)
    fj = -total_time(pj[:, 0], pj[:, 1], beta2, h2x2, cfg)
    pj1, pj2 = pj[:b], pj[b:]
    f1, f2 = fj[:b], fj[b:]

    # Eq. (24): child1 replaces the split vertex, child2 takes the next free
    # slot, retired rows keep their store.  Written as two masked one-hot
    # selects rather than a row scatter: XLA CPU executes scatters as a
    # serial per-row loop, while the selects fuse into one vectorized pass
    # over the store — and the store is narrow (lazy m), so the pass is
    # cheap.  The two masks are disjoint (slot idx is already valid;
    # slot nvalid is the first free one).
    cols = jnp.arange(m)
    mask1 = (cols[None, :] == idx[:, None]) & active[:, None]
    mask2 = (cols[None, :] == nvalid[:, None]) & active[:, None]
    verts = jnp.where(mask1[..., None], child1[:, None, :],
                      jnp.where(mask2[..., None], child2[:, None, :], verts))
    vproj = jnp.where(mask1[..., None], pj1[:, None, :],
                      jnp.where(mask2[..., None], pj2[:, None, :], vproj))
    vfval = jnp.where(mask1, f1[:, None],
                      jnp.where(mask2, f2[:, None], vfval))
    valid = valid | mask2
    nvalid = nvalid + active.astype(jnp.int32)

    return (beta, h2, e_max, verts, vproj, vfval, valid, active,
            prev_best, best_f, best_proj, iters, nvalid, idx)


@partial(jax.jit, static_argnames=("eps",), donate_argnums=(0,))
def _step_select(state, *, eps):
    return _select_impl(state, eps)


@partial(jax.jit, static_argnames=("cfg", "backend", "n_bisect"),
         donate_argnums=(0,))
def _step_children(state, *, cfg, backend, n_bisect):
    return _children_impl(state, cfg, backend, n_bisect)


@jax.jit
def _gather(state, idx, n_real):
    """Compact a bucket: keep rows `idx` (padded), mark padding inactive."""
    out = tuple(a[idx] for a in state)
    active = out[_ACTIVE] & (jnp.arange(idx.shape[0]) < n_real)
    return out[:_ACTIVE] + (active,) + out[_ACTIVE + 1:]


@partial(jax.jit, static_argnames=("new_m",), donate_argnums=(0,))
def _grow(state, *, new_m):
    """Append vertex-store columns (lazy capacity: the store starts at 8
    columns because pairs empirically retire after a handful of iterations,
    and grows toward max_iter + 3 only for the rare stragglers — by which
    point compaction has shrunk the row count, so the wide store is never
    paid for at full batch).  New columns carry valid=False / fval=-inf, so
    they are inert until a child is written into them."""
    (beta, h2, e_max, verts, vproj, vfval, valid, active,
     prev_best, best_f, best_proj, iters, nvalid, idx) = state
    b, m = vfval.shape
    pad = new_m - m
    verts = jnp.concatenate([verts, jnp.zeros((b, pad, 2), verts.dtype)], 1)
    vproj = jnp.concatenate([vproj, jnp.zeros((b, pad, 2), vproj.dtype)], 1)
    vfval = jnp.concatenate([vfval, jnp.full((b, pad), -jnp.inf, vfval.dtype)], 1)
    valid = jnp.concatenate([valid, jnp.zeros((b, pad), bool)], 1)
    return (beta, h2, e_max, verts, vproj, vfval, valid, active,
            prev_best, best_f, best_proj, iters, nvalid, idx)


def _fused_stage_impl(state, cfg, backend, n_bisect, eps, t_start, t_end):
    """One fused stage of the polyblock loop: iterations t_start..t_end-1 as
    a single `lax.while_loop`, with no host sync inside.  The body replays
    the step driver's trajectory exactly — selection half-step, then the
    child projections only while any row is still active — so per-row
    results (and `iterations`) are bit-equal to the phase-split path; only
    the *synchronization schedule* differs (the step driver syncs the active
    mask every iteration, this stage never does)."""

    def cond(carry):
        t, st = carry
        return (t < t_end) & st[_ACTIVE].any()

    def body(carry):
        t, st = carry
        st = _select_impl(st, eps)
        # No guard on the children half-step: every write in _children_impl
        # is masked by `active`, so running it after a select that retired
        # the last row is a bit-exact no-op — cheaper than a lax.cond per
        # iteration, and the trajectory still replays the step driver
        # (which never runs children after its final select) exactly.
        st = _children_impl(st, cfg, backend, n_bisect)
        return t + 1, st

    _, state = jax.lax.while_loop(cond, body, (jnp.int32(t_start), state))
    return state


@partial(jax.jit,
         static_argnames=("cfg", "backend", "n_bisect", "eps",
                          "t_start", "t_end"),
         donate_argnums=(0,))
def _fused_stage(state, *, cfg, backend, n_bisect, eps, t_start, t_end):
    return _fused_stage_impl(state, cfg, backend, n_bisect, eps,
                             t_start, t_end)


@partial(jax.jit,
         static_argnames=("cfg", "backend", "n_bisect", "eps",
                          "t_start", "t_end"),
         donate_argnums=(0,))
def _fused_stage_sharded(state, *, cfg, backend, n_bisect, eps,
                         t_start, t_end):
    """Device-axis sharded stage: every state leaf has leading dim rows, so
    row sharding is collective-free (each pair's polyblock loop is
    independent).  Same pad-and-drop pattern as `fl.sim._dispatch_group`;
    per-shard early exit is safe because retired rows are frozen (the
    selection half-step is a no-op on a fully-retired shard), so results
    stay bit-identical to the unsharded path."""
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(jax.local_devices()), ("rows",))
    spec = PartitionSpec("rows")
    fn = jax.shard_map(
        lambda st: _fused_stage_impl(st, cfg, backend, n_bisect, eps,
                                     t_start, t_end),
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False)
    return fn(state)


def _roundup(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def solve_pairs_fused(
    beta,
    h2,
    cfg: WirelessConfig,
    e_max=None,
    *,
    eps: float | None = None,
    max_iter: int = 64,
    backend: str | None = None,
    n_bisect: int = 60,
    shard: bool | None = None,
) -> RAResult:
    """Fused-stage Algorithm 1: the whole polyblock loop as (at most) three
    jitted `while_loop` stages instead of ~2 dispatches + 1 host sync per
    iteration.

    Drop-in for `solve_pairs_jit` (same arguments and RAResult contract).
    Two overheads of the step driver are removed at once:

      * host syncs — the iteration tail runs as jitted `while_loop` stages
        with no host round-trip inside.  The sync *schedule* follows the
        empirical retirement curve at Table-I physics (the active set
        collapses ~4096 -> 2980 -> 1208 -> 346 over iterations 2-4): the
        driver still syncs-and-compacts after each of the wide iterations
        2, 3, 4 — where compaction pays for the sync many times over — and
        then fuses the long narrow tail in one stage per store width
        (8 -> 24 -> max_iter + 3 slots; an m-slot store covers through
        iteration m - 3, since step t writes slot <= t + 1).  ~19 syncs
        become <= 6, and none happen where the batch is already narrow;

      * transcendental volume — with backend "mixed" (the default on
        every platform), the child projections run the fp32-bulk/
        fp64-polish Newton (`kernels.polyblock_project.
        project_newton_mixed`): same safeguarded loop, ~2x the SIMD width
        for the bracket contraction, fp64 polish pinned to the f64 Newton
        root at ~1e-12 relative (the fp32-accumulation study, DESIGN.md
        §13).

    backend: as in `solve_pairs_jit`, plus "mixed", and "pallas" here means
    the *fully fused* single-kernel solve (`kernels.polyblock_fused`) —
    vertex store, selection, and the 60-step bisection projection in one
    VMEM-resident pass per (pair-tile, 128-lane) block — rather than a
    Pallas projection inside the jnp loop.  With backend "newton"/"bisect"
    the trajectory replays `solve_pairs_jit` bit-for-bit (including
    `iterations`); with "mixed" the roots agree to ~1e-12, which is
    indistinguishable at the eq. (26) retirement tolerance on the
    differential grid (<= 1e-6 contract, tests/test_fused_solver.py).

    shard: None (auto: shard the row axis over local devices when more than
    one is visible), True, or False.  Sharded and unsharded paths are
    bit-identical (tests/test_sharding_and_launch.py).
    """
    h2 = np.asarray(h2, dtype=np.float64)
    shape = h2.shape
    e_max = cfg.e_max_j if e_max is None else e_max
    eps = 0.01 if eps is None else float(eps)
    if backend is None:
        backend = "mixed"
    if backend == "jnp":
        backend = "bisect"

    beta_f = np.broadcast_to(np.asarray(beta, np.float64), shape).reshape(-1)
    h2f = h2.reshape(-1)
    e_f = np.broadcast_to(np.asarray(e_max, np.float64), shape).reshape(-1)
    n = h2f.shape[0]

    feas = ~is_infeasible(h2f, cfg, e_f)
    tau = np.full(n, np.nan)
    p = np.full(n, np.nan)
    time_s = np.full(n, np.inf)
    energy = np.full(n, np.nan)
    iters_out = np.zeros(n, dtype=np.int64)

    def flush(rows_mask, row_orig, bp, bf, it):
        rows = np.where(rows_mask & (row_orig >= 0))[0]
        if rows.size == 0:
            return
        orig = row_orig[rows]
        tau[orig] = bp[rows, 0]
        p[orig] = bp[rows, 1]
        time_s[orig] = -bf[rows]
        energy[orig] = total_energy(bp[rows, 0], bp[rows, 1],
                                    beta_f[orig], h2f[orig], cfg)
        iters_out[orig] = it[rows]

    work = np.where(feas)[0]
    if work.size and backend == "pallas":
        from ..kernels.polyblock_fused.ops import polyblock_solve_fused

        interpret = jax.default_backend() != "tpu"
        # Compiled, the kernel runs float32 and Mosaic refuses 64-bit
        # scalars, so x64 is on only for the float64 interpret path.
        with jax.enable_x64(interpret):
            k_tau, k_p, k_time, k_it = polyblock_solve_fused(
                beta_f[work], h2f[work], e_f[work], cfg,
                eps=eps, max_iter=max_iter, n_bisect=n_bisect,
                interpret=interpret,
                dtype=np.float64 if interpret else np.float32)
        tau[work] = np.asarray(k_tau, np.float64)
        p[work] = np.asarray(k_p, np.float64)
        time_s[work] = np.asarray(k_time, np.float64)
        energy[work] = total_energy(tau[work], p[work],
                                    beta_f[work], h2f[work], cfg)
        iters_out[work] = np.asarray(k_it, np.int64)
    elif work.size:
        ndev = jax.local_device_count()
        use_shard = (ndev > 1) if shard is None else bool(shard)
        if use_shard and ndev == 1:
            use_shard = False
        m_full = max_iter + 3
        # Iteration t writes child2 into slot t + 1, so an m-slot store
        # covers through t_end = m - 2: starting at 5 slots carries the
        # full-width iterations 0-3 with the narrowest store that fits
        # them, and the grow ladder below widens in small steps (the wide
        # passes are long gone by the time the store is).
        m = min(5, m_full)
        b = _bucket(work.size)
        if use_shard:
            b = _roundup(b, ndev)
        pad = b - work.size
        row_orig = np.concatenate([work, np.full(pad, -1, np.int64)])
        stage = _fused_stage_sharded if use_shard else _fused_stage
        # Stage boundaries: sync after each of the wide iterations 2-6 (the
        # retirement knee spans t=2..5 at Table-I physics; a sync is ~50us
        # while a mistimed full-width stage costs milliseconds, and the
        # gather-if-half rule below decides whether a sync actually pays
        # for a copy), then one fused stage per store width.
        bounds = [tb for tb in (2, 3, 4, 5, 6) if tb < max_iter]
        mm = 24
        while True:
            te = min(mm - 2, max_iter)
            bounds.append(te)
            if te >= max_iter:
                break
            mm = min(3 * mm, m_full)
        bounds = sorted(set(bounds))
        with jax.enable_x64(True):
            state = _init_state(
                jnp.asarray(np.concatenate([beta_f[work], np.ones(pad)])),
                jnp.asarray(np.concatenate([h2f[work], np.ones(pad)])),
                jnp.asarray(np.concatenate([e_f[work], np.full(pad, np.inf)])),
                jnp.int32(work.size),
                cfg=cfg, m=m, backend=backend, n_bisect=n_bisect)
            t = 0
            for t_end in bounds:
                with tracing.span("gamma.stage", rows=b, t_end=t_end):
                    while m - 2 < t_end and m < m_full:  # widen the store
                        new_m = min(max(m + (m >> 1), t_end + 2), m_full)
                        state = _grow(state, new_m=new_m)
                        m = new_m
                    state = stage(state, cfg=cfg, backend=backend,
                                  n_bisect=n_bisect, eps=eps,
                                  t_start=t, t_end=t_end)
                    t = t_end
                    act = np.asarray(state[_ACTIVE])
                    tracing.count("gamma.host_syncs")
                na = int(act.sum())
                if na == 0 or t >= max_iter:
                    break
                nb = _bucket(na)
                if use_shard:
                    nb = _roundup(nb, ndev)
                # Compact only when the bucket at least halves: a gather
                # copies the whole state, so a 25% trim costs more than the
                # width it saves in the next stage.
                if nb <= b // 2:
                    bp, bf, it = (np.asarray(state[_BESTP]),
                                  np.asarray(state[_BESTF]),
                                  np.asarray(state[_ITERS]))
                    tracing.count("gamma.host_syncs", 3)
                    flush(~act, row_orig, bp, bf, it)
                    keep = np.where(act)[0]
                    idx = np.concatenate(
                        [keep, np.zeros(nb - na, np.int64)]).astype(np.int32)
                    state = _gather(state, jnp.asarray(idx), jnp.int32(na))
                    row_orig = np.concatenate(
                        [row_orig[keep], np.full(nb - na, -1, np.int64)])
                    b = nb
            bp, bf, it = (np.asarray(state[_BESTP]),
                          np.asarray(state[_BESTF]),
                          np.asarray(state[_ITERS]))
            tracing.count("gamma.host_syncs", 3)
            flush(np.ones(b, bool), row_orig, bp, bf, it)

    return RAResult(
        tau=tau.reshape(shape),
        p=p.reshape(shape),
        time_s=time_s.reshape(shape),
        energy_j=energy.reshape(shape),
        feasible=feas.reshape(shape),
        iterations=iters_out.reshape(shape),
    )


def solve_pairs_jit(
    beta,
    h2,
    cfg: WirelessConfig,
    e_max=None,
    *,
    eps: float | None = None,
    max_iter: int = 64,
    backend: str | None = None,
    n_bisect: int = 60,
) -> RAResult:
    """Batched jitted Algorithm 1 over pairs of any shape.

    Drop-in for `monotonic.solve_pairs` (same arguments and RAResult contract,
    host numpy outputs); pass the whole-horizon (rounds x K x N) channel
    tensor to amortize a single solve over the training horizon.  backend:
    None or "mixed" (the default on every platform), "newton", "bisect"
    (exact mirror of the host bisection), "jnp" (alias of "bisect"), or
    "pallas" (float32, opt-in).  On a TPU v5e only "mixed" and "bisect"
    hold the 1e-6 contract (ROADMAP S2).
    n_bisect sets the bisection step count of the "bisect"/"pallas"
    projections; the "newton" backend converges by a different rule and has
    its own fixed step budget (`project_newton`'s n_steps).
    """
    h2 = np.asarray(h2, dtype=np.float64)
    shape = h2.shape
    e_max = cfg.e_max_j if e_max is None else e_max
    eps = 0.01 if eps is None else float(eps)
    if backend is None:
        backend = "mixed"
    if backend == "jnp":
        backend = "bisect"

    beta_f = np.broadcast_to(np.asarray(beta, np.float64), shape).reshape(-1)
    h2f = h2.reshape(-1)
    e_f = np.broadcast_to(np.asarray(e_max, np.float64), shape).reshape(-1)
    n = h2f.shape[0]

    feas = ~is_infeasible(h2f, cfg, e_f)
    tau = np.full(n, np.nan)
    p = np.full(n, np.nan)
    time_s = np.full(n, np.inf)
    energy = np.full(n, np.nan)
    iters_out = np.zeros(n, dtype=np.int64)

    def flush(rows_mask, row_orig, bp, bf, it):
        rows = np.where(rows_mask & (row_orig >= 0))[0]
        if rows.size == 0:
            return
        orig = row_orig[rows]
        tau[orig] = bp[rows, 0]
        p[orig] = bp[rows, 1]
        time_s[orig] = -bf[rows]
        energy[orig] = total_energy(bp[rows, 0], bp[rows, 1],
                                    beta_f[orig], h2f[orig], cfg)
        iters_out[orig] = it[rows]

    work = np.where(feas)[0]
    if work.size:
        m_full = max_iter + 3                  # all slots + one spare column
        m = min(8, m_full)                     # lazy store, grown on demand
        b = _bucket(work.size)
        pad = b - work.size
        row_orig = np.concatenate([work, np.full(pad, -1, np.int64)])
        with jax.enable_x64(True):
            state = _init_state(
                jnp.asarray(np.concatenate([beta_f[work], np.ones(pad)])),
                jnp.asarray(np.concatenate([h2f[work], np.ones(pad)])),
                jnp.asarray(np.concatenate([e_f[work], np.full(pad, np.inf)])),
                jnp.int32(work.size),
                cfg=cfg, m=m, backend=backend, n_bisect=n_bisect)
            t = 0
            while t < max_iter:
                state = _step_select(state, eps=eps)
                act = np.asarray(state[_ACTIVE])
                na = int(act.sum())
                if na == 0:
                    break
                nb = _bucket(na)
                if nb < b:                     # compact BEFORE projecting
                    bp, bf, it = (np.asarray(state[_BESTP]),
                                  np.asarray(state[_BESTF]),
                                  np.asarray(state[_ITERS]))
                    flush(~act, row_orig, bp, bf, it)
                    keep = np.where(act)[0]
                    idx = np.concatenate([keep, np.zeros(nb - na, np.int64)])
                    state = _gather(state, jnp.asarray(idx), jnp.int32(na))
                    row_orig = np.concatenate(
                        [row_orig[keep], np.full(nb - na, -1, np.int64)])
                    b = nb
                if m < t + 3:                  # step t writes slot <= t+1
                    m = min(2 * m, m_full)
                    state = _grow(state, new_m=m)
                state = _step_children(state, cfg=cfg, backend=backend,
                                       n_bisect=n_bisect)
                t += 1
            bp, bf, it = (np.asarray(state[_BESTP]),
                          np.asarray(state[_BESTF]),
                          np.asarray(state[_ITERS]))
            flush(np.ones(b, bool), row_orig, bp, bf, it)

    return RAResult(
        tau=tau.reshape(shape),
        p=p.reshape(shape),
        time_s=time_s.reshape(shape),
        energy_j=energy.reshape(shape),
        feasible=feas.reshape(shape),
        iterations=iters_out.reshape(shape),
    )


def precompute_gamma(
    beta,
    h2_all,
    cfg: WirelessConfig,
    e_max=None,
    **kw,
) -> RAResult:
    """Whole-horizon Γ: solve all (round, sub-channel, device) pairs at once.

    h2_all has shape (rounds, K, N); beta broadcasts as (N,).  Returns an
    RAResult whose fields are (rounds, K, N) — Γ is `time_s`, the
    Proposition-1 mask is `feasible`.  One batched solve replaces `rounds`
    host solver invocations (speedup tracked in BENCH_control_plane.json,
    benchmarks/control_plane.py).

    solver: "fused" (default — `solve_pairs_fused`, staged whole-loop jit)
    or "step" (`solve_pairs_jit`, per-iteration phase-split driver).  Both
    produce bit-identical results; "fused" amortizes dispatch and host-sync
    overhead over the whole horizon.
    """
    h2_all = np.asarray(h2_all, np.float64)
    solver = kw.pop("solver", "fused")
    solve = solve_pairs_fused if solver == "fused" else solve_pairs_jit
    return solve(np.asarray(beta, np.float64)[None, None, :],
                 h2_all, cfg, e_max, **kw)
