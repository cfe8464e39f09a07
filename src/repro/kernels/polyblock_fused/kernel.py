"""Whole-horizon Algorithm 1 as ONE Pallas kernel (DESIGN.md §13).

`kernels.polyblock_project` fused the *projection* (eqs. 27-29); the
surrounding polyblock loop — vertex store, selection (paper steps 9-10),
retirement (eq. 26), child splitting (eq. 23) and the store writes
(eq. 24) — still lived in `core.monotonic_jax` as separate XLA dispatches
with a host-visible sync schedule.  This kernel moves the entire solve
inside one `pallas_call`: each (bm, 128) tile of the flattened pair axis
loads beta, |h|^2 and E^max once, keeps the whole vertex store as a
(m, bm, 128) VMEM-resident loop carry, and runs every polyblock iteration
— selection, both child bisections, store update — on the VPU without
touching HBM until the final (tau*, p*, T*, iterations) write.

Replication contract (pinned by tests/test_fused_solver.py and
tests/test_kernels.py): the arithmetic below mirrors the jnp solver
operation-for-operation —

  * the energy constraint g of eq. (22) and the objective T of eq. (8)
    are spelled exactly like `wireless.total_energy` / `total_time`
    (constants folded at compile time, same guard epsilons, same
    evaluation order);
  * the projection is the 60-step bisection of `project_jnp` (same
    `mid = (lo + hi)/2`, same `g > 0` branch sense, same TINY floor);
  * selection replicates `jnp.argmax`'s first-max tie-break via a
    min-index reduction (`idx = min(where(f == fbest, slot, m))` — a
    plain argmax lowering is not guaranteed first-match on all backends);
  * every store write is masked by the active set, so retired lanes are
    frozen bit-exactly as in the phase-split driver.

So in float64 (interpret mode, the CPU test path) the kernel is
*bit-identical* to `solve_pairs_fused(backend="bisect")` including the
per-pair iteration count; in float32 it is the fp32-accumulation study's
subject: pairs whose eq.-26 retirement is decided clear of fp32 noise
(all but ~1% of a random batch) keep the f64 trajectory exactly and land
at <= 1e-4 relative, and a boundary pair (|Δf| within fp32 noise of
eps = 0.01) may retire one iteration early/late but stays within the
retirement tolerance itself, |T - T_f64| <= eps (DESIGN.md §13).  That
holds with IEEE float32 arithmetic; compiled for a v5e, whose float32
log is good to ~3e-4 relative, the kernel misses it (ROADMAP S2), which
is why no driver takes it by default.

Layout: the vertex store needs max_iter + 1 slots (iteration t writes
child2 into slot t + 1), carried as five (m, bm, 128) arrays — at the
default bm = 8, max_iter = 64 that is ~1.7 MB in f32, comfortably
VMEM-resident.  Lanes are independent pairs; a tile exits its while_loop
as soon as every lane has retired (eq. 26), so tiles of easy pairs cost
only their own iterations.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["polyblock_solve_call"]

_TINY = 1e-12
_LN2 = math.log(2.0)


def _solve_kernel(beta_ref, h2_ref, emax_ref,
                  tau_ref, p_ref, time_ref, it_ref,
                  *, eps: float, max_iter: int, n_bisect: int,
                  kappa0_mu: float, mu_cycles: float, cpu_hz: float,
                  pt_w: float, model_bits: float, bandwidth_hz: float):
    beta = beta_ref[...]
    h2 = h2_ref[...]
    e_max = emax_ref[...]
    dt = beta.dtype
    shape = beta.shape
    m = max_iter + 1          # slot t + 1 is written at iteration t

    def energy(tau, p):
        # eq. (10), spelled as wireless.total_energy: E^cp + p P_t T^cm.
        e_cp = kappa0_mu * beta * (tau * cpu_hz) ** 2
        rate = bandwidth_hz * jnp.log1p(p * h2) / _LN2
        t_cm = model_bits / jnp.maximum(rate, 1e-30)
        return e_cp + p * pt_w * t_cm

    def neg_time(tau, p):
        # -T of eq. (8), spelled as wireless.total_time (f of eq. 21).
        t_cp = mu_cycles * beta / jnp.maximum(tau, 1e-30) / cpu_hz
        rate = bandwidth_hz * jnp.log1p(p * h2) / _LN2
        t_cm = model_bits / jnp.maximum(rate, 1e-30)
        return -(t_cp + t_cm)

    def project(tau_v, p_v):
        # eqs. (27-29): mirror of project_jnp's 60-step bisection.
        need = energy(tau_v, p_v) - e_max > 0.0

        def body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            take_hi = energy(mid * tau_v, mid * p_v) - e_max > 0.0
            return jnp.where(take_hi, lo, mid), jnp.where(take_hi, mid, hi)

        lo = jnp.full_like(tau_v, _TINY)
        hi = jnp.ones_like(tau_v)
        lo, _ = jax.lax.fori_loop(0, n_bisect, body, (lo, hi))
        zeta = jnp.where(need, lo, jnp.asarray(1.0, dt))
        return zeta * tau_v, zeta * p_v

    one = jnp.ones(shape, dt)
    pj0_tau, pj0_p = project(one, one)
    f0 = neg_time(pj0_tau, pj0_p)
    neg_inf = jnp.asarray(-jnp.inf, dt)

    # Vertex store: five (m, bm, 128) carries.  fval == -inf marks an
    # unwritten slot (the jnp driver's `valid` mask): written slots always
    # carry a finite f, since tau, p >= TINY * TINY keeps T finite.  Slot 0
    # is written by a select over the slot iota: Mosaic has no scatter.
    slot = jax.lax.broadcasted_iota(jnp.int32, (m,) + shape, 0)
    first = slot == 0
    zeros = jnp.zeros((m,) + shape, dt)
    verts_tau = jnp.where(first, one[None], zeros)
    verts_p = jnp.where(first, one[None], zeros)
    proj_tau = jnp.where(first, pj0_tau[None], zeros)
    proj_p = jnp.where(first, pj0_p[None], zeros)
    vfval = jnp.where(first, f0[None], neg_inf)

    # The active mask is carried as int32: Mosaic cannot carry a bool
    # vector through a loop (it fails to legalize the scf.yield).
    def cond(carry):
        t, *_, active, _pb, _bf, _bt, _bp, _it, _nv = carry
        return (t < max_iter) & (jnp.max(active) > 0)

    def body(carry):
        (t, verts_tau, verts_p, proj_tau, proj_p, vfval,
         active_i, prev_best, best_f, best_tau, best_p, iters, nvalid) = carry
        active = active_i > 0

        # Selection half-step (paper steps 9-10).  First-max tie-break as
        # a min-index reduction over the slot axis.
        fbest = jnp.max(vfval, axis=0)
        idx = jnp.min(jnp.where(vfval == fbest[None], slot, m), axis=0)
        sel = slot == idx[None]
        zero = jnp.zeros(shape, dt)
        sel_ptau = jnp.sum(jnp.where(sel, proj_tau, zero), axis=0)
        sel_pp = jnp.sum(jnp.where(sel, proj_p, zero), axis=0)
        improved = fbest > best_f
        best_f = jnp.where(improved, fbest, best_f)
        best_tau = jnp.where(improved, sel_ptau, best_tau)
        best_p = jnp.where(improved, sel_pp, best_p)
        done = jnp.abs(fbest - prev_best) <= eps        # eq. (26)
        prev_best = fbest
        active = active & ~done
        iters = iters + active.astype(jnp.int32)

        # Children half-step (paper steps 11-13, eq. 23): split the chosen
        # vertex at its projection, project both children.
        v_tau = jnp.sum(jnp.where(sel, verts_tau, zero), axis=0)
        v_p = jnp.sum(jnp.where(sel, verts_p, zero), axis=0)
        c1_tau, c1_p = project(sel_ptau, v_p)           # child1 = (phi_t, v_p)
        c2_tau, c2_p = project(v_tau, sel_pp)           # child2 = (v_t, phi_p)
        f1 = neg_time(c1_tau, c1_p)
        f2 = neg_time(c2_tau, c2_p)

        # eq. (24): child1 replaces the split slot, child2 takes the first
        # free one; both writes masked by `active` so retired lanes freeze.
        mask1 = sel & active[None]
        mask2 = (slot == nvalid[None]) & active[None]
        verts_tau = jnp.where(mask1, sel_ptau[None],
                              jnp.where(mask2, v_tau[None], verts_tau))
        verts_p = jnp.where(mask1, v_p[None],
                            jnp.where(mask2, sel_pp[None], verts_p))
        proj_tau = jnp.where(mask1, c1_tau[None],
                             jnp.where(mask2, c2_tau[None], proj_tau))
        proj_p = jnp.where(mask1, c1_p[None],
                           jnp.where(mask2, c2_p[None], proj_p))
        vfval = jnp.where(mask1, f1[None],
                          jnp.where(mask2, f2[None], vfval))
        nvalid = nvalid + active.astype(jnp.int32)

        return (t + 1, verts_tau, verts_p, proj_tau, proj_p, vfval,
                active.astype(jnp.int32), prev_best, best_f, best_tau,
                best_p, iters, nvalid)

    carry = (jnp.int32(0), verts_tau, verts_p, proj_tau, proj_p, vfval,
             jnp.ones(shape, jnp.int32), jnp.full(shape, jnp.inf, dt),
             f0, pj0_tau, pj0_p,
             jnp.zeros(shape, jnp.int32), jnp.ones(shape, jnp.int32))
    carry = jax.lax.while_loop(cond, body, carry)
    (_, _, _, _, _, _, _, _, best_f, best_tau, best_p, iters, _) = carry

    tau_ref[...] = best_tau.astype(tau_ref.dtype)
    p_ref[...] = best_p.astype(p_ref.dtype)
    time_ref[...] = (-best_f).astype(time_ref.dtype)
    it_ref[...] = iters


def polyblock_solve_call(beta, h2, e_max, *, eps: float, max_iter: int,
                         n_bisect: int, kappa0_mu: float, mu_cycles: float,
                         cpu_hz: float, pt_w: float, model_bits: float,
                         bandwidth_hz: float, bm: int = 8,
                         interpret: bool = False):
    """All operands (rows, 128), rows % bm == 0 -> (tau, p, time_s, iters)
    of the same shape (iters int32)."""
    rows, lanes = beta.shape
    assert lanes == 128 and rows % bm == 0, (beta.shape, bm)
    dt = beta.dtype
    kern = partial(
        _solve_kernel, eps=eps, max_iter=max_iter, n_bisect=n_bisect,
        kappa0_mu=kappa0_mu, mu_cycles=mu_cycles, cpu_hz=cpu_hz, pt_w=pt_w,
        model_bits=model_bits, bandwidth_hz=bandwidth_hz,
    )
    spec = pl.BlockSpec((bm, 128), lambda i: (i, jnp.int32(0)))
    return pl.pallas_call(
        kern,
        grid=(rows // bm,),
        in_specs=[spec] * 3,
        out_specs=(spec, spec, spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, 128), dt),
            jax.ShapeDtypeStruct((rows, 128), dt),
            jax.ShapeDtypeStruct((rows, 128), dt),
            jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        ),
        interpret=interpret,
    )(beta, h2, e_max)
