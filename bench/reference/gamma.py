"""Plain NumPy reference for Algorithm 1 (the per-pair resource allocation
Γ) and the churn dynamics folded into it.

A copy of the program's host solver (`core.monotonic.solve_pairs`, the
bisection projection of `kernels.polyblock_project.ref`, the closed forms
of `core.wireless` and Proposition 1 of `core.feasibility`) and of
`scenarios.apply_dynamics`, kept here so that no change to the program can
move the yardstick.  It imports nothing of the program.

`dtype` selects the arithmetic: float64 is the reference; float32 is the
control (the nearest precision below the float64 that the configuration
states), which the check must refuse.
"""
from __future__ import annotations

import numpy as np

TINY = 1e-12
C_LIGHT = 3e8


class Physics:
    """Table I wireless constants, read from a configuration's `wireless`
    group (the keys of the program's `WirelessConfig`)."""

    def __init__(self, w: dict):
        self.bandwidth_hz = float(w["bandwidth_hz"])
        self.pt_w = 10.0 ** (float(w["pt_dbm"]) / 10.0) * 1e-3
        self.kappa0 = float(w["kappa0"])
        self.mu_cycles = float(w["mu_cycles"])
        self.cpu_hz = float(w["cpu_hz"])
        self.model_bits = float(w["model_bits"])

    # eqs. (1)-(5), (8), (10)
    def compute_time(self, tau, beta):
        return self.mu_cycles * beta / np.maximum(tau, 1e-30) / self.cpu_hz

    def compute_energy(self, tau, beta):
        return self.kappa0 * self.mu_cycles * beta * (tau * self.cpu_hz) ** 2

    def comm_time(self, p, h2):
        rate = self.bandwidth_hz * np.log1p(p * h2) / np.log(2.0)
        return self.model_bits / np.maximum(rate, 1e-30)

    def total_time(self, tau, p, beta, h2):
        return self.compute_time(tau, beta) + self.comm_time(p, h2)

    def total_energy(self, tau, p, beta, h2):
        return (self.compute_energy(tau, beta)
                + p * self.pt_w * self.comm_time(p, h2))

    def infeasible(self, h2, e_max):
        """Proposition 1, eq. (15)."""
        e_min = (np.log(2.0) * self.pt_w * self.model_bits
                 / (self.bandwidth_hz * np.maximum(h2, 1e-300)))
        return e_min >= e_max


def _project(ph: Physics, v, beta, h2, e_max, n_bisect=60):
    """zeta * v on the boundary of {g <= 0} by bisection (eqs. 27-29)."""
    tau_v, p_v = v[..., 0], v[..., 1]
    need = ph.total_energy(tau_v, p_v, beta, h2) - e_max > 0.0
    lo = np.full_like(tau_v, TINY)
    hi = np.ones_like(tau_v)
    for _ in range(n_bisect):
        mid = (lo + hi) * tau_v.dtype.type(0.5)
        over = ph.total_energy(mid * tau_v, mid * p_v, beta, h2) - e_max > 0.0
        hi = np.where(over, mid, hi)
        lo = np.where(over, lo, mid)
    zeta = np.where(need, lo, tau_v.dtype.type(1.0))
    return zeta[..., None] * v


def solve_pairs(ph: Physics, beta, h2, e_max, *, dtype=np.float64,
                eps=0.01, max_iter=64):
    """Algorithm 1 on flat pair arrays; returns (tau, p, time_s, energy_j,
    feasible).  time_s is +inf where Proposition 1 rules the pair out."""
    beta = np.asarray(beta, dtype).copy()
    h2 = np.asarray(h2, dtype).copy()
    e_max = np.asarray(e_max, dtype).copy()
    n = h2.shape[0]
    feas = ~ph.infeasible(h2.astype(np.float64), e_max.astype(np.float64))
    m = max_iter + 2
    verts = np.zeros((n, m, 2), dtype)
    vproj = np.zeros((n, m, 2), dtype)
    vfval = np.full((n, m), -np.inf, dtype)
    valid = np.zeros((n, m), bool)

    def f_obj(pj, b, h):
        return -ph.total_time(pj[:, 0], pj[:, 1], b, h)

    verts[:, 0] = 1.0
    vproj[:, 0] = _project(ph, verts[:, 0], beta, h2, e_max)
    vfval[:, 0] = f_obj(vproj[:, 0], beta, h2)
    valid[:, 0] = True
    active = feas.copy()
    prev_best = np.full(n, np.inf, dtype)
    best_proj = vproj[:, 0].copy()
    best_f = vfval[:, 0].copy()
    rows = np.arange(n)
    for t in range(max_iter):
        if not active.any():
            break
        fv = np.where(valid, vfval, -np.inf)
        idx = np.argmax(fv, axis=1)
        fbest = fv[rows, idx]
        improved = fbest > best_f
        best_f = np.where(improved, fbest, best_f)
        best_proj = np.where(improved[:, None], vproj[rows, idx], best_proj)
        done = np.abs(fbest - prev_best) <= eps          # eq. (26)
        prev_best = fbest
        active &= ~done
        if not active.any():
            break
        a = np.where(active)[0]
        v = verts[a, idx[a]]
        phi = vproj[a, idx[a]]
        child1 = v.copy()
        child1[:, 0] = phi[:, 0]
        child2 = v.copy()
        child2[:, 1] = phi[:, 1]
        for child, slot in ((child1, idx[a]), (child2, np.full(len(a), t + 1))):
            pj = _project(ph, child, beta[a], h2[a], e_max[a])
            verts[a, slot] = child
            vproj[a, slot] = pj
            vfval[a, slot] = f_obj(pj, beta[a], h2[a])
            valid[a, slot] = True
    tau = np.where(feas, best_proj[:, 0], np.nan).astype(np.float64)
    p = np.where(feas, best_proj[:, 1], np.nan).astype(np.float64)
    time_s = np.where(feas, -best_f, np.inf).astype(np.float64)
    energy = np.where(feas, ph.total_energy(best_proj[:, 0], best_proj[:, 1],
                                            beta, h2), np.nan).astype(np.float64)
    return tau, p, time_s, energy, feas


def with_dynamics(ph: Physics, tau, time_s, energy, feas, avail, slowdown,
                  beta):
    """Churn folded into solved pairs (the program's `apply_dynamics`):
    an absent device is infeasible; a straggler's compute share of the
    round time stretches by its slowdown s, its compute energy by 1/s^2."""
    feas = feas & avail
    tau_ok = np.where(feas, tau, 0.5)
    t_cp = ph.compute_time(tau_ok, beta)
    e_cp = ph.compute_energy(tau_ok, beta)
    time_s = np.where(feas, time_s + (slowdown - 1.0) * t_cp, np.inf)
    energy = np.where(feas, energy + (1.0 / slowdown ** 2 - 1.0) * e_cp,
                      np.nan)
    return time_s, energy, feas
