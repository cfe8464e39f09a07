"""The numbers that decide `correct`, each compared with its limit.  Every
number is a gap between what the timed path produced and the plain
reference (`reference/`), so each is 0 for a perfect program."""
from __future__ import annotations

import numpy as np

from .reference import gamma as ref_gamma


def sample_pairs(rng: np.random.Generator, shape, n: int) -> np.ndarray:
    """Flat indices of `n` distinct pairs of a Γ block, drawn from `rng`."""
    size = int(np.prod(shape))
    return rng.choice(size, size=min(n, size), replace=False)


def gamma_sample(config: dict, h2, e_max, beta, idx, dtype=np.float64):
    """Reference Algorithm 1 at flat pair indices `idx` of an (R, K, N)
    block: (time_s, tau, p, feasible)."""
    ph = ref_gamma.Physics(config["wireless"])
    r, _, n = np.unravel_index(idx, h2.shape)
    tau, p, t, _, f = ref_gamma.solve_pairs(
        ph, np.asarray(beta, np.float64)[n], h2.reshape(-1)[idx],
        np.asarray(e_max)[r, n], dtype=dtype)
    return t, tau, p, f


def engine_input(config: dict, shape, raw, beta, avail, slowdown, idx):
    """What the engine must be fed at pairs `idx`, by the reference's churn
    rule applied to a solved Γ `raw` = (time_s, tau, p, feasible) at those
    pairs: (float32 time, feasible)."""
    ph = ref_gamma.Physics(config["wireless"])
    r, _, n = np.unravel_index(idx, shape)
    t, tau, _, f = raw
    t, _, f = ref_gamma.with_dynamics(
        ph, tau, t, np.zeros_like(t), f, np.asarray(avail)[r, n],
        np.asarray(slowdown)[r, n], np.asarray(beta, np.float64)[n])
    return t.astype(np.float32), f


def gamma_gaps(got: dict, ref: dict) -> dict:
    """Γ readings `got` (the program's, or the control's) against the
    reference's.  Each pair feasible on both sides reads the worst
    relative gap of its time, tau and p; the numbers are the 99th
    percentile of those pair gaps, the largest and the mean; then the
    pairs whose Proposition-1 feasibility differs, and the pairs where
    the engine was fed other than the churn rule makes of the solve.

    Algorithm 1 picks a vertex by comparing objective values and retires
    a pair by the eq.-26 step rule: rounding that tips a near tie sends a
    few pairs to another stopping vertex, so the largest gap swings from
    sample to sample, while the percentile reads the arithmetic."""
    (gt, gtau, gp, gf), (rt, rtau, rp, rf) = got["raw"], ref["raw"]
    both = np.asarray(gf) & np.asarray(rf)
    rel = np.zeros(int(both.sum()))
    for a, b in ((gt, rt), (gtau, rtau), (gp, rp)):
        a, b = np.asarray(a, np.float64)[both], np.asarray(b, np.float64)[both]
        rel = np.maximum(rel, np.abs(a - b) / np.abs(b))
    if not rel.size:
        rel = np.full(1, np.inf)
    want_t, want_f = got["expected_input"]
    in_t, in_f = got["input"]
    bad_in = (np.asarray(in_f) != want_f) | (
        want_f & (np.asarray(in_t, np.float32) != want_t))
    return {"gamma_p99_rel_err": float(np.quantile(rel, 0.99)),
            "gamma_max_rel_err": float(rel.max()),
            "gamma_mean_rel_err": float(rel.mean()),
            "feasible_mismatch": int((np.asarray(gf) != np.asarray(rf)).sum()),
            "input_mismatch": int(bad_in.sum())}


def loss_rel_err(prog: list[float], ref: list[float]) -> float:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def update_gap(prog_norms: dict, ref_norms: dict) -> float:
    """Worst leaf's gap between the program's and the reference's norm of
    the parameters' change, against the larger of that leaf's reference
    norm and the median leaf's.  A leaf the reference leaves at nought to
    rounding (under a thousandth of the median leaf) is left out."""
    med = float(np.median(list(ref_norms.values())))
    worst = 0.0
    for leaf, r in ref_norms.items():
        if r < 1e-3 * med:
            continue
        p = prog_norms[leaf]
        if not np.isfinite(p):
            return float("inf")
        worst = max(worst, abs(p - r) / max(r, med))
    return worst


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when none exceeds it."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
