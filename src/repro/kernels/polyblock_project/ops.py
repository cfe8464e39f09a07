"""Dispatcher for the fused polyblock projection.

Five interchangeable backends (tests assert pairwise agreement):

  "ref"    — host NumPy bisection (`ref.py`), float64; also what the legacy
             `core.monotonic._project` runs.
  "bisect" — fused jax.numpy `lax.fori_loop` mirror of "ref" (same
             arithmetic in the same order), jit/vmap-safe; float64 under an
             `jax.enable_x64(True)` scope.  Alias: "jnp".
  "newton" — safeguarded Newton-bisection hybrid: each step evaluates g of
             eq. (22) ONCE (the expensive log1p is shared between g and g'),
             takes the Newton step when it stays inside the current bracket
             and falls back to the midpoint otherwise.  Quadratic
             convergence reaches the float64 root in ~8 engaged steps, so
             `n_steps` = 16 replaces the reference's 60 bisections (~4x
             fewer constraint evaluations) while agreeing with it to
             ~1e-12 in zeta on the CPU.  On a TPU v5e (emulated float64)
             it measured up to 4.25e-1 relative off NumPy `core.monotonic`,
             so it is opt-in there (ROADMAP S2).
  "mixed"  — "newton" with the bracket contraction in float32 and a
             float64 polish (`project_newton_mixed`): the default of both
             Γ drivers on every platform.
  "pallas" — the VMEM-resident kernel (`kernel.py`), float32 (TPU has no
             f64).  Opt-in: compiled on a TPU, interpret mode (the CPU
             test path) elsewhere.

`project_jnp` / `project_newton` are exported separately because
`core.monotonic_jax` embeds them inside its jitted solver steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.wireless import WirelessConfig, total_energy
from .kernel import polyblock_project_call
from .ref import TINY, project_ref

__all__ = ["polyblock_project", "project_jnp", "project_newton",
           "project_newton_mixed", "project_pallas"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def project_jnp(v, beta, h2, e_max, cfg: WirelessConfig, *, n_bisect: int = 60):
    """Fused jnp mirror of `ref.project_ref` (same arithmetic, same order)."""
    tau_v, p_v = v[..., 0], v[..., 1]

    def g_con(tau, p):
        return total_energy(tau, p, beta, h2, cfg) - e_max

    need_root = g_con(tau_v, p_v) > 0.0

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        take_hi = g_con(mid * tau_v, mid * p_v) > 0.0
        return jnp.where(take_hi, lo, mid), jnp.where(take_hi, mid, hi)

    lo = jnp.full_like(tau_v, TINY)
    hi = jnp.ones_like(tau_v)
    lo, _ = jax.lax.fori_loop(0, n_bisect, body, (lo, hi))
    zeta = jnp.where(need_root, lo, 1.0)
    return zeta[..., None] * v


def project_newton(v, beta, h2, e_max, cfg: WirelessConfig, *,
                   n_steps: int = 14):
    """Safeguarded log-space Newton root of g(zeta * v) = 0 on (0, 1].

    Newton in y = log(zeta) — cand = x * exp(-g / (x g')) — so convergence is
    scale-free: roots spanning many decades (they reach ~1e-4 for weak
    channels) are approached multiplicatively, where linear-space Newton
    stagnates against its bracket.  The bisection bracket [lo, hi] is kept
    for guaranteed convergence with a *geometric*-mean fallback whenever the
    candidate leaves the open bracket (NaN/inf candidates — e.g. padded rows
    with e_max = inf — fail the comparison too, keeping them harmless).  With

        g(x)  = a x^2 + b x / L(cx) - e_max,   L = log1p,
        g'(x) = 2 a x + b (L(cx) - cx/(1 + cx)) / L(cx)^2,

    where a = kappa0 mu beta (tau_v C)^2, b = p_v P_t D ln2 / B and
    c = p_v |h|^2, so g and g' share one log1p per step.

    Warm start: as zeta -> 0 the communication term flattens to its
    Proposition-1 infimum b/c, so x0 = sqrt((e_max - b/c) / a) is the exact
    root of the low-SNR limit — Newton then only corrects the rate curvature.
    14 steps reproduce the reference 60-step bisection root to ~1e-9
    relative on Prop-1 feasible pairs (tests/test_monotonic_jax.py) at ~4x
    fewer transcendental evaluations.
    """
    tau_v, p_v = v[..., 0], v[..., 1]
    a = cfg.kappa0 * cfg.mu_cycles * beta * (tau_v * cfg.cpu_hz) ** 2
    b = p_v * cfg.pt_w * cfg.model_bits * np.log(2.0) / cfg.bandwidth_hz
    c = p_v * h2

    def g_gp(x):
        u = c * x
        el = jnp.log1p(u)
        elc = jnp.maximum(el, 1e-300)
        g = a * x * x + b * x / elc - e_max
        gp = 2.0 * a * x + b * (el - u / (1.0 + u)) / (elc * elc)
        return g, gp

    need_root = g_gp(jnp.ones_like(tau_v))[0] > 0.0
    x0 = jnp.sqrt(jnp.maximum(e_max - b / jnp.maximum(c, 1e-300), 1e-300)
                  / jnp.maximum(a, 1e-300))
    x0 = jnp.clip(x0, TINY, 1.0 - 1e-9)

    def body(_, carry):
        lo, hi, x = carry
        g, gp = g_gp(x)
        pos = g > 0.0
        lo = jnp.where(pos, lo, x)
        hi = jnp.where(pos, x, hi)
        cand = x * jnp.exp(-g / (x * gp))
        ok = (cand > lo) & (cand < hi)
        return lo, hi, jnp.where(ok, cand, jnp.sqrt(lo * hi))

    lo = jnp.full_like(tau_v, TINY)
    hi = jnp.ones_like(tau_v)
    lo, hi, x = jax.lax.fori_loop(0, n_steps, body, (lo, hi, x0))
    zeta = jnp.where(need_root, jnp.clip(x, TINY, 1.0), 1.0)
    return zeta[..., None] * v


def project_newton_mixed(v, beta, h2, e_max, cfg: WirelessConfig, *,
                         n_f32: int = 6, n_f64: int = 2, x0_hint=None):
    """Mixed-precision Newton: fp32 bulk iterations + fp64 polish.

    The fp32-accumulation study behind the fused solver (DESIGN.md §13):
    the safeguarded log-space Newton loop is precision-agnostic, and on CPU
    the fp32 `log1p`/`exp` run at twice the SIMD width of fp64, so the bulk
    of the bracket contraction is done in fp32 (rel error ~1e-7 at the f32
    root), then `n_f64` safeguarded fp64 steps restart from that root —
    Newton's quadratic convergence turns 1e-7 into ~1e-14 in one engaged
    step, so the polished root matches `project_newton`'s to ~1e-12
    relative.  The `need_root` boundary test (g(v) > 0) runs in fp64:
    pairs with g(v) within fp32 noise of zero must classify exactly like
    the reference, or a spurious projection shifts the vertex by ~1e-7.

    Only sound where the fp32 loop lands inside the basin of quadratic
    convergence — the warm start (exact low-SNR root) makes that hold at
    Table-I physics; the fp64 safeguard bracket keeps stragglers convergent
    rather than wrong.  tests/test_monotonic_jax.py pins this to the f64
    backends at 1e-9.
    """
    v64 = jnp.asarray(v)
    tau_v, p_v = v64[..., 0], v64[..., 1]
    a = cfg.kappa0 * cfg.mu_cycles * beta * (tau_v * cfg.cpu_hz) ** 2
    b = p_v * cfg.pt_w * cfg.model_bits * np.log(2.0) / cfg.bandwidth_hz
    c = p_v * h2

    def g_gp(x):
        u = c * x
        el = jnp.log1p(u)
        elc = jnp.maximum(el, 1e-300)
        g = a * x * x + b * x / elc - e_max
        gp = 2.0 * a * x + b * (el - u / (1.0 + u)) / (elc * elc)
        return g, gp

    # fp32 bulk: same loop as project_newton, all operands cast down.
    f32 = jnp.float32
    a32, b32, c32 = a.astype(f32), b.astype(f32), c.astype(f32)
    e32 = jnp.asarray(e_max).astype(f32)

    def g_gp32(x):
        u = c32 * x
        el = jnp.log1p(u)
        elc = jnp.maximum(el, f32(1e-38))
        g = a32 * x * x + b32 * x / elc - e32
        gp = 2.0 * a32 * x + b32 * (el - u / (1.0 + u)) / (elc * elc)
        return g, gp

    # Warm start, regime-split.  `project_newton` starts every row at the
    # low-SNR-limit root sqrt(q / a), q = e_max - b/c — exact when the
    # quadratic compute term dominates, but near the Prop-1 feasibility
    # boundary the root drops to ~1e-3 where the *linear* comm correction
    # dominates (a x^2 << b x / 2) and the sqrt start overshoots by orders
    # of magnitude (those rows are why the cold loop needs 14 steps).  One
    # order deeper, L(u) = u (1 - u/2) + O(u^3) flattens the constraint to
    # a x^2 + (b/2) x - q = 0, whose positive root (Muller's form,
    # cancellation-free as either coefficient vanishes) is near-exact
    # precisely when its own expansion variable u = c x stays small — so
    # each row picks the quadratic start when it is self-consistent
    # (c x_quad < 1/2) and the sqrt start otherwise.
    q = jnp.maximum(e32 - b32 / jnp.maximum(c32, f32(1e-38)), f32(1e-38))
    bh = 0.5 * b32
    a_s = jnp.maximum(a32, f32(1e-38))
    x_quad = 2.0 * q / (bh + jnp.sqrt(bh * bh + 4.0 * a_s * q))
    x_sqrt = jnp.sqrt(q / a_s)
    x0 = jnp.where(c32 * x_quad < 0.5, x_quad, x_sqrt)
    if x0_hint is not None:
        # Polyblock children shrink one coordinate of their parent, and the
        # per-device energy of eq. (10) is increasing in both tau and p, so
        # g_child <= g_parent pointwise and the parent's root zeta_par is a
        # lower bound on the child's: starting at max(low-SNR root,
        # zeta_par) puts every row inside the quadratic basin (the root
        # moved *up* from a known point), where the cold start is only exact
        # in the low-SNR limit.  Non-finite hints (retired rows carry junk
        # slots) fall back to the cold start.
        h32 = jnp.asarray(x0_hint).astype(f32)
        x0 = jnp.where(jnp.isfinite(h32), jnp.maximum(x0, h32), x0)
    x0 = jnp.clip(x0, f32(TINY), f32(1.0 - 1e-7))

    def body32(_, carry):
        # Boundary-EQUAL candidates are accepted (>=): at fp32 convergence
        # g rounds to exactly 0, the bracket endpoint is set to x itself,
        # and cand == x == lo — the strict test would hand a converged root
        # to the geometric fallback, which hurls it to sqrt(root * 1).
        lo, hi, x = carry
        g, gp = g_gp32(x)
        pos = g > 0.0
        lo = jnp.where(pos, lo, x)
        hi = jnp.where(pos, x, hi)
        cand = x * jnp.exp(-g / (x * gp))
        ok = (cand >= lo) & (cand <= hi)
        return lo, hi, jnp.where(ok, cand, jnp.sqrt(lo * hi))

    lo32 = jnp.full_like(x0, f32(TINY))
    hi32 = jnp.ones_like(x0)
    _, _, x32 = jax.lax.fori_loop(0, n_f32, body32, (lo32, hi32, x0))

    # fp64 polish: fresh safeguard bracket, start at the fp32 root.  Unlike
    # the cold-start loop, the fallback *keeps x* rather than jumping to the
    # bracket's geometric mean: at exact convergence the Newton candidate
    # rounds onto a bracket endpoint (cand == x == lo or hi), and with only
    # a handful of polish steps the bracket can still be one-sided, so the
    # geometric fallback would hurl a converged root to sqrt(root * 1).
    # Boundary-equal candidates are accepted (>=); NaN/runaway candidates
    # fail the comparison and leave x unchanged.
    need_root = g_gp(jnp.ones_like(tau_v))[0] > 0.0
    x = jnp.clip(x32.astype(tau_v.dtype), TINY, 1.0 - 1e-12)

    def body64(_, carry):
        # Halley instead of Newton: g'' is algebraic once log1p(u) is in
        # hand — with F = x / L and w = u / (1 + u),
        #   F'' = c t (1 - t) / L^2 - 2 c t (L - w) / L^3,   t = 1/(1 + u),
        # so the third-order step costs the same single transcendental as a
        # Newton step (and skips the log-space exp: the fp32 bulk already
        # landed near the root, where the linear step is safe inside the
        # bracket).  Cubic convergence turns the bulk's ~1e-4 into ~1e-12
        # in ONE engaged step where Newton needs two.
        lo, hi, x = carry
        u = c * x
        el = jnp.log1p(u)
        elc = jnp.maximum(el, 1e-300)
        t1 = 1.0 / (1.0 + u)
        w = u * t1
        g = a * x * x + b * x / elc - e_max
        gp = 2.0 * a * x + b * (el - w) / (elc * elc)
        g2 = 2.0 * a + b * c * t1 * ((1.0 - t1) / (elc * elc)
                                     - 2.0 * (el - w) / (elc * elc * elc))
        pos = g > 0.0
        lo = jnp.where(pos, lo, x)
        hi = jnp.where(pos, x, hi)
        cand = x - 2.0 * g * gp / (2.0 * gp * gp - g * g2)
        ok = (cand >= lo) & (cand <= hi)
        return lo, hi, jnp.where(ok, cand, x)

    lo = jnp.full_like(tau_v, TINY)
    hi = jnp.ones_like(tau_v)
    _, _, x = jax.lax.fori_loop(0, n_f64, body64, (lo, hi, x))
    zeta = jnp.where(need_root, jnp.clip(x, TINY, 1.0), 1.0)
    return zeta[..., None] * v64


def project_pallas(v, beta, h2, e_max, cfg: WirelessConfig, *,
                   n_bisect: int = 60, bm: int = 8, interpret: bool | None = None):
    """Pad + tile the flattened batch to (rows, 128) and run the kernel."""
    if interpret is None:
        interpret = not _on_tpu()
    v = jnp.asarray(v, jnp.float32)
    shape = v.shape[:-1]
    n = int(np.prod(shape)) if shape else 1
    flat = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), shape).reshape(-1)
    tau_v, p_v = v[..., 0].reshape(-1), v[..., 1].reshape(-1)
    betaf, h2f, emaxf = flat(beta), flat(h2), flat(e_max)

    tile = bm * 128
    pad = (-n) % tile
    if pad:
        # Padding lanes bisect a harmless dummy element (g(1,1) <= 0 there).
        ones = jnp.ones(pad, jnp.float32)
        tau_v, p_v = jnp.concatenate([tau_v, ones]), jnp.concatenate([p_v, ones])
        betaf = jnp.concatenate([betaf, ones])
        h2f = jnp.concatenate([h2f, ones])
        emaxf = jnp.concatenate([emaxf, jnp.full(pad, 1e9, jnp.float32)])
    shape2d = (-1, 128)
    zeta = polyblock_project_call(
        tau_v.reshape(shape2d), p_v.reshape(shape2d), betaf.reshape(shape2d),
        h2f.reshape(shape2d), emaxf.reshape(shape2d),
        n_bisect=n_bisect, kappa0_mu=cfg.kappa0 * cfg.mu_cycles,
        cpu_hz=cfg.cpu_hz, pt_w=cfg.pt_w, model_bits=cfg.model_bits,
        bandwidth_hz=cfg.bandwidth_hz, bm=bm, interpret=interpret,
    )
    zeta = zeta.reshape(-1)[:n].reshape(shape)
    return zeta[..., None] * v


def polyblock_project(v, beta, h2, e_max, cfg: WirelessConfig, *,
                      n_bisect: int = 60, backend: str | None = None,
                      interpret: bool | None = None):
    """Project a batch of vertices.

    backend: None or "mixed" (fp32-bulk/fp64-polish Newton, the default
    on every platform), "ref", "bisect" (alias "jnp"), "newton", or
    "pallas".
    """
    if backend is None:
        backend = "mixed"
    if backend == "ref":
        return project_ref(v, beta, h2, e_max, cfg, n_bisect=n_bisect)
    if backend in ("bisect", "jnp"):
        return project_jnp(jnp.asarray(v), beta, h2, e_max, cfg, n_bisect=n_bisect)
    if backend == "newton":
        return project_newton(jnp.asarray(v), beta, h2, e_max, cfg)
    if backend == "mixed":
        return project_newton_mixed(jnp.asarray(v), beta, h2, e_max, cfg)
    if backend == "pallas":
        return project_pallas(v, beta, h2, e_max, cfg,
                              n_bisect=n_bisect, interpret=interpret)
    raise ValueError(f"unknown backend: {backend}")
