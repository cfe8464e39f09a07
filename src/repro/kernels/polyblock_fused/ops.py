"""Host wrapper for the fully fused polyblock solve kernel.

Pads the flattened feasible-pair batch to (rows, 128) tiles (padding lanes
get the same harmless dummy element the projection kernel uses: beta = 1,
|h|^2 = 1, E^max = 1e9 — g(1, 1) < 0, so they retire after two iterations
without ever projecting below zeta = 1), invokes `polyblock_solve_call`,
and strips the padding.

Callers (the `backend="pallas"` branch of `core.monotonic_jax.
solve_pairs_fused`, the differential tests, `benchmarks/control_plane.py`)
pass Proposition-1 *feasible* pairs only; infeasibility is resolved before
the kernel, exactly as in the jnp drivers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.wireless import WirelessConfig
from .kernel import polyblock_solve_call

__all__ = ["polyblock_solve_fused"]


def polyblock_solve_fused(beta, h2, e_max, cfg: WirelessConfig, *,
                          eps: float = 0.01, max_iter: int = 64,
                          n_bisect: int = 60, bm: int = 8,
                          interpret: bool | None = None, dtype=None):
    """Solve a flat batch of feasible (beta, |h|^2, E^max) pairs entirely
    inside one Pallas kernel.

    Returns (tau, p, time_s, iterations) as flat arrays of the input
    length; dtype defaults to float64 in interpret mode (bit-identical to
    the jnp `backend="bisect"` solver) and float32 compiled on TPU (the
    fp32-accumulation study, <= 1e-4 relative on the CPU — DESIGN.md §13;
    compiled on a v5e it measured up to ~7e-2, ROADMAP S2).
    Interpret mode is the CPU test path only; on a TPU the kernel compiles.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if dtype is None:
        dtype = np.float64 if interpret else np.float32
    dtype = jnp.dtype(dtype)

    betaf = jnp.asarray(beta, dtype).reshape(-1)
    h2f = jnp.asarray(h2, dtype).reshape(-1)
    emaxf = jnp.broadcast_to(jnp.asarray(e_max, dtype), h2f.shape).reshape(-1)
    n = int(h2f.shape[0])

    tile = bm * 128
    pad = (-n) % tile
    if pad:
        ones = jnp.ones(pad, dtype)
        betaf = jnp.concatenate([betaf, ones])
        h2f = jnp.concatenate([h2f, ones])
        emaxf = jnp.concatenate([emaxf, jnp.full(pad, 1e9, dtype)])

    shape2d = (-1, 128)
    tau, p, time_s, iters = polyblock_solve_call(
        betaf.reshape(shape2d), h2f.reshape(shape2d), emaxf.reshape(shape2d),
        eps=float(eps), max_iter=int(max_iter), n_bisect=int(n_bisect),
        kappa0_mu=cfg.kappa0 * cfg.mu_cycles, mu_cycles=cfg.mu_cycles,
        cpu_hz=cfg.cpu_hz, pt_w=cfg.pt_w, model_bits=cfg.model_bits,
        bandwidth_hz=cfg.bandwidth_hz, bm=bm, interpret=interpret,
    )
    unpad = lambda x: x.reshape(-1)[:n]
    return unpad(tau), unpad(p), unpad(time_s), unpad(iters)
