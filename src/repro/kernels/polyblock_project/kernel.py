"""Fused polyblock projection (eqs. 27-29) as a Pallas kernel.

The projection is the inner loop of Algorithm 1: every polyblock iteration
projects two child vertices per active pair, and each projection runs
`n_bisect` (= 60) evaluations of the energy constraint g of eq. (22).  Done
naively that is 60 round trips through HBM per (pair, vertex) batch; at
framework scale (rounds x K x N pairs solved in one whole-horizon sweep,
DESIGN.md §6) the traffic is pure overhead because the working set per
element is five scalars.

The kernel therefore fuses the entire bisection — g-evaluation at the
midpoint, interval update, and final zeta selection — into one VMEM-resident
pass: the grid tiles the flattened (pair, vertex) axis into (bm, 128) blocks;
each block loads tau, p, beta, |h|^2 and E^max once, runs all 60 halvings on
the VPU, and writes a single zeta per element.  One HBM read of 5 floats and
one write per element, independent of n_bisect.

Wireless constants enter as compile-time Python floats (they are frozen per
`WirelessConfig`), so the kernel body hard-codes eq. (22):

    g(z*tau, z*p) = kappa0*mu*beta*(z*tau*C)^2
                  + z*p*P_t*D / (B*log2(1 + z*p*|h|^2)) - E^max
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["polyblock_project_call"]

_TINY = 1e-12
_LN2 = math.log(2.0)


def _project_kernel(tau_ref, p_ref, beta_ref, h2_ref, emax_ref, zeta_ref,
                    *, n_bisect: int, kappa0_mu: float, cpu_hz: float,
                    pt_w: float, model_bits: float, bandwidth_hz: float):
    tau_v = tau_ref[...]
    p_v = p_ref[...]
    beta = beta_ref[...]
    h2 = h2_ref[...]
    e_max = emax_ref[...]

    def g_con(tau, p):
        e_cp = kappa0_mu * beta * (tau * cpu_hz) ** 2
        rate = bandwidth_hz * jnp.log1p(p * h2) / _LN2
        t_cm = model_bits / jnp.maximum(rate, 1e-30)
        return e_cp + p * pt_w * t_cm - e_max

    need_root = g_con(tau_v, p_v) > 0.0

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        take_hi = g_con(mid * tau_v, mid * p_v) > 0.0
        return jnp.where(take_hi, lo, mid), jnp.where(take_hi, mid, hi)

    lo = jnp.full_like(tau_v, _TINY)
    hi = jnp.ones_like(tau_v)
    lo, _ = jax.lax.fori_loop(0, n_bisect, body, (lo, hi))
    zeta_ref[...] = jnp.where(need_root, lo, 1.0).astype(zeta_ref.dtype)


def polyblock_project_call(tau_v, p_v, beta, h2, e_max, *, n_bisect: int = 60,
                           kappa0_mu: float, cpu_hz: float, pt_w: float,
                           model_bits: float, bandwidth_hz: float,
                           bm: int = 8, interpret: bool = False):
    """All operands (rows, 128), rows % bm == 0 -> zeta of the same shape."""
    rows, lanes = tau_v.shape
    assert lanes == 128 and rows % bm == 0, (tau_v.shape, bm)
    kern = partial(
        _project_kernel, n_bisect=n_bisect, kappa0_mu=kappa0_mu,
        cpu_hz=cpu_hz, pt_w=pt_w, model_bits=model_bits,
        bandwidth_hz=bandwidth_hz,
    )
    spec = pl.BlockSpec((bm, 128), lambda i: (i, jnp.int32(0)))
    return pl.pallas_call(
        kern,
        grid=(rows // bm,),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, 128), tau_v.dtype),
        interpret=interpret,
    )(tau_v, p_v, beta, h2, e_max)
