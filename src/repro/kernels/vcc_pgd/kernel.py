"""Pallas TPU kernel: fused VCC projected-gradient epoch.

Layout: clusters on lanes, hours on sublanes. The wrappers take and return
the ``(n, H)`` arrays of ``ref`` and move clusters onto the last axis
outside the ``pallas_call`` (plain XLA transposes), padding it to the
lane tile. Grid = (n_padded / tile,); each step loads an ``(H, tile)``
block of every wide operand (delta, eta, pi, pow_nom, lo, ub) and a
``(1, tile)`` block of every per-cluster scalar into VMEM and runs the
FULL inner optimization epoch — ``iters`` x [gradient of the linearized
carbon+peak objective → exact breakpoint projection onto the
conservation simplex slab] — without touching HBM between iterations.
Every reduction over hours (softmax max and sum, the projection's sum
and its bracket over the 48 breakpoints) runs down the sublanes of each
vreg, elementwise across its 128 clusters, so the epoch fills every lane
and needs no cross-lane traffic. The day-ahead optimizer calls this once
per dual-ascent round for the whole fleet.

``temp`` and ``lambda_e`` ride in as broadcast (1, n) operands rather than
compile-time constants: the day cycle derives ``temp`` from the problem
inside jit, so they may be traced scalars.

Validated with interpret=True against ref.pgd_epoch_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
DEFAULT_TILE = 256
ENS_TILE = 128    # smaller cluster tile: each block also carries K members


def _project_rows(z, lo, ub):
    """Exact projection of each cluster's hours onto {sum_h = 0} ∩
    [lo, ub] (the set of ref.project_row, whose bisection reaches this
    answer to float resolution). z/lo/ub: (H, tile), clusters on lanes
    and independent; every reduction runs over axis 0. The ONE copy all
    kernels call — the identical-members bitwise contract between the
    plain and ensemble epochs rides on them projecting identically.

    The answer is clip(z - nu, lo, ub) where f(nu) = sum_h clip(z_h - nu,
    lo_h, ub_h) crosses 0. f is non-increasing and linear between the 2H
    breakpoints z - ub, z - lo, so it is evaluated at all of them at once,
    (2H, tile) on the sublanes, hour by hour and with no sort. (The equal
    sum of z_h - clip(b, z_h - ub_h, z_h - lo_h) takes one op fewer but
    cancels, and conserves several times less precisely.) The last
    breakpoint with f >= 0 and the first with f <= 0 bracket the root,
    and nu interpolates between them; where f is 0 over an interval,
    every hour is at a bound there and nu is its end. A row with no
    breakpoint on one side cannot conserve: nu = -inf puts every hour at
    ub (sum ub < 0), nu = +inf every hour at lo (sum lo > 0). lo == ub
    rows come out pinned either way."""
    b = jnp.concatenate([z - ub, z - lo], axis=0)       # (2H, tile)
    f = jnp.zeros_like(b)                               # f at each b
    for h in range(z.shape[0]):
        f = f + jnp.clip(z[h:h + 1] - b, lo[h:h + 1], ub[h:h + 1])
    ge, le = f >= 0, f <= 0
    lo_b = jnp.max(jnp.where(ge, b, -jnp.inf), axis=0, keepdims=True)
    hi_b = jnp.min(jnp.where(le, b, jnp.inf), axis=0, keepdims=True)
    f_lo = jnp.min(jnp.where(ge, f, jnp.inf), axis=0, keepdims=True)
    f_hi = jnp.max(jnp.where(le, f, -jnp.inf), axis=0, keepdims=True)
    den = f_lo - f_hi
    t = jnp.where(den > 0, f_lo / jnp.where(den > 0, den, 1.0), 0.0)
    nu = lo_b + t * (hi_b - lo_b)
    nu = jnp.where(lo_b == -jnp.inf, -jnp.inf, nu)
    nu = jnp.where(hi_b == jnp.inf, jnp.inf, nu)
    return jnp.clip(z - nu, lo, ub)


def _carry(x):
    """``x`` as the start of a loop carry. Under ``shard_map`` Pallas types
    a value loaded from a ref with the operands' varying mesh axes but
    the result of arithmetic with none (jax 0.9), so a carry that starts
    from a load would change type in the loop. Adding zero types it like
    the loop's results, and leaves every value but -0.0 as it was."""
    return x + 0.0


def _lane_tile(n, tile):
    """A block's lane extent: the whole ``n`` below one vreg's lanes,
    else ``tile`` (a multiple of 128)."""
    return n if n < LANES else tile


def _to_lanes(x, n, pad, fill=0.0):
    """Clusters onto the lane axis: (..., n, H) -> (..., H, n + pad) and
    (n, 1) or a scalar -> (1, n + pad); dead lanes hold ``fill``."""
    x = jnp.asarray(x)
    if x.ndim == 0:
        x = jnp.broadcast_to(x.astype(jnp.float32), (n, 1))
    x = jnp.swapaxes(x, -1, -2)
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=fill)


def _pgd_kernel(delta_ref, eta_ref, pi_ref, pow_ref, tau_ref, price_ref,
                lo_ref, ub_ref, lr_ref, temp_ref, lame_ref, out_ref, *,
                iters):
    delta = delta_ref[...].astype(jnp.float32)        # (H, TC)
    eta = eta_ref[...].astype(jnp.float32)
    pi = pi_ref[...].astype(jnp.float32)
    pow_nom = pow_ref[...].astype(jnp.float32)
    tau24 = tau_ref[...].astype(jnp.float32)          # (1, TC)
    price = price_ref[...].astype(jnp.float32)
    lo = lo_ref[...].astype(jnp.float32)
    ub = ub_ref[...].astype(jnp.float32)
    lr = lr_ref[...].astype(jnp.float32)
    temp = temp_ref[...].astype(jnp.float32)          # (1, TC) broadcast
    lambda_e = lame_ref[...].astype(jnp.float32)      # (1, TC) broadcast

    def body(i, d):
        pow_h = pow_nom + pi * d * tau24
        s = pow_h / temp
        s = s - jnp.max(s, axis=0, keepdims=True)
        e = jnp.exp(s)
        w = e / jnp.sum(e, axis=0, keepdims=True)
        grad = (lambda_e * eta + price * w) * pi * tau24
        return _project_rows(d - lr * grad, lo, ub)

    out_ref[...] = jax.lax.fori_loop(0, iters, body, _carry(delta)).astype(
        out_ref.dtype)


def _pgd_ens_kernel(delta_ref, eta_ref, pi_ref, pow_ref, tau_ref, price_ref,
                    lo_ref, ub_ref, lr_ref, temp_ref, lame_ref, risk_ref,
                    out_ref, *, iters):
    """CVaR ensemble epoch: blocks carry a (K, H, TC) member tile of
    eta/pow_nom; the member axis is reduced IN-KERNEL (per-cluster
    soft-CVaR tilt, anchored on member 0 — mirrors ref.pgd_step_ens_arrays
    op for op, so identical members collapse bitwise). Every member-axis
    value keeps its unit hour axis, (K, 1, TC), so it stays on the
    clusters' lanes."""
    delta = delta_ref[...].astype(jnp.float32)          # (H, TC)
    eta_e = eta_ref[...].astype(jnp.float32)            # (K, H, TC)
    pi = pi_ref[...].astype(jnp.float32)
    pow_e = pow_ref[...].astype(jnp.float32)            # (K, H, TC)
    tau24 = tau_ref[...].astype(jnp.float32)            # (1, TC)
    price = price_ref[...].astype(jnp.float32)
    lo = lo_ref[...].astype(jnp.float32)
    ub = ub_ref[...].astype(jnp.float32)
    lr = lr_ref[...].astype(jnp.float32)
    temp = temp_ref[...].astype(jnp.float32)            # (1, TC) broadcast
    lambda_e = lame_ref[...].astype(jnp.float32)        # (1, TC) broadcast
    risk_s = risk_ref[...].astype(jnp.float32)          # (1, TC) broadcast

    def body(i, d):
        ph = pow_e + (pi * d * tau24)[None]             # (K, H, TC)
        s = ph / temp[None]
        s = s - jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s)
        w_peak = e / jnp.sum(e, axis=1, keepdims=True)
        cost = lambda_e[None] * jnp.sum(eta_e * ph, axis=1, keepdims=True) \
            + price[None] * jnp.sum(w_peak * ph, axis=1,
                                    keepdims=True)      # (K, 1, TC)
        z = cost - cost[:1]
        dev = cost - jnp.mean(cost, axis=0, keepdims=True)
        scale = jnp.mean(jnp.abs(dev), axis=0, keepdims=True) + 1e-9
        t = risk_s[None] * z / scale
        t = t - jnp.max(t, axis=0, keepdims=True)
        et = jnp.exp(t)
        wm = et / jnp.sum(et, axis=0, keepdims=True)     # (K, 1, TC)
        eta_w = eta_e[0] + jnp.sum(wm * (eta_e - eta_e[:1]), axis=0)
        w_w = w_peak[0] + jnp.sum(wm * (w_peak - w_peak[:1]), axis=0)
        grad = (lambda_e * eta_w + price * w_w) * pi * tau24
        return _project_rows(d - lr * grad, lo, ub)

    out_ref[...] = jax.lax.fori_loop(0, iters, body, _carry(delta)).astype(
        out_ref.dtype)


def _out_shape(shape, dtype, args):
    """A kernel output's type. Under ``shard_map`` it varies over every
    mesh axis an operand varies over, which ``pallas_call`` cannot infer;
    elsewhere that set is empty."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def pgd_epoch_pallas(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr, *,
                     temp, lambda_e, iters: int, tile: int = DEFAULT_TILE,
                     interpret: bool = False):
    """All matrices (n, H); tau24/price/lr (n, 1); temp/lambda_e scalar
    (float or traced). Returns new delta (n, H)."""
    n, H = delta.shape
    tile = _lane_tile(n, tile)
    pad = (-n) % tile
    args = [_to_lanes(x, n, pad) for x in (delta, eta, pi, pow_nom, tau24,
                                           price, lo, ub, lr)]
    args += [_to_lanes(temp, n, pad, fill=1.0),   # dead lanes divide by it
             _to_lanes(lambda_e, n, pad)]
    kernel = functools.partial(_pgd_kernel, iters=iters)
    wide = pl.BlockSpec((H, tile), lambda i: (0, i))
    slim = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=((n + pad) // tile,),
        in_specs=[wide, wide, wide, wide, slim, slim, wide, wide, slim,
                  slim, slim],
        out_specs=wide,
        out_shape=_out_shape((H, n + pad), delta.dtype, args),
        interpret=interpret,
    )(*args)
    return out[:, :n].T


def _joint_kernel(d_ref, s_ref, eta_ref, pi_ref, pow_ref, tau_ref, uif_ref,
                  uifq_ref, ratio_ref, upow_ref, cap_ref, price_ref, lr_ref,
                  temp_ref, lame_ref, dout_ref, gs_ref, *, drop_limit):
    """Fused joint spatio-temporal step (mirrors ref.joint_step_arrays op
    for op, clusters on lanes): recompute the temporal bounds from the
    shifted budget tau + s, take the linearized carbon + softmax-peak
    gradient at the shifted point, project delta exactly, and emit the
    per-cluster shift gradient. The fleet-coupled s projection
    (sum_c s = 0) happens outside the cluster-tiled grid."""
    d = d_ref[...].astype(jnp.float32)               # (H, TC)
    s = s_ref[...].astype(jnp.float32)               # (1, TC)
    eta = eta_ref[...].astype(jnp.float32)
    pi = pi_ref[...].astype(jnp.float32)
    pow_nom = pow_ref[...].astype(jnp.float32)
    tau = tau_ref[...].astype(jnp.float32)           # (1, TC)
    u_if = uif_ref[...].astype(jnp.float32)
    u_if_q = uifq_ref[...].astype(jnp.float32)
    ratio = ratio_ref[...].astype(jnp.float32)
    u_pow_cap = upow_ref[...].astype(jnp.float32)    # (1, TC)
    capacity = cap_ref[...].astype(jnp.float32)      # (1, TC)
    price = price_ref[...].astype(jnp.float32)       # (1, TC)
    lr_d = lr_ref[...].astype(jnp.float32)           # (1, TC)
    temp = temp_ref[...].astype(jnp.float32)         # (1, TC) broadcast
    lambda_e = lame_ref[...].astype(jnp.float32)     # (1, TC) broadcast

    tau_s = tau + s
    t24 = jnp.clip(tau_s / 24.0, 1e-9, None)
    ub = jnp.minimum((u_pow_cap - u_if_q) / t24 - 1.0,
                     (capacity / ratio - u_if) / t24 - 1.0)
    ub = jnp.clip(ub, -drop_limit, 24.0)
    feas = (jnp.sum(ub, axis=0, keepdims=True) >= 0.0) \
        & (tau_s > 1e-6) \
        & jnp.all(ub > -drop_limit + 1e-9, axis=0, keepdims=True)
    ub = jnp.where(feas, ub, 0.0)
    # -drop_limit (0 where infeasible) and never above ub; taking it as a
    # minimum with ub lays it out row by row, as _project_rows slices it
    # (a broadcast of a (1, TC) row has no rows to slice)
    lo = jnp.minimum(jnp.where(feas, -drop_limit, 0.0), ub)

    pow_h = pow_nom + pi * (d * tau_s + s) / 24.0
    z = pow_h / temp
    z = z - jnp.max(z, axis=0, keepdims=True)
    e = jnp.exp(z)
    w = e / jnp.sum(e, axis=0, keepdims=True)
    gcoef = (lambda_e * eta + price * w) * pi
    g_d = gcoef * (tau_s / 24.0)
    g_s = jnp.sum(gcoef * (1.0 + d), axis=0, keepdims=True) / 24.0
    d2 = _project_rows(d - lr_d * g_d, lo, ub)
    dout_ref[...] = d2.astype(dout_ref.dtype)
    gs_ref[...] = g_s.astype(gs_ref.dtype)


def joint_step_pallas(delta, s, eta, pi, pow_nom, tau, u_if, u_if_q, ratio,
                      u_pow_cap, capacity, price, lr_d, *, temp, lambda_e,
                      drop_limit: float, tile: int = DEFAULT_TILE,
                      interpret: bool = False):
    """Wide operands (n, H); slim operands (n, 1); temp/lambda_e scalar
    (float or traced); drop_limit static. Returns (delta' (n, H),
    g_s (n, 1))."""
    n, H = delta.shape
    tile = _lane_tile(n, tile)
    pad = (-n) % tile
    args = [_to_lanes(x, n, pad) for x in (delta, s, eta, pi, pow_nom, tau,
                                           u_if, u_if_q)]
    args += [_to_lanes(ratio, n, pad, fill=1.0)]  # dead lanes divide by it
    args += [_to_lanes(x, n, pad) for x in (u_pow_cap, capacity, price,
                                            lr_d)]
    args += [_to_lanes(temp, n, pad, fill=1.0),   # dead lanes divide by it
             _to_lanes(lambda_e, n, pad)]
    kernel = functools.partial(_joint_kernel, drop_limit=drop_limit)
    wide = pl.BlockSpec((H, tile), lambda i: (0, i))
    slim = pl.BlockSpec((1, tile), lambda i: (0, i))
    d2, g_s = pl.pallas_call(
        kernel,
        grid=((n + pad) // tile,),
        in_specs=[wide, slim, wide, wide, wide, slim, wide, wide, wide,
                  slim, slim, slim, slim, slim, slim],
        out_specs=(wide, slim),
        out_shape=(_out_shape((H, n + pad), delta.dtype, args),
                   _out_shape((1, n + pad), jnp.float32, args)),
        interpret=interpret,
    )(*args)
    return d2[:, :n].T, g_s[:, :n].T


def pgd_epoch_ens_pallas(delta, eta_e, pi, pow_nom_e, tau24, price, lo, ub,
                         lr, *, temp, lambda_e, risk_s, iters: int,
                         tile: int = ENS_TILE, interpret: bool = False):
    """CVaR ensemble epoch. eta_e/pow_nom_e: (K, n, H) member stacks;
    the rest as in ``pgd_epoch_pallas``; ``risk_s`` scalar (float or
    traced) soft-CVaR sharpness (0 = risk-neutral). The grid tiles the
    cluster axis only — every block loads its full K-member slab into VMEM
    and reduces the member axis in-kernel (a (K, H, 128) float32 slab is
    384 KiB at K = 32, the sweep's largest)."""
    K, n, H = eta_e.shape
    tile = _lane_tile(n, tile)
    pad = (-n) % tile
    args = [_to_lanes(x, n, pad) for x in (delta, eta_e, pi, pow_nom_e,
                                           tau24, price, lo, ub, lr)]
    args += [_to_lanes(temp, n, pad, fill=1.0),   # dead lanes divide by it
             _to_lanes(lambda_e, n, pad), _to_lanes(risk_s, n, pad)]
    kernel = functools.partial(_pgd_ens_kernel, iters=iters)
    wide = pl.BlockSpec((H, tile), lambda i: (0, i))
    slim = pl.BlockSpec((1, tile), lambda i: (0, i))
    ens = pl.BlockSpec((K, H, tile), lambda i: (0, 0, i))
    out = pl.pallas_call(
        kernel,
        grid=((n + pad) // tile,),
        in_specs=[wide, ens, wide, ens, slim, slim, wide, wide, slim,
                  slim, slim, slim],
        out_specs=wide,
        out_shape=_out_shape((H, n + pad), delta.dtype, args),
        interpret=interpret,
    )(*args)
    return out[:, :n].T
