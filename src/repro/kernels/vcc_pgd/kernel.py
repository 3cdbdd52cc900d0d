"""Pallas TPU kernel: fused VCC projected-gradient epoch.

Tiling: grid = (n_clusters / TC,); each step loads a (TC, 24) cluster tile
(delta, eta, pi, pow_nom, lo, ub + per-cluster scalars) into VMEM and runs
the FULL inner optimization epoch — ``iters`` x [gradient of the linearized
carbon+peak objective → 50-step bisection projection onto the conservation
simplex slab] — without touching HBM between iterations. The day-ahead
optimizer calls this once per dual-ascent round for the whole fleet
(~O(100k) clusters x 24 h), so HBM round-trips per PGD iteration are the
hotspot being removed.

``temp`` and ``lambda_e`` ride in as broadcast (n, 1) operands rather than
compile-time constants: the day cycle derives ``temp`` from the problem
inside jit, so they may be traced scalars.

Validated with interpret=True against ref.pgd_epoch_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 256


def _project_rows(z, lo, ub, proj_iters):
    """Shared in-VMEM bisection projection onto {sum_h = 0} ∩ [lo, ub]
    (same math as ref.project_row; rows independent). The ONE copy both
    kernels call — the identical-members bitwise contract between the
    plain and ensemble epochs rides on them projecting identically."""
    a = jnp.min(z, 1) - jnp.max(ub, 1)
    b = jnp.max(z, 1) - jnp.min(lo, 1)

    def pbody(i, ab):
        a, b = ab
        m = 0.5 * (a + b)
        f = jnp.sum(jnp.clip(z - m[:, None], lo, ub), axis=1)
        a = jnp.where(f > 0, m, a)
        b = jnp.where(f > 0, b, m)
        return a, b

    a, b = jax.lax.fori_loop(0, proj_iters, pbody, (a, b))
    nu = 0.5 * (a + b)
    return jnp.clip(z - nu[:, None], lo, ub)


def _carry(x):
    """``x`` as the start of a loop carry. Under ``shard_map`` Pallas types
    a value loaded from a ref with the operands' varying mesh axes but
    the result of arithmetic with none (jax 0.9), so a carry that starts
    from a load would change type in the loop. Adding zero types it like
    the loop's results, and leaves every value but -0.0 as it was."""
    return x + 0.0


def _pgd_kernel(delta_ref, eta_ref, pi_ref, pow_ref, tau_ref, price_ref,
                lo_ref, ub_ref, lr_ref, temp_ref, lame_ref, out_ref, *,
                iters, proj_iters):
    delta = delta_ref[...].astype(jnp.float32)
    eta = eta_ref[...].astype(jnp.float32)
    pi = pi_ref[...].astype(jnp.float32)
    pow_nom = pow_ref[...].astype(jnp.float32)
    tau24 = tau_ref[...].astype(jnp.float32)
    price = price_ref[...].astype(jnp.float32)
    lo = lo_ref[...].astype(jnp.float32)
    ub = ub_ref[...].astype(jnp.float32)
    lr = lr_ref[...].astype(jnp.float32)
    temp = temp_ref[...].astype(jnp.float32)          # (TC, 1) broadcast
    lambda_e = lame_ref[...].astype(jnp.float32)      # (TC, 1) broadcast

    def body(i, d):
        pow_h = pow_nom + pi * d * tau24
        s = pow_h / temp
        s = s - jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s)
        w = e / jnp.sum(e, axis=1, keepdims=True)
        grad = (lambda_e * eta + price * w) * pi * tau24
        return _project_rows(d - lr * grad, lo, ub, proj_iters)

    out_ref[...] = jax.lax.fori_loop(0, iters, body, _carry(delta)).astype(
        out_ref.dtype)


def _pgd_ens_kernel(delta_ref, eta_ref, pi_ref, pow_ref, tau_ref, price_ref,
                    lo_ref, ub_ref, lr_ref, temp_ref, lame_ref, risk_ref,
                    out_ref, *, iters, proj_iters):
    """CVaR ensemble epoch: blocks carry a (K, TC, H) member tile of
    eta/pow_nom; the member axis is reduced IN-KERNEL (per-cluster
    soft-CVaR tilt, anchored on member 0 — mirrors ref.pgd_step_ens_arrays
    op for op, so identical members collapse bitwise). Every member-axis
    value keeps its trailing unit hour axis, (K, TC, 1): Mosaic lowers no
    (TC, 1) -> (TC,) relayout of a kernel value."""
    delta = delta_ref[...].astype(jnp.float32)          # (TC, H)
    eta_e = eta_ref[...].astype(jnp.float32)            # (K, TC, H)
    pi = pi_ref[...].astype(jnp.float32)
    pow_e = pow_ref[...].astype(jnp.float32)            # (K, TC, H)
    tau24 = tau_ref[...].astype(jnp.float32)            # (TC, 1)
    price = price_ref[...].astype(jnp.float32)
    lo = lo_ref[...].astype(jnp.float32)
    ub = ub_ref[...].astype(jnp.float32)
    lr = lr_ref[...].astype(jnp.float32)
    temp = temp_ref[...].astype(jnp.float32)            # (TC, 1) broadcast
    lambda_e = lame_ref[...].astype(jnp.float32)        # (TC, 1) broadcast
    risk_s = risk_ref[...].astype(jnp.float32)          # (TC, 1) broadcast

    def body(i, d):
        ph = pow_e + (pi * d * tau24)[None]             # (K, TC, H)
        s = ph / temp[None]
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        w_peak = e / jnp.sum(e, axis=-1, keepdims=True)
        cost = lambda_e[None] * jnp.sum(eta_e * ph, axis=-1, keepdims=True) \
            + price[None] * jnp.sum(w_peak * ph, axis=-1,
                                    keepdims=True)      # (K, TC, 1)
        z = cost - cost[:1]
        dev = cost - jnp.mean(cost, axis=0, keepdims=True)
        scale = jnp.mean(jnp.abs(dev), axis=0, keepdims=True) + 1e-9
        t = risk_s[None] * z / scale
        t = t - jnp.max(t, axis=0, keepdims=True)
        et = jnp.exp(t)
        wm = et / jnp.sum(et, axis=0, keepdims=True)     # (K, TC, 1)
        eta_w = eta_e[0] + jnp.sum(wm * (eta_e - eta_e[:1]), axis=0)
        w_w = w_peak[0] + jnp.sum(wm * (w_peak - w_peak[:1]), axis=0)
        grad = (lambda_e * eta_w + price * w_w) * pi * tau24
        return _project_rows(d - lr * grad, lo, ub, proj_iters)

    out_ref[...] = jax.lax.fori_loop(0, iters, body, _carry(delta)).astype(
        out_ref.dtype)


def _out_shape(shape, dtype, args):
    """A kernel output's type. Under ``shard_map`` it varies over every
    mesh axis an operand varies over, which ``pallas_call`` cannot infer;
    elsewhere that set is empty."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def pgd_epoch_pallas(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr, *,
                     temp, lambda_e, iters: int, proj_iters: int = 50,
                     tile: int = DEFAULT_TILE, interpret: bool = False):
    """All matrices (n, H); tau24/price/lr (n, 1); temp/lambda_e scalar
    (float or traced). Returns new delta."""
    n, H = delta.shape
    tile = min(tile, n)
    pad = (-n) % tile

    def p2(x):
        return jnp.pad(x, ((0, pad), (0, 0)))

    temp_a = jnp.broadcast_to(jnp.asarray(temp, jnp.float32), (n, 1))
    lame_a = jnp.broadcast_to(jnp.asarray(lambda_e, jnp.float32), (n, 1))
    # pad temp with ones: the body divides by it in dead padded rows
    temp_a = jnp.pad(temp_a, ((0, pad), (0, 0)), constant_values=1.0)
    args = [p2(x) for x in (delta, eta, pi, pow_nom, tau24, price, lo, ub,
                            lr)] + [temp_a, p2(lame_a)]
    nt = (n + pad) // tile
    kernel = functools.partial(_pgd_kernel, iters=iters,
                               proj_iters=proj_iters)
    wide = pl.BlockSpec((tile, H), lambda i: (i, 0))
    slim = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[wide, wide, wide, wide, slim, slim, wide, wide, slim,
                  slim, slim],
        out_specs=wide,
        out_shape=_out_shape((n + pad, H), delta.dtype, args),
        interpret=interpret,
    )(*args)
    return out[:n]


def _joint_kernel(d_ref, s_ref, eta_ref, pi_ref, pow_ref, tau_ref, uif_ref,
                  uifq_ref, ratio_ref, upow_ref, cap_ref, price_ref, lr_ref,
                  temp_ref, lame_ref, dout_ref, gs_ref, *, drop_limit,
                  proj_iters):
    """Fused joint spatio-temporal step (mirrors ref.joint_step_arrays op
    for op): recompute the temporal bounds from the shifted budget
    tau + s, take the linearized carbon + softmax-peak gradient at the
    shifted point, project delta exactly, and emit the per-cluster shift
    gradient. The fleet-coupled s projection (sum_c s = 0) happens
    outside the cluster-tiled grid."""
    d = d_ref[...].astype(jnp.float32)               # (TC, H)
    s = s_ref[...].astype(jnp.float32)               # (TC, 1)
    eta = eta_ref[...].astype(jnp.float32)
    pi = pi_ref[...].astype(jnp.float32)
    pow_nom = pow_ref[...].astype(jnp.float32)
    tau = tau_ref[...].astype(jnp.float32)           # (TC, 1)
    u_if = uif_ref[...].astype(jnp.float32)
    u_if_q = uifq_ref[...].astype(jnp.float32)
    ratio = ratio_ref[...].astype(jnp.float32)
    u_pow_cap = upow_ref[...].astype(jnp.float32)    # (TC, 1)
    capacity = cap_ref[...].astype(jnp.float32)      # (TC, 1)
    price = price_ref[...].astype(jnp.float32)       # (TC, 1)
    lr_d = lr_ref[...].astype(jnp.float32)           # (TC, 1)
    temp = temp_ref[...].astype(jnp.float32)         # (TC, 1) broadcast
    lambda_e = lame_ref[...].astype(jnp.float32)     # (TC, 1) broadcast

    tau_s = tau + s
    t24 = jnp.clip(tau_s / 24.0, 1e-9, None)
    ub = jnp.minimum((u_pow_cap - u_if_q) / t24 - 1.0,
                     (capacity / ratio - u_if) / t24 - 1.0)
    ub = jnp.clip(ub, -drop_limit, 24.0)
    feas = (jnp.sum(ub, axis=1, keepdims=True) >= 0.0) \
        & (tau_s > 1e-6) \
        & jnp.all(ub > -drop_limit + 1e-9, axis=1, keepdims=True)
    lo = jnp.where(feas, jnp.full_like(ub, -drop_limit), 0.0)
    ub = jnp.where(feas, ub, 0.0)

    pow_h = pow_nom + pi * (d * tau_s + s) / 24.0
    z = pow_h / temp
    z = z - jnp.max(z, axis=1, keepdims=True)
    e = jnp.exp(z)
    w = e / jnp.sum(e, axis=1, keepdims=True)
    gcoef = (lambda_e * eta + price * w) * pi
    g_d = gcoef * (tau_s / 24.0)
    g_s = jnp.sum(gcoef * (1.0 + d), axis=1, keepdims=True) / 24.0
    d2 = _project_rows(d - lr_d * g_d, lo, ub, proj_iters)
    dout_ref[...] = d2.astype(dout_ref.dtype)
    gs_ref[...] = g_s.astype(gs_ref.dtype)


def joint_step_pallas(delta, s, eta, pi, pow_nom, tau, u_if, u_if_q, ratio,
                      u_pow_cap, capacity, price, lr_d, *, temp, lambda_e,
                      drop_limit: float, proj_iters: int = 50,
                      tile: int = DEFAULT_TILE, interpret: bool = False):
    """Wide operands (n, H); slim operands (n, 1); temp/lambda_e scalar
    (float or traced); drop_limit static. Returns (delta', g_s (n, 1))."""
    n, H = delta.shape
    tile = min(tile, n)
    pad = (-n) % tile

    def p2(x, fill=0.0):
        return jnp.pad(x, ((0, pad), (0, 0)), constant_values=fill)

    def scal(v, fill=0.0):
        a = jnp.broadcast_to(jnp.asarray(v, jnp.float32), (n, 1))
        return jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)

    args = [p2(delta), p2(s), p2(eta), p2(pi), p2(pow_nom), p2(tau),
            p2(u_if), p2(u_if_q),
            p2(ratio, fill=1.0),       # dead rows divide by ratio
            p2(u_pow_cap), p2(capacity), p2(price), p2(lr_d),
            scal(temp, fill=1.0),      # dead rows divide by temp
            scal(lambda_e)]
    nt = (n + pad) // tile
    kernel = functools.partial(_joint_kernel, drop_limit=drop_limit,
                               proj_iters=proj_iters)
    wide = pl.BlockSpec((tile, H), lambda i: (i, 0))
    slim = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    d2, g_s = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[wide, slim, wide, wide, wide, slim, wide, wide, wide,
                  slim, slim, slim, slim, slim, slim],
        out_specs=(wide, slim),
        out_shape=(_out_shape((n + pad, H), delta.dtype, args),
                   _out_shape((n + pad, 1), jnp.float32, args)),
        interpret=interpret,
    )(*args)
    return d2[:n], g_s[:n]


ENS_TILE = 64     # smaller cluster tile: each block also carries K members


def pgd_epoch_ens_pallas(delta, eta_e, pi, pow_nom_e, tau24, price, lo, ub,
                         lr, *, temp, lambda_e, risk_s, iters: int,
                         proj_iters: int = 50, tile: int = ENS_TILE,
                         interpret: bool = False):
    """CVaR ensemble epoch. eta_e/pow_nom_e: (K, n, H) member stacks;
    the rest as in ``pgd_epoch_pallas``; ``risk_s`` scalar (float or
    traced) soft-CVaR sharpness (0 = risk-neutral). The grid tiles the
    cluster axis only — every block loads its full K-member slab into VMEM
    and reduces the member axis in-kernel (K x (tile, H) fits VMEM for the
    sweep sizes K <= 32, tile = 64)."""
    K, n, H = eta_e.shape
    tile = min(tile, n)
    pad = (-n) % tile

    def p2(x):
        return jnp.pad(x, ((0, pad), (0, 0)))

    def p3(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))

    def scal(v, fill=0.0):
        a = jnp.broadcast_to(jnp.asarray(v, jnp.float32), (n, 1))
        return jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)

    args = [p2(delta), p3(eta_e), p2(pi), p3(pow_nom_e), p2(tau24),
            p2(price), p2(lo), p2(ub), p2(lr),
            scal(temp, fill=1.0),      # body divides by temp in dead rows
            scal(lambda_e), scal(risk_s)]
    nt = (n + pad) // tile
    kernel = functools.partial(_pgd_ens_kernel, iters=iters,
                               proj_iters=proj_iters)
    wide = pl.BlockSpec((tile, H), lambda i: (i, 0))
    slim = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    ens = pl.BlockSpec((K, tile, H), lambda i: (0, i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[wide, ens, wide, ens, slim, slim, wide, wide, slim,
                  slim, slim, slim],
        out_specs=wide,
        out_shape=_out_shape((n + pad, H), delta.dtype, args),
        interpret=interpret,
    )(*args)
    return out[:n]
