"""Dispatching wrapper for the fused VCC PGD epoch.

Same convention as the other kernel packages (``flash_attention``,
``linear_scan``): ``use_pallas=None`` auto-selects the Pallas kernel on TPU
and the jnp oracle elsewhere; ``interpret=True`` forces the kernel through
the Pallas interpreter (CPU parity tests). ``core.vcc.solve_vcc`` routes its
inner loop here for BOTH the legacy fleet path and the sim engine.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import tpu_available
from repro.kernels.vcc_pgd import ref as _ref


def pgd_epoch(prob, delta, mu, lo, ub, lr_eff, temp, iters,
              use_pallas: Optional[bool] = None, interpret: bool = False):
    """Adapter from a repro.core.vcc.VCCProblem to the kernel layout.

    ``temp`` and ``prob.lambda_e`` may be traced scalars (the day cycle
    computes temp from the problem inside jit/vmap). Problems carrying
    ensemble axes (``prob.eta_ens``/``prob.pow_nom_ens`` not None, K > 1)
    route to the CVaR ensemble epoch, which reduces the member axis
    in-kernel; plain problems keep the exact legacy epoch graph.
    """
    tau24 = (prob.tau[:, None] / 24.0).astype(jnp.float32)
    price = (prob.lambda_p + mu[prob.campus])[:, None].astype(jnp.float32)
    lr = jnp.broadcast_to(jnp.asarray(lr_eff, jnp.float32),
                          (delta.shape[0], 1)) \
        if jnp.ndim(lr_eff) < 2 else lr_eff.astype(jnp.float32)
    kw = dict(temp=temp, lambda_e=prob.lambda_e, iters=int(iters))
    if use_pallas is None:
        use_pallas = tpu_available()
    if getattr(prob, "eta_ens", None) is not None:
        kw["risk_s"] = _ref.cvar_sharpness(prob.risk_beta)
        if use_pallas or interpret:
            from repro.kernels.vcc_pgd import kernel as _kernel
            return _kernel.pgd_epoch_ens_pallas(
                delta, prob.eta_ens, prob.pi, prob.pow_nom_ens, tau24,
                price, lo, ub, lr, interpret=interpret, **kw)
        return _ref.pgd_epoch_ens_ref(delta, prob.eta_ens, prob.pi,
                                      prob.pow_nom_ens, tau24, price, lo,
                                      ub, lr, **kw)
    if use_pallas or interpret:
        from repro.kernels.vcc_pgd import kernel as _kernel
        return _kernel.pgd_epoch_pallas(
            delta, prob.eta, prob.pi, prob.pow_nom, tau24, price, lo, ub,
            lr, interpret=interpret, **kw)
    return _ref.pgd_epoch_ref(delta, prob.eta, prob.pi, prob.pow_nom, tau24,
                              price, lo, ub, lr, **kw)


def joint_step(prob, delta, s, mu, lr_d, temp,
               use_pallas: Optional[bool] = None, interpret: bool = False):
    """One fused JOINT spatio-temporal step for a VCCProblem: temporal
    bounds recomputed from the shifted budget tau + s, delta gradient +
    exact projection, and the per-cluster shift gradient g_s (n, 1) as a
    second output (the fleet-coupled s projection happens in
    ``core.solver.joint_epochs``). Same dispatch convention as
    ``pgd_epoch``; ``temp``/``prob.lambda_e`` may be traced scalars."""
    f32 = jnp.float32
    n = delta.shape[0]
    price = (prob.lambda_p + mu[prob.campus])[:, None].astype(f32)
    lr = jnp.broadcast_to(jnp.asarray(lr_d, f32), (n, 1)) \
        if jnp.ndim(lr_d) < 2 else lr_d.astype(f32)
    sv = s[:, None].astype(f32)
    tau = prob.tau[:, None].astype(f32)
    u_pow_cap = prob.u_pow_cap[:, None].astype(f32)
    capacity = prob.capacity[:, None].astype(f32)
    kw = dict(temp=temp, lambda_e=prob.lambda_e,
              drop_limit=float(prob.drop_limit))
    if use_pallas is None:
        use_pallas = tpu_available()
    if use_pallas or interpret:
        from repro.kernels.vcc_pgd import kernel as _kernel
        return _kernel.joint_step_pallas(
            delta, sv, prob.eta, prob.pi, prob.pow_nom, tau, prob.u_if,
            prob.u_if_q, prob.ratio, u_pow_cap, capacity, price, lr,
            interpret=interpret, **kw)
    return _ref.joint_step_arrays(
        delta, sv, prob.eta, prob.pi, prob.pow_nom, tau, prob.u_if,
        prob.u_if_q, prob.ratio, u_pow_cap, capacity, price, lr, **kw)
