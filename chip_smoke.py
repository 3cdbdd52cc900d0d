"""Chip smoke run: the batched fleet rollout on a TPU, Pallas kernels on.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded rollout against one device

Drives the sim engine's main path, ``sim.rollout_batch`` over the staged
day step, at fleet size (256 clusters, 64 campuses, 16 grid zones, a
35-day burn-in) with the ``vcc_pgd`` kernels that the TPU backend selects,
and checks what comes out:

* main phase: the default scenario library x 4 seeds (44 rollouts) over
  14 days. Every ledger leaf is finite, and flexible work is conserved per
  cluster (served + carried queue == arrived + initial queue, as in
  tests/test_ledger_invariants.py).
* batch phase: the main batch's first 11 rollouts (the per-device batch
  of ``--four-chips``) as a batch of their own, against their rows of
  the main run. It prints every leaf that differs and checks the fleet
  totals of each rollout to 1%.
* reference phase: ``vcc.solve_vcc`` through the kernel against the jnp
  oracle at 256 clusters, to the tolerances of
  tests/test_stages_parity.py, plus conservation and bounds.
* flag phases: 3-day rollouts (2 scenarios x 2 seeds) with ``streaming``,
  ``joint_spatial``, ``mpc`` + ``streaming``, ``telemetry`` and an
  8-member CVaR ensemble.

Every program must call the Pallas kernel its path exists for
(``_pgd_kernel``, ``_joint_kernel`` or ``_pgd_ens_kernel`` as the
``kernel_name`` of a custom call in its lowering, and ``tpu_custom_call``
in the compiled HLO): that proves the jnp oracle was not used. The
compile and steady seconds printed per phase are for information only.

``--four-chips`` runs only the sharded path: the main phase's 44-rollout
batch through ``rollout_batch_sharded`` over a 4-device mesh, against
``rollout_batch`` of the whole batch on one device and of its four
11-rollout slices one after another on one device. It prints every leaf
that differs between the three, checks that the sharded batch equals the
slices bitwise, and that each rollout's fleet totals agree to 1% between
sharded and unsharded.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. A failed check
exits non-zero before it, and so does a run without a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

KERNEL = "tpu_custom_call"
# the Pallas kernel each program must call (kernel_name in its lowering)
PGD, ENS, JOINT = "_pgd_kernel", "_pgd_ens_kernel", "_joint_kernel"
FLEET = dict(n_clusters=256, n_campuses=64, n_zones=16, hist_days=35)
MAIN_DAYS, MAIN_SEEDS = 14, (0, 1, 2, 3)
FLAG_DAYS, FLAG_SEEDS = 3, (0, 1)
# On the TPU a rollout's numbers depend on the extent of the batch it runs
# in (the day step's forecast reductions round differently), and the SLO
# and admission decisions carry that to percent gaps in single clusters.
# Across batch extents and devices the contract is each rollout's fleet
# totals, to this relative tolerance.
FLEET_TOTAL_RTOL = 1e-2


class CheckFailed(Exception):
    pass


def check(ok, msg):
    if not ok:
        raise CheckFailed(msg)


def compile_with_kernel(jitted, *args, name, kernels):
    """Lower + compile. The lowering must call each named Pallas kernel,
    and the compiled HLO must still hold a kernel call."""
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    found = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    missing = sorted(set(kernels) - found)
    check(not missing, f"{name}: no call to {missing} in the program "
          f"(found {sorted(found)}; the jnp oracle ran)")
    check(KERNEL in compiled.as_text(),
          f"{name}: no {KERNEL} in the compiled HLO (jnp oracle ran)")
    return compiled, compile_s


def run_timed(compiled, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def check_finite(tree, name):
    import jax
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        if a.dtype.kind == "f":
            check(np.isfinite(a).all(),
                  f"{name}: non-finite values in {jax.tree_util.keystr(path)}")


def check_conservation(cfg, batch, state, led, name):
    """served + carried queue == arrived + initial queue, per rollout and
    cluster, for the shaped run and its unshaped counterfactual."""
    import jax
    from repro.sim import make_init
    state0 = jax.jit(jax.vmap(make_init(cfg)))(batch)
    arrived = np.asarray(led.arrived, np.float64)
    for tag, served, q0, q1 in (
            ("shaped", led.served, state0.queue, state.queue),
            ("counterfactual", led.cf_served, state0.cf_queue,
             state.cf_queue)):
        balance = np.asarray(q0, np.float64) + arrived
        spent = np.asarray(served, np.float64) + np.asarray(q1, np.float64)
        err = np.abs(spent - balance) - (1e-3 + 1e-4 * np.abs(balance))
        check(err.max() <= 0.0,
              f"{name}: {tag} flex CPU-h not conserved (worst excess "
              f"{err.max():.3g})")


def rollout_phase(name, cfg, scenarios, seeds, days, kernels=(PGD,)):
    from repro.sim import build_batch, rollout_batch
    batch = build_batch(cfg, scenarios, list(seeds), days)
    compiled, compile_s = compile_with_kernel(
        rollout_batch(cfg, days), batch, name=name, kernels=kernels)
    out, steady_s = run_timed(compiled, batch)
    check_finite(out[1], name)
    check_finite(out[2], name)
    n = len(scenarios) * len(seeds)
    print(f"{name}: {n} rollouts x {days} days x {cfg.n_clusters} clusters"
          f" | compile {compile_s:.2f} s | steady {steady_s:.3f} s")
    return batch, out


def leaf_diffs(ref, out, name):
    """Print every leaf of ``out`` that is not bitwise equal to ``ref``;
    return how many differ."""
    import jax
    n_diff = 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ref)[0],
                            jax.tree.leaves(out)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if not np.array_equal(a, b):
            n_diff += 1
            d = np.abs(a - b).max()
            print(f"  differs: {jax.tree_util.keystr(path)} max abs {d:.6g} "
                  f"(relative to the leaf's max {d / np.abs(a).max():.3g})")
    print(f"{name}: {n_diff} of {len(jax.tree.leaves(ref))} leaves differ")
    return n_diff


def check_fleet_totals(led_ref, led, name):
    """Each rollout's fleet totals agree to FLEET_TOTAL_RTOL."""
    for field in ("carbon_kg", "kwh", "served", "cf_carbon_kg"):
        u = np.asarray(getattr(led_ref, field), np.float64).sum(axis=-1)
        v = np.asarray(getattr(led, field), np.float64).sum(axis=-1)
        rel = float((np.abs(v - u) / np.abs(u)).max())
        print(f"  {name} fleet total {field}: max relative gap over "
              f"rollouts {rel:.3g}")
        check(rel <= FLEET_TOTAL_RTOL,
              f"{name}: fleet total {field} off by {rel:.3g} relative")


def slices(batch, k):
    """The batch cut into consecutive batches of ``k`` rollouts."""
    import jax
    n = len(jax.tree.leaves(batch)[0])
    return [jax.tree.map(lambda a: a[i:i + k], batch)
            for i in range(0, n, k)]


def reference_phase(n):
    """Kernel vs jnp oracle inside ``solve_vcc`` on the chip."""
    import jax
    import jax.numpy as jnp
    from repro.core import vcc
    from repro.core.admission import hour_sum
    p = vcc.synthetic_problem(n, seed=7, n_campuses=FLEET["n_campuses"])

    def solve(use_pallas):
        return jax.jit(lambda q: vcc.solve_vcc(
            q, inner_iters=40, outer_iters=4, use_pallas=use_pallas))

    compiled, compile_s = compile_with_kernel(solve(True), p,
                                              name="reference",
                                              kernels=(PGD,))
    ker, steady_s = run_timed(compiled, p)
    ref = solve(False)(p)
    # tolerances of tests/test_stages_parity.py
    np.testing.assert_allclose(np.asarray(ker.delta), np.asarray(ref.delta),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ker.vcc), np.asarray(ref.vcc),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ker.shaped),
                                  np.asarray(ref.shaped))
    np.testing.assert_allclose(float(ker.objective), float(ref.objective),
                               rtol=1e-5)
    lo, ub, feasible = vcc.delta_bounds(p)
    lo = jnp.where(feasible[:, None], lo, 0.0)
    ub = jnp.where(feasible[:, None], ub, 0.0)
    d = np.asarray(ker.delta)
    resid = float(np.abs(np.asarray(hour_sum(ker.delta))).max())
    check(resid <= 1e-4, f"reference: sum_h delta = {resid:.3g} != 0")
    check((d >= np.asarray(lo) - 1e-6).all()
          and (d <= np.asarray(ub) + 1e-6).all(),
          "reference: delta outside its bounds")
    diff = float(np.abs(d - np.asarray(ref.delta)).max())
    print(f"reference: solve_vcc kernel vs oracle at {n} clusters | max "
          f"|delta diff| {diff:.3g} | max |sum_h delta| {resid:.3g} | "
          f"compile {compile_s:.2f} s | steady {steady_s:.3f} s")


def one_chip():
    from repro.sim import (SimConfig, default_library, forecast_bust_library,
                           mobility_sweep_library, risk_sweep_library,
                           rollout_batch)
    cfg = SimConfig(**FLEET)
    batch, out = rollout_phase("main", cfg, default_library(MAIN_DAYS),
                               MAIN_SEEDS, MAIN_DAYS)
    state, led, _ = out
    check_conservation(cfg, batch, state, led, "main")
    print("main: ledger finite, flex work conserved")
    batch_size_phase(cfg, batch, out, rollout_batch(cfg, MAIN_DAYS))
    reference_phase(FLEET["n_clusters"])
    d = FLAG_DAYS
    for name, flags, scenarios, kernels in (
            ("streaming", dict(streaming=True), default_library(d)[:2],
             (PGD,)),
            ("joint_spatial", dict(joint_spatial=True),
             mobility_sweep_library(d)[-2:], (PGD, JOINT)),
            ("mpc+streaming", dict(mpc=True, streaming=True),
             forecast_bust_library(d)[:2], (PGD,)),
            ("telemetry", dict(telemetry=True), default_library(d)[:2],
             (PGD,)),
            ("n_members=8", dict(n_members=8), risk_sweep_library(d)[:2],
             (ENS,))):
        rollout_phase(name, dataclasses.replace(cfg, **flags), scenarios,
                      FLAG_SEEDS, d, kernels)


def batch_size_phase(cfg, batch, out, run):
    """The main batch's first quarter as a batch of its own, against its
    rows of the whole batch: the per-device batch of ``--four-chips``,
    compiled and run on one chip."""
    import jax
    part = slices(batch, len(jax.tree.leaves(batch)[0]) // 4)[0]
    k = len(jax.tree.leaves(part)[0])
    compiled, compile_s = compile_with_kernel(run, part, name="batch",
                                              kernels=(PGD,))
    got, steady_s = run_timed(compiled, part)
    rows = jax.tree.map(lambda a: np.asarray(a)[:k], out)
    name = f"batch {k} vs its rows of batch {len(jax.tree.leaves(batch)[0])}"
    leaf_diffs(rows, got, name)
    check_fleet_totals(rows[1], got[1], name)
    print(f"{name} | compile {compile_s:.2f} s | steady {steady_s:.3f} s")


def four_chips():
    """The main batch sharded over 4 devices, against the unsharded batch
    on one device and against its four per-device slices run one after
    another on one device."""
    import jax
    from repro.launch.mesh import make_batch_mesh
    from repro.sim import (SimConfig, build_batch, default_library,
                           rollout_batch, rollout_batch_sharded)
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = SimConfig(**FLEET)
    batch = build_batch(cfg, default_library(MAIN_DAYS), list(MAIN_SEEDS),
                        MAIN_DAYS)
    n = len(jax.tree.leaves(batch)[0])
    outs = {}
    for name, run in (
            ("sharded", jax.jit(rollout_batch_sharded(
                cfg, MAIN_DAYS, make_batch_mesh(4)))),
            ("unsharded", rollout_batch(cfg, MAIN_DAYS))):
        compiled, compile_s = compile_with_kernel(run, batch, name=name,
                                                  kernels=(PGD,))
        outs[name], steady_s = run_timed(compiled, batch)
        print(f"{name}: {n} rollouts x {MAIN_DAYS} days | compile "
              f"{compile_s:.2f} s | steady {steady_s:.3f} s")
    parts = slices(batch, n // 4)
    compiled, compile_s = compile_with_kernel(
        rollout_batch(cfg, MAIN_DAYS), parts[0], name="per-slice",
        kernels=(PGD,))
    per = [run_timed(compiled, part)[0] for part in parts]
    outs["per-slice"] = jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *per)
    print(f"per-slice: {len(parts)} x {n // 4} rollouts on one device | "
          f"compile {compile_s:.2f} s")
    single, sharded = outs["unsharded"], outs["sharded"]
    check_finite(sharded, "sharded")
    leaf_diffs(outs["per-slice"], single, "unsharded vs per-slice")
    # sharding adds no rounding of its own: each device runs the program
    # of its 11-rollout slice
    check(leaf_diffs(outs["per-slice"], sharded, "sharded vs per-slice")
          == 0, "sharded batch is not bitwise equal to its slices run on "
          "one device")
    leaf_diffs(single, sharded, "sharded vs unsharded")
    check_fleet_totals(single[1], sharded[1], "sharded vs unsharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded-vs-unsharded check")
    args = ap.parse_args(argv)
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"FAIL: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device['platform']} {device['kind']} x "
          f"{device['count']} | jax {jax.__version__}")
    if dev.platform != "tpu":
        print("FAIL: no TPU found", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    try:
        four_chips() if args.four_chips else one_chip()
    except (CheckFailed, AssertionError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
