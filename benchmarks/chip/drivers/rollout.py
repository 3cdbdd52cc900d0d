"""Rollout traffic: the batched fleet rollout (``sim.rollout_batch``, or
``sim.rollout_batch_sharded`` over the cell's chips), one
(scenario x seed) batch per call, call after call.

Consecutive calls alternate between ``distinct_batches`` batches whose
rollout seeds are derived from the run seed; every batch has the same
scenarios, sizes and horizon, so every seed gives the same work.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.chip import reference, traffic

LEDGER = ("carbon_kg", "kwh", "served", "cf_carbon_kg", "cf_kwh")


class Driver:
    def __init__(self, h):
        self.h = h
        self.fleet = h.config["fleet"]
        self.t = h.traffic
        self.days = self.t["days"]
        self.calls = []

    # ----------------------------------------------------------- set-up
    def inputs(self):
        t = self.t
        self.batches = []
        for j in range(t["distinct_batches"]):
            seeds = traffic.sub_seeds(self.h.seed, j,
                                      t["seeds_per_scenario"])
            self.batches.append(traffic.rollout_batch(
                self.fleet, t["scenarios"], seeds, self.days))
        self.rows = len(t["scenarios"]) * t["seeds_per_scenario"]
        jax.block_until_ready(self.batches)

    def setup(self):
        from repro.sim import SimConfig, SimParams, rollout_batch
        from repro.sim import rollout_batch_sharded
        h = self.h
        t0 = time.perf_counter()
        self.inputs()
        t1 = time.perf_counter()
        self.cfg = SimConfig(**self.fleet)

        def params(d):
            return SimParams(
                **{k: d[k] for k in SimParams._fields if k in d})

        self.params = [params(b) for b in self.batches]
        if h.chips > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.launch.mesh import make_batch_mesh
            mesh = make_batch_mesh(h.chips)
            run = rollout_batch_sharded(self.cfg, self.days, mesh)
            put = NamedSharding(mesh, P("batch"))
            self.params = [jax.device_put(p, put) for p in self.params]
        else:
            run = rollout_batch(self.cfg, self.days)
        jax.block_until_ready(self.params)
        t2 = time.perf_counter()
        self.run = h.compile(jax.jit(run), self.params[0])
        t3 = time.perf_counter()
        jax.block_until_ready(self.run(self.params[0]))
        self.setup_parts = {"inputs_s": t1 - t0, "compile_s": t3 - t2,
                            "warm_s": time.perf_counter() - t3}

    # ----------------------------------------------------------- window
    def window(self, seconds, span):
        self.calls = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            j = len(self.calls) % len(self.params)
            with span("call"):
                state, led, _ = self.run(self.params[j])
                jax.block_until_ready(led)
            self.calls.append((j, led, state))
        elapsed = time.perf_counter() - t0
        fleet_days = len(self.calls) * self.rows * self.days
        return {"elapsed": elapsed, "attempted": len(self.calls) * self.rows,
                "metrics": {"fleet_days_per_s": fleet_days / elapsed},
                "work": {"fleet_days": fleet_days,
                         "epoch_rows": self.rows // self.h.chips
                         * self.fleet["n_clusters"]},
                "summary": {"calls": len(self.calls),
                            "call_s": elapsed / max(len(self.calls), 1)}}

    def failed(self):
        bad = 0
        for _, led, _ in self.calls:
            finite = np.ones(self.rows, bool)
            for leaf in jax.tree.leaves(led):
                a = np.asarray(leaf).reshape(self.rows, -1)
                finite &= np.isfinite(a).all(axis=1)
            bad += int((~finite).sum())
        return bad

    # ------------------------------------------------------------ check
    def sample(self):
        """The call to check and one rollout from each quarter of its
        batch (each quarter is one device's slice on four chips), drawn
        from the run seed."""
        rng = np.random.default_rng(traffic.sub_seeds(self.h.seed, 99, 1))
        c = int(rng.integers(len(self.calls)))
        q = self.rows // 4
        rows = [int(i * q + rng.integers(q)) for i in range(4)]
        return c, rows

    def program_answers(self, c, rows):
        """The sampled rollouts' ledger totals and final backlog, as the
        timed calls produced them; the rest is freed."""
        j, led, state = self.calls[c]
        out = {k: np.asarray(getattr(led, k))[rows]
               for k in LEDGER + ("arrived",)}
        out["queue_end"] = np.asarray(state.queue)[rows]
        hd = state.hist_usage.shape[2]
        out["usage"] = np.asarray(state.hist_usage[:, :, hd - self.days:])[
            rows]
        self.calls = []
        self.run = None
        self.params = None
        return j, out

    def reference(self, j, rows, dtype):
        sub = jax.tree.map(lambda a: a[np.asarray(rows)], self.batches[j])
        fn = jax.jit(jax.vmap(lambda r: reference.simulate(
            r, self.fleet, self.h.config["solver"], self.days, dtype)))
        return {k: np.asarray(v, np.float64) for k, v in fn(sub).items()}

    def compare(self, got, want):
        """The compared numbers of the sampled rollouts:

        * ``ledger_gap``: the widest relative gap of a fleet total
          against the reference's;
        * ``usage_gap_median``: per cluster, the relative L1 gap of its
          hourly usage over the rollout's days, at the median cluster.
          The widest cluster (``usage_gap_max``, reported, not compared)
          is set by SLO pauses that flip on rounding and send single
          clusters apart; the median stays at the plans' rounding;
        * ``flex_conservation``: how far the worst cluster's flexible
          work is from conserved beyond the configuration's tolerance
          (served + backlog left == arrived + backlog burned in).
        """
        u = np.asarray(got["usage"], np.float64)
        w = np.asarray(want["usage"], np.float64)
        per = np.abs(u - w).sum(axis=(-1, -2)) / np.abs(w).sum(axis=(-1, -2))
        if not np.isfinite(per).all():
            per = np.full_like(per, np.inf)
        self.info = {"usage_gap_max": float(per.max())}
        gap = 0.0
        for k in LEDGER:
            u = np.asarray(want[k], np.float64).sum(axis=-1)
            v = np.asarray(got[k], np.float64).sum(axis=-1)
            gap = max(gap, float(np.max(np.abs(v - u) / np.abs(u))))
        tol = self.h.config["guarantees"]["flex_conservation"]
        q0 = np.asarray(want["queue0"], np.float64)
        balance = q0 + np.asarray(got["arrived"], np.float64)
        spent = np.asarray(got["served"], np.float64) \
            + np.asarray(got["queue_end"], np.float64)
        excess = np.abs(spent - balance) - (tol["abs"] + tol["rel"]
                                            * np.abs(balance))
        if not np.isfinite(excess).all():
            excess = np.full_like(excess, np.inf)
        return {"ledger_gap": gap,
                "usage_gap_median": float(np.median(per)),
                "flex_conservation": max(float(excess.max()), 0.0)}

    def check(self):
        c, rows = self.sample()
        j, got = self.program_answers(c, rows)
        want = self.reference(j, rows, "float32")
        return self.compare(got, want)

    def control(self):
        """The reference in bfloat16 in the program's place, on the
        rollouts a run would check in its first call."""
        self.inputs()
        self.calls = [None]
        _, rows = self.sample()
        want = self.reference(0, rows, "float32")
        got = self.reference(0, rows, "bfloat16")
        return self.compare(got, want)
