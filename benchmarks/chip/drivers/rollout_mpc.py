"""Closed-loop rollout traffic: the batched fleet rollout
(``sim.rollout_batch``) of a configuration with the hourly MPC loop on,
one (scenario x seed) batch per call, call after call, on one chip.

As ``rollout``, and:

* the timed program keeps, of each call's final state, only what the
  check reads (the final backlog and the last ``days`` of usage), beside
  the ledger and the trajectory, so a window of fast calls does not fill
  the device with whole states;
* the reference is the closed-loop one (``reference_mpc``), with the
  configuration's ``mpc`` block;
* the window hands the per-layer readers the compiled program's scope
  map (``work["scopes"]``), made once in set-up;
* each call is dispatched before the one ahead of it is waited on, so
  the device always has the next call queued: a host stall shorter than
  a call (30 to 200 ms ones left the device idle in one run of four,
  with blocking calls of one second) no longer shows in the window;
* the summary gives the share of the sampled rollouts' cluster-hours
  whose re-solved suffix was accepted, by the program
  (``traj["recourse_hours"]``, where the program counts it) and by the
  reference, side by side and not compared: triggers flip on rounding.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Optional

import jax
import numpy as np

from benchmarks.chip import reference_mpc, scopes
from benchmarks.chip.drivers import rollout


class Kept(NamedTuple):
    """What a call keeps of its final state, read as ``rollout`` reads a
    whole state: the backlog, the usage history (its last ``days``
    only) and the accepted re-plans (None where the program has no
    such count)."""
    queue: jax.Array
    hist_usage: jax.Array
    recourse_hours: Optional[jax.Array]


class Driver(rollout.Driver):
    def setup(self):
        from repro.sim import SimConfig, SimParams, rollout_batch
        h = self.h
        if h.chips != 1:
            raise ValueError("closed-loop rollout traffic runs on one chip")
        t0 = time.perf_counter()
        self.inputs()
        t1 = time.perf_counter()
        self.cfg = SimConfig(**self.fleet)
        self.params = [SimParams(**{k: b[k] for k in SimParams._fields
                                    if k in b}) for b in self.batches]
        jax.block_until_ready(self.params)
        roll = rollout_batch(self.cfg, self.days)
        tail = self.fleet["hist_days"] - self.days

        def timed(p):
            state, led, traj = roll(p)
            return Kept(state.queue, state.hist_usage[:, :, tail:],
                        traj.get("recourse_hours")), led, traj

        t2 = time.perf_counter()
        self.run = h.compile(jax.jit(timed), self.params[0])
        t3 = time.perf_counter()
        self.scopes = scopes.scope_map(self.run.as_text())
        t4 = time.perf_counter()
        jax.block_until_ready(self.run(self.params[0]))
        self.setup_parts = {"inputs_s": t1 - t0, "compile_s": t3 - t2,
                            "scope_map_s": t4 - t3,
                            "warm_s": time.perf_counter() - t4}

    def window(self, seconds, span):
        """``rollout``'s window with one call in flight behind the one
        the host waits on: a call is dispatched while the window is
        open, and the window closes when the last one is done."""
        self.calls = []
        t0 = time.perf_counter()
        ahead = None
        while True:
            nxt = None
            if time.perf_counter() - t0 < seconds:
                j = (len(self.calls) + (ahead is not None)) \
                    % len(self.params)
                with span("call"):
                    nxt = (j, self.run(self.params[j]))
            if ahead is not None:
                j, (kept, led, _) = ahead
                jax.block_until_ready(led)
                self.calls.append((j, led, kept))
            if nxt is None:
                break
            ahead = nxt
        elapsed = time.perf_counter() - t0
        fleet_days = len(self.calls) * self.rows * self.days
        return {"elapsed": elapsed, "attempted": len(self.calls) * self.rows,
                "metrics": {"fleet_days_per_s": fleet_days / elapsed},
                "work": {"fleet_days": fleet_days,
                         "epoch_rows": self.rows * self.fleet["n_clusters"],
                         "scopes": self.scopes},
                "summary": {"calls": len(self.calls),
                            "call_s": elapsed / max(len(self.calls), 1)}}

    def program_answers(self, c, rows):
        rec = self.calls[c][2].recourse_hours
        rec = None if rec is None else np.asarray(rec)[rows]
        j, out = super().program_answers(c, rows)
        if rec is not None:
            out["recourse_hours"] = rec
        return j, out

    def reference(self, j, rows, dtype):
        sub = jax.tree.map(lambda a: a[np.asarray(rows)], self.batches[j])
        fn = jax.jit(jax.vmap(lambda r: reference_mpc.simulate(
            r, self.fleet, self.h.config["solver"], self.h.config["mpc"],
            self.days, dtype)))
        return {k: np.asarray(v, np.float64) for k, v in fn(sub).items()}

    def check(self):
        c, rows = self.sample()
        j, got = self.program_answers(c, rows)
        want = self.reference(j, rows, "float32")
        numbers = self.compare(got, want)
        hours = len(rows) * self.days * self.fleet["n_clusters"] * 24
        if "recourse_hours" in got:
            self.info["recourse_share_program"] = float(
                got["recourse_hours"].sum(dtype=np.float64) / hours)
        self.info["recourse_share_reference"] = float(
            want["recourse_hours"].sum() / hours)
        return numbers
