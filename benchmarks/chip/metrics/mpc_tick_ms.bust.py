"""Device time of the MPC hour loop outside its re-solves per simulated
fleet-day (ms), summed over the cell's chips: the ops under the
program's ``mpc.hour`` scope and not under ``mpc.resolve`` (the
admission ticks, the staleness signals, the nowcast and the warm
start). None where the program names no such scope or the driver hands
over no scope map."""

from benchmarks.chip import scopes


def read(tr):
    if "scopes" not in tr.work or "fleet_days" not in tr.work:
        return None
    return scopes.ms_per_unit(tr.devices, tr.work["scopes"],
                              tr.work["fleet_days"], "mpc.hour",
                              exclude=("mpc.resolve",))
