"""Device time of the hourly suffix re-solves per simulated fleet-day
(ms), summed over the cell's chips: every op under the program's
``mpc.resolve`` scope, its kernel events included. None where the
program names no such scope or the driver hands over no scope map."""

from benchmarks.chip import scopes


def read(tr):
    if "scopes" not in tr.work or "fleet_days" not in tr.work:
        return None
    return scopes.ms_per_unit(tr.devices, tr.work["scopes"],
                              tr.work["fleet_days"], "mpc.resolve")
