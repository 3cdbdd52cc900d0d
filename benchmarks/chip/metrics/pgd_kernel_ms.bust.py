"""Device time of the Pallas PGD kernel per simulated fleet-day (ms),
summed over the cell's chips: the day-ahead solve's epochs and the
hourly suffix re-solves' epochs alike."""


def read(tr):
    if "fleet_days" not in tr.work:
        return None
    ns = sum(e[4] for d in tr.devices for e in d.pallas())
    if ns == 0:
        return None
    return ns / 1e6 / tr.work["fleet_days"]
