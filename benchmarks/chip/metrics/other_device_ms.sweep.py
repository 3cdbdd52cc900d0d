"""Device busy time outside the Pallas kernels per simulated fleet-day
(ms), summed over the cell's chips: the staged day step's forecasts,
power fits, grid draws, admission and the burn-in."""


def read(tr):
    if "fleet_days" not in tr.work:
        return None
    busy = sum(d.busy_ns for d in tr.devices)
    kernel = sum(e[4] for d in tr.devices for e in d.pallas())
    return (busy - kernel) / 1e6 / tr.work["fleet_days"]
