"""Idle share (%) of the device during the closed-loop rollout window:
1 - (union of op intervals) / window, on the idlest chip of the cell."""


def read(tr):
    if "fleet_days" not in tr.work:
        return None
    return 100.0 * max(d.idle_share for d in tr.devices)
