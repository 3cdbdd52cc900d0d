"""Share (%) of the HBM roofline the PGD kernel reaches in the
closed-loop rollout window: the bytes each epoch's problem defines
(``roofline.py``, from shapes) at the published HBM bandwidth, over the
kernel's device time, summed over the cell's chips. A suffix epoch reads
and writes the same operands as a day-ahead epoch (its pinned hours are
bounds like any other), so every Pallas event counts one epoch's
bytes."""

from benchmarks.chip import roofline


def read(tr):
    if "fleet_days" not in tr.work:
        return None
    evs = [e for d in tr.devices for e in d.pallas()]
    kernel_s = sum(e[4] for e in evs) / 1e9
    nbytes = len(evs) * roofline.pgd_epoch_bytes(tr.work["epoch_rows"])
    return roofline.roofline_pct(nbytes, kernel_s,
                                 tr.peaks["hbm_bytes_per_s"])
