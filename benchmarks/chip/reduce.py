"""Reduction of a profiler trace to the quantities the per-layer metrics
read: device busy time, idle share, Pallas kernel time, and the longest
idle gaps with what the host was doing in them.

A trace is first flattened to a list of events
``[plane, line, name, start_ns, duration_ns]`` (``load_events``); the
rest works on that list, so it can be checked on a small recorded trace.

On a TPU, a device is a plane ``/device:TPU:<i>``. Its line ``XLA Ops``
holds one event per executed HLO op, nested (a ``while`` op spans the ops
of its body); its line ``XLA Modules`` one event per executed program,
named ``jit_<function>(<hash>)``. A Pallas kernel is an ``XLA Ops`` event
whose name holds ``custom_call_target="tpu_custom_call"``. Host threads
are lines of the plane ``/host:CPU``; the benchmark's own spans
(``jax.profiler.TraceAnnotation``) are events named ``bench.<what>`` on
the thread that made them. Host and device events share one clock.
"""
from __future__ import annotations

import collections
import glob
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
PALLAS = 'custom_call_target="tpu_custom_call"'
DEVICE = re.compile(r"^/device:TPU:(\d+)$")
HOST = "/host:CPU"
SPAN = "bench."


def load_events(trace_dir: str) -> list:
    """Every event of the trace under ``trace_dir`` as a flat list."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append([plane.name, line.name, e.name, e.start_ns,
                            e.duration_ns])
    return out


def devices(events) -> list:
    """Device plane names, in device order."""
    found = {e[0] for e in events if DEVICE.match(e[0])}
    return sorted(found, key=lambda p: int(DEVICE.match(p).group(1)))


def union(intervals) -> list:
    """Merge [start, end) intervals into disjoint ones, in order."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def short(name: str) -> str:
    """An op's name without its HLO signature or instance number."""
    head = name.split(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


class Device:
    """One device's events inside the window [lo, hi)."""

    def __init__(self, events, plane, lo, hi):
        self.ops = [e for e in events if e[0] == plane and e[1] == OPS
                    and lo <= e[3] < hi]
        self.modules = [e for e in events if e[0] == plane
                        and e[1] == MODULES and lo <= e[3] < hi]
        self.busy = clip(union([[e[3], e[3] + e[4]] for e in self.ops]),
                         lo, hi)
        self.lo, self.hi = lo, hi

    @property
    def busy_ns(self) -> float:
        return float(sum(e - s for s, e in self.busy))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.hi - self.lo)

    def pallas(self, module=None) -> list:
        """Pallas kernel events, optionally only those inside executions
        of programs whose name starts with ``jit_<module>(``."""
        evs = [e for e in self.ops if PALLAS in e[2]]
        if module is None:
            return evs
        spans = [(m[3], m[3] + m[4]) for m in self.modules
                 if m[2].startswith(f"jit_{module}(")]
        return [e for e in evs if any(s <= e[3] < t for s, t in spans)]

    def module_count(self, module: str) -> int:
        return sum(m[2].startswith(f"jit_{module}(") for m in self.modules)

    def gaps(self) -> list:
        """Idle [start, end) intervals between busy ones in the window."""
        edges = [self.lo] + [x for iv in self.busy for x in iv] + [self.hi]
        return [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]


def window(events) -> tuple:
    """The traced window: the span of the benchmark's ``bench.window``
    annotation."""
    w = [e for e in events if e[0] == HOST and e[2] == f"{SPAN}window"]
    if len(w) != 1:
        raise RuntimeError(f"expected one {SPAN}window span, found {len(w)}")
    return w[0][3], w[0][3] + w[0][4]


def top_ops(dev: Device, k: int = 10) -> list:
    """The device ops that took most time, summed by short name. Ops
    that only contain others (``while``, ``conditional``, ``call``) are
    left out; Pallas kernels are named ``pallas:<op>``."""
    agg = collections.Counter()
    for e in dev.ops:
        name = short(e[2])
        if name.startswith(("while", "conditional", "call")):
            continue
        if PALLAS in e[2]:
            name = "pallas:" + name
        agg[name] += e[4]
    return [[n, v / 1e9] for n, v in agg.most_common(k)]


def host_doing(events, lo, hi) -> str:
    """What the host was doing in [lo, hi): the benchmark span and the
    other host event that cover most of it (the shorter, innermost one
    on ties), as ``span: event``."""
    best = {True: (None, "no span"), False: (None, "nothing traced")}
    for e in events:
        if e[0] != HOST or e[2] == f"{SPAN}window":
            continue
        c = min(e[3] + e[4], hi) - max(e[3], lo)
        if c <= 0:
            continue
        is_span = e[2].startswith(SPAN)
        key = (c, -e[4])
        if best[is_span][0] is None or key > best[is_span][0]:
            best[is_span] = (key, e[2])
    return f"{best[True][1]}: {best[False][1]}"


def idle_gaps(events, dev: Device, k: int = 10) -> list:
    """The ``k`` longest idle gaps, each named by what the host did."""
    gaps = sorted(dev.gaps(), key=lambda g: g[0] - g[1])[:k]
    return [[host_doing(events, s, e), (e - s) / 1e9] for s, e in gaps]
