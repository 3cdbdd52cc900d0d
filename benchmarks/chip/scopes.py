"""Device time by the program's own layer names.

The program names its layers with ``jax.named_scope``: ``engine.*``
(burn-in, ledger), ``stage.*`` (the day step's stages), ``solver.*`` (the
VCC solve's parts) and ``mpc.*`` (the hourly controller). A scope changes
only metadata: every instruction of the compiled program carries its
path, ``metadata={op_name="jit(run)/vmap()/while/body/.../stage.power/..."}``.
A trace names each device op by its instruction (on a TPU the ``XLA Ops``
event ``%fusion.12 = f32[8] fusion(...), ...``; on the CPU ``fusion.12``)
and carries no metadata, so the trace is joined to the compiled program's
text by instruction name.

* ``scope_map(hlo_text)``: ``{instruction name: op_name path}`` of one
  compiled program (``compiled.as_text()``);
* ``instr(event_name)``: the event's full instruction name;
* ``intervals`` / ``scoped_ns``: the union of the intervals of a device's
  leaf ops whose path holds a scope element;
* ``ms_by_scope``: device ms per unit of work for every element of the
  vocabulary, with the busy time no element names (``unscoped``) and the
  time of events no instruction of the map matches (``unresolved``).

An element is matched whole (``stage.power`` is not ``stage.power_fit``),
also where a transform wraps it (``vmap(engine.burnin)``: a scope entered
at the top of a vmapped function).
"""
from __future__ import annotations

import re

from benchmarks.chip import reduce

VOCAB = re.compile(r"(?:^|[/(])((?:engine|stage|solver|mpc)\.\w+)(?=$|[/)])")
CONTAINERS = ("while", "conditional", "call")
UNSCOPED, UNRESOLVED = "unscoped", "unresolved"

_COMP = re.compile(r"^(?:ENTRY\s+)?%(\S+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%(\S+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,}]+)")


def _common_scope(paths) -> str:
    """The path elements that ``paths`` share, each path without its
    last element (the operation's own name)."""
    parts = [p.split("/")[:-1] for p in paths]
    if not parts:
        return ""
    out = []
    for elems in zip(*parts):
        if any(e != elems[0] for e in elems):
            break
        out.append(elems[0])
    return "/".join(out)


def scope_map(hlo_text: str) -> dict:
    """``{instruction name: op_name path}`` from the compiled HLO's
    metadata. Instructions the compiler added without metadata (copies,
    cloned fusions) take the path of the fused computation's root where
    they are fusions, else the common path of the other instructions of
    their computation: the loop or stage they run in."""
    comps, comp = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = comps.setdefault(m.group(1), {"rows": [], "root": None})
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        path = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        comp["rows"].append((m.group(2), path.group(1) if path else None,
                             calls.group(1) if calls else None))
        if m.group(1):
            comp["root"] = m.group(2)
    prefix = {c: _common_scope([p for _, p, _ in v["rows"] if p])
              for c, v in comps.items()}

    def root_path(cname, seen):
        for name, path, calls in comps[cname]["rows"]:
            if name != comps[cname]["root"]:
                continue
            if path is not None:
                return path
            if calls in comps and calls not in seen:
                return root_path(calls, seen | {calls})
        return prefix[cname]

    out = {}
    for cname, c in comps.items():
        for name, path, calls in c["rows"]:
            if path is None:
                path = (root_path(calls, {calls}) if calls in comps
                        else prefix[cname])
            out[name] = path
    return out


def instr(event_name: str) -> str:
    """The instruction an op event ran: ``fusion.12``, where
    ``reduce.short`` gives ``fusion``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def holds(path: str, element: str) -> bool:
    """Whether ``path`` has ``element`` as a whole path element."""
    return re.search(rf"(?:^|[/(]){re.escape(element)}(?:$|[/)])",
                     path) is not None


def elements(path: str) -> list:
    """The vocabulary's elements in ``path``, outermost first."""
    return VOCAB.findall(path)


def leaves(dev: reduce.Device) -> list:
    """The device's op events that run work themselves: containers
    (``while``, ``conditional``, ``call``) are left out, as
    ``reduce.top_ops`` leaves them out."""
    return [e for e in dev.ops
            if not reduce.short(e[2]).startswith(CONTAINERS)]


def intervals(dev: reduce.Device, scopes: dict, element: str,
              exclude=(), pallas: bool = True) -> list:
    """Union of the intervals of the leaf ops whose path holds
    ``element`` and none of ``exclude``; ``pallas=False`` leaves out the
    Pallas kernel events."""
    out = []
    for e in leaves(dev):
        path = scopes.get(instr(e[2]))
        if path is None or not holds(path, element):
            continue
        if any(holds(path, x) for x in exclude):
            continue
        if not pallas and reduce.PALLAS in e[2]:
            continue
        out.append([e[3], e[3] + e[4]])
    return reduce.clip(reduce.union(out), dev.lo, dev.hi)


def _ns(ivs) -> float:
    return float(sum(e - s for s, e in ivs))


def scoped_ns(dev: reduce.Device, scopes: dict, element: str, exclude=(),
              pallas: bool = True) -> float:
    return _ns(intervals(dev, scopes, element, exclude, pallas))


def ms_per_unit(devices, scopes: dict, units: float, element: str,
                exclude=(), pallas: bool = True):
    """Device ms per unit of work (such as a simulated fleet-day) under
    ``element``, summed over ``devices``; None where no instruction of
    the map holds the element (a program without scopes)."""
    if not units or not any(holds(p, element) for p in scopes.values()):
        return None
    ns = sum(scoped_ns(d, scopes, element, exclude, pallas)
             for d in devices)
    return ns / 1e6 / units


def ms_by_scope(devices, scopes: dict, units: float) -> dict:
    """Device ms per unit of work for each element of the vocabulary
    that the program's paths hold (nested elements overlap: a stage's
    time includes its solver parts), plus ``unscoped``, the busy time of
    leaf ops whose path holds none of them, and ``unresolved``, that of
    events no instruction of the map matches. Each sums over
    ``devices``."""
    names = sorted({x for p in scopes.values() for x in elements(p)})
    per = {x: [] for x in names + [UNSCOPED, UNRESOLVED]}
    for dev in devices:
        ivs = {x: [] for x in per}
        for e in leaves(dev):
            path = scopes.get(instr(e[2]))
            iv = [e[3], e[3] + e[4]]
            if path is None:
                ivs[UNRESOLVED].append(iv)
                continue
            found = set(elements(path))
            for x in found:
                ivs[x].append(iv)
            if not found:
                ivs[UNSCOPED].append(iv)
        clip = {x: reduce.clip(reduce.union(v), dev.lo, dev.hi)
                for x, v in ivs.items()}
        scoped = reduce.union([iv for x in names for iv in clip[x]])
        rest = reduce.union(scoped + clip[UNSCOPED])
        every = reduce.union(rest + clip[UNRESOLVED])
        for x in names:
            per[x].append(_ns(clip[x]))
        per[UNSCOPED].append(_ns(rest) - _ns(scoped))
        per[UNRESOLVED].append(_ns(every) - _ns(rest))
    return {x: sum(v) / 1e6 / units for x, v in per.items()}
