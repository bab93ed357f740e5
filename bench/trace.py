"""Reduce a profiler trace to device time, idle time and per-program time.

The JAX profiler writes an ``.xplane.pb``.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per
program execution, named ``<jit name>(<fingerprint>)``, and whose line
``XLA Ops`` holds the operations those executions ran.  The host plane
``/host:CPU`` holds the harness's own ``bench.*`` spans on the same clock,
and the program's ``repro.*`` spans with their arguments.

All reductions work on plain ``(name, start_ns, duration_ns)`` tuples so
they can be checked on a small recorded trace.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start ns, duration ns
#: name, start ns, duration ns, the span's arguments
ProgramEvent = Tuple[str, float, float, Dict[str, object]]


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]                       # bench.* spans
    program: List[ProgramEvent] = dataclasses.field(default_factory=list)


def load(path: str) -> Trace:
    """Read the device planes, the harness's host spans and the program's
    host spans of one trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, prog = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith("repro."):
                        prog.append((e.name, e.start_ns, e.duration_ns,
                                     dict(e.stats)))
    host.sort(key=lambda e: e[1])
    prog.sort(key=lambda e: e[1])
    return Trace(devices, host, prog)


def marker(host: Sequence[Event], name: str) -> Optional[float]:
    """Start (ns) of the first host span called ``name``."""
    return next((s for n, s, _ in host if n == name), None)


def union(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events`` clipped to ``[lo, hi]``."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s < hi and s + d > lo)
    out: List[List[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(events, lo, hi))


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which no operation ran."""
    gaps, t = [], lo
    for a, b in union(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def program(name: str) -> str:
    """``jit__model_decode(123)`` -> ``jit__model_decode``."""
    return name.split("(", 1)[0]


def executions(modules: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, List[float]]:
    """Device ns of each execution that starts inside ``[lo, hi]``, keyed
    by the full module name (program and fingerprint)."""
    out: Dict[str, List[float]] = collections.defaultdict(list)
    for n, s, d in modules:
        if lo <= s < hi:
            out[n].append(d)
    return dict(out)


def attribute(gaps: Sequence[Tuple[float, float]], host: Sequence[Event]
              ) -> List[Tuple[str, float]]:
    """Name each gap by the innermost harness span covering its middle
    (``idle`` where the harness was in none)."""
    spans = [(n, s, s + d) for n, s, d in host if d > 0]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [(e - s, n) for n, s, e in spans if s <= mid < e]
        out.append((min(inside)[1] if inside else "idle", b - a))
    return out


#: control-flow instructions whose events span the operations of their
#: bodies; the bodies' operations are counted instead
CONTAINERS = ("while", "conditional", "call")


def top_ops(dev: Device, lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operations with the most device ns in ``[lo, hi]``, named
    ``<program>/<instruction>`` by the execution that contains them
    (control-flow containers left out)."""
    mods = sorted((s, s + d, program(n)) for n, s, d in dev.modules)
    total: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for n, s, d in sorted(dev.ops, key=lambda e: e[1]):
        if not (lo <= s < hi):
            continue
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        owner = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
        instr = n.split(" = ", 1)[0].lstrip("%")
        if instr.split(".", 1)[0] in CONTAINERS:
            continue
        total[f"{owner}/{instr}"] += d
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]
