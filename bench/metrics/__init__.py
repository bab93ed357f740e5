"""Per-layer metric readers, one file each, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(ctx) -> Optional[float]``.  A
reader that finds nothing to read returns ``None`` and the harness leaves
the metric out of the line; none returns 0 for a share of a roofline or
a peak.  ``Context`` is what the traced run hands every reader: the
requests and ticks, the trace (device planes, the harness's ``bench.*``
spans and the program's ``repro.*`` spans, ``spans``), and the counters
each tier's endpoint showed at the window's markers (``counter``).
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Callable, Dict, List, Optional, Tuple

from bench import spec
from bench import trace as tr
from bench.drive import Record, Tick

#: the program's jitted entry points, as they are named in the trace
DECODE = "jit__model_decode"
PREFILL = "jit__prefill_rows"


@dataclasses.dataclass
class Context:
    cfg: dict                           # the configuration file
    records: List[Record]
    ticks: List[Tick]
    window: Tuple[float, float]         # traced window, host clock (s)
    due_window: Tuple[float, float]     # nominal window the requests are due in
    stop: float                         # when the run stopped
    compiles: int                       # XLA compiles inside the window
    cloud_tier: str
    peaks: dict
    trace: Optional[tr.Trace] = None
    trace_window: Tuple[float, float] = (0.0, 0.0)   # same window, trace ns
    cloud_decode: Optional[str] = None  # the cloud's decode module name
    root: pathlib.Path = spec.ROOT      # where the cell's files were read
    #: tier -> counter -> (start, end), as ``drive.Result.counters``
    counters: Dict[str, Dict[str, Tuple]] = dataclasses.field(
        default_factory=dict)

    def device(self) -> tr.Device:
        return self.trace.devices[0]

    def spans(self, name: str) -> List[tr.ProgramEvent]:
        """The program's host spans called ``name`` that start in the
        traced window (none without a trace)."""
        if self.trace is None:
            return []
        lo, hi = self.trace_window
        return [e for e in self.trace.program
                if e[0] == name and lo <= e[1] < hi]

    def counter(self, tier: str, name: str) -> Tuple:
        """``(start, end)`` of an endpoint counter over the window;
        ``(None, None)`` where the endpoint or the run did not have it."""
        return self.counters.get(tier, {}).get(name, (None, None))

    def modules(self, prog: str) -> Dict[str, List[float]]:
        """Executions (device ns) of ``prog`` in the window, per module."""
        lo, hi = self.trace_window
        return {n: d for n, d in
                tr.executions(self.device().modules, lo, hi).items()
                if tr.program(n) == prog}

    def finished(self) -> List[Record]:
        lo, hi = self.window
        return [r for r in self.records if r.served and lo <= r.t_done < hi]

    def prefilled(self) -> List[Record]:
        lo, hi = self.window
        return [r for r in self.records if r.served and lo <= r.t_first < hi]

    def decode_steps(self) -> List[Tuple[Record, int]]:
        """(request, context) of every decode step taken in the window.
        A request's ``n_out - 1`` steps are spread evenly between its
        first and last token; step j attends to ``prompt + j`` positions."""
        lo, hi = self.window
        out = []
        for r in self.records:
            if not r.served or r.n_out < 2:
                continue
            dt = (r.t_done - r.t_first) / (r.n_out - 1)
            for j in range(1, r.n_out):
                if lo <= r.t_first + j * dt < hi:
                    out.append((r, r.prompt_len + j))
        return out


def reader(name: str) -> Callable[[Context], Optional[float]]:
    return spec.load_module(spec.ROOT, f"bench/metrics/{name}.py").read
