"""Per-layer metric readers, one file each, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(ctx) -> Optional[float]``.  A
reader that finds nothing to read returns ``None`` and the harness leaves
the metric out of the line; none returns 0 for a share of a roofline or
a peak.  ``Context`` is what the traced run hands every reader.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
from typing import Callable, Dict, List, Optional, Tuple

from bench import trace as tr
from bench.drive import Record, Tick

HERE = pathlib.Path(__file__).resolve().parent

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

    def device(self) -> tr.Device:
        return self.trace.devices[0]

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
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
