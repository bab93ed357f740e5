"""Drive the live continuum with an open-loop schedule on the wall clock.

The drive loop submits every request whose due time has passed, ticks the
continuum while anything is queued or in flight, and otherwise sleeps
until the next due time.  ``Continuum.tick`` blocks until the work it
admitted retires, so requests that fall due during a tick are submitted
when it returns; each request is timed from its due time, so that wait
counts.  After the window closes, arrivals continue on schedule until
every request due in the window is served or failed, for at most the
traffic's ``post_s`` seconds; one still open then counts as failed.

At each window marker it reads the named counters of every tier's
endpoint (``getattr``; a counter the endpoint lacks reads ``None``).

Host spans (``jax.profiler.TraceAnnotation``): ``bench.submit``,
``bench.tick``, ``bench.wait``, and the zero-length markers
``bench.window_start`` / ``bench.window_end`` that tie the profiler's
clock to the drive loop's.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from bench.traffic import Arrival


@dataclasses.dataclass
class Record:
    """One request as the client saw it (host ``perf_counter`` seconds)."""
    rid: int
    phase: str
    due: float
    prompt_len: int
    max_new: int
    submitted: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    n_out: int = 0
    tier: Optional[str] = None
    failed: bool = False
    # link and tier latency the platform charges beyond the wall clock
    charged_s: float = 0.0
    output: Optional[list] = None
    prompt: Optional[object] = None

    @property
    def served(self) -> bool:
        return self.output is not None


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    steps: int
    served: int
    backlog: int


@dataclasses.dataclass
class Result:
    records: List[Record]
    ticks: List[Tick]
    window: tuple                  # nominal (start, end), perf_counter
    marks: tuple                   # (start, end) markers as emitted
    end: float                     # when the loop stopped
    #: tier -> counter -> (value at the window's start, at its end)
    counters: Dict[str, Dict[str, Tuple]] = dataclasses.field(
        default_factory=dict)


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def drive(cc, fn: str, arrivals: List[Arrival], pre_s: float,
          window_s: float, post_s: float,
          on_start: Callable[[], None] = lambda: None,
          request_factory=None, counters: Sequence[str] = ()) -> Result:
    """Run the schedule against ``cc`` (a ``repro.platform.Continuum``
    with ``fn`` deployed).  ``on_start`` runs as the window opens (the
    traced run starts the profiler there).  ``request_factory(rid,
    tokens, max_new)`` builds the program's request object.
    ``counters`` names the endpoint attributes read at the markers."""
    endpoints = {t.name: t.endpoints[fn] for t in cc.tiers}

    def snapshot():
        return {t: {c: getattr(ep, c, None) for c in counters}
                for t, ep in endpoints.items()}

    t0 = time.perf_counter()
    w0, w1 = t0 + pre_s, t0 + pre_s + window_s
    deadline = w1 + post_s
    records: List[Record] = []
    reqs = []
    ticks: List[Tick] = []
    open_window: List[int] = []
    marks = [None, None]
    snaps = [None, None]
    i = 0
    while True:
        now = time.perf_counter()
        if marks[0] is None and now >= w0:
            on_start()
            with _annotate("bench.window_start"):
                marks[0] = time.perf_counter()
            snaps[0] = snapshot()
        if marks[0] is not None and marks[1] is None and now >= w1:
            with _annotate("bench.window_end"):
                marks[1] = time.perf_counter()
            snaps[1] = snapshot()
        if marks[1] is not None:
            open_window = [k for k in open_window
                           if not (reqs[k].output is not None
                                   or reqs[k].failed)]
            if not open_window or now >= deadline:
                break
        with _annotate("bench.submit"):
            while i < len(arrivals) and t0 + arrivals[i].due_s <= now:
                a = arrivals[i]
                req = request_factory(a.rid, a.tokens, a.max_new)
                rec = Record(a.rid, a.phase, t0 + a.due_s, a.prompt_len,
                             a.max_new, prompt=a.tokens)
                cc.submit(fn, req)
                rec.submitted = time.perf_counter()
                records.append(rec)
                reqs.append(req)
                if a.phase == "window":
                    open_window.append(len(reqs) - 1)
                i += 1
        if cc.queued or cc.in_flight:
            ts = time.perf_counter()
            with _annotate("bench.tick"):
                rec_t = cc.tick()
            ticks.append(Tick(ts, time.perf_counter(), int(rec_t["steps"]),
                              int(sum(rec_t["tiers"].values())),
                              int(sum(rec_t["backlog"].values()))))
            continue
        nxt = t0 + arrivals[i].due_s if i < len(arrivals) else deadline
        for edge in (w0, w1):
            if now < edge:
                nxt = min(nxt, edge)
        with _annotate("bench.wait"):
            time.sleep(max(0.0, nxt - time.perf_counter()))
    end = time.perf_counter()
    for rec, req in zip(records, reqs):
        rec.failed = bool(req.failed)
        if req.output is not None:
            rec.output = [int(t) for t in req.output]
            rec.n_out = len(rec.output)
            rec.t_first, rec.t_done = req.t_first, req.t_done
            rec.tier = req.tier
            rec.charged_s = req.latency_s - (req.t_done - req.arrival_s)
    per_tier = {t: {c: (snaps[0][t][c], snaps[1][t][c]) for c in counters}
                for t in endpoints}
    return Result(records, ticks, (w0, w1), tuple(marks), end, per_tier)
