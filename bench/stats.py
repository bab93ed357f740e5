"""End-to-end metrics from the run's request table.

Every statistic covers all requests due in the window (or all work
finished in it), never medians of chunks:

* ``latency_p95_ms``: due time -> last token, plus the link and tier
  latency the platform charges (the paper's application latency);
* ``ttft_p95_ms``: due time -> first token;
* ``tpot_p95_ms``: ``(t_done - t_first) / (n_out - 1)`` over served
  requests with two tokens or more;
* ``tokens_per_s``: output tokens of requests that finished inside the
  window, over the window's seconds.

A request due in the window that failed (rejected by a full gateway) or
was still open when the run stopped enters the latency and TTFT tails
as ``stop - due``: it had not been served when the run ended, so its
latency is at least that.  It also counts in ``failed``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.drive import Record


def p95(values: Sequence[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def in_window(records: List[Record], window) -> List[Record]:
    w0, w1 = window
    return [r for r in records if w0 <= r.due < w1]


def end_to_end(records: List[Record], window, stop: float) -> Dict[str,
                                                                   float]:
    w0, w1 = window
    due = in_window(records, window)
    lat, ttft, tpot = [], [], []
    for r in due:
        if r.served:
            lat.append(r.t_done - r.due + r.charged_s)
            ttft.append(r.t_first - r.due)
            if r.n_out >= 2:
                tpot.append((r.t_done - r.t_first) / (r.n_out - 1))
        else:
            lat.append(stop - r.due)
            ttft.append(stop - r.due)
    done = sum(r.n_out for r in records if r.served and w0 <= r.t_done < w1)
    out = {"tokens_per_s": done / (w1 - w0)}
    if due:
        out["latency_p95_ms"] = 1e3 * p95(lat)
        out["ttft_p95_ms"] = 1e3 * p95(ttft)
    if tpot:
        out["tpot_p95_ms"] = 1e3 * p95(tpot)
    return out


def counts(records: List[Record], window) -> Dict[str, int]:
    """Requests due in the window, and how many of them failed (rejected,
    or never served)."""
    due = in_window(records, window)
    return {"attempted": len(due),
            "failed": sum(1 for r in due if not r.served)}


def tokens_made_per_s(records: List[Record], window) -> float:
    """Output tokens made inside the window, over its seconds: each served
    request's first token at ``t_first`` and the rest spread evenly up to
    ``t_done``, as ``bench/metrics`` places decode steps.  Unlike
    ``tokens_per_s`` it does not swing with which long requests happen to
    finish just inside the window."""
    w0, w1 = window
    made = 0
    for r in records:
        if not r.served:
            continue
        times = np.linspace(r.t_first, r.t_done, r.n_out)
        made += int(np.count_nonzero((times >= w0) & (times < w1)))
    return made / (w1 - w0)


def lateness_p95_ms(records: List[Record], window) -> float:
    """How late the drive loop submitted requests due in the window (it waits
    for a running tick): p95 of submit - due."""
    due = in_window(records, window)
    return 1e3 * p95([r.submitted - r.due for r in due]) if due else 0.0
