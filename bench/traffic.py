"""Open-loop arrival schedules, generated from a traffic file and a seed.

One general generator reads every mix.  A traffic file gives:

* ``arrivals``: ``{"kind": "poisson", "x_knee": r}`` (steady rate
  ``r`` times the cell's knee) or ``{"kind": "on_off", "base_x_knee": b,
  "burst_x_knee": h, "on_s": on, "period_s": p}`` (rate ``h`` for ``on``
  seconds of every ``p``, ``b`` otherwise; after ``Trace.bursty`` and
  BurstGPT, arXiv:2401.17644);
* ``prompt`` and ``output``: lognormal lengths ``{"median", "sigma",
  "min", "max"}`` truncated to ``[min, max]``;
* ``pre_s``: the untimed phase before the window; ``post_s``: how long
  arrivals continue after it while the window's requests finish.

Every seed gets the same arrivals and the same sizes, in another order:
the arrival times (and the burst phase) come from one fixed stream, so
the window holds the same ``round(mean rate x seconds)`` requests at the
same instants for every seed (spread over the on and off phases in
proportion to their rates, uniform inside each phase: Poisson given the
counts); each phase's prompt and output lengths are one stratified set
of quantiles of the truncated lognormals, which the seed permutes, and
the seed draws the prompt tokens.  So runs with different seeds differ
in which request comes when and what it says, not in how much work they
offer or when it arrives.  Equal prompt lengths may fall due together;
the endpoint then prefills them as one batch, and the harness warms
every batch the schedule can form (``prefill_shapes``).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from statistics import NormalDist
from typing import Callable, Dict, List, Tuple

import numpy as np

_N01 = NormalDist()
#: the fixed stream the arrival instants come from
ARRIVALS_SEED = 0x0A221FA1


@dataclasses.dataclass
class Arrival:
    """One request of the schedule.  ``due_s`` counts from the start of
    the pre-window phase."""
    rid: int
    due_s: float
    prompt_len: int
    max_new: int
    phase: str                 # "pre" | "window" | "post"
    tokens: np.ndarray


def rate_segments(arrivals: dict, knee_rps: float, phase_s: float,
                  t0: float, t1: float) -> List[Tuple[float, float, float]]:
    """``(start, end, rate)`` pieces of ``[t0, t1)`` at constant rate."""
    kind = arrivals["kind"]
    if kind == "poisson":
        return [(t0, t1, arrivals["x_knee"] * knee_rps)]
    if kind != "on_off":
        raise ValueError(f"unknown arrival kind {kind!r}")
    on, period = float(arrivals["on_s"]), float(arrivals["period_s"])
    hi = arrivals["burst_x_knee"] * knee_rps
    lo = arrivals["base_x_knee"] * knee_rps
    segs = []
    # cycle k is "on" over [k*period - phase, k*period - phase + on)
    k = math.floor((t0 + phase_s) / period)
    while True:
        start = k * period - phase_s
        for a, b, r in ((start, start + on, hi),
                        (start + on, start + period, lo)):
            a, b = max(a, t0), min(b, t1)
            if b > a:
                segs.append((a, b, r))
        if start + period >= t1:
            return segs
        k += 1


def segment_counts(segs: List[Tuple[float, float, float]]) -> List[int]:
    """Requests per segment: the expected counts, rounded by largest
    remainder so that they sum to the rounded expected total."""
    want = np.asarray([(b - a) * r for a, b, r in segs], np.float64)
    total = int(round(float(want.sum())))
    base = np.floor(want).astype(int)
    rem = total - int(base.sum())
    order = np.argsort(-(want - base), kind="stable")
    base[order[:max(rem, 0)]] += 1
    return base.tolist()


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal truncated to
    ``[min, max]``, as integers in ascending order."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if n == 0:
        return np.zeros(0, np.int64)
    mu, sig = math.log(spec["median"]), float(spec["sigma"])
    f_lo = _N01.cdf((math.log(lo) - mu) / sig)
    f_hi = _N01.cdf((math.log(hi) - mu) / sig)
    q = f_lo + (np.arange(n) + 0.5) / n * (f_hi - f_lo)
    out = np.asarray([math.exp(mu + sig * _N01.inv_cdf(float(u))) for u in q])
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def schedule(traffic: dict, knee_rps: float, seed: int, window_s: float,
             vocab: int) -> List[Arrival]:
    """The whole run's arrivals: ``traffic["pre_s"]`` untimed seconds,
    the ``window_s`` window, then ``traffic["post_s"]`` seconds more."""
    rng = np.random.default_rng(seed)
    when = np.random.default_rng(ARRIVALS_SEED)
    arr = traffic["arrivals"]
    phase_s = (float(when.uniform(0.0, arr["period_s"]))
               if arr["kind"] == "on_off" else 0.0)
    pre, post = float(traffic["pre_s"]), float(traffic["post_s"])
    parts = (("pre", 0.0, pre), ("window", pre, pre + window_s),
             ("post", pre + window_s, pre + window_s + post))
    out: List[Arrival] = []
    for phase, t0, t1 in parts:
        segs = rate_segments(arr, knee_rps, phase_s, t0, t1)
        times = np.sort(np.concatenate(
            [when.uniform(a, b, c) for (a, b, _), c in
             zip(segs, segment_counts(segs))] or [np.zeros(0)]))
        n = len(times)
        prompt = lengths(traffic["prompt"], n)[rng.permutation(n)]
        new = lengths(traffic["output"], n)[rng.permutation(n)]
        for t, p, m in zip(times, prompt, new):
            toks = rng.integers(0, vocab, size=int(p), dtype=np.int32)
            out.append(Arrival(len(out), float(t), int(p), int(m), phase,
                               toks))
    return out


def pow2(n: int) -> int:
    """The power of two that ``n`` pads to."""
    return 1 << max(n - 1, 0).bit_length()


def prefill_shapes(arrivals: List[Arrival],
                   pad: Callable[[int], int] = pow2
                   ) -> Dict[int, Tuple[int, int]]:
    """For each length the schedule's prompts are prefilled at (``pad``:
    the family's ``prefill_length``), the longest prompt in it and the
    most prompts of one length: the endpoint prefills equal lengths
    admitted together as one batch, so no batch it forms from this
    schedule is larger."""
    same = collections.Counter(a.prompt_len for a in arrivals)
    out: Dict[int, Tuple[int, int]] = {}
    for length, count in same.items():
        longest, most = out.get(pad(length), (0, 0))
        out[pad(length)] = (max(longest, length), max(most, count))
    return dict(sorted(out.items()))
