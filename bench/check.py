"""Whether what the timed path produced is correct.

After the window, the served requests are checked against the plain
reference (the module at the configuration's ``"reference"`` path, which
defines ``served_gaps``), which is fed each sampled request's prompt and
served tokens.  Greedy serving must put first, at every
position, a token whose reference logit is the reference's best or within
rounding of it: ``logit_gap`` is the widest gap over the sample.  The
sample is drawn from the seed among the requests due in the window that
were served: the longest of them, then requests from each tier in turn,
until it holds ``sample_tokens`` served tokens; a tier that served none
of them adds one request it served before or after the window.

Exact checks (limit 0) over every request due in the window:

* ``unanswered``: neither served nor refused by a gateway when the
  run stopped (a refusal is an answer; silence is not);
* ``served_twice``: tokens delivered by the ticks beyond one output per
  served request (tick served counts minus served requests);
* ``wrong_length``: served outputs whose length is not ``max_new``;
* ``out_of_vocab``: served tokens outside the vocabulary.
"""

from __future__ import annotations

import pathlib
from typing import Dict, List

import numpy as np

from bench import spec
from bench.drive import Record, Tick


def sample(records: List[Record], seed: int, tokens: int) -> List[Record]:
    """The longest served request due in the window, then one per tier in
    turn, chosen by the seed, until ``tokens`` served tokens are in the
    sample.  A tier that served none of the window's requests but others
    of the run adds one of those: it ran the same compiled programs."""
    served = [r for r in records if r.served]
    window = [r for r in served if r.phase == "window"]
    if not window:
        return []
    rng = np.random.default_rng([seed, 0x5EED])
    longest = max(window, key=lambda r: (r.prompt_len + r.n_out, r.rid))
    pools = {}
    for r in window:
        if r is not longest:
            pools.setdefault(r.tier, []).append(r)
    for pool in pools.values():
        rng.shuffle(pool)
    out, have = [longest], longest.n_out
    tiers = sorted(pools)
    while have < tokens and any(pools.values()):
        for t in tiers:
            if pools[t] and have < tokens:
                r = pools[t].pop()
                out.append(r)
                have += r.n_out
    for t in sorted({r.tier for r in served} - {r.tier for r in out}):
        others = [r for r in served if r.tier == t]
        out.append(others[int(rng.integers(len(others)))])
    return out


def exact(records: List[Record], ticks: List[Tick], vocab: int
          ) -> Dict[str, int]:
    served = [r for r in records if r.served]
    return {
        "unanswered": sum(1 for r in records
                          if r.phase == "window" and not r.served
                          and not r.failed),
        "served_twice": max(sum(t.served for t in ticks) - len(served), 0),
        "wrong_length": sum(1 for r in served if r.n_out != r.max_new),
        "out_of_vocab": sum(1 for r in served
                            if min(r.output) < 0 or max(r.output) >= vocab),
    }


def logit_gap(cfg: dict, params, picked: List[Record],
              mode: str = "float32", root: pathlib.Path = spec.ROOT
              ) -> float:
    """Widest gap over every served token of ``picked``, by the reference
    that ``cfg`` names (looked for under ``root`` first).  With ``mode``
    other than ``"float32"`` it is the control's reading: at each position
    of the same prompts and served tokens, the gap of the token that the
    reference computed in ``mode`` puts first, as if it had been served."""
    reference = spec.load_module(root, cfg["reference"])
    worst = 0.0
    for r in picked:
        _, gap = reference.served_gaps(cfg, params, np.asarray(r.prompt),
                                       np.asarray(r.output), mode=mode)
        worst = max(worst, float(gap.max()))
    return worst


def passes(checks: Dict[str, dict]) -> bool:
    """Whether every number compared lies within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
