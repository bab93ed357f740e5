"""Find a cell's knee: steady Poisson at fixed rates, one process.

    python3 bench/sweep.py --workload stablelm-1.6b.burst \
        --rates 1,2,3 --seconds 30 --seeds 7,8

Each rate runs the cell's configuration and its mix's prompt and output
lengths as steady Poisson arrivals (a pre-window phase of ``--pre``
seconds, long enough that the window starts in steady state, then the
window and the drain) on a fresh continuum over the same weights, once
per seed.  A rate is sustained on a seed when the tokens made inside the
window (``stats.tokens_made_per_s``) reach 0.95 of the tokens it
offered, no request fails, and the backlog at the window's last tick is
no larger than at its first.  The knee is the highest rate that, with
every lower rate tested, is sustained on every seed; the cell's file
records it, and ``PERF.md`` the sweeps.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (bench/run.py: puts the checkout on sys.path)
import jax  # noqa: E402

from bench import drive, model, spec, stats, traffic, weights  # noqa: E402


def one_rate(cell, params, seed: int, rate: float, seconds: float,
             pre_s: float) -> dict:
    from repro.platform import Request
    mix = copy.deepcopy(cell.traffic)
    mix["arrivals"] = {"kind": "poisson", "x_knee": 1.0}
    mix["pre_s"] = pre_s
    cc = run.build(cell, params, seed)
    fn = cell.config["name"]
    arrivals = traffic.schedule(mix, rate, seed, seconds,
                                int(cell.config["vocab_size"]))
    fam = model.family(cell.config, cell.root)
    run.warm_up(cc, fn, traffic.prefill_shapes(
        arrivals, lambda n: fam.prefill_length(cell.config, n)))
    res = drive.drive(cc, fn, arrivals, mix["pre_s"], seconds, mix["post_s"],
                      request_factory=lambda r, t, m: Request(
                          rid=r, tokens=t, max_new=m))
    del cc
    gc.collect()                   # the caches go before the next rate's
    w0, w1 = res.window
    due = stats.in_window(res.records, res.window)
    ticks = [t for t in res.ticks if w0 <= t.start < w1]
    e2e = stats.end_to_end(res.records, res.window, res.end)
    offered = sum(r.max_new for r in due) / seconds
    out = {"rate_rps": rate, "seed": seed, "requests": len(due),
           "offered_tokens_per_s": offered,
           "tokens_made_per_s": stats.tokens_made_per_s(res.records,
                                                        res.window),
           "tokens_per_s": e2e["tokens_per_s"],
           "failed": stats.counts(res.records, res.window)["failed"],
           "backlog_first": ticks[0].backlog if ticks else 0,
           "backlog_last": ticks[-1].backlog if ticks else 0,
           "ttft_p95_ms": e2e.get("ttft_p95_ms"),
           "latency_p95_ms": e2e.get("latency_p95_ms"),
           "tpot_p95_ms": e2e.get("tpot_p95_ms"),
           "steps": sum(t.steps for t in ticks),
           "ticks": len(ticks),
           "tick_s_mean": (sum(t.end - t.start for t in ticks)
                           / max(len(ticks), 1)),
           "offload_share": 100.0 * sum(r.tier == "cloud" for r in due
                                        if r.served) / max(len(due), 1),
           "lateness_p95_ms": stats.lateness_p95_ms(res.records,
                                                    res.window)}
    out["sustained"] = (out["tokens_made_per_s"] >= 0.95 * offered
                        and out["failed"] == 0
                        and out["backlog_last"] <= out["backlog_first"])
    return out


def knee(rows) -> float:
    """The highest rate sustained on every seed, with every lower rate."""
    best = None
    for rate in sorted({r["rate_rps"] for r in rows}):
        if not all(r["sustained"] for r in rows if r["rate_rps"] == rate):
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="7")
    ap.add_argument("--pre", type=float, default=36.0,
                    help="untimed seconds before the window: long enough "
                         "for the longest request, so the window starts "
                         "in steady state")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    run.enable_cache()
    fam = model.family(cell.config, cell.root)
    mcfg = fam.program_config(cell.config)
    seeds = [int(s) for s in args.seeds.split(",")]
    params = weights.make_params(model.param_shapes(mcfg), seeds[0],
                                 fam.FAN_IN, mcfg.param_dtype)
    rows = []
    for seed in seeds:
        for rate in (float(r) for r in args.rates.split(",")):
            rows.append(one_rate(cell, params, seed, rate, args.seconds,
                                 args.pre))
            print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "knee_rps": knee(rows),
                      "seconds": args.seconds, "seeds": seeds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
