"""Read the numbers a cell's correctness limit is set from, one process.

    python3 bench/control.py --workload stablelm-1.6b.burst \
        --seeds 1,2,3 --seconds 12

For each seed: a run of the cell at its own load (``--seconds`` of
window), then the check of what the program served (``logit_gap``, the
lower reading, and ``correct``), and the same check with the control in
the program's place: at each position of the same sampled prompts and
served tokens, the token that the reference computed in float8 e4m3 (the
step below the configuration's bfloat16) puts first, judged as if it had
been served (the upper reading, and ``control_correct``, which has to
come out false).  The benchmark's own runs never run the control.  The
limit in ``bench/cells/<cell>.json`` lies between the largest lower
reading and the smallest upper one; ``PERF.md`` gives both.
"""

import argparse
import gc
import json
import sys

import run  # noqa: E402  (bench/run.py: puts the checkout on sys.path)
import jax  # noqa: E402

from bench import check, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control", type=int, default=1,
                    help="0 reads the program only")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if jax.default_backend() != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        sv = run.serve(cell, seed, args.seconds, False)
        checks, picked = run.judge(cell, sv, seed)
        row = {"seed": seed, "program_gap": checks["logit_gap"]["value"],
               "correct": check.passes(checks),
               "exact": {k: c["value"] for k, c in checks.items()
                         if k != "logit_gap"},
               "sample_tokens": sum(r.n_out for r in picked),
               "tiers": sorted({r.tier for r in picked}),
               "memory_peak_bytes": sv.info["memory_peak_bytes"]}
        if args.control:
            ctl, _ = run.judge(cell, sv, seed, mode="fp8")
            row["control_gap"] = ctl["logit_gap"]["value"]
            row["control_correct"] = check.passes(ctl)
        print(json.dumps(row), flush=True)
        del sv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
