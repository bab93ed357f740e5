"""Run one cell of the benchmark on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the window): the
weights are made from the seed on the device, the continuum is built as
the configuration's deployment says and the function deployed on it,
every prefill and decode shape the cell's traffic uses is compiled (or
loaded from the persistent cache in ``.jax_cache``), the control plane's
router is compiled for every power-of-two batch, and the traffic's
untimed pre-window phase brings queues and controller to steady state.
Then the window runs for ``--seconds`` and the requests due in it finish.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window and reports its per-layer metrics, ``busy_s`` /
``window_s`` and a breakdown.  Both check the served tokens against the
plain reference and print each number compared beside its limit, last on
standard error and last in the result line.  Earlier lines of standard
output are the request table and the compile counts.

Exits 3 without a result when JAX finds no TPU or fewer chips than the
cell asks for, and 2 when the cell or its files (the configuration's
family module and reference among them) are missing.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional, Tuple  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, cost, drive, metrics, model, spec, stats  # noqa
from bench import trace as tr  # noqa: E402
from bench import traffic, weights  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

#: perf_counter of every program this process compiled or loaded from
#: the persistent cache (JAX reports both as a backend compile), and of
#: every cache load
COMPILES = []
CACHE_LOADS = []


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES.append(time.perf_counter())
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        CACHE_LOADS.append(time.perf_counter())


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, so that only a cell's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


#: the deployment's tiers, as ``Request.tier`` names them
TIERS = ("edge", "cloud")


def build(cell: spec.Cell, params, seed: int):
    """The continuum the configuration's deployment describes, with the
    model deployed on it (the serve CLI's pair of tiers)."""
    from repro.core import offload
    from repro.core.replication import AutoscalingPolicy, FunctionSpec
    from repro.platform import Continuum, TierConfig
    dep = cell.config["deployment"]
    tier = lambda t: TierConfig(slots=int(t["slots"]),           # noqa
                                max_len=int(t["max_len"]),
                                extra_latency_s=float(t.get(
                                    "extra_latency_s", 0.0)))
    cc = Continuum(edge=tier(dep["edge"]), cloud=tier(dep["cloud"]),
                   policy=dep["policy"], offload_cfg=offload.OffloadConfig(),
                   seed=weights.jax_seed(seed),
                   max_steps_per_tick=dep.get("max_steps_per_tick"))
    name = cell.config["name"]
    cc.deploy(FunctionSpec(name=name, arch=name, revision=1,
                           autoscaling=AutoscalingPolicy()),
              model.program_config(cell.config, cell.root), params)
    return cc


def warm_up(cc, fn: str, shapes, on_cloud=None) -> None:
    """Compile every tier's decode step and its bucketed prefill at each
    length the prompts are prefilled at and each power-of-two batch up
    to the most equal lengths the schedule holds (``shapes``, from
    ``traffic.prefill_shapes``), through the endpoint's own slot API (no
    request state survives), then the router at every power-of-two batch
    up to 256.  ``on_cloud`` wraps the deepest tier's warm-up (the traced
    run profiles it to learn which decode program is the cloud's)."""
    import contextlib
    for i, t in enumerate(cc.tiers):
        ep = t.endpoints[fn]
        ctx = (on_cloud() if on_cloud and i == len(cc.tiers) - 1
               else contextlib.nullcontext())
        with ctx:
            for longest, most in shapes.values():
                batch = 1
                while batch <= min(ep.slots, traffic.pow2(most)):
                    slots = [ep.try_claim() for _ in range(batch)]
                    first = ep.prefill_batch(
                        {s: np.zeros(longest, np.int32) for s in slots})
                    ep.decode_all(first)
                    for s in slots:
                        ep.release(s)
                    batch *= 2
    key = jax.random.PRNGKey(0)
    for n in (1 << k for k in range(9)):
        cc.control.route_tiers(key, np.zeros(n, np.int32))


class _Profile:
    """``jax.profiler`` over a block, into a directory of the checkout."""

    def __init__(self, path: pathlib.Path):
        self.path = path

    def start(self):
        shutil.rmtree(self.path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.path), profiler_options=opts)

    def stop(self) -> tr.Trace:
        jax.profiler.stop_trace()
        pb = next(self.path.rglob("*.xplane.pb"))
        out = tr.load(str(pb))
        shutil.rmtree(self.path, ignore_errors=True)
        return out

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.trace = self.stop()


def memory_peak() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def per_layer(cell, res, prof_trace, cloud_decode, info) -> tuple:
    """(metrics, busy_s, window_s, breakdown) of the traced window."""
    host = prof_trace.host
    lo = tr.marker(host, "bench.window_start")
    hi = tr.marker(host, "bench.window_end")
    ps, pe = res.marks
    ctx = metrics.Context(
        cfg=cell.config, records=res.records, ticks=res.ticks,
        window=(ps, pe), due_window=res.window, stop=res.end,
        compiles=sum(1 for t in COMPILES if ps <= t < pe),
        cloud_tier="cloud", peaks=cost.peaks(info["kind"]),
        trace=prof_trace, trace_window=(lo, hi), cloud_decode=cloud_decode,
        root=cell.root, counters=res.counters)
    out = {}
    for m in cell.per_layer:
        v = metrics.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = prof_trace.devices[0]
    busy = tr.busy_ns(dev.ops, lo, hi)
    spans = [e for e in host if not e[0].startswith("bench.window_")]
    gaps = sorted(tr.attribute(tr.idle_gaps(dev.ops, lo, hi), spans),
                  key=lambda g: -g[1])[:10]
    breakdown = {
        "device_ops": [[n, ns * 1e-9] for n, ns in tr.top_ops(dev, lo, hi)],
        "idle_gaps": [[n, ns * 1e-9] for n, ns in gaps]}
    return out, busy * 1e-9, (hi - lo) * 1e-9, breakdown


@dataclasses.dataclass
class Served:
    """What a cell's timed path produced, and what it was made from."""
    res: drive.Result
    params: dict
    vocab: int
    info: dict
    setup_compiles: int
    #: set-up seconds by phase: weights, build, warm_up, pre_window
    setup_split: dict
    trace: Optional[tr.Trace] = None
    cloud_decode: Optional[str] = None


def serve(cell: spec.Cell, seed: int, seconds: float, trace: bool) -> Served:
    """Set up, run the window, drain; the program's state is freed on
    return (the weights, the benchmark's own, are kept for the check)."""
    from repro.platform import Request
    fam = model.family(cell.config, cell.root)
    mcfg = fam.program_config(cell.config)
    t = [time.perf_counter()]
    params = weights.make_params(model.param_shapes(mcfg), seed, fam.FAN_IN,
                                 mcfg.param_dtype)
    jax.block_until_ready(params)
    t.append(time.perf_counter())
    cc = build(cell, params, seed)
    t.append(time.perf_counter())
    fn = cell.config["name"]
    arrivals = traffic.schedule(cell.traffic, cell.knee_rps, seed, seconds,
                                mcfg.vocab_size)
    cloud_prof = _Profile(TRACE_DIR) if trace else None
    warm_up(cc, fn, traffic.prefill_shapes(
        arrivals, lambda n: fam.prefill_length(cell.config, n)),
            on_cloud=(lambda: cloud_prof) if trace else None)
    cloud_decode = None
    if trace:
        decodes = [n for n, _, _ in cloud_prof.trace.devices[0].modules
                   if tr.program(n) == metrics.DECODE]
        cloud_decode = decodes[-1] if decodes else None
    setup_compiles = len(COMPILES)
    t.append(time.perf_counter())
    prof = _Profile(TRACE_DIR) if trace else None
    res = drive.drive(
        cc, fn, arrivals, cell.traffic["pre_s"], seconds,
        cell.traffic["post_s"], on_start=prof.start if prof else (
            lambda: None),
        request_factory=lambda rid, toks, m: Request(rid=rid, tokens=toks,
                                                     max_new=m),
        counters=fam.COUNTERS)
    prof_trace = prof.stop() if prof else None
    info = device_info()
    info["memory_peak_bytes"] = memory_peak()
    del cc
    gc.collect()           # endpoints hold jitted closures over themselves
    t.append(res.window[0])
    split = dict(zip(("weights", "build", "warm_up", "pre_window"),
                     np.diff(t).tolist()))
    return Served(res, params, mcfg.vocab_size, info, setup_compiles,
                  split, prof_trace, cloud_decode)


def judge(cell: spec.Cell, sv: Served, seed: int,
          mode: str = "float32") -> Tuple[dict, list]:
    """The numbers compared, each beside its limit, and the sampled
    requests the reference checked.  ``mode="fp8"`` judges the control's
    tokens in place of the served ones (``bench/control.py``)."""
    res = sv.res
    picked = check.sample(res.records, seed,
                          int(cell.limits["sample_tokens"]))
    gap = (check.logit_gap(cell.config, sv.params, picked, mode, cell.root)
           if picked else None)
    checks = {"logit_gap": {"value": gap, "limit": cell.limits["logit_gap"]}}
    for k, v in check.exact(res.records, res.ticks, sv.vocab).items():
        checks[k] = {"value": v, "limit": 0}
    return checks, picked


def report(sv: Served) -> None:
    """The request table and the compile counts (earlier stdout lines)."""
    res = sv.res
    w0, _ = res.window
    for r in res.records:
        print("req", r.rid, r.phase, r.prompt_len, r.max_new, r.tier,
              "failed" if r.failed else "", round(r.due - w0, 6),
              round(r.submitted - r.due, 6),
              round(r.t_first - r.due, 6) if r.served else "",
              round(r.t_done - r.due, 6) if r.served else "")
    ps, pe = res.marks
    print(json.dumps({
        "programs": {"setup": sv.setup_compiles,
                     "pre_window": sum(1 for t in COMPILES if t < ps)
                     - sv.setup_compiles,
                     "window": sum(1 for t in COMPILES if ps <= t < pe),
                     "after": sum(1 for t in COMPILES if t >= pe),
                     "cache_loads": len(CACHE_LOADS)},
        "setup_split_s": sv.setup_split,
        "lateness_p95_ms": stats.lateness_p95_ms(res.records, res.window),
        "ticks": len(res.ticks)}), flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float = T0) -> dict:
    """One run of ``cell``; returns the result object (``checks`` last,
    then the sample the reference checked)."""
    sv = serve(cell, seed, seconds, trace)
    report(sv)
    res, info, extra = sv.res, sv.info, {}
    if trace:
        out, busy, win, breakdown = per_layer(cell, res, sv.trace,
                                              sv.cloud_decode, info)
        info["busy_s"], info["window_s"] = busy, win
        extra["breakdown"] = breakdown
    else:
        e2e = stats.end_to_end(res.records, res.window, res.end)
        e2e["setup_s"] = res.window[0] - t_start
        out = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
               for m in cell.end_to_end if m["name"] in e2e}
    checks, picked = judge(cell, sv, seed)
    counts = stats.counts(res.records, res.window)
    return {"correct": check.passes(checks),
            "attempted": counts["attempted"], "failed": counts["failed"],
            "metrics": out, "device": info, **extra, "checks": checks,
            "sample": {"requests": len(picked),
                       "tokens": sum(r.n_out for r in picked),
                       "tiers": sorted({r.tier for r in picked}),
                       "tiers_unchecked": [t for t in TIERS if t not in {
                           r.tier for r in picked}]}}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        model.family(cell.config, cell.root)
        spec.load_module(cell.root, cell.config["reference"])
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if jax.default_backend() != "tpu" or len(jax.devices()) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(jax.devices())} {jax.default_backend()} device(s)",
              file=sys.stderr)
        return 3
    enable_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sample = result.pop("sample")
    print(f"check sample {json.dumps(sample)}", file=sys.stderr)
    for t in sample["tiers_unchecked"]:
        print(f"check note: the {t} tier served no request in this run, "
              f"so the reference checked none of its tokens",
              file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
