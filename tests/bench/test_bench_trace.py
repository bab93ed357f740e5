"""The trace reduction, on a small trace recorded on a TPU v5e (two
programs of one name, ``jit__model_decode``, at 2 and 16 rows, run three
times each inside ``bench.tick`` spans, with ``bench.wait`` between) and
on hand-made events."""

import pathlib

import pytest

from bench import trace as tr

RECORDED = pathlib.Path(__file__).parent / "data" / \
    "tpu_v5e_two_programs.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(RECORDED))


def test_union_merges_and_clips():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 38, 20)]
    assert tr.union(ev, 0, 100) == [(0, 15), (30, 58)]
    assert tr.union(ev, 8, 35) == [(8, 15), (30, 35)]
    assert tr.busy_ns(ev, 0, 100) == 43
    assert tr.idle_gaps(ev, 0, 100) == [(15, 30), (58, 100)]


def test_attribute_takes_the_innermost_span():
    host = [("bench.tick", 0, 100), ("bench.submit", 10, 10),
            ("bench.wait", 100, 50)]
    got = tr.attribute([(12, 14), (50, 60), (120, 130), (200, 210)], host)
    assert got == [("bench.submit", 2), ("bench.tick", 10),
                   ("bench.wait", 10), ("idle", 10)]


def test_recorded_trace_programs(recorded):
    assert len(recorded.devices) == 1
    dev = recorded.devices[0]
    ex = tr.executions(dev.modules, 0, float("inf"))
    assert len(ex) == 2                       # two fingerprints, one name
    assert {tr.program(n) for n in ex} == {"jit__model_decode"}
    assert sorted(len(v) for v in ex.values()) == [3, 3]
    for runs in ex.values():
        assert all(0 < d < 1e6 for d in runs)       # microseconds each


def test_recorded_trace_busy_and_idle(recorded):
    dev = recorded.devices[0]
    lo = min(s for _, s, _ in recorded.host)
    hi = max(s + d for _, s, d in recorded.host)
    busy = tr.busy_ns(dev.ops, lo, hi)
    total = sum(d for _, s, d in dev.ops)
    assert 0 < busy <= total
    gaps = tr.idle_gaps(dev.ops, lo, hi)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(hi - lo)
    named = tr.attribute(gaps, recorded.host)
    longest = max(named, key=lambda g: g[1])
    assert longest[0] == "bench.wait"         # the 10 ms sleeps


def test_recorded_trace_top_ops(recorded):
    dev = recorded.devices[0]
    top = tr.top_ops(dev, 0, float("inf"))
    assert top and all(n.startswith("jit__model_decode/") for n, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])
    assert sum(ns for _, ns in top) <= sum(d for _, _, d in dev.ops)


def test_top_ops_leave_out_loop_containers():
    dev = tr.Device("d", ops=[("%while.3 = (f32[]) while(...)", 0, 100),
                              ("%fusion.1 = f32[8] fusion(...)", 10, 30),
                              ("%copy.2 = f32[8] copy(...)", 50, 20)],
                    modules=[("jit_step(1)", 0, 100)])
    assert tr.top_ops(dev, 0, 100) == [("jit_step/fusion.1", 30),
                                       ("jit_step/copy.2", 20)]


def test_marker():
    host = [("bench.tick", 5, 1), ("bench.window_start", 7, 0)]
    assert tr.marker(host, "bench.window_start") == 7
    assert tr.marker(host, "bench.window_end") is None


def recorded_context(trace, cfg):
    """A reader's context over the recorded trace: its two decode programs
    (the busier one as the cloud's), with a fixed set of requests and
    ticks on the host clock.  The trace's programs are small and ``cfg``
    is not, so the values stand for nothing: they only have to repeat."""
    from bench import metrics
    from bench.drive import Record, Tick
    recs = []
    for i in range(12):
        r = Record(i, "window", 0.5 * i, 128 + 61 * i, 8 + 3 * i)
        r.n_out = r.max_new
        r.output = [1] * r.n_out
        r.t_first, r.t_done = 0.5 * i + 0.3, 0.5 * i + 0.3 + 0.03 * r.n_out
        r.tier = "edge" if i % 5 == 0 else "cloud"
        recs.append(r)
    ticks = [Tick(0.25 * k, 0.25 * k + 0.2, 4, 1, 0) for k in range(40)]
    lo = min(s for _, s, _ in trace.host)
    hi = max(s + d for _, s, d in trace.host)
    mods = tr.executions(trace.devices[0].modules, lo, hi)
    cloud = max(mods, key=lambda n: sum(mods[n]))
    return metrics.Context(
        cfg=cfg, records=recs, ticks=ticks, window=(0.0, 8.0),
        due_window=(0.0, 8.0), stop=9.0, compiles=0, cloud_tier="cloud",
        peaks={"flops": 197e12, "hbm_bytes_per_s": 819e9,
               "hbm_bytes": 16e9},
        trace=trace, trace_window=(lo, hi), cloud_decode=cloud)


READERS = ("cloud_decode_ms", "decode_roofline", "idle_share", "mfu",
           "offload_share", "prefill_us_per_token", "rows_per_step",
           "window_compiles")


@pytest.mark.parametrize("config", ["stablelm-1.6b", "qwen2.5-14b-12l"])
def test_readers_on_the_recorded_trace_match_the_parent(recorded, config):
    """Every reader reads what the parent commit's readers read on the same
    context (``data/dense_golden.json``, recorded from the parent)."""
    import json

    from bench import metrics, spec
    golden = json.loads((RECORDED.parent / "dense_golden.json").read_text())
    cfg = spec.read_json(spec.ROOT / "bench" / "configs" / f"{config}.json")
    ctx = recorded_context(recorded, cfg)
    got = {m: metrics.reader(m)(ctx) for m in READERS}
    assert got == golden["readers"][config]
    assert got["decode_roofline"] is not None and got["mfu"] is not None


def test_program_spans_and_counters_reach_the_readers(recorded):
    """The recorded trace predates the program's spans: ``program`` is
    empty there and ``host`` keeps only ``bench.*``.  A context hands
    ``repro.*`` spans in its window and endpoint counters on."""
    from bench import metrics
    assert recorded.program == []
    assert all(n.startswith("bench.") for n, _, _ in recorded.host)
    trace = tr.Trace([], [], [("repro.decode", 5, 3, {"rows": 2}),
                              ("repro.step", 6, 1, {}),
                              ("repro.decode", 50, 3, {"rows": 4})])
    ctx = metrics.Context(
        cfg={}, records=[], ticks=[], window=(0.0, 1.0),
        due_window=(0.0, 1.0), stop=1.0, compiles=0, cloud_tier="cloud",
        peaks={}, trace=trace, trace_window=(0, 40),
        counters={"cloud": {"decode_calls": (3, 9)}})
    assert ctx.spans("repro.decode") == [("repro.decode", 5, 3, {"rows": 2})]
    assert ctx.counter("cloud", "decode_calls") == (3, 9)
    assert ctx.counter("cloud", "no_such") == (None, None)
    assert ctx.counter("edge", "decode_calls") == (None, None)
