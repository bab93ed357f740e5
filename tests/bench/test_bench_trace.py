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
