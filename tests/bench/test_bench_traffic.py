"""The arrival schedule: fixed by the seed, and the same work for every
seed."""

import collections

import numpy as np
import pytest

from bench import spec, traffic

BURST = spec.read_json(spec.ROOT / "bench" / "traffic" / "burst.json")
HEAVY = spec.read_json(spec.ROOT / "bench" / "traffic" / "decode_heavy.json")


def _key(arrivals):
    return [(a.due_s, a.prompt_len, a.max_new, a.phase, a.tokens.tobytes())
            for a in arrivals]


@pytest.mark.parametrize("mix", [BURST, HEAVY], ids=["burst", "heavy"])
def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, 2.5, 2 ** 33 + 5, 48.0, 1000)
    b = traffic.schedule(mix, 2.5, 2 ** 33 + 5, 48.0, 1000)
    assert _key(a) == _key(b)


@pytest.mark.parametrize("mix", [BURST, HEAVY], ids=["burst", "heavy"])
def test_other_seed_other_order_same_window_work(mix):
    a = traffic.schedule(mix, 2.5, 1, 48.0, 1000)
    b = traffic.schedule(mix, 2.5, 2, 48.0, 1000)
    assert _key(a) != _key(b)
    wa = [x for x in a if x.phase == "window"]
    wb = [x for x in b if x.phase == "window"]
    mean = (0.75 if mix is BURST else 1.25) * 2.5
    assert len(wa) == len(wb) == round(mean * 48.0)
    assert sorted(x.prompt_len for x in wa) == sorted(x.prompt_len
                                                      for x in wb)
    assert sorted(x.max_new for x in wa) == sorted(x.max_new for x in wb)
    assert [x.due_s for x in a] == [x.due_s for x in b]


def test_window_and_phases_are_where_they_belong():
    arr = traffic.schedule(BURST, 3.0, 9, 48.0, 100)
    pre, post = BURST["pre_s"], BURST["post_s"]
    for a in arr:
        lo, hi = {"pre": (0, pre), "window": (pre, pre + 48),
                  "post": (pre + 48, pre + 48 + post)}[a.phase]
        assert lo <= a.due_s < hi
        assert BURST["prompt"]["min"] <= a.prompt_len <= BURST["prompt"]["max"]
        assert BURST["output"]["min"] <= a.max_new <= BURST["output"]["max"]
        assert len(a.tokens) == a.prompt_len and a.tokens.max() < 100
    dues = [a.due_s for a in arr]
    assert dues == sorted(dues)


def test_bursts_carry_their_share():
    """On/off: 4 s of every 12 at 1.75x, the rest at 0.25x of the knee."""
    segs = traffic.rate_segments(BURST["arrivals"], 4.0, 5.0, 12.0, 60.0)
    on = sum(b - a for a, b, r in segs if r == 7.0)
    off = sum(b - a for a, b, r in segs if r == 1.0)
    assert on == pytest.approx(16.0) and off == pytest.approx(32.0)
    counts = traffic.segment_counts(segs)
    assert sum(counts) == round(4.0 * 0.75 * 48)


def test_prefill_shapes_cover_every_batch_the_schedule_can_form():
    arr = traffic.schedule(BURST, 8.0, 3, 48.0, 100)
    shapes = traffic.prefill_shapes(arr)
    same = collections.Counter(a.prompt_len for a in arr)
    for length, count in same.items():
        longest, most = shapes[traffic.pow2(length)]
        assert length <= longest <= traffic.pow2(length)
        assert count <= most
    assert max(most for _, most in shapes.values()) == max(same.values())
    assert shapes == traffic.prefill_shapes(
        traffic.schedule(BURST, 8.0, 4, 48.0, 100))


def test_lengths_follow_the_lognormal():
    spec_ = {"median": 384, "sigma": 0.5, "min": 128, "max": 1024}
    n = traffic.lengths(spec_, 1001)
    assert n[500] == pytest.approx(384, abs=5)
    assert n.min() >= 128 and n.max() <= 1024
    assert list(n) == sorted(n)


def test_pow2():
    assert [traffic.pow2(n) for n in (1, 2, 3, 128, 129, 1000, 1024)] == \
        [1, 2, 4, 128, 256, 1024, 1024]
