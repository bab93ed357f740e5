"""The runtime's spans, counters and stamps.

A tiny two-tier continuum serves a few requests under ``jax.profiler`` on
the CPU backend; its trace is read back and every span of the documented
table must be there, nested under its documented parent, with its args.
The endpoints' counters are held to the tick records and the requests,
and the stamps to their order.  With no profiler running, the spans must
format no strings and build no lists.
"""

import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import configs
from repro.core.replication import AutoscalingPolicy, FunctionSpec
from repro.models import model_zoo
from repro.platform import Continuum, Request, TierConfig
from repro.serving import engine, tiers
from repro.serving.engine import Endpoint

#: span -> the span it runs inside (``test.tick`` wraps each tick here)
PARENT = {
    "repro.control": "test.tick",
    "repro.route": "test.tick",
    "repro.scrape": "test.tick",
    "repro.step": "test.tick",
    "repro.requeue": "test.tick",
    "repro.decode": "repro.step",
    "repro.decode.wait": "repro.decode",
    "repro.sample": "repro.decode",
    "repro.retire": "repro.step",
    "repro.admit": "repro.step",
    "repro.prefill": "repro.admit",
    "repro.prefill.wait": "repro.prefill",
}

#: the ints each span carries
INT_ARGS = {"repro.route": {"n"}, "repro.step": {"i"},
            "repro.decode": {"rows", "fold"}, "repro.retire": {"n"},
            "repro.admit": {"rows"}, "repro.requeue": {"n"},
            "repro.prefill": {"L", "Lb", "G", "Bp"}}


@pytest.fixture(scope="module")
def cc():
    """Edge 2 slots + cloud 4 behind a 50% static split, 3 steps a tick,
    so that rows stay resident across ticks and both tiers decode."""
    cfg = configs.get_smoke_config("stablelm-1.6b")
    params = model_zoo.init(jax.random.PRNGKey(0), cfg)
    c = Continuum(edge=TierConfig(slots=2, max_len=64),
                  cloud=TierConfig(slots=4, max_len=64),
                  policy=50.0, seed=0, max_steps_per_tick=3)
    c.deploy(FunctionSpec(name="fn", arch="stablelm-1.6b",
                          autoscaling=AutoscalingPolicy()), cfg, params)
    return c


def _serve(cc, rid0, n=8):
    """Submit ``n`` requests (two prompt lengths, 2-6 new tokens), tick
    until all are served; returns the requests and the tick records."""
    reqs = [Request(rid=rid0 + i,
                    tokens=np.arange(5 + 3 * (i % 2), dtype=np.int32),
                    max_new=2 + i % 5) for i in range(n)]
    recs = []
    for k, r in enumerate(reqs):
        cc.submit("fn", r)
        if k % 3 == 2:
            with TraceAnnotation("test.tick"):
                recs.append(cc.tick())
    while cc.queued or cc.in_flight:
        with TraceAnnotation("test.tick"):
            recs.append(cc.tick())
    assert all(r.output is not None for r in reqs)
    return reqs, recs


def _counters(cc):
    return {t.name: {k: getattr(t.endpoints["fn"], k) for k in (
        "decode_calls", "decode_rows", "prefill_total_tokens",
        "prefill_computed_tokens")} for t in cc.tiers}


def _host_spans(path):
    """(name, start_ns, end_ns, args) of the host plane's repro.* and
    test.* events, by start."""
    pb = next(pathlib.Path(path).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith(("repro.", "test.")))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(cc, tmp_path_factory):
    """One served run under the profiler: its spans, requests, tick
    records, counter deltas and the active rows of every decode call."""
    before = _counters(cc)
    active = []
    orig = Endpoint.decode_all

    def decode_all(self, tokens_by_slot):
        active.append((self, self.active))
        return orig(self, tokens_by_slot)
    mp = pytest.MonkeyPatch()
    mp.setattr(Endpoint, "decode_all", decode_all)
    path = tmp_path_factory.mktemp("trace")
    try:
        with jax.profiler.trace(str(path)):
            reqs, recs = _serve(cc, 100)
    finally:
        mp.undo()
    after = _counters(cc)
    delta = {t: {k: after[t][k] - before[t][k] for k in after[t]}
             for t in after}
    by_ep = {id(t.endpoints["fn"]): t.name for t in cc.tiers}
    rows = [(by_ep[id(ep)], a) for ep, a in active]
    return _host_spans(path), reqs, recs, delta, rows


def _parent(spans, k):
    """The innermost other span that encloses span ``k``."""
    _, s, e, _ = spans[k]
    inside = [(pe - ps, pn) for j, (pn, ps, pe, _) in enumerate(spans)
              if j != k and ps <= s and e <= pe and (pe - ps) >= (e - s)]
    return min(inside)[1] if inside else None


def test_every_span_appears_nested_with_its_args(traced):
    spans = traced[0]
    names = {n for n, *_ in spans}
    assert set(PARENT) <= names, set(PARENT) - names
    for k, (name, _, _, args) in enumerate(spans):
        if name == "test.tick":
            continue
        assert _parent(spans, k) == PARENT[name], (name, args)
        for a in INT_ARGS.get(name, ()):
            assert isinstance(args[a], int) and args[a] >= 0, (name, args)
    steps = [a["i"] for n, *_, a in spans if n == "repro.step"]
    assert 0 in steps and max(steps) >= 2


def test_decode_spans_carry_tier_and_the_active_rows(traced, cc):
    spans, _, _, delta, rows = traced
    dec = [(a["tier"], a["rows"]) for n, *_, a in spans
           if n == "repro.decode"]
    assert dec == rows
    assert {t for t, _ in dec} == {"edge", "cloud"}
    folds = {t.name: t.endpoints["fn"].kv_fold for t in cc.tiers}
    assert all(a["fold"] == folds[a["tier"]] for n, *_, a in spans
               if n == "repro.decode")
    for tier, c in delta.items():
        assert c["decode_calls"] == sum(1 for t, _ in dec if t == tier)
        assert c["decode_rows"] == sum(r for t, r in dec if t == tier)


def test_counters_match_the_ticks_and_the_tokens(traced):
    spans, reqs, recs, delta, _ = traced
    calls = sum(c["decode_calls"] for c in delta.values())
    assert calls >= sum(r["steps"] for r in recs) > 0
    assert sum(c["decode_rows"] for c in delta.values()) == sum(
        len(r.output) - 1 for r in reqs)
    assert sum(c["prefill_total_tokens"] for c in delta.values()) == sum(
        len(r.tokens) for r in reqs)
    computed = sum(a["Bp"] * a["Lb"] for n, *_, a in spans
                   if n == "repro.prefill")
    assert sum(c["prefill_computed_tokens"] for c in delta.values()) \
        == computed
    assert computed >= sum(len(r.tokens) for r in reqs)


def test_stamps_are_in_order(traced):
    reqs = traced[1]
    for r in reqs:
        assert 0 < r.arrival_s <= r.t_admit <= r.t_first <= r.t_done, r.rid


def test_admit_spans_name_their_requests_when_traced(traced):
    spans, reqs = traced[0], traced[1]
    admits = [a for n, *_, a in spans if n == "repro.admit"]
    rids = [int(x) for a in admits for x in str(a["rids"]).split()]
    assert sorted(rids) == sorted(r.rid for r in reqs)
    assert all(len(str(a["rids"]).split()) == a["rows"] for a in admits)
    assert {a["tier"] for a in admits} == {"edge", "cloud"}


def test_untraced_spans_format_nothing_and_build_no_lists(cc, monkeypatch):
    """With no profiler running no span gets ``rids`` or metadata, and
    every arg is an int or a tier's own name (no string is formatted)."""
    calls = []

    class Recorder:
        def __init__(self, name, **args):
            calls.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **args):
            calls.append(("set_metadata", args))

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(tiers, "span", Recorder)
    monkeypatch.setattr(engine, "span", Recorder)
    _serve(cc, 200)
    assert {n for n, _ in calls} == set(PARENT)
    names = [t.name for t in cc.tiers]
    for name, args in calls:
        for v in args.values():
            assert type(v) is int or any(v is t for t in names), (name, args)
