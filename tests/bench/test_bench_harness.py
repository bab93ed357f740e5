"""The harness end to end on the CPU at a tiny size: it refuses to run
without a TPU, a clean run comes out correct, the fp8 control and every
fault a served cell can have come out not correct.

``run_cell`` is the whole run after the look for a chip, so these tests
call it directly on a tiny configuration (``data/tiny``: two layers of
width 64 in bf16, an edge of 2 slots and a cloud of 4)."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import check, spec

ROOT = spec.ROOT
TINY = pathlib.Path(__file__).parent / "data" / "tiny"


def _run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("workload,code", [("stablelm-1.6b.burst", 3),
                                           ("no-such-cell", 2)])
def test_refuses_without_a_chip_or_a_cell(workload, code):
    p = _run_cli("--workload", workload, "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert p.returncode == code, p.stderr[-2000:]
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _tiny_run(seed=5):
    from bench import run
    cell = spec.load_cell("tiny.burst", root=TINY)
    return run.run_cell(cell, seed, 2.0, False)


@pytest.fixture(scope="module")
def served():
    """One served tiny run, with its cell, for the control to judge."""
    from bench import run
    cell = spec.load_cell("tiny.burst", root=TINY)
    return cell, run.serve(cell, 5, 2.0, False)


def test_clean_run_is_correct():
    res = _tiny_run()
    assert res["correct"], res["checks"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-2:] == ["checks", "sample"]
    assert set(res["metrics"]) == {"latency_p95_ms", "ttft_p95_ms",
                                   "tpot_p95_ms", "tokens_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["sample"]["tiers_unchecked"] == []
    json.dumps(res)


def test_fp8_control_fails_the_limit(served):
    """The control's tokens, judged in the served ones' place by the same
    check, come out not correct; the served ones correct."""
    from bench import run
    cell, sv = served
    program, _ = run.judge(cell, sv, 5)
    control, _ = run.judge(cell, sv, 5, mode="fp8")
    assert check.passes(program), program
    assert not check.passes(control), control
    assert control["logit_gap"]["value"] > program["logit_gap"]["value"]


def _rec(rid, phase, tier, n_out=4):
    from bench.drive import Record
    return Record(rid, phase, float(rid), 8, n_out, n_out=n_out, tier=tier,
                  output=[0] * n_out)


def test_sample_checks_a_tier_that_served_only_outside_the_window():
    recs = ([_rec(i, "pre", "edge") for i in range(3)]
            + [_rec(i, "window", "cloud") for i in range(3, 20)])
    picked = check.sample(recs, 7, 12)
    assert {r.tier for r in picked} == {"edge", "cloud"}
    assert sum(r.phase == "window" for r in picked) >= 3
    assert [r.phase for r in picked if r.tier == "edge"] == ["pre"]
    only_cloud = [r for r in recs if r.tier == "cloud"]
    assert {r.tier for r in check.sample(only_cloud, 7, 12)} == {"cloud"}


def _state_unchanged(monkeypatch):
    from repro.models import model_zoo
    orig = model_zoo.decode

    def decode(cfg, params, cache, tokens, t, active=None):
        return orig(cfg, params, cache, tokens, t, active=active)[0], cache
    monkeypatch.setattr(model_zoo, "decode", decode)


def _half_rows_left_out(monkeypatch):
    """The decode step computes the second half of its rows and hands the
    first half their results."""
    from repro.models import model_zoo
    orig = model_zoo.decode

    def decode(cfg, params, cache, tokens, t, active=None):
        import jax.numpy as jnp
        logits, cache = orig(cfg, params, cache, tokens, t, active=active)
        half = tokens.shape[0] // 2
        rows = jnp.arange(tokens.shape[0]) % half + half
        return logits[rows], cache
    monkeypatch.setattr(model_zoo, "decode", decode)


def _token_altered(monkeypatch):
    from repro.serving.engine import Endpoint
    orig = Endpoint.decode_all
    calls = [0]

    def decode_all(self, tokens_by_slot):
        out = orig(self, tokens_by_slot)
        calls[0] += 1
        if calls[0] % 7 == 0:
            s = min(out)
            out[s] = (out[s] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(Endpoint, "decode_all", decode_all)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_rows_left_out,
                                   _token_altered],
                         ids=["state_unchanged", "half_rows_left_out",
                              "token_altered"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _tiny_run()
    assert not res["correct"], res["checks"]


def test_every_cell_finds_its_files_and_readers():
    """Each cell of BENCHMARK.json loads from its data files, its
    configuration's family module and reference load, and each per-layer
    metric it reports has a reader of its own."""
    from bench import metrics, model
    bm = spec.read_json(ROOT / "BENCHMARK.json")
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.knee_rps > 0 and cell.end_to_end and cell.per_layer
        assert callable(model.family(cell.config, cell.root).program_config)
        assert callable(spec.load_module(
            cell.root, cell.config["reference"]).served_gaps)
        for m in cell.per_layer:
            assert callable(metrics.reader(m["name"]))
