"""Model families behind the configuration's ``"family"`` key.

* The dense family gives every number the parent commit's
  ``bench/model.py``, ``bench/cost.py`` and ``bench/weights.py`` gave
  (``data/dense_golden.json``, recorded from them before the dense family
  moved into ``bench/families/dense.py``).  The weights are pinned in two
  parts: each leaf's shape and rule (kind and scale) at the configurations'
  own sizes, and a hash of every leaf ``make_params`` draws for the same
  leaf names at a small size (``small``), since the values are a function
  of the seed, the leaf's name, shape and rule alone.
* The dispatch: no ``"family"`` is dense, an unknown family names the
  path it looked for, and ``check.logit_gap`` calls the reference that the
  configuration names.
* ``data/tiny_moe`` brings a family, a reference, a cell and its traffic
  of its own, and nothing under ``bench/`` names it: its clean run is
  correct, its fp8 control and a served fault are not, and the endpoint's
  counters reach the readers.
"""

import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import check, cost, model, spec, weights
from bench.families import dense

DATA = pathlib.Path(__file__).parent / "data"
GOLDEN = json.loads((DATA / "dense_golden.json").read_text())
CONFIGS = sorted(GOLDEN["configs"])
TINY_MOE = DATA / "tiny_moe"


def _cfg(name):
    return spec.read_json(spec.ROOT / "bench" / "configs" / f"{name}.json")


def _fields(mcfg):
    import dataclasses
    return {k: (v if isinstance(v, (int, float, str, bool, type(None)))
                else repr(v))
            for k, v in dataclasses.asdict(mcfg).items()}


def _leaf_hashes(cfg):
    fam = model.family(cfg)
    params = weights.make_params(
        model.param_shapes(fam.program_config(cfg)), GOLDEN["seed"],
        fam.FAN_IN)
    return {p: hashlib.sha256(np.asarray(a).view(np.uint16).tobytes())
            .hexdigest() for p, a in sorted(params.items())}


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_program_config_shapes_and_rules_match_the_parent(name):
    cfg, want = _cfg(name), GOLDEN["configs"][name]
    assert model.family(cfg).__file__ == dense.__file__
    mcfg = model.program_config(cfg)
    assert _fields(mcfg) == want["program_config"]
    shapes = model.param_shapes(mcfg)
    assert {p: list(s) for p, s in shapes.items()} == want["param_shapes"]
    assert {p: list(weights._leaf_rule(p, s, dense.FAN_IN))
            for p, s in shapes.items()} == want["init"]


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_counts_match_the_parent(name):
    cfg, want = _cfg(name), GOLDEN["configs"][name]
    assert dense.layer_params(cfg) == want["layer_params"]
    assert dense.head_params(cfg) == want["head_params"]
    assert dense.decode_weight_bytes(cfg) == want["decode_weight_bytes"]
    assert [dense.decode_row_bytes(cfg, c) for c in GOLDEN["contexts"]] \
        == want["decode_row_bytes"]
    assert cost.kv_bytes_per_token(cfg) == want["kv_bytes_per_token"]
    assert [cost.decode_bytes(cfg, n, cs) for n, cs in GOLDEN["decode"]] \
        == want["decode_bytes"]
    assert [cost.token_flops(cfg, c) for c in GOLDEN["contexts"]] \
        == want["token_flops"]
    assert [cost.token_flops(cfg, c, head=False)
            for c in GOLDEN["contexts"]] == want["token_flops_no_head"]
    assert [cost.prefill_flops(cfg, p) for p in GOLDEN["prompts"]] \
        == want["prefill_flops"]


@pytest.mark.parametrize("name", CONFIGS + ["tiny"])
def test_dense_leaves_match_the_parent(name):
    if name == "tiny":
        cfg = spec.read_json(DATA / "tiny" / "bench" / "configs"
                             / "tiny.json")
        want = GOLDEN["tiny_leaf_sha256"]
    else:
        small = GOLDEN["configs"][name]["small"]
        cfg = {**_cfg(name), **small}
        want = GOLDEN["configs"][name]["small_leaf_sha256"]
    assert _leaf_hashes(cfg) == want


def _no_family(tmp_path):
    cfg = {k: v for k, v in _cfg("qwen2.5-14b-12l").items()
           if k != "family"}
    assert model.family(cfg, tmp_path).__file__ == dense.__file__
    assert _fields(model.program_config(cfg, tmp_path)) == \
        GOLDEN["configs"]["qwen2.5-14b-12l"]["program_config"]


def _unknown_family(tmp_path):
    cfg = {**_cfg("qwen2.5-14b-12l"), "family": "no_such_family"}
    with pytest.raises(FileNotFoundError) as e:
        model.family(cfg, tmp_path)
    for root in (tmp_path, spec.ROOT):
        assert str(root.resolve() / "bench" / "families"
                   / "no_such_family.py") in str(e.value)


def _stub_reference(tmp_path):
    from bench.drive import Record
    (tmp_path / "bench").mkdir()
    (tmp_path / "bench" / "stub_reference.py").write_text(
        "import numpy as np\n"
        "CALLS = []\n"
        "def served_gaps(cfg, params, prompt, served, mode='float32'):\n"
        "    CALLS.append((len(prompt), len(served), mode))\n"
        "    gap = np.full(len(served), 0.125 * len(CALLS))\n"
        "    return gap, gap\n")
    cfg = {"reference": "bench/stub_reference.py"}
    r = Record(0, "window", 0.0, 3, 2, n_out=2, output=[5, 6],
               prompt=[1, 2, 3])
    assert check.logit_gap(cfg, None, [r, r], "fp8", tmp_path) == 0.25
    stub = spec.load_module(tmp_path, "bench/stub_reference.py")
    assert stub.CALLS == [(3, 2, "fp8"), (3, 2, "fp8")]


@pytest.mark.parametrize("case", [_no_family, _unknown_family,
                                  _stub_reference],
                         ids=["no_family_is_dense", "unknown_family_raises",
                              "stub_reference_is_called"])
def test_family_and_reference_dispatch(case, tmp_path):
    case(tmp_path)


# --- a family that the harness has never seen: data/tiny_moe ------------

def _moe_cell():
    return spec.load_cell("tiny_moe.burst", root=TINY_MOE)


@pytest.fixture(scope="module")
def moe_served():
    """One served tiny_moe run, for the control and the counters."""
    from bench import run
    cell = _moe_cell()
    return cell, run.serve(cell, 2915000007, 2.0, False)


def test_tiny_moe_clean_run_is_correct():
    from bench import run
    cell = _moe_cell()
    assert model.family(cell.config, cell.root).__file__ == str(
        TINY_MOE.resolve() / "bench" / "families" / "moe.py")
    assert model.program_config(cell.config, cell.root).family == "moe"
    res = run.run_cell(cell, 2915000005, 2.0, False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["sample"]["tiers_unchecked"] == []


def test_tiny_moe_fp8_control_fails_the_limit(moe_served):
    from bench import run
    cell, sv = moe_served
    program, _ = run.judge(cell, sv, 2915000007)
    control, _ = run.judge(cell, sv, 2915000007, mode="fp8")
    assert check.passes(program), program
    assert not check.passes(control), control


def test_tiny_moe_counters_advance_across_the_window(moe_served):
    from bench import metrics
    cell, sv = moe_served
    res = sv.res
    ctx = metrics.Context(
        cfg=cell.config, records=res.records, ticks=res.ticks,
        window=res.marks, due_window=res.window, stop=res.end, compiles=0,
        cloud_tier="cloud", peaks={}, root=cell.root, counters=res.counters)
    start, end = ctx.counter("cloud", "decode_calls")
    assert 0 <= start < end
    rows = ctx.counter("cloud", "decode_rows")
    assert rows[1] - rows[0] >= end - start
    # the readers price the window through the fixture's own family
    ctx.peaks = {"flops": 197e12}
    fam = model.family(cell.config, cell.root)
    steps = ctx.decode_steps()
    assert steps and metrics.reader("mfu")(ctx) > 0
    assert cost.token_flops(cell.config, 9, root=cell.root) == \
        fam.token_flops(cell.config, 9) != dense.token_flops(cell.config, 9)


def test_tiny_moe_shared_experts_left_out_is_not_correct(monkeypatch):
    """The served MoE layer drops its shared experts' output (as if their
    sigmoid gate read 0); the reference keeps them."""
    import jax.numpy as jnp

    from bench import run
    from repro.models import moe
    orig = moe.moe_ffn

    def moe_ffn(cfg, p, x, prefix="moe/"):
        p = {**p, prefix + "shared/wo": jnp.zeros_like(
            p[prefix + "shared/wo"])}
        return orig(cfg, p, x, prefix)
    monkeypatch.setattr(moe, "moe_ffn", moe_ffn)
    res = run.run_cell(_moe_cell(), 2915000005, 2.0, False)
    assert not res["correct"], res["checks"]
