"""The operation and byte counts against hand counts for stablelm-1.6b,
through the dispatchers of ``bench/cost.py`` and the dense family's own
parameter and byte counts."""

import pytest

from bench import cost, spec
from bench.families import dense

SL = spec.read_json(spec.ROOT / "bench" / "configs" / "stablelm-1.6b.json")
QW = spec.read_json(spec.ROOT / "bench" / "configs" / "qwen2.5-14b-12l.json")


def test_stablelm_layer_and_head_params():
    d, F = 2048, 5632
    per_layer = 4 * d * d + 3 * d * F + 3 * d + 2 * 2 * d
    assert dense.layer_params(SL) == per_layer
    assert dense.head_params(SL) == 100352 * d + 2 * d
    # the published model has 1.64e9 parameters with its embedding
    total = 24 * per_layer + dense.head_params(SL) + 100352 * d
    assert total == pytest.approx(1.645e9, rel=2e-3)


def test_stablelm_kv_and_decode_bytes():
    assert cost.kv_bytes_per_token(SL) == 24 * 2 * 32 * 64 * 2 == 196608
    assert dense.decode_weight_bytes(SL) == 2 * (
        24 * dense.layer_params(SL) + dense.head_params(SL))
    assert dense.decode_row_bytes(SL, 1000) == (196608 * 1000 + 2048 * 2
                                                + 4 * 100352)


def test_stablelm_flops():
    lp = dense.layer_params(SL)
    assert cost.token_flops(SL, 10) == (2 * 24 * lp + 4 * 24 * 32 * 64 * 10
                                        + 2 * 100352 * 2048)
    assert cost.token_flops(SL, 10, head=False) == (2 * 24 * lp
                                                    + 4 * 24 * 32 * 64 * 10)
    # prefill = every position through the layers + the head once
    p = 7
    assert cost.prefill_flops(SL, p) == (
        sum(cost.token_flops(SL, c, head=False) for c in range(1, p + 1))
        + 2 * 100352 * 2048)


def test_qwen_stage_weights():
    # 12 layers of 5120-wide GQA with bias, the head: about 8.1 GB to read
    assert dense.decode_weight_bytes(QW) == pytest.approx(8.16e9, rel=1e-2)
    assert cost.kv_bytes_per_token(QW) == 12 * 2 * 8 * 128 * 2


def test_unknown_chip_is_an_error():
    assert cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peaks("cpu")
