"""What the algorithm needs, from the configuration alone, and the chip's
peaks.

The operations and bytes here count what a decoder step must do, not
what a compiled program happens to do, so they read the same whatever
implements the step.  Weights and the key/value cache are counted in the
configuration's ``torch_dtype``; logits in float32.
"""

from __future__ import annotations

#: published peaks per ``device_kind`` (Google Cloud documentation, "TPU
#: v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip)
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def _dims(cfg: dict):
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    Hkv = int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim", d // H))
    return (d, H, Hkv, D, int(cfg["intermediate_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["vocab_size"]))


def layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer: q/k/v/o, the three MLP matrices,
    the q/k/v biases and the two norms."""
    d, H, Hkv, D, F, _, _ = _dims(cfg)
    n = d * (H + 2 * Hkv) * D + H * D * d + 3 * d * F
    if cfg["qkv_bias"]:
        n += (H + 2 * Hkv) * D
    n += 2 * d * (2 if cfg["norm"] == "layernorm" else 1)
    return n


def head_params(cfg: dict) -> int:
    """The output head's matrix and the final norm."""
    d, _, _, _, _, _, V = _dims(cfg)
    return V * d + d * (2 if cfg["norm"] == "layernorm" else 1)


def kv_bytes_per_token(cfg: dict) -> int:
    d, H, Hkv, D, F, L, V = _dims(cfg)
    return L * 2 * Hkv * D * DTYPE_BYTES[cfg["torch_dtype"]]


def decode_weight_bytes(cfg: dict) -> int:
    """Weights one decode execution must read: every layer and the head
    (the embedding is read a row per token, which the per-row count
    ``decode_row_bytes`` carries)."""
    L = int(cfg["num_hidden_layers"])
    return (L * layer_params(cfg) + head_params(cfg)) \
        * DTYPE_BYTES[cfg["torch_dtype"]]


def decode_row_bytes(cfg: dict, context: int) -> int:
    """Bytes one row adds to a decode execution at ``context`` filled
    positions (the new token's included): its keys and values, its
    embedding row and its float32 logits."""
    d, _, _, _, _, _, V = _dims(cfg)
    return (kv_bytes_per_token(cfg) * context
            + d * DTYPE_BYTES[cfg["torch_dtype"]] + 4 * V)


def token_flops(cfg: dict, context: int, head: bool = True) -> int:
    """Operations to push one token through the model attending to
    ``context`` positions (itself included): two per weight of every
    layer, two per weight of the head when its logits are needed, and
    QK^T plus PV over the context."""
    d, H, Hkv, D, F, L, V = _dims(cfg)
    n = 2 * L * layer_params(cfg) + 4 * L * H * D * context
    return n + (2 * V * d if head else 0)


def prefill_flops(cfg: dict, prompt: int) -> int:
    """A prompt's prefill: every position through the layers, causal
    attention, and the head at the last position only."""
    d, H, Hkv, D, F, L, V = _dims(cfg)
    return (2 * L * layer_params(cfg) * prompt
            + 4 * L * H * D * prompt * (prompt + 1) // 2 + 2 * V * d)
