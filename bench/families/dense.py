"""The dense decoder family: pre-norm attention (grouped key/value heads,
optional q/k/v bias) and a SwiGLU MLP in every layer.

The configuration files keep the source's own key names (``hidden_size``,
``num_hidden_layers``, ...) so they can be read against the published
``config.json``; ``program_config`` maps them onto the program.  The
operations and bytes count what a decoder step must do, not what a
compiled program happens to do, so they read the same whatever implements
the step.  Weights and the key/value cache are counted in the
configuration's ``torch_dtype``; logits in float32.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from bench.cost import DTYPE_BYTES
from bench.traffic import pow2

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

FAN_IN = {
    "lm_head": (1,),
    "attn/wq": (0,), "attn/wk": (0,), "attn/wv": (0,), "attn/wo": (0, 1),
    "mlp/wi": (0,), "mlp/wg": (0,), "mlp/wo": (0,),
}

COUNTERS = ("decode_calls", "decode_rows", "prefill_total_tokens",
            "prefill_computed_tokens")


def norm_eps(cfg: dict) -> float:
    return float(cfg.get("layer_norm_eps", cfg.get("rms_norm_eps")))


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim",
                       cfg["hidden_size"] // cfg["num_attention_heads"]))


def program_config(cfg: dict):
    """The ``repro.models.common.ModelConfig`` that serves ``cfg``."""
    from repro.models.common import ModelConfig
    if cfg.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the program applies rotary to every head dim")
    dt = DTYPES[cfg["torch_dtype"]]
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=head_dim(cfg), d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), qkv_bias=bool(cfg["qkv_bias"]),
        rope_theta=float(cfg["rope_theta"]), activation="swiglu",
        norm_type=cfg["norm"], norm_eps=norm_eps(cfg),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=dt, compute_dtype=dt)


def prefill_length(cfg: dict, length: int) -> int:
    """The program pads a dense prompt to the next power of two (causal
    masking keeps the padded tail inert)."""
    return pow2(length)


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


def decode_bytes(cfg: dict, executions: int, contexts: Sequence[int]
                 ) -> int:
    """Every weight once per execution, and each row-step's own bytes: a
    dense step reads the same weights however many rows share it."""
    return (executions * decode_weight_bytes(cfg)
            + sum(decode_row_bytes(cfg, c) for c in contexts))


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
