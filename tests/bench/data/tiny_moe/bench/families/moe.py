"""The ``moe`` family (Qwen2-MoE's block), as a configuration brings its
own: the dense family's attention, then a routed FFN of ``num_experts``
SwiGLU experts, ``num_experts_per_tok`` a token by a softmax router whose
top weights are renormalised, beside shared experts (one SwiGLU of
``shared_expert_intermediate_size``) behind a sigmoid gate.  It maps the
configuration onto the program's ``moe`` family.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from bench.cost import DTYPE_BYTES
from bench.families import dense

FAN_IN = {
    **{k: v for k, v in dense.FAN_IN.items() if not k.startswith("mlp/")},
    "moe/router": (0,), "moe/experts/wi": (1,), "moe/experts/wg": (1,),
    "moe/experts/wo": (1,), "moe/shared/wi": (0,), "moe/shared/wg": (0,),
    "moe/shared/wo": (0,), "moe/shared/gate": (0,),
}

COUNTERS = dense.COUNTERS

kv_bytes_per_token = dense.kv_bytes_per_token


def prefill_length(cfg: dict, length: int) -> int:
    """The program prefills an MoE prompt at its own length: padding
    tokens would take places in the experts' queues."""
    return length


def program_config(cfg: dict):
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k weights")
    return dataclasses.replace(
        dense.program_config(cfg), family="moe",
        num_experts=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        moe_d_ff=int(cfg["moe_intermediate_size"]),
        num_shared_experts=int(cfg["num_shared_experts"]),
        shared_d_ff=int(cfg["shared_expert_intermediate_size"]),
        capacity_factor=float(cfg["capacity_factor"]))


def _params(cfg: dict):
    """(parameters of a layer outside its routed experts, of one routed
    expert, experts per token, experts)."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    Hkv, D = int(cfg["num_key_value_heads"]), dense.head_dim(cfg)
    norm = d * (2 if cfg["norm"] == "layernorm" else 1)
    E = int(cfg["num_experts"])
    attn = d * (H + 2 * Hkv) * D + H * D * d + norm
    if cfg["qkv_bias"]:
        attn += (H + 2 * Hkv) * D
    shared = 3 * d * int(cfg["shared_expert_intermediate_size"]) + d
    outside = attn + d * E + shared + norm
    return (outside, 3 * d * int(cfg["moe_intermediate_size"]),
            int(cfg["num_experts_per_tok"]), E)


def decode_bytes(cfg: dict, executions: int, contexts: Sequence[int]
                 ) -> int:
    """Each execution reads every weight outside the routed experts, the
    head, and at least ``num_experts_per_tok`` experts of each layer; how
    many more depends on how its rows route, which the configuration does
    not fix, so the count is a lower bound.  Each row-step adds its own
    bytes as in the dense family."""
    outside, expert, k, E = _params(cfg)
    L = int(cfg["num_hidden_layers"])
    weights = (L * (outside + min(k, E) * expert) + dense.head_params(cfg)) \
        * DTYPE_BYTES[cfg["torch_dtype"]]
    return (executions * weights
            + sum(dense.decode_row_bytes(cfg, c) for c in contexts))


def _active(cfg: dict) -> int:
    outside, expert, k, _ = _params(cfg)
    return outside + k * expert


def token_flops(cfg: dict, context: int, head: bool = True) -> int:
    """Two operations per weight a token uses (its own experts only), QK^T
    and PV over the context, and the head where its logits are needed."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    L, V = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    n = 2 * L * _active(cfg) + 4 * L * H * dense.head_dim(cfg) * context
    return n + (2 * V * d if head else 0)


def prefill_flops(cfg: dict, prompt: int) -> int:
    """Every position through the layers, causal attention, and the head
    at the last position only."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    L, V = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    return (2 * L * _active(cfg) * prompt
            + 4 * L * H * dense.head_dim(cfg) * prompt * (prompt + 1) // 2
            + 2 * V * d)
