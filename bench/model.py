"""A configuration file as the program's ``ModelConfig``.

The files keep the source's own key names (``hidden_size``,
``num_hidden_layers``, ...) so they can be read against the published
``config.json``; this is the one place that maps them onto the program.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


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


def param_shapes(model_cfg) -> Dict[str, Tuple[int, ...]]:
    """The program's parameter names and shapes for ``model_cfg``."""
    from repro.models import model_zoo
    return {p: tuple(s.shape)
            for p, s in model_zoo.param_table(model_cfg).items()}
