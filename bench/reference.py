"""Plain reference of the dense decoder the configurations describe.

Straight ``jax.numpy`` in float32 at ``precision="highest"``, one layer at
a time, with no cache, kernels or batching: pre-norm attention (rotary on
the first ``partial_rotary_factor`` of each head, rotate-half convention;
grouped key/value heads; optional q/k/v bias; softmax over the causal
prefix) and a pre-norm SwiGLU MLP, then a final norm and the output head.
It imports nothing of the program and reads the weights by their names:
``silu(h @ mlp/wi) * (h @ mlp/wg) @ mlp/wo`` is the MLP those names hold.

``mode="fp8"`` is the control: every matmul input (weights and
activations, attention's included) rounded to float8 e4m3 with one
amax scale per tensor, accumulation in float32 -- the step below the
configurations' bfloat16 that a later change might take.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: sequences are padded to a multiple of this many tokens (fewer shapes)
PAD_TO = 256
_F8_MAX = 448.0


def _round(a: jax.Array, mode: str) -> jax.Array:
    if mode == "float32":
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _F8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(spec: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=HIGHEST)


def _norm(x, scale, bias, eps):
    if bias is None:                                   # RMSNorm
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotary(x: jax.Array, theta: float, rot: int) -> jax.Array:
    """x (S, H, D): rotate the first ``rot`` dims of each head."""
    S = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]   # (S, rot/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, rest], -1)


def _static(cfg: dict) -> Tuple:
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg.get("head_dim", cfg["hidden_size"] // H)
    eps = float(cfg.get("layer_norm_eps", cfg.get("rms_norm_eps")))
    rot = int(round(D * cfg.get("partial_rotary_factor", 1.0)))
    return (H, Hkv, D, eps, float(cfg["rope_theta"]), rot,
            cfg["norm"] == "layernorm", bool(cfg["qkv_bias"]))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(static, mode, x, p):
    H, Hkv, D, eps, theta, rot, ln, bias = static
    f = {k: v.astype(jnp.float32) for k, v in p.items()}
    S = x.shape[0]
    h = _norm(x, f["attn/norm/scale"], f.get("attn/norm/bias") if ln else
              None, eps)
    q = _dot("sd,dhk->shk", h, f["attn/wq"], mode)
    k = _dot("sd,dhk->shk", h, f["attn/wk"], mode)
    v = _dot("sd,dhk->shk", h, f["attn/wv"], mode)
    if bias:
        q, k, v = q + f["attn/bq"], k + f["attn/bk"], v + f["attn/bv"]
    q, k = _rotary(q, theta, rot), _rotary(k, theta, rot)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = _dot("qhd,khd->hqk", q, k, mode) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _dot("hqk,khd->qhd", w, v, mode)
    x = x + _dot("qhd,hdm->qm", o, f["attn/wo"], mode)
    h = _norm(x, f["mlp/norm/scale"], f.get("mlp/norm/bias") if ln else
              None, eps)
    a = jax.nn.silu(_dot("sd,df->sf", h, f["mlp/wi"], mode)) \
        * _dot("sd,df->sf", h, f["mlp/wg"], mode)
    return x + _dot("sf,fd->sd", a, f["mlp/wo"], mode)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(static, mode, x, scale, bias, w):
    h = _norm(x, scale.astype(jnp.float32),
              None if bias is None else bias.astype(jnp.float32), static)
    return _dot("sd,vd->sv", h, w.astype(jnp.float32), mode)


def logits(cfg: dict, params: Dict[str, jax.Array], tokens: np.ndarray,
           first_row: int, mode: str = "float32") -> jax.Array:
    """(S - first_row, V) float32 logits at positions ``first_row ..
    len(tokens) - 1`` of one sequence, computed layer by layer."""
    static = _static(cfg)
    S = len(tokens)
    Sp = -(-S // PAD_TO) * PAD_TO
    ids = np.zeros(Sp, np.int32)
    ids[:S] = tokens
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
        for layer in range(int(cfg["num_hidden_layers"])):
            p = {k[len("layers/"):]: v[layer] for k, v in params.items()
                 if k.startswith("layers/")}
            x = _layer(static, mode, x, p)
        head = params["embed" if cfg["tie_word_embeddings"] else "lm_head"]
        V = head.shape[0]
        chunks = 8 if V % 8 == 0 else 1
        c = V // chunks
        bias = (params["final_norm/bias"] if cfg["norm"] == "layernorm"
                else None)
        rows = x[first_row:S]
        return jnp.concatenate(
            [_head(static[3], mode, rows, params["final_norm/scale"], bias,
                   head[i * c:(i + 1) * c]) for i in range(chunks)], -1)


def served_gaps(cfg: dict, params, prompt: np.ndarray, served: np.ndarray,
                mode: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """How far below the reference's best each served token's logit lies,
    at every position where a token was served, and the same for the
    token that ``mode``'s logits put first there (the control's reading;
    equal to the first for ``mode="float32"``).  The reference is fed the
    prompt and the served tokens, so every position sees what the server
    saw."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    ref = logits(cfg, params, seq, len(prompt) - 1)
    best = jnp.max(ref, -1)
    rows = jnp.arange(len(served))
    gap = best - ref[rows, jnp.asarray(served, jnp.int32)]
    if mode == "float32":
        return np.asarray(gap), np.asarray(gap)
    low = logits(cfg, params, seq, len(prompt) - 1, mode)
    ctl = best - ref[rows, jnp.argmax(low, -1)]
    return np.asarray(gap), np.asarray(ctl)
