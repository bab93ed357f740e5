"""Plain reference of the ``moe`` family's decoder.

Straight ``jax.numpy`` in float32 at ``precision="highest"``, one layer at
a time, with no cache, kernels, batching or capacity: pre-norm attention
(rotary on every head dim, rotate-half convention; grouped key/value
heads; q/k/v bias; softmax over the causal prefix), then a pre-norm FFN
in which a softmax router over every expert picks ``num_experts_per_tok``
of them for each token, their weights renormalised to sum to one, each a
SwiGLU (``silu(h @ wi) * (h @ wg) @ wo``), plus the shared experts' SwiGLU
times ``sigmoid(h @ shared/gate)``; then a final RMSNorm and the head.  No
token is dropped.  It imports nothing of the program and reads the weights
by their names.

``mode="fp8"`` is the control: every matmul input rounded to float8 e4m3
with one amax scale per tensor, accumulation in float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


def _round(a, mode):
    if mode == "float32":
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _F8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(spec, a, b, mode):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x (S, H, D): rotate every dim of each head."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = D // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) \
        * sin


def _swiglu(h, wi, wg, wo, spec_in, spec_out, mode):
    return _dot(spec_out, jax.nn.silu(_dot(spec_in, h, wi, mode))
                * _dot(spec_in, h, wg, mode), wo, mode)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(static, mode, x, p):
    H, Hkv, D, eps, theta, k = static
    f = {n: v.astype(jnp.float32) for n, v in p.items()}
    S = x.shape[0]
    h = _rms(x, f["attn/norm/scale"], eps)
    q = _dot("sd,dhk->shk", h, f["attn/wq"], mode) + f["attn/bq"]
    kk = _dot("sd,dhk->shk", h, f["attn/wk"], mode) + f["attn/bk"]
    v = _dot("sd,dhk->shk", h, f["attn/wv"], mode) + f["attn/bv"]
    q, kk = _rotary(q, theta), _rotary(kk, theta)
    kk = jnp.repeat(kk, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = _dot("qhd,khd->hqk", q, kk, mode) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    x = x + _dot("qhd,hdm->qm", _dot("hqk,khd->qhd", w, v, mode),
                 f["attn/wo"], mode)
    h = _rms(x, f["moe/norm/scale"], eps)
    probs = jax.nn.softmax(_dot("sd,de->se", h, f["moe/router"], mode), -1)
    top, idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(
        top / jnp.sum(top, -1, keepdims=True))
    experts = _swiglu(h, f["moe/experts/wi"], f["moe/experts/wg"],
                      f["moe/experts/wo"], "sd,edf->sef", "sef,efd->sed",
                      mode)
    routed = jnp.einsum("se,sed->sd", gates, experts, precision=HIGHEST)
    shared = _swiglu(h, f["moe/shared/wi"], f["moe/shared/wg"],
                     f["moe/shared/wo"], "sd,df->sf", "sf,fd->sd", mode)
    gate = jax.nn.sigmoid(_dot("sd,do->so", h, f["moe/shared/gate"], mode))
    return x + routed + gate * shared


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(eps, mode, x, scale, w):
    return _dot("sd,vd->sv", _rms(x, scale.astype(jnp.float32), eps),
                w.astype(jnp.float32), mode)


def logits(cfg: dict, params: Dict[str, jax.Array], tokens: np.ndarray,
           first_row: int, mode: str = "float32") -> jax.Array:
    """(S - first_row, V) float32 logits at positions ``first_row ..
    len(tokens) - 1`` of one sequence, computed layer by layer."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = float(cfg["rms_norm_eps"])
    static = (H, Hkv, cfg["hidden_size"] // H, eps, float(cfg["rope_theta"]),
              int(cfg["num_experts_per_tok"]))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        for layer in range(int(cfg["num_hidden_layers"])):
            p = {k[len("layers/"):]: v[layer] for k, v in params.items()
                 if k.startswith("layers/")}
            x = _layer(static, mode, x, p)
        return _head(eps, mode, x[first_row:], params["final_norm/scale"],
                     params["lm_head"])


def served_gaps(cfg: dict, params, prompt: np.ndarray, served: np.ndarray,
                mode: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """How far below the reference's best each served token's logit lies,
    at every position where a token was served, and the same for the
    token that ``mode``'s logits put first there."""
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
