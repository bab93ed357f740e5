"""Attention: GQA flash (chunked online softmax) + single-token decode.

Two execution paths per call site:

* **XLA path** (default; `cfg.use_pallas=False`) — the same blocked
  online-softmax algorithm as the Pallas kernel, expressed with
  ``lax.scan`` over KV chunks (and over Q chunks for long prefill). XLA
  fuses each chunk step; peak memory is O(q_chunk × kv_chunk) instead of
  O(S²). This is what the multi-pod dry-run lowers, so HLO cost analysis
  reflects the flash-style memory behaviour.
* **Pallas path** (`cfg.use_pallas=True`) — ``repro.kernels`` TPU kernels
  (validated on CPU in interpret mode), same math, MXU-aligned tiles.

Masking is positional: every query/key carries an absolute position;
causality, sliding windows (mixtral/hymba) and cache-slot validity
(position < 0 = empty slot) are all expressed as position predicates, so
prefill, decode and rolling caches share one mask rule.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig

NEG_INF = -1e30


def _mask(q_pos: jax.Array, kv_pos: jax.Array, window: Optional[int],
          causal: bool) -> jax.Array:
    """(..., S_q, S_k) bool — True where attention is allowed.

    q_pos: (..., S_q), kv_pos: (..., S_k). Slots with kv_pos < 0 are invalid.
    """
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    ok = kv_pos[..., None, :] >= 0
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    return ok


def _layer_window(cfg: ModelConfig, layer_idx: Optional[jax.Array]) -> Optional[int]:
    """Static sliding-window width for this layer (None = full)."""
    del layer_idx
    return cfg.sliding_window


# --------------------------------------------------------------------------
# Flash attention over full sequences (training / prefill)
# --------------------------------------------------------------------------


def flash_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, *,
                    causal: bool = True,
                    window: Optional[int] = None) -> jax.Array:
    """Blocked online-softmax attention with GQA.

    Args:
      q: (B, S, Hq, D); k, v: (B, T, Hkv, D).
      q_pos: (B, S) absolute positions; kv_pos: (B, T).
      window: sliding-window width (None = dense causal).
    Returns:
      (B, S, Hq, D) in q.dtype.
    """
    if cfg.use_pallas:
        from repro.kernels import ops
        return ops.flash_attention(q, k, v, q_pos, kv_pos, causal=causal,
                                   window=window, softcap=cfg.attn_logit_softcap)
    return _flash_lax(q, k, v, q_pos, kv_pos, causal=causal, window=window,
                      kv_chunk=cfg.attn_chunk, q_chunk=cfg.q_chunk,
                      softcap=cfg.attn_logit_softcap,
                      bf16_dots=cfg.opt_bf16_dots)


def _flash_lax(q, k, v, q_pos, kv_pos, *, causal, window, kv_chunk, q_chunk,
               softcap=None, bf16_dots=False):
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    kv_chunk = min(kv_chunk, T)
    q_chunk = min(q_chunk, S)
    # Pad T to a multiple of kv_chunk with invalid slots (pos = -1).
    pad_t = (-T) % kv_chunk
    if pad_t:
        k = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_t)), constant_values=-1)
    Tp = T + pad_t
    nk = Tp // kv_chunk
    pad_s = (-S) % q_chunk
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_s)), constant_values=-1)
    Sp = S + pad_s
    nq = Sp // q_chunk

    # bf16_dots (§Perf): operands stay in their storage dtype; the MXU
    # accumulates in fp32 via preferred_element_type — no materialized
    # fp32 copies of q/k/v chunks.
    in_dt = q.dtype if bf16_dots else jnp.float32
    qg = q.reshape(B, nq, q_chunk, Hkv, G, D).astype(in_dt)
    kc = k.reshape(B, nk, kv_chunk, Hkv, D).astype(in_dt)
    vc = v.reshape(B, nk, kv_chunk, Hkv, D).astype(in_dt)
    qp = q_pos.reshape(B, nq, q_chunk)
    kp = kv_pos.reshape(B, nk, kv_chunk)

    def q_step(_, qi):
        qblk = qg[:, qi]                       # (B,c,Hkv,G,D)
        qpb = qp[:, qi]                        # (B,c)

        def kv_step(carry, inp):
            num, den, m = carry
            kblk, vblk, kpb = inp              # (B,kc,Hkv,D), (B,kc)
            s = jnp.einsum("bqkgd,btkd->bkgqt", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            ok = _mask(qpb, kpb, window, causal)              # (B,c,kc)
            s = jnp.where(ok[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))       # (B,Hkv,G,c)
            # Fully-masked-so-far rows keep m_new = NEG_INF; guard the
            # exp(NEG_INF - NEG_INF) = nan corner.
            alive = m_new > NEG_INF / 2
            p = jnp.where(alive[..., None], jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.where(alive, jnp.exp(m - m_new), 1.0)
            num = num * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(in_dt), vblk,
                preferred_element_type=jnp.float32)
            den = den * corr + jnp.sum(p, axis=-1)
            return (num, den, m_new), None

        num0 = jnp.zeros((B, Hkv, G, q_chunk, D), jnp.float32)
        den0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        (num, den, _), _ = jax.lax.scan(
            kv_step, (num0, den0, m0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), kp.swapaxes(0, 1)))
        out = num / jnp.maximum(den[..., None], 1e-30)        # (B,Hkv,G,c,D)
        return None, out.transpose(0, 3, 1, 2, 4)             # (B,c,Hkv,G,D)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))      # (nq,B,c,Hkv,G,D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, Hq, D)
    return out[:, :S].astype(q.dtype)


# --------------------------------------------------------------------------
# Decode attention (one new token vs a filled KV cache)
# --------------------------------------------------------------------------


def decode_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, kv_pos: jax.Array, *,
                     window: Optional[int] = None) -> jax.Array:
    """Single-position attention: q (B, Hq, D) vs cache k/v (B, T, Hc, f*D).

    The cache is read in its stored layout: ``f`` kv heads side by side
    in each row, kv head ``c*f + j`` in lanes ``[j*D, (j+1)*D)`` of
    folded head ``c`` (f = 1 is the plain (B, T, Hkv, D)).
    q_pos: (B,) absolute position of the new token; kv_pos: (B, T) absolute
    positions of cache slots (-1 = empty; rolling caches leave these
    unordered — the mask doesn't care).
    Returns (B, Hq, D).
    """
    if cfg.use_pallas:
        from repro.kernels import ops
        return ops.decode_attention(q, k, v, q_pos, kv_pos, window=window,
                                    softcap=cfg.attn_logit_softcap)
    B, Hq, D = q.shape
    Hc, E = k.shape[2], k.shape[3]
    f = E // D
    G = Hq // (Hc * f)
    scale = D ** -0.5
    ok = _mask(q_pos[:, None], kv_pos, window, causal=True)[:, 0]   # (B,T)

    def softmax(s):                                      # (..., T) scores
        s = s * scale
        if cfg.attn_logit_softcap is not None:
            s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
        s = jnp.where(ok[:, None, None], s, NEG_INF)
        return jax.nn.softmax(s, axis=-1)

    if G == 1:
        # One query row per kv head: a float32 multiply-reduce that reads
        # the bf16 cache once, through a convert fused into each reduction.
        # ``lanes`` (E, f) maps each lane to its head in the row; a product
        # with it at HIGHEST precision (float32-exact) sums each head's own
        # lanes without splitting the 128-lane dim.  Scores come out
        # (B, T, Hc, f), the order in which the compiler fuses the
        # multiply into that product instead of materializing it.
        f32 = jnp.float32
        lanes = jnp.repeat(jnp.eye(f, dtype=f32), D, axis=0)
        qf = q.reshape(B, 1, Hc, E).astype(f32)
        s = jnp.einsum("btce,ej->btcj", k.astype(f32) * qf, lanes,
                       precision=jax.lax.Precision.HIGHEST)
        s = s.transpose(0, 2, 3, 1)
        p = softmax(s).transpose(0, 3, 1, 2)                # (B,T,Hc,f)
        pl = sum(p[..., j:j + 1] * lanes[:, j] for j in range(f))
        out = jnp.sum(pl * v.astype(f32), axis=1)            # (B,Hc,E)
        return out.reshape(B, Hq, D).astype(q.dtype)

    # Grouped queries: one dot per folded head, its R = f*G query rows
    # block-diagonal (row j*G+g holds head (c*f+j)*G+g in lanes of block j,
    # zeros elsewhere), so the contraction runs over the stored lanes.
    # bf16_dots (§Perf): the cache is the dominant memory stream in decode;
    # reading it through a bf16 dot (fp32 accumulation) instead of a
    # materialized .astype(f32) copy removes ~3x of the per-token traffic.
    in_dt = k.dtype if cfg.opt_bf16_dots else jnp.float32
    eye = jnp.eye(f, dtype=in_dt)
    qf = q.reshape(B, Hc, f, G, 1, D).astype(in_dt)
    qbd = (qf * eye[:, None, :, None]).reshape(B, Hc, f * G, E)
    s = jnp.einsum("bcre,btce->bcrt", qbd, k.astype(in_dt),
                   preferred_element_type=jnp.float32)
    p = softmax(s)
    out = jnp.einsum("bcrt,btce->bcre", p.astype(in_dt), v.astype(in_dt),
                     preferred_element_type=jnp.float32)     # (B,Hc,R,E)
    # keep each row's own head: lanes of block j for rows j*G..j*G+G-1
    out = jnp.sum(out.reshape(B, Hc, f, G, f, D) * eye[:, None, :, None],
                  axis=4)
    return out.reshape(B, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# Reference (naive) attention — oracle for tests
# --------------------------------------------------------------------------


def reference_attention(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
                        softcap=None) -> jax.Array:
    """O(S²) materialized-scores oracle, fp32."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = q.reshape(B, S, Hkv, G, D).astype(jnp.float32) * D ** -0.5
    s = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(jnp.float32))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    ok = _mask(q_pos, kv_pos, window, causal)          # (B,S,T)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key: softmax of all -inf -> uniform; zero them.
    any_ok = jnp.any(ok, axis=-1)[:, None, None, :, None]
    p = jnp.where(any_ok, p, 0.0)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, D).astype(q.dtype)
