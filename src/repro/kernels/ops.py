"""Jit'd public wrappers over the Pallas kernels.

Call sites go through these (``cfg.use_pallas=True`` flips the model code
here); each op:

* pads/validates shapes, picks TPU-aligned block sizes;
* runs ``interpret=True`` on the CPU backend only (tests) and compiled
  Mosaic on every other backend — a kernel Mosaic refuses fails there,
  it never falls back to the interpreter;
* carries a ``custom_vjp`` whose backward recomputes through the pure-jnp
  oracle (``ref.py``) — numerically identical to differentiating the
  oracle, so training through kernels needs no hand-written backward
  kernels while inference gets the fused forward.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ref
from repro.kernels import rwkv6_scan as _rwkv
from repro.kernels import ssd_scan as _ssd


#: Pallas calls traced so far, by mode ("mosaic" or "interpret"): a chip
#: run asserts that none of its kernels was interpreted.
TRACED = collections.Counter()


def _interpret() -> bool:
    interpret = jax.default_backend() == "cpu"
    TRACED["interpret" if interpret else "mosaic"] += 1
    return interpret


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention(q, k, v, q_pos, kv_pos, causal=True, window=None,
                    softcap=None):
    return _fa.flash_attention(q, k, v, q_pos, kv_pos, causal=causal,
                               window=window, softcap=softcap,
                               interpret=_interpret())


def _fa_fwd(q, k, v, q_pos, kv_pos, causal, window, softcap):
    out = flash_attention(q, k, v, q_pos, kv_pos, causal, window, softcap)
    return out, (q, k, v, q_pos, kv_pos)


def _fa_bwd(causal, window, softcap, res, g):
    q, k, v, q_pos, kv_pos = res
    def f(q, k, v):
        return ref.flash_attention(q, k, v, q_pos, kv_pos, causal=causal,
                                   window=window, softcap=softcap)
    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# --------------------------------------------------------------------------
# decode attention (inference only — no vjp needed, but harmless)
# --------------------------------------------------------------------------


def _unfold(cache: jax.Array, head_dim: int) -> jax.Array:
    """A folded (..., Hkv/f, f*D) KV cache as the kernels' (..., Hkv, D):
    the same row-major bytes (``repro.models.transformer.kv_fold``)."""
    return cache.reshape(cache.shape[:-2] + (-1, head_dim))


def decode_attention(q, k, v, q_pos, kv_pos, window=None, softcap=None):
    """k/v: the cache (B, T, Hkv/f, f*D) in its stored layout."""
    D = q.shape[-1]
    return _dec.decode_attention(q, _unfold(k, D), _unfold(v, D), q_pos,
                                 kv_pos, window=window, softcap=softcap,
                                 interpret=_interpret())


def paged_decode_attention(q, k_pages, v_pages, page_tables, q_pos,
                           kv_pos_pages, window=None, softcap=None):
    """Flash-decode straight off a paged KV pool (no gather roundtrip):
    K/V tiles stream through the request's page table via scalar
    prefetch.  Bit-identical to ``decode_attention`` with
    ``blk_k=page_size`` on the gathered view.  k/v_pages:
    (P, page, Hkv/f, f*D), the pool's stored layout."""
    D = q.shape[-1]
    return _dec.paged_decode_attention(q, _unfold(k_pages, D),
                                       _unfold(v_pages, D), page_tables,
                                       q_pos, kv_pos_pages, window=window,
                                       softcap=softcap, interpret=_interpret())


# --------------------------------------------------------------------------
# WKV6
# --------------------------------------------------------------------------


@jax.custom_vjp
def rwkv6_scan(r, k, v, lw, u, s0):
    return _rwkv.rwkv6_scan(r, k, v, lw, u, s0, interpret=_interpret())


def _rwkv_fwd(r, k, v, lw, u, s0):
    return rwkv6_scan(r, k, v, lw, u, s0), (r, k, v, lw, u, s0)


def _rwkv_bwd(res, g):
    r, k, v, lw, u, s0 = res
    _, vjp = jax.vjp(lambda *a: ref.rwkv6_scan(*a), r, k, v, lw, u, s0)
    return vjp(g)


rwkv6_scan.defvjp(_rwkv_fwd, _rwkv_bwd)


# --------------------------------------------------------------------------
# selective-SSM scan
# --------------------------------------------------------------------------


@jax.custom_vjp
def ssd_scan(a, b, h0):
    return _ssd.ssd_scan(a, b, h0, interpret=_interpret())


def _ssd_fwd(a, b, h0):
    return ssd_scan(a, b, h0), (a, b, h0)


def _ssd_bwd(res, g):
    a, b, h0 = res
    _, vjp = jax.vjp(lambda *x: ref.ssd_scan(*x), a, b, h0)
    return vjp(g)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
