"""Exact tensor-parallel serving over ``shard_map``: no split contraction.

A cost-modeled tier whose ``mesh_shape`` spans more than one device runs
its :class:`~repro.serving.engine.Endpoint` through this module: params
and KV cache live sharded over the mesh's ``"model"`` axis and every
prefill/decode step runs inside one ``shard_map``.

The layout is the **weight-gather** tensor-parallel scheme, chosen so no
contraction is split across devices: on two forced host devices the
sharded token stream is *bit-identical* to the unsharded engine (pinned
by ``tests/test_sharded_tier.py``).  A narrower local output can still
change how the compiler orders a dot's accumulation (four forced host
devices do, for 16-wide q/k/v blocks, at f32 rounding);
``chip_smoke.py --four-chips`` holds each block to one bf16 ulp of one
chip on four TPU chips:

* Column-parallel mats shard their *output* dim — ``wq``/``wk``/``wv``
  (heads), ``wi``/``wg`` (ffn), ``lm_head`` (vocab), the embed table
  (model dim) — exactly :func:`repro.launch.sharding.param_shardings`'s
  ``serve_replicated`` layout, so launch-side checkpoints drop in as-is.
  Output-dim slicing never splits a contraction: each local block of the
  result sums the same products as the unsharded dot.
* Row-parallel mats (``attn/wo``, ``mlp/wo``) are *stored* sharded on
  their contraction dim but ``all_gather(tiled=True)``-reconstructed
  right before their einsum — a bitwise concatenation, so the einsum
  sees inputs identical to the unsharded program instead of the psum of
  per-shard partial dots (float addition reordering is where psum TP
  loses bit-parity).  The activations feeding them (attention ``o``,
  MLP ``act``) are all-gathered the same way.
* Norms, residual stream, rope, cache writes and the attention kernels
  are replicated or per-head — reused **unmodified** from
  :mod:`repro.models.transformer` (head-count slicing preserves the GQA
  group size because ``validate_tp`` requires both head counts divide
  ``tp``; the kernels read head counts from shapes, not the config).

The *pricing* of a sharded tier deliberately uses the other scheme —
:mod:`repro.launch.tier_cost`'s psum layout (2 all-reduces per layer) —
because that is what a deployment at pod scale would run; this module is
what lets CPU tests pin parity.  See docs/architecture.md.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer
from repro.models.common import (ModelConfig, apply_norm, embed_tokens)

AXIS = "model"                 # TP axis name (mesh is ("data", "model"))

PyTree = Any


def tier_mesh(mesh_shape: Tuple[int, int]) -> Optional[Mesh]:
    """Build the tier's ``("data", "model")`` mesh.

    On the CPU backend a host with too few devices gets ``None``: the
    endpoint then runs unsharded (numerically identical), so dev boxes
    can run cloud-tier topologies.  On an accelerator a short host raises
    — a tier priced for a mesh must not quietly serve from one chip."""
    need = int(mesh_shape[0]) * int(mesh_shape[1])
    have = len(jax.devices())
    if have < need:
        if jax.default_backend() != "cpu":
            raise ValueError(
                f"mesh_shape {tuple(mesh_shape)} needs {need} devices, "
                f"{jax.default_backend()} host has {have}")
        warnings.warn(
            f"mesh_shape {tuple(mesh_shape)} needs {need} devices, host "
            f"has {have}: deploying unsharded (bit-identical fallback)")
        return None
    from repro.launch import mesh as mesh_mod
    return mesh_mod.make_mesh(tuple(int(a) for a in mesh_shape),
                              ("data", "model"), jax.devices()[:need])


def validate_tp(cfg: ModelConfig, tp: int) -> None:
    """Reject configs the exact weight-gather TP scheme cannot serve.

    Exactness needs every sharded output dim to divide ``tp`` (a
    replicate-on-indivisible fallback would silently change the layout
    the parity tests pin), the KV cache's folded kv-head axis among them,
    and the reused transformer blocks must be the dense family's.  Note
    this is stricter than the *cost model*, which ceils head counts — a
    pricing choice, documented in docs/architecture.md.
    """
    if tp <= 1:
        return
    if cfg.family != "dense":
        raise ValueError(
            f"tensor-parallel serving covers the dense family, "
            f"got {cfg.family!r}")
    if cfg.use_pallas:
        raise ValueError("tensor-parallel serving requires the lax "
                         "attention path (use_pallas=False)")
    if cfg.tie_embeddings:
        raise ValueError("tensor-parallel serving requires an untied "
                         "lm_head (vocab-sharded output head)")
    for field, value in (("num_heads", cfg.num_heads),
                         ("num_kv_heads / kv_fold",
                          cfg.num_kv_heads // transformer.kv_fold(cfg)),
                         ("d_ff", cfg.d_ff),
                         ("vocab_size", cfg.vocab_size),
                         ("d_model", cfg.d_model)):
        if value % tp:
            raise ValueError(
                f"exact TP needs {field} divisible by tp={tp}, "
                f"got {value}")


# --------------------------------------------------------------------------
# Spec builders (PartitionSpec pytrees for shard_map in/out_specs)
# --------------------------------------------------------------------------


def tp_param_specs(cfg: ModelConfig, mesh: Mesh) -> Dict[str, P]:
    """PartitionSpec per parameter path — the launch ``serve_replicated``
    layout (column mats shard outputs, row mats shard contractions,
    norms replicated), which is exactly what the weight-gather scheme
    stores."""
    from repro.launch import sharding as launch_sharding
    return {path: s.spec for path, s in
            launch_sharding.param_shardings(cfg, mesh,
                                            "serve_replicated").items()}


def _kv_leaf_spec(ndim: int) -> P:
    """k/v leaves shard their folded kv-heads dim (axis ndim-2 in both
    the stacked (L,B,W,Hkv/f,f*Dh) and per-layer (B,W,Hkv/f,f*Dh)
    layouts): each shard holds whole rows of f heads, its own heads'
    history."""
    spec = [None] * ndim
    spec[ndim - 2] = AXIS
    return P(*spec)


def tp_cache_specs(cache: PyTree) -> PyTree:
    """PartitionSpec pytree for a KV cache: k/v shard kv-heads over the
    model axis (each shard owns its local heads' history — the dual of
    the head-sharded qkv projections); ``pos`` is replicated.

    This is deliberately NOT :func:`repro.launch.sharding.cache_shardings`
    (whose flash-decode layout shards ``cache_seq``): sharding the
    sequence would split the attention *contraction* and reintroduce the
    psum reordering the weight-gather scheme exists to avoid.
    """
    def one(tree: Dict[str, jax.Array]) -> Dict[str, P]:
        out = {}
        for key, leaf in tree.items():
            if key in ("k", "v"):
                out[key] = _kv_leaf_spec(leaf.ndim)
            else:
                out[key] = P()
        return out

    if isinstance(cache, dict):
        return one(cache)
    return [one(layer) for layer in cache]


def init_params(key: jax.Array, cfg: ModelConfig, mesh: Mesh) -> PyTree:
    """Random weights initialised directly in the tensor-parallel layout:
    one jitted init whose outputs are placed by :func:`tp_param_specs`,
    so each device materialises only its own shards and a model too large
    for one chip never lands whole on the first."""
    from repro.models import model_zoo
    out = {k: NamedSharding(mesh, s)
           for k, s in tp_param_specs(cfg, mesh).items()}
    return jax.jit(model_zoo.init, static_argnums=1,
                   out_shardings=out)(key, cfg)


def shard_params(params: PyTree, mesh: Mesh,
                 specs: Dict[str, P]) -> PyTree:
    return jax.device_put(
        params, {k: NamedSharding(mesh, specs[k]) for k in params})


def shard_cache(cache: PyTree, mesh: Mesh, specs: PyTree) -> PyTree:
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(cache, shardings)


# --------------------------------------------------------------------------
# The per-layer block (mirrors transformer.dense_layer op-for-op)
# --------------------------------------------------------------------------


def _gather(x: jax.Array, axis_name: str, axis: int) -> jax.Array:
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _tp_attention_block(cfg: ModelConfig, axis: str, p, x, positions,
                        cache, mode: str, layer_idx, prefix: str = "attn/"):
    """transformer.attention_block with local heads + weight-gather wo.

    Everything up to the output projection reuses the unsharded code on
    the local head slice (norm replicated; qkv/rope/cache-write/kernels
    are per-head); then ``o`` and the contraction-sharded ``wo`` are
    all-gathered so the final einsum is the unsharded program verbatim.
    """
    window = transformer._window_for_layer(cfg, layer_idx)
    h = apply_norm(cfg, p, prefix + "norm", x)
    if mode == "decode":
        q, k, v = transformer.qkv_project(cfg, p, h, positions, prefix)
        cache, ck, cv, kpos = transformer.decode_write(cache, k, v,
                                                       positions)
        q1 = q[:, 0]
        from repro.models import attention
        o = attention.decode_attention(cfg, q1, ck, cv, positions[:, 0],
                                       kpos, window=window)
        o = o[:, None]
    else:
        q, k, v = transformer.qkv_project(cfg, p, h, positions, prefix)
        from repro.models import attention
        o = attention.flash_attention(cfg, q, k, v, positions, positions,
                                      causal=True, window=window)
        if mode == "prefill":
            cache = transformer._cache_write(cache, k, v, positions)
    o = _gather(o, axis, axis=2)                       # (B,S,Hq,Dh) full
    wo = _gather(p[prefix + "wo"], axis, axis=0)       # (Hq,Dh,d) full
    out = jnp.einsum("bshk,hkd->bsd", o, wo.astype(x.dtype))
    return out, cache


def _tp_mlp_block(cfg: ModelConfig, axis: str, p, x,
                  prefix: str = "mlp/") -> jax.Array:
    h = apply_norm(cfg, p, prefix + "norm", x)
    gate = jnp.einsum("bsd,df->bsf", h, p[prefix + "wi"].astype(x.dtype))
    up = None
    if cfg.activation == "swiglu":
        up = jnp.einsum("bsd,df->bsf", h, p[prefix + "wg"].astype(x.dtype))
    act = transformer.activate(cfg, gate, up)
    act = _gather(act, axis, axis=2)                   # (B,S,F) full
    wd = _gather(p[prefix + "wo"], axis, axis=0)       # (F,d) full
    return jnp.einsum("bsf,fd->bsd", act, wd.astype(x.dtype))


def _tp_layer(cfg: ModelConfig, axis: str, p, x, positions, cache,
              mode: str, layer_idx=None, meta=None):
    a, cache = _tp_attention_block(cfg, axis, p, x, positions, cache,
                                   mode, layer_idx)
    x = x + a
    x = x + _tp_mlp_block(cfg, axis, x=x, p=p)
    return x, cache, {}


def _tp_embeds(cfg: ModelConfig, axis: str, params, batch):
    """assemble_embeds with the model-dim-sharded table: local row
    gather, then all-gather the embedding columns (a bitwise concat)."""
    emb = embed_tokens(params["embed"], batch["tokens"], cfg.compute_dtype)
    emb = _gather(emb, axis, axis=2)
    B, S = emb.shape[0], emb.shape[1]
    offset = batch.get("offset")
    positions = jnp.arange(S, dtype=jnp.int32)[None, :] + (
        offset[:, None].astype(jnp.int32) if offset is not None else 0)
    positions = jnp.broadcast_to(positions, (B, S))
    return emb, positions


def _tp_output_head(cfg: ModelConfig, axis: str, params, x) -> jax.Array:
    """output_head with the vocab-sharded lm_head: local logits columns,
    all-gathered (column-slicing a dot's output dim is bitwise-safe)."""
    x = apply_norm(cfg, params, "final_norm", x)
    w = params["lm_head"]          # validate_tp rejects tied embeddings
    if cfg.opt_bf16_dots:
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                            w.astype(jnp.float32))
    return _gather(logits, axis, axis=2)


# --------------------------------------------------------------------------
# shard_map-wrapped model functions (the Endpoint's drop-in backends)
# --------------------------------------------------------------------------


def make_tp_functions(cfg: ModelConfig, mesh: Mesh, cache: PyTree):
    """Build ``(tp_prefill, tp_decode, param_specs, cache_specs)``.

    ``tp_decode(params, cache, tokens, t, active)`` mirrors
    ``transformer.decode_step``; ``tp_prefill(params, tokens, lengths,
    cache)`` mirrors ``transformer.prefill`` with ``lengths`` always
    materialized (``take_along_axis`` at ``lengths-1 == S-1`` is bitwise
    equal to the ``x[:, -1:]`` branch).  Prefill runs through shard_map
    too — compiling it under GSPMD instead would psum the row-parallel
    projections and break bit-parity.
    """
    tp = mesh.shape[AXIS]
    validate_tp(cfg, tp)
    pspecs = tp_param_specs(cfg, mesh)
    cspecs = tp_cache_specs(cache)
    rep = P()

    def layer_fn(cfg_, p, x, positions, c, mode, layer_idx, meta=None):
        return _tp_layer(cfg_, AXIS, p, x, positions, c, mode,
                         layer_idx, meta=meta)

    def _decode_local(params, cache, tokens, t, active):
        batch = {"tokens": tokens[:, None], "offset": t}
        emb, positions = _tp_embeds(cfg, AXIS, params, batch)
        x, cache, _ = transformer.forward(cfg, params, emb, positions,
                                          cache, "decode", layer_fn,
                                          active=active)
        logits = _tp_output_head(cfg, AXIS, params, x)
        return logits[:, 0], cache

    def _prefill_local(params, tokens, lengths, cache):
        emb, positions = _tp_embeds(cfg, AXIS, params, {"tokens": tokens})
        x, cache, _ = transformer.forward(cfg, params, emb, positions,
                                          cache, "prefill", layer_fn)
        idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0,
                       x.shape[1] - 1)
        xl = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = _tp_output_head(cfg, AXIS, params, xl)
        return logits[:, 0], cache

    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    tp_decode = smap(_decode_local, in_specs=(pspecs, cspecs, rep, rep, rep),
                     out_specs=(rep, cspecs))
    tp_prefill = smap(_prefill_local, in_specs=(pspecs, rep, rep, cspecs),
                      out_specs=(rep, cspecs))
    return tp_prefill, tp_decode, pspecs, cspecs
