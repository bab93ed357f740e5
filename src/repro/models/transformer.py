"""Decoder-only transformer: dense GQA LM + the generic layer-stack driver.

The layer stack is the shared chassis for every family: ``forward`` runs a
layer function over stacked per-layer params either as one ``lax.scan``
step (O(1) HLO in depth — required for the 126-layer dry-run) or as a
python-unrolled loop (hymba: per-layer cache shapes differ). Caches are
pytrees; in scan mode their leaves carry a leading layer axis, in unrolled
mode the cache is a list of per-layer pytrees.

Modes:
  * ``train``   — full sequence, no cache, remat per layer.
  * ``prefill`` — full sequence; emits a filled KV cache.
  * ``decode``  — one token per sequence against the cache.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention
from repro.models.common import (ModelConfig, ParamSpec, Params, activate,
                                 apply_norm, apply_rope, chunked_softmax_xent,
                                 embed_tokens, layer_slice, norm_specs,
                                 stack_layers)
from repro.sharding import shd

Cache = Any  # pytree: dict of arrays (scan mode) or list of dicts (unrolled)


# --------------------------------------------------------------------------
# Parameter tables
# --------------------------------------------------------------------------


def _prefixed(prefix: str, table: Dict[str, ParamSpec]) -> Dict[str, ParamSpec]:
    return {prefix + k: v for k, v in table.items()}


def attn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, Hq, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = {
        "wq": ParamSpec((d, Hq, Dh), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, Hkv, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, Hkv, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((Hq, Dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((Hq, Dh), ("heads", "head_dim"), "zeros")
        t["bk"] = ParamSpec((Hkv, Dh), ("kv_heads", "head_dim"), "zeros")
        t["bv"] = ParamSpec((Hkv, Dh), ("kv_heads", "head_dim"), "zeros")
    t.update(_prefixed("norm/", norm_specs(cfg)))
    return t


def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    F = d_ff or cfg.d_ff
    t = {"wi": ParamSpec((d, F), ("embed", "ffn")),
         "wo": ParamSpec((F, d), ("ffn", "embed"))}
    if cfg.activation == "swiglu":
        t["wg"] = ParamSpec((d, F), ("embed", "ffn"))
    t.update(_prefixed("norm/", norm_specs(cfg)))
    return t


def dense_layer_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    return {**_prefixed("attn/", attn_specs(cfg)),
            **_prefixed("mlp/", mlp_specs(cfg))}


def head_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    """Embedding + final norm + output head."""
    t = {
        # input table: rows gathered locally (embed dim sharded over model)
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model),
                           ("vocab_in", "embed_table")),
        **_prefixed("final_norm/", norm_specs(cfg)),
    }
    if not cfg.tie_embeddings:
        # output head: vocab sharded over model (parallel logsumexp in CE)
        t["lm_head"] = ParamSpec((cfg.vocab_size, cfg.d_model),
                                 ("vocab", "embed"))
    return t


def param_table(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    return {**head_specs(cfg),
            **stack_layers(dense_layer_specs(cfg), cfg.num_layers)}


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------


def _window_for_layer(cfg: ModelConfig, layer_idx: Optional[int]) -> Optional[int]:
    """Static per-layer sliding window (hymba: some layers are global)."""
    if cfg.sliding_window is None:
        return None
    if layer_idx is not None and layer_idx in cfg.global_layers:
        return None
    return cfg.sliding_window


def qkv_project(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
                prefix: str = "attn/"):
    """x (B,S,d) -> q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh), rope applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p[prefix + "bq"].astype(x.dtype)
        k = k + p[prefix + "bk"].astype(x.dtype)
        v = v + p[prefix + "bv"].astype(x.dtype)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shd(q, "batch", "seq", "heads", "head_dim")
    k = shd(k, "batch", "seq", "kv_heads", "head_dim")
    v = shd(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def kv_fold(cfg: ModelConfig) -> int:
    """Kv heads folded into one row of the KV cache's minor dim.

    ``f = 128 // head_dim`` when that divides 128 and ``num_kv_heads``,
    else 1.  The cache stores K and V as ``(..., W, Hkv // f, f * Dh)``:
    the same row-major bytes as ``(..., W, Hkv, Dh)``, but a 64-wide
    minor dim pads to the TPU's 128 lanes, so the compiler would store a
    stacked cache length-minor and relay out every layer it reads.
    """
    D, H = cfg.head_dim, cfg.num_kv_heads
    if D < 128 and 128 % D == 0 and H % (128 // D) == 0:
        return 128 // D
    return 1


def _fold(x: jax.Array, like: jax.Array) -> jax.Array:
    """(B,S,Hkv,Dh) -> the cache's (B,S,Hkv/f,f*Dh), cast to its dtype."""
    return x.reshape(x.shape[:2] + like.shape[-2:]).astype(like.dtype)


def _cache_write(cache: Dict[str, jax.Array], k: jax.Array, v: jax.Array,
                 positions: jax.Array) -> Dict[str, jax.Array]:
    """Scatter new k/v (B,S,Hkv,Dh) at slots pos % W (rolling or full).

    For rolling caches only the last W tokens are written (earlier ones
    would be overwritten anyway; slicing keeps scatter slots unique).
    """
    W = cache["k"].shape[1]
    B, S = positions.shape
    if S > W:
        k, v, positions = k[:, -W:], v[:, -W:], positions[:, -W:]
        S = W
    slots = positions % W                                    # (B,S)
    b = jnp.arange(B)[:, None]
    new_k = cache["k"].at[b, slots].set(_fold(k, cache["k"]))
    new_v = cache["v"].at[b, slots].set(_fold(v, cache["v"]))
    new_pos = cache["pos"].at[b, slots].set(positions)
    return {"k": new_k, "v": new_v, "pos": new_pos}


@dataclasses.dataclass(frozen=True)
class KVStack:
    """A decode layer's handle on the scanned stack's whole KV cache.

    ``stack`` is the scan's carry: ``k``/``v`` (L,B,W,Hkv/f,f*Dh) and
    ``pos`` (L,B,W); ``layer`` is the scan index; ``rows`` (B,) is the
    row each batch entry writes, out of range (dropped) for inactive
    rows.  A decode step writes only each active row's new token into
    the donated stack, so nothing of size one layer's K or V is copied,
    selected or written back, and attention reads the layer's slice in
    the stored layout.
    """
    stack: Dict[str, jax.Array]
    layer: jax.Array
    rows: jax.Array

    def write(self, k: jax.Array, v: jax.Array,
              positions: jax.Array) -> "KVStack":
        """Store (B,1,Hkv,Dh) k/v and (B,1) positions at slot pos % W."""
        sk, sv, sp = self.stack["k"], self.stack["v"], self.stack["pos"]
        idx = (self.layer, self.rows, positions[:, 0] % sp.shape[2])
        new = {"k": sk.at[idx].set(_fold(k, sk)[:, 0], mode="drop"),
               "v": sv.at[idx].set(_fold(v, sv)[:, 0], mode="drop"),
               "pos": sp.at[idx].set(positions[:, 0], mode="drop")}
        return dataclasses.replace(self, stack=new)

    def read(self, key: str) -> jax.Array:
        return jax.lax.dynamic_index_in_dim(self.stack[key], self.layer, 0,
                                            False)


def decode_write(cache, k: jax.Array, v: jax.Array, positions: jax.Array):
    """Write one token per row into a decode layer's cache.

    Returns ``(new_cache, k, v, pos)``, the last three this layer's
    cache as attention reads it: (B,W,Hkv/f,f*Dh) and (B,W).
    """
    if isinstance(cache, KVStack):
        cache = cache.write(k, v, positions)
        return cache, cache.read("k"), cache.read("v"), cache.read("pos")
    cache = _cache_write(cache, k, v, positions)
    return cache, cache["k"], cache["v"], cache["pos"]


def attention_block(cfg: ModelConfig, p: Params, x: jax.Array,
                    positions: jax.Array, cache: Optional[Dict[str, jax.Array]],
                    mode: str, layer_idx: Optional[int] = None,
                    prefix: str = "attn/", window_override=None):
    """Pre-norm attention residual branch. Returns (out, new_cache).

    ``window_override`` may be a *traced* per-layer width (scan-mode hymba:
    SWA layers vs global layers differ only in this predicate) — the lax
    mask path handles dynamic windows; the Pallas kernel needs it static.
    """
    window = (window_override if window_override is not None
              else _window_for_layer(cfg, layer_idx))
    h = apply_norm(cfg, p, prefix + "norm", x)
    if mode == "decode":
        # x: (B,1,d); cache holds the history INCLUDING this token after write.
        q, k, v = qkv_project(cfg, p, h, positions, prefix)
        cache, ck, cv, kpos = decode_write(cache, k, v, positions)
        q1 = q[:, 0]                                          # (B,Hq,Dh)
        ck = shd(ck, "batch", "cache_seq", "kv_heads", "head_dim")
        cv = shd(cv, "batch", "cache_seq", "kv_heads", "head_dim")
        o = attention.decode_attention(cfg, q1, ck, cv, positions[:, 0],
                                       kpos, window=window)
        o = o[:, None]                                        # (B,1,Hq,Dh)
    else:
        q, k, v = qkv_project(cfg, p, h, positions, prefix)
        o = attention.flash_attention(cfg, q, k, v, positions, positions,
                                      causal=True, window=window)
        if mode == "prefill":
            cache = _cache_write(cache, k, v, positions)
    o = shd(o, "batch", "seq", "heads", "head_dim")
    out = jnp.einsum("bshk,hkd->bsd", o, p[prefix + "wo"].astype(x.dtype))
    return out, cache


def mlp_block(cfg: ModelConfig, p: Params, x: jax.Array,
              prefix: str = "mlp/", d_ff: Optional[int] = None) -> jax.Array:
    h = apply_norm(cfg, p, prefix + "norm", x)
    gate = jnp.einsum("bsd,df->bsf", h, p[prefix + "wi"].astype(x.dtype))
    gate = shd(gate, "batch", "seq", "ffn")
    up = None
    if cfg.activation == "swiglu":
        up = jnp.einsum("bsd,df->bsf", h, p[prefix + "wg"].astype(x.dtype))
        up = shd(up, "batch", "seq", "ffn")
    act = activate(cfg, gate, up)
    return jnp.einsum("bsf,fd->bsd", act, p[prefix + "wo"].astype(x.dtype))


def dense_layer(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
                cache, mode: str, layer_idx: Optional[int] = None,
                meta=None):
    a, cache = attention_block(cfg, p, x, positions, cache, mode, layer_idx)
    x = x + a
    x = x + mlp_block(cfg, p, x)
    x = shd(x, "batch", "seq", "embed")
    return x, cache, {}


# --------------------------------------------------------------------------
# Layer-stack driver (scan or unrolled), shared by all families
# --------------------------------------------------------------------------

LayerFn = Callable[..., Tuple[jax.Array, Any, Dict[str, jax.Array]]]


def layer_metadata(cfg: ModelConfig) -> Optional[Dict[str, jax.Array]]:
    """Per-layer static metadata as stacked arrays (scan-mode xs).

    Families whose layers differ only by *predicate* (hymba: SWA vs global
    attention) expose that difference here so the stack can still be one
    ``lax.scan`` step — O(1) HLO in depth — instead of a python unroll.
    """
    if cfg.family == "hymba" and cfg.sliding_window is not None:
        flags = jnp.asarray([i in cfg.global_layers
                             for i in range(cfg.num_layers)])
        return {"is_global": flags}
    return None


def _use_scan(cfg: ModelConfig, mode: str) -> bool:
    if mode == "train" and cfg.scan_layers_train is not None:
        return cfg.scan_layers_train
    return cfg.scan_layers


def _constrain_layer_params(cfg: ModelConfig, layer_params: Params) -> Params:
    """§Perf cell B: pin each weight slice's sharding at its use site.

    ``with_sharding_constraint`` transposes to the same constraint on the
    cotangent, so the per-layer weight grads materialize directly in the
    FSDP shard layout *inside* the backward scan — GSPMD then emits a
    reduce-scatter instead of a full all-reduce + slice per layer.
    """
    if not cfg.opt_weight_constraints:
        return layer_params
    from repro.sharding import get_param_rules
    rules = get_param_rules()
    if rules is None:
        return layer_params
    from repro.models import model_zoo
    table = model_zoo.param_table(cfg)
    out = {}
    for k, v in layer_params.items():
        spec = table.get("layers/" + k)
        if spec is None or len(spec.axes) - 1 != v.ndim:
            out[k] = v
            continue
        axes = spec.axes[1:]                    # drop the "layers" dim
        out[k] = jax.lax.with_sharding_constraint(
            v, rules.sharding(axes, v.shape))
    return out


def _keep_inactive(new: Any, old: Any, active: Optional[jax.Array]) -> Any:
    """One layer's cache update with inactive rows (batch axis 0 of every
    per-layer leaf) kept bit-for-bit as they were."""
    if active is None:
        return new

    def one(n, o):
        return jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(one, new, old)


def forward(cfg: ModelConfig, params: Params, embeds: jax.Array,
            positions: jax.Array, cache: Optional[Cache], mode: str,
            layer_fn: LayerFn = dense_layer,
            active: Optional[jax.Array] = None):
    """Run the layer stack. Returns (hidden, new_cache, aux_sums).

    ``aux_sums`` accumulates per-layer scalars (MoE aux losses).
    ``active`` (B,) bool, decode only: rows outside it keep their cache
    bit-for-bit.  A scanned stack with a cache carries the whole stacked
    cache.  A decode step over a KV cache (the ``k``/``v``/``pos`` dict
    of :func:`init_cache`, every leaf with a length axis) hands each
    layer a :class:`KVStack`, which writes only the new token of each
    active row into the donated stack; any other cache (recurrent state,
    no length axis) and prefill update one layer slice at a time, the
    decode's inactive rows kept by a masked select.
    """
    stacked, _ = layer_slice(params)
    x = embeds
    meta = layer_metadata(cfg)

    def one_layer(x, layer_params, layer_cache, layer_idx, layer_meta):
        layer_params = _constrain_layer_params(cfg, layer_params)
        return layer_fn(cfg, layer_params, x, positions, layer_cache, mode,
                        layer_idx, meta=layer_meta)

    aux0: Dict[str, jax.Array] = (
        {"moe_aux": jnp.float32(0.0), "router_z": jnp.float32(0.0)}
        if cfg.family == "moe" else {})
    if _use_scan(cfg, mode) and cache is not None:
        token_writes = (mode == "decode" and isinstance(cache, dict)
                        and set(cache) == {"k", "v", "pos"})
        if token_writes:
            B = positions.shape[0]
            rows = jnp.arange(B, dtype=jnp.int32)
            if active is not None:
                rows = jnp.where(active, rows, B)

        def cache_body(carry, xs):
            x, aux, full = carry
            i, layer_params, layer_meta = xs
            if token_writes:
                x, new, a = one_layer(x, layer_params,
                                      KVStack(full, i, rows), None,
                                      layer_meta)
                full = new.stack
            else:
                old = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, False),
                    full)
                x, new, a = one_layer(x, layer_params, old, None, layer_meta)
                new = _keep_inactive(new, old, active)
                full = jax.tree.map(
                    lambda c, n: jax.lax.dynamic_update_index_in_dim(
                        c, n, i, 0), full, new)
            aux = {k: aux.get(k, 0.0) + v for k, v in a.items()} if a else aux
            return (x, aux, full), None

        (x, aux, cache), _ = jax.lax.scan(
            cache_body, (x, aux0, cache),
            (jnp.arange(cfg.num_layers), stacked, meta))
        return x, cache, aux

    if _use_scan(cfg, mode):
        def body(carry, xs):
            x, aux = carry
            layer_params, layer_meta = xs
            x, _, a = one_layer(x, layer_params, None, None, layer_meta)
            aux = {k: aux.get(k, 0.0) + v for k, v in a.items()} if a else aux
            return (x, aux), None

        G = cfg.remat_group if (cfg.remat and mode == "train") else 1
        if G > 1 and cfg.num_layers % G == 0:
            # two-level remat, scan-of-scans: HBM keeps only GROUP
            # boundaries (activations / G); the group's backward replays
            # the group forward, and each layer inside is itself
            # checkpointed so layer internals stay transient. Costs one
            # extra forward pass — the classic sqrt-ish remat trade.
            nG = cfg.num_layers // G
            grp = lambda v: v.reshape((nG, G) + v.shape[1:])
            xs2 = jax.tree.map(grp, (stacked, meta))
            inner = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

            def group_body(carry, gxs):
                return jax.lax.scan(inner, carry, gxs)

            group_body = jax.checkpoint(
                group_body, policy=jax.checkpoint_policies.nothing_saveable)
            (x, aux), _ = jax.lax.scan(group_body, (x, aux0), xs2)
        else:
            if cfg.remat and mode == "train":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), (stacked, meta))
        return x, None, aux

    aux: Dict[str, jax.Array] = {}
    new_cache = [] if cache is not None else None
    for i in range(cfg.num_layers):
        layer_params = {k: v[i] for k, v in stacked.items()}
        layer_cache = cache[i] if cache is not None else None
        layer_meta = (jax.tree.map(lambda m: m[i], meta)
                      if meta is not None else None)
        fn = one_layer
        if cfg.remat and mode == "train":
            fn = jax.checkpoint(one_layer, static_argnums=(3,))
        x, c, a = fn(x, layer_params, layer_cache, i, layer_meta)
        if cache is not None:
            new_cache.append(_keep_inactive(c, layer_cache, active))
        for k, v in (a or {}).items():
            aux[k] = aux.get(k, 0.0) + v
    return x, new_cache, aux


# --------------------------------------------------------------------------
# Top-level model functions (dense; other families override layer_fn)
# --------------------------------------------------------------------------


def assemble_embeds(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]):
    """Token/frontend embeddings + positions.

    ``batch`` carries "tokens" (B,S) and, for vision frontends, "patches"
    (B,P,d) — precomputed patch embeddings prepended to the token stream
    (the assignment stubs the modality encoder). Audio frontends pass
    token ids over the EnCodec codebook (vocab_size=2048), i.e. plain LM.
    """
    emb = None
    if "tokens" in batch:
        emb = embed_tokens(params["embed"], batch["tokens"], cfg.compute_dtype)
    if "embeds" in batch:                      # fully precomputed stream
        e = batch["embeds"].astype(cfg.compute_dtype)
        emb = e if emb is None else jnp.concatenate([emb, e], axis=1)
    if "patches" in batch:                     # vision prefix
        p = batch["patches"].astype(cfg.compute_dtype)
        emb = p if emb is None else jnp.concatenate([p, emb], axis=1)
    B, S = emb.shape[0], emb.shape[1]
    offset = batch.get("offset")
    positions = jnp.arange(S, dtype=jnp.int32)[None, :] + (
        offset[:, None].astype(jnp.int32) if offset is not None else 0)
    positions = jnp.broadcast_to(positions, (B, S))
    emb = shd(emb, "batch", "seq", "embed")
    return emb, positions


def output_head(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm + logits for the given hidden states."""
    x = apply_norm(cfg, params, "final_norm", x)
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.opt_bf16_dots:
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                            w.astype(jnp.float32))
    return shd(logits, "batch", "seq", "vocab")


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            layer_fn: LayerFn = dense_layer):
    """Mean-token CE over the batch (labels: next tokens; -1 = masked)."""
    emb, positions = assemble_embeds(cfg, params, batch)
    x, _, aux = forward(cfg, params, emb, positions, None, "train", layer_fn)
    x = apply_norm(cfg, params, "final_norm", x)
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    labels = batch["labels"]
    if x.shape[1] != labels.shape[1]:          # vision prefix: no labels there
        x = x[:, x.shape[1] - labels.shape[1]:]
    loss, count = chunked_softmax_xent(x, w, labels, cfg.ce_chunk,
                                       bf16_dots=cfg.opt_bf16_dots)
    metrics = {"loss": loss, "tokens": count}
    if aux:
        for k, v in aux.items():
            metrics[k] = v / cfg.num_layers
        loss = loss + cfg.router_aux_coef * metrics.get("moe_aux", 0.0)
    return loss, metrics


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               abstract: bool = False) -> Cache:
    """Allocate (or shape-spec) the KV cache.

    Layers with a sliding window get a rolling buffer of that width;
    global-attention layers get the full ``max_len``.  K and V are
    ``(B, W, Hkv/f, f*Dh)`` per layer with ``f = kv_fold(cfg)`` heads
    side by side in each row (a leading layer axis in scan mode);
    ``pos`` is ``(B, W)``, -1 for an empty slot.
    """
    Hkv, Dh, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    f = kv_fold(cfg)
    dt = cfg.compute_dtype

    def one(width: int):
        kv = (batch_size, width, Hkv // f, f * Dh)
        ps = (batch_size, width)
        if abstract:
            return {"k": jax.ShapeDtypeStruct(kv, dt),
                    "v": jax.ShapeDtypeStruct(kv, dt),
                    "pos": jax.ShapeDtypeStruct(ps, jnp.int32)}
        return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
                "pos": jnp.full(ps, -1, jnp.int32)}

    def width_for(i: int) -> int:
        w = _window_for_layer(cfg, i)
        return max_len if w is None else min(w, max_len)

    if cfg.scan_layers:
        w = width_for(0)          # uniform by construction in scan mode
        per = one(w)
        if abstract:
            return {k: jax.ShapeDtypeStruct((L,) + v.shape, v.dtype)
                    for k, v in per.items()}
        return {k: jnp.broadcast_to(v, (L,) + v.shape).copy()
                for k, v in per.items()}
    return [one(width_for(i)) for i in range(L)]


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            cache: Cache, layer_fn: LayerFn = dense_layer,
            lengths: Optional[jax.Array] = None):
    """Full-sequence forward; fills the cache. Returns (last_logits, cache).

    ``lengths`` (B,) selects each row's true last prompt position when the
    batch is right-padded to a shared bucket length (causal masking keeps
    positions < length unaffected by the padding; padded cache positions
    carry pos > t and are masked until decode overwrites them).
    """
    emb, positions = assemble_embeds(cfg, params, batch)
    x, cache, _ = forward(cfg, params, emb, positions, cache, "prefill", layer_fn)
    if lengths is None:
        xl = x[:, -1:]
    else:
        idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, x.shape[1] - 1)
        xl = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = output_head(cfg, params, xl)
    return logits[:, 0], cache


def decode_step(cfg: ModelConfig, params: Params, cache: Cache,
                tokens: jax.Array, t: jax.Array,
                layer_fn: LayerFn = dense_layer,
                active: Optional[jax.Array] = None):
    """One decode step. tokens: (B,), t: (B,) current positions; rows
    outside ``active`` (B,) bool keep their cache bit-for-bit.

    Returns (logits (B,V), new_cache).
    """
    batch = {"tokens": tokens[:, None], "offset": t}
    emb, positions = assemble_embeds(cfg, params, batch)
    x, cache, _ = forward(cfg, params, emb, positions, cache, "decode",
                          layer_fn, active=active)
    logits = output_head(cfg, params, x)
    return logits[:, 0], cache
