"""Uniform model API over all families.

Every family exposes the same six entry points, keyed by
``cfg.family``:

    param_table(cfg)                  -> {path: ParamSpec}
    init(key, cfg)                    -> params
    loss(cfg, params, batch)          -> (loss, metrics)        # train step body
    prefill(cfg, params, batch, cache)-> (last_logits, cache)
    decode(cfg, params, cache, tok, t)-> (logits, cache)
    init_cache(cfg, batch, max_len)   -> cache pytree (abstract= for dry-run)

The serving engine, trainer, dry-run and tests all go through this table —
adding an architecture is one config module + (optionally) one layer fn.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax

from repro.models import common, hymba, moe, rwkv6, transformer
from repro.models.common import ModelConfig, Params


class Family:
    def __init__(self, layer_fn, table_fn, cache_fn):
        self.layer_fn = layer_fn
        self.table_fn = table_fn
        self.cache_fn = cache_fn


_FAMILIES: Dict[str, Family] = {
    "dense": Family(transformer.dense_layer, transformer.param_table,
                    transformer.init_cache),
    "moe": Family(moe.moe_layer, moe.param_table, transformer.init_cache),
    "rwkv6": Family(rwkv6.rwkv_layer, rwkv6.param_table, rwkv6.init_cache),
    "hymba": Family(hymba.hymba_layer, hymba.param_table, hymba.init_cache),
}


def family(cfg: ModelConfig) -> Family:
    return _FAMILIES[cfg.family]


def param_table(cfg: ModelConfig):
    return family(cfg).table_fn(cfg)


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    return common.init_params(key, param_table(cfg), cfg.param_dtype)


def abstract_params(cfg: ModelConfig) -> Params:
    return common.abstract_params(param_table(cfg), cfg.param_dtype)


def loss(cfg: ModelConfig, params: Params, batch) :
    return transformer.loss_fn(cfg, params, batch, family(cfg).layer_fn)


def prefill(cfg: ModelConfig, params: Params, batch, cache, lengths=None):
    return transformer.prefill(cfg, params, batch, cache, family(cfg).layer_fn,
                               lengths=lengths)


def decode(cfg: ModelConfig, params: Params, cache, tokens, t, active=None):
    return transformer.decode_step(cfg, params, cache, tokens, t,
                                   family(cfg).layer_fn, active=active)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False):
    return family(cfg).cache_fn(cfg, batch, max_len, abstract=abstract)


def kv_fold(cfg: ModelConfig) -> int:
    """Kv heads side by side in each row of the KV cache
    (:func:`repro.models.transformer.kv_fold`); 1 for a family whose
    cache stores them one to a row or holds no keys and values."""
    if family(cfg).cache_fn is transformer.init_cache:
        return transformer.kv_fold(cfg)
    return 1
