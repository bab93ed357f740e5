"""Serving engine: jitted prefill/decode steps + a continuous-batching
instance pool per tier.

The engine is the *data plane* the paper's control plane routes to. One
:class:`Endpoint` wraps a (config, params) pair with jitted ``prefill`` and
``decode`` steps and a KV cache pool (continuous batching: requests claim/
release slots independently; one decode step advances every active slot).
Latency per request is what feeds the paper's Eq (1).

The pool has two layouts:

* **dense** (default): one contiguous ``max_len`` cache row per slot —
  slot count caps concurrency regardless of how much context each row
  actually holds.
* **paged** (``paged=True``): the pool is ``total_pages`` fixed
  ``page_size``-token pages (``repro.cache.PagePool``); each request
  claims a *page table* sized to its declared extent, requests sharing a
  system/function prompt reference the same prefix pages
  (``repro.cache.PrefixRegistry``, copy-on-write past the fork point),
  and an exact-prompt hit skips prefill compute entirely.  Decode
  gathers each row's pages into the same contiguous view the dense pool
  stores and runs the *same* jitted decode program, then scatters only
  the written page back — so the token stream is bit-identical to dense
  by construction (the TPU fast path replaces the XLA gather with the
  fused paged-attention kernel in ``kernels/decode_attention.py``).
  Migration ships only the *used* pages of a row.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import PagePool, PrefixRegistry, pages_for_tokens, \
    pages_needed, token_extent
from repro.core.metrics import span
from repro.models import model_zoo
from repro.models.common import ModelConfig


@dataclasses.dataclass
class Request:
    """One inference request (token ids in, token ids out)."""
    rid: int
    tokens: np.ndarray            # (prompt_len,)
    max_new: int = 8
    arrival_s: float = 0.0
    # filled by the runtime: slot claimed (just before prefill), first
    # token, last token; arrival_s <= t_admit <= t_first <= t_done
    t_admit: float = 0.0
    output: Optional[np.ndarray] = None
    t_first: float = 0.0
    t_done: float = 0.0
    # charged end-to-end latency as the platform accounts it (includes
    # backdated link-crossing charges the wall-clock stamps miss)
    latency_s: Optional[float] = None
    # name of the tier whose endpoint produced ``output``
    tier: Optional[str] = None
    # set by the runtime when a bounded gateway rejects/drops the request
    # (the live 503) — ``output`` will never be filled
    failed: bool = False


@dataclasses.dataclass
class PagedRow:
    """One extracted paged row: the migration payload.

    Only the pages covering the row's filled positions are shipped
    (``page_leaves``: each paged cache leaf narrowed to ``n_pages``
    pages), plus the per-slot residual state (recurrent lanes,
    rolling-window blocks — leaves the pool does not page)."""
    n_pages: int
    pos: int
    page_leaves: List[jax.Array]
    resid_leaves: List[jax.Array]

    @property
    def nbytes(self) -> float:
        return float(sum(l.nbytes for l in self.page_leaves)
                     + sum(l.nbytes for l in self.resid_leaves))


def _cache_len_axes(cfg: ModelConfig, slots: int, max_len: int) -> list:
    """Per-leaf sequence-length axis of the cache pytree, or None for
    leaves whose size does not depend on ``max_len`` (recurrent state).

    Derived exactly, like :func:`_cache_batch_axes`: the length axis is
    wherever the abstract cache shape changes when ``max_len`` does.
    """
    a = jax.tree_util.tree_leaves(
        model_zoo.init_cache(cfg, slots, max_len, abstract=True))
    b = jax.tree_util.tree_leaves(
        model_zoo.init_cache(cfg, slots, max_len + 1, abstract=True))
    axes = []
    for la, lb in zip(a, b):
        axis = None
        for i, (x, y) in enumerate(zip(la.shape, lb.shape)):
            if x != y:
                axis = i
                break
        axes.append(axis)
    return axes


def _cache_batch_axes(cfg: ModelConfig, slots: int, max_len: int) -> list:
    """Per-leaf slot-axis of the cache pytree, or None for leaves that do
    not depend on the batch size.

    Derived exactly (not guessed from shapes, which is ambiguous when e.g.
    num_layers == slots): the slot axis is wherever the abstract cache
    shape changes when the batch size does.
    """
    a = jax.tree_util.tree_leaves(
        model_zoo.init_cache(cfg, slots, max_len, abstract=True))
    b = jax.tree_util.tree_leaves(
        model_zoo.init_cache(cfg, slots + 1, max_len, abstract=True))
    axes = []
    for la, lb in zip(a, b):
        axis = None
        for i, (x, y) in enumerate(zip(la.shape, lb.shape)):
            if x != y:
                axis = i
                break
        axes.append(axis)
    return axes


def _copy_slot_row(dst: jax.Array, src: jax.Array, slot: jax.Array,
                   axis) -> jax.Array:
    """Copy one slot's row of ``src`` into ``dst`` along ``axis``."""
    if axis is None:
        return dst
    idx = (slice(None),) * axis + (slot,)
    return dst.at[idx].set(src[idx])


def _broadcast_rows(template: jax.Array, axis, n: int) -> jax.Array:
    """Tile a single-row init template to ``n`` rows along ``axis`` (cache
    init values are row-independent, so one stored row stands for all)."""
    if axis is None:
        return template
    t = jnp.moveaxis(template, axis, 0)[0]
    t = jnp.broadcast_to(t, (n,) + t.shape)
    return jnp.moveaxis(t, 0, axis)


class Endpoint:
    """A deployed model ("Knative Service" analogue) on one tier.

    ``slots`` is the max concurrent sequences; requests batch up to
    ``slots`` per decode step — the TPU-idiomatic version of request
    concurrency.  With ``paged=True`` the KV pool is ``total_pages``
    pages of ``page_size`` tokens and admission is bounded by *pages*
    (memory actually reserved), not slots alone.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 8,
                 max_len: int = 256, donate: bool = True,
                 bucket_prefill: bool = True,
                 paged: bool = False, page_size: int = 16,
                 total_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefix_capacity: int = 64,
                 mesh=None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.bucket_prefill = bucket_prefill
        self.slot_pos = np.zeros(slots, np.int32)          # next position
        self.slot_free = [True] * slots
        self.peak_active = 0
        # work counters: decode calls and the active rows summed over
        # them; real prompt tokens offered to prefill, and the padded
        # rows x length actually computed (prefix-cache hits compute none)
        self.decode_calls = 0
        self.decode_rows = 0
        # kv heads side by side in each cache row (the cache's layout)
        self.kv_fold = model_zoo.kv_fold(cfg)
        self.prefill_total_tokens = 0
        self.prefill_computed_tokens = 0
        # ``mesh`` switches the endpoint to shard_map tensor-parallel
        # serving (repro.serving.sharded): params/KV sharded over the
        # mesh's "model" axis, token stream bit-identical to unsharded.
        self.mesh = mesh
        self._tp = int(mesh.shape["model"]) if mesh is not None else 1
        if self._tp > 1 and paged:
            raise ValueError(
                "paged=True is not supported on tensor-parallel endpoints "
                "(page gather/scatter would cross the kv-head sharding)")

        batch_axes = _cache_batch_axes(cfg, slots, max_len)
        self._batch_axes = batch_axes
        self._len_axes = _cache_len_axes(cfg, slots, max_len)
        # Single-row init template, built ONCE: reset_slot and the
        # bucketed-prefill fresh cache tile rows from it instead of
        # materializing a full pool-sized init_cache per call.
        self._row_init = model_zoo.init_cache(cfg, 1, max_len)
        self._row_leaves = jax.tree_util.tree_leaves(self._row_init)
        self._treedef = jax.tree_util.tree_structure(self._row_init)

        # -- paged layout ---------------------------------------------------
        self.paged = bool(paged)
        self.page_size = int(page_size)
        # A leaf pages iff it is per-slot AND its length axis is the full
        # context budget immediately after the slot axis (the standard KV
        # block layout).  Recurrent state and rolling-window blocks stay
        # per-slot ("residual") and move with the row as one unit.
        self._is_paged_leaf = [
            bax is not None and sax == bax + 1
            and leaf.shape[sax] == max_len
            for leaf, bax, sax in zip(self._row_leaves, batch_axes,
                                      self._len_axes)]
        if self.paged:
            if not bucket_prefill:
                raise ValueError("paged=True requires bucket_prefill=True")
            if not (0 < page_size <= max_len) or max_len % page_size:
                raise ValueError(
                    f"page_size must divide max_len ({max_len}), "
                    f"got {page_size}")
            if not any(self._is_paged_leaf):
                raise ValueError(
                    f"model family {cfg.family!r} has no pageable cache "
                    "leaves (no full-context KV blocks)")
            self.pages_per_row = -(-max_len // page_size)
            if total_pages is None:
                total_pages = slots * self.pages_per_row
            if total_pages < self.pages_per_row:
                raise ValueError(
                    f"total_pages={total_pages} cannot hold one full row "
                    f"({self.pages_per_row} pages)")
            self.total_pages = int(total_pages)
            self.pool = PagePool(self.total_pages, self.page_size)
            self.prefix: Optional[PrefixRegistry] = (
                PrefixRegistry(self.pool, prefix_capacity)
                if prefix_cache else None)
            # physical id of the reserved always-empty page that pads
            # every table to a fixed (slots, pages_per_row) device shape
            self._null_page = self.total_pages
            self._tables: List[Optional[List[int]]] = [None] * slots
            self._table_np = np.full((slots, self.pages_per_row),
                                     self._null_page, np.int32)
            # exact-prompt prefill hits pending their (free) first token
            self._pending_first: Dict[int, Tuple[int, int]] = {}
            # miss claims carrying a registrable prompt
            self._claim_meta: Dict[int, Optional[np.ndarray]] = {}
            self.prefill_hit_tokens = 0
            self.cache = self._init_paged_pool()
        else:
            self.pages_per_row = 0
            self.total_pages = 0
            self.pool = None
            self.prefix = None
            self.cache = model_zoo.init_cache(cfg, slots, max_len)

        # Model-function indirection: the closures below call these, so
        # the dense/sharded choice is made once, here, and every pool
        # operation (masking, scatter, migration slicing) stays shared.
        if self._tp > 1:
            from repro.serving import sharded
            tp_prefill, tp_decode, pspecs, cspecs = \
                sharded.make_tp_functions(cfg, mesh, self.cache)
            self.params = sharded.shard_params(params, mesh, pspecs)
            self.cache = sharded.shard_cache(self.cache, mesh, cspecs)

            def _model_prefill(params, batch, cache, lengths=None):
                tokens = batch["tokens"]
                if lengths is None:
                    # take_along_axis at lengths-1 == S-1 is bitwise
                    # equal to the unsharded x[:, -1:] branch
                    lengths = jnp.full((tokens.shape[0],), tokens.shape[1],
                                       jnp.int32)
                return tp_prefill(params, tokens, lengths, cache)

            _model_decode = tp_decode
        else:
            def _model_prefill(params, batch, cache, lengths=None):
                return model_zoo.prefill(cfg, params, batch, cache,
                                         lengths=lengths)

            def _model_decode(params, cache, tokens, t, active):
                return model_zoo.decode(cfg, params, cache, tokens, t,
                                        active=active)

        def _prefill(params, batch, cache):
            return _model_prefill(params, batch, cache)

        def _rows(cache, src, slot):
            leaves, treedef = jax.tree_util.tree_flatten(cache)
            src_leaves = jax.tree_util.tree_leaves(src)
            out = [_copy_slot_row(c, s, slot, ax)
                   for c, s, ax in zip(leaves, src_leaves, batch_axes)]
            return jax.tree_util.tree_unflatten(treedef, out)

        def _reset_slot(cache, template, slot):
            """Restore one slot's rows from the single-row template.
            In paged mode only the residual (non-paged) leaves are
            per-slot; pool pages are scrubbed at allocation instead."""
            leaves, treedef = jax.tree_util.tree_flatten(cache)
            tmpl = jax.tree_util.tree_leaves(template)
            out = []
            for c, s, ax, pg in zip(leaves, tmpl, batch_axes,
                                    self._is_paged_leaf):
                if ax is None or (self.paged and pg):
                    out.append(c)
                    continue
                idx = (slice(None),) * ax + (slot,)
                src_idx = (slice(None),) * ax + (0,)
                out.append(c.at[idx].set(s[src_idx]))
            return jax.tree_util.tree_unflatten(treedef, out)

        def _restore_slot(cache, snap, slot):
            return _rows(cache, snap, slot)

        def _prefill_rows(params, tokens, lengths, template):
            """Bucketed prefill compute: run the group on a *fresh* small
            cache (batch = pow2 bucket, tiled from the single-row init
            template) and return the logits + filled rows.  Both pool
            layouts scatter from this same program, so a paged endpoint's
            prefill logits are bit-identical to a dense one's."""
            Bp = tokens.shape[0]
            small = jax.tree_util.tree_unflatten(
                self._treedef,
                [_broadcast_rows(l, ax, Bp)
                 for l, ax in zip(jax.tree_util.tree_leaves(template),
                                  batch_axes)])
            return _model_prefill(params, {"tokens": tokens}, small,
                                  lengths=lengths)

        def _scatter_rows(pool, small, slot_arr):
            """Scatter a prefilled group's rows into the dense pool at
            ``slot_arr`` — other slots are never touched."""
            G = slot_arr.shape[0]
            pool_leaves, treedef = jax.tree_util.tree_flatten(pool)
            small_leaves = jax.tree_util.tree_leaves(small)
            out = []
            for pl, sl, ax in zip(pool_leaves, small_leaves, batch_axes):
                if ax is None:
                    out.append(pl)
                    continue
                rows = jax.lax.slice_in_dim(sl, 0, G, axis=ax)
                idx = (slice(None),) * ax + (slot_arr,)
                out.append(pl.at[idx].set(rows))
            return jax.tree_util.tree_unflatten(treedef, out)

        def _extract_row(cache, slot):
            """Slice one slot's cache rows out of the pool: a pytree of
            per-slot leaves (batch axis kept at size 1) that can be
            shipped to a peer endpoint of the same model/max_len —
            mid-stream migration's unit of state."""
            leaves = jax.tree_util.tree_leaves(cache)
            return [jnp.take(l, slot[None], axis=ax)
                    for l, ax in zip(leaves, batch_axes) if ax is not None]

        def _insert_row(cache, rows, slot):
            """Scatter one extracted row state into this pool at ``slot``
            (the other side of migration: resume without re-prefill)."""
            leaves, treedef = jax.tree_util.tree_flatten(cache)
            it = iter(rows)
            out = []
            for l, ax in zip(leaves, batch_axes):
                if ax is None:
                    out.append(l)
                    continue
                idx = (slice(None),) * ax + (slot[None],)
                out.append(l.at[idx].set(next(it)))
            return jax.tree_util.tree_unflatten(treedef, out)

        # ``donate`` governs every jitted step that consumes the cache
        # (we always rebind ``self.cache`` to the result).
        dn0 = (0,) if donate else ()
        self._prefill = jax.jit(_prefill, donate_argnums=(2,) if donate else ())
        self._prefill_rows = jax.jit(_prefill_rows)
        self._scatter_rows = jax.jit(_scatter_rows, donate_argnums=dn0)
        # One decode step with a per-row active mask: inactive rows keep
        # their cache rows bit-for-bit.  Under continuous batching slots
        # retire (and hedge losers are cancelled) mid-stream, so a freed
        # row must not drift — KV rows must not collect writes at a stale
        # position and recurrent state must not advance on the zero-token
        # placeholder — while its neighbors keep decoding.  The model masks
        # each layer's cache write, so the donated pool is updated in place.
        self._decode = jax.jit(_model_decode,
                               donate_argnums=(1,) if donate else ())
        self._reset = jax.jit(_reset_slot, donate_argnums=dn0)
        self._restore = jax.jit(_restore_slot, donate_argnums=dn0)
        self._extract = jax.jit(_extract_row)
        self._insert = jax.jit(_insert_row, donate_argnums=dn0)
        if self.paged:
            self._build_paged_ops(donate)
        # Length padding is sound only for the dense family: causal
        # masking hides padded positions there, but recurrent state
        # threads through every token, and MoE expert capacity is
        # sequence-global (C scales with padded S and padding tokens
        # compete for expert slots, perturbing real-token logits).  It
        # must also stay below any rolling-window width (padding must not
        # wrap over live keys).
        self._pad_len = cfg.family == "dense"
        self._len_cap = max_len
        if cfg.sliding_window is not None:
            self._len_cap = min(self._len_cap, cfg.sliding_window)
        # Attention caches are self-healing on slot reuse (a cache index is
        # always overwritten at position == index before any query can
        # attend it), so only families that thread recurrent state through
        # prefill need their rows scrubbed between requests — and only on
        # the legacy full-pool path; the bucketed path always prefills
        # rows from a fresh cache.
        self._reset_on_claim = (cfg.family not in ("dense", "moe")
                                and not bucket_prefill)

    # -- paged pool construction -------------------------------------------
    def _init_paged_pool(self):
        """Build the pooled cache pytree: paged leaves hold
        ``total_pages + 1`` pages (the extra one is the reserved null
        page), residual leaves keep their per-slot dense layout."""
        leaves = []
        for l, bax, pg in zip(self._row_leaves, self._batch_axes,
                              self._is_paged_leaf):
            if pg:
                leaves.append(_broadcast_rows(
                    self._page_template(l, bax), bax, self.total_pages + 1))
            elif bax is not None:
                leaves.append(_broadcast_rows(l, bax, self.slots))
            else:
                leaves.append(l)
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _page_template(self, row_leaf, bax):
        """One init page of a paged leaf (init values are position-uniform,
        so the first ``page_size`` positions of the template row serve)."""
        sl = [slice(None)] * row_leaf.ndim
        sl[bax + 1] = slice(0, self.page_size)
        return row_leaf[tuple(sl)]

    def _build_paged_ops(self, donate: bool) -> None:
        batch_axes = self._batch_axes
        is_paged = self._is_paged_leaf
        page, ppr = self.page_size, self.pages_per_row
        page_tmpl = [self._page_template(l, bax)
                     for l, bax, pg in zip(self._row_leaves, batch_axes,
                                           is_paged) if pg]
        paged_bax = [bax for bax, pg in zip(batch_axes, is_paged) if pg]

        def _split(cache):
            leaves = jax.tree_util.tree_leaves(cache)
            return leaves

        def _gather(cache, tables):
            """Pooled pages -> the contiguous per-slot view the dense pool
            stores (same values, same layout: the decode program is shared
            with dense mode, pinning bit-identity)."""
            B = tables.shape[0]
            leaves = _split(cache)
            out = []
            for l, bax, pg in zip(leaves, batch_axes, is_paged):
                if not pg:
                    out.append(l)
                    continue
                g = jnp.take(l, tables.reshape(-1), axis=bax)
                shape = list(g.shape)
                split = shape[:bax] + [B, ppr, shape[bax + 1]] + shape[bax + 2:]
                merged = shape[:bax] + [B, ppr * shape[bax + 1]] + shape[bax + 2:]
                out.append(g.reshape(split).reshape(merged))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        def _writeback(cache, new_dense, tables, t, active):
            """Scatter each active row's *written page* back into the pool
            (every other page is untouched by one decode step); residual
            leaves take the dense result wholesale."""
            B = tables.shape[0]
            wp = jnp.clip((t % self.max_len) // page, 0, ppr - 1)   # (B,)
            phys = tables[jnp.arange(B), wp]                        # (B,)
            pool_leaves = _split(cache)
            new_leaves = jax.tree_util.tree_leaves(new_dense)
            out = []
            for pl, nl, bax, pg in zip(pool_leaves, new_leaves, batch_axes,
                                       is_paged):
                if not pg:
                    out.append(nl if bax is not None else pl)
                    continue
                shape = list(nl.shape)
                d = nl.reshape(shape[:bax] + [B, ppr, page] + shape[bax + 2:])
                d = jnp.moveaxis(d, (bax, bax + 1), (0, 1))
                new_page = d[jnp.arange(B), wp]          # (B, ..., page, ...)
                old = jnp.moveaxis(jnp.take(pl, phys, axis=bax), bax, 0)
                mask = jnp.reshape(active, (B,) + (1,) * (new_page.ndim - 1))
                val = jnp.where(mask, new_page, old)
                pooled = jnp.moveaxis(pl, bax, 0).at[phys].set(val)
                out.append(jnp.moveaxis(pooled, 0, bax))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        def _scrub_pages(cache, pids):
            """Reset freshly-allocated pages to init values (their ``pos``
            entries in particular: a recycled page must not resurrect its
            previous owner's positional validity)."""
            leaves = _split(cache)
            out = []
            ti = iter(zip(page_tmpl, paged_bax))
            for l, pg in zip(leaves, is_paged):
                if not pg:
                    out.append(l)
                    continue
                tmpl, bax = next(ti)
                idx = (slice(None),) * bax + (pids,)
                out.append(l.at[idx].set(tmpl))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        def _copy_page(cache, src, dst):
            """The device half of a copy-on-write fork."""
            leaves = _split(cache)
            out = []
            for l, bax, pg in zip(leaves, batch_axes, is_paged):
                if not pg:
                    out.append(l)
                    continue
                d = (slice(None),) * bax + (dst,)
                s = (slice(None),) * bax + (src,)
                out.append(l.at[d].set(l[s]))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        def _adopt_row(cache, small, row_i, pids, slot):
            """Move one prefilled row from the fresh group cache into the
            pool: its first ``len(pids)`` pages into the paged leaves,
            its residual state into the slot's dense rows."""
            n = pids.shape[0]
            pool_leaves = _split(cache)
            small_leaves = jax.tree_util.tree_leaves(small)
            out = []
            for pl, sl, bax, pg in zip(pool_leaves, small_leaves, batch_axes,
                                       is_paged):
                if bax is None:
                    out.append(pl)
                    continue
                if not pg:
                    idx = (slice(None),) * bax + (slot,)
                    src = (slice(None),) * bax + (row_i,)
                    out.append(pl.at[idx].set(sl[src]))
                    continue
                shape = list(sl.shape)
                row = jnp.take(sl, row_i, axis=bax)      # drop batch axis
                row = row.reshape(shape[:bax] + [ppr, page] + shape[bax + 2:])
                pages = jax.lax.slice_in_dim(row, 0, n, axis=bax)
                idx = (slice(None),) * bax + (pids,)
                out.append(pl.at[idx].set(pages))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        def _take_pages(cache, pids):
            """Gather page contents (migration extract)."""
            leaves = _split(cache)
            return [jnp.take(l, pids, axis=bax)
                    for l, bax, pg in zip(leaves, batch_axes, is_paged)
                    if pg]

        def _put_pages(cache, page_leaves, pids):
            """Scatter shipped page contents (migration insert)."""
            leaves = _split(cache)
            it = iter(page_leaves)
            out = []
            for l, bax, pg in zip(leaves, batch_axes, is_paged):
                if not pg:
                    out.append(l)
                    continue
                idx = (slice(None),) * bax + (pids,)
                out.append(l.at[idx].set(next(it)))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        def _take_resid(cache, slot):
            leaves = _split(cache)
            return [jnp.take(l, slot[None], axis=bax)
                    for l, bax, pg in zip(leaves, batch_axes, is_paged)
                    if bax is not None and not pg]

        def _put_resid(cache, resid, slot):
            leaves = _split(cache)
            it = iter(resid)
            out = []
            for l, bax, pg in zip(leaves, batch_axes, is_paged):
                if bax is None or pg:
                    out.append(l)
                    continue
                idx = (slice(None),) * bax + (slot[None],)
                out.append(l.at[idx].set(next(it)))
            return jax.tree_util.tree_unflatten(self._treedef, out)

        dn0 = (0,) if donate else ()
        self._gather = jax.jit(_gather)
        self._writeback = jax.jit(_writeback, donate_argnums=dn0)
        self._scrub = jax.jit(_scrub_pages, donate_argnums=dn0)
        self._cow = jax.jit(_copy_page, donate_argnums=dn0)
        self._adopt = jax.jit(_adopt_row, donate_argnums=dn0)
        self._take_pages = jax.jit(_take_pages)
        self._put_pages = jax.jit(_put_pages, donate_argnums=dn0)
        self._take_resid = jax.jit(_take_resid)
        self._put_resid = jax.jit(_put_resid, donate_argnums=dn0)

    # -- paged bookkeeping ---------------------------------------------------
    @property
    def free_pages(self) -> int:
        return self.pool.free_pages if self.paged else 0

    @property
    def used_pages(self) -> int:
        return self.pool.used_pages if self.paged else 0

    def page_need(self, prompt_len: int, max_new: int) -> int:
        """Pages a fresh request of this size must be able to reserve
        (ignores prefix sharing: an admission bound, never an overclaim)."""
        if not self.paged:
            return 0
        return pages_needed(prompt_len, max_new, self.page_size, self.max_len)

    def pages_for(self, n_tokens: int) -> int:
        """Pages reserving positions ``[0, n_tokens)`` (full row past
        ``max_len`` — the rolling-wrap case touches every page)."""
        if not self.paged:
            return 0
        if n_tokens > self.max_len:
            return self.pages_per_row
        return max(1, pages_for_tokens(n_tokens, self.page_size))

    def resident_page_demand(self) -> int:
        """Pages referenced by live page tables (shared pages count once
        per table — this is a *demand* signal, not an occupancy count)."""
        return sum(len(t) for t in self._tables if t is not None)

    @property
    def admissible_pages(self) -> int:
        """Pages a new claim could obtain: free pages plus pages pinned
        only by the prefix registry — those are reclaimable under
        pressure (:meth:`_alloc` evicts LRU prefixes until an allocation
        fits), so admission control must count them as available."""
        pinned: set = set()
        for t in self._tables:
            if t is not None:
                pinned.update(t)
        return self.pool.num_pages - len(pinned)

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Pool allocation with registry back-pressure: when the free
        list falls short, evict LRU prefix entries (their pages free once
        no live row shares them) and retry — a request is never refused
        memory that only the prefix cache is holding."""
        ids = self.pool.alloc(n)
        while (ids is None and self.prefix is not None
               and len(self.prefix)):
            self.prefix.evict_lru()
            ids = self.pool.alloc(n)
        return ids

    @property
    def pool_nbytes(self) -> float:
        """Bytes of the KV page pool (paged) or of the per-slot KV rows
        (dense) — the denominator of resident-requests-per-GB."""
        total = 0.0
        leaves = jax.tree_util.tree_leaves(self.cache)
        for leaf, sax, pg in zip(leaves, self._len_axes, self._is_paged_leaf):
            if self.paged:
                if pg:
                    total += leaf.nbytes
            elif sax is not None:
                total += leaf.nbytes
        return total

    @property
    def prefill_hit_rate(self) -> float:
        """Fraction of offered prefill tokens whose KV was already
        resident (prefix-registry exact hits; 0 before any prefill)."""
        if not self.paged or self.prefill_total_tokens == 0:
            return 0.0
        return self.prefill_hit_tokens / self.prefill_total_tokens

    def _tables_device(self) -> jax.Array:
        # a copy: the CPU backend may alias a numpy buffer zero-copy, and
        # the host table mutates while dispatched steps still read it
        return jnp.asarray(self._table_np.copy())

    def _set_table(self, slot: int, table: List[int]) -> None:
        self._tables[slot] = table
        self._table_np[slot] = self._null_page
        self._table_np[slot, :len(table)] = table

    def _cow_page(self, slot: int, wp: int) -> None:
        """Copy-on-write fork page ``wp`` of ``slot``'s table."""
        table = self._tables[slot]
        fresh = self._alloc(1)
        if fresh is None:
            raise RuntimeError(
                f"page pool exhausted during copy-on-write (slot {slot})")
        self.cache = self._cow(self.cache,
                               jnp.asarray(table[wp], jnp.int32),
                               jnp.asarray(fresh[0], jnp.int32))
        self.pool.release([table[wp]])
        table[wp] = fresh[0]
        self._table_np[slot, wp] = fresh[0]

    def _grow_table(self, slot: int) -> None:
        """Append one scrubbed page (a caller decoded past its declared
        reservation)."""
        fresh = self._alloc(1)
        if fresh is None:
            raise RuntimeError(
                f"page pool exhausted growing slot {slot}'s table")
        self.cache = self._scrub(self.cache, jnp.asarray(fresh, jnp.int32))
        self._tables[slot].append(fresh[0])
        self._table_np[slot, len(self._tables[slot]) - 1] = fresh[0]

    # -- slot management ---------------------------------------------------
    def try_claim(self, tokens: Optional[np.ndarray] = None,
                  max_new: int = 1,
                  reserve_tokens: Optional[int] = None) -> Optional[int]:
        """Claim a slot (dense) or a slot *plus a page reservation*
        (paged).  Paged claims size the reservation from the request
        (``tokens``/``max_new``), from an explicit token extent
        (``reserve_tokens`` — the migration-landing path), or — with no
        size information — a conservative full row; an exact prompt match
        in the prefix registry shares the resident prefix pages
        (copy-on-write past the fork point) and arms a compute-free
        prefill.  Returns None when no slot (or no sufficient page run)
        is available; a failed paged claim allocates nothing."""
        slot = None
        for i, free in enumerate(self.slot_free):
            if free:
                slot = i
                break
        if slot is None:
            return None
        if self.paged:
            if not self._claim_pages(slot, tokens, max_new, reserve_tokens):
                return None
        self.slot_free[slot] = False
        self.peak_active = max(self.peak_active, self.active)
        if self._reset_on_claim:
            self.reset_slot(slot)
        return slot

    def _claim_pages(self, slot: int, tokens, max_new: int,
                     reserve_tokens: Optional[int]) -> bool:
        page = self.page_size
        if reserve_tokens is not None or tokens is None:
            n = (self.pages_for(reserve_tokens)
                 if reserve_tokens is not None else self.pages_per_row)
            ids = self._alloc(n)
            if ids is None:
                return False
            self.cache = self._scrub(self.cache, jnp.asarray(ids, jnp.int32))
            self._set_table(slot, ids)
            return True
        L = len(tokens)
        extent = token_extent(L, max_new)
        wrap = extent > self.max_len
        n_total = pages_needed(L, max_new, page, self.max_len)
        hit = (None if (wrap or self.prefix is None)
               else self.prefix.lookup(tokens))
        if hit is None:
            ids = self._alloc(n_total)
            if ids is None:
                return False
            self.cache = self._scrub(self.cache, jnp.asarray(ids, jnp.int32))
            self._set_table(slot, ids)
            # wrap rows touch every page, so their prompt pages can never
            # be pinned immutable — they are not registrable
            self._claim_meta[slot] = (np.asarray(tokens, np.int32)
                                      if (self.prefix is not None
                                          and not wrap) else None)
            return True
        # Exact-prompt hit: reference the resident prefix pages; the page
        # the first decode write lands in must be private (COW fork).
        n_pref = len(hit.page_ids)
        cow_partial = extent > L and L % page != 0
        fresh_needed = (n_total - n_pref) + (1 if cow_partial else 0)
        # retain BEFORE allocating: _alloc may evict this very entry
        # under pressure, and our references must keep its pages alive
        self.pool.retain(hit.page_ids)
        fresh = self._alloc(fresh_needed)
        if fresh is None:
            self.pool.release(hit.page_ids)
            return False
        table = list(hit.page_ids)
        fi = 0
        if cow_partial:
            cow = fresh[fi]
            fi += 1
            self.cache = self._cow(self.cache,
                                   jnp.asarray(table[L // page], jnp.int32),
                                   jnp.asarray(cow, jnp.int32))
            self.pool.release([table[L // page]])
            table[L // page] = cow
        tail = fresh[fi:]
        if tail:
            self.cache = self._scrub(self.cache, jnp.asarray(tail, jnp.int32))
            table += tail
        self._set_table(slot, table)
        self._pending_first[slot] = (hit.first_token, hit.length)
        return True

    def reset_slot(self, slot: int) -> None:
        """Restore one slot's cache rows to their init values.

        Required between requests for recurrent families (rwkv6 / hymba's
        SSM lanes), whose prefill starts from the row's *current* state — a
        reused slot would otherwise leak the previous request's state into
        the next prompt.  Copies from the single-row init template (built
        once in ``__init__``) rather than materializing a pool-sized init.
        """
        self.cache = self._reset(self.cache, self._row_init,
                                 jnp.asarray(slot, jnp.int32))

    def release(self, slot: int) -> None:
        self.slot_free[slot] = True
        self.slot_pos[slot] = 0
        if self.paged:
            table = self._tables[slot]
            if table is not None:
                self.pool.release(table)
            self._tables[slot] = None
            self._table_np[slot] = self._null_page
            self._pending_first.pop(slot, None)
            self._claim_meta.pop(slot, None)

    @property
    def active(self) -> int:
        return sum(not f for f in self.slot_free)

    # -- mid-stream migration state -----------------------------------------
    def compatible_with(self, other: "Endpoint") -> bool:
        """Row states are interchangeable between two endpoints iff they
        serve the same model at the same context budget with the same
        pool layout (every shipped leaf then has identical non-batch
        dimensions)."""
        return (other.cfg is self.cfg and other.max_len == self.max_len
                and other.paged == self.paged
                and (not self.paged or other.page_size == self.page_size)
                and getattr(other, "_tp", 1) == self._tp)

    def extract_rows(self, slots: List[int]) -> List[Any]:
        """Slice the given slots' cache rows out of the pool.

        Dense pool: one full row state per slot (each cache leaf with the
        batch axis narrowed to size 1).  Paged pool: a :class:`PagedRow`
        carrying only the pages covering the row's *filled* positions
        plus its residual leaves — a partially-filled row ships strictly
        fewer bytes than a full dense row.
        """
        if not self.paged:
            return [self._extract(self.cache, jnp.asarray(s, jnp.int32))
                    for s in slots]
        out = []
        for s in slots:
            pos = int(self.slot_pos[s])
            n = min(self.pages_for(max(pos, 1)), len(self._tables[s]))
            pids = jnp.asarray(self._tables[s][:n], jnp.int32)
            out.append(PagedRow(
                n_pages=n, pos=pos,
                page_leaves=self._take_pages(self.cache, pids),
                resid_leaves=self._take_resid(self.cache,
                                              jnp.asarray(s, jnp.int32))))
        return out

    def insert_rows(self, rows: List[Any], slots: List[int],
                    positions: List[int]) -> None:
        """Scatter extracted row states into *claimed* slots of this pool
        and set their decode positions — the receiving half of mid-stream
        migration: decode resumes at ``positions`` with no re-prefill.
        Paged rows land in the slot's reserved pages (grown on demand if
        the reservation was tighter than the shipped state).
        """
        for state, slot, pos in zip(rows, slots, positions):
            if not self.paged:
                self.cache = self._insert(self.cache, state,
                                          jnp.asarray(slot, jnp.int32))
            else:
                while len(self._tables[slot]) < state.n_pages:
                    self._grow_table(slot)
                pids = jnp.asarray(self._tables[slot][:state.n_pages],
                                   jnp.int32)
                self.cache = self._put_pages(self.cache, state.page_leaves,
                                             pids)
                self.cache = self._put_resid(self.cache, state.resid_leaves,
                                             jnp.asarray(slot, jnp.int32))
            self.slot_pos[slot] = min(pos, self.max_len)

    def cache_nbytes_per_row(self, length: int) -> float:
        """Bytes of one slot's live cache state at decode position
        ``length`` — what a migration actually ships over a link.

        Leaves with a sequence axis (KV blocks) count only their filled
        positions; recurrent state leaves (no length axis) count in full.
        In paged mode the filled extent rounds UP to page granularity —
        the transfer ships whole pages, and ``_Transit.nbytes``,
        ``link_MB`` and the simulator's payload model must agree on what
        actually crosses the link.

        Bytes are computed from each leaf's *logical* shape and dtype —
        never from its device buffer footprint.  A replicated or sharded
        template leaf on a multi-device (tensor-parallel) endpoint can
        report a physical ``nbytes`` that multiplies per device replica,
        but a migration ships the logical row exactly once.
        """
        if self.paged:
            eff = min(self.pages_for(max(length, 1)) * self.page_size,
                      self.max_len)
        else:
            eff = min(length, self.max_len)
        total = 0.0
        for leaf, bax, sax in zip(self._row_leaves, self._batch_axes,
                                  self._len_axes):
            if bax is None:
                continue
            # template: batch axis = 1, so this is already per-row
            per_row = float(np.prod(leaf.shape)
                            * np.dtype(leaf.dtype).itemsize)
            if sax is not None:
                per_row *= eff / leaf.shape[sax]
            total += per_row
        return total

    # -- steps --------------------------------------------------------------
    def prefill_one(self, slot: int, tokens: np.ndarray) -> int:
        """Run prefill for a single request into its slot's cache rows.

        Returns the first generated token.
        """
        return self.prefill_batch({slot: tokens})[slot]

    def prefill_batch(self, prompts: Dict[int, np.ndarray]) -> Dict[int, int]:
        """Pack multiple claimed slots' prompts into shared prefill calls.

        Prompts are grouped by length; each group runs one jitted prefill
        at a power-of-two *bucketed* batch (next pow2 >= group size, capped
        at the pool) on a fresh cache whose rows are scattered into the
        pool — small waves stop paying full-pool prefill cost, and a
        handful of compiled shapes are reused.  Pure-attention families
        additionally right-pad each group to a power-of-two length (causal
        masking keeps the padded tail inert).  Recurrent families thread
        per-row state token by token, so their rows are never length-padded.

        In paged mode, slots whose claim hit the prefix registry skip
        compute entirely: their prompt pages are already resident and the
        registered first token seeds their stream (bit-identical to a
        fresh prefill — the registering prefill ran the same program on
        the same inputs).  Missing prompts prefill normally, land in the
        slot's reserved pages, and register themselves for the next
        invocation.  Returns slot -> first generated token.
        """
        self.prefill_total_tokens += sum(len(t) for t in prompts.values())
        if self.paged:
            out: Dict[int, int] = {}
            miss: Dict[int, np.ndarray] = {}
            for slot, toks in prompts.items():
                pend = self._pending_first.pop(slot, None)
                if pend is not None:
                    first, L = pend
                    self.slot_pos[slot] = L
                    self.prefill_hit_tokens += L
                    out[slot] = first
                else:
                    miss[slot] = toks
            if miss:
                out.update(self._prefill_batch_bucketed(miss))
            return out
        if self.bucket_prefill:
            return self._prefill_batch_bucketed(prompts)
        return self._prefill_batch_padded(prompts)

    def _prefill_batch_bucketed(self,
                                prompts: Dict[int, np.ndarray]
                                ) -> Dict[int, int]:
        by_len: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        for slot, toks in prompts.items():
            by_len.setdefault(len(toks), []).append((slot, toks))
        out: Dict[int, int] = {}
        for L, group in sorted(by_len.items()):
            G = len(group)
            Bp = min(self.slots, max(1, 1 << (G - 1).bit_length()))
            Lb = L
            if self._pad_len:
                cand = 1 << max(L - 1, 0).bit_length()
                if L <= cand <= self._len_cap:
                    Lb = cand
            self.prefill_computed_tokens += Bp * Lb
            with span("repro.prefill", L=L, Lb=Lb, G=G, Bp=Bp):
                # Pad batch rows AND the scatter index to the pow2 bucket
                # by duplicating the last real row: jit then only ever
                # sees power-of-two shapes, and the duplicate scatter
                # writes carry identical row values (rows are
                # batch-independent), so the overlapping update is
                # value-deterministic.
                tok = np.zeros((Bp, Lb), np.int32)
                slot_arr = np.zeros(Bp, np.int32)
                for i in range(Bp):
                    slot, toks = group[min(i, G - 1)]
                    tok[i, :L] = toks
                    slot_arr[i] = slot
                lengths = (jnp.full(Bp, L, jnp.int32) if self._pad_len
                           else None)
                logits, small = self._prefill_rows(
                    self.params, jnp.asarray(tok), lengths, self._row_init)
                if self.paged:
                    self._adopt_group(group, small, L)
                else:
                    self.cache = self._scatter_rows(self.cache, small,
                                                    jnp.asarray(slot_arr))
                with span("repro.prefill.wait"):
                    logits.block_until_ready()
                lg = np.asarray(logits)
                for i, (slot, _) in enumerate(group):
                    self.slot_pos[slot] = L
                    out[slot] = int(np.argmax(lg[i]))
                    if self.paged:
                        self._register_prefix(slot, out[slot])
        return out

    def _adopt_group(self, group, small, L: int) -> None:
        """Scatter one prefilled length group's rows into their slots'
        reserved pages (paged pools have no contiguous rows to scatter
        into)."""
        n = self.pages_for(max(L, 1))
        for i, (slot, _) in enumerate(group):
            pids = jnp.asarray(self._tables[slot][:n], jnp.int32)
            self.cache = self._adopt(self.cache, small,
                                     jnp.asarray(i, jnp.int32), pids,
                                     jnp.asarray(slot, jnp.int32))

    def _register_prefix(self, slot: int, first_token: int) -> None:
        """Publish a just-prefilled prompt to the prefix registry.  The
        registry's view must stay immutable while the owning row decodes
        on, so a partially-filled last page is registered as a private
        copy (the full pages are shared as-is: the owner never rewrites
        positions below its prompt length)."""
        meta = self._claim_meta.pop(slot, None)
        if meta is None or self.prefix is None:
            return
        L = len(meta)
        n = self.pages_for(max(L, 1))
        reg_ids = list(self._tables[slot][:n])
        copied = None
        if L % self.page_size != 0:
            cp = self._alloc(1)
            if cp is None:
                return                 # pool too tight to pin: skip
            self.cache = self._cow(self.cache,
                                   jnp.asarray(reg_ids[-1], jnp.int32),
                                   jnp.asarray(cp[0], jnp.int32))
            reg_ids[-1] = cp[0]
            copied = cp
        self.prefix.register(meta, reg_ids, first_token)
        if copied is not None:
            # the registry holds its own reference now (or declined to)
            self.pool.release(copied)

    def _prefill_batch_padded(self,
                              prompts: Dict[int, np.ndarray]
                              ) -> Dict[int, int]:
        """Legacy path: every length group pads to batch=slots and runs on
        the pool cache, snapshot-protecting busy rows (kept as the
        before/after baseline for ``benchmarks/serving_bench.py``)."""
        by_len: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        for slot, toks in prompts.items():
            by_len.setdefault(len(toks), []).append((slot, toks))
        out: Dict[int, int] = {}
        # A prefill call writes cache rows for *every* batch row, so it
        # would clobber busy rows outside the current length group: slots
        # mid-decode, rows an earlier group just filled, and — for
        # recurrent families, whose state a zero-token prefill advances —
        # claimed rows a later group has yet to fill.  (Attention rows of
        # later groups need no protection: groups run shortest-first, so
        # their own prefill fully overwrites the polluted positions.)
        external = [s for s in range(self.slots)
                    if not self.slot_free[s] and s not in prompts]
        done: List[int] = []
        for L, group in sorted(by_len.items()):
            group_slots = {slot for slot, _ in group}
            protect = external + done
            if self._reset_on_claim:            # recurrent state families
                protect = [s for s in range(self.slots)
                           if not self.slot_free[s] and s not in group_slots]
            snap = (jax.tree_util.tree_map(jnp.copy, self.cache)
                    if protect else None)
            tok = np.zeros((self.slots, L), np.int32)
            for slot, toks in group:
                tok[slot] = toks
            self.prefill_computed_tokens += self.slots * L
            logits, self.cache = self._prefill(
                self.params, {"tokens": jnp.asarray(tok)}, self.cache)
            for s in protect:
                self.cache = self._restore(self.cache, snap,
                                           jnp.asarray(s, jnp.int32))
            lg = np.asarray(logits)
            for slot, _ in group:
                self.slot_pos[slot] = L
                out[slot] = int(np.argmax(lg[slot]))
                done.append(slot)
        return out

    def decode_all(self, tokens_by_slot: Dict[int, int]) -> Dict[int, int]:
        """One decode step for every active slot. tokens_by_slot maps
        slot -> last emitted token. Returns slot -> next token.

        Slots outside ``tokens_by_slot`` are masked inactive for the step:
        their cache rows (KV positions, recurrent state) are untouched, so
        rows that retired or were cancelled mid-stream stay frozen while
        their neighbors decode.

        Paged pools first guarantee every stepping row's *write page* is
        private (copy-on-write fork of a still-shared page, one lazily
        grown page for rows decoding past their reservation), then gather
        pages into the contiguous per-row view, run the same jitted
        decode program dense mode runs, and scatter only the written
        pages back."""
        self.decode_calls += 1
        self.decode_rows += len(tokens_by_slot)
        tok = np.zeros(self.slots, np.int32)
        act = np.zeros(self.slots, bool)
        # a copy, not a view: ``slot_pos`` advances below while the paged
        # writeback dispatched with ``t`` may still be pending, and the CPU
        # backend can alias a numpy buffer zero-copy
        t = np.array(self.slot_pos, np.int32)
        for s, v in tokens_by_slot.items():
            tok[s] = v
            act[s] = True
        if not self.paged:
            logits, self.cache = self._decode(self.params, self.cache,
                                              jnp.asarray(tok),
                                              jnp.asarray(t),
                                              jnp.asarray(act))
        else:
            for s in tokens_by_slot:
                wp = (int(self.slot_pos[s]) % self.max_len) // self.page_size
                while wp >= len(self._tables[s]):
                    self._grow_table(s)
                if self.pool.is_shared(self._tables[s][wp]):
                    self._cow_page(s, wp)
            tables = self._tables_device()
            dense = self._gather(self.cache, tables)
            logits, new_dense = self._decode(self.params, dense,
                                             jnp.asarray(tok),
                                             jnp.asarray(t),
                                             jnp.asarray(act))
            self.cache = self._writeback(self.cache, new_dense, tables,
                                         jnp.asarray(t), jnp.asarray(act))
        # the wait holds only the device's work: the copy of the logits to
        # the host that follows leaves the device idle, and is the host's
        with span("repro.decode.wait"):
            logits.block_until_ready()
        with span("repro.sample"):
            lg = np.asarray(logits)
            out = {}
            for s in tokens_by_slot:
                self.slot_pos[s] += 1
                out[s] = int(np.argmax(lg[s]))
        return out


def make_serve_step(cfg: ModelConfig,
                    mode: str) -> Callable:
    """The pure functions the dry-run lowers (no engine state).

    mode="prefill": (params, batch, cache) -> (last_logits, cache)
    mode="decode":  (params, cache, tokens, t) -> (logits, cache)
    """
    if mode == "prefill":
        def serve_step(params, batch, cache):
            return model_zoo.prefill(cfg, params, batch, cache)
        return serve_step
    if mode == "decode":
        def serve_step(params, cache, tokens, t):
            return model_zoo.decode(cfg, params, cache, tokens, t)
        return serve_step
    raise ValueError(mode)
