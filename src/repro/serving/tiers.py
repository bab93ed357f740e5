"""The live N-tier continuum runtime.

This is the live (non-simulated) integration of every paper component:

    EdgeCloudContinuum (over a Topology chain, ingress at tier 0)
      ├── tier 0..N-1:  Gateway (bounded backlog queue) + Endpoint pool
      │                 (slots/model) + MetricsRegistry + per-function
      │                 Autoscaler (Knative-KPA concurrency)
      ├── ReplicationController  (deepest-tier spec -> shallower tiers,
      │                           selective merge)
      ├── ControlLoop + Policy   (Eqs (1)-(4) / static / net-aware / hedged
      │                           — one controller boundary per adjacent
      │                           tier pair, the same loop the simulator
      │                           drives)
      └── Router                 (vectorized categorical assignment of the
                                  queued batch over the tier distribution)

Requests enter at the ingress gateway (``submit``); each scheduler tick
runs one scrape-and-update cycle through the shared
:class:`repro.core.policy.ControlLoop`, assigns the ingress batch over
the tiers by the composed R_t distribution, and serves **each tier's own
gateway** with a *continuous-batching decode loop*: every scheduler step
runs one shared ``decode_all`` step across all slot-resident requests,
retires finished rows immediately, and admits queued requests into the
freed slots the same step (packed bucketed prefill) — so a short request
never waits out a long co-resident one, and the losing twin of a hedge
pair is **cancelled** (slot evicted, no latency recorded) the step its
sibling completes.  ``scheduler="wave"`` keeps the legacy
run-to-completion wave drain as the before/after baseline, and
``max_steps_per_tick`` lets long requests stay slot-resident across
ticks.  Moving a request down the chain — routing past a
boundary or (with ``topology.waterfall``) spilling a stalled tier's load
— crosses the corresponding :class:`~repro.core.topology.LinkSpec`,
charging its RTT + payload serialization to the request's latency clock
and counting the boundary crossing.

Policies carrying a ``migrate_threshold`` (``"auto+migrate"``) extend
offloading to **slot-resident** work: when a boundary's R_t reaches the
threshold, the tier cancels its most slot-hungry in-flight rows (longest
remaining decode first), extracts their KV/state rows from the cache
pool, and ships them over the link — ``nbytes`` = live cache bytes at
the row's position plus the token tail — and the destination re-admits
them into free slots *without re-prefill*, resuming decode at the same
position (token-stream bit-identity is pinned by tests).  A landing that
finds the destination full ABORTS: the row resumes at its source, never
lost; transfers still in flight when a step-capped tick ends land on a
later tick.

The controller sees the continuum the way the paper's Knative deployment
does (queue-proxy depth/age gauges per component): boundary b is fed tier
b's latency windows, tier b's **own gateway backlog ages**, and the
demand that actually **crossed** into tier b this interval (the
per-boundary ``arrivals`` form of ``ControlLoop.step_tiers``), so an
intermediate boundary's R_t rises when its own backlog ages — before its
completions drain — and ``auto+net`` caps each boundary by the link it
actually crosses.  Requests an admission budget could not serve stay queued in
their tier's gateway (the ingress gateway's backlog re-enters routing;
deeper backlogs belong to their tier), which is exactly the simulator's
per-tier queue state.

The historical two-tier constructor (``edge=..., cloud=...``) builds a
2-tier :class:`~repro.core.topology.Topology` via :meth:`Topology.pair`;
``edge``/``cloud`` remain as attribute aliases for the ingress/deepest
tiers.  Everything model-related goes through ``serving.engine.Endpoint``;
tier capacities are expressed in concurrent slots, so the same runtime
works with real TPU meshes (slots = per-pod batch) or the CPU tests
(slots=4).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
import warnings
import zlib
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import offload
from repro.core.autoscaler import Autoscaler
from repro.core.metrics import MetricsRegistry, span
from repro.core.policy import AutoOffload, ControlLoop, Policy, PolicySpec
from repro.core.replication import (AutoscalingPolicy, FunctionSpec,
                                    ReplicationController)
from repro.core.topology import TierSpec, Topology
from repro.models.common import ModelConfig
from repro.serving.engine import Endpoint, Request
from repro.workloads.faults import (LINK_KINDS, FaultEvent, FaultSchedule,
                                    LinkState)
from repro.workloads.trace import Trace


@dataclasses.dataclass
class TierConfig:
    """Legacy two-tier tier shape (sugar for a named
    :class:`~repro.core.topology.TierSpec` via ``Topology.pair``)."""
    slots: int = 4
    max_len: int = 256
    # synthetic per-request overhead (edge->cloud WAN RTT), seconds
    extra_latency_s: float = 0.0
    # default KPA bounds for functions deployed without an explicit policy
    autoscaling: Optional[AutoscalingPolicy] = None
    stable_window_s: float = 60.0
    panic_window_s: float = 6.0


@dataclasses.dataclass
class _Queued:
    """One gateway queue entry (+ hedge bookkeeping)."""
    fn: str
    req: Request
    t_submit: float
    tick_no: int = 0
    hedge: bool = False
    pair: Optional["_HedgePair"] = None


@dataclasses.dataclass
class _InFlight:
    """One slot-resident request inside a tier's continuous decode loop."""
    item: _Queued
    slot: int
    toks: List[int]               # generated tokens so far (first from prefill)
    need: int                     # total tokens to generate
    done_at: float = 0.0


@dataclasses.dataclass
class _Transit:
    """One migrated request's extracted state, in flight over a link.

    Created by :meth:`EdgeCloudContinuum._fire_migrations` (the source
    tier already cancelled the row and freed its slot); resolved by
    :meth:`EdgeCloudContinuum._land_migrations` once the wall clock
    passes ``t_land`` — possibly ticks later, when the link is slow.
    """
    item: _Queued
    fn: str
    rows: List                     # Endpoint.extract_rows state (one row)
    pos: int                       # decode position at extraction
    toks: List[int]                # tokens generated so far
    need: int                      # total tokens to generate
    src: int                       # source tier index
    dst: int                       # destination tier index
    t_land: float                  # wall-clock landing time
    nbytes: float                  # cache bytes + token tail shipped


@dataclasses.dataclass
class _HedgePair:
    """Links a primary request to its hedge twin so only the winning
    arm's latency feeds the controller.

    Under the continuous scheduler the race settles the moment one arm
    finishes: ``winner`` flips from ``None`` to ``"primary"``/``"twin"``
    and :meth:`EdgeCloudContinuum._evict_loser` cancels the slot-resident
    sibling the same scheduler step.  The legacy wave scheduler still uses
    :meth:`note` + latency comparison (both arms run to completion there).
    """
    fn: str
    # continuous-scheduler resolution state
    winner: Optional[str] = None            # None | "primary" | "twin"
    winner_req: Optional[Request] = None
    primary_ref: Optional[Tuple[int, _InFlight]] = None   # (tier_idx, rec)
    twin_ref: Optional[Tuple[int, _InFlight]] = None
    # wave-scheduler bookkeeping (legacy run-to-completion path)
    primary_lat: Optional[float] = None
    primary_tier: Optional["Tier"] = None
    twin_lat: Optional[float] = None
    twin_tier: Optional["Tier"] = None
    twin_req: Optional[Request] = None

    def note(self, item: "_Queued", tier: "Tier", lat: float) -> None:
        if item.hedge:
            self.twin_lat, self.twin_tier = lat, tier
            self.twin_req = item.req
        else:
            self.primary_lat, self.primary_tier = lat, tier

    def set_ref(self, hedge: bool, tier_idx: int, rec: _InFlight) -> None:
        """Remember where an arm is slot-resident so the loser can be
        evicted the step its sibling completes."""
        if hedge:
            self.twin_ref = (tier_idx, rec)
        else:
            self.primary_ref = (tier_idx, rec)


class Gateway:
    """One tier's bounded backlog queue (the Knative queue-proxy stand-in).

    Requests wait here between scheduler ticks; the controller boundary
    of the owning tier reads the backlog's ages each scrape.  ``capacity``
    bounds the *resting* backlog (``None`` = unbounded): client submits
    and requeues past it are rejected (the live 503), while in-tick
    placement uses ``force=True`` because a routed request may still be
    served this very tick.
    """

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity
        self.items: Deque[_Queued] = deque()
        self.rejected = 0

    def push(self, item: _Queued, force: bool = False) -> bool:
        if (not force and self.capacity is not None
                and len(self.items) >= self.capacity):
            self.rejected += 1
            return False
        self.items.append(item)
        return True

    def pop_all(self) -> List[_Queued]:
        items = list(self.items)
        self.items.clear()
        return items

    def backlog_ages(self, now: float, tick_no: int,
                     fn_ids: Dict[str, int],
                     num_functions: int) -> List[List[float]]:
        """Per-function ages of true *backlog*: entries that survived a
        previous scheduler round.  Fresh arrivals have waited ~0 s —
        mixing those into X_l(t) would drag p50 toward zero and fire
        Eq (1) spuriously."""
        ages: List[List[float]] = [[] for _ in range(num_functions)]
        for item in self.items:
            if item.tick_no < tick_no:
                ages[fn_ids[item.fn]].append(now - item.t_submit)
        return ages

    def __len__(self) -> int:
        return len(self.items)


class Tier:
    """One serving location: endpoints by function name + metrics +
    per-function KPA autoscalers.

    ``cfg`` may be a legacy :class:`TierConfig` or an N-tier
    :class:`~repro.core.topology.TierSpec` — both carry the same serving
    fields."""

    def __init__(self, name: str, cfg):
        self.name = name
        self.cfg = cfg
        self.endpoints: Dict[str, Endpoint] = {}
        self.autoscalers: Dict[str, Autoscaler] = {}
        self.metrics = MetricsRegistry([])
        # continuous-batching decode loop state: fn -> slot -> _InFlight
        self.inflight: Dict[str, Dict[int, _InFlight]] = {}

    def deploy(self, fn_name: str, model_cfg: ModelConfig, params,
               autoscaling: Optional[AutoscalingPolicy] = None) -> None:
        """Stand up this tier's endpoint pool for one function.

        A cost-modeled :class:`TierSpec` must arrive *resolved*
        (``Topology.costed``/``resolve_costs``): its ``slots`` are then
        already HBM-clamped by the same ``hlo_cost`` pricing that set
        the simulator's service rate — the sim<->live shared-cost-model
        contract.  ``spec.model`` names the architecture that *priced*
        the tier; ``model_cfg`` is what this pool actually serves (tests
        deploy smoke-sized configs against production-priced specs).  A
        ``mesh_shape`` deploys the pool shard_map tensor-parallel when
        the host has enough devices, else falls back unsharded with a
        warning (bit-identical either way).
        """
        if getattr(self.cfg, "model", None) is not None and \
                not getattr(self.cfg, "resolved", True):
            raise ValueError(
                f"tier {self.name!r} declares a cost model "
                f"({self.cfg.model}) but is unresolved; build the chain "
                f"via Topology.costed(...) or call .resolve_costs() "
                f"before deploying")
        mesh = None
        mesh_shape = getattr(self.cfg, "mesh_shape", None)
        if mesh_shape is not None and (
                int(mesh_shape[0]) * int(mesh_shape[1])) > 1:
            from repro.serving import sharded
            mesh = sharded.tier_mesh(mesh_shape)
        page_size = getattr(self.cfg, "page_size", None)
        self.endpoints[fn_name] = Endpoint(
            model_cfg, params, slots=self.cfg.slots, max_len=self.cfg.max_len,
            paged=page_size is not None,
            page_size=page_size if page_size is not None else 16,
            total_pages=getattr(self.cfg, "pool_pages", None),
            mesh=mesh)
        self.inflight.setdefault(fn_name, {})
        self.metrics.register(fn_name)
        # A TierSpec that declares its own KPA bounds governs its whole
        # pool (e.g. an intermediate tier pinned to zero with max_scale=0).
        # Legacy TierConfig keeps its documented fallback semantics: the
        # function's spec wins, the tier's bounds apply only when the
        # function has none.
        if isinstance(self.cfg, TierSpec) and self.cfg.autoscaling is not None:
            policy = self.cfg.autoscaling
        else:
            policy = autoscaling or self.cfg.autoscaling or AutoscalingPolicy()
        self.autoscalers[fn_name] = Autoscaler(
            policy,
            stable_window_s=self.cfg.stable_window_s,
            panic_window_s=self.cfg.panic_window_s)

    # -- capacity ----------------------------------------------------------
    def free_slots(self, fn_name: str) -> int:
        ep = self.endpoints[fn_name]
        return ep.slots - ep.active

    def capacity(self, fn_name: str) -> int:
        """Admitted concurrency right now: ceil(replicas x target
        concurrency), bounded by the KV-cache pool. 0 when scaled to zero.
        A fractional target under-one admits *less* than one request per
        replica (e.g. 2 replicas x 0.5 admit 1), not one per replica.
        On a cost-modeled tier the pool bound (``Endpoint.slots``) is the
        HBM-derived slot count from ``launch/tier_cost.py`` — the same
        number the simulator's ``_SimTier`` pools use, so live KPA
        admission and simulated capacity share one cost model."""
        asc = self.autoscalers[fn_name]
        want = math.ceil(asc.replicas * asc.policy.target_concurrency)
        return min(self.endpoints[fn_name].slots, want)

    def replicas(self, fn_name: str) -> int:
        return self.autoscalers[fn_name].replicas

    def inflight_count(self, fn_name: str) -> int:
        return len(self.inflight.get(fn_name, ()))

    def admission_budget(self, fn_name: str, items: List["_Queued"],
                         cap: Optional[int] = None) -> int:
        """How many of ``items`` (in order) this tier can admit right
        now.  Dense pools: free slots (bounded by ``cap``, the caller's
        KPA-admitted concurrency).  Paged pools additionally walk the
        queue head charging each request the pages it must be able to
        reserve (``Endpoint.page_need`` — sharing-blind, so never an
        overclaim): admission is gated on *memory actually reserved*,
        not slot count alone."""
        ep = self.endpoints[fn_name]
        budget = self.free_slots(fn_name)
        if cap is not None:
            budget = min(budget, cap)
        budget = max(0, min(budget, len(items)))
        if not ep.paged or budget == 0:
            return budget
        free = ep.admissible_pages
        n = 0
        for item in items[:budget]:
            need = ep.page_need(len(item.req.tokens),
                                max(item.req.max_new, 1))
            if need > free:
                break
            free -= need
            n += 1
        return n

    # -- continuous-batching decode loop ------------------------------------
    # One scheduler step is: decode every in-flight slot once (``step``),
    # retire finished rows immediately, then admit queued requests into the
    # freed slots (``admit``) — so a short request never waits for a long
    # co-resident one, and a cancelled hedge loser's slot is reusable the
    # same step it is evicted.

    def admit(self, fn_name: str, items: List[_Queued]
              ) -> Tuple[List[_InFlight], List[_InFlight]]:
        """Claim slots for ``items`` and run one packed bucketed prefill.

        Returns ``(in_flight, finished)``: requests needing only their
        prefill token retire immediately (their slot frees right away);
        the rest join the tier's in-flight set for the shared
        ``decode_all`` stream.  The caller sizes admissions within
        ``free_slots`` — over-admission raises, as in ``serve_batch``.
        """
        ep = self.endpoints[fn_name]
        claimed: List[Tuple[_Queued, int]] = []
        with span("repro.admit", tier=self.name, rows=len(items)) as sp:
            if span.is_enabled():
                sp.set_metadata(rids=" ".join(str(it.req.rid)
                                              for it in items))
            for item in items:
                slot = ep.try_claim(tokens=item.req.tokens,
                                    max_new=max(item.req.max_new, 1))
                if slot is None:
                    for _, s in claimed:
                        ep.release(s)
                    raise RuntimeError(
                        f"{self.name}/{fn_name}: admission of {len(items)} "
                        f"exceeds free slots/pages — scheduler admitted "
                        f"past capacity")
                claimed.append((item, slot))
            now = time.perf_counter()
            for item, _ in claimed:
                item.req.t_admit = now
            try:
                firsts = ep.prefill_batch(
                    {slot: item.req.tokens for item, slot in claimed})
            # lint: ignore[swallowed-exception] -- cleanup-and-reraise:
            # slots must be released on ANY prefill failure or they leak
            except Exception:
                for _, s in claimed:
                    ep.release(s)
                raise
        now = time.perf_counter()
        in_flight: List[_InFlight] = []
        finished: List[_InFlight] = []
        for item, slot in claimed:
            item.req.t_first = now
            rec = _InFlight(item, slot, [firsts[slot]],
                            max(item.req.max_new, 1))
            if rec.need == 1:
                rec.done_at = now
                ep.release(slot)
                finished.append(rec)
            else:
                self.inflight[fn_name][slot] = rec
                in_flight.append(rec)
        return in_flight, finished

    def step(self, fn_name: str) -> List[_InFlight]:
        """One shared ``decode_all`` step over every in-flight slot of
        ``fn_name``; finished rows are retired (slot released) immediately
        and returned."""
        fl = self.inflight.get(fn_name)
        if not fl:
            return []
        ep = self.endpoints[fn_name]
        with span("repro.decode", tier=self.name, rows=len(fl),
                  fold=ep.kv_fold):
            nxt = ep.decode_all({slot: rec.toks[-1]
                                 for slot, rec in fl.items()})
            now = time.perf_counter()
            finished: List[_InFlight] = []
            for slot, tok in nxt.items():
                rec = fl[slot]
                rec.toks.append(tok)
                if len(rec.toks) >= rec.need:
                    rec.done_at = now
                    ep.release(slot)
                    del fl[slot]
                    finished.append(rec)
        return finished

    def cancel(self, fn_name: str, slot: int) -> _InFlight:
        """Evict one in-flight request mid-decode (a hedge loser): the
        slot frees immediately and no latency sample is recorded."""
        rec = self.inflight[fn_name].pop(slot)
        self.endpoints[fn_name].release(slot)
        return rec

    def finish(self, fn_name: str, rec: _InFlight) -> float:
        """Fill the request's output from a retired in-flight record and
        return its end-to-end latency (metrics recording is the caller's
        call — hedge losers never record)."""
        req = rec.item.req
        req.output = np.asarray(rec.toks, np.int32)
        req.tier = self.name
        req.t_done = rec.done_at
        req.latency_s = (rec.done_at - rec.item.t_submit
                         + self.cfg.extra_latency_s)
        return req.latency_s

    # -- serving -----------------------------------------------------------
    def serve_batch(self, fn_name: str,
                    items: List[Tuple[Request, float]],
                    record: Optional[List[bool]] = None
                    ) -> List[Tuple[np.ndarray, float]]:
        """Serve a wave of requests together on one endpoint.

        All prompts share packed prefill calls and one ``decode_all``
        stream; each request's latency is measured from its submit
        timestamp to the decode step that finished it. ``record`` masks
        which latencies feed this tier's metrics (hedged arms defer to the
        pair winner). The caller is responsible for sizing waves within
        ``free_slots`` — admission past the pool raises instead of
        silently corrupting a live slot's KV cache (the old ``slot = 0``
        fallback).
        """
        ep = self.endpoints[fn_name]
        claimed: List[Tuple[Request, float, int]] = []
        for req, t_submit in items:
            slot = ep.try_claim(tokens=req.tokens,
                                max_new=max(req.max_new, 1))
            if slot is None:
                for _, _, s in claimed:
                    ep.release(s)
                raise RuntimeError(
                    f"{self.name}/{fn_name}: wave of {len(items)} exceeds "
                    f"free slots/pages — scheduler admitted past capacity")
            claimed.append((req, t_submit, slot))
        now = time.perf_counter()
        for req, _, _ in claimed:
            req.t_admit = now

        try:
            firsts = ep.prefill_batch(
                {slot: req.tokens for req, _, slot in claimed})
            now = time.perf_counter()
            outs: Dict[int, List[int]] = {}
            need: Dict[int, int] = {}
            done_at: Dict[int, float] = {}
            active: Dict[int, int] = {}
            for req, _, slot in claimed:
                outs[slot] = [firsts[slot]]
                need[slot] = max(req.max_new, 1)
                done_at[slot] = now
                req.t_first = now
                if need[slot] > 1:
                    active[slot] = firsts[slot]
            while active:
                nxt = ep.decode_all(active)
                now = time.perf_counter()
                for s, tok in nxt.items():
                    outs[s].append(tok)
                    if len(outs[s]) >= need[s]:
                        del active[s]
                        done_at[s] = now
                    else:
                        active[s] = tok
        # lint: ignore[swallowed-exception] -- cleanup-and-reraise: decode
        # slots must be released on ANY mid-stream failure or they leak
        except Exception:
            for _, _, s in claimed:
                ep.release(s)
            raise

        results: List[Tuple[np.ndarray, float]] = []
        for i, (req, t_submit, slot) in enumerate(claimed):
            lat = done_at[slot] - t_submit + self.cfg.extra_latency_s
            if record is None or record[i]:
                self.metrics.record_latency(fn_name, lat)
            req.output = np.asarray(outs[slot], np.int32)
            req.tier = self.name
            req.t_done = done_at[slot]
            req.latency_s = lat
            ep.release(slot)
            results.append((req.output, lat))
        return results

    def serve_one(self, fn_name: str, req: Request,
                  now_s: float = 0.0) -> Tuple[np.ndarray, float]:
        """Serial single-request path (the pre-batching baseline)."""
        del now_s
        [(out, lat)] = self.serve_batch(fn_name, [(req, time.perf_counter())])
        return out, lat


class EdgeCloudContinuum:
    """The full platform: replication + policy-driven offloading across an
    N-tier topology, with per-tier gateways and a continuous-batching
    scheduler (``scheduler="wave"`` keeps the legacy wave drain)."""

    def __init__(self, edge=None, cloud=None,
                 policy: PolicySpec = "auto",
                 offload_cfg: Optional[offload.OffloadConfig] = None,
                 window: int = 64, seed: int = 0,
                 control_interval_s: float = 1.0,
                 max_waves_per_tick: Optional[int] = None,
                 topology: Optional[Topology] = None,
                 reject_latency_s: float = 0.005,
                 scheduler: str = "continuous",
                 max_steps_per_tick: Optional[int] = None,
                 req_bytes: Optional[float] = None,
                 trace: Optional[Trace] = None,
                 faults: Optional[FaultSchedule] = None,
                 trace_vocab: int = 128,
                 trace_prompts: str = "random",
                 eq1: str = "window",
                 sketch=None):
        if trace_prompts not in ("random", "per_fn"):
            raise ValueError(
                f"trace_prompts must be 'random' or 'per_fn', "
                f"got {trace_prompts!r}")
        if scheduler not in ("continuous", "wave"):
            raise ValueError(
                f"scheduler must be 'continuous' or 'wave', got {scheduler!r}")
        if topology is None:
            if edge is None or cloud is None:
                raise ValueError(
                    "pass either topology=... or the 2-tier edge=/cloud= pair")
            topology = Topology.pair(edge, cloud)
        self.topology = topology
        self.tiers: List[Tier] = [Tier(spec.name, spec)
                                  for spec in topology.tiers]
        self.gateways: List[Gateway] = [
            Gateway(None if spec.queue_depth_per_slot is None
                    else spec.slots * spec.queue_depth_per_slot)
            for spec in topology.tiers]
        self.offload_cfg = offload_cfg or offload.OffloadConfig()
        self._policy_spec: PolicySpec = policy
        # Average request payload hint for net-aware caps.  The simulator
        # derives this from its workload profile; the live runtime takes
        # it as a constructor hint so an auto+net deployment can divide
        # its links by the real payload (and sim-live R_t parity holds).
        self.req_bytes = req_bytes
        self.policy = Policy.parse(policy, offload_cfg=self.offload_cfg,
                                   req_bytes=req_bytes)
        if scheduler == "wave" and self.policy.migrate_threshold is not None:
            # the wave scheduler runs every admitted request to
            # completion — there is no slot-resident state to migrate
            warnings.warn(
                "mid-stream migration (migrate_threshold="
                f"{self.policy.migrate_threshold}) requires the "
                "continuous scheduler; scheduler='wave' will never "
                "migrate", stacklevel=2)
        self.window = window
        self.control_interval_s = control_interval_s
        # Eq-(1) front end for the control loop: "window" (exact sorted
        # percentiles, the golden-pinned default) or "sketch" (streaming
        # histogram quantiles drained from the tier registries each
        # scrape — the sub-millisecond 10k-function path).
        self.eq1 = eq1
        self.sketch = sketch
        # Fast rejections are part of the latency distribution Eq (1)
        # scrapes (queue-proxy 503 semantics, same as the simulator).
        self.reject_latency_s = reject_latency_s
        # One reconciler per shallower tier: each edge cluster mirrors the
        # cloud specs independently, so a crashed tier's view can be wiped
        # and rebuilt (scale-from-zero re-registration) without touching
        # its siblings.  ``replicator`` keeps the historical single-edge
        # attribute as a view of the first one.
        self.replicators: List[ReplicationController] = [
            ReplicationController()
            for _ in range(max(len(self.tiers) - 1, 1))]
        self.cloud_specs: Dict[str, FunctionSpec] = {}
        self._artifacts: Dict[str, Tuple[ModelConfig, object]] = {}
        self.fn_names: List[str] = []
        self._fn_ids: Dict[str, int] = {}
        self.control: Optional[ControlLoop] = None
        self.key = jax.random.PRNGKey(seed)
        # Demand per boundary since the last scrape: boundary b counts the
        # requests that *reached* tier b (submit, routing, or spill) —
        # what its net-aware cap divides the link capacity by.
        self._num_boundaries = max(len(self.tiers) - 1, 1)
        # One (F,) count vector per boundary, indexed by function id —
        # the controller scrape hands these straight to the batched
        # ControlLoop without any per-function Python.
        self._crossings: List[np.ndarray] = [
            np.zeros(0, np.int64) for _ in range(self._num_boundaries)]
        # Platform-level counters (hedging outcomes etc.).
        self.metrics = MetricsRegistry([])
        # Mid-stream migrations currently in flight over a link, and the
        # cumulative per-link egress bytes (every crossing: routing,
        # spill, hedge twins, migrated cache state) — the live
        # counterpart of the simulator's net_links_MBps series.
        self.migrations: List[_Transit] = []
        self.link_bytes: List[float] = [0.0] * len(topology.links)
        self._link_bytes_seen: List[float] = [0.0] * len(topology.links)
        # None = drain every gateway every tick; an int caps the admission
        # rounds per tick, so overload leaves per-tier *backlogs* whose
        # in-flight ages the next scrape mixes into Eq (1) (the
        # simulator's onset signal, now per boundary).
        self.max_waves_per_tick = max_waves_per_tick
        # "continuous" (default): persistent in-flight slots, one shared
        # decode step per scheduler step, retire-and-admit mid-stream.
        # "wave": the legacy run-to-completion wave drain (kept as the
        # before/after baseline for benchmarks/serving_bench.py).
        self.scheduler = scheduler
        # Continuous scheduler only: cap the decode steps one tick may run,
        # letting long requests stay slot-resident ACROSS ticks (new
        # arrivals are admitted into freed slots next tick, mid-request).
        # None = run each tick until all admitted work retires.
        self.max_steps_per_tick = max_steps_per_tick
        self.log: List[Dict] = []
        self._clock = 0.0          # logical control-plane time (scrapes)
        self._tick_no = 0
        self._rejected_seen = 0    # for per-tick deltas in tick() records
        # Fault overlay (repro.workloads.faults): links are crossed
        # through their mutable LinkState (identity multipliers while
        # healthy) and crashed tiers forward traffic but cannot serve.
        # The schedule is applied against the logical clock at the top of
        # each tick; apply_fault() is also public so tests can drive the
        # live runtime and the simulator through identical fault events.
        self.link_state: List[LinkState] = [LinkState(l)
                                            for l in topology.links]
        self.tier_up: List[bool] = [True] * len(self.tiers)
        self.faults = faults
        if faults is not None:
            faults.validate(len(self.tiers))
            faults.reset()
        # Trace-driven arrivals (repro.workloads.trace): rows are
        # submitted at the top of the tick covering their arrival time,
        # with prompt tokens drawn from a dedicated deterministic RNG.
        self.trace = trace
        self.trace_vocab = trace_vocab
        # "random": every arrival draws fresh prompt tokens (the
        # historical behavior).  "per_fn": a function's prompt is a
        # deterministic function of (fn, prompt_len) — invocations of the
        # same function share their prompt, modeling the shared
        # system/function prompt that makes prefix caching pay.
        self.trace_prompts = trace_prompts
        self.trace_requests: List[Request] = []
        self._trace_pos = 0
        self._trace_rng = np.random.default_rng(seed)

    # Ingress / deepest tier aliases (the historical two-tier attributes).
    @property
    def edge(self) -> Tier:
        return self.tiers[0]

    @property
    def replicator(self) -> ReplicationController:
        """The ingress tier's reconciler (historical single-edge view)."""
        return self.replicators[0]

    @property
    def cloud(self) -> Tier:
        return self.tiers[-1]

    @property
    def queue(self) -> Deque[_Queued]:
        """The ingress gateway's queue (historical attribute)."""
        return self.gateways[0].items

    @property
    def queued(self) -> int:
        """Total backlog across every tier's gateway."""
        return sum(len(g) for g in self.gateways)

    @property
    def in_flight(self) -> int:
        """Slot-resident requests across every tier plus migrated state
        still in flight over a link (continuous scheduler; nonzero
        between ticks only under ``max_steps_per_tick`` or while a
        cross-tick migration is landing)."""
        return (sum(t.inflight_count(fn)
                    for t in self.tiers for fn in t.endpoints)
                + len(self.migrations))

    @property
    def migrations_open(self) -> int:
        """Mid-stream migrations fired but not yet landed/aborted."""
        return len(self.migrations)

    @property
    def hedges_open(self) -> int:
        """Hedge pairs still racing (fired but neither won nor cancelled)."""
        c = self.metrics.counter
        return int(c("hedges_fired") - c("hedges_won")
                   - c("hedges_cancelled"))

    # -- deployment (paper §3.3.1) ------------------------------------------
    def deploy(self, spec: FunctionSpec, model_cfg: ModelConfig, params) -> None:
        """Deploy to the deepest tier; replication mirrors the spec to
        every shallower tier of the chain."""
        self.cloud.deploy(spec.name, model_cfg, params, spec.autoscaling)
        self.cloud_specs[spec.name] = spec
        self._artifacts[spec.name] = (model_cfg, params)
        for i, tier in enumerate(self.tiers[:-1]):
            changed = self.replicators[i].reconcile(self.cloud_specs)
            if changed.get(spec.name, True):
                tier.deploy(spec.name, model_cfg, params, spec.autoscaling)
        if spec.name not in self.fn_names:
            self._fn_ids[spec.name] = len(self.fn_names)
            self.fn_names.append(spec.name)
            self._crossings = [np.concatenate([c, np.zeros(1, np.int64)])
                               for c in self._crossings]
            # Each boundary parses the policy against ITS link's capacity,
            # so auto+net caps offload by the link actually being crossed
            # (mirrors the simulator's per-boundary policies).
            links = self.topology.links
            boundary_policies = [
                Policy.parse(self._policy_spec, offload_cfg=self.offload_cfg,
                             link_bytes_per_s=(
                                 links[min(b, len(links) - 1)].bandwidth_Bps
                                 if links else None),
                             req_bytes=self.req_bytes)
                for b in range(self._num_boundaries)]
            self.control = ControlLoop(
                self.policy, len(self.fn_names), window=self.window,
                control_interval_s=self.control_interval_s,
                num_tiers=len(self.tiers),
                boundary_policies=boundary_policies,
                eq1=self.eq1, sketch=self.sketch)

    # -- request path (paper §3.3.2) ------------------------------------------
    def submit(self, fn_name: str, req: Request) -> bool:
        """Queue a request at the ingress gateway.  Returns False when the
        bounded backlog is full (the live 503 — a fast rejection whose
        latency feeds Eq (1)'s bimodality, as in the simulator)."""
        req.arrival_s = time.perf_counter()
        item = _Queued(fn_name, req, req.arrival_s, tick_no=self._tick_no)
        # Every arrival is ingress demand, admitted or not — the simulator
        # counts a 503'd arrival into arrivals_in_interval the same way.
        self._count_crossing(0, fn_name)
        if not self.gateways[0].push(item):
            req.failed = True
            self._reject(0, fn_name)
            return False
        return True

    def _count_crossing(self, b: int, fn: str) -> None:
        if b < self._num_boundaries:
            i = self._fn_ids.get(fn)
            if i is not None:
                self._crossings[b][i] += 1

    def _reject(self, ti: int, fn: str) -> None:
        self.metrics.inc("rejected")
        if ti < len(self.tiers) - 1 or len(self.tiers) == 1:
            self.tiers[ti].metrics.record_latency(fn, self.reject_latency_s)

    def _cross_link(self, item: _Queued, l: int) -> None:
        """Move one queued request over link l (tier l -> tier l+1):
        charge RTT + payload serialization to its latency clock (by
        backdating the submit stamp, so both the measured latency and the
        backlog age include time in flight, as in the simulator) and count
        the boundary crossing for per-boundary demand."""
        if l < len(self.topology.links):
            item.t_submit -= self.link_state[l].latency_s(
                item.req.tokens.nbytes)
            self.link_bytes[l] += item.req.tokens.nbytes
        if not item.hedge:
            self._count_crossing(l + 1, item.fn)

    # -- fault injection (repro.workloads.faults) -----------------------------

    def _route_target(self, j: int) -> Optional[int]:
        """Resolve an assigned tier against the fault state: crashed
        tiers forward but cannot serve, a partitioned link cuts off
        everything past it.  Prefer the shallowest serviceable tier at or
        past the assignment, else the deepest one before it; None when
        nothing can serve (the request 503s)."""
        if self.faults is None and all(self.tier_up):
            return j
        reach = 0
        for l in range(len(self.tiers) - 1):
            if not self.link_state[l].up:
                break
            reach = l + 1
        up = [i for i in range(reach + 1) if self.tier_up[i]]
        if not up:
            return None
        for i in up:
            if i >= j:
                return i
        return up[-1]

    def apply_fault(self, ev: FaultEvent) -> None:
        """Apply one fault event NOW (also driven by the ``faults=``
        schedule at the top of each tick).  Public so tests can push the
        simulator and the live runtime through identical fault scripts."""
        self.metrics.inc("faults_applied")
        if ev.kind in LINK_KINDS:
            ls = self.link_state[ev.target]
            ls.apply(ev)
            # a net-aware boundary re-caps against the changed link
            if self.control is not None:
                pol = self.control.policies[
                    min(ev.target, len(self.control.policies) - 1)]
                if isinstance(pol, AutoOffload):
                    pol.set_link_capacity(ls.effective_capacity())
        elif ev.kind == "crash_tier":
            self._crash_tier(ev.target)
        else:
            self._restore_tier(ev.target)

    def _replay(self, item: _Queued, away_from: int) -> None:
        """Re-route one request lost to a crash/partition: back into a
        reachable serviceable gateway (original submit stamp — the lost
        work stays on its latency clock), or failed when nothing can
        serve.  Nothing is ever silently dropped."""
        self.metrics.inc("replayed")
        tgt = self._route_target(away_from)
        if tgt is None or not self.gateways[tgt].push(item, force=True):
            item.req.failed = True
            self._reject(0, item.fn)

    def _crash_tier(self, i: int) -> None:
        """Tier ``i`` goes down: slots, in-flight rows, backlog, and the
        tier's replicated specs are lost.  Every resident primary replays
        at a reachable tier; hedge arms resolve so the conservation and
        hedge identities hold (a lost twin concedes to its primary, a
        primary whose twin already won adopts the twin's result)."""
        tier = self.tiers[i]
        self.tier_up[i] = False
        lost: List[_Queued] = self.gateways[i].pop_all()
        for fn, fl in tier.inflight.items():
            for rec in fl.values():
                item = rec.item
                pair = item.pair
                if item.hedge:
                    # a lost twin concedes: the primary serves normally
                    if pair.winner is None:
                        pair.winner = "primary"
                        self.metrics.inc("hedges_cancelled")
                    continue
                if pair is not None and pair.winner == "twin":
                    self._adopt(item, pair)      # already served by twin
                    continue
                lost.append(item)
        # the crashed pool is gone: endpoints, autoscalers, in-flight
        # rows, and (for a shallower tier) the replicated edge view —
        # restore rebuilds all of it through the reconciler
        tier.endpoints = {}
        tier.autoscalers = {}
        tier.inflight = {}
        if i < len(self.tiers) - 1:
            self.replicators[i] = ReplicationController()
        for item in lost:
            self._replay(item, i)

    def _restore_tier(self, i: int) -> None:
        """Tier ``i`` comes back empty.  A shallower tier re-registers
        its functions through the replication path — fresh reconciler,
        every spec reports changed, redeploy from the stored artifacts —
        and the fresh autoscalers start at ``min_scale`` (scale-from-zero
        when the policy allows it).  The deepest tier redeploys directly
        (it *is* the spec source)."""
        self.tier_up[i] = True
        if i < len(self.tiers) - 1:
            changed = self.replicators[i].reconcile(self.cloud_specs)
        else:
            changed = {name: True for name in self.cloud_specs}
        for name, spec in self.cloud_specs.items():
            if changed.get(name, True):
                model_cfg, params = self._artifacts[name]
                self.tiers[i].deploy(name, model_cfg, params,
                                     spec.autoscaling)

    # -- trace-driven arrivals (repro.workloads.trace) ------------------------

    def _ingest_trace(self) -> int:
        """Submit every trace row arriving within the interval this tick
        covers.  Rows name functions by the trace's ``fn_names``; names
        not deployed here fall back to deployment order by index."""
        if self.trace is None:
            return 0
        horizon = self._clock + self.control_interval_s
        n = 0
        while (self._trace_pos < len(self.trace)
               and float(self.trace.t[self._trace_pos]) < horizon):
            i = self._trace_pos
            self._trace_pos += 1
            name = self.trace.fn_names[int(self.trace.fn[i])]
            if name not in self._fn_ids:
                if not self.fn_names:
                    raise RuntimeError(
                        "trace ingestion before any function is deployed")
                name = self.fn_names[int(self.trace.fn[i])
                                     % len(self.fn_names)]
            L = max(int(self.trace.prompt_len[i]), 1)
            if self.trace_prompts == "per_fn":
                fn_rng = np.random.default_rng(
                    zlib.crc32(f"{name}:{L}".encode()))
                tokens = fn_rng.integers(0, self.trace_vocab,
                                         L).astype(np.int32)
            else:
                tokens = self._trace_rng.integers(
                    0, self.trace_vocab, L).astype(np.int32)
            req = Request(
                rid=len(self.trace_requests),
                tokens=tokens,
                max_new=max(int(self.trace.max_new[i]), 1))
            self.trace_requests.append(req)
            self.submit(name, req)
            n += 1
        return n

    def controller_update(self) -> np.ndarray:
        """One scrape-and-update cycle through the shared ControlLoop:
        every boundary b sees tier b's latency windows, tier b's own
        gateway backlog ages, and the demand that crossed into tier b
        since the last scrape; returns the ingress boundary's R_t
        percentages."""
        with span("repro.control"):
            now = time.perf_counter()
            qages = []
            for b in range(self.control.num_boundaries):
                tier_i = min(b, len(self.tiers) - 1)   # 1-tier chain: b=0
                qages.append(self.gateways[tier_i].backlog_ages(
                    now, self._tick_no, self._fn_ids, len(self.fn_names)))
            arrivals = list(self._crossings)
            if self.control.eq1 == "sketch":
                # Streaming scrape: only the samples recorded since the
                # last tick leave each tier's registry (no windows, no
                # sort).
                samples = [
                    self.tiers[min(b, len(self.tiers) - 1)]
                    .metrics.drain_fresh()
                    for b in range(self.control.num_boundaries)]
                R_all = self.control.step_stream(samples, queue_ages=qages,
                                                 arrivals=arrivals)
            else:
                lats, valids = [], []
                for b in range(self.control.num_boundaries):
                    tier_i = min(b, len(self.tiers) - 1)
                    lat, valid = self.tiers[tier_i].metrics.latency_windows(
                        self.window)
                    lats.append(lat)
                    valids.append(valid)
                R_all = self.control.step_tiers(lats, valids,
                                                queue_ages=qages,
                                                arrivals=arrivals)
            self._crossings = [np.zeros_like(c) for c in self._crossings]
            return R_all[0]

    def _latency_windows(self):
        """(F, W) ingress-tier latency windows in deployment order."""
        return self.edge.metrics.latency_windows(self.window)

    # -- scheduler ------------------------------------------------------------
    def tick(self) -> Dict[str, float]:
        """One scheduler round: controller update, tier assignment of the
        ingress batch, then the per-tier serving loop.

        ``scheduler="continuous"`` (default) runs the continuous-batching
        decode loop — each scheduler step decodes every in-flight slot
        once, retires finished rows immediately (cancelling their hedge
        siblings), and admits queued requests into the freed slots the
        same step.  ``scheduler="wave"`` keeps the legacy
        run-to-completion wave drain as the before/after baseline."""
        # Chaos first: fault events due on the logical clock reshape the
        # continuum before anything routes, then trace rows arriving in
        # this tick's interval enter the ingress gateway (their demand is
        # part of this very scrape).
        if self.faults is not None:
            for ev in self.faults.due(self._clock):
                self.apply_fault(ev)
        self._ingest_trace()
        R = self.controller_update()
        self._clock += self.control_interval_s
        self._tick_no += 1
        # Mid-stream migration: boundaries whose R_t crossed their
        # policy's threshold ship slot-resident victims down-chain NOW —
        # freed slots are admissible this very tick, the state lands
        # when its link transfer completes (possibly ticks later).
        mig_fired = self._fire_migrations()
        hedged = 0
        pairs: List[_HedgePair] = []
        twins: List[Tuple[int, _Queued]] = []

        # Route the ingress gateway's queue (fresh arrivals + ingress
        # backlog) over the tiers; each assigned request crosses the links
        # down to its tier's gateway.  Deeper gateways' backlogs are NOT
        # re-routed: like the simulator's per-tier queues, they belong to
        # their tier until served or spilled.
        items = self.gateways[0].pop_all()
        if items:
            with span("repro.route", n=len(items)):
                hedged = self._route(items, pairs, twins)
        if hedged:
            self.metrics.inc("hedges_fired", hedged)

        # This tick's work: every tier's gateway contents + hedge twins.
        pending: Dict[Tuple[int, str], List[_Queued]] = {}
        for ti, gw in enumerate(self.gateways):
            for it in gw.pop_all():
                pending.setdefault((ti, it.fn), []).append(it)
        for bj, it in twins:
            pending.setdefault((bj, it.fn), []).append(it)

        with span("repro.scrape"):
            self._scrape(pending)

        if self.scheduler == "wave":
            body = self._run_waves(pending, pairs)
        else:
            body = self._run_continuous(pending)

        # Per-tick rejection count, like every sibling field (submit-time
        # rejections since the last tick land in this tick's record).
        rejected_total = sum(g.rejected for g in self.gateways)
        rejected_tick = rejected_total - self._rejected_seen
        self._rejected_seen = rejected_total
        served = body.pop("served")
        # Per-tick link egress (MB), like every sibling field — routing,
        # spill, twins, and migrated cache state all count.
        link_MB = [(b - s) / 1e6 for b, s in
                   zip(self.link_bytes, self._link_bytes_seen)]
        self._link_bytes_seen = list(self.link_bytes)
        rec = {"R": float(R.mean()) if len(R) else 0.0,
               "edge": served[self.tiers[0].name],
               "cloud": served[self.tiers[-1].name],
               "tiers": dict(served),
               "hedged": hedged,
               "migrations_fired": mig_fired,
               **body,
               "link_MB": link_MB,
               "backlog": {t.name: len(g)
                           for t, g in zip(self.tiers, self.gateways)},
               "rejected": rejected_tick,
               "replicas": {t.name: {fn: t.replicas(fn)
                                     for fn in t.autoscalers}
                            for t in self.tiers}}
        self.log.append(rec)
        return rec

    def _route(self, items: List[_Queued], pairs: List[_HedgePair],
               twins: List[Tuple[int, _Queued]]) -> int:
        """Assign the ingress batch over the tiers: each request crosses
        the links down to its tier's gateway, a hedged one's twin goes to
        its backup tier (``pairs``, ``twins``).  Returns the hedges
        fired."""
        last = len(self.tiers) - 1
        hedged = 0
        fn_ids = np.asarray([self._fn_ids[it.fn] for it in items], np.int32)
        self.key, sub = jax.random.split(self.key)
        tier_idx = self.control.route_tiers(sub, fn_ids)
        now = time.perf_counter()
        ages = np.asarray([now - it.t_submit for it in items], np.float32)
        lat, valid = self._latency_windows()
        self.key, hk = jax.random.split(self.key)
        hedge = self.control.hedge(hk, ages, fn_ids, lat, valid)
        for it, tj, hedge_it in zip(items, tier_idx, hedge):
            j = self._route_target(int(tj))
            if j is None:
                # no serviceable tier is reachable: the live 503
                it.req.failed = True
                self._reject(0, it.fn)
                continue
            if bool(hedge_it) and it.pair is None:
                # backup request on another tier (straggler hedge);
                # only the winning arm's latency feeds the windows.
                # An already-paired leftover is never re-hedged.
                # The twin is stamped before the primary crosses any
                # link, so it does not inherit the primary's hop cost.
                bj = self._route_target(0 if j == last else last)
                if bj is not None:
                    twin = Request(rid=it.req.rid, tokens=it.req.tokens,
                                   max_new=it.req.max_new,
                                   arrival_s=it.req.arrival_s)
                    pair = _HedgePair(fn=it.fn)
                    it.pair = pair
                    twin_item = _Queued(it.fn, twin, it.t_submit,
                                        tick_no=self._tick_no,
                                        hedge=True, pair=pair)
                    # the twin travels from the ingress gateway to its
                    # backup tier, paying the same links a routed
                    # request would (no crossing counters: it is
                    # duplicate work, not demand) — else the
                    # twin-vs-primary win comparison is biased toward
                    # the free-riding twin
                    for l in range(bj):
                        self._cross_link(twin_item, l)
                    twins.append((bj, twin_item))
                    pairs.append(pair)
                    hedged += 1
            for l in range(j):
                self._cross_link(it, l)
            self.gateways[j].push(it, force=True)
        return hedged

    def _scrape(self, pending: Dict[Tuple[int, str], List[_Queued]]
                ) -> None:
        """KPA scrape: every (tier, fn) observes its assigned concurrency
        — queued plus already slot-resident, including zeros (that is
        what ages idle functions to zero)."""
        for ti, tier in enumerate(self.tiers):
            for fn, asc in tier.autoscalers.items():
                ep = tier.endpoints.get(fn)
                if ep is not None and ep.paged:
                    # Paged pools meter demand in pages (memory actually
                    # reserved), normalized to full-row equivalents so the
                    # target-concurrency units match the dense scrape: a
                    # half-row request is half a unit of demand.
                    ppr = ep.pages_per_row
                    pages = sum(
                        ep.page_need(len(it.req.tokens),
                                     max(it.req.max_new, 1))
                        for it in pending.get((ti, fn), []))
                    pages += ep.resident_page_demand()
                    pages += sum(
                        ep.pages_for(max(tr.pos + tr.need - len(tr.toks), 1))
                        for tr in self.migrations
                        if tr.dst == ti and tr.fn == fn)
                    conc = pages / ppr
                else:
                    conc = (len(pending.get((ti, fn), []))
                            + tier.inflight_count(fn)
                            # migrated state headed here is inbound demand
                            # — the destination must not scale to zero
                            # under it
                            + sum(1 for tr in self.migrations
                                  if tr.dst == ti and tr.fn == fn))
                asc.observe(self._clock, float(conc))
                asc.desired(self._clock)

    # -- continuous-batching scheduler (the default) --------------------------

    def _adopt(self, item: _Queued, pair: _HedgePair) -> None:
        """A losing/stranded primary's client still gets the winning
        twin's completed result (served once, by the twin)."""
        item.req.output = pair.winner_req.output
        item.req.tier = pair.winner_req.tier
        item.req.t_admit = pair.winner_req.t_admit
        item.req.t_first = pair.winner_req.t_first
        item.req.t_done = pair.winner_req.t_done
        item.req.latency_s = pair.winner_req.latency_s

    def _evict_loser(self, pair: _HedgePair) -> None:
        """Cancel the losing arm of a just-resolved pair if it is still
        slot-resident: the slot frees this very scheduler step (the next
        admission can claim it), no latency sample is recorded for the
        evicted arm, and a cancelled primary adopts the winner's output."""
        ref = pair.primary_ref if pair.winner == "twin" else pair.twin_ref
        if ref is None:
            return
        ti, rec = ref
        tier = self.tiers[ti]
        if tier.inflight.get(pair.fn, {}).get(rec.slot) is rec:
            tier.cancel(pair.fn, rec.slot)
            if pair.winner == "twin":
                self._adopt(rec.item, pair)

    def _settle_resolved(self, item: _Queued) -> bool:
        """A queued item whose hedge pair already resolved never runs: a
        losing twin is dropped, a primary whose twin won adopts the twin's
        completed result.  Returns True when the item leaves the queue."""
        pair = item.pair
        if pair is None or pair.winner is None:
            return False
        if item.hedge:
            return True
        if pair.winner == "twin":
            self._adopt(item, pair)
            return True
        item.pair = None           # twin lost/abandoned: runs normally
        return False

    # -- mid-stream migration (continuous scheduler only) ----------------------

    def _fire_migrations(self) -> int:
        """Launch mid-stream migrations for every boundary whose policy
        carries a ``migrate_threshold`` that its current R_t reaches.

        Tier b selects ``ceil(eligible * R_t/100)`` victims among its
        slot-resident rows — longest remaining decode first (the most
        slot-hungry work) — cancels them locally via the eviction
        machinery, extracts their KV/state rows, and ships them over
        link b: ``nbytes`` is the live cache bytes at the row's decode
        position plus its token tail, the transfer occupies the
        request's clock until it lands, and the bytes count toward the
        link's egress like any other crossing.  Hedge twins and rows of
        already-resolved pairs never migrate (duplicate work is evicted,
        not shipped).
        """
        if self.control is None or self.scheduler != "continuous":
            return 0
        fired = 0
        now = time.perf_counter()
        for b in range(min(self._num_boundaries, len(self.tiers) - 1)):
            pol = self.control.policies[b]
            thr = pol.migrate_threshold
            if thr is None:
                continue
            if not (self.link_state[b].up and self.tier_up[b + 1]):
                continue       # no migrating into a partition/crash
            tier, dst = self.tiers[b], self.tiers[b + 1]
            link = self.link_state[b]
            for fn, fl in tier.inflight.items():
                if not fl:
                    continue
                R_b = float(self.control.R_all[b][self._fn_ids[fn]])
                if R_b < thr:
                    continue
                ep = tier.endpoints[fn]
                dep = dst.endpoints.get(fn)
                if dep is None or not ep.compatible_with(dep):
                    continue       # rows only transplant onto a twin pool
                eligible = [
                    rec for rec in fl.values()
                    if not rec.item.hedge
                    and (rec.item.pair is None
                         or rec.item.pair.winner is None)
                    and rec.need - len(rec.toks) >= pol.migrate_min_remaining]
                n = min(len(eligible), math.ceil(len(eligible) * R_b / 100.0))
                if n <= 0:
                    continue
                eligible.sort(key=lambda r: (-(r.need - len(r.toks)), r.slot))
                victims = eligible[:n]
                states = ep.extract_rows([r.slot for r in victims])
                for rec, state in zip(victims, states):
                    pos = int(ep.slot_pos[rec.slot])
                    tier.cancel(fn, rec.slot)      # slot frees NOW
                    nbytes = (ep.cache_nbytes_per_row(pos)
                              + 4.0 * (len(rec.item.req.tokens)
                                       + len(rec.toks)))
                    self.link_bytes[b] += nbytes
                    self._count_crossing(b + 1, fn)
                    self.migrations.append(_Transit(
                        item=rec.item, fn=fn, rows=state, pos=pos,
                        toks=rec.toks, need=rec.need, src=b, dst=b + 1,
                        t_land=now + link.latency_s(nbytes),
                        nbytes=nbytes))
                    fired += 1
        if fired:
            self.metrics.inc("migrations_fired", fired)
        return fired

    def _readmit(self, ti: int, tr: _Transit, force: bool = False) -> bool:
        """Insert a landed row state into tier ``ti``'s pool and resume
        its decode (no re-prefill).  Respects the autoscaler-admitted
        budget unless ``force`` (the migration analogue of the
        scale-from-zero floor: a resident request implies >= 1 desired
        replica, so a both-ends-scaled-to-zero deadlock resumes anyway).
        """
        tier = self.tiers[ti]
        ep = tier.endpoints.get(tr.fn)
        if ep is None:             # tier crashed: its pool is gone
            return False
        if not force and min(
                tier.free_slots(tr.fn),
                tier.capacity(tr.fn) - tier.inflight_count(tr.fn)) <= 0:
            return False
        # the landing row must reserve pages for its remaining decode —
        # a page-full destination aborts the migration (in pages, like
        # admission), even under force
        extent = max(tr.pos + max(tr.need - len(tr.toks), 0), 1)
        if ep.paged and ep.admissible_pages < ep.pages_for(extent):
            return False
        slot = ep.try_claim(reserve_tokens=extent if ep.paged else None)
        if slot is None:
            return False
        ep.insert_rows([tr.rows], [slot], [tr.pos])
        rec = _InFlight(tr.item, slot, tr.toks, tr.need)
        tier.inflight[tr.fn][slot] = rec
        if tr.item.pair is not None:
            tr.item.pair.set_ref(tr.item.hedge, ti, rec)
        return True

    def _abort_transit(self, tr: _Transit) -> None:
        """A transit that can never land at its destination: resume at
        the source, or — when the source too is crashed or has no free
        slot — replay the request from scratch at a reachable gateway.
        Counted aborted either way; never lost, never left in transit."""
        self.metrics.inc("migrations_aborted")
        pair = tr.item.pair
        if pair is not None and pair.winner is not None:
            if pair.winner == "twin":
                self._adopt(tr.item, pair)
            return
        if self.tier_up[tr.src] and self._readmit(tr.src, tr, force=True):
            return
        self._replay(tr.item, tr.src)

    def _land_migrations(self) -> Tuple[int, int]:
        """Resolve in-flight migrations whose transfer completed.

        A landing row re-enters decode at the destination; a full
        destination ABORTS the migration and the row resumes at its
        source instead — never lost (both ends full: it stays in
        transit and is retried next scheduler step).  A row whose hedge
        pair resolved against it mid-flight is dropped (its twin already
        served the request) and counts as aborted.  Returns
        ``(completed, aborted)``.
        """
        if not self.migrations:
            return 0, 0
        now = time.perf_counter()
        completed = aborted = 0
        still: List[_Transit] = []
        for tr in self.migrations:
            if (not self.link_state[tr.dst - 1].up
                    or not self.tier_up[tr.dst]):
                # the link partitioned (or the destination crashed) with
                # the transfer in flight: the state never arrives —
                # abort back to the source NOW, not at t_land, so
                # drain() can never spin on an unlandable transit
                self._abort_transit(tr)
                aborted += 1
                continue
            if now < tr.t_land:
                still.append(tr)
                continue
            pair = tr.item.pair
            if pair is not None and pair.winner is not None:
                if pair.winner == "twin":
                    self._adopt(tr.item, pair)
                self.metrics.inc("migrations_aborted")
                aborted += 1
            elif self._readmit(tr.dst, tr):
                self.metrics.inc("migrations_completed")
                completed += 1
            elif self._readmit(tr.src, tr):
                self.metrics.inc("migrations_aborted")
                aborted += 1
            else:
                still.append(tr)
        self.migrations = still
        return completed, aborted

    def _run_continuous(self, pending: Dict[Tuple[int, str], List[_Queued]]
                        ) -> Dict:
        """The continuous-batching decode loop over every tier.

        Each iteration is one scheduler step: (1) one shared ``decode_all``
        step per endpoint with in-flight slots, retiring finished rows
        immediately (a retiring hedge arm wins its pair and evicts its
        slot-resident sibling); (2) one admission pass packing queued
        requests into the freed slots (bucketed prefill), capped at
        ``max_waves_per_tick`` admission rounds.  With
        ``max_steps_per_tick`` set, long requests stay slot-resident
        across ticks; otherwise the tick runs until all admitted work
        retires, preserving the PR-1..3 per-tick window semantics."""
        served: Dict[str, int] = {t.name: 0 for t in self.tiers}
        last = len(self.tiers) - 1
        waves = steps = spilled = 0
        won = cancelled = 0
        mig_completed = mig_aborted = 0

        def adm_capped() -> bool:
            return (self.max_waves_per_tick is not None
                    and waves >= self.max_waves_per_tick)

        def stp_capped() -> bool:
            return (self.max_steps_per_tick is not None
                    and steps >= self.max_steps_per_tick)

        def retire(ti: int, fn: str, rec: _InFlight) -> None:
            """A finished row left its slot: resolve its hedge pair and
            record/serve it (losers record nothing)."""
            nonlocal won, cancelled
            tier = self.tiers[ti]
            item = rec.item
            lat = tier.finish(fn, rec)
            pair = item.pair
            arm = "twin" if item.hedge else "primary"
            if pair is not None and pair.winner is None:
                # first arm home wins; the sibling's slot is evicted NOW
                pair.winner = arm
                pair.winner_req = item.req
                if item.hedge:
                    won += 1
                    self.metrics.inc("hedges_won")
                else:
                    cancelled += 1
                    self.metrics.inc("hedges_cancelled")
                self._evict_loser(pair)
            elif pair is not None and pair.winner != arm:
                return             # losing arm outran its eviction: drop
            tier.metrics.record_latency(fn, lat)
            served[tier.name] += 1

        def retire_all(ti: int, fn: str, recs: List[_InFlight]) -> None:
            if recs:
                with span("repro.retire", n=len(recs)):
                    for rec in recs:
                        retire(ti, fn, rec)

        def admit_batch(ti: int, fn: str, batch: List[_Queued]) -> None:
            in_flight, finished = self.tiers[ti].admit(fn, batch)
            for rec in in_flight:
                if rec.item.pair is not None:
                    rec.item.pair.set_ref(rec.item.hedge, ti, rec)
            retire_all(ti, fn, finished)

        def admit_round() -> bool:
            admitted_any = False
            for (ti, fn), lst in pending.items():
                if not lst:
                    continue
                lst[:] = [it for it in lst if not self._settle_resolved(it)]
                tier = self.tiers[ti]
                budget = tier.admission_budget(
                    fn, lst,
                    cap=tier.capacity(fn) - tier.inflight_count(fn))
                if budget <= 0 or not lst:
                    continue
                batch, pending[(ti, fn)] = lst[:budget], lst[budget:]
                admit_batch(ti, fn, batch)
                admitted_any = True
            return admitted_any

        def await_landing() -> None:
            """Nothing to decode or admit until a transfer lands: wait
            out the earliest link arrival (sub-tick landings; a
            step-capped tick instead breaks out of the loop and the
            landing happens a later tick — the cross-tick case)."""
            nonlocal mig_completed, mig_aborted
            wait = (min(tr.t_land for tr in self.migrations)
                    - time.perf_counter())
            if wait > 0:
                time.sleep(wait)
            c, a = self._land_migrations()
            mig_completed += c
            mig_aborted += a
            if not (c or a):
                # Landing blocked on capacity at BOTH ends (e.g. scaled
                # to zero): resume anyway — the migration analogue of
                # the scale-from-zero floor.  Only a transit whose link
                # transfer has actually completed may be force-landed;
                # one exists, since we just slept to the earliest t_land.
                now = time.perf_counter()
                idx = next(i for i, tr in enumerate(self.migrations)
                           if tr.t_land <= now)
                tr = self.migrations.pop(idx)
                if self._readmit(tr.dst, tr, force=True):
                    self.metrics.inc("migrations_completed")
                    mig_completed += 1
                elif self._readmit(tr.src, tr, force=True):
                    self.metrics.inc("migrations_aborted")
                    mig_aborted += 1
                else:
                    raise RuntimeError(
                        "scheduler wedged: migrated state cannot "
                        "land on any tier")

        for i in itertools.count():
            with span("repro.step", i=i):
                # (0) land migrated state whose link transfer completed:
                # the rows re-enter the destination's decode stream
                c, a = self._land_migrations()
                mig_completed += c
                mig_aborted += a
                # (1) one decode step across every endpoint with work
                stepped = False
                for ti, tier in enumerate(self.tiers):
                    for fn in tier.endpoints:
                        if tier.inflight_count(fn) == 0:
                            continue
                        stepped = True
                        retire_all(ti, fn, tier.step(fn))
                if stepped:
                    steps += 1
                # (2) admit into freed slots, same step — also under a
                # step cap, so paced ticks keep admitting fresh arrivals
                # into free slots alongside the slot-resident work
                admitted = False
                if not adm_capped():
                    admitted = admit_round()
                    if admitted:
                        waves += 1
                if stepped and stp_capped():
                    break          # in-flight work carries over to next tick
                if self.in_flight == 0:
                    if not any(pending.values()):
                        break
                    if adm_capped():
                        break          # leftovers requeue below
                if stepped or admitted:
                    continue
                if not any(pending.values()):
                    if not self.migrations:
                        break          # only resolved-pair items were swept
                    await_landing()    # idle until the next transfer arrives
                    continue
                # Stalled: nothing decoding, nothing admissible.
                progress = False
                if self.topology.waterfall:
                    # Waterfall: a tier with no admitted capacity (e.g.
                    # scaled to zero with scale-up disabled) spills its
                    # pending load over the link to the next tier's queue.
                    for (ti, fn), lst in list(pending.items()):
                        tier = self.tiers[ti]
                        if (lst and ti < last
                                and self.link_state[ti].up
                                and self.tier_up[ti + 1]
                                and tier.admission_budget(
                                    fn, lst[:1],
                                    cap=tier.capacity(fn)
                                    - tier.inflight_count(fn)) <= 0):
                            for it in lst:
                                self._cross_link(it, ti)
                            pending.setdefault((ti + 1, fn), []).extend(lst)
                            pending[(ti, fn)] = []
                            spilled += len(lst)
                            progress = True
                if progress:
                    continue
                # Scale-from-zero floor: a queued request implies >= 1
                # desired replica next scrape; don't deadlock on degenerate
                # autoscaling bounds in the meantime.
                for (ti, fn), lst in pending.items():
                    if lst and self.tiers[ti].admission_budget(
                            fn, lst[:1]) > 0:
                        admit_batch(ti, fn, [lst.pop(0)])
                        waves += 1
                        progress = True
                        break
                if not progress:
                    if self.migrations:
                        await_landing()    # a landing frees slots/capacity
                        continue
                    raise RuntimeError("scheduler wedged: pending work but "
                                       "no free slot on any tier")

        with span("repro.requeue", n=sum(map(len, pending.values()))):
            # Tick over: unserved hedge twins are abandoned — the pair
            # resolves to the primary, which records normally when it
            # completes.
            for lst in pending.values():
                for it in lst:
                    if it.hedge and it.pair.winner is None:
                        it.pair.winner = "primary"
                        cancelled += 1
                        self.metrics.inc("hedges_cancelled")
            # Unserved primaries whose twin already won adopt the twin's
            # result; the rest go back to *their tier's* gateway, keeping
            # their original submit time and tick stamp so the backlog age
            # the next scrape reads stays monotone.  A primary whose twin
            # is still slot-resident (steps capped) keeps its pair link —
            # the race settles next tick.
            adopted = 0
            requeue: Dict[int, List[_Queued]] = {}
            for (ti, fn), lst in pending.items():
                for it in lst:
                    if it.hedge:
                        continue
                    pair = it.pair
                    if pair is not None and pair.winner == "twin":
                        self._adopt(it, pair)
                        adopted += 1
                        continue
                    if pair is not None and pair.winner == "primary":
                        it.pair = None
                    requeue.setdefault(ti, []).append(it)
            for ti, lst in requeue.items():
                for it in sorted(lst, key=lambda it: it.t_submit):
                    if not self.gateways[ti].push(it):
                        # the tier's bounded backlog is full: the request
                        # is dropped for good (queue-proxy 503), says so
                        it.req.failed = True
                        self._reject(ti, it.fn)
                        if it.pair is not None and it.pair.winner is None:
                            # a dropped primary can never adopt: abandon
                            # the race and evict its still-running twin
                            it.pair.winner = "primary"
                            cancelled += 1
                            self.metrics.inc("hedges_cancelled")
                            self._evict_loser(it.pair)
        return {"served": served, "hedges_won": won,
                "hedges_cancelled": cancelled, "spilled": spilled,
                "waves": waves, "steps": steps,
                "migrated": mig_completed,
                "migrations_aborted": mig_aborted,
                "inflight": self.in_flight}

    # -- legacy run-to-completion wave scheduler -------------------------------

    def _run_waves(self, pending: Dict[Tuple[int, str], List[_Queued]],
                   pairs: List[_HedgePair]) -> Dict:
        """Drain every tier's gateway in autoscaler-budgeted waves, each
        run to completion (the pre-async baseline kept for
        ``bench_continuous_vs_wave``)."""
        served: Dict[str, int] = {t.name: 0 for t in self.tiers}
        last = len(self.tiers) - 1
        waves = spilled = 0

        def dispatch(ti: int, fn: str, batch: List[_Queued]) -> None:
            nonlocal waves
            tier = self.tiers[ti]
            record = [it.pair is None for it in batch]
            results = tier.serve_batch(
                fn, [(it.req, it.t_submit) for it in batch], record=record)
            waves += 1
            for it, (_, lat) in zip(batch, results):
                if it.pair is not None:
                    it.pair.note(it, tier, lat)
                if not it.hedge:
                    served[tier.name] += 1

        def capped() -> bool:
            return (self.max_waves_per_tick is not None
                    and waves >= self.max_waves_per_tick)

        # Drain in waves: each wave packs up to the autoscaler-admitted
        # concurrency into one batched serve (shared prefill + decode_all).
        while any(pending.values()) and not capped():
            progress = False
            for (ti, fn), lst in pending.items():
                if not lst or capped():
                    continue
                tier = self.tiers[ti]
                budget = tier.admission_budget(fn, lst,
                                               cap=tier.capacity(fn))
                if budget <= 0:
                    continue
                batch, pending[(ti, fn)] = lst[:budget], lst[budget:]
                dispatch(ti, fn, batch)
                progress = True
            if not progress and self.topology.waterfall:
                # Waterfall: a tier with no admitted capacity (e.g. scaled
                # to zero with scale-up disabled) spills its pending load
                # over the link to the next tier's work queue.
                for (ti, fn), lst in list(pending.items()):
                    tier = self.tiers[ti]
                    if (lst and ti < last
                            and self.link_state[ti].up
                            and self.tier_up[ti + 1]
                            and tier.admission_budget(
                                fn, lst[:1], cap=tier.capacity(fn)) <= 0):
                        for it in lst:
                            self._cross_link(it, ti)
                        pending.setdefault((ti + 1, fn), []).extend(lst)
                        pending[(ti, fn)] = []
                        spilled += len(lst)
                        progress = True
            if not progress:
                # Scale-from-zero floor: a queued request implies >= 1
                # desired replica next scrape; don't deadlock on degenerate
                # autoscaling bounds in the meantime.
                for (ti, fn), lst in pending.items():
                    if lst and self.tiers[ti].admission_budget(
                            fn, lst[:1]) > 0:
                        dispatch(ti, fn, [lst.pop(0)])
                        progress = True
                        break
                if not progress:
                    raise RuntimeError("scheduler wedged: pending work but "
                                       "no free slot on any tier")

        # Wave budget exhausted: unserved primaries whose hedge twin
        # already completed adopt the twin's result (served once, by the
        # twin — never requeued and served a second time); the rest go
        # back to *their tier's* gateway, keeping their submit time and
        # tick stamp so the next scrape sees their queue age at the
        # boundary they actually wait at.  Unserved hedge twins are
        # dropped.
        adopted = 0
        requeue: Dict[int, List[_Queued]] = {}
        for (ti, fn), lst in pending.items():
            for it in lst:
                if it.hedge:
                    continue
                pair = it.pair
                if pair is not None and pair.twin_lat is not None:
                    pair.winner = "twin"
                    pair.winner_req = pair.twin_req
                    self._adopt(it, pair)
                    pair.twin_tier.metrics.record_latency(it.fn,
                                                          pair.twin_lat)
                    served[pair.twin_tier.name] += 1
                    adopted += 1
                    continue
                if pair is not None:
                    # the unserved twin is dropped with its primary
                    # requeued: the hedge is over (counted cancelled)
                    pair.winner = "primary"
                it.pair = None       # a requeued primary records normally
                requeue.setdefault(ti, []).append(it)
        for ti, lst in requeue.items():
            for it in sorted(lst, key=lambda it: it.t_submit):
                if not self.gateways[ti].push(it):
                    # the tier's bounded backlog is full: the request is
                    # dropped for good (queue-proxy 503) and says so
                    it.req.failed = True
                    self._reject(ti, it.fn)

        # Resolve hedge pairs: only the winning arm's latency feeds the
        # controller windows, so a slow loser cannot bias R_t.  Both arms
        # ran to completion here (no mid-flight cancellation in wave mode);
        # ``winner`` is stamped so pair-level accounting stays consistent.
        won = adopted
        cancelled = 0
        for pair in pairs:
            if pair.primary_lat is None:
                if pair.winner == "primary" and pair.twin_lat is None:
                    cancelled += 1   # both arms unserved: hedge abandoned
                continue         # primary requeued or adopted; handled above
            if pair.twin_lat is not None and pair.twin_lat < pair.primary_lat:
                pair.twin_tier.metrics.record_latency(pair.fn, pair.twin_lat)
                pair.winner = "twin"
                won += 1
            else:
                pair.primary_tier.metrics.record_latency(pair.fn,
                                                         pair.primary_lat)
                pair.winner = "primary"
                cancelled += 1
        if won:
            self.metrics.inc("hedges_won", won)
        if cancelled:
            self.metrics.inc("hedges_cancelled", cancelled)
        return {"served": served, "hedges_won": won,
                "hedges_cancelled": cancelled, "spilled": spilled,
                "waves": waves, "steps": 0, "migrated": 0,
                "migrations_aborted": 0, "inflight": 0}
