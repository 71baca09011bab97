"""Continuous-batching multi-tenant serving engine — the single entry point
for SGDRC serving, with two interchangeable backends behind one API.

**JAX backend** (``backend="jax"``): executes real model forwards on the local
device with slot-based continuous batching. Each tenant owns a fixed pool of
decode slots; requests carry an explicit phase state machine (``WAITING ->
PREFILLING(pos) -> DECODING -> FINISHED``) and every quantum is composed by
the :class:`~repro.serving.scheduler.TokenBudgetScheduler`: decode tokens
first (one batched decode across the tenant's DECODING slots), then
admission, then cached-context prefill *chunks* of at most ``chunk_size``
tokens per request, all bounded by the class's per-quantum token budget — a
long prompt prefills across several quanta while decode keeps ticking (the
TBT guarantee a monolithic prefill quantum used to break), with the quantum
boundary the TPU analogue of the paper's tile-quantum preemption point.
Chunks run through one batched ``tf.prefill_step`` call per length group
(Sq-token query chunks attending to their ``pos + Sq`` cached KV); the final
prompt position is always its own one-token chunk, so generated tokens are
bit-equal across chunk sizes and to the seed's scan-of-decode-steps prefill.

With ``paged=True`` the KV cache is a :class:`~repro.serving.kv_cache.
PagedKVCache`: slots share a page pool carved from the ColoredArena (LS/BE
page sets follow the plan's ``ch_be`` channel split) and admission is
*page-table* admission — a request enters a slot when ``ceil((prompt +
max_new) / page_size)`` pages are free, not when a whole ``max_seq`` row is,
so the same arena bytes sustain more concurrent decode slots. Prefill blits
whole pages; decode appends one page entry per row (no full-cache rewrite);
pages are freed at eviction. ``use_flash=True`` additionally routes decode
attention through the ragged Pallas flash-decode kernel.

With ``prefix_cache=True`` (requires ``paged``) each tenant additionally
keeps a :class:`~repro.serving.prefix_cache.PrefixCache`: a radix tree over
prompt token ids whose nodes own ref-counted KV pages in the colored arena.
Admission matches the prompt against the tree, maps the cached prefix pages
copy-on-write into the slot's page table, and prefills only the uncached
suffix — batched through the same cached-context chunk path as everything
else (no per-token replay loop, so ``prefix_min_hit`` defaults to 0) —
strictly fewer free pages and strictly fewer prefill FLOPs/bytes per hit,
which is extra admission capacity and extra lendable bandwidth at equal
arena bytes. The scheduler's hit-aware admission orders the waiting queue by
predicted hit size, so under pool pressure the cheap admissions land first
and the batch runs wider. Committed prompt (and, at eviction, generated)
pages are donated back to the tree; zero-ref leaves are LRU-evicted under
pool pressure; shared pages referenced by any live page table are pinned out
of tidal ``resplit`` migrations until their references drop.

**Sim backend** (``backend="sim"``): drives the discrete-event
``core.simulator.GPUSimulator`` with the same request stream, so the paper's
Fig. 5/6/11/12 scenario sweeps and the real reduced-scale execution share one
engine API (see benchmarks/fig12_invram.py).

The offline controller's :class:`~repro.core.controller.ResourcePlan` is
threaded end-to-end: ``plan.sm_be`` becomes the BE *quantum share* — the
fraction of engine quanta granted to BE tenants while LS work is pending
(elastic multiplexing: BE gets everything when LS idles, and with no plan BE
is strictly preempted, the conservative default) — ``plan.ch_be`` sets the
ColoredArena channel split (and the simulator's hard bandwidth split),
``plan.prefill_budget`` caps BE prefill tokens per quantum (the scheduler's
throttle, so tidal re-planning can slow BE prompt processing without
touching BE's SM share), and ``metrics()`` reports per-class SLO attainment
/ throughput plus p50/p99 TTFT and TBT so the plan's effect on both latency
phases is observable.

**Online control plane**: pass ``controller=`` (an
:class:`~repro.core.controller.OnlineController` over a plan frontier, or a
:class:`~repro.core.controller.PlanSchedule`) and the plan becomes
*time-varying*. On the JAX backend the engine builds a
:class:`~repro.core.compute.LoadSignal` from LS queue depth, slot occupancy
and windowed SLO attainment every ``control_interval`` quanta, and adopts
the controller's plan at the step boundary via :meth:`apply_plan` — new
``sm_be`` takes effect at the next quantum pick; a ``ch_be`` move resplits
the ColoredArena (migrating off-color pages) and recolors every tenant's KV
page pool. LS work arriving while the full-lending plan is active triggers
an immediate out-of-band control tick, so the LS preemption delay is
bounded by one engine quantum. On the sim backend the controller is handed
to ``GPUSimulator`` and consulted every ``control_dt`` simulated seconds.
``transitions`` records every adopted plan with the pages migrated.

Scheduling invariants:
  * LS quanta strictly precede BE quanta whenever no plan grants BE a share,
  * per-tenant KV caches are bump-allocated from a ColoredArena when coloring
    is enabled (the SPT indirection is exercised by the kernels' tests; the
    engine tracks channel placement and isolation violations),
  * host<->device weight/cache traffic goes through the PCIe CFS.
"""
from __future__ import annotations

import re
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.compute import ComputePolicy
from ..core.coloring.allocator import (ColoredArena, OutOfColoredMemory,
                                       split_channels)
from ..core.controller import ResourcePlan, measured_prefix_hit
from ..core.simulator import (GPU_DEVICES, GPUSimulator, Kernel, Tenant,
                              request_kernels)
from ..core.tenancy import TenantSpec
from ..models import transformer as tf
from ..models.common import name_key
from .. import obs
from .faults import ColdPageCorrupt, FaultPlane, HostTierFault, safe_floor
from .kv_cache import PagedKVCache, kv_bytes_per_token
from .prefix_cache import PrefixCache
from .scheduler import (Phase, QuantumReport, TokenBudgetScheduler,
                        split_tiles)
from .swap import HostSwapPool


@dataclass
class Request:
    rid: int
    tenant: str
    tokens: np.ndarray             # [S] prompt
    max_new: int
    t_submit: float
    t_admit: Optional[float] = None   # entered a decode slot
    t_first: Optional[float] = None   # first output token (TTFT)
    t_last: Optional[float] = None    # latest output token (TBT tracking)
    t_done: Optional[float] = None
    output: Optional[list] = None
    slot: Optional[int] = None
    failed: bool = False           # rejected (e.g. can never fit KV pages)
    hit_tokens: int = 0            # prefix-cache hit length at admission
    # phase state machine (serving.scheduler): WAITING -> PREFILLING(pos)
    # -> DECODING -> FINISHED; ``prefill_pos`` is the next prompt position
    # to compute (a prefix-cache hit starts at its uncached suffix)
    phase: Phase = Phase.WAITING
    prefill_pos: int = 0
    # KV-hierarchy state: a preempted request restarts from scratch; a
    # swapped-out decode keeps its host page keys plus the decode state to
    # resume from once the pages fault back in (SWAPPED -> SWAPPING)
    swap_keys: Optional[list] = None   # host-tier keys, logical page order
    swap_cursor: int = 0               # next page to fault in
    resume_pos: int = 0                # rt.pos at swap-out
    resume_tok: int = 0                # rt.last_tok at swap-out
    t_evicted: Optional[float] = None  # set at preempt/swap-out, cleared at
    preempts: int = 0                  # the resume token (warm-restart gap)
    # chaos-plane state: deadline is absolute (clock units) — an expired BE
    # request is load-shed instead of served late; rejected marks submit
    # backpressure (bounded queue / oversized prompt); shed marks a request
    # dropped by a recovery path (deadline, grow-deadlock). swap_retries /
    # swap_backoff drive the bounded retry-with-backoff of swap-in faults
    # (backoff = engine step index before which no retry is attempted).
    deadline: Optional[float] = None
    rejected: bool = False
    shed: bool = False
    swap_retries: int = 0
    swap_backoff: int = 0

    @property
    def latency(self):
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def ttft(self):
        return None if self.t_first is None else self.t_first - self.t_submit


@dataclass
class _TenantRT:
    spec: TenantSpec
    cfg: ModelConfig
    params: object
    decode_fn: object
    prefill_fn: object
    n_slots: int
    queue: List[Request] = field(default_factory=list)
    done: List[Request] = field(default_factory=list)
    # slot-pool decode state (JAX backend)
    cache: object = None
    pos: Optional[np.ndarray] = None        # [n_slots] next write position
    last_tok: Optional[np.ndarray] = None   # [n_slots] last emitted token
    active: List[Optional[Request]] = field(default_factory=list)
    alloc_name: Optional[str] = None
    kv: Optional[PagedKVCache] = None       # page-table state (paged mode)
    prefix: Optional[PrefixCache] = None    # radix-tree page sharing
    chunk_fn: object = None                 # jitted cached-context prefill
    peak_active: int = 0                    # max concurrent decode slots seen
    prefill_tokens: int = 0                 # prompt tokens admitted
    prefill_computed: int = 0               # prompt tokens actually prefilled
    tbt_gaps: List[float] = field(default_factory=list)  # inter-token gaps
    # KV-hierarchy state (swap mode)
    host: Optional[HostSwapPool] = None     # host tier for swapped pages
    preemptions: int = 0                    # requests restarted from scratch
    swap_outs: int = 0                      # decode page groups pushed to host
    swap_ins: int = 0                       # page groups faulted back
    grow_stalls: int = 0                    # decode quanta stalled on growth
    chunk_aborts: int = 0                   # sub-chunk prefill preemptions
    resume_gaps: List[float] = field(default_factory=list)  # evict->token
    # chaos-plane state (serving.faults): counters for the recovery paths
    # plus the per-tenant degradation ladder — every recovery costs one
    # point of fault_score; each fault_budget points takes the next rung
    rejected: int = 0                       # submit backpressure rejections
    shed: int = 0                           # requests load-shed by recovery
    grow_deadlocks: int = 0                 # growth exhausted all victims
    deadlock_streak: int = 0                # consecutive victimless stalls
    swap_retries: int = 0                   # swap-in fault retries
    fault_recoveries: Dict[str, int] = field(default_factory=dict)
    fault_score: int = 0
    degraded: List[str] = field(default_factory=list)  # ladder rungs taken
    flash: bool = False                     # current attention path
    swap_degraded: bool = False             # rung: swap-out -> preempt
    grow_degraded: bool = False             # rung: growth -> full extent
    # sim-backend knobs / results
    closed_loop: bool = False
    sim_seq: Optional[int] = None
    max_kernels: int = 24
    sim_completed: int = 0
    sim_swap_bytes: int = 0                 # modeled swap traffic per request

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.active)


def _earliest_outstanding(rt: "_TenantRT") -> float:
    """Hoisted tenant-priority key for ``ServingEngine._pick``: earliest
    submit time among this tenant's queued + active requests."""
    ts = [r.t_submit for r in rt.queue]
    ts += [r.t_submit for r in rt.active if r is not None]
    return min(ts) if ts else float("inf")


def _tagged(fn, tenant: str):
    """``fn`` renamed ``<name>__<tenant>`` (non-word characters as ``_``):
    jit names its program after it."""
    tag = re.sub(r"\W", "_", tenant)
    fn.__name__ = fn.__qualname__ = f"{fn.__name__}__{tag}"
    return fn


def _rids(reqs) -> str:
    """Request ids of a call's live rows, as one span argument: joined by
    spaces, since the profiler ends an argument's value at a comma."""
    return " ".join(str(r.rid) for r in reqs)


def _scatter_rows(dst_cache, src_cache, slots):
    """Write the per-request rows of a freshly prefilled cache into the slot
    cache. ``layers`` leaves are [n_periods, B, ...] (batch axis 1, from the
    layer scan); ``prefix`` entries are per-layer trees with batch axis 0."""
    out = dict(dst_cache)
    if "prefix" in dst_cache:
        out["prefix"] = [
            jax.tree.map(lambda d, s: d.at[slots].set(s.astype(d.dtype)),
                         dp, sp)
            for dp, sp in zip(dst_cache["prefix"], src_cache["prefix"])]
    out["layers"] = jax.tree.map(
        lambda d, s: d.at[:, slots].set(s.astype(d.dtype)),
        dst_cache["layers"], src_cache["layers"])
    return out


class _JaxBackend:
    """Slot-pool continuous batching on the local device."""

    def __init__(self, engine: "ServingEngine"):
        self.engine = engine

    def _build_fns(self, rt: _TenantRT):
        """(Re)build the tenant's jitted forwards. The attention path is
        captured from ``rt.flash`` *by value*, so the degradation ladder's
        flash->dense rung can rebuild one live tenant mid-run without
        touching any other tenant or the engine-wide default."""
        eng = self.engine
        cfg = rt.cfg
        flash = rt.flash

        def _prefill(p, tokens, cap):
            return tf.prefill(p, cfg, {"tokens": tokens}, cap)

        def _decode(p, tok, cache, pos):
            return tf.decode_step(p, cfg, tok, cache, pos,
                                  use_flash=flash)

        def _decode_paged(p, tok, cache, pos, pt):
            return tf.decode_step(p, cfg, tok, cache, pos,
                                  ctx_extra={"page_table": pt},
                                  use_flash=flash)

        def _chunk(p, toks, cache, pos):
            return tf.prefill_step(p, cfg, toks, cache, pos,
                                   use_flash=flash)

        def _chunk_paged(p, toks, cache, pos, pt):
            return tf.prefill_step(p, cfg, toks, cache, pos,
                                   ctx_extra={"page_table": pt},
                                   use_flash=flash)

        # each program is named after its tenant as well as its kind
        # (``jit__decode_paged__ls_qwen3_1_7b``), so a profile says which
        # tenant ran it
        tenant = rt.spec.name
        # monolithic prompt processing survives only as the fallback for
        # models the cached-context chunk path can't serve (SSM state,
        # encoders, vision cross-attn: tf.chunkable is False)
        rt.prefill_fn = jax.jit(_tagged(_prefill, tenant), static_argnums=2)
        if tf.chunkable(cfg):
            rt.chunk_fn = jax.jit(
                _tagged(_chunk_paged if eng.paged else _chunk, tenant),
                donate_argnums=(2,))
        # the previous cache is dead after each decode step — donate it so
        # the one-token append is in-place instead of a full pool copy
        rt.decode_fn = jax.jit(
            _tagged(_decode_paged if eng.paged else _decode, tenant),
            donate_argnums=(2,))

    def add_tenant(self, rt: _TenantRT):
        eng = self.engine
        rt.flash = eng.use_flash
        self._build_fns(rt)
        if eng.paged:
            chans = cap = None
            if eng.arena is not None:
                chans = eng.ls_ch if rt.spec.is_ls else eng.be_ch
                if eng.controller is not None:
                    # tidal pools: size the device pool for the lending
                    # maximum (every channel); live admission still runs
                    # against the class's current colored bytes
                    cap = tuple(range(eng.arena.num_channels))
            rt.kv = PagedKVCache(rt.cfg, rt.n_slots, eng.max_seq,
                                 eng.page_size,
                                 n_pages=eng.kv_pages, arena=eng.arena,
                                 channels=chans, name=rt.spec.name,
                                 cap_channels=cap,
                                 sharing=eng.prefix_cache)
            if eng.faults is not None:
                # chaos plane: allocation faults defer admission/growth at
                # the call sites, never inside can_admit_pages (kv_cache)
                rt.kv.fault_hook = (
                    lambda _rt=rt: eng.faults.active(
                        "alloc_fail", eng.clock(),
                        target=_rt.spec.name) is not None)
            if eng.prefix_cache:
                rt.prefix = PrefixCache(eng.page_size, rt.kv)
            rt.cache = rt.kv.init_pools()
            if eng.swap:
                rt.host = HostSwapPool(eng.cold_dtype,
                                       tenant=rt.spec.name,
                                       priority=rt.spec.priority,
                                       nice=rt.spec.nice,
                                       faults=eng.faults,
                                       verify=eng.fault_recovery)
                if eng.tracer.level >= 0:
                    rt.host.tracer = eng.tracer
                    rt.host.trace_prefix = eng._trace_prefix
                if rt.prefix is not None:
                    # cold prefix tier: evicted leaves' pages survive on the
                    # host and fault back in before a matching admission
                    def _store(key, page, _rt=rt):
                        _rt.host.drop(key)   # re-evicted after a re-donate
                        _rt.host.put(_rt.cache, key, page, t=eng.clock())

                    def _load(key, page, _rt=rt):
                        _rt.cache, _ = _rt.host.get(_rt.cache, key, page,
                                                    t=eng.clock())

                    rt.prefix.cold_store = _store
                    rt.prefix.cold_loader = _load
                    rt.prefix.cold_has = lambda key, _rt=rt: key in _rt.host
        else:
            rt.cache = tf.init_cache(rt.cfg, rt.n_slots, eng.max_seq)
        rt.pos = np.zeros(rt.n_slots, np.int32)
        rt.last_tok = np.zeros(rt.n_slots, np.int32)
        rt.active = [None] * rt.n_slots

    # -- step-boundary admission / eviction ------------------------------
    def _finish(self, rt: _TenantRT, slot: int):
        req = rt.active[slot]
        eng = self.engine
        eng._trace_leave(rt, req, slot, req.phase.name.lower(), "finished")
        req.t_done = eng.clock()
        req.phase = Phase.FINISHED
        rt.done.append(req)
        eng._trace_done(rt, req)
        rt.active[slot] = None
        pos = int(rt.pos[slot])
        rt.pos[slot] = 0
        rt.last_tok[slot] = 0
        if rt.prefix is not None:
            # KV token stream: prompt, then the fed-back outputs (the last
            # output token's KV was never written) — donate full pages to
            # the radix tree, then release the slot's private pages
            stream = np.concatenate(
                [req.tokens,
                 np.asarray(req.output[:max(pos - len(req.tokens), 0)],
                            np.int32)])
            rt.prefix.release_slot(slot, stream, pos)
        elif rt.kv is not None:
            rt.kv.free_slot(slot)

    # -- KV hierarchy: growth / preemption / swap ----------------------
    def _drop_slot_pages(self, rt: _TenantRT, slot: int):
        """Free a slot's pages *without* donating to the prefix tree (the
        preempt/swap-out path: the content either restarts from scratch or
        already lives on the host)."""
        if rt.prefix is not None:
            rt.prefix.release_slot(slot, None, 0)
        elif rt.kv is not None:
            rt.kv.free_slot(slot)

    # -- chaos plane: recovery bookkeeping / degradation ladder ---------
    def _record_recovery(self, rt: _TenantRT, kind: str):
        """Charge a recovery action against the tenant's fault budget.
        Every ``fault_budget`` points the degradation ladder takes its next
        rung (serving.faults module docstring): flash->dense decode,
        swap-out->preempt-restart, page-growth->full-extent admission —
        each trades peak efficiency for fewer moving parts under a storm."""
        rt.fault_recoveries[kind] = rt.fault_recoveries.get(kind, 0) + 1
        rt.fault_score += 1
        eng = self.engine
        eng.tracer.instant("recovery", kind, eng.clock(),
                           f"{eng._trace_prefix}recovery",
                           tenant=rt.spec.name, score=rt.fault_score)
        while rt.fault_score >= eng.fault_budget * (len(rt.degraded) + 1):
            if rt.flash:
                rt.flash = False
                self._build_fns(rt)
                rt.degraded.append("flash_to_dense")
            elif eng.swap and not rt.swap_degraded:
                rt.swap_degraded = True
                rt.degraded.append("swap_to_preempt")
            elif eng.grow_pages and not rt.grow_degraded:
                rt.grow_degraded = True
                rt.degraded.append("grow_to_full")
            else:
                break

    def _shed(self, rt: _TenantRT, req: Request, reason: str):
        """Load-shed a request (deadline expiry, grow deadlock): device
        pages freed without donation, host-tier pages dropped, and the
        request finishes failed+shed — recovery trades one BE request for
        the batch's forward progress instead of stalling everyone."""
        eng = self.engine
        eng.tracer.instant("recovery", reason, eng.clock(),
                           f"{eng._trace_prefix}recovery",
                           tenant=rt.spec.name, rid=req.rid)
        if req.slot is not None:
            s = req.slot
            eng._trace_leave(rt, req, s, req.phase.name.lower(), reason)
            self._drop_slot_pages(rt, s)
            rt.active[s] = None
            rt.pos[s] = 0
            rt.last_tok[s] = 0
            req.slot = None
        elif req in rt.queue:
            rt.queue.remove(req)
        if req.swap_keys and rt.host is not None:
            for k in req.swap_keys:
                rt.host.drop(k)
        req.swap_keys = None
        req.failed = True
        req.shed = True
        req.phase = Phase.FINISHED
        req.t_done = self.engine.clock()
        if req.output is None:
            req.output = []
        rt.shed += 1
        rt.done.append(req)
        eng._trace_done(rt, req)

    def _youngest_victim(self, rt: _TenantRT, exclude: int,
                         younger_than: Optional[Request] = None
                         ) -> Optional[Request]:
        """Preemption victim under pool exhaustion: the youngest (latest
        submit) other active request in this tenant's pool — least sunk
        work, and it re-queues behind everything it raced. The growing slot
        itself is excluded (self-preemption would livelock), and with
        ``younger_than`` only requests strictly younger than the grower
        qualify: under preempt-restart (swap off or degraded) two peers
        stealing each other's pages would otherwise reset each other's
        output forever — seniority makes the eldest's progress monotone,
        which is what guarantees the pool eventually drains."""
        age = (lambda r: (r.t_submit, r.rid))
        cands = [r for s, r in enumerate(rt.active)
                 if r is not None and s != exclude
                 and r.phase in (Phase.PREFILLING, Phase.DECODING)
                 and (younger_than is None or age(r) > age(younger_than))]
        if not cands:
            return None
        return max(cands, key=age)

    def _preempt(self, rt: _TenantRT, req: Request):
        """Restart a victim from scratch (swap off, or a mid-prefill victim
        with no resumable decode state): pages freed without donation, phase
        back to WAITING, re-queued. Deterministic greedy decode makes the
        restart emit identical tokens."""
        s = req.slot
        self.engine._trace_leave(rt, req, s, req.phase.name.lower(),
                                 "preempt")
        self._drop_slot_pages(rt, s)
        rt.active[s] = None
        rt.pos[s] = 0
        rt.last_tok[s] = 0
        req.t_evicted = self.engine.clock()
        req.phase = Phase.WAITING
        req.slot = None
        req.prefill_pos = 0
        req.output = None
        req.t_first = req.t_last = req.t_admit = None
        req.hit_tokens = 0
        req.swap_keys = None
        req.preempts += 1
        rt.preemptions += 1
        rt.queue.append(req)

    def _swap_out(self, rt: _TenantRT, req: Request) -> int:
        """Move a decoding victim's whole page group to the host tier:
        page contents copied in logical order (quantized per ``cold_dtype``),
        decode resume state saved, device pages freed without donation, the
        request re-queued as SWAPPED. Returns pages moved."""
        eng = self.engine
        s, kv = req.slot, rt.kv
        n = kv.mapped_count(s)
        now = eng.clock()
        keys = []
        try:
            for j in range(n):
                key = ("req", req.rid, j)
                rt.host.drop(key)
                rt.host.put(rt.cache, key, int(kv.page_table[s, j]), t=now)
                keys.append(key)
        except HostTierFault:
            # mid-group write fault: the host must never hold a partial
            # page group — drop what landed, let the caller pick a fallback
            for k in keys:
                rt.host.drop(k)
            raise
        req.swap_keys = keys
        req.swap_cursor = 0
        req.resume_pos = int(rt.pos[s])
        req.resume_tok = int(rt.last_tok[s])
        if req.t_evicted is not None:
            # re-evicted before decoding a token after its last swap-in:
            # close the pending warm-restart gap here so every completed
            # swap-in records exactly one resume gap
            rt.resume_gaps.append(now - req.t_evicted)
        req.t_evicted = now
        eng._trace_leave(rt, req, s, req.phase.name.lower(), "swap_out")
        req.phase = Phase.SWAPPED
        req.slot = None
        self._drop_slot_pages(rt, s)
        rt.active[s] = None
        rt.pos[s] = 0
        rt.last_tok[s] = 0
        rt.swap_outs += 1
        rt.queue.append(req)
        return n

    def _ensure_growth(self, rt: _TenantRT, slots: List[int]):
        """Growth pre-pass before the decode batch: map the page each
        decode write needs (growth-mode admission only reserved the
        prompt's pages). On pool exhaustion: free cold prefix leaves first,
        then swap out — or, with swap off / for a mid-prefill victim,
        preempt — the youngest other active request; a slot that still
        can't grow stalls out of this quantum's decode batch. Under the
        chaos plane: an ``alloc_fail`` window defers every growth (no
        eviction), a swap write fault downgrades that victim to a preempt,
        and a *persistent* no-victim deadlock (``deadlock_patience``
        quanta) sheds the youngest BE request rather than spinning.
        Victims must be strictly younger than their grower, so the eldest
        request's progress is monotone — the liveness argument for the
        preempt-restart path. Returns (ready slots, pages swapped out)."""
        eng = self.engine
        kv = rt.kv
        ready, out_pages = [], 0
        if kv.alloc_fault():
            # allocator fault window: defer every growth this quantum —
            # nothing is evicted, the growers stall, and slots that already
            # own their next page decode normally
            for s in slots:
                req = rt.active[s]
                if req is None or req.phase is not Phase.DECODING:
                    continue
                if kv.needs_grow(s, int(rt.pos[s])):
                    rt.grow_stalls += 1
                else:
                    ready.append(s)
            return ready, 0
        for s in slots:
            req = rt.active[s]
            if req is None or req.phase is not Phase.DECODING:
                continue          # taken as a victim by an earlier grower
            if not kv.needs_grow(s, int(rt.pos[s])):
                ready.append(s)
                continue
            grown = False
            while True:
                if kv.can_admit_pages(1):
                    kv.grow_slot(s)
                    grown = True
                    rt.deadlock_streak = 0
                    break
                if rt.prefix is not None and rt.prefix.evict_until(1):
                    continue
                victim = self._youngest_victim(rt, exclude=s,
                                               younger_than=req)
                if victim is None:
                    if self._youngest_victim(rt, exclude=s) is not None:
                        # only elders are killable: stall — seniority says
                        # the eldest grower wins, and its monotone progress
                        # is what drains the pool for this slot later
                        break
                    # every other slot is SWAPPING/unkillable. The old code
                    # spun here forever re-picking nothing (grow livelock) —
                    # but one victimless quantum is usually just a swap-in
                    # mid-flight, so only a *persistent* streak
                    # (deadlock_patience quanta) counts as a deadlock; then
                    # BE under recovery sheds the youngest active request
                    # of any phase — including the grower itself — so the
                    # pool drains. LS stalls and surfaces via the counter
                    # instead of losing work.
                    rt.deadlock_streak += 1
                    if rt.deadlock_streak >= eng.deadlock_patience:
                        rt.deadlock_streak = 0
                        rt.grow_deadlocks += 1
                        if not rt.spec.is_ls and eng.fault_recovery:
                            cands = [r for r in rt.active if r is not None]
                            if cands:
                                shed = max(cands,
                                           key=lambda r: (r.t_submit, r.rid))
                                self._shed(rt, shed, "grow_deadlock")
                                if shed is not req:
                                    continue
                    break
                if (rt.host is not None and not rt.swap_degraded
                        and victim.phase is Phase.DECODING):
                    try:
                        out_pages += self._swap_out(rt, victim)
                    except HostTierFault:
                        # host write window: fall back one rung for this
                        # victim — preempt-restart instead of stalling
                        if eng.fault_recovery:
                            self._record_recovery(rt, "swap_write")
                            self._preempt(rt, victim)
                        else:
                            break
                else:
                    self._preempt(rt, victim)
            if grown:
                ready.append(s)
            elif rt.active[s] is not None:
                rt.grow_stalls += 1
        return ([s for s in ready if rt.active[s] is not None
                 and rt.active[s].phase is Phase.DECODING], out_pages)

    def _swap_progress(self, rt: _TenantRT) -> int:
        """Fault host pages back into SWAPPING slots, up to the engine's
        ``swap_quantum_pages`` per quantum — the SWAPPING phase is paced
        across quanta so decode keeps ticking next to a fault storm. A
        slot whose page group completes resumes DECODING where it left
        off (pos + last token restored)."""
        eng = self.engine
        budget = eng.swap_quantum_pages
        pages = 0
        for s in eng.scheduler.swap_slots(rt):
            if budget <= 0:
                break
            req = rt.active[s]
            if req.swap_backoff > eng._step_idx:
                continue          # backing off a faulted swap-in
            faulted = False
            while budget > 0 and req.swap_cursor < len(req.swap_keys):
                dst = int(rt.kv.page_table[s, req.swap_cursor])
                try:
                    rt.cache, _ = rt.host.get(
                        rt.cache, req.swap_keys[req.swap_cursor], dst,
                        t=eng.clock())
                except HostTierFault as e:
                    faulted = True
                    if not eng.fault_recovery:
                        break     # naive baseline: blind retry next quantum
                    req.swap_retries += 1
                    rt.swap_retries += 1
                    if (isinstance(e, ColdPageCorrupt)
                            or req.swap_retries > eng.swap_retry_limit):
                        # unrecoverable (corrupt page / retries exhausted):
                        # abandon the host copy and preempt-restart — the
                        # deterministic replay re-emits identical tokens
                        for k in req.swap_keys:
                            rt.host.drop(k)
                        self._record_recovery(rt, "swap_read")
                        self._preempt(rt, req)
                        req.swap_retries = 0
                        req.swap_backoff = 0
                    else:
                        # bounded retry with exponential backoff, in engine
                        # steps — the transient window clears while other
                        # slots keep their swap-in budget
                        req.swap_backoff = eng._step_idx + (
                            1 << min(req.swap_retries, 4))
                    break
                req.swap_cursor += 1
                budget -= 1
                pages += 1
            if faulted:
                continue
            if req.swap_cursor >= len(req.swap_keys):
                rt.pos[s] = req.resume_pos
                rt.last_tok[s] = req.resume_tok
                eng._trace_phase(rt, req, "swapping", "decoding")
                req.phase = Phase.DECODING
                req.swap_keys = None
                req.swap_retries = 0
                req.swap_backoff = 0
                rt.swap_ins += 1
        return pages

    def _write_sentinel(self, rt: _TenantRT) -> int:
        """A cache position no batched call may write: dense caches drop any
        position >= max_seq; paged lookups drop any logical page >= the
        table width. Used to mask rows out of a batched decode/chunk call
        (their compute runs, their writes drop, their outputs are
        ignored)."""
        if rt.kv is not None:
            return rt.kv.pages_per_slot * rt.kv.page_size
        return self.engine.max_seq

    def _seed_first_token(self, rt: _TenantRT, req: Request, first_tok: int):
        """Prefill-completion epilogue: the request enters DECODING seeded
        with its first output token; the committed full prompt pages are
        donated to the prefix tree; degenerate (max_new<=1) requests finish
        immediately."""
        eng = self.engine
        s = req.slot
        L = len(req.tokens)
        now = eng.clock()
        req.t_first = req.t_last = now
        if req.t_evicted is not None:       # preempt-restart warm TTFT
            rt.resume_gaps.append(now - req.t_evicted)
            req.t_evicted = None
        eng._trace_phase(rt, req, req.phase.name.lower(), "decoding")
        req.phase = Phase.DECODING
        req.output = [int(first_tok)]
        rt.pos[s] = L
        rt.last_tok[s] = req.output[0]
        if rt.prefix is not None:
            rt.prefix.donate(s, req.tokens, L)
        if len(req.output) >= max(req.max_new, 1) or rt.pos[s] >= eng.max_seq:
            self._finish(rt, s)
        elif eng.migrate_hook is not None and eng.migrate_hook(rt, req):
            # disaggregated handoff: the decode slice owns the request now;
            # the hook serialized the page group, so only free the slot
            # (the prefix donation above already happened — no double
            # donation, and no local decode step runs for this request)
            eng._trace_leave(rt, req, s, "decoding", "migrated")
            self._drop_slot_pages(rt, s)
            rt.active[s] = None
            rt.pos[s] = 0
            rt.last_tok[s] = 0

    def _prefill_monolithic(self, rt: _TenantRT, reqs: List[Request]) -> int:
        """Fallback prompt processing for non-chunkable models (SSM state,
        encoders): one batched ``tf.prefill`` call per prompt-length group,
        rows scattered into the slot cache. Whole prompts, one quantum."""
        eng = self.engine
        by_len: Dict[int, List[Request]] = {}
        for r in reqs:
            by_len.setdefault(len(r.tokens), []).append(r)
        tokens = 0
        for L, group in by_len.items():
            toks = jnp.asarray(np.stack([r.tokens for r in group]))
            slots = [r.slot for r in group]
            last_logits, pcache = rt.prefill_fn(rt.params, toks, eng.max_seq)
            rt.cache = _scatter_rows(rt.cache, pcache,
                                     jnp.asarray(slots, jnp.int32))
            with obs.span("sync", tenant=rt.spec.name):
                first = np.asarray(jnp.argmax(last_logits[:, 0], axis=-1))
            rt.prefill_computed += L * len(group)
            tokens += L * len(group)
            for j, req in enumerate(group):
                req.prefill_pos = L
                if eng.logits_hook is not None:
                    eng.logits_hook(rt, req, last_logits[j, 0])
                self._seed_first_token(rt, req, int(first[j]))
        return tokens

    def _run_chunks(self, rt: _TenantRT, chunks) -> int:
        """Execute this quantum's prefill chunks: waves preserve per-slot
        chunk order, each wave batches equal-length chunks into one
        cached-context ``prefill_step`` call across the slot pool (rows not
        in the group sit at the write sentinel — writes drop, logits
        ignored). A chunk write landing in a shared page forks it
        copy-on-write first; a chunk that reaches the end of its prompt
        seeds the request's first output token. Returns tokens computed.

        Sub-chunk preemption (``eng.preempt_tile``): a BE tenant's chunks
        are split into tiles of at most ``preempt_tile`` tokens, and after
        every executed wave the engine holds a preemption point — if an LS
        request is waiting, the remaining tiles are aborted (each executed
        tile already committed its ``prefill_pos``, so the abandoned work
        is exactly zero tokens) and the waiting LS requests are admitted in
        *this* quantum instead of after the full chunk. A resumed chunk is
        just a smaller chunk, so tokens are bit-equal under any preemption
        pattern (the kernel-level analogue is ``prefill_attention``'s
        abort/progress protocol)."""
        eng = self.engine
        kv = rt.kv
        tenant = rt.spec.name
        preemptable = bool(eng.preempt_tile) and not rt.spec.is_ls
        if preemptable:
            chunks = split_tiles(chunks, eng.preempt_tile)
        by_slot: Dict[int, list] = {}
        for c in chunks:
            by_slot.setdefault(c.slot, []).append(c)
        tokens = 0
        sentinel = self._write_sentinel(rt)
        while any(by_slot.values()):
            wave = [lst.pop(0) for lst in by_slot.values() if lst]
            wave_tokens = 0
            by_len: Dict[int, list] = {}
            for c in wave:
                by_len.setdefault(c.length, []).append(c)
            for Sq, group in by_len.items():
                with obs.span("dispatch", tenant=tenant, kind="chunk", sq=Sq,
                              slots=rt.n_slots, live=len(group),
                              tokens=Sq * len(group),
                              rids=_rids(c.req for c in group)):
                    toks = np.zeros((rt.n_slots, Sq), np.int32)
                    pos = np.full(rt.n_slots, sentinel, np.int32)
                    for c in group:
                        toks[c.slot] = c.req.tokens[c.start:c.start + Sq]
                        pos[c.slot] = c.start
                        if kv is not None:
                            # fork every shared page this chunk writes into
                            for pg in range(c.start // kv.page_size,
                                            (c.start + Sq - 1)
                                            // kv.page_size + 1):
                                if kv.needs_fork(c.slot, pg * kv.page_size):
                                    rt.cache = kv.fork_cow(rt.cache, c.slot,
                                                           pg)
                    if kv is not None:
                        logits, rt.cache = rt.chunk_fn(
                            rt.params, jnp.asarray(toks), rt.cache,
                            jnp.asarray(pos), kv.device_page_table())
                    else:
                        logits, rt.cache = rt.chunk_fn(
                            rt.params, jnp.asarray(toks), rt.cache,
                            jnp.asarray(pos))
                rt.prefill_computed += Sq * len(group)
                if eng.tracer.enabled("chunk"):
                    t_c = eng.clock()
                    for c in group:
                        eng.tracer.instant(
                            "chunk", f"c{c.start}", t_c,
                            eng._tr_track(rt, c.slot), rid=c.req.rid,
                            start=c.start, len=Sq)
                if eng._aborted_rids and eng.tracer.enabled("preempt"):
                    t_c = eng.clock()
                    for c in group:
                        if c.req.rid in eng._aborted_rids:
                            eng.tracer.instant(
                                "preempt", "resume", t_c,
                                eng._tr_track(rt, c.slot),
                                tenant=rt.spec.name, rid=c.req.rid,
                                start=c.start)
                for c in group:
                    eng._aborted_rids.discard(c.req.rid)
                tokens += Sq * len(group)
                wave_tokens += Sq * len(group)
                done = [c for c in group
                        if c.start + Sq >= len(c.req.tokens)]
                if done:
                    with obs.span("sync", tenant=tenant):
                        arg = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
                hook = self.engine.chunk_hook
                for c in group:
                    c.req.prefill_pos = c.start + Sq
                    if hook is not None and c.start + Sq < len(c.req.tokens):
                        # mid-prompt commit: stream the newly completed KV
                        # pages while the remaining chunks still run
                        hook(rt, c.req)
                if done:
                    with obs.span("emit", tenant=tenant,
                                  tokens=len(done)) as sp:
                        for c in done:
                            if eng.logits_hook is not None:
                                eng.logits_hook(rt, c.req, logits[c.slot, 0])
                            self._seed_first_token(rt, c.req,
                                                   int(arg[c.slot]))
                        sp.set_metadata(finished=sum(
                            c.req.phase is Phase.FINISHED for c in done))
            if eng.arrival_hook is not None:
                eng.arrival_hook(wave_tokens)
            if preemptable and any(by_slot.values()) \
                    and self._preempt_now():
                self._abort_remaining(rt, by_slot)
                break
        return tokens

    def _preempt_now(self) -> bool:
        """Preemption predicate at the tile boundary: an LS request is
        waiting for admission (``preempt_hook`` overrides for tests —
        e.g. always/never/seeded-random preemption)."""
        eng = self.engine
        if eng.preempt_hook is not None:
            return bool(eng.preempt_hook())
        return any(rt.spec.is_ls
                   and any(r.phase in (Phase.WAITING, Phase.SWAPPED)
                           for r in rt.queue)
                   for rt in eng.tenants.values())

    def _abort_remaining(self, rt: _TenantRT, by_slot):
        """Abort the quantum's remaining BE tiles and admit waiting LS
        requests in the same quantum. Executed tiles already committed
        their ``prefill_pos``, so the aborted requests resume next BE
        quantum as smaller chunks with zero recomputation and zero token
        drift."""
        eng = self.engine
        now = eng.clock()
        rt.chunk_aborts += 1
        eng.preempt_aborts += 1
        remaining = [lst[0].req for lst in by_slot.values() if lst]
        for req in remaining:
            eng._aborted_rids.add(req.rid)
        if eng.tracer.enabled("preempt"):
            for req in remaining:
                eng.tracer.instant(
                    "preempt", "abort", now, eng._tr_track(rt, req.slot),
                    tenant=rt.spec.name, rid=req.rid, pos=req.prefill_pos)
        for ls_rt in eng.tenants.values():
            if not ls_rt.spec.is_ls or not ls_rt.queue:
                continue
            for r in eng.scheduler.admit(ls_rt, eng):
                eng.preempt_waits.append(max(now - r.t_submit, 0.0))

    def _decode(self, rt: _TenantRT, slots: List[int]):
        """One batched decode across the tenant's DECODING slots. Rows not
        in ``slots`` (free, or mid-prefill) are masked to the write
        sentinel: their cache writes drop and their outputs are ignored, so
        a slot prefilling across quanta is never corrupted by the decode
        batch it shares the pool with."""
        eng = self.engine
        tenant = rt.spec.name
        rt.peak_active = max(rt.peak_active,
                             sum(r is not None for r in rt.active))
        with obs.span("dispatch", tenant=tenant, kind="decode", sq=1,
                      slots=rt.n_slots, live=len(slots), tokens=len(slots),
                      rids=_rids(rt.active[s] for s in slots)):
            live = np.zeros(rt.n_slots, bool)
            live[slots] = True
            if rt.prefix is not None:
                # safety net: a decode append must never mutate a shared
                # page (admission reserves + chunk execution fork every
                # predicted write, so this does not fire on the predicted
                # paths)
                for s in slots:
                    if rt.kv.needs_fork(s, int(rt.pos[s])):
                        rt.cache = rt.kv.fork_cow(
                            rt.cache, s, int(rt.pos[s]) // rt.kv.page_size)
            dec_pos = np.where(live, rt.pos,
                               self._write_sentinel(rt)).astype(np.int32)
            toks = jnp.asarray(rt.last_tok[:, None])
            if rt.kv is not None:
                logits, rt.cache = rt.decode_fn(rt.params, toks, rt.cache,
                                                jnp.asarray(dec_pos),
                                                rt.kv.device_page_table())
            else:
                logits, rt.cache = rt.decode_fn(rt.params, toks, rt.cache,
                                                jnp.asarray(dec_pos))
        with obs.span("sync", tenant=tenant):
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        with obs.span("emit", tenant=tenant, tokens=len(slots)) as sp:
            now = eng.clock()
            finished = 0
            for s in slots:
                req = rt.active[s]
                if eng.logits_hook is not None:
                    eng.logits_hook(rt, req, logits[s, 0])
                rt.pos[s] += 1
                tok = int(nxt[s])
                req.output.append(tok)
                rt.last_tok[s] = tok
                if req.t_last is not None:
                    rt.tbt_gaps.append(now - req.t_last)
                req.t_last = now
                if req.t_evicted is not None:   # first token after a swap-in
                    rt.resume_gaps.append(now - req.t_evicted)
                    req.t_evicted = None
                if len(req.output) >= max(req.max_new, 1) \
                        or rt.pos[s] >= eng.max_seq:
                    self._finish(rt, s)
                    finished += 1
            sp.set_metadata(finished=finished)

    def quantum(self, rt: _TenantRT) -> bool:
        """One scheduler-composed quantum: decode first (every DECODING slot
        emits a token — and a request finishing here releases its KV pages
        *before* this quantum's admission pass, so pages freed mid-window
        admit a waiting request in the same window), then admission (slots +
        pages only), then prefill chunks under the class token budget. A
        prompt therefore prefills across quanta while decode keeps
        ticking."""
        eng = self.engine
        sched = eng.scheduler
        tenant = rt.spec.name
        shed_now = 0
        with obs.span("sched", tenant=tenant):
            if eng.fault_recovery:
                # deadline shed pre-pass: an expired queued (WAITING/SWAPPED)
                # request is dropped before it can consume admission or
                # pages — under a fault storm BE deadlines turn backlog into
                # shed work instead of batch-wide stall
                now = eng.clock()
                for req in [r for r in rt.queue
                            if r.deadline is not None and now > r.deadline]:
                    self._shed(rt, req, "deadline")
                    shed_now += 1
            report = QuantumReport(tenant, rt.spec.priority,
                                   budget=sched.budget_for(rt.spec.priority))
            dec = sched.decode_slots(rt)
            if dec and eng.grow_pages and rt.kv is not None:
                dec, report.swap_out_pages = self._ensure_growth(rt, dec)
        if dec:
            self._decode(rt, dec)
            report.decode_tokens = len(dec)
            if eng.arrival_hook is not None:
                eng.arrival_hook(len(dec))
        chunks = None
        with obs.span("sched", tenant=tenant):
            admitted = sched.admit(rt, eng)
            if rt.host is not None:
                report.swap_in_pages = self._swap_progress(rt)
            if rt.chunk_fn is not None:
                chunks = sched.prefill_chunks(rt, len(dec))
        if chunks:
            report.prefill_tokens = self._run_chunks(rt, chunks)
        elif admitted and rt.chunk_fn is None:
            report.prefill_tokens = self._prefill_monolithic(rt, admitted)
        progressed = bool(dec or admitted or report.prefill_tokens
                          or report.swap_in_pages or report.swap_out_pages
                          or shed_now)
        if progressed:
            eng.quantum_log.append(report)
            tr = eng.tracer
            if tr.enabled("quantum"):
                tr.instant(
                    "quantum", rt.spec.priority, eng.clock(),
                    f"{eng._trace_prefix}quanta/{rt.spec.name}",
                    tenant=rt.spec.name, step=eng._step_idx,
                    decode_tokens=report.decode_tokens,
                    prefill_tokens=report.prefill_tokens,
                    budget=report.budget,
                    swap_in_pages=report.swap_in_pages,
                    swap_out_pages=report.swap_out_pages)
        return progressed

    def run_until_idle(self, max_steps: int = 100_000, horizon=None) -> int:
        eng = self.engine
        n = stall = 0
        while n < max_steps:
            if eng.step():
                n += 1
                stall = 0
                continue
            # under a fault plane a quantum may legitimately defer (alloc
            # window, swap backoff) — idle means no tenant has work, not
            # one workless step; the stall cap bounds a wedged storm
            if eng.faults is None or stall >= 10_000 \
                    or not any(rt.has_work() for rt in eng.tenants.values()):
                break
            stall += 1
        return n


class _SimBackend:
    """Drives the discrete-event contention simulator with the engine's
    request stream (pod-scale what-if: Figs. 5/6/11/12)."""

    def __init__(self, engine: "ServingEngine", device="tpu-v5e",
                 policy: str = "sgdrc"):
        self.engine = engine
        self.dev = GPU_DEVICES[device] if isinstance(device, str) else device
        self.policy_kind = policy
        self.result = None

    def add_tenant(self, rt: _TenantRT):
        pass   # kernel sequences are derived lazily from the request stream

    def quantum(self, rt: _TenantRT) -> bool:
        raise RuntimeError("sim backend executes via run_until_idle(horizon=)")

    def run_until_idle(self, max_steps: int = 100_000, horizon=None) -> int:
        eng = self.engine
        plan = eng.plan
        built = []
        t_max = 0.0
        for name, rt in eng.tenants.items():
            pending = sorted(rt.queue, key=lambda r: r.t_submit)
            arrivals = [r.t_submit for r in pending]
            if arrivals:
                t_max = max(t_max, arrivals[-1])
            # explicit sim_seq keeps the scenario's pure-prefill modeling
            # (fig12 etc.); stream-derived tenants split the request into a
            # prompt-sized prefill plus per-step decode kernels, so the
            # generated tokens are costed once, in the decode phase
            steps = 0
            if rt.sim_seq is not None:
                S = rt.sim_seq
            elif pending:
                S = max(len(pending[0].tokens), 1)
                steps = pending[0].max_new
            else:
                S = eng.max_seq
            B = max(1, rt.spec.batch_size)
            # prefix-cache: replay the stream through a token-only radix
            # tree to estimate the mean cached-prefix length — the cost
            # model then charges prefill traffic only for the uncached
            # suffix (the bandwidth the sharing returns to the budget)
            prefix_est = 0
            if eng.prefix_cache and pending and rt.sim_seq is None:
                est = PrefixCache(eng.page_size)
                seen = []
                for r in pending:
                    seen.append(min(est.match_len(r.tokens),
                                    max(len(r.tokens) - 1, 0)))
                    est.insert_tokens(r.tokens)
                prefix_est = int(np.mean(seen)) if seen else 0
            # chunked-prefill modeling: with a chunk_size the prefill phase
            # becomes one kernel per chunk (the simulator's preemption
            # boundary, like the engine's quanta) and the cost model
            # charges the per-chunk KV re-read + weight re-read tax
            kern = request_kernels(rt.cfg, B, S, "prefill", self.dev,
                                   rt.max_kernels, prefix=prefix_est,
                                   chunk=eng.chunk_size,
                                   tile=(eng.preempt_tile
                                         if not rt.spec.is_ls else None))
            n_prefill_k = len(kern)
            # decode phase carries the KV-cache *write* traffic of the
            # engine's actual decode path — paged appends are O(tokens);
            # whole-row mask-scatter rewrites the window. Kept at (chunked)
            # step granularity so the simulator can still preempt/readmit
            # at decode-step boundaries, like the real engine's quanta.
            if steps > 0:
                dec = request_kernels(
                    rt.cfg, B, S + steps, "decode", self.dev,
                    rt.max_kernels,
                    kv_write="paged" if eng.paged else "scatter")
                f = sum(k.flops for k in dec)
                b = sum(k.bytes for k in dec)
                n_chunks = min(steps, max(1, rt.max_kernels))
                per = steps / n_chunks
                step_k = Kernel(f * per, b * per,
                                b / self.dev.hbm_bw > f / self.dev.peak_flops)
                kern = kern + [step_k] * n_chunks
            if rt.sim_swap_bytes > 0:
                # KV swap traffic modeled as one memory-bound kernel at the
                # resume point (right after prefill): with coloring on, its
                # bytes drain at the owning class's ch_be bandwidth split,
                # so BE swap storms never stretch LS decode gaps
                kern = (kern[:n_prefill_k]
                        + [Kernel(0.0, float(rt.sim_swap_bytes), True)]
                        + kern[n_prefill_k:])
            tn = Tenant(name, rt.spec.priority, kern,
                        arrivals=arrivals or None,
                        closed_loop=rt.closed_loop,
                        prefill_kernels=n_prefill_k if steps > 0 else None)
            built.append((rt, pending, tn))
        if horizon is None:
            horizon = t_max * 1.05 + 1.0
        sm_be = plan.sm_be if plan is not None else ComputePolicy().sm_be
        policy = ComputePolicy(kind=self.policy_kind, sm_be=sm_be)
        sim = GPUSimulator(self.dev, policy, coloring=eng.coloring,
                           ch_be=eng.ch_be, controller=eng.controller,
                           control_dt=eng.control_dt,
                           migration_bytes=eng.migration_bytes,
                           faults=eng.faults,
                           tracer=(eng.tracer if eng.tracer.level >= 0
                                   else None))
        res = sim.run([tn for _, _, tn in built], horizon)
        eng.migrated_bytes += sim.migrated_bytes
        total = 0
        for rt, pending, tn in built:
            if tn.closed_loop:
                rt.sim_completed = tn.completed
                total += tn.completed
                continue
            for req, lat in zip(pending, tn.latencies):
                req.t_done = req.t_submit + lat
                req.output = []
                rt.done.append(req)
                rt.queue.remove(req)
                eng._trace_done(rt, req)
                total += 1
        self.result = res
        eng.sim_result = res
        # virtual timelines all start at t=0, so across repeated drains the
        # widest horizon is the serving window metrics() divides by
        eng._elapsed = max(eng._elapsed or 0.0, res.horizon)
        return total


class ServingEngine:
    """One engine, two backends. See module docstring.

    Parameters of note:
      plan         ResourcePlan from ``controller.grid_search``; sets the BE
                   quantum share (sm_be) and the channel split (ch_be).
      backend      "jax" (real execution, continuous batching) | "sim"
                   (contention simulator; pass arrival times via submit(at=)).
      slots_ls/be  decode-slot pool size per tenant class (JAX backend).
      paged        page-table KV admission (PagedKVCache) instead of
                   whole-row slots; with coloring, page pools are carved
                   from the tenant class's arena channel set.
      page_size    tokens per KV page (paged mode).
      kv_pages     page-pool size override per tenant (default: dense-row
                   capacity equivalent, or the arena class capacity).
      grow_pages   dynamic page growth: admit on ``ceil(prompt/page_size)``
                   pages only and allocate decode pages at page-boundary
                   crossings; on pool exhaustion the youngest other active
                   request is preempted (or swapped out, with ``swap``)
                   instead of the admission failing.
      swap         host KV tier over the PCIe bus: preempted decode page
                   groups and evicted prefix-tree leaves move to a
                   per-tenant HostSwapPool instead of being discarded, and
                   fault back in (a SWAPPED request re-admits into the
                   SWAPPING phase; cold prefix pages re-adopt before
                   planning).
      cold_dtype   host-tier storage: "int8" (per-page abs-max scale,
                   ~2-4x less host memory + bus traffic, bounded
                   dequantization error) or "fp16" (native pool dtype,
                   exact — swapped tokens stay bit-equal).
      swap_quantum_pages  max host pages faulted back per engine quantum
                   (paces swap-in next to live decode).
      use_flash    route decode attention through the ragged Pallas
                   flash-decode kernel (interpret mode off-TPU).
      chunk_size   max prefill tokens a request advances per quantum
                   (serving.scheduler): a long prompt prefills across
                   several quanta while decode keeps ticking, bounding the
                   TBT spike a monolithic co-located prefill inflicts.
                   None = whole prompt per quantum (still through the
                   cached-context chunk path for chunkable models).
      token_budget per-class per-quantum token cap: decode tokens first,
                   prefill chunks fill the remainder.
      hit_aware    admission orders the waiting queue by predicted
                   prefix-cache hit size (ties FIFO) — hits admit first
                   under pool pressure.
      seed         tie-break seed for deterministic tenant ordering.
      device       DeviceSpec or name for the sim backend.
      policy       ComputePolicy kind for the sim backend.
      faults       serving.faults.FaultPlane: seeded, deterministic fault
                   injection at the GPU / PCIe / host-tier / controller
                   seams (both backends; see the faults module docstring).
      fault_recovery  master switch for the graceful-degradation paths —
                   deadline shedding, swap retry+backoff, controller
                   watchdog, cold-page checksum verify, degradation
                   ladder. False is the naive ablation chaos_bench
                   measures against.
      fault_budget recoveries per degradation-ladder rung (per tenant).
      max_queue    per-tenant submit backpressure bound (excess rejects).
      swap_retry_limit  swap-in retries before preempt-restart.
      deadlock_patience  consecutive victimless growth stalls before a
                   grow_deadlock is declared (and, for BE under recovery,
                   the youngest active request shed) — one stall is
                   usually just a swap-in mid-flight.
      watchdog_quanta   LS-starvation window before the safe-plan snap
                   (default: 4 control intervals when faults+controller
                   are both present, else disabled).
      safe_plan    explicit watchdog target (default: the frontier's most
                   conservative entry, else faults.safe_floor(plan)).
    """

    def __init__(self, max_seq: int = 128, *, backend: str = "jax",
                 plan: Optional[ResourcePlan] = None, coloring: bool = False,
                 ch_be: float = 1 / 3, arena_bytes: int = 64 << 20,
                 hash_model=None, now_fn=None, slots_ls: int = 4,
                 slots_be: int = 4, paged: bool = False, page_size: int = 8,
                 kv_pages: Optional[int] = None, use_flash: bool = False,
                 chunk_size: Optional[int] = None,
                 token_budget: Optional[int] = None, hit_aware: bool = True,
                 device="tpu-v5e", policy: str = "sgdrc",
                 controller=None, control_interval: int = 4,
                 control_dt: float = 0.02, prefix_cache: bool = False,
                 prefix_min_hit: float = 0.0,
                 migration_bytes: float = 0.0, seed: int = 0,
                 grow_pages: bool = False, swap: bool = False,
                 cold_dtype: str = "int8", swap_quantum_pages: int = 4,
                 faults: Optional[FaultPlane] = None,
                 fault_recovery: bool = True, fault_budget: int = 8,
                 max_queue: int = 4096, swap_retry_limit: int = 3,
                 deadlock_patience: int = 8,
                 watchdog_quanta: Optional[int] = None,
                 safe_plan: Optional[ResourcePlan] = None,
                 tracer=None, trace_name: str = "",
                 preempt_tile: Optional[int] = None,
                 arrival_hook=None, chunk_governor=None):
        self.max_seq = max_seq
        # telemetry plane (repro.obs): the engine always owns a tracer so
        # emission sites stay branch-free; the default level-"off" tracer
        # drops everything, which is what keeps untraced runs trivially
        # bit-equal to traced ones (tracing is pure observation). All
        # timestamps come from self.clock — never wall time directly.
        self.tracer = tracer if tracer is not None else obs.Tracer("off",
                                                                   ring=1)
        self._trace_prefix = f"{trace_name}/" if trace_name else ""
        self.registry = obs.MetricsRegistry()
        self.paged = paged
        self.page_size = page_size
        self.kv_pages = kv_pages
        self.use_flash = use_flash
        self.chunk_size = chunk_size
        # KV memory hierarchy: grow_pages admits on the prompt's pages only
        # and allocates decode pages at boundary crossings (preempting the
        # youngest request on exhaustion); swap adds the host tier — victims'
        # page groups and evicted prefix leaves move over the PCIe bus
        # instead of dying, stored per cold_dtype ("int8" quantized with a
        # per-page scale, "fp16" exact native-dtype passthrough) and faulted
        # back at most swap_quantum_pages per quantum
        if (grow_pages or swap) and backend == "jax" and not paged:
            raise ValueError("grow_pages/swap require paged=True")
        self.grow_pages = grow_pages
        self.swap = swap
        assert cold_dtype in ("int8", "fp16"), cold_dtype
        self.cold_dtype = cold_dtype
        self.swap_quantum_pages = max(int(swap_quantum_pages), 1)
        # construction-time default the tidal controller restores when a
        # plan stops carrying a swap_quantum_pages override (apply_plan)
        self._default_swap_quantum = self.swap_quantum_pages
        # disaggregation seams (serving.disagg): chunk_hook(rt, req) fires
        # after each mid-prompt chunk commits (layer-pipelined KV page-group
        # streaming overlaps the remaining prefill); migrate_hook(rt, req)
        # fires when prefill completes on a still-live request and returns
        # True to take the slot (the request leaves this engine)
        self.chunk_hook = None
        self.migrate_hook = None
        # sub-chunk preemption (kernel latency floor): BE prefill chunks
        # split into tiles of at most preempt_tile tokens, with a
        # preemption point per tile — on LS arrival mid-quantum the
        # remaining tiles abort and LS admits in the same quantum.
        # arrival_hook(n_tokens) fires after every executed prefill wave
        # and decode batch (benches drive a virtual token clock with it);
        # preempt_hook (attribute) overrides the LS-waiting predicate for
        # tests (always/never/seeded-random preemption patterns).
        self.preempt_tile = (None if not preempt_tile
                             else max(int(preempt_tile), 1))
        self.arrival_hook = arrival_hook
        self.preempt_hook = None
        # logits_hook(rt, req, logits) (attribute) observes the [V] device
        # logits row behind every token a request emits: the seeding
        # prefill's row for its first token, then one per decode step
        self.logits_hook = None
        self.preempt_aborts = 0
        self.preempt_waits: List[float] = []
        self._aborted_rids: set = set()
        # SLO-driven chunk sizing: a ChunkGovernor rides the control tick
        # and retunes chunk_size/prefill_budget from the windowed LS TBT
        # p99 (cause "chunk_adapt" in the transition log)
        self.chunk_governor = chunk_governor
        # radix-tree copy-on-write KV page sharing (serving.prefix_cache):
        # common prompt prefixes map cached pages into new slots' tables and
        # only the uncached suffix is prefilled
        if prefix_cache and backend == "jax" and not paged:
            raise ValueError("prefix_cache=True requires paged=True")
        self.prefix_cache = prefix_cache
        # minimum hit fraction to use a match: 0 since the suffix replay is
        # a batched cached-context prefill (any full-page hit pays off; the
        # old one-token-per-step replay justified a 12.5% floor)
        self.prefix_min_hit = prefix_min_hit
        # phase-aware chunked-prefill token-budget scheduler: owns
        # admission order and per-quantum chunk composition
        self.scheduler = TokenBudgetScheduler(
            chunk_size=chunk_size, budget_ls=token_budget,
            budget_be=token_budget,
            prefill_budget_be=(plan.prefill_budget
                               if plan is not None else None),
            hit_aware=hit_aware, prefix_min_hit=prefix_min_hit)
        self.quantum_log: List[QuantumReport] = []
        # resplit-aware migration costing: jax backend accumulates the
        # arena's actual moved-page bytes; the sim backend charges
        # migration_bytes * |Δch_be| of memory-system stall per transition
        self.migration_bytes = migration_bytes
        self.migrated_bytes = 0
        self.tenants: Dict[str, _TenantRT] = {}
        self.clock = now_fn or time.perf_counter
        self._t0 = self.clock()     # epoch for sim-backend virtual arrivals
        self._rid = 0
        self.plan = plan
        self.coloring = coloring
        self.ch_be = plan.ch_be if plan is not None else ch_be
        # BE quantum share: fraction of engine quanta BE receives while LS
        # work is pending (None/0 -> strict LS priority, the seed behaviour)
        self.sm_be = plan.sm_be if plan is not None else 0.0
        self._be_credit = 0.0
        # online control plane (module docstring): a decide()-bearing
        # controller makes the plan time-varying at step boundaries
        self.controller = controller
        self.control_interval = max(int(control_interval), 1)
        self.control_dt = control_dt
        # chaos plane (serving.faults): an attached FaultPlane injects at
        # the seams above; fault_recovery gates every graceful-degradation
        # path at once (off = the naive ablation: blind retries, no
        # watchdog, no shedding, unverified cold pages). fault_budget is
        # recoveries-per-rung of the degradation ladder; watchdog_quanta
        # defaults to 4 control intervals when a controller rides next to
        # a fault plane and stays off otherwise.
        self.faults = faults
        if faults is not None and tracer is not None:
            faults.tracer = self.tracer
        self.fault_recovery = fault_recovery
        self.fault_budget = max(int(fault_budget), 1)
        self.max_queue = max(int(max_queue), 1)
        self.swap_retry_limit = max(int(swap_retry_limit), 0)
        self.deadlock_patience = max(int(deadlock_patience), 1)
        if (watchdog_quanta is None and faults is not None
                and controller is not None and fault_recovery):
            watchdog_quanta = 4 * self.control_interval
        self.watchdog_quanta = watchdog_quanta
        self.safe_plan = safe_plan
        self.watchdog_trips = 0
        self.missed_ticks = 0
        self.stale_signals = 0
        self._stale_sig = None
        self._last_ls_step: Optional[int] = None
        self._ls_work_since: Optional[int] = None
        self.transitions: List[dict] = []
        self._applied_plan = None
        self._last_ctl_step: Optional[int] = None
        self._ctl_done_idx: Dict[str, int] = {}
        self._last_window = None
        self.slots_ls, self.slots_be = slots_ls, slots_be
        self.events: List[tuple] = []   # (quantum_idx, tenant, class)
        # deterministic tenant tie-breaking: ranks drawn from a seeded rng
        # at add_tenant, so equal-arrival picks are stable across runs
        self._tie_rng = np.random.default_rng(seed)
        self._tie_rank: Dict[str, float] = {}
        self._ctl_tbt_idx: Dict[str, int] = {}
        self._step_idx = 0
        self.sim_result = None
        self._elapsed = None
        self.arena = None
        if backend == "jax":
            self.backend = _JaxBackend(self)
        elif backend == "sim":
            self.backend = _SimBackend(self, device=device, policy=policy)
        else:
            raise ValueError(f"unknown backend {backend!r}")
        self.backend_name = backend
        if coloring and backend == "jax":
            assert hash_model is not None
            self.arena = ColoredArena(arena_bytes, hash_model.channel_of,
                                      hash_model.num_channels,
                                      hash_model.granularity)
            self.ls_ch, self.be_ch = split_channels(
                hash_model.num_channels, self.ch_be)

    # ------------------------------------------------------------------
    def add_tenant(self, spec: TenantSpec, cfg: ModelConfig, params=None,
                   key=None, n_slots: Optional[int] = None,
                   closed_loop: bool = False, sim_seq: Optional[int] = None,
                   max_kernels: int = 24, sim_swap_bytes: int = 0):
        if params is None and self.backend_name == "jax":
            params = tf.init_params(
                key if key is not None
                else name_key(spec.name), cfg)
        n_slots = n_slots or (self.slots_ls if spec.is_ls else self.slots_be)
        row_bytes = chans = None
        if self.arena is not None:
            chans = self.ls_ch if spec.is_ls else self.be_ch
            if not self.paged:
                # whole-row admission: the arena must hold one dense
                # [max_seq] KV row per slot — cap the pool to what the
                # class's colored bytes actually fit (paged mode instead
                # allocates per-request page groups at admission)
                row_bytes = kv_bytes_per_token(cfg) * self.max_seq
                cap = (self.arena.free_pages(chans) * self.arena.granularity
                       // max(row_bytes, 1))
                if cap < 1:
                    raise OutOfColoredMemory(
                        f"{spec.name}: arena cannot hold one KV row")
                n_slots = min(n_slots, int(cap))
        rt = _TenantRT(spec, cfg, params, decode_fn=None, prefill_fn=None,
                       n_slots=n_slots,
                       closed_loop=closed_loop, sim_seq=sim_seq,
                       max_kernels=max_kernels, sim_swap_bytes=sim_swap_bytes)
        self.backend.add_tenant(rt)
        self._tie_rank[spec.name] = float(self._tie_rng.random())
        if self.arena is not None and not self.paged:
            # SSM-state tenants have no attention KV; keep a nonzero slice
            # so their placement is still tracked/colored
            self.arena.alloc(spec.name,
                             max(row_bytes * rt.n_slots, 1024), chans)
            rt.alloc_name = spec.name
        self.tenants[spec.name] = rt
        return rt

    def submit(self, tenant: str, tokens, max_new: int = 8, at=None,
               deadline: Optional[float] = None):
        """Queue a request. ``at`` overrides the submit timestamp (virtual
        arrival time for the sim backend's scenario traces). Sim-backend
        submissions without ``at`` default to engine-epoch-relative time, so
        the simulated horizon starts near t=0 rather than at the process
        uptime perf_counter() reports.

        ``deadline`` is in clock units after submit: an expired request
        still WAITING/SWAPPED is load-shed instead of served late (chaos
        recovery; no-op when ``fault_recovery`` is off). Malformed input
        raises (unknown tenant: KeyError; empty / non-1-D prompt:
        ValueError); an oversized prompt (real-execution backend only —
        the sim backend cost-models arbitrary shapes) or a full per-tenant
        queue (``max_queue``) is *rejected* — the request finishes immediately
        with ``failed=rejected=True`` and counts in ``rt.rejected`` —
        backpressure instead of a poisoned batch."""
        if tenant not in self.tenants:
            raise KeyError(f"unknown tenant {tenant!r}")
        rt = self.tenants[tenant]
        toks = np.asarray(tokens, np.int32)
        if toks.ndim != 1 or toks.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        self._rid += 1
        if at is not None:
            t = float(at)
        elif self.backend_name == "sim":
            t = self.clock() - self._t0
        else:
            t = self.clock()
        req = Request(self._rid, tenant, toks, max_new, t,
                      deadline=(t + float(deadline)
                                if deadline is not None else None))
        # the sim backend cost-models arbitrary prompt shapes (paper-scale
        # scenarios) without allocating cache rows, so the max_seq bound
        # only protects the real-execution backend
        oversize = toks.size > self.max_seq and self.backend_name != "sim"
        if oversize or len(rt.queue) >= self.max_queue:
            req.failed = req.rejected = True
            req.phase = Phase.FINISHED
            req.t_done = t
            req.output = []
            rt.rejected += 1
            rt.done.append(req)
            self._trace_done(rt, req)
            return req
        rt.queue.append(req)
        self.tracer.instant("request", "submit", t,
                            f"{self._trace_prefix}slo", rid=req.rid,
                            tenant=tenant, prompt_len=int(toks.size),
                            max_new=int(max_new))
        return req

    # -- telemetry plane (repro.obs) ------------------------------------
    # Per-slot tracks give LIFO B/E nesting (request span wraps phase
    # spans); instants land on shared class tracks. All guarded by the
    # tracer's level so the "off" default costs one comparison per seam.
    def _tr_track(self, rt, slot) -> str:
        return f"{self._trace_prefix}{rt.spec.name}/slot{slot}"

    def _trace_enter(self, rt, req, phase_name: str):
        """Request admitted to a slot: open request + first phase spans."""
        tr = self.tracer
        if not tr.enabled("phase"):
            return
        t, track = self.clock(), self._tr_track(rt, req.slot)
        tr.begin("request", f"r{req.rid}", t, track, rid=req.rid,
                 tenant=rt.spec.name)
        tr.begin("phase", phase_name, t, track, rid=req.rid)

    def _trace_phase(self, rt, req, old: str, new: str):
        tr = self.tracer
        if not tr.enabled("phase"):
            return
        t, track = self.clock(), self._tr_track(rt, req.slot)
        tr.end("phase", old, t, track, rid=req.rid)
        tr.begin("phase", new, t, track, rid=req.rid)

    def _trace_leave(self, rt, req, slot, phase_name: str, outcome: str):
        """Request leaves its slot (finish/preempt/swap-out/shed/migrate):
        close the open phase and request spans."""
        tr = self.tracer
        if not tr.enabled("phase") or slot is None:
            return
        t, track = self.clock(), self._tr_track(rt, slot)
        tr.end("phase", phase_name, t, track, rid=req.rid)
        tr.end("request", f"r{req.rid}", t, track, rid=req.rid,
               outcome=outcome)

    def _trace_done(self, rt, req):
        """Terminal accounting instant with the SLO verdict: ``ok`` is
        True/False against ``spec.slo_ms`` (milliseconds) or, failing that,
        the request's own ``deadline`` (clock units); None when the request
        carries no SLO at all. Violations additionally emit a
        ``violation`` instant — the SLOTimeline's attribution anchor."""
        tr = self.tracer
        if not tr.enabled("request"):
            return
        t = req.t_done if req.t_done is not None else self.clock()
        lat = req.latency
        slo = rt.spec.slo_ms
        has_slo = slo is not None or req.deadline is not None
        if req.failed:
            ok = False if has_slo else None
        elif slo is not None and lat is not None:
            ok = bool(lat * 1e3 <= slo)
        elif req.deadline is not None:
            ok = bool(t <= req.deadline)
        else:
            ok = None
        lat_ms = lat * 1e3 if lat is not None else None
        track = f"{self._trace_prefix}slo"
        tr.instant("request", "done", t, track, rid=req.rid,
                   tenant=rt.spec.name, cls=rt.spec.priority, ok=ok,
                   latency_ms=lat_ms, t_submit=req.t_submit,
                   shed=req.shed, rejected=req.rejected)
        if ok is False:
            tr.instant("violation", "slo", t, track, rid=req.rid,
                       tenant=rt.spec.name, latency_ms=lat_ms,
                       t_submit=req.t_submit)

    # -- online control plane ------------------------------------------
    def _load_signal(self):
        """LoadSignal over the window since the last control tick, with the
        window's LS latency split into its phases: p99 TTFT (admission +
        prefill) and p99 TBT (inter-token gaps) next to the end-to-end SLO
        attainment."""
        from ..core.compute import LoadSignal
        q = a = slots = slo_ok = slo_n = 0
        ttfts, gaps = [], []
        for name, rt in self.tenants.items():
            if not rt.spec.is_ls:
                continue
            q += len(rt.queue)
            a += sum(r is not None for r in rt.active)
            slots += rt.n_slots
            i0 = self._ctl_done_idx.get(name, 0)
            self._ctl_done_idx[name] = len(rt.done)
            g0 = self._ctl_tbt_idx.get(name, 0)
            self._ctl_tbt_idx[name] = len(rt.tbt_gaps)
            gaps += rt.tbt_gaps[g0:]
            for r in rt.done[i0:]:
                if r.failed or r.latency is None:
                    continue
                if r.ttft is not None:
                    ttfts.append(r.ttft)
                if rt.spec.slo_ms is not None:
                    slo_n += 1
                    slo_ok += r.latency * 1e3 <= rt.spec.slo_ms
        # the window's samples flow through the registry's histograms and
        # the p99s are read back out of them (nearest-rank over log-linear
        # buckets, see repro.obs.metrics), so the controller consumes the
        # same numbers metrics() reports instead of a parallel computation
        reg = self.registry
        h_ttft = reg.histogram("ls_ttft_ms")
        h_tbt = reg.histogram("ls_tbt_ms")
        for v in ttfts:
            h_ttft.record(v * 1e3)
        for v in gaps:
            h_tbt.record(v * 1e3)
        if slo_n:
            reg.gauge("ls_slo_attainment").set(slo_ok / slo_n)
        sig = LoadSignal(ls_queued=q, ls_active=a, ls_slots=max(slots, 1),
                         ls_slo_attainment=(slo_ok / slo_n) if slo_n
                         else None,
                         ls_ttft_p99_ms=h_ttft.percentile(99, window=True),
                         ls_tbt_p99_ms=h_tbt.percentile(99, window=True))
        reg.gauge("ls_load").set(sig.ls_load)
        reg.tick()   # close the control window
        return sig

    def _maybe_control(self):
        """Consult the controller at the quantum boundary: every
        ``control_interval`` quanta, plus out-of-band whenever LS work shows
        up under a full-lending plan (the bounded tidal snap-back)."""
        due = (self._last_ctl_step is None
               or self._step_idx - self._last_ctl_step
               >= self.control_interval)
        if not due and self.sm_be >= 1.0:
            due = any(rt.spec.is_ls and rt.has_work()
                      for rt in self.tenants.values())
        if not due:
            return
        with obs.span("control"):
            self._control_tick()

    def _control_tick(self):
        """One control tick: read the window's load signal, then adopt the
        controller's plan (or drain off-color pages left by an earlier
        one)."""
        self._last_ctl_step = self._step_idx
        now = self.clock()
        if (self.faults is not None
                and self.faults.active("ctl_missed_tick", now) is not None):
            # control-plane fault: the tick is dropped on the floor — the
            # previous plan stays in force and the step() watchdog is the
            # backstop that re-asserts the LS guarantee
            self.missed_ticks += 1
            return
        sig = self._load_signal()
        # live prefix-hit feedback as a windowed gauge: the timeline can
        # show hit-rate against plan transitions (re-planning from it is
        # still future work — see ROADMAP "Telemetry & attribution")
        hit = measured_prefix_hit(self)
        self.registry.gauge("measured_prefix_hit").set(hit)
        tr = self.tracer
        if tr.enabled("gauge"):
            sig_track = f"{self._trace_prefix}signals"
            tr.counter("ls_load", now, sig.ls_load, track=sig_track)
            if sig.ls_slo_attainment is not None:
                tr.counter("ls_slo_attainment", now, sig.ls_slo_attainment,
                           track=sig_track)
            tr.counter("measured_prefix_hit", now, hit, track=sig_track)
        if (self.faults is not None
                and self.faults.active("ctl_stale_signal", now) is not None):
            # stale telemetry: the controller decides on the last healthy
            # window's signal instead of the current one
            self.stale_signals += 1
            if self._stale_sig is not None:
                sig = self._stale_sig
        else:
            self._stale_sig = sig
        if self.chunk_governor is not None:
            self._govern_chunks(sig, now)
        if self.controller is None:
            return
        plan = self.controller.decide(sig, t=float(self._step_idx))
        if plan is not self._applied_plan:
            cause = getattr(self.controller, "last_cause", None)
            if cause is None:
                cause = "initial" if self._applied_plan is None else "replan"
            self.apply_plan(plan, cause=cause)
        elif self.arena is not None:
            # drain leftover off-color pages from an earlier partial
            # migration (BE groups still borrowing LS channels) — but never
            # a pinned shared group: a prefix-tree page another slot's page
            # table still references stays put until its refs drop, then
            # drains to the current color here
            pinned = set()
            debt = {}
            for rt in self.tenants.values():
                if rt.prefix is not None:
                    pinned.update(rt.prefix.pinned_names())
                    debt.update(rt.prefix.drain_recolor())
            debt.update({n: a.channels
                         for n, a in self.arena.allocations.items()
                         if n not in pinned and n not in debt
                         and self.arena.isolation_violations(a)})
            if debt:
                self.arena.resplit(debt, pinned=pinned)
                self.migrated_bytes += self.arena.last_resplit["bytes"]

    def _govern_chunks(self, sig, now: float):
        """SLO-driven chunk sizing: feed the window's LS TBT p99 (the same
        registry histogram the controller reads) to the ChunkGovernor and
        adopt its decision — chunk_size plus the derived BE prefill budget
        — logged as a ``chunk_adapt`` transition next to plan moves."""
        decision = self.chunk_governor.update(sig.ls_tbt_p99_ms)
        if decision is None:
            return
        chunk, budget = decision
        self.chunk_size = chunk
        self.scheduler.chunk_size = chunk
        self.scheduler.set_prefill_budget(budget)
        self.transitions.append({"step": self._step_idx,
                                 "sm_be": float(self.sm_be),
                                 "ch_be": float(self.ch_be),
                                 "pages_moved": 0, "bytes_moved": 0,
                                 "pinned_groups": 0,
                                 "chunk_size": int(chunk),
                                 "prefill_budget": int(budget),
                                 "cause": "chunk_adapt"})
        self.tracer.instant("plan", "chunk_adapt", now,
                            f"{self._trace_prefix}plan",
                            sm_be=float(self.sm_be),
                            ch_be=float(self.ch_be),
                            chunk_size=int(chunk),
                            prefill_budget=int(budget),
                            step=self._step_idx)

    def _channel_sets(self, ch_be: float):
        """Engine-local channel sets for a plan's ``ch_be`` (the plan's own
        sets were drawn for the *controller's* DeviceSpec, whose channel
        count may differ from the hash model's). ``ch_be >= 1`` is the
        lending plan: BE may borrow every channel while LS keeps its
        assignment, so snap-back never migrates LS pages."""
        C = self.arena.num_channels
        if ch_be >= 1.0 - 1e-9:
            return self.ls_ch, tuple(range(C))
        return split_channels(C, ch_be)

    def apply_plan(self, plan: ResourcePlan, cause: str = "replan"):
        """Adopt a ResourcePlan at a step boundary: the BE quantum share
        moves immediately; a ``ch_be`` move resplits the arena (off-color
        pages migrate to the new sets) and recolors every KV page pool so
        future page groups land on the new split. Device pools and page
        tables are untouched — a mid-run plan change never alters tokens.

        Prefix-tree node groups whose pages are still referenced by a live
        page table are *pinned* out of the resplit (they drain later via
        :meth:`_maybe_control`); the migration's moved bytes are charged to
        the window's traffic budget (``migrated_bytes``), not treated as
        free bookkeeping."""
        prev = self._applied_plan
        self.sm_be = plan.sm_be
        # prefill-budget knob: tidal re-planning throttles BE prefill
        # tokens per quantum, not only BE's SM share
        self.scheduler.set_prefill_budget(
            getattr(plan, "prefill_budget", None))
        # swap-aware knob: a contended plan throttles BE host-tier fault
        # bandwidth (pages per quantum) together with sm_be/ch_be; a plan
        # without the knob restores the construction-time default
        sq = getattr(plan, "swap_quantum_pages", None)
        self.swap_quantum_pages = (self._default_swap_quantum if sq is None
                                   else max(int(sq), 1))
        moved = 0
        pinned = []
        if self.arena is not None and (prev is None
                                       or plan.ch_be != prev.ch_be):
            new_ls, new_be = self._channel_sets(plan.ch_be)
            mapping = {}
            for rt in self.tenants.values():
                chans = new_ls if rt.spec.is_ls else new_be
                if rt.kv is not None:
                    mapping.update(rt.kv.recolor(chans))
                    if rt.prefix is not None:
                        mapping.update(rt.prefix.recolor(chans))
                        pinned += rt.prefix.pinned_names()
                elif rt.alloc_name is not None:
                    mapping[rt.alloc_name] = chans
            self.ls_ch, self.be_ch = new_ls, new_be
            moved = sum(self.arena.resplit(mapping, pinned=pinned).values())
            self.migrated_bytes += self.arena.last_resplit["bytes"]
        self._applied_plan = plan
        self.transitions.append({"step": self._step_idx,
                                 "sm_be": plan.sm_be, "ch_be": plan.ch_be,
                                 "pages_moved": int(moved),
                                 "bytes_moved": int(
                                     moved * (self.arena.granularity
                                              if self.arena else 0)),
                                 "pinned_groups": len(pinned),
                                 "cause": cause})
        self.tracer.instant("plan", cause, self.clock(),
                            f"{self._trace_prefix}plan",
                            sm_be=float(plan.sm_be),
                            ch_be=float(plan.ch_be),
                            pages_moved=int(moved), step=self._step_idx)

    def _safe_plan(self) -> Optional[ResourcePlan]:
        """The conservative plan the watchdog snaps to: an explicit
        ``safe_plan`` wins; else the controller frontier's most conservative
        entry; else the current plan clamped to the hard floor
        (``faults.safe_floor``)."""
        if self.safe_plan is not None:
            return self.safe_plan
        fr = getattr(self.controller, "frontier", None)
        if fr is not None and getattr(fr, "entries", None):
            return fr.entries[-1][1]
        base = self._applied_plan or self.plan
        return safe_floor(base) if base is not None else None

    def _watchdog(self, ls_work: bool):
        """Controller watchdog (chaos recovery): if LS has had work for
        ``watchdog_quanta`` consecutive steps without a single LS quantum
        executing, while the live plan is more generous to BE than the safe
        plan, snap to the safe plan immediately. This bounds the damage of
        a wedged/stale controller to one watchdog window instead of letting
        a full-lending plan starve LS for the rest of the run."""
        if not ls_work:
            self._ls_work_since = None
            return
        if self._ls_work_since is None:
            self._ls_work_since = self._step_idx
        anchor = self._ls_work_since
        if self._last_ls_step is not None:
            anchor = max(anchor, self._last_ls_step)
        if self._step_idx - anchor < self.watchdog_quanta:
            return
        safe = self._safe_plan()
        if safe is None or self.sm_be <= safe.sm_be + 1e-9:
            # already at (or below) the safe share: nothing to snap; re-arm
            self._last_ls_step = self._step_idx
            return
        self.apply_plan(safe, cause="watchdog")
        self.transitions[-1]["watchdog"] = True
        self.watchdog_trips += 1
        self.tracer.instant("recovery", "watchdog", self.clock(),
                            f"{self._trace_prefix}recovery",
                            step=self._step_idx)
        self._last_ls_step = self._step_idx

    # ------------------------------------------------------------------
    def _pick(self, rts: List[_TenantRT]) -> List[_TenantRT]:
        """Earliest outstanding request first (FIFO across tenants), ties
        broken by each tenant's seeded rank (deterministic across runs —
        the old closure key left equal-arrival ordering to sort stability
        over dict insertion order)."""
        return sorted(rts, key=lambda rt: (_earliest_outstanding(rt),
                                           self._tie_rank[rt.spec.name]))

    def step(self) -> bool:
        """One engine quantum (JAX backend): choose a tenant class via the
        plan's BE quantum share, then run one batched prefill-or-decode
        quantum for one tenant of that class. LS strictly preempts BE at
        this boundary when no plan grants BE a share. With an online
        controller attached this boundary is also where re-plans land."""
        with obs.span("step", step=self._step_idx):
            return self._step()

    def _step(self) -> bool:
        if (self.controller is not None or self.chunk_governor is not None) \
                and self.backend_name == "jax":
            self._maybe_control()
        ls = [rt for rt in self.tenants.values()
              if rt.spec.is_ls and rt.has_work()]
        be = [rt for rt in self.tenants.values()
              if not rt.spec.is_ls and rt.has_work()]
        if (self.watchdog_quanta and self.fault_recovery
                and self.backend_name == "jax"):
            self._watchdog(bool(ls))
        if ls and be and self.sm_be > 0:
            # deficit counter: BE receives sm_be of contended quanta
            self._be_credit += self.sm_be
            if self._be_credit >= 1.0:
                self._be_credit -= 1.0
                pick = be
            else:
                pick = ls
        elif ls:
            pick = ls
        elif be:
            pick = be   # resource lending: BE runs at full rate when LS idles
        else:
            return False
        other = be if pick is ls else ls
        # a tenant whose queue head is blocked (paged mode: waiting on KV
        # pages another tenant holds) must not strand the rest: fall through
        # to the next tenant of the class, then to the other class
        for rt in self._pick(pick) + self._pick(other):
            if self.backend.quantum(rt):
                if rt.spec.is_ls:
                    self._last_ls_step = self._step_idx
                self.events.append((self._step_idx,
                                    rt.spec.name, rt.spec.priority))
                self._step_idx += 1
                return True
        # a workless or fully-deferred step still advances the quantum
        # index: swap retry backoffs and the watchdog window are measured
        # in _step_idx, and freezing it during a stall would turn a
        # transient fault window into a permanent wedge
        self._step_idx += 1
        return False

    def _class_counts(self):
        c = {"LS": [0, 0], "BE": [0, 0]}       # [completed, tokens]
        for rt in self.tenants.values():
            served = [r for r in rt.done if not r.failed]
            c[rt.spec.priority][0] += len(served) + rt.sim_completed
            c[rt.spec.priority][1] += sum(len(r.output or ()) for r in served)
        return c

    def run_until_idle(self, max_steps: int = 100_000, horizon=None) -> int:
        """JAX backend: run quanta until no tenant has work (returns #quanta).
        Sim backend: build tenants from the submitted stream, run the
        simulator over ``horizon`` and write completions back (returns
        #completed requests; the raw SimResult lands in ``self.sim_result``).

        Each call is one serving *window*: per-window rates land in
        ``metrics()['_window']``, next to the cumulative rollup (whose
        denominator spans every window — across repeated drains the
        cumulative ``throughput_rps`` mixes windows, so window rates are
        the honest per-run signal)."""
        t0 = self.clock()
        before = self._class_counts()
        mig0 = self.migrated_bytes
        n = self.backend.run_until_idle(max_steps=max_steps, horizon=horizon)
        if self.backend_name == "jax":
            # accumulate across calls: metrics() divides cumulative
            # completions by cumulative serving time
            win = self.clock() - t0
            self._elapsed = (self._elapsed or 0.0) + win
        else:
            # this drain's virtual horizon (cumulative _elapsed keeps the
            # widest-horizon semantics the sim backend always had)
            win = self.sim_result.horizon if self.sim_result else 0.0
        after = self._class_counts()
        # resplit-aware migration costing: the window's HBM traffic budget
        # carries the pages the tidal controller moved during it
        self._last_window = {"elapsed_s": win,
                             "migrated_bytes": int(self.migrated_bytes
                                                   - mig0)}
        for pri in ("LS", "BE"):
            done = after[pri][0] - before[pri][0]
            toks = after[pri][1] - before[pri][1]
            self._last_window[pri] = {
                "completed": done,
                "throughput_rps": done / win if win > 0 else None,
                "tokens_per_s": toks / win if win > 0 else None,
            }
        return n

    # ------------------------------------------------------------------
    @staticmethod
    def _pcts(vals, keys=("p50", "p99")):
        """{p50_ms, p99_ms} (or TTFT/TBT-prefixed variants) for a latency
        list in seconds; None entries when the list is empty. Nearest-rank
        (repro.obs.metrics): the interpolated p99 np.percentile reports on
        small samples is a value no request actually experienced."""
        return obs.pcts(vals, {k: float(k[1:]) for k in keys}, scale=1e3)

    def metrics(self):
        out = {}
        cls = {"LS": {"done": [], "ttft": [], "tbt": [], "tokens": 0,
                      "slo_ok": 0, "slo_n": 0, "completed": 0},
               "BE": {"done": [], "ttft": [], "tbt": [], "tokens": 0,
                      "slo_ok": 0, "slo_n": 0, "completed": 0}}
        for name, rt in self.tenants.items():
            served = [r for r in rt.done if not r.failed]
            n_failed = len(rt.done) - len(served)
            lats = [r.latency for r in served if r.latency is not None]
            ttfts = [r.ttft for r in served if r.ttft is not None]
            out[name] = {
                "completed": len(served) + rt.sim_completed,
                "failed": n_failed,
                **self._pcts(lats),
                "ttft": self._pcts(ttfts),
                "tbt": self._pcts(rt.tbt_gaps),
                "peak_active": rt.peak_active,
            }
            if rt.kv is not None:
                out[name]["kv_pages"] = {"total": rt.kv.n_pages,
                                         "in_use": rt.kv.used_pages,
                                         "page_size": rt.kv.page_size}
            if rt.prefix is not None:
                out[name]["prefix_cache"] = rt.prefix.stats()
            if rt.chunk_aborts:
                out[name]["chunk_aborts"] = rt.chunk_aborts
            if rt.host is not None or rt.preemptions or rt.grow_stalls:
                sw = {"preemptions": rt.preemptions,
                      "swap_outs": rt.swap_outs,
                      "swap_ins": rt.swap_ins,
                      "grow_stalls": rt.grow_stalls,
                      "resume": self._pcts(rt.resume_gaps)}
                if rt.host is not None:
                    sw["host"] = rt.host.stats()
                out[name]["swap"] = sw
            if rt.prefill_tokens:
                out[name]["prefill_tokens"] = {
                    "admitted": rt.prefill_tokens,
                    "computed": rt.prefill_computed,
                    "saved": rt.prefill_tokens - rt.prefill_computed,
                }
            if (rt.rejected or rt.shed or rt.grow_deadlocks
                    or rt.swap_retries or rt.fault_recoveries
                    or rt.degraded):
                out[name]["faults"] = {
                    "rejected": rt.rejected,
                    "shed": rt.shed,
                    "grow_deadlocks": rt.grow_deadlocks,
                    "swap_retries": rt.swap_retries,
                    "recovered": dict(rt.fault_recoveries),
                    "degraded": list(rt.degraded),
                }
            c = cls[rt.spec.priority]
            c["done"] += lats
            c["ttft"] += ttfts
            c["tbt"] += rt.tbt_gaps
            c["completed"] += len(served) + rt.sim_completed
            c["tokens"] += sum(len(r.output or ()) for r in served)
            if rt.spec.slo_ms is not None:
                c["slo_n"] += len(lats)
                c["slo_ok"] += sum(l * 1e3 <= rt.spec.slo_ms for l in lats)
        elapsed = self._elapsed
        out["_class"] = {}
        for pri, c in cls.items():
            lats = c["done"]
            out["_class"][pri] = {
                "completed": c["completed"],
                **self._pcts(lats),
                "ttft": self._pcts(c["ttft"]),
                "tbt": self._pcts(c["tbt"]),
                "throughput_rps": (c["completed"] / elapsed
                                   if elapsed else None),
                "tokens_per_s": (c["tokens"] / elapsed if elapsed else None),
                "slo_attainment": (c["slo_ok"] / c["slo_n"]
                                   if c["slo_n"] else None),
            }
        if self._last_window is not None:
            out["_window"] = self._last_window
        # sub-chunk preemption rollup: aborts plus the LS submit->admit
        # waits measured at preemption boundaries (the latency the abort
        # protocol exists to bound)
        if self.preempt_tile or self.preempt_aborts:
            out["_preempt"] = {"tile": self.preempt_tile,
                               "aborts": self.preempt_aborts,
                               "wait": self._pcts(self.preempt_waits)}
        if self.chunk_governor is not None:
            out["_chunk_governor"] = self.chunk_governor.stats()
        if self.plan is not None:
            out["_plan"] = {"sm_be": self.plan.sm_be,
                            "ch_be": self.plan.ch_be,
                            "thres_dram": self.plan.thres_dram}
        applied = self._applied_plan
        if applied is not None or self.transitions:
            out["_online"] = {
                "sm_be": applied.sm_be if applied else None,
                "ch_be": applied.ch_be if applied else None,
                "transitions": len(self.transitions),
                "pages_moved": sum(t["pages_moved"]
                                   for t in self.transitions),
                "migrated_bytes": int(self.migrated_bytes),
            }
        if self.arena is not None:
            out["_coloring"] = {
                name: {"violations": self.arena.isolation_violations(a),
                       "pages": a.n_pages}
                for name, a in self.arena.allocations.items()}
        # chaos-plane rollup: injected (observed) events vs. the recovery
        # actions they triggered, plus the degradation state — present
        # whenever a fault plane is attached or any recovery path fired
        fa = {"injected": dict(self.faults.counts())
              if self.faults is not None else {},
              "recovered": {}, "shed": 0, "rejected": 0,
              "grow_deadlocks": 0, "swap_retries": 0,
              "watchdog_trips": self.watchdog_trips,
              "missed_ticks": self.missed_ticks,
              "stale_signals": self.stale_signals,
              "degraded_tenants": {}}
        for name, rt in self.tenants.items():
            for k, v in rt.fault_recoveries.items():
                fa["recovered"][k] = fa["recovered"].get(k, 0) + v
            fa["shed"] += rt.shed
            fa["rejected"] += rt.rejected
            fa["grow_deadlocks"] += rt.grow_deadlocks
            fa["swap_retries"] += rt.swap_retries
            if rt.degraded:
                fa["degraded_tenants"][name] = list(rt.degraded)
        fa["degraded"] = bool(fa["degraded_tenants"])
        if self.faults is not None or fa["recovered"] or fa["shed"] \
                or fa["rejected"] or fa["grow_deadlocks"] \
                or fa["swap_retries"] or fa["watchdog_trips"]:
            out["faults"] = fa
        # telemetry-plane rollup: the same windowed registry the control
        # loop reads (LoadSignal p99s come out of these histograms), plus
        # tracer volume when tracing is on
        if (self.registry.ticks or self.registry.histograms
                or self.registry.gauges):
            out["_registry"] = self.registry.snapshot()
        if self.tracer.level >= 0:
            out["_trace"] = self.tracer.stats()
        return out
