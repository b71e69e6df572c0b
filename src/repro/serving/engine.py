"""JAX serving engine: continuous batching with Chameleon integrated.

This is the *real* data plane (tier 1 in DESIGN §2): a jit'd decode
step over slot-padded KV caches and LoRA adapter-slot buffers, driven
by the same ChameleonScheduler / AdapterCache / MemoryPool objects the
simulator uses. LoRA matmuls route through the dispatch layer in
``repro.kernels.ops`` (``EngineConfig.lora_backend``): under ``auto``,
TPU backends run the fused Pallas bgmv (decode) / sgmv (prefill)
kernels and this CPU container runs the jnp einsum reference — the same
math, asserted token-identical by the CI parity jobs, which force the
kernel path in interpret mode.

Adapter loading is asynchronous by default (``EngineConfig.async_load``,
the systems half of paper §4's "minimize adapter loading times"): a
cache miss *dispatches* the host→device slot write and marks the cache
entry LOADING; the step loop keeps decoding the current batch while the
transfer is in flight, the scheduler refuses to place the loading
request (and only that request — the bypass lane may fill its seat),
and readiness is polled at the top of each step. Queued-request and
histogram prefetchers issue the same non-blocking loads ahead of
demand, so prefetch transfers overlap decode compute too.

Static-shape design (TPU-native):
- ``max_slots`` request slots; inactive slots run masked garbage that is
  never surfaced (standard TPU continuous batching);
- KV lives in a **paged pool** by default (``EngineConfig.paged``):
  fixed-size pages (L, n_pages, page, Kh, Dh) plus a per-slot page
  table, with page 0 reserved as the trash page inactive slots write.
  Pages are allocated on demand at prefill and per decoded page
  boundary, and freed on finish/squash, so ``MemoryPool`` request holds
  are *real* occupancy — the adapter cache, admission headroom, and
  ``queue_pressure()`` all see actual free HBM instead of a
  worst-case-reserved fiction. Decode attention routes through
  ``kernels.ops.paged_attention`` (Pallas on TPU, jnp reference on
  CPU). When a page cannot be allocated even after shrinking the
  adapter cache, the slot is preempted (pages freed, request requeued)
  — the price of admitting against actual rather than predicted
  occupancy. ``paged=False`` keeps the dense
  (L, max_slots, max_len, Kh, Dh) slab for parity testing;
- ``n_lora_slots`` adapter-slot buffers; the cache manager's on_load
  writes adapter weights into a slot (device-side copy), on_evict frees
  it. Residency decisions stay 100 % in repro.core — this file only
  moves bytes.

The decode hot loop is **device-resident** by default
(``EngineConfig.fused_hotloop``, DESIGN §2): one donated-buffer jit
dispatch fuses decode + sampling + ``cache_len`` advance (logits never
leave the device, KV updates in place — no double buffering), batch
state (active mask, sample positions, per-row sampling params, decode
budgets, stop tokens, page table) stays on device with rebuilds only
at batch epochs (place/finish/squash), and when no admissions are due
and no deadline/cancel sweep is armed an adaptive K-step micro-horizon
runs in one ``lax.scan`` with an on-device done-mask, syncing K tokens
per host round-trip with the next horizon dispatched before the
previous one's readback (pipelined). Under backlog K=1, so TTFT and
admission latency match the seed loop, which stays selectable
(``fused_hotloop=False``) as the ``benchmarks/decode_hotloop.py``
baseline — both loops are token-identical by construction and by the
``tests/test_hotloop_parity.py`` whole-engine A/B.

Engine surface (DESIGN §3): the engine implements the unified
``ServingSystem`` protocol — ``submit`` is non-blocking and returns a
``RequestHandle`` (streaming tokens, lifecycle state machine,
``cancel()``, per-request ``SamplingParams`` and deadlines), ``step``
runs one iteration — lifecycle sweep, *batched* prefill admission,
one fused decode horizon (or the seed decode + sampling pair) — and
``drain`` runs the queue dry. Prefills admitted in the same iteration
share one jit'd call over a (B, S) bucket instead of one
compile-and-launch per request, so TTFT under burst load reflects
batch admission, not serial prefill launches. Real prompt token ids (``Request.prompt``) feed the
prefill; trace-driven workloads without token material fall back to a
deterministic synthetic prompt. Squash/preemption preserves the
streamed prefix and its latency records across the requeue (the handle
never re-streams a position).

One engine can span a device **mesh** (``EngineConfig.mesh_shape``,
DESIGN §4.1): weights and LoRA-slot dout shard over the "model" axis,
KV pages / dense KV and per-request batch state over "data", and every
jit'd entry point (prefill, both decode loops, sampling, slot writes)
carries explicit in/out shardings from the ``distributed.sharding``
rule table via ``ShardPlan``. The control plane (pool, scheduler, page
tables, prefix cache) stays host-side and global, which is what keeps
a mesh>1 engine token-identical to single-device (``mesh_shape=None``,
the default) — asserted by ``tests/test_sharded_engine.py``.

Multi-replica serving shares one ``AdapterCatalog`` (host-side adapter
weights + size metadata) across engines: replicas differ only in device
state, never in adapter bytes. A gateway (``serving/gateway.py``) can
front any of this — engine, DES node, or cluster — adding per-tenant
admission control without the engine knowing tenants exist.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (AdapterCache, AdapterInfo, CacheStats,
                        ChameleonScheduler, HistogramPrefetcher,
                        MemoryPool, NoisyOraclePredictor, PoolError,
                        PrefixCache, QueuedRequestPrefetcher, Request,
                        RequestState, SamplingParams)
from repro.distributed.act_sharding import activation_sharding
from repro.kernels.ops import DISPATCH_METER, resolve_lora_backend
from repro.launch.mesh import make_serving_mesh
from repro.models import api
from repro.models.base import ModelConfig
from repro.models.lora_apply import (init_lora_slots, random_lora_weights,
                                     write_adapter_to_slot)
from repro.serving.handles import RequestHandle, prepare_request
from repro.serving.metrics import RequestRecord, RunMetrics
from repro.serving.shard_plan import ShardPlan
from repro.serving.spans import NO_SPANS, NULL_SPAN, SPANS


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_len: int = 256
    n_lora_slots: int = 8
    r_max: int = 32
    n_adapters: int = 16
    predictor_accuracy: float = 0.8
    seed: int = 0
    # Paged KV data plane (S-LoRA-style unified paging). ``paged=False``
    # falls back to the dense (L, max_slots, max_len, Kh, Dh) slab;
    # families without paged decode support fall back automatically.
    paged: bool = True
    page_size: int = 16
    # LoRA data-plane backend: "auto" = Pallas bgmv/sgmv on TPU, einsum
    # elsewhere; "kernel"/"einsum" force a path (kernel runs Pallas
    # interpret mode off-TPU — the CI parity jobs use this).
    lora_backend: str = "auto"
    # Async adapter loading: dispatch the host→device slot write and
    # keep stepping; placement waits on readiness (paper §4 overlap).
    # False restores the blocking load (the A/B baseline).
    async_load: bool = True
    # Modeled H2D link bandwidth (GB/s) for load-latency experiments;
    # 0 = unmodeled (readiness is actual device-write completion).
    # Sync mode stalls the step loop for the modeled transfer time,
    # async mode only defers the affected adapter's readiness.
    h2d_gbps: float = 0.0
    # Prefetchers (paper §4.1): walk the wait queues / per-adapter
    # arrival histograms and issue non-blocking loads ahead of demand.
    queued_prefetch: bool = True
    histogram_prefetch: bool = True
    # Device-resident fused decode hot loop (DESIGN §2): one jit
    # dispatch fuses decode + sampling + cache_len advance (logits
    # never leave the device), the KV/token/length buffers are donated
    # into it (in-place update — no double-buffered KV), and batch
    # state (active mask, positions, sampling params, page table) stays
    # device-resident with updates only at place/finish/squash
    # boundaries. False restores the seed two-dispatch loop — the
    # baseline `benchmarks/decode_hotloop.py` A/Bs against.
    fused_hotloop: bool = True
    # Adaptive micro-horizon: up to this many decode steps run in one
    # on-device lax.scan (with an on-device done-mask) when no
    # admissions are due and no deadline/cancel sweep is armed, so the
    # host syncs K tokens at a time. Under backlog the horizon drops to
    # 1 so TTFT and admission latency are untouched. Keep below
    # page_size: horizon page pre-growth stays within one page of the
    # seed loop's per-boundary allocation.
    max_horizon: int = 8
    # Pipelined readback: dispatch horizon N+1 from the carried device
    # state before syncing horizon N's tokens (host bookkeeping runs
    # one horizon behind the device while the batch is stable).
    pipeline_readback: bool = True
    # Prefix KV reuse (ROADMAP 1): a token-id-keyed radix tree over the
    # paged pool keeps *prompt* KV pages resident after requests finish;
    # the next request with a matching prefix maps those pages into its
    # page table and prefills only the suffix (COW fork on a mid-page
    # divergence). Paged mode only. False restores the seed prefill
    # path bit-for-bit (the A/B baseline).
    prefix_cache: bool = True
    # What may share a cached page:
    #   "exact" — pages are keyed per adapter. LoRA here touches the
    #   q/k/v/o projections, so prompt KV is adapter-dependent; only
    #   same-adapter reuse is output-identical to the cache-off run.
    #   "alora" — prompt prefill runs with the *base* model and the
    #   adapter activates at generation ("Activated LoRA", PAPERS.md):
    #   prefix pages become adapter-invariant and one tree serves every
    #   adapter (true cross-adapter reuse). Changes prefill semantics
    #   for *all* requests (cache on or off) so the A/B stays paired.
    prefix_mode: str = "exact"
    # Mesh-sharded data plane (DESIGN §4): (data, model) shape of the
    # ("data", "model") serving mesh one engine spans — resolved through
    # ``launch.make_serving_mesh``, the single mesh factory. Weights and
    # LoRA-slot dout shard over "model"; KV pages, dense KV batch and
    # all per-request batch state over "data"; every jit'd entry point
    # gets explicit in/out shardings from the ``sharding.py`` rule
    # table. None = single-device (bit-for-bit the seed path). The
    # control plane (pool, scheduler, page tables) stays host-side and
    # global, so a mesh>1 engine is token-identical to mesh=1 — the
    # parity lock ``tests/test_sharded_engine.py`` asserts.
    mesh_shape: Optional[tuple] = None
    # Chunked prefill (ROADMAP 3 stepping stone): a prompt longer than
    # this many tokens prefills in chunks of this size — one chunk per
    # engine step — instead of one monolithic jit'd call, so in-flight
    # decodes keep stepping between chunks and a long prefill can no
    # longer stall them for its full duration. Paged mode only (chunks
    # write straight into the slot's pages via the suffix-prefill entry
    # point). Tokens are unchanged: each chunk attends to all previously
    # written positions, so the final logits match the monolithic
    # prefill bit-for-bit. 0 disables (the seed behavior, and the A/B
    # baseline of ``benchmarks/disagg_interference.py``).
    prefill_chunk_tokens: int = 0
    # Speculative draft–verify decoding (ROADMAP 5) inside the fused
    # hot loop: a small dense *draft* model (base weights only — the
    # LoRA adapters ride along at verify time) proposes up to ``spec_k``
    # tokens per row, the target scores all drafted positions in ONE
    # multi-token verify dispatch, and the accept mask / bonus token /
    # per-row cache_len rollback are computed on device — greedy tokens
    # stay bit-identical to the non-speculative loop; seeded sampling
    # uses (seed, position)-keyed rejection sampling. ``spec_k`` adapts
    # down with the measured acceptance EWMA, and the existing backlog /
    # deadline K=1 demotions turn speculation off for that step. Needs
    # the fused loop and a dense draft family; anything else warns once
    # at construction and falls back.
    spec_decode: bool = False
    spec_k: int = 4
    spec_draft: str = "internlm2-1.8b"


class AdapterCatalog:
    """Host-side LoRA adapter store shared by every engine replica.

    Holds the adapter weights ("host memory" in the paper) and the
    AdapterInfo metadata the control plane prices residency with. One
    catalog serves N engines: replicas keep per-device slot buffers but
    never duplicate the host-side weights (DESIGN §3). The weights are
    numpy arrays in host RAM, so a cache fill is a real host-to-device
    transfer and catalog adapters take no device memory.
    """

    def __init__(self, cfg: ModelConfig, n_adapters: int, r_max: int,
                 seed: int = 0):
        self.cfg = cfg
        self.r_max = r_max
        key = jax.random.PRNGKey(seed)
        self.ranks = [min(cfg.lora_ranks[i % len(cfg.lora_ranks)], r_max)
                      for i in range(n_adapters)]
        keys = jax.random.split(key, n_adapters)
        self.weights = {
            aid: jax.tree.map(np.asarray, random_lora_weights(
                keys[aid], self.ranks[aid], r_max, cfg.n_layers,
                cfg.d_model, cfg.q_dim, cfg.kv_dim))
            for aid in range(n_adapters)}
        kv_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
        lora_bytes = {aid: sum(
            int(np.prod(a.shape) + np.prod(b.shape)) * 2
            for a, b in self.weights[aid].values())
            for aid in self.weights}
        self.infos = {aid: AdapterInfo(
            adapter_id=aid, rank=self.ranks[aid],
            size_bytes=lora_bytes[aid],
            size_tokens=max(1, lora_bytes[aid] // kv_tok))
            for aid in self.weights}

    def __len__(self) -> int:
        return len(self.weights)

    def rank_of(self, adapter_id: int) -> int:
        return self.infos[adapter_id].rank


class ChameleonEngine:
    """Single-host engine over a (small) real model."""

    def __init__(self, cfg: ModelConfig, params: dict,
                 ecfg: EngineConfig | None = None,
                 scheduler_cls=ChameleonScheduler, cache_enabled=True,
                 catalog: AdapterCatalog | None = None,
                 clock: Optional[Callable[[], float]] = None,
                 draft: Optional[tuple] = None,
                 devices: Optional[list] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg or EngineConfig()
        e = self.ecfg
        key = jax.random.PRNGKey(e.seed)

        # --- serving mesh (DESIGN §4): one engine across N devices ---
        # ``devices`` pins this engine to an explicit device list (a
        # cluster hands every replica its own chip or mesh slice); None
        # keeps the default placement. A single-device pin commits the
        # resident state (weights, slot arena, KV, batch state) to that
        # device, so every jit follows its committed inputs there.
        self.mesh = None
        self.plan: Optional[ShardPlan] = None
        self._device = None
        if e.mesh_shape is not None:
            d, m = e.mesh_shape
            self.mesh = make_serving_mesh(d * m, m, devices=devices)
            self.plan = ShardPlan(self.mesh, cfg)
            # Weights land sharded over "model" once, up front —
            # resident, never re-gathered per step.
            self._params_sh = self.plan.params(params)
            self.params = params = jax.device_put(params, self._params_sh)
        elif devices is not None:
            self._device = devices[0]
            self.params = params = self._pin(params)

        # --- LoRA adapter catalog (host-side weights = "host memory") ---
        self.catalog = catalog or AdapterCatalog(cfg, e.n_adapters,
                                                 e.r_max, seed=e.seed)
        self.host_adapters = self.catalog.weights
        # Device adapter-slot buffers (per replica).
        self.lora = self._pin(init_lora_slots(
            key, e.n_lora_slots, cfg.n_layers, cfg.d_model, cfg.q_dim,
            cfg.kv_dim, self.catalog.r_max))
        # Sharded slot arena: A replicated, B dout over "model" (the
        # LoRA delta adds to the sharded projection output without a
        # reshard — S-LoRA's TP partition strategy). Host adapter
        # weights upload *directly into this layout*: each device
        # receives only its dout slice of B, never the full tensor.
        self._lora_sh = None
        self._adapter_w_sh = None
        if self.plan is not None:
            self._lora_sh = self.plan.lora_slots(self.lora)
            self.lora = jax.device_put(self.lora, self._lora_sh)
            if self.host_adapters:
                self._adapter_w_sh = self.plan.adapter_weights(
                    next(iter(self.host_adapters.values())))
        self.slot_of: dict[int, int] = {}       # adapter_id -> lora slot
        self.free_slots = list(range(e.n_lora_slots))
        # Double-buffered async loads: slot writes land in the
        # *staging* chain (``_lora_staging``) while the jit'd steps
        # keep reading the active ``self.lora`` — no data dependency on
        # an in-flight transfer, so decode genuinely overlaps the copy.
        # ``_pending_loads`` maps adapter_id -> (staging snapshot to
        # swap active to — None once swapped, fresh device arrays to
        # poll, modeled-ready wall time); `_poll_loads` swaps snapshots
        # in FIFO order as writes land and READYs entries once the
        # modeled time also passed.
        self._lora_staging = self.lora
        self._pending_loads: dict[
            int, tuple[Optional[dict], tuple, float]] = {}
        self.n_async_loads = 0
        self._lora_backend = resolve_lora_backend(e.lora_backend)

        # --- memory pool in token units ---
        infos = self.catalog.infos
        cap = e.max_slots * e.max_len \
            + 4 * max(c.size_tokens for c in infos.values())
        self.paged = bool(e.paged) and api.supports_paged(cfg)
        # Per-device sizing is telemetry (n_shards); the *accounting*
        # stays global so admission/eviction decisions — and therefore
        # emitted tokens — are identical at every mesh shape.
        self.pool = MemoryPool(capacity_tokens=cap,
                               page_size=e.page_size if self.paged else 1,
                               n_shards=(self.mesh.size
                                         if self.mesh is not None else 1))
        self.cache = AdapterCache(self.pool, infos,
                                  enabled=cache_enabled,
                                  on_load=self._load_adapter,
                                  on_evict=self._evict_adapter,
                                  max_entries=e.n_lora_slots)
        pred = NoisyOraclePredictor(accuracy=e.predictor_accuracy,
                                    seed=e.seed)
        skw = dict(max_batch_requests=e.max_slots)
        if issubclass(scheduler_cls, ChameleonScheduler):
            skw["t_refresh"] = 5.0
        self.sched = scheduler_cls(self.pool, self.cache, infos, pred,
                                   **skw)
        # §4.1 prefetchers: their cache.prefetch calls run through the
        # same async `_load_adapter`, so prefetch H2D transfers overlap
        # decode compute instead of stalling the loop.
        self.q_prefetch = (QueuedRequestPrefetcher(self.cache)
                           if e.queued_prefetch else None)
        self.h_prefetch = (HistogramPrefetcher(self.cache)
                           if e.histogram_prefetch else None)
        # Paged mode: the engine holds exactly its allocated pages in
        # the pool (per req_id) and grows/frees them itself; the
        # scheduler's worst-case reservation is switched off.
        self.sched.reserve_from_pool = not self.paged

        # --- device state ---
        if self.paged:
            ps = e.page_size
            # One physical page per pool page + the reserved trash page
            # (page 0). Sizing pages to the *whole* pool is the unified
            # paging: KV can spread into memory adapters are not using.
            self.n_pages = cap // ps + 1
            if self.mesh is not None:
                # Round physical pages up to the data-axis size so the
                # page axis shards evenly. The pool still caps
                # allocation at the unrounded capacity and pages pop
                # off the free list in the same 1, 2, 3… order, so the
                # extra pages are never allocated — control-plane
                # decisions (hence tokens) stay mesh-invariant.
                ds = self.mesh.shape["data"]
                self.n_pages = -(-self.n_pages // ds) * ds
            self.pages_per_slot = -(-e.max_len // ps)
            self.kv_pages = self._pin(api.init_paged_serve_state(
                cfg, self.n_pages, ps, jnp.float32))
            if self.plan is not None:
                kvp = self.plan.kv_pages(self.kv_pages[0].shape)
                self._kv_sh = (kvp, kvp)
                self.kv_pages = jax.device_put(self.kv_pages, self._kv_sh)
            self.page_table = np.zeros(
                (e.max_slots, self.pages_per_slot), np.int32)
            self.slot_pages: list[list[int]] = [[] for _ in
                                                range(e.max_slots)]
            self.free_pages = list(range(self.n_pages - 1, 0, -1))
            self.kv = None
        else:
            self.kv = self._pin(api.init_serve_state(
                cfg, e.max_slots, e.max_len, jnp.float32))
            if self.plan is not None:
                kvd = self.plan.kv_dense(self.kv[0].shape)
                self._kv_sh = (kvd, kvd)
                self.kv = jax.device_put(self.kv, self._kv_sh)
        # --- prefix KV reuse (radix tree over the paged pool) ---
        if e.prefix_mode not in ("exact", "alora"):
            raise ValueError(f"unknown prefix_mode {e.prefix_mode!r}")
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.pool, e.page_size)
            if self.paged and e.prefix_cache else None)
        # Per-slot shared prefix pages (subset of slot_pages the slot
        # only *references*: freed via release_shared, never returned
        # to free_pages directly).
        self.slot_shared: list[list[int]] = [[] for _ in range(e.max_slots)]
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.n_prefix_hits = 0          # placements with a nonzero match
        self.n_cow_forks = 0
        self.tokens = self._pin(jnp.zeros((e.max_slots, 1), jnp.int32))
        self.cache_len = self._pin(jnp.zeros((e.max_slots,), jnp.int32))
        self.active = np.zeros((e.max_slots,), bool)
        self.adapter_slot = self._pin(jnp.zeros((e.max_slots,), jnp.int32))
        if self.plan is not None:
            # Batch state over "data". ``_batch_sh(ndim)`` reuses the
            # fitted row spec so a max_slots that doesn't divide the
            # data axis degrades to replicated everywhere consistently.
            row = self.plan.batch((e.max_slots,))
            ax = row.spec[0] if len(row.spec) else None
            self._batch_ax = ax
            self.tokens = self.plan.put(self.tokens, self._batch_sh(2))
            self.cache_len = self.plan.put(self.cache_len,
                                           self._batch_sh(1))
            self.adapter_slot = self.plan.put(self.adapter_slot,
                                              self._batch_sh(1))
        self.slot_req: list[Optional[Request]] = [None] * e.max_slots
        self.t0 = time.monotonic()
        self._clock = clock
        # Engine spans (``serving/spans.py``) while a profiler capture
        # runs; an injected clock's times are not the capture's.
        self.spans = SPANS if clock is None else NO_SPANS
        self._horizon_k = 0                    # the step's decode K
        self._prefill_span: dict[int, int] = {}   # req_id -> prefill span
        # adapter_id -> (dispatch time, dispatching step, attributes) of
        # an in-flight load, held while spans are on.
        self._load_spans: dict[int, tuple] = {}
        self.completed: list[Request] = []
        self.records: list[RequestRecord] = []
        self.outputs: dict[int, list[int]] = {}
        self._tbts: dict[int, list[float]] = {}
        self._last_tok: dict[int, float] = {}
        self.handles: dict[int, RequestHandle] = {}
        self.batch_occupancy: list[int] = []   # active slots per step
        self.n_preempted = 0                   # paged: out-of-page squashes
        self.n_cancelled = 0
        self.n_expired = 0
        # Chunked prefill (``EngineConfig.prefill_chunk_tokens``): slots
        # whose prompt is mid-prefill. The slot holds its request and
        # all prompt pages but stays off the active mask until the last
        # chunk produces the first token, so decode steps interleave
        # with the chunks. slot -> {"req", "prompt", "done"}.
        self._chunked: dict[int, dict] = {}
        self.n_chunked_prefills = 0
        # Disaggregated serving (serving/disagg.py): requests detached
        # from decode by ``begin_migration`` while their KV crosses to
        # a decode replica. The slot stays occupied (slot_req set,
        # active False) until complete/abort, so its pool holds and
        # shared-page refs keep the KV pinned mid-handoff.
        # req_id -> slot.
        self._migrating: dict[int, int] = {}
        self.n_kv_exports = 0
        self.n_kv_imports = 0
        self.kv_handoff_bytes = 0
        # Lifecycle fast path: deadline/cancel sweeps run only once a
        # request armed them (keeps the hot step loop scan-free).
        self._deadlines_armed = False
        self._cancel_armed = False
        self._cancel_races: list[Request] = []

        # --- device-resident hot loop state (DESIGN §2) ---
        # ``batch_epoch`` counts batch-composition changes (place /
        # finish / squash); the device-side batch state — active mask,
        # sample positions, per-row sampling params, decode budgets,
        # stop-token matrix — is rebuilt from the Python requests only
        # when the epoch moved, and otherwise carried on device across
        # fused steps. Same for the paged page table: ``self.page_table``
        # (host numpy) is uploaded only when a page was allocated or
        # freed, not per step.
        self.fused = bool(e.fused_hotloop) and api.supports_fused(cfg)
        if e.fused_hotloop and not self.fused:
            warnings.warn(
                f"fused_hotloop=True ignored: model family "
                f"{cfg.family.name} has no fused decode path "
                f"(api.supports_fused) — falling back to the per-step "
                f"seed decode loop", RuntimeWarning, stacklevel=2)
        self.batch_epoch = 0
        self._dev: Optional[dict] = None
        self._dev_epoch = -1
        self._page_table_dev = None
        self._page_table_dirty = True
        # One dispatched-but-unsynced horizon: (toks (K, B) on device,
        # emits (K, B) on device, K). Host bookkeeping for it runs at
        # the next step boundary — after the *next* horizon was
        # dispatched, when the batch is stable (pipelined readback).
        self._inflight: Optional[tuple] = None

        # --- speculative draft–verify decoding (ROADMAP 5) ---
        # ``self.spec`` is the *effective* switch: spec_decode=True with
        # a validated dense draft on a fused single-device engine.
        # Invalid draft configs raise here (construction), never in jit.
        self.spec = False
        self.draft_cfg: Optional[ModelConfig] = None
        self.draft_params: Optional[dict] = None
        self.draft_kv = None
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.n_spec_dispatches = 0
        self.n_spec_draft_dispatches = 0
        self.n_spec_verify_dispatches = 0
        self._spec_ewma = 1.0     # acceptance EWMA → adaptive spec_k
        if e.spec_decode:
            self._init_spec(draft)

        self._decode_jit = jax.jit(self._decode_fn)
        self._decode_paged_jit = jax.jit(self._decode_paged_fn)
        # Fused decode+sample horizon: tokens/KV/cache_len/active/
        # positions are *donated* — XLA updates the KV slab (the big
        # buffer) in place instead of allocating a second copy per
        # step. K and the all-greedy fast path are static (bounded
        # variants: K is bucketed to powers of two).
        self._fused_jit = jax.jit(
            self._fused_fn, static_argnames=("K", "all_greedy"),
            donate_argnums=(2, 3, 4, 5, 6))
        self._fused_paged_jit = jax.jit(
            self._fused_paged_fn, static_argnames=("K", "all_greedy"),
            donate_argnums=(2, 3, 5, 6, 7))
        self._prefill_jit = jax.jit(self._prefill_fn,
                                    static_argnames=("S",))
        # Suffix prefill straight into donated KV pages (prefix path).
        self._prefill_paged_jit = jax.jit(self._prefill_paged_fn,
                                          static_argnames=("S",),
                                          donate_argnums=(3,))
        # A fresh prefill's K/V into its pages (paged, prefix off): one
        # program writes every page into the donated pool in place; one
        # compile per (B, S) prefill bucket.
        self._place_pages_jit = jax.jit(api.write_prompt_pages,
                                        donate_argnums=(0,))
        self._sample_jit = jax.jit(api.sample_tokens)
        if self.spec:
            # Speculative round: tokens, target KV, draft KV, cache_len,
            # active and positions are donated (same in-place invariant
            # as the fused horizon); spec_k / n_rounds / all_greedy are
            # static (spec_k is bucketed to powers of two, n_rounds
            # derives from it, so jit variants stay bounded).
            self._spec_jit = jax.jit(
                self._spec_fn,
                static_argnames=("spec_k", "n_rounds", "all_greedy"),
                donate_argnums=(2, 3, 4, 5, 6, 7))
            self._spec_paged_jit = jax.jit(
                self._spec_paged_fn,
                static_argnames=("spec_k", "n_rounds", "all_greedy"),
                donate_argnums=(2, 3, 5, 6, 7, 8))
            # Draft-KV catch-up: batched multi-token draft forward that
            # replays tokens the draft cache is missing (placement,
            # prefix-cache import, chunked prefill, squash re-execution
            # all leave the draft behind the target; see _draft_sync).
            self._draft_catchup_jit = jax.jit(
                self._draft_catchup_fn, static_argnames=("S",),
                donate_argnums=(2,))
        # Prefill shapes vary per (B, S) admission bucket, so their
        # sharded jits (fitted in/out shardings per bucket) are built
        # lazily; the fixed-shape decode/fused jits above are replaced
        # with explicitly-sharded versions here.
        self._sharded_prefill_cache: dict = {}
        if self.plan is not None:
            self._install_sharded_jits()

    # ------------------------------------- speculative decoding setup
    def _init_spec(self, draft: Optional[tuple]) -> None:
        """Validate and build the speculative-decoding state.

        ``draft`` is an optional ``(draft_cfg, draft_params)`` pair
        (tests and benchmarks pass reduced models); None resolves
        ``EngineConfig.spec_draft`` through the config registry and
        initialises base weights from the engine seed. Config errors —
        non-dense draft family, vocab mismatch, bad spec_k — raise
        here, at engine construction, never inside jit; unsupported
        *engine* shapes (non-fused target, mesh>1) warn once and leave
        speculation off."""
        e = self.ecfg
        if draft is not None:
            draft_cfg, draft_params = draft
        else:
            from repro.configs import get_config
            draft_cfg = get_config(e.spec_draft)
            draft_params = None
        if not api.supports_spec_draft(draft_cfg):
            raise ValueError(
                f"spec_decode=True needs a dense draft model: draft "
                f"{draft_cfg.name!r} is family {draft_cfg.family.name}, "
                f"which has no dense-KV decode_step for the speculative "
                f"scan (api.supports_spec_draft). Pick a Family.DENSE "
                f"config for EngineConfig.spec_draft (e.g. "
                f"'internlm2-1.8b') or turn spec_decode off.")
        if draft_cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"spec_decode draft {draft_cfg.name!r} has vocab_size="
                f"{draft_cfg.vocab_size} but the target "
                f"{self.cfg.name!r} has vocab_size="
                f"{self.cfg.vocab_size}; draft and target must share a "
                f"vocabulary for draft tokens to be target-scorable.")
        if e.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {e.spec_k}")
        if not self.fused:
            warnings.warn(
                f"spec_decode=True ignored: target family "
                f"{self.cfg.family.name} (or fused_hotloop=False) has "
                f"no fused decode path to speculate inside — running "
                f"the non-speculative loop", RuntimeWarning,
                stacklevel=2)
            return
        if self.mesh is not None:
            warnings.warn(
                "spec_decode=True ignored on a mesh-sharded engine: "
                "the speculative jits are not in the sharding rule "
                "table yet — running the non-speculative fused loop",
                RuntimeWarning, stacklevel=2)
            return
        if draft_params is None:
            draft_params = api.init_params(
                draft_cfg, jax.random.PRNGKey(e.seed + 1))
        self.draft_cfg = draft_cfg
        self.draft_params = self._pin(draft_params)
        # The draft KV is a dense slab outside the paged pool: the
        # draft is small and adapter-free, so its cache is priced as
        # part of the (constant) speculation overhead, not as
        # per-request pool occupancy. "Freeing" a slot's draft KV is
        # bookkeeping: _draft_len drops to 0 and the slab rows are
        # rewritten by the next occupant's catch-up.
        self.draft_kv = self._pin(api.init_serve_state(
            draft_cfg, e.max_slots, e.max_len, jnp.float32))
        # Tokens of the *target* cache the draft cache mirrors, per
        # slot (host truth — the lazy catch-up syncs the gap).
        self._draft_len = np.zeros(e.max_slots, np.int64)
        self.spec = True

    # --------------------------------------------- sharded data plane
    def _pin(self, tree):
        """Commit ``tree`` to this engine's pinned device (no-op when
        the engine is unpinned)."""
        if self._device is None:
            return tree
        return jax.device_put(tree, self._device)

    def _batch_sh(self, ndim: int):
        """NamedSharding for (max_slots, ...) batch-state tensors."""
        return self.plan.named(
            P(self._batch_ax, *([None] * (ndim - 1))))

    def _act_scope(self):
        """Activation-sharding anchors (constrain_* in models/) are
        armed only while a mesh engine traces/dispatches — scoped, not
        global, so single-device engines in the same process (cluster
        replicas, A/B baselines) are untouched."""
        if self.mesh is None:
            return contextlib.nullcontext()
        # Batch axes are *empty* in exact mode: data-splitting the batch
        # halves every local matmul's M, and XLA picks a different
        # blocking (FP summation order) for the smaller shape — measured
        # 2e-6 logit drift at mesh (2,2) even with replicated weights.
        # Compute therefore runs at full batch; the "data" axis shards
        # storage (KV pages, batch-state vectors) via the jit in/out
        # shardings, and GSPMD inserts the elementwise (exact)
        # gather/scatter at the jit boundary.
        return activation_sharding(
            (), model_size=self.mesh.shape["model"],
            mesh=self.mesh, exact_reductions=True)

    def _install_sharded_jits(self) -> None:
        """Explicit in/out shardings for the fixed-shape entry points.

        Derived entirely from the ``sharding.py`` rule table via the
        engine's ShardPlan: weights + LoRA-B over "model", KV (pages or
        dense batch) and every per-request vector over "data". Donated
        buffers (tokens/KV/cache_len/active/positions) keep identical
        in- and out-shardings so XLA's in-place aliasing survives
        sharding. Only the active data plane's pair is rebuilt; the
        other keeps its unsharded default (it is never called)."""
        b1, b2 = self._batch_sh(1), self._batch_sh(2)
        hor = self.plan.named(P(None, self._batch_ax))   # (K, B) blocks
        logits_sh = self.plan.logits(
            (self.ecfg.max_slots, self.cfg.vocab_size))
        params_sh, lora_sh = self._params_sh, self._lora_sh
        kv_sh = self._kv_sh
        if self.paged:
            self._decode_paged_jit = jax.jit(
                self._decode_paged_fn,
                in_shardings=(params_sh, lora_sh, b2, kv_sh, b2, b1, b1),
                out_shardings=(logits_sh, kv_sh))
            carry = (b2, kv_sh, b1, b1, b1)

            # pjit rejects *any* kwargs once in_shardings is explicit,
            # so the static knobs move to trailing positional args and
            # a thin wrapper keeps the call sites' K=/all_greedy=
            # keyword surface identical to the unsharded jits.
            def fp(params, lora, tokens, kv_pages, page_table,
                   cache_len, active, positions, adapter_slot, budget,
                   stop, temp, topk, topp, seeds, K, all_greedy):
                return self._fused_paged_fn(
                    params, lora, tokens, kv_pages, page_table,
                    cache_len, active, positions, adapter_slot, budget,
                    stop, temp, topk, topp, seeds, K=K,
                    all_greedy=all_greedy)
            fp_jit = jax.jit(
                fp, static_argnums=(15, 16),
                donate_argnums=(2, 3, 5, 6, 7),
                in_shardings=(params_sh, lora_sh, b2, kv_sh, b2, b1,
                              b1, b1, b1, b1, b2, b1, b1, b1, b1),
                out_shardings=(carry, hor, hor))
            self._fused_paged_jit = (
                lambda *a, K, all_greedy: fp_jit(*a, K, all_greedy))
        else:
            self._decode_jit = jax.jit(
                self._decode_fn,
                in_shardings=(params_sh, lora_sh, b2, kv_sh, b1, b1),
                out_shardings=(logits_sh, kv_sh))
            carry = (b2, kv_sh, b1, b1, b1)

            def fd(params, lora, tokens, kv, cache_len, active,
                   positions, adapter_slot, budget, stop, temp, topk,
                   topp, seeds, K, all_greedy):
                return self._fused_fn(
                    params, lora, tokens, kv, cache_len, active,
                    positions, adapter_slot, budget, stop, temp, topk,
                    topp, seeds, K=K, all_greedy=all_greedy)
            fd_jit = jax.jit(
                fd, static_argnums=(14, 15),
                donate_argnums=(2, 3, 4, 5, 6),
                in_shardings=(params_sh, lora_sh, b2, kv_sh, b1, b1,
                              b1, b1, b1, b2, b1, b1, b1, b1),
                out_shardings=(carry, hor, hor))
            self._fused_jit = (
                lambda *a, K, all_greedy: fd_jit(*a, K, all_greedy))

    def _get_prefill_jit(self, B: int, S: int):
        """Sharded dense prefill jit for one (B, S) bucket. pjit input
        shardings demand exact divisibility, so each bucket fits its
        own specs (B=1 rows degrade to replicated)."""
        if self.plan is None:
            return self._prefill_jit
        key = ("dense", B, S)
        jitf = self._sharded_prefill_cache.get(key)
        if jitf is None:
            pl, cfg = self.plan, self.cfg
            lora_sh = (None if self.ecfg.prefix_mode == "alora"
                       else self._lora_sh)
            bB = pl.batch((B,))
            kv = pl.kv_dense((cfg.n_layers, B, S, cfg.n_kv_heads,
                              cfg.head_dim))
            jitf = jax.jit(
                self._prefill_fn, static_argnames=("S",),
                in_shardings=(self._params_sh, lora_sh,
                              pl.batch((B, S)), bB, bB),
                out_shardings=(pl.logits((B, cfg.vocab_size)),
                               (kv, kv)))
            self._sharded_prefill_cache[key] = jitf
        return jitf

    def _get_prefill_paged_jit(self, B: int, S: int):
        """Sharded suffix-prefill jit for one (B, S) bucket; the KV
        pool keeps its fixed pages-over-"data" sharding (donated)."""
        if self.plan is None:
            return self._prefill_paged_jit
        key = ("paged", B, S)
        jitf = self._sharded_prefill_cache.get(key)
        if jitf is None:
            pl, cfg = self.plan, self.cfg
            lora_sh = (None if self.ecfg.prefix_mode == "alora"
                       else self._lora_sh)
            bB = pl.batch((B,))
            jitf = jax.jit(
                self._prefill_paged_fn, static_argnames=("S",),
                donate_argnums=(3,),
                in_shardings=(self._params_sh, lora_sh,
                              pl.batch((B, S)), self._kv_sh,
                              pl.batch((B, self.pages_per_slot)),
                              bB, bB, bB),
                out_shardings=(pl.logits((B, cfg.vocab_size)),
                               self._kv_sh))
            self._sharded_prefill_cache[key] = jitf
        return jitf

    def _get_place_pages_jit(self, B: int, S: int):
        """Sharded page write for one (B, S) prefill bucket: the pool
        keeps its pages-over-"data" sharding (donated), the prefill's
        K/V comes in as the dense prefill jit left it."""
        if self.plan is None:
            return self._place_pages_jit
        key = ("place", B, S)
        jitf = self._sharded_prefill_cache.get(key)
        if jitf is None:
            pl, cfg = self.plan, self.cfg
            kv = pl.kv_dense((cfg.n_layers, B, S, cfg.n_kv_heads,
                              cfg.head_dim))
            P = -(-S // self.pool.page_size)
            jitf = jax.jit(
                api.write_prompt_pages, donate_argnums=(0,),
                in_shardings=(self._kv_sh, kv, kv, pl.batch((B, P))),
                out_shardings=self._kv_sh)
            self._sharded_prefill_cache[key] = jitf
        return jitf

    def _commit(self, x, sh):
        """Re-commit a host-updated device value to its planned
        sharding before a jit with explicit in_shardings sees it
        (eager ``.at[].set`` preserves sharding in practice, making
        this a free no-op — but pjit hard-errors on a mismatch, so the
        invariant is enforced, not assumed)."""
        if self.plan is None:
            return x
        return jax.device_put(x, sh)

    def _commit_batch_state(self) -> None:
        if self.plan is None:
            return
        self.tokens = self._commit(self.tokens, self._batch_sh(2))
        self.cache_len = self._commit(self.cache_len, self._batch_sh(1))
        self.adapter_slot = self._commit(self.adapter_slot,
                                         self._batch_sh(1))
        if self.paged:
            self.kv_pages = self._commit(self.kv_pages, self._kv_sh)
        else:
            self.kv = self._commit(self.kv, self._kv_sh)

    # ------------------------------------------------------------- clock
    def now(self) -> float:
        if self._clock is not None:
            return self._clock()
        return time.monotonic() - self.t0

    # ----------------------------------------------------- adapter moves
    def _load_adapter(self, info: AdapterInfo) -> None:
        """Cache ``on_load`` hook: stage the adapter into a device slot.

        Async mode (default) dispatches the host→device write into the
        *staging* buffer chain and marks the entry LOADING; the jit'd
        steps keep reading the active ``self.lora``, which has no data
        dependency on the in-flight transfer, so decode overlaps the
        copy for real. `_poll_loads` swaps the staging snapshot in once
        the write lands. Sync mode blocks until the write (plus any
        modeled H2D time) lands — the S-LoRA baseline the fig10 loading
        A/B measures against.
        """
        if not self.free_slots:
            raise RuntimeError(
                "adapter slot accounting drift: no free LoRA slot for "
                f"adapter {info.adapter_id} "
                f"(n_lora_slots={self.ecfg.n_lora_slots}, "
                f"slot_of={dict(sorted(self.slot_of.items()))}, "
                f"cache_resident={sorted(self.cache.resident_ids())}, "
                f"cache_loading={sorted(self.cache.loading_ids())})")
        slot = self.free_slots.pop()
        self.slot_of[info.adapter_id] = slot
        if self.spans.on:
            self._load_spans[info.adapter_id] = (
                time.monotonic(), self.spans.outermost(),
                dict(adapter=info.adapter_id, rank=info.rank, slot=slot,
                     bytes=info.size_bytes))
        self._lora_staging = write_adapter_to_slot(
            self._lora_staging, self.host_adapters[info.adapter_id], slot,
            shardings=self._adapter_w_sh)
        if self._lora_sh is not None:
            # The slot write preserves the arena sharding; re-commit so
            # the jits' explicit in_shardings never see a drifted one.
            self._lora_staging = jax.device_put(self._lora_staging,
                                                self._lora_sh)
        e = self.ecfg
        delay = (info.size_bytes / (e.h2d_gbps * 1e9)
                 if e.h2d_gbps > 0 else 0.0)
        if e.async_load:
            self.cache.mark_loading(info.adapter_id)
            self._pending_loads[info.adapter_id] = (
                self._lora_staging,
                jax.tree_util.tree_leaves(self._lora_staging),
                self.now() + delay)
            self.n_async_loads += 1
        else:
            jax.block_until_ready(self._lora_staging)
            self.lora = self._lora_staging
            self._record_load(info.adapter_id)
            if delay:
                time.sleep(delay)   # modeled H2D stall blocks the loop

    def _poll_loads(self) -> None:
        """Retire in-flight loads; runs every step, never blocks.

        Two decoupled transitions so snapshots die fast: (1) once a
        load's device write completes, its staging snapshot is swapped
        into the active buffer and *dropped* — snapshots live only for
        the actual write (ms), not the modeled transfer window, so at
        most a couple of extra slot-buffer copies exist transiently
        during a load burst; (2) the cache entry flips READY only after
        the modeled ``h2d_gbps`` time also elapsed. Swaps are FIFO:
        each snapshot was built on the previous one, so activating the
        first *unswapped* head is monotone and never exposes a later
        in-flight write.
        """
        now = self.now()
        for aid in list(self._pending_loads):
            staged, leaves, t_ready = self._pending_loads[aid]
            if staged is not None:
                if not all(x.is_ready() for x in leaves):
                    break           # FIFO: later writes chain on this one
                self.lora = staged
                self._record_load(aid)
                self._pending_loads[aid] = (None, (), t_ready)
            if now >= t_ready:
                del self._pending_loads[aid]
                self.cache.mark_ready(aid)

    def flush_loads(self) -> None:
        """Barrier: block until every in-flight load lands (warmup /
        stats resets — a rebased clock must not strand a modeled
        ready-time in the old epoch)."""
        if not self._pending_loads:
            return
        jax.block_until_ready(self._lora_staging)
        self.lora = self._lora_staging
        for aid in list(self._pending_loads):
            del self._pending_loads[aid]
            self._record_load(aid)
            self.cache.mark_ready(aid)

    def _record_load(self, aid: int) -> None:
        """An ``adapter.load`` span, from the write's dispatch to the
        swap of its snapshot into the active arena."""
        held = self._load_spans.pop(aid, None)
        if held is not None:
            t, parent, attrs = held
            self.spans.record("adapter.load", t, time.monotonic(),
                              parent=parent, **attrs)

    def _evict_adapter(self, info: AdapterInfo) -> None:
        slot = self.slot_of.pop(info.adapter_id)
        self.free_slots.append(slot)
        # LOADING entries are never eviction candidates, so a pending
        # load here is unreachable; drop it anyway to stay consistent.
        self._pending_loads.pop(info.adapter_id, None)
        self._load_spans.pop(info.adapter_id, None)

    # ------------------------------------------------------- jit'd steps
    # ``self._lora_backend`` is a resolved Python constant captured by
    # these jit'd closures, so one engine = one backend = one coherent
    # jit cache (no per-call retraces on the backend choice).
    def _decode_fn(self, params, lora, tokens, kv, cache_len,
                   adapter_slot):
        return api.decode_step(self.cfg, params, tokens, kv, cache_len,
                               lora=lora, adapter_idx=adapter_slot,
                               lora_backend=self._lora_backend)

    def _decode_paged_fn(self, params, lora, tokens, kv_pages,
                         page_table, cache_len, adapter_slot):
        return api.decode_step_paged(self.cfg, params, tokens, kv_pages,
                                     page_table, cache_len, lora=lora,
                                     adapter_idx=adapter_slot,
                                     lora_backend=self._lora_backend)

    def _fused_fn(self, params, lora, tokens, kv, cache_len, active,
                  positions, adapter_slot, budget, stop, temp, topk,
                  topp, seeds, *, K, all_greedy):
        return api.decode_fused(
            self.cfg, params, tokens, kv, cache_len, active, positions,
            budget, stop, temp, topk, topp, seeds, n_steps=K,
            all_greedy=all_greedy, max_ctx=self.ecfg.max_len, lora=lora,
            adapter_idx=adapter_slot, lora_backend=self._lora_backend)

    def _fused_paged_fn(self, params, lora, tokens, kv_pages, page_table,
                        cache_len, active, positions, adapter_slot,
                        budget, stop, temp, topk, topp, seeds, *, K,
                        all_greedy):
        return api.decode_fused_paged(
            self.cfg, params, tokens, kv_pages, page_table, cache_len,
            active, positions, budget, stop, temp, topk, topp, seeds,
            n_steps=K, all_greedy=all_greedy, max_ctx=self.ecfg.max_len,
            lora=lora, adapter_idx=adapter_slot,
            lora_backend=self._lora_backend)

    def _spec_fn(self, params, lora, tokens, kv, draft_kv, cache_len,
                 active, positions, adapter_slot, budget, stop, temp,
                 topk, topp, seeds, *, spec_k, n_rounds, all_greedy):
        return api.decode_spec_fused(
            self.cfg, params, self.draft_cfg, self.draft_params, tokens,
            kv, draft_kv, cache_len, active, positions, budget, stop,
            temp, topk, topp, seeds, spec_k=spec_k, n_rounds=n_rounds,
            all_greedy=all_greedy, max_ctx=self.ecfg.max_len, lora=lora,
            adapter_idx=adapter_slot, lora_backend=self._lora_backend)

    def _spec_paged_fn(self, params, lora, tokens, kv_pages, page_table,
                       draft_kv, cache_len, active, positions,
                       adapter_slot, budget, stop, temp, topk, topp,
                       seeds, *, spec_k, n_rounds, all_greedy):
        return api.decode_spec_fused_paged(
            self.cfg, params, self.draft_cfg, self.draft_params, tokens,
            kv_pages, page_table, draft_kv, cache_len, active,
            positions, budget, stop, temp, topk, topp, seeds,
            spec_k=spec_k, n_rounds=n_rounds, all_greedy=all_greedy,
            max_ctx=self.ecfg.max_len, lora=lora,
            adapter_idx=adapter_slot, lora_backend=self._lora_backend)

    def _draft_catchup_fn(self, draft_params, tokens, draft_kv, start,
                          seq_len, S):
        del S
        _, dkv = api.verify(self.draft_cfg, draft_params, tokens,
                            draft_kv, start, seq_len=seq_len)
        return dkv

    def _prefill_fn(self, params, lora, tokens, adapter_slot, last_pos,
                    S):
        del S
        return api.prefill(self.cfg, params, tokens, lora=lora,
                           adapter_idx=adapter_slot, last_pos=last_pos,
                           lora_backend=self._lora_backend)

    def _prefill_paged_fn(self, params, lora, tokens, kv_pages,
                          page_table, start, seq_len, adapter_slot, S):
        del S
        return api.prefill_paged(self.cfg, params, tokens, kv_pages,
                                 page_table, start, seq_len, lora=lora,
                                 adapter_idx=adapter_slot,
                                 lora_backend=self._lora_backend)

    def _prefill_lora(self):
        """LoRA tensors for *prefill*. aLoRA prefix mode computes prompt
        KV with the base model (the adapter activates at generation), so
        cached prefix pages are adapter-invariant; decode is untouched."""
        return None if self.ecfg.prefix_mode == "alora" else self.lora

    # ------------------------------------------------------- page moves
    def _alloc_page(self, req_id: int, now: float) -> Optional[int]:
        """One physical page for ``req_id``; None when HBM is truly full.

        The pool gate runs first: if the unified pool has no free page,
        idle prefix-cache pages (refcount 1, LRU) are reclaimed, then
        the adapter cache is asked to shrink (§4.1 dynamic downsizing,
        second-tier protection for queued adapters applies). Cached
        prefixes and resident adapters are both *accounted* idle memory
        that live requests displace on demand. Physical pages cannot
        run out before pool pages — the page arrays are sized to the
        whole pool.
        """
        ps = self.pool.page_size
        if self.pool.free_tokens < ps and self.prefix is not None:
            self.free_pages.extend(self.prefix.evict_lru(1))
        if self.pool.free_tokens < ps and not self.cache.shrink_for_requests(
                ps, now, self.sched.queued_adapter_ids()):
            return None
        if not self.free_pages:
            return None
        try:
            self.pool.reserve_request_pages(req_id, 1)
        except PoolError:
            return None
        return self.free_pages.pop()

    def _grow_slot(self, slot: int, n_pages: int, now: float) -> bool:
        """Grow a slot's page list by ``n_pages``; all-or-nothing."""
        req = self.slot_req[slot]
        got = []
        for _ in range(n_pages):
            pid = self._alloc_page(req.req_id, now)
            if pid is None:
                for p in got:
                    self.free_pages.append(p)
                if got:
                    self.pool.shrink_request(
                        req.req_id, len(got) * self.pool.page_size)
                return False
            got.append(pid)
        base = len(self.slot_pages[slot])
        self.slot_pages[slot].extend(got)
        self.page_table[slot, base:base + len(got)] = got
        self._page_table_dirty = True
        return True

    def _free_slot_pages(self, slot: int, req_id: int) -> None:
        if not self.paged:
            return
        shared = self.slot_shared[slot]
        if shared:
            # Drop the slot's references; pages only return to the
            # physical free list once the prefix tree also lets go
            # (eviction) — the tree holds its own pool ref, so this
            # normally frees nothing and the prefix stays resident.
            self.free_pages.extend(self.pool.release_shared(shared))
            self.slot_shared[slot] = []
            shared_set = set(shared)
            private = [p for p in self.slot_pages[slot]
                       if p not in shared_set]
        else:
            private = self.slot_pages[slot]
        self.free_pages.extend(private)
        self.slot_pages[slot] = []
        self.page_table[slot, :] = 0
        self._page_table_dirty = True
        self.pool.release_request(req_id)

    def _stash_progress(self, req: Request) -> None:
        """Squash/preemption: move the request's already-streamed tokens
        and TBT records onto the request itself so the requeue keeps
        them (re-execution regenerates the same prefix deterministically
        and never re-streams it — the handle dedups by position)."""
        rid = req.req_id
        req.stash_progress(self.outputs.pop(rid, None),
                           self._tbts.pop(rid, None),
                           self._last_tok.pop(rid, None))

    def _squash_slot(self, slot: int) -> None:
        """Free a slot and requeue its request (squash path: bypass
        misprediction or page preemption — the request re-executes,
        keeping its streamed prefix)."""
        req = self.slot_req[slot]
        self.active[slot] = False
        self.slot_req[slot] = None
        self.batch_epoch += 1
        if self.spec:
            self._draft_len[slot] = 0    # draft KV freed with the slot
        self._stash_progress(req)
        self._free_slot_pages(slot, req.req_id)
        self.sched.on_squash(req, self.now())

    def _preempt(self, slot: int) -> None:
        """Out of pages mid-flight: squash, and count it."""
        self.n_preempted += 1
        self._squash_slot(slot)

    # ---------------------------------------------------------- lifecycle
    def submit(self, req: Request, *,
               sampling: Optional[SamplingParams] = None,
               on_token=None, ttl: Optional[float] = None,
               ) -> RequestHandle:
        """Non-blocking: enqueue with the scheduler; no device work.
        Returns the request's handle (DESIGN §3 serving surface)."""
        now = self.now()
        handle = prepare_request(req, self, now, sampling, on_token, ttl)
        self.handles[req.req_id] = handle
        if req.deadline is not None:
            self._deadlines_armed = True
        self.sched.submit(req, now)
        if self.h_prefetch is not None:
            self.h_prefetch.observe_arrival(req.adapter_id, now)
        return handle

    def cancel(self, handle) -> bool:
        """Cancel a request. Queued / LOADING-deferred requests release
        their adapter pin and terminate immediately; RUNNING requests
        are finalised at the next step boundary (the in-flight jit'd
        decode cannot be interrupted). False once already terminal."""
        req = handle.req if isinstance(handle, RequestHandle) else handle
        if req.terminal:
            return False
        now = self.now()
        if any(r is req for r in self.slot_req):
            req.cancel_requested = True    # step() sweeps it
            self._cancel_armed = True      # fused loop: force a sweep
            return True
        if self.sched.cancel(req, now):
            self._finalize_unplaced(req, RequestState.CANCELLED, now)
            return True
        # Mid-transition race (e.g. cancelled from an on_token callback
        # while being placed): mark it; the step sweep resolves it.
        req.cancel_requested = True
        self._cancel_armed = True
        self._cancel_races.append(req)
        return True

    def _finalize_unplaced(self, req: Request, state: RequestState,
                           now: float) -> None:
        """Terminal transition for a request that never held a slot
        (queued cancel / queue-side deadline expiry). The scheduler
        already released the adapter pin; queued requests hold no pool
        reservation or quota charges."""
        req.state = state
        req.finish_time = now
        self._record_request_spans(req)
        if state is RequestState.CANCELLED:
            self.n_cancelled += 1
        else:
            self.n_expired += 1

    def _record_request_spans(self, req: Request) -> None:
        """A terminal request's ``request.queued`` (arrival to first
        admission), ``request.prefill`` (admission to first token, under
        the ``engine.prefill`` span that served it) and
        ``request.decode`` (first token to last), from the times it
        holds on the engine clock."""
        parent = self._prefill_span.pop(req.req_id, None)
        if not self.spans.on:
            return
        rid, t0 = req.req_id, self.t0
        attrs = dict(adapter=req.adapter_id,
                     rank=self.catalog.rank_of(req.adapter_id),
                     prompt=req.input_len, tokens=req.generated,
                     state=req.state.value, squashes=req.squash_count)
        admitted, first = req.first_scheduled_time, req.first_token_time
        self.spans.record(
            "request.queued", t0 + req.arrival_time,
            t0 + (admitted if admitted is not None else req.finish_time),
            req_id=rid, adapter_wait=req.adapter_load_wait, **attrs)
        if admitted is not None and first is not None:
            self.spans.record("request.prefill", t0 + admitted, t0 + first,
                              parent=parent, req_id=rid, **attrs)
        if first is not None:
            self.spans.record("request.decode", t0 + first,
                              t0 + req.finish_time, req_id=rid, **attrs)

    # ------------------------------------------------------ token delivery
    def _record_token(self, req: Request, pos: int, tok: int,
                      now: float) -> None:
        """Record (and stream) the token at output position ``pos``.

        Re-executed positions after a squash overwrite in place and are
        *not* re-streamed or re-timed: the TBT of the first genuinely
        new token is measured from the last token the user actually saw
        (``last_stream_time`` survives the requeue)."""
        rid = req.req_id
        out = self.outputs[rid]
        if pos < len(out):
            out[pos] = tok         # deterministic regeneration of prefix
            return
        out.append(tok)
        if pos >= 1:
            tbts = self._tbts[rid]
            if len(tbts) < pos:
                tbts.append(now - self._last_tok[rid])
        self._last_tok[rid] = now
        handle = self.handles.get(rid)
        if handle is not None and not req.cancel_requested:
            # A cancelled request's slot is finalised at the next step
            # boundary, but tokens already in flight (the seed loop's
            # current step; up to two undrained horizons on the fused
            # loop) are still recorded internally — they must not reach
            # the handle after cancel() returned.
            handle._push(pos, tok)

    def _free_slots(self) -> list[int]:
        """Slots a new placement may take: off the active mask *and*
        holding no request. A slot can be inactive yet occupied — a
        chunked prefill in progress, or a MIGRATING request whose KV is
        mid-handoff — and clobbering either would corrupt its pages."""
        return [s for s in range(self.ecfg.max_slots)
                if not self.active[s] and self.slot_req[s] is None]

    def _place_batch(self, reqs: list[Request]) -> None:
        """Batched prefill admission: one jit'd prefill over a (B, S)
        bucket covers every request admitted this iteration.

        Right-padding is safe under causal attention (positions past
        ``last_pos`` never influence the selected logits), and padded
        batch rows run masked garbage exactly like inactive decode
        slots. Buckets are powers of two so recompiles stay bounded.
        """
        if not reqs:
            return
        e = self.ecfg
        if self.paged and e.prefill_chunk_tokens > 0:
            big = [r for r in reqs if r.input_len > e.prefill_chunk_tokens]
            if big:
                reqs = [r for r in reqs
                        if r.input_len <= e.prefill_chunk_tokens]
                # Chunks write their own K/V: placement is the pages.
                with self.spans.span("engine.kv_place") as sp:
                    if sp:
                        sp.set(pages=sum(self.pool.pages_for(r.input_len)
                                         for r in big), programs=0)
                    self._start_chunked(big)
            if not reqs:
                return
        if self.prefix is not None:
            return self._place_batch_prefix(reqs)
        free = self._free_slots()
        if self.paged:
            # Allocate each request's prompt pages up front; a request
            # whose prompt cannot get pages even after shrinking the
            # cache bounces straight back to its queue (squash path).
            now = self.now()
            placed = []
            for req in reqs:
                slot = free[len(placed)]
                self.slot_req[slot] = req
                if self._grow_slot(slot, self.pool.pages_for(req.input_len),
                                   now):
                    placed.append(req)
                else:
                    self.slot_req[slot] = None
                    self.n_preempted += 1
                    self.sched.on_squash(req, now)
            reqs = placed
            if not reqs:
                return
        S = 1 << max(3, (max(r.input_len for r in reqs) - 1).bit_length())
        B = 1 << max(0, (len(reqs) - 1).bit_length())
        toks = np.zeros((B, S), np.int32)
        last_pos = np.zeros((B,), np.int32)
        lslots = np.zeros((B,), np.int32)
        for i, req in enumerate(reqs):
            toks[i, :req.input_len] = self._prompt_tokens(req)
            last_pos[i] = req.input_len - 1
            lslots[i] = self.slot_of[req.adapter_id]
        with self._prefill_span_for(reqs, B, S):
            with self._act_scope():
                logits, (k_new, v_new) = self._get_prefill_jit(B, S)(
                    self.params, self._prefill_lora(), jnp.asarray(toks),
                    jnp.asarray(lslots), jnp.asarray(last_pos), S)
            first_toks = self._first_tokens(logits, reqs, B)
        now = self.now()
        # Each prompt's K/V into its slot, and its row seated with the
        # first token.
        with self.spans.span("engine.kv_place") as sp:
            if self.paged:
                # One program writes whole pages; a pad row and the
                # pages past a prompt point at the trash page 0.
                rows = np.zeros((B, -(-S // self.pool.page_size)),
                                np.int32)
                for i in range(len(reqs)):
                    pages = self.slot_pages[free[i]]
                    rows[i, :len(pages)] = pages
                self.kv_pages = self._get_place_pages_jit(B, S)(
                    self.kv_pages, k_new, v_new, jnp.asarray(rows))
                programs = 1
            else:
                k, v = self.kv
                for i, req in enumerate(reqs):
                    L = req.input_len
                    k = k.at[:, free[i], :L].set(k_new[:, i, :L])
                    v = v.at[:, free[i], :L].set(v_new[:, i, :L])
                self.kv = (k, v)
                programs = 2 * len(reqs)
            if sp:
                sp.set(pages=sum(self.pool.pages_for(r.input_len)
                                 for r in reqs) if self.paged else 0,
                       programs=programs)
            for i, req in enumerate(reqs):
                slot = free[i]
                self.active[slot] = True
                self.slot_req[slot] = req
                L = req.input_len
                first = int(first_toks[i])
                self.tokens = self.tokens.at[slot, 0].set(first)
                self.cache_len = self.cache_len.at[slot].set(L)
                self.adapter_slot = self.adapter_slot.at[slot].set(
                    int(lslots[i]))
                req.generated = 1
                rid = req.req_id
                if req.preserved_tokens:
                    # Squash survivor: restore the streamed prefix and its
                    # latency records; re-execution regenerates (and the
                    # handle ignores) positions the user already has.
                    self.outputs[rid] = list(req.preserved_tokens)
                    self._tbts[rid] = list(req.preserved_tbts)
                    if req.last_stream_time is not None:
                        self._last_tok[rid] = req.last_stream_time
                else:
                    self.outputs[rid] = []
                    self._tbts[rid] = []
                    req.first_token_time = now
                self._record_token(req, 0, first, now)
        self.batch_epoch += 1      # admission boundary: device batch
        for i, req in enumerate(reqs):   # state rebuilds next dispatch
            if req.done or self._hit_stop(req):
                self._finish(free[i])

    def _prompt_tokens(self, req: Request) -> np.ndarray:
        """The request's prompt token ids, vocab-folded. Trace-driven
        workloads carry lengths, not token material: fabricate a
        deterministic prompt (identical across re-executions and across
        the prefix-on/off arms)."""
        if req.prompt is not None:
            return np.asarray(req.prompt, np.int32) % self.cfg.vocab_size
        return (np.arange(req.input_len) % self.cfg.vocab_size) \
            .astype(np.int32)

    def _prefill_span_for(self, reqs: list[Request], B: int, S: int):
        """The ``engine.prefill`` span of one prefill call, from its
        dispatch until its first tokens are on the host; each request
        it serves first is remembered as its ``request.prefill``'s
        parent."""
        if not self.spans.on:
            return NULL_SPAN
        sp = self.spans.span("engine.prefill", B=B, S=S,
                             req_ids=[r.req_id for r in reqs])
        for r in reqs:
            self._prefill_span.setdefault(r.req_id, sp.id)
        return sp

    def _first_tokens(self, logits, reqs: list[Request], B: int
                      ) -> np.ndarray:
        """The first token of each prefilled row, on the host."""
        if self._all_greedy(reqs):
            return np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        return np.asarray(self._sample_jit(
            logits, *self._sampling_arrays(reqs, B, first=True)))

    def _sig_of(self, req: Request) -> int:
        """KV signature a cached page is keyed by (see EngineConfig
        .prefix_mode): the adapter in exact mode, one shared tree in
        aLoRA mode (prompt KV is base-model-only there)."""
        return -1 if self.ecfg.prefix_mode == "alora" else req.adapter_id

    def _place_batch_prefix(self, reqs: list[Request]) -> None:
        """Prefix-cache admission (paged): match each prompt against
        the radix tree, map the shared pages into the slot's page
        table, COW-fork a mid-page divergence, then batch-prefill only
        the suffixes via ``prefill_paged`` (hits and misses share the
        one jit — a miss is simply start=0). Freshly computed full
        prompt pages are adopted into the tree afterwards."""
        now = self.now()
        free = self._free_slots()
        ps = self.pool.page_size
        placed, slots, starts, prompts = [], [], [], []
        forks = self.n_cow_forks
        # Mapping each prompt onto pages (shared, forked, allocated) is
        # this path's page placement: the prefill writes the K/V itself.
        with self.spans.span("engine.kv_place") as kv:
            for req in reqs:
                slot = free[len(placed)]
                toksr = self._prompt_tokens(req)
                L = req.input_len
                sig = self._sig_of(req)
                # Cap the match at L-1: the last prompt position always
                # prefills, so first-token logits are computed fresh.
                pages, m, ppage, plen = self.prefix.match(sig, toksr, L - 1)
                # Reference everything we plan to read *before* allocating
                # (allocation pressure may evict refcount-1 tree pages —
                # ours must not be candidates).
                self.pool.share_pages(pages)
                if ppage is not None:
                    self.pool.share_pages([ppage])
                self.slot_req[slot] = req
                self.slot_pages[slot] = list(pages)
                self.slot_shared[slot] = list(pages)
                if pages:
                    self.page_table[slot, :len(pages)] = pages
                    self._page_table_dirty = True
                n_priv = self.pool.pages_for(L) - len(pages)
                if not self._grow_slot(slot, n_priv, now):
                    # Bounce: undo the mapping and requeue (squash path).
                    self.free_pages.extend(self.pool.release_shared(pages))
                    if ppage is not None:
                        self.free_pages.extend(
                            self.pool.release_shared([ppage]))
                    self.slot_pages[slot] = []
                    self.slot_shared[slot] = []
                    self.page_table[slot, :] = 0
                    self.slot_req[slot] = None
                    self.n_preempted += 1
                    self.sched.on_squash(req, now)
                    continue
                start = m
                if ppage is not None:
                    # Divergence mid-page: copy the agreeing head of the
                    # cached page into the request's first private page
                    # (which the suffix prefill then extends in place).
                    with self.spans.span("engine.cow_fork"):
                        dst = self.slot_pages[slot][len(pages)]
                        kp, vp = self.kv_pages
                        kp = kp.at[:, dst, :plen].set(kp[:, ppage, :plen])
                        vp = vp.at[:, dst, :plen].set(vp[:, ppage, :plen])
                        self.kv_pages = (kp, vp)
                    self.free_pages.extend(self.pool.release_shared([ppage]))
                    self.n_cow_forks += 1
                    start = m + plen
                self.prefix_lookup_tokens += L
                self.prefix_hit_tokens += start
                if start:
                    self.n_prefix_hits += 1
                placed.append(req)
                slots.append(slot)
                starts.append(start)
                prompts.append(toksr)
            if kv:
                kv.set(pages=sum(len(self.slot_pages[s]) for s in slots),
                       programs=2 * (self.n_cow_forks - forks))
        if not placed:
            return
        S = 1 << max(3, (max(r.input_len - s for r, s in
                             zip(placed, starts)) - 1).bit_length())
        B = 1 << max(0, (len(placed) - 1).bit_length())
        toks = np.zeros((B, S), np.int32)
        start_arr = np.zeros((B,), np.int32)
        seq_len = np.ones((B,), np.int32)    # pad rows: 1 trash token
        lslots = np.zeros((B,), np.int32)
        row_table = np.zeros((B, self.pages_per_slot), np.int32)
        for i, req in enumerate(placed):
            s, L = starts[i], req.input_len
            toks[i, :L - s] = prompts[i][s:]
            start_arr[i] = s
            seq_len[i] = L - s
            lslots[i] = self.slot_of[req.adapter_id]
            row_table[i] = self.page_table[slots[i]]
        if self.plan is not None:
            # The COW fork above host-updates the (donated) pool —
            # re-commit so the explicit in_shardings hold exactly.
            self.kv_pages = self._commit(self.kv_pages, self._kv_sh)
        with self._prefill_span_for(placed, B, S):
            with self._act_scope():
                logits, self.kv_pages = self._get_prefill_paged_jit(B, S)(
                    self.params, self._prefill_lora(), jnp.asarray(toks),
                    self.kv_pages, jnp.asarray(row_table),
                    jnp.asarray(start_arr), jnp.asarray(seq_len),
                    jnp.asarray(lslots), S)
            first_toks = self._first_tokens(logits, placed, B)
        now = self.now()
        for i, req in enumerate(placed):
            slot = slots[i]
            self.active[slot] = True
            L = req.input_len
            first = int(first_toks[i])
            self.tokens = self.tokens.at[slot, 0].set(first)
            self.cache_len = self.cache_len.at[slot].set(L)
            self.adapter_slot = self.adapter_slot.at[slot].set(
                int(lslots[i]))
            req.generated = 1
            rid = req.req_id
            if req.preserved_tokens:
                self.outputs[rid] = list(req.preserved_tokens)
                self._tbts[rid] = list(req.preserved_tbts)
                if req.last_stream_time is not None:
                    self._last_tok[rid] = req.last_stream_time
            else:
                self.outputs[rid] = []
                self._tbts[rid] = []
                req.first_token_time = now
            self._record_token(req, 0, first, now)
            self._adopt_prompt_pages(slot, req, prompts[i])
        self.batch_epoch += 1
        for i, req in enumerate(placed):
            if req.done or self._hit_stop(req):
                self._finish(slots[i])

    def _adopt_prompt_pages(self, slot: int, req: Request,
                            toks: np.ndarray) -> None:
        """Hand the request's fully-written prompt pages to the radix
        tree. Accounting is a conserving transfer per adopted page:
        the request's hold shrinks by one page, the shared ledger gains
        it (tree ref), and the slot takes its mapping ref — the pages
        it keeps reading are now shared, tracked in ``slot_shared``."""
        n_full = req.input_len // self.pool.page_size
        if n_full == 0:
            return
        ps = self.pool.page_size
        pages = self.slot_pages[slot][:n_full]
        adopted = self.prefix.insert(self._sig_of(req), toks[:n_full * ps],
                                     pages)
        for pid in adopted:
            self.pool.shrink_request(req.req_id, ps)
            self.pool.add_shared_page(pid)
            self.pool.share_pages([pid])
            self.slot_shared[slot].append(pid)

    # ------------------------------------------------- chunked prefill
    def _start_chunked(self, reqs: list[Request]) -> None:
        """Admit long prompts onto slots without prefilling them yet:
        the slot takes the request and all its prompt pages up front
        (so the memory admission decision is identical to the
        monolithic path — a prompt that cannot get pages bounces via
        the squash path exactly as before), then ``_advance_chunked``
        runs one ``prefill_chunk_tokens`` chunk per engine step."""
        now = self.now()
        free = self._free_slots()
        n_placed = 0
        for req in reqs:
            slot = free[n_placed]
            self.slot_req[slot] = req
            if self._grow_slot(slot, self.pool.pages_for(req.input_len),
                               now):
                # The decode dispatches that run between chunks write
                # their per-row KV at ``cache_len[row]`` for *every*
                # slot, masked or not — inactive rows are harmless only
                # because their page-table row points at the trash
                # page. So the real row lives privately here until
                # activation; the global table keeps the trash mapping.
                row = self.page_table[slot].copy()
                self.page_table[slot, :] = 0
                self._page_table_dirty = True
                self._chunked[slot] = {
                    "req": req, "prompt": self._prompt_tokens(req),
                    "done": 0, "table": row}
                self.n_chunked_prefills += 1
                n_placed += 1
            else:
                self.slot_req[slot] = None
                self.n_preempted += 1
                self.sched.on_squash(req, now)

    def _advance_chunked(self) -> None:
        """Run one prefill chunk for every mid-prefill slot (each is a
        B=1 suffix-prefill call: chunk tokens attend to all previously
        written positions, so the final logits — and therefore every
        token — match the monolithic prefill). The last chunk's logits
        produce the first token and the slot joins the decode batch."""
        if not self._chunked:
            return
        if self.plan is not None:
            self.kv_pages = self._commit(self.kv_pages, self._kv_sh)
        chunk = self.ecfg.prefill_chunk_tokens
        for slot in sorted(self._chunked):
            st = self._chunked[slot]
            req = st["req"]
            done = st["done"]
            L = req.input_len
            n = min(chunk, L - done)
            S = 1 << max(3, (n - 1).bit_length())
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = st["prompt"][done:done + n]
            row_table = st["table"][None, :]
            lslot = self.slot_of[req.adapter_id]
            with self._prefill_span_for([req], 1, S):
                with self._act_scope():
                    logits, self.kv_pages = self._get_prefill_paged_jit(
                        1, S)(
                        self.params, self._prefill_lora(),
                        jnp.asarray(toks), self.kv_pages,
                        jnp.asarray(row_table),
                        jnp.asarray([done], np.int32),
                        jnp.asarray([n], np.int32),
                        jnp.asarray([lslot], np.int32), S)
                st["done"] = done + n
                if st["done"] >= L:
                    del self._chunked[slot]
                    self.page_table[slot] = st["table"]
                    self._page_table_dirty = True
                    self._activate_chunked(slot, req, logits, lslot)

    def _activate_chunked(self, slot: int, req: Request, logits,
                          lslot: int) -> None:
        """Last chunk landed: sample the first token and join the
        decode batch — the same bookkeeping the monolithic placement
        runs after its prefill call."""
        if self._all_greedy([req]):
            first = int(np.asarray(
                jnp.argmax(logits[0:1], axis=-1).astype(jnp.int32))[0])
        else:
            first = int(np.asarray(self._sample_jit(
                logits[0:1], *self._sampling_arrays([req], 1,
                                                    first=True)))[0])
        now = self.now()
        self.active[slot] = True
        self.tokens = self.tokens.at[slot, 0].set(first)
        self.cache_len = self.cache_len.at[slot].set(req.input_len)
        self.adapter_slot = self.adapter_slot.at[slot].set(lslot)
        req.generated = 1
        rid = req.req_id
        if req.preserved_tokens:
            self.outputs[rid] = list(req.preserved_tokens)
            self._tbts[rid] = list(req.preserved_tbts)
            if req.last_stream_time is not None:
                self._last_tok[rid] = req.last_stream_time
        else:
            self.outputs[rid] = []
            self._tbts[rid] = []
            req.first_token_time = now
        self._record_token(req, 0, first, now)
        self.batch_epoch += 1
        if req.done or self._hit_stop(req):
            self._finish(slot)

    # ------------------------------------------- KV handoff (disagg)
    def begin_migration(self, req: Request) -> Optional[dict]:
        """Detach ``req`` from decode and serialize its KV for a
        prefill->decode handoff (serving/disagg.py). Returns the
        shipment dict, or None when the request is not in a migratable
        state (mid-chunk-prefill, already finished, or not here).

        The slot is *not* freed: slot_req stays set (so no placement
        can take the slot), the request's pool holds and shared-page
        refs stay live (so neither the prefix tree's LRU eviction nor
        the adapter cache's shrink can reclaim the pages mid-copy), and
        the request enters MIGRATING. ``complete_migration`` (transfer
        landed) or ``abort_migration`` (cancel/deadline) releases it.
        """
        self._sync_inflight()
        slot = next((i for i, r in enumerate(self.slot_req) if r is req),
                    None)
        if slot is None or slot in self._chunked \
                or not self.active[slot] or req.terminal:
            return None
        cache_len = req.input_len + req.generated - 1
        pending = int(np.asarray(self.tokens)[slot, 0])
        if self.paged:
            n_pages = self.pool.pages_for(cache_len)
            pages = self.slot_pages[slot][:n_pages]
            kp, vp = self.kv_pages
            idx = jnp.asarray(np.asarray(pages, np.int32))
            k_pay = np.asarray(kp[:, idx])
            v_pay = np.asarray(vp[:, idx])
        else:
            k, v = self.kv
            k_pay = np.asarray(k[:, slot, :cache_len])
            v_pay = np.asarray(v[:, slot, :cache_len])
        rid = req.req_id
        shipment = {
            "req": req,
            "cache_len": cache_len,
            "pending_token": pending,
            "paged": self.paged,
            "k": k_pay, "v": v_pay,
            "nbytes": int(k_pay.nbytes + v_pay.nbytes),
            # Streamed-token state moves with the request so the decode
            # replica's bookkeeping (dedup positions, TBT reference
            # point) continues exactly where the source stopped.
            "outputs": self.outputs.pop(rid, []),
            "tbts": self._tbts.pop(rid, []),
            "last_tok": self._last_tok.pop(rid, None),
            "handle": self.handles.pop(rid, None),
        }
        req.state = RequestState.MIGRATING
        self.active[slot] = False
        self._migrating[rid] = slot
        self.batch_epoch += 1
        return shipment

    def complete_migration(self, req: Request) -> None:
        """The shipment landed on the decode replica: release this
        end — adapter pin (scheduler on_finish), KV pages, the slot."""
        slot = self._migrating.pop(req.req_id, None)
        if slot is None:
            return
        now = self.now()
        self.sched.on_finish(req, now)
        self._free_slot_pages(slot, req.req_id)
        self.slot_req[slot] = None
        self.batch_epoch += 1
        self.n_kv_exports += 1

    def abort_migration(self, req: Request,
                        state: RequestState = RequestState.CANCELLED,
                        shipment: Optional[dict] = None) -> bool:
        """Cancel / deadline expiry while MIGRATING: finalize on the
        source (the destination never saw the request). The shipment,
        when passed back, restores the streamed-token records that
        export popped, so ``handle.result()`` still reports the tokens
        and TBTs the user actually saw."""
        slot = self._migrating.pop(req.req_id, None)
        if slot is None:
            return False
        if shipment is not None:
            rid = req.req_id
            self.outputs[rid] = list(shipment["outputs"])
            self._tbts[rid] = list(shipment["tbts"])
            if shipment["handle"] is not None:
                self.handles[rid] = shipment["handle"]
        self._finalize_slot(slot, state)
        return True

    def import_request_kv(self, shipment: dict) -> bool:
        """Decode-replica end of the handoff: pin the adapter, reserve
        pages in this pool, scatter the shipped KV in, restore the
        streamed-token state and join the decode batch. Returns False
        (with nothing held) when a slot, the adapter, or pages are not
        available — the caller retries next step.

        The adapter load this may trigger is flushed synchronously
        (``flush_loads``): the modeled H2D time already overlapped the
        KV transfer on the link, and the disagg router pre-warms decode
        replicas, so a blocking flush here is the rare path."""
        self._sync_inflight()
        req = shipment["req"]
        free = self._free_slots()
        if not free:
            return False
        slot = free[0]
        now = self.now()
        aid = req.adapter_id
        rid = req.req_id
        cache_len = shipment["cache_len"]
        protect = self.sched.queued_adapter_ids() - {aid}
        need = (self.pool.pages_for(cache_len) * self.pool.page_size
                if self.paged else req.input_len + req.predicted_output)
        extra = (0 if self.cache.resident(aid)
                 else self.catalog.infos[aid].size_tokens)
        if not self.cache.shrink_for_requests(need + extra, now, protect):
            return False
        try:
            self.cache.acquire(aid, now, queued_protect=protect)
        except PoolError:
            return False
        if not self.cache.is_ready(aid):
            self.flush_loads()
        self.slot_req[slot] = req
        if self.paged:
            if not self._grow_slot(slot, self.pool.pages_for(cache_len),
                                   now):
                self.slot_req[slot] = None
                self.cache.release(aid, now)
                return False
            pages = self.slot_pages[slot]
            idx = jnp.asarray(np.asarray(pages, np.int32))
            kp, vp = self.kv_pages
            kp = kp.at[:, idx].set(jnp.asarray(shipment["k"]))
            vp = vp.at[:, idx].set(jnp.asarray(shipment["v"]))
            self.kv_pages = (kp, vp)
            self._page_table_dirty = True
        else:
            try:
                self.pool.reserve_request(rid, need)
            except PoolError:
                self.slot_req[slot] = None
                self.cache.release(aid, now)
                return False
            req.reserved_tokens = need
            k, v = self.kv
            k = k.at[:, slot, :cache_len].set(jnp.asarray(shipment["k"]))
            v = v.at[:, slot, :cache_len].set(jnp.asarray(shipment["v"]))
            self.kv = (k, v)
        req.adapter_ref = True
        self.active[slot] = True
        self.tokens = self.tokens.at[slot, 0].set(
            shipment["pending_token"])
        self.cache_len = self.cache_len.at[slot].set(cache_len)
        self.adapter_slot = self.adapter_slot.at[slot].set(
            self.slot_of[aid])
        self.outputs[rid] = list(shipment["outputs"])
        self._tbts[rid] = list(shipment["tbts"])
        if shipment["last_tok"] is not None:
            self._last_tok[rid] = shipment["last_tok"]
        if shipment["handle"] is not None:
            self.handles[rid] = shipment["handle"]
        req.state = RequestState.RUNNING
        if req.deadline is not None:
            self._deadlines_armed = True
        self.batch_epoch += 1
        self.n_kv_imports += 1
        self.kv_handoff_bytes += shipment["nbytes"]
        return True

    def _hit_stop(self, req: Request) -> bool:
        """Did the latest recorded token hit a SamplingParams stop id?"""
        sp = req.sampling
        if sp is None or not sp.stop_token_ids:
            return False
        return self.outputs[req.req_id][req.generated - 1] \
            in sp.stop_token_ids

    @staticmethod
    def _all_greedy(reqs) -> bool:
        """Host-side fast-path test: with no stochastic row in the
        batch, sampling is plain argmax — skip building the sampler
        inputs and the full sorted/softmax/Gumbel sampler call (the
        default path, and the one every greedy benchmark measures)."""
        return all(r is None or r.sampling is None or r.sampling.greedy
                   for r in reqs)

    def _sampling_arrays(self, reqs, B: int, first: bool = False):
        """Per-row sampler inputs for a prefill batch (``reqs`` list,
        ``first=True`` → all positions 0) or the decode batch
        (``reqs = slot_req``; inactive slots run greedy garbage)."""
        temp = np.zeros(B, np.float32)
        topk = np.zeros(B, np.int32)
        topp = np.ones(B, np.float32)
        seeds = np.zeros(B, np.uint32)
        pos = np.zeros(B, np.int32)
        for i, req in enumerate(reqs):
            if req is None:
                continue
            sp = req.sampling
            if sp is not None and not sp.greedy:
                temp[i] = sp.temperature
                topk[i] = sp.top_k
                topp[i] = sp.top_p
                seeds[i] = sp.seed_for(req.req_id)
            if not first:
                pos[i] = req.generated
        return (jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(topp),
                jnp.asarray(seeds), jnp.asarray(pos))

    def _finish(self, slot: int) -> None:
        # A cancel that raced the final token (e.g. issued from the
        # on_token callback that delivered it) still honours the
        # cancel() contract: the request terminates as CANCELLED.
        req = self.slot_req[slot]
        self._finalize_slot(slot, RequestState.CANCELLED
                            if req.cancel_requested
                            else RequestState.FINISHED)

    def _finalize_slot(self, slot: int, state: RequestState) -> None:
        """Terminal transition for the request occupying ``slot``:
        FINISHED, CANCELLED (handle.cancel on a running request) or
        EXPIRED (deadline passed mid-decode). All three release the
        slot, its KV pages and the scheduler/pool/cache holds; only
        FINISHED contributes a RequestRecord to the run metrics."""
        req = self.slot_req[slot]
        req.state = state
        now = self.now()
        req.finish_time = now
        self.sched.on_finish(req, now)
        self._free_slot_pages(slot, req.req_id)
        self.active[slot] = False
        self.slot_req[slot] = None
        self.batch_epoch += 1
        if self.spec:
            self._draft_len[slot] = 0    # draft KV freed with the slot
        tbts = self._tbts.pop(req.req_id, [])
        req.preserved_tbts = tbts    # handle.result() reads these
        self._last_tok.pop(req.req_id, None)
        self._record_request_spans(req)
        if state is RequestState.CANCELLED:
            self.n_cancelled += 1
            return
        if state is RequestState.EXPIRED:
            self.n_expired += 1
            return
        self.completed.append(req)
        self.records.append(RequestRecord(
            req_id=req.req_id, adapter_id=req.adapter_id,
            rank=self.catalog.rank_of(req.adapter_id),
            input_len=req.input_len, output_len=req.output_len,
            arrival=req.arrival_time,
            ttft=req.ttft() or 0.0, e2e=req.e2e() or 0.0,
            tbt_mean=float(np.mean(tbts)) if tbts else 0.0,
            tbt_p99=float(np.percentile(tbts, 99)) if tbts else 0.0,
            slowdown=1.0,   # no isolated-run oracle on the real engine
            squashes=req.squash_count, bypassed=req.bypassed,
            queue_wait=req.queue_wait() or 0.0,
            load_wait=req.adapter_load_wait))

    def _ensure_decode_pages(self, lens: Optional[np.ndarray] = None
                             ) -> None:
        """Grow each active slot to cover its next decode write; slots
        that cannot get a page even after shrinking the adapter cache
        are preempted (freed pages let the remaining slots proceed).
        The fused loop passes host-derived lengths so this never forces
        a device sync on the hot path."""
        now = self.now()
        if lens is None:
            lens = np.asarray(self.cache_len)
        ps = self.pool.page_size
        for slot in np.where(self.active)[0]:
            needed = int(lens[slot]) // ps + 1
            short = needed - len(self.slot_pages[slot])
            if short > 0 and not self._grow_slot(int(slot), short, now):
                self._preempt(int(slot))

    def _run_prefetchers(self, now: float) -> None:
        """Ahead-of-demand loads (paper §4.1). Dispatched async, they
        overlap the decode compute this same step launches; admission
        ran first, so prefetch never steals memory from the batch."""
        # Prefetch only fills *idle* slots: with every slot occupied it
        # would have to evict, fighting the cost-aware policy (§4.1:
        # prefetching must never evict useful entries). The budget is
        # the live free-slot count, re-read between prefetchers, so a
        # round can never load past the last idle slot. The simulator
        # has no slot cap, so this gate lives here, not in the
        # prefetchers.
        if not self.free_slots:
            return
        queued = self.sched.queued_requests_in_order()
        if self.q_prefetch is not None and queued:
            self.q_prefetch.run(queued, now, budget=len(self.free_slots))
        if self.h_prefetch is not None and self.free_slots:
            self.h_prefetch.run(
                now, queued_protect={r.adapter_id for r in queued},
                budget=len(self.free_slots))

    def _sweep_lifecycle(self, now: float) -> None:
        """Lifecycle enforcement at the step boundary: reap queued
        requests past their deadline, then finalise active slots whose
        request was cancelled (``handle.cancel()``) or expired."""
        if self._deadlines_armed:
            for req in self.sched.reap_expired(now):
                self._finalize_unplaced(req, RequestState.EXPIRED, now)
            # Disarm once no live request carries a deadline, so the
            # fused loop's micro-horizon re-opens after TTL'd work
            # drains (submit re-arms).
            self._deadlines_armed = (
                any(r is not None and r.deadline is not None
                    for r in self.slot_req)
                or any(r.deadline is not None
                       for r in self.sched.queued_requests_in_order()))
        self._cancel_armed = False      # re-armed below by racing cancels
        for slot in np.where(self.active)[0]:
            req = self.slot_req[slot]
            if req.cancel_requested:
                self._finalize_slot(int(slot), RequestState.CANCELLED)
            elif req.deadline is not None and now >= req.deadline:
                self._finalize_slot(int(slot), RequestState.EXPIRED)
        # Mid-chunk prefills are off the active mask but hold a slot
        # and pages — cancel/expiry must reap them here too.
        for slot in list(self._chunked):
            req = self._chunked[slot]["req"]
            if req.cancel_requested:
                del self._chunked[slot]
                self._finalize_slot(slot, RequestState.CANCELLED)
            elif req.deadline is not None and now >= req.deadline:
                del self._chunked[slot]
                self._finalize_slot(slot, RequestState.EXPIRED)
        # A cancel that raced placement (neither queued nor in a slot
        # at cancel() time) is caught here once it settles somewhere.
        if self._cancel_races:
            races, self._cancel_races = self._cancel_races, []
            for req in races:
                if not req.terminal:
                    self.cancel(req)

    def _idle_wait(self) -> None:
        """Idle with loads in flight: wait until the earliest in-flight
        load's modeled readiness instead of spinning a fixed busy-wait;
        already-due loads (waiting only on the actual device write)
        poll at a tight interval. Under an *injected* clock the owner
        of that clock controls time — modeled waits are virtual-time
        deltas, so sleeping real wall time for them would stall a DES
        replay; the engine returns immediately and lets the driver
        advance the clock."""
        if self._pending_loads and self._clock is None:
            t_next = min(t for _, _, t in self._pending_loads.values())
            wait = t_next - self.now()
            with self.spans.span("engine.idle_wait"):
                time.sleep(min(max(wait, 1e-4), 0.05))

    def step(self) -> None:
        """One engine iteration: retire finished loads -> enforce
        deadlines/cancellations -> admit -> prefetch -> batched prefill
        -> decode. With ``fused_hotloop`` the decode half is the
        device-resident fused loop (one dispatch per K-token horizon);
        otherwise the seed two-dispatch loop runs. While a profiler
        capture runs, the step and its phases are ``engine.*`` spans
        (``serving/spans.py``)."""
        loop = self._step_fused if self.fused else self._step_seed
        if not self.spans.on:
            return loop()
        with self.spans.span("engine.step") as sp:
            sp.set(rows=int(self.active.sum()),
                   queued=self.sched.pending_count())
            self._horizon_k = 0
            loop()
            sp.set(K=self._horizon_k)

    def _schedule(self) -> list[Request]:
        """Lifecycle sweep, admission and prefetch at a step boundary;
        returns the requests admitted."""
        now = self.now()
        with self.spans.span("engine.schedule") as sp:
            deferred = getattr(self.sched, "n_deferred", 0)
            self._sweep_lifecycle(now)
            running = [r for r in self.slot_req if r is not None]
            admitted = self.sched.schedule(now, running)
            self._run_prefetchers(now)
            if sp:
                sp.set(admitted=len(admitted), deferred=getattr(
                    self.sched, "n_deferred", 0) - deferred)
        return admitted

    def _step_seed(self) -> None:
        """The seed decode loop: one decode dispatch, logits back to a
        second sampling dispatch, per-step host re-uploads of page
        table / active mask / sampling arrays, and a blocking token
        sync before bookkeeping. Kept verbatim (plus the dispatch
        meter) as the ``decode_hotloop.py`` A/B baseline and the
        fallback for model families without fused decode support."""
        with self.spans.span("engine.poll_loads"):
            self._poll_loads()
        self._place_batch(self._schedule())
        self._advance_chunked()
        if self.paged:
            self._ensure_decode_pages()
        if not self.active.any():
            self._idle_wait()
            return
        rows = int(self.active.sum())
        self.batch_occupancy.append(rows)
        self._horizon_k = 1
        with self.spans.span("engine.decode", K=1, rows=rows, spec=False):
            self._commit_batch_state()
            DISPATCH_METER.tick()
            with self._act_scope():
                if self.paged:
                    logits, self.kv_pages = self._decode_paged_jit(
                        self.params, self.lora, self.tokens, self.kv_pages,
                        jnp.asarray(self.page_table), self.cache_len,
                        self.adapter_slot)
                else:
                    logits, self.kv = self._decode_jit(
                        self.params, self.lora, self.tokens, self.kv,
                        self.cache_len, self.adapter_slot)
            DISPATCH_METER.tick()
            if self._all_greedy(self.slot_req):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                nxt = self._sample_jit(
                    logits, *self._sampling_arrays(self.slot_req,
                                                   self.ecfg.max_slots))
            self.tokens = nxt[:, None]
            self.cache_len = self.cache_len + jnp.asarray(self.active,
                                                          jnp.int32)
        now = self.now()
        with DISPATCH_METER.sync(), self.spans.span("engine.readback"):
            nxt_host = np.asarray(nxt)
        to_finish, to_squash = [], []
        for slot in np.where(self.active)[0]:
            req = self.slot_req[slot]
            pos = req.generated
            req.generated += 1
            self._record_token(req, pos, int(nxt_host[slot]), now)
            if req.done or self._hit_stop(req) \
                    or req.generated + req.input_len \
                    >= self.ecfg.max_len - 1:
                to_finish.append(slot)
            elif req.bypassed and req.exceeded_prediction():
                to_squash.append(slot)
        for slot in to_finish:
            self._finish(slot)
        for slot in to_squash:
            self._squash_slot(slot)

    # ------------------------------------ fused device-resident hot loop
    #
    # Dataflow (DESIGN §2): everything the per-token loop touches lives
    # on device — KV (donated, updated in place), current tokens,
    # cache_len, the active mask, sample positions, per-row sampling
    # params, decode budgets, stop-token matrix, and (paged) the page
    # table. The host crosses the boundary only at *batch epochs*
    # (place/finish/squash rebuild the batch state; page alloc/free
    # re-uploads the table) and once per K-step horizon to sync the
    # (K, B) token block. Under backlog, armed deadlines, pending
    # cancels or in-flight adapter loads the horizon collapses to K=1,
    # so admission latency and lifecycle sweeps behave exactly like the
    # seed loop.

    def _host_work_pending(self) -> bool:
        """Anything that needs host-truth bookkeeping before the next
        dispatch: queued admissions, armed deadline/cancel sweeps, or
        in-flight adapter loads to poll."""
        return bool(self._deadlines_armed or self._cancel_armed
                    or self._cancel_races or self._pending_loads
                    or self._chunked
                    or self.sched.pending_count() > 0)

    def _refresh_device_state(self) -> None:
        """(Re)build the device-resident batch state — only when the
        batch epoch moved (satellite: the seed loop rebuilt
        ``_all_greedy`` + ``_sampling_arrays`` from Python requests
        every step even with an unchanged batch)."""
        if self._dev_epoch == self.batch_epoch and self._dev is not None:
            return
        B = self.ecfg.max_slots
        reqs = self.slot_req
        # One source of truth with the seed loop: the sampler inputs
        # (temperature/top_k/top_p/seeds/positions) come from the same
        # builder its per-step path uses; only the fused-loop extras —
        # decode budgets, the stop-token matrix, the active mask — are
        # built here.
        temp, topk, topp, seeds, pos = self._sampling_arrays(reqs, B)
        budget = np.zeros(B, np.int32)
        n_stop = max((len(r.sampling.stop_token_ids) for r in reqs
                      if r is not None and r.sampling is not None),
                     default=0)
        stop = np.full((B, n_stop), -1, np.int32)
        for i, r in enumerate(reqs):
            if r is None:
                continue
            budget[i] = r.max_output_tokens
            if r.sampling is not None and r.sampling.stop_token_ids:
                stop[i, :len(r.sampling.stop_token_ids)] = \
                    r.sampling.stop_token_ids
        self._dev = dict(
            active=jnp.asarray(self.active),
            positions=pos, budget=jnp.asarray(budget),
            stop=jnp.asarray(stop), temp=temp, topk=topk, topp=topp,
            seeds=seeds, all_greedy=self._all_greedy(reqs))
        self._dev_epoch = self.batch_epoch

    def _host_lens(self) -> np.ndarray:
        """cache_len derived from host truth (no device sync):
        ``input_len + generated - 1`` for occupied slots."""
        lens = np.zeros(self.ecfg.max_slots, np.int64)
        for i, r in enumerate(self.slot_req):
            if r is not None:
                lens[i] = r.input_len + r.generated - 1
        return lens

    def _choose_horizon(self) -> int:
        """Adaptive micro-horizon K: number of decode steps fused into
        the next dispatch. K=1 whenever the host may need to intervene
        between tokens — backlog (``queue_pressure`` via the scheduler
        queue), armed deadlines/cancels, in-flight loads — so TTFT and
        admission latency are untouched; otherwise up to
        ``max_horizon``, clamped by the longest surviving row's budget
        and (for bypassers) the first possible squash point, then
        bucketed to a power of two so jit variants stay bounded."""
        e = self.ecfg
        if e.max_horizon <= 1 or self._host_work_pending():
            return 1
        reqs = [r for r in self.slot_req if r is not None]
        if not reqs:
            return 1
        K = min(e.max_horizon,
                max(r.max_output_tokens - r.generated for r in reqs))
        # A bypasser squashes on the token that exceeds its predicted
        # length — that check is host-side, so the horizon must end
        # exactly there (the seed loop checks it every token).
        for r in reqs:
            if r.bypassed:
                K = min(K, r.predicted_output - r.generated + 1)
        if K <= 1:
            return 1
        return 1 << (K.bit_length() - 1)

    def _page_cover(self) -> int:
        """Paged mode: tokens writable from *already-allocated* pages,
        minimised over active slots (host truth — no device sync). The
        horizon is clamped to this instead of pre-allocating ahead, so
        page-allocation (and therefore preemption) timing is identical
        to the seed loop's per-boundary ``_ensure_decode_pages``: the
        scan cannot allocate mid-flight, and it never needs to."""
        ps = self.pool.page_size
        cover = 1 << 30
        for slot in np.where(self.active)[0]:
            r = self.slot_req[slot]
            cover = min(cover, len(self.slot_pages[slot]) * ps
                        - (r.input_len + r.generated - 1))
        return cover

    # ------------------------------- speculative draft–verify dispatch
    def _spec_k_eff(self) -> int:
        """Adaptive draft length: the acceptance EWMA scales ``spec_k``
        down when drafts stop landing (each round costs ``kk + 1`` draft
        steps + one verify regardless of acceptance, so a cold draft
        should shrink toward kk=1). Bucketed to a power of two so the
        static-spec_k jit variants stay bounded."""
        e = self.ecfg
        kk = int(round(self._spec_ewma * (e.spec_k + 1)))
        kk = max(1, min(e.spec_k, kk))
        return 1 << (kk.bit_length() - 1)

    def _draft_sync(self) -> None:
        """Lazy draft-KV catch-up: replay, through one batched
        multi-token draft forward, every token the target cache holds
        that the draft cache does not (per-slot ``_draft_len`` tracks
        the synced length). Placement, prefix-cache hits, chunked
        prefill, KV import and squash re-execution all advance the
        target without touching the draft — this one entry point makes
        them all spec-compatible without per-path hooks. Token material
        comes from host truth (prompt + recorded outputs), so the draft
        prefix is identical across re-executions."""
        lens = self._host_lens()
        rows = [int(s) for s in np.where(self.active)[0]
                if self._draft_len[s] < lens[s]]
        if not rows:
            return
        gap = max(int(lens[s] - self._draft_len[s]) for s in rows)
        S = 1 << max(3, (gap - 1).bit_length())
        B = self.ecfg.max_slots
        toks = np.zeros((B, S), np.int32)
        start = np.zeros(B, np.int32)
        seq = np.zeros(B, np.int32)
        for s in rows:
            req = self.slot_req[s]
            full = np.concatenate([
                self._prompt_tokens(req),
                np.asarray(self.outputs[req.req_id], np.int32)])
            lo, hi = int(self._draft_len[s]), int(lens[s])
            toks[s, :hi - lo] = full[lo:hi]
            start[s] = lo
            seq[s] = hi - lo
        DISPATCH_METER.tick()
        DISPATCH_METER.tick_draft()
        self.n_spec_draft_dispatches += 1
        self.draft_kv = self._draft_catchup_jit(
            self.draft_params, jnp.asarray(toks), self.draft_kv,
            jnp.asarray(start), jnp.asarray(seq), S=S)
        for s in rows:
            self._draft_len[s] = int(lens[s])

    def _shrink_spec_pages(self) -> None:
        """Roll back speculative page growth: after the round drained,
        every surviving slot keeps exactly the pages the seed loop's
        ``_ensure_decode_pages`` would hold (``len // ps + 1`` — the
        next write covered), and the rest go back to the free list with
        the pool hold shrunk to match. Rejected drafts therefore never
        inflate pool occupancy past one round, so admission headroom
        and preemption timing stay honest."""
        ps = self.pool.page_size
        lens = self._host_lens()
        for slot in np.where(self.active)[0]:
            req = self.slot_req[slot]
            if req is None:
                continue
            keep = int(lens[slot]) // ps + 1
            extra = len(self.slot_pages[slot]) - keep
            if extra <= 0:
                continue
            for _ in range(extra):
                pid = self.slot_pages[slot].pop()
                self.page_table[slot, len(self.slot_pages[slot])] = 0
                self.free_pages.append(pid)
            self.pool.shrink_request(req.req_id, extra * ps)
            self._page_table_dirty = True

    def _dispatch_spec(self) -> bool:
        """One speculative block: draft catch-up, then ``n_rounds``
        draft–verify rounds in a single fused dispatch, drained
        synchronously (emission counts are data-dependent, so the
        pipelined-readback page math cannot cover a speculative block).
        Returns False — caller falls back to the normal fused horizon —
        when speculation is not viable this step: nothing to decode, a
        bypasser's squash point inside the round, no context headroom,
        or (paged) not even one round of page cover after best-effort
        growth. Speculation only ever *shrinks* on pressure; it never
        preempts a slot to grow."""
        e = self.ecfg
        reqs = [r for r in self.slot_req if r is not None]
        if not reqs or max(r.max_output_tokens - r.generated
                           for r in reqs) < 1:
            return False
        kk = self._spec_k_eff()
        has_bypass = False
        for r in reqs:
            # A bypasser squashes on the token exceeding its predicted
            # length (host-side check): the round must end at or before
            # that point, exactly like ``_choose_horizon``.
            if r.bypassed:
                has_bypass = True
                kk = min(kk, r.predicted_output - r.generated)
        lens = self._host_lens()
        for slot in np.where(self.active)[0]:
            # Rows at the context edge finish within a step or two —
            # don't burn drafts past their done-mask.
            kk = min(kk, e.max_len - 2 - int(lens[slot]))
        if kk < 1:
            return False
        kk = 1 << (kk.bit_length() - 1)
        n_rounds = 1 if has_bypass else max(1, e.max_horizon // (kk + 1))
        if self.paged:
            now = self.now()
            ps = self.pool.page_size
            need = n_rounds * (kk + 1)
            for slot in np.where(self.active)[0]:
                needed = (int(lens[slot]) + need - 1) // ps + 1
                short = needed - len(self.slot_pages[slot])
                if short > 0:
                    self._grow_slot(int(slot), short, now)  # best effort
            cover = self._page_cover()
            while n_rounds > 1 and n_rounds * (kk + 1) > cover:
                n_rounds -= 1
            if kk + 1 > cover:
                kk = cover - 1
                if kk < 1:
                    self._shrink_spec_pages()
                    return False
                kk = 1 << (kk.bit_length() - 1)
        self._horizon_k = n_rounds * (kk + 1)
        with self.spans.span("engine.decode", K=n_rounds * (kk + 1),
                             spec=True) as sp:
            if sp:
                sp.set(rows=int(self.active.sum()))
            self._refresh_device_state()
            self._draft_sync()
            d = self._dev
            self._commit_batch_state()
            DISPATCH_METER.tick()
            DISPATCH_METER.tick_draft(n_rounds * (kk + 1))
            DISPATCH_METER.tick_verify(n_rounds)
            self.n_spec_dispatches += 1
            self.n_spec_draft_dispatches += n_rounds * (kk + 1)
            self.n_spec_verify_dispatches += n_rounds
            with self._act_scope():
                if self.paged:
                    if self._page_table_dirty or self._page_table_dev is None:
                        self._page_table_dev = jnp.asarray(self.page_table)
                        self._page_table_dirty = False
                    carry, toks, emits, accs = self._spec_paged_jit(
                        self.params, self.lora, self.tokens, self.kv_pages,
                        self._page_table_dev, self.draft_kv, self.cache_len,
                        d["active"], d["positions"], self.adapter_slot,
                        d["budget"], d["stop"], d["temp"], d["topk"],
                        d["topp"], d["seeds"], spec_k=kk, n_rounds=n_rounds,
                        all_greedy=d["all_greedy"])
                    (self.tokens, self.kv_pages, self.draft_kv,
                     self.cache_len, d["active"], d["positions"]) = carry
                else:
                    carry, toks, emits, accs = self._spec_jit(
                        self.params, self.lora, self.tokens, self.kv,
                        self.draft_kv, self.cache_len, d["active"],
                        d["positions"], self.adapter_slot, d["budget"],
                        d["stop"], d["temp"], d["topk"], d["topp"],
                        d["seeds"], spec_k=kk, n_rounds=n_rounds,
                        all_greedy=d["all_greedy"])
                    (self.tokens, self.kv, self.draft_kv, self.cache_len,
                     d["active"], d["positions"]) = carry
            self._inflight = (toks, emits, n_rounds * (kk + 1),
                              (accs, kk, n_rounds))
        self._drain_inflight()
        # The draft cache advanced in lockstep with the target for
        # every surviving slot (garbage entries past the accepted
        # prefix are overwritten before the next read, same as the
        # target's); finished/squashed slots were cleared by their
        # terminal hooks.
        lens = self._host_lens()
        for slot in range(e.max_slots):
            if self.active[slot] and self.slot_req[slot] is not None:
                self._draft_len[slot] = int(lens[slot])
        if self.paged:
            self._shrink_spec_pages()
        return True

    def _dispatch_horizon(self, K: int, refresh: bool = True) -> None:
        """Launch one fused K-step horizon and re-point the engine's
        device state at its (asynchronous) outputs. The inputs are
        donated — after this call the previous buffers are gone, which
        is exactly the in-place-KV invariant.

        ``refresh=False`` (pipelined dispatch): host truth lags the
        device by the in-flight horizon, so rebuilding active/positions
        from the Python requests would *rewind* the device state — the
        carried device arrays are the only truth until the next full
        sync. A finish the device discovered mid-horizon is already off
        the carried active mask; a host-side squash leaves its row
        decoding masked garbage until the next synced placement rebuild.
        """
        self._horizon_k = K
        with self.spans.span("engine.decode", K=K, spec=False) as sp:
            if sp:
                sp.set(rows=int(self.active.sum()))
            if refresh:
                self._refresh_device_state()
            d = self._dev
            self._commit_batch_state()
            DISPATCH_METER.tick()
            with self._act_scope():
                if self.paged:
                    if self._page_table_dirty or self._page_table_dev is None:
                        self._page_table_dev = jnp.asarray(self.page_table)
                        if self.plan is not None:
                            self._page_table_dev = jax.device_put(
                                self._page_table_dev,
                                self._batch_sh(2))
                        self._page_table_dirty = False
                    carry, toks, emits = self._fused_paged_jit(
                        self.params, self.lora, self.tokens, self.kv_pages,
                        self._page_table_dev, self.cache_len, d["active"],
                        d["positions"], self.adapter_slot, d["budget"],
                        d["stop"], d["temp"], d["topk"], d["topp"], d["seeds"],
                        K=K, all_greedy=d["all_greedy"])
                    (self.tokens, self.kv_pages, self.cache_len,
                     d["active"], d["positions"]) = carry
                else:
                    carry, toks, emits = self._fused_jit(
                        self.params, self.lora, self.tokens, self.kv,
                        self.cache_len, d["active"], d["positions"],
                        self.adapter_slot, d["budget"], d["stop"], d["temp"],
                        d["topk"], d["topp"], d["seeds"],
                        K=K, all_greedy=d["all_greedy"])
                    (self.tokens, self.kv, self.cache_len,
                     d["active"], d["positions"]) = carry
            self._inflight = (toks, emits, K)

    def _drain_inflight(self) -> None:
        """Sync the in-flight horizon's token block and replay the
        seed loop's per-token bookkeeping from it: record/stream each
        emitted token, then finish / squash slots in sub-step order
        (the on-device done-mask already stopped finished rows, so a
        request that hit EOS inside the horizon emitted nothing past
        it)."""
        if self._inflight is None:
            return
        toks, emits, _K = self._inflight[:3]
        spec_meta = self._inflight[3] if len(self._inflight) > 3 else None
        self._inflight = None
        with DISPATCH_METER.sync(), self.spans.span("engine.readback"):
            toks_h = np.asarray(toks)
            emits_h = np.asarray(emits)
            accs_h = (np.asarray(spec_meta[0])
                      if spec_meta is not None else None)
        if spec_meta is not None:
            # Speculative block: emissions are per-round prefix-masked
            # (round r emits its first cnt[b] of kk+1 slots), so a
            # rejected round leaves empty *interior* steps — count the
            # round-start rows (step 0 of each round is emitted by
            # every row active then) for drafted/accepted accounting.
            _, kk, n_rounds = spec_meta
            round_act = emits_h.reshape(n_rounds, kk + 1, -1)[:, 0, :]
            drafted = int(round_act.sum()) * kk
            self.spec_drafted_tokens += drafted
            self.spec_accepted_tokens += int(accs_h.sum())
            if drafted:
                self._spec_ewma = (0.8 * self._spec_ewma
                                   + 0.2 * int(accs_h.sum()) / drafted)
        now = self.now()
        for k in range(toks_h.shape[0]):
            em = emits_h[k]
            if not em.any():
                if spec_meta is not None:
                    continue    # interior rejection gap, later rounds
                break               # every row finished earlier in the scan
            self.batch_occupancy.append(int(em.sum()))
            to_finish, to_squash = [], []
            for slot in np.where(em)[0]:
                req = self.slot_req[slot]
                if req is None:
                    # Slot squashed at an earlier sub-step of this (or
                    # the previous, pipelined) horizon: the device kept
                    # emitting, but the request re-executes from its
                    # requeue — dropping the tail keeps the stream
                    # identical to the seed loop's.
                    continue
                pos = req.generated
                req.generated += 1
                self._record_token(req, pos, int(toks_h[k, slot]), now)
                if req.done or self._hit_stop(req) \
                        or req.generated + req.input_len \
                        >= self.ecfg.max_len - 1:
                    to_finish.append(int(slot))
                elif req.bypassed and req.exceeded_prediction():
                    to_squash.append(int(slot))
            for slot in to_finish:
                self._finish(slot)
            for slot in to_squash:
                self._squash_slot(slot)

    def _sync_inflight(self) -> None:
        """Barrier: retire any dispatched-but-unsynced horizon so host
        records are complete (warmup resets, external state reads)."""
        self._drain_inflight()

    def _plan_pipelined_horizon(self) -> Optional[int]:
        """Pipelined readback: decide whether the *next* horizon can be
        dispatched from the carried device state before the in-flight
        one is synced. Requires zero host work due, at least one row
        that is provably still decoding after the in-flight horizon's
        K steps, and (paged) page coverage for both horizons' writes —
        host truth lags the device by exactly the in-flight K, so every
        bound is computed against that worst case. Returns the next K,
        or None to sync first."""
        e = self.ecfg
        if (not e.pipeline_readback or self._inflight is None
                or self._host_work_pending()):
            return None
        _, _, k_in = self._inflight
        alive = 0
        for r in self.slot_req:
            if r is None or (r.sampling is not None
                             and r.sampling.stop_token_ids):
                continue        # a stop token could end the row any step
            rem = r.max_output_tokens - r.generated - k_in
            rem = min(rem, (e.max_len - 1 - r.input_len
                            - r.generated - k_in))
            alive = max(alive, rem)
        if alive <= 0:
            return None
        K = min(e.max_horizon, alive)
        for r in self.slot_req:
            # A bypasser's squash point is host-side: the combined
            # in-flight + next horizon must end exactly on it.
            if r is not None and r.bypassed:
                K = min(K, r.predicted_output - r.generated - k_in + 1)
        if K < 1:
            return None
        if self.paged:
            # Host truth lags the device by the in-flight k_in writes;
            # existing pages must cover those plus the next horizon.
            K = min(K, self._page_cover() - k_in)
            if K < 1:
                return None     # sync, then allocate at the boundary
        return 1 << (K.bit_length() - 1)

    def _step_fused(self) -> None:
        """One fused-loop iteration. Steady state (stable batch, empty
        queue): dispatch horizon N+1 from the carried device state,
        *then* sync horizon N's tokens — the host-side bookkeeping of
        step N overlaps the device compute of step N+1. Any pending
        host work (admissions, lifecycle, loads) first syncs the
        in-flight horizon, then runs the same admit/place path as the
        seed loop."""
        with self.spans.span("engine.poll_loads"):
            self._poll_loads()
        if self._inflight is not None:
            k_next = self._plan_pipelined_horizon()
            if k_next is not None:
                prev, self._inflight = self._inflight, None
                self._dispatch_horizon(k_next, refresh=False)
                nxt, self._inflight = self._inflight, prev
                self._drain_inflight()
                self._inflight = nxt
                return
            self._drain_inflight()
        self._place_batch(self._schedule())
        self._advance_chunked()
        if self.paged:
            self._ensure_decode_pages(self._host_lens())
        if not self.active.any():
            self._idle_wait()
            return
        K = self._choose_horizon()
        if self.spec and K > 1 and self._dispatch_spec():
            # Speculative block dispatched and drained (the K=1
            # demotions — backlog, armed sweeps, loads — reach here as
            # K == 1 and keep speculation off for the step, exactly
            # like the horizon collapse).
            return
        if self.paged and K > 1:
            # Clamp to allocated pages (cover >= 1: the _ensure pass
            # grew or preempted) — allocation timing stays seed-equal.
            K = 1 << (max(1, min(K, self._page_cover())).bit_length() - 1)
        self._dispatch_horizon(K)
        if not self.ecfg.pipeline_readback:
            self._drain_inflight()

    def busy(self) -> bool:
        """True while any work is in flight or queued. Mid-prefill
        chunked slots count; MIGRATING slots do not — their next step
        belongs to the handoff plane (the disagg cluster's ``busy``
        covers in-flight shipments)."""
        return (bool(self.active.any()) or bool(self._chunked)
                or self.sched.pending_count() > 0)

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.busy():
                break
            self.step()

    # ``drain`` is the surface name the cluster layer uses (DESIGN §3).
    drain = run_until_drained

    def reset_stats(self) -> None:
        """Clear accounting after a warmup pass (jit compiles, first
        adapter loads) so reported metrics cover only the measured run.
        Device state and cache residency are kept — replicas start warm
        but identically so across routing policies."""
        self._sync_inflight()
        self.flush_loads()
        self.completed = []
        self.records = []
        self.outputs = {}
        self._tbts = {}
        self._last_tok = {}
        self.handles = {}
        self.batch_occupancy = []
        self.spans.clear()
        self._prefill_span = {}
        self.n_preempted = 0
        self.n_cancelled = 0
        self.n_expired = 0
        self.n_async_loads = 0
        self.n_chunked_prefills = 0
        self.n_kv_exports = 0
        self.n_kv_imports = 0
        self.kv_handoff_bytes = 0
        # Speculation accounting restarts; the acceptance EWMA stays
        # warm (like cache residency) so the measured run speculates at
        # the adapted spec_k, not the optimistic cold start.
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.n_spec_dispatches = 0
        self.n_spec_draft_dispatches = 0
        self.n_spec_verify_dispatches = 0
        # Prefix-cache hit accounting restarts; the cached pages stay
        # resident (warm prefixes, like warm adapters).
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.n_prefix_hits = 0
        self.n_cow_forks = 0
        if self.prefix is not None:
            self.prefix.evictions = 0
            self.prefix.inserts = 0
        self.cache.stats = CacheStats()
        for counter in ("n_bypassed", "n_squashed", "n_deferred"):
            if hasattr(self.sched, counter):
                setattr(self.sched, counter, 0)

    # ---------------------------------------------------------- reporting
    def queue_pressure(self) -> float:
        """Routing signal: scheduler backlog plus occupied batch slots."""
        return self.sched.queue_pressure() + float(self.active.sum())

    def kv_page_stats(self) -> dict:
        """Page-occupancy telemetry (paged mode; empty dict for dense)."""
        if not self.paged:
            return {}
        total = self.n_pages - 1     # page 0 is the trash page
        used = total - len(self.free_pages)
        return {"kv_pages_used": used, "kv_pages_total": total,
                "kv_page_util": used / max(1, total),
                "preempted": self.n_preempted}

    def handoff_stats(self) -> dict:
        """Chunked-prefill / KV-handoff gauges (zeros when unused)."""
        if not (self.n_chunked_prefills or self.n_kv_exports
                or self.n_kv_imports or self._chunked
                or self._migrating):
            return {}
        return {"chunked_prefills": self.n_chunked_prefills,
                "kv_exports": self.n_kv_exports,
                "kv_imports": self.n_kv_imports,
                "kv_handoff_gb": round(self.kv_handoff_bytes / 1e9, 6),
                "migrating": len(self._migrating)}

    def prefix_stats(self) -> dict:
        """Prefix-reuse gauges (empty dict when the cache is off)."""
        if self.prefix is None:
            return {}
        return {
            "prefix_hit_rate": round(
                self.prefix_hit_tokens
                / max(1, self.prefix_lookup_tokens), 4),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_lookup_tokens": self.prefix_lookup_tokens,
            "prefix_hits": self.n_prefix_hits,
            "prefix_shared_pages": self.pool.n_shared_pages,
            "prefix_nodes": len(self.prefix),
            "prefix_evictions": self.prefix.evictions,
            "cow_forks": self.n_cow_forks,
        }

    def spec_stats(self) -> dict:
        """Speculative-decoding gauges (empty dict when spec is off).

        Acceptance is drafted-token yield: ``spec_accepted_tokens``
        counts draft proposals verified equal/accepted by the target,
        over ``spec_drafted_tokens`` proposed (``spec_k_eff`` per row
        per round). Emitted tokens run higher than accepted — every
        round also emits its correction/bonus token. The per-phase
        dispatch counters split DISPATCH_METER-style device work into
        draft forwards (chained proposal steps + catch-up replays) and
        multi-token target verifies."""
        if not self.spec:
            return {}
        return {
            "spec_accept_rate": round(
                self.spec_accepted_tokens
                / max(1, self.spec_drafted_tokens), 4),
            "spec_drafted_tokens": self.spec_drafted_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "spec_draft_dispatches": self.n_spec_draft_dispatches,
            "spec_verify_dispatches": self.n_spec_verify_dispatches,
            "spec_dispatches": self.n_spec_dispatches,
            "spec_k_eff": self._spec_k_eff(),
        }

    def shard_stats(self) -> dict:
        """Per-device data-plane gauges (empty dict off-mesh): physical
        page occupancy per data shard and resident LoRA-arena bytes per
        device. Collective time is in a profiler capture's device
        trace (docs/OPERATIONS.md §2.5)."""
        if self.mesh is None:
            return {}
        out = {
            "mesh_shape": [self.mesh.shape["data"],
                           self.mesh.shape["model"]],
            "n_devices": self.mesh.size,
        }
        if self.paged:
            ds = self.mesh.shape["data"]
            stride = self.n_pages // ds
            per = [0] * ds
            used = set(range(1, self.n_pages)) - set(self.free_pages)
            for pid in used:
                per[pid // stride] += 1
            out["per_shard_pages_used"] = per
            out["per_shard_pages_total"] = stride
        # Actual bytes one device holds for the slot arena — with B
        # sharded over "model" this is arena_bytes/model_size + the
        # replicated A halves.
        arena = 0
        for a, b in self.lora.values():
            arena += a.addressable_shards[0].data.nbytes
            arena += b.addressable_shards[0].data.nbytes
        out["per_shard_lora_slot_bytes"] = arena
        return out

    def stats(self) -> dict:
        return {
            "completed": len(self.completed),
            "cache": self.cache.stats.__dict__.copy(),
            "bypassed": getattr(self.sched, "n_bypassed", 0),
            "squashed": getattr(self.sched, "n_squashed", 0),
            "deferred": getattr(self.sched, "n_deferred", 0),
            "cancelled": self.n_cancelled,
            "expired": self.n_expired,
            "async_loads": self.n_async_loads,
            "pending_loads": len(self._pending_loads),
            "resident_adapters": sorted(self.cache.resident_ids()),
            "pool": self.pool.snapshot(),
            # Fused hot loop (DESIGN §2): the device batch state is
            # rebuilt only when this epoch counter moves.
            "fused_hotloop": self.fused,
            "batch_epoch": self.batch_epoch,
            **self.kv_page_stats(),
            **self.handoff_stats(),
            **self.prefix_stats(),
            **self.spec_stats(),
            **self.shard_stats(),
        }

    def metrics(self) -> RunMetrics:
        """Per-node RunMetrics, aggregatable at cluster level."""
        # Submitted = completed + in the batch + still queued, so a
        # truncated run shows its loss instead of a fake 100% rate.
        n_sub = (len(self.records) + int(self.active.sum())
                 + self.sched.pending_count())
        m = RunMetrics(records=list(self.records), horizon=self.now(),
                       n_submitted=n_sub)
        m.cache_stats = {
            "hit_rate": round(self.cache.stats.hit_rate, 4),
            "hits": self.cache.stats.hits,
            "misses": self.cache.stats.misses,
            "evictions": self.cache.stats.evictions,
            "gb_loaded": round(self.cache.stats.bytes_loaded / 1e9, 6),
        }
        m.sched_stats = {
            "bypassed": getattr(self.sched, "n_bypassed", 0),
            "squashed": getattr(self.sched, "n_squashed", 0),
            "deferred": getattr(self.sched, "n_deferred", 0),
            "cancelled": self.n_cancelled,
            "expired": self.n_expired,
            "async_loads": self.n_async_loads,
            "pressure": round(self.queue_pressure(), 3),
            "batch_occupancy_mean": round(
                float(np.mean(self.batch_occupancy))
                if self.batch_occupancy else 0.0, 3),
            **self.kv_page_stats(),
            **self.handoff_stats(),
            **self.prefix_stats(),
            **self.spec_stats(),
            **self.shard_stats(),
        }
        return m
