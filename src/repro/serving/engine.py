"""Hetis inference engine: continuous batching + dynamic head dispatching.

The engine is the paper's full control loop on real JAX compute:

  admit   — new requests get head placements from the Dispatcher LP (Eq 7);
            their prompt K/V is computed with a real prefill and stored into
            the head-granular paged pool on the assigned devices;
  decode  — one token per running request per step; K/V consumed in place
            from the paged pool by the Pallas paged-attention kernel, cache
            grown via grow_context (Eq 8 bookkeeping);
  balance — Θ-triggered re-dispatching and device-local LIFO handling of
            memory exhaustion (§5.3), with migration bytes scheduled by the
            Hauler into compute-overlap windows;
  clock   — a simulated clock advances by the profiler-modelled step time of
            the heterogeneous deployment (Table 1 device classes), so TTFT /
            TPOT / throughput are measured as the paper measures them, while
            the token stream itself is exact JAX compute.

Paged decode fast path (``EngineConfig.decode_mode == "paged"``, default):

  * The K/V pools are device-resident JAX arrays (``PagedHeadCache``); the
    engine hands ``transformer.paged_decode_step`` the pools plus
    ``(B, Hkv, max_pages)`` block tables, per-request lengths and the
    (slot, offset) of each new token.  Dense QKV/MLP projections and the
    Pallas paged-attention kernel run inside ONE jitted function; the new
    token's K/V is scattered into the pool per layer — cache contents never
    cross the host boundary (h2d traffic is tokens + tables, a few KB).
  * Shapes are bucketed: the batch and the block-table page axis are padded
    to the next power of two, so jit compilation count is bounded by
    ``bucket_count()`` (≈ log²) instead of growing with every new
    (batch, context) combination.  Padded rows write to the pool's sink
    slot and carry length 0 — never read, outputs discarded.
  * The dense reference path (``decode_mode == "dense"``) gathers pages
    into a host-side dense cache each step (``gather_dense``) and re-uploads
    it — kept as the token-exactness oracle, for MLA/ssm configs, and for
    the before/after record in ``benchmarks/engine_decode_bench.py``.

Chunked prefill fast path (``EngineConfig.prefill_mode == "paged"``,
default):

  * Prompts are decomposed into fixed-size chunks; each ``step()`` runs ONE
    chunk per prefilling request, with several requests' chunks batched
    into a single jitted ``transformer.paged_prefill_chunk`` call whose
    K/V is scattered **directly into the device-resident pools** via
    (slot, offset) index arrays — the dense ``(L, 1, max_seq, ...)``
    intermediate cache and the ``store_prompt_request`` round-trip of the
    serial path never happen.  Chunks interleave with decode steps, so a
    long prompt no longer stalls the running decode batch (Sarathi-style
    piggybacking).
  * Chunk shapes are pow2-bucketed in (batch, chunk length, table pages);
    compile count is bounded by ``prefill_bucket_count()``.  Padded rows
    carry length 0 and padded tokens write to the sink slot.
  * The serial dense path (``prefill_mode == "dense"``) runs ``prefill`` +
    ``store_prompt_request`` per request — kept as the token-exactness
    oracle and for MLA/ssm configs.

Fused prefill+decode step (``EngineConfig.step_mode == "fused"``,
default whenever both paged paths apply):

  * Each iteration issues ONE jitted ``transformer.paged_fused_step``
    call whose row batch mixes decode rows (the degenerate chunk: one
    token at position ``ctx - 1``) and prefill rows (chunks of ≤ C prompt
    tokens), driven entirely by the per-row ``starts``/``lengths`` SMEM
    scalars of the chunked-prefill kernel — per-step dispatch drops from
    two jitted calls to one while token streams stay bit-identical to the
    split schedule (``step_mode == "split"``, kept as the fallback and
    exactness oracle).
  * The scheduler is a **token-budget packer**: every step has a budget
    ``B_tok`` (``token_budget``, default ``max_batch + prefill_chunk``);
    decode rows are always admitted (one token each) and the remainder is
    packed with prefill chunk tokens FCFS, at most ``chunk_now`` per
    request.
  * ``chunk_now`` is **autotuned** against a decode TPOT SLO
    (``tpot_slo_s``): warm (compile-free) fused-step wall latencies feed a
    telemetry ``Histogram``; when its EWMA overruns the SLO the chunk
    halves, and when there is ≥2x headroom it doubles back — pow2-clamped
    to ``[1, prefill_chunk]`` so the fused ``(B, C, P)`` bucket universe
    stays enumerable via ``fused_bucket_count()``.

Telemetry (``repro.telemetry``): a typed :class:`MetricsRegistry` replaces
the old flat metrics dict — byte counters are computed from the actual
array dtypes, TTFT/TPOT are histograms whose percentiles are evaluated
lazily at read time, KV-pool occupancy and per-device memory are
callable-backed gauges, every jitted callable is wrapped with a
jit-recompile counter, and the wall-clock counters ``queue/wait_s`` /
``queue/served`` (submit to first scheduled chunk) are always on.  ``engine.metrics`` stays a backward-compatible mapping
view over the registry; ``engine.snapshot()`` is the typed API.  With
``EngineConfig.telemetry`` on, a :class:`Tracer` records the nested spans
``step`` > ``admit`` (> ``lp``, ``alloc``) / ``plan`` / ``upload`` /
``fused_step`` / ``readback`` / ``rebalance`` (> ``migrate``), each also a
``jax.profiler`` annotation ``engine.<name>`` on the profiler's clock,
exportable as Chrome ``trace_event`` JSON; the jitted steps are named
functions (``jit_fused_step``, ...) whose bodies carry
``jax.named_scope``s.  ``trace_modules`` additionally runs the eager
per-module probe
(``transformer.paged_decode_step_traced``) whose device-sync'd
Attention/MLP span durations feed the dispatcher's measured snapshot
(EWMA-smoothed per-device gauges consumed by ``maybe_rebalance``), the
hauler's measured-bandwidth link model, and the cost model's calibrated
dense-module efficiency.

Token-exactness is tested against a plain dense decode (tests/test_engine,
tests/test_engine_paged — the latter interleaves migration/preemption).
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import (Deque, Dict, Iterable, List, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cluster import ClusterSpec, Device
from repro.core.costmodel import (ModelProfile, calibrate_efficiency,
                                  dense_flops_layer)
from repro.core.dispatcher import (ATTN_SNAPSHOT_PREFIX, AttnRequest,
                                   WorkerState, apply_placement,
                                   current_attention_time, dispatch_lp,
                                   grow_context, handle_memory_exhaustion,
                                   maybe_rebalance, release_request)
from repro.core.hauler import MigrationScheduler, MigrationTask, \
    migration_bytes, plan_migration
from repro.core.profiler import (analytic_attention_model,
                                 analytic_transfer_model)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.kvcache import PagedHeadCache
from repro.serving.request import Request, RequestState
from repro.telemetry import (MetricsRegistry, MetricsView, Tracer,
                             count_recompiles)


def _bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two >= n (>= lo)."""
    b = max(1, lo)
    while b < n:
        b *= 2
    return b


def _pow2s(n: int) -> List[int]:
    """All bucket values up to _bucket(n): [1, 2, 4, ..., _bucket(n)]."""
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    out.append(b)
    return out


def _bucket0(n: int) -> int:
    """_bucket with a 0 bucket: the staging-exchange lane axis is usually
    empty (single-device rows), and 0 lanes must not round up to 1."""
    return 0 if n == 0 else _bucket(n)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 32
    page_size: int = 16
    theta: float = 0.5              # re-dispatch trigger (paper Θ)
    cache_gb_per_device: Optional[Dict[int, float]] = None
    max_seq: int = 512
    # "paged": device-resident pools + Pallas kernel + bucketed jit;
    # "dense": gather_dense reference path (token-exactness oracle).
    decode_mode: str = "paged"
    # "paged": prompts decomposed into chunks written straight into the
    # pools, chunks of several requests batched per step and interleaved
    # with decode (Sarathi-style piggybacking); "dense": serial full-prompt
    # prefill + store_prompt_request (token-exactness oracle).
    prefill_mode: str = "paged"
    prefill_chunk: int = 32         # max prompt tokens per chunk (pow2)
    # "fused": ONE jitted paged_fused_step per iteration packs decode rows
    # (always admitted) and prefill chunk tokens into a single row batch
    # under the token budget; "split": the two-call schedule (one prefill
    # chunk call + one decode call per step) — kept as fallback/oracle.
    # Fused requires both paged paths; unsupported configs fall back.
    step_mode: str = "fused"
    # per-step token budget for the fused packer; 0 = auto
    # (max_batch decode tokens + prefill_chunk prompt tokens)
    token_budget: int = 0
    # decode TPOT SLO (seconds of warm fused-step wall latency) driving
    # the per-step prefill chunk autotuner; 0 = autotuner off (chunk
    # stays at prefill_chunk).  Timing the step costs a device sync, so
    # only enable when an SLO is actually configured.
    tpot_slo_s: float = 0.0
    # fraction of the modeled step time handed to the migration hauler as
    # compute-overlap window (§6); 0.5 = migrations ride in half the step
    migration_overlap: float = 0.5
    # tracing: off by default (disabled tracer is zero-cost — no per-step
    # allocations); the MetricsRegistry is always on.
    telemetry: bool = False
    # run the eager per-module probe (device-sync'd Attention/MLP spans
    # whose durations feed the dispatcher/hauler/costmodel calibration);
    # implies telemetry.
    trace_modules: bool = False
    trace_capacity: int = 65536     # tracer ring-buffer size (spans)


def _cache_bytes(ecfg: EngineConfig, dev: Device) -> float:
    """KV-cache bytes the dispatcher may place on one device."""
    cap_gb = (ecfg.cache_gb_per_device or {}).get(dev.device_id,
                                                  dev.cls.mem_gb * 0.3)
    return cap_gb * 1e9


def pool_shard_slots(cfg: ModelConfig, cluster: ClusterSpec,
                     device_ids: Sequence[int], ecfg: EngineConfig
                     ) -> Tuple[Dict[int, int], int]:
    """(pool slots per device shard, anchor staging slots) of an engine —
    the pool arrays' sizes, known before any is allocated.

    A shard holds what the device's cache bytes allow, but never more than
    backing ``max_batch`` sequences at ``max_seq`` with every head group on
    that one device: capacity beyond that is dispatcher bookkeeping, not
    pool memory.  The anchor's staging region must hold every remote page
    one step can reference — max_batch rows x n_kv_heads chains x
    pages_per_seq pages, the same cap (single-shard engines need none)."""
    devs = {d.device_id: d for d in cluster.devices}
    # bytes per pool slot from the pool's actual dtype
    slot_bytes = (2 * cfg.n_layers * ecfg.page_size * cfg.head_dim
                  * PagedHeadCache.pool_dtype(cfg).itemsize)
    pages_per_seq = -(-ecfg.max_seq // ecfg.page_size)
    pool_cap = ecfg.max_batch * cfg.n_kv_heads * pages_per_seq
    slots = {}
    for did in device_ids:
        by_mem = max(1, int(_cache_bytes(ecfg, devs[did]) / max(1, slot_bytes)
                            / max(1, cfg.n_kv_heads)))
        slots[did] = min(by_mem, pool_cap)
    return slots, (pool_cap if len(slots) > 1 else 0)


def jitted_steps(cfg: ModelConfig, max_seq: int, anchor: int,
                 anchor_sink: int) -> Tuple:
    """The engine's jitted callables, each a named function so its module
    reads ``jit_<name>`` in a profile and in dumped HLO: (dense decode,
    dense prefill, paged decode, paged prefill chunk, fused step).

    The paged steps donate the pool-shard pytrees (arguments 1 and 2), so
    XLA updates them in place: the pool dicts passed in are dead after the
    call, and the engine holds only the dicts each call returns
    (``kv.install_pools``).  ``anchor`` / ``anchor_sink`` are static
    (baked into the trace); the exchange lane arrays stage remote pool
    shards' pages through the anchor inside the same jitted call (see
    ``transformer.sharded_decode_step``)."""

    # the argument names become the HLO parameters' names: gd/gs/gt gather
    # lanes, wd/ws_/wt (wsb) writeback lanes, then the step's own operands
    def dense_decode(p, c, t):
        return T.decode_step(cfg, p, c, t)

    def dense_prefill(p, b):
        return T.prefill(cfg, p, b, max_seq=max_seq)

    def decode_step(p, kp, vp, gd, gs, gt, wd, ws_, wt, bt, ln, ws, wo, t,
                    pos):
        return T.sharded_decode_step(
            cfg, p, kp, vp, anchor, anchor_sink, gd, gs, gt, wd, ws_, wt,
            bt, ln, ws, wo, t, pos)

    def prefill_chunk(p, kp, vp, gd, gs, gt, wd, wsb, wt, bt, ln, st, ws,
                      wo, t, li):
        return T.sharded_prefill_chunk(
            cfg, p, kp, vp, anchor, anchor_sink, gd, gs, gt, wd, wsb, wt,
            bt, ln, st, ws, wo, t, li)

    def fused_step(p, kp, vp, gd, gs, gt, wd, wsb, wt, bt, ln, st, ws, wo,
                   t, li):
        return T.sharded_fused_step(
            cfg, p, kp, vp, anchor, anchor_sink, gd, gs, gt, wd, wsb, wt,
            bt, ln, st, ws, wo, t, li)

    donate = (1, 2)
    return (jax.jit(dense_decode), jax.jit(dense_prefill),
            jax.jit(decode_step, donate_argnums=donate),
            jax.jit(prefill_chunk, donate_argnums=donate),
            jax.jit(fused_step, donate_argnums=donate))


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params, cluster: ClusterSpec,
                 primary_ids: Sequence[int], pool_ids: Sequence[int],
                 engine_cfg: Optional[EngineConfig] = None,
                 rng: int = 0):
        engine_cfg = EngineConfig() if engine_cfg is None \
            else engine_cfg
        self.cfg = cfg
        self.params = params
        self.cluster = cluster
        self.ecfg = engine_cfg
        self.profile = cfg.profile()

        # Dispatcher worker states from analytic profiler models
        devs = self._devs
        self.workers: List[WorkerState] = []
        device_ids = list(primary_ids) + list(pool_ids)
        for did in device_ids:
            d = devs[did]
            attn_model = analytic_attention_model(d.cls, self.profile)
            xfer = (None if did in primary_ids else
                    analytic_transfer_model(d.cls.inter_link_gbps))
            self.workers.append(WorkerState(
                did, attn_model, xfer,
                capacity_bytes=_cache_bytes(engine_cfg, d)))
        self.primary_ids = list(primary_ids)

        # Per-device pool shards, anchored on the first primary.
        self.device_slots, stage = pool_shard_slots(cfg, cluster, device_ids,
                                                    engine_cfg)
        self.kv = PagedHeadCache(cfg, self.device_slots,
                                 page_size=engine_cfg.page_size,
                                 anchor=self.primary_ids[0],
                                 stage_slots=stage)
        self._kv_itemsize = int(self.kv.dtype.itemsize)
        self.hauler = MigrationScheduler({})
        # Eq 6 reads REAL per-partition free bytes: clamp each worker's
        # accounting capacity to its pool shard's physical free space.
        for w in self.workers:
            part = self.kv.partitions[w.device_id]
            w.free_bytes_fn = (lambda p=part, kv=self.kv:
                               float(p.free * kv.bytes_per_slot()))

        self.queue: Deque[Request] = collections.deque()
        self.running: List[Request] = []
        # admitted but not fully written to the pool (chunked prefill)
        self.prefilling: List[Request] = []
        self.attn_reqs: Dict[int, AttnRequest] = {}
        self.finished: List[Request] = []
        self.clock = 0.0

        # ------------------------------------------------------- telemetry
        self.registry = MetricsRegistry()
        self.tracer = Tracer(enabled=engine_cfg.telemetry,
                             capacity=engine_cfg.trace_capacity,
                             profiler_prefix="engine.")
        self._trace_modules = (engine_cfg.telemetry
                               and engine_cfg.trace_modules)
        reg = self.registry
        self._c_migr = reg.counter("migrated_bytes")
        # device-to-device traffic of the sharded pools: re-dispatch
        # migrations (cross-pool page copies, budgeted by the hauler) and
        # the fast paths' staging gathers/writebacks for multi-device rows
        self._c_d2d = reg.counter("migrate/d2d_bytes")
        self._c_migr_partial = reg.counter("migrate/partial")
        self._c_gather_d2d = reg.counter("fastpath/gather_d2d_bytes")
        self._c_evict = reg.counter("evictions")
        self._c_redisp = reg.counter("redispatches")
        self._c_steps = reg.counter("steps")
        self._c_h2d = reg.counter("h2d_bytes")
        self._c_d2h = reg.counter("d2h_bytes")
        self._c_pre_h2d = reg.counter("prefill_h2d_bytes")
        self._c_chunks = reg.counter("prefill_chunks")
        self._c_recompiles = reg.counter("jit/recompiles")
        # fused-step scheduler instruments: jitted model dispatches per
        # step, fused iterations, warm (recompile-free) fused latencies
        # feeding the chunk autotuner, SLO overruns, undrained exits
        self._c_model_calls = reg.counter("model_calls")
        self._c_fused = reg.counter("fused_steps")
        self._c_slo_viol = reg.counter("tpot_slo_violations")
        self._c_undrained = reg.counter("run_undrained")
        self._h_fused_warm = reg.histogram("fused_warm_step_s")
        reg.gauge("prefill/chunk_now", fn=lambda: float(self._chunk_now))
        self._h_ttft = reg.histogram("ttft_s")
        self._h_tpot = reg.histogram("tpot_s")
        # wall-clock seconds from submit to the step that first schedules
        # a chunk of the request, and the requests they sum over
        self._c_wait = reg.counter("queue/wait_s")
        self._c_served = reg.counter("queue/served")
        self._h_attn_mod = reg.histogram("attn_module_s")
        self._h_dense_mod = reg.histogram("dense_module_s")
        self._g_h2d_gbps = reg.gauge("xfer/h2d_gbps")
        # KV-pool occupancy / per-device memory gauges: callable-backed —
        # evaluated at snapshot()/read time, zero cost per step
        for did, part in self.kv.partitions.items():
            reg.gauge(f"kv/device/{did}/used_slots",
                      fn=(lambda p=part: float(p.used)))
            reg.gauge(f"kv/device/{did}/used_bytes",
                      fn=(lambda p=part, kv=self.kv:
                          float(p.used * kv.bytes_per_slot())))
        reg.gauge("kv/occupancy", fn=self._pool_occupancy)
        # whether any measured module-span attribution has landed yet
        self._measured_attn = False
        # calibrated dense-module roofline efficiency (cost model); the
        # 0.5 analytic prior is EWMA-updated from measured dense spans
        self._dense_eff = 0.5
        # backward-compatible mapping view over the registry (old flat
        # dict interface; ttft percentiles computed lazily at read)
        self.metrics = MetricsView({
            "migrated_bytes": lambda: self._c_migr.value,
            "evictions": lambda: self._c_evict.value,
            "redispatches": lambda: self._c_redisp.value,
            "steps": lambda: self._c_steps.value,
            "h2d_bytes": lambda: self._c_h2d.value,
            "d2h_bytes": lambda: self._c_d2h.value,
            "prefill_h2d_bytes": lambda: self._c_pre_h2d.value,
            "prefill_chunks": lambda: self._c_chunks.value,
            "model_calls": lambda: self._c_model_calls.value,
            "fused_steps": lambda: self._c_fused.value,
            "ttft_p50": lambda: self._h_ttft.percentile(50),
            "ttft_p95": lambda: self._h_ttft.percentile(95),
        })

        self.use_paged = (engine_cfg.decode_mode == "paged"
                          and T.supports_paged_decode(cfg))
        self.use_paged_prefill = (engine_cfg.prefill_mode == "paged"
                                  and T.supports_paged_prefill(cfg))
        jits = jitted_steps(cfg, engine_cfg.max_seq, self.kv.anchor,
                            self.kv.sink)
        (self._decode_fn, self._prefill_fn, self._paged_fn, self._chunk_fn,
         self._fused_fn) = (count_recompiles(f, self._c_recompiles)
                            for f in jits)
        self._decode_shapes: Set[Tuple[int, int, int]] = set()
        self._prefill_shapes: Set[Tuple[int, int, int, int]] = set()
        self._fused_shapes: Set[Tuple[int, int, int, int]] = set()
        # fused mode needs BOTH paged paths (decode rows and prefill rows
        # share the chunked-prefill kernel); otherwise fall back to split
        self.use_fused = (engine_cfg.step_mode == "fused"
                          and self.use_paged and self.use_paged_prefill
                          and T.supports_fused_step(cfg))
        why = T.fused_step_blocker(cfg)
        if why and "paged" in (engine_cfg.decode_mode,
                               engine_cfg.prefill_mode):
            warnings.warn(f"{cfg.name} falls back from the requested paged "
                          f"path to the dense reference path: {why}",
                          RuntimeWarning, stacklevel=2)
        # autotuned per-step prefill chunk, pow2 in [1, prefill_chunk]
        self._chunk_now = _bucket(engine_cfg.prefill_chunk)

    # --------------------------------------------------------------- cluster
    # ``cluster`` is a property so the device_id -> Device map the modeled-
    # time helpers consume is precomputed once and invalidated only when
    # the cluster actually changes (it used to be rebuilt from
    # ``cluster.devices`` on every `_model_prefill_time` /
    # `_model_decode_parts` call — a per-step dict build).
    @property
    def cluster(self) -> ClusterSpec:
        return self._cluster

    @cluster.setter
    def cluster(self, cluster: ClusterSpec) -> None:
        self._cluster = cluster
        self._devs: Dict[int, Device] = {d.device_id: d
                                         for d in cluster.devices}

    # ------------------------------------------------------------- telemetry
    def _pool_occupancy(self) -> float:
        used = sum(p.used for p in self.kv.partitions.values())
        total = sum(p.total for p in self.kv.partitions.values())
        return used / total if total else 0.0

    def snapshot(self, prefix: Optional[str] = None) -> Dict[str, float]:
        """Typed metrics snapshot (see MetricsRegistry.snapshot) — the API
        the dispatcher, hauler, and cost model calibration consume."""
        return self.registry.snapshot(prefix)

    def _module_span_args(self, reqs: List[Request]) -> Dict[str, float]:
        """(h, g) annotation for attention spans: resident query heads and
        resident KV bytes — the profiler's fit grid, from live traffic."""
        cfg = self.cfg
        ctx = sum(r.ctx_len for r in reqs)
        kv_bytes = (ctx * 2 * cfg.n_kv_heads * cfg.head_dim
                    * cfg.n_layers * self._kv_itemsize)
        return {"heads": float(len(reqs) * cfg.n_heads),
                "cache_bytes": float(kv_bytes)}

    def _attribute_module_times(self, attn_s: float, dense_s: float
                                ) -> None:
        """Fold one probe step's measured module durations into telemetry:
        module histograms, per-device measured-attention gauges (analytic
        share of each device, rescaled by the measured aggregate, EWMA-
        smoothed), and the calibrated dense roofline efficiency."""
        if attn_s > 0.0:
            self._h_attn_mod.observe(attn_s)
            loaded = [w for w in self.workers
                      if w.alive and (w.heads > 0 or w.cache_bytes > 0)]
            if loaded and self.attn_reqs:
                r0 = next(iter(self.attn_reqs.values()))
                f = {w.device_id: w.f_time(r0.group_ratio, r0.head_dim,
                                           r0.dtype_bytes) for w in loaded}
                total_f = sum(f.values())
                if total_f > 0.0:
                    for did, fi in f.items():
                        est = attn_s * fi / total_f
                        self.registry.gauge(
                            f"{ATTN_SNAPSHOT_PREFIX}{did}").ewma(est)
                    self._measured_attn = True
        if dense_s > 0.0:
            self._h_dense_mod.observe(dense_s)
            devs = self._devs
            nb = max(1, len(self.running))
            analytic = 0.0
            for did in self.primary_ids:
                cls = devs[did].cls
                fl = (dense_flops_layer(self.profile, nb)
                      * self.profile.n_layers / len(self.primary_ids))
                analytic = max(analytic, fl / (cls.dense_tflops * 1e12))
            self._dense_eff = calibrate_efficiency(
                self._dense_eff, analytic, dense_s)

    def _probe_totals(self) -> Tuple[float, float]:
        """(attention, dense-module) aggregate span seconds so far — the
        per-step delta isolates one probe call's module durations."""
        t = self.tracer
        return (t.total("attention"),
                t.total("embed") + t.total("mlp") + t.total("lm_head"))

    def _upload(self, host: Tuple[np.ndarray, ...], nbytes: int):
        """Host arrays -> device.  When the module probe is on, the
        transfer is timed (block_until_ready) and folded into the measured
        h2d bandwidth gauge the hauler's link model calibrates from."""
        if not self._trace_modules:
            return tuple(jnp.asarray(a) for a in host)
        t0 = time.perf_counter()
        dev = tuple(jnp.asarray(a) for a in host)
        jax.block_until_ready(dev)
        dt = time.perf_counter() - t0
        if dt > 0.0 and nbytes > 0:
            self._g_h2d_gbps.ewma(nbytes / dt / 1e9)
        return dev

    # -------------------------------------------------------- compile bounds
    def _max_pages(self) -> int:
        return -(-self.ecfg.max_seq // self.ecfg.page_size)

    def _gw_pow2s(self) -> List[int]:
        """Bucket values of the staging-exchange lane axis: 0 (no remote
        pages this step — the single-device common case) plus pow2s up to
        the staging capacity.  Single-partition engines have no remote
        pages at all, so the axis collapses to {0}."""
        if self.kv.stage == 0:
            return [0]
        return [0] + _pow2s(self.kv.stage)

    def decode_bucket_shapes(self) -> List[Tuple[int, int, int]]:
        """Every (batch-bucket, pages-bucket, exchange-bucket) shape the
        paged decode step can be jitted at — the full compile universe."""
        return [(b, p, g) for b in _pow2s(self.ecfg.max_batch)
                for p in _pow2s(self._max_pages())
                for g in self._gw_pow2s()]

    def prefill_bucket_shapes(self) -> List[Tuple[int, int, int, int]]:
        """Every (batch-bucket, chunk-bucket, pages-bucket,
        exchange-bucket) shape the chunked prefill step can be jitted
        at."""
        return [(b, c, p, g) for b in _pow2s(self.ecfg.max_batch)
                for c in _pow2s(self.ecfg.prefill_chunk)
                for p in _pow2s(self._max_pages())
                for g in self._gw_pow2s()]

    def fused_bucket_shapes(self) -> List[Tuple[int, int, int, int]]:
        """Every (batch-bucket, chunk-bucket, pages-bucket,
        exchange-bucket) shape the fused step can be jitted at.  The
        chunk axis spans the FULL ``prefill_chunk`` universe — the
        autotuner only moves ``chunk_now`` along pow2 values inside it
        (decode-only steps land on chunk bucket 1, the degenerate
        chunk)."""
        return [(b, c, p, g) for b in _pow2s(self.ecfg.max_batch)
                for c in _pow2s(self.ecfg.prefill_chunk)
                for p in _pow2s(self._max_pages())
                for g in self._gw_pow2s()]

    def bucket_count(self) -> int:
        """Upper bound on paged-decode jit compilations: one per
        (batch-bucket, pages-bucket) pair."""
        return len(self.decode_bucket_shapes())

    def prefill_bucket_count(self) -> int:
        """Upper bound on chunked-prefill jit compilations: one per
        (batch-bucket, chunk-bucket, pages-bucket) triple."""
        return len(self.prefill_bucket_shapes())

    def decode_compile_count(self) -> int:
        """Actual number of paged-decode compilations so far."""
        return int(self._paged_fn._cache_size())

    def prefill_compile_count(self) -> int:
        """Actual number of chunked-prefill compilations so far."""
        return int(self._chunk_fn._cache_size())

    def fused_bucket_count(self) -> int:
        """Upper bound on fused-step jit compilations: one per
        (batch-bucket, chunk-bucket, pages-bucket) triple."""
        return len(self.fused_bucket_shapes())

    def fused_compile_count(self) -> int:
        """Actual number of fused-step compilations so far."""
        return int(self._fused_fn._cache_size())

    # ------------------------------------------------------------------ admit
    def submit(self, req: Request) -> None:
        req.arrival = req.arrival or self.clock
        req.submit_s = time.perf_counter()
        self.queue.append(req)

    def _note_scheduled(self, reqs: Iterable[Request]) -> None:
        """Count each request's wait from submit to the first step that
        schedules a chunk of it (``queue/wait_s``, ``queue/served``) —
        once: a preemption replay keeps the first wait."""
        for r in reqs:
            if r.queue_wait_s is None and r.submit_s is not None:
                r.queue_wait_s = time.perf_counter() - r.submit_s
                self._c_wait.inc(r.queue_wait_s)
                self._c_served.inc()

    def _try_admit(self) -> List[Request]:
        tr = self.tracer
        admitted = []
        while self.queue and (len(self.running) + len(self.prefilling)
                              < self.ecfg.max_batch):
            req = self.queue[0]
            if req.arrival > self.clock:
                if not self.running and not self.prefilling and not admitted:
                    # idle: jump to the next arrival
                    self.clock = req.arrival
                else:
                    break
            ar = AttnRequest(rid=req.rid, ctx_len=req.ctx_len,
                             n_heads=self.cfg.n_heads,
                             group_ratio=self.cfg.gqa_ratio,
                             head_dim=self.cfg.head_dim,
                             dtype_bytes=self._kv_itemsize,
                             arrival=req.arrival)
            with tr.span("lp", args={"rid": req.rid}):
                placement = dispatch_lp(self.workers, [ar])
                if placement is not None:
                    apply_placement(self.workers, [ar], placement)
            if placement is None:
                break
            req.placement = placement[ar.rid]
            self.attn_reqs[req.rid] = ar
            # page allocation per kv group on assigned devices
            with tr.span("alloc", args={"rid": req.rid}):
                ok = self._alloc_pages(req, ar)
            if not ok:
                release_request(self.workers, ar)
                del self.attn_reqs[req.rid]
                break
            self.queue.popleft()
            admitted.append(req)
        return admitted

    def _groups_by_device(self, placement: Dict[int, int]) -> Dict[int, int]:
        """query-head placement -> kv-group counts per device."""
        r = self.cfg.gqa_ratio
        return {dev: heads // r for dev, heads in placement.items()}

    def _alloc_pages(self, req: Request, ar: AttnRequest) -> bool:
        g = 0
        for dev, ngroups in self._groups_by_device(req.placement).items():
            for _ in range(ngroups):
                if not self.kv.ensure_capacity(req.rid, g, dev,
                                               req.ctx_len):
                    self.kv.release(req.rid)
                    return False
                self.kv.lengths[(req.rid, g)] = req.ctx_len
                g += 1
        return g == self.cfg.n_kv_heads

    # ---------------------------------------------------------------- prefill
    def _prefill(self, req: Request) -> None:
        # a PREEMPTED request resumes with prompt + generated tokens as the
        # prefill input (teacher-forcing: identical K/V and next-token
        # logits to the decode steps it replays, so resumption stays exact)
        self._note_scheduled((req,))
        tokens = jnp.asarray(req.prompt + req.output, jnp.int32)[None]
        ctx = int(tokens.shape[1])
        with self.tracer.span("prefill", args={"rid": req.rid, "ctx": ctx}):
            logits, cache = self._prefill_fn(self.params, {"tokens": tokens})
            self.tracer.sync(logits)
        self._c_model_calls.inc()
        self._c_h2d.inc(tokens.nbytes)
        self._c_pre_h2d.inc(tokens.nbytes)
        # bulk-store prompt K/V for all head groups: one device scatter,
        # no host round-trip of the cache contents
        kv = cache["groups"][0]
        self.kv.store_prompt_request(req.rid, kv["k"][:, 0, :ctx],
                                     kv["v"][:, 0, :ctx])
        req.prefill_pos = ctx
        first = int(np.argmax(np.asarray(logits[0])))
        self._c_d2h.inc(np.asarray(logits).nbytes)
        req.output.append(first)
        # one token appended to every group's cache next decode step
        req.state = RequestState.RUNNING
        if req.ttft is None:
            req.ttft = self.clock - req.arrival
            self._h_ttft.observe(req.ttft)
        self.running.append(req)
        if req.done:        # max_new_tokens == 1, or resume filled the last
            self._finish(req)

    def _prefill_chunk_step(self) -> None:
        """Run ONE prompt chunk for every prefilling request, batched into
        a single jitted ``paged_prefill_chunk`` call.  K/V lands directly
        in the device pools; a request whose chunk completes its prompt
        (incl. preemption-replay tokens) samples its first token and joins
        the decode batch.  Long prompts spread over several steps, so the
        running decode batch keeps producing tokens in between (Sarathi-
        style piggybacking)."""
        rows = [(r, r.prompt + r.output) for r in self.prefilling]
        if not rows:
            return
        cfg = self.cfg
        tr = self.tracer
        with tr.span("plan"):
            self._note_scheduled(r for r, _ in rows)
            Hkv, page = cfg.n_kv_heads, self.kv.page
            chunk = self.ecfg.prefill_chunk
            spans = [(r, full, min(chunk, len(full) - r.prefill_pos))
                     for r, full in rows]
            Bp = _bucket(len(spans))
            Cp = _bucket(max(n for _, _, n in spans))
            maxp = max(-(-(r.prefill_pos + n) // page)
                       for r, _, n in spans)
            Pp = _bucket(maxp)
            sink = self.kv.sink
            plan = self.kv.step_plan()
            toks = np.zeros((Bp, Cp), np.int32)
            starts = np.zeros((Bp,), np.int32)
            lengths = np.zeros((Bp,), np.int32)
            last_idx = np.zeros((Bp,), np.int32)
            tables = np.full((Bp, Hkv, Pp), sink, np.int32)
            wslots = np.full((Bp, Hkv, Cp), sink, np.int32)
            woffs = np.zeros((Bp, Cp), np.int32)
            for i, (r, full, n) in enumerate(spans):
                s0 = r.prefill_pos
                toks[i, :n] = full[s0:s0 + n]
                starts[i] = s0
                lengths[i] = s0 + n
                last_idx[i] = n - 1
                slots, offs = plan.scatter_indices(r.rid, s0, n)
                wslots[i, :, :n] = slots
                woffs[i, :n] = offs
                # the chain covers the FULL prompt; the kernel only reads
                # pages with base < lengths[i], so only those are staged
                # from remote shards (anchor-local pages keep the full
                # chain)
                tables[i] = plan.block_table_matrix(r.rid, Pp,
                                                    n_tokens=s0 + n)
            Gp = _bucket0(plan.gather_count)
            exch = plan.exchange_arrays(Gp)
            self._prefill_shapes.add((Bp, Cp, Pp, Gp))
            host = exch + (tables, lengths, starts, wslots, woffs, toks,
                           last_idx)
            h2d = sum(a.nbytes for a in host)
            self._c_gather_d2d.inc(plan.d2d_bytes())
        with tr.span("upload"):
            dev = self._upload(host, h2d)
        with tr.span("prefill_chunk",
                     args={"batch": Bp, "chunk": Cp, "pages": Pp}):
            if self._trace_modules:
                a0, d0 = self._probe_totals()
                kps, vps = self.kv.pools()
                logits, kps, vps = T.sharded_prefill_chunk_traced(
                    cfg, self.params, kps, vps, self.kv.anchor,
                    self.kv.sink, *dev, tracer=self.tracer,
                    span_args=self._module_span_args(
                        [r for r, _, _ in spans]))
                self.kv.install_pools(kps, vps)
                a1, d1 = self._probe_totals()
                self._attribute_module_times(a1 - a0, d1 - d0)
            else:
                kps, vps = self.kv.pools()
                logits, kps, vps = self._chunk_fn(
                    self.params, kps, vps, *dev)
                self.kv.install_pools(kps, vps)
            self.tracer.sync(logits)
        self._c_model_calls.inc()
        self._c_h2d.inc(h2d)
        self._c_pre_h2d.inc(h2d)
        self._c_chunks.inc()
        self.clock += self._model_prefill_time(
            sum(n for _, _, n in spans))
        with tr.span("readback"):
            nxt = None
            for i, (r, full, n) in enumerate(spans):
                r.prefill_pos += n
                if r.prefill_pos < len(full):
                    continue
                if nxt is None:             # logits pulled once, on demand
                    nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
                    self._c_d2h.inc(logits.nbytes)
                r.output.append(int(nxt[i]))
                r.state = RequestState.RUNNING
                self.prefilling.remove(r)
                self.running.append(r)
                if r.ttft is None:
                    r.ttft = self.clock - r.arrival
                    self._h_ttft.observe(r.ttft)
                if r.done:  # max_new_tokens == 1, or resume filled the last
                    self._finish(r)

    # ----------------------------------------------------------------- decode
    def _decode_batch(self) -> None:
        reqs = [r for r in self.running if not r.done]
        if not reqs:
            return
        if self.use_paged:
            self._decode_batch_paged(reqs)
        else:
            self._decode_batch_dense(reqs)

    def _reserve_decode_rows(self, reqs: List[Request]) -> List[Request]:
        """Reserve page room for this step's token in every group chain;
        exhaustion triggers §5.3 handling, which may preempt requests
        (possibly the one being reserved, possibly a prefilling one) out
        of this step's batch.  Returns the rows that survived with
        capacity in hand."""
        active: List[Request] = []
        for r in reqs:
            if r not in self.running:
                continue                       # evicted by a prior handler
            ok = True
            for grp, dev in self._group_devices(r):
                n = r.ctx_len - 1              # tokens stored so far
                if self.kv.ensure_capacity(r.rid, grp, dev, n + 1):
                    continue
                self._on_memory_exhausted(dev)
                if r not in self.running or \
                        not self.kv.ensure_capacity(r.rid, grp, dev, n + 1):
                    ok = False
                    break
            if ok and r in self.running:
                active.append(r)
        return [r for r in active if r in self.running]

    def _decode_batch_paged(self, reqs: List[Request]) -> None:
        """Fast path: block tables + device-resident pools, no gather."""
        cfg = self.cfg
        tr = self.tracer
        with tr.span("plan"):
            Hkv, page = cfg.n_kv_heads, self.kv.page
            active = self._reserve_decode_rows(reqs)
            if not active:
                return
            B = len(active)
            Bp = _bucket(B)
            maxp = max(-(-r.ctx_len // page) for r in active)
            Pp = _bucket(maxp)
            sink = self.kv.sink
            plan = self.kv.step_plan()
            tables = np.full((Bp, Hkv, Pp), sink, np.int32)
            lengths = np.zeros((Bp,), np.int32)
            wslot = np.full((Bp, Hkv), sink, np.int32)
            woff = np.zeros((Bp,), np.int32)
            pos = np.zeros((Bp,), np.int32)
            toks = np.zeros((Bp, 1), np.int32)
            for i, r in enumerate(active):
                p_new = r.ctx_len - 1
                tables[i] = plan.block_table_matrix(r.rid, Pp,
                                                    n_tokens=p_new + 1)
                slots, offs = plan.scatter_indices(r.rid, p_new, 1)
                wslot[i] = slots[:, 0]
                lengths[i] = p_new + 1
                woff[i] = offs[0]
                pos[i] = p_new
                toks[i, 0] = r.output[-1]
            Gp = _bucket0(plan.gather_count)
            exch = plan.exchange_arrays(Gp)
            self._decode_shapes.add((Bp, Pp, Gp))
            host = exch + (tables, lengths, wslot, woff, toks, pos)
            h2d = sum(a.nbytes for a in host)
            self._c_gather_d2d.inc(plan.d2d_bytes())
        with tr.span("upload"):
            dev = self._upload(host, h2d)
        with tr.span("paged_decode", args={"batch": Bp, "pages": Pp}):
            if self._trace_modules:
                a0, d0 = self._probe_totals()
                kps, vps = self.kv.pools()
                logits, kps, vps = T.sharded_decode_step_traced(
                    cfg, self.params, kps, vps, self.kv.anchor,
                    self.kv.sink, *dev, tracer=self.tracer,
                    span_args=self._module_span_args(active))
                self.kv.install_pools(kps, vps)
                a1, d1 = self._probe_totals()
                self._attribute_module_times(a1 - a0, d1 - d0)
            else:
                kps, vps = self.kv.pools()
                logits, kps, vps = self._paged_fn(
                    self.params, kps, vps, *dev)
                self.kv.install_pools(kps, vps)
            self.tracer.sync(logits)
        self._c_model_calls.inc()
        self._c_h2d.inc(h2d)
        with tr.span("readback"):
            nxt = np.asarray(jnp.argmax(logits[:B], axis=-1), np.int32)
            self._c_d2h.inc(logits.nbytes)
            for r in active:
                # the reservation above already advanced kv.lengths; the
                # jitted step scattered the token K/V into those pages
                grow_context(self.workers, self.attn_reqs[r.rid], 1)
            for i, r in enumerate(active):
                r.output.append(int(nxt[i]))
                if r.done:
                    self._finish(r)

    def _decode_batch_dense(self, reqs: List[Request]) -> None:
        """Reference path: gather pages into a dense host-side cache,
        upload, decode, download the written K/V and re-page it."""
        cfg = self.cfg
        B = len(reqs)
        max_len = max(r.ctx_len + 1 for r in reqs)
        max_len = min(max_len, self.ecfg.max_seq)
        L, Hkv, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        K = np.zeros((L, B, max_len, Hkv, dh), np.float32)
        V = np.zeros_like(K)
        pos = np.zeros((B,), np.int32)
        toks = np.zeros((B, 1), np.int32)
        for i, r in enumerate(reqs):
            k, v = self.kv.gather_dense(r.rid, max_len)
            K[:, i] = k
            V[:, i] = v
            pos[i] = r.ctx_len - 1          # position of the not-yet-stored
            toks[i, 0] = r.output[-1]       # last generated token
        cache = {"groups": [{"k": jnp.asarray(K), "v": jnp.asarray(V)}],
                 "pos": jnp.asarray(pos)}
        self._c_h2d.inc(K.nbytes + V.nbytes + pos.nbytes + toks.nbytes)
        with self.tracer.span("dense_decode", args={"batch": B}):
            logits, new_cache = self._decode_fn(self.params, cache,
                                                jnp.asarray(toks))
            self.tracer.sync(logits)
        self._c_model_calls.inc()
        nk = np.asarray(new_cache["groups"][0]["k"])
        nv = np.asarray(new_cache["groups"][0]["v"])
        self._c_d2h.inc(nk.nbytes + nv.nbytes + np.asarray(logits).nbytes)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        for i, r in enumerate(reqs):
            p = int(pos[i])
            ar = self.attn_reqs[r.rid]
            # store the token K/V written by decode into pages + grow
            for grp, dev in self._group_devices(r):
                ok = self.kv.append_token(
                    r.rid, grp, dev, (nk[:, i, p, grp], nv[:, i, p, grp]))
                if not ok:
                    self._on_memory_exhausted(dev)
                    self.kv.append_token(
                        r.rid, grp, dev,
                        (nk[:, i, p, grp], nv[:, i, p, grp]))
            grow_context(self.workers, ar, 1)
            r.output.append(int(nxt[i]))
            if r.done:
                self._finish(r)

    # ------------------------------------------------------------ fused step
    def _fused_step(self) -> None:
        """ONE jitted ``paged_fused_step`` call per iteration: the row
        batch mixes decode rows (the degenerate chunk — one token at
        position ``ctx - 1``) and prefill rows (FCFS chunks of ≤
        ``chunk_now`` prompt tokens), packed under the per-step token
        budget.  Decode rows are always admitted; prefill tokens fill the
        remainder.  Token streams are identical to the split schedule —
        only the step a finished prefill row starts decoding on shifts by
        one (it joins ``running`` after this call instead of decoding in
        the same iteration's second call)."""
        cfg = self.cfg
        tr = self.tracer
        with tr.span("plan"):
            Hkv, page = cfg.n_kv_heads, self.kv.page
            # reserve decode capacity FIRST: §5.3 handling inside may
            # preempt prefilling requests, which must not be in this
            # step's row batch
            dec = self._reserve_decode_rows(
                [r for r in self.running if not r.done])
            budget = self.ecfg.token_budget or (self.ecfg.max_batch
                                                + self.ecfg.prefill_chunk)
            left = budget - len(dec)        # decode rows always admitted
            spans: List[Tuple[Request, List[int], int]] = []
            for r in self.prefilling:
                if left <= 0:
                    break
                full = r.prompt + r.output
                n = min(self._chunk_now, len(full) - r.prefill_pos, left)
                if n <= 0:
                    break
                spans.append((r, full, n))
                left -= n
            if not dec and not spans:
                return
            self._note_scheduled(r for r, _, _ in spans)
            rows = ([(r.rid, r.ctx_len - 1, 1) for r in dec]
                    + [(r.rid, r.prefill_pos, n) for r, _, n in spans])
            B = len(rows)
            Bp = _bucket(B)
            Cp = _bucket(max(n for _, _, n in rows))
            maxp = max(-(-(s + n) // page) for _, s, n in rows)
            Pp = _bucket(maxp)
            sink = self.kv.sink
            plan = self.kv.step_plan()
            toks = np.zeros((Bp, Cp), np.int32)
            starts = np.zeros((Bp,), np.int32)
            lengths = np.zeros((Bp,), np.int32)
            last_idx = np.zeros((Bp,), np.int32)
            tables = np.full((Bp, Hkv, Pp), sink, np.int32)
            ws, wo = plan.mixed_scatter_indices(rows, Cp)
            wslots = np.full((Bp, Hkv, Cp), sink, np.int32)
            woffs = np.zeros((Bp, Cp), np.int32)
            wslots[:B] = ws
            woffs[:B] = wo
            for i, (rid, s0, n) in enumerate(rows):
                starts[i] = s0
                lengths[i] = s0 + n
                last_idx[i] = n - 1
                # the chain covers the FULL prompt; the kernel only reads
                # pages with base < lengths[i], so only those are staged
                # from remote shards (anchor-local pages keep the full
                # chain)
                tables[i] = plan.block_table_matrix(rid, Pp,
                                                    n_tokens=s0 + n)
            for i, r in enumerate(dec):
                toks[i, 0] = r.output[-1]
            for j, (r, full, n) in enumerate(spans):
                toks[len(dec) + j, :n] = full[r.prefill_pos:
                                              r.prefill_pos + n]
            Gp = _bucket0(plan.gather_count)
            exch = plan.exchange_arrays(Gp)
            self._fused_shapes.add((Bp, Cp, Pp, Gp))
            host = exch + (tables, lengths, starts, wslots, woffs, toks,
                           last_idx)
            h2d = sum(a.nbytes for a in host)
            self._c_gather_d2d.inc(plan.d2d_bytes())
        with tr.span("upload"):
            dev = self._upload(host, h2d)
        n_pre = sum(n for _, _, n in spans)
        # timing the step for the autotuner costs a device sync, so only
        # pay it when an SLO is configured (the eager probe already syncs)
        time_it = self.ecfg.tpot_slo_s > 0.0 and not self._trace_modules
        rc0 = self._c_recompiles.value
        with tr.span("fused_step", args={"batch": Bp, "chunk": Cp,
                                         "pages": Pp,
                                         "decode_rows": len(dec),
                                         "prefill_tokens": n_pre}):
            t0 = time.perf_counter() if time_it else 0.0
            if self._trace_modules:
                a0, d0 = self._probe_totals()
                kps, vps = self.kv.pools()
                logits, kps, vps = T.sharded_fused_step_traced(
                    cfg, self.params, kps, vps, self.kv.anchor,
                    self.kv.sink, *dev, tracer=tr,
                    span_args=self._module_span_args(
                        dec + [r for r, _, _ in spans]))
                self.kv.install_pools(kps, vps)
                a1, d1 = self._probe_totals()
                self._attribute_module_times(a1 - a0, d1 - d0)
            else:
                kps, vps = self.kv.pools()
                logits, kps, vps = self._fused_fn(
                    self.params, kps, vps, *dev)
                self.kv.install_pools(kps, vps)
            tr.sync(logits)
            if time_it:
                jax.block_until_ready(logits)
                if self._c_recompiles.value == rc0:
                    self._autotune_chunk(time.perf_counter() - t0)
        self._c_model_calls.inc()
        self._c_fused.inc()
        self._c_h2d.inc(h2d)
        if spans:
            self._c_pre_h2d.inc(h2d)
            self._c_chunks.inc()
            self.clock += self._model_prefill_time(n_pre)
        with tr.span("readback"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            self._c_d2h.inc(logits.nbytes)
            for r in dec:
                # the reservation already advanced kv.lengths; the jitted
                # step scattered the token K/V into those pages on device
                grow_context(self.workers, self.attn_reqs[r.rid], 1)
            for i, r in enumerate(dec):
                r.output.append(int(nxt[i]))
                if r.done:
                    self._finish(r)
            for j, (r, full, n) in enumerate(spans):
                r.prefill_pos += n
                if r.prefill_pos < len(full):
                    continue
                r.output.append(int(nxt[len(dec) + j]))
                r.state = RequestState.RUNNING
                self.prefilling.remove(r)
                self.running.append(r)
                if r.ttft is None:
                    r.ttft = self.clock - r.arrival
                    self._h_ttft.observe(r.ttft)
                if r.done:  # max_new_tokens == 1, or resume filled the last
                    self._finish(r)

    def _autotune_chunk(self, warm_s: float) -> None:
        """Feed one warm (recompile-free) fused-step wall latency to the
        chunk autotuner: when the EWMA overruns the decode TPOT SLO the
        prefill chunk halves (shed prompt work from the iteration); with
        ≥2x headroom it doubles back.  Pow2 moves clamped to
        [1, prefill_chunk] keep every reachable shape inside
        ``fused_bucket_shapes()``."""
        self._h_fused_warm.observe(warm_s)
        slo = self.ecfg.tpot_slo_s
        if warm_s > slo:
            self._c_slo_viol.inc()
        ew = self._h_fused_warm.ewma
        if ew > slo and self._chunk_now > 1:
            self._chunk_now //= 2
        elif (ew < 0.5 * slo
              and self._chunk_now < _bucket(self.ecfg.prefill_chunk)):
            self._chunk_now *= 2

    def _group_devices(self, req: Request):
        out = []
        g = 0
        for dev, ngroups in self._groups_by_device(req.placement).items():
            for _ in range(ngroups):
                out.append((g, dev))
                g += 1
        return out

    def _finish(self, req: Request) -> None:
        req.state = RequestState.FINISHED
        req.finish_time = self.clock
        if req.ttft is not None and len(req.output) > 1:
            decode_s = max(0.0, (self.clock - req.arrival) - req.ttft)
            self._h_tpot.observe(decode_s / (len(req.output) - 1))
        self.kv.release(req.rid)
        ar = self.attn_reqs.pop(req.rid, None)
        if ar is not None:
            release_request(self.workers, ar)
        self.running.remove(req)
        self.finished.append(req)

    # ---------------------------------------------------------------- balance
    def _on_memory_exhausted(self, device_id: int) -> None:
        decisions, evicted = handle_memory_exhaustion(
            self.workers, list(self.attn_reqs.values()), device_id,
            theta=self.ecfg.theta)
        for d in decisions:
            self._apply_migration(d.request.rid, d.new_placement)
            self._c_redisp.inc()
        for ar in evicted:
            req = next(r for r in self.running + self.prefilling
                       if r.rid == ar.rid)
            self._preempt(req)

    def _preempt(self, req: Request) -> None:
        """Device-local LIFO eviction (§5.3): release the request's pages
        and requeue it at the front; it resumes via replay prefill (the
        chunked path replays prompt + generated tokens chunk by chunk)."""
        self.kv.release(req.rid)
        req.state = RequestState.PREEMPTED
        req.placement = {}
        req.prefill_pos = 0
        if req in self.running:
            self.running.remove(req)
        if req in self.prefilling:
            self.prefilling.remove(req)
        self.attn_reqs.pop(req.rid, None)
        self.queue.appendleft(req)
        self._c_evict.inc()

    def _apply_migration(self, rid: int, new_placement: Dict[int, int]
                         ) -> None:
        req = next((r for r in self.running + self.prefilling
                    if r.rid == rid), None)
        if req is None:
            return
        with self.tracer.span("migrate", args={"rid": rid}) as sp:
            req.placement = dict(new_placement)
            # Move group chains to their new devices by cross-pool copy.
            # Only bytes that PHYSICALLY moved are metered and handed to
            # the hauler (as per-source-device tasks debited against the
            # compute-overlap window in step()); an all-or-nothing refusal
            # (destination shard full) is surfaced instead of silently
            # booked.
            moved_bytes = 0.0
            tasks: List[MigrationTask] = []
            incomplete = copied = 0
            for grp, dev in self._group_devices(req):
                res = self.kv.migrate_group(rid, grp, dev)
                if not res.complete:
                    incomplete += 1
                    continue
                copied += res.moved > 0
                moved_bytes += res.nbytes
                for src, pages in res.by_src.items():
                    tasks.append(MigrationTask(
                        rid, src, dev, heads=float(self.cfg.gqa_ratio),
                        nbytes=float(pages * self.kv.bytes_per_slot())))
            sp.set(groups=copied, bytes=moved_bytes)
        if incomplete:
            self._c_migr_partial.inc(incomplete)
            warnings.warn(
                f"migration of rid={rid} incomplete: {incomplete} head "
                f"group(s) stayed on their source device (destination "
                f"pool shard full); physical placement diverges from the "
                f"dispatcher's until pages free up", RuntimeWarning,
                stacklevel=2)
        if tasks:
            self.hauler.submit(tasks)
        self._c_migr.inc(moved_bytes)
        self._c_d2d.inc(moved_bytes)

    # ------------------------------------------------------------------- step
    def step(self) -> Dict[str, float]:
        tr = self.tracer
        with tr.span("step"):
            with tr.span("admit"):
                admitted = self._try_admit()
            for req in admitted:
                req.prefill_start = self.clock
                if self.use_paged_prefill:
                    # chunked: prompt writes spread over the next steps,
                    # interleaved with decode — no head-of-line blocking
                    self.prefilling.append(req)
                else:
                    self.clock += self._model_prefill_time(len(req.prompt))
                    self._prefill(req)
            if self.use_fused:
                # ONE jitted call packs decode rows + prefill chunks
                self._fused_step()
            else:
                if self.use_paged_prefill:
                    self._prefill_chunk_step()
                self._decode_batch()
            # Θ-triggered rebalance (at most one request per step, §5.3);
            # once the module probe has attributed measured attention time,
            # the dispatcher recalibrates from the snapshot first
            with tr.span("rebalance") as sp:
                snap = (self.snapshot(ATTN_SNAPSHOT_PREFIX)
                        if self._measured_attn else None)
                d = maybe_rebalance(self.workers,
                                    list(self.attn_reqs.values()),
                                    theta=self.ecfg.theta, snapshot=snap)
                if d is not None:
                    sp.set(rid=d.request.rid)
                    self._apply_migration(d.request.rid, d.new_placement)
                    self._c_redisp.inc()
            step_time = self._model_decode_time()
            # migrations ride in the dense-compute overlap window (§6);
            # the link model follows the measured h2d bandwidth gauge
            if self._g_h2d_gbps.value > 0.0:
                self.hauler.calibrate_from_snapshot(self.snapshot("xfer/"))
            self.hauler.advance(step_time * self.ecfg.migration_overlap)
            self.clock += step_time
            self._c_steps.inc()
        return {"clock": self.clock, "running": len(self.running),
                "prefilling": len(self.prefilling),
                "queued": len(self.queue)}

    # ------------------------------------------------------ simulated timing
    def _model_prefill_time(self, prompt_len: int) -> float:
        devs = self._devs
        t = 0.0
        for did in self.primary_ids:
            cls = devs[did].cls
            fl = dense_flops_layer(self.profile, prompt_len) \
                * self.profile.n_layers / len(self.primary_ids)
            t = max(t, fl / (cls.dense_tflops * 1e12 * self._dense_eff))
        return t

    def _model_decode_parts(self) -> Tuple[float, float]:
        """(attention, dense) modeled step seconds; the dense term uses the
        calibrated roofline efficiency (EWMA-updated from measured dense
        module spans when the probe runs, 0.5 analytic prior otherwise)."""
        if not self.attn_reqs:
            return 1e-4, 0.0
        r0 = next(iter(self.attn_reqs.values()))
        attn_t = current_attention_time(self.workers, r0.group_ratio,
                                        r0.head_dim, r0.dtype_bytes)
        devs = self._devs
        dense_t = 0.0
        nb = max(1, len(self.running))
        for did in self.primary_ids:
            cls = devs[did].cls
            fl = dense_flops_layer(self.profile, nb) * self.profile.n_layers \
                / len(self.primary_ids)
            dense_t = max(dense_t, fl / (cls.dense_tflops * 1e12
                                         * self._dense_eff))
        return attn_t, dense_t

    def _model_decode_time(self) -> float:
        attn_t, dense_t = self._model_decode_parts()
        return attn_t + dense_t

    # ------------------------------------------------------------------- run
    def run_until_drained(self, max_steps: int = 10000) -> bool:
        """Step until every request finishes or ``max_steps`` elapse.
        Returns ``True`` when drained; hitting the step cap with work
        still queued/running warns and bumps the ``run_undrained``
        counter instead of exiting silently."""
        for _ in range(max_steps):
            if not self.queue and not self.running and not self.prefilling:
                return True
            self.step()
        if self.queue or self.running or self.prefilling:
            self._c_undrained.inc()
            warnings.warn(
                f"run_until_drained exiting at max_steps={max_steps} with "
                f"{len(self.queue)} queued / {len(self.running)} running / "
                f"{len(self.prefilling)} prefilling requests unfinished",
                RuntimeWarning, stacklevel=2)
            return False
        return True
