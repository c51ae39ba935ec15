"""MoSKA serving engine: continuous batching over slot-based decode waves.

The full request path of the paper's system:

  register_corpus()  — precompute a domain corpus' KV once (prefill) and
                       chunk it into a SharedKVStore ("experts"), persistent
                       across requests — the Shared-KV node state.
  submit()/run()     — scheduler admits requests into B slots; unique
                       prefill writes per-slot caches (Unique-KV node
                       state); each decode wave runs one jit'd step where
                       every layer routes + batches shared attention across
                       all concurrent slots (the GEMM) and LSE-merges with
                       per-slot unique attention.

Static shapes: (B slots, max_seq) so decode steps hit one compiled program.
Slot raggedness is handled by per-slot lengths; inactive slots decode
garbage into slot-local buffers that are masked out of results.

Zero-copy hot path: the (L, B, S, KH, D) unique-KV batch cache is allocated
once, kept resident on device across ``run()`` calls, and **donated** into
the jit'd decode step and the per-slot admission write — XLA mutates the
cache buffer in place instead of copying it every wave
(``engine/decode_cache_bytes_copied`` reports 0 when donation is on).
Admission writes only the admitted slot (``kvcache.write_slot_prefix``),
not a full-cache merge. Prefill prompt lengths are rounded up to a small
bucket set so the prefill jit cache stays bounded
(``engine/prefill_compile_count``) instead of growing with every distinct
prompt length; pad positions are excluded from routing and logits so the
bucketed program computes exactly what the exact-length program would.
``run()`` may be called repeatedly on one engine; finished slots are
rewritten (and their tails zeroed) on re-admission.

Paged KV layout (``EngineConfig(kv_layout="paged")``): instead of the
per-slot ``max_seq`` slab, unique KV lives in a pool of ``block_size``-token
pages mapped through per-slot block tables (``repro.kvcache``). Admission
allocates only the prompt's blocks, decode appends pages on demand, and
identical prompts over one corpus share pages copy-on-write — so the same
``mem_budget_bytes`` admits more concurrent requests. Generations are
bit-identical to the slotted layout (the gather view tiles ``max_seq``
exactly and masked positions carry exactly-zero probability). Prompts
longer than ``max_seq`` are served via chunked prefill
(``prefill_chunk``-token pieces against a growing scratch context).

Host memory tier (``EngineConfig(host_pool_blocks=N)``): prefix entries
the device pool LRU-evicts are copied page-granularly to a host-side
pool instead of being dropped; a later hit on the same
(corpus-fingerprint, prompt) key swaps the pages back into free device
blocks bit-exactly, skipping the prefill entirely
(``kvcache/swap_in_hits`` vs ``engine/prefill_tokens``). Only when the
host tier has also evicted the entry does the engine fall back to the
deterministic rebuild-from-tokens path. The scheduler participates via
the offload admission path: under block-budget pressure cold resident
pages are offloaded to admit new work rather than deferring it.
"""
from __future__ import annotations

import collections
import hashlib
import time
from dataclasses import dataclass
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.scheduler import Request, Scheduler, SchedulerConfig
from repro.core.shared_attention import DispatchStats
from repro.core.shared_kv import SharedKVStore, build_store
from repro.kvcache.block_table import (SlotTables, blocks_for,
                                       validate_block_size)
from repro.kvcache.cache import KVCache, write_slot_prefix
from repro.kvcache.paged import (BlockPool, HostBlockPool, PagedKVCache,
                                 PoolExhausted, copy_block, extract_blocks,
                                 grow_paged_kv_cache, insert_blocks,
                                 write_blocks)
from repro.kvcache.transfer import PrefetchEngine
from repro.models.model import Model, build_model

#: smallest prefill bucket; "auto" buckets are powers of two from here up
#: to 128, then multiples of 128 (the MoSKA prefill route-block size) up
#: to max_seq.
MIN_PREFILL_BUCKET = 16


def resolve_prefill_buckets(spec: Union[str, Sequence[int], None],
                            max_seq: int) -> Optional[Tuple[int, ...]]:
    """Resolve an EngineConfig.prefill_buckets spec to a sorted tuple.

    ``"auto"`` — powers of two in [16, 128], then multiples of 128, capped
    at max_seq. ``None`` or an empty sequence — bucketing off (exact
    prompt lengths; one prefill program per distinct length). A sequence —
    used as-is (each bucket must be <= 128 or a multiple of 128 for the
    routed shared-attention prefill to block evenly).
    """
    if spec is None:
        return None
    if isinstance(spec, str):
        if spec != "auto":
            raise ValueError(f"unknown prefill_buckets spec {spec!r}")
        buckets = []
        b = MIN_PREFILL_BUCKET
        while b <= min(max_seq, 128):
            buckets.append(b)
            b *= 2
        b = 256
        while b <= max_seq:
            buckets.append(b)
            b += 128
        return tuple(buckets) if buckets else None
    buckets = tuple(sorted(set(int(b) for b in spec)))
    if not buckets:
        return None
    for b in buckets:
        if b < 1 or b > max_seq:
            raise ValueError(f"prefill bucket {b} outside [1, {max_seq}]")
        if b > 128 and b % 128:
            raise ValueError(
                f"prefill bucket {b} > 128 must be a multiple of 128 "
                "(MoSKA prefill route-block size)")
    return buckets


def bucket_for(buckets: Optional[Tuple[int, ...]], n: int) -> int:
    """Smallest bucket >= n; falls back to the exact length when bucketing
    is off or n exceeds the largest bucket."""
    if buckets:
        for b in buckets:
            if b >= n:
                return b
    return n


class Stepped(NamedTuple):
    """A served program's new state (cache, pool or prefill context) with
    the shared path's per-layer dispatch stats beside it (None without a
    store). The programs keep two outputs, (token(s), state), so a wrapper
    of them sees the shape it always had; one that hands back a bare state
    records no stats (``unstep``)."""
    state: Any
    stats: Optional[DispatchStats]


def unstep(out) -> Tuple[Any, Optional[DispatchStats]]:
    """(state, stats) of a served program's second output."""
    return (out.state, out.stats) if isinstance(out, Stepped) else (out, None)


@dataclass
class EngineConfig:
    max_slots: int = 4
    max_seq: int = 512
    eos_id: int = -1           # -1: never stop early
    greedy: bool = True
    mem_budget_bytes: float = float("inf")
    # shared attention in prefill and decode: None (jnp) or 'pallas' (the
    # kernel: compiled on a TPU, interpreted on the CPU backend)
    kernel: Optional[str] = None
    cache_dtype: Any = jnp.bfloat16
    # donate the persistent batch cache into the jit'd decode step and the
    # per-slot admission write (zero-copy; off = functional copies)
    donate_cache: bool = True
    # "auto" | None (exact lengths) | explicit bucket sequence
    prefill_buckets: Union[str, Sequence[int], None] = "auto"
    # -- paged KV layout ------------------------------------------------
    # "slotted": one (L, B, max_seq, KH, D) slab, every slot pays max_seq.
    # "paged": block-pool unique KV with per-slot block tables
    # (dense-family caches only); bit-identical generations, less HBM.
    kv_layout: str = "slotted"
    block_size: int = 16        # tokens per page; must divide max_seq
    # fixed pool size in blocks (incl. the reserved null block); None =
    # start small and grow on demand (hbm_high_water_bytes tracks demand)
    num_blocks: Optional[int] = None
    # chunk length for prompts past max_seq (multiple of 128 keeps the
    # shared-attention route blocks aligned with the single-shot prefill)
    prefill_chunk: int = 128
    # cache completed prompts' pages and remap them (copy-on-write) into
    # later requests with an identical (corpus-content, prompt) key —
    # keyed by corpus *fingerprint*, not id, so identical prompt prefixes
    # hit regardless of which registered store a request is bound to;
    # LRU-evicted under pool pressure
    share_prefix_blocks: bool = True
    # host memory tier (paged layout): capacity, in blocks, of the host
    # pool that LRU-evicted prefix pages are offloaded to instead of
    # being dropped; a later prefix hit swaps them back into free device
    # blocks bit-exactly. 0 disables the tier (evictions rebuild from
    # tokens on the next cold hit).
    host_pool_blocks: int = 0
    # -- async serving pipeline (paged layout) --------------------------
    # in-flight budget for prefetched host->device page copies: during
    # each decode wave, prefix entries the scheduler lookahead predicts
    # will be admitted next are device_put'd early, so the swap-in at
    # admission pays no transfer stall (kvcache/prefetch_{issued,hits,
    # wasted}). 0 disables prefetching (the PR 9 synchronous swap-in).
    prefetch_depth: int = 2
    # speculative decode appends: allocate the *next* page for any slot
    # whose next token lands on a fresh page boundary during the current
    # wave, keeping allocator/eviction work off the boundary wave's
    # critical path; unused pages are reclaimed on release
    # (kvcache/spec_pages_{alloc,reclaimed}).
    spec_append: bool = True
    # wave-overlap execution: dispatch the jit'd decode step, run the
    # next wave's host-side work (table tick, speculative appends,
    # prefetch issue) while the device computes, then block on results
    # (engine/overlap_saved_s vs engine/decode_stall_s). Off = block
    # immediately after dispatch, bit-identical generations.
    overlap_waves: bool = True


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, engine_cfg: EngineConfig):
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.model = build_model(cfg)
        self.params = params
        self.stores: Dict[str, SharedKVStore] = {}
        self.scheduler = Scheduler(SchedulerConfig(
            max_slots=engine_cfg.max_slots,
            mem_budget_bytes=engine_cfg.mem_budget_bytes,
            unique_bytes_per_token=cfg.kv_bytes_per_token,
            max_seq=engine_cfg.max_seq,
            kv_layout=engine_cfg.kv_layout,
            block_size=engine_cfg.block_size))
        self.scheduler.set_store_evictor(self._on_store_evicted)
        obs.watch_compiles()
        donate = engine_cfg.donate_cache
        self._decode = jax.jit(self._decode_impl,
                               static_argnames=("use_store",),
                               donate_argnums=(2,) if donate else ())
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("use_store",))
        self._write_slot = jax.jit(self._write_slot_impl,
                                   donate_argnums=(0,) if donate else ())
        self._write_slot_pytree = jax.jit(
            self._write_slot_pytree_impl,
            donate_argnums=(0,) if donate else ())
        self._buckets = resolve_prefill_buckets(engine_cfg.prefill_buckets,
                                                engine_cfg.max_seq)
        self._prefill_keys: set = set()
        self._cache = None          # persistent (L, B, S, KH, D) batch cache
        # corpus token ids kept host-side so evicted stores can be rebuilt
        self._corpus_tokens: Dict[str, np.ndarray] = {}
        self._hbm_high_water = 0.0
        if engine_cfg.kv_layout == "paged":
            self._init_paged_state()
        elif engine_cfg.kv_layout != "slotted":
            raise ValueError(
                f"unknown kv_layout {engine_cfg.kv_layout!r} "
                "(expected 'slotted' or 'paged')")
        elif engine_cfg.host_pool_blocks:
            raise ValueError(
                "host_pool_blocks requires kv_layout='paged' (the host "
                "tier offloads pages, and the slotted layout has none)")
        self.metrics = {"decode_steps": 0, "prefills": 0,
                        "tokens_generated": 0, "wall_s": 0.0}
        # host-side callbacks run at the end of every decode wave (e.g.
        # the streaming metrics exporter's tick); must not touch device
        # state — the next wave may already be dispatched
        self.wave_hooks: List[Any] = []

    def _init_paged_state(self):
        ecfg = self.ecfg
        self.model._require_paged("kv_layout='paged'")
        validate_block_size(ecfg.block_size, ecfg.max_seq)
        if ecfg.prefill_chunk % ecfg.block_size:
            raise ValueError(
                f"prefill_chunk {ecfg.prefill_chunk} must be a multiple "
                f"of block_size {ecfg.block_size}")
        if ecfg.prefill_chunk > 128 and ecfg.prefill_chunk % 128:
            raise ValueError(
                f"prefill_chunk {ecfg.prefill_chunk} > 128 must be a "
                "multiple of 128 (shared-attention route-block size)")
        m0 = ecfg.max_seq // ecfg.block_size
        # pool growth quantum: one slotted slot's worth of pages, so the
        # decode program recompiles O(total/max_seq) times, not per request
        self._pool_quantum = m0
        cap = ecfg.num_blocks if ecfg.num_blocks is not None else 1 + m0
        self._block_pool = BlockPool(cap)
        self._tables = SlotTables(ecfg.max_slots, m0, ecfg.block_size)
        self._pool: Optional[PagedKVCache] = None   # device pages, lazy
        # (corpus fingerprint, prompt tuple) -> {"blocks": [...],
        # "first": tok}, LRU — fingerprint-keyed so identical prefixes
        # hit across stores with the same corpus content
        self._prefix_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._corpus_fp: Dict[str, str] = {}
        # host memory tier for LRU-evicted prefix pages (capacity 0 = off)
        self._host_pool = HostBlockPool(ecfg.host_pool_blocks)
        # async swap-in: prefetched host->device copies for predicted
        # admissions (only meaningful when the host tier can hold entries
        # and prefix sharing gives them a key to hit)
        self._prefetch: Optional[PrefetchEngine] = None
        if ecfg.host_pool_blocks and ecfg.prefetch_depth and \
                ecfg.share_prefix_blocks:
            self._prefetch = PrefetchEngine(self._host_pool,
                                            ecfg.prefetch_depth)
        # speculatively appended pages not yet written: slot -> table
        # index of the pre-allocated next page (reclaimed on release)
        self._spec_pending: Dict[int, int] = {}
        # the live device pool while run() executes, so the scheduler's
        # offload admission path can extract pages mid-schedule()
        self._cur_pool: Optional[PagedKVCache] = None
        self.scheduler.set_page_offloader(self._cold_page_bytes,
                                          self._offload_cold_pages)
        if ecfg.host_pool_blocks:
            self.registry.set_gauge("kvcache/host_pool_capacity_blocks",
                                    ecfg.host_pool_blocks)
        donate = ecfg.donate_cache
        self._decode_paged = jax.jit(self._decode_paged_impl,
                                     static_argnames=("use_store",),
                                     donate_argnums=(2,) if donate else ())
        self._prefill_chunked = jax.jit(self._prefill_chunk_impl,
                                        static_argnames=("use_store",))
        self._write_blocks = jax.jit(self._write_blocks_impl,
                                     donate_argnums=(0,) if donate else ())
        self._insert_blocks = jax.jit(insert_blocks,
                                      donate_argnums=(0,) if donate else ())

    @property
    def registry(self) -> obs.MetricsRegistry:
        return obs.get_registry()

    @property
    def prefill_buckets(self) -> Optional[Tuple[int, ...]]:
        return self._buckets

    # ------------------------------------------------------------------
    def register_corpus(self, corpus_id: str, tokens: np.ndarray) -> int:
        """Precompute + chunk a shared corpus' KV. Returns #chunks."""
        C = self.cfg.moska.chunk_size
        n = (len(tokens) // C) * C
        if n == 0:
            raise ValueError("corpus shorter than one chunk")
        toks = np.asarray(tokens[:n], np.int32)
        store = self._build_store(corpus_id, toks)
        self.stores[corpus_id] = store
        self._corpus_tokens[corpus_id] = toks
        self.scheduler.register_store(corpus_id, _pytree_nbytes(store))
        reg = self.registry
        reg.inc("engine/corpora_registered")
        reg.inc("engine/corpus_tokens_prefilled", n)
        reg.set_gauge(f"engine/corpus/{corpus_id}/chunks", store.num_chunks)
        return store.num_chunks

    def _build_store(self, corpus_id: str, toks: np.ndarray) -> SharedKVStore:
        C = self.cfg.moska.chunk_size
        with obs.span("engine.register_corpus", corpus_id=corpus_id,
                      tokens=len(toks)):
            cache = self.model.init_cache(1, len(toks), self.ecfg.cache_dtype)
            _, cache = self.model.prefill(self.params,
                                          jnp.asarray(toks)[None], cache)
            return build_store(jax.block_until_ready(cache.k)[:, 0],
                               cache.v[:, 0], C,
                               head_dim=self.cfg.head_dim)

    def _on_store_evicted(self, corpus_id: str) -> None:
        """Scheduler LRU eviction callback: drop the store's device arrays
        (the host token ids are kept, so it can be rebuilt on demand)."""
        self.stores.pop(corpus_id, None)
        self.registry.inc("kvcache/stores_dropped")

    def _get_store(self, corpus_id: Optional[str]) -> Optional[SharedKVStore]:
        """The corpus' device store, rebuilding it if the scheduler evicted
        it for memory; touches its LRU clock."""
        if corpus_id is None:
            return None
        store = self.stores.get(corpus_id)
        if store is None:
            if corpus_id not in self._corpus_tokens:
                raise KeyError(f"corpus {corpus_id!r} not registered")
            store = self._build_store(corpus_id,
                                      self._corpus_tokens[corpus_id])
            self.stores[corpus_id] = store
            self.scheduler.mark_store_loaded(corpus_id)
            # rebalance: reloading may push colder stores out
            self.scheduler._evict_stores_for(0.0, keep=corpus_id)
            self.registry.inc("kvcache/store_reloads")
        self.scheduler.touch_store(corpus_id)
        return store

    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               corpus_id: Optional[str] = None) -> int:
        # registration outlives device residency: an LRU-evicted store is
        # rebuilt from its kept tokens when the corpus becomes resident
        if corpus_id is not None and corpus_id not in self._corpus_tokens \
                and corpus_id not in self.stores:
            raise KeyError(f"corpus {corpus_id!r} not registered")
        return self.scheduler.submit(prompt, max_new_tokens, corpus_id)

    # ------------------------------------------------------------------
    # The jit'd programs below return the shared path's per-layer
    # DispatchStats beside their new state (``Stepped``), so the dispatch
    # metrics reach the host with the tokens, in the same transfer, and no
    # program carries a host callback.
    def _decode_impl(self, params, tokens, cache, store, use_store: bool):
        logits, cache, stats = self.model.decode_step(
            params, tokens, cache, store=store if use_store else None,
            kernel=self.ecfg.kernel, return_stats=True)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, Stepped(cache, stats)

    def _prefill_impl(self, params, tokens, true_len, start, store,
                      use_store: bool):
        """One request's (possibly bucket-padded) prefill into a fresh
        1-batch cache sized to the bucket. Returns (first token,
        Stepped(cache, stats))."""
        slot_cache = self.model.init_cache(1, tokens.shape[1],
                                           self.ecfg.cache_dtype)
        logits, slot_cache, stats = self.model.prefill(
            params, tokens, slot_cache,
            store=store if use_store else None,
            start_pos=start, true_len=true_len, kernel=self.ecfg.kernel,
            return_stats=True)
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        return first, Stepped(slot_cache, stats)

    def _write_slot_impl(self, cache, slot_cache, slot, true_len):
        return write_slot_prefix(cache, slot_cache, slot, true_len)

    def _write_slot_pytree_impl(self, cache, slot_cache, slot):
        """Slot-granular write for non-KVCache cache families (ssm/hybrid
        state pytrees): each (L, 1, S, ...) leaf lands at batch slot
        ``slot`` via dynamic_update_slice — donated, so the batch pytree is
        mutated in place instead of the legacy full-copy merge."""
        def merge(dst, src):
            if dst.ndim == 1:                    # (B,) lengths / offsets
                return dst.at[slot].set(src[0].astype(dst.dtype))
            start = (0, slot) + (0,) * (dst.ndim - 2)
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                                start)
        return jax.tree.map(merge, cache, slot_cache)

    def _decode_paged_impl(self, params, tokens, pool, table, lengths,
                           offsets, store, use_store: bool):
        logits, pool, stats = self.model.decode_step_paged(
            params, tokens, pool, table, lengths, offsets,
            store=store if use_store else None, kernel=self.ecfg.kernel,
            return_stats=True)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, Stepped(pool, stats)

    def _prefill_chunk_impl(self, params, tokens, ctx, start, chunk_len,
                            store, use_store: bool):
        """One fixed-size chunk of a long prompt against the growing
        scratch context ``ctx``; returns (last-real-token argmax,
        Stepped(ctx, stats))."""
        logits, ctx, stats = self.model.prefill_chunk(
            params, tokens, ctx, store=store if use_store else None,
            start_pos=start, chunk_len=chunk_len, kernel=self.ecfg.kernel,
            return_stats=True)
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        return first, Stepped(ctx, stats)

    def _write_blocks_impl(self, pool, block_ids, slot_k, slot_v, true_len):
        """Scatter a (possibly bucket-padded) 1-batch prefill cache into
        the pool pages ``block_ids``; pads/slices the prefix to exactly
        tile the blocks (positions >= true_len are zeroed either way)."""
        heads = pool.k.shape[-2:]                # the pages' (KH, D)
        k = slot_k[:, 0].reshape(slot_k.shape[0], -1, *heads)  # (L,S,KH,D)
        v = slot_v[:, 0].reshape(slot_v.shape[0], -1, *heads)
        V = block_ids.shape[0] * pool.block_size
        S = k.shape[1]
        if S > V:
            k, v = k[:, :V], v[:, :V]
        elif S < V:
            pad = jnp.zeros((k.shape[0], V - S) + k.shape[2:], k.dtype)
            k = jnp.concatenate([k, pad], axis=1)
            v = jnp.concatenate([v, pad.astype(v.dtype)], axis=1)
        return write_blocks(pool, block_ids, k, v, true_len)

    def _active_store(self) -> Optional[SharedKVStore]:
        return self._get_store(self.scheduler.resident_corpus)

    # ------------------------------------------------------------------
    def _ensure_cache(self):
        """The persistent batch cache: allocated once, reused across
        ``run()`` calls (and reallocated only if a failed donated step
        consumed it)."""
        cache = self._cache
        if cache is not None:
            leaves = jax.tree.leaves(cache)
            if any(getattr(l, "is_deleted", lambda: False)() for l in leaves):
                cache = None
        if cache is None:
            cache = self.model.init_cache(self.ecfg.max_slots,
                                          self.ecfg.max_seq,
                                          self.ecfg.cache_dtype)
        nbytes = sum(getattr(l, "nbytes", 0) for l in jax.tree.leaves(cache))
        self.registry.set_gauge(
            "engine/decode_cache_bytes_copied",
            0 if self.ecfg.donate_cache else nbytes)
        self.registry.set_gauge("engine/decode_cache_bytes", nbytes)
        return cache

    def _note_hbm(self, kv_nbytes: float) -> None:
        """Track the peak of (unique KV + loaded shared stores) device
        bytes — the number the paged layout exists to shrink."""
        total = kv_nbytes + self.scheduler.shared_bytes
        if total > self._hbm_high_water:
            self._hbm_high_water = total
        self.registry.set_gauge("engine/hbm_high_water_bytes",
                                self._hbm_high_water)

    def run(self, max_waves: int = 10**9) -> List[Request]:
        """Drive to completion (or max_waves); returns finished requests.

        May be called repeatedly: the batch cache (slotted) / block pool
        (paged) stays resident on device between calls. Raises
        RuntimeError on a livelocked configuration (queued work that can
        never be admitted under mem_budget_bytes).
        """
        if self.ecfg.kv_layout == "paged":
            return self._run_paged(max_waves)
        B = self.ecfg.max_slots
        reg = self.registry
        t0 = time.perf_counter()
        tok0 = self.metrics["tokens_generated"]
        cache = self._ensure_cache()
        self._cache = None      # run() holds the only live reference
        cache_nbytes = _pytree_nbytes(cache)
        slot_tokens = np.zeros((B,), np.int32)

        waves = 0
        host_s = 0.0        # engine host time since the last token vector
        try:
            with obs.span("engine.run"):
                while not self.scheduler.idle and waves < max_waves:
                    with obs.span("engine.schedule") as sp:
                        admitted = self.scheduler.schedule()
                    host_s += sp.duration_s
                    for req in admitted:
                        with obs.span("engine.prefill", uid=req.uid):
                            tp = time.perf_counter()
                            cache, first = self._prefill_slot(cache, req)
                            reg.observe("engine/prefill_latency_s",
                                        time.perf_counter() - tp,
                                        obs.LATENCY_EDGES_S)
                            slot_tokens[req.slot] = first
                            self.scheduler.record_token(req, int(first),
                                                        self.ecfg.eos_id)
                            self.metrics["tokens_generated"] += 1
                            reg.inc("engine/tokens_generated")
                    active = self.scheduler.active()
                    if not active:
                        if not admitted and not self.scheduler.idle:
                            # nothing running, nothing admissible, queue
                            # non-empty: no wave can ever make progress
                            # (counted under scheduler/admission_deferred_mem)
                            raise RuntimeError(
                                "serving livelock: "
                                f"{len(self.scheduler.queue)} queued "
                                "request(s) but none admissible — "
                                f"mem_budget_bytes="
                                f"{self.ecfg.mem_budget_bytes:.3g} is below "
                                "one slot's cost "
                                f"({self.scheduler._slot_cost():.3g} bytes "
                                "+ resident shared stores)")
                        waves += 1
                        continue
                    with obs.span("engine.decode_dispatch") as sp:
                        store = self._active_store()
                        use_store = (store is not None
                                     and self.cfg.moska.enabled)
                        self._note_hbm(cache_nbytes)
                        # batch density: fraction of the static wave the
                        # decode step spends on live requests (the GEMM's N)
                        reg.observe("engine/wave_batch_density",
                                    len(active) / B, obs.FRACTION_EDGES)
                        reg.observe("engine/wave_active_slots", len(active),
                                    obs.COUNT_EDGES)
                        td = time.perf_counter()
                        nxt, out = self._decode(
                            self.params, jnp.asarray(slot_tokens), cache,
                            store, use_store)
                        cache, stats = unstep(out)
                    reg.observe("engine/host_step_s", host_s + sp.duration_s,
                                obs.LATENCY_EDGES_S)
                    with obs.span("engine.decode_wait"):
                        # device sync: latency includes it
                        nxt, stats = jax.device_get((nxt, stats))
                        reg.observe("engine/decode_step_latency_s",
                                    time.perf_counter() - td,
                                    obs.LATENCY_EDGES_S)
                    with obs.span("engine.record") as sp:
                        for req in list(active):
                            tok = int(nxt[req.slot])
                            slot_tokens[req.slot] = tok
                            self.scheduler.record_token(req, tok,
                                                        self.ecfg.eos_id)
                            self.metrics["tokens_generated"] += 1
                            reg.inc("engine/tokens_generated")
                            reg.inc("engine/decoded_tokens")
                        record_dispatch(reg, stats)
                        self.metrics["decode_steps"] += 1
                        reg.inc("engine/decode_steps")
                    host_s = sp.duration_s
                    with obs.span("engine.wave_hooks"):
                        for hook in self.wave_hooks:
                            hook()
                    waves += 1
        finally:
            self._cache = cache
        wall = time.perf_counter() - t0
        self.metrics["wall_s"] += wall
        reg.set_gauge("engine/last_run_wall_s", wall)
        reg.set_gauge("engine/last_run_tokens_per_s",
                      (self.metrics["tokens_generated"] - tok0) / wall
                      if wall > 0 else 0.0)
        return self.scheduler.finished

    # -- paged KV layout ------------------------------------------------
    def _ensure_pool(self) -> PagedKVCache:
        """The persistent device block pool (paged analogue of
        ``_ensure_cache``)."""
        pool = self._pool
        if pool is not None:
            leaves = jax.tree.leaves(pool)
            if any(getattr(l, "is_deleted", lambda: False)() for l in leaves):
                pool = None
        if pool is None:
            pool = self.model.init_paged_cache(self._block_pool.num_blocks,
                                               self.ecfg.block_size,
                                               self.ecfg.cache_dtype)
        self.registry.set_gauge("engine/decode_cache_bytes", pool.nbytes)
        self.registry.set_gauge(
            "engine/decode_cache_bytes_copied",
            0 if self.ecfg.donate_cache else pool.nbytes)
        return pool

    def _corpus_fingerprint(self, corpus_id: Optional[str]) -> Optional[str]:
        """Content fingerprint of a registered corpus: requests bound to
        *different* store ids with identical corpus tokens share one
        prefix-cache namespace (their prefills are bit-identical — the
        unique KV depends only on corpus tokens + prompt, not the id)."""
        if corpus_id is None:
            return None
        fp = self._corpus_fp.get(corpus_id)
        if fp is None:
            toks = self._corpus_tokens[corpus_id]
            fp = hashlib.blake2b(np.ascontiguousarray(toks).tobytes(),
                                 digest_size=16).hexdigest()
            self._corpus_fp[corpus_id] = fp
        return fp

    def _prefix_key(self, req: Request):
        return (self._corpus_fingerprint(req.corpus_id), tuple(req.prompt))

    def _bytes_per_block(self) -> float:
        return self.cfg.kv_bytes_per_token * self.ecfg.block_size

    def _offload_entry(self, pool: PagedKVCache, key, entry) -> None:
        """Copy an evicted prefix entry's pages to the host tier — only
        when every page is cold (held solely by the prefix cache; pages a
        live slot still shares stay device-resident and re-park later)."""
        if not self.ecfg.host_pool_blocks:
            return
        bp = self._block_pool
        blocks = entry["blocks"]
        if any(bp.refcount(b) != 1 for b in blocks):
            return
        reg = self.registry
        t0 = time.perf_counter()
        k, v = extract_blocks(pool, blocks)
        gens = [(b, bp.generation(b)) for b in blocks]
        evicted = self._host_pool.offload(key, k, v, entry["first"], gens)
        reg.observe("kvcache/swap_out_latency_s",
                    time.perf_counter() - t0, obs.LATENCY_EDGES_S)
        nbytes = k.nbytes + v.nbytes
        reg.inc("kvcache/offload_bytes", nbytes)
        reg.observe("kvcache/swap_bytes", nbytes, obs.BYTES_EDGES)
        reg.inc("kvcache/offloads")
        if evicted:
            reg.inc("kvcache/host_pool_evictions", len(evicted))
        reg.set_gauge("kvcache/host_pool_blocks_used",
                      self._host_pool.used_blocks)

    def _evict_prefix_entries(self, pool: PagedKVCache,
                              need_blocks: int) -> Tuple[int, list]:
        """Evict LRU prefix-cache entries until ``need_blocks`` pages were
        actually released (or the cache is empty), offloading each cold
        entry's pages to the host tier first; returns (#released, evicted
        keys in eviction order)."""
        reg = self.registry
        released = 0
        evicted_keys = []
        while self._prefix_cache and released < need_blocks:
            key, entry = self._prefix_cache.popitem(last=False)
            self._offload_entry(pool, key, entry)
            released += self._block_pool.free(entry["blocks"])
            evicted_keys.append(key)
            reg.inc("kvcache/prefix_evictions")
        if released:
            reg.inc("kvcache/blocks_evicted", released)
        return released, evicted_keys

    def _cold_page_bytes(self) -> float:
        """Budget charge of pages held *only* by the prefix cache — what
        the scheduler's offload admission path can reclaim."""
        bp = self._block_pool
        cold = sum(1 for e in self._prefix_cache.values()
                   for b in e["blocks"] if bp.refcount(b) == 1)
        return cold * self._bytes_per_block()

    def _offload_cold_pages(self, need_bytes: float) -> float:
        """Scheduler callback (offload-vs-defer): move at least
        ``need_bytes`` of cold prefix pages to the host tier (or drop
        them when the tier is off) so a new request can be admitted.
        Returns the bytes actually freed."""
        pool = self._cur_pool
        if pool is None or not self._prefix_cache:
            return 0.0
        bpb = self._bytes_per_block()
        need_blocks = int(-(-need_bytes // bpb))
        released, _ = self._evict_prefix_entries(pool, need_blocks)
        return released * bpb

    def _alloc_blocks(self, pool: PagedKVCache, n: int,
                      reserve: int = 0) -> Tuple[PagedKVCache, List[int]]:
        """Allocate ``n`` pages, evicting cold prefix entries (offloading
        them to the host tier) and (in auto-sized mode) growing the device
        pool when the free list is short. ``reserve`` pages beyond ``n``
        size the growth so a request's decode appends don't retrigger it."""
        bp = self._block_pool
        want = n + reserve
        if bp.available < want:
            self._evict_prefix_entries(pool, want - bp.available)
        if bp.available < want and self.ecfg.num_blocks is None:
            q = self._pool_quantum
            shortfall = want - bp.available
            new_cap = bp.num_blocks + -(-shortfall // q) * q
            pool = grow_paged_kv_cache(pool, new_cap)
            bp.grow(new_cap)
            self.registry.inc("kvcache/pool_growths")
        return pool, bp.alloc(n)     # PoolExhausted if still short of n

    def _record_block_gauges(self) -> None:
        bp = self._block_pool
        reg = self.registry
        reg.set_gauge("kvcache/blocks_in_use", bp.in_use)
        reg.set_gauge("kvcache/blocks_free", bp.available)
        reg.set_gauge("kvcache/block_capacity", bp.capacity)
        reg.set_gauge("kvcache/block_utilization",
                      bp.in_use / max(bp.capacity, 1))

    def _prefill_slot_paged(self, pool: PagedKVCache, req: Request
                            ) -> Tuple[PagedKVCache, int]:
        """Admit one request into the paged pool: prefix-cache hit remaps
        shared pages; in-bucket prompts reuse the bucketed jit'd prefill
        (bit-identical to slotted) + a block scatter; prompts past max_seq
        go through chunked prefill."""
        reg = self.registry
        bs = self.ecfg.block_size
        true_len = len(req.prompt)
        total_blocks = blocks_for(true_len + req.max_new_tokens, bs)
        if total_blocks > self._tables.blocks_per_slot:
            self._tables.grow(total_blocks)   # wider gather view; recompile
        store = self._get_store(req.corpus_id)
        start = store.total_tokens if store is not None else 0
        use_store = store is not None and self.cfg.moska.enabled

        key = self._prefix_key(req)
        entry = (self._prefix_cache.get(key)
                 if self.ecfg.share_prefix_blocks else None)
        if entry is not None:
            self._prefix_cache.move_to_end(key)
            self._block_pool.incref(entry["blocks"])
            self._tables.assign(req.slot, entry["blocks"], true_len, start)
            reg.inc("kvcache/prefix_hits")
            reg.inc("kvcache/blocks_shared", len(entry["blocks"]))
            return pool, int(entry["first"])

        nb = blocks_for(true_len, bs)
        if self.ecfg.share_prefix_blocks and key in self._host_pool:
            # host-tier hit: swap the offloaded pages back into freshly
            # allocated device blocks — bit-exact, no prefill at all.
            # Fetch before alloc: the alloc may evict other prefix
            # entries into the host pool, which must not push this one out
            host_entry = self._host_pool.fetch(key)
            tr = (self._prefetch.take(key)
                  if self._prefetch is not None else None)
            pool, ids = self._alloc_blocks(pool, nb,
                                           reserve=total_blocks - nb)
            t0 = time.perf_counter()
            if tr is not None and tr["gens"] == host_entry["gens"]:
                # prefetched during an earlier wave: the pages are already
                # device-resident (or mid-flight — the insert sequences
                # after the async copy, a bounded wait, never a re-issue)
                src_k, src_v = tr["k"], tr["v"]
                reg.inc("kvcache/prefetch_hits")
            else:
                if tr is not None:
                    # the tier churned since issue: this transfer names a
                    # dead page lifetime — discard it and swap in the
                    # current entry (bit-identical values either way; the
                    # generation tags are the identity proof)
                    reg.inc("kvcache/prefetch_wasted")
                src_k, src_v = host_entry["k"], host_entry["v"]
            pool = self._insert_blocks(pool, jnp.asarray(ids, jnp.int32),
                                       src_k, src_v)
            reg.observe("kvcache/swap_in_latency_s",
                        time.perf_counter() - t0, obs.LATENCY_EDGES_S)
            nbytes = host_entry["k"].nbytes + host_entry["v"].nbytes
            reg.inc("kvcache/swap_in_bytes", nbytes)
            reg.observe("kvcache/swap_bytes", nbytes, obs.BYTES_EDGES)
            reg.inc("kvcache/swap_in_hits")
            reg.set_gauge("kvcache/host_pool_blocks_used",
                          self._host_pool.used_blocks)
            self._tables.assign(req.slot, ids, true_len, start)
            # the slot owns the swapped-in pages exactly as if it had
            # rebuilt them (same block pressure, no CoW on the tail);
            # they re-park in the prefix cache at release
            return pool, int(host_entry["first"])
        if self.ecfg.host_pool_blocks and self.ecfg.share_prefix_blocks:
            # cold miss in both tiers: deterministic rebuild-from-tokens
            reg.inc("kvcache/host_pool_misses")
        pool, ids = self._alloc_blocks(pool, nb, reserve=total_blocks - nb)
        if true_len <= self.ecfg.max_seq:
            pad_len = bucket_for(self._buckets, true_len)
            padded = np.zeros((1, pad_len), np.int32)
            padded[0, :true_len] = req.prompt
            pkey = (pad_len, use_store,
                    tuple(store.k.shape) if use_store else None)
            if pkey not in self._prefill_keys:
                self._prefill_keys.add(pkey)
                reg.set_gauge("engine/prefill_compile_count",
                              len(self._prefill_keys))
            first, out = self._prefill(
                self.params, jnp.asarray(padded),
                jnp.asarray(true_len, jnp.int32),
                jnp.asarray(start, jnp.int32), store, use_store)
            slot_cache, stats = unstep(out)
            stats = [stats]
        else:
            first, slot_cache, stats = self._prefill_chunked_prompt(
                req, store, use_store, start)
        pool = self._write_blocks(pool, jnp.asarray(ids, jnp.int32),
                                  slot_cache.k, slot_cache.v,
                                  jnp.asarray(true_len, jnp.int32))
        self._tables.assign(req.slot, ids, true_len, start)
        first, stats = jax.device_get((first, stats))
        for st in stats:
            record_dispatch(self.registry, st)
        self.metrics["prefills"] += 1
        reg.inc("engine/prefills")
        reg.inc("engine/prefill_tokens", true_len)
        return pool, int(first)

    def _prefill_chunked_prompt(self, req: Request, store, use_store: bool,
                                start: int):
        """Long-prompt prefill in ``prefill_chunk``-token pieces against a
        growing scratch context (one compiled program per (chunk, context)
        shape pair, bounded regardless of prompt length). Returns (first
        token, context, each chunk's dispatch stats)."""
        C = self.ecfg.prefill_chunk
        true_len = len(req.prompt)
        v_tot = blocks_for(true_len, C) * C
        ctx = self.model.init_cache(1, v_tot, self.ecfg.cache_dtype)
        pkey = ("chunk", C, v_tot, use_store,
                tuple(store.k.shape) if use_store else None)
        if pkey not in self._prefill_keys:
            self._prefill_keys.add(pkey)
            self.registry.set_gauge("engine/prefill_compile_count",
                                    len(self._prefill_keys))
        first, stats = None, []
        for s0 in range(0, true_len, C):
            clen = min(C, true_len - s0)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :clen] = req.prompt[s0:s0 + clen]
            first, out = self._prefill_chunked(
                self.params, jnp.asarray(chunk), ctx,
                jnp.asarray(start, jnp.int32), jnp.asarray(clen, jnp.int32),
                store, use_store)
            ctx, st = unstep(out)
            stats.append(st)
            self.registry.inc("engine/prefill_chunks")
        self.registry.inc("engine/chunked_prefills")
        return first, ctx, stats

    def _prepare_wave_blocks(self, pool: PagedKVCache,
                             active: List[Request]) -> PagedKVCache:
        """Pre-wave page maintenance: every active slot is about to append
        one token at its current length — make sure the target page exists
        and is exclusively owned (copy-on-write for prefix-shared pages)."""
        tables = self._tables
        bp = self._block_pool
        reg = self.registry
        for req in active:
            slot = req.slot
            bi = int(tables.length[slot]) // self.ecfg.block_size
            spec = self._spec_pending.get(slot)
            if spec is not None and bi >= spec:
                # the speculatively appended page is now the write target:
                # it is fresh (refcount 1, never shared) so neither the
                # append nor the CoW branch below applies — exactly the
                # state the synchronous append would have produced
                del self._spec_pending[slot]
                continue
            if bi >= int(tables.n_blocks[slot]):
                if bi >= tables.blocks_per_slot:
                    tables.grow(bi + 1)
                pool, ids = self._alloc_blocks(pool, 1)
                tables.append_block(slot, ids[0])
                reg.inc("kvcache/blocks_appended")
            else:
                blk = int(tables.table[slot, bi])
                if bp.needs_copy(blk):
                    pool, ids = self._alloc_blocks(pool, 1)
                    pool = copy_block(pool, ids[0], blk)
                    tables.replace_block(slot, bi, ids[0])
                    bp.free([blk])
                    reg.inc("kvcache/cow_copies")
        return pool

    def _speculative_appends(self, active: List[Request]) -> None:
        """Decode-boundary page pre-allocation: any slot whose *next* token
        will land on a fresh page gets that page appended now, during the
        current wave, so the next ``_prepare_wave_blocks`` finds it already
        in the table (host-metadata work only — BlockPool free-list +
        numpy table mutation; the device pool is untouched, which matters
        because it is donated into the still-in-flight decode step).

        Deliberately conservative: never evicts, never grows the pool,
        never raises — a full free list simply defers to the synchronous
        append path, bit-identically. A wrong speculation (the request
        finishes on the boundary token) is reclaimed in
        ``_release_slot_paged``."""
        if not self.ecfg.spec_append:
            return
        tables = self._tables
        bp = self._block_pool
        bs = self.ecfg.block_size
        reg = self.registry
        for req in active:
            slot = req.slot
            if slot in self._spec_pending:
                continue
            # lengths were just tick()'d: the slot's NEXT append lands at
            # tables.length[slot]; speculate only when that position opens
            # a page the table doesn't have yet
            bi = int(tables.length[slot]) // bs
            if bi < int(tables.n_blocks[slot]) or \
                    bi >= tables.blocks_per_slot or bp.available < 1:
                continue
            ids = bp.alloc(1)
            tables.append_block(slot, ids[0])
            self._spec_pending[slot] = bi
            reg.inc("kvcache/spec_pages_alloc")
            reg.inc("kvcache/blocks_appended")

    def _issue_prefetches(self) -> None:
        """Prefetch host-tier entries the scheduler lookahead predicts will
        be admitted next: issue non-blocking host->device copies now so the
        swap-in at admission finds device-resident pages. Also sweeps
        transfers whose host entry churned since issue (counted as wasted).
        Host-metadata + async-dispatch work only — safe in the overlap
        window."""
        pf = self._prefetch
        if pf is None:
            return
        reg = self.registry
        stale = pf.sweep()
        if stale:
            reg.inc("kvcache/prefetch_wasted", stale)
        for req in self.scheduler.lookahead(pf.depth):
            key = self._prefix_key(req)
            if key in self._prefix_cache:
                continue    # device-resident: admission remaps, no copy
            if pf.issue(key):
                reg.inc("kvcache/prefetch_issued")

    def _wave_bookkeeping(self, active: List[Request]) -> None:
        """The paged wave's host-side work on the page tables: advance the
        lengths, pre-allocate next pages, issue prefetches, read gauges."""
        self._tables.tick()
        self._speculative_appends(active)
        self._issue_prefetches()
        self._record_block_gauges()

    def _release_slot_paged(self, req: Request, slot: int) -> None:
        """Free a finished request's pages; with prefix sharing on, its
        prompt pages (incl. the partial tail — later writers CoW it) are
        parked in the LRU prefix cache keyed by (corpus, prompt)."""
        tables = self._tables
        if self._spec_pending.pop(slot, None) is not None:
            # wrong speculation: the request finished before writing its
            # pre-allocated boundary page; tables.clear below frees it
            # with the rest of the slot (it is never in prefix_blocks —
            # it sits beyond the written region)
            self.registry.inc("kvcache/spec_pages_reclaimed")
        key = self._prefix_key(req)
        if self.ecfg.share_prefix_blocks and req.generated and \
                key not in self._prefix_cache:
            pblocks = tables.prefix_blocks(slot, len(req.prompt))
            if pblocks:
                self._block_pool.incref(pblocks)
                self._prefix_cache[key] = {"blocks": pblocks,
                                           "first": req.generated[0]}
        self._block_pool.free(tables.clear(slot))
        self.registry.inc("kvcache/slots_released")

    def _run_paged(self, max_waves: int) -> List[Request]:
        B = self.ecfg.max_slots
        reg = self.registry
        t0 = time.perf_counter()
        tok0 = self.metrics["tokens_generated"]
        pool = self._ensure_pool()
        self._pool = None       # run() holds the only live reference
        slot_tokens = np.zeros((B,), np.int32)

        waves = 0
        host_s = 0.0        # engine host time since the last token vector
        try:
            with obs.span("engine.run"):
                while not self.scheduler.idle and waves < max_waves:
                    with obs.span("engine.schedule") as sp:
                        # the offload admission path may extract pages from
                        # the live pool during schedule() (read-only)
                        self._cur_pool = pool
                        admitted = self.scheduler.schedule()
                    host_s += sp.duration_s
                    for req in admitted:
                        with obs.span("engine.prefill", uid=req.uid):
                            tp = time.perf_counter()
                            slot = req.slot
                            pool, first = self._prefill_slot_paged(pool, req)
                            reg.observe("engine/prefill_latency_s",
                                        time.perf_counter() - tp,
                                        obs.LATENCY_EDGES_S)
                            slot_tokens[slot] = first
                            self.scheduler.record_token(req, int(first),
                                                        self.ecfg.eos_id)
                            if req.done:
                                self._release_slot_paged(req, slot)
                            self.metrics["tokens_generated"] += 1
                            reg.inc("engine/tokens_generated")
                    active = self.scheduler.active()
                    if not active:
                        if not admitted and not self.scheduler.idle:
                            head = self.scheduler.queue[0]
                            raise RuntimeError(
                                "serving livelock: "
                                f"{len(self.scheduler.queue)} queued "
                                "request(s) but none admissible — "
                                f"mem_budget_bytes="
                                f"{self.ecfg.mem_budget_bytes:.3g} is below "
                                "the head request's block cost "
                                f"({self.scheduler._request_cost(head):.3g} "
                                "bytes + resident shared stores)")
                        waves += 1
                        continue
                    with obs.span("engine.decode_dispatch") as sp:
                        store = self._active_store()
                        use_store = (store is not None
                                     and self.cfg.moska.enabled)
                        pool = self._prepare_wave_blocks(pool, active)
                        self._note_hbm(pool.nbytes)
                        reg.observe("engine/wave_batch_density",
                                    len(active) / B, obs.FRACTION_EDGES)
                        reg.observe("engine/wave_active_slots", len(active),
                                    obs.COUNT_EDGES)
                        tbl, lens, offs = self._tables.device_args()
                        td = time.perf_counter()
                        nxt, out = self._decode_paged(
                            self.params, jnp.asarray(slot_tokens), pool,
                            jnp.asarray(tbl), jnp.asarray(lens),
                            jnp.asarray(offs), store, use_store)
                        pool, stats = unstep(out)
                    reg.observe("engine/host_step_s", host_s + sp.duration_s,
                                obs.LATENCY_EDGES_S)
                    # jax returns from _decode_paged as soon as the step is
                    # *dispatched*; the device_get is the block. The wave's
                    # host-side bookkeeping (table tick, speculative page
                    # appends, prefetch issue, gauge reads) is identical
                    # either way — overlap mode runs it inside the dispatch
                    # window so the block absorbs it, sync mode runs it
                    # after, in a span of its own that counts as host step
                    # time. None of it may touch the device pool: that
                    # buffer is donated into the in-flight step.
                    with obs.span("engine.decode_wait"):
                        if self.ecfg.overlap_waves:
                            th = time.perf_counter()
                            self._wave_bookkeeping(active)
                            reg.observe("engine/overlap_saved_s",
                                        time.perf_counter() - th,
                                        obs.LATENCY_EDGES_S)
                        ts = time.perf_counter()
                        # device sync (residual wait with overlap, else
                        # the full wait)
                        nxt, stats = jax.device_get((nxt, stats))
                        stall = time.perf_counter() - ts
                    host_s = 0.0
                    if not self.ecfg.overlap_waves:
                        with obs.span("engine.wave_bookkeeping") as sp:
                            self._wave_bookkeeping(active)
                        host_s = sp.duration_s
                    reg.observe("engine/decode_step_latency_s",
                                time.perf_counter() - td,
                                obs.LATENCY_EDGES_S)
                    reg.observe("engine/decode_stall_s", stall,
                                obs.LATENCY_EDGES_S)
                    with obs.span("engine.record") as sp:
                        for req in list(active):
                            tok = int(nxt[req.slot])
                            slot = req.slot
                            slot_tokens[slot] = tok
                            self.scheduler.record_token(req, tok,
                                                        self.ecfg.eos_id)
                            if req.done:
                                self._release_slot_paged(req, slot)
                            self.metrics["tokens_generated"] += 1
                            reg.inc("engine/tokens_generated")
                            reg.inc("engine/decoded_tokens")
                        record_dispatch(reg, stats)
                        self.metrics["decode_steps"] += 1
                        reg.inc("engine/decode_steps")
                    host_s += sp.duration_s
                    with obs.span("engine.wave_hooks"):
                        for hook in self.wave_hooks:
                            hook()
                    waves += 1
        finally:
            self._pool = pool
            self._cur_pool = None
        self._record_block_gauges()
        wall = time.perf_counter() - t0
        self.metrics["wall_s"] += wall
        reg.set_gauge("engine/last_run_wall_s", wall)
        reg.set_gauge("engine/last_run_tokens_per_s",
                      (self.metrics["tokens_generated"] - tok0) / wall
                      if wall > 0 else 0.0)
        return self.scheduler.finished

    # ------------------------------------------------------------------
    def _prefill_slot(self, cache, req: Request):
        """Prefill one slot: bucket-padded jit'd prefill + in-place per-slot
        write into the (donated) batch cache."""
        store = self._get_store(req.corpus_id)
        if not isinstance(cache, KVCache):
            # non-KVCache families (ssm/hybrid/encdec states): legacy
            # full-merge path, exact lengths
            return self._prefill_slot_fallback(cache, req, store)
        true_len = len(req.prompt)
        pad_len = bucket_for(self._buckets, true_len)
        padded = np.zeros((1, pad_len), np.int32)
        padded[0, :true_len] = req.prompt
        start = store.total_tokens if store is not None else 0
        use_store = store is not None and self.cfg.moska.enabled
        key = (pad_len, use_store,
               tuple(store.k.shape) if use_store else None)
        if key not in self._prefill_keys:
            self._prefill_keys.add(key)
            self.registry.set_gauge("engine/prefill_compile_count",
                                    len(self._prefill_keys))
        first, out = self._prefill(
            self.params, jnp.asarray(padded),
            jnp.asarray(true_len, jnp.int32), jnp.asarray(start, jnp.int32),
            store, use_store)
        slot_cache, stats = unstep(out)
        cache = self._write_slot(cache, slot_cache,
                                 jnp.asarray(req.slot, jnp.int32),
                                 jnp.asarray(true_len, jnp.int32))
        first, stats = jax.device_get((first, stats))
        record_dispatch(self.registry, stats)
        self.metrics["prefills"] += 1
        self.registry.inc("engine/prefills")
        self.registry.inc("engine/prefill_tokens", true_len)
        return cache, int(first)

    def _prefill_slot_fallback(self, cache, req: Request, store):
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        slot_cache = self.model.init_cache(1, self.ecfg.max_seq,
                                           self.ecfg.cache_dtype)
        start = store.total_tokens if store is not None else 0
        logits, slot_cache = self.model.prefill(
            self.params, toks, slot_cache, store=store, start_pos=start)
        self.metrics["prefills"] += 1
        self.registry.inc("engine/prefills")
        first = int(np.argmax(np.asarray(logits)[0]))
        cache = self._write_slot_pytree(cache, slot_cache,
                                        jnp.asarray(req.slot, jnp.int32))
        return cache, first


def record_dispatch(reg: obs.MetricsRegistry, stats) -> None:
    """File one program call's per-layer dispatch stats (host copies,
    fetched with the call's tokens) under the ``moska/*`` names: one
    utilization observation per layer, the routes dispatched and dropped,
    and the per-layer views. ``stats`` None (no store) records nothing."""
    if stats is None:
        return
    for i, (fill, dropped) in enumerate(zip(stats.fill.tolist(),
                                            stats.dropped.tolist())):
        reg.observe("moska/dispatch_capacity_utilization", fill,
                    obs.FRACTION_EDGES)
        reg.observe(f"moska/dispatch_capacity_utilization_by_layer/L{i}",
                    fill, obs.FRACTION_EDGES)
        reg.inc(f"moska/dropped_queries_by_layer/L{i}", dropped)
    reg.inc("moska/dispatched_queries", int(stats.dispatched.sum()))
    reg.inc("moska/dropped_queries", int(stats.dropped.sum()))


def _pytree_nbytes(tree) -> int:
    return sum(getattr(l, "nbytes", 0) for l in jax.tree.leaves(tree))


def _merge_slot_cache(cache, slot_cache, slot: int):
    """Copy a 1-batch cache pytree into batch slot ``slot`` (full-copy
    reference path; the jit'd hot paths use ``write_slot_prefix`` /
    ``_write_slot_pytree``; kept as the differential-test oracle)."""
    def merge(dst, src):
        if dst.ndim == 1:          # (B,) lengths / offsets
            return dst.at[slot].set(src[0])
        # layer-stacked arrays: (L, B, ...) vs (L, 1, ...)
        if dst.ndim >= 2 and src.shape[0] == dst.shape[0] and \
                src.shape[1] == 1:
            if src.shape[2] <= dst.shape[2]:
                return dst.at[:, slot, :src.shape[2]].set(src[:, 0])
        raise ValueError(f"unmergeable cache leaf {dst.shape} <- {src.shape}")

    return jax.tree.map(merge, cache, slot_cache)
