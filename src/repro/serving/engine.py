"""A small batched serving engine — the node's Model Manager backend.

Real (not simulated) JAX inference with **slot-based continuous batching**
(DESIGN.md §6.1): the engine keeps a persistent decode cache with
``max_batch`` row slots, each resident sequence decoding at its own depth
(per-row cache lengths).  After every decode step finished sequences are
evicted and queued requests are prefilled into the freed slots — a short
request no longer holds the batch hostage for the longest request's budget.
Prompts are right-padded, which causal attention keeps inert, so a request's
greedy output is independent of what it happens to be batched with (wave
batching, ``continuous=False``, produces bit-identical greedy results in
more decode steps).

``Engine(paged=True)`` swaps the per-slot contiguous cache for a **paged KV
cache** (DESIGN.md §6.1, paged backend): a fixed pool of page-sized KV
blocks with a per-sequence block table, grown one page at a time during
decode.  Admission charges a request's *prompt* pages only (not
``prompt + max_new`` as the contiguous slot cache must reserve), finished
sequences return their pages to the pool, and when the pool exhausts
mid-decode the most recently admitted sequence is preempted — its pages
reclaimed, its request requeued at the head of the queue for a greedy-
deterministic restart.  Greedy outputs stay bit-identical to the slot and
wave paths while strictly more requests are resident on the same KV budget.

``Engine(paged=True, prefix_cache=True)`` turns the page pool into a
**cross-request prefix cache** (DESIGN.md §6.1-prefix): every full prompt
page is content-addressed by a page-aligned hash chain, pages carry holder
refcounts, and prefill skips any prefix whose chain is already resident —
the uncached suffix is computed in one multi-token verify forward against
the shared pages.  Divergence mid-page is a chain miss (copy-on-write at
page granularity: the diverging request gets fresh pages from its first
differing page).  Released cached pages go *cold* instead of free — still
content-addressable, evicted LRU-first only when the free list is empty —
so eviction happens strictly at refcount zero.  Greedy outputs stay
bit-identical to a cold prefill: cached pages hold exactly the KV the
cold forward would recompute, and the suffix forward attends to them
through the same block-table indirection.

``Engine(spec_draft=(draft_cfg, draft_params), spec_k=k)`` layers
**speculative decoding** (DESIGN.md §6.1-spec) on top of the paged backend:
a small same-tokenizer draft model proposes ``k`` tokens greedily, the
target verifies all of them in ONE batched multi-token forward
(``Family.paged_verify``), and the longest prefix of drafts matching the
target's own greedy choices is accepted — plus the target's correction
token, carried as next-step logits.  KV pages are claimed for accepted
tokens only (rejected drafts' writes sit beyond the valid length and are
overwritten).  Greedy outputs stay bit-identical to the non-speculative
paged engine: every emitted token is the argmax of the target's logits
over the same prefix, speculation only changes how many target forwards
that takes.

This is the backend used by the runnable examples and the end-to-end
decentralized serving driver (``repro.launch.serve``, via
``repro.serving.executor.EngineExecutor``); the large-scale scheduling
benchmarks use the simulated executor instead (see DESIGN.md §6.1).
"""

from __future__ import annotations

import functools
import zlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.models import registry
from repro.obs import WALL, get_registry, get_tracer, wall_now
from repro.models.config import ModelConfig
from repro.serving.sampling import sample
from repro.sim.executor import (paged_admit_ok, pages_for, prefix_hit_pages,
                                quantized_pages)
from repro.sim.servicemodel import (PREFIX_FINGERPRINT_K,
                                    PREFIX_HIT_EMA_BETA, SPEC_ALPHA0,
                                    SPEC_EMA_BETA, SPEC_K)


def _greedy_tokens(logits: "jax.Array", vocab_size: int) -> "jax.Array":
    """Greedy token at every position of ``logits`` (..., V), with padded
    vocab entries masked — the same masking + argmax as the temperature-0
    path of :func:`repro.serving.sampling.sample`, so speculative
    verification reproduces non-speculative greedy choices exactly."""
    lg = logits.astype(jnp.float32)
    if vocab_size < lg.shape[-1]:
        pad_mask = jnp.arange(lg.shape[-1]) >= vocab_size
        lg = jnp.where(pad_mask, -1e30, lg)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32)


@dataclass
class GenRequest:
    rid: str
    tokens: np.ndarray            # (S,) prompt token ids
    max_new: int = 32
    temperature: float = 0.0
    result: Optional[np.ndarray] = None
    # engine metrics (wall-clock)
    enqueued_at: float = 0.0
    started_at: float = 0.0       # admitted into a slot (prefill)
    first_token_at: float = 0.0   # first output token sampled
    finished_at: float = 0.0


@dataclass
class EngineStats:
    served: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    batches: int = 0              # prefill batches
    decode_steps: int = 0         # batched decode_step invocations
    prefill_wall_s: float = 0.0   # wall time inside prefill calls
    decode_wall_s: float = 0.0    # wall time inside decode_step calls
    # real prompt tokens the prefill forwards computed for (a warm prefill:
    # the uncached suffix); prefill_tokens counts the padded positions
    prefill_prompt_tokens: int = 0
    steps: int = 0                # slot/paged step() calls (engine.step)
    step_wall_s: float = 0.0      # wall time inside them
    peak_resident: int = 0        # max concurrently resident sequences
    preempted: int = 0            # paged: preempt-and-requeue events
    # paged decode: the pages holding each fed row's valid KV, summed over
    # steps, and the pages of the tables those steps passed (max_batch x
    # width): the share of the tables that the attention kernel reads
    decode_kv_pages: int = 0
    decode_table_pages: int = 0
    handoffs: int = 0             # disagg: KV handoffs extracted/accepted
    handoff_bytes: int = 0        # disagg: valid KV bytes handed off
    # speculative decoding (DESIGN.md §6.1-spec).  decode_tokens counts
    # EMITTED tokens and decode_wall_s the target-side verify walls, so
    # decode_tokens / decode_wall_s is the effective target decode
    # throughput; the draft's own cost is tracked in draft_wall_s.
    spec_steps: int = 0           # verify forwards (each checks spec_k drafts)
    spec_drafted: int = 0         # draft tokens proposed
    spec_accepted: int = 0        # draft tokens matching the target's greedy
    draft_wall_s: float = 0.0     # wall time inside draft prefill/decode jits
    verify_wall_s: float = 0.0    # wall time inside the verify jit


@dataclass
class KVHandoff:
    """A prefilled request leaving a disaggregated prefill engine
    (DESIGN.md §6.1-disagg): its populated KV pages, the tokens it has
    already sampled (the prefill side emits the first token), and the
    next-token logits the decode side resumes from.  ``k``/``v`` are
    page-granular copies — the prefill engine's physical pages are released
    the moment the handoff is extracted; the decode engine scatters them
    into its own pool under fresh page numbers (``Engine.accept_handoff``).
    """

    req: GenRequest
    out: List[int]                # tokens sampled on the prefill side (>= 1)
    length: int                   # valid KV tokens: prompt + len(out)
    k: "jax.Array"                # (L, n_pages, page, Hkv, dh)
    v: "jax.Array"
    logits: "jax.Array"           # (1, V) next-token logits
    page_size: int
    # prefix tokens the DECODE side already holds cached and pinned
    # (DESIGN.md §6.1-prefix): those pages are not gathered into k/v and
    # their bytes never cross the wire.  Always a page multiple.
    cached_tokens: int = 0

    @property
    def kv_bytes(self) -> int:
        """Bytes of *valid* KV crossing the wire — the sim's transfer cost
        model charges the same quantity (prompt-dominated: len(out) is 1
        unless the prefill side raced ahead).  Pages the decode side holds
        cached (``cached_tokens``) never travel, so neither end counts
        them."""
        n_layers, _, _, n_kv, dh = self.k.shape
        return (2 * n_layers * (self.length - self.cached_tokens)
                * n_kv * dh * self.k.dtype.itemsize)


class _Slot:
    """One resident sequence: its request, sampled tokens, cache depth."""

    __slots__ = ("req", "out")

    def __init__(self, req: GenRequest) -> None:
        self.req = req
        self.out: List[int] = []


def _one_device(tree, what: str) -> "jax.Device":
    """The single device holding every array of ``tree``."""
    devs = {d for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}
    if len(devs) != 1:
        raise ValueError(f"{what} must sit on exactly one device, found "
                         f"{sorted(str(d) for d in devs)}")
    return devs.pop()


class Engine:
    """Persistent-slot continuous batching with a jitted step per bucket.

    The engine runs on the device that holds ``params``: its KV pools or
    slot cache, block tables, lengths, carried logits, sampling key and
    every host input are placed there explicitly, so replicas whose params
    sit on different devices never share one (``devices()`` reports it).
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 bucket: int = 64, seed: int = 0,
                 capacity: Optional[int] = None,
                 continuous: bool = True,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 spec_draft: Optional[Tuple[ModelConfig, Dict]] = None,
                 spec_k: int = SPEC_K) -> None:
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.bucket = bucket
        self.continuous = continuous
        self.device = _one_device(params, "engine params")
        self.key = jax.device_put(jax.random.PRNGKey(seed), self.device)
        self.stats = EngineStats()
        # trace span identity (DESIGN.md §Observability): the owning
        # executor forwards the node id the Node binds onto it
        self.owner = ""
        fam = registry.get_family(cfg)
        # right-padding is only inert with a full cache: a sliding-window
        # ring keeps the last `window` positions of the PADDED sequence, so
        # trailing pads would evict real in-window KV — window configs stay
        # on the left-padded lock-step wave path
        self.slot_decode = fam.slot_decode and cfg.sliding_window is None
        # the jitted steps are named functions, so a profile's module line
        # reads ``jit_paged_decode_step`` rather than ``jit__lambda``
        if self.slot_decode:
            def prefill_step(p, b, cap, lp):
                return fam.prefill(p, cfg, b, q_chunk=256, kv_chunk=256,
                                   capacity=cap, last_positions=lp)
        else:
            # families without per-row cache depths fall back to left-padded
            # lock-step wave batching
            def prefill_step(p, b, cap):
                return fam.prefill(p, cfg, b, q_chunk=256, kv_chunk=256,
                                   capacity=cap)
        self._prefill = jax.jit(prefill_step, static_argnums=(2,))

        def decode_step(p, c, t):
            return fam.decode_step(p, cfg, c, t)
        self._decode = jax.jit(decode_step)
        self.eos_id = cfg.eos_id

        # persistent slot state
        self._queue: List[GenRequest] = []
        self._slots: List[Optional[_Slot]] = [None] * max_batch
        self._lengths = np.zeros(max_batch, np.int64)   # per-row cache depth
        self._cache: Optional[Dict] = None
        self._logits: Optional[jax.Array] = None
        self._capacity = int(capacity or 0)

        # paged-KV state (DESIGN.md §6.1, paged backend)
        self.paged = bool(paged)
        self.page_size = int(page_size)
        if self.paged:
            if not (self.slot_decode and fam.paged_decode is not None):
                raise ValueError(
                    "paged KV requires a paged-capable slot-decode family "
                    "(dense/vlm with full attention)")
            # the decode/verify caches are DONATED: with the pools carried
            # through the layer scan (dense.paged_decode_step), donation
            # makes the page scatter a true in-place update, so step cost
            # is independent of pool size (§Perf-kernels).  Never reuse a
            # cache array after passing it in — the engine always reads the
            # returned cache.
            def paged_decode_step(p, c, t):
                return fam.paged_decode(p, cfg, c, t)
            self._decode_paged = jax.jit(paged_decode_step,
                                         donate_argnums=(1,))
            self._scatter_pages = jax.jit(fam.prefill_to_pages,
                                          donate_argnums=(0,))
            # zero pools are created on the engine's device, never staged
            # through the default one (they fill most of a chip's HBM)
            self._init_pools = jax.jit(
                functools.partial(fam.init_paged_pools, cfg),
                static_argnums=(0, 1),
                out_shardings=SingleDeviceSharding(self.device))
            usable = (int(num_pages) if num_pages is not None
                      else max_batch * pages_for(2 * bucket, self.page_size))
            # int8 KV pages: the same HBM budget holds 2x the pages — the
            # shared sim/engine capacity rule (DESIGN.md §6.1-paged)
            usable = quantized_pages(usable, cfg.kv_quant)
            self._num_pages = usable + 1          # page 0 is scratch
            self._pools: Optional[Dict] = None    # lazy device alloc
            self._pool_names = (("k_pool", "v_pool", "k_scale_pool",
                                 "v_scale_pool") if cfg.kv_quant
                                else ("k_pool", "v_pool"))
            self._free_pages: List[int] = list(range(1, self._num_pages))
            self._row_pages: List[List[int]] = [[] for _ in range(max_batch)]
            self._maxp = max(1, pages_for(2 * bucket, self.page_size))
            self._block_tables = np.zeros((max_batch, self._maxp), np.int32)
            # device-resident block table + lengths (§Perf-kernels): the
            # decode cache passes both through, so steady-state decode skips
            # the per-step host->device upload; any host-side mutation
            # (admission, release, page claim) marks them dirty
            self._bt_dev: Optional[jax.Array] = None
            self._len_dev: Optional[jax.Array] = None
            self._tables_dirty = True
            # admission order, for LIFO preemption under pool pressure
            self._slot_seq = np.zeros(max_batch, np.int64)
            self._admit_seq = 0
            # cross-request prefix caching (DESIGN.md §6.1-prefix): pages
            # content-addressed by a page-aligned hash chain over the
            # prompt.  The maps exist (empty) for every paged engine so the
            # pool accounting below is uniform; lookups and registration
            # only happen with ``prefix_cache=True``.
            self._chain: Dict[int, int] = {}      # chain hash -> phys page
            self._page_hash: Dict[int, int] = {}  # phys page -> chain hash
            self._page_ref: Dict[int, int] = {}   # phys page -> holder count
            # cold cached pages: refcount 0 but content still addressable;
            # ordered oldest-touched first, evicted only when the free list
            # is empty (insertion at the MRU end in _drop_page)
            self._cold: "OrderedDict[int, None]" = OrderedDict()
            # depth-1 chain hashes by recency — the resident-prefix
            # fingerprint that load snapshots/digests advertise
            self._head_lru: "OrderedDict[int, None]" = OrderedDict()
            # rid -> pages claimed for an in-flight disagg handoff
            self._pinned: Dict[str, List[int]] = {}
            self.prefix_hit_rate = 0.0
            self.prefix_hit_tokens = 0
            self.prefix_lookup_tokens = 0

        # speculative decoding (DESIGN.md §6.1-spec)
        self.spec = spec_draft is not None
        self.spec_k = int(spec_k) if self.spec else 0
        if self.spec:
            if not self.paged:
                raise ValueError("speculative decoding requires paged=True "
                                 "(the verify step targets the page pools)")
            if fam.paged_verify is None:
                raise ValueError("family has no paged_verify capability")
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            draft_cfg, draft_params = spec_draft
            if _one_device(draft_params, "draft params") != self.device:
                raise ValueError("draft params must sit on the engine's "
                                 "device")
            dfam = registry.get_family(draft_cfg)
            if not (dfam.slot_decode and draft_cfg.sliding_window is None):
                raise ValueError("draft model must support slot decode "
                                 "with full attention")
            if (draft_cfg.vocab_size != cfg.vocab_size
                    or draft_cfg.eos_id != cfg.eos_id):
                raise ValueError("draft and target must share the tokenizer "
                                 "(vocab_size / eos_id)")
            self.spec_draft_cfg = draft_cfg
            self.spec_draft_params = draft_params
            self._verify = self._verify_jit(fam)

            def draft_prefill_step(p, b, cap, lp):
                return dfam.prefill(p, draft_cfg, b, q_chunk=256,
                                    kv_chunk=256, capacity=cap,
                                    last_positions=lp)

            def draft_decode_step(p, c, t):
                return dfam.decode_step(p, draft_cfg, c, t)
            self._draft_prefill = jax.jit(draft_prefill_step,
                                          static_argnums=(2,))
            self._draft_decode = jax.jit(draft_decode_step)
            # draft slot cache: contiguous per-row-depth KV, mirrored to the
            # target's slots (re-prefilled from scratch after preemption)
            self._draft_cache: Optional[Dict] = None
            self._draft_lengths = np.zeros(max_batch, np.int64)
            self._draft_capacity = 0
            # online per-token acceptance-rate EMA, seeded from the same sim
            # constant the SpecTokenBucketExecutor defaults to, so sim and
            # engine agree until real observations move it
            self.spec_alpha = SPEC_ALPHA0
            # accepted-length distribution: spec_accept_hist[a] counts
            # verify steps that accepted exactly a of spec_k drafts
            self.spec_accept_hist = [0] * (self.spec_k + 1)

        # cross-request prefix caching (DESIGN.md §6.1-prefix)
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache:
            if not self.paged:
                raise ValueError("prefix caching requires paged=True "
                                 "(it shares pool pages across requests)")
            if fam.paged_verify is None:
                raise ValueError(
                    "prefix caching needs a paged_verify-capable family: "
                    "cached-suffix prefill is a multi-token verify forward")
            if not self.spec:
                # warm prefill reuses the speculative verify kernel: only
                # the uncached suffix is computed, attending to the shared
                # prefix pages through the block-table indirection (the
                # spec engine already built this jit above)
                self._verify = self._verify_jit(fam)

    def _verify_jit(self, fam) -> Callable:
        """The multi-token paged verify forward (speculative verify and
        warm prefill), its cache donated like the paged decode's."""
        cfg = self.cfg

        def paged_verify_step(p, c, t):
            return fam.paged_verify(p, cfg, c, t)
        return jax.jit(paged_verify_step, donate_argnums=(1,))

    def _put(self, x, dtype=None) -> jax.Array:
        """A host value, committed to this engine's device."""
        return jax.device_put(np.asarray(x, dtype), self.device)

    def _sample(self, logits: jax.Array, temperature) -> jax.Array:
        """Next token per row from ``logits`` (B, 1, V), advancing the
        engine's key.  Eager sampling's own temporaries (the padded-vocab
        mask) are made on this engine's device too."""
        self.key, sk = jax.random.split(self.key)
        with jax.default_device(self.device):
            return sample(sk, logits, temperature=temperature,
                          vocab_size=self.cfg.vocab_size)

    def devices(self) -> set:
        """Every device holding one of this engine's arrays: params, KV
        pools or slot cache, device block tables and lengths, carried
        logits, and the draft model and its cache.  By construction this
        is ``{self.device}``."""
        arrays = [self.params, self.key, self._cache, self._logits]
        if self.paged:
            arrays += [self._pools, self._bt_dev, self._len_dev]
        if self.spec:
            arrays += [self.spec_draft_params, self._draft_cache]
        return {d for leaf in jax.tree_util.tree_leaves(arrays)
                for d in leaf.devices()}

    def _pad_bucket(self, n: int) -> int:
        b = self.bucket
        return max(b, (n + b - 1) // b * b)

    def _required(self, r: GenRequest) -> int:
        """Worst-case cache tokens a request may touch.  A speculative
        verify writes up to ``spec_k`` positions past the pending token, so
        the spec engine's worst case extends past pad(prompt)+pad(max_new)
        by the draft depth (rejected drafts' writes still need a mapped
        page, even though they never become valid tokens)."""
        extra = self.spec_k if self.spec else 0
        return (self._pad_bucket(len(r.tokens))
                + self._pad_bucket(r.max_new) + extra)

    def _draft_required(self, r: GenRequest) -> int:
        """Draft-cache capacity for ``r``: the page-rounded prefill width
        (the draft prefills the same right-padded prompt batch as the
        target) plus room to decode the pending token and ``spec_k``
        drafts at positions up to ``prompt + max_new - 2 + spec_k``."""
        plen = (-(-self._pad_bucket(len(r.tokens)) // self.page_size)
                * self.page_size)
        return plen + self._pad_bucket(r.max_new + self.spec_k)

    # ------------------------------------------------------------- interface
    def submit(self, r: GenRequest) -> None:
        if self.spec and r.temperature > 0.0:
            raise ValueError(
                "the speculative engine is greedy-only: draft acceptance "
                "compares argmax choices (temperature sampling would need "
                "rejection sampling, which breaks the bit-parity invariant)")
        r.enqueued_at = wall_now()
        self._queue.append(r)

    def requeue(self, r: GenRequest) -> None:
        """Put a preempted/rerouted request back at the head of the queue
        WITHOUT re-stamping ``enqueued_at`` — its queue wait keeps counting
        from the original submission, so ``queue_wait`` stays monotone
        across preemption round-trips (the disagg executor routes
        decode-side preemptions back through the prefill engine)."""
        self._queue.insert(0, r)

    def take_queued(self) -> List[GenRequest]:
        """Drain and return the queue (admission re-routing: the disagg
        executor uses this to pull decode-side preemptions back out, since
        handoffs never travel through the decode engine's own queue)."""
        q, self._queue = self._queue, []
        return q

    def has_work(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def queued(self) -> int:
        return len(self._queue)

    def load_snapshot(self) -> Dict[str, object]:
        """Occupancy counts for Executor.load() — the supported view of the
        slot/queue/page-pool bookkeeping (token counts are *remaining* work;
        this dict, not the private pool state, is the sanctioned external
        view — a grep-guard in tests/test_compat.py enforces it)."""
        active = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        snap = dict(
            active_streams=len(active),
            queued_streams=len(self._queue),
            queued_prompt_tokens=sum(len(r.tokens) for r in self._queue),
            queued_new_tokens=sum(r.max_new for r in self._queue),
            pending_decode_tokens=sum(s.req.max_new - len(s.out)
                                      for _, s in active),
            pages_used=0, pages_total=0, free_pages=0, page_size=0,
            cached_pages=0, prefix_hit_rate=0.0, resident_prefixes=())
        if self.paged:
            usable = self._num_pages - 1
            cold = len(self._cold)
            used = usable - len(self._free_pages) - cold
            snap.update(
                pages_used=used, pages_total=usable,
                # cold cached pages are evicted on demand, so admission
                # counts them as free (DESIGN.md §6.1-prefix)
                free_pages=len(self._free_pages) + cold,
                page_size=self.page_size,
                # paged KV charges pages actually held, not reservations
                kv_used=used * self.page_size,
                kv_budget=usable * self.page_size,
                cached_pages=cold,
                prefix_hit_rate=self.prefix_hit_rate,
                resident_prefixes=tuple(reversed(self._head_lru))
                [:PREFIX_FINGERPRINT_K])
        else:
            snap.update(
                kv_used=int(sum(self._lengths[i] + s.req.max_new - len(s.out)
                                for i, s in active)),
                kv_budget=self.max_batch * max(self._capacity, 1))
        return snap

    def serve(self, reqs: List[GenRequest]) -> List[GenRequest]:
        """Submit ``reqs`` and pump steps until the engine drains."""
        if not self.slot_decode:
            return self._serve_wave_legacy(reqs)
        for r in reqs:
            self.submit(r)
        while self.has_work():
            self.step()
        return reqs

    def generate_batch(self, reqs: List[GenRequest]) -> List[GenRequest]:
        """Serve up to max_batch requests together; returns them completed."""
        assert len(reqs) <= self.max_batch
        return self.serve(reqs)

    # ------------------------------------------------------------- admission
    def _admit(self) -> None:
        if self.paged:
            self._admit_paged()
            return
        if not self._queue:
            return
        resident = any(s is not None for s in self._slots)
        if not self.continuous and resident:
            return                     # wave batching: refill only when empty
        if resident and any(self._required(r) > self._capacity
                            for r in self._queue):
            # a queued request needs a bigger cache, which can only be
            # allocated while nothing is resident: stop backfilling so the
            # batch drains and the growth branch below runs (otherwise a
            # steady stream of small requests starves the big one forever)
            return
        if not resident:
            # grow the cache while nothing is resident (allocation is static
            # under jit, so capacity only changes between generations)
            needed = max(self._required(r)
                         for r in self._queue[:self.max_batch])
            if self._cache is None or needed > self._capacity:
                self._capacity = max(self._capacity, needed)
                self._cache = None
                self._logits = None
        free = [i for i, s in enumerate(self._slots) if s is None]
        take: List[Tuple[int, GenRequest]] = []
        rest: List[GenRequest] = []
        for r in self._queue:
            # skip requests the current cache can't hold; they are admitted
            # at the next idle point, when capacity can grow
            if free and self._required(r) <= self._capacity:
                take.append((free.pop(0), r))
            else:
                rest.append(r)
        self._queue = rest
        if take:
            self._prefill_into(take)

    def _prefill_into(self, take: List[Tuple[int, GenRequest]]) -> None:
        n = len(take)
        plen = self._pad_bucket(max(len(r.tokens) for _, r in take))
        toks = np.full((n, plen), self.eos_id, np.int32)
        last = np.zeros(n, np.int32)
        for j, (_, r) in enumerate(take):
            toks[j, : len(r.tokens)] = r.tokens      # right-pad (inert)
            last[j] = len(r.tokens) - 1
        real = sum(len(r.tokens) for _, r in take)
        with get_tracer().wall("engine.prefill", who=self.owner,
                               rows=n, tokens=plen * n,
                               prompt_tokens=real) as sp:
            logits, cache = self._prefill(self.params,
                                          {"tokens": self._put(toks)},
                                          self._capacity, self._put(last))
            logits.block_until_ready()
        self.stats.prefill_wall_s += sp.dt
        self.stats.prefill_tokens += plen * n
        self.stats.prefill_prompt_tokens += real
        self.stats.batches += 1
        kv = {k: v for k, v in cache.items() if k != "length"}
        rows = self._put([i for i, _ in take])
        if self._cache is None:
            self._cache = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(
                    (leaf.shape[0], self.max_batch) + leaf.shape[2:],
                    leaf.dtype, device=self.device), kv)
            self._logits = jnp.zeros((self.max_batch, 1, logits.shape[-1]),
                                     logits.dtype, device=self.device)
        self._cache = jax.tree_util.tree_map(
            lambda p, nw: p.at[:, rows].set(nw), self._cache, kv)
        self._logits = self._logits.at[rows].set(logits)
        now = wall_now()
        for i, r in take:
            r.started_at = now
            self._slots[i] = _Slot(r)
            self._lengths[i] = len(r.tokens)
        self.stats.peak_resident = max(self.stats.peak_resident,
                                       self.active_slots())

    # -------------------------------------------------------- paged admission
    def _pages(self, tokens: int) -> int:
        return pages_for(tokens, self.page_size)

    def _admit_paged(self) -> None:
        if not self._queue:
            return
        resident = any(s is not None for s in self._slots)
        if not self.continuous and resident:
            return                     # wave batching: refill only when empty
        usable = self._num_pages - 1
        if resident and any(self._pages(self._required(r)) > usable
                            or (self.spec and self._draft_required(r)
                                > self._draft_capacity)
                            for r in self._queue):
            # a queued request cannot fit the pool (or the draft cache) even
            # alone; stop backfilling so the batch drains and the growth
            # branch runs
            return
        if not resident:
            # grow the pool while nothing is resident, so any single admitted
            # request can always run to completion (its worst-case pages fit
            # the pool) — this is what makes LIFO preemption livelock-free.
            # Growth reallocates every page, so it also forgets the prefix
            # cache and is deferred while handoff pins hold page content.
            needed = max(self._pages(self._required(r))
                         for r in self._queue[:self.max_batch])
            if (self._pools is None or needed > usable) \
                    and not self._pinned:
                self._num_pages = max(self._num_pages, needed + 1)
                usable = self._num_pages - 1
                self._pools = None
                self._logits = None
                self._free_pages = list(range(1, self._num_pages))
                self._flush_prefix_cache()
            if self.spec:
                # the draft cache is allocation-static under jit too: grow
                # it at the same idle points as the pool
                dneeded = max(self._draft_required(r)
                              for r in self._queue[:self.max_batch])
                if self._draft_cache is None \
                        or dneeded > self._draft_capacity:
                    self._draft_capacity = max(self._draft_capacity, dneeded)
                    self._draft_cache = None
        free_slots = [i for i, s in enumerate(self._slots) if s is None]
        # cold cached pages are evictable on demand, so they count as free —
        # but a cold page a taken request will *share* stops being evictable
        # (it revives to refcount 1), so it costs headroom exactly once
        free_now = len(self._free_pages) + len(self._cold)
        cold_reserved: set = set()
        take: List[Tuple[int, GenRequest]] = []
        rest: List[GenRequest] = []
        taking = resident
        for r in self._queue:
            hit_pages = (self._prefix_lookup_pages(r.tokens)
                         if self.prefix_cache else [])
            cold_cost = sum(1 for pg in hit_pages
                            if pg in self._cold and pg not in cold_reserved)
            suffix_tokens = len(r.tokens) - len(hit_pages) * self.page_size
            need = self._pages(suffix_tokens)
            if (free_slots and need + cold_cost <= free_now
                    and self._pages(self._required(r)) <= usable
                    and (not self.spec
                         or self._draft_required(r) <= self._draft_capacity)
                    and paged_admit_ok(free_now - cold_cost, suffix_tokens,
                                       self.page_size, resident=taking)):
                take.append((free_slots.pop(0), r))
                free_now -= need + cold_cost
                cold_reserved.update(pg for pg in hit_pages
                                     if pg in self._cold)
                taking = True
            else:
                rest.append(r)
        self._queue = rest
        if take:
            self._grow_block_tables(max(self._pages(self._required(r))
                                        for _, r in take))
            self._prefill_paged(take)

    def _grow_block_tables(self, maxp: int) -> None:
        if maxp <= self._maxp:
            return
        wider = np.zeros((self.max_batch, maxp), np.int32)
        wider[:, : self._maxp] = self._block_tables
        self._block_tables = wider
        self._maxp = maxp
        self._tables_dirty = True

    def _table_width(self, lookahead: int = 1) -> int:
        """Logical-page width the decode block table needs this step: every
        resident row's allocated pages, plus one column PAST the page its
        next ``lookahead`` writes land in.  The extra column matters for
        riding-along rows whose prompt exactly fills their pages: their
        inert write targets the next (unallocated) logical page, and
        without the column the clamped table lookup would alias slot 0 of
        their own last real page.  Rounded up to a power of two (few jit
        shapes), capped at the full table."""
        need = 1
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            last_write = (int(self._lengths[i]) + lookahead - 1)
            need = max(need, len(self._row_pages[i]),
                       last_write // self.page_size + 1)
        w = 1
        while w < need:
            w *= 2
        return min(w, self._maxp)

    def _prefill_paged(self, take: List[Tuple[int, GenRequest]]) -> None:
        """Prefill admitted rows into pool pages.  Rows with no cached
        prefix take the cold path (right-padded contiguous prefill, then a
        page scatter; pad-tail pages alias the scratch page 0, which
        per-row lengths keep inert).  With prefix caching, rows whose
        prompt head is already chain-resident pin the shared pages and
        compute only the uncached suffix via one multi-token verify
        forward (DESIGN.md §6.1-prefix)."""
        ps = self.page_size
        # All acquires happen before any register: rows admitted in the same
        # batch never share each other's fresh pages.  Allowing it would let
        # a warm row attend into pages another row is still writing inside
        # the same verify forward — sharing is cross-batch only.
        shared: Dict[int, List[int]] = {}
        for i, r in take:
            shared[i] = (self._prefix_acquire(np.asarray(r.tokens, np.int32))
                         if self.prefix_cache else [])
        for i, r in take:
            hits = len(shared[i])
            fresh = [self._claim_page()
                     for _ in range(self._pages(len(r.tokens)) - hits)]
            if self.prefix_cache:
                self._prefix_register(np.asarray(r.tokens, np.int32),
                                      hits, fresh)
            pages = shared[i] + fresh
            self._row_pages[i] = pages
            self._block_tables[i, :] = 0
            self._block_tables[i, : len(pages)] = pages
            self._slots[i] = _Slot(r)
            self._lengths[i] = len(r.tokens)
            self._slot_seq[i] = self._admit_seq
            self._admit_seq += 1
        self._tables_dirty = True
        cold = [(i, r) for i, r in take if not shared[i]]
        warm = [(i, r) for i, r in take if shared[i]]
        if cold:
            self._prefill_cold(cold)
        if warm:
            self._prefill_warm(warm, {i: len(shared[i]) for i, _ in warm})
        now = wall_now()                # started_at matches the slot path:
        for _, r in take:               # stamped after prefill completes
            r.started_at = now
        self.stats.batches += 1
        self.stats.peak_resident = max(self.stats.peak_resident,
                                       self.active_slots())
        if self.prefix_cache:
            reg = get_registry()
            for i, r in take:
                cached = len(shared[i]) * ps
                p = max(1, len(r.tokens))
                self.prefix_lookup_tokens += p
                self.prefix_hit_tokens += cached
                self.prefix_hit_rate += PREFIX_HIT_EMA_BETA * (
                    cached / p - self.prefix_hit_rate)
                reg.counter("engine.prefix.lookup_tokens").inc(p)
                reg.counter("engine.prefix.hit_tokens").inc(cached)
        if self.spec:
            plen = self._pad_bucket(max(len(r.tokens) for _, r in take))
            plen = -(-plen // ps) * ps
            toks = np.full((len(take), plen), self.eos_id, np.int32)
            last = np.zeros(len(take), np.int32)
            for j, (_, r) in enumerate(take):
                toks[j, : len(r.tokens)] = r.tokens
                last[j] = len(r.tokens) - 1
            self._spec_prefill_draft(take, toks, last)

    def _prefill_cold(self, cold: List[Tuple[int, GenRequest]]) -> None:
        """Right-padded prompt prefill, then scatter the contiguous KV into
        the rows' already-allocated pool pages."""
        n = len(cold)
        plen = self._pad_bucket(max(len(r.tokens) for _, r in cold))
        plen = -(-plen // self.page_size) * self.page_size  # page multiple
        toks = np.full((n, plen), self.eos_id, np.int32)
        last = np.zeros(n, np.int32)
        phys = np.zeros((n, plen // self.page_size), np.int32)
        for j, (i, r) in enumerate(cold):
            toks[j, : len(r.tokens)] = r.tokens      # right-pad (inert)
            last[j] = len(r.tokens) - 1
            phys[j, : len(self._row_pages[i])] = self._row_pages[i]
        real = sum(len(r.tokens) for _, r in cold)
        with get_tracer().wall("engine.prefill", who=self.owner, path="cold",
                               rows=n, tokens=plen * n,
                               prompt_tokens=real) as sp:
            logits, cache = self._prefill(self.params,
                                          {"tokens": self._put(toks)},
                                          plen, self._put(last))
            logits.block_until_ready()
        self.stats.prefill_wall_s += sp.dt
        self.stats.prefill_tokens += plen * n
        self.stats.prefill_prompt_tokens += real
        kv = {k: v for k, v in cache.items() if k != "length"}
        if self._pools is None:
            self._pools = self._init_pools(self._num_pages, self.page_size)
            self._logits = jnp.zeros((self.max_batch, 1, logits.shape[-1]),
                                     logits.dtype, device=self.device)
        self._pools = self._scatter_pages(self._pools, kv, self._put(phys))
        rows = self._put([i for i, _ in cold])
        self._logits = self._logits.at[rows].set(logits)

    def _prefill_warm(self, warm: List[Tuple[int, GenRequest]],
                      hits: Dict[int, int]) -> None:
        """Cached-suffix prefill (DESIGN.md §6.1-prefix): warm rows enter
        with ``_lengths`` temporarily set to their cached token count, and
        ONE batched multi-token verify forward computes the uncached
        suffix attending to the shared prefix pages — same kernel, same
        rider semantics as a speculative verify: non-warm rows' inert
        writes land on the scratch page or beyond their valid length, and
        their carried logits are untouched."""
        ps = self.page_size
        assert self._pools is not None   # a chain hit implies prior prefills
        suf_lens = {i: len(r.tokens) - hits[i] * ps for i, r in warm}
        S = -(-max(suf_lens.values()) // ps) * ps    # page-rounded jit width
        toks = np.full((self.max_batch, S), self.eos_id, np.int32)
        for i, r in warm:
            toks[i, : suf_lens[i]] = np.asarray(r.tokens[hits[i] * ps:],
                                                np.int32)
            self._lengths[i] = hits[i] * ps  # valid tokens = cached prefix
        # every rider row (including cold rows prefilled this round) writes
        # at lengths + j for j < S; the table must be wide enough that
        # those lookups hit a zero entry -> scratch, never a real page
        need_w = max((int(self._lengths[i]) + S - 1) // ps + 1
                     for i, s in enumerate(self._slots) if s is not None)
        self._grow_block_tables(need_w)
        w = self._table_width(lookahead=S)
        cache = {**self._pools,
                 "block_tables": self._put(self._block_tables[:, :w]),
                 "lengths": self._put(self._lengths, jnp.int32)}
        real = sum(suf_lens.values())
        with get_tracer().wall("engine.prefill", who=self.owner, path="warm",
                               rows=len(warm), tokens=S * len(warm),
                               prompt_tokens=real,
                               cached_pages=sum(hits.values())) as sp:
            vlogits, cache = self._verify(self.params, cache,
                                          self._put(toks))
            vlogits.block_until_ready()
        self.stats.prefill_wall_s += sp.dt
        self.stats.prefill_tokens += S * len(warm)
        self.stats.prefill_prompt_tokens += real
        self._pools = {n: cache[n] for n in self._pool_names}
        self._tables_dirty = True
        rows = self._put([i for i, _ in warm])
        pos = self._put([suf_lens[i] - 1 for i, _ in warm])
        self._logits = self._logits.at[rows].set(vlogits[rows, pos][:, None])
        for i, r in warm:
            self._lengths[i] = len(r.tokens)

    # ------------------------------------------------- prefix cache internals
    # (DESIGN.md §6.1-prefix) — content-addressed pages with holder
    # refcounts; the chain, cold LRU, and free list partition the pool.

    def _chain_hashes(self, tokens: np.ndarray) -> List[int]:
        """Cumulative page-aligned content hashes over the prompt's full
        pages: ``h_i = crc32(page_i, h_{i-1})``.  A prefix match is a
        chain walk, so two prompts share pages exactly up to their first
        differing page — copy-on-write at page granularity (a mid-page
        divergence is a miss at that depth, never a partial-page share)."""
        arr = np.ascontiguousarray(np.asarray(tokens, np.int32))
        ps = self.page_size
        out: List[int] = []
        h = 0
        for i in range(len(arr) // ps):
            h = zlib.crc32(arr[i * ps:(i + 1) * ps].tobytes(), h)
            out.append(h)
        return out

    def _prefix_lookup_pages(self, tokens: np.ndarray) -> List[int]:
        """Dry chain walk: the cached pages a prompt would reuse, capped by
        the shared hit rule (no refcounts move — ``_prefix_acquire`` claims
        at prefill time)."""
        hashes = self._chain_hashes(np.asarray(tokens, np.int32))
        matched = 0
        for h in hashes:
            if h not in self._chain:
                break
            matched += 1
        hits = prefix_hit_pages(len(tokens), self.page_size,
                                matched * self.page_size)
        return [self._chain[h] for h in hashes[:hits]]

    def _prefix_acquire(self, tokens: np.ndarray) -> List[int]:
        """Claim the cached prefix pages for a row about to prefill: bump
        holder refcounts (reviving cold pages out of the eviction LRU) and
        return them in chain order, capped by the shared hit rule."""
        hashes = self._chain_hashes(tokens)
        matched = 0
        for h in hashes:
            if h not in self._chain:
                break
            matched += 1
        hits = prefix_hit_pages(len(tokens), self.page_size,
                                matched * self.page_size)
        pages: List[int] = []
        for h in hashes[:hits]:
            pg = self._chain[h]
            if pg in self._cold:
                del self._cold[pg]
            self._page_ref[pg] = self._page_ref.get(pg, 0) + 1
            pages.append(pg)
        if pages and hashes[0] in self._head_lru:
            self._head_lru.move_to_end(hashes[0])
        return pages

    def _prefix_register(self, tokens: np.ndarray, hits: int,
                         fresh: List[int]) -> None:
        """Enter a row's freshly computed FULL prompt pages into the
        content chain so later requests can share them.  Partial tail
        pages stay private (decode keeps writing into them), as does any
        page whose chain hash is already taken by another physical page
        (first writer wins; the duplicate stays an unshared holder)."""
        hashes = self._chain_hashes(tokens)
        if hits and hashes[0] in self._head_lru:
            self._head_lru.move_to_end(hashes[0])
        for j in range(hits, len(hashes)):
            h = hashes[j]
            pg = fresh[j - hits]
            if h in self._chain or pg in self._page_hash:
                continue
            self._chain[h] = pg
            self._page_hash[pg] = h
            if j == 0:
                self._head_lru[h] = None
                self._head_lru.move_to_end(h)

    def _claim_page(self) -> int:
        """One page for a row to hold: the free list first, then evict the
        LRU cold cached page (cold pages have refcount 0 by construction —
        warm pages are never eviction candidates)."""
        if self._free_pages:
            pg = self._free_pages.pop()
        else:
            pg, _ = self._cold.popitem(last=False)
            self._evict_entry(pg)
        if self.prefix_cache:
            self._page_ref[pg] = 1
        return pg

    def _evict_entry(self, pg: int) -> None:
        h = self._page_hash.pop(pg, None)
        if h is not None:
            self._chain.pop(h, None)
            self._head_lru.pop(h, None)

    def _drop_page(self, pg: int) -> None:
        """One holder lets go of a page.  Refcounted pages go *cold* at
        zero holders when chain-registered — still content-addressable,
        LRU-evictable — else back to the free list; unrefcounted pages
        (prefix cache off) free directly."""
        ref = self._page_ref.get(pg)
        if ref is None:
            self._free_pages.append(pg)
            return
        if ref > 1:
            self._page_ref[pg] = ref - 1
            return
        del self._page_ref[pg]
        if pg in self._page_hash:
            self._cold[pg] = None           # lands at the MRU end
        else:
            self._free_pages.append(pg)

    def _flush_prefix_cache(self) -> None:
        """Pool reallocation invalidates every page's content: forget the
        chain and the cold set (callers reset the free list)."""
        self._chain.clear()
        self._page_hash.clear()
        self._page_ref.clear()
        self._cold.clear()
        self._head_lru.clear()

    def debug_page_accounting(self) -> Dict[str, int]:
        """Reconcile the free list, cold cache, refcounts, and row/pin
        holdings (the §6.1-prefix conservation invariant, exercised by the
        churn tests): every usable page is exactly one of free, cold, or
        held; shared pages are counted once; per-page refcounts equal the
        number of holders."""
        assert self.paged
        usable = self._num_pages - 1
        free = set(self._free_pages)
        cold = set(self._cold)
        held: Dict[int, int] = {}
        for pages in self._row_pages:
            for pg in pages:
                held[pg] = held.get(pg, 0) + 1
        for pages in self._pinned.values():
            for pg in pages:
                held[pg] = held.get(pg, 0) + 1
        assert len(free) == len(self._free_pages), "free list has duplicates"
        assert not free & cold, "page both free and cold-cached"
        assert not free & set(held), "page both free and row-held"
        assert not cold & set(held), "page both cold and row-held"
        for pg, n in held.items():
            ref = self._page_ref.get(pg)
            if ref is not None:
                assert ref == n, f"page {pg}: refcount {ref} != holders {n}"
            else:
                assert n == 1, f"untracked page {pg} shared by {n} holders"
        every = free | cold | set(held)
        assert every <= set(range(1, usable + 1)), "page id out of range"
        assert len(free) + len(cold) + len(held) == usable, (
            f"page leak/double-free: {len(free)} free + {len(cold)} cold "
            f"+ {len(held)} held != {usable} usable")
        return {"free": len(free), "cold": len(cold), "held": len(held)}

    def prefix_pin(self, req: GenRequest) -> int:
        """Decode-side cache consultation for a disagg handoff (DESIGN.md
        §6.1-prefix): walk the chain for ``req``'s prompt, claim the
        matched pages NOW (so they cannot be evicted while the handoff is
        on the wire), remember them under the request id, and return the
        cached token count — the prefill side then neither gathers nor
        byte-counts those pages.  Returns 0 when caching is off, the pool
        is unallocated, the request is already pinned, or it would force a
        pool growth (growth reallocates every page, which would strand the
        pin)."""
        if (not self.prefix_cache or self._pools is None
                or req.rid in self._pinned
                or self._pages(self._required(req)) > self._num_pages - 1):
            return 0
        pages = self._prefix_acquire(np.asarray(req.tokens, np.int32))
        p = max(1, len(req.tokens))
        cached = len(pages) * self.page_size
        self.prefix_lookup_tokens += p
        self.prefix_hit_tokens += cached
        reg = get_registry()
        reg.counter("engine.prefix.lookup_tokens").inc(p)
        reg.counter("engine.prefix.hit_tokens").inc(cached)
        self.prefix_hit_rate += PREFIX_HIT_EMA_BETA * (
            cached / p - self.prefix_hit_rate)
        if not pages:
            return 0
        self._pinned[req.rid] = pages
        return cached

    def _spec_prefill_draft(self, take: List[Tuple[int, GenRequest]],
                            toks: np.ndarray, last: np.ndarray) -> None:
        """Run the draft model's prefill over the same right-padded prompts
        and install its contiguous KV rows next to the target's slots
        (DESIGN.md §6.1-spec).  The draft's prompt logits are discarded:
        drafting always starts by feeding the pending token."""
        with get_tracer().wall("engine.spec_draft", who=self.owner,
                               path="prefill", rows=len(take)) as sp:
            dlogits, dcache = self._draft_prefill(
                self.spec_draft_params, {"tokens": self._put(toks)},
                self._draft_capacity, self._put(last))
            dlogits.block_until_ready()
        self.stats.draft_wall_s += sp.dt
        dkv = {k: v for k, v in dcache.items() if k != "length"}
        if self._draft_cache is None:
            self._draft_cache = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(
                    (leaf.shape[0], self.max_batch) + leaf.shape[2:],
                    leaf.dtype, device=self.device), dkv)
        rows = self._put([i for i, _ in take])
        self._draft_cache = jax.tree_util.tree_map(
            lambda p, nw: p.at[:, rows].set(nw), self._draft_cache, dkv)
        for i, r in take:
            self._draft_lengths[i] = len(r.tokens)

    # ----------------------------------------------------- page pool dynamics
    def _release_pages(self, i: int) -> None:
        for pg in self._row_pages[i]:
            self._drop_page(pg)
        self._row_pages[i] = []
        self._block_tables[i, :] = 0
        self._tables_dirty = True

    def _preempt(self, i: int) -> None:
        """Reclaim row ``i``'s pages and requeue its request at the head of
        the queue (vLLM-style recompute preemption: generated tokens are
        discarded; the greedy restart reproduces them bit-identically).

        The admission clocks are reset along with the discarded tokens:
        ``started_at``/``first_token_at`` belong to the aborted attempt, so
        leaving them set would let a mid-flight reader (metrics scrape, the
        disagg executor re-routing the request) report a TTFT for tokens
        the user never kept.  The restart re-stamps both, which also keeps
        ``enqueued_at <= started_at <= first_token_at <= finished_at``
        monotone on the completion record."""
        r = self._slots[i].req
        r.result = None
        r.started_at = 0.0
        r.first_token_at = 0.0
        self._release_pages(i)
        self._slots[i] = None
        self._lengths[i] = 0
        if self.spec:
            # the draft row is re-prefilled from scratch on re-admission
            self._draft_lengths[i] = 0
        self._queue.insert(0, r)
        self.stats.preempted += 1
        get_registry().counter("engine.preempted").inc()
        tr = get_tracer()
        if tr.enabled:
            tr.event("executor.preempt", r.rid, self.owner, wall_now(),
                     clock=WALL, row=i)

    def _ensure_decode_pages(self, survivors: List[int],
                             lookahead: int = 1) -> List[int]:
        """Allocate pages covering the next ``lookahead`` write positions
        for every surviving row (1 for plain decode; ``spec_k + 1`` for a
        speculative verify, which writes the pending token plus k drafts).
        Under pool pressure the most recently admitted resident is
        preempted until a page frees; oldest rows are served first, so the
        oldest admission always makes progress and the preemption loop
        terminates."""
        for i in sorted(survivors, key=lambda i: self._slot_seq[i]):
            while (self._slots[i] is not None
                   and (self._lengths[i] + lookahead - 1) // self.page_size
                   >= len(self._row_pages[i])):
                if self._free_pages or self._cold:
                    pg = self._claim_page()
                    self._row_pages[i].append(pg)
                    idx = len(self._row_pages[i]) - 1
                    self._grow_block_tables(idx + 1)
                    self._block_tables[i, idx] = pg
                    self._tables_dirty = True
                else:
                    victims = [j for j, s in enumerate(self._slots)
                               if s is not None]
                    self._preempt(max(victims, key=lambda j:
                                      self._slot_seq[j]))
        return [i for i in survivors if self._slots[i] is not None]

    # ------------------------------------------- disaggregated KV handoff
    # (DESIGN.md §6.1-disagg) — both ends live here because the page pool,
    # block tables, and free list are private to the engine (grep-guarded).

    def extract_handoffs(self, cached_tokens_fn: Optional[
            Callable[[GenRequest], int]] = None) -> List[KVHandoff]:
        """Disagg prefill side: pop every resident row that has sampled at
        least one token as a ``KVHandoff`` and release its local pages.

        Driven after each ``step()`` of a prefill-role engine: a freshly
        admitted row samples its first token and decodes it (writing its KV)
        within that same step, so no row ever survives two steps here — the
        prefill engine's pool only ever holds prompts mid-prefill.  The
        gathered ``k``/``v`` are copies, which is what the simulated
        transfer cost model charges for.

        ``cached_tokens_fn`` is the decode side's ``prefix_pin`` (DESIGN.md
        §6.1-prefix): it returns how many prompt tokens the decode engine
        already holds cached (a page multiple, pinned against eviction);
        those leading pages are neither gathered nor counted in
        ``handoff_bytes`` on either end.
        """
        assert self.paged, "KV handoff requires the paged backend"
        assert not self.spec, "KV handoff and speculative decoding are " \
            "separate backends (the draft cache does not travel)"
        assert not self.cfg.kv_quant, "KV handoff carries fp pages only " \
            "(quantized scale pools do not travel; DESIGN.md §6.1-paged)"
        out: List[KVHandoff] = []
        for i, s in enumerate(self._slots):
            if s is None or not s.out:
                continue
            cached = int(cached_tokens_fn(s.req)) if cached_tokens_fn else 0
            pages = self._put(self._row_pages[i][cached // self.page_size:],
                              np.int32)
            h = KVHandoff(
                req=s.req, out=list(s.out), length=int(self._lengths[i]),
                k=self._pools["k_pool"][:, pages],
                v=self._pools["v_pool"][:, pages],
                logits=self._logits[i], page_size=self.page_size,
                cached_tokens=cached)
            self._release_pages(i)
            self._slots[i] = None
            self._lengths[i] = 0
            self.stats.handoffs += 1
            self.stats.handoff_bytes += h.kv_bytes
            out.append(h)
        return out

    def accept_handoff(self, h: KVHandoff) -> bool:
        """Disagg decode side: allocate pages for a handed-off request,
        scatter its KV into this engine's pool, and install it in a free
        slot with its prefill logits — decode resumes exactly where the
        prefill engine stopped, so greedy outputs stay bit-identical to a
        colocated paged engine.  Returns False (caller retries after a
        completion) when no slot or not enough free pages are available.
        """
        assert self.paged and h.page_size == self.page_size
        assert not self.spec, "KV handoff and speculative decoding are " \
            "separate backends (the draft cache does not travel)"
        assert not self.cfg.kv_quant, "KV handoff carries fp pages only " \
            "(quantized scale pools do not travel; DESIGN.md §6.1-paged)"
        free_slots = [i for i, s in enumerate(self._slots) if s is None]
        if not free_slots:
            return False
        resident = any(s is not None for s in self._slots)
        usable = self._num_pages - 1
        worst = self._pages(self._required(h.req))
        if not resident:
            # grow the pool while nothing is resident (mirror _admit_paged)
            # so any single accepted handoff can always run to completion —
            # deferred while handoff pins hold page content, since growth
            # reallocates every page and forgets the prefix cache
            if (self._pools is None or worst > usable) \
                    and not self._pinned:
                self._num_pages = max(self._num_pages, worst + 1)
                usable = self._num_pages - 1
                self._pools = None
                self._logits = None
                self._free_pages = list(range(1, self._num_pages))
                self._flush_prefix_cache()
        if worst > usable:
            return False               # can never fit: wait for drain+growth
        pinned = self._pinned.get(h.req.rid, [])
        assert len(pinned) * self.page_size == h.cached_tokens, \
            "handoff was sliced against a pin this engine no longer holds"
        need = pages_for(h.length, self.page_size)
        fresh_need = need - len(pinned)
        if fresh_need > len(self._free_pages) + len(self._cold):
            return False               # keep the pin; caller retries
        self._pinned.pop(h.req.rid, None)
        if self._pools is None:
            self._pools = self._init_pools(self._num_pages, self.page_size)
            self._logits = jnp.zeros(
                (self.max_batch, 1, h.logits.shape[-1]), h.logits.dtype,
                device=self.device)
        i = free_slots[0]
        fresh = [self._claim_page() for _ in range(fresh_need)]
        if fresh:
            phys = self._put(fresh, np.int32)
            # the handoff's KV comes from the prefill engine, which may
            # hold another device: the transfer lands it on this one
            self._pools = {
                "k_pool": self._pools["k_pool"].at[:, phys].set(
                    jax.device_put(h.k[:, :fresh_need], self.device)),
                "v_pool": self._pools["v_pool"].at[:, phys].set(
                    jax.device_put(h.v[:, :fresh_need], self.device))}
        pages = pinned + fresh
        if self.prefix_cache:
            # the transported full prompt pages are now valid content:
            # register them so later requests (and later handoffs, via
            # prefix_pin) can share them
            self._prefix_register(np.asarray(h.req.tokens, np.int32),
                                  len(pinned), fresh)
        self._grow_block_tables(max(need, worst))
        self._row_pages[i] = pages
        self._block_tables[i, :] = 0
        self._block_tables[i, :need] = pages
        self._tables_dirty = True
        slot = _Slot(h.req)
        slot.out = list(h.out)
        self._slots[i] = slot
        self._lengths[i] = h.length
        self._slot_seq[i] = self._admit_seq
        self._admit_seq += 1
        self._logits = self._logits.at[i].set(
            jax.device_put(h.logits, self.device))
        self.stats.handoffs += 1
        self.stats.handoff_bytes += h.kv_bytes
        self.stats.peak_resident = max(self.stats.peak_resident,
                                       self.active_slots())
        return True

    # ------------------------------------------------------------ decode step
    def _append_token(self, i: int, t: int, now: float,
                      finished: List[GenRequest]) -> bool:
        """Append one emitted token to row ``i``, retiring the row on EOS
        or budget exhaustion (shared by the plain sampling phase and the
        speculative acceptance loop, so multi-token emission keeps the
        exact single-token semantics: EOS is dropped from the result
        unless it is the only token).  Returns True while the row
        survives."""
        slot = self._slots[i]
        slot.out.append(t)
        if len(slot.out) == 1:
            slot.req.first_token_at = now
        hit_eos = t == self.eos_id
        if hit_eos or len(slot.out) >= slot.req.max_new:
            row = slot.out[:-1] if hit_eos and len(slot.out) > 1 \
                else slot.out
            slot.req.result = np.asarray(row, np.int32)
            slot.req.finished_at = now
            finished.append(slot.req)
            self._slots[i] = None
            if self.paged:
                self._release_pages(i)         # pages return to the pool
            self.stats.served += 1
            return False
        return True

    def step(self) -> List[GenRequest]:
        """One engine iteration: sample a token for every resident sequence,
        retire finished ones, prefill admissions into freed slots, then run
        one batched decode step for the sequences that continue.

        The slot/paged step records one ``engine.step`` wall span, and
        inside it a span per phase: ``engine.admit`` (the prefills nest in
        it), ``engine.sample``, ``engine.retire``, ``engine.pages``,
        ``engine.decode_step`` and ``engine.carry``."""
        if not self.slot_decode:
            return self._step_wave_legacy()
        if self.spec:
            return self._step_spec()
        tr = get_tracer()
        with tr.wall("engine.step", who=self.owner) as sp:
            finished = self._step_phases(tr)
        self.stats.steps += 1
        self.stats.step_wall_s += sp.dt
        return finished

    def _step_phases(self, tr) -> List[GenRequest]:
        who = self.owner
        with tr.wall("engine.admit", who=who):
            self._admit()
        resident = [i for i, s in enumerate(self._slots) if s is not None]
        if not resident:
            return []
        # 1. sample next token for all resident rows from their current logits
        with tr.wall("engine.sample", who=who, rows=len(resident)):
            temps_np = np.zeros(self.max_batch, np.float32)
            for i in resident:
                temps_np[i] = self._slots[i].req.temperature
            temps = 0.0 if (temps_np <= 0.0).all() else self._put(temps_np)
            cur = self._sample(self._logits, temps)
            cur_np = np.asarray(cur[:, 0])
        now = wall_now()
        finished: List[GenRequest] = []
        survivors: List[int] = []
        with tr.wall("engine.retire", who=who):
            for i in resident:
                if self._append_token(i, int(cur_np[i]), now, finished):
                    survivors.append(i)
        # 2. admit queued work into freed slots between decode steps
        if self.continuous and finished:
            with tr.wall("engine.admit", who=who):
                self._admit()
        # 2b. paged: claim this step's write page per survivor, preempting
        #     the most recent admissions if the pool is exhausted
        if self.paged and survivors:
            with tr.wall("engine.pages", who=who):
                survivors = self._ensure_decode_pages(survivors)
        # 3. one batched decode step advances the surviving rows; rows that
        #    were empty or just prefilled ride along (static batch shape) —
        #    their cache write lands at their own depth and is overwritten by
        #    their first real decode, and their logits are kept, not replaced
        if survivors:
            with tr.wall("engine.decode_step", who=who,
                         rows=len(survivors)) as spn:
                if self.paged:
                    # trim the table to the pages live rows can actually
                    # touch and reuse the device-resident copy whenever no
                    # host-side mutation invalidated it (§Perf-kernels)
                    w = self._table_width()
                    spn.note(width=w)
                    if (self._tables_dirty or self._bt_dev is None
                            or self._bt_dev.shape[1] != w):
                        self._bt_dev = self._put(self._block_tables[:, :w])
                        self._len_dev = self._put(self._lengths, jnp.int32)
                    cache = {**self._pools, "block_tables": self._bt_dev,
                             "lengths": self._len_dev}
                    logits, cache = self._decode_paged(self.params, cache,
                                                       cur)
                    logits.block_until_ready()
                    # ceil((length + 1) / page): through the new token
                    self.stats.decode_kv_pages += int(
                        ((self._lengths[survivors] + self.page_size)
                         // self.page_size).sum())
                    self.stats.decode_table_pages += self.max_batch * w
                    self._pools = {n: cache[n] for n in self._pool_names}
                    # the cache is donated: only the RETURNED tables/lengths
                    # are valid now.  They advanced every row by one; reuse
                    # is only sound when every active row was a survivor — a
                    # rider row (admitted mid-step) holds its prompt length
                    # on the host but length+1 on the device, so its next
                    # write would skip a position.  Any rider forces a
                    # re-upload.
                    self._bt_dev = cache["block_tables"]
                    self._len_dev = cache["lengths"]
                    self._tables_dirty = self.active_slots() != len(survivors)
                else:
                    cache = {**self._cache,
                             "length": self._put(self._lengths, jnp.int32)}
                    logits, cache = self._decode(self.params, cache, cur)
                    logits.block_until_ready()
                    self._cache = {k: v for k, v in cache.items()
                                   if k != "length"}
            self.stats.decode_wall_s += spn.dt
            with tr.wall("engine.carry", who=who):
                keep = self._put(survivors)
                self._logits = self._logits.at[keep].set(logits[keep])
                self._lengths[survivors] += 1
            self.stats.decode_tokens += len(survivors)
            self.stats.decode_steps += 1
        return finished

    # ------------------------------------------------- speculative decoding
    def _step_spec(self) -> List[GenRequest]:
        """One speculative engine iteration (DESIGN.md §6.1-spec).

        The pending token is sampled for every resident row from its
        carried logits exactly as the plain paged step does; then, instead
        of one single-token decode, the draft model proposes ``spec_k``
        tokens greedily and ONE batched target forward
        (``Family.paged_verify``) scores pending + drafts at once.  The
        longest draft prefix matching the target's own greedy choices is
        emitted; the correction token is NOT emitted here — the verify
        logits after the last accepted token become the carried logits, so
        the next iteration's sampling phase reproduces it.  Every emitted
        token is therefore the argmax of target logits over the same
        prefix as non-speculative decode: greedy outputs are
        bit-identical, speculation only changes how many target forwards
        they take.
        """
        self._admit()
        resident = [i for i, s in enumerate(self._slots) if s is not None]
        if not resident:
            return []
        # 1. pending token from carried logits (identical to the base step;
        #    spec rows are greedy-only, enforced at submit)
        cur = self._sample(self._logits, 0.0)
        cur_np = np.asarray(cur[:, 0])
        now = wall_now()
        finished: List[GenRequest] = []
        survivors: List[int] = []
        for i in resident:
            if self._append_token(i, int(cur_np[i]), now, finished):
                survivors.append(i)
        # 2. admit queued work into freed slots between steps (freshly
        #    prefilled rows ride along this verify and join the next one)
        if self.continuous and finished:
            self._admit()
        # 2b. claim pages covering the pending token + spec_k draft writes,
        #     preempting the most recent admissions if the pool exhausts
        if survivors:
            survivors = self._ensure_decode_pages(survivors,
                                                  lookahead=self.spec_k + 1)
        if not survivors:
            return finished
        k = self.spec_k
        # 3. draft k tokens greedily, feeding the pending token first; the
        #    draft cache rows advance in lock-step with the target's pages
        #    (riding-along rows write garbage at their own stale depth,
        #    fully overwritten before it is ever attended)
        drafts = np.zeros((self.max_batch, k), np.int32)
        tok = cur
        with get_tracer().wall("engine.spec_draft", who=self.owner,
                               k=k, batch=len(survivors)) as dsp:
            for j in range(k):
                dcache = {**self._draft_cache,
                          "length": self._put(self._draft_lengths + j,
                                                jnp.int32)}
                dlogits, dcache = self._draft_decode(self.spec_draft_params,
                                                     dcache, tok)
                dlogits.block_until_ready()
                self._draft_cache = {n: v for n, v in dcache.items()
                                     if n != "length"}
                with jax.default_device(self.device):
                    tok = _greedy_tokens(dlogits[:, -1],
                                         self.spec_draft_cfg.vocab_size
                                         )[:, None]
                drafts[:, j] = np.asarray(tok[:, 0])
            # land the last draft's KV too: each proposing forward writes
            # its INPUT token, so d_k would be missing from the draft cache
            # when all k drafts are accepted and the next round builds on it
            # — one discarded forward writes it at draft position n + k
            # (harmless for rows that accept less: the position is past
            # their valid prefix and overwritten before it is ever attended)
            dcache = {**self._draft_cache,
                      "length": self._put(self._draft_lengths + k,
                                            jnp.int32)}
            dlogits, dcache = self._draft_decode(self.spec_draft_params,
                                                 dcache, tok)
            dlogits.block_until_ready()
            self._draft_cache = {n: v for n, v in dcache.items()
                                 if n != "length"}
        self.stats.draft_wall_s += dsp.dt
        self.stats.spec_drafted += k * len(survivors)
        # 4. verify pending + drafts in ONE batched target forward; the
        #    verify scatters all k+1 tokens' KV into the pages claimed in
        #    2b (rejected drafts land beyond the valid length and are
        #    overwritten by the next verify at the same positions)
        toks = np.concatenate([cur_np[:, None], drafts], axis=1)
        # spec lengths advance by a variable 1+a per row, so the device
        # tables are rebuilt every verify (no resident reuse); the width is
        # still trimmed to the pages the k+1 writes can touch
        w = self._table_width(lookahead=self.spec_k + 1)
        cache = {**self._pools,
                 "block_tables": self._put(self._block_tables[:, :w]),
                 "lengths": self._put(self._lengths, jnp.int32)}
        with get_tracer().wall("engine.spec_verify", who=self.owner,
                               k=k, batch=len(survivors)) as vsp:
            vlogits, cache = self._verify(self.params, cache,
                                          self._put(toks))
            vlogits.block_until_ready()
        self.stats.decode_wall_s += vsp.dt
        self.stats.verify_wall_s += vsp.dt
        self._pools = {n: cache[n] for n in self._pool_names}
        # the target's greedy choice at every position, with the same
        # vocab masking + argmax as sample(temperature=0)
        with jax.default_device(self.device):
            tgt = np.asarray(_greedy_tokens(vlogits, self.cfg.vocab_size))
        # 5. per row: accept the longest draft prefix matching the target,
        #    emit it under the usual EOS/budget rules, advance the caches
        #    over pending + accepted tokens only
        now = wall_now()
        rows: List[int] = []
        pos: List[int] = []
        accepts: List[int] = []
        for i in survivors:
            a = 0
            while a < k and drafts[i, a] == tgt[i, a]:
                a += 1
            self.spec_accept_hist[a] += 1
            self.stats.spec_accepted += a
            accepts.append(a)
            appended = 0
            alive = True
            for j in range(a):
                appended += 1
                if not self._append_token(i, int(drafts[i, j]), now,
                                          finished):
                    alive = False
                    break
            # count tokens fed to a target forward as valid context — the
            # same rule the plain path's len(survivors) implements: a
            # request's FINAL emitted token (here: the draft that retired
            # the row) never feeds a forward, so both engines accumulate
            # identical decode_tokens for identical outputs
            self.stats.decode_tokens += appended + (1 if alive else 0)
            if alive:
                self._lengths[i] += 1 + a
                self._draft_lengths[i] = self._lengths[i]
                rows.append(i)
                pos.append(a)       # carry logits after the last accepted
        # ONE EMA update per verify step (the documented SPEC_EMA_BETA
        # semantics), over the step's mean acceptance — per-row updates
        # would scale the effective smoothing with batch size
        obs = sum(accepts) / (k * len(accepts))
        self.spec_alpha += SPEC_EMA_BETA * (obs - self.spec_alpha)
        # 6. carry each surviving row's correction logits: position a is the
        #    target's distribution after [pending, d_1..d_a] — next step's
        #    argmax emits the correction (or the bonus token when a == k)
        if rows:
            ridx = self._put(rows)
            upd = vlogits[ridx, self._put(pos)][:, None]
            self._logits = self._logits.at[ridx].set(upd)
        self.stats.decode_steps += 1
        self.stats.spec_steps += 1
        return finished

    # ----------------------------------------------- legacy wave (non-dense)
    def _step_wave_legacy(self) -> List[GenRequest]:
        if not self._queue:
            return []
        wave, self._queue = (self._queue[: self.max_batch],
                             self._queue[self.max_batch:])
        return self._generate_wave(wave)

    def _serve_wave_legacy(self, reqs: List[GenRequest]) -> List[GenRequest]:
        out: List[GenRequest] = []
        for i in range(0, len(reqs), self.max_batch):
            out.extend(self._generate_wave(reqs[i: i + self.max_batch]))
        return out

    def _generate_wave(self, reqs: List[GenRequest]) -> List[GenRequest]:
        """Left-padded lock-step decode for families without per-row cache
        depths (shared scalar cache length)."""
        assert len(reqs) <= self.max_batch
        max_prompt = max(len(r.tokens) for r in reqs)
        plen = self._pad_bucket(max_prompt)
        max_new = max(r.max_new for r in reqs)
        toks = np.full((len(reqs), plen), self.eos_id, np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.tokens):] = r.tokens     # left-pad
        batch = {"tokens": self._put(toks)}
        cap = plen + self._pad_bucket(max_new)
        with get_tracer().wall("engine.prefill", who=self.owner, path="wave",
                               rows=len(reqs),
                               tokens=plen * len(reqs)) as sp:
            logits, cache = self._prefill(self.params, batch, cap)
            logits.block_until_ready()
        self.stats.prefill_wall_s += sp.dt
        self.stats.prefill_tokens += plen * len(reqs)
        self.stats.batches += 1
        started = wall_now()
        for r in reqs:
            r.started_at = started

        out = np.zeros((len(reqs), max_new), np.int32)
        done = np.zeros(len(reqs), bool)
        temps_np = np.array([r.temperature for r in reqs], np.float32)
        # all-greedy batches (the default) keep the scalar fast path in
        # sample(), skipping the per-step Gumbel draw over the vocab
        temps = 0.0 if (temps_np <= 0.0).all() else self._put(temps_np)
        budgets = np.array([r.max_new for r in reqs])
        for step in range(max_new):
            cur = self._sample(logits, temps)
            out[:, step] = np.asarray(cur[:, 0])
            if step == 0:
                now = wall_now()
                for r in reqs:
                    r.first_token_at = now
            done |= out[:, step] == self.eos_id
            done |= step + 1 >= budgets
            if done.all():
                break
            with get_tracer().wall("engine.decode_step", who=self.owner,
                                   batch=int((~done).sum())) as sp:
                logits, cache = self._decode(self.params, cache, cur)
                logits.block_until_ready()
            self.stats.decode_wall_s += sp.dt
            self.stats.decode_tokens += int((~done).sum())
            self.stats.decode_steps += 1
        for i, r in enumerate(reqs):
            row = out[i, : r.max_new]
            end = np.argmax(row == self.eos_id) if (row ==
                                                    self.eos_id).any() \
                else r.max_new
            r.result = row[: max(int(end), 1)]
            r.finished_at = wall_now()
        self.stats.served += len(reqs)
        return reqs

    def logprob_of(self, tokens: np.ndarray) -> float:
        """Sequence log-likelihood under this engine's model — used by the
        real-engine duel judges (DESIGN.md §6.2)."""
        t = self._put(tokens[None, :])
        logits = registry.apply_logits(self.params, self.cfg,
                                       {"tokens": t[:, :-1]},
                                       q_chunk=256, kv_chunk=256)
        logits = logits.astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        gold = jnp.take_along_axis(lp, t[:, 1:, None], axis=-1)
        return float(jnp.sum(gold))
