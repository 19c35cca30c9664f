"""Serving runtime: continuous batching with chunked streamed prefill.

The serving analogue of the paper's case study: prefill is the one-sided
bulk transfer of the prompt into the cache (the ``gasnet_put``), decode is
the ART pattern of many small transfers.  PR 5 rebuilds both on the
pipeline scheduler:

* **Admission** is per slot: a request's prompt is prefilled into a
  full-length K/V scratch by incremental *chunk steps*
  (``dist/steps.build_prefill_chunk_step`` over
  ``models/prefill.prefill_chunk``), at most one chunk per server step, so
  prefill work interleaves with decode steps instead of blocking them —
  chunked prefill admission kills the head-of-line blocking a long prompt
  used to impose on every decoding request.  The finished scratch is
  converted into a single-request cache and written into its batch row
  with one donated ``dynamic_update_slice`` per leaf
  (``build_slot_write_step`` — the per-slot PUT).  Every arch in the zoo
  rides this path with its own chunk carry
  (``configs.base.chunk_carry_spec``: K/V ring rows, MLA latents,
  constant-size SSD state, the hybrid pair, encoder-once cross-K/V); the
  one runtime gate is ``models/prefill.chunk_support`` (the blockwise
  attention impl), and a gated arch — or ``prefill_chunk=None`` — admits
  with one bulk per-slot prefill instead, *with* a build warning and a
  ``stats()['admission_mode']`` signal (same numerics, whole-prompt
  latency).  Chunk sizes round up to the carry's ``chunk_multiple`` so
  SSD state hand-offs stay on ``ssm_chunk`` boundaries.
* **Decode** runs the donated ``build_serve_step`` with ``sample=True``:
  per-slot positions let every cache row advance independently, argmax
  runs on device, and the server fetches one stacked ``(B,)`` id vector
  per step instead of per-slot logits syncs.

**Paged KV pool** (PR 6, ``ServerConfig.paged``): the monolithic per-rank
cache becomes a pool of fixed-size KV blocks addressed through a per-slot
block table (``models/decode.init_paged_cache``).  Admission converts the
finished prefill into pool blocks and pushes only the *private* ones with
one donated block-write (``dist/steps.build_block_write_step`` — the
block-granular ``gasnet_put``; ``core/pgas.BlockSegment`` is the global
addressing it models); a host-side ref-counted :class:`BlockPool` runs the
free list and the prefix cache, so identical prompt prefixes are admitted
once and aliased copy-on-write into many slots' tables.  Decode through
the table is bit-identical to the contiguous ring (asserted by
tests/test_serving.py).

TTFT accounting: ``Request.first_token`` is stamped when the request's
first *decode token id* has actually been sampled and fetched — never at
prefill completion — and stays correct under chunked admission because the
stamp rides the token append, not the scheduler phase.  ``admitted`` (the
request took a slot) and ``prefill_start`` (its bulk prefill, or first
chunk, was dispatched) split the wait before it.

Tracing: every phase of :meth:`Server.step` runs inside a named host span
(``jax.profiler.TraceAnnotation``, the ``SPAN_*`` names below).  Under a
running profiler the spans land in its trace on the clock of the device
events, nested as the calls are; with no profiler each costs about a
microsecond, so they are always on.  docs/serving.md ("Tracing a server")
lists what each span covers.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig, chunk_carry_spec
from repro.dist.steps import (
    StepConfig,
    build_block_write_step,
    build_prefill_chunk_step,
    build_prefill_step,
    build_serve_step,
    build_slot_write_step,
)
from repro.models.decode import (
    init_cache,
    init_paged_cache,
    kv_buf_len,
    paged_slot_blocks,
    supports_paged,
)
from repro.models.prefill import (
    cache_to_blocks,
    chunk_support,
    init_prefill_scratch,
    prefill_chunk_cuts,
    scratch_to_blocks,
    scratch_to_cache,
    seed_scratch_from_blocks,
)

_span = jax.profiler.TraceAnnotation

#: host spans of the serving loop; a request's spans carry its ``rid``
SPAN_SUBMIT = "serve.submit"            # Server.submit
SPAN_STEP = "serve.step"                # the whole of Server.step
SPAN_ADMIT = "serve.admit"              # queued requests take free slots
#: prompt upload through the dispatch of the prefill (or chunk) and of the
#: slot writer; args ``rid`` and ``tokens`` (positions prefilled)
SPAN_PREFILL = "serve.prefill"
SPAN_FIRST_TOKEN = "serve.first_token"  # blocking fetch of the first id
SPAN_DECODE = "serve.decode"            # token upload + decode dispatch
SPAN_FETCH = "serve.fetch"              # host waits for the decoded ids
SPAN_EMIT = "serve.emit"                # per-slot append / retire


class BlockPool:
    """Host-side ref-counted free list over the paged KV pool.

    Block ids ``[0, reserved)`` are *parking* blocks (one per batch row —
    an idle row's table points at its own parking block so its dead decode
    writes can never touch allocated blocks) and are never handed out.
    Every other id is either on the free list or ref-counted live: one ref
    per slot whose table maps the block, plus one per prefix-cache entry
    that pins it.  Entries are LRU-evicted (their refs dropped) when
    ``alloc`` runs short — blocks still mapped by running requests survive
    eviction of the entry that cached them (copy-on-write sharing).
    """

    def __init__(self, n_blocks: int, reserved: int = 0):
        self.n_blocks = int(n_blocks)
        self.reserved = int(reserved)
        assert 0 <= self.reserved <= self.n_blocks
        # LIFO free list, low ids first out (nicer to read in tests)
        self._free = list(range(self.n_blocks - 1, self.reserved - 1, -1))
        self._refs: Dict[int, int] = {}
        self._entries: "dict[bytes, List[int]]" = {}   # insertion = LRU order
        self.evictions = 0
        self._lost: set = set()          # ids on failed partitions
        self._quarantined: set = set()   # lost ids already swept off free/live

    # -- invariant surface (the hypothesis tests drive these) ---------------

    @property
    def free_blocks(self) -> int:
        """Blocks immediately available to ``alloc``."""
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        """Blocks with at least one reference (slots or cache entries)."""
        return len(self._refs)

    @property
    def cached_entries(self) -> int:
        """Resident prefix-cache entries."""
        return len(self._entries)

    @property
    def lost_blocks(self) -> int:
        """Ids on dead partitions (``fail_partition``), reserved included."""
        return len(self._lost)

    @property
    def quarantined_blocks(self) -> int:
        """Lost non-reserved ids swept out of circulation — admission's
        capacity target shrinks by exactly this many blocks while a
        partition is quarantined."""
        return len(self._quarantined)

    def evictable_blocks(self) -> int:
        """Blocks that evicting *every* idle prefix-cache entry would
        return to the free list: pinned only by cache entries, on a live
        partition.  Blocks shared with running requests (COW) stay live
        after eviction and do not count."""
        pins: Dict[int, int] = {}
        for bids in self._entries.values():
            for b in bids:
                pins[b] = pins.get(b, 0) + 1
        return sum(1 for b, p in pins.items()
                   if self._refs.get(b, 0) == p and b not in self._lost)

    def usable_blocks(self) -> int:
        """Upper bound on what one ``alloc`` can deliver: free now plus
        everything cache eviction could recover."""
        return len(self._free) + self.evictable_blocks()

    def can_cover(self, n: int) -> bool:
        """True when ``alloc(n)`` would succeed — *without* touching the
        cache.  Admission consults this so a burst during quarantine
        defers requests instead of wiping the prefix cache on a doomed
        claim."""
        return int(n) <= self.usable_blocks()

    def check_conservation(self):
        """Every non-reserved block is free xor referenced xor quarantined
        — no leaks, no aliasing between the free list and live tables, and
        the invariant *holds across a partition shrink*: a lost block is
        quarantined the moment its last reference drops (or immediately,
        when it was free), never re-entering circulation."""
        assert (self.free_blocks + self.live_blocks
                + len(self._quarantined)) \
            == self.n_blocks - self.reserved, (
                self.free_blocks, self.live_blocks,
                len(self._quarantined), self.n_blocks)
        assert not set(self._free) & set(self._refs)
        assert not set(self._free) & self._quarantined
        assert not self._quarantined & set(self._refs)
        # a quarantined block is always a lost one
        assert self._quarantined <= self._lost

    # -- partition shrink (decode-rank loss) ---------------------------------

    def partition(self, rank: int, n_ranks: int) -> range:
        """Contiguous id range owned by decode rank ``rank`` of
        ``n_ranks`` — the pool's PGAS segment map (each rank backs an
        equal contiguous span of block ids, remainders to the tail)."""
        assert 0 <= rank < n_ranks, (rank, n_ranks)
        lo = rank * self.n_blocks // n_ranks
        hi = (rank + 1) * self.n_blocks // n_ranks
        return range(lo, hi)

    def fail_partition(self, rank: int, n_ranks: int) -> frozenset:
        """Mark rank ``rank``'s id span dead and shrink the pool around it.

        Free lost ids quarantine immediately; live lost ids stay counted
        as live until their holders drain and ``release`` them (at which
        point they quarantine instead of returning to the free list);
        prefix-cache entries pinning any lost block are purged (their pin
        refs dropped — surviving entries keep serving COW hits).  Returns
        the lost id set so the server can find the victim slots.
        """
        return self.fail_partitions([rank], n_ranks)

    def fail_partitions(self, ranks, n_ranks: int) -> frozenset:
        """Batch form of :meth:`fail_partition`: quarantine the union of
        several ranks' id spans in **one** sweep — the multi-rank-loss
        path, where every rank missing the same lease deadline is
        excluded atomically (one free-list rebuild, one cache purge,
        conservation held throughout)."""
        lost = frozenset(b for r in ranks
                         for b in self.partition(r, n_ranks))
        self._lost |= lost
        self._free = [b for b in self._free if b not in lost]
        self._quarantined |= {b for b in lost
                              if b >= self.reserved and b not in self._refs}
        for key in [k for k, bids in self._entries.items()
                    if set(bids) & lost]:
            self.release(self._entries.pop(key))
        return lost

    def restore_partition(self, rank: int, n_ranks: int) -> frozenset:
        """Re-admit rank ``rank``'s id span — the scale-out/rejoin path.

        Quarantined ids in the span return to the free list (descending
        order, so low ids still pop first); reserved parking ids are
        simply un-lost.  Ids still referenced (a straggler holding a lost
        block that never drained) stay out until their refs drop — they
        are un-lost here, so ``release`` will free them normally.
        Returns the restored id set.
        """
        span = frozenset(self.partition(rank, n_ranks)) & self._lost
        back = sorted((b for b in span & self._quarantined), reverse=True)
        self._quarantined -= span
        self._lost -= span
        self._free.extend(back)
        return span

    # -- alloc / refcount ----------------------------------------------------

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks off the free list (one ref each), LRU-evicting
        idle prefix-cache entries under pressure; raises ``MemoryError``
        when the pool genuinely cannot cover the request.

        The feasibility check runs *first*: a doomed claim (``n`` beyond
        free + evictable, e.g. an alloc burst while a partition is
        quarantined) raises without evicting anything, so the prefix
        cache survives the failure instead of being wiped for nothing.
        """
        if not self.can_cover(n):
            raise MemoryError(
                f"block pool exhausted: want {n}, free {len(self._free)}, "
                f"evictable {self.evictable_blocks()}, "
                f"quarantined {len(self._quarantined)}")
        while len(self._free) < n and self._entries:
            self._evict_lru()
        if len(self._free) < n:
            raise MemoryError(
                f"block pool exhausted: want {n}, free {len(self._free)}")
        bids = [self._free.pop() for _ in range(n)]
        for b in bids:
            self._refs[b] = 1
        return bids

    def retain(self, bids: List[int]):
        """Add one reference to each (already live) block."""
        for b in bids:
            if b not in self._refs:
                raise ValueError(f"retain of unallocated block {b}")
            self._refs[b] += 1

    def release(self, bids: List[int]):
        """Drop one reference from each block; blocks reaching zero return
        to the free list — or to quarantine when their partition died
        (``fail_partition``), so a lost id never re-enters circulation.
        Releasing a free block raises (double free)."""
        for b in bids:
            if b not in self._refs:
                raise ValueError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                if b in self._lost:
                    if b >= self.reserved:
                        self._quarantined.add(b)
                else:
                    self._free.append(b)

    # -- prefix cache --------------------------------------------------------

    def cache_insert(self, key: bytes, bids: List[int]):
        """Pin ``bids`` (one extra ref each) as the cached blocks of prompt
        prefix ``key``; a no-op if the key is already resident."""
        if key in self._entries:
            return
        self.retain(bids)
        self._entries[key] = list(bids)

    def cache_lookup(self, key: bytes) -> Optional[List[int]]:
        """If ``key`` is resident, retain its blocks for the caller and
        return them (freshest LRU position); else ``None``."""
        if key not in self._entries:
            return None
        bids = self._entries.pop(key)
        self._entries[key] = bids                     # move to LRU tail
        self.retain(bids)
        return list(bids)

    def _evict_lru(self):
        key = next(iter(self._entries))
        self.release(self._entries.pop(key))
        self.evictions += 1


@dataclasses.dataclass
class ServerConfig:
    """Continuous-batching knobs (see docs/serving.md)."""

    max_batch: int = 8
    max_seq: int = 256
    max_new_tokens: int = 32
    eos_id: int = -1               # -1: disabled (synthetic workloads)
    greedy: bool = True
    #: tokens per admitted prefill chunk (the streamed-prefill ART chunk);
    #: None/0 admits with one bulk per-slot prefill instead
    prefill_chunk: Optional[int] = 16
    #: paged KV pool: decode gathers each row's ring through a per-slot
    #: block table (bit-identical to the contiguous cache)
    paged: bool = False
    #: KV positions per pool block; must divide the ring extent and (for
    #: prefix caching) be a multiple of ``prefill_chunk``
    block_size: int = 16
    #: pool size; default = parking row per slot + a full table per slot
    #: + one spare table's worth of prefix-cache headroom
    n_blocks: Optional[int] = None
    #: admit identical prompt prefixes once (shared ref-counted blocks)
    prefix_cache: bool = True


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray             # (S,) int32
    frontend_embeds: Optional[np.ndarray] = None   # frontend (vlm) archs
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    submitted: float = 0.0
    admitted: Optional[float] = None        # took a slot
    prefill_start: Optional[float] = None   # prefill (first chunk) dispatched
    first_token: Optional[float] = None
    finished: Optional[float] = None
    cancelled: bool = False
    # scheduler state (not part of the public result surface)
    phase: str = "queued"          # queued | prefill | decode
    _scratch: Optional[dict] = None
    _cursor: int = 0               # next prompt position to prefill
    _blocks: List[int] = dataclasses.field(default_factory=list)
    _shared: int = 0               # leading blocks aliased from the cache
    _recovered: bool = False       # drained off a dead rank, awaiting re-admit


class Server:
    """Fixed-slot continuous-batching server over the serve step bundles."""

    def __init__(self, cfg: ModelConfig, params, mesh, scfg=None,
                 srv: ServerConfig = ServerConfig(), fault_plan=None,
                 membership=None):
        self.cfg, self.params, self.srv = cfg, params, srv
        self.mesh = mesh
        self.scfg = scfg or StepConfig()
        self.fault_plan = fault_plan
        # live detector path: a MembershipService polled every tick; its
        # view changes (not the scripted plan) drive fail/admit below
        self.membership = membership
        assert srv.greedy, "only greedy sampling is implemented"
        ok, why = chunk_support(cfg)
        if srv.prefill_chunk and not ok:
            # never fall back silently: admission mode is a serving
            # property the operator asked for
            warnings.warn(
                f"{cfg.name}: chunked prefill requested "
                f"(prefill_chunk={srv.prefill_chunk}) but unsupported — "
                f"{why}; admitting with bulk per-slot prefill",
                stacklevel=2)
        self._chunkable = ok and bool(srv.prefill_chunk)
        self._fallback_reason = ("" if self._chunkable
                                 else (why if srv.prefill_chunk
                                       else "prefill_chunk disabled"))
        # chunk sizes round up to the carry contract's multiple (SSD state
        # hand-off is bit-exact only on ssm_chunk boundaries)
        mult = chunk_carry_spec(cfg).chunk_multiple
        self._eff_chunk = (-(-int(srv.prefill_chunk) // mult) * mult
                           if self._chunkable else 0)
        self._paged = bool(srv.paged)
        if self._paged:
            assert supports_paged(cfg), \
                f"{cfg.name} has no paged-cache layout"
            self._sb = kv_buf_len(cfg, srv.max_seq)
            self._blk = int(srv.block_size)
            self._npb = paged_slot_blocks(cfg, srv.max_seq, self._blk)
            self._n_blocks = int(srv.n_blocks or
                                 srv.max_batch * (1 + self._npb) + self._npb)
            if srv.prefix_cache and self._chunkable:
                assert self._blk % self._eff_chunk == 0, (
                    "prefix caching needs block_size to be a multiple of "
                    f"the effective chunk ({self._blk} % {self._eff_chunk})")
            self.pool = BlockPool(self._n_blocks, reserved=srv.max_batch)
            self.bundle = build_serve_step(
                cfg, mesh, self.scfg, batch=srv.max_batch,
                max_seq=srv.max_seq, sample=True,
                block_size=self._blk, n_blocks=self._n_blocks)
        else:
            self.pool = None
            self.bundle = build_serve_step(cfg, mesh, self.scfg,
                                           batch=srv.max_batch,
                                           max_seq=srv.max_seq, sample=True)
        self.writer = build_slot_write_step(cfg, mesh, srv.max_batch,
                                            srv.max_seq)
        from repro.dist.sharding import to_shardings
        self._cache_sh = to_shardings(mesh, self.bundle.in_specs[1])
        self._slot_sh = to_shardings(mesh, self.writer.in_specs[1])
        # each program the server runs has a name of its own (``jit_<def>``
        # in a profiler trace); the decode step alone is ``jit_fn``
        if self._paged:
            blk, nb = self._blk, self._n_blocks

            def cache_init():
                return init_paged_cache(cfg, srv.max_batch, srv.max_seq,
                                        blk, nb)

            self.cache = jax.jit(cache_init,
                                 out_shardings=self._cache_sh)()
            npb, sb = self._npb, self._sb

            def park(cache, i):
                out = dict(cache)
                out["block_ids"] = lax.dynamic_update_slice_in_dim(
                    cache["block_ids"],
                    jnp.broadcast_to(i.astype(jnp.int32), (1, npb)),
                    i, axis=0)
                out["slot_pos"] = lax.dynamic_update_slice_in_dim(
                    cache["slot_pos"], jnp.full((1, sb), -1, jnp.int32),
                    i, axis=0)
                out["pos"] = lax.dynamic_update_slice_in_dim(
                    cache["pos"], jnp.zeros((1,), jnp.int32), i, axis=0)
                return out

            self._park_fn = jax.jit(
                park, in_shardings=(self._cache_sh, None),
                out_shardings=self._cache_sh, donate_argnums=(0,))
        else:
            def cache_init():
                return init_cache(cfg, srv.max_batch, srv.max_seq)

            self.cache = jax.jit(cache_init,
                                 out_shardings=self._cache_sh)()
        self._chunk_bundles: Dict[tuple, object] = {}   # (S, lo, C) -> bundle
        self._bulk_bundles: Dict[int, object] = {}      # S -> fn
        self._scratch_inits: Dict[int, object] = {}     # S -> jitted init
        self._finish_fns: Dict[int, object] = {}        # S -> jitted convert
        self._blocks_fns: Dict[int, object] = {}        # S -> jitted convert
        self._seed_fns: Dict[tuple, object] = {}        # (S, m) -> jitted
        self._block_writers: Dict[int, object] = {}     # n_write -> bundle
        self.slots: List[Optional[Request]] = [None] * srv.max_batch
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._next_tok = np.zeros((srv.max_batch,), np.int32)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self._ticks = 0
        self._dead_slots: set = set()   # rows whose parking block died
        self.recoveries = 0             # drain/re-admit cycles survived
        self.reprefilled_tokens = 0     # positions re-prefilled on recovery

    @property
    def chunked_admission(self) -> bool:
        """Whether admission actually runs as streamed prefill chunks.
        False means every prompt admits with one bulk per-slot prefill —
        either ``ServerConfig.prefill_chunk`` is disabled or the arch is
        gated out by ``models/prefill.chunk_support`` (in which case the
        constructor warned and ``stats()['admission_fallback']`` carries
        the reason)."""
        return self._chunkable

    def _eff_len(self, s: int) -> int:
        """Prefill-row count of an ``s``-token prompt: vlm frontend rows
        prefix the token rows (they are positions in the same scratch);
        encdec frames feed the encoder, not the decoder stream."""
        if self.cfg.frontend and self.cfg.family != "encdec":
            return s + self.cfg.frontend_tokens
        return s

    # -- request intake -------------------------------------------------------

    def submit(self, prompt: np.ndarray,
               frontend_embeds: Optional[np.ndarray] = None) -> int:
        rid = len(self.queue) + len(self.done) + sum(s is not None
                                                     for s in self.slots)
        with _span(SPAN_SUBMIT, rid=rid):
            prompt = np.asarray(prompt, np.int32)
            eff = self._eff_len(prompt.size)
            assert prompt.ndim == 1 and 0 < eff <= self.srv.max_seq, (
                prompt.shape, self.srv.max_seq)
            if self.cfg.family == "encdec":
                assert prompt.size <= self.cfg.decoder_max_seq, prompt.shape
            if self.cfg.frontend:
                assert frontend_embeds is not None, (
                    f"{self.cfg.name} requires frontend embeddings per "
                    f"request")
                frontend_embeds = np.asarray(frontend_embeds, np.float32)
                assert frontend_embeds.shape == (self.cfg.frontend_tokens,
                                                 self.cfg.frontend_dim), \
                    frontend_embeds.shape
            self.queue.append(Request(rid=rid, prompt=prompt,
                                      frontend_embeds=frontend_embeds,
                                      submitted=time.perf_counter()))
        return rid

    def _admit(self):
        """Assign queued requests to free slots (state only — their prompts
        are prefilled chunk-by-chunk between the following decode steps).
        Paged admission also claims the slot's pool blocks here, reusing
        ref-counted prefix-cache blocks when the prompt's leading full
        blocks are already resident; a dry pool leaves the request queued
        (backpressure) until a retire frees blocks."""
        for i, slot in enumerate(self.slots):
            if i in self._dead_slots:
                continue        # parking block lost: row capacity is gone
            if slot is None and self.queue:
                req = self.queue[0]
                if self._paged and not self._claim_blocks(req):
                    break
                self.queue.pop(0)
                if req.admitted is None:
                    # a recovered request keeps its first stamps
                    req.admitted = time.perf_counter()
                req.phase = "prefill"
                req._cursor = 0
                if self._chunkable:
                    se = self._eff_len(int(req.prompt.size))
                    req._scratch = self._scratch_init(se)()
                    if self._paged and req._shared:
                        req._scratch = self._seed_fn(se, req._shared)(
                            req._scratch, self.cache,
                            jnp.asarray(req._blocks[:req._shared],
                                        jnp.int32))
                        req._cursor = (req._shared * self._blk
                                       // self._eff_chunk)
                if req._recovered:
                    # the surviving committed prefix came back COW
                    # (``_shared`` blocks); only the rest re-prefills
                    req._recovered = False
                    self.reprefilled_tokens += (
                        self._eff_len(int(req.prompt.size))
                        - req._shared * (self._blk if self._paged else 0))
                self.slots[i] = req

    # -- paged block accounting ----------------------------------------------

    def _share_ok(self, s: int) -> bool:
        """Whether a prompt of length ``s`` may alias prefix-cache blocks:
        sharing is copy-on-write (shared blocks are never rewritten), so
        decode must be provably unable to ring-wrap into them."""
        return (self._paged and self.srv.prefix_cache and self._chunkable
                and self.cfg.window is None and not self.cfg.frontend
                and s + self.srv.max_new_tokens <= self._sb)

    def _m_max(self, s: int) -> int:
        """Most leading *full* blocks of an ``s``-token prompt that can be
        shared — at least one token (one chunk) must remain to prefill, so
        the final chunk's logits can emit the first decode token."""
        return min((s - 1) // self._blk, self._npb)

    def _claim_blocks(self, req: Request) -> bool:
        """Claim the slot's ``S_buf/blk`` pool blocks: the longest resident
        prompt prefix supplies shared blocks (retained, not copied), the
        rest come off the free list.  False = pool dry, leave queued."""
        s = int(req.prompt.size)
        shared: List[int] = []
        if self._share_ok(s):
            for m in range(self._m_max(s), 0, -1):
                got = self.pool.cache_lookup(
                    req.prompt[:m * self._blk].tobytes())
                if got is not None:
                    shared = got
                    break
            if shared:
                self.prefix_hits += 1
            else:
                self.prefix_misses += 1
        need = self._npb - len(shared)
        if not self.pool.can_cover(need):
            # quarantine backpressure: the capacity target shrank, so a
            # burst defers (stays queued) instead of wiping the prefix
            # cache on a claim that cannot succeed anyway
            if shared:
                self.pool.release(shared)
                self.prefix_hits -= 1
                self.prefix_misses += 1
            return False
        try:
            private = self.pool.alloc(need)
        except MemoryError:
            if shared:
                self.pool.release(shared)
                self.prefix_hits -= 1
                self.prefix_misses += 1
            return False
        req._blocks = shared + private
        req._shared = len(shared)
        return True

    def _scratch_specs(self, se: int):
        """Shardings of the size-``se`` prefill scratch (committed arrays
        must match the chunk bundles' in-sharding exactly)."""
        from repro.dist.sharding import cache_pspecs, to_shardings
        cfg = self.cfg
        shape = jax.eval_shape(lambda: init_prefill_scratch(cfg, 1, se))
        return to_shardings(self.mesh,
                            cache_pspecs(cfg, self.mesh, shape))

    def _seed_fn(self, se: int, m: int):
        """Jitted prefix-hit seeder: gather ``m`` shared blocks out of the
        pool into positions ``[0, m·blk)`` of a fresh scratch (donated),
        so chunked prefill resumes at the first uncached chunk."""
        key = (se, m)
        if key not in self._seed_fns:
            cfg = self.cfg
            ssh = self._scratch_specs(se)

            def seed_scratch(scratch, cache, bids):
                bk = jnp.take(cache["kp"], bids, axis=1)
                bv = jnp.take(cache["vp"], bids, axis=1)
                return seed_scratch_from_blocks(cfg, scratch, bk, bv)

            self._seed_fns[key] = jax.jit(
                seed_scratch, in_shardings=(ssh, self._cache_sh, None),
                out_shardings=ssh, donate_argnums=(0,))
        return self._seed_fns[key]

    def _blocks_fn(self, s: int):
        """Jitted scratch→pool-blocks conversion (the paged finish)."""
        if s not in self._blocks_fns:
            cfg, max_seq, blk = self.cfg, self.srv.max_seq, self._blk

            def scratch_to_pool(scr):
                return scratch_to_blocks(cfg, scr, blk, cache_len=max_seq)

            self._blocks_fns[s] = jax.jit(scratch_to_pool,
                                          donate_argnums=(0,))
        return self._blocks_fns[s]

    def _block_writer(self, n_write: int):
        if n_write not in self._block_writers:
            self._block_writers[n_write] = build_block_write_step(
                self.cfg, self.mesh, self.srv.max_batch, self.srv.max_seq,
                self._blk, self._n_blocks, n_write)
        return self._block_writers[n_write]

    def _install_paged(self, i: int, req: Request, blocks):
        """Push the slot's private blocks into the pool and install its
        table row — then register every full-block prompt prefix with the
        prefix cache (nested entries, so future prompts match the longest
        common prefix block-chain)."""
        bk, bv, slot_pos_row, pos_row = blocks
        m = req._shared
        table = jnp.asarray(req._blocks, jnp.int32)
        self.cache = self._block_writer(self._npb - m).fn(
            self.cache, bk[:, m:], bv[:, m:], table[m:], table,
            slot_pos_row, pos_row, jnp.int32(i))
        s = int(req.prompt.size)
        if self._share_ok(s):
            for m2 in range(1, self._m_max(s) + 1):
                self.pool.cache_insert(
                    req.prompt[:m2 * self._blk].tobytes(),
                    req._blocks[:m2])

    # -- prefill scheduling ---------------------------------------------------

    def _chunk_bundle(self, se: int, lo: int, c: int,
                      n_fe: Optional[int] = None):
        """Chunk-step bundle for a size-``se`` scratch at offset ``lo``.
        ``n_fe``: frontend rows riding this chunk (the vlm fe-row slice,
        or the full frame tensor on the encdec chunk 0)."""
        key = (se, lo, c, n_fe)
        if key not in self._chunk_bundles:
            wf = ((n_fe, self.cfg.frontend_dim) if n_fe is not None
                  else None)
            self._chunk_bundles[key] = build_prefill_chunk_step(
                self.cfg, self.mesh, self.scfg, batch=1, prompt_len=se,
                lo=lo, chunk_len=c, with_frontend=wf)
        return self._chunk_bundles[key]

    def _scratch_init(self, se: int):
        """Jitted scratch allocator, sharded like the chunk step's input."""
        if se not in self._scratch_inits:
            cfg = self.cfg

            def scratch_init():
                return init_prefill_scratch(cfg, 1, se)

            self._scratch_inits[se] = jax.jit(
                scratch_init, out_shardings=self._scratch_specs(se))
        return self._scratch_inits[se]

    def _bulk_fn(self, s: int):
        if s not in self._bulk_bundles:
            wf = ((self.cfg.frontend_tokens, self.cfg.frontend_dim)
                  if self.cfg.frontend else None)
            self._bulk_bundles[s] = build_prefill_step(
                self.cfg, self.mesh, self.scfg, batch=1, seq_len=s,
                with_frontend=wf, cache_len=self.srv.max_seq).fn
        return self._bulk_bundles[s]

    def _finish_fn(self, s: int):
        """Jitted scratch→ring-cache conversion, sharded like the slot
        writer's slot-cache input."""
        if s not in self._finish_fns:
            cfg, max_seq = self.cfg, self.srv.max_seq

            def scratch_to_ring(scr):
                return scratch_to_cache(cfg, scr, cache_len=max_seq)

            self._finish_fns[s] = jax.jit(scratch_to_ring,
                                          out_shardings=self._slot_sh)
        return self._finish_fns[s]

    def _emit_first_token(self, i: int, req: Request, logits):
        """Sample the request's first decode token from the final prefill
        logits and move the slot to the decode phase.  ``first_token`` is
        stamped *here* — after the id has been computed and fetched, i.e.
        at the first decode token, not at prefill completion."""
        with _span(SPAN_FIRST_TOKEN, rid=req.rid):
            tok = int(jnp.argmax(logits[0], axis=-1))
        if req.first_token is None:
            # a re-admitted (recovered) request already stamped TTFT on
            # its genuine first token, pre-failure
            req.first_token = time.perf_counter()
        req.out_tokens.append(tok)
        req.phase = "decode"
        self._next_tok[i] = tok
        if (len(req.out_tokens) >= self.srv.max_new_tokens
                or tok == self.srv.eos_id):
            self._retire(i, req)

    def _prefill_tick(self):
        """Run at most one prefill chunk (or one bulk per-slot prefill) for
        the earliest-admitted slot still in the prefill phase — the
        admission work a server step interleaves between decode steps."""
        pending = [(req.rid, i, req) for i, req in enumerate(self.slots)
                   if req is not None and req.phase == "prefill"]
        if not pending:
            return
        _, i, req = min(pending)
        if req.prefill_start is None:
            # a recovered request keeps its first stamps
            req.prefill_start = time.perf_counter()
        s = int(req.prompt.size)
        se = self._eff_len(s)

        if not self._chunkable:
            with _span(SPAN_PREFILL, rid=req.rid, tokens=se):
                args = (self.params, jnp.asarray(req.prompt[None, :]))
                if self.cfg.frontend:
                    args += (jnp.asarray(req.frontend_embeds[None, :]),)
                cache1, logits = self._bulk_fn(s)(*args)
                if self._paged:
                    self._install_paged(i, req,
                                        cache_to_blocks(self.cfg, cache1,
                                                        self._blk))
                else:
                    self.cache = self.writer.fn(self.cache, cache1,
                                                jnp.int32(i))
            self._emit_first_token(i, req, logits)
            return

        cuts = prefill_chunk_cuts(se, chunk_len=self._eff_chunk)
        lo, hi = cuts[req._cursor]
        with _span(SPAN_PREFILL, rid=req.rid, tokens=hi - lo):
            toks = jnp.asarray(req.prompt[None, :])
            cfg = self.cfg
            if cfg.family == "encdec":
                # frames feed the encoder exactly once, on chunk 0
                n_fe = cfg.frontend_tokens if lo == 0 else None
                fe = (jnp.asarray(req.frontend_embeds[None, :])
                      if lo == 0 else None)
                tok_slice = toks[:, lo:hi]
            elif cfg.frontend:
                # vlm: frontend rows prefix the token rows of the scratch —
                # slice each exactly as the bulk concat lays them out
                ft = cfg.frontend_tokens
                n_fe = max(0, min(hi, ft) - lo) if lo < ft else None
                fe = (jnp.asarray(req.frontend_embeds[None, lo:min(hi, ft)])
                      if n_fe else None)
                if n_fe == 0:
                    n_fe = None
                tok_slice = toks[:, max(0, lo - ft):max(0, hi - ft)]
            else:
                n_fe, fe = None, None
                tok_slice = toks[:, lo:hi]
            fn = self._chunk_bundle(se, lo, hi - lo, n_fe).fn
            args = (self.params, req._scratch, tok_slice)
            if n_fe is not None:
                args += (fe,)
            req._scratch, logits = fn(*args)
            req._cursor += 1
            if req._cursor < len(cuts):
                return                      # more chunks; decode proceeds
            if self._paged:
                blocks = self._blocks_fn(se)(req._scratch)
                req._scratch = None
                self._install_paged(i, req, blocks)
            else:
                cache1 = self._finish_fn(se)(req._scratch)
                req._scratch = None
                self.cache = self.writer.fn(self.cache, cache1, jnp.int32(i))
        self._emit_first_token(i, req, logits)

    def _retire(self, i: int, req: Request,
                now: Optional[float] = None):
        """The one retire path — finished, EOS, cancel, or timeout, at any
        phase.  Reclaims the unfinished admission scratch (a mid-prefill
        retire used to leak it), drops the slot's pool-block refs, and
        parks the row's block table so dead decode writes land in the
        slot's private parking block."""
        req.finished = time.perf_counter() if now is None else now
        req.phase = "done"
        req._scratch = None
        if self._paged and req._blocks:
            self.pool.release(req._blocks)
            req._blocks = []
            req._shared = 0
            self.cache = self._park_fn(self.cache, jnp.int32(i))
        self.done.append(req)
        self.slots[i] = None

    def cancel(self, rid: int) -> bool:
        """Abort a request wherever it is: queued → dropped; mid-prefill or
        decoding → retired through :meth:`_retire` (scratch and pool blocks
        reclaimed).  Returns whether the request was found in flight."""
        for q, req in enumerate(self.queue):
            if req.rid == rid:
                req.cancelled = True
                self.queue.pop(q)
                req.finished = time.perf_counter()
                req.phase = "done"
                self.done.append(req)
                return True
        for i, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                req.cancelled = True
                self._retire(i, req)
                return True
        return False

    # -- decode loop ----------------------------------------------------------

    def fail_decode_rank(self, rank: int, n_ranks: Optional[int] = None):
        """Single-rank form of :meth:`fail_decode_ranks`."""
        return self.fail_decode_ranks([rank], n_ranks)

    def fail_decode_ranks(self, ranks, n_ranks: Optional[int] = None):
        """Survive the loss of decode ranks ``ranks``: drain and re-admit.

        The pool's block ids are partitioned contiguously across
        ``n_ranks`` decode ranks (default: the mesh's data extent — the
        replicated rows that host pool shards).  Losing a rank loses its
        id span: the pool quarantines it (:meth:`BlockPool.fail_partition`,
        conservation holds throughout), prefix-cache entries pinning lost
        blocks are purged, and every in-flight slot whose table touches
        the span — or whose parking block died — is *drained*: blocks
        released, scratch dropped, and the request re-queued at the front
        with a **replay prompt** of ``prompt + tokens emitted so far``.
        Greedy decode is deterministic and prefill ≡ decode (asserted
        repo-wide), so the re-admitted continuation emits exactly the
        tokens the unfailed run would have; committed prefix blocks on
        surviving ranks come back copy-on-write through the prefix cache,
        so only the lost tail actually re-prefills.  Rows whose parking
        block died are retired from capacity (``_dead_slots``).

        In this single-process simulation the lost span's *array data* is
        physically intact — what the failure costs is re-prefill work and
        pool capacity, which is exactly what ``netmodel`` prices
        (``recovery_time``) and ``stats()`` reports.

        Several ranks lost in the same lease window are excluded in
        **one** sweep (:meth:`BlockPool.fail_partitions`): one free-list
        rebuild, one victim drain, one conservation check — never N
        sequential recoveries.
        """
        assert self._paged, \
            "decode-rank loss recovery needs the paged pool (paged=True)"
        if n_ranks is None:
            n_ranks = max(1, int(self.mesh.shape.get("data", 1)))
        dead = sorted({min(int(r), n_ranks - 1) for r in ranks})
        lost = self.pool.fail_partitions(dead, n_ranks)
        self._dead_slots |= {i for i in range(self.srv.max_batch)
                             if i in lost and i < self.pool.reserved}
        victims = [(req.rid, i, req) for i, req in enumerate(self.slots)
                   if req is not None
                   and (i in self._dead_slots or set(req._blocks) & lost)]
        drained = []
        for _, i, req in sorted(victims):
            if req._blocks:
                self.pool.release(req._blocks)
                req._blocks, req._shared = [], 0
            req._scratch = None
            req._cursor = 0
            if req.out_tokens:
                # replay = everything the request has already established;
                # re-prefilling it reproduces the decode state bit-exactly
                req.prompt = np.concatenate(
                    [req.prompt,
                     np.asarray(req.out_tokens, np.int32)]).astype(np.int32)
            req.phase = "queued"
            req._recovered = True
            self.slots[i] = None
            if i not in self._dead_slots:
                self.cache = self._park_fn(self.cache, jnp.int32(i))
            drained.append(req)
            self.recoveries += 1
        self.queue = drained + self.queue   # victims re-admit first
        self.pool.check_conservation()
        return len(drained)

    def admit_decode_rank(self, rank: int, n_ranks: Optional[int] = None):
        """Scale the pool back out: re-admit decode rank ``rank``'s span.

        The membership detector drives this at an epoch boundary when a
        joiner (a recovered victim, or fresh capacity) announces itself.
        Quarantined ids in the span return to the free list
        (:meth:`BlockPool.restore_partition` — admission capacity grows
        back by exactly that many blocks), and batch rows whose parking
        block was in the span rejoin capacity: they are re-parked (their
        tables point at their own parking block again) and removed from
        ``_dead_slots``.  Returns the number of block ids restored.
        """
        assert self._paged, \
            "decode-rank admission needs the paged pool (paged=True)"
        if n_ranks is None:
            n_ranks = max(1, int(self.mesh.shape.get("data", 1)))
        span = self.pool.restore_partition(min(int(rank), n_ranks - 1),
                                           n_ranks)
        revived = {i for i in self._dead_slots if i in span}
        for i in sorted(revived):
            self.cache = self._park_fn(self.cache, jnp.int32(i))
        self._dead_slots -= revived
        self.pool.check_conservation()
        return len(span)

    def step(self):
        """One scheduler tick: admit, run one prefill chunk, decode.

        With a :class:`~repro.runtime.faults.FaultPlan` attached, scripted
        kills are delivered here at host level (compiled steps never
        re-enter the conduit) and handled in place via
        :meth:`fail_decode_rank` — serving absorbs the loss instead of
        propagating it.  With a
        :class:`~repro.runtime.membership.MembershipService` attached,
        the *detector* decides instead: the plan only suppresses victims'
        leases, the service declares at a lease deadline, and its
        :class:`~repro.runtime.membership.MembershipEvent` drives
        :meth:`fail_decode_ranks` (one call per epoch bump, however many
        ranks died) and :meth:`admit_decode_rank` (scale-out joins)."""
        with _span(SPAN_STEP):
            self._ticks += 1
            if self.membership is not None:
                ev = self.membership.on_step(self._ticks)
                if ev is not None:
                    n = self.membership.n_ranks
                    if ev.died:
                        self.fail_decode_ranks(ev.died, n_ranks=n)
                    for r in ev.joined:
                        self.admit_decode_rank(r, n_ranks=n)
            elif self.fault_plan is not None:
                from repro.core.conduit import RankFailure
                try:
                    self.fault_plan.on_step(self._ticks, "serve_step")
                except RankFailure as e:
                    dead = e.rank if e.rank is not None else 0
                    self.fault_plan.repair(dead)
                    self.fail_decode_rank(dead)
            with _span(SPAN_ADMIT):
                self._admit()
            self._prefill_tick()
            if not any(r is not None and r.phase == "decode"
                       for r in self.slots):
                return
            with _span(SPAN_DECODE):
                toks = jnp.asarray(self._next_tok)
                self.cache, ids = self.bundle.fn(self.params, self.cache,
                                                 toks)
            with _span(SPAN_FETCH):
                choice = np.asarray(ids)    # ONE stacked host transfer
            now = time.perf_counter()
            with _span(SPAN_EMIT):
                for i, req in enumerate(self.slots):
                    if req is None or req.phase != "decode":
                        continue
                    tok = int(choice[i])
                    req.out_tokens.append(tok)
                    self._next_tok[i] = tok
                    if (len(req.out_tokens) >= self.srv.max_new_tokens
                            or tok == self.srv.eos_id):
                        self._retire(i, req, now)

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return steps

    # -- metrics ---------------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        lat = [r.finished - r.submitted for r in self.done if r.finished]
        ttft = [r.first_token - r.submitted for r in self.done
                if r.first_token]
        itl = [(r.finished - r.first_token) / (len(r.out_tokens) - 1)
               for r in self.done
               if r.finished and r.first_token and len(r.out_tokens) > 1]
        toks = sum(len(r.out_tokens) for r in self.done)
        wall = (max(r.finished for r in self.done)
                - min(r.submitted for r in self.done)) if self.done else 0.0
        out = {
            "requests": len(self.done),
            "tokens": toks,
            "throughput_tok_s": toks / wall if wall else 0.0,
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "mean_ttft_s": float(np.mean(ttft)) if ttft else 0.0,
            "mean_itl_s": float(np.mean(itl)) if itl else 0.0,
            # admission mode is part of the serving surface: no arch may
            # fall back to bulk without this signal (and a build warning)
            "admission_mode": (f"chunked({self._eff_chunk})"
                               if self._chunkable else "bulk"),
            "admission_fallback": self._fallback_reason,
        }
        if self._paged:
            out.update({
                "prefix_hits": float(self.prefix_hits),
                "prefix_misses": float(self.prefix_misses),
                "pool_evictions": float(self.pool.evictions),
                "pool_free_blocks": float(self.pool.free_blocks),
                "recoveries": float(self.recoveries),
                "reprefilled_tokens": float(self.reprefilled_tokens),
                "lost_blocks": float(self.pool.lost_blocks),
                "quarantined_blocks": float(self.pool.quarantined_blocks),
                "dead_slots": float(len(self._dead_slots)),
            })
        return out


def drive_arrivals(server: Server, prompts, every: int,
                   max_steps: int = 10_000) -> int:
    """Run ``server`` under synthetic arrivals: one prompt up front, one
    more every ``every`` scheduler ticks, until the queue drains.  Each
    item is a prompt array, or a ``(prompt, frontend_embeds)`` pair for
    frontend archs (vlm patches / encdec frames).  The one arrival loop
    both the CLI (``launch/serve.py --arrive-every``) and the measured
    benchmark section (``benchmarks/serve_bench.py``) drive, so they
    always measure the same workload.  Returns the tick count.
    """
    def _submit(item):
        if isinstance(item, tuple):
            server.submit(item[0], item[1])
        else:
            server.submit(item)

    pending = list(prompts)
    _submit(pending.pop(0))
    steps = 0
    while ((pending or server.queue
            or any(s is not None for s in server.slots))
           and steps < max_steps):
        server.step()
        steps += 1
        if pending and steps % max(1, every) == 0:
            _submit(pending.pop(0))
    return steps
