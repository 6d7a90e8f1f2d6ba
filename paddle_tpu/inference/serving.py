"""ServingEngine — continuous batching over a paged KV-cache block pool.

ref (capability): the reference serving stack's block_multihead_attention
paged caches + its request-level serving loop; design lineage: Orca
iteration-level scheduling over vLLM PagedAttention pages. PR 1's
DecodeEngine made a SINGLE static batch fast (one fused dispatch per
window, donated caches, zero steady-state retraces) but a request that
finishes early holds its padded slot until the whole batch drains and
new requests wait for a full generate() call. This module schedules at
the ITERATION level instead:

  1. `BlockAllocator` owns a pool of fixed-size KV pages shared by all
     in-flight requests (free-list alloc/free, page ids recycled
     LIFO, page 0 reserved as the scratch page inactive rows write to).
     The device pool arrays are allocated ONCE per engine
     (`model.init_paged_cache`) and never resized — allocation is pure
     id bookkeeping, so admitting/retiring a request moves zero cache
     bytes.

  2. `ServingEngine.step()` is one scheduler iteration over a FIXED-SLOT
     in-flight batch (`max_slots` rows, shapes never change):
       - retire/admit: finished rows already freed their pages; queued
         requests prefill into freshly allocated pages through the
         bucketed `_paged_prefill`. An admission batch is sized by a
         TOKEN budget, not by the slot count: `PREFILL_TOKENS // bucket`
         rows (`ServingEngine._prefill_rows`), so each bucket still has
         ONE row count and one compilation, and what does not fit one
         batch prefills in further dispatches of the same step;
       - decode: ALL slots advance `decode_window` tokens in ONE fused
         jitted dispatch (`_serve_window`: a lax.scan whose single-token
         steps route the model through `cached_attention`'s
         PagedKVCache branch — the pallas paged kernel on TPU, a gather
         reference elsewhere), with ONE host sync per window to read
         the emitted tokens.
     Because slot count, page-pool shape, and window length are static,
     requests joining and leaving the batch never change a traced
     shape: steady-state serving is ZERO retraces (`trace_counts()`,
     shared with inference.engine, proves it; bench.py gates on it).

  3. Preemption: when the pool runs out of pages mid-decode, the
     lowest-priority (then youngest) in-flight request is EVICTED — its
     pages are freed, its prompt + generated prefix goes back to the
     queue — and later resumes by re-prefilling prompt+prefix (greedy
     decoding makes the resumed stream exactly the uninterrupted one).

  4. Prefix caching + chunked prefill (both opt-in, both bit-equal;
     docs/serving.md#prefix-caching-and-chunked-prefill):
     `prefix_cache=True` shares full pages of identical prompt
     prefixes across requests through the allocator's refcounted
     content-hash index (copy-on-write on the one page a
     full-coverage hit must rewrite; refcount-0 pages park on a
     hittable LRU) and prefills only the unshared suffix;
     `prefill_chunk=N` admits long prompts as <=N-token chunks fused
     with the decode window (`_serve_chunk_step`), so one 8k-token
     arrival never stalls in-flight streams for a whole-prompt
     prefill. A chunked request occupies its slot but emits nothing
     until its last chunk commits — then its first window runs in the
     SAME dispatch, preserving monolithic semantics exactly.

  5. Tensor parallelism (docs/serving.md#tp-sharded-serving):
     `ServingEngine(model, tp=4)` (or `mesh=serving_mesh(4)`) runs the
     SAME scheduler loop against TP-sharded device state — page pools
     carry a NamedSharding splitting the kv-head dim over the 'tp'
     axis, the fused dispatches run the llama forward through the
     megatron column->row layout (GSPMD inserts the per-layer
     all-reduces; the `serving/*` shardlint suites gate the census
     against a declared budget), and block tables, slot/context
     mirrors, and ALL host scheduler state stay replicated — greedy
     streams are bit-equal to the single-device engine, zero
     steady-state retraces included.

  6. Speculative + quantized + per-request-sampled serving
     (docs/serving.md#speculative-and-quantized-serving):
     `ServingEngine(model, draft=..., num_draft_tokens=k)` turns every
     non-chunk iteration into a fused draft-propose / target-verify
     window (the DecodeEngine's shared draft contract over the paged
     pool: per-slot accept counts make the step output ragged,
     committed through the per-row kv_write_pos machinery) — greedy
     streams bit-equal to the non-speculative engine, sampled rows
     rejection-sampled distribution-correct. `kv_cache_dtype='int8'`
     backs the slots with int8 paged pools (QuantPagedKVCache:
     per-row scales ride with the pages, so quantization survives
     prefix sharing, CoW, preemption, and restore bit-identically) —
     double the effective KV capacity. Sampling params (temperature,
     top-k/p, per-request seed) are SLOT STATE, uploaded as device
     data: a mixed greedy/sampled/speculative workload shares one
     batch with zero retraces as the mix changes.

Engine-level sampling config provides per-request defaults; greedy
(temperature=0) is the parity-tested path: per-request outputs are
exactly `DecodeEngine.generate`'s batch-1 outputs. See docs/serving.md
for the scheduler loop and the block-table layout.

Resilience (docs/serving.md#resilience): requests carry optional
deadlines and can be cancelled; `submit()` load-sheds against a
bounded queue (`QueueFull`) and a pool-pressure watermark pauses
admission before OutOfBlocks can force a preemption storm; a request
that cannot be served — pool still dry after maximal preemption, or a
fault injected during its prefill — FAILS alone (`state='failed'`,
pages freed) while the rest of the batch keeps decoding; and
`snapshot()`/`restore()` capture the host-authoritative scheduler
state so a supervisor can rebuild a crashed replica (warmed from a
PR-7 AOT artifact) and finish every stream bit-equal to an
uninterrupted run. Failure paths are exercised on purpose through the
`paddle_tpu.testing.faults` seams wired at the host boundaries below.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import hashlib
import heapq
import inspect
import itertools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.moe import ROUTING_FIELDS
from ..models.generation import layer_kinds, pad_lanes, pool_rows_set
from ..observability import journal as _journal
from ..observability import metrics as _obs
from ..observability import timeseries as _obs_ts
from ..observability import tracing as _obs_trace
# top-level like the rest of the observability imports: the package
# __init__ already pulls watchdog/httpd eagerly, so deferring these
# would save nothing and only hide the dependency
from ..observability import watchdog as _obs_wd
from ..observability.httpd import start_ops_server as _start_ops_server
from ..testing import faults as _faults
from ._schema import KV_BLOB_KIND, SNAPSHOT_SCHEMA
from .engine import (COMPILE_CACHE, DEFAULT_BUCKETS, _count_trace,
                     bucket_length, total_traces, trace_counts)

# Padded positions one admission-prefill dispatch may hold: a batch at
# bucket Sb is `PREFILL_TOKENS // Sb` rows wide (at least 1, at most
# max_slots — `ServingEngine._prefill_rows`, the one place that knows).
# A few times the chip's ridge point (v5e: 197e12 FLOP/s over 819e9 B/s
# is ~240 tokens a dispatch for bf16 weights): a dispatch this size is
# already compute-bound, so every further padded row costs what a real
# one does and buys nothing. A constant, not an option: the row count is
# a traced shape, so it keys every compiled admission program and AOT
# artifact (`_geometry`, `aot_config`); the value was settled by a chip
# sweep over {512, 1024, 2048} (PERF.md section 6, PR 26).
PREFILL_TOKENS = 1024


class OutOfBlocks(RuntimeError):
    """The block pool cannot satisfy an allocation. The ServingEngine
    catches this and preempts; direct BlockAllocator users see it
    raised deterministically (need/have in the message)."""


class QueueFull(RuntimeError):
    """`submit()` rejected the request: the admission queue is at
    `max_queue` and the shed policy found nothing to displace. The
    deterministic load-shedding signal — callers back off and retry,
    instead of the queue growing without bound until preemption storms
    or host OOM kill every in-flight request."""


class InvalidSamplingParams(ValueError):
    """`submit()` rejected a request's per-request sampling params
    (temperature < 0, top_p outside (0, 1]) BEFORE the prompt copy was
    paid — the typed pre-admission validation signal (top_k is clamped
    to the vocab instead, mirroring `filter_logits`'s HF semantics)."""


class RequestError(RuntimeError):
    """Base for terminal non-success request states, raised by
    `result()`. Carries `rid`, the terminal `state`, a human `reason`,
    and (for failures) the original `error` object."""

    state = 'unknown'

    def __init__(self, rid, reason, error=None):
        super().__init__(f'request {rid} {self.state}: {reason}')
        self.rid = rid
        self.reason = reason
        self.error = error


class RequestFailed(RequestError):
    """The request is unservable (pool can never fit it even drained,
    or a fault hit its prefill/admission). `error` is the underlying
    exception (a repr string after snapshot/restore)."""

    state = 'failed'


class RequestExpired(RequestError):
    """The request's `deadline_s` passed before it finished (checked
    at the per-window commit sync and at admission)."""

    state = 'expired'


class RequestCancelled(RequestError):
    """The request was cancelled (`cancel(rid)`) or shed from a full
    queue by a higher-priority arrival (`reason` says which)."""

    state = 'cancelled'


class PageKindsUnsupported(NotImplementedError):
    """A model that keeps more than one kind of KV page
    (`GenerationMixin.page_kinds`) was asked to serve with an option the
    second kind has no path through yet. Raised at construction, naming
    the option, so that nothing is half-run."""


class BlockAllocator:
    """Free-list allocator over a fixed pool of KV-cache pages, with
    per-page REFCOUNTS and a content-hash PREFIX INDEX (vLLM-style
    prefix caching — ROADMAP item 2).

    Pure id bookkeeping: the device page pools live in the engine and
    are NEVER reallocated — alloc/free hand out integer page ids, so
    the pool stays pointer-stable across any alloc/free sequence. Page
    0 is reserved as the scratch page (inactive/frozen slots write
    there), so usable capacity is num_blocks - 1 and every handed-out
    id is >= 1. Freed ids are reused LIFO (most-recently-freed first —
    deterministic, and the hottest pages stay hot).

    Prefix caching: a page holding a FULL block of prompt-token KV can
    be bound to its chain hash (`register_prefix`); a later request
    whose prompt starts with the same token pages walks the chain
    (`match_prefix`) and takes references on the pages (`share` —
    refcount++ instead of alloc: the KV bytes are reused and the
    prefill compute for those tokens is skipped). A freed page whose
    refcount hits zero parks on an LRU of CACHED pages (still indexed,
    still hittable) instead of the free list; `alloc` harvests the LRU
    oldest-first only once the free list runs dry, so caching never
    shrinks the allocatable pool. `cow` swaps a writer's reference on
    a shared page for a private fresh page (copy-on-write — the device
    row copy is the engine's job; the allocator only moves ids)."""

    def __init__(self, num_blocks, block_size):
        num_blocks = int(num_blocks)
        if num_blocks < 2:
            raise ValueError(
                f'num_blocks must be >= 2 (page 0 is the reserved '
                f'scratch page), got {num_blocks}')
        self.num_blocks = num_blocks
        self.block_size = int(block_size)
        # LIFO stack, low ids on top: the first alloc after init hands
        # out 1, 2, ... in order (deterministic, test-friendly)
        self._free = list(range(num_blocks - 1, 0, -1))
        self._ref: dict = {}             # page -> refcount (held pages)
        # prefix index: chain hash <-> page, plus the refcount-0 cached
        # pages in least-recently-freed-first order (python dicts are
        # insertion-ordered, so "pop oldest" is one iteration step and
        # "re-free" reinserts at the tail)
        self._index: dict = {}           # chain hash -> page
        self._hash_of: dict = {}         # page -> chain hash (indexed)
        self._cached: dict = {}          # page -> None (LRU, oldest first)
        self.alloc_count = 0
        self.free_count = 0
        self.high_water = 0
        self.cow_count = 0               # copy-on-write page swaps
        self.prefix_shares = 0           # pages handed out via share()
        self.prefix_evictions = 0        # cached pages harvested by alloc
        # device bytes one page costs across ALL layers (k + v), set by
        # the owning engine from the real pool arrays (the allocator
        # itself only moves ids); stats() reports real-unit pool sizes
        # once it is known
        self.bytes_per_page = None
        # which scheduler phase is allocating ('admit' / 'window' /
        # 'cow' / None for direct users) — set by the owning engine
        # around its call sites purely so fault scripts can target one
        # phase ("pool dries mid-decode but admission still works")
        self.phase = None
        # which flight recorder the pool events land in — set by a
        # private-registry engine so N in-process replicas' alloc/free
        # trails never interleave (None = the process journal)
        self.journal = None

    def _record(self, kind, **fields):
        (self.journal if self.journal is not None
         else _journal.JOURNAL).record(kind, **fields)

    @property
    def usable(self):
        return self.num_blocks - 1

    def available(self):
        """Pages an alloc() can hand out: the free list plus the
        refcount-0 cached prefix pages (reclaimable on demand — the
        prefix cache never shrinks the allocatable pool)."""
        return len(self._free) + len(self._cached)

    def in_use(self):
        return len(self._ref)

    def cached(self):
        """Refcount-0 prefix pages parked on the LRU."""
        return len(self._cached)

    def shared(self):
        """Held pages with MORE than one reference."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, page):
        """Live references on `page` (0 = cached or free)."""
        return self._ref.get(page, 0)

    def utilization(self):
        """Held fraction of the usable pool (scratch page excluded;
        cached refcount-0 pages are reclaimable and do not count)."""
        return len(self._ref) / max(self.usable, 1)

    def alloc(self, n):
        """n page ids, or OutOfBlocks (the pool is untouched on
        failure — no partial allocation to unwind). When the free list
        alone cannot cover, refcount-0 cached prefix pages are evicted
        oldest-first (their index bindings drop) to make up the rest."""
        n = int(n)
        if n < 0:
            raise ValueError(f'cannot allocate {n} pages')
        if _faults.ACTIVE is not None:   # pre-check: alloc is per-page-op
            _faults.fire('alloc', n=n, free=len(self._free),
                         phase=self.phase)
        if n > len(self._free) + len(self._cached):
            raise OutOfBlocks(
                f'need {n} page(s), {len(self._free) + len(self._cached)} '
                f'free ({len(self._ref)}/{self.usable} in use)')
        harvest = max(0, n - len(self._free))
        if harvest:
            victims = list(itertools.islice(self._cached, harvest))
            if _faults.ACTIVE is not None:
                # seams fire BEFORE any mutation, so a scripted
                # prefix-evict fault leaves the pool untouched
                for p in victims:
                    _faults.fire('prefix_evict', page=p, phase=self.phase)
            for p in victims:
                self._unindex(p)
                del self._cached[p]
                self._free.append(p)
                self._record('prefix_evict', page=p, phase=self.phase)
            self.prefix_evictions += harvest
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self.alloc_count += n
        self.high_water = max(self.high_water, len(self._ref))
        self._record('alloc', n=n, phase=self.phase,
                     free=len(self._free))
        return pages

    def free(self, pages):
        """Drop one reference per listed page. The last reference
        either returns the page to the free list or — when the page is
        prefix-indexed — parks it on the cached LRU (still hittable).
        Over-freeing and foreign ids raise — both are allocator-
        corruption bugs worth failing on."""
        pages = list(pages)
        if _faults.ACTIVE is not None:   # pre-check: free is per-page-op
            _faults.fire('free', pages=pages)
        drops: dict = {}
        for p in pages:
            drops[p] = drops.get(p, 0) + 1
        for p, k in drops.items():
            if self._ref.get(p, 0) < k:
                raise ValueError(
                    f'page {p} is not currently allocated '
                    f'(double-free or foreign id)')
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p]:
                continue
            del self._ref[p]
            if p in self._hash_of:
                self._cached[p] = None       # LRU tail (newest)
            else:
                self._free.append(p)
        self.free_count += len(pages)
        self._record('free', n=len(pages))

    # -- prefix index ------------------------------------------------------

    def match_prefix(self, hashes):
        """Pages for the longest indexed leading run of `hashes`.
        Every returned page is held or cached RIGHT NOW — `share()`
        them before relying on the ids (an interleaved alloc could
        harvest a cached one)."""
        pages = []
        for h in hashes:
            p = self._index.get(h)
            if p is None:
                break
            pages.append(p)
        return pages

    def share(self, pages):
        """Take one more reference on each page (a prefix-cache hit):
        held pages refcount++, cached pages revive off the LRU.
        Sharing a free page is corruption and raises (nothing is
        mutated on failure)."""
        pages = list(pages)
        for p in pages:
            if p not in self._ref and p not in self._cached:
                raise ValueError(
                    f'page {p} is neither held nor cached — cannot share')
        for p in pages:
            if p in self._cached:
                del self._cached[p]
                self._ref[p] = 1
            else:
                self._ref[p] += 1
        self.prefix_shares += len(pages)
        self.high_water = max(self.high_water, len(self._ref))
        self._record('share', n=len(pages))
        return pages

    def register_prefix(self, page, h):
        """Bind chain hash `h` to a held page whose FULL block of
        prompt-token KV has been written. First writer wins: when the
        hash is already bound (a concurrent request computed the same
        block) the existing binding stays and this page simply remains
        unindexed. Returns True when the binding was recorded."""
        if page not in self._ref:
            raise ValueError(f'page {page} is not allocated')
        if h in self._index:
            return False
        self._index[h] = page
        self._hash_of[page] = h
        return True

    def cow(self, page):
        """Copy-on-write: hand the caller a private fresh page id for
        shared/indexed `page`. The caller must hold a reference on
        `page` and KEEPS it — that reference is the copy-pin: until
        the device rows are actually copied old -> new (the engine
        defers the copy into its next fused dispatch), freeing it
        would park an indexed source on the harvestable LRU, where a
        same-step allocation could hand it to another request whose
        prefill overwrites it BEFORE the copy reads it. Free the pin
        only once the copy has landed. Fires the alloc seam with
        phase='cow' so fault scripts can target exactly this path; on
        failure nothing changes."""
        if page not in self._ref:
            raise ValueError(f'page {page} is not allocated — cannot CoW')
        prev, self.phase = self.phase, 'cow'
        try:
            new = self.alloc(1)[0]
        finally:
            self.phase = prev
        self.cow_count += 1
        self._record('cow', src=page, new=new)
        return new

    def _unindex(self, page):
        h = self._hash_of.pop(page, None)
        if h is not None and self._index.get(h) == page:
            del self._index[h]

    def stats(self):
        s = {
            'num_blocks': self.num_blocks,
            'block_size': self.block_size,
            'in_use': self.in_use(),
            'free': self.available(),
            'utilization': round(self.utilization(), 4),
            'high_water': self.high_water,
            'allocs': self.alloc_count,
            'frees': self.free_count,
        }
        prefix = {
            'shared_pages': self.shared(),
            'cached_pages': len(self._cached),
            'indexed_pages': len(self._hash_of),
            'cow_pages': self.cow_count,
            'shares': self.prefix_shares,
            'evictions': self.prefix_evictions,
        }
        if self.bytes_per_page:
            # real units: page counts x per-page KV bytes across all
            # layers and both of k/v, at the pool dtype — what an HBM
            # budget is actually written in
            bpp = int(self.bytes_per_page)
            s['bytes_per_page'] = bpp
            s['bytes_total'] = self.num_blocks * bpp
            s['bytes_in_use'] = self.in_use() * bpp
            s['bytes_high_water'] = self.high_water * bpp
            prefix['bytes_shared'] = prefix['shared_pages'] * bpp
            prefix['bytes_cached'] = prefix['cached_pages'] * bpp
            prefix['bytes_cow'] = prefix['cow_pages'] * bpp
        s['prefix'] = prefix
        return s


def prompt_page_hashes(prompt, block_size):
    """Chain hashes for the FULL pages of `prompt` (one 16-byte
    blake2b digest per `block_size` tokens; each digest chains the
    previous one, so hash k covers the whole prefix through page k —
    a hit on page k implies the entire leading context matches). ONE
    batched token->bytes conversion covers the whole prompt — the
    admission hot path never converts per page in a loop (the
    tracelint host-sync discipline)."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    n = len(prompt) // block_size
    if not n:
        return []
    raw = np.ascontiguousarray(prompt[:n * block_size]).tobytes()
    step = 4 * block_size                 # int32 tokens
    out = []
    h = b'paddle_tpu.prefix.v1'
    for i in range(n):
        h = hashlib.blake2b(h + raw[i * step:(i + 1) * step],
                            digest_size=16).digest()
        out.append(h)
    return out


class Request:
    """One serving request. `generated` accumulates committed tokens
    across admissions (a preempted request keeps its prefix and resumes
    by re-prefill over prompt + prefix).

    `times` is the lifecycle trail: (event, perf_counter) pairs stamped
    at arrival / enqueued / admitted / prefill_dispatch / first_token /
    window / preempted / finished — always at points the host already
    owns (submission, scheduling, the one per-window commit sync), so
    collecting them costs no device round trip. The engine rolls them
    into the registry's ttft/itl/queue-wait histograms.

    Terminal states are `finished` / `failed` / `expired` /
    `cancelled`: `result` holds the output ids (finished only),
    `reason` the human-readable cause and `error` the underlying
    exception (failed only). `deadline` is an absolute perf_counter
    instant armed at submit from `deadline_s`."""

    __slots__ = ('rid', 'prompt', 'max_new_tokens', 'priority', 'generated',
                 'seq', 'state', 'admit_seq', 'times', 'enqueued_at',
                 'deadline', 'reason', 'error', 'result', 'page_hashes',
                 'temperature', 'top_k', 'top_p', 'sample_seed',
                 'spec_next', 'journal')

    def __init__(self, rid, prompt, max_new_tokens, priority,
                 temperature=0.0, top_k=0, top_p=1.0, sample_seed=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.priority = int(priority)
        # per-request sampling params — SLOT STATE, not engine statics:
        # the engine uploads them as (SLOTS,) device data each window,
        # so a batch mixing greedy/top-k/nucleus rows never retraces.
        # `sample_seed` keys the stateless per-token PRNG chain (rid by
        # default — deterministic, and it rides snapshot/restore so
        # resumed sampled streams stay bit-equal). `spec_next` is the
        # speculative window's carried next-token choice (the verify's
        # committed pick, incl. the rejection resample), persisted so
        # preemption/restore resumes mid-stream bit-equal.
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.sample_seed = int(sample_seed if sample_seed is not None
                               else rid)
        self.spec_next = None
        self.generated: list = []
        # which flight recorder mark() writes to — the owning engine
        # re-binds it to its own journal before the first mark, so a
        # private-registry replica's request trails stay private
        # (None = the process journal)
        self.journal = None
        self.page_hashes = None  # full-prompt-page chain hashes, lazy
        self.seq = None          # arrival order, stamped by RequestQueue
        self.admit_seq = None    # last admission order (preemption ties)
        self.state = 'queued'
        self.times: list = []
        self.enqueued_at = None
        self.deadline = None     # absolute perf_counter instant, or None
        self.reason = None       # terminal cause (non-finished states)
        self.error = None        # underlying exception (failed only)
        self.result = None       # output ids (finished only)

    def mark(self, event, t=None, **fields):
        """Append one lifecycle timestamp (no-op while telemetry is
        off, so a disabled server keeps zero per-request overhead).
        Callers that already hold a fresh perf_counter (the window
        commit loop stamps every slot at one instant) pass it as `t`
        instead of re-reading the clock per request.

        Every mark is ALSO one flight-recorder event keyed by rid —
        `fields` carry the scheduler-decision context (slot, pages,
        reason, token counts) the journal's `trail(rid)` replays; the
        `times` list keeps only the (event, t) pairs the histograms
        roll up."""
        if _obs.enabled():
            t = time.perf_counter() if t is None else t
            self.times.append((event, t))
            (self.journal if self.journal is not None
             else _journal.JOURNAL).record(event, rid=self.rid, t=t,
                                           **fields)

    def when(self, event):
        """First timestamp for `event`, or None."""
        for e, t in self.times:
            if e == event:
                return t
        return None

    @property
    def remaining(self):
        return self.max_new_tokens - len(self.generated)

    @property
    def context_len(self):
        return len(self.prompt) + len(self.generated)


class RequestQueue:
    """Admission queue: higher `priority` first, FIFO within a
    priority. A preempted request keeps its original arrival seq, so it
    resumes ahead of later arrivals of the same priority.

    `remove()` is LAZY (cancel/shed mark the rid dead; the stale heap
    entry is discarded when it surfaces at peek/pop) so cancellation is
    O(1) and never reshuffles the heap under the scheduler."""

    def __init__(self):
        self._heap: list = []
        self._seq = itertools.count()
        self._dead: set = set()

    def push(self, req):
        if req.seq is None:
            req.seq = next(self._seq)
        if req.state != 'preempted':     # keep eviction observable
            req.state = 'queued'
        # queue-wait accounting starts here (covers first arrival AND
        # every preemption requeue — a resumed request waits again)
        req.enqueued_at = time.perf_counter()
        req.mark('enqueued', req.enqueued_at, state=req.state)
        heapq.heappush(self._heap, (-req.priority, req.seq, req))

    def remove(self, req):
        """Lazily drop a queued/preempted request (cancel / shed)."""
        self._dead.add(req.rid)

    def reset_seq(self, start):
        """Continue arrival order from `start` — restore() calls this
        after re-pushing a snapshot's requests (which keep their
        original seqs) so new submissions never tie or jump ahead of
        restored peers of equal priority."""
        self._seq = itertools.count(start)

    def _prune(self):
        while self._heap and self._heap[0][2].rid in self._dead:
            _, _, dropped = heapq.heappop(self._heap)
            self._dead.discard(dropped.rid)

    def peek(self):
        self._prune()
        return self._heap[0][2] if self._heap else None

    def pop(self):
        self._prune()
        return heapq.heappop(self._heap)[2]

    def __len__(self):
        return len(self._heap) - len(self._dead)

    def __iter__(self):
        """Live requests in pop order (snapshot serialization)."""
        return (r for _, _, r in sorted(self._heap)
                if r.rid not in self._dead)

    def live(self):
        """Live requests in heap (arbitrary) order, O(n). The
        submit-reject backpressure path scans the whole queue — the
        expiry sweep filters by deadline, the shed scan takes a min()
        — and neither needs __iter__'s O(n log n) pop-order sort."""
        return (r for _, _, r in self._heap if r.rid not in self._dead)


# ---------------------------------------------------------------------------
# Module-level compiled steps (the persistent jit cache, PR-1 style)
# ---------------------------------------------------------------------------

def _pin(x, *spec_entries):
    """Sharding pin for the fused serving dispatches: a
    `with_sharding_constraint` that degrades to identity when no mesh
    is active (the single-device engines trace through here with the
    graph unchanged). Under a TP mesh the pins keep every dispatch
    OUTPUT on the layout its matching input was uploaded with — pools
    kv-head-sharded over 'tp', everything host-facing replicated — so
    the jit cache key is stable from the very first call (warmup and
    live dispatch compile the same executable; zero steady-state
    retraces holds on the sharded engine exactly as it does on one
    chip)."""
    from ..distributed.mp_layers import sharding_constraint

    return sharding_constraint(x, *spec_entries)


def _pin_pages(pages):
    """Pin every page pool to the kv-head 'tp' split (identity without
    a mesh; clamps to replicated when kv_heads does not divide tp —
    the same GQA fallback `init_paged_cache` places with). Every field
    of both pool containers (PagedKVCache kp/vp, QuantPagedKVCache
    kp/vp/ks/vs) carries the kv-head dim at axis 1, so one spec pins
    them all."""
    return [type(pc)(*[_pin(f, None, 'tp') for f in pc]) for pc in pages]


def _pool_quant(pages):
    """Whether the page pools are int8 (QuantPagedKVCache — per-row
    scale fields ride along with the data pages)."""
    return hasattr(pages[0], 'ks')


def _tmp_cache(model, pages, K, Sb):
    """Throwaway contiguous temp cache for a fused multi-token body
    (admission prefill, chunk continuation, speculative verify), in the
    POOL's quantization world: plain bf16 (k, v) pairs for PagedKVCache
    pools; RowQuantKVCache for int8 pools — the forward then writes
    per-row-quantized rows and attends dequantized ones, so every value
    it sees is exactly the int8-roundtripped value a paged decode step
    sees. That shared world is what keeps int8 greedy streams bit-equal
    across monolithic prefill, chunked prefill, speculative windows,
    preemption re-prefill, and prefix-cache hits."""
    with jax.named_scope('attn/cache'):
        if _pool_quant(pages):
            from ..models.generation import RowQuantKVCache

            _, Hkv, _, D = pages[0].kp.shape
            z8 = jnp.zeros((K, Sb, Hkv, D), jnp.int8)
            zs = jnp.zeros((K, Sb, Hkv), jnp.float32)
            return [RowQuantKVCache(z8, z8, zs, zs) for _ in pages]
        return model.init_cache(K, Sb)


def _pool_scatter(pc, tmp_entry, pflat, sflat, take=None):
    """Scatter one layer's temp-cache rows into its page pool at
    (pflat, sflat) flat (page, slot) targets. `take` (K, S) optionally
    re-gathers a sub-range of the temp cache first (the chunk/verify
    bodies scatter only the rows they wrote, clamped in-range). Int8
    pools copy int8 bytes AND the per-row scales — no requantization,
    so the pool holds exactly what the temp-cache write produced."""
    with jax.named_scope('attn/cache'):
        if hasattr(pc, 'ks'):
            kq, vq, ks, vs = tmp_entry
            if take is not None:
                idx4 = take[:, :, None, None]
                idx3 = take[:, :, None]
                kq = jnp.take_along_axis(kq, idx4, axis=1)
                vq = jnp.take_along_axis(vq, idx4, axis=1)
                ks = jnp.take_along_axis(ks, idx3, axis=1)
                vs = jnp.take_along_axis(vs, idx3, axis=1)
            rows = (pflat.shape[0],) + kq.shape[2:]
            srows = (pflat.shape[0],) + ks.shape[2:]
            return type(pc)(
                pc.kp.at[pflat, :, sflat, :].set(kq.reshape(rows)),
                pc.vp.at[pflat, :, sflat, :].set(vq.reshape(rows)),
                pc.ks.at[pflat, :, sflat].set(ks.reshape(srows)),
                pc.vs.at[pflat, :, sflat].set(vs.reshape(srows)))
        k, v = tmp_entry
        if take is not None:
            idx4 = take[:, :, None, None]
            k = jnp.take_along_axis(k, idx4, axis=1)
            v = jnp.take_along_axis(v, idx4, axis=1)
        def rows(x, pool):
            """x's (K, S) rows flat, at the pool's width (a row wider than a
            lane tile is kept in whole tiles: `generation.lane_padded`)."""
            flat = x.reshape((pflat.shape[0],) + x.shape[2:])
            return pad_lanes(flat.astype(pool.dtype), pool.shape[-1])

        return type(pc)(pool_rows_set(pc.kp, pflat, sflat, rows(k, pc.kp)),
                        pool_rows_set(pc.vp, pflat, sflat, rows(v, pc.vp)))


def _pool_gather(pages, btabs, st, Sb):
    """Gather each row's committed prefix [0, st[b]) out of its pages
    into a contiguous temp cache of static length Sb (positions >=
    st read the scratch page — never attended, the per-row mask stops
    at the write position). Int8 pools gather int8 bytes + per-row
    scales into a RowQuantKVCache, so the continuation forward attends
    the SAME roundtripped values a paged decode step would."""
    from ..models.generation import RowQuantKVCache

    with jax.named_scope('attn/cache'):
        K = btabs.shape[0]
        bs = pages[0].kp.shape[2]
        maxb = btabs.shape[1]
        s = jnp.arange(Sb)
        blk = jnp.minimum(s // bs, maxb - 1)
        gpage = jnp.take_along_axis(
            btabs, jnp.broadcast_to(blk[None, :], (K, Sb)), axis=1)
        gpage = jnp.where(s[None, :] < st[:, None], gpage, 0)
        soff = jnp.broadcast_to((s % bs)[None, :], (K, Sb))
        if _pool_quant(pages):
            return [RowQuantKVCache(pc.kp[gpage, :, soff, :],
                                    pc.vp[gpage, :, soff, :],
                                    pc.ks[gpage, :, soff],
                                    pc.vs[gpage, :, soff])
                    for pc in pages]
        return [(pc.kp[gpage, :, soff, :], pc.vp[gpage, :, soff, :])
                for pc in pages]


# per-request sampling randomness is STATELESS: the key for one
# sampled event is fold_in(fold_in(PRNGKey(request seed), generated
# token index), sub-stream id). A resumed request (preemption requeue,
# snapshot/restore) re-derives exactly the keys the uninterrupted run
# used — sampled streams stay bit-equal with no carried key state.
_SUB_PROPOSE = 0      # sampling a token (decode windows, draft props)
_SUB_ACCEPT = 1       # the speculative accept coin
_SUB_RESAMPLE = 2     # the speculative rejection resample


def _row_keys(seed, gen, sub):
    """One PRNG key per batch row: fold the row's generated-token index
    and the sub-stream id into its request seed."""
    def one(s, n):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(s), n), sub)

    return jax.vmap(one)(jnp.asarray(seed, jnp.uint32),
                         jnp.asarray(gen, jnp.int32))


# What a batch's rows ask the sampler for (`_row_asks`): the per-row
# params with the rows that only take an argmax neutralised, and the
# three scalars the sampler's `lax.cond`s branch on.
_RowAsks = collections.namedtuple(
    '_RowAsks', 'temp topk topp any_sampled any_top_k any_top_p')


def _row_asks(temp, topk, topp, live):
    """The sampler does the work the LIVE rows ask for. A greedy row
    (temp == 0) or an empty slot asks for no filter, whatever top_k /
    top_p it carries (`submit(prompt, top_k=50)` at temperature 0 is
    legal): its params are neutralised here, its filtered dist is never
    consumed. The scalars say whether any row samples, any asks for the
    top-k filter, any for the nucleus: replicated device data that does
    not change inside a window, so a caller computes them ONCE, outside
    its scan, and the scan closes over them."""
    with jax.named_scope('sample'):
        sampled = (temp > 0) & live
        temp = jnp.where(sampled, temp, 0.0)
        topk = jnp.where(sampled, jnp.asarray(topk, jnp.int32), 0)
        topp = jnp.where(sampled, jnp.asarray(topp, jnp.float32), 1.0)
        return _RowAsks(temp, topk, topp, jnp.any(sampled),
                        jnp.any(topk > 0), jnp.any(topp < 1.0))


def _filtered_logits(lg, asks):
    """Per-row tempered and filtered float32 logits (rows with
    temp == 0 use temp 1). The filters the batch does not ask for are
    skipped on the device (`filter_logits_batched`)."""
    from ..models.generation import filter_logits_batched

    safe_t = jnp.where(asks.temp > 0, asks.temp, 1.0)
    return filter_logits_batched(
        lg / safe_t[:, None], asks.topk, asks.topp,
        any_top_k=asks.any_top_k, any_top_p=asks.any_top_p)


def _draw_rows(f, greedy, asks, seed, gen):
    """Sampled rows draw from their filtered logits `f` under their own
    stateless key; greedy rows keep their argmax."""
    keys = _row_keys(seed, gen, _SUB_PROPOSE)
    sampled = jax.vmap(jax.random.categorical)(keys, f).astype(jnp.int32)
    return jnp.where(asks.temp > 0, sampled, greedy)


def _sample_rows(logits, asks, seed, gen):
    """Per-row next-token choice over one batch of logits: greedy
    argmax where temp == 0, categorical over the row's filtered /
    tempered distribution elsewhere. A batch pays for what its live
    rows ask for: the per-row keys, the filter and the categorical draw
    run under a `lax.cond` on "any row samples", so an all-greedy batch
    takes an argmax and nothing else, and inside it each filter runs
    only if a row asks for it. Both sides of every `cond` live in ONE
    trace, so a batch mixing greedy and sampled rows (the per-request
    sampling contract) never retraces as the mix changes; a sampled
    row's token is the same whichever rows sit beside it."""
    with jax.named_scope('sample'):
        lg = logits.astype(jnp.float32)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.lax.cond(
            asks.any_sampled,
            lambda: _draw_rows(_filtered_logits(lg, asks), greedy, asks,
                               seed, gen),
            lambda: greedy)


def _filtered_dist(logits, asks):
    """Per-row filtered/tempered probability dist over (K, V) logits
    (rows with temp == 0 use temp 1 — their dist is never consumed;
    the greedy rule takes argmax instead)."""
    return jax.nn.softmax(
        _filtered_logits(logits.astype(jnp.float32), asks), -1)


def _sample_rows_dist(logits, asks, seed, gen):
    """`_sample_rows` + the row's filtered dist from ONE shared filter
    pass (the speculative draft loop needs both per proposal — two
    separate calls would double the full-vocab sorts in the hottest
    scan of the spec window). The dist has to exist, so the filter pass
    stands outside the "any row samples" `cond`; the draw is inside."""
    with jax.named_scope('sample'):
        lg = logits.astype(jnp.float32)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        f = _filtered_logits(lg, asks)
        tok = jax.lax.cond(
            asks.any_sampled,
            lambda: _draw_rows(f, greedy, asks, seed, gen),
            lambda: greedy)
        return tok, jax.nn.softmax(f, -1)


def _prefill_kv(model, pages, ids, real_len, btabs):
    """Bucketed BATCHED admission prefill INTO pages (traced body,
    shared by the standalone `_paged_prefill` jit and the fused
    `_serve_step`/`_serve_spec_step`): run the model once over K
    RIGHT-padded prompts (K, Sb) with a throwaway contiguous
    cache in the pool's quantization world (the standard causal path —
    pad rows come after the real tokens, so rows < real_len never see
    them), then scatter every K/V row into its request's pages: row s
    of request b lands in page btabs[b, s // BS] slot s % BS, pad and
    DUMMY rows (real_len == 0) land on the scratch page 0. The batch
    width K is read off `ids`: the engine fixes ONE width per bucket
    (`ServingEngine._prefill_rows`, a token budget) and real lengths
    ride as device data, so one compilation per bucket serves every
    admission count and every prompt length in the bucket. `btabs` is
    one table a kind of page where the model keeps several
    (`GenerationMixin.page_kinds`), and a layer's rows go by its kind's.
    Returns (per-row last-token logits (K, V), pages)."""
    K, Sb = ids.shape
    tmp = _tmp_cache(model, pages, K, Sb)
    logits, tmp = model(ids, caches=tmp, cache_index=0)
    rl = jnp.reshape(jnp.asarray(real_len, jnp.int32), (K,))
    with jax.named_scope('head'):
        last = jnp.take_along_axis(
            logits, jnp.maximum(rl - 1, 0)[:, None, None], axis=1)[:, 0]
    bs = pages[0].kp.shape[2]
    s = jnp.arange(Sb)

    def targets(tab):
        """Flat page ids of the (K, Sb) positions in one kind's table: an
        entry of 0 (a pad, a dummy row, a page recycled behind the
        kind's window) sends the row to the scratch page."""
        blk = jnp.minimum(s // bs, tab.shape[1] - 1)
        page = jnp.where(s[None, :] < rl[:, None],
                         jnp.take_along_axis(tab, blk[None, :], axis=1),
                         0)                                   # (K, Sb)
        return page.reshape(-1)

    kinds = model.page_kinds()
    with jax.named_scope('attn/cache'):
        pflat = [targets(t)
                 for t in (btabs if len(kinds) > 1 else (btabs,))]
        sflat = jnp.broadcast_to(s % bs, (K, Sb)).reshape(-1)
    out_pages = [_pool_scatter(pc, t, pflat[i], sflat)
                 for t, pc, i in zip(tmp, pages,
                                     layer_kinds(kinds, len(pages)))]
    return last, out_pages


def _prefill_body(model, pages, last_logits, ids, real_len, btabs, slots):
    """`_prefill_kv` plus the per-slot logits commit: each request's
    next-token logits land in its slot's row of `last_logits` (dummy
    rows carry slot == SLOTS, dropped by the out-of-bounds scatter)."""
    last, out_pages = _prefill_kv(model, pages, ids, real_len, btabs)
    with jax.named_scope('head'):
        last_logits = last_logits.at[slots].set(
            last.astype(last_logits.dtype), mode='drop')
    return _pin(last_logits), _pin_pages(out_pages)


def _window_body(model, pages, last_logits, btab, ctx, live, budget,
                 temp, topk, topp, seed, plen, *, window, eos_token_id,
                 forced_tok=None, forced=None):
    """One decode window for the whole fixed-slot batch as ONE compiled
    lax.scan (traced body, shared by `_serve_window` and the fused
    `_serve_step`): per step, choose every slot's next token from the
    carried logits under ITS OWN sampling params (temp/topk/topp/seed
    ride as (SLOTS,) device data — a batch mixing greedy and sampled
    requests shares this one trace, changing the mix never retraces,
    and the sampler does only what the live rows ask for: `_row_asks`),
    step the model over the paged caches (per-row write
    positions = ctx, attention through the block tables), advance the
    committed length of live rows. Sampled rows draw their key
    statelessly from (request seed, generated-token index), so a
    resumed request replays exactly the keys the uninterrupted run
    used. Rows freeze when they hit eos, burn their budget, or were
    never live (empty slots): frozen rows still ride through the
    static-shape forward but write only to their frozen position / the
    scratch page and commit nothing — exactly how requests leave the
    batch without changing a traced shape. Returns (tokens (SLOTS,
    window), last_logits, pages, ctx, routed); the host reads the tokens
    ONCE per window and does all bookkeeping there. `routed` is what the
    model's expert layers routed for the committing rows over the window
    (`distributed.moe.ROUTING_FIELDS`, summed over layers and steps), or
    None for a model with no such layer: it rides the same host read."""
    from ..distributed.moe import routing_counts

    pad_tok = eos_token_id if eos_token_id is not None else 0
    plen = jnp.asarray(plen, jnp.int32)
    asks = _row_asks(temp, topk, topp, live)     # once, outside the scan

    def step(carry, t):
        last_logits, pages, ctx, finished = carry
        tok = _sample_rows(last_logits, asks, seed, ctx - plen)
        if forced is not None:
            # a speculative engine's chunk step: rows carrying a
            # pending verify-chosen next-token (incl. the rejection
            # RESAMPLE for sampled rows) consume it as this window's
            # FIRST token instead of re-sampling — the carried choice
            # is the committed one, whatever dispatch shape lands it
            tok = jnp.where(forced & (t == 0),
                            jnp.asarray(forced_tok, tok.dtype), tok)
        frozen = finished | (t >= budget)
        tok = jnp.where(frozen, jnp.asarray(pad_tok, tok.dtype), tok)
        commit = ~frozen
        if eos_token_id is not None:
            finished = finished | (commit & (tok == eos_token_id))
        with routing_counts(rows=commit[:, None]) as routed:
            logits, pages = model(tok[:, None], caches=pages,
                                  kv_write_pos=ctx, block_tables=btab)
        ctx = ctx + commit.astype(jnp.int32)
        return (logits[:, -1, :], pages, ctx, finished), (tok,
                                                          routed.total())

    state = (last_logits, pages, jnp.asarray(ctx, jnp.int32), ~live)
    (last_logits, pages, ctx, _), (toks, routed) = jax.lax.scan(
        step, state, jnp.arange(window, dtype=jnp.int32))
    if routed is not None:
        routed = _pin(routed.sum(0))
    return (_pin(toks.T), _pin(last_logits), _pin_pages(pages), _pin(ctx),
            routed)


@functools.partial(jax.jit, donate_argnames=('pages', 'last_logits'))
def _paged_prefill(model, pages, last_logits, ids, real_len, btabs, slots):
    """Standalone admission prefill (see _prefill_body) — for every
    admission group of a step beyond the first: further buckets, and
    what of one bucket did not fit its batch's rows. The first
    (largest) group rides fused inside _serve_step."""
    _count_trace('serve_prefill')
    return _prefill_body(model, pages, last_logits, ids, real_len, btabs,
                         slots)


@functools.partial(
    jax.jit, donate_argnames=('pages', 'last_logits'),
    static_argnames=('window', 'eos_token_id'))
def _serve_window(model, pages, last_logits, btab, ctx, live, budget,
                  temp, topk, topp, seed, plen, *, window, eos_token_id):
    """A pure decode window (no admissions this step): see
    _window_body."""
    _count_trace('serve_window')
    return _window_body(model, pages, last_logits, btab, ctx, live,
                        budget, temp, topk, topp, seed, plen,
                        window=window, eos_token_id=eos_token_id)


@functools.partial(
    jax.jit, donate_argnames=('pages', 'last_logits'),
    static_argnames=('window', 'eos_token_id'))
def _serve_step(model, pages, last_logits, ids, real_len, btabs, slots,
                btab, ctx, live, budget, temp, topk, topp, seed, plen, *,
                window, eos_token_id):
    """THE scheduler iteration as one fused jitted dispatch: freshly
    admitted rows bucket-prefill into their newly allocated pages
    (_prefill_body), then every slot — new and old — decodes a window
    through the paged kernel (_window_body). One compilation per
    (bucket, window) pair covers every admission count (the prefill
    batch's width is a function of the bucket alone); a step with no
    admissions uses _serve_window instead."""
    _count_trace('serve_step')
    last_logits, pages = _prefill_body(model, pages, last_logits, ids,
                                       real_len, btabs, slots)
    return _window_body(model, pages, last_logits, btab, ctx, live,
                        budget, temp, topk, topp, seed, plen,
                        window=window, eos_token_id=eos_token_id)


def _spec_window_impl(target, draft, pages, dpages, last_logits,
                      forced_tok, forced, btab, ctx, live, budget, temp,
                      topk, topp, seed, plen, *, k, ctx_bucket,
                      eos_token_id):
    """One speculative propose/verify/commit window over the fixed-slot
    batch (traced body of `_serve_spec_window` / `_serve_spec_step`) —
    the DecodeEngine's fused window contract composed with the paged
    pool and per-request sampling:

      1. candidate 0: the previous window's carried next-token
         (`forced_tok` where `forced` — the committed choice the verify
         already made, incl. the rejection RESAMPLE for sampled rows)
         or, on a slot's first window after admission, a per-row
         sample/argmax off the prefill's `last_logits`;
      2. draft propose: k+1 single-token steps through the DRAFT's
         paged pools (same block tables, same kv_write_pos offsets —
         the k-th proposal's own KV row is written too, the
         DecodeEngine pattern), each proposal chosen under the row's
         own sampling params;
      3. target verify: ONE (K, k+1) forward over the target with the
         committed prefix GATHERED from its pages into a contiguous
         temp cache of static length `ctx_bucket` (the chunked-prefill
         machinery — per-row kv_write_pos offsets, zero model changes);
      4. commit rule per row: greedy rows accept the longest draft
         prefix the target's argmax agrees with; sampled rows run the
         Leviathan/Chen accept coin min(1, pt/pd) per position with a
         rejection RESAMPLE from the normalised residual (pt - pd)+ —
         the output law equals sampling the target directly. ncommit =
         accepted + 1, clamped by budget and truncated at eos;
      5. only the committed rows' target K/V scatter back into pages
         (rejected rows land on the scratch page), so the pages hold
         exactly what a non-speculative step would have written —
         greedy streams stay bit-equal spec-on vs spec-off.

    Returns (cand (K, k+1), ncommit (K,), next_tok (K,), last_logits,
    pages, dpages, ctx): `cand[:ncommit]` are this window's committed
    tokens; `next_tok` is the carried choice the host feeds back as
    `forced_tok` (and persists per request, so preemption and
    snapshot/restore resume sampled streams bit-equal)."""
    K, V = last_logits.shape
    ctx = jnp.asarray(ctx, jnp.int32)
    plen = jnp.asarray(plen, jnp.int32)
    budget = jnp.asarray(budget, jnp.int32)
    gen0 = ctx - plen
    asks = _row_asks(temp, topk, topp, live)     # once, outside the scan
    sampled_row = asks.temp > 0
    cand0 = jnp.where(forced, jnp.asarray(forced_tok, jnp.int32),
                      _sample_rows(last_logits, asks, seed, gen0))

    def dstep(carry, i):
        tok, dpages = carry
        dlogits, dpages = draft(tok[:, None], caches=dpages,
                                kv_write_pos=ctx + i, block_tables=btab)
        nxt, pd = _sample_rows_dist(dlogits[:, -1, :], asks, seed,
                                    gen0 + i + 1)
        return (nxt, dpages), (nxt, pd)

    (_, dpages), (toks, pds) = jax.lax.scan(
        dstep, (cand0, dpages), jnp.arange(k + 1, dtype=jnp.int32))
    drafts = jnp.swapaxes(toks[:k], 0, 1)                  # (K, k)
    pd = jnp.swapaxes(pds[:k], 0, 1)                       # (K, k, V)
    window_ids = jnp.concatenate([cand0[:, None], drafts], axis=1)
    # verify: the whole (K, k+1) window in one target forward over the
    # gathered contiguous prefix (rows write at ctx..ctx+k inside tmp)
    tmp = _pool_gather(pages, btab, ctx, ctx_bucket)
    tlogits, tmp = target(window_ids, caches=tmp, kv_write_pos=ctx)
    tlg = tlogits.astype(jnp.float32)                      # (K, k+1, V)
    tchoice = jnp.argmax(tlg, axis=-1).astype(jnp.int32)   # (K, k+1)
    # per-row filtered target dists at every window position
    rep = lambda x: jnp.repeat(x, k + 1, axis=0)  # noqa: E731
    pt = _filtered_dist(
        tlg.reshape(K * (k + 1), V),
        asks._replace(temp=rep(asks.temp), topk=rep(asks.topk),
                      topp=rep(asks.topp))).reshape(K, k + 1, V)
    # accept rule per draft position
    greedy_acc = drafts == tchoice[:, :k]
    px_t = jnp.take_along_axis(pt[:, :k, :], drafts[:, :, None],
                               axis=-1)[..., 0]            # (K, k)
    px_d = jnp.take_along_axis(pd, drafts[:, :, None], axis=-1)[..., 0]

    def coin(s, n):
        kk = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(s), n), _SUB_ACCEPT)
        return jax.random.uniform(kk)

    u = jax.vmap(lambda s_, n0: jax.vmap(
        lambda i: coin(s_, n0 + i + 1))(jnp.arange(k)))(
            jnp.asarray(seed, jnp.uint32), gen0)           # (K, k)
    samp_acc = u < jnp.minimum(1.0, px_t / jnp.maximum(px_d, 1e-30))
    acc = jnp.where(sampled_row[:, None], samp_acc, greedy_acc)
    m = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
    # the carried next token: greedy rows take the target's choice at
    # the first disagreement; sampled rows resample from the residual
    # (pt - pd)+ — a full-accept row's pd pads to 0, so its residual
    # IS pt_k (the bonus-token rule falls out of the same expression)
    pt_m = jnp.take_along_axis(pt, m[:, None, None], axis=1)[:, 0]
    pd_pad = jnp.concatenate([pd, jnp.zeros((K, 1, V), pd.dtype)],
                             axis=1)
    pd_m = jnp.take_along_axis(pd_pad, m[:, None, None], axis=1)[:, 0]
    res = jnp.maximum(pt_m - pd_m, 0.0)
    rs = jnp.sum(res, axis=-1, keepdims=True)
    res = jnp.where(rs > 0, res / jnp.maximum(rs, 1e-30), pt_m)
    rkeys = _row_keys(seed, gen0 + m + 1, _SUB_RESAMPLE)
    sampled_next = jax.vmap(jax.random.categorical)(
        rkeys, jnp.log(jnp.maximum(res, 1e-30))).astype(jnp.int32)
    greedy_next = jnp.take_along_axis(tchoice, m[:, None],
                                      axis=1)[:, 0]
    next_tok = jnp.where(sampled_row, sampled_next, greedy_next)
    # commit count: accepted prefix + the candidate that started it,
    # clamped by the row's remaining budget, truncated at the first
    # eos inside the committed prefix, zero for dead rows
    nc = jnp.minimum(m + 1, budget)
    if eos_token_id is not None:
        iseos = window_ids == eos_token_id
        first = jnp.argmax(iseos, axis=1)
        nc = jnp.where(jnp.any(iseos, axis=1) & (first < nc),
                       first + 1, nc)
    nc = jnp.where(live, nc, 0)
    # scatter ONLY the committed rows' target K/V back into pages
    # (rejected/beyond-budget rows go to the scratch page — the next
    # window rewrites those positions anyway)
    bs = pages[0].kp.shape[2]
    maxb = btab.shape[1]
    i = jnp.arange(k + 1)
    wpos = ctx[:, None] + i[None, :]                       # (K, k+1)
    wblk = jnp.minimum(wpos // bs, maxb - 1)
    wpage = jnp.where(i[None, :] < nc[:, None],
                      jnp.take_along_axis(btab, wblk, axis=1), 0)
    pflat = wpage.reshape(-1)
    sflat = (wpos % bs).reshape(-1)
    take = jnp.minimum(wpos, ctx_bucket - 1)
    pages = [_pool_scatter(pc, t, pflat, sflat, take=take)
             for t, pc in zip(tmp, pages)]
    # next window's sampling base for rows that keep going: the
    # target's logits at the last committed position (rows that stop —
    # eos/budget — are retired by the host before the next window)
    last = jnp.take_along_axis(
        tlg, jnp.maximum(nc - 1, 0)[:, None, None], axis=1)[:, 0]
    last_logits = jnp.where(live[:, None],
                            last.astype(last_logits.dtype), last_logits)
    ctx = ctx + nc
    return (_pin(jnp.asarray(window_ids, jnp.int32)), _pin(nc),
            _pin(next_tok), _pin(last_logits), _pin_pages(pages),
            _pin_pages(dpages), _pin(ctx))


@functools.partial(
    jax.jit, donate_argnames=('pages', 'dpages', 'last_logits'),
    static_argnames=('k', 'ctx_bucket', 'eos_token_id'))
def _serve_spec_window(target, draft, pages, dpages, last_logits,
                       forced_tok, forced, btab, ctx, live, budget, temp,
                       topk, topp, seed, plen, *, k, ctx_bucket,
                       eos_token_id):
    """A pure speculative window (no admissions this step): see
    _spec_window_impl."""
    _count_trace('serve_spec_window')
    return _spec_window_impl(target, draft, pages, dpages, last_logits,
                             forced_tok, forced, btab, ctx, live, budget,
                             temp, topk, topp, seed, plen, k=k,
                             ctx_bucket=ctx_bucket,
                             eos_token_id=eos_token_id)


@functools.partial(
    jax.jit, donate_argnames=('pages', 'dpages', 'last_logits'),
    static_argnames=('k', 'ctx_bucket', 'eos_token_id'))
def _serve_spec_step(target, draft, pages, dpages, last_logits, ids,
                     real_len, btabs, slots, forced_tok, forced, btab,
                     ctx, live, budget, temp, topk, topp, seed, plen, *,
                     k, ctx_bucket, eos_token_id):
    """The speculative scheduler iteration as one fused jitted
    dispatch: freshly admitted rows bucket-prefill into their pages on
    BOTH models (the draft's pool mirrors the target's block tables,
    so one allocator serves both), then every slot runs a
    propose/verify/commit window (_spec_window_impl). One compilation
    per (k, bucket, ctx bucket) triple covers every admission count
    and sampling mix."""
    _count_trace('serve_spec_step')
    last_logits, pages = _prefill_body(target, pages, last_logits, ids,
                                       real_len, btabs, slots)
    _, dpages = _prefill_kv(draft, dpages, ids, real_len, btabs)
    dpages = _pin_pages(dpages)
    return _spec_window_impl(target, draft, pages, dpages, last_logits,
                             forced_tok, forced, btab, ctx, live, budget,
                             temp, topk, topp, seed, plen, k=k,
                             ctx_bucket=ctx_bucket,
                             eos_token_id=eos_token_id)


def _chunk_body(model, pages, last_logits, ids, chunk_len, start, btabs,
                slots, cow_src, cow_dst, *, ctx_bucket):
    """Chunked / continuation prefill INTO pages (traced body, fused
    ahead of the decode window by `_serve_chunk_step`): each row b
    already owns positions [0, start[b]) of its context in its pages —
    a prior chunk's output, or shared prefix-cache pages — and appends
    chunk_len[b] new tokens at positions [start[b], start[b] +
    chunk_len[b]).

    The model needs no paged-prefill support: each row's committed
    prefix K/V is GATHERED out of its pages into a throwaway
    contiguous cache of static length `ctx_bucket` (the bucket of the
    largest end position in the batch), the chunk runs through the
    standard per-row-offset forward (`kv_write_pos` — the speculative-
    verify machinery: causal within the chunk, full attention over the
    gathered prefix), and the new K/V rows scatter back into pages
    exactly like `_prefill_body`. Rows whose chunk COMPLETES their
    context carry their slot id in `slots` and commit next-token
    logits; still-prefilling and dummy rows carry max_slots and are
    dropped by the OOB scatter — so a chunked request occupies its
    slot but emits nothing until its last chunk commits.

    `cow_src`/`cow_dst` apply the copy-on-write page copies the
    scheduler armed this step (dst := src, FIRST, so the gather and
    the scatter below both see the private copy through the already-
    rewritten block tables); rows with no pending copy carry (0, 0) —
    a harmless scratch-page self-copy."""
    K, Cb = ids.shape
    bs = pages[0].kp.shape[2]
    maxb = btabs.shape[1]
    Sb = int(ctx_bucket)
    cl = jnp.reshape(jnp.asarray(chunk_len, jnp.int32), (K,))
    st = jnp.reshape(jnp.asarray(start, jnp.int32), (K,))
    # CoW copies first (every pool field — int8 pools copy the per-row
    # scale rows with their page, so a shared page's quantization
    # survives the private fork byte for byte)
    pages = [type(pc)(*[f.at[cow_dst].set(f[cow_src]) for f in pc])
             for pc in pages]
    # gather each row's prefix rows [0, start) into a contiguous
    # (K, Sb, ...) temp cache in the pool's quantization world;
    # positions >= start read the scratch page (never attended: the
    # per-row causal mask stops at qpos)
    tmp = _pool_gather(pages, btabs, st, Sb)
    logits, tmp = model(ids, caches=tmp, kv_write_pos=st)
    last = jnp.take_along_axis(
        logits, jnp.maximum(cl - 1, 0)[:, None, None], axis=1)[:, 0]
    # scatter the chunk's K/V rows back into pages: position start + i
    # of row b lands in page btabs[b, (start+i) // bs] slot (start+i) %
    # bs; pad and dummy rows (i >= chunk_len) land on the scratch page
    i = jnp.arange(Cb)
    wpos = st[:, None] + i[None, :]                        # (K, Cb)
    wblk = jnp.minimum(wpos // bs, maxb - 1)
    wpage = jnp.where(i[None, :] < cl[:, None],
                      jnp.take_along_axis(btabs, wblk, axis=1), 0)
    pflat = wpage.reshape(-1)
    sflat = (wpos % bs).reshape(-1)
    take = jnp.minimum(wpos, Sb - 1)
    out_pages = [_pool_scatter(pc, t, pflat, sflat, take=take)
                 for t, pc in zip(tmp, pages)]
    last_logits = last_logits.at[slots].set(
        last.astype(last_logits.dtype), mode='drop')
    return _pin(last_logits), _pin_pages(out_pages)


@functools.partial(
    jax.jit, donate_argnames=('pages', 'last_logits'),
    static_argnames=('ctx_bucket', 'window', 'eos_token_id'))
def _serve_chunk_step(model, pages, last_logits, ids, chunk_len, start,
                      btabs, slots, cow_src, cow_dst, btab, ctx, live,
                      budget, temp, topk, topp, seed, plen, forced_tok,
                      forced, *, ctx_bucket, window, eos_token_id):
    """The chunked-prefill scheduler iteration as one fused jitted
    dispatch: every in-progress chunked/continuation row appends its
    chunk into its pages (_chunk_body — CoW copies first, prefix
    gathered from pages, completing rows commit their logits), then
    every slot decodes a window (_window_body; still-prefilling rows
    ride frozen on the scratch page). One compilation per (window,
    chunk bucket, context bucket) triple covers every row count, chunk
    length, and prefill progress — a long-prompt flood never changes a
    traced shape."""
    _count_trace('serve_chunk_step')
    last_logits, pages = _chunk_body(model, pages, last_logits, ids,
                                     chunk_len, start, btabs, slots,
                                     cow_src, cow_dst,
                                     ctx_bucket=ctx_bucket)
    return _window_body(model, pages, last_logits, btab, ctx, live,
                        budget, temp, topk, topp, seed, plen,
                        window=window, eos_token_id=eos_token_id,
                        forced_tok=forced_tok, forced=forced)


@functools.partial(
    jax.jit, donate_argnames=('dpages', 'dlogits'),
    static_argnames=('ctx_bucket',))
def _draft_chunk(draft, dpages, dlogits, ids, chunk_len, start, btabs,
                 slots, cow_src, cow_dst, *, ctx_bucket):
    """Draft-side mirror of the chunk/continuation prefill: a
    speculative engine must keep the DRAFT's pages current through
    every admission path, or chunk-admitted and prefix-hit rows would
    draft against missing prompt KV and speculation would silently
    degrade to pure overhead (accept rate collapse with no error).
    Same body as the target's chunk leg — CoW copies fork the draft's
    pages too, the gathered prefix is the draft's own — with the
    logits commit dropped by all-dummy slot indices (`dlogits` is a
    throwaway donated buffer)."""
    _count_trace('serve_draft_chunk')
    return _chunk_body(draft, dpages, dlogits, ids, chunk_len, start,
                       btabs, slots, cow_src, cow_dst,
                       ctx_bucket=ctx_bucket)


@functools.partial(jax.jit, static_argnames=('ctx_bucket',))
def _kv_export(pages, btabs, st, *, ctx_bucket):
    """Gather ONE request's committed KV prefix [0, st[0]) out of its
    pages into contiguous per-layer rows (the `_serve_chunk_step`
    gather path at K=1) — the device half of `export_kv`. No donation:
    the source pool must survive the export (the request keeps serving
    until its owner decides the handoff). Outputs pin REPLICATED: under
    a tp mesh this is the all-gather that reassembles the kv-head
    shards into one host-fetchable, degree-agnostic blob (the
    migration shardlint suite budgets it exactly). Int8 pools gather
    int8 bytes + per-row scales, so the blob reproduces the pool
    bit-for-bit at half the bf16 bytes."""
    _count_trace('serve_export')
    tmp = _pool_gather(pages, btabs, st, ctx_bucket)
    out = []
    for t in tmp:
        fs = [_pin(f) for f in t]
        out.append(type(t)(*fs) if hasattr(t, '_fields') else tuple(fs))
    return out


@functools.partial(jax.jit, donate_argnames=('pages',),
                   static_argnames=('ctx_bucket',))
def _kv_import(pages, blob, pflat, sflat, *, ctx_bucket):
    """Scatter an exported blob's contiguous rows into this pool's
    pages at flat (page, slot) targets — the device half of
    `import_kv`, riding the same `.at[...].set` write the chunk bodies
    commit through. Rows the host masked (past the export length, or
    covered by shared prefix pages) land on the reserved scratch page.
    The replicated blob re-shards on write under a tp mesh (each shard
    keeps its own kv-head rows — a slice, not a collective), so a
    blob exported at one tp degree imports at any other."""
    del ctx_bucket           # shapes carry it; static keys the registry
    _count_trace('serve_import')
    out = [_pool_scatter(pc, t, pflat, sflat)
           for t, pc in zip(blob, pages)]
    return _pin_pages(out)


# kind -> (its jitted function, the geometry params its registry tag
# holds after the kind, in order). A kind's arguments, statics and
# outputs are `ServingEngine._dispatch`'s to state.
_SERVE_DISPATCHES = {
    'serve_prefill': (_paged_prefill, ('bucket',)),
    'serve_window': (_serve_window, ('window',)),
    'serve_step': (_serve_step, ('window', 'bucket')),
    'serve_chunk_step': (_serve_chunk_step, ('window', 'chunk', 'bucket')),
    'serve_spec_window': (_serve_spec_window, ('spec', 'ctx')),
    'serve_spec_step': (_serve_spec_step, ('spec', 'bucket', 'ctx')),
    'serve_draft_chunk': (_draft_chunk, ('chunk', 'bucket')),
    'serve_export': (_kv_export, ('ctx',)),
    'serve_import': (_kv_import, ('ctx',)),
}

# One jitted call as `ServingEngine._dispatch` states it:
# `fn(*models, *args, **statics)`; `tag` keys the registry note of a
# `primary` call (a draft-side leg notes nothing); `rebind` holds the
# (engine field, output index) pairs that take the donated pools back
# (index None: the output whole).
_Dispatch = collections.namedtuple(
    '_Dispatch', 'fn models args statics tag rebind primary')


def _avals(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _ceil_div(a, b):
    return -(-a // b)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Continuous-batching serving over one model.

        engine = ServingEngine(model, max_slots=8, num_blocks=...,
                               max_new_tokens=64, eos_token_id=2)
        rid = engine.submit(prompt_ids)          # 1-D int array
        engine.run()                             # drain queue + batch
        out = engine.result(rid)                 # (S + max_new,) ids

        outs = engine.serve(list_of_prompts)     # submit+run+collect

    Greedy outputs per request are exactly `DecodeEngine.generate`'s
    batch-1 outputs (eos-padded to max_new_tokens, prompt echoed back).
    The model must accept `block_tables` in its cached forward (the
    Llama family, AFMoE and MiMo-V2 do). A model states its kinds of KV
    page (`GenerationMixin.page_kinds`); each kind has its own pools,
    allocator and block table. A kind with no window keeps every page
    of a context (the sliding-window layers of a one-kind model too: the
    kernel skips the pages behind a window, the allocator does not free
    them); a kind WITH a window holds at most `ceil((window +
    decode_window) / block_size) + 1` pages a slot, the pages wholly
    behind the window going back to its free list as the row decodes
    (docs/serving.md#kinds-of-page). A model's
    `distributed.moe.ExpertShare` layers report what they routed with
    each window's one host read (`serve.routing`).
    """

    def __init__(self, model, max_slots=8, block_size=16, num_blocks=None,
                 max_context_len=None, max_new_tokens=32, decode_window=8,
                 temperature=0.0, top_k=0, top_p=1.0, eos_token_id=None,
                 buckets=None, max_queue=None, admit_watermark=1.0,
                 shed_policy='reject', max_terminal=1024,
                 prefix_cache=False, prefill_chunk=None,
                 postmortem_dir=None, mesh=None, tp=None,
                 ops_port=None, ops_host='127.0.0.1', watchdog=None,
                 slo_rules=None, ts_interval_s=None,
                 draft=None, num_draft_tokens=4, kv_cache_dtype=None,
                 phase_role='monolithic', metrics_registry=None,
                 journal=None, rid_start=0):
        params = inspect.signature(model.forward).parameters
        # telemetry scope (docs/observability.md#per-replica-scopes):
        # metrics_registry gives this engine a PRIVATE MetricsRegistry
        # — every serve.*/pool.* series, the windowed rate gauges, and
        # the watchdog's health series land there instead of the
        # process registry, so N in-process replicas (the fleet shape)
        # never merge their series. A private registry implies a
        # private flight-recorder journal too (request trails, pool
        # events) unless `journal=` passes one explicitly. None/None =
        # the process globals, prior behavior bit-identical.
        self._registry = (metrics_registry if metrics_registry is not None
                          else _obs.REGISTRY)
        if journal is not None:
            self._jr = journal
        elif metrics_registry is not None:
            self._jr = _journal.Journal()
        else:
            self._jr = _journal.JOURNAL
        # rid_start offsets this engine's request-id space: fleet
        # replicas take disjoint strides so a request keeps its rid
        # across a drain-migration or kill-resurrection hop to another
        # replica (rids are the join key for trails and results)
        self._rid = int(rid_start)
        if self._rid < 0:
            raise ValueError(f'rid_start must be >= 0, got {rid_start}')
        self._rid_start = self._rid
        if 'block_tables' not in params:
            raise NotImplementedError(
                f'{type(model).__name__} lacks block_tables in its '
                f'cached forward: paged serving needs a decoder whose '
                f'layers go through models.llama.cached_attention '
                f'(LlamaForCausalLM, AfmoeForCausalLM); use DecodeEngine '
                f'for this model')
        # speculative serving (docs/serving.md#speculative-serving):
        # draft != None turns every non-chunk scheduler iteration into
        # a propose/verify window — the DecodeEngine's fused
        # speculative contract (docs/decode_engine.md) composed with
        # the paged pool. The draft keeps its OWN page pools indexed
        # by the SAME block tables (page ids are bookkeeping, so one
        # allocator covers both models); greedy streams stay bit-equal
        # to the non-speculative engine, sampled streams are
        # distribution-correct (Leviathan/Chen rejection sampling).
        self.draft = draft
        self.spec_window = None
        if draft is not None:
            self.spec_window = int(num_draft_tokens)
            if self.spec_window < 1:
                raise ValueError('num_draft_tokens must be >= 1')
            dparams = inspect.signature(draft.forward).parameters
            for need in ('block_tables', 'kv_write_pos'):
                if need not in dparams:
                    raise NotImplementedError(
                        f'{type(draft).__name__} lacks {need} in its '
                        f'cached forward: the speculative draft runs '
                        f'paged single-token steps at per-row offsets')
            if 'kv_write_pos' not in params:
                raise NotImplementedError(
                    f'{type(model).__name__} lacks kv_write_pos: the '
                    f'speculative verify commits at per-row offsets')
        # kv_cache_dtype='int8' backs the slots with int8 paged pools
        # (QuantPagedKVCache: per-row scales ride with the pages, so
        # quantization is write-order independent — preemption
        # re-prefill, prefix sharing, CoW, and snapshot/restore all
        # reproduce bit-identical pages). None = the model's cache
        # dtype (prior behavior, byte for byte). 'bfloat16' keeps the
        # unquantized layout at 2-byte rows — the deployment baseline
        # the int8 migration blob's ~half-bytes headline is measured
        # against (gate_serve_disagg).
        if kv_cache_dtype is None:
            self.kv_cache_dtype = None
        else:
            kd = jnp.dtype(kv_cache_dtype)
            if kd not in (jnp.int8, jnp.bfloat16):
                raise ValueError(
                    f"kv_cache_dtype must be None, 'int8', or "
                    f"'bfloat16', got {kv_cache_dtype!r}")
            self.kv_cache_dtype = kd
        # phase-disaggregated serving (docs/serving.md#disaggregated-
        # serving): the role tags what this engine is FOR — 'prefill'
        # pools admit/chunk and hand every request off at first token
        # (disagg.PrefillEngine), 'decode' pools receive `import_kv`
        # migrations and only decode. The role changes no dispatch
        # semantics here; it keys the AOT geometry enumeration (a
        # decode pool warms import scatters, not admission prefills),
        # rides /statusz + /healthz, and lets a phase-aware router
        # place by role. 'monolithic' is prior behavior bit-for-bit.
        if phase_role not in ('monolithic', 'prefill', 'decode'):
            raise ValueError(
                f"phase_role must be 'monolithic', 'prefill', or "
                f"'decode', got {phase_role!r}")
        self.phase_role = phase_role
        # tensor-parallel serving (docs/serving.md#tp-sharded-serving):
        # the engine owns ONE mesh whose only >1 axis is 'tp'. Device
        # state shards kv-heads over it (page pools, via
        # init_paged_cache); block tables, slot/context mirrors, and
        # every other host-fed arg upload REPLICATED; and the whole
        # host scheduler loop — admission, preemption, prefix
        # refcounts, CoW, deadlines, snapshot/restore, the journal —
        # runs on replicated host state exactly as on one chip.
        # Accepts a Mesh, a bare tp=int (serving_mesh builds the 1-D
        # mesh, virtual-device fallback included), or — when neither
        # is passed — adopts an ambient tp-only global mesh the way
        # generate() does.
        from ..distributed.mesh import get_mesh, serving_mesh

        if tp is not None and mesh is not None:
            raise ValueError(
                'pass ServingEngine(mesh=...) OR tp=..., not both')
        if tp is not None:
            tp = int(tp)
            if tp < 1:
                raise ValueError(f'tp must be >= 1, got {tp}')
            mesh = serving_mesh(tp) if tp > 1 else None
        elif mesh is None:
            amb = get_mesh()
            if (amb is not None and 'tp' in amb.axis_names
                    and amb.shape['tp'] > 1
                    and all(amb.shape[a] == 1 for a in amb.axis_names
                            if a != 'tp')):
                mesh = amb
        if mesh is not None:
            if 'tp' not in mesh.axis_names:
                raise ValueError(
                    f"ServingEngine mesh needs a 'tp' axis; got axes "
                    f'{tuple(mesh.axis_names)}')
            extra = [a for a in mesh.axis_names
                     if a != 'tp' and mesh.shape[a] > 1]
            if extra:
                raise ValueError(
                    f'ServingEngine shards over tp only; mesh axes '
                    f'{extra} have degree > 1 — run dp replicas as '
                    f'separate engines behind one queue')
            if mesh.shape['tp'] == 1:
                mesh = None          # degree 1 IS single-device serving
        self.mesh = mesh
        self.tp = int(mesh.shape['tp']) if mesh is not None else 1
        # the model's kinds of KV page (docs/serving.md#kinds-of-page):
        # the one description pools, allocators, tables and the page
        # counts of `serve.dispatch` read. `_ring` is the index of the
        # kind whose pages are recycled behind its window, None for a
        # model of one kind (every path below is then the one it was).
        self._kinds = tuple(model.page_kinds())
        self._ring = None
        if len(self._kinds) > 1:
            self._ring = self._two_kinds(
                model, prefix_cache=prefix_cache,
                prefill_chunk=prefill_chunk, draft=draft, tp=self.tp,
                kv_cache_dtype=('int8' if self.kv_cache_dtype == jnp.int8
                                else None),      # bfloat16 quantizes nothing
                phase_role=phase_role)
        self._rep = None             # replicated NamedSharding, lazy below
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            self._rep = NamedSharding(self.mesh, P())
            kvh = self._kinds[0].kv_heads
            if kvh % self.tp != 0:
                import warnings

                warnings.warn(
                    f'{kvh} kv heads do not divide tp={self.tp}: the '
                    f'page pools clamp to REPLICATED (correct, but the '
                    f'KV cache no longer splits across chips) — pick a '
                    f'tp degree dividing the kv heads', stacklevel=2)
            # place the model per its declared PartitionSpecs (the
            # Llama family ships megatron column->row specs on every
            # projection; a caller that already parallelize()d gets
            # the identical placement re-applied)
            from ..distributed.parallel import shard_model

            with self._use_mesh():
                model = shard_model(model, self.mesh)
                if self.draft is not None:
                    self.draft = shard_model(self.draft, self.mesh)
        self.model = model
        self.max_slots = int(max_slots)
        self.block_size = int(block_size)
        self.max_new_tokens = int(max_new_tokens)
        self.decode_window = int(decode_window)
        if self.decode_window < 1 or self.max_slots < 1:
            raise ValueError('decode_window and max_slots must be >= 1')
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token_id = (int(eos_token_id) if eos_token_id is not None
                             else None)
        self.buckets = tuple(sorted(buckets)) if buckets else DEFAULT_BUCKETS
        if max_context_len is None:
            mp = getattr(getattr(model, 'config', None),
                         'max_position_embeddings', None)
            max_context_len = int(mp) if mp else 2048
        self.max_context_len = int(max_context_len)
        self.max_blocks_per_seq = _ceil_div(self.max_context_len,
                                            self.block_size)
        if num_blocks is None:
            # full coverage: every slot can hold a max-length request
            # (+1 for the reserved scratch page); pass a smaller pool to
            # actually exercise preemption
            num_blocks = self.max_slots * self.max_blocks_per_seq + 1
        self.allocator = BlockAllocator(num_blocks, self.block_size)
        # the recycled kind's own allocator, sized so that every slot
        # can hold its bound: its pool never runs dry of itself
        self.win_allocator = None
        if self._ring is not None:
            self.win_pages_per_slot = _ceil_div(
                self._kinds[self._ring].window + self.decode_window,
                self.block_size) + 1
            self.win_allocator = BlockAllocator(
                self.max_slots * self.win_pages_per_slot + 1,
                self.block_size)
        if self._jr is not _journal.JOURNAL:
            self.allocator.journal = self._jr
            if self.win_allocator is not None:
                self.win_allocator.journal = self._jr
        self.queue = RequestQueue()
        # admission control / load shedding (docs/serving.md#resilience):
        # max_queue bounds what submit() will hold (QueueFull past it —
        # preemption requeues ride above the bound, at most max_slots of
        # them); admit_watermark pauses admission while the POST-admit
        # pool utilization would exceed it, so steady traffic degrades
        # to queueing instead of preemption storms; shed_policy says
        # what a full queue does with a new arrival ('reject' it, or
        # 'evict' the lowest-priority queued request when the arrival
        # outranks it)
        self.max_queue = None if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError('max_queue must be >= 1 (or None)')
        self.admit_watermark = float(admit_watermark)
        if not 0.0 < self.admit_watermark <= 1.0:
            raise ValueError(
                f'admit_watermark must be in (0, 1], got {admit_watermark}')
        if shed_policy not in ('reject', 'evict'):
            raise ValueError(
                f"shed_policy must be 'reject' or 'evict', "
                f'got {shed_policy!r}')
        self.shed_policy = shed_policy
        # prefix caching + chunked prefill (docs/serving.md#prefix):
        # prefix_cache shares full pages of identical prompt prefixes
        # across requests through the allocator's hash index (system
        # prompts amortize to ~zero prefill); prefill_chunk splits
        # long-prompt admission into <=prefill_chunk-token chunks
        # interleaved with decode windows so one long arrival never
        # stalls in-flight streams for a whole-prompt prefill. Both
        # default OFF: the monolithic admission path is bit-identical
        # to prior behavior.
        self.prefix_cache = bool(prefix_cache)
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError('prefill_chunk must be >= 1 (or None)')

        # device state, allocated ONCE (shapes never change). Under a
        # tp mesh the pools come back kv-head-sharded and the logits /
        # rng upload committed-replicated (self._put), so every later
        # dispatch sees the same input shardings the first one did.
        with self._use_mesh():
            self._pages = model.init_paged_cache(
                self._by_kind(num_blocks, self.win_allocator
                              and self.win_allocator.num_blocks),
                self.block_size, dtype=self.kv_cache_dtype)
            self._dpages = None
            self._kernel_layers = {}        # bucket -> attn_kernel_layers
            if self.draft is not None:
                # the draft's pools share the target's page-id space:
                # same num_blocks/block_size, indexed by the same block
                # tables — one allocator, zero extra bookkeeping
                self._dpages = self.draft.init_paged_cache(
                    num_blocks, self.block_size,
                    dtype=self.kv_cache_dtype)
            vocab = model.config.vocab_size
            self._last_logits = self._put(
                jnp.zeros((self.max_slots, vocab), model.cache_dtype()))
            # sampling randomness is STATELESS per request (seed +
            # generated index fold_in chains) — the engine carries no
            # PRNG key. The draft's throwaway logits buffer feeds the
            # draft-side prefill dispatches (its per-slot scatter is
            # dropped by all-dummy slot indices; the buffer only
            # donates and comes back).
            self._dlogits = None
            self._dummy_slots = None
            if self.draft is not None:
                self._dlogits = self._put(jnp.zeros(
                    (self.max_slots, self.draft.config.vocab_size),
                    self.draft.cache_dtype()))
                # all-dummy slot indices, by batch width (`_dummy`):
                # the draft-side prefill and chunk legs drop their
                # logits commit through the OOB scatter
                self._dummy_slots = {}
        # (chunk bucket, ctx bucket) shapes the draft's chunk/catch-up
        # legs have dispatched — a fresh shape's step counts as a
        # cache MISS (it paid trace + compile)
        self._draft_shapes: set = set()
        # constant all-zero forced args for the non-speculative chunk
        # path (spec_next can never be set without a draft, so the
        # per-step _forced_state scan is pure waste there)
        self._zero_ftok = self._put(np.zeros((self.max_slots,),
                                             np.int32))
        self._zero_forced = self._put(np.zeros((self.max_slots,), bool))
        # real-unit pool accounting: one page costs the sum of every
        # pool field's per-page bytes (k+v per layer; int8 pools add
        # their per-row scale rows; a draft's mirrored pools add
        # theirs) — threaded into allocator.stats() and the pool.*
        # gauges. Field shapes are the GLOBAL logical shapes even when
        # the pool is tp-sharded (each shard holds kv_heads/tp of it),
        # so the bytes_* gauges keep reporting whole-pool HBM —
        # per-shard itemsize x tp — and capacity dashboards never
        # shrink by 1/tp (tests/test_serving_tp.py pins the arithmetic)

        def _pool_page_bytes(pages):
            return int(sum(
                int(np.prod(f.shape[1:])) * f.dtype.itemsize
                for pc in pages for f in pc))

        if self._ring is None:
            self.allocator.bytes_per_page = (
                _pool_page_bytes(self._pages)
                + (_pool_page_bytes(self._dpages) if self._dpages else 0))
        else:
            # a page id of a kind stands for a page in each of THAT
            # kind's layers
            by_kind = [[pc for pc, k in zip(self._pages, layer_kinds(
                self._kinds, len(self._pages))) if k == i]
                for i in range(2)]
            self.win_allocator.bytes_per_page = _pool_page_bytes(
                by_kind[self._ring])
            self.allocator.bytes_per_page = _pool_page_bytes(
                by_kind[1 - self._ring])

        # host-authoritative per-slot state (device copies ride in as
        # small int32/bool args each window)
        self._slot_req: list = [None] * self.max_slots
        self._slot_pages: list = [[] for _ in range(self.max_slots)]
        self._btab = np.zeros((self.max_slots, self.max_blocks_per_seq),
                              np.int32)
        # the recycled kind's twin of (_slot_pages, _btab): a slot holds
        # the pages of logical indices [_wfirst, _wfirst + len) and the
        # table's other entries are 0 (never read behind the window; a
        # prefill's rows for them land on the scratch page)
        self._slot_wpages: list = [[] for _ in range(self.max_slots)]
        self._wfirst = [0] * self.max_slots
        self._wtab = (np.zeros_like(self._btab) if self._ring is not None
                      else None)
        self._ctx = np.zeros((self.max_slots,), np.int32)
        # (window, layers) per distinct attention window, 0 = the whole
        # context: what `_kernel_pages` counts the paged kernel's work by
        self._layer_windows = sorted(collections.Counter(
            m.sliding_window or 0 for m in model.sublayers()
            if hasattr(m, 'sliding_window')).items())
        self._budget = np.zeros((self.max_slots,), np.int32)
        # per-slot sampling params — DATA, not statics (the traced
        # bodies take them as (SLOTS,) device args): a mixed
        # greedy/sampled/speculative workload shares one batch with
        # zero retraces as the mix changes. Mutated only at
        # place/clear, so the device copies ride the _dev mirror.
        self._temp = np.zeros((self.max_slots,), np.float32)
        self._topk = np.zeros((self.max_slots,), np.int32)
        self._topp = np.ones((self.max_slots,), np.float32)
        self._seed = np.zeros((self.max_slots,), np.uint32)
        self._plen = np.zeros((self.max_slots,), np.int32)
        # speculative engines track how much of each slot's context the
        # DRAFT's pages hold (_dctx <= _ctx): tokens committed by a
        # chunk-step's plain decode window never pass through the
        # draft, so the next speculative step first catches the draft
        # up over the hole (a _draft_chunk dispatch) — without it the
        # draft would propose against missing KV and the accept rate
        # would silently collapse
        self._dctx = np.zeros((self.max_slots,), np.int32)
        # per-slot prefill progress: None = fully prefilled (decoding);
        # an int = context tokens already in pages — the slot is mid
        # chunked/continuation prefill, rides decode windows frozen on
        # the scratch page, and emits nothing until its last chunk
        # commits. `_cow_pending` holds the (src, dst) page copy the
        # slot's first chunk dispatch must perform (prefix-cache CoW).
        self._pfill: list = [None] * self.max_slots
        self._cow_pending: list = [None] * self.max_slots
        self._cow_release: list = []     # pins freed post chunk dispatch
        # device mirror of (btab, ctx, live): rebuilt only when a slot
        # changes (admission/retire/preempt/page top-up); between those
        # the window's returned ctx is carried device-resident, so a
        # steady-state window uploads ONE small array (the budgets)
        self._dev = None

        # request registries: every submitted request is in exactly one
        # of these until its result is retrieved — `_live` (queued /
        # running / preempted) or `_terminal` (finished / failed /
        # expired / cancelled, popped by result()). `counts` are the
        # host-truth resilience counters (stats() reports them even
        # with telemetry off; the registry counters mirror them).
        # `_terminal` is bounded at `max_terminal` records (oldest
        # evicted first) so fire-and-forget cancellation or a client
        # that never collects cannot grow host memory forever; an
        # evicted rid reads as already-retrieved (KeyError).
        self.max_terminal = int(max_terminal)
        if self.max_terminal < 1:
            raise ValueError('max_terminal must be >= 1')
        self._live: dict = {}
        self._terminal: dict = {}
        # rids an active serve() batch will collect: the max_terminal
        # eviction skips these (released per-rid by result())
        self._collect_guard: set = set()
        self._deadlines_live = 0     # live requests with a deadline armed
        self.counts = {'finished': 0, 'failed': 0, 'expired': 0,
                       'cancelled': 0, 'rejected': 0, 'shed': 0,
                       'admission_paused': 0}
        self._admit_seq = itertools.count()
        self.preemption_count = 0
        self._tokens_out = 0
        self._serve_time = 0.0
        # host-truth prefix/chunk counters (stats() reports them even
        # with telemetry off; snapshot()/restore() carries them like
        # `counts` so monitoring sees no discontinuity)
        self.prefix_counts = {'hits': 0, 'misses': 0, 'hits_skipped': 0,
                              'hit_tokens': 0, 'chunked_admissions': 0,
                              'chunk_steps': 0}
        # host-truth speculative counters (stats()['spec'] reports them
        # even with telemetry off; snapshot()/restore() carries them
        # like `counts` so accept-rate dashboards see no discontinuity
        # across a failover)
        self.spec_counts = {'windows': 0, 'proposed': 0, 'accepted': 0}
        # host-truth KV-migration counters (stats()['migration'] even
        # with telemetry off; snapshot()/restore() carries them like
        # `counts`). bytes_* are blob payload bytes — what the int8
        # half-the-bf16-bytes headline is measured over.
        self.migration_counts = {'exported': 0, 'imported': 0,
                                 'import_failed': 0, 'handoffs': 0,
                                 'bytes_exported': 0, 'bytes_imported': 0}
        # telemetry hot-path caches: metric handles (refreshed when the
        # registry generation changes, i.e. after a reset) and the last
        # occupancy tuple (gauges re-set only when it moves) — keeps
        # per-step recording to a handful of attribute writes so the
        # 3% overhead gate holds even on tiny/fast models
        self._mgen = -1
        self._mx = None
        self._last_occ = None
        # cost observatory: dispatch-tag -> static flops/bytes (loaded
        # from an AOT artifact's manifest at warmup, or via
        # costs.measure_dispatch_costs). Empty = one failed dict.get
        # per step and no mfu gauges — the costless default.
        self._dispatch_costs: dict = {}
        self._peak_flops = None
        self._last_mfu = None
        # crash forensics: a propagating step() exception (the PR-8
        # worker-death path) auto-dumps a postmortem bundle here
        self.postmortem_dir = (postmortem_dir
                               or os.environ.get(
                                   'PADDLE_TPU_POSTMORTEM_DIR')
                               or None)
        self._postmortem_seq = 0
        self.last_postmortem = None
        # what the step just ended delivered: (rid, first_index, n) per
        # request that received tokens, rewritten by every step()
        self.last_deliveries = []
        # journal edge-trigger for pool-pressure pauses: the counter
        # ticks every paused sweep, but the rid-keyed journal event
        # fires once per STALL (a multi-hour stall must not grow the
        # held head's live — hence unevictable — trail per step)
        self._paused_head = None
        # live operability layer (docs/observability.md#slo-watchdog):
        # a windowed timeseries committed at the existing per-window
        # sync, an SLO watchdog evaluated per committed window, and an
        # opt-in ops HTTP endpoint. With none of the knobs set the
        # engine feeds the PROCESS-default ring (so `serve.tok_s` is
        # live for free) and runs no watchdog — zero new journal
        # events, prior behavior bit-identical. Any knob set gives the
        # engine a PRIVATE ring: its window BOUNDARIES and interval
        # are its own (another engine's commit cadence can't shear its
        # SLO windows), but the windowed DATA still comes from the
        # process-global registry — per-replica SLO isolation means
        # one engine per process, the dp-replica fleet shape (or a
        # custom Watchdog over a WindowedTimeseries(registry=...)).
        # `draining` flips /healthz to 503 and refuses new
        # submissions — the rolling-restart half the supervisor recipe
        # needs (drain, wait out in-flight, snapshot, close(), hand
        # off).
        self.draining = False
        self.ops_server = None
        self._watchdog = None
        # a private registry forces the private ring too: the whole
        # point of metrics_registry= is per-replica series, and the
        # windowed rate gauges ARE series (they must derive from and
        # publish into THIS replica's registry, not the process one)
        private = self._registry is not _obs.REGISTRY
        want_ops = (ops_port is not None or watchdog is not None
                    or slo_rules is not None or ts_interval_s is not None
                    or private)
        if want_ops:
            self._ts = _obs_ts.WindowedTimeseries(
                interval_s=(1.0 if ts_interval_s is None
                            else float(ts_interval_s)),
                registry=self._registry if private else None,
                journal=self._jr if private else None)
            if watchdog is not False:
                if isinstance(watchdog, _obs_wd.Watchdog):
                    self._watchdog = watchdog
                    if self._watchdog.postmortem_engine is None:
                        self._watchdog.postmortem_engine = self
                    if private and self._watchdog.registry is None:
                        self._watchdog.registry = self._registry
                    if private and self._watchdog.journal is None:
                        self._watchdog.journal = self._jr
                else:
                    rules = (slo_rules if slo_rules is not None
                             else _obs_wd.default_serving_rules(
                                 engine=self))
                    self._watchdog = _obs_wd.Watchdog(
                        rules, postmortem_engine=self,
                        registry=self._registry if private else None,
                        journal=self._jr if private else None)
        else:
            self._ts = _obs_ts.TIMESERIES
        if ops_port is not None:
            self.ops_server = _start_ops_server(
                self, port=ops_port, host=ops_host,
                registry=self._registry if private else None,
                journal=self._jr if private else None)
        self._update_gauges()

    # -- bookkeeping -------------------------------------------------------

    def _two_kinds(self, model, **options):
        """The index of the recycled kind of a two-kind model, after
        refusing what a second kind of page has no path through yet
        (ROADMAP M2 queues each)."""
        name = type(model).__name__
        ring = [i for i, k in enumerate(self._kinds) if k.window is not None]
        if len(self._kinds) != 2 or len(ring) != 1:
            raise PageKindsUnsupported(
                f'{name} states {len(self._kinds)} kinds of KV page, '
                f'{len(ring)} with a window: the engine runs one kind, or '
                f'one that keeps every page beside one recycled behind '
                f'its window')
        why = {
            'prefix_cache': 'the prefix index shares pages of one kind',
            'prefill_chunk': 'a chunk continuation gathers its prefix '
                             'from one table',
            'draft': "a draft's pools mirror one table",
            'tp': 'the pools of two kinds have no sharding rule',
            'kv_cache_dtype': 'int8 pools have one shape',
            'phase_role': 'a migration blob holds one kind of page'}
        plain = {'prefix_cache': False, 'prefill_chunk': None,
                 'draft': None, 'tp': 1, 'kv_cache_dtype': None,
                 'phase_role': 'monolithic'}
        for option, value in options.items():
            if value != plain[option]:
                raise PageKindsUnsupported(
                    f'{name} keeps two kinds of KV page '
                    f'({", ".join(k.name for k in self._kinds)}): '
                    f'ServingEngine({option}={value!r}) is not carried for '
                    f'it ({why[option]})')
        return ring[0]

    def _one_kind(self, what):
        if self._ring is not None:
            raise PageKindsUnsupported(
                f'{type(self.model).__name__} keeps two kinds of KV page: '
                f'{what} moves one kind (a migration blob holds one table)')

    def _by_kind(self, kept, recycled):
        """One value a kind of page in the model's own order, or `kept`
        alone for a model of one kind."""
        if self._ring is None:
            return kept
        return (recycled, kept) if self._ring == 0 else (kept, recycled)

    @contextlib.contextmanager
    def _use_mesh(self):
        """Pin the process-global mesh to THIS engine's mesh (None
        included) for a dispatch: the traced bodies' sharding pins and
        the model's own `sharding_constraint`s read `get_mesh()` at
        trace time, so every trace — warmup or live — must see exactly
        the engine's mesh regardless of ambient state. Identity-check
        fast path: steady-state steps under an already-matching (or
        absent) global mesh pay two attribute reads."""
        from ..distributed import mesh as _mesh_mod

        prev = _mesh_mod.get_mesh()
        if prev is self.mesh:
            yield
            return
        _mesh_mod.set_mesh(self.mesh)
        try:
            yield
        finally:
            _mesh_mod.set_mesh(prev)

    def _put(self, x):
        """Upload one host-fed dispatch arg. Unsharded engines:
        `jnp.asarray` (prior behavior, byte for byte). TP engines:
        committed-REPLICATED over the mesh — mixing committed sharded
        pools with uncommitted single-device mirrors would give the
        first and second dispatch of a geometry different input
        shardings (one retrace each), and warm-attach zero-compile
        plus the steady-state zero-retrace contract both need the key
        stable from call one."""
        if self._rep is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._rep)

    def _sampling_key(self):
        return (self.max_new_tokens, self.temperature, self.top_k,
                self.top_p, self.eos_token_id)

    def _geometry(self):
        # tp is part of the geometry: a tp=1 and a tp=2 engine over
        # the same pool shape dispatch DIFFERENT executables (jax keys
        # them by input sharding), so the CompileCache registry must
        # not let their notes collide either. A speculative engine
        # additionally folds in its draft's identity + window: two
        # engines over the same target but different drafts trace
        # different programs.
        # So is the admission batch's token budget: it fixes the row
        # count of every prefill program (`_prefill_rows`).
        g = ('paged', self.max_slots, self.allocator.num_blocks,
             self.block_size, self.max_blocks_per_seq, PREFILL_TOKENS,
             self.tp)
        if self.draft is not None:
            from .engine import model_tag

            g = g + ('spec', self.spec_window, model_tag(self.draft))
        return g

    def registry_key(self, *tag):
        """The EXACT CompileCache key `_note(*tag)` records (the shared
        recipe: pool shape + POOL dtype + sampling config + `tag` +
        geometry). A tag is a dispatch's kind and then the buckets
        `_SERVE_DISPATCHES` lists for it, e.g. ('serve_step', W, Sb).
        The pool dtype (int8 vs the model's cache dtype) keys here, so
        a quantized and an unquantized engine over one model never
        collide. Exposed so aot.GeometrySet enumeration and the live
        engine provably agree key-for-key."""
        return COMPILE_CACHE.key(
            self.model, self._pages[0].kp.shape,
            self._pages[0].kp.dtype,
            self._sampling_key() + tag, geometry=self._geometry())

    def _note(self, *tag):
        """Record one engine-level registry key. Returns the registry
        verdict — True on hit, False when the key is NEW (this dispatch
        pays trace + compile; step() turns that into a compile span
        with the measured wall duration)."""
        return COMPILE_CACHE.note(self.registry_key(*tag))

    # scoped-telemetry writers: a private-registry replica's counters/
    # gauges land in ITS registry (the fleet's per-replica signals),
    # a default engine hits the module conveniences byte-for-byte.
    # compile.* stays global on purpose — engine.py's trace counters
    # are process truth either way.
    def _inc(self, name, n=1):
        if self._registry is _obs.REGISTRY:
            _obs.inc(name, n)
        elif _obs.enabled():
            self._registry.counter(name).inc(n)

    def _set_gauge(self, name, v):
        if self._registry is _obs.REGISTRY:
            _obs.set_gauge(name, v)
        elif _obs.enabled():
            self._registry.gauge(name).set(v)

    def _record(self, kind, **fields):
        self._jr.record(kind, **fields)

    def _metrics(self):
        """Cached registry handles for the hot per-step records (the
        generation check makes a registry reset() safe: stale handles
        are re-resolved instead of written into orphaned objects)."""
        R = self._registry
        if self._mgen != R.generation:
            self._mx = {
                'ttft': R.histogram('serve.ttft_ms'),
                'itl': R.histogram('serve.itl_ms'),
                'qwait': R.histogram('serve.queue_wait_ms'),
                'step_ms': R.histogram('serve.step_ms'),
                'steps': R.counter('serve.steps'),
                'tokens': R.counter('serve.tokens'),
                'in_flight': R.gauge('serve.in_flight'),
                'queue_depth': R.gauge('serve.queue_depth'),
                'pages_in_use': R.gauge('pool.pages_in_use'),
                'util': R.gauge('pool.utilization'),
                'bytes_in_use': R.gauge('pool.bytes_in_use'),
                'bytes_total': R.gauge('pool.bytes_total'),
                'pressure': R.gauge('serve.pool_pressure'),
                'pfx_shared': R.gauge('pool.prefix_shared_pages'),
                'pfx_cached': R.gauge('pool.prefix_cached_pages'),
                'pfx_cow': R.gauge('pool.prefix_cow_pages'),
                'pfx_shared_b': R.gauge('pool.prefix_shared_bytes'),
                'pfx_cached_b': R.gauge('pool.prefix_cached_bytes'),
                'migration_ms': R.histogram('serve.migration_ms'),
            }
            self._mgen = R.generation
            self._last_occ = None          # force a gauge refresh
        return self._mx

    def _update_gauges(self):
        """Occupancy/pool gauges, refreshed at the step boundary only
        when occupancy actually moved (host bookkeeping only; a steady
        full batch skips all six writes)."""
        if not _obs.enabled():
            return
        m = self._metrics()
        a, wa = self.allocator, self.win_allocator
        occ = (self.in_flight(), len(self.queue), a.in_use(),
               a.cached(), a.shared(), a.cow_count)
        if wa is not None:
            occ += (wa.in_use(),)
        if occ == self._last_occ:
            return
        self._last_occ = occ
        if wa is not None:
            # the recycled kind's pool beside the other's `pool.*`
            self._set_gauge('pool.window.pages_in_use', occ[6])
            self._set_gauge('pool.window.utilization', wa.utilization())
            self._set_gauge('pool.window.bytes_in_use',
                            occ[6] * wa.bytes_per_page)
            self._set_gauge('pool.window.bytes_total',
                            wa.num_blocks * wa.bytes_per_page)
        m['in_flight'].set(occ[0])
        m['queue_depth'].set(occ[1])
        m['pages_in_use'].set(occ[2])
        m['util'].set(a.utilization())
        # watermark-relative pool pressure: 1.0 == AT the admission
        # watermark (>= 1.0 means admission is pausing)
        m['pressure'].set(a.utilization() / self.admit_watermark)
        m['pfx_cached'].set(occ[3])
        m['pfx_shared'].set(occ[4])
        m['pfx_cow'].set(occ[5])
        if a.bytes_per_page:
            m['bytes_in_use'].set(occ[2] * a.bytes_per_page)
            m['bytes_total'].set(a.num_blocks * a.bytes_per_page)
            m['pfx_cached_b'].set(occ[3] * a.bytes_per_page)
            m['pfx_shared_b'].set(occ[4] * a.bytes_per_page)

    def in_flight(self):
        return sum(r is not None for r in self._slot_req)

    def stats(self):
        """Serving observability: throughput, occupancy, pool
        utilization, scheduling counters, and the shared retrace
        counters (steady-state serving must hold total_traces flat —
        bench.py's gate_serve_retrace_zero asserts it)."""
        return {
            'trace_counts': trace_counts(),
            'total_traces': total_traces(),
            'tokens_generated': self._tokens_out,
            'tokens_per_s': (self._tokens_out / self._serve_time
                             if self._serve_time > 0 else 0.0),
            'in_flight': self.in_flight(),
            'queue_depth': len(self.queue),
            'preemptions': self.preemption_count,
            'resilience': {'max_queue': self.max_queue,
                           'admit_watermark': self.admit_watermark,
                           'shed_policy': self.shed_policy,
                           **self.counts},
            'prefix': {'enabled': self.prefix_cache,
                       'prefill_chunk': self.prefill_chunk,
                       **self.prefix_counts,
                       **self.allocator.stats()['prefix']},
            # host-truth speculative record: accept_rate is accepted
            # draft tokens over proposed (None before the first window)
            'spec': {'enabled': self.draft is not None,
                     'num_draft_tokens': self.spec_window,
                     'kv_cache_dtype': (str(self.kv_cache_dtype)
                                        if self.kv_cache_dtype else None),
                     **self.spec_counts,
                     'accept_rate': (
                         self.spec_counts['accepted']
                         / self.spec_counts['proposed']
                         if self.spec_counts['proposed'] else None)},
            # host-truth MFU record of the last all-hit window (tag,
            # static flops, wall) — what gate_flight_recorder checks
            # the serve.mfu_est gauge and the AOT manifest against
            'mfu': self._last_mfu,
            # host-truth health verdict (None when no watchdog is
            # configured) + drain state — what /statusz and a
            # supervisor poll without parsing /healthz
            'watchdog': (self._watchdog.verdict()
                         if self._watchdog is not None else None),
            'draining': self.draining,
            # disaggregated serving: which phase this engine runs, and
            # the host-truth migration record (export/import/handoff
            # counts + blob bytes moved)
            'phase_role': self.phase_role,
            'migration': dict(self.migration_counts),
            'blocks': self.allocator.stats(),
            # the recycled kind's pool of a two-kind model, else None
            'blocks_window': (self.win_allocator.stats()
                              if self.win_allocator is not None else None),
            'geometry': {'kind': 'paged', 'max_slots': self.max_slots,
                         'block_size': self.block_size,
                         'num_blocks': self.allocator.num_blocks,
                         'max_blocks_per_seq': self.max_blocks_per_seq,
                         'decode_window': self.decode_window,
                         'tp': self.tp},
        }

    # -- AOT artifact hooks (paddle_tpu.aot) -------------------------------

    def aot_config(self):
        """Compilation-relevant config as a dict of primitives (what
        two engines must share for one EngineArtifact to serve both;
        weights are structure, not values — see DecodeEngine)."""
        from .engine import model_struct, model_tag

        return {
            'engine': 'ServingEngine',
            'model': model_tag(self.model),
            'model_struct': model_struct(self.model),
            'cache_dtype': str(self.model.cache_dtype()),
            'max_slots': self.max_slots,
            'block_size': self.block_size,
            'num_blocks': self.allocator.num_blocks,
            'max_context_len': self.max_context_len,
            'max_new_tokens': self.max_new_tokens,
            'decode_window': self.decode_window,
            'temperature': self.temperature,
            'top_k': self.top_k,
            'top_p': self.top_p,
            'eos_token_id': self.eos_token_id,
            'buckets': list(self.buckets),
            # the admission batch's width is `_prefill_rows(bucket)`: an
            # artifact built under another budget holds programs of
            # other row counts, which this engine would never look up
            'prefill_tokens': PREFILL_TOKENS,
            'prefix_cache': self.prefix_cache,
            'prefill_chunk': self.prefill_chunk,
            # speculative + quantized serving are compilation-relevant:
            # a spec artifact's executables close over the draft's
            # structure, an int8 artifact's over the pool dtype —
            # attaching across either must refuse (ArtifactMismatch
            # names the field)
            'kv_cache_dtype': (str(self.kv_cache_dtype)
                               if self.kv_cache_dtype else None),
            'num_draft_tokens': self.spec_window,
            'draft': (model_tag(self.draft) if self.draft is not None
                      else None),
            'draft_struct': (model_struct(self.draft)
                             if self.draft is not None else None),
            # the mesh degree is compilation-relevant: a tp=4
            # artifact's executables are 4-shard SPMD programs a tp=1
            # engine can never look up — attaching across degrees must
            # refuse (ArtifactMismatch names this field)
            'tp': self.tp,
        }

    def _aot_jitted_fns(self):
        """The module-level jitted steps this engine's geometries
        dispatch — what `aot.build` cache-evicts (per FUNCTION, not
        process-wide) to force real persisting compiles."""
        return tuple(fn for fn, _ in _SERVE_DISPATCHES.values())

    # -- the serve dispatches' calling convention --------------------------

    def _window_tail(self, dev, budget):
        """The per-slot arguments every window-bearing dispatch ends
        its positional list with (but for `carried`), in call order."""
        return (dev['btab'], dev['ctx'], dev['live'], budget, dev['temp'],
                dev['topk'], dev['topp'], dev['seed'], dev['plen'])

    def _dispatch(self, kind, *key, batch=(), carried=(), tail=(),
                  draft=False):
        """THE calling convention of the nine jitted serve dispatches:
        the one place, besides a function's own `def`, that orders its
        arguments, names its statics and says which outputs are the
        donated pools coming back. `step()`, the migration pair and
        `_dispatches` (warm-up, cost specs, export) all call through
        it, so they cannot disagree. `(kind, *key)` is the registry
        tag; `batch` is the admission batch (`_prefill_args`), the
        chunk batch (`_chunk_args` less its buckets) or the migration
        payload; `carried` the (forced_tok, forced) pair; `tail` is
        `_window_tail`'s. `draft=True` asks for the draft-side leg of
        a prefill, export or import (`serve_draft_chunk` is always
        one): the draft's pools, all-dummy slots, `primary` False.
        Builds a record and calls nothing: `_run` makes the call."""
        fn, tag = _SERVE_DISPATCHES[kind][0], (kind, *key)
        models, statics, primary = (self.model,), {}, not draft
        eos = self.eos_token_id
        if kind in ('serve_export', 'serve_import'):        # (Cx)
            field = '_dpages' if draft else '_pages'
            models, args = (), (getattr(self, field),) + batch
            statics = dict(ctx_bucket=key[0])
            rebind = ((field, None),) if kind == 'serve_import' else ()
        elif draft or kind == 'serve_draft_chunk':          # (Sb) | (Cb, Sb)
            # ids, lengths[, starts], block tables, SLOTS[, CoW pairs]:
            # the leg commits no logits (`_dlogits` only donates and
            # comes back), so its slots are all dummies
            at = 3 if kind == 'serve_prefill' else 4
            models, primary = (self.draft,), False
            args = ((self._dpages, self._dlogits) + batch[:at]
                    + (self._dummy(batch[0].shape[0]),) + batch[at + 1:])
            if kind == 'serve_draft_chunk':
                statics = dict(ctx_bucket=key[1])
            rebind = (('_dlogits', 0), ('_dpages', 1))
        elif kind == 'serve_prefill':                       # (Sb)
            args = (self._pages, self._last_logits) + batch
            rebind = (('_last_logits', 0), ('_pages', 1))
        elif kind in ('serve_spec_step', 'serve_spec_window'):
            # (k[, Sb], Cx) -> cand, ncommit, next_tok, last_logits,
            # pages, dpages, ctx
            models = (self.model, self.draft)
            args = ((self._pages, self._dpages, self._last_logits) + batch
                    + carried + tail)
            statics = dict(k=key[0], ctx_bucket=key[-1], eos_token_id=eos)
            rebind = (('_last_logits', 3), ('_pages', 4), ('_dpages', 5))
        else:
            # (W[, Sb] | W, Cb, Sb) -> toks, last_logits, pages, ctx, routed
            args = (self._pages, self._last_logits) + batch + tail
            statics = dict(window=key[0], eos_token_id=eos)
            if kind == 'serve_chunk_step':
                args += carried
                statics['ctx_bucket'] = key[2]
            rebind = (('_last_logits', 1), ('_pages', 2))
        return _Dispatch(fn, models, args, statics, tag, rebind, primary)

    def _run(self, d):
        """Make the call a `_dispatch` record states, re-assign the
        donated pools from its outputs, and return the outputs."""
        out = d.fn(*d.models, *d.args, **d.statics)
        for field, i in d.rebind:
            setattr(self, field, out if i is None else out[i])
        return out

    def _dispatches(self, g):
        """The jitted calls ONE enumerated geometry stands for, as
        `_dispatch` records over an all-dummy batch: real_len 0 rows
        land on the scratch page, slot indices max_slots drop their
        logits on the OOB scatter, live=False freezes every row, a
        zero export length reads and all-zero import targets write
        only the scratch page. The primary call comes first, then the
        draft-side legs the live path runs beside it. The arguments
        come from the builders step() uses (`_prefill_args`,
        `_device_state`, `_blob_device_entries`), so the avals are the
        live ones by construction. Lazy, and nothing is called here:
        a record reads the pools as they are when it is asked for (a
        consumer that runs one re-binds them before the next), and no
        consumer keeps one (it would keep the pools alive)."""
        kind, *key = self._geometry_cost_tag(g)
        K, z = self.max_slots, self._zero_ftok

        def rows(n, width=None, fill=0):
            return self._put(np.full(
                (n,) if width is None else (n, width), fill, np.int32))

        def chunk_batch(Cb):
            return (rows(K, Cb), z, z, rows(K, self.max_blocks_per_seq),
                    rows(K, fill=K), z, z)

        batch = tail = ()
        if kind in ('serve_step', 'serve_spec_step', 'serve_prefill'):
            batch = self._prefill_args(g.params['bucket'], [])
        elif kind == 'serve_chunk_step':
            batch = chunk_batch(key[1])
        elif kind == 'serve_export':
            batch = (rows(1, self.max_blocks_per_seq), rows(1))
        elif kind == 'serve_import':
            batch = (self._blob_device_entries(self._pages, key[0]),
                     rows(key[0]), rows(key[0]))
        if kind not in ('serve_prefill', 'serve_export', 'serve_import'):
            tail = self._window_tail(self._device_state(),
                                     self._put(self._budget))
        yield self._dispatch(kind, *key, batch=batch, tail=tail,
                             carried=(z, self._zero_forced))
        if self.draft is None:
            return
        catch_up = None
        if kind in ('serve_prefill', 'serve_export'):
            yield self._dispatch(kind, *key, batch=batch, draft=True)
        elif kind == 'serve_import':
            yield self._dispatch(
                kind, *key, draft=True, batch=(self._blob_device_entries(
                    self._dpages, key[0]),) + batch[1:])
        elif kind == 'serve_chunk_step':
            yield self._dispatch('serve_draft_chunk', *key[1:], batch=batch)
            catch_up = key[2]
        elif (self.prefill_chunk is not None or self.prefix_cache
                or self.phase_role == 'decode'):
            # chunk steps can commit window tokens past the draft; the
            # catch-up `_draft_chunk` shapes a live spec step can then
            # dispatch (hole bucket x THIS geometry's ctx bucket) must
            # be warm too, or a warm-attached engine would compile
            # mid-serve (decode-role pools re-enter through the
            # one-token continuation chunk, which opens the same hole)
            catch_up = key[-1]
        # holes are bounded by one decode window per step, so their
        # chunk buckets are the ladder entries at or below
        # bucket(decode_window); a shape already dispatched is warm
        v = 1
        while catch_up is not None and v <= self.decode_window:
            cb = bucket_length(v, self.buckets)
            v = cb + 1
            if (cb, catch_up) not in self._draft_shapes:
                yield self._dispatch('serve_draft_chunk', cb, catch_up,
                                     batch=chunk_batch(cb))

    def _primary(self, g):
        return next(d for d in self._dispatches(g) if d.primary)

    def _warm_geometry(self, g, draft=None):
        """Drive ONE enumerated geometry through the SAME module-level
        jitted steps the scheduler dispatches, with `_dispatches`'
        all-dummy batch — so warming an IDLE engine (enforced below)
        mutates no scheduler state beyond the (donated, re-assigned)
        device pools."""
        p = g.params
        W = self.decode_window
        if p.get('window', W) != W:
            raise ValueError(
                f'geometry {g.label()} was enumerated for decode_window '
                f"{p['window']}, engine has {W}")
        if 'spec' in p and self.draft is None:
            raise ValueError(
                f'geometry {g.label()} needs a speculative engine '
                f'(construct with draft=...)')
        if 'spec' in p and int(p['spec']) != self.spec_window:
            raise ValueError(
                f"geometry {g.label()} was enumerated for "
                f"num_draft_tokens {p['spec']}, engine has "
                f'{self.spec_window}')
        if self.in_flight():
            # the dummy batch is only inert when every slot is empty: a
            # LIVE row would really decode through the dummy window
            # (pages written, last_logits advanced) while the host
            # mirror commits nothing — silent token corruption for
            # every in-flight request
            raise RuntimeError(
                f'cannot warm a ServingEngine with {self.in_flight()} '
                f'request(s) in flight: drain the batch (run()) before '
                f'warmup/aot.build')
        with self._use_mesh():
            for d in self._dispatches(g):
                if d.primary:
                    self._note(*d.tag)
                elif d.fn is _draft_chunk:
                    self._draft_shapes.add(d.tag[1:])
                self._run(d)

    def warmup(self, artifact=None, geometries=None, draft=None):
        """Pre-populate the module-level jit caches (and the
        CompileCache registry) for every geometry this engine's config
        implies, BEFORE the first request — with an `aot.EngineArtifact`
        the compiles are persistent-cache disk reads, so a fresh
        replica's first request is ZERO compiles. Returns a report
        dict; see docs/aot_warmup.md."""
        from ..aot.artifact import warm_attach

        return warm_attach(self, artifact=artifact, geometries=geometries,
                           draft=draft)

    def _export_specs(self, g, draft=None):
        """[(suffix, jitted_fn, args)] for `aot.build(...,
        export_stablehlo=True)`: the geometry's primary dispatch with
        the model(s) closed over (the jit.save idiom — a Layer in the
        calling convention would refuse to serialize) and the rest as
        avals; the page pools stay ARGS (they are state, not weights —
        the exported module must take them, and PagedKVCache is a
        registered serializable container)."""
        if g.kind in ('serve_export', 'serve_import'):
            raise NotImplementedError(
                f'no StableHLO export for geometry kind {g.kind!r}')
        d = self._primary(g)
        # tracelint: disable=TL001 - one-shot export wrapper (model
        # and statics baked into the closure; never a hot path)
        fn = jax.jit(functools.partial(d.fn.__wrapped__, *d.models,
                                       **d.statics))
        return [('', fn, _avals(d.args))]

    def _cost_specs(self, g, draft=None):
        """[(jitted_fn, args, static_kwargs)] for
        `observability.costs.geometry_cost`: the geometry's primary
        dispatch, the SAME module-level jitted step the scheduler
        calls, over avals with the live model(s) leading — so the
        lowered HLO (and its cost analysis) is exactly the served
        executable's, not a weights-as-constants export variant."""
        d = self._primary(g)
        return [(d.fn, d.models + _avals(d.args), d.statics)]

    def _geometry_cost_tag(self, g):
        """The dispatch tag `step()` keys its registry notes with, for
        one enumerated geometry — the join key between the manifest's
        per-geometry costs and the live window-commit MFU math, and
        what `aot.GeometrySet.registry_keys` hands `registry_key`."""
        if g.kind not in _SERVE_DISPATCHES:
            raise ValueError(f'unknown serving geometry kind {g.kind!r}')
        return (g.kind, *(int(g.params[n])
                          for n in _SERVE_DISPATCHES[g.kind][1]))

    def _note_geometry_cost(self, g, cost):
        """Bind one geometry's static flops/bytes (an aot manifest's
        `cost` entry, or costs.geometry_cost output) to its dispatch
        tag. From then on every all-hit window commit derives
        `serve.mfu_est` / roofline gauges from host data alone — the
        static flops and the wall clock the commit already reads."""
        if not isinstance(cost, dict) or not cost.get('flops'):
            return
        self._dispatch_costs[self._geometry_cost_tag(g)] = cost
        if self._peak_flops is None:
            from ..observability import costs as _costs

            self._peak_flops = _costs.device_peak_flops()

    # -- public API --------------------------------------------------------

    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_s=None, temperature=None, top_k=None,
               top_p=None, seed=None):
        """Queue one request; returns its id for `result()`. Validated
        against the pool so an undeliverable request fails HERE, not as
        a livelock mid-serve. `deadline_s` (seconds from now) bounds
        total latency: a request still unfinished past it transitions
        to state 'expired' at the next window commit (or at admission,
        if it expires while queued). Raises `QueueFull` when the queue
        is at `max_queue` and the shed policy keeps the newcomer out —
        the caller's backpressure signal.

        `temperature`/`top_k`/`top_p`/`seed` are PER-REQUEST sampling
        params (default: the engine's construction-time config; seed
        defaults to the rid). They ride as slot data, so any mix of
        greedy and sampled requests shares one batch with zero
        retraces. Validated HERE with a typed `InvalidSamplingParams`
        BEFORE the prompt copy is paid: temperature < 0 and
        top_p outside (0, 1] reject; top_k clamps to the vocab (the
        `filter_logits` HF semantics — top_k > V means keep-all,
        top_k <= 0 disables the filter)."""
        temperature = (self.temperature if temperature is None
                       else float(temperature))
        if temperature < 0:
            raise InvalidSamplingParams(
                f'temperature must be >= 0 (0 = greedy), got '
                f'{temperature}')
        top_p = self.top_p if top_p is None else float(top_p)
        if not 0.0 < top_p <= 1.0:
            raise InvalidSamplingParams(
                f'top_p must be in (0, 1], got {top_p}')
        top_k = self.top_k if top_k is None else int(top_k)
        top_k = max(0, min(top_k, int(self.model.config.vocab_size)))
        if self.draining:
            # drain is admission control, not validation: refuse with
            # the same typed backpressure signal a full queue gives,
            # counted under 'rejected' so the refusals are visible
            self.counts['rejected'] += 1
            self._inc('serve.rejected')
            raise QueueFull(
                'engine draining: new submissions refused — route to '
                'another replica (drain(False) reopens admission)')
        mnt = (self.max_new_tokens if max_new_tokens is None
               else int(max_new_tokens))
        if mnt < 1:
            raise ValueError('max_new_tokens must be >= 1')
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError('deadline_s must be > 0 (seconds from now)')
        # coerced HERE so the shed decision ranks the newcomer exactly
        # as Request will store it (a fractional 0.5 must not outrank
        # the priority-0 peer it would be stored equal to)
        priority = int(priority)
        # validation and the queue-bound verdict both read the token
        # COUNT alone: a rejected submit is the designed high-frequency
        # backpressure path, so it must not pay the Request's prompt
        # copy just to throw it away. np.size is O(1) on an ndarray and
        # counts the flattened length Request.__init__ will reshape to,
        # so multi-dimensional prompts can't sneak past the fit guards
        plen = int(np.size(prompt))
        if plen == 0:
            raise ValueError('empty prompt')
        total = plen + mnt
        if total > self.max_context_len:
            raise ValueError(
                f'prompt + max_new_tokens = {total} exceeds '
                f'max_context_len {self.max_context_len}')
        if _ceil_div(total, self.block_size) > self.allocator.usable:
            raise ValueError(
                f'request needs {_ceil_div(total, self.block_size)} '
                f'pages but the pool only has {self.allocator.usable} '
                f'usable — grow num_blocks')
        victim = None
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # never shed live traffic to protect dead work: entries
            # whose deadline already passed while queued are retired
            # here (they'd be swept at admission anyway) before the
            # bound is judged
            self._sweep_expired_queue()
            if len(self.queue) >= self.max_queue:
                victim = self._shed_for(priority)  # raises QueueFull
                                                   # unless it can evict
        # the victim is only PICKED above — it is evicted after Request
        # construction succeeds, so a malformed prompt that np.asarray
        # rejects cannot cancel an innocent queued request on its way
        # to raising
        req = Request(self._rid, prompt, mnt, priority,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      sample_seed=(self._rid if seed is None
                                   else int(seed)))
        if self._jr is not _journal.JOURNAL:
            req.journal = self._jr
        if victim is not None:
            self._shed(victim)
        self._rid += 1
        if deadline_s is not None:
            req.deadline = time.perf_counter() + float(deadline_s)
            self._deadlines_live += 1
        req.mark('arrival', prompt_len=plen, max_new_tokens=mnt,
                 priority=priority)
        self._inc('serve.requests')
        self._live[req.rid] = req
        self.queue.push(req)
        return req.rid

    def _sweep_expired_queue(self):
        """Retire every queued or preempted request whose deadline has
        already passed — called when the queue bound is hit, so a
        full-of-dead-work queue never rejects live traffic (deadline
        death is not shedding: even a preempted request's generated
        work is worthless once nobody is waiting for it). Early-outs
        without scanning when no live request has a deadline armed —
        the common config on the reject hot path."""
        if not self._deadlines_live:
            return
        now = time.perf_counter()
        for r in [r for r in self.queue.live()
                  if r.deadline is not None and now >= r.deadline]:
            self.queue.remove(r)
            self._retire(r, 'expired',
                         reason='deadline exceeded while queued')

    def _shed_for(self, priority):
        """The queue is full: under 'evict', pick the lowest-priority
        (then youngest-arrival) QUEUED request for displacement if the
        newcomer at `priority` outranks it — preempted requests are
        never shed (they hold generated work). Otherwise reject the
        newcomer. Deterministic either way; returns the victim (the
        caller evicts via `_shed` once the newcomer is actually
        admissible) or raises QueueFull."""
        victim = None
        if self.shed_policy == 'evict':
            queued = [r for r in self.queue.live() if r.state == 'queued']
            if queued:
                cand = min(queued, key=lambda r: (r.priority, -r.seq))
                if cand.priority < priority:
                    victim = cand
        if victim is None:
            self.counts['rejected'] += 1
            self._inc('serve.rejected')
            raise QueueFull(
                f'queue full ({len(self.queue)}/{self.max_queue}), '
                f'policy={self.shed_policy!r}: request rejected — back '
                f'off and resubmit')
        return victim

    def _shed(self, victim):
        """Evict a `_shed_for` victim from the queue."""
        self.queue.remove(victim)
        # counted under 'shed' ONLY (count=False): serve.cancelled
        # means cancel(rid), and summing the terminal counters + shed
        # must count every request exactly once
        self._retire(victim, 'cancelled',
                     reason=f'shed: displaced by higher-priority '
                            f'arrival (queue full at {self.max_queue})',
                     count=False)
        self.counts['shed'] += 1
        self._inc('serve.shed')

    def result(self, rid):
        """Terminal outcome of a request, handed over ONCE (removed
        from the engine on retrieval, so a long-running server does not
        accumulate one record per request ever served):

          - finished  -> the (prompt + max_new_tokens) ids (eos-padded
                         past an early stop, matching
                         DecodeEngine.generate);
          - failed    -> raises RequestFailed (`.error` = the cause);
          - expired   -> raises RequestExpired;
          - cancelled -> raises RequestCancelled (`.reason` says
                         whether cancel() or load shedding);
          - still pending (queued/running/preempted) -> None;
          - unknown rid (never submitted, or already retrieved)
                      -> raises KeyError(rid).
        """
        req = self._terminal.pop(rid, None)
        if req is None:
            if rid in self._live:
                return None
            raise KeyError(rid)
        self._collect_guard.discard(rid)
        if req.state == 'finished':
            return req.result
        cls = {'failed': RequestFailed, 'expired': RequestExpired,
               'cancelled': RequestCancelled}[req.state]
        raise cls(rid, req.reason, error=req.error)

    def status(self, rid):
        """Current state string for a known request (non-destructive —
        `result()` still hands the outcome over). KeyError when the rid
        is unknown or its result was already retrieved."""
        req = self._live.get(rid) or self._terminal.get(rid)
        if req is None:
            raise KeyError(rid)
        return req.state

    def cancel(self, rid):
        """Drop a request: frees its pages (running), removes it from
        the queue (queued/preempted — requeue-safe: a preempted
        request's stale heap entry is discarded lazily). Returns True
        when this call cancelled it, False when it was already
        terminal; KeyError for unknown rids. Takes effect at the host
        scheduler boundary — the engine is single-threaded."""
        req = self._live.get(rid)
        if req is None:
            if rid in self._terminal:
                return False
            raise KeyError(rid)
        if req.state in ('queued', 'preempted'):
            self.queue.remove(req)
        else:                     # running: release its slot and pages
            slot = self._slot_req.index(req)
            self._clear_slot(slot)
        self._retire(req, 'cancelled', reason='cancelled by caller')
        self._update_gauges()
        return True

    def drain(self, on=True):
        """Stop accepting new work while in-flight requests finish —
        the supervisor's rolling-restart first half (drain, wait for
        `in_flight() == 0` stepping the remainder out, `snapshot()`,
        hand off). While draining, `submit()` refuses with QueueFull
        (counted under 'rejected') and `/healthz` answers 503
        `{"status": "draining"}` so a router stops sending traffic
        immediately, whatever the SLO rules say. `drain(False)`
        reopens admission."""
        on = bool(on)
        if on == self.draining:
            return
        self.draining = on
        self._record('drain', on=on)
        self._set_gauge('serve.draining', 1.0 if on else 0.0)

    def close(self):
        """Release the engine's external resources — today that is the
        ops HTTP server's listening socket and thread (idempotent;
        engines without `ops_port` have nothing to release). The
        supervisor hand-off MUST call this on the old replica before
        binding a replacement on the same port: a daemon server thread
        dies with the process, not with the engine object, so two
        engine generations in one process would otherwise collide with
        EADDRINUSE."""
        if self.ops_server is not None:
            self.ops_server.close()
            self.ops_server = None

    def serve(self, prompts, max_new_tokens=None):
        """Submit + run + collect, preserving submission order.

        When a `max_queue` bound is configured, submission interleaves
        with scheduler steps (client backoff in miniature): a QueueFull
        reject drains one iteration and retries, so the convenience API
        never trips its own engine's admission control.
        """
        prompts = list(prompts)
        rids = []
        # guard this batch's terminal records against the max_terminal
        # eviction: serve() is the one caller that WILL collect every
        # record, so the bound that protects against abandonment must
        # not evict outputs the collection loop below is about to
        # return. result() releases each rid as it hands the outcome
        # over; after a raise below the remainder stays guarded (still
        # individually retrievable) until drained or until the next
        # serve() batch replaces the guard.
        self._collect_guard = set()
        for p in prompts:
            while True:
                try:
                    rid = self.submit(p, max_new_tokens)
                    break
                except QueueFull:
                    if self.draining:
                        raise       # stepping can never reopen a drain
                    self.step()
            rids.append(rid)
            self._collect_guard.add(rid)
        self.run()
        # surface the first failure BEFORE popping any finished record:
        # result() hands outcomes over destructively, so raising midway
        # through collection would throw away completed outputs — this
        # way they all stay individually retrievable via result()
        bad = next((r for r in rids if self.status(r) != 'finished'),
                   None)
        if bad is not None:
            self.result(bad)         # raises the typed terminal error
        return [self.result(r) for r in rids]

    def run(self, max_steps=None):
        """Step until queue and batch drain (or max_steps)."""
        steps = 0
        while len(self.queue) or self.in_flight():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return steps

    # -- crash-safe warm restart (snapshot / restore) ----------------------

    def _snapshot_config(self):
        """The config a snapshot must agree on to resume bit-equal:
        same model (structure hash — weights are the artifact's
        problem) and same sampling contract. Pool geometry is NOT here:
        a snapshot may restore into a bigger or smaller pool, each
        request re-validated for fit."""
        from .engine import model_struct, model_tag

        return {'model': model_tag(self.model),
                'model_struct': model_struct(self.model),
                'temperature': self.temperature, 'top_k': self.top_k,
                'top_p': self.top_p, 'eos_token_id': self.eos_token_id,
                'max_context_len': self.max_context_len}

    def _request_record(self, req, now):
        """One request as a JSON-serializable dict — the wire format
        `snapshot()` carries per request AND the `request` section of
        an `export_kv` migration blob (one schema, one versioning
        story: a blob survives exactly the process boundaries a
        snapshot does)."""
        return {
            'rid': req.rid, 'prompt': req.prompt.tolist(),
            'generated': [int(t) for t in req.generated],
            'max_new_tokens': req.max_new_tokens,
            'priority': req.priority, 'seq': req.seq,
            'state': req.state, 'reason': req.reason,
            'error': repr(req.error) if req.error is not None else None,
            'deadline_left_s': (req.deadline - now
                                if req.deadline is not None else None),
            'result': (req.result.tolist()
                       if req.result is not None else None),
            # per-request sampling params + the speculative carried
            # next-token (schema-1 compatible additions): a
            # restored sampled stream re-derives its stateless key
            # chain from (seed, generated index), and a restored
            # speculative stream resumes from exactly the verify's
            # pending choice — both bit-equal to uninterrupted
            'temperature': req.temperature, 'top_k': req.top_k,
            'top_p': req.top_p, 'sample_seed': req.sample_seed,
            'spec_next': req.spec_next,
        }

    def _rebuild_request(self, r, now):
        """Rebuild one `_request_record` dict into a live Request —
        the restore path's inverse, shared with `import_kv` (a
        migrated request keeps its identity: rid, sampling params,
        seed, generated prefix, remaining deadline, speculative carry
        all survive the hop)."""
        req = Request(r['rid'], r['prompt'], r['max_new_tokens'],
                      r['priority'],
                      temperature=r.get('temperature', self.temperature),
                      top_k=r.get('top_k', self.top_k),
                      top_p=r.get('top_p', self.top_p),
                      sample_seed=r.get('sample_seed'))
        if self._jr is not _journal.JOURNAL:
            req.journal = self._jr
        sn = r.get('spec_next')
        req.spec_next = int(sn) if sn is not None else None
        req.generated = [int(t) for t in r['generated']]
        req.seq = r['seq']
        req.state = r['state']
        req.reason = r['reason']
        req.error = r['error']          # repr string post-restore
        if r['result'] is not None:
            req.result = np.asarray(r['result'], np.int32)
        if r['deadline_left_s'] is not None:
            req.deadline = now + max(float(r['deadline_left_s']), 0.0)
        return req

    def snapshot(self):
        """JSON-serializable host state for crash recovery: every
        non-terminal request (queued / running / preempted — prompt,
        generated prefix, priority, remaining deadline, arrival seq)
        plus unretrieved terminal records, the rid/seq counters, and
        the sampling RNG key. ALL of it is host-authoritative — the
        device pools hold only KV rows that re-prefill reconstructs —
        so a supervisor can checkpoint at any scheduler boundary for
        the cost of a dict copy, rebuild a fresh engine from a PR-7
        AOT artifact, `restore()`, and finish every stream bit-equal
        to an uninterrupted greedy run (gate_resilience proves it)."""
        now = time.perf_counter()
        rec = functools.partial(self._request_record, now=now)
        live = ([rec(r) for r in self.queue]
                + [rec(r) for r in self._slot_req if r is not None])
        terminal = [rec(r) for r in self._terminal.values()]
        # flight-recorder trails ride the snapshot (JSON-able event
        # dicts), so a restored replica's `trail(rid)` is still one
        # ordered record from arrival to terminal state — restore()
        # re-injects them with the journal seq bumped past ours
        trails = {}
        if _journal.journal_enabled():
            for r in live + terminal:
                t = self._jr.trail(r['rid'])
                if t:
                    trails[str(r['rid'])] = t
        self._record('snapshot', requests=len(live),
                     terminal=len(terminal))
        return {
            'schema': SNAPSHOT_SCHEMA,
            'config': self._snapshot_config(),
            'requests': live,
            'terminal': terminal,
            'trails': trails,
            # SLO health history rides along (schema-1 compatible,
            # like 'trails'): a restored standby reports the primary's
            # breach state instead of silently re-arming every rule
            'watchdog': (self._watchdog.snapshot_state()
                         if self._watchdog is not None else None),
            'next_rid': self._rid,
            'preemptions': self.preemption_count,
            'counts': dict(self.counts),
            'prefix_counts': dict(self.prefix_counts),
            'spec_counts': dict(self.spec_counts),
            'migration_counts': dict(self.migration_counts),
            'tokens_out': self._tokens_out,
            'serve_time': self._serve_time,
            # the drain flag rides too (schema-1 compatible): a
            # standby resurrected from a draining primary's snapshot
            # must keep refusing submissions, or the router's drain
            # decision silently un-happens on failover
            'draining': self.draining,
        }

    def restore(self, snap):
        """Load a `snapshot()` into a FRESH engine (nothing submitted,
        nothing in flight). In-flight requests come back as
        'preempted' — they lost their slot to the crash and resume by
        re-prefilling prompt + generated prefix, the same machinery
        that makes ordinary preemption bit-equal. Deadlines re-arm from
        their remaining budget; rid/seq counters continue past the
        snapshot so new submissions never collide. Raises ValueError on
        a config mismatch (naming the differing fields) or a request
        that cannot fit THIS pool, RuntimeError when the engine is not
        fresh. Returns a report dict."""
        if (self.in_flight() or len(self.queue) or self._live
                or self._terminal or self._rid != self._rid_start):
            raise RuntimeError(
                'restore() needs a fresh engine: this one has requests '
                'queued, in flight, or unretrieved, or has already '
                'served traffic (its lifetime counters would be '
                'silently overwritten)')
        if snap.get('schema') != SNAPSHOT_SCHEMA:
            raise ValueError(
                f"unsupported snapshot schema {snap.get('schema')!r} "
                f'(this engine reads schema {SNAPSHOT_SCHEMA})')
        # name every missing required key at once, before any state is
        # touched — "KeyError: 'terminal'" from the middle of the loop
        # below names a symptom, not the defect (a truncated or
        # hand-built snapshot)
        missing = sorted(k for k in ('requests', 'terminal')
                         if k not in snap)
        if missing:
            raise ValueError(
                f'snapshot missing required key(s) {missing}: not a '
                f'ServingEngine.snapshot() dict (or truncated in '
                f'transit)')
        cfg = self._snapshot_config()
        got = snap.get('config', {})
        diff = sorted(k for k in cfg if got.get(k) != cfg[k])
        if diff:
            raise ValueError(
                f'snapshot config mismatch on {diff}: snapshot '
                f'{ {k: got.get(k) for k in diff} } vs engine '
                f'{ {k: cfg[k] for k in diff} }')
        now = time.perf_counter()
        max_seq = -1
        rebuild = functools.partial(self._rebuild_request, now=now)
        # validate EVERY request's fit before touching engine state: a
        # mid-loop raise would leave the standby half-restored (its
        # fresh-engine check then refuses a retry, and stepping it
        # would silently serve a subset of the snapshot's streams)
        for r in snap['requests']:
            total = len(r['prompt']) + r['max_new_tokens']
            if (total > self.max_context_len
                    or _ceil_div(total, self.block_size)
                    > self.allocator.usable):
                raise ValueError(
                    f"snapshot request {r['rid']} needs {total} context "
                    f'tokens — it cannot fit this engine '
                    f'(max_context_len {self.max_context_len}, '
                    f'{self.allocator.usable} usable pages)')
        # re-register the snapshot's flight-recorder trails FIRST (the
        # journal bumps its seq past the injected events), so the
        # 'restored'/'enqueued' marks below extend each trail in order;
        # a same-process hot standby shares the journal and injects
        # nothing (the trails are already there)
        for rid_s, evs in (snap.get('trails') or {}).items():
            self._jr.inject_trail(int(rid_s), evs)
        self._record('restore', requests=len(snap['requests']),
                     terminal=len(snap['terminal']))
        for r in snap['requests']:
            req = rebuild(r)
            if req.state == 'running':
                # its slot died with the old replica; re-enters as
                # preempted so it keeps arrival order and re-prefills
                req.state = 'preempted'
            max_seq = max(max_seq, req.seq if req.seq is not None else -1)
            req.mark('restored', state=req.state,
                     generated=len(req.generated))
            self._live[req.rid] = req
            if req.deadline is not None:
                self._deadlines_live += 1
            self.queue.push(req)
        for r in snap['terminal']:
            req = rebuild(r)
            max_seq = max(max_seq, req.seq if req.seq is not None else -1)
            self._terminal[req.rid] = req
        while len(self._terminal) > self.max_terminal:
            self._terminal.pop(next(iter(self._terminal)))
        self.queue.reset_seq(max_seq + 1)
        self._rid = max(int(snap.get('next_rid', 0)), self._rid)
        # monitoring continuity across the failover: the replica's
        # lifetime counters continue from the snapshot
        self.preemption_count = int(snap.get('preemptions', 0))
        for k, v in snap.get('counts', {}).items():
            if k in self.counts:
                self.counts[k] = int(v)
        for k, v in snap.get('prefix_counts', {}).items():
            if k in self.prefix_counts:
                self.prefix_counts[k] = int(v)
        for k, v in snap.get('spec_counts', {}).items():
            if k in self.spec_counts:
                self.spec_counts[k] = int(v)
        for k, v in snap.get('migration_counts', {}).items():
            if k in self.migration_counts:
                self.migration_counts[k] = int(v)
        self._tokens_out = int(snap.get('tokens_out', self._tokens_out))
        # without the matching serve-time, tokens_per_s would divide the
        # lifetime token total by the standby's near-zero wall time — a
        # phantom throughput spike on every failover
        self._serve_time = float(snap.get('serve_time', self._serve_time))
        # a draining primary's standby keeps refusing submissions (the
        # router decided to drain the REPLICA, not the process); older
        # snapshots without the key restore un-drained
        if snap.get('draining', False):
            self.draining = True
            self._set_gauge('serve.draining', 1.0)
        # older snapshots carry an 'rng' key from the pre-PR-15 shared
        # sampling stream; per-request stateless keys made it
        # meaningless, so it is accepted and ignored
        # continuous health history across the failover: rules matched
        # by name, so a standby with a tweaked ruleset still adopts
        # the states both sides define (a snapshot without watchdog
        # state — or a standby without a watchdog — is a no-op)
        if snap.get('watchdog') and self._watchdog is not None:
            self._watchdog.load_state(snap['watchdog'])
        self._update_gauges()
        return {'requests': len(snap['requests']),
                'terminal': len(snap['terminal']),
                'next_rid': self._rid}

    def adopt_request(self, record, trail=None):
        """Adopt ONE migrated request into this RUNNING engine — the
        fleet's scale-down path (docs/serving.md#fleet). `restore()`
        rebuilds a whole snapshot onto a fresh standby; a drain-
        migration instead scatters a victim replica's requests across
        survivors that are mid-serve, so this takes a single
        `_request_record` dict (+ its flight-recorder trail) and
        splices it in: terminal records land in `_terminal` (result()
        semantics unchanged — the rid answers on THIS replica now),
        live ones re-enter as preempted via the queue (their pages
        died with the victim; re-prefill reproduces the stream
        bit-equal, exactly the restore contract). Queue-bound exempt,
        like preemption requeues: migrated work was already admitted
        once. Raises ValueError on a rid collision (live, or terminal
        and unretrieved here) or a request this pool cannot fit —
        before any state is touched."""
        rid = int(record['rid'])
        if rid in self._live or rid in self._terminal:
            raise ValueError(
                f'adopt_request: rid {rid} already exists on this '
                f'engine — fleet rid_start strides must keep replica '
                f'id spaces disjoint')
        total = len(record['prompt']) + record['max_new_tokens']
        if (total > self.max_context_len
                or _ceil_div(total, self.block_size)
                > self.allocator.usable):
            raise ValueError(
                f'adopt_request: rid {rid} needs {total} context '
                f'tokens — it cannot fit this engine (max_context_len '
                f'{self.max_context_len}, {self.allocator.usable} '
                f'usable pages)')
        if trail:
            self._jr.inject_trail(rid, trail)
        now = time.perf_counter()
        req = self._rebuild_request(record, now=now)
        if req.state in ('finished', 'failed', 'expired', 'cancelled'):
            self._terminal[rid] = req
            while len(self._terminal) > self.max_terminal:
                self._terminal.pop(next(iter(self._terminal)))
            return rid
        if req.state == 'running':
            req.state = 'preempted'
        # fresh arrival seq on THIS engine: the victim's seq space can
        # collide with the survivor's, and a heap tie on (priority,
        # seq) would fall through to comparing Request objects
        req.seq = None
        req.mark('adopted', state=req.state,
                 generated=len(req.generated))
        self._live[rid] = req
        if req.deadline is not None:
            self._deadlines_live += 1
        self.queue.push(req)
        self._update_gauges()
        return rid

    # -- KV-cache migration (disaggregated prefill/decode serving) ---------

    def _blob_device_entries(self, pages, Cx, layers=None):
        """Device-resident per-layer scatter payloads for `_kv_import`,
        padded to the `Cx` bucket and uploaded replicated — ONE
        builder for the live import and the warmup dummy (layers=None
        -> zeros), so the warmed avals are the live ones by
        construction (the zero-mid-serve-compiles contract)."""
        from ..models.generation import RowQuantKVCache

        ents = []
        for li, pc in enumerate(pages):
            Hkv, D = int(pc.kp.shape[1]), int(pc.kp.shape[3])
            lay = layers[li] if layers is not None else None

            def up(field, shape, dtype):
                buf = np.zeros(shape, dtype)
                if lay is not None:
                    src = np.asarray(lay[field])
                    buf[0, :src.shape[0]] = src
                return self._put(buf)

            if hasattr(pc, 'ks'):
                ents.append(RowQuantKVCache(
                    up('k', (1, Cx, Hkv, D), np.int8),
                    up('v', (1, Cx, Hkv, D), np.int8),
                    up('ks', (1, Cx, Hkv), np.float32),
                    up('vs', (1, Cx, Hkv), np.float32)))
            else:
                dt = pc.kp.dtype
                ents.append((up('k', (1, Cx, Hkv, D), dt),
                             up('v', (1, Cx, Hkv, D), dt)))
        return ents

    def _check_blob_layers(self, name, layers, pages, n):
        """Structural validation of one blob KV group against THIS
        engine's pool before any allocator/block-table/pool mutation:
        layer count, field set, per-field dtype and row shape must be
        exactly what `_blob_device_entries` will scatter. A truncated
        or tampered blob fails here with the defect named — never
        mid-scatter with a broadcast error after pages were taken (the
        no-partial-scatter half of the atomic-placement contract)."""
        if not isinstance(layers, (list, tuple)) or len(layers) != len(pages):
            got = len(layers) if isinstance(layers, (list, tuple)) else \
                type(layers).__name__
            raise ValueError(
                f'corrupt KV blob: {name} carries {got} layer(s), this '
                f'engine scatters into {len(pages)}')
        for li, (lay, pc) in enumerate(zip(layers, pages)):
            Hkv, D = int(pc.kp.shape[1]), int(pc.kp.shape[3])
            if hasattr(pc, 'ks'):
                want = {'k': ((n, Hkv, D), np.dtype(np.int8)),
                        'v': ((n, Hkv, D), np.dtype(np.int8)),
                        'ks': ((n, Hkv), np.dtype(np.float32)),
                        'vs': ((n, Hkv), np.dtype(np.float32))}
            else:
                dt = np.dtype(pc.kp.dtype)
                want = {'k': ((n, Hkv, D), dt), 'v': ((n, Hkv, D), dt)}
            if not isinstance(lay, dict) or set(lay) != set(want):
                got = sorted(lay) if isinstance(lay, dict) else \
                    type(lay).__name__
                raise ValueError(
                    f'corrupt KV blob: {name}[{li}] fields {got} != '
                    f'expected {sorted(want)} for this pool')
            for field, (shape, dt) in want.items():
                a = np.asarray(lay[field])
                if tuple(a.shape) != shape or a.dtype != dt:
                    raise ValueError(
                        f'corrupt KV blob: {name}[{li}].{field} is '
                        f'{a.dtype}{tuple(a.shape)}, this pool scatters '
                        f'{dt}{shape}')

    @staticmethod
    def _blob_layer_bytes(blob):
        """Total payload bytes of a blob's KV arrays (target + draft) —
        the unit the bytes_exported/bytes_imported counters move in."""
        n = 0
        for group in ('layers', 'draft_layers'):
            for lay in blob.get(group) or []:
                n += sum(np.asarray(v).nbytes for v in lay.values())
        return n

    def export_kv(self, rid):
        """Gather running request `rid`'s paged KV (and draft KV when
        speculative) into one contiguous, process-portable migration
        blob — the prefill half of disaggregated serving
        (docs/serving.md#disaggregated-serving).

        The blob is a JSON-shaped dict plus numpy arrays: schema (1,
        shared with `snapshot()`), engine config, the full
        `_request_record` (identity, sampling params, seed, generated
        prefix, remaining deadline, speculative carry), per-layer
        contiguous K/V rows for positions [0, context_len - 1), and
        the request's flight-recorder trail. Int8 pools ship int8
        bytes + per-row f32 scales — BIT-identical pages at ~half the
        bf16 bytes. Position context_len - 1 is deliberately NOT
        shipped: the importer recomputes it through the existing
        continuation-chunk machinery, which also reproduces the next
        token's logits — so the migrated greedy stream is bit-equal
        to the source engine's own. Read-only: the request keeps
        serving here until its owner retires it (PrefillEngine's
        handoff sweep, or `cancel()`)."""
        self._one_kind('export_kv')
        t0 = time.perf_counter()
        req = self._live.get(rid)
        if req is None or req.state != 'running':
            state = req.state if req is not None else 'unknown/terminal'
            raise KeyError(
                f'export_kv needs a RUNNING request: rid {rid} is '
                f'{state!r} (queued/preempted requests have no pages '
                f'to export — snapshot() covers those)')
        slot = next(s for s, q in enumerate(self._slot_req) if q is req)
        if self._pfill[slot] is not None:
            raise RuntimeError(
                f'request {rid} is mid chunked prefill '
                f'({self._pfill[slot]}/{req.context_len} context tokens '
                f'in pages) — step until its prefill completes before '
                f'exporting')
        kvlen = req.context_len - 1
        if kvlen < 1:
            raise RuntimeError(
                f'request {rid} has no committed KV to export '
                f'(context_len {req.context_len})')
        tag = ('serve_export', bucket_length(kvlen, self.buckets))
        dkvlen = None
        with self._use_mesh():
            hit = self._note(*tag)
            t_dispatch = time.perf_counter()
            btabs = self._put(self._btab[slot:slot + 1])
            st = self._put(np.asarray([kvlen], np.int32))
            out = self._run(self._dispatch(*tag, batch=(btabs, st)))
            dout = None
            if self.draft is not None:
                # the draft pool's coverage can trail the target's
                # (window tokens the draft never saw) — ship what it
                # has; the importer's catch-up machinery fills the rest
                dkvlen = min(int(self._dctx[slot]), kvlen)
                dst = self._put(np.asarray([dkvlen], np.int32))
                dout = self._run(self._dispatch(
                    *tag, batch=(btabs, dst), draft=True))
            host = jax.device_get(out)
            dhost = jax.device_get(dout) if dout is not None else None
        t_commit = time.perf_counter()
        if not hit:
            _obs_trace.compile_event(
                'compile:serve_export', key=tag,
                dur_s=t_commit - t_dispatch,
                geometry=str(self._geometry()))
            self._record('compile', dispatch='serve_export',
                         key=str(tag),
                         dur_ms=round((t_commit - t_dispatch) * 1e3, 3))

        def crop(tmp, n):
            layers = []
            for t in tmp:
                if hasattr(t, 'ks'):
                    layers.append({'k': np.asarray(t.kq[0, :n]),
                                   'v': np.asarray(t.vq[0, :n]),
                                   'ks': np.asarray(t.ks[0, :n]),
                                   'vs': np.asarray(t.vs[0, :n])})
                else:
                    k, v = t
                    layers.append({'k': np.asarray(k[0, :n]),
                                   'v': np.asarray(v[0, :n])})
            return layers

        layers = crop(host, kvlen)
        draft_layers = crop(dhost, dkvlen) if dhost is not None else None
        nbytes = sum(v.nbytes for lay in layers for v in lay.values())
        if draft_layers is not None:
            nbytes += sum(v.nbytes for lay in draft_layers
                          for v in lay.values())
        # mark BEFORE snapshotting the trail, so the export event
        # itself rides the blob to the destination engine
        req.mark('kv_export', kv_len=kvlen, bytes=nbytes)
        blob = {
            'schema': SNAPSHOT_SCHEMA,
            'kind': KV_BLOB_KIND,
            'config': self._snapshot_config(),
            'kv_cache_dtype': (str(self.kv_cache_dtype)
                               if self.kv_cache_dtype else None),
            'block_size': self.block_size,
            'kv_len': kvlen,
            'request': self._request_record(req, time.perf_counter()),
            'layers': layers,
            'draft_kv_len': dkvlen,
            'draft_layers': draft_layers,
            'trail': (self._jr.trail(rid)
                      if _journal.journal_enabled() else []),
        }
        self.migration_counts['exported'] += 1
        self.migration_counts['bytes_exported'] += nbytes
        if _obs.enabled():
            self._metrics()['migration_ms'].observe(
                (time.perf_counter() - t0) * 1e3)
            self._inc('serve.kv_exported')
        return blob

    def import_kv(self, rid, blob):
        """Scatter an `export_kv` blob into THIS engine's pool and
        resume request `rid` — the decode half of disaggregated
        serving. The request re-enters as a one-token continuation
        chunk: the import places KV rows [0, kv_len) through the
        existing block-table machinery, then the next step's chunk
        dispatch recomputes position kv_len (= context_len - 1), which
        commits both that KV row and the first decode logits BIT-equal
        to the source engine's own step — no new dispatch kind, and
        the AOT-warmed chunk/import shapes cover it (zero mid-serve
        compiles on a warm-attached decode pool).

        Prefix-cache engines share full prompt pages below kv_len with
        the allocator's hash index (refcounts balanced); the page
        containing the recompute position stays private, so the import
        path never needs a CoW copy. Placement is ATOMIC: any failure
        — no free slot (QueueFull: retryable), a dry pool
        (OutOfBlocks), schema/config/dtype mismatch (ValueError) —
        rolls back every page and refcount taken and leaves the engine
        exactly as before the call. Returns the slot index."""
        self._one_kind('import_kv')
        t0 = time.perf_counter()
        rid = int(rid)
        if (blob.get('schema') != SNAPSHOT_SCHEMA
                or blob.get('kind') != KV_BLOB_KIND):
            raise ValueError(
                f"unsupported KV blob (schema {blob.get('schema')!r}, "
                f"kind {blob.get('kind')!r}): this engine reads "
                f"{KV_BLOB_KIND} schema {SNAPSHOT_SCHEMA}")
        # name every missing required key at once — a blob without its
        # request record or KV payload fails here with the defect
        # named, not as a KeyError from the placement machinery
        missing = sorted(k for k in ('request', 'kv_len', 'layers')
                         if k not in blob)
        if missing:
            raise ValueError(
                f'KV blob missing required key(s) {missing}: not an '
                f'export_kv blob (or stripped in transit)')
        cfg = self._snapshot_config()
        got_cfg = blob.get('config', {})
        diff = sorted(k for k in cfg if got_cfg.get(k) != cfg[k])
        if diff:
            raise ValueError(
                f'KV blob config mismatch on {diff}: blob '
                f'{ {k: got_cfg.get(k) for k in diff} } vs engine '
                f'{ {k: cfg[k] for k in diff} }')
        want = (str(self.kv_cache_dtype) if self.kv_cache_dtype else None)
        if blob.get('kv_cache_dtype') != want:
            raise ValueError(
                f"KV blob pool dtype {blob.get('kv_cache_dtype')!r} != "
                f'engine pool dtype {want!r}: migrating across '
                f'quantization worlds would break bit-equality — match '
                f'kv_cache_dtype across the pair')
        r = blob['request']
        if int(r['rid']) != rid:
            raise ValueError(f"blob carries rid {r['rid']}, not {rid}")
        if rid in self._live or rid in self._terminal:
            raise ValueError(
                f'rid {rid} is already registered on this engine — a '
                f'migrated request keeps its identity, so the '
                f'destination must not have seen it')
        if self.draft is not None and blob.get('draft_layers') is None:
            raise ValueError(
                'this engine is speculative but the blob carries no '
                'draft KV: export from a speculative source (or run '
                'the pair without a draft)')
        kvlen = int(blob['kv_len'])
        now = time.perf_counter()
        req = self._rebuild_request(r, now)
        if req.context_len != kvlen + 1:
            raise ValueError(
                f'corrupt KV blob: kv_len {kvlen} does not match the '
                f'carried request (context_len {req.context_len}; the '
                f'export contract is kv_len == context_len - 1)')
        total = len(req.prompt) + req.max_new_tokens
        if (total > self.max_context_len
                or _ceil_div(total, self.block_size)
                > self.allocator.usable):
            raise ValueError(
                f'imported request {rid} needs {total} context tokens — '
                f'it cannot fit this engine (max_context_len '
                f'{self.max_context_len}, {self.allocator.usable} '
                f'usable pages)')
        # structural check of every KV array BEFORE any allocator,
        # block-table, or pool mutation: a truncated/tampered blob
        # must leave the engine exactly as it found it
        self._check_blob_layers('layers', blob.get('layers'),
                                self._pages, kvlen)
        if self.draft is not None:
            self._check_blob_layers('draft_layers',
                                    blob.get('draft_layers'),
                                    self._dpages,
                                    int(blob.get('draft_kv_len') or 0))
        slot = next((s for s, q in enumerate(self._slot_req)
                     if q is None), None)
        if slot is None:
            raise QueueFull(
                f'no free slot for imported request {rid} '
                f'({self.max_slots} in flight) — retry after a step')
        a = self.allocator
        bs = self.block_size
        total_pages = _ceil_div(req.context_len, bs)
        shared: list = []
        if self.prefix_cache:
            hit_pages = a.match_prefix(prompt_page_hashes(req.prompt, bs))
            # share only pages FULLY below the recompute position: the
            # page holding position kvlen gets WRITTEN by the
            # continuation chunk, so it stays private — the import
            # path never needs a CoW copy (and has none to roll back)
            shared = hit_pages[:min(len(hit_pages), kvlen // bs)]
        pages: list = []
        try:
            a.phase = 'import'
            if shared:
                a.share(shared)
                pages.extend(shared)
            pages.extend(a.alloc(total_pages - len(shared)))
        except Exception:
            # atomic failure: return the shares (refcounts balanced),
            # free anything allocated, leave the pool untouched
            if pages:
                a.free(pages)
            self.migration_counts['import_failed'] += 1
            self._record('kv_import_failed', rid=rid, kv_len=kvlen)
            raise
        finally:
            a.phase = None
        Cx = bucket_length(kvlen, self.buckets)
        tag = ('serve_import', Cx)
        dkvlen = None
        if self.draft is not None:
            dkvlen = min(int(blob.get('draft_kv_len') or 0), kvlen)
        try:
            with self._use_mesh():
                reg_hit = self._note(*tag)
                t_dispatch = time.perf_counter()
                pages_np = np.asarray(pages, np.int32)
                i = np.arange(Cx)
                blk = np.minimum(i // bs, len(pages) - 1)
                # rows the pool must NOT take from the blob — past the
                # export length, or covered by shared prefix pages —
                # scatter onto the reserved scratch page instead
                live_rows = (i < kvlen) & (i >= len(shared) * bs)
                sflat = self._put((i % bs).astype(np.int32))
                pflat = self._put(
                    np.where(live_rows, pages_np[blk], 0)
                    .astype(np.int32))
                ents = self._blob_device_entries(self._pages, Cx,
                                                 blob['layers'])
                self._run(self._dispatch(
                    *tag, batch=(ents, pflat, sflat)))
                if self.draft is not None:
                    drows = (i < dkvlen) & (i >= len(shared) * bs)
                    dpflat = self._put(
                        np.where(drows, pages_np[blk], 0)
                        .astype(np.int32))
                    dents = self._blob_device_entries(
                        self._dpages, Cx, blob['draft_layers'])
                    self._run(self._dispatch(
                        *tag, batch=(dents, dpflat, sflat), draft=True))
        except Exception:
            a.free(pages)
            self.migration_counts['import_failed'] += 1
            self._record('kv_import_failed', rid=rid, kv_len=kvlen)
            raise
        t_commit = time.perf_counter()
        if not reg_hit:
            _obs_trace.compile_event(
                'compile:serve_import', key=tag,
                dur_s=t_commit - t_dispatch,
                geometry=str(self._geometry()))
            self._record('compile', dispatch='serve_import',
                         key=str(tag),
                         dur_ms=round((t_commit - t_dispatch) * 1e3, 3))
        # ONE trail follows the request across engines: re-register
        # the source's events FIRST (the journal bumps its seq past
        # them; a same-process pair shares the journal and injects
        # nothing), so the marks below extend the trail in order
        if blob.get('trail'):
            self._jr.inject_trail(rid, blob['trail'])
        self._live[rid] = req
        if req.deadline is not None:
            self._deadlines_live += 1
        self._place(slot, req, pages)
        # the import covers [0, kvlen); the continuation-chunk
        # machinery recomputes position kvlen from the carried tokens
        # on the next step (take=1 — its chunk bucket is warmed)
        self._pfill[slot] = kvlen
        self._cow_pending[slot] = None
        self._dctx[slot] = dkvlen if dkvlen is not None else kvlen
        if self.prefix_cache:
            # the imported rows ARE completed prompt KV: index the
            # full prompt pages now (shared ones stay with their first
            # writer), so later imports/admissions of the same prefix
            # hit — and count this import against the same hit/miss
            # telemetry the admission path feeds
            req.page_hashes = prompt_page_hashes(req.prompt, bs)
            self._register_prefix_pages(slot, req, 0, kvlen)
            if shared:
                self.prefix_counts['hits'] += 1
                self.prefix_counts['hit_tokens'] += len(shared) * bs
            else:
                self.prefix_counts['misses'] += 1
        self._rid = max(self._rid, rid + 1)
        nbytes = self._blob_layer_bytes(blob)
        req.mark('kv_import', kv_len=kvlen, bytes=nbytes, slot=slot,
                 shared_pages=len(shared))
        self.migration_counts['imported'] += 1
        self.migration_counts['bytes_imported'] += nbytes
        if _obs.enabled():
            self._metrics()['migration_ms'].observe(
                (time.perf_counter() - t0) * 1e3)
            self._inc('serve.kv_imported')
        self._update_gauges()
        return slot

    # -- the scheduler iteration -------------------------------------------

    def step(self):
        """One iteration: admit queued requests into free slots, top up
        pages for the coming window (preempting if the pool is dry),
        then run ONE fused jitted dispatch — admission prefill into the
        fresh pages composed with a decode window over ALL slots
        (_serve_step; _serve_window when nothing was admitted) — and
        finally commit tokens / retire finished rows from the single
        per-window host read. Returns the requests that finished this
        step; `last_deliveries` holds what it delivered, one
        `(rid, first_index, n)` per request that received tokens.

        Spans (host ring and, under a jax.profiler session, the
        profiler's trace): `serve.step` with children `serve.admit`,
        `serve.top_up`, `serve.prefill`, `serve.stage`, `serve.dispatch`,
        `serve.host_read` and `serve.commit`, each closed on every path
        out; a step with nothing to run ends `serve.step` as
        `kind='idle'`.

        Telemetry rides the step's EXISTING host points: lifecycle
        timestamps and the ttft/itl/queue-wait histograms are all
        recorded at the per-window commit (right after the one
        device_get this loop already does), so instrumentation adds no
        sync and no retrace — bench.py's gate_observability_overhead
        and gate_serve_retrace_zero both hold it to that."""
        t0 = time.perf_counter()
        _step_span = _obs_trace.span('serve.step', cat='scheduler').begin()
        self.last_deliveries = []
        try:
            # the engine's mesh (None included) is pinned for the whole
            # iteration: any trace this step pays — first-time buckets,
            # chunk pairs — sees exactly the engine's sharding world
            with self._use_mesh():
                finished = self._step_impl(t0, _step_span)
        except Exception as e:
            # the PR-8 worker-death path (a propagating window-dispatch
            # or top-up fault): drop the forensic bundle — metrics,
            # host trace, journal tail, restorable snapshot — BEFORE
            # re-raising, so the supervisor that restarts this replica
            # has the incident on disk
            self._auto_postmortem(e)
            raise
        finally:
            # ended in finally: a propagating window fault (worker
            # death) must not leak an open span into the host trace
            _step_span.end()
        # windowed timeseries + SLO watchdog ride the step boundary —
        # an existing host point that fires on EVERY outcome, including
        # a step whose whole admission group failed (nothing
        # dispatched, nothing committed — exactly the windows an
        # error-rate rule must see). OUTSIDE the try above: an
        # exception from a user-supplied on_breach callback must
        # surface as its own error, not masquerade as a worker death
        # and dump a false crash bundle. Off the interval boundary the
        # probe is two compares; on it, one pass over the registry
        # plus the rule evaluations — pure host arithmetic, zero new
        # syncs, zero retraces (gate_watchdog holds the tok/s ratio
        # within 3%)
        w = self._ts.maybe_commit(time.perf_counter())
        if w is not None and self._watchdog is not None:
            self._watchdog.evaluate(w, self._ts)
        return finished

    def _auto_postmortem(self, error):
        """Best-effort crash-bundle dump (enabled by `postmortem_dir`
        or PADDLE_TPU_POSTMORTEM_DIR; one numbered subdirectory per
        crash). NEVER raises — forensics must not mask the crash being
        recorded."""
        if not self.postmortem_dir:
            return
        try:
            from ..observability import postmortem as _postmortem

            self._postmortem_seq += 1
            out = os.path.join(
                self.postmortem_dir,
                f'postmortem-{os.getpid()}-{self._postmortem_seq}')
            self._record('postmortem', error=repr(error))
            _postmortem.dump_bundle(out, engine=self, error=error,
                                    reason='worker death in step()')
            self.last_postmortem = out
            self._inc('serve.postmortems')
        except Exception:  # noqa: BLE001 - never mask the real crash
            pass

    def _step_impl(self, t0, step_span):
        groups = self._admit()
        if not self.in_flight():
            self._serve_time += time.perf_counter() - t0
            self._update_gauges()   # admission may have expired/failed
            step_span.set(kind='idle')
            return []
        # assemble this step's CHUNK group: every slot mid chunked /
        # continuation prefill advances one chunk. Completions are
        # marked now — a slot whose last chunk commits this step
        # decodes its first window inside this very dispatch (the
        # monolithic _serve_step semantics), so the page top-up below
        # must already cover its window.
        chunk_rows = []
        for slot, req in enumerate(self._slot_req):
            p = self._pfill[slot]
            if req is None or p is None:
                continue
            take = req.context_len - p
            if self.prefill_chunk is not None:
                take = min(take, self.prefill_chunk)
            chunk_rows.append((slot, req, p, take))
        for slot, req, p, take in chunk_rows:
            self._pfill[slot] = (None if p + take >= req.context_len
                                 else p + take)
        if chunk_rows:
            self._dev = None
        top_up = _obs_trace.span('serve.top_up', cat='scheduler').begin()
        preempted = self.preemption_count
        try:
            self._ensure_window_pages()
        except Exception:
            # only an injected fault escapes the top-up (OutOfBlocks is
            # absorbed above): the 'preempt' seam, or a non-OutOfBlocks
            # alloc/free fault in the window phase. It models the
            # worker dying mid-eviction and PROPAGATES — but the groups
            # admitted THIS step have pages armed with no prefill run
            # yet, so they demote first (same hazard the window-seam
            # handler below covers), keeping the engine steppable in
            # place with sound KV on every surviving slot. Chunk rows
            # claimed progress whose dispatch now never runs — they
            # demote too and re-prefill from scratch on resume.
            for _Sb, g in groups:
                for slot, r in g:
                    if self._slot_req[slot] is r:
                        self._demote(slot, r)
            for slot, r, _p, _t in chunk_rows:
                if self._slot_req[slot] is r:
                    self._demote(slot, r)
            raise
        finally:
            top_up.end(preempted=self.preemption_count - preempted)
        # the top-up above may have preempted (or failed) a
        # just-admitted request: drop it from the prefill groups (its
        # slot is parked on the scratch page; a preempted one
        # re-prefills when re-admitted)
        kept = []
        for Sb, g in groups:
            g = [(s, r) for s, r in g if self._slot_req[s] is r]
            if g:
                kept.append((Sb, g))
        groups = kept
        chunk_rows = [(s, r, p, t) for s, r, p, t in chunk_rows
                      if self._slot_req[s] is r]
        # the chunk group's fault seam (per-request isolation, same
        # contract as a prefill group: a scripted chunk fault fails the
        # affected rows, pages freed, the rest of the batch decodes on)
        if chunk_rows and not self._chunk_seam_ok(chunk_rows):
            chunk_rows = []
        W = self.decode_window
        # admissions beyond the fused dispatch prefill standalone (a
        # step that admits across buckets, or any monolithic admission
        # landing on a step where a chunk group holds the fused slot).
        # The 'dispatch' fault seam fires BEFORE each prefill dispatch
        # (per-request failure isolation: a fault scripted for a
        # request's prefill — the poisoned-request model — fails THAT
        # admission group, pages freed, and the rest of the batch keeps
        # decoding; the real dispatch is never interrupted mid-flight,
        # so donated buffers stay sound).
        standalone = groups if chunk_rows else groups[1:]
        for Sb, group in standalone:
            if not self._prefill_seam_ok(Sb, group):
                continue
            for _s, r in group:
                r.mark('prefill_dispatch', bucket=Sb, fused=False)
            self._prefill_group(Sb, group)
            if self.prefix_cache:
                for slot, r in group:
                    self._register_prefix_pages(slot, r, 0, r.context_len)
        fused = groups[0] if groups and not chunk_rows else None
        if fused is not None and not self._prefill_seam_ok(*fused):
            fused = None
        if not self.in_flight():
            # every live slot failed at its prefill seam: nothing to
            # decode this step, and step() must not abort
            self._serve_time += time.perf_counter() - t0
            self._update_gauges()
            step_span.set(kind='idle')
            return []
        live = sum(self._live_rows())

        def stage():
            # the host-to-device uploads a dispatch waits for
            return _obs_trace.span('serve.stage', cat='scheduler')

        def dispatch(bucket=0, real_lens=(), padded_rows=None):
            # the one jitted call of the step (it returns futures), with
            # the fill of its fused admission: zeros for a bare window
            return _obs_trace.span(
                'serve.dispatch', cat='scheduler', kind=kind, live=live,
                slots=self.max_slots, **sampler_asks, **kernel_pages,
                **self._fill(bucket, real_lens, padded_rows))

        with stage():
            dev = self._device_state()
            budget = self._put(self._budget)    # shrinks every window
            kernel_pages = self._kernel_pages()
            sampler_asks = self._sampler_asks()
        tail = self._window_tail(dev, budget)
        spec = self.draft is not None and not chunk_rows
        kind = ('spec' if spec else 'chunk' if chunk_rows
                else 'step' if fused is not None else 'window')
        # a fault scripted at kind='window' models the whole worker
        # dying mid-serve and PROPAGATES out of step() by design, so a
        # supervisor snapshots and restores — the crash path
        # tests/test_resilience.py and gate_resilience exercise. Before
        # it raises, the fused group admitted THIS step is demoted back
        # to the queue: its pages are armed but its prefill rides
        # inside the dispatch that now never runs, so leaving it
        # 'running' would let a caller who keeps stepping in place
        # decode uninitialized pages (the standalone prefills above
        # already completed — every other row's KV is sound either way)
        try:
            if _faults.ACTIVE is not None:       # skip ctx build when off
                _faults.fire('dispatch', kind='window',
                             in_flight=self.in_flight())
        except Exception:
            if fused is not None:
                for slot, r in fused[1]:
                    self._demote(slot, r)
            for slot, r, _p, _t in chunk_rows:
                if self._slot_req[slot] is r:
                    self._demote(slot, r)
            raise
        if spec:
            # the draft-dispatch fault seam (testing/faults.py): a
            # draft-model fault is ISOLATING, not a worker death — it
            # fails exactly the requests whose window needed the draft
            # (every live decoding slot this step, the fused admission
            # group included), pages freed, and the engine stays
            # steppable: queued requests admit next step and decode
            # bit-equal, nothing was dispatched with a half-written
            # draft cache
            try:
                if _faults.ACTIVE is not None:
                    _faults.fire(
                        'draft_dispatch', k=self.spec_window,
                        rids=[r.rid for s, r in enumerate(self._slot_req)
                              if r is not None
                              and self._pfill[s] is None])
            except Exception as e:  # noqa: BLE001 - scripted faults
                self._fail_group(
                    [(s, r) for s, r in enumerate(self._slot_req)
                     if r is not None and self._pfill[s] is None], e)
                self._serve_time += time.perf_counter() - t0
                self._update_gauges()
                step_span.set(kind='idle')
                return []
        spec_out = routed = None
        t_dispatch = time.perf_counter()
        if spec:
            k = self.spec_window
            max_ctx = max(int(self._ctx[s])
                          for s, r in enumerate(self._slot_req)
                          if r is not None and self._pfill[s] is None)
            Sb_ctx = bucket_length(max_ctx + k + 1, self.buckets)
            with stage():
                carried = self._forced_state()
            # draft catch-up first (rows whose commits bypassed the
            # draft on a chunk step): the spec window's proposals must
            # run against complete draft KV. Sb_ctx covers every
            # row's end position by construction.
            catchup = self._draft_catchup_rows()
            fresh_draft = bool(catchup) and self._draft_advance(
                catchup, Sb_ctx)
            if fused is not None:
                Sb, group = fused
                for _s, r in group:
                    r.mark('prefill_dispatch', bucket=Sb, fused=True)
                with stage():
                    batch = self._prefill_args(Sb, group)
                dispatch_key = ('serve_spec_step', k, Sb, Sb_ctx)
                hit = self._note(*dispatch_key)
                with dispatch(Sb, [r.context_len for _s, r in group]):
                    cand, nc, nxt, _, _, _, ctx_out = self._run(
                        self._dispatch(*dispatch_key, batch=batch,
                                       carried=carried, tail=tail))
                if self.prefix_cache:
                    for slot, r in group:
                        self._register_prefix_pages(slot, r, 0,
                                                    r.context_len)
            else:
                dispatch_key = ('serve_spec_window', k, Sb_ctx)
                hit = self._note(*dispatch_key)
                with dispatch():
                    cand, nc, nxt, _, _, _, ctx_out = self._run(
                        self._dispatch(*dispatch_key, carried=carried,
                                       tail=tail))
            spec_out = (cand, nc, nxt)
            # a fresh draft catch-up shape paid its compile inside
            # this step's wall: count the window as a MISS so the
            # compile time is excluded from ITL/MFU like any other
            hit = hit and not fresh_draft
            self.spec_counts['windows'] += 1
        elif chunk_rows:
            with stage():
                batch, Cb, Sb = self._chunk_args(chunk_rows)
            for _s, r, _p, _t in chunk_rows:
                r.mark('prefill_dispatch', chunk=True, start=_p, take=_t)
            dispatch_key = ('serve_chunk_step', W, Cb, Sb)
            hit = self._note(*dispatch_key)
            if self.draft is not None:
                # keep the DRAFT's pages current through the chunk
                # path: same chunk/CoW args, logits commit dropped —
                # issued before the CoW pins are released below, so
                # both dispatches read the pinned source pages
                if (Cb, Sb) not in self._draft_shapes:
                    self._draft_shapes.add((Cb, Sb))
                    hit = False          # this step pays its compile
                self._run(self._dispatch('serve_draft_chunk', Cb, Sb,
                                         batch=batch))
                for s, _r, p, t in chunk_rows:
                    self._dctx[s] = p + t
                # decoding rows' draft holes (the PREVIOUS chunk-step
                # window's commits) catch up eagerly, so no hole ever
                # exceeds one window
                catchup = self._draft_catchup_rows()
                if catchup and self._draft_advance(
                        catchup,
                        bucket_length(max(p + t for _s, _r, p, t
                                          in catchup), self.buckets)):
                    hit = False
                # decoding rows may carry a pending verify-chosen next
                # token (spec_next): the chunk window consumes it as
                # each row's first token
                carried = self._forced_state()
            else:
                # non-speculative engines can never have forced rows —
                # the constant zero uploads skip the per-step scan
                carried = self._zero_ftok, self._zero_forced
            with dispatch(Cb, [t for _s, _r, _p, t in chunk_rows],
                          self.max_slots):
                toks, _, _, ctx_out, routed = self._run(self._dispatch(
                    *dispatch_key, batch=batch, carried=carried,
                    tail=tail))
            self.prefix_counts['chunk_steps'] += 1
            self._inc('serve.chunk_steps')
            if self._cow_release:
                # the dispatch carrying the CoW copies is issued: the
                # pinned source pages may now be freed (any future
                # writer of those pages is ordered after the copy by
                # the device dataflow through self._pages)
                self.allocator.free(self._cow_release)
                self._cow_release = []
            if self.prefix_cache:
                for slot, r, p, t in chunk_rows:
                    self._register_prefix_pages(slot, r, p, p + t)
        elif fused is not None:
            Sb, group = fused
            for _s, r in group:
                r.mark('prefill_dispatch', bucket=Sb, fused=True)
            with stage():
                batch = self._prefill_args(Sb, group)
            dispatch_key = ('serve_step', W, Sb)
            hit = self._note(*dispatch_key)
            with dispatch(Sb, [r.context_len for _s, r in group]):
                toks, _, _, ctx_out, routed = self._run(self._dispatch(
                    *dispatch_key, batch=batch, tail=tail))
            if self.prefix_cache:
                for slot, r in group:
                    self._register_prefix_pages(slot, r, 0, r.context_len)
        else:
            dispatch_key = ('serve_window', W)
            hit = self._note(*dispatch_key)
            with dispatch():
                toks, _, _, ctx_out, routed = self._run(
                    self._dispatch(*dispatch_key, tail=tail))
        # the returned ctx equals the host's post-commit view whenever
        # no slot is retired below (retiring invalidates the mirror)
        dev['ctx'] = ctx_out
        # ONE batched host read per window — the scheduler needs the
        # emitted tokens (and, speculatively, the per-slot accept
        # counts + carried next-token) to detect eos/budget and refill
        # the batch; all other state is host-authoritative.
        # tracelint: disable=TL002 - single sync per window by design
        with _obs_trace.span('serve.host_read', cat='scheduler'):
            if spec_out is not None:
                cand_h, nc_h, nxt_h = jax.device_get(spec_out)
                cand_h, nc_h, nxt_h = (np.asarray(cand_h),
                                       np.asarray(nc_h), np.asarray(nxt_h))
                tokens = None
            else:
                tokens, routed = jax.device_get((toks, routed))
                tokens = np.asarray(tokens)
        if routed is not None:
            # what the window's expert layers routed, beside its dispatch
            _obs_trace.instant(
                'serve.routing', cat='scheduler', kind=kind,
                **dict(zip(ROUTING_FIELDS, map(float, routed))))
        t_commit = time.perf_counter()
        commit = _obs_trace.span('serve.commit', cat='scheduler').begin()
        step_tokens = 0
        finished = []
        try:
            if not hit:
                # a NEW registry key means this dispatch paid trace +
                # compile: surface it as a compile span whose wall duration
                # is dispatch-to-commit (trace + compile + first window)
                _obs_trace.compile_event(
                    f'compile:{dispatch_key[0]}', key=dispatch_key,
                    dur_s=t_commit - t_dispatch,
                    geometry=str(self._geometry()))
                self._record(
                    'compile', dispatch=dispatch_key[0],
                    key=str(dispatch_key),
                    dur_ms=round((t_commit - t_dispatch) * 1e3, 3))
            # steady-state per-token latency: the window advances every live
            # slot one token per scan step, so each committed token costs
            # window_wall / W — recorded once per token at this commit point
            # (window granularity, no per-token host syncs). A cache-MISS
            # window's wall is trace+compile, not decoding: its tokens are
            # excluded from the ITL histogram (they'd report compile time as
            # inter-token latency) and counted aside; TTFT keeps including
            # it — a request that waited on a compile really waited.
            per_tok_ms = ((t_commit - t_dispatch) * 1e3 / W) if hit else None
            telemetry = _obs.enabled()
            mx = self._metrics() if telemetry else None
            for slot, req in enumerate(self._slot_req):
                if req is None or self._pfill[slot] is not None:
                    # mid-prefill slots rode the window frozen: they
                    # emitted pad tokens and commit nothing until their
                    # last chunk lands
                    continue
                if spec_out is not None:
                    # ragged speculative commit: the device already
                    # clamped the accept count by budget and truncated at
                    # eos (ncommit); the carried next-token persists on
                    # the request so preemption/restore resumes bit-equal
                    take = int(nc_h[slot])
                    committed = [int(t) for t in cand_h[slot, :take]]
                    req.spec_next = int(nxt_h[slot])
                    # the draft scan wrote every committed position's KV
                    self._dctx[slot] += take
                    self.spec_counts['proposed'] += self.spec_window
                    self.spec_counts['accepted'] += max(0, take - 1)
                    if telemetry:
                        self._inc('serve.spec_proposed', self.spec_window)
                        self._inc('serve.spec_accepted', max(0, take - 1))
                else:
                    take = min(W, req.remaining)
                    committed = []
                    for t in range(take):
                        tok = int(tokens[slot, t])
                        committed.append(tok)
                        if (self.eos_token_id is not None
                                and tok == self.eos_token_id):
                            break
                    if committed:
                        # the window consumed any pending speculative
                        # carried token as its first commit (the forced
                        # path) — a stale spec_next must not force a later
                        # spec window at the wrong position
                        req.spec_next = None
                if committed:
                    self.last_deliveries.append(
                        (req.rid, len(req.generated), len(committed)))
                req.generated.extend(committed)
                self._ctx[slot] += len(committed)
                # keep the device-side freeze live: next window's budget is
                # the CURRENT remaining, so a continuing row can never
                # commit past its max_new on device and ctx_out stays equal
                # to the host view
                self._budget[slot] = req.remaining
                self._tokens_out += len(committed)
                step_tokens += len(committed)
                if telemetry and committed:
                    itl_n = len(committed)
                    if req.when('first_token') is None:
                        req.mark('first_token', t_commit)
                        arrived = req.when('arrival')
                        if arrived is not None:
                            mx['ttft'].observe((t_commit - arrived) * 1e3)
                        itl_n -= 1        # the first-ever token is TTFT
                    row_ms = per_tok_ms
                    if spec_out is not None and hit:
                        # ragged window: this row's per-token latency is
                        # the window wall over ITS committed count
                        row_ms = ((t_commit - t_dispatch) * 1e3
                                  / max(len(committed), 1))
                    if row_ms is not None:
                        mx['itl'].observe(row_ms, n=itl_n)
                    else:
                        self._inc('serve.itl_skipped_compile', itl_n)
                    req.mark('window', t_commit, n=len(committed),
                             total=len(req.generated))
                done = (req.remaining == 0
                        or (self.eos_token_id is not None and committed
                            and committed[-1] == self.eos_token_id))
                if done:
                    self._finish(slot, req)
                    finished.append(req)
                elif req.deadline is not None and t_commit >= req.deadline:
                    # deadline check rides the existing per-window commit
                    # sync (t_commit is already in hand — no extra clock
                    # read, no device sync): an unfinished request past its
                    # deadline expires HERE, pages freed, slot recycled
                    self._clear_slot(slot)
                    self._retire(
                        req, 'expired',
                        reason=f'deadline exceeded after '
                               f'{len(req.generated)} committed token(s)')
            self._serve_time += time.perf_counter() - t0
            if telemetry:
                mx['steps'].inc()
                mx['tokens'].inc(step_tokens)
                mx['step_ms'].observe((time.perf_counter() - t0) * 1e3)
                # live MFU / roofline: static flops of THIS dispatch's
                # geometry (the AOT manifest's cost stamp) over the
                # host-measured dispatch-to-commit wall — pure host
                # arithmetic on numbers already in hand (zero new syncs,
                # zero retraces). Cache-MISS windows are excluded like ITL:
                # their wall is trace+compile, not model execution.
                cost = (self._dispatch_costs.get(dispatch_key)
                        if self._dispatch_costs and hit else None)
                if cost is not None:
                    wall = t_commit - t_dispatch
                    fl = cost.get('flops')
                    if fl and wall > 0:
                        fps = fl / wall
                        self._set_gauge('serve.model_flops_per_s', fps)
                        mfu = (fps / self._peak_flops
                               if self._peak_flops else None)
                        if mfu is not None:
                            self._set_gauge('serve.mfu_est', mfu)
                        ba = cost.get('bytes_accessed')
                        if ba:
                            self._set_gauge('serve.roofline_intensity',
                                            fl / ba)
                        self._last_mfu = {
                            'tag': dispatch_key, 'flops': fl,
                            'bytes_accessed': ba,
                            'window_wall_ms': wall * 1e3,
                            'flops_per_s': fps, 'mfu_est': mfu,
                            'peak_flops': self._peak_flops,
                        }
                self._update_gauges()
        finally:
            commit.end(committed=step_tokens, finished=len(finished))
        step_span.set(kind=kind, live=live, committed=step_tokens)
        return finished

    # -- internals ---------------------------------------------------------

    def _free_slots(self):
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _draft_catchup_rows(self):
        """Decoding slots whose draft pages lag their committed context
        (tokens a chunk-step's plain decode window committed never
        passed through the draft): (slot, req, start, take) rows for a
        `_draft_chunk` catch-up dispatch. Holes are bounded by one
        window per step (catch-up runs every speculative AND chunk
        step), so the take always buckets at or below the decode
        window's bucket."""
        rows = []
        for s, r in enumerate(self._slot_req):
            if r is None or self._pfill[s] is not None:
                continue
            hole = int(self._ctx[s]) - int(self._dctx[s])
            if hole > 0:
                rows.append((s, r, int(self._dctx[s]), hole))
        return rows

    def _draft_advance(self, rows, Sb):
        """One `_draft_chunk` dispatch appending each row's tokens
        [start, start+take) into the DRAFT's pages (no CoW — catch-up
        rows are past-prefill decoding slots), then advance their
        draft-valid context. Returns True when this (chunk bucket, ctx
        bucket) shape is NEW to the engine — its dispatch paid trace +
        compile, so the caller must count the step as a cache MISS
        (the wall would otherwise pollute the ITL/MFU gauges as decode
        time). Warmup drives the reachable shapes (`_warm_geometry`),
        so a warm-attached engine never sees a fresh one."""
        K = self.max_slots
        Cb = bucket_length(max(t for *_x, t in rows), self.buckets)
        fresh = (Cb, Sb) not in self._draft_shapes
        self._draft_shapes.add((Cb, Sb))
        ids = np.zeros((K, Cb), np.int32)
        clen = np.zeros((K,), np.int32)
        start = np.zeros((K,), np.int32)
        btabs = np.zeros((K, self.max_blocks_per_seq), np.int32)
        for i, (slot, req, p, take) in enumerate(rows):
            toks = np.concatenate([req.prompt,
                                   np.asarray(req.generated, np.int32)])
            ids[i, :take] = toks[p:p + take]
            clen[i] = take
            start[i] = p
            btabs[i] = self._btab[slot]
        z = self._put(np.zeros((K,), np.int32))
        self._run(self._dispatch('serve_draft_chunk', Cb, Sb, batch=(
            self._put(ids), self._put(clen), self._put(start),
            self._put(btabs), self._dummy(K), z, z)))
        for slot, req, p, take in rows:
            self._dctx[slot] = p + take
        return fresh

    def _forced_state(self):
        """Per-slot (forced_tok, forced) device args: rows carrying a
        speculative window's pending next-token choice (req.spec_next)
        commit it as their next token, whatever dispatch shape runs
        them. All-False on non-speculative engines (spec_next is never
        set) — the shared chunk-step trace stays identical."""
        forced = np.zeros((self.max_slots,), bool)
        ftok = np.zeros((self.max_slots,), np.int32)
        for s, r in enumerate(self._slot_req):
            if r is not None and r.spec_next is not None:
                forced[s] = True
                ftok[s] = r.spec_next
        return self._put(ftok), self._put(forced)

    def _device_state(self):
        """Device copies of the per-slot scheduler state, cached until
        a slot mutation invalidates them (self._dev = None), or the
        tables alone where a live slot only took or gave back pages
        (`_tables_changed`). Slots mid
        chunked prefill ride the decode window FROZEN on the scratch
        page: their real block tables stay host-side (the chunk
        dispatch gets them as explicit args), so the window's clamped
        frozen-row write can never touch a page a chunk is still
        filling."""
        if self._dev is None:
            ctx = self._ctx
            live = self._live_rows()
            if any(p is not None for p in self._pfill):
                ctx = ctx.copy()
                for i, p in enumerate(self._pfill):
                    if p is not None:
                        ctx[i] = 0
            self._dev = {
                'btab': self._put_tables(),
                'ctx': self._put(ctx),
                'live': self._put(np.asarray(live)),
                # per-slot sampling params ride the same slot-mutation
                # cadence (set at place, zeroed at clear) — a steady
                # window re-uses these uploads untouched
                'temp': self._put(self._temp),
                'topk': self._put(self._topk),
                'topp': self._put(self._topp),
                'seed': self._put(self._seed),
                'plen': self._put(self._plen),
            }
        elif self._dev['btab'] is None:
            self._dev['btab'] = self._put_tables()
        return self._dev

    def _put_tables(self):
        """The block tables' device copy, one a kind of page: the frozen
        rows of `_device_state` ride with an empty table."""
        btab = self._btab
        if any(p is not None for p in self._pfill):
            btab = btab.copy()
            for i, p in enumerate(self._pfill):
                if p is not None:
                    btab[i] = 0
        return jax.tree.map(self._put, self._by_kind(btab, self._wtab))

    def _tables_changed(self):
        """A live slot took or gave back pages and nothing else of it
        changed: the tables' device copy alone is stale, so the next
        dispatch uploads one array a kind and not the slots' whole state
        (a row crosses a page every `block_size / decode_window`
        windows, so most steps of a busy engine come through here)."""
        if self._dev is not None:
            self._dev['btab'] = None

    def _admit(self):
        """Fill free slots from the queue head (priority order — a head
        that cannot get its prefill pages waits, no barging past it).
        Returns this step's admissions as prefill groups [(bucket,
        [(slot, req)])], each of one bucket and at most that bucket's
        `_prefill_rows`, LARGEST group first (that one rides fused
        inside _serve_step; the rest prefill standalone in this same
        step, before it). A batch's width is a function of its bucket
        alone, with dummy rows masked to the scratch page, so the
        admission count never changes a traced shape."""
        if not len(self.queue):
            # steady-state fast path: nothing to admit, skip even the
            # admit span (most steps of a drained-queue run land here)
            return []
        free = self._free_slots()
        placed = []
        admitted = 0
        a, wa = self.allocator, self.win_allocator
        with _obs_trace.span('serve.admit', cat='scheduler') as _sp:
            while free and len(self.queue):
                req = self.queue.peek()
                if (req.deadline is not None
                        and time.perf_counter() >= req.deadline):
                    # expired while queued: never admitted, no prefill
                    # wasted on a stream nobody is waiting for anymore
                    self.queue.pop()
                    self._retire(req, 'expired',
                                 reason='deadline exceeded while queued')
                    continue
                total_pages = _ceil_div(req.context_len, self.block_size)
                hit = []
                hit_skipped = False
                if self.prefix_cache:
                    if req.page_hashes is None:
                        req.page_hashes = prompt_page_hashes(
                            req.prompt, self.block_size)
                    hit = a.match_prefix(req.page_hashes)
                if hit:
                    # profitability guard: a hit is taken only when it
                    # SHRINKS the prefill to a smaller bucket. A short
                    # hit on a short prompt lands in the same bucket —
                    # it saves (almost) no compute but pays the
                    # continuation gather and an extra chunk-step
                    # bookkeeping pass, a measured net loss on plain
                    # traffic. Skipped hits leave the pages cached for
                    # a longer-prefix arrival.
                    suffix = req.context_len - min(
                        len(hit) * self.block_size, req.context_len - 1)
                    if (bucket_length(suffix, self.buckets)
                            >= bucket_length(req.context_len,
                                             self.buckets)):
                        self.prefix_counts['hits_skipped'] += 1
                        hit = []
                        hit_skipped = True
                # continuation start: everything before it is valid KV
                # in shared pages. At least the LAST context token must
                # be recomputed (its logits seed the decode), so a
                # full-coverage hit backs off one token — into a shared
                # page, which the writer must copy-on-write first.
                start = min(len(hit) * self.block_size,
                            req.context_len - 1)
                cow = len(hit) * self.block_size > start
                need = total_pages - len(hit) + (1 if cow else 0)
                # cached pages the hit will revive stop being
                # allocatable the moment they are shared — the fresh
                # pages must fit in what remains, or the head waits
                # (checking available() alone would churn the LRU
                # through a share/unwind/re-park cycle every step)
                revive = sum(1 for p in hit if a.refcount(p) == 0)
                if need > a.available() - revive:
                    break
                # the recycled kind holds the pages the next query's
                # window reaches (a re-prefill writes those alone)
                wfirst, wgot = 0, []
                if wa is not None:
                    wfirst = self._window_first(req.context_len)
                    if total_pages - wfirst > wa.available():
                        break
                held_after = a.in_use() + need + revive
                if (held_after / a.usable > self.admit_watermark
                        and self.in_flight() > 0):
                    # pool-pressure watermark: admitting would push the
                    # pool past the watermark and something is already
                    # running — hold the head back so decode windows
                    # top up from headroom instead of forcing a
                    # preemption storm. With NOTHING in flight the head
                    # always admits (forward progress beats pressure).
                    # Shared pages a hit would revive off the cached
                    # LRU count as pressure too.
                    self.counts['admission_paused'] += 1
                    self._inc('serve.admission_paused')
                    if self._paused_head != req.rid:
                        # edge-triggered: one trail event per stall,
                        # not one per paused scheduler step
                        self._paused_head = req.rid
                        self._record('admission_paused', rid=req.rid,
                                     held_after=held_after)
                    break
                self.queue.pop()
                got = []             # references to return on unwind
                cow_pair = None      # (src, dst): src ref is the PIN
                try:
                    if _faults.ACTIVE is not None:
                        _faults.fire('admit', rid=req.rid, need=need)
                    a.phase = 'admit'
                    if hit:
                        a.share(hit)
                        got.extend(hit)
                    if cow:
                        # the slot's page table carries the private
                        # copy; the reference on the SOURCE page stays
                        # held (allocator.cow's copy-pin contract) so
                        # no same-step allocation can harvest and
                        # overwrite it before the deferred device copy
                        # in the chunk dispatch reads it — released in
                        # _step_impl once that dispatch is issued (or
                        # by _clear_slot if the slot dies first)
                        cp = a.cow(hit[-1])
                        got.append(cp)
                        cow_pair = (hit[-1], cp)
                    got.extend(a.alloc(total_pages - len(hit)))
                    if wa is not None:
                        wgot = wa.alloc(total_pages - wfirst)
                except OutOfBlocks:
                    # transient pool pressure (an injected dry spell,
                    # or stats racing a concurrent free): release any
                    # shares already taken, requeue at the head, and
                    # stop admitting this step
                    if got:
                        a.free(got)
                    self.queue.push(req)
                    break
                except Exception as e:  # noqa: BLE001 - scripted faults
                    # a fault at THIS request's admission (the
                    # poisoned-request model): fail it alone — shares
                    # returned, zero leaked references — and keep
                    # admitting the rest of the queue
                    if got:
                        a.free(got)
                    self._retire(req, 'failed',
                                 reason=f'fault at admission: {e!r}',
                                 error=e)
                    continue
                finally:
                    a.phase = None
                if cow_pair is not None:
                    # page list for the slot: prefix with the private
                    # copy at the boundary position (the pinned source
                    # is NOT part of the slot's table)
                    pages_for_slot = (hit[:-1] + [cow_pair[1]]
                                      + got[len(hit) + 1:])
                else:
                    pages_for_slot = got
                slot = free.pop(0)
                self._place(slot, req, pages_for_slot, wgot, wfirst)
                admitted += 1
                if self.prefix_cache:
                    if hit:
                        self.prefix_counts['hits'] += 1
                        self.prefix_counts['hit_tokens'] += start
                        self._inc('serve.prefix_hits')
                        self._inc('serve.prefix_hit_tokens', start)
                    elif not hit_skipped:
                        # a matched-but-unprofitable hit counts in
                        # NEITHER hits nor misses (hits_skipped above):
                        # hit rate = hits/(hits+misses) must read cache
                        # effectiveness, not the guard's declines
                        self.prefix_counts['misses'] += 1
                        self._inc('serve.prefix_misses')
                chunked = (self.prefill_chunk is not None
                           and req.context_len - start > self.prefill_chunk)
                if start > 0 or chunked:
                    # continuation / chunked admission: this slot rides
                    # the fused chunk dispatch (starting this very
                    # step) instead of the monolithic bucket prefill —
                    # it occupies its slot but emits no tokens until
                    # its last chunk commits
                    self._pfill[slot] = start
                    self._cow_pending[slot] = cow_pair
                    # the draft holds only the shared-prefix pages so
                    # far (valid: previous owners wrote them); its
                    # chunk legs advance this alongside the target's
                    self._dctx[slot] = start
                    if chunked:
                        self.prefix_counts['chunked_admissions'] += 1
                        self._inc('serve.chunked_admissions')
                else:
                    placed.append((slot, req))
            _sp.set(admitted=admitted, queue_depth=len(self.queue))
        by_bucket: dict = {}
        for slot, req in placed:
            Sb = bucket_length(req.context_len, self.buckets)
            by_bucket.setdefault(Sb, []).append((slot, req))
        groups = []
        for Sb, members in by_bucket.items():
            rows = self._prefill_rows(Sb)
            groups.extend((Sb, members[i:i + rows])
                          for i in range(0, len(members), rows))
        return sorted(groups, key=lambda kv: -len(kv[1]))

    def _place(self, slot, req, pages, wpages=(), wfirst=0):
        """Arm a slot (host bookkeeping only; the batched prefill in
        `_admit` moves the actual KV rows). `wpages` are the recycled
        kind's, for the logical pages from `wfirst` on."""
        self._slot_req[slot] = req
        self._slot_pages[slot] = pages
        self._btab[slot] = 0
        self._btab[slot, :len(pages)] = pages
        if self._ring is not None:
            self._slot_wpages[slot] = list(wpages)
            self._wfirst[slot] = wfirst
            self._wtab[slot] = 0
            self._wtab[slot, wfirst:wfirst + len(wpages)] = wpages
        self._ctx[slot] = req.context_len
        self._budget[slot] = req.remaining
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._topp[slot] = req.top_p
        self._seed[slot] = np.uint32(req.sample_seed)
        self._plen[slot] = len(req.prompt)
        # monolithic admissions prefill BOTH models this same step; a
        # chunk-path admission overrides this to its continuation start
        # right after placement (_admit)
        self._dctx[slot] = req.context_len
        self._dev = None
        req.state = 'running'
        req.admit_seq = next(self._admit_seq)
        self._paused_head = None     # admission resumed: re-arm the
                                     # admission_paused edge trigger
        req.mark('admitted', slot=slot, pages=len(pages))
        wait_ms = None
        if req.enqueued_at is not None:
            wait_ms = (time.perf_counter() - req.enqueued_at) * 1e3
        if _obs.enabled():
            self._inc('serve.admissions')
            if wait_ms is not None:
                self._metrics()['qwait'].observe(wait_ms)
        _obs_trace.instant(
            'serve.admission', cat='scheduler', rid=req.rid, slot=slot,
            pages=len(pages), wait_ms=wait_ms, prompt_len=len(req.prompt),
            bucket=bucket_length(req.context_len, self.buckets))

    def _prefill_rows(self, Sb):
        """Rows of an admission-prefill batch at bucket `Sb`: what the
        token budget holds, at least one and never more than there are
        slots. The ONE place that knows the width: `_admit` splits by
        it, `_prefill_args` builds it (for `step()` and, through
        `_dispatches`, for warm-up and the specs) and `_fill` reports
        it."""
        return max(1, min(PREFILL_TOKENS // Sb, self.max_slots))

    def _dummy(self, rows):
        """All-dummy slot indices for a draft-side leg of `rows` rows
        (uploaded once per width)."""
        d = self._dummy_slots.get(rows)
        if d is None:
            d = self._dummy_slots[rows] = self._put(
                np.full((rows,), self.max_slots, np.int32))
        return d

    def _live_rows(self):
        """Per slot, whether its row decodes in the window: it holds a
        request and is not mid chunked prefill."""
        return [r is not None and self._pfill[i] is None
                for i, r in enumerate(self._slot_req)]

    def _sampler_asks(self):
        """What the live rows ask the sampler for, as `serve.dispatch`
        reports it (the host's mirror of `_row_asks`): `sampled`, the
        live rows with a temperature, and `filtered`, those of them with
        a top-k or a nucleus. At 0 the dispatch takes an argmax a row
        and sorts nothing."""
        sampled = (self._temp > 0) & np.asarray(self._live_rows())
        filtered = sampled & ((self._topk > 0) | (self._topp < 1.0))
        return {'sampled': int(sampled.sum()),
                'filtered': int(filtered.sum())}

    def _kernel_pages(self):
        """The paged kernel's work in one token-step, as `serve.dispatch`
        reports it: `pages_needed`, the pages its calls (one an attention
        layer) walk at the live rows' contexts — ceil(ctx / block_size)
        less the pages wholly behind the layer's window — beside
        `pages_table`, the table entries those calls are handed. Their
        ratio is what the kernel's time follows, and how far
        `max_context_len` is over-provisioned. A model of two kinds of
        page adds what the slots hold of each (`full_pages_held`,
        `win_pages_held`, `kv_bytes_held`) beside what they would hold
        were nothing recycled behind the window (`win_pages_unbounded`,
        `kv_bytes_unbounded`)."""
        ctx = self._ctx[self._live_rows()].astype(np.int64)
        bs = self.block_size
        last = -(-ctx // bs)
        needed = sum(
            n * int((last - (np.maximum(ctx - w, 0) // bs if w else 0)).sum())
            for w, n in self._layer_windows)
        layers = sum(n for _, n in self._layer_windows)
        counts = dict(pages_needed=needed,
                      pages_table=layers * self.max_slots
                      * self.max_blocks_per_seq)
        if self._ring is not None:
            # what the slots hold of each kind, beside what the recycled
            # kind's layers would hold with nothing recycled (a page of
            # every logical page the rows hold of the other kind)
            a, wa = self.allocator, self.win_allocator
            held, wheld = a.in_use(), wa.in_use()
            counts.update(
                full_pages_held=held, win_pages_held=wheld,
                win_pages_unbounded=held,
                kv_bytes_held=(held * a.bytes_per_page
                               + wheld * wa.bytes_per_page),
                kv_bytes_unbounded=held * (a.bytes_per_page
                                           + wa.bytes_per_page))
        return counts

    def _fill(self, bucket, real_lens, padded_rows=None):
        """How full one fixed-width prefill batch is, as `serve.dispatch`
        and `serve.prefill` report it: its `rows` real rows and their
        real tokens beside the `padded_rows * bucket` positions the
        batch holds. The width is the admission batch's unless given (a
        chunk batch is `max_slots` wide); a bare window has none. An
        admission batch also reports `attn_kernel_layers`
        (`_attn_kernel_layers`); a chunk batch continues rows from their
        pages and a bare window prefills nothing, so theirs is 0."""
        admission = bool(bucket) and padded_rows is None
        if padded_rows is None:
            padded_rows = self._prefill_rows(bucket) if bucket else 0
        return dict(bucket=bucket, rows=len(real_lens),
                    padded_rows=padded_rows, real_tokens=sum(real_lens),
                    padded_tokens=padded_rows * bucket,
                    attn_kernel_layers=(self._attn_kernel_layers(bucket)
                                        if admission else 0))

    def _attn_kernel_layers(self, bucket):
        """How many layers' attention an admission prefill at `bucket`
        runs through the flash kernel, the target's and the draft's: the
        layers whose temp-cache entry `models.llama.prefill_takes_kernel`
        routes to it, which is the test `cached_attention` makes when the
        dispatch traces (0 off the chip, on int8 pools and under
        `PREFILL_KERNEL_MIN_ROWS`)."""
        n = self._kernel_layers.get(bucket)
        if n is None:
            from ..models.llama import prefill_takes_kernel

            n = 0
            for model, pages in ((self.model, self._pages),
                                 (self.draft, self._dpages)):
                if model is None:
                    continue
                tmp = jax.eval_shape(
                    lambda: _tmp_cache(model, pages, 1, bucket))
                n += sum(prefill_takes_kernel(bucket, c[0].shape[-1], c, 0)
                         for c in tmp)
            self._kernel_layers[bucket] = n
        return n

    def _prefill_args(self, Sb, group):
        """Device args for one fixed-width admission-prefill batch
        (all of `group` shares bucket Sb; at most `_prefill_rows(Sb)`
        members — `_admit` splits a bucket's admissions so). Rows
        beyond the group are dummies: real_len 0 (their K/V land on the
        scratch page) and slot index SLOTS (their logits row is dropped
        by the OOB scatter)."""
        K = self._prefill_rows(Sb)
        ids = np.zeros((K, Sb), np.int32)
        real_len = np.zeros((K,), np.int32)
        btabs = np.zeros((K, self.max_blocks_per_seq), np.int32)
        wtabs = np.zeros_like(btabs) if self._ring is not None else None
        slots = np.full((K,), self.max_slots, np.int32)      # dummy: drop
        for i, (slot, req) in enumerate(group):
            toks = np.concatenate([req.prompt,
                                   np.asarray(req.generated, np.int32)])
            ids[i, :len(toks)] = toks                        # RIGHT-pad
            real_len[i] = len(toks)
            btabs[i] = self._btab[slot]
            if wtabs is not None:
                wtabs[i] = self._wtab[slot]
            slots[i] = slot
        return (self._put(ids), self._put(real_len),
                jax.tree.map(self._put, self._by_kind(btabs, wtabs)),
                self._put(slots))

    def _prefill_group(self, Sb, group):
        """Standalone prefill dispatch for an admission group that did
        not fit the fused step (a second bucket, more of one bucket
        than its batch has rows, or any monolithic admission landing on
        a step whose fused dispatch is the chunk group's). A
        speculative engine prefills the DRAFT's pages too — the draft
        must hold every admitted row's prompt KV or its proposals would
        be conditioned on zeros and the accept rate would silently
        collapse."""
        with _obs_trace.span(
                'serve.prefill', cat='scheduler',
                **self._fill(Sb, [r.context_len for _s, r in group])):
            batch = self._prefill_args(Sb, group)
            self._note('serve_prefill', Sb)
            self._run(self._dispatch('serve_prefill', Sb, batch=batch))
            if self.draft is not None:
                self._run(self._dispatch('serve_prefill', Sb, batch=batch,
                                         draft=True))

    def _chunk_args(self, rows):
        """Device args for one fixed-width chunk-continuation batch
        (the row discipline of `_prefill_args`, but `max_slots` wide
        whatever the chunk bucket — the token budget does not size it
        yet: row i of the batch is rows[i] = (slot, req, progress,
        take); everything past the group is a dummy that lands on the
        scratch page and drops its logits). Returns the arrays plus
        the static (chunk bucket, context bucket) pair that keys the
        dispatch — row counts, chunk lengths, and per-row progress all
        ride as device data, so a whole long-prompt flood shares one
        compilation per bucket pair."""
        K = self.max_slots
        Cb = bucket_length(max(t for _s, _r, _p, t in rows), self.buckets)
        Sb = bucket_length(max(p + t for _s, _r, p, t in rows),
                           self.buckets)
        ids = np.zeros((K, Cb), np.int32)
        clen = np.zeros((K,), np.int32)
        start = np.zeros((K,), np.int32)
        btabs = np.zeros((K, self.max_blocks_per_seq), np.int32)
        slots = np.full((K,), self.max_slots, np.int32)   # dummy: drop
        cow_src = np.zeros((K,), np.int32)
        cow_dst = np.zeros((K,), np.int32)
        for i, (slot, req, p, take) in enumerate(rows):
            toks = np.concatenate([req.prompt,
                                   np.asarray(req.generated, np.int32)])
            ids[i, :take] = toks[p:p + take]
            clen[i] = take
            start[i] = p
            btabs[i] = self._btab[slot]
            if self._pfill[slot] is None:     # last chunk: commit logits
                slots[i] = slot
            pair = self._cow_pending[slot]
            if pair is not None:              # CoW rides the first chunk
                cow_src[i], cow_dst[i] = pair
                self._cow_pending[slot] = None
                # the copy-pin reference on the source drops once the
                # dispatch consuming this copy is issued (the caller
                # frees these right after the _serve_chunk_step call —
                # from then on the device dataflow orders any reuse of
                # the page after the copy that read it)
                self._cow_release.append(pair[0])
        return (self._put(ids), self._put(clen), self._put(start),
                self._put(btabs), self._put(slots),
                self._put(cow_src), self._put(cow_dst)), Cb, Sb

    def _chunk_seam_ok(self, rows):
        """Fire the per-dispatch fault seam for the chunk group
        (kind='chunk'). A scripted fault fails every member —
        per-request failure isolation, pages freed, shares returned —
        and returns False so the caller skips the chunk dispatch while
        the rest of the batch keeps decoding."""
        try:
            if _faults.ACTIVE is not None:       # skip ctx build when off
                _faults.fire('dispatch', kind='chunk',
                             rids=[r.rid for _s, r, _p, _t in rows])
        except Exception as e:  # noqa: BLE001 - scripted faults only
            self._fail_group([(s, r) for s, r, _p, _t in rows], e)
            return False
        return True

    def _register_prefix_pages(self, slot, req, lo, hi):
        """Bind the chain hash of every FULL prompt page whose KV the
        dispatch covering context positions [lo, hi) just completed.
        Only prompt-token pages index (generated tokens are
        per-request data); a hash already bound — shared pages, or a
        concurrent duplicate that computed the same block — stays with
        its first writer."""
        if req.page_hashes is None:
            return
        a = self.allocator
        pages = self._slot_pages[slot]
        bs = self.block_size
        for j in range(lo // bs, min(hi // bs, len(req.page_hashes))):
            a.register_prefix(pages[j], req.page_hashes[j])

    def _ensure_window_pages(self):
        """Every live slot must own pages covering the positions the
        coming window can write (ctx .. ctx + min(window, remaining)).
        A dry pool preempts the lowest-priority / youngest victim until
        the top-up fits (the needy slot may evict itself). A slot whose
        top-up STILL cannot be satisfied once it is the last request
        standing — maximal preemption reached — is unservable: that
        request fails alone (pages freed, pool invariants intact) and
        step() keeps decoding whatever remains; `OutOfBlocks` never
        escapes the scheduler. Of the recycled kind a slot first gives
        back the pages now wholly behind its window (`_recycle`), then
        takes those the window's writes need: host bookkeeping alone."""
        a, wa = self.allocator, self.win_allocator
        # per-step maximum commit: a speculative window can land up to
        # k+1 tokens (draft writes beyond the committed region fall on
        # the scratch page, so coverage only needs the committable max)
        adv = self.decode_window
        if self.spec_window is not None:
            adv = max(adv, self.spec_window + 1)
        for slot in range(self.max_slots):
            req = self._slot_req[slot]
            if req is None or self._pfill[slot] is not None:
                # mid-prefill slots already own every page their
                # admission allocated and ride the window frozen — no
                # top-up until their last chunk commits
                continue
            target = _ceil_div(
                int(self._ctx[slot]) + min(adv, req.remaining),
                self.block_size)
            if wa is not None:
                self._recycle(slot, req)
            while self._slot_req[slot] is req:
                pages, wpages = self._slot_pages[slot], self._slot_wpages[slot]
                need = target - len(pages)
                wneed = (0 if wa is None
                         else target - self._wfirst[slot] - len(wpages))
                if need <= 0 and wneed <= 0:
                    break
                try:
                    a.phase = 'window'
                    # each kind's pages are the slot's as soon as they
                    # are taken: a dry pool below has nothing to unwind
                    if need > 0:
                        new = a.alloc(need)
                        self._btab[slot, len(pages):len(pages) + need] = new
                        pages.extend(new)
                        self._tables_changed()
                    if wneed > 0:
                        wa.phase = 'window'
                        new = wa.alloc(wneed)
                        at = self._wfirst[slot] + len(wpages)
                        self._wtab[slot, at:at + wneed] = new
                        wpages.extend(new)
                        self._tables_changed()
                except OutOfBlocks as e:
                    others = any(
                        r is not None and s != slot
                        for s, r in enumerate(self._slot_req))
                    if others and self._preempt_one():
                        continue
                    # maximal preemption: this request is the only one
                    # left and a (nearly) drained pool still cannot
                    # cover its window — submit()'s fit check makes
                    # that unreachable for honest pools, so this is an
                    # injected fault or a snapshot restored into a
                    # smaller geometry; either way the REQUEST dies,
                    # never the step
                    self._clear_slot(slot)
                    self._retire(
                        req, 'failed',
                        reason=f'unservable: window page top-up failed '
                               f'after maximal preemption ({e})',
                        error=e)
                    break
                finally:
                    a.phase = None
                    if wa is not None:
                        wa.phase = None

    def _window_first(self, ctx):
        """The first logical page the recycled kind's window reaches
        from a query at position `ctx`: what lies before is never read
        again."""
        window = self._kinds[self._ring].window
        return max(0, int(ctx) - window + 1) // self.block_size

    def _recycle(self, slot, req):
        """Give back the slot's recycled-kind pages that now lie wholly
        behind its window, zero their table entries, and say so
        (`serve.recycle`). The ids go to the top of the kind's free
        list: the next taker, often this very slot, is handed them
        again."""
        first = self._window_first(self._ctx[slot])
        drop = first - self._wfirst[slot]
        if drop <= 0:
            return
        pages = self._slot_wpages[slot][:drop]
        del self._slot_wpages[slot][:drop]
        self._wtab[slot, self._wfirst[slot]:first] = 0
        self._wfirst[slot] = first
        self.win_allocator.free(pages)
        self._tables_changed()
        _obs_trace.instant('serve.recycle', cat='scheduler', rid=req.rid,
                           slot=slot, pages=len(pages))

    def _preempt_one(self):
        """Evict the lowest-priority (then youngest) in-flight request:
        free its pages, park the slot on the scratch page, requeue the
        request WITH its generated prefix (it resumes by re-prefill —
        greedy decoding makes the resumed stream identical to an
        uninterrupted one). Returns False when there is nothing to
        evict (the caller decides what dies; this never raises)."""
        victims = [(req.priority, -req.admit_seq, slot)
                   for slot, req in enumerate(self._slot_req)
                   if req is not None]
        if not victims:
            return False
        _, _, slot = min(victims)
        req = self._slot_req[slot]
        if _faults.ACTIVE is not None:
            _faults.fire('preempt', rid=req.rid, slot=slot)
        with _obs_trace.span('serve.preempt', cat='scheduler',
                             rid=req.rid, slot=slot,
                             generated=len(req.generated)):
            self._demote(slot, req)
        return True

    def _demote(self, slot, req):
        """Evict `slot` back to the queue as 'preempted' with full
        preemption bookkeeping (count, metric, lifecycle mark) — shared
        by pool-pressure eviction and the crash paths that requeue a
        just-admitted group whose prefill never ran, so a supervisor
        watching preemption rate sees every forced requeue."""
        self._clear_slot(slot)
        req.state = 'preempted'
        self.preemption_count += 1
        req.mark('preempted', generated=len(req.generated))
        self._inc('serve.preemptions')
        self.queue.push(req)

    def _retire(self, req, state, reason=None, error=None, result=None,
                count=True):
        """Move a request to its terminal state: stamp the lifecycle
        trail, count it (host counters work with telemetry off —
        stats() is truth), and park the record in `_terminal` for ONE
        `result()` retrieval. Callers release slot/queue residency
        first; this only flips the books. `count=False` lets a caller
        that owns its own counter (shedding) skip the per-state one, so
        every request lands in exactly one counter."""
        req.state = state
        req.reason = reason
        req.error = error
        if result is not None:
            req.result = result
        req.mark(state, reason=reason, tokens=len(req.generated))
        if count:
            self.counts[state] += 1
            self._inc(f'serve.{state}')
        if self._live.pop(req.rid, None) is not None \
                and req.deadline is not None:
            self._deadlines_live -= 1
        self._terminal[req.rid] = req
        while len(self._terminal) > self.max_terminal:
            victim = next((r for r in self._terminal
                           if r not in self._collect_guard), None)
            if victim is None:
                # every record belongs to an active serve() collection
                # — allow the overshoot (bounded by that one batch)
                # rather than evict outputs about to be returned
                break
            self._terminal.pop(victim)

    def _prefill_seam_ok(self, Sb, group):
        """Fire the per-prefill 'dispatch' fault seam for one admission
        group. A scripted fault fails the whole group (per-request
        failure isolation — the real dispatch is never interrupted
        mid-flight, so donated buffers stay sound) and returns False so
        the caller skips that prefill."""
        try:
            if _faults.ACTIVE is not None:       # skip ctx build when off
                _faults.fire('dispatch', kind='prefill', bucket=Sb,
                             rids=[r.rid for _s, r in group])
        except Exception as e:  # noqa: BLE001 - scripted faults only
            self._fail_group(group, e)
            return False
        return True

    def _fail_group(self, group, error):
        """Per-request failure isolation for one admission group whose
        prefill hit a fault: free each member's pages and fail it; the
        rest of the batch keeps decoding."""
        for slot, req in group:
            if self._slot_req[slot] is req:
                self._clear_slot(slot)
                self._retire(
                    req, 'failed',
                    reason=f'fault injected during prefill: {error!r}',
                    error=error)

    def _finish(self, slot, req):
        pad = self.eos_token_id if self.eos_token_id is not None else 0
        gen = (req.generated
               + [pad] * (req.max_new_tokens - len(req.generated)))
        out = np.concatenate(
            [req.prompt, np.asarray(gen, req.prompt.dtype)])
        self._clear_slot(slot)
        self._retire(req, 'finished', result=out)

    def _clear_slot(self, slot):
        self.allocator.free(self._slot_pages[slot])
        if self._cow_pending[slot] is not None:
            # the slot died before its first chunk dispatched: release
            # the copy-pin reference on the CoW source page too
            self.allocator.free([self._cow_pending[slot][0]])
        if self._ring is not None:
            self.win_allocator.free(self._slot_wpages[slot])
            self._slot_wpages[slot] = []
            self._wfirst[slot] = 0
            self._wtab[slot] = 0
        self._slot_req[slot] = None
        self._slot_pages[slot] = []
        self._btab[slot] = 0
        self._ctx[slot] = 0
        self._budget[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        self._seed[slot] = 0
        self._plen[slot] = 0
        self._dctx[slot] = 0
        self._pfill[slot] = None
        self._cow_pending[slot] = None
        self._dev = None


__all__ = ['ServingEngine', 'BlockAllocator', 'RequestQueue', 'Request',
           'OutOfBlocks', 'QueueFull', 'RequestError', 'RequestFailed',
           'RequestExpired', 'RequestCancelled', 'InvalidSamplingParams',
           'PageKindsUnsupported', 'prompt_page_hashes']
