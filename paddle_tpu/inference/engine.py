"""DecodeEngine — the compiled serving hot path (ref: the reference
serving runtime's executor: "async dispatch is native"; here the same
property is won by never leaving compiled code between tokens).

Why an engine instead of model.generate(): the mixin loops re-trace
their scan on every call (and the speculative loops used to define
their @jax.jit closures INSIDE the loop function — a guaranteed fresh
trace per invocation). This module owns the serving path end to end:

  1. Persistent compiled-function cache. Every jitted step lives at
     MODULE level, so jax's trace cache is keyed on
     (model pytree structure, cache shapes/dtypes, static sampling
     config) and survives across calls, engines, and requests. The
     `CompileCache` registry records those keys and a per-function
     retrace counter (`trace_counts()`), so steady-state can be
     ASSERTED to be 0 retraces (bench.py does). With
     persistent_cache=True (or PADDLE_TPU_PERSISTENT_CACHE=1) the
     compiled executables also go to disk via
     sysconfig.enable_persistent_compilation_cache, surviving process
     restarts.

  2. Buffer donation. Prefill, the decode loop, and both speculative
     window functions donate their KV-cache arguments
     (`donate_argnames`), so XLA updates the cache IN PLACE instead of
     copying (B, max_len, Hkv, D) per step. Contract: a cache passed to
     an engine step is dead to the caller — see
     docs/decode_engine.md.

  3. Bucketed prefill. Prompt lengths are padded LEFT to a small set of
     power-of-two buckets; the real length rides in as a DEVICE scalar
     (positions / kv_start are computed from it inside the trace), so
     every prompt length in a bucket reuses one compilation. Tokens are
     bit-identical to unpadded prefill: pad rows are excluded by
     kv_start (per-row window start — the fused decode kernel's scalar-
     prefetch path, ops/pallas/decode_attention.py) at prefill and at
     every later step.

  4. Fused speculative windows. Each window runs draft-propose (a
     lax.scan over k+1 steps), target-verify, and the greedy commit
     rule on device; batch-1 goes further and runs the WHOLE window
     loop inside one compiled lax.while_loop (_spec_decode_b1), so a
     generate_speculative call is one dispatch and ONE host sync total.
     Batched rows commit at per-row offsets and sync once per window
     (_spec_window_batched). The models/generation.py loops delegate
     here, so the public generate_speculative API gets the same
     steady-state-0-retrace property.

Single-token decode steps route through the fused pallas decode kernel
(ops/pallas/decode_attention.py's dispatcher) via the model's
cached_attention, exactly like model.generate().
"""
from __future__ import annotations

import ast
import collections
import functools
import inspect
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import journal as _journal
from ..observability import metrics as _obs
from ..observability import tracing as _obs_trace

# ---------------------------------------------------------------------------
# Compile accounting: retrace counters + the keyed registry
# ---------------------------------------------------------------------------

_TRACE_COUNTS: collections.Counter = collections.Counter()


def _count_trace(name):
    """Called from INSIDE to-be-jitted python bodies: runs only while
    tracing, so the counter is exactly the number of (re)compilations.
    Each firing is also a `compile.traces` tick in the process-global
    metrics registry, a `trace:<name>` instant on the host trace, and a
    `trace` flight-recorder event (observability's compile/retrace
    accounting)."""
    _TRACE_COUNTS[name] += 1
    _obs.inc('compile.traces')
    _obs_trace.compile_event(f'trace:{name}')
    _journal.record('trace', fn=name)


def trace_counts():
    """Per-function trace counts since process start (or the last
    reset): {'prefill': 2, 'decode_loop': 1, ...}."""
    return dict(_TRACE_COUNTS)


def total_traces():
    return sum(_TRACE_COUNTS.values())


def reset_trace_counts():
    _TRACE_COUNTS.clear()


def model_tag(model):
    """Stable, serializable identity for a model CLASS: the qualified
    import path. Replaces the old `id(type(model))` key component —
    object ids are neither stable across processes nor serializable,
    which the AOT artifact manifest (paddle_tpu.aot) needs them to be."""
    t = type(model)
    return f'{t.__module__}.{t.__qualname__}'


def model_struct(model):
    """Structural hash of a model pytree: sha256 over every array
    leaf's (shape, dtype), in tree order. Compilation keys on exactly
    this (values don't enter the HLO shape), so the AOT artifact
    config hashes it — two same-class models of different sizes must
    NOT share an artifact (every cache lookup would silently miss),
    while same-architecture checkpoints with different weights must."""
    import hashlib

    parts = []
    for leaf in jax.tree.leaves(model):
        if hasattr(leaf, 'shape') and hasattr(leaf, 'dtype'):
            parts.append(f'{tuple(leaf.shape)}:{leaf.dtype}')
        else:
            parts.append(repr(leaf))
    return hashlib.sha256('|'.join(parts).encode()).hexdigest()[:16]


def key_str(key):
    """Stable string form of a CompileCache key. Keys are tuples of
    primitives (str/int/float/bool/None, nested tuples) by contract, so
    `repr` round-trips exactly through `key_from_str` — the property the
    AOT manifest relies on to persist per-geometry keys."""
    return repr(key)


def key_from_str(s):
    """Inverse of `key_str` (ast.literal_eval: data only, no code)."""
    return ast.literal_eval(s)


class CompileCache:
    """Bookkeeping mirror of jax's jit cache for the engine functions.

    jax itself caches compiled executables keyed on (function, pytree
    structure, avals, statics); this registry records the engine-level
    key — (model-tag, model-id, cache shape, cache dtype,
    sampling-config, geometry) — for each compilation the engine
    requests, so serving code can observe hits/misses and tests can
    assert the steady state.

    Key contract (relied on by paddle_tpu.aot): every key is a tuple of
    PRIMITIVES — str/int/float/bool/None and nested tuples of the same.
    No object ids, no callables, no arrays. `key_str`/`key_from_str`
    round-trip any key through its stable string form, which is what
    the artifact manifest persists."""

    def __init__(self):
        self._keys: dict = {}
        self.hits = 0
        self.misses = 0

    def key(self, model, cache_shape, cache_dtype, sampling,
            geometry=('contiguous',)):
        # _engine_model_id is a monotonic per-process counter stamped on
        # first use — it never recycles (id(model) can, after gc) and
        # it is a PRIMITIVE, so keys stay serializable (the aot
        # manifest contract). The raw-id fallback only covers __slots__
        # models that refuse the stamp (model_tag keeps two classes'
        # ids from colliding). The counter starts at 0, so compare
        # against None (a bare `or` would throw away the first model's
        # id as falsy)
        #
        # `geometry` is the engine's batch-capacity tuple: DecodeEngine
        # passes ('contiguous', B, max_len), ServingEngine passes
        # ('paged', slots, num_blocks, block_size, max_blocks) — without
        # it a paged engine and a contiguous engine over the same model
        # and sampling config would collide on one registry key and the
        # hit/miss accounting would lie about both
        mid = getattr(model, '_engine_model_id', None)
        if mid is None:
            try:
                model._engine_model_id = mid = next(_MODEL_IDS)
            except AttributeError:
                mid = id(model)
        return (model_tag(model), mid,
                tuple(int(s) for s in cache_shape), str(cache_dtype),
                tuple(sampling), tuple(geometry))

    def note(self, key):
        if key in self._keys:
            self.hits += 1
            _obs.inc('compile.cache_hits')
            return True
        self._keys[key] = total_traces()
        self.misses += 1
        _obs.inc('compile.cache_misses')
        return False

    def keys(self):
        return list(self._keys)

    def __len__(self):
        return len(self._keys)


COMPILE_CACHE = CompileCache()

# monotonic model ids for the registry key: id(model) can be recycled
# after a served model is garbage-collected, which would let a NEW
# model's first call masquerade as a registry hit
_MODEL_IDS = itertools.count()


# ---------------------------------------------------------------------------
# Prefill buckets
# ---------------------------------------------------------------------------

# powers of two: small prompts hit small buckets; the padding overhead
# is < 2x prefill FLOPs worst-case and buys one compilation per bucket
# instead of one per prompt length
DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def bucket_length(seq_len, buckets=None):
    """Smallest bucket >= seq_len; past the largest bucket, the next
    power of two (a rare long prompt still compiles, it just doesn't
    share)."""
    for b in (buckets or DEFAULT_BUCKETS):
        if b >= seq_len:
            return b
    b = 1
    while b < seq_len:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# Module-level compiled steps (the persistent jit cache)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnames=('caches',))
def _prefill_exact(model, caches, ids):
    """Unpadded prefill (prompt length == bucket, or speculative loops
    which manage their own offsets). Donates the cache."""
    _count_trace('prefill')
    logits, caches = model(ids, caches=caches, cache_index=0)
    return logits[:, -1, :], caches


@functools.partial(jax.jit, donate_argnames=('caches',))
def _prefill_padded(model, caches, ids, real_len):
    """Left-padded bucketed prefill. ids is (B, Sb) with the prompt
    right-aligned; real_len (B,) rides as DEVICE data so every prompt
    length in the bucket shares this one compilation. Pad rows get
    position 0 and are excluded from all attention by kv_start (the
    per-row window start), at prefill and forever after."""
    _count_trace('prefill')
    B, Sb = ids.shape
    real_len = jnp.broadcast_to(jnp.asarray(real_len, jnp.int32), (B,))
    kv_start = Sb - real_len                               # (B,)
    positions = jnp.maximum(
        jnp.arange(Sb, dtype=jnp.int32)[None, :] - kv_start[:, None], 0)
    logits, caches = model(ids, caches=caches, cache_index=0,
                           positions=positions, kv_start=kv_start)
    return logits[:, -1, :], caches


@functools.partial(
    jax.jit, donate_argnames=('caches',),
    static_argnames=('max_new_tokens', 'temperature', 'top_k', 'top_p',
                     'eos_token_id', 'padded'))
def _decode_loop(model, caches, last_logits, real_len, rng_key, *,
                 max_new_tokens, temperature, top_k, top_p, eos_token_id,
                 padded):
    """The whole decode phase as ONE compiled lax.scan: sample, step the
    model over the donated cache, repeat. Write index = bucket length +
    t (static + scan counter); rope positions / kv_start come from the
    traced real_len, so one executable serves every prompt length in
    the bucket."""
    _count_trace('decode_loop')
    B = last_logits.shape[0]
    # bucket length is static: cache max_len minus the decode budget
    Sb = _cache_max_len(caches) - max_new_tokens
    real_len = jnp.broadcast_to(jnp.asarray(real_len, jnp.int32), (B,))
    kv_start = Sb - real_len

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        from ..models.generation import filter_logits

        logits = filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def step(carry, t):
        last_logits, caches, key, finished = carry
        key, sub = jax.random.split(key)
        tok = sample(last_logits, sub)
        if eos_token_id is not None:
            tok = jnp.where(finished, jnp.asarray(eos_token_id, tok.dtype),
                            tok)
            finished = finished | (tok == eos_token_id)
        extra = {}
        if padded:
            extra = dict(positions=(real_len + t)[:, None],
                         kv_start=kv_start)
        logits, caches = model(tok[:, None], caches=caches,
                               cache_index=Sb + t, **extra)
        return (logits[:, -1, :], caches, key, finished), tok

    (_, caches, _, _), tokens = jax.lax.scan(
        step, (last_logits, caches, rng_key, jnp.zeros((B,), bool)),
        jnp.arange(max_new_tokens, dtype=jnp.int32))
    return tokens.T, caches                                # (B, new), caches


def _window_b1(target, draft, tcaches, dcaches, c, L, k):
    """One speculative window, batch-1 (uniform cache_index): draft
    proposes k tokens (scan over k+1 steps so the k-th proposal's own
    kv row is written too), target verifies the whole [c, d1..dk]
    window in one forward, and the greedy commit rule (longest agreeing
    prefix) runs as a cumprod. Traced body of _spec_decode_b1's
    while_loop, kept separate as the single-window unit of the
    commit-rule contract (_commit_window is its host-side spec)."""

    def body(carry, i):
        tok, dc = carry
        logits, dc = draft(tok, caches=dc, cache_index=L + i)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (nxt[:, None], dc), nxt

    (_, dcaches), toks = jax.lax.scan(body, (c, dcaches),
                                      jnp.arange(k + 1))
    drafts = toks[:k, 0]                                   # (k,)
    window = jnp.concatenate([c, drafts[None, :]], axis=1)  # (1, k+1)
    tlogits, tcaches = target(window, caches=tcaches, cache_index=L)
    choices = jnp.argmax(tlogits[0], axis=-1).astype(jnp.int32)  # (k+1,)
    eq = (drafts == choices[:k]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(eq))                           # accepted prefix
    next_c = choices[m]
    return drafts, choices, m, next_c, tcaches, dcaches


@functools.partial(jax.jit, donate_argnames=('tcaches', 'dcaches'),
                   static_argnames=('k', 'max_new_tokens', 'eos_token_id'))
def _spec_decode_b1(target, draft, tcaches, dcaches, c, L0, *, k,
                    max_new_tokens, eos_token_id):
    """The WHOLE batch-1 speculative decode as one compiled
    lax.while_loop over fused windows: the accepted length is
    data-dependent, but it only steers on-device state (committed
    length L, token count n), so nothing about it needs the host — one
    dispatch and ONE host sync per generate call, not per window.

    Each window dynamic_update_slices its full k+1 candidate tokens
    [c, d1..dk] into the output buffer at offset n and advances n by
    the accepted m+1 only, so a later window's write starts exactly
    where the rejected tail begins and overwrites it; the buffer
    carries k+1 rows of slack so the final window's full-width write
    stays in bounds (no OOB clamping, which would corrupt the tail).
    Returns (buf, n): buf[:min(n, max_new_tokens)] is the committed
    stream. Both caches are donated."""
    _count_trace('spec_decode')
    buf = jnp.zeros((max_new_tokens + k + 1,), jnp.int32)

    def cond(state):
        _, _, n, finished = state[:4]
        return (n < max_new_tokens) & ~finished

    def body(state):
        c, L, n, finished, buf, tcaches, dcaches = state
        drafts, choices, m, next_c, tcaches, dcaches = _window_b1(
            target, draft, tcaches, dcaches, c, L, k)
        committed = jnp.concatenate([c[0], drafts])        # (k+1,)
        buf = jax.lax.dynamic_update_slice(buf, committed, (n,))
        ncommit = m + 1
        if eos_token_id is not None:
            idx = jnp.arange(k + 1)
            finished = finished | jnp.any(
                (committed == eos_token_id) & (idx < ncommit))
        return (next_c[None, None], L + ncommit, n + ncommit, finished,
                buf, tcaches, dcaches)

    state = (c, jnp.asarray(L0, jnp.int32), jnp.asarray(0, jnp.int32),
             jnp.asarray(False), buf, tcaches, dcaches)
    _, _, n, _, buf, tcaches, dcaches = jax.lax.while_loop(cond, body,
                                                           state)
    return buf, n, tcaches, dcaches


@functools.partial(jax.jit, donate_argnames=('tcaches', 'dcaches'),
                   static_argnames=('k',))
def _spec_window_batched(target, draft, tcaches, dcaches, c, wp, *, k):
    """Batched speculative window: rows commit at their own per-row
    offsets (kv_write_pos), commit rule vectorised over rows. c (B, 1),
    wp (B,). Returns per-row (drafts (B,k), choices (B,k+1), m (B,),
    next_c (B,))."""
    _count_trace('spec_window')

    def body(carry, i):
        tok, dc = carry
        logits, dc = draft(tok, caches=dc, kv_write_pos=wp + i)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (nxt[:, None], dc), nxt

    (_, dcaches), toks = jax.lax.scan(body, (c, dcaches),
                                      jnp.arange(k + 1))
    drafts = toks[:k].T                                    # (B, k)
    window = jnp.concatenate([c, drafts], axis=1)          # (B, k+1)
    tlogits, tcaches = target(window, caches=tcaches, kv_write_pos=wp)
    choices = jnp.argmax(tlogits, axis=-1).astype(jnp.int32)  # (B, k+1)
    eq = (drafts == choices[:, :k]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)           # (B,)
    next_c = jnp.take_along_axis(choices, m[:, None], axis=1)[:, 0]
    return drafts, choices, m, next_c, tcaches, dcaches


def _cache_max_len(caches):
    """max_len from any cache entry ((k, v) tuples or QuantKVCache)."""
    leaf = caches[0]
    arr = leaf[0] if isinstance(leaf, tuple) else leaf.kq
    return arr.shape[1]


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class DecodeEngine:
    """Owns the compiled serving path for one model.

    Construction pins the sampling config (it is part of the
    compilation key); `generate` then runs prefill + the scanned decode
    loop through the module-level jit cache — repeated same-bucket
    calls are ZERO retraces (see `stats()`), and the KV cache is
    donated to every step (never copied).

        engine = DecodeEngine(model, max_new_tokens=64)
        out = engine.generate(input_ids)               # ids (B, S)
        out = engine.generate_speculative(draft, ids)  # greedy, lossless

    Bucketing: prompts are left-padded to `buckets` (powers of two by
    default); models must accept `positions`/`kv_start` in their cached
    forward (the Llama family does) unless every prompt length is
    exactly a bucket boundary.

    persistent_cache=True additionally wires jax's on-disk executable
    cache (sysconfig.enable_persistent_compilation_cache) so a server
    restart skips XLA compilation; PADDLE_TPU_PERSISTENT_CACHE=1 does
    the same without code changes.
    """

    def __init__(self, model, max_new_tokens=32, temperature=0.0, top_k=0,
                 top_p=1.0, eos_token_id=None, buckets=None,
                 persistent_cache=None):
        self.model = model
        if getattr(model, '_engine_model_id', None) is None:
            try:
                model._engine_model_id = next(_MODEL_IDS)
            except AttributeError:  # __slots__ model: id(model) fallback
                pass
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token_id = (int(eos_token_id) if eos_token_id is not None
                             else None)
        self.buckets = tuple(sorted(buckets)) if buckets else DEFAULT_BUCKETS
        if persistent_cache is None:
            # on/off only: where the cache lives is sysconfig's rule
            persistent_cache = os.environ.get(
                'PADDLE_TPU_PERSISTENT_CACHE', '').strip().lower() in (
                    '1', 'true', 'yes', 'on')
        if persistent_cache:
            from .. import sysconfig

            sysconfig.enable_persistent_compilation_cache()
        params = inspect.signature(model.forward).parameters
        self._supports_padding = ('positions' in params
                                  and 'kv_start' in params)

    # -- bookkeeping -------------------------------------------------------

    def _sampling_key(self):
        return (self.max_new_tokens, self.temperature, self.top_k,
                self.top_p, self.eos_token_id)

    def _geometry(self, batch, max_len):
        """Batch-capacity component of the registry key: a contiguous
        cache of (batch, max_len). Keeps this engine's keys disjoint
        from ServingEngine's ('paged', ...) keys over the same model."""
        return ('contiguous', int(batch), int(max_len))

    def stats(self):
        """{'trace_counts', 'total_traces', 'cache_keys', 'hits',
        'misses', 'geometry'} — steady-state serving must show
        total_traces frozen across calls (bench.py asserts exactly
        that). `geometry` records the engine kind + capacity knobs that
        feed the registry key, so two engines' stats are attributable."""
        return {
            'trace_counts': trace_counts(),
            'total_traces': total_traces(),
            'cache_keys': len(COMPILE_CACHE),
            'hits': COMPILE_CACHE.hits,
            'misses': COMPILE_CACHE.misses,
            'geometry': {'kind': 'contiguous',
                         'max_new_tokens': self.max_new_tokens,
                         'buckets': self.buckets},
        }

    # -- AOT artifact hooks (paddle_tpu.aot) -------------------------------

    def aot_config(self):
        """Compilation-relevant config as a dict of primitives: what
        two engines must share for one EngineArtifact to serve both.
        Model weight VALUES are deliberately absent (a finetuned
        checkpoint of the same architecture attaches to the same
        artifact) but the STRUCTURE rides in as `model_struct` —
        compilation keys on shapes/dtypes, so a differently-sized model
        of the same class must refuse, not silently miss every cache
        entry."""
        return {
            'engine': 'DecodeEngine',
            'model': model_tag(self.model),
            'model_struct': model_struct(self.model),
            'cache_dtype': str(self.model.cache_dtype()),
            'max_new_tokens': self.max_new_tokens,
            'temperature': self.temperature,
            'top_k': self.top_k,
            'top_p': self.top_p,
            'eos_token_id': self.eos_token_id,
            'buckets': list(self.buckets),
        }

    def registry_key_generate(self, batch, prompt_len, max_new_tokens=None):
        """The EXACT CompileCache key a `generate(ids)` call with this
        (batch, prompt length, budget) would note — the unit
        GeometrySet enumeration is checked against."""
        mnt = (self.max_new_tokens if max_new_tokens is None
               else int(max_new_tokens))
        max_len = bucket_length(int(prompt_len), self.buckets) + mnt
        return COMPILE_CACHE.key(
            self.model, (int(batch), max_len), self.model.cache_dtype(),
            self._sampling_key() + ('generate',),
            geometry=self._geometry(batch, max_len))

    def registry_key_speculative(self, batch, prompt_len, max_new_tokens,
                                 num_draft_tokens):
        """The key a `generate_speculative` call would note (prompts are
        NOT bucketed on that path, so the exact prompt length is part
        of the cache shape)."""
        max_len = int(prompt_len) + int(max_new_tokens) + (
            int(num_draft_tokens) + 1)
        return COMPILE_CACHE.key(
            self.model, (int(batch), max_len), self.model.cache_dtype(),
            (int(num_draft_tokens), 'speculative'),
            geometry=self._geometry(batch, max_len))

    def _aot_jitted_fns(self):
        """The module-level jitted steps this engine's geometries
        dispatch — what `aot.build` cache-evicts (per FUNCTION, not
        process-wide) to force real persisting compiles."""
        return (_prefill_exact, _prefill_padded, _decode_loop,
                _spec_decode_b1, _spec_window_batched)

    def _warm_geometry(self, g, draft=None):
        """Drive ONE enumerated geometry through the LIVE serving path
        (a dummy generate call), populating jax's module-level trace
        cache and the CompileCache registry with exactly the entries a
        real request of this shape will hit. Dummy token ids are zeros;
        outputs are discarded."""
        p = g.params
        ids = jnp.zeros((p['batch'], p['prompt_len']), jnp.int32)
        if g.kind == 'decode_spec':
            if draft is None:
                raise ValueError(
                    'geometry kind decode_spec needs the draft model: '
                    'pass warmup(..., draft=draft_model)')
            self.generate_speculative(
                draft, ids, max_new_tokens=p['max_new_tokens'],
                num_draft_tokens=p['num_draft_tokens'])
        else:
            self.generate(ids, max_new_tokens=p['max_new_tokens'])

    def warmup(self, artifact=None, geometries=None, draft=None):
        """Pre-populate the module-level jit caches (and the
        CompileCache registry) for every geometry this engine will
        dispatch, BEFORE the first request. With `artifact` (an
        `aot.EngineArtifact` or its path) the manifest is
        fingerprint-checked and jax's persistent executable cache is
        wired to the artifact's, so the warmup compiles are disk reads,
        not XLA runs — the zero-compile cold start. Returns a report
        dict; see docs/aot_warmup.md."""
        from ..aot.artifact import warm_attach

        return warm_attach(self, artifact=artifact, geometries=geometries,
                           draft=draft)

    def _export_specs(self, g, draft=None):
        """(suffix, jitted_fn, args) tuples for `aot.build(...,
        export_stablehlo=True)`: the geometry's traced computations
        over ShapeDtypeStruct avals (nothing allocated, nothing
        executed). The model is CLOSED OVER — the jit.save idiom:
        weights ride as constants, so the exported module is
        self-contained and its pytree carries only arrays and
        registered containers (a Layer in the calling convention would
        refuse to serialize). A bucketed generate spans two jitted
        steps, so one geometry exports two StableHLO modules."""
        p = g.params
        if g.kind != 'decode':
            raise NotImplementedError(
                f'no StableHLO export for geometry kind {g.kind!r}')
        B, L = int(p['batch']), int(p['prompt_len'])
        mnt = int(p['max_new_tokens'])
        Sb = bucket_length(L, self.buckets)
        max_len = Sb + mnt
        caches = jax.eval_shape(
            functools.partial(self.model.init_cache, B, max_len))
        ids = jax.ShapeDtypeStruct((B, Sb), jnp.int32)
        rl = jax.ShapeDtypeStruct((B,), jnp.int32)
        exact = L == Sb
        base_pre = (_prefill_exact if exact else _prefill_padded)
        pre_args = (caches, ids) if exact else (caches, ids, rl)
        # tracelint: disable=TL001 - one-shot export wrappers (statics
        # and the model baked into the closure; never a hot path)
        pre = jax.jit(functools.partial(
            getattr(base_pre, '__wrapped__', base_pre), self.model))
        logits_sds, caches_sds = jax.eval_shape(pre, *pre_args)
        yield ('-prefill', pre, pre_args)
        # tracelint: disable=TL001 - one-shot export wrapper (see above)
        dec = jax.jit(functools.partial(
            getattr(_decode_loop, '__wrapped__', _decode_loop),
            self.model, max_new_tokens=mnt, temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p,
            eos_token_id=self.eos_token_id, padded=not exact))
        yield ('-decode', dec,
               (caches_sds, logits_sds, rl, jax.random.PRNGKey(0)))

    def _cost_specs(self, g, draft=None):
        """(jitted_fn, args, static_kwargs) triples for
        `observability.costs.geometry_cost`: the module-level jitted
        prefill + decode steps a `generate` of this geometry
        dispatches, over ShapeDtypeStruct avals with the live model as
        an argument (the served HLO, not an export variant).
        Speculative geometries have no cost specs (NotImplementedError
        — recorded, never fatal, by the callers)."""
        p = g.params
        if g.kind != 'decode':
            raise NotImplementedError(
                f'no cost specs for geometry kind {g.kind!r}')
        B, L = int(p['batch']), int(p['prompt_len'])
        mnt = int(p['max_new_tokens'])
        Sb = bucket_length(L, self.buckets)
        max_len = Sb + mnt
        caches = jax.eval_shape(
            functools.partial(self.model.init_cache, B, max_len))
        ids = jax.ShapeDtypeStruct((B, Sb), jnp.int32)
        rl = jax.ShapeDtypeStruct((B,), jnp.int32)
        exact = L == Sb
        pre = _prefill_exact if exact else _prefill_padded
        pre_args = ((self.model, caches, ids) if exact
                    else (self.model, caches, ids, rl))
        logits_sds, caches_sds = jax.eval_shape(pre, *pre_args)
        yield (pre, pre_args, {})
        yield (_decode_loop,
               (self.model, caches_sds, logits_sds, rl,
                jax.random.PRNGKey(0)),
               dict(max_new_tokens=mnt, temperature=self.temperature,
                    top_k=self.top_k, top_p=self.top_p,
                    eos_token_id=self.eos_token_id, padded=not exact))

    # -- generate ----------------------------------------------------------

    def generate(self, input_ids, max_new_tokens=None, rng_key=None):
        """Greedy/sampled decode, compiled end to end. Returns
        (B, S + max_new_tokens) ids (the ORIGINAL prompt, not the
        padded one, is echoed back)."""
        input_ids = jnp.asarray(input_ids)
        B, S = input_ids.shape
        mnt = (self.max_new_tokens if max_new_tokens is None
               else int(max_new_tokens))
        Sb = bucket_length(S, self.buckets)
        pad = Sb - S
        if pad and not self._supports_padding:
            raise NotImplementedError(
                f'{type(self.model).__name__} lacks positions/kv_start in '
                f'its cached forward, so bucketed prefill cannot mask the '
                f'pad rows; pass prompts of exactly a bucket length '
                f'{self.buckets} or use a Llama-family model')
        max_len = Sb + mnt
        caches = self.model.init_cache(B, max_len)
        key = self._sampling_key() + ('generate',)
        COMPILE_CACHE.note(COMPILE_CACHE.key(
            self.model, (B, max_len), self.model.cache_dtype(), key,
            geometry=self._geometry(B, max_len)))
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        real_len = jnp.full((B,), S, jnp.int32)
        if pad:
            ids = jnp.pad(input_ids, ((0, 0), (pad, 0)))
            last_logits, caches = _prefill_padded(self.model, caches, ids,
                                                  real_len)
        else:
            last_logits, caches = _prefill_exact(self.model, caches,
                                                 input_ids)
        tokens, caches = _decode_loop(
            self.model, caches, last_logits, real_len, rng_key,
            max_new_tokens=mnt, temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p,
            eos_token_id=self.eos_token_id, padded=bool(pad))
        return jnp.concatenate([input_ids, tokens.astype(input_ids.dtype)],
                               axis=1)

    # -- speculative -------------------------------------------------------

    def generate_speculative(self, draft, input_ids, max_new_tokens=None,
                             num_draft_tokens=4):
        """Greedy speculative decoding through the fused window step:
        LOSSLESS vs `generate` (temperature 0) on the target alone; one
        host sync per CALL at batch 1 (the window loop is a compiled
        lax.while_loop), per window for batched rows. Prompts are NOT
        bucketed on this path
        (the window loop already reuses one compilation for any prompt
        length via traced offsets... for batch 1; batched rows commit
        per-row via kv_write_pos)."""
        input_ids = jnp.asarray(input_ids)
        B, S = input_ids.shape
        mnt = (self.max_new_tokens if max_new_tokens is None
               else int(max_new_tokens))
        k = int(num_draft_tokens)
        if k < 1:
            raise ValueError('num_draft_tokens must be >= 1')
        if B != 1:
            for m_ in (self.model, draft):
                if 'kv_write_pos' not in inspect.signature(
                        m_.forward).parameters:
                    raise NotImplementedError(
                        f'{type(m_).__name__} does not support batched '
                        f'speculative decoding (cached forward lacks '
                        f'kv_write_pos); loop prompts individually')
        max_len = S + mnt + k + 1
        tcaches = self.model.init_cache(B, max_len)
        dcaches = draft.init_cache(B, max_len)
        COMPILE_CACHE.note(COMPILE_CACHE.key(
            self.model, (B, max_len), self.model.cache_dtype(),
            (k, 'speculative'), geometry=self._geometry(B, max_len)))
        if B == 1:
            gen = _spec_loop_host_b1(self.model, draft, tcaches, dcaches,
                                     input_ids, mnt, k, self.eos_token_id)
        else:
            gen = _spec_loop_host_batched(self.model, draft, tcaches,
                                          dcaches, input_ids, mnt, k,
                                          self.eos_token_id)
        return jnp.concatenate(
            [input_ids, jnp.asarray(gen, input_ids.dtype)], axis=1)


# ---------------------------------------------------------------------------
# Host-side speculative drivers (shared with models/generation.py)
# ---------------------------------------------------------------------------

def _spec_loop_host_b1(target, draft, tcaches, dcaches, input_ids,
                       max_new_tokens, k, eos_token_id):
    """Batch-1 driver: two async prefill dispatches, then the WHOLE
    window loop as one compiled dispatch (_spec_decode_b1) and one
    device_get — a single host sync for the entire generate call."""
    B, S = input_ids.shape
    last_logits, tcaches = _prefill_exact(target, tcaches, input_ids)
    _, dcaches = _prefill_exact(draft, dcaches, input_ids)
    c = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]
    buf, n, _, _ = _spec_decode_b1(
        target, draft, tcaches, dcaches, c, jnp.asarray(S, jnp.int32),
        k=k, max_new_tokens=max_new_tokens, eos_token_id=eos_token_id)
    buf, n = jax.device_get((buf, n))       # the ONE host sync
    out = [int(x) for x in buf[:min(int(n), max_new_tokens)]]
    if eos_token_id is not None:
        if eos_token_id in out:
            out = out[:out.index(eos_token_id) + 1]
        out += [eos_token_id] * (max_new_tokens - len(out))
    return [out[:max_new_tokens]]


def _spec_loop_host_batched(target, draft, tcaches, dcaches, input_ids,
                            max_new_tokens, k, eos_token_id):
    """B > 1: rows commit at their own rates (per-row kv_write_pos);
    rule per row identical to batch-1, so losslessness holds row-wise.
    Finished/full rows still ride through the static-shape window but
    commit nothing (their L stays put; scratch rows get overwritten)."""
    B, S = input_ids.shape
    c0, tcaches = _prefill_exact(target, tcaches, input_ids)
    _, dcaches = _prefill_exact(draft, dcaches, input_ids)
    c_host = np.asarray(jnp.argmax(c0, axis=-1)).astype(np.int64)  # (B,)

    out = [[] for _ in range(B)]
    finished = [False] * B
    L = np.full((B,), S, np.int64)

    def row_needs(b):
        return not finished[b] and len(out[b]) < max_new_tokens

    while any(row_needs(b) for b in range(B)):
        cj = jnp.asarray(c_host[:, None], jnp.int32)
        wp = jnp.asarray(L, jnp.int32)
        drafts, choices, m, next_c, tcaches, dcaches = _spec_window_batched(
            target, draft, tcaches, dcaches, cj, wp, k=k)
        # batched rows commit at their OWN rates, so the host must read
        # the per-row accepts between windows — one batched device_get
        # per WINDOW (never per token) is the contract this loop keeps.
        # tracelint: disable=TL002 - single sync per window by design
        d, m_h, nc = jax.device_get((drafts, m, next_c))
        for b in range(B):
            if not row_needs(b):
                continue
            mb = int(m_h[b])
            committed = [int(c_host[b])] + [int(x) for x in d[b, :mb]]
            c_host[b] = int(nc[b])
            out[b].extend(committed)
            if eos_token_id is not None and eos_token_id in committed:
                out[b] = out[b][:out[b].index(eos_token_id) + 1]
                finished[b] = True
            L[b] += len(committed)

    pad = eos_token_id if eos_token_id is not None else 0
    return [out[b][:max_new_tokens]
            + [pad] * (max_new_tokens - len(out[b][:max_new_tokens]))
            for b in range(B)]


def donation_supported():
    """Whether this backend honors jit buffer donation (all current
    CPU/TPU jaxlibs do; the probe keeps tests honest on exotic ones)."""
    x = jnp.zeros((8,))
    # tracelint: disable=TL001 - one-off capability probe, not a hot path
    jax.jit(lambda a: a + 1, donate_argnums=(0,))(x)
    return x.is_deleted()


__all__ = [
    'DecodeEngine', 'CompileCache', 'COMPILE_CACHE', 'DEFAULT_BUCKETS',
    'bucket_length', 'trace_counts', 'total_traces', 'reset_trace_counts',
    'donation_supported', 'model_tag', 'model_struct', 'key_str',
    'key_from_str',
]
