"""Geometry enumeration — the declarative half of the AOT subsystem.

A *geometry* is one (jitted function, traced shapes, static config)
combination an engine will dispatch: a prefill bucket at a batch width,
a decode window over the paged pool, a speculative window, a train
micro-batch scan. The engines already key their CompileCache registries
on exactly these combinations; this module enumerates them STATICALLY
from an engine's config, so a build machine can compile every one of
them before the first request exists (aot.build) and a fresh replica
can warm-attach the results (engine.warmup).

The contract tests/test_aot.py pins: for a declared workload, the
GeometrySet's `registry_keys(engine)` equal EXACTLY the keys the live
engine notes while serving that workload — no missing (a first request
would compile) and no extra (the artifact would carry dead executables
and the build would overclaim coverage).

Every Geometry is a dict of primitives (it round-trips through the
artifact manifest's JSON); see docs/aot_warmup.md.
"""
from __future__ import annotations

import re

from ..inference.engine import bucket_length

_SAFE = re.compile(r'[^A-Za-z0-9_.]')


class Geometry:
    """One compilable dispatch shape: `kind` + a params dict of
    primitives. Kinds and their params:

      decode           batch, prompt_len, max_new_tokens
      decode_spec      batch, prompt_len, max_new_tokens, num_draft_tokens
      serve_step       window, bucket
      serve_window     window
      serve_prefill    bucket
      serve_chunk_step window, chunk, bucket (chunked/continuation
                       prefill fused with the decode window: `chunk`
                       buckets the per-step token width, `bucket` the
                       contiguous temp-cache length — the largest end
                       position in the batch)
      serve_spec_step  spec, bucket, ctx (speculative propose/verify
                       window fused with an admission prefill: `spec`
                       is the draft window k, `bucket` the admission
                       prefill bucket, `ctx` the verify's gathered
                       temp-cache length — bucket(max live context +
                       k + 1))
      serve_spec_window spec, ctx (a pure speculative window, no
                       admissions this step)
      serve_export     ctx (the KV-migration gather behind
                       `export_kv`: `ctx` buckets the exported
                       kv length — bucket(context_len - 1))
      serve_import     ctx (the KV-migration scatter behind
                       `import_kv`, same `ctx` bucketing — a decode
                       pool warms these instead of admission kinds)
      train_step       input_shapes, input_dtypes, label_shapes,
                       label_dtypes (shape entries are tuples/lists of int)
    """

    __slots__ = ('kind', 'params')

    def __init__(self, kind, **params):
        self.kind = str(kind)
        self.params = params

    def to_dict(self):
        return {'kind': self.kind, **self.params}

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        kind = d.pop('kind')
        # JSON turns tuples into lists; normalise shape-like params back
        # so keys computed from a loaded manifest equal freshly
        # enumerated ones
        for k, v in d.items():
            if isinstance(v, list):
                d[k] = tuple(tuple(x) if isinstance(x, list) else x
                             for x in v)
        return cls(kind, **d)

    def label(self):
        """Filesystem-safe short name (stablehlo export file stems,
        warmup report lines)."""

        def flat(v):
            if isinstance(v, (list, tuple)):
                return 'x'.join(flat(x) for x in v)
            return _SAFE.sub('', str(v))

        bits = [self.kind]
        for k in sorted(self.params):
            bits.append(f'{k[0]}{flat(self.params[k])}')
        return '-'.join(bits)

    def _key(self):
        def freeze(v):
            if isinstance(v, (list, tuple)):
                return tuple(freeze(x) for x in v)
            return v

        return (self.kind,
                tuple(sorted((k, freeze(v))
                             for k, v in self.params.items())))

    def __eq__(self, other):
        return (isinstance(other, Geometry)
                and self._key() == other._key())

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        return f'Geometry({self.label()})'


class GeometrySet:
    """An ordered, de-duplicated collection of Geometry entries plus
    the key-derivation against a live engine."""

    def __init__(self, entries=()):
        self.entries = []
        seen = set()
        for g in entries:
            if g not in seen:
                seen.add(g)
                self.entries.append(g)

    def __iter__(self):
        return iter(self.entries)

    def __len__(self):
        return len(self.entries)

    def to_manifest(self):
        return [g.to_dict() for g in self.entries]

    @classmethod
    def from_manifest(cls, dicts):
        return cls(Geometry.from_dict(d) for d in dicts)

    def registry_keys(self, engine):
        """The exact CompileCache keys the live `engine` notes when it
        dispatches these geometries, deduped in enumeration order.
        (Multiple geometries can share one registry key: a bucketed
        generate records one key per (B, bucket) while dispatching two
        jitted functions.)"""
        keys, seen = [], set()
        for g in self.entries:
            k = _registry_key(engine, g)
            if k not in seen:
                seen.add(k)
                keys.append(k)
        return keys


def _registry_key(engine, g):
    p = g.params
    if g.kind == 'decode':
        return engine.registry_key_generate(
            p['batch'], p['prompt_len'], p['max_new_tokens'])
    if g.kind == 'decode_spec':
        return engine.registry_key_speculative(
            p['batch'], p['prompt_len'], p['max_new_tokens'],
            p['num_draft_tokens'])
    if g.kind.startswith('serve_'):
        # the tag is the engine's to state: its table of dispatches
        return engine.registry_key(*engine._geometry_cost_tag(g))
    if g.kind == 'train_step':
        return engine.registry_key(p['input_shapes'][0],
                                   p['input_dtypes'][0])
    raise ValueError(f'unknown geometry kind {g.kind!r}')


# ---------------------------------------------------------------------------
# Per-engine enumeration
# ---------------------------------------------------------------------------

def for_decode_engine(engine, prompt_lens, batch_sizes=(1,),
                      max_new_tokens=None, spec_draft_tokens=None,
                      spec_batch_sizes=(1,)):
    """Geometries a DecodeEngine serves for the declared workload.

    `prompt_lens` — iterable of prompt lengths the deployment admits
    (only their BUCKETS matter for `generate`: one geometry per
    (batch, bucket) pair). `max_new_tokens` — per-call budgets; None
    means the engine default. `spec_draft_tokens` — iterable of k
    values to additionally enumerate speculative windows for (the
    speculative path is NOT bucketed, so every distinct prompt length
    is its own geometry there)."""
    entries = []
    mnts = (max_new_tokens if isinstance(max_new_tokens, (list, tuple))
            else [max_new_tokens])
    for B in batch_sizes:
        for mnt in mnts:
            budget = engine.max_new_tokens if mnt is None else int(mnt)
            seen_buckets = set()
            for L in prompt_lens:
                b = bucket_length(int(L), engine.buckets)
                # one representative prompt length per (bucket,
                # exactness) pair: any padded length in a bucket shares
                # one compilation (left-pad + traced real_len), but an
                # EXACT-length prompt takes the unpadded prefill and
                # the padded=False decode loop — a distinct trace under
                # the same registry key, so both variants must be
                # warmable when the workload declares both
                variant = (b, int(L) == b)
                if variant in seen_buckets:
                    continue
                seen_buckets.add(variant)
                entries.append(Geometry(
                    'decode', batch=int(B), prompt_len=int(L),
                    max_new_tokens=budget))
    if spec_draft_tokens:
        # the speculative path honors the same per-call budgets as
        # generate (and is NOT bucketed: the exact prompt length is
        # part of its cache shape, so every declared length enumerates)
        for B in spec_batch_sizes:
            for k in spec_draft_tokens:
                for mnt in mnts:
                    budget = (engine.max_new_tokens if mnt is None
                              else int(mnt))
                    for L in prompt_lens:
                        entries.append(Geometry(
                            'decode_spec', batch=int(B),
                            prompt_len=int(L), max_new_tokens=budget,
                            num_draft_tokens=int(k)))
    return GeometrySet(entries)


def for_serving_engine(engine, prompt_lens=None,
                       include_standalone_prefill=True,
                       max_new_tokens=None, migration=False):
    """Geometries a ServingEngine dispatches: one fused admit+decode
    step per admission bucket, the pure decode window, (when
    `include_standalone_prefill`) the standalone prefill each bucket
    can additionally hit on a multi-bucket admission step, and — for
    engines with `prefill_chunk` and/or `prefix_cache` configured —
    the fused chunk-continuation step per (chunk bucket, context
    bucket) pair.

    `prompt_lens` bounds the admission context lengths (prompt +
    resumed prefix) the deployment will see; default is full coverage
    of 1..max_context_len — the safe choice for an artifact, since a
    preempted request re-prefills at prompt+prefix length.

    With chunking enabled, contexts longer than `prefill_chunk` ride
    the chunk path, so the MONOLITHIC serve_step/serve_prefill buckets
    clamp to lengths <= prefill_chunk; the chunk pairs cover every
    (per-step token width, end position) bucket combination a chunked
    or prefix-hit-continuation admission can dispatch (chunk widths
    cap at bucket(prefill_chunk); with prefix caching alone the width
    is the unshared suffix, at most max_context_len - block_size
    since a hit is at least one full page).

    Disaggregated roles (engine.phase_role) reshape the set:

      'decode'   — an import-fed decode pool dispatches NO admission
                   kinds at all: only the `serve_import` scatter, the
                   one-token continuation chunk that recomputes the
                   boundary position, and the pure decode window.
                   `prompt_lens` then declares the CONTEXT lengths at
                   import (prompt + tokens generated on the prefill
                   side). Assumes no preemption re-admissions — size
                   the pool for the declared workload.
      'prefill'  — the monolithic set plus the `serve_export` gather
                   per reachable handoff context bucket (the request
                   hands off holding 1..decode_window tokens).
      'monolithic' (default) — unchanged; pass `migration=True` to
                   additionally enumerate export+import at the
                   declared buckets (a monolithic engine exercising
                   round-trip migration, e.g. the bit-equality
                   tests)."""
    W = engine.decode_window
    if prompt_lens is None:
        prompt_lens = range(1, engine.max_context_len + 1)
    prompt_lens = [int(L) for L in prompt_lens]
    chunk = getattr(engine, 'prefill_chunk', None)
    prefix = bool(getattr(engine, 'prefix_cache', False))
    spec = getattr(engine, 'spec_window', None)
    role = getattr(engine, 'phase_role', 'monolithic')
    if role == 'decode':
        return _for_decode_pool(engine, prompt_lens, W, spec,
                                max_new_tokens)
    mono_lens = (prompt_lens if chunk is None
                 else [L for L in prompt_lens if L <= chunk])
    buckets = []
    for L in mono_lens:
        b = bucket_length(L, engine.buckets)
        if b not in buckets:
            buckets.append(b)
    if spec is None:
        entries = [Geometry('serve_step', window=W, bucket=b)
                   for b in buckets]
        entries.append(Geometry('serve_window', window=W))
    else:
        # a speculative engine dispatches serve_spec_step /
        # serve_spec_window on every non-chunk iteration — the plain
        # serve_step/serve_window executables are never reached, so
        # enumerating them would stamp dead executables into the
        # artifact. The verify's gathered temp-cache length is
        # bucket(max live context + k + 1): live contexts M run from
        # the smallest declared admission length up to the largest
        # context a still-decoding row can hold — min(max prompt +
        # max_new_tokens, max_context_len) - 1 (a live row always has
        # >= 1 token of budget left), honoring per-call
        # `max_new_tokens` overrides when declared.
        k = int(spec)
        mnts = (max_new_tokens if isinstance(max_new_tokens,
                                             (list, tuple))
                else [max_new_tokens])
        budget = max(engine.max_new_tokens if m is None else int(m)
                     for m in mnts)
        m_lo = min(prompt_lens)
        m_hi = min(max(prompt_lens) + budget,
                   engine.max_context_len) - 1
        ladder, v = [], m_lo + k + 1
        while v <= m_hi + k + 1:
            b = bucket_length(v, engine.buckets)
            ladder.append(b)
            v = b + 1
        entries = []
        # fused admission + spec window: the verify bucket can never
        # sit below the smallest context this admission bucket can
        # contribute (the admitted row is live, so max-live-ctx >= its
        # own length); every ladder entry at or above that floor is
        # reachable by batching the admission with a longer-context
        # in-flight row
        for Sb in buckets:
            lmin = min(L for L in mono_lens
                       if bucket_length(L, engine.buckets) == Sb)
            floor = bucket_length(lmin + k + 1, engine.buckets)
            entries.extend(
                Geometry('serve_spec_step', spec=k, bucket=Sb, ctx=c)
                for c in ladder if c >= floor)
        entries.extend(Geometry('serve_spec_window', spec=k, ctx=c)
                       for c in ladder)
    if include_standalone_prefill:
        entries.extend(Geometry('serve_prefill', bucket=b)
                       for b in buckets)
    if (chunk is not None or prefix) and prompt_lens:
        max_end = max(prompt_lens)
        # the bucket ladder every chunk END can land on (intermediate
        # chunk ends cover 1..max_end even when prompt_lens is sparse)
        ladder, L = [], 1
        while L <= max_end:
            b = bucket_length(L, engine.buckets)
            ladder.append(b)
            L = b + 1
        if chunk is not None:
            max_take = min(chunk, max_end)
        else:
            max_take = max(1, max_end - engine.block_size)
        cb_max = bucket_length(max_take, engine.buckets)
        # equal-bucket pairs are only reachable through a start-0
        # chunked admission's FIRST chunk, whose take is exactly
        # prefill_chunk (so cb == sb == bucket(prefill_chunk), and
        # only when some declared context exceeds the chunk at all):
        # later chunks and tails sit at end > chunk (sb > cb), and a
        # prefix-hit continuation passes the profitability guard only
        # when bucket(take) < bucket(end) — any other equal pair would
        # be a dead executable in the artifact
        entries.extend(
            Geometry('serve_chunk_step', window=W, chunk=cb, bucket=sb)
            for cb in ladder if cb <= cb_max
            for sb in ladder
            if cb < sb or (chunk is not None and max_end > chunk
                           and cb == sb == cb_max))
    if role == 'prefill' or migration:
        # the handoff export: a prefill-role request hands off holding
        # g in 1..W generated tokens, so the exported kv length is
        # L + g - 1 — one serve_export per reachable bucket. The
        # migration=True monolithic variant covers the same range (an
        # export mid-decode reaches higher contexts; declare them via
        # prompt_lens).
        cxs = []
        for L in prompt_lens:
            for g in range(1, W + 1):
                n = L + g - 1
                if n < 1 or n + 1 > engine.max_context_len:
                    continue
                c = bucket_length(n, engine.buckets)
                if c not in cxs:
                    cxs.append(c)
        entries.extend(Geometry('serve_export', ctx=c) for c in cxs)
        if migration:
            entries.extend(Geometry('serve_import', ctx=c) for c in cxs)
    return GeometrySet(entries)


def _for_decode_pool(engine, context_lens, W, spec, max_new_tokens):
    """The decode-role set: import scatter + one-token continuation
    chunk per import-context bucket, plus the pure window (speculative
    engines: the spec window over its reachable verify ladder). No
    admission kinds — an import-fed pool never dispatches them, and
    enumerating them would stamp dead executables into the artifact
    (the no-extra half of the exactness contract)."""
    cb1 = bucket_length(1, engine.buckets)
    entries = []
    sbs, cxs = [], []
    for L in context_lens:
        if L < 2:
            continue               # an import carries kv_len >= 1
        sb = bucket_length(L, engine.buckets)
        if sb not in sbs:
            sbs.append(sb)
        c = bucket_length(L - 1, engine.buckets)
        if c not in cxs:
            cxs.append(c)
    entries.extend(Geometry('serve_import', ctx=c) for c in cxs)
    entries.extend(
        Geometry('serve_chunk_step', window=W, chunk=cb1, bucket=sb)
        for sb in sbs)
    if spec is None:
        entries.append(Geometry('serve_window', window=W))
    else:
        # the verify ladder over live decode contexts, exactly the
        # monolithic spec derivation with import contexts as the floor
        k = int(spec)
        mnts = (max_new_tokens if isinstance(max_new_tokens,
                                             (list, tuple))
                else [max_new_tokens])
        budget = max(engine.max_new_tokens if m is None else int(m)
                     for m in mnts)
        lens = [L for L in context_lens if L >= 2]
        if lens:
            m_lo = min(lens)
            m_hi = min(max(lens) + budget, engine.max_context_len) - 1
            ladder, v = [], m_lo + k + 1
            while v <= m_hi + k + 1:
                b = bucket_length(v, engine.buckets)
                ladder.append(b)
                v = b + 1
            entries.extend(Geometry('serve_spec_window', spec=k, ctx=c)
                           for c in ladder)
    return GeometrySet(entries)


def for_train_engine(engine, batch_shape, batch_dtype='int32',
                     extra_input_shapes=(), extra_input_dtypes=(),
                     label_shapes=(), label_dtypes=()):
    """The fused-train-step geometry for one global batch shape (pass
    several shapes through repeated calls + `GeometrySet(a.entries +
    b.entries)` if the loader yields more than one)."""
    shapes = (tuple(int(s) for s in batch_shape),) + tuple(
        tuple(int(s) for s in sh) for sh in extra_input_shapes)
    dtypes = (str(batch_dtype),) + tuple(str(d) for d in extra_input_dtypes)
    return GeometrySet([Geometry(
        'train_step',
        input_shapes=shapes, input_dtypes=dtypes,
        label_shapes=tuple(tuple(int(s) for s in sh)
                           for sh in label_shapes),
        label_dtypes=tuple(str(d) for d in label_dtypes))])


# ---------------------------------------------------------------------------
# Donation contract per serve kind
# ---------------------------------------------------------------------------

# Which ARGUMENT NAMES each serve-dispatch kind donates to jit — the
# single source of truth shared by the dispatch decorators in
# inference/serving.py and the hlolint HL001 prover (which counts the
# `input_output_alias` entries XLA actually emitted against the flat
# leaves of these args). serve_export deliberately donates NOTHING:
# the source pool must survive the export (the request keeps serving
# until its owner retires it).
DONATED_ARGNAMES = {
    'serve_step': ('pages', 'last_logits'),
    'serve_window': ('pages', 'last_logits'),
    'serve_prefill': ('pages', 'last_logits'),
    'serve_chunk_step': ('pages', 'last_logits'),
    'serve_spec_step': ('pages', 'dpages', 'last_logits'),
    'serve_spec_window': ('pages', 'dpages', 'last_logits'),
    'serve_export': (),
    'serve_import': ('pages',),
}


def donated_argnames(kind):
    """Declared donated argument names for a serve-dispatch geometry
    kind. Raises on unknown kinds so a new dispatch cannot silently
    ship with an undeclared (and therefore unproven) donation
    contract."""
    try:
        return DONATED_ARGNAMES[kind]
    except KeyError:
        raise ValueError(
            f'no declared donation contract for geometry kind {kind!r}'
            f' — add it to aot.geometry.DONATED_ARGNAMES') from None


def for_engine(engine, **workload):
    """Dispatch on engine type (the `aot.build` entry point)."""
    from ..inference.engine import DecodeEngine
    from ..inference.serving import ServingEngine
    from ..training.engine import TrainEngine

    if isinstance(engine, ServingEngine):
        return for_serving_engine(engine, **workload)
    if isinstance(engine, DecodeEngine):
        return for_decode_engine(engine, **workload)
    if isinstance(engine, TrainEngine):
        return for_train_engine(engine, **workload)
    raise TypeError(
        f'no geometry enumeration for {type(engine).__name__}; expected '
        f'a DecodeEngine, ServingEngine, or TrainEngine')


__all__ = ['Geometry', 'GeometrySet', 'for_engine', 'for_decode_engine',
           'for_serving_engine', 'for_train_engine',
           'DONATED_ARGNAMES', 'donated_argnames']
