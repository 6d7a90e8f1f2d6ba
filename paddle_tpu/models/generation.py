"""Shared autoregressive generation over the cached-call contract (ref:
python/paddle/nn/decode.py + the reference generation loops).

Any causal LM that implements
  - ``init_cache(batch_size, max_len, dtype=None)`` and
  - ``self(input_ids, caches=..., cache_index=...) -> (logits, caches)``
gets greedy/temperature/top-k/top-p sampling and beam search by mixing
this in (LlamaForCausalLM, MoEForCausalLM). Everything is static-shape
`lax.scan` so one call compiles to a single XLA program.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np


def filter_logits(logits, top_k=0, top_p=1.0):
    """top-k / nucleus filtering on (already temperature-scaled) logits
    — the one implementation behind sampled generate() and sampled
    speculative decoding (filtering both target and draft keeps the
    rejection-sampling identity: it holds for ANY pt/pd pair).

    top_k may be a Python int (static: folded into the trace) or a
    traced scalar (e.g. a serving knob passed as a jit argument): the
    traced path clamps with lax.min/max and gathers the k-th threshold
    dynamically — no host sync, and top_k <= 0 still means keep-all.
    """
    V = logits.shape[-1]
    if isinstance(top_k, jax.core.Tracer):
        # clamp to [1, V] on device; the k<1 case is masked out by the
        # where(top_k > 0, ...) below, the clamp just keeps the gather
        # index in bounds
        k = jax.lax.max(jnp.int32(1),
                        jax.lax.min(jnp.asarray(top_k, jnp.int32),
                                    jnp.int32(V)))
        srt = jnp.sort(logits, axis=-1)
        idx = jnp.broadcast_to(jnp.asarray(V - k, jnp.int32),
                               logits.shape[:-1] + (1,))
        kth = jnp.take_along_axis(srt, idx, axis=-1)
        logits = jnp.where(top_k > 0,
                           jnp.where(logits < kth, -jnp.inf, logits),
                           logits)
    elif top_k > 0:
        # clamp to the vocab (HF semantics): top_k > V means "keep all",
        # not an IndexError at trace time
        top_k = min(int(top_k), V)
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), -1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def default_positions(batch, seq, cache_index=None, kv_write_pos=None):
    """The serving-contract position rule shared by every causal LM:
    per-row offsets when kv_write_pos is given (batched speculative),
    else the uniform cache_index base."""
    if kv_write_pos is not None:
        wp = jnp.reshape(jnp.asarray(kv_write_pos, jnp.int32), (-1,))
        positions = wp[:, None] + jnp.arange(seq)[None, :]
    else:
        base = 0 if cache_index is None else cache_index
        positions = base + jnp.arange(seq)[None, :].astype(jnp.int32)
    return jnp.broadcast_to(positions, (batch, seq))


class QuantKVCache(typing.NamedTuple):
    """Cache-KV int8 (ref capability:
    python/paddle/incubate/nn/functional/block_multihead_attention.py:44,60
    — dynamic/static cache-KV quantization in the reference serving
    stack). K/V live int8 in HBM with per-(kv-head, dim) f32 scales,
    calibrated at prefill ('dynamic' in the reference's terms) and held
    static over decode. Halves the cache stream — the binding term of
    decode at batch >= 8 and long contexts."""

    kq: jax.Array        # int8 (B, max_len, Hkv, D)
    vq: jax.Array        # int8 (B, max_len, Hkv, D)
    kscale: jax.Array    # f32 (Hkv, D)
    vscale: jax.Array    # f32 (Hkv, D)


class QuantPagedKVCache(typing.NamedTuple):
    """Int8 paged KV pool with PER-ROW scales (the ServingEngine's
    `kv_cache_dtype='int8'` layout — ref capability: the reference
    serving stack's cache-KV int8 block_multihead_attention). K/V pages
    live int8; each written row (one token's K or V at one kv head)
    carries its own f32 scale at `ks[page, head, slot]` /
    `vs[page, head, slot]`, computed from that row alone
    (`quantize_kv_row`). Per-row scales make quantization a pure
    function of the token's bf16 K/V row — independent of write
    batching — so re-prefill after preemption, prefix-cache sharing,
    CoW copies, and snapshot/restore all reproduce bit-identical int8
    pages, which is what keeps greedy serving streams bit-equal across
    every scheduler path. Storage overhead is 4/D per element (~6% at
    D=64). Halves the decode cache stream vs bf16 — the binding term
    at batch >= 8 and long contexts."""

    kp: jax.Array        # int8 (num_blocks, Hkv, block_size, D)
    vp: jax.Array        # int8 (num_blocks, Hkv, block_size, D)
    ks: jax.Array        # f32 (num_blocks, Hkv, block_size)
    vs: jax.Array        # f32 (num_blocks, Hkv, block_size)


class RowQuantKVCache(typing.NamedTuple):
    """CONTIGUOUS int8 KV cache with per-row scales — the temp-cache
    twin of QuantPagedKVCache, used by the serving engine's fused
    multi-token bodies (admission prefill, chunked prefill, the
    speculative verify): rows gathered from int8 pages stay int8 here
    (scales ride along), and rows the forward writes quantize with the
    SAME per-row rule the paged pools use. Attending through this
    cache therefore sees exactly the int8-roundtripped values a paged
    decode step would — the invariant that makes int8 serving streams
    bit-equal across monolithic prefill, chunked prefill, speculative
    windows, and plain decode (every path attends the same quantized
    world). Layouts: kq/vq (B, max_len, Hkv, D) int8, ks/vs
    (B, max_len, Hkv) f32."""

    kq: jax.Array
    vq: jax.Array
    ks: jax.Array
    vs: jax.Array


class PagedKVCache(typing.NamedTuple):
    """Paged (block-table) KV cache for continuous-batching serving
    (ref capability: the reference serving stack's
    block_multihead_attention pages; design: vLLM PagedAttention). K/V
    live as a POOL of fixed-size pages (num_blocks, Hkv, block_size, D)
    shared by every in-flight request; a per-request block table maps
    logical block j of the sequence to a physical page id. Page 0 is
    reserved as the SCRATCH page (inactive/finished rows write there
    harmlessly), so allocators hand out ids >= 1 — see
    inference/serving.py::BlockAllocator. Decode steps route through
    `cached_attention(..., block_tables=...)`, which dispatches the
    fused pallas paged kernel on TPU and a gather reference elsewhere."""

    kp: jax.Array        # (num_blocks, Hkv, block_size, D) pages
    vp: jax.Array        # (num_blocks, Hkv, block_size, D) pages


class PageKind(typing.NamedTuple):
    """One kind of KV page a model keeps, as the model states it
    (`GenerationMixin.page_kinds`) and the caches, the serving engine's
    allocators and tables, and its page counts read it: the layers whose
    pools have this shape, their kv heads, the widths of a K and of a V
    row, and `window` where no layer of the kind attends further back
    than that many positions, so that pages wholly behind it need not
    be kept (None: every page of the context is kept)."""

    name: str
    layers: tuple
    kv_heads: int
    k_width: int
    v_width: int
    window: typing.Optional[int] = None


def lane_padded(width):
    """The minor dim a page pool gives a row of `width`: the row itself up
    to one 128-lane tile or at whole tiles, else the next whole tile, the
    rest zero. The chip's HBM tiling holds such a row in whole tiles
    whatever the logical shape says, and Mosaic refuses to slice a page
    whose minor dim is neither (a K row of 192): stating the tiles keeps
    the pool's bytes what they were and lets the paged kernel copy it."""
    return width if width <= 128 else -(-width // 128) * 128


def pad_lanes(x, width):
    """x with its minor dim zero-padded to `width` (a pool's, see
    `lane_padded`); x itself where it is that wide."""
    short = width - x.shape[-1]
    return x if not short else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def pool_rows_set(pool, pages, slots, rows):
    """`pool` (num_blocks, Hkv, block_size, D) with `rows` (n, Hkv, D)
    written at (pages[i], :, slots[i], :): what a decode step and an
    admission prefill do to a page pool. With fewer than 8 kv heads the
    write goes to the pool seen as (num_blocks, Hkv * block_size, D) rows
    (the same bytes): XLA lays the operand of the 4-D scatter out heads-
    minor for such a pool, and the paged kernel, which wants a page's
    (block_size, D) tiles, then gets a copy of the WHOLE pool every
    token-step (two full-layer pools of 0.8 GB cost 12 % of a serving
    cell's device time: chip, PR 33). From 8 heads on the 4-D form keeps
    the layout and stays as it was."""
    nb, hkv, bs, d = pool.shape
    if hkv >= 8:
        return pool.at[pages, :, slots, :].set(rows)
    at = jnp.arange(hkv, dtype=slots.dtype) * bs + slots[:, None]
    return pool.reshape(nb, hkv * bs, d).at[pages[:, None], at, :].set(
        rows).reshape(pool.shape)


def layer_kinds(kinds, num_layers):
    """Per layer, the index in `kinds` of its kind."""
    of = {l: i for i, k in enumerate(kinds) for l in k.layers}
    return [of[l] for l in range(num_layers)]


def layer_tables(kinds, block_tables, num_layers):
    """Per layer, its kind's block table: `block_tables` is one table
    where the model has one kind of page, else one a kind in the kinds'
    order."""
    if len(kinds) == 1:
        return [block_tables] * num_layers
    return [block_tables[i] for i in layer_kinds(kinds, num_layers)]


def quantize_kv_rows(x, scale):
    """Symmetric int8 quantization of new K/V rows (B, S, Hkv, D) with
    per-(head, dim) scales; saturates rows that exceed the prefill
    calibration range."""
    q = jnp.round(x.astype(jnp.float32) / scale[None, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def calibrate_kv_scale(x, margin=1.0):
    """Per-(kv-head, dim) amax scales from the prefill rows."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(0, 1))
    return jnp.maximum(amax * margin, 1e-6) / 127.0


def quantize_kv_row(x):
    """PER-ROW symmetric int8 quantization: each (..., Hkv, D) row
    quantizes against its own per-(row, head) amax — a pure function
    of the row's values, so the SAME bf16 row always produces the SAME
    int8 bytes + scale no matter when or where it is written (prefill
    scatter, decode append, chunk continuation, speculative verify,
    re-prefill after preemption). Returns (q int8 (..., Hkv, D),
    scale f32 (..., Hkv))."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv_row(q, scale, dtype):
    """Inverse of `quantize_kv_row`: int8 rows x their per-row scales,
    cast to the compute dtype. The ONE dequant expression every
    attention path shares (paged gather reference, RowQuant contiguous
    fallback, pallas in-VMEM) so the attended values are bit-identical
    across them."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def filter_logits_batched(logits, top_k, top_p, *, any_top_k=None,
                          any_top_p=None):
    """Per-ROW top-k / nucleus filtering: `top_k` (B,) int32 and
    `top_p` (B,) f32 ride as DEVICE data, so a batch can mix greedy,
    top-k, and nucleus rows in one trace (the serving engine's
    per-request sampling — changing the mix never retraces). Semantics
    per row match `filter_logits` exactly: top_k <= 0 keeps all,
    top_k > V clamps to keep-all, top_p == 1.0 is a no-op (masked, not
    skipped — the cumsum's float roundoff must not drop valid tokens
    for keep-all rows).

    A batch pays for the filters its rows ask for: the top-k sort runs
    under a `lax.cond` on "any row has top_k > 0", the nucleus sort,
    softmax and cumsum under one on "any row has top_p < 1". A row that
    keeps everything is masked out of either, so skipping them changes
    no row of any mix. `any_top_k` / `any_top_p` are those scalars when
    the caller already holds them (a scan closes over them: they do not
    change inside a window). Never call this under `vmap`: a batched
    predicate lowers to a select and both sides run."""
    V = logits.shape[-1]
    top_k = jnp.asarray(top_k, jnp.int32)
    tp = jnp.asarray(top_p, jnp.float32)
    if any_top_k is None:
        any_top_k = jnp.any(top_k > 0)
    if any_top_p is None:
        any_top_p = jnp.any(tp < 1.0)

    def keep_top_k(logits):
        k = jnp.clip(top_k, 1, V)
        srt = jnp.sort(logits, axis=-1)
        kth = jnp.take_along_axis(srt, (V - k)[:, None], axis=-1)
        return jnp.where((top_k > 0)[:, None],
                         jnp.where(logits < kth, -jnp.inf, logits), logits)

    def keep_nucleus(logits):
        sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), -1)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < tp[:, None], axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx, axis=-1)
        nucleus = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jnp.where((tp < 1.0)[:, None], nucleus, logits)

    logits = jax.lax.cond(any_top_k, keep_top_k, lambda lg: lg, logits)
    return jax.lax.cond(any_top_p, keep_nucleus, lambda lg: lg, logits)


class GenerationMixin:
    def quantize_weights(self, bits=8):
        """Weight-only PTQ for serving: every 2-D trainable projection
        becomes a pallas-served QuantizedWeight (int8 or packed int4) —
        decode streams 2x/4x fewer weight bytes from HBM. Per-model
        exemptions are STRUCTURAL: lookup tables / routers declare
        `no_quantize` on their layer class (embed_tokens, wte/wpe, MoE
        gates) and nn.Embedding subtrees are never touched. Returns a
        new model; the original is untouched.

        MoE expert weights (E, in, out) quantize too at bits=8
        (per-(expert, out-col) scales). Caveats: int4 leaves experts fp
        (packing unimplemented), and tied heads served off the embedding
        table stay full precision (see
        quantization.quantize_matmul_weights)."""
        from ..quantization import quantize_matmul_weights

        return quantize_matmul_weights(self, bits=bits, min_features=1)

    def cache_dtype(self):
        """Dtype for the preallocated KV cache — override per model
        (usually the embedding table's dtype)."""
        raise NotImplementedError

    def page_kinds(self):
        """The kinds of KV page this model's layers keep (`PageKind`),
        the ONE statement of its cache's shape: `init_cache`,
        `init_paged_cache` and the serving engine read it and nothing
        else guesses. Default: one kind for every layer, from
        `self.config` (`num_key_value_heads`, `head_dim` or hidden_size
        // num_attention_heads), every page kept. A model whose layers
        differ in kv heads or widths, or whose window layers' pages may
        be recycled, overrides this."""
        cfg = self.config
        head_dim = getattr(cfg, 'head_dim', None)
        if head_dim is None:
            head_dim = cfg.hidden_size // cfg.num_attention_heads
        kv_heads = (getattr(cfg, 'num_key_value_heads', None)
                    or cfg.num_attention_heads)
        return (PageKind('full', tuple(range(cfg.num_hidden_layers)),
                         kv_heads, head_dim, head_dim),)

    def init_cache(self, batch_size, max_len, dtype=None, quantized=False):
        """Per-layer (k, v) zero pairs of (B, max_len, kv_heads, head_dim),
        in the shapes `page_kinds` states.

        quantized=True returns QuantKVCache entries (int8 data +
        per-(head, dim) scales). The first cached call must be a
        multi-token prefill — that's where the scales calibrate."""
        cfg = self.config
        kinds = self.page_kinds()
        if len(kinds) > 1:
            if quantized:
                raise NotImplementedError(
                    f'{type(self).__name__} keeps {len(kinds)} kinds of '
                    f'KV page: its contiguous cache has no int8 form')
            dtype = dtype or self.cache_dtype()
            rows = (batch_size, max_len)
            return [(jnp.zeros(rows + (kinds[i].kv_heads, kinds[i].k_width),
                               dtype),
                     jnp.zeros(rows + (kinds[i].kv_heads, kinds[i].v_width),
                               dtype))
                    for i in layer_kinds(kinds, cfg.num_hidden_layers)]
        kv_heads, head_dim = kinds[0].kv_heads, kinds[0].k_width
        dtype = dtype or self.cache_dtype()
        shape = (batch_size, max_len, kv_heads, head_dim)

        def make():
            return jnp.zeros(shape, dtype)

        mesh = None
        if not isinstance(batch_size, jax.core.Tracer):
            from ..distributed.mesh import get_mesh

            mesh = get_mesh()
        if mesh is not None:
            # sharded serving (ref: fleet mpu mp_layers serving path —
            # mp_layers.py:47,334,541): KV cache lives TP-sharded on the
            # heads axis (and dp/fsdp on batch when divisible) so a
            # 7B-class model's cache splits across chips instead of
            # replicating; GSPMD keeps the decode step's attention local
            # to each head shard
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..distributed.parallel import _valid_spec

            spec = _valid_spec(P(('dp', 'fsdp'), None, 'tp', None),
                               shape, mesh)
            sharding = NamedSharding(mesh, spec)

            def make():  # noqa: F811 - mesh-aware variant
                return jax.device_put(jnp.zeros(shape, dtype), sharding)

        if quantized:
            sshape = (kv_heads, head_dim)

            def make_scale():
                return jnp.zeros(sshape, jnp.float32)

            if mesh is not None:
                sspec = _valid_spec(P('tp', None), sshape, mesh)
                ssharding = NamedSharding(mesh, sspec)

                def make_scale():  # noqa: F811
                    return jax.device_put(jnp.zeros(sshape, jnp.float32),
                                          ssharding)

            def make_q():
                z = jnp.zeros(shape, jnp.int8)
                return jax.device_put(z, sharding) if mesh is not None else z

            return [QuantKVCache(make_q(), make_q(), make_scale(),
                                 make_scale())
                    for _ in range(cfg.num_hidden_layers)]
        return [(make(), make()) for _ in range(cfg.num_hidden_layers)]

    def init_paged_cache(self, num_blocks, block_size, dtype=None):
        """Per-layer PagedKVCache pools of (num_blocks, kv_heads,
        block_size, head_dim) zero pages (a row wider than a lane tile
        in whole tiles: `lane_padded`). The pool is request-agnostic:
        the ServingEngine's BlockAllocator hands page ids to requests
        and the per-request block tables ride into each decode step as
        device data (inference/serving.py). Page 0 is the reserved
        scratch page, so a usable pool needs num_blocks >= 2. The pools'
        shapes are `page_kinds`'; a model of several kinds takes one
        `num_blocks` a kind, in their order."""
        from ..distributed.mesh import get_mesh

        cfg = self.config
        kinds = self.page_kinds()
        dtype = dtype or self.cache_dtype()
        dtype = jnp.dtype(dtype)
        quant = dtype == jnp.int8
        if len(kinds) > 1:
            # a pool group a kind, each in its own shape (no padding of
            # one kind to another's heads or widths)
            if quant or get_mesh() is not None:
                raise NotImplementedError(
                    f'{type(self).__name__} keeps {len(kinds)} kinds of '
                    f'KV page: no int8 and no tp-sharded pools for it')
            shapes = [(int(n), k.kv_heads, int(block_size))
                      for n, k in zip(num_blocks, kinds)]
            return [PagedKVCache(
                jnp.zeros(shapes[i] + (lane_padded(kinds[i].k_width),), dtype),
                jnp.zeros(shapes[i] + (lane_padded(kinds[i].v_width),), dtype))
                for i in layer_kinds(kinds, cfg.num_hidden_layers)]
        kv_heads, head_dim = kinds[0].kv_heads, lane_padded(kinds[0].k_width)
        shape = (int(num_blocks), kv_heads, int(block_size), head_dim)
        sshape = shape[:3]                    # per-row scales (NB,Hkv,BS)

        def make(sh=shape, dt=dtype):
            return jnp.zeros(sh, dt)

        mesh = get_mesh()
        if mesh is not None:
            # TP-sharded serving (ROADMAP item 1; the ServingEngine
            # activates its mesh around this call): the page pools
            # carry a NamedSharding splitting the kv-head dim over
            # 'tp' — a 7B-class model's paged KV splits across chips
            # instead of replicating, mirroring init_cache's layout.
            # Page ids / block tables stay replicated host state.
            # kv_heads % tp != 0 clamps to replicated (the GQA
            # fallback, same as init_cache).
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..distributed.parallel import _valid_spec

            spec = _valid_spec(P(None, 'tp', None, None), shape, mesh)
            sharding = NamedSharding(mesh, spec)
            sspec = _valid_spec(P(None, 'tp', None), sshape, mesh)
            ssharding = NamedSharding(mesh, sspec)

            def make(sh=shape, dt=dtype):  # noqa: F811 - mesh-aware
                s = ssharding if len(sh) == 3 else sharding
                return jax.device_put(jnp.zeros(sh, dt), s)

        if quant:
            # int8 pages + per-row f32 scales (QuantPagedKVCache): the
            # scale pools shard on the same kv-head axis, so one page's
            # data AND its scales live on the same shard
            return [QuantPagedKVCache(make(), make(),
                                      make(sshape, jnp.float32),
                                      make(sshape, jnp.float32))
                    for _ in range(cfg.num_hidden_layers)]
        return [PagedKVCache(make(), make())
                for _ in range(cfg.num_hidden_layers)]

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0, top_k=0,
                 top_p=1.0, rng_key=None, eos_token_id=None, num_beams=1,
                 length_penalty=0.0, attention_mask=None,
                 kv_cache_int8=False):
        """attention_mask (B, S) 0/1 supports LEFT-padded batches of
        unequal-length prompts (HF decoder-only convention): positions
        are counted from each row's first real token and pad rows never
        receive attention. Requires the model's cached forward to accept
        `positions`/`kvalid` (the Llama family does).

        kv_cache_int8=True serves with a quantized KV cache (see
        QuantKVCache): scales calibrate on the prompt, decode streams
        half the cache bytes. Requires a multi-token prompt."""
        if attention_mask is not None and not isinstance(
                attention_mask, jax.core.Tracer):
            # HF tokenizers hand back an all-ones mask for equal-length
            # batches; collapsing it to None BEFORE the capability
            # checks keeps GPT/beam-search usable with standard HF
            # pipelines and preserves the fused decode kernel. The
            # collapse (and the left-contiguity fast path) inspect
            # CONCRETE masks only — a traced mask skips both, so
            # jit-wrapping generate() should pass mask=None for
            # equal-length batches (the errors below say so).
            if bool(np.asarray(attention_mask).all()):
                attention_mask = None
        if attention_mask is not None:
            import inspect

            traced_hint = (
                ' (note: the mask is a tracer here — the all-ones '
                'collapse only inspects concrete masks, so jit-wrapped '
                'generate() must pass attention_mask=None for '
                'equal-length batches)'
                if isinstance(attention_mask, jax.core.Tracer) else '')
            params = inspect.signature(self.forward).parameters
            if 'kvalid' not in params:
                raise NotImplementedError(
                    f'{type(self).__name__} does not support attention_mask '
                    f'generation (cached forward lacks positions/kvalid)'
                    + traced_hint)
            if num_beams > 1:
                raise NotImplementedError(
                    'attention_mask + beam search is not supported yet'
                    + traced_hint)
        # decode always runs in eval mode: dropout inside the scan would
        # corrupt greedy decoding and make beam scores non-deterministic
        # (the mode flag is static layer state, restored on exit)
        was_training = bool(getattr(self, 'training', False))
        if was_training:
            self.eval()
        try:
            if num_beams > 1:
                if temperature != 0.0 or top_k != 0 or top_p != 1.0:
                    raise ValueError(
                        'beam search is deterministic: temperature/top_k/'
                        'top_p are not supported with num_beams > 1')
                return self.beam_search(input_ids, max_new_tokens, num_beams,
                                        eos_token_id=eos_token_id,
                                        length_penalty=length_penalty,
                                        kv_cache_int8=kv_cache_int8)
            return self._generate_sample(input_ids, max_new_tokens,
                                         temperature, top_k, top_p, rng_key,
                                         eos_token_id, attention_mask,
                                         kv_cache_int8=kv_cache_int8)
        finally:
            if was_training:
                self.train()

    def beam_search(self, input_ids, max_new_tokens=32, num_beams=4,
                    eos_token_id=None, length_penalty=0.0,
                    kv_cache_int8=False):
        """Static-shape beam search with a shared KV-cache (ref:
        python/paddle/nn/decode.py::BeamSearchDecoder semantics on the
        causal-LM surface).

        Every step scores all num_beams*vocab continuations, keeps the
        top num_beams by cumulative log-prob (finished beams frozen),
        and gathers the KV-cache rows along the flattened batch*beam
        axis — one `lax.scan`, fully jittable.
        """
        B, S = input_ids.shape
        if kv_cache_int8 and S < 2:
            raise ValueError(
                'kv_cache_int8 needs a multi-token prompt: the per-head '
                'scales calibrate on the prefill rows')
        K = num_beams
        max_len = S + max_new_tokens
        NEG = -1e9

        # prefill ONCE at batch B, then replicate the KV rows K ways —
        # the K beams share an identical prompt, so prefilling (B*K, S)
        # would do K-fold redundant attention/MLP work
        caches = self.init_cache(B, max_len, quantized=kv_cache_int8)
        logits, caches = self(input_ids, caches=caches, cache_index=0)
        # replicate per-beam: only the 4-D (B, L, H, D) data leaves have a
        # batch axis — QuantKVCache scales are 2-D and beam-invariant
        caches = jax.tree.map(
            lambda c: jnp.repeat(c, K, axis=0) if c.ndim == 4 else c, caches)
        logp = jax.nn.log_softmax(
            logits[:, -1, :].astype(jnp.float32), axis=-1)
        logp = jnp.repeat(logp, K, axis=0)               # (B*K, V)
        V = logp.shape[-1]

        def select_and_reorder(scores_kv, caches, bufs):
            """scores_kv: (B, K, V) candidate scores → top-K beams."""
            flat = scores_kv.reshape(B, K * V)
            top_scores, top_idx = jax.lax.top_k(flat, K)  # (B, K)
            beam_idx = top_idx // V
            tok = (top_idx % V).astype(input_ids.dtype)
            gather = (jnp.arange(B)[:, None] * K + beam_idx).reshape(-1)
            caches = jax.tree.map(
                lambda c: c[gather] if c.ndim == 4 else c, caches)
            bufs = [b[jnp.arange(B)[:, None], beam_idx] for b in bufs]
            return top_scores, tok, caches, bufs, beam_idx

        # first expansion: all K rows hold the same prefix — keep only
        # beam 0's candidates or every beam would duplicate
        first = jnp.where(jnp.arange(K)[None, :, None] == 0,
                          logp.reshape(B, K, V), NEG)
        tokens_buf = jnp.zeros((B, K, max_new_tokens), input_ids.dtype)
        finished0 = jnp.zeros((B, K), bool)
        lengths0 = jnp.ones((B, K), jnp.float32)
        scores, tok, caches, (tokens_buf,), _ = select_and_reorder(
            first, caches, [tokens_buf])
        tokens_buf = tokens_buf.at[:, :, 0].set(tok)
        if eos_token_id is not None:
            finished0 = tok == eos_token_id

        def step(carry, i):
            scores, tok, finished, lengths, caches, tokens_buf = carry
            logits, caches = self(tok.reshape(B * K, 1), caches=caches,
                                  cache_index=S + i)
            logp = jax.nn.log_softmax(
                logits[:, -1, :].astype(jnp.float32), -1).reshape(B, K, V)
            if eos_token_id is not None:
                # finished beams emit only eos at zero cost (frozen score)
                frozen = jnp.full((V,), NEG).at[eos_token_id].set(0.0)
                logp = jnp.where(finished[:, :, None], frozen[None, None],
                                 logp)
            cand = scores[:, :, None] + logp
            scores, tok, caches, bufs, beam_idx = select_and_reorder(
                cand, caches, [tokens_buf, finished.astype(jnp.float32),
                               lengths])
            tokens_buf, finished_f, lengths = bufs
            finished = finished_f > 0.5
            lengths = jnp.where(finished, lengths, lengths + 1)
            if eos_token_id is not None:
                finished = finished | (tok == eos_token_id)
            tokens_buf = tokens_buf.at[:, :, i + 1].set(tok)
            return (scores, tok, finished, lengths, caches, tokens_buf), None

        if max_new_tokens > 1:
            (scores, _, finished, lengths, _, tokens_buf), _ = jax.lax.scan(
                step, (scores, tok, finished0, lengths0, caches, tokens_buf),
                jnp.arange(max_new_tokens - 1))
        else:
            lengths = lengths0

        if length_penalty:
            final = scores / (lengths ** length_penalty)
        else:
            final = scores
        best = jnp.argmax(final, axis=-1)                # (B,)
        seq = tokens_buf[jnp.arange(B), best]            # (B, max_new)
        return jnp.concatenate([input_ids, seq], axis=1)

    def _generate_sample(self, input_ids, max_new_tokens=32, temperature=0.0,
                         top_k=0, top_p=1.0, rng_key=None, eos_token_id=None,
                         attention_mask=None, kv_cache_int8=False):
        """Greedy / sampled decode with a preallocated KV-cache.

        Functional loop (`lax.while_loop`-shaped via scan): prefill once,
        then one-token steps; static shapes throughout so the whole decode
        compiles to a single XLA program. With `attention_mask`, prompts
        are LEFT-padded: per-row positions count real tokens only and
        pad cache rows stay invalid for every later step.
        """
        B, S = input_ids.shape
        if kv_cache_int8 and S < 2:
            raise ValueError(
                'kv_cache_int8 needs a multi-token prompt: the per-head '
                'scales calibrate on the prefill rows')
        max_len = S + max_new_tokens
        caches = self.init_cache(B, max_len, quantized=kv_cache_int8)
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)

        if attention_mask is not None:
            import inspect

            am = jnp.asarray(attention_mask, jnp.int32)
            # pad rows clip to position 0; they are masked out anyway
            prompt_pos = jnp.maximum(jnp.cumsum(am, axis=1) - 1, 0)
            real_len = am.sum(axis=1).astype(jnp.int32)       # (B,)
            kvalid = jnp.concatenate(
                [am, jnp.ones((B, max_new_tokens), jnp.int32)], axis=1)
            extra = dict(positions=prompt_pos, kvalid=kvalid)
            # left-padded masks are the contiguous window [S - real_len,
            # now]: models that accept kv_start keep the fused decode
            # kernel (per-row start) instead of the masked XLA fallback.
            # Gate on verified left-contiguity (host check on the
            # concrete mask): a right-padded or holed mask must keep the
            # exact masked path — kv_start would attend the wrong window.
            if ('kv_start' in inspect.signature(self.forward).parameters
                    and not isinstance(am, jax.core.Tracer)):
                amn = np.asarray(am)
                rl = amn.sum(axis=1)
                left_contig = bool(
                    (amn == (np.arange(S)[None, :]
                             >= (S - rl)[:, None])).all())
                if left_contig:
                    extra['kv_start'] = S - real_len
        else:
            extra = {}

        # prefill
        logits, caches = self(input_ids, caches=caches, cache_index=0,
                              **extra)
        last_logits = logits[:, -1, :]

        def sample(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(input_ids.dtype)
            logits = filter_logits(logits / temperature, top_k, top_p)
            return jax.random.categorical(key, logits, axis=-1).astype(input_ids.dtype)

        finished0 = jnp.zeros((B,), bool)

        def step(carry, _):
            last_logits, caches, idx, key, finished = carry
            key, sub = jax.random.split(key)
            tok = sample(last_logits, sub)
            if eos_token_id is not None:
                # finished rows emit eos forever (HF pads with
                # pad_token_id == eos in the default setup)
                tok = jnp.where(finished,
                                jnp.asarray(eos_token_id, tok.dtype), tok)
                finished = finished | (tok == eos_token_id)
            if attention_mask is not None:
                # per-row rope position = real tokens so far; buffer
                # index stays the uniform idx
                step_extra = dict(
                    positions=(real_len + (idx - S))[:, None], kvalid=kvalid)
                if 'kv_start' in extra:
                    step_extra['kv_start'] = extra['kv_start']
            else:
                step_extra = {}
            logits, caches = self(tok[:, None], caches=caches, cache_index=idx,
                                  **step_extra)
            return (logits[:, -1, :], caches, idx + 1, key, finished), tok

        (_, _, _, _, _), tokens = jax.lax.scan(
            step,
            (last_logits, caches, jnp.asarray(S, jnp.int32), rng_key,
             finished0),
            None, length=max_new_tokens,
        )
        return jnp.concatenate([input_ids, tokens.T], axis=1)


def generate_speculative(target, draft, input_ids, max_new_tokens=32,
                         num_draft_tokens=4, eos_token_id=None,
                         kv_cache_int8=False):
    """Greedy speculative decoding (ref capability: the reference
    ecosystem's speculative/draft-model inference).

    LOSSLESS for greedy: emits exactly the tokens `target.generate(...)`
    would, but the big model runs one forward per accepted window
    (~(m+1) tokens per dispatch, m = accepted draft prefix) instead of
    one per token. Both models keep KV caches; rejected draft rows are
    simply overwritten on the next window (cache writes always start at
    the committed length, and position masking hides rows beyond it).

    The accepted length is data-dependent, but at batch 1 it only
    steers on-device state, so the whole window loop runs as ONE
    compiled lax.while_loop with a single host sync per call — the win
    is fewer *target* forwards, which is what dominates when the draft
    is much smaller. Batched prompts (B > 1, equal length; these sync
    once per window) commit per row at their own rates via per-row
    cache write offsets (`kv_write_pos` — models that lack it are
    batch-1 only): each row commits by the same greedy rule its solo
    `generate()` follows. (As with batched generate(), bit-exactness vs
    a SOLO run holds unless some step's top-2 logits sit within float
    rounding of each other — XLA may tile batched matmuls differently;
    see examples/generate.py for the same caveat.)

    kv_cache_int8=True serves BOTH models with quantized KV caches
    (scales calibrate at their prefills); the greedy commit rule then
    matches `target.generate(..., kv_cache_int8=True)`.
    """
    B, S = input_ids.shape
    if kv_cache_int8 and S < 2:
        raise ValueError(
            'kv_cache_int8 needs a multi-token prompt: the per-head '
            'scales calibrate on the prefill rows')
    if B != 1:
        import inspect

        for m_ in (target, draft):
            if 'kv_write_pos' not in inspect.signature(
                    m_.forward).parameters:
                raise NotImplementedError(
                    f'{type(m_).__name__} does not support batched '
                    f'speculative decoding (cached forward lacks '
                    f'kv_write_pos); loop prompts individually')
    # same eval-mode rule as generate(): dropout would break the
    # losslessness contract (and differ between draft and verify)
    restore = []
    for m_ in (target, draft):
        if bool(getattr(m_, 'training', False)):
            m_.eval()
            restore.append(m_)
    try:
        if B == 1:
            return _speculative_loop(target, draft, input_ids,
                                     max_new_tokens, num_draft_tokens,
                                     eos_token_id, kv_cache_int8)
        return _speculative_loop_batched(target, draft, input_ids,
                                         max_new_tokens, num_draft_tokens,
                                         eos_token_id, kv_cache_int8)
    finally:
        for m_ in restore:
            m_.train()


def _commit_window(c, d_row, t_row, k):
    """The greedy speculative commit rule as a host-side REFERENCE:
    accept the longest draft prefix the target agrees with, commit [c]
    + that prefix, and pick the next committed token from the target's
    own choices. Returns (committed_tokens, next_c).

    The production loops now run this rule ON DEVICE inside the fused
    window step (inference.engine._spec_window_*: m = sum(cumprod(d ==
    t[:k])), next = t[m]); this function stays as the executable spec
    the engine's commit is tested against
    (tests/test_decode_engine.py)."""
    # one host transfer per ROW, not one per token: the old while loop
    # did int(d_row[i]) == int(t_row[i]) per position — two device
    # round-trips per draft token (tracelint TL002). Pull both rows
    # across once, then the commit rule is pure host arithmetic (and
    # the cumprod mirrors the engine's on-device form exactly).
    d = np.asarray(d_row)
    t = np.asarray(t_row)
    agree = (d[:k] == t[:k]).astype(np.int64)
    m_acc = int(agree.cumprod().sum())
    committed = [int(c)] + [int(x) for x in d[:m_acc]]
    next_c = int(t[m_acc]) if m_acc < k else int(t[k])
    return committed, next_c


def _speculative_loop(target, draft, input_ids, max_new_tokens,
                      num_draft_tokens, eos_token_id,
                      kv_cache_int8=False):
    """Batch-1 greedy speculative decoding through the COMPILED whole
    loop (inference.engine._spec_decode_b1): propose + verify + commit
    for EVERY window run inside one module-level-jitted lax.while_loop
    (steady state: zero retraces across calls — the jit closures used
    to live inside this function, guaranteeing a fresh trace every
    invocation), KV caches are donated (updated in place), and the
    host syncs once per generate call."""
    from ..inference.engine import _spec_loop_host_b1

    B, S = input_ids.shape
    k = int(num_draft_tokens)
    if k < 1:
        raise ValueError('num_draft_tokens must be >= 1')
    max_len = S + max_new_tokens + k + 1      # room for the last window
    tcaches = target.init_cache(B, max_len, quantized=kv_cache_int8)
    dcaches = draft.init_cache(B, max_len, quantized=kv_cache_int8)
    gen = _spec_loop_host_b1(target, draft, tcaches, dcaches, input_ids,
                             max_new_tokens, k, eos_token_id)
    return jnp.concatenate(
        [input_ids, jnp.asarray(gen, input_ids.dtype)], axis=1)


def _speculative_loop_batched(target, draft, input_ids, max_new_tokens,
                              num_draft_tokens, eos_token_id,
                              kv_cache_int8=False):
    """B > 1 speculative decoding: rows accept different draft prefixes,
    so each row carries its OWN committed length — cache writes go to
    per-row offsets (kv_write_pos) and attention masks by per-row
    position. The per-row commit rule is byte-identical to the batch-1
    loop, so losslessness holds row-wise. Runs through the compiled
    fused window (inference.engine._spec_window_batched) with donated
    caches — one dispatch and one host sync per window."""
    from ..inference.engine import _spec_loop_host_batched

    B, S = input_ids.shape
    k = int(num_draft_tokens)
    if k < 1:
        raise ValueError('num_draft_tokens must be >= 1')
    max_len = S + max_new_tokens + k + 1
    tcaches = target.init_cache(B, max_len, quantized=kv_cache_int8)
    dcaches = draft.init_cache(B, max_len, quantized=kv_cache_int8)
    gen = _spec_loop_host_batched(target, draft, tcaches, dcaches,
                                  input_ids, max_new_tokens, k,
                                  eos_token_id)
    return jnp.concatenate(
        [input_ids, jnp.asarray(gen, input_ids.dtype)], axis=1)


def _speculative_accept_dists(pt, pd):
    """The rejection-sampling identity, exposed for testing: given the
    target and draft distributions at one position, the procedure
    'sample x~pd; accept w.p. min(1, pt(x)/pd(x)); else resample from
    norm((pt-pd)+)' outputs exactly pt. Returns (accept_prob_per_token,
    residual_dist)."""
    accept = jnp.minimum(1.0, pt / jnp.maximum(pd, 1e-30))
    residual = jnp.maximum(pt - pd, 0.0)
    residual = residual / jnp.maximum(residual.sum(-1, keepdims=True),
                                      1e-30)
    return accept, residual


def generate_speculative_sampled(target, draft, input_ids,
                                 max_new_tokens=32, num_draft_tokens=4,
                                 temperature=1.0, top_k=0, top_p=1.0,
                                 rng_key=None, eos_token_id=None):
    """SAMPLED speculative decoding (ref capability: the speculative
    sampling loops of the reference serving ecosystem — Leviathan/Chen
    rejection sampling): the draft proposes tokens sampled at
    `temperature`; each is accepted with probability
    min(1, p_target/p_draft), and a rejection resamples from the
    normalised residual (p_target - p_draft)+. The OUTPUT DISTRIBUTION
    equals sampling from the target directly (with temperature/top_k/
    top_p applied to BOTH models, the law is the filtered target's) —
    speculative execution changes the cost, not the law (see
    tests/test_decode.py::TestSampledSpeculative for the identity
    check). temperature=0 delegates to the lossless greedy loop.

    Batch 1 (rows would commit at different lengths); host-driven like
    the greedy loop — one sync per window.
    """
    if temperature == 0.0:
        return generate_speculative(target, draft, input_ids,
                                    max_new_tokens, num_draft_tokens,
                                    eos_token_id)
    B, S = input_ids.shape
    if B != 1:
        raise NotImplementedError(
            'sampled speculative decoding is batch-1; loop prompts '
            'individually (greedy supports batches)')
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    restore = []
    for m_ in (target, draft):
        if bool(getattr(m_, 'training', False)):
            m_.eval()
            restore.append(m_)
    try:
        return _speculative_sampled_loop(target, draft, input_ids,
                                         max_new_tokens, num_draft_tokens,
                                         temperature, top_k, top_p,
                                         rng_key, eos_token_id)
    finally:
        for m_ in restore:
            m_.train()


def _sampled_dist(logits, temperature, top_k, top_p):
    """temperature + top-k/top-p filtering applied to BOTH models'
    dists; -inf entries softmax to exact 0, so filtered-out tokens can
    neither be proposed nor resampled."""
    return jax.nn.softmax(
        filter_logits(logits.astype(jnp.float32) / temperature, top_k,
                      top_p), -1)


# Module-level jits (the same persistent-cache discipline as
# inference.engine): sampling config rides as static args, caches are
# donated — repeated calls with one (model, shapes, config) never
# retrace and never copy the KV cache.

@functools.partial(jax.jit, donate_argnames=('caches',),
                   static_argnames=('temperature', 'top_k', 'top_p'))
def _sampled_prefill(m, caches, ids, *, temperature, top_k, top_p):
    logits, caches = m(ids, caches=caches, cache_index=0)
    return _sampled_dist(logits[:, -1, :], temperature, top_k,
                         top_p), caches


@functools.partial(jax.jit, donate_argnames=('caches',),
                   static_argnames=('k', 'temperature', 'top_k', 'top_p'))
def _sampled_propose(m, caches, c, idx, key, *, k, temperature, top_k,
                     top_p):
    """Draft samples k tokens; returns them WITH the draft's full
    distribution at every position (the acceptance rule needs p_draft
    of the chosen token and the residual needs the target dist,
    gathered on the host per window)."""
    def body(carry, i):
        tok, caches, key = carry
        logits, caches = m(tok, caches=caches, cache_index=idx + i)
        p = _sampled_dist(logits[:, -1], temperature, top_k, top_p)
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(
            sub, jnp.log(jnp.maximum(p, 1e-30))).astype(jnp.int32)
        return (nxt[:, None], caches, key), (nxt, p)
    (_, caches, key), (toks, ps) = jax.lax.scan(
        body, (c, caches, key), jnp.arange(k + 1))
    return toks[:k, 0], ps[:k, 0], caches, key   # (k,), (k, V)


@functools.partial(jax.jit, donate_argnames=('caches',),
                   static_argnames=('temperature', 'top_k', 'top_p'))
def _sampled_verify(m, caches, window, idx, *, temperature, top_k, top_p):
    logits, caches = m(window, caches=caches, cache_index=idx)
    return _sampled_dist(logits[0], temperature, top_k, top_p), caches


def _speculative_sampled_loop(target, draft, input_ids, max_new_tokens,
                              num_draft_tokens, temperature, top_k, top_p,
                              rng_key, eos_token_id):
    B, S = input_ids.shape
    k = int(num_draft_tokens)
    if k < 1:
        raise ValueError('num_draft_tokens must be >= 1')
    max_len = S + max_new_tokens + k + 1
    tcaches = target.init_cache(B, max_len)
    dcaches = draft.init_cache(B, max_len)
    cfg = dict(temperature=float(temperature), top_k=int(top_k),
               top_p=float(top_p))

    def propose(m, caches, c, idx, key):
        return _sampled_propose(m, caches, c, idx, key, k=k, **cfg)

    def verify(m, caches, window, idx):
        return _sampled_verify(m, caches, window, idx, **cfg)

    p_last, tcaches = _sampled_prefill(target, tcaches, input_ids, **cfg)
    _, dcaches = _sampled_prefill(draft, dcaches, input_ids, **cfg)
    rng_key, sub = jax.random.split(rng_key)
    c_host = int(jax.random.categorical(
        sub, jnp.log(jnp.maximum(p_last[0], 1e-30))))

    out = []
    L = S
    # independent streams: the accept/resample coins must not correlate
    # with the proposal keys (the exactness proof assumes independence)
    rng_key, seed_key = jax.random.split(rng_key)
    rng = np.random.default_rng(int(jax.random.randint(
        seed_key, (), 0, 2 ** 31 - 1)))
    while len(out) < max_new_tokens:
        c = jnp.asarray([[c_host]], jnp.int32)
        rng_key, pkey = jax.random.split(rng_key)
        drafts, pd, dcaches, _ = propose(draft, dcaches, c,
                                         jnp.asarray(L, jnp.int32), pkey)
        window = jnp.concatenate([c, drafts[None, :]], axis=1)
        pt, tcaches = verify(target, tcaches, window,
                             jnp.asarray(L, jnp.int32))
        # ONE batched host read per window (the speculative serving
        # contract): the drafts and both models' distributions cross
        # the fence together — was three separate np.asarray syncs per
        # window before tracelint.
        # tracelint: disable=TL002 - one sync per window by design
        d, pt_h, pd_h = jax.device_get((drafts, pt, pd))  # (k,),(k+1,V),(k,V)
        def draw(p):
            # float64 renormalize: f32 quotients can miss Generator.
            # choice's sum-to-1 tolerance at large vocabs
            p = np.asarray(p, np.float64)
            return int(rng.choice(len(p), p=p / p.sum()))

        committed = [c_host]
        nxt = None
        for i in range(k):
            x = int(d[i])
            # ONE source of the acceptance math (the identity-tested
            # helper) for both the test and the production loop
            accept, residual = _speculative_accept_dists(
                jnp.asarray(pt_h[i]), jnp.asarray(pd_h[i]))
            if rng.random() < float(accept[x]):
                committed.append(x)
                continue
            residual = np.asarray(residual, np.float64)
            if residual.sum() <= 0:                   # degenerate: pt<=pd
                residual = pt_h[i]
            nxt = draw(residual)
            break
        if nxt is None:                               # full window accepted
            nxt = draw(pt_h[k])
        out.extend(committed)
        if eos_token_id is not None and eos_token_id in committed:
            out = out[:out.index(eos_token_id) + 1]
            break
        c_host = nxt
        L += len(committed)
    if eos_token_id is not None and len(out) < max_new_tokens:
        out += [eos_token_id] * (max_new_tokens - len(out))
    gen = jnp.asarray([out[:max_new_tokens]], input_ids.dtype)
    return jnp.concatenate([input_ids, gen], axis=1)
