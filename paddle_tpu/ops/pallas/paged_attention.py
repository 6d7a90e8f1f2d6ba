"""Paged (block-table) and head-major decode attention — pallas, TPU.

ref (capability): the reference serving stack's
`block_multihead_attention` paged-KV decode
(python/paddle/incubate/nn/functional/block_multihead_attention.py:30 —
CUDA kernels over [max_block_num, num_head, block_size, head_size]
pages) and `masked_multihead_attention` (contiguous
[2, B, num_head, max_seq, head_size] caches). TPU-native design: the
page pools stay in HBM and the kernel fetches a row's pages itself. The
grid is one step a row; inside it a loop walks the row's OWN pages, from
the first page its window reaches to the page its last token is on, in
chunks of `P` pages: `P` async copies of one contiguous (Hkv, BS, D) page
each, named by the scalar-prefetched block table, into a double-buffered
VMEM scratch, the next chunk (or the next row's first) in flight while
this one is computed. Table entries outside that range are never read,
so a wide table or an idle slot costs nothing. `P` follows the shapes
(`_pick_pages`). Each kv head's `group` queries meet that head's keys
only; positions are masked in a row's first and last chunk alone. The
contiguous head-major cache keeps a BlockSpec grid over S-slices and
shares the ONE online-softmax update. Optional int8 scales dequantize in
VMEM. A K row and a V row may differ in width (the pools' own shapes say),
and an optional per-query-head `sink` logit starts the running max and sum:
it takes its share of the softmax's mass and adds no value. Inference-only
(no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# keys a chunk should hold, keys a piece of it (what is copied, waited for
# and, in a masked chunk, computed at a time), and the VMEM the four chunk
# buffers (K and V, two slots each) may take: see _pick_pages. From a sweep
# on a v5e at the benchmark's shapes (PERF.md, PR 29): 256/128 and 1024/128
# cost 20-30 % more where rows are long, pieces of 64 30-40 %
CHUNK_KEYS = 512
EDGE_KEYS = 128
CHUNK_VMEM_BUDGET = 8 * 1024 * 1024


def _interpret():
    from . import interpret_mode

    return interpret_mode()


def _attend(q_ref, kv, acc, m_scr, l_scr, *, scale, keys, span=None):
    """The one online-softmax update: every kv head's `group` queries
    (q_ref[0, h]: (group, D)) against the `keys` keys and values `kv(h)`
    returns as float32 (keys, D). `span` = (first key's position, start,
    count) masks positions outside [start, count) out of the scores and
    zeroes their V rows, so stale or unspecified memory (inf/nan bit
    patterns) cannot reach the products; without it every key counts."""
    _, hkv, group, _ = q_ref.shape
    keep = vkeep = None
    if span is not None:
        base, start, count = span
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (group, keys), 1)
        keep = (pos < count) & (pos >= start)
        vpos = base + jax.lax.broadcasted_iota(
            jnp.int32, (keys, acc.shape[-1]), 0)
        vkeep = (vpos < count) & (vpos >= start)
    for h in range(hkv):
        k, v = kv(h)
        q = q_ref[0, h].astype(jnp.float32) * scale         # (group, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)                 # (group, T)
            v = jnp.where(vkeep, v, 0.0)
        m_prev = m_scr[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[h][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[h] = acc[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])


def _reset(acc, m_scr, l_scr, sink_ref=None):
    """Nothing attended yet; with a sink, one logit a query head that is
    already in the running max and sum (exp(sink - sink) = 1) and brings
    no value."""
    acc[...] = jnp.zeros_like(acc)
    if sink_ref is None:
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
    else:
        m_scr[...] = sink_ref[...]
        l_scr[...] = jnp.ones_like(l_scr)


def _finish(o_ref, acc, l_scr):
    safe = jnp.maximum(l_scr[...][:, :, :1], 1e-30)
    o_ref[0] = (acc[...] / safe).astype(o_ref.dtype)


def _paged_kernel(cl_ref, st_ref, tbl_ref, q_ref, k_hbm, v_hbm, *rest, scale,
                  bs, pages, edge_pages, maxb, quant, rowscale, sink):
    """Grid step b = row b. Its pages [lo, hi) are walked in chunks of
    `pages`; chunk c lives in buffer slot (first + c) % 2, where `first`
    is the slot the previous row left this row's chunk 0 in (it starts
    that copy during its own last chunk)."""
    sink_ref = None
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    if rowscale:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf = rest[:7]
    elif quant:
        ks_ref, vs_ref, o_ref, kbuf, vbuf = rest[:5]
    else:
        o_ref, kbuf, vbuf = rest[:3]
    sems, carry, acc, m_scr, l_scr = rest[-5:]
    b = pl.program_id(0)
    rows = pl.num_programs(0)

    def extent(row):
        """Pages [lo, hi) row `row` attends, and its number of chunks."""
        lo = st_ref[row] // bs
        hi = pl.cdiv(cl_ref[row], bs)
        return lo, hi, pl.cdiv(hi - lo, pages)

    streams = [(k_hbm, kbuf, 0), (v_hbm, vbuf, 1)]
    if rowscale:
        streams += [(ks_hbm, ksbuf, 2), (vs_hbm, vsbuf, 3)]

    def pieces(first, hi, each):
        """each(i) for the pieces of the chunk that starts at page `first`
        which hold a page of the row."""
        for i in range(pages // edge_pages):
            pl.when(first + i * edge_pages < hi)(functools.partial(each, i))

    def fetch(row, lo, hi, c, slot):
        """Start the copies of row `row`'s chunk c into `slot`. A piece is
        copied whole, one contiguous page a copy; its places past the
        row's last page take that page again (under edge_pages pages a
        row, masked like the page's own tail), so neither the copies nor
        their waits branch a page."""
        first = lo + c * pages

        def start(i):
            for p in range(i * edge_pages, (i + 1) * edge_pages):
                page = tbl_ref[row * maxb + jnp.minimum(first + p, hi - 1)]
                for src, dst, s in streams:
                    pltpu.make_async_copy(src.at[page], dst.at[slot, p],
                                          sems.at[slot, s]).start()

        pieces(first, hi, start)

    def wait(first, hi, slot):
        """One wait a piece and stream: a wait counts bytes, so a
        descriptor of the piece's size stands for its pages' copies."""
        def done(i):
            for _, dst, s in streams:
                piece = dst.at[slot, pl.ds(i * edge_pages, edge_pages)]
                pltpu.make_async_copy(piece, piece, sems.at[slot, s]).wait()

        pieces(first, hi, done)

    lo, hi, n = extent(b)
    start, count = st_ref[b], cl_ref[b]

    @pl.when(b == 0)
    def _():
        carry[0] = 0
        carry[1] = 0

    first_slot = carry[0]

    @pl.when((n > 0) & (carry[1] == 0))
    def _():
        fetch(b, lo, hi, 0, first_slot)

    carry[1] = 0
    _reset(acc, m_scr, l_scr, sink_ref)

    def chunk(c, _):
        slot = (first_slot + c) % 2

        # what follows this chunk is in flight while it is computed: the
        # row's next chunk, or after its last the next row's first
        last = c + 1 == n
        nrow = jnp.where(last, jnp.minimum(b + 1, rows - 1), b)
        nlo, nhi, nn = extent(nrow)

        @pl.when(jnp.where(last, (b + 1 < rows) & (nn > 0), True))
        def _():
            fetch(nrow, nlo, nhi, jnp.where(last, 0, c + 1), 1 - slot)

            @pl.when(last)
            def _():
                carry[0] = 1 - slot
                carry[1] = 1

        first = lo + c * pages
        wait(first, hi, slot)

        def part(at, npages):
            """attend() over pages [at, at + npages) of the chunk."""
            def kv(h):
                span = pl.ds(at, npages)
                k = kbuf[slot, span, h].astype(jnp.float32)  # (n, BS, D)
                v = vbuf[slot, span, h].astype(jnp.float32)
                # int8 dequant rides the (n, BS, D) layout BEFORE the
                # major-dim collapse (the Mosaic-proven pattern). Two
                # scale layouts: (Hkv, D) global per-(head, dim)
                # calibration (QuantKVCache), or PER-ROW scales in
                # page-shaped pools, fetched with their page
                # (QuantPagedKVCache — each token row carries its own
                # amax, so quantization is write-order independent)
                if rowscale:
                    k = k * ksbuf[slot, span, h][:, :bs][:, :, None]
                    v = v * vsbuf[slot, span, h][:, :bs][:, :, None]
                elif quant:
                    k = k * ks_ref[h][None]
                    v = v * vs_ref[h][None]
                return (k.reshape(npages * bs, k.shape[-1]),
                        v.reshape(npages * bs, v.shape[-1]))

            return functools.partial(_attend, q_ref, kv, acc, m_scr, l_scr,
                                     scale=scale, keys=npages * bs)

        # only a row's last chunk (its end, the pages not fetched) and,
        # behind a window, its first hold positions to mask. Such a chunk
        # goes `edge_pages` at a time, as far as it has pages: a short
        # row or an idle slot pays for the keys it has, not for a chunk
        edge = (c + 1 == n) | ((c == 0) & (start > 0))

        @pl.when(edge)
        def _():
            def piece(i, _):
                at = i * edge_pages
                part(at, edge_pages)(
                    span=((first + at) * bs, start, count))

            jax.lax.fori_loop(
                0, pl.cdiv(jnp.minimum(hi - first, pages), edge_pages),
                piece, None)

        pl.when(jnp.logical_not(edge))(part(0, pages))

    jax.lax.fori_loop(0, n, chunk, None)
    _finish(o_ref, acc, l_scr)


def _pick_pages(bs, hkv, D, itemsize, maxb, Dv=None):
    """(pages a chunk, pages a piece of a masked chunk): a piece holds
    EDGE_KEYS keys, a chunk as many whole pieces as CHUNK_KEYS keys take,
    as far as two slots of K and V pages fit CHUNK_VMEM_BUDGET (an int8
    page budgets as 2-byte: it is dequantized to f32 a head at a time, so
    the working set follows the chunk's LENGTH) and the table is wide."""
    pair = hkv * bs * (D + (D if Dv is None else Dv)) * max(itemsize, 2)
    fit = max(1, min(CHUNK_VMEM_BUDGET // (2 * pair), maxb))
    piece = min(pl.cdiv(EDGE_KEYS, bs), fit)
    return max(1, min(CHUNK_KEYS // (piece * bs), fit // piece)) * piece, piece


def _head_scales(hkv, D):
    """Global (Hkv, D) int8 scales, whole in VMEM as (Hkv, 1, D): a head's
    row is a tile of its own."""
    return pl.BlockSpec((hkv, 1, D), lambda *_: (0, 0, 0))


def _state(hkv, group, D):
    """The accumulator (as wide as a V row), the running max and sum."""
    return [pltpu.VMEM((hkv, group, D), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32)]


def paged_decode_attention(q, key_cache, value_cache, block_tables,
                           context_lens, scale=None, k_scale=None,
                           v_scale=None, window=None, sink=None):
    """One fused paged decode step.

    q: (B, 1, Hq, D); key_cache: (NB, Hkv, BS, D) pages and value_cache:
    (NB, Hkv, BS, Dv) pages, Dv = D unless the model's V rows are of
    another width;
    block_tables: (B, MAXB) int32 page ids (entries past the sequence's
    pages may be any value — they are never read); context_lens: (B,)
    valid positions per row. Optional k_scale/v_scale dequantize int8
    pages in VMEM, in either of two layouts: (Hkv, D) f32 global
    per-(head, dim) calibration (QuantKVCache), or (NB, Hkv, BS) f32
    PER-ROW scales riding page-shaped pools (QuantPagedKVCache — a
    page's scales are fetched with it). `window` (static int): the query,
    at position context_lens - 1, attends the last `window` positions
    only; pages wholly behind them are neither copied nor computed (they
    stay allocated or are the caller's to recycle: the table is the
    caller's, and entries behind the window are never read). `sink`:
    (Hq,) float32, one logit a query head that joins the softmax's
    denominator and adds no value. A row of length 0 reads nothing and
    returns zeros. Returns (B, 1, Hq, Dv).
    """
    B, Sq, Hq, D = q.shape
    if Sq != 1:
        raise ValueError(f'paged decode is single-token (Sq=1), got {Sq}')
    _, Hkv, BS, _ = key_cache.shape
    if Hq % Hkv:
        raise ValueError(
            f'query heads ({Hq}) must be a multiple of kv heads ({Hkv})')
    pages, edge_pages = _pick_pages(BS, Hkv, D, key_cache.dtype.itemsize,
                                    block_tables.shape[1],
                                    value_cache.shape[-1])
    return _paged_call(
        q, key_cache, value_cache, block_tables, context_lens, k_scale,
        v_scale, sink,
        scale=scale if scale is not None else 1.0 / (D ** 0.5),
        window=None if window is None else int(window), pages=pages,
        edge_pages=edge_pages, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=(
    'scale', 'window', 'pages', 'edge_pages', 'interpret'))
def _paged_call(q, key_cache, value_cache, block_tables, context_lens,
                k_scale, v_scale, sink=None, *, scale, window, pages,
                edge_pages, interpret):
    """The kernel's call, jitted by itself: a model calls it once a layer
    with the same shapes, and tracing and lowering the kernel's body (a
    chunk's copies and eight heads' products, unrolled) once a layer was
    ~0.8 s each, in every process, compile cache or not."""
    B, _, Hq, D = q.shape
    NB, Hkv, BS, _ = key_cache.shape
    Dv = value_cache.shape[-1]
    group = Hq // Hkv
    maxb = block_tables.shape[1]
    # out-of-range / sentinel (-1) page ids must not index OOB: clamp
    # (inside a row's pages the table is the caller's word)
    tbl = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, NB - 1)
    cl = jnp.clip(jnp.broadcast_to(
        jnp.reshape(jnp.asarray(context_lens, jnp.int32), (-1,)), (B,)),
        0, maxb * BS)
    # scalar-prefetched: lengths, each row's first attended position (0
    # without a window) and the table, flat (a 2-D SMEM array pads its
    # rows to 128 words)
    st = jnp.maximum(cl - window, 0) if window is not None \
        else jnp.zeros_like(cl)
    prefetch = [cl, st, tbl.reshape(-1)]

    quant = k_scale is not None
    rowscale = quant and k_scale.ndim == 3
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # heads split as (kv head, group): a free reshape in HBM, and each
    # head's queries start a VMEM tile
    heads = pl.BlockSpec((1, Hkv, group, D), lambda b, *_: (b, 0, 0, 0))
    in_specs = [heads, hbm, hbm]
    args = [q.reshape(B, Hkv, group, D), key_cache, value_cache]
    scratch = [pltpu.VMEM((2, pages, Hkv, BS, D), key_cache.dtype),
               pltpu.VMEM((2, pages, Hkv, BS, Dv), value_cache.dtype)]
    if sink is not None:
        # a head's logit across the lanes of the running max's tile
        in_specs.append(pl.BlockSpec((Hkv, group, 128),
                                     lambda *_: (0, 0, 0)))
        args.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(Hkv, group, 1),
            (Hkv, group, 128)))
    if quant:
        scales = [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
        if rowscale:
            # Mosaic cannot slice a page off a pool whose minor dim is not
            # whole lanes: the (small) scale pools are padded to them
            lanes = BS + -BS % 128
            scales = [jnp.pad(s, ((0, 0), (0, 0), (0, lanes - BS)))
                      for s in scales]
            in_specs += [hbm, hbm]
            scratch += [pltpu.VMEM((2, pages, Hkv, lanes), jnp.float32)] * 2
        else:
            in_specs += [_head_scales(Hkv, D)] * 2
            scales = [s.reshape(Hkv, 1, D) for s in scales]
        args += scales
    scratch += [pltpu.SemaphoreType.DMA((2, 4)), pltpu.SMEM((2,), jnp.int32)]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=BS, pages=pages,
                          edge_pages=edge_pages, maxb=maxb, quant=quant,
                          rowscale=rowscale, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv, group, Dv),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=scratch + _state(Hkv, group, Dv)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dv), q.dtype),
        # rows in order: a row starts the next row's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret, name='paged_attention',
    )(*prefetch, *args)
    return out.reshape(B, 1, Hq, Dv)


def _headmajor_kernel(cl_ref, q_ref, k_ref, v_ref, *rest, scale, bs, quant):
    """Grid step (b, j): S-slice j of row b's contiguous cache."""
    if quant:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    count = cl_ref[b]
    pl.when(j == 0)(lambda: _reset(acc, m_scr, l_scr))

    def kv(h):
        k = k_ref[0, h].astype(jnp.float32)                 # (bs, D)
        v = v_ref[0, h].astype(jnp.float32)
        if quant:
            k = k * ks_ref[h]
            v = v * vs_ref[h]
        return k, v

    attend = functools.partial(_attend, q_ref, kv, acc, m_scr, l_scr,
                               scale=scale, keys=bs)
    # a slice wholly inside the row needs no mask, one wholly past its
    # end no work
    inside = (j + 1) * bs <= count
    pl.when(inside)(attend)
    pl.when(jnp.logical_not(inside) & (j * bs < count))(
        lambda: attend(span=(j * bs, 0, count)))
    pl.when(j == pl.num_programs(1) - 1)(lambda: _finish(o_ref, acc, l_scr))


def decode_attention_headmajor(q, k_cache, v_cache, context_lens,
                               scale=None, k_scale=None, v_scale=None,
                               block_s=1024):
    """Fused decode over a CONTIGUOUS head-major cache (B, Hkv, S, D) —
    the masked_multihead_attention layout. Same online-softmax update as
    the paged kernel: chunk j is simply S-slice j, blocked to a VMEM
    budget, so any cache length streams once with no transpose."""
    B, Sq, Hq, D = q.shape
    if Sq != 1:
        raise ValueError(f'decode is single-token (Sq=1), got {Sq}')
    _, Hkv, S, _ = k_cache.shape
    if Hq % Hkv:
        raise ValueError(
            f'query heads ({Hq}) must be a multiple of kv heads ({Hkv})')
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    interp = _interpret()
    # VMEM-bounded block along S: the same policy as the contiguous
    # kernel, shared so tuning lands in both
    from .decode_attention import _pick_block

    bs = _pick_block(block_s, S, Hkv, D, k_cache.dtype.itemsize, interp)
    cl = jnp.minimum(jnp.broadcast_to(
        jnp.reshape(jnp.asarray(context_lens, jnp.int32), (-1,)), (B,)), S)

    quant = k_scale is not None
    heads = pl.BlockSpec((1, Hkv, group, D), lambda b, j, cl: (b, 0, 0, 0))
    in_specs = [
        heads,
        pl.BlockSpec((1, Hkv, bs, D), lambda b, j, cl: (b, 0, j, 0)),
        pl.BlockSpec((1, Hkv, bs, D), lambda b, j, cl: (b, 0, j, 0)),
    ]
    args = [cl, q.reshape(B, Hkv, group, D), k_cache, v_cache]
    if quant:
        in_specs += [_head_scales(Hkv, D)] * 2
        args += [s.astype(jnp.float32).reshape(Hkv, 1, D)
                 for s in (k_scale, v_scale)]
    out = pl.pallas_call(
        functools.partial(_headmajor_kernel, scale=scale, bs=bs, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, pl.cdiv(S, bs)),
            in_specs=in_specs, out_specs=heads,
            scratch_shapes=_state(Hkv, group, D)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interp, name='paged_attention',
    )(*args)
    return out.reshape(B, 1, Hq, D)
