"""Paged (block-table) and head-major decode attention — pallas, TPU.

ref (capability): the reference serving stack's
`block_multihead_attention` paged-KV decode
(python/paddle/incubate/nn/functional/block_multihead_attention.py:30 —
CUDA kernels over [max_block_num, num_head, block_size, head_size]
pages) and `masked_multihead_attention` (contiguous
[2, B, num_head, max_seq, head_size] caches). TPU-native design: for
pages, the block table itself is SCALAR-PREFETCHED and drives the
BlockSpec index map, so each grid step DMAs exactly the page the
sequence occupies — no gather materialisation. The contiguous head-major
cache is the degenerate case of the same kernel (page j = S-slice j), so
both share ONE online-softmax body. Optional per-(head, dim) int8 scales
dequantize in VMEM. Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret():
    from . import interpret_mode

    return interpret_mode()


def _body(cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc, m_scr,
          l_scr, *, scale, nb, bs, hkv, group, rowscale=False, st_ref=None):
    """Shared head-major online-softmax pass. Column order: the (hkv, bs,
    D) block flattens to c = h*bs + s, so head(c) = c // bs and
    position(c) = j*bs + c % bs. With `st_ref` (a sliding window) row b
    attends positions [st_ref[b], count) only, and a page that lies
    wholly before the window's start is skipped: no compute here, and no
    copy either (its index map names the first page inside the window)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def page():
        hq = group * hkv
        cols = hkv * bs
        D = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32)                 # (Hq, D)
        k = k_ref[0].astype(jnp.float32)                    # (hkv, bs, D)
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # int8 dequant rides the (hkv, bs, D) layout BEFORE the
            # major-dim collapse (the Mosaic-proven pattern). Two scale
            # layouts: (Hkv, D) global per-(head, dim) calibration
            # (QuantKVCache), or (1, Hkv, BS) PER-ROW scales riding the
            # page itself (QuantPagedKVCache — each token row carries its
            # own amax, so quantization is write-order independent)
            if rowscale:
                k = k * ks_ref[0][:, :, None]
                v = v * vs_ref[0][:, :, None]
            else:
                k = k * ks_ref[...][:, None, :]
                v = v * vs_ref[...][:, None, :]
        k = k.reshape(cols, D)
        v = v.reshape(cols, D)

        count = cl_ref[b]
        vpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (cols, D), 0) % bs
        rowh = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0) // group
        colh = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1) // bs
        colp = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (hq, cols), 1) % bs
        if st_ref is None:
            v = jnp.where(vpos < count, v, 0.0)
            keep = (rowh == colh) & (colp < count)
        else:
            start = st_ref[b]
            v = jnp.where((vpos < count) & (vpos >= start), v, 0.0)
            keep = (rowh == colh) & (colp < count) & (colp >= start)

        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(keep, s, NEG_INF)                     # (Hq, cols)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(keep, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc[:] = acc[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if st_ref is None:
        page()
    else:
        pl.when((j + 1) * bs > st_ref[b])(page)

    @pl.when(j == nb - 1)
    def _():
        safe = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc[:] / safe[:, None]).astype(o_ref.dtype)


def _kernel(cl_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr,
            **kw):
    _body(cl_ref, q_ref, k_ref, v_ref, None, None, o_ref, acc, m_scr,
          l_scr, **kw)


def _kernel_q8(cl_ref, tbl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
               acc, m_scr, l_scr, **kw):
    _body(cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc, m_scr,
          l_scr, **kw)


def _kernel_win(cl_ref, tbl_ref, st_ref, q_ref, k_ref, v_ref, o_ref, acc,
                m_scr, l_scr, **kw):
    _body(cl_ref, q_ref, k_ref, v_ref, None, None, o_ref, acc, m_scr,
          l_scr, st_ref=st_ref, **kw)


def _kernel_win_q8(cl_ref, tbl_ref, st_ref, q_ref, k_ref, v_ref, ks_ref,
                   vs_ref, o_ref, acc, m_scr, l_scr, **kw):
    _body(cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc, m_scr,
          l_scr, st_ref=st_ref, **kw)


def _kernel_hm(cl_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, **kw):
    _body(cl_ref, q_ref, k_ref, v_ref, None, None, o_ref, acc, m_scr,
          l_scr, **kw)


def _kernel_hm_q8(cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc,
                  m_scr, l_scr, **kw):
    _body(cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc, m_scr,
          l_scr, **kw)


def _run(kernel, grid, in_specs, out_spec, args, out_sd, interp):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=grid[0],
            grid=grid[1],
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM(out_sd.shape[-2:], jnp.float32),
                pltpu.VMEM((out_sd.shape[-2], 128), jnp.float32),
                pltpu.VMEM((out_sd.shape[-2], 128), jnp.float32),
            ],
        ),
        out_shape=out_sd,
        interpret=interp, name='paged_attention',
    )(*args)


def paged_decode_attention(q, key_cache, value_cache, block_tables,
                           context_lens, scale=None, k_scale=None,
                           v_scale=None, window=None):
    """One fused paged decode step.

    q: (B, 1, Hq, D); key_cache/value_cache: (NB, Hkv, BS, D) pages;
    block_tables: (B, MAXB) int32 page ids (entries past the sequence's
    pages may be any value — they are clamped and masked); context_lens:
    (B,) valid positions per row. Optional k_scale/v_scale dequantize
    int8 pages in VMEM, in either of two layouts: (Hkv, D) f32 global
    per-(head, dim) calibration (QuantKVCache), or (NB, Hkv, BS) f32
    PER-ROW scales riding page-shaped pools (QuantPagedKVCache — the
    scale block is prefetched by the same block-table index map as its
    page). `window` (static int): the query, at position
    context_lens - 1, attends the last `window` positions only; pages
    wholly behind them are neither copied nor computed (they stay
    allocated: the table is the caller's). Returns (B, 1, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    if Sq != 1:
        raise ValueError(f'paged decode is single-token (Sq=1), got {Sq}')
    NB, Hkv, BS, _ = key_cache.shape
    if Hq % Hkv:
        raise ValueError(
            f'query heads ({Hq}) must be a multiple of kv heads ({Hkv})')
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    nb = block_tables.shape[1]
    # out-of-range / sentinel (-1) page ids must not index OOB: clamp —
    # the count mask already zeroes their contribution
    tbl = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, NB - 1)
    cl = jnp.minimum(jnp.broadcast_to(
        jnp.reshape(jnp.asarray(context_lens, jnp.int32), (-1,)), (B,)),
        nb * BS)

    # scalar-prefetched: lengths, the table and, under a window, each
    # row's first attended position. The prefetched block table IS the
    # page index: grid step (b, j) DMAs page block_tables[b, j], or the
    # window's first page while j is still behind it (the same block
    # again, so nothing is copied)
    prefetch = [cl, tbl]
    if window is not None:
        prefetch.append(jnp.maximum(cl - int(window), 0))

    def page(b, j, cl, tbl, *st):
        return tbl[b, jnp.maximum(j, st[0][b] // BS) if st else j]

    def whole(b, j, *_):
        return (b, 0, 0, 0)

    quant = k_scale is not None
    rowscale = quant and k_scale.ndim == 3
    in_specs = [
        pl.BlockSpec((1, 1, Hq, D), whole),
        pl.BlockSpec((1, Hkv, BS, D), lambda *a: (page(*a), 0, 0, 0)),
        pl.BlockSpec((1, Hkv, BS, D), lambda *a: (page(*a), 0, 0, 0)),
    ]
    args = prefetch + [q, key_cache, value_cache]
    kw = dict(scale=scale, nb=nb, bs=BS, hkv=Hkv, group=group,
              rowscale=rowscale)
    if quant:
        kernel = _kernel_q8 if window is None else _kernel_win_q8
        if rowscale:
            # per-row scales live in page-shaped (NB, Hkv, BS) pools:
            # the scale block for grid step (b, j) is the same
            # prefetched page the K/V blocks DMA
            in_specs += [pl.BlockSpec(
                (1, Hkv, BS), lambda *a: (page(*a), 0, 0))] * 2
        else:
            in_specs += [pl.BlockSpec((Hkv, D), lambda *a: (0, 0))] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    else:
        kernel = _kernel if window is None else _kernel_win
    return _run(
        functools.partial(kernel, **kw), (len(prefetch), (B, nb)), in_specs,
        pl.BlockSpec((1, 1, Hq, D), whole),
        args, jax.ShapeDtypeStruct((B, 1, Hq, D), q.dtype), _interpret())


def decode_attention_headmajor(q, k_cache, v_cache, context_lens,
                               scale=None, k_scale=None, v_scale=None,
                               block_s=1024):
    """Fused decode over a CONTIGUOUS head-major cache (B, Hkv, S, D) —
    the masked_multihead_attention layout. Same body as the paged
    kernel: page j is simply S-slice j, blocked to a VMEM budget, so any
    cache length streams once with no transpose."""
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
    nb = pl.cdiv(S, bs)
    cl = jnp.minimum(jnp.broadcast_to(
        jnp.reshape(jnp.asarray(context_lens, jnp.int32), (-1,)), (B,)), S)

    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, 1, Hq, D), lambda b, j, cl: (b, 0, 0, 0)),
        pl.BlockSpec((1, Hkv, bs, D), lambda b, j, cl: (b, 0, j, 0)),
        pl.BlockSpec((1, Hkv, bs, D), lambda b, j, cl: (b, 0, j, 0)),
    ]
    args = [cl, q, k_cache, v_cache]
    kw = dict(scale=scale, nb=nb, bs=bs, hkv=Hkv, group=group)
    if quant:
        kernel = functools.partial(_kernel_hm_q8, **kw)
        in_specs += [pl.BlockSpec((Hkv, D), lambda b, j, cl: (0, 0))] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    else:
        kernel = functools.partial(_kernel_hm, **kw)
    return _run(
        kernel, (1, (B, nb)), in_specs,
        pl.BlockSpec((1, 1, Hq, D), lambda b, j, cl: (b, 0, 0, 0)),
        args, jax.ShapeDtypeStruct((B, 1, Hq, D), q.dtype), interp)
