"""Attention functionals.

`scaled_dot_product_attention` (ref: python/paddle/nn/functional/
flash_attention.py) dispatches to the pallas flash-attention TPU kernel
when available, else to a fused lax reference (same math, XLA-fused).
Layout: (batch, seq, num_heads, head_dim) — Paddle's flash-attn layout.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _sdpa_reference(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                    scale=None, rng_key=None, training=True,
                    return_probs=False):
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = scale or (1.0 / math.sqrt(D))
    # GQA: broadcast kv heads if fewer than q heads
    Hk = k.shape[2]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum('bqhd,bkhd->bhqk', qf, k.astype(jnp.float32))
    if is_causal:
        causal = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        logits = jnp.where(causal[None, None], logits, -1e30)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -1e30)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and training:
        from ...framework import random as random_mod

        key = rng_key if rng_key is not None else random_mod.split_key()
        keep = jax.random.bernoulli(key, 1 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1 - dropout_p), 0.0)
    out = jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))
    return (out.astype(q.dtype), p) if return_probs else out.astype(q.dtype)


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    scale=None,
    training=True,
    rng_key=None,
    segment_ids=None,
    kv_segment_ids=None,
    window_size=None,
):
    """Flash attention on TPU; lax reference elsewhere/with masks it can't take.

    segment_ids (+optional kv_segment_ids for Sq != Sk): (B, Sq)/(B, Sk)
    int32 packed-sequence ids — attention is block-diagonal within equal
    ids (flash kernel fast path on TPU).

    window_size: optional int — causal sliding-window attention (each
    query sees only its last `window_size` keys, self included). On TPU
    this takes the flash kernel's block-skipping fast path (ref:
    python/paddle/nn/functional/flash_attention.py:1106); elsewhere the
    band folds into the mask.
    """
    from ...ops import use_pallas

    if kv_segment_ids is not None and segment_ids is None:
        raise ValueError('kv_segment_ids requires segment_ids')
    if segment_ids is not None and kv_segment_ids is None:
        if query.shape[1] != key.shape[1]:
            raise ValueError(
                'segment_ids with Sq != Sk requires kv_segment_ids')
        kv_segment_ids = segment_ids
    if window_size is not None and not is_causal:
        raise ValueError('window_size requires is_causal=True')

    use_flash = (
        dropout_p == 0.0
        and attn_mask is None
        and query.shape[-1] % 8 == 0
        and query.shape[1] >= 128
        and use_pallas()
    )
    if use_flash:
        from jax.sharding import PartitionSpec as P

        from ...ops import DATA_AXES, head_axis, mesh_kernel
        from ...ops.pallas.flash_attention import flash_attention

        def kernel(q, k, v, *segs):
            return flash_attention(
                q, k, v, causal=is_causal, scale=scale,
                segment_ids=segs[0] if segs else None,
                kv_segment_ids=segs[1] if segs else None,
                window_size=window_size)

        segs = (() if segment_ids is None
                else (segment_ids, kv_segment_ids))
        qkv = P(DATA_AXES, None,
                head_axis(query.shape[2], key.shape[2]), None)
        return mesh_kernel(kernel, (query, key, value) + segs,
                           (qkv,) * 3 + (P(DATA_AXES, None),) * len(segs))
    if window_size is not None:
        # fold the band into the mask for the reference path
        Sq, Sk = query.shape[1], key.shape[1]
        qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
        kpos = jnp.arange(Sk)[None, :]
        band = (qpos - kpos < window_size)[None, None]    # causal half below
        if attn_mask is None:
            attn_mask = band
        elif attn_mask.dtype == jnp.bool_:
            attn_mask = attn_mask & band
        else:
            attn_mask = jnp.where(band, attn_mask.astype(jnp.float32), -1e30)
    if segment_ids is not None:
        qseg = jnp.asarray(segment_ids)
        kseg = jnp.asarray(kv_segment_ids)
        seg_mask = (qseg[:, :, None] == kseg[:, None, :])[:, None]
        if attn_mask is None:
            attn_mask = seg_mask
        elif attn_mask.dtype == jnp.bool_:
            attn_mask = attn_mask & seg_mask
        else:
            # additive float mask: masked-out pairs get -inf-like bias
            attn_mask = jnp.where(seg_mask, attn_mask, -1e30)
    out = _sdpa_reference(
        query, key, value, attn_mask, dropout_p, is_causal, scale, rng_key, training
    )
    if segment_ids is not None:
        # match the kernel's empty-segment convention: a query whose
        # segment has no kv tokens returns 0 (softmax of an all-masked
        # row would otherwise emit the uniform mean of v and leak grads)
        row_valid = jnp.any(seg_mask[:, 0], axis=-1)     # (B, Sq)
        out = jnp.where(row_valid[:, :, None, None], out, 0.0)
    return out


flash_attention = scaled_dot_product_attention


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, *, fixed_seed_offset=None,
                         rng_name='', training=True, name=None):
    """Packed-QKV flash attention (ref: nn/functional/flash_attention.py::
    flash_attn_qkvpacked). qkv: (B, S, 3, H, D). Returns (out, softmax) —
    softmax is None unless requested (and requesting it forces the
    non-flash path, as the reference's kernel does for its debug mode)."""
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if return_softmax:
        return _sdpa_reference(q, k, v, dropout_p=dropout, is_causal=causal,
                               training=training, return_probs=True)
    out = scaled_dot_product_attention(q, k, v, dropout_p=dropout,
                                       is_causal=causal, training=training)
    return out, None


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q, max_seqlen_k, scale,
                                dropout=0.0, causal=False,
                                return_softmax=False, fixed_seed_offset=None,
                                rng_name='', varlen_padded=True,
                                training=True, name=None):
    """Varlen packed flash attention (ref: flash_attention.py::
    flash_attn_varlen_qkvpacked). qkv: (total_tokens, 3, H, D) with
    cumulative sequence boundaries `cu_seqlens_*`.

    TPU-native mapping: the token stream is ONE long row and the varlen
    boundaries become segment ids — exactly the packed-sequence fast path
    the pallas flash kernel already supports (block-diagonal masking),
    so no unpadding/repadding round-trip is needed.
    """
    total, _, h, d = qkv.shape
    q = qkv[None, :, 0]
    k = qkv[None, :, 1]
    v = qkv[None, :, 2]
    positions = jnp.arange(total)
    seg_q = jnp.searchsorted(jnp.asarray(cu_seqlens_q)[1:], positions,
                             side='right').astype(jnp.int32)[None]
    if return_softmax:  # debug mode: dense block-diagonal probabilities
        seg_mask = (seg_q[:, :, None] == seg_q[:, None, :])[:, None]
        out, p = _sdpa_reference(q, k, v, attn_mask=seg_mask,
                                 dropout_p=dropout, is_causal=causal,
                                 scale=scale, training=training,
                                 return_probs=True)
        return out[0], p[0]
    out = scaled_dot_product_attention(
        q, k, v, dropout_p=dropout, is_causal=causal, scale=scale,
        training=training, segment_ids=seg_q)
    return out[0], None


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=False, window_size=None,
                        fixed_seed_offset=None, rng_name='', training=True,
                        name=None):
    """FlashMask attention (ref: flash_attention.py::flashmask_attention).

    `startend_row_indices` (B, H|1, Sk, 1|2|4) encodes column-wise sparse
    masks: with 1 value LTS (causal: rows >= LTS masked), 2 values
    [LTS, LTE) masked below the diagonal, 4 values
    [LTS, LTE) ∪ [UTS, UTE) for bidirectional. This implementation lowers
    the encoding to a boolean mask consumed by the fused attention path —
    the row-index compression is a CUDA-kernel memory optimisation; under
    XLA the mask fuses into the attention einsum anyway.
    """
    b, sq, h, d = query.shape
    sk = key.shape[1]
    rows = jnp.arange(sq)[:, None]                      # query index
    if startend_row_indices is None:
        mask = None
    else:
        idx = jnp.asarray(startend_row_indices)         # (B, Hm, Sk, C)
        c = idx.shape[-1]
        idx = idx.transpose(0, 1, 3, 2)[:, :, :, None, :]  # (B,Hm,C,1,Sk)
        if causal:
            if c == 1:
                lts = idx[:, :, 0]
                mask = rows < lts                        # keep rows < LTS
            elif c == 2:
                lts, lte = idx[:, :, 0], idx[:, :, 1]
                mask = (rows < lts) | (rows >= lte)
            else:
                raise ValueError(f'causal flashmask expects 1 or 2 values, '
                                 f'got {c}')
        else:
            if c == 2:
                lts, ute = idx[:, :, 0], idx[:, :, 1]
                mask = (rows < lts) & (rows >= ute)
            elif c == 4:
                lts, lte = idx[:, :, 0], idx[:, :, 1]
                uts, ute = idx[:, :, 2], idx[:, :, 3]
                mask = ~(((rows >= lts) & (rows < lte))
                         | ((rows >= uts) & (rows < ute)))
            else:
                raise ValueError(f'non-causal flashmask expects 2 or 4 '
                                 f'values, got {c}')
    if window_size is not None:
        w = (window_size, window_size) if isinstance(window_size, int) \
            else tuple(window_size)
        cols = jnp.arange(sk)[None, :]
        win = (rows - cols <= w[0]) & (cols - rows <= w[1])
        mask = win[None, None] if mask is None else mask & win[None, None]
    out = scaled_dot_product_attention(
        query, key, value, attn_mask=mask, dropout_p=dropout,
        is_causal=causal, training=training)
    if mask is not None:
        # same empty-row convention as the segment-masked kernels: a query
        # whose every key is masked returns 0, not the uniform mean of v
        eff = mask
        if causal:
            cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            eff = eff & cm[None, None]
        row_valid = jnp.any(eff, axis=-1)                # (B, Hm, Sq)
        out = jnp.where(
            jnp.moveaxis(row_valid, 1, -1)[..., None], out, 0.0)
    return out


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None):
    """CSR-patterned sparse attention (ref: nn/functional/
    sparse_attention.py; the reference requires CUDA 11.3+). q/k/v:
    (B, H, S, D); offset (B, H, S+1); columns (B, H, nnz).

    On TPU the CSR pattern is lowered to a boolean mask and fused into
    the dense attention — XLA's MXU tiling beats gather-based sparse
    matmul until sparsity is extreme, and the semantics (softmax only
    over the listed columns) are preserved exactly.
    """
    b, h, s, d = query.shape
    nnz = sparse_csr_columns.shape[-1]

    def one_head(offset, columns):
        row_of = jnp.searchsorted(offset, jnp.arange(nnz), side='right') - 1
        m = jnp.zeros((s, s), bool)
        return m.at[row_of, columns].set(True)

    mask = jax.vmap(jax.vmap(one_head))(
        jnp.asarray(sparse_csr_offset), jnp.asarray(sparse_csr_columns))
    if key_padding_mask is not None:
        mask = mask & (jnp.asarray(key_padding_mask) != 0)[:, None, None, :]
    if attn_mask is not None:
        mask = mask & (jnp.asarray(attn_mask) != 0)[None, None]
    qt = query.transpose(0, 2, 1, 3)    # -> (B, S, H, D) sdpa layout
    kt = key.transpose(0, 2, 1, 3)
    vt = value.transpose(0, 2, 1, 3)
    out = scaled_dot_product_attention(qt, kt, vt, attn_mask=mask)
    return out.transpose(0, 2, 1, 3)
