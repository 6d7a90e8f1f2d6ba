"""Fused ops (ref: python/paddle/incubate/nn/functional/*).

The reference hand-fuses these into single CUDA kernels; on TPU the
same fusion happens in XLA, so each "fused_*" here is the composed jnp
expression (single dispatch under jit) routed through the pallas fast
paths where one exists (rms_norm, flash attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False):
    """ref: incubate/nn/functional/fused_matmul_bias.py."""
    if transpose_x:
        x = jnp.swapaxes(x, -1, -2)
    if transpose_y:
        y = jnp.swapaxes(y, -1, -2)
    out = x @ y
    return out if bias is None else out + bias


fused_linear = fused_matmul_bias


def swiglu(x, y=None):
    """ref: incubate/nn/functional/swiglu.py — silu(x) * y; single-arg
    form splits the last dim in half."""
    if y is None:
        x, y = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(x) * y


def _flatten_norm(x, begin_norm_axis):
    """Paddle norm semantics: normalize over ALL trailing axes from
    begin_norm_axis; returns (flattened x, restore shape) — a no-op view
    for the default last-axis case."""
    axis = begin_norm_axis % x.ndim if begin_norm_axis >= 0 else \
        x.ndim + begin_norm_axis
    if axis == x.ndim - 1:
        return x, None
    shape = x.shape
    return x.reshape(shape[:axis] + (-1,)), shape


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kw):
    """ref: fused_rms_norm.py — dispatches to the pallas kernel on TPU."""
    from ...ops import rms_norm as _rms

    xf, shape = _flatten_norm(x, begin_norm_axis)
    out = _rms(xf, norm_weight.reshape(-1) if norm_weight is not None
               else None, epsilon)
    if norm_bias is not None:
        out = out + norm_bias.reshape(-1)
    return out if shape is None else out.reshape(shape)


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, residual=None, **kw):
    """ref: fused_layer_norm.py (residual-add + LN)."""
    from ...nn.functional.norm import layer_norm

    if residual is not None:
        x = x + residual
    xf, shape = _flatten_norm(x, begin_norm_axis)
    out = layer_norm(xf, xf.shape[-1],
                     norm_weight.reshape(-1) if norm_weight is not None
                     else None,
                     norm_bias.reshape(-1) if norm_bias is not None
                     else None, epsilon)
    return out if shape is None else out.reshape(shape)


def fused_dropout_add(x, y, p=0.0, training=True, mode='upscale_in_train',
                      rng_key=None):
    """ref: fused_dropout_add.py — dropout(x) + y."""
    if p == 0.0:
        return x + y
    if not training:
        # downscale_in_infer: train keeps raw activations, infer scales
        if mode == 'downscale_in_infer':
            x = x * (1 - p)
        return x + y
    from ...framework import random as random_mod

    key = rng_key if rng_key is not None else random_mod.split_key()
    keep = jax.random.bernoulli(key, 1 - p, x.shape)
    if mode == 'upscale_in_train':
        x = jnp.where(keep, x / (1 - p), 0.0)
    else:
        x = jnp.where(keep, x, 0.0)
    return x + y


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True):
    """ref: fused_rotary_position_embedding.py.

    q/k/v: (B, S, H, D). When sin/cos are None they are computed from
    positions with the default 10000 theta. Accepts the reference's
    full-head-dim cos/sin layout ((1, S, 1, D), both halves duplicated)
    or the compact (S, D/2)/(B, S, D/2) tables. use_neox_rotary_style
    selects rotate-half (True) vs GPT-J interleaved pairs (False).
    Returns rotated (q, k, v) — v passes through (rope only mixes q/k,
    the reference accepts it for API parity).
    """
    from ...models.llama import apply_rotary, rope_cos_sin

    B, S, _, D = q.shape
    if cos is None or sin is None:
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        cos, sin = rope_cos_sin(position_ids, D, dtype=q.dtype)
    else:
        def canon(t):
            t = jnp.asarray(t)
            if t.ndim == 4:                # reference layout (B|1, S, 1, D)
                t = t[:, :, 0, :]
            if t.ndim == 2:                # (S, Dx) → (1, S, Dx)
                t = t[None]
            if t.shape[-1] == D:
                # full-head-dim table: halves duplicated (neox) or
                # pairwise-duplicated (interleaved)
                t = t[..., ::2] if not use_neox_rotary_style else \
                    t[..., :D // 2]
            if position_ids is not None:
                # gather table rows at the requested positions (decode
                # steps pass the full-length table + position_ids=[[t]])
                t = jnp.broadcast_to(t, (B,) + t.shape[1:])
                t = jnp.take_along_axis(
                    t, jnp.asarray(position_ids)[:, :, None], axis=1)
            return jnp.broadcast_to(t, (B, S, D // 2))

        cos, sin = canon(cos), canon(sin)

    if use_neox_rotary_style:
        rot = lambda x: apply_rotary(x, cos, sin)
    else:
        # GPT-J style: rotate adjacent pairs (2i, 2i+1)
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]

        def rot(x):
            xp = x.reshape(*x.shape[:-1], D // 2, 2)
            xe, xo = xp[..., 0], xp[..., 1]
            re = xe * c - xo * s
            ro = xo * c + xe * s
            return jnp.stack([re, ro], -1).reshape(x.shape).astype(x.dtype)

    out_q = rot(q)
    out_k = rot(k) if k is not None else None
    return out_q, out_k, v


def fused_multi_head_attention(x, qkv_weight, linear_weight, pre_layer_norm=False,
                               pre_ln_scale=None, pre_ln_bias=None,
                               ln_scale=None, ln_bias=None, pre_ln_epsilon=1e-5,
                               qkv_bias=None, linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.0,
                               attn_dropout_rate=0.0, ln_epsilon=1e-5,
                               training=True, num_heads=None):
    """ref: fused_transformer.py::fused_multi_head_attention — packed-QKV
    self-attention block with residual + layer norm, flash-attention fast
    path on TPU.

    x: (B, S, E); qkv_weight: (3, num_heads, head_dim, E) (reference
    layout); linear_weight: (E, E).
    """
    from ...nn.functional.attention import scaled_dot_product_attention
    from ...nn.functional.norm import layer_norm

    B, S, E = x.shape
    three, H, D, _ = qkv_weight.shape
    assert three == 3 and H * D == E

    residual = x
    if pre_layer_norm:
        x = layer_norm(x, E, pre_ln_scale, pre_ln_bias, pre_ln_epsilon)
    qkv = jnp.einsum('bse,thde->bsthd', x, qkv_weight)     # (B,S,3,H,D)
    if qkv_bias is not None:
        qkv = qkv + qkv_bias.reshape(3, H, D)[None, None]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]     # (B,S,H,D)
    new_cache = None
    if cache_kv is not None:
        # ref layout (2, B, H, S_past, D): append, attend over the
        # full prefix, and return the grown cache alongside the output
        past_k = jnp.swapaxes(cache_kv[0], 1, 2)           # (B,S_past,H,D)
        past_v = jnp.swapaxes(cache_kv[1], 1, 2)
        k = jnp.concatenate([past_k, k], axis=1)
        v = jnp.concatenate([past_v, v], axis=1)
        new_cache = jnp.stack([jnp.swapaxes(k, 1, 2),
                               jnp.swapaxes(v, 1, 2)])
    out = scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate,
        training=training)
    out = out.reshape(B, S, E) @ linear_weight
    if linear_bias is not None:
        out = out + linear_bias
    if dropout_rate:
        out = fused_dropout_add(out, residual, dropout_rate, training)
    else:
        out = out + residual
    if not pre_layer_norm:
        out = layer_norm(out, E, ln_scale, ln_bias, ln_epsilon)
    if new_cache is not None:
        return out, new_cache
    return out


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation='relu',
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True):
    """ref: fused_transformer.py::fused_feedforward — LN + MLP + residual."""
    from ...nn.functional.norm import layer_norm

    E = x.shape[-1]
    residual = x
    if pre_layer_norm:
        x = layer_norm(x, E, ln1_scale, ln1_bias, ln1_epsilon)
    act = {'relu': jax.nn.relu, 'gelu': jax.nn.gelu,
           'silu': jax.nn.silu}[activation]
    h = act(fused_matmul_bias(x, linear1_weight, linear1_bias))
    if dropout1_rate and training:
        h = fused_dropout_add(h, jnp.zeros_like(h), dropout1_rate, training)
    h = fused_matmul_bias(h, linear2_weight, linear2_bias)
    out = fused_dropout_add(h, residual, dropout2_rate, training) \
        if dropout2_rate and training else h + residual
    if not pre_layer_norm:
        out = layer_norm(out, E, ln2_scale, ln2_bias, ln2_epsilon)
    return out


def fused_bias_act(x, bias=None, act_method='gelu'):
    """ref: fused_bias_act.py."""
    if bias is not None:
        x = x + bias
    return {'gelu': jax.nn.gelu, 'relu': jax.nn.relu, 'silu': jax.nn.silu,
            'swiglu': swiglu}[act_method](x)


# ---------------------------------------------------------------------------
# Serving attention primitives (paged + masked decode)
# ---------------------------------------------------------------------------

def _split_qkv(x, num_heads, num_kv_heads, head_dim):
    """(T, (Hq + 2*Hkv) * D) fused qkv -> q (T, Hq, D), k/v (T, Hkv, D)."""
    q_sz = num_heads * head_dim
    kv_sz = num_kv_heads * head_dim
    q = x[..., :q_sz].reshape(*x.shape[:-1], num_heads, head_dim)
    k = x[..., q_sz:q_sz + kv_sz].reshape(*x.shape[:-1], num_kv_heads,
                                          head_dim)
    v = x[..., q_sz + kv_sz:].reshape(*x.shape[:-1], num_kv_heads, head_dim)
    return q, k, v


def _rope_rows(q, k, cos, sin, neox):
    """Rotate one row per sequence: q/k (N, H, D); cos/sin (N, D/2).
    neox=True -> rotate-half; False -> GPT-J interleaved pairs (the
    reference default), mirroring fused_rotary_position_embedding."""
    if neox:
        from ...models.llama import apply_rotary

        return (apply_rotary(q[:, None], cos[:, None], sin[:, None])[:, 0],
                apply_rotary(k[:, None], cos[:, None], sin[:, None])[:, 0])

    def rot(x):
        D = x.shape[-1]
        xp = x.reshape(*x.shape[:-1], D // 2, 2)
        xe, xo = xp[..., 0], xp[..., 1]
        c, sn = cos[:, None, :], sin[:, None, :]
        return jnp.stack([xe * c - xo * sn, xo * c + xe * sn],
                         -1).reshape(x.shape).astype(x.dtype)

    return rot(q), rot(k)


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype='default', out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """Single-token decode MHA over a contiguous cache (ref:
    python/paddle/incubate/nn/functional/masked_multihead_attention.py:74
    — the reference generation loop's fused decode attention).

    x: (B, 3*H*D) fused qkv for ONE new token per row; cache_kv:
    (2, B, H, max_seq, D); sequence_lengths: (B, 1) current per-row
    lengths (write position). rotary_tensor: optional (2, B, S, D/2)
    cos/sin stack applied to q/k at each row's position. Returns
    (out (B, H*D), cache_kv_out).

    TPU-native: the cache row is attended by the paged decode kernel
    (ops/pallas/paged_attention.py, one page per row) when the row fits
    VMEM; the XLA masked path otherwise. The reference's smooth-quant
    int8 GEMM pipeline knobs (qkv_out_scale / out_shift / out_smooth /
    int32 x / out_scale) are CUDA-pipeline-specific and rejected.
    """
    for name, v_ in (('qkv_out_scale', qkv_out_scale),
                     ('out_shift', out_shift), ('out_smooth', out_smooth),
                     ('beam_cache_offset', beam_cache_offset)):
        if v_ is not None:
            raise NotImplementedError(
                f'{name} belongs to the reference CUDA smooth-quant/beam '
                f'pipeline; quantize with paddle_tpu.quantization + '
                f'kv_cache_int8 instead')
    if out_scale != -1:
        raise NotImplementedError('out_scale quantized output unsupported')
    if cache_kv is None:
        raise ValueError(
            'masked_multihead_attention requires cache_kv (the '
            '(2, B, H, max_seq, D) decode cache written at prefill) — '
            'there is no cache-less decode step')
    _, B, H, S, D = cache_kv.shape
    if cache_kv.dtype == jnp.int8:
        raise NotImplementedError(
            'int8 cache_kv is not supported by masked_multihead_attention '
            '(no scale inputs in this API) — use block_multihead_attention '
            'with static dequant scales, or the model-level '
            'generate(kv_cache_int8=True) path')
    q, k, v = _split_qkv(x, H, H, D)                     # (B, H, D) each
    if bias is not None:
        b3 = jnp.asarray(bias).reshape(3, H, D)
        q, k, v = q + b3[0], k + b3[1], v + b3[2]
    if sequence_lengths is None:
        raise ValueError(
            'sequence_lengths is required (per-row cache write position)')
    if not isinstance(sequence_lengths, jax.core.Tracer):
        import numpy as _np

        if (_np.reshape(_np.asarray(sequence_lengths), (-1,)) >= S).any():
            raise ValueError(
                f'cache is full (sequence_length >= max_seq {S}): the new '
                f'token has nowhere to land — grow the cache (JAX would '
                f'silently drop the out-of-bounds write)')
    lens = jnp.reshape(jnp.asarray(sequence_lengths, jnp.int32), (-1,))
    if rotary_tensor is not None:
        rt = jnp.asarray(rotary_tensor)
        if rt.ndim != 4 or rt.shape[0] != 2:
            raise NotImplementedError(
                'rotary_tensor must be a (2, B, S, D/2) cos/sin stack '
                '(the reference CUDA layouts are kernel-internal); or '
                'pre-rotate q/k and pass rotary_tensor=None')
        pos = lens[:, None]                              # (B, 1)
        cos = jnp.take_along_axis(rt[0], pos[:, :, None], axis=1)[:, 0]
        sin = jnp.take_along_axis(rt[1], pos[:, :, None], axis=1)[:, 0]
        q, k = _rope_rows(q, k, cos, sin, use_neox_rotary_style)

    ck, cv = cache_kv[0], cache_kv[1]                    # (B, H, S, D)
    rows = jnp.arange(B)
    ck = ck.at[rows, :, lens].set(k.astype(ck.dtype))
    cv = cv.at[rows, :, lens].set(v.astype(cv.dtype))
    counts = lens + 1

    out = None
    if src_mask is None:
        from ...ops import use_pallas

        if use_pallas() and D % 8 == 0:
            # head-major contiguous variant of the paged kernel:
            # streams any cache length blockwise, no transpose
            from ...ops.pallas.paged_attention import (
                decode_attention_headmajor)

            out = decode_attention_headmajor(
                q[:, None], ck, cv, counts)[:, 0]
    if out is None:
        logits = jnp.einsum('bhd,bhsd->bhs', q.astype(jnp.float32),
                            ck.astype(jnp.float32)) / (D ** 0.5)
        mask = jnp.arange(S)[None, None, :] < counts[:, None, None]
        logits = jnp.where(mask, logits, -1e30)
        if src_mask is not None:
            logits = logits + jnp.asarray(src_mask,
                                          jnp.float32).reshape(B, 1, -1)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum('bhs,bhsd->bhd', p,
                         cv.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, H * D), jnp.stack([ck, cv])


def block_multihead_attention(
        qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
        seq_lens_this_time, padding_offsets=None, cum_offsets=None,
        cu_seqlens_q=None, cu_seqlens_k=None, block_tables=None,
        pre_key_cache=None, pre_value_cache=None, cache_k_quant_scales=None,
        cache_v_quant_scales=None, cache_k_dequant_scales=None,
        cache_v_dequant_scales=None, qkv_out_scale=None, qkv_bias=None,
        out_shift=None, out_smooth=None, max_enc_len_this_time=None,
        max_dec_len_this_time=None, rope_emb=None, mask=None, tgt_mask=None,
        max_seq_len=-1, block_size=64, use_neox_style=False,
        use_dynamic_cachekv_quant=False, quant_round_type=1,
        quant_max_bound=127.0, quant_min_bound=-127.0, out_scale=-1,
        compute_dtype='default', num_heads=None, num_kv_heads=None):
    """Paged-KV serving attention (ref:
    python/paddle/incubate/nn/functional/block_multihead_attention.py:30).

    The serving loop's two phases are both supported, per call:
      - PREFILL (seq_lens_encoder > 0): the unpadded token stream
        attends causally within each sequence (varlen segment-id flash
        on TPU) and its K/V rows are scattered into the paged cache via
        block_tables.
      - DECODE (seq_lens_decoder > 0, one token per row): the new K/V
        row lands in its page and the fused paged kernel streams exactly
        the pages the row occupies (ops/pallas/paged_attention.py — the
        block table drives the BlockSpec index map via scalar prefetch).

    Layouts follow the reference: qkv (token_num, (Hq+2*Hkv)*D);
    key_cache/value_cache (max_block_num, Hkv, block_size, D);
    block_tables (B, MAXB); cu_seqlens_q (B+1,) prefix sums of this
    call's tokens. STATIC cache-KV int8 is supported via
    cache_k/v_dequant_scales of shape (Hkv,) or (Hkv, D) with int8
    caches (quantization on write uses the reciprocal). Mode must be
    host-decidable (concrete seq_lens): mixed prefill+decode in one call
    and dynamic per-batch cache quant are rejected with guidance.
    Returns (out, qkv, key_cache, value_cache).
    """
    import numpy as _np

    for name, v_ in (('qkv_out_scale', qkv_out_scale),
                     ('out_shift', out_shift), ('out_smooth', out_smooth),
                     ('pre_key_cache', pre_key_cache),
                     ('pre_value_cache', pre_value_cache)):
        if v_ is not None:
            raise NotImplementedError(
                f'{name} is part of the reference CUDA smooth-quant/'
                f'pre-cache pipeline and is not supported on TPU')
    if use_dynamic_cachekv_quant:
        raise NotImplementedError(
            'dynamic cache-KV quant (per-batch scales) is not supported: '
            'use static dequant scales, or the model-level '
            'generate(kv_cache_int8=True) path which calibrates at '
            'prefill')
    if out_scale != -1:
        raise NotImplementedError('quantized fmha output unsupported')
    if isinstance(seq_lens_encoder, jax.core.Tracer) or isinstance(
            seq_lens_decoder, jax.core.Tracer):
        raise NotImplementedError(
            'block_multihead_attention needs host-known sequence lengths '
            'to pick the prefill/decode phase (the serving loop knows '
            'its phase; call it with concrete seq_lens)')

    NB, Hkv, BS, D = key_cache.shape
    if block_size != BS:
        raise ValueError(f'block_size={block_size} != cache page size {BS}')
    enc = _np.reshape(_np.asarray(seq_lens_encoder), (-1,))
    dec = _np.reshape(_np.asarray(seq_lens_decoder), (-1,))
    B = enc.shape[0]
    if num_kv_heads is None:
        num_kv_heads = Hkv
    if num_heads is None:
        num_heads = qkv.shape[-1] // D - 2 * num_kv_heads
    Hq = num_heads
    q, k, v = _split_qkv(qkv, Hq, num_kv_heads, D)       # (T, H*, D)
    if qkv_bias is not None:
        bq, bk, bv = _split_qkv(jnp.asarray(qkv_bias)[None], Hq,
                                num_kv_heads, D)
        q, k, v = q + bq[0], k + bk[0], v + bv[0]

    prefill = bool((enc > 0).any())
    decode = bool((dec > 0).any()) and not prefill
    if prefill and bool((dec > 0).any()):
        raise NotImplementedError(
            'mixed prefill+decode batches are not supported in one call; '
            'split the batch by phase (the reference serving loop '
            'schedules them separately too)')

    tbl = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, NB - 1)
    quant_cache = key_cache.dtype == jnp.int8
    if quant_cache:
        def canon_scale(s):
            s = jnp.asarray(s, jnp.float32)
            return jnp.broadcast_to(s[:, None], (Hkv, D)) if s.ndim == 1 \
                else s
        kds = canon_scale(cache_k_dequant_scales)
        vds = canon_scale(cache_v_dequant_scales)

        def quantize_rows(x, ds):
            qx = jnp.round(x.astype(jnp.float32) / ds[None])
            return jnp.clip(qx, quant_min_bound,
                            quant_max_bound).astype(jnp.int8)
    if rope_emb is not None:
        re = jnp.asarray(rope_emb)
        if re.ndim == 5:                                  # (2,B,S,1,D/2)
            re = re[:, :, :, 0, :]
        if re.ndim != 4 or re.shape[0] != 2:
            raise NotImplementedError(
                'rope_emb must be (2, B, max_seq, [1,] D/2) cos/sin')

    if prefill:
        # ---- varlen causal prefill over the unpadded token stream ----
        cu = jnp.reshape(jnp.asarray(cu_seqlens_q, jnp.int32), (-1,))
        T = q.shape[0]
        tok = jnp.arange(T)
        seg = jnp.searchsorted(cu[1:], tok, side='right').astype(jnp.int32)
        pos = tok - cu[seg]                               # position in seq
        if rope_emb is not None:
            cos = re[0][seg, pos]                         # (T, D/2)
            sin = re[1][seg, pos]
            q, k = _rope_rows(q, k, cos, sin, use_neox_style)
        from ...nn.functional.attention import scaled_dot_product_attention

        out = scaled_dot_product_attention(
            q[None], k[None], v[None], is_causal=True,
            segment_ids=seg[None])[0]                     # (T, Hq, D)
        # scatter K/V rows into pages: token t of seq b at position p
        # lands in page tbl[b, p // BS] slot p % BS
        page = tbl[seg, pos // BS]
        slot = pos % BS
        kw, vw = (quantize_rows(k, kds), quantize_rows(v, vds)) \
            if quant_cache else (k.astype(key_cache.dtype),
                                 v.astype(value_cache.dtype))
        key_cache = key_cache.at[page, :, slot].set(kw)
        value_cache = value_cache.at[page, :, slot].set(vw)
        return out.reshape(T, Hq * D), qkv, key_cache, value_cache

    if decode:
        # ---- one token per row: paged fused decode -------------------
        if q.shape[0] != B:
            raise NotImplementedError(
                f'decode expects one qkv row per batch row (got '
                f'{q.shape[0]} tokens for batch {B}); keep finished rows '
                f'in the batch with seq_lens_this_time=0')
        this = _np.reshape(_np.asarray(seq_lens_this_time), (-1,))
        active = jnp.asarray(this > 0)                   # (B,)
        if ((dec + (this > 0)) > tbl.shape[1] * BS).any():
            raise ValueError(
                f'page capacity exceeded: a row needs position '
                f'{int(dec.max())} but block_tables provides only '
                f'{tbl.shape[1]} pages x {BS} slots — allocate another '
                f'page for the row (JAX clamping would silently '
                f'overwrite a live slot)')
        lens = jnp.asarray(dec, jnp.int32)               # context so far
        rows = jnp.arange(B)
        page = tbl[rows, lens // BS]
        slot = lens % BS
        if rope_emb is not None:
            pos = lens[:, None]
            cos = jnp.take_along_axis(re[0], pos[:, :, None], axis=1)[:, 0]
            sin = jnp.take_along_axis(re[1], pos[:, :, None], axis=1)[:, 0]
            q, k = _rope_rows(q, k, cos, sin, use_neox_style)
        kw, vw = (quantize_rows(k, kds), quantize_rows(v, vds)) \
            if quant_cache else (k.astype(key_cache.dtype),
                                 v.astype(value_cache.dtype))
        # finished/inactive rows (seq_lens_this_time == 0) must not
        # scatter their dummy token — keep the existing page contents
        old_k = key_cache[page, :, slot]
        old_v = value_cache[page, :, slot]
        key_cache = key_cache.at[page, :, slot].set(
            jnp.where(active[:, None, None], kw, old_k))
        value_cache = value_cache.at[page, :, slot].set(
            jnp.where(active[:, None, None], vw, old_v))
        counts = lens + 1

        out = None
        from ...ops import use_pallas

        if use_pallas() and D % 8 == 0 and tgt_mask is None:
            from ...ops.pallas.paged_attention import (
                paged_decode_attention)

            out = paged_decode_attention(
                q[:, None], key_cache, value_cache, tbl, counts,
                k_scale=kds if quant_cache else None,
                v_scale=vds if quant_cache else None)[:, 0]
        if out is None:
            # XLA fallback: gather each row's pages to a contiguous view
            maxb = tbl.shape[1]
            ck = key_cache[tbl]                           # (B,MAXB,Hkv,BS,D)
            cv = value_cache[tbl]
            ck = jnp.swapaxes(ck, 2, 3).reshape(B, maxb * BS, Hkv, D)
            cv = jnp.swapaxes(cv, 2, 3).reshape(B, maxb * BS, Hkv, D)
            if quant_cache:
                ck = ck.astype(jnp.float32) * kds[None, None]
                cv = cv.astype(jnp.float32) * vds[None, None]
            rep = Hq // Hkv
            ckr = jnp.repeat(ck.astype(jnp.float32), rep, axis=2)
            cvr = jnp.repeat(cv.astype(jnp.float32), rep, axis=2)
            logits = jnp.einsum('bhd,bshd->bhs', q.astype(jnp.float32),
                                ckr) / (D ** 0.5)
            msk = jnp.arange(maxb * BS)[None, None, :] < counts[:, None,
                                                                None]
            if tgt_mask is not None:
                tm = jnp.asarray(tgt_mask, jnp.float32).reshape(B, 1, -1)
                logits = logits + jnp.pad(
                    tm, ((0, 0), (0, 0), (0, maxb * BS - tm.shape[-1])))
            logits = jnp.where(msk, logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum('bhs,bshd->bhd', p, cvr).astype(qkv.dtype)
        return out.reshape(B, Hq * D), qkv, key_cache, value_cache

    raise ValueError('neither prefill (seq_lens_encoder) nor decode '
                     '(seq_lens_decoder) rows present')


# ---------------------------------------------------------------------------
# Remaining reference functional surface
# ---------------------------------------------------------------------------

def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size):
    """ref: incubate/nn/functional/blha_get_max_len.py — the serving
    loop's helper: max encoder/decoder lengths this step (feeds
    block_multihead_attention's max_enc/dec_len_this_time)."""
    enc = jnp.max(jnp.reshape(jnp.asarray(seq_lens_encoder, jnp.int32),
                              (-1,)))
    dec = jnp.max(jnp.reshape(jnp.asarray(seq_lens_decoder, jnp.int32),
                              (-1,)))
    return enc.reshape(1), dec.reshape(1)


def fused_dot_product_attention(query, key, value, attn_mask=None,
                                dropout_p=0.0, is_causal=False,
                                scaling_factor=None, training=True,
                                name=None):
    """ref: incubate/nn/functional/fused_dot_product_attention.py (cuDNN
    fused attention, [B, S, H, D] layout) — on TPU this IS
    scaled_dot_product_attention (flash kernel underneath)."""
    from ...nn.functional.attention import scaled_dot_product_attention

    return scaled_dot_product_attention(
        query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, scale=scaling_factor, training=training)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    """ref: incubate/nn/functional/variable_length_memory_efficient_
    attention.py (CUTLASS varlen attention, [B, H, S, D] layout):
    per-row query/key validity from seq_lens/kv_seq_lens, optional
    additive mask, causal option. The XLA softmax fuses; rows beyond a
    sequence's length contribute nothing and emit zeros."""
    if pre_cache_length:
        raise NotImplementedError(
            'pre_cache_length belongs to the reference CUDA pre-cache '
            'pipeline')
    B, H, Sq, D = query.shape
    Sk = key.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    ql = jnp.reshape(jnp.asarray(seq_lens, jnp.int32), (-1,))
    kl = jnp.reshape(jnp.asarray(kv_seq_lens, jnp.int32), (-1,))
    logits = jnp.einsum('bhqd,bhkd->bhqk', query.astype(jnp.float32),
                        key.astype(jnp.float32)) * scale
    keep = (jnp.arange(Sk)[None, None, None, :] < kl[:, None, None, None])
    if causal:
        keep = keep & (jnp.arange(Sk)[None, None, None, :]
                       <= jnp.arange(Sq)[None, None, :, None])
    logits = jnp.where(keep, logits, -1e30)
    if mask is not None:
        logits = logits + jnp.asarray(mask, jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd', p, value.astype(jnp.float32))
    # rows past a sequence's own length are undefined in the reference;
    # zero them so garbage can't leak downstream
    qvalid = (jnp.arange(Sq)[None, None, :, None]
              < ql[:, None, None, None])
    return jnp.where(qvalid, out, 0.0).astype(query.dtype)


def fused_moe(x, gate_weight, ffn1_weight, ffn2_weight, ffn1_bias=None,
              ffn1_scale=None, ffn2_bias=None, ffn2_scale=None,
              quant_method='None', moe_topk=2, norm_topk_prob=True):
    """ref: incubate/nn/functional/fused_moe.py — the fused serving MoE:
    per-token top-k over precomputed gate logits ([B, S, E]), SwiGLU
    experts with fused gate+up ffn1 ([E, d, 2*dff]), optional int8
    weights dequantized by ffn1/2_scale. TPU-native: the dropless
    sort + lax.ragged_dot grouped-GEMM path (distributed.moe)."""
    from ...distributed.moe import F as _moeF  # silu
    from ...distributed.moe import ragged_expert_apply

    if quant_method not in ('None', None, 'weight_only_int8'):
        raise NotImplementedError(f'quant_method={quant_method!r}')
    if quant_method == 'weight_only_int8' and (ffn1_scale is None
                                               or ffn2_scale is None):
        raise ValueError(
            "quant_method='weight_only_int8' requires ffn1_scale and "
            'ffn2_scale — raw int8 codes without scales would silently '
            'produce garbage')
    if ffn1_bias is not None:
        raise NotImplementedError(
            'ffn1_bias (inside the activation) is not supported by the '
            'ragged path; fold it into the checkpoint (the reference '
            'CUTLASS kernel does apply it — fc1_expert_biases)')
    B, S, d = x.shape
    E = gate_weight.shape[-1]
    logits = jnp.asarray(gate_weight, jnp.float32).reshape(B * S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, moe_topk)
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    w1 = jnp.asarray(ffn1_weight)
    w2 = jnp.asarray(ffn2_weight)
    if ffn1_scale is not None:
        w1 = w1.astype(jnp.float32) * jnp.asarray(ffn1_scale)[:, None, :]
    if ffn2_scale is not None:
        w2 = w2.astype(jnp.float32) * jnp.asarray(ffn2_scale)[:, None, :]
    w1 = w1.astype(x.dtype)
    w2 = w2.astype(x.dtype)
    dff2 = w1.shape[-1]
    # fused gate+up: split [.., 2*dff] -> swiglu halves
    w_gate, w_up = w1[..., :dff2 // 2], w1[..., dff2 // 2:]

    tokens = x.reshape(B * S, d)
    out = ragged_expert_apply(tokens, expert_idx, gate_vals, w_gate, w_up,
                              w2, E, act=_moeF.silu)
    if ffn2_bias is not None:
        # per-expert output bias: gather-free second pass
        oh = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, k, E)
        w = (oh * gate_vals[..., None]).sum(1)                 # (T, E)
        b2 = jnp.asarray(ffn2_bias).reshape(E, d)
        out = out + (w @ b2).astype(out.dtype)
    return out.reshape(B, S, d)


def fused_gate_attention(query, key=None, query_weight=None,
                         key_weight=None, value_weight=None,
                         qkv_weight=None, gate_linear_weight=None,
                         gate_linear_bias=None, out_linear_weight=None,
                         out_linear_bias=None, nonbatched_bias=None,
                         attn_mask=None, has_gating=True, merge_qkv=True,
                         use_flash_attn=False):
    """ref: incubate/nn/functional/fused_gate_attention.py (AlphaFold
    gated self-attention): q/k/v projections, attention with an optional
    nonbatched bias, sigmoid gating, output projection. Layouts follow
    the reference: query (B, M, R, qdim); merged qkv_weight
    (3, H, D, qdim); separate q/k/v weights (qdim, H, D);
    gate/out weights (qdim, H, D) / (H, D, odim)."""
    q_in = jnp.asarray(query)
    if merge_qkv:
        if qkv_weight is None:
            raise ValueError('merge_qkv=True requires qkv_weight')
        qkv = jnp.einsum('bmrc,thdc->tbmrhd', q_in, jnp.asarray(qkv_weight))
        q, k, v = qkv[0], qkv[1], qkv[2]        # (B, M, R, H, D)
    else:
        if key is None:
            key = query
        k_in = jnp.asarray(key)
        q = jnp.einsum('bmrc,chd->bmrhd', q_in, jnp.asarray(query_weight))
        k = jnp.einsum('bmrc,chd->bmrhd', k_in, jnp.asarray(key_weight))
        v = jnp.einsum('bmrc,chd->bmrhd', k_in, jnp.asarray(value_weight))
    D = q.shape[-1]
    logits = jnp.einsum('bmrhd,bmshd->bmhrs', q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (1.0 / (D ** 0.5))
    if nonbatched_bias is not None:
        # reference layout (B, 1, H, R, S): broadcasts over the msa axis
        # directly — no extra axis
        logits = logits + jnp.asarray(nonbatched_bias, jnp.float32)
    if attn_mask is not None:
        logits = logits + jnp.asarray(attn_mask, jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bmhrs,bmshd->bmrhd', p, v.astype(jnp.float32))
    out = out.astype(q_in.dtype)
    if has_gating:
        if gate_linear_weight is None:
            raise ValueError('has_gating=True requires gate_linear_weight')
        gate = jnp.einsum('bmrc,chd->bmrhd', q_in,
                          jnp.asarray(gate_linear_weight))
        if gate_linear_bias is not None:
            gate = gate + jnp.asarray(gate_linear_bias)
        out = out * jax.nn.sigmoid(gate)
    if out_linear_weight is not None:
        out = jnp.einsum('bmrhd,hdc->bmrc', out,
                         jnp.asarray(out_linear_weight))
        if out_linear_bias is not None:
            out = out + jnp.asarray(out_linear_bias)
    return out


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5,
                                           ln_epsilon=1e-5, training=True,
                                           mode='upscale_in_train',
                                           name=None):
    """ref: fused_transformer.py::fused_bias_dropout_residual_layer_norm
    — LN(residual + dropout(x + bias))."""
    if bias is not None:
        x = x + bias
    h = fused_dropout_add(x, residual, dropout_rate, training=training,
                          mode=mode)
    return fused_layer_norm(h, ln_scale, ln_bias, ln_epsilon)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation='gelu'):
    """ref: fused_linear_activation — matmul + bias + activation (the
    cuBLASLt epilogue fusion; XLA fuses the same chain on TPU)."""
    acts = {'gelu': jax.nn.gelu, 'relu': jax.nn.relu, 'none': lambda a: a,
            '': lambda a: a}
    if activation not in acts:
        raise ValueError(f'activation must be one of {list(acts)}')
    out = fused_matmul_bias(x, y, bias, transpose_x=trans_x,
                            transpose_y=trans_y)
    return acts[activation](out)


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights,
                            qkv_biases, linear_weights, linear_biases,
                            ffn_ln_scales, ffn_ln_biases, ffn1_weights,
                            ffn1_biases, ffn2_weights, ffn2_biases,
                            pre_layer_norm=True, epsilon=1e-5,
                            residual_alpha=1.0, cache_kvs=None,
                            beam_offset=None, pre_caches=None,
                            seq_lens=None, rotary_embs=None,
                            time_step=None, attn_mask=None,
                            dropout_rate=0.0, rotary_emb_dims=0,
                            activation='gelu', training=False,
                            mode='upscale_in_train', trans_qkvw=True,
                            ring_id=-1, norm_type='layernorm',
                            use_neox_rotary_style=True,
                            gqa_group_size=-1, name=None):
    """ref: fused_transformer.py::fused_multi_transformer — the
    FUNCTIONAL form of the serving decoder stack (per-layer weight
    lists; PaddleNLP's inference path calls this directly). Same math
    as incubate.nn.FusedMultiTransformer: prefill writes the
    (2, B, H, max_seq, D) caches through the flash path, `time_step`
    decode routes the fused head-major kernel. The CUDA-pipeline knobs
    (beam_offset, pre_caches, rotary_embs, gqa) are rejected with
    guidance.
    """
    for nm, v in (('beam_offset', beam_offset), ('pre_caches', pre_caches),
                  ('rotary_embs', rotary_embs)):
        if v is not None:
            raise NotImplementedError(
                f'{nm}: use the Llama-family models for RoPE/beam serving')
    if not trans_qkvw:
        raise NotImplementedError('trans_qkvw=False unsupported')
    if gqa_group_size not in (-1, 0):
        raise NotImplementedError(
            'gqa: use the Llama family (GQA-native) models')
    if residual_alpha != 1.0:
        raise NotImplementedError('residual_alpha != 1 unsupported')
    from ...nn.functional.norm import layer_norm
    from ...ops import rms_norm

    if norm_type == 'layernorm':
        def norm(h, scale, bias_):
            return layer_norm(h, h.shape[-1],
                              scale.reshape(-1) if scale is not None
                              else None,
                              bias_.reshape(-1) if bias_ is not None
                              else None, epsilon)
    elif norm_type == 'rmsnorm':
        def norm(h, scale, bias_):
            out = rms_norm(h, scale.reshape(-1) if scale is not None
                           else None, epsilon)
            return out + bias_ if bias_ is not None else out
    else:
        raise ValueError(f'norm_type must be layernorm|rmsnorm, '
                         f'got {norm_type!r}')
    acts = {'gelu': jax.nn.gelu, 'relu': jax.nn.relu,
            'silu': jax.nn.silu}
    if activation not in acts:
        raise ValueError(f'activation must be one of {list(acts)}')
    act = acts[activation]
    from ...nn.functional.attention import scaled_dot_product_attention

    if time_step is not None and x.shape[1] != 1:
        raise ValueError('time_step decode expects one token per row')
    if time_step is not None and cache_kvs is None:
        raise ValueError(
            'time_step decode requires cache_kvs (the per-layer '
            '(2, B, H, max_seq, D) caches written at prefill)')
    if time_step is not None and attn_mask is not None:
        raise NotImplementedError(
            'attn_mask is not applied on time_step decode steps (the '
            'cache window is positional) — drive padded decode via '
            'seq_lens instead of a mask')

    num_layers = len(qkv_weights)
    new_caches = [] if cache_kvs is not None else None
    for i in range(num_layers):
        qkv_w = jnp.asarray(qkv_weights[i])         # (3, H, D, E)
        _, H, D, _ = qkv_w.shape
        residual = x
        h = norm(x, ln_scales[i], ln_biases[i]) if pre_layer_norm else x
        cache = cache_kvs[i] if cache_kvs is not None else None
        if time_step is not None:
            xt = h[:, 0]
            qkv_flat = jnp.einsum('be,thde->bthd', xt, qkv_w).reshape(
                xt.shape[0], 3 * H * D)
            if qkv_biases[i] is not None:
                qkv_flat = qkv_flat + jnp.asarray(qkv_biases[i]).reshape(-1)
            lens = (jnp.reshape(jnp.asarray(seq_lens, jnp.int32), (-1, 1))
                    if seq_lens is not None
                    else jnp.full((x.shape[0], 1), time_step, jnp.int32))
            attn_out, nc = masked_multihead_attention(
                qkv_flat, cache_kv=cache, sequence_lengths=lens)
            attn_out = attn_out[:, None]
        else:
            qkv = jnp.einsum('bse,thde->bsthd', h, qkv_w)
            if qkv_biases[i] is not None:
                qkv = qkv + jnp.asarray(qkv_biases[i]).reshape(
                    3, H, D)[None, None]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            attn_out = scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                is_causal=attn_mask is None).reshape(*h.shape[:2], H * D)
            nc = cache
            if cache is not None:
                S = h.shape[1]
                nc = cache.at[0, :, :, :S].set(
                    jnp.swapaxes(k, 1, 2).astype(cache.dtype))
                nc = nc.at[1, :, :, :S].set(
                    jnp.swapaxes(v, 1, 2).astype(cache.dtype))
        if new_caches is not None:
            new_caches.append(nc)
        attn_out = attn_out @ jnp.asarray(linear_weights[i])
        if linear_biases[i] is not None:
            attn_out = attn_out + jnp.asarray(linear_biases[i])
        x = fused_dropout_add(attn_out, residual, dropout_rate,
                              training=training, mode=mode)
        if not pre_layer_norm:
            x = norm(x, ln_scales[i], ln_biases[i])

        residual = x
        h = norm(x, ffn_ln_scales[i], ffn_ln_biases[i]) \
            if pre_layer_norm else x
        h = h @ jnp.asarray(ffn1_weights[i])
        if ffn1_biases[i] is not None:
            h = h + jnp.asarray(ffn1_biases[i])
        h = act(h) @ jnp.asarray(ffn2_weights[i])
        if ffn2_biases[i] is not None:
            h = h + jnp.asarray(ffn2_biases[i])
        x = fused_dropout_add(h, residual, dropout_rate,
                              training=training, mode=mode)
        if not pre_layer_norm:
            x = norm(x, ffn_ln_scales[i], ffn_ln_biases[i])
    if cache_kvs is not None:
        return x, new_caches
    return x


@functools.partial(
    jax.jit, donate_argnames=('cache_kvs',),
    static_argnames=('pre_layer_norm', 'epsilon', 'activation',
                     'norm_type'))
def _fmt_decode_step(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                     linear_weights, linear_biases, ffn_ln_scales,
                     ffn_ln_biases, ffn1_weights, ffn1_biases, ffn2_weights,
                     ffn2_biases, cache_kvs, seq_lens, time_step, *,
                     pre_layer_norm, epsilon, activation, norm_type):
    # engine-wide retrace accounting (runs only while tracing)
    from ...inference.engine import _count_trace

    _count_trace('fmt_decode_step')
    return fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases,
        pre_layer_norm=pre_layer_norm, epsilon=epsilon,
        cache_kvs=cache_kvs, seq_lens=seq_lens, time_step=time_step,
        activation=activation, training=False, norm_type=norm_type)


def fused_multi_transformer_decode_step(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, cache_kvs, time_step,
        seq_lens=None, pre_layer_norm=True, epsilon=1e-5,
        activation='gelu', norm_type='layernorm'):
    """The fused_multi_transformer time_step path under the
    DecodeEngine's compilation/donation contract (docs/decode_engine.md):
    a MODULE-LEVEL jit (steady-state serving never retraces — the trace
    is keyed on the weight-list pytree structure, cache shapes, and the
    static config) with `cache_kvs` DONATED, so every layer's
    (2, B, H, max_seq, D) cache is updated in place instead of copied
    per token.

    Contract: the cache_kvs buffers passed in are DEAD to the caller
    after this returns — keep only the returned caches (the serving
    loop's natural `caches = step(..., caches)` shape). time_step may be
    a traced/device scalar: one compilation serves every step index.

    Returns (x_out, new_cache_kvs) exactly like
    fused_multi_transformer(time_step=...)."""
    return _fmt_decode_step(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, cache_kvs,
        seq_lens, jnp.asarray(time_step, jnp.int32),
        pre_layer_norm=bool(pre_layer_norm), epsilon=float(epsilon),
        activation=activation, norm_type=norm_type)
