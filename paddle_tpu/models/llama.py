"""Llama-2 family — the flagship decoder LM.

ref (architecture parity): PaddleNLP Llama / the reference's
`python/paddle/incubate` transformer stacks; components: RMSNorm
pre-norm, rotary position embedding, SwiGLU MLP, grouped-query
attention, tied-or-untied LM head.

TPU-native design notes:
  - the whole model is a pytree `nn.Layer`; one `jax.jit` / `pjit`
    train step covers fwd+bwd+update.
  - attention goes through `F.scaled_dot_product_attention`, which
    dispatches to the pallas flash-attention kernel on TPU.
  - parameters carry default `PartitionSpec`s for tensor parallelism
    (column-split QKV/gate/up, row-split o_proj/down) so
    `distributed.parallelize` can shard with zero per-model rules;
    the embedding is vocab-sharded ('tp' on vocab axis).
  - generation decodes with a functional KV-cache under
    `lax.while_loop` (static shapes: cache preallocated at max_len).
"""
from __future__ import annotations

import dataclasses
import math
import typing

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.base import Layer, Parameter
from .generation import GenerationMixin, PagedKVCache


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32          # < num_attention_heads → GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # optional dict, e.g. {'rope_type': 'llama3', 'factor': 8.0, ...}
    # (Llama-3.x frequency rescale); None = plain RoPE
    rope_scaling: typing.Optional[dict] = None
    tie_word_embeddings: bool = False
    attention_bias: bool = False           # qkv biases (Qwen2-style)
    initializer_range: float = 0.02
    dtype: str = 'float32'                 # param dtype; compute follows
    remat: bool = False                    # jax.checkpoint each decoder layer
    remat_policy: str = 'dots'             # 'full' | 'dots' (save matmul outs)
    sequence_parallel: bool = False        # shard seq over the 'sp' axis
    sp_mode: str = 'ring'                  # 'ring' | 'ulysses' attention
    # sliding-window (local) attention: each token attends its last
    # `sliding_window` positions (Mistral/Qwen2-style SWA). None = full
    # causal. Layers with index < max_window_layers keep FULL attention
    # (Qwen2's use_sliding_window/max_window_layers semantics).
    sliding_window: typing.Optional[int] = None
    max_window_layers: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama_7b() -> LlamaConfig:
    """Llama-2-7B pretrain config (headline benchmark shape)."""
    return LlamaConfig()


def llama_tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, kv_heads=2,
               intermediate_size=128, max_pos=128) -> LlamaConfig:
    """Tiny config for tests / dryruns."""
    return LlamaConfig(
        vocab_size=vocab_size, hidden_size=hidden_size,
        intermediate_size=intermediate_size, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        max_position_embeddings=max_pos,
    )


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def _llama3_scaled_inv_freq(inv_freq, scaling):
    """Llama-3.x rope scaling (ref: transformers
    modeling_rope_utils._compute_llama3_parameters): long wavelengths
    are slowed by `factor`, short ones kept, with a smooth ramp between
    the low/high frequency cutoffs."""
    factor = scaling['factor']
    low = scaling.get('low_freq_factor', 1.0)
    high = scaling.get('high_freq_factor', 4.0)
    orig = scaling.get('original_max_position_embeddings', 8192)
    wavelen = 2.0 * math.pi / inv_freq
    smooth = (orig / wavelen - low) / (high - low)
    interp = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    return jnp.where(wavelen < orig / high, inv_freq,
                     jnp.where(wavelen > orig / low, inv_freq / factor,
                               interp))


def _yarn_scaled_inv_freq(inv_freq, scaling, head_dim, theta):
    """YaRN rope scaling (ref: transformers
    modeling_rope_utils._compute_yarn_parameters): interpolated (long-
    wavelength) and extrapolated (short-wavelength) frequencies blended
    by a per-dimension linear ramp between the beta_fast/beta_slow
    correction dims. Returns (inv_freq, attention_factor) — the factor
    scales cos/sin (softmax temperature correction)."""
    factor = scaling['factor']
    beta_fast = scaling.get('beta_fast', 32.0)
    beta_slow = scaling.get('beta_slow', 1.0)
    # `or`: an explicit None (transformers accepts it) must not reach
    # math.log; model callers inject config.max_position_embeddings
    orig = scaling.get('original_max_position_embeddings') or 4096

    def get_mscale(scale, mscale=1.0):
        # transformers' guard: no temperature correction for scale <= 1
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    attention_factor = scaling.get('attention_factor')
    if attention_factor is None:
        mscale = scaling.get('mscale')
        mscale_all_dim = scaling.get('mscale_all_dim')
        if mscale and mscale_all_dim:
            # DeepSeek-style: the two mscales RATIO (transformers
            # _compute_yarn_parameters); mscale without mscale_all_dim is
            # ignored, matching transformers
            attention_factor = float(get_mscale(factor, mscale)
                                     / get_mscale(factor, mscale_all_dim))
        else:
            attention_factor = get_mscale(factor)

    def correction_dim(num_rotations):
        return (head_dim * math.log(orig / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001), 0.0, 1.0)
    extrapolation_factor = 1.0 - ramp
    inv_freq = (inv_freq / factor * (1 - extrapolation_factor)
                + inv_freq * extrapolation_factor)
    return inv_freq, float(attention_factor)


def rope_cos_sin(positions, head_dim, theta=10000.0, dtype=jnp.float32,
                 rope_scaling=None):
    """cos/sin tables for the given integer positions, shape (..., head_dim//2).

    rope_scaling: optional dict; rope_type 'llama3' applies the Llama-3.x
    frequency rescale, 'yarn' the YaRN interpolation (incl. the
    attention-temperature factor on cos/sin, matching transformers);
    other types are rejected at config time."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    att = 1.0
    if rope_scaling:
        rt = rope_scaling.get('rope_type', rope_scaling.get('type'))
        if rt == 'llama3':
            inv_freq = _llama3_scaled_inv_freq(inv_freq, rope_scaling)
        elif rt == 'yarn':
            inv_freq, att = _yarn_scaled_inv_freq(inv_freq, rope_scaling,
                                                  head_dim, theta)
        elif rt not in (None, 'default'):
            raise ValueError(f'unsupported rope_scaling type {rt!r}')
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., D/2)
    return ((jnp.cos(angles) * att).astype(dtype),
            (jnp.sin(angles) * att).astype(dtype))


def apply_rotary(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2). Rotate-half form."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]  # (B, S, 1, D/2)
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def masked_attention(q, k, v, mask, sink=None):
    """softmax(q kᵀ / sqrt(D)) v over the keys `mask` allows, grouped
    (each kv head's queries meet its keys unrepeated), products in the
    inputs' type with float32 accumulation. What `cached_attention`'s XLA
    path computes where `scaled_dot_product_attention` cannot: a V row
    of another width than a K row, and `sink` (H,), one logit a query
    head that joins the softmax as one more column and is dropped before
    the values are weighed. q (B, S, H, D), k (B, T, Hkv, D), v (B, T,
    Hkv, Dv), mask broadcastable to (B, H, S, T). Returns (B, S, H, Dv)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scores = jnp.einsum('bshgd,bthd->bhgst', q.reshape(B, S, Hkv, g, D), k,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
    mask = jnp.broadcast_to(mask, (B, H, S, T)).reshape(B, Hkv, g, S, T)
    scores = jnp.where(mask, scores, -1e30)
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, Hkv, g, 1, 1),
            (B, Hkv, g, S, 1))
        scores = jnp.concatenate([scores, col], axis=-1)
    p = jax.nn.softmax(scores, axis=-1)[..., :T]
    out = jnp.einsum('bhgst,bthd->bshgd', p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def _xla_attention(q, k, v, mask, sink):
    """The masked XLA path of `cached_attention`: the shared functional
    where it can compute this (no sink, V rows as wide as K rows), else
    `masked_attention`."""
    if sink is None and v.shape[-1] == q.shape[-1]:
        return F.scaled_dot_product_attention(q, k, v, attn_mask=mask)
    return masked_attention(q, k, v, mask, sink)


def cached_attention(q, k, v, cache, cache_index, kvalid=None,
                     kv_start=None, kv_write_pos=None, window=None,
                     block_tables=None, sink=None):
    """Shared KV-cached attention step (LlamaAttention, GPTAttention):
    write the S new rows at cache_index, attend over the full cache
    masked by position; single-token steps dispatch to the fused pallas
    decode kernel. `kvalid` (B, max_len) 0/1 marks cache rows that may
    be attended at all — left-padded batched generation puts 0 on the
    pad rows. `kv_start` (B,) asserts the caller's kvalid is exactly the
    contiguous window [kv_start, now] (left-pad hole at the front) —
    with it, single-token steps KEEP the fused kernel (per-row start via
    scalar prefetch) instead of falling back to the masked XLA path.
    `kv_write_pos` (B,) replaces the uniform cache_index with PER-ROW
    write offsets (batched speculative decoding: rows commit at
    different lengths); rows stay contiguous per row — position i of the
    chunk lands at kv_write_pos[b] + i, and attention masks by per-row
    position. `window` (int) applies sliding-window attention over the
    cache: only the last `window` positions are attended — on the fused
    decode path this is just a larger per-row start, so the kernel still
    streams only the live band. `sink` (H,) float32: one logit a query
    head in the softmax's denominator, with no value (a learned attention
    sink); it and a V row of another width than a K row take the XLA path
    (`masked_attention`) but on paged decode, where the paged kernel has
    both. Returns (out (B, S, H, Dv), new_cache).

    A QuantKVCache stores K/V int8 with per-(head, dim) scales: prefill
    (S > 1) calibrates the scales from its own rows, decode steps
    quantize against them; attention dequantizes (in-kernel on the
    pallas path, whole-cache on the XLA fallback).

    A PagedKVCache (with `block_tables` (B, MAXB) int32) is the
    continuous-batching serving layout: the new K/V row of batch row b
    lands in page block_tables[b, wp // BS] slot wp % BS (wp =
    kv_write_pos[b], required), and attention streams exactly the pages
    the row occupies — the fused pallas paged kernel on TPU
    (ops/pallas/paged_attention.py, block table scalar-prefetched into
    the BlockSpec index map), a gather reference elsewhere. Decode-only
    (S == 1); rows whose table entry is 0 write to the reserved scratch
    page (inference/serving.py parks inactive slots there)."""
    from .generation import (PagedKVCache, QuantKVCache,
                             QuantPagedKVCache, RowQuantKVCache,
                             calibrate_kv_scale, dequantize_kv_row,
                             quantize_kv_row, quantize_kv_rows)

    B, S, H, D = q.shape
    if isinstance(cache, (PagedKVCache, QuantPagedKVCache)):
        return _paged_cached_attention(q, k, v, cache, kv_write_pos,
                                       block_tables, window, kvalid,
                                       kv_start, sink)
    if kv_write_pos is not None:
        wp = jnp.reshape(jnp.asarray(kv_write_pos, jnp.int32), (-1,))
        wp = jnp.broadcast_to(wp, (B,))
        rows = jnp.arange(B)[:, None]
        wcols = wp[:, None] + jnp.arange(S)[None, :]

        def write(buf, new):
            return buf.at[rows, wcols].set(new.astype(buf.dtype))
    else:
        def write(buf, new):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (0, cache_index, 0, 0))
    rowquant = isinstance(cache, RowQuantKVCache)
    if rowquant:
        # per-row int8 (the serving engine's fused multi-token bodies):
        # rows quantize one at a time against their own amax — the
        # exact rule the QuantPagedKVCache pools apply — and the whole
        # cache dequantizes EAGERLY for attention, so every attended
        # value is the int8-roundtripped one a paged decode step would
        # see. That shared roundtrip is what keeps int8 serving streams
        # bit-equal across prefill / chunk / speculative / decode paths.
        kq, vq, ks, vs = cache
        knew, ks_new = quantize_kv_row(k)
        vnew, vs_new = quantize_kv_row(v)
        kq = write(kq, knew)
        vq = write(vq, vnew)
        if kv_write_pos is not None:
            ks = ks.at[rows, wcols].set(ks_new)
            vs = vs.at[rows, wcols].set(vs_new)
        else:
            ks = jax.lax.dynamic_update_slice(ks, ks_new,
                                              (0, cache_index, 0))
            vs = jax.lax.dynamic_update_slice(vs, vs_new,
                                              (0, cache_index, 0))
        new_cache = RowQuantKVCache(kq, vq, ks, vs)
        ck = dequantize_kv_row(kq, ks, q.dtype)
        cv = dequantize_kv_row(vq, vs, q.dtype)
    quant = isinstance(cache, QuantKVCache)
    if quant:
        kq, vq, kscale, vscale = cache
        # calibrate ONLY on the index-0 prefill: a later multi-token
        # chunk (chunked prefill, speculative verify) must keep the
        # existing scales — recalibrating would reinterpret every int8
        # row already in the cache under new scales. cache_index is a
        # concrete 0 at prefill in all generation loops; traced indices
        # are by construction later steps.
        is_prefill = (S > 1 and kv_write_pos is None
                      and not isinstance(cache_index, jax.core.Tracer)
                      and int(cache_index) == 0)
        if is_prefill:
            kscale = calibrate_kv_scale(k)
            vscale = calibrate_kv_scale(v)
        kq = write(kq, quantize_kv_rows(k, kscale))
        vq = write(vq, quantize_kv_rows(v, vscale))
        new_cache = QuantKVCache(kq, vq, kscale, vscale)
        ck, cv = kq, vq
    elif not rowquant:                 # rowquant set ck/cv above
        ck, cv = cache
        ck = write(ck, k)
        cv = write(cv, v)
        new_cache = (ck, cv)
    max_len = ck.shape[1]
    out = None
    plain = sink is None and v.shape[-1] == D
    if (S == 1 and D % 8 == 0 and plain
            and (kvalid is None or kv_start is not None)):
        from ..ops import use_pallas

        if use_pallas():
            # fused single-token decode: one streaming pass over the
            # cache, routed through the serving dispatcher
            # (ops/pallas/decode_attention.py — the same entry point the
            # DecodeEngine decode loop reaches). Under a mesh each shard
            # runs its own kernel, placed as init_cache places the cache
            # (batch over dp/fsdp, heads over tp), so a sharded cache is
            # NOT all-gathered every decode step
            from jax.sharding import PartitionSpec as P

            from ..ops import DATA_AXES, head_axis, mesh_kernel
            from ..ops.pallas.decode_attention import (
                dispatch_decode_attention)

            def kernel(q_, k_, v_, vl_, st_, *scales):
                return dispatch_decode_attention(
                    q_, k_, v_, vl_, start=st_, window=window,
                    k_scale=scales[0] if scales else None,
                    v_scale=scales[1] if scales else None)

            vl = jnp.broadcast_to(jnp.asarray(
                wp + 1 if kv_write_pos is not None else cache_index + 1,
                jnp.int32), (B,))
            st = jnp.broadcast_to(jnp.asarray(
                0 if kv_start is None else kv_start, jnp.int32), (B,))
            heads = head_axis(H, ck.shape[2])
            hspec = P(DATA_AXES, None, heads, None)
            scales = (kscale, vscale) if quant else ()
            out = mesh_kernel(
                kernel, (q, ck, cv, vl, st) + scales,
                (hspec, hspec, hspec, P(DATA_AXES), P(DATA_AXES))
                + (P(heads, None),) * len(scales))
    if out is None:
        # valid keys: position <= current query position (& kvalid)
        kpos = jnp.arange(max_len)
        if kv_write_pos is not None:
            # per-row query positions (batched speculative verify)
            qpos = wp[:, None] + jnp.arange(S)[None, :]        # (B, S)
            mask = (kpos[None, None, None, :] <= qpos[:, None, :, None])
        else:
            qpos = cache_index + jnp.arange(S)
            mask = (kpos[None, :] <= qpos[:, None])[None, None]
        if kvalid is not None:
            mask = mask & (kvalid[:, None, None, :] > 0)
        if kv_start is not None:
            # honor the window start here too: a caller passing only
            # kv_start must see the same window whether or not the
            # fused kernel ran
            st = jnp.reshape(jnp.asarray(kv_start, jnp.int32), (-1,))
            mask = mask & (kpos[None, :] >= st[:, None])[:, None, None, :]
        if window is not None:
            # sliding window: qpos - kpos < window (qpos is (S,) uniform
            # or (B, S) per-row; both broadcast against kpos)
            if qpos.ndim == 2:
                band = (qpos[:, :, None] - kpos[None, None, :]
                        < window)[:, None]
            else:
                band = (qpos[:, None] - kpos[None, :] < window)[None, None]
            mask = mask & band
        if quant:
            # XLA fallback: whole-cache dequant (correctness path; the
            # bandwidth win lives in the pallas kernel)
            ck = (ck.astype(jnp.float32) * kscale[None, None]).astype(q.dtype)
            cv = (cv.astype(jnp.float32) * vscale[None, None]).astype(q.dtype)
        out = _xla_attention(q, ck, cv, mask, sink)
    return out, new_cache


def _paged_cached_attention(q, k, v, cache, kv_write_pos, block_tables,
                            window, kvalid, kv_start, sink=None):
    """Single-token decode over a PagedKVCache: scatter the new row
    into its page, then attend over the row's pages masked by the
    per-row valid length (kv_write_pos + 1) and, with `window`, to its
    last `window` positions (the kernel skips the pages behind them;
    whether they stay allocated is the table's owner's affair: their
    entries are never read). See cached_attention."""
    B, S, H, D = q.shape
    if kvalid is not None or kv_start is not None:
        # these are masking CONTRACTS on the other branches — dropping
        # them silently would attend pad rows; paged serving right-pads
        # at prefill so neither is ever needed (positions [0, wp) are
        # always exactly the live tokens)
        raise NotImplementedError(
            'kvalid/kv_start are not supported with a PagedKVCache: '
            'paged prefill is right-padded, so the valid window is '
            'always [0, kv_write_pos) with no pad hole to mask')
    if S != 1:
        raise NotImplementedError(
            'PagedKVCache is decode-only (S == 1): prefill scatters '
            'whole prompts into pages via '
            'inference.serving._paged_prefill, and speculative windows '
            'are not paged yet')
    if kv_write_pos is None or block_tables is None:
        raise ValueError(
            'PagedKVCache needs kv_write_pos (per-row write positions) '
            'and block_tables (per-row page ids)')
    from .generation import (QuantPagedKVCache, dequantize_kv_row,
                             pad_lanes, pool_rows_set, quantize_kv_row)

    quant = isinstance(cache, QuantPagedKVCache)
    if quant:
        kp, vp, kss, vss = cache
    else:
        kp, vp = cache
    NB, Hkv, BS, Dp = kp.shape
    Dv, Dvp = v.shape[-1], vp.shape[-1]

    def cut(x, to):
        return x if x.shape[-1] == to else x[..., :to]

    tbl = jnp.asarray(block_tables, jnp.int32)
    maxb = tbl.shape[1]
    wp = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(kv_write_pos, jnp.int32), (-1,)), (B,))
    rows = jnp.arange(B)
    # frozen rows can sit one position past their last allocated page:
    # clamp the COLUMN (the scheduler parks such rows on table entry 0,
    # the scratch page, so the clamped write stays harmless)
    page = tbl[rows, jnp.minimum(wp // BS, maxb - 1)]
    slot = wp % BS
    if quant:
        # per-row int8: the new row quantizes against its own amax (the
        # same pure-function rule the serving prefill scatter applies),
        # so this row's int8 bytes are identical whether it was written
        # here or by a re-prefill after preemption
        kq, ksr = quantize_kv_row(k[:, 0])       # (B, Hkv, D), (B, Hkv)
        vq, vsr = quantize_kv_row(v[:, 0])
        kp = kp.at[page, :, slot, :].set(kq)
        vp = vp.at[page, :, slot, :].set(vq)
        kss = kss.at[page, :, slot].set(ksr)
        vss = vss.at[page, :, slot].set(vsr)
        new_cache = QuantPagedKVCache(kp, vp, kss, vss)
    else:
        kp = pool_rows_set(kp, page, slot,
                           pad_lanes(k[:, 0], Dp).astype(kp.dtype))
        vp = pool_rows_set(vp, page, slot,
                           pad_lanes(v[:, 0], Dvp).astype(vp.dtype))
        new_cache = PagedKVCache(kp, vp)
    counts = wp + 1
    out = None
    if D % 8 == 0:
        from ..ops import use_pallas

        if use_pallas():
            from jax.sharding import PartitionSpec as P

            from ..ops import DATA_AXES, head_axis, mesh_kernel
            from ..ops.pallas.paged_attention import (
                paged_decode_attention)

            def kernel(q_, kp_, vp_, tbl_, counts_, *scales):
                # q's zero lanes meet the pool's: the scores are the
                # rows' own, scaled by their own width
                return cut(paged_decode_attention(
                    pad_lanes(q_, Dp), kp_, vp_, tbl_, counts_,
                    scale=1.0 / (D ** 0.5),
                    k_scale=scales[0] if scales else None,
                    v_scale=scales[1] if scales else None, window=window,
                    sink=sink), Dv)

            # pools split their kv-head dim over tp (init_paged_cache's
            # placement); tables and lengths follow the batch
            heads = head_axis(H, Hkv)
            pool = P(None, heads, None, None)
            scales = (kss, vss) if quant else ()
            out = mesh_kernel(
                kernel, (q, kp, vp, tbl, counts) + scales,
                (P(DATA_AXES, None, heads, None), pool, pool,
                 P(DATA_AXES, None), P(DATA_AXES))
                + (P(None, heads, None),) * len(scales))
    if out is None:
        # gather reference (CPU tests / non-TPU): pages -> a contiguous
        # (B, MAXB*BS, Hkv, D) view, masked by per-row valid length;
        # int8 pools dequantize with the shared per-row expression
        gk, gv = kp[tbl], vp[tbl]                # (B, maxb, Hkv, BS, D)
        if quant:
            gk = dequantize_kv_row(gk, kss[tbl], q.dtype)
            gv = dequantize_kv_row(gv, vss[tbl], q.dtype)
        ck = cut(jnp.swapaxes(gk, 2, 3).reshape(B, maxb * BS, Hkv, Dp), D)
        cv = cut(jnp.swapaxes(gv, 2, 3).reshape(B, maxb * BS, Hkv, Dvp), Dv)
        kpos = jnp.arange(maxb * BS)[None, :]
        mask = kpos < counts[:, None]
        if window is not None:
            mask = mask & (kpos >= counts[:, None] - window)
        out = _xla_attention(q, ck, cv, mask[:, None, None, :], sink)
    return out, new_cache


class LlamaAttention(Layer):
    """GQA attention with RoPE. Column-parallel QKV, row-parallel output."""

    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        # Qwen2 semantics: SWA only on layers >= max_window_layers
        self.sliding_window = (
            config.sliding_window
            if (config.sliding_window is not None
                and layer_idx >= config.max_window_layers) else None)
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.rope_theta = config.rope_theta
        rs = config.rope_scaling
        if (rs and rs.get('rope_type', rs.get('type')) == 'yarn'
                and rs.get('original_max_position_embeddings') is None):
            # transformers falls back to config.max_position_embeddings
            # for the yarn correction ramp — a 4096 guess here would
            # silently skew every frequency
            rs = dict(rs, original_max_position_embeddings=config
                      .max_position_embeddings)
        self.rope_scaling = rs
        self.sequence_parallel = config.sequence_parallel
        if self.sequence_parallel and self.sliding_window is not None:
            import warnings

            warnings.warn(
                'sliding_window disables the ring/ulysses sequence-'
                'parallel attention path (the ring schedule has no '
                'window fast path yet); attention falls back to the '
                'flash kernel on sp-sharded activations, which GSPMD '
                'reshards — expect a perf cliff, not wrong results',
                stacklevel=3)
        if config.sp_mode not in ('ring', 'ulysses'):
            raise ValueError(
                f"sp_mode must be 'ring' or 'ulysses', got "
                f'{config.sp_mode!r}')
        self.sp_mode = config.sp_mode
        init = I.Normal(0.0, config.initializer_range)
        h, d = config.hidden_size, self.head_dim
        self.q_proj = Parameter(init((h, self.num_heads * d), config.dtype), spec=P(None, 'tp'))
        self.k_proj = Parameter(init((h, self.num_kv_heads * d), config.dtype), spec=P(None, 'tp'))
        self.v_proj = Parameter(init((h, self.num_kv_heads * d), config.dtype), spec=P(None, 'tp'))
        self.o_proj = Parameter(init((self.num_heads * d, h), config.dtype), spec=P('tp', None))
        if config.attention_bias:          # Qwen2-style qkv biases
            zeros = lambda n: jnp.zeros((n,), jnp.dtype(config.dtype))
            self.q_bias = Parameter(zeros(self.num_heads * d), spec=P('tp'))
            self.k_bias = Parameter(zeros(self.num_kv_heads * d), spec=P('tp'))
            self.v_bias = Parameter(zeros(self.num_kv_heads * d), spec=P('tp'))
        else:
            self.q_bias = self.k_bias = self.v_bias = None

    def forward(self, x, positions, attn_mask=None, cache=None,
                cache_index=None, kvalid=None, kv_start=None,
                kv_write_pos=None, block_tables=None):
        """x: (B, S, H). cache: optional (k, v) of (B, max_len, Hkv, D).

        Returns (out, new_cache). With a cache, writes the S new kv rows at
        cache_index and attends over the full cache (masked by position;
        `kvalid` additionally invalidates rows — left-pad support).
        """
        B, S, _ = x.shape
        q, k, v = x @ self.q_proj, x @ self.k_proj, x @ self.v_proj
        if self.q_bias is not None:
            q, k, v = q + self.q_bias, k + self.k_bias, v + self.v_bias
        q = q.reshape(B, S, self.num_heads, self.head_dim)
        k = k.reshape(B, S, self.num_kv_heads, self.head_dim)
        v = v.reshape(B, S, self.num_kv_heads, self.head_dim)

        cos, sin = rope_cos_sin(positions, self.head_dim, self.rope_theta,
                                rope_scaling=self.rope_scaling)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

        if cache is None:
            win = self.sliding_window
            if kvalid is not None or (win is not None
                                      and attn_mask is not None):
                # honor pad-invalidation (and the SWA band when a user
                # mask blocks the kernel path) on the uncached path too:
                # fold into an explicit causal mask (silently ignoring
                # kvalid would let real tokens attend to pads)
                extra_mask = (jnp.arange(S)[None, :]
                              <= jnp.arange(S)[:, None])[None, None]
                if kvalid is not None:
                    extra_mask = extra_mask & (
                        kvalid[:, :S] > 0)[:, None, None, :]
                if win is not None:
                    extra_mask = extra_mask & (
                        jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
                        < win)[None, None]
                    win = None          # folded; don't pass to sdpa too
                if attn_mask is None:
                    attn_mask = extra_mask
                elif attn_mask.dtype == jnp.bool_:
                    attn_mask = attn_mask & extra_mask
                else:                  # additive float mask (see attention.py)
                    attn_mask = attn_mask + jnp.where(
                        extra_mask, 0.0, -1e30).astype(attn_mask.dtype)
            out = None
            if (self.sequence_parallel and attn_mask is None
                    and win is None):
                from ..distributed.mesh import get_mesh

                mesh = get_mesh()
                if (mesh is not None and 'sp' in mesh.axis_names
                        and mesh.shape['sp'] > 1
                        and S % mesh.shape['sp'] == 0):
                    n_sp = mesh.shape['sp']
                    use_ulysses = self.sp_mode == 'ulysses'
                    if use_ulysses and (self.num_heads % n_sp
                                        or self.num_kv_heads % n_sp):
                        import warnings

                        warnings.warn(
                            f'sp_mode=ulysses needs heads divisible by the '
                            f'sp axis ({self.num_heads}/{self.num_kv_heads} '
                            f'heads vs sp={n_sp}); falling back to ring '
                            f'attention', stacklevel=2)
                        use_ulysses = False
                    if use_ulysses:
                        # all-to-all swaps the shard dim seq->heads; each
                        # rank runs full-seq flash for its head slice
                        from ..distributed.ulysses import (
                            ulysses_attention_sharded)

                        out = ulysses_attention_sharded(
                            q, k, v, mesh, axis='sp', causal=True)
                    else:
                        # KV blocks ring around the ICI via ppermute —
                        # no device ever holds the full KV
                        from ..distributed.ring_attention import (
                            ring_attention_sharded)

                        out = ring_attention_sharded(q, k, v, mesh,
                                                     axis='sp', causal=True)
            if out is None:
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
                    window_size=win)
            new_cache = None
        else:
            out, new_cache = cached_attention(q, k, v, cache, cache_index,
                                              kvalid=kvalid,
                                              kv_start=kv_start,
                                              kv_write_pos=kv_write_pos,
                                              window=self.sliding_window,
                                              block_tables=block_tables)

        out = out.reshape(B, S, self.num_heads * self.head_dim)
        return out @ self.o_proj, new_cache


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)). Column gate/up, row down."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = Parameter(init((h, m), config.dtype), spec=P(None, 'tp'))
        self.up_proj = Parameter(init((h, m), config.dtype), spec=P(None, 'tp'))
        self.down_proj = Parameter(init((m, h), config.dtype), spec=P('tp', None))

    def forward(self, x):
        return (F.silu(x @ self.gate_proj) * (x @ self.up_proj)) @ self.down_proj


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, positions, attn_mask=None, cache=None,
                cache_index=None, kvalid=None, kv_start=None,
                kv_write_pos=None, block_tables=None):
        attn_out, new_cache = self.self_attn(
            self.input_layernorm(x), positions, attn_mask, cache,
            cache_index, kvalid, kv_start, kv_write_pos,
            block_tables=block_tables
        )
        x = x + attn_out
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, new_cache


class LlamaModel(Layer):
    """Embedding + decoder stack + final norm."""

    # vocab table is gathered (and .T-served when tied) — exempt from
    # weight-only PTQ (quantization.quantize_matmul_weights)
    no_quantize = ('embed_tokens',)

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embed_tokens = Parameter(
            init((config.vocab_size, config.hidden_size), config.dtype), spec=P('tp', None)
        )
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)]
        )
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, positions=None, attn_mask=None, caches=None,
                cache_index=None, kvalid=None, kv_start=None,
                kv_write_pos=None, block_tables=None):
        B, S = input_ids.shape
        if positions is None:
            from .generation import default_positions

            positions = default_positions(B, S, cache_index, kv_write_pos)
        # mesh-aware lookup: one_hot matmul under a sharded mesh so the
        # (tp, fsdp) table sharding doesn't force an activation remat
        # (see distributed.embedding_lookup)
        from ..distributed import embedding_lookup
        x = embedding_lookup(self.embed_tokens, input_ids)
        new_caches = [] if caches is not None else None
        use_remat = self.config.remat and caches is None
        for i, layer in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            if use_remat:
                # 'dots': keep matmul outputs, recompute elementwise — far
                # cheaper recompute than full remat at slightly more HBM
                policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                          if self.config.remat_policy == 'dots' else None)
                x = jax.checkpoint(
                    lambda lyr, h: lyr(h, positions, attn_mask,
                                       kvalid=kvalid)[0],
                    policy=policy,
                )(layer, x)
                nc = None
            else:
                x, nc = layer(x, positions, attn_mask, cache, cache_index,
                              kvalid, kv_start, kv_write_pos,
                              block_tables=block_tables)
            if new_caches is not None:
                new_caches.append(nc)
        return self.norm(x), new_caches


class LlamaForCausalLM(GenerationMixin, Layer):
    """LM head on top; loss = causal cross-entropy (shifted); generation
    (greedy/sampled/beam) via models/generation.py::GenerationMixin."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            init = I.Normal(0.0, config.initializer_range)
            self.lm_head = Parameter(
                init((config.hidden_size, config.vocab_size), config.dtype),
                spec=P(None, 'tp'),
            )

    def logits(self, hidden):
        if self.lm_head is None:
            return hidden @ self.model.embed_tokens.T
        return hidden @ self.lm_head

    def forward(self, input_ids, positions=None, attn_mask=None, caches=None,
                cache_index=None, kvalid=None, kv_start=None,
                kv_write_pos=None, block_tables=None):
        hidden, new_caches = self.model(input_ids, positions, attn_mask, caches,
                                        cache_index, kvalid, kv_start,
                                        kv_write_pos, block_tables)
        logits = self.logits(hidden)
        if caches is None:
            return logits
        return logits, new_caches

    def loss(self, input_ids, labels=None):
        """Next-token cross-entropy (fused pallas softmax-xent on TPU)."""
        from ..ops import softmax_cross_entropy

        if labels is None:
            labels = input_ids[:, 1:]
            input_ids = input_ids[:, :-1]
        logits = self(input_ids)
        return softmax_cross_entropy(logits, labels).mean()


    # -- generation (loops from GenerationMixin) ---------------------------
    def cache_dtype(self):
        return self.model.embed_tokens.dtype



# ---------------------------------------------------------------------------
# TP sharding rules (consumed by distributed.parallelize)
# ---------------------------------------------------------------------------

LLAMA_TP_RULES: typing.List[typing.Tuple[str, typing.Any]] = [
    (r'.*embed_tokens$', P('tp', None)),
    (r'.*(q|k|v)_proj$', P(None, 'tp')),
    (r'.*o_proj$', P('tp', None)),
    (r'.*(gate|up)_proj$', P(None, 'tp')),
    (r'.*down_proj$', P('tp', None)),
    (r'.*lm_head$', P(None, 'tp')),
]
