"""Flash attention (pallas, TPU) — fwd + custom-VJP bwd.

ref (capability): the reference's flash_attention op
(python/paddle/nn/functional/flash_attention.py → CUDA flash-attn
kernels). This is a from-scratch TPU kernel: online-softmax tiling over
(q-block × k-block) grid steps, fp32 accumulators in VMEM scratch,
MXU-shaped (128×128) tiles, causal masking, GQA via head-index mapping.

Layout: (B, S, H, D) in/out (Paddle's flash layout); kernels run on
(B, H, S, D) transposed views.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 1024x1024 measured 8.5x faster than 128x128 on v5e (59.9 vs 7.0 TF/s
# effective): the grid collapses from ~49k tiny steps to ~770, amortising
# per-step overhead; VMEM use stays ~6.5MB
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


def _interpret():
    from . import interpret_mode

    return interpret_mode()


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *refs,
                scale, causal, bq, bk, nk, offset, Sq, Sk, has_seg=False,
                window=None):
    if has_seg:
        qseg_ref, kseg_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
    else:
        o_ref, lse_ref, acc, m_scr, l_scr = refs
    ik = pl.program_id(3)
    iq = pl.program_id(2)
    k_tail = Sk % bk != 0                               # static

    @pl.when(ik == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # causal block skip: whole q block above the diagonal → contributes 0
    live = (iq * bq + (bq - 1) + offset >= ik * bk) if causal else True
    if window is not None:
        # sliding-window block skip: a k block wholly BEFORE every query's
        # window start (qpos + offset - w < kpos) is dead — same static
        # machinery as the causal skip, mirrored to the other side
        live = live & (ik * bk + (bk - 1) > iq * bq + offset - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if k_tail:
            # padded key rows read unspecified memory; zero v so the
            # (masked-to-zero-prob) tail can't inject inf/nan into acc
            vrow = ik * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(vrow < Sk, v, 0.0)

        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        ok = None
        if causal or k_tail or has_seg or window is not None:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            # bottom-right causal (matches _sdpa_reference tril k=Sk-Sq),
            # merged with the key-tail validity and segment masks
            ok = (qpos + offset >= kpos) if causal else \
                jnp.ones((bq, bk), bool)
            if window is not None:
                # attend only the last `window` positions (incl. self)
                ok = ok & (qpos + offset - kpos < window)
            if k_tail:
                ok = ok & (kpos < Sk)
            if has_seg:
                ok = ok & (qseg_ref[0][:, None] == kseg_ref[0][None, :])
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:, 0]                             # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if ok is not None:
            # a fully-masked row has m_new == NEG_INF and exp(0) == 1
            # everywhere — force those probabilities to the true 0 so
            # empty-segment queries return 0 and leak no gradient
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc[:] = acc[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[:, 0]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc[:] / safe[:, None]).astype(o_ref.dtype)
        # lse stored (B,H,1,Sq): Sq on the lane dim — a (B,H,Sq,1) layout
        # pads the trailing 1 to 128 lanes in HBM (128x expansion, ~190MB
        # at 7B bench shapes)
        lse_ref[0, 0, 0] = m_scr[:, 0] + jnp.log(safe)


def _fwd(q, k, v, scale, causal, bq, bk, qseg=None, kseg=None,
         window=None):
    """q: (B, H, Sq, D); k/v: (B, Hkv, Sk, D) → (out, lse).

    qseg/kseg: optional (B, Sq)/(B, Sk) int32 segment ids — tokens only
    attend within equal ids (packed-sequence block-diagonal mask).
    """
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Sk, bk)
    has_seg = qseg is not None

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, offset=Sk - Sq,
                               Sq=Sq, Sk=Sk, has_seg=has_seg, window=window)
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)),
    ]
    operands = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bq), lambda b, h, i, j: (b, i)),
            pl.BlockSpec((1, bk), lambda b, h, i, j: (b, j)),
        ]
        operands += [jnp.asarray(qseg, jnp.int32),
                     jnp.asarray(kseg, jnp.int32)]
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=_interpret(), name='flash_attention_fwd',
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale, causal, bq, bk, nk, offset, Sq, Sk,
                   has_seg=False, window=None):
    if has_seg:
        qseg_ref, kseg_ref, dq_ref, dq_acc = refs
    else:
        dq_ref, dq_acc = refs
    ik = pl.program_id(3)
    iq = pl.program_id(2)
    k_tail = Sk % bk != 0                                # static

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    # causal block skip (same as fwd): fully-masked blocks contribute 0
    live = (iq * bq + (bq - 1) + offset >= ik * bk) if causal else True
    if window is not None:
        live = live & (ik * bk + (bk - 1) > iq * bq + offset - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]                           # (bq,)
        delta = delta_ref[0, 0, 0]                       # (bq,)
        if k_tail:
            krow = ik * bk + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
            k = jnp.where(krow < Sk, k, 0.0)
            v = jnp.where(krow < Sk, v, 0.0)

        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kvalid = True
        if causal or k_tail or has_seg or window is not None:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            # bottom-right causal (matches _sdpa_reference tril k=Sk-Sq)
            ok = (qpos + offset >= kpos) if causal else \
                jnp.ones((bq, bk), bool)
            if window is not None:
                ok = ok & (qpos + offset - kpos < window)
            if k_tail:
                kvalid = kpos < Sk
                ok = ok & kvalid
            if has_seg:
                ok = ok & (qseg_ref[0][:, None] == kseg_ref[0][None, :])
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        if causal or k_tail or has_seg or window is not None:
            # empty-segment rows: lse ≈ NEG_INF makes exp(s - lse) = 1
            p = jnp.where(ok, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        if k_tail:
            ds = jnp.where(kvalid, ds, 0.0)
        dq_acc[:] = dq_acc[:] + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, scale, causal, bq, bk, nq,
                    offset, Sq, Sk, has_seg=False, window=None):
    if has_seg:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    iq = pl.program_id(3)
    ik = pl.program_id(2)
    q_tail = Sq % bq != 0                                # static

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal block skip (same as fwd): fully-masked blocks contribute 0
    live = (iq * bq + (bq - 1) + offset >= ik * bk) if causal else True
    if window is not None:
        live = live & (ik * bk + (bk - 1) > iq * bq + offset - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]                           # (bq,)
        delta = delta_ref[0, 0, 0]                       # (bq,)
        qvalid = True
        if q_tail:
            # padded query rows read unspecified q/do/lse/delta — they
            # would contaminate the dk/dv sums over the query axis. Zero
            # the loads and (below) the p/ds rows.
            qrow = iq * bq + jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
            q = jnp.where(qrow < Sq, q, 0.0)
            do = jnp.where(qrow < Sq, do, 0.0)
            qvalid = iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) < Sq

        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if causal or has_seg or window is not None:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            # bottom-right causal (matches _sdpa_reference tril k=Sk-Sq)
            ok = (qpos + offset >= kpos) if causal else \
                jnp.ones((bq, bk), bool)
            if window is not None:
                ok = ok & (qpos + offset - kpos < window)
            if has_seg:
                ok = ok & (qseg_ref[0][:, None] == kseg_ref[0][None, :])
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if causal or has_seg or window is not None:
            # empty-segment rows: lse ≈ NEG_INF makes exp(s - lse) = 1
            p = jnp.where(ok, p, 0.0)
        if q_tail:
            p = jnp.where(qvalid, p, 0.0)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        if q_tail:
            ds = jnp.where(qvalid, ds, 0.0)
        dk_acc[:] = dk_acc[:] + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, bq, bk, res, g, qseg=None, kseg=None,
         window=None):
    q, k, v, out, lse = res
    do, _ = g
    has_seg = qseg is not None
    seg_ops = ([jnp.asarray(qseg, jnp.int32), jnp.asarray(kseg, jnp.int32)]
               if has_seg else [])
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    bq_ = min(bq, Sq)
    bk_ = min(bk, Sk)
    nq, nk = pl.cdiv(Sq, bq_), pl.cdiv(Sk, bk_)

    # (B, H, 1, Sq): Sq on the lane dim to avoid 128x HBM padding
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    dq_in_specs = [
        pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk_, D), lambda b, h, i, j: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bk_, D), lambda b, h, i, j: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, 1, bq_), lambda b, h, i, j: (b, h, 0, i)),
        pl.BlockSpec((1, 1, 1, bq_), lambda b, h, i, j: (b, h, 0, i)),
    ]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, bq_), lambda b, h, i, j: (b, i)),
            pl.BlockSpec((1, bk_), lambda b, h, i, j: (b, j)),
        ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq_, bk=bk_, nk=nk, offset=Sk - Sq,
                          Sq=Sq, Sk=Sk, has_seg=has_seg, window=window),
        grid=(B, H, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_, D), jnp.float32)],
        interpret=_interpret(), name='flash_attention_dq',
    )(q, k, v, do, lse, delta, *seg_ops)

    # per-q-head dk/dv, then reduce GQA groups
    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq_, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk_, D), lambda b, h, j, i: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bk_, D), lambda b, h, j, i: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bq_, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, 1, bq_), lambda b, h, j, i: (b, h, 0, i)),
        pl.BlockSpec((1, 1, 1, bq_), lambda b, h, j, i: (b, h, 0, i)),
    ]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, bq_), lambda b, h, j, i: (b, i)),
            pl.BlockSpec((1, bk_), lambda b, h, j, i: (b, j)),
        ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq_, bk=bk_, nq=nq, offset=Sk - Sq,
                          Sq=Sq, Sk=Sk, has_seg=has_seg, window=window),
        grid=(B, H, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk_, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk_, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_, D), jnp.float32),
            pltpu.VMEM((bk_, D), jnp.float32),
        ],
        interpret=_interpret(), name='flash_attention_dkv',
    )(q, k, v, do, lse, delta, *seg_ops)

    if group > 1:
        dk = dk.reshape(B, Hkv, group, Sk, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, group, Sk, D).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, bq, bk, window):
    out, _ = _fwd(q, k, v, scale, causal, bq, bk, window=window)
    return out


def _flash_fwd(q, k, v, scale, causal, bq, bk, window):
    out, lse = _fwd(q, k, v, scale, causal, bq, bk, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, bq, bk, window, res, g):
    return _bwd(scale, causal, bq, bk, res, (g, None), window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_seg(q, k, v, qseg, kseg, scale, causal, bq, bk, window):
    out, _ = _fwd(q, k, v, scale, causal, bq, bk, qseg, kseg, window=window)
    return out


def _flash_seg_fwd(q, k, v, qseg, kseg, scale, causal, bq, bk, window):
    out, lse = _fwd(q, k, v, scale, causal, bq, bk, qseg, kseg,
                    window=window)
    return out, (q, k, v, out, lse, qseg, kseg)


def _flash_seg_bwd(scale, causal, bq, bk, window, res, g):
    q, k, v, out, lse, qseg, kseg = res
    dq, dk, dv = _bwd(scale, causal, bq, bk, (q, k, v, out, lse),
                      (g, None), qseg, kseg, window=window)
    return dq, dk, dv, None, None


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    segment_ids=None, kv_segment_ids=None, window_size=None):
    """q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D). Returns (B, Sq, H, D).

    segment_ids/(kv_segment_ids): optional (B, Sq)/(B, Sk) int32 packed-
    sequence ids — attention is block-diagonal within equal ids (tokens
    of different packed documents never attend to each other). With
    causal=True both masks compose. A query whose segment has no kv
    tokens returns 0 for that row.

    window_size: optional int — sliding-window (local) attention: each
    query attends only the last `window_size` keys including itself
    (ref: python/paddle/nn/functional/flash_attention.py:1106 —
    flash_attention's window_size). Requires causal=True; k blocks
    wholly outside the band are SKIPPED (same grid machinery as the
    causal skip), so long-sequence SWA costs O(S·w) not O(S²).
    """
    if window_size is not None:
        window_size = int(window_size)
        if not causal:
            raise ValueError(
                'window_size requires causal=True (decoder sliding-window '
                'attention); use an explicit mask for bidirectional bands')
        if window_size < 1:
            raise ValueError(f'window_size must be >= 1, got {window_size}')
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        out = _flash_seg(qt, kt, vt, jnp.asarray(segment_ids, jnp.int32),
                         jnp.asarray(kv_seg, jnp.int32),
                         float(scale), bool(causal), block_q, block_k,
                         window_size)
    else:
        out = _flash(qt, kt, vt, float(scale), bool(causal), block_q,
                     block_k, window_size)
    return jnp.swapaxes(out, 1, 2)
