"""Fused softmax cross-entropy (pallas, TPU) with custom VJP.

ref (capability): the reference's c_softmax_with_cross_entropy /
softmax_with_cross_entropy fused kernels (paddle/phi/kernels/gpu/
c_softmax_with_cross_entropy_kernel.cu). One pass over the vocab per
row computes max / sum-exp / label logit together (no materialised
softmax); backward streams softmax-minus-onehot directly.

For a 'tp'-sharded vocab use `distributed.parallel_cross_entropy`
(GSPMD inserts the cross-shard max/sum); this kernel is the
single-shard fast path.
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


def _fwd_kernel(x_ref, label_ref, loss_ref, lse_ref, m_scr, l_scr, p_scr, *,
                bv, nv, V):
    """grid (row_blocks, vocab_blocks); scratch persists across vocab steps."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        p_scr[:] = jnp.full_like(p_scr, NEG_INF)

    x = x_ref[:].astype(jnp.float32)                    # (br, bv)
    label = label_ref[:, 0]                             # (br,)
    br = x.shape[0]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (br, x.shape[1]), 1)
    if V % bv:
        # tail vocab block: the padded columns read unspecified memory —
        # mask them out of the running max / sum-exp
        x = jnp.where(cols < V, x, NEG_INF)
    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, 0] * alpha + jnp.sum(jnp.exp(x - m_new[:, None]), axis=-1)
    m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    # pick this block's label logit if the label falls in [j*bv, (j+1)*bv)
    hit = cols == label[:, None]
    picked = jnp.max(jnp.where(hit, x, NEG_INF), axis=-1)
    p_scr[:] = jnp.maximum(p_scr[:], jnp.broadcast_to(picked[:, None],
                                                      p_scr.shape))

    @pl.when(j == nv - 1)
    def _():
        lse = m_scr[:, 0] + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30))
        loss_ref[:, 0] = lse - p_scr[:, 0]
        lse_ref[:, 0] = lse


def _bwd_kernel(x_ref, label_ref, lse_ref, g_ref, dx_ref, *, bv, V):
    j = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)
    label = label_ref[:, 0]
    lse = lse_ref[:, 0]
    g = g_ref[:, 0]
    br = x.shape[0]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (br, x.shape[1]), 1)
    p = jnp.exp(x - lse[:, None])                       # softmax block
    if V % bv:
        # tail block: exp(garbage) can be inf/nan — force dx=0 off-vocab
        p = jnp.where(cols < V, p, 0.0)
    onehot = (cols == label[:, None]).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * g[:, None]).astype(dx_ref.dtype)


def _block_sizes(R, V):
    bv = min(V, 2048)
    br = max(8, min(256, (1 << 21) // max(4 * bv, 1)))
    return min(br, R), bv


def _run_fwd(x2, labels):
    R, V = x2.shape
    br, bv = _block_sizes(R, V)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, nv=pl.cdiv(V, bv), V=V),
        grid=(pl.cdiv(R, br), pl.cdiv(V, bv)),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
        ],
        interpret=_interpret(), name='softmax_xent_fwd',
    )(x2, labels[:, None])
    return loss[:, 0], lse[:, 0]


@jax.custom_vjp
def _xent2d(x2, labels):
    loss, _ = _run_fwd(x2, labels)
    return loss


def _xent_fwd(x2, labels):
    loss, lse = _run_fwd(x2, labels)
    return loss, (x2, labels, lse)


def _xent_bwd(res, g):
    x2, labels, lse = res
    R, V = x2.shape
    br, bv = _block_sizes(R, V)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, bv=bv, V=V),
        grid=(pl.cdiv(R, br), pl.cdiv(V, bv)),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, V), x2.dtype),
        interpret=_interpret(), name='softmax_xent_bwd',
    )(x2, labels[:, None], lse[:, None], g[:, None].astype(jnp.float32))
    return dx, None


_xent2d.defvjp(_xent_fwd, _xent_bwd)


def softmax_cross_entropy_with_logits(logits, labels):
    """logits: (..., V); labels: (...) int. Returns per-example nll (...)."""
    V = logits.shape[-1]
    shape = logits.shape[:-1]
    loss = _xent2d(logits.reshape(-1, V), labels.reshape(-1).astype(jnp.int32))
    return loss.reshape(shape)
