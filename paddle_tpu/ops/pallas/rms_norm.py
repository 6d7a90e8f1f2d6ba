"""Fused RMSNorm (pallas, TPU) with custom VJP.

ref (capability): the reference's FusedRMSNorm
(paddle/phi/kernels/fusion/gpu/fused_rms_norm*). One pass over HBM for
the forward (XLA would otherwise materialise the normalised
intermediate when the weight multiply lands in a different fusion);
row-blocked over the flattened leading dims, feature dim resident in
VMEM. Backward computes dx in one fused kernel; dweight is a cross-row
reduction left to XLA (it fuses into the surrounding backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _block_rows(n_feat: int, n_rows: int) -> int:
    """Rows per block sized so the working set (~6 fp32 row-buffers:
    x, g, gw, out + copies) stays well under the 16MB VMEM budget; a
    multiple of 8, which Mosaic asks of a block that is not the whole
    array (a feature dim of 3072 gave 170)."""
    target = (2 * 1024 * 1024) // max(4 * n_feat, 1)   # ~2MB per buffer
    rows = max(8, min(256, target) // 8 * 8)
    return min(rows, n_rows)


def _interpret():
    from . import interpret_mode

    return interpret_mode()


def _fwd_kernel(x_ref, w_ref, o_ref, r_ref, *, epsilon):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + epsilon)                      # (rows, 1)
    o_ref[:] = (x * r * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    r_ref[:] = r


def _dx_kernel(x_ref, w_ref, r_ref, g_ref, dx_ref, *, n_feat):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    r = r_ref[:]                                          # (rows, 1)
    gw = g * w
    # dx = r*gw - x * r^3 * mean(gw * x)
    mean_gwx = jnp.mean(gw * x, axis=-1, keepdims=True)
    dx_ref[:] = (r * gw - x * (r * r * r) * mean_gwx).astype(dx_ref.dtype)


def _run_fwd(x2, w, epsilon, rows_blk):
    R, N = x2.shape
    grid = (pl.cdiv(R, rows_blk),)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, epsilon=epsilon),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_blk, N), lambda i: (i, 0)),
            pl.BlockSpec((N,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((rows_blk, N), lambda i: (i, 0)),
            pl.BlockSpec((rows_blk, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=_interpret(), name='rms_norm_fwd',
    )(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm2d(x2, w, epsilon):
    out, _ = _run_fwd(x2, w, epsilon, _block_rows(x2.shape[1], x2.shape[0]))
    return out


def _rms_fwd(x2, w, epsilon):
    out, r = _run_fwd(x2, w, epsilon, _block_rows(x2.shape[1], x2.shape[0]))
    return out, (x2, w, r)


def _rms_bwd(epsilon, res, g):
    x2, w, r = res
    R, N = x2.shape
    rows_blk = _block_rows(N, R)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, n_feat=N),
        grid=(pl.cdiv(R, rows_blk),),
        in_specs=[
            pl.BlockSpec((rows_blk, N), lambda i: (i, 0)),
            pl.BlockSpec((N,), lambda i: (0,)),
            pl.BlockSpec((rows_blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows_blk, N), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_blk, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, N), x2.dtype),
        interpret=_interpret(), name='rms_norm_bwd',
    )(x2, w, r, g)
    # dw: cross-row reduction — XLA fuses this fine
    xf = x2.astype(jnp.float32)
    dw = jnp.sum(g.astype(jnp.float32) * xf * r, axis=0).astype(w.dtype)
    return dx, dw


_rms_norm2d.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, weight=None, epsilon=1e-6):
    """x: (..., N). Fused on TPU; matches nn.functional.norm.rms_norm."""
    N = x.shape[-1]
    if weight is None:
        weight = jnp.ones((N,), x.dtype)
    shape = x.shape
    out = _rms_norm2d(x.reshape(-1, N), weight, float(epsilon))
    return out.reshape(shape)
