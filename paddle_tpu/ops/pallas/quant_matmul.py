"""Int8 weight-only quantized matmul (pallas, TPU).

ref (capability): python/paddle/quantization + the reference's
weight_only_linear fused kernels (paddle/phi/kernels/fusion/gpu/
weight_only_linear_kernel.cu). Weights stored int8 with per-column
fp32 scales; the kernel dequantises tiles in VMEM right before the
MXU dot, so HBM traffic is halved vs bf16 weights.

Off-TPU, quant_matmul/quant_matmul_int4 dispatch to a native-XLA
equivalent (_quant_matmul_xla) instead of the pallas interpreter: the
math is identical (f32 dot over raw codes, per-column scale on the
accumulator) but it runs at XLA-CPU matmul speed, so quantized serving
benches on dev boxes measure the model, not the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    from . import interpret_mode

    return interpret_mode()


def quantize_weight(w, axis=0):
    """fp weight (K, N) → (int8 weight, fp32 per-output-column scale)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.reshape(-1)


FP8_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


def quantize_weight_fp8(w, axis=0, dtype=jnp.float8_e4m3fn):
    """fp weight (K, N) → (fp8 weight, fp32 per-output-column scale).

    Closes SURVEY §2.6/§2.12 fp8 stretch: same kernel as int8 (the
    dequant is an `astype` in VMEM), fp8 keeps ~2 decimal digits of
    mantissa where int8 keeps uniform steps — better for outlier-heavy
    weights; HBM traffic is halved vs bf16 either way.
    """
    fmax = FP8_MAX[dtype]
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / fmax, 1e-12)
    q = (w.astype(jnp.float32) / scale).astype(dtype)
    return q, scale.reshape(-1)


def quantize_weight_int4(w, axis=0):
    """fp weight (K, N) → (packed int4 weight (⌈K/2⌉, N) int8, scale).

    Two 4-bit codes per byte along K (row 2r in the low nibble, 2r+1 in
    the high nibble) — HALF the HBM traffic of the int8 path; the kernel
    sign-extends both nibbles in VMEM right before the MXU. Odd K pads
    one zero row.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / 7.0, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -8, 7)
    q = q.astype(jnp.int8)
    if q.shape[0] % 2:
        q = jnp.concatenate([q, jnp.zeros((1, q.shape[1]), jnp.int8)], 0)
    lo = q[0::2].astype(jnp.uint8) & 0xF
    hi = (q[1::2].astype(jnp.uint8) & 0xF) << 4
    return (lo | hi).astype(jnp.int8), scale.reshape(-1)


def _unpack_int4(w8):
    """(bk/2, bn) packed int8 → (bk, bn) fp32 sign-extended codes."""
    # widened first: Mosaic has no shifts on int8 vectors
    w32 = w8.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(w32, 28), 28)    # arithmetic: sext
    hi = jnp.right_shift(w32, 4)
    half, bn = w8.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * half, bn).astype(
        jnp.float32)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc, *, nk, bk, K, int4=False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[:].astype(jnp.float32)                     # (bm, bk)
    if int4:
        w = _unpack_int4(w_ref[:])                       # (bk, bn) from bk/2
    else:
        w = w_ref[:].astype(jnp.float32)                 # (bk, bn) dequant in VMEM
    if K % bk:
        # tail K block: the padded x columns / w rows read unspecified
        # memory — zero them out of the accumulation
        kcol = k * bk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(kcol < K, x, 0.0)
        krow = k * bk + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        w = jnp.where(krow < K, w, 0.0)
    acc[:] = acc[:] + jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[:] = (acc[:] * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _quant_matmul_xla(x, wq, scale, out_dtype):
    """Native-XLA path for non-TPU backends: the same math as _kernel
    (f32 dot over the raw codes, per-output-column scale applied to the
    accumulator) without the pallas interpreter, whose per-instruction
    emulation made the int8 DRAFT model slower than the bf16 target on
    CPU and sank the speculative-decode bench."""
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), wq.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return (acc * scale[None, :].astype(jnp.float32)).astype(out_dtype)


def quant_matmul(x, wq, scale, block_m=256, block_n=256, block_k=512,
                 out_dtype=None, interpret=None):
    """x: (M, K) fp; wq: (K, N) int8; scale: (N,) fp32 → (M, N).

    interpret=None (auto): pallas kernel on TPU, native XLA elsewhere.
    interpret=True forces the interpret-mode pallas kernel (kernel
    correctness tests)."""
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2
    out_dtype = out_dtype or x.dtype
    if interpret is None and _interpret():
        return _quant_matmul_xla(x, wq, scale, out_dtype)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    nk = pl.cdiv(K, bk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bk=bk, K=K),
        grid=(pl.cdiv(M, bm), pl.cdiv(N, bn), nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=bool(interpret) or _interpret(),
    )(x, wq, scale.reshape(1, N))


def quant_matmul_int4(x, wq_packed, scale, block_m=256, block_n=256,
                      block_k=512, out_dtype=None, interpret=None):
    """x: (M, K) fp; wq_packed: (⌈K/2⌉, N) int8 (two int4 codes per
    byte along K); scale: (N,) fp32 → (M, N). interpret as in
    quant_matmul."""
    M, K = x.shape
    half, N = wq_packed.shape
    if half * 2 not in (K, K + 1):
        raise ValueError(
            f'packed int4 weight rows {half} do not match K={K}')
    out_dtype = out_dtype or x.dtype
    if K % 2:
        x = jnp.concatenate([x, jnp.zeros((M, 1), x.dtype)], axis=1)
        K = K + 1
    if interpret is None and _interpret():
        return _quant_matmul_xla(x, _unpack_int4(wq_packed), scale,
                                 out_dtype)
    bm, bn = min(block_m, M), min(block_n, N)
    bk = min(block_k, K)
    bk = bk + (bk % 2)                                   # even K blocks
    nk = pl.cdiv(K, bk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bk=bk, K=K, int4=True),
        grid=(pl.cdiv(M, bm), pl.cdiv(N, bn), nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=bool(interpret) or _interpret(),
    )(x, wq_packed, scale.reshape(1, N))


def weight_only_linear(x, wq, scale, bias=None, weight_dtype='int8'):
    """ref: paddle.nn.quant.weight_only_linear. x: (..., K)."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    mm = quant_matmul_int4 if weight_dtype == 'int4' else quant_matmul
    out = mm(x.reshape(-1, K), wq, scale)
    out = out.reshape(*lead, -1)
    if bias is not None:
        out = out + bias
    return out
