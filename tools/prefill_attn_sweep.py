"""The serving prefill's attention: the flash kernel beside the masked XLA
path it replaced, at the benchmark's cells' admission-prefill shapes.

Run ON THE REAL CHIP (the only process using it):
    python tools/prefill_attn_sweep.py
For each shape (rows x bucket, query / kv heads, K / V width, window,
sink) it runs each side `REPS` times in one `lax.scan` under one jit, the
carry fed by the output, and prints microseconds a call, the kernel's
share of the XLA path's time and the largest gap between the two sides'
outputs. The kernel side is `ops.pallas.flash_attention.prefill_attention`
(what `models.llama.cached_attention` calls on a fresh prefill); the XLA
side is `models.llama._xla_attention` over the mask `cached_attention`
builds for the same call (causal, the band, a temp cache as long as the
bucket).

Importable anywhere (pytest collection, tracelint): jax is only imported
inside the functions, and main() returns 2 with a clear message when no
TPU backend is reachable — the guard tools/gmm_sweep.py carries.
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

REPS = 16
PREFILL_TOKENS = 1024           # inference/serving.py: rows = 1024 // bucket

# (name, bucket, query heads, kv heads, K width, V width, window, sink):
# the cells' configurations (benchmark/configs) at the buckets their
# traffic lands in; rows follow the admission batch's token budget
SHAPES = (
    ('chat/docs', 128, 32, 8, 128, 128, None, False),
    ('chat/docs', 256, 32, 8, 128, 128, None, False),
    ('chat/docs', 512, 32, 8, 128, 128, None, False),
    ('chat/docs', 1024, 32, 8, 128, 128, None, False),
    ('trinity', 128, 48, 8, 128, 128, None, False),
    ('trinity', 256, 48, 8, 128, 128, None, False),
    ('trinity', 512, 48, 8, 128, 128, None, False),
    ('mimo window', 1024, 64, 8, 192, 128, 128, True),
    ('mimo full', 1024, 64, 4, 192, 128, None, False),
    ('mimo window', 2048, 64, 8, 192, 128, 128, True),
    ('mimo full', 2048, 64, 4, 192, 128, None, False),
)


def rows(bucket):
    return max(1, PREFILL_TOKENS // bucket)


def sides(window):
    """name -> fn(q, k, v, sink) for the kernel and the XLA path."""
    import jax.numpy as jnp

    from paddle_tpu.models.llama import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import prefill_attention

    def kernel(q, k, v, sink):
        return prefill_attention(q, k, v, sink, window=window)

    def xla(q, k, v, sink):
        S = q.shape[1]
        pos = jnp.arange(S)
        mask = pos[None, :] <= pos[:, None]
        if window is not None:
            mask = mask & (pos[:, None] - pos[None, :] < window)
        return _xla_attention(q, k, v, mask[None, None], sink)

    return {'kernel': kernel, 'xla': xla}


def time_call(fn, q, k, v, sink):
    """Seconds a call of fn(q, k, v, sink), REPS calls in one scan."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(q, k, v, sink):
        def step(q, _):
            out = fn(q, k, v, sink)
            s = out[0, 0, 0, 0].astype(jnp.float32)
            # the next call waits for this one
            return q + (s * 0.0).astype(q.dtype), None

        return jax.lax.scan(step, q, None, length=REPS)[0]

    run(q, k, v, sink).block_until_ready()
    t0 = time.perf_counter()
    run(q, k, v, sink).block_until_ready()
    return (time.perf_counter() - t0) / REPS


def bench_shape(bucket, heads, kv_heads, dk, dv, window, sink, seed=0):
    """({side: us a call}, the largest gap between the sides' outputs)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    B = rows(bucket)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    q = draw(B, bucket, heads, dk)
    k = draw(B, bucket, kv_heads, dk)
    v = draw(B, bucket, kv_heads, dv)
    s = (jnp.asarray(rng.normal(size=(heads,)) + 3.0, jnp.float32)
         if sink else None)
    us, outs = {}, {}
    for side, fn in sides(window).items():
        us[side] = time_call(fn, q, k, v, s) * 1e6
        outs[side] = np.asarray(fn(q, k, v, s).astype(jnp.float32))
    return us, float(np.abs(outs['kernel'] - outs['xla']).max())


def main():
    import jax

    if jax.default_backend() != 'tpu':
        print(f'prefill_attn_sweep: needs the real chip '
              f'(backend={jax.default_backend()}); run it on a machine '
              f'with a TPU')
        return 2
    print(f'device: {jax.devices()[0].device_kind}')
    print('| cell shape | rows x bucket | heads q/kv | K/V | window | sink | '
          'kernel us | XLA us | kernel / XLA | gap |')
    print('| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |')
    for name, bucket, h, hkv, dk, dv, window, sink in SHAPES:
        us, gap = bench_shape(bucket, h, hkv, dk, dv, window, sink)
        k, x = us['kernel'], us['xla']
        print(f'| {name} | {rows(bucket)} x {bucket} | {h}/{hkv} | '
              f'{dk}/{dv} | {window or "-"} | {"yes" if sink else "no"} | '
              f'{k:.1f} | {x:.1f} | {k / x:.3f} | {gap:.4f} |', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
