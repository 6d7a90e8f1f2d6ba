"""Small-seq flash-attention occupancy sweep (VERDICT r4 weak #5).

Run ON THE REAL CHIP (the only process using it):
    python tools/flash_sweep.py
Measures the standalone fwd+bwd kernel at seq 2048/4096 across block
configurations (and the swapaxes overhead), prints TFLOP/s per config so
the default block heuristic can be tuned with evidence instead of
guesses.

Importable anywhere (pytest collection, tracelint): jax is only
imported inside the functions, and main() returns 2 with a clear
message when no TPU backend is reachable — the same no-TPU guard
tools/mosaic_check.py carries.
"""
import functools
import os
import sys
import time

# `python tools/flash_sweep.py` puts tools/ (not the repo root) on
# sys.path and paddle_tpu is not pip-installed on the dev boxes — make
# the repo importable no matter where the script is launched from
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def bench_flash(B, H, S, D, bq, bk, reps=8):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=())
    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk).astype(jnp.float32).sum()
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    l, _ = fwd_bwd(q, k, v)
    float(l)
    t0 = time.perf_counter()
    for _ in range(reps):
        l, grads = fwd_bwd(q, k, v)
    float(l)
    dt = (time.perf_counter() - t0) / reps
    # 3.5x-fwd FLOP convention, causal halved (matches performance.md)
    flops = 3.5 * (4 * B * H * S * S * D) * 0.5
    return dt, flops / dt / 1e12


def main():
    import jax

    # guard, not assert: `python -O` strips asserts, and importing this
    # module must never touch the backend — only main() does
    if jax.default_backend() != 'tpu':
        print(f'flash_sweep: needs the real chip '
              f'(backend={jax.default_backend()}); run it on a machine '
              f'with a TPU')
        return 2
    print(f'device: {jax.devices()[0].device_kind}')
    for (B, H, S) in [(4, 32, 2048), (1, 32, 4096), (1, 32, 8192)]:
        for (bq, bk) in [(1024, 1024), (512, 1024), (512, 512),
                         (256, 512), (2048, 512), (1024, 512)]:
            if bq > S or bk > S:
                continue
            try:
                dt, tf = bench_flash(B, H, S, 128, bq, bk)
                print(f'S={S:6d} B={B} bq={bq:5d} bk={bk:5d}: '
                      f'{dt * 1e3:7.2f} ms  {tf:6.1f} TF/s')
            except Exception as e:  # noqa: BLE001
                print(f'S={S:6d} bq={bq} bk={bk}: FAILED {e}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
