"""The grouped-matmul kernel alone beside XLA's `lax.ragged_dot`, at the
benchmark's expert cells' decode and admission shapes.

Run ON THE REAL CHIP (the only process using it):
    python tools/gmm_sweep.py
For each shape it draws routings with the ledger's statistics (the share
of picks that chose an expert held here, the fullest held expert over the
mean), runs each side `REPS` times in one `lax.scan` under one jit, the
carry fed by the output, and prints microseconds a call beside the least
time the hit experts' weights take to read (`PEAK_BYTES_S`). Rows are
sorted as `distributed.moe.ragged_expert_apply` sorts them: held groups
first, the un-held picks behind.

Importable anywhere (pytest collection, tracelint): jax is only imported
inside the functions, and main() returns 2 with a clear message when no
TPU backend is reachable — the guard tools/flash_sweep.py carries.
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

PEAK_BYTES_S = 819e9            # TPU v5e HBM (benchmark/harness/peaks.py)
REPS = 16

# (name, pair rows, held experts, K, N, picks local, fullest over mean):
# the cells' geometry (64 slots x top-k a token-step; an admission's
# tokens x top-k) and `expert_local_share_pct.moe` /
# `expert_load_max_over_mean_pct.moe` of the ledger's PR 33 lines
SHAPES = (
    ('mimo decode gate/up', 512, 16, 4096, 2048, 0.06, 2.8),
    ('mimo decode down', 512, 16, 2048, 4096, 0.06, 2.8),
    ('trinity decode', 256, 32, 3072, 3072, 0.12, 4.3),
    ('trinity admission 1k', 4096, 32, 3072, 3072, 0.12, 4.3),
    ('mimo admission 1k gate/up', 8192, 16, 4096, 2048, 0.06, 2.8),
    ('mimo admission 2k gate/up', 16384, 16, 4096, 2048, 0.06, 2.8),
    ('mimo admission 2k down', 16384, 16, 2048, 4096, 0.06, 2.8),
)


def draw_group_sizes(rng, rows, experts, local, max_over_mean, draws):
    """(draws, experts) int32: `rows * local` picks over the held experts,
    skewed (p_e ~ exp(-a e), a by bisection on the draw itself) until the
    fullest holds `max_over_mean` times the mean."""
    import numpy as np

    held = max(1, round(rows * local))
    u = rng.random((draws, held))

    def sizes(a):
        p = np.exp(-a * np.arange(experts))
        edges = np.cumsum(p / p.sum())
        pick = np.minimum((u[..., None] > edges).sum(-1), experts - 1)
        return np.stack([np.bincount(row, minlength=experts)
                         for row in pick])

    lo, hi = 0.0, 4.0
    for _ in range(30):
        a = (lo + hi) / 2
        s = sizes(a)
        if (s.max(1) / s.mean(1)).mean() < max_over_mean:
            lo = a
        else:
            hi = a
    # a deployment's experts are not ordered by load
    return np.stack([rng.permutation(row) for row in sizes(a)]).astype(
        np.int32)


def time_call(fn, x, weights, group_sizes):
    """Seconds a call of fn(x, *weights, sizes), over the routings in
    `group_sizes`, one scan step each."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x, weights, group_sizes):
        def step(x, sizes):
            out = fn(x, *weights, sizes)
            s = out[0, 0].astype(jnp.float32)
            # the next call waits for this one; rows no group holds may
            # be anything
            return x + (jnp.where(jnp.isfinite(s), s, 0.0) * 0.0).astype(
                x.dtype), None

        return jax.lax.scan(step, x, group_sizes)[0]

    run(x, weights, group_sizes).block_until_ready()
    t0 = time.perf_counter()
    run(x, weights, group_sizes).block_until_ready()
    return (time.perf_counter() - t0) / group_sizes.shape[0]


def sides():
    """name -> (one product, the gate and up products with the activation
    between them) for the kernel and for XLA's op."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    def ragged(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)

    def ragged_gated(x, w_gate, w_up, sizes):
        return (jax.nn.silu(ragged(x, w_gate, sizes))
                * ragged(x, w_up, sizes)).astype(x.dtype)

    def kernel_gated(x, w_gate, w_up, sizes):
        return gmm.grouped_gated(x, w_gate, w_up, sizes, jax.nn.silu)

    return {'kernel': (gmm.grouped_matmul, kernel_gated),
            'ragged_dot': (ragged, ragged_gated)}


def bench_shape(rows, experts, K, N, local, max_over_mean, seed=0):
    """{(side, 'one'|'gated'): us a call}, the floors in us, and the
    largest difference between the sides on the held rows."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.bfloat16)
    weights = [jnp.asarray(rng.normal(size=(experts, K, N)) * K ** -0.5,
                           jnp.bfloat16) for _ in range(2)]
    sizes = draw_group_sizes(rng, rows, experts, local, max_over_mean, REPS)
    hit = float((sizes > 0).sum(1).mean())
    floor = hit * K * N * 2 / PEAK_BYTES_S * 1e6
    group_sizes = jnp.asarray(sizes)
    us, outs = {}, {}
    for side, (one, gated) in sides().items():
        us[side, 'one'] = time_call(one, x, weights[:1], group_sizes) * 1e6
        us[side, 'gated'] = time_call(gated, x, weights, group_sizes) * 1e6
        held = int(sizes[0].sum())
        outs[side] = [np.asarray(one(x, weights[0], group_sizes[0]))[:held],
                      np.asarray(gated(x, *weights, group_sizes[0]).astype(
                          jnp.float32))[:held]]
    gap = max(float(np.abs(a - b).max(initial=0.0))
              for a, b in zip(outs['kernel'], outs['ragged_dot']))
    return us, {'one': floor, 'gated': 2 * floor}, hit, gap


def main():
    import jax

    if jax.default_backend() != 'tpu':
        print(f'gmm_sweep: needs the real chip '
              f'(backend={jax.default_backend()}); run it on a machine '
              f'with a TPU')
        return 2
    print(f'device: {jax.devices()[0].device_kind}')
    print('| shape (rows x experts x K x N) | experts hit | product(s) | '
          'hit weights\' floor us | kernel us (% of floor) | '
          'ragged_dot us | gap |')
    print('| --- | --- | --- | --- | --- | --- | --- |')
    for name, rows, experts, K, N, local, skew in SHAPES:
        us, floor, hit, gap = bench_shape(rows, experts, K, N, local, skew)
        for what in ('one', 'gated'):
            k, r = us['kernel', what], us['ragged_dot', what]
            print(f'| {name} ({rows} x {experts} x {K} x {N}) | {hit:.1f} '
                  f'| {what} | {floor[what]:.1f} | {k:.1f} '
                  f'({100 * floor[what] / k:.1f}) | {r:.1f} | {gap:.4f} |',
                  flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
