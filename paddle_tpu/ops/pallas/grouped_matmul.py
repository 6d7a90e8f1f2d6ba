"""Grouped matmul over the experts one rank holds — pallas, TPU.

ref (capability): the reference's per-expert GEMMs behind
`global_scatter` (incubate/distributed/models/moe) and, for the shape of
the schedule, `jax.experimental.pallas.ops.tpu.megablox.gmm`. TPU-native
design for SERVING, where an expert sees a handful of rows and the call's
time is the weights it has to read: rows arrive sorted by held expert
(`distributed.moe.ragged_expert_apply`: held groups first, the un-held
picks behind them) and the grid walks (expert, row tile) VISITS from a
scalar-prefetched schedule built from `group_sizes`. An expert with no
rows gets no visit, the rows behind the last held group get none, and a
grid step past the schedule's end keeps the previous block indices (no
copy) and computes nothing, so the weights read are the HIT experts',
each once a row tile it has rows in. A row tile (`row_tile`) is what of
`x` a visit holds in VMEM: the whole call up to `ROW_TILE` rows, so a
decode step reads every hit expert exactly once whatever its rows.
Inside a visit the products run over the group's OWN rows, a slab
(`_slab`) at a time at aligned offsets, and a slab's rows outside the
group keep what their own visit wrote. An expert's matrix comes in
(K, tn) column blocks, the widest whose two buffers fit the budget:
whole-K products, no accumulator, large copies. Operands as stored,
float32 accumulation. Inference-only (no VJP): training and `MoELayer`
keep `lax.ragged_dot`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of `x` a visit holds (a (ROW_TILE, K) block, two buffers), and the
# VMEM the weights' column blocks (every matrix of the call, two buffers
# each) may take
ROW_TILE = 1024
WEIGHT_VMEM_BUDGET = 24 * 1024 * 1024


def _interpret():
    from . import interpret_mode

    return interpret_mode()


def _slab(rows):
    """Rows one product takes: a call of few rows has 1-8 an expert and
    pays the MXU a weight tile's load a slab, so slabs are the smallest
    aligned ones (a bfloat16 tile's 16 sublanes); a prefill's groups of
    16-130 rows take the MXU's own height."""
    return 16 if rows <= ROW_TILE else 128


def row_tile(rows):
    """Rows of a call of `rows` rows that one visit holds."""
    slab = _slab(rows)
    return min(-(-rows // slab) * slab, ROW_TILE)


def visits(group_sizes, rows):
    """(E,) int32: the row tiles each group has rows in, for a call of
    `rows` rows with these `group_sizes`: the (expert, row tile) visits the
    kernel makes, each one read of the expert's matrix."""
    tm = row_tile(rows)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    return jnp.where(group_sizes > 0,
                     (ends - 1) // tm - starts // tm + 1, 0).astype(jnp.int32)


def _pick_cols(K, N, itemsize, n_weights):
    """Columns of an expert's matrix a grid step reads: the widest
    divisor of N in whole lane tiles whose blocks fit the budget (N whole
    where it is no multiple of 128)."""
    if N % 128:
        return N
    fit = WEIGHT_VMEM_BUDGET // (2 * n_weights * K * itemsize)
    return max((c for c in range(128, N + 1, 128)
                if N % c == 0 and c <= fit), default=128)


def _gmm_kernel(expert_ref, tile_ref, start_ref, end_ref, total_ref, x_ref,
                *refs, tm, slab, act):
    """Grid step (n, v): visit v of the schedule, column block n."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)

    @pl.when(v < total_ref[0])
    def _visit():
        e = expert_ref[v]
        base = tile_ref[v] * tm
        lo = jnp.maximum(start_ref[e] - base, 0)
        hi = jnp.minimum(end_ref[e] - base, tm)

        def product(j, _):
            r0 = j * slab
            rows = pl.ds(r0, slab)
            xs = x_ref[rows, :]
            out = [jnp.dot(xs, w[...], preferred_element_type=jnp.float32)
                   for w in w_refs]
            val = out[0] if act is None else act(out[0]) * out[1]
            at = r0 + jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
            o_ref[rows, :] = jnp.where(
                (at >= lo) & (at < hi), val,
                o_ref[rows, :].astype(jnp.float32)).astype(o_ref.dtype)

        jax.lax.fori_loop(lo // slab, pl.cdiv(hi, slab), product, None)


def _schedule(group_sizes, rows, tm, n_visits):
    """The scalar-prefetched walk: each visit's expert and row tile, every
    group's first and last row, and the visits in all. Entries past the
    last visit repeat it, so their blocks are not copied again."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    per = visits(group_sizes, rows)
    upto = jnp.cumsum(per)
    total = upto[-1]
    v = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(upto, v, side='right'),
                         E - 1).astype(jnp.int32)
    tile = starts[expert] // tm + v - (upto[expert] - per[expert])
    tile = jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32)
    return expert, tile, starts, ends, total.reshape(1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=('act', 'out_dtype', 'interpret'))
def _gmm_call(x, weights, group_sizes, *, act, out_dtype, interpret):
    """The kernel's call, jitted by itself: an expert layer calls it twice
    with the same shapes in every layer, and a call site should not trace
    and lower the body again (PR 29, `_paged_call`)."""
    rows, K = x.shape
    E, _, N = weights[0].shape
    tm, slab = row_tile(rows), _slab(rows)
    padded = -(-rows // tm) * tm
    if padded != rows:
        x = jnp.pad(x, ((0, padded - rows), (0, 0)))
    n_tiles = padded // tm
    n_visits = E + n_tiles - 1
    tn = _pick_cols(K, N, weights[0].dtype.itemsize, len(weights))
    prefetch = _schedule(jnp.asarray(group_sizes, jnp.int32), padded, tm,
                         n_visits)
    blocks = 2 * (len(weights) * K * tn * weights[0].dtype.itemsize
                  + tm * K * x.dtype.itemsize
                  + tm * tn * jnp.dtype(out_dtype).itemsize)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, slab=slab, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            # column blocks outermost: a row tile's output block stays in
            # VMEM over the visits that write it
            grid=(N // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, K),
                                   lambda n, v, ex, tl, *_: (tl[v], 0))]
            + [pl.BlockSpec((None, K, tn),
                            lambda n, v, ex, tl, *_: (ex[v], 0, n))
               ] * len(weights),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, ex, tl, *_: (tl[v], n))),
        out_shape=jax.ShapeDtypeStruct((padded, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=blocks + 16 * 1024 * 1024),
        interpret=interpret, name='grouped_matmul',
    )(*prefetch, x, *weights)
    return out[:rows]


def _check(x, weights, group_sizes):
    E, K, N = weights[0].shape
    if x.ndim != 2 or x.shape[1] != K:
        raise ValueError(f'rows of {x.shape} do not meet experts of {K} rows')
    if any(w.shape != (E, K, N) for w in weights):
        raise ValueError('the experts\' matrices differ in shape: '
                         f'{[w.shape for w in weights]}')
    if group_sizes.shape != (E,):
        raise ValueError(f'{group_sizes.shape} group sizes for {E} experts')


def grouped_matmul(x, w, group_sizes):
    """x[rows of group e] @ w[e] for every group with rows.

    x: (rows, K), sorted by group, group e's rows behind group e-1's;
    w: (E, K, N); group_sizes: (E,) int32, summing to at most `rows`.
    Returns (rows, N) float32. Rows behind the last group, and every row
    where no group has one, hold whatever was there: the caller drops them.
    """
    _check(x, (w,), group_sizes)
    return _gmm_call(x, (w,), group_sizes, act=None, out_dtype=jnp.float32,
                     interpret=_interpret())


def grouped_gated(x, w_gate, w_up, group_sizes, act):
    """act(x @ w_gate[e]) * (x @ w_up[e]) over each group's rows, both
    products in one pass over a visit's rows (one read of them, one grid),
    in float32, rounded to x's dtype. Shapes as `grouped_matmul`; returns
    (rows, N) of x's dtype."""
    _check(x, (w_gate, w_up), group_sizes)
    return _gmm_call(x, (w_gate, w_up), group_sizes, act=act,
                     out_dtype=x.dtype, interpret=_interpret())
