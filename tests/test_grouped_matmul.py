"""The grouped-matmul kernel (`ops/pallas/grouped_matmul.py`) and the rank-
share branch of `distributed.moe.ragged_expert_apply` that calls it, on
the CPU in interpret mode:

  - the kernel against `lax.ragged_dot` on the same sorted rows, and its
    schedule's visits against a walk in Python;
  - `ExpertShare.forward` on the kernel's path against the parent's
    formula, kept here as `lax.ragged_dot` calls;
  - a tiny `ServingEngine` of each family with expert layers: the same
    greedy tokens on either path, nothing retraced by a second wave, every
    hit expert read once in a decode window, and no `ragged_dot` left in a
    serve dispatch.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu.ops as ops  # noqa: E402
from benchmark.harness import common  # noqa: E402
from paddle_tpu import aot  # noqa: E402
from paddle_tpu.distributed import moe  # noqa: E402
from paddle_tpu.inference import serving  # noqa: E402
from paddle_tpu.inference.engine import total_traces  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.observability.tracing import TRACER  # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as gmm  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_afmoe  # noqa: E402  (the families' tiny configurations)
import test_mimo_v2  # noqa: E402

# (rows, K, N, group sizes): rows behind the groups are un-held picks
CASES = {
    'an empty expert between full ones': (48, 32, 64, [16, 0, 20]),
    'every pick un-held': (40, 32, 64, [0, 0, 0, 0]),
    'one expert holds every row': (64, 64, 32, [0, 64, 0]),
    'rows no multiple of the tile': (37, 32, 48, [5, 9, 1, 7]),
    'one row an expert': (24, 32, 64, [1] * 8),
    'K over N': (32, 128, 16, [3, 4, 0, 9]),
    'N over K, in column blocks': (32, 16, 256, [7, 0, 2]),
    # past ROW_TILE rows: slabs of 128, groups over several row tiles
    'a prefill, 16 rows an expert': (2048, 32, 64, [16, 12, 0, 20, 16, 31]),
    'a prefill, 130 rows an expert': (2100, 48, 32, [130, 0, 1000, 64, 900]),
    'a prefill, every pick held': (2048, 32, 32, [512] * 4),
}


def operands(rows, K, N, sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, K)), dtype)
    w = [jnp.asarray(rng.normal(size=(len(sizes), K, N)) * K ** -0.5, dtype)
         for _ in range(2)]
    return x, w, jnp.asarray(sizes, jnp.int32)


def ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize('case', CASES)
def test_kernel_is_ragged_dot_on_the_held_rows(case, dtype, monkeypatch):
    rows, K, N, sizes = CASES[case]
    # column blocks even at these widths
    monkeypatch.setattr(gmm, 'WEIGHT_VMEM_BUDGET', 2 * 2 * K * 128 * 4)
    x, (w_gate, w_up), group_sizes = operands(rows, K, N, sizes, dtype)
    held = sum(sizes)
    got = gmm.grouped_matmul(x, w_gate, group_sizes)
    assert got.shape == (rows, N) and got.dtype == jnp.float32
    want = ragged(x, w_gate, group_sizes)
    # the same operands, float32 accumulation: summation order alone
    np.testing.assert_allclose(got[:held], want[:held], atol=1e-5, rtol=1e-5)
    gated = gmm.grouped_gated(x, w_gate, w_up, group_sizes, jax.nn.silu)
    assert gated.shape == (rows, N) and gated.dtype == dtype
    want = (jax.nn.silu(want) * ragged(x, w_up, group_sizes)).astype(dtype)
    ulp = 1e-5 if dtype == jnp.float32 else 2.0 ** -7   # one rounding apart
    np.testing.assert_allclose(gated[:held].astype(jnp.float32),
                               want[:held].astype(jnp.float32),
                               atol=ulp, rtol=ulp)


@pytest.mark.parametrize('case', CASES)
def test_schedule_visits_each_hit_expert_once_a_row_tile(case):
    rows, _, _, sizes = CASES[case]
    tm = gmm.row_tile(rows)
    padded = -(-rows // tm) * tm
    n_visits = len(sizes) + padded // tm - 1
    expert, tile, starts, ends, total = map(np.asarray, gmm._schedule(
        jnp.asarray(sizes, jnp.int32), padded, tm, n_visits))
    walk, at = [], 0
    for e, n in enumerate(sizes):
        walk += [(e, t) for t in range(at // tm, -(-(at + n) // tm))
                 if n]
        at += n
    assert int(total[0]) == len(walk) <= n_visits
    assert list(zip(expert, tile))[:len(walk)] == walk
    # past the end the last visit's blocks stay
    assert all(pair == (walk[-1] if walk else (len(sizes) - 1, 0))
               for pair in list(zip(expert, tile))[len(walk):])
    assert list(ends - starts) == sizes
    assert int(gmm.visits(jnp.asarray(sizes), rows).sum()) == len(walk)
    if rows <= gmm.ROW_TILE:            # one tile: a read a hit expert
        assert len(walk) == sum(n > 0 for n in sizes)


def test_shapes_that_do_not_meet_are_refused():
    x, (w, _), sizes = operands(8, 16, 32, [4, 4], jnp.float32)
    with pytest.raises(ValueError, match='do not meet'):
        gmm.grouped_matmul(x[:, :8], w, sizes)
    with pytest.raises(ValueError, match='group sizes'):
        gmm.grouped_matmul(x, w, sizes[:1])
    with pytest.raises(ValueError, match='differ in shape'):
        gmm.grouped_gated(x, w, w[:, :, :16], sizes, jax.nn.silu)


def parent_share(share, x):
    """`ExpertShare.forward` as the parent commit computed it."""
    B, S, H = x.shape
    tokens = x.reshape(B * S, H)
    gate_vals, expert_idx = share.route(tokens)
    k, held_n = share.top_k, share.experts_held
    flat_e = expert_idx.reshape(-1).astype(jnp.int32) - share.expert_offset
    held = (flat_e >= 0) & (flat_e < held_n)
    flat_e = jnp.where(held, flat_e, held_n)
    order = jnp.argsort(flat_e, stable=True)
    tok_ids = order // k
    rows = jnp.take(tokens, tok_ids, axis=0)
    sizes = jnp.bincount(flat_e, length=held_n + 1)[:held_n].astype(jnp.int32)
    h = share.act(ragged(rows, share.w_gate, sizes)) * ragged(
        rows, share.w_up, sizes)
    y = ragged(h.astype(rows.dtype), share.w_down, sizes)
    y = y * jnp.take(gate_vals.reshape(-1), order)[:, None]
    y = jnp.where(jnp.take(held, order)[:, None], y, 0.0)
    out = jnp.zeros((B * S, H), y.dtype).at[tok_ids].add(y)
    if share.shared_gate is not None:
        hid = share.act(tokens @ share.shared_gate) * (
            tokens @ share.shared_up)
        out = out + (hid @ share.shared_down).astype(out.dtype)
    return out.reshape(B, S, H).astype(x.dtype)


# a layer of each family at its tiny test configuration: AFMoE's has a
# shared expert and a route scale, MiMo-V2's neither
SHARES = {
    'afmoe': dict(shared_intermediate=32, route_scale=2.448),
    'mimo_v2': dict(),
}


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('family', SHARES)
def test_expert_share_is_the_parents_formula(family, dtype, monkeypatch):
    # the kernels' branch of every dispatch, in interpret mode
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    share = moe.ExpertShare(64, 32, 16, 4, experts_held=4, expert_offset=4,
                            dtype=dtype, **SHARES[family])
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    share.router = jax.random.normal(next(keys), share.router.shape)
    for name in ('w_gate', 'w_up', 'w_down', 'shared_gate', 'shared_up',
                 'shared_down'):
        if getattr(share, name) is not None:
            setattr(share, name, 0.3 * jax.random.normal(
                next(keys), getattr(share, name).shape).astype(dtype))
    x = jax.random.normal(next(keys), (2, 9, 64)).astype(dtype)
    calls = []
    kernel = gmm._gmm_call

    def spy(*args, **kw):
        calls.append(kw['act'])
        return kernel(*args, **kw)

    monkeypatch.setattr(gmm, '_gmm_call', spy)
    got = share(x)
    assert calls == [share.act, None]       # gate and up in one pass, down
    # float32 summation order, and in bfloat16 one rounding of the output
    tol = 1e-5 if dtype == 'float32' else 2.0 ** -7
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(parent_share(share, x),
                                                np.float32),
        atol=tol, rtol=tol)


FAMILIES = {'afmoe': test_afmoe.CFG, 'mimo_v2': test_mimo_v2.CFG}
GEOMETRY = dict(max_slots=2, block_size=4, max_context_len=64,
                decode_window=4, max_new_tokens=12, buckets=(32,))


def engine_of(cfg):
    for program in (serving._serve_step, serving._serve_window,
                    serving._paged_prefill):
        program.clear_cache()           # the other path has to be traced
    return ServingEngine(common.family(cfg).make_model(cfg, 11, 64),
                         **GEOMETRY)


def prompts(cfg):
    rng = np.random.default_rng(11)
    return [rng.integers(0, cfg['vocab_size'], n).astype(np.int32)
            for n in (27, 20)]


@pytest.mark.parametrize('family', FAMILIES)
def test_engine_serves_the_same_tokens_on_the_kernel(family, monkeypatch):
    cfg = FAMILIES[family]
    before = [np.asarray(o) for o in engine_of(cfg).serve(prompts(cfg))]
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    engine = engine_of(cfg)
    TRACER.clear()
    first = [np.asarray(o) for o in engine.serve(prompts(cfg))]
    for a, b in zip(before, first):
        np.testing.assert_array_equal(a, b)
    # every hit expert read once in a decode window, inside an admitting
    # step or not: 8 pair rows are one row tile
    routed = [e['args'] for e in TRACER.events()
              if e['name'] == 'serve.routing']
    assert {a['kind'] for a in routed} == {'window', 'step'}
    assert all(0 < a['weight_visits'] == a['experts_hit'] for a in routed)
    t0 = total_traces()
    again = [np.asarray(o) for o in engine.serve(prompts(cfg))]
    assert total_traces() - t0 == 0
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize('kind', ['serve_window', 'serve_step'])
@pytest.mark.parametrize('family', FAMILIES)
def test_no_ragged_dot_in_a_serve_dispatch(family, kind, monkeypatch):
    def lowered():
        engine = engine_of(FAMILIES[family])
        g = next(g for g in aot.for_serving_engine(engine, prompt_lens=[8])
                 if g.kind == kind)
        (fn, args, statics), = engine._cost_specs(g)
        # the jaxpr: on the CPU `ragged_dot` lowers to dense products
        return str(jax.make_jaxpr(lambda *a: fn(*a, **statics))(*args))

    assert 'ragged_dot' in lowered()    # the probe sees XLA's op
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    assert 'ragged_dot' not in lowered()


def test_the_sweep_tool_off_the_chip(monkeypatch, capsys):
    """`tools/gmm_sweep.py`: refuses to time anything without a TPU, draws
    routings with the statistics asked for, and its two sides agree."""
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import gmm_sweep

    assert gmm_sweep.main() == 2 and 'needs the real chip' in (
        capsys.readouterr().out)
    sizes = gmm_sweep.draw_group_sizes(np.random.default_rng(0), 512, 16,
                                       0.06, 2.8, 64)
    assert sizes.shape == (64, 16) and (sizes.sum(1) == 31).all()
    assert 2.5 < (sizes.max(1) / sizes.mean(1)).mean() < 3.1
    monkeypatch.setattr(gmm_sweep, 'REPS', 2)
    us, floor, hit, gap = gmm_sweep.bench_shape(64, 4, 32, 128, 0.25, 2.0)
    assert set(us) == {(side, what) for side in ('kernel', 'ragged_dot')
                       for what in ('one', 'gated')}
    assert 0 < hit <= 4 and floor['gated'] == 2 * floor['one'] > 0
    assert gap < 1e-2
