"""The fresh prefill's attention through the flash kernel
(`ops/pallas/flash_attention.py` `prefill_attention`: a sink, V narrower
than K, grouped heads a grid step, a banded grid), in interpret mode and
float32 against
`models.llama.masked_attention`, the XLA path it replaces; and the route
`models.llama.cached_attention` takes to it: which calls take the kernel,
which keep the XLA path, and what the serving engine reports of it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import ops
from paddle_tpu.models import generation, llama
from paddle_tpu.ops.pallas import flash_attention as fa

TOL = 2e-5


def _draw(seed, B, S, H, Hkv, D, Dv, sink):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    s = normal(H) + 1.0 if sink else None
    return normal(B, S, H, D), normal(B, S, Hkv, D), normal(B, S, Hkv, Dv), s


def _reference(q, k, v, window, sink):
    S = q.shape[1]
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen = seen & (ahead < window)
    return llama.masked_attention(q, k, v, seen[None, None], sink)


def _kernel_calls(closed, name='flash_attention_prefill'):
    """pallas_calls of `name` in a traced program, one a call site: a
    nested jit's body is counted each time it is called."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                n += eqn.params['name'] == name
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, 'jaxpr', sub)
                    if hasattr(sub, 'eqns'):
                        n += walk(sub)
        return n

    return walk(closed.jaxpr)


# (id, B, S, H, Hkv, D, Dv, window, sink): several q blocks a case and,
# where a window is given, a banded k walk; blocks wholly under the
# diagonal (and inside the band) take the unmasked step; in the last two
# a short sequence that is no multiple of 16 keeps one head a step
CASES = [
    ('sink_and_band', 1, 512, 16, 2, 32, 32, 40, True),
    ('sink_no_band', 1, 256, 16, 2, 32, 32, None, True),
    ('v_128_of_k_192_group8', 1, 512, 8, 1, 192, 128, 128, True),
    ('v_16_of_k_24_group16', 2, 256, 16, 1, 24, 16, None, False),
    ('group16_sink_band', 1, 640, 16, 1, 24, 16, 100, True),
    ('band_under_a_block', 1, 384, 8, 1, 24, 16, 20, True),
    ('not_a_block_multiple', 1, 1000, 8, 2, 24, 24, 50, True),
    ('not_a_block_multiple_causal', 1, 1000, 8, 2, 16, 8, None, False),
    ('interior_blocks_unmasked', 1, 1024, 8, 2, 16, 8, None, True),
    ('band_interior_blocks_unmasked', 1, 2048, 2, 2, 8, 8, 1600, False),
    ('short_one_head_a_step', 1, 100, 4, 2, 16, 8, 30, True),
    ('seven_rows', 2, 7, 4, 2, 16, 8, None, True),
]


@pytest.mark.parametrize('case', CASES, ids=[c[0] for c in CASES])
def test_kernel_matches_masked_attention(case):
    _, B, S, H, Hkv, D, Dv, window, sink = case
    q, k, v, s = _draw(0, B, S, H, Hkv, D, Dv, sink)
    got = fa.prefill_attention(q, k, v, s, window=window)
    want = _reference(q, k, v, window, s)
    assert got.shape == (B, S, H, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)


@pytest.mark.parametrize('kv_heads', [2, 1], ids=['group8', 'group16'])
def test_right_padded_rows_leave_the_real_rows_alone(kv_heads):
    """A serving admission right-pads its prompts: the real rows must read
    what a prompt of their own length would."""
    real, S = 150, 256
    q, k, v, s = _draw(1, 1, S, 16, kv_heads, 24, 16, True)
    got = fa.prefill_attention(q, k, v, s, window=32)
    want = _reference(q[:, :real], k[:, :real], v[:, :real], 32, s)
    np.testing.assert_allclose(np.asarray(got[:, :real]), np.asarray(want),
                               rtol=TOL, atol=TOL)


def test_the_window_grid_walks_only_the_band():
    """MiMo's window layer at 2,048: 16 q blocks of 128 rows a kv head
    (its 8 query heads folded into one step) walk 2 k blocks each, not
    16, and a full layer folds 16 heads over every block."""
    sds = jax.ShapeDtypeStruct
    q = sds((1, 2048, 64, 192), jnp.bfloat16)
    sink = sds((64,), jnp.float32)

    def grid(kv_heads, window):
        kv = (sds((1, 2048, kv_heads, 192), jnp.bfloat16),
              sds((1, 2048, kv_heads, 128), jnp.bfloat16))
        closed = jax.make_jaxpr(
            lambda q, k, v, s: fa.prefill_attention(q, k, v, s,
                                                    window=window))(
            q, *kv, sink)
        from paddle_tpu.analysis.mosaic import iter_eqns
        call, = [e for e in iter_eqns(closed.jaxpr)
                 if e.primitive.name == 'pallas_call']
        return tuple(call.params['grid_mapping'].grid)

    assert grid(8, 128) == (1, 8, 16, 2)
    assert grid(4, None) == (1, 4, 16, 8)


@pytest.mark.parametrize('what', ['sink', 'narrower_v', 'plain'])
def test_a_differentiated_call_raises(what):
    q, k, v, s = _draw(2, 1, 64, 4, 2, 16, 8 if what == 'narrower_v' else 16,
                       what == 'sink')
    with pytest.raises(NotImplementedError,
                       match='prefill_attention has no backward'):
        jax.grad(lambda q: fa.prefill_attention(q, k, v, s).sum())(q)


# ---------------------------------------------------------------------------
# the route in cached_attention
# ---------------------------------------------------------------------------

@pytest.fixture
def pallas_on(monkeypatch):
    """The pallas path as on the chip; the kernels run in interpret mode."""
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)


def _cached(q, k, v, cache, **kw):
    return llama.cached_attention(q, k, v, cache, kw.pop('cache_index', 0),
                                  **kw)


@pytest.mark.parametrize('window,sink,dv', [(None, False, 24), (40, True, 16)],
                         ids=['plain', 'window_sink_narrow_v'])
def test_a_fresh_prefill_takes_the_kernel_and_matches_xla(
        monkeypatch, window, sink, dv):
    B, S, T, H, Hkv, D = 1, llama.PREFILL_KERNEL_MIN_ROWS, 1040, 8, 2, 24
    q, k, v, s = _draw(3, B, S, H, Hkv, D, dv, sink)
    cache = (jnp.zeros((B, T, Hkv, D)), jnp.zeros((B, T, Hkv, dv)))
    want, want_cache = _cached(q, k, v, cache, window=window, sink=s)
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    assert llama.prefill_takes_kernel(S, D, cache, 0)
    got, got_cache = _cached(q, k, v, cache, window=window, sink=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)
    for a, b in zip(got_cache, want_cache):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    closed = jax.make_jaxpr(
        lambda q, k, v: _cached(q, k, v, cache, window=window, sink=s))(
        q, k, v)
    assert _kernel_calls(closed) == 1


def _int8_caches(B, T, Hkv, D):
    z8 = jnp.zeros((B, T, Hkv, D), jnp.int8)
    return {
        'quant': generation.QuantKVCache(z8, z8, jnp.ones((Hkv, D)),
                                         jnp.ones((Hkv, D))),
        'rowquant': generation.RowQuantKVCache(
            z8, z8, jnp.zeros((B, T, Hkv)), jnp.zeros((B, T, Hkv))),
    }


@pytest.mark.parametrize('call', ['kvalid', 'kv_write_pos', 'cache_index_3',
                                  'quant', 'rowquant', 'one_row', 'short'])
def test_other_calls_keep_the_xla_path(pallas_on, call):
    B, S, T, H, Hkv, D = 2, llama.PREFILL_KERNEL_MIN_ROWS, 1040, 4, 2, 16
    q, k, v, _ = _draw(4, B, S, H, Hkv, D, D, False)
    cache = (jnp.zeros((B, T, Hkv, D)), jnp.zeros((B, T, Hkv, D)))
    kw = {
        'kvalid': dict(kvalid=jnp.ones((B, T), jnp.int32)),
        'kv_write_pos': dict(kv_write_pos=jnp.zeros((B,), jnp.int32)),
        'cache_index_3': dict(cache_index=3),
        'quant': {}, 'rowquant': {}, 'one_row': {}, 'short': {},
    }[call]
    if call in ('quant', 'rowquant'):
        cache = _int8_caches(B, T, Hkv, D)[call]
    if call in ('one_row', 'short'):
        # a decode step; a prefill shorter than the kernel's least rows
        n = 1 if call == 'one_row' else S // 2
        q, k, v = q[:, :n], k[:, :n], v[:, :n]
    assert not llama.prefill_takes_kernel(
        q.shape[1], D, cache, kw.get('cache_index', 0), kw.get('kvalid'),
        None, kw.get('kv_write_pos'))
    closed = jax.make_jaxpr(lambda q, k, v: _cached(q, k, v, cache, **kw))(
        q, k, v)
    assert _kernel_calls(closed) == 0


def test_a_traced_cache_index_keeps_the_xla_path(pallas_on):
    B, S, T, H, Hkv, D = 1, 16, 32, 4, 2, 16
    q, k, v, _ = _draw(5, B, S, H, Hkv, D, D, False)
    cache = (jnp.zeros((B, T, Hkv, D)), jnp.zeros((B, T, Hkv, D)))
    closed = jax.make_jaxpr(
        lambda q, k, v, i: _cached(q, k, v, cache, cache_index=i))(
        q, k, v, jnp.int32(0))
    assert _kernel_calls(closed) == 0


def test_off_the_chip_nothing_takes_the_kernel():
    S = llama.PREFILL_KERNEL_MIN_ROWS
    cache = (jnp.zeros((1, S, 2, 16)), jnp.zeros((1, S, 2, 16)))
    assert not llama.prefill_takes_kernel(S, 16, cache, 0)


# ---------------------------------------------------------------------------
# what the serving engine reports
# ---------------------------------------------------------------------------

def _engine(**kw):
    from paddle_tpu.inference.serving import ServingEngine

    pt_model = llama.LlamaForCausalLM(llama.llama_tiny(layers=3))
    return ServingEngine(pt_model, max_slots=2, block_size=8,
                         max_context_len=64, max_new_tokens=4,
                         buckets=(16, 32), **kw)


@pytest.mark.parametrize('dtype,want', [(None, 3), ('int8', 0)],
                         ids=['bf16_pools', 'int8_pools'])
def test_the_engine_counts_the_layers_its_prefill_program_routes(
        monkeypatch, dtype, want):
    from paddle_tpu.inference import serving

    big = llama.PREFILL_KERNEL_MIN_ROWS
    engine = _engine(kv_cache_dtype=dtype)
    assert engine._attn_kernel_layers(big) == 0          # off the chip
    engine._kernel_layers.clear()
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    assert engine._attn_kernel_layers(big) == want
    assert engine._attn_kernel_layers(32) == 0           # a short bucket
    for bucket in (big, 32):
        ids, real_len, btabs, _ = engine._prefill_args(bucket, [])
        closed = jax.make_jaxpr(
            lambda pages, ids, rl, bt: serving._prefill_kv(
                engine.model, pages, ids, rl, bt))(
            engine._pages, ids, real_len, btabs)
        assert _kernel_calls(closed) == engine._attn_kernel_layers(bucket)
    assert engine._fill(big, [900])['attn_kernel_layers'] == want
    # a chunk batch continues rows from their pages; a window prefills
    # nothing
    assert engine._fill(big, [900], padded_rows=2)[
        'attn_kernel_layers'] == 0
    assert engine._fill(0, [])['attn_kernel_layers'] == 0
