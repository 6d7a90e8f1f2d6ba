"""Kernel dispatch: the pallas path must actually be taken when
use_pallas() is true, and a kernel that fails there must raise — there is
no fall-back to the lax reference on a TPU."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.ops as ops


@pytest.fixture(autouse=True)
def _fresh_dispatch_state():
    pt.set_flags({'FLAGS_use_pallas_kernels': True})
    yield
    pt.set_flags({'FLAGS_use_pallas_kernels': True})


def test_rms_norm_dispatches_to_pallas(monkeypatch):
    from paddle_tpu.nn.functional.norm import rms_norm as ref
    from paddle_tpu.ops.pallas import rms_norm as kmod
    calls = []

    def fake(x, weight, eps):
        calls.append('rms_norm')
        return ref(x, weight, eps)

    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    monkeypatch.setattr(kmod, 'rms_norm', fake)
    x = jnp.ones((2, 128))
    out = ops.rms_norm(x)
    assert calls == ['rms_norm']
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x)), rtol=1e-6)


def test_softmax_xent_dispatches_to_pallas(monkeypatch):
    from paddle_tpu.ops.pallas import softmax_xent as kmod
    calls = []
    orig = kmod.softmax_cross_entropy_with_logits

    def fake(logits, labels):
        calls.append('xent')
        return orig(logits, labels)

    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    monkeypatch.setattr(kmod, 'softmax_cross_entropy_with_logits', fake)
    logits = jnp.zeros((4, 256))
    labels = jnp.zeros((4,), dtype=jnp.int32)
    ops.softmax_cross_entropy(logits, labels)
    assert calls == ['xent']


def test_flash_attention_dispatches_to_pallas(monkeypatch):
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops.pallas import flash_attention as kmod
    calls = []
    orig = kmod.flash_attention

    def fake(q, k, v, **kw):
        calls.append('flash')
        return orig(q, k, v, **kw)

    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    monkeypatch.setattr(kmod, 'flash_attention', fake)
    q = jnp.ones((1, 128, 2, 8))
    F.scaled_dot_product_attention(q, q, q)
    assert calls == ['flash']


def test_failing_kernel_raises(monkeypatch):
    from paddle_tpu.ops.pallas import rms_norm as kmod

    def broken(x, weight, eps):
        raise ValueError('kernel exploded')

    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    monkeypatch.setattr(kmod, 'rms_norm', broken)
    with pytest.raises(ValueError, match='kernel exploded'):
        ops.rms_norm(jnp.ones((2, 128)))


def test_no_pallas_when_disabled(monkeypatch):
    from paddle_tpu.ops.pallas import rms_norm as kmod

    def fake(x, weight, eps):  # pragma: no cover - must not run
        raise AssertionError('pallas path taken with flag off')

    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    monkeypatch.setattr(kmod, 'rms_norm', fake)
    pt.set_flags({'FLAGS_use_pallas_kernels': False})
    out = ops.rms_norm(jnp.ones((2, 128)))
    assert out.shape == (2, 128)


# ---------------------------------------------------------------------------
# Under a multi-device mesh: Mosaic kernels cannot be partitioned by GSPMD
# (the chip's lowering refuses them outside shard_map; interpret mode on the
# CPU lowers to plain ops and never shows it), so every dispatch site wraps
# its kernel in shard_map through ops.mesh_kernel.
# ---------------------------------------------------------------------------

def _rms_case(rng):
    from paddle_tpu.nn.functional.norm import rms_norm as ref

    x = jnp.asarray(rng.normal(size=(4, 16, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    return ops.rms_norm, ref, (x, w), [P(('dp', 'fsdp')), P()]


def _xent_case(rng):
    import jax

    def ref(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]

    logits = jnp.asarray(rng.normal(size=(4, 8, 256)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 256, (4, 8)), jnp.int32)
    # vocab-parallel logits, as the tp-sharded lm_head produces them
    return (ops.softmax_cross_entropy, ref, (logits, labels),
            [P(('dp', 'fsdp'), None, 'tp'), P(('dp', 'fsdp'))])


def _flash_case(rng):
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional.attention import _sdpa_reference

    q, k, v = (jnp.asarray(rng.normal(size=(4, 128, 4, 8)), jnp.float32)
               for _ in range(3))
    spec = P(('dp', 'fsdp'), None, 'tp', None)
    return (lambda q, k, v: F.scaled_dot_product_attention(
                q, k, v, is_causal=True),
            lambda q, k, v: _sdpa_reference(q, k, v, is_causal=True),
            (q, k, v), [spec] * 3)


def _paged_case(rng):
    from paddle_tpu.models.generation import PagedKVCache
    from paddle_tpu.models.llama import cached_attention

    B, H, D, BS, MAXB = 4, 4, 8, 8, 3
    NB = B * MAXB + 1
    q, k, v = (jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
               for _ in range(3))
    kp, vp = (jnp.asarray(rng.normal(size=(NB, H, BS, D)), jnp.float32)
              for _ in range(2))
    tbl = jnp.asarray(1 + np.arange(B * MAXB).reshape(B, MAXB), jnp.int32)
    wp = jnp.asarray([3, 9, 17, 22], jnp.int32)

    def run(q, k, v, kp, vp):
        return cached_attention(q, k, v, PagedKVCache(kp, vp), None,
                                kv_write_pos=wp, block_tables=tbl)[0]

    def ref(*args):                 # the gather reference, kernels off
        pt.set_flags({'FLAGS_use_pallas_kernels': False})
        try:
            return run(*args)
        finally:
            pt.set_flags({'FLAGS_use_pallas_kernels': True})

    pool = P(None, 'tp', None, None)
    head = P(None, None, 'tp', None)
    return run, ref, (q, k, v, kp, vp), [head, head, head, pool, pool]


@pytest.mark.parametrize('case', [_rms_case, _xent_case, _flash_case,
                                  _paged_case])
def test_kernels_run_per_shard_under_a_mesh(case, monkeypatch):
    import jax
    from jax.sharding import NamedSharding

    from paddle_tpu.distributed import mesh as mesh_mod

    fn, ref, args, specs = case(np.random.default_rng(0))
    want = np.asarray(ref(*args))
    mesh = mesh_mod.build_mesh(devices=jax.devices()[:4], tp=2, fsdp=2,
                               dp=1)
    monkeypatch.setattr(mesh_mod, '_global_mesh', mesh)
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    args = [jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip(args, specs)]
    assert 'shard_map' in str(jax.make_jaxpr(fn)(*args))
    got = jax.jit(fn)(*args)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
