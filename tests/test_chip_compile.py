"""The main path's pallas kernels, compiled by the TPU's own compiler for a
described (not attached) v5e chip, at Llama-2-7B width.

Interpret mode cannot see what Mosaic refuses (an op it cannot legalize, a
block not aligned to the tiling, too much VMEM): `quant_matmul_int4` passed
every interpret-mode test and mosaiclint while Mosaic rejected its int8
shifts. These compiles are the authority on "would lower on the chip";
nothing here runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import
or collection time: only one process may hold libtpu, and under xdist every
worker imports this file while only one is handed its tests. Keep these
tests in this one file for the same reason.
"""
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HIDDEN, FFN, HEADS, HEAD_DIM, VOCAB, CTX = 4096, 11008, 32, 128, 32000, 2048
SLOTS, PAGE = 8, 16                     # ServingEngine defaults


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """compile(fn, *(shape, dtype)) -> compiled text, with the kernels on
    their TPU branch and the persistent cache off (an executable for a
    described device is written to it but cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    import paddle_tpu.ops.pallas as pallas_pkg

    monkeypatch.setattr(pallas_pkg, 'interpret_mode', lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


def _grad_of_sum(fn, argnums):
    def fwd_bwd(*args):
        return jax.grad(
            lambda *a: fn(*a).astype(jnp.float32).sum(), argnums)(*args)

    return fwd_bwd


QKV = ((2, CTX, HEADS, HEAD_DIM), jnp.bfloat16)


def test_flash_attention_fwd(chip_compile):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    text = chip_compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
                        QKV, QKV, QKV)
    assert 'tpu_custom_call' in text


def test_flash_attention_fwd_bwd(chip_compile):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    text = chip_compile(
        _grad_of_sum(lambda q, k, v: flash_attention(q, k, v, causal=True),
                     (0, 1, 2)), QKV, QKV, QKV)
    assert text.count('tpu_custom_call') >= 3        # fwd, dq, dkv


def _without_locations(text):
    """A compiled program's text less what moves with a source line: the
    stack-frame tables, each op's metadata and each Mosaic body (which
    carries its kernel's locations; the kernel's jaxpr stands for it)."""
    text = re.sub(r'\n\n(FileNames|FunctionNames|FileLocations|StackFrames)'
                  r'\n.*?(?=\n\n)', '', text, flags=re.S)
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    return re.sub(r', metadata=\{[^}]*\}', '', text)


# The train cell's flash call (qwen25_3b_pretrain_4k: 1 x 4096, 16 query
# heads over 2, head 128), sha256 of its jaxpr and of its compiled text
# less locations, as they were before the forward kernel learned sinks,
# narrower V rows, grouped heads and banded grids. Those features are
# the serving prefill's; the train program has to be this one, byte for
# byte, or its numbers are another program's.
TRAIN_FLASH = {
    'fwd': ('2856787e3e3ef3231cdc9ec78944917590b1911e504f3356536633bf76f1136e',
            'b465e2c50753097c60aab218c9dfb4ec42237ea9a2d434c9169115d73052a697'),
    'fwd_bwd': (
        '7a80cc81d8bc0d99b170c0e72081f09ed3a86432d62f182f2c9e504d24ccea05',
        '9b43c9cfe24ff1c1ace5b083041aed3111eed29e8d0a803186341858009acfdd'),
}


@pytest.mark.parametrize('what', sorted(TRAIN_FLASH))
def test_the_train_cells_flash_program_is_unchanged(chip_compile, one_chip,
                                                    what):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    specs = [((1, 4096, h, 128), jnp.bfloat16) for h in (16, 2, 2)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    fn = fwd if what == 'fwd' else _grad_of_sum(fwd, (0, 1, 2))
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    text = _without_locations(chip_compile(fn, *specs))
    assert tuple(hashlib.sha256(t.encode()).hexdigest()
                 for t in (jaxpr, text)) == TRAIN_FLASH[what]


# The admission prefill's attention (`prefill_attention`, what
# `cached_attention` calls on a fresh prefill) at the serving cells'
# shapes: (rows, bucket, query heads, kv heads, K width, V width, window,
# sink)
PREFILL = {
    'mimo_window_2048': (1, 2048, 64, 8, 192, 128, 128, True),
    'mimo_full_2048': (1, 2048, 64, 4, 192, 128, None, False),
    'mistral_1024': (1, 1024, 32, 8, 128, 128, None, False),
    'trinity_256': (4, 256, 48, 8, 128, 128, None, False),
}


@pytest.mark.parametrize('cell', sorted(PREFILL))
def test_prefill_attention(chip_compile, cell):
    from paddle_tpu.ops.pallas.flash_attention import prefill_attention

    B, S, H, Hkv, D, Dv, window, sink = PREFILL[cell]
    specs = [((B, S, H, D), jnp.bfloat16), ((B, S, Hkv, D), jnp.bfloat16),
             ((B, S, Hkv, Dv), jnp.bfloat16)]
    if sink:
        specs.append(((H,), jnp.float32))
    text = chip_compile(
        lambda *a: prefill_attention(*a, window=window), *specs)
    assert text.count('tpu_custom_call') == 1


def test_prefill_blocks_fit_the_vmem_budget():
    """mosaiclint's ML006 over the prefill kernel's registered shapes: the
    double-buffered blocks and the scratch of every grid step fit a
    core's default scoped VMEM."""
    from paddle_tpu.analysis.mosaic import all_entries, get_rule, lint_entries

    entries = [e for e in all_entries() if e.name.endswith('_prefill')]
    assert len(entries) == 4
    violations, _ = lint_entries(entries, rules=[get_rule('ML006')])
    assert not violations, violations


def test_rms_norm_fwd_bwd(chip_compile):
    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    text = chip_compile(_grad_of_sum(rms_norm, (0, 1)),
                        ((2 * CTX, HIDDEN), jnp.bfloat16),
                        ((HIDDEN,), jnp.bfloat16))
    assert 'tpu_custom_call' in text


def test_softmax_xent_fwd_bwd(chip_compile):
    from paddle_tpu.ops.pallas.softmax_xent import (
        softmax_cross_entropy_with_logits)

    text = chip_compile(
        lambda lg, lb: jax.value_and_grad(
            lambda x: softmax_cross_entropy_with_logits(x, lb).sum())(lg),
        ((2 * CTX, VOCAB), jnp.float32), ((2 * CTX,), jnp.int32))
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('kv_heads', [32, 8])
@pytest.mark.parametrize('cache_dtype', [jnp.bfloat16, jnp.int8])
def test_decode_attention(chip_compile, cache_dtype, kv_heads):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    q = ((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = ((SLOTS, CTX, kv_heads, HEAD_DIM), cache_dtype)
    vl = ((SLOTS,), jnp.int32)
    if cache_dtype == jnp.int8:
        sc = ((kv_heads, HEAD_DIM), jnp.float32)
        text = chip_compile(
            lambda q, k, v, n, ks, vs: decode_attention(
                q, k, v, n, k_scale=ks, v_scale=vs), q, kv, kv, vl, sc, sc)
    else:
        text = chip_compile(decode_attention, q, kv, kv, vl)
    assert 'tpu_custom_call' in text


def _paged_specs(slots, heads, kv_heads, ctx, cache_dtype, page=PAGE):
    """A full-coverage pool plus the scratch page, as ServingEngine sizes
    it: q, the two pools, the block table, the lengths."""
    maxb = ctx // page
    nb = slots * maxb + 1
    pool = ((nb, kv_heads, page, HEAD_DIM), cache_dtype)
    return [((slots, 1, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
            ((slots, maxb), jnp.int32), ((slots,), jnp.int32)]


@pytest.mark.parametrize('slots,heads,kv_heads,ctx', [
    (SLOTS, HEADS, HEADS, CTX),         # Llama-2-7B, the engine's defaults
    (16, 32, 8, 2048),                  # the Mistral cells: group 4
    (16, 8, 2, 2048),                   # one of their tp=4 shards
])
def test_paged_decode_attention(chip_compile, slots, heads, kv_heads, ctx):
    """The ServingEngine decode dispatch's kernel, default page size."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    text = chip_compile(paged_decode_attention, *_paged_specs(
        slots, heads, kv_heads, ctx, jnp.bfloat16))
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('rowscale', [True, False])
@pytest.mark.parametrize('kv_heads,page', [(HEADS, PAGE), (8, 32)])
def test_paged_decode_attention_int8(chip_compile, kv_heads, page, rowscale):
    """int8 pools in both scale layouts: per-row scales in page-shaped
    pools (QuantPagedKVCache), global per-(head, dim) ones (QuantKVCache);
    at the default page and at int8's own sublane count."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    specs = _paged_specs(SLOTS, HEADS, kv_heads, CTX, jnp.int8, page)
    nb = specs[1][0][0]
    sc = (((nb, kv_heads, page) if rowscale else (kv_heads, HEAD_DIM)),
          jnp.float32)
    text = chip_compile(
        lambda q, k, v, t, n, ks, vs: paged_decode_attention(
            q, k, v, t, n, k_scale=ks, v_scale=vs), *specs, sc, sc)
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('slots,ctx', [(64, 1024), (4, 8192)])
def test_paged_decode_attention_windowed(chip_compile, slots, ctx):
    """AFMoE's window layers at Trinity's widths (48 query over 8 kv heads:
    group 6, a window of 4096): the benchmark cell's geometry (64 rows, a
    64-wide table) and the window-crossing one."""
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    text = chip_compile(
        lambda q, k, v, t, n: paged_decode_attention(q, k, v, t, n,
                                                     window=4096),
        *_paged_specs(slots, 48, 8, ctx, jnp.bfloat16))
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('kv_heads,pages,window', [
    (4, 64 * 256 + 1, None),            # its full layers' pool
    (8, 64 * 10 + 1, 128),              # its window layers' recycled pool
])
def test_paged_decode_attention_of_two_widths(chip_compile, kv_heads, pages,
                                              window):
    """MiMo-V2's two kinds of page at the benchmark cell's geometry (64
    rows, a 256-wide table, 64 query heads): K rows of 192 in whole lane
    tiles (256: Mosaic refuses to slice a page 192 wide), V rows of 128,
    and on window layers a sink a query head."""
    from paddle_tpu.models.generation import lane_padded
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    assert lane_padded(192) == 256 and lane_padded(128) == 128
    text = chip_compile(
        lambda q, k, v, t, n, s: paged_decode_attention(
            q, k, v, t, n, scale=192 ** -0.5, window=window,
            sink=s if window else None),
        ((64, 1, 64, 256), jnp.bfloat16),
        ((pages, kv_heads, PAGE, 256), jnp.bfloat16),
        ((pages, kv_heads, PAGE, 128), jnp.bfloat16),
        ((64, 256), jnp.int32), ((64,), jnp.int32), ((64,), jnp.float32))
    assert 'tpu_custom_call' in text


def test_rms_norm_at_a_width_that_is_no_power_of_two(chip_compile):
    """3072 features: 2 MB of rows is 170 of them, and a block of rows has
    to be a multiple of 8."""
    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    text = chip_compile(rms_norm, ((1024, 3072), jnp.bfloat16),
                        ((3072,), jnp.float32))
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('cache_dtype', [jnp.bfloat16, jnp.int8])
def test_decode_attention_headmajor(chip_compile, cache_dtype):
    from paddle_tpu.ops.pallas.paged_attention import (
        decode_attention_headmajor)

    q = ((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = ((SLOTS, HEADS, CTX, HEAD_DIM), cache_dtype)
    vl = ((SLOTS,), jnp.int32)
    if cache_dtype == jnp.int8:
        sc = ((HEADS, HEAD_DIM), jnp.float32)
        text = chip_compile(
            lambda q, k, v, n, ks, vs: decode_attention_headmajor(
                q, k, v, n, k_scale=ks, v_scale=vs), q, kv, kv, vl, sc, sc)
    else:
        text = chip_compile(decode_attention_headmajor, q, kv, kv, vl)
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('rows', [SLOTS, 2048])      # decode, prefill
@pytest.mark.parametrize('bits', [8, 4])
def test_quant_matmul(chip_compile, bits, rows):
    """The weight-only matmul at the 7B MLP's up projection."""
    from paddle_tpu.ops.pallas.quant_matmul import (quant_matmul,
                                                    quant_matmul_int4)

    x = ((rows, HIDDEN), jnp.bfloat16)
    scale = ((FFN,), jnp.float32)
    if bits == 4:
        text = chip_compile(quant_matmul_int4, x,
                            ((HIDDEN // 2, FFN), jnp.int8), scale)
    else:
        text = chip_compile(quant_matmul, x, ((HIDDEN, FFN), jnp.int8),
                            scale)
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('rows,experts,K,N', [
    (256, 32, 3072, 3072),              # Trinity's token-step: 64 x top 4
    (512, 16, 4096, 2048),              # MiMo's, gate and up: 64 x top 8
    (512, 16, 2048, 4096),              # MiMo's, down
    (16384, 16, 4096, 2048),            # MiMo's admission of 2,048 tokens
])
@pytest.mark.parametrize('gated', [True, False])
def test_grouped_matmul(chip_compile, gated, rows, experts, K, N):
    """The served expert layers' products at the benchmark cells' shapes:
    blocks of several MB under the limit the call states for itself."""
    from paddle_tpu.ops.pallas.grouped_matmul import (grouped_gated,
                                                      grouped_matmul)

    x = ((rows, K), jnp.bfloat16)
    w = ((experts, K, N), jnp.bfloat16)
    sizes = ((experts,), jnp.int32)
    if gated:
        text = chip_compile(
            lambda x, g, u, s: grouped_gated(x, g, u, s, jax.nn.silu),
            x, w, w, sizes)
    else:
        text = chip_compile(grouped_matmul, x, w, sizes)
    assert 'tpu_custom_call' in text
