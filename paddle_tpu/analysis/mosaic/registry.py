"""The mosaiclint kernel registry.

Every pallas kernel the repo ships is registered here with the
representative shape/dtype suites bench.py exercises (7B-ish dims:
hidden 4096, heads 32, head_dim 128, vocab 32000, seq 2048), plus the
serving variants the DecodeEngine actually dispatches (GQA, int8
cache, sliding window, paged).  Suites are `jax.ShapeDtypeStruct`s —
nothing is allocated, nothing executes; `make_jaxpr` traces the exact
pallas_calls these shapes would lower on a chip.

A kernel is "covered" when every pallas_call it can emit appears in at
least one entry: forward AND backward (traced through `jax.grad`),
quantized and fp variants, tail shapes.  To add a kernel:

  1. write a `_build_*` returning `(fn, args, kwargs)` over SDS args,
  2. append an `Entry` with a unique `family/variant` name and the
     public entry point as `anchor`,
  3. optionally add an `onchip` runner (real data vs the lax/XLA
     reference) — tools/mosaic_check.py runs it on the chip,
  4. if a rule fires and the kernel is RIGHT, suppress with a reason
     that will survive review.

tests/test_mosaiclint.py's meta-test lints every entry; the bench gate
fails the run on new violations.
"""
from __future__ import annotations

from .engine import Entry


def _sds(shape, dtype_name):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype_name))


# ---------------------------------------------------------------------------
# flash attention (fwd + custom-VJP bwd)
# ---------------------------------------------------------------------------

def _flash_fwd_bwd(**kw):
    def build():
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.flash_attention import flash_attention

        opts = dict(kw)
        B, S, H, D = (opts.pop('B', 1), opts.pop('S', 2048),
                      opts.pop('H', 32), 128)
        q = _sds((B, S, H, D), 'bfloat16')

        def fwd_bwd(q, k, v):
            def loss(q, k, v):
                return flash_attention(
                    q, k, v, **opts).astype(jnp.float32).sum()

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        return fwd_bwd, (q, q, q), {}

    return build


def _build_flash_segmented():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D = 2, 2048, 8, 128
    q = _sds((B, S, H, D), 'bfloat16')
    seg = _sds((B, S), 'int32')

    def fwd(q, k, v, seg):
        return flash_attention(q, k, v, causal=True, segment_ids=seg)

    return fwd, (q, q, q, seg), {}


def _prefill(B, S, H, Hkv, D, Dv, window, sink):
    """The serving prefill's forward kernel at a cell's admission shape
    (grouped heads a step, a banded grid under a window)."""
    def build():
        from paddle_tpu.ops.pallas.flash_attention import prefill_attention

        q = _sds((B, S, H, D), 'bfloat16')
        k = _sds((B, S, Hkv, D), 'bfloat16')
        v = _sds((B, S, Hkv, Dv), 'bfloat16')
        args = (q, k, v) + ((_sds((H,), 'float32'),) if sink else ())
        return (lambda *a: prefill_attention(*a, window=window)), args, {}

    return build


def _onchip_prefill():
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.llama import masked_attention
    from paddle_tpu.ops.pallas.flash_attention import prefill_attention

    rng = np.random.default_rng(0)
    B, S, H, Hkv, D, Dv, w = 1, 1024, 16, 2, 192, 128, 128

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    q, k, v = draw(B, S, H, D), draw(B, S, Hkv, D), draw(B, S, Hkv, Dv)
    sink = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    got = np.asarray(prefill_attention(q, k, v, sink, window=w),
                     np.float32)
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    mask = ((ahead >= 0) & (ahead < w))[None, None]
    want = np.asarray(masked_attention(q, k, v, mask, sink), np.float32)
    assert np.max(np.abs(got - want)) <= 2e-2 * np.max(np.abs(want))


# ---------------------------------------------------------------------------
# decode attention (contiguous cache, serving entry)
# ---------------------------------------------------------------------------

def _build_decode_start():
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    B, S, Hq, Hkv, D = 2, 2048, 32, 8, 128
    q = _sds((B, 1, Hq, D), 'bfloat16')
    kv = _sds((B, S, Hkv, D), 'bfloat16')
    count = _sds((B,), 'int32')
    return (lambda q, k, v, vl, st: decode_attention(q, k, v, vl, start=st),
            (q, kv, kv, count, count), {})


def _build_decode_int8():
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    B, S, Hq, Hkv, D = 8, 2048, 32, 8, 128
    q = _sds((B, 1, Hq, D), 'bfloat16')
    kv8 = _sds((B, S, Hkv, D), 'int8')
    scale = _sds((Hkv, D), 'float32')
    count = _sds((B,), 'int32')
    return (lambda q, k, v, vl, ks, vs: decode_attention(
                q, k, v, vl, k_scale=ks, v_scale=vs),
            (q, kv8, kv8, count, scale, scale), {})


def _build_dispatch_window():
    from paddle_tpu.ops.pallas.decode_attention import (
        dispatch_decode_attention)

    B, S, Hq, Hkv, D = 4, 2048, 32, 32, 128
    q = _sds((B, 1, Hq, D), 'bfloat16')
    kv = _sds((B, S, Hkv, D), 'bfloat16')
    count = _sds((B,), 'int32')
    return (lambda q, k, v, vl: dispatch_decode_attention(
                q, k, v, vl, window=512),
            (q, kv, kv, count), {})


# ---------------------------------------------------------------------------
# paged / head-major attention
# ---------------------------------------------------------------------------

def _build_paged(quant=False):
    def build():
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_decode_attention)

        B, NB, Hkv, BS, D, Hq, MAXB = 2, 32, 8, 128, 128, 8, 4
        q = _sds((B, 1, Hq, D), 'bfloat16')
        cache = _sds((NB, Hkv, BS, D), 'int8' if quant else 'bfloat16')
        tbl = _sds((B, MAXB), 'int32')
        lens = _sds((B,), 'int32')
        if quant:
            scale = _sds((Hkv, D), 'float32')
            return (lambda q, k, v, t, c, ks, vs: paged_decode_attention(
                        q, k, v, t, c, k_scale=ks, v_scale=vs),
                    (q, cache, cache, tbl, lens, scale, scale), {})
        return (paged_decode_attention, (q, cache, cache, tbl, lens), {})

    return build


def _build_paged_rowscale():
    """The QuantPagedKVCache variant: int8 pages whose PER-ROW scales
    ride in page-shaped (NB, Hkv, BS) pools, a page's scales fetched
    with it by the same block-table entry — the serving engine's
    kv_cache_dtype='int8' decode dispatch."""
    def build():
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_decode_attention)

        slots, Hkv, D, Hq = 8, 8, 128, 32
        BS = 32                              # int8 sublane = 32
        maxb = 2048 // BS
        NB = slots * maxb + 1
        q = _sds((slots, 1, Hq, D), 'bfloat16')
        cache = _sds((NB, Hkv, BS, D), 'int8')
        tbl = _sds((slots, maxb), 'int32')
        lens = _sds((slots,), 'int32')
        scale = _sds((NB, Hkv, BS), 'float32')
        return (lambda q, k, v, t, c, ks, vs: paged_decode_attention(
                    q, k, v, t, c, k_scale=ks, v_scale=vs),
                (q, cache, cache, tbl, lens, scale, scale), {})

    return build


def _build_paged_serving(quant=False):
    """The ServingEngine block-table call pattern at a production-scale
    serving geometry: 8 in-flight slots, 2048-token contexts over
    block_size-16 pages (128 table entries per row, full-coverage pool
    + scratch page — the engine's DEFAULT sizing; bench.py's measured
    serve run uses a smaller 4-slot instance of the same pattern). The
    int8-cache variant keeps the pool at int8's 32-sublane page size.
    Inference-only kernels: fwd suites, no VJP."""
    def build():
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_decode_attention)

        slots, Hkv, D, Hq = 8, 8, 128, 32
        BS = 32 if quant else 16             # int8 sublane = 32
        maxb = 2048 // BS                    # ServingEngine max_context
        NB = slots * maxb + 1                # full-coverage pool + scratch
        q = _sds((slots, 1, Hq, D), 'bfloat16')
        cache = _sds((NB, Hkv, BS, D), 'int8' if quant else 'bfloat16')
        tbl = _sds((slots, maxb), 'int32')
        lens = _sds((slots,), 'int32')
        if quant:
            scale = _sds((Hkv, D), 'float32')
            return (lambda q, k, v, t, c, ks, vs: paged_decode_attention(
                        q, k, v, t, c, k_scale=ks, v_scale=vs),
                    (q, cache, cache, tbl, lens, scale, scale), {})
        return (paged_decode_attention, (q, cache, cache, tbl, lens), {})

    return build


def _build_paged_window():
    """AFMoE's window layers at Trinity's widths and the benchmark
    cell's geometry: 64 slots, group 6 (48 query over 8 kv heads), a
    64-entry table, window 4096."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention)

    slots, Hkv, D, Hq, BS, maxb = 64, 8, 128, 48, 16, 64
    cache = _sds((slots * maxb + 1, Hkv, BS, D), 'bfloat16')
    return (lambda q, k, v, t, c: paged_decode_attention(
                q, k, v, t, c, window=4096),
            (_sds((slots, 1, Hq, D), 'bfloat16'), cache, cache,
             _sds((slots, maxb), 'int32'), _sds((slots,), 'int32')), {})


def _build_headmajor():
    from paddle_tpu.ops.pallas.paged_attention import (
        decode_attention_headmajor)

    B, Hkv, S, D, Hq = 2, 8, 1024, 128, 8
    q = _sds((B, 1, Hq, D), 'bfloat16')
    kv = _sds((B, Hkv, S, D), 'bfloat16')
    lens = _sds((B,), 'int32')
    return decode_attention_headmajor, (q, kv, kv, lens), {}


# ---------------------------------------------------------------------------
# quantized matmul (int8 / fp8 / packed int4)
# ---------------------------------------------------------------------------

def _build_quant_matmul(weight_dtype='int8'):
    def build():
        from paddle_tpu.ops.pallas.quant_matmul import (quant_matmul,
                                                        quant_matmul_int4)

        M, K, N = 2048, 4096, 4096
        x = _sds((M, K), 'bfloat16')
        scale = _sds((N,), 'float32')
        if weight_dtype == 'int4':
            wq = _sds((K // 2, N), 'int8')
            return quant_matmul_int4, (x, wq, scale), {}
        wq = _sds((K, N), weight_dtype)
        return quant_matmul, (x, wq, scale), {}

    return build


# ---------------------------------------------------------------------------
# grouped matmul (a served rank's expert products)
# ---------------------------------------------------------------------------

def _build_grouped(rows, experts, K, N, gated=False):
    """The benchmark's expert cells: `rows` (token, choice) pairs sorted
    by held expert, `experts` held matrices of (K, N)."""
    def build():
        import jax

        from paddle_tpu.ops.pallas.grouped_matmul import (grouped_gated,
                                                          grouped_matmul)

        x = _sds((rows, K), 'bfloat16')
        w = _sds((experts, K, N), 'bfloat16')
        sizes = _sds((experts,), 'int32')
        if gated:
            return (lambda x, g, u, s: grouped_gated(x, g, u, s,
                                                     jax.nn.silu),
                    (x, w, w, sizes), {})
        return grouped_matmul, (x, w, sizes), {}

    return build


# ---------------------------------------------------------------------------
# rms_norm / softmax_xent (fwd + bwd)
# ---------------------------------------------------------------------------

def _build_rms(rows):
    def build():
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.rms_norm import rms_norm

        x = _sds((rows, 4096), 'bfloat16')
        w = _sds((4096,), 'bfloat16')

        def fwd_bwd(x, w):
            def loss(x, w):
                return rms_norm(x, w).astype(jnp.float32).sum()

            return jax.grad(loss, argnums=(0, 1))(x, w)

        return fwd_bwd, (x, w), {}

    return build


def _build_xent():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.softmax_xent import (
        softmax_cross_entropy_with_logits)

    logits = _sds((12288, 32000), 'float32')
    labels = _sds((12288,), 'int32')

    def fwd_bwd(logits, labels):
        def loss(logits):
            return softmax_cross_entropy_with_logits(logits, labels).sum()

        return jax.value_and_grad(loss)(logits)

    return fwd_bwd, (logits, labels), {}


# ---------------------------------------------------------------------------
# on-chip runners (tools/mosaic_check.py) — real data vs references
# ---------------------------------------------------------------------------

def _onchip_decode_start():
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional.attention import _sdpa_reference
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.default_rng(0)
    B, S, H, D = 2, 512, 8, 128
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.bfloat16)
    ck = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    start = jnp.asarray([3, 200], jnp.int32)
    valid = jnp.asarray([400, 512], jnp.int32)
    out = np.asarray(decode_attention(q, ck, cv, valid, start=start))
    assert np.isfinite(out).all()
    mask = ((np.arange(S)[None, :] < np.asarray(valid)[:, None])
            & (np.arange(S)[None, :] >= np.asarray(start)[:, None]))
    want = np.asarray(_sdpa_reference(
        q.astype(jnp.float32), ck.astype(jnp.float32),
        cv.astype(jnp.float32),
        attn_mask=jnp.asarray(mask)[:, None, None, :]))
    assert np.max(np.abs(out.astype(np.float32) - want)) < 3e-2


def _onchip_decode_int8():
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.generation import (calibrate_kv_scale,
                                              quantize_kv_rows)
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.default_rng(0)
    B, S, H, D = 2, 512, 8, 128
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.bfloat16)
    ck = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    ks, vs = calibrate_kv_scale(ck), calibrate_kv_scale(cv)
    k8, v8 = quantize_kv_rows(ck, ks), quantize_kv_rows(cv, vs)
    got = np.asarray(decode_attention(q, k8, v8, 400,
                                      k_scale=ks, v_scale=vs))
    want = np.asarray(decode_attention(
        q, ck.astype(jnp.bfloat16), cv.astype(jnp.bfloat16), 400))
    assert np.isfinite(got).all()
    assert np.max(np.abs(got.astype(np.float32)
                         - want.astype(np.float32))) < 5e-2


def _onchip_flash_window():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    B, S, H, D = 1, 2048, 4, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True, window_size=256)
    assert np.isfinite(np.asarray(out).astype(np.float32)).all()
    g = jax.grad(lambda a: flash_attention(
        a, a, a, causal=True,
        window_size=256).astype(jnp.float32).sum())(q)
    assert np.isfinite(np.asarray(g).astype(np.float32)).all()


def _onchip_paged():
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    NB, Hkv, BS, D, B, Hq = 32, 8, 128, 128, 2, 8
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.bfloat16)
    tbl = jnp.asarray([[3, 7, 1, 12], [0, 5, 9, 2]], jnp.int32)
    out = np.asarray(paged_decode_attention(
        q, kc, vc, tbl, jnp.asarray([300, 512], jnp.int32)))
    assert np.isfinite(out.astype(np.float32)).all()


def _onchip_serve_decode():
    """Serving-shape paged decode on chip: ServingEngine's default
    block_size-16 pages, shuffled non-contiguous tables, ragged
    per-row lengths."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    slots, NB, Hkv, BS, D, Hq, maxb = 4, 64, 8, 16, 128, 32, 8
    q = jnp.asarray(rng.normal(size=(slots, 1, Hq, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.bfloat16)
    tbl = jnp.asarray(rng.permutation(np.arange(1, NB))[:slots * maxb]
                      .reshape(slots, maxb), jnp.int32)
    lens = jnp.asarray([17, 128, 63, 96], jnp.int32)
    out = np.asarray(paged_decode_attention(q, kc, vc, tbl, lens))
    assert np.isfinite(out.astype(np.float32)).all()


def _onchip_serve_decode_window():
    """Rows of very different lengths behind a window of 100 positions,
    two idle slots among them, against the gathered pages in float32:
    the loop's bounds, the chunks in flight and the masks, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    slots, Hkv, BS, D, Hq, maxb, window = 8, 8, 16, 128, 48, 64, 100
    NB = slots * maxb + 1
    q = jnp.asarray(rng.normal(size=(slots, 1, Hq, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.bfloat16)
    tbl = jnp.asarray(rng.permutation(np.arange(1, NB)).reshape(slots, maxb),
                      jnp.int32)
    lens = jnp.asarray([1, 1024, 0, 513, 512, 17, 700, 128], jnp.int32)
    for win in (None, window):
        got = np.asarray(paged_decode_attention(q, kc, vc, tbl, lens,
                                                window=win), np.float32)
        k = jnp.swapaxes(kc[tbl], 2, 3).reshape(slots, maxb * BS, Hkv, D)
        v = jnp.swapaxes(vc[tbl], 2, 3).reshape(slots, maxb * BS, Hkv, D)
        qg = q[:, 0].astype(jnp.float32).reshape(slots, Hkv, Hq // Hkv, D)
        s = jnp.einsum('bhgd,bshd->bhgs', qg, k.astype(jnp.float32),
                       precision='highest') / D ** 0.5
        pos = jnp.arange(maxb * BS)[None]
        seen = (pos < lens[:, None]) & (
            pos >= lens[:, None] - (win or maxb * BS))
        p = jnp.where(seen[:, None, None], jax.nn.softmax(
            jnp.where(seen[:, None, None], s, -1e30), -1), 0.0)
        want = np.asarray(jnp.einsum(
            'bhgs,bshd->bhgd', p, v.astype(jnp.float32),
            precision='highest')).reshape(got.shape)
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() < 2e-2, np.abs(got - want).max()


def _onchip_headmajor():
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import (
        decode_attention_headmajor)

    rng = np.random.default_rng(0)
    B, Hkv, S, D, Hq = 2, 8, 1024, 128, 8
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.bfloat16)
    ck = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.bfloat16)
    out = np.asarray(decode_attention_headmajor(
        q, ck, cv, jnp.asarray([800, 1024], jnp.int32)))
    assert np.isfinite(out.astype(np.float32)).all()


def _onchip_grouped():
    """Groups of 0 to 40 rows over two row tiles' worth of slabs, an
    empty expert and un-held rows behind, against `lax.ragged_dot`: the
    schedule, the masked slabs and both epilogues, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.grouped_matmul import (grouped_gated,
                                                      grouped_matmul)

    rng = np.random.default_rng(0)
    for rows, sizes in ((256, [3, 0, 17, 1, 40, 0, 5, 9]),
                        (2048, [130, 0, 1000, 64, 16, 1, 500, 0])):
        E, K, N = len(sizes), 512, 1024
        x = jnp.asarray(rng.normal(size=(rows, K)), jnp.bfloat16)
        wg, wu = (jnp.asarray(rng.normal(size=(E, K, N)) * K ** -0.5,
                              jnp.bfloat16) for _ in range(2))
        gs = jnp.asarray(sizes, jnp.int32)
        held = sum(sizes)

        def ragged(w):
            return jax.lax.ragged_dot(x, w, gs,
                                      preferred_element_type=jnp.float32)

        got = np.asarray(grouped_matmul(x, wg, gs))[:held]
        want = np.asarray(ragged(wg))[:held]
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() < 2e-2, np.abs(got - want).max()
        got = np.asarray(grouped_gated(x, wg, wu, gs, jax.nn.silu).astype(
            jnp.float32))[:held]
        want = np.asarray((jax.nn.silu(ragged(wg)) * ragged(wu)).astype(
            jnp.bfloat16).astype(jnp.float32))[:held]
        assert np.abs(got - want).max() < 5e-2, np.abs(got - want).max()


def _onchip_quant_matmul_int4():
    """The packed-int4 unpack (widen, shift, interleave) against the
    native-XLA path over the same packed codes."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.quant_matmul import (_quant_matmul_xla,
                                                    _unpack_int4,
                                                    quant_matmul_int4,
                                                    quantize_weight_int4)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 1024)), jnp.bfloat16)
    wq, scale = quantize_weight_int4(
        jnp.asarray(rng.normal(size=(1024, 512)), jnp.float32))
    got = np.asarray(quant_matmul_int4(x, wq, scale), np.float32)
    want = np.asarray(_quant_matmul_xla(x, _unpack_int4(wq), scale,
                                        jnp.bfloat16), np.float32)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) <= 2e-2 * np.max(np.abs(want))


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_FLASH = 'paddle_tpu.ops.pallas.flash_attention:flash_attention'
_PREFILL = 'paddle_tpu.ops.pallas.flash_attention:prefill_attention'
_DECODE = 'paddle_tpu.ops.pallas.decode_attention:decode_attention'
_DISPATCH = ('paddle_tpu.ops.pallas.decode_attention:'
             'dispatch_decode_attention')
_PAGED = 'paddle_tpu.ops.pallas.paged_attention:paged_decode_attention'
_HEADMAJOR = ('paddle_tpu.ops.pallas.paged_attention:'
              'decode_attention_headmajor')
_GMM = 'paddle_tpu.ops.pallas.grouped_matmul:grouped_matmul'
_GATED = 'paddle_tpu.ops.pallas.grouped_matmul:grouped_gated'
_QMM = 'paddle_tpu.ops.pallas.quant_matmul:quant_matmul'
_QMM4 = 'paddle_tpu.ops.pallas.quant_matmul:quant_matmul_int4'
_RMS = 'paddle_tpu.ops.pallas.rms_norm:rms_norm'
_XENT = ('paddle_tpu.ops.pallas.softmax_xent:'
         'softmax_cross_entropy_with_logits')

ENTRIES = (
    Entry('flash_attention/causal_fwd_bwd', _FLASH,
          _flash_fwd_bwd(causal=True)),
    Entry('flash_attention/window_fwd_bwd', _FLASH,
          _flash_fwd_bwd(H=4, causal=True, window_size=256),
          onchip=_onchip_flash_window),
    Entry('flash_attention/tail_fwd_bwd', _FLASH,
          _flash_fwd_bwd(S=1792, H=8, causal=True)),
    Entry('flash_attention/segmented_fwd', _FLASH, _build_flash_segmented),
    Entry('flash_attention/mimo_window_prefill', _PREFILL,
          _prefill(1, 2048, 64, 8, 192, 128, 128, True),
          onchip=_onchip_prefill),
    Entry('flash_attention/mimo_full_prefill', _PREFILL,
          _prefill(1, 2048, 64, 4, 192, 128, None, False)),
    Entry('flash_attention/mistral_prefill', _PREFILL,
          _prefill(1, 1024, 32, 8, 128, 128, None, False)),
    Entry('flash_attention/trinity_prefill', _PREFILL,
          _prefill(4, 256, 48, 8, 128, 128, None, False)),
    Entry('decode_attention/bf16_start', _DECODE, _build_decode_start,
          onchip=_onchip_decode_start),
    Entry('decode_attention/int8_cache', _DECODE, _build_decode_int8,
          onchip=_onchip_decode_int8),
    Entry('decode_attention/dispatch_window', _DISPATCH,
          _build_dispatch_window),
    Entry('paged_attention/paged', _PAGED, _build_paged(),
          onchip=_onchip_paged),
    Entry('paged_attention/paged_int8', _PAGED, _build_paged(quant=True)),
    Entry('paged_attention/serve_decode', _PAGED, _build_paged_serving(),
          onchip=_onchip_serve_decode),
    Entry('paged_attention/serve_decode_int8', _PAGED,
          _build_paged_serving(quant=True)),
    Entry('paged_attention/serve_decode_int8_rowscale', _PAGED,
          _build_paged_rowscale()),
    Entry('paged_attention/serve_decode_window', _PAGED, _build_paged_window,
          onchip=_onchip_serve_decode_window),
    Entry('paged_attention/headmajor', _HEADMAJOR, _build_headmajor,
          onchip=_onchip_headmajor),
    Entry('grouped_matmul/trinity_decode_gated', _GATED,
          _build_grouped(256, 32, 3072, 3072, gated=True),
          onchip=_onchip_grouped),
    Entry('grouped_matmul/trinity_decode_down', _GMM,
          _build_grouped(256, 32, 3072, 3072)),
    Entry('grouped_matmul/mimo_decode_gated', _GATED,
          _build_grouped(512, 16, 4096, 2048, gated=True)),
    Entry('grouped_matmul/mimo_decode_down', _GMM,
          _build_grouped(512, 16, 2048, 4096)),
    Entry('grouped_matmul/mimo_admission_gated', _GATED,
          _build_grouped(16384, 16, 4096, 2048, gated=True)),
    Entry('quant_matmul/int8', _QMM, _build_quant_matmul('int8')),
    Entry('quant_matmul/fp8', _QMM, _build_quant_matmul('float8_e4m3fn')),
    Entry('quant_matmul/int4', _QMM4, _build_quant_matmul('int4'),
          onchip=_onchip_quant_matmul_int4),
    Entry('rms_norm/fwd_bwd', _RMS, _build_rms(12288)),
    Entry('rms_norm/ragged_rows', _RMS, _build_rms(1000),
          suppress={
              'ML002': 'row-tail blocks read unspecified rows but every '
                       'kernel (fwd and dx) maps rows independently with '
                       'no cross-row reduction: garbage rows land only '
                       'in the discarded pad region of the output, never '
                       'in a live row (dw reduces OUTSIDE the kernel '
                       'over the unpadded array)',
          }),
    Entry('softmax_xent/fwd_bwd', _XENT, _build_xent),
)


def all_entries():
    """Every registered kernel suite, in registry order."""
    return list(ENTRIES)


def entries_for(paths=None, root=None):
    """Entries whose anchor file falls under one of `paths` (root-
    relative prefixes); all of them when `paths` is falsy."""
    entries = all_entries()
    if not paths:
        return entries
    import os

    root = root or os.getcwd()
    norm = []
    for p in paths:
        if os.path.isabs(p):
            try:
                p = os.path.relpath(p, root)
            except ValueError:
                pass
        norm.append(os.path.normpath(p).replace(os.sep, '/'))
    out = []
    for e in entries:
        path, _ = e.resolve_anchor(root=root)
        if any(path == p or path.startswith(p.rstrip('/') + '/')
               for p in norm):
            out.append(e)
    return out
