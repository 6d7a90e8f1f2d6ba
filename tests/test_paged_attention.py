"""Paged (block-table) serving attention.

ref: python/paddle/incubate/nn/functional/block_multihead_attention.py:30
and masked_multihead_attention.py:74. The pallas kernel's block table is
scalar-prefetched and drives the BlockSpec index map; these tests verify
it against a gather-then-mask reference (interpret mode on CPU), then the
API wrappers end-to-end: prefill writes pages, decode reads them, int8
pages dequantize, and a multi-step loop matches contiguous-cache
generation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt  # noqa: F401 - env/flags init
from paddle_tpu.incubate.nn.functional import (block_multihead_attention,
                                               masked_multihead_attention)
from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention


def _gather_ref(q, kc, vc, tbl, counts):
    """Reference: gather pages to contiguous, masked softmax."""
    B = q.shape[0]
    NB, Hkv, BS, D = kc.shape
    maxb = tbl.shape[1]
    ck = kc[np.clip(np.asarray(tbl), 0, NB - 1)]         # (B,MAXB,Hkv,BS,D)
    cv = vc[np.clip(np.asarray(tbl), 0, NB - 1)]
    ck = jnp.swapaxes(jnp.asarray(ck), 2, 3).reshape(B, maxb * BS, Hkv, D)
    cv = jnp.swapaxes(jnp.asarray(cv), 2, 3).reshape(B, maxb * BS, Hkv, D)
    Hq = q.shape[2]
    rep = Hq // Hkv
    ckr = jnp.repeat(ck.astype(jnp.float32), rep, axis=2)
    cvr = jnp.repeat(cv.astype(jnp.float32), rep, axis=2)
    logits = jnp.einsum('bhd,bshd->bhs', q[:, 0].astype(jnp.float32),
                        ckr) / (q.shape[-1] ** 0.5)
    msk = jnp.arange(maxb * BS)[None, None, :] < counts[:, None, None]
    p = jax.nn.softmax(jnp.where(msk, logits, -1e30), axis=-1)
    return jnp.einsum('bhs,bshd->bhd', p, cvr)[:, None].astype(q.dtype)


class TestPagedKernel:
    def test_matches_gather_reference(self):
        rng = np.random.default_rng(0)
        B, NB, Hkv, BS, D, Hq, MAXB = 3, 16, 2, 32, 16, 4, 4
        q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        # rows use non-contiguous, shuffled pages; row 2 short
        tbl = jnp.asarray([[3, 7, 1, 12], [0, 5, 9, 2], [14, 6, -1, -1]],
                          jnp.int32)
        counts = jnp.asarray([100, 128, 40], jnp.int32)
        got = paged_decode_attention(q, kc, vc, tbl, counts)
        want = _gather_ref(q, kc, vc, tbl, counts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize('window', [5, 32, 40, 64, 500])
    def test_window_matches_gather_reference(self, window):
        """Only the last `window` positions count, whether the window
        starts inside the first page, on a page's edge, or before the
        row began; pages behind it are skipped."""
        rng = np.random.default_rng(1)
        B, NB, Hkv, BS, D, Hq, MAXB = 3, 16, 2, 32, 16, 4, 4
        q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        tbl = jnp.asarray([[3, 7, 1, 12], [0, 5, 9, 2], [14, 6, -1, -1]],
                          jnp.int32)
        counts = jnp.asarray([100, 128, 40], jnp.int32)
        got = paged_decode_attention(q, kc, vc, tbl, counts, window=window)
        # the reference sees a window as keys before it zeroed out of the
        # softmax: mask by moving the start
        pos = jnp.arange(MAXB * BS)[None, :]
        seen = (pos < counts[:, None]) & (pos >= counts[:, None] - window)
        ck = kc[np.clip(np.asarray(tbl), 0, NB - 1)]
        cv = vc[np.clip(np.asarray(tbl), 0, NB - 1)]
        ck = jnp.swapaxes(ck, 2, 3).reshape(B, MAXB * BS, Hkv, D)
        cv = jnp.swapaxes(cv, 2, 3).reshape(B, MAXB * BS, Hkv, D)
        ck, cv = (jnp.repeat(x, Hq // Hkv, axis=2) for x in (ck, cv))
        logits = jnp.einsum('bhd,bshd->bhs', q[:, 0], ck) / (D ** 0.5)
        p = jax.nn.softmax(jnp.where(seen[:, None], logits, -1e30), -1)
        want = jnp.einsum('bhs,bshd->bhd', p, cv)[:, None]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        if window >= 128:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(paged_decode_attention(
                    q, kc, vc, tbl, counts)), rtol=1e-6, atol=1e-6)

    def test_int8_pages_dequantize(self):
        from paddle_tpu.models.generation import (calibrate_kv_scale,
                                                  quantize_kv_rows)

        rng = np.random.default_rng(1)
        B, NB, Hkv, BS, D, Hq = 2, 8, 2, 32, 16, 4
        q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
        kf = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        vf = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        # calibrate over (pages, slots) per (head, dim): move axes so the
        # shared helper sees (N, S, H, D)
        ks = calibrate_kv_scale(jnp.swapaxes(kf, 1, 2))
        vs = calibrate_kv_scale(jnp.swapaxes(vf, 1, 2))
        k8 = jnp.swapaxes(quantize_kv_rows(jnp.swapaxes(kf, 1, 2), ks), 1, 2)
        v8 = jnp.swapaxes(quantize_kv_rows(jnp.swapaxes(vf, 1, 2), vs), 1, 2)
        tbl = jnp.asarray([[0, 3], [5, 1]], jnp.int32)
        counts = jnp.asarray([60, 64], jnp.int32)
        got = paged_decode_attention(q, k8, v8, tbl, counts,
                                     k_scale=ks, v_scale=vs)
        want = paged_decode_attention(q, kf, vf, tbl, counts)
        assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < 1e-2


class TestMaskedMHA:
    def test_matches_einsum_reference_and_writes_cache(self):
        rng = np.random.default_rng(2)
        B, H, S, D = 2, 4, 32, 16
        x = jnp.asarray(rng.normal(size=(B, 3 * H * D)), jnp.float32)
        cache = jnp.asarray(rng.normal(size=(2, B, H, S, D)), jnp.float32)
        lens = jnp.asarray([[5], [17]], jnp.int32)
        out, new_cache = masked_multihead_attention(
            x, cache_kv=cache, sequence_lengths=lens)
        assert out.shape == (B, H * D)
        # the new k/v row landed at each row's length
        q, k, v = np.split(np.asarray(x).reshape(B, 3, H, D), 3, axis=1)
        np.testing.assert_allclose(np.asarray(new_cache[0][0, :, 5]),
                                   k[0, 0], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(new_cache[1][1, :, 17]),
                                   v[1, 0], rtol=1e-6)
        # reference attention over the updated cache
        ck, cv = np.asarray(new_cache[0]), np.asarray(new_cache[1])
        for b, L in ((0, 6), (1, 18)):
            logits = np.einsum('hd,hsd->hs', q[b, 0], ck[b]) / np.sqrt(D)
            logits[:, L:] = -1e30
            p = jax.nn.softmax(jnp.asarray(logits), axis=-1)
            want = np.einsum('hs,hsd->hd', np.asarray(p), cv[b])
            np.testing.assert_allclose(
                np.asarray(out)[b].reshape(H, D), want, rtol=2e-4,
                atol=2e-4)

    def test_smoothquant_knobs_rejected(self):
        x = jnp.zeros((1, 3 * 2 * 8), jnp.float32)
        cache = jnp.zeros((2, 1, 2, 8, 8), jnp.float32)
        with pytest.raises(NotImplementedError, match='smooth-quant'):
            masked_multihead_attention(
                x, cache, sequence_lengths=jnp.ones((1, 1), jnp.int32),
                qkv_out_scale=jnp.ones((3, 2, 8)))


class TestBlockMHA:
    def _setup(self, quant=False):
        rng = np.random.default_rng(3)
        B, Hq, Hkv, D, BS, NB, MAXB = 2, 4, 2, 16, 16, 12, 4
        dtype = jnp.int8 if quant else jnp.float32
        kc = jnp.zeros((NB, Hkv, BS, D), dtype)
        vc = jnp.zeros((NB, Hkv, BS, D), dtype)
        tbl = jnp.asarray([[2, 7, 4, 9], [0, 5, 11, 1]], jnp.int32)
        return rng, B, Hq, Hkv, D, BS, kc, vc, tbl

    def test_prefill_then_decode_matches_contiguous(self):
        """Serving flow: varlen prefill writes pages, then 3 decode
        steps; every step must match a contiguous-cache reference."""
        rng, B, Hq, Hkv, D, BS, kc, vc, tbl = self._setup()
        lens = [20, 33]
        T = sum(lens)
        qkv = jnp.asarray(rng.normal(size=(T, (Hq + 2 * Hkv) * D)),
                          jnp.float32)
        cu = jnp.asarray([0, lens[0], T], jnp.int32)
        out, _, kc, vc = block_multihead_attention(
            qkv, kc, vc,
            seq_lens_encoder=jnp.asarray([[lens[0]], [lens[1]]], jnp.int32),
            seq_lens_decoder=jnp.zeros((B, 1), jnp.int32),
            seq_lens_this_time=jnp.asarray([[lens[0]], [lens[1]]],
                                           jnp.int32),
            cu_seqlens_q=cu, cu_seqlens_k=cu, block_tables=tbl,
            block_size=BS, num_heads=Hq, num_kv_heads=Hkv)
        # reference: per-sequence causal attention on the same tokens
        from paddle_tpu.nn.functional.attention import _sdpa_reference
        from paddle_tpu.incubate.nn.functional import _split_qkv

        q, k, v = _split_qkv(qkv, Hq, Hkv, D)
        o0 = _sdpa_reference(q[None, :lens[0]], k[None, :lens[0]],
                             v[None, :lens[0]], is_causal=True)[0]
        o1 = _sdpa_reference(q[None, lens[0]:], k[None, lens[0]:],
                             v[None, lens[0]:], is_causal=True)[0]
        want = jnp.concatenate([o0, o1]).reshape(T, Hq * D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

        # ---- decode steps over the filled pages ----------------------
        ctx = np.asarray(lens)
        for step in range(3):
            dq = jnp.asarray(
                rng.normal(size=(B, (Hq + 2 * Hkv) * D)), jnp.float32)
            out_d, _, kc, vc = block_multihead_attention(
                dq, kc, vc,
                seq_lens_encoder=jnp.zeros((B, 1), jnp.int32),
                seq_lens_decoder=jnp.asarray(ctx[:, None], jnp.int32),
                seq_lens_this_time=jnp.ones((B, 1), jnp.int32),
                block_tables=tbl, block_size=BS, num_heads=Hq,
                num_kv_heads=Hkv)
            # contiguous reference: gather pages and attend
            qd, kd, vd = _split_qkv(dq, Hq, Hkv, D)
            got_ref = _gather_ref(qd[:, None], kc, vc, tbl,
                                  jnp.asarray(ctx + 1, jnp.int32))
            np.testing.assert_allclose(
                np.asarray(out_d).reshape(B, 1, Hq, D),
                np.asarray(got_ref), rtol=2e-4, atol=2e-4,
                err_msg=f'decode step {step}')
            ctx += 1

    def test_static_cache_int8(self):
        """int8 pages with static per-head dequant scales: decode output
        tracks the fp page run within quantization noise."""
        rng, B, Hq, Hkv, D, BS, kc8, vc8, tbl = self._setup(quant=True)
        kcf = jnp.zeros(kc8.shape, jnp.float32)
        vcf = jnp.zeros(vc8.shape, jnp.float32)
        scales = jnp.full((Hkv,), 0.05, jnp.float32)
        lens = [16, 16]
        T = sum(lens)
        qkv = jnp.asarray(rng.normal(size=(T, (Hq + 2 * Hkv) * D)),
                          jnp.float32)
        cu = jnp.asarray([0, 16, 32], jnp.int32)
        kw = dict(
            seq_lens_encoder=jnp.asarray([[16], [16]], jnp.int32),
            seq_lens_decoder=jnp.zeros((B, 1), jnp.int32),
            seq_lens_this_time=jnp.asarray([[16], [16]], jnp.int32),
            cu_seqlens_q=cu, cu_seqlens_k=cu, block_tables=tbl,
            block_size=BS, num_heads=Hq, num_kv_heads=Hkv)
        _, _, kc8, vc8 = block_multihead_attention(
            qkv, kc8, vc8, cache_k_dequant_scales=scales,
            cache_v_dequant_scales=scales, **kw)
        _, _, kcf, vcf = block_multihead_attention(qkv, kcf, vcf, **kw)

        dq = jnp.asarray(rng.normal(size=(B, (Hq + 2 * Hkv) * D)),
                         jnp.float32)
        dkw = dict(
            seq_lens_encoder=jnp.zeros((B, 1), jnp.int32),
            seq_lens_decoder=jnp.asarray([[16], [16]], jnp.int32),
            seq_lens_this_time=jnp.ones((B, 1), jnp.int32),
            block_tables=tbl, block_size=BS, num_heads=Hq,
            num_kv_heads=Hkv)
        out8, _, _, _ = block_multihead_attention(
            dq, kc8, vc8, cache_k_dequant_scales=scales,
            cache_v_dequant_scales=scales, **dkw)
        outf, _, _, _ = block_multihead_attention(dq, kcf, vcf, **dkw)
        assert np.max(np.abs(np.asarray(out8) - np.asarray(outf))) < 5e-2

    def test_mixed_phase_rejected(self):
        rng, B, Hq, Hkv, D, BS, kc, vc, tbl = self._setup()
        qkv = jnp.zeros((3, (Hq + 2 * Hkv) * D), jnp.float32)
        with pytest.raises(NotImplementedError, match='mixed'):
            block_multihead_attention(
                qkv, kc, vc,
                seq_lens_encoder=jnp.asarray([[2], [0]], jnp.int32),
                seq_lens_decoder=jnp.asarray([[0], [5]], jnp.int32),
                seq_lens_this_time=jnp.asarray([[2], [1]], jnp.int32),
                cu_seqlens_q=jnp.asarray([0, 2, 3], jnp.int32),
                cu_seqlens_k=jnp.asarray([0, 2, 3], jnp.int32),
                block_tables=tbl, block_size=BS, num_heads=Hq,
                num_kv_heads=Hkv)


class TestDispatch:
    def test_block_mha_decode_dispatches_paged_kernel(self, monkeypatch):
        import paddle_tpu.ops as ops
        from paddle_tpu.ops.pallas import paged_attention as kmod

        calls = []
        orig = kmod.paged_decode_attention

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        monkeypatch.setattr(ops, '_on_tpu', lambda: True)
        monkeypatch.setattr(kmod, 'paged_decode_attention', spy)
        pt.set_flags({'FLAGS_use_pallas_kernels': True})

        rng = np.random.default_rng(5)
        B, Hq, Hkv, D, BS, NB = 2, 4, 2, 16, 16, 8
        kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        tbl = jnp.asarray([[0, 3], [5, 1]], jnp.int32)
        dq = jnp.asarray(rng.normal(size=(B, (Hq + 2 * Hkv) * D)),
                         jnp.float32)
        out, _, _, _ = block_multihead_attention(
            dq, kc, vc,
            seq_lens_encoder=jnp.zeros((B, 1), jnp.int32),
            seq_lens_decoder=jnp.asarray([[10], [20]], jnp.int32),
            seq_lens_this_time=jnp.ones((B, 1), jnp.int32),
            block_tables=tbl, block_size=BS, num_heads=Hq,
            num_kv_heads=Hkv)
        assert calls, 'paged kernel was not dispatched'
        assert out.shape == (B, Hq * D)


class TestReviewRegressions:
    def test_headmajor_kernel_matches_reference(self):
        from paddle_tpu.ops.pallas.paged_attention import (
            decode_attention_headmajor)

        rng = np.random.default_rng(7)
        B, Hkv, S, D, Hq = 2, 2, 96, 16, 4
        q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
        ck = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
        cv = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
        counts = jnp.asarray([40, 96], jnp.int32)
        got = decode_attention_headmajor(q, ck, cv, counts, block_s=32)
        # reference via the contiguous kernel on the transposed layout
        from paddle_tpu.ops.pallas.decode_attention import decode_attention

        want = decode_attention(q, jnp.swapaxes(ck, 1, 2),
                                jnp.swapaxes(cv, 1, 2), counts, block_s=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_masked_mha_int8_cache_rejected(self):
        x = jnp.zeros((1, 3 * 2 * 8), jnp.float32)
        cache = jnp.zeros((2, 1, 2, 8, 8), jnp.int8)
        with pytest.raises(NotImplementedError, match='int8'):
            masked_multihead_attention(
                x, cache, sequence_lengths=jnp.ones((1, 1), jnp.int32))

    def test_inactive_decode_rows_do_not_write(self):
        rng = np.random.default_rng(8)
        B, Hq, Hkv, D, BS, NB = 2, 4, 2, 16, 16, 8
        kc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NB, Hkv, BS, D)), jnp.float32)
        tbl = jnp.asarray([[0, 3], [5, 1]], jnp.int32)
        before_k = np.asarray(kc)
        dq = jnp.asarray(rng.normal(size=(B, (Hq + 2 * Hkv) * D)),
                         jnp.float32)
        # row 1 finished: seq_lens_this_time 0 — its page row 5 slot 0
        # (lens=0 -> page tbl[1,0]) must stay untouched
        _, _, kc2, _ = block_multihead_attention(
            dq, kc, vc,
            seq_lens_encoder=jnp.zeros((B, 1), jnp.int32),
            seq_lens_decoder=jnp.asarray([[10], [0]], jnp.int32),
            seq_lens_this_time=jnp.asarray([[1], [0]], jnp.int32),
            block_tables=tbl, block_size=BS, num_heads=Hq,
            num_kv_heads=Hkv)
        after_k = np.asarray(kc2)
        np.testing.assert_array_equal(after_k[5], before_k[5])
        # the active row DID write (page 0, slot 10)
        assert not np.array_equal(after_k[0, :, 10], before_k[0, :, 10])

    def test_interleaved_rope_differs_from_neox(self):
        """use_neox_rotary_style flag is honored: the two styles give
        different outputs on the same inputs."""
        rng = np.random.default_rng(9)
        B, H, S, D = 1, 2, 16, 8
        x = jnp.asarray(rng.normal(size=(B, 3 * H * D)), jnp.float32)
        cache = jnp.zeros((2, B, H, S, D), jnp.float32)
        rt = jnp.asarray(rng.normal(size=(2, B, S, D // 2)), jnp.float32)
        lens = jnp.asarray([[3]], jnp.int32)
        out_gj, _ = masked_multihead_attention(
            x, cache, sequence_lengths=lens, rotary_tensor=rt,
            use_neox_rotary_style=False)
        out_nx, _ = masked_multihead_attention(
            x, cache, sequence_lengths=lens, rotary_tensor=rt,
            use_neox_rotary_style=True)
        assert not np.allclose(np.asarray(out_gj), np.asarray(out_nx))


class TestCapacityGuards:
    def test_block_mha_page_capacity_exceeded(self):
        B, Hq, Hkv, D, BS, NB = 1, 4, 2, 16, 16, 8
        kc = jnp.zeros((NB, Hkv, BS, D), jnp.float32)
        vc = jnp.zeros((NB, Hkv, BS, D), jnp.float32)
        tbl = jnp.asarray([[0, 1]], jnp.int32)            # 2 pages = 32 slots
        dq = jnp.zeros((B, (Hq + 2 * Hkv) * D), jnp.float32)
        with pytest.raises(ValueError, match='capacity'):
            block_multihead_attention(
                dq, kc, vc,
                seq_lens_encoder=jnp.zeros((B, 1), jnp.int32),
                seq_lens_decoder=jnp.asarray([[32]], jnp.int32),  # full
                seq_lens_this_time=jnp.ones((B, 1), jnp.int32),
                block_tables=tbl, block_size=BS, num_heads=Hq,
                num_kv_heads=Hkv)

    def test_masked_mha_full_cache_rejected(self):
        x = jnp.zeros((1, 3 * 2 * 8), jnp.float32)
        cache = jnp.zeros((2, 1, 2, 8, 8), jnp.float32)
        with pytest.raises(ValueError, match='full'):
            masked_multihead_attention(
                x, cache, sequence_lengths=jnp.asarray([[8]], jnp.int32))
