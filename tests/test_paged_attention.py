"""Paged (block-table) serving attention.

ref: python/paddle/incubate/nn/functional/block_multihead_attention.py:30
and masked_multihead_attention.py:74. The pallas kernel walks each row's
own pages, named by the scalar-prefetched block table, several a loop
step; these tests verify it against a gather-then-mask reference
(interpret mode on CPU), with chunks made small enough that tiny rows
cross them, then the API wrappers end-to-end: prefill writes pages,
decode reads them, int8 pages dequantize, and a multi-step loop matches
contiguous-cache generation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt  # noqa: F401 - env/flags init
from paddle_tpu.incubate.nn.functional import (block_multihead_attention,
                                               masked_multihead_attention)
from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention


def _reference(q, kc, vc, tbl, counts, window=None):
    """Gather pages to contiguous, mask to [count - window, count), softmax
    in float32; a row of count 0 attends nothing and reads zeros."""
    B, _, Hq, D = q.shape
    NB, Hkv, BS, _ = kc.shape
    maxb = tbl.shape[1]
    t = np.clip(np.asarray(tbl), 0, NB - 1)
    ck = jnp.swapaxes(jnp.asarray(kc, jnp.float32)[t], 2, 3)
    cv = jnp.swapaxes(jnp.asarray(vc, jnp.float32)[t], 2, 3)
    ck = ck.reshape(B, maxb * BS, Hkv, D)
    cv = cv.reshape(B, maxb * BS, Hkv, D)
    qg = q[:, 0].astype(jnp.float32).reshape(B, Hkv, Hq // Hkv, D)
    logits = jnp.einsum('bhgd,bshd->bhgs', qg, ck) / (D ** 0.5)
    pos = jnp.arange(maxb * BS)[None, :]
    seen = pos < counts[:, None]
    if window is not None:
        seen &= pos >= counts[:, None] - window
    p = jax.nn.softmax(jnp.where(seen[:, None, None], logits, -1e30), -1)
    p = jnp.where(seen[:, None, None], p, 0.0)
    out = jnp.einsum('bhgs,bshd->bhgd', p, cv).reshape(B, 1, Hq, D)
    return out.astype(q.dtype)


def _gather_ref(q, kc, vc, tbl, counts):
    return _reference(q, kc, vc, jnp.asarray(tbl), jnp.asarray(counts))


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


class TestTwoWidthsAndASink:
    """What a second kind of page asks of the kernel (MiMo-V2): K rows and
    V rows of different widths, a sink logit a query head, and a window
    that starts inside a page whose predecessors' table entries are 0
    (recycled). Against `masked_attention` over the gathered pages."""

    B, NB, Hkv, BS, D, Dv, Hq, MAXB = 3, 16, 2, 16, 24, 16, 8, 8

    def _case(self, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(self.B, 1, self.Hq, self.D)),
                        jnp.float32)
        kc = jnp.asarray(rng.normal(size=(self.NB, self.Hkv, self.BS,
                                          self.D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(self.NB, self.Hkv, self.BS,
                                          self.Dv)), jnp.float32)
        tbl = rng.permutation(np.arange(1, 16))[:15].reshape(3, 5)
        tbl = np.concatenate([tbl, np.zeros((3, 3), np.int64)], 1)
        sink = jnp.asarray(rng.normal(size=(self.Hq,)) + 1.0, jnp.float32)
        return q, kc, vc, tbl, jnp.asarray([70, 80, 13], jnp.int32), sink

    def _want(self, q, kc, vc, tbl, counts, window, sink):
        from paddle_tpu.models.llama import masked_attention

        n = self.MAXB * self.BS
        ck = jnp.swapaxes(kc[tbl], 2, 3).reshape(self.B, n, self.Hkv, self.D)
        cv = jnp.swapaxes(vc[tbl], 2, 3).reshape(self.B, n, self.Hkv, self.Dv)
        pos = jnp.arange(n)[None]
        seen = pos < counts[:, None]
        if window:
            seen &= pos >= counts[:, None] - window
        return masked_attention(q, ck, cv, seen[:, None, None, :], sink)

    @pytest.mark.parametrize('with_sink', [False, True])
    @pytest.mark.parametrize('window', [None, 21, 32])
    def test_matches_the_gather_path(self, window, with_sink):
        q, kc, vc, tbl, counts, sink = self._case()
        sink = sink if with_sink else None
        got = paged_decode_attention(q, kc, vc, jnp.asarray(tbl, jnp.int32),
                                     counts, window=window, sink=sink)
        assert got.shape == (self.B, 1, self.Hq, self.Dv)
        np.testing.assert_allclose(
            got, self._want(q, kc, vc, tbl, counts, window, sink),
            rtol=2e-5, atol=2e-5)

    def test_the_sink_takes_mass_and_adds_no_value(self):
        q, kc, vc, tbl, counts, sink = self._case(1)
        tbl = jnp.asarray(tbl, jnp.int32)
        plain = paged_decode_attention(q, kc, vc, tbl, counts, window=21)
        sunk = paged_decode_attention(q, kc, vc, tbl, counts, window=21,
                                      sink=sink)
        # out_sink = out_plain * l / (l + exp(sink - m)): the same
        # direction, shorter by the sink's share, head by head
        ratio = np.asarray(sunk / plain)
        assert (ratio > 0).all() and (ratio < 1).all()
        np.testing.assert_allclose(
            ratio, np.broadcast_to(ratio[..., :1], ratio.shape), rtol=1e-4)
        # a sink far below every score changes nothing
        np.testing.assert_allclose(
            paged_decode_attention(q, kc, vc, tbl, counts, window=21,
                                   sink=sink - 60.0), plain, atol=1e-6)

    def test_pages_behind_the_window_may_be_gone(self):
        """Entries wholly behind the window zeroed (recycled) and their
        pages overwritten: the result does not move."""
        q, kc, vc, tbl, counts, sink = self._case(2)
        want = paged_decode_attention(q, kc, vc, jnp.asarray(tbl, jnp.int32),
                                      counts, window=21, sink=sink)
        gone = tbl.copy()
        for b, n in enumerate(np.asarray(counts)):
            first = max(0, n - 21) // self.BS
            kc = kc.at[tbl[b, :first]].set(jnp.nan)
            vc = vc.at[tbl[b, :first]].set(jnp.nan)
            gone[b, :first] = 0
        got = paged_decode_attention(q, kc, vc, jnp.asarray(gone, jnp.int32),
                                     counts, window=21, sink=sink)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize('hkv', [2, 4, 8])
    def test_a_pools_rows_are_written_alike_in_either_form(self, hkv):
        """`pool_rows_set`: under 8 kv heads the write goes through the
        pool's (pages, heads x slots, D) view; the result is the 4-D
        scatter's, duplicates on the scratch page included."""
        from paddle_tpu.models.generation import pool_rows_set

        rng = np.random.default_rng(hkv)
        pool = jnp.asarray(rng.normal(size=(9, hkv, 16, 24)), jnp.float32)
        pages = jnp.asarray([3, 0, 7, 0, 3], jnp.int32)
        slots = jnp.asarray([5, 1, 15, 2, 6], jnp.int32)
        rows = jnp.asarray(rng.normal(size=(5, hkv, 24)), jnp.float32)
        np.testing.assert_array_equal(
            pool_rows_set(pool, pages, slots, rows),
            pool.at[pages, :, slots, :].set(rows))

    def test_a_row_wider_than_a_lane_tile_is_kept_in_whole_tiles(self):
        """K rows of 192 through `cached_attention`'s paged branch: pools
        256 wide (`lane_padded`), written and read at the rows' own width,
        kernel and gather path alike."""
        from paddle_tpu import ops
        from paddle_tpu.models.generation import PagedKVCache, lane_padded
        from paddle_tpu.models.llama import cached_attention

        rng = np.random.default_rng(3)
        B, Hq, Hkv, D, Dv, BS, NB = 2, 4, 2, 192, 128, 16, 9
        assert lane_padded(D) == 256 and lane_padded(Dv) == 128
        q, k = (jnp.asarray(rng.normal(size=(B, 1, h, D)), jnp.float32)
                for h in (Hq, Hkv))
        v = jnp.asarray(rng.normal(size=(B, 1, Hkv, Dv)), jnp.float32)
        cache = PagedKVCache(
            jnp.asarray(rng.normal(size=(NB, Hkv, BS, 256)), jnp.float32
                        ).at[..., D:].set(0.0),
            jnp.asarray(rng.normal(size=(NB, Hkv, BS, Dv)), jnp.float32))
        tbl = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7]], jnp.int32)
        wp = jnp.asarray([40, 50], jnp.int32)
        sink = jnp.asarray(rng.normal(size=(Hq,)), jnp.float32)
        outs = {}
        for name, on in (('kernel', True), ('gather', False)):
            was, ops.use_pallas = ops.use_pallas, lambda on=on: on
            try:
                outs[name], new = cached_attention(
                    q, k, v, cache, None, kv_write_pos=wp, window=24,
                    block_tables=tbl, sink=sink)
            finally:
                ops.use_pallas = was
            assert outs[name].shape == (B, 1, Hq, Dv)
            assert new.kp.shape == cache.kp.shape
            np.testing.assert_array_equal(new.kp[3, :, 8, :D], k[0, 0])
            assert not np.asarray(new.kp[..., D:]).any()
        np.testing.assert_allclose(outs['kernel'], outs['gather'],
                                   rtol=2e-5, atol=2e-5)


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of 4 pages of 16 tokens, masked pieces of 2: rows of a few
    dozen tokens cross pages, pieces and chunks."""
    from paddle_tpu.ops.pallas import paged_attention as kmod

    monkeypatch.setattr(kmod, 'CHUNK_KEYS', 64)
    monkeypatch.setattr(kmod, 'EDGE_KEYS', 32)
    assert kmod._pick_pages(16, 2, 16, 4, 16) == (4, 2)


class TestPagedKernelLoopBounds:
    """What a loop over a row's own pages can get wrong. 16 table entries
    a row, pages of 16 tokens, chunks of 4 pages (64 keys)."""

    BS, MAXB, D = 16, 16, 16

    def _pools(self, seed, B, Hq, Hkv, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        NB = B * self.MAXB + 1
        shape = (NB, Hkv, self.BS, self.D)
        q = jnp.asarray(rng.normal(size=(B, 1, Hq, self.D)), dtype)
        kc = jnp.asarray(rng.normal(size=shape), dtype)
        vc = jnp.asarray(rng.normal(size=shape), dtype)
        tbl = rng.permutation(np.arange(1, NB)).reshape(B, self.MAXB)
        return rng, q, kc, vc, jnp.asarray(tbl, jnp.int32)

    def _check(self, q, kc, vc, tbl, counts, tol=2e-4, **kw):
        counts = jnp.asarray(counts, jnp.int32)
        got = paged_decode_attention(q, kc, vc, tbl, counts, **kw)
        want = _reference(q, kc, vc, tbl, counts, kw.get('window'))
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        return got

    @pytest.mark.parametrize('counts', [
        [0, 1, 0], [1, 1, 1],            # idle slots: nothing, one key
        [16, 32, 48],                    # ends on a page, on a piece
        [64, 128, 256],                  # ends on a chunk; the table's end
        [15, 17, 63], [65, 127, 129],    # one off either side of each
        [1, 250, 17], [200, 0, 64],      # very different rows in one call
    ], ids=str)
    def test_counts(self, small_chunks, counts):
        _, q, kc, vc, tbl = self._pools(10, 3, 8, 2)
        got = self._check(q, kc, vc, tbl, counts)
        for b, n in enumerate(counts):
            if n == 0:
                assert not np.asarray(got[b]).any()

    @pytest.mark.parametrize('fill', [-1, 10 ** 6, 'nan_page'])
    def test_table_entries_past_the_rows_end_are_never_read(
            self, small_chunks, fill):
        """-1, an id far out of range, or a page full of NaN after each
        row's last page: no such entry may reach a product."""
        _, q, kc, vc, tbl = self._pools(11, 3, 8, 2)
        counts = [40, 64, 1]
        if fill == 'nan_page':
            kc = kc.at[0].set(jnp.nan)
            vc = vc.at[0].set(jnp.nan)
            fill = 0
        tbl = np.asarray(tbl).copy()
        for b, n in enumerate(counts):
            tbl[b, -(-n // self.BS):] = fill
        want_tbl = np.where(tbl == fill, 1, tbl)       # any finite page
        counts = jnp.asarray(counts, jnp.int32)
        got = paged_decode_attention(q, kc, vc, jnp.asarray(tbl), counts)
        want = _reference(q, kc.at[0].set(0.0), vc.at[0].set(0.0),
                          want_tbl, counts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_stale_keys_behind_the_count_do_not_leak(self, small_chunks):
        """The last page's tail holds another tenant's NaNs."""
        _, q, kc, vc, tbl = self._pools(12, 2, 8, 2)
        counts = [21, 70]
        clean = self._check(q, kc, vc, tbl, counts)
        for b, n in enumerate(counts):
            page = int(tbl[b, n // self.BS])
            kc = kc.at[page, :, n % self.BS:].set(jnp.nan)
            vc = vc.at[page, :, n % self.BS:].set(jnp.inf)
        got = paged_decode_attention(q, kc, vc, tbl,
                                     jnp.asarray(counts, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(clean),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize('window', [
        5,       # the last page alone, or its neighbour
        40,      # starts inside a page
        100,     # starts inside a chunk, pages behind it skipped
        64,      # on a chunk's edge for the rows that end on one
        1000,    # beyond every context: masks nothing
    ])
    def test_window(self, small_chunks, window):
        _, q, kc, vc, tbl = self._pools(13, 4, 8, 2)
        counts = [200, 128, 37, 1]
        got = self._check(q, kc, vc, tbl, counts, window=window)
        if window >= 256:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(paged_decode_attention(
                    q, kc, vc, tbl, jnp.asarray(counts, jnp.int32))),
                rtol=1e-6, atol=1e-6)

    def test_pages_behind_a_window_are_never_read(self, small_chunks):
        _, q, kc, vc, tbl = self._pools(14, 2, 8, 2)
        counts, window = [200, 130], 70
        for b, n in enumerate(counts):
            for j in range((n - window) // self.BS):
                kc = kc.at[int(tbl[b, j])].set(jnp.nan)
                vc = vc.at[int(tbl[b, j])].set(jnp.nan)
        got = paged_decode_attention(q, kc, vc, tbl,
                                     jnp.asarray(counts, jnp.int32),
                                     window=window)
        want = _reference(q, jnp.nan_to_num(kc), jnp.nan_to_num(vc), tbl,
                          jnp.asarray(counts, jnp.int32), window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize('heads,kv_heads', [
        (32, 8), (48, 8), (8, 2), (12, 2), (4, 4)],
        ids=lambda v: str(v))
    @pytest.mark.parametrize('window', [None, 50])
    def test_groups_and_kv_heads(self, small_chunks, heads, kv_heads,
                                 window):
        """Group 4 (Mistral) and 6 (Trinity) over 8 kv heads and over the
        2 a tp shard holds; no grouping at all."""
        _, q, kc, vc, tbl = self._pools(15, 2, heads, kv_heads)
        self._check(q, kc, vc, tbl, [150, 33], window=window)

    def test_bfloat16_pools(self, small_chunks):
        _, q, kc, vc, tbl = self._pools(16, 3, 8, 2, jnp.bfloat16)
        self._check(q, kc, vc, tbl, [250, 64, 7], tol=2e-2)

    @pytest.mark.parametrize('window', [None, 50])
    @pytest.mark.parametrize('layout', ['rowscale', 'global'])
    def test_int8_scale_layouts(self, small_chunks, layout, window):
        """Per-row scales in page-shaped pools, and global per-(head, dim)
        ones: both against the float reference over the dequantized
        pools."""
        from paddle_tpu.models.generation import (calibrate_kv_scale,
                                                  quantize_kv_row,
                                                  quantize_kv_rows)

        _, q, kf, vf, tbl = self._pools(17, 3, 8, 2)
        rows = lambda x: jnp.swapaxes(x, 1, 2)      # noqa: E731 (N,S,H,D)
        if layout == 'rowscale':
            (k8, ks), (v8, vs) = (quantize_kv_row(rows(x)) for x in (kf, vf))
            deq = [rows(x.astype(jnp.float32) * s[..., None])
                   for x, s in ((k8, ks), (v8, vs))]
            ks, vs = rows(ks), rows(vs)             # (NB, Hkv, BS)
        else:
            ks, vs = (calibrate_kv_scale(rows(x)) for x in (kf, vf))
            k8, v8 = (quantize_kv_rows(rows(x), s)
                      for x, s in ((kf, ks), (vf, vs)))
            deq = [rows(x.astype(jnp.float32) * s[None, None])
                   for x, s in ((k8, ks), (v8, vs))]
        counts = jnp.asarray([250, 64, 7], jnp.int32)
        got = paged_decode_attention(q, rows(k8), rows(v8), tbl, counts,
                                     k_scale=ks, v_scale=vs, window=window)
        want = _reference(q, deq[0], deq[1], tbl, counts, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_chunk_follows_the_shapes(self):
        """(pages a chunk, pages a masked piece): 512 and 128 keys where
        VMEM allows, whole pieces, never more than the table holds."""
        from paddle_tpu.ops.pallas.paged_attention import _pick_pages

        assert _pick_pages(16, 8, 128, 2, 128) == (32, 8)    # the cells
        assert _pick_pages(16, 2, 128, 2, 128) == (32, 8)    # a tp shard
        assert _pick_pages(16, 8, 128, 1, 128) == (32, 8)    # int8 as bf16
        assert _pick_pages(16, 8, 128, 2, 4) == (4, 4)       # a narrow table
        assert _pick_pages(128, 8, 128, 2, 64) == (4, 1)     # a large page
        pages, piece = _pick_pages(16, 32, 128, 2, 128)      # 32 kv heads
        assert pages % piece == 0 and 4 * pages * 32 * 16 * 128 * 2 <= 8 << 20


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
