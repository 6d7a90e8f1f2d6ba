"""ServingEngine (inference/serving.py): continuous batching over the
paged KV block pool.

Covers the tentpole properties:
  - BlockAllocator: alloc/free round-trip, deterministic exhaustion,
    LIFO free-list reuse (pool stays pointer-stable — ids only),
    utilization accounting under a randomized fuzz loop;
  - scheduler parity: greedy outputs per request are EXACTLY batch-1
    DecodeEngine outputs, across admission order, mixed lengths, eos
    stops, and preemption/resume;
  - zero retraces after warmup as requests join and leave the
    fixed-slot batch (the shapes-never-change contract);
  - paged cached_attention: the PagedKVCache decode step matches the
    contiguous-cache step, and the pallas paged kernel is dispatched
    on the (mocked) TPU path;
  - preemption: a starved pool evicts and resumes with its generated
    prefix, outputs still exact, preemption_count visible in stats.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt

# tier-1: these tests guard the continuous-batching serving path's
# parity / zero-retrace / allocator invariants (shared tiny model, same
# budget profile as test_decode_engine.py)
pytestmark = pytest.mark.tier1

from paddle_tpu.inference.engine import (  # noqa: E402
    COMPILE_CACHE,
    DecodeEngine,
    total_traces,
)
from paddle_tpu.inference.serving import (  # noqa: E402
    BlockAllocator,
    OutOfBlocks,
    RequestQueue,
    Request,
    ServingEngine,
)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


@functools.lru_cache(maxsize=None)
def _model():
    pt.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                       layers=2))


def _model_like(cfg):
    """The same seeded weights under full attention."""
    import dataclasses

    pt.seed(2)
    return LlamaForCausalLM(dataclasses.replace(cfg, sliding_window=None))


def _prompt(seed, n, lo=3, hi=96):
    return np.random.default_rng(seed).integers(lo, hi, (n,)).astype(np.int32)


def _refs(prompts, mnts, eos=None):
    """Batch-1 DecodeEngine outputs — the parity oracle."""
    model = _model()
    eng = DecodeEngine(model, max_new_tokens=max(mnts), eos_token_id=eos)
    return [np.asarray(eng.generate(jnp.asarray(p[None], jnp.int32),
                                    max_new_tokens=m))[0]
            for p, m in zip(prompts, mnts)]


class TestBlockAllocator:
    def test_alloc_free_round_trip(self):
        a = BlockAllocator(9, 16)
        assert a.usable == 8 and a.available() == 8
        pages = a.alloc(3)
        assert pages == [1, 2, 3]            # page 0 reserved: ids >= 1
        assert a.in_use() == 3 and a.available() == 5
        a.free(pages)
        assert a.in_use() == 0 and a.available() == 8
        assert a.alloc_count == 3 and a.free_count == 3

    def test_exhaustion_raises_deterministically(self):
        a = BlockAllocator(5, 16)
        a.alloc(3)
        with pytest.raises(OutOfBlocks, match='need 2 page'):
            a.alloc(2)
        # the failed alloc must not leak partial state
        assert a.available() == 1
        a.alloc(1)
        with pytest.raises(OutOfBlocks):
            a.alloc(1)

    def test_free_list_reuse_is_pointer_stable(self):
        """Ids are recycled (LIFO), never grown: the device pool indexed
        by them can stay allocated once for the engine's lifetime."""
        a = BlockAllocator(9, 16)
        first = a.alloc(4)
        a.free(first[1:3])                   # free 2, 3
        again = a.alloc(2)
        assert again == [3, 2]               # most-recently-freed first
        assert set(again) <= set(first)      # reuse, not fresh ids
        everything = a.alloc(a.available())
        held = set(first[0:1] + first[3:4] + again + everything)
        assert held == set(range(1, 9))      # exactly the usable ids

    def test_double_free_and_foreign_ids_raise(self):
        a = BlockAllocator(5, 16)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(ValueError, match='not currently allocated'):
            a.free(pages[:1])
        with pytest.raises(ValueError, match='not currently allocated'):
            a.free([0])                      # the scratch page is not yours

    def test_utilization_fuzz_matches_ground_truth(self):
        rng = np.random.default_rng(0)
        a = BlockAllocator(33, 8)
        held = []
        for _ in range(300):
            if held and rng.random() < 0.45:
                k = int(rng.integers(1, len(held) + 1))
                idx = rng.choice(len(held), size=k, replace=False)
                batch = [held[i] for i in idx]
                held = [p for i, p in enumerate(held) if i not in set(idx)]
                a.free(batch)
            else:
                want = int(rng.integers(1, 5))
                try:
                    held.extend(a.alloc(want))
                except OutOfBlocks:
                    assert want > a.available()
            assert a.in_use() == len(held)
            assert len(set(held)) == len(held)        # no id issued twice
            assert all(1 <= p < a.num_blocks for p in held)
            assert a.utilization() == pytest.approx(len(held) / a.usable)
            assert a.available() + a.in_use() == a.usable

    def test_min_pool_rejected(self):
        with pytest.raises(ValueError, match='num_blocks'):
            BlockAllocator(1, 16)


class TestRequestQueue:
    def test_priority_then_fifo(self):
        q = RequestQueue()
        a = Request(0, [1], 4, priority=0)
        b = Request(1, [1], 4, priority=5)
        c = Request(2, [1], 4, priority=0)
        for r in (a, b, c):
            q.push(r)
        assert [q.pop().rid for _ in range(3)] == [1, 0, 2]

    def test_preempted_request_resumes_before_later_arrivals(self):
        q = RequestQueue()
        a = Request(0, [1], 4, priority=0)
        b = Request(1, [1], 4, priority=0)
        q.push(a)
        q.push(b)
        victim = q.pop()                     # a admitted...
        q.push(victim)                       # ...then preempted
        assert q.pop().rid == 0              # original arrival seq kept


class TestServingParity:
    def test_mixed_lengths_match_batch1_decode_engine(self):
        """The acceptance shape: mixed generation lengths, early
        finishers free slots, outputs exactly the batch-1 engine's."""
        prompts = [_prompt(s, 6) for s in range(8)]
        mnts = [3, 8, 5, 8, 3, 6, 4, 8]
        refs = _refs(prompts, mnts)
        srv = ServingEngine(_model(), max_slots=3, block_size=8,
                            max_context_len=32, max_new_tokens=8,
                            decode_window=4)
        outs = srv.serve(prompts, None)  # per-request budgets below
        # serve() used the engine default; redo with per-request budgets
        srv2 = ServingEngine(_model(), max_slots=3, block_size=8,
                             max_context_len=32, max_new_tokens=8,
                             decode_window=4)
        rids = [srv2.submit(p, m) for p, m in zip(prompts, mnts)]
        srv2.run()
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(srv2.result(rid), ref)
        assert srv2.stats()['tokens_generated'] == sum(mnts)
        assert outs[0].shape == (6 + 8,)

    def test_eos_early_stop_matches_engine(self):
        """Pick an eos that actually fires for one of the rows by
        reading the reference output, then assert both paths stop and
        pad identically."""
        prompts = [_prompt(s, 5) for s in (11, 12, 13)]
        plain = _refs(prompts, [8, 8, 8])
        eos = int(plain[0][5 + 2])           # row 0's 3rd generated token
        refs = _refs(prompts, [8, 8, 8], eos=eos)
        srv = ServingEngine(_model(), max_slots=2, block_size=8,
                            max_context_len=32, max_new_tokens=8,
                            decode_window=3, eos_token_id=eos)
        outs = srv.serve(prompts)
        for o, ref in zip(outs, refs):
            np.testing.assert_array_equal(o, ref)

    def test_preemption_resume_is_exact(self):
        """A pool too small for two full requests forces evictions; the
        evicted request resumes from its generated prefix and the final
        streams are still bit-equal to uninterrupted batch-1 decode."""
        prompts = [_prompt(s, 6) for s in range(4)]
        mnts = [10, 10, 10, 10]
        refs = _refs(prompts, mnts)
        srv = ServingEngine(_model(), max_slots=2, block_size=4,
                            num_blocks=6, max_context_len=16,
                            max_new_tokens=10, decode_window=4)
        outs = srv.serve(prompts)
        for o, ref in zip(outs, refs):
            np.testing.assert_array_equal(o, ref)
        assert srv.preemption_count > 0
        assert srv.stats()['preemptions'] == srv.preemption_count
        # everything was released on drain
        assert srv.allocator.in_use() == 0

    def test_a_page_taken_uploads_the_table_alone(self):
        """A live row that only crosses into a new page keeps the slots'
        device state and refreshes the block table; admission and retire
        still upload all of it. Outputs stay the batch-1 engine's."""
        prompt, mnt = _prompt(31, 5), 12
        srv = ServingEngine(_model(), max_slots=2, block_size=4,
                            max_context_len=32, max_new_tokens=mnt,
                            decode_window=2)
        puts = []
        real_put = srv._put
        srv._put = lambda x: (puts.append(np.shape(x)), real_put(x))[1]
        rid = srv.submit(prompt, mnt)
        per_step = []
        while srv.in_flight() or len(srv.queue):
            del puts[:]
            held = len(srv._slot_pages[0])
            dev = srv._dev
            srv.step()
            per_step.append((dev is not None and srv._dev is dev,
                             len(srv._slot_pages[0]) > held > 0,
                             list(puts)))
        np.testing.assert_array_equal(srv.result(rid),
                                      _refs([prompt], [mnt])[0])
        table = np.shape(srv._btab)
        grew = [p for kept, took, p in per_step if kept and took]
        assert len(grew) >= 2               # pages of 4, windows of 2
        for p in grew:
            # the table and the budget every window uploads
            assert sorted(p) == sorted([table, (srv.max_slots,)])
        assert len(per_step[0][2]) > 8      # the admitting step: all of it

    def test_priority_admission_order(self):
        """With one slot, the high-priority request must be served
        first even when submitted last."""
        prompts = [_prompt(s, 5) for s in (20, 21)]
        srv = ServingEngine(_model(), max_slots=1, block_size=8,
                            max_context_len=32, max_new_tokens=4,
                            decode_window=4)
        srv.submit(prompts[0], 4, priority=0)
        hi = srv.submit(prompts[1], 4, priority=9)
        done = srv.step()                    # admits + finishes one
        assert [r.rid for r in done] == [hi]
        srv.run()


class TestZeroRetraces:
    def test_join_leave_steady_state(self):
        """After one warmup batch covering the buckets in play, a whole
        second wave of requests joining and leaving the in-flight batch
        must compile NOTHING."""
        prompts = [_prompt(s, 6) for s in range(6)]
        mnts = [3, 8, 5, 8, 3, 6]
        srv = ServingEngine(_model(), max_slots=3, block_size=8,
                            max_context_len=32, max_new_tokens=8,
                            decode_window=4)
        rids = [srv.submit(p, m) for p, m in zip(prompts, mnts)]
        srv.run()                            # warmup: buckets + window
        t0 = total_traces()
        rids2 = [srv.submit(p, m) for p, m in zip(prompts, mnts)]
        srv.run()
        assert total_traces() - t0 == 0, (
            f'steady-state serving re-traced: {srv.stats()}')
        for a, b in zip(rids, rids2):
            np.testing.assert_array_equal(srv.result(a), srv.result(b))

    def test_engines_never_collide_in_compile_cache(self):
        """The geometry component keeps the paged engine's registry
        keys disjoint from the contiguous engine's over the SAME model
        and sampling config (the PR-5 key fix)."""
        model = _model()
        key_c = COMPILE_CACHE.key(model, (1, 24), 'float32', (8, 0.0),
                                  geometry=('contiguous', 1, 24))
        key_p = COMPILE_CACHE.key(model, (9, 2, 8, 16), 'float32', (8, 0.0),
                                  geometry=('paged', 3, 9, 8, 4))
        assert key_c != key_p
        srv = ServingEngine(model, max_slots=2, block_size=8,
                            max_context_len=32, max_new_tokens=4)
        assert srv.stats()['geometry']['kind'] == 'paged'
        eng = DecodeEngine(model, max_new_tokens=4)
        assert eng.stats()['geometry']['kind'] == 'contiguous'


class TestPagedCachedAttention:
    def test_paged_step_matches_contiguous_step(self):
        """One decode step through the model with a PagedKVCache (pages
        shuffled, non-contiguous) must match the contiguous-cache step
        to float tolerance."""
        import jax

        from paddle_tpu.models.generation import PagedKVCache

        model = _model()
        rng = np.random.default_rng(3)
        L, BS = 11, 4
        ctx = jnp.asarray(rng.integers(3, 96, (1, L)), jnp.int32)
        caches = model.init_cache(1, L + 1)
        logits, caches = model(ctx, caches=caches, cache_index=0)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        ref, _ = model(tok, caches=caches, cache_index=L)

        # pages: scatter the same context into shuffled pages
        pages = model.init_paged_cache(8, BS)
        perm = [5, 2, 7]                     # 3 pages cover L+1 = 12 slots
        tbl = np.zeros((1, 4), np.int32)
        tbl[0, :3] = perm
        new_pages = []
        for (k, v), pc in zip(caches, pages):
            kp, vp = pc.kp, pc.vp
            for s in range(L):
                kp = kp.at[perm[s // BS], :, s % BS, :].set(
                    jnp.swapaxes(k[0, s:s + 1], 0, 1)[:, 0])
                vp = vp.at[perm[s // BS], :, s % BS, :].set(
                    jnp.swapaxes(v[0, s:s + 1], 0, 1)[:, 0])
            new_pages.append(PagedKVCache(kp, vp))
        got, out_pages = model(tok, caches=new_pages,
                               kv_write_pos=jnp.asarray([L], jnp.int32),
                               block_tables=jnp.asarray(tbl))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # the new row landed in page perm[2] slot L % BS
        wrote = np.asarray(out_pages[0].kp[perm[L // BS], :, L % BS])
        assert not np.allclose(wrote, 0.0)

    def test_paged_requires_write_pos_and_tables(self):
        model = _model()
        pages = model.init_paged_cache(4, 4)
        tok = jnp.zeros((1, 1), jnp.int32)
        with pytest.raises(ValueError, match='kv_write_pos'):
            model(tok, caches=pages)
        with pytest.raises(NotImplementedError, match='decode-only'):
            model(jnp.zeros((1, 2), jnp.int32), caches=pages,
                  kv_write_pos=jnp.asarray([0], jnp.int32),
                  block_tables=jnp.zeros((1, 2), jnp.int32))

    def test_pallas_paged_kernel_dispatches(self, monkeypatch):
        """On the (mocked) TPU path the paged kernel must be the one
        serving the decode step."""
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
        try:
            model = _model()
            pages = model.init_paged_cache(6, 8)
            tbl = jnp.asarray([[1, 2]], jnp.int32)
            tok = jnp.asarray([[5]], jnp.int32)
            out, _ = model(tok, caches=pages,
                           kv_write_pos=jnp.asarray([3], jnp.int32),
                           block_tables=tbl)
            assert calls, 'paged kernel was not dispatched'
            assert np.isfinite(np.asarray(out, np.float32)).all()
        finally:
            pt.set_flags({'FLAGS_use_pallas_kernels': False})


class TestGuards:
    def test_oversized_request_rejected_at_submit(self):
        srv = ServingEngine(_model(), max_slots=2, block_size=8,
                            max_context_len=32, max_new_tokens=8)
        with pytest.raises(ValueError, match='max_context_len'):
            srv.submit(_prompt(0, 30), 8)
        srv2 = ServingEngine(_model(), max_slots=1, block_size=4,
                             num_blocks=3, max_context_len=16,
                             max_new_tokens=8)
        with pytest.raises(ValueError, match='pages'):
            srv2.submit(_prompt(0, 6), 8)    # needs 4 pages, pool has 2

    def test_model_without_block_tables_rejected(self):
        class NoPages:
            def forward(self, input_ids):
                return input_ids

        with pytest.raises(NotImplementedError, match='block_tables'):
            ServingEngine(NoPages())

    def test_sliding_window_model_is_served(self):
        """A window smaller than the context through the paged path:
        greedy tokens are those of the model's own uncached forward."""
        pt.seed(2)
        cfg = llama_tiny(vocab_size=96, hidden_size=64, layers=2)
        cfg.sliding_window = 8
        swa = LlamaForCausalLM(cfg)
        srv = ServingEngine(swa, max_slots=2, block_size=4,
                            max_context_len=48, max_new_tokens=12,
                            decode_window=4, buckets=(32,))
        prompts = [_prompt(0, 21), _prompt(1, 13)]
        outs = srv.serve(prompts)
        for p, out in zip(prompts, outs):
            out = np.asarray(out)
            logits = np.asarray(swa(jnp.asarray(out[None, :-1])))[0]
            np.testing.assert_array_equal(
                logits.argmax(-1)[len(p) - 1:], out[len(p):])
        full = ServingEngine(_model_like(cfg), max_slots=2, block_size=4,
                             max_context_len=48, max_new_tokens=12,
                             decode_window=4, buckets=(32,))
        assert any(not np.array_equal(a, b)
                   for a, b in zip(outs, full.serve(prompts)))
