"""DecodeEngine (inference/engine.py): the compiled serving path.

Covers the four tentpole properties:
  - persistent jit cache: steady-state retrace count is 0 across
    repeated generate calls (trace-counting wrapper inside the jitted
    bodies — increments only while tracing);
  - KV-cache buffer donation: the cache is updated IN PLACE (input
    buffer deleted, output reuses the same memory);
  - bucketed prefill: padded-to-bucket prompts produce tokens
    bit-identical to unpadded prefill;
  - fused speculative windows: output matches greedy target-only
    decode, and the on-device commit rule matches the host reference
    (_commit_window).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt

# tier-1: these tests guard the serving hot path's zero-retrace /
# donation / bucketing invariants and must run in the ROADMAP verify
# command (they share one tiny model pair, so the whole file stays
# well inside the tier-1 time box)
pytestmark = pytest.mark.tier1

from paddle_tpu.inference.engine import (  # noqa: E402
    COMPILE_CACHE,
    DecodeEngine,
    bucket_length,
    donation_supported,
    total_traces,
)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


@functools.lru_cache(maxsize=None)
def _models():
    """One (target, draft) pair for the whole module: the module-level
    jit cache is keyed on the model pytree, so sharing the instances
    keeps this file fast AND exercises the cross-call cache hits the
    engine exists for."""
    pt.seed(0)
    target = LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                         layers=2))
    pt.seed(1)
    draft = LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=32,
                                        layers=1, intermediate_size=64))
    return target, draft


def _prompt(seed, shape, lo=3, hi=96):
    return jnp.asarray(np.random.default_rng(seed).integers(lo, hi, shape),
                       jnp.int32)


class TestBucketing:
    def test_bucket_length(self):
        assert bucket_length(5) == 16
        assert bucket_length(16) == 16
        assert bucket_length(17) == 32
        assert bucket_length(5000) == 8192      # past the table: next pow2
        assert bucket_length(5, buckets=(4, 8)) == 8

    def test_bucketed_prefill_matches_unpadded(self):
        """Prompt lengths 5 and 6 both pad to bucket 16; tokens must be
        bit-identical to the mixin's unpadded generate()."""
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8)
        for seed, S in ((0, 5), (3, 6)):
            ids = _prompt(seed, (1, S))
            ref = target.generate(ids, max_new_tokens=8)
            out = eng.generate(ids)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                          err_msg=f'prompt len {S}')

    def test_bucketed_prefill_batched(self):
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8)
        ids = _prompt(7, (2, 6))
        ref = target.generate(ids, max_new_tokens=8)
        out = eng.generate(ids)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_exact_bucket_boundary_skips_padding(self):
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8)
        ids = _prompt(9, (1, 16))               # exactly a bucket
        ref = target.generate(ids, max_new_tokens=8)
        out = eng.generate(ids)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestCompileCache:
    def test_steady_state_zero_retraces(self):
        """Repeated generate calls — same shape AND a different prompt
        length in the same bucket — must not re-trace anything."""
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8)
        eng.generate(_prompt(0, (1, 6)))        # populate the cache
        t0 = total_traces()
        eng.generate(_prompt(1, (1, 6)))        # same shape
        eng.generate(_prompt(2, (1, 5)))        # same bucket, new length
        assert total_traces() - t0 == 0, (
            f'steady-state serving re-traced: {eng.stats()}')

    def test_second_engine_shares_the_cache(self):
        """The jit cache is module-level: a NEW engine over the same
        model/config compiles nothing."""
        target, _ = _models()
        DecodeEngine(target, max_new_tokens=8).generate(_prompt(0, (1, 6)))
        t0 = total_traces()
        eng2 = DecodeEngine(target, max_new_tokens=8)
        eng2.generate(_prompt(4, (1, 6)))
        assert total_traces() - t0 == 0

    def test_new_bucket_compiles(self):
        """Crossing a bucket boundary is a genuine new key — the counter
        must see it (proves the counter isn't just always 0)."""
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8)
        eng.generate(_prompt(0, (1, 6)))
        t0 = total_traces()
        eng.generate(_prompt(0, (1, 17)))       # bucket 32
        assert total_traces() - t0 > 0
        assert len(COMPILE_CACHE) >= 2

    def test_speculative_steady_state_zero_retraces(self):
        from paddle_tpu.models.generation import generate_speculative

        target, draft = _models()
        ids = _prompt(11, (1, 6))
        generate_speculative(target, draft, ids, max_new_tokens=8,
                             num_draft_tokens=3)
        t0 = total_traces()
        generate_speculative(target, draft, ids, max_new_tokens=8,
                             num_draft_tokens=3)
        assert total_traces() - t0 == 0


class TestDonation:
    def test_prefill_updates_cache_in_place(self):
        """The donated cache buffer must be REUSED: the input arrays die
        and the returned cache lives at the same addresses."""
        if not donation_supported():
            pytest.skip('backend ignores buffer donation')
        from paddle_tpu.inference.engine import _prefill_exact

        target, _ = _models()
        caches = target.init_cache(1, 24)
        ptrs = {c[0].unsafe_buffer_pointer() for c in caches}
        ids = _prompt(0, (1, 6))
        _, new_caches = _prefill_exact(target, caches, ids)
        assert all(c[0].is_deleted() for c in caches), (
            'donated cache inputs must be consumed, not copied')
        new_ptrs = {c[0].unsafe_buffer_pointer() for c in new_caches}
        assert new_ptrs == ptrs, (
            'donation did not reuse the cache buffers in place')

    def test_generate_usable_after_donation(self):
        """End to end: donation must never corrupt results across
        repeated calls (each call allocates a fresh cache; the donated
        buffers are recycled inside the call chain)."""
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8)
        a = np.asarray(eng.generate(_prompt(5, (1, 6))))
        b = np.asarray(eng.generate(_prompt(5, (1, 6))))
        np.testing.assert_array_equal(a, b)


class TestSpeculative:
    def test_commit_rule_matches_host_reference(self):
        """The on-device commit (m = sum(cumprod(d == t[:k])), next =
        t[m]) must agree with the executable host spec _commit_window
        on random windows."""
        from paddle_tpu.models.generation import _commit_window

        rng = np.random.default_rng(0)
        k = 4
        for _ in range(50):
            d = rng.integers(0, 3, (k,))        # small vocab: collisions
            t = rng.integers(0, 3, (k + 1,))
            c = int(rng.integers(0, 3))
            committed_ref, next_ref = _commit_window(c, d, t, k)
            eq = (d == t[:k]).astype(np.int64)
            m = int(np.sum(np.cumprod(eq)))
            committed = [c] + [int(x) for x in d[:m]]
            assert committed == committed_ref
            assert int(t[m]) == next_ref

    def test_engine_speculative_matches_greedy(self):
        target, draft = _models()
        ids = _prompt(0, (1, 6))
        ref = target.generate(ids, max_new_tokens=8)
        eng = DecodeEngine(target, max_new_tokens=8)
        out = eng.generate_speculative(draft, ids, num_draft_tokens=3)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_engine_speculative_batched_matches_solo(self):
        target, draft = _models()
        ids = _prompt(5, (2, 6))
        eng = DecodeEngine(target, max_new_tokens=8)
        out = np.asarray(eng.generate_speculative(draft, ids,
                                                  num_draft_tokens=3))
        for b in range(2):
            solo = np.asarray(target.generate(ids[b:b + 1],
                                              max_new_tokens=8))
            np.testing.assert_array_equal(out[b:b + 1], solo,
                                          err_msg=f'row {b}')


class TestSamplingConfig:
    def test_top_k_larger_than_vocab_clamps(self):
        """HF semantics: top_k > V means keep everything, not an
        IndexError at trace time."""
        from paddle_tpu.models.generation import filter_logits

        logits = jnp.asarray([[0.1, 0.4, 0.2]])
        np.testing.assert_allclose(
            np.asarray(filter_logits(logits, top_k=10)),
            np.asarray(logits))
        target, _ = _models()
        ids = _prompt(0, (1, 5))
        out = target.generate(ids, max_new_tokens=4, temperature=1.0,
                              top_k=500)        # vocab is 96
        assert out.shape == (1, 9)

    def test_sampled_engine_reproducible(self):
        target, _ = _models()
        eng = DecodeEngine(target, max_new_tokens=8, temperature=0.8,
                           top_k=20)
        key = jax.random.PRNGKey(7)
        a = np.asarray(eng.generate(_prompt(0, (1, 6)), rng_key=key))
        b = np.asarray(eng.generate(_prompt(0, (1, 6)), rng_key=key))
        np.testing.assert_array_equal(a, b)


class TestPersistentCacheWiring:
    def test_sysconfig_round_trip(self, tmp_path):
        from paddle_tpu import sysconfig

        try:
            d = sysconfig.enable_persistent_compilation_cache(
                str(tmp_path / 'xla_cache'))
            assert d == str(tmp_path / 'xla_cache')
            assert sysconfig.persistent_compilation_cache_dir() == d
            assert jax.config.jax_compilation_cache_dir == d
        finally:
            # do not leave the worker's later tests persisting into a
            # vanished tmp dir
            sysconfig.restore_persistent_compilation_cache(None)
