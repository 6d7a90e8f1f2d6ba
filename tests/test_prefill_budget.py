"""The admission prefill's width comes from a token budget
(inference/serving.py `PREFILL_TOKENS`, `ServingEngine._prefill_rows`),
not from `max_slots`.

Pins what the budget may and may not change:

  - the row count is a function of the bucket alone, clamped to
    [1, max_slots]: one program a bucket, as before;
  - greedy streams are token-for-token those of the same engine forced
    to `max_slots` rows, with and without a draft, bf16 and int8 pages,
    and on a tp=2 mesh: only dummy rows are left out;
  - a bucket's admissions beyond its rows are split into further
    groups that prefill standalone IN THE SAME STEP, and the ring's
    `serve.dispatch`/`serve.prefill` events say how wide each batch was;
  - everything that restates the shape goes through the one function:
    after `warmup()` a burst that needs fused and standalone dispatches
    of every bucket compiles nothing, `_cost_specs` lowers to the warmed
    programs and `_export_specs` has the same avals;
  - the budget keys the registry and the AOT config: an artifact built
    under another budget is refused, not attached.

Most cases run under a budget of 64 tokens (monkeypatched) so that tiny
buckets are enough; one parity case and the arithmetic run under the
real constant.
"""
import types

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import aot
from paddle_tpu import observability as obs
from paddle_tpu.aot.artifact import (ArtifactMismatch, EngineArtifact,
                                     config_hash, fingerprint)
from paddle_tpu.inference import serving
from paddle_tpu.inference.engine import COMPILE_CACHE, total_traces
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

pytestmark = pytest.mark.tier1

FULL_WIDTH = 1 << 30            # a budget no bucket divides below max_slots
SMALL = 64                      # buckets 16/32/64 -> 4/2/1 rows of 4 slots
BUCKETS = (16, 32, 64)
_MODELS = {}


def _model(seed=0, **kw):
    key = (seed, tuple(sorted(kw.items())))
    if key not in _MODELS:
        pt.seed(seed)
        cfg = dict(vocab_size=96, hidden_size=64, layers=2, heads=4,
                   kv_heads=2, max_pos=128)
        cfg.update(kw)
        _MODELS[key] = LlamaForCausalLM(llama_tiny(**cfg))
    return _MODELS[key]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(3, 96, (n,)).astype(np.int32)


def _engine(model=None, **kw):
    cfg = dict(max_slots=4, block_size=8, max_context_len=64,
               max_new_tokens=6, decode_window=4, buckets=BUCKETS)
    cfg.update(kw)
    return ServingEngine(model if model is not None else _model(), **cfg)


@pytest.fixture
def small_budget(monkeypatch):
    monkeypatch.setattr(serving, 'PREFILL_TOKENS', SMALL)


@pytest.fixture(autouse=True)
def _telemetry():
    obs.set_enabled(True)
    obs.TRACER.clear()
    yield
    obs.set_enabled(True)


def _admitting():
    """args of every ring event that carried admissions, in order."""
    return [e['args'] for e in obs.TRACER.events()
            if e['name'] in ('serve.dispatch', 'serve.prefill')
            and e['args'].get('rows')]


@pytest.mark.parametrize('bucket,slots,rows', [
    (128, 16, 8), (256, 16, 4), (512, 16, 2), (1024, 16, 1),
    (2048, 16, 1),                       # over the budget: still one row
    (16, 4, 4), (512, 4, 2), (64, 2, 2),  # never more than there are slots
])
def test_rows_under_the_real_budget(bucket, slots, rows):
    assert serving.PREFILL_TOKENS == 1024
    engine = types.SimpleNamespace(max_slots=slots)
    assert ServingEngine._prefill_rows(engine, bucket) == rows


class TestParity:
    """Only dummy rows are left out: same streams as at full width."""

    @staticmethod
    def _serve(monkeypatch, budget, prompts, **kw):
        monkeypatch.setattr(serving, 'PREFILL_TOKENS', budget)
        obs.TRACER.clear()
        srv = _engine(**kw)
        outs = srv.serve(prompts)
        return outs, {(a['bucket'], a['padded_rows'])
                      for a in _admitting()}

    @pytest.mark.parametrize('kv', ['bfloat16', 'int8'])
    @pytest.mark.parametrize('draft', [False, True], ids=['plain', 'spec'])
    def test_streams_equal_full_width(self, monkeypatch, draft, kv):
        lens = [5, 40, 12, 60, 20, 9, 30, 50, 25, 14]
        prompts = [_prompt(100 + i, n) for i, n in enumerate(lens)]
        kw = dict(kv_cache_dtype=kv, max_context_len=72)
        if draft:
            kw.update(draft=_model(1), num_draft_tokens=3)
        want, wide = self._serve(monkeypatch, FULL_WIDTH, prompts, **kw)
        got, narrow = self._serve(monkeypatch, SMALL, prompts, **kw)
        assert wide == {(16, 4), (32, 4), (64, 4)}
        assert narrow == {(16, 4), (32, 2), (64, 1)}
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_streams_equal_full_width_tp2(self, monkeypatch):
        """A row count needs no divisibility by the mesh: host-fed
        arguments are replicated, only the pools shard."""
        lens = [5, 40, 12, 60, 20, 9, 30]
        prompts = [_prompt(150 + i, n) for i, n in enumerate(lens)]
        kw = dict(max_context_len=72)
        want, wide = self._serve(monkeypatch, FULL_WIDTH, prompts, **kw)
        got, narrow = self._serve(monkeypatch, SMALL, prompts, tp=2, **kw)
        assert wide == {(16, 4), (32, 4), (64, 4)}
        assert narrow == {(16, 4), (32, 2), (64, 1)}
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_streams_equal_full_width_real_constant(self, monkeypatch):
        """The constant as shipped: a 512 and a 1024 bucket, four slots."""
        lens = [300, 700, 420, 90, 1000, 510, 130]
        prompts = [_prompt(200 + i, n) for i, n in enumerate(lens)]
        kw = dict(model=_model(max_pos=1100), buckets=(256, 512, 1024),
                  block_size=16, max_context_len=1024 + 16,
                  max_new_tokens=4)
        want, wide = self._serve(monkeypatch, FULL_WIDTH, prompts, **kw)
        got, narrow = self._serve(monkeypatch, 1024, prompts, **kw)
        assert wide == {(256, 4), (512, 4), (1024, 4)}
        assert narrow == {(256, 4), (512, 2), (1024, 1)}
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)


class TestSplit:
    @pytest.mark.parametrize('n,rows', [(10, 4), (20, 2), (40, 1)])
    def test_a_bucket_beyond_its_rows_splits_within_the_step(
            self, small_budget, n, rows):
        srv = _engine()
        bucket = serving.bucket_length(n, BUCKETS)
        assert srv._prefill_rows(bucket) == rows
        reqs = [srv._live[srv.submit(_prompt(300 + i, n))]
                for i in range(4)]
        srv.step()
        # every member prefilled in the step that admitted it: each
        # already holds its first window's tokens
        assert all(len(r.generated) == 4 for r in reqs)
        events = _admitting()
        assert len(events) == 4 // rows
        fused = [e for e in obs.TRACER.events()
                 if e['name'] == 'serve.dispatch']
        assert len(fused) == 1 and fused[0]['args']['kind'] == 'step'
        assert len([e for e in obs.TRACER.events()
                    if e['name'] == 'serve.prefill']) == 4 // rows - 1
        for a in events:
            assert a['bucket'] == bucket
            assert a['rows'] == a['padded_rows'] == rows
            assert a['padded_tokens'] == a['padded_rows'] * a['bucket']
            assert a['real_tokens'] == a['rows'] * n
        srv.run()
        assert all(r.state == 'finished' for r in reqs)
        assert srv.allocator.in_use() == 0

    def test_largest_group_rides_fused(self, small_budget):
        """Three of bucket 32 (rows 2) and one of bucket 64: groups of
        2, 1 and 1; the pair is the fused one."""
        srv = _engine()
        for i, n in enumerate([40, 20, 21, 22]):
            srv.submit(_prompt(400 + i, n))
        srv.step()
        events = _admitting()
        assert [(a['bucket'], a['rows']) for a in events] == [
            (64, 1), (32, 1), (32, 2)]       # standalone first, then fused
        assert all(a['padded_tokens'] == a['padded_rows'] * a['bucket']
                   for a in events)
        srv.run()

    def test_a_chunk_batch_stays_max_slots_wide(self, small_budget):
        srv = _engine(prefill_chunk=16)
        srv.serve([_prompt(500, 40)])
        chunks = [e['args'] for e in obs.TRACER.events()
                  if e['name'] == 'serve.dispatch'
                  and e['args']['kind'] == 'chunk']
        assert chunks
        assert all(a['padded_rows'] == 4
                   and a['padded_tokens'] == 4 * a['bucket']
                   for a in chunks)
        bare = [e['args'] for e in obs.TRACER.events()
                if e['name'] == 'serve.dispatch'
                and e['args']['kind'] == 'window']
        assert all(a['padded_rows'] == a['padded_tokens'] == 0
                   for a in bare)


def _avals(x):
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), x)


class TestOneFunctionOwnsTheShape:
    # engine -> (constructor arguments, workload): between them every
    # kind `for_serving_engine` enumerates
    ENGINES = {
        'plain': ({}, {}),
        'spec': (dict(num_draft_tokens=2), {}),
        'chunk': (dict(prefill_chunk=16, prefix_cache=True),
                  dict(migration=True)),
        'decode': (dict(phase_role='decode'), {}),
    }
    KINDS = {
        'plain': {'serve_step', 'serve_window', 'serve_prefill'},
        'spec': {'serve_spec_step', 'serve_spec_window', 'serve_prefill'},
        'chunk': {'serve_step', 'serve_window', 'serve_prefill',
                  'serve_chunk_step', 'serve_export', 'serve_import'},
        'decode': {'serve_import', 'serve_chunk_step', 'serve_window'},
    }

    @pytest.mark.parametrize('case', list(ENGINES))
    def test_warmup_then_burst_compiles_nothing(self, small_budget, case):
        # a width no other test uses, so nothing is warm by accident
        # (nor the decode pool's programs by the chunked engine's)
        width = dict(hidden_size=48,
                     intermediate_size=96 if case == 'decode' else 80)
        kw, workload = self.ENGINES[case]
        kw = dict(kw, model=_model(**width))
        if case == 'spec':
            kw.update(draft=_model(1, **width))
        blobs = []
        if case == 'decode':
            # what the pool will import, exported before anything is
            # counted: the source engine compiles programs of its own
            src = _engine(model=kw['model'])
            for n in (10, 20, 40):
                rid = src.submit(_prompt(600 + n, n))
                src.step()
                blobs.append((rid, src.export_kv(rid)))
        srv = _engine(**kw)
        gs = aot.for_serving_engine(srv, **workload)
        assert {g.kind for g in gs} == self.KINDS[case]
        rep = srv.warmup(geometries=gs)
        assert rep['traces'] > 0
        t0, m0 = total_traces(), COMPILE_CACHE.misses
        # the specs ARE the warmed programs: lowering them traces
        # nothing, the export avals are the cost avals less the
        # model(s), and the tag is the one inside the registry key
        step_kinds = ('serve_step', 'serve_prefill', 'serve_spec_step')
        for g in gs:
            spec = g.kind.startswith('serve_spec')
            costs = list(srv._cost_specs(g))
            for fn, args, statics in costs:
                fn.lower(*args, **statics)
            (_, args, _), = costs
            skip = sum(isinstance(a, pt.nn.Layer) for a in args)
            assert skip == (0 if g.kind in ('serve_export', 'serve_import')
                            else 2 if spec else 1)  # the model(s) lead
            key, = aot.GeometrySet([g]).registry_keys(srv)
            assert key == srv.registry_key(*srv._geometry_cost_tag(g))
            assert srv._geometry_cost_tag(g)[0] == g.kind
            if skip:
                (_, _, exported), = srv._export_specs(g)
                assert _avals(exported) == _avals(args[skip:])
            else:
                with pytest.raises(NotImplementedError):
                    list(srv._export_specs(g))
            if g.kind in step_kinds:
                rows = srv._prefill_rows(g.params['bucket'])
                ids = args[skip + (3 if spec else 2)]   # after the pools
                assert ids.shape == (rows, g.params['bucket'])
        assert total_traces() - t0 == 0
        if case == 'decode':
            for rid, blob in blobs:
                srv.import_kv(rid, blob)
            srv.run()
            assert all(srv.result(rid) is not None for rid, _ in blobs)
            assert total_traces() - t0 == 0
            assert COMPILE_CACHE.misses - m0 == 0
            return
        # fused and standalone dispatches of every bucket
        bursts = [[40, 41],                   # 64: rows 1 -> fused + alone
                  [20, 21, 22, 23],           # 32: rows 2 -> fused + alone
                  [20, 21, 10],               # 16 standalone beside a pair
                  [10, 11]]                   # 16 fused
        outs = []
        for lens in bursts:
            rids = [srv.submit(_prompt(600 + n, n)) for n in lens]
            srv.run()
            outs += [srv.result(r) for r in rids]
        assert all(o is not None for o in outs)
        seen = {(e['name'], e['args']['bucket'])
                for e in obs.TRACER.events()
                if e['name'] in ('serve.dispatch', 'serve.prefill')
                and e['args'].get('rows')}
        # past `prefill_chunk` an admission rides the chunk step: only
        # the 16 bucket is left to the fused and the standalone prefill
        assert seen == {(name, b)
                        for b in (BUCKETS[:1] if case == 'chunk' else BUCKETS)
                        for name in ('serve.dispatch', 'serve.prefill')}
        assert total_traces() - t0 == 0
        assert COMPILE_CACHE.misses - m0 == 0

    def test_enumeration_matches_live_under_the_budget(self, small_budget):
        srv = _engine(model=_model(hidden_size=32, intermediate_size=64))
        want = set(aot.for_serving_engine(srv).registry_keys(srv))
        before = set(COMPILE_CACHE.keys())
        for lens in ([40, 41], [20, 21, 22], [20, 21, 10], [10]):
            for n in lens:
                srv.submit(_prompt(700 + n, n))
            srv.run()
        got = set(COMPILE_CACHE.keys()) - before
        assert got == want, (
            f'missing={sorted(want - got)} extra={sorted(got - want)}')


class TestTheBudgetKeysThePrograms:
    @pytest.mark.parametrize('built,attached', [(SMALL, 1024),
                                                (1024, SMALL)])
    def test_artifact_of_another_budget_is_refused(
            self, monkeypatch, tmp_path, built, attached):
        monkeypatch.setattr(serving, 'PREFILL_TOKENS', built)
        a = _engine()
        cfg = a.aot_config()
        assert cfg['prefill_tokens'] == built
        key, geometry = a.registry_key('serve_prefill', 32), a._geometry()
        art = EngineArtifact(str(tmp_path), {
            'version': 1, 'fingerprint': fingerprint(), 'engine': cfg,
            'config_hash': config_hash(cfg), 'geometries': [],
        })
        art.check(a)                          # its own budget attaches
        monkeypatch.setattr(serving, 'PREFILL_TOKENS', attached)
        b = _engine()
        with pytest.raises(ArtifactMismatch, match="'prefill_tokens'"):
            art.check(b)
        with pytest.raises(ArtifactMismatch, match="'prefill_tokens'"):
            b.warmup(artifact=art)
        # and the registry never confuses the two engines' programs
        assert b.registry_key('serve_prefill', 32)[2:] != key[2:]
        assert b._geometry() != geometry
        assert b._geometry()[-1] == 1         # tp stays last
