"""Flight recorder + cost observatory + postmortem bundles (PR 12).

Covers the tentpole properties:
  - Journal: bounded ring with drop accounting, complete per-request
    trails (never truncated by ring wrap), closed-trail eviction,
    JSONL round-trip, the journal-only kill switch, seq continuation
    across `inject_trail`;
  - determinism: identical seeded fault scripts over identical
    workloads produce identical event sequences (timing fields
    excluded);
  - trail completeness for EVERY terminal state — finished / failed /
    expired / cancelled — including preemption-resume and
    snapshot()/restore() into a fresh journal;
  - costs.analyze: the list-vs-dict / raising / missing-key quirks of
    XLA's cost_analysis handled once, geometry costs on all three
    engines, manifest stamping + warm-attach loading, and the live
    serve.mfu_est / train.mfu_est gauges consistent with the static
    flops;
  - postmortem bundles: schema round-trip, validation catching
    missing/corrupt pieces, and the ServingEngine worker-death
    auto-dump;
  - meta: the new observability modules stay jax-free at import.
"""
import functools
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt

# tier-1: the forensic layer the ROADMAP's operability story assumes;
# regressions here blind incident debugging and the MFU target
pytestmark = pytest.mark.tier1

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.observability import costs  # noqa: E402
from paddle_tpu.observability import journal as jr  # noqa: E402
from paddle_tpu.observability import postmortem as pm  # noqa: E402
from paddle_tpu.observability.journal import (  # noqa: E402
    Journal,
    strip_times,
    trail_complete,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Fresh registry/tracer/journal per test; telemetry AND journal
    guaranteed back ON afterwards."""
    obs.set_enabled(True)
    jr.set_journal_enabled(True)
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    jr.JOURNAL.clear()
    yield
    obs.set_enabled(True)
    jr.set_journal_enabled(True)


@functools.lru_cache(maxsize=None)
def _model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                       layers=2))


def _prompt(seed, n=6, lo=3, hi=96):
    return np.random.default_rng(seed).integers(
        lo, hi, (n,)).astype(np.int32)


def _engine(**kw):
    from paddle_tpu.inference.serving import ServingEngine

    base = dict(max_slots=4, block_size=8, max_context_len=32,
                max_new_tokens=10, decode_window=4)
    base.update(kw)
    return ServingEngine(_model(), **base)


# ---------------------------------------------------------------------------
# Journal core semantics
# ---------------------------------------------------------------------------

class TestJournalCore:
    def test_ring_bounded_with_drop_accounting(self):
        j = Journal(max_events=10)
        for i in range(25):
            j.record('tick', i=i)
        assert len(j) == 10
        assert j.dropped == 15
        assert j.events()[-1]['i'] == 24

    def test_trail_survives_ring_wrap(self):
        """The forensic property: a request's trail stays COMPLETE even
        after the chronological ring dropped its early events."""
        j = Journal(max_events=4)
        j.record('arrival', rid=7)
        for i in range(20):
            j.record('noise', i=i)
        j.record('finished', rid=7)
        assert len(j) == 4                       # ring wrapped
        assert [e['kind'] for e in j.trail(7)] == ['arrival', 'finished']
        assert trail_complete(j.trail(7), 'finished') == []

    def test_seq_strictly_increasing(self):
        j = Journal()
        for i in range(5):
            j.record('e', rid=1)
        seqs = [e['seq'] for e in j.trail(1)]
        assert seqs == sorted(seqs) and len(set(seqs)) == 5

    def test_closed_trail_eviction_spares_live(self):
        j = Journal(max_trails=2)
        j.record('arrival', rid=1)
        j.record('finished', rid=1)              # closed
        j.record('arrival', rid=2)
        j.record('finished', rid=2)              # closed
        j.record('arrival', rid=3)               # live
        j.record('arrival', rid=4)               # live: 4 trails > 2
        j.record('arrival', rid=5)               # live overshoot allowed
        assert j.trail(1) == [] and j.trail(2) == []
        assert j.trail_evictions == 2
        assert j.trail(3) and j.trail(4) and j.trail(5)

    def test_jsonl_round_trip(self, tmp_path):
        j = Journal()
        j.record('arrival', rid=1, prompt_len=6)
        j.record('fault', site='alloc', n=2)
        path = j.save(tmp_path / 'journal.jsonl')
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert [e['kind'] for e in lines] == ['arrival', 'fault']
        assert lines[0]['rid'] == 1 and lines[1]['site'] == 'alloc'

    def test_disabled_records_nothing(self):
        j = Journal()
        jr.set_journal_enabled(False)
        j.record('e', rid=1)
        assert len(j) == 0 and j.trail(1) == []
        jr.set_journal_enabled(True)
        obs.set_enabled(False)                   # global switch gates too
        j.record('e', rid=1)
        obs.set_enabled(True)
        assert len(j) == 0

    def test_inject_trail_continues_seq(self):
        j = Journal()
        old = [{'seq': 100, 'kind': 'arrival', 'rid': 9},
               {'seq': 105, 'kind': 'window', 'rid': 9}]
        assert j.inject_trail(9, old) == 2
        j.record('finished', rid=9)
        seqs = [e['seq'] for e in j.trail(9)]
        assert seqs == [100, 105, 106]
        assert trail_complete(j.trail(9), 'finished') == []

    def test_inject_trail_skips_already_present(self):
        """Same-process hot standby: the journal already holds the
        trail, so re-injecting the snapshot's copy is a no-op."""
        j = Journal()
        j.record('arrival', rid=3)
        j.record('window', rid=3)
        snap = j.trail(3)
        assert j.inject_trail(3, snap) == 0
        assert len(j.trail(3)) == 2

    def test_trail_complete_problems(self):
        assert trail_complete([]) == ['empty trail']
        bad = [{'seq': 1, 'kind': 'window'}, {'seq': 1, 'kind': 'finished'}]
        probs = trail_complete(bad, 'failed')
        assert any('arrival' in p for p in probs)
        assert any('seq' in p for p in probs)
        assert any('failed' in p for p in probs)


# ---------------------------------------------------------------------------
# costs.analyze quirks + engines
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, cost, mem=None, raise_cost=False):
        self._cost = cost
        self._mem = mem
        self._raise = raise_cost

    def cost_analysis(self):
        if self._raise:
            raise RuntimeError('no cost analysis on this backend')
        return self._cost

    def memory_analysis(self):
        if self._mem is None:
            raise RuntimeError('no memory analysis')
        return self._mem


class TestCostsAnalyze:
    def test_dict_form(self):
        c = costs.analyze(_FakeCompiled({'flops': 10.0,
                                         'bytes accessed': 4.0}))
        assert c['flops'] == 10.0 and c['bytes_accessed'] == 4.0
        assert c['transcendentals'] is None

    def test_list_quirk(self):
        """Some jax versions return one dict per partition."""
        c = costs.analyze(_FakeCompiled([{'flops': 7.0}]))
        assert c['flops'] == 7.0
        assert costs.analyze(_FakeCompiled([]))['flops'] is None

    def test_raise_quirk_degrades(self):
        c = costs.analyze(_FakeCompiled(None, raise_cost=True))
        assert c == {'flops': None, 'bytes_accessed': None,
                     'transcendentals': None, 'memory': {}}

    def test_memory_analysis(self):
        class Mem:
            argument_size_in_bytes = 8
            output_size_in_bytes = 4
            temp_size_in_bytes = 2

        c = costs.analyze(_FakeCompiled({'flops': 1.0}, mem=Mem()))
        assert c['memory'] == {'argument_bytes': 8, 'output_bytes': 4,
                               'temp_bytes': 2}

    def test_lowered_accepted_and_compile_failure_degrades(self):
        import jax
        import jax.numpy as jnp

        # tracelint: disable=TL001 - one-shot analysis jit in a test
        lowered = jax.jit(lambda x: x * 2 + 1).lower(jnp.ones((8, 8)))
        c = costs.analyze(lowered)
        assert c['flops'] and c['flops'] > 0

        class BadLowered:
            def compile(self):
                raise RuntimeError('backend refused')

        assert costs.analyze(BadLowered())['flops'] is None

    def test_intensity(self):
        assert costs.intensity({'flops': 8.0, 'bytes_accessed': 2.0}) == 4.0
        assert costs.intensity({'flops': None, 'bytes_accessed': 2.0}) is None

    def test_peak_flops_env_override(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_PEAK_FLOPS', '2.5e12')
        assert costs.device_peak_flops() == 2.5e12

    @pytest.mark.parametrize('platform,kind,want', [
        ('tpu', 'TPU v5 lite', 197e12),
        ('tpu', 'TPU v5p', 459e12),
        ('cpu', 'cpu', None),
        ('tpu', 'TPU v99', ValueError),
    ])
    def test_peak_flops_table(self, monkeypatch, platform, kind, want):
        """One table keyed by device_kind; a TPU that is not in it is an
        error, never an assumed peak."""
        import types

        monkeypatch.delenv('PADDLE_TPU_PEAK_FLOPS', raising=False)
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        if want is ValueError:
            with pytest.raises(ValueError, match='TPU v99'):
                costs.device_peak_flops(dev)
        else:
            assert costs.device_peak_flops(dev) == want

    def test_unified_call_sites_flops_and_op_summary(self):
        """The three duplicated cost_analysis sites now share analyze:
        utils.flops and profiler.op_summary agree on the same model."""
        import jax.numpy as jnp

        from paddle_tpu.profiler import op_summary
        from paddle_tpu.utils.flops import flops as flops_fn

        model = _model()
        ids = jnp.zeros((1, 8), jnp.int32)
        total = flops_fn(model, inputs=(ids,))
        assert total > 0
        stats = op_summary(lambda m, x: m(x), model, ids,
                           print_table=False)
        assert stats['flops'] and stats['flops'] > 0
        assert stats['bytes_accessed'] and stats['bytes_accessed'] > 0
        assert int(stats['flops']) == total

    def test_compilation_report_uses_analyze(self):
        import jax.numpy as jnp

        from paddle_tpu import jit as pjit

        rep = pjit.compilation_report(lambda x: x @ x, jnp.ones((16, 16)))
        assert rep['flops'] > 0
        assert rep['compile_time_s'] > 0


class TestCostsOnEngines:
    def test_serving_geometry_cost(self):
        from paddle_tpu.aot.geometry import Geometry

        srv = _engine()
        c = costs.geometry_cost(
            srv, Geometry('serve_window', window=srv.decode_window))
        assert c['flops'] > 0 and c['bytes_accessed'] > 0
        assert c['specs'] == 1

    def test_decode_geometry_cost(self):
        from paddle_tpu.aot.geometry import Geometry
        from paddle_tpu.inference.engine import DecodeEngine

        eng = DecodeEngine(_model(), max_new_tokens=4)
        c = costs.geometry_cost(
            eng, Geometry('decode', batch=1, prompt_len=6,
                          max_new_tokens=4))
        assert c['flops'] > 0
        assert c['specs'] == 2                   # prefill + decode loop

    def test_decode_spec_geometry_not_implemented(self):
        from paddle_tpu.aot.geometry import Geometry
        from paddle_tpu.inference.engine import DecodeEngine

        eng = DecodeEngine(_model(), max_new_tokens=4)
        with pytest.raises(NotImplementedError):
            costs.geometry_cost(
                eng, Geometry('decode_spec', batch=1, prompt_len=6,
                              max_new_tokens=4, num_draft_tokens=2))

    def test_train_geometry_cost_and_mfu(self, monkeypatch):
        import jax.numpy as jnp

        from paddle_tpu.aot.geometry import for_train_engine
        from paddle_tpu.optimizer import AdamW
        from paddle_tpu.training.engine import TrainEngine

        monkeypatch.setenv('PADDLE_TPU_PEAK_FLOPS', '1e12')
        # a PRIVATE model: the fused train step donates the params, so
        # the shared lru-cached serving model must not ride in here
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        pt.seed(0)
        model = LlamaForCausalLM(llama_tiny(
            vocab_size=64, hidden_size=32, layers=1, heads=2,
            kv_heads=2, intermediate_size=64))
        eng = TrainEngine(model, AdamW(learning_rate=1e-3),
                          log_window=2)
        gs = for_train_engine(eng, (2, 9))
        rep = costs.measure_dispatch_costs(eng, geometries=gs)
        (cost,) = rep.values()
        assert cost['flops'] > 0
        batch = jnp.zeros((2, 9), jnp.int32)
        eng.step((batch,))
        eng.step((batch,))                       # closes window 1
        # window 1 contained the compile MISS: its wall is trace +
        # compile, so it must publish NO mfu (the serving engine's
        # MISS-exclusion rule at window granularity)
        assert eng.stats()['mfu'] is None
        assert 'train.mfu_est' not in obs.REGISTRY.snapshot()
        eng.step((batch,))
        eng.step((batch,))                       # closes window 2 (hot)
        rec = eng.stats()['mfu']
        assert rec is not None
        assert rec['flops'] == pytest.approx(2 * cost['flops'])
        snap = obs.REGISTRY.snapshot()
        assert snap['train.mfu_est']['value'] == pytest.approx(
            rec['mfu_est'])
        assert snap['train.model_flops_per_s']['value'] > 0

    def test_serving_live_mfu_consistent(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_PEAK_FLOPS', '1e12')
        srv = _engine()
        srv.serve([_prompt(0)], 10)              # warm both step kinds
        costs.measure_dispatch_costs(srv)
        srv.serve([_prompt(s) for s in range(4)], 10)
        rec = srv.stats()['mfu']
        assert rec is not None
        assert rec['peak_flops'] == 1e12
        expect = (rec['flops'] / (rec['window_wall_ms'] / 1e3)) / 1e12
        assert rec['mfu_est'] == pytest.approx(expect)
        snap = obs.REGISTRY.snapshot()
        assert snap['serve.mfu_est']['value'] == pytest.approx(
            rec['mfu_est'])
        assert snap['serve.roofline_intensity']['value'] == pytest.approx(
            rec['flops'] / rec['bytes_accessed'])

    def test_manifest_stamping_and_warm_attach_loading(self, tmp_path):
        from paddle_tpu import aot

        srv = _engine(max_new_tokens=8)
        art = aot.build(srv, str(tmp_path / 'art'))
        for g in art.manifest['geometries']:
            assert g['cost']['flops'] > 0
            assert g['cost']['bytes_accessed'] > 0
        fresh = _engine(max_new_tokens=8)
        rep = fresh.warmup(artifact=str(tmp_path / 'art'))
        assert rep['costs_loaded'] == len(art.manifest['geometries'])
        assert len(fresh._dispatch_costs) > 0
        # the stripped geometry set still equals a fresh enumeration
        # (the cost stamp is build metadata, not a geometry param)
        from paddle_tpu.aot import geometry as geo

        assert (art.geometry_set().to_manifest()
                == geo.for_engine(srv).to_manifest())
        from paddle_tpu import sysconfig

        sysconfig.restore_persistent_compilation_cache(None)

    def test_stamp_costs_off(self, tmp_path):
        from paddle_tpu import aot
        from paddle_tpu.aot.geometry import Geometry, GeometrySet

        srv = _engine()
        art = aot.build(
            srv, str(tmp_path / 'nc'), stamp_costs=False,
            geometries=GeometrySet(
                [Geometry('serve_window', window=srv.decode_window)]))
        assert 'cost' not in art.manifest['geometries'][0]
        from paddle_tpu import sysconfig

        sysconfig.restore_persistent_compilation_cache(None)


# ---------------------------------------------------------------------------
# Trails through the serving engine: every terminal state
# ---------------------------------------------------------------------------

class TestServingTrails:
    def test_finished_trails_complete(self):
        srv = _engine()
        rids = [srv.submit(_prompt(s)) for s in range(6)]
        srv.run()
        for r in rids:
            assert srv.result(r) is not None
            t = jr.trail(r)
            assert trail_complete(t, 'finished') == []
            kinds = [e['kind'] for e in t]
            for k in ('arrival', 'enqueued', 'admitted',
                      'prefill_dispatch', 'first_token', 'window'):
                assert k in kinds

    def test_failed_trail_carries_fault(self):
        from paddle_tpu.testing.faults import FaultInjector

        srv = _engine()
        srv.serve([_prompt(0)])                  # warm
        inj = FaultInjector(seed=0)
        inj.script('admit', times=1)
        with inj:
            rid = srv.submit(_prompt(1))
            srv.run()
        assert srv.status(rid) == 'failed'
        t = jr.trail(rid)
        assert trail_complete(t, 'failed') == []
        fault = [e for e in t if e['kind'] == 'fault']
        assert fault and fault[0]['site'] == 'admit'
        assert t[-1]['reason'].startswith('fault at admission')

    def test_expired_and_cancelled_trails(self):
        srv = _engine()
        rid_c = srv.submit(_prompt(0))
        srv.cancel(rid_c)
        rid_e = srv.submit(_prompt(1), deadline_s=1e-6)
        srv.run()
        assert srv.status(rid_c) == 'cancelled'
        assert srv.status(rid_e) == 'expired'
        assert trail_complete(jr.trail(rid_c), 'cancelled') == []
        assert trail_complete(jr.trail(rid_e), 'expired') == []

    def test_preemption_resume_trail(self):
        srv = _engine(max_slots=2, block_size=4, num_blocks=6,
                      max_new_tokens=10)
        rids = [srv.submit(_prompt(s, 4)) for s in range(4)]
        srv.run()
        assert srv.preemption_count > 0
        preempted = [r for r in rids
                     if any(e['kind'] == 'preempted'
                            for e in jr.trail(r))]
        assert preempted
        for r in preempted:
            t = jr.trail(r)
            assert trail_complete(t, 'finished') == []
            kinds = [e['kind'] for e in t]
            # the resume shows as a second enqueue + admission AFTER
            # the preemption, all in one ordered trail
            i = kinds.index('preempted')
            assert 'enqueued' in kinds[i:] and 'admitted' in kinds[i:]

    def test_restore_trail_spans_failover(self):
        srv = _engine()
        rids = [srv.submit(_prompt(s)) for s in range(4)]
        srv.step()
        snap = json.loads(json.dumps(srv.snapshot()))
        assert snap['trails']
        jr.JOURNAL.clear()                       # simulate a FRESH process
        fresh = _engine()
        fresh.restore(snap)
        fresh.run()
        for r in rids:
            assert fresh.result(r) is not None
            t = jr.trail(r)
            assert trail_complete(t, 'finished') == []
        # an in-flight request crossed the failover: its one trail has
        # pre-crash events, the 'restored' mark, and the finish
        crossed = [r for r in rids
                   if any(e['kind'] == 'restored' for e in jr.trail(r))]
        assert crossed
        kinds = [e['kind'] for e in jr.trail(crossed[0])]
        assert kinds.index('restored') > 0
        assert kinds[-1] == 'finished'

    def test_allocator_and_compile_events_in_journal(self):
        # a decode_window no other test uses: this serve must really
        # trace+compile, so the journal sees 'trace' and 'compile'
        # events even when the module-level jit caches are warm
        srv = _engine(decode_window=5)
        srv.serve([_prompt(0)])
        kinds = {e['kind'] for e in jr.JOURNAL.events()}
        assert 'alloc' in kinds and 'free' in kinds
        assert 'trace' in kinds and 'compile' in kinds

    def test_journal_off_serving_still_works(self):
        jr.set_journal_enabled(False)
        srv = _engine()
        out = srv.serve([_prompt(0)])
        assert out[0] is not None
        assert len(jr.JOURNAL) == 0


class TestDeterminism:
    def _run_flood(self, srv):
        """One seeded faulted workload on a WARMED engine (no compile
        events — a second run in the same process must journal
        identically)."""
        from paddle_tpu.inference.serving import OutOfBlocks
        from paddle_tpu.testing.faults import FaultInjector

        inj = FaultInjector(seed=3)
        inj.script('admit', after=6, times=2)
        inj.script('alloc', exc=OutOfBlocks('injected: dry'),
                   when=lambda c: c.get('phase') == 'window',
                   after=10, times=1)
        rids = [srv.submit(_prompt(100 + i)) for i in range(8)]
        with inj:
            srv.run()
        for r in rids:
            try:
                srv.result(r)
            except Exception:  # noqa: BLE001 - failed requests expected
                pass
        return rids

    def test_seeded_fault_runs_journal_identically(self):
        srv = _engine()
        srv.serve([_prompt(0), _prompt(1)])      # warm every step kind
        jr.JOURNAL.clear()
        self._run_flood(srv)
        first = strip_times(jr.JOURNAL.events())
        jr.JOURNAL.clear()
        self._run_flood(srv)
        second = strip_times(jr.JOURNAL.events())
        # rid/seq values differ run to run (monotonic counters), but
        # the event STRUCTURE — kinds, fields, relative order — must
        # be identical for identical seeded workloads
        def canon(evs):
            rid_map, seq_map = {}, {}
            out = []
            for e in evs:
                e = dict(e)
                if 'rid' in e:
                    e['rid'] = rid_map.setdefault(e['rid'],
                                                  len(rid_map))
                e['seq'] = seq_map.setdefault(e['seq'], len(seq_map))
                out.append(e)
            return out

        assert canon(first) == canon(second)


# ---------------------------------------------------------------------------
# Postmortem bundles
# ---------------------------------------------------------------------------

class TestPostmortem:
    def test_bundle_round_trip(self, tmp_path):
        srv = _engine()
        srv.serve([_prompt(0)])
        rep = pm.dump_bundle(str(tmp_path / 'b'), engine=srv,
                             reason='test dump')
        assert not rep['errors']
        ok, problems = pm.validate_bundle(str(tmp_path / 'b'))
        assert ok, problems
        b = pm.load_bundle(str(tmp_path / 'b'))
        assert b['manifest']['schema'] == pm.BUNDLE_SCHEMA
        assert b['manifest']['reason'] == 'test dump'
        assert b['manifest']['engine']['geometry']['kind'] == 'paged'
        assert isinstance(b['metrics'], dict) and b['metrics']
        assert b['journal'] and b['snapshot'] is not None

    def test_validate_catches_missing_and_corrupt(self, tmp_path):
        ok, problems = pm.validate_bundle(str(tmp_path / 'nope'))
        assert not ok
        pm.dump_bundle(str(tmp_path / 'b'))
        os.remove(str(tmp_path / 'b' / 'metrics.json'))
        ok, problems = pm.validate_bundle(str(tmp_path / 'b'))
        assert not ok and any('metrics.json' in p for p in problems)
        pm.dump_bundle(str(tmp_path / 'c'))
        with open(str(tmp_path / 'c' / 'bundle.json'), 'w') as f:
            f.write('not json')
        ok, problems = pm.validate_bundle(str(tmp_path / 'c'))
        assert not ok

    def test_worker_death_auto_dump(self, tmp_path):
        from paddle_tpu.testing.faults import FaultInjector

        srv = _engine(postmortem_dir=str(tmp_path))
        rid = srv.submit(_prompt(0))
        inj = FaultInjector(seed=0)
        inj.script('dispatch', when=lambda c: c.get('kind') == 'window')
        with inj:
            with pytest.raises(Exception):
                srv.step()
        assert srv.last_postmortem is not None
        ok, problems = pm.validate_bundle(srv.last_postmortem)
        assert ok, problems
        b = pm.load_bundle(srv.last_postmortem)
        assert b['manifest']['error']['type'] == 'FaultError'
        assert b['manifest']['reason'] == 'worker death in step()'
        # the engine kept the demoted request and finishes in place
        srv.run()
        assert srv.result(rid) is not None
        assert obs.REGISTRY.snapshot()['serve.postmortems']['value'] == 1

    def test_no_dir_no_dump(self):
        from paddle_tpu.testing.faults import FaultInjector

        srv = _engine()
        srv.submit(_prompt(0))
        inj = FaultInjector(seed=0)
        inj.script('dispatch', when=lambda c: c.get('kind') == 'window')
        with inj:
            with pytest.raises(Exception):
                srv.step()
        assert srv.last_postmortem is None
        srv.run()

    def test_cli_validates_and_prints_trail(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(REPO, 'tools'))
        try:
            import postmortem as cli
        finally:
            sys.path.pop(0)

        srv = _engine()
        rid = srv.submit(_prompt(0))
        srv.run()
        srv.result(rid)
        pm.dump_bundle(str(tmp_path / 'b'), engine=srv)
        assert cli.main([str(tmp_path / 'b')]) == 0
        assert cli.main([str(tmp_path / 'b'), '--rid', str(rid)]) == 0
        out = capsys.readouterr().out
        assert 'bundle validates' in out
        assert 'complete and ordered' in out
        assert cli.main([str(tmp_path)]) == 1    # not a bundle


# ---------------------------------------------------------------------------
# Tracer satellite: overflow counter + save alias
# ---------------------------------------------------------------------------

class TestTracerDroppedCounter:
    def test_overflow_counts_into_registry(self):
        from paddle_tpu.observability.tracing import HostTracer

        t = HostTracer(max_events=5)
        for i in range(12):
            t.instant(f'e{i}')
        assert t.dropped == 7
        snap = obs.REGISTRY.snapshot()
        assert snap['trace.dropped_events']['value'] == 7

    def test_save_alias(self, tmp_path):
        from paddle_tpu.observability.tracing import HostTracer

        t = HostTracer()
        t.instant('x')
        path = t.save(tmp_path / 'trace.json')
        assert json.load(open(path))[0]['name'] == 'x'


# ---------------------------------------------------------------------------
# Meta: the new modules stay backend-free at import
# ---------------------------------------------------------------------------

class TestMeta:
    def test_new_modules_have_no_top_level_jax(self):
        """journal/postmortem are stdlib-only; costs reaches for jax
        only inside helpers — all three must import (and the journal
        must record) without a backend."""
        for mod in (jr, pm, costs):
            top = [ln for ln in open(mod.__file__).read().splitlines()
                   if ln.startswith(('import ', 'from '))]
            assert not any('jax' in ln for ln in top), mod.__name__
