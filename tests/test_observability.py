"""Observability layer (paddle_tpu/observability/): the unified
runtime telemetry contract.

Covers the tentpole properties:
  - MetricsRegistry: counter/gauge/histogram semantics, bucket
    percentile math against known distributions, JSON snapshot,
    Prometheus text exposition, the global on/off switch;
  - request lifecycle: a ServingEngine run records arrival -> enqueued
    -> admitted -> prefill_dispatch -> first_token -> window ->
    finished timestamps in order, with EXACT histogram counts (one
    ttft per request, one itl per non-first token, one queue wait per
    admission) — and survives admission + preemption-resume;
  - HostTracer: the exported host_trace.json is a valid Chrome
    trace_event array carrying scheduler-step / admission / preemption
    / compile spans; the buffer is bounded;
  - RecordEvent bridges one name onto BOTH timelines;
  - pool bytes in real units (allocator stats + registry gauges);
  - TrainEngine / prefetch windows feed the registry with no extra
    syncs;
  - meta: the instrumented tree introduces ZERO new tracelint
    violations and the committed baseline is still zero.
"""
import functools
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt

# tier-1: this is the instrumentation layer ROADMAP items 2 and 4
# assume; regressions here blind the serving SLO metrics
pytestmark = pytest.mark.tier1

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.observability.metrics import (  # noqa: E402
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from paddle_tpu.observability.tracing import HostTracer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Isolate every test: fresh registry/tracer state, telemetry
    guaranteed back ON afterwards (a leaked disable would silently
    skip recording in every later test)."""
    obs.set_enabled(True)
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    yield
    obs.set_enabled(True)


@functools.lru_cache(maxsize=None)
def _model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                       layers=2))


def _prompt(seed, n, lo=3, hi=96):
    return np.random.default_rng(seed).integers(lo, hi, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# Metric semantics
# ---------------------------------------------------------------------------

class TestCounterGauge:
    def test_counter_monotonic(self):
        c = Counter('c')
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert c.snapshot() == {'type': 'counter', 'value': 5}

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            Counter('c').inc(-1)

    def test_gauge_last_write_wins(self):
        g = Gauge('g')
        assert g.value is None
        g.set(3)
        g.set(1.5)
        assert g.value == 1.5
        assert g.snapshot()['value'] == 1.5


class TestHistogram:
    def test_percentiles_uniform(self):
        """Uniform 1..100 over unit buckets: linear interpolation makes
        the estimate exact."""
        h = Histogram('h', buckets=range(1, 101))
        for v in range(1, 101):
            h.observe(v)
        assert h.count == 100
        assert h.min == 1.0 and h.max == 100.0
        assert h.percentile(50) == pytest.approx(50.0)
        assert h.percentile(95) == pytest.approx(95.0)
        assert h.percentile(99) == pytest.approx(99.0)

    def test_percentile_within_bucket_resolution(self):
        """Coarse buckets: the estimate lands inside the bucket that
        actually holds the target rank."""
        h = Histogram('h', buckets=(10, 100, 1000))
        for v in (1, 2, 3, 40, 50, 60, 70, 400, 500, 900):
            h.observe(v)
        assert 10 < h.percentile(50) <= 100
        assert 100 < h.percentile(99) <= 1000

    def test_overflow_bucket_reports_max(self):
        h = Histogram('h', buckets=(1.0,))
        h.observe(0.5)
        h.observe(7.0)
        h.observe(9.0)
        assert h.percentile(99) == 9.0

    def test_weighted_observe(self):
        h = Histogram('h', buckets=(1, 2, 3))
        h.observe(1.5, n=4)
        assert h.count == 4
        assert h.sum == pytest.approx(6.0)
        h.observe(1.5, n=0)                  # n < 1 is a no-op
        assert h.count == 4

    def test_empty_percentile_none(self):
        assert Histogram('h').percentile(50) is None
        assert Histogram('h').snapshot()['p99'] is None

    def test_snapshot_fields(self):
        h = Histogram('h', buckets=(1, 10))
        h.observe(0.5)
        h.observe(5.0)
        s = h.snapshot()
        assert s['type'] == 'histogram'
        assert s['count'] == 2
        assert s['mean'] == pytest.approx(2.75)
        assert s['min'] == 0.5 and s['max'] == 5.0


class TestRegistry:
    def test_get_or_create_is_idempotent(self):
        r = MetricsRegistry()
        assert r.counter('x') is r.counter('x')

    def test_kind_conflict_raises(self):
        r = MetricsRegistry()
        r.counter('x')
        with pytest.raises(TypeError):
            r.gauge('x')

    def test_reset_drops_everything(self):
        r = MetricsRegistry()
        r.counter('x').inc()
        r.reset()
        assert r.snapshot() == {}
        r.counter('x').inc(2)                # recreate after reset
        assert r.get('x').value == 2

    def test_snapshot_round_trips_json(self):
        r = MetricsRegistry()
        r.counter('c').inc()
        r.gauge('g').set(1)
        r.histogram('h').observe(3)
        assert json.loads(r.to_json()) == r.snapshot()

    def test_disabled_records_nothing(self):
        r = MetricsRegistry()
        obs.set_enabled(False)
        r.counter('c').inc(5)
        r.gauge('g').set(1)
        r.histogram('h').observe(3)
        obs.set_enabled(True)
        assert r.get('c').value == 0
        assert r.get('g').value is None
        assert r.get('h').count == 0

    def test_percentile_accessor(self):
        r = MetricsRegistry()
        assert r.percentile('missing', 99) is None
        r.counter('c')
        assert r.percentile('c', 99) is None        # not a histogram
        h = r.histogram('h', buckets=range(1, 101))
        for v in range(1, 101):
            h.observe(v)
        assert r.percentile('h', 95) == 95.0

    def test_module_level_conveniences(self):
        obs.inc('m.c', 2)
        obs.set_gauge('m.g', 7)
        obs.observe('m.h', 3.0, n=2)
        snap = obs.REGISTRY.snapshot()
        assert snap['m.c']['value'] == 2
        assert snap['m.g']['value'] == 7.0
        assert snap['m.h']['count'] == 2


class TestPrometheus:
    def test_exposition_shape(self):
        r = MetricsRegistry()
        r.counter('serve.tokens', help='tokens committed').inc(5)
        r.gauge('pool.utilization').set(0.5)
        h = r.histogram('serve.ttft_ms', buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        text = r.to_prometheus()
        # names sanitized to the legal charset, one TYPE line per metric
        assert '# TYPE serve_tokens counter' in text
        assert 'serve_tokens 5' in text
        assert '# TYPE pool_utilization gauge' in text
        assert '# TYPE serve_ttft_ms histogram' in text
        # cumulative buckets + the canonical _sum/_count/+Inf trio
        assert 'serve_ttft_ms_bucket{le="1.0"} 1' in text
        assert 'serve_ttft_ms_bucket{le="10.0"} 2' in text
        assert 'serve_ttft_ms_bucket{le="+Inf"} 2' in text
        assert 'serve_ttft_ms_count 2' in text
        assert '# HELP serve_tokens tokens committed' in text

    def test_sanitization_collisions_disambiguated(self):
        """Two distinct names sanitizing to one Prometheus name must
        NOT emit duplicate series: every collider gets a deterministic
        name-hash suffix, non-colliders keep their plain sanitized
        name, and the collision warns once."""
        import warnings

        from paddle_tpu.observability.metrics import _COLLISIONS_WARNED

        r = MetricsRegistry()
        r.counter('serve.tok/s').inc(1)
        r.counter('serve.tok_s').inc(2)
        r.counter('serve.tokens').inc(3)
        _COLLISIONS_WARNED.discard('serve_tok_s')
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter('always')
            text = r.to_prometheus()
        assert any('serve_tok_s' in str(w.message) for w in caught)
        series = {ln.split()[0]: ln.split()[1]
                  for ln in text.splitlines() if not ln.startswith('#')}
        # both colliders present, under DISTINCT suffixed names
        suffixed = sorted(k for k in series
                          if k.startswith('serve_tok_s_'))
        assert len(suffixed) == 2 and len(set(suffixed)) == 2
        assert {series[k] for k in suffixed} == {'1', '2'}
        assert 'serve_tok_s' not in series      # no bare duplicate
        assert series['serve_tokens'] == '3'    # non-collider untouched
        # deterministic: a second exposition maps identically
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            assert r.to_prometheus() == text

    def test_exposition_safe_under_concurrent_registration(self):
        """The ops-server scrape thread runs to_prometheus()/snapshot()
        while the scheduler lazily registers metrics — the name set is
        copied under the registry lock, so the scrape can never die
        with 'dictionary changed size during iteration' at exactly the
        state-transition moments a scrape cares about."""
        import threading

        r = MetricsRegistry()
        stop = threading.Event()

        def churn():
            # fresh registries in a cycle: every loop REGISTERS new
            # names (the racing mutation), but the registry stays
            # small so the scrape side stays O(small) per call
            while not stop.is_set():
                with r._lock:
                    r._metrics.clear()
                for i in range(32):
                    r.counter(f'm{i}').inc()

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        try:
            for _ in range(300):
                r.to_prometheus()
                r.snapshot()
                r.names()
        finally:
            stop.set()
            t.join(timeout=5)

    def test_histogram_suffix_row_collisions_disambiguated(self):
        """A counter literally named `x_count` collides with histogram
        `x`'s derived `_count` row — collision detection covers every
        series a metric EMITS, not just base names."""
        import warnings

        from paddle_tpu.observability.metrics import _COLLISIONS_WARNED

        r = MetricsRegistry()
        r.histogram('serve.ttft_ms', buckets=(1.0,)).observe(0.5)
        r.counter('serve.ttft_ms_count').inc(7)
        _COLLISIONS_WARNED.discard('serve_ttft_ms')
        _COLLISIONS_WARNED.discard('serve_ttft_ms_count')
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter('always')
            text = r.to_prometheus()
        assert caught
        samples = {}
        for ln in text.splitlines():
            if not ln.startswith('#'):
                name, value = ln.rsplit(maxsplit=1)
                assert name not in samples, f'duplicate series {name!r}'
                samples[name] = value
        # both metrics present under distinct (suffixed) names, the
        # histogram's _count row included
        assert any(k.startswith('serve_ttft_ms_count_')
                   and samples[k] == '7' for k in samples)
        assert any(k.startswith('serve_ttft_ms_')
                   and k.endswith('_count') and samples[k] == '1'
                   for k in samples)

    def test_help_text_escaped(self):
        r = MetricsRegistry()
        r.counter('c', help='line one\nback\\slash').inc(1)
        text = r.to_prometheus()
        assert '# HELP c line one\\nback\\\\slash' in text
        # the exposition stays one-row-per-line parseable
        assert all(ln.startswith(('#', 'c ')) for ln in
                   text.strip().splitlines())

    def test_exposition_round_trip(self):
        """Parse the exposition text back and recover every value —
        the format contract a real scraper depends on: one unique
        series name per sample row, TYPE emitted exactly once per
        name, histogram bucket rows cumulative and capped by +Inf."""
        r = MetricsRegistry()
        r.counter('serve.tokens', help='tokens').inc(42)
        r.gauge('pool.utilization').set(0.25)
        h = r.histogram('serve.ttft_ms', buckets=(1.0, 10.0),
                        help='ttft with\nnewline')
        h.observe(0.5, n=3)
        h.observe(5.0, n=2)
        h.observe(100.0)
        text = r.to_prometheus()

        types, samples = {}, {}
        for ln in text.splitlines():
            if ln.startswith('# TYPE'):
                _, _, name, kind = ln.split(maxsplit=3)
                assert name not in types, f'duplicate TYPE for {name}'
                types[name] = kind
            elif ln.startswith('# HELP'):
                _, _, name, help_text = ln.split(maxsplit=3)
                assert '\n' not in help_text
            elif ln:
                name, value = ln.rsplit(maxsplit=1)
                assert name not in samples, f'duplicate series {name!r}'
                samples[name] = float(value)
        assert types == {'serve_tokens': 'counter',
                         'pool_utilization': 'gauge',
                         'serve_ttft_ms': 'histogram'}
        assert samples['serve_tokens'] == 42
        assert samples['pool_utilization'] == 0.25
        assert samples['serve_ttft_ms_bucket{le="1.0"}'] == 3
        assert samples['serve_ttft_ms_bucket{le="10.0"}'] == 5
        assert samples['serve_ttft_ms_bucket{le="+Inf"}'] == 6
        assert samples['serve_ttft_ms_count'] == 6
        assert samples['serve_ttft_ms_sum'] == pytest.approx(111.5)


# ---------------------------------------------------------------------------
# Host tracer
# ---------------------------------------------------------------------------

class TestHostTracer:
    def test_span_and_instant_shape(self):
        t = HostTracer()
        with t.span('work', cat='test', k=1):
            pass
        t.instant('tick', cat='test')
        evs = t.events()
        assert [e['ph'] for e in evs] == ['X', 'i']
        assert evs[0]['name'] == 'work' and evs[0]['dur'] >= 0
        assert evs[0]['args'] == {'k': 1}
        assert evs[1]['s'] == 'p'
        assert all('ts' in e and 'pid' in e and 'tid' in e for e in evs)

    def test_export_is_valid_trace_event_array(self, tmp_path):
        t = HostTracer()
        with t.span('a'):
            pass
        t.compile_event('compile:x', key=('k', 1), dur_s=0.01)
        path = t.export(tmp_path / 'host_trace.json')
        loaded = json.load(open(path))
        assert isinstance(loaded, list) and len(loaded) == 2
        for e in loaded:
            assert {'name', 'ph', 'ts', 'pid', 'tid'} <= set(e)
        comp = loaded[1]
        assert comp['cat'] == 'compile'
        assert comp['dur'] == pytest.approx(1e4)      # 0.01 s in us
        assert comp['args']['key'] == str(('k', 1))

    def test_ring_is_bounded(self):
        t = HostTracer(max_events=10)
        for i in range(25):
            t.instant(f'e{i}')
        assert len(t) == 10
        assert t.dropped == 15
        # oldest dropped, newest kept
        assert t.events()[-1]['name'] == 'e24'

    def test_disabled_records_nothing(self):
        t = HostTracer()
        obs.set_enabled(False)
        with t.span('x'):
            pass
        t.instant('y')
        t.compile_event('z')
        obs.set_enabled(True)
        assert len(t) == 0

    def test_annotate_records_host_span(self):
        n0 = len(obs.TRACER)
        with obs.span('dual_name'):
            pass
        evs = obs.TRACER.events()[n0:]
        assert [e['name'] for e in evs] == ['dual_name']


class TestRecordEventBridge:
    def test_context_manager_hits_host_timeline(self):
        from paddle_tpu.profiler import RecordEvent

        n0 = len(obs.TRACER)
        with RecordEvent('bridged'):
            pass
        evs = obs.TRACER.events()[n0:]
        assert [e['name'] for e in evs] == ['bridged']
        assert evs[0]['cat'] == 'record_event'

    def test_decorator_hits_host_timeline(self):
        from paddle_tpu.profiler import RecordEvent

        @RecordEvent('deco')
        def f(x):
            return x + 1

        n0 = len(obs.TRACER)
        assert f(1) == 2
        assert [e['name'] for e in obs.TRACER.events()[n0:]] == ['deco']


# ---------------------------------------------------------------------------
# Request lifecycle through the serving engine
# ---------------------------------------------------------------------------

class TestRequestLifecycle:
    def _serve(self, n=6, mnt=8, window=4, max_slots=4, block_size=8,
               **kw):
        from paddle_tpu.inference.serving import ServingEngine

        srv = ServingEngine(_model(), max_slots=max_slots,
                            block_size=block_size, max_context_len=32,
                            max_new_tokens=mnt,
                            decode_window=window, **kw)
        prompts = [_prompt(s, 6) for s in range(n)]
        rids = [srv.submit(p) for p in prompts]
        finished = []
        while srv.in_flight() or len(srv.queue):
            finished.extend(srv.step())
        assert all(srv.result(r) is not None for r in rids)
        return srv, finished

    def test_histogram_counts_are_exact(self):
        from paddle_tpu.inference.serving import ServingEngine

        n, mnt = 6, 8
        srv = ServingEngine(_model(), max_slots=4, block_size=8,
                            max_context_len=32, max_new_tokens=mnt,
                            decode_window=4)
        # warm both compiled step kinds, then count from a clean
        # registry: tokens decoded in a cache-MISS window are excluded
        # from the ITL histogram by design (their wall is compile, not
        # decoding), so exact-count assertions need all-hit windows
        srv.serve([_prompt(90, 6), _prompt(91, 6)])
        obs.REGISTRY.reset()
        rids = [srv.submit(_prompt(s, 6)) for s in range(n)]
        while srv.in_flight() or len(srv.queue):
            srv.step()
        assert all(srv.result(r) is not None for r in rids)
        snap = obs.REGISTRY.snapshot()
        # one TTFT per request; every other token is one ITL
        # observation; one queue wait per admission (no preemption
        # here, so admissions == requests)
        assert snap['serve.ttft_ms']['count'] == n
        assert snap['serve.itl_ms']['count'] == n * mnt - n
        assert snap['serve.queue_wait_ms']['count'] == n
        assert snap['serve.tokens']['value'] == n * mnt
        assert snap['serve.requests']['value'] == n
        assert snap['serve.finished']['value'] == n
        assert snap['serve.ttft_ms']['p50'] is not None
        assert snap['serve.itl_ms']['p99'] is not None
        assert 'serve.itl_skipped_compile' not in snap

    def test_lifecycle_timestamps_ordered(self):
        _, finished = self._serve(n=3, mnt=4)
        for req in finished:
            events = [e for e, _ in req.times]
            ts = [t for _, t in req.times]
            assert ts == sorted(ts), 'lifecycle timestamps not monotone'
            for ev in ('arrival', 'enqueued', 'admitted',
                       'prefill_dispatch', 'first_token', 'window',
                       'finished'):
                assert ev in events, f'missing lifecycle event {ev}'
            # arrival precedes admission precedes first token
            assert req.when('arrival') <= req.when('admitted')
            assert req.when('admitted') <= req.when('first_token')
            assert req.when('first_token') <= req.when('finished')

    def test_preemption_resume_lifecycle(self):
        """A starved pool (the test_serving preemption shape): the
        evicted request carries a 'preempted' mark, re-waits in the
        queue (queue-wait observations exceed request count), and the
        preemption shows in both the counter and the host trace."""
        srv, finished = self._serve(n=4, mnt=10, window=4, max_slots=2,
                                    block_size=4, num_blocks=6)
        assert srv.preemption_count > 0
        snap = obs.REGISTRY.snapshot()
        assert snap['serve.preemptions']['value'] == srv.preemption_count
        assert (snap['serve.admissions']['value']
                > snap['serve.requests']['value'])
        assert (snap['serve.queue_wait_ms']['count']
                == snap['serve.admissions']['value'])
        preempted = [r for r in finished if r.when('preempted')]
        assert preempted
        for req in preempted:
            ts = [t for _, t in req.times]
            assert ts == sorted(ts)
        names = {e['name'] for e in obs.TRACER.events()}
        assert 'serve.preempt' in names

    def test_trace_has_scheduler_spans(self):
        self._serve(n=3, mnt=4)
        evs = obs.TRACER.events()
        names = {e['name'] for e in evs}
        assert 'serve.step' in names
        assert 'serve.admit' in names
        assert 'serve.admission' in names
        steps = [e for e in evs if e['name'] == 'serve.step']
        assert all(e['ph'] == 'X' and e['dur'] > 0 for e in steps)

    def test_exported_serve_trace_is_valid(self, tmp_path):
        self._serve(n=3, mnt=4)
        loaded = json.load(open(obs.TRACER.export(
            tmp_path / 'host_trace.json')))
        assert isinstance(loaded, list) and loaded
        for e in loaded:
            assert {'name', 'ph', 'ts', 'pid', 'tid'} <= set(e)
            assert e['ph'] in ('X', 'i')

    def test_disabled_serving_records_nothing_and_still_serves(self):
        obs.set_enabled(False)
        srv, finished = self._serve(n=3, mnt=4)
        obs.set_enabled(True)
        assert len(finished) == 3
        assert obs.REGISTRY.snapshot() == {}
        assert all(not r.times for r in finished)

    def test_pool_bytes_real_units(self):
        srv, _ = self._serve(n=3, mnt=4)
        model = _model()
        cfg = model.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        kv_heads = cfg.num_key_value_heads or cfg.num_attention_heads
        itemsize = np.dtype(model.cache_dtype()).itemsize
        bpp = (cfg.num_hidden_layers * 2 * kv_heads * srv.block_size
               * head_dim * itemsize)
        stats = srv.allocator.stats()
        assert stats['bytes_per_page'] == bpp
        assert stats['bytes_total'] == srv.allocator.num_blocks * bpp
        assert stats['bytes_in_use'] == 0           # drained
        assert stats['bytes_high_water'] > 0
        assert srv.stats()['blocks']['bytes_total'] == stats['bytes_total']
        snap = obs.REGISTRY.snapshot()
        assert snap['pool.bytes_total']['value'] == stats['bytes_total']
        assert snap['pool.bytes_in_use']['value'] == 0.0


# ---------------------------------------------------------------------------
# Train engine + prefetch windows
# ---------------------------------------------------------------------------

class TestTrainTelemetry:
    def _engine(self, **kw):
        import jax.numpy as jnp

        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        from paddle_tpu.optimizer import AdamW
        from paddle_tpu.training.engine import TrainEngine

        pt.seed(0)
        model = LlamaForCausalLM(llama_tiny(
            vocab_size=64, hidden_size=32, layers=1, heads=2,
            kv_heads=2, intermediate_size=64))
        eng = TrainEngine(model, AdamW(learning_rate=1e-3), **kw)
        rng = np.random.default_rng(0)
        batch = jnp.asarray(rng.integers(0, 64, (4, 9)), jnp.int32)
        return eng, batch

    def test_window_metrics_recorded_at_sync_only(self):
        eng, batch = self._engine(log_window=3)
        eng.step((batch,))
        eng.step((batch,))
        snap = obs.REGISTRY.snapshot()
        assert 'train.steps' not in snap        # window still open
        eng.step((batch,))                      # closes the window
        snap = obs.REGISTRY.snapshot()
        assert snap['train.steps']['value'] == 3
        assert snap['train.tokens']['value'] == 3 * batch.size
        assert snap['train.step_ms']['count'] == 3
        assert snap['train.tokens_per_s']['value'] > 0
        assert snap['train.loss']['value'] is not None
        assert snap['train.traces']['value'] >= 1   # the first compile

    def test_loss_scale_rides_the_window_sync(self):
        from paddle_tpu.amp import GradScaler

        eng, batch = self._engine(log_window=2,
                                  scaler=GradScaler(
                                      init_loss_scaling=512.0))
        eng.step((batch,))
        eng.step((batch,))
        snap = obs.REGISTRY.snapshot()
        assert snap['train.loss_scale']['value'] >= 512.0

    def test_prefetch_metrics(self):
        batches = [np.ones((2, 3), np.float32) for _ in range(5)]
        from paddle_tpu.io.dataloader import prefetch_to_device

        out = list(prefetch_to_device(iter(batches), size=2))
        assert len(out) == 5
        snap = obs.REGISTRY.snapshot()
        assert snap['io.prefetch_batches']['value'] == 5
        assert snap['io.prefetch_wait_ms']['count'] == 5
        assert snap['io.prefetch_depth']['value'] is not None

    def test_shm_backoff_counter(self):
        from paddle_tpu.io.dataloader import _push_with_backoff

        calls = []

        def push():
            calls.append(1)
            return len(calls) >= 4

        _push_with_backoff(push, timeout=1, sleep=lambda s: None)
        snap = obs.REGISTRY.snapshot()
        assert snap['io.shm_backoff']['value'] == 3


# ---------------------------------------------------------------------------
# Meta: the instrumented tree stays tracelint-clean
# ---------------------------------------------------------------------------

class TestMetaTracelint:
    def test_no_new_violations_and_baseline_is_zero(self):
        """The acceptance property for an instrumentation PR: adding
        telemetry introduced no jit/donation/host-sync violations, and
        the committed baseline is still ZERO (burned down in PR 3 —
        neither the PR-6 metrics layer nor the PR-12 flight-recorder /
        cost-observatory / postmortem instrumentation may regrow it)."""
        from paddle_tpu.analysis import (filter_new, lint_paths,
                                         load_baseline)

        vs = lint_paths([os.path.join(REPO, 'paddle_tpu')], root=REPO)
        baseline = load_baseline(
            os.path.join(REPO, 'tools', 'tracelint_baseline.json'))
        new = filter_new(vs, baseline)
        assert new == [], 'new tracelint violations:\n' + '\n'.join(
            v.render() for v in new)
        assert sum(baseline.get('counts', {}).values()) == 0, (
            'the tracelint baseline must stay ZERO')
        # the flight-recorder modules specifically: the whole-tree lint
        # above covers them, but pin the instrumentation baseline at
        # zero BY NAME so a future per-file baseline bump here is loud
        obs_dir = os.path.join(REPO, 'paddle_tpu', 'observability')
        for name in ('journal.py', 'costs.py', 'postmortem.py',
                     'timeseries.py', 'watchdog.py', 'httpd.py'):
            vs = lint_paths([os.path.join(obs_dir, name)], root=REPO)
            assert vs == [], (
                f'{name} must stay tracelint-clean:\n'
                + '\n'.join(v.render() for v in vs))

    def test_observability_core_has_no_jax_dependency(self):
        """The registry/tracer/journal/postmortem must be importable
        (and recordable) without a backend — stdlib-only at module
        level by design; tracing only reaches for jax inside
        span(), costs only inside its device/lowering helpers."""
        import paddle_tpu.observability.costs as c
        import paddle_tpu.observability.httpd as hs
        import paddle_tpu.observability.journal as j
        import paddle_tpu.observability.metrics as m
        import paddle_tpu.observability.postmortem as p
        import paddle_tpu.observability.timeseries as s
        import paddle_tpu.observability.tracing as t
        import paddle_tpu.observability.watchdog as w

        assert 'import jax' not in open(m.__file__).read()
        for mod in (t, j, c, p, s, w, hs):
            top_level = [ln for ln in open(mod.__file__).read().splitlines()
                         if ln.startswith(('import ', 'from '))]
            assert not any('jax' in ln for ln in top_level), mod.__name__


# ---------------------------------------------------------------------------
# The engines' spans in the profiler's trace and in the ring
# ---------------------------------------------------------------------------

class _Session:
    """A jax.profiler session over a block (python tracing off), then the
    program's own spans as the profiler recorded them:
    `spans` = [(line, name, start_ns, end_ns)] in start order."""

    def __init__(self, trace_dir):
        self.dir, self.spans = str(trace_dir), None

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(self.dir, 'plugins', 'profile', '*',
                                       '*.xplane.pb'))
        self.spans = sorted(
            ((f'{plane.name}/{line.name}', e.name, e.start_ns,
              e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith('/host:')
             for line in plane.lines for e in line.events
             if e.name.startswith(('serve.', 'train.'))),
            key=lambda s: (s[2], -s[3]))
        return False

    def named(self, name):
        return [s for s in self.spans if s[1] == name]

    def inside(self, parent):
        """The spans that began while `parent` was open, on its line."""
        return [s for s in self.spans if s is not parent
                and s[0] == parent[0] and parent[2] <= s[2] < parent[3]]


def _ring(name, events=None):
    events = obs.TRACER.events() if events is None else events
    return [e for e in events if e['name'] == name]


STEP_CHILDREN = {'serve.admit', 'serve.admission', 'serve.top_up',
                 'serve.prefill', 'serve.stage', 'serve.dispatch',
                 'serve.host_read', 'serve.commit'}


class TestSpansOnTheProfilersClock:
    def _engine(self, max_slots=2):
        from paddle_tpu.inference.serving import ServingEngine

        srv = ServingEngine(_model(), max_slots=max_slots, block_size=8,
                            max_context_len=64, max_new_tokens=8,
                            decode_window=4)
        # both buckets' programs and the window compile outside the
        # session, and leave spans in the ring from before it
        srv.serve([_prompt(70, 6), _prompt(71, 20)])
        return srv

    @staticmethod
    def _run(srv, prompts):
        """submit + step to the end. Returns (requests, steps made,
        deliveries [(rid, first, n)] over all steps)."""
        reqs = [srv._live[srv.submit(p)] for p in prompts]
        steps, delivered = 0, []
        while srv.in_flight() or len(srv.queue):
            srv.step()
            steps += 1
            delivered += srv.last_deliveries
        return reqs, steps, delivered

    def test_serve_spans_nest_in_the_profilers_trace(self, tmp_path):
        srv = self._engine()
        before = len(obs.TRACER)
        with _Session(tmp_path) as prof:
            # two slots, four requests of two buckets: steps that admit
            # (one with a standalone second-bucket prefill) and bare ones
            _, steps, _ = self._run(srv, [_prompt(1, 6), _prompt(2, 20),
                                          _prompt(3, 7), _prompt(4, 5)])
        self._run(srv, [_prompt(5, 6)])          # after the session
        parents = prof.named('serve.step')
        assert len(parents) == steps >= 4
        assert prof.named('serve.prefill')
        kinds = [e['args']['kind'] for e in _ring('serve.step',
                                                  obs.TRACER.traced())]
        assert kinds.count('step') >= 2 and kinds.count('window') >= 2
        covered = 0
        for parent in parents:
            children = prof.inside(parent)
            assert {c[1] for c in children} <= STEP_CHILDREN
            assert {'serve.top_up', 'serve.stage', 'serve.dispatch',
                    'serve.host_read', 'serve.commit'} <= {
                        c[1] for c in children}
            assert all(c[3] <= parent[3] for c in children), children
            edge = parent[2]
            for _line, _name, start, end in children:    # their union
                covered += max(0, end - max(start, edge))
                edge = max(edge, end)
        whole = sum(p[3] - p[2] for p in parents)
        assert covered >= 0.9 * whole
        # the ring's cut to the session: the same spans in the same
        # number, none from before start_trace or after stop_trace
        traced = obs.TRACER.traced()
        count = lambda names: sorted(           # noqa: E731
            (n, names.count(n)) for n in set(names))
        assert count([e['name'] for e in traced]) == count(
            [s[1] for s in prof.spans])
        assert all(e.get('traced') for e in traced)
        events = obs.TRACER.events()
        assert not any(e.get('traced') for e in events[:before])
        assert len(_ring('serve.step')) > len(parents) + 1
        assert len(_ring('serve.step', traced)) == len(parents)

    def test_span_args_count_what_the_step_did(self):
        srv = self._engine(max_slots=4)
        obs.TRACER.clear()
        prompts = [_prompt(11, 6), _prompt(12, 20), _prompt(13, 9)]
        reqs, steps, delivered = self._run(srv, prompts)
        tokens = sum(len(r.generated) for r in reqs)
        assert tokens == 3 * 8
        assert sum(e['args']['committed']
                   for e in _ring('serve.commit')) == tokens
        assert sum(e['args']['committed']
                   for e in _ring('serve.step')) == tokens
        assert sum(e['args']['finished']
                   for e in _ring('serve.commit')) == 3
        admitting = [e['args'] for e in _ring('serve.dispatch')
                     + _ring('serve.prefill') if e['args']['rows']]
        assert [a['bucket'] for a in admitting] == [16, 32]   # fused first
        assert all(a['padded_tokens'] == 4 * a['bucket'] for a in admitting)
        assert sum(a['real_tokens'] for a in admitting) == 6 + 20 + 9
        assert sum(a['rows'] for a in admitting) == 3
        first, = [e['args'] for e in _ring('serve.dispatch')
                  if e['args']['kind'] == 'step']
        assert (first['live'], first['slots']) == (3, 4)
        bare = [e['args'] for e in _ring('serve.dispatch')
                if e['args']['kind'] == 'window']
        assert len(bare) == steps - 1
        assert all(a['padded_tokens'] == a['real_tokens'] == 0
                   for a in bare)
        admit, = _ring('serve.admit')
        assert admit['args'] == {'admitted': 3, 'queue_depth': 0}
        waits = {e['args']['rid']: e['args'] for e in
                 _ring('serve.admission')}
        for req in reqs:
            a = waits[req.rid]
            assert a['wait_ms'] == pytest.approx(
                (req.when('admitted') - req.enqueued_at) * 1e3, abs=1.0)
            assert a['prompt_len'] == len(req.prompt)
            assert a['bucket'] == (32 if len(req.prompt) > 16 else 16)

    def test_dispatch_counts_the_paged_kernels_pages(self):
        """`pages_needed`: per attention layer and live row, the pages up
        to the row's context less those wholly behind the layer's window;
        `pages_table`: the table entries the layers' calls are handed.
        One window layer (8 positions) and one full layer, pages of 4."""
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import afmoe

        pt.seed(0)
        model = afmoe.AfmoeForCausalLM(afmoe.afmoe_tiny(
            num_hidden_layers=2, layer_types=[afmoe.SLIDING, afmoe.FULL],
            sliding_window=8))
        srv = ServingEngine(model, max_slots=4, block_size=4,
                            max_context_len=64, max_new_tokens=8,
                            decode_window=4)
        obs.TRACER.clear()
        self._run(srv, [_prompt(51, 6), _prompt(52, 21)])
        got = [(e['args']['kind'], e['args']['pages_needed'],
                e['args']['pages_table']) for e in _ring('serve.dispatch')]
        # contexts 6 and 21, then 10 and 25. Full layer: 2 + 6 pages, then
        # 3 + 7. Window layer: positions from 0 and 13 on, then 2 and 17:
        # 2 - 0 and 6 - 3 pages, then 3 - 0 and 7 - 4
        table = 2 * 4 * (64 // 4)
        assert got == [('step', 8 + 5, table), ('window', 10 + 6, table)]

    def test_last_deliveries_is_what_the_step_delivered(self):
        srv = self._engine(max_slots=4)
        reqs, steps, delivered = self._run(srv, [_prompt(21, 6),
                                                 _prompt(22, 20)])
        for req in reqs:
            mine = [(first, n) for rid, first, n in delivered
                    if rid == req.rid]
            assert mine == [(0, 4), (4, 4)]
        srv.step()                                   # nothing to run
        assert srv.last_deliveries == []
        assert _ring('serve.step')[-1]['args'] == {'kind': 'idle'}

    def test_a_step_that_raises_leaves_no_span_open(self, tmp_path):
        from paddle_tpu.testing.faults import FaultError, FaultInjector

        srv = self._engine()
        obs.TRACER.clear()
        rids = [srv.submit(_prompt(s, 6)) for s in (31, 32)]
        inj = FaultInjector()
        inj.script('dispatch', when=lambda c: c.get('kind') == 'window',
                   times=1)
        with _Session(tmp_path) as prof:
            with inj:
                with pytest.raises(FaultError):
                    srv.step()
            srv.run()
        assert all(srv.result(r) is not None for r in rids)
        steps = prof.named('serve.step')
        assert len(steps) == len(_ring('serve.step')) >= 3
        # the step that raised is closed in both sinks (an open
        # annotation is never recorded; an open ring span never emitted)
        # and the next step is not nested inside it
        assert all(a[3] <= b[2] for a, b in zip(steps, steps[1:]))
        raised = prof.inside(steps[0])
        assert {'serve.top_up', 'serve.stage'} <= {c[1] for c in raised}
        assert 'serve.dispatch' not in {c[1] for c in raised}
        assert all(c[3] <= steps[0][3] for c in raised)
        assert 'kind' not in _ring('serve.step')[0].get('args', {})

    def test_telemetry_off_keeps_the_profilers_spans(self, tmp_path):
        srv = self._engine()
        obs.TRACER.clear()
        obs.set_enabled(False)
        try:
            with _Session(tmp_path) as prof:
                _, steps, _ = self._run(srv, [_prompt(41, 6)])
            # and with no session either: nothing recorded, nothing raised
            self._run(srv, [_prompt(42, 6)])
        finally:
            obs.set_enabled(True)
        assert len(obs.TRACER) == 0 and obs.TRACER.traced() == []
        assert len(prof.named('serve.step')) == steps
        assert len(prof.named('serve.dispatch')) == steps
        assert len(prof.named('serve.admission')) == 1

    def test_train_spans_in_both_sinks(self, tmp_path):
        eng, batch = TestTrainTelemetry._engine(None, log_window=10 ** 9)
        eng.step((batch,))
        eng.sync()                               # compiled
        obs.TRACER.clear()
        with _Session(tmp_path) as prof:
            feed = eng.prefetch(np.asarray(batch) for _ in range(3))
            for b in feed:
                eng.step((b,))
            eng.sync()
        for name, n in (('train.step', 3), ('train.sync', 1),
                        ('train.feed', 4)):      # the 4th finds the end
            assert len(prof.named(name)) == n, name
            assert len(_ring(name, obs.TRACER.traced())) == n, name
        assert [e['args'] for e in _ring('train.step')] == [
            {'tokens': batch.size}] * 3
        assert _ring('train.sync')[0]['args'] == {'window': 3}
        # a step is dispatch only: no span of the feed or the sync inside
        for step in prof.named('train.step'):
            assert prof.inside(step) == []

    def test_traced_is_the_newest_session(self, tmp_path):
        t = HostTracer()
        with t.span('before'):
            pass
        with _Session(tmp_path / 'a'):
            with t.span('first', n=1):
                pass
        t.instant('between')
        assert [e['name'] for e in t.traced()] == ['first']
        with _Session(tmp_path / 'b'):
            with t.span('outer') as sp:
                t.instant('tick', k=2)
                sp.set(late=3)
        with t.span('after'):
            pass
        assert [(e['name'], e.get('args')) for e in t.traced()] == [
            ('tick', {'k': 2}), ('outer', {'late': 3})]
        assert [e['name'] for e in t.events() if e.get('traced')] == [
            'first', 'tick', 'outer']
        t.clear()
        assert t.traced() == []

    def test_late_args_reach_the_annotation(self, tmp_path):
        import glob

        from jax.profiler import ProfileData

        with _Session(tmp_path):
            sp = obs.span('serve.commit', cat='test', slot=1).begin()
            sp.end(committed=5)
            obs.instant('serve.admission', rid=7)
        path, = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*' /
                              '*.xplane.pb'))
        stats = {e.name: dict(e.stats)
                 for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events
                 if e.name.startswith('serve.')}
        assert stats == {'serve.commit': {'slot': 1, 'committed': 5},
                         'serve.admission': {'rid': 7}}
        assert _ring('serve.commit')[0]['args'] == {'slot': 1,
                                                    'committed': 5}

    def test_one_code_path_opens_a_span(self):
        """`span` is the one host span primitive: nothing else in the
        package opens a TraceAnnotation of its own."""
        import re

        hits = []
        for root, _dirs, files in os.walk(os.path.join(REPO, 'paddle_tpu')):
            for name in files:
                if name.endswith('.py'):
                    path = os.path.join(root, name)
                    if re.search(r'TraceAnnotation\(', open(path).read()):
                        hits.append(os.path.relpath(path, REPO))
        assert hits in ([], ['paddle_tpu/observability/tracing.py'])
