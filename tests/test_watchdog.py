"""SLO watchdog + windowed timeseries + ops endpoint (PR 14).

Covers the tentpole properties:
  - timeseries: EXACT window/rate/percentile arithmetic against
    hand-computed sequences, interval pacing, bounded ring, registry-
    reset safety, derived rate gauges (`serve.tok_s` et al.);
  - watchdog: expression forms, for_windows/clear_windows hysteresis
    with breach/recovery EDGES (journaled + counted), no-data
    semantics (missing evidence neither pages nor clears), throttled
    auto-postmortem, state snapshot/load;
  - httpd: /metrics, /healthz (drain-aware 200/503), /statusz, /slo
    over a real socket;
  - engine integration: /healthz flips 200 -> 503 -> 200 under a
    FaultInjector-induced failure storm and recovery, watchdog state
    survives `snapshot()`/`restore()`, draining refuses submissions,
    zero retraces from the operability layer;
  - meta: the three new modules stay jax-free and tracelint-clean.
"""
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt

# tier-1: the live health verdict ROADMAP item 1's fleet routing and
# drain/rebalance are built on; a silent regression here strands a
# router on a sick replica
pytestmark = pytest.mark.tier1

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.observability import journal as jr  # noqa: E402
from paddle_tpu.observability import timeseries as ts  # noqa: E402
from paddle_tpu.observability import watchdog as wd  # noqa: E402
from paddle_tpu.observability.httpd import start_ops_server  # noqa: E402
from paddle_tpu.observability.timeseries import (  # noqa: E402
    WindowedTimeseries,
    percentile_from_buckets,
)
from paddle_tpu.observability.watchdog import (  # noqa: E402
    SLORule,
    Watchdog,
    default_serving_rules,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.set_enabled(True)
    jr.set_journal_enabled(True)
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    jr.JOURNAL.clear()
    ts.TIMESERIES.reset()
    yield
    obs.set_enabled(True)
    jr.set_journal_enabled(True)


def _get(url):
    """(status, parsed json|text) tolerating non-2xx."""
    try:
        r = urllib.request.urlopen(url, timeout=10)
        code, body = r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        code, body = e.code, e.read().decode()
    try:
        return code, json.loads(body)
    except ValueError:
        return code, body


# ---------------------------------------------------------------------------
# Windowed timeseries: exact arithmetic
# ---------------------------------------------------------------------------

class TestTimeseries:
    def test_counter_delta_and_rate_exact(self):
        t = WindowedTimeseries(interval_s=1.0)
        assert t.maybe_commit(now=100.0) is None      # baseline only
        obs.inc('serve.tokens', 30)
        w = t.commit(now=102.0)                       # 2s window
        assert w['counters']['serve.tokens'] == {'delta': 30,
                                                 'rate': 15.0}
        obs.inc('serve.tokens', 10)
        w2 = t.commit(now=106.0)                      # 4s window
        assert w2['counters']['serve.tokens'] == {'delta': 10,
                                                  'rate': 2.5}
        assert w2['idx'] == w['idx'] + 1
        # accessors agree with the per-window records
        assert t.rate('serve.tokens') == 2.5
        assert t.delta('serve.tokens', windows=2) == 40
        # rolling rate over both windows: 40 tokens over 6 seconds
        assert t.rate('serve.tokens', windows=2) == pytest.approx(40 / 6)

    def test_interval_pacing(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=10.0)
        obs.inc('c', 1)
        assert t.maybe_commit(now=10.5) is None       # inside the window
        assert len(t) == 0
        w = t.maybe_commit(now=11.25)                 # past the interval
        assert w is not None and w['dur_s'] == pytest.approx(1.25)
        assert w['counters']['c']['delta'] == 1

    def test_gauges_ride_as_last_values(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        obs.set_gauge('serve.queue_depth', 7)
        w = t.commit(now=1.0)
        assert w['gauges']['serve.queue_depth'] == 7.0
        assert t.gauge('serve.queue_depth') == 7.0

    def test_histogram_window_percentile_hand_computed(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        # buckets (1, 2, 4, 8): 2 obs land in le=1, 3 in le=4
        for v in (0.5, 1.0, 3.0, 3.0, 4.0):
            obs.observe('lat', v, buckets=(1, 2, 4, 8))
        w = t.commit(now=1.0)
        h = w['hists']['lat']
        assert h['count'] == 5
        assert h['sum'] == pytest.approx(11.5)
        assert h['mean'] == pytest.approx(2.3)
        assert h['buckets'] == [2, 0, 3, 0, 0]
        # p50: rank 2.5 -> lands in le=4 (prev_cum 2, c 3):
        # lo=2, hi=4, frac=(2.5-2)/3 -> 2 + 2/6
        assert h['p50'] == pytest.approx(2 + 2 / 6)
        # p99: rank 4.95 -> frac (4.95-2)/3 -> 2 + 2*0.98333
        assert h['p99'] == pytest.approx(2 + 2 * (2.95 / 3))

    def test_window_percentile_is_windowed_not_cumulative(self):
        """The rolling view forgets what the cumulative histogram
        absorbed: a bad first window must not pollute the second."""
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        for _ in range(100):
            obs.observe('lat', 900.0, buckets=(1, 10, 1000))
        w1 = t.commit(now=1.0)
        assert w1['hists']['lat']['p50'] > 10
        for _ in range(100):
            obs.observe('lat', 0.5, buckets=(1, 10, 1000))
        w2 = t.commit(now=2.0)
        assert w2['hists']['lat']['p50'] <= 1.0       # the window's own
        # cumulative registry p50 still blends both (pinned AT the
        # first bucket edge by the 50/50 split)
        assert obs.REGISTRY.get('lat').percentile(50) >= 1.0
        # merged rolling percentile over both windows straddles
        merged = t.wpercentile('lat', 50, windows=2)
        assert 0 < merged <= 10.0

    def test_percentile_from_buckets_edge_cases(self):
        edges = (1, 2, 4)
        assert percentile_from_buckets(edges, [0, 0, 0, 0], 99) is None
        # everything in the +inf bucket clamps to the last finite edge
        assert percentile_from_buckets(edges, [0, 0, 0, 5], 50) == 4.0
        # first bucket interpolates from 0
        assert percentile_from_buckets(edges, [4, 0, 0, 0], 50) == \
            pytest.approx(0.5)

    def test_ring_bounded(self):
        t = WindowedTimeseries(interval_s=1.0, max_windows=4)
        t.maybe_commit(now=0.0)
        for i in range(10):
            t.commit(now=float(i + 1))
        assert len(t) == 4
        idxs = [w['idx'] for w in t.windows()]
        assert idxs == [6, 7, 8, 9]
        assert t.snapshot()['committed'] == 10

    def test_registry_reset_never_goes_negative(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        obs.inc('c', 100)
        t.commit(now=1.0)
        obs.REGISTRY.reset()                  # counters restart at zero
        obs.inc('c', 3)
        w = t.commit(now=2.0)
        assert w['counters']['c']['delta'] == 3

    def test_derived_rate_gauges_published(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        obs.inc('serve.tokens', 50)
        obs.inc('serve.requests', 4)
        obs.inc('serve.finished', 3)
        obs.inc('serve.failed', 1)
        obs.inc('serve.preemptions', 2)
        t.commit(now=2.0)
        R = obs.REGISTRY
        assert R.get('serve.tok_s').value == 25.0
        assert R.get('serve.req_s').value == 2.0
        assert R.get('serve.preempt_s').value == 1.0
        assert R.get('serve.err_rate').value == 0.25
        # a window with no terminal outcomes leaves err_rate untouched
        obs.inc('serve.tokens', 10)
        t.commit(now=3.0)
        assert R.get('serve.err_rate').value == 0.25
        assert R.get('serve.tok_s').value == 10.0

    def test_private_registry_derived_gauges_stay_private(self):
        """The per-replica isolation recipe: a ring over a PRIVATE
        registry publishes its rate gauges into THAT registry — never
        clobbering another replica's serve.tok_s in the global one."""
        from paddle_tpu.observability.metrics import MetricsRegistry

        priv = MetricsRegistry()
        t = WindowedTimeseries(interval_s=1.0, registry=priv)
        t.maybe_commit(now=0.0)
        priv.counter('serve.tokens').inc(40)
        t.commit(now=2.0)
        assert priv.get('serve.tok_s').value == 20.0
        assert obs.REGISTRY.get('serve.tok_s') is None

    def test_disabled_telemetry_commits_nothing(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        obs.set_enabled(False)
        assert t.commit(now=5.0) is None
        assert len(t) == 0

    def test_snapshot_json_roundtrip(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        obs.inc('serve.tokens', 5)
        obs.observe('lat', 2.0, buckets=(1, 4))
        t.commit(now=1.0)
        snap = json.loads(t.to_json())
        assert snap['windows'][0]['counters']['serve.tokens']['delta'] == 5
        assert snap['windows'][0]['hists']['lat']['count'] == 1

    def test_validation(self):
        with pytest.raises(ValueError):
            WindowedTimeseries(interval_s=0)
        with pytest.raises(ValueError):
            WindowedTimeseries(max_windows=0)


# ---------------------------------------------------------------------------
# SLO rules + watchdog state machine
# ---------------------------------------------------------------------------

def _mkwindow(tseries, now):
    """Commit one window on the shared registry through `tseries`."""
    w = tseries.commit(now=now)
    assert w is not None
    return w


class TestSLORule:
    def test_expr_forms(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        obs.inc('serve.tokens', 20)
        obs.inc('serve.failed', 1)
        obs.inc('serve.requests', 4)
        obs.set_gauge('serve.queue_depth', 9)
        obs.observe('serve.ttft_ms', 100.0, n=4, buckets=(50, 200, 400))
        w = _mkwindow(t, 2.0)
        assert SLORule('a', 'rate(serve.tokens)', '>', 0).evaluate(
            w, t) == 10.0
        assert SLORule('b', 'delta(serve.tokens)', '>', 0).evaluate(
            w, t) == 20
        assert SLORule('c', 'gauge(serve.queue_depth)', '>', 0).evaluate(
            w, t) == 9.0
        assert SLORule('d', 'counter(serve.tokens)', '>', 0).evaluate(
            w, t) == 20
        assert SLORule('e', 'ratio(serve.failed,serve.requests)', '>',
                       0).evaluate(w, t) == 0.25
        assert SLORule('f', 'p99(serve.ttft_ms)', '>', 0).evaluate(
            w, t) == pytest.approx(50 + 150 * (3.96 - 0) / 4)
        assert SLORule('g', 'mean(serve.ttft_ms)', '>', 0).evaluate(
            w, t) == pytest.approx(100.0)
        # histogram delta/rate through the counter forms
        assert SLORule('h', 'delta(serve.ttft_ms)', '>', 0).evaluate(
            w, t) == 4
        # absent metric -> None (no data)
        assert SLORule('i', 'rate(nope)', '>', 0).evaluate(w, t) is None

    def test_invalid_exprs_and_ops(self):
        with pytest.raises(ValueError):
            SLORule('x', 'bogus(serve.tokens)', '>', 0)
        with pytest.raises(ValueError):
            SLORule('x', 'rate serve.tokens', '>', 0)
        with pytest.raises(ValueError):
            SLORule('x', 'rate(a,b)', '>', 0)       # two args, not ratio
        with pytest.raises(ValueError):
            SLORule('x', 'ratio(a)', '>', 0)        # ratio needs two
        with pytest.raises(ValueError):
            SLORule('x', 'rate(a)', '~', 0)
        with pytest.raises(ValueError):
            SLORule('x', 'rate(a)', '>', 0, for_windows=0)


class TestWatchdog:
    def _dog(self, for_windows=2, clear_windows=2, **kw):
        return Watchdog([SLORule('qd', 'gauge(q)', '>=', 10.0,
                                 for_windows=for_windows,
                                 clear_windows=clear_windows)], **kw)

    def _drive(self, dog, t, now, q):
        if q is not None:
            obs.set_gauge('q', q)
        dog.evaluate(_mkwindow(t, now), t)

    def test_hysteresis_breach_and_recovery_edges(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog(for_windows=3, clear_windows=2)
        self._drive(dog, t, 1.0, 15)        # 1 breaching window: still ok
        assert dog.healthy()
        self._drive(dog, t, 2.0, 15)
        assert dog.healthy()
        self._drive(dog, t, 3.0, 15)        # 3rd consecutive: BREACH edge
        assert not dog.healthy() and dog.breaching() == ['qd']
        assert dog.breaches_total == 1
        self._drive(dog, t, 4.0, 15)        # still breached, no new edge
        assert dog.breaches_total == 1
        self._drive(dog, t, 5.0, 2)         # 1 clean window: still breached
        assert not dog.healthy()
        self._drive(dog, t, 6.0, 2)         # 2nd clean: RECOVERY edge
        assert dog.healthy()
        assert dog.recoveries_total == 1
        # edges journaled as structured events, counted in watchdog.*
        kinds = [e['kind'] for e in jr.JOURNAL.tail()]
        assert kinds.count('slo_breach') == 1
        assert kinds.count('slo_recovered') == 1
        breach = next(e for e in jr.JOURNAL.tail()
                      if e['kind'] == 'slo_breach')
        assert breach['rule'] == 'qd' and breach['value'] == 15
        R = obs.REGISTRY
        assert R.get('watchdog.breaches').value == 1
        assert R.get('watchdog.recoveries').value == 1
        assert R.get('watchdog.healthy').value == 1.0

    def test_blip_never_pages(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog(for_windows=2)
        for i, q in enumerate((15, 2, 15, 2, 15, 2)):   # alternating blips
            self._drive(dog, t, float(i + 1), q)
        assert dog.healthy() and dog.breaches_total == 0

    def test_no_data_resets_recovery_streak_too(self):
        """Recovery needs clear_windows CONSECUTIVE healthy windows
        WITH data — a no-evidence gap restarts the count, so an
        intermittent-traffic engine cannot flap out of breach faster
        than the hysteresis promises."""
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog(for_windows=1, clear_windows=2)
        self._drive(dog, t, 1.0, 15)                 # breach
        assert not dog.healthy()
        self._drive(dog, t, 2.0, 2)                  # healthy #1
        obs.REGISTRY.reset()
        self._drive(dog, t, 3.0, None)               # no data: restart
        self._drive(dog, t, 4.0, 2)                  # healthy #1 again
        assert not dog.healthy()
        self._drive(dog, t, 5.0, 2)                  # healthy #2
        assert dog.healthy()

    def test_no_data_neither_pages_nor_clears(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog(for_windows=2, clear_windows=1)
        self._drive(dog, t, 1.0, 15)
        # gauge never written again would still ride as last value in
        # later windows — reach no_data via a registry reset instead
        obs.REGISTRY.reset()
        self._drive(dog, t, 2.0, None)       # no data: streak reset
        st = dog.state()['qd']
        assert st['last'] == 'no_data' and st['true_streak'] == 0
        self._drive(dog, t, 3.0, 15)
        assert dog.healthy()                 # needed 2 CONSECUTIVE
        self._drive(dog, t, 4.0, 15)
        assert not dog.healthy()
        obs.REGISTRY.reset()
        self._drive(dog, t, 5.0, None)       # no data while breached:
        assert not dog.healthy()             # the breach HOLDS

    def test_duplicate_rule_names_refused(self):
        r = SLORule('x', 'rate(a)', '>', 0)
        with pytest.raises(ValueError):
            Watchdog([r, SLORule('x', 'rate(b)', '>', 0)])

    def test_state_snapshot_load_roundtrip(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog(for_windows=1)
        self._drive(dog, t, 1.0, 15)
        assert not dog.healthy()
        snap = json.loads(json.dumps(dog.snapshot_state()))
        dog2 = self._dog(for_windows=1)
        assert dog2.load_state(snap) == 1
        assert not dog2.healthy()
        assert dog2.breaches_total == 1
        # unknown rules in the snapshot are dropped; rules the
        # snapshot never saw keep fresh state
        dog3 = Watchdog([SLORule('other', 'rate(a)', '>', 0)])
        assert dog3.load_state(snap) == 0
        assert dog3.healthy()
        with pytest.raises(ValueError):
            dog2.load_state({'schema': 99})

    def test_last_window_idx_rides_snapshot(self):
        """A restored standby's verdict() reports the primary's last
        evaluated window index, not a fresh None — and a schema-1
        snapshot from before the field existed still loads."""
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog()
        self._drive(dog, t, 1.0, 2)
        self._drive(dog, t, 2.0, 2)
        assert dog.last_window_idx is not None
        snap = json.loads(json.dumps(dog.snapshot_state()))
        assert snap['last_window_idx'] == dog.last_window_idx
        dog2 = self._dog()
        dog2.load_state(snap)
        assert dog2.last_window_idx == dog.last_window_idx
        assert (dog2.verdict()['last_window_idx']
                == dog.last_window_idx)
        # back-compat: the field is a schema-1-compatible addition
        old = {k: v for k, v in snap.items() if k != 'last_window_idx'}
        dog3 = self._dog()
        dog3.load_state(old)
        assert dog3.last_window_idx is None

    def test_recovery_after_restored_state_clamps_duration(self):
        """A standby adopting the primary's breach carries the
        PRIMARY's window index; recovering on the standby's fresh ring
        must journal breached_windows 0, never a negative count."""
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        donor = self._dog(for_windows=1, clear_windows=1)
        snap = donor.snapshot_state()
        snap['rules']['qd'].update({'state': 'breach',
                                    'breached_at_idx': 500,
                                    'breaches': 1})
        dog = self._dog(for_windows=1, clear_windows=1)
        dog.load_state(snap)
        assert not dog.healthy()
        self._drive(dog, t, 1.0, 2)          # heals on window idx 0
        assert dog.healthy()
        ev = [e for e in jr.JOURNAL.tail()
              if e['kind'] == 'slo_recovered'][-1]
        assert ev['breached_windows'] == 0

    def test_throttled_auto_postmortem(self, tmp_path):
        class FakeEngine:
            postmortem_dir = str(tmp_path)

            def __init__(self):
                self.dumps = []

            def _auto_postmortem(self, error):
                self.dumps.append(repr(error))

        eng = FakeEngine()
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = self._dog(for_windows=1, clear_windows=1,
                        postmortem_engine=eng,
                        postmortem_min_interval_s=3600.0)
        self._drive(dog, t, 1.0, 15)         # breach 1: dumps
        self._drive(dog, t, 2.0, 2)          # recover
        self._drive(dog, t, 3.0, 15)         # breach 2: THROTTLED
        assert len(eng.dumps) == 1
        assert 'qd' in eng.dumps[0]

    def test_default_serving_rules_catalog(self):
        names = {r.name for r in default_serving_rules()}
        assert {'ttft_p99', 'itl_p99', 'error_rate', 'steady_retraces',
                'pool_pressure', 'trace_drops', 'journal_drops',
                'mfu_floor'} <= names
        assert 'queue_depth' not in names    # unbounded queue: no rule

        class Eng:
            max_queue = 100

        rules = default_serving_rules(engine=Eng())
        qd = next(r for r in rules if r.name == 'queue_depth')
        assert qd.threshold == 90.0
        # the default ruleset evaluates clean on an empty window
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = Watchdog(rules)
        dog.evaluate(_mkwindow(t, 1.0), t)
        assert dog.healthy()


# ---------------------------------------------------------------------------
# Ops HTTP endpoint (no engine)
# ---------------------------------------------------------------------------

class TestOpsServer:
    def test_endpoints_standalone(self):
        obs.inc('serve.tokens', 5)
        jr.record('hello', rid=1)
        srv = start_ops_server(None)
        try:
            code, body = _get(srv.url('/metrics'))
            assert code == 200 and 'serve_tokens 5' in body
            code, body = _get(srv.url('/healthz'))
            assert code == 200
            assert body == {'status': 'ok', 'watchdog': False,
                            'phase_role': 'monolithic'}
            code, body = _get(srv.url('/slo'))
            assert code == 404
            code, body = _get(srv.url('/statusz'))
            assert code == 200
            assert any(e['kind'] == 'hello' for e in body['journal_tail'])
            code, body = _get(srv.url('/bogus'))
            assert code == 404 and '/healthz' in body['paths']
        finally:
            srv.close()

    def test_healthz_verdicts(self):
        t = WindowedTimeseries(interval_s=1.0)
        t.maybe_commit(now=0.0)
        dog = Watchdog([SLORule('qd', 'gauge(q)', '>=', 10.0)])
        obs.set_gauge('q', 99)
        dog.evaluate(t.commit(now=1.0), t)
        srv = start_ops_server(None, watchdog=dog, timeseries=t)
        try:
            code, body = _get(srv.url('/healthz'))
            assert code == 503 and body['status'] == 'breach'
            assert body['breaching'] == ['qd']
            code, body = _get(srv.url('/slo'))
            assert code == 200 and body['rules']['qd']['state'] == 'breach'
            obs.set_gauge('q', 1)
            dog.evaluate(t.commit(now=2.0), t)
            code, body = _get(srv.url('/healthz'))
            assert code == 200 and body['status'] == 'ok'
        finally:
            srv.close()

    def test_healthz_drain_wins(self):
        class Eng:
            draining = True
            _ts = None
            _watchdog = None

            def stats(self):
                return {'ok': True}

        srv = start_ops_server(Eng())
        try:
            code, body = _get(srv.url('/healthz'))
            assert code == 503 and body == {'status': 'draining',
                                            'phase_role': 'monolithic'}
            code, body = _get(srv.url('/statusz'))
            assert code == 200 and body['draining'] is True
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# ServingEngine integration
# ---------------------------------------------------------------------------

def _model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                       layers=2))


def _err_rules(for_windows=2, clear_windows=2):
    return [SLORule('error_rate', 'ratio(serve.failed,serve.requests)',
                    '>', 0.2, for_windows=for_windows,
                    clear_windows=clear_windows)]


def _engine(model, **kw):
    from paddle_tpu.inference.serving import ServingEngine

    kw.setdefault('max_slots', 4)
    kw.setdefault('block_size', 8)
    kw.setdefault('max_context_len', 48)
    kw.setdefault('max_new_tokens', 8)
    kw.setdefault('decode_window', 4)
    return ServingEngine(model, **kw)


class TestServingIntegration:
    def test_default_engine_feeds_process_ring(self, monkeypatch):
        # a fresh process ring whose interval cannot elapse mid-test: on
        # the default 1 s ring a slow (loaded) host commits a window
        # during serve() and the forced window below can come out empty
        monkeypatch.setattr(ts, 'TIMESERIES',
                            ts.WindowedTimeseries(interval_s=3600.0))
        model = _model()
        srv = _engine(model)
        assert srv._ts is ts.TIMESERIES and srv._watchdog is None
        # first step opens the process ring's baseline; later steps
        # land inside the open window — force-close and look
        srv.serve([_p(i) for i in range(4)], 8)
        w = ts.TIMESERIES.commit()
        assert w['counters']['serve.tokens']['delta'] > 0

    def test_healthz_flips_under_faults_and_recovers(self):
        import time

        from paddle_tpu.testing.faults import FaultInjector

        model = _model()
        srv = _engine(model, ops_port=0, slo_rules=_err_rules(),
                      ts_interval_s=0.02)
        url = srv.ops_server.url
        try:
            for _ in range(3):
                srv.serve([_p(i) for i in range(4)], 4)
            assert _get(url('/healthz'))[0] == 200
            inj = FaultInjector(seed=0)
            inj.script('admit', times=10**9)
            deadline = time.perf_counter() + 60.0
            with inj:
                while (srv._watchdog.healthy()
                       and time.perf_counter() < deadline):
                    rids = [srv.submit(_p(i), 4) for i in range(4)]
                    srv.run()
                    for r in rids:
                        with pytest.raises(Exception):
                            srv.result(r)
            assert not srv._watchdog.healthy()
            code, body = _get(url('/healthz'))
            assert code == 503 and body['status'] == 'breach'
            assert 'error_rate' in body['breaching']
            assert any(e['kind'] == 'slo_breach'
                       for e in jr.JOURNAL.tail())
            deadline = time.perf_counter() + 60.0
            while (not srv._watchdog.healthy()
                   and time.perf_counter() < deadline):
                srv.serve([_p(i) for i in range(4)], 4)
            assert srv._watchdog.healthy()
            assert _get(url('/healthz'))[0] == 200
            assert any(e['kind'] == 'slo_recovered'
                       for e in jr.JOURNAL.tail())
        finally:
            srv.ops_server.close()

    def test_watchdog_state_survives_snapshot_restore(self):
        import time

        from paddle_tpu.testing.faults import FaultInjector

        model = _model()
        srv = _engine(model, slo_rules=_err_rules(), ts_interval_s=0.02)
        inj = FaultInjector(seed=0)
        inj.script('admit', times=10**9)
        deadline = time.perf_counter() + 60.0
        with inj:
            while (srv._watchdog.healthy()
                   and time.perf_counter() < deadline):
                rid = srv.submit(_p(1), 4)
                srv.run()
                with pytest.raises(Exception):
                    srv.result(rid)
        assert not srv._watchdog.healthy()
        snap = json.loads(json.dumps(srv.snapshot()))   # wire round-trip
        assert snap['watchdog']['rules']['error_rate']['state'] == 'breach'
        standby = _engine(model, slo_rules=_err_rules(),
                          ts_interval_s=0.02)
        standby.restore(snap)
        # continuous health history: the standby reports the
        # primary's ACTIVE breach instead of silently re-arming
        assert not standby._watchdog.healthy()
        assert standby._watchdog.breaches_total >= 1
        assert standby.stats()['watchdog']['healthy'] is False

    def test_snapshot_without_watchdog_restores_clean(self):
        model = _model()
        srv = _engine(model)
        rid = srv.submit(_p(2), 4)
        srv.run()
        srv.result(rid)
        snap = srv.snapshot()
        assert snap['watchdog'] is None
        standby = _engine(model, slo_rules=_err_rules())
        standby.restore(json.loads(json.dumps(snap)))   # no-op adopt
        assert standby._watchdog.healthy()

    def test_drain_refuses_submissions_and_flips_healthz(self):
        from paddle_tpu.inference.serving import QueueFull

        model = _model()
        srv = _engine(model, ops_port=0)
        try:
            srv.drain()
            code, body = _get(srv.ops_server.url('/healthz'))
            assert code == 503 and body == {'status': 'draining',
                                            'phase_role': 'monolithic'}
            with pytest.raises(QueueFull):
                srv.submit(_p(3), 4)
            assert srv.counts['rejected'] == 1
            assert srv.stats()['draining'] is True
            assert any(e['kind'] == 'drain' for e in jr.JOURNAL.tail())
            srv.drain(False)
            assert _get(srv.ops_server.url('/healthz'))[0] == 200
            rid = srv.submit(_p(3), 4)
            srv.run()
            assert srv.result(rid) is not None
        finally:
            srv.ops_server.close()

    def test_operability_layer_adds_zero_retraces(self):
        from paddle_tpu.inference.engine import total_traces

        model = _model()
        srv = _engine(model, watchdog=True, ts_interval_s=0.01)
        srv.serve([_p(i) for i in range(4)], 4)         # warm
        t0 = total_traces()
        for _ in range(3):
            srv.serve([_p(i) for i in range(4)], 4)
        srv._ts.commit()
        srv._watchdog.evaluate(srv._ts.last(), srv._ts)
        assert total_traces() == t0

    def test_close_releases_ops_port_for_replacement(self):
        """The supervisor hand-off rebinds the SAME port: without
        engine.close() the old daemon server thread holds the listen
        socket for the process lifetime and the new bind dies with
        EADDRINUSE."""
        model = _model()
        srv = _engine(model, ops_port=0)
        port = srv.ops_server.port
        srv.close()
        assert srv.ops_server is None
        srv.close()                                  # idempotent
        fresh = _engine(model, ops_port=port)        # rebinds cleanly
        try:
            assert _get(fresh.ops_server.url('/healthz'))[0] == 200
        finally:
            fresh.close()

    def test_breach_callback_error_is_not_a_worker_death(self, tmp_path):
        """An exception out of a user on_breach callback must surface
        as its own error — never ride the PR-8 crash path and dump a
        false 'worker death' postmortem bundle."""
        model = _model()
        rules = _err_rules(for_windows=1)
        dog = Watchdog(rules, on_breach=lambda r, st: (_ for _ in ()
                                                       ).throw(
                                                           RuntimeError(
                                                               'cb boom')))
        from paddle_tpu.testing.faults import FaultInjector

        srv = _engine(model, watchdog=dog, ts_interval_s=0.01,
                      postmortem_dir=str(tmp_path))
        import time

        inj = FaultInjector(seed=0)
        inj.script('admit', times=10**9)
        deadline = time.perf_counter() + 60.0
        raised = None
        with inj:
            while time.perf_counter() < deadline and raised is None:
                rid = srv.submit(_p(1), 4)
                try:
                    srv.run()
                except RuntimeError as e:
                    raised = e
                try:
                    srv.result(rid)
                except Exception:
                    pass
        assert raised is not None and 'cb boom' in str(raised)
        # the crash path did NOT fire: no bundle, engine steppable
        assert srv.last_postmortem is None
        srv.run()

    def test_statusz_reports_engine_truth(self):
        model = _model()
        srv = _engine(model, ops_port=0, watchdog=True,
                      ts_interval_s=0.02)
        try:
            srv.serve([_p(i) for i in range(4)], 4)
            srv._ts.commit()
            code, body = _get(srv.ops_server.url('/statusz'))
            assert code == 200
            assert body['engine']['geometry']['max_slots'] == 4
            assert body['watchdog']['healthy'] is True
            assert body['timeseries']['windows']
            assert body['journal_tail']
        finally:
            srv.ops_server.close()


def _p(seed, n=6):
    return np.random.default_rng(seed).integers(3, 96, (n,)).astype(
        np.int32)


# ---------------------------------------------------------------------------
# Meta: the new modules stay jax-free and tracelint-clean
# ---------------------------------------------------------------------------

class TestMeta:
    def test_new_modules_have_no_top_level_jax(self):
        from paddle_tpu.observability import httpd

        for mod in (ts, wd, httpd):
            top = [ln for ln in open(mod.__file__).read().splitlines()
                   if ln.startswith(('import ', 'from '))]
            assert not any('jax' in ln for ln in top), mod.__name__

    def test_new_modules_tracelint_clean(self):
        from paddle_tpu.analysis import lint_paths

        obs_dir = os.path.join(REPO, 'paddle_tpu', 'observability')
        for name in ('timeseries.py', 'watchdog.py', 'httpd.py'):
            vs = lint_paths([os.path.join(obs_dir, name)], root=REPO)
            assert vs == [], (
                f'{name} must stay tracelint-clean:\n'
                + '\n'.join(v.render() for v in vs))
