"""The readers of the program's own spans, through the files that name
them, on a hand-made trace plus ring events: durations come from the
profiler's trace, counts from the `args` of the same spans in the
program's ring (`TRACER.traced()`)."""
import pytest

from benchmark.harness import common
from benchmark.harness import trace_reduce as tr

from paddle_tpu.observability import tracing

# two scheduler steps and one train step, in seconds: the first step
# fuses an admission and runs a second bucket's prefill beside it
HOST = [('bench.step', 0.0, 0.51), ('serve.step', 0.0, 0.5),
        ('serve.admit', 0.001, 0.002), ('serve.admission', 0.002, 1e-6),
        ('serve.prefill', 0.01, 0.1), ('serve.dispatch', 0.12, 0.004),
        ('serve.host_read', 0.125, 0.37), ('serve.commit', 0.495, 0.005),
        ('serve.step', 0.6, 0.25), ('serve.dispatch', 0.602, 0.003),
        ('serve.host_read', 0.606, 0.24),
        ('serve.host_read', 0.9, 0.05),          # inside no step: not cut
        ('train.step', 1.0, 0.004), ('train.step', 1.2, 0.006)]


def ring(traced):
    dispatch = dict(kind='step', live=3, slots=4, bucket=16, rows=2,
                    real_tokens=20, padded_tokens=64)
    window = dict(kind='window', live=1, slots=4, bucket=0, rows=0,
                  real_tokens=0, padded_tokens=0)
    prefill = dict(bucket=32, rows=1, real_tokens=28, padded_tokens=128)
    events = [('serve.admission', {'rid': r, 'wait_ms': w})
              for r, w in enumerate((10.0, 30.0, 20.0))]
    events += [('serve.prefill', prefill), ('serve.dispatch', dispatch),
               ('serve.dispatch', window), ('serve.commit', {'committed': 9})]
    return [{'name': n, 'args': a, 'ts': float(i), **traced}
            for i, (n, a) in enumerate(events)]


EXPECT = {
    # ((0.5 - 0.37) + (0.25 - 0.24)) / 2 steps
    'sched_host_ms.latency': 70.0, 'sched_host_ms.batch': 70.0,
    'queue_wait_p95_ms.latency': 29.0,           # p95 of 10, 20, 30
    'slot_occupancy_pct.latency': 50.0,          # (3 + 1) / (4 + 4)
    'slot_occupancy_pct.batch': 50.0,
    'admit_fill_pct.latency': 25.0,              # (20 + 28) / (64 + 128)
    'admit_fill_pct.batch': 25.0,
    'train_dispatch_ms.train': 5.0}


@pytest.fixture
def tracer(monkeypatch):
    t = tracing.HostTracer()
    monkeypatch.setattr(tracing, 'TRACER', t)
    return t


def read(name, host):
    env = type('Env', (), {'per_layer': [name]})
    trace = tr.Trace(ops={}, programs={}, host=host)
    return common.read_metrics(env, {'trace': trace})


@pytest.mark.parametrize('name', sorted(EXPECT))
def test_span_reader(name, tracer):
    tracer._events.extend(ring({'traced': True}))
    got = read(name, HOST)
    assert got[name]['value'] == pytest.approx(EXPECT[name])
    assert got[name]['unit'] == common.load('metrics', name)['unit']
    # a trace without the program's spans (the parent's, the CPU
    # rehearsal's recorded one): nothing to read, whatever the ring holds
    assert read(name, [h for h in HOST if h[0].startswith('bench.')]) == {}


@pytest.mark.parametrize('name', sorted(n for n in EXPECT
                                        if 'sched' not in n
                                        and 'train' not in n))
def test_ring_reader_counts_traced_events_only(name, tracer, monkeypatch):
    tracer._events.extend(ring({}))              # recorded outside a session
    assert read(name, HOST) == {}
    tracer._events.extend(ring({'traced': True}))
    assert read(name, HOST)[name]['value'] == pytest.approx(EXPECT[name])
    # a program whose tracer has no `traced()`, as the parent's
    monkeypatch.setattr(tracing, 'TRACER', object())
    assert read(name, HOST) == {}
