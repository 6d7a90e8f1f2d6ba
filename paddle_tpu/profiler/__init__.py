"""Profiler (ref: python/paddle/profiler/profiler.py).

Wraps `jax.profiler`: traces go to TensorBoard-compatible files; the
same RecordEvent/Profiler surface as the reference, with XLA's own
per-op timeline replacing Paddle's host/device event collation.
"""
from __future__ import annotations

import contextlib
import os
import time

import jax

__all__ = ['Profiler', 'RecordEvent', 'ProfilerTarget', 'profile',
           'start_profiler', 'stop_profiler', 'StepTimer']


class ProfilerTarget:
    CPU = 'cpu'
    GPU = 'gpu'
    TPU = 'tpu'
    CUSTOM_DEVICE = 'custom'


class RecordEvent:
    """ref: paddle.profiler.RecordEvent — named trace annotation.

    Also usable as a decorator. ONE API, BOTH timelines:
    `observability.tracing.span` opens the jax.profiler annotation (the
    XLA/TensorBoard timeline) AND records the host span (the Perfetto
    host_trace.json) under the same name — the reference's host/device
    event collation, rebuilt on the two recorders this stack actually
    has.
    """

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        from ..observability import tracing as _tracing

        self._span = _tracing.span(self.name, cat='record_event').begin()

    def end(self):
        if self._span is not None:
            self._span.end()
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        from ..observability import tracing as _tracing

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with _tracing.annotate(self.name, cat='record_event'):
                return fn(*a, **kw)

        return wrapped


class Profiler:
    """ref: paddle.profiler.Profiler.

    with Profiler(on_trace_ready=...) as p:
        for batch in loader:
            train_step(...)
            p.step()
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 log_dir='./profiler_log', timer_only=False, **kw):
        self.log_dir = log_dir
        self.timer_only = timer_only
        self.on_trace_ready = on_trace_ready
        self._running = False
        self._step_times = []
        self._t_last = None

    def start(self):
        if not self.timer_only:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
        self._running = True
        self._t_last = time.perf_counter()
        return self

    def stop(self):
        if self._running and not self.timer_only:
            jax.profiler.stop_trace()
            # drop the host-side span trace next to jax's device trace:
            # one log_dir holds both halves of the timeline
            # any failure here (unwritable log_dir, import oddity) must
            # cost only the host-trace artifact, never break stop():
            # the device trace is already closed and on_trace_ready
            # still has to fire
            try:
                from ..observability import tracing as _tracing

                _tracing.export(os.path.join(self.log_dir,
                                             'host_trace.json'))
            except Exception:  # noqa: BLE001 - artifact is best-effort
                pass
        self._running = False
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now

    def step_info(self, unit=None):
        if not self._step_times:
            return 'no steps recorded'
        import numpy as np

        t = np.asarray(self._step_times)
        return (f'steps={len(t)} avg={t.mean() * 1e3:.2f}ms '
                f'p50={np.percentile(t, 50) * 1e3:.2f}ms '
                f'p99={np.percentile(t, 99) * 1e3:.2f}ms')

    def summary(self, sorted_by=None, views=None, **kw):
        """Formatted step-timing report (ref profiler.py summary tables;
        per-op device timing lives in the exported trace — use
        `profiler.op_summary(fn, *args)` for the compile-time view)."""
        if not self._step_times:
            print('no steps recorded')
            return
        import numpy as np

        t = np.asarray(self._step_times) * 1e3
        rows = [
            ('steps', f'{len(t)}'),
            ('avg', f'{t.mean():.2f} ms'),
            ('p50', f'{np.percentile(t, 50):.2f} ms'),
            ('p90', f'{np.percentile(t, 90):.2f} ms'),
            ('p99', f'{np.percentile(t, 99):.2f} ms'),
            ('min', f'{t.min():.2f} ms'),
            ('max', f'{t.max():.2f} ms'),
            ('total', f'{t.sum():.2f} ms'),
        ]
        w = max(len(k) for k, _ in rows)
        sep = '-' * (w + 14)
        print(sep)
        print(f'{"step timing":<{w + 2}}')
        print(sep)
        for k, v in rows:
            print(f'{k:<{w + 2}}{v}')
        print(sep)
        if not self.timer_only:
            print(f'device trace: {self.log_dir} (TensorBoard / Perfetto)')

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False


@contextlib.contextmanager
def profile(log_dir='./profiler_log'):
    p = Profiler(log_dir=log_dir).start()
    try:
        yield p
    finally:
        p.stop()


_global_profiler = None


def start_profiler(log_dir='./profiler_log', **kw):
    global _global_profiler
    _global_profiler = Profiler(log_dir=log_dir, **kw).start()


def stop_profiler():
    global _global_profiler
    if _global_profiler is not None:
        _global_profiler.stop()
        _global_profiler = None


class StepTimer:
    """Lightweight step timing (timer_only Profiler convenience)."""

    def __init__(self):
        self._p = Profiler(timer_only=True).start()

    def step(self):
        self._p.step()

    def info(self):
        return self._p.step_info()


class ProfilerState:
    """ref: paddle.profiler.ProfilerState."""

    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys:
    """ref: paddle.profiler.SortedKeys (summary ordering)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """ref: paddle.profiler.SummaryView."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """ref: paddle.profiler.make_scheduler — step -> ProfilerState
    callable driving window-based capture."""
    cycle = closed + ready + record

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    """ref: paddle.profiler.export_chrome_tracing — on_trace_ready
    callback. jax.profiler already writes TensorBoard/Perfetto traces
    into the profiler's log dir; this returns a callback that records
    where."""

    def handler(prof):
        prof.exported_to = dir_name
        return dir_name

    return handler


def export_protobuf(dir_name, worker_name=None):
    """ref: paddle.profiler.export_protobuf — same artifact family
    (jax traces are already protobuf-based under the hood)."""
    return export_chrome_tracing(dir_name, worker_name)


def op_summary(fn, *args, print_table=True, top=20, **kwargs):
    """Per-op report for a jittable function (the reference's operator/
    kernel summary views, rebuilt on XLA's compile-time analyses).

    Compiles `fn(*args)` and reports: opcode histogram of the optimized
    HLO (what XLA actually runs, post-fusion), total FLOPs and bytes
    from `cost_analysis`, and the memory footprint split from
    `memory_analysis`. Returns the stats dict (also printed as a table
    unless print_table=False).
    """
    import collections
    import re

    import jax as _jax

    # tracelint: disable=TL001 - one-shot profiling compile, not served
    compiled = _jax.jit(fn).lower(*args, **kwargs).compile()
    hist = collections.Counter()
    for mod in compiled.as_text().splitlines():
        m = re.search(r'=\s+[\w\[\],{}() ]*?\s*([a-z][\w-]*)\(', mod)
        if m and not mod.lstrip().startswith(('ROOT', '//')):
            hist[m.group(1)] += 1
        elif mod.lstrip().startswith('ROOT'):
            m = re.search(r'=\s+\S+\s+([a-z][\w-]*)\(', mod)
            if m:
                hist[m.group(1)] += 1
    # cost/memory quirks (list-vs-dict, raising backends) are handled
    # ONCE in observability.costs — the same normalized reading the AOT
    # manifest cost stamps and the live MFU gauges use
    from ..observability.costs import analyze

    cost = analyze(compiled)
    mem_stats = cost['memory']
    stats = {
        'opcode_histogram': dict(hist.most_common()),
        'flops': cost['flops'],
        'bytes_accessed': cost['bytes_accessed'],
        'memory': mem_stats,
    }
    if print_table:
        print('-' * 44)
        print(f'{"opcode":<28}{"count":>8}')
        print('-' * 44)
        for op, n in hist.most_common(top):
            print(f'{op:<28}{n:>8}')
        print('-' * 44)
        if stats['flops']:
            print(f'{"total flops":<28}{stats["flops"]:>14.3e}')
        if stats['bytes_accessed']:
            print(f'{"bytes accessed":<28}{stats["bytes_accessed"]:>14.3e}')
        for k, v in mem_stats.items():
            print(f'{k:<28}{v:>14,}')
        print('-' * 44)
    return stats


def load_profiler_result(filename):
    """ref: paddle.profiler.load_profiler_result — load an exported
    chrome trace JSON for programmatic inspection."""
    import gzip
    import json

    opener = gzip.open if str(filename).endswith('.gz') else open
    with opener(filename, 'rt') as f:
        return json.load(f)


__all__ += ['ProfilerState', 'SortedKeys', 'SummaryView', 'make_scheduler', 'op_summary',
            'export_chrome_tracing', 'export_protobuf',
            'load_profiler_result']
