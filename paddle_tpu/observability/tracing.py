"""HostTracer — Chrome/Perfetto `trace_event` spans for host-side
scheduler decisions.

`jax.profiler` captures the DEVICE timeline (XLA ops, DMA, compiles as
XLA sees them) but the host half of serving — admission decisions,
preemptions, window dispatch cadence, CompileCache misses — is
invisible there. This tracer records those as standard Chrome
trace_event JSON (`ph: "X"` complete spans and `ph: "i"` instants), so
`host_trace.json` loads in Perfetto / chrome://tracing directly and can
sit in the same UI session as a jax.profiler device trace
(docs/observability.md shows the overlay recipe).

Design constraints, same discipline as the metrics registry:

  - host-only: recording is an append of one small dict; NOTHING here
    touches the device or forces a sync;
  - bounded: a ring of `max_events` (default 100k) so a server that
    runs for weeks cannot leak the host heap — overflow drops the
    OLDEST events and counts `dropped`;
  - switchable: every record checks `metrics.enabled()`, so the bench
    overhead gate's telemetry-off run skips this too.

One primitive, two sinks: `span(name)` opens a
`jax.profiler.TraceAnnotation` under the same name (a no-op while no
profiler session runs; the session is its switch) AND, with telemetry
on, records the ring event here. So the engines' spans are in the
profiler's trace, on the profiler's clock, beside the device's
operations, and the ring holds their `args`. A ring event recorded while
a session was on carries `traced: true`; `HostTracer.traced()` cuts the
ring to the newest session without a clock match.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

from . import metrics as _metrics

__all__ = ['HostTracer', 'TRACER', 'span', 'instant', 'compile_event',
           'annotate', 'export', 'save', 'to_chrome_trace']

# one process-wide epoch so every event's ts is comparable; perf_counter
# is monotonic (wall-clock jumps cannot reorder spans)
_EPOCH = time.perf_counter()


def _now_us():
    return (time.perf_counter() - _EPOCH) * 1e6


# jax.profiler.TraceAnnotation, looked up at the first span: None = not
# looked up yet, False = this installation has none (ring only)
_ANNOTATION = None


def _open_annotation(name, args):
    """(entered TraceAnnotation or None, whether a profiler session is
    on). Never raises: annotation must not be able to break the
    annotated code."""
    global _ANNOTATION
    try:
        if _ANNOTATION is None:
            try:
                import jax

                _ANNOTATION = jax.profiler.TraceAnnotation
            except Exception:  # noqa: BLE001 - degrade to ring-only
                _ANNOTATION = False
        if not _ANNOTATION:
            return None, False
        ann = _ANNOTATION(name, **args)
        ann.__enter__()
        return ann, _ANNOTATION.is_enabled()
    except Exception:  # noqa: BLE001 - annotation is best-effort
        return None, False


class _Span:
    """Open span handle: context manager OR explicit begin()/end()
    (profiler.RecordEvent needs the latter). The telemetry switch gates
    the ring event only; the annotation follows the profiler session."""

    __slots__ = ('_tracer', 'name', 'cat', 'args', '_t0', '_ann',
                 '_traced')

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = self._ann = None
        self._traced = False

    def begin(self):
        self._ann, self._traced = _open_annotation(self.name, self.args)
        if _metrics.enabled():
            self._t0 = self._tracer._observe(self._traced)
        return self

    def set(self, **args):
        """Args known only once the work is done (`committed`,
        `admitted`): onto the ring event and the annotation's
        metadata."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def end(self, **args):
        if args:
            self.set(**args)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0 is not None:
            self._tracer._emit(self.name, self.cat, self._t0,
                               _now_us() - self._t0, self.args,
                               traced=self._traced)
            self._t0 = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False


class HostTracer:
    """Bounded host-side trace_event recorder."""

    def __init__(self, max_events=100_000):
        self.max_events = int(max_events)
        self._events: collections.deque = collections.deque(
            maxlen=self.max_events)
        self.dropped = 0
        self._pid = os.getpid()
        self._session_on = False
        self._session_ts = 0.0

    # -- recording ---------------------------------------------------------

    def _observe(self, session_on):
        """Now, in us; called as each span or instant begins with whether
        a profiler session is on, so the newest session's first instant
        is known to `traced()`."""
        ts = _now_us()
        if session_on and not self._session_on:
            self._session_ts = ts
        self._session_on = session_on
        return ts

    def _emit(self, name, cat, ts, dur, args, ph='X', traced=False):
        ev = {'name': name, 'cat': cat, 'ph': ph, 'ts': ts,
              'pid': self._pid, 'tid': threading.get_ident() % 2**31}
        if ph == 'X':
            ev['dur'] = dur
        elif ph == 'i':
            ev['s'] = 'p'
        if args:
            ev['args'] = args
        if traced:
            ev['traced'] = True
        if len(self._events) == self.max_events:
            # silent event loss is itself an observability bug: surface
            # ring overflow as a registry counter so dashboards see a
            # truncated trace for what it is
            self.dropped += 1
            _metrics.inc('trace.dropped_events')
        self._events.append(ev)

    def span(self, name, cat='host', **args):
        """Context manager (or begin()/end() handle): a TraceAnnotation
        in the profiler's trace and one complete ring event on exit."""
        return _Span(self, name, cat, args)

    def instant(self, name, cat='host', **args):
        """A point in time: an annotation opened and closed at once, and
        a `ph: "i"` ring event."""
        ann, traced = _open_annotation(name, args)
        if ann is not None:
            ann.__exit__(None, None, None)
        if _metrics.enabled():
            self._emit(name, cat, self._observe(traced), 0.0, args, ph='i',
                       traced=traced)

    def compile_event(self, name, key=None, dur_s=None, **args):
        """One compile/retrace event on the `compile` track. With a
        wall duration it renders as a span covering the compiling
        dispatch; without one (a bare retrace count tick) it is an
        instant."""
        if not _metrics.enabled():
            return
        if key is not None:
            args['key'] = str(key)
        if dur_s is None:
            self._emit(name, 'compile', _now_us(), 0.0, args, ph='i')
        else:
            dur_us = float(dur_s) * 1e6
            self._emit(name, 'compile', _now_us() - dur_us, dur_us, args)

    # -- reading / export --------------------------------------------------

    def events(self):
        return list(self._events)

    def traced(self):
        """The events of the newest profiler session: the ring cut to
        exactly the traced window. A session is known by a span or
        instant that began while it was on, so two sessions with nothing
        recorded between them read as one."""
        return [e for e in self.events()
                if e.get('traced') and e['ts'] >= self._session_ts]

    def __len__(self):
        return len(self._events)

    def clear(self):
        self._events.clear()
        self.dropped = 0
        self._session_on = False
        self._session_ts = 0.0

    def to_chrome_trace(self):
        """The `trace_event` ARRAY form (what Perfetto and
        chrome://tracing both accept)."""
        return self.events()

    def to_json(self, **kw):
        # default=str: span args are caller-supplied (annotate(**args))
        # and a non-serializable arg must degrade to its repr, never
        # make the export raise
        kw.setdefault('default', str)
        return json.dumps(self.to_chrome_trace(), **kw)

    def export(self, path):
        """Write host_trace.json (trace_event array) and return the
        path."""
        with open(path, 'w') as f:
            json.dump(self.to_chrome_trace(), f, default=str)
        return path

    def save(self, path):
        """`export` alias — the artifact-writing verb the registry
        (`to_json`) and journal (`save`) families use."""
        return self.export(path)


TRACER = HostTracer()


# -- module-level conveniences over the global tracer ----------------------

def span(name, cat='host', **args):
    return TRACER.span(name, cat, **args)


def instant(name, cat='host', **args):
    TRACER.instant(name, cat, **args)


def compile_event(name, key=None, dur_s=None, **args):
    TRACER.compile_event(name, key=key, dur_s=dur_s, **args)


def export(path):
    return TRACER.export(path)


def save(path):
    return TRACER.export(path)


def to_chrome_trace():
    return TRACER.to_chrome_trace()


# the name profiler.RecordEvent's decorator form and older callers use
# for the same primitive
annotate = span
