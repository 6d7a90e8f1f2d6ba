"""paddle_tpu.observability — unified runtime telemetry.

The system-wide view the private counters (`trace_counts()`,
`BlockAllocator.stats()`, windowed metric sync) never gave: one
process-global metrics registry + one host-span tracer, threaded
through the serving engine, the train engine, the dataloader, and the
compile caches. Rebuilds the reference's Profiler/event-collation
subsystem jax-natively: `jax.profiler` keeps the device timeline, this
package owns the host one, and `tracing.span` (which
`profiler.RecordEvent` routes through) writes each host span into both.

Contracts (tested in tests/test_observability.py, gated in bench.py):
  - zero device syncs: every record happens at an EXISTING host point
    (the per-window commit, the train sync, the prefetch loop) on data
    the host already has;
  - tracelint-clean: no jit, no donation, no host syncs to police;
  - bounded: fixed-bucket histograms, ring-buffered tracer;
  - cheap: telemetry-on serving stays within 3% of telemetry-off
    (`gate_observability_overhead`).

The forensic + cost layer rides on top: `journal` (the flight
recorder — bounded event journal with complete per-request trails),
`costs` (one normalized reading of XLA's compile-time cost model,
feeding the AOT manifest and the live MFU/roofline gauges), and
`postmortem` (crash bundles composing metrics + trace + journal +
engine snapshot).

The LIVE operability layer answers "is this engine healthy right
now": `timeseries` (fixed-interval windowed rings over the registry —
rates, deltas, rolling percentiles — committed at the existing sync
points), `watchdog` (declarative SLO rules with hysteresis and a
machine-readable verdict, breaches journaled), and `httpd` (the
opt-in stdlib ops endpoint: /metrics, /healthz, /statusz, /slo).

See docs/observability.md for the metric catalog and span taxonomy.
"""
from __future__ import annotations

from . import (  # noqa: F401
    costs, httpd, journal, metrics, postmortem, timeseries, tracing,
    watchdog,
)
from .httpd import OpsServer, start_ops_server  # noqa: F401
from .journal import (  # noqa: F401
    JOURNAL, Journal, journal_enabled, set_journal_enabled,
    trail, trail_complete,
)
from .metrics import (  # noqa: F401
    REGISTRY, Counter, Gauge, Histogram, MetricsRegistry, enabled,
    inc, observe, set_enabled, set_gauge,
)
from .postmortem import dump_bundle, load_bundle, validate_bundle  # noqa: F401
from .timeseries import TIMESERIES, WindowedTimeseries  # noqa: F401
from .tracing import (  # noqa: F401
    DEVICE_SCOPES, TRACER, HostTracer, compile_event, instant, span,
)
from .watchdog import SLORule, Watchdog, default_serving_rules  # noqa: F401

__all__ = [
    'metrics', 'tracing', 'journal', 'costs', 'postmortem',
    'timeseries', 'watchdog', 'httpd',
    'REGISTRY', 'Counter', 'Gauge', 'Histogram', 'MetricsRegistry',
    'enabled', 'set_enabled', 'inc', 'set_gauge', 'observe',
    'TRACER', 'HostTracer', 'DEVICE_SCOPES', 'span', 'instant',
    'compile_event',
    'JOURNAL', 'Journal', 'journal_enabled', 'set_journal_enabled',
    'trail', 'trail_complete',
    'dump_bundle', 'validate_bundle', 'load_bundle',
    'TIMESERIES', 'WindowedTimeseries',
    'SLORule', 'Watchdog', 'default_serving_rules',
    'OpsServer', 'start_ops_server',
]
