"""A statistic over the `args` of the program's own spans, counted where
the work happens. The profiler's trace keeps a host event's name, start
and duration; the numbers a span carries (`live`, `real_tokens`,
`wait_ms`) are in the program's ring, whose `traced()` gives the events
recorded while the profiler's session was on. Over the events named in
`spans` that carry `value`: with `over`, the sum of `value` as a share of
the sum of `over`, in per cent; with `percentile`, that percentile of
`value`. Nothing where the trace holds no such span, or the program has
no such ring."""
import numpy as np


def read(ctx, spans, value, over=None, percentile=None):
    if not any(n in spans for n, _, _ in ctx['trace'].host):
        return None
    try:
        from paddle_tpu.observability.tracing import TRACER

        events = TRACER.traced()
    except (ImportError, AttributeError):
        return None
    rows = [e['args'] for e in events if e['name'] in spans
            and e.get('args', {}).get(value) is not None]
    if not rows:
        return None
    if over is None:
        return float(np.percentile([a[value] for a in rows], percentile))
    whole = sum(a[over] for a in rows)
    return 100.0 * sum(a[value] for a in rows) / whole if whole else None
