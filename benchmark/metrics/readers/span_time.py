"""Mean duration of the program's own spans named `span`, from the
profiler's trace (`paddle_tpu.observability.tracing.span` opens a
TraceAnnotation under each span's name), less the spans named `minus` that
lie inside one: with `serve.step` less `serve.host_read`, the host's own
work on the critical path of a synchronous step."""


def read(ctx, span, minus=None):
    host = ctx['trace'].host
    spans = [(s, s + d) for n, s, d in host if n == span]
    if not spans:
        return None
    inner = sum(d for n, s, d in host if n == minus
                and any(a <= s and s + d <= b for a, b in spans))
    return 1e3 * (sum(b - a for a, b in spans) - inner) / len(spans)
