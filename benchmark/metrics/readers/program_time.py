"""Mean device time of one execution of the programs whose name matches,
from the trace's per-program events."""
from benchmark.harness import trace_reduce


def read(ctx, pattern):
    times = trace_reduce.program_times(ctx['trace'], pattern)
    return 1e3 * sum(times) / len(times) if times else None
