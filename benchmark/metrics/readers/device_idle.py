"""One minus the union of the device's busy intervals over the traced
window, averaged over the chips used."""
from benchmark.harness import trace_reduce


def read(ctx):
    return 100.0 * trace_reduce.idle_share(ctx['trace'], ctx['window_s'])
