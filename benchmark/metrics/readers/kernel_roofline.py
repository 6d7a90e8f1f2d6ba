"""A kernel's share of its roofline: the least time the chip could take
for the work the traffic needed (the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s) over the summed device time of the kernel's
events. The events are found by program and instruction: the compiled
text's stack-frame tables say which file under ops/pallas/ issued each
Mosaic call."""
from benchmark.harness import trace_reduce


def read(ctx, kernel, program, needed):
    names = {n for module, kernels in ctx['kernels'].items()
             for n in kernels.get(kernel, ())}
    seconds, events = trace_reduce.op_seconds(ctx['trace'], names, program)
    flops, nbytes = getattr(ctx['flops'], needed)(ctx)
    if not events or not flops:
        return None
    least = max(flops / ctx['peak'].flops_bf16,
                nbytes / ctx['peak'].hbm_bytes_s) / ctx['chips']
    return 100.0 * least / seconds
