"""Device time of the instructions whose name matches `ops`, in the programs
whose name matches `program`. The profiler names a device event by its
instruction, and XLA names what it makes of a `lax.ragged_dot`
`ragged-dot-*`, whatever scope traced it: the grouped products of an expert
layer are found by that name. With `in_loop`, only the events that began
inside a `while` event of such a program (a serve program's decode window is
its one scan; an admission's prefill lies outside it). Without `needed`:
their share of those programs' device time, in per cent. With `needed` (a
count of the family's, `(flops, bytes)`): the least time the chip could take
for that work over their device time, a share of their roofline. Nothing
where no such instruction ran."""
import bisect
import re

from benchmark.harness import trace_reduce


def seconds_of(trace, ops, program, in_loop):
    """(device seconds, events) of the matching instructions, averaged
    over the devices."""
    named, inside = re.compile(ops), re.compile(program)
    total, events = 0.0, 0
    for rows in trace.ops.values():
        loops = trace_reduce.union(
            (start, start + dur) for _, opcode, start, dur, prog in rows
            if opcode == 'while' and inside.search(prog))
        begins = [s for s, _ in loops]
        for name, _, start, dur, prog in rows:
            if not (named.search(name) and inside.search(prog)):
                continue
            if in_loop:
                i = bisect.bisect_right(begins, start) - 1
                if i < 0 or start > loops[i][1]:
                    continue
            total, events = total + dur, events + 1
    return total / max(1, len(trace.ops)), events


def read(ctx, ops, program, in_loop=False, needed=None):
    seconds, events = seconds_of(ctx['trace'], ops, program, in_loop)
    if not events:
        return None
    if needed is None:
        whole = sum(trace_reduce.program_times(ctx['trace'], program))
        whole /= max(1, len(ctx['trace'].programs))
        return 100.0 * seconds / whole if whole else None
    flops, nbytes = getattr(ctx['flops'], needed)(ctx)
    if not flops:
        return None
    least = max(flops / ctx['peak'].flops_bf16,
                nbytes / ctx['peak'].hbm_bytes_s) / ctx['chips']
    return 100.0 * least / seconds
