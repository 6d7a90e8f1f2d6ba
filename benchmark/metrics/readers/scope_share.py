"""Device time of the instructions a model part issued, by the program's own
scopes (`harness/scopes.py`: each instruction of a compiled serve program
mapped to the `jax.named_scope` path it was traced under, each execution to
the compiled variant it ran), in the programs
whose name matches `program`. The instructions whose scope path matches
`scope` (a regex, searched), of `part`: `loop`, those that began inside a
`while` event of such a program (a serve program's decode window is its one
scan), `outside` the others, `all` both. Loops and calls are left out,
their bodies are counted. `value`: `pct_of_programs`, their share of those
programs' device time; `pct_of_part`, of the part's instruction time;
`ms_per_execution`, their device ms over the executions of those programs.
Nothing where no such instruction ran: a program with no scopes reads
nothing."""
import bisect
import re

from benchmark.harness import scopes, trace_reduce

VALUES = ('pct_of_programs', 'pct_of_part', 'ms_per_execution')


def events(trace, program, part):
    """(device, row, program, instruction, opcode, seconds) of each
    instruction event of `part` in the programs whose name matches
    `program`; `row` indexes the device's events."""
    inside = re.compile(program)
    for device, rows in trace.ops.items():
        loops = trace_reduce.union(
            (start, start + dur) for _, opcode, start, dur, prog in rows
            if opcode == 'while' and inside.search(prog))
        begins = [s for s, _ in loops]
        for k, (name, opcode, start, dur, prog) in enumerate(rows):
            if opcode in trace_reduce.CONTAINERS or not inside.search(prog):
                continue
            if part != 'all':
                i = bisect.bisect_right(begins, start) - 1
                in_loop = i >= 0 and start <= loops[i][1]
                if in_loop != (part == 'loop'):
                    continue
            yield device, k, prog, name, opcode, dur


def seconds_of(trace, paths, scope, program, part):
    """(device seconds of the matching instructions, of the part's
    instructions, events matched), averaged over the devices. `paths` is
    `scopes.row_paths`'."""
    wanted = re.compile(scope)
    hit = whole = 0.0
    matched = 0
    for device, k, _, _, _, dur in events(trace, program, part):
        whole += dur
        if wanted.search(paths[device][k]):
            hit, matched = hit + dur, matched + 1
    n = max(1, len(trace.ops))
    return hit / n, whole / n, matched


def read(ctx, scope, program, part='all', value='pct_of_programs'):
    if part not in ('loop', 'outside', 'all') or value not in VALUES:
        raise ValueError(f'scope_share: part {part!r}, value {value!r}')
    trace = ctx['trace']
    hit, whole, matched = seconds_of(trace, scopes.of_context(ctx), scope,
                                     program, part)
    if not matched:
        return None
    if value == 'pct_of_part':
        return 100.0 * hit / whole
    runs = trace_reduce.program_times(trace, program)
    n = max(1, len(trace.programs))
    if value == 'ms_per_execution':
        return 1e3 * hit / (len(runs) / n)
    return 100.0 * hit / (sum(runs) / n)
