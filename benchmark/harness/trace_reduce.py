"""From a profiler trace to numbers: busy union, idle share, sums per
program and per instruction, the longest idle gaps and what the host was
doing in them.

The reduction works on a plain `Trace` (lists of named intervals in
seconds), so it is checked on a small recorded one
(`benchmark/tests/recorded_trace.json`); `load_xplane` is the one place
that knows the profiler's file.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re


@dataclasses.dataclass
class Trace:
    """Named intervals in seconds."""
    ops: dict           # {device: [(name, opcode, start, dur, program)]}
    programs: dict      # {device: [(name, start, dur)]}
    host: list          # [(name, start, dur)] of the harness's own spans

    def to_json(self):
        return {'ops': self.ops, 'programs': self.programs,
                'host': self.host}

    @classmethod
    def from_json(cls, obj):
        def tup(rows):
            return [tuple(r) for r in rows]
        return cls({d: tup(v) for d, v in obj['ops'].items()},
                   {d: tup(v) for d, v in obj['programs'].items()},
                   tup(obj['host']))


HOST_SPANS = re.compile(r'^(bench\.|serve\.|train\.)')
CONTAINERS = ('while', 'conditional', 'call')   # their children are events too
_OPCODE = re.compile(r' ([a-z][a-z0-9\-]*)\(')


def instruction(text):
    """(name, opcode) of a device event: the profiler names an event by
    the instruction's whole text, `%name = shape opcode(operands), ...`."""
    name = text.split(' = ', 1)[0].lstrip('%')
    m = _OPCODE.search(text)
    return name, (m.group(1) if m else '')


def load_xplane(trace_dir):
    """The newest `.xplane.pb` under `trace_dir` as a `Trace`. Device
    planes are `/device:TPU:<n>`; their `XLA Ops` line holds one event per
    executed instruction, `XLA Modules` one per program execution, and an
    instruction belongs to the program that was running when it began."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    data = ProfileData.from_file(paths[-1])
    ops, programs, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith('/device:TPU:'):
            lines = {line.name: line for line in plane.lines}
            if 'XLA Ops' not in lines:
                continue
            progs = sorted(
                (e.start_ns * 1e-9, e.duration_ns * 1e-9,
                 e.name.split('(')[0])
                for e in lines['XLA Modules'].events)
            programs[plane.name] = [(n, s, d) for s, d, n in progs]
            starts = [s for s, _, _ in progs]
            rows = []
            for e in lines['XLA Ops'].events:
                start = e.start_ns * 1e-9
                i = bisect.bisect_right(starts, start) - 1
                inside = i >= 0 and start <= progs[i][0] + progs[i][1]
                rows.append((*instruction(e.name), start,
                             e.duration_ns * 1e-9,
                             progs[i][2] if inside else ''))
            ops[plane.name] = rows
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events if HOST_SPANS.match(e.name)]
    return Trace(ops, programs, sorted(host, key=lambda h: h[1]))


def union(intervals):
    """[(start, end)] merged, in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(trace):
    """Seconds in which an instruction ran, averaged over the devices."""
    per_device = [sum(e - s for s, e in union(
        (st, st + d) for _, _, st, d, _ in rows)) for rows in trace.ops.values()]
    if not per_device:
        raise ValueError('the trace holds no device operation')
    return sum(per_device) / len(per_device)


def idle_share(trace, window_s):
    return 1.0 - busy_seconds(trace) / window_s


def program_times(trace, pattern):
    """Durations of the executions of programs whose name matches."""
    rx = re.compile(pattern)
    return [d for rows in trace.programs.values()
            for name, _, d in rows if rx.search(name)]


def op_seconds(trace, names, program):
    """(summed device seconds, events) of the instructions `names` inside
    programs whose name matches `program`, averaged over the devices."""
    prx = re.compile(program)
    total, n = 0.0, 0
    for rows in trace.ops.values():
        for name, _, _, d, prog in rows:
            if name in names and prx.search(prog):
                total, n = total + d, n + 1
    return total / max(1, len(trace.ops)), n


def top_ops(trace, k=10):
    """The k instructions that took most device time, summed by program
    and name; loops and calls are left out, their bodies are counted."""
    sums = collections.Counter()
    for rows in trace.ops.values():
        for name, opcode, _, d, prog in rows:
            if opcode not in CONTAINERS:
                sums[f'{prog}:{name}:{opcode}'] += d / len(trace.ops)
    return [[n, s] for n, s in sums.most_common(k)]


def idle_gaps(trace, k=10):
    """Idle seconds of the first device, summed by the innermost harness
    span open at each gap's middle; the k largest sums."""
    if not trace.ops:
        return []
    rows = next(iter(trace.ops.values()))
    busy = union((st, st + d) for _, _, st, d, _ in rows)
    sums = collections.Counter()
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        mid, name = (e0 + s1) / 2, 'no span open'
        for span, st, d in trace.host:
            if st > mid:
                break
            if st + d >= mid:
                name = span                     # later start = inner span
        sums[name] += s1 - e0
    return [[n, s] for n, s in sums.most_common(k)]


def breakdown(trace):
    return {'device_ops': top_ops(trace), 'idle_gaps': idle_gaps(trace)}


def dump(trace, path):
    with open(path, 'w') as f:
        json.dump(trace.to_json(), f)
