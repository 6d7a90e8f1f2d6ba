"""Which model part each instruction of a compiled serve program came from.

The program opens a `jax.named_scope` at the boundary of each model part
(`paddle_tpu.observability.tracing.DEVICE_SCOPES`), and XLA keeps the
scopes open where an operation was traced in its instruction's `op_name`
metadata: `jit(_serve_window)/while/body/closed_call/attn/qkv/dot_general`.
The vocabulary is copied here, so that the yardstick does not move with the
program. An instruction's scope path is the vocabulary's segments of its
`op_name`, in order, joined by `/` (`attn/qkv`); one with none is `other`.
A fusion is what it computes: the scope of the largest (by output bytes)
dot, convolution or custom call in its fused computation that has one, else
of its root, else of the largest of its instructions that has one. XLA's own
instructions (copies, async slices, buffers) carry no path: each goes with
the nearest scoped instruction that consumes it, else that feeds it.
The profiler names a device event by its instruction and its program's
module, not by the program's bucket: an execution is matched to the
compiled variant whose instruction names it ran, and an instruction name
that variants tied in that match give to different scopes is `ambiguous`.
"""
from __future__ import annotations

import bisect
import collections
import gc
import math
import re

VOCABULARY = (
    'embed', 'norm', 'attn/qkv', 'attn/cache', 'attn/core', 'attn/out',
    'mlp', 'expert_share/route', 'expert_share/dispatch',
    'expert_share/matmul', 'expert_share/combine', 'expert_share/shared',
    'head', 'sample')
SEGMENTS = frozenset(s for path in VOCABULARY for s in path.split('/'))
OTHER, AMBIGUOUS = 'other', 'ambiguous'
WORK = ('dot', 'convolution', 'custom-call')   # what a fusion is named by

# a transformation wraps the scope it runs under: `transpose(jvp(head))`
_TRANSFORM = re.compile(r'^(?:(?:jvp|transpose|vmap|remat|checkpoint)\()+')
_HEADER = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$')
_OPCODE = re.compile(r' ([a-z][a-z0-9\-]*)\(')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%([\w.\-]+)')
_OPERAND = re.compile(r'%([\w.\-]+)')
_ARRAY = re.compile(r'\b([a-z]+\d*)\[([\d,]*)\]')
_BITS = {'pred': 8, 'bf16': 16, 'f16': 16, 'f32': 32, 'f64': 64, 's4': 4,
         'u4': 4, 's8': 8, 'u8': 8, 's16': 16, 'u16': 16, 's32': 32,
         'u32': 32, 's64': 64, 'u64': 64, 'c64': 64, 'c128': 128}

Instruction = collections.namedtuple(
    'Instruction', 'name opcode out_bytes op_name calls root operands')


def path_of(op_name):
    """The vocabulary's segments of an `op_name`, in order, or `other`.
    An instruction XLA made of several keeps their names, `;` between
    them: the first that has a segment speaks for it."""
    for name in (op_name or '').split(';'):
        found = []
        for segment in name.split('/'):
            segment = _TRANSFORM.sub('', segment).rstrip(')')
            if segment in SEGMENTS:
                found.append(segment)
        if found:
            return '/'.join(found)
    return OTHER


def _out_bytes(shape):
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        bits = _BITS.get(dtype, 8 if dtype.startswith('f8') else 32)
        total += math.prod(int(d) for d in dims.split(',') if d) * bits // 8
    return total


def parse(hlo_text):
    """(module name, {computation: [Instruction]}) of a compiled text."""
    module = re.search(r'HloModule\s+([\w.\-]+)', hlo_text)
    computations, current = {}, None
    for line in hlo_text.splitlines():
        header = _HEADER.match(line)
        if header:
            current = computations.setdefault(header.group(1), [])
            continue
        if line.startswith('}'):
            current = None
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if not m:
            continue
        body = m.group(3)
        op = _OPCODE.search(body)
        shape = body[:op.start()] if op else body
        op_name = _OP_NAME.search(body)
        current.append(Instruction(
            m.group(2), op.group(1) if op else '', _out_bytes(shape),
            op_name.group(1) if op_name else None,
            _CALLS.findall(body) if op and op.group(1) == 'fusion' else [],
            bool(m.group(1)), _OPERAND.findall(body[op.end():]) if op else []))
    return (module.group(1) if module else None), computations


def program_scopes(hlo_text):
    """(module, {instruction: scope path}) over the instructions of every
    computation but the fused ones, among them all the device runs as
    events."""
    module, computations = parse(hlo_text)
    fused = {c for body in computations.values() for i in body
             for c in i.calls}

    def inside(name, seen):
        """A fused computation's instructions, nested fusions' included,
        largest output first."""
        out = []
        for i in computations.get(name, ()):
            out.append(i)
            for c in i.calls:
                if c not in seen:
                    seen.add(c)
                    out += inside(c, seen)
        return sorted(out, key=lambda i: -i.out_bytes)

    def fusion_path(i):
        """The scope of its largest scoped WORK, else of its root, else of
        its largest scoped instruction, else its own."""
        body = inside(i.calls[0], {i.calls[0]})
        roots = [r for r in computations.get(i.calls[0], ()) if r.root]
        for candidate in ([w for w in body if w.opcode in WORK] + roots
                          + body + [i]):
            path = path_of(candidate.op_name)
            if path != OTHER:
                return path
        return OTHER

    scopes = {}
    for name, body in computations.items():
        if name in fused:
            continue
        own = {i.name: fusion_path(i) if i.calls else path_of(i.op_name)
               for i in body}
        users = collections.defaultdict(list)
        feeds = {i.name: [o for o in i.operands if o in own] for i in body}
        for i in body:
            for operand in feeds[i.name]:
                users[operand].append(i.name)
        for i in body:
            if own[i.name] == OTHER and '/' not in (i.op_name or ''):
                own[i.name] = (_nearest(i.name, own, users)
                               or _nearest(i.name, own, feeds) or OTHER)
        scopes.update(own)
    return module, scopes


def _nearest(name, own, edges):
    """XLA's own instructions (copies, async slices, buffers, relayouts of
    a weight) carry no `op_name`, or a bare one (`lt_to`, a parameter's
    name) with no path. Such a one is the nearest scoped instruction that
    consumes it, else the nearest that feeds it: breadth first along
    `edges` (users, or operands) inside its computation; None if none."""
    queue, seen = collections.deque(edges[name]), {name}
    while queue:
        other = queue.popleft()
        if other in seen:
            continue
        seen.add(other)
        if own[other] != OTHER:
            return own[other]
        queue.extend(edges[other])
    return None


def merge(maps):
    """{instruction: path} over maps of compiled variants of one program:
    `ambiguous` where they disagree."""
    out = {}
    for scopes in maps:
        for name, path in scopes.items():
            out[name] = path if out.get(name, path) == path else AMBIGUOUS
    return out


def compiled(engine, geometries):
    """(module, {instruction: path}) of each program `engine` dispatches
    for `geometries`: the same jitted functions on the same arguments as
    it warmed, so jax hands back the executables that ran."""
    for g in geometries:
        for fn, args, kwargs in engine._cost_specs(g):
            text = fn.lower(*args, **kwargs).compile().as_text()
            yield program_scopes(text)


def live():
    """{module: [{instruction: path} of each compiled variant]} over the
    programs of the serving engines this process holds, each at the
    geometries it has noted as warmed or dispatched (the program's compile
    registry)."""
    from paddle_tpu.aot import geometry
    from paddle_tpu.inference.engine import COMPILE_CACHE
    from paddle_tpu.inference.serving import ServingEngine

    noted, out = set(COMPILE_CACHE.keys()), {}
    for engine in [o for o in gc.get_objects()
                   if isinstance(o, ServingEngine)]:
        for module, scopes in compiled(engine, [
                g for g in geometry.for_serving_engine(engine)
                if engine.registry_key(*engine._geometry_cost_tag(g))
                in noted]):
            out.setdefault(module, []).append(scopes)
    return out


def row_paths(trace, programs):
    """{device: [scope path of each of the device's instruction events]}.
    `programs` is {module: [{instruction: path} of each compiled variant]}.
    The profiler names an event by its instruction and its program's
    module, not by the program's bucket, and bucket variants number their
    instructions apart: each execution of a program takes the variant
    whose instruction names it ran most of (each has names of its own),
    and each of its events that variant's path; where variants tie and
    disagree on a name, it is `ambiguous`."""
    out = {}
    for device, rows in trace.ops.items():
        runs = sorted((start, start + dur)
                      for _, start, dur in trace.programs.get(device, ()))
        starts = [start for start, _ in runs]
        members = collections.defaultdict(list)
        for k, (_, _, start, _, prog) in enumerate(rows):
            i = bisect.bisect_right(starts, start) - 1
            members[prog, i if i >= 0 and start <= runs[i][1] else -1].append(k)
        paths = [OTHER] * len(rows)
        for (prog, _), ks in members.items():
            maps = programs.get(prog, [])
            ran = {rows[k][0] for k in ks}
            hits = [len(ran & m.keys()) for m in maps]
            table = merge(m for m, h in zip(maps, hits) if h == max(hits))
            for k in ks:
                paths[k] = table.get(rows[k][0], OTHER)
        out[device] = paths
    return out


def of_context(ctx):
    """{device: [scope path of each event of `ctx['trace']`]} from
    `ctx['scopes']`, {module: [map of each compiled variant]}, made from
    the live engines at the first reader's call (the harness hands its
    readers no engine); both kept in the context."""
    if 'scope_paths' not in ctx:
        if 'scopes' not in ctx:
            ctx['scopes'] = live()
        ctx['scope_paths'] = row_paths(ctx['trace'], ctx['scopes'])
    return ctx['scope_paths']
