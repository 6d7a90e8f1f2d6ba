#!/usr/bin/env python3
"""A look by hand, on the chip: one traced run of a cell through run.py's
own path, then its device time by model part, as the program's own device
scopes name each instruction (`harness/scopes.py`): the decode loop (the
instructions inside `jit__serve_window`'s and `jit__serve_step`'s scans),
the admission (`jit__paged_prefill`, and `jit__serve_step` outside its
scan) and the three serve programs whole; under each part the opcodes, and
the largest instructions left `other` or `ambiguous`; the trace and the
scope maps are kept for a look afterwards. Beside it the checks that the
split is sound: the expert products' share against the grouped
kernel's events (`expert_kernel_dev_share_pct.moe`), the attention core in
the loop against the paged kernel's events (what `paged_attn_roofline.*`
divides by), and a digest of the scope map, which a second run served from
the compile cache has to repeat. Not part of a benchmark run.

    python benchmark/tests/chip_scopes.py --workload <cell> --seed 5 \
        --seconds 16 --tag change

Prints the run's result line, then one line `{"scopes": ...}`; the whole
table goes to `chiprun_out/scopes_<cell>_<tag>_<seed>.json`, the trace and
the maps beside it (`_trace.json.gz`).
"""
import argparse
import collections
import gzip
import hashlib
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, 'chiprun_out')

SERVE = '^jit__(serve_(step|window)|paged_prefill)'
PARTS = {'decode_loop': ('^jit__serve_(step|window)', 'loop'),
         'admission': ('^jit__(paged_prefill|serve_step)', 'outside'),
         'serve_programs': (SERVE, 'all')}


def split(trace, paths, program, part, top=8):
    """{path: {ms, pct, opcodes {opcode: ms}}} of a part's instruction
    time (ms summed over the traced window), and its largest `other` and
    `ambiguous` instructions."""
    from benchmark.harness import scopes
    from benchmark.metrics.readers import scope_share

    by_path = collections.defaultdict(collections.Counter)
    unnamed = collections.Counter()
    total = 0.0
    for device, k, prog, name, opcode, dur in scope_share.events(
            trace, program, part):
        path = paths[device][k]
        by_path[path][opcode] += 1e3 * dur
        total += 1e3 * dur
        if path in (scopes.OTHER, scopes.AMBIGUOUS):
            unnamed[f'{path}:{prog}:{name}:{opcode}'] += 1e3 * dur
    table = {path: {'ms': sum(ops.values()),
                    'pct': 100.0 * sum(ops.values()) / total if total else 0,
                    'opcodes': dict(ops.most_common())}
             for path, ops in sorted(by_path.items(),
                                     key=lambda kv: -sum(kv[1].values()))}
    return {'total_ms': total, 'paths': table,
            'largest_unnamed_ms': unnamed.most_common(top)}


def checks(ctx, metrics):
    """The cross-checks of the attribution against what is found by name."""
    from benchmark.harness import trace_reduce
    from benchmark.metrics.readers import scope_share

    trace, paths = ctx['trace'], ctx['scope_paths']
    out = {}
    matmul, _, n = scope_share.seconds_of(trace, paths,
                                          '^expert_share/matmul', SERVE, 'all')
    whole = sum(trace_reduce.program_times(trace, SERVE)) / max(
        1, len(trace.programs))
    if n:
        out['expert_matmul_scope_pct'] = 100.0 * matmul / whole
        kernel = metrics.get('expert_kernel_dev_share_pct.moe')
        if kernel:
            out['expert_kernel_dev_share_pct'] = kernel['value']
    paged = {k for kernels in ctx['kernels'].values()
             for k in kernels.get('paged_attention', ())}
    kernel_s, events = trace_reduce.op_seconds(trace, paged,
                                               '^jit__serve_(step|window)')
    core, _, _ = scope_share.seconds_of(trace, paths, '^attn/core$',
                                        '^jit__serve_(step|window)', 'loop')
    if events:
        out['paged_kernel_ms'] = 1e3 * kernel_s
        out['attn_core_loop_ms'] = 1e3 * core
        out['attn_core_over_kernel'] = core / kernel_s
    variants = ctx['scopes']
    digest = hashlib.sha256(json.dumps(
        {m: sorted(sorted(v.items()) for v in maps)
         for m, maps in sorted(variants.items())}).encode())
    out['scope_map_sha256'] = digest.hexdigest()
    out['scope_map_instructions'] = {m: [len(v) for v in maps]
                                     for m, maps in variants.items()}
    return out


def instruction_lines(trace, top=30):
    """{program:instruction: [its compiled line in each variant]} of the
    instructions that took most device time in the serve programs, from
    the live engines' executables (the program's own text: what a copy
    copies, what a fusion holds)."""
    import gc
    import re

    from paddle_tpu.aot import geometry
    from paddle_tpu.inference.engine import COMPILE_CACHE
    from paddle_tpu.inference.serving import ServingEngine

    from benchmark.harness import trace_reduce
    from benchmark.metrics.readers import scope_share

    sums = collections.Counter()
    for _, _, prog, name, opcode, dur in scope_share.events(trace, SERVE,
                                                            'all'):
        if opcode not in trace_reduce.CONTAINERS:
            sums[prog, name] += dur
    wanted = [key for key, _ in sums.most_common(top)]
    lines = collections.defaultdict(list)
    noted = set(COMPILE_CACHE.keys())
    for engine in [o for o in gc.get_objects()
                   if isinstance(o, ServingEngine)]:
        for g in geometry.for_serving_engine(engine):
            if engine.registry_key(*engine._geometry_cost_tag(g)) not in noted:
                continue
            for fn, a, kw in engine._cost_specs(g):
                text = fn.lower(*a, **kw).compile().as_text()
                module = re.search(r'HloModule\s+([\w.\-]+)', text).group(1)
                for prog, name in wanted:
                    m = prog == module and re.search(
                        rf'^\s+(?:ROOT\s+)?%{re.escape(name)} = (.*)$', text,
                        re.M)
                    if m:
                        lines[f'{prog}:{name}'].append(
                            f'{g.label()}: {m.group(1)[:400]}')
    return {key: lines[key] for key in (f'{p}:{n}' for p, n in wanted)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=5)
    ap.add_argument('--seconds', type=float, default=16)
    ap.add_argument('--tag', default='change')
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)

    from benchmark import run as bench_run
    from benchmark.harness import common, scopes

    seen = {}
    read_metrics = common.read_metrics

    def keep(env, ctx):
        t = time.perf_counter()
        scopes.of_context(ctx)
        seen['scope_map_s'] = time.perf_counter() - t
        seen['lines'] = instruction_lines(ctx['trace'])
        metrics = read_metrics(env, ctx)
        seen['ctx'], seen['metrics'] = ctx, metrics
        return metrics

    common.read_metrics = keep
    load = common.Profile.load

    def modules(profile):
        """The program events' whole names (the trace keeps what comes
        before the first `(`), then the trace as the harness loads it."""
        import glob

        from jax.profiler import ProfileData

        for path in sorted(glob.glob(os.path.join(
                profile.dir, 'plugins', 'profile', '*', '*.xplane.pb')))[-1:]:
            seen['module_events'] = collections.Counter(
                e.name for plane in ProfileData.from_file(path).planes
                if plane.name.startswith('/device:TPU:')
                for line in plane.lines if line.name == 'XLA Modules'
                for e in line.events).most_common(12)
        return load(profile)

    common.Profile.load = modules
    cell, cfg, traffic, env = bench_run.open_run(
        args.workload, args.seed, args.seconds, True, T0)
    line = bench_run.execute(cell, cfg, traffic, env)
    print(json.dumps(line), flush=True)
    ctx = seen['ctx']
    report = {'workload': args.workload, 'seed': args.seed, 'tag': args.tag,
              'scope_map_s': seen['scope_map_s'],
              'module_events': seen.get('module_events'),
              'largest_instructions': seen['lines'],
              'compiles': {'hits': env.compiles.hits,
                           'misses': env.compiles.misses},
              'checks': checks(ctx, seen['metrics']),
              'parts': {part: split(ctx['trace'], ctx['scope_paths'], *where)
                        for part, where in PARTS.items()}}
    stem = os.path.join(
        OUT, f'scopes_{args.workload}_{args.tag}_{args.seed}')
    with open(stem + '.json', 'w') as f:
        json.dump(report, f, indent=1)
    with gzip.open(stem + '_trace.json.gz', 'wt') as f:
        json.dump({'trace': ctx['trace'].to_json(), 'scopes': ctx['scopes'],
                   'kernels': {m: {k: sorted(v) for k, v in ks.items()}
                               for m, ks in ctx['kernels'].items()}}, f)
    brief = {part: {p: round(v['pct'], 2)
                    for p, v in table['paths'].items()}
             for part, table in report['parts'].items()}
    print(json.dumps({'scopes': brief, 'checks': report['checks'],
                      'scope_map_s': report['scope_map_s']}), flush=True)


if __name__ == '__main__':
    main()
