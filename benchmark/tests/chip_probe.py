#!/usr/bin/env python3
"""A look by hand, on the chip: one short traced run of a serve cell with
the raw trace's planes, lines and event names written to
`chiprun_out/`, then `memory_analysis()` and the Mosaic instructions of
every program the cell dispatches. Not part of a benchmark run.

    python benchmark/tests/chip_probe.py --workload <cell> --seed 5 --seconds 6
"""
import argparse
import collections
import glob
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, 'chiprun_out')


def summarise_xplane(trace_dir, out_path):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))[-1]
    data = ProfileData.from_file(path)
    summary = {'file_bytes': os.path.getsize(path), 'planes': []}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            sums = collections.Counter()
            for e in events:
                sums[e.name] += e.duration_ns
            lines.append({
                'name': line.name, 'events': len(events),
                'first': [{'name': e.name, 'start_ns': e.start_ns,
                           'dur_ns': e.duration_ns,
                           'stats': {k: str(v)[:120] for k, v in e.stats}}
                          for e in events[:12]],
                'top': sums.most_common(40)})
        summary['planes'].append({'name': plane.name, 'lines': lines})
    with open(out_path, 'w') as f:
        json.dump(summary, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=5)
    ap.add_argument('--seconds', type=float, default=6)
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)

    from benchmark import run as bench_run
    from benchmark.harness import common, programs

    cell, cfg, traffic, env = bench_run.open_run(
        args.workload, args.seed, args.seconds, True, T0)
    load = common.Profile.load

    def load_and_summarise(self):
        summarise_xplane(self.dir, os.path.join(
            OUT, f'xplane_{args.workload}.json'))
        trace = load(self)
        from benchmark.harness import trace_reduce
        trace_reduce.dump(trace, os.path.join(
            OUT, f'trace_{args.workload}.json'))
        return trace

    common.Profile.load = load_and_summarise
    try:
        result = bench_run.execute(cell, cfg, traffic, env)
        print(json.dumps(result), flush=True)
    except Exception as e:                       # the look goes on
        import traceback
        traceback.print_exc()
        print(f'probe: traced run failed: {e!r}', flush=True)
    if cell['driver'].startswith('serve'):
        from paddle_tpu.aot import geometry

        from benchmark.harness import serve_driver
        engine = serve_driver.build_engine(
            common.family(cfg), cfg, cell['geometry'], args.seed)
        serve_driver.warm(engine, traffic['buckets'])
        table = []
        for g in geometry.for_serving_engine(
                engine, prompt_lens=list(traffic['buckets'])):
            for fn, a, kw in engine._cost_specs(g):
                table.append(programs.describe(g.label(), fn, a, kw))
                d = table[-1]
                print(d['label'], f'{d["needs_gib"]:.2f} GiB (arguments '
                      f'{d["arguments_gib"]:.2f}, temporaries '
                      f'{d["temporaries_gib"]:.2f})',
                      {k: len(v) for k, v in d['kernels'].items()},
                      flush=True)
        with open(os.path.join(OUT, f'programs_{args.workload}.json'),
                  'w') as f:
            json.dump(table, f, indent=1)
        print('bytes_limit', env.device.memory_stats().get('bytes_limit'))


if __name__ == '__main__':
    main()
