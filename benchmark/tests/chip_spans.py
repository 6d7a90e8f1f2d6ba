#!/usr/bin/env python3
"""A look by hand, on the chip: one traced run of a cell through run.py's
own path, then the program's own spans as its ring recorded them inside
the traced window (`TRACER.traced()`): per span name the count, the summed,
mean, median and longest duration on the host's clock, the dispatches by kind, and
the sums of every number the spans carry. This is the split of a step
that PERF.md section 5 gives. Not part of a benchmark run.

    python benchmark/tests/chip_spans.py --workload <cell> --seed 5 --seconds 50

Prints the run's result line, then one line `{"spans": ...}`.
"""
import argparse
import collections
import json
import os
import statistics
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def summarise(events):
    """{span: {n, sum_s, mean/median/longest ms, sums of its number
    args}} and the dispatches by kind."""
    spans, kinds = {}, collections.Counter()
    durations = collections.defaultdict(list)
    for e in events:
        row = spans.setdefault(e['name'], {'n': 0, 'sum_s': 0.0})
        row['n'] += 1
        row['sum_s'] += e.get('dur', 0.0) * 1e-6
        durations[e['name']].append(e.get('dur', 0.0) * 1e-3)
        for key, value in e.get('args', {}).items():
            if isinstance(value, (int, float)) and key not in ('rid', 'slot'):
                row[f'sum_{key}'] = row.get(f'sum_{key}', 0) + value
        if e['name'] == 'serve.dispatch':
            kinds[e['args']['kind']] += 1
    for name, row in spans.items():
        row['mean_ms'] = 1e3 * row['sum_s'] / row['n']
        row['median_ms'] = statistics.median(durations[name])
        row['longest_ms'] = max(durations[name])
    return {'spans': spans, 'dispatch_kinds': dict(kinds)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=5)
    ap.add_argument('--seconds', type=float, default=50)
    args = ap.parse_args()

    from benchmark import run as bench_run

    from paddle_tpu.observability import tracing

    cell, cfg, traffic, env = bench_run.open_run(
        args.workload, args.seed, args.seconds, True, T0)
    print(json.dumps(bench_run.execute(cell, cfg, traffic, env)), flush=True)
    print(json.dumps(summarise(tracing.TRACER.traced())), flush=True)


if __name__ == '__main__':
    main()
