#!/usr/bin/env python3
"""The sweep that finds an open-loop cell's knee, once, on the chip: one
engine, the cell's mix at a few fixed rates, and for each whether the
backlog grew through the window. Not part of a benchmark run.

    python benchmark/tests/chip_sweep.py --workload <cell> --rates 2,3,4 --seeds 7,8 --seconds 30

With `--schedules` it also answers whether the tails belong to the mix or
to the one shuffle its cycle is cut from (`loadgen.SCHEDULE`): the same
rate and seeds under other shuffles.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=30)
    ap.add_argument('--seeds', default='77')
    ap.add_argument('--schedules', default=str(24))
    args = ap.parse_args()

    import numpy as np

    from benchmark import run as bench_run
    from benchmark.harness import common, loadgen, serve_driver

    seeds = [int(x) for x in args.seeds.split(',')]
    cell, cfg, traffic, _ = bench_run.open_run(
        args.workload, seeds[0], args.seconds, False, time.perf_counter())
    engine = serve_driver.build_engine(
        common.family(cfg), cfg, cell['geometry'], seeds[0])
    serve_driver.warm(engine, traffic['buckets'])
    runs = [(float(r), int(c), x) for r in args.rates.split(',')
            for c in args.schedules.split(',') for x in seeds]
    for rate, schedule, seed in runs:
        loadgen.SCHEDULE = schedule
        mix = dict(traffic, rate_rps=rate)
        source = serve_driver.OpenSource(loadgen.open_loop(
            mix, cfg['vocab_size'], seed, args.seconds),
            args.seconds)
        t0 = time.perf_counter()
        records, steps, late = serve_driver.drive(
            engine, source, args.seconds, traffic['drain_limit_s'])
        outs = serve_driver.collect(engine, records)
        e2e = serve_driver.end_to_end(records, outs, args.seconds,
                                      traffic['drain_limit_s'])
        # requests due but not yet finished, at the end of each third
        thirds = []
        for k in (1, 2, 3):
            t = args.seconds * k / 3
            thirds.append(sum(r.plan.due <= t and (r.done is None
                                                   or r.done > t)
                              for r in records if r.plan.measured))
        window = [r for r in records if r.plan.measured]
        first = [r.token_times()[0] - r.plan.due for r in window
                 if r.plan.due < args.seconds / 2]
        second = [r.token_times()[0] - r.plan.due for r in window
                  if r.plan.due >= args.seconds / 2]
        drain = max(r.done for r in records) - args.seconds
        gaps = np.concatenate([np.diff(r.token_times()) for r in window])
        ttfts = [r.token_times()[0] - r.plan.due for r in window]
        print(json.dumps({
            'rate_rps': rate, 'schedule': schedule, 'seed': seed,
            'attempted': e2e['attempted'],
            'failed': e2e['failed'], 'ttft_p95_s': e2e['ttft_p95_s'],
            'itl_p95_ms': e2e['itl_p95_ms'], 'tok_s': e2e['serve_tok_s'],
            'gap_ms_p90_93_95_97_99': [round(1e3 * float(np.percentile(
                gaps, q)), 1) for q in (90, 93, 95, 97, 99)],
            'ttft_s_p50_90_95_99': [round(float(np.percentile(ttfts, q)), 3)
                                    for q in (50, 90, 95, 99)],
            'outstanding_at_thirds': thirds,
            'ttft_p95_first_half': serve_driver.p95(first),
            'ttft_p95_second_half': serve_driver.p95(second),
            'drain_s': drain, 'steps': len(steps),
            'step_mean_s': float(np.mean([b - a for a, b in steps])),
            'late_ms_max': 1e3 * max(late),
            'phase_s': time.perf_counter() - t0}), flush=True)


if __name__ == '__main__':
    main()
