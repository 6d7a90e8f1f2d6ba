#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at the cell's own
size, in one process: the program's numbers on a dozen seeds (the lower
reading is their largest) and, on the first few, the control's (the
reference at the precision below the configuration's, in the program's
place) and each planted fault's (the upper reading is their smallest).
Every control and fault has to come out as not correct by the cell's own
limits: the exit code is 1 where one does not, or where the program itself
is not correct. Not part of a benchmark run; one JSON line a seed.

    python benchmark/tests/chip_control.py --workload <cell> --seeds 101,102,... --controls 4 --seconds 8
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
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--controls', type=int, default=3)
    ap.add_argument('--seconds', type=float, default=8)
    args = ap.parse_args()

    import dataclasses

    from benchmark import run as bench_run

    seeds = [int(s) for s in args.seeds.split(',')]
    cell, cfg, traffic, env = bench_run.open_run(
        args.workload, seeds[0], args.seconds, False, time.perf_counter())
    sound = True
    for i, seed in enumerate(seeds):
        out = bench_run.execute(
            cell, cfg, traffic,
            dataclasses.replace(env, seed=seed, t_start=time.perf_counter()),
            control=i < args.controls)
        keep = {k: out[k] for k in ('correct', 'attempted', 'failed',
                                    'compared', 'control', 'leaves',
                                    'served_tokens_checked') if k in out}
        fooled = [n for n, c in out.get('control', {}).items()
                  if c['correct']]
        sound = sound and out['correct'] and not fooled
        print(json.dumps({'seed': seed, 'controls_that_passed': fooled,
                          **keep}), flush=True)
    sys.exit(0 if sound else 1)


if __name__ == '__main__':
    main()
