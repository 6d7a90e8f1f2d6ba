#!/usr/bin/env python3
"""A serving cell's control and planted faults, on the chip at the cell's
own size, in one process: sessions of the cell's own traffic served through
`ServingEngine` at its geometry, then the same served tokens held to the
plain float32 reference as it is (has to be correct), to the control
precisions in the program's place, and to the reference with each of the
family's `SERVE_FAULTS` planted (`cfg['fault']`). One JSON line a seed with
every reading beside the cell's limit; the exit code is 1 where the program
is not correct or a fault named in `--must-fail` passes. Not part of a
benchmark run (that is `chip_control.py`'s, whose serving side reads the
cell's one control).

    python benchmark/tests/chip_faults.py --workload mimo_ep16_reason_long --seeds 7,8 --sessions 8 --check 2 --must-fail no_sink,no_window
"""
import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--sessions', type=int, default=8,
                    help='requests of the traffic served at once')
    ap.add_argument('--check', type=int, default=2,
                    help='of them, the longest held to the reference')
    ap.add_argument('--controls', default='int8,fp8')
    ap.add_argument('--must-fail', default='')
    args = ap.parse_args()

    import jax

    from benchmark.harness import common, loadgen, serve_driver
    from benchmark.reference import serve_ref

    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        entry = next(w for w in json.load(f)['workloads']
                     if w['name'] == args.workload)
    cell = common.load('workloads', args.workload)
    cfg = common.load('configs', entry['config'])
    traffic = common.load('traffic', entry['traffic'])
    fam, limit = common.family(cfg), cell['limits']['served_logit_gap']
    faults = list(getattr(fam.reference, 'SERVE_FAULTS', ()))
    must = [f for f in args.must_fail.split(',') if f]
    pad_to = -(-loadgen.longest(traffic) // 128) * 128
    sound = True
    for seed in (int(s) for s in args.seeds.split(',')):
        t0 = time.perf_counter()
        engine = serve_driver.build_engine(fam, cfg, cell['geometry'], seed)
        serve_driver.warm(engine, traffic['buckets'])
        plans = list(itertools.islice(
            loadgen.closed_loop(traffic, cfg['vocab_size'], seed),
            args.sessions))
        rids = [engine.submit(p.prompt, p.new_tokens) for p in plans]
        engine.run()
        outs = [np.asarray(engine.result(r)) for r in rids]
        served_s = time.perf_counter() - t0
        del engine
        common.free_device()
        longest = sorted(range(len(plans)), key=lambda i: -len(outs[i]))
        sample = [(plans[i].prompt, outs[i][len(plans[i].prompt):])
                  for i in longest[:args.check]]
        read = {}
        got = serve_ref.served_gaps(fam, cfg, seed, sample, pad_to)
        read['program'] = got['served_gap']
        for control in (c for c in args.controls.split(',') if c):
            read[control] = serve_ref.served_gaps(
                fam, cfg, seed, sample, pad_to, control=control)['control_gap']
        for fault in faults:
            jax.clear_caches()          # the fault is a static of the trace
            read[fault] = serve_ref.served_gaps(
                fam, dict(cfg, fault=fault), seed, sample,
                pad_to)['served_gap']
        passed = [n for n, v in read.items() if n != 'program' and v <= limit]
        sound = (sound and read['program'] <= limit
                 and not set(must) & set(passed))
        print(json.dumps({
            'seed': seed, 'limit': limit, 'gaps': read,
            'not_caught': passed, 'served_tokens': got['served_tokens'],
            'contexts': [len(o) for o in outs], 'served_s': served_s,
            'all_s': time.perf_counter() - t0}), flush=True)
    sys.exit(0 if sound else 1)


if __name__ == '__main__':
    main()
