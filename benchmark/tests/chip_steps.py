#!/usr/bin/env python3
"""A look by hand, on the chip: every `step()` of a serving cell timed on
the harness's clock, one engine and several seeds' traffic in turn (a run
of the cell's window each, no reference afterwards), to see what the steps
of a slow run do that the others do not. Beside each step: the queue and
the live rows before it, and whether `_device_state` uploaded the slots'
whole state. One line a seed, the steps themselves to
`chiprun_out/steps_<tag>_<seed>.json`. Not part of a benchmark run.

    python benchmark/tests/chip_steps.py --workload mistral7b_chat_steady \
        --seeds 2147485001,3000000019 --tag parent

Two sides of a comparison: one process a side from its own checkout, the
same seeds, in one call (PERF.md, PR 33: the machine can run every step
2-3 % long for minutes, on either side). `--tiny` rehearses on the CPU.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, 'chiprun_out')


def percentiles(values):
    import numpy as np

    if not len(values):
        return []
    return [round(float(x), 3)
            for x in np.percentile(values, [5, 25, 50, 75, 90, 95])]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, default=50.0)
    ap.add_argument('--tag', default='steps')
    ap.add_argument('--tiny', action='store_true')
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(',')]
    os.makedirs(OUT, exist_ok=True)

    import numpy as np

    from benchmark import run as bench_run
    from benchmark.harness import common, loadgen
    from benchmark.harness import serve_driver as sd

    if args.tiny:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import tiny

        cell, cfg, traffic = tiny.SERVE_CELL, tiny.TINY_SERVE_CFG, tiny.OPEN
    else:
        cell, cfg, traffic, _ = bench_run.open_run(
            args.workload, seeds[0], args.seconds, False, T0)
    seconds = args.seconds
    fam, vocab = common.family(cfg), cfg['vocab_size']
    engine = sd.build_engine(fam, cfg, cell['geometry'], seeds[0])
    sd.warm(engine, traffic['buckets'])
    print(f'set up in {time.perf_counter() - T0:.1f} s', flush=True)

    log, uploads = [], [0]
    real_step, real_state = engine.step, engine._device_state

    def state():
        uploads[0] += engine._dev is None
        return real_state()

    def step():
        rec = [len(engine.queue), engine.in_flight(), 0, 0.0]
        uploads[0] = 0
        t = time.perf_counter()
        out = real_step()
        rec[3] = time.perf_counter() - t
        rec[2] = uploads[0]
        log.append(rec)
        return out

    engine._device_state, engine.step = state, step
    for seed in seeds:
        del log[:]
        if traffic['loop'] == 'open':
            source = sd.OpenSource(
                loadgen.open_loop(traffic, vocab, seed, seconds), seconds)
        else:
            source = sd.ClosedSource(
                loadgen.closed_loop(traffic, vocab, seed),
                traffic['clients'], traffic['lead_in_s'], seconds)
        records, steps, _ = sd.drive(engine, source, seconds,
                                     traffic['drain_limit_s'])
        e2e = sd.end_to_end(records, sd.collect(engine, records), seconds,
                            traffic['drain_limit_s'])
        gaps = 1e3 * np.asarray(e2e.pop('gaps'))
        rows = [[t0, t1] + r for (t0, t1), r in zip(steps, log)]
        inside = [r for r in rows if 0.0 <= r[0] < seconds]

        def ms(keep):
            return [1e3 * r[5] for r in inside if keep(r)]

        line = dict(
            e2e, tag=args.tag, seed=seed, gaps=len(gaps),
            gaps_not_0=int((gaps > 1.0).sum()), steps=len(inside),
            admitting=len(ms(lambda r: r[2] > 0)),
            bare_all_uploaded=len(ms(lambda r: r[2] == 0 and r[4])),
            bare_ms=percentiles(ms(lambda r: r[2] == 0)),
            bare_all_uploaded_ms=percentiles(ms(lambda r: r[2] == 0 and r[4])),
            bare_kept_ms=percentiles(ms(lambda r: r[2] == 0 and not r[4])),
            admitting_ms=percentiles(ms(lambda r: r[2] > 0)))
        print(json.dumps(line), flush=True)
        with open(os.path.join(OUT, f'steps_{args.tag}_{seed}.json'),
                  'w') as f:
            # [t0, t1, queued, live, uploaded everything, seconds] a step
            json.dump({'line': line, 'steps': rows}, f)


if __name__ == '__main__':
    main()
