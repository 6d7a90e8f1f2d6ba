#!/usr/bin/env python3
"""The window, once, on the chip: sessions whose contexts cross a sliding
layer's window during decode, through `ServingEngine` at the
configuration's own widths, against the plain reference; and the same
served tokens against a reference that lacks the window, which has to come
out as not correct by `--limit`. No cell's contexts cross a window (PERF.md,
Open questions), so the configuration is named and the geometry is this
script's. One JSON line; not part of a benchmark run.

    python benchmark/tests/chip_window.py --config trinity-large-preview --limit 2.5 --seed 7 --prompts 3968,4000,4050 --new 288
"""
import argparse
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
    ap.add_argument('--config', required=True,
                    help='a name under benchmark/configs, or a file')
    ap.add_argument('--limit', type=float, required=True,
                    help='of served_logit_gap')
    ap.add_argument('--seed', type=int, default=7)
    ap.add_argument('--prompts', default='3968,4000,4050')
    ap.add_argument('--new', type=int, default=288)
    ap.add_argument('--context', type=int, default=8192)
    args = ap.parse_args()

    from benchmark.harness import common, serve_driver, verdict
    from benchmark.reference import serve_ref

    if os.path.exists(args.config):
        with open(args.config) as f:
            cfg = json.load(f)
    else:
        cfg = common.load('configs', args.config)
    fam, window = common.family(cfg), cfg['sliding_window']
    lens = [int(n) for n in args.prompts.split(',')]
    geometry = {'max_slots': 4, 'block_size': 16, 'decode_window': 8,
                'max_new_tokens': args.new, 'max_context_len': args.context}
    engine = serve_driver.build_engine(fam, cfg, geometry, args.seed)
    serve_driver.warm(engine, lens)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg['vocab_size'], n).astype(np.int32)
               for n in lens]
    t0 = time.perf_counter()
    outs = [np.asarray(o) for o in engine.serve(prompts)]
    served_s = time.perf_counter() - t0
    import jax

    peak = common.memory_peak(jax.devices()[:1])
    del engine
    common.free_device()
    limit = args.limit
    pad_to = -(-(max(lens) + args.new) // 128) * 128
    gaps = {'windowed': [], 'no_window': []}
    for p, o in zip(prompts, outs):             # one request a pass: it fits
        request = [(p, o[len(p):])]
        gaps['windowed'].append(serve_ref.served_gaps(
            fam, cfg, args.seed, request, pad_to)['served_gap'])
        gaps['no_window'].append(serve_ref.served_gaps(
            fam, dict(cfg, sliding_window=None), args.seed, request,
            pad_to)['served_gap'])
    held = verdict.Verdict()
    held.hold('served_logit_gap', max(gaps['windowed']), limit)
    fooled = verdict.judged(
        {'no_window': {'served_logit_gap': max(gaps['no_window'])}},
        {'served_logit_gap': limit})
    held.report()
    crossed = [len(p) < window < len(o) for p, o in zip(prompts, outs)]
    print(json.dumps({
        'correct': held.correct, 'window': window, 'prompts': lens,
        'contexts_at_the_end': [len(o) for o in outs], 'crossed': crossed,
        'served_s': served_s, 'memory_peak_bytes': peak, 'gaps': gaps,
        'compared': held.compared(), 'control': fooled}), flush=True)
    sys.exit(0 if held.correct and all(crossed)
             and not fooled['no_window']['correct'] else 1)


if __name__ == '__main__':
    main()
