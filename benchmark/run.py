#!/usr/bin/env python3
"""The benchmark's command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: weights and traffic from `--seed`, warm-up
of the cell's own shapes (set-up), a measured window of `--seconds`, then
the comparison with the plain reference that decides `correct`. The last
line of standard output is the result. Which driver runs, at which sizes,
under which traffic and with which per-layer metrics is data:
`benchmark/workloads/<cell>.json`, the configuration and the traffic mix it
names, the family's files that the configuration names
(`benchmark/families`), the entries of `BENCHMARK.json`, and
`benchmark/metrics/<metric>.json`.

Exits non-zero with no result line when jax finds no TPU or fewer chips
than the cell asks for, when the chip's kind has no published peak, or
when anything went through the compiler inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DRIVERS = {'serve': ('benchmark.harness.serve_driver', 'run_cell'),
           'train': ('benchmark.harness.train_driver', 'run_cell')}


def cell_metrics(bench, name):
    """(end-to-end names, per-layer names) that `BENCHMARK.json` gives the
    cell: a metric with no `workloads` list belongs to every cell."""
    def mine(entries):
        return [m['name'] for m in entries
                if name in m.get('workloads', [name])]
    return mine(bench['end_to_end']), mine(bench['per_layer'])


def load_cell(bench, name):
    """The cell's own files, found by the names in `BENCHMARK.json`.
    Returns (cell, configuration, traffic, per-layer metric names)."""
    from benchmark.harness import common

    entry = next((w for w in bench['workloads'] if w['name'] == name), None)
    if entry is None:
        raise SystemExit(f'benchmark: BENCHMARK.json has no cell {name!r}')
    cell = common.load('workloads', name)
    cell['chips'] = entry['chips']
    cell['end_to_end'], per_layer = cell_metrics(bench, name)
    cfg = common.load('configs', entry['config'])
    common.family(cfg)          # ends the run here where it has no files
    return cell, cfg, common.load('traffic', entry['traffic']), per_layer


def execute(cell, cfg, traffic, env, **kwargs):
    """The driver the cell file names, on the cell."""
    module, function = DRIVERS[cell['driver']]
    __import__(module)
    return getattr(sys.modules[module], function)(cell, cfg, traffic, env,
                                                  **kwargs)


def open_run(workload, seed, seconds, trace, t_start):
    """The cell's files, the look for the chips it asks for, the compile
    cache and the run's environment. Ends the process, with no result
    line, off a TPU or on a chip whose kind has no published peak."""
    from benchmark.harness import common, peaks, programs

    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell, cfg, traffic, per_layer = load_cell(bench, workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != 'tpu':
        raise SystemExit(f'benchmark: needs a TPU, jax found '
                         f'{devices[0].platform!r}: nothing was run')
    if len(devices) < cell['chips']:
        raise SystemExit(f'benchmark: {workload} asks for {cell["chips"]} '
                         f'chip(s), jax found {len(devices)}')
    peak = peaks.peak_for(devices[0].device_kind)

    from paddle_tpu import sysconfig

    cache_dir = sysconfig.enable_persistent_compilation_cache()
    print(f'{len(devices)} x {devices[0].device_kind}; compile cache at '
          f'{cache_dir} ({len(os.listdir(cache_dir))} entries)', flush=True)
    env = common.Env(t_start=t_start, seed=seed, seconds=seconds,
                     trace=trace, device=devices[0], peak=peak,
                     compiles=programs.CompileLog(), per_layer=per_layer)
    return cell, cfg, traffic, env


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, cfg, traffic, env = open_run(args.workload, args.seed,
                                       args.seconds, bool(args.trace),
                                       T_START)
    print(json.dumps(execute(cell, cfg, traffic, env)), flush=True)


if __name__ == '__main__':
    main()
