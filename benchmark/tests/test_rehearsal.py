"""CPU rehearsal of each driver at tiny size through run.py's own code
path (`load_cell`, `execute`), and the proofs that the harness is driven
by data: `run.py` prints no result off a TPU, an unknown device kind has no
peak, and a fourth cell is picked up from new files and new entries alone.
"""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tiny

from benchmark import run as bench_run
from benchmark.harness import common, loadgen, peaks

ROOT = common.ROOT


def test_run_py_prints_no_result_off_a_tpu():
    proc = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload',
         'mistral7b_chat_steady', '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), timeout=300)
    assert proc.returncode != 0
    assert 'needs a TPU' in proc.stderr
    assert '"correct"' not in proc.stdout


def test_unknown_device_kind_has_no_peak():
    assert peaks.peak_for('TPU v5 lite').flops_bf16 == 197e12
    assert peaks.peak_for('TPU v5 lite').hbm_bytes_s == 819e9
    with pytest.raises(SystemExit):
        peaks.peak_for('TPU v9 imaginary')


def test_benchmark_json_and_cell_files_agree():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    e2e = {m['name'] for m in bench['end_to_end']}
    for w in bench['workloads']:
        cell, cfg, traffic, per_layer = bench_run.load_cell(bench, w['name'])
        assert cell['driver'] in bench_run.DRIVERS
        assert 'setup_s' in cell['end_to_end'] and len(cell['end_to_end']) > 1
        assert per_layer, w['name']
        for name in per_layer:
            spec = common.load('metrics', name)
            entry = next(m for m in bench['per_layer'] if m['name'] == name)
            assert spec['moves'] == entry['moves'] in cell['end_to_end']
            assert spec['layer'] == entry['layer']
            assert spec['unit'] == entry['unit']
            assert os.path.exists(os.path.join(
                common.BENCH, 'metrics', 'readers', spec['reader'] + '.py'))
        assert set(cfg['reduced']) == set(next(
            c for c in bench['configs'] if c['name'] == w['config'])['reduced'])
    assert e2e >= {'ttft_p95_s', 'itl_p95_ms', 'serve_tok_s', 'train_tok_s',
                   'setup_s'}


def test_every_seed_gets_the_same_work_in_another_order():
    traffic = common.load('traffic', 'chat_steady')
    n = round(traffic['rate_rps'] * 20)
    a = loadgen.open_loop(traffic, 1000, 1, 20)
    b = loadgen.open_loop(traffic, 1000, 2 ** 31 + 5, 20)
    window = lambda plan: [p for p in plan if p.measured]    # noqa: E731
    sizes = lambda plan: [(len(p.prompt), p.new_tokens)      # noqa: E731
                          for p in window(plan)]
    assert len(sizes(a)) == len(sizes(b)) == n
    assert sizes(a) != sizes(b) and sorted(sizes(a)) == sorted(sizes(b))
    # the same cycle, entered elsewhere: b's window is a's, turned
    turns = [sizes(a)[k:] + sizes(a)[:k] for k in range(n)]
    assert sizes(b) in turns
    gaps = lambda plan: np.diff([p.due for p in window(plan)])  # noqa: E731
    assert sorted(np.round(gaps(a), 9)) != [] and np.allclose(
        sorted(np.append(gaps(a), 20 - window(a)[-1].due)),
        sorted(np.append(gaps(b), 20 - window(b)[-1].due)))
    for plan in (a, b):
        assert min(len(p.prompt) for p in plan) >= 100
        assert max(len(p.prompt) for p in plan) <= 1024
        assert all(0 <= p.due < 20 for p in window(plan))
        lead = [p for p in plan if not p.measured]
        assert lead and all(-1.5 * traffic['lead_in_s'] <= p.due < 0
                            for p in lead)
        # the lead-in is the stretch of the cycle just before the window
        assert [(len(p.prompt), p.new_tokens) for p in lead] == sizes(
            plan)[-len(lead):]
    again = loadgen.open_loop(traffic, 1000, 1, 20)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               for x, y in zip(a, again))
    other = window(loadgen.open_loop(traffic, 1000, 2, 20))
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x in window(a) for y in other)


@pytest.mark.parametrize('loop', ['open', 'closed'])
def test_serve_driver_rehearsal(loop):
    cell = copy.deepcopy(tiny.SERVE_CELL)
    traffic = tiny.OPEN if loop == 'open' else tiny.CLOSED
    if loop == 'closed':
        cell['end_to_end'] = ['serve_tok_s', 'setup_s']
        cell['geometry']['max_new_tokens'] = 9
    out = bench_run.execute(cell, tiny.TINY_SERVE_CFG, traffic, tiny.env())
    assert out['correct'] is True, out['compared']
    assert out['attempted'] > 0 and out['failed'] == 0
    assert set(out['metrics']) == set(cell['end_to_end'])
    assert all(m['value'] > 0 for m in out['metrics'].values())
    assert list(out)[-1] == 'compared'
    assert out['compared']['served_logit_gap']['value'] <= 0.25


def test_train_driver_rehearsal():
    out = bench_run.execute(copy.deepcopy(tiny.TRAIN_CELL),
                            tiny.TINY_TRAIN_CFG, tiny.TRAIN,
                            tiny.env(seed=2 ** 31 + 7))
    assert out['correct'] is True, out['compared']
    assert set(out['metrics']) == {'train_tok_s', 'setup_s'}
    assert out['attempted'] >= tiny.TRAIN_CELL['sync_every']
    assert set(out['compared']) == set(tiny.TRAIN_CELL['limits']) | {
        'nonfinite_loss'}


def test_a_fourth_cell_is_new_files_and_entries_only(tmp_path, monkeypatch):
    """A second rate of the chat mix on a tiny configuration: three data
    files and four entries, and the same `load_cell`/`execute`."""
    bench_dir = tmp_path / 'benchmark'
    for kind in ('workloads', 'traffic', 'configs', 'metrics'):
        shutil.copytree(os.path.join(common.BENCH, kind), bench_dir / kind)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    (bench_dir / 'configs' / 'tiny-lm.json').write_text(json.dumps(
        dict(tiny.TINY_SERVE_CFG, source='test', reduced=[])))
    (bench_dir / 'traffic' / 'chat_double.json').write_text(json.dumps(
        dict(tiny.OPEN, rate_rps=2 * tiny.OPEN['rate_rps'])))
    cell = {k: v for k, v in tiny.SERVE_CELL.items()
            if k not in ('chips', 'end_to_end')}
    (bench_dir / 'workloads' / 'tiny_chat_double.json').write_text(
        json.dumps(cell))
    bench['configs'].append({'name': 'tiny-lm', 'source': 'test',
                             'file': 'benchmark/configs/tiny-lm.json',
                             'reduced': [], 'why': 'test'})
    bench['workloads'].append({'name': 'tiny_chat_double',
                               'config': 'tiny-lm', 'traffic': 'chat_double',
                               'chips': 1, 'why': 'test'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'mistral7b_chat_steady' in m.get('workloads', []):
            m['workloads'].append('tiny_chat_double')
    monkeypatch.setattr(common, 'BENCH', str(bench_dir))
    cell, cfg, traffic, per_layer = bench_run.load_cell(bench,
                                                        'tiny_chat_double')
    assert set(cell['end_to_end']) == {'ttft_p95_s', 'itl_p95_ms', 'setup_s'}
    assert 'step_mfu_pct.latency' in per_layer
    out = bench_run.execute(cell, cfg, traffic, tiny.env())
    assert out['correct'] is True and out['attempted'] == round(
        2 * tiny.OPEN['rate_rps'] * 2.0)


class RecordedProfile:
    """Stands in for the profiler: the slice recorded on the chip."""

    def __init__(self, trace_dir):
        self.window_s = 0.46

    def start(self):
        pass

    def stop(self):
        pass

    def load(self):
        from benchmark.harness import trace_reduce

        with open(os.path.join(os.path.dirname(__file__),
                               'recorded_trace.json')) as f:
            return trace_reduce.Trace.from_json(json.load(f))


@pytest.mark.parametrize('name', ['mistral7b_chat_steady',
                                  'mistral7b_docs_backlog',
                                  'qwen25_3b_pretrain_4k'])
def test_traced_run_reads_the_cells_own_metrics(name, monkeypatch):
    """The `--trace 1` path with the profiler stubbed: every per-layer
    metric `BENCHMARK.json` gives the cell goes through the reader its file
    names, and one that finds nothing to read is left out of the line."""
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    real, _, real_traffic, per_layer = bench_run.load_cell(bench, name)
    monkeypatch.setattr(common, 'Profile', RecordedProfile)
    env = tiny.env(trace=True)
    env.per_layer, env.peak = per_layer, peaks.peak_for('TPU v5 lite')
    if real['driver'] == 'train':
        cell, cfg, traffic = tiny.TRAIN_CELL, tiny.TINY_TRAIN_CFG, tiny.TRAIN
    else:
        cell, cfg = tiny.SERVE_CELL, tiny.TINY_SERVE_CFG
        traffic = tiny.OPEN if real_traffic['loop'] == 'open' else tiny.CLOSED
    out = bench_run.execute(copy.deepcopy(cell), cfg, traffic, env)
    assert out['correct'] is True
    got = set(out['metrics'])
    assert got <= set(per_layer) and 'setup_s' not in got
    assert {n for n in per_layer if n.startswith(('step_mfu', 'device_idle',
                                                  'step_wall'))} <= got
    # no Mosaic call in a CPU program: the roofline readers return nothing
    assert not any('roofline' in n for n in got)
    assert out['device']['busy_s'] > 0 and out['device']['window_s'] == 0.46
    assert set(out['breakdown']) == {'device_ops', 'idle_gaps'}
    assert all(len(n) < 120 for n, _ in out['breakdown']['device_ops'])


def test_the_windows_two_ends_are_off_the_clock():
    """What `on_window` takes (the profiler's start and stop: longer than
    the drain limit in a traced chat run) is neither the window's nor the
    drain's: the requests live at the close still finish and none counts
    as failed."""
    import time

    from benchmark.harness import serve_driver

    geometry = dict(tiny.SERVE_CELL['geometry'], max_new_tokens=9)
    engine = serve_driver.build_engine(
        common.family(tiny.TINY_SERVE_CFG), tiny.TINY_SERVE_CFG, geometry, 3)
    serve_driver.warm(engine, tiny.CLOSED['buckets'])
    seconds, drain = 1.0, 0.5
    source = serve_driver.ClosedSource(
        loadgen.closed_loop(tiny.CLOSED, 512, 3), tiny.CLOSED['clients'],
        tiny.CLOSED['lead_in_s'], seconds)
    records, steps, _ = serve_driver.drive(
        engine, source, seconds, drain,
        on_window=lambda opening: time.sleep(3 * drain))
    e2e = serve_driver.end_to_end(
        records, serve_driver.collect(engine, records), seconds, drain)
    assert e2e['attempted'] > 0 and e2e['failed'] == 0
    assert any(r.done > seconds for r in records if r.plan.measured)
    assert steps[-1][1] < seconds + drain
