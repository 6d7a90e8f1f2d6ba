"""The per-layer metrics that read the program's own device scopes
(`readers/scope_share.py` over `harness/scopes.py`'s map): on a trace small
enough to check by hand, with a scope map made by hand; on the slice
recorded on the chip (`recorded_trace.json`), where the paged kernel's calls
are the one part named; and the readers that were there before, which read
the same on that slice as they did before the scope readers came."""
import json
import os

import pytest

from benchmark.harness import common, scopes
from benchmark.harness import trace_reduce as tr

DEV = '/device:TPU:0'
PAGED = {f'closed_call.{n}' for n in range(398, 432, 3)}
NEW = ('window_proj_ms.latency', 'window_proj_ms.moe',
       'expert_glue_dev_share_pct.moe', 'admit_head_share_pct.batch')


def by_hand():
    """Two bare windows of 4 s (a 2.5 s scan each), a fused step of 3 s (a
    2 s scan) and a prefill of 1 s."""
    ops = []
    for t0 in (0.0, 10.0):
        ops += [('fusion.9', 'fusion', t0, 0.5, 'jit__serve_window'),
                ('while.1', 'while', t0 + 0.5, 2.5, 'jit__serve_window'),
                ('fusion.1', 'fusion', t0 + 0.5, 1.0, 'jit__serve_window'),
                ('kernel.7', 'custom-call', t0 + 1.5, 1.0,
                 'jit__serve_window'),
                ('fusion.2', 'fusion', t0 + 2.5, 0.5, 'jit__serve_window'),
                ('fusion.3', 'fusion', t0 + 3.25, 0.5, 'jit__serve_window')]
    ops += [('fusion.20', 'fusion', 5.0, 0.5, 'jit__serve_step'),
            ('fusion.21', 'fusion', 5.5, 0.5, 'jit__serve_step'),
            ('while.3', 'while', 6.0, 2.0, 'jit__serve_step'),
            ('fusion.1', 'fusion', 6.0, 1.0, 'jit__serve_step'),
            ('kernel.7', 'custom-call', 7.0, 0.75, 'jit__serve_step'),
            ('sort.4', 'sort', 7.75, 0.25, 'jit__serve_step'),
            ('fusion.30', 'fusion', 8.5, 0.3, 'jit__paged_prefill'),
            ('fusion.31', 'fusion', 8.8, 0.7, 'jit__paged_prefill')]
    return tr.Trace(
        ops={DEV: ops},
        programs={DEV: [('jit__serve_window', 0.0, 4.0),
                        ('jit__serve_step', 5.0, 3.0),
                        ('jit__paged_prefill', 8.5, 1.0),
                        ('jit__serve_window', 10.0, 4.0)]},
        host=[])


HAND_SCOPES = {     # one compiled variant a program
    'jit__serve_window': [{'fusion.9': 'sample', 'while.1': 'other',
                           'fusion.1': 'attn/qkv', 'kernel.7': 'attn/core',
                           'fusion.2': 'expert_share/shared',
                           'fusion.3': 'expert_share/route'}],
    'jit__serve_step': [{'fusion.20': 'head', 'fusion.21': 'attn/cache',
                         'fusion.1': 'attn/qkv', 'kernel.7': 'attn/core',
                         'sort.4': 'expert_share/dispatch'}],
    'jit__paged_prefill': [{'fusion.30': 'head', 'fusion.31': 'mlp'}]}


def read(names, ctx):
    env = type('Env', (), {'per_layer': list(names)})
    return {k: v['value'] for k, v in common.read_metrics(env, ctx).items()}


def test_the_new_metrics_by_hand():
    """window_proj: q/k/v (1 + 1) and the shared expert (0.5 + 0.5) inside
    the two scans, over two executions; the expert glue: route (0.5 +
    0.5) and dispatch (0.25) over 12 s of serve programs; the admission's
    head: 0.5 of the step's 1 s outside its scan and 0.3 of the prefill's
    1 s."""
    got = read(NEW, {'trace': by_hand(), 'scopes': HAND_SCOPES})
    assert got['window_proj_ms.latency'] == pytest.approx(1500.0)
    assert got['window_proj_ms.moe'] == pytest.approx(1500.0)
    assert got['expert_glue_dev_share_pct.moe'] == pytest.approx(
        100 * 1.25 / 12.0)
    assert got['admit_head_share_pct.batch'] == pytest.approx(40.0)


def test_each_part_and_value_by_hand():
    from benchmark.metrics.readers import scope_share

    trace, ctx = by_hand(), {'scopes': HAND_SCOPES}
    ctx['trace'] = trace

    def share(scope, program, part, value):
        return scope_share.read(ctx, scope, program, part, value)

    # the paged kernel: 1 + 1 + 0.75 s, all inside scans
    assert share('attn/core', '^jit__serve_', 'all',
                 'pct_of_programs') == pytest.approx(100 * 2.75 / 11.0)
    assert share('attn/core', '^jit__serve_', 'loop',
                 'ms_per_execution') == pytest.approx(1e3 * 2.75 / 3)
    assert share('attn/core', '^jit__serve_', 'outside', 'pct_of_part') is None
    # the windows' scans hold 2 x 2.5 s of instructions, 2 x 1 of them q/k/v
    assert share('^attn/qkv$', '^jit__serve_window', 'loop',
                 'pct_of_part') == pytest.approx(100 * 2 / 5)
    # outside them, the sampler and the route's 0.5 s each a window
    assert share('sample', '^jit__serve_window', 'outside',
                 'pct_of_part') == pytest.approx(50.0)
    with pytest.raises(ValueError):
        share('head', '', 'inside', 'pct_of_part')


def test_a_program_with_no_scopes_reads_nothing():
    """The parent's programs: every instruction `other` (or, where the
    expert layer opened its one scope, `expert_share` alone)."""
    bare = {p: [{n: 'other' for n in names} for names in maps]
            for p, maps in HAND_SCOPES.items()}
    bare['jit__serve_step'][0]['sort.4'] = 'expert_share'
    assert read(NEW, {'trace': by_hand(), 'scopes': bare}) == {}


def recorded():
    with open(os.path.join(os.path.dirname(__file__),
                           'recorded_trace.json')) as f:
        return tr.Trace.from_json(json.load(f))


def test_the_recorded_slice_with_the_paged_kernel_named():
    """The two fused steps' paged calls (98 events, 0.0923172 s: what
    `test_trace_reduce` finds by name) as `attn/core`: the reader finds the
    same seconds by scope."""
    from benchmark.metrics.readers import scope_share

    ctx = {'trace': recorded(),
           'scopes': {'jit__serve_step': [{n: 'attn/core' for n in PAGED}]}}
    steps = 0.3601396 + 0.3599923
    assert scope_share.read(
        ctx, 'attn/core', '^jit__serve_(step|window)', 'all',
        'pct_of_programs') == pytest.approx(100 * 0.0923172 / steps,
                                            abs=1e-3)
    assert scope_share.read(
        ctx, 'attn/core', '^jit__serve_step', 'all',
        'ms_per_execution') == pytest.approx(1e3 * 0.0923172 / 2, abs=1e-2)
    assert scope_share.read(ctx, 'attn/core', '^jit__paged_prefill', 'all',
                            'pct_of_programs') is None


def test_the_readers_that_were_there_read_as_before():
    """The recorded slice through the metric files that read the trace
    alone, as the parent of the scope readers read it (no bare window
    and no expert kernel in the slice: those read nothing)."""
    names = ('admit_dev_ms.latency', 'admit_dev_ms.batch',
             'admit_step_share_pct.latency', 'window_dev_ms.latency',
             'window_dev_ms.moe', 'device_idle_pct.latency',
             'expert_kernel_dev_share_pct.moe',
             'expert_matmul_dev_share_pct.moe')
    got = read(names, {'trace': recorded(), 'window_s': 0.46})
    assert got == pytest.approx({
        'admit_dev_ms.latency': 263.2011, 'admit_dev_ms.batch': 263.2011,
        'admit_step_share_pct.latency': 100.0,
        'device_idle_pct.latency': 6.662413043478832})


def test_the_map_of_a_live_engine():
    """With no map in the context the readers make one from the serving
    engine the process holds, at the geometries it warmed: its three
    programs, each instruction of them a path or `other`."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import tiny

    from paddle_tpu.aot import geometry
    from paddle_tpu.inference.serving import ServingEngine

    cfg = dict(tiny.TINY_SERVE_CFG, torch_dtype='float32')
    engine = ServingEngine(
        common.family(cfg).make_model(cfg, 3, 64), max_slots=2,
        block_size=16, max_context_len=64, decode_window=4,
        max_new_tokens=8, buckets=(32, 64))
    engine.warmup(geometries=geometry.for_serving_engine(
        engine, prompt_lens=[32]))
    ctx = {'trace': tr.Trace(ops={}, programs={}, host=[])}
    assert scopes.of_context(ctx) == {}
    got = ctx['scopes']
    assert {m: len(v) for m, v in got.items()} == {
        'jit__serve_step': 1, 'jit__serve_window': 1,
        'jit__paged_prefill': 1}
    paths = {p for maps in got.values() for m in maps for p in m.values()}
    assert paths <= set(scopes.VOCABULARY) | {scopes.OTHER,
                                              scopes.AMBIGUOUS}
    assert {'attn/qkv', 'attn/core', 'mlp', 'head', 'sample'} <= paths
    del engine
