"""The reduction from trace to numbers, on a trace small enough to check
by hand and on a slice recorded on the chip (`recorded_trace.json`: 0.46 s
of `mistral7b_chat_steady`, PR 24: a standalone prefill and two fused
admit + decode steps; events under 0.1 ms dropped to keep it small)."""
import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

DEV = '/device:TPU:0'
RECORDED_BUSY = 0.4293529
PAGED = {f'closed_call.{n}' for n in range(398, 432, 3)}


def by_hand():
    return tr.Trace(
        ops={DEV: [('while.1', 'while', 0.0, 4.0, 'jit_step'),
                   ('fusion.1', 'fusion', 0.0, 1.0, 'jit_step'),
                   ('kernel.7', 'custom-call', 0.5, 1.5, 'jit_step'),
                   ('fusion.1', 'fusion', 3.0, 1.0, 'jit_step'),
                   ('kernel.7', 'custom-call', 6.0, 1.0, 'jit_other'),
                   ('copy.2', 'copy', 9.0, 0.5, '')]},
        programs={DEV: [('jit_step', 0.0, 4.0), ('jit_other', 6.0, 1.0)]},
        host=[('bench.step', 0.0, 4.2), ('bench.stamp', 4.2, 0.1),
              ('bench.step', 4.5, 4.0), ('serve.step', 4.6, 3.0)])


def test_busy_union_and_idle_share_by_hand():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]
    trace = by_hand()
    assert tr.busy_seconds(trace) == pytest.approx(4.0 + 1.0 + 0.5)
    assert tr.idle_share(trace, 10.0) == pytest.approx(0.45)
    with pytest.raises(ValueError):
        tr.busy_seconds(tr.Trace({}, {}, []))


def test_sums_per_program_and_per_instruction_by_hand():
    trace = by_hand()
    assert tr.program_times(trace, '^jit_step') == [4.0]
    assert tr.program_times(trace, '^jit_') == [4.0, 1.0]
    assert tr.program_times(trace, 'absent') == []
    assert tr.op_seconds(trace, {'kernel.7'}, '^jit_step$') == (1.5, 1)
    assert tr.op_seconds(trace, {'kernel.7'}, '^jit_') == (2.5, 2)
    assert tr.op_seconds(trace, {'absent'}, '') == (0.0, 0)
    # the loop is left out of the top list: its body's events are there
    assert tr.top_ops(trace, 2) == [['jit_step:fusion.1:fusion', 2.0],
                                    ['jit_step:kernel.7:custom-call', 1.5]]


def test_idle_gaps_are_named_by_the_innermost_span_by_hand():
    gaps = dict(tr.idle_gaps(by_hand()))
    # 4..6 idle: its middle lies in bench.step (from 4.5) and, inside
    # that, serve.step (from 4.6); 7..9: only bench.step is still open
    assert gaps == {'serve.step': pytest.approx(2.0),
                    'bench.step': pytest.approx(2.0)}


def test_instruction_names_as_the_profiler_writes_them():
    text = ('%closed_call.413 = bf16[16,1,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(s32[16]{0:T(128)S(1)} %get-tuple-element.4705), '
            'custom_call_target="tpu_custom_call"')
    assert tr.instruction(text) == ('closed_call.413', 'custom-call')
    assert tr.instruction('%while.7 = (s32[]{:T(128)}, bf16[16,8]{1,0}) '
                          'while((s32[]) %tuple.279), condition=%c') == (
        'while.7', 'while')


def test_recorded_slice_from_the_chip():
    with open(os.path.join(os.path.dirname(__file__),
                           'recorded_trace.json')) as f:
        trace = tr.Trace.from_json(json.load(f))
    assert [n for n, _, _ in trace.programs[DEV]] == [
        'jit__paged_prefill', 'jit__serve_step', 'jit__serve_step']
    assert tr.program_times(trace, '^jit__serve_step') == pytest.approx(
        [0.3601396, 0.3599923], abs=1e-6)
    assert tr.program_times(trace, '^jit__paged_prefill') == pytest.approx(
        [0.0694714], abs=1e-6)
    busy = tr.busy_seconds(trace)
    assert busy == pytest.approx(RECORDED_BUSY, abs=1e-4)
    assert tr.idle_share(trace, 0.46) == pytest.approx(
        1 - RECORDED_BUSY / 0.46, abs=1e-3)
    seconds, events = tr.op_seconds(trace, PAGED, '^jit__serve_(step|window)')
    assert events == 98 and seconds == pytest.approx(0.0923172, abs=1e-5)
    assert tr.op_seconds(trace, PAGED, '^jit__paged_prefill') == (0.0, 0)
    gaps = dict(tr.idle_gaps(trace))
    assert max(gaps, key=gaps.get) == 'bench.step'
    top = tr.breakdown(trace)
    assert len(top['device_ops']) == 10 and all(
        ':while' not in n for n, _ in top['device_ops'])



def test_the_step_share_and_the_gap_tail_readers():
    """The two readers that stand beside `itl_p95_ms`, through the files
    that name them: of three executions one is a bare window; zero gaps
    (a window's tokens arriving together) are left out of the tail."""
    from benchmark.harness import common

    trace = tr.Trace(ops={}, host=[], programs={DEV: [
        ('jit__serve_window', 0.0, 0.2), ('jit__serve_step', 0.2, 0.4),
        ('jit__paged_prefill', 0.6, 0.1), ('jit__serve_step', 0.7, 0.4)]})
    env = type('Env', (), {'per_layer': ['admit_step_share_pct.latency',
                                         'itl_step_p95_ms.latency']})
    gaps = [0.0] * 70 + [0.2] * 9 + [0.4]
    got = common.read_metrics(env, {'trace': trace, 'gaps': gaps})
    assert got['admit_step_share_pct.latency']['value'] == pytest.approx(
        200 / 3)
    assert got['itl_step_p95_ms.latency']['value'] == pytest.approx(310.0)
    nothing = tr.Trace(ops={}, host=[], programs={DEV: []})
    assert common.read_metrics(env, {'trace': nothing, 'gaps': [0.0]}) == {}
