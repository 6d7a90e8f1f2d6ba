"""The `afmoe` family (Trinity) through run.py's own code path at tiny
size: both drivers' rehearsal come out `correct: true`; a reference that
lacks the window comes out `correct: false`; the control (the reference a
step below bfloat16, in the program's place) does too, AT THIS SIZE; the
needed work of an expert layer counts the chip's share; and the reader of
the grouped products' device time finds them by name, inside the loop.

At the published widths the control does not: with 256 router outputs a
near-tie flips a fourth expert in a few per cent of (token, layer) pairs
in bfloat16 and in the control alike, and the widest gap of a run is a
flipped token's in both (PERF.md section 2, PR 28): the cell's one limit
catches another model, not another precision, until `run_cell` can hold a
second compared number.
"""
import copy

import jax
import numpy as np
import pytest

import tiny

from benchmark import run as bench_run
from benchmark.harness import common, model_flops

S, F = 'sliding_attention', 'full_attention'
TINY_AFMOE = {
    'name': 'tiny-afmoe', 'family': 'afmoe', 'hidden_size': 64,
    'intermediate_size': 128, 'moe_intermediate_size': 32,
    'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 16,
    'num_hidden_layers': 5, 'num_dense_layers': 1,
    'layer_types': [S, S, S, S, F], 'sliding_window': 8, 'vocab_size': 512,
    'rope_theta': 10000, 'rope_scaling': None, 'rms_norm_eps': 1e-5,
    'tie_word_embeddings': False, 'hidden_act': 'silu',
    'score_func': 'sigmoid', 'n_group': 1, 'mup_enabled': True,
    'num_experts': 4, 'expert_offset': 4, 'published': {'num_experts': 16},
    'num_experts_per_tok': 4, 'num_shared_experts': 1, 'route_norm': True,
    'route_scale': 2.448, 'torch_dtype': 'bfloat16'}
# wide enough that rounding moves first choices (test_faults' reasoning)
WIDE_AFMOE = dict(TINY_AFMOE, hidden_size=256, intermediate_size=512,
                  moe_intermediate_size=128, num_attention_heads=8,
                  num_key_value_heads=2, head_dim=32, vocab_size=4096)


@pytest.mark.parametrize('loop', ['open', 'closed'])
def test_serve_driver_rehearsal(loop):
    cell = copy.deepcopy(tiny.SERVE_CELL)
    traffic = tiny.OPEN if loop == 'open' else tiny.CLOSED
    if loop == 'closed':
        cell['end_to_end'] = ['serve_tok_s', 'setup_s']
        cell['geometry']['max_new_tokens'] = 9
    out = bench_run.execute(cell, TINY_AFMOE, traffic, tiny.env())
    assert out['correct'] is True, out['compared']
    assert out['attempted'] > 0 and out['failed'] == 0
    assert set(out['metrics']) == set(cell['end_to_end'])
    assert out['compared']['served_logit_gap']['value'] <= 0.05


def test_train_driver_rehearsal():
    """In float32 the program's loss, gradients and update are the
    reference's to rounding, routing included."""
    out = bench_run.execute(copy.deepcopy(tiny.TRAIN_CELL),
                            dict(TINY_AFMOE, torch_dtype='float32',
                                 num_hidden_layers=3,
                                 layer_types=[S, S, F]),
                            tiny.TRAIN, tiny.env(seed=2 ** 31 + 7))
    assert out['correct'] is True, out['compared']
    assert out['compared']['grad_proj_gap']['value'] < 1e-3


def test_the_control_is_not_correct_at_tiny_size():
    """Top 4 of 16 over a few hundred tokens: flips are rare enough here
    for one number to tell the precisions apart (see the module's note)."""
    cell = dict(copy.deepcopy(tiny.SERVE_CELL), check_requests=8,
                control='fp8', limits={'served_logit_gap': 0.13})
    out = bench_run.execute(cell, WIDE_AFMOE, tiny.OPEN, tiny.env(seed=9),
                            control=True)
    assert out['correct'] is True, out['compared']
    low = out['control']['fp8']
    assert low['correct'] is False, out
    gap = low['compared']['served_logit_gap']
    assert gap['value'] > gap['limit'] == 0.13
    assert gap['value'] >= 2 * out['compared']['served_logit_gap']['value']


def test_a_reference_without_the_window_is_not_correct(monkeypatch):
    """Contexts pass the window of 8, so the window layers really mask."""
    fam = common.family(TINY_AFMOE)
    forward = fam.reference.layer_forward
    monkeypatch.setattr(
        fam.reference, 'layer_forward',
        lambda cfg, lp, x, layer, quant=None: forward(
            dict(cfg, sliding_window=None), lp, x, layer, quant))
    jax.clear_caches()
    out = bench_run.execute(copy.deepcopy(tiny.SERVE_CELL), TINY_AFMOE,
                            tiny.OPEN, tiny.env())
    jax.clear_caches()
    assert out['correct'] is False
    gap = out['compared']['served_logit_gap']
    assert gap['value'] > gap['limit']


def test_counts_are_of_the_chips_share():
    fam = common.family(TINY_AFMOE)
    work = model_flops.Work(fam)
    h, m, d = 64, 32, 16
    attn = 3 * h * 4 * d + 2 * h * 2 * d            # q, gate, o; k, v
    assert fam.matmul_params(TINY_AFMOE, 0) == attn + 3 * h * 128
    # router over all 16, the shared expert, 4 picks x 4 held / 16
    assert fam.matmul_params(TINY_AFMOE, 1) == (
        attn + h * 16 + 3 * h * m + 3 * h * m)
    assert [fam.layer_like(TINY_AFMOE, l) for l in range(5)] == [0, 1, 1, 1, 4]
    assert fam.attn_keys(TINY_AFMOE, 1, 20) == 8
    assert fam.attn_keys(TINY_AFMOE, 4, 20) == 20
    assert list(fam.attn_keys(TINY_AFMOE, 2, np.array([3, 30]))) == [3, 8]
    log = [(20, 0, 1), (20, 1, 4)]
    flops, nbytes = work.paged_attn_needed(TINY_AFMOE, log)
    keys = sum(4 * min(20 + j, 8) + 20 + j for j in range(1, 5))
    assert flops == keys * 4 * 4 * d
    assert nbytes == keys * 2 * 2 * d * 2 + 4 * 5 * 2 * 4 * d * 2
    # the program counted no routing here: nothing to read, no error
    assert work.needed_expert_matmuls({'cfg': TINY_AFMOE}) == (0, 0)


def test_the_published_cut_is_the_issues():
    cfg = common.load('configs', 'trinity-large-preview')
    fam = common.family(cfg)
    params = sum(int(np.prod(s)) for l in range(cfg['num_hidden_layers'])
                 for s, _ in fam.layer_shapes(cfg, l).values())
    params += sum(int(np.prod(s)) for s, _ in fam.global_shapes(cfg).values())
    assert 8.6e9 < 2 * params < 8.7e9               # bf16 bytes, 8.64 GB
    assert fam.router_width(cfg) == 256 and cfg['num_experts'] == 32
    # attention 62.9 M + router 0.8 M + shared 28.3 M + 4 x 32/256 experts
    assert fam.matmul_params(cfg, 1) == (
        62914560 + 786432 + 28311552 + 28311552 // 2)
    assert [fam.layer_like(cfg, l) for l in range(5)] == [0, 1, 1, 1, 4]


def test_the_grouped_products_are_found_by_name_and_loop():
    """`named_ops`: a `ragged-dot-*` event counts by its name in the
    programs asked for, and with `in_loop` only where it began inside one
    of their `while` events; the roofline share is the needed work's least
    time over that device time."""
    import os
    import types

    from benchmark.harness import trace_reduce

    reader = common.load_module(os.path.join(
        common.BENCH, 'metrics', 'readers', 'named_ops.py'))
    step, prefill = 'jit__serve_step', 'jit__paged_prefill'
    ops = [('ragged-dot-none.1', 'custom-call', 0.10, 0.02, step),  # prefill
           ('while.7', 'while', 0.20, 0.50, step),
           ('ragged-dot-none.2', 'custom-call', 0.25, 0.03, step),
           ('ragged-dot-metadata.3', 'custom-call', 0.30, 0.01, step),
           ('fusion.9', 'fusion', 0.40, 0.10, step),
           ('while.1', 'while', 1.00, 0.10, prefill),
           ('ragged-dot-none.4', 'custom-call', 1.05, 0.04, prefill)]
    trace = trace_reduce.Trace(
        {'/device:TPU:0': ops},
        {'/device:TPU:0': [(step, 0.0, 0.8), (prefill, 1.0, 0.2)]}, [])
    ctx = {'trace': trace, 'chips': 1,
           'peak': types.SimpleNamespace(flops_bf16=1e3, hbm_bytes_s=1e2),
           'flops': types.SimpleNamespace(
               needed=lambda ctx: (10.0, 2.0), nothing=lambda ctx: (0, 0))}
    both = '^jit__(serve_step|paged_prefill)'
    assert reader.seconds_of(trace, '^ragged-dot-', both, False) == (
        pytest.approx(0.10), 4)
    assert reader.seconds_of(trace, '^ragged-dot-', '^jit__serve_step',
                             True) == (pytest.approx(0.04), 2)
    assert reader.read(ctx, '^ragged-dot-', both) == pytest.approx(10.0)
    # least time: the larger of 10 / 1e3 and 2 / 1e2 seconds, over 0.04
    assert reader.read(ctx, '^ragged-dot-', '^jit__serve_step', in_loop=True,
                       needed='needed') == pytest.approx(50.0)
    assert reader.read(ctx, '^ragged-dot-', '^jit__serve_step', in_loop=True,
                       needed='nothing') is None
    assert reader.read(ctx, '^no-such-op', both) is None
