"""The `mimo_v2` family (MiMo-V2.5) through run.py's own code path at tiny
size: the serve driver's rehearsal comes out `correct: true` on two kinds
of page, window pages recycled; each planted fault of the reference that
the configuration adds (`no_sink`, `no_window`) comes out `correct: false`,
and the rest read as they read; the needed work counts each kind of layer
at its own head counts and widths and the chip's share of the experts; the
published cut is the issue's.
"""
import copy

import jax
import numpy as np
import pytest

import tiny

from benchmark import run as bench_run
from benchmark.harness import common, model_flops

TINY_MIMO = {
    'name': 'tiny-mimo', 'family': 'mimo_v2', 'hidden_size': 64,
    'intermediate_size': 128, 'moe_intermediate_size': 32,
    'num_hidden_layers': 4, 'hybrid_layer_pattern': [0, 1, 1, 0],
    'moe_layer_freq': [0, 1, 1, 1], 'sliding_window': 8,
    'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 24,
    'v_head_dim': 16, 'rope_theta': 1e7, 'swa_num_attention_heads': 4,
    'swa_num_key_value_heads': 4, 'swa_head_dim': 24, 'swa_v_head_dim': 16,
    'swa_rope_theta': 1e4, 'partial_rotary_factor': 0.334,
    'attention_value_scale': 0.707, 'add_swa_attention_sink_bias': True,
    'add_full_attention_sink_bias': False, 'attention_bias': False,
    'layernorm_epsilon': 1e-5, 'vocab_size': 512, 'hidden_act': 'silu',
    'scoring_func': 'sigmoid', 'n_group': 1, 'topk_method': 'noaux_tc',
    'n_shared_experts': None, 'norm_topk_prob': True,
    'routed_scaling_factor': None, 'tie_word_embeddings': False,
    'rope_scaling': {'rope_type': 'default', 'type': 'default'},
    'n_routed_experts': 4, 'expert_offset': 4,
    'published': {'n_routed_experts': 16}, 'num_experts_per_tok': 4,
    'torch_dtype': 'bfloat16'}
# wide enough that a missing part moves first choices (test_faults' reasoning)
WIDE_MIMO = dict(TINY_MIMO, hidden_size=256, intermediate_size=512,
                 moe_intermediate_size=128, num_attention_heads=8,
                 num_key_value_heads=2, swa_num_attention_heads=8,
                 swa_num_key_value_heads=4, head_dim=48, swa_head_dim=48,
                 v_head_dim=32, swa_v_head_dim=32, vocab_size=4096)


@pytest.mark.parametrize('loop', ['open', 'closed'])
def test_serve_driver_rehearsal(loop):
    cell = copy.deepcopy(tiny.SERVE_CELL)
    traffic = tiny.OPEN if loop == 'open' else tiny.CLOSED
    if loop == 'closed':
        cell['end_to_end'] = ['serve_tok_s', 'setup_s']
        cell['geometry']['max_new_tokens'] = 9
    out = bench_run.execute(cell, TINY_MIMO, traffic, tiny.env())
    assert out['correct'] is True, out['compared']
    assert out['attempted'] > 0 and out['failed'] == 0
    assert set(out['metrics']) == set(cell['end_to_end'])
    assert out['compared']['served_logit_gap']['value'] <= 0.05


def served_gaps(monkeypatch, fault):
    """The cell's one compared number with `fault` planted in the float32
    reference (None: the reference as it is), at the wide tiny size."""
    fam = common.family(WIDE_MIMO)
    forward = fam.reference.layer_forward
    if fault is not None:
        assert fault in fam.reference.SERVE_FAULTS
        monkeypatch.setattr(
            fam.reference, 'layer_forward',
            lambda cfg, lp, x, layer, quant=None: forward(
                dict(cfg, fault=fault), lp, x, layer, quant))
    jax.clear_caches()
    cell = dict(copy.deepcopy(tiny.SERVE_CELL), check_requests=6)
    out = bench_run.execute(cell, WIDE_MIMO, tiny.OPEN, tiny.env(seed=9))
    jax.clear_caches()
    return out


def test_the_reference_as_it_is_finds_the_program_correct(monkeypatch):
    out = served_gaps(monkeypatch, None)
    assert out['correct'] is True, out['compared']


@pytest.mark.parametrize('fault', ['no_sink', 'no_window'])
def test_what_the_configuration_adds_is_not_correct_without_it(monkeypatch,
                                                               fault):
    """Contexts pass the window of 8 several times, and the sink (3 +
    normal) holds a visible share of a window layer's mass."""
    out = served_gaps(monkeypatch, fault)
    assert out['correct'] is False
    gap = out['compared']['served_logit_gap']
    assert gap['value'] > gap['limit']


@pytest.mark.parametrize('fault', ['full_theta_on_window', 'no_value_scale',
                                   'rotary_all_dims', 'choice_by_s'])
def test_the_other_planted_faults_read_a_number(monkeypatch, fault):
    """They move the reference's logits; whether one number catches them
    is read on the chip at the cell's own size (PERF.md section 2)."""
    out = served_gaps(monkeypatch, fault)
    assert np.isfinite(out['compared']['served_logit_gap']['value'])


def test_counts_are_of_each_kind_and_of_the_chips_share():
    fam = common.family(TINY_MIMO)
    work = model_flops.Work(fam)
    h, m = 64, 32
    full = h * 4 * 24 + h * 2 * (24 + 16) + 4 * 16 * h      # q; k, v; o
    swa = h * 4 * 24 + h * 4 * (24 + 16) + 4 * 16 * h
    assert fam.matmul_params(TINY_MIMO, 0) == full + 3 * h * 128
    # router over all 16, 4 picks x 4 held / 16, no shared expert
    assert fam.matmul_params(TINY_MIMO, 1) == swa + h * 16 + 3 * h * m
    assert fam.matmul_params(TINY_MIMO, 3) == full + h * 16 + 3 * h * m
    assert [fam.layer_like(TINY_MIMO, l) for l in range(4)] == [0, 1, 1, 3]
    assert fam.attn_keys(TINY_MIMO, 1, 20) == 8
    assert fam.attn_keys(TINY_MIMO, 3, 20) == 20
    assert list(fam.attn_keys(TINY_MIMO, 2, np.array([3, 30]))) == [3, 8]
    assert fam.cache_bytes_token(TINY_MIMO, 0) == 2 * (24 + 16) * 2
    assert fam.cache_bytes_token(TINY_MIMO, 1) == 4 * (24 + 16) * 2
    assert fam.attn_flops_key(TINY_MIMO, 1) == 2 * 4 * (24 + 16)
    log = [(20, 0, 1), (20, 1, 4)]
    flops, nbytes = work.paged_attn_needed(TINY_MIMO, log)
    ctxs = [20 + j for j in range(1, 5)]
    keys = {'full': sum(ctxs) * 2, 'swa': 8 * 4 * 2}        # two layers each
    assert flops == (keys['full'] + keys['swa']) * 2 * 4 * 40
    assert nbytes == (keys['full'] * 160 + keys['swa'] * 320
                      + 4 * 4 * 4 * 40 * 2)
    # the program counted no routing here: nothing to read, no error
    assert work.needed_expert_matmuls({'cfg': TINY_MIMO}) == (0, 0)
    # leaves: a sink a query head on window layers alone
    assert 'self_attn.attention_sink_bias' in fam.layer_shapes(TINY_MIMO, 1)
    assert 'self_attn.attention_sink_bias' not in fam.layer_shapes(
        TINY_MIMO, 3)


def test_the_published_cut_is_the_issues():
    cfg = common.load('configs', 'mimo-v2.5')
    fam = common.family(cfg)
    params = sum(int(np.prod(s)) for l in range(cfg['num_hidden_layers'])
                 for s, _ in fam.layer_shapes(cfg, l).values())
    params += sum(int(np.prod(s)) for s, _ in fam.global_shapes(cfg).values())
    assert 3.42e9 < params < 3.44e9                 # 6.87 GB in bfloat16
    assert fam.router_width(cfg) == 256 and cfg['n_routed_experts'] == 16
    # full attention 89.1 M + the dense SwiGLU 201.3 M
    assert fam.matmul_params(cfg, 0) == 89128960 + 201326592
    # window attention 94.4 M + router 1.05 M + 8 x 16/256 experts
    assert fam.matmul_params(cfg, 1) == (
        94371840 + 1048576 + 25165824 // 2)
    assert [fam.layer_like(cfg, l) for l in range(7)] == [0, 1, 1, 1, 1, 1, 6]
    assert fam.cache_bytes_token(cfg, 0) == 2560
    assert fam.cache_bytes_token(cfg, 1) == 5120
    assert fam.attn_flops_key(cfg, 1) == 2 * 64 * (192 + 128)
    assert fam.attn_keys(cfg, 1, 2000) == 128
    with open(common.ROOT + '/BENCHMARK.json') as f:
        import json
        bench = json.load(f)
    entry = next(c for c in bench['configs'] if c['name'] == 'mimo-v2.5')
    assert entry['reduced'] == cfg['reduced']
    assert cfg['published']['n_routed_experts'] == 256
