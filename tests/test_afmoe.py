"""AFMoE (`models/afmoe.py`, `distributed.moe.ExpertShare`) against the
benchmark's plain float32 reference (`benchmark/reference/families/
afmoe.py`), at tiny sizes in float32, where the two agree to rounding:

  - prefill and paged decode through `ServingEngine`, teacher-forced
    through the reference: the served tokens' logits, with a window smaller
    than the context, so window layers really mask and full layers really
    lack RoPE; and four faults planted in the program fail that comparison;
  - the shares add up: eight ranks' shares of one expert layer, the shared
    expert counted once, are the uncut layer;
  - the router against a case worked by hand.
"""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import common, weights  # noqa: E402
from benchmark.reference import serve_ref  # noqa: E402
from paddle_tpu.distributed import moe  # noqa: E402
from paddle_tpu.inference import serving  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.models import afmoe  # noqa: E402

SLIDING, FULL = afmoe.SLIDING, afmoe.FULL
CFG = {
    'name': 'tiny-afmoe', 'family': 'afmoe', 'hidden_size': 64,
    'intermediate_size': 128, 'moe_intermediate_size': 32,
    'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 16,
    'num_hidden_layers': 3, 'num_dense_layers': 1,
    'layer_types': [SLIDING, SLIDING, FULL], 'sliding_window': 8,
    'vocab_size': 256, 'rope_theta': 10000, 'rope_scaling': None,
    'rms_norm_eps': 1e-5, 'tie_word_embeddings': False, 'hidden_act': 'silu',
    'score_func': 'sigmoid', 'n_group': 1, 'mup_enabled': True,
    'num_experts': 4, 'expert_offset': 4, 'published': {'num_experts': 16},
    'num_experts_per_tok': 4, 'num_shared_experts': 1, 'route_norm': True,
    'route_scale': 2.448, 'torch_dtype': 'float32'}
SEED, EXACT = 11, 2e-4


def served_gap(mutate=None):
    """The widest gap of a served token's logit below the reference's best,
    over two requests whose contexts (to 39) pass the window (8)."""
    for program in (serving._serve_step, serving._serve_window,
                    serving._paged_prefill):
        program.clear_cache()           # a planted fault has to be traced
    fam = common.family(CFG)
    model = fam.make_model(CFG, SEED, 64)
    if mutate is not None:
        mutate(model)
    engine = ServingEngine(model, max_slots=2, block_size=4,
                           max_context_len=64, decode_window=4,
                           max_new_tokens=12, buckets=(32,))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, CFG['vocab_size'], n).astype(np.int32)
               for n in (27, 20)]
    outs = [np.asarray(o) for o in engine.serve(prompts)]
    assert all(len(o) == len(p) + 12 for o, p in zip(outs, prompts))
    return serve_ref.served_gaps(
        fam, CFG, SEED, [(p, o[len(p):]) for p, o in zip(prompts, outs)],
        64)['served_gap']


def test_served_tokens_are_the_references_first_choices():
    from paddle_tpu.observability.tracing import TRACER

    TRACER.clear()
    assert served_gap() < EXACT
    # what the windows routed came back with their one host read: 2 x 12
    # committed tokens through 2 expert layers, 4 picks each, 4 of 16 held
    routed = [e['args'] for e in TRACER.events()
              if e['name'] == 'serve.routing']
    total = {k: sum(a[k] for a in routed) for k in moe.ROUTING_FIELDS}
    assert total['picks_total'] == 2 * 12 * 2 * 4
    assert 0 < total['picks_local'] < total['picks_total']
    assert 0 < total['experts_hit'] <= 4 * total['layer_steps']
    assert 2 * 12 <= total['layer_steps'] <= 2 * 2 * 12     # in step or not


def rope_on_the_full_layer(model):
    model.layers[2].self_attn.sliding = True        # its window stays None


def window_off_by_one(model):
    for layer in model.layers[:2]:
        layer.self_attn.sliding_window += 1


def no_mup_scale(model):
    model.config.mup_enabled = False


@pytest.mark.parametrize('fault', [rope_on_the_full_layer, window_off_by_one,
                                   no_mup_scale, 'no_attention_gate'])
def test_a_planted_fault_is_not_the_reference(fault, monkeypatch):
    if fault == 'no_attention_gate':
        monkeypatch.setattr(afmoe, '_gated', lambda out, gate: out)
        fault = None
    assert served_gap(fault) > 50 * EXACT


def test_eight_shares_add_up_to_the_uncut_layer():
    fam = common.family(CFG)
    whole = dict(CFG, num_experts=16, expert_offset=0)
    lp = weights.make_layer(fam, weights.base_key(SEED), whole, 1, 1)
    m = jax.random.normal(jax.random.PRNGKey(3), (2, 9, CFG['hidden_size']))
    want = fam.reference.experts(whole, lp, m, None)
    shared = fam.reference.swiglu(m, lp['mlp.shared_gate'],
                                  lp['mlp.shared_up'],
                                  lp['mlp.shared_down'], None)
    total, local = 0.0, 0.0
    for rank in range(8):
        share = moe.ExpertShare(
            64, 32, 16, 4, experts_held=2, expert_offset=2 * rank,
            shared_intermediate=32, route_scale=CFG['route_scale'])
        share.router, share.expert_bias = lp['mlp.router'], lp['mlp.expert_bias']
        for name in ('shared_gate', 'shared_up', 'shared_down'):
            setattr(share, name, lp[f'mlp.{name}'])
        for name in ('w_gate', 'w_up', 'w_down'):
            setattr(share, name, lp[f'mlp.{name}'][2 * rank:2 * rank + 2])
        with moe.routing_counts() as counts:
            part = share(m)
        total = total + part - shared           # the shared expert once
        picks_total, picks_local = np.asarray(counts.total())[:2]
        assert picks_total == 2 * 9 * 4
        local += picks_local
        # one share is what the reference gives for the same cut
        cut = dict(CFG, num_experts=2, expert_offset=2 * rank)
        cut_lp = dict(lp, **{f'mlp.{n}': lp[f'mlp.{n}'][2 * rank:2 * rank + 2]
                             for n in ('w_gate', 'w_up', 'w_down')})
        np.testing.assert_allclose(
            part, fam.reference.experts(cut, cut_lp, m, None), atol=1e-5)
    assert local == 2 * 9 * 4                   # every pick is some rank's
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 1e-3


def test_the_router_chooses_by_s_plus_b_and_weighs_by_s():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 1.0, 0.0])
    s = [1 / (1 + math.exp(-x)) for x in (2.0, 1.0, 0.5, 0.0, -1.0, -2.0)]
    # s + b puts expert 4 (0.269 + 1) first, then 0 and 1; 2 is left out
    w, idx = moe.sigmoid_topk_gates(logits, bias, 3, True, 2.448)
    assert idx.tolist() == [[4, 0, 1]]
    chosen = s[4] + s[0] + s[1]
    np.testing.assert_allclose(
        w[0], [2.448 * s[4] / chosen, 2.448 * s[0] / chosen,
               2.448 * s[1] / chosen], rtol=1e-6)
    raw, _ = moe.sigmoid_topk_gates(logits, bias, 3, False, 1.0)
    np.testing.assert_allclose(raw[0], [s[4], s[0], s[1]], rtol=1e-6)
    # with no bias the choice is by s alone
    assert moe.sigmoid_topk_gates(logits, 0 * bias, 3)[1].tolist() == [
        [0, 1, 2]]
