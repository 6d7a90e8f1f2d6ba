"""`correct` has to come out false when the timed path is broken
underneath, and the control (the reference at the precision below the
configuration's, in the program's place) has to come out as not correct
by the cell's own limits, as has every planted fault. These drive the rest
of a run, the harness's look for a chip skipped, at a size the CPU holds.
"""
import copy

import jax
import numpy as np
import pytest

import tiny

from benchmark import run as bench_run
from benchmark.harness import common

# wide enough that rounding flips first choices; still seconds on a CPU.
# At this size int8 reads only twice what bfloat16 does, so the test's
# control is the other step below bfloat16, fp8.
WIDE_SERVE_CFG = dict(tiny.TINY_SERVE_CFG, hidden_size=256,
                      intermediate_size=512, num_attention_heads=8,
                      num_key_value_heads=2, head_dim=32,
                      num_hidden_layers=3, vocab_size=4096)


@pytest.fixture(autouse=True)
def fresh_traces():
    """A patched method is only traced if nothing cached stands in."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.inference.serving import ServingEngine

    step = ServingEngine.step

    def altered(self):
        live = [r for r in self._live.values() if r.generated]
        finished = step(self)
        for req in live[:1]:
            req.generated[-1] = (req.generated[-1] + 7) % 512
        return finished

    monkeypatch.setattr(ServingEngine, 'step', altered)
    out = bench_run.execute(copy.deepcopy(tiny.SERVE_CELL),
                            tiny.TINY_SERVE_CFG, tiny.OPEN, tiny.env(seed=5))
    assert out['correct'] is False
    gap = out['compared']['served_logit_gap']
    assert gap['value'] > gap['limit']


def test_the_serve_control_is_not_correct():
    cell = dict(copy.deepcopy(tiny.SERVE_CELL), check_requests=8,
                control='fp8', limits={'served_logit_gap': 0.03})
    out = bench_run.execute(cell, WIDE_SERVE_CFG, tiny.OPEN,
                            tiny.env(seed=9), control=True)
    assert out['correct'] is True, out['compared']
    low = out['control']['fp8']
    assert low['correct'] is False, out
    gap = low['compared']['served_logit_gap']
    assert gap['value'] > gap['limit'] == cell['limits']['served_logit_gap']
    assert gap['value'] >= 3 * max(
        out['compared']['served_logit_gap']['value'], 1e-3), out


def _train(**env):
    return bench_run.execute(copy.deepcopy(tiny.TRAIN_CELL),
                             tiny.TINY_TRAIN_CFG, tiny.TRAIN,
                             tiny.env(**env))


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from paddle_tpu.optimizer import AdamW

    monkeypatch.setattr(AdamW, 'apply_gradients',
                        lambda self, model, grads, state=None, lr=None:
                        (model, state))
    out = _train(seed=4)
    assert out['correct'] is False
    assert out['compared']['change_norm_gap']['value'] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    fam = common.family(tiny.TINY_TRAIN_CFG)
    make_model = fam.make_model

    def halved(cfg, seed, max_positions):
        model = make_model(cfg, seed, max_positions)
        loss = type(model).loss
        monkeypatch.setattr(
            type(model), 'loss', lambda self, input_ids, labels=None: loss(
                self, input_ids[:, :input_ids.shape[1] // 2 + 1]))
        return model

    monkeypatch.setattr(fam, 'make_model', halved)
    out = _train(seed=4)
    assert out['correct'] is False
    gap = out['compared']['grad_norm_gap']
    assert gap['value'] > gap['limit']


def test_dropped_bias_gradients_are_not_correct(monkeypatch):
    """Small leaves, wholly wrong: the q, k and v biases' gradients never
    reach the optimizer."""
    from paddle_tpu.optimizer import AdamW

    apply = AdamW.apply_gradients

    def dropped(self, model, grads, state=None, lr=None):
        grads = jax.tree_util.tree_map_with_path(
            lambda p, g: jax.numpy.zeros_like(g)
            if jax.tree_util.keystr(p).endswith('_bias') else g, grads)
        return apply(self, model, grads, state, lr)

    monkeypatch.setattr(AdamW, 'apply_gradients', dropped)
    out = _train(seed=4)
    assert out['correct'] is False
    gap = out['compared']['grad_proj_gap']
    assert gap['value'] > gap['limit']


def test_the_train_control_and_every_planted_fault_are_not_correct():
    out = bench_run.execute(copy.deepcopy(tiny.TRAIN_CELL),
                            tiny.TINY_TRAIN_CFG, tiny.TRAIN,
                            tiny.env(seed=4), control=True)
    assert out['correct'] is True, out['compared']
    assert set(out['control']) == {'fp8', 'half_batch', 'frozen',
                                   'no_bias_grad'}
    for name, judged in out['control'].items():
        assert judged['correct'] is False, (name, judged)
    program = {k: v['value'] for k, v in out['compared'].items()}
    read = lambda name, number: out['control'][name]['compared'][  # noqa: E731
        number]['value']
    # rounding to fp8 is of second order in the norms and of first order
    # in the probed difference: that number is the control's to fail
    assert read('fp8', 'grad_proj_gap') >= 3 * program['grad_proj_gap']
    assert read('half_batch', 'grad_norm_gap') >= 10 * program[
        'grad_norm_gap']
    assert read('frozen', 'change_norm_gap') == pytest.approx(1.0)
    assert read('no_bias_grad', 'grad_proj_gap') >= 10 * program[
        'grad_proj_gap']
    assert np.isfinite(read('fp8', 'grad_norm_gap'))
