"""The seam between the harness and a model's shape: a configuration names
its family, and a second family whose files stand under `benchmark/tests/`
alone (`families/layered.py`, `reference/families/layered.py`) goes
through both drivers by the same lookup. Its layers are described by their
index, so a table that is wrong on the odd layers only has to come out
`correct: false`: the index reaches shapes, reference and counts. And what
must not move for the two configurations the benchmark has: the needed
work on a fixed request log and the seeded weights, to the parent's bits.
"""
import copy
import os

import jax
import numpy as np
import pytest

import tiny

from benchmark import run as bench_run
from benchmark.harness import common, model_flops, weights

HERE = os.path.dirname(os.path.abspath(__file__))
SOUND, PLANTED = ['full', 'full'], ['full', 'self']
LOG = [(256, 0, 1), (256, 1, 8), (256, 9, 8), (1000, 0, 1), (1000, 1, 7),
       (131, 0, 2), (131, 2, 8), (640, 17, 3)]


@pytest.fixture
def layered(monkeypatch):
    monkeypatch.setattr(common, 'FAMILIES', HERE)
    return lambda cfg, types: dict(cfg, name='tiny-layered',
                                   family='layered', layer_types=types)


@pytest.mark.parametrize('types', [SOUND, PLANTED], ids=['sound', 'planted'])
@pytest.mark.parametrize('driver', ['serve', 'train'])
def test_a_family_of_test_files_alone_runs_both_drivers(layered, driver,
                                                        types):
    if driver == 'serve':
        out = bench_run.execute(
            copy.deepcopy(tiny.SERVE_CELL),
            layered(tiny.TINY_SERVE_CFG, types), tiny.OPEN, tiny.env())
    else:
        out = bench_run.execute(
            copy.deepcopy(tiny.TRAIN_CELL),
            layered(tiny.TINY_TRAIN_CFG, types), tiny.TRAIN,
            tiny.env(seed=2 ** 31 + 7))
    assert out['attempted'] > 0 and out['failed'] == 0
    assert out['correct'] is (types is SOUND), out['compared']
    if types is PLANTED:
        number = ('served_logit_gap' if driver == 'serve'
                  else 'grad_proj_gap')
        assert (out['compared'][number]['value']
                > out['compared'][number]['limit'])


def test_counts_go_through_the_layers_kinds(layered):
    llama = model_flops.Work(common.load_module(os.path.join(
        common.BENCH, 'families', 'llama.py')))
    sound, planted = (layered(tiny.TINY_SERVE_CFG, t)
                      for t in (SOUND, PLANTED))
    work = model_flops.Work(common.family(sound))
    for cfg in (sound, planted):
        assert work.train_flops(cfg, 2, 32) <= llama.train_flops(cfg, 2, 32)
    for name, args in (('serve_flops', (LOG,)), ('paged_attn_needed', (LOG,)),
                       ('train_flops', (2, 32)),
                       ('flash_attn_flops', (32, True)),
                       ('flash_attn_bytes', (32, True))):
        whole = getattr(llama, name)(sound, *args)
        assert getattr(work, name)(sound, *args) == whole
        assert np.all(np.less_equal(getattr(work, name)(planted, *args),
                                    whole))
    # a `self` layer attends one key whatever the context
    d = tiny.TINY_SERVE_CFG
    one = 4 * d['num_attention_heads'] * d['head_dim']
    assert (llama.decode_flops(sound, 100) - work.decode_flops(planted, 100)
            == 99 * one)
    # a count of the family's own file comes before the shared module's
    ctx = {'cfg': sound, 'deliveries': LOG}
    assert work.needed_full_attn(ctx) == work.needed_paged_attn(ctx)
    half = work.needed_full_attn(dict(ctx, cfg=planted))
    assert [2 * x for x in half] == list(work.needed_paged_attn(ctx))
    with pytest.raises(AttributeError, match='needed_nothing'):
        work.needed_nothing


def test_a_configuration_names_a_family_that_has_files(layered):
    with pytest.raises(SystemExit, match='tiny-orphan.*names no family'):
        common.family({'name': 'tiny-orphan', 'hidden_size': 64})
    # the benchmark's own families are not looked for beside the tests'
    with pytest.raises(SystemExit, match=r"'llama'.*tiny.*has no .*llama.py"):
        common.family(tiny.TINY_SERVE_CFG)
    with pytest.raises(SystemExit, match=r"'retention'.*has no "
                       r".*families.retention.py and no "
                       r".*reference.families.retention.py"):
        common.family(layered(tiny.TINY_SERVE_CFG, SOUND)
                      | {'family': 'retention'})


def test_a_leaf_name_may_carry_an_index():
    """`experts.3.up_proj` of layer 5: the fold takes any name, and two
    experts of one layer get different values."""
    fam = common.family(tiny.TINY_SERVE_CFG)
    base = weights.base_key(3)
    a, b = (np.asarray(weights.make_leaf(fam, base, 5, name, (4, 4),
                                         jax.numpy.float32))
            for name in ('mlp.experts.3.up_proj', 'mlp.experts.4.up_proj'))
    assert not np.array_equal(a, b)


# Computed on the parent (PR 26, c670676) by the functions this PR moved:
# `harness/model_flops.py` over `cfg` alone, `weights.make_leaf` at seed 3
# (the first 8 values' bits: bfloat16 as uint16, float32 as uint32).
K_PROJ = [15029, 48302, 47875, 15416, 48032, 15561, 15479, 15552]
NORM = [1065914003, 1065532002, 1065448082, 1066369177, 1066075466,
        1065376003, 1065432481, 1065597050]
PINS = {
    'mistral-7b-v0.3': {
        'serve_flops': 7563059658752,
        'paged_attn_needed': (2842558464, 717520896),
        'train_flops': 72567767433216,
        'needed_flash_attn': (24739011624960, 15099494400),
        'leaves': {(2, 'self_attn.k_proj'): K_PROJ,
                   (1, 'post_attention_layernorm.weight'): NORM,
                   (-1, 'lm_head'): [48280, 47525, 15287, 48338, 15464,
                                     15495, 15506, 15239]}},
    'qwen2.5-3b': {
        'serve_flops': 1366129836032,
        'paged_attn_needed': (710639616, 90550272),
        'train_flops': 20248623316992,
        'needed_flash_attn': (6184752906240, 3397386240),
        'leaves': {(2, 'self_attn.k_proj'): K_PROJ,
                   (1, 'post_attention_layernorm.weight'): NORM,
                   (-1, 'embed_tokens'): [48026, 15598, 15511, 48491, 48165,
                                          15208, 15508, 47513]}}}


@pytest.mark.parametrize('name', sorted(PINS))
def test_needed_work_and_weights_are_the_parents(name):
    cfg, pins = common.load('configs', name), PINS[name]
    fam = common.family(cfg)
    assert fam.__name__.endswith(os.path.join('families', 'llama.py'))
    work = model_flops.Work(fam)
    ctx = {'cfg': cfg, 'deliveries': LOG, 'train_steps': 5, 'batch': 1,
           'seq': 4096}
    assert work.serve_flops(cfg, LOG) == pins['serve_flops']
    assert work.paged_attn_needed(cfg, LOG) == pins['paged_attn_needed']
    assert work.needed_paged_attn(ctx) == pins['paged_attn_needed']
    assert work.train_flops(cfg, 1, 4096) == pins['train_flops']
    assert work.needed_flash_attn(ctx) == pins['needed_flash_attn']
    base = weights.base_key(3)
    for (layer, leaf), bits in pins['leaves'].items():
        shapes = (fam.global_shapes(cfg) if layer < 0
                  else fam.layer_shapes(cfg, layer))
        shape, dtype = shapes[leaf]
        got = np.asarray(jax.jit(lambda b: weights.make_leaf(
            fam, b, layer, leaf, shape, dtype).reshape(-1)[:8])(base))
        width = np.uint16 if got.dtype.itemsize == 2 else np.uint32
        assert got.view(width).tolist() == bits, (layer, leaf)
