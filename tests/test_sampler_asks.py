"""The serving sampler does the work its live rows ask for (PR 31).

`_sample_rows`, `_sample_rows_dist`, `_filtered_dist` and
`filter_logits_batched` skip, on the device and under `lax.cond`, the
filters and the draw no live row asks for. No benchmark cell has a
sampled row, so these tests hold the sampled branch:

  - against the UNCONDITIONAL formulas the functions had before (kept
    below as plain `jax.numpy`), tokens and sampled rows' distributions
    are BIT-equal over every mix of greedy / temperature / top-k /
    nucleus rows;
  - through a tiny `ServingEngine`, a sampled request's stream is the
    same whichever rows sit beside it, a greedy request that carries
    filters decodes as a plain greedy one, and a batch that turns from
    all greedy to sampled retraces nothing;
  - in the compiled text of the serve dispatches every `sort` sits in a
    computation reached through a `conditional`;
  - `serve.dispatch` counts the live rows that sample and that filter.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import aot
from paddle_tpu import observability as obs
from paddle_tpu.inference import serving as srv
from paddle_tpu.inference.engine import total_traces
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.generation import filter_logits_batched
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

K, V = 6, 257


# -- the parent's formulas: every branch computed for every row ----------

def _old_filter(logits, top_k, top_p):
    vocab = logits.shape[-1]
    k = jnp.clip(jnp.asarray(top_k, jnp.int32), 1, vocab)
    srt = jnp.sort(logits, axis=-1)
    kth = jnp.take_along_axis(srt, (vocab - k)[:, None], axis=-1)
    logits = jnp.where((jnp.asarray(top_k, jnp.int32) > 0)[:, None],
                       jnp.where(logits < kth, -jnp.inf, logits), logits)
    tp = jnp.asarray(top_p, jnp.float32)
    sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), -1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < tp[:, None], axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx, axis=-1)
    nucleus = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jnp.where((tp < 1.0)[:, None], nucleus, logits)


def _old_filtered_dist(logits, temp, topk, topp):
    lg = logits.astype(jnp.float32)
    safe_t = jnp.where(temp > 0, temp, 1.0)
    return jax.nn.softmax(_old_filter(lg / safe_t[:, None], topk, topp), -1)


def _old_sample_rows_dist(logits, temp, topk, topp, seed, gen):
    keys = srv._row_keys(seed, gen, srv._SUB_PROPOSE)
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temp > 0, temp, 1.0)
    f = _old_filter(lg / safe_t[:, None], topk, topp)
    sampled = jax.vmap(jax.random.categorical)(keys, f).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy), jax.nn.softmax(f, -1)


# -- the mixes: (temperature, top_k, top_p) a row -------------------------

G = (0.0, 0, 1.0)
MIXES = {
    'all_greedy': [G] * K,
    'temperature_only': [(0.7, 0, 1.0), (1.0, 0, 1.0), (1.3, 0, 1.0)] * 2,
    'top_k_only': [(1.0, 5, 1.0), (0.8, 40, 1.0), (1.2, 1, 1.0)] * 2,
    'top_p_only': [(1.0, 0, 0.9), (0.8, 0, 0.5), (1.2, 0, 0.05)] * 2,
    'both': [(1.0, 20, 0.9), (0.6, 7, 0.7), (1.4, 100, 0.3)] * 2,
    'greedy_beside_sampled': [G, (0.9, 0, 1.0), G, (1.1, 12, 1.0),
                              (0.7, 0, 0.8), (1.0, 9, 0.6)],
    'greedy_carrying_filters': [(0.0, 50, 0.5), (0.0, 50, 0.5),
                                (0.9, 50, 0.5), (0.0, 3, 1.0),
                                (0.0, 0, 0.2), (1.0, 0, 1.0)],
    'top_k_over_vocab': [(1.0, V + 1, 1.0), (0.8, 10_000, 0.9),
                         (0.0, 10_000, 1.0), (1.0, V, 1.0),
                         (1.2, V - 1, 1.0), G],
}


def _batch(mix, rows_live=None):
    rng = np.random.default_rng(sorted(MIXES).index(mix))
    logits = jnp.asarray(rng.normal(0, 3, (K, V)), jnp.bfloat16)
    temp, topk, topp = (jnp.asarray(col, dt) for col, dt in zip(
        zip(*MIXES[mix]), (jnp.float32, jnp.int32, jnp.float32)))
    seed = jnp.asarray(rng.integers(0, 2**32, (K,)), jnp.uint32)
    gen = jnp.asarray(rng.integers(0, 50, (K,)), jnp.int32)
    live = jnp.ones((K,), bool) if rows_live is None else rows_live
    return logits, temp, topk, topp, seed, gen, live


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _same_bits(a, b):
    return np.array_equal(_bits(a), _bits(b))


@jax.jit
def _new_rows(logits, temp, topk, topp, seed, gen, live):
    return srv._sample_rows(logits, srv._row_asks(temp, topk, topp, live),
                            seed, gen)


@jax.jit
def _new_rows_dist(logits, temp, topk, topp, seed, gen, live):
    return srv._sample_rows_dist(
        logits, srv._row_asks(temp, topk, topp, live), seed, gen)


@jax.jit
def _new_filtered_dist(logits, temp, topk, topp, live):
    return srv._filtered_dist(logits,
                              srv._row_asks(temp, topk, topp, live))


@pytest.mark.parametrize('mix', sorted(MIXES))
class TestBitEqualToTheUnconditionalSampler:
    def test_sample_rows(self, mix):
        logits, temp, topk, topp, seed, gen, live = _batch(mix)
        want, _ = jax.jit(_old_sample_rows_dist)(logits, temp, topk, topp,
                                                 seed, gen)
        got = _new_rows(logits, temp, topk, topp, seed, gen, live)
        assert got.dtype == jnp.int32
        assert np.array_equal(got, want)

    def test_sample_rows_dist(self, mix):
        logits, temp, topk, topp, seed, gen, live = _batch(mix)
        want_tok, want_pd = jax.jit(_old_sample_rows_dist)(
            logits, temp, topk, topp, seed, gen)
        tok, pd = _new_rows_dist(logits, temp, topk, topp, seed, gen, live)
        assert np.array_equal(tok, want_tok)
        rows = np.asarray(temp) > 0          # a greedy row's is never read
        assert _same_bits(np.asarray(pd)[rows], np.asarray(want_pd)[rows])
        assert np.allclose(np.asarray(pd).sum(-1), 1.0, atol=1e-5)

    def test_filtered_dist(self, mix):
        logits, temp, topk, topp, _, _, live = _batch(mix)
        want = jax.jit(_old_filtered_dist)(logits, temp, topk, topp)
        got = _new_filtered_dist(logits, temp, topk, topp, live)
        rows = np.asarray(temp) > 0
        assert _same_bits(np.asarray(got)[rows], np.asarray(want)[rows])

    def test_filter_logits_batched_every_row(self, mix):
        """The filter itself drops nothing it used to compute for a row
        that asks: every row of every mix, greedy rows' params as
        given, with the scalars handed in or worked out inside."""
        logits, _, topk, topp, _, _, _ = _batch(mix)
        lg = logits.astype(jnp.float32)
        want = jax.jit(_old_filter)(lg, topk, topp)
        assert _same_bits(jax.jit(filter_logits_batched)(lg, topk, topp),
                          want)
        assert _same_bits(
            jax.jit(lambda a, k, p: filter_logits_batched(
                a, k, p, any_top_k=jnp.any(k > 0),
                any_top_p=jnp.any(p < 1.0)))(lg, topk, topp), want)


def test_a_row_that_is_not_live_asks_for_nothing():
    """An empty slot or a row mid chunked prefill rides the window
    frozen: its params are neutralised with the greedy rows', so it
    cannot switch the sampler on."""
    _, temp, topk, topp, _, _, _ = _batch('both')
    asks = srv._row_asks(temp, topk, topp, jnp.zeros((K,), bool))
    assert not bool(asks.any_sampled | asks.any_top_k | asks.any_top_p)
    assert not np.asarray(asks.temp).any()
    one = srv._row_asks(temp, topk, topp, jnp.arange(K) == 2)
    assert bool(one.any_sampled & one.any_top_k & one.any_top_p)
    assert np.array_equal(np.asarray(one.topk) > 0, np.arange(K) == 2)


def test_greedy_rows_carrying_filters_switch_no_filter_on():
    temp, topk, topp = (jnp.asarray(c) for c in zip(
        *[(0.0, 50, 0.5), (0.0, 3, 1.0), (1.0, 0, 1.0)]))
    asks = srv._row_asks(temp, topk.astype(jnp.int32), topp,
                         jnp.ones((3,), bool))
    assert bool(asks.any_sampled)
    assert not bool(asks.any_top_k) and not bool(asks.any_top_p)


# -- through a tiny engine ------------------------------------------------

_MODELS = {}


def _model(seed=0):
    if seed not in _MODELS:
        pt.seed(seed)
        _MODELS[seed] = LlamaForCausalLM(llama_tiny(
            vocab_size=96, hidden_size=64, layers=2, heads=4, kv_heads=2,
            max_pos=256))
    return _MODELS[seed]


def _engine(**kw):
    base = dict(max_slots=3, block_size=8, max_new_tokens=10,
                eos_token_id=None)
    base.update(kw)
    return ServingEngine(_model(), **base)


def _prompts(n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 96, size=int(rng.integers(4, 14)))
            .astype(np.int32) for _ in range(n)]


SAMPLED = {
    'temperature_only': dict(temperature=0.9),
    'top_k': dict(temperature=1.1, top_k=12),
    'top_p': dict(temperature=0.8, top_p=0.7),
    'both': dict(temperature=1.0, top_k=30, top_p=0.85),
}


@pytest.mark.parametrize('kind', sorted(SAMPLED))
def test_sampled_stream_same_beside_greedy_or_sampled_rows(kind):
    ps = _prompts(3, seed=41)
    params = dict(SAMPLED[kind], seed=7)
    outs = []
    for mates in (None, [{}, {}], [dict(temperature=1.3, top_k=5, seed=1),
                                  dict(temperature=0.6, top_p=0.5, seed=2)]):
        e = _engine()
        rid = e.submit(ps[0], **params)
        for p, kw in zip(ps[1:], mates or ()):
            e.submit(p, **kw)
        e.run()
        outs.append(np.asarray(e.result(rid)))
    assert np.array_equal(outs[0], outs[1])      # beside greedy rows
    assert np.array_equal(outs[0], outs[2])      # beside sampled rows


@pytest.mark.parametrize('draft', [False, True], ids=['plain', 'spec'])
def test_greedy_request_carrying_filters_decodes_as_plain_greedy(draft):
    ps = _prompts(2, seed=43)
    kw = dict(draft=_model(1), num_draft_tokens=3) if draft else {}
    want = _engine(**kw).serve(ps)
    e = _engine(**kw)
    rids = [e.submit(ps[0], top_k=50, top_p=0.5),
            e.submit(ps[1], top_k=1)]
    e.run()
    assert all(np.array_equal(e.result(r), w) for r, w in zip(rids, want))


def test_all_greedy_batch_then_sampled_rows_zero_retraces():
    """The sampled side of every `cond` is compiled with the first
    all-greedy window: a batch that starts to sample runs the same
    program."""
    e = _engine()
    ps = _prompts(6, seed=47)
    e.serve(ps[:3])                              # greedy rows only
    t0 = total_traces()
    e.submit(ps[3], temperature=0.9, seed=3)
    e.submit(ps[4], temperature=1.0, top_k=8, top_p=0.9, seed=4)
    e.submit(ps[5])
    e.run()
    assert total_traces() - t0 == 0


def test_dispatch_counts_the_rows_that_sample_and_filter():
    e = _engine(max_slots=4, max_new_tokens=6)
    e.serve(_prompts(2, seed=53))                # compile outside the ring
    obs.TRACER.clear()
    ps = _prompts(4, seed=59)
    e.submit(ps[0])
    e.submit(ps[1], top_k=50, top_p=0.5)         # greedy: asks for nothing
    e.submit(ps[2], temperature=0.8)
    e.submit(ps[3], temperature=1.0, top_k=5)
    e.run()
    got = [(a['kind'], a['live'], a['sampled'], a['filtered'])
           for a in (ev['args'] for ev in obs.TRACER.events()
                     if ev['name'] == 'serve.dispatch')]
    assert got and got[0] == ('step', 4, 2, 1)
    assert all(g[1:] == (4, 2, 1) for g in got)
    obs.TRACER.clear()
    e.serve(_prompts(3, seed=61))
    assert {(a['args']['sampled'], a['args']['filtered'])
            for a in obs.TRACER.events()
            if a['name'] == 'serve.dispatch'} == {(0, 0)}


# -- the compiled text ----------------------------------------------------

def sorts_outside_conditionals(text):
    """The `sort` instructions of an optimized HLO module that run
    whenever the program does: those in a computation reached from the
    entry through calls, fusions and `while` bodies alone. A sort behind
    a `conditional` runs only when its branch is taken. Returns (those
    sorts, all sorts, conditionals)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = re.match(r'^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$', line)
        if head and not line.startswith(' '):
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith('}'):
            cur = None
        elif cur:
            comps[cur].append(line)
    is_sort = re.compile(r'\bsort\(').search
    is_cond = re.compile(r'\bconditional\(').search
    seen, todo, always = set(), [entry], []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            if is_sort(line):
                always.append((comp, line.strip()))
            if not is_cond(line):
                todo += re.findall(
                    r'(?:calls|to_apply|body|condition)=%?([\w.\-]+)', line)
    lines = [line for body in comps.values() for line in body]
    return (always, sum(map(bool, map(is_sort, lines))),
            sum(map(bool, map(is_cond, lines))))


def test_the_walk_finds_a_sort_that_always_runs():
    text = jax.jit(lambda x: jax.lax.scan(
        lambda c, _: (jnp.sort(c), None), x, None, length=3)[0]).lower(
            jnp.zeros((5, 64))).compile().as_text()
    always, sorts, conds = sorts_outside_conditionals(text)
    assert always and sorts >= 1 and conds == 0


@pytest.mark.parametrize('kind', ['serve_window', 'serve_step',
                                  'serve_spec_window'])
def test_no_sort_of_a_serve_dispatch_outside_a_conditional(kind):
    spec = kind.startswith('serve_spec')
    e = _engine(**(dict(draft=_model(1), num_draft_tokens=2) if spec
                   else {}))
    g, = [g for g in aot.for_serving_engine(e, prompt_lens=[8])
          if g.kind == kind][:1]
    (fn, args, statics), = e._cost_specs(g)
    text = fn.lower(*args, **statics).compile().as_text()
    always, sorts, conds = sorts_outside_conditionals(text)
    assert always == []
    # the filters are compiled, in branches: top-k and nucleus (twice
    # where the draft's pass and the target's both filter)
    assert sorts >= 2 and conds >= 3
