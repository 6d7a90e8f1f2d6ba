"""MiMo-V2 (`models/mimo_v2.py`) on two kinds of KV page, against the
benchmark's plain float32 reference (`benchmark/reference/families/
mimo_v2.py`), at tiny sizes in float32, where the two agree to rounding:

  - prefill and paged decode through `ServingEngine`, teacher-forced
    through the reference: the served tokens' logits, with kv heads 2 / 4,
    K rows 24 and V rows 16 wide and a window of 8 over contexts past 40,
    so that a row's window pages are recycled several times; faults planted
    in the program fail that comparison;
  - the shares add up: eight ranks' shares of one expert layer are the
    uncut layer (no shared expert to count once);
  - the allocator of the recycled kind: a slot never holds more than its
    bound, recycled ids are handed out again, a preempted and re-admitted
    request serves the same tokens, both kinds return to their free lists;
  - what a second kind of page has no path through is refused by name at
    construction, and a model of one kind is served as it was.
"""
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
from paddle_tpu.inference.serving import (PageKindsUnsupported,  # noqa: E402
                                          ServingEngine)
from paddle_tpu.models import llama, mimo_v2  # noqa: E402
from paddle_tpu.observability.tracing import TRACER  # noqa: E402

CFG = {
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
    'layernorm_epsilon': 1e-5, 'vocab_size': 256, 'hidden_act': 'silu',
    'scoring_func': 'sigmoid', 'n_group': 1, 'topk_method': 'noaux_tc',
    'n_shared_experts': None, 'norm_topk_prob': True,
    'routed_scaling_factor': None, 'tie_word_embeddings': False,
    'rope_scaling': {'rope_type': 'default', 'type': 'default'},
    'n_routed_experts': 4, 'expert_offset': 4,
    'published': {'n_routed_experts': 16}, 'num_experts_per_tok': 4,
    'torch_dtype': 'float32'}
SEED, EXACT, NEW = 11, 5e-6, 24
GEOMETRY = dict(max_slots=2, block_size=4, max_context_len=64,
                decode_window=4, max_new_tokens=NEW, buckets=(32,))
BOUND = -(-(8 + 4) // 4) + 1            # ceil((window + decode_window) / 4) + 1


def fresh_engine(mutate=None, **geometry):
    for program in (serving._serve_step, serving._serve_window,
                    serving._paged_prefill):
        program.clear_cache()           # a planted fault has to be traced
    model = common.family(CFG).make_model(CFG, SEED, 64)
    if mutate is not None:
        mutate(model)
    return ServingEngine(model, **dict(GEOMETRY, **geometry))


def prompts_of(*lengths):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, CFG['vocab_size'], n).astype(np.int32)
            for n in lengths]


def served_logit_error(mutate=None):
    """The widest distance between the logits the engine decoded from
    (`_last_logits`, read after every step: the admission prefill's, then
    each decode window's last) and the reference's at the same position of
    the same tokens, over two requests whose contexts (to 51) pass the
    window (8) five times over. Also the served ids."""
    engine, prompts = fresh_engine(mutate), prompts_of(27, 20)
    rids = [engine.submit(p) for p in prompts]
    read = []                                   # (request, position, logits)
    while engine.in_flight() or len(engine.queue):
        engine.step()
        for slot, req in enumerate(engine._slot_req):
            if req is not None:
                read.append((rids.index(req.rid), int(engine._ctx[slot]) - 1,
                             np.asarray(engine._last_logits[slot])))
    outs = [np.asarray(engine.result(r)) for r in rids]
    assert all(len(o) == len(p) + NEW for o, p in zip(outs, prompts))
    assert len(read) >= 2 * (NEW // 4 - 1)
    ids = np.zeros((2, 64), np.int32)
    for r, o in enumerate(outs):
        ids[r, :len(o)] = o
    with jax.default_matmul_precision('highest'):
        want = np.asarray(serve_ref._forward(
            common.family(CFG), CFG, SEED, jnp.asarray(ids),
            jnp.broadcast_to(jnp.arange(64), (2, 64)), None))
    return max(float(np.abs(lg - want[r, pos]).max())
               for r, pos, lg in read), outs


def test_prefill_and_paged_decode_give_the_references_logits():
    TRACER.clear()
    error, outs = served_logit_error()
    assert error < EXACT
    # and the served tokens are the reference's first choices
    gaps = serve_ref.served_gaps(
        common.family(CFG), CFG, SEED,
        [(o[:len(p)], o[len(p):]) for p, o in zip(prompts_of(27, 20), outs)],
        64)
    assert gaps['served_gap'] < EXACT
    events = TRACER.events()
    # window pages went back as the rows decoded, and the dispatches
    # counted each kind: never more than the bound a slot, and fewer than
    # the same rows' window layers would hold with nothing recycled
    recycled = [e['args'] for e in events if e['name'] == 'serve.recycle']
    assert sum(a['pages'] for a in recycled) >= 2 * (NEW // 4 - 2)
    counted = [e['args'] for e in events if e['name'] == 'serve.dispatch']
    assert all(a['win_pages_held'] <= 2 * BOUND for a in counted)
    last = counted[-1]
    assert last['win_pages_held'] < last['win_pages_unbounded']
    assert last['win_pages_unbounded'] == last['full_pages_held'] >= 2 * 11
    assert last['kv_bytes_held'] < last['kv_bytes_unbounded']
    routed = [e['args'] for e in events if e['name'] == 'serve.routing']
    assert sum(a['picks_total'] for a in routed) == 2 * NEW * 3 * 4


def no_sink(model):
    for layer in model.layers[1:3]:
        layer.self_attn.attention_sink_bias = None


def window_off_by_one(model):
    for layer in model.layers[1:3]:
        layer.self_attn.sliding_window += 1


def no_value_scale(model):
    for layer in model.layers:
        layer.self_attn.value_scale = 1.0


def rotary_on_every_dim(model):
    for layer in model.layers:
        layer.self_attn.rotary_dim = layer.self_attn.head_dim


def one_theta(model):
    for layer in model.layers[1:3]:
        layer.self_attn.rope_theta = CFG['rope_theta']


@pytest.mark.parametrize('fault', [no_sink, window_off_by_one,
                                   no_value_scale, rotary_on_every_dim,
                                   one_theta])
def test_a_planted_fault_is_not_the_reference(fault):
    assert served_logit_error(fault)[0] > 10 * EXACT


def test_the_model_without_a_cache_is_the_reference():
    """The uncached forward (what `loss` runs), logits against the
    reference's full forward."""
    fam = common.family(CFG)
    model = fam.make_model(CFG, SEED, 64)
    ids = jnp.asarray(prompts_of(45)[0][None])
    where = jnp.arange(45)[None]
    with jax.default_matmul_precision('highest'):
        want = serve_ref._forward(fam, CFG, SEED, ids, where, None)
        got = model(ids)
    np.testing.assert_allclose(got, want, atol=EXACT)


def test_eight_shares_add_up_to_the_uncut_layer():
    fam = common.family(CFG)
    whole = dict(CFG, n_routed_experts=16, expert_offset=0)
    lp = weights.make_layer(fam, weights.base_key(SEED), whole, 1, 1)
    m = jax.random.normal(jax.random.PRNGKey(3), (2, 9, CFG['hidden_size']))
    want = fam.reference.experts(whole, lp, m, None)
    total, local = 0.0, 0.0
    for rank in range(8):
        share = moe.ExpertShare(64, 32, 16, 4, experts_held=2,
                                expert_offset=2 * rank)
        share.router, share.expert_bias = lp['mlp.router'], lp['mlp.expert_bias']
        for name in ('w_gate', 'w_up', 'w_down'):
            setattr(share, name, lp[f'mlp.{name}'][2 * rank:2 * rank + 2])
        with moe.routing_counts() as counts:
            part = share(m)
        total = total + part
        local += np.asarray(counts.total())[1]
        # one share is what the reference gives for the same cut
        cut = dict(CFG, n_routed_experts=2, expert_offset=2 * rank)
        cut_lp = dict(lp, **{f'mlp.{n}': lp[f'mlp.{n}'][2 * rank:2 * rank + 2]
                             for n in ('w_gate', 'w_up', 'w_down')})
        np.testing.assert_allclose(
            part, fam.reference.experts(cut, cut_lp, m, None), atol=1e-5)
    assert local == 2 * 9 * 4                   # every pick is some rank's
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert float(jnp.abs(want).max()) > 1e-3


def test_a_slots_window_pages_stay_within_the_bound_and_are_handed_out_again():
    engine = fresh_engine()
    assert engine.win_pages_per_slot == BOUND
    assert engine.win_allocator.num_blocks == 2 * BOUND + 1
    # a pool group a kind, each in its own shape
    assert [(p.kp.shape[1:], p.vp.shape[1:]) for p in engine._pages] == [
        ((2, 4, 24), (2, 4, 16)), ((4, 4, 24), (4, 4, 16)),
        ((4, 4, 24), (4, 4, 16)), ((2, 4, 24), (2, 4, 16))]
    for p in prompts_of(26, 21):    # windows that start late in a page
        engine.submit(p)
    seen, most = set(), 0
    while engine.in_flight() or len(engine.queue):
        engine.step()
        for slot, req in enumerate(engine._slot_req):
            if req is None:
                continue
            held = engine._slot_wpages[slot]
            most = max(most, len(held))
            first = engine._wfirst[slot]
            # the table holds the held pages at their logical places and 0
            # elsewhere; what is held reaches the window's first position
            row = engine._wtab[slot]
            assert list(row[first:first + len(held)]) == held
            assert not row[:first].any() and not row[first + len(held):].any()
            assert first * 4 <= max(0, int(engine._ctx[slot]) - 8 + 1)
            seen.update(held)
    assert most == BOUND
    a, wa = engine.allocator, engine.win_allocator
    # 2 * BOUND ids served every page the two rows ever held
    assert len(seen) <= 2 * BOUND < wa.alloc_count
    assert a.in_use() == wa.in_use() == 0
    assert a.available() == a.usable and wa.available() == wa.usable
    stats = engine.stats()
    assert stats['blocks_window']['allocs'] == wa.alloc_count
    assert stats['blocks']['bytes_per_page'] == 2 * 2 * 4 * (24 + 16) * 4
    assert stats['blocks_window']['bytes_per_page'] == 2 * 4 * 4 * (24 + 16) * 4


def test_a_preempted_request_serves_the_tokens_it_would_have():
    prompts = prompts_of(27, 20)
    want = [np.asarray(o) for o in fresh_engine().serve(prompts)]
    # a pool too small for both contexts at their ends: the younger
    # request is evicted mid-decode and re-prefilled, its window layers
    # from its last positions alone
    engine = fresh_engine(num_blocks=21)
    got = [np.asarray(o) for o in engine.serve(prompts)]
    assert engine.preemption_count >= 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert engine.allocator.in_use() == engine.win_allocator.in_use() == 0


@pytest.mark.parametrize('option', [
    dict(prefix_cache=True), dict(prefill_chunk=16), dict(tp=2),
    dict(kv_cache_dtype='int8'), dict(phase_role='decode'), 'draft'])
def test_what_two_kinds_do_not_carry_is_refused_at_construction(option):
    model = mimo_v2.MimoV2ForCausalLM(mimo_v2.mimo_v2_tiny())
    if option == 'draft':
        option = dict(draft=llama.LlamaForCausalLM(llama.llama_tiny()))
    with pytest.raises(PageKindsUnsupported, match=next(iter(option))):
        ServingEngine(model, max_slots=2, block_size=4, **option)


def test_a_migration_of_two_kinds_is_refused_by_name():
    engine = fresh_engine()
    with pytest.raises(PageKindsUnsupported, match='export_kv'):
        engine.export_kv(0)
    with pytest.raises(PageKindsUnsupported, match='import_kv'):
        engine.import_kv(0, {})


def test_a_model_of_one_kind_is_served_as_it_was():
    """One table, one pool group, one allocator; the dispatches take the
    arguments they took and count the pages they counted."""
    model = llama.LlamaForCausalLM(llama.llama_tiny())
    (kind,) = model.page_kinds()
    assert (kind.kv_heads, kind.k_width, kind.v_width, kind.window) == (
        2, 16, 16, None)
    engine = ServingEngine(model, **GEOMETRY)
    assert engine.win_allocator is None and engine._wtab is None
    assert engine.stats()['blocks_window'] is None
    assert all(p.kp.shape == p.vp.shape == (2 * 16 + 1, 2, 4, 16)
               for p in engine._pages)
    batch = engine._prefill_args(32, [])
    assert [a.shape for a in batch] == [(2, 32), (2,), (2, 16), (2,)]
    assert engine._device_state()['btab'].shape == (2, 16)
    TRACER.clear()
    engine.serve(prompts_of(9))
    counted = [e['args'] for e in TRACER.events()
               if e['name'] == 'serve.dispatch']
    assert counted and all(
        'pages_needed' in a and 'pages_table' in a
        and not {'win_pages_held', 'kv_bytes_held', 'full_pages_held'} & set(a)
        for a in counted)
    assert not [e for e in TRACER.events() if e['name'] == 'serve.recycle']


def test_its_prefill_through_the_flash_kernel_is_the_xla_prefill(monkeypatch):
    """On the pallas path (interpret mode here) a fresh prefill's window
    layers (sink, band) and full layers (V narrower than K) attend through
    the flash kernel: the logits are the XLA path's, and the engine counts
    every layer for `serve.prefill` / `serve.dispatch`."""
    from paddle_tpu import ops

    engine = fresh_engine()
    model = engine.model
    S = llama.PREFILL_KERNEL_MIN_ROWS
    (ids,) = prompts_of(S)
    ids = jnp.asarray(ids)[None]
    want, _ = model(ids, caches=model.init_cache(1, S), cache_index=0)
    assert engine._attn_kernel_layers(S) == 0
    engine._kernel_layers.clear()
    monkeypatch.setattr(ops, '_on_tpu', lambda: True)
    got, _ = model(ids, caches=model.init_cache(1, S), cache_index=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=EXACT * 4, atol=EXACT * 4)
    assert engine._attn_kernel_layers(S) == CFG['num_hidden_layers']
