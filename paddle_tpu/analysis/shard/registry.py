"""The shardlint suite registry.

Every collective-carrying path in `paddle_tpu/distributed/` is
registered here as a suite over `jax.ShapeDtypeStruct`s on a virtual
8-device CPU mesh — the megatron ColumnParallel→RowParallel pair,
`data_sharding` batch placement, the ZeRO `zero_spec` sharded update,
ring and Ulysses sequence parallelism, the MoE dense dispatch, the
GPipe and 1F1B pipeline schedules, and the raw `collective` wrappers —
plus, beyond distributed/, the TP-sharded ServingEngine's fused
dispatches (`serving/*`: serve_step, serve_window, serve_chunk_step
over head-sharded page pools), so ROADMAP item 1's serving wire cost
and item 5's ≥50%-MFU hybrid pretrain both land against a linter that
already knows their intended communication budget.

Shapes keep the 7B RATIOS at a compile-friendly scale: unlike
mosaiclint (which only abstract-traces), every suite here pays a real
CPU SPMD compile, and the sharding/collective STRUCTURE the rules
check is invariant to scaling all dims by a constant — only the census
byte payloads shrink with it, and the budgets are declared at the
suite's own shapes.  All dims divide the mesh axes they shard over.

Each suite declares its communication budget as
{kind: {'count': exact call sites, 'bytes': per-device payload
ceiling}} — counts are exact (a new call site is exactly the
undeclared-collective regression SL002 exists for), byte ceilings
carry ~25% headroom over the measured payload so layout-level jitter
between jax versions does not page anyone while a 2x payload jump
still does.

To add a suite: write a `_build_*` returning a `Suite`, append an
`Entry` with a unique `family/variant` name and the public entry point
as `anchor`, run `shardlint` once to measure the census, and declare
it.  If a rule fires and the code is RIGHT, suppress with a reason
that will survive review.  tests/test_shardlint.py's meta-test lints
every entry; the bench gate fails the run on new violations.
"""
from __future__ import annotations

from .engine import Entry, Suite, virtual_mesh

KB = 1024
MB = 1024 * 1024


def _sds(shape, dtype_name):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype_name))


def _sds_like(tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


# ---------------------------------------------------------------------------
# mp_layers: the megatron ColumnParallel -> RowParallel pair, fwd+bwd
# ---------------------------------------------------------------------------

def _build_mp_column_row():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.distributed.mp_layers import (ColumnParallelLinear,
                                                  RowParallelLinear)
    from paddle_tpu.distributed.parallel import model_shardings

    mesh = virtual_mesh(tp=8)
    pt.seed(0)
    col = ColumnParallelLinear(512, 2048, gather_output=False)
    row = RowParallelLinear(2048, 512, input_is_parallel=True)

    def fwd_bwd(col, row, x):
        def loss(col, row):
            h = jax.nn.silu(col(x))
            return (row(h).astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1))(col, row)

    ms_col = model_shardings(col, mesh)
    ms_row = model_shardings(row, mesh)
    x = _sds((8, 128, 512), 'float32')
    return Suite(
        fn=fwd_bwd,
        args=(_sds_like(col), _sds_like(row), x),
        mesh=mesh,
        in_shardings=(ms_col, ms_row, NamedSharding(mesh, P())),
        # grads stay sharded like their params (the train-step contract)
        out_shardings=(ms_col, ms_row),
    )


# ---------------------------------------------------------------------------
# sharding: data_sharding batch placement + ZeRO zero_spec update
# ---------------------------------------------------------------------------

def _build_data_batch():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import sharding as shmod

    mesh = virtual_mesh(dp=4, fsdp=2)
    batch_sharding = shmod.data_sharding(mesh)

    def grad_step(w, batch):
        def loss(w):
            y = jnp.tanh(batch @ w)
            return (y ** 2).mean()

        return jax.grad(loss)(w)

    def host_probe():
        # the CLEAN host pattern under a sharded batch: reduce to a
        # replicated scalar on device, device_get only that
        w = jnp.zeros((256, 256), jnp.float32)
        b = jax.device_put(
            jnp.asarray(np.ones((64, 256), np.float32)), batch_sharding)
        # tracelint: disable=TL001 - one-shot SL004 probe: runs exactly
        # once per lint pass, never on a serving path
        g = jax.jit(grad_step, in_shardings=(None, batch_sharding))(w, b)
        jax.device_get((g ** 2).sum())

    return Suite(
        fn=grad_step,
        args=(_sds((256, 256), 'float32'), _sds((64, 256), 'float32')),
        mesh=mesh,
        in_shardings=(NamedSharding(mesh, P()), batch_sharding),
        out_shardings=NamedSharding(mesh, P()),
        specs={'data_axes': P(('dp', 'fsdp'))},
        host_probe=host_probe,
    )


def _build_zero_update():
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import sharding as shmod

    mesh = virtual_mesh(dp=8)
    shape = (1024, 1024)
    zspec = shmod.zero_spec(shape, mesh)
    zsh = NamedSharding(mesh, zspec)
    rsh = NamedSharding(mesh, P())

    def zero_step(param, moment, grad):
        # stage-2 shape: incoming grads constrained to the slot spec
        # (reduce-scatter form), sharded moment update, replicated
        # param refresh (the all-gather in the budget IS ZeRO's
        # gather-after-update)
        g = jax.lax.with_sharding_constraint(grad, zsh)
        moment = 0.9 * moment + 0.1 * g
        param = param - 0.01 * moment
        return param, moment

    return Suite(
        fn=zero_step,
        args=(_sds(shape, 'float32'),) * 3,
        mesh=mesh,
        in_shardings=(rsh, zsh, rsh),
        out_shardings=(rsh, zsh),
        donate={0: 0, 1: 1},
        specs={'zero_spec': zspec},
    )


# ---------------------------------------------------------------------------
# sequence parallelism: ring + Ulysses over 'sp'
# ---------------------------------------------------------------------------

def _seq_sharding(mesh):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return NamedSharding(mesh, P(None, 'sp', None, None))


def _build_ring_fwd_bwd():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.ring_attention import ring_attention_sharded

    mesh = virtual_mesh(sp=8)
    q = _sds((1, 1024, 8, 64), 'float32')
    sh = _seq_sharding(mesh)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            out = ring_attention_sharded(q, k, v, mesh, axis='sp',
                                         causal=True)
            return out.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return Suite(fn=fwd_bwd, args=(q, q, q), mesh=mesh,
                 in_shardings=(sh, sh, sh), out_shardings=(sh, sh, sh))


def _build_ulysses_fwd():
    from paddle_tpu.distributed.ulysses import ulysses_attention_sharded

    mesh = virtual_mesh(sp=8)
    q = _sds((1, 1024, 8, 64), 'float32')
    sh = _seq_sharding(mesh)

    def fwd(q, k, v):
        return ulysses_attention_sharded(q, k, v, mesh, axis='sp',
                                         causal=True)

    return Suite(fn=fwd, args=(q, q, q), mesh=mesh,
                 in_shardings=(sh, sh, sh), out_shardings=sh)


# ---------------------------------------------------------------------------
# MoE: dense GShard dispatch with 'ep'-sharded experts
# ---------------------------------------------------------------------------

def _build_moe_dispatch():
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.distributed.moe import MoELayer
    from paddle_tpu.distributed.parallel import model_shardings

    mesh = virtual_mesh(ep=8)
    pt.seed(0)
    moe = MoELayer(64, 128, num_experts=8, top_k=2, return_aux=True)

    def dispatch_combine(moe, x):
        out, aux = moe(x)
        return out.astype(jnp.float32).sum() + aux

    ms = model_shardings(moe, mesh)
    return Suite(
        fn=dispatch_combine,
        args=(_sds_like(moe), _sds((8, 16, 64), 'float32')),
        mesh=mesh,
        in_shardings=(ms, NamedSharding(mesh, P())),
    )


# ---------------------------------------------------------------------------
# pipeline: GPipe forward + fused 1F1B, manual 'pp' ring
# ---------------------------------------------------------------------------

def _build_pipeline_gpipe():
    import jax.numpy as jnp

    from paddle_tpu.distributed import pipeline as pl_mod

    mesh = virtual_mesh(4, pp=4)

    def gpipe(w, mbs):
        return pl_mod.pipeline_apply(
            w, mbs, lambda p, x: jnp.tanh(x @ p['w']), mesh, 4)

    return Suite(
        fn=gpipe,
        args=({'w': _sds((4, 64, 64), 'float32')},
              _sds((4, 4, 64), 'float32')),
        mesh=mesh,
    )


def _build_pipeline_1f1b():
    import jax.numpy as jnp

    from paddle_tpu.distributed import pipeline as pl_mod

    mesh = virtual_mesh(4, pp=4)

    def f1b(w, extra, mbs, targets):
        return pl_mod.pipeline_1f1b(
            w, extra, mbs, targets,
            lambda p, x: jnp.tanh(x @ p['w']),
            lambda e, y, t: jnp.mean((y + e['b'] - t) ** 2),
            mesh, 4)

    return Suite(
        fn=f1b,
        args=({'w': _sds((4, 64, 64), 'float32')},
              {'b': _sds((64,), 'float32')},
              _sds((4, 4, 64), 'float32'), _sds((4, 4, 64), 'float32')),
        mesh=mesh,
    )


# ---------------------------------------------------------------------------
# collective wrappers: ring exchange + gather on a manual axis
# ---------------------------------------------------------------------------

def _build_collective_exchange():
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed._spmd import shard_map

    mesh = virtual_mesh(dp=8)

    def body(x):
        y = collective.send_recv(x, group='dp', shift=1)
        y = y + collective.all_reduce(x, group='dp')
        return y

    def exchange(x):
        return shard_map(body, mesh=mesh, in_specs=(P('dp'),),
                         out_specs=P('dp'), check_vma=False)(x)

    return Suite(fn=exchange, args=(_sds((64, 128), 'float32'),),
                 mesh=mesh)


# ---------------------------------------------------------------------------
# serving: the TP-sharded ServingEngine's fused dispatches
# ---------------------------------------------------------------------------

def _serving_fixture():
    """Shared fixture for the serving suites: a tiny llama whose every
    dim divides tp=8 (8 kv heads head-shard the page pools; 128-vocab
    embedding and 128-wide MLP split cleanly), plus the SDS avals of
    one fused serving dispatch at gate-like shapes. The model rides as
    a Suite ARG with its declared megatron column->row specs
    (`model_shardings`), the page pools as P(None, 'tp') kv-head
    shards, and every host-fed arg — ids, block tables, slot/context
    mirrors, budgets, rng — fully REPLICATED: exactly the layout
    `ServingEngine(tp=...)` serves with, so the census this compiles
    IS the per-window wire cost of the live engine."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.distributed.parallel import model_shardings
    from paddle_tpu.models.generation import PagedKVCache
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    mesh = virtual_mesh(tp=8)
    pt.seed(0)
    model = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, layers=2, heads=8, kv_heads=8,
        intermediate_size=128, max_pos=64))
    K, NB, BS, MAXB = 4, 17, 8, 8
    page = _sds((NB, 8, BS, 8), 'float32')
    shapes = {
        'mesh': mesh,
        'model': model,
        'model_sds': _sds_like(model),
        'model_sh': model_shardings(model, mesh),
        'pages': [PagedKVCache(page, page) for _ in range(2)],
        'pages_sh': NamedSharding(mesh, P(None, 'tp', None, None)),
        'rep': NamedSharding(mesh, P()),
        'logits': _sds((K, 128), 'float32'),
        'vec': _sds((K,), 'int32'),
        'fvec': _sds((K,), 'float32'),
        'svec': _sds((K,), 'uint32'),
        'live': _sds((K,), 'bool'),
        'btab': _sds((K, MAXB), 'int32'),
        # per-request sampling params ride as replicated DEVICE data
        # (PR 15): temp/topk/topp/seed/plen — the statics shrink to
        # the truly static window/eos pair
        'statics': dict(window=4, eos_token_id=2),
        'K': K,
    }
    # temp, topk, topp, seed, plen — appended to every dispatch
    shapes['samp'] = (shapes['fvec'], shapes['vec'], shapes['fvec'],
                      shapes['svec'], shapes['vec'])
    return shapes


def _build_serving_serve_step():
    from paddle_tpu.inference import serving as srv

    f = _serving_fixture()
    body = getattr(srv._serve_step, '__wrapped__', srv._serve_step)
    statics, Sb = f['statics'], 16

    def serve_step(model, pages, logits, ids, real_len, btabs, slots,
                   btab, ctx, live, budget, temp, topk, topp, seed,
                   plen):
        return body(model, pages, logits, ids, real_len, btabs, slots,
                    btab, ctx, live, budget, temp, topk, topp, seed,
                    plen, **statics)

    ids = _sds((f['K'], Sb), 'int32')
    rep = f['rep']
    return Suite(
        fn=serve_step,
        args=(f['model_sds'], f['pages'], f['logits'], ids, f['vec'],
              f['btab'], f['vec'], f['btab'], f['vec'], f['live'],
              f['vec']) + f['samp'],
        mesh=f['mesh'],
        in_shardings=(f['model_sh'], f['pages_sh']) + (rep,) * 14,
    )


def _build_serving_serve_window():
    from paddle_tpu.inference import serving as srv

    f = _serving_fixture()
    body = getattr(srv._serve_window, '__wrapped__', srv._serve_window)
    statics = f['statics']

    def serve_window(model, pages, logits, btab, ctx, live, budget,
                     temp, topk, topp, seed, plen):
        return body(model, pages, logits, btab, ctx, live, budget,
                    temp, topk, topp, seed, plen, **statics)

    rep = f['rep']
    return Suite(
        fn=serve_window,
        args=(f['model_sds'], f['pages'], f['logits'], f['btab'],
              f['vec'], f['live'], f['vec']) + f['samp'],
        mesh=f['mesh'],
        in_shardings=(f['model_sh'], f['pages_sh']) + (rep,) * 10,
    )


def _build_serving_chunk_step():
    from paddle_tpu.inference import serving as srv

    f = _serving_fixture()
    body = getattr(srv._serve_chunk_step, '__wrapped__',
                   srv._serve_chunk_step)
    statics, Cb, Sb = f['statics'], 8, 16

    def chunk_step(model, pages, logits, ids, chunk_len, start, btabs,
                   slots, cow_src, cow_dst, btab, ctx, live, budget,
                   temp, topk, topp, seed, plen, ftok, forced):
        return body(model, pages, logits, ids, chunk_len, start, btabs,
                    slots, cow_src, cow_dst, btab, ctx, live, budget,
                    temp, topk, topp, seed, plen, ftok, forced,
                    ctx_bucket=Sb, **statics)

    ids = _sds((f['K'], Cb), 'int32')
    rep = f['rep']
    return Suite(
        fn=chunk_step,
        args=(f['model_sds'], f['pages'], f['logits'], ids, f['vec'],
              f['vec'], f['btab'], f['vec'], f['vec'], f['vec'],
              f['btab'], f['vec'], f['live'], f['vec']) + f['samp']
             + (f['vec'], f['live']),
        mesh=f['mesh'],
        in_shardings=(f['model_sh'], f['pages_sh']) + (rep,) * 19,
    )


def _build_serving_spec_step():
    """The speculative serving dispatch (PR 15): draft propose (k+1
    paged single-token steps on the DRAFT model) + target verify (one
    (K, k+1) forward over the gathered prefix) + the commit rule, all
    in one fused program over head-sharded pools for BOTH models. The
    census is the megatron forward count of draft + target work: the
    draft scan contributes its per-layer all-reduces k+1 times, the
    verify once."""
    from paddle_tpu.inference import serving as srv

    f = _serving_fixture()
    body = getattr(srv._serve_spec_window, '__wrapped__',
                   srv._serve_spec_window)
    k = 2

    def spec_window(model, dmodel, pages, dpages, logits, ftok, forced,
                    btab, ctx, live, budget, temp, topk, topp, seed,
                    plen):
        return body(model, dmodel, pages, dpages, logits, ftok, forced,
                    btab, ctx, live, budget, temp, topk, topp, seed,
                    plen, k=k, ctx_bucket=16,
                    eos_token_id=f['statics']['eos_token_id'])

    import paddle_tpu as pt
    from paddle_tpu.distributed.parallel import model_shardings
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(1)
    dmodel = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, layers=1, heads=8, kv_heads=8,
        intermediate_size=128, max_pos=64))
    rep = f['rep']
    return Suite(
        fn=spec_window,
        args=(f['model_sds'], _sds_like(dmodel), f['pages'],
              f['pages'][:1], f['logits'], f['vec'], f['live'],
              f['btab'], f['vec'], f['live'], f['vec']) + f['samp'],
        mesh=f['mesh'],
        in_shardings=(f['model_sh'], model_shardings(dmodel, f['mesh']),
                      f['pages_sh'], f['pages_sh']) + (rep,) * 12,
    )


def _build_serving_kv_export():
    """The disagg migration gather (export half): one request's pages
    collected contiguous from the head-sharded pool, output pinned
    REPLICATED for the host download — the replication pin over the
    sharded gather IS the migration's wire cost, so the all-gather
    census here is exactly the per-export collective bill."""
    from paddle_tpu.inference import serving as srv

    f = _serving_fixture()
    body = getattr(srv._kv_export, '__wrapped__', srv._kv_export)

    def kv_export(pages, btabs, st):
        return body(pages, btabs, st, ctx_bucket=16)

    rep = f['rep']
    return Suite(
        fn=kv_export,
        args=(f['pages'], _sds((1, 8), 'int32'), _sds((1,), 'int32')),
        mesh=f['mesh'],
        in_shardings=(f['pages_sh'], rep, rep),
    )


def _build_serving_kv_import():
    """The import half: a replicated host-uploaded blob scattered into
    the head-sharded destination pool through the block-table rows. A
    replicated->sharded write is a local slice per device — the
    declared budget is EMPTY, and any collective appearing here is a
    resharded pool (the regression this suite pins)."""
    from paddle_tpu.inference import serving as srv

    f = _serving_fixture()
    body = getattr(srv._kv_import, '__wrapped__', srv._kv_import)
    Cx = 16

    def kv_import(pages, blob, pflat, sflat):
        return body(pages, blob, pflat, sflat, ctx_bucket=Cx)

    ent = (_sds((1, Cx, 8, 8), 'float32'),
           _sds((1, Cx, 8, 8), 'float32'))
    rep = f['rep']
    return Suite(
        fn=kv_import,
        args=(f['pages'], [ent, ent], _sds((Cx,), 'int32'),
              _sds((Cx,), 'int32')),
        mesh=f['mesh'],
        in_shardings=(f['pages_sh'], rep, rep, rep),
    )


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_MP = 'paddle_tpu.distributed.mp_layers:ColumnParallelLinear'
_DS = 'paddle_tpu.distributed.sharding:data_sharding'
_ZS = 'paddle_tpu.distributed.sharding:zero_spec'
_RING = 'paddle_tpu.distributed.ring_attention:ring_attention'
_ULY = 'paddle_tpu.distributed.ulysses:ulysses_attention'
_MOE = 'paddle_tpu.distributed.moe:MoELayer'
_GPIPE = 'paddle_tpu.distributed.pipeline:pipeline_apply'
_1F1B = 'paddle_tpu.distributed.pipeline:pipeline_1f1b'
_COLL = 'paddle_tpu.distributed.collective:send_recv'
_SRV = 'paddle_tpu.inference.serving:ServingEngine'

ENTRIES = (
    Entry('mp_layers/column_row_fwd_bwd', _MP, _build_mp_column_row,
          budget={'all-reduce': {'count': 1, 'bytes': 3 * MB}}),
    Entry('sharding/data_batch_grad', _DS, _build_data_batch,
          budget={'all-reduce': {'count': 1, 'bytes': 384 * KB}}),
    Entry('sharding/zero_update', _ZS, _build_zero_update,
          budget={'all-gather': {'count': 1, 'bytes': 5 * MB}},
          suppress={
              'SL003': 'ZeRO stage-1/2 keeps the PARAMS (and incoming '
                       'grads) replicated by design — only optimizer '
                       'state shards; the replicated 4 MB param/grad '
                       'pair is the contract, and the all-gather in '
                       'the budget is the gather-after-sharded-update',
          }),
    Entry('ring_attention/causal_fwd_bwd', _RING, _build_ring_fwd_bwd,
          budget={'collective-permute': {'count': 4, 'bytes': 2 * MB},
                  'all-reduce': {'count': 3, 'bytes': 1 * MB}}),
    Entry('ulysses/causal_fwd', _ULY, _build_ulysses_fwd,
          budget={'all-to-all': {'count': 4, 'bytes': 2 * MB}}),
    Entry('moe/dense_dispatch_fwd', _MOE, _build_moe_dispatch,
          budget={'all-reduce': {'count': 4, 'bytes': 64 * KB}}),
    Entry('pipeline/gpipe_fwd', _GPIPE, _build_pipeline_gpipe,
          budget={'collective-permute': {'count': 1, 'bytes': 8 * KB},
                  'all-reduce': {'count': 1, 'bytes': 8 * KB}}),
    Entry('pipeline/1f1b_fwd_bwd', _1F1B, _build_pipeline_1f1b,
          budget={'collective-permute': {'count': 2, 'bytes': 8 * KB},
                  'all-reduce': {'count': 4, 'bytes': 16 * KB}}),
    Entry('collective/ring_exchange', _COLL, _build_collective_exchange,
          budget={'collective-permute': {'count': 1, 'bytes': 64 * KB},
                  'all-reduce': {'count': 1, 'bytes': 64 * KB}}),
    # ServingEngine fused dispatches under tp=8 (ROADMAP item 1's
    # "declared per-window collective budget"). The all-reduce census
    # is exactly the megatron layout's: 2 per layer (attention o_proj
    # + MLP down_proj row-parallel psums) + 1 for the vocab-parallel
    # embedding = 2L+1 call sites per llama forward (5 at the
    # fixture's 2 layers; the window scan counts its body ONCE).
    # serve_step / serve_chunk_step fuse a prefill/chunk forward ahead
    # of the window = 2 forwards = 10. The all-gathers are the
    # host-facing replication pins (emitted tokens, next-step logits,
    # ctx) — nothing else may appear: an undeclared reduce-scatter or
    # a count bump here is a resharded pool or a broken pin, the
    # regression this suite exists to catch before a real pod does.
    # PR 15 moved the sampling params from jit statics to replicated
    # per-slot DEVICE data: the batched top-k/top-p filter and the
    # per-row sampler work over the vocab-parallel logits of every
    # window body. As jax 0.9.0 partitions them (re-recorded in PR 21)
    # that is 5 all-reduces per body (reduce_max, two reduce_sums, two
    # take_along_axis gathers), sub-KB all-gather pins on the sort and
    # sampling outputs, and 1 byte-scale collective-permute (the rev of
    # the descending sort) — all flat in batch and model size. Since
    # PR 31 the filter's and the draw's collectives sit in the branch
    # computations of the sampler's `lax.cond`s (the predicates are
    # replicated scalars: every device takes the same branch, so they
    # stay matched) and run only when a live row asks; the census
    # counts call sites in the whole module and reads as before.
    # Counts stay exact; byte ceilings carry ~25% headroom over the
    # measured payload.
    Entry('serving/serve_step_tp', _SRV, _build_serving_serve_step,
          budget={'all-reduce': {'count': 15, 'bytes': 112 * KB},
                  'all-gather': {'count': 9, 'bytes': 13 * KB},
                  'collective-permute': {'count': 1, 'bytes': KB}}),
    Entry('serving/serve_window_tp', _SRV, _build_serving_serve_window,
          budget={'all-reduce': {'count': 10, 'bytes': 9 * KB},
                  'all-gather': {'count': 8, 'bytes': 11 * KB},
                  'collective-permute': {'count': 1, 'bytes': KB}}),
    Entry('serving/serve_chunk_step_tp', _SRV, _build_serving_chunk_step,
          budget={'all-reduce': {'count': 15, 'bytes': 60 * KB},
                  'all-gather': {'count': 9, 'bytes': 13 * KB},
                  'collective-permute': {'count': 1, 'bytes': KB}}),
    # the speculative window: the 1-layer draft's scan contributes its
    # per-layer megatron all-reduces once per fused draft step (k+1 =
    # 3), the 2-layer target verify once, plus the sampling-path
    # reductions of both models — 24 sites; all-gathers are the
    # host-facing replication pins (cand/ncommit/next_tok/logits/ctx +
    # both pools) and the sort pins, permutes the two models' sort
    # revs. hlolint's HL005 cross-check demands EXACT agreement with
    # these counts, so they are re-recorded whenever the toolchain
    # changes what the partitioner emits.
    Entry('serving/serve_spec_step_tp', _SRV, _build_serving_spec_step,
          budget={'all-reduce': {'count': 24, 'bytes': 29 * KB},
                  'all-gather': {'count': 15, 'bytes': 35 * KB},
                  'collective-permute': {'count': 2, 'bytes': 2 * KB}}),
    # KV-cache migration (disaggregated serving, ISSUE 16): the export
    # gather's replication pins are its entire wire cost — one
    # all-gather per pool field (2 layers x k,v = 4 at the fixture),
    # bytes = the migrated rows themselves. The import scatter is a
    # replicated-blob -> sharded-pool write: a LOCAL slice per device,
    # so its budget is {} — any collective surfacing there means the
    # destination pool resharded (exactly what would silently multiply
    # migration cost by the mesh degree on a real pod).
    Entry('serving/kv_export_tp', _SRV, _build_serving_kv_export,
          budget={'all-gather': {'count': 4, 'bytes': 20 * KB}}),
    Entry('serving/kv_import_tp', _SRV, _build_serving_kv_import,
          budget={}),
)


def all_entries():
    """Every registered sharding suite, in registry order."""
    return list(ENTRIES)


def entries_for(paths=None, root=None):
    """Entries whose anchor file falls under one of `paths` (root-
    relative prefixes); all of them when `paths` is falsy."""
    entries = all_entries()
    if not paths:
        return entries
    import os

    root = root or os.getcwd()
    norm = []
    for p in paths:
        if os.path.isabs(p):
            try:
                p = os.path.relpath(p, root)
            except ValueError:
                pass
        norm.append(os.path.normpath(p).replace(os.sep, '/'))
    out = []
    for e in entries:
        path, _ = e.resolve_anchor(root=root)
        if any(path == p or path.startswith(p.rstrip('/') + '/')
               for p in norm):
            out.append(e)
    return out
