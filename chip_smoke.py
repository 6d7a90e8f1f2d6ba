#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: serve phase, train phase
    python chip_smoke.py --chips 4   # one four-chip host: the sharded paths only

One process, no child, no fallback. It drives the public entry points at the
full width of Llama-2-7B (`paddle_tpu/models/llama.py` `LlamaConfig` defaults:
hidden 4096, ffn 11008, 32 heads of 128, vocab 32000, bf16) with depth cut to
what one 16 GB v5e chip holds, random weights from a fixed seed:

- serve: `ServingEngine` (paged bf16 KV pools, default page size, 2048-token
  contexts, 8 slots) answers six prompts of mixed length through
  `submit`/`step`/`result`; every request must finish with the tokens asked
  for, and the greedy tokens must agree with the plain non-paged model of the
  same weights on the same chip — a forward pass and `model.generate()` —
  at a stated bf16 tolerance (`PlainReference` says which and why);
- train: `TrainEngine` + `AdamW` take four steps on one repeated batch through
  `prefetch`/`step`/`sync`; the loss must be finite and lower at the end;
- both: the compiled programs the phase dispatches must contain the Mosaic
  custom calls of the pallas kernels (not the lax references) and, by
  `memory_analysis()`, leave at least 1 GiB of the chip free.

`--chips 4` runs, and only runs, `ServingEngine(tp=4)` and a
`dist.parallelize`d train step on a tp=2 x fsdp=2 mesh, each against its
one-chip twin in the same process, after asserting that a KV pool and a
column-parallel weight really sit in quarters on four distinct devices.

Any failed check, any exception, or a platform other than `tpu` ends the run
with a non-zero exit code. The last stdout line of a good run is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Timings printed on the way end in a host read; none of them is a benchmark.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import os
import re
import time

GiB = 2 ** 30
# Llama-2-7B width: LlamaConfig's own defaults, stated here so a changed
# default cannot silently narrow the smoke
WIDTH = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
             num_attention_heads=32, num_key_value_heads=32, dtype='bfloat16')


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that is cut to fit; `FULL` is what the driver runs, the
    CPU rehearsal in tests/test_chip_smoke.py passes a tiny one."""

    width: dict
    serve_layers: int
    slots: int
    context: int
    prompt_lens: tuple          # FULL: two admission buckets (1024, 128)
    reference: tuple            # indices of the prompts generate() re-decodes
    new_tokens: int             # more than one decode window
    tolerance: float            # in logit units, see PlainReference
    train_layers: int
    train_batch: int
    train_seq: int
    train_steps: int
    min_free_bytes: int = GiB
    seed: int = 0


# Depth and batch as the chip's compiler sizes them for one 16 GB v5e
# (15.75 GiB usable), by memory_analysis() of the largest program dispatched:
# - serve: the fused admit+decode step needs 6-7 GiB of temporaries whatever
#   it admits (the decode half's 4.2 and per-layer copies on top), most at
#   the 128 bucket, which this smoke's largest admission group now rides
#   (PR 26): 14.90 GiB at 12 layers (weights 5.0, pools 3.0, temporaries
#   6.9) and each layer adds 1.21 (13.69 at 11), so 11 of Llama-2-7B's 32
#   layers is the deepest stack that leaves >= 1 GiB;
# - train: weights + AdamW moments are 9.98 GiB at 4 layers and each
#   2048-token sample adds ~1.27 GiB of temporaries: batch 3 is 13.85 GiB,
#   batch 4 would be 15.1 and the compiler rematerialises to squeeze it in.
FULL = Sizes(width=WIDTH, serve_layers=11, slots=8, context=2048,
             prompt_lens=(900, 640, 530, 100, 90, 70), reference=(0, 3),
             new_tokens=24, tolerance=0.25, train_layers=4, train_batch=3,
             train_seq=2048, train_steps=4)

# The kernels each phase's compiled programs must carry. No flash attention
# on the serve side: the admission prefill runs the model over a throwaway
# contiguous cache, and cached_attention attends a multi-token query through
# the masked XLA path, not the flash kernel (found by this script on the
# chip; PERF.md, PR 21). The decode windows use the paged kernel.
SERVE_KERNELS = {'paged_attention', 'rms_norm'}
TRAIN_KERNELS = {'flash_attention', 'rms_norm', 'softmax_xent'}


def say(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit(f'chip_smoke: CHECK FAILED: {what}')
    say(f'  ok: {what}')


class CompileLog:
    """What jax itself reports about compilation, so that a persistent
    cache that never hits is visible: requests served from the cache,
    requests compiled, and the seconds spent in either."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.hits += event == '/jax/compilation_cache/cache_hits'
        self.misses += event == '/jax/compilation_cache/cache_misses'

    def _duration(self, event, seconds, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.seconds += seconds

    def report(self, phase):
        say(f'{phase}: {self.hits + self.misses} programs through the '
            f'compile cache, {self.hits} found there (warm), {self.misses} '
            f'compiled (cold), {self.seconds:.1f} s in all')
        self.hits = self.misses = 0
        self.seconds = 0.0


def mosaic_kernels(hlo_text):
    """{pallas module: Mosaic custom calls} of one compiled program, each
    call attributed through the text's own stack-frame tables to the file
    under ops/pallas/ that issued it."""
    tables, current = {}, None
    for line in hlo_text.splitlines():
        if line in ('FileNames', 'FileLocations', 'StackFrames'):
            current = tables.setdefault(line, {})
        elif not line.strip():
            current = None
        elif current is not None:
            key, _, rest = line.partition(' ')
            current[int(key)] = rest
    counts = collections.Counter()
    for m in re.finditer(
            r'custom_call_target="tpu_custom_call"[^\n]*?stack_frame_id=(\d+)',
            hlo_text):
        frame = tables['StackFrames'][int(m.group(1))]
        loc = tables['FileLocations'][
            int(re.search(r'file_location_id=(\d+)', frame).group(1))]
        path = tables['FileNames'][
            int(re.search(r'file_name_id=(\d+)', loc).group(1))].strip('"')
        counts[os.path.splitext(os.path.basename(path))[0]] += 1
    return dict(counts)


def inspect_programs(specs, total_traces):
    """Lower and compile (label, jitted fn, args, static kwargs) — the same
    module-level jitted functions on the same arguments the engine has just
    dispatched, so nothing is traced again and the persistent cache hands
    back the executable that ran. Returns {label: (program bytes, kernels)}."""
    out = {}
    traced, t0 = total_traces(), time.perf_counter()
    for label, fn, args, kwargs in specs:
        compiled = fn.lower(*args, **kwargs).compile()
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        kernels = mosaic_kernels(compiled.as_text())
        out[label] = (need, kernels)
        say(f'  {label}: {need / GiB:.2f} GiB (arguments '
            f'{mem.argument_size_in_bytes / GiB:.2f}, temporaries '
            f'{mem.temp_size_in_bytes / GiB:.2f}), Mosaic calls {kernels}')
    say(f'  (lowered and compiled again in '
        f'{time.perf_counter() - t0:.1f} s)')
    check(total_traces() == traced,
          'they are the programs that were dispatched (lowering them '
          'traced nothing new)')
    return out


def check_fits(programs, sizes, device):
    """The largest program — its arguments are the weights and the pools —
    must leave `min_free_bytes` of the device. Off the chip (the CPU
    rehearsal) there is no limit to hold it to."""
    stats = device.memory_stats() or {}
    limit = stats.get('bytes_limit')
    label, (need, _) = max(programs.items(), key=lambda kv: kv[1][0])
    if limit is None:
        say(f'  largest program {label}: {need / GiB:.2f} GiB '
            f'(no device memory limit reported on {device.platform})')
        return
    check(limit - need >= sizes.min_free_bytes,
          f'largest program {label} needs {need / GiB:.2f} GiB of '
          f'{limit / GiB:.2f}: {(limit - need) / GiB:.2f} GiB free '
          f'(>= {sizes.min_free_bytes / GiB:.0f})')


def make_model(sizes, layers, max_pos):
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    pt.seed(sizes.seed)
    return LlamaForCausalLM(LlamaConfig(
        **sizes.width, num_hidden_layers=layers,
        max_position_embeddings=max_pos))


def make_prompts(sizes):
    import numpy as np

    rng = np.random.default_rng(sizes.seed)
    return [rng.integers(0, sizes.width['vocab_size'], (n,)).astype(np.int32)
            for n in sizes.prompt_lens]


def drive_serving(engine, prompts, new_tokens):
    """submit everything, step until drained, read every result. Each
    step() commits through a host read of the window's tokens."""
    import numpy as np

    rids = [engine.submit(p, new_tokens) for p in prompts]
    steps = []
    while engine.in_flight() or len(engine.queue):
        t0 = time.perf_counter()
        engine.step()
        steps.append(time.perf_counter() - t0)
    return [np.asarray(engine.result(r)) for r in rids], steps


def check_served(outs, prompts, sizes):
    import numpy as np

    for out, p in zip(outs, prompts):
        check(out.shape == (len(p) + sizes.new_tokens,)
              and np.array_equal(out[:len(p)], p)
              and (0 <= out).all()
              and (out < sizes.width['vocab_size']).all(),
              f'request of {len(p)} prompt tokens finished with '
              f'{sizes.new_tokens} new tokens in range')


def serving_specs(engine, sizes):
    """The programs this smoke's admissions dispatched, as the engine's own
    registry noted them: everything is submitted before the first step and
    fits the slots, so step one is the fused admit+decode step of the
    largest admission group plus a standalone prefill for every other
    group (another bucket, or what of a bucket did not fit its batch's
    rows), and every later step is the pure decode window."""
    from paddle_tpu.aot import geometry
    from paddle_tpu.inference.engine import COMPILE_CACHE

    noted = set(COMPILE_CACHE.keys())
    for g in geometry.for_serving_engine(engine,
                                         prompt_lens=sizes.prompt_lens):
        key, = geometry.GeometrySet([g]).registry_keys(engine)
        if key in noted:
            for fn, args, kwargs in engine._cost_specs(g):
                yield g.label(), fn, args, kwargs


class PlainReference:
    """The same weights with no pages, no scheduler, no buckets and no mesh.

    Logits are bf16 (spacing 2**-5 between 4 and 8) and two paths through
    the system sum in different orders, so a greedy choice between two
    candidates closer than `tolerance` may differ and everything after it
    then differs too. FULL's 0.25 is 8 such steps; with these random
    weights the logits have a standard deviation near 1.3 and a maximum
    near 5, so a token chosen with the wrong KV rows is several units
    behind. Hence two comparisons, both at that tolerance. Teacher-forced:
    every served token must be within it of the best logit of one plain
    forward pass over the served sequence. And a second decoding of the
    same request (generate(), or another engine) must give the served
    tokens up to the first such near-tie.
    """

    def __init__(self, model, sizes):
        import jax
        import jax.numpy as jnp

        self.model, self.sizes = model, sizes

        @jax.jit
        def teacher_forced(m, seq):
            logits = m(seq[None, :-1])[0].astype(jnp.float32)
            top2 = jax.lax.top_k(logits, 2)[0]
            chosen = jnp.take_along_axis(logits, seq[1:, None], axis=1)[:, 0]
            return top2[:, 0] - chosen, top2[:, 0] - top2[:, 1]

        self._teacher_forced = teacher_forced
        self._generate = jax.jit(
            lambda m, ids: m.generate(ids, max_new_tokens=sizes.new_tokens))

    def generate(self, prompt):
        import jax.numpy as jnp
        import numpy as np

        return np.asarray(
            self._generate(self.model, jnp.asarray(prompt)[None]))[0]

    def check(self, prompt, served, other, other_name):
        import jax.numpy as jnp
        import numpy as np

        n, new, tol = len(prompt), self.sizes.new_tokens, self.sizes.tolerance
        behind, gap = (np.asarray(a)[n - 1:] for a in
                       self._teacher_forced(self.model, jnp.asarray(served)))
        check(behind.max() <= tol,
              f'{n}-token prompt: every served token is within {tol} of '
              f'the plain forward pass\'s best logit (worst '
              f'{behind.max():.4f}; its argmax itself at '
              f'{int((behind == 0).sum())} of {new})')
        ties = np.flatnonzero(gap <= tol)
        first_tie = int(ties[0]) if len(ties) else new
        differ = np.flatnonzero(served[n:] != other[n:])
        same = int(differ[0]) if len(differ) else new
        check(same >= first_tie,
              f'{n}-token prompt: {other_name} gives the served tokens for '
              f'the first {same} of {new} (first near-tie at {first_tie})')


def make_engine(model, sizes, **kwargs):
    from paddle_tpu.inference.serving import ServingEngine

    engine = ServingEngine(model, max_slots=sizes.slots,
                           max_context_len=sizes.context,
                           max_new_tokens=sizes.new_tokens, **kwargs)
    say(f'  page size {engine.block_size}, pools '
        f'{engine.allocator.stats()["bytes_total"] / GiB:.2f} GiB of '
        f'{model.cache_dtype()} pages, tp={engine.tp}')
    return engine


def serve_and_check(engine, prompts, sizes):
    outs, steps = drive_serving(engine, prompts, sizes.new_tokens)
    say(f'  {len(steps)} steps: first {steps[0]:.2f} s (admission: prefill '
        f'and one window, compiles included), then '
        f'{[round(s, 3) for s in steps[1:]]} s per decode window of '
        f'{engine.decode_window}')
    check_served(outs, prompts, sizes)
    return outs


def serve_phase(sizes, device):
    """ServingEngine at full width against the plain model. Returns the
    kernels found in the compiled dispatches."""
    from paddle_tpu.inference.engine import total_traces

    say(f'serve phase: {sizes.serve_layers} layers at width '
        f'{sizes.width["hidden_size"]} ({sizes.slots} slots, context '
        f'{sizes.context}, prompts {sizes.prompt_lens}, '
        f'{sizes.new_tokens} new tokens each)')
    model = make_model(sizes, sizes.serve_layers, sizes.context)
    engine = make_engine(model, sizes)
    prompts = make_prompts(sizes)
    outs = serve_and_check(engine, prompts, sizes)

    plain = PlainReference(model, sizes)
    for i in sizes.reference:
        t0 = time.perf_counter()
        plain.check(prompts[i], outs[i], plain.generate(prompts[i]),
                    'generate()')
        say(f'  (references for that prompt: '
            f'{time.perf_counter() - t0:.1f} s)')

    say('  the dispatched programs:')
    programs = inspect_programs(serving_specs(engine, sizes), total_traces)
    check_fits(programs, sizes, device)
    kernels = collections.Counter()
    for _, found in programs.values():
        kernels.update(found)
    return dict(kernels)


def train_phase(sizes, device):
    """TrainEngine + AdamW on one repeated batch. Returns the kernels
    found in the compiled fused step."""
    import numpy as np

    from paddle_tpu.aot import geometry
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.training.engine import TrainEngine, total_traces

    say(f'train phase: {sizes.train_layers} layers at width '
        f'{sizes.width["hidden_size"]}, batch {sizes.train_batch} x '
        f'{sizes.train_seq}, AdamW, {sizes.train_steps} steps')
    model = make_model(sizes, sizes.train_layers, sizes.train_seq)
    engine = TrainEngine(model, AdamW(learning_rate=1e-3, weight_decay=0.01),
                         log_window=sizes.train_steps + 1)
    shape = (sizes.train_batch, sizes.train_seq + 1)
    batch = np.random.default_rng(sizes.seed).integers(
        0, sizes.width['vocab_size'], shape).astype(np.int32)
    losses, seconds = [], []
    for b in engine.prefetch(batch for _ in range(sizes.train_steps)):
        t0 = time.perf_counter()
        engine.step((b,))
        losses.append(engine.sync()['loss'])        # the host read
        seconds.append(time.perf_counter() - t0)
    say(f'  losses {[round(x, 4) for x in losses]}; first step '
        f'{seconds[0]:.2f} s (compile included), then '
        f'{[round(s, 3) for s in seconds[1:]]} s')
    check(all(np.isfinite(losses)), 'every loss is finite')
    check(losses[-1] < losses[0],
          f'loss fell on the repeated batch ({losses[0]:.4f} -> '
          f'{losses[-1]:.4f})')

    say('  the dispatched program:')
    programs = inspect_programs(
        (('train_step',) + spec
         for g in geometry.for_train_engine(engine, shape)
         for spec in engine._cost_specs(g)), total_traces)
    check_fits(programs, sizes, device)
    return programs['train_step'][1]


def check_quartered(array, what):
    """Code that has only seen a virtual mesh may put everything on the
    first device: four shards, four devices, a quarter of the bytes each."""
    shards = array.addressable_shards
    sizes = [s.data.nbytes for s in shards]
    check(len(shards) == 4 and len({s.device for s in shards}) == 4
          and all(abs(4 * b - array.nbytes) <= 0.01 * array.nbytes
                  for b in sizes),
          f'{what} {array.shape} sits in quarters on four devices '
          f'({[s.device.id for s in shards]}, {sizes} of {array.nbytes} '
          f'bytes, {array.sharding.spec})')


def tp_serve_phase(sizes):
    """ServingEngine(tp=4) against the one-chip engine, same requests."""
    say(f'tp=4 serve phase: {sizes.serve_layers} layers at width '
        f'{sizes.width["hidden_size"]}, one-chip engine first')
    model = make_model(sizes, sizes.serve_layers, sizes.context)
    prompts = make_prompts(sizes)
    one_chip = serve_and_check(make_engine(model, sizes), prompts, sizes)

    engine = make_engine(model, sizes, tp=4)
    check_quartered(engine._pages[0].kp, 'the K page pool of layer 0')
    check_quartered(engine.model.model.layers[0].self_attn.q_proj,
                    'the column-parallel q_proj of layer 0')
    outs = serve_and_check(engine, prompts, sizes)
    plain = PlainReference(model, sizes)
    for i in sizes.reference:
        plain.check(prompts[i], outs[i], one_chip[i], 'the one-chip engine')


def sharded_train_phase(sizes, devices):
    """The README's hybrid-parallel train step on a tp=2 x fsdp=2 mesh
    against the same step on one chip, same weights, same batch."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import distributed as dist
    from paddle_tpu.models.llama import LLAMA_TP_RULES
    from paddle_tpu.optimizer import AdamW

    batch_size = sizes.train_batch - sizes.train_batch % 2     # fsdp=2
    say(f'tp=2 x fsdp=2 train phase: {sizes.train_layers} layers at width '
        f'{sizes.width["hidden_size"]}, batch {batch_size} x '
        f'{sizes.train_seq}, AdamW, {sizes.train_steps} steps, one chip '
        f'first')
    opt = AdamW(learning_rate=1e-3, weight_decay=0.01)
    batch = np.random.default_rng(sizes.seed).integers(
        0, sizes.width['vocab_size'],
        (batch_size, sizes.train_seq + 1)).astype(np.int32)

    # the README's step, with the weights and moments donated: without
    # that one chip would have to hold them twice
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(model, state, batch):
        loss, grads = pt.autograd.value_and_grad(
            lambda m: m.loss(batch))(model)
        model, state = opt.apply_gradients(model, grads, state)
        return model, state, loss

    def run(model, batch):
        state, losses = opt.init(model), []
        for _ in range(sizes.train_steps):
            model, state, loss = train_step(model, state, batch)
            losses.append(float(loss))                 # the host read
        return losses

    one_chip = run(make_model(sizes, sizes.train_layers, sizes.train_seq),
                   batch)
    mesh = dist.init_parallel_env(devices=devices, tp=2, fsdp=2, dp=-1)
    model = dist.parallelize(
        make_model(sizes, sizes.train_layers, sizes.train_seq), mesh,
        rules=LLAMA_TP_RULES, fsdp_axis='fsdp')
    check_quartered(model.model.layers[0].self_attn.q_proj,
                    'the column-parallel q_proj of layer 0')
    sharded = run(model, dist.shard_batch(batch, mesh))
    say(f'  losses on one chip {[round(x, 4) for x in one_chip]}, on the '
        f'mesh {[round(x, 4) for x in sharded]}')
    # the first loss is the same weights on the same batch: only the order
    # of the bf16 sums differs. Later ones follow AdamW updates of +-lr
    # whose sign can flip where a gradient is near zero, so they drift.
    check(all(np.isfinite(sharded)) and sharded[-1] < sharded[0]
          and abs(sharded[0] - one_chip[0]) <= 1e-2 * one_chip[0]
          and all(abs(a - b) <= 5e-2 * b
                  for a, b in zip(sharded, one_chip)),
          'the mesh\'s losses are finite, fall, and agree with one chip '
          '(first within 1%, every one within 5%)')


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1,
                    help='1 (default): serve and train phases on one chip; '
                         '4: only the paths sharded across a four-chip host')
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from paddle_tpu import sysconfig

    t_start = time.perf_counter()
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        raise SystemExit(
            f'chip_smoke: needs a TPU, jax found {devices[0].platform!r}: '
            f'nothing was run')
    if len(devices) < args.chips:
        raise SystemExit(f'chip_smoke: --chips {args.chips} on a host with '
                         f'{len(devices)} device(s)')
    device = devices[0]
    cache_dir = sysconfig.enable_persistent_compilation_cache()
    say(f'jax {jax.__version__}, jaxlib {jaxlib.__version__}, '
        f'{len(devices)} x {device.device_kind}; compile cache at '
        f'{cache_dir} ({len(os.listdir(cache_dir))} entries at start)')

    compiles = CompileLog()
    if args.chips == 1:
        found = serve_phase(FULL, device)
        check(SERVE_KERNELS <= set(found),
              f'serve dispatches carry the Mosaic kernels {found}')
        compiles.report('serve phase')
        found = train_phase(FULL, device)
        check(TRAIN_KERNELS <= set(found),
              f'train step carries the Mosaic kernels {found}')
        compiles.report('train phase')
    else:
        tp_serve_phase(FULL)
        compiles.report('tp=4 serve phase')
        sharded_train_phase(FULL, devices[:4])
        compiles.report('tp=2 x fsdp=2 train phase')
    stats = device.memory_stats()
    say(f'HBM peak {stats["peak_bytes_in_use"] / GiB:.2f} of '
        f'{stats["bytes_limit"] / GiB:.2f} GiB on device 0; '
        f'{len(os.listdir(cache_dir))} cache entries; '
        f'{time.perf_counter() - t_start:.0f} s in all')
    print(json.dumps({'ok': True, 'device': {
        'platform': device.platform, 'kind': device.device_kind,
        'count': args.chips}}), flush=True)


if __name__ == '__main__':
    main()
