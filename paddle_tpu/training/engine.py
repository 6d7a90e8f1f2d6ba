"""TrainEngine — the compiled training hot path (the training-side twin
of inference/engine.py's DecodeEngine).

Why an engine instead of hapi's per-Model jitted closure: the hapi loop
host-synced `float(loss)` on EVERY step, host-computed the lr schedule
each iteration (including a device readback of the step counter), never
donated the params or optimizer state (a full copy of both per step),
and ran one update per loader batch with no way to accumulate. This
module owns the train step end to end:

  1. Persistent compiled-function cache. The fused step lives at MODULE
     level, so jax's trace cache is keyed on (optimizer, loss, model
     pytree structure, batch shapes, static config) and survives across
     engines and fit() calls. `trace_counts()` exposes a per-function
     retrace counter so steady-state training can be ASSERTED to be 0
     retraces (bench.py and tests/test_train_engine.py do).

  2. Buffer donation. The params, the optimizer state, and the AMP
     scaler state are donated (`donate_argnames`), so XLA updates them
     IN PLACE instead of allocating a second copy of the model + two
     Adam moments every step. Contract: a (model, opt_state) passed to
     `step()` is dead to the caller — read the new ones back off the
     engine.

  3. Gradient accumulation inside the dispatch. `accum_steps=k` splits
     the global batch into k microbatches and runs them as a `lax.scan`
     INSIDE the one compiled step — grads accumulate in fp32 on device,
     the optimizer applies ONE update per global batch, and the whole
     thing is still a single dispatch with no host round trip between
     microbatches. Mean-of-micro-means equals the fused full-batch
     loss/grads (equal micro sizes), so k is a pure memory knob.

  4. The lr schedule and AMP loss scale are traced. A traceable
     LRScheduler is evaluated from the DEVICE step counter inside the
     compiled step (no host work at all); a plain float lr rides in as
     a traced scalar argument (so `set_lr` still takes effect without a
     retrace); only host-only schedulers (ReduceOnPlateau — metric
     driven by construction) fall back to a host-computed traced
     argument. fp16 dynamic loss scaling runs entirely on device:
     scale/unscale, the non-finite check, the skip-update select, and
     the scale growth/backoff are all inside the trace.

  5. Windowed metric sync. `step()` returns nothing for
     `log_window - 1` out of every `log_window` calls; losses, preds
     and labels stay on device in a pending buffer and `sync()` fetches
     the WHOLE window with one `jax.device_get` (mirroring the decode
     engine's `_commit_window` contract: one host sync per window,
     never per step).

Input side: `prefetch(iterator)` wraps io.dataloader.prefetch_to_device
with a mesh-aware batch sharding (distributed.sharding.data_sharding),
so H2D DMA of the next global batch overlaps the current step's compute
and dp/fsdp shards land directly on their devices.
"""
from __future__ import annotations

import collections
import functools
import inspect
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tree import split_trainable
from ..inference.engine import CompileCache, model_struct, model_tag
from ..observability import journal as _journal
from ..observability import metrics as _obs
from ..observability import timeseries as _obs_ts
from ..observability import tracing as _obs_trace

# ---------------------------------------------------------------------------
# Compile accounting (the training twin of inference.engine's counters)
# ---------------------------------------------------------------------------

_TRACE_COUNTS: collections.Counter = collections.Counter()


def _count_trace(name):
    """Called from INSIDE to-be-jitted python bodies: runs only while
    tracing, so the counter is exactly the number of (re)compilations.
    Also ticks the shared `compile.traces` registry counter and drops a
    `trace:<name>` instant on the host trace (the same compile/retrace
    accounting the inference engines feed)."""
    _TRACE_COUNTS[name] += 1
    _obs.inc('compile.traces')
    _obs_trace.compile_event(f'trace:{name}')
    _journal.record('trace', fn=name)


def trace_counts():
    return dict(_TRACE_COUNTS)


def total_traces():
    return sum(_TRACE_COUNTS.values())


def reset_trace_counts():
    _TRACE_COUNTS.clear()


# the engine-level compilation-key registry, same bookkeeping class the
# decode engine uses (hits/misses observable, tests assert steady state)
TRAIN_COMPILE_CACHE = CompileCache()

# monotonic ENGINE ids for the registry key. Unlike the decode engine,
# the model cannot carry the id: stamping an attribute on a Layer
# changes its pytree static structure (Layer aux data is the __dict__),
# which would break tree-maps against pre-stamp trees — and the model
# OBJECT is replaced by every donated step anyway. The engine instance
# is the stable identity on the training side.
_ENGINE_IDS = itertools.count()


# ---------------------------------------------------------------------------
# Module-level compiled steps (the persistent jit cache)
# ---------------------------------------------------------------------------

def _compute_loss(model, inputs, labels, loss_fn, loss_mode):
    """The one forward contract: 'fn' -> preds = model(*inputs), loss =
    loss_fn(preds, *labels) (the hapi shape); 'model' -> the model owns
    its loss (LlamaForCausalLM.loss — the bench shape); 'none' -> preds
    only (eval without a loss)."""
    if loss_mode == 'model':
        return model.loss(*inputs, *labels), ()
    preds = model(*inputs)
    if loss_mode == 'none' or loss_fn is None:
        return jnp.zeros((), jnp.float32), preds
    return loss_fn(preds, *labels), preds


def _zeros_like_grads(model):
    """fp32 accumulator tree shaped like the trainable partition (None
    leaves align with frozen slots, as value_and_grad returns them)."""
    t, _ = split_trainable(model)
    return jax.tree.map(
        lambda p: None if p is None else jnp.zeros(p.shape, jnp.float32),
        t, is_leaf=lambda x: x is None)


@functools.partial(
    jax.jit,
    donate_argnames=('model', 'opt_state', 'scaler_state'),
    static_argnames=('opt', 'loss_fn', 'loss_mode', 'accum', 'lr_mode',
                     'scaler_cfg', 'with_preds'))
def _fused_train_step(model, opt_state, scaler_state, inputs, labels,
                      host_lr, *, opt, loss_fn, loss_mode, accum, lr_mode,
                      scaler_cfg, with_preds):
    """ONE dispatch per global batch: scan over `accum` microbatches
    (grads accumulated in fp32 on device), one optimizer update, lr and
    loss scale resolved inside the trace. Params, optimizer state and
    scaler state are donated — updated in place, never copied."""
    from .. import autograd

    _count_trace('train_step')
    if lr_mode == 'traced':
        # schedule math lives on device, keyed by the DEVICE step
        # counter — no host work, no readback, no retrace
        lr = opt.get_lr(opt_state['step'] + 1)
    else:
        lr = host_lr                       # traced scalar arg (or unused)
    scale = (scaler_state['scale'] if scaler_state is not None
             else jnp.ones((), jnp.float32))

    def scaled_loss(m, x, y):
        loss, preds = _compute_loss(m, x, y, loss_fn, loss_mode)
        # the forward may update layer state in place on the traced copy
        # (BatchNorm running stats): carry the mutated model out via aux
        # so the update lands in the returned pytree
        return loss * scale.astype(loss.dtype), (m, loss, preds)

    vg = autograd.value_and_grad(scaled_loss, has_aux=True)

    if accum == 1:
        (_, (model, loss, preds)), grads = vg(model, inputs, labels)
        if not with_preds:
            # drop preds from the jit OUTPUTS: a returned value cannot
            # be DCE'd, and the [B, S, V] logits of an LM step are real
            # HBM when nobody consumes them
            preds = ()
    else:
        micro = jax.tree.map(
            lambda a: a.reshape((accum, a.shape[0] // accum) + a.shape[1:]),
            (inputs, labels))

        def body(carry, mb):
            m, gsum = carry
            x, y = mb
            # grads w.r.t. the carried model: its TRAINABLE leaves are
            # the originals (only buffers evolve across microbatches)
            (_, (m, mloss, mpreds)), g = vg(m, x, y)
            gsum = jax.tree.map(
                lambda s, gg: None if s is None else s + gg.astype(s.dtype),
                gsum, g, is_leaf=lambda v: v is None)
            return (m, gsum), (mloss, mpreds if with_preds else ())

        (model, gsum), (losses, mpreds) = jax.lax.scan(
            body, (model, _zeros_like_grads(model)), micro)
        grads = jax.tree.map(
            lambda s: None if s is None else s / accum,
            gsum, is_leaf=lambda v: v is None)
        loss = jnp.mean(losses)
        # (k, B/k, ...) microbatch outputs fold back to the global batch
        preds = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
            mpreds) if with_preds else ()

    new_scaler_state = scaler_state
    if scaler_state is not None:
        inv = 1.0 / scale
        grads = jax.tree.map(
            lambda g: None if g is None else g * inv.astype(g.dtype),
            grads, is_leaf=lambda v: v is None)
        found_inf = jnp.zeros((), bool)
        for g in jax.tree.leaves(grads):
            found_inf = found_inf | jnp.any(
                ~jnp.isfinite(g.astype(jnp.float32)))
    else:
        found_inf = None

    if lr_mode == 'none':
        new_model, new_state = opt.apply_gradients(model, grads, opt_state)
    else:
        new_model, new_state = opt.apply_gradients(model, grads, opt_state,
                                                   lr=lr)

    if found_inf is not None:
        # non-finite grads: keep the old params/state (the update is a
        # no-op select on device — no host involvement in the skip)
        keep = lambda old, new: jax.tree.map(  # noqa: E731
            lambda o, n: o if o is None else jnp.where(found_inf, o, n),
            old, new, is_leaf=lambda v: v is None)
        new_model = keep(model, new_model)
        new_state = keep(opt_state, new_state)
        incr_ratio, decr_ratio, incr_every = scaler_cfg
        good = jnp.where(found_inf, 0, scaler_state['good'] + 1)
        scale = jnp.where(
            found_inf,
            jnp.maximum(scale * decr_ratio, 1.0),
            jnp.where(good >= incr_every, scale * incr_ratio, scale))
        good = jnp.where(good >= incr_every, 0, good)
        new_scaler_state = {'scale': scale, 'good': good}

    return new_model, new_state, new_scaler_state, loss, preds


@functools.partial(jax.jit,
                   static_argnames=('loss_fn', 'loss_mode', 'with_preds'))
def _eval_step(model, inputs, labels, *, loss_fn, loss_mode, with_preds):
    _count_trace('eval_step')
    loss, preds = _compute_loss(model, inputs, labels, loss_fn, loss_mode)
    return loss, (preds if with_preds else ())


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

def _to_tuple(x):
    if x is None:
        return ()
    return tuple(x) if isinstance(x, (list, tuple)) else (x,)


def _callable_tag(fn):
    """Serializable identity for a loss callable: qualified name plus
    a hash over bytecode, constants, AND closure cell values — two
    different lambdas (both '<lambda>'), same bytecode with different
    constants (`* 0.5` vs `* 0.7`), or factory-made closures over
    different values all compile different HLO and must not share an
    AOT artifact config hash."""
    if fn is None:
        return None
    name = (f'{getattr(fn, "__module__", "?")}.'
            f'{getattr(fn, "__qualname__", type(fn).__qualname__)}')
    code = getattr(fn, '__code__', None)
    if code is not None:
        import hashlib

        h = hashlib.sha256(code.co_code)
        h.update(repr(code.co_consts).encode())
        for cell in (getattr(fn, '__closure__', None) or ()):
            try:
                h.update(repr(cell.cell_contents).encode())
            except ValueError:       # empty cell
                pass
        name += ':' + h.hexdigest()[:8]
    return name


class TrainEngine:
    """Owns the compiled train/eval path for one (model, optimizer,
    loss) triple.

        eng = TrainEngine(model, optimizer, loss_fn=loss, metrics=[acc],
                          accum_steps=4, log_window=10)
        for batch in eng.prefetch(loader):
            logs = eng.step(inputs, labels)   # None until the window
            if logs is not None:              # closes — ONE device_get
                print(logs['loss'])           # per log_window steps
        logs = eng.sync()                     # flush the tail

    Contract (docs/train_engine.md):
      - `eng.model` / `eng.opt_state` are the live pytrees; the ones you
        passed in (and every pre-step snapshot) are DONATED — dead after
        the next step().
      - exactly one jit trace per (batch shape, static config); steady
        state is 0 retraces (`total_traces()` is the proof).
      - at most one host sync per `log_window` steps; `step()` itself
        never blocks on the device.
      - `accum_steps=k` requires the global batch divisible by k and
        matches the fused full-batch update within float tolerance.

    `loss_fn=None` uses `model.loss(*inputs)` (the Llama pretrain
    shape); otherwise hapi's `loss_fn(model(*inputs), *labels)`.
    `optimizer=None` builds an eval-only engine (hapi uses this when
    prepare() got no optimizer).
    """

    def __init__(self, model, optimizer=None, loss_fn=None, *,
                 accum_steps=1, scaler=None, metrics=(), log_window=10,
                 mesh=None, opt_state=None, loss_mode=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.accum_steps = int(accum_steps)
        if self.accum_steps < 1:
            raise ValueError(
                f'accum_steps must be >= 1, got {self.accum_steps}')
        self.metrics = list(metrics)
        self.log_window = max(1, int(log_window))
        self.mesh = mesh
        if loss_mode is None:
            loss_mode = 'fn' if loss_fn is not None else 'model'
        self.loss_mode = loss_mode
        self._engine_id = next(_ENGINE_IDS)
        self.opt_state = None
        if optimizer is not None:
            self.opt_state = (opt_state if opt_state is not None
                              else optimizer.init(model))
        # lr threading: does apply_gradients accept a traced lr at all?
        self._lr_kw = False
        if optimizer is not None:
            try:
                params = inspect.signature(
                    optimizer.apply_gradients).parameters
                self._lr_kw = 'lr' in params and hasattr(optimizer, 'get_lr')
            except (TypeError, ValueError):
                pass
        # AMP: fp16 dynamic loss scaling folds into the trace; bf16
        # scalers are disabled (scale 1) and cost nothing
        self.scaler = scaler
        self.scaler_state = None
        self._scaler_cfg = None
        if scaler is not None and scaler.is_enable():
            self._scaler_cfg = (float(scaler.incr_ratio),
                                float(scaler.decr_ratio),
                                int(scaler.incr_every_n_steps))
            self.scaler_state = scaler.state()
        self._host_step = 0
        self._pending = []              # train window: (loss, preds, labels)
        self._eval_pending = []
        self._last_vals = None
        self._last_loss = None
        # telemetry window accounting (host wall clock + input-element
        # counts, rolled into the registry at each sync — the window
        # boundary is the ONLY place train metrics are recorded, so
        # instrumentation inherits the one-sync-per-window contract)
        self._window_t0 = None
        self._window_tokens = 0
        self._last_scale_seen = None
        self._traces_mark = total_traces()
        # cost observatory: (batch shape, dtype) -> static flops/bytes
        # per fused step (loaded from an AOT artifact's manifest at
        # warmup, or via costs.measure_dispatch_costs); step()
        # accumulates the window's static flops so sync() can derive
        # train.mfu_est from the wall it already measures
        self._dispatch_costs: dict = {}
        self._peak_flops = None
        self._window_flops = 0.0
        self._window_bytes = 0.0
        # a window containing a compile-MISS step publishes no MFU:
        # its wall is trace+compile, not model execution (the serving
        # engine's per-dispatch MISS exclusion, at window granularity)
        self._window_miss = False
        self._last_mfu = None

    # -- lr resolution -----------------------------------------------------

    def _lr_mode(self):
        """'traced' — schedule evaluated from the device step counter
        inside the compiled step; 'arg' — lr rides in as a traced scalar
        (float lr, so set_lr works; or a host-only scheduler); 'none' —
        wrapper optimizers whose apply_gradients has no lr kwarg keep
        their own stored rate."""
        if not self._lr_kw:
            return 'none'
        from ..optimizer.lr import LRScheduler

        sched = self.optimizer._learning_rate
        if isinstance(sched, LRScheduler):
            return 'traced' if getattr(sched, 'traceable', True) else 'arg'
        return 'arg'

    def _host_lr(self, lr_mode):
        if lr_mode != 'arg':
            return 0.0
        from ..optimizer.lr import LRScheduler

        sched = self.optimizer._learning_rate
        if isinstance(sched, LRScheduler):
            # host-only scheduler (ReduceOnPlateau): its rate is plain
            # host state — no device readback, no retrace (traced arg)
            if hasattr(sched, 'last_lr'):
                return float(sched.last_lr)
            return float(sched.get_lr_at(self._host_step + 1))
        return float(sched)

    # -- AOT artifact hooks (paddle_tpu.aot) -------------------------------

    def _step_statics(self, lr_mode):
        """The static_argnames kwargs of `_fused_train_step`, in ONE
        place so `step()` and `_warm_geometry` can never drift apart
        (a drifted static is a fresh trace — exactly the cold-start
        cost warmup exists to pre-pay)."""
        return dict(opt=self.optimizer, loss_fn=self.loss_fn,
                    loss_mode=self.loss_mode, accum=self.accum_steps,
                    lr_mode=lr_mode, scaler_cfg=self._scaler_cfg,
                    with_preds=(bool(self.metrics)
                                and self.loss_mode == 'fn'))

    def registry_key(self, batch_shape, batch_dtype):
        """The EXACT TRAIN_COMPILE_CACHE key a `step()` over this batch
        shape notes — tuples of primitives only (see
        inference.engine.CompileCache's key contract)."""
        return (model_tag(self.model), self._engine_id,
                tuple(int(s) for s in batch_shape), str(batch_dtype),
                (self.accum_steps, self._lr_mode(), self.loss_mode,
                 self._scaler_cfg))

    def aot_config(self):
        """Compilation-relevant config as a dict of primitives (the
        artifact-compatibility contract; weight VALUES and host-side
        knobs like log_window are deliberately absent, the model's
        param STRUCTURE rides in as `model_struct`)."""
        opt = self.optimizer
        return {
            'engine': 'TrainEngine',
            'model': model_tag(self.model),
            'model_struct': model_struct(self.model),
            'optimizer': (f'{type(opt).__module__}.'
                          f'{type(opt).__qualname__}'
                          if opt is not None else None),
            'loss_fn': _callable_tag(self.loss_fn),
            'loss_mode': self.loss_mode,
            'lr_mode': self._lr_mode() if opt is not None else None,
            'accum_steps': self.accum_steps,
            'scaler_cfg': (list(self._scaler_cfg)
                           if self._scaler_cfg is not None else None),
            # the mesh geometry is compilation-relevant: a dp=8
            # engine's fused step is an 8-shard SPMD program a
            # mesh-less engine can never look up — attaching across
            # mesh shapes must refuse (ArtifactMismatch names this
            # field)
            'mesh': (dict(self.mesh.shape)
                     if self.mesh is not None else None),
        }

    def _aot_jitted_fns(self):
        """The module-level jitted steps this engine's geometries
        dispatch — what `aot.build` cache-evicts (per FUNCTION, not
        process-wide) to force real persisting compiles."""
        return (_fused_train_step,)

    def _warm_geometry(self, g, draft=None):
        """Drive ONE train-step geometry through `_fused_train_step`
        with dummy zero batches and DEEP-COPIED params / optimizer /
        scaler trees: the copies are what gets donated, so the engine's
        live state is untouched by the warmup step (the optimizer
        result on garbage data is discarded). Statics come from
        `_step_statics`, identical to a real `step()`."""
        if g.kind != 'train_step':
            raise ValueError(
                f'unknown train geometry kind {g.kind!r} (was this '
                f'GeometrySet enumerated for a different engine?)')
        if self.optimizer is None:
            raise RuntimeError('cannot warm a train step without an '
                               'optimizer (eval-only engine)')
        p = g.params

        def zeros(shapes, dtypes):
            return tuple(jnp.zeros(tuple(s), d)
                         for s, d in zip(shapes, dtypes))

        inputs = zeros(p['input_shapes'], p['input_dtypes'])
        labels = zeros(p.get('label_shapes', ()), p.get('label_dtypes', ()))

        def copy_tree(tree):
            # donated leaves must be REAL copies (an aliasing view would
            # hand the live buffer to XLA for in-place reuse); non-array
            # leaves ride through untouched so their avals — including
            # python-scalar weak types — match the real step exactly
            return jax.tree.map(
                lambda x: x.copy() if isinstance(x, jax.Array) else x,
                tree)

        lr_mode = self._lr_mode()
        if inputs:
            TRAIN_COMPILE_CACHE.note(self.registry_key(
                inputs[0].shape, inputs[0].dtype))
        scaler_copy = (copy_tree(self.scaler_state)
                       if self.scaler_state is not None else None)
        _fused_train_step(
            copy_tree(self.model), copy_tree(self.opt_state), scaler_copy,
            inputs, labels, self._host_lr(lr_mode),
            **self._step_statics(lr_mode))

    def warmup(self, artifact=None, geometries=None, draft=None):
        """Pre-populate the fused-train-step jit cache (and the
        TRAIN_COMPILE_CACHE registry) before the first real batch —
        with an `aot.EngineArtifact`, compiles are persistent-cache
        disk reads. Params are NOT touched (the dummy step runs on
        copies). Returns a report dict; see docs/aot_warmup.md."""
        from ..aot.artifact import warm_attach

        return warm_attach(self, artifact=artifact, geometries=geometries,
                           draft=draft)

    def _export_specs(self, g, draft=None):
        """(suffix, jitted_fn, args) for `aot.build(...,
        export_stablehlo=True)` — the fused train step over
        ShapeDtypeStruct batch avals (export only traces; nothing is
        donated or stepped). The model is closed over (the jit.save
        idiom: a Layer in the calling convention would refuse to
        serialize) and the updated params return FLATTENED, so the
        exported module's pytrees carry only arrays, dicts, and
        tuples."""
        if g.kind != 'train_step':
            raise NotImplementedError(
                f'no StableHLO export for geometry kind {g.kind!r}')
        p = g.params

        def sds(shapes, dtypes):
            return tuple(jax.ShapeDtypeStruct(tuple(s), d)
                         for s, d in zip(shapes, dtypes))

        inputs = sds(p['input_shapes'], p['input_dtypes'])
        labels = sds(p.get('label_shapes', ()), p.get('label_dtypes', ()))
        lr_mode = self._lr_mode()
        statics = self._step_statics(lr_mode)
        base = getattr(_fused_train_step, '__wrapped__',
                       _fused_train_step)
        model = self.model

        def step_flat(opt_state, scaler_state, ins, labs, host_lr):
            new_model, new_state, new_scaler, loss, _ = base(
                model, opt_state, scaler_state, ins, labs, host_lr,
                **statics)
            return (tuple(jax.tree.leaves(new_model)), new_state,
                    new_scaler, loss)

        # tracelint: disable=TL001 - one-shot export wrapper, not a hot
        # path
        yield ('', jax.jit(step_flat),
               (self.opt_state, self.scaler_state, inputs, labels,
                self._host_lr(lr_mode)))

    def _cost_specs(self, g, draft=None):
        """(jitted_fn, args, static_kwargs) for
        `observability.costs.geometry_cost`: the module-level fused
        train step over ShapeDtypeStruct batch avals with the live
        model/opt-state riding as arguments — the exact served HLO."""
        if g.kind != 'train_step':
            raise NotImplementedError(
                f'no cost specs for geometry kind {g.kind!r}')
        if self.optimizer is None:
            raise NotImplementedError(
                'eval-only engine: no train step to cost')
        p = g.params

        def sds(shapes, dtypes):
            return tuple(jax.ShapeDtypeStruct(tuple(s), d)
                         for s, d in zip(shapes, dtypes))

        inputs = sds(p['input_shapes'], p['input_dtypes'])
        labels = sds(p.get('label_shapes', ()), p.get('label_dtypes', ()))
        lr_mode = self._lr_mode()
        yield (_fused_train_step,
               (self.model, self.opt_state, self.scaler_state, inputs,
                labels, self._host_lr(lr_mode)),
               self._step_statics(lr_mode))

    def _cost_key(self, shape, dtype):
        return (tuple(int(s) for s in shape), str(dtype))

    def _note_geometry_cost(self, g, cost):
        """Bind one train-step geometry's static flops/bytes (an aot
        manifest `cost` entry, or costs.geometry_cost output) to its
        batch-shape key; `step()` then accumulates window flops and
        `sync()` turns them into `train.mfu_est` — host arithmetic on
        the wall the window sync already measures."""
        if (g.kind != 'train_step' or not isinstance(cost, dict)
                or not cost.get('flops')):
            return
        p = g.params
        self._dispatch_costs[self._cost_key(
            p['input_shapes'][0], p['input_dtypes'][0])] = cost
        if self._peak_flops is None:
            from ..observability import costs as _costs

            self._peak_flops = _costs.device_peak_flops()

    # -- the hot path ------------------------------------------------------

    def step(self, inputs, labels=()):
        """Run one fused train step. Returns the window logs dict when
        this step closes a log window (one device_get), else None."""
        if self.optimizer is None:
            raise RuntimeError('TrainEngine built without an optimizer '
                               'is eval-only; pass one to train')
        if self.loss_mode == 'none':
            # loud failure beats silently "training" on a zero loss
            # while weight decay corrupts the params step by step
            raise RuntimeError(
                'TrainEngine has no loss to train on: pass loss_fn '
                '(hapi prepare(optimizer, loss=...)) or use '
                'loss_fn=None with a model that defines .loss()')
        with _obs_trace.span('train.step', cat='train') as sp:
            return self._step(sp, inputs, labels)

    def _step(self, span, inputs, labels):
        inputs = tuple(jnp.asarray(x) for x in _to_tuple(inputs))
        labels = tuple(jnp.asarray(x) for x in _to_tuple(labels))
        if self.accum_steps > 1:
            for a in inputs + labels:
                if a.shape[0] % self.accum_steps:
                    raise ValueError(
                        f'global batch {a.shape[0]} not divisible by '
                        f'accum_steps={self.accum_steps}')
        if self._window_t0 is None:        # first step of a new window
            self._window_t0 = time.perf_counter()
        if inputs and hasattr(inputs[0], 'size'):
            self._window_tokens += int(inputs[0].size)
            span.set(tokens=int(inputs[0].size))
        lr_mode = self._lr_mode()
        if inputs:
            if not TRAIN_COMPILE_CACHE.note(self.registry_key(
                    inputs[0].shape, inputs[0].dtype)):
                self._window_miss = True
            if self._dispatch_costs:
                c = self._dispatch_costs.get(self._cost_key(
                    inputs[0].shape, inputs[0].dtype))
                if c is not None:
                    self._window_flops += c.get('flops') or 0.0
                    self._window_bytes += c.get('bytes_accessed') or 0.0
        (self.model, self.opt_state, self.scaler_state, loss,
         preds) = _fused_train_step(
            self.model, self.opt_state, self.scaler_state, inputs, labels,
            self._host_lr(lr_mode), **self._step_statics(lr_mode))
        self._host_step += 1
        # without metrics only the loss scalar is worth fetching: don't
        # retain (or D2H-transfer) whole pred/label tensors per window
        if self.metrics:
            self._pending.append((loss, preds, labels))
        else:
            self._pending.append((loss, (), ()))
        if len(self._pending) >= self.log_window:
            return self.sync()
        return None

    def sync(self):
        """Close the window: ONE batched device_get for every step since
        the last sync, feed the host metrics, return the logs. Mirrors
        the decode engine's one-sync-per-window contract.

        The telemetry registry is fed HERE and only here (step time,
        tokens/s, loss, loss scale, retrace count) — the current AMP
        scale rides inside the same device_get, so instrumentation adds
        zero extra syncs."""
        if not self._pending:
            return self._last_vals and dict(self._last_vals)
        pending, self._pending = self._pending, []
        # the scaler state is donated to the NEXT step, so fetch the
        # LIVE scale now, folded into the window's one host transfer
        # (holding per-step scale refs would read donated buffers)
        scale_dev = (self.scaler_state['scale']
                     if self.scaler_state is not None else None)
        with _obs_trace.span('train.sync', cat='train',
                             window=len(pending)):
            window, scale = jax.device_get((pending, scale_dev))
        for loss, preds, labels in window:
            self._feed_metrics(preds, labels)
        self._last_loss = float(window[-1][0])
        logs = {'loss': self._last_loss,
                'loss_mean': float(np.mean([w[0] for w in window])),
                'window': len(window)}
        for m in self.metrics:
            names, accs = m.name(), m.accumulate()
            if isinstance(names, list):
                logs.update(dict(zip(names, accs)))
            else:
                logs[names] = accs
        self._last_vals = logs
        self._record_window(len(window), scale)
        return dict(logs)

    def _record_window(self, n_steps, scale):
        """Roll one closed window into the process-global registry
        (host arithmetic on data the sync already fetched)."""
        if not _obs.enabled():
            self._window_t0 = None
            self._window_tokens = 0
            self._window_flops = 0.0
            self._window_bytes = 0.0
            self._window_miss = False
            return
        now = time.perf_counter()
        if self._window_t0 is not None and n_steps:
            wall = now - self._window_t0
            if wall > 0:
                _obs.set_gauge('train.tokens_per_s',
                               self._window_tokens / wall)
                if self._window_flops and not self._window_miss:
                    # live MFU / roofline: the window's accumulated
                    # static step flops (the AOT manifest's cost
                    # stamps) over the wall this sync already measures
                    # — zero extra syncs, zero retraces. A window that
                    # paid a compile publishes nothing (its wall is
                    # not model execution — the MISS-exclusion rule)
                    fps = self._window_flops / wall
                    _obs.set_gauge('train.model_flops_per_s', fps)
                    mfu = (fps / self._peak_flops
                           if self._peak_flops else None)
                    if mfu is not None:
                        _obs.set_gauge('train.mfu_est', mfu)
                    if self._window_bytes:
                        _obs.set_gauge(
                            'train.roofline_intensity',
                            self._window_flops / self._window_bytes)
                    self._last_mfu = {
                        'flops': self._window_flops,
                        'bytes_accessed': self._window_bytes or None,
                        'window_wall_ms': wall * 1e3,
                        'steps': n_steps, 'flops_per_s': fps,
                        'mfu_est': mfu,
                        'peak_flops': self._peak_flops,
                    }
            # per-step time is known at window granularity only (the
            # steps never synced individually — that is the point)
            _obs.observe('train.step_ms', wall * 1e3 / n_steps,
                         n=n_steps)
        _obs.inc('train.steps', n_steps)
        _obs.inc('train.tokens', self._window_tokens)
        _obs.set_gauge('train.loss', self._last_loss)
        _obs.set_gauge('train.accum_steps', self.accum_steps)
        traces = total_traces()
        # clamp: a reset_trace_counts() between windows would otherwise
        # make the delta negative and Counter.inc raise mid-sync
        _obs.inc('train.traces', max(0, traces - self._traces_mark))
        self._traces_mark = traces
        if scale is not None:
            s = float(scale)
            _obs.set_gauge('train.loss_scale', s)
            # a scale DROP between windows means the in-trace skip path
            # fired at least once inside the window (window-granular by
            # design: per-step skip visibility would cost a sync)
            if (self._last_scale_seen is not None
                    and s < self._last_scale_seen):
                _obs.inc('train.scale_backoffs')
            self._last_scale_seen = s
        # the windowed timeseries commits at THIS existing sync point
        # (the training mirror of the serving per-window commit): the
        # process-default ring derives train.tok_s and windowed
        # train.step_ms percentiles with zero new syncs
        _obs_ts.TIMESERIES.maybe_commit(now)
        self._window_t0 = None
        self._window_tokens = 0
        self._window_flops = 0.0
        self._window_bytes = 0.0
        self._window_miss = False

    def _feed_metrics(self, preds, labels):
        if preds is None or (isinstance(preds, tuple) and not preds):
            return
        for m in self.metrics:
            args = m.compute(preds, *labels)
            if not isinstance(args, tuple):
                args = (args,)
            m.update(*args)

    # -- eval --------------------------------------------------------------

    def eval_step(self, inputs, labels=()):
        """Buffer one eval batch on device (no host sync); windows flush
        through eval_sync() / automatically every log_window batches."""
        inputs = tuple(jnp.asarray(x) for x in _to_tuple(inputs))
        labels = tuple(jnp.asarray(x) for x in _to_tuple(labels))
        with_preds = bool(self.metrics) and self.loss_mode != 'model'
        loss, preds = _eval_step(self.model, inputs, labels,
                                 loss_fn=self.loss_fn,
                                 loss_mode=self.loss_mode,
                                 with_preds=with_preds)
        if self.metrics:
            self._eval_pending.append((loss, preds, labels))
        else:
            self._eval_pending.append((loss, (), ()))
        if len(self._eval_pending) >= self.log_window:
            return self.eval_sync()
        return None

    def eval_sync(self):
        """One device_get for the buffered eval window; returns the list
        of host losses (metrics are fed as a side effect)."""
        if not self._eval_pending:
            return []
        pending, self._eval_pending = self._eval_pending, []
        window = jax.device_get(pending)
        for loss, preds, labels in window:
            self._feed_metrics(preds, labels)
        return [float(w[0]) for w in window]

    # -- input side --------------------------------------------------------

    def prefetch(self, iterator, size=2):
        """Wrap a host batch iterator with sharded device prefetch:
        `size` global batches stay in flight to HBM (H2D overlaps
        compute), each sharded over the mesh's data axes when the
        engine has one (dp/fsdp global arrays)."""
        from ..io.dataloader import prefetch_to_device

        sharding = None
        if self.mesh is not None:
            from ..distributed.sharding import data_sharding

            sharding = data_sharding(self.mesh)
        feed = prefetch_to_device(iterator, size=size, sharding=sharding)
        while True:
            # one `next()` of the feed, on whichever thread asks: the
            # upstream iterator's batch and its host-to-device put
            with _obs_trace.span('train.feed', cat='train'):
                try:
                    batch = next(feed)
                except StopIteration:
                    return
            yield batch

    # -- bookkeeping -------------------------------------------------------

    def loss_scale(self):
        """Current AMP loss scale (host float; one off-hot-path sync)."""
        if self.scaler_state is None:
            return 1.0
        return float(jax.device_get(self.scaler_state['scale']))

    def stats(self):
        """{'trace_counts', 'total_traces', 'cache_keys', 'hits',
        'misses'} — steady-state training must show total_traces frozen
        across steps (bench.py asserts exactly that)."""
        return {
            'trace_counts': trace_counts(),
            'total_traces': total_traces(),
            'cache_keys': len(TRAIN_COMPILE_CACHE),
            'hits': TRAIN_COMPILE_CACHE.hits,
            'misses': TRAIN_COMPILE_CACHE.misses,
            # host-truth MFU record of the last closed window (static
            # window flops, wall, mfu_est) — what tests check the
            # train.mfu_est gauge against
            'mfu': self._last_mfu,
        }


__all__ = [
    'TrainEngine', 'TRAIN_COMPILE_CACHE', 'trace_counts', 'total_traces',
    'reset_trace_counts',
]
