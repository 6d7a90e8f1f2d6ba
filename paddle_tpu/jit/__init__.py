"""paddle_tpu.jit — graph capture & compile (ref: python/paddle/jit).

Paddle: @to_static traces Python → ProgramDesc → PIR passes → CINN → CUDA.
Here: @to_static traces via jax → StableHLO → XLA:TPU. One decorator, the
whole compiler stack is XLA's.

`jit.save`/`jit.load` export params (npz) + the StableHLO module text
(via jax.export) — the TPU-native analogue of the inference Program
Paddle serialises.
"""
from __future__ import annotations

import functools
import os

import jax
import numpy as np


class InputSpec:
    """ref: paddle.static.InputSpec."""

    def __init__(self, shape, dtype='float32', name=None):
        from ..framework import dtype as dtype_mod

        self.shape = tuple(shape)
        self.dtype = dtype_mod.convert_dtype(dtype)
        self.name = name

    def to_shape_struct(self):
        shape = tuple(1 if s in (None, -1) else s for s in self.shape)
        return jax.ShapeDtypeStruct(shape, self.dtype)


class StaticFunction:
    """Compiled wrapper around a fn or Layer (ref: jit/dy2static 'StaticFunction')."""

    def __init__(self, fn, input_spec=None, donate_argnums=(), static_argnums=None, backend=None):
        self._fn = fn
        self._input_spec = input_spec
        self._is_layer = not callable(fn) or hasattr(fn, 'forward')
        from ..nn.layer.base import Layer

        self._layer = fn if isinstance(fn, Layer) else None
        if self._layer is not None:
            layer = self._layer

            def call(model, *args, **kwargs):
                return model(*args, **kwargs)

            # cached on self: a StaticFunction wraps one callable for
            # its lifetime, so the jit (and its trace cache) is built
            # exactly once here
            # tracelint: disable=TL001
            self._jitted = jax.jit(call, donate_argnums=donate_argnums)
        else:
            # tracelint: disable=TL001 - cached on self (see above)
            self._jitted = jax.jit(fn, donate_argnums=donate_argnums,
                                   static_argnums=static_argnums)
        functools.update_wrapper(self, fn if callable(fn) else fn.forward)

    def __call__(self, *args, **kwargs):
        if self._layer is not None:
            return self._jitted(self._layer, *args, **kwargs)
        return self._jitted(*args, **kwargs)

    @property
    def forward(self):
        return self

    def concrete_program(self, *args):
        return self._jitted.lower(*args)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph=True, donate_argnums=(), static_argnums=None, **kwargs):
    """Decorator/wrapper: compile a function or Layer with XLA
    (ref: paddle.jit.to_static)."""

    def wrap(fn):
        return StaticFunction(fn, input_spec, donate_argnums, static_argnums, backend)

    if function is not None:
        return wrap(function)
    return wrap


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None


def save(obj, path, input_spec=None, **config):
    """Export a Layer or StaticFunction: weights (.npz) + StableHLO (.mlir)
    (ref: paddle.jit.save → __model__ + params)."""
    from ..framework.io import save as save_state
    from ..nn.layer.base import Layer

    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    layer = obj._layer if isinstance(obj, StaticFunction) else obj
    if isinstance(layer, Layer):
        save_state(layer.state_dict(), path + '.pdiparams')
    if input_spec:
        structs = [
            s.to_shape_struct() if isinstance(s, InputSpec) else jax.ShapeDtypeStruct(s.shape, s.dtype)
            for s in input_spec
        ]
        if isinstance(layer, Layer):
            eval_layer = layer.eval() if hasattr(layer, 'eval') else layer

            def fwd(*xs):
                return eval_layer(*xs)

            # tracelint: disable=TL001 - one-shot export, not a hot path
            exported = jax.export.export(jax.jit(fwd))(*structs)
        else:
            fn = obj._fn if isinstance(obj, StaticFunction) else obj
            # tracelint: disable=TL001 - one-shot export, not a hot path
            exported = jax.export.export(jax.jit(fn))(*structs)
        with open(path + '.mlir', 'wb') as f:
            # the FULL Exported flatbuffer (what jax.export.deserialize
            # reads back) — not just mlir_module_serialized, which loses
            # the calling convention and cannot be restored
            f.write(exported.serialize())
        with open(path + '.pdmodel.txt', 'w') as f:
            f.write(str(exported.mlir_module()))


def load(path, **config):
    """Load a jit.save'd artifact. Returns a callable running the exported
    StableHLO if present, else the raw state dict."""
    from ..framework.io import load as load_state

    mlir_path = path + '.mlir'
    params_path = path + '.pdiparams'
    state = load_state(params_path) if os.path.exists(params_path) else None
    if os.path.exists(mlir_path):
        with open(mlir_path, 'rb') as f:
            exported = jax.export.deserialize(bytearray(f.read()))

        class LoadedFunction(TranslatedLayer):
            def __init__(self):
                self.state_dict_ = state

            def __call__(self, *args):
                return exported.call(*args)

            def state_dict(self):
                return self.state_dict_

        return LoadedFunction()
    return state


def enable_to_static(flag=True):
    return None


def enable_compilation_cache(cache_dir=None, min_compile_time_secs=1.0):
    """AOT compile cache (ref capability: CINN compile cache + Paddle's
    program cache). Wires jax's persistent compilation cache so repeat
    runs skip XLA compilation entirely.

    Delegates to sysconfig.enable_persistent_compilation_cache — the
    ONE place that owns the wiring and the default directory
    (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`) — then
    re-raises the persistence threshold to `min_compile_time_secs`
    (this entry point's contract: only compilations worth caching)."""
    import jax

    from ..sysconfig import enable_persistent_compilation_cache

    path = enable_persistent_compilation_cache(cache_dir)
    if min_compile_time_secs:
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          min_compile_time_secs)
    return path


def compilation_report(fn, *example_args, **kw):
    """Compile-time reporting (ref: @to_static build reporting): returns
    {compile_time_s, flops, bytes, hlo_text_head}."""
    import time

    from ..observability.costs import analyze

    # tracelint: disable=TL001 - one-shot compile-time report
    jitted = jax.jit(fn, **kw)
    t0 = time.perf_counter()
    lowered = jitted.lower(*example_args)
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    # quirk handling (list-vs-dict, raising backends) lives in
    # observability.costs.analyze, shared with profiler.op_summary and
    # the AOT manifest cost stamps
    cost = analyze(compiled)
    return {
        'compile_time_s': dt,
        'flops': cost['flops'] or 0,
        'bytes_accessed': cost['bytes_accessed'] or 0,
        'hlo_head': compiled.as_text()[:2000] if hasattr(compiled, 'as_text') else '',
    }


# `jit.load` returns this callable wrapper; the reference's equivalent
# class is TranslatedLayer (ref: python/paddle/jit/translated_layer.py)
TranslatedLayer = type('TranslatedLayer', (), {})  # isinstance marker base

_sot_verbosity = [0]


def set_verbosity(level=0, also_to_stdout=False):
    """ref: paddle.jit.set_verbosity — tracing has no bytecode
    translator here; the knob stores intent for debugging hooks."""
    _sot_verbosity[0] = level


def set_code_level(level=100, also_to_stdout=False):
    """ref: paddle.jit.set_code_level (SOT bytecode dump — N/A under
    jax tracing; kept for script compatibility)."""
    _sot_verbosity[0] = level
