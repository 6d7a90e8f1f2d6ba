"""Paddle-style method surface on jax arrays.

The reference monkey-patches its whole tensor-op namespace onto every
``Tensor`` so user code can write ``x.unsqueeze(0)``, ``x.numpy()``,
``x.add(y)`` etc.:

- ref python/paddle/tensor/__init__.py:459 ``tensor_method_func`` (382
  names) and :848 ``magic_method_func``
- ref python/paddle/base/dygraph/tensor_patch_methods.py:86
  ``monkey_patch_tensor`` (numpy/item/cpu/cuda/to/backward/...)
- ref python/paddle/base/dygraph/math_op_patch.py:68
  ``monkey_patch_math_tensor`` (astype/dim/ndimension/...)

Here ``Tensor`` IS ``jax.Array``; we attach the same surface as thin
delegates to the functional ops, onto both the concrete array class
(``jaxlib...ArrayImpl``) and ``jax.core.Tracer`` so every method also
works on traced values inside ``jit``.

Notes on semantics (see docs/migration.md):
- in-place variants (``add_`` ...) return their result; jax arrays are
  immutable, and the reference's in-place forms also return the tensor.
- reductions accept both paddle's ``keepdim`` and numpy's ``keepdims``.
- ``backward()/register_hook`` on a raw array raise with guidance (the
  eager tape lives on ``paddle_tpu.autograd.Variable``).
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ._method_list import MAGIC_METHODS, TENSOR_METHOD_NAMES

__all__ = [
    'monkey_patch_tensor',
    'TENSOR_METHOD_NAMES',
    'MAGIC_METHODS',
    'unbound_methods',
]

# Names whose jax/numpy built-in is already exactly what ported scripts
# expect; do not shadow them with the functional delegate.
_KEEP_BUILTIN = frozenset({'item', 'astype', 'tolist',
                           # jnp.reshape delegates to the method — routing
                           # it back through the functional op would recurse
                           'reshape'})

# originals captured before overriding (e.g. jax's dtype-reinterpret view)
_ORIGINALS = {}

# Methods where a ported script may pass the shape/perm as varargs
# (torch habit: ``x.reshape(2, 3)``); pack into a list before
# delegating to the paddle-signature functional op.
_VARARG_SHAPE = frozenset({'reshape', 'reshape_', 'tile', 'expand',
                           'transpose', 'transpose_', 'view', 'squeeze',
                           'unsqueeze', 'permute'})

_warned = set()


def _warn_once(key, msg):
    if key not in _warned:
        _warned.add(key)
        warnings.warn(msg, stacklevel=3)


def _numpy(self):
    """Tensor.numpy() — ref tensor_patch_methods.py: host round-trip."""
    return np.asarray(self)


def _detach(self):
    return jax.lax.stop_gradient(self)


def _cast(self, dtype):
    from .manipulation import cast
    return cast(self, dtype)


def _cpu(self):
    try:
        return jax.device_put(self, jax.devices('cpu')[0])
    except Exception:
        return self  # traced value: device motion is a no-op under jit


def _device_noop(self, *args, **kwargs):
    # cuda()/pin_memory(): data already lives on the accelerator jax
    # chose; keep as identity (ref tensor_patch_methods.py:1081,1102).
    return self


def _place_to_str(p):
    from ..device import _Place
    if isinstance(p, _Place):
        return str(p).split('(')[-1].rstrip(')')  # Place(cpu:0) -> cpu:0
    return p


def _to(self, *args, **kwargs):
    """Tensor.to(device|dtype|other, ...) — ref tensor_patch_methods.py:682."""
    from ..device import _Place
    device = _place_to_str(kwargs.pop('device', None))
    dtype = kwargs.pop('dtype', None)
    kwargs.pop('blocking', None)
    for a in args:
        if isinstance(a, _Place):
            device = _place_to_str(a)
        elif isinstance(a, jax.Array):
            # .to(other): adopt the other tensor's dtype. Must precede the
            # hasattr(a, 'name') dtype test — patched arrays carry a
            # `name` property
            dtype = a.dtype
        elif isinstance(a, str):
            # 'cpu', 'gpu', 'gpu:0', 'tpu', or a dtype string
            if a.split(':')[0] in ('cpu', 'gpu', 'tpu', 'xpu', 'npu'):
                device = a
            else:
                dtype = a
        elif isinstance(a, (jnp.dtype, np.dtype, type)) or hasattr(a, 'name'):
            dtype = a
    out = self
    if dtype is not None:
        out = _cast(out, dtype)
    if device is not None and device.split(':')[0] == 'cpu':
        out = _cpu(out)
    return out


def _backward(self, *args, **kwargs):
    raise RuntimeError(
        'Tensor.backward() is not available on a raw jax array: gradients '
        'are functional on TPU. Either use paddle_tpu.autograd.Variable '
        '(an op-recording eager tape with .backward()/.grad) or rewrite '
        'the step as loss, grads = '
        'paddle_tpu.autograd.value_and_grad(loss_fn)(model, batch). '
        'See docs/migration.md.'
    )


def _register_hook(self, hook):
    raise RuntimeError(
        'Tensor.register_hook is not supported on raw jax arrays; '
        'wrap the value in paddle_tpu.autograd.Variable or use a '
        'custom VJP (paddle_tpu.autograd.PyLayer). See docs/migration.md.'
    )


def _set_value(self, value):
    raise RuntimeError(
        'Tensor.set_value cannot mutate an immutable jax array. Load '
        'weights through Layer.set_state_dict / load_state_dict, or '
        'rebind the variable to a new tensor. See docs/migration.md.'
    )


def _clear_grad(self):
    return None


def _gradient(self):
    return None


def _value(self):
    return self


def _apply(self, func):
    return func(self)


def _element_size(self):
    return jnp.dtype(self.dtype).itemsize


def _dim(self):
    return self.ndim


def _numel_m(self):
    return int(np.prod(self.shape)) if self.shape else 1


def _to_sparse_coo(self, sparse_dim=2):
    from .. import sparse as _sparse
    dense = np.asarray(self)
    nz = np.nonzero(np.any(
        dense.reshape(dense.shape[:sparse_dim] + (-1,)) != 0, axis=-1)
        if dense.ndim > sparse_dim else dense != 0)
    indices = np.stack(nz)
    values = dense[tuple(indices)]
    return _sparse.sparse_coo_tensor(indices, values, dense.shape)


def _to_dense(self):
    return self


def _md5sum(self):
    import hashlib
    return hashlib.md5(np.ascontiguousarray(np.asarray(self))).hexdigest()


def _pt():
    import paddle_tpu
    return paddle_tpu


def _special_table():
    """name -> callable taking the tensor as first arg."""
    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    from .. import signal
    from . import linalg as _linalg
    from . import random as _random

    return {
        'numpy': _numpy,
        'detach': _detach,
        'detach_': _detach,
        'cast': _cast,
        'cast_': _cast,
        'cpu': _cpu,
        'cuda': _device_noop,
        'pin_memory': _device_noop,
        'to': _to,
        'backward': _backward,
        'register_hook': _register_hook,
        'set_value': _set_value,
        'clear_grad': _clear_grad,
        'clear_gradient': _clear_grad,
        'gradient': _gradient,
        'value': _value,
        'apply': _apply,
        'apply_': _apply,
        'element_size': _element_size,
        'dim': _dim,
        'ndimension': _dim,
        'numel': _numel_m,
        'to_sparse_coo': _to_sparse_coo,
        'to_dense': _to_dense,
        '_md5sum': _md5sum,
        'sigmoid': F.sigmoid,
        'sigmoid_': F.sigmoid,
        'inverse': _linalg.inv,
        'stft': signal.stft,
        'istft': signal.istft,
        'top_p_sampling': _random.top_p_sampling,
        'create_tensor': pt.tensor.creation.create_tensor,
        # C++-generated in-place methods not in the python lists
        'zero_': lambda self: jnp.zeros_like(self),
        'fill_': lambda self, v: jnp.full_like(self, v),
        'clone': pt.tensor.creation.clone,
        'view': pt.tensor.manipulation.view,
    }


def _resolve(name, pt, special):
    if name in special:
        return special[name]
    fn = getattr(pt, name, None)
    if fn is None and name.endswith('_'):
        fn = getattr(pt, name[:-1], None)
    return fn


def _allowed_kwargs(fn):
    try:
        import inspect

        params = inspect.signature(fn).parameters
        if any(p.kind == inspect.Parameter.VAR_KEYWORD
               for p in params.values()):
            return None
        return set(params)
    except (TypeError, ValueError):
        return None


def _make_method(fn, name, orig=None, allowed=None):
    vararg_shape = name in _VARARG_SHAPE

    def method(self, *args, **kwargs):
        if 'keepdims' in kwargs and 'keepdim' not in kwargs:
            kwargs['keepdim'] = kwargs.pop('keepdims')
        # numpy's dispatch protocol (np.sum/np.reshape/... on a non-ndarray)
        # calls the method with out=/order= kwargs paddle ops don't have
        if kwargs.get('out', 'absent') is None:
            kwargs.pop('out')
        if kwargs.get('order', 'absent') in (None, 'C', 'K', 'A'):
            kwargs.pop('order', None)
        if (orig is not None and allowed is not None
                and any(k not in allowed for k in kwargs)):
            # numpy-protocol kwargs the paddle op doesn't know (where=,
            # initial=, ... — jnp.nansum etc. call the METHOD with them):
            # route to the original jax method, numpy spelling restored
            if 'keepdim' in kwargs:
                kwargs['keepdims'] = kwargs.pop('keepdim')
            return orig(self, *args, **kwargs)
        if (vararg_shape and len(args) > 1
                and all(isinstance(a, (int, np.integer)) for a in args)):
            args = (list(args),)
        return fn(self, *args, **kwargs)

    method.__name__ = name
    method.__qualname__ = f'Tensor.{name}'
    method.__doc__ = getattr(fn, '__doc__', None)
    return method


# ---------------------------------------------------------------------------
# properties (ref tensor_patch_methods.py: grad/place/stop_gradient/name)

def _prop_grad(self):
    return None


def _prop_place(self):
    from ..device import CPUPlace, TPUPlace
    try:
        platform = list(self.devices())[0].platform
    except Exception:
        platform = 'tpu'
    return CPUPlace() if platform == 'cpu' else TPUPlace(0)


def _prop_stop_gradient(self):
    return True


def _set_stop_gradient(self, value):
    _warn_once(
        'stop_gradient',
        'Setting Tensor.stop_gradient on a raw jax array is a no-op: '
        'trainability is decided by where the leaf sits in the Layer '
        'pytree (non-trainable params are filtered out of autograd). '
        'Use layer.weight.trainable / parameter.stop_gradient at module '
        'level, or lax.stop_gradient(x) inside the loss. '
        'See docs/migration.md.',
    )


def _prop_name(self):
    return f'eager_tensor_{id(self) & 0xFFFFFF:x}'


def _prop_persistable(self):
    return False


_PROPERTIES = {
    'grad': property(_prop_grad),
    'place': property(_prop_place),
    'stop_gradient': property(_prop_stop_gradient, _set_stop_gradient),
    'name': property(_prop_name),
    'persistable': property(_prop_persistable),
}


def _is_descriptor(cls, name):
    import inspect
    try:
        attr = inspect.getattr_static(cls, name)
    except AttributeError:
        return False
    return hasattr(attr, '__set__') or isinstance(attr, property)


def _patch_targets():
    # resolve the concrete array class WITHOUT creating an array:
    # instantiating one would initialise the jax backend — and claim the
    # chip — at `import paddle_tpu`
    from jax._src.array import ArrayImpl

    return (ArrayImpl, jax.core.Tracer)


_unbound = {}


def unbound_methods():
    """The resolved name -> function map (for the parity guard test)."""
    return dict(_unbound)


def _patch_trace_diagnostics():
    """Migration-aware trace errors (ref jit/sot bytecode capture is
    replaced by jax tracing — see docs/migration.md): when a ported
    script branches on a tensor value inside ``to_static``/``jit``, the
    stock TracerBoolConversionError doesn't say what the paddle-level
    fix is. Append the playbook to the exception message."""
    tracer = jax.core.Tracer
    orig_bool = tracer.__bool__
    if getattr(orig_bool, '_pt_patched', False):
        return

    def __bool__(self):
        try:
            return orig_bool(self)
        except (jax.errors.TracerBoolConversionError,
                jax.errors.ConcretizationTypeError) as e:
            hint = (
                '\n\n[paddle_tpu] A Python `if`/`while` branched on a '
                'traced tensor inside jit/to_static. The reference '
                'captures this with SOT bytecode translation; the '
                'TPU-native fixes are:\n'
                '  - value-based branch  -> paddle_tpu.static.nn.cond'
                '(pred, true_fn, false_fn)\n'
                '  - value-based loop    -> paddle_tpu.static.nn.'
                'while_loop / lax.scan\n'
                '  - elementwise select  -> paddle_tpu.where(cond, a, b)\n'
                '  - shape/config branch -> hoist it out of the jitted '
                'function (it is static)\n'
                'See docs/migration.md ("control flow").')
            e.args = (str(e.args[0]) + hint,) + e.args[1:] if e.args else (
                hint,)
            raise

    __bool__._pt_patched = True
    try:
        tracer.__bool__ = __bool__
    except (AttributeError, TypeError):
        pass


def monkey_patch_tensor():
    """Bind the paddle Tensor method surface onto jax array classes.

    Idempotent; called once from ``paddle_tpu/__init__``.
    """
    _patch_trace_diagnostics()
    pt = _pt()
    special = _special_table()
    targets = _patch_targets()

    for _n in ('view',):   # consumed by tensor.manipulation.view
        orig = getattr(targets[0], _n, None)
        if orig is not None and _n not in _ORIGINALS:
            _ORIGINALS[_n] = orig

    names = set(TENSOR_METHOD_NAMES) | set(special)
    unresolved = []
    for name in sorted(names):
        fn = _resolve(name, pt, special)
        if fn is None:
            unresolved.append(name)
            continue
        _unbound[name] = fn
        if name in _KEEP_BUILTIN and hasattr(targets[0], name):
            continue
        allowed = _allowed_kwargs(fn)
        for cls in targets:
            if _is_descriptor(cls, name):
                # never shadow a property/getset like .shape/.real —
                # jax internals and paddle attribute-style access both
                # depend on it (paddle Tensor.shape is an attribute too)
                continue
            # first-capture the TRUE builtin per (cls, name): repeated
            # patching must not stack wrappers (idempotence)
            okey = (cls.__name__, name)
            if okey not in _ORIGINALS:
                orig = getattr(cls, name, None)
                _ORIGINALS[okey] = orig if callable(orig) else None
            try:
                setattr(cls, name, _make_method(
                    fn, name, orig=_ORIGINALS[okey], allowed=allowed))
            except (AttributeError, TypeError):  # immutable class
                pass

    for magic, opname in MAGIC_METHODS:
        # jax arrays already implement these; only fill genuine gaps.
        fn = getattr(pt, opname, None)
        for cls in targets:
            if fn is not None and not hasattr(cls, magic):
                try:
                    setattr(cls, magic, _make_method(fn, magic))
                except (AttributeError, TypeError):
                    pass

    for pname, prop in _PROPERTIES.items():
        for cls in targets:
            if not hasattr(cls, pname):
                try:
                    setattr(cls, pname, prop)
                except (AttributeError, TypeError):
                    pass

    return unresolved
