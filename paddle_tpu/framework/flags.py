"""Global flags (ref: paddle.set_flags / get_flags over FLAGS_* env).

Known flags map to jax config / XLA behaviour where a TPU equivalent
exists; unknown FLAGS_* are stored and readable (many reference flags
are CUDA-specific and intentionally inert here).
"""
from __future__ import annotations

import typing

_flags: typing.Dict[str, typing.Any] = {
    'FLAGS_cudnn_deterministic': False,
    'FLAGS_embedding_deterministic': 0,
    'FLAGS_check_nan_inf': False,
    'FLAGS_use_pallas_kernels': True,
    'FLAGS_default_dtype': 'float32',
}


def set_flags(flags: dict):
    """ref: paddle.set_flags."""
    import jax

    for k, v in flags.items():
        _flags[k] = v
        if k == 'FLAGS_cudnn_deterministic' and v:
            # TPU analogue: make XLA reductions deterministic
            jax.config.update('jax_default_matmul_precision', 'highest')
        if k == 'FLAGS_check_nan_inf':
            jax.config.update('jax_debug_nans', bool(v))


def get_flags(keys):
    """ref: paddle.get_flags."""
    if isinstance(keys, str):
        keys = [keys]
    return {k: _flags.get(k) for k in keys}
