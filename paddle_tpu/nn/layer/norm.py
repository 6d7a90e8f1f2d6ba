"""Norm layers (ref: python/paddle/nn/layer/norm.py).

BatchNorm keeps running stats as buffer leaves and updates them in-place
on the (possibly traced) layer object — returning the model from a jitted
train step carries the new stats out functionally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import functional as F
from .. import initializer as I
from .base import Buffer, Layer
from .common import _init_of


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format='NCHW', use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is not False:
            self.weight = self.create_parameter(
                (num_features,), initializer=_init_of(weight_attr) or I.Constant(1.0)
            )
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter((num_features,), is_bias=True)
        else:
            self.bias = None
        self.register_buffer('_mean', jnp.zeros((num_features,)))
        self.register_buffer('_variance', jnp.ones((num_features,)))

    def forward(self, x):
        training = self.training and not self.use_global_stats
        out, new_mean, new_var = F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=training, momentum=self.momentum, epsilon=self.epsilon,
            data_format=self.data_format,
        )
        if training:
            object.__setattr__(self, '_mean', new_mean)
            object.__setattr__(self, '_variance', new_var)
        return out


class BatchNorm(_BatchNormBase):
    pass


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format='NCL', use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, use_global_stats, name)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format='NCDHW', use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr,
                         data_format, use_global_stats, name)


class SyncBatchNorm(_BatchNormBase):
    """On TPU under pjit, per-device batch stats are already global when the
    batch axis is sharded and reductions run under GSPMD — XLA inserts the
    cross-replica psum. So SyncBatchNorm == BatchNorm in this framework
    (ref: nn/layer/norm.py::SyncBatchNorm, which wraps NCCL allreduce).
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is not False:
            self.weight = self.create_parameter(
                self.normalized_shape, initializer=_init_of(weight_attr) or I.Constant(1.0)
            )
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter(self.normalized_shape, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias, self.epsilon)


class RMSNorm(Layer):
    """ref: paddle.incubate.nn.FusedRMSNorm / Llama RMSNorm."""

    def __init__(self, normalized_shape, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            self.normalized_shape, initializer=_init_of(weight_attr) or I.Constant(1.0)
        )

    def forward(self, x):
        from ...ops import rms_norm as fused_rms_norm

        with jax.named_scope('norm'):
            return fused_rms_norm(x, self.weight, self.epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format='NCHW', name=None):
        super().__init__()
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.epsilon = epsilon
        self.data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            (num_channels,), initializer=_init_of(weight_attr) or I.Constant(1.0)
        )
        self.bias = None if bias_attr is False else self.create_parameter(
            (num_channels,), is_bias=True
        )

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.weight, self.bias, self.epsilon, self.data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format='NCHW', name=None):
        super().__init__()
        self.num_features = num_features
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is not False:
            self.weight = self.create_parameter(
                (num_features,), initializer=_init_of(weight_attr) or I.Constant(1.0)
            )
            self.bias = self.create_parameter((num_features,), is_bias=True)
        else:
            self.weight = None
            self.bias = None

    def forward(self, x):
        return F.instance_norm(x, self.weight, self.bias, self.epsilon, self.data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format='NCHW', name=None):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta, self.k, self.data_format)


class SpectralNorm(Layer):
    """Power-iteration spectral norm of a weight (ref: nn/layer/norm.py)."""

    def __init__(self, weight_shape, axis=0, power_iters=1, epsilon=1e-12, dtype='float32'):
        super().__init__()
        self.axis = axis
        self.power_iters = power_iters
        self.epsilon = epsilon
        import numpy as np

        h = weight_shape[axis]
        w = int(np.prod(weight_shape)) // h
        from ...framework import random as random_mod
        import jax

        self.register_buffer('weight_u', jax.random.normal(random_mod.split_key(), (h,)))
        self.register_buffer('weight_v', jax.random.normal(random_mod.split_key(), (w,)))

    def forward(self, weight):
        w_mat = jnp.moveaxis(weight, self.axis, 0).reshape(weight.shape[self.axis], -1)
        u, v = self.weight_u, self.weight_v
        for _ in range(self.power_iters):
            v = w_mat.T @ u
            v = v / (jnp.linalg.norm(v) + self.epsilon)
            u = w_mat @ v
            u = u / (jnp.linalg.norm(u) + self.epsilon)
        object.__setattr__(self, 'weight_u', u)
        object.__setattr__(self, 'weight_v', v)
        sigma = u @ w_mat @ v
        return weight / sigma
