"""Normalization layers (reference batch_norm_op.cc, layer_norm_op.cc,
sync_batch_norm_op.cu, python/paddle/nn/layer/norm.py).

SyncBatchNorm computes cross-replica statistics with lax.pmean inside
shard_map/pjit (the reference used a dedicated NCCL kernel).
"""
from __future__ import annotations

import numpy as np

from . import functional as F
from . import initializer as I
from .layer import Layer


class _NormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                [num_features], attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                [num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", np.zeros(num_features, np.float32))
        self.register_buffer("_variance", np.ones(num_features, np.float32))


class BatchNorm(_NormBase):
    """fluid.dygraph.BatchNorm parity (acts on axis 1)."""

    def forward(self, x):
        return F.batch_norm(x, self._mean, self._variance, self.weight,
                            self.bias, training=self.training,
                            momentum=self._momentum, epsilon=self._epsilon,
                            data_format=self._data_format,
                            use_global_stats=self._use_global_stats)


class BatchNorm1D(BatchNorm):
    pass


class BatchNorm2D(BatchNorm):
    pass


class BatchNorm3D(BatchNorm):
    pass


class SyncBatchNorm(BatchNorm):
    """Cross-replica BN (reference operators/sync_batch_norm_op.cu): when run
    inside shard_map over a data-parallel mesh axis, moments are averaged
    with lax.pmean over that axis."""

    axis_name = "data"

    def forward(self, x):
        import jax

        try:
            jax.core.get_axis_size(self.axis_name)  # inside pmap/shard_map?
            in_spmd = True
        except Exception:
            in_spmd = False
        if not in_spmd or not self.training:
            return super().forward(x)
        return self._sync_forward(x)

    def _sync_forward(self, x):
        import jax
        import jax.numpy as jnp

        from ..framework.op import primitive

        @primitive("sync_batch_norm")
        def _sync_bn(x, weight, bias, eps, axis_name):
            axes = tuple(i for i in range(x.ndim) if i != 1)
            mean = jax.lax.pmean(jnp.mean(x, axis=axes), axis_name)
            mean2 = jax.lax.pmean(jnp.mean(jnp.square(x), axis=axes), axis_name)
            var = mean2 - jnp.square(mean)
            shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
            out = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + eps)
            if weight is not None:
                out = out * weight.reshape(shape)
            if bias is not None:
                out = out + bias.reshape(shape)
            return out

        return _sync_bn(x, self.weight, self.bias, self._epsilon, self.axis_name)

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        """Recursively convert BatchNorm sublayers to SyncBatchNorm."""
        if isinstance(layer, BatchNorm) and not isinstance(layer, SyncBatchNorm):
            new = cls(layer._num_features, layer._momentum, layer._epsilon,
                      data_format=layer._data_format)
            new.weight, new.bias = layer.weight, layer.bias
            new._buffers["_mean"] = layer._mean
            new._buffers["_variance"] = layer._variance
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, (int, np.integer)):
            normalized_shape = (int(normalized_shape),)
        self._normalized_shape = tuple(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                self._normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias,
                            self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}"


class RMSNorm(Layer):
    """x / rms(x) * weight over the last axis; no mean, no bias. With
    ``zero_centered`` the scale is ``1 + weight`` and ``weight`` starts
    at zero: weight decay then pulls the scale to 1 and not to 0 (a
    different trained model, not another spelling of the same one)."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None,
                 name=None, zero_centered=False):
        super().__init__()
        self._epsilon = epsilon
        self._zero_centered = bool(zero_centered)
        self.weight = self.create_parameter(
            [int(hidden_size)], attr=weight_attr,
            default_initializer=I.Constant(
                0.0 if self._zero_centered else 1.0))

    def forward(self, x):
        scale = self.weight + 1.0 if self._zero_centered else self.weight
        return F.rms_norm(x, scale, self._epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            [num_channels], attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self.weight, self.bias,
                            self._epsilon, self._data_format)


class InstanceNorm1D(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCL",
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = None if weight_attr is False else self.create_parameter(
            [num_features], attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self._epsilon)


class InstanceNorm2D(InstanceNorm1D):
    pass


class InstanceNorm3D(InstanceNorm1D):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta,
                                     self.k, self.data_format)


class SpectralNorm(Layer):
    """Reference spectral_norm_op.cc: power iteration on a weight."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 name=None):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.weight_u = self.create_parameter(
            [h], default_initializer=I.Normal(0, 1))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(
            [w], default_initializer=I.Normal(0, 1))
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        import jax.numpy as jnp

        from ..framework.op import primitive
        from ..framework.tensor import Tensor

        w = weight
        mat = jnp.moveaxis(w.value if isinstance(w, Tensor) else w, self._dim, 0)
        h = mat.shape[0]
        mat = mat.reshape(h, -1)
        u, v = self.weight_u.value, self.weight_v.value
        for _ in range(self._power_iters):
            v = mat.T @ u
            v = v / (jnp.linalg.norm(v) + self._eps)
            u = mat @ v
            u = u / (jnp.linalg.norm(u) + self._eps)
        self.weight_u._value = u
        self.weight_v._value = v

        @primitive("spectral_norm")
        def _apply(weight, u, v, dim):
            mat = jnp.moveaxis(weight, dim, 0).reshape(weight.shape[dim], -1)
            sigma = u @ (mat @ v)
            return weight / sigma

        return _apply(weight, u, v, self._dim)
