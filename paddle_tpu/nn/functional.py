"""nn functional ops.

Parity with the reference NN operator set (/root/reference/paddle/fluid/
operators/: activation_op.cc, conv_op.cc, pool_op.cc, batch_norm_op.cc,
layer_norm_op.cc, softmax_op.cc, cross_entropy_op.cc, dropout_op.cc,
lookup_table_v2_op.cc, interpolate_op.cc ...). Convs/matmuls lower to MXU
via lax.conv_general_dilated / dot_general; everything else is fusable
elementwise work for the VPU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtype_mod
from ..framework.op import primitive
from ..framework.random import next_rng_key
from ..framework.tensor import Tensor, unwrap

# ---------------------------------------------------------------------------
# activations (activation_op.cc)
# ---------------------------------------------------------------------------


@primitive("relu")
def relu(x, name=None):
    return jax.nn.relu(x)


@primitive("relu2")
def relu2(x, name=None):
    """relu(x) squared."""
    return jnp.square(jax.nn.relu(x))


@primitive("relu6")
def relu6(x, name=None):
    return jnp.clip(x, 0.0, 6.0)


@primitive("leaky_relu")
def leaky_relu(x, negative_slope=0.01, name=None):
    return jnp.where(x >= 0, x, negative_slope * x)


@primitive("prelu_fn")
def prelu(x, weight, data_format="NCHW", name=None):
    if weight.size > 1:
        shape = [1] * x.ndim
        axis = 1 if data_format == "NCHW" else x.ndim - 1
        shape[axis] = weight.size
        weight = weight.reshape(shape)
    return jnp.where(x >= 0, x, weight * x)


@primitive("elu")
def elu(x, alpha=1.0, name=None):
    return jax.nn.elu(x, alpha)


@primitive("selu")
def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


@primitive("celu")
def celu(x, alpha=1.0, name=None):
    return jax.nn.celu(x, alpha)


@primitive("gelu")
def gelu(x, approximate=False, name=None):
    return jax.nn.gelu(x, approximate=approximate)


@primitive("silu")
def silu(x, name=None):
    return jax.nn.silu(x)


swish = silu


@primitive("hardswish")
def hardswish(x, name=None):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


@primitive("hardsigmoid")
def hardsigmoid(x, slope=1.0 / 6.0, offset=0.5, name=None):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


@primitive("hardtanh")
def hardtanh(x, min=-1.0, max=1.0, name=None):
    return jnp.clip(x, min, max)


@primitive("hardshrink")
def hardshrink(x, threshold=0.5, name=None):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@primitive("softshrink")
def softshrink(x, threshold=0.5, name=None):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


@primitive("tanhshrink")
def tanhshrink(x, name=None):
    return x - jnp.tanh(x)


@primitive("softplus")
def softplus(x, beta=1.0, threshold=20.0, name=None):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jnp.log1p(jnp.exp(scaled)) / beta)


@primitive("softsign")
def softsign(x, name=None):
    return x / (1.0 + jnp.abs(x))


@primitive("mish")
def mish(x, name=None):
    return x * jnp.tanh(jax.nn.softplus(x))


@primitive("thresholded_relu")
def thresholded_relu(x, threshold=1.0, name=None):
    return jnp.where(x > threshold, x, 0.0)


@primitive("maxout")
def maxout(x, groups, axis=1, name=None):
    c = x.shape[axis]
    axis = axis % x.ndim
    new_shape = x.shape[:axis] + (c // groups, groups) + x.shape[axis + 1:]
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


@primitive("softmax")
def softmax(x, axis=-1, dtype=None, name=None):
    if dtype is not None:
        x = x.astype(dtype_mod.convert_dtype(dtype))
    return jax.nn.softmax(x, axis=axis)


@primitive("log_softmax")
def log_softmax(x, axis=-1, dtype=None, name=None):
    if dtype is not None:
        x = x.astype(dtype_mod.convert_dtype(dtype))
    return jax.nn.log_softmax(x, axis=axis)


@primitive("gumbel_softmax")
def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    g = jax.random.gumbel(next_rng_key(), x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        hard_y = jnp.zeros_like(y).at[...].set(0.0)
        hard_y = jnp.where(
            jnp.arange(y.shape[axis]).reshape(
                [-1 if i == axis % y.ndim else 1 for i in range(y.ndim)]) == idx,
            1.0, 0.0)
        # straight-through estimator
        y = hard_y - jax.lax.stop_gradient(y) + y
    return y


@primitive("sigmoid_fn")
def sigmoid(x, name=None):
    return jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# linear / embedding (mul_op.cc fc, lookup_table_v2_op.cc)
# ---------------------------------------------------------------------------


@primitive("linear")
def linear(x, weight, bias=None, name=None):
    if x.ndim < 1 or weight.ndim != 2 or x.shape[-1] != weight.shape[0]:
        from ..framework.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"linear: input features {tuple(x.shape)}[-1] must match "
            f"weight rows {tuple(weight.shape)} — W is (in_features, "
            "out_features) in this framework (reference fc/mul op)")
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


@primitive("embedding_fn")
def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    if not jnp.issubdtype(x.dtype, jnp.integer):
        from ..framework.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"embedding: ids must be an integer tensor, got {x.dtype} "
            f"shape {tuple(x.shape)} (cast labels/ids with "
            ".astype('int64'))")
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out


@primitive("one_hot")
def one_hot(x, num_classes, name=None):
    return jax.nn.one_hot(x, num_classes, dtype=dtype_mod.get_default_dtype())


# ---------------------------------------------------------------------------
# dropout family (dropout_op.cc)
# ---------------------------------------------------------------------------


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return _scale_only(x, factor=1.0 - p)
        return x if isinstance(x, Tensor) else Tensor(x)
    return _dropout(x, p=p, axis=axis, mode=mode, key=next_rng_key())


@primitive("dropout")
def _dropout(x, p, axis, mode, key):
    if axis is None:
        shape = x.shape
    else:
        axes = [axis] if isinstance(axis, int) else list(axis)
        shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0)
    return jnp.where(keep, x, 0.0)


@primitive("scale_only")
def _scale_only(x, factor):
    return x * factor


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    return _alpha_dropout(x, p=p, key=next_rng_key())


@primitive("alpha_dropout")
def _alpha_dropout(x, p, key):
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = (1.0 / math.sqrt((1.0 - p) * (1.0 + p * alpha_p ** 2))) if p < 1 else 0.0
    b = -a * alpha_p * p
    return a * jnp.where(keep, x, alpha_p) + b


# ---------------------------------------------------------------------------
# convolutions (conv_op.cc / conv_transpose_op.cc) — MXU path
# ---------------------------------------------------------------------------


def _tuple_n(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(x) for x in v)


def _conv_padding(padding, n, strides=None):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, (int, np.integer)):
        return [(int(padding),) * 2] * n
    padding = list(padding)
    if len(padding) == n:
        if isinstance(padding[0], (list, tuple)):
            return [tuple(int(v) for v in p) for p in padding]
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)]
    raise ValueError(f"Bad padding {padding}")


def _conv(x, weight, bias, stride, padding, dilation, groups, n, channel_last):
    from ..framework.errors import InvalidArgumentError

    if x.ndim != n + 2 or weight.ndim != n + 2:
        raise InvalidArgumentError(
            f"conv{n}d: expected rank-{n + 2} input and weight, got "
            f"input {tuple(x.shape)} and weight {tuple(weight.shape)}")
    cin = x.shape[-1] if channel_last else x.shape[1]
    if cin != weight.shape[1] * groups:
        raise InvalidArgumentError(
            f"conv{n}d: input {tuple(x.shape)} "
            f"({'channel-last' if channel_last else 'channel-first'}, "
            f"C_in={cin}) is incompatible with weight "
            f"{tuple(weight.shape)} — weight layout is (C_out, "
            f"C_in/groups, *kernel) and needs C_in == "
            f"{weight.shape[1]} * groups({groups})")
    stride = _tuple_n(stride, n)
    dilation = _tuple_n(dilation, n)
    pad = _conv_padding(padding, n)
    if channel_last:
        spatial = "DHW"[-n:]
        lhs_spec = "N" + spatial + "C"
    else:
        spatial = "DHW"[-n:]
        lhs_spec = "NC" + spatial
    rhs_spec = "OI" + spatial
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        (lhs_spec, rhs_spec, lhs_spec))
    # no preferred_element_type here: the TPU MXU accumulates bf16 convs
    # in f32 natively, and requesting an f32 output makes the conv
    # transpose rule see an f32 cotangent against bf16 operands (dtype
    # mismatch at trace time under value_and_grad)
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        bshape = [1] * out.ndim
        bshape[-1 if channel_last else 1] = bias.shape[0]
        out = out + bias.reshape(bshape)
    return out


def short_conv(x, taps, bias=None):
    """Causal depthwise convolution along the sequence, on jax arrays
    (the token mixers call it inside their primitives): x (B, T, C), taps
    (W, C), bias (C,) or None; the last tap multiplies the current
    token."""
    width = taps.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + t] * taps[j] for j in range(width))
    return out if bias is None else out + bias


@primitive("conv1d")
def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 1,
                 channel_last=data_format == "NLC")


@primitive("conv2d")
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 2,
                 channel_last=data_format == "NHWC")


@primitive("conv3d")
def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 3,
                 channel_last=data_format == "NDHWC")


def _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation,
                    groups, n, channel_last):
    stride = _tuple_n(stride, n)
    dilation = _tuple_n(dilation, n)
    output_padding = _tuple_n(output_padding, n)
    if isinstance(padding, str):
        raise ValueError("string padding unsupported for conv_transpose")
    pad = _conv_padding(padding, n)
    if channel_last:
        spatial = "DHW"[-n:]
        lhs_spec = "N" + spatial + "C"
    else:
        spatial = "DHW"[-n:]
        lhs_spec = "NC" + spatial
    rhs_spec = "IO" + spatial  # paddle stores transpose weight as (Cin, Cout/g, K...)
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        (lhs_spec, rhs_spec, lhs_spec))
    # gradient-of-conv formulation: lhs_dilation=stride
    k = [(weight.shape[2 + i] - 1) * dilation[i] for i in range(n)]
    tpad = [(k[i] - pad[i][0], k[i] - pad[i][1] + output_padding[i])
            for i in range(n)]
    if groups > 1:
        # weight (Cin, Cout/g, K) -> grouped transpose conv via reshape
        cin = weight.shape[0]
        w = weight.reshape(groups, cin // groups, *weight.shape[1:])
        w = jnp.flip(w, axis=tuple(range(3, 3 + n)))
        w = jnp.swapaxes(w, 1, 2)  # (g, Cout/g, Cin/g, K)
        w = w.reshape(groups * w.shape[1], *w.shape[2:])  # (Cout, Cin/g, K)
        dn2 = jax.lax.conv_dimension_numbers(
            x.shape, w.shape, (lhs_spec, "OI" + spatial, lhs_spec))
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=(1,) * n, padding=tpad,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn2,
            feature_group_count=groups)
    else:
        w = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
        w = jnp.swapaxes(w, 0, 1)  # (Cout, Cin, K)
        dn2 = jax.lax.conv_dimension_numbers(
            x.shape, w.shape, (lhs_spec, "OI" + spatial, lhs_spec))
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=(1,) * n, padding=tpad,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn2)
    if bias is not None:
        bshape = [1] * out.ndim
        bshape[-1 if channel_last else 1] = bias.shape[0]
        out = out + bias.reshape(bshape)
    return out


@primitive("conv1d_transpose")
def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCL", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 1, data_format == "NLC")


@primitive("conv2d_transpose")
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 2, data_format == "NHWC")


@primitive("conv3d_transpose")
def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 3, data_format == "NDHWC")


# ---------------------------------------------------------------------------
# pooling (pool_op.cc)
# ---------------------------------------------------------------------------


def _pool(x, kernel, stride, padding, n, channel_last, op, ceil_mode=False,
          count_include_pad=True):
    kernel = _tuple_n(kernel, n)
    stride = _tuple_n(stride if stride is not None else kernel, n)
    pad = _conv_padding(padding, n)
    if channel_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = [(0, 0)] + (pad if isinstance(pad, list) else pad) + [(0, 0)] if not isinstance(pad, str) else pad
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = [(0, 0), (0, 0)] + pad if not isinstance(pad, str) else pad
    if isinstance(pads, str):
        pads = jax.lax.padtype_to_pads(x.shape, window, strides, pads)
    if ceil_mode:
        pads = list(pads)
        spatial_off = 1 if channel_last else 2
        for i in range(n):
            dim = spatial_off + i
            size = x.shape[dim] + pads[dim][0] + pads[dim][1]
            rem = (size - kernel[i]) % stride[i]
            if rem != 0:
                pads[dim] = (pads[dim][0], pads[dim][1] + stride[i] - rem)
    if op == "max":
        init = -jnp.inf if dtype_mod.is_floating(x.dtype) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if count_include_pad:
        denom = float(np.prod(kernel))
        return ssum / denom
    ones = jnp.ones_like(x)
    counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
    return ssum / counts


@primitive("max_pool1d")
def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCL", name=None):
    return _pool(x, kernel_size, stride, padding, 1, data_format == "NLC",
                 "max", ceil_mode)


@primitive("max_pool2d")
def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    return _pool(x, kernel_size, stride, padding, 2, data_format == "NHWC",
                 "max", ceil_mode)


@primitive("max_pool3d")
def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    return _pool(x, kernel_size, stride, padding, 3, data_format == "NDHWC",
                 "max", ceil_mode)


@primitive("avg_pool1d")
def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCL", name=None):
    return _pool(x, kernel_size, stride, padding, 1, data_format == "NLC",
                 "avg", ceil_mode, count_include_pad=not exclusive)


@primitive("avg_pool2d")
def avg_pool2d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 2, data_format == "NHWC",
                 "avg", ceil_mode, count_include_pad=not exclusive)


@primitive("avg_pool3d")
def avg_pool3d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCDHW", name=None):
    return _pool(x, kernel_size, stride, padding, 3, data_format == "NDHWC",
                 "avg", ceil_mode, count_include_pad=not exclusive)


def _adaptive_pool(x, output_size, n, op, channel_last=False):
    out_sizes = _tuple_n(output_size, n)
    spatial_off = 1 if channel_last else 2
    out = x
    for i in range(n):
        dim = spatial_off + i
        in_size = out.shape[dim]
        o = out_sizes[i] if out_sizes[i] is not None else in_size
        if in_size % o == 0:
            k = in_size // o
            shape = out.shape[:dim] + (o, k) + out.shape[dim + 1:]
            r = out.reshape(shape)
            out = jnp.max(r, axis=dim + 1) if op == "max" else jnp.mean(r, axis=dim + 1)
        else:
            # general adaptive: gather variable windows
            starts = (np.arange(o) * in_size) // o
            ends = ((np.arange(o) + 1) * in_size + o - 1) // o
            segs = []
            for s, e in zip(starts, ends):
                sl = jax.lax.slice_in_dim(out, int(s), int(e), axis=dim)
                red = jnp.max(sl, axis=dim, keepdims=True) if op == "max" \
                    else jnp.mean(sl, axis=dim, keepdims=True)
                segs.append(red)
            out = jnp.concatenate(segs, axis=dim)
    return out


@primitive("adaptive_avg_pool1d")
def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_pool(x, output_size, 1, "avg")


@primitive("adaptive_avg_pool2d")
def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool(x, output_size, 2, "avg", data_format == "NHWC")


@primitive("adaptive_avg_pool3d")
def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_pool(x, output_size, 3, "avg", data_format == "NDHWC")


@primitive("adaptive_max_pool1d")
def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    return _adaptive_pool(x, output_size, 1, "max")


@primitive("adaptive_max_pool2d")
def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive_pool(x, output_size, 2, "max")


@primitive("adaptive_max_pool3d")
def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    return _adaptive_pool(x, output_size, 3, "max")


# ---------------------------------------------------------------------------
# normalization (batch_norm_op.cc, layer_norm_op.cc, group_norm_op.cc,
# instance_norm_op.cc, norm_op.cc)
# ---------------------------------------------------------------------------


@primitive("layer_norm")
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = (int(normalized_shape),)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Stateful wrapper: updates running stats in training mode (eager)."""
    axis = _bn_axis(unwrap(x).ndim, data_format)
    use_stats = (not training) if use_global_stats is None else use_global_stats
    if use_stats:
        return _batch_norm_infer(x, running_mean, running_var, weight, bias,
                                 epsilon=epsilon, axis=axis)
    out, mean, var = _batch_norm_train(x, weight, bias, epsilon=epsilon,
                                       axis=axis)
    if isinstance(running_mean, Tensor):
        m = unwrap(mean)
        v = unwrap(var)
        running_mean._value = momentum * running_mean._value + (1 - momentum) * m
        running_var._value = momentum * running_var._value + (1 - momentum) * v
    return out


def _bn_axis(ndim, data_format):
    if data_format in ("NCHW", "NCL", "NCDHW", "NC"):
        return 1
    return ndim - 1


@primitive("batch_norm_infer")
def _batch_norm_infer(x, mean, var, weight, bias, epsilon, axis):
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    out = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@primitive("batch_norm_train")
def _batch_norm_train(x, weight, bias, epsilon, axis):
    axes = tuple(i for i in range(x.ndim) if i != axis)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    out = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, mean, var


@primitive("group_norm_fn")
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None):
    if data_format != "NCHW" and x.ndim == 4:
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    r = x.reshape(n, g, c // g, *x.shape[2:])
    axes = tuple(range(2, r.ndim))
    mean = jnp.mean(r, axis=axes, keepdims=True)
    var = jnp.var(r, axis=axes, keepdims=True)
    out = ((r - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = [1, c] + [1] * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if data_format != "NCHW" and out.ndim == 4:
        out = jnp.moveaxis(out, 1, -1)
    return out


@primitive("instance_norm_fn")
def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
        out = out * weight.reshape(shape)
    if bias is not None:
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
        out = out + bias.reshape(shape)
    return out


@primitive("local_response_norm")
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    chan_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    sq = jnp.square(x)
    half = size // 2
    pads = [(0, 0)] * x.ndim
    pads[chan_axis] = (half, size - half - 1)
    sq = jnp.pad(sq, pads)
    window = [1] * x.ndim
    window[chan_axis] = size
    ssum = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(window),
                                 (1,) * x.ndim, [(0, 0)] * x.ndim)
    return x / jnp.power(k + alpha * ssum, beta)


@primitive("normalize")
def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    nrm = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(nrm, epsilon)


l2_normalize = normalize


@primitive("rms_norm")
def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    return out


# ---------------------------------------------------------------------------
# losses (cross_entropy_op.cc, softmax_with_cross_entropy_op.cc, ...)
# ---------------------------------------------------------------------------


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


@primitive("fused_linear_cross_entropy", nondiff=("label",))
def fused_linear_cross_entropy(h, weight, bias, label, ignore_index=-100,
                               reduction="mean", name=None):
    """softmax-xent of (h @ weight^T + bias) without materialising the
    (rows, vocab) logits in HBM: the Pallas kernel streams vocab tiles
    with an online logsumexp (ops/pallas/fused_xent.py — the MLM head's
    ~1 GB logits round-trips were the top non-MXU cost at bert512).
    weight: (V, H) (embedding layout, tied-decoder ready); falls back to
    the equivalent XLA computation off-TPU. ``reduction``: ``"mean"``
    over the labelled rows, or ``"none"``: each row's loss in ``label``'s
    shape, float32, zero where the label is ``ignore_index``."""
    from ..ops.pallas.fused_xent import fused_linear_cross_entropy as core

    return core(h, weight, bias, label, ignore_index=ignore_index,
                reduction=reduction)


@primitive("expected_exit_loss", nondiff=("label",))
def expected_exit_loss(pass_loss, gate_logit, label, beta=0.0,
                       ignore_index=-100, name=None):
    """A looped model's training loss: the expected loss under its exit
    distribution, with an entropy term (a uniform prior over exits).

    ``pass_loss`` (T, ...): each position's loss after pass 1 .. T;
    ``gate_logit`` (T - 1, ...): the exit gate's logit ``a^t`` after
    pass 1 .. T - 1; ``label`` (...): positions with ``ignore_index``
    count for nothing. In float32, from log-sigmoids:

        log p^t = log sigmoid(a^t) + sum_{j<t} log sigmoid(-a^j)   t < T
        log p^T = sum_{j<T} log sigmoid(-a^j)         (the p sum to one)
        loss = mean_i [sum_t p^t_i l^t_i + beta sum_t p^t_i log p^t_i]

    The mean is over the labelled positions."""
    loss = pass_loss.astype(jnp.float32)
    a = gate_logit.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-a), axis=0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay], axis=0)
    log_p = before + jnp.concatenate(
        [jax.nn.log_sigmoid(a), jnp.zeros_like(stay[:1])], axis=0)
    p = jnp.exp(log_p)
    each = jnp.sum(p * (loss + beta * log_p), axis=0)
    valid = label != ignore_index
    count = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(jnp.where(valid, each, 0.0)) / count


@primitive("softmax_with_cross_entropy")
def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    if use_softmax:
        logp = jax.nn.log_softmax(input, axis=axis)
    else:
        logp = jnp.log(jnp.maximum(input, 1e-30))
    n_classes = input.shape[axis]
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis)
        if reduction == "mean":
            return jnp.mean(loss)
        return _reduce_loss(loss, reduction)
    lbl = label
    if not jnp.issubdtype(lbl.dtype, jnp.integer):
        from ..framework.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"cross_entropy: hard labels must be integer class ids, got "
            f"{lbl.dtype} {tuple(lbl.shape)}; pass soft_label=True for "
            "probability targets")
    if lbl.ndim == logp.ndim:
        lbl = jnp.squeeze(lbl, axis=axis)
    elif lbl.ndim != logp.ndim - 1:
        from ..framework.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"cross_entropy: label shape {tuple(label.shape)} must be "
            f"logits shape {tuple(input.shape)} without the class axis "
            f"(or with a trailing 1)")
    if label_smoothing > 0.0:
        onehot = jax.nn.one_hot(lbl, n_classes, dtype=logp.dtype, axis=axis)
        soft = onehot * (1 - label_smoothing) + label_smoothing / n_classes
        loss = -jnp.sum(soft * logp, axis=axis)
    else:
        safe_lbl = jnp.where(lbl == ignore_index, 0, lbl)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(safe_lbl, axis), axis=axis)
        loss = -jnp.squeeze(picked, axis=axis)
    valid = lbl != ignore_index
    loss = jnp.where(valid, loss, 0.0)
    if weight is not None:
        w = jnp.take(weight, jnp.where(valid, lbl, 0))
        loss = loss * w
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, w, 0.0)), 1e-12)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
    return _reduce_loss(loss, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    from ..framework.tensor import Tensor as _T

    loss_nd = loss
    if not soft_label:
        lu = unwrap(label)
        if lu.ndim < unwrap(logits).ndim:
            from . import functional as F  # noqa

            loss_nd = _unsqueeze_like(loss, axis=axis)
    if return_softmax:
        return loss_nd, softmax(logits, axis=axis)
    return loss_nd


@primitive("unsqueeze_like")
def _unsqueeze_like(x, axis):
    return jnp.expand_dims(x, axis)


@primitive("nll_loss")
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    picked = jnp.take_along_axis(input, label[..., None], axis=-1)[..., 0]
    loss = -picked
    valid = label != ignore_index
    loss = jnp.where(valid, loss, 0.0)
    if weight is not None:
        w = jnp.take(weight, jnp.where(valid, label, 0))
        loss = loss * w
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, w, 0.0)), 1e-12)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
    return _reduce_loss(loss, reduction)


@primitive("binary_cross_entropy")
def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.maximum(input, eps)) +
             (1 - label) * jnp.log(jnp.maximum(1 - input, eps)))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


@primitive("sigmoid_cross_entropy_with_logits")
def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    neg_abs = -jnp.abs(logit)
    base = jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(neg_abs))
    if pos_weight is not None:
        log_weight = 1 + (pos_weight - 1) * label
        base = jnp.maximum(logit, 0) - logit * label + \
            log_weight * jnp.log1p(jnp.exp(neg_abs)) + \
            (log_weight - 1) * jnp.maximum(-logit, 0)
    if weight is not None:
        base = base * weight
    return _reduce_loss(base, reduction)


@primitive("mse_loss")
def mse_loss(input, label, reduction="mean", name=None):
    return _reduce_loss(jnp.square(input - label), reduction)


@primitive("l1_loss")
def l1_loss(input, label, reduction="mean", name=None):
    return _reduce_loss(jnp.abs(input - label), reduction)


@primitive("smooth_l1_loss")
def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    d = jnp.abs(input - label)
    loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    return _reduce_loss(loss, reduction)


@primitive("huber_loss")
def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    d = jnp.abs(input - label)
    loss = jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    return _reduce_loss(loss, reduction)


@primitive("kl_div")
def kl_div(input, label, reduction="mean", name=None):
    loss = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce_loss(loss, reduction)


@primitive("margin_ranking_loss")
def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return _reduce_loss(jnp.maximum(0.0, -label * (input - other) + margin),
                        reduction)


@primitive("hinge_embedding_loss")
def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    loss = jnp.where(label == 1.0, input, jnp.maximum(0.0, margin - input))
    return _reduce_loss(loss, reduction)


@primitive("cosine_embedding_loss")
def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    cos = jnp.sum(input1 * input2, axis=-1) / jnp.maximum(
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1),
        1e-12)
    loss = jnp.where(label == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce_loss(loss, reduction)


@primitive("triplet_margin_loss")
def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def pdist(a, b):
        return jnp.sum(jnp.abs(a - b + epsilon) ** p, axis=-1) ** (1.0 / p)

    dp = pdist(input, positive)
    dn = pdist(input, negative)
    if swap:
        dn = jnp.minimum(dn, pdist(positive, negative))
    return _reduce_loss(jnp.maximum(dp - dn + margin, 0.0), reduction)


@primitive("square_error_cost")
def square_error_cost(input, label):
    return jnp.square(input - label)


@primitive("log_loss")
def log_loss(input, label, epsilon=1e-4, name=None):
    return -label * jnp.log(input + epsilon) - \
        (1 - label) * jnp.log(1 - input + epsilon)


@primitive("ctc_loss_fn")
def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False, name=None):
    """CTC forward (operators/warpctc_op.cc parity) as a lax.scan DP."""
    # log_probs: (T, B, C) log-softmax scores; labels: (B, L)
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    ext = jnp.full((B, S), blank, dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    neg_inf = jnp.asarray(-1e30, log_probs.dtype)

    emit = jnp.take_along_axis(
        jnp.transpose(log_probs, (1, 0, 2)),  # (B, T, C)
        jnp.broadcast_to(ext[:, None, :], (B, T, S)), axis=2)  # (B,T,S)
    emit = jnp.transpose(emit, (1, 0, 2))  # (T, B, S)

    can_skip = jnp.concatenate(
        [jnp.zeros((B, 2), bool),
         (ext[:, 2:] != ext[:, :-2]) & (ext[:, 2:] != blank)], axis=1)

    alpha0 = jnp.full((B, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(emit[0, :, 0])
    alpha0 = alpha0.at[:, 1].set(jnp.where(L > 0, emit[0, :, 1], neg_inf))

    def step(alpha, e):
        shift1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], 1)
        shift2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], 1)
        shift2 = jnp.where(can_skip, shift2, neg_inf)
        new = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2) + e
        return new, new

    _, alphas = jax.lax.scan(step, alpha0, emit[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, B, S)

    t_idx = jnp.clip(input_lengths - 1, 0, T - 1)
    final = alphas[t_idx, jnp.arange(B)]  # (B, S)
    s_last = 2 * label_lengths  # blank after last label
    ll = jnp.logaddexp(
        jnp.take_along_axis(final, s_last[:, None], axis=1)[:, 0],
        jnp.take_along_axis(final, jnp.maximum(s_last - 1, 0)[:, None], axis=1)[:, 0])
    loss = -ll
    if norm_by_times:
        loss = loss / input_lengths.astype(loss.dtype)
    if reduction == "mean":
        return jnp.mean(loss / jnp.maximum(label_lengths, 1).astype(loss.dtype))
    return _reduce_loss(loss, reduction)


# ---------------------------------------------------------------------------
# attention — see ops/pallas/flash_attention.py for the fused TPU kernel
# (reference fused op: operators/fused/multihead_matmul_op.cu)
# ---------------------------------------------------------------------------


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None, window=None):
    """query: (B, L, H, D) paddle layout; key/value: (B, L, Hkv, D) with
    H a multiple of Hkv (grouped-query attention: query head h reads key
    head h // (H / Hkv); Hkv == H is multi-head attention). ``window``,
    with ``is_causal``, keeps the keys ``0 <= i - j < window`` of query
    i: a sliding window that holds the query's own position."""
    use_dropout = dropout_p > 0.0 and training
    return _sdpa(query, key, value, attn_mask,
                 dropout_p=dropout_p if use_dropout else 0.0,
                 is_causal=is_causal,
                 key_rng=next_rng_key() if use_dropout else None,
                 window=window)


@primitive("sdpa")
def _sdpa(q, k, v, mask, dropout_p, is_causal, key_rng, window=None):
    from ..ops.pallas.flash_attention import flash_attention_or_fallback

    return flash_attention_or_fallback(q, k, v, mask, dropout_p, is_causal,
                                       key_rng, window=window)


# ---------------------------------------------------------------------------
# rotary position embedding (the rotate_half convention of the Hugging
# Face decoders: channel i pairs with channel i + D/2)
# ---------------------------------------------------------------------------


def rope_inv_freq(head_dim, theta=10000.0):
    """Plain rotary inverse frequencies ``theta ** (-2i / D)``, i = 0 ..
    D/2 - 1, float64 numpy: a constant of the layer that holds it."""
    return 1.0 / float(theta) ** (np.arange(0, head_dim, 2,
                                            dtype=np.float64) / head_dim)


def yarn_inv_freq(head_dim, theta, factor, original_max_position,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN's inverse frequencies (Hugging Face
    ``_compute_yarn_parameters``): frequency i is interpolated (divided
    by ``factor``) where it turns fewer than ``beta_slow`` times over
    ``original_max_position`` positions, kept where it turns more than
    ``beta_fast`` times, and blended linearly between: with ``c(r) = D
    ln(P / (2 pi r)) / (2 ln theta)``, ``low = floor(c(beta_fast))`` and
    ``high = ceil(c(beta_slow))`` clamped to [0, D - 1], ``ramp_i =
    clip((i - low) / (high - low), 0, 1)`` and ``inv_freq = interp * ramp
    + extrap * (1 - ramp)``. Returns ``(inv_freq float64 (D/2,), low,
    high)``; the scale YaRN puts on cos and sin (``attention_factor``,
    0.1 ln(factor) + 1 by default) is :func:`rotary_embedding`'s
    ``scale``."""
    def turns_to_dim(turns):
        return head_dim * math.log(original_max_position
                                   / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_to_dim(beta_fast)), 0)
    high = min(math.ceil(turns_to_dim(beta_slow)), head_dim - 1)
    extrap = rope_inv_freq(head_dim, theta)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return extrap / factor * ramp + extrap * (1.0 - ramp), low, high


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


@primitive("rotary_embedding")
def rotary_embedding(x, inv_freq, scale=1.0, positions=None, name=None):
    """Rotate x (B, L, H, D) by its positions: ``x cos + rotate_half(x)
    sin`` with ``cos, sin`` of ``positions[:, None] * inv_freq`` repeated
    over both halves of D and multiplied by ``scale``. The tables are
    float32, cast to x's type for the product. ``positions`` (L,)
    defaults to 0 .. L - 1."""
    with jax.named_scope("rotary_embedding"):
        length = x.shape[1]
        pos = jnp.arange(length, dtype=jnp.float32) if positions is None \
            else jnp.asarray(positions, jnp.float32)
        angles = pos[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
        angles = jnp.concatenate([angles, angles], axis=-1)   # (L, D)
        cos = (jnp.cos(angles) * scale).astype(x.dtype)[None, :, None, :]
        sin = (jnp.sin(angles) * scale).astype(x.dtype)[None, :, None, :]
        return x * cos + _rotate_half(x) * sin


# ---------------------------------------------------------------------------
# misc nn (interpolate_op.cc, pixel_shuffle_op.cc, pad ops, ...)
# ---------------------------------------------------------------------------


@primitive("interpolate")
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if not channel_last:
        perm = (0,) + tuple(range(2, x.ndim)) + (1,)
        xcl = jnp.transpose(x, perm)
    else:
        xcl = x
    spatial = xcl.shape[1:-1]
    if size is None:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(spatial)
        size = [int(s * f) for s, f in zip(spatial, scale_factor)]
    size = tuple(int(s) for s in size)
    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
    if align_corners and jmode == "linear":
        # jax.image.resize is half-pixel-center only; do per-dim linear
        # interp with endpoint-preserving src = i*(in-1)/(out-1) sampling.
        out = xcl
        for d, o in enumerate(size):
            dim = 1 + d
            n = out.shape[dim]
            if n == o:
                continue
            if o == 1 or n == 1:
                src = jnp.zeros((o,))
            else:
                src = jnp.arange(o) * (n - 1) / (o - 1)
            lo = jnp.clip(jnp.floor(src).astype(jnp.int32), 0, n - 1)
            hi = jnp.clip(lo + 1, 0, n - 1)
            w = (src - lo).astype(out.dtype)
            shape = [1] * out.ndim
            shape[dim] = o
            w = w.reshape(shape)
            out = (jnp.take(out, lo, axis=dim) * (1 - w) +
                   jnp.take(out, hi, axis=dim) * w)
    else:
        out = jax.image.resize(
            xcl, (xcl.shape[0],) + size + (xcl.shape[-1],), method=jmode)
    if not channel_last:
        inv = (0, x.ndim - 1) + tuple(range(1, x.ndim - 1))
        out = jnp.transpose(out, inv)
    return out


upsample = interpolate


@primitive("pixel_shuffle")
def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        out = x.reshape(n, c // (r * r), r, r, h, w)
        out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
        return out.reshape(n, c // (r * r), h * r, w * r)
    n, h, w, c = x.shape
    out = x.reshape(n, h, w, r, r, c // (r * r))
    out = jnp.transpose(out, (0, 1, 3, 2, 4, 5))
    return out.reshape(n, h * r, w * r, c // (r * r))


@primitive("pixel_unshuffle")
def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // r, r, w // r, r)
    out = jnp.transpose(out, (0, 1, 3, 5, 2, 4))
    return out.reshape(n, c * r * r, h // r, w // r)


@primitive("channel_shuffle")
def channel_shuffle(x, groups, data_format="NCHW", name=None):
    n, c, h, w = x.shape
    out = x.reshape(n, groups, c // groups, h, w)
    out = jnp.swapaxes(out, 1, 2)
    return out.reshape(n, c, h, w)


@primitive("affine_grid")
def affine_grid(theta, out_shape, align_corners=True, name=None):
    n, _, h, w = int(out_shape[0]), out_shape[1], int(out_shape[2]), int(out_shape[3])
    if align_corners:
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
    else:
        ys = (jnp.arange(h) * 2 + 1) / h - 1
        xs = (jnp.arange(w) * 2 + 1) / w - 1
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1).reshape(1, h * w, 3)
    grid = jnp.matmul(jnp.tile(base, (theta.shape[0], 1, 1)),
                      jnp.swapaxes(theta, 1, 2))
    return grid.reshape(theta.shape[0], h, w, 2)


@primitive("grid_sample")
def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    n, c, h, w = x.shape
    gx, gy = grid[..., 0], grid[..., 1]
    if align_corners:
        fx = (gx + 1) * (w - 1) / 2
        fy = (gy + 1) * (h - 1) / 2
    else:
        fx = ((gx + 1) * w - 1) / 2
        fy = ((gy + 1) * h - 1) / 2

    x0 = jnp.floor(fx)
    y0 = jnp.floor(fy)
    x1 = x0 + 1
    y1 = y0 + 1

    def gather(yy, xx):
        valid = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        flat = x.reshape(n, c, h * w)
        idx = (yc * w + xc).reshape(n, 1, -1)
        out = jnp.take_along_axis(flat, jnp.broadcast_to(idx, (n, c, idx.shape[-1])), axis=2)
        out = out.reshape(n, c, *yy.shape[1:])
        if padding_mode == "zeros":
            out = out * valid[:, None].astype(out.dtype)
        return out

    wa = ((x1 - fx) * (y1 - fy))[:, None]
    wb = ((fx - x0) * (y1 - fy))[:, None]
    wc = ((x1 - fx) * (fy - y0))[:, None]
    wd = ((fx - x0) * (fy - y0))[:, None]
    if mode == "nearest":
        return gather(jnp.round(fy), jnp.round(fx))
    return (gather(y0, x0) * wa + gather(y0, x1) * wb +
            gather(y1, x0) * wc + gather(y1, x1) * wd)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ..ops.manipulation import pad as _pad_nd

    return _pad_nd(x, pad, mode=mode, value=value, data_format=data_format)


@primitive("temporal_shift")
def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    nt, c, h, w = x.shape
    n = nt // seg_num
    r = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([r[:, 1:, :fold], jnp.zeros_like(r[:, :1, :fold])], 1)
    right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold:2 * fold]),
                             r[:, :-1, fold:2 * fold]], 1)
    rest = r[:, :, 2 * fold:]
    return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)


@primitive("label_smooth")
def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    k = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / k


@primitive("npair_loss")
def npair_loss(anchor, positive, labels, l2_reg=0.002):
    sim = jnp.matmul(anchor, positive.T)
    lbl = labels.reshape(-1, 1)
    target = (lbl == lbl.T).astype(sim.dtype)
    target = target / jnp.sum(target, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.mean(jnp.sum(target * logp, axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(anchor), 1)) +
                    jnp.mean(jnp.sum(jnp.square(positive), 1))) * 0.25
    return ce + reg


@primitive("fused_bias_act")
def fused_bias_act(x, bias=None, act="gelu"):
    if bias is not None:
        x = x + bias
    if act == "gelu":
        return jax.nn.gelu(x)
    if act == "relu":
        return jax.nn.relu(x)
    return x


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    lengths_arr = unwrap(lengths)
    if maxlen is None:
        maxlen = int(np.asarray(lengths_arr).max())
    return _sequence_mask(lengths, maxlen=int(maxlen),
                          dtype=dtype_mod.convert_dtype(dtype))


@primitive("sequence_mask")
def _sequence_mask(lengths, maxlen, dtype):
    steps = jnp.arange(maxlen)
    return (steps[None, :] < lengths[..., None]).astype(dtype)


@primitive("diag_embed")
def diag_embed(input, offset=0, dim1=-2, dim2=-1, name=None):
    n = input.shape[-1] + abs(offset)
    out = jnp.zeros(input.shape[:-1] + (n, n), input.dtype)
    idx = jnp.arange(input.shape[-1])
    r = idx + max(0, -offset)
    c = idx + max(0, offset)
    out = out.at[..., r, c].set(input)
    return out


# -- fluid.layers long-tail losses/activations ------------------------------
@primitive("brelu")
def brelu(x, t_min=0.0, t_max=24.0, name=None):
    """Bounded relu (activation_op.cc BRelu)."""
    return jnp.clip(x, t_min, t_max)


@primitive("soft_relu")
def soft_relu(x, threshold=40.0, name=None):
    """log(1+exp(clip(x))) (activation_op.cc SoftRelu)."""
    return jnp.log1p(jnp.exp(jnp.clip(x, -threshold, threshold)))


@primitive("dice_loss")
def dice_loss(input, label, epsilon=1e-5, name=None):
    """Dice coefficient loss for segmentation (layers/nn.py dice_loss):
    input (N, ..., C) probabilities, label (N, ..., 1) int."""
    label_oh = jax.nn.one_hot(jnp.squeeze(label, -1), input.shape[-1],
                              dtype=input.dtype)
    reduce_dims = tuple(range(1, input.ndim))
    inter = jnp.sum(input * label_oh, axis=reduce_dims)
    union = jnp.sum(input, axis=reduce_dims) + \
        jnp.sum(label_oh, axis=reduce_dims)
    dice = (2 * inter + epsilon) / (union + epsilon)
    return jnp.mean(1 - dice)


@primitive("bpr_loss", nondiff=("label",))
def bpr_loss(input, label, name=None):
    """Bayesian personalized ranking loss (bpr_loss_op.cc): input
    (N, C) raw scores, label (N, 1) the positive class."""
    label = jnp.reshape(label, (-1,))
    pos = jnp.take_along_axis(input, label[:, None], axis=1)
    # -mean over negatives of log sigmoid(pos - neg)
    diff = pos - input
    logsig = jax.nn.log_sigmoid(diff)
    n = input.shape[1]
    mask = jax.nn.one_hot(label, n, dtype=input.dtype)
    return jnp.mean(-jnp.sum(logsig * (1 - mask), axis=1) / (n - 1))


@primitive("rank_loss")
def rank_loss(label, left, right, name=None):
    """RankNet pairwise loss (rank_loss_op.cc)."""
    diff = left - right
    return jnp.mean(-label * diff + jnp.log1p(jnp.exp(diff)))


@primitive("margin_rank_loss")
def margin_rank_loss(label, left, right, margin=0.1, name=None):
    """max(0, -label*(left-right)+margin) (margin_rank_loss_op.cc)."""
    return jnp.maximum(0.0, -label * (left - right) + margin)


@primitive("teacher_student_sigmoid_loss")
def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0, name=None):
    """Distillation CTR loss (teacher_student_sigmoid_loss_op.cc):
    label in [0,1] teacher or {0,1} click."""
    x = jnp.clip(input, soft_max_lower_bound, soft_max_up_bound)
    return jnp.mean(x - x * label + jnp.log1p(jnp.exp(-jnp.abs(x))))


@primitive("sigmoid_focal_loss", nondiff=("normalizer",))
def sigmoid_focal_loss_fluid(input, label, fg_num=None, gamma=2.0,
                             alpha=0.25, normalizer=None, name=None):
    """RetinaNet focal loss (sigmoid_focal_loss_op.cc), summed form."""
    p = jax.nn.sigmoid(input)
    ce = -(label * jnp.log(jnp.maximum(p, 1e-12)) +
           (1 - label) * jnp.log(jnp.maximum(1 - p, 1e-12)))
    pt = label * p + (1 - label) * (1 - p)
    w = (label * alpha + (1 - label) * (1 - alpha)) * (1 - pt) ** gamma
    loss = w * ce
    denom = normalizer if normalizer is not None else fg_num
    if denom is not None:
        loss = loss / jnp.maximum(jnp.asarray(denom, loss.dtype), 1.0)
    return loss


@primitive("center_loss", nondiff=("label", "update_center", "alpha"))
def center_loss(input, label, centers, alpha=0.1, update_center=False,
                name=None):
    """Distance to per-class centers (center_loss_op.cc). Functional:
    returns the loss; center updates are the caller's optimizer's job
    (pass centers as a Parameter and let autograd update it)."""
    label = jnp.reshape(label, (-1,))
    c = jnp.take(centers, label, axis=0)
    return 0.5 * jnp.sum(jnp.square(input - c), axis=1, keepdims=True)


@primitive("bilinear_tensor_product")
def bilinear_tensor_product_fn(x, y, weight, bias=None, name=None):
    """out[:, i] = x W_i y^T (bilinear_tensor_product_op.cc);
    weight: (size, dx, dy)."""
    out = jnp.einsum("bi,oij,bj->bo", x, weight, y)
    if bias is not None:
        out = out + bias
    return out


@primitive("affine_channel")
def affine_channel(x, scale, bias, data_layout="NCHW", name=None):
    """Per-channel scale+bias (affine_channel_op.cc; folded-BN form)."""
    if data_layout == "NCHW":
        shape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        shape = (1,) * (x.ndim - 1) + (-1,)
    return x * scale.reshape(shape) + bias.reshape(shape)


@primitive("fsp_matrix")
def fsp_matrix(x, y, name=None):
    """Flow-of-solution-procedure matrix for distillation
    (fsp_op.cc): (N,C1,H,W),(N,C2,H,W) -> (N,C1,C2)."""
    n, c1, h, w = x.shape
    c2 = y.shape[1]
    return jnp.einsum("nchw,ndhw->ncd", x, y) / (h * w)


@primitive("row_conv")
def row_conv(input, weight, name=None):
    """Lookahead row convolution (row_conv_op.cc): input (B, T, D),
    weight (future_context, D)."""
    k = weight.shape[0]
    pads = ((0, 0), (0, k - 1), (0, 0))
    xp = jnp.pad(input, pads)
    out = jnp.zeros_like(input)
    for i in range(k):
        out = out + xp[:, i:i + input.shape[1], :] * weight[i][None, None, :]
    return out


@primitive("nce", nondiff=("label", "num_neg_samples", "seed"))
def nce(input, label, weight, bias=None, num_neg_samples=5,
        sampler="uniform", seed=None, name=None):
    """Noise-contrastive estimation loss (nce_op.cc): input (B, D),
    label (B, 1) positive class, weight (num_classes, D). Uniform
    negative sampling; returns (B, 1) losses."""
    num_classes = weight.shape[0]
    b = input.shape[0]
    from ..framework import random as random_mod
    from ..framework.random import next_rng_key

    # fresh negatives each step unless the caller pins a seed
    key = random_mod.make_key(seed) if seed else next_rng_key()
    neg = jax.random.randint(key, (b, num_neg_samples), 0, num_classes)
    label = jnp.reshape(label, (-1, 1))

    def score(cls):
        w = jnp.take(weight, cls, axis=0)          # (B, K, D)
        s = jnp.einsum("bd,bkd->bk", input, w)
        if bias is not None:
            s = s + jnp.take(bias, cls, axis=0)
        return s

    s_pos = score(label)                           # (B, 1)
    s_neg = score(neg)                             # (B, K)
    # log-odds vs uniform noise: q = K/num_classes
    log_q = jnp.log(jnp.asarray(num_neg_samples / num_classes,
                                input.dtype))
    pos_loss = -jax.nn.log_sigmoid(s_pos - log_q)
    neg_loss = -jnp.sum(jax.nn.log_sigmoid(-(s_neg - log_q)), axis=1,
                        keepdims=True)
    return pos_loss + neg_loss


@primitive("sampled_softmax_with_cross_entropy",
           nondiff=("label", "num_samples", "seed"))
def sampled_softmax_with_cross_entropy(logits_weight, input, label,
                                       num_samples, seed=None, name=None):
    """Sampled-softmax CE (sample_logits_op.cc + layers
    sampled_softmax_with_cross_entropy): full softmax over
    [true class, num_samples uniform negatives] only. logits_weight
    (num_classes, D), input (B, D), label (B, 1)."""
    from ..framework import random as random_mod

    num_classes = logits_weight.shape[0]
    b = input.shape[0]
    from ..framework.random import next_rng_key

    key = random_mod.make_key(seed) if seed else next_rng_key()
    neg = jax.random.randint(key, (b, num_samples), 0, num_classes)
    label = jnp.reshape(label, (-1, 1))
    cls = jnp.concatenate([label, neg], axis=1)    # (B, 1+S)
    w = jnp.take(logits_weight, cls, axis=0)       # (B, 1+S, D)
    logits = jnp.einsum("bd,bkd->bk", input, w)
    # subtract expected sampling correction log q (uniform)
    logq = jnp.log(jnp.asarray(num_samples / num_classes, logits.dtype))
    logits = logits - logq
    # mask accidental hits of the true class among negatives
    hit = cls[:, 1:] == label
    logits = logits.at[:, 1:].set(
        jnp.where(hit, -1e9, logits[:, 1:]))
    return -jax.nn.log_softmax(logits, axis=1)[:, :1]


@primitive("fused_embedding_seq_pool", nondiff=("ids",))
def fused_embedding_seq_pool(table, ids, combiner="sum", padding_idx=None,
                             name=None):
    """Fused lookup_table + sequence_pool — the (B, S, D) gathered
    intermediate never reaches HBM (reference fused/
    fused_embedding_seq_pool_op.cc; Pallas scalar-prefetch kernel on TPU,
    XLA fallback elsewhere). table (V, D); ids (B, S) with padding_idx /
    negative entries ignored; combiner sum|mean|sqrtn. Returns (B, D)."""
    from ..ops.pallas.fused_embedding import fused_embedding_seq_pool as fe

    return fe(table, ids, combiner=combiner, padding_idx=padding_idx)


# ---------------------------------------------------------------------------
# 2.0-alpha functional surface completion (reference
# python/paddle/nn/functional/__init__.py __all__): names whose
# implementations live in the op/layer library are re-exported lazily via
# PEP 562 so the static layer surface is not imported at module load.
# Audited by tests/test_namespace_freeze.py.
# ---------------------------------------------------------------------------

# fluid-surface names keep their fluid semantics/signatures (e.g.
# hard_sigmoid slope=0.2, not Hardsigmoid's 1/6 — the v1.8 functional
# namespace aliases the fluid ops)
_LAYER_ALIASES = (
    "add_position_encoding", "continuous_value_model", "filter_by_instag",
    "multiclass_nms", "polygon_box_transform", "random_crop",
    "rpn_target_assign", "similarity_focus", "target_assign", "warpctc",
    "pad_constant_like", "pad2d", "unfold", "assign", "pool2d", "pool3d",
    "adaptive_pool2d", "adaptive_pool3d", "edit_distance",
    "iou_similarity", "sigmoid_cross_entropy_with_logits",
    "sigmoid_focal_loss", "smooth_l1", "ssd_loss", "hsigmoid",
    "hard_sigmoid", "hard_swish", "tanh",
)

_LOCAL_ALIASES = {
    "conv_transpose1d": "conv1d_transpose",
    "conv_transpose2d": "conv2d_transpose",
    "conv_transpose3d": "conv3d_transpose",
}


def __getattr__(name):
    import sys

    mod = sys.modules[__name__]
    if name in _LOCAL_ALIASES:
        return getattr(mod, _LOCAL_ALIASES[name])
    if name in ("erf", "logsigmoid"):
        from .. import ops as _ops

        return getattr(_ops, {"logsigmoid": "log_sigmoid"}.get(name, name))
    if name in _LAYER_ALIASES:
        from ..static import layers as _L

        return getattr(_L, name)
    raise AttributeError(name)


from ..framework.op import primitive as _primitive  # noqa: E402


@_primitive(name="bilinear")
def bilinear(x1, x2, weight, bias=None, name=None):
    """paddle.nn.functional.bilinear (reference nn/functional/common.py):
    out[b, k] = x1[b, i] W[k, i, j] x2[b, j] (+ bias)."""
    out = jnp.einsum("bi,kij,bj->bk", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


@_primitive(name="cosine_similarity")
def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    """paddle.nn.functional.cosine_similarity (reference
    nn/functional/common.py): cos of the angle along ``axis``."""
    num = jnp.sum(x1 * x2, axis=axis)
    den = jnp.sqrt(jnp.sum(x1 * x1, axis=axis)) * \
        jnp.sqrt(jnp.sum(x2 * x2, axis=axis))
    return num / jnp.maximum(den, eps)
