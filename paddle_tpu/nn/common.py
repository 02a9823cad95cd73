"""Common layers: Linear, Embedding, Dropout, activations, padding, upsample.

Parity with the reference 2.0 layer set (/root/reference/python/paddle/nn/
layer/common.py) and the dygraph layers (fluid/dygraph/nn.py).
"""
from __future__ import annotations

from . import functional as F
from .layer import Layer


class Identity(Layer):
    def forward(self, x):
        return x


class Linear(Layer):
    """y = x @ W + b, W: (in_features, out_features) (reference fc/mul op)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr)
        self.bias = self.create_parameter(
            [out_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.linear(x, self.weight, self.bias)

    def extra_repr(self):
        return f"in_features={self._in_features}, out_features={self._out_features}"


class GatedFFN(Layer):
    """(act(x W_gate) * x W_up) W_down, no biases: the feed-forward of
    the gated decoder blocks (dense layers, and the shared expert of a
    mixture whose routed experts are gated)."""

    def __init__(self, d_model, d_hidden, activation="silu"):
        super().__init__()
        self.gate_proj = Linear(d_model, d_hidden, bias_attr=False)
        self.up_proj = Linear(d_model, d_hidden, bias_attr=False)
        self.down_proj = Linear(d_hidden, d_model, bias_attr=False)
        self._act = getattr(F, activation)

    def forward(self, x):
        return self.down_proj(self._act(self.gate_proj(x)) * self.up_proj(x))


class PlainFFN(Layer):
    """act(x W_up) W_down, no biases and no gate: ``activation`` is a
    name of ``nn.functional`` (``relu2`` among them: relu, squared)."""

    def __init__(self, d_model, d_hidden, activation="relu2"):
        super().__init__()
        self.up_proj = Linear(d_model, d_hidden, bias_attr=False)
        self.down_proj = Linear(d_hidden, d_model, bias_attr=False)
        self._act = getattr(F, activation)

    def forward(self, x):
        return self.down_proj(self._act(self.up_proj(x)))


class Embedding(Layer):
    """Reference lookup_table_v2_op.cc; rows gathered via jnp.take."""

    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self._padding_idx = None if padding_idx is None else (
            padding_idx if padding_idx >= 0 else num_embeddings + padding_idx)
        from . import initializer as I

        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierUniform())
        if self._padding_idx is not None:
            import jax.numpy as jnp

            self.weight._value = self.weight._value.at[self._padding_idx].set(0.0)

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self._padding_idx)


class Dropout(Layer):
    def __init__(self, p=0.5, axis=None, mode="upscale_in_train", name=None):
        super().__init__()
        self.p = p
        self.axis = axis
        self.mode = mode

    def forward(self, x):
        return F.dropout(x, p=self.p, axis=self.axis, training=self.training,
                         mode=self.mode)


class Dropout2D(Layer):
    def __init__(self, p=0.5, data_format="NCHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, x):
        return F.dropout2d(x, p=self.p, training=self.training,
                           data_format=self.data_format)


class Dropout3D(Layer):
    def __init__(self, p=0.5, data_format="NCDHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, x):
        return F.dropout3d(x, p=self.p, training=self.training,
                           data_format=self.data_format)


class AlphaDropout(Layer):
    def __init__(self, p=0.5, name=None):
        super().__init__()
        self.p = p

    def forward(self, x):
        return F.alpha_dropout(x, p=self.p, training=self.training)


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis = start_axis
        self.stop_axis = stop_axis

    def forward(self, x):
        from .. import ops

        return ops.flatten(x, self.start_axis, self.stop_axis)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False, align_mode=0, data_format="NCHW",
                 name=None):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.mode = mode
        self.align_corners = align_corners
        self.align_mode = align_mode
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, size=self.size, scale_factor=self.scale_factor,
                             mode=self.mode, align_corners=self.align_corners,
                             align_mode=self.align_mode,
                             data_format=self.data_format)


class UpsamplingBilinear2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW", name=None):
        super().__init__(size, scale_factor, mode="bilinear",
                         align_corners=True, data_format=data_format)


class UpsamplingNearest2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW", name=None):
        super().__init__(size, scale_factor, mode="nearest",
                         data_format=data_format)


class PixelShuffle(Layer):
    def __init__(self, upscale_factor, data_format="NCHW", name=None):
        super().__init__()
        self.upscale_factor = upscale_factor
        self.data_format = data_format

    def forward(self, x):
        return F.pixel_shuffle(x, self.upscale_factor, self.data_format)


class Pad1D(Layer):
    def __init__(self, padding, mode="constant", value=0.0, data_format="NCL",
                 name=None):
        super().__init__()
        self.padding, self.mode, self.value = padding, mode, value
        self.data_format = data_format

    def forward(self, x):
        return F.pad(x, self.padding, mode=self.mode, value=self.value,
                     data_format=self.data_format)


class Pad2D(Pad1D):
    def __init__(self, padding, mode="constant", value=0.0, data_format="NCHW",
                 name=None):
        super().__init__(padding, mode, value, data_format, name)


class Pad3D(Pad1D):
    def __init__(self, padding, mode="constant", value=0.0, data_format="NCDHW",
                 name=None):
        super().__init__(padding, mode, value, data_format, name)


class CosineSimilarity(Layer):
    def __init__(self, axis=1, eps=1e-8):
        super().__init__()
        self.axis, self.eps = axis, eps

    def forward(self, x1, x2):
        from ..ops.linalg import cosine_similarity

        return cosine_similarity(x1, x2, axis=self.axis, eps=self.eps)


class Bilinear(Layer):
    """Reference bilinear_tensor_product_op.cc."""

    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [out_features, in1_features, in2_features], attr=weight_attr)
        self.bias = self.create_parameter([1, out_features], attr=bias_attr,
                                          is_bias=True)

    def forward(self, x1, x2):
        from .. import ops

        out = ops.einsum("bi,oij,bj->bo", x1, self.weight, x2)
        if self.bias is not None:
            out = out + self.bias
        return out


# activation layers
def _act_layer(name, fn, params=()):
    def __init__(self, *args, **kwargs):
        Layer.__init__(self)
        for p, default in params:
            setattr(self, p, kwargs.pop(p, args[params.index((p, default))]
                                        if params.index((p, default)) < len(args)
                                        else default))

    def forward(self, x):
        kw = {p: getattr(self, p) for p, _ in params}
        return fn(x, **kw)

    return type(name, (Layer,), {"__init__": __init__, "forward": forward})


ReLU = _act_layer("ReLU", F.relu)
ReLU6 = _act_layer("ReLU6", F.relu6)
LeakyReLU = _act_layer("LeakyReLU", F.leaky_relu, (("negative_slope", 0.01),))
ELU = _act_layer("ELU", F.elu, (("alpha", 1.0),))
CELU = _act_layer("CELU", F.celu, (("alpha", 1.0),))
SELU = _act_layer("SELU", F.selu)
GELU = _act_layer("GELU", F.gelu, (("approximate", False),))
Silu = _act_layer("Silu", F.silu)
Swish = _act_layer("Swish", F.silu)
Mish = _act_layer("Mish", F.mish)
Hardswish = _act_layer("Hardswish", F.hardswish)
Hardsigmoid = _act_layer("Hardsigmoid", F.hardsigmoid)
Hardtanh = _act_layer("Hardtanh", F.hardtanh, (("min", -1.0), ("max", 1.0)))
Hardshrink = _act_layer("Hardshrink", F.hardshrink, (("threshold", 0.5),))
Softshrink = _act_layer("Softshrink", F.softshrink, (("threshold", 0.5),))
Tanhshrink = _act_layer("Tanhshrink", F.tanhshrink)
Softplus = _act_layer("Softplus", F.softplus, (("beta", 1.0), ("threshold", 20.0)))
Softsign = _act_layer("Softsign", F.softsign)
Sigmoid = _act_layer("Sigmoid", F.sigmoid)
LogSigmoid = _act_layer("LogSigmoid", None)
Tanh = _act_layer("Tanh", None)
Softmax = _act_layer("Softmax", F.softmax, (("axis", -1),))
LogSoftmax = _act_layer("LogSoftmax", F.log_softmax, (("axis", -1),))
ThresholdedReLU = _act_layer("ThresholdedReLU", F.thresholded_relu,
                             (("threshold", 1.0),))
Maxout = _act_layer("Maxout", F.maxout, (("groups", 2), ("axis", 1)))


def _tanh_forward(self, x):
    from .. import ops

    return ops.tanh(x)


def _logsigmoid_forward(self, x):
    from .. import ops

    return ops.log_sigmoid(x)


Tanh.forward = _tanh_forward
LogSigmoid.forward = _logsigmoid_forward


class PReLU(Layer):
    def __init__(self, num_parameters=1, init=0.25, weight_attr=None,
                 data_format="NCHW", name=None):
        super().__init__()
        from . import initializer as I

        self.data_format = data_format
        self.weight = self.create_parameter(
            [num_parameters], attr=weight_attr,
            default_initializer=I.Constant(init))

    def forward(self, x):
        return F.prelu(x, self.weight, data_format=self.data_format)
