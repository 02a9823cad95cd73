"""Static-graph op kernels: op_type -> pure jnp function over named slots.

TPU-native counterpart of the reference kernel registry
(/root/reference/paddle/fluid/framework/op_registry.h:268
REGISTER_OP_CPU_KERNEL + operator.cc:1068 ChooseKernel). There is no
(place, dtype, layout) dispatch: one kernel per op, written in jnp, lowered
by XLA for whatever backend jit targets. Kernels are pure; stateful ops
(optimizers, batch_norm running stats) return their updated tensors and the
executor writes them back to the scope (functional state, no mutation).

Kernel signature: fn(ins: dict slot->list[jax.Array], attrs: dict,
ctx: ExecContext) -> dict slot->list[jax.Array].
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

from ..framework import dtype as dtype_mod

KERNELS: Dict[str, Callable] = {}


@dataclass
class ExecContext:
    """Per-lowering context threaded to kernels that need RNG or step."""
    rng_key: Any = None          # jax PRNGKey (traced)
    op_index: int = 0            # position in block, folds into the key
    is_test: bool = False
    program: Any = None          # set by run_block: owning Program
    env: Any = None              # set by run_block: live name->array env
                                 # (control-flow kernels snapshot it)

    def key(self):
        return jax.random.fold_in(self.rng_key, self.op_index)


def kernel(op_type):
    def deco(fn):
        KERNELS[op_type] = fn
        fn.op_type = op_type
        return fn
    return deco


def _x(ins, slot="X"):
    return ins[slot][0]


def _dt(name):
    return dtype_mod.convert_dtype(name)


def _out(*arrays, slot="Out"):
    return {slot: list(arrays)}


# ---------------------------------------------------------------------------
# creation / initialization (startup-program ops; reference
# operators/fill_constant_op.cc, gaussian_random_op.cc, uniform_random_op.cc)
# ---------------------------------------------------------------------------
@kernel("fill_constant")
def _fill_constant(ins, attrs, ctx):
    shape = tuple(attrs["shape"])
    return _out(jnp.full(shape, attrs["value"], _dt(attrs["dtype"])))


@kernel("gaussian_random")
def _gaussian_random(ins, attrs, ctx):
    shape = tuple(attrs["shape"])
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        ctx.key(), shape, _dt(attrs.get("dtype", "float32")))
    return _out(out)


@kernel("uniform_random")
def _uniform_random(ins, attrs, ctx):
    shape = tuple(attrs["shape"])
    return _out(jax.random.uniform(
        ctx.key(), shape, _dt(attrs.get("dtype", "float32")),
        attrs.get("min", -1.0), attrs.get("max", 1.0)))


@kernel("truncated_gaussian_random")
def _trunc_gaussian(ins, attrs, ctx):
    shape = tuple(attrs["shape"])
    std = attrs.get("std", 1.0)
    out = attrs.get("mean", 0.0) + std * jax.random.truncated_normal(
        ctx.key(), -2.0, 2.0, shape, _dt(attrs.get("dtype", "float32")))
    return _out(out)


@kernel("assign_value")
def _assign_value(ins, attrs, ctx):
    import numpy as np
    vals = np.asarray(attrs["values"], dtype=attrs.get("dtype", "float32"))
    return _out(jnp.asarray(vals.reshape(tuple(attrs["shape"]))))


# ---------------------------------------------------------------------------
# elementwise (reference operators/elementwise/) — numpy broadcasting; the
# reference's `axis` attr aligns a lower-rank Y at a given axis
# ---------------------------------------------------------------------------
def _align(x, y, axis):
    if axis in (None, -1) or y.ndim == x.ndim:
        return y
    return y.reshape(y.shape + (1,) * (x.ndim - axis - y.ndim))


def _ew(op_type, fn):
    @kernel(op_type)
    def k(ins, attrs, ctx, _fn=fn):
        x, y = _x(ins), ins["Y"][0]
        return _out(_fn(x, _align(x, y, attrs.get("axis", -1))))
    return k


_ew("elementwise_add", jnp.add)
_ew("elementwise_sub", jnp.subtract)
_ew("elementwise_mul", jnp.multiply)
_ew("elementwise_div", jnp.divide)
_ew("elementwise_max", jnp.maximum)
_ew("elementwise_min", jnp.minimum)
_ew("elementwise_pow", jnp.power)
_ew("elementwise_mod", jnp.mod)
_ew("elementwise_floordiv", jnp.floor_divide)


@kernel("scale")
def _scale(ins, attrs, ctx):
    x = _x(ins)
    s, b = attrs.get("scale", 1.0), attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        return _out(x * s + b)
    return _out((x + b) * s)


@kernel("cast")
def _cast(ins, attrs, ctx):
    return _out(_x(ins).astype(_dt(attrs["out_dtype"])))


@kernel("clip")
def _clip(ins, attrs, ctx):
    return _out(jnp.clip(_x(ins), attrs.get("min"), attrs.get("max")))


# unary activations (reference operators/activation_op.cc)
def _unary(op_type, fn):
    @kernel(op_type)
    def k(ins, attrs, ctx, _fn=fn):
        return _out(_fn(_x(ins)))
    return k


_unary("relu", jax.nn.relu)
_unary("sigmoid", jax.nn.sigmoid)
_unary("tanh", jnp.tanh)
_unary("exp", jnp.exp)
_unary("log", jnp.log)
_unary("sqrt", jnp.sqrt)
_unary("rsqrt", jax.lax.rsqrt)
_unary("square", jnp.square)
_unary("abs", jnp.abs)
_unary("floor", jnp.floor)
_unary("ceil", jnp.ceil)
_unary("round", jnp.round)
_unary("reciprocal", jnp.reciprocal)
_unary("sign", jnp.sign)
_unary("softsign", jax.nn.soft_sign)
_unary("softplus", jax.nn.softplus)
_unary("cos", jnp.cos)
_unary("sin", jnp.sin)
_unary("acos", jnp.arccos)
_unary("asin", jnp.arcsin)
_unary("atan", jnp.arctan)
_unary("sinh", jnp.sinh)
_unary("cosh", jnp.cosh)
_unary("erf", jax.scipy.special.erf)
_unary("logsigmoid", jax.nn.log_sigmoid)
_unary("tanh_shrink", lambda x: x - jnp.tanh(x))


@kernel("cumsum")
def _cumsum(ins, attrs, ctx):
    x = _x(ins)
    axis = attrs.get("axis", -1)
    if attrs.get("flatten"):
        x, axis = x.reshape(-1), 0
    if attrs.get("reverse"):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    else:
        out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive"):
        out = out - x
    return _out(out)


@kernel("softshrink")
def _softshrink(ins, attrs, ctx):
    x = _x(ins)
    lam = attrs.get("lambda", 0.5)
    return _out(jnp.where(x > lam, x - lam,
                          jnp.where(x < -lam, x + lam, 0.0)).astype(x.dtype))


@kernel("hard_shrink")
def _hard_shrink(ins, attrs, ctx):
    x = _x(ins)
    t = attrs.get("threshold", 0.5)
    return _out(jnp.where(jnp.abs(x) > t, x, 0.0).astype(x.dtype))


@kernel("thresholded_relu")
def _thresholded_relu(ins, attrs, ctx):
    x = _x(ins)
    t = attrs.get("threshold", 1.0)
    return _out(jnp.where(x > t, x, 0.0).astype(x.dtype))


@kernel("gelu")
def _gelu(ins, attrs, ctx):
    return _out(jax.nn.gelu(_x(ins), approximate=attrs.get("approximate",
                                                           False)))


@kernel("leaky_relu")
def _leaky_relu(ins, attrs, ctx):
    return _out(jax.nn.leaky_relu(_x(ins), attrs.get("alpha", 0.02)))


@kernel("hard_swish")
def _hard_swish(ins, attrs, ctx):
    return _out(jax.nn.hard_swish(_x(ins)))


@kernel("swish")
def _swish(ins, attrs, ctx):
    x = _x(ins)
    return _out(x * jax.nn.sigmoid(attrs.get("beta", 1.0) * x))


@kernel("pow")
def _pow(ins, attrs, ctx):
    return _out(jnp.power(_x(ins), attrs.get("factor", 1.0)))


@kernel("fake_quantize_dequantize_abs_max")
def _fake_quantize_dequantize_abs_max(ins, attrs, ctx):
    """Simulated quantization (reference fake_quantize_op.cc
    FakeQuantizeDequantizeAbsMax): quantize to bit_length ints at the
    dynamic abs-max scale, dequantize back, straight-through gradient
    (the jax.vjp over this forward sees identity). Used by
    contrib.QuantizeTranspiler.training_transpile."""
    x = _x(ins)
    bits = int(attrs.get("bit_length", 8))
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    if attrs.get("is_test", False) and "InScale" in ins:
        scale = ins["InScale"][0]
    # clip BEFORE rounding: values beyond the (frozen) scale must
    # saturate exactly like the deployed int8 model would
    q = jnp.round(jnp.clip(x / scale, -1.0, 1.0) * qmax) / qmax * scale
    out = x + jax.lax.stop_gradient(q - x)
    return {"Out": [out], "OutScale": [scale]}


@kernel("fused_elemwise_activation")
def _fused_elemwise_activation(ins, attrs, ctx):
    """Fused binary-elementwise + activation (reference
    operators/fused/fused_elemwise_activation_op.cc, emitted by
    fuse_elewise_add_act_pass). The IR fusion pass (static/passes.py)
    lowers matched elementwise->act chains onto this kernel; it
    delegates to the registered component kernels so the math stays
    bit-identical to the unfused pair."""
    functors = attrs["functor_list"]
    binary_t, act_t = functors[0], functors[1]
    mid = KERNELS[binary_t]({"X": ins["X"], "Y": ins["Y"]},
                            {"axis": attrs.get("axis", -1)}, ctx)["Out"]
    out = KERNELS[act_t]({"X": mid}, dict(attrs.get("act_attrs") or {}),
                         ctx)["Out"]
    return {"Out": out}


# ---------------------------------------------------------------------------
# matmul / fc (reference operators/matmul_op.cc, mul_op.cc, math/fc.cc)
# ---------------------------------------------------------------------------
@kernel("matmul")
def _matmul(ins, attrs, ctx):
    x, y = _x(ins), ins["Y"][0]
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2)
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2)
    out = jnp.matmul(x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return _out(out)


@kernel("mul")
def _mul(ins, attrs, ctx):
    """Flattening matmul: x flattened to 2D at num_col_dims (reference
    mul_op.cc x_num_col_dims)."""
    x, y = _x(ins), ins["Y"][0]
    xnc = attrs.get("x_num_col_dims", 1)
    ync = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    x2 = x.reshape((-1, _prod(xs[xnc:])))
    y2 = y.reshape((int(_prod(ys[:ync])), -1))
    out = x2 @ y2
    return _out(out.reshape(xs[:xnc] + ys[ync:]))


def _prod(t):
    r = 1
    for v in t:
        r *= int(v)
    return r


# ---------------------------------------------------------------------------
# reductions (reference operators/reduce_ops/)
# ---------------------------------------------------------------------------
def _reduce(op_type, fn):
    @kernel(op_type)
    def k(ins, attrs, ctx, _fn=fn):
        dims = attrs.get("dim")
        if attrs.get("reduce_all", False) or dims is None:
            axis = None
        else:
            axis = tuple(dims) if isinstance(dims, (list, tuple)) else (dims,)
        return _out(_fn(_x(ins), axis=axis,
                        keepdims=attrs.get("keep_dim", False)))
    return k


_reduce("reduce_sum", jnp.sum)
_reduce("reduce_mean", jnp.mean)
_reduce("reduce_max", jnp.max)
_reduce("reduce_min", jnp.min)
_reduce("reduce_prod", jnp.prod)
_reduce("reduce_any", jnp.any)
_reduce("reduce_all", jnp.all)


@kernel("mean")
def _mean(ins, attrs, ctx):
    return _out(jnp.mean(_x(ins)))


@kernel("sum")
def _sum_op(ins, attrs, ctx):
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return _out(out)


# ---------------------------------------------------------------------------
# shape manipulation (reference reshape_op.cc, transpose_op.cc, concat_op.cc)
# ---------------------------------------------------------------------------
@kernel("reshape2")
def _reshape(ins, attrs, ctx):
    x = _x(ins)
    shape = [int(s) for s in attrs["shape"]]
    # paddle semantics: 0 means copy input dim, -1 inferred
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)]
    return _out(jnp.reshape(x, shape))


@kernel("transpose2")
def _transpose(ins, attrs, ctx):
    return _out(jnp.transpose(_x(ins), attrs["axis"]))


@kernel("concat")
def _concat(ins, attrs, ctx):
    return _out(jnp.concatenate(ins["X"], axis=attrs.get("axis", 0)))


@kernel("split")
def _split(ins, attrs, ctx):
    x = _x(ins)
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections")
    if sections:
        idx, acc = [], 0
        for s in sections[:-1]:
            acc += int(s)
            idx.append(acc)
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return _out(*outs)


@kernel("stack")
def _stack(ins, attrs, ctx):
    return _out(jnp.stack(ins["X"], axis=attrs.get("axis", 0)), slot="Y")


@kernel("squeeze2")
def _squeeze(ins, attrs, ctx):
    axes = attrs.get("axes") or None
    return _out(jnp.squeeze(_x(ins), axis=tuple(axes) if axes else None))


@kernel("unsqueeze2")
def _unsqueeze(ins, attrs, ctx):
    return _out(jnp.expand_dims(_x(ins), tuple(attrs["axes"])))


@kernel("slice")
def _slice(ins, attrs, ctx):
    x = ins.get("Input", ins.get("X"))[0]
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(attrs["axes"], attrs["starts"], attrs["ends"]):
        idx[ax] = slice(st, en if en < 2 ** 31 - 1 else None)
    return _out(x[tuple(idx)])


@kernel("expand_as")
def _expand_as(ins, attrs, ctx):
    return _out(jnp.broadcast_to(_x(ins), ins["target_tensor"][0].shape))


@kernel("expand")
def _expand(ins, attrs, ctx):
    x = _x(ins)
    times = attrs["expand_times"]
    return _out(jnp.tile(x, times))


@kernel("flatten2")
def _flatten(ins, attrs, ctx):
    x = _x(ins)
    ax = attrs.get("axis", 1)
    lead = _prod(x.shape[:ax])
    return _out(x.reshape((lead, -1)))


@kernel("shape")
def _shape(ins, attrs, ctx):
    x = ins.get("X", ins.get("Input"))[0]
    return _out(jnp.asarray(x.shape, jnp.int32))


@kernel("lookup_table_v2")
def _lookup_table(ins, attrs, ctx):
    w, ids = ins["W"][0], ins["Ids"][0]
    out = jnp.take(w, ids, axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad >= 0:
        out = jnp.where((ids == pad)[..., None], 0.0, out)
    return _out(out)


@kernel("one_hot_v2")
def _one_hot(ins, attrs, ctx):
    return _out(jax.nn.one_hot(_x(ins), attrs["depth"], dtype=jnp.float32))


@kernel("arg_max")
def _arg_max(ins, attrs, ctx):
    # reference arg_max outputs int64 (truncates to int32 without x64)
    return _out(jnp.argmax(_x(ins), axis=attrs.get("axis", -1))
                .astype(jnp.int64))


@kernel("top_k_v2")
def _top_k(ins, attrs, ctx):
    vals, idx = jax.lax.top_k(_x(ins), attrs["k"])
    return {"Out": [vals], "Indices": [idx.astype(jnp.int32)]}


@kernel("gather")
def _gather(ins, attrs, ctx):
    return _out(jnp.take(_x(ins), ins["Index"][0],
                         axis=attrs.get("axis", 0)))


@kernel("where")
def _where(ins, attrs, ctx):
    return _out(jnp.where(ins["Condition"][0], _x(ins), ins["Y"][0]))


@kernel("masked_select_rows")
def _masked_select_rows(ins, attrs, ctx):
    """Row-wise merge for the IfElse construct (legacy_flow.py): rows
    where the (batch, 1) mask is true come from X, else from Y."""
    m = ins["Mask"][0].astype(bool).reshape(-1)
    x = _x(ins)
    while m.ndim < x.ndim:
        m = m[..., None]
    return _out(jnp.where(m, x, ins["Y"][0]))


@kernel("fill_zeros_like")
def _fill_zeros_like(ins, attrs, ctx):
    return _out(jnp.zeros_like(_x(ins)))


@kernel("assign")
def _assign(ins, attrs, ctx):
    return _out(_x(ins))


# comparison / logical (reference operators/controlflow/compare_op.cc)
for _t, _f in [("equal", jnp.equal), ("not_equal", jnp.not_equal),
               ("less_than", jnp.less), ("less_equal", jnp.less_equal),
               ("greater_than", jnp.greater),
               ("greater_equal", jnp.greater_equal)]:
    _ew(_t, _f)

_unary("logical_not", jnp.logical_not)
_ew("logical_and", jnp.logical_and)
_ew("logical_or", jnp.logical_or)
_ew("logical_xor", jnp.logical_xor)


# ---------------------------------------------------------------------------
# NN ops (reference softmax_op.cc, cross_entropy_op.cc, conv_op.cc,
# pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc)
# ---------------------------------------------------------------------------
@kernel("softmax")
def _softmax(ins, attrs, ctx):
    return _out(jax.nn.softmax(_x(ins), axis=attrs.get("axis", -1)))


@kernel("log_softmax")
def _log_softmax(ins, attrs, ctx):
    return _out(jax.nn.log_softmax(_x(ins), axis=attrs.get("axis", -1)))


@kernel("cross_entropy")
def _cross_entropy(ins, attrs, ctx):
    x, label = _x(ins), ins["Label"][0]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + 1e-12), axis=-1, keepdims=True)
    else:
        picked = jnp.take_along_axis(
            x, label.astype(jnp.int32).reshape(label.shape[:1] + (1,)),
            axis=-1)
        loss = -jnp.log(picked + 1e-12)
    return _out(loss, slot="Y")


@kernel("softmax_with_cross_entropy")
def _softmax_ce(ins, attrs, ctx):
    logits, label = ins["Logits"][0], ins["Label"][0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lab = label.astype(jnp.int32)
        if lab.ndim == logits.ndim:
            lab = lab[..., 0]
        loss = -jnp.take_along_axis(logp, lab[..., None], axis=-1)
    return {"Softmax": [jnp.exp(logp)], "Loss": [loss]}


@kernel("accuracy")
def _accuracy(ins, attrs, ctx):
    pred, label = _x(ins, "Out"), ins["Label"][0]
    k = attrs.get("k", 1)
    _, topk_idx = jax.lax.top_k(pred, k)
    lab = label.reshape(pred.shape[0], 1).astype(topk_idx.dtype)
    hit = jnp.any(topk_idx == lab, axis=-1)
    correct = jnp.sum(hit)
    total = pred.shape[0]
    acc = correct.astype(jnp.float32) / total
    return {"Accuracy": [acc], "Correct": [correct.astype(jnp.int32)],
            "Total": [jnp.asarray(total, jnp.int32)]}


@kernel("dropout")
def _dropout(ins, attrs, ctx):
    x = _x(ins)
    p = attrs.get("dropout_prob", 0.5)
    if attrs.get("is_test", False) or ctx.is_test or p == 0.0:
        mask = jnp.ones_like(x)
        return {"Out": [x], "Mask": [mask]}
    keep = jax.random.bernoulli(ctx.key(), 1.0 - p, x.shape)
    impl = attrs.get("dropout_implementation", "upscale_in_train")
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0)
    else:
        out = jnp.where(keep, x, 0.0)
    return {"Out": [out], "Mask": [keep.astype(x.dtype)]}


@kernel("conv2d")
def _conv2d(ins, attrs, ctx):
    x, w = ins["Input"][0], ins["Filter"][0]
    stride = tuple(attrs.get("strides", [1, 1]))
    pad = attrs.get("paddings", [0, 0])
    dil = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    if len(pad) == 2:
        pad = [(pad[0], pad[0]), (pad[1], pad[1])]
    else:
        pad = [(pad[0], pad[1]), (pad[2], pad[3])]
    # no preferred_element_type: MXU accumulates bf16 convs in f32
    # natively, and an f32 output breaks the conv transpose rule under
    # append_backward (f32 cotangent vs bf16 operands)
    out = jax.lax.conv_general_dilated(
        x, w, stride, pad, rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return _out(out, slot="Output")


@kernel("pool2d")
def _pool2d(ins, attrs, ctx):
    x = _x(ins)
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        if ptype == "max":
            return _out(jnp.max(x, axis=(2, 3), keepdims=True))
        return _out(jnp.mean(x, axis=(2, 3), keepdims=True))
    k = tuple(attrs["ksize"])
    s = tuple(attrs.get("strides", k))
    p = attrs.get("paddings", [0, 0])
    pads = [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])]
    window = (1, 1) + k
    strides = (1, 1) + s
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                    strides, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                       pads)
        if attrs.get("exclusive", True) and any(v for pair in pads
                                                for v in pair):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           strides, pads)
            out = summed / counts
        else:
            out = summed / (k[0] * k[1])
    return _out(out)


@kernel("batch_norm")
def _batch_norm(ins, attrs, ctx):
    x = _x(ins)
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    axis = tuple(i for i in range(x.ndim) if i != 1)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if attrs.get("is_test", False) or ctx.is_test:
        y = (x - mean.reshape(shape)) * jax.lax.rsqrt(
            var.reshape(shape) + eps) * scale.reshape(shape) + \
            bias.reshape(shape)
        return {"Y": [y], "MeanOut": [mean], "VarianceOut": [var],
                "SavedMean": [mean], "SavedVariance": [var]}
    bmean = jnp.mean(x, axis=axis)
    bvar = jnp.var(x, axis=axis)
    y = (x - bmean.reshape(shape)) * jax.lax.rsqrt(
        bvar.reshape(shape) + eps) * scale.reshape(shape) + \
        bias.reshape(shape)
    new_mean = momentum * mean + (1 - momentum) * bmean
    new_var = momentum * var + (1 - momentum) * bvar
    return {"Y": [y], "MeanOut": [new_mean], "VarianceOut": [new_var],
            "SavedMean": [bmean], "SavedVariance": [bvar]}


@kernel("layer_norm")
def _layer_norm(ins, attrs, ctx):
    x = _x(ins)
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if "Scale" in ins:
        y = y * ins["Scale"][0].reshape((1,) * begin + x.shape[begin:])
    if "Bias" in ins:
        y = y + ins["Bias"][0].reshape((1,) * begin + x.shape[begin:])
    return {"Y": [y], "Mean": [jnp.squeeze(mean)],
            "Variance": [jnp.squeeze(var)]}


# ---------------------------------------------------------------------------
# optimizer update ops (reference operators/optimizers/*.cc) — pure
# functional: outputs are the updated params/accumulators. An optional
# FoundInfinite input (wired by the fp16 auto_mixed_precision pass)
# gates the WHOLE update: on a non-finite step params, moments and
# beta-pow accumulators all keep their previous values — the
# GradScaler skip-step semantics, inside the compiled program.
# ---------------------------------------------------------------------------
def _gate_update(ins, outs):
    found = ins.get("FoundInfinite")
    if not found:
        return outs
    skip = found[0].reshape(())
    olds = {"ParamOut": "Param", "VelocityOut": "Velocity",
            "Moment1Out": "Moment1", "Moment2Out": "Moment2",
            "Beta1PowOut": "Beta1Pow", "Beta2PowOut": "Beta2Pow"}
    return {slot: [jnp.where(skip, ins[olds[slot]][0], new)
                   for new in vals]
            for slot, vals in outs.items()}


@kernel("sgd")
def _sgd(ins, attrs, ctx):
    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    return _gate_update(ins, {"ParamOut": [p - lr * g]})


@kernel("momentum")
def _momentum(ins, attrs, ctx):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    lr = ins["LearningRate"][0]
    mu = attrs.get("mu", 0.9)
    use_nesterov = attrs.get("use_nesterov", False)
    v_new = mu * v + g
    if use_nesterov:
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    return _gate_update(ins, {"ParamOut": [p_new],
                              "VelocityOut": [v_new]})


@kernel("adam")
def _adam(ins, attrs, ctx):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, v = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    lr = ins["LearningRate"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p * b2) / (1 - b1p * b1)
    p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    return _gate_update(ins, {
        "ParamOut": [p_new], "Moment1Out": [m_new],
        "Moment2Out": [v_new], "Beta1PowOut": [b1p * b1],
        "Beta2PowOut": [b2p * b2]})


def _lamb_moments(p, g, m, v, b1p, b2p, attrs):
    """Lamb's element-wise phase: the advanced moments and the
    trust-ratio numerator ``r``. What follows needs the norms of ``p``
    and ``r`` over a whole parameter: the op below takes them of the
    arrays in hand, a ZeRO bucket (``stepplan.chunk_update``) of
    its chunk's segments, summed over the devices."""
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    b1p_new, b2p_new = b1p * b1, b2p * b2
    m_hat = m_new / (1 - b1p_new)
    v_hat = v_new / (1 - b2p_new)
    r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    return m_new, v_new, r, b1p_new, b2p_new


@kernel("lamb")
def _lamb(ins, attrs, ctx):
    p = ins["Param"][0]
    m_new, v_new, r, b1p_new, b2p_new = _lamb_moments(
        p, ins["Grad"][0], ins["Moment1"][0], ins["Moment2"][0],
        ins["Beta1Pow"][0], ins["Beta2Pow"][0], attrs)
    lr = ins["LearningRate"][0]
    p_norm = jnp.linalg.norm(p)
    r_norm = jnp.linalg.norm(r)
    trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    return _gate_update(ins, {
        "ParamOut": [p - lr * trust * r], "Moment1Out": [m_new],
        "Moment2Out": [v_new], "Beta1PowOut": [b1p_new],
        "Beta2PowOut": [b2p_new]})


@kernel("check_finite_and_unscale")
def _check_finite_and_unscale(ins, attrs, ctx):
    """Reference operators/amp/check_finite_and_scale_op.cc: divide every
    grad by the loss scale and flag non-finite values. Inserted by the
    auto_mixed_precision pass under fp16 (static loss scaling); the pass
    also wires FoundInfinite into the downstream update ops, which skip
    the whole step (params, moments, beta-pows) when it fires — the
    static-graph equivalent of GradScaler skipping optimizer.step().
    Grads are zeroed too, as a belt-and-braces for update ops outside
    the gated set."""
    xs = list(ins.get("X", []))
    scale = ins["Scale"][0] if ins.get("Scale") else attrs.get("scale", 1.0)
    inv = 1.0 / scale
    found = jnp.zeros((), jnp.bool_)
    for x in xs:
        found = found | jnp.any(~jnp.isfinite(x))
    outs = [jnp.where(found, jnp.zeros_like(x), (x * inv).astype(x.dtype))
            for x in xs]
    return {"Out": outs, "FoundInfinite": [found.reshape((1,))]}


@kernel("increment")
def _increment(ins, attrs, ctx):
    x = _x(ins)
    return _out(x + jnp.asarray(attrs.get("step", 1.0), x.dtype))


# ---------------------------------------------------------------------------
# control flow (reference operators/controlflow/conditional_block_op.cc and
# while_op.cc: an inner Executor runs the sub-block; here the sub-block is
# traced into lax.cond / lax.while_loop so the whole construct compiles)
# ---------------------------------------------------------------------------


def _sub_ctx(ctx, block_idx, extra=None):
    """Context for a sub-block trace: distinct RNG stream per block (and
    per loop iteration via `extra`), so random ops inside control flow
    don't reuse the outer block's per-op keys."""
    from dataclasses import replace

    key = ctx.rng_key
    if key is not None:
        key = jax.random.fold_in(key, 7919 + block_idx)
        if extra is not None:
            key = jax.random.fold_in(key, extra)
    return replace(ctx, rng_key=key)


@kernel("cond")
def _cond(ins, attrs, ctx):
    from .executor import run_block

    pred = jnp.reshape(ins["Cond"][0], ()).astype(bool)
    prog = ctx.program
    outer_env = dict(ctx.env)

    def make_branch(block_idx, out_names):
        blk = prog.blocks[block_idx]

        def branch(_):
            env = dict(outer_env)
            env = run_block(blk, env, _sub_ctx(ctx, block_idx))
            return tuple(env[n] for n in out_names)

        return branch

    outs = jax.lax.cond(
        pred,
        make_branch(attrs["sub_block_t"], attrs["out_t"]),
        make_branch(attrs["sub_block_f"], attrs["out_f"]),
        None)
    return {"Out": list(outs)}


@kernel("while")
def _while(ins, attrs, ctx):
    from .executor import run_block

    prog = ctx.program
    blk = prog.blocks[attrs["sub_block"]]
    loop_in = attrs["loop_in"]          # parent names body ops read
    body_out = attrs["body_out"]        # names body ops write
    cond_out = attrs["cond_out"]        # recomputed condition name
    outer_env = dict(ctx.env)
    init_vals = tuple(ins["X"])
    init_cond = jnp.reshape(ins["Cond"][0], ()).astype(bool)

    def cond_fn(state):
        return state[0]

    def body_fn(state):
        _, it, vals = state
        env = dict(outer_env)
        env.update(zip(loop_in, vals))
        # fresh RNG stream per iteration (it rides the loop carry)
        env = run_block(blk, env, _sub_ctx(ctx, attrs["sub_block"], it))
        return (jnp.reshape(env[cond_out], ()).astype(bool), it + 1,
                tuple(env[n] for n in body_out))

    _, _, final = jax.lax.while_loop(
        cond_fn, body_fn, (init_cond, jnp.asarray(0, jnp.int32), init_vals))
    return {"Out": list(final)}


# -- tensor arrays (reference LoDTensorArray + lod_tensor_array ops:
# operators/controlflow/while_op + array_write/read; here an array is a
# python list flowing through the env, so structure is trace-static) -----
@kernel("create_array")
def _create_array(ins, attrs, ctx):
    return {"Out": [[]]}


@kernel("array_write")
def _array_write(ins, attrs, ctx):
    """Write at a concrete index (overwrite or append, fluid semantics).
    A traced index falls back to append — the only pattern that cannot
    restructure a trace-static list, and the ubiquitous one (loops write
    at i == len)."""
    arr = list(ins["Array"][0])
    val = _x(ins)
    i = attrs.get("static_index")
    if i is None:
        try:
            i = int(ins["I"][0])
        except (KeyError, TypeError, jax.errors.ConcretizationTypeError):
            arr.append(val)
            return {"Out": [arr]}
    if i < len(arr):
        arr[i] = val
    elif i == len(arr):
        arr.append(val)
    else:
        raise IndexError(
            f"array_write index {i} beyond array length {len(arr)}")
    return {"Out": [arr]}


@kernel("array_read")
def _array_read(ins, attrs, ctx):
    arr = ins["X"][0]
    if "static_index" in attrs:
        return {"Out": [arr[int(attrs["static_index"])]]}
    i = ins["I"][0]
    try:
        return {"Out": [arr[int(i)]]}
    except (TypeError, jax.errors.ConcretizationTypeError):
        # traced index: stack equal-shaped elements, dynamic-index
        stacked = jnp.stack(arr, axis=0)
        return {"Out": [jax.lax.dynamic_index_in_dim(
            stacked, jnp.reshape(i, ()).astype(jnp.int32), axis=0,
            keepdims=False)]}


@kernel("array_length")
def _array_length(ins, attrs, ctx):
    return {"Out": [jnp.asarray([len(ins["X"][0])], jnp.int32)]}


@kernel("tensor_array_to_tensor")
def _tensor_array_to_tensor(ins, attrs, ctx):
    arr = ins["X"][0]
    axis = attrs.get("axis", 0)
    if attrs.get("use_stack", False):
        out = jnp.stack(arr, axis=axis)
        idx = jnp.asarray([1] * len(arr), jnp.int32)
    else:
        out = jnp.concatenate(arr, axis=axis)
        idx = jnp.asarray([a.shape[axis] for a in arr], jnp.int32)
    return {"Out": [out], "OutIndex": [idx]}
