"""Layer: the module base class.

Parity with the reference dygraph Layer
(/root/reference/python/paddle/fluid/dygraph/layers.py:675 Layer.__call__,
create_parameter, sublayers, state_dict) re-designed for JAX: parameters
are Tensors (mutable buffer holders), and the whole layer tree can be
snapshotted to / restored from a pytree so one model definition serves
eager mode and jit-compiled functional training steps (see paddle_tpu.jit).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from ..framework import dtype as dtype_mod
from ..framework import nan_inf
from ..framework.tensor import Tensor
from . import initializer as I


class ParamAttr:
    """Parity with fluid.ParamAttr (name/initializer/lr/regularizer/trainable)."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=False,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if attr is False:
            return None
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        raise TypeError(f"Cannot interpret {attr!r} as ParamAttr")


class Parameter(Tensor):
    __slots__ = ("optimize_attr", "regularizer", "need_clip", "is_distributed")

    def __init__(self, value, trainable=True, name=None, learning_rate=1.0,
                 regularizer=None, need_clip=True):
        super().__init__(value, stop_gradient=not trainable, name=name,
                         persistable=True)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": learning_rate}
        self.regularizer = regularizer
        self.need_clip = need_clip
        self.is_distributed = False


_name_counters = {}


def _scope_name(name) -> str:
    """The ``jax.named_scope`` of a sublayer registered under ``name``:
    the name itself, and ``layer`` for an index in a container
    (``layers.3``), so that a profile groups the twelve encoder layers
    under one ``encoder/layer`` and no scope holds a layer index."""
    name = str(name)
    return "layer" if name.isdigit() else name


def _unique_name(prefix):
    n = _name_counters.get(prefix, 0)
    _name_counters[prefix] = n + 1
    return f"{prefix}_{n}"


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        self._dtype = dtype_mod.convert_dtype(dtype)
        self._full_name = _unique_name(name_scope or type(self).__name__.lower())
        self.training = True
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()

    # -- construction -------------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is None:
            return None
        dtype = dtype_mod.convert_dtype(dtype) if dtype else self._dtype
        if default_initializer is None:
            default_initializer = I.global_initializer(is_bias) or (
                I.Constant(0.0) if is_bias else I.XavierUniform())
        init = I._resolve(attr.initializer, default_initializer)
        value = init(tuple(int(s) for s in shape), dtype)
        return Parameter(value, trainable=attr.trainable,
                         name=attr.name or _unique_name(self._full_name + ".w"),
                         learning_rate=attr.learning_rate,
                         regularizer=attr.regularizer, need_clip=attr.need_clip)

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            object.__setattr__(sublayer, "_scope", _scope_name(name))
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        if tensor is not None:
            tensor.persistable = persistable
        self._buffers[name] = tensor
        return tensor

    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter) and params is not None:
            params[name] = value
            layers.pop(name, None)
            buffers.pop(name, None) if buffers else None
        elif isinstance(value, Layer) and layers is not None:
            layers[name] = value
            params.pop(name, None)
            object.__setattr__(value, "_scope", _scope_name(name))
        else:
            if params is not None and name in params and value is None:
                params[name] = None
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # -- forward ------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        # the name its parent registered it under: metadata of the ops
        # traced inside (``bert/encoder/layer/self_attn``), nothing at
        # step time; a root layer has none
        scope = self.__dict__.get("_scope")
        # the step's FLAGS_check_nan_inf record, in a step built with the
        # flag set: a row for each output (framework/nan_inf.py)
        record = nan_inf.record
        if scope is None:
            out = self.forward(*inputs, **kwargs)
        elif record is None:
            with jax.named_scope(scope):
                out = self.forward(*inputs, **kwargs)
        else:
            with jax.named_scope(scope):
                out = record.layer_call(self, inputs, kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    def register_forward_pre_hook(self, hook):
        handle = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.hook_id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[handle.hook_id] = hook
        return handle

    # -- traversal ----------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield p, layer
            yield from layer.named_sublayers(prefix=p)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                lp = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(prefix=lp):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                lp = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(prefix=lp)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # -- modes --------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # -- state dict ---------------------------------------------------------
    def _state_targets(self, structured_name_prefix=""):
        """The LIVE persistable tensors, un-cast: set_state_dict must
        mutate these, never the save-dtype copies state_dict hands out."""
        out = OrderedDict()
        for n, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            out[n] = p
        for n, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            if b.persistable:
                out[n] = b
        return out

    def state_dict(self, include_sublayers=True, structured_name_prefix=""):
        out = self._state_targets(structured_name_prefix)
        # amp.decorate(save_dtype=...): checkpoints keep the requested
        # dtype even when the live params run low precision under O2
        # (fresh Tensors — the live params are not touched)
        save_dtype = getattr(self, "_amp_save_dtype", None)
        if save_dtype is not None:
            target = dtype_mod.convert_dtype(save_dtype)
            for n, t in out.items():
                if dtype_mod.is_inexact(t.dtype) and \
                        dtype_mod.convert_dtype(t.dtype) != target:
                    out[n] = Tensor(t.value.astype(target))
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self._state_targets()
        missing = []
        for name, tensor in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                tensor.set_value(arr.astype(np.dtype(tensor.dtype)))
            else:
                missing.append(name)
        return missing

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- pytree snapshot (bridge to functional/jit execution) ----------------
    def param_pytree(self, trainable_only=False):
        return {
            n: p.value for n, p in self.named_parameters()
            if (p.trainable or not trainable_only)
        }

    def buffer_pytree(self):
        return {n: b.value for n, b in self.named_buffers()}

    def load_param_pytree(self, tree):
        for n, p in self.named_parameters():
            if n in tree:
                p._value = tree[n]

    def load_buffer_pytree(self, tree):
        for n, b in self.named_buffers():
            if n in tree:
                b._value = tree[n]

    # -- dtype / device moves ------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dtype = dtype_mod.convert_dtype(dtype)
            for p in self.parameters():
                p._value = p._value.astype(dtype)
            for _, b in self.named_buffers():
                if dtype_mod.is_inexact(b.dtype):
                    b._value = b._value.astype(dtype)
            for l in self.sublayers(include_self=True):
                l._dtype = dtype
        if device is not None:
            from ..framework.place import Place

            if isinstance(device, str):
                from ..framework.place import set_device

                place = set_device(device)
            else:
                place = device
            dev = place.jax_device()
            for p in self.parameters():
                p._value = jax.device_put(p._value, dev)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def full_name(self):
        return self._full_name

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n".join("  " + l for l in mod_str.split("\n"))
            lines.append(f"  ({name}): {mod_str.strip()}")
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"


class HookRemoveHelper:
    _next_id = [0]

    def __init__(self, hooks):
        self._hooks = hooks
        self.hook_id = HookRemoveHelper._next_id[0]
        HookRemoveHelper._next_id[0] += 1

    def remove(self):
        self._hooks.pop(self.hook_id, None)
