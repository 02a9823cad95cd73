"""Tensor: the user-facing value type.

TPU-native replacement for the reference VarBase/LoDTensor pair
(/root/reference/paddle/fluid/imperative/layer.cc VarBase,
framework/lod_tensor.cc): a thin mutable wrapper over an immutable
jax.Array. Mutability (in-place optimizer updates, set_value) swaps the
underlying buffer; the array itself lives wherever XLA placed it (HBM).
LoD raggedness is represented as dense + separate segment metadata
(see paddle_tpu.ops.sequence), not offset-carrying tensors.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from . import tape as tape_mod

_tensor_count = [0]


class Tensor:
    __slots__ = ("_value", "stop_gradient", "grad", "_node", "name",
                 "persistable", "trainable", "__weakref__")

    def __init__(self, value, dtype=None, place=None, stop_gradient=True,
                 name=None, persistable=False):
        if isinstance(value, Tensor):
            value = value._value
        if not isinstance(value, jax.Array) or dtype is not None:
            np_dtype = dtype_mod.convert_dtype(dtype) if dtype is not None else None
            if np_dtype is None and not hasattr(value, "dtype"):
                # python scalars / lists follow the default dtype for floats
                arr = np.asarray(value)
                if arr.dtype == np.float64:
                    np_dtype = dtype_mod.get_default_dtype()
            value = jnp.asarray(value, dtype=np_dtype)
        if place is not None:
            value = jax.device_put(value, place.jax_device())
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self._node = None
        if name is None:
            _tensor_count[0] += 1
            name = f"tensor_{_tensor_count[0]}"
        self.name = name
        self.persistable = persistable
        self.trainable = not stop_gradient

    # -- basic properties ---------------------------------------------------
    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return tuple(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self):
        from .place import CPUPlace, TPUPlace

        dev = next(iter(self._value.devices()))
        if dev.platform == "tpu":
            return TPUPlace(dev.id)
        return CPUPlace(dev.id)

    @property
    def T(self):
        from .. import ops

        return ops.transpose(self, list(range(self.ndim))[::-1])

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.shape[0]

    def __repr__(self):
        grad_str = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={list(self.shape)}, dtype={dtype_mod.dtype_name(self.dtype)}"
                f"{grad_str},\n       {np.asarray(self._value)})")

    # -- conversion ---------------------------------------------------------
    def numpy(self):
        return np.asarray(self._value)

    def item(self):
        return self._value.item()

    def tolist(self):
        return np.asarray(self._value).tolist()

    def astype(self, dtype):
        from .. import ops

        return ops.cast(self, dtype)

    def cast(self, dtype):
        return self.astype(dtype)

    def cpu(self):
        from .place import CPUPlace

        return Tensor(jax.device_put(self._value, CPUPlace(0).jax_device()),
                      stop_gradient=self.stop_gradient)

    def to(self, place_or_dtype):
        from .place import Place

        if isinstance(place_or_dtype, Place):
            return Tensor(jax.device_put(self._value, place_or_dtype.jax_device()),
                          stop_gradient=self.stop_gradient)
        return self.astype(place_or_dtype)

    def __array__(self, dtype=None):
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype is not None else arr

    def __float__(self):
        return float(self._value)

    def __int__(self):
        return int(self._value)

    def __bool__(self):
        return bool(self._value)

    def __index__(self):
        return int(self._value)

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        tape_mod.backward(self, grad_tensor, retain_graph)

    def detach(self):
        t = Tensor(self._value, stop_gradient=True, name=self.name + ".detach")
        return t

    def clone(self):
        from .. import ops

        return ops.assign(self)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):
        self.grad = None

    def _accumulate_grad(self, g):
        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True, name=self.name + "@GRAD")
        else:
            self.grad._value = self.grad._value + g

    # -- in-place (buffer-swap) mutation ------------------------------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._value
        new = jnp.asarray(value, dtype=self.dtype)
        if tuple(new.shape) != self.shape:
            raise ValueError(f"set_value shape mismatch {new.shape} vs {self.shape}")
        self._value = new
        return self

    def copy_(self, other):
        return self.set_value(other)

    def fill_(self, v):
        self._value = jnp.full(self.shape, v, dtype=self.dtype)
        return self

    def zero_(self):
        return self.fill_(0)

    def scale_(self, s):
        self._value = self._value * s
        return self

    def add_(self, other):
        o = other._value if isinstance(other, Tensor) else other
        self._value = self._value + jnp.asarray(o, dtype=self.dtype)
        return self

    def subtract_(self, other):
        o = other._value if isinstance(other, Tensor) else other
        self._value = self._value - jnp.asarray(o, dtype=self.dtype)
        return self

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, idx):
        from .. import ops

        return ops.getitem(self, idx)

    def __setitem__(self, idx, value):
        v = value._value if isinstance(value, Tensor) else value
        self._value = self._value.at[idx].set(v)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


def is_tensor(x):
    return isinstance(x, Tensor)


def unwrap(x):
    """Tensor|array -> jax array (helper for op implementations)."""
    return x._value if isinstance(x, Tensor) else x
