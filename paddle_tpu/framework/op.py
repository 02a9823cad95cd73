"""Op primitive bridge: pure jnp function -> eager Tensor op with autograd.

TPU-native replacement for the reference op registry + kernel dispatch
(/root/reference/paddle/fluid/framework/op_registry.h:223 REGISTER_OPERATOR,
operator.cc:1068 ChooseKernel): there is no (place,dtype,layout) kernel map —
XLA is the only backend. An "op" here is a pure function over jax arrays;
the @primitive decorator makes it accept/return Tensors, records a TapeNode
(via jax.vjp) in eager mode, and passes raw tracers straight through inside
jit so the same op library serves both execution engines.
"""
from __future__ import annotations

import functools
import inspect
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from . import flags
from . import nan_inf
from . import tape as tape_mod
from .tensor import Tensor

# global op registry: name -> wrapped callable (for introspection/parity checks)
OP_REGISTRY: Dict[str, Callable] = {}


def _is_tensor_leaf(x):
    return isinstance(x, Tensor)


def _differentiable(t: Tensor) -> bool:
    return (not t.stop_gradient) and dtype_mod.is_inexact(t.dtype)


def primitive(name=None, nondiff=()):
    """Wrap a pure jnp function as a framework op.

    The wrapped function receives jax arrays wherever the caller passed
    Tensors (including inside lists/tuples one level deep), plus untouched
    static kwargs, and must return an array or a (nested) tuple of arrays.

    nondiff: names of args never differentiated even if Tensors (matched
    against the function signature, so positional calls are covered too).
    """

    def deco(fn):
        op_name = name or fn.__name__
        try:
            _sig = inspect.signature(fn)
        except (TypeError, ValueError):
            _sig = None

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            flat, treedef = jax.tree_util.tree_flatten(
                (args, kwargs), is_leaf=_is_tensor_leaf
            )
            tensor_pos = [i for i, x in enumerate(flat) if isinstance(x, Tensor)]
            if not tensor_pos:
                out = fn(*args, **kwargs)
                return _wrap_outputs(out, stop_gradient=True)

            arrays = list(flat)
            for i in tensor_pos:
                arrays[i] = flat[i]._value

            from ..amp import amp_enabled, maybe_cast_inputs

            if amp_enabled():
                casted = maybe_cast_inputs(
                    op_name, [arrays[i] for i in tensor_pos])
                for i, a in zip(tensor_pos, casted):
                    arrays[i] = a

            record = tape_mod.grad_enabled()
            diff_pos = (
                [i for i in tensor_pos if _differentiable(flat[i])] if record else []
            )
            # nondiff args: drop their positions from diff set (bind via
            # the signature so positionally-passed args are covered)
            if diff_pos and nondiff:
                sources = {k: kwargs[k] for k in nondiff if k in kwargs}
                if _sig is not None and len(sources) < len(nondiff):
                    try:
                        bound = _sig.bind(*args, **kwargs)
                        for k in nondiff:
                            if k in bound.arguments:
                                sources[k] = bound.arguments[k]
                    except TypeError:
                        pass
                banned = set()
                for val in sources.values():
                    sub, _ = jax.tree_util.tree_flatten(
                        val, is_leaf=_is_tensor_leaf
                    )
                    banned.update(id(x) for x in sub if isinstance(x, Tensor))
                diff_pos = [i for i in diff_pos if id(flat[i]) not in banned]

            if not diff_pos:
                a, kw = jax.tree_util.tree_unflatten(treedef, arrays)
                out = fn(*a, **kw)
                if flags.get_flag("check_nan_inf"):
                    nan_inf.check_outputs(op_name, out)
                return _wrap_outputs(out, stop_gradient=True)

            def pure(*diff_arrays):
                buf = list(arrays)
                for p, arr in zip(diff_pos, diff_arrays):
                    buf[p] = arr
                a, kw = jax.tree_util.tree_unflatten(treedef, buf)
                return fn(*a, **kw)

            primals = [arrays[p] for p in diff_pos]
            out, vjp = jax.vjp(pure, *primals)
            node = tape_mod.TapeNode(vjp, [flat[p] for p in diff_pos],
                                     op_name, pure_fn=pure, primals=primals)
            result = _wrap_outputs(out, stop_gradient=False, node=node)
            if flags.get_flag("check_nan_inf"):
                nan_inf.check_outputs(op_name, out)
            return result

        wrapper.op_name = op_name
        wrapper.raw_fn = fn
        OP_REGISTRY[op_name] = wrapper
        return wrapper

    return deco


def _wrap_outputs(out, stop_gradient, node=None):
    leaves, treedef = jax.tree_util.tree_flatten(out)
    wrapped = []
    for leaf in leaves:
        t = Tensor(leaf, stop_gradient=stop_gradient)
        if node is not None:
            t._node = node
            node.add_output(t)
        wrapped.append(t)
    return jax.tree_util.tree_unflatten(treedef, wrapped)


def unwrap_args(*xs):
    return tuple(x._value if isinstance(x, Tensor) else x for x in xs)
