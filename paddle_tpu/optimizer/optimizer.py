"""Optimizers.

Parity with /root/reference/python/paddle/fluid/optimizer.py (Optimizer :56,
SGD :947, Momentum :1041, LarsMomentum :1591, Adagrad :1705, Adam :1821,
Adamax :2087, DecayedAdagrad :2354, Adadelta :2464, RMSProp :2583,
Ftrl :2771, Lamb :2930) re-designed functionally: every optimizer is a pure
(grads, params, state, lr, step) -> (params, state) rule. Eager .step()
runs the rule as one jitted pytree update (the whole optimizer is a single
fused XLA program — the reference needed fuse_optimizer_ops_pass for this);
jitted train steps call the same rule inline.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..framework import random as random_mod
from ..framework.tensor import Tensor
from ..nn.clip import ClipGradBase
from .lr import LRScheduler

_tmap = jax.tree_util.tree_map


class Optimizer:
    """Base class. Subclasses define init_slot(p) and rule(g, p, slots, lr, t)."""

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        self._learning_rate = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        if weight_decay is None:
            self._l2_coeff = 0.0
            self._wd = None
        elif isinstance(weight_decay, (int, float)):
            self._l2_coeff = float(weight_decay)
            self._wd = None
        elif self.DECOUPLED_WD:
            # AdamW-style: a regularizer object degrades to its coefficient,
            # applied decoupled (reference AdamW semantics take a float)
            self._l2_coeff = float(getattr(weight_decay, "coeff", 0.0))
            self._wd = None
        else:
            # coupled regularizer (L1Decay/L2Decay): folded into grads
            self._l2_coeff = 0.0
            self._wd = weight_decay
        self._regs_by_key = {}   # per-param override (ParamAttr.regularizer)
        self._grad_clip = grad_clip
        self._step_count = 0
        self._slots: Dict[int, dict] = {}
        self._jit_update = None
        # multi-precision (amp.decorate O2 master_weight=True): a low-
        # precision param keeps an f32 master copy in its slot dict; the
        # rule runs in f32 and the param gets the cast-down of the master
        self._multi_precision = bool(multi_precision)

    # -- functional API ------------------------------------------------------
    def init_state(self, params, param_objs=None):
        """params: pytree of arrays -> state pytree (slots + step).

        If `param_objs` (name -> Parameter, matching the keys of a dict
        `params`) is given, slots restored via set_state_dict seed the
        state instead of zeros, so checkpoint-resume keeps optimizer
        moments when training through jit.TrainStep."""
        if param_objs and isinstance(params, dict):
            self._set_regs({n: getattr(p, "regularizer", None)
                            for n, p in param_objs.items()})
            slots = {}
            for n, p in params.items():
                base = self._init_slot_mp(p)
                restored = (self._slots.get(id(param_objs[n]))
                            if n in param_objs else None)
                if restored:
                    for k, v in restored.items():
                        if k in base:
                            base[k] = jnp.asarray(
                                v, getattr(base[k], "dtype", None))
                slots[n] = base
        else:
            slots = _tmap(lambda p: self._init_slot_mp(p), params)
        return {"slots": slots,
                "step": jnp.asarray(self._step_count, jnp.int32)}

    def _init_slot_mp(self, p):
        """init_slot, plus the f32 master copy when multi-precision is on
        and the param itself is low precision: moments are seeded from
        (and shaped like) the master so the whole update runs f32."""
        if self._multi_precision and p.dtype in (jnp.bfloat16, jnp.float16):
            master = p.astype(jnp.float32)
            slots = dict(self.init_slot(master))
            slots["__master__"] = master
            return slots
        return self.init_slot(p)

    def apply_gradients_fn(self, grads, params, state, lr=None):
        """Pure update: returns (new_params, new_state). Used inside jit."""
        lr = self.get_lr() if lr is None else lr
        step = state["step"] + 1
        if self._grad_clip is not None:
            grads = self._grad_clip.apply_pytree(grads)
        grads = self._append_regularization(grads, params)

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_p = treedef.flatten_up_to(params)
        flat_s = treedef.flatten_up_to(state["slots"])
        new_p, new_s = [], []
        for g, p, s in zip(flat_g, flat_p, flat_s):
            if g is None:
                new_p.append(p)
                new_s.append(s)
                continue
            # the gradient is whole before its update reads it. Without
            # the barrier XLA merges the update into the fusion that
            # forms a weight's gradient, and that merged fusion is slower
            # than the product and the update apart: BERT's step lost
            # 3.2% to it on a v5e, the Kimi cell 0.4% (PERF.md section 6,
            # PR 29). Arithmetic and memory peak are unchanged.
            g = jax.lax.optimization_barrier(g)
            master = s.get("__master__") if isinstance(s, dict) else None
            if master is not None:
                # multi-precision: update the f32 master, cast down for
                # the compute param — the low-precision grad only ever
                # touches f32 state
                lr32 = jnp.asarray(lr, master.dtype)
                sub = {k: v for k, v in s.items() if k != "__master__"}
                m2, s2 = self.rule(g.astype(master.dtype), master, sub,
                                   lr32, step)
                if self._l2_coeff and self.DECOUPLED_WD:
                    m2 = m2 - lr32 * self._l2_coeff * master
                s2 = dict(s2)
                s2["__master__"] = m2
                new_p.append(m2.astype(p.dtype))
                new_s.append(s2)
                continue
            p2, s2 = self.rule(g, p, s, jnp.asarray(lr, p.dtype), step)
            if self._l2_coeff and self.DECOUPLED_WD:
                p2 = p2 - jnp.asarray(lr, p.dtype) * self._l2_coeff * p
            new_p.append(p2)
            new_s.append(s2)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                {"slots": jax.tree_util.tree_unflatten(treedef, new_s),
                 "step": step})

    DECOUPLED_WD = False

    def _append_regularization(self, grads, params):
        """Fold weight-decay gradient terms into `grads`. A per-parameter
        regularizer (ParamAttr.regularizer, collected into _regs_by_key)
        overrides the optimizer-level one — the reference's
        append_regularization_ops precedence (fluid/regularizer.py:36)."""
        from .. import regularizer as _reg

        default = self._wd
        if default is None and self._l2_coeff and not self.DECOUPLED_WD:
            default = _reg.L2Decay(self._l2_coeff)
        table = self._regs_by_key
        if not table and default is None:
            return grads

        def f(path, g, p):
            key = path[-1].key if path and hasattr(path[-1], "key") else None
            reg = table.get(key, default)
            return g if reg is None else g + reg.grad_term(p)

        return jax.tree_util.tree_map_with_path(f, grads, params)

    def init_slot(self, p):
        return {}

    def rule(self, g, p, slots, lr, t):
        raise NotImplementedError

    def _set_regs(self, table):
        """Record per-param regularizers; the jitted update closes over the
        table at trace time, so a change invalidates the cached trace."""
        table = {k: v for k, v in table.items() if v is not None}
        if table != self._regs_by_key:
            self._regs_by_key = table
            self._jit_update = None

    # -- eager API -----------------------------------------------------------
    def _params(self):
        if self._parameter_list is None:
            raise ValueError("Optimizer constructed without parameters; "
                             "pass parameters=layer.parameters()")
        return [p for p in self._parameter_list if p.trainable]

    def step(self):
        params = self._params()
        updatable = [(i, p) for i, p in enumerate(params) if p.grad is not None]
        if not updatable:
            self._step_count += 1
            return
        names = [str(i) for i, _ in updatable]
        pdict = {n: p.value for n, (_, p) in zip(names, updatable)}
        gdict = {n: p.grad.value for n, (_, p) in zip(names, updatable)}
        # per-param slots live on the Tensor id
        sdict = {}
        for n, (_, p) in zip(names, updatable):
            if id(p) not in self._slots:
                self._slots[id(p)] = self._init_slot_mp(p.value)
            sdict[n] = self._slots[id(p)]
        state = {"slots": sdict, "step": jnp.asarray(self._step_count, jnp.int32)}
        self._set_regs({n: getattr(p, "regularizer", None)
                        for n, (_, p) in zip(names, updatable)})
        lr = self.get_lr()
        if self._jit_update is None:
            self._jit_update = jax.jit(
                lambda g, p, s, lr: self.apply_gradients_fn(g, p, s, lr))
        new_params, new_state = self._jit_update(gdict, pdict, state,
                                                 jnp.asarray(lr, jnp.float32))
        for n, (_, p) in zip(names, updatable):
            p._value = new_params[n]
            self._slots[id(p)] = new_state["slots"][n]
        self._step_count += 1

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        if loss is not None and loss._node is not None and all(
                p.grad is None for p in self._params()):
            loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=False):
        for p in self._params():
            p.clear_grad()

    clear_gradients = clear_grad

    # -- lr ------------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("set_lr cannot override an LRScheduler")
        self._learning_rate = float(value)

    # -- state dict ----------------------------------------------------------
    def state_dict(self):
        out = {"step": self._step_count}
        params = self._parameter_list or []
        for p in params:
            s = self._slots.get(id(p))
            if s:
                for k, v in s.items():
                    out[f"{p.name}@{k}"] = Tensor(v)
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("step", 0))
        params = self._parameter_list or []
        for p in params:
            slot = {}
            for key, v in state.items():
                if key.startswith(p.name + "@"):
                    slot[key.split("@", 1)[1]] = (
                        v.value if isinstance(v, Tensor) else jnp.asarray(v))
            if slot:
                self._slots[id(p)] = slot
        if "LR_Scheduler" in state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])


class SGD(Optimizer):
    def rule(self, g, p, slots, lr, t):
        return p - lr * g, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._momentum = momentum
        self._nesterov = use_nesterov

    def init_slot(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        v = self._momentum * slots["velocity"] + g
        if self._nesterov:
            p2 = p - lr * (g + self._momentum * v)
        else:
            p2 = p - lr * v
        return p2, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def init_slot(self, p):
        return {"moment1": jnp.zeros_like(p), "moment2": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment1"] + (1 - b1) * g
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        mhat = m / (1 - b1 ** tf).astype(p.dtype)
        vhat = v / (1 - b2 ** tf).astype(p.dtype)
        p2 = p - lr * mhat / (jnp.sqrt(vhat) + self._eps)
        return p2, {"moment1": m, "moment2": v}


class AdamW(Adam):
    DECOUPLED_WD = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 grad_clip=None, lr_ratio=None, apply_decay_param_fun=None,
                 name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, **kw)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def init_slot(self, p):
        return {"moment": jnp.zeros_like(p), "inf_norm": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * slots["inf_norm"], jnp.abs(g))
        tf = t.astype(jnp.float32)
        lr_t = lr / (1 - b1 ** tf).astype(p.dtype)
        p2 = p - lr_t * m / (u + self._eps)
        return p2, {"moment": m, "inf_norm": u}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def init_slot(self, p):
        return {"moment": jnp.full_like(p, self._init_acc)}

    def rule(self, g, p, slots, lr, t):
        acc = slots["moment"] + jnp.square(g)
        p2 = p - lr * g / (jnp.sqrt(acc) + self._eps)
        return p2, {"moment": acc}


class DecayedAdagrad(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 parameters=None, weight_decay=None, grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._decay, self._eps = decay, epsilon

    def init_slot(self, p):
        return {"moment": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        acc = self._decay * slots["moment"] + (1 - self._decay) * jnp.square(g)
        p2 = p - lr * g / (jnp.sqrt(acc) + self._eps)
        return p2, {"moment": acc}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._eps, self._rho = epsilon, rho

    def init_slot(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p),
                "avg_squared_update": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        rho, eps = self._rho, self._eps
        eg = rho * slots["avg_squared_grad"] + (1 - rho) * jnp.square(g)
        update = -jnp.sqrt((slots["avg_squared_update"] + eps) / (eg + eps)) * g
        eu = rho * slots["avg_squared_update"] + (1 - rho) * jnp.square(update)
        return p + lr * update, {"avg_squared_grad": eg,
                                 "avg_squared_update": eu}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def init_slot(self, p):
        return {"mean_square": jnp.zeros_like(p),
                "mean_grad": jnp.zeros_like(p),
                "momentum": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        rho = self._rho
        ms = rho * slots["mean_square"] + (1 - rho) * jnp.square(g)
        mg = rho * slots["mean_grad"] + (1 - rho) * g if self._centered \
            else slots["mean_grad"]
        denom = ms - jnp.square(mg) if self._centered else ms
        mom = self._momentum * slots["momentum"] + \
            lr * g / jnp.sqrt(denom + self._eps)
        return p - mom, {"mean_square": ms, "mean_grad": mg, "momentum": mom}


class Ftrl(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 parameters=None, weight_decay=None, grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def init_slot(self, p):
        return {"squared": jnp.zeros_like(p), "linear": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        n, z = slots["squared"], slots["linear"]
        n2 = n + jnp.square(g)
        lp = -self._lr_power
        sigma = (n2 ** lp - n ** lp) / lr
        z2 = z + g - sigma * p
        p2 = jnp.where(
            jnp.abs(z2) <= self._l1, jnp.zeros_like(p),
            -(z2 - jnp.sign(z2) * self._l1) /
            (n2 ** lp / lr + 2 * self._l2))
        return p2, {"squared": n2, "linear": z2}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None, **kw):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def init_slot(self, p):
        return {"moment1": jnp.zeros_like(p), "moment2": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment1"] + (1 - b1) * g
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        mhat = m / (1 - b1 ** tf).astype(p.dtype)
        vhat = v / (1 - b2 ** tf).astype(p.dtype)
        r = mhat / (jnp.sqrt(vhat) + self._eps) + self._lamb_wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, {"moment1": m, "moment2": v}


class LarsMomentum(Optimizer):
    """operators/optimizers/lars_momentum_op.cc parity."""

    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 epsilon=1e-9, **kw):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision=kw.get("multi_precision", False))
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon

    def init_slot(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def rule(self, g, p, slots, lr, t):
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            lr * self._lars_coeff * w_norm /
            (g_norm + self._lars_wd * w_norm + self._eps), lr)
        v = self._momentum * slots["velocity"] + \
            local_lr * (g + self._lars_wd * p)
        return p - v, {"velocity": v}


class Dpsgd(Optimizer):
    """Differentially-private SGD (reference optimizer.py:2259): gaussian
    noise added to gradients."""

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16,
                 sigma=1.0, parameters=None, seed=0, **kw):
        super().__init__(learning_rate, parameters,
                         multi_precision=kw.get("multi_precision", False))
        self._clip, self._batch, self._sigma = clip, batch_size, sigma
        self._key = random_mod.make_key(seed or 0)

    def rule(self, g, p, slots, lr, t):
        sub = jax.random.fold_in(self._key, t)
        gnorm = jnp.sqrt(jnp.sum(jnp.square(g)))
        g = g / jnp.maximum(1.0, gnorm / self._clip)
        noise = self._sigma * self._clip / self._batch * \
            jax.random.normal(sub, g.shape, g.dtype)
        return p - lr * (g + noise), slots
