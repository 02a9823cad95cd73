"""Meta-optimizers: wrappers that change the update schedule.

Parity with the reference optimizer.py meta family (ModelAverage :3102,
EMA :3411, PipelineOptimizer :3661, RecomputeOptimizer :4513, Lookahead
:4822, GradientMergeOptimizer :4988). Pipeline lives in
paddle_tpu.parallel.pipeline; recompute maps onto jax.checkpoint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from .optimizer import Optimizer


class GradientMergeOptimizer:
    """Accumulate grads for k_steps micro-batches, then apply once
    (reference optimizer.py:4988).

    avg semantics: with ``avg=True`` the MERGED gradient is divided by
    ``k_steps`` once before the single inner step — single-large-batch
    parity — never a per-microbatch lr rescale. After the merged update
    the param grads are cleared here (not left to the caller): the
    reference's minimize-only protocol issues no clear_grad between
    cycles, and a stale merged grad would be double-counted into the
    next cycle's first backward()."""

    def __init__(self, inner_optimizer, k_steps=1, avg=True):
        self.inner = inner_optimizer
        self.k_steps = k_steps
        self.avg = avg
        self._acc = {}
        self._count = 0

    def step(self):
        params = self.inner._params()
        self._count += 1
        for p in params:
            if p.grad is None:
                continue
            if id(p) in self._acc:
                self._acc[id(p)] = self._acc[id(p)] + p.grad.value
            else:
                self._acc[id(p)] = p.grad.value
        if self._count < self.k_steps:
            for p in params:
                p.clear_grad()
            return False
        for p in params:
            if id(p) in self._acc:
                g = self._acc[id(p)]
                if self.avg:
                    g = g / self.k_steps
                p.grad = Tensor(g)
        self.inner.step()
        for p in params:
            p.clear_grad()
        self._acc.clear()
        self._count = 0
        return True

    def minimize(self, loss, **kw):
        if loss._node is not None:
            loss.backward()
        self.step()
        return None, None

    def clear_grad(self):
        self.inner.clear_grad()

    def __getattr__(self, item):
        return getattr(self.inner, item)


def _segment_params(fn):
    """Trainable Tensors a recompute segment closes over: a Layer's (or
    a bound Layer method's) parameters. Plain functions close over
    nothing trainable — their tensor args carry the gradient path."""
    owner = fn
    if not hasattr(owner, "parameters") and hasattr(fn, "__self__"):
        owner = fn.__self__
    if hasattr(owner, "parameters"):
        try:
            return list(owner.parameters())
        except TypeError:
            return list(owner.parameters)
    return []


@functools.cache
def _kept_policy():
    """What a recomputed segment keeps beside its inputs: the values
    named ``flash_attention.KEPT`` (the flash kernels' output and
    logsumexp), ``kda.KEPT`` (the KDA chunk kernel's output and chunk
    states) and ``nn.moe.KEPT`` (the sort of a dropless expert layer's
    pairs on its kernels' rungs). ONE object for every segment: jax keys
    its partial-evaluation caches on the policy, and a policy a block
    would part the jitted helpers the blocks share (``tril``, ``silu``,
    ...) once a block."""
    from ..nn import moe
    from ..ops.pallas import flash_attention, kda

    return jax.checkpoint_policies.save_only_these_names(
        flash_attention.KEPT, kda.KEPT, moe.KEPT)


def recompute(function, *args, **kwargs):
    """Eager activation rematerialization (reference
    fleet.utils.recompute / RecomputeOptimizer checkpoints): run
    ``function`` WITHOUT recording per-op vjp closures — the tape gets
    ONE node for the whole segment whose backward re-runs the segment
    under ``jax.vjp`` at cotangent time. Forward-pass memory for the
    segment is its inputs + params, not its activations.

    RNG correctness: the default generator's state is snapshotted before
    the forward run and restored around the recompute, so a dropout
    inside the segment replays the bitwise-identical mask.

    Inside a jit trace (TrainStep) the same call lowers to
    ``jax.checkpoint`` — XLA remat, same semantics, compiled. There a
    segment keeps its inputs, its parameters AND what the flash
    attention kernels inside it wrote, their output and logsumexp
    (``flash_attention.KEPT``: H x (2 dv + 4) bytes a token beside the
    segment's input): the backward's second run of the segment brings
    q, k, v back from the projections, which is what recomputation is
    for, and would launch the O(T^2) forward kernel only to write the
    same two arrays again. There is no length the kernels accept at
    which that launch is cheaper than the bytes, so it is no option.
    The dispatch counts ``flash_attention.kept_across_recompute``. The
    same holds for the KDA chunk kernel (``kda.KEPT``: its float32
    output and the state every 64-token chunk started from, 4 H (V +
    V K / 64) bytes a token; ``kda_chunk.kept_across_recompute``): the
    second run brings q, k, v, g, beta back and does not walk the
    sequence again. The Mamba-2 scan's outputs are not kept: its second
    run is 1.3% of that cell's step for the same bytes (ROADMAP S19(c)).

    A segment may be called several times in one step on the SAME
    parameters (a looped model walks its blocks ``total_ut_steps``
    times): every call is a segment of its own, with its own kept input
    and its own kept flash outputs, and a parameter's gradient is the sum
    over its uses. Under jit that is ``jax.checkpoint``'s own transpose;
    on the eager tape each call is one node whose vjp hands the
    parameter a cotangent, and the tape adds them into ``p.grad``
    (``tests/test_looped_lm.py`` holds both to an untied model)."""
    from ..framework import nan_inf
    from ..framework import random as random_mod
    from ..framework import tape as tape_mod
    from ..framework.tensor import Tensor

    # keyword Tensors get no tape edge (the vjp replay substitutes
    # positional tensors only) — silently wrong gradients; refuse, like
    # the reference fleet.utils.recompute
    for k, v in kwargs.items():
        if isinstance(v, Tensor):
            raise ValueError(
                f"recompute: Tensor keyword argument {k!r} is not "
                "supported — pass tensors positionally so gradients "
                "flow through them")
    params = _segment_params(function)
    arg_ts = [a for a in args if isinstance(a, Tensor)]

    def _call_with(arg_vals, param_vals, meta):
        saved = [(p, p._value) for p in params]
        try:
            for p, v in zip(params, param_vals):
                p._value = v
            it = iter(arg_vals)
            new_args = [Tensor(next(it)) if isinstance(a, Tensor) else a
                        for a in args]
            with tape_mod.no_grad():
                out = function(*new_args, **kwargs)
        finally:
            for p, v in saved:
                p._value = v
        single = not isinstance(out, (tuple, list))
        meta["single"] = single
        outs = [out] if single else list(out)
        return [o.value if isinstance(o, Tensor) else jnp.asarray(o)
                for o in outs]

    traced = any(isinstance(getattr(t, "_value", None), jax.core.Tracer)
                 for t in arg_ts + params)
    meta: dict = {}
    if traced:
        # jit path: values are tracers, the tape is off — lower straight
        # to jax.checkpoint over a pure function of (args, params)
        # (handing out the FLAGS_check_nan_inf rows made inside, in a
        # step built with the flag set; jax.checkpoint itself else)
        from ..ops.pallas import counters

        with counters.recomputed():
            vals = nan_inf.checkpoint(
                lambda av, pv: _call_with(av, pv, meta),
                policy=_kept_policy())(
                    [t.value for t in arg_ts], [p.value for p in params])
        outs = [Tensor(v, stop_gradient=False) for v in vals]
        return outs[0] if meta["single"] else tuple(outs)

    gen = random_mod.default_generator()
    rng_before = (gen._key, gen._seed)
    out_vals = _call_with([t.value for t in arg_ts],
                          [p.value for p in params], meta)
    in_tensors = [t for t in arg_ts + params if not t.stop_gradient]
    single = meta["single"]
    if not (tape_mod.grad_enabled() and in_tensors):
        outs = [Tensor(v) for v in out_vals]
        return outs[0] if single else tuple(outs)

    in_ids = {id(t) for t in in_tensors}

    def pure(*vals):
        # re-run the segment with the cotangent-path inputs substituted
        # and the RNG rewound: identical draws, recomputed activations
        sub = dict(zip((id(t) for t in in_tensors), vals))
        av = [sub.get(id(t), t.value) for t in arg_ts]
        pv = [sub.get(id(p), p.value) for p in params]
        saved_rng = (gen._key, gen._seed)
        gen._key, gen._seed = rng_before
        try:
            return tuple(_call_with(av, pv, {}))
        finally:
            gen._key, gen._seed = saved_rng

    def vjp(cts):
        cts = cts if isinstance(cts, tuple) else (cts,)
        primals = tuple(t.value for t in in_tensors)
        _, vjp_fn = jax.vjp(pure, *primals)
        return vjp_fn(tuple(cts))

    node = tape_mod.TapeNode(vjp, in_tensors, "recompute")
    outs = []
    for v in out_vals:
        t = Tensor(v, stop_gradient=False)
        t._node = node
        node.add_output(t)
        outs.append(t)
    del in_ids
    return outs[0] if single else tuple(outs)


class RecomputeOptimizer:
    """Reference optimizer.py:4513, made real on both execution paths.

    Static: ``minimize`` on a static ``Variable`` loss appends the
    backward op WITH the registered checkpoint names — the
    recompute_segmentation pass (static/passes.py) splits the forward
    region at them and the executor lowers each segment through
    ``jax.checkpoint`` (BuildStrategy.recompute is the knob-only
    spelling of the same thing; fleet.distributed_optimizer routes a
    recompute strategy onto those knobs).

    Dygraph: ``_set_checkpoints`` accepts sub-Layers / callables; each
    has its forward wrapped in :func:`recompute` IN PLACE, so the next
    forward pass records one tape node per segment and ``minimize``'s
    backward rematerializes activations instead of reading stashed
    residuals (identical dropout masks — RNG state is rewound for the
    replay)."""

    def __init__(self, optimizer):
        self.inner = optimizer
        self._checkpoints = None
        self._wrapped = []

    def _set_checkpoints(self, checkpoints):
        self._unwrap_layers()
        self._checkpoints = list(checkpoints or [])
        for c in self._checkpoints:
            if callable(c) and not isinstance(c, str):
                self._wrap_layer(c)

    def _wrap_layer(self, layer):
        import functools

        orig = layer.forward

        @functools.wraps(orig)
        def wrapped(*a, **k):
            return recompute(orig, *a, **k)

        layer.forward = wrapped
        self._wrapped.append((layer, orig))

    def _unwrap_layers(self):
        for layer, orig in self._wrapped:
            layer.forward = orig
        self._wrapped = []

    def _static_checkpoint_names(self):
        names = []
        for c in self._checkpoints or []:
            if isinstance(c, str):
                names.append(c)
            elif hasattr(c, "name") and not callable(c):
                names.append(c.name)
        return names

    def step(self):
        self.inner.step()

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from ..static.ir import Variable as StaticVariable

        if isinstance(loss, StaticVariable) and \
                hasattr(self.inner, "apply_gradients"):
            from ..static.backward import append_backward

            from ..static.optimizer import resolve_grad_clip

            params_grads = append_backward(
                loss, parameter_list, no_grad_set,
                checkpoints=self._static_checkpoint_names() or None)
            clip = resolve_grad_clip(self.inner)
            if clip is not None:
                params_grads = clip(params_grads)
            self.inner.apply_gradients(params_grads)
            return [], params_grads
        return self.inner.minimize(loss)

    def clear_grad(self):
        self.inner.clear_grad()

    def __getattr__(self, item):
        return getattr(self.inner, item)


class LookAhead(Optimizer):
    """lookahead: slow/fast weights (reference optimizer.py:4822)."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5, name=None):
        self.inner = inner_optimizer
        self.alpha = alpha
        self.k = k
        self._slow = {}
        self._n = 0

    def _params(self):
        return self.inner._params()

    def step(self):
        self.inner.step()
        self._n += 1
        if self._n % self.k == 0:
            for p in self.inner._params():
                if id(p) not in self._slow:
                    self._slow[id(p)] = p.value
                slow = self._slow[id(p)] + self.alpha * (p.value - self._slow[id(p)])
                self._slow[id(p)] = slow
                p._value = slow

    def minimize(self, loss, **kw):
        if loss._node is not None:
            loss.backward()
        self.step()
        return None, None

    def clear_grad(self):
        self.inner.clear_grad()


class LocalSGDOptimizer:
    """LocalSGD (reference transpiler/collective.py:270 LocalSGD, fleet
    meta_optimizers/localsgd_optimizer.py): each data-parallel worker takes
    k_steps local optimizer steps, then parameters are averaged across the
    replica group. On TPU the averaging is a pmean collective when running
    under a multi-device group (no-op at world size 1)."""

    def __init__(self, inner_optimizer, k_steps=1, begin_step=1):
        self._inner = inner_optimizer
        self._k = max(1, int(k_steps))
        self._begin = begin_step
        self._step_cnt = 0

    def step(self):
        self._inner.step()
        self._step_cnt += 1
        if self._step_cnt >= self._begin and self._step_cnt % self._k == 0:
            self._average_params()

    def _average_params(self):
        if jax.process_count() > 1:
            # multi-process eager DP: average each replica's params across
            # processes (the reference's c_allreduce over trainer ranks)
            from jax.experimental import multihost_utils

            for p in self._inner._params():
                stacked = multihost_utils.process_allgather(p._value)
                p._value = jnp.mean(stacked, axis=0)
            return
        # inside shard_map/pmap this lowers to pmean; world size 1: no-op
        from ..distributed.collective import ReduceOp, all_reduce

        for p in self._inner._params():
            all_reduce(p, op=ReduceOp.AVG)

    def minimize(self, loss, **kw):
        if getattr(loss, "_node", None) is not None:
            loss.backward()
        self.step()
        return None, None

    def clear_grad(self):
        self._inner.clear_grad()

    def __getattr__(self, item):
        return getattr(self._inner, item)


class DGCMomentum(Optimizer):
    """Deep gradient compression momentum (reference operators/dgc_op.cc +
    fluid/optimizer.py:1176 DGCMomentumOptimizer): momentum-corrected
    residual accumulation with top-k sparsification. Before
    rampup_begin_step it is plain momentum; after, only the largest
    (1-sparsity) fraction of accumulated-gradient entries update the
    velocity each step, the rest stay in local residuals (u, v).

    The rule is pure, so it runs inside the compiled TrainStep. Under
    multi-process DP the sparsified tensor is what crosses the wire; in
    the single-program SPMD world the same semantics apply to the already
    psum-ed gradient."""

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 rampup_begin_step=0, rampup_step=1,
                 sparsity=(0.999,), parameters=None, use_nesterov=False,
                 weight_decay=None, grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._rampup_begin = int(rampup_begin_step)
        # warmup schedule: each entry of `sparsity` holds for
        # rampup_step/len(sparsity) steps after rampup_begin_step
        self._sparsities = (tuple(float(s) for s in sparsity)
                            if isinstance(sparsity, (list, tuple))
                            else (float(sparsity),))
        self._rampup_step = max(1, int(rampup_step))
        self._nesterov = use_nesterov

    def init_slot(self, p):
        return {"velocity": jnp.zeros_like(p),
                "u": jnp.zeros_like(p),     # momentum-corrected accumulator
                "v": jnp.zeros_like(p)}     # residual (unsent) gradient

    def _dgc_update(self, g, p, slots, lr, sparsity):
        m = self._momentum
        u = m * slots["u"] + g
        v = slots["v"] + u
        flat = v.ravel()
        n = flat.shape[0]
        k = max(1, int(n * (1.0 - sparsity)))
        topv, _ = jax.lax.top_k(jnp.abs(flat), k)
        thr = topv[-1]
        mask = jnp.abs(v) >= thr
        sent = jnp.where(mask, v, 0.0)          # sparse allreduce payload
        vel = m * slots["velocity"] + sent
        if self._nesterov:
            p2 = p - lr * (sent + m * vel)
        else:
            p2 = p - lr * vel
        return p2, {"velocity": vel,
                    "u": jnp.where(mask, 0.0, u),
                    "v": jnp.where(mask, 0.0, v)}

    def _momentum_update(self, g, p, slots, lr):
        vel = self._momentum * slots["velocity"] + g
        if self._nesterov:
            p2 = p - lr * (g + self._momentum * vel)
        else:
            p2 = p - lr * vel
        return p2, {"velocity": vel, "u": slots["u"], "v": slots["v"]}

    def rule(self, g, p, slots, lr, t):
        sparsities = self._sparsities
        if len(sparsities) == 1:
            def dgc_branch():
                return self._dgc_update(g, p, slots, lr, sparsities[0])
        else:
            # top_k needs a static k, so each warmup sparsity is its own
            # branch; the traced step picks one with lax.switch
            steps_per = max(1, self._rampup_step // len(sparsities))
            branches = [
                (lambda s=s: self._dgc_update(g, p, slots, lr, s))
                for s in sparsities
            ]

            def dgc_branch():
                phase = jnp.clip((t - self._rampup_begin - 1) // steps_per,
                                 0, len(sparsities) - 1).astype(jnp.int32)
                return jax.lax.switch(phase, branches)

        if self._rampup_begin <= 0:
            return dgc_branch()
        return jax.lax.cond(
            t > self._rampup_begin,
            dgc_branch,
            lambda: self._momentum_update(g, p, slots, lr))


class EMA:
    """Exponential moving average of params (reference optimizer.py:3411)."""

    def __init__(self, decay=0.999, thres_steps=None):
        self._decay = decay
        self._ema = {}
        self._backup = {}
        self._step = 0
        self._params = []

    def register(self, parameters):
        self._params = list(parameters)
        for p in self._params:
            self._ema[id(p)] = p.value

    def update(self):
        self._step += 1
        d = min(self._decay, (1 + self._step) / (10 + self._step))
        for p in self._params:
            if id(p) not in self._ema:
                self._ema[id(p)] = p.value
            else:
                self._ema[id(p)] = d * self._ema[id(p)] + (1 - d) * p.value

    def apply(self, need_restore=True):
        for p in self._params:
            self._backup[id(p)] = p.value
            p._value = self._ema[id(p)]

    def restore(self):
        for p in self._params:
            if id(p) in self._backup:
                p._value = self._backup.pop(id(p))


class ModelAverage(EMA):
    """Running average of params (reference optimizer.py:3102) — on TPU the
    same mechanism as EMA with uniform averaging."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000000):
        super().__init__(decay=0.0)
        self._sum = {}
        self._count = 0

    def update(self):
        self._count += 1
        for p in self._params:
            self._sum[id(p)] = self._sum.get(id(p), 0) + p.value
            self._ema[id(p)] = self._sum[id(p)] / self._count


class PipelineOptimizer:
    """Pipeline-parallel training facade (reference fluid/optimizer.py
    :3661 PipelineOptimizer — splits a program into SectionWorker
    stages). The TPU pipeline is a compiled schedule, not a program
    rewrite: this class pairs an inner optimizer with the
    parallel.pipeline machinery and runs GPipe or 1F1B over a staged
    model.

    Usage::

        opt = PipelineOptimizer(paddle.optimizer.Adam(...),
                                num_microbatches=8)
        # GPipe forward over stacked stages:
        y = opt.pipeline_apply(stage_fn, stage_params, x,
                               mesh=mesh, axis="pp")
        # 1F1B training step (embedding/head inside the pipeline):
        loss, grads = opt.pipeline_value_and_grad(
            stage_fn, first_fn, last_fn, params, batch,
            mesh=mesh, axis="pp")

    or hand `strategy.pipeline = True` to fleet.distributed_optimizer,
    which routes through the same schedule (distributed/fleet.py).
    """

    def __init__(self, optimizer, num_microbatches=1, start_cpu_core_id=0):
        self.inner_opt = optimizer
        self.num_microbatches = num_microbatches

    def pipeline_apply(self, stage_fn, stage_params, x, *, mesh, axis,
                       **kw):
        from ..parallel import pipeline as pp

        return pp.pipeline_apply(stage_fn, stage_params, x, mesh=mesh,
                                 axis=axis,
                                 num_microbatches=self.num_microbatches,
                                 **kw)

    def pipeline_value_and_grad(self, stage_fn, first_fn, last_fn, *args,
                                **kw):
        from ..parallel import pipeline as pp

        kw.setdefault("num_microbatches", self.num_microbatches)
        return pp.pipeline_1f1b_value_and_grad(stage_fn, first_fn,
                                               last_fn, *args, **kw)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        return self.inner_opt.minimize(loss, startup_program,
                                       parameter_list, no_grad_set)

    def __getattr__(self, item):
        return getattr(self.inner_opt, item)
