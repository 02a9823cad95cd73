"""jit: compiled execution of eager-defined models.

TPU-native replacement for the reference @to_static / dygraph_to_static AST
rewriter (/root/reference/python/paddle/fluid/dygraph/dygraph_to_static/)
and the static-graph Executor fast path: instead of rewriting Python into a
ProgramDesc, the layer's parameters/buffers are swapped for tracers and the
unchanged Python forward is traced by jax.jit into one XLA program.
TrainStep fuses forward+backward+optimizer into a single compiled step —
the moral equivalent of ParallelExecutor's build-once-run-many graph.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .framework import nan_inf
from .framework import random as random_mod
from .framework import tape as tape_mod
from .framework.random import rng_scope
from .framework.tensor import Tensor
from .nn.layer import Layer

_tree = jax.tree_util


def _wrap_in(x):
    return Tensor(x) if isinstance(x, jax.Array) else x


def _unwrap_out(x):
    return x.value if isinstance(x, Tensor) else x


class _FunctionalModel:
    """Pure-function view of a Layer: (params, buffers, *args) -> out."""

    def __init__(self, layer: Layer):
        self.layer = layer

    def __call__(self, params, buffers, args, kwargs, rng_key=None):
        layer = self.layer
        saved_p = {n: p._value for n, p in layer.named_parameters()}
        saved_b = {n: b._value for n, b in layer.named_buffers()}
        layer.load_param_pytree(params)
        layer.load_buffer_pytree(buffers)
        try:
            with tape_mod.no_grad():
                if rng_key is not None:
                    with rng_scope(rng_key):
                        out = layer(*[_wrap_in(a) for a in args],
                                    **{k: _wrap_in(v) for k, v in kwargs.items()})
                else:
                    out = layer(*[_wrap_in(a) for a in args],
                                **{k: _wrap_in(v) for k, v in kwargs.items()})
            new_buffers = {n: b._value for n, b in layer.named_buffers()}
            out_arrays = _tree.tree_map(
                _unwrap_out, out, is_leaf=lambda x: isinstance(x, Tensor))
        finally:
            for n, p in layer.named_parameters():
                p._value = saved_p[n]
            for n, b in layer.named_buffers():
                b._value = saved_b[n]
        return out_arrays, new_buffers


_ast_cache = {}


def _maybe_ast(fn):
    """AST-rewrite tensor-dependent Python control flow (dy2static) when
    enabled; trace-only fallback otherwise. Mirrors the reference's
    ProgramTranslator default-on behavior (program_translator.py).
    Memoized per source function so repeated to_static(f) calls share one
    transformed function (and so one _fn_compiled jit cache entry)."""
    from . import dy2static

    if not dy2static.ast_enabled():
        return fn
    if fn in _ast_cache:
        return _ast_cache[fn]
    try:
        out = dy2static.ast_transform(fn)
    except (OSError, TypeError, ValueError, SyntaxError) as e:
        try:
            fn.__dy2static_fallback_reason__ = str(e)
        except (AttributeError, TypeError):
            pass
        out = fn
    _ast_cache[fn] = out
    return out


def to_static(layer_or_fn=None, input_spec=None, **jit_kwargs):
    """Compile a Layer's forward (or a function over Tensors) with jax.jit.
    Python `if`/`while`/`for range()` over traced Tensors are first
    AST-rewritten to lax control flow (see paddle_tpu.dy2static)."""
    if layer_or_fn is None:
        return functools.partial(to_static, input_spec=input_spec, **jit_kwargs)
    if isinstance(layer_or_fn, Layer):
        layer = layer_or_fn
        fwd = type(layer).forward
        converted = _maybe_ast(fwd)
        if converted is not fwd:
            layer.forward = converted.__get__(layer)
        return CompiledLayer(layer, **jit_kwargs)
    fn = _maybe_ast(layer_or_fn)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return _jit_fn(fn)(*args, **kwargs)

    return wrapper


@functools.lru_cache(maxsize=None)
def _fn_compiled(fn):
    def pure(arg_arrays, kw_arrays):
        args = _tree.tree_map(_wrap_in, arg_arrays)
        kwargs = _tree.tree_map(_wrap_in, kw_arrays)
        with tape_mod.no_grad():
            out = fn(*args, **kwargs)
        from .dy2static import UndefinedVarError, _Undefined

        for leaf in _tree.tree_leaves(
                out, is_leaf=lambda x: isinstance(x, (Tensor, _Undefined))):
            if isinstance(leaf, _Undefined):
                raise UndefinedVarError(
                    "the returned value is undefined on some branch path "
                    "— either a tensor-dependent `if` returns on one path "
                    "and falls through on the other, or a returned "
                    "variable was assigned on only one branch")
        return _tree.tree_map(_unwrap_out, out,
                              is_leaf=lambda x: isinstance(x, Tensor))

    return jax.jit(pure)


def _jit_fn(fn):
    compiled = _fn_compiled(fn)

    def run(*args, **kwargs):
        arg_arrays = _tree.tree_map(
            _unwrap_out, args, is_leaf=lambda x: isinstance(x, Tensor))
        kw_arrays = _tree.tree_map(
            _unwrap_out, kwargs, is_leaf=lambda x: isinstance(x, Tensor))
        out = compiled(arg_arrays, kw_arrays)
        return _tree.tree_map(_wrap_in, out)

    return run


class CompiledLayer:
    """jit-compiled inference wrapper around a Layer (AnalysisPredictor-ish)."""

    def __init__(self, layer: Layer, donate_buffers: bool = False):
        self.layer = layer
        self.fmodel = _FunctionalModel(layer)
        self._compiled = jax.jit(
            lambda params, buffers, args, kwargs:
            self.fmodel(params, buffers, args, kwargs),
            static_argnames=())

    def __call__(self, *args, **kwargs):
        params = self.layer.param_pytree()
        buffers = self.layer.buffer_pytree()
        arg_arrays = _tree.tree_map(
            _unwrap_out, args, is_leaf=lambda x: isinstance(x, Tensor))
        kw_arrays = _tree.tree_map(
            _unwrap_out, kwargs, is_leaf=lambda x: isinstance(x, Tensor))
        out, new_buffers = self._compiled(params, buffers, arg_arrays, kw_arrays)
        self.layer.load_buffer_pytree(new_buffers)
        return _tree.tree_map(_wrap_in, out)

    def forward(self, *args, **kwargs):
        return self(*args, **kwargs)


class TrainStep:
    """One fused XLA program: forward + backward + optimizer update.

    Replaces the reference's per-op executor hot loop (executor.cc:476) with
    a single compiled step. loss_fn(model, *batch) must return a scalar
    Tensor (or a tuple whose first element is the loss).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 seed: int = 0, donate: bool = True, mesh=None,
                 param_rules=None, data_axes=("dp", "data"),
                 data_spec=None, sequence_parallel=None, zero_stage=0,
                 zero_axis="dp"):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.fmodel = _FunctionalModel(model)
        self._opt_state = None
        self._seed = seed
        self._compiled = None
        self._donate = bool(donate)
        self._seen_sigs = set()   # batch signatures already compiled
        self._donated_nbytes = None  # cached donated-set size
        self._lr_cache = None     # (float value, device scalar)
        self._mesh = mesh
        self._param_rules = param_rules
        self._data_axes = data_axes
        self._data_spec = data_spec  # explicit PartitionSpec for batch leaves
        # "sp" / (axis, impl): bake ring/Ulysses context-parallel attention
        # into the traced step (deterministic, unlike the dynamic
        # sequence_parallel() scope — see parallel/ring.py module note)
        if isinstance(sequence_parallel, str):
            sequence_parallel = (sequence_parallel, "ring")
        self._sequence_parallel = sequence_parallel
        # ZeRO: 0 = off, 1/2 = shard optimizer slots over zero_axis,
        # 3 = also shard the params themselves
        self._zero_stage = zero_stage
        self._zero_axis = zero_axis
        self._placed = False
        self._pinned = None  # (param, slot) shardings of the placement
        # FLAGS_check_nan_inf, as it stood when the step was built: the
        # record's static keys and the last step's table, on the device
        self._numerics_keys = None
        self._numerics = None
        # route this step's XLA compiles through the disk-persistent cache
        from .static.compile_cache import ensure_enabled
        ensure_enabled()

    def _batch_row_axes(self) -> tuple:
        """Mesh axes the batch's leading (row) dims shard over, from
        data_spec (axis names or tuples per dim) or data_axes."""
        if self._mesh is None:
            return ()
        axes = []
        if self._data_spec is not None:
            for entry in self._data_spec:
                if entry is None:
                    continue
                axes += (list(entry) if isinstance(entry, (tuple, list))
                         else [entry])
        elif self._data_axes:
            axes = list(self._data_axes)
        return tuple(a for a in axes if a in self._mesh.axis_names)

    def _place_spmd(self, params, buffers, batch_arrays):
        """First-call SPMD placement: params per TP rules (replicated over
        dp), batch sharded on the data axes. XLA's partitioner then inserts
        the gradient psum/collectives (replaces the reference's
        multi_devices_graph_pass + allreduce op handles)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from .parallel.sharding import shard_params, zero_shardings

        mesh = self._mesh
        if not self._placed:
            if self._zero_stage:
                pshard, slot_sharding = zero_shardings(
                    params, mesh, axis=self._zero_axis,
                    stage=self._zero_stage, rules=self._param_rules)
            else:
                pshard = shard_params(params, mesh, self._param_rules)

                def slot_sharding(nn, arr):
                    return pshard[nn]
            for n in params:
                params[n] = jax.device_put(params[n], pshard[n])
            rep = NamedSharding(mesh, PartitionSpec())
            for n in buffers:
                buffers[n] = jax.device_put(buffers[n], rep)
            if self._opt_state is not None:
                slots = self._opt_state["slots"]
                for n in slots:
                    slots[n] = _tree.tree_map(
                        lambda a, nn=n: jax.device_put(
                            a, slot_sharding(nn, a)), slots[n])
                # the step counter too: left on one device it comes back
                # from step 1 replicated over the mesh, and step 2 is
                # traced and compiled a second time for that
                self._opt_state["step"] = jax.device_put(
                    self._opt_state["step"], rep)
            self._pinned = (
                {n: a.sharding for n, a in params.items()},
                _tree.tree_map(lambda a: a.sharding,
                               self._opt_state["slots"]))
            self._placed = True
        axes = tuple(a for a in self._data_axes if a in mesh.axis_names)
        if axes or self._data_spec is not None:
            def shard_batch(a):
                nd = getattr(a, "ndim", 0)
                if nd < 1:
                    return a
                if self._data_spec is not None:
                    cleaned = tuple(
                        ax if ax is None or ax in mesh.axis_names else None
                        for ax in self._data_spec[:nd])
                    spec = PartitionSpec(*cleaned)
                else:
                    spec = PartitionSpec(axes if len(axes) > 1 else axes[0])
                return jax.device_put(a, NamedSharding(mesh, spec))

            batch_arrays = tuple(
                _tree.tree_map(shard_batch, b) for b in batch_arrays)
        return params, buffers, batch_arrays

    def _build(self):
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        model = self.model
        from contextlib import nullcontext

        from .ops.pallas import counters

        # read here, as the amp level is: a step is built with the record
        # or without it, and the flag's later value does not retrace it
        check_nan_inf = nan_inf.enabled()

        # the name is the XLA module's (``jit_train_step``), the key of
        # compile_cache.seconds_by_function() and of counters.step_work()
        def train_step(params, buffers, opt_state, lr, batch):
            step_idx = opt_state["step"]

            def loss_of(params, sink=None):
                if sink is not None:    # the gradient probes' way out
                    nan_inf.record.sink = sink
                key = jax.random.fold_in(random_mod.make_key(self._seed), step_idx)
                saved_p ={n: p._value for n, p in model.named_parameters()}
                saved_b = {n: b._value for n, b in model.named_buffers()}
                model.load_param_pytree(params)
                model.load_buffer_pytree(buffers)
                from .parallel.ring import sequence_parallel as _sp_scope

                sp_ctx = (_sp_scope(*self._sequence_parallel,
                                    mesh=self._mesh)
                          if self._sequence_parallel else nullcontext())
                try:
                    with tape_mod.no_grad(), rng_scope(key), sp_ctx, \
                            jax.named_scope("loss"):
                        out = loss_fn(model, *[_wrap_in(b) for b in batch])
                    loss = out[0] if isinstance(out, (tuple, list)) else out
                    aux = out[1:] if isinstance(out, (tuple, list)) else ()
                    new_buffers = {n: b._value for n, b in model.named_buffers()}
                    loss_arr = _unwrap_out(loss)
                    aux_arr = _tree.tree_map(
                        _unwrap_out, tuple(aux),
                        is_leaf=lambda x: isinstance(x, Tensor))
                finally:
                    for n, p in model.named_parameters():
                        p._value = saved_p[n]
                    for n, b in model.named_buffers():
                        b._value = saved_b[n]
                if sink is not None:    # the forward rows, as an output
                    return loss_arr, (new_buffers, aux_arr,
                                      nan_inf.record.frames[0].stacked())
                return loss_arr, (new_buffers, aux_arr)

            from .parallel.mesh import trace_mesh

            # mark the mesh governing this trace (+ the axes batch rows
            # shard over), for the loss AND the update: Pallas kernels
            # outside a shard_map consult it to shard_map themselves
            # (fused_xent) or to self-gate (flash, fused optimizer)
            # this body runs when jax traces it: once for each compiled
            # step, so what the kernels' dispatches declare inside is the
            # work of one execution (counters.step_work("train_step"))
            with trace_mesh(self._mesh, self._batch_row_axes()), \
                    counters.capture("train_step"):
                with counters.differentiated(), \
                        (nan_inf.recording(model) if check_nan_inf
                         else nullcontext()) as record:
                    if record is None:
                        (loss, (new_buffers, aux)), grads = \
                            jax.value_and_grad(loss_of, has_aux=True)(params)
                    else:
                        (loss, (new_buffers, aux, rows)), (grads, slots) = \
                            jax.value_and_grad(
                                loss_of, argnums=(0, 1), has_aux=True)(
                                    params, jnp.zeros((nan_inf.GRAD_SLOTS, 3),
                                                      jnp.float32))
                        self._numerics_keys, numerics = record.finish(
                            loss, rows, slots, grads)
                with jax.named_scope("optimizer"):
                    new_params, new_opt_state = \
                        optimizer.apply_gradients_fn(
                            grads, params, opt_state, lr)
            if self._pinned is not None:
                # hand params and slots back in the placement they came
                # in with. Left free, XLA returns whatever its sharding
                # propagation picked and the next call compiles again
                # for that.
                p_sh, s_sh = self._pinned
                new_params = {
                    n: jax.lax.with_sharding_constraint(v, p_sh[n])
                    for n, v in new_params.items()}
                new_opt_state = dict(new_opt_state, slots=_tree.tree_map(
                    jax.lax.with_sharding_constraint,
                    new_opt_state["slots"], s_sh))
            if check_nan_inf:
                return (loss, aux, new_params, new_buffers, new_opt_state,
                        numerics)
            return loss, aux, new_params, new_buffers, new_opt_state

        # params + optimizer state are donated: XLA updates the (large)
        # parameter/moment buffers in place instead of allocating a fresh
        # set per step. donate=False keeps every input buffer readable.
        jit_kwargs = {"donate_argnums": (0, 2)} if self._donate else {}
        self._compiled = jax.jit(train_step, **jit_kwargs)

    def _inputs(self, batch):
        """(params, buffers, lr, batch arrays) of a call on ``batch``;
        builds the step and the optimizer state the first time."""
        model = self.model
        params = {n: p.value for n, p in model.named_parameters()
                  if p.trainable}
        buffers = model.buffer_pytree()
        if self._opt_state is None:
            self._opt_state = self.optimizer.init_state(
                params, {n: p for n, p in model.named_parameters()
                         if p.trainable})
        if self._compiled is None:
            self._build()
        from . import profiler

        # device lr scalar is cached on its float value: an unchanged lr
        # costs zero per-step h2d transfers (schedulers invalidate it)
        lr_val = float(self.optimizer.get_lr())
        if self._lr_cache is None or self._lr_cache[0] != lr_val:
            self._lr_cache = (lr_val, jnp.asarray(lr_val, jnp.float32))
            profiler.bump_counter("h2d_bytes", 4)
        batch_arrays = tuple(
            _tree.tree_map(_unwrap_out, b,
                           is_leaf=lambda x: isinstance(x, Tensor))
            for b in batch)
        return params, buffers, self._lr_cache[1], batch_arrays

    def lower(self, *batch):
        """The ``jax.stages.Lowered`` of this step for ``batch``, nothing
        run or donated. Its ``as_text(debug_info=True)`` holds the named
        scopes (``loss``, ``optimizer``, the layers' names); its
        ``compile().as_text()`` the device's instruction names, each with
        the ``op_name`` it came from (``tools/profile_step.py`` joins a
        device trace on them)."""
        params, buffers, lr, batch_arrays = self._inputs(batch)
        if self._mesh is not None:
            params, buffers, batch_arrays = self._place_spmd(
                params, buffers, batch_arrays)
        return self._compiled.lower(params, buffers, self._opt_state, lr,
                                    batch_arrays)

    def __call__(self, *batch):
        from . import profiler

        model = self.model
        params, buffers, lr, batch_arrays = self._inputs(batch)
        sig = tuple(
            (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "")))
            for a in _tree.tree_leaves(batch_arrays))
        new_sig = sig not in self._seen_sigs
        if new_sig:
            self._seen_sigs.add(sig)
        profiler.bump_counter(
            "compile_cache_misses" if new_sig else "compile_cache_hits")
        profiler.bump_counter("executor_steps")
        if self._mesh is not None:
            params, buffers, batch_arrays = self._place_spmd(
                params, buffers, batch_arrays)
        if self._donate:
            if new_sig or self._donated_nbytes is None:
                # O(param leaves) walk only on a fresh signature — the
                # donated set is invariant across steady-state steps
                self._donated_nbytes = sum(
                    int(getattr(a, "nbytes", 0) or 0)
                    for tree in (params, self._opt_state)
                    for a in _tree.tree_leaves(tree))
            profiler.bump_counter("donated_bytes", self._donated_nbytes)
        loss, aux, new_params, new_buffers, new_opt_state, *numerics = \
            self._compiled(params, buffers, self._opt_state, lr, batch_arrays)
        if numerics:
            self._numerics, = numerics
        for n, p in model.named_parameters():
            if n in new_params:
                p._value = new_params[n]
            # mirror device-side slots into the optimizer's eager store so
            # optimizer.state_dict() (Model.save) sees trained moments
            if n in new_opt_state["slots"]:
                self.optimizer._slots[id(p)] = new_opt_state["slots"][n]
        model.load_buffer_pytree(new_buffers)
        self._opt_state = new_opt_state
        # host-side counter: no device sync per step (async dispatch stays
        # ahead of the chip; the device-side step lives in opt_state)
        self.optimizer._step_count += 1
        if aux:
            return (Tensor(loss),) + tuple(_tree.tree_map(_wrap_in, a) for a in aux)
        return Tensor(loss)

    def numerics(self, check=False):
        """The last step's ``FLAGS_check_nan_inf`` record, fetched: a
        ``framework.nan_inf.Numerics``, ``{key: {"pass", "nonfinite",
        "absmax"}}`` in execution order with ``first_nonfinite`` and
        ``first_pass``; None for a step built with the flag off or not
        run yet. With ``check``, raise ``FloatingPointError`` naming the
        first non-finite key and its pass. The fetch is the caller's:
        the step itself adds none."""
        if self._numerics is None:
            return None
        return nan_inf.report(self._numerics_keys, self._numerics, check)

    @property
    def opt_state(self):
        return self._opt_state


def _check_save_load_config(config):
    """SaveLoadConfig knobs the StableHLO export does not implement
    must fail LOUDLY, not round-trip into the void (r5 review): the
    export always carries all forward outputs under the default
    .pdmodel/.pdiparams names."""
    cfg = config.pop("config", None)
    if config:
        raise TypeError(f"unknown jit.save/load options {sorted(config)}")
    if cfg is None:
        return
    unsupported = []
    if getattr(cfg, "output_spec", None):
        unsupported.append("output_spec (all outputs are exported; "
                           "select at call time)")
    for knob in ("model_filename", "params_filename"):
        if getattr(cfg, knob, None):
            unsupported.append(f"{knob} (fixed .pdmodel/.pdiparams "
                               "naming)")
    if unsupported:
        raise NotImplementedError(
            "SaveLoadConfig knobs not supported by the StableHLO "
            "export: " + "; ".join(unsupported))


def _merge_configs_alias(config, configs):
    """Reference signature parity: jit.save/load take the knob container
    as ``configs=`` (fluid/dygraph/jit.py); ``config=`` is the historical
    keyword this port accepted. Either spelling lands in the same checked
    slot; passing both is ambiguous and refused."""
    if configs is not None:
        if config.get("config") is not None:
            raise TypeError(
                "pass the SaveLoadConfig as either config= or configs=, "
                "not both")
        config["config"] = configs
    return config


def save(layer, path, input_spec=None, configs=None, **config):
    """jit.save parity: persist params + a StableHLO export of forward."""
    from .io.serialization import save_inference_model

    _check_save_load_config(_merge_configs_alias(config, configs))
    save_inference_model(path, layer, input_spec)


def load(path, configs=None, **config):
    from .io.serialization import load_inference_model

    _check_save_load_config(_merge_configs_alias(config, configs))
    return load_inference_model(path)


class SaveLoadConfig:
    """jit.SaveLoadConfig parity (reference fluid/dygraph/jit.py:270):
    knob container for jit.save/load. output_spec selects forward
    outputs to keep; model/params filenames name the export files;
    separate_params/keep_name_table are storage-layout knobs the
    StableHLO export does not need but keeps for API compatibility."""

    def __init__(self):
        self._output_spec = None
        self._model_filename = None
        self._params_filename = None
        self._separate_params = False
        self._keep_name_table = False

    @property
    def output_spec(self):
        return self._output_spec

    @output_spec.setter
    def output_spec(self, spec):
        self._output_spec = spec

    @property
    def model_filename(self):
        return self._model_filename

    @model_filename.setter
    def model_filename(self, filename):
        self._model_filename = filename

    @property
    def params_filename(self):
        return self._params_filename

    @params_filename.setter
    def params_filename(self, filename):
        self._params_filename = filename

    @property
    def separate_params(self):
        return self._separate_params

    @separate_params.setter
    def separate_params(self, value):
        self._separate_params = bool(value)

    @property
    def keep_name_table(self):
        return self._keep_name_table

    @keep_name_table.setter
    def keep_name_table(self, value):
        self._keep_name_table = bool(value)


def __getattr__(name):
    """Lazy paddle.jit surface re-exports (import-cycle-free):
    TracedLayer lives in dygraph.py, ProgramTranslator in dy2static,
    TranslatedLayer in io.serialization."""
    if name == "TracedLayer":
        from .dygraph import TracedLayer

        return TracedLayer
    if name == "ProgramTranslator":
        from .dy2static import ProgramTranslator

        return ProgramTranslator
    if name == "TranslatedLayer":
        from .io.serialization import TranslatedLayer

        return TranslatedLayer
    raise AttributeError(f"module 'paddle_tpu.jit' has no attribute {name!r}")
