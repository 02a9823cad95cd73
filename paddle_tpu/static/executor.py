"""Static-graph Executor + Scope.

TPU-native counterpart of the reference serial Executor
(/root/reference/paddle/fluid/framework/executor.cc:180 Run, hot loop :476)
and the Python front (python/paddle/fluid/executor.py:470/:911).

Design: the reference interprets the block op-by-op with per-op kernel
launches and a Scope of mutable Variables. Here `Executor.run` LOWERS the
whole block to one pure jax function (feed arrays + persistable state in,
fetches + updated state out) and jit-compiles it — XLA fuses what the
reference's 89 IR passes fuse by hand, and a training step (forward +
backward + optimizer ops) becomes a single device program. The Scope is a
host-side dict of jax arrays (functional state), not a mutable var tree.

Startup programs run through the same lowering (initializer ops write
persistables). Before lowering, the block is rewritten by the IR pass
pipeline (passes.py — dead-op elim, constant folding, CSE, identity
elision, elementwise+act fusion, gated by BuildStrategy knobs).

Compiled executables are cached CONTENT-ADDRESSED: the key is a sha256
of (optimized program dict, feed signature, fetch list, state signature,
sharding, donation), held in a process-global table — so
Program.clone()/parse_from_string() copies, and a second Executor in the
same process, all hit the same entry (the reference's
ExecutorPrepareContext cache was per-executor and identity-keyed). A
per-program weak-keyed fast path avoids re-hashing on every step.
Compilation additionally goes through jax's disk-persistent cache
(compile_cache.py), so a relaunched trainer
skips the cold compile; the executor AOT-splits jit into lower()
(trace_ms) and compile() (compile_ms) so both phases are measurable.
"""
from __future__ import annotations

import hashlib
import json
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtype_mod
from ..framework import random as random_mod
from ..framework.place import CPUPlace
from ..observability.flight_recorder import flight_recorder
from ..observability.step_trace import active_step_trace
from .ir import Block, Program, Variable, grad_var_name
from .kernels import KERNELS, ExecContext

_PHASE_HIST = None


def _phase_hist():
    """The executor_step_phase_ms histogram (feed/dispatch/fetch labels)
    — engine-side latency truth for the training hot path, scraped at
    /metrics and percentile-derivable from its buckets."""
    global _PHASE_HIST
    if _PHASE_HIST is None:
        from ..observability.metrics import default_registry

        _PHASE_HIST = default_registry().histogram(
            "executor_step_phase_ms", labels=("phase",))
    return _PHASE_HIST


_DEVICE_KIND: Optional[str] = None


def _device_kind() -> str:
    """The local chip's PJRT device_kind, resolved once — keys the
    device_peaks lookup behind the live mfu/arith_intensity gauges."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        try:
            _DEVICE_KIND = jax.devices()[0].device_kind
        except Exception:
            _DEVICE_KIND = "unknown"
    return _DEVICE_KIND


class Scope:
    """name -> jax.Array store (reference framework/scope.cc, but flat &
    functional: executors read a snapshot and write back results).

    Arrays handed out through the public accessors are marked *exposed*:
    the caller may hold a reference, so a donating executor must not let
    XLA invalidate that buffer in place — it copies exposed entries
    before donation (the copy is what gets donated; the caller's alias
    stays readable). The executor's own reads/writes go through the
    underscore accessors, which don't mark — and a write-back clears the
    mark, because the freshly produced array has no external aliases."""

    def __init__(self):
        self._vars: Dict[str, Any] = {}
        self._exposed: set = set()

    def find_var(self, name):
        v = self._vars.get(name)
        if v is not None:
            self._exposed.add(name)
        return v

    def var(self, name):
        v = self._vars.setdefault(name, None)
        if v is not None:
            self._exposed.add(name)
        return v

    def set(self, name, value):
        # the caller necessarily holds a reference to what it just set
        self._vars[name] = value
        self._exposed.add(name)

    def keys(self):
        return self._vars.keys()

    def items(self):
        self._exposed.update(self._vars.keys())
        return self._vars.items()

    def drop(self, name):
        self._vars.pop(name, None)
        self._exposed.discard(name)

    # -- executor-internal access (no exposure bookkeeping) ---------------
    def _peek(self, name):
        return self._vars.get(name)

    def _write_back(self, name, value):
        self._vars[name] = value
        self._exposed.discard(name)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        saved = _global_scope
        _global_scope = scope
        try:
            yield scope
        finally:
            _global_scope = saved

    return guard()


# ---------------------------------------------------------------------------
# lowering: Block -> pure function(env) -> env
# ---------------------------------------------------------------------------
def run_block(block: Block, env: Dict[str, Any], ctx: ExecContext,
              stop_at: Optional[int] = None,
              post_writes: Optional[Dict[int, Dict[str, Any]]] = None,
              start: int = 0) -> Dict[str, Any]:
    """Interpret ops of a block over an env dict. Called under jit trace —
    this IS the compilation step, not the runtime (no per-op dispatch cost
    after compile).

    post_writes: {op_index: {var_name: value}} — after op i runs, override
    env entries (used by backward.py to treat an intermediate var as a free
    input for gradient computation w.r.t. it).

    start/stop_at bound the op range [start, stop_at): backward.py runs
    checkpoint segments through here, and the gradient-merge step runs
    the post-backward (optimizer) region separately — op indices stay
    ABSOLUTE so ``__rng_slot`` fallbacks and post_writes keys are stable
    whatever the entry point."""
    from .backward import run_backward_op  # local: avoids import cycle

    if not hasattr(ctx, "initial_env"):
        ctx.initial_env = dict(env)
    stop = len(block.ops) if stop_at is None else stop_at
    for i in range(start, stop):
        op = block.ops[i]
        # __rng_slot (stamped by passes.py) pins index-keyed random ops
        # to their pre-rewrite RNG stream: op removal must not shift a
        # surviving dropout/uniform/gaussian draw
        ctx.op_index = op.attrs.get("__rng_slot", i)
        # control-flow kernels (cond/while) recurse into sub-blocks and
        # need the program + a snapshot of the enclosing env
        ctx.program = block.program
        ctx.env = env
        if op.type == "backward":
            run_backward_op(block, i, op, env, ctx)
            continue
        if op.type in ("feed", "fetch"):
            continue  # handled natively by the executor
        fn = KERNELS.get(op.type)
        if fn is None:
            raise NotImplementedError(
                f"no static kernel registered for op {op.type!r}")
        ins = {slot: [env[n] for n in names]
               for slot, names in op.inputs.items()
               if all(n in env for n in names)}
        outs = fn(ins, op.attrs, ctx)
        for slot, names in op.outputs.items():
            produced = outs.get(slot)
            if produced is None:
                continue
            for name, arr in zip(names, produced):
                env[name] = arr
        if post_writes and i in post_writes:
            env.update(post_writes[i])
    return env


def _feed_signature(feed: Dict[str, np.ndarray]):
    # weak_type matters: executables are AOT-compiled, and a weak-typed
    # jax array has a different input aval than the same shape/dtype
    # strong-typed one
    return tuple(sorted((k, tuple(v.shape), str(v.dtype),
                         bool(getattr(v, "weak_type", False)))
                        for k, v in feed.items()))


def _state_signature(state) -> tuple:
    # weak_type included for the same reason as in _feed_signature: the
    # executable is AOT-compiled, and a weak-typed scope entry (e.g. a
    # python-scalar-derived lr) has a different input aval
    return tuple((tuple(a.shape) if hasattr(a, "shape") else None,
                  str(getattr(a, "dtype", type(a).__name__)),
                  bool(getattr(a, "weak_type", False)))
                 for a in state)


def _strategy_signature(strategy) -> tuple:
    if strategy is None:
        return ()
    # scalar knobs plus scalar tuples/lists and shallow dicts — bools
    # select passes, strings/numbers carry the amp dtype/level/loss-scale
    # and the gradient_merge_k, tuples the recompute checkpoint names,
    # dicts the mesh_shape/sharding_hints (all shape which executable is
    # built)
    out = []
    for k, v in vars(strategy).items():
        if isinstance(v, (bool, int, float, str)):
            out.append((k, str(v)))
        elif isinstance(v, (tuple, list)) and all(
                isinstance(x, (bool, int, float, str)) for x in v):
            out.append((k, str(tuple(v))))
        elif isinstance(v, dict):
            out.append((k, repr(sorted(
                (str(kk), repr(vv)) for kk, vv in v.items()))))
    return tuple(sorted(out))


class _ExecEntry:
    """One content-cache slot: the AOT executable plus the optimized
    program and pass report that produced it (dump/debug surface).
    ``is_gm`` records whether the step really compiled as a
    scan-over-microbatches (a gradient_merge_k strategy on a
    backward-less program falls back to the plain step — its dispatches
    must not count as merged). ``cost`` caches the analytic
    cost_model.CostReport for the executable (one walk per entry, the
    warm path pays an attribute read; ``False`` = computation failed,
    don't retry)."""

    __slots__ = ("compiled", "optimized_program", "pass_report", "is_gm",
                 "cost", "comm_stats", "plan_gauges")

    def __init__(self, compiled, optimized_program, pass_report,
                 is_gm=False):
        self.compiled = compiled
        self.optimized_program = optimized_program
        self.pass_report = pass_report
        self.is_gm = is_gm
        self.cost = None
        # per-step quantized-collective accounting when the executable
        # compiled with the explicit bucketed all-reduce (see
        # _comm_entry_stats): wire bytes sent/saved per dispatch plus
        # the comm_buckets / allreduce_overlap_frac gauges
        self.comm_stats = None
        # plan-layer gauges (pp_stages, pp_bubble_frac, zero_*) recorded
        # at build time and REPLAYED on every cache hit — a warm
        # executor reports the executable's schedule, not the last
        # built one's
        self.plan_gauges = {}


# process-global content-addressed executable cache: every Executor in
# the process shares it, so identical programs (clones, deserialized
# copies, or a second Executor) never recompile. Bounded LRU — evicted
# entries release their executables.
_EXEC_CACHE: "OrderedDict[str, _ExecEntry]" = OrderedDict()
_EXEC_CACHE_MAX = 128


def _exec_cache_get(key: str) -> Optional[_ExecEntry]:
    entry = _EXEC_CACHE.get(key)
    if entry is not None:
        _EXEC_CACHE.move_to_end(key)
    return entry


def _exec_cache_put(key: str, entry: _ExecEntry) -> None:
    _EXEC_CACHE[key] = entry
    _EXEC_CACHE.move_to_end(key)
    while len(_EXEC_CACHE) > _EXEC_CACHE_MAX:
        _EXEC_CACHE.popitem(last=False)


def _escape_env_signature() -> tuple:
    """A kernel escape hatch reads the environment at TRACE time (the
    explicit MoE exchange), so two content-identical programs traced
    under different toggles are different executables — the toggle
    must join both cache keys or a cached leg silently defangs the
    env pin."""
    import os

    return (("PADDLE_MOE_A2A", os.environ.get("PADDLE_MOE_A2A", "")),)


def _content_key(opt_program, feed_sig, fetch_names, persist_names,
                 state_sig, sharding, donate, gm=None, pp=None,
                 comm=None, schedule=None, zero=None,
                 interleave=None) -> str:
    # gm (gradient merge), pp (pipeline stage count), the pipeline
    # schedule and the zero stage change the compiled step's STRUCTURE
    # (scan / pipeline slot order / sharded-optimizer regions over
    # microbatches) without touching the program content, so they must
    # join the hash; remat and sharding change the content itself
    # (__remat_seg / __sharding_spec / __pp_stage stamps) and the
    # sharding map additionally lands here via shard_desc (mesh shape +
    # per-name NamedShardings)
    shard_desc = None
    if sharding:
        shard_desc = sorted((k, str(v)) for k, v in sharding.items())
    env_desc = list(_escape_env_signature())
    blob = json.dumps(
        [opt_program.to_dict(), list(feed_sig), list(fetch_names),
         list(persist_names), list(state_sig), shard_desc, bool(donate),
         list(gm) if gm else None, pp,
         list(comm) if comm else None, schedule, zero, interleave,
         env_desc],
        sort_keys=True, default=str).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()


def _nbytes(arr) -> int:
    """Array payload bytes; 0 for extended dtypes (typed PRNG keys raise
    on .nbytes) and non-arrays."""
    try:
        return int(arr.nbytes)
    except Exception:
        return 0


# per-dispatch quantized-collective accounting — lives with the plan
# layer now (stepplan.comm_entry_stats); re-exported for callers that
# imported it from here
from .stepplan import comm_entry_stats as _comm_entry_stats  # noqa: E402
from .stepplan import zero_entry_stats as _zero_entry_stats  # noqa: E402


class Executor:
    """exe = Executor(place); exe.run(program, feed=..., fetch_list=...).

    The step loop is allocation- and transfer-minimal: persistable state
    lives on device across steps (uploaded + sharded once, never bounced
    through host numpy), and the state/rng arguments are DONATED to XLA
    so parameter/optimizer buffers are updated in place. Arrays a caller
    obtained through the Scope's public API are copied before donation
    (see Scope) so stale references stay readable. ``donate_state=False``
    opts out entirely."""

    def __init__(self, place=None, donate_state: bool = True):
        import weakref
        self.place = place if place is not None else CPUPlace()
        # fast path: program object -> {step key -> content hash}. The
        # executables themselves live in the process-global
        # content-addressed _EXEC_CACHE; this weak map only avoids
        # re-running passes + re-hashing on every step.
        self._cache = weakref.WeakKeyDictionary()
        self._step = 0
        from .compile_cache import ensure_enabled
        ensure_enabled()  # disk compile cache, once
        self._donate = bool(donate_state)
        # last executable this executor dispatched — memory_stats() and
        # the xla_*_bytes gauges read its compiled.memory_analysis()
        self._last_entry: Optional[_ExecEntry] = None
        # per-executor view of the hot-path counters; the module-global
        # aggregate lives in the profiler's metrics registry (the
        # /metrics endpoint reads that one)
        import collections
        self._counters = collections.Counter()
        # trainer scrape surface: PADDLE_METRICS_PORT starts the
        # process-wide /metrics server once (no-op when unset)
        from ..observability.server import maybe_start_metrics_server
        maybe_start_metrics_server()

    def _bump(self, name: str, n: int = 1):
        from .. import profiler

        self._counters[name] += n
        profiler.bump_counter(name, n)

    @property
    def counters(self) -> Dict[str, int]:
        """This executor's hot-path counters (cache hits/misses, h2d
        bytes, donated bytes, steps) — cumulative since construction —
        plus the process-global fault-tolerance counters (retry_*,
        ckpt_*, faults_injected, trainer_relaunches): a retry or a
        checkpoint fallback is a process event, not a per-executor one,
        but operators read both off the same dashboard."""
        from .. import profiler

        out = dict(self._counters)
        snap = profiler.counters_snapshot()
        for name in (profiler.FAULT_COUNTER_NAMES
                     + profiler.COMPILE_COUNTER_NAMES
                     + profiler.ELASTIC_COUNTER_NAMES
                     + profiler.PS_COUNTER_NAMES
                     + profiler.COMM_COUNTER_NAMES):
            if name in snap:
                out[name] = snap[name]
        return out

    @staticmethod
    def _memory_analysis_dict(entry) -> Dict[str, int]:
        """compiled.memory_analysis() flattened to plain ints, {} when
        the backend doesn't expose the analysis. peak_bytes is the
        arguments + outputs + XLA temp working set (the quantity remat
        shrinks); CPU/TPU PJRT report no finer peak."""
        if entry is None:
            return {}
        try:
            ma = entry.compiled.memory_analysis()
            temp = int(getattr(ma, "temp_size_in_bytes", 0))
            arg = int(getattr(ma, "argument_size_in_bytes", 0))
            out = int(getattr(ma, "output_size_in_bytes", 0))
            gen = int(getattr(ma, "generated_code_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
        except Exception:
            return {}
        return {"temp_bytes": temp, "argument_bytes": arg,
                "output_bytes": out, "generated_code_bytes": gen,
                "alias_bytes": alias, "peak_bytes": temp + arg + out}

    def memory_stats(self) -> Dict[str, int]:
        """XLA memory analysis of the LAST executable this executor ran:
        peak_bytes / temp_bytes / argument_bytes / output_bytes /
        generated_code_bytes / alias_bytes, as the compiler of the
        backend it ran on counts them (so no figure of what a step
        holds on another backend). {} before the first run."""
        return self._memory_analysis_dict(self._last_entry)

    def cost_stats(self, top: int = 10) -> Dict[str, Any]:
        """Analytic cost breakdown of the LAST executable this executor
        dispatched (static/cost_model.py over the optimized Program IR,
        with the gm/remat/shard step structure folded in): per-op and
        per-step model_flops / hbm_bytes / comm_bytes, flops/bytes by op
        type, top ops, plus the device peaks and the live derived
        gauges (mfu, arith_intensity) from the last measured step.
        {} before the first run or when the model could not cost the
        program."""
        entry = self._last_entry
        cost = getattr(entry, "cost", None) if entry is not None else None
        if not cost:
            return {}
        from ..observability.device_peaks import machine_balance, peaks_for

        out = cost.to_dict(top=top)
        kind = _device_kind()
        out["device_kind"] = kind
        peaks = peaks_for(kind)
        if peaks is not None:
            out["peak_flops"] = peaks.flops
            out["peak_hbm_bytes_per_s"] = peaks.hbm_bytes_per_s
            mb = machine_balance(kind)
            if mb:
                out["machine_balance"] = round(mb, 3)
        for g in ("step_model_flops", "step_hbm_bytes",
                  "step_comm_bytes", "mfu", "arith_intensity"):
            if g in self._counters:
                out[g] = self._counters[g]
        return out

    def _publish_cost_gauges(self, cost, phases) -> Dict[str, Any]:
        """Land one step's cost-model totals + derived utilization in
        the gauges: step_model_flops / step_hbm_bytes / step_comm_bytes
        from the report, mfu from the MEASURED dispatch+fetch seconds
        against the device peak (fetch is included because jax dispatch
        is async — the host-side conversion is where the device step is
        actually awaited), arith_intensity = flops per HBM byte."""
        from .. import profiler
        from ..observability.device_peaks import peaks_for

        vals: Dict[str, Any] = {
            "step_model_flops": cost.model_flops,
            "step_hbm_bytes": cost.hbm_bytes,
            "step_comm_bytes": cost.comm_bytes,
            "arith_intensity": round(cost.arith_intensity, 3),
        }
        step_s = (phases.get("dispatch", 0.0)
                  + phases.get("fetch", 0.0)) / 1e3
        peaks = peaks_for(_device_kind())
        if peaks is not None and peaks.flops > 0 and step_s > 0 \
                and cost.model_flops:
            # 6 decimals: a tiny probe's true MFU can sit at 1e-5 — a
            # 4-decimal gauge would floor it to an indistinguishable 0
            vals["mfu"] = round(
                cost.model_flops / step_s / peaks.flops, 6)
        else:
            # not computable for THIS step (matmul-free program, or no
            # known peak): overwrite, never leave a previous program's
            # mfu standing next to step_model_flops=0
            vals["mfu"] = 0
        for name, v in vals.items():
            self._counters[name] = v
            profiler.set_counter(name, v)
        return vals

    def _clear_cost_gauges(self) -> None:
        """Zero the cost gauges unconditionally (another executor may
        have set the process-global ones): 5 dict writes per uncosted
        step, negligible next to the dispatch."""
        from .. import profiler

        for name in ("step_model_flops", "step_hbm_bytes",
                     "step_comm_bytes", "mfu", "arith_intensity"):
            self._counters[name] = 0
            profiler.set_counter(name, 0)

    def _update_memory_gauges(self, entry) -> None:
        """Mirror the last executable's memory analysis into the
        counters as GAUGES (assigned, not accumulated): xla_temp_bytes /
        xla_peak_bytes / xla_argument_bytes / xla_output_bytes."""
        from .. import profiler

        stats = self._memory_analysis_dict(entry)
        for key in ("temp_bytes", "peak_bytes", "argument_bytes",
                    "output_bytes"):
            if key in stats:
                self._counters[f"xla_{key}"] = stats[key]
                profiler.set_counter(f"xla_{key}", stats[key])

    def close(self):
        self._cache.clear()

    # -- main entry -------------------------------------------------------
    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True,
            use_program_cache: bool = True):
        """One step. The hot path is phase-instrumented: feed (host prep
        + h2d, includes rare builds), dispatch (compiled XLA step), and
        fetch (write-back + host conversion) land in the
        ``executor_step_phase_ms`` histogram; with a StepTrace active
        (``PADDLE_STEP_TRACE``) each step also emits a JSONL record
        stamped ``paddle_step_<id>`` for XPlane correlation, and every
        step rides the crash flight recorder's bounded ring."""
        trace = active_step_trace()
        tr_scope = trace.step("executor") if trace is not None else None
        obs: Dict[str, Any] = {"t0": time.perf_counter()}
        if tr_scope is not None:
            tr_scope.__enter__()
        try:
            return self._run_impl(program, feed, fetch_list, scope,
                                  return_numpy, use_program_cache, obs)
        finally:
            self._finish_step_obs(obs, tr_scope)

    def _finish_step_obs(self, obs, tr_scope) -> None:
        """Close one step's observability: histogram observes, flight
        ring append, step-trace record (exception-safe — runs in run()'s
        finally with the in-flight exception, if any, via exc_info)."""
        import sys as _sys

        t_end = time.perf_counter()
        t_feed, t_disp = obs.get("t_feed"), obs.get("t_dispatch")
        phases: Dict[str, float] = {}
        cost_vals: Dict[str, Any] = {}
        if t_disp is not None:
            phases["feed"] = (t_feed - obs["t0"]) * 1e3
            phases["dispatch"] = (t_disp - t_feed) * 1e3
            phases["fetch"] = (t_end - t_disp) * 1e3
            h = _phase_hist()
            for name, ms in phases.items():
                h.observe(ms, phase=name)
            cost = obs.get("cost")
            if cost is not None:
                cost_vals = self._publish_cost_gauges(cost, phases)
            else:
                # an uncostable program must not leave the previous
                # program's flops/mfu on the dashboard: the gauges
                # describe the LAST DISPATCHED step, so zero them
                self._clear_cost_gauges()
            flight_recorder().record_step({
                "exe_step": self._step,
                "cache_hit": obs.get("cache_hit", False),
                "h2d_bytes": obs.get("h2d_bytes", 0),
                "phases": {k: round(v, 3) for k, v in phases.items()}})
        if tr_scope is not None:
            tr_scope._phases.update(phases)
            if t_disp is not None:
                tr_scope.set("exe_step", self._step)
                tr_scope.set("cache_hit", obs.get("cache_hit", False))
                tr_scope.set("h2d_bytes", obs.get("h2d_bytes", 0))
                for name, v in cost_vals.items():
                    tr_scope.set(name, v)
            tr_scope.__exit__(*_sys.exc_info())
            if obs.get("cost") is not None:
                # per-executable breakdown record (kind="cost"): totals,
                # per-op top tables, device peaks — the top-K/roofline
                # source tools/perf_report.py reads next to the per-step
                # rows (emitted AFTER the step record so file order
                # stays a single monotone step-id sequence; de-duped per
                # trace so warm steps don't repeat it)
                self._emit_cost_record(tr_scope._trace, obs["cost"])

    def _emit_cost_record(self, trace, cost) -> None:
        from ..observability.device_peaks import peaks_for

        # per-trace dedup: one record per REPORT OBJECT, not per step —
        # keyed by identity with the object held strongly (an id() alone
        # could be reused after a cache-evicted report is GC'd, silently
        # skipping a new executable), LRU-bounded so alternating
        # programs (train+eval) emit once each, not once per step
        seen = getattr(trace, "_cost_seen", None)
        if seen is None:
            seen = trace._cost_seen = OrderedDict()
        if id(cost) in seen:
            seen.move_to_end(id(cost))
            return
        seen[id(cost)] = cost
        while len(seen) > 64:
            seen.popitem(last=False)
        try:
            rec = cost.to_dict(top=20)
            kind = _device_kind()
            rec["device_kind"] = kind
            peaks = peaks_for(kind)
            if peaks is not None:
                rec["peak_flops"] = peaks.flops
                rec["peak_hbm_bytes_per_s"] = peaks.hbm_bytes_per_s
            trace.record("cost", rec)
        except Exception:
            pass  # tracing must never take down the step

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, obs):
        from .ir import default_main_program
        from .compiler import CompiledProgram

        sharding = None
        strategy = None
        if isinstance(program, CompiledProgram):
            sharding = program._data_sharding()
            strategy = program._build_strategy
            program = program._program
        if program is None:
            program = default_main_program()
        if strategy is None:
            # fleet.distributed_optimizer's static path stamps the
            # program with the BuildStrategy its DistributedStrategy
            # maps to (recompute/gradient_merge/amp knobs) — honored for
            # raw-Program runs so fleet users need no CompiledProgram
            strategy = getattr(program, "_fleet_build_strategy", None)
        # let the program's py_readers stage batches directly into the
        # feed layout on their prefetch thread; set unconditionally so a
        # later raw-Program run clears a stale data-parallel stash
        program._feed_sharding = sharding
        scope = scope or global_scope()
        if not feed and not fetch_list:
            # startup-program shape: run initializers eagerly into the scope
            return self.run_startup(program, scope)
        feed = {k: np.asarray(v) if not isinstance(v, jax.Array) else v
                for k, v in (feed or {}).items()}
        # started py_readers feed their data vars (read_op parity —
        # static/py_reader.py; raises EOFException when exhausted)
        for _rdr in getattr(program, "_py_readers", []):
            if _rdr._started:
                for k, v in _rdr._next_feed().items():
                    feed.setdefault(k, v)
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]

        block = program.global_block
        # mixed precision (BuildStrategy.amp / PADDLE_AMP): float32 feeds
        # are cast HOST-side to the low dtype — half the h2d bytes — and
        # the amp config joins the step key so flipping the env (or the
        # strategy) can never hit a stale executable. Stash the feed
        # dtype map on the program (like _feed_sharding) so py_reader
        # prefetch threads stage batches already low.
        from .passes import (amp_feed_dtypes_cached, resolve_amp,
                             resolve_comm, resolve_gradient_merge,
                             resolve_pipeline, resolve_pipeline_schedule,
                             resolve_sharding, resolve_zero)

        amp = resolve_amp(strategy)
        gm = resolve_gradient_merge(strategy)
        shard_cfg = resolve_sharding(strategy)
        pp = resolve_pipeline(strategy)
        comm = resolve_comm(strategy)
        zero = resolve_zero(strategy)
        if gm is None:
            # mirrors apply_passes: pipeline_stages without
            # gradient_merge_k > 1 has no microbatches to schedule
            pp = None
        schedule = interleave = None
        if pp is not None:
            # the schedule only shapes a pipelined step; resolving it
            # to None otherwise keeps non-pp step keys unchanged
            schedule, interleave = resolve_pipeline_schedule(strategy)
        fdt = amp_feed_dtypes_cached(program, amp)
        program._amp_feed_dtypes = fdt

        def _amp_fix_feed(k, v):
            if not isinstance(v, jax.Array):
                if fdt and k in fdt and v.dtype == np.float32:
                    return v.astype(fdt[k])
                return v
            # device-staged feeds must match the dtype this run traces
            # with: the program-level stash is shared, so a prefetch
            # thread serving a DIFFERENT amp config (amp-on train +
            # amp-off eval over one Program) can stage the wrong dtype —
            # a cheap on-device cast beats a silent wrong-graph feed or
            # a recompile ping-pong
            if fdt and k in fdt and v.dtype == jnp.float32:
                return v.astype(jnp.dtype(fdt[k]))
            if not fdt:
                dv = block.vars.get(k)
                if dv is not None and dv.is_data \
                        and dv.dtype == "float32" \
                        and v.dtype in (jnp.bfloat16, jnp.float16):
                    return v.astype(jnp.float32)
            return v

        feed = {k: _amp_fix_feed(k, v) for k, v in feed.items()}
        peek = getattr(scope, "_peek", scope.find_var)
        persist_names = sorted(
            n for n, v in block.vars.items()
            if v.persistable and peek(n) is not None)
        if shard_cfg is not None:
            # GSPMD static sharding (BuildStrategy.mesh_shape +
            # sharding_hints): build the real mesh and the jit-boundary
            # sharding map — it REPLACES any CompiledProgram
            # data-parallel map (mesh_shape is the more general spelling
            # of the same thing) and rides program._feed_sharding so
            # prefetch threads stage batches already partitioned.
            # Memoized on the shapes that decide it (spec fitting checks
            # divisibility against live shapes) — the warm path pays one
            # key comparison, not a NamedSharding rebuild per step.
            shard_key = (
                program._version, shard_cfg, tuple(persist_names),
                tuple(sorted((k, tuple(getattr(v, "shape", ())))
                             for k, v in feed.items())))
            cached = getattr(self, "_shard_map_cache", None)
            if cached is not None and cached[0] == shard_key:
                sharding = cached[1]
            else:
                from ..parallel.mesh import mesh_for_shape
                from .passes import shard_boundary_shardings

                mesh = mesh_for_shape(dict(shard_cfg[0]))
                sharding = shard_boundary_shardings(
                    mesh, block, feed, persist_names, shard_cfg, peek)
                self._shard_map_cache = (shard_key, sharding)
            program._feed_sharding = sharding
        # quantized DP collectives (BuildStrategy.comm_quant /
        # PADDLE_QUANT_ALLREDUCE): resolve eligibility + the gradient
        # bucket plan up front — the error-feedback residuals ride the
        # DONATED state, so they must join persist_names before the
        # state gather, and the comm tuple joins the step/content keys
        # so a codec/bucket flip can never hit a stale executable
        comm_plan = None
        if comm is not None:
            comm_plan = self._comm_eligibility(
                program, block, comm, shard_cfg, gm, feed, sharding,
                pp=pp)
            if comm_plan is not None and comm[2]:
                sharding = dict(sharding) if sharding else {}
                persist_names = list(persist_names)
                persist_names += self._ensure_ef_state(
                    scope, comm_plan, shard_cfg, sharding)
                program._feed_sharding = sharding
        # ZeRO sharded optimizer states (BuildStrategy.zero_stage /
        # PADDLE_ZERO): rides the SAME engaged comm plan — the grad
        # all-reduce decomposes into reduce-scatter + all-gather and the
        # optimizer runs on local (g, c) state rows, which join the
        # donated state exactly like the error-feedback residuals
        zero_plan = None
        if zero is not None:
            zero_plan = self._zero_eligibility(
                program, block, zero, comm, comm_plan, shard_cfg, gm,
                pp, fetch_names)
            if zero_plan is not None:
                sharding = dict(sharding) if sharding else {}
                added, dropped = self._ensure_zero_state(
                    scope, zero_plan, shard_cfg, sharding)
                persist_names = [n for n in persist_names
                                 if n not in dropped] + added
                program._feed_sharding = sharding
        if zero_plan is None and peek("__zero_layout__") is not None:
            # ZeRO turned off (or went ineligible) between steps while
            # the scope still holds sharded rows: flip the per-var
            # state back before the replicated step gathers it
            from .stepplan import zero_flip_back

            restored = zero_flip_back(scope)
            have = set(persist_names)
            persist_names = list(persist_names) + sorted(
                n for n in set(restored) - have
                if n in block.vars and block.vars[n].persistable)
        feed_keys = sorted(feed.keys())
        feed_vals = [feed[k] for k in feed_keys]
        state = self._gather_state(scope, persist_names, feed_vals,
                                   sharding)
        seed = program.random_seed or random_mod.default_generator().initial_seed()
        rng = jax.random.fold_in(random_mod.make_key(seed), self._step)
        # shape/dtype only — never materialize device arrays for the key
        feed_sig = _feed_signature(feed)
        state_sig = _state_signature(state)
        step_key = (program._version, feed_sig, tuple(fetch_names),
                    tuple(persist_names), state_sig, bool(sharding),
                    _strategy_signature(strategy), amp, gm, shard_cfg,
                    pp, comm, comm_plan is not None, schedule,
                    interleave if schedule == "interleaved" else None,
                    zero, zero_plan is not None,
                    _escape_env_signature())
        per_prog = self._cache.setdefault(program, {})
        entry = None
        if use_program_cache:
            ck = per_prog.get(step_key)
            if ck is not None:
                entry = _exec_cache_get(ck)
                if entry is not None:
                    self._bump("compile_cache_hits")
                    obs["cache_hit"] = True
        if entry is None:
            # rewrite the block through the IR pass pipeline, then look
            # up / build the executable by CONTENT — a cloned or
            # deserialized copy of a compiled program lands on the same
            # sha, as does any other Executor in this process
            from .passes import apply_passes

            opt_program, report = apply_passes(
                program, feed_keys, fetch_names, strategy)
            self._record_pass_report(report)
            ck = _content_key(opt_program, feed_sig, fetch_names,
                              persist_names, state_sig, sharding,
                              self._donate, gm, pp, comm,
                              schedule=schedule, zero=zero,
                              interleave=interleave
                              if schedule == "interleaved" else None)
            per_prog[step_key] = ck
            entry = _exec_cache_get(ck) if use_program_cache else None
            if entry is not None:
                self._bump("compile_cache_hits")
                obs["cache_hit"] = True
            else:
                is_gm = gm is not None and any(
                    op.type == "backward"
                    for op in opt_program.global_block.ops)
                compiled_fn = self._build(
                    opt_program.global_block, feed_keys, fetch_names,
                    persist_names, sharding, feed_vals, state, rng, gm,
                    pp, comm=comm, comm_plan=comm_plan,
                    schedule=schedule, zero=zero, zero_plan=zero_plan,
                    interleave=interleave)
                entry = _ExecEntry(compiled_fn, opt_program, report,
                                   is_gm)
                entry.plan_gauges = dict(
                    getattr(self, "_last_plan_gauges", {}) or {})
                if comm_plan is not None and any(
                        op.type == "backward"
                        for op in opt_program.global_block.ops):
                    entry.comm_stats = (
                        _zero_entry_stats(comm_plan)
                        if zero_plan is not None
                        else _comm_entry_stats(comm_plan))
                if use_program_cache:
                    _exec_cache_put(ck, entry)
                self._bump("compile_cache_misses")
        compiled = entry.compiled
        if entry is not getattr(self, "_last_entry", None):
            self._last_entry = entry
            self._update_memory_gauges(entry)
            for name, v in entry.plan_gauges.items():
                self._set_plan_gauge(name, v)
        if entry.cost is None:
            # one analytic walk per executable (VarDesc arithmetic, no
            # tracing); False = model couldn't cost this program, never
            # retried on the hot path
            try:
                from .cost_model import program_cost

                entry.cost = program_cost(
                    entry.optimized_program,
                    feed_shapes={k: tuple(getattr(v, "shape", ()) or ())
                                 for k, v in feed.items()},
                    gm=gm if entry.is_gm else None,
                    shard_cfg=shard_cfg, pp=pp,
                    comm=comm if getattr(entry, "comm_stats", None)
                    else None,
                    schedule=schedule, interleave=interleave,
                    zero=zero if zero_plan is not None else None)
            except Exception:
                entry.cost = False
        if entry.cost:
            obs["cost"] = entry.cost

        self._step += 1
        self._bump("executor_steps")
        if gm and entry.is_gm:
            # one dispatch covers gm[0] microbatches (one optimizer
            # update): the tokens-per-dispatch win gradient merge buys
            self._bump("gm_dispatches")
            self._bump("gm_microbatches", gm[0])
        if getattr(entry, "comm_stats", None):
            # collective wire accounting, per dispatch: cumulative byte
            # counters plus point-in-time bucket/overlap gauges. ZeRO
            # dispatches ride their own counter pair — their wire is an
            # encoded half-ring reduce-scatter + raw-f32 all-gather, a
            # different profile than the quantized all-reduce ring the
            # comm_quant_* counters (and their saved>sent codec
            # invariant) account for
            from .. import profiler

            cs = entry.comm_stats
            if cs.get("zero"):
                self._bump("zero_wire_bytes_sent", cs["bytes_sent"])
                self._bump("zero_wire_bytes_saved", cs["bytes_saved"])
            else:
                self._bump("comm_quant_bytes_sent", cs["bytes_sent"])
                self._bump("comm_quant_bytes_saved", cs["bytes_saved"])
            for name in ("comm_buckets", "allreduce_overlap_frac"):
                self._counters[name] = cs[name]
                profiler.set_counter(name, cs[name])
        feed_h2d = sum(_nbytes(v) for v in feed_vals
                       if not isinstance(v, jax.Array))
        if feed_h2d:
            self._bump("h2d_bytes", feed_h2d)
        if self._donate:
            self._bump("donated_bytes",
                       sum(_nbytes(a) for a in state) + _nbytes(rng))
        obs["h2d_bytes"] = feed_h2d
        obs["t_feed"] = time.perf_counter()
        fetches, new_state = compiled(feed_vals, state, rng)
        obs["t_dispatch"] = time.perf_counter()
        write_back = getattr(scope, "_write_back", scope.set)
        for n, v in zip(persist_names, new_state):
            write_back(n, v)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        # a fetched persistable may share its buffer with the state just
        # written back (same traced value — XLA may alias the outputs);
        # mark it exposed so the next donating step copies first
        if self._donate and hasattr(scope, "_exposed"):
            persist_set = set(persist_names)
            scope._exposed.update(n for n in fetch_names
                                  if n in persist_set)
        return list(fetches)

    def _gather_state(self, scope, persist_names, feed_vals, sharding):
        """Read persistable state for one step, keeping it device-resident:
        host entries (numpy — e.g. fresh from static.io.load) are uploaded
        ONCE, already laid out with the program's parameter sharding, and
        written back so every later step passes resident jax.Arrays —
        zero per-step host->device traffic for state. Under donation,
        caller-visible aliases are copied so donation can't invalidate a
        buffer the caller still holds (or hand XLA one buffer twice)."""
        peek = getattr(scope, "_peek", scope.find_var)
        write_back = getattr(scope, "_write_back", scope.set)
        exposed = getattr(scope, "_exposed", set())
        param_shard = sharding.get("__param__") if sharding else None
        state = []
        # a feed array doubling as state must not be donated out from
        # under the feed argument
        seen = {id(v) for v in feed_vals if isinstance(v, jax.Array)}
        from ..parallel.sharding import device_put_counted

        for n in persist_names:
            arr = peek(n)
            if not isinstance(arr, jax.Array):
                host = np.asarray(arr)
                # device_put_counted bumps the global h2d_bytes; the
                # state-specific slice (and this executor's view) are
                # tracked here. A per-name entry (shard_propagation's
                # hinted params) beats the blanket __param__ fallback —
                # the upload lands already tp/dp-partitioned.
                arr = device_put_counted(
                    host, sharding.get(n, param_shard)
                    if sharding else None)
                self._counters["h2d_bytes"] += host.nbytes
                self._bump("state_h2d_bytes", host.nbytes)
                write_back(n, arr)
            elif sharding is not None:
                # a resident array laid out for a DIFFERENT config (the
                # user flipped sharding_hints/mesh_shape between runs on
                # one scope) must be re-placed or the AOT step rejects
                # the arg; a matching layout costs one equality check,
                # and a reshard is device-to-device (no h2d)
                target = sharding.get(n, param_shard)
                if target is not None and \
                        getattr(arr, "sharding", None) != target:
                    arr = jax.device_put(arr, target)
                    write_back(n, arr)
            if self._donate:
                aliased = id(arr) in seen
                seen.add(id(arr))
                if aliased or n in exposed:
                    arr = jnp.array(arr)   # the copy is what gets donated
                    self._bump("donation_fallback_copies")
            state.append(arr)
        return state

    def _record_pass_report(self, report) -> None:
        """Land the pipeline's per-pass op deltas + wall time in the
        profiler counters (and this executor's view): ir_ops_before/
        ir_ops_after, ir_pass_ms, ir_vars_dropped, pass_<name>_*."""
        self._bump("ir_ops_before", report.ops_before)
        self._bump("ir_ops_after", report.ops_after)
        self._bump("ir_pass_ms", round(report.ms, 3))
        if report.vars_dropped:
            self._bump("ir_vars_dropped", report.vars_dropped)
        for s in report.stats:
            if s.removed:
                self._bump(f"pass_{s.name}_removed_ops", s.removed)
            self._bump(f"pass_{s.name}_ms", round(s.ms, 3))
        for name, v in getattr(report, "amp", {}).items():
            self._bump(name, v)
        for name, v in getattr(report, "remat", {}).items():
            self._bump(name, v)
        for name, v in getattr(report, "shard", {}).items():
            if name == "pp_stages":   # point-in-time, not cumulative
                from .. import profiler

                self._counters[name] = v
                profiler.set_counter(name, v)
            else:
                self._bump(name, v)

    def _build(self, block, feed_keys, fetch_names, persist_names,
               sharding, feed_vals, state, rng, gm=None, pp=None,
               comm=None, comm_plan=None, schedule=None, zero=None,
               zero_plan=None, interleave=None):
        """AOT-compile one step: jit -> lower() (trace_ms) -> compile()
        (compile_ms). The split makes trace vs XLA-compile time
        measurable, and compile() goes through jax's persistent
        compilation cache (compile_cache.py) — a
        relaunched trainer's cold build becomes a disk read
        (disk_cache_hits in exe.counters).

        The step's SHAPE — plain forward, gm scan, pipeline schedule
        (gpipe/1f1b/interleaved), explicit quantized comm, or ZeRO
        sharded-optimizer — is the step-plan layer's job
        (static/stepplan.py): ``build_plan`` selects the registered
        plan kind and ``build_step_fn`` produces the traced callable.
        This method only wires the plan's boundary shardings + donation
        into substrate.aot_compile — the ONE compiled-step build path
        this executor shares with the decode engine (inference/decode)
        and, through Executor.run, the serving predictor."""
        from . import stepplan

        plan = stepplan.build_plan(
            block, gm=gm, pp=pp, comm=comm, comm_plan=comm_plan,
            schedule=schedule, zero=zero, zero_plan=zero_plan,
            sharding=sharding, donate=self._donate)
        if interleave is not None:
            plan.meta["interleave"] = interleave
        gauges = self._last_plan_gauges = {}

        def notify(name, value):
            gauges[name] = value   # replayed on cache hits (_ExecEntry)
            self._set_plan_gauge(name, value)

        step = stepplan.build_step_fn(
            plan, block, feed_keys, fetch_names, persist_names,
            feed_vals, notify=notify)
        in_shardings, out_shardings = plan.boundary_shardings(
            feed_keys, persist_names, fetch_names)
        from .substrate import aot_compile

        cs = aot_compile(
            step, (feed_vals, state, rng),
            donate_argnums=plan.donate_argnums,
            in_shardings=in_shardings, out_shardings=out_shardings,
            bump=self._bump)
        return cs.compiled

    def _set_plan_gauge(self, name, value):
        """Plan-layer gauge sink (pp_stages, pp_bubble_frac,
        pp_stash_depth, zero_*): point-in-time values set at step-plan
        build time — assigned, not accumulated."""
        from .. import profiler

        self._counters[name] = value
        profiler.set_counter(name, value)

    # -- quantized DP collectives (ISSUE 15: EQuARX-style comm layer) ------
    def _comm_eligibility(self, program, block, comm, shard_cfg, gm,
                          feed, sharding, pp=None):
        """Gate + plan for the explicit quantized-collective DP step —
        the logic lives in stepplan.comm_eligibility; this wrapper only
        keeps the per-executor memo (the warm step pays one key
        comparison, and counters bump once per verdict, not per step)."""
        from .stepplan import comm_eligibility

        self._comm_elig_cache = comm_eligibility(
            program, block, comm, shard_cfg, gm, feed, sharding, pp=pp,
            memo=getattr(self, "_comm_elig_cache", None))
        return self._comm_elig_cache[1]

    def _ensure_ef_state(self, scope, comm_plan, shard_cfg, sharding):
        from .stepplan import ensure_ef_state

        return ensure_ef_state(scope, comm_plan, shard_cfg, sharding)

    def _zero_eligibility(self, program, block, zero, comm, comm_plan,
                          shard_cfg, gm, pp, fetch_names):
        """Gate + layout plan for ZeRO sharded optimizer states — the
        logic lives in stepplan.zero_eligibility; the wrapper keeps the
        per-executor memo so counters bump once per verdict."""
        from .stepplan import zero_eligibility

        self._zero_elig_cache = zero_eligibility(
            program, block, zero, comm, comm_plan, shard_cfg, gm, pp,
            fetch_names, memo=getattr(self, "_zero_elig_cache", None))
        return self._zero_elig_cache[1]

    def _ensure_zero_state(self, scope, zero_plan, shard_cfg, sharding):
        from .stepplan import ensure_zero_state

        return ensure_zero_state(scope, zero_plan, shard_cfg, sharding)

    # -- dataset-driven training (reference executor.py:1593) -------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Train over an entire Dataset (reference Executor.train_from_dataset
        executor.py:1593 → C++ MultiTrainer/HogwildWorker TrainFiles,
        hogwild_worker.cc:191).

        TPU-native shape: the reference spawns one op-loop thread per core
        because each CPU thread is a compute unit; on TPU the chip runs one
        XLA program at a time, so `thread` buys input overlap instead —
        batches are parsed/padded on host threads and prefetched into a
        bounded queue while the device executes the previous step. Sparse
        slots arrive as (values, lod) pairs and are padded to power-of-two
        buckets (static shapes — each bucket compiles once); a program var
        named `<slot>_lens` receives the true lengths (the dense+lengths
        LoD rewrite used across ops/sequence.py).
        """
        import queue as queue_mod
        import threading

        from .compiler import CompiledProgram
        from .ir import default_main_program

        if dataset is None:
            raise ValueError("train_from_dataset requires a dataset")
        run_target = program if program is not None else \
            default_main_program()
        # a CompiledProgram trains data-parallel: steps run through
        # self.run (which applies its sharding to the compiled step) and
        # the prefetcher stages each batch DIRECTLY into the feed's
        # sharded layout — no per-step re-partition
        sharding = None
        strategy = None
        program = run_target
        if isinstance(program, CompiledProgram):
            sharding = program._data_sharding()
            strategy = program._build_strategy
            program = program._program
        scope = scope or global_scope()
        block = program.global_block
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [
            getattr(v, "name", str(v)) for v in fetch_list]

        q: queue_mod.Queue = queue_mod.Queue(maxsize=max(2, int(thread) * 2))
        _END = object()
        producer_error = []

        # multi-worker ingestion: `thread` producers over per-file dataset
        # shards (reference thread-per-DeviceWorker DataFeed channels);
        # batch->feed padding runs in the producer threads so the device
        # never waits on host-side parse/pad
        shards = (dataset.ingest_shards(int(thread))
                  if hasattr(dataset, "ingest_shards") and int(thread) > 1
                  else [dataset])

        def producer(shard):
            try:
                for batch in shard:
                    q.put(self._dataset_batch_to_feed(batch, block))
            except BaseException as e:  # surfaced in the consumer
                producer_error.append(e)
            finally:
                q.put(_END)

        producers = [threading.Thread(target=producer, args=(s,),
                                      daemon=True)
                     for s in shards]
        for t in producers:
            t.start()

        from .prefetch import FeedPrefetcher

        def host_feeds():
            ended = 0
            while ended < len(producers):
                item = q.get()
                if item is _END:
                    ended += 1
                elif item:          # skip empty feed dicts
                    yield item

        # second pipeline stage: while the device executes step N, the
        # prefetch thread device_puts batch N+1 (the producers above
        # keep parsing/padding N+2...). Depth scales with ingestion
        # parallelism but stays bounded — each slot pins device memory.
        # Under AMP, float32 feeds are cast low on the prefetch thread
        # BEFORE the h2d copy (half the transfer, amp_feed_dtypes).
        from .passes import (amp_feed_dtypes, resolve_amp,
                             resolve_sharding, shard_boundary_shardings)

        feed_dtypes = amp_feed_dtypes(block, resolve_amp(strategy))
        shard_cfg = resolve_sharding(strategy)
        if shard_cfg is not None:
            # BuildStrategy.mesh_shape (GSPMD) beats the classic
            # CompiledProgram data-parallel map, exactly as in _run_impl:
            # batches must stage into the SAME layout the AOT step's
            # in_shardings expect, or the dispatch rejects the committed
            # arrays. Derived per batch (stage_feed runs on the prefetch
            # thread) because divisibility is checked against the live
            # batch shapes.
            from ..parallel.mesh import mesh_for_shape
            from .prefetch import stage_feed

            shard_mesh = mesh_for_shape(dict(shard_cfg[0]))

            def _stage(item):
                m = shard_boundary_shardings(shard_mesh, block, item, (),
                                             shard_cfg)
                return stage_feed(item, m, feed_dtypes)

            prefetcher = FeedPrefetcher(host_feeds(),
                                        depth=max(2, int(thread)),
                                        stage=_stage)
        else:
            prefetcher = FeedPrefetcher(host_feeds(),
                                        depth=max(2, int(thread)),
                                        sharding=sharding,
                                        feed_dtypes=feed_dtypes)
        step = 0
        last_fetch = None
        try:
            # one-batch lookahead so the final step is known (it always
            # fetches, like the reference's end-of-epoch metric read)
            pending = next(prefetcher, None)
            while pending is not None:
                feed = pending
                pending = next(prefetcher, None)
                final_step = pending is None
                want_fetch = fetch_list and (
                    debug or final_step or step % print_period == 0)
                out = self.run(run_target, feed=feed,
                               fetch_list=fetch_list if want_fetch else None,
                               scope=scope)
                if want_fetch:
                    last_fetch = out
                    if debug:
                        msg = ", ".join(f"{n}={np.asarray(v).ravel()[:4]}"
                                        for n, v in zip(fetch_info, out))
                        print(f"[train_from_dataset] step {step}: {msg}")
                step += 1
        finally:
            # teardown order matters: signal the prefetch thread FIRST
            # (no join yet — it may be blocked on q.get while producers
            # are still filling q), then unblock/join the producers, then
            # re-seed the _END sentinels the drain may have eaten so
            # host_feeds() always reaches its exit count, and only then
            # join the prefetch thread.
            prefetcher.stop()
            while any(t.is_alive() for t in producers):
                try:
                    q.get(timeout=0.1)
                except queue_mod.Empty:
                    pass
            for t in producers:
                t.join()
            for _ in producers:
                try:
                    q.put_nowait(_END)
                except queue_mod.Full:
                    # q full ⇒ the worker is past q.get (it consumed a
                    # batch) and will see the stop flag, not block again
                    break
            prefetcher.close()
        if producer_error:
            raise producer_error[0]
        return last_fetch

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Same loop as train_from_dataset over an inference program
        (reference executor.py:1491)."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    @staticmethod
    def _dataset_batch_to_feed(batch, block):
        """Map a Dataset batch (slot -> dense array | (values, lod)) onto
        the program's data vars, padding ragged slots to pow-2 buckets."""
        feed = {}
        for name, val in batch.items():
            if isinstance(val, tuple):
                vals, lod = val
                rows = len(lod) - 1
                lens = np.diff(lod).astype(np.int64)
                longest = int(lens.max()) if rows else 1
                maxlen = 1 << max(0, int(longest - 1).bit_length())
                if np.issubdtype(vals.dtype, np.unsignedinteger):
                    vals = vals.astype(np.int64)
                dense = np.zeros((rows, maxlen), vals.dtype)
                for i in range(rows):
                    dense[i, :lens[i]] = vals[lod[i]:lod[i + 1]]
                if name in block.vars:
                    feed[name] = dense
                if f"{name}_lens" in block.vars:
                    feed[f"{name}_lens"] = lens
            elif name in block.vars:
                if np.issubdtype(getattr(val, "dtype", np.float32),
                                 np.unsignedinteger):
                    val = val.astype(np.int64)
                feed[name] = val
        return feed

    # -- startup-program path --------------------------------------------
    def run_startup(self, program: Program, scope: Optional[Scope] = None):
        """Run initializer ops eagerly, writing persistables to scope.
        (Executor.run on a startup program delegates here.)"""
        scope = scope or global_scope()
        seed = program.random_seed or random_mod.default_generator().initial_seed()
        ctx = ExecContext(rng_key=random_mod.make_key(seed))
        peek = getattr(scope, "_peek", scope.find_var)
        write_back = getattr(scope, "_write_back", scope.set)
        env = {n: peek(n) for n in program.global_block.vars
               if peek(n) is not None}
        env = run_block(program.global_block, env, ctx)
        for name, desc in program.global_block.vars.items():
            if desc.persistable and name in env and env[name] is not None:
                write_back(name, env[name])
        return []
