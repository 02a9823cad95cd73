"""Compiled step-plan layer: how one training step is SHAPED.

Extracted from ``static/executor.py`` (ROADMAP-flagged: the executor
had absorbed the ``_gm_step_fn``/``_pp_step_fn``/``_comm_step_fn``
step-function zoo plus the plan/eligibility logic, and 1F1B + ZeRO
were each about to add another method on top). The split mirrors the
PR 13 substrate extraction: ``substrate.aot_compile`` owns HOW a step
compiles, this module owns WHAT the step computes — the executor keeps
only feed/fetch/state plumbing and dispatch.

A :class:`StepPlan` is built once per executable from the optimized
Program + resolved BuildStrategy knobs: the plan KIND (plain / gm /
pipeline:<schedule> / comm / zero), the microbatch count, the comm
bucket plan, the boundary shardings and the donation map. Each
step-function builder is a registered plan kind (:func:`plan_kind`),
so new schedules land as registry entries instead of executor methods:

- ``plain``            one forward(+backward+optimizer) pass
- ``gm``               lax.scan over k microbatches (gradient merge)
- ``pipeline:gpipe``   gm microbatches on the GPipe fill-drain schedule
- ``pipeline:1f1b``    one-forward-one-backward schedule: warmup of
                       S-1-s forwards per stage, then strict F/B
                       alternation — ≤S live microbatch activations by
                       construction instead of GPipe's fill-phase stash
- ``pipeline:interleaved``  1F1B with v virtual stages per chip
- ``comm``             explicit bucketed quantized DP all-reduce
                       (shard_map over the pure-dp mesh)
- ``zero``             the comm step with ZeRO-2/3 sharded optimizer
                       states: bucketed quantized reduce-scatter, the
                       optimizer region on LOCAL shards only, and a
                       post-update param all-gather

Parity contracts the kinds agree on (tested): every kind derives a
microbatch's RNG key as ``fold_in(step_key, m)`` (dropout replays
bitwise across gm/gpipe/1f1b/comm), f32 gradient accumulation in
ascending-microbatch order (gpipe and 1f1b merge bitwise-identical
gradients), and the fp16 FoundInfinite flag OR-reduces across
microbatches (and devices, on the comm/zero kinds).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtype_mod
from .kernels import (KERNELS, ExecContext, _gate_update,
                      _lamb_moments)

__all__ = [
    "StepPlan", "build_plan", "build_step_fn", "plan_kind", "PLAN_KINDS",
    "merge_region", "comm_eligibility", "comm_entry_stats",
    "ensure_ef_state", "zero_eligibility", "ensure_zero_state",
    "zero_flip_back", "zero_state_layout", "ZERO_OPT_OPS",
]


# ---------------------------------------------------------------------------
# the plan object + kind registry
# ---------------------------------------------------------------------------

PLAN_KINDS: Dict[str, Callable] = {}


def plan_kind(name: str):
    """Register a step-function builder under a plan kind name. The
    builder signature is ``fn(plan, block, feed_keys, fetch_names,
    persist_names, feed_vals, notify) -> step`` where ``step(feed_vals,
    state, rng) -> (fetches, new_state)`` is what gets AOT-compiled."""

    def deco(fn):
        PLAN_KINDS[name] = fn
        return fn

    return deco


class StepPlan:
    """Everything that shapes ONE compiled training step, resolved
    once per executable: the schedule kind, the microbatch count, the
    comm bucket plan, the ZeRO layout, the jit boundary shardings and
    the donation map. ``meta`` carries kind-specific extras (stage
    count, stash depth, bubble fraction) for gauges and dump tools."""

    __slots__ = ("kind", "gm", "pp", "schedule", "comm", "comm_plan",
                 "zero", "zero_plan", "bwd_idx", "sharding", "donate",
                 "meta")

    def __init__(self, kind, *, gm=None, pp=None, schedule=None,
                 comm=None, comm_plan=None, zero=None, zero_plan=None,
                 bwd_idx=None, sharding=None, donate=True):
        self.kind = kind
        self.gm = gm
        self.pp = pp
        self.schedule = schedule
        self.comm = comm
        self.comm_plan = comm_plan
        self.zero = zero
        self.zero_plan = zero_plan
        self.bwd_idx = bwd_idx
        self.sharding = sharding
        self.donate = donate
        self.meta: Dict[str, Any] = {}

    @property
    def microbatches(self) -> int:
        return self.gm[0] if self.gm is not None else 1

    @property
    def donate_argnums(self):
        # state + rng buffers are reused in place by XLA; feeds are
        # fresh per step and stay un-donated
        return (1, 2) if self.donate else None

    def boundary_shardings(self, feed_keys, persist_names, fetch_names):
        """The jit in/out sharding maps for this plan's step signature
        ``(feed_vals, state, rng) -> (fetches, new_state)``."""
        if self.sharding is None:
            return None, None
        sharding = self.sharding
        param_shard = sharding.get("__param__")
        # per-name entries (the shard_propagation boundary map: hinted
        # tp/dp params, __comm_ef_*/__zero_* rows) beat the blanket
        # __param__ fallback; the classic data-parallel map has no
        # per-name entries so this degenerates to [param_shard] * N
        state_shards = [sharding.get(n, param_shard)
                        for n in persist_names]
        in_shardings = (
            [sharding.get(k) for k in feed_keys],
            state_shards,
            sharding.get("__rng__"))
        # pin state OUTPUTS to the same layout: chained steps feed
        # new_state straight back in without re-partitioning
        out_shardings = (
            [None] * len(fetch_names),
            state_shards)
        return in_shardings, out_shardings


def build_plan(block, *, gm=None, pp=None, comm=None, comm_plan=None,
               schedule=None, zero=None, zero_plan=None, sharding=None,
               donate=True) -> StepPlan:
    """Select the plan kind for one optimized block + resolved config.

    Selection order mirrors the pre-refactor ``Executor._build``: an
    engaged comm plan on a backward block wins (zero variant when the
    ZeRO layout engaged too), then the pipeline schedule when gm+pp and
    ``__pp_stage`` stamps are present, then the gm scan, else plain."""
    bwd_idx = next((i for i, op in enumerate(block.ops)
                    if op.type == "backward"), None)
    if comm_plan is not None and bwd_idx is not None:
        kind = "zero" if zero_plan is not None else "comm"
    elif gm is not None and bwd_idx is not None and pp is not None \
            and pp > 1 and any("__pp_stage" in op.attrs
                               for op in block.ops):
        kind = f"pipeline:{schedule or 'gpipe'}"
    elif gm is not None and bwd_idx is not None:
        kind = "gm"
    else:
        kind = "plain"
    return StepPlan(kind, gm=gm, pp=pp, schedule=schedule, comm=comm,
                    comm_plan=comm_plan, zero=zero, zero_plan=zero_plan,
                    bwd_idx=bwd_idx, sharding=sharding, donate=donate)


def build_step_fn(plan: StepPlan, block, feed_keys, fetch_names,
                  persist_names, feed_vals,
                  notify: Optional[Callable[[str, Any], None]] = None):
    """Build the traced step callable for ``plan`` through its
    registered kind. ``notify(name, value)`` is the executor's gauge
    sink (pp_stages, pp_bubble_frac, ...); pass None to skip."""
    base = plan.kind.split(":", 1)[0]
    builder = PLAN_KINDS.get(base)
    if builder is None:
        raise KeyError(f"no step-plan kind registered for {plan.kind!r}")
    if notify is None:
        def notify(_name, _value):
            pass
    return builder(plan, block, feed_keys, fetch_names, persist_names,
                   feed_vals, notify)


# ---------------------------------------------------------------------------
# shared region split (the gm scan / pipeline schedules / comm step all
# agree on this boundary — their parity depends on it)
# ---------------------------------------------------------------------------


def merge_region(block, feed_keys, feed_vals, persist_names,
                 fetch_names, k, bwd_idx):
    """Split one training block at the backward boundary for a
    k-microbatch merged step — shared by the gm scan, the pipeline
    schedules and the comm/zero steps (their parity depends on
    agreeing on this split). Returns ``(scan_end, grad_names,
    found_name, state_carry, carry_out, post_outs)``: ops
    [0, scan_end) run per microbatch (forward + backward + an adjacent
    fp16 check_finite_and_unscale), ops [scan_end, ...) are the
    optimizer region run once on the merged gradient; state_carry is
    the per-microbatch persistable writes, carry_out everything else
    the post region or a fetch reads."""
    for key, v in zip(feed_keys, feed_vals):
        shp = tuple(getattr(v, "shape", ()))
        if not shp or shp[0] % k:
            raise ValueError(
                f"gradient_merge_k={k}: feed {key!r} batch dim "
                f"{shp[0] if shp else None} is not divisible by k")
    ops = block.ops
    scan_end = bwd_idx + 1
    if scan_end < len(ops) and \
            ops[scan_end].type == "check_finite_and_unscale":
        scan_end += 1
    grad_names = list(ops[bwd_idx].outputs.get("Grads", []))
    found_name = None
    if ops[scan_end - 1].type == "check_finite_and_unscale":
        fo = ops[scan_end - 1].outputs.get("FoundInfinite")
        found_name = fo[0] if fo else None
    produced: set = set()
    for op in ops[:scan_end]:
        produced.update(op.output_names())
    post_reads: set = set()
    post_outs: set = set()
    for op in ops[scan_end:]:
        post_reads.update(op.input_names())
        post_outs.update(op.output_names())
    special = set(grad_names) | {found_name} - {None}
    persist_set = set(persist_names)
    # state written per microbatch rides the carry; everything else
    # the post region or a fetch reads rides the stacked ys
    state_carry = sorted(produced & persist_set)
    carry_out = sorted(((post_reads | set(fetch_names)) & produced)
                       - special - persist_set)
    return (scan_end, grad_names, found_name, state_carry,
            carry_out, post_outs)


def comm_entry_stats(comm_plan) -> Dict[str, Any]:
    """Per-dispatch quantized-collective accounting for one compiled
    executable: encoded ring bytes actually moved per device per step
    (``bytes_sent``), the f32 bytes the codec saved (``bytes_saved``),
    the bucket count, and the analytic overlap fraction — with nb
    buckets emitted in completion order, nb-1 of them have a later
    bucket's work in flight behind them (the last one drains alone),
    the same analytic convention as pp_bubble_frac."""
    _axis, _g, plan = comm_plan
    sent = sum(b["ring_encoded"] for b in plan)
    f32 = sum(b["ring_f32"] for b in plan)
    nb = len(plan)
    return {
        "bytes_sent": int(sent),
        "bytes_saved": int(max(0, f32 - sent)),
        "comm_buckets": nb,
        "allreduce_overlap_frac": round((nb - 1) / nb, 4) if nb else 0.0,
    }


def zero_entry_stats(comm_plan) -> Dict[str, Any]:
    """Per-dispatch wire accounting for a ZeRO step: the sharded
    optimizer replaces the bucketed all-reduce ring with a half-ring
    reduce-scatter of the ENCODED grads plus a raw-f32 all-gather of
    the updated values, so ``bytes_sent`` is that rs+ag profile and
    ``bytes_saved`` is measured against the f32 all-reduce ring. Kept
    out of the ``comm_quant_*`` counters: the all-gather leg moves raw
    f32, and folding its bytes into the quantized-ring counters would
    break their saved>sent codec invariant (ride
    ``zero_wire_bytes_*`` instead — see the executor's dispatch
    bump)."""
    _axis, _g, plan = comm_plan
    rs = sum(b["ring_encoded"] // 2 for b in plan)
    ag = sum(b["ring_f32"] - b["ring_f32"] // 2 for b in plan)
    f32 = sum(b["ring_f32"] for b in plan)
    nb = len(plan)
    return {
        "zero": True,
        "bytes_sent": int(rs + ag),
        "bytes_saved": int(max(0, f32 - (rs + ag))),
        "comm_buckets": nb,
        "allreduce_overlap_frac": round((nb - 1) / nb, 4) if nb else 0.0,
    }


# ---------------------------------------------------------------------------
# plain + gm kinds
# ---------------------------------------------------------------------------


@plan_kind("plain")
def _plain_step_fn(plan, block, feed_keys, fetch_names, persist_names,
                   feed_vals, notify):
    from .executor import run_block

    def step(feed_vals, state, rng):
        env = dict(zip(feed_keys, feed_vals))
        env.update(zip(persist_names, state))
        ctx = ExecContext(rng_key=rng)
        env = run_block(block, env, ctx)
        fetches = [env[n] for n in fetch_names]
        new_state = [env.get(n, s)
                     for n, s in zip(persist_names, state)]
        return fetches, new_state

    return step


@plan_kind("gm")
def _gm_step_fn(plan, block, feed_keys, fetch_names, persist_names,
                feed_vals, notify):
    """In-step gradient merge: compile the train step as ONE lax.scan
    over k microbatches (GPipe-style accumulation, inside a single
    dispatch).

    The op list splits at the backward boundary: ops [0, scan_end)
    (forward + backward + an adjacent fp16 check_finite_and_unscale)
    run PER MICROBATCH inside the scan; ops [scan_end, ...) — the
    optimizer update region — run ONCE on the merged gradient.
    Mechanics:

    - every feed is reshaped (B, ...) -> (k, B//k, ...) inside the
      trace (host layout untouched; B must divide by k)
    - gradients accumulate in f32 whatever the compute dtype (AMP
      bf16/fp16 microbatch grads are upcast before the add), and
      with avg=True the MERGED sum is divided by k once — never a
      per-microbatch lr rescale
    - the fp16 FoundInfinite flag is OR-reduced over microbatches:
      one bad microbatch skips the whole merged update
    - persistable state written inside the scanned region
      (batch_norm running stats, step counters) threads through the
      scan carry, so microbatch i sees microbatch i-1's updates
    - each microbatch folds its index into the step RNG key —
      dropout draws fresh masks per microbatch
    - float fetches produced inside the scanned region (the loss)
      are averaged over microbatches; non-float fetches report the
      last microbatch
    """
    from .executor import run_block

    k, avg = plan.gm
    bwd_idx = plan.bwd_idx
    (scan_end, grad_names, found_name, state_carry, carry_out,
     post_outs) = merge_region(block, feed_keys, feed_vals,
                               persist_names, fetch_names, k, bwd_idx)

    def _micro(mb_feed, state_env, carried, key):
        env = dict(zip(feed_keys, mb_feed))
        env.update(state_env)
        env.update(carried)
        ctx = ExecContext(rng_key=key)
        return run_block(block, env, ctx, stop_at=scan_end)

    # grad avals (shape/dtype of ONE microbatch's grads): read from
    # the grad VarDescs when fully static — append_backward declares
    # them with the param's shape/dtype — falling back to an
    # abstract eval_shape trace only for dynamic shapes
    # (calc_gradient w.r.t. a batch-dim intermediate). The probe
    # re-interprets the whole scanned region, so skipping it halves
    # merged-build trace time in the common (param-grad) case.
    grad_avals = []
    for g in grad_names:
        desc = block.vars.get(g)
        shape = getattr(desc, "shape", None)
        if not shape or any(int(d) < 0 for d in shape):
            grad_avals = None
            break
        grad_avals.append(jax.ShapeDtypeStruct(
            tuple(int(d) for d in shape),
            jnp.dtype(dtype_mod.convert_dtype(desc.dtype))))

    mb_avals = [jax.ShapeDtypeStruct(
        (int(v.shape[0]) // k,) + tuple(int(d) for d in v.shape[1:]),
        getattr(v, "dtype", np.asarray(v).dtype))
        for v in feed_vals]

    def _probe(mb_feed, state, rng):
        env = _micro(mb_feed, dict(zip(persist_names, state)), {},
                     rng)
        return [env[g] for g in grad_names]

    def step(feed_vals, state, rng):
        state_env0 = dict(zip(persist_names, state))
        avals = grad_avals if grad_avals is not None else \
            jax.eval_shape(_probe, mb_avals, state, rng)
        mbs = [v.reshape((k, v.shape[0] // k) + tuple(v.shape[1:]))
               for v in feed_vals]

        def body(carry, xs):
            accum, carried, found = carry
            mb, mi = xs
            env = _micro(mb, state_env0, carried,
                         jax.random.fold_in(rng, mi))
            accum = [a + env[g].astype(jnp.float32)
                     for a, g in zip(accum, grad_names)]
            carried = {n: env[n] for n in state_carry}
            if found_name is not None:
                found = found | jnp.reshape(
                    env[found_name], ()).astype(bool)
            ys = {n: env[n] for n in carry_out}
            return (accum, carried, found), ys

        init = ([jnp.zeros(a.shape, jnp.float32) for a in avals],
                {n: state_env0[n] for n in state_carry},
                jnp.zeros((), jnp.bool_))
        (accum, carried, found), ys = jax.lax.scan(
            body, init, (mbs, jnp.arange(k)))
        env = dict(zip(feed_keys, feed_vals))  # full batch for post
        env.update(state_env0)
        env.update(carried)
        env.update({n: ys[n][-1] for n in carry_out})
        for g, a, aval in zip(grad_names, accum, avals):
            merged = a / k if avg else a
            env[g] = merged.astype(aval.dtype)
        if found_name is not None:
            env[found_name] = jnp.reshape(found, (1,))
        ctx = ExecContext(rng_key=rng)
        env = run_block(block, env, ctx, start=scan_end)
        fetches = []
        for n in fetch_names:
            if n in ys and n not in post_outs:
                stacked = ys[n]
                if jnp.issubdtype(stacked.dtype, jnp.inexact):
                    fetches.append(jnp.mean(
                        stacked.astype(jnp.float32), axis=0
                    ).astype(stacked.dtype))
                else:
                    fetches.append(stacked[-1])
            else:
                fetches.append(env[n])
        new_state = [env.get(n, s)
                     for n, s in zip(persist_names, state)]
        return fetches, new_state

    return step


# ---------------------------------------------------------------------------
# pipeline kinds (gpipe / 1f1b / interleaved — one executor body, the
# schedule decides the slot order)
# ---------------------------------------------------------------------------


@plan_kind("pipeline")
def _pipeline_step_fn(plan, block, feed_keys, fetch_names,
                      persist_names, feed_vals, notify):
    """Pipeline-composed gradient merge: the k microbatches of
    BuildStrategy.gradient_merge_k flow through the
    ``__pp_stage``-stamped forward stages on the resolved schedule
    (``parallel.pipeline``), still as ONE compiled, donated,
    device-resident dispatch.

    Differences from the plain gm scan:

    - the microbatch loop is schedule-ordered instead of sequential —
      within a tick every (stage, microbatch) pair is data-independent,
      which is the property that lets XLA overlap the stages across a
      'pp' mesh axis (and on one chip compiles to the same math)
    - a microbatch's backward (+ fp16 finite check) runs when it
      retires from the last stage; f32 gradient accumulation happens
      in retirement order == microbatch order, so the merged gradient
      matches the scan's within reassociation roundoff — and matches
      BITWISE across schedules (gpipe/1f1b/interleaved retire
      microbatches in the same ascending order)
    - persistable state written INSIDE the forward region does not
      thread microbatch-to-microbatch (stages overlap, so there is no
      earlier-microbatch value to read); every microbatch sees the
      step-entry state and the LAST retired microbatch's writes carry
      out — bn running stats behave like classic GPipe, parameter
      updates are untouched (they live in the post region)

    Schedules: ``gpipe`` drives the fill-drain ``gpipe_schedule``
    exactly as before; ``1f1b``/``interleaved`` drive the
    ``pipeline_timeline`` slot stream — same per-microbatch math, a
    different emission order, and a bounded modeled stash depth (the
    ``pp_stash_depth`` gauge). Everything else (feed reshape,
    merged-gradient averaging, FoundInfinite OR-reduce, loss-fetch
    averaging, single optimizer region on the merged gradient) mirrors
    the gm scan."""
    from ..parallel.pipeline import (
        gpipe_schedule, pipeline_timeline, schedule_bubble_fraction)
    from .executor import run_block

    k, avg = plan.gm
    bwd_idx = plan.bwd_idx
    schedule = plan.schedule or "gpipe"
    interleave = plan.meta.get("interleave", 2)
    (scan_end, grad_names, found_name, state_carry, carry_out,
     post_outs) = merge_region(block, feed_keys, feed_vals,
                               persist_names, fetch_names, k, bwd_idx)
    ops = block.ops

    # stage op ranges from the __pp_stage stamps: stage s covers the
    # absolute index range (start_s, end_s]; un-stamped prefix ops
    # (feeds) ride stage 0, un-stamped trailing forward ops ride the
    # last stage
    stage_last: Dict[int, int] = {}
    for i in range(bwd_idx):
        sid = ops[i].attrs.get("__pp_stage")
        if sid is not None:
            stage_last[int(sid)] = i
    n_stages = max(stage_last) + 1
    ranges = []
    start = 0
    for s in range(n_stages):
        end = bwd_idx if s == n_stages - 1 else stage_last[s] + 1
        ranges.append((start, end))
        start = end
    notify("pp_stages", n_stages)
    if schedule == "interleaved" and n_stages % interleave:
        # the stamped stage count (which can be smaller than the
        # requested pipeline_stages on shallow nets) must divide by the
        # virtual-chunk factor; degrade to plain 1f1b instead of
        # refusing the step — same math, same retirement order
        plan.meta["schedule_fallback"] = (
            f"interleaved: {n_stages} stages not divisible by "
            f"interleave {interleave} — running 1f1b")
        schedule = "1f1b"
        notify("pp_schedule_fallback", 1)
    if schedule != "gpipe":
        # the slot stream for the non-gpipe schedules; gpipe keeps its
        # original generator below (bitwise-stable trace order)
        slots = [(kind_, s, m) for _t, tick in pipeline_timeline(
            schedule, n_stages, k, interleave=interleave)
            for kind_, s, m in tick]
        stash = plan.meta["stash_depth"] = _modeled_stash_depth(
            pipeline_timeline(schedule, n_stages, k,
                              interleave=interleave), k)
        notify("pp_stash_depth", stash)
    bubble = schedule_bubble_fraction(schedule, n_stages, k,
                                      interleave=interleave)
    plan.meta.update(n_stages=n_stages, bubble_frac=bubble)
    notify("pp_bubble_frac", round(bubble, 4))

    def _retire(env, ctx, s, accum, grad_dtypes, found, carried, ys, m):
        # microbatch m retires: backward + fp16 finite check, then
        # f32 accumulation (ascending-m retirement order on every
        # schedule — the cross-schedule bitwise-parity invariant)
        run_block(block, env, ctx, start=ranges[s][1], stop_at=scan_end)
        if grad_dtypes is None:
            grad_dtypes = [env[g].dtype for g in grad_names]
        g = [env[gn].astype(jnp.float32) for gn in grad_names]
        accum = g if accum is None else \
            [a + b for a, b in zip(accum, g)]
        if found_name is not None:
            found = found | jnp.reshape(
                env[found_name], ()).astype(bool)
        carried = {n: env[n] for n in state_carry}
        for n in carry_out:
            ys[n][m] = env[n]
        return accum, grad_dtypes, found, carried

    def step(feed_vals, state, rng):
        state_env0 = dict(zip(persist_names, state))
        mbs = [v.reshape((k, v.shape[0] // k) + tuple(v.shape[1:]))
               for v in feed_vals]
        accum = None
        grad_dtypes = None
        found = jnp.zeros((), jnp.bool_)
        carried: Dict[str, Any] = {}
        ys = {n: [None] * k for n in carry_out}
        live: Dict[int, tuple] = {}

        def _enter(m):
            env = dict(zip(feed_keys, [mb[m] for mb in mbs]))
            env.update(state_env0)
            # same per-microbatch key derivation as the gm scan:
            # dropout masks match the scan leg bitwise
            live[m] = (env, ExecContext(
                rng_key=jax.random.fold_in(rng, m)))

        if schedule == "gpipe":
            for _t, pairs in gpipe_schedule(n_stages, k):
                for s, m in pairs:
                    if s == 0:
                        _enter(m)
                    env, ctx = live[m]
                    run_block(block, env, ctx,
                              start=ranges[s][0], stop_at=ranges[s][1])
                    if s == n_stages - 1:
                        accum, grad_dtypes, found, carried = _retire(
                            env, ctx, s, accum, grad_dtypes, found,
                            carried, ys, m)
                        del live[m]
        else:
            for kind_, s, m in slots:
                if kind_ != "F":
                    continue  # the backward op is monolithic: it runs
                    # at retirement (the last-stage F slot below)
                if s == 0:
                    _enter(m)
                env, ctx = live[m]
                run_block(block, env, ctx,
                          start=ranges[s][0], stop_at=ranges[s][1])
                if s == n_stages - 1:
                    accum, grad_dtypes, found, carried = _retire(
                        env, ctx, s, accum, grad_dtypes, found,
                        carried, ys, m)
                    del live[m]
        env = dict(zip(feed_keys, feed_vals))  # full batch for post
        env.update(state_env0)
        env.update(carried)
        env.update({n: ys[n][-1] for n in carry_out})
        for gname, a, dt in zip(grad_names, accum or (),
                                grad_dtypes or ()):
            merged = a / k if avg else a
            env[gname] = merged.astype(dt)
        if found_name is not None:
            env[found_name] = jnp.reshape(found, (1,))
        ctx = ExecContext(rng_key=rng)
        env = run_block(block, env, ctx, start=scan_end)
        fetches = []
        for n in fetch_names:
            if n in ys and n not in post_outs:
                stacked = jnp.stack(ys[n])
                if jnp.issubdtype(stacked.dtype, jnp.inexact):
                    fetches.append(jnp.mean(
                        stacked.astype(jnp.float32), axis=0
                    ).astype(stacked.dtype))
                else:
                    fetches.append(stacked[-1])
            else:
                fetches.append(env[n])
        new_state = [env.get(n, s_)
                     for n, s_ in zip(persist_names, state)]
        return fetches, new_state

    return step


def _modeled_stash_depth(timeline, n_micro: int) -> int:
    """Max simultaneously-live microbatch activations a schedule
    timeline implies: a microbatch is live from its first F slot to its
    LAST B slot (stage-0 backward frees the stash)."""
    first_f: Dict[int, int] = {}
    last_b: Dict[int, int] = {}
    for t, tick in timeline:
        for kind_, _s, m in tick:
            if kind_ == "F":
                first_f.setdefault(m, t)
            else:
                last_b[m] = t
    depth = 0
    for t in range(max(last_b.values(), default=0) + 1):
        live = sum(1 for m in first_f
                   if first_f[m] <= t <= last_b.get(m, first_f[m]))
        depth = max(depth, live)
    return depth


# ---------------------------------------------------------------------------
# comm kind (ISSUE 15: EQuARX-style quantized DP collectives) + the
# eligibility gate and error-feedback state the executor wires up
# ---------------------------------------------------------------------------


def comm_eligibility(program, block, comm, shard_cfg, gm, feed,
                     sharding, pp=None, memo=None, bump=None):
    """Gate + plan for the explicit quantized-collective DP step.

    Returns ``(key, result)`` where ``result`` is ``(axis_name, group,
    plan)`` when the build is eligible, else None after bumping the
    ``quant_allreduce.xla`` dispatch counter with the reason (the
    established kernel pattern — the XLA f32 GSPMD path is the
    fallback, bitwise-identical to the pre-quantization baseline).
    Pass the previous return as ``memo`` to reuse the warm verdict
    without re-bumping counters (the executor keeps it per-instance:
    the warm step pays one key comparison).

    Eligible means: a PURE data-parallel mesh (exactly one 'dp'/'data'
    axis, no sharding hints — tensor/pipeline layouts keep XLA's
    partitioner-owned collectives), one static ``backward`` gradient
    plan, no persistable writes inside the scanned region (per-device
    batch-norm style stats would diverge silently under a
    replicated-out shard_map), every dynamic-batch feed actually
    sharded over the axis, and local batches divisible by
    gradient_merge_k."""
    from ..ops.pallas.counters import bump as _bump
    from .passes import comm_bucket_plan, comm_data_axis

    if bump is None:
        bump = _bump
    key = (program._version, comm, shard_cfg, gm, pp,
           tuple(sorted((k, tuple(getattr(v, "shape", ())))
                        for k, v in feed.items())))
    if memo is not None and memo[0] == key:
        return memo

    def verdict(result, reason=None):
        if result is None:
            bump("quant_allreduce", "xla", reason)
        else:
            bump("quant_allreduce", "quant")
        return (key, result)

    if shard_cfg is None:
        return verdict(None, "comm_quant set but no mesh_shape — "
                             "quantized collectives need a dp mesh")
    if pp is not None:
        return verdict(None, "pipeline_stages > 1 — the pipeline "
                             "schedule keeps XLA collectives")
    axis = comm_data_axis(shard_cfg)
    if axis is None:
        return verdict(None, "mesh is not pure data-parallel "
                             f"(axes {shard_cfg[0]})")
    if shard_cfg[1]:
        return verdict(None, "sharding_hints present — tensor-"
                             "parallel layouts keep XLA collectives")
    name, g = axis
    plan = comm_bucket_plan(block, comm, g)
    if plan is None:
        return verdict(None, "no static gradient plan (no backward "
                             "op, or dynamic grad shapes)")
    ops = block.ops
    bwd_idx = next(i for i, op in enumerate(ops)
                   if op.type == "backward")
    persist = {n for n, v in block.vars.items() if v.persistable}
    written = {n for op in ops[:bwd_idx] for n in op.output_names()
               if n in persist}
    if written:
        return verdict(None, f"persistable writes in the forward "
                             f"region ({sorted(written)[:3]}) would "
                             "diverge per-device")
    for k_, v in feed.items():
        dv = block.vars.get(k_)
        shape = getattr(dv, "shape", None)
        if not shape or shape[0] is None or int(shape[0]) >= 0:
            continue
        sh = sharding.get(k_) if sharding else None
        spec = getattr(sh, "spec", None)
        if not spec or not spec[0]:
            return verdict(None, f"feed {k_!r} batch dim not "
                                 f"sharded over {name!r} (size not "
                                 f"divisible by {g}?)")
        local_b = int(getattr(v, "shape", (0,))[0]) // g
        if gm is not None and local_b % gm[0]:
            return verdict(None, f"local batch {local_b} not "
                                 f"divisible by gradient_merge_k="
                                 f"{gm[0]}")
    return verdict((name, g, plan))


def ensure_ef_state(scope, comm_plan, shard_cfg, sharding):
    """Materialize the error-feedback residual buffers as DONATED
    executor state: one ``(g, padded)`` f32 array per bucket, sharded
    over the data axis so each device owns its row. Returns the names
    (appended to persist_names; XLA updates them in place step over
    step through the normal donation path)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.collectives import padded_len
    from ..parallel.mesh import mesh_for_shape

    axis, g, plan = comm_plan
    mesh = mesh_for_shape(dict(shard_cfg[0]))
    shard = NamedSharding(mesh, PartitionSpec(axis, None))
    peek = getattr(scope, "_peek", scope.find_var)
    write_back = getattr(scope, "_write_back", scope.set)
    names = []
    for i, b in enumerate(plan):
        n = f"__comm_ef_{i}"
        padded = padded_len(b["elems"], g)
        arr = peek(n)
        if not isinstance(arr, jax.Array) or \
                tuple(arr.shape) != (g, padded):
            arr = jax.device_put(np.zeros((g, padded), np.float32),
                                 shard)
            write_back(n, arr)
        sharding[n] = shard
        names.append(n)
    return names


@plan_kind("comm")
def _comm_step_fn(plan, block, feed_keys, fetch_names, persist_names,
                  feed_vals, notify):
    """Compile the DP train step with an EXPLICIT bucketed, quantized
    gradient all-reduce instead of XLA's implicit f32 psum: the whole
    step runs inside shard_map over the pure-dp mesh — each device
    traces the forward+backward on its LOCAL batch shard, the
    per-bucket gradients reduce through parallel.collectives'
    quantized ring (encode per hop, f32 accumulation, deterministic
    decode → bitwise-replicated reduced values), and the optimizer
    region then runs replicated on every device (same grads + same
    params ⇒ same updates, so state out-specs are replicated by
    construction).

    Overlap: every bucket's reduce-scatter is ISSUED (in backward-
    completion order, the comm_bucketing plan) before any bucket's
    all-gather completes — XLA's latency-hiding scheduler is free
    to run them concurrently instead of one barrier-shaped reduce.

    Composition: with ``gradient_merge_k`` the local microbatch
    scan accumulates f32 grads exactly like the gm kind and the
    MERGED gradient is reduced once per step (quantize once per
    step, the PR 5 accumulator discipline). ``avg=True`` on the
    collective turns sum-of-local-mean-grads into the global-mean
    gradient, matching the GSPMD leg's mean-loss semantics.

    Fetch assembly: dynamic-batch fetches gather over the axis
    (out-spec carries the batch dim), other float fetches are
    pmean'd (exact for replicated values, the global mean for
    per-shard losses), the rest report the local value.

    Error feedback (``comm_error_feedback``): each device adds its
    residual to its contribution, quantizes ONCE locally, carries
    the new residual out through the donated ``__comm_ef_<i>``
    state row, and feeds the dequantized contribution into the
    ring."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import (
        allreduce_done, allreduce_start, padded_len, quant_decode,
        quant_encode, shard_map_nocheck)
    from ..parallel.mesh import mesh_for_shape
    from .executor import run_block

    sharding = plan.sharding
    gm = plan.gm
    bwd_idx = plan.bwd_idx
    axis, g, cplan = plan.comm_plan
    codec, _bucket_bytes, ef = plan.comm
    k, avg_gm = gm if gm is not None else (1, True)
    (scan_end, grad_names, found_name, state_carry, carry_out,
     post_outs) = merge_region(block, feed_keys, feed_vals,
                               persist_names, fetch_names, 1, bwd_idx)
    mesh = mesh_for_shape({axis: g})
    ef_names = [f"__comm_ef_{i}" for i in range(len(cplan))] \
        if ef else []
    ef_set = set(ef_names)
    reg_names = [n for n in persist_names if n not in ef_set]

    grad_elems = {}
    grad_shapes = {}
    for gn in grad_names:
        desc = block.vars.get(gn)
        shape = tuple(int(d) for d in (desc.shape or ()))
        grad_shapes[gn] = shape
        e = 1
        for d in shape:
            e *= d
        grad_elems[gn] = e

    def spec_of(n):
        sh = sharding.get(n) if sharding else None
        spec = getattr(sh, "spec", None)
        return P(*spec) if spec is not None else P()

    # fetch modes: dynamic-batch fetches re-assemble over the axis;
    # float fetches pmean (global mean for shard-varying losses, a
    # no-op for replicated values); the rest report local
    fetch_modes = []
    for n in fetch_names:
        v = block.vars.get(n)
        shape = getattr(v, "shape", None)
        dt = str(getattr(v, "dtype", "float32"))
        if shape and (shape[0] is None or int(shape[0]) < 0):
            fetch_modes.append("gather")
        elif dt.startswith("float") or dt == "bfloat16":
            fetch_modes.append("pmean")
        else:
            fetch_modes.append("local")

    in_specs = ([spec_of(kk) for kk in feed_keys],
                [P(axis, None) if n in ef_set else P()
                 for n in persist_names],
                P())
    out_specs = ([P(axis) if m == "gather" else P()
                  for m in fetch_modes],
                 [P(axis, None) if n in ef_set else P()
                  for n in persist_names])

    def reduce_buckets(env, ef_rows):
        """Bucketed quantized all-reduce of env's grads, overlap-
        emitted; returns (env with reduced grads, new ef rows)."""
        xs, new_ef = [], []
        for i, b in enumerate(cplan):
            flats = [env[gn].astype(jnp.float32).reshape(-1)
                     for gn in b["grads"]]
            flat = flats[0] if len(flats) == 1 else \
                jnp.concatenate(flats)
            padded = padded_len(b["elems"], g)
            if padded != flat.shape[0]:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((padded - flat.shape[0],),
                                     jnp.float32)])
            if ef:
                flat = flat + ef_rows[i]
                q, sc = quant_encode(flat, codec)
                dec = quant_decode(q, sc, codec)
                new_ef.append(flat - dec)
                flat = dec
            xs.append(flat)
        starts = [allreduce_start(x, axis, codec=codec, axis_size=g)
                  for x in xs]
        reduced = [allreduce_done(c, avg=True) for c in starts]
        for b, r in zip(cplan, reduced):
            off = 0
            for gn in b["grads"]:
                e = grad_elems[gn]
                env[gn] = r[off:off + e].reshape(
                    grad_shapes[gn]).astype(env[gn].dtype)
                off += e
        return env, new_ef

    def local_step(feed_local, state, rng):
        state_env = dict(zip(persist_names, state))
        ef_rows = [state_env[n][0] for n in ef_names]
        state_env0 = {n: state_env[n] for n in reg_names}
        found = jnp.zeros((), jnp.bool_)
        if k > 1:
            mbs = [v.reshape((k, v.shape[0] // k)
                             + tuple(v.shape[1:]))
                   for v in feed_local]

            def body(carry, xs):
                accum, found = carry
                mb, mi = xs
                env = dict(zip(feed_keys, mb))
                env.update(state_env0)
                ctx = ExecContext(
                    rng_key=jax.random.fold_in(rng, mi))
                env = run_block(block, env, ctx, stop_at=scan_end)
                accum = [a + env[gn].astype(jnp.float32)
                         for a, gn in zip(accum, grad_names)]
                if found_name is not None:
                    found = found | jnp.reshape(
                        env[found_name], ()).astype(bool)
                ys = {n: env[n] for n in carry_out}
                return (accum, found), ys

            init = ([jnp.zeros((grad_elems[gn],), jnp.float32
                               ).reshape(grad_shapes[gn])
                     for gn in grad_names],
                    jnp.zeros((), jnp.bool_))
            (accum, found), ys = jax.lax.scan(
                body, init, (mbs, jnp.arange(k)))
            env = dict(zip(feed_keys, feed_local))
            env.update(state_env0)
            env.update({n: ys[n][-1] for n in carry_out})
            for gn, a in zip(grad_names, accum):
                env[gn] = (a / k if avg_gm else a)
            scanned_ys = ys
        else:
            env = dict(zip(feed_keys, feed_local))
            env.update(state_env0)
            ctx = ExecContext(rng_key=rng)
            env = run_block(block, env, ctx, stop_at=scan_end)
            if found_name is not None:
                found = jnp.reshape(env[found_name], ()).astype(bool)
            scanned_ys = None
        env, new_ef = reduce_buckets(env, ef_rows)
        if found_name is not None:
            # one non-finite microbatch on ANY device skips the
            # whole replicated update (pmax = cross-device OR)
            found = jax.lax.pmax(found.astype(jnp.int32), axis) > 0
            env[found_name] = jnp.reshape(found, (1,))
        ctx = ExecContext(rng_key=rng)
        env = run_block(block, env, ctx, start=scan_end)
        fetches = []
        for n, mode in zip(fetch_names, fetch_modes):
            if scanned_ys is not None and n in scanned_ys \
                    and n not in post_outs:
                stacked = scanned_ys[n]
                if jnp.issubdtype(stacked.dtype, jnp.inexact):
                    val = jnp.mean(stacked.astype(jnp.float32),
                                   axis=0).astype(stacked.dtype)
                else:
                    val = stacked[-1]
            else:
                val = env[n]
            if mode == "pmean" and jnp.issubdtype(
                    jnp.asarray(val).dtype, jnp.inexact):
                val = jax.lax.pmean(
                    val.astype(jnp.float32), axis).astype(val.dtype)
            fetches.append(val)
        new_state = []
        ef_iter = iter(new_ef)
        for n, s in zip(persist_names, state):
            if n in ef_set:
                new_state.append(next(ef_iter)[None, :]
                                 if ef else s)
            else:
                new_state.append(env.get(n, s))
        return fetches, new_state

    sharded = shard_map_nocheck(local_step, mesh, in_specs,
                                out_specs)

    def step(feed_vals, state, rng):
        return sharded(feed_vals, state, rng)

    return step


# ---------------------------------------------------------------------------
# zero kind (ISSUE 18: ZeRO-2/3 sharded optimizer states riding the
# engaged comm plan) + its eligibility gate, state layout and flip-back
# ---------------------------------------------------------------------------

# optimizer ops that run on a (chunk,) shard. sgd/momentum/adam are
# ELEMENTWISE, so they commute with the concat/pad/chunk reshuffle
# unchanged and a bucket's update IS the op's kernel. lamb runs the
# TWO-PHASE trust plan of :func:`chunk_update`: per-chunk partial
# per-param sq-norms -> one tiny psum over the dp axis -> the
# elementwise finish consumes the global norms — so its
# global-param-norm trust ratio does not block sharding (it is
# tolerance-parity vs the unsharded op: the norm sum reassociates
# across devices).
ZERO_OPT_OPS = ("sgd", "momentum", "adam", "lamb")

# per-op state slots that shard into (g, chunk) rows, and the scalar
# accumulators that stay replicated per-var (the bucket's update
# returns them through the op's own gated Beta*PowOut rule)
_ZERO_ROLES = {"sgd": (), "momentum": ("Velocity",),
               "adam": ("Moment1", "Moment2"),
               "lamb": ("Moment1", "Moment2")}
_ZERO_SCALARS = {"sgd": (), "momentum": (),
                 "adam": ("Beta1Pow", "Beta2Pow"),
                 "lamb": ("Beta1Pow", "Beta2Pow")}


def _chunk_segments(param_elems, position, c):
    """Per-element segment ids of a (c,) chunk inside the bucket's
    padded concat buffer: element j of param i maps to segment i, the
    padding tail to the sentinel segment len(param_elems)."""
    ends = np.cumsum(np.asarray(param_elems, np.int64))
    pos = position + jnp.arange(c, dtype=jnp.int32)
    return jnp.searchsorted(jnp.asarray(ends, jnp.int32), pos,
                            side="right")


def chunk_update(op_type, ins, attrs, ctx, *, axis=None, param_elems=None,
                 position=None):
    """One ZeRO bucket's per-device (chunk,) update.

    sgd/momentum/adam are elementwise-closed on the chunk — they ARE
    the op's own kernel. lamb needs the per-param trust ratio, a
    GLOBAL norm over buffers this device only holds 1/g of — the
    two-phase plan:

    1. segment the chunk by ``param_elems`` (static per-param element
       counts; ``position`` is this device's traced flat offset) and
       reduce per-segment partial sq-norms of the param chunk and the
       lamb ``r`` numerator (``kernels._lamb_moments``, the op's own
       elementwise phase)
    2. one tiny ``lax.psum`` of the two (n_params+1,) partials over
       ``axis`` (None: the chunk is the whole bucket) -> global
       per-param norms -> per-element trust gathered back through the
       segment ids -> elementwise finish.

    Parity vs the unsharded lamb op is TOLERANCE, not bitwise: the
    sq-norm sum reassociates across devices (documented; the ZeRO
    parity gate is the same amp-style loss tolerance the int8 ring
    uses)."""
    if op_type != "lamb":
        return KERNELS[op_type](ins, attrs, ctx)

    p = ins["Param"][0].reshape(-1)
    m = ins["Moment1"][0].reshape(-1)
    v = ins["Moment2"][0].reshape(-1)
    lr = ins["LearningRate"][0].reshape(())
    m_new, v_new, r, b1p_new, b2p_new = _lamb_moments(
        p, ins["Grad"][0].reshape(-1), m, v,
        ins["Beta1Pow"][0].reshape(()), ins["Beta2Pow"][0].reshape(()),
        attrs)

    n_seg = len(param_elems) + 1
    seg = _chunk_segments(param_elems, position, p.shape[0])
    sq_p = jax.ops.segment_sum(p * p, seg, num_segments=n_seg)
    sq_r = jax.ops.segment_sum(r * r, seg, num_segments=n_seg)
    if axis is not None:
        sq_p = jax.lax.psum(sq_p, axis)
        sq_r = jax.lax.psum(sq_r, axis)
    p_norm = jnp.sqrt(sq_p)
    r_norm = jnp.sqrt(sq_r)
    trust = jnp.where((p_norm > 0) & (r_norm > 0),
                      p_norm / jnp.where(r_norm > 0, r_norm, 1.0), 1.0)
    p_new = p - lr * trust[seg] * r
    return _gate_update(
        {**ins, "Param": [p], "Moment1": [m], "Moment2": [v]},
        {"ParamOut": [p_new], "Moment1Out": [m_new],
         "Moment2Out": [v_new], "Beta1PowOut": [b1p_new],
         "Beta2PowOut": [b2p_new]})


def _zero_row_sources(stage, bucket):
    """role -> source var names for one bucket's sharded rows (params
    join the rows at stage 3)."""
    src = {role: names for role, names in bucket["roles"].items()}
    if stage >= 3:
        src["Param"] = bucket["params"]
    return src


def zero_eligibility(program, block, zero, comm, comm_plan, shard_cfg,
                     gm, pp, fetch_names, memo=None, bump=None):
    """Gate + plan for ZeRO-2/3 sharded optimizer states.

    Returns ``(key, result)`` where ``result`` is the zero_plan dict
    when eligible, else None after bumping the ``zero.xla`` dispatch
    counter with the reason (the same counted-fallback pattern as
    :func:`comm_eligibility` — the replicated comm/GSPMD step is the
    fallback). Pass the previous return as ``memo`` for the warm path.

    ZeRO rides the ENGAGED quantized comm plan: the bucketed all-reduce
    decomposes into reduce-scatter + all-gather and the optimizer
    region collapses to one update per bucket on
    this device's (chunk,) shard. Eligible means: the comm plan is
    engaged, every bucket's params are updated by allowlisted
    chunk-shardable optimizer ops (:data:`ZERO_OPT_OPS`; lamb via the
    two-phase trust-ratio plan) with ONE uniform
    type/attrs/lr/gate per bucket (the bucket's update synthesizes a
    single op), params and grads are f32 (a chunked f32 update of a bf16
    param would drift from the reference kernel's native-dtype math),
    no surviving post-region op reads the merged gradient / sharded
    moments / stage-3 params (never materialized), and no fetch asks
    for absorbed state."""
    from ..ops.pallas.counters import bump as _bump
    from ..parallel.collectives import padded_len

    if bump is None:
        bump = _bump
    key = (program._version, zero, comm, comm_plan is not None,
           shard_cfg, gm, pp, tuple(fetch_names))
    if memo is not None and memo[0] == key:
        return memo

    def verdict(result, reason=None):
        if result is None:
            bump("zero", "xla", reason)
        else:
            bump("zero", "zero")
        return (key, result)

    if comm_plan is None:
        return verdict(None, "zero_stage set but the quantized comm "
                             "plan is not engaged — ZeRO rides its "
                             "bucketed ring (set comm_quant; the "
                             "quant_allreduce.xla counter has that "
                             "refusal)")
    axis, g, cplan = comm_plan
    ops = block.ops
    bwd_idx = next((i for i, op in enumerate(ops)
                    if op.type == "backward"), None)
    if bwd_idx is None:
        return verdict(None, "no backward op")
    scan_end = bwd_idx + 1
    if scan_end < len(ops) and \
            ops[scan_end].type == "check_finite_and_unscale":
        scan_end += 1
    bwd = ops[bwd_idx]
    g2p = dict(zip(bwd.outputs.get("Grads", ()),
                   bwd.inputs.get("Params", ())))
    opt_at = {}
    for i in range(scan_end, len(ops)):
        op = ops[i]
        pn = op.inputs.get("Param")
        if pn and op.inputs.get("Grad"):
            opt_at[pn[0]] = (i, op)

    def _f32(name):
        v = block.vars.get(name)
        return v is not None and jnp.dtype(
            dtype_mod.convert_dtype(v.dtype)) == jnp.float32

    buckets = []
    absorbed: List[str] = []
    replaced: set = set()
    for bi, b in enumerate(cplan):
        params, idxs = [], []
        sig = None
        for gn in b["grads"]:
            pn = g2p.get(gn)
            if pn is None or pn not in opt_at:
                return verdict(None, f"param for grad {gn!r} has no "
                                     "optimizer op in the update "
                                     "region")
            i, op = opt_at[pn]
            if op.type not in ZERO_OPT_OPS:
                return verdict(None, f"optimizer {op.type!r} is not "
                                     "chunk-shardable; allowlist: "
                                     f"{ZERO_OPT_OPS}")
            if not _f32(pn) or not _f32(gn):
                return verdict(None, f"param/grad for {pn!r} is not "
                                     "f32 — the chunked f32 update "
                                     "would drift from the reference "
                                     "kernel's native-dtype math")
            lr = op.inputs.get("LearningRate")
            if not lr:
                return verdict(None, f"{op.type} op for {pn!r} has "
                                     "no LearningRate input")
            attrs = {a: v for a, v in sorted(op.attrs.items())
                     if not a.startswith("__")}
            s = (op.type, repr(attrs), lr[0],
                 op.inputs.get("FoundInfinite", [None])[0])
            if sig is None:
                sig = s
            elif s != sig:
                return verdict(None, f"mixed optimizer configs inside "
                                     f"comm bucket {bi} — the "
                                     "chunk update needs one uniform "
                                     "type/attrs/lr per bucket")
            params.append(pn)
            idxs.append(i)
        op0 = ops[idxs[0]]
        roles = {r: [ops[i].inputs[r][0] for i in idxs]
                 for r in _ZERO_ROLES[op0.type]}
        scalars = {r: [ops[i].inputs[r][0] for i in idxs]
                   for r in _ZERO_SCALARS[op0.type]}
        padded = padded_len(b["elems"], g)
        shapes = [tuple(int(d) for d in (block.vars[pn].shape or ()))
                  for pn in params]
        buckets.append({
            "grads": list(b["grads"]), "params": params,
            "elems": int(b["elems"]), "padded": int(padded),
            "chunk": int(padded) // g, "op_type": op0.type,
            "attrs": dict(op0.attrs), "lr": sig[2], "found": sig[3],
            "roles": roles, "scalars": scalars,
            "op_idxs": sorted(idxs), "param_shapes": shapes,
        })
        replaced.update(idxs)
        for names in roles.values():
            absorbed.extend(names)
        if zero >= 3:
            absorbed.extend(params)
    grads_all = set(g2p)
    moments_all = {n for b_ in buckets
                   for ns in b_["roles"].values() for n in ns}
    params_s3 = set(g2p.values()) if zero >= 3 else set()
    for i in range(scan_end, len(ops)):
        if i in replaced:
            continue
        reads = {n for ns in ops[i].inputs.values() for n in ns}
        for bad, what in ((reads & grads_all, "the merged gradient"),
                          (reads & moments_all,
                           "sharded optimizer state"),
                          (reads & params_s3, "stage-3 params")):
            if bad:
                return verdict(
                    None, f"post-region op {ops[i].type!r} reads "
                          f"{what} ({sorted(bad)[:2]}) which is never "
                          f"materialized under zero_stage={zero}")
    bad = set(fetch_names) & set(absorbed)
    if bad:
        return verdict(None, f"fetch of sharded state "
                             f"{sorted(bad)[:2]} under "
                             f"zero_stage={zero}")
    rep = sh = 0
    for b_ in buckets:
        nrows = len(b_["roles"]) + (1 if zero >= 3 else 0)
        rep += b_["elems"] * 4 * nrows
        sh += b_["chunk"] * 4 * nrows
    plan = {"stage": int(zero), "axis": axis, "group": int(g),
            "buckets": buckets, "scan_end": scan_end,
            "absorbed": tuple(sorted(set(absorbed))),
            "bytes_replicated": int(rep), "bytes_sharded": int(sh)}
    return verdict(plan)


def zero_state_layout(zero_plan):
    """``[(row_name, role, bucket_idx, (g, chunk))]`` — the donated
    state rows the plan owns. Row storage is RING-PLACED: row r holds
    flat chunk ``(r+1) % g`` of the bucket's padded concat buffer, so
    device r's local row lines up exactly with the reduced chunk
    :func:`parallel.collectives.reduce_scatter` hands it (no extra
    permute hop per step; flip-back un-rolls once)."""
    g = zero_plan["group"]
    out = []
    for i, b in enumerate(zero_plan["buckets"]):
        for role in _zero_row_sources(zero_plan["stage"], b):
            out.append((f"__zero_{role.lower()}_{i}", role, i,
                        (g, b["chunk"])))
    return out


def ensure_zero_state(scope, zero_plan, shard_cfg, sharding):
    """Materialize the sharded state rows as DONATED executor state:
    one ``(g, chunk)`` f32 row buffer per (bucket, role), sharded
    ``P(axis, None)`` so each device owns its row. Existing per-var
    state (warm start: momentum already accumulated, adam moments
    mid-run) is ABSORBED — concat, pad, ring-roll — and the per-var
    scope entries are cleared so they drop out of persist_names; the
    ``__zero_layout__`` scope marker (not a block var, never persisted)
    records enough to :func:`zero_flip_back` when ZeRO turns off.
    Returns ``(added_names, dropped_names)``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import mesh_for_shape

    g = zero_plan["group"]
    mesh = mesh_for_shape(dict(shard_cfg[0]))
    shard = NamedSharding(mesh, PartitionSpec(zero_plan["axis"], None))
    peek = getattr(scope, "_peek", scope.find_var)
    write_back = getattr(scope, "_write_back", scope.set)
    added = []
    for i, b in enumerate(zero_plan["buckets"]):
        for role, names in _zero_row_sources(zero_plan["stage"],
                                             b).items():
            rn = f"__zero_{role.lower()}_{i}"
            arr = peek(rn)
            if not isinstance(arr, jax.Array) or \
                    tuple(arr.shape) != (g, b["chunk"]):
                flats = []
                for n, shp in zip(names, b["param_shapes"]):
                    v = peek(n)
                    flats.append(
                        np.zeros(int(np.prod(shp or (1,))), np.float32)
                        if v is None
                        else np.asarray(v, np.float32).reshape(-1))
                flat = np.concatenate(flats) if len(flats) > 1 \
                    else flats[0]
                flat = np.pad(flat, (0, b["padded"] - flat.size))
                rows = np.roll(flat.reshape(g, b["chunk"]), -1, axis=0)
                arr = jax.device_put(rows, shard)
                write_back(rn, arr)
            sharding[rn] = shard
            added.append(rn)
    for n in zero_plan["absorbed"]:
        if peek(n) is not None:
            write_back(n, None)
    write_back("__zero_layout__", {
        "stage": zero_plan["stage"], "group": g,
        "buckets": [{"roles": dict(b["roles"]), "params": b["params"],
                     "param_shapes": b["param_shapes"],
                     "elems": b["elems"], "chunk": b["chunk"]}
                    for b in zero_plan["buckets"]]})
    return added, set(zero_plan["absorbed"])


def zero_flip_back(scope):
    """Reconstruct the per-var optimizer state (and stage-3 params)
    from the sharded row buffers when ZeRO turns OFF between steps:
    un-roll the ring placement, strip the padding, split per var.
    Clears the rows and the layout marker; returns the restored names
    (the executor splices them back into persist_names)."""
    peek = getattr(scope, "_peek", scope.find_var)
    write_back = getattr(scope, "_write_back", scope.set)
    layout = peek("__zero_layout__")
    if not isinstance(layout, dict):
        return []
    restored = []
    for i, b in enumerate(layout["buckets"]):
        for role, names in _zero_row_sources(layout["stage"],
                                             b).items():
            rn = f"__zero_{role.lower()}_{i}"
            rows = peek(rn)
            if rows is None:
                continue
            flat = np.roll(np.asarray(rows, np.float32), 1,
                           axis=0).reshape(-1)[:b["elems"]]
            off = 0
            for n, shp in zip(names, b["param_shapes"]):
                e = int(np.prod(shp or (1,)))
                write_back(n, jnp.asarray(
                    flat[off:off + e].reshape(shp)))
                restored.append(n)
                off += e
            write_back(rn, None)
    write_back("__zero_layout__", None)
    return restored


@plan_kind("zero")
def _zero_step_fn(plan, block, feed_keys, fetch_names, persist_names,
                  feed_vals, notify):
    """The comm step with ZeRO-2/3 sharded optimizer states: the
    bucketed quantized all-reduce DECOMPOSES into its two ring halves
    and the optimizer region runs on per-device shards between them.

    Per bucket (backward-completion order, overlap preserved):

    - grads concat/pad (+error feedback) → quantized ring
      reduce-scatter: each device keeps ONLY its owned reduced f32
      chunk — the full merged gradient is never materialized (the
      ZeRO-2 gradient shard), and the optimizer consumes the chunk
      UN-quantized (one fewer encode than the all-reduce path; with
      codec='f32' the step is bitwise the replicated comm step for
      the elementwise rules — lamb is tolerance-parity: its segment
      norms psum across devices, which reassociates the sum)
    - ONE update per bucket (the op's own kernel on the chunk;
      :func:`chunk_update` for lamb) advances the param
      chunk (stage 2: sliced from the replicated param concat at the
      ring-owned position; stage 3: this device's param row) against
      the moment rows — eligibility guaranteed uniform op
      type/attrs/lr per bucket, so the synthesized call IS the op
    - stage 2: the updated param chunks all-gather RAW F32 (the codec
      applies to gradients only — sharded-update results must come
      back exact) and unpack into the replicated params; stage 3
      skips that gather entirely and the NEXT step's pre-forward
      gather serves the params
    - scalar accumulators (adam beta-pows) stay replicated per var,
      updated through the op's own gated Beta*PowOut rule
    - surviving post-region ops (lr schedules, counters) run in
      original op order around the replaced optimizer ops, each
      bucket's update firing at its first replaced index
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import (
        all_gather, quant_decode, quant_encode, reduce_scatter,
        shard_map_nocheck)
    from ..parallel.mesh import mesh_for_shape
    from .executor import run_block

    sharding = plan.sharding
    gm = plan.gm
    bwd_idx = plan.bwd_idx
    axis, g, cplan = plan.comm_plan
    codec, _bucket_bytes, ef = plan.comm
    zplan = plan.zero_plan
    stage = zplan["stage"]
    zbuckets = zplan["buckets"]
    k, avg_gm = gm if gm is not None else (1, True)
    (scan_end, grad_names, found_name, state_carry, carry_out,
     post_outs) = merge_region(block, feed_keys, feed_vals,
                               persist_names, fetch_names, 1, bwd_idx)
    mesh = mesh_for_shape({axis: g})
    ef_names = [f"__comm_ef_{i}" for i in range(len(cplan))] \
        if ef else []
    ef_set = set(ef_names)
    row_names = [rn for rn, _r, _i, _s in zero_state_layout(zplan)]
    row_set = ef_set | set(row_names)
    reg_names = [n for n in persist_names if n not in row_set]

    # locate the optimizer ops in THIS block: the plan's op_idxs refer
    # to the pre-pass program, and the IR pipeline may have shifted
    # indices — param names are the stable join key
    opt_idx = {}
    for i in range(scan_end, len(block.ops)):
        op = block.ops[i]
        pn = op.inputs.get("Param")
        if pn and op.inputs.get("Grad"):
            opt_idx[pn[0]] = i
    replaced: set = set()
    first_op = {}
    for bi, b in enumerate(zbuckets):
        idxs = [opt_idx[pn] for pn in b["params"]]
        replaced.update(idxs)
        first_op[min(idxs)] = bi

    grad_elems = {}
    grad_shapes = {}
    for gn in grad_names:
        desc = block.vars.get(gn)
        shape = tuple(int(d) for d in (desc.shape or ()))
        grad_shapes[gn] = shape
        e = 1
        for d in shape:
            e *= d
        grad_elems[gn] = e
    pdtypes = {pn: jnp.dtype(dtype_mod.convert_dtype(
        block.vars[pn].dtype))
        for b in zbuckets for pn in b["params"]}

    notify("zero_stage_active", stage)
    notify("zero_buckets", len(zbuckets))
    notify("zero_state_bytes_replicated", zplan["bytes_replicated"])
    notify("zero_state_bytes_sharded", zplan["bytes_sharded"])
    rep = zplan["bytes_replicated"]
    notify("zero_state_bytes_saved_pct",
           round(100.0 * (1.0 - zplan["bytes_sharded"] / rep), 2)
           if rep else 0.0)

    def spec_of(n):
        sh = sharding.get(n) if sharding else None
        spec = getattr(sh, "spec", None)
        return P(*spec) if spec is not None else P()

    fetch_modes = []
    for n in fetch_names:
        v = block.vars.get(n)
        shape = getattr(v, "shape", None)
        dt = str(getattr(v, "dtype", "float32"))
        if shape and (shape[0] is None or int(shape[0]) < 0):
            fetch_modes.append("gather")
        elif dt.startswith("float") or dt == "bfloat16":
            fetch_modes.append("pmean")
        else:
            fetch_modes.append("local")

    in_specs = ([spec_of(kk) for kk in feed_keys],
                [P(axis, None) if n in row_set else P()
                 for n in persist_names],
                P())
    out_specs = ([P(axis) if m == "gather" else P()
                  for m in fetch_modes],
                 [P(axis, None) if n in row_set else P()
                  for n in persist_names])

    def local_step(feed_local, state, rng):
        state_env = dict(zip(persist_names, state))
        ef_rows = [state_env[n][0] for n in ef_names]
        rows = {n: state_env[n][0] for n in row_names}
        state_env0 = {n: state_env[n] for n in reg_names}
        if stage >= 3:
            # params live only as sharded rows: all-gather raw f32
            # before the forward (the post-update gather is skipped —
            # next step's pre-forward gather serves it)
            for bi, b in enumerate(zbuckets):
                full = all_gather(rows[f"__zero_param_{bi}"], axis,
                                  codec="f32", axis_size=g)
                off = 0
                for pn, shp in zip(b["params"], b["param_shapes"]):
                    e = 1
                    for d in shp:
                        e *= d
                    state_env0[pn] = full[off:off + e].reshape(
                        shp).astype(pdtypes[pn])
                    off += e
        found = jnp.zeros((), jnp.bool_)
        if k > 1:
            mbs = [v.reshape((k, v.shape[0] // k)
                             + tuple(v.shape[1:]))
                   for v in feed_local]

            def body(carry, xs):
                accum, found = carry
                mb, mi = xs
                env = dict(zip(feed_keys, mb))
                env.update(state_env0)
                ctx = ExecContext(
                    rng_key=jax.random.fold_in(rng, mi))
                env = run_block(block, env, ctx, stop_at=scan_end)
                accum = [a + env[gn].astype(jnp.float32)
                         for a, gn in zip(accum, grad_names)]
                if found_name is not None:
                    found = found | jnp.reshape(
                        env[found_name], ()).astype(bool)
                ys = {n: env[n] for n in carry_out}
                return (accum, found), ys

            init = ([jnp.zeros((grad_elems[gn],), jnp.float32
                               ).reshape(grad_shapes[gn])
                     for gn in grad_names],
                    jnp.zeros((), jnp.bool_))
            (accum, found), ys = jax.lax.scan(
                body, init, (mbs, jnp.arange(k)))
            env = dict(zip(feed_keys, feed_local))
            env.update(state_env0)
            env.update({n: ys[n][-1] for n in carry_out})
            for gn, a in zip(grad_names, accum):
                env[gn] = (a / k if avg_gm else a)
            scanned_ys = ys
        else:
            env = dict(zip(feed_keys, feed_local))
            env.update(state_env0)
            ctx = ExecContext(rng_key=rng)
            env = run_block(block, env, ctx, stop_at=scan_end)
            if found_name is not None:
                found = jnp.reshape(env[found_name], ()).astype(bool)
            scanned_ys = None
        # bucketed quantized ring reduce-scatter, overlap-emitted:
        # each device keeps only its owned reduced f32 chunk
        mine_chunks, new_ef = [], []
        for i, b in enumerate(zbuckets):
            flats = [env[gn].astype(jnp.float32).reshape(-1)
                     for gn in b["grads"]]
            flat = flats[0] if len(flats) == 1 else \
                jnp.concatenate(flats)
            if b["padded"] != flat.shape[0]:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((b["padded"] - flat.shape[0],),
                                     jnp.float32)])
            if ef:
                flat = flat + ef_rows[i]
                q, sc = quant_encode(flat, codec)
                dec = quant_decode(q, sc, codec)
                new_ef.append(flat - dec)
                flat = dec
            mine_chunks.append(reduce_scatter(
                flat, axis, codec=codec, axis_size=g, avg=True))
        if found_name is not None:
            found = jax.lax.pmax(found.astype(jnp.int32), axis) > 0
            env[found_name] = jnp.reshape(found, (1,))
        ctx = ExecContext(rng_key=rng)
        idx = jax.lax.axis_index(axis)
        new_rows = {}

        def apply_bucket(bi):
            b = zbuckets[bi]
            c = b["chunk"]
            if stage >= 3:
                p_chunk = rows[f"__zero_param_{bi}"]
            else:
                flats = [env[pn].astype(jnp.float32).reshape(-1)
                         for pn in b["params"]]
                flat = flats[0] if len(flats) == 1 else \
                    jnp.concatenate(flats)
                if b["padded"] != flat.shape[0]:
                    flat = jnp.concatenate(
                        [flat,
                         jnp.zeros((b["padded"] - flat.shape[0],),
                                   jnp.float32)])
                p_chunk = jax.lax.dynamic_slice(
                    flat, (jnp.mod(idx + 1, g) * c,), (c,))
            ins = {"Param": [p_chunk], "Grad": [mine_chunks[bi]],
                   "LearningRate": [env[b["lr"]]]}
            for role in b["roles"]:
                ins[role] = [rows[f"__zero_{role.lower()}_{bi}"]]
            for srole, names in b["scalars"].items():
                ins[srole] = [env[names[0]]]
            if b["found"] is not None:
                ins["FoundInfinite"] = [env[b["found"]]]
            # lamb threads the per-param element layout + this
            # device's ring position so its two-phase trust plan can
            # psum the segment norms over the dp axis
            outs = chunk_update(
                b["op_type"], ins, b["attrs"], ctx, axis=axis,
                param_elems=tuple(
                    int(np.prod(shp or (1,)))
                    for shp in b["param_shapes"]),
                position=jnp.mod(idx + 1, g) * c)
            for role in b["roles"]:
                new_rows[f"__zero_{role.lower()}_{bi}"] = \
                    outs[role + "Out"][0]
            for srole, names in b["scalars"].items():
                val = outs[srole + "Out"][0]
                for n in names:
                    env[n] = val
            new_p = outs["ParamOut"][0]
            if stage >= 3:
                new_rows[f"__zero_param_{bi}"] = new_p
            else:
                # raw f32 gather: codec applies to gradients only —
                # sharded-update results must come back exact
                full = all_gather(new_p, axis, codec="f32",
                                  axis_size=g)
                off = 0
                for pn in b["params"]:
                    old = env[pn]
                    e = old.size
                    env[pn] = full[off:off + e].reshape(
                        old.shape).astype(old.dtype)
                    off += e

        i = scan_end
        n_ops = len(block.ops)
        while i < n_ops:
            bi = first_op.get(i)
            if bi is not None:
                apply_bucket(bi)
            if i not in replaced:
                env = run_block(block, env, ctx, start=i,
                                stop_at=i + 1)
            i += 1
        fetches = []
        for n, mode in zip(fetch_names, fetch_modes):
            if scanned_ys is not None and n in scanned_ys \
                    and n not in post_outs:
                stacked = scanned_ys[n]
                if jnp.issubdtype(stacked.dtype, jnp.inexact):
                    val = jnp.mean(stacked.astype(jnp.float32),
                                   axis=0).astype(stacked.dtype)
                else:
                    val = stacked[-1]
            else:
                val = env[n]
            if mode == "pmean" and jnp.issubdtype(
                    jnp.asarray(val).dtype, jnp.inexact):
                val = jax.lax.pmean(
                    val.astype(jnp.float32), axis).astype(val.dtype)
            fetches.append(val)
        new_state = []
        ef_iter = iter(new_ef)
        for n, s in zip(persist_names, state):
            if n in new_rows:
                new_state.append(new_rows[n][None, :])
            elif n in ef_set:
                new_state.append(next(ef_iter)[None, :]
                                 if ef else s)
            else:
                new_state.append(env.get(n, s))
        return fetches, new_state

    sharded = shard_map_nocheck(local_step, mesh, in_specs,
                                out_specs)

    def step(feed_vals, state, rng):
        return sharded(feed_vals, state, rng)

    return step
