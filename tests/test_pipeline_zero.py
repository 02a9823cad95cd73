"""Compiled 1F1B/interleaved pipeline schedules + ZeRO sharded
optimizer states (ISSUE 18).

The correctness story extends the GPipe gate of test_shard_pass.py:

- 1f1b and interleaved retire microbatches in the SAME ascending order
  as gpipe, so the merged gradient — and therefore the loss stream —
  matches gpipe BITWISE at S=4/M=8 (dropout included: per-microbatch
  RNG folds identically)
- the modeled bubble fraction orders gpipe > 1f1b > interleaved, and
  the executor publishes it (pp_bubble_frac gauge)
- rematerialization composes: the step recomputes its segments under
  the schedule, and the schedules stay bitwise
- the schedule joins the step AND content keys (flips recompile, never
  hit a stale executable); PADDLE_PP_SCHEDULE is the env override and
  "0"/"gpipe" the escape leg
- ZeRO-2 shards optimizer states over dp riding the engaged quantized
  comm plan: per-device state bytes collapse, the loss tracks the
  replicated comm step within the int8 gate, and the f32 codec leg is
  bitwise; every refusal lands a counted reason (zero.xla)
"""
import re

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import passes as passes_mod
from paddle_tpu.utils import unique_name


@pytest.fixture(autouse=True)
def _pin_env(monkeypatch):
    for k in ("PADDLE_IR_PASSES", "PADDLE_AMP", "PADDLE_PP_SCHEDULE",
              "PADDLE_ZERO", "PADDLE_QUANT_ALLREDUCE"):
        monkeypatch.delenv(k, raising=False)


def _deep_mlp(seed=1234, dropout=True, h=64, opt="sgd"):
    """5 fc layers -> >= 12 forward ops: pipeline_stages=4 stamps a
    true 4-stage split (the ceil op-split needs enough ops)."""
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = static.data("x", [-1, 16])
        label = static.data("label", [-1, 1], dtype="int64")
        t = static.nn.fc(x, h, act="relu")
        if dropout:
            t = static.dropout(t, dropout_prob=0.1)
        t = static.nn.fc(t, h, act="relu")
        t = static.nn.fc(t, h, act="relu")
        t = static.nn.fc(t, 16, act="relu")
        logits = static.nn.fc(t, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label))
        if opt == "adam":
            static.Adam(0.01).minimize(loss)
        elif opt == "momentum":
            static.Momentum(0.05, momentum=0.9).minimize(loss)
        else:
            static.SGD(0.05).minimize(loss)
    return main, startup, loss, [p.name for p in main.all_parameters()]


def _feed(b=16):
    rng = np.random.RandomState(3)
    return {"x": rng.randn(b, 16).astype(np.float32),
            "label": rng.randint(0, 4, (b, 1)).astype(np.int64)}


def _pp_strategy(schedule="gpipe", pp=4, k=8, remat=False,
                 interleave=2):
    bs = static.BuildStrategy()
    bs.gradient_merge_k = k
    bs.pipeline_stages = pp
    bs.pipeline_schedule = schedule
    bs.pipeline_interleave = interleave
    bs.recompute = remat
    return bs


def _run(strategy, steps=3, dropout=True, opt="sgd", b=16):
    losses, exe, scope, params = _run_exe(strategy, steps, dropout, opt, b)
    return losses, dict(exe.counters), scope, params


def _run_exe(strategy, steps=3, dropout=True, opt="sgd", b=16):
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup, loss, params = _deep_mlp(dropout=dropout,
                                                    opt=opt)
            exe = static.Executor()
            exe.run(startup)
            target = static.CompiledProgram(main,
                                            build_strategy=strategy)
            losses = [exe.run(target, feed=_feed(b), fetch_list=[loss])[0]
                      for _ in range(steps)]
            return (np.concatenate([np.ravel(x) for x in losses]),
                    exe, scope, params)


# ---------------------------------------------------------------------------
# resolve + timeline units (no executor)
# ---------------------------------------------------------------------------
def test_resolve_pipeline_schedule():
    bs = _pp_strategy("1f1b", interleave=4)
    assert passes_mod.resolve_pipeline_schedule(bs) == ("1f1b", 4)
    bs.pipeline_schedule = "nope"
    with pytest.raises(ValueError, match="pipeline_schedule"):
        passes_mod.resolve_pipeline_schedule(bs)


def test_resolve_pipeline_schedule_env(monkeypatch):
    bs = _pp_strategy("1f1b")
    monkeypatch.setenv("PADDLE_PP_SCHEDULE", "0")
    assert passes_mod.resolve_pipeline_schedule(bs)[0] == "gpipe"
    monkeypatch.setenv("PADDLE_PP_SCHEDULE", "interleaved")
    assert passes_mod.resolve_pipeline_schedule(bs)[0] == "interleaved"
    monkeypatch.setenv("PADDLE_PP_SCHEDULE", "junk")
    with pytest.raises(ValueError, match="PADDLE_PP_SCHEDULE"):
        passes_mod.resolve_pipeline_schedule(bs)


def test_schedule_generators_are_dependency_valid():
    from paddle_tpu.parallel.pipeline import pipeline_timeline

    for sched, v in (("gpipe", 2), ("1f1b", 2), ("interleaved", 2)):
        S, M = 4, 8
        f_done = {}
        b_done = {}
        for t, tick in pipeline_timeline(sched, S, M, interleave=v):
            stages_this_tick = set()
            for kind, s, m in tick:
                assert s not in stages_this_tick or sched == \
                    "interleaved", (sched, t, tick)
                stages_this_tick.add(s)
                if kind == "F":
                    assert s == 0 or f_done.get((s - 1, m), -1) < t
                    f_done[(s, m)] = t
                else:
                    assert f_done.get((s, m), -1) < t
                    b_done[(s, m)] = t
        assert len(f_done) == S * M
        if sched != "gpipe":
            assert len(b_done) == S * M


def test_bubble_fractions_ordered():
    from paddle_tpu.parallel.pipeline import schedule_bubble_fraction

    g = schedule_bubble_fraction("gpipe", 4, 8)
    o = schedule_bubble_fraction("1f1b", 4, 8)
    i = schedule_bubble_fraction("interleaved", 4, 8, interleave=2)
    assert g > o > i > 0
    assert g == pytest.approx(3 / 11)
    assert o == pytest.approx(3 / 27)


# ---------------------------------------------------------------------------
# executor legs (8 forced CPU devices from conftest)
# ---------------------------------------------------------------------------
def test_1f1b_bitwise_parity_and_lower_bubble():
    gp, cg, _, _ = _run(_pp_strategy("gpipe"))
    ob, co, _, _ = _run(_pp_strategy("1f1b"))
    assert gp.tobytes() == ob.tobytes()   # ascending retirement order
    assert cg["pp_stages"] == 4 and co["pp_stages"] == 4
    assert co["pp_bubble_frac"] < cg["pp_bubble_frac"]
    assert 0 < co["pp_bubble_frac"] < 1
    assert co["pp_stash_depth"] >= 1
    # still one merged dispatch per step covering k microbatches
    assert co["gm_dispatches"] == 3 and co["gm_microbatches"] == 24


def test_interleaved_bitwise_parity_and_lowest_bubble():
    gp, cg, _, _ = _run(_pp_strategy("gpipe"))
    il, ci, _, _ = _run(_pp_strategy("interleaved"))
    assert gp.tobytes() == il.tobytes()
    assert ci["pp_bubble_frac"] < cg["pp_bubble_frac"]
    assert "pp_schedule_fallback" not in ci   # 4 stages % 2 == 0


def test_interleaved_indivisible_stages_degrades_to_1f1b():
    # pp=4 requested but interleave=3 does not divide the 4 stamped
    # stages: the plan degrades to 1f1b (counted), never refuses
    gp, _, _, _ = _run(_pp_strategy("gpipe"))
    il, ci, _, _ = _run(_pp_strategy("interleaved", interleave=3))
    assert gp.tobytes() == il.tobytes()
    assert ci["pp_schedule_fallback"] == 1
    assert ci["pp_bubble_frac"] == pytest.approx(3 / 27, abs=1e-3)


def test_1f1b_composes_with_remat():
    def scopes(exe):    # the op names of the last step it compiled
        return "\n".join(re.findall(
            r'op_name="([^"]*)"', exe._last_entry.compiled.as_text()))

    gp, _, _, _ = _run(_pp_strategy("gpipe", remat=True))
    ob, exe, _, _ = _run_exe(_pp_strategy("1f1b", remat=True))
    _, exe_plain, _, _ = _run_exe(_pp_strategy("1f1b"))
    assert gp.tobytes() == ob.tobytes()
    assert exe.counters["remat_segments"] > 1
    assert exe.counters["pp_stages"] == 4
    # remat composed: the 1f1b step recomputes its segments (a
    # differentiated jax.checkpoint's scope in the step's op names), the
    # remat-off leg holds no checkpoint. Whether that lowers memory on a
    # v5e is ROADMAP S7(xv)'s; a CPU compiler's byte count is not it.
    assert "checkpoint/rematted_computation" in scopes(exe)
    assert "checkpoint" not in scopes(exe_plain)


def test_schedule_joins_both_cache_keys():
    feed = _feed()
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup, loss, _ = _deep_mlp(dropout=False)
            exe = static.Executor()
            exe.run(startup)

            def go(schedule):
                cp = static.CompiledProgram(
                    main, build_strategy=_pp_strategy(schedule))
                exe.run(cp, feed=feed, fetch_list=[loss])

            go("gpipe")
            misses = exe.counters["compile_cache_misses"]
            go("1f1b")   # schedule flip -> fresh executable
            assert exe.counters["compile_cache_misses"] == misses + 1
            hits = exe.counters.get("compile_cache_hits", 0)
            go("1f1b")   # unchanged -> pure hit
            assert exe.counters["compile_cache_hits"] == hits + 1


def test_pp_schedule_env_escape_leg(monkeypatch):
    # strategy says 1f1b; PADDLE_PP_SCHEDULE=0 forces today's gpipe
    monkeypatch.setenv("PADDLE_PP_SCHEDULE", "0")
    ob, co, _, _ = _run(_pp_strategy("1f1b"))
    monkeypatch.delenv("PADDLE_PP_SCHEDULE")
    gp, cg, _, _ = _run(_pp_strategy("gpipe"))
    assert gp.tobytes() == ob.tobytes()
    assert co["pp_bubble_frac"] == cg["pp_bubble_frac"]
    assert "pp_stash_depth" not in co   # the gpipe generator ran


# ---------------------------------------------------------------------------
# ZeRO-2/3 sharded optimizer states on the engaged comm plan
# ---------------------------------------------------------------------------
def _dp_net(seed=77, hidden=(64, 32), opt="momentum"):
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = static.data("x", [-1, 16])
        label = static.data("label", [-1, 1], dtype="int64")
        h = x
        for w in hidden:
            h = static.nn.fc(h, w, act="relu")
        logits = static.nn.fc(h, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label))
        {"sgd": lambda: static.SGD(0.05),
         "momentum": lambda: static.Momentum(0.05, momentum=0.9),
         "adam": lambda: static.Adam(0.01),
         "lamb": lambda: static.Lamb(0.01)}[opt]().minimize(loss)
    return main, startup, loss


def _comm_bs(codec="int8", bucket_bytes=1 << 20):
    bs = static.BuildStrategy()
    bs.mesh_shape = {"dp": 8}
    bs.comm_quant = codec
    bs.comm_bucket_bytes = bucket_bytes
    return bs


def _zero_bs(codec="int8", stage=2, bucket_bytes=1 << 20):
    bs = _comm_bs(codec, bucket_bytes)
    bs.zero_stage = stage
    return bs


def _run_legs(legs, opt="momentum", steps_each=2, fetch_extra=(),
              hidden=(64, 32)):
    """Run steps_each steps per leg strategy on ONE executor+scope
    (None leg = uncompiled program). Returns (losses, exe, scope,
    main)."""
    rng = np.random.RandomState(5)
    feed = {"x": rng.randn(16, 16).astype(np.float32),
            "label": rng.randint(0, 4, (16, 1)).astype(np.int64)}
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup, loss = _dp_net(opt=opt, hidden=hidden)
            exe = static.Executor()
            exe.run(startup)
            losses = []
            for bs in legs:
                target = static.CompiledProgram(
                    main, build_strategy=bs) if bs is not None else main
                for _ in range(steps_each):
                    losses.append(float(np.ravel(exe.run(
                        target, feed=feed,
                        fetch_list=[loss] + list(fetch_extra))[0])[0]))
            return np.asarray(losses), exe, scope, main


def _peek(scope):
    return getattr(scope, "_peek", scope.find_var)


def test_zero2_int8_tracks_replicated_comm_and_shards_state():
    """Acceptance: ZeRO-2 int8 at dp=8 — loss within the 1e-2 comm
    gate of the REPLICATED comm step, per-device optimizer-state bytes
    collapse to ~1/8 (+ ring padding), moments absorbed into (g, c)
    rows."""
    from paddle_tpu.ops.pallas import counters as pk

    base, _, _, _ = _run_legs([_comm_bs("int8")] * 3, opt="adam")
    pk.reset()
    zz, exe, scope, main = _run_legs([_zero_bs("int8")] * 3, opt="adam")
    assert np.max(np.abs(base - zz)) <= 1e-2, (base, zz)
    assert pk.snapshot().get("zero.zero", 0) >= 1
    c = dict(exe.counters)
    assert c["zero_stage_active"] == 2
    assert c["zero_buckets"] == 1          # 1 MiB target: one bucket
    rep, sh = (c["zero_state_bytes_replicated"],
               c["zero_state_bytes_sharded"])
    # ~1/8th + padding: the bucket pads to g*block elems, two adam
    # moment rows -> at most 2 * 512 * 4 bytes of padding per device
    assert sh <= rep / 8 + 2 * 512 * 4
    assert c["zero_state_bytes_saved_pct"] >= 40
    # moments left the scope as per-var entries; the rows replaced them
    block = main.global_block
    m1 = [op.inputs["Moment1"][0] for op in block.ops
          if op.type == "adam"]
    assert m1 and all(_peek(scope)(n) is None for n in m1)
    rows = _peek(scope)("__zero_moment1_0")
    assert rows is not None and tuple(rows.shape)[0] == 8


def test_zero2_f32_codec_bitwise_through_absorb_and_flip_back():
    """With the f32 codec the zero step is BITWISE the replicated comm
    step: 2 comm steps -> 2 zero steps (warm-start ABSORBS the live
    velocity) -> 2 comm steps (flip-back restores it) must equal 6
    straight comm steps, and the round-trip leaves no rows behind."""
    base, _, _, _ = _run_legs([_comm_bs("f32")] * 3, opt="momentum")
    mix, _, scope, main = _run_legs(
        [_comm_bs("f32"), _zero_bs("f32"), _comm_bs("f32")],
        opt="momentum")
    assert base.tobytes() == mix.tobytes()
    block = main.global_block
    vel = [op.inputs["Velocity"][0] for op in block.ops
           if op.type == "momentum"]
    assert vel and all(_peek(scope)(n) is not None for n in vel)
    assert _peek(scope)("__zero_velocity_0") is None
    assert _peek(scope)("__zero_layout__") is None


def test_zero3_shards_params_too():
    """Stage 3: params live only as sharded rows (pre-forward raw-f32
    all-gather), still bitwise with the replicated comm leg under the
    f32 codec, and flip-back restores the params on the way out."""
    base, _, _, _ = _run_legs([_comm_bs("f32")] * 2, opt="momentum")
    z3, exe, scope, main = _run_legs([_zero_bs("f32", stage=3)] * 2,
                                     opt="momentum")
    assert base.tobytes() == z3.tobytes()
    c = dict(exe.counters)
    assert c["zero_stage_active"] == 3
    block = main.global_block
    params = [op.inputs["Param"][0] for op in block.ops
              if op.type == "momentum"]
    assert params and all(_peek(scope)(n) is None for n in params)
    assert _peek(scope)("__zero_param_0") is not None
    # saved pct climbs vs stage 2: params join the sharded rows
    assert c["zero_state_bytes_saved_pct"] >= 40
    # turning zero off restores the params for plain execution
    again, _, scope2, _ = _run_legs(
        [_zero_bs("f32", stage=3), _comm_bs("f32")], opt="momentum")
    more, _, _, _ = _run_legs([_comm_bs("f32")] * 2, opt="momentum")
    assert again.tobytes() == more.tobytes()


def test_zero_fallbacks_are_counted_with_reasons():
    """Every refusal is a counted zero.xla verdict, never a silent
    ignore or a crash: no engaged comm plan and a fetch of absorbed
    state both fall back to the replicated step."""
    from paddle_tpu.ops.pallas import counters as pk

    # zero_stage without comm_quant: comm plan not engaged, the step
    # falls back to the plain GSPMD leg (bitwise the zero-off run)
    pk.reset()
    mesh_only = static.BuildStrategy()
    mesh_only.mesh_shape = {"dp": 8}
    bs = static.BuildStrategy()
    bs.mesh_shape = {"dp": 8}
    bs.zero_stage = 2
    base, _, _, _ = _run_legs([mesh_only], opt="momentum")
    z, _, _, _ = _run_legs([bs], opt="momentum")
    assert base.tobytes() == z.tobytes()
    assert pk.snapshot().get("zero.xla", 0) >= 1
    # fetching a sharded moment cannot be served from rows
    pk.reset()
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup, loss = _dp_net(opt="momentum")
            vel = [op.inputs["Velocity"][0]
                   for op in main.global_block.ops
                   if op.type == "momentum"][0]
            exe = static.Executor()
            exe.run(startup)
            rng = np.random.RandomState(5)
            feed = {"x": rng.randn(16, 16).astype(np.float32),
                    "label": rng.randint(0, 4, (16, 1)).astype(
                        np.int64)}
            exe.run(static.CompiledProgram(
                main, build_strategy=_zero_bs("f32")),
                feed=feed, fetch_list=[loss, vel])
    assert pk.snapshot().get("zero.xla", 0) >= 1


def test_zero_lamb_two_phase_trust_engages_and_tracks():
    """lamb is chunk-shardable now (ISSUE 19): the fused kernel's
    two-phase trust plan — per-chunk partial per-param sq-norms, one
    tiny psum over dp, elementwise finish against the global norms —
    replaces PR 18's counted refusal. The sharded run ENGAGES
    (zero.zero) and tracks the replicated comm leg within the norm
    reassociation tolerance; moments shard into rows like adam's."""
    from paddle_tpu.ops.pallas import counters as pk

    base, _, _, _ = _run_legs([_comm_bs("f32")] * 2, opt="lamb")
    pk.reset()
    z, exe, scope, _ = _run_legs([_zero_bs("f32")] * 2, opt="lamb")
    assert pk.snapshot().get("zero.zero", 0) >= 1
    assert pk.snapshot().get("zero.xla", 0) == 0
    np.testing.assert_allclose(z, base, rtol=1e-5, atol=1e-6)
    assert dict(exe.counters)["zero_stage_active"] == 2
    assert _peek(scope)("__zero_moment1_0") is not None
    assert _peek(scope)("__zero_moment2_0") is not None


def test_zero_env_escape_leg(monkeypatch):
    """PADDLE_ZERO=0 with zero_stage=2 requested runs the replicated
    comm step bitwise — the ops-side pin when ZeRO misbehaves."""
    monkeypatch.setenv("PADDLE_ZERO", "0")
    esc, exe, _, _ = _run_legs([_zero_bs("f32")] * 2, opt="momentum")
    monkeypatch.delenv("PADDLE_ZERO")
    base, _, _, _ = _run_legs([_comm_bs("f32")] * 2, opt="momentum")
    assert base.tobytes() == esc.tobytes()
    assert "zero_stage_active" not in dict(exe.counters)


def test_zero_joins_compile_cache_keys():
    """Flipping zero_stage can never reuse a stale executable; the
    unchanged repeat is a pure cache hit."""
    rng = np.random.RandomState(5)
    feed = {"x": rng.randn(16, 16).astype(np.float32),
            "label": rng.randint(0, 4, (16, 1)).astype(np.int64)}
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            # hidden sizes no other test uses: the content cache is
            # process-global, a shared sha would turn the first build
            # into a hit
            main, startup, loss = _dp_net(opt="momentum",
                                          hidden=(48, 24))
            exe = static.Executor()
            exe.run(startup)

            def go(bs):
                exe.run(static.CompiledProgram(main, build_strategy=bs),
                        feed=feed, fetch_list=[loss])

            go(_comm_bs("f32"))
            misses = exe.counters.get("compile_cache_misses", 0)
            go(_zero_bs("f32"))      # zero flip -> fresh executable
            assert exe.counters.get("compile_cache_misses", 0) == \
                misses + 1
            hits = exe.counters.get("compile_cache_hits", 0)
            go(_zero_bs("f32"))      # unchanged -> pure hit
            assert exe.counters.get("compile_cache_hits", 0) == hits + 1


# ---------------------------------------------------------------------------
# cost model: schedule bubble + zero pseudo-ops (closed forms)
# ---------------------------------------------------------------------------
def test_cost_report_schedule_bubble_closed_forms():
    from paddle_tpu.static.cost_model import CostReport

    mk = lambda **kw: CostReport([], gm_k=8, pp_stages=4, **kw)
    assert mk(schedule="gpipe").pp_bubble_frac == 3 / 11
    assert mk(schedule="1f1b").pp_bubble_frac == 3 / 27
    assert mk(schedule="interleaved",
              interleave=2).pp_bubble_frac == 3 / 51
    # not pipelined -> no bubble whatever the schedule says
    assert CostReport([], gm_k=1, pp_stages=4,
                      schedule="1f1b").pp_bubble_frac == 0.0
    d = mk(schedule="1f1b", zero_stage=2).to_dict()
    assert d["pp_schedule"] == "1f1b"
    assert d["pp_bubble_frac"] == round(3 / 27, 4)
    assert d["zero_stage"] == 2


def test_cost_model_zero_splits_ring_into_rs_and_ag():
    """With the zero plan engaged the cost model replaces the single
    comm_allreduce pseudo-op with comm_reduce_scatter (encoded half
    ring) + comm_all_gather (raw f32 params) — the collectives' own
    closed forms, exactly once per step each."""
    from paddle_tpu.parallel.collectives import (all_gather_nbytes,
                                                 reduce_scatter_nbytes)
    from paddle_tpu.static.passes import comm_bucket_plan

    _losses, exe, _scope, _main = _run_legs([_zero_bs("int8")] * 2,
                                            opt="adam")
    entry = exe._last_entry
    cost = entry.cost
    assert cost, "zero leg must still be costable"
    plan = comm_bucket_plan(entry.optimized_program.global_block,
                            ("int8", 1 << 20, False), 8)
    by_type = {}
    for o in cost.ops:
        if o.type.startswith("comm_"):
            by_type.setdefault(o.type, []).append(o)
    assert "comm_allreduce" not in by_type
    (rs,) = by_type["comm_reduce_scatter"]
    (ag,) = by_type["comm_all_gather"]
    assert rs.comm_bytes == sum(
        reduce_scatter_nbytes(b["elems"], 8, "int8") for b in plan)
    assert ag.comm_bytes == sum(
        all_gather_nbytes(b["elems"], 8, "f32") for b in plan)
    # the encoded rs half is exactly half the encoded full ring
    assert rs.comm_bytes == sum(b["ring_encoded"] // 2 for b in plan)
    assert cost.to_dict()["zero_stage"] == 2
    # the dispatch counters ride the SAME rs+ag profile, under their
    # own names — a zero dispatch never bumps the quantized-ring pair
    # (the raw-f32 all-gather would break its saved>sent invariant)
    per_step = rs.comm_bytes + ag.comm_bytes
    c = dict(exe.counters)
    assert c["zero_wire_bytes_sent"] > 0
    assert c["zero_wire_bytes_sent"] % per_step == 0
    ring_f32 = sum(b["ring_f32"] for b in plan)
    steps_run = c["zero_wire_bytes_sent"] // per_step
    assert c["zero_wire_bytes_saved"] == \
        steps_run * max(0, ring_f32 - per_step)
