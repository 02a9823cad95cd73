"""Program-IR pass pipeline: numeric parity, op-count reduction, knob
matrix, content-addressed + disk-persistent compile caching.

Every pass must be a *bitwise* no-op on the fetched values: the
unoptimized and optimized program run from identical state and must
fetch identical bytes (passes rewrite the graph, never the numerics).
The RNG-slot stamp makes that hold even for dropout/random ops when
earlier ops are removed.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import passes as passes_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_KNOBS = ("fuse_elewise_add_act_ops", "memory_optimize",
             "enable_inplace", "constant_folding", "cse")


def _strategy(**on):
    bs = static.BuildStrategy()
    for k in ALL_KNOBS:
        setattr(bs, k, bool(on.get(k, False)))
    return bs


def _train_program(seed=1234):
    """Training program with food for every pass: fusable fc+relu, a
    scale-by-1, duplicate subexpressions, an all-constant chain, and a
    dead branch."""
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = static.data("x", [-1, 8])
        label = static.data("label", [-1, 1], dtype="int64")
        h = static.nn.fc(x, 16, act="relu")
        h = static.scale(h, scale=1.0)
        a = static.reduce_mean(h, dim=[1], keep_dim=True)
        b = static.reduce_mean(h, dim=[1], keep_dim=True)
        h = static.elementwise_add(static.elementwise_sub(h, a),
                                   static.elementwise_sub(h, b))
        c = static.elementwise_mul(
            static.fill_constant([1], "float32", 0.25),
            static.fill_constant([1], "float32", 2.0))
        h = static.elementwise_mul(h, c)
        static.nn.fc(h, 3)  # dead branch: output never fetched
        logits = static.nn.fc(h, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label))
        static.SGD(0.05).minimize(loss)
    return main, startup, loss


def _feed(n=8):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(n, 8).astype(np.float32),
            "label": rng.randint(0, 4, (n, 1)).astype(np.int64)}


def _run_leg(strategy, steps=3):
    """Fresh scope + executor: run the training program `steps` times
    under `strategy`, return (loss bytes, exe counters)."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = static.Executor()
        exe.run(startup)
        cp = static.CompiledProgram(main, build_strategy=strategy)
        feed = _feed()
        out = [exe.run(cp, feed=feed, fetch_list=[loss])[0]
               for _ in range(steps)]
        return (b"".join(np.ravel(v).tobytes() for v in out),
                dict(exe.counters))


# ---------------------------------------------------------------------------
# per-pass parity + reduction (the BuildStrategy knob on/off matrix)
# ---------------------------------------------------------------------------
BASELINE = None


def _baseline():
    global BASELINE
    if BASELINE is None:
        BASELINE = _run_leg(_strategy())  # all knobs off
    return BASELINE


@pytest.mark.parametrize("knob,reduces", [
    ("constant_folding", True),
    ("enable_inplace", True),
    ("fuse_elewise_add_act_ops", True),
    ("memory_optimize", True),
    # CSE is restricted to post-backward ops on training graphs (merging
    # upstream restructures vjp accumulation — bitwise hazard), so it
    # removes nothing here; its reduction is covered on the inference
    # program below
    ("cse", False),
])
def test_single_pass_parity_and_reduction(knob, reduces):
    base_bytes, _ = _baseline()
    leg_bytes, counters = _run_leg(_strategy(**{knob: True}))
    assert leg_bytes == base_bytes, f"{knob}: fetches not bitwise equal"
    before = counters.get("ir_ops_before", 0)
    after = counters.get("ir_ops_after", 0)
    if reduces:
        assert after < before, f"{knob}: expected op-count reduction"
    else:
        assert after == before


def test_all_passes_parity_and_reduction():
    base_bytes, base_counters = _baseline()
    leg_bytes, counters = _run_leg(_strategy(
        **{k: True for k in ALL_KNOBS}))
    assert leg_bytes == base_bytes
    assert counters["ir_ops_after"] < counters["ir_ops_before"]
    # pipeline time + AOT trace/compile split are measured
    assert counters.get("ir_pass_ms", 0) > 0
    assert counters.get("trace_ms", 0) > 0
    assert counters.get("compile_ms", 0) > 0
    # the all-off leg must not report a reduction
    assert base_counters["ir_ops_after"] == base_counters["ir_ops_before"]


def test_knob_matrix_selects_passes():
    main, _, loss = _train_program()
    for knob, pass_name in [
            ("constant_folding", "constant_folding"),
            ("enable_inplace", "elide_identities"),
            ("cse", "cse"),
            ("fuse_elewise_add_act_ops", "fuse_elemwise_act"),
            ("memory_optimize", "dead_code_elimination")]:
        _, report = passes_mod.apply_passes(
            main, ["x", "label"], [loss.name], _strategy(**{knob: True}))
        ran = {s.name for s in report.stats}
        assert pass_name in ran, (knob, ran)
        others = set(dict([
            ("constant_folding", "constant_folding"),
            ("enable_inplace", "elide_identities"),
            ("cse", "cse"),
            ("fuse_elewise_add_act_ops", "fuse_elemwise_act"),
            ("memory_optimize", "dead_code_elimination")]).values()) - {
                pass_name}
        assert not (ran & others), (knob, ran)


def test_pipeline_env_escape(monkeypatch):
    monkeypatch.setenv("PADDLE_IR_PASSES", "0")
    main, _, loss = _train_program()
    opt, report = passes_mod.apply_passes(
        main, ["x", "label"], [loss.name],
        _strategy(**{k: True for k in ALL_KNOBS}))
    assert opt is main  # untouched original
    assert report.removed == 0 and not report.stats


# ---------------------------------------------------------------------------
# CSE on an inference graph (no backward op -> full-block merging)
# ---------------------------------------------------------------------------
def test_cse_merges_on_inference_program():
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [-1, 8])
        a = static.reduce_mean(x, dim=[1], keep_dim=True)
        b = static.reduce_mean(x, dim=[1], keep_dim=True)
        out = static.elementwise_add(a, b)
    opt, report = passes_mod.apply_passes(
        main, ["x"], [out.name], _strategy(cse=True))
    assert report.removed >= 1
    feed = {"x": np.random.RandomState(0).randn(4, 8).astype(np.float32)}
    exe = static.Executor()
    r_opt = exe.run(static.CompiledProgram(
        main, build_strategy=_strategy(cse=True)),
        feed=feed, fetch_list=[out])[0]
    r_off = exe.run(static.CompiledProgram(
        main, build_strategy=_strategy()),
        feed=feed, fetch_list=[out])[0]
    assert r_opt.tobytes() == r_off.tobytes()


# ---------------------------------------------------------------------------
# RNG stability: removing ops must not shift a surviving dropout's mask
# ---------------------------------------------------------------------------
def test_random_op_stream_stable_under_dce():
    main = static.Program()
    main.random_seed = 77
    with static.program_guard(main):
        x = static.data("x", [-1, 8])
        static.scale(x, scale=2.0)      # dead op BEFORE the dropout
        h = static.dropout(x, dropout_prob=0.5)
        out = static.reduce_mean(h)
    feed = {"x": np.ones((4, 8), np.float32)}
    legs = {}
    for mode, bs in (("off", _strategy()),
                     ("on", _strategy(memory_optimize=True))):
        exe = static.Executor()
        legs[mode] = exe.run(static.CompiledProgram(main, build_strategy=bs),
                             feed=feed, fetch_list=[out])[0]
        if mode == "on":
            assert exe.counters["ir_ops_after"] < \
                exe.counters["ir_ops_before"]
    assert legs["on"].tobytes() == legs["off"].tobytes(), \
        "dropout mask shifted: __rng_slot stamping broken"


# ---------------------------------------------------------------------------
# fusion details
# ---------------------------------------------------------------------------
def test_fusion_emits_fused_op_and_matches():
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [-1, 6])
        y = static.data("y", [-1, 6])
        out = static.relu(static.elementwise_add(x, y))
    opt, report = passes_mod.apply_passes(
        main, ["x", "y"], [out.name],
        _strategy(fuse_elewise_add_act_ops=True))
    types = [op.type for op in opt.global_block.ops]
    assert "fused_elemwise_activation" in types
    assert "relu" not in types and "elementwise_add" not in types
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(3, 6).astype(np.float32),
            "y": rng.randn(3, 6).astype(np.float32)}
    exe = static.Executor()
    fused = exe.run(static.CompiledProgram(
        main, build_strategy=_strategy(fuse_elewise_add_act_ops=True)),
        feed=feed, fetch_list=[out])[0]
    np.testing.assert_array_equal(
        fused, np.maximum(feed["x"] + feed["y"], 0.0))


def test_fusion_skips_multi_consumer_intermediate():
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [-1, 6])
        y = static.data("y", [-1, 6])
        s = static.elementwise_add(x, y)
        r = static.relu(s)
        out = static.elementwise_add(r, s)  # s consumed twice
    opt, _ = passes_mod.apply_passes(
        main, ["x", "y"], [out.name],
        _strategy(fuse_elewise_add_act_ops=True))
    assert "fused_elemwise_activation" not in [
        op.type for op in opt.global_block.ops]


# ---------------------------------------------------------------------------
# identity elision corner: a protected (fetched) scale-by-1 stays
# ---------------------------------------------------------------------------
def test_elide_keeps_fetched_identity():
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [-1, 4])
        out = static.scale(x, scale=1.0)
    opt, report = passes_mod.apply_passes(
        main, ["x"], [out.name], _strategy(enable_inplace=True))
    assert [op.type for op in opt.global_block.ops] == ["scale"]
    feed = {"x": np.arange(8, dtype=np.float32).reshape(2, 4)}
    exe = static.Executor()
    got = exe.run(static.CompiledProgram(
        main, build_strategy=_strategy(enable_inplace=True)),
        feed=feed, fetch_list=[out])[0]
    np.testing.assert_array_equal(got, feed["x"])


# ---------------------------------------------------------------------------
# name reassignment: aliasing through a multiply-defined name is invalid
# (this IR allows reassignment — legacy_flow assign-into-loop-var)
# ---------------------------------------------------------------------------
def _reassign_program(dup_fill):
    """ops: a=1.0; (b=a | b=1.0); a=2.0; out=b+a — correct fetch 3.0.
    A stale alias b->a would compute a+a = 4.0."""
    from paddle_tpu.static.ir import OpDesc

    main = static.Program()
    blk = main.global_block
    blk.create_var(name="a", shape=[1], dtype="float32")
    blk.create_var(name="b", shape=[1], dtype="float32")
    blk.create_var(name="out", shape=[1], dtype="float32")
    fill = {"shape": [1], "dtype": "float32"}
    blk.ops.append(OpDesc("fill_constant", {}, {"Out": ["a"]},
                          dict(fill, value=1.0)))
    if dup_fill:   # CSE bait: identical to the first fill
        blk.ops.append(OpDesc("fill_constant", {}, {"Out": ["b"]},
                              dict(fill, value=1.0)))
    else:          # elision bait: b aliases a
        blk.ops.append(OpDesc("assign", {"X": ["a"]}, {"Out": ["b"]}, {}))
    blk.ops.append(OpDesc("fill_constant", {}, {"Out": ["a"]},
                          dict(fill, value=2.0)))
    blk.ops.append(OpDesc("elementwise_add", {"X": ["b"], "Y": ["a"]},
                          {"Out": ["out"]}, {}))
    return main


@pytest.mark.parametrize("dup_fill,knob", [
    (False, "enable_inplace"),   # assign elision across reassignment
    (True, "cse"),               # fill merge across reassignment
    (True, "constant_folding"),  # folding must track reassignment too
])
def test_reassigned_name_not_aliased(dup_fill, knob):
    main = _reassign_program(dup_fill)
    exe = static.Executor()
    got = exe.run(static.CompiledProgram(
        main, build_strategy=_strategy(**{knob: True})),
        feed={}, fetch_list=["out"])[0]
    assert float(got[0]) == 3.0, \
        f"{knob}: stale alias across name reassignment (got {got})"


# ---------------------------------------------------------------------------
# weak-typed state: same shape/dtype, different aval -> recompile, not
# an AOT input-mismatch crash
# ---------------------------------------------------------------------------
def test_weak_typed_state_recompiles():
    import jax.numpy as jnp

    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [-1, 4])
            w = main.global_block.create_var(
                name="gain", shape=[], dtype="float32", persistable=True)
            out = static.elementwise_mul(static.reduce_mean(x), w)
        exe = static.Executor()
        feed = {"x": np.ones((2, 4), np.float32)}
        scope.set("gain", jnp.asarray(np.float32(2.0)))  # strong-typed
        r1 = exe.run(main, feed=feed, fetch_list=[out])[0]
        scope.set("gain", jnp.asarray(3.0))              # weak-typed
        r2 = exe.run(main, feed=feed, fetch_list=[out])[0]
        assert float(r1[()]) == 2.0 and float(r2[()]) == 3.0


# ---------------------------------------------------------------------------
# content-addressed executable cache
# ---------------------------------------------------------------------------
def test_clone_hits_compile_cache():
    """Satellite regression: Program.clone() used to recompile (identity
    -keyed cache); the content hash must hit."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        exe.run(main, feed=feed, fetch_list=[loss])
        misses0 = exe.counters["compile_cache_misses"]
        exe.run(main.clone(), feed=feed, fetch_list=[loss])
        assert exe.counters["compile_cache_misses"] == misses0
        assert exe.counters["compile_cache_hits"] >= 1


def test_deserialized_program_hits_compile_cache():
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        exe.run(main, feed=feed, fetch_list=[loss])
        misses0 = exe.counters["compile_cache_misses"]
        copy = static.Program.parse_from_string(
            main.serialize_to_string())
        exe.run(copy, feed=feed, fetch_list=[loss])
        assert exe.counters["compile_cache_misses"] == misses0
        hits_after_copy = exe.counters["compile_cache_hits"]
        assert hits_after_copy >= 1
        # clone(for_test=True) of an inference-only program is also
        # content-identical -> same entry
        infer = static.Program()
        with static.program_guard(infer):
            x = static.data("x", [-1, 4])
            out = static.relu(x)
        f2 = {"x": np.ones((2, 4), np.float32)}
        exe.run(infer, feed=f2, fetch_list=[out])
        m = exe.counters["compile_cache_misses"]
        exe.run(infer.clone(for_test=True), feed=f2, fetch_list=[out])
        assert exe.counters["compile_cache_misses"] == m


def test_second_executor_reuses_executable():
    """Acceptance: a second Executor in the same process compiles
    nothing for an already-built program."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        exe.run(main, feed=feed, fetch_list=[loss])
        exe2 = static.Executor()
        exe2.run(main, feed=feed, fetch_list=[loss])
        assert exe2.counters.get("compile_cache_misses", 0) == 0
        assert exe2.counters["compile_cache_hits"] == 1


# ---------------------------------------------------------------------------
# disk-persistent compile cache (fresh process resumes without compile)
# ---------------------------------------------------------------------------
_DISK_WORKER = """
import numpy as np
import paddle_tpu.static as static
main, startup = static.Program(), static.Program()
main.random_seed = 7
with static.program_guard(main, startup):
    x = static.data("x", [-1, 8])
    out = static.reduce_mean(static.nn.fc(x, 4, act="relu"))
exe = static.Executor()
exe.run(startup)
exe.run(main, feed={"x": np.ones((2, 8), np.float32)}, fetch_list=[out])
c = exe.counters
print("COUNTERS", c.get("disk_cache_hits", 0), c.get("disk_cache_misses", 0))
"""


def test_disk_cache_warm_process_hits(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "1"   # conftest turns it off
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def run():
        out = subprocess.run([sys.executable, "-c", _DISK_WORKER], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("COUNTERS")][0]
        _, hits, misses = line.split()
        return int(hits), int(misses)

    hits1, misses1 = run()
    assert misses1 > 0 and hits1 == 0, (hits1, misses1)
    hits2, misses2 = run()
    assert hits2 > 0, "fresh process did not reuse the disk cache"
    assert misses2 == 0, (hits2, misses2)


# ---------------------------------------------------------------------------
# prune: dead sub-blocks + unreferenced vars dropped, round-trip parity
# ---------------------------------------------------------------------------
def _program_with_dead_while():
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = 5
    with static.program_guard(main, startup):
        x = static.data("x", [-1, 4])
        h = static.nn.fc(x, 8, act="relu")
        i = static.fill_constant([1], "int64", 0)
        ten = static.fill_constant([1], "int64", 5)
        cond = static.less_than(i, ten)
        w = static.While(cond)
        with w.block():
            i2 = static.increment(i, value=1, in_place=False)
            static.assign(i2, i)
            static.less_than(i, ten, cond=cond)
        out = static.nn.fc(h, 2)
    return main, startup, out


def test_prune_drops_dead_subblock_and_vars():
    main, _, out = _program_with_dead_while()
    pruned = main.clone(for_test=True).prune(["x"], [out.name])
    assert len(pruned.blocks) == len(main.blocks)  # indices stable
    assert pruned.blocks[1].ops == [] and pruned.blocks[1].vars == {}
    used = set()
    for op in pruned.global_block.ops:
        used |= set(op.input_names()) | set(op.output_names())
    for name in pruned.global_block.vars:
        assert name in used or name == "x"
    assert len(pruned.serialize_to_string()) < \
        len(main.serialize_to_string())


def test_save_inference_model_roundtrip_parity(tmp_path):
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, out = _program_with_dead_while()
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).randn(3, 4).astype(
            np.float32)}
        # (clone(for_test=True) strips `increment` — an optimizer op
        # type — out of the While body, a pre-existing quirk; the live
        # program is the parity reference)
        want = exe.run(main, feed=feed, fetch_list=[out])[0]
        d = str(tmp_path / "model")
        static.save_inference_model(d, ["x"], [out], exe,
                                    main_program=main)
        prog, feed_names, fetch_vars = static.load_inference_model(d, exe)
        got = exe.run(prog, feed=feed, fetch_list=fetch_vars)[0]
        assert got.tobytes() == want.tobytes()


# ---------------------------------------------------------------------------
# drop_unused_vars shrinks the optimized program's var table
# ---------------------------------------------------------------------------
def test_unused_vars_dropped_from_optimized_program():
    main, _, loss = _train_program()
    opt, report = passes_mod.apply_passes(
        main, ["x", "label"], [loss.name],
        _strategy(**{k: True for k in ALL_KNOBS}))
    assert report.vars_dropped > 0
    assert len(opt.global_block.vars) < len(main.global_block.vars)
    # user program untouched
    assert main.global_block.ops and opt is not main


# ---------------------------------------------------------------------------
# tools/dump_passes.py smoke
# ---------------------------------------------------------------------------
def test_dump_passes_tool_demo():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dump_passes.py"),
         "--demo"], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TOTAL" in out.stdout
    assert "dead_code_elimination" in out.stdout
