"""Driver-contract regression tests for bench.py, chip_smoke.py and
backend bring-up.

The contract: jax initialises in-process, once, and nothing lands on the
CPU because the platform asked for was missing — a first device touch on
an unusable platform raises, a ``TPUPlace`` without a TPU raises, a Pallas
kernel that was chosen and then fails raises, ``chip_smoke.py`` refuses
any platform but ``tpu``, and ``bench.py`` measures full shapes on a TPU
only (``BENCH_SMOKE=1`` is the explicit CPU contract run whose row fields
are pinned below). The disk compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path in the
checkout.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}{env.get('PYTHONPATH', '')}"
    env.update(extra)
    return env


def _py(src, env, timeout=180):
    return subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=timeout)


def _run_streaming(cmd, env, first_row_deadline, total_deadline):
    """Run cmd; return (rc, lines, seconds_to_first_json_line)."""
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)
    lines, first_at = [], [None]
    t0 = time.monotonic()

    def reader():
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{") and first_at[0] is None:
                first_at[0] = time.monotonic() - t0
            if line:
                lines.append(line)

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    try:
        rc = proc.wait(timeout=total_deadline)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        pytest.fail(f"bench.py exceeded {total_deadline}s; "
                    f"captured lines: {lines}")
    th.join(timeout=10)
    assert first_at[0] is not None, f"no JSON line in output: {lines}"
    assert first_at[0] < first_row_deadline, (
        f"first JSON row took {first_at[0]:.1f}s "
        f"(limit {first_row_deadline}s)")
    return rc, lines, first_at[0]


def test_bench_smoke_row_contract(tmp_path):
    """BENCH_SMOKE=1 on the CPU + tiny overrides: a parseable row in
    <60 s, rc 0, every probe's fields present."""
    captures = tmp_path / "captures.jsonl"
    env = _env(BENCH_SMOKE="1", BENCH_LAYERS="1", BENCH_BATCH="2",
               BENCH_SEQ="16", BENCH_STEPS="1", BENCH_NO_PERSIST="0",
               BENCH_CAPTURES_PATH=str(captures))
    # total deadline covers the in-process probes PLUS the multichip
    # subprocess probe (fresh interpreter + 8-virtual-device compiles)
    rc, lines, _ = _run_streaming(
        [sys.executable, BENCH], env,
        first_row_deadline=60, total_deadline=240)
    assert rc == 0
    rows = [json.loads(ln) for ln in lines if ln.startswith("{")]
    assert rows, lines
    # every measured row must leave a durable capture (ts + git sha +
    # backend), so measured numbers survive as artifacts
    caps = [json.loads(ln) for ln in
            captures.read_text().strip().splitlines()]
    assert caps, "measured row was not persisted to BENCH_CAPTURES"
    assert all(c.get("placeholder") is None for c in caps), caps
    cap = caps[-1]
    assert cap["kind"] == "bench" and cap["ts"] and cap["git_sha"]
    assert cap["backend"] == "cpu" and cap["config"] == "bert"
    assert cap["value"] == rows[-1]["value"]
    # the placeholder precedes the measurement; the LAST row is the one
    # the driver parses and it must carry the headline metric
    last = rows[-1]
    assert last["metric"] == "bert_base_pretrain_tokens_per_sec_per_chip"
    assert last["backend"] == "cpu"
    assert last.get("comparable") is False
    assert last.get("degraded") is True
    assert rows[0].get("placeholder") is True
    # provenance: no driver-captured baseline exists yet, so no ratio
    assert last.get("baseline_provenance") in ("none", None)
    # IR pass pipeline contract: the bert row carries the static-graph
    # probe's op-count reduction (bitwise-parity-gated), the AOT
    # trace/compile split, and the disk-cache counter
    for key in ("ops_before", "ops_after", "trace_ms", "compile_ms",
                "disk_cache_hits"):
        assert key in last, f"bench row missing {key!r}"
    assert last["ops_after"] < last["ops_before"], last
    assert last.get("pass_parity_bitwise") is True, last
    assert last.get("exec_cache_shared_hit") is True, last
    # the suite runs with JAX_ENABLE_COMPILATION_CACHE=0 (conftest) and
    # the bench inherits it -> no disk traffic
    assert last["disk_cache_hits"] == 0
    # graph-derived cost model cross-check: the IR-walked flop count of
    # the bert-shaped probe agrees with the closed-form flops_per_step
    # within 2% (the two accountings can never silently drift)
    for key in ("ir_flops_per_step", "ir_flops_delta"):
        assert key in last, f"bench row missing {key!r}"
    assert last["ir_flops_per_step"] > 0, last
    assert last["ir_flops_delta"] <= 0.02, last
    # mixed-precision probe contract: amp-on runs end to end, the loss
    # delta vs f32 stays within roundoff tolerance, casts were inserted
    # and the bf16 feed path really shrank the h2d transfer
    for key in ("amp_tokens_per_sec", "amp_loss_delta",
                "amp_casts_inserted", "amp_casts_elided",
                "amp_master_params", "amp_h2d_bytes",
                "amp_f32_h2d_bytes"):
        assert key in last, f"bench row missing {key!r}"
    assert last["amp_tokens_per_sec"] > 0, last
    assert last["amp_loss_delta"] <= 1e-2, last
    assert last["amp_casts_inserted"] > 0, last
    assert last["amp_master_params"] > 0, last
    assert last["amp_h2d_bytes"] < last["amp_f32_h2d_bytes"], last
    # rematerialization probe contract: XLA temp/peak bytes strictly
    # drop with remat on, at BITWISE-identical loss (dropout replay
    # inside recomputed segments); gradient_merge_k=4 covers 4
    # microbatches per compiled dispatch within 1e-5 of unmerged f32
    for key in ("remat_temp_bytes", "f32_temp_bytes", "remat_peak_bytes",
                "f32_peak_bytes", "gm_tokens_per_sec", "memory_stats",
                "gm_loss_delta"):
        assert key in last, f"bench row missing {key!r}"
    assert last["remat_temp_bytes"] < last["f32_temp_bytes"], last
    assert last["remat_peak_bytes"] < last["f32_peak_bytes"], last
    assert last.get("remat_parity_bitwise") is True, last
    assert last["remat_segments"] > 1, last
    assert last["gm_loss_delta"] <= 1e-5, last
    assert last["gm_k"] == 4 and last["gm_microbatches"] == \
        4 * last["gm_dispatches"], last
    for key in ("temp_bytes", "peak_bytes", "argument_bytes"):
        assert last["memory_stats"].get(key, 0) > 0, last["memory_stats"]
    # serving probe contract: the continuous-batching engine served the
    # whole closed-loop run — with faults off at nominal load, ZERO
    # requests shed, deadline-expired, degraded, or failed — and reports
    # throughput, tail latency, and batch fill
    for key in ("serve_requests_per_sec", "serve_p50_ms", "serve_p99_ms",
                "serve_requests", "serve_batches", "serve_shed",
                "serve_deadline_expired", "serve_degraded",
                "serve_failed", "serve_batch_fill_pct", "serve_ok"):
        assert key in last, f"bench row missing {key!r}"
    assert last["serve_requests_per_sec"] > 0, last
    assert last["serve_p99_ms"] >= last["serve_p50_ms"] > 0, last
    # engine-side latency truth: the bucket-derived percentiles the
    # engine's serve_e2e_ms / serve_queue_wait_ms histograms report —
    # load_gen's client view is no longer the only latency record
    for key in ("serve_engine_p50_ms", "serve_engine_p99_ms",
                "serve_queue_wait_p50_ms", "serve_queue_wait_p99_ms",
                "serve_client_p50_ms", "serve_client_p99_ms"):
        assert key in last, f"bench row missing {key!r}"
    assert last["serve_engine_p99_ms"] >= last["serve_engine_p50_ms"] > 0, \
        last
    assert last["serve_queue_wait_p99_ms"] >= \
        last["serve_queue_wait_p50_ms"] >= 0, last
    assert last["serve_client_p99_ms"] >= last["serve_client_p50_ms"] > 0, \
        last
    assert last["serve_ok"] == last["serve_requests"] > 0, last
    assert last["serve_shed"] == 0, last
    assert last["serve_deadline_expired"] == 0, last
    assert last["serve_degraded"] == 0 and last["serve_failed"] == 0, last
    assert 0 < last["serve_batch_fill_pct"] <= 100.0, last
    assert last["serve_batches"] <= last["serve_requests"], last
    # LLM decode probe contract: the paged continuous-batching engine
    # beats the padded-bucket data path ON THE SAME MODEL at mixed
    # lengths with IDENTICAL greedy outputs, engine-side p50/p99 come
    # from the decode histograms' buckets, and with faults off at
    # nominal load nothing sheds/expires/fails
    for key in ("decode_tokens_per_sec", "decode_padded_tokens_per_sec",
                "decode_padded_parity", "decode_engine_p50_ms",
                "decode_engine_p99_ms", "decode_step_p50_ms",
                "decode_step_p99_ms", "decode_ttft_p50_ms",
                "decode_requests", "decode_tokens", "decode_prefills",
                "decode_steps", "decode_shed", "decode_deadline_expired",
                "decode_failed", "decode_batch_fill_pct",
                "decode_page_util_peak_pct", "kv_page_evictions",
                "decode_ok", "trace_spans_per_request",
                "decode_slowest_trace", "decode_slowest_trace_ms"):
        assert key in last, f"bench row missing {key!r}"
    assert last["decode_tokens_per_sec"] > 0, last
    # the acceptance gate: ragged paged decode beats padded recompute
    assert last["decode_tokens_per_sec"] > \
        last["decode_padded_tokens_per_sec"] > 0, last
    assert last["decode_padded_parity"] is True, last
    assert last["decode_engine_p99_ms"] >= last["decode_engine_p50_ms"] \
        > 0, last
    assert last["decode_step_p99_ms"] >= last["decode_step_p50_ms"] > 0, \
        last
    assert last["decode_ok"] == last["decode_requests"] > 0, last
    assert last["decode_tokens"] > 0 and last["decode_steps"] > 0, last
    assert last["decode_shed"] == 0, last
    assert last["decode_deadline_expired"] == 0, last
    assert last["decode_failed"] == 0, last
    assert 0 < last["decode_batch_fill_pct"] <= 100.0, last
    assert 0 < last["decode_page_util_peak_pct"] <= 100.0, last
    # tracing contract: the probe runs traced — every served request
    # leaves at least its client root + decode.request + queue +
    # prefill spans, and the slowest request is named by trace id
    assert last["trace_spans_per_request"] >= 3.0, last
    assert isinstance(last["decode_slowest_trace"], str) \
        and len(last["decode_slowest_trace"]) == 16, last
    assert last["decode_slowest_trace_ms"] > 0, last
    # decode token-economics contract: speculative decoding is EXACT
    # under greedy (spec_parity) and pays for itself (every accepted
    # draft token is a ragged step never run → strictly fewer steps
    # and more tokens/sec than the spec-off leg); int8 KV pages stay
    # inside the quant-loss gate at ~2x+ pool headroom; the repeated
    # prompt hits the shared-prefix index
    for key in ("spec_tokens_per_sec", "spec_accept_rate", "spec_steps",
                "spec_proposed", "spec_accepted", "spec_parity",
                "spec_beats_dense", "kv_quant_loss_delta",
                "kv_pool_headroom_x", "kv_prefix_hits",
                "kv_prefix_parity"):
        assert key in last, f"bench row missing {key!r}"
    assert last["spec_parity"] is True, last
    assert last["spec_proposed"] >= last["spec_accepted"] > 0, last
    assert last["spec_accept_rate"] > 0, last
    assert last["spec_steps"] < last["decode_steps"], last
    assert last["spec_beats_dense"] is True, last
    assert last["spec_tokens_per_sec"] > \
        last["decode_tokens_per_sec"], last
    assert 0 <= last["kv_quant_loss_delta"] <= 5e-2, last
    assert last["kv_pool_headroom_x"] >= 2.0, last
    assert last["kv_prefix_hits"] > 0, last
    assert last["kv_prefix_parity"] is True, last
    # overlapped decode data plane contract (ISSUE 20): the async
    # double-buffered tick loop is EXACT under greedy (async_parity,
    # byte-identical outputs vs the PADDLE_ASYNC_DECODE=0 twin) and
    # wins the majority of paired rounds against it; the host-RAM KV
    # tier holds more concurrent sessions than the HBM pool alone
    # could (kv_sessions_per_pool_x > 1), park/resume is invisible in
    # the tokens, and the int8 host rows save most of the f32 bytes
    for key in ("async_tokens_per_sec", "sync_tokens_per_sec",
                "async_parity", "async_beats_sync", "async_round_wins",
                "decode_overlap_frac", "kv_sessions_per_pool_x",
                "kv_offload_parity", "kv_offload_bytes_saved_pct",
                "kv_offload_bytes", "kv_sessions_parked",
                "kv_sessions_resumed", "kv_page_restores"):
        assert key in last, f"bench row missing {key!r}"
    assert last["async_parity"] is True, last
    assert last["async_beats_sync"] is True, last
    assert last["async_tokens_per_sec"] > 0, last
    assert last["sync_tokens_per_sec"] > 0, last
    assert 0.0 < last["decode_overlap_frac"] <= 1.0, last
    assert last["kv_sessions_per_pool_x"] > 1.0, last
    assert last["kv_offload_parity"] is True, last
    assert last["kv_offload_bytes_saved_pct"] > 50.0, last
    assert last["kv_offload_bytes"] > 0, last
    assert last["kv_sessions_parked"] >= 1, last
    assert last["kv_sessions_resumed"] >= 1, last
    assert last["kv_page_restores"] >= 1, last
    # FLEET probe contract: two engines behind the serving router —
    # the zipf-session workload reports throughput + p99 TTFT, the
    # deterministic mid-generation engine stop fails over with the
    # survivor's greedy replay BITWISE equal to the dense oracle, and
    # KV page migration both saves wire bytes (int8 frame vs f32) and
    # degrades cleanly when the transport is dead (fallback counted)
    for key in ("fleet_tokens_per_sec", "fleet_p99_ttft_ms",
                "fleet_requests_ok", "router_failovers",
                "router_replays", "fleet_failover_parity",
                "kv_migration_ok", "kv_migration_adopted",
                "kv_migration_bytes_saved_pct",
                "kv_migration_fallbacks"):
        assert key in last, f"bench row missing {key!r}"
    assert last["fleet_tokens_per_sec"] > 0, last
    assert last["fleet_p99_ttft_ms"] > 0, last
    assert last["fleet_requests_ok"] > 0, last
    assert last["router_failovers"] >= 1, last
    assert last["router_replays"] >= 1, last
    assert last["fleet_failover_parity"] is True, last
    assert last["kv_migration_ok"] is True, last
    assert last["kv_migration_adopted"] >= 1, last
    assert last["kv_migration_bytes_saved_pct"] > 50.0, last
    assert last["kv_migration_fallbacks"] >= 1, last
    # MULTICHIP probe contract: the DP×TP static-executor step (forced
    # 8-device CPU topology in a subprocess) matches the single-chip
    # loss within the established gm tolerance, the row-parallel hint
    # really produced psum accounting, and the gradient-merge×pipeline
    # composition reports its GPipe stage count + analytic bubble (CPU
    # rows stay comparable: false — the fields are the contract, the
    # tokens/s are movement-only)
    for key in ("shard_tokens_per_sec", "shard_parity_delta",
                "shard_psums_inserted", "pp_bubble_frac", "pp_stages",
                "shard_vars_annotated"):
        assert key in last, f"bench row missing {key!r}"
    assert last["shard_tokens_per_sec"] > 0, last
    assert last["shard_parity_delta"] <= 1.2e-7, last
    assert last["shard_psums_inserted"] >= 1, last
    assert last["shard_vars_annotated"] > 0, last
    assert last["pp_stages"] == 2, last
    assert 0.0 < last["pp_bubble_frac"] < 1.0, last
    # quantized-collective contract (ISSUE 15): the int8 bucketed DP
    # all-reduce must save >= 60% of the f32 ring bytes while holding
    # the loss inside the established amp-style gate, with the buckets
    # emitted in completion order (overlap fraction (nb-1)/nb)
    for key in ("quant_allreduce_tokens_per_sec", "quant_loss_delta",
                "comm_bytes_saved_pct", "allreduce_overlap_frac",
                "comm_buckets"):
        assert key in last, f"bench row missing {key!r}"
    assert last["quant_allreduce_tokens_per_sec"] > 0, last
    assert last["quant_loss_delta"] <= 1e-2, last
    assert last["comm_bytes_saved_pct"] >= 60.0, last
    assert last["comm_buckets"] >= 2, last
    assert 0.0 < last["allreduce_overlap_frac"] < 1.0, last
    # pipeline-schedule + ZeRO contract (ISSUE 18): 1F1B's modeled
    # bubble beats gpipe's at the same (S, M); ZeRO-2 over dp=8 engages
    # (counted zero dispatch), collapses >= 40% of the per-device
    # optimizer-state bytes, and holds the loss inside the quant gate
    # vs the replicated comm leg
    for key in ("pp_1f1b_tokens_per_sec", "pp_1f1b_bubble_frac",
                "zero_stage", "zero_state_bytes_saved_pct",
                "zero_loss_delta", "zero_dispatches"):
        assert key in last, f"bench row missing {key!r}"
    assert last["pp_1f1b_tokens_per_sec"] > 0, last
    assert 0.0 < last["pp_1f1b_bubble_frac"] < last["pp_bubble_frac"], \
        last
    assert last["zero_stage"] == 2, last
    assert last["zero_state_bytes_saved_pct"] >= 40.0, last
    assert last["zero_loss_delta"] <= 1e-2, last
    assert last["zero_dispatches"] >= 1, last
    # the MoE probe's explicit all_to_all path is parity-gated vs the
    # dense oracle with its wire bytes charged in the cost model
    for key in ("moe_tokens_per_sec",
                "moe_parity_delta", "moe_int8_loss_delta",
                "moe_capacity_drop_pct", "moe_a2a_dispatches",
                "moe_a2a_bytes", "moe_a2a_bytes_saved_pct"):
        assert key in last, f"bench row missing {key!r}"
    assert last["moe_tokens_per_sec"] > 0, last
    assert last["moe_parity_delta"] <= 1e-5, last
    assert last["moe_int8_loss_delta"] <= 1e-2, last
    assert last["moe_a2a_dispatches"] >= 1, last
    assert last["moe_a2a_bytes"] > 0, last
    assert last["moe_a2a_bytes_saved_pct"] > 0.0, last


def test_bench_refuses_full_shapes_off_tpu():
    """No TPU and no BENCH_SMOKE=1: non-zero exit naming the backend,
    and no row under a device metric's name."""
    env = _env()
    env.pop("BENCH_SMOKE", None)
    out = subprocess.run([sys.executable, BENCH], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "not a TPU" in out.stderr, out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py under JAX_PLATFORMS=cpu exits non-zero before any
    phase, naming the platform it found, and prints no result."""
    out = subprocess.run([sys.executable, SMOKE], env=_env(),
                         capture_output=True, text=True, timeout=180)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stdout, out.stdout
    assert "== " not in out.stdout, "a phase started on the CPU"
    assert not out.stdout.strip().splitlines()[-1].startswith("{")


def test_first_touch_on_unusable_platform_raises():
    """`import paddle_tpu; to_tensor(...)` with a platform jax cannot
    initialise raises — it does not come back as a CPU tensor."""
    src = ("import numpy as np, paddle_tpu as paddle\n"
           "t = paddle.to_tensor(np.ones((2, 2), np.float32))\n"
           "print('PLATFORM', t.value.devices().pop().platform)\n")
    out = _py(src, _env(JAX_PLATFORMS="nosuchchip"))
    assert out.returncode != 0
    assert "PLATFORM" not in out.stdout
    assert "nosuchchip" in out.stderr, out.stderr[-2000:]


def test_place_without_its_device_raises():
    import paddle_tpu as paddle

    with pytest.raises(RuntimeError, match="no such device"):
        paddle.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="no such device"):
        paddle.CPUPlace(10 ** 6).jax_device()     # no modulo wrap
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"
    assert paddle.to_tensor([1.0]).place == paddle.CPUPlace(0)
    assert not paddle.is_compiled_with_tpu()


def test_bringup_starts_no_process_and_writes_no_file():
    import inspect

    import paddle_tpu.framework.bringup as bringup

    src = inspect.getsource(bringup)
    for word in ("subprocess", "open(", "tempfile", "expanduser"):
        assert word not in src, word


_CACHE_SRC = (
    "import jax, paddle_tpu\n"
    "from paddle_tpu.static import compile_cache as cc\n"
    "from paddle_tpu.ops.pallas import autotune\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "seen = []\n"
    "real = jax.config.update\n"
    "jax.config.update = lambda k, v: (seen.append(k), real(k, v))\n"
    "cc.ensure_enabled()\n"
    "print('DIR', cc.cache_dir())\n"
    "print('JAX', before, jax.config.jax_compilation_cache_dir)\n"
    "print('AUTOTUNE', autotune._cache_dir())\n"
    "print('UPDATED', 'jax_compilation_cache_dir' in seen)\n")


def _cache_report(env):
    out = _py(_CACHE_SRC, env)
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(ln.split(" ", 1) for ln in out.stdout.splitlines())


def test_compile_cache_dir_resolution(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: used, and jax's cache-directory
    config is never touched. Unset: one fixed path in the checkout, the
    same in every process. The autotune verdicts sit beside either."""
    given = str(tmp_path / "given")
    rep = _cache_report(_env(JAX_COMPILATION_CACHE_DIR=given))
    assert rep["DIR"] == given
    assert rep["JAX"] == f"{given} {given}"
    assert rep["AUTOTUNE"] == os.path.join(given, "autotune")
    assert rep["UPDATED"] == "False"

    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    fixed = os.path.join(REPO, ".jax_cache")
    reps = [_cache_report(env), _cache_report(env)]
    assert reps[0] == reps[1]
    assert reps[0]["DIR"] == fixed
    assert reps[0]["JAX"] == f"None {fixed}"
    assert reps[0]["AUTOTUNE"] == os.path.join(fixed, "autotune")
    assert reps[0]["UPDATED"] == "True"


@pytest.mark.parametrize("family", ["flash", "fused_xent", "fused_embedding",
                                    "kda_chunk"])
def test_chosen_pallas_kernel_failure_propagates(monkeypatch, family):
    """A kernel that passed its gate and then fails raises; it is not
    counted as ``<family>.xla`` and served from the XLA reference.
    (paged attention and sampling: tests/test_paged_attention.py,
    tests/test_fused_sampling.py.)"""
    import jax.numpy as jnp

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import (counters, flash_attention,
                                       fused_embedding, fused_xent, kda)

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    if family == "flash":
        monkeypatch.setattr(flash_attention, "_flash_attention_pallas", boom)
        q = jnp.zeros((1, 256, 1, 64), jnp.float32)
        call = lambda: flash_attention.flash_attention_or_fallback(q, q, q)
    elif family == "fused_xent":
        monkeypatch.setattr(fused_xent, "_fused_xent_core", boom)
        call = lambda: fused_xent.fused_linear_cross_entropy(
            jnp.zeros((256, 128)), jnp.zeros((256, 128)), jnp.zeros((256,)),
            jnp.zeros((256,), jnp.int32))
    elif family == "fused_embedding":
        monkeypatch.setattr(fused_embedding, "_bag_pallas", boom)
        call = lambda: fused_embedding.fused_embedding_seq_pool(
            jnp.zeros((64, 128)), jnp.zeros((8, 8), jnp.int32))
    else:
        monkeypatch.setattr(kda, "_pallas_fwd", boom)
        x = jnp.zeros((1, 64, 1, 128), jnp.float32)
        call = lambda: kda.chunk_kda(x, x, x, x, jnp.zeros((1, 64, 1)))
    with pytest.raises(RuntimeError, match="mosaic said no"):
        call()
    assert not [k for k in counters.snapshot() if k.endswith(".xla")]


_KEY_SRC = """
import hashlib, sys
import jax, jax.numpy as jnp
import paddle_tpu
from jax._src import cache_key
from paddle_tpu.ops.pallas import sampling as sp
from paddle_tpu.static import compile_cache

compile_cache.ensure_enabled()
l = jnp.zeros((8, 1024), jnp.float32)

def lowered(f, *a):      # Mosaic lowering needs no device
    return jax.jit(f).trace(*a).lower(lowering_platforms=("tpu",))

if sys.argv[1] == "tuned":
    # what an autotune round leaves behind: the same kernel traced
    # earlier from another call site, filling jax's inner-jit caches
    lowered(lambda ll: sp._fused_sample_pallas(ll, l, 1.0, 8), l).as_text()
step = lowered(lambda a, n: sp._fused_sample_pallas(a * 2, n, 0.8, 8), l, l)
ir = cache_key._canonicalize_ir(step.compiler_ir("stablehlo"),
                                cache_key.IgnoreCallbacks.NO)
print("KEY", hashlib.sha256(ir).hexdigest())
"""


def test_kernel_cache_key_ignores_trace_history():
    """The disk-cache key of a step holding a Pallas kernel must not
    depend on what was traced before it: a run whose autotuner timed
    the kernel first and a run that read the verdict from disk have to
    find each other's executables (on a v5e they did not, PR 21)."""
    keys = []
    for mode in ("fresh", "tuned"):
        out = subprocess.run([sys.executable, "-c", _KEY_SRC, mode],
                             env=_env(), capture_output=True, text=True,
                             timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        keys.append([ln for ln in out.stdout.splitlines()
                     if ln.startswith("KEY")][0])
    assert keys[0] == keys[1]
