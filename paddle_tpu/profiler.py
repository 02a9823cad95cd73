"""Profiler: host event annotation + device tracing.

Parity with the reference profiler stack
(/root/reference/paddle/fluid/platform/profiler.h:126 RecordEvent, :208
EnableProfiler, :211 DisableProfiler; python front
python/paddle/fluid/profiler.py:131 start_profiler, :198 stop_profiler,
:255 profiler context manager). TPU-native mapping: `RecordEvent` is an
RAII scope that both feeds a host-side aggregation table (the reference's
sorted summary) and emits a `jax.profiler.TraceAnnotation` so the scope
shows up on the TensorBoard/XPlane device timeline; `start_profiler` with
a trace dir runs `jax.profiler.start_trace` (the CUPTI DeviceTracer
equivalent — XLA runtime events + TPU counters).
"""
from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Optional

import jax

_state = {
    "enabled": False,
    "trace_dir": None,
    # name -> [calls, total_s, min_s, max_s]
    "events": defaultdict(lambda: [0, 0.0, float("inf"), 0.0]),
    # (name, start_us, dur_us, tid) spans for chrome-trace export
    "spans": [],
    # thread ident -> small sequential tid (stable chrome-trace rows)
    "tids": {},
}


# ---------------------------------------------------------------------------
# executor hot-path counters.
#
# The reference profiler only times host events; the quantities that decide
# TPU step-loop health — did the step recompile, did state bounce through
# host memory, were parameter buffers donated — are invisible to a timer.
# Every executor (static Executor, jit.TrainStep) bumps these; a reader
# takes counters_snapshot() before and counters_delta() after what it
# watches (chip_smoke.py does). The kernels' own tables live with the
# kernels: ops.pallas.counters (dispatch counts, printed on the
# ``pallas counters`` line of every benchmarks/run.py log, and the work
# ledger counters.step_work), ops.pallas.autotune.stats() and
# static.compile_cache.seconds_by_function().
#
# Names in use:
#   compile_cache_hits / compile_cache_misses  per-step executable lookup
#   h2d_bytes          all host->device payload bytes (feeds + uploads)
#   state_h2d_bytes    the persistable-state slice of h2d_bytes only —
#                      zero after the first step when state stays resident
#   donated_bytes      bytes of buffers offered to XLA for in-place reuse
#   donation_fallback_copies  aliased/exposed state arrays copied so a
#                      caller-held reference survives donation
#   executor_steps     compiled steps dispatched
#
# Fault-tolerance counters (paddle_tpu.fault, io.snapshot,
# distributed.launch) use the same table:
# IR pass pipeline + compile cache counters (static/passes.py,
# static/executor.py, static/compile_cache.py):
#   ir_ops_before / ir_ops_after  block-0 op counts entering/leaving the
#                      pass pipeline (cumulative over builds; read
#                      as a delta)
#   ir_pass_ms         total pipeline wall-time (ms, float)
#   ir_vars_dropped    unused VarDescs dropped by the cleanup pass
#   pass_<name>_removed_ops / pass_<name>_ms  per-pass movement
#   trace_ms           jit .lower() wall-time (Python trace -> StableHLO)
#   compile_ms         .compile() wall-time (XLA; a disk-cache hit makes
#                      this a file read)
#
# Mixed-precision counters (the auto_mixed_precision pass in
# static/passes.py, gated by BuildStrategy.amp / PADDLE_AMP):
#   amp_casts_inserted amp cast ops added to the forward region
#   amp_casts_elided   casts removed by the cleanup sub-pass (dup casts,
#                      exact lowp->f32->lowp round trips)
#   amp_ops_lowprec    ops rewritten to run in bf16/fp16
#   amp_master_params  f32 parameters that got a low-precision compute
#                      copy (master weights: optimizer updates stay f32)
#   amp_lowprec_feeds  float32 data vars flipped to the low dtype (the
#                      feed paths cast host-side; h2d bytes halve)
#   amp_loss_scaled    fp16 static loss scaling wired through the
#                      check_finite_and_unscale kernel (1 per build)
#   disk_cache_hits / disk_cache_misses  jax persistent-compilation-cache
#                      traffic (static/compile_cache.py); process
#                      events, merged into exe.counters like the fault
#                      counters below
#
# Rematerialization + gradient-merge counters (recompute_segmentation
# pass in static/passes.py; _gm_step_fn in static/executor.py):
#   remat_segments     checkpoint segments the forward region was split
#                      into (per build)
#   remat_stash_vars / remat_recompute_vars  boundary vars saved for the
#                      backward vs interior vars recomputed
#   gm_dispatches / gm_microbatches  gradient-merge steps dispatched and
#                      the microbatches they covered (microbatches /
#                      dispatches = k)
#
# GSPMD sharding counters (shard_propagation pass in static/passes.py;
# _pp_step_fn in static/executor.py):
#   shard_vars_annotated  VarDescs stamped with a propagated
#                      PartitionSpec (__sharding_spec attr) per build
#   shard_conflicts_replicated  spec conflicts (disagreeing inputs,
#                      reduced sharded dims on unknown ops) resolved by
#                      replication
#   shard_psums_inserted  contracted/reduced dims found sharded — each
#                      is a psum XLA's SPMD partitioner materializes
#                      (row-parallel matmul, dp loss reduction)
#   pp_stages          GAUGE: pipeline stage count of the last
#                      pipelined (GPipe-scheduled) build
#   autotune_disk_hits flash autotune verdicts served from the
#                      persistent disk cache (<compile cache>/autotune;
#                      ops/pallas/autotune.py)
#   xla_temp_bytes / xla_peak_bytes / xla_argument_bytes /
#   xla_output_bytes   GAUGES (set_counter, not accumulated): the last
#                      built executable's compiled.memory_analysis() —
#                      the objective remat gate (temp/peak must drop
#                      with recompute on; exe.memory_stats() mirrors)
#
# Serving counters (inference/serving.py ServingEngine +
# distributed/http_kv.py hardening; SERVE_COUNTER_NAMES below):
#   serve_requests     requests admitted past admission control
#   serve_shed         requests shed at admission (queue bound or token
#                      bucket) with a typed Overloaded error
#   serve_deadline_expired  requests dropped (admission, assembly, or
#                      respond) because their deadline passed/was
#                      unmakeable, with a typed DeadlineExceeded
#   serve_degraded     requests that fell back to the batch-1 eager path
#                      after the compiled dispatch exhausted its retries
#   serve_failed       requests failed outright (fallback failed too):
#                      typed RequestFailed to the caller
#   serve_batches      compiled batches dispatched
#   serve_queue_depth  GAUGE: admission-queue depth after the last
#                      submit/assembly
#   serve_batch_fill_pct  GAUGE: cumulative mean of rows/bucket-capacity
#                      per dispatched batch, in percent
#   kv_rejected_oversize  KV/health PUTs rejected 413 over the body cap
#   kv_conn_timeouts   KV/health connections closed on socket timeout
#   supervisor_drains  launch.Supervisor graceful shutdowns started
#   supervisor_drain_kills  children SIGKILLed after the drain window
#
# Elastic-membership counters (distributed/elastic.py ElasticAgent +
# auto_checkpoint mid-epoch resume; ELASTIC_COUNTER_NAMES below):
#   elastic_generations  generations this process rendezvoused into
#                      (initial join + every reform)
#   worker_lost        peers declared lost (lease expiry / dead send
#                      thread) — typed WorkerLost raised each time
#   lease_expirations  heartbeat leases observed expired
#   barrier_timeouts   bounded elastic barriers that hit their deadline
#                      (typed RendezvousTimeout)
#   kv_poll_backoffs   KV polls slowed by the capped-exponential
#                      backoff (KVClient.wait + ElasticAgent polling)
#   nan_guard_trips    non-finite loss observations (NanGuard; typed
#                      NumericalDivergence after N consecutive)
#   resume_batch_offset  GAUGE: the batch offset the last mid-epoch
#                      resume restarted at (0 = epoch boundary)
#
# Parameter-server fault-tolerance counters (ps/replication.py +
# ps/service.py; PS_COUNTER_NAMES below, merged into Executor.counters
# like the fault/elastic/serve slices):
#   ps_failovers       client failovers: primary unreachable past the
#                      retry budget, shard map refreshed, request
#                      REPLAYED against the promoted backup
#   ps_promotions      backups promoted to primary by the
#                      ReplicaCoordinator after a lease expiry (each one
#                      is a shard-map epoch bump)
#   ps_rpc_retries     PS RPC re-attempts after a transient socket
#                      failure (subset of retry_attempts, PS-scoped)
#   ps_snapshot_commits  crash-safe pserver table snapshots committed
#                      through SnapshotStore (shard_<k>/seq_<n>/)
#   ps_replication_lag GAUGE: frames accepted by the primary but not yet
#                      replicated (async mode queue depth; 0 in sync)
#   ps_conn_timeouts   pserver connections closed on the per-connection
#                      idle timeout (mirrors kv_conn_timeouts)
#
#   retry_attempts     re-attempts after a retryable failure (Retrier)
#   retry_giveups      retry budget/deadline exhausted, last error raised
#   faults_injected    armed fault points fired (tests / PADDLE_FAULT_SPEC)
#   ckpt_commits       snapshot manifest commits (the atomic rename ran)
#   ckpt_corrupt_skipped  torn/sha-mismatched snapshots skipped at load
#   ckpt_fallbacks     loads that fell back past a newer broken snapshot
#   trainer_relaunches dead trainers re-exec'd by launch.supervise
# These are process events, not per-executor ones, so Executor.counters
# merges the FAULT_COUNTER_NAMES slice of this table into its view.
# ---------------------------------------------------------------------------
FAULT_COUNTER_NAMES = (
    "retry_attempts", "retry_giveups", "faults_injected",
    "ckpt_commits", "ckpt_corrupt_skipped", "ckpt_fallbacks",
    "trainer_relaunches",
)

# elastic-membership + mid-epoch-resume counters (distributed/elastic
# ElasticAgent, http_kv poll backoff, auto_checkpoint resume), merged
# into Executor.counters like the fault slice
ELASTIC_COUNTER_NAMES = (
    "elastic_generations", "worker_lost", "lease_expirations",
    "barrier_timeouts", "kv_poll_backoffs", "nan_guard_trips",
    "resume_batch_offset",
)

# process-level compile-cache counters merged into Executor.counters
# (bumped by the jax monitoring listener in static/compile_cache.py;
# autotune_disk_hits by ops/pallas/autotune.py — tuned kernel configs
# persist alongside compiled steps under compile_cache.cache_dir())
COMPILE_COUNTER_NAMES = ("disk_cache_hits", "disk_cache_misses",
                         "autotune_disk_hits")

# quantized-collective counters (parallel/collectives.py encodings:
# the executor's bucketed DP all-reduce step bumps per dispatch, the
# PS client/replicator per quantized wire payload; merged into
# Executor.counters like the fault slice). comm_buckets and
# allreduce_overlap_frac are point-in-time gauges of the last
# quantized-collective build.
COMM_COUNTER_NAMES = (
    "comm_quant_bytes_sent", "comm_quant_bytes_saved",
    "comm_buckets", "allreduce_overlap_frac",
)

# pipeline-schedule + ZeRO plan gauges (static/stepplan.py notifies at
# step-plan build; the executor replays them on warm cache hits).
# Declaration-only for dashboards/catalog: the values ride each
# executor's OWN counters via its plan-gauge hook — merging the
# process-global snapshot here would leak one executor's plan gauges
# into a fresh executor's view
ZERO_COUNTER_NAMES = (
    "pp_bubble_frac", "pp_stash_depth", "pp_schedule_fallback",
    "zero_stage_active", "zero_buckets",
    "zero_state_bytes_replicated", "zero_state_bytes_sharded",
    "zero_state_bytes_saved_pct",
    # cumulative wire counters of ZeRO dispatches (encoded half-ring
    # reduce-scatter + raw-f32 all-gather) — deliberately separate from
    # comm_quant_bytes_* so the quantized-ring saved>sent invariant
    # stays a codec property
    "zero_wire_bytes_sent", "zero_wire_bytes_saved",
)

# parameter-server fault-tolerance counters (ps/replication.py replica
# groups + ps/service.py hardened RPC), merged into Executor.counters
# and the chaos drill's counter table
PS_COUNTER_NAMES = (
    "ps_failovers", "ps_promotions", "ps_rpc_retries",
    "ps_snapshot_commits", "ps_replication_lag", "ps_conn_timeouts",
)

# LLM decode-engine counters (inference/decode: paged KV pool + ragged
# paged attention + continuous prefill/decode scheduling;
# DecodeEngine.counters merges these plus the fault slice)
DECODE_COUNTER_NAMES = (
    "decode_requests", "decode_tokens", "decode_steps",
    "decode_prefills", "decode_shed", "decode_deadline_expired",
    "decode_preempted", "decode_failed", "decode_batch_fill_pct",
    "kv_pages_in_use", "kv_page_evictions",
    "spec_proposed", "spec_accepted", "spec_accept_rate",
    "kv_prefix_hits", "kv_pages_shared", "kv_pages_cached",
    "kv_cow_copies",
    "decode_overlap_frac",
    "kv_pages_host", "kv_offload_bytes", "kv_page_restores",
    "kv_sessions_parked", "kv_sessions_resumed", "kv_restore_fallbacks",
)

# fleet-router + KV-migration counters (serving/router.py dispatch,
# failover, replay, SLO shed; serving/disagg.py page shipping;
# FleetRouter.counters merges these plus the fault slice)
ROUTER_COUNTER_NAMES = (
    "router_requests", "router_dispatches", "router_failovers",
    "router_replays", "router_affinity_hits", "router_sheds",
    "router_engines_routable",
    "kv_migration_bytes", "kv_migration_bytes_saved",
    "kv_migration_pages", "kv_migration_fallbacks",
)

# serving-path counters (ServingEngine.counters merges these plus the
# fault slice, mirroring Executor.counters)
SERVE_COUNTER_NAMES = (
    "serve_requests", "serve_shed", "serve_deadline_expired",
    "serve_degraded", "serve_failed", "serve_batches",
    "serve_queue_depth", "serve_batch_fill_pct",
    "kv_rejected_oversize", "kv_conn_timeouts",
    "supervisor_drains", "supervisor_drain_kills",
)

# The counter table is now the SCALAR TIER of the typed metrics
# registry (paddle_tpu.observability.metrics): every name above is a
# declared Counter/Gauge with help text (observability.catalog), the
# registry adds labeled metrics + fixed-bucket latency histograms, and
# every http_kv listener (KVServer, ServingHealthServer, the standalone
# PADDLE_METRICS_PORT server) exposes the whole table as Prometheus
# text at GET /metrics. The functions below are thin compat shims —
# byte-identical snapshots, zero call-site churn.
from .observability import metrics as _obs_metrics
from .observability.catalog import declare_standard_metrics as _declare

_REGISTRY = _obs_metrics.default_registry()
_declare(_REGISTRY)
# the registry lock doubles as the host-span state lock (RecordEvent
# mutation vs summary()/export_chrome_tracing iteration)
_state_lock = _REGISTRY.lock


def metrics_registry() -> "_obs_metrics.MetricsRegistry":
    """The process-global typed metrics registry behind the counter
    shims — declare histograms/labeled metrics here; render with
    ``render_prometheus()`` or scrape any KV/health listener's
    ``/metrics``."""
    return _REGISTRY


def render_prometheus() -> str:
    """Prometheus text exposition of the whole registry (the scrape-free
    path; the HTTP form rides http_kv's GET /metrics)."""
    return _REGISTRY.render_prometheus()


def bump_counter(name: str, n: int = 1) -> None:
    """Add ``n`` to the global executor counter ``name`` (thread-safe)."""
    _REGISTRY.inc_scalar(name, n)


def set_counter(name: str, value: int) -> None:
    """GAUGE semantics: overwrite counter ``name`` with ``value``
    (thread-safe). Used for point-in-time quantities — the xla_*_bytes
    memory-analysis numbers of the last-built executable — where
    accumulation would be meaningless."""
    _REGISTRY.set_scalar(name, value)


def counters_snapshot() -> dict:
    """Copy of the global executor counters (pair with counters_delta)."""
    return _REGISTRY.flat_snapshot()


def counters_delta(before: dict) -> dict:
    """Non-zero counter movement since ``before`` (a counters_snapshot)."""
    return _REGISTRY.flat_delta(before)


def reset_counters() -> None:
    _REGISTRY.reset_values()


class RecordEvent:
    """RAII profiling scope (reference platform/profiler.h:126).

    Usable as context manager or explicit begin()/end() pair. Always emits
    a TraceAnnotation (cheap when no trace is active); host aggregation
    only while the profiler is enabled.
    """

    def __init__(self, name: str, event_type: str = "PyUserDefined"):
        self.name = name
        self._ann = None
        self._t0 = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if _state["enabled"]:
            self._t0 = time.perf_counter()
        return self

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0 is not None:
            t1 = time.perf_counter()
            dt = t1 - self._t0
            import threading

            ident = threading.get_ident()
            # registry lock: prefetch/serving threads end() concurrently
            # with summary()/export_chrome_tracing iterating these
            with _state_lock:
                rec = _state["events"][self.name]
                rec[0] += 1
                rec[1] += dt
                rec[2] = min(rec[2], dt)
                rec[3] = max(rec[3], dt)
                tid = _state["tids"].setdefault(ident, len(_state["tids"]))
                _state["spans"].append(
                    (self.name, self._t0 * 1e6, dt * 1e6, tid))
            self._t0 = None

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


def record_event(name):
    return RecordEvent(name)


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   trace_dir: Optional[str] = None):
    """Enable host aggregation; with trace_dir, also start a device trace
    (reference profiler.py:131; state kept for API parity)."""
    with _state_lock:
        _state["enabled"] = True
        _state["events"].clear()
        _state["spans"].clear()
        _state["tids"].clear()
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        _state["trace_dir"] = trace_dir


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: Optional[str] = None,
                  print_table: bool = True):
    """Disable profiling, write the aggregated event table to
    ``profile_path`` or print it (reference profiler.py:198).
    ``print_table=False`` silences the no-path default — library
    callers and tests read the returned table instead of stdout."""
    _state["enabled"] = False
    if _state["trace_dir"]:
        jax.profiler.stop_trace()
        _state["trace_dir"] = None
    table = summary(sorted_key)
    if profile_path:
        d = os.path.dirname(profile_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(profile_path, "w") as f:
            f.write(table)
    elif print_table:
        print(table)
    return table


def summary(sorted_key: Optional[str] = "total") -> str:
    rows = []
    with _state_lock:   # recording threads mutate events concurrently
        events = {k: list(v) for k, v in _state["events"].items()}
    for name, (calls, total, mn, mx) in events.items():
        rows.append((name, calls, total, total / max(calls, 1), mn, mx))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key or "total", 2)
    rows.sort(key=lambda r: -r[key_idx])
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}"
             f"{'Min(s)':>12}{'Max(s)':>12}"]
    for name, calls, total, ave, mn, mx in rows:
        lines.append(f"{name:<40}{calls:>8}{total:>12.6f}{ave:>12.6f}"
                     f"{mn:>12.6f}{mx:>12.6f}")
    counters = counters_snapshot()   # locked copy: prefetch threads bump
    if counters:
        lines.append("")
        lines.append(f"{'Executor counter':<40}{'Value':>12}")
        for name in sorted(counters):
            lines.append(f"{name:<40}{counters[name]:>12}")
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None,
             trace_dir: Optional[str] = None,
             print_table: bool = True):
    """`with profiler.profiler():` parity (reference profiler.py:255).
    ``print_table`` forwards to :func:`stop_profiler`."""
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path, print_table=print_table)


def export_chrome_tracing(path: str, process_name: str = "paddle_tpu"):
    """Write recorded host spans as a chrome://tracing JSON file — the
    reference's timeline output (platform/profiler.proto + tools
    timeline.py). Device-side traces live in the XPlane dir from
    start_profiler(trace_dir=...)."""
    import json

    events = [{"name": "process_name", "ph": "M", "pid": 0,
               "args": {"name": process_name}}]
    with _state_lock:   # recording threads append spans concurrently
        spans = list(_state["spans"])
    for name, start_us, dur_us, tid in spans:
        events.append({"name": name, "ph": "X", "pid": 0, "tid": tid,
                       "ts": start_us, "dur": dur_us, "cat": "host"})
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path


# convenience re-exports of the underlying device tracer
start_trace = jax.profiler.start_trace
stop_trace = jax.profiler.stop_trace


def cuda_profiler(*a, **k):
    """Reference fluid.profiler.cuda_profiler parity: no CUDA on TPU;
    returns a null context so call sites keep working."""
    return contextlib.nullcontext()
