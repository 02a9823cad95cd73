"""CompiledProgram: multi-device data-parallel execution of a Program.

Reference: /root/reference/python/paddle/fluid/compiler.py:87
CompiledProgram / :160 with_data_parallel — builds a ParallelExecutor that
clones the SSA graph per GPU and inserts NCCL allreduce op-handles
(parallel_executor.cc).

TPU-native design: no graph cloning, no comm-op insertion. The executor
jit-compiles the SAME lowered step function with jax.sharding annotations:
feeds are sharded over the mesh's "data" axis, persistables replicated,
and XLA's SPMD partitioner inserts the gradient all-reduces over ICI
(exactly the role of the reference's AllReduceOpHandle, but compiled).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .ir import Program


class BuildStrategy:
    """Knob parity (reference details/build_strategy.h), now WIRED: the
    graph-rewrite knobs select which IR passes (static/passes.py) run
    before the Executor traces the program —

      fuse_elewise_add_act_ops  elementwise+activation fusion onto the
                                fused_elemwise_activation kernel
      memory_optimize           dead-op elimination + unused-VarDesc drop
      enable_inplace            identity elision (assign / scale-by-1)
      constant_folding          all-constant subgraph folding (new)
      cse                       common-subexpression elimination (new)

    Mixed-precision knobs (the auto_mixed_precision pass,
    static/passes.py; `PADDLE_AMP=bf16|fp16|0` env overrides them all):

      amp                   run white/black-list bf16 (or fp16) rewrite
                            of the forward region; params stay f32
                            master weights, float32 feeds go low
                            host-side (h2d bytes halve)
      amp_dtype             "bfloat16" (TPU default; no loss scaling
                            needed) or "float16"
      amp_level             "O1" white-list only; "O2" lowers gray ops
                            too (black list always stays f32)
      amp_init_loss_scale   static loss scale threaded through
                            check_finite_and_unscale under fp16

    Memory / microbatching knobs (ISSUE 5 — rematerialization + in-step
    gradient merge; `PADDLE_IR_PASSES=0` disables both with the rest of
    the pipeline):

      recompute             run the recompute_segmentation pass: the
                            forward region is split into checkpoint
                            segments and the executor wraps each
                            segment's backward re-trace in
                            jax.checkpoint — activations are recomputed
                            instead of stashed (XLA temp bytes drop;
                            exe.memory_stats() shows the movement)
      recompute_checkpoints var names marking segment boundaries (the
                            reference RecomputeConfig.checkpoints); empty
                            = automatic ~sqrt(#ops) split
      recompute_segments    override the automatic segment count (0 =
                            sqrt heuristic)
      gradient_merge_k      k > 1 compiles the train step as a lax.scan
                            over k microbatches (feed batch must be
                            divisible by k): f32 gradient accumulators,
                            ONE optimizer update and ONE dispatch per k
                            microbatches; fp16 FoundInfinite from any
                            microbatch gates the merged update
      gradient_merge_avg    divide the MERGED gradient by k once
                            (single-large-batch semantics); False sums

    GSPMD sharding knobs (the shard_propagation pass, static/passes.py;
    `PADDLE_IR_PASSES=0` disables them with the rest of the pipeline):

      mesh_shape            {'dp': 2, 'tp': 2}-style axis sizes; non-empty
                            turns on the shard_propagation pass and the
                            executor compiles the step over a real
                            jax.sharding.Mesh of that shape (the pjit
                            in/out_shardings pattern). Axes named 'dp' /
                            'data' carry the feed batch dim.
      sharding_hints        {var_name: PartitionSpec-like tuple} seed
                            specs, e.g. {'fc_w_0': (None, 'tp')} for a
                            column-parallel weight or ('tp', None) for a
                            row-parallel one (the pass counts the psum on
                            the contracted dim). Specs propagate across
                            every VarDesc through op-level rules; feeds
                            default to batch-over-'dp'.
      pipeline_stages       S > 1 splits the forward region into S
                            contiguous stages and composes the
                            gradient-merge microbatch loop into a
                            pipeline schedule (requires
                            gradient_merge_k > 1 — the k microbatches
                            are the pipeline's microbatches)
      pipeline_schedule     "gpipe" (fill-drain, the default and the
                            escape leg) | "1f1b" (one-forward-one-
                            backward: bounded activation stash, the
                            warmup bubble amortised over the full
                            forward+backward steady state) |
                            "interleaved" (1F1B over
                            pipeline_interleave virtual chunks per
                            worker). `PADDLE_PP_SCHEDULE` overrides.
      pipeline_interleave   virtual stages per worker for
                            pipeline_schedule="interleaved"
                            (pipeline_stages must divide by it)
      zero_stage            0 | 2 | 3: ZeRO sharded optimizer states
                            over the dp axis, riding the quantized
                            comm layer (requires comm_quant engaged —
                            the grad reduce decomposes into the same
                            ring's reduce-scatter + all-gather).
                            Stage 2 shards optimizer states; stage 3
                            also shards the params between steps.
                            `PADDLE_ZERO=0` is the escape leg.

    Communication-efficiency knobs (the comm_bucketing concern in
    static/passes.py + parallel/collectives.py; pure data-parallel
    meshes only — `PADDLE_QUANT_ALLREDUCE=0` is the bitwise escape):

      comm_quant            "int8" | "bf16" | "off": quantize the DP
                            gradient all-reduce (EQuARX-style blocked
                            encodings, f32 accumulation at every reduce
                            hop). The executor compiles an explicit
                            bucketed ring all-reduce into the step;
                            ineligible configs fall back to the XLA f32
                            path with a dispatch-counter reason.
      comm_bucket_bytes     target f32 payload bytes per gradient
                            bucket; buckets are ordered by backward
                            completion so bucket k's all-reduce is
                            issued while bucket k+1's is still forming
                            (reduce/compute overlap).
      comm_error_feedback   carry each device's local quantization
                            residual in DONATED executor state and fold
                            it into the next step's contribution
                            (compressed-gradient error feedback).

    Comm-layout knobs (reduce_strategy, fuse_all_reduce_ops) stay
    descriptive: XLA's SPMD partitioner owns cross-chip scheduling."""

    def __init__(self):
        self.reduce_strategy = "AllReduce"
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.memory_optimize = True
        self.enable_inplace = True
        self.constant_folding = True
        self.cse = True
        self.amp = False
        self.amp_dtype = "bfloat16"
        self.amp_level = "O1"
        self.amp_init_loss_scale = 2.0 ** 15
        self.recompute = False
        self.recompute_checkpoints = ()
        self.recompute_segments = 0
        self.gradient_merge_k = 1
        self.gradient_merge_avg = True
        self.mesh_shape = {}
        self.sharding_hints = {}
        self.pipeline_stages = 1
        self.pipeline_schedule = "gpipe"
        self.pipeline_interleave = 2
        self.zero_stage = 0
        self.comm_quant = "off"
        self.comm_bucket_bytes = 4 << 20
        self.comm_error_feedback = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 10


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy: Optional[
            BuildStrategy] = None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = ExecutionStrategy()
        self._data_parallel = False
        self._mesh: Optional[Mesh] = None
        self._loss_name = None
        self._sharding_cache = None
        self._stash_amp_feed_dtypes()

    def _stash_amp_feed_dtypes(self):
        """Publish the AMP host-cast map on the program NOW, not at the
        first run: py_reader prefetch threads started before Executor.run
        would otherwise stage their first `depth` batches f32 and force
        a second compile of the training step."""
        from .passes import amp_feed_dtypes_cached, resolve_amp

        prog = self._program
        if hasattr(prog, "global_block"):
            prog._amp_feed_dtypes = amp_feed_dtypes_cached(
                prog, resolve_amp(self._build_strategy))

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, places=None):
        self._data_parallel = True
        self._sharding_cache = None
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
            self._stash_amp_feed_dtypes()
        if exec_strategy is not None:
            self._exec_strategy = exec_strategy
        from ..parallel.mesh import DATA_AXIS_NAMES, create_mesh, get_mesh
        self._mesh = get_mesh()
        if self._mesh is None or not any(
                a in self._mesh.axis_names for a in DATA_AXIS_NAMES):
            n = len(places) if places else len(jax.devices())
            self._mesh = create_mesh({"data": n})
        return self

    def _data_sharding(self):
        """Sharding map consumed by Executor._build: feed names -> sharding
        (batch split over the mesh's data-like axes — mesh.data_sharding
        derives them from the axis names, so a 'dp' mesh works as well as
        the classic 'data' one), "__param__" -> replicated. Built once
        and cached — the executor applies it when state is first uploaded
        (and via in/out_shardings on the compiled step), so chained steps
        never re-partition resident state."""
        if not self._data_parallel or self._mesh is None:
            return None
        # keyed on the program version: data vars added after the first
        # run (another py_reader, a late feed) still get batch-split
        version = getattr(self._program, "_version", 0)
        if self._sharding_cache is None or \
                self._sharding_cache[0] != version:
            from ..parallel.mesh import data_sharding
            shard = data_sharding(self._mesh)
            rep = NamedSharding(self._mesh, PartitionSpec())
            feeds = {v.name: shard for v in self._program.list_vars()
                     if v.desc.is_data}
            feeds["__param__"] = rep
            self._sharding_cache = (version, feeds)
        return self._sharding_cache[1]
