"""Analytic cost model over the *optimized* Program IR: per-op and
per-step ``{model_flops, hbm_bytes, comm_bytes}`` derived from the
OpDescs the pass pipeline actually compiles — not from hand-coded
per-model closed forms.

Accounting conventions (PaLM-style MFU numerator):

- ``model_flops`` counts matmul-class ops only (matmul/mul/conv) at
  2 FLOPs per MAC; elementwise/reduction/normalization ops contribute
  HBM bytes, not FLOPs — they are bandwidth-bound and excluded from the
  MFU numerator exactly like benchmarks/flops.py's closed forms do.
- ``hbm_bytes`` is the dtype-aware payload traffic of every op: input
  reads + output writes from VarDesc shapes and dtypes. The AMP pass
  stamps rewritten vars bf16/fp16, so mixed-precision bytes halve with
  no extra bookkeeping here. Gather-class ops (lookup_table, gather)
  read the gathered rows, never the whole table.
- ``comm_bytes`` is cross-chip traffic from the shard_propagation
  stamps: an op carrying ``__psum_axes`` costs a ring all-reduce of its
  per-shard output over those axes (``2*(g-1)/g`` bytes per payload
  byte).

The executor's real step structure folds in on top of the per-op walk:

- a ``backward`` op multiplies every forward op by 3 (one forward + two
  backward passes, the PaLM train-step convention); ops stamped
  ``__remat_seg`` add one more forward (the recompute pass re-runs the
  segment in the backward)
- ``gradient_merge_k``: ops in the scanned region (forward + backward +
  an adjacent ``check_finite_and_unscale``) run per microbatch at
  ``B/k`` and are counted k times; the optimizer region runs once — the
  compiled ``lax.scan`` structure, mirrored
- sharding (``__sharding_spec`` stamps + the build's mesh axis sizes):
  an op's work divides by the product of the distinct mesh axes its
  operands are partitioned over — per-CHIP cost, matching per-chip MFU
- ``pipeline_stages`` is recorded (pipelining moves work in time, not
  in amount); with a schedule the report also carries the analytic
  bubble fraction (``parallel.pipeline.schedule_bubble_fraction``), so
  the roofline can discount idle slots per schedule
- ``zero`` (the engaged ZeRO stage): the gradient traffic decomposes
  into a ``comm_reduce_scatter`` of the ENCODED bucket (half the ring)
  plus a ``comm_all_gather`` of the updated params in RAW f32 — the
  exact wire structure static/stepplan.py's zero kind compiles —
  instead of the single ``comm_allreduce`` pseudo-op

Everything is static VarDesc arithmetic — no tracing, no device touch —
so a cost report for a BERT-sized program costs microseconds and can
run per compiled executable in the executor hot path (cached on the
executable's cache entry).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["OpCost", "CostReport", "program_cost", "paged_decode_cost",
           "kv_offload_page_bytes"]

# matmul-class ops: the MFU numerator (2 FLOPs per MAC)
_MATMUL_OPS = {"mul", "matmul", "matmul_v2"}
# conv ops as the IR actually emits them (layers.py conv2d,
# layers_ext/layers_compat "_s"-suffixed 3D + transpose forms). Weight
# layouts differ: forward convs carry (Co, Ci/g, k...) and cost per
# OUTPUT element; transpose convs carry (Ci, Co/g, k...) and cost per
# INPUT element — both are 2 * elements * prod(W.shape[1:]) FLOPs.
_CONV_OPS = {"conv2d", "conv3d_s"}
_CONV_TRANSPOSE_OPS = {"conv2d_transpose_s", "conv3d_transpose_s"}
# gather-class: read the gathered rows + indices, not the whole table
_GATHER_OPS = {"lookup_table", "lookup_table_v2", "gather", "gather_nd",
               "embedding"}
# layout-only ops XLA compiles away: no HBM traffic charged
_FREE_OPS = {"feed", "fetch", "backward", "reshape2", "assign",
             "share_data", "shape", "increment"}
# write-only producers: charge the output, there is no tensor input
_PRODUCER_OPS = {"fill_constant", "assign_value", "gaussian_random",
                 "uniform_random", "truncated_gaussian_random",
                 "uniform_random_batch_size_like", "randint", "range",
                 "eye", "one_hot", "one_hot_v2"}

_ITEMSIZE = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "float16": 2, "bfloat16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
}


def _itemsize(dtype) -> int:
    return _ITEMSIZE.get(str(dtype), 4)


def _prod(seq) -> int:
    out = 1
    for v in seq:
        out *= int(v)
    return out


def _spec_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (list, tuple)) else (entry,)


class OpCost:
    """One op's per-step cost after structure multipliers: ``flops``
    (model FLOPs), ``hbm_bytes``, ``comm_bytes``; ``mult`` is the step
    multiplier applied (fwd/bwd/remat × gradient-merge k),
    ``shard_factor`` the per-chip division."""

    __slots__ = ("index", "type", "out", "flops", "hbm_bytes",
                 "comm_bytes", "mult", "shard_factor")

    def __init__(self, index, type, out, flops, hbm_bytes, comm_bytes,
                 mult, shard_factor):
        self.index = index
        self.type = type
        self.out = out
        self.flops = flops
        self.hbm_bytes = hbm_bytes
        self.comm_bytes = comm_bytes
        self.mult = mult
        self.shard_factor = shard_factor

    @property
    def arith_intensity(self) -> float:
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0

    def to_dict(self) -> dict:
        return {"index": self.index, "type": self.type, "out": self.out,
                "flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "comm_bytes": self.comm_bytes, "mult": self.mult,
                "shard_factor": self.shard_factor,
                "arith_intensity": round(self.arith_intensity, 3)}


class CostReport:
    """Per-op costs plus step totals for one optimized program."""

    def __init__(self, ops: List[OpCost], gm_k: int = 1,
                 pp_stages: int = 1, n_shards: int = 1,
                 batch: int = 1, schedule: str = "gpipe",
                 interleave: int = 2, zero_stage: int = 0):
        self.ops = ops
        self.gm_k = gm_k
        self.pp_stages = pp_stages
        self.n_shards = n_shards
        self.batch = batch
        self.schedule = schedule or "gpipe"
        self.interleave = int(interleave or 2)
        self.zero_stage = int(zero_stage or 0)
        self.model_flops = sum(o.flops for o in ops)
        self.hbm_bytes = sum(o.hbm_bytes for o in ops)
        self.comm_bytes = sum(o.comm_bytes for o in ops)

    @property
    def arith_intensity(self) -> float:
        return (self.model_flops / self.hbm_bytes
                if self.hbm_bytes else 0.0)

    @property
    def moe_a2a_bytes(self) -> int:
        """Wire bytes of the explicit MoE dispatch/combine all_to_alls
        (moe ops stamped ``__moe_ep`` by shard propagation)."""
        return sum(o.comm_bytes for o in self.ops if o.type == "moe")

    @property
    def pp_bubble_frac(self) -> float:
        """Analytic idle fraction of the pipelined step under the
        compiled schedule — 0.0 when not pipelined (S <= 1 or a single
        microbatch leaves nothing to overlap)."""
        if self.pp_stages <= 1 or self.gm_k <= 1:
            return 0.0
        from ..parallel.pipeline import schedule_bubble_fraction

        return schedule_bubble_fraction(
            self.schedule, self.pp_stages, self.gm_k, self.interleave)

    def by_type(self, field: str = "flops") -> Dict[str, int]:
        out: Dict[str, int] = {}
        for o in self.ops:
            v = getattr(o, field)
            if v:
                out[o.type] = out.get(o.type, 0) + v
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def top_ops(self, k: int = 10, by: str = "flops") -> List[OpCost]:
        return sorted((o for o in self.ops if getattr(o, by)),
                      key=lambda o: -getattr(o, by))[:k]

    def to_dict(self, top: int = 20) -> dict:
        """JSON-able summary — what the executor stamps into the
        step-trace ``cost`` record and ``exe.cost_stats()`` returns."""
        return {
            "model_flops": self.model_flops,
            "hbm_bytes": self.hbm_bytes,
            "comm_bytes": self.comm_bytes,
            "moe_a2a_bytes": self.moe_a2a_bytes,
            "arith_intensity": round(self.arith_intensity, 3),
            "n_ops": len(self.ops),
            "batch": self.batch,
            "gm_k": self.gm_k,
            "pp_stages": self.pp_stages,
            "pp_schedule": self.schedule,
            "pp_bubble_frac": round(self.pp_bubble_frac, 4),
            "zero_stage": self.zero_stage,
            "n_shards": self.n_shards,
            "flops_by_type": self.by_type("flops"),
            "bytes_by_type": self.by_type("hbm_bytes"),
            "top_flops": [o.to_dict() for o in self.top_ops(top, "flops")],
            "top_bytes": [o.to_dict()
                          for o in self.top_ops(top, "hbm_bytes")],
        }


def _resolve_batch(block, feed_shapes: Optional[Dict[str, Sequence[int]]],
                   batch_size: Optional[int]) -> int:
    """The dynamic-dim substitution value: derived from the live feed
    shapes against the data VarDescs' ``-k`` sentinel dims (``-k`` means
    "dynamic batch times static k"), else ``batch_size``, else 1."""
    if feed_shapes:
        for name, shape in feed_shapes.items():
            v = block.vars.get(name)
            dshape = getattr(v, "shape", None)
            if not dshape or not shape:
                continue
            d0 = -1 if dshape[0] is None else int(dshape[0])
            if d0 < 0 and int(shape[0]) > 0:
                return max(1, int(shape[0]) // -d0)
    return max(1, int(batch_size or 1))


def program_cost(program, feed_shapes=None, batch_size=None, gm=None,
                 shard_cfg=None, pp=None, comm=None, schedule=None,
                 interleave=None, zero=None) -> CostReport:
    """Walk ``program``'s optimized global block into a CostReport.

    ``feed_shapes``: {data var name -> live array shape} — resolves the
    dynamic batch dim. ``gm``/``shard_cfg``/``pp`` are the executor's
    resolve_gradient_merge/resolve_sharding/resolve_pipeline results for
    the build (None each when off). ``comm`` is the resolve_comm result
    when the build compiled the EXPLICIT quantized DP gradient
    all-reduce (parallel/collectives.py): the gradient buckets then
    charge their ENCODED ring bytes (payload + per-block scales, the
    encoded_nbytes closed form) into comm_bytes as a ``comm_allreduce``
    pseudo-op — never the f32 bytes the escape leg would move, so
    step_comm_bytes and the perf_report roofline stay truthful under
    quantization. (With comm=None the DP grad reduce is XLA's implicit
    f32 psum, uncharged — the pre-quantization accounting, unchanged.)

    ``schedule``/``interleave`` are the resolve_pipeline_schedule
    result for a pipelined build (None otherwise): they pick the
    closed-form ``pp_bubble_frac`` the report exposes. ``zero`` is the
    ZeRO stage (2|3) WHEN THE STEP-PLAN ENGAGED it (None otherwise —
    the caller gates on the live zero plan, mirroring how ``comm``
    gates on comm_stats): the gradient ring then splits into a
    ``comm_reduce_scatter`` pseudo-op at the encoded half-ring bytes
    plus a ``comm_all_gather`` at the RAW f32 updated-param bytes (the
    optimizer consumes the unquantized reduced chunk and re-broadcasts
    params unencoded — stepplan.py's wire structure, both stages move
    one param gather per step)."""
    block = program.global_block
    comm_cfg = comm   # the per-op loop below reuses `comm` as a local
    batch = _resolve_batch(block, feed_shapes, batch_size)
    axis_sizes: Dict[str, int] = dict(shard_cfg[0]) if shard_cfg else {}
    n_shards = _prod(axis_sizes.values()) if axis_sizes else 1

    ops = block.ops
    first_bwd = next((i for i, op in enumerate(ops)
                      if op.type == "backward"), None)
    gm_k = int(gm[0]) if (gm and first_bwd is not None) else 1
    scan_end = len(ops)
    if first_bwd is not None:
        scan_end = first_bwd + 1
        if scan_end < len(ops) and \
                ops[scan_end].type == "check_finite_and_unscale":
            scan_end += 1

    def shape_of(name: str, b: int) -> Optional[Tuple[int, ...]]:
        v = block.vars.get(name)
        shape = getattr(v, "shape", None)
        if shape is None:
            return None
        # dynamic dims come as -k ("dynamic batch times k") or a bare
        # None (the Paddle 2.x [None, ...] spelling) — both resolve
        # through the batch substitution
        return tuple(int(-(d if d is not None else -1)) * b
                     if d is None or int(d) < 0 else int(d)
                     for d in shape)

    def nbytes_of(name: str, b: int) -> int:
        shape = shape_of(name, b)
        if shape is None:
            return 0
        v = block.vars.get(name)
        return _prod(shape) * _itemsize(getattr(v, "dtype", "float32"))

    def spec_of(name: str):
        v = block.vars.get(name)
        return (getattr(v, "attrs", None) or {}).get("__sharding_spec")

    def shard_axes_of(op) -> Tuple[str, ...]:
        """Distinct mesh axes partitioning any operand of ``op`` (or its
        psum stamp): the op's work divides by their size product —
        row-parallel matmuls shard the contracted (input) dim, column-
        parallel the output dim, dp the batch dim; the union covers all
        three."""
        axes = set(op.attrs.get("__psum_axes") or ())
        for name in list(op.input_names()) + list(op.output_names()):
            for entry in (spec_of(name) or ()):
                axes.update(a for a in _spec_axes(entry)
                            if a in axis_sizes)
        return tuple(a for a in axes if a in axis_sizes)

    out: List[OpCost] = []
    for i, op in enumerate(ops):
        t = op.type
        if t in ("feed", "fetch", "backward"):
            continue
        # region structure: forward ops run 1 fwd + 2 bwd passes when a
        # backward op exists (+1 recompute under remat); the scanned
        # region repeats per microbatch at B/k; the optimizer region
        # runs once on the merged gradient at full batch
        in_scan = first_bwd is not None and i < scan_end
        b = max(1, batch // gm_k) if (in_scan and gm_k > 1) else batch
        if first_bwd is not None and i < first_bwd:
            mult = 3 + (1 if "__remat_seg" in op.attrs else 0)
        else:
            mult = 1
        if in_scan and gm_k > 1:
            mult *= gm_k

        ins = [n for n in op.input_names()]
        outs = [n for n in op.output_names()]
        flops = 0
        moe_comm = 0
        if t == "mul":
            o = outs[0] if outs else None
            oshape = shape_of(o, b) if o else None
            xshape = shape_of((op.inputs.get("X") or [None])[0], b)
            ncol = int(op.attrs.get("x_num_col_dims", 1))
            if oshape and xshape:
                k_dim = _prod(xshape[ncol:])
                flops = 2 * _prod(oshape) * k_dim
        elif t in _MATMUL_OPS:
            o = outs[0] if outs else None
            oshape = shape_of(o, b) if o else None
            xshape = shape_of((op.inputs.get("X") or [None])[0], b)
            if oshape and xshape:
                # both attr spellings: "transpose_X" (matmul) and
                # "trans_x" (matmul_v2 from deserialized 2.x programs —
                # the shard pass defends against the same pair)
                trans_x = (op.attrs.get("transpose_X")
                           or op.attrs.get("trans_x"))
                k_dim = int(xshape[-2] if trans_x else xshape[-1])
                flops = 2 * _prod(oshape) * k_dim
        elif t in _CONV_OPS or t in _CONV_TRANSPOSE_OPS:
            if t in _CONV_TRANSPOSE_OPS:
                base_name = (op.inputs.get("Input") or [None])[0]
            else:
                base_name = outs[0] if outs else None
            bshape = shape_of(base_name, b) if base_name else None
            wshape = shape_of((op.inputs.get("Filter")
                               or op.inputs.get("W") or [None])[0], b)
            if bshape and wshape:
                flops = 2 * _prod(bshape) * _prod(wshape[1:])
        elif t == "moe":
            # gate matmul + dispatch/combine einsums over the (e, c, d)
            # capacity grid + the expert FFNs on their capacity blocks
            xshape = shape_of((op.inputs.get("X") or [None])[0], b)
            w1shape = shape_of((op.inputs.get("W1") or [None])[0], b)
            if xshape and w1shape:
                tkn, d = _prod(xshape[:-1]), int(xshape[-1])
                e, h = int(w1shape[0]), int(w1shape[-1])
                cf = float(op.attrs.get("capacity_factor", 2.0))
                cap = max(1, int(cf * tkn / e))
                flops = (2 * tkn * d * e          # gate logits
                         + 4 * tkn * e * cap * d  # dispatch + combine
                         + 4 * e * cap * d * h)   # two FFN matmuls
                ep = op.attrs.get("__moe_ep")
                if ep:
                    # explicit exchange plan: charge the two hand-
                    # placed all_to_alls (dispatch may ride int8)
                    from ..nn.moe import moe_a2a_nbytes

                    moe_comm = moe_a2a_nbytes(
                        e, cap, d, int(ep[1]),
                        op.attrs.get("dispatch_codec") or None)

        if t == "paged_attention":
            # ragged paged decode attention: only the GATHERED live
            # pages (page-table entries x page bytes, K and V) count
            # toward hbm_bytes — never the whole pool the KPages/VPages
            # operands declare. FLOPs are the two attention matmuls
            # (scores + values) over the table-bounded context, the
            # same accounting the closed forms use.
            q_name = (op.inputs.get("Q") or [None])[0]
            kp_name = (op.inputs.get("KPages") or [None])[0]
            pt_name = (op.inputs.get("PageTable") or [None])[0]
            qshape = shape_of(q_name, b) if q_name else None
            kshape = shape_of(kp_name, b) if kp_name else None
            tshape = shape_of(pt_name, b) if pt_name else None
            if qshape and kshape and tshape:
                h, d = qshape[-2], qshape[-1]
                page_size = kshape[-3]
                live_tokens = _prod(tshape) * page_size
                kp_dtype = str(getattr(block.vars.get(kp_name),
                                       "dtype", "float32"))
                if kp_dtype in ("int8", "uint8"):
                    # quantized pool (kv_codec="int8"): the DMA moves
                    # the ENCODED page — int8 payload + one f32 scale
                    # per token row (ps/codec blocked layout with
                    # block = H*D), the same closed form the wire
                    # codec and the engine gauges share
                    from ..ps.codec import encoded_nbytes

                    kv_bytes = 2 * live_tokens * encoded_nbytes(
                        h * d, "int8", block=h * d)
                else:
                    kv_bytes = (2 * live_tokens * h * d
                                * _itemsize(kp_dtype))
                flops = 4 * h * d * live_tokens   # 2 matmuls x 2 F/MAC
                hbm = (kv_bytes                         # live K+V pages
                       + sum(nbytes_of(n, b) for n in (q_name,) if n)
                       + sum(nbytes_of(n, b) for n in outs)
                       + (nbytes_of(pt_name, b) if pt_name else 0))
            else:
                hbm = 0
        elif t in _FREE_OPS:
            hbm = 0
        elif t in _PRODUCER_OPS:
            hbm = sum(nbytes_of(n, b) for n in outs)
        elif t in _GATHER_OPS:
            # reads gathered rows (== out bytes) + indices, writes out
            ids = (op.inputs.get("Ids") or op.inputs.get("Index")
                   or [None])[0]
            out_b = sum(nbytes_of(n, b) for n in outs)
            hbm = 2 * out_b + (nbytes_of(ids, b) if ids else 0)
        else:
            hbm = (sum(nbytes_of(n, b) for n in ins)
                   + sum(nbytes_of(n, b) for n in outs))

        shard_axes = shard_axes_of(op)
        factor = _prod(axis_sizes[a] for a in shard_axes) \
            if shard_axes else 1
        comm = 0
        psum_axes = [a for a in (op.attrs.get("__psum_axes") or ())
                     if a in axis_sizes]
        if psum_axes and outs:
            g = _prod(axis_sizes[a] for a in psum_axes)
            if g > 1:
                # ring all-reduce of the per-shard output block: the
                # output spec's axes give its partitioning BEFORE the
                # psum replicates it over the contracted axes
                out_axes = {a for n in outs
                            for entry in (spec_of(n) or ())
                            for a in _spec_axes(entry)
                            if a in axis_sizes}
                out_factor = _prod(axis_sizes[a] for a in out_axes) \
                    if out_axes else 1
                payload = sum(nbytes_of(n, b) for n in outs) // out_factor
                comm = int(2 * (g - 1) * payload // g) * mult
        if moe_comm:
            comm += int(moe_comm) * mult

        out.append(OpCost(
            index=i, type=t, out=(outs[0] if outs else ""),
            flops=flops * mult // factor,
            hbm_bytes=hbm * mult // factor,
            comm_bytes=comm, mult=mult, shard_factor=factor))

    if comm_cfg is not None and first_bwd is not None:
        from .passes import comm_bucket_plan, comm_data_axis

        axis = comm_data_axis(shard_cfg)
        plan = (comm_bucket_plan(block, comm_cfg, axis[1])
                if axis is not None else None)
        if plan and zero:
            # ZeRO decomposition: the grad moves as the encoded
            # reduce-scatter HALF of the ring; the optimizer updates
            # its local chunk and the params come back as a raw-f32
            # all-gather (stage 2 post-update, stage 3 pre-forward —
            # one per step either way). Both once per step, like the
            # all-reduce they replace.
            from ..parallel.collectives import (all_gather_nbytes,
                                                reduce_scatter_nbytes)

            g = axis[1]
            out.append(OpCost(
                index=first_bwd, type="comm_reduce_scatter", out="",
                flops=0, hbm_bytes=0,
                comm_bytes=sum(
                    reduce_scatter_nbytes(b["elems"], g, comm_cfg[0])
                    for b in plan),
                mult=1, shard_factor=1))
            out.append(OpCost(
                index=first_bwd, type="comm_all_gather", out="",
                flops=0, hbm_bytes=0,
                comm_bytes=sum(
                    all_gather_nbytes(b["elems"], g, "f32")
                    for b in plan),
                mult=1, shard_factor=1))
        elif plan:
            # the bucketed quantized all-reduce runs ONCE per step on
            # the merged gradient (no gm multiplier — the PR 5
            # quantize-once-per-step discipline)
            out.append(OpCost(
                index=first_bwd, type="comm_allreduce", out="",
                flops=0, hbm_bytes=0,
                comm_bytes=sum(b["ring_encoded"] for b in plan),
                mult=1, shard_factor=1))

    return CostReport(out, gm_k=gm_k, pp_stages=int(pp or 1),
                      n_shards=n_shards, batch=batch,
                      schedule=schedule or "gpipe",
                      interleave=interleave or 2,
                      zero_stage=int(zero or 0))


def paged_decode_cost(config, live_lens: Sequence[int], page_size: int,
                      itemsize: int = 4,
                      kv_codec: str = "off") -> Dict[str, float]:
    """Analytic cost of ONE ragged paged decode step — the decode
    engine's source for the ``step_model_flops`` / ``step_hbm_bytes``
    / ``mfu`` / ``arith_intensity`` gauges (PR 12 plane), kept truthful
    on decode: attention HBM counts the GATHERED LIVE PAGES of each
    sequence (``ceil(len/page_size) * page_size`` positions), never the
    whole pool.

    ``config`` carries the model dims (``DecodeModelConfig`` or
    anything with n_layers/n_heads/head_dim/ffn_dim/vocab_size);
    ``live_lens`` is the attended context length per LIVE slot this
    step.

    FLOPs (matmul-class only, the MFU numerator): per live token the
    qkv+out projections (8E²) + ffn pair (4EF) + vocab head (2EV), plus
    per layer the two attention matmuls over the live context (4·E·ctx).
    HBM: the weights stream once per step (decode is bandwidth-bound
    precisely because of this) + the live K/V pages read and the new
    token's K/V written.

    With ``kv_codec="int8"`` the K/V page traffic is charged at the
    ENCODED byte cost — ``ps.codec.encoded_nbytes(E, "int8", block=E)``
    per token row (int8 payload + one f32 scale), the exact layout the
    pool stores — while params/logits stay at ``itemsize``."""
    L = int(config.n_layers)
    H = int(config.n_heads)
    D = int(config.head_dim)
    E = H * D
    F = int(config.ffn_dim)
    V = int(config.vocab_size)
    n = len(live_lens)
    if kv_codec == "int8":
        from ..ps.codec import encoded_nbytes

        kv_row_bytes = encoded_nbytes(E, "int8", block=E)
    else:
        kv_row_bytes = E * itemsize
    flops = 0
    page_tokens = 0
    for ln in live_lens:
        flops += L * (8 * E * E + 4 * E * F + 4 * E * int(ln)) \
            + 2 * E * V
        page_tokens += -(-int(ln) // int(page_size)) * int(page_size)
    param_bytes = (L * (4 * E * E + 2 * E * F) + 2 * V * E) * itemsize
    hbm = (param_bytes
           + 2 * L * page_tokens * kv_row_bytes     # live K+V pages read
           + 2 * L * n * kv_row_bytes               # new K+V written
           + n * V * itemsize)                      # logits out
    return {"model_flops": int(flops), "hbm_bytes": int(hbm),
            "arith_intensity": flops / hbm if hbm else 0.0,
            "live_slots": n, "live_page_tokens": int(page_tokens),
            "kv_codec": kv_codec,
            "kv_row_bytes": int(kv_row_bytes)}


def kv_offload_page_bytes(config, page_size: int) -> int:
    """Encoded bytes ONE KV page costs in the host offload tier — the
    closed form behind ``HostKVPool.page_nbytes`` and the d2h/h2d
    traffic the ``kv_offload_bytes`` counter charges per spilled page.

    Host records are always int8 rows regardless of the device pool
    dtype (f32 pools pay one deterministic row quantize on the way
    out), so the cost is the ps/codec blocked layout with block = one
    token row: K and V planes, ``n_layers`` each, ``page_size`` rows of
    ``n_heads * head_dim`` int8 payload plus one f32 scale per row."""
    from ..ps.codec import encoded_nbytes

    row = int(config.n_heads) * int(config.head_dim)
    return 2 * int(config.n_layers) * encoded_nbytes(
        int(page_size) * row, "int8", block=row)
