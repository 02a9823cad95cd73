"""Fused linear + softmax cross-entropy for large vocabularies.

The BERT MLM head computes logits = h @ W^T + b with W the tied
(vocab, hidden) embedding table, then softmax-xent over vocab. At the
benchmark's BERT-base cells (64 x 512 rows, 5,120 of them labelled,
vocab 30592) the logits of the 8,192 rows the kernels are handed would
be 0.5 GB in bf16 (2 GB for all 32,768) — written to HBM by the matmul,
read back by the softmax, and the same again for dlogits in the
backward. That HBM traffic is pure overhead: these Pallas kernels
stream W in vocab tiles over a 2D grid (rows-block outer, vocab-block
inner — the inner axis revisits the same output block, the canonical
Pallas reduction idiom), carrying an online max/sumexp + label-logit
forward and recomputing the logit blocks in the backward for dh and
dW/db (the flash trick: p = exp(s - lse) needs only the saved lse).
Logits never land in HBM in either direction.

Rows whose label is ignored add nothing to the loss or to any gradient,
so the single-device path leaves them out: it counts the labelled rows
on the device, orders the rows labelled-first and runs the kernels on
the first K of them, K the smallest rung of a short ladder of static
row capacities (``_ladder``) that holds the count, picked with
``lax.switch``. Each rung's kernels run under a role name of their own
(``_tag``), so a device trace shows which rung ran and the work
ledger charges exactly that rung's rows.

Reference analog: softmax_with_cross_entropy_op.cu fuses softmax+xent
(but not the matmul); the matmul fusion is the TPU-native extension
the MFU push needs (VERDICT r4 #2). XLA fallback covers ineligible
shapes/backends; dispatch truth via ops.pallas.counters("fused_xent").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...framework.flags import define_flag
from .counters import kernel_call, nbytes
from .flash_attention import _dot, _sds

define_flag("fused_vocab_xent", True,
            "Route large-vocab linear+cross-entropy heads (BERT MLM) "
            "through the streamed Pallas kernel; False materialises "
            "logits via XLA (the A/B arm for the live session)")

_F32 = jnp.float32
_NEG = -1e30



_BN_CANDIDATES = (1024, 512, 256)
_BV_CANDIDATES = (512, 384, 256, 128)
#: pad modulus = the smallest row block we can always fall back to
_BN_MIN = _BN_CANDIDATES[-1]
#: per-kernel budget (bytes) for the block-resident f32 tensors as
#: _fits counts them
_VMEM_BUDGET = 10 * 1024 * 1024
#: scoped-VMEM limit handed to Mosaic. _fits counts one f32 copy of each
#: block; Mosaic also double-buffers every block and keeps more
#: temporaries live, and against the 16 MiB default the two largest
#: admitted working sets did not compile (measured on a v5e, PR 21: dW at
#: bn=1024 bv=128 hd=768 bf16 took 16.02 MB, dh at bn=512 bv=128 hd=2048
#: f32 18.01 MB — up to 2.1x _fits' count). 3x the budget, of 128 MiB
#: physical.
_VMEM_LIMIT = 3 * _VMEM_BUDGET


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _fits(bn, bv, hd):
    """Both backward kernels' block-resident f32 footprints must fit:
    dh holds h + f32 dh accumulator + w tile + s/p pair; dW holds
    h + w + f32 dW accumulator + s/p pair. Overflow fails Mosaic at
    COMPILE time, so no over-budget pair may ever be picked."""
    dh_kernel = 4 * (2 * bn * hd + bv * hd + 2 * bn * bv)
    dw_kernel = 4 * (bn * hd + 2 * bv * hd + 2 * bn * bv)
    return max(dh_kernel, dw_kernel) <= _VMEM_BUDGET


def _pick_blocks(n, hd, v):
    """Joint (block_n, block_v) choice, LARGEST bn first: every grid
    row-block streams the ENTIRE weight table once (47 MB for BERT),
    so bn — not bv — sets the dominant HBM traffic; at the benchmark's
    rung (n=8192, hd=768) 1024-row blocks read W 8x (~0.38 GB) vs 32x
    (~1.5 GB) at 256. A greedy-large bv that forced a smaller bn under
    the VMEM cap would double exactly that traffic, so bv concedes
    first. Returns None when nothing divides + fits (dispatch falls
    back to XLA via _eligible). Vocab lane modulus 128: BERT's 30592
    = 128 * 239 only admits 128-wide vocab blocks anyway."""
    for bn in _BN_CANDIDATES:
        if n % bn != 0:
            continue
        for bv in _BV_CANDIDATES:
            if v % bv == 0 and _fits(bn, bv, hd):
                return bn, bv
    return None


# ---------------------------------------------------------------------------
# forward: grid (rows/bn, vocab/bv); m/l/ll accumulators live in output
# refs indexed by the row block only (inner vocab steps revisit them)
# ---------------------------------------------------------------------------


def _fwd_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, ll_ref, m_ref,
                l_ref, *, num_v, block_v):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    h = h_ref[...].astype(_F32)                    # (bn, H)
    labels = lab_ref[0, :]                         # (bn,)
    bn = h.shape[0]
    s = _dot(h, w_ref[...].astype(_F32), trans_b=True)   # (bn, bv)
    s = s + b_ref[0, :][None, :]
    m = m_ref[0, :]
    l = l_ref[0, :]
    m_new = jnp.maximum(m, jnp.max(s, axis=1))
    l_new = l * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(s - m_new[:, None]), axis=1)
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, block_v),
                                                 1)
    hit = col == labels[:, None]
    ll_ref[...] = ll_ref[...] + jnp.sum(
        jnp.where(hit, s, 0.0), axis=1)[None, :]
    m_ref[...] = m_new[None, :]
    l_ref[...] = l_new[None, :]

    @pl.when(j == num_v - 1)
    def _finalize():
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


# ---------------------------------------------------------------------------
# backward: dh over (rows, vocab) grid; dW/db over (vocab, rows) grid
# ---------------------------------------------------------------------------


def _bwd_dh_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dh_ref, *,
                   block_v):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    h = h_ref[...].astype(_F32)
    w = w_ref[...].astype(_F32)
    labels = lab_ref[0, :]
    lse = lse_ref[0, :]
    g = g_ref[0, :]
    bn = h.shape[0]
    s = _dot(h, w, trans_b=True) + b_ref[0, :][None, :]
    p = jnp.exp(s - lse[:, None])
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, block_v),
                                                 1)
    p = p - (col == labels[:, None]).astype(_F32)
    # dh_ref is f32 regardless of input dtype: accumulating across the
    # vocab grid steps in bf16 would compound rounding per step
    dh_ref[...] = dh_ref[...] + _dot(p * g[:, None], w)


def _bwd_dw_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref,
                   dw_ref, db_ref, *, block_n, block_v):
    from jax.experimental import pallas as pl

    vj = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    w = w_ref[...].astype(_F32)                     # (bv, H)
    bv = w.shape[0]
    h = h_ref[...].astype(_F32)                     # (bn, H)
    labels = lab_ref[0, :]
    lse = lse_ref[0, :]
    g = g_ref[0, :]
    s = _dot(h, w, trans_b=True) + b_ref[0, :][None, :]
    p = jnp.exp(s - lse[:, None])
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, bv), 1)
    p = (p - (col == labels[:, None]).astype(_F32)) * g[:, None]
    # f32 accumulator refs (cast to the param dtype happens outside)
    dw_ref[...] = dw_ref[...] + _dot(p.T, h)
    db_ref[...] = db_ref[...] + jnp.sum(p, axis=0)[None, :]


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom_vjp
# ---------------------------------------------------------------------------


def _fwd_call(h, w, bias, labels, block_n, block_v, tag=""):
    from jax.experimental import pallas as pl

    n, hd = h.shape
    v = w.shape[0]
    num_v = v // block_v
    lse, ll, _m, _l = kernel_call(
        f"fused_xent_{tag}fwd",
        functools.partial(_fwd_kernel, num_v=num_v, block_v=block_v),
        grid=(n // block_n, num_v),
        in_specs=[
            pl.BlockSpec((block_n, hd), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_shape=[
            _sds((1, n), _F32, h),     # lse
            _sds((1, n), _F32, h),     # label logit
            _sds((1, n), _F32, h),     # running max (scratch-as-output)
            _sds((1, n), _F32, h),     # running sumexp
        ],
        compiler_params=_compiler_params(),
    )(h, w, bias[None, :], labels[None, :])
    return lse[0], ll[0]


def _bwd_call(h, w, bias, labels, lse, g, block_n, block_v, tag=""):
    from jax.experimental import pallas as pl

    n, hd = h.shape
    v = w.shape[0]
    # both backward kernels under the one role: a trace sums them
    dh = kernel_call(
        f"fused_xent_{tag}bwd",
        functools.partial(_bwd_dh_kernel, block_v=block_v),
        grid=(n // block_n, v // block_v),
        in_specs=[
            pl.BlockSpec((block_n, hd), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_n, hd), lambda i, j: (i, 0)),
        out_shape=_sds((n, hd), _F32, h),
        compiler_params=_compiler_params(),
    )(h, w, bias[None, :], labels[None, :], lse[None, :], g[None, :])
    dw, db = kernel_call(
        f"fused_xent_{tag}bwd",
        functools.partial(_bwd_dw_kernel, block_n=block_n,
                          block_v=block_v),
        grid=(v // block_v, n // block_n),
        in_specs=[
            pl.BlockSpec((block_n, hd), lambda vj, i: (i, 0)),
            pl.BlockSpec((block_v, hd), lambda vj, i: (vj, 0)),
            pl.BlockSpec((1, block_v), lambda vj, i: (0, vj)),
            pl.BlockSpec((1, block_n), lambda vj, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda vj, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda vj, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((block_v, hd), lambda vj, i: (vj, 0)),
            pl.BlockSpec((1, block_v), lambda vj, i: (0, vj)),
        ],
        out_shape=[
            _sds((v, hd), _F32, h),
            _sds((1, v), _F32, h),
        ],
        compiler_params=_compiler_params(),
    )(h, w, bias[None, :], labels[None, :], lse[None, :], g[None, :])
    return dh.astype(h.dtype), dw.astype(w.dtype), db[0]


def _ladder(n, block_n):
    """The static row capacities a dispatch on ``n`` rows may run at:
    n/8, n/4, n/2 and n, each rounded up to whole row blocks, ascending
    (one rung when n is one block). Powers of two of the row count, so
    no capacity is a constant someone tuned; below n/8 the head is a few
    percent of a step."""
    return tuple(sorted({-(-n // (d * block_n)) * block_n
                         for d in (8, 4, 2, 1)}))


def _tag(k, n):
    """What sets a rung's role names apart: ``fused_xent_rows<K>_fwd`` /
    ``_bwd`` below the top, today's ``fused_xent_fwd`` / ``_bwd`` at it.
    No rung's name holds another's, so a trace reader that matches rows
    by role (``benchmarks/kernel_rows.py``) charges a rung's seconds to
    that rung's declared work alone."""
    return "" if k == n else f"rows{k}_"


def _blocks(h, w):
    blocks = _pick_blocks(h.shape[0], h.shape[1], w.shape[0])
    if blocks is None:
        raise ValueError(
            f"fused_xent: no (block_n, block_v) divides+fits h "
            f"{h.shape} x w {w.shape} — dispatch should have taken the "
            "XLA path (_eligible)")
    return blocks


def _fused_xent_core(h, w, bias, labels, ignore_index):
    """mean loss = sum / clamp(count): derived from the ONE sum-form
    custom_vjp below (autodiff of the division supplies the 1/count
    the hand-written mean backward used to hard-code — r5 review
    dedup)."""
    rungs = _ladder(h.shape[0], _blocks(h, w)[0])
    s, c = _fused_xent_sums(h, w, bias, labels, ignore_index, rungs)
    return s / jnp.maximum(c, 1.0)


# -- the single custom_vjp: per-shard (loss_sum, valid_count), so the
# shard_map'd multi-device path can psum BEFORE the mean. ``rungs`` are
# the row capacities it may run at, the last one all of its rows --------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_xent_sums(h, w, bias, labels, ignore_index, rungs):
    (s, c), _ = _fused_xent_sums_fwd(h, w, bias, labels, ignore_index, rungs)
    return s, c


def _fused_xent_sums_fwd(h, w, bias, labels, ignore_index, rungs):
    n = h.shape[0]
    valid = labels != ignore_index
    # an unlabelled row that rides along in a rung (or fills the top one)
    # gets a label the in-kernel hit-test never matches; its loss is
    # masked here and its cotangent is zero in the backward
    safe = jnp.where(valid, labels, -1).astype(jnp.int32)
    bn, bv = _blocks(h, w)
    count = jnp.sum(valid, dtype=jnp.int32)
    # labelled rows first: the first K of this order are distinct, in
    # range and hold every labelled row whenever count <= K
    order = jnp.argsort(~valid, stable=True) if len(rungs) > 1 else None
    rung = jnp.sum(count > jnp.asarray(rungs[:-1], jnp.int32))

    def at(k):
        def run(h, safe, valid):
            if k == n:
                lse, ll = _fwd_call(h, w, bias, safe, bn, bv)
            else:
                rows = order[:k]
                h, safe, valid = h[rows], safe[rows], valid[rows]
                lse, ll = _fwd_call(h, w, bias, safe, bn, bv, _tag(k, n))
            # lse stays in the rung's own row order, for its backward
            return (jnp.sum(jnp.where(valid, lse - ll, 0.0)),
                    jnp.pad(lse, (0, n - k)))
        return run

    s, lse = jax.lax.switch(rung, [at(k) for k in rungs], h, safe, valid)
    return (s, count.astype(_F32)), (h, w, bias, safe, valid, lse, order,
                                     rung)


def _fused_xent_sums_bwd(ignore_index, rungs, res, ct):
    ds, _dc = ct   # count is a step function of int labels: no grad path
    h, w, bias, safe, valid, lse, order, rung = res
    n = h.shape[0]
    g = jnp.where(valid, ds, 0.0).astype(_F32)
    bn, bv = _blocks(h, w)

    def at(k):
        def run(h, safe, lse, g):
            if k == n:
                return _bwd_call(h, w, bias, safe, lse, g, bn, bv)
            rows = order[:k]
            dh, dw, db = _bwd_call(h[rows], w, bias, safe[rows], lse[:k],
                                   g[rows], bn, bv, _tag(k, n))
            # the rows left out are unlabelled: their dh is exactly zero
            return (jnp.zeros_like(h).at[rows].set(dh, unique_indices=True),
                    dw, db)
        return run

    dh, dw, db = jax.lax.switch(rung, [at(k) for k in rungs], h, safe, lse,
                                g)
    return dh, dw, db.astype(bias.dtype), None


_fused_xent_sums.defvjp(_fused_xent_sums_fwd, _fused_xent_sums_bwd)


def _sharded_fused(h2, w, bias, lab, mesh, row_axes, ignore_index):
    """Row-parallel fused xent under a multi-device TrainStep trace:
    shard_map over the batch-row axes (each shard streams the full W —
    replicated spec; pjit inserts the gather if TP shards it), psum the
    per-shard sums, divide once. This is how the opaque pallas call
    becomes SPMD-partitionable — the manual axes make the partitioning
    explicit instead of asking XLA to infer it."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.ring import _SHARD_MAP_CHECK_VMA, _shard_map

    def local(hs, ws, bs, ls):
        # W and bias arrive replicated over the row axes but each shard's
        # dW/db is its own partial sum: cast them to varying, so the
        # custom_vjp's cotangents type-check under check_vma and the
        # cast's transpose is the psum that adds the partials up. (With
        # the check off — the interpret-mode tests — nothing is typed
        # and shard_map's own transpose does that sum.)
        if _SHARD_MAP_CHECK_VMA[0]:
            ws, bs = (jax.lax.pcast(a, row_axes, to="varying")
                      for a in (ws, bs))
        # every shard on all of its rows: the ladder is the single-device
        # path's (PERF.md §7)
        s, c = _fused_xent_sums(hs, ws, bs, ls, ignore_index,
                                (hs.shape[0],))
        s = jax.lax.psum(s, row_axes)
        c = jax.lax.psum(c, row_axes)
        return s / jnp.maximum(c, 1.0)

    return _shard_map(local, mesh,
                      (P(row_axes, None), P(None, None), P(None),
                       P(row_axes)), P())(h2, w, bias, lab)


def _trace_shard_plan(n, hd, v):
    """(mesh, row_axes) when the current TrainStep trace is multi-device
    AND the rows divide into kernel-eligible shards; 'gate' when it is
    multi-device but unshardable (XLA fallback keeps correctness);
    None for single-device/no-trace."""
    from ...parallel.mesh import active_trace_mesh, active_trace_row_axes

    mesh = active_trace_mesh()
    if mesh is None or mesh.size <= 1:
        return None
    row_axes = tuple(active_trace_row_axes())
    if row_axes:
        import math

        shards = math.prod(mesh.shape[a] for a in row_axes)
        if (shards > 0 and n % shards == 0
                and _eligible(n // shards, hd, v)):
            return mesh, row_axes
    return "gate"


def _eligible(n, hd, v):
    from ...framework.bringup import pallas_enabled

    if not pallas_enabled():
        return False
    # the hidden width's ceiling is whatever _pick_blocks can hold in
    # VMEM at its smallest blocks (about 4,600 float32 columns), not a
    # constant of its own: 2304 runs at (256, 256)
    return _pick_blocks(n, hd, v) is not None and hd % 128 == 0


def _work(h2, w, bias, lab, rungs):
    """``work=`` / ``grad_work=`` of one call that runs at one of the row
    capacities ``rungs``, for the ledger in ``counters``, each rung under
    its own roles: the logits matmul forward (2 K H V); dh and dW backward
    (4 K H V, the recomputed logits not counted). Bytes: K rows of h and
    labels, W and bias read, lse and the label logit written; backward
    reads those with lse and the row cotangent and writes dh, dW, db."""
    n, hd = h2.shape
    v = w.shape[0]
    work, grad_work = {}, {}
    for k in rungs:
        rows = nbytes(h2, lab) * k // n
        read = rows + nbytes(w, bias)
        tag = _tag(k, n)
        work[f"fused_xent_{tag}fwd"] = (2.0 * k * hd * v, read + 8 * k)
        grad_work[f"fused_xent_{tag}bwd"] = (
            4.0 * k * hd * v,
            read + 8 * k + nbytes(h2) * k // n + nbytes(w, bias))
    return {"work": work, "grad_work": grad_work}


def fused_linear_cross_entropy(h, w, bias, labels, ignore_index=-100):
    """mean softmax-xent of (h @ w^T + bias) against labels, streaming
    the vocab axis so the logits never land in HBM. h: (..., H); w:
    (V, H); bias: (V,); labels: (...,) int. Falls back to the XLA
    logits path off-TPU / for ineligible shapes (counters record
    which)."""
    from .counters import bump

    hd = h.shape[-1]
    h2 = h.reshape(-1, hd)
    lab = labels.reshape(-1)
    n = h2.shape[0]
    pad = (-n) % _BN_MIN
    plan = _trace_shard_plan(n, hd, w.shape[0])
    if plan == "gate":
        bump("fused_xent", "xla",
             "multi-device trace without shard-divisible rows/row axes "
             "(opaque pallas call is unpartitionable; XLA path is "
             "value-identical and partitionable)")
    elif plan is not None:
        mesh, row_axes = plan
        out = _sharded_fused(h2, w, bias, lab, mesh, row_axes,
                             int(ignore_index))
        bump("fused_xent", "pallas_sharded",
             **_work(h2, w, bias, lab, (n,)))
        return out
    elif _eligible(n + pad, hd, w.shape[0]):
        if pad:
            h2 = jnp.concatenate(
                [h2, jnp.zeros((pad, hd), h2.dtype)], 0)
            lab = jnp.concatenate(
                [lab, jnp.full((pad,), ignore_index, lab.dtype)], 0)
        out = _fused_xent_core(h2, w, bias, lab, int(ignore_index))
        rungs = _ladder(n + pad, _blocks(h2, w)[0])
        bump("fused_xent", "pallas", **_work(h2, w, bias, lab, rungs))
        if len(rungs) > 1:
            bump("fused_xent", "ladder")
        return out
    else:
        bump("fused_xent", "xla",
             f"dispatch ineligible (n={n}, w={tuple(w.shape)})")
    logits = (h2 @ w.T).astype(_F32) + bias.astype(_F32)
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, safe[:, None].astype(jnp.int32), axis=1)[:, 0]
    count = jnp.maximum(jnp.sum(valid.astype(_F32)), 1.0)
    return jnp.sum(jnp.where(valid, lse - ll, 0.0)) / count
