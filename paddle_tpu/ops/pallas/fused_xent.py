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

A grid step costs what its (block_n, block_v) tile costs: the logits
product, element-wise float32 work on the tile, the second product in
the backward, and nothing that crosses lanes or turns a row into a
column (a step of the first forward spent 5.5 of its 6.5 us on three
cross-lane reductions and their relayouts, PERF.md section 6, PR 30).
What is where:

* resident blocks: the row block of h and the vocabulary block of W,
  both as the MXU takes them (``_mxu_dtype``: bfloat16 at the default
  matmul precision, rounded once in XLA before the call), the bias and
  the per-row vectors as lane-major (1, block) rows, and the float32
  accumulator the inner axis revisits (dh, or dW);
* VMEM scratch, filled at the first inner step of an outer block: the
  forward's running maximum, sum of exponentials and label logit, each
  (block_n, 128) — one value per row AND lane column, updated
  element-wise from the tile's 128-lane column groups and reduced across
  the 128 lanes once, at the last vocabulary step; and every per-row
  vector a kernel needs along sublanes, as a lane-replicated
  (block_n, 128) column (labels in all of forward and dh; lse and the
  row cotangent in dh);
* dW/db run on the TRANSPOSED tile, W · h^T: there the per-row vectors
  are lane-major rows as they arrive (the row block changes every step,
  so a column could not be kept), the bias is the column in scratch, the
  probabilities meet h without a transpose, and db sums lane-wise into a
  (block_v, 128) scratch that is reduced at the last row step.

Rows whose label is ignored add nothing to the loss or to any gradient,
so the single-device path leaves them out: it counts the labelled rows
on the device, orders the rows labelled-first and runs the kernels on
the first K of them, K the smallest rung of a short ladder of static
row capacities (``_ladder``) that holds the count, picked with
``lax.switch``. Each rung's kernels run under a role name of their own
(``_tag``), so a device trace shows which rung ran and the work
ledger charges exactly that rung's rows.

``reduction="none"`` hands back each row's own loss and takes a cotangent
a row on the way back, on the same two launches: the kernels always
computed ``lse - ll`` a row and always took a per-row ``g`` in the
backward; the mean's wrapper sums before it returns and broadcasts one
``ds``. A caller that weighs rows (a looped model's expected loss over
its exits, ``models/causal_lm.py``) stacks them and calls ONCE. One
table used by four passes' rows is then one use of the parameter: dW is
accumulated over all the stacked rows inside the dW launch's row axis
and written once, where four calls would each write a float32 dW the
size of the table (403 MB at 49,152 x 2048) for autodiff to add up.

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
from .flash_attention import _sds

define_flag("fused_vocab_xent", True,
            "Route large-vocab linear+cross-entropy heads (BERT MLM) "
            "through the streamed Pallas kernel; False materialises "
            "logits via XLA (the A/B arm for the live session)")

_F32 = jnp.float32
_NEG = -1e30
#: lanes of a vreg: the width of the lane-wise statistics and of a
#: per-row vector replicated across lanes
_LANES = 128

_BN_CANDIDATES = (1024, 512, 256)
_BV_CANDIDATES = (512, 384, 256, 128)
#: pad modulus = the smallest row block we can always fall back to
_BN_MIN = _BN_CANDIDATES[-1]
#: per-kernel budget (bytes) for what a grid step keeps in VMEM as _fits
#: counts it: one copy of each block, the scratch, two float32 tiles
_VMEM_BUDGET = 10 * 1024 * 1024
#: scoped-VMEM limit handed to Mosaic. _fits counts one copy of each
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


def _mxu_dtype(dtype):
    """The type the kernels hand the MXU an operand of ``dtype`` in. At
    the default matmul precision a Mosaic product of float32 operands is
    ONE bfloat16 pass with float32 accumulation: bit for bit the product
    of the operands cast to bfloat16 first, and no slower a grid step
    (measured on a v5e, PR 30: PERF.md section 6). So the cast is made
    once, in XLA, before the call, for what it halves: the kernels'
    streams from HBM (every row block re-reads the whole table; every
    vocabulary block of dW re-reads the rung of h, which bound that
    kernel). Under any higher precision the operands stay as they are
    and the product splits them as before."""
    one_pass = jax.config.jax_default_matmul_precision in (
        None, "default", "bfloat16")
    return jnp.dtype(jnp.bfloat16 if one_pass and dtype == _F32 else dtype)


def _fits(bn, bv, hd, itemsize):
    """What one grid step of the widest kernel keeps in VMEM, one copy of
    each block: the h and W blocks at the operands' ``itemsize``, the
    float32 accumulator (dh: a row block; dW: a vocabulary block), the
    lane-replicated (·, 128) float32 scratch (dh: labels, lse and the
    cotangent as columns; dW: the bias column and db's lane-wise sums;
    the forward's four are under dh's accumulator wherever hd >= 128),
    and the float32 logits tile with its probabilities. Overflow fails
    Mosaic at COMPILE time, so no over-budget pair may ever be picked."""
    blocks = (bn + bv) * hd * itemsize + 2 * 4 * bn * bv
    dh_kernel = blocks + 4 * bn * hd + 3 * 4 * bn * _LANES
    dw_kernel = blocks + 4 * bv * hd + 2 * 4 * bv * _LANES
    return max(dh_kernel, dw_kernel) <= _VMEM_BUDGET


def _pick_blocks(n, hd, v, itemsize=4):
    """Joint (block_n, block_v) choice, LARGEST bn first: every grid
    row-block streams the ENTIRE weight table once (47 MB of bfloat16
    for BERT), so bn — not bv — sets the dominant HBM traffic; at the
    benchmark's rung (n=8192, hd=768) 1024-row blocks read W 8x vs 32x
    at 256. A greedy-large bv that forced a smaller bn under the VMEM
    cap would double exactly that traffic, so bv concedes first.
    ``itemsize`` is the MXU operands' (_mxu_dtype); the default, 4, is
    the widest and what eligibility is decided on. Returns None when
    nothing divides + fits (dispatch falls back to XLA via _eligible).
    Vocab lane modulus 128: BERT's 30592 = 128 * 239 only admits
    128-wide vocab blocks anyway."""
    for bn in _BN_CANDIDATES:
        if n % bn != 0:
            continue
        for bv in _BV_CANDIDATES:
            if v % bv == 0 and _fits(bn, bv, hd, itemsize):
                return bn, bv
    return None


# ---------------------------------------------------------------------------
# what a grid step is made of: one product and element-wise work on its
# (block_n, block_v) float32 tile. Nothing in a step crosses lanes or
# turns a row into a column.
# ---------------------------------------------------------------------------


def _dot(a, b, ca, cb):
    """a · b over a's dimension ``ca`` and b's ``cb``, accumulated in
    float32 (fused_xent's own: flash_attention._dot is the flash
    kernels'). A product of bfloat16 operands is exact in one pass, and
    Mosaic refuses to be asked for more of one; float32 operands take
    the ambient precision."""
    one_pass = a.dtype == jnp.bfloat16
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=_F32,
        precision=jax.lax.Precision.DEFAULT if one_pass else None)


def _lane_groups(x):
    """The static, lane-aligned 128-wide column groups of a tile."""
    return [x[:, c:c + _LANES] for c in range(0, x.shape[1], _LANES)]


def _wide(x, width):
    """A lane-replicated (rows, 128) vector across ``width`` lanes."""
    return jnp.concatenate([x] * (width // _LANES), axis=1)


def _column(row_ref):
    """A (1, rows) lane-major block as a lane-replicated (rows, 128)
    column: the one relayout of a per-row vector, made when its block
    comes in and kept in scratch for the steps that revisit it."""
    return jnp.broadcast_to(row_ref[0, :][:, None],
                            (row_ref.shape[1], _LANES))


# ---------------------------------------------------------------------------
# forward: grid (rows/bn, vocab/bv). The running maximum, sum of
# exponentials and label logit are kept per row AND lane column in VMEM
# scratch; the 128 lanes of a row are reduced once, at the row block's
# last vocabulary step
# ---------------------------------------------------------------------------


def _fwd_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, ll_ref, m_scr, l_scr,
                ll_scr, lab_scr, *, num_v, block_v):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        ll_scr[...] = jnp.zeros_like(ll_scr)
        lab_scr[...] = _column(lab_ref)

    s = _dot(h_ref[...], w_ref[...], 1, 1) + b_ref[...]       # (bn, bv)
    groups = _lane_groups(s)
    m_old = m_scr[...]
    m_new = functools.reduce(jnp.maximum, groups, m_old)
    l = l_scr[...] * jnp.exp(m_old - m_new)
    ll = ll_scr[...]
    # the label's column, counted from this tile's first
    at = lab_scr[...] - j * block_v
    lane = jax.lax.broadcasted_iota(jnp.int32, m_old.shape, 1)
    for c, sc in enumerate(groups):
        l = l + jnp.exp(sc - m_new)
        ll = ll + jnp.where(at == lane + c * _LANES, sc, 0.0)
    m_scr[...] = m_new
    l_scr[...] = l
    ll_scr[...] = ll

    @pl.when(j == num_v - 1)
    def _finalize():
        # every lane column has seen V/128 logits, so its l is at least 1
        # and the row's sum at least the maximal column's: no log of 0. A
        # column far under the row's maximum underflows to exactly 0
        m_row = jnp.max(m_new, axis=1)
        l_row = jnp.sum(l * jnp.exp(m_new - m_row[:, None]), axis=1)
        lse_ref[...] = (m_row + jnp.log(l_row))[None, :]
        ll_ref[...] = jnp.sum(ll, axis=1)[None, :]


# ---------------------------------------------------------------------------
# backward: dh over a (rows, vocab) grid, its per-row vectors columns in
# scratch; dW/db over a (vocab, rows) grid on the TRANSPOSED tile, where
# a per-row vector is a lane-major row as it arrives, the bias is the
# column, and the probabilities meet h without a transpose
# ---------------------------------------------------------------------------


def _bwd_dh_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dh_ref,
                   lab_scr, lse_scr, g_scr, *, block_v):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        lab_scr[...] = _column(lab_ref)
        lse_scr[...] = _column(lse_ref)
        g_scr[...] = _column(g_ref)

    w = w_ref[...]
    s = _dot(h_ref[...], w, 1, 1) + b_ref[...]                # (bn, bv)
    p = jnp.exp(s - _wide(lse_scr[...], block_v))
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.where(col == _wide(lab_scr[...], block_v), p - 1.0, p)
    p = p * _wide(g_scr[...], block_v)
    # dh_ref is f32 regardless of input dtype: accumulating across the
    # vocab grid steps in bf16 would compound rounding per step
    dh_ref[...] = dh_ref[...] + _dot(p.astype(w.dtype), w, 1, 0)


def _bwd_dw_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref,
                   dw_ref, db_ref, b_scr, db_scr, *, num_n, block_n,
                   block_v):
    from jax.experimental import pallas as pl

    vj = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_scr[...] = jnp.zeros_like(db_scr)
        b_scr[...] = _column(b_ref)

    h = h_ref[...]                                            # (bn, H)
    st = _dot(w_ref[...], h, 1, 1) + _wide(b_scr[...], block_n)  # (bv, bn)
    p = jnp.exp(st - lse_ref[...])
    row = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    p = jnp.where(row == lab_ref[...], p - 1.0, p) * g_ref[...]
    # f32 accumulator refs (cast to the param dtype happens outside)
    dw_ref[...] = dw_ref[...] + _dot(p.astype(h.dtype), h, 1, 0)
    db_scr[...] = functools.reduce(jnp.add, _lane_groups(p), db_scr[...])

    @pl.when(i == num_n - 1)
    def _finalize():
        db_ref[...] = jnp.sum(db_scr[...], axis=1)[None, :]


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom_vjp
# ---------------------------------------------------------------------------


def _as_mxu(*operands):
    return [x.astype(_mxu_dtype(x.dtype)) for x in operands]


def _lane_scratch(rows, dtype=_F32):
    """VMEM scratch for one value a row and lane column."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((rows, _LANES), dtype)


def _fwd_call(h, w, bias, labels, block_n, block_v, tag=""):
    from jax.experimental import pallas as pl

    h, w = _as_mxu(h, w)
    n, hd = h.shape
    v = w.shape[0]
    num_v = v // block_v
    lse, ll = kernel_call(
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
        ],
        out_shape=[
            _sds((1, n), _F32, h),     # lse
            _sds((1, n), _F32, h),     # label logit
        ],
        # running max, running sumexp, label logit; the labels as a column
        scratch_shapes=[_lane_scratch(block_n)] * 3
        + [_lane_scratch(block_n, jnp.int32)],
        compiler_params=_compiler_params(),
    )(h, w, bias[None, :], labels[None, :])
    return lse[0], ll[0]


def _bwd_call(h, w, bias, labels, lse, g, block_n, block_v, tag=""):
    from jax.experimental import pallas as pl

    dtypes = h.dtype, w.dtype
    h, w = _as_mxu(h, w)
    n, hd = h.shape
    v = w.shape[0]
    operands = (h, w, bias[None, :], labels[None, :], lse[None, :],
                g[None, :])
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
        # labels, lse and the row cotangent as columns
        scratch_shapes=[_lane_scratch(block_n, jnp.int32),
                        _lane_scratch(block_n), _lane_scratch(block_n)],
        compiler_params=_compiler_params(),
    )(*operands)
    dw, db = kernel_call(
        f"fused_xent_{tag}bwd",
        functools.partial(_bwd_dw_kernel, num_n=n // block_n,
                          block_n=block_n, block_v=block_v),
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
        # the bias as a column; db's sums, lane-wise
        scratch_shapes=[_lane_scratch(block_v)] * 2,
        compiler_params=_compiler_params(),
    )(*operands)
    return dh.astype(dtypes[0]), dw.astype(dtypes[1]), db[0]


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
    blocks = _pick_blocks(h.shape[0], h.shape[1], w.shape[0],
                          _mxu_dtype(h.dtype).itemsize)
    if blocks is None:
        raise ValueError(
            f"fused_xent: no (block_n, block_v) divides+fits h "
            f"{h.shape} x w {w.shape} — dispatch should have taken the "
            "XLA path (_eligible)")
    return blocks


def _fused_xent_core(h, w, bias, labels, ignore_index):
    """mean loss = sum / clamp(count): derived from the sum-form
    custom_vjp below (autodiff of the division supplies the 1/count
    the hand-written mean backward used to hard-code — r5 review
    dedup)."""
    rungs = _ladder(h.shape[0], _blocks(h, w)[0])
    s, c = _fused_xent_sums(h, w, bias, labels, ignore_index, rungs)
    return s / jnp.maximum(c, 1.0)


# -- two custom_vjps on the same two launches (:func:`_forward`,
# :func:`_backward`). ``_fused_xent_sums``: per-shard (loss_sum,
# valid_count), so the shard_map'd multi-device path can psum BEFORE the
# mean. ``_fused_xent_rows``: every row's own loss, zero where the label
# is ignored, and a cotangent a row on the way back. ``rungs`` are the
# row capacities either may run at, the last one all of its rows ---------


def _forward(h, w, bias, labels, ignore_index, rungs, per_row):
    """(the sum of the rows' losses, or with ``per_row`` the (n,) losses
    in the caller's row order; the labelled rows' count; the residuals
    :func:`_backward` takes)."""
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
            rows = None
            if k == n:
                lse, ll = _fwd_call(h, w, bias, safe, bn, bv)
            else:
                rows = order[:k]
                h, safe, valid = h[rows], safe[rows], valid[rows]
                lse, ll = _fwd_call(h, w, bias, safe, bn, bv, _tag(k, n))
            loss = jnp.where(valid, lse - ll, 0.0)
            if not per_row:
                loss = jnp.sum(loss)
            elif rows is not None:
                # the rows left out are unlabelled: their loss is zero
                loss = jnp.zeros((n,), _F32).at[rows].set(
                    loss, unique_indices=True)
            # lse stays in the rung's own row order, for its backward
            return loss, jnp.pad(lse, (0, n - k))
        return run

    out, lse = jax.lax.switch(rung, [at(k) for k in rungs], h, safe, valid)
    return out, count.astype(_F32), (h, w, bias, safe, valid, lse, order,
                                     rung)


def _backward(rungs, res, ct):
    """(dh, dw, db, None) for the cotangent ``ct`` of the rows' losses:
    one scalar for their sum, or (n,), one a row. A row without a label
    gets none."""
    h, w, bias, safe, valid, lse, order, rung = res
    n = h.shape[0]
    g = jnp.where(valid, ct, 0.0).astype(_F32)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_xent_sums(h, w, bias, labels, ignore_index, rungs):
    (s, c), _ = _fused_xent_sums_fwd(h, w, bias, labels, ignore_index, rungs)
    return s, c


def _fused_xent_sums_fwd(h, w, bias, labels, ignore_index, rungs):
    s, count, res = _forward(h, w, bias, labels, ignore_index, rungs, False)
    return (s, count), res


def _fused_xent_sums_bwd(ignore_index, rungs, res, ct):
    ds, _dc = ct   # count is a step function of int labels: no grad path
    return _backward(rungs, res, ds)


_fused_xent_sums.defvjp(_fused_xent_sums_fwd, _fused_xent_sums_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_xent_rows(h, w, bias, labels, ignore_index, rungs):
    return _forward(h, w, bias, labels, ignore_index, rungs, True)[0]


def _fused_xent_rows_fwd(h, w, bias, labels, ignore_index, rungs):
    rows, _, res = _forward(h, w, bias, labels, ignore_index, rungs, True)
    return rows, res


def _fused_xent_rows_bwd(ignore_index, rungs, res, ct):
    return _backward(rungs, res, ct)


_fused_xent_rows.defvjp(_fused_xent_rows_fwd, _fused_xent_rows_bwd)


def _sharded_fused(h2, w, bias, lab, mesh, row_axes, ignore_index,
                   per_row=False):
    """Row-parallel fused xent under a multi-device TrainStep trace:
    shard_map over the batch-row axes (each shard streams the full W —
    replicated spec; pjit inserts the gather if TP shards it), psum the
    per-shard sums, divide once; with ``per_row`` each shard hands back
    its own rows' losses and nothing is summed. This is how the opaque
    pallas call becomes SPMD-partitionable — the manual axes make the
    partitioning explicit instead of asking XLA to infer it."""
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
        if per_row:
            return _fused_xent_rows(hs, ws, bs, ls, ignore_index,
                                    (hs.shape[0],))
        s, c = _fused_xent_sums(hs, ws, bs, ls, ignore_index,
                                (hs.shape[0],))
        s = jax.lax.psum(s, row_axes)
        c = jax.lax.psum(c, row_axes)
        return s / jnp.maximum(c, 1.0)

    return _shard_map(local, mesh,
                      (P(row_axes, None), P(None, None), P(None),
                       P(row_axes)),
                      P(row_axes) if per_row else P())(h2, w, bias, lab)


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
    # VMEM at its smallest blocks on float32 operands (3,840 columns), not
    # a constant of its own: 2304 runs at (256, 256) there and at
    # (512, 256) on bfloat16 operands
    return _pick_blocks(n, hd, v) is not None and hd % 128 == 0


def _work(h2, w, bias, lab, rungs, per_row=False):
    """``work=`` / ``grad_work=`` of one call that runs at one of the row
    capacities ``rungs``, for the ledger in ``counters``, each rung under
    its own roles: the logits matmul forward (2 K H V); dh and dW backward
    (4 K H V, the recomputed logits not counted). Bytes: K rows of h and
    labels, W and bias read, lse and the label logit written; backward
    reads those with lse and the row cotangent and writes dh, dW, db.
    ``per_row`` adds what leaves and enters a row at a time: K float32
    losses out, K float32 cotangents in."""
    n, hd = h2.shape
    v = w.shape[0]
    work, grad_work = {}, {}
    for k in rungs:
        rows = nbytes(h2, lab) * k // n
        read = rows + nbytes(w, bias)
        tag = _tag(k, n)
        each = 4 * k if per_row else 0
        work[f"fused_xent_{tag}fwd"] = (2.0 * k * hd * v,
                                        read + 8 * k + each)
        grad_work[f"fused_xent_{tag}bwd"] = (
            4.0 * k * hd * v,
            read + 8 * k + each + nbytes(h2) * k // n + nbytes(w, bias))
    return {"work": work, "grad_work": grad_work}


def fused_linear_cross_entropy(h, w, bias, labels, ignore_index=-100,
                               reduction="mean"):
    """softmax-xent of (h @ w^T + bias) against labels, streaming the
    vocab axis so the logits never land in HBM. h: (..., H); w: (V, H);
    bias: (V,); labels: (...,) int. ``reduction="mean"``: the mean over
    the labelled rows, a scalar. ``reduction="none"``: every row's own
    loss, float32 in the labels' shape, zero where the label is
    ``ignore_index``; its cotangent comes back a row at a time, so a
    caller may weigh the rows as it likes (a looped model's expected loss
    over its exits: ``models/causal_lm.py`` stacks its passes' rows into
    ONE such call, so that the float32 dW of the table is accumulated
    inside one launch and not written once a pass and added up). Falls
    back to the XLA logits path off-TPU / for ineligible shapes
    (counters record which)."""
    from .counters import bump

    if reduction not in ("mean", "none"):
        raise ValueError(f"reduction {reduction!r}: 'mean' or 'none'")
    per_row = reduction == "none"
    hd = h.shape[-1]
    h2 = h.reshape(-1, hd)
    lab = labels.reshape(-1)
    n = h2.shape[0]
    pad = (-n) % _BN_MIN
    plan = _trace_shard_plan(n, hd, w.shape[0])
    if per_row:
        bump("fused_xent", "per_row")
    if plan == "gate":
        bump("fused_xent", "xla",
             "multi-device trace without shard-divisible rows/row axes "
             "(opaque pallas call is unpartitionable; XLA path is "
             "value-identical and partitionable)")
    elif plan is not None:
        mesh, row_axes = plan
        out = _sharded_fused(h2, w, bias, lab, mesh, row_axes,
                             int(ignore_index), per_row)
        bump("fused_xent", "pallas_sharded",
             **_work(h2, w, bias, lab, (n,), per_row))
        return out.reshape(labels.shape) if per_row else out
    elif _eligible(n + pad, hd, w.shape[0]):
        if pad:
            h2 = jnp.concatenate(
                [h2, jnp.zeros((pad, hd), h2.dtype)], 0)
            lab = jnp.concatenate(
                [lab, jnp.full((pad,), ignore_index, lab.dtype)], 0)
        rungs = _ladder(n + pad, _blocks(h2, w)[0])
        if per_row:
            out = _fused_xent_rows(h2, w, bias, lab, int(ignore_index),
                                   rungs)[:n].reshape(labels.shape)
        else:
            out = _fused_xent_core(h2, w, bias, lab, int(ignore_index))
        bump("fused_xent", "pallas",
             **_work(h2, w, bias, lab, rungs, per_row))
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
    if per_row:
        return jnp.where(valid, lse - ll, 0.0).reshape(labels.shape)
    count = jnp.maximum(jnp.sum(valid.astype(_F32)), 1.0)
    return jnp.sum(jnp.where(valid, lse - ll, 0.0)) / count
