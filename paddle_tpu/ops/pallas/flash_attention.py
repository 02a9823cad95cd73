"""Flash attention for TPU (forward + backward Pallas kernels).

TPU-native replacement for the reference fused attention CUDA kernel
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor.cu): an online-softmax Pallas kernel tiled for
the MXU (q blocks stream over kv blocks), a matching flash backward
(one kernel that recomputes the probabilities from the saved logsumexp
once a tile pair and forms dq, dk and dv from them), wired together with
jax.custom_vjp so the kernel is used in training too. An XLA fallback
covers shapes/backends the kernel does not (masks, dropout, unaligned
lengths, CPU tests).

Layout convention is paddle's (batch, seq, heads, head_dim). Speed
against the XLA path on the chip: not measured (no committed capture);
the seq<256 dispatch floor routes short sequences to XLA.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .counters import kernel_call, nbytes

_NEG_INF = -1e30
_F32 = jnp.float32
#: what every forward rule here calls its kernel's output and logsumexp:
#: ``optimizer.meta.recompute`` keeps the values of this name across a
#: recomputed segment, so the segment's second run launches no forward
#: kernel (its q, k, v come back from the projections; the O(T^2) launch
#: would only write again what the first had written). An identity
#: outside a checkpoint with a policy: it lowers to nothing
KEPT = "flash_attention_out_lse"
_kept = functools.partial(checkpoint_name, name=KEPT)


def _xla_attention(q, k, v, mask, dropout_p, is_causal, key_rng,
                   window=None):
    """Reference XLA path: fused well enough for short sequences. With
    fewer key/value heads than query heads, query head h reads key head
    h // (H / Hkv) through the einsum's own batching (no copy of K or V);
    ``window`` keeps, under the causal mask, the keys with
    ``0 <= i - j < window``."""
    # (B, L, H, D) -> (B, H, L, D)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    b, h, ql, d = qh.shape
    hkv, kl = kh.shape[1], kh.shape[2]
    if hkv == h:
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                            preferred_element_type=jnp.float32)
    else:
        scores = jnp.einsum(
            "bngqd,bnkd->bngqk", qh.reshape(b, hkv, h // hkv, ql, d), kh,
            preferred_element_type=jnp.float32).reshape(b, h, ql, kl)
    scores = scores / math.sqrt(d)
    if is_causal:
        causal = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        if window is not None:
            causal &= ~jnp.tril(jnp.ones((ql, kl), bool),
                                k=kl - ql - window)
        scores = jnp.where(causal, scores, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, _NEG_INF)
        else:
            scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key_rng is not None:
        keep = jax.random.bernoulli(key_rng, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    if hkv == h:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    else:
        out = jnp.einsum(
            "bngqk,bnkd->bngqd", probs.reshape(b, hkv, h // hkv, ql, kl),
            vh).reshape(b, h, ql, vh.shape[-1])
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# forward kernel: online softmax over streamed KV blocks; also emits the
# per-row logsumexp needed by the backward recomputation
# ---------------------------------------------------------------------------


def _dot(a, b, trans_b=False):
    dims = (((1,), (1,)), ((), ())) if trans_b else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _sds(shape, dtype, ref):
    """ShapeDtypeStruct for pallas_call out_shape that inherits `ref`'s
    varying-manual-axes type: under shard_map (the flash-ring path)
    check_vma requires outputs to declare how they vary over the mesh."""
    vma = jax.typeof(ref).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _keep_mask(seed, row, qi, j, shape, dropout_p):
    """Regenerable per-tile dropout keep-mask from the TPU hardware PRNG.
    Seeding with (seed, row, q_tile, kv_tile) makes the mask a pure
    function of tile coordinates, so the forward and the backward kernel
    reproduce identical bits without any HBM mask tensor."""
    from jax.experimental.pallas import tpu as pltpu

    # Mosaic takes at most 2 seed words: fold the tile coordinates into
    # one (collision-free: row < 2^15 batch*head rows, <=2^8 tiles per
    # axis — enforced by _pallas_ok's seq/shape ceilings)
    pltpu.prng_seed(seed, (row << 16) + (qi << 8) + j)
    bits = jax.lax.bitcast_convert_type(
        pltpu.prng_random_bits(shape), jnp.uint32)
    threshold = jnp.uint32(min(int(dropout_p * (1 << 32)), (1 << 32) - 1))
    return bits >= threshold


def _band(s, q_pos, k_pos, window):
    """Scores outside the causal mask, and with a ``window`` outside
    ``0 <= q_pos - k_pos < window``, set to -inf. A row whose keys in a
    block are all masked adds ``exp(0)`` terms to its carry; the next
    block that holds one of its keys rescales them by ``exp(-1e30 - m)``
    = 0, and every row's own position is such a key."""
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return jnp.where(keep, s, _NEG_INF)


def _first_kv_block(qi, q_block, block_kv, window):
    """The first kv block a q block's band meets (0 with no window)."""
    if window is None:
        return 0
    return jnp.maximum(qi * q_block - (window - 1), 0) // block_kv


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, kv_len,
                      block_kv, sm_scale, causal, q_block, masked=False,
                      dropout_p=0.0, window=None):
    from jax.experimental import pallas as pl

    rest = list(rest)
    mask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout_p > 0.0 else None
    o_ref, lse_ref = rest
    q = q_ref[...].astype(_F32) * sm_scale       # (bq, d)
    bq = q.shape[0]
    row = pl.program_id(0)
    qi = pl.program_id(1)
    num_kv = kv_len // block_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.dslice(j * block_kv, block_kv), :].astype(_F32)
        v = v_ref[pl.dslice(j * block_kv, block_kv), :].astype(_F32)
        s = _dot(q, k, trans_b=True)             # (bq, bkv)
        if mask_ref is not None:
            mb = mask_ref[0, pl.dslice(j * block_kv, block_kv)]
            s = s + mb[None, :].astype(_F32)
        if causal:
            q_pos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_kv), 0)
            k_pos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_kv), 1)
            s = _band(s, q_pos, k_pos, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        # dropout hits only the value accumulation; the normalizer l uses
        # the undropped p, so out = dropout(softmax(s)) @ v exactly
        l_new = alpha * l + jnp.sum(p, axis=1)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0, 0], row, qi, j,
                              (bq, block_kv), dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        acc_new = acc * alpha[:, None] + _dot(p, v)
        return m_new, l_new, acc_new

    if causal:
        # exact bound: last kv tile containing column (qi+1)*q_block - 1
        last = jnp.minimum(((qi + 1) * q_block - 1) // block_kv + 1, num_kv)
    else:
        last = num_kv
    m0 = jnp.full((bq,), _NEG_INF, _F32)
    l0 = jnp.zeros((bq,), _F32)
    acc0 = jnp.zeros((bq, v_ref.shape[-1]), _F32)
    m, l, acc = jax.lax.fori_loop(
        _first_kv_block(qi, q_block, block_kv, window), last, body,
        (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(jnp.maximum(l, 1e-30)))[None, :]


# ---------------------------------------------------------------------------
# backward kernel (flash bwd, one launch): probabilities recomputed from
# lse once a (q block, kv block) pair, and all three gradients formed from
# them; delta = rowsum(dout * out) precomputed outside
# ---------------------------------------------------------------------------


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, q_len, block_q, sm_scale,
                      causal, kv_block, masked=False, dropout_p=0.0,
                      window=None, group=1):
    """One query head's dQ and its part of dK, dV, one kv block a grid
    step. The head's Q, dO and statistics stay resident over its kv
    blocks, and so does its whole dQ: float32 scratch that every kv block
    adds its q blocks' ``dS K`` to, in ascending order, and that the last
    kv block scales and writes out. With ``group`` query heads to a key
    head the grid is (key heads, group, kv blocks): dK, dV are summed in
    float32 scratch that holds the key head's whole dK, dV, and the last
    head of the group writes each block out."""
    from jax.experimental import pallas as pl

    rest = list(rest)
    mask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout_p > 0.0 else None
    dq_ref, dk_ref, dv_ref, dq_acc = rest[:4]
    k = k_ref[...].astype(_F32)                  # (bkv, d)
    v = v_ref[...].astype(_F32)
    bkv = k.shape[0]
    row = pl.program_id(0)
    axis = 1 if group == 1 else 2                # the kv blocks' grid axis
    kj = pl.program_id(axis)
    num_q = q_len // block_q

    def q_rows(i):
        return pl.dslice(i * block_q, block_q)

    def each_q_block(fn):
        jax.lax.fori_loop(0, num_q, lambda i, _: fn(i), None)

    @pl.when(kj == 0)
    def _():
        zeros = jnp.zeros((block_q, dq_acc.shape[1]), _F32)

        def clear(i):
            dq_acc[q_rows(i), :] = zeros

        each_q_block(clear)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[q_rows(i), :].astype(_F32) * sm_scale
        do = do_ref[q_rows(i), :].astype(_F32)
        lse = lse_ref[0, q_rows(i)]
        delta = delta_ref[0, q_rows(i)]
        s = _dot(q, k, trans_b=True)             # (bq, bkv)
        if mask_ref is not None:
            mb = mask_ref[0, :]
            s = s + mb[None, :].astype(_F32)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bkv), 0)
            k_pos = kj * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bkv), 1)
            s = _band(s, q_pos, k_pos, window)
        p = jnp.exp(s - lse[:, None])
        dp = _dot(do, v, trans_b=True)
        if dropout_p > 0.0:
            # (row, q_tile=i, kv_tile=kj) matches the forward's seeding;
            # delta = rowsum(do * out) already equals <dp_dropped, p>
            keep = _keep_mask(seed_ref[0, 0], row, i, kj,
                              (block_q, bkv), dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            dv = dv + _dot(jnp.where(keep, p * inv, 0.0).T, do)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            dv = dv + _dot(p.T, do)
        ds = p * (dp - delta[:, None])
        dq_acc[q_rows(i), :] += _dot(ds, k)      # grad wrt scaled q
        dk = dk + _dot(ds.T, q)                  # q already scaled
        return dk, dv

    if causal:
        # q blocks strictly before this kv block never attend to it
        first = (kj * kv_block) // block_q
    else:
        first = 0
    if window is None:
        last = num_q
    else:
        # the last q block whose band still holds a key of this block
        last = jnp.minimum(
            ((kj + 1) * kv_block + window - 2) // block_q + 1, num_q)
    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    dk, dv = jax.lax.fori_loop(first, last, body, (dk0, dv0))

    @pl.when(kj == pl.num_programs(axis) - 1)
    def _():
        def write(i):
            dq_ref[q_rows(i), :] = (dq_acc[q_rows(i), :]
                                    * sm_scale).astype(dq_ref.dtype)

        each_q_block(write)

    if group == 1:
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)
        return
    dk_acc, dv_acc = rest[4:]
    g = pl.program_id(1)
    rows = pl.dslice(kj * kv_block, kv_block)

    @pl.when(g == 0)
    def _():
        dk_acc[rows, :] = dk
        dv_acc[rows, :] = dv

    @pl.when(g > 0)
    def _():
        dk_acc[rows, :] += dk
        dv_acc[rows, :] += dv

    @pl.when(g == group - 1)
    def _():
        dk_ref[rows, :] = dk_acc[rows, :].astype(dk_ref.dtype)
        dv_ref[rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom_vjp
# ---------------------------------------------------------------------------


def _lanes(width):
    """Columns a row of ``width`` takes in VMEM: whole vregs of 128."""
    return -(-width // 128) * 128


def _stream_params(rows, d, dv, itemsize, extra=0):
    """Scoped-VMEM limit of the streaming kernels: each keeps one head's
    whole K and V (the backward kernel: Q and dO) resident, double-buffered,
    beside its blocks and float32 temporaries; ``extra`` is what else a
    launch holds whole. Mosaic's 16 MiB default holds that up to about
    8192 rows of 128 bfloat16 columns; 8192 x (192 + 128) needed 17.7 MB
    (seen compiling for a v5e, PR 26), a row of 192 taking the 256 lanes
    of two vregs. Of 128 MiB physical."""
    from jax.experimental.pallas import tpu as pltpu

    resident = 2 * rows * (_lanes(d) + _lanes(dv)) * itemsize + extra
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(16 << 20, min(resident + (12 << 20), 96 << 20)))


def _mergeheads(x):
    b, l, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, l, d)


def _splitheads(x, b, h):
    bh, l, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, l, d), 1, 2)


def _roles(window, group):
    """(forward, backward) role names of the streaming launches. One key
    head a query head and no window: ``flash_attention_stream_fwd`` /
    ``_bwd``, as ever. A group's launches (``flash_attention_grouped``)
    and a window's (``flash_attention_window``) are rows of their own in
    a trace and in the work ledger, ONE name for the forward and the
    backward launch: a trace reduction that keeps ten rows then
    holds a layer kind's attention as one row (the Mellum cell's full
    layer's forward and backward, apart, both fell under its tenth row;
    PERF.md section 6, PR 31), and a profile tells them apart by scope."""
    if window is None and group == 1:
        return "flash_attention_stream_fwd", "flash_attention_stream_bwd"
    name = "flash_attention_" + ("grouped" if window is None else "window")
    return name, name


def _kv_row(i, group):
    """The key/value row (batch x key heads) that query row ``i`` (batch
    x query heads) reads: consecutive query rows of a group map to one
    block index, so the resident K and V are fetched once a key head."""
    return i if group == 1 else i // group


def _fwd_call(qm, km, vm, causal, block_q, block_kv, sm_scale,
              mask_bias=None, heads=1, dropout_p=0.0, seed=None,
              window=None):
    from jax.experimental import pallas as pl

    bh, ql, d = qm.shape
    kl, dv = km.shape[1], vm.shape[2]      # values may be narrower (MLA)
    group = bh // km.shape[0]              # query heads to a key head
    grid = (bh, ql // block_q)
    masked = mask_bias is not None
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, kl, d), lambda i, j: (_kv_row(i, group), 0, 0)),
        pl.BlockSpec((None, kl, dv), lambda i, j: (_kv_row(i, group), 0, 0)),
    ]
    operands = [qm, km, vm]
    if masked:
        # bias stays (b, 1, kl) in HBM; the grid maps each merged
        # batch-head row back to its batch entry (no h-fold copy)
        in_specs.append(pl.BlockSpec((None, 1, kl),
                                     lambda i, j: (i // heads, 0, 0)))
        operands.append(mask_bias)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
        operands.append(seed)
    out, lse = kernel_call(
        _roles(window, group)[0],
        functools.partial(_flash_fwd_kernel, kv_len=kl, block_kv=block_kv,
                          sm_scale=sm_scale, causal=causal, q_block=block_q,
                          masked=masked, dropout_p=dropout_p, window=window),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            _sds((bh, ql, dv), qm.dtype, qm),
            _sds((bh, 1, ql), _F32, qm),
        ],
        compiler_params=_stream_params(kl, d, dv, km.dtype.itemsize),
    )(*operands)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_core(q, k, v, causal, block_q, block_kv, window=None):
    """q (B, L, H, D); k, v (B, L, Hkv, D) with H a multiple of Hkv:
    query head h reads key head h // (H / Hkv) through the kernels' block
    index maps, and dK, dV come out Hkv heads wide. ``window`` (with
    ``causal``) keeps the keys ``0 <= i - j < window`` and visits only
    the blocks that meet that band."""
    out, _ = _flash_attention_core_fwd(q, k, v, causal, block_q, block_kv,
                                       window)
    return out


def _flash_attention_core_fwd(q, k, v, causal, block_q, block_kv,
                              window=None):
    b, ql, h, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qm, km, vm = _mergeheads(q), _mergeheads(k), _mergeheads(v)
    out_m, lse = _kept(_fwd_call(qm, km, vm, causal, block_q, block_kv,
                                 sm_scale, window=window))
    return _splitheads(out_m, b, h), (qm, km, vm, out_m, lse, b, h)


def _bwd_call(qm, km, vm, dom, lse, delta, causal, block_q, block_kv,
              sm_scale, mask_bias=None, heads=1, dropout_p=0.0, seed=None,
              window=None):
    """(dq, dk, dv) from ONE launch of :func:`_flash_bwd_kernel`: grid
    (query heads, kv blocks), or with ``group`` query heads to a key head
    (key heads, group, kv blocks). A query head's Q, dO and statistics
    are resident over its kv blocks, beside its whole dQ as the output
    block and as float32 scratch; a group's dK, dV (batch x key heads,
    kl, .) are held the same way over the group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, ql, d = qm.shape
    rows, kl, dv = km.shape[0], km.shape[1], vm.shape[2]
    group = bh // rows
    masked = mask_bias is not None
    size = qm.dtype.itemsize
    scratch = [pltpu.VMEM((ql, d), _F32)]
    # beside the resident Q and dO: the output blocks that stay, double
    # buffered, and their float32 scratch
    extra = ql * _lanes(d) * (2 * size + 4)
    if group == 1:
        grid = (bh, kl // block_kv)

        def head(i, j):
            return (i, 0, 0)

        def block(i, j):
            return (i, j, 0)

        dk_spec = pl.BlockSpec((None, block_kv, d), block)
        dv_spec = pl.BlockSpec((None, block_kv, dv), block)
    else:
        grid = (rows, group, kl // block_kv)

        def head(i, g, j):
            return (i * group + g, 0, 0)

        def block(i, g, j):
            return (i, j, 0)

        def whole(i, g, j):
            return (i, 0, 0)

        dk_spec = pl.BlockSpec((None, kl, d), whole)
        dv_spec = pl.BlockSpec((None, kl, dv), whole)
        scratch += [pltpu.VMEM((kl, d), _F32), pltpu.VMEM((kl, dv), _F32)]
        extra += kl * (_lanes(d) + _lanes(dv)) * (2 * size + 4)
    in_specs = [
        pl.BlockSpec((None, ql, d), head),
        pl.BlockSpec((None, block_kv, d), block),
        pl.BlockSpec((None, block_kv, dv), block),
        pl.BlockSpec((None, ql, dv), head),
        pl.BlockSpec((None, 1, ql), head),
        pl.BlockSpec((None, 1, ql), head),
    ]
    operands = [qm, km, vm, dom, lse, delta]
    # a mask or dropout comes with one key head a query head
    if masked:
        in_specs.append(pl.BlockSpec((None, 1, block_kv),
                                     lambda i, j: (i // heads, 0, j)))
        operands.append(mask_bias)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
        operands.append(seed)
    return kernel_call(
        _roles(window, group)[1],
        functools.partial(_flash_bwd_kernel, q_len=ql, block_q=block_q,
                          sm_scale=sm_scale, causal=causal,
                          kv_block=block_kv, masked=masked,
                          dropout_p=dropout_p, window=window, group=group),
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, ql, d), head), dk_spec, dv_spec],
        out_shape=[
            _sds((bh, ql, d), qm.dtype, qm),
            _sds((rows, kl, d), km.dtype, qm),
            _sds((rows, kl, dv), vm.dtype, qm),
        ],
        scratch_shapes=scratch,
        compiler_params=_stream_params(ql, d, dv, size, extra=extra),
    )(*operands)


def _flash_attention_core_bwd(causal, block_q, block_kv, window, res, dout):
    qm, km, vm, out_m, lse, b, h = res
    d = qm.shape[-1]
    sm_scale = 1.0 / math.sqrt(d)
    dom = _mergeheads(dout)
    delta = jnp.sum(dom.astype(_F32) * out_m.astype(_F32),
                    axis=-1)[:, None, :]                     # (bh, 1, ql)
    dq, dk, dv = _bwd_call(qm, km, vm, dom, lse, delta, causal, block_q,
                           block_kv, sm_scale, window=window)
    hkv = km.shape[0] // b
    return (_splitheads(dq, b, h), _splitheads(dk, b, hkv),
            _splitheads(dv, b, hkv))


_flash_attention_core.defvjp(_flash_attention_core_fwd,
                             _flash_attention_core_bwd)


# -- masked variant: additive (batch, kv_len) bias, e.g. key-padding -------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_attention_core_masked(q, k, v, mask_bias, causal, block_q,
                                 block_kv):
    out, _ = _flash_attention_core_masked_fwd(q, k, v, mask_bias, causal,
                                              block_q, block_kv)
    return out


def _flash_attention_core_masked_fwd(q, k, v, mask_bias, causal, block_q,
                                     block_kv):
    b, ql, h, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qm, km, vm = _mergeheads(q), _mergeheads(k), _mergeheads(v)
    mm = mask_bias.astype(_F32)[:, None, :]      # (b, 1, kl), no h copy
    out_m, lse = _kept(_fwd_call(qm, km, vm, causal, block_q, block_kv,
                                 sm_scale, mask_bias=mm, heads=h))
    return (_splitheads(out_m, b, h),
            (qm, km, vm, out_m, lse, mm, mask_bias, b, h))


def _flash_attention_core_masked_bwd(causal, block_q, block_kv, res, dout):
    qm, km, vm, out_m, lse, mm, mask_bias, b, h = res
    d = qm.shape[-1]
    sm_scale = 1.0 / math.sqrt(d)
    dom = _mergeheads(dout)
    delta = jnp.sum(dom.astype(_F32) * out_m.astype(_F32),
                    axis=-1)[:, None, :]
    dq, dk, dv = _bwd_call(qm, km, vm, dom, lse, delta, causal, block_q,
                           block_kv, sm_scale, mask_bias=mm, heads=h)
    # mask_bias is boolean-derived (bool masks only reach this path), so
    # its cotangent is structurally zero
    return (_splitheads(dq, b, h), _splitheads(dk, b, h),
            _splitheads(dv, b, h), jnp.zeros_like(mask_bias))


_flash_attention_core_masked.defvjp(_flash_attention_core_masked_fwd,
                                    _flash_attention_core_masked_bwd)


# -- dropout variant: keep-mask generated in-kernel from the TPU PRNG ------
# (replaces the XLA path's HBM-materialised (B, H, L, L) dropout mask; the
# reference fuses attention+dropout similarly in bert_encoder_functor.cu)
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_core_dropout(q, k, v, seed, causal, block_q, block_kv,
                                  dropout_p):
    out, _ = _flash_attention_core_dropout_fwd(q, k, v, seed, causal,
                                               block_q, block_kv, dropout_p)
    return out


def _flash_attention_core_dropout_fwd(q, k, v, seed, causal, block_q,
                                      block_kv, dropout_p):
    b, ql, h, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qm, km, vm = _mergeheads(q), _mergeheads(k), _mergeheads(v)
    out_m, lse = _kept(_fwd_call(qm, km, vm, causal, block_q, block_kv,
                                 sm_scale, dropout_p=dropout_p, seed=seed))
    return _splitheads(out_m, b, h), (qm, km, vm, out_m, lse, seed, b, h)


def _flash_attention_core_dropout_bwd(causal, block_q, block_kv, dropout_p,
                                      res, dout):
    import numpy as np

    qm, km, vm, out_m, lse, seed, b, h = res
    d = qm.shape[-1]
    sm_scale = 1.0 / math.sqrt(d)
    # barrier: a structurally-constant cotangent (e.g. grad of sum(out))
    # otherwise constant-folds into the Mosaic kernel, which mis-lowers
    # broadcast operands (observed on v5e: wrong dq/dk/dv for dout=ones)
    dom = _mergeheads(jax.lax.optimization_barrier(dout))
    delta = jnp.sum(dom.astype(_F32) * out_m.astype(_F32),
                    axis=-1)[:, None, :]
    dq, dk, dv = _bwd_call(qm, km, vm, dom, lse, delta, causal, block_q,
                           block_kv, sm_scale, dropout_p=dropout_p,
                           seed=seed)
    # integer seed: cotangent is the symbolic zero dtype float0
    dseed = np.zeros(seed.shape, jax.dtypes.float0)
    return (_splitheads(dq, b, h), _splitheads(dk, b, h),
            _splitheads(dv, b, h), dseed)


_flash_attention_core_dropout.defvjp(_flash_attention_core_dropout_fwd,
                                     _flash_attention_core_dropout_bwd)


# ---------------------------------------------------------------------------
# short-sequence single-block kernels (seq <= _SHORT_SEQ_MAX): a head's
# whole (L, L) score tile lives in VMEM, so softmax is computed directly
# (no online-softmax carry) and the whole backward (delta, dq, dk, dv) is
# one launch that recomputes the scores once.
#
# Layout: the kernels take q, k, v, the output and their cotangents as
# (B, L, H*D), the layout the projections write and the output
# projection reads (from (B, L, H, D) a reshape of contiguous trailing
# axes: nothing moves), and address heads inside it: grid (B, H*D / W),
# blocks (L, W) at lane block j. W (_short_block_width) is the smallest
# multiple of 128 lanes that holds whole heads: one head a step where
# D % 128 == 0, two where D == 64 and H is even. Two 64-wide heads share
# a block because a block narrower than the 128 lanes of a vreg can be
# neither cut from a wider array nor stored densely, and sharing costs
# the MXU nothing: a 64-deep contraction and a 64-wide result occupy a
# 128 x 128 pass anyway. Per head the operands are masked to the head's
# lanes (_head_lanes), so every product adds only exact zeros to what the
# head alone would give, and the heads' results, zero outside their own
# lanes, sum to one lane-dense store. Any other (H, D) (odd H at 64,
# D = 192) moves the heads beside the batch first (_mergeheads, one
# transposing copy each way) and runs the same kernels on (B*H, L, D), one
# head a row.
# The 512 ceiling includes the bert512 shape on purpose; what a block's
# heads keep in VMEM is counted in _short_call.
# ---------------------------------------------------------------------------

_SHORT_SEQ_MAX = 512


def _short_block_width(h, d):
    """Lanes of one block of the packed (B, L, H*D) layout, or None where
    no multiple of 128 lanes holds whole heads of this width."""
    if d % 128 == 0:
        return d
    if d == 64 and h % 2 == 0:
        return 128
    return None


def _short_pack(x):
    """(B, L, H, D) -> what the short kernels address, (rows, L, H' * D)
    with H' heads a row: the packed layout itself, or the heads merged
    into the rows where it has no block width."""
    b, l, h, d = x.shape
    if _short_block_width(h, d) is None:
        return _mergeheads(x)
    return x.reshape(b, l, h * d)


def _short_unpack(x, shape):
    """Back from :func:`_short_pack`'s layout to ``shape`` (B, L, H, D)."""
    b, l, h, d = shape
    if _short_block_width(h, d) is None:
        return _splitheads(x, b, h)
    return x.reshape(shape)


def _head_lanes(x, a, d):
    """x with every lane outside head a's d zeroed (x itself where the
    block is one head wide)."""
    if x.shape[1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= a * d) & (lane < (a + 1) * d), x, 0.0)


def _short_scores(q, k, sm_scale, causal):
    s = _dot(q * sm_scale, k, trans_b=True)          # (L, L) f32
    if causal:
        L, Lk = s.shape
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def _first_head(heads, group):
    """Index, over batch and heads, of the first head of this grid step:
    each head seeds a dropout mask of its own from its index."""
    from jax.experimental import pallas as pl

    return pl.program_id(0) * heads + pl.program_id(1) * group


def _short_fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, d,
                      heads, dropout_p=0.0):
    rest = list(rest)
    seed_ref = rest.pop(0) if dropout_p > 0.0 else None
    o_ref, lse_ref = rest
    q = q_ref[...].astype(_F32)
    k = k_ref[...].astype(_F32)
    v = v_ref[...].astype(_F32)
    group = q.shape[1] // d                          # heads in this block
    out = jnp.zeros_like(q)
    for a in range(group):
        s = _short_scores(_head_lanes(q, a, d), k, sm_scale, causal)
        m = jnp.max(s, axis=1)
        p = jnp.exp(s - m[:, None])
        l = jnp.sum(p, axis=1)
        p = p / l[:, None]
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0, 0], _first_head(heads, group) + a,
                              0, 0, p.shape, dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        out = out + _dot(p, _head_lanes(v, a, d))
        lse_ref[a] = (m + jnp.log(jnp.maximum(l, 1e-30)))[None, :]
    o_ref[...] = out.astype(o_ref.dtype)


def _short_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                      sm_scale, causal, d, heads, dropout_p=0.0):
    rest = list(rest)
    seed_ref = rest.pop(0) if dropout_p > 0.0 else None
    dq_ref, dk_ref, dv_ref = rest
    q = q_ref[...].astype(_F32) * sm_scale
    k = k_ref[...].astype(_F32)
    v = v_ref[...].astype(_F32)
    o = o_ref[...].astype(_F32)
    do = do_ref[...].astype(_F32)
    group = q.shape[1] // d
    dq, dk, dv = jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
    for a in range(group):
        qa, doa = _head_lanes(q, a, d), _head_lanes(do, a, d)
        # rowsum(do * out) over the head: with dropout on it already
        # equals <dp_dropped, p>
        delta = jnp.sum(doa * o, axis=1)
        s = _short_scores(qa, k, 1.0, causal)        # q pre-scaled
        p = jnp.exp(s - lse_ref[a, 0, :][:, None])   # (L, L)
        dp = _dot(doa, v, trans_b=True)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0, 0], _first_head(heads, group) + a,
                              0, 0, p.shape, dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            dv = dv + _dot(jnp.where(keep, p * inv, 0.0).T, doa)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            dv = dv + _dot(p.T, doa)
        ds = p * (dp - delta[:, None])
        dq = dq + _dot(ds, _head_lanes(k, a, d))
        dk = dk + _dot(ds.T, qa)
    dq_ref[...] = (dq * sm_scale).astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _short_call(role, kernel, blocks, lse, seed, d, causal, dropout_p):
    """One launch of a short kernel on (rows, L, H' * D) operands
    ``blocks``: grid (rows, H' * D / W), one (L, W) block of every operand
    a step beside the logsumexp rows of the block's heads. The forward
    (``lse`` None) writes the output and the logsumexp; the backward
    reads ``lse`` and writes dq, dk, dv. ``seed`` is read with dropout
    on."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, L, hd = blocks[0].shape
    heads = hd // d
    width = _short_block_width(heads, d) or d        # merged rows: H' = 1
    group = width // d
    block = pl.BlockSpec((None, L, width), lambda i, j: (i, 0, j))
    lse_spec = pl.BlockSpec((None, group, 1, L), lambda i, j: (i, j, 0, 0))
    like = jax.ShapeDtypeStruct(blocks[0].shape, blocks[0].dtype)
    operands, in_specs = list(blocks), [block] * len(blocks)
    if lse is None:
        out_specs = [block, lse_spec]
        out_shape = [like, jax.ShapeDtypeStruct((rows, heads, 1, L), _F32)]
    else:
        operands.append(lse)
        in_specs.append(lse_spec)
        out_specs, out_shape = [block] * 3, [like] * 3
    if dropout_p > 0.0:
        operands.append(seed)
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
    # scoped VMEM: a head of the block keeps about eight (L, L) float32
    # intermediates in the backward (scores, probabilities, dP, dS, two
    # transposes, a 'highest' product's split operands) beside sixteen
    # (L, W) blocks and their float32 copies. Mosaic's 16 MiB default fell
    # short by 0.1 MB at (512, 2 x 64) with dropout and by 2.4 MB at
    # (512, 256) float32, both under 'highest' (compiling for a v5e,
    # PR 28); twice the estimate, of 128 MiB physical
    need = 4 * L * (8 * L * group + 16 * width)
    return kernel_call(
        role,
        functools.partial(kernel, sm_scale=1.0 / math.sqrt(d), causal=causal,
                          d=d, heads=heads, dropout_p=dropout_p),
        grid=(rows, hd // width),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, min(2 * need, 96 << 20))),
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_attention_core_short(q, k, v, seed, causal, dropout_p):
    out, _ = _flash_attention_core_short_fwd(q, k, v, seed, causal,
                                             dropout_p)
    return out


def _flash_attention_core_short_fwd(q, k, v, seed, causal, dropout_p):
    qp, kp, vp = _short_pack(q), _short_pack(k), _short_pack(v)
    out_p, lse = _kept(_short_call(
        "flash_attention_short_fwd", _short_fwd_kernel, (qp, kp, vp), None,
        seed, q.shape[-1], causal, dropout_p))
    return _short_unpack(out_p, q.shape), (qp, kp, vp, out_p, lse, seed)


def _flash_attention_core_short_bwd(causal, dropout_p, res, dout):
    import numpy as np

    qp, kp, vp, out_p, lse, seed = res
    # same constant-cotangent Mosaic guard as the streaming dropout bwd;
    # on the packed array, where it does not stand between the kernel's
    # layout and the product that writes the cotangent
    dop = jax.lax.optimization_barrier(_short_pack(dout))
    grads = _short_call(
        "flash_attention_short_bwd", _short_bwd_kernel,
        (qp, kp, vp, out_p, dop), lse, seed, dout.shape[-1], causal,
        dropout_p)
    dseed = None if seed is None else np.zeros(seed.shape,
                                               jax.dtypes.float0)
    return (*(_short_unpack(g, dout.shape) for g in grads), dseed)


_flash_attention_core_short.defvjp(_flash_attention_core_short_fwd,
                                   _flash_attention_core_short_bwd)


def _short_ok(q, k, causal):
    from ...framework.bringup import pallas_enabled
    from ...parallel.mesh import auto_partitioned_trace

    if not pallas_enabled() or auto_partitioned_trace():
        return False
    b, ql, h, d = q.shape
    kl = k.shape[1]
    # b*h < 2^15: _keep_mask folds (row << 16) + tile coords into one
    # int32 seed word — beyond that rows would share dropout masks
    return (ql == kl and 128 <= ql <= _SHORT_SEQ_MAX and ql % 128 == 0 and
            d % 64 == 0 and d <= 256 and b * h < (1 << 15))


@functools.partial(jax.jit, static_argnames=("causal", "dropout_p"))
def _flash_attention_pallas_short(q, k, v, seed=None, causal=False,
                                  dropout_p=0.0):
    return _flash_attention_core_short(q, k, v, seed, causal, dropout_p)


def _pick_blocks(ql, kl, block_q, block_kv):
    """Block sizes that DIVIDE the lengths (the grid floors otherwise,
    silently skipping tail tiles): the largest of {requested, halves,
    ..., 128} that divides — so a 512-default degrades to 256 at seq
    256, not straight to the 128 tile modulus. Lengths outside the
    128-modulus contract fail loudly instead of corrupting the
    output."""
    def fit(req, length):
        b = req
        while b > 128 and length % b != 0:
            b //= 2
        # a non-power-of-two request can halve past the tile modulus
        # without ever trying it — 128 is always the final fallback
        return b if b >= 128 and length % b == 0 else 128

    bq, bkv = fit(block_q, ql), fit(block_kv, kl)
    if ql % bq != 0 or kl % bkv != 0:
        raise ValueError(
            f"flash attention needs seq lengths divisible by 128 "
            f"(q {ql}, kv {kl}); route other shapes through "
            f"flash_attention_or_fallback")
    return bq, bkv


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_kv", "window"))
def _flash_attention_pallas(q, k, v, causal=False, block_q=512,
                            block_kv=512, window=None):
    # a windowed launch keeps the 512-wide blocks: at 2 x 8,192 x 32/4 x
    # 128 with a 1,024 window, forward + backward took 15.3 ms at 512,
    # 20.0 at 256 and 41.6 at 128 (a v5e, PR 31): a grid step costs about
    # 2.5 us beside its blocks, more than the narrower band saves
    bq, bkv = _pick_blocks(q.shape[1], k.shape[1], block_q, block_kv)
    return _flash_attention_core(q, k, v, causal, bq, bkv, window)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_kv"))
def _flash_attention_pallas_masked(q, k, v, mask_bias, causal=False,
                                   block_q=512, block_kv=512):
    bq, bkv = _pick_blocks(q.shape[1], k.shape[1], block_q, block_kv)
    return _flash_attention_core_masked(q, k, v, mask_bias, causal, bq, bkv)


@functools.partial(jax.jit, static_argnames=("causal", "dropout_p",
                                             "block_q", "block_kv"))
def _flash_attention_pallas_dropout(q, k, v, seed, dropout_p, causal=False,
                                    block_q=512, block_kv=512):
    bq, bkv = _pick_blocks(q.shape[1], k.shape[1], block_q, block_kv)
    return _flash_attention_core_dropout(q, k, v, seed, causal, bq, bkv,
                                         dropout_p)


def _kv_mask_bias(mask, batch, kv_len):
    """Normalise a BOOLEAN key-padding mask to an additive (batch, kv_len)
    bias, or None when ineligible: non-bool masks (e.g. learnable float
    biases, whose gradient this kernel does not produce) and per-query
    masks keep the XLA path."""
    m = mask
    if m.dtype != jnp.bool_:
        return None
    while m.ndim > 2 and m.shape[1] == 1:
        m = m[:, 0]
    if m.ndim != 2 or m.shape != (batch, kv_len):
        return None
    return jnp.where(m, 0.0, _NEG_INF).astype(_F32)


def _pallas_ok(q, k, causal, seq_floor=256, v=None):
    from ...framework.bringup import pallas_enabled
    from ...parallel.mesh import auto_partitioned_trace

    if not pallas_enabled() or auto_partitioned_trace():
        return False
    b, ql, h, d = q.shape
    kl = k.shape[1]
    # a value width of its own (MLA: keys 192, values 128) is the plain
    # streaming kernels' alone, under the same lane rule as the keys
    if v is not None and not (v.shape[-1] % 64 == 0 and v.shape[-1] <= d):
        return False
    # 128 is the hard tile modulus (the wrappers fall back to 128-wide
    # blocks when 256 doesn't divide); seq_floor is a pure perf floor —
    # where the kernel beats XLA (short sequences fuse fine in XLA).
    # Ceiling keeps K/V VMEM-resident.
    return (ql >= seq_floor and kl >= seq_floor and
            ql % 128 == 0 and kl % 128 == 0 and d % 64 == 0 and
            d <= 256 and kl <= 8192 and ql <= 8192 and
            (not causal or ql == kl) and h % k.shape[2] == 0)


def _auto_note():
    from ...parallel.mesh import auto_partitioned_trace

    return ("; multi-device GSPMD trace: Mosaic kernels cannot be "
            "automatically partitioned" if auto_partitioned_trace() else "")


def _get_flag_short():
    from ...framework.flags import get_flag

    return get_flag("flash_short_seq")


def _short_choice(q, k, causal, dropout_p):
    """Dispatch verdict for the short-seq window: the manual
    FLAGS_flash_short_seq override wins, else the on-device autotune
    (None = keep static dispatch). The single source for both the
    mask-free and the dropout dispatch sites."""
    if _get_flag_short() and _short_ok(q, k, causal):
        return "short"
    from .autotune import short_window_choice

    return short_window_choice(q, k, causal, dropout_p)


def _rng_seed_arr(key_rng):
    """(1, 1) int32 seed operand for the in-kernel PRNG from a jax key."""
    bits = jax.random.bits(key_rng, (1, 1), jnp.uint32)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def band_pairs(length, window):
    """(query, key) pairs with ``0 <= i - j < window`` among ``length``
    positions: the first W rows' triangle, then W a row."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def _work(kind, q, k, v, causal, window=None):
    """``work=`` / ``grad_work=`` of one call of the ``kind`` ("short" or
    "stream") kernels, for the ledger in ``counters``, under the roles
    the launches carry (:func:`_roles` for the streaming ones: a group's
    or a window's forward and backward add up under one role).
    Forward: Q K^T and P V, 4 B A Sq Sk D; backward: dV, dP, dQ, dK,
    8 B A Sq Sk D (the recomputed scores not counted); half of each
    under a causal mask, and with a window the band's own pairs
    (:func:`band_pairs`). Bytes: q, k, v read, the output and the f32
    logsumexp written; backward reads those with the output's cotangent
    and writes dq, dk, dv. K and V count as they are: once a key head,
    however many query heads read them."""
    b, ql, a, d = q.shape
    pairs = band_pairs(ql, window) if window is not None \
        else (0.5 if causal else 1.0) * ql * k.shape[1]
    # Q K^T is d wide and P V is v's width (the same, but for MLA)
    mm = b * a * pairs * (d + v.shape[-1]) / 2
    out = b * ql * a * v.shape[-1] * q.dtype.itemsize
    qkv, lse = nbytes(q, k, v), 4 * b * a * ql
    fwd, bwd = ("flash_attention_short_fwd", "flash_attention_short_bwd") \
        if kind == "short" else _roles(window, a // k.shape[2])
    return {"work": {fwd: (4.0 * mm, qkv + out + lse)},
            "grad_work": {bwd: (8.0 * mm, 2 * qkv + 2 * out + lse)}}


def _bump_pallas(kind, q, k, v, causal, window=None):
    """Count one dispatch to the ``kind`` kernels with the work it
    declares, that a streaming dispatch's backward is one launch
    (:func:`_bwd_call`) and, traced inside a segment that ``recompute``
    runs again, that the segment keeps this launch's output and
    logsumexp (:data:`KEPT`)."""
    from .counters import bump, in_recomputed

    bump("flash_attention", "pallas",
         **_work(kind, q, k, v, causal, window))
    if kind == "stream":
        bump("flash_attention", "bwd_one_launch")
    if in_recomputed():
        bump("flash_attention", "kept_across_recompute")


def _bump_short(q, k, v, causal):
    """Count one dispatch to the short kernels, and whether they took the
    projections' layout as it is (``short_packed``) or behind the
    transposing wrapper."""
    from .counters import bump

    _bump_pallas("short", q, k, v, causal)
    if _short_block_width(q.shape[2], q.shape[3]) is not None:
        bump("flash_attention", "short_packed")


def _one_width(q, v):
    """The short, masked and dropout kernels take one head width."""
    return v.shape[-1] == q.shape[-1]


def _local_attention(q, k, v, is_causal):
    """Best single-device mask-free attention: Pallas when eligible,
    else XLA. Used directly and as ring_attention's fallback."""
    from .counters import bump

    # a kernel that was chosen and then fails raises (no except -> XLA)
    choice = _short_choice(q, k, is_causal, 0.0) if _one_width(q, v) \
        else None
    if choice == "short":
        out = _flash_attention_pallas_short(q, k, v, causal=is_causal)
        _bump_short(q, k, v, is_causal)
        return out
    if choice == "xla":
        bump("flash_attention", "xla", "autotuned: xla wins this shape")
        return _xla_attention(q, k, v, None, 0.0, is_causal, None)
    # choice == "stream" or no autotune verdict: static streaming path
    if _pallas_ok(q, k, is_causal, v=v):
        out = _flash_attention_pallas(q, k, v, causal=is_causal)
        _bump_pallas("stream", q, k, v, is_causal)
        if not _one_width(q, v):
            # a value width of its own: latent attention's 192 / 128
            bump("flash_attention", "latent")
        return out
    bump("flash_attention", "xla",
         f"dispatch ineligible (q {tuple(q.shape)}, values "
         f"{v.shape[-1]} wide, causal={is_causal}; floor/modulus in "
         f"_pallas_ok{_auto_note()})")
    return _xla_attention(q, k, v, None, 0.0, is_causal, None)


def _grouped_attention(q, k, v, mask, dropout_p, is_causal, key_rng,
                       window):
    """Attention with fewer key/value heads than query heads, a sliding
    window, or both. The streaming kernels take them as they are; what
    they do not take goes to XLA, which batches over the key heads, or
    (a mask or dropout without a window) through the one-head-a-query
    paths on K and V repeated to the query heads. Each fallback is
    counted with its reason."""
    from ...parallel.ring import active_sequence_parallel
    from .counters import bump

    group = q.shape[2] // k.shape[2]
    if q.shape[2] != group * k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads are no multiple of "
                         f"{k.shape[2]} key/value heads")
    if window is not None and not (is_causal and window >= 1):
        raise ValueError("a sliding window is the band 0 <= i - j < window "
                         "of the causal mask: pass is_causal=True and "
                         "window >= 1")
    if window is not None and window >= k.shape[1]:
        window = None                       # the band is the whole triangle
    if window is None and group == 1:
        return flash_attention_or_fallback(q, k, v, mask, dropout_p,
                                           is_causal, key_rng)
    plain = mask is None and dropout_p == 0.0
    if active_sequence_parallel() is not None:
        if window is not None:
            raise NotImplementedError(
                "a sliding window under sequence_parallel()")
        plain = False
    if plain and _pallas_ok(q, k, is_causal, v=v):
        out = _flash_attention_pallas(q, k, v, causal=is_causal,
                                      window=window)
        _bump_pallas("stream", q, k, v, is_causal, window)
        if group > 1:
            bump("flash_attention", "grouped")
        if window is not None:
            bump("flash_attention", "windowed")
        return out
    if window is None and not plain:
        # the masked, dropout and ring paths read one key head a query head
        bump("flash_attention", "grouped_replicated_kv",
             f"mask={None if mask is None else tuple(mask.shape)}, "
             f"dropout_p={dropout_p}, sequence_parallel="
             f"{active_sequence_parallel() is not None}: K and V repeated "
             f"{group}x to the query heads")
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        return flash_attention_or_fallback(q, k, v, mask, dropout_p,
                                           is_causal, key_rng)
    bump("flash_attention", "xla",
         f"grouped/windowed dispatch ineligible (q {tuple(q.shape)}, kv "
         f"heads {k.shape[2]}, window={window}, mask="
         f"{None if mask is None else tuple(mask.shape)}, dropout_p="
         f"{dropout_p}; floor/modulus in _pallas_ok{_auto_note()})")
    return _xla_attention(q, k, v, mask, dropout_p, is_causal, key_rng,
                          window=window)


def _as_kv_padding_mask(mask, b, lk):
    """(B, Lk) bool view of a key-padding mask, or None if the mask
    depends on the query position ((B, Lq, Lk), full (B, H, Lq, Lk), ...)
    and cannot ride the ring as a per-key mask. Bool masks only: a
    non-bool mask is an ADDITIVE bias (0 = attend, -1e9 = masked) —
    casting it to bool would invert its meaning (cf. _kv_mask_bias)."""
    if mask is None:
        return None
    m = jnp.asarray(mask)
    if m.dtype != jnp.bool_:
        return None
    if m.ndim == 2 and m.shape == (b, lk):
        return m
    if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1 \
            and m.shape[0] == b and m.shape[3] == lk:
        return m[:, 0, 0, :]
    if m.ndim == 3 and m.shape == (b, 1, lk):
        return m[:, 0, :]
    return None


#: decomposition inspects the whole mask host-side; above this many
#: elements the transfer+compare costs more than it saves — tell the
#: user to pass the decomposed form instead
_DECOMPOSE_MAX_ELEMS = 1 << 26


def _decompose_concrete_mask(mask, b, lq, lk):
    """Factor a CONCRETE (non-traced) boolean query-dependent mask into
    ring-ridable parts: returns ``(kv_mask, add_causal)`` when
    ``mask == bottom-right-tril & key_padding`` (the standard causal +
    padding training mask) or ``mask`` is constant over the query axis
    (pure padding in query-dependent clothing); None otherwise.

    Eager-path only, by construction: a traced mask (any mask passed as
    an argument through jit, e.g. via TrainStep) has no inspectable
    values. Jitted training code should pass ``is_causal=True`` plus a
    (B, Lk) padding mask — that form rides the ring natively under jit,
    no decomposition needed. Very large masks are also skipped: the
    host-side verify is linear in the mask but the transfer alone
    defeats the purpose at ring-attention scale."""
    import numpy as np

    if mask is None or isinstance(mask, jax.core.Tracer):
        return None
    size = getattr(mask, "size", None)
    if isinstance(size, int) and size > _DECOMPOSE_MAX_ELEMS:
        return None
    m = np.asarray(mask)
    if m.dtype != np.bool_:
        return None
    if m.ndim == 4 and m.shape[:2] == (b, 1):
        m = m[:, 0]
    if m.shape != (b, lq, lk):
        return None
    pad = m.any(axis=1)                                   # (b, lk)
    if (m == pad[:, None, :]).all():
        return jnp.asarray(pad), False
    tril = np.tril(np.ones((lq, lk), np.bool_), k=lk - lq)
    if (m == (tril[None] & pad[:, None, :])).all():
        return jnp.asarray(pad), True
    return None


def flash_attention_or_fallback(q, k, v, mask=None, dropout_p=0.0,
                                is_causal=False, key_rng=None, window=None):
    if window is not None or k.shape[2] != q.shape[2]:
        return _grouped_attention(q, k, v, mask, dropout_p, is_causal,
                                  key_rng, window)
    if dropout_p == 0.0:
        # context parallelism: shard the sequence axis over the mesh
        # (ring / Ulysses attention) when a sequence_parallel() scope is
        # on. Key-padding masks ride the ring at block granularity, and
        # concrete causal+padding masks are decomposed onto the native
        # ring path (eager only — traced masks have no values); masks
        # the ring cannot carry raise unless FLAGS_sp_mask_fallback
        # opts into replicated attention.
        from ...parallel.ring import (_log_sp_fallback,
                                      active_sequence_parallel,
                                      ring_attention)

        sp = active_sequence_parallel()
        if sp is not None:
            axis, impl, batch_axis, mesh = sp
            kv_mask = _as_kv_padding_mask(mask, q.shape[0], k.shape[1])
            ride_causal = is_causal
            if mask is not None and kv_mask is None:
                dec = _decompose_concrete_mask(
                    mask, q.shape[0], q.shape[1], k.shape[1])
                if dec is not None:
                    kv_mask, add_causal = dec
                    ride_causal = is_causal or add_causal
            if mask is None or kv_mask is not None:
                return ring_attention(q, k, v, mesh=mesh, seq_axis=axis,
                                      batch_axis=batch_axis,
                                      is_causal=ride_causal, impl=impl,
                                      kv_mask=kv_mask)
            from ...framework.flags import get_flag

            if not get_flag("sp_mask_fallback"):
                raise ValueError(
                    "sequence_parallel attention received a "
                    "query-dependent mask it cannot ride the ring with. "
                    "Pass is_causal=True plus a (B, L) key-padding mask "
                    "instead (that form runs natively, including "
                    "combined, and works under jit — full (B, 1, Lq, "
                    "Lk) masks can only be decomposed eagerly, never "
                    "inside jit where values are traced). Or set "
                    "FLAGS_sp_mask_fallback=True to accept replicated "
                    "XLA attention for this mask (a per-device memory "
                    "and compute cliff).")
            _log_sp_fallback("query-dependent attention mask "
                             "(FLAGS_sp_mask_fallback=True)")
        elif mask is None:
            return _local_attention(q, k, v, is_causal)
    from .counters import bump

    if mask is None and dropout_p > 0.0 and key_rng is not None \
            and _one_width(q, v):
        choice = _short_choice(q, k, is_causal, dropout_p)
        if choice == "short":
            out = _flash_attention_pallas_short(
                q, k, v, seed=_rng_seed_arr(key_rng),
                causal=is_causal, dropout_p=dropout_p)
            _bump_short(q, k, v, is_causal)
            return out
        if choice == "xla":
            bump("flash_attention", "xla",
                 "autotuned: xla wins this shape")
            return _xla_attention(q, k, v, mask, dropout_p, is_causal,
                                  key_rng)
        # choice == "stream"/None: static streaming dispatch below
    if (mask is None and dropout_p > 0.0 and key_rng is not None and
            q.shape[0] * q.shape[2] < (1 << 15) and _one_width(q, v) and
            _pallas_ok(q, k, is_causal)):
        # dropout rides the kernel's hardware PRNG — no HBM mask tensor
        # (the XLA path materialises (B, H, L, L) keep masks). Floor is
        # the shared 256: with rbg keys XLA-with-dropout wins at seq 128
        # (122.8K vs 107.7K tok/s, BERT-base b128 v5e) and loses from
        # 256 up (105.8K vs 111.8K at b64/s256; 77.0K vs 98.9K at
        # b32/s512)
        out = _flash_attention_pallas_dropout(
            q, k, v, _rng_seed_arr(key_rng), dropout_p, causal=is_causal)
        _bump_pallas("stream", q, k, v, is_causal)
        return out
    if mask is not None and dropout_p == 0.0 and _one_width(q, v) \
            and _pallas_ok(q, k, is_causal):
        # key-padding masks ride the Pallas kernel as an additive kv bias;
        # per-query masks keep the XLA path
        bias = _kv_mask_bias(jnp.asarray(mask), q.shape[0], k.shape[1])
        if bias is not None:
            out = _flash_attention_pallas_masked(q, k, v, bias,
                                                 causal=is_causal)
            _bump_pallas("stream", q, k, v, is_causal)
            return out
    bump("flash_attention", "xla",
         f"dropout/mask dispatch ineligible (q {tuple(q.shape)}, mask="
         f"{None if mask is None else tuple(mask.shape)}, dropout_p="
         f"{dropout_p}; floor/modulus in _pallas_ok or per-query mask"
         f"{_auto_note()})")
    return _xla_attention(q, k, v, mask, dropout_p, is_causal, key_rng)
