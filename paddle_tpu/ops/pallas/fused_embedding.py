"""Fused embedding lookup + sequence pool Pallas kernel.

Reference: /root/reference/paddle/fluid/operators/fused/
fused_embedding_seq_pool_op.cc (lookup_table + sequence_pool fused so the
(B, S, D) gathered tensor never exists). The XLA lowering of
gather-then-reduce materializes that intermediate in HBM; for CTR-style
models (tens of sparse fields, large D) the fused kernel keeps each
pooled row accumulating in VMEM and streams exactly one table row per
grid step via scalar-prefetched indices — HBM traffic drops from
O(B*S*D) write + read to O(B*S*D) read + O(B*D) write.

Forward runs the Pallas kernel on TPU (XLA fallback elsewhere); backward
is a plain XLA scatter-add (scatter is not an XLA weak spot, and the
(B, S, D) intermediate does not appear in the gradient either).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .counters import kernel_call, nbytes


def _xla_bag(table, ids, combiner):
    """Reference path: masked gather + pooled reduce (what XLA fuses)."""
    valid = (ids >= 0)
    w = valid.astype(table.dtype)
    emb = table[jnp.maximum(ids, 0)] * w[..., None]     # (B, S, D)
    out = jnp.sum(emb, axis=1)
    if combiner == "sum":
        return out
    cnt = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1.0)
    if combiner == "mean":
        return out / cnt
    if combiner == "sqrtn":
        return out / jnp.sqrt(cnt)
    raise ValueError(f"unknown combiner {combiner!r}")


def _bag_kernel(ids_ref, table_blk_ref, out_ref, cnt_ref, *, seq, combiner):
    """Blocks are 8 rows tall — the TPU sublane tile modulus; (1, d)
    row blocks do not lower on real hardware (Mosaic requires the
    second-to-last block dim % 8). The streamed table block is the
    8-row group containing the wanted row; the output block holds 8
    bags, revisited across the 8*seq grid steps that share it."""
    bi = pl.program_id(0)
    s = pl.program_id(1)
    off = bi % 8

    @pl.when(jnp.logical_and(s == 0, off == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(s == 0)
    def _init_cnt():
        cnt_ref[off] = 0.0

    idx = ids_ref[bi * seq + s]
    valid = (idx >= 0).astype(jnp.float32)
    # accumulate in f32 regardless of table dtype: bf16 += over long
    # bags loses low bits and diverges from the XLA fallback (ADVICE r2)
    row = table_blk_ref[pl.dslice(jnp.maximum(idx, 0) % 8, 1),
                        :].astype(jnp.float32)
    out_ref[pl.dslice(off, 1), :] += valid * row
    cnt_ref[off] += valid

    if combiner in ("mean", "sqrtn"):
        @pl.when(s == seq - 1)
        def _normalize():
            c = jnp.maximum(cnt_ref[off], 1.0)
            denom = c if combiner == "mean" else jnp.sqrt(c)
            out_ref[pl.dslice(off, 1), :] = \
                out_ref[pl.dslice(off, 1), :] / denom


def _bag_pallas(table, ids, combiner):
    b, s = ids.shape
    v, d = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s),
        in_specs=[
            # the 8-row table group containing the wanted row
            pl.BlockSpec(
                (8, d), lambda bi, si, idv: (jnp.maximum(
                    idv[bi * s + si], 0) // 8, 0)),
        ],
        # 8 bags per output block, shared by 8 consecutive bi
        out_specs=pl.BlockSpec((8, d), lambda bi, si, idv: (bi // 8, 0)),
        scratch_shapes=[pltpu.SMEM((8,), jnp.float32)],
    )
    kernel = functools.partial(_bag_kernel, seq=s, combiner=combiner)
    out = kernel_call(
        "fused_embedding",
        kernel,
        grid_spec=grid_spec,
        # f32 accumulator output; cast back to the table dtype at the end
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
    )(ids.reshape(-1).astype(jnp.int32), table)
    return out.astype(table.dtype)


def _eligible(table, ids):
    from ...framework.bringup import pallas_enabled
    from ...parallel.mesh import auto_partitioned_trace

    if not pallas_enabled() or auto_partitioned_trace():
        return False
    v, d = table.shape
    b = ids.shape[0]
    # lane-aligned embedding dim; tiny bags fuse fine in XLA; the 8-row
    # block layout needs vocab and batch on the sublane modulus. f32
    # tables only: a 16-bit table packs two rows per sublane and Mosaic
    # refuses the kernel's dynamic single-row slice of its block
    # ("cannot statically prove that index in dimension 0 is a multiple
    # of 8", v5e, PR 21).
    return (table.dtype == jnp.float32 and d % 128 == 0
            and ids.shape[1] >= 8 and v % 8 == 0 and b % 8 == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bag_core(table, ids, combiner):
    from .counters import bump

    if _eligible(table, ids):
        # a chosen kernel that fails raises: a silent except here once
        # hid a Mosaic tile-rule bug for a full round
        out = _bag_pallas(table, ids, combiner)
        # a gather and a sum: no matmul; the ids and one table row per
        # id read, the pooled rows written
        bump("fused_embedding", "pallas", work={"fused_embedding": (
            0.0, nbytes(ids, out)
            + ids.size * table.shape[1] * table.dtype.itemsize)})
        return out
    bump("fused_embedding", "xla",
         f"ineligible (table {tuple(table.shape)} {table.dtype}, ids "
         f"{tuple(ids.shape)}: need f32, d%128==0, seq>=8, vocab%8==0, "
         "batch%8==0, pallas enabled, no multi-device GSPMD trace)")
    return _xla_bag(table, ids, combiner)


def _bag_fwd(table, ids, combiner):
    out = _bag_core(table, ids, combiner)
    valid = (ids >= 0)
    cnt = jnp.maximum(jnp.sum(valid.astype(table.dtype), axis=1), 1.0)
    # table rides along for its shape/dtype only (same buffer, no copy)
    return out, (ids, cnt, table)


def _bag_bwd(combiner, res, g):
    ids, cnt, table = res
    tshape, tdtype = table.shape, table.dtype
    if combiner == "mean":
        g = g / cnt[:, None]
    elif combiner == "sqrtn":
        g = g / jnp.sqrt(cnt)[:, None]
    valid = (ids >= 0)
    safe = jnp.where(valid, ids, 0)
    rows = jnp.broadcast_to(g[:, None, :], ids.shape + (g.shape[-1],))
    rows = rows * valid[..., None].astype(g.dtype)
    d_table = jnp.zeros(tshape, tdtype).at[safe.reshape(-1)].add(
        rows.reshape(-1, g.shape[-1]))
    return d_table, None


_bag_core.defvjp(_bag_fwd, _bag_bwd)


def fused_embedding_seq_pool(table, ids, combiner="sum", padding_idx=None,
                             name=None):
    """Pooled bag-of-ids embedding (fused_embedding_seq_pool_op.cc).

    table: (V, D) float; ids: (B, S) int — entries equal to
    ``padding_idx`` (or negative) contribute nothing. combiner:
    sum | mean | sqrtn (mean/sqrtn normalize by the VALID id count).
    Returns (B, D).
    """
    from ...framework.tensor import Tensor

    if combiner not in ("sum", "mean", "sqrtn"):
        # validate up front: the Pallas kernel would otherwise silently
        # sum-pool while the XLA fallback raises (platform-dependent bug)
        raise ValueError(f"unknown combiner {combiner!r}")
    t = table.value if isinstance(table, Tensor) else jnp.asarray(table)
    i = ids.value if isinstance(ids, Tensor) else jnp.asarray(ids)
    if padding_idx is not None and padding_idx >= 0:
        i = jnp.where(i == padding_idx, -1, i)
    out = _bag_core(t, i, combiner)
    return out
