"""Ragged paged attention for TPU decode steps (Pallas kernel + XLA
gather fallback).

The LLM decode data path (PAPERS.md "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU"): each
sequence's KV history lives in fixed-size PAGES of a device-resident
pool, and a decode step attends one query token per sequence against
only that sequence's LIVE pages, addressed through a per-sequence page
table — no length padding, so a batch mixing a 40-token and a
4000-token context does 40+4000 tokens of work, not 2×4000.

Layout:

- ``q``          (B, H, D)        one query token per sequence
- ``k_pages``    (P, S, H, D)     the pool: P pages of S tokens each
- ``v_pages``    (P, S, H, D)
- ``page_table`` (B, T) int32     page ids per sequence, -1 = unused
- ``seq_lens``   (B,) int32       live tokens per sequence (ragged)

Kernel shape: grid (B, T) with the page table SCALAR-PREFETCHED
(``PrefetchScalarGridSpec``) so each grid step's KV block is DMA'd
straight from the page the table names — the gather never materializes
a contiguous copy of the context. Online-softmax carries (m, l, acc)
persist in VMEM scratch across a sequence's page steps; pages past
``ceil(seq_len/S)`` are skipped (``pl.when``), which is where the
ragged win comes from. (Formulation notes sit above the kernel.)

Dispatch follows the established kernel pattern (flash_attention.py):
an eligibility gate (``_paged_ok``), per-decision counters
(``paged_attention.pallas`` / ``.xla`` with a reason), an autotuned
choice persisted in the PR 10 disk cache (autotune.py), and
``PADDLE_PAGED_ATTENTION=0`` as the bitwise escape leg that pins the
XLA gather path.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from .counters import kernel_call

_NEG_INF = -1e30
_F32 = jnp.float32

__all__ = ["paged_attention", "paged_write", "paged_prefill_write",
           "paged_write_quant", "paged_prefill_write_quant"]


# ---------------------------------------------------------------------------
# XLA gather fallback — the reference data path the kernel is parity-
# gated against (and the only path off-TPU / for ineligible shapes)
# ---------------------------------------------------------------------------
def _xla_paged_attention(q, k_pages, v_pages, page_table, seq_lens):
    """Gather each sequence's pages, mask the ragged tail, attend."""
    B, H, D = q.shape
    S = k_pages.shape[1]
    T = page_table.shape[1]
    safe = jnp.maximum(page_table, 0)                      # (B, T)
    k = k_pages[safe].reshape(B, T * S, H, D)
    v = v_pages[safe].reshape(B, T * S, H, D)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(_F32), k.astype(_F32),
                   preferred_element_type=_F32) / math.sqrt(D)
    pos = jnp.arange(T * S, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None],
                  s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v.astype(_F32))
    return out.astype(q.dtype)


def _xla_paged_attention_quant(q, k_pages, v_pages, k_scales, v_scales,
                               page_table, seq_lens):
    """Quantized-pool twin of :func:`_xla_paged_attention`: the pool
    holds int8 rows with one f32 scale per token row (codec.py's
    ``jnp_encode_kv_rows`` layout, block = H*D); dequant happens inside
    the gather, so nothing f32-sized ever persists in HBM."""
    B, H, D = q.shape
    S = k_pages.shape[1]
    T = page_table.shape[1]
    safe = jnp.maximum(page_table, 0)                      # (B, T)
    ks = k_scales[safe].reshape(B, T * S)                  # (B, K)
    vs = v_scales[safe].reshape(B, T * S)
    k = k_pages[safe].reshape(B, T * S, H, D).astype(_F32)
    v = v_pages[safe].reshape(B, T * S, H, D).astype(_F32)
    k = k * ks[..., None, None]
    v = v * vs[..., None, None]
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(_F32), k,
                   preferred_element_type=_F32) / math.sqrt(D)
    pos = jnp.arange(T * S, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None],
                  s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, T), page table scalar-prefetched, online
# softmax carried in VMEM scratch across a sequence's page steps.
#
# Mosaic-friendly formulation: the pool is viewed as (P, S, H*D) so a page
# is one lane-dense (S, H*D) tile, and the per-head reductions ride the
# MXU through a 0/1 head-segment matrix instead of in-kernel transposes
# or batched vector-matrix products (neither lowers):
#   scores (S, Hp) = (k * q_row) @ seg          seg[c, h] = (c // D == h)
#   p_exp  (S, HD) = p @ seg^T                  (each head's prob over its D)
#   acc    (1, HD) += sum_s(p_exp * v)
# Hp is the head count padded to one 128-lane tile. The three segment
# matmuls run at HIGHEST precision: they only SUM or COPY f32 values, so
# the kernel is f32-exact whatever the ambient matmul precision.
# ---------------------------------------------------------------------------
_HEAD_LANES = 128
_HI = jax.lax.Precision.HIGHEST
#: bytes of VMEM the gate lets one grid step hold (v5e's default scoped
#: limit is 16 MiB; leave headroom for Mosaic's own buffers)
_VMEM_BUDGET = 12 * 1024 * 1024


def _paged_attn_kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                       page_size, table_width, sm_scale, quant):
    """One (sequence, page) grid step. With ``quant`` the page DMA
    brings int8 rows + their per-row f32 scales into VMEM and the
    dequant (one multiply per row) happens right there — the f32 view
    of a page exists only transiently in VMEM, which is the whole ~4x
    pool-headroom win."""
    from jax.experimental import pallas as pl

    del pt_ref, table_width                 # consumed by the index maps
    if quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    seg_ref, segt_ref, o_ref, m_sc, l_sc, acc_sc = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length = lens_ref[b]

    @pl.when(j * page_size < length)
    def _page():
        q = q_ref[...].astype(_F32) * sm_scale           # (1, HD)
        k = k_ref[...].astype(_F32)                      # (S, HD)
        v = v_ref[...].astype(_F32)
        if quant:
            k = k * ks_ref[...]                          # (S, 1) scales
            v = v * vs_ref[...]
        s = jnp.dot(k * q, seg_ref[...], precision=_HI,
                    preferred_element_type=_F32)         # (S, Hp)
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_sc[...]                               # (8, Hp) rows equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new[0:1, :])                   # (S, Hp)
        alpha = jnp.exp(m_prev - m_new)                  # (8, Hp)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=0, keepdims=True)
        m_sc[...] = m_new
        p_exp = jnp.dot(p, segt_ref[...], precision=_HI,
                        preferred_element_type=_F32)     # (S, HD)
        alpha_exp = jnp.dot(alpha, segt_ref[...], precision=_HI,
                            preferred_element_type=_F32)  # (8, HD)
        acc_sc[...] = acc_sc[...] * alpha_exp + jnp.sum(
            p_exp * v, axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        norm = jnp.dot(jnp.maximum(l_sc[...], 1e-30), segt_ref[...],
                       precision=_HI, preferred_element_type=_F32)
        o_ref[...] = (acc_sc[...] / norm)[0:1, :].astype(o_ref.dtype)


def _paged_call(q, k_pages, v_pages, page_table, seq_lens, k_scales=None,
                v_scales=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    P, S = k_pages.shape[:2]
    T = page_table.shape[1]
    HD = H * D
    quant = k_scales is not None
    # dead/unused table entries route the DMA at a real page (0); the
    # pl.when page gate skips their compute and the ragged mask keeps
    # their positions out of the softmax either way. Flat 1-D so the
    # SMEM copy is B*T words, not a lane-padded 2-D tile per row.
    safe_table = jnp.maximum(page_table, 0).astype(jnp.int32).reshape(-1)
    seg = (jnp.arange(HD, dtype=jnp.int32)[:, None] // D
           == jnp.arange(_HEAD_LANES, dtype=jnp.int32)[None, :]
           ).astype(_F32)                                # (HD, Hp)

    def page_map(b, j, pt, lens):
        return (pt[b * T + j], 0, 0)

    def row_map(b, j, pt, lens):
        return (b, 0, 0)

    def const_map(b, j, pt, lens):
        return (0, 0)

    page_spec = pl.BlockSpec((None, S, HD), page_map)
    in_specs = [pl.BlockSpec((None, 1, HD), row_map), page_spec, page_spec]
    operands = [q.reshape(B, 1, HD), k_pages.reshape(P, S, HD),
                v_pages.reshape(P, S, HD)]
    if quant:
        scale_spec = pl.BlockSpec((None, S, 1), page_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales.reshape(P, S, 1), v_scales.reshape(P, S, 1)]
    in_specs += [pl.BlockSpec((HD, _HEAD_LANES), const_map),
                 pl.BlockSpec((_HEAD_LANES, HD), const_map)]
    operands += [seg, seg.T]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # page_table, seq_lens
        grid=(B, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, HD), row_map),
        scratch_shapes=[
            pltpu.VMEM((8, _HEAD_LANES), _F32),   # running max m
            pltpu.VMEM((8, _HEAD_LANES), _F32),   # running normalizer l
            pltpu.VMEM((8, HD), _F32),            # value accumulator
        ],
    )
    out = kernel_call(
        "paged_attention",
        functools.partial(_paged_attn_kernel, page_size=S, table_width=T,
                          sm_scale=1.0 / math.sqrt(D), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, HD), q.dtype),
    )(safe_table, seq_lens.astype(jnp.int32), *operands)
    return out.reshape(B, H, D)


@jax.jit
def _paged_attention_pallas(q, k_pages, v_pages, page_table, seq_lens):
    return _paged_call(q, k_pages, v_pages, page_table, seq_lens)


@jax.jit
def _paged_attention_pallas_quant(q, k_pages, v_pages, k_scales,
                                  v_scales, page_table, seq_lens):
    return _paged_call(q, k_pages, v_pages, page_table, seq_lens,
                       k_scales, v_scales)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _paged_ok(q, k_pages) -> bool:
    from ...framework.bringup import pallas_enabled

    if not pallas_enabled():
        return False
    B, H, D = q.shape
    S = k_pages.shape[1]
    # S % 128 / (H*D) % 128: a page is one (S, H*D) tile, sublane- and
    # lane-aligned for the segment matmuls; D % 64 / <= 256 mirrors the
    # flash kernel's head-dim contract; H <= 128: heads pad to one lane
    # tile. The VMEM bound counts the double-buffered K and V page
    # blocks plus the kernel's (S, H*D) f32 temporaries, ~8 tiles.
    return (S % 128 == 0 and D % 64 == 0 and D <= 256 and H <= 128 and
            (H * D) % 128 == 0 and 8 * S * H * D * 4 <= _VMEM_BUDGET)


def _escape_pinned() -> bool:
    """PADDLE_PAGED_ATTENTION=0 pins the XLA gather path — the bitwise
    escape leg (same shape as PADDLE_IR_PASSES=0 for the pass
    pipeline)."""
    return os.environ.get("PADDLE_PAGED_ATTENTION", "").strip() == "0"


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    k_scales=None, v_scales=None):
    """Decode-step attention over the paged KV pool: best path for the
    backend (Pallas when eligible — autotune-arbitrated in the window
    where it competes with XLA — else the XLA gather fallback). One
    counter bump per dispatch decision (trace time under jit).

    When ``k_scales``/``v_scales`` (P, S) are given the pool is int8
    (``kv_codec="int8"``): both paths dequant per token row inside the
    gather/page-DMA; the quant leg keeps the same escape env and
    counters but skips the f32 autotune verdict (different memory
    traffic, not comparable)."""
    from .counters import bump

    # no work is declared to the ledger here: what a decode step must
    # read follows the live lengths, which are values, not shapes
    quant = k_scales is not None
    if _escape_pinned():
        bump("paged_attention", "xla", "PADDLE_PAGED_ATTENTION=0 pin")
        if quant:
            return _xla_paged_attention_quant(q, k_pages, v_pages,
                                              k_scales, v_scales,
                                              page_table, seq_lens)
        return _xla_paged_attention(q, k_pages, v_pages, page_table,
                                    seq_lens)
    if quant:
        if _paged_ok(q, k_pages):
            out = _paged_attention_pallas_quant(
                q, k_pages, v_pages, k_scales, v_scales,
                page_table, seq_lens)
            bump("paged_attention", "pallas")
            return out
        bump("paged_attention", "xla",
             f"dispatch ineligible (q {tuple(q.shape)}, page "
             f"{k_pages.shape[1]}; gate in _paged_ok)")
        return _xla_paged_attention_quant(q, k_pages, v_pages, k_scales,
                                          v_scales, page_table, seq_lens)
    if _paged_ok(q, k_pages):
        from .autotune import paged_attention_choice

        choice = paged_attention_choice(q, k_pages, page_table)
        if choice == "xla":
            bump("paged_attention", "xla", "autotuned: xla wins this shape")
            return _xla_paged_attention(q, k_pages, v_pages, page_table,
                                        seq_lens)
        out = _paged_attention_pallas(q, k_pages, v_pages,
                                      page_table, seq_lens)
        bump("paged_attention", "pallas")
        return out
    bump("paged_attention", "xla",
         f"dispatch ineligible (q {tuple(q.shape)}, page "
         f"{k_pages.shape[1]}; gate in _paged_ok)")
    return _xla_paged_attention(q, k_pages, v_pages, page_table, seq_lens)


# ---------------------------------------------------------------------------
# page writes: decode-step single-token scatter + prefill bulk scatter
# ---------------------------------------------------------------------------
def paged_write(k_pages, v_pages, page_table, positions, new_k, new_v,
                active=None):
    """Scatter ONE new token's K/V per sequence into its page slot.

    ``positions`` (B,) is the absolute write position; the owning page
    is ``page_table[b, positions[b] // S]``. Inactive batch slots (and
    unused -1 table entries) are routed at the reserved trash page 0,
    which the pool manager never allocates — their writes land
    harmlessly where no live page table points."""
    S = k_pages.shape[1]
    pidx = jnp.take_along_axis(page_table,
                               (positions // S)[:, None], axis=1)[:, 0]
    pidx = jnp.maximum(pidx, 0)
    if active is not None:
        pidx = jnp.where(active, pidx, 0)
    off = positions % S
    k_pages = k_pages.at[pidx, off].set(new_k.astype(k_pages.dtype))
    v_pages = v_pages.at[pidx, off].set(new_v.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_prefill_write(k_pages, v_pages, page_ids, new_k, new_v):
    """Scatter one prefilled prompt's K/V into its allocated pages.

    ``page_ids`` (n,) names the pages; ``new_k``/``new_v`` are
    (n * S, H, D) — the prompt padded up to a whole number of pages
    (pad positions are dead: seq_lens masks them at attention time)."""
    S = k_pages.shape[1]
    n = page_ids.shape[0]
    H, D = new_k.shape[-2], new_k.shape[-1]
    k_pages = k_pages.at[page_ids].set(
        new_k.reshape(n, S, H, D).astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids].set(
        new_v.reshape(n, S, H, D).astype(v_pages.dtype))
    return k_pages, v_pages


def paged_write_quant(k_pages, v_pages, k_scales, v_scales, page_table,
                      positions, new_k, new_v, active=None):
    """int8-pool twin of :func:`paged_write`: each token row is
    encoded (codec.py ``jnp_encode_kv_rows``, one scale per row) and
    both the int8 payload and the f32 scale land in the slot the page
    table names. Trash-page-0 routing for inactive lanes is identical
    — their scales land there too, harmlessly."""
    from ...ps.codec import jnp_encode_kv_rows

    S = k_pages.shape[1]
    pidx = jnp.take_along_axis(page_table,
                               (positions // S)[:, None], axis=1)[:, 0]
    pidx = jnp.maximum(pidx, 0)
    if active is not None:
        pidx = jnp.where(active, pidx, 0)
    off = positions % S
    qk, sk = jnp_encode_kv_rows(new_k)                  # (B,H,D) / (B,)
    qv, sv = jnp_encode_kv_rows(new_v)
    k_pages = k_pages.at[pidx, off].set(qk)
    v_pages = v_pages.at[pidx, off].set(qv)
    k_scales = k_scales.at[pidx, off].set(sk)
    v_scales = v_scales.at[pidx, off].set(sv)
    return k_pages, v_pages, k_scales, v_scales


def paged_prefill_write_quant(k_pages, v_pages, k_scales, v_scales,
                              page_ids, new_k, new_v):
    """int8-pool twin of :func:`paged_prefill_write`: the (n * S, H, D)
    prompt K/V is row-encoded and scattered as whole pages, scales
    reshaped alongside as (n, S)."""
    from ...ps.codec import jnp_encode_kv_rows

    S = k_pages.shape[1]
    n = page_ids.shape[0]
    H, D = new_k.shape[-2], new_k.shape[-1]
    qk, sk = jnp_encode_kv_rows(new_k)              # (n*S,H,D) / (n*S,)
    qv, sv = jnp_encode_kv_rows(new_v)
    k_pages = k_pages.at[page_ids].set(qk.reshape(n, S, H, D))
    v_pages = v_pages.at[page_ids].set(qv.reshape(n, S, H, D))
    k_scales = k_scales.at[page_ids].set(sk.reshape(n, S))
    v_scales = v_scales.at[page_ids].set(sv.reshape(n, S))
    return k_pages, v_pages, k_scales, v_scales
