"""Sequence/context parallelism: ring attention and Ulysses (all-to-all).

The reference framework predates sequence parallelism entirely (SURVEY §2.6:
TP/SP/CP/ring attention absent; long sequences were handled only via
recompute, /root/reference/python/paddle/fluid/backward.py:629, and pipeline
micro-batching, /root/reference/paddle/fluid/framework/section_worker.cc).
This module is the TPU-first design for that gap: the sequence axis of
q/k/v is sharded over a named mesh axis, and

- **ring attention**: every device keeps its local Q block resident and
  streams K/V blocks around the ring with `lax.ppermute` (ICI
  neighbour-exchange), combining partial results with a numerically stable
  online softmax — flash attention across chips.
- **Ulysses**: `lax.all_to_all` re-shards (seq-sharded, all heads) ->
  (full seq, head-sharded), runs ordinary attention locally per head group,
  and re-shards back. Cheaper for moderate sequence lengths when
  num_heads % axis_size == 0.

Both are plain collectives inside `shard_map`, so they compose with data /
tensor parallel axes of the same mesh and with `jax.grad` (XLA
differentiates ppermute/all_to_all natively).

NOTE on tracing: the `sequence_parallel()` context is consulted at TRACE
time. A function jitted outside the context keeps its non-ring executable
in jax's cache even if later called inside the context (and vice versa).
For the training hot path, prefer the explicit
`jit.TrainStep(..., sequence_parallel="sp")` knob, which bakes the ring
path into the compiled step deterministically.
"""
from __future__ import annotations

import functools
import math
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from ..framework.flags import define_flag, get_flag
from .mesh import get_mesh

_NEG_INF = -1e30

define_flag("ring_flash", True,
            "Route each ring-attention step's local block compute through "
            "the Pallas flash kernel (SURVEY hard part f). Eligible shapes "
            "only; False keeps the einsum online-softmax walk everywhere "
            "(the A/B arm)")


# Tests flip this to run interpret-mode Pallas under shard_map: the hlo
# interpreter evaluates kernel bodies as jax ops, where kernel-internal
# constants carry empty vma and trip check_vma (jax 0.9 rough edge).
# Real Mosaic lowering never vma-types kernel internals.
_SHARD_MAP_CHECK_VMA = [True]


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         check_vma=_SHARD_MAP_CHECK_VMA[0])


# ---------------------------------------------------------------------------
# flash-ring: the ring walk's local block compute routed through the Pallas
# flash kernels (SURVEY.md hard part f: "ring attention as a Pallas
# flash-attention kernel with ppermute KV rotation"). Forward runs the
# streaming flash FORWARD kernel on each arriving KV block and merges the
# normalized block outputs by their logsumexp; backward re-walks the ring
# calling the flash backward kernel with the GLOBAL lse (the standard flash
# decomposition: p = exp(s - lse_global) is the true probability, so each
# block's dq/dk/dv contribution is exact), rotating each block's dk/dv
# accumulators around the ring WITH the block so they arrive home after a
# full circle.
# ---------------------------------------------------------------------------


def _ring_flash_eligible(q, k, is_causal):
    """Static-shape gate for the flash-ring path (per-device shards)."""
    from ..framework.bringup import pallas_enabled

    # FLAGS_ring_flash is defined at this module's import, so a plain
    # lookup is safe
    if not get_flag("ring_flash") or not pallas_enabled():
        return False
    b, lq, h, d = q.shape
    lk = k.shape[1]
    # kernel tile modulus 128, head_dim lane modulus 64; causal block
    # classification below (before/diagonal/after) assumes equal shards
    return (lq % 128 == 0 and lk % 128 == 0 and lq >= 128 and lk >= 128
            and d % 64 == 0 and d <= 256 and (not is_causal or lq == lk))


def _ring_branch(origin, idx, is_causal, bias, masked):
    """0 = skip, 1 = full block, 2 = diagonal (in-block causal mask).

    With equal shards, block `origin` is entirely before the local Q
    block iff origin < idx (full), entirely after iff origin > idx
    (skip under causal). Mask-empty blocks are skipped outright."""
    if is_causal:
        branch = jnp.where(origin > idx, 0,
                           jnp.where(origin == idx, 2, 1))
    else:
        branch = jnp.ones((), jnp.int32)
    if masked:
        branch = jnp.where(jnp.any(bias > -1e29), branch, 0)
    return branch


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_flash(q, k, v, kv_bias, axis_name, axis_size, is_causal, masked):
    out, _ = _ring_flash_fwd(q, k, v, kv_bias, axis_name, axis_size,
                             is_causal, masked)
    return out


def _ring_flash_fwd(q, k, v, kv_bias, axis_name, axis_size, is_causal,
                    masked):
    from ..ops.pallas.flash_attention import (_fwd_call, _mergeheads,
                                              _pick_blocks, _splitheads)

    size = axis_size
    idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    bq, bkv = _pick_blocks(lq, lk, 512, 512)
    qm, km, vm = _mergeheads(q), _mergeheads(k), _mergeheads(v)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def merge(acc, lse, out_b, lse_b):
        # both partials are normalized over disjoint key sets: combine
        # with logsumexp weights (numerically the online-softmax rescale)
        new = jnp.logaddexp(lse, lse_b)                  # (bh, 1, lq)
        w_old = jnp.exp(lse - new)[:, 0, :, None]        # (bh, lq, 1)
        w_new = jnp.exp(lse_b - new)[:, 0, :, None]
        return acc * w_old + out_b.astype(jnp.float32) * w_new, new

    def step_update(s, acc, lse, kc, vc, bc):
        origin = jnp.mod(idx - s, size)

        def compute(causal):
            mb = bc[:, None, :] if masked else None
            out_b, lse_b = _fwd_call(qm, kc, vc, causal, bq, bkv,
                                     sm_scale, mask_bias=mb, heads=h)
            return merge(acc, lse, out_b, lse_b)

        branch = _ring_branch(origin, idx, is_causal, bc, masked)
        return jax.lax.switch(branch, (lambda: (acc, lse),
                                       lambda: compute(False),
                                       lambda: compute(True)))

    def body(s, carry):
        acc, lse, kc, vc, bc = carry
        acc, lse = step_update(s, acc, lse, kc, vc, bc)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if masked:
            bc = jax.lax.ppermute(bc, axis_name, perm)
        return acc, lse, kc, vc, bc

    # carries derive from inputs (0*x) for shard_map's vma typing; lse in
    # f32 at the kernels' -1e30 floor (finite: logaddexp/exp stay NaN-free
    # even for fully-masked rows)
    acc0 = (0.0 * qm).astype(jnp.float32)
    lse0 = (0.0 * qm[..., 0]).astype(jnp.float32)[:, None, :] + _NEG_INF
    bc0 = kv_bias if masked else jnp.zeros((), jnp.float32)
    # last block needs no rotation afterwards: size-1 rotations, final
    # fold outside the loop (saves one ICI hop)
    acc, lse, kc, vc, bc = jax.lax.fori_loop(
        0, size - 1, body, (acc0, lse0, km, vm, bc0))
    acc, lse = step_update(size - 1, acc, lse, kc, vc, bc)
    out_m = acc.astype(q.dtype)
    return (_splitheads(out_m, b, h),
            (qm, km, vm, out_m, lse, kv_bias, b, h))


def _ring_flash_bwd(axis_name, axis_size, is_causal, masked, res, dout):
    from ..ops.pallas.flash_attention import (_bwd_call, _mergeheads,
                                              _pick_blocks, _splitheads)

    qm, km, vm, out_m, lse, kv_bias, b, h = res
    size = axis_size
    idx = jax.lax.axis_index(axis_name)
    bh, lq, d = qm.shape
    lk = km.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    bq, bkv = _pick_blocks(lq, lk, 512, 512)
    # constant-cotangent Mosaic guard, as in the single-device bwd paths
    dom = _mergeheads(jax.lax.optimization_barrier(dout))
    delta = jnp.sum(dom.astype(jnp.float32) * out_m.astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (bh, 1, lq)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(s, dq, dkc, dvc, kc, vc, bc):
        origin = jnp.mod(idx - s, size)

        def compute(causal):
            mb = bc[:, None, :] if masked else None
            dqb, dkb, dvb = _bwd_call(qm, kc, vc, dom, lse, delta, causal,
                                      bq, bkv, sm_scale, mask_bias=mb,
                                      heads=h)
            return (dq + dqb.astype(jnp.float32),
                    dkc + dkb.astype(jnp.float32),
                    dvc + dvb.astype(jnp.float32))

        branch = _ring_branch(origin, idx, is_causal, bc, masked)
        return jax.lax.switch(branch, (lambda: (dq, dkc, dvc),
                                       lambda: compute(False),
                                       lambda: compute(True)))

    def body(s, carry):
        dq, dkc, dvc, kc, vc, bc = carry
        dq, dkc, dvc = step(s, dq, dkc, dvc, kc, vc, bc)
        # each block's grad accumulators travel WITH the block: after a
        # full circle (size process+rotate iterations) dk/dv are home
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if masked:
            bc = jax.lax.ppermute(bc, axis_name, perm)
        return dq, dkc, dvc, kc, vc, bc

    dq0 = (0.0 * qm).astype(jnp.float32)
    dk0 = (0.0 * km).astype(jnp.float32)
    dv0 = (0.0 * vm).astype(jnp.float32)
    bc0 = kv_bias if masked else jnp.zeros((), jnp.float32)
    dq, dk, dv, _, _, _ = jax.lax.fori_loop(
        0, size, body, (dq0, dk0, dv0, km, vm, bc0))
    return (_splitheads(dq.astype(qm.dtype), b, h),
            _splitheads(dk.astype(km.dtype), b, h),
            _splitheads(dv.astype(vm.dtype), b, h),
            jnp.zeros_like(kv_bias))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# ---------------------------------------------------------------------------
# ring attention (inside shard_map; q/k/v local blocks (B, L_local, H, D))
# ---------------------------------------------------------------------------


def ring_attention_local(q, k, v, axis_name: str, is_causal: bool = False,
                         axis_size: Optional[int] = None, kv_mask=None):
    """Ring attention over `axis_name`; call inside shard_map.

    q/k/v: (B, L_local, H, D) — this device's sequence shard. Returns the
    attention output for the local Q block, (B, L_local, H, D). The KV ring
    walk is a `fori_loop`, so HLO size stays O(1) in the axis size.

    kv_mask: optional (B, L_local) bool — this device's key-padding shard
    (True = attend). It rides the ring with its K/V block, so padded keys
    are masked at block granularity without materialising a global
    (B, L, L) mask. Rows whose every key is padded produce zeros.

    Eligible shapes route each block's compute through the Pallas flash
    kernels (_ring_flash, FLAGS_ring_flash); the einsum online-softmax
    walk below is the exact fallback for everything else.
    """
    size = (axis_size if axis_size is not None
            else jax.lax.axis_size(axis_name))
    from ..ops.pallas.counters import bump

    if _ring_flash_eligible(q, k, is_causal):
        bias = (jnp.where(kv_mask.astype(jnp.bool_), 0.0,
                          _NEG_INF).astype(jnp.float32)
                if kv_mask is not None else jnp.zeros((), jnp.float32))
        out = _ring_flash(q, k, v, bias, axis_name, size, is_causal,
                          kv_mask is not None)
        bump("ring_attention", "pallas")
        return out
    bump("ring_attention", "xla",
         f"dispatch ineligible (q {tuple(q.shape)}, causal="
         f"{is_causal}; modulus/shape gate in _ring_flash_eligible)")
    idx = jax.lax.axis_index(axis_name)

    orig_dtype = q.dtype
    # MXU einsums run in the INPUT dtype (bf16 under AMP = 2x throughput);
    # softmax statistics and the accumulator stay f32 (flash-standard
    # mixed precision: scores/acc accumulate via preferred_element_type)
    qh = jnp.swapaxes(q, 1, 2)                       # (b, h, lq, d)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    b, h, lq, d = qh.shape
    lk = kh.shape[2]
    scale = 1.0 / math.sqrt(d)
    has_mask = kv_mask is not None
    mh = kv_mask.astype(jnp.bool_) if has_mask else None  # (b, lk)

    perm = [(i, (i + 1) % size) for i in range(size)]
    # causal alignment matches _xla_attention's bottom-right tril(k=kl-ql):
    # the last lq*size query positions align with the end of the kv axis
    causal_offset = (lk - lq) * size

    def block_update(s, m, l, acc, kc, vc, mc):
        # after s rotations this device holds the block that originated on
        # device (idx - s) mod size
        origin = jnp.mod(idx - s, size)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kc,
                            preferred_element_type=jnp.float32) * scale
        valid = None
        if is_causal:
            q_pos = idx * lq + jnp.arange(lq)[:, None] + causal_offset
            k_pos = origin * lk + jnp.arange(lk)[None, :]
            valid = jnp.broadcast_to(q_pos >= k_pos, (1, 1, lq, lk))
        if has_mask:
            kvalid = mc[:, None, None, :]              # (b, 1, 1, lk)
            valid = kvalid if valid is None else (valid & kvalid)
        if valid is not None:
            scores = jnp.where(valid, scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        if valid is not None:
            # fully-masked rows have scores == m_new == _NEG_INF and would
            # otherwise contribute exp(0) = 1
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def guarded_update(s, m, l, acc, kc, vc, mc):
        """block_update behind a lax.cond that skips whole KV blocks:
        causal blocks entirely above the diagonal (the ~2x win at long
        sequence — the classic ring walk computes then discards them)
        and fully-padded blocks. The ppermute always runs; only the
        einsum pair is skipped."""
        needed = None
        if is_causal:
            origin = jnp.mod(idx - s, size)
            # intersects the causal triangle iff the local Q block's last
            # position can see the arriving KV block's first position
            q_last = idx * lq + (lq - 1) + causal_offset
            needed = q_last >= origin * lk
        if has_mask:
            any_valid = jnp.any(mc)
            needed = any_valid if needed is None else (needed & any_valid)
        if needed is None:
            return block_update(s, m, l, acc, kc, vc, mc)
        return jax.lax.cond(
            needed,
            lambda: block_update(s, m, l, acc, kc, vc, mc),
            lambda: (m, l, acc))

    def body(s, carry):
        m, l, acc, kc, vc, mc = carry
        m, l, acc = guarded_update(s, m, l, acc, kc, vc, mc)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if has_mask:
            mc = jax.lax.ppermute(mc, axis_name, perm)
        return m, l, acc, kc, vc, mc

    # derive initial carries from the inputs (0*q) so they carry the same
    # varying-manual-axes type as the loop outputs (shard_map vma check);
    # f32 regardless of input dtype — they are the softmax statistics
    zero_q = (0.0 * qh[..., 0]).astype(jnp.float32)  # (b, h, lq)
    m0 = zero_q + _NEG_INF
    l0 = zero_q
    acc0 = zero_q[..., None] * vh[..., :1, :].astype(jnp.float32)
    # a dummy all-True mask keeps the carry structure static when unmasked
    mc0 = mh if has_mask else jnp.zeros((), jnp.bool_)
    # the last block needs no rotation afterwards: loop size-1 rotations,
    # then fold in the final kv block outside the loop (saves one ICI hop)
    m, l, acc, kc, vc, mc = jax.lax.fori_loop(
        0, size - 1, body, (m0, l0, acc0, kh, vh, mc0))
    m, l, acc = guarded_update(size - 1, m, l, acc, kc, vc, mc)

    # fully-masked rows: l == 0 -> output 0 (not NaN)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Ulysses attention (all-to-all head/sequence reshuffle)
# ---------------------------------------------------------------------------


def ulysses_attention_local(q, k, v, axis_name: str, is_causal: bool = False,
                            axis_size: Optional[int] = None, kv_mask=None):
    """Ulysses sequence parallelism; call inside shard_map.

    q/k/v: (B, L_local, H, D), H divisible by the axis size. all_to_all to
    (B, L_full, H/size, D), local full attention, all_to_all back.
    kv_mask: optional (B, L_full) bool key-padding mask, replicated over
    the axis (after the all-to-all every device sees the full kv axis).

    The post-all-to-all local attention sees the FULL sequence with a
    head subset — exactly the flash kernel's sweet spot at the long
    lengths Ulysses exists for — so the mask-free path dispatches
    through _local_attention (Pallas when eligible; NOT
    flash_attention_or_fallback, which would re-enter the active
    sequence_parallel context and recurse).
    """
    from ..ops.pallas.flash_attention import _local_attention, _xla_attention

    def a2a_fwd(x):   # seq-sharded -> head-sharded
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def a2a_bwd(x):   # head-sharded -> seq-sharded
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qa, ka, va = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    if kv_mask is None:
        out = _local_attention(qa, ka, va, is_causal)
    else:
        out = _xla_attention(qa, ka, va,
                             kv_mask[:, None, None, :].astype(jnp.bool_),
                             0.0, is_causal, None)
    return a2a_bwd(out)


# ---------------------------------------------------------------------------
# user-facing wrappers (shard_map over a mesh)
# ---------------------------------------------------------------------------


def _log_sp_fallback(reason: str):
    """Sequence-parallel fallbacks are a silent perf cliff (the full
    attention runs replicated); surface them (FLAGS_sp_fallback_warn)."""
    from ..framework.flags import get_flag

    try:
        warn = get_flag("sp_fallback_warn")
    except KeyError:
        warn = True
    if warn:
        import warnings

        warnings.warn(
            f"sequence-parallel attention fell back to the local/XLA "
            f"path: {reason}", RuntimeWarning, stacklevel=3)


def ring_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                   seq_axis: str = "sp", batch_axis: str = "dp",
                   head_axis: str = "tp",
                   is_causal: bool = False, impl: str = "ring",
                   kv_mask=None):
    """Context-parallel attention over `seq_axis` of `mesh`.

    q/k/v: (B, L, H, D) global arrays (or sharded under pjit — specs
    compose). impl: "ring" (ppermute KV rotation) or "ulysses"
    (all-to-all head split). kv_mask: optional (B, L) bool key-padding
    mask (True = attend) — sharded over the sequence axis and streamed
    around the ring with its K/V block. Shapes the sharded path cannot
    handle (sequence/batch/heads not divisible by the relevant axis
    sizes) fall back to plain XLA attention, logged via
    FLAGS_sp_fallback_warn.
    """
    from ..ops.pallas.flash_attention import _local_attention, _xla_attention

    def fallback(reason):
        _log_sp_fallback(reason)
        if kv_mask is None:
            return _local_attention(q, k, v, is_causal)
        return _xla_attention(q, k, v,
                              kv_mask[:, None, None, :].astype(jnp.bool_),
                              0.0, is_causal, None)

    mesh = mesh or get_mesh()
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if mesh is None or seq_axis not in mesh.axis_names:
        return fallback(f"no mesh axis {seq_axis!r}")
    size = mesh.shape[seq_axis]
    if size <= 1:
        return fallback(f"axis {seq_axis!r} has size 1")
    if lq % size != 0 or lk % size != 0:
        return fallback(
            f"sequence lengths ({lq}, {lk}) not divisible by "
            f"{seq_axis}={size}")
    ba = batch_axis if (batch_axis in mesh.axis_names
                        and batch_axis != seq_axis
                        and b % mesh.shape[batch_axis] == 0) else None
    # keep the head axis sharded (e.g. over tp) so attention is not
    # redundantly replicated across tensor-parallel devices
    ha = head_axis if (head_axis in mesh.axis_names
                       and head_axis not in (seq_axis, ba)
                       and h % mesh.shape[head_axis] == 0) else None
    h_local = h // (mesh.shape[ha] if ha else 1)
    if impl == "ulysses" and h_local % size != 0:
        impl = "ring"   # ulysses needs local heads divisible by the sp axis
    spec = PartitionSpec(ba, seq_axis, ha, None)
    local = ring_attention_local if impl == "ring" else ulysses_attention_local
    fn = functools.partial(local, axis_name=seq_axis, is_causal=is_causal,
                           axis_size=size)
    if kv_mask is None:
        return _shard_map(fn, mesh, (spec, spec, spec), spec)(q, k, v)
    kv_mask = jnp.asarray(kv_mask)
    # ring: the mask shard travels with its kv block; ulysses: every
    # device needs the full kv axis after the all-to-all -> replicated
    mspec = (PartitionSpec(ba, seq_axis) if impl == "ring"
             else PartitionSpec(ba, None))
    wrapped = lambda q_, k_, v_, m_: fn(q_, k_, v_, kv_mask=m_)  # noqa: E731
    return _shard_map(wrapped, mesh,
                      (spec, spec, spec, mspec), spec)(q, k, v, kv_mask)


ulysses_attention = functools.partial(ring_attention, impl="ulysses")


# ---------------------------------------------------------------------------
# sequence-parallel context: routes nn.functional.scaled_dot_product_attention
# through ring/ulysses attention when active (trace-time — see module note)
# ---------------------------------------------------------------------------

_SP_STATE = {"axis": None, "impl": "ring", "batch_axis": "dp", "mesh": None}


@contextmanager
def sequence_parallel(seq_axis: str = "sp", impl: str = "ring",
                      batch_axis: str = "dp", mesh: Optional[Mesh] = None):
    """Within this context, scaled_dot_product_attention shards the sequence
    axis over `seq_axis` using ring/Ulysses attention (mask-free paths).

    Pass `mesh` to pin the mesh (TrainStep does); otherwise the global
    mesh at trace time is used. Trace-time semantics: affects code being
    traced/compiled inside the context. Already-compiled executables are
    not retraced — for jitted training steps use
    `TrainStep(..., sequence_parallel=...)` instead.
    """
    prev = dict(_SP_STATE)
    _SP_STATE.update(axis=seq_axis, impl=impl, batch_axis=batch_axis,
                     mesh=mesh)
    try:
        yield
    finally:
        _SP_STATE.update(prev)


def active_sequence_parallel():
    """(axis, impl, batch_axis, mesh) if a usable sp context + mesh axis
    exist; the scope's pinned mesh wins over the global one."""
    axis = _SP_STATE["axis"]
    if axis is None:
        return None
    mesh = _SP_STATE["mesh"] or get_mesh()
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return None
    return axis, _SP_STATE["impl"], _SP_STATE["batch_axis"], mesh
