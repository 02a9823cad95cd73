"""Mamba-2's state-space scan (SSD: a scalar decay a head, B and C shared
by a group of heads), chunked, forward and backward.

Per head h of group g = h // (H / G), with inputs ``u_t`` in R^P, a step
``delta_t > 0``, a rate ``A_h < 0`` and the group's ``B_t``, ``C_t`` in
R^N, the recurrence over a state ``S`` (P x N, zero at the start of a
row) is

    S_t = exp(delta_t A_h) S_{t-1} + delta_t u_t (x) B_t       y_t = S_t C_t

Nothing here walks the tokens. A row is cut into chunks of ``Q`` tokens;
inside a chunk the log decays are accumulated (``l_i``: the inclusive
running sum of ``delta_j A`` from the chunk's start) and

    y_i    = sum_{j<=i} (C_i . B_j) exp(l_i - l_j) delta_j u_j + exp(l_i) S_prev C_i
    S_next = exp(l_Q) S_prev + sum_j exp(l_Q - l_j) delta_j u_j (x) B_j

**Every exponent is <= 0**: a decay is only ever applied forward in
time, so nothing overflows and nothing is capped.

One grid step of the kernels is one chunk of one GROUP: its H / G heads
share ``C B^T`` (formed once), ``u`` and ``y`` are lane-dense
``(Q, H/G * P)`` blocks of the projections' own ``(B, T, H * P)`` layout
(no transposed copy round the kernel), and the state of the group's
heads, ``(H/G * P, N)`` float32, is carried in VMEM from chunk to chunk.
``delta``, the log decays, the states and every accumulator are float32
whatever the operands' type; the products' operands are ``u``'s type
(the autocast type in a training step).

The backward walks the chunks in reverse with the state's cotangent as
its carry; it reads the state each chunk started from (saved by the
differentiated forward: ``T / Q`` states a head) and recomputes the
chunk's ``Q x Q`` blocks. It is hand-derived (:func:`_chunk_bwd`).

:func:`_chunk_fwd` is plain ``jax.numpy`` on 2-D blocks and serves both
paths: under ``lax.scan``, differentiated by jax, it is the XLA
formulation — what the CPU and any ineligible shape run, counted as
``ssd.xla`` with the reason, and the oracle of the kernels' hand-derived
backward — and as the body of the Pallas kernel it is the TPU path. The
kernels launch under ONE role, ``ssd_chunk``, forward and backward
alike (a trace reduction that keeps ten rows then holds the scan as one
row). The token-by-token recurrence lives only in the tests and in the
benchmark's reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .counters import bump, kernel_call
from .flash_attention import _sds

_F32 = jnp.float32
#: tokens in a chunk
CHUNK = 128
#: the kernels' role in a device trace and in ``counters.step_work``
ROLE = "ssd_chunk"


def _mm(a, b, trans_b=False):
    """A product with a float32 result. Operands below float32 say their
    one pass themselves: Mosaic refuses them a multi-pass precision that
    a caller's ``jax_default_matmul_precision`` would else hand down."""
    dims = (((1,), (1,)), ((), ())) if trans_b else (((1,), (0,)), ((), ()))
    precision = None if a.dtype == _F32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _to_col(row, eye):
    """(1, Q) -> (Q, 1) without a transpose."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    """(Q, 1) -> (1, Q)."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _last(row):
    """The last entry of a (1, Q) row, (1, 1)."""
    q = row.shape[1]
    return jnp.sum(jnp.where(_iota(row.shape, 1) == q - 1, row, 0.0),
                   axis=1, keepdims=True)


def _decay(diff, below, eye):
    """e^diff strictly below the diagonal, 1 on it, 0 above. The
    diagonal's exponent is l_i - l_i: written as the constant it is, no
    gradient flows through it into ``l`` (the two would cancel, leaving
    their rounding behind: the largest entries of a block under strong
    decay)."""
    return jnp.where(eye, 1.0, jnp.exp(jnp.where(below, diff, -jnp.inf)))


def _chunk_fwd(u, bm, cm, rows, s):
    """One chunk of one group. u: (Q, HG * P) and bm, cm: (Q, N) in the
    products' type; rows: (2 HG, Q) float32, head j's running log decay
    ``l`` in row j and its ``delta`` in row HG + j; s: the group's states,
    (HG * P, N) float32. Returns (y (Q, HG * P) float32, next states)."""
    q = u.shape[0]
    hg = rows.shape[0] // 2
    p = u.shape[1] // hg
    i, k = _iota((q, q), 0), _iota((q, q), 1)
    eye, lower = i == k, i > k
    cb = _mm(cm, bm, trans_b=True)                       # (i, k)
    ys, states = [], []
    for j in range(hg):
        l_row, d_row = rows[j:j + 1], rows[hg + j:hg + j + 1]
        l_col, d_col = _to_col(l_row, eye), _to_col(d_row, eye)
        uj, sj = u[:, j * p:(j + 1) * p], s[j * p:(j + 1) * p]
        m = cb * _decay(l_col - l_row, lower, eye) * d_row
        y = _mm(m.astype(u.dtype), uj) + jnp.exp(l_col) * _mm(
            cm, sj.astype(u.dtype), trans_b=True)
        l_last = _last(l_row)
        w = jnp.exp(l_last - l_col) * d_col              # (Q, 1)
        ys.append(y)
        states.append(jnp.exp(l_last) * sj + _mm(
            (uj.astype(_F32) * w).T.astype(u.dtype), bm))
    return jnp.concatenate(ys, axis=1), jnp.concatenate(states, axis=0)


def _chunk_bwd(u, bm, cm, rows, s, dy, ds_next):
    """Cotangents of one chunk of one group, all float32: (du, dbm, dcm,
    drows, ds). With ``M_ik = (C_i . B_k) e^(l_i - l_k) delta_k`` (i >= k),
    ``w_k = e^(l_Q - l_k) delta_k``:

        Y  = M U + e^l * (C S^T)            S' = e^(l_Q) S + (U * w)^T B
        dU = M^T dY + w * (B dS'^T)         dM = dY U^T
        dC = (dM * e^(l_i-l_k) delta_k) B + (dY * e^l) S
        dB = (dM * ...)^T C + (U * w) dS'   dS = e^(l_Q) dS' + (dY * e^l)^T C
        dl_i = sum_k G_ik - sum_k G_ki + dY_i . Y_prev_i - dw_i w_i  (G = dM * M)
        ddelta_k = sum_i (dM * M / delta)_ik + dw_k e^(l_Q - l_k)
        dl_Q += sum_k dw_k w_k + e^(l_Q) <dS', S>,    dw_k = U_k . (B dS'^T)_k

    ``M^T`` is built in its own layout from ``B C^T`` (no transposed
    ``Q x Q`` block per head)."""
    q = u.shape[0]
    hg = rows.shape[0] // 2
    p = u.shape[1] // hg
    i, k = _iota((q, q), 0), _iota((q, q), 1)
    eye, lower, upper = i == k, i > k, k > i
    is_last = _iota((1, q), 1) == q - 1
    dt = u.dtype
    cb = _mm(cm, bm, trans_b=True)                       # (i, k)
    bc = _mm(bm, cm, trans_b=True)                       # (k, i)
    dcb = jnp.zeros((q, q), _F32)
    dbm = jnp.zeros(bm.shape, _F32)
    dcm = jnp.zeros(cm.shape, _F32)
    dus, dstates, dl_rows, dd_rows = [], [], [], []
    for j in range(hg):
        l_row, d_row = rows[j:j + 1], rows[hg + j:hg + j + 1]
        l_col, d_col = _to_col(l_row, eye), _to_col(d_row, eye)
        uj, sj = u[:, j * p:(j + 1) * p], s[j * p:(j + 1) * p]
        dyj, dsj = dy[:, j * p:(j + 1) * p], ds_next[j * p:(j + 1) * p]
        uf, s_op, ds_op = uj.astype(_F32), sj.astype(dt), dsj.astype(dt)
        dec = _decay(l_col - l_row, lower, eye)          # (i, k)
        dec_t = _decay(l_row - l_col, upper, eye)        # (k, i)
        l_last = _last(l_row)
        e_last = jnp.exp(l_last)
        out = jnp.exp(l_last - l_col)                    # e^(l_Q - l_k)
        w = out * d_col
        # the inputs: through the chunk's own block and through the state
        t1 = _mm(bm, ds_op, trans_b=True)                # (Q, P)
        dus.append(_mm((bc * dec_t * d_col).astype(dt), dyj) + w * t1)
        # the chunk's own block
        dm = _mm(dyj, uj, trans_b=True)                  # (i, k)
        dmd = dm * dec
        g0 = dmd * cb
        # (the diagonal's e^(l_i - l_i) does not depend on l)
        g = jnp.where(lower, g0 * d_row, 0.0)
        dcb = dcb + dmd * d_row
        # what the state the chunk started from adds to y
        dye = dyj.astype(_F32) * jnp.exp(l_col)
        dcm = dcm + _mm(dye.astype(dt), s_op)
        dstates.append(e_last * dsj + _mm(dye.T.astype(dt), cm))
        # the state that leaves the chunk
        dw = jnp.sum(uf * t1, axis=1, keepdims=True)     # (Q, 1)
        dbm = dbm + _mm((uf * w).astype(dt), ds_op)
        dl_col = jnp.sum(g, axis=1, keepdims=True) - dw * w + jnp.sum(
            dye * _mm(cm, s_op, trans_b=True), axis=1, keepdims=True)
        dl_last = jnp.sum(dw * w, axis=0, keepdims=True) + e_last * jnp.sum(
            jnp.sum(dsj * sj, axis=1, keepdims=True), axis=0, keepdims=True)
        dl_rows.append(_to_row(dl_col, eye)
                       - jnp.sum(g, axis=0, keepdims=True)
                       + jnp.where(is_last, dl_last, 0.0))
        dd_rows.append(_to_row(dw * out, eye)
                       + jnp.sum(g0, axis=0, keepdims=True))
    dcm = dcm + _mm(dcb.astype(dt), bm)
    dbm = dbm + _mm(dcb.T.astype(dt), cm)
    return (jnp.concatenate(dus, axis=1), dbm, dcm,
            jnp.concatenate(dl_rows + dd_rows, axis=0),
            jnp.concatenate(dstates, axis=0))


# ---------------------------------------------------------------------------
# what both forms share: the per-head rows of a chunk
# ---------------------------------------------------------------------------
def _rows(delta, a, groups, chunk):
    """(B, G, 2 HG, T) float32: for each group, its heads' running log
    decay inside each chunk, then their ``delta``. delta: (B, T, H)
    float32, a: (H,) float32 (negative)."""
    b, t, h = delta.shape
    l = jnp.cumsum((delta * a).reshape(b, t // chunk, chunk, h),
                   axis=2).reshape(b, t, h)

    def by_group(x):
        return x.reshape(b, t, groups, h // groups).transpose(0, 2, 3, 1)

    return jnp.concatenate([by_group(l), by_group(delta)], axis=2)


# ---------------------------------------------------------------------------
# the XLA formulation: the chunk formula under lax.scan, batch and groups
# under vmap; jax differentiates it
# ---------------------------------------------------------------------------
def _xla_scan(u, bm, cm, rows, chunk):
    b, t, width = u.shape
    groups = rows.shape[1]
    n = bm.shape[-1] // groups
    nc = t // chunk

    def by_chunk(x):
        """(B, T, G * D) -> (NC, B, G, Q, D)."""
        return x.reshape(b, nc, chunk, groups, -1).transpose(1, 0, 3, 2, 4)

    step = jax.vmap(jax.vmap(_chunk_fwd))

    def body(s, xs):
        y, s_next = step(*xs, s)
        return s_next, y

    xs = (by_chunk(u), by_chunk(bm), by_chunk(cm),
          rows.reshape(b, groups, -1, nc, chunk).transpose(3, 0, 1, 2, 4))
    _, y = jax.lax.scan(
        body, jnp.zeros((b, groups, width // groups, n), _F32), xs)
    return y.transpose(1, 0, 3, 2, 4).reshape(b, t, width).astype(u.dtype)


# ---------------------------------------------------------------------------
# the Pallas kernels: grid (batch, group, chunk), chunks in order, the
# group's states (their cotangent, backward) in a VMEM scratch across them
# ---------------------------------------------------------------------------
def _fwd_kernel(u_ref, b_ref, c_ref, r_ref, y_ref, *rest):
    from jax.experimental import pallas as pl

    carry = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        carry[...] = jnp.zeros_like(carry)

    s = carry[...]
    if len(rest) == 2:          # the differentiated forward saves them
        rest[0][...] = s
    y, s_next = _chunk_fwd(u_ref[...], b_ref[...], c_ref[...], r_ref[...], s)
    y_ref[...] = y.astype(y_ref.dtype)
    carry[...] = s_next


def _bwd_kernel(u_ref, b_ref, c_ref, r_ref, s_ref, dy_ref,
                du_ref, db_ref, dc_ref, dr_ref, carry):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        carry[...] = jnp.zeros_like(carry)

    du, dbm, dcm, drows, ds = _chunk_bwd(
        u_ref[...], b_ref[...], c_ref[...], r_ref[...], s_ref[...],
        dy_ref[...], carry[...])
    du_ref[...] = du.astype(du_ref.dtype)
    db_ref[...] = dbm.astype(db_ref.dtype)
    dc_ref[...] = dcm.astype(dc_ref.dtype)
    dr_ref[...] = drows
    carry[...] = ds


def _specs(width, n, rows, chunk, nc, reverse):
    """Block specs by kind: a group's (Q, HG * P) block of the (B, T,
    H * P) arrays, its (Q, N) block of B and C, its (2 HG, Q) rows and a
    chunk's (HG * P, N) states."""
    from jax.experimental import pallas as pl

    def at(c):
        return nc - 1 - c if reverse else c

    def tokens(d):
        return pl.BlockSpec((None, chunk, d), lambda b, g, c: (b, at(c), g))

    row = pl.BlockSpec((None, None, rows, chunk),
                       lambda b, g, c: (b, g, 0, at(c)))
    state = pl.BlockSpec((None, None, None, width, n),
                         lambda b, g, c: (b, g, at(c), 0, 0))
    return tokens(width), tokens(n), row, state


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _pallas_fwd(u, bm, cm, rows, chunk, save_states):
    from jax.experimental.pallas import tpu as pltpu

    b, t, hp = u.shape
    groups = rows.shape[1]
    width, n, nc = hp // groups, bm.shape[-1] // groups, t // chunk
    wide, narrow, row, state = _specs(width, n, rows.shape[2], chunk, nc,
                                      reverse=False)
    out = kernel_call(
        ROLE, _fwd_kernel, grid=(b, groups, nc),
        in_specs=[wide, narrow, narrow, row],
        out_specs=[wide] + [state] * int(save_states),
        out_shape=[_sds((b, t, hp), u.dtype, u)] + [_sds(
            (b, groups, nc, width, n), _F32, u)] * int(save_states),
        scratch_shapes=[pltpu.VMEM((width, n), _F32)],
        compiler_params=_compiler_params(),
    )(u, bm, cm, rows)
    return (out[0], out[1]) if save_states else (out[0], None)


def _pallas_bwd(u, bm, cm, rows, states, dy, chunk):
    from jax.experimental.pallas import tpu as pltpu

    b, t, hp = u.shape
    groups = rows.shape[1]
    width, n, nc = hp // groups, bm.shape[-1] // groups, t // chunk
    wide, narrow, row, state = _specs(width, n, rows.shape[2], chunk, nc,
                                      reverse=True)
    return kernel_call(
        ROLE, _bwd_kernel, grid=(b, groups, nc),
        in_specs=[wide, narrow, narrow, row, state, wide],
        out_specs=[wide, narrow, narrow, row],
        out_shape=[_sds(u.shape, u.dtype, u), _sds(bm.shape, bm.dtype, u),
                   _sds(cm.shape, cm.dtype, u), _sds(rows.shape, _F32, u)],
        scratch_shapes=[pltpu.VMEM((width, n), _F32)],
        compiler_params=_compiler_params(),
    )(u, bm, cm, rows, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pallas_scan(u, bm, cm, rows, chunk):
    return _pallas_fwd(u, bm, cm, rows, chunk, save_states=False)[0]


def _pallas_scan_fwd(u, bm, cm, rows, chunk):
    y, states = _pallas_fwd(u, bm, cm, rows, chunk, save_states=True)
    return y, (u, bm, cm, rows, states)


def _pallas_scan_bwd(chunk, res, dy):
    return _pallas_bwd(*res, dy, chunk)


_pallas_scan.defvjp(_pallas_scan_fwd, _pallas_scan_bwd)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _ineligible(hp, gn, groups, chunk):
    """Why the kernels do not take these shapes; None when they do. They
    take a single-device TPU trace, lane-dense blocks (a group's heads
    together and its state width in whole 128 lanes) and chunks of whole
    128 lanes (the per-head rows run along them)."""
    from ...framework.bringup import pallas_enabled
    from ...parallel.mesh import auto_partitioned_trace

    if not pallas_enabled():
        return "backend"
    if auto_partitioned_trace():
        return "a multi-device trace (GSPMD cannot partition the kernel)"
    if (hp // groups) % 128 or (gn // groups) % 128 or chunk % 128:
        return (f"a group's heads {hp // groups} wide, its state "
                f"{gn // groups} wide, chunk {chunk}: whole 128 lanes each")
    return None


def ssd_work(b, t, heads, p, groups, n, chunk, itemsize):
    """``work=`` / ``grad_work=`` of one call under the one role: the
    chunked algorithm's own products — ``C B^T`` 2 Q N a token and group,
    and a head 2 Q P for the chunk's own block + 4 N P for the state in
    and out, whole Q x Q blocks counted; twice that backward — and the
    bytes it cannot avoid: u, B, C and delta read and y written once; the
    same again backward."""
    tokens = b * t
    flops = tokens * (groups * 2.0 * chunk * n
                      + heads * (2.0 * chunk * p + 4.0 * n * p))
    moved = tokens * (itemsize * (2 * heads * p + 2 * groups * n)
                      + 4 * heads)
    return {"work": {ROLE: (flops, moved)},
            "grad_work": {ROLE: (2.0 * flops, 2.0 * moved)}}


def ssd_chunk_scan(u, delta, a, bm, cm, groups, chunk=CHUNK):
    """The scan over each row of a batch from a zero state.

    u: (B, T, H * P), the heads side by side as a projection leaves
    them; delta: (B, T, H), positive; a: (H,), negative; bm, cm: (B, T,
    G * N). Head h reads group ``h // (H / G)``. Returns y (B, T, H * P)
    in ``u``'s type. A length that is no multiple of ``chunk`` is padded
    with tokens that neither write nor decay."""
    b, t, hp = u.shape
    heads = delta.shape[-1]
    gn = bm.shape[-1]
    delta, a = delta.astype(_F32), a.astype(_F32)
    bm, cm = bm.astype(u.dtype), cm.astype(u.dtype)
    pad = (-t) % chunk
    if pad:
        u, delta, bm, cm = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (u, delta, bm, cm))
    rows = _rows(delta, a, groups, chunk)
    why = _ineligible(hp, gn, groups, chunk)
    if why is None:
        bump("ssd", "pallas", **ssd_work(
            b, t + pad, heads, hp // heads, groups, gn // groups, chunk,
            u.dtype.itemsize))
        y = _pallas_scan(u, bm, cm, rows, chunk)
    else:
        bump("ssd", "xla", f"dispatch ineligible: {why}")
        y = _xla_scan(u, bm, cm, rows, chunk)
    return y[:, :t] if pad else y


def ssd_scan(u, dt, a_log, bm, cm, dt_bias, groups, chunk=CHUNK):
    """Mamba-2's selective scan from its parameters: ``delta =
    softplus(dt + dt_bias)``, ``A = -exp(A_log)`` and the recurrence of
    the module docstring; the skip ``D_h u`` is the caller's (the mixer
    adds it where it gates and norms). u: (B, T, H * P); dt: (B, T, H);
    a_log, dt_bias: (H,); bm, cm: (B, T, G * N). Returns (B, T, H * P) in
    ``u``'s type."""
    delta = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    return ssd_chunk_scan(u, delta, -jnp.exp(a_log.astype(_F32)), bm, cm,
                          groups, chunk)
