"""Chunked gated delta rule with channel-wise decay (Kimi Delta
Attention), forward and backward.

Per head, with keys ``k_t`` and queries ``q_t`` in R^K, values ``v_t``
in R^V, a log-decay ``g_t <= 0`` per key channel and a write strength
``beta_t`` in (0, 1), the recurrence over a state ``S`` (K x V, zero at
the start of a row) is

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

Nothing here walks the tokens. A row is cut into chunks of ``C`` tokens;
inside a chunk the decays are accumulated in log space (``G``: the
inclusive cumulative sum of ``g``), the rank-one updates of the chunk
are resolved at once by one unit-lower-triangular system

    (I + M B) U = V - (K * e^G) S_0,   M_ri = sum_c k_rc k_ic e^(G_rc - G_ic)  (i < r)

(``B = Diag(beta)``, ``U`` the chunk's corrected values), and one state
is carried from chunk to chunk:

    O = (Q * e^G) S_0 + (P B) U,       P_ri as M_ri with q_r, i <= r
    S_C = Diag(e^(G_C)) S_0 + (K * e^(G_C - G) B)^T U

``e^(G_r - G_i)`` is never split into ``e^(G_r)`` and ``e^(-G_i)``, which
overflows under strong decay: rows are taken ``_SUB`` at a time, each
group against the cumulative decay just before its first row, so every
exponent is either non-positive or spans less than one group.

A decay that is one SCALAR a head (the same in all K channels: Gated
DeltaNet's) is a second static form of the same body (``scalar=True``):
then ``e^(G_r - G_i)`` is one C x C matrix a head and is taken as it
stands, every exponent non-positive, so a decay of any strength is exact
(the groups' reference decay holds only while a group's span stays under
e^80, 5 a token; a scalar decay started at A = 16, dt = softplus(1)
falls by 21 a token). Everything else of a chunk is the same lines.

The backward walks the chunks in reverse with the state's cotangent as
its carry; it reads the state each chunk started from (saved by the
forward, ``C`` times smaller than per-token states) and recomputes the
chunk's triangular system.

The triangular system is solved by 16 x 16 blocks
(:func:`_unit_lower_inverse`): a float32 inverse formed from powers of
the whole 64 x 64 matrix is lost to their rounding once a chunk's keys
are correlated, and the wrong ``U`` then grows the state a factor a
chunk until it overflows (PR 37 found it, PR 38 repaired it).

One set of chunk formulas (:func:`_chunk_fwd`, :func:`_chunk_bwd`, plain
``jax.numpy`` on 2-D blocks) serves both paths: under ``lax.scan`` they
are the XLA form — the CPU path and the kernels' parity oracle — and as
the bodies of the two Pallas kernels, launched under the roles
``kda_chunk_fwd`` and ``kda_chunk_bwd``, they are the TPU path. A grid
step of a kernel is one chunk of ``G`` heads (:func:`_heads_a_step`):
lane-dense ``(C, G * 128)`` blocks of the projections' own ``(B, T,
H * D)`` arrays, and inside the step the chunk formulas on a leading
axis of ``G`` heads (``jax.vmap``, as the XLA form runs them), so that
every link of the dependent chain is ``G`` independent products the
MXUs take back to back. Everything outside the chunk formulas is on that
flat layout too (:func:`chunk_kda_flat`: the cumulative decay, the
residuals, the cotangents), so that no array with the heads on the
sublanes is made round a launch; :func:`chunk_kda` is the same call on
(B, T, H, D) arrays. The token-by-token recurrence lives only in the
tests and in the benchmark's reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...framework import nan_inf
from .counters import bump, in_recomputed, kernel_call
from .flash_attention import _sds

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: tokens in a chunk, and rows in a group that shares a reference decay
CHUNK = 64
_SUB = 16
#: exponents of decays that a mask discards anyway are held under this
_EXP_CAP = 80.0
#: what the forward rule calls the two arrays its Pallas launch wrote, o
#: and the states the chunks started from: ``optimizer.meta.recompute``
#: keeps the values of this name across a recomputed segment, so the
#: segment's second run brings q, k, v, g, beta back from the projections
#: and does not launch the O(T) recurrence again to write the same two
#: arrays (48 KB a token and layer at 32 heads of 128 x 128). An identity
#: outside a checkpoint with a policy: it lowers to nothing
KEPT = "kda_chunk_out_states"
_kept = functools.partial(checkpoint_name, name=KEPT)


def _dot(a, b, trans_b=False):
    """float32 matrix product at full precision: the triangular system
    amplifies what a bfloat16 pass would round away."""
    dims = (((1,), (1,)), ((), ())) if trans_b else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _unit_lower_inverse(n):
    """(I + N)^-1 for a strictly lower triangular ``n``, by ``_SUB`` x
    ``_SUB`` blocks, all on the MXU. The diagonal blocks, all at once,
    by the product form (I - N)(I + N^2)(I + N^4)(I + N^8) of ``n``
    masked to them: exact for a nilpotent block, and its powers stay
    under C(14, 7). Then blocks are merged two and two, D <- D - D L D
    with L the part of ``n`` under the diagonal blocks inside each
    merged one. (The product form on the whole chunk sums powers that
    reach C(62, 31) and cancel to entries under 1: with correlated keys
    their float32 rounding is larger than the inverse.)"""
    c = n.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)

    def same_block(width):
        return jax.lax.div(row, jnp.int32(width)) \
            == jax.lax.div(col, jnp.int32(width))

    merged = same_block(_SUB)
    inside = jnp.where(merged, n, 0.0)
    inv, power, span = (row == col).astype(_F32) - inside, inside, 1
    while 2 * span < _SUB:
        power = _dot(power, power)
        inv = inv + _dot(inv, power)
        span *= 2
    width = _SUB
    while width < c:
        width *= 2
        wider = same_block(width)
        below = jnp.where(wider & ~merged, n, 0.0)
        inv = inv - _dot(_dot(inv, below), inv)
        merged = wider
    return inv


def _groups(c):
    return [(lo, min(lo + _SUB, c)) for lo in range(0, c, _SUB)]


def _intra(q, k, gc):
    """P (q rows against k columns, i <= r) and M (k against k, i < r)
    of one chunk, with what the backward needs again per group of rows:
    (lo, hi, E, F) — E = e^(G_r - ref) on the group's rows, F =
    e^(ref - G_i) on the columns up to the group's last."""
    c = q.shape[0]
    row = _iota((c, 1), 0)
    parts, p_rows, m_rows = [], [], []
    for lo, hi in _groups(c):
        ref = gc[lo - 1:lo] if lo else jnp.zeros_like(gc[:1])
        e = jnp.exp(gc[lo:hi] - ref)
        f = jnp.where(row < hi,
                      jnp.exp(jnp.minimum(ref - gc, _EXP_CAP)), 0.0)
        both = jnp.concatenate([q[lo:hi] * e, k[lo:hi] * e], axis=0)
        a = _dot(both, k * f, trans_b=True)              # (2 sub, C)
        p_rows.append(a[:hi - lo])
        m_rows.append(a[hi - lo:])
        parts.append((lo, hi, e, f, both))
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    p = jnp.where(r >= i, jnp.concatenate(p_rows, axis=0), 0.0)
    m = jnp.where(r > i, jnp.concatenate(m_rows, axis=0), 0.0)
    return p, m, parts


def _intra_scalar(q, k, gc):
    """:func:`_intra` for a decay that is the same in every channel of
    the head (``gc``'s columns are equal): P and M from ONE product and
    the C x C matrix e^(G_r - G_i), no exponent above zero. Returns (p,
    m, (a_p, a_m, d)): the undecayed products and the decays, for the
    backward."""
    c = q.shape[0]
    span = jnp.minimum(gc[:, :1] - gc.T[:1], 0.0)             # (C, C)
    d = jnp.exp(span)
    a = _dot(jnp.concatenate([q, k], axis=0), k, trans_b=True)  # (2C, C)
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    p = jnp.where(r >= i, a[:c] * d, 0.0)
    m = jnp.where(r > i, a[c:] * d, 0.0)
    return p, m, (a[:c], a[c:], d)


def _chunk_fwd(q, k, v, gc, beta, st, scalar=False):
    """One chunk of one head. q, k, gc: (C, K); v: (C, V); beta: (1, C);
    st: the state TRANSPOSED, (V, K). Returns (o (C, V), next state).
    ``scalar``: the decay is one scalar a token (``gc``'s columns are
    equal)."""
    p, m, _ = (_intra_scalar if scalar else _intra)(q, k, gc)
    gam = jnp.exp(gc)
    u = _dot(_unit_lower_inverse(m * beta),
             v - _dot(k * gam, st, trans_b=True))
    o = _dot(q * gam, st, trans_b=True) + _dot(p * beta, u)
    last = gc[-1:]
    st_next = st * jnp.exp(last) + _dot(u.T * beta, k * jnp.exp(last - gc))
    return o, st_next


def _chunk_bwd(q, k, v, gc, beta, st, do, dst_next, scalar=False):
    """Cotangents of one chunk: (dq, dk, dv, dgc, dbeta (1, C), dst).
    With ``scalar`` the decay's cotangent is the sum of ``dgc`` over the
    head's channels (how it is spread over them is no one's to read)."""
    c = q.shape[0]
    p, m, parts = (_intra_scalar if scalar else _intra)(q, k, gc)
    gam = jnp.exp(gc)
    qt, kt = q * gam, k * gam
    inv = _unit_lower_inverse(m * beta)
    u = _dot(inv, v - _dot(kt, st, trans_b=True))
    last = gc[-1:]
    decay_out = jnp.exp(last - gc)
    kbar = k * decay_out
    ubt = u.T * beta                                      # (V, C)

    # the state that leaves the chunk: st * e^last + ubt @ kbar
    dubt = _dot(dst_next, kbar, trans_b=True)             # (V, C)
    dkbar = _dot(ubt.T, dst_next)                         # (C, K)
    dbeta = jnp.sum(dubt * u.T, axis=0, keepdims=True)
    dlast = jnp.sum(dst_next * st, axis=0, keepdims=True) * jnp.exp(last)
    # the output: qt @ st^T + (p * beta) @ u
    du = _dot((p * beta).T, do) + (dubt * beta).T
    dpb = _dot(do, u, trans_b=True)
    dbeta = dbeta + jnp.sum(dpb * p, axis=0, keepdims=True)
    dqt = _dot(do, st)
    # u = inv @ (v - kt @ st^T)
    dr = _dot(inv.T, du)
    dmb = -_dot(dr, u, trans_b=True)
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    dmb = jnp.where(r > i, dmb, 0.0)
    dpb = jnp.where(r >= i, dpb, 0.0)
    dbeta = dbeta + jnp.sum(dmb * m, axis=0, keepdims=True)
    dkt = -_dot(dr, st)
    dst = _dot(do.T, qt) + dst_next * jnp.exp(last) - _dot(dr.T, kt)

    dq = dqt * gam
    dk = dkt * gam + dkbar * decay_out
    dkbar_g = dkbar * kbar
    dgc = dqt * qt + dkt * kt - dkbar_g
    dlast = dlast + jnp.sum(dkbar_g, axis=0, keepdims=True)
    row = _iota((c, 1), 0)
    dgc = dgc + jnp.where(row == c - 1, dlast, 0.0)
    dp, dm = dpb * beta, dmb * beta
    if scalar:
        # P = a_p d and M = a_m d (under their masks, which dp and dm
        # already carry), d = e^(G_r - G_i)
        a_p, a_m, d = parts
        both = jnp.concatenate([q, k], axis=0)
        da = jnp.concatenate([dp * d, dm * d], axis=0)         # (2C, C)
        dboth = _dot(da, k)
        dq = dq + dboth[:c]
        dk = dk + dboth[c:] + _dot(da.T, both)
        # G_r takes its row's sum of d's cotangent, G_i gives its
        # column's: as products with ones, which leave each sum in every
        # channel of the head; a K-th of it each
        dspan = (dp * a_p + dm * a_m) * d
        ones = jnp.full_like(gc, 1.0 / gc.shape[1])
        dgc = dgc + _dot(dspan, ones) - _dot(dspan.T, ones)
        return dq, dk, dr, dgc, dbeta, dst
    # P and M, a group of rows at a time
    dq_rows, dk_rows, dg_rows = [], [], []
    for lo, hi, e, f, both in parts:
        da = jnp.concatenate([dp[lo:hi], dm[lo:hi]], axis=0)   # (2 sub, C)
        dboth = _dot(da, k * f)
        dkf = _dot(da.T, both)                                 # (C, K)
        dq_rows.append(dboth[:hi - lo] * e)
        dk_rows.append(dboth[hi - lo:] * e)
        dge = (dboth[:hi - lo] * q[lo:hi] + dboth[hi - lo:] * k[lo:hi]) * e
        dg_rows.append(dge)
        dgf = dkf * k * f
        dk = dk + dkf * f
        dgc = dgc - dgf
        if lo:
            dref = jnp.sum(dgf, axis=0, keepdims=True) \
                - jnp.sum(dge, axis=0, keepdims=True)
            dgc = dgc + jnp.where(row == lo - 1, dref, 0.0)
    dq = dq + jnp.concatenate(dq_rows, axis=0)
    dk = dk + jnp.concatenate(dk_rows, axis=0)
    dgc = dgc + jnp.concatenate(dg_rows, axis=0)
    return dq, dk, dr, dgc, dbeta, dst


# ---------------------------------------------------------------------------
# the XLA form: the same chunk formulas under lax.scan, heads under vmap
# ---------------------------------------------------------------------------
def _by_chunk(x, heads, chunk):
    """(B, T, H * D) -> (NC, B, H, C, D)."""
    b, t, width = x.shape
    return x.reshape(b, t // chunk, chunk, heads, width // heads).transpose(
        1, 0, 3, 2, 4)


def _from_chunks(x):
    """(NC, B, H, C, D) -> (B, T, H * D)."""
    nc, b, h, c, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(b, nc * c, h * d)


def _beta_rows(beta, chunk):
    """(B, T, H) -> (B, H, NC, 1, C): a (1, C) row per head and chunk."""
    b, t, h = beta.shape
    return beta.reshape(b, t // chunk, chunk, h).transpose(
        0, 3, 1, 2)[:, :, :, None, :]


def _beta_from_rows(rows):
    """(B, H, NC, 1, C) -> (B, T, H)."""
    b, h, nc, _, c = rows.shape
    return rows[:, :, :, 0, :].transpose(0, 2, 3, 1).reshape(b, nc * c, h)


_heads = functools.partial(jax.vmap, in_axes=0)


def _form(fn, scalar):
    """The chunk formula ``fn`` in its scalar-decay form, or as it is."""
    return functools.partial(fn, scalar=True) if scalar else fn


def _xla_fwd(q, k, v, gc, beta, chunk, scalar=False):
    b, _, h = beta.shape
    kd, vd = q.shape[-1] // h, v.shape[-1] // h
    step = _heads(_heads(_form(_chunk_fwd, scalar)))

    def body(st, xs):
        o, st_next = step(*xs, st)
        return st_next, (o, st)

    xs = tuple(_by_chunk(a, h, chunk) for a in (q, k, v, gc)) \
        + (jnp.moveaxis(_beta_rows(beta, chunk), 2, 0),)
    _, (o, states) = jax.lax.scan(body, jnp.zeros((b, h, vd, kd), _F32), xs)
    return _from_chunks(o), states


def _xla_bwd(q, k, v, gc, beta, states, do, chunk, scalar=False):
    b, _, h = beta.shape
    kd, vd = q.shape[-1] // h, v.shape[-1] // h
    step = _heads(_heads(_form(_chunk_bwd, scalar)))

    def body(dst, xs):
        dq, dk, dv, dgc, dbeta, dst = step(*xs, dst)
        return dst, (dq, dk, dv, dgc, dbeta)

    xs = tuple(_by_chunk(a, h, chunk) for a in (q, k, v, gc)) \
        + (jnp.moveaxis(_beta_rows(beta, chunk), 2, 0), states,
           _by_chunk(do, h, chunk))
    _, (dq, dk, dv, dgc, dbeta) = jax.lax.scan(
        body, jnp.zeros((b, h, vd, kd), _F32), xs, reverse=True)
    dbeta = _beta_from_rows(jnp.moveaxis(dbeta, 0, 2))
    return tuple(_from_chunks(a) for a in (dq, dk, dv, dgc)) + (dbeta,)


# ---------------------------------------------------------------------------
# the Pallas kernels: grid (batch, heads / G, chunk), chunks in order, the
# G states (their cotangents, backward) in a VMEM scratch across them
# ---------------------------------------------------------------------------
#: scoped VMEM a launch may use: Mosaic's default on a v5e, which the
#: launches leave unsaid (a limit spelled out is written by XLA under
#: every instruction of the module, ``scoped_memory_configs``)
_VMEM_LIMIT = 16 << 20
#: (C, 128) float32 arrays a head's backward chain keeps alive beside its
#: blocks (Mosaic counted 19.4 MB for eight heads at C = 64, PR 38)
_LIVE = 50


def _heads_a_step(h, kd, vd, chunk):
    """G, the heads one grid step takes: the largest of 8, 4, 2, 1 that
    divides ``h`` and fits the VMEM limit with its blocks double-buffered
    and its temporaries. Counted on the backward, the larger launch (q,
    k, v, gc, do and a state in, five cotangents out, beta's rows padded
    to a tile), so that both launches of a call take the same G."""
    tokens = 4 * chunk * (6 * kd + 3 * vd) + 2 * 4 * 8 * max(chunk, 128)
    blocks = 2 * (tokens + 4 * vd * kd) + 4 * vd * kd
    a_head = blocks + _LIVE * 4 * chunk * max(kd, vd)
    return next((g for g in (8, 4, 2)
                 if h % g == 0 and g * a_head <= _VMEM_LIMIT), 1)


def _heads_of(ref, heads):
    """A (C, G * D) token block as (G, C, D): each head's 128-lane
    slice, stacked on a leading axis (whole tiles: nothing moves)."""
    d = ref.shape[-1] // heads
    return jnp.stack([ref[:, n * d:(n + 1) * d] for n in range(heads)])


def _to_heads(ref, x):
    """(G, C, D) back into a (C, G * D) token block."""
    d = x.shape[-1]
    for n in range(x.shape[0]):
        ref[:, n * d:(n + 1) * d] = x[n]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, carry,
                *, scalar=False):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        carry[...] = jnp.zeros_like(carry)

    heads = carry.shape[0]
    st = carry[...]
    st_ref[...] = st
    # the chunk formulas on a leading axis of G heads, as the XLA form
    # runs them: every product is G independent ones, back to back
    o, st_next = _heads(_form(_chunk_fwd, scalar))(
        _heads_of(q_ref, heads), _heads_of(k_ref, heads),
        _heads_of(v_ref, heads), _heads_of(g_ref, heads), b_ref[...], st)
    _to_heads(o_ref, o)
    carry[...] = st_next


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, carry, *,
                scalar=False):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        carry[...] = jnp.zeros_like(carry)

    heads = carry.shape[0]
    dq, dk, dv, dgc, dbeta, dst = _heads(_form(_chunk_bwd, scalar))(
        _heads_of(q_ref, heads), _heads_of(k_ref, heads),
        _heads_of(v_ref, heads), _heads_of(g_ref, heads), b_ref[...],
        st_ref[...], _heads_of(do_ref, heads), carry[...])
    for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dgc)):
        _to_heads(ref, x)
    db_ref[...] = dbeta
    carry[...] = dst


def _specs(heads, kd, vd, chunk, nc, reverse):
    """Block specs by kind: a (C, G * D) block of the (B, T, H * D)
    arrays at head group hg (no transposed copy of q, k, v in HBM), G
    (1, C) rows of beta and a chunk's G (V, K) states."""
    from jax.experimental import pallas as pl

    def at(c):
        return nc - 1 - c if reverse else c

    def tokens(d):
        return pl.BlockSpec((None, chunk, heads * d),
                            lambda b, hg, c: (b, at(c), hg))

    row = pl.BlockSpec((None, heads, None, 1, chunk),
                       lambda b, hg, c: (b, hg, at(c), 0, 0))
    state = pl.BlockSpec((None, heads, None, vd, kd),
                         lambda b, hg, c: (b, hg, at(c), 0, 0))
    return tokens(kd), tokens(vd), row, state


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# each launch in a jit of its own: a step calls them twelve times (four
# layers; forward, recomputed, backward) and jax traces and lowers a
# kernel once a shape, not once a call. The jit holds the launch ALONE,
# on the arrays as the kernel takes them: with the reshapes around it
# inside too, XLA formed other fusions round the kernels in a recomputed
# block and the Kimi step's other operations cost 10 ms more (PR 38)
@functools.partial(jax.jit, static_argnames=("scalar",))
def _launch_fwd(q, k, v, gc, rows, scalar=False):
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = q.shape
    _, h, nc, _, chunk = rows.shape
    kd, vd = q.shape[-1] // h, v.shape[-1] // h
    heads = _heads_a_step(h, kd, vd, chunk)
    keys, vals, row, state = _specs(heads, kd, vd, chunk, nc, reverse=False)
    return kernel_call(
        "kda_chunk_fwd", _form(_fwd_kernel, scalar),
        grid=(b, h // heads, nc),
        in_specs=[keys, keys, vals, keys, row],
        out_specs=[vals, state],
        out_shape=[_sds((b, t, h * vd), _F32, q),
                   _sds((b, h, nc, vd, kd), _F32, q)],
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), _F32)],
        compiler_params=_compiler_params(),
    )(q, k, v, gc, rows)


@functools.partial(jax.jit, static_argnames=("scalar",))
def _launch_bwd(q, k, v, gc, rows, states, do, scalar=False):
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = q.shape
    _, h, nc, vd, kd = states.shape
    chunk = rows.shape[-1]
    heads = _heads_a_step(h, kd, vd, chunk)
    keys, vals, row, state = _specs(heads, kd, vd, chunk, nc, reverse=True)
    return kernel_call(
        "kda_chunk_bwd", _form(_bwd_kernel, scalar),
        grid=(b, h // heads, nc),
        in_specs=[keys, keys, vals, keys, row, state, vals],
        out_specs=[keys, keys, vals, keys, row],
        out_shape=[_sds((b, t, h * kd), _F32, q),
                   _sds((b, t, h * kd), _F32, q),
                   _sds((b, t, h * vd), _F32, q),
                   _sds((b, t, h * kd), _F32, q),
                   _sds((b, h, nc, 1, chunk), _F32, q)],
        scratch_shapes=[pltpu.VMEM((heads, vd, kd), _F32)],
        compiler_params=_compiler_params(),
    )(q, k, v, gc, rows, states, do)


def _pallas_fwd(q, k, v, gc, beta, chunk, scalar=False):
    return _launch_fwd(q, k, v, gc, _beta_rows(beta, chunk), scalar=scalar)


def _pallas_bwd(q, k, v, gc, beta, states, do, chunk, scalar=False):
    *tokens, dbeta = _launch_bwd(q, k, v, gc, _beta_rows(beta, chunk),
                                 states, do, scalar=scalar)
    return (*tokens, _beta_from_rows(dbeta))


# ---------------------------------------------------------------------------
# dispatch + custom_vjp
# ---------------------------------------------------------------------------
def _kernel_takes(kd, vd, chunk):
    """The kernels take lane-dense heads (128-wide keys and values) on a
    single-device TPU trace; everything else runs the XLA form."""
    from ...framework.bringup import pallas_enabled
    from ...parallel.mesh import auto_partitioned_trace

    return (pallas_enabled() and not auto_partitioned_trace()
            and kd % 128 == 0 and vd % 128 == 0
            and chunk % _SUB == 0 and chunk % 8 == 0)


def _cumulate(g, chunk, reverse=False):
    """Inclusive cumulative sum inside each chunk of a (B, T, H * D)
    array, the tokens on the sublanes; ``reverse``: from the chunk's end
    (the transpose). As a product with a triangle of ones on the MXU
    (exact in its bfloat16 passes: every term is the float32 sum's)."""
    b, t, width = g.shape
    row, col = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    ones = (row <= col if reverse else row >= col).astype(_F32)
    return jnp.einsum("ij,bnjw->bniw", ones,
                      g.reshape(b, t // chunk, chunk, width),
                      precision=_HIGHEST,
                      preferred_element_type=_F32).reshape(g.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunk_kda(q, k, v, g, beta, chunk, kernel, record=False, scalar=False):
    return _chunk_kda_fwd(q, k, v, g, beta, chunk, kernel, record,
                          scalar)[0]


def _chunk_kda_fwd(q, k, v, g, beta, chunk, kernel, record, scalar):
    """``record`` (a step built under FLAGS_check_nan_inf): the result
    is (o, the ``nan_inf.row`` of the states the chunks started from),
    which no probe outside this rule can reach. What the kernel wrote is
    named :data:`KEPT` (before the record reads it); the XLA form names
    nothing, and ``gc`` is a segment's to compute again."""
    gc = _cumulate(g, chunk)
    o, states = _kept(_pallas_fwd(q, k, v, gc, beta, chunk, scalar)) \
        if kernel else _xla_fwd(q, k, v, gc, beta, chunk, scalar)
    return ((o, nan_inf.row(states)) if record else o,
            (q, k, v, gc, beta, states))


def _chunk_kda_bwd(chunk, kernel, record, scalar, res, do):
    if record:
        do, _ = do
    q, k, v, gc, beta, states = res
    dq, dk, dv, dgc, dbeta = (_pallas_bwd if kernel else _xla_bwd)(
        q, k, v, gc, beta, states, do, chunk, scalar)
    return dq, dk, dv, _cumulate(dgc, chunk, reverse=True), dbeta


_chunk_kda.defvjp(_chunk_kda_fwd, _chunk_kda_bwd)


def kda_work(b, t, h, kd, vd, key_heads=None, scalar_decay=False):
    """``work=`` / ``grad_work=`` of one call: the recurrence's own
    operations (decay, read, rank-one write and query of a K x V state:
    6 K V a token and head; twice that backward) and the bytes it cannot
    avoid — q, k, v, g (float32) and beta read and o written once; the
    same again plus o's cotangent read and five cotangents written. q and
    k count once a KEY head (``key_heads``, where fewer than ``h``) and a
    scalar decay as one float a token and head: the recurrence's work,
    whatever the launch is handed."""
    tokens = b * t
    kh = h if key_heads is None else key_heads
    moved = 4 * tokens * (2 * kh * kd + h * (1 if scalar_decay else kd)
                          + h * (2 * vd + 1))
    flops = 6.0 * tokens * h * kd * vd
    return {
        "work": {"kda_chunk_fwd": (flops, moved)},
        "grad_work": {"kda_chunk_bwd": (2 * flops, 2 * moved)}}


def _to_value_heads(x, key_heads, heads):
    """(B, T, key_heads * K) -> (B, T, heads * K): value head j reads
    key head j // (heads / key_heads)."""
    b, t, width = x.shape
    kd = width // key_heads
    return jnp.broadcast_to(
        x.reshape(b, t, key_heads, 1, kd),
        (b, t, key_heads, heads // key_heads, kd)).reshape(b, t, heads * kd)


def chunk_kda_flat(q, k, v, g, beta, chunk=CHUNK, key_heads=None):
    """Gated delta rule over each row of a batch from a zero state, on
    the projections' own layout: q, k, g (B, T, H * K); v (B, T, H * V);
    beta (B, T, H), which says how many heads the channels are; ``g`` is
    the per-token, per-channel LOG decay (<= 0). Returns o (B, T, H * V),
    float32. A length that is no multiple of ``chunk`` is padded with
    tokens that neither write nor decay.

    Two static forms of the one call. A decay of ``beta``'s shape (B, T,
    H) is one SCALAR a head (the same in all K channels of it; counted
    ``gdn.scalar_decay``; the chunk formulas' ``scalar`` form, exact at
    any strength), and ``key_heads`` says that q and k hold that many
    heads, each read by ``H / key_heads`` value heads. Both are made the
    launch's own shapes out here, as broadcasts whose transposes sum the
    cotangents (``dg`` over a head's channels, ``dq`` and ``dk`` over a
    key head's readers); the work declared is the recurrence's, with q,
    k and g at their own sizes."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    b, t, h = beta.shape
    kh = h if key_heads is None else int(key_heads)
    if h % kh:
        raise ValueError(f"{h} value heads are no multiple of {kh} key heads")
    kd, vd = q.shape[-1] // kh, v.shape[-1] // h
    scalar_decay = g.shape == beta.shape and kd != 1
    if scalar_decay:
        bump("gdn", "scalar_decay")
        g = jnp.repeat(g, kd, axis=-1)
    if kh != h:
        q, k = (_to_value_heads(a, kh, h) for a in (q, k))
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
    kernel = _kernel_takes(kd, vd, chunk)
    if kernel:
        bump("kda_chunk", "pallas", **kda_work(
            b, t + pad, h, kd, vd, kh, scalar_decay))
        bump("kda_chunk", f"heads{_heads_a_step(h, kd, vd, chunk)}")
        if in_recomputed():
            bump("kda_chunk", "kept_across_recompute")
    else:
        bump("kda_chunk", "xla",
             f"dispatch ineligible ({h} heads of {kd} x {vd}, chunk {chunk}"
             "; backend or 128-lane heads)")
    o = _chunk_kda(q, k, v, g, beta, chunk, kernel,
                   nan_inf.record is not None, scalar_decay)
    if nan_inf.record is not None:
        o, states_row = o
        nan_inf.probe_row("kda_states", states_row)
    return o[:, :t] if pad else o


def chunk_kda(q, k, v, g, beta, chunk=CHUNK):
    """:func:`chunk_kda_flat` with the heads on an axis of their own: q,
    k, g (B, T, H, K); v (B, T, H, V); returns o (B, T, H, V)."""
    b, t, h, _ = q.shape
    o = chunk_kda_flat(*(a.reshape(b, t, -1) for a in (q, k, v, g)), beta,
                       chunk)
    return o.reshape(b, t, h, -1)
