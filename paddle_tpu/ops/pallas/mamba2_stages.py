"""The Mamba-2 mixer's two element-wise stages, on either side of its
scan, each ONE pass over HBM forward and ONE backward in the arrays' own
(autocast) type, every intermediate float32 on the chip only.

    before the scan   [u | B | C] = SiLU(ShortConv(xBC) + bias)
    after it          out = RMSNorm_group((y + D u) * SiLU(z)) * weight

Both read their slice of the in-projection ``[z | xBC | dt]`` through
the block index of the projection itself (no sliced copy), and both are
``jax.custom_vjp`` functions whose residuals are their own inputs, which
the step holds anyway; the backward recomputes what it needs in VMEM.

*The convolution.* A grid step is ``rows`` tokens of one batch row by a
tile of whole 128-lane channel groups. The ``W - 1`` earlier tokens come
from a second, 16-row block of the same array (the block before; zeros
at a row's start, so no sequence of the batch leaks into the next), and
the row shifts are sublane rotations of float32 slabs. The backward
recomputes the pre-activation, forms ``d_pre = dy * SiLU'`` for its own
rows and the ``W - 1`` after them (a 16-row block of ``x`` and of ``dy``
from the block after; zero past the row's end), then ``dx[t] = sum_j
taps[j] d_pre[t + W-1-j]``; ``dtaps`` and ``dbias`` accumulate in
float32 over the row blocks, eight sublanes apart, and are reduced once
outside.

*The gated norm.* A block is ``rows`` tokens by ONE norm group (the
heads the scan keeps together), so the group's mean is a lane reduction
of the block. Backward in one pass: ``dy``, the skip's part of ``du``,
``dz``, and float32 ``dD``, ``dweight`` accumulated like ``dtaps``.

Each launch sits in a ``jax.jit`` of its own: a step calls a stage
twelve times (four layers; forward, recomputed, backward) and jax then
traces and lowers its kernel once a shape, not once a call (a whole
second of a warm start otherwise).

The formulas these replace (:func:`conv_silu_xla`,
:func:`gate_norm_xla`: float32 arrays in HBM, jax's own transpose) stay
as the path of the CPU and of any shape the stages do not take — counted
``mamba2_stage.xla`` with the reason — and as the tests' oracle.
"""
from __future__ import annotations

import functools
import operator
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .counters import bump, kernel_call, nbytes
from .flash_attention import _sds

_F32 = jnp.float32
#: the kernels' roles in a device trace and in ``counters.step_work``
ROLE_CONV = "mamba2_conv"
ROLE_NORM = "mamba2_gate_norm"
#: rows of the neighbouring block a convolution step reads: one sublane
#: tile of a 16-bit type, of which W - 1 are used
HALO = 16
#: rows a kernel works on at a time inside its block (whole HALOs)
SLAB = 32
#: elements of a block, forward and backward: rows = elements / lanes
BLOCK = {"fwd": 512 * 512, "bwd": 256 * 512}


def _silu_grad(x):
    """(SiLU(x), dSiLU/dx), float32."""
    s = jax.nn.sigmoid(x)
    return x * s, s * (1.0 + x * (1.0 - s))


class ConvForm(NamedTuple):
    """What a caller of the convolution's launches fixes beside the
    shapes: the kernels' role name, the type the forward writes (the
    input's when None) and, behind SiLU, an L2 norm of each head —
    ``norm = (lanes of a head, scale, epsilon)``: ``y * scale *
    rsqrt(sum_head(y^2) + epsilon)`` — or None. Or, for a convolution
    gated on both sides and with no SiLU, ``gate = (m, g)``: the
    channels at offset ``m`` of the same array multiply the
    convolution's input, those at ``g`` its output (``x_g *
    ShortConv(x * x_m)``); the backward then returns the three
    gradients."""
    role: str
    out_dtype: Optional[Any] = None
    norm: Optional[Tuple[int, float, float]] = None
    gate: Optional[Tuple[int, int]] = None

    @property
    def head(self):
        """Lanes a channel tile must hold whole."""
        return self.norm[0] if self.norm else 128


def per_head(fn, head, *xs):
    """``fn`` on each head's ``head`` lanes of (n, C) arrays (whole lane
    tiles: nothing moves); its result, or each of a tuple of results,
    side by side again."""
    outs = [fn(*(x[:, lo:lo + head] for x in xs))
            for lo in range(0, xs[0].shape[1], head)]

    def join(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    return tuple(map(join, zip(*outs))) if isinstance(outs[0], tuple) \
        else join(outs)


# ---------------------------------------------------------------------------
# the formulas as they were: float32 arrays, differentiated by jax
# ---------------------------------------------------------------------------
def conv_silu_xla(proj, taps, bias, start, widths):
    from ...nn.functional import short_conv

    # each part through its own channels of the convolution: three arrays
    # as the scan reads them; recomputed in the backward from the
    # projection
    @jax.checkpoint
    def mixed(proj, taps, bias):
        def part(lo, hi):
            x = short_conv(
                proj[..., start + lo:start + hi].astype(_F32),
                taps[:, lo:hi].astype(_F32), bias[lo:hi].astype(_F32))
            return jax.nn.silu(x).astype(proj.dtype)

        return tuple(part(lo, hi) for lo, hi in _parts(widths))

    return mixed(proj, taps, bias)


def gate_norm_xla(y, u, proj, d_skip, weight, groups, epsilon):
    b, t, inner = y.shape

    @jax.checkpoint
    def gated_norm(y, u, z, d_skip, weight):
        skip = jnp.repeat(d_skip.astype(_F32), inner // d_skip.shape[0])
        y = y.astype(_F32) + skip * u.astype(_F32)
        y = (y * jax.nn.silu(z.astype(_F32))).reshape(b, t, groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + epsilon)
        return y.reshape(b, t, inner) * weight.astype(_F32)

    return gated_norm(y, u, proj[..., :inner], d_skip, weight)


def _parts(widths):
    lo = 0
    for w in widths:
        yield lo, lo + w
        lo += w


# ---------------------------------------------------------------------------
# what the kernels share
# ---------------------------------------------------------------------------
def _fold(x):
    """(n, C) -> (8, C): the rows summed eight sublanes apart."""
    return sum(x[r:r + 8] for r in range(0, x.shape[0], 8))


def _row_ids(first, n):
    return first + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _slabs(rows, body, start=0):
    """``body(first row)`` for every SLAB of a block from the
    ``start``-th."""
    from jax.experimental import pallas as pl

    def step(k, carry):
        body(pl.multiple_of(k * SLAB, SLAB))
        return carry

    if rows > start * SLAB:     # (a loop of no step is traced all the same)
        jax.lax.fori_loop(start, rows // SLAB, step, None)


def _block_rows(t, lanes, direction):
    """Rows of a block: whole SLABs, no more than the row has."""
    rows = min(BLOCK[direction] // lanes, -(-t // SLAB) * SLAB)
    return max(SLAB, rows // SLAB * SLAB)


def _lanes(*offsets, head=128):
    """The widest channel tile that starts and ends on every offset and
    holds whole heads."""
    return next(c for c in (512, 256, 128)
                if c % head == 0 and all(o % c == 0 for o in offsets))


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# convolution + bias + SiLU
# ---------------------------------------------------------------------------
def _down(x, s):
    """The rows of a float32 slab moved ``s`` down (up for a negative
    ``s``), round its ends."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, s % x.shape[0], 0) if s else x


def _shifted(xe, width, lead):
    """xe: rows [r - lead, r + n) of a block, float32. For tap j the
    (n, C) rows ``x[. - (W-1-j)]``."""
    return [_down(xe, width - 1 - j)[lead:] for j in range(width)]


def _first_slab(prev_ref, x_ref, first):
    """The block's first SLAB behind the HALO before it: zeros where the
    block is the ``first`` of its row."""
    prev = prev_ref[...]
    prev = jnp.where(first, jnp.zeros_like(prev), prev)
    return jnp.concatenate([prev, x_ref[0:SLAB, :]], axis=0)


def _conv_pre(xs, taps, bias):
    pre = sum(taps[j:j + 1] * x for j, x in enumerate(xs))
    return pre if bias is None else bias + pre


def _l2_norm(y, norm):
    """Each head of SiLU's result over its own length."""
    head, scale, epsilon = norm
    return per_head(lambda h: h * (scale * jax.lax.rsqrt(
        jnp.sum(h * h, axis=-1, keepdims=True) + epsilon)), head, y)


def _l2_norm_bwd(y, dn, norm):
    """With r = rsqrt(sum y^2 + eps), u = y r and n = scale u:
    dy = scale r (dn - u sum(dn u)), a head at a time."""
    head, scale, epsilon = norm

    def one(y, dn):
        r = jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + epsilon)
        u = y * r
        return (scale * r) * (dn - u * jnp.sum(dn * u, axis=-1,
                                               keepdims=True))

    return per_head(one, head, y, dn)


def _product(slabs):
    """The convolution's input, float32: a form's own rows, times its
    multiplier's where it has one."""
    return functools.reduce(operator.mul, (s.astype(_F32) for s in slabs))


def _conv_fwd_kernel(prev_ref, x_ref, *refs, norm=None, gated=False):
    """refs: of a gated form the multiplier's HALO and block and the
    output gate's block; the taps; the bias' (1, C) row where there is
    one; then the result."""
    from jax.experimental import pallas as pl

    sources = [(prev_ref, x_ref)]
    if gated:
        sources.append(refs[:2])
        gate_ref, *refs = refs[2:]
    taps_ref, *bias_ref, out_ref = refs
    taps, bias = taps_ref[...], bias_ref[0][...] if bias_ref else None
    width = taps.shape[0]

    def emit(r0, slabs):
        pre = _conv_pre(_shifted(_product(slabs), width, HALO), taps, bias)
        if gated:
            y = pre * gate_ref[pl.ds(r0, SLAB), :].astype(_F32)
        else:
            y = pre * jax.nn.sigmoid(pre)
        if norm is not None:
            y = _l2_norm(y, norm)
        out_ref[pl.ds(r0, SLAB), :] = y.astype(out_ref.dtype)

    first = pl.program_id(2) == 0
    emit(0, [_first_slab(p, x, first) for p, x in sources])
    _slabs(x_ref.shape[0], lambda r0: emit(r0, [
        x[pl.ds(r0 - HALO, HALO + SLAB), :] for _, x in sources]), start=1)


def _conv_bwd_kernel(prev_ref, x_ref, next_ref, dy_ref, dynext_ref, *refs,
                     length, norm=None, gated=False):
    """refs: of a gated form the multiplier's HALO, block and next HALO
    and the output gate's block and next HALO; the taps; the bias' row
    where there is one; then dx, of a gated form the multiplier's and the
    output gate's gradients, the partial sums (dtaps' rows; behind them
    dbias' with a bias) and dp_ref: ``d_pre`` of the block's rows and of
    the HALO after them, float32 scratch."""
    from jax.experimental import pallas as pl

    sources = [(prev_ref, x_ref, next_ref)]
    if gated:
        sources.append(refs[:3])
        gate_ref, gatenext_ref, *refs = refs[3:]
        *refs, dm_ref, dg_ref, dw_ref, dp_ref = refs
        taps_ref, *bias_ref, dx_ref = refs
    else:
        taps_ref, *bias_ref, dx_ref, dw_ref, dp_ref = refs
    rows = x_ref.shape[0]
    taps, bias = taps_ref[...], bias_ref[0][...] if bias_ref else None
    width = taps.shape[0]
    i = pl.program_id(2)
    ragged = length % rows != 0

    @pl.when(i == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def d_pre(r0, slabs, dy, own, gate=None):
        """``d_pre`` of rows [r0, r0 + n) into the scratch; with ``own``
        their part of dtaps and dbias (the rows after the block are the
        next step's own) and, of a gated form, the output gate's
        gradient."""
        n = dy.shape[0]
        xe = _product(slabs)
        if ragged:          # what lies past the row's end is not data
            xe = jnp.where(_row_ids(i * rows + r0 - HALO, HALO + n) < length,
                           xe, 0.0)
        xs = _shifted(xe, width, HALO)
        pre = _conv_pre(xs, taps, bias)
        if gated:
            dy = dy.astype(_F32)
            dp = dy * gate.astype(_F32)
            if own:
                dg_ref[pl.ds(r0, n), :] = (dy * pre).astype(dg_ref.dtype)
        else:
            y, slope = _silu_grad(pre)
            dy = dy.astype(_F32)
            if norm is not None:
                dy = _l2_norm_bwd(y, dy, norm)
            dp = dy * slope
        if ragged or not own:
            dp = jnp.where(_row_ids(i * rows + r0, n) < length, dp, 0.0)
        dp_ref[pl.ds(r0, n), :] = dp
        if own:
            for j, x in enumerate(xs):
                dw_ref[j] += _fold(dp * x)
            if bias is not None:
                dw_ref[width] += _fold(dp)

    d_pre(0, [_first_slab(p, x, i == 0) for p, x, _ in sources],
          dy_ref[0:SLAB, :], True, gate_ref[0:SLAB, :] if gated else None)
    _slabs(rows, lambda r0: d_pre(
        r0, [x[pl.ds(r0 - HALO, HALO + SLAB), :] for _, x, _ in sources],
        dy_ref[pl.ds(r0, SLAB), :], True,
        gate_ref[pl.ds(r0, SLAB), :] if gated else None), start=1)
    d_pre(rows, [jnp.concatenate([x[rows - HALO:rows, :], after[...]], axis=0)
                 for _, x, after in sources],
          dynext_ref[...], False, gatenext_ref[...] if gated else None)

    def emit(r0):
        de = dp_ref[pl.ds(r0, SLAB + HALO), :]
        dx = sum(taps[j:j + 1] * _down(de, j + 1 - width)[:SLAB]
                 for j in range(width))
        if gated:       # the input was x * m: each takes the other's rows
            sl = pl.ds(r0, SLAB)
            mult = sources[1][1]
            dm_ref[sl, :] = (dx * x_ref[sl, :].astype(_F32)).astype(
                dm_ref.dtype)
            dx = dx * mult[sl, :].astype(_F32)
        dx_ref[pl.ds(r0, SLAB), :] = dx.astype(dx_ref.dtype)

    _slabs(rows, emit)


def _conv_specs(t, rows, lanes, first):
    """Block specs of a part that starts ``first`` channel tiles into the
    array: its own (rows, lanes) block, the HALO rows before and after
    it (clamped into the row; the kernels zero what lies outside)."""
    from jax.experimental import pallas as pl

    per, last = rows // HALO, -(-t // HALO) - 1
    own = pl.BlockSpec((None, rows, lanes), lambda b, c, i: (b, i, first + c))
    before = pl.BlockSpec(
        (None, HALO, lanes),
        lambda b, c, i: (b, jnp.maximum(i * per - 1, 0), first + c))
    after = pl.BlockSpec(
        (None, HALO, lanes),
        lambda b, c, i: (b, jnp.minimum((i + 1) * per, last), first + c))
    return own, before, after


def _vector_spec(k, lanes):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((k, lanes), lambda b, c, i: (0, c))


def _partial_spec(k, lanes):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, k, 8, lanes), lambda b, c, i: (b, 0, 0, c))


def _conv_vectors(taps, bias, lanes):
    """(operands, block specs) of the taps and, where there is one, the
    bias' row."""
    vectors = [taps] if bias is None else [taps, bias]
    return vectors, [_vector_spec(v.shape[0], lanes) for v in vectors]


def _gate_specs(form, t, rows, lanes):
    """Block specs of a gated form's two other column groups: (the
    multiplier's own block and its HALOs before and after, the output
    gate's the same way)."""
    return tuple(_conv_specs(t, rows, lanes, o // lanes) for o in form.gate)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _conv_part_fwd(proj, taps, bias, offset, form=ConvForm(ROLE_CONV)):
    """One part of xBC: channels [offset, offset + w) of the projection,
    w = taps.shape[1]; bias (1, w) or None."""
    b, t, _ = proj.shape
    width, w = taps.shape
    lanes = _lanes(offset, w, *(form.gate or ()), head=form.head)
    rows = _block_rows(t, lanes, "fwd")
    own, before, _ = _conv_specs(t, rows, lanes, offset // lanes)
    mine = _conv_specs(t, rows, lanes, 0)[0]
    vectors, vector_specs = _conv_vectors(taps, bias, lanes)
    gates = []
    if form.gate:
        (m_own, m_before, _), (g_own, _, _) = _gate_specs(form, t, rows, lanes)
        gates = [m_before, m_own, g_own]
    return kernel_call(
        form.role, functools.partial(_conv_fwd_kernel, norm=form.norm,
                                     gated=bool(form.gate)),
        grid=(b, w // lanes, -(-t // rows)),
        in_specs=[before, own, *gates, *vector_specs], out_specs=mine,
        out_shape=_sds((b, t, w), form.out_dtype or proj.dtype, proj),
        compiler_params=_compiler_params(),
    )(proj, proj, *[proj] * len(gates), *vectors)


@functools.partial(jax.jit, static_argnums=(3, 5))
def _conv_part_bwd(proj, taps, bias, offset, dy, form=ConvForm(ROLE_CONV)):
    """(dx (B, T, w), partial sums (B, W + 1, 8, w) float32: dtaps' rows,
    then dbias'; (B, W, 8, w) without a bias). Of a gated form (dx, the
    multiplier's gradient, the output gate's, partial sums)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = proj.shape
    width, w = taps.shape
    lanes = _lanes(offset, w, *(form.gate or ()), head=form.head)
    rows = _block_rows(t, lanes, "bwd")
    own, before, after = _conv_specs(t, rows, lanes, offset // lanes)
    mine, _, mine_after = _conv_specs(t, rows, lanes, 0)
    vectors, vector_specs = _conv_vectors(taps, bias, lanes)
    sums = width + len(vectors) - 1
    gates = []
    if form.gate:
        (m_own, m_before, m_after), (g_own, _, g_after) = _gate_specs(
            form, t, rows, lanes)
        gates = [m_before, m_own, m_after, g_own, g_after]
    grads = [_sds((b, t, w), proj.dtype, proj)] * (3 if form.gate else 1)
    return kernel_call(
        form.role, functools.partial(_conv_bwd_kernel, length=t,
                                     norm=form.norm, gated=bool(form.gate)),
        grid=(b, w // lanes, -(-t // rows)),
        in_specs=[before, own, after, mine, mine_after, *gates,
                  *vector_specs],
        out_specs=[*[mine] * len(grads), _partial_spec(sums, lanes)],
        out_shape=[*grads, _sds((b, sums, 8, w), _F32, proj)],
        scratch_shapes=[pltpu.VMEM((rows + HALO, lanes), _F32)],
        compiler_params=_compiler_params(),
    )(proj, proj, proj, dy, dy, *[proj] * len(gates), *vectors)


def _conv_operands(taps, bias, lo, hi):
    return taps[:, lo:hi].astype(_F32), bias[None, lo:hi].astype(_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_fused(proj, taps, bias, start, widths):
    return tuple(
        _conv_part_fwd(proj, *_conv_operands(taps, bias, lo, hi), start + lo)
        for lo, hi in _parts(widths))


def _conv_fused_fwd(proj, taps, bias, start, widths):
    return _conv_fused(proj, taps, bias, start, widths), (proj, taps, bias)


def _conv_fused_bwd(start, widths, res, dys):
    proj, taps, bias = res
    b, t, total = proj.shape
    width = taps.shape[0]
    dxs, partials = zip(*(
        _conv_part_bwd(proj, *_conv_operands(taps, bias, lo, hi), start + lo,
                       dy)
        for (lo, hi), dy in zip(_parts(widths), dys)))
    rest = total - start - sum(widths)
    # written out once (a sum of pads, which XLA folds into the operands
    # of the in-projection's backward products, cost the step 0.5% more)
    dproj = jnp.concatenate(
        [jnp.zeros((b, t, start), proj.dtype), *dxs,
         jnp.zeros((b, t, rest), proj.dtype)], axis=-1)
    sums = jnp.sum(jnp.concatenate(partials, axis=-1), axis=(0, 2))
    return (dproj, sums[:width].astype(taps.dtype),
            sums[width].astype(bias.dtype))


_conv_fused.defvjp(_conv_fused_fwd, _conv_fused_bwd)


# ---------------------------------------------------------------------------
# skip + gate + grouped RMSNorm
# ---------------------------------------------------------------------------
def _norm_fwd_kernel(y_ref, u_ref, z_ref, d_ref, w_ref, out_ref, *, epsilon):
    from jax.experimental import pallas as pl

    d, w = d_ref[...], w_ref[...]

    def emit(r0):
        r = pl.ds(r0, SLAB)
        z = z_ref[r, :].astype(_F32)
        v = (y_ref[r, :].astype(_F32) + d * u_ref[r, :].astype(_F32)) * (
            z * jax.nn.sigmoid(z))
        n = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                              + epsilon)
        out_ref[r, :] = (n * w).astype(out_ref.dtype)

    _slabs(y_ref.shape[0], emit)


def _norm_bwd_kernel(y_ref, u_ref, z_ref, do_ref, d_ref, w_ref,
                     dy_ref, du_ref, dz_ref, dw_ref, *, epsilon, length):
    """With a = y + D u, g = SiLU(z), v = a g, r = rsqrt(mean v^2 + eps),
    n = v r and out = n w:  dn = dout w;  dv = r (dn - n mean(dn n));
    da = dv g;  dz = dv a SiLU'(z);  dy = da;  du = D da;
    dweight = sum dout n;  dD = sum da u (over a head's channels)."""
    from jax.experimental import pallas as pl

    rows = y_ref.shape[0]
    d, w = d_ref[...], w_ref[...]
    i = pl.program_id(2)
    ragged = length % rows != 0

    @pl.when(i == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def emit(r0):
        sl = pl.ds(r0, SLAB)
        u, z = u_ref[sl, :].astype(_F32), z_ref[sl, :].astype(_F32)
        a = y_ref[sl, :].astype(_F32) + d * u
        g, slope = _silu_grad(z)
        v = a * g
        r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + epsilon)
        n = v * r
        do = do_ref[sl, :].astype(_F32)
        dn = do * w
        dv = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        da = dv * g
        dy_ref[sl, :] = da.astype(dy_ref.dtype)
        du_ref[sl, :] = (da * d).astype(du_ref.dtype)
        dz_ref[sl, :] = (dv * a * slope).astype(dz_ref.dtype)
        dweight, dskip = do * n, da * u
        if ragged:          # what lies past the row's end is not data
            inside = _row_ids(i * rows + r0, SLAB) < length
            dweight = jnp.where(inside, dweight, 0.0)
            dskip = jnp.where(inside, dskip, 0.0)
        dw_ref[0] += _fold(dweight)
        dw_ref[1] += _fold(dskip)

    _slabs(rows, emit)


def _norm_specs(rows, lanes):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, rows, lanes), lambda b, c, i: (b, i, c))


def _norm_operands(d_skip, weight, inner):
    skip = jnp.repeat(d_skip.astype(_F32), inner // d_skip.shape[0])
    return skip[None], weight.astype(_F32)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
@functools.partial(jax.jit, static_argnums=(5, 6))
def _norm_fused(y, u, proj, d_skip, weight, groups, epsilon):
    b, t, inner = y.shape
    lanes = inner // groups
    rows = _block_rows(t, lanes, "fwd")
    tokens = _norm_specs(rows, lanes)
    return kernel_call(
        ROLE_NORM, functools.partial(_norm_fwd_kernel, epsilon=epsilon),
        grid=(b, groups, -(-t // rows)),
        in_specs=[tokens, tokens, tokens, _vector_spec(1, lanes),
                  _vector_spec(1, lanes)],
        out_specs=tokens, out_shape=_sds((b, t, inner), proj.dtype, proj),
        compiler_params=_compiler_params(),
    )(y, u, proj, *_norm_operands(d_skip, weight, inner))


def _norm_fused_fwd(y, u, proj, d_skip, weight, groups, epsilon):
    return (_norm_fused(y, u, proj, d_skip, weight, groups, epsilon),
            (y, u, proj, d_skip, weight))


def _norm_fused_bwd(groups, epsilon, res, dout):
    return _norm_bwd(*res, dout, groups, epsilon)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _norm_bwd(y, u, proj, d_skip, weight, dout, groups, epsilon):
    b, t, inner = y.shape
    lanes = inner // groups
    rows = _block_rows(t, lanes, "bwd")
    tokens = _norm_specs(rows, lanes)
    dy, du, dz, partial = kernel_call(
        ROLE_NORM, functools.partial(_norm_bwd_kernel, epsilon=epsilon,
                                     length=t),
        grid=(b, groups, -(-t // rows)),
        in_specs=[tokens, tokens, tokens, tokens, _vector_spec(1, lanes),
                  _vector_spec(1, lanes)],
        out_specs=[tokens, tokens, tokens, _partial_spec(2, lanes)],
        out_shape=[_sds(y.shape, y.dtype, y), _sds(u.shape, u.dtype, y),
                   _sds(y.shape, proj.dtype, y),
                   _sds((b, 2, 8, inner), _F32, y)],
        compiler_params=_compiler_params(),
    )(y, u, proj, dout, *_norm_operands(d_skip, weight, inner))
    sums = jnp.sum(partial, axis=(0, 2))
    dproj = jnp.concatenate(
        [dz, jnp.zeros((b, t, proj.shape[-1] - inner), proj.dtype)], axis=-1)
    heads = d_skip.shape[0]
    return (dy, du, dproj,
            jnp.sum(sums[1].reshape(heads, -1), axis=1).astype(d_skip.dtype),
            sums[0].astype(weight.dtype))


_norm_fused.defvjp(_norm_fused_fwd, _norm_fused_bwd)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _ineligible(*lanes_of):
    """Why the kernels do not take a stage whose channel tiles must start
    and end on ``lanes_of``; None when they do. They take a single-device
    TPU trace and whole 128-lane tiles."""
    from ...framework.bringup import pallas_enabled
    from ...parallel.mesh import auto_partitioned_trace

    if not pallas_enabled():
        return "backend"
    if auto_partitioned_trace():
        return "a multi-device trace (GSPMD cannot partition the kernel)"
    if any(o % 128 for o in lanes_of):
        return f"channel offsets {lanes_of}: whole 128 lanes each"
    return None


def _too_many_taps(width):
    """Why the convolution's launches do not take ``width`` taps (the
    earlier tokens come from half a HALO); None when they do."""
    if width - 1 > HALO // 2:
        return f"{width} taps: at most {HALO // 2 + 1}"
    return None


def conv_silu(proj, taps, bias, start, widths):
    """``SiLU(ShortConv(xBC) + bias)`` of the channels ``xBC = proj[...,
    start:start + sum(widths)]``, causal along T from zeros at each row's
    start, as one array a width: proj (B, T, C); taps (W, sum(widths)),
    the last tap on the current token; bias (sum(widths),). In ``proj``'s
    type."""
    b, t, _ = proj.shape
    width, channels = taps.shape
    why = _ineligible(start, *widths) or _too_many_taps(width)
    if why is not None:
        bump("mamba2_stage", "xla", f"convolution ineligible: {why}")
        return conv_silu_xla(proj, taps, bias, start, widths)
    moved = b * t * channels * proj.dtype.itemsize
    bump("mamba2_stage", "fused", work={ROLE_CONV: (0.0, 2.0 * moved)},
         grad_work={ROLE_CONV: (0.0, 3.0 * moved)})
    return _conv_fused(proj, taps, bias, start, tuple(widths))


def gate_norm(y, u, proj, d_skip, weight, groups, epsilon):
    """``RMSNorm_group((y + D u) * SiLU(z)) * weight`` with ``z =
    proj[..., :inner]``: y, u (B, T, inner), the scan's output and input;
    d_skip (H,); weight (inner,); the norm over each of ``groups`` groups
    of inner / groups channels. In ``proj``'s type from the kernel,
    float32 from the XLA formula."""
    b, t, inner = y.shape
    why = _ineligible(inner // groups)
    if why is not None:
        bump("mamba2_stage", "xla", f"gated norm ineligible: {why}")
        return gate_norm_xla(y, u, proj, d_skip, weight, groups, epsilon)
    moved = nbytes(y, u) + 2 * b * t * inner * proj.dtype.itemsize
    bump("mamba2_stage", "fused", work={ROLE_NORM: (0.0, float(moved))},
         grad_work={ROLE_NORM: (0.0, 1.75 * moved)})
    return _norm_fused(y, u, proj, d_skip, weight, groups, float(epsilon))
