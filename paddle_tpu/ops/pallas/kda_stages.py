"""The KDA mixer's two element-wise stages, on either side of its
recurrence, each ONE pass over HBM forward and ONE backward on the
projections' own flat ``(B, T, H * D)`` arrays, every intermediate
float32 on the chip only.

    before the recurrence   q = L2norm_head(SiLU(ShortConv(x W_q))) / sqrt(D)
                            k = L2norm_head(SiLU(ShortConv(x W_k)))
                            v = SiLU(ShortConv(x W_v))
    after it                out = RMSNorm_head(o) * weight * act(gate)
                            (act: sigmoid, or SiLU for a mixer that says so)

*Before.* The convolution is ``mamba2_stages``' (its halo, slab and block
logic; no bias here), told to write float32 — the recurrence's operands
are float32 whatever the projections' type — and, for q and k, to
normalise each head behind SiLU: a head is whole 128-lane tiles of the
block a step already holds, so its length is a lane reduction. The
backward recomputes the pre-activation and the norm in VMEM from the
projection and takes the float32 cotangents the recurrence's backward
returns.

*After.* A block is ``rows`` tokens by a tile of whole heads. Backward in
one pass: ``do`` (float32), ``dgate`` in the gate's type, and float32
``dweight`` accumulated eight sublanes apart like ``dtaps``.

Both are ``jax.custom_vjp`` functions whose residuals are their own
inputs, and each launch sits in a ``jax.jit`` of its own (a step calls a
stage twelve times; jax traces a kernel once a shape). The float32
formulas (:func:`conv_norm_xla`, :func:`norm_gate_xla`) stay as the path
of the CPU and of any shape the stages do not take — counted
``kda_stage.xla`` with the reason — and as the tests' oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import mamba2_stages as shared
from .counters import bump, kernel_call, nbytes
from .flash_attention import _sds

_F32 = jnp.float32
#: the kernels' roles in a device trace and in ``counters.step_work``
ROLE_CONV = "kda_conv"
ROLE_NORM = "kda_gate_norm"
#: added to the squared length under the L2 normalisation of q and k
L2_EPS = 1e-6
#: what the stage after the recurrence may put on its gate: a static
#: argument of the ONE stage
GATES = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}


# ---------------------------------------------------------------------------
# the formulas as they were: float32 arrays, differentiated by jax
# ---------------------------------------------------------------------------
def _heads(x, head):
    return x.reshape(*x.shape[:-1], x.shape[-1] // head, head)


def _scales(head):
    """What follows SiLU for q, k, v: each head's L2 norm times this, or
    nothing (None)."""
    return head ** -0.5, 1.0, None


def conv_norm_xla(q, k, v, q_taps, k_taps, v_taps, head):
    """:func:`conv_norm` in float32 arrays; each operand recomputed in
    the backward from its projection."""
    from ...nn.functional import short_conv

    def mixed(scale):
        @jax.checkpoint
        def one(x, taps):
            y = jax.nn.silu(short_conv(x.astype(_F32), taps.astype(_F32)))
            if scale is None:
                return y
            y = _heads(y, head)
            y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                  + L2_EPS)
            return (y * scale).reshape(x.shape)
        return one

    return tuple(mixed(scale)(x, taps) for x, taps, scale in zip(
        (q, k, v), (q_taps, k_taps, v_taps), _scales(head)))


def norm_gate_xla(o, gate, weight, epsilon, gate_fn="sigmoid"):
    head = weight.shape[0]
    act = GATES[gate_fn]

    @jax.checkpoint
    def gated(o, gate, weight):
        o = _heads(o.astype(_F32), head)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + epsilon)
        return (o * weight.astype(_F32)).reshape(gate.shape) \
            * act(gate.astype(_F32))

    return gated(o, gate, weight)


# ---------------------------------------------------------------------------
# convolution + SiLU (+ L2 norm of each head): mamba2_stages' kernels
# ---------------------------------------------------------------------------
def _form(head, scale):
    return shared.ConvForm(ROLE_CONV, _F32,
                           None if scale is None else (head, scale, L2_EPS))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_fused(x, taps, head, scale):
    return shared._conv_part_fwd(x, taps.astype(_F32), None, 0,
                                 _form(head, scale))


def _conv_fused_fwd(x, taps, head, scale):
    return _conv_fused(x, taps, head, scale), (x, taps)


def _conv_fused_bwd(head, scale, res, dy):
    x, taps = res
    dx, partial = shared._conv_part_bwd(x, taps.astype(_F32), None, 0, dy,
                                        _form(head, scale))
    return dx, jnp.sum(partial, axis=(0, 2)).astype(taps.dtype)


_conv_fused.defvjp(_conv_fused_fwd, _conv_fused_bwd)


# ---------------------------------------------------------------------------
# RMSNorm of each head x weight x act(gate)
# ---------------------------------------------------------------------------
def _rms(o, epsilon):
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + epsilon)
    return o * r, r


def _gate_and_slope(g, gate_fn):
    """(act(g), act'(g)) of a gate's float32 block."""
    s = jax.nn.sigmoid(g)
    if gate_fn == "sigmoid":
        return s, s * (1.0 - s)
    return g * s, s * (1.0 + g * (1.0 - s))


def _norm_fwd_kernel(o_ref, g_ref, w_ref, out_ref, *, head, epsilon,
                     gate_fn):
    from jax.experimental import pallas as pl

    w = w_ref[...]
    act = GATES[gate_fn]

    def emit(r0):
        r = pl.ds(r0, shared.SLAB)
        n = shared.per_head(lambda o: _rms(o, epsilon)[0], head,
                            o_ref[r, :].astype(_F32))
        out_ref[r, :] = (n * w * act(
            g_ref[r, :].astype(_F32))).astype(out_ref.dtype)

    shared._slabs(o_ref.shape[0], emit)


def _norm_bwd_kernel(o_ref, g_ref, dout_ref, w_ref, do_ref, dg_ref, dw_ref,
                     *, head, epsilon, length, gate_fn):
    """With r = rsqrt(mean o^2 + eps), n = o r, a = act(gate) and
    out = n w a:  dn = dout w a;  do = r (dn - n mean(dn n));
    dgate = dout n w act'(gate);  dweight = sum dout n a (over the
    tokens and the heads)."""
    from jax.experimental import pallas as pl

    rows = o_ref.shape[0]
    w = w_ref[...]
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def one(o, dn):
        n, r = _rms(o, epsilon)
        return n, r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))

    def emit(r0):
        sl = pl.ds(r0, shared.SLAB)
        a, slope = _gate_and_slope(g_ref[sl, :].astype(_F32), gate_fn)
        dout = dout_ref[sl, :].astype(_F32)
        n, do = shared.per_head(one, head, o_ref[sl, :].astype(_F32),
                                dout * a * w)
        do_ref[sl, :] = do.astype(do_ref.dtype)
        dout_n = dout * n
        dweight = dout_n * a
        dg_ref[sl, :] = (dout_n * w * slope).astype(dg_ref.dtype)
        if length % rows:       # what lies past the row's end is not data
            dweight = jnp.where(
                shared._row_ids(i * rows + r0, shared.SLAB) < length,
                dweight, 0.0)
        dw_ref[0] += shared._fold(dweight)

    shared._slabs(rows, emit)


def _norm_blocks(o, weight, direction):
    """(lanes of a tile, rows of a block, the grid, the weight as a row
    over the whole width)."""
    b, t, width = o.shape
    head = weight.shape[0]
    lanes = shared._lanes(width, head=head)
    rows = shared._block_rows(t, lanes, direction)
    return (lanes, rows, (b, width // lanes, -(-t // rows)),
            jnp.tile(weight.astype(_F32), width // head)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
@functools.partial(jax.jit, static_argnums=(3, 4))
def _norm_fused(o, gate, weight, epsilon, gate_fn):
    lanes, rows, grid, row = _norm_blocks(o, weight, "fwd")
    tokens = shared._norm_specs(rows, lanes)
    return kernel_call(
        ROLE_NORM, functools.partial(_norm_fwd_kernel, head=weight.shape[0],
                                     epsilon=epsilon, gate_fn=gate_fn),
        grid=grid, in_specs=[tokens, tokens, shared._vector_spec(1, lanes)],
        out_specs=tokens, out_shape=_sds(o.shape, _F32, o),
        compiler_params=shared._compiler_params(),
    )(o, gate, row)


def _norm_fused_fwd(o, gate, weight, epsilon, gate_fn):
    return _norm_fused(o, gate, weight, epsilon, gate_fn), (o, gate, weight)


def _norm_fused_bwd(epsilon, gate_fn, res, dout):
    return _norm_bwd(*res, dout, epsilon, gate_fn)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _norm_bwd(o, gate, weight, dout, epsilon, gate_fn):
    b, _, width = o.shape
    head = weight.shape[0]
    lanes, rows, grid, row = _norm_blocks(o, weight, "bwd")
    tokens = shared._norm_specs(rows, lanes)
    do, dgate, partial = kernel_call(
        ROLE_NORM, functools.partial(_norm_bwd_kernel, head=head,
                                     epsilon=epsilon, length=o.shape[1],
                                     gate_fn=gate_fn),
        grid=grid,
        in_specs=[tokens, tokens, tokens, shared._vector_spec(1, lanes)],
        out_specs=[tokens, tokens, shared._partial_spec(1, lanes)],
        out_shape=[_sds(o.shape, o.dtype, o), _sds(o.shape, gate.dtype, o),
                   _sds((b, 1, 8, width), _F32, o)],
        compiler_params=shared._compiler_params(),
    )(o, gate, dout, row)
    dweight = jnp.sum(partial.reshape(-1, width // head, head), axis=(0, 1))
    return do, dgate, dweight.astype(weight.dtype)


_norm_fused.defvjp(_norm_fused_fwd, _norm_fused_bwd)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _ineligible(head):
    """Why the kernels do not take heads of ``head`` channels; None when
    they do: ``mamba2_stages``' rule (a single-device TPU trace, whole
    128-lane tiles) and a channel tile that holds whole heads."""
    why = shared._ineligible(head)
    if why is None and 512 % head:
        why = f"heads of {head} channels: 128, 256 or 512 lanes"
    return why


def conv_norm(q, k, v, q_taps, k_taps, v_taps, head):
    """The recurrence's three float32 operands from the projections q, k,
    v (B, T, H * head) in their own type and the taps (W, H * head), the
    last tap on the current token, causal along T from zeros at each
    row's start: ``L2norm_head(SiLU(ShortConv(q))) / sqrt(head)``,
    ``L2norm_head(SiLU(ShortConv(k)))``, ``SiLU(ShortConv(v))``."""
    taps = q_taps.shape[0]
    why = _ineligible(head) or shared._too_many_taps(taps)
    if why is not None:
        bump("kda_stage", "xla", f"convolution ineligible: {why}")
        return conv_norm_xla(q, k, v, q_taps, k_taps, v_taps, head)
    read, written = nbytes(q, k, v), 4 * (q.size + k.size + v.size)
    bump("kda_stage", "fused",
         work={ROLE_CONV: (0.0, float(read + written))},
         grad_work={ROLE_CONV: (0.0, float(2 * read + written))})
    return tuple(_conv_fused(x, w, head, scale) for x, w, scale in zip(
        (q, k, v), (q_taps, k_taps, v_taps), _scales(head)))


def norm_gate(o, gate, weight, epsilon, gate_fn="sigmoid"):
    """``RMSNorm_head(o) * weight * act(gate)``, float32: o (B, T,
    H * head) float32, the recurrence's output; gate the same shape in
    the projections' type; weight (head,); ``gate_fn`` names ``act``
    (:data:`GATES`)."""
    if gate_fn not in GATES:
        raise ValueError(f"gate {gate_fn!r}: {sorted(GATES)} are built")
    why = _ineligible(weight.shape[0])
    if why is not None:
        bump("kda_stage", "xla", f"gated norm ineligible: {why}")
        return norm_gate_xla(o, gate, weight, epsilon, gate_fn)
    moved = nbytes(o, gate) + 4 * o.size
    bump("kda_stage", "fused", work={ROLE_NORM: (0.0, float(moved))},
         grad_work={ROLE_NORM: (0.0, float(moved + nbytes(o, gate)))})
    return _norm_fused(o, gate, weight, float(epsilon), gate_fn)
