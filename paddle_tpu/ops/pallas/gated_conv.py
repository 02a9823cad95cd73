"""A short convolution gated on both sides, the whole of a gated
short-convolution mixer between its two projections, ONE pass over HBM
forward and ONE backward on the in-projection's flat ``(B, T, 3 D)``
array:

    [B | C | X] = proj          three column groups, in THAT order
    y = C * ShortConv(B * X)    depthwise, causal, zeros before a row's start

It is the third form of ``mamba2_stages``' convolution launches (their
halo, slab and block logic; no bias, no SiLU): the forward reads the three
groups through the projection's own block index (no sliced copy) and
writes ``y`` once in the projection's (autocast) type; the backward
recomputes the convolution in VMEM and writes ``dB``, ``dC``, ``dX`` and
float32 partial sums of ``dtaps``; every intermediate is float32 on the
chip only. A ``jax.custom_vjp`` whose residuals are its own inputs, each
launch in a ``jax.jit`` of its own, forward and backward under ONE role.
The float32 formula (:func:`gated_conv_xla`, jax's own transpose) stays
as the path of the CPU, of a multi-device trace and of any shape the
launches do not take — counted ``gated_conv.xla`` with the reason — and
as the tests' oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mamba2_stages as shared
from .counters import bump

_F32 = jnp.float32
#: the kernels' role in a device trace and in ``counters.step_work``
ROLE = "gated_conv"


def gated_conv_xla(proj, taps):
    """:func:`gated_conv` in float32 arrays, recomputed in the backward
    from the projection."""
    from ...nn.functional import short_conv

    d = taps.shape[1]

    @jax.checkpoint
    def mixed(proj, taps):
        b, c, x = (proj[..., n * d:(n + 1) * d].astype(_F32)
                   for n in range(3))
        return c * short_conv(b * x, taps.astype(_F32))

    return mixed(proj, taps)


def _form(d):
    """B's channels lead; X's (at 2 D) multiply the convolution's input,
    C's (at D) its output."""
    return shared.ConvForm(ROLE, gate=(2 * d, d))


@jax.custom_vjp
def _fused(proj, taps):
    return shared._conv_part_fwd(proj, taps.astype(_F32), None, 0,
                                 _form(taps.shape[1]))


def _fused_fwd(proj, taps):
    return _fused(proj, taps), (proj, taps)


def _fused_bwd(res, dy):
    proj, taps = res
    db, dx, dc, partial = shared._conv_part_bwd(
        proj, taps.astype(_F32), None, 0, dy, _form(taps.shape[1]))
    # written out once, as the projection's backward products read it
    return (jnp.concatenate([db, dc, dx], axis=-1),
            jnp.sum(partial, axis=(0, 2)).astype(taps.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def gated_conv(proj, taps):
    """``C * ShortConv(B * X)`` of ``[B | C | X] = proj`` (B, T, 3 D),
    causal along T from zeros at each row's start: taps (W, D), the last
    tap on the current token. (B, T, D), in ``proj``'s type from the
    kernels, float32 from the XLA formula."""
    b, t, _ = proj.shape
    width, d = taps.shape
    if proj.shape[-1] != 3 * d:
        raise ValueError(f"a projection of {proj.shape[-1]} channels is not "
                         f"three groups of the taps' {d}")
    why = shared._ineligible(d) or shared._too_many_taps(width)
    if why is not None:
        bump("gated_conv", "xla", why)
        return gated_conv_xla(proj, taps)
    moved = float(b * t * d * proj.dtype.itemsize)
    # forward: B, C, X read and y written; backward: those three and dy
    # read, dB, dC, dX written
    bump("gated_conv", "fused", work={ROLE: (0.0, 4.0 * moved)},
         grad_work={ROLE: (0.0, 7.0 * moved)})
    return _fused(proj, taps)
