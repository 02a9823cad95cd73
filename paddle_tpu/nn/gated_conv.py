"""A gated short convolution: a token mixer that looks ``taps - 1``
tokens back through a depthwise convolution, gated by the input on both
sides.

    [B | C | X] = x W_in        three groups of ``hidden`` columns, in THAT order
    c_t = sum_j w_j (B * X)_{t - (taps-1) + j}     causal, zeros before a
                                                   row's start; w is (hidden, taps)
    out = (C * c) W_out

Everything between the two projections is ``ops/pallas/gated_conv.py``'s
ONE pass on the projection's flat ``(B, T, 3 hidden)`` array (a third
form of the convolution launches the Mamba-2 and KDA mixers share): it
reads and writes the projection's (autocast) type and is float32 inside.
"""
from __future__ import annotations

import math

import jax

from ..framework.op import primitive
from .common import Linear
from .layer import Layer

__all__ = ["GatedShortConv", "gated_conv_mix"]


@primitive("gated_conv_mix")
def gated_conv_mix(proj, conv_weight):
    """Everything of the mixer between its projections. proj: (B, T,
    3 hidden), the input projection's ``[B | C | X]``; conv_weight:
    (hidden, taps), the last tap on the current token. (B, T, hidden): in
    ``proj``'s type from the fused stage, float32 from its XLA formula."""
    from ..ops.pallas.gated_conv import gated_conv

    with jax.named_scope("gated_conv"):
        return gated_conv(proj, conv_weight.T)


class GatedShortConv(Layer):
    """The mixer above as a layer: ``taps`` taps a channel, no bias on the
    convolution or on either projection (``bias=True`` is refused)."""

    def __init__(self, hidden_size, taps=3, bias=False):
        super().__init__()
        from .initializer import Normal

        if bias:
            raise NotImplementedError(
                "conv_bias: a bias on the convolution and on both "
                "projections is not built")
        self.in_proj = Linear(hidden_size, 3 * hidden_size, bias_attr=False)
        self.conv_weight = self.create_parameter(
            [hidden_size, taps],
            default_initializer=Normal(0.0, 1.0 / math.sqrt(taps)))
        self.out_proj = Linear(hidden_size, hidden_size, bias_attr=False)

    def forward(self, x):
        return self.out_proj(gated_conv_mix(self.in_proj(x),
                                            self.conv_weight))
