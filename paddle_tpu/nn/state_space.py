"""Mamba-2: a state-space token mixer whose per-head state follows a
scalar decay (SSD), with B and C shared by a group of heads.

    [z | xBC | dt] = x W_in                     inner + (inner + 2 G N) + H wide
    xBC = SiLU(ShortConv(xBC) + b_conv)         causal, depthwise
    [u | B | C] = xBC                           u: H heads of P; B, C: G groups of N
    delta_t = softplus(dt_t + dt_bias);   A = -exp(A_log)
    S_t = exp(delta_t A) S_{t-1} + delta_t u_t (x) B_t;   y_t = S_t C_t + D u_t
    y = RMSNorm_group(y * SiLU(z)) * weight     the gate FIRST, then the norm
                                                over each of G groups of inner / G
    out = y W_out

The recurrence runs chunk by chunk in ``ops/pallas/ssd.py``, on ``u``,
``B`` and ``C`` as the convolution leaves them, heads side by side. The
two element-wise stages on either side of it (convolution + SiLU; skip +
gate + grouped norm) are ``ops/pallas/mamba2_stages.py``'s: each reads
and writes the projection's (autocast) type once a direction and is
float32 inside the pass; ``delta`` and the decay are float32 whatever
the autocast level, the scan's products take the projection's type.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from ..framework.op import primitive
from .common import Linear
from .layer import Layer

__all__ = ["Mamba2Mixer", "mamba2_mix"]


@primitive("mamba2_mix")
def mamba2_mix(proj, conv_taps, conv_bias, a_log, dt_bias, d_skip,
               norm_weight, num_heads, head_dim, groups, state_size,
               epsilon=1e-5):
    """Everything of the mixer between its input projection and its
    output projection. proj: (B, T, 2 inner + 2 G N + H), the input
    projection's ``[z | xBC | dt]``. Returns (B, T, inner): in ``proj``'s
    type from the fused stages (the rounding the output projection
    applies under autocast, at the same point), float32 from their XLA
    formulas."""
    from ..ops.pallas import mamba2_stages as stages
    from ..ops.pallas import ssd

    inner, gn = num_heads * head_dim, groups * state_size
    with jax.named_scope("short_conv"):
        u, bm, cm = stages.conv_silu(proj, conv_taps, conv_bias, inner,
                                     (inner, gn, gn))
    with jax.named_scope("ssd_scan"):
        y = ssd.ssd_scan(u, proj[..., 2 * inner + 2 * gn:], a_log, bm, cm,
                         dt_bias, groups)
    with jax.named_scope("gated_norm"):
        return stages.gate_norm(y, u, proj, d_skip, norm_weight, groups,
                                epsilon)


class Mamba2Mixer(Layer):
    """The mixer above as a layer: ``num_heads`` heads of ``head_dim``
    (the inner width is their product), ``groups`` groups of B and C of
    ``state_size``, a ``conv_size``-tap convolution with a bias over
    ``[u | B | C]``, no bias on either projection."""

    def __init__(self, hidden_size, num_heads, head_dim, state_size,
                 groups=1, conv_size=4, epsilon=1e-5,
                 time_step=(1e-3, 1e-1), time_step_floor=1e-4):
        super().__init__()
        from .initializer import Assign, Constant, Normal

        if num_heads % groups:
            raise ValueError(f"{num_heads} heads are no multiple of "
                             f"{groups} groups")
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.groups, self.state_size = int(groups), int(state_size)
        self._epsilon = epsilon
        inner = self.num_heads * self.head_dim
        conv_dim = inner + 2 * self.groups * self.state_size
        self.in_proj = Linear(hidden_size, inner + conv_dim + self.num_heads,
                              bias_attr=False)
        self.xbc_conv = self.create_parameter(
            [conv_size, conv_dim],
            default_initializer=Normal(0.0, 1.0 / math.sqrt(conv_size)))
        self.conv_bias = self.create_parameter([conv_dim], is_bias=True)
        # the family's start: decay rates A in [1, 16], time steps
        # log-uniform in ``time_step`` (floored), dt_bias their inverse
        # softplus, a unit skip
        rng = np.random.default_rng(0)
        self.A_log = self.create_parameter(
            [self.num_heads], default_initializer=Assign(np.log(
                rng.uniform(1.0, 16.0, self.num_heads)).astype("float32")))
        step = np.maximum(np.exp(rng.uniform(
            math.log(time_step[0]), math.log(time_step[1]), self.num_heads)),
            time_step_floor)
        self.dt_bias = self.create_parameter(
            [self.num_heads], default_initializer=Assign(
                (step + np.log(-np.expm1(-step))).astype("float32")))
        self.D = self.create_parameter(
            [self.num_heads], default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            [inner], default_initializer=Constant(1.0))
        self.out_proj = Linear(inner, hidden_size, bias_attr=False)

    def forward(self, x):
        mixed = mamba2_mix(
            self.in_proj(x), self.xbc_conv, self.conv_bias, self.A_log,
            self.dt_bias, self.D, self.norm_weight,
            num_heads=self.num_heads, head_dim=self.head_dim,
            groups=self.groups, state_size=self.state_size,
            epsilon=self._epsilon)
        return self.out_proj(mixed)
