"""Kimi Delta Attention: a linear-attention token mixer whose per-head
state follows the gated delta rule with a decay per key channel.

    q_t = L2norm(SiLU(ShortConv(x W_q)))_t / sqrt(d_k)
    k_t = L2norm(SiLU(ShortConv(x W_k)))_t       v_t = SiLU(ShortConv(x W_v))_t
    g_t = -exp(A_log_h) * softplus((x W_f1 W_f2)_t + dt_bias)     (log decay)
    beta_t = sigmoid(x W_b)_t
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y_t = [RMSNorm_head(o_t) * sigmoid((x W_g1 W_g2 + b_g)_t)] W_o

``ShortConv`` is a causal depthwise convolution along the sequence. The
recurrence runs chunk by chunk in ``ops/pallas/kda.py``; everything
between the projections and that call is float32 whatever the autocast
level (the norms, the decay and the state are precision-sensitive), and
everything is laid out as the projections are, (B, T, H * D). On the TPU
the element-wise work on either side of the recurrence is two fused
stages (``ops/pallas/kda_stages.py``: convolution + SiLU + L2 norm
before it, head norm x gate after it) that read the projections once
and keep their float32 intermediates in VMEM; HBM holds the projections,
the recurrence's float32 operands (q, k, v, g, beta) and its output.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.nan_inf import probe
from ..framework.op import primitive
from .common import Linear
from .layer import Layer

__all__ = ["KimiDeltaAttention", "kda_mix"]

_F32 = jnp.float32


@primitive("kda_mix")
def kda_mix(q, k, v, q_taps, k_taps, v_taps, decay, a_log, dt_bias,
            beta_logits, gate, norm_weight, num_heads, epsilon=1e-5):
    """Everything of the mixer between its input projections and its
    output projection. q, k, v, decay, gate: (B, T, H * D) projections of
    the block's input; beta_logits: (B, T, H). Returns (B, T, H * D),
    float32."""
    from ..ops.pallas import kda_stages as stages
    from ..ops.pallas.kda import chunk_kda_flat

    d = q.shape[-1] // num_heads
    # every array between the projections and ``o_proj`` is (B, T, H * D),
    # as the projections are and as the three launches take their blocks:
    # none has the heads on an axis of its own (on the chip that is
    # another tiling, and a relayout each way). What lives in HBM: the
    # projections in their (autocast) type, which are also all that the
    # two stages keep for their backward; the recurrence's float32
    # operands q, k, v, g, beta and its output. The convolution's
    # pre-activation, SiLU, the norms and the gate are float32 in VMEM
    # only, forward and (recomputed) backward.
    with jax.named_scope("kda_before"):
        q, k, v = stages.conv_norm(q, k, v, q_taps, k_taps, v_taps, d)
        g = -jnp.repeat(jnp.exp(a_log.astype(_F32)), d) * jax.nn.softplus(
            decay.astype(_F32) + dt_bias.astype(_F32))
        beta = jax.nn.sigmoid(beta_logits.astype(_F32))
    # the recurrence's operands and result, and as the cotangents of
    # these the five gradients that leave its hand-written backward
    # (``probe`` is the identity except in a step built under
    # FLAGS_check_nan_inf)
    o = chunk_kda_flat(*(probe(name, a, grad=True) for name, a in zip(
        ("kda_q", "kda_k", "kda_v", "kda_g", "kda_beta"), (q, k, v, g, beta))))
    with jax.named_scope("kda_after"):
        return stages.norm_gate(probe("kda_o", o, grad=True), gate,
                                norm_weight, epsilon)


class KimiDeltaAttention(Layer):
    """The mixer above as a layer. The decay's and the gate's low-rank
    projections are ``head_dim`` wide inside (the family's convention)."""

    def __init__(self, hidden_size, num_heads, head_dim, conv_size=4,
                 epsilon=1e-5):
        super().__init__()
        from .initializer import Assign, Constant, Normal

        self.num_heads = num_heads
        self.head_dim = head_dim
        self._epsilon = epsilon
        width, low_rank = num_heads * head_dim, head_dim

        def proj(i, o, bias=False):
            return Linear(i, o, bias_attr=None if bias else False)

        self.q_proj = proj(hidden_size, width)
        self.k_proj = proj(hidden_size, width)
        self.v_proj = proj(hidden_size, width)
        taps = Normal(0.0, 1.0 / math.sqrt(conv_size))
        self.q_conv = self.create_parameter([conv_size, width],
                                            default_initializer=taps)
        self.k_conv = self.create_parameter([conv_size, width],
                                            default_initializer=taps)
        self.v_conv = self.create_parameter([conv_size, width],
                                            default_initializer=taps)
        self.f_a_proj = proj(hidden_size, low_rank)
        self.f_b_proj = proj(low_rank, width)
        # the family's start: decay rates A in [1, 16], time steps dt in
        # [1e-3, 1e-1] (log-uniform), dt_bias their inverse softplus
        rng = np.random.default_rng(0)
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=Assign(
                np.log(rng.uniform(1.0, 16.0, num_heads)).astype("float32")))
        dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), width))
        self.dt_bias = self.create_parameter(
            [width], default_initializer=Assign(
                (dt + np.log(-np.expm1(-dt))).astype("float32")))
        self.b_proj = proj(hidden_size, num_heads)
        self.g_a_proj = proj(hidden_size, low_rank)
        self.g_b_proj = proj(low_rank, width, bias=True)
        self.o_norm = self.create_parameter(
            [head_dim], default_initializer=Constant(1.0))
        self.o_proj = proj(width, hidden_size)

    def forward(self, x):
        mixed = kda_mix(
            self.q_proj(x), self.k_proj(x), self.v_proj(x),
            self.q_conv, self.k_conv, self.v_conv,
            self.f_b_proj(self.f_a_proj(x)), self.A_log, self.dt_bias,
            self.b_proj(x), self.g_b_proj(self.g_a_proj(x)), self.o_norm,
            num_heads=self.num_heads, epsilon=self._epsilon)
        return self.o_proj(mixed)
