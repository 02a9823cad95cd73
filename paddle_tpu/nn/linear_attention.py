"""Two linear-attention token mixers whose per-head state follows the
gated delta rule, through ONE chunk body (``ops/pallas/kda.py``) and ONE
pair of element-wise stages (``ops/pallas/kda_stages.py``): Kimi Delta
Attention, a decay per key channel, and Gated DeltaNet (below it), one
scalar decay a head under fewer key heads than value heads.

Kimi Delta Attention:

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

Gated DeltaNet (``H_k`` key heads of ``d_k`` under ``H_v = r H_k`` value
heads of ``d_v``; value head j reads query/key head j // r):

    [q | k | v | z] = x W_qkvz          [b | a] = x W_ba
    q, k, v = SiLU(ShortConv([q | k | v]))   causal, depthwise, no bias
    q_h = L2norm(q_h) / sqrt(d_k),  k_h = L2norm(k_h)       per key head
    beta_t,j = sigmoid(b_t,j)
    g_t,j = -exp(A_log_j) * softplus(a_t,j + dt_bias_j)     (log decay)
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                       per value head
    y_t = [RMSNorm_head(o_t) * w * SiLU(z_t)] W_o

The same recurrence with ``g`` the same in all ``d_k`` channels of a
head: ``chunk_kda_flat`` takes the decay at ``beta``'s shape and the
key heads' count; the convolution of a concatenation is the convolution
of each part, run on the ``H_k`` key heads and not on ``H_v`` copies;
the stage after the recurrence takes the gate's function (sigmoid there,
SiLU here) as a static argument.
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

__all__ = ["KimiDeltaAttention", "kda_mix", "GatedDeltaNet", "gdn_mix"]

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


@primitive("gdn_mix")
def gdn_mix(qkvz, ba, taps, a_log, dt_bias, norm_weight, num_key_heads,
            num_value_heads, key_dim, value_dim, epsilon=1e-6):
    """Everything of the Gated DeltaNet mixer between its two input
    projections and its output projection. qkvz: (B, T, 2 Hk dk + 2 Hv
    dv), the columns ``[q | k | v | z]``; ba: (B, T, 2 Hv), ``[b | a]``;
    taps: (W, 2 Hk dk + Hv dv) over ``[q | k | v]``; a_log, dt_bias:
    (Hv,); norm_weight: (dv,). Returns (B, T, Hv * dv), float32."""
    from ..ops.pallas import kda_stages as stages
    from ..ops.pallas.kda import chunk_kda_flat

    kw, vw = num_key_heads * key_dim, num_value_heads * value_dim
    q, k, v, z = jnp.split(qkvz, [kw, 2 * kw, 2 * kw + vw], axis=-1)
    b, a = jnp.split(ba, 2, axis=-1)
    with jax.named_scope("gdn_before"):
        q, k, v = stages.conv_norm(
            q, k, v, *jnp.split(taps, [kw, 2 * kw], axis=1), key_dim)
        # one float a token and value head: the decay is the same in all
        # the channels of a head
        g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
            a.astype(_F32) + dt_bias.astype(_F32))
        beta = jax.nn.sigmoid(b.astype(_F32))
    o = chunk_kda_flat(*(probe(name, x, grad=True) for name, x in zip(
        ("gdn_q", "gdn_k", "gdn_v", "gdn_g", "gdn_beta"),
        (q, k, v, g, beta))), key_heads=num_key_heads)
    with jax.named_scope("gdn_after"):
        return stages.norm_gate(probe("gdn_o", o, grad=True), z,
                                norm_weight, epsilon, "silu")


class GatedDeltaNet(Layer):
    """The mixer above as a layer: ONE in-projection for q, k, v and the
    output gate z, one for the write strength and the decay. The columns'
    order inside them is this layer's (``[q | k | v | z]``, ``[b | a]``,
    each part head after head); a checkpoint that groups them by key head
    is a loader's permutation away."""

    def __init__(self, hidden_size, num_key_heads, num_value_heads,
                 key_head_dim, value_head_dim, conv_size=4, epsilon=1e-6):
        super().__init__()
        from .initializer import Assign, Constant, Normal

        if num_value_heads % num_key_heads:
            raise ValueError(f"{num_value_heads} value heads are no "
                             f"multiple of {num_key_heads} key heads")
        self.num_key_heads, self.num_value_heads = (int(num_key_heads),
                                                    int(num_value_heads))
        self.key_head_dim, self.value_head_dim = (int(key_head_dim),
                                                  int(value_head_dim))
        self._epsilon = epsilon
        key_width = self.num_key_heads * self.key_head_dim
        value_width = self.num_value_heads * self.value_head_dim
        self.in_proj_qkvz = Linear(hidden_size,
                                   2 * key_width + 2 * value_width,
                                   bias_attr=False)
        self.in_proj_ba = Linear(hidden_size, 2 * self.num_value_heads,
                                 bias_attr=False)
        self.qkv_conv = self.create_parameter(
            [conv_size, 2 * key_width + value_width],
            default_initializer=Normal(0.0, 1.0 / math.sqrt(conv_size)))
        # the family's start: decay rates A uniform in (0, 16], the time
        # step's bias one
        rng = np.random.default_rng(0)
        self.A_log = self.create_parameter(
            [self.num_value_heads], default_initializer=Assign(np.log(
                16.0 * (1.0 - rng.random(self.num_value_heads))
            ).astype("float32")))
        self.dt_bias = self.create_parameter(
            [self.num_value_heads], default_initializer=Constant(1.0))
        self.o_norm = self.create_parameter(
            [self.value_head_dim], default_initializer=Constant(1.0))
        self.o_proj = Linear(value_width, hidden_size, bias_attr=False)

    def forward(self, x):
        mixed = gdn_mix(
            self.in_proj_qkvz(x), self.in_proj_ba(x), self.qkv_conv,
            self.A_log, self.dt_bias, self.o_norm,
            num_key_heads=self.num_key_heads,
            num_value_heads=self.num_value_heads,
            key_dim=self.key_head_dim, value_dim=self.value_head_dim,
            epsilon=self._epsilon)
        return self.o_proj(mixed)
