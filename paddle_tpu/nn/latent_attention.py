"""Multi-head latent attention (MLA) in its expanded, training form.

    q = x W_q                              -> heads x (d_nope + d_pe)
    [c, k_pe] = x W_kv_down                -> kv_rank + d_pe
    [k_nope_h, v_h] = RMSNorm(c) W_kv_up   -> heads x (d_nope + d_v)
    k_h = [k_nope_h ; k_pe]                (k_pe shared by every head)
    o_h = softmax_causal(q_h k_h^T / sqrt(d_nope + d_pe)) v_h
    y = [o_h] W_o

Keys and values are low-rank in ``c``; nothing is absorbed into the
projections here (that is a decode-time rewrite). Without ``rope`` the
``d_pe`` part is carried unrotated (NoPE). With ``rope``
(``{"rope_theta": ..., "interleave": bool}``) q's ``d_pe`` part and the
ONE ``k_pe`` row a token are rotated by their position before ``k_pe``
is handed to the heads (:func:`mla_rope`):

    q_pe, k_pe = R_t(P q_pe), R_t(P k_pe)

``R_t`` is ``nn.functional.rotary_embedding`` (rotate_half, frequencies
``theta^(-2i / d_pe)``) and ``P`` the de-interleave ``(x0, x1, x2, x3,
...) -> [x0, x2, ... | x1, x3, ...]`` of a source that stores the pairs
side by side (``interleave``), the identity otherwise. The value width
may differ from the key width; the attention entry
(``ops/pallas/flash_attention.py``) takes that as it is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.op import primitive
from . import functional as F
from .common import Linear
from .layer import Layer
from .norm import RMSNorm

__all__ = ["MLAttention", "mla_rope"]


@primitive("mla_rope")
def mla_rope(q, k_pe, inv_freq, nope, interleave=False):
    """q (B, T, H, nope + pe) with its last ``pe`` channels rotated, and
    k_pe (B, T, 1, pe) rotated: once a token, before the heads share
    it. The tables are float32 and cast to each operand's type
    (``nn.functional.rotary_embedding``)."""
    def rotate(x):
        if interleave:
            x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        return F.rotary_embedding.raw_fn(x, inv_freq)

    with jax.named_scope("mla_rope"):
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        return q, rotate(k_pe)


class MLAttention(Layer):
    def __init__(self, hidden_size, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank, epsilon=1e-5,
                 rope=None):
        super().__init__()
        if rope is not None and rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"MLAttention with rope_type {rope['rope_type']!r}: the "
                "plain frequencies (nn.functional.rope_inv_freq) are built; "
                "a scaled variant also rescales the softmax")
        self.inv_freq = None if rope is None else F.rope_inv_freq(
            qk_rope_head_dim, rope["rope_theta"])
        self.interleave = bool(rope and rope.get("interleave", False))
        self.num_heads = num_heads
        self.nope, self.pe, self.v_dim = (qk_nope_head_dim,
                                          qk_rope_head_dim, v_head_dim)
        self.kv_rank = kv_lora_rank
        self.q_proj = Linear(hidden_size, num_heads * (self.nope + self.pe),
                             bias_attr=False)
        self.kv_down_proj = Linear(hidden_size, kv_lora_rank + self.pe,
                                   bias_attr=False)
        self.kv_norm = RMSNorm(kv_lora_rank, epsilon=epsilon)
        self.kv_up_proj = Linear(kv_lora_rank,
                                 num_heads * (self.nope + v_head_dim),
                                 bias_attr=False)
        self.o_proj = Linear(num_heads * v_head_dim, hidden_size,
                             bias_attr=False)

    def forward(self, x):
        from .. import ops
        from ..ops.pallas.counters import bump

        bump("mla", "nope" if self.inv_freq is None else "rotary")
        b, t = x.shape[0], x.shape[1]
        h = self.num_heads
        q = ops.reshape(self.q_proj(x), [b, t, h, self.nope + self.pe])
        down = self.kv_down_proj(x)
        latent, k_pe = down[:, :, :self.kv_rank], down[:, :, self.kv_rank:]
        up = ops.reshape(self.kv_up_proj(self.kv_norm(latent)),
                         [b, t, h, self.nope + self.v_dim])
        k_nope, v = up[:, :, :, :self.nope], up[:, :, :, self.nope:]
        k_pe = ops.reshape(k_pe, [b, t, 1, self.pe])
        if self.inv_freq is not None:
            q, k_pe = mla_rope(q, k_pe, self.inv_freq, self.nope,
                               self.interleave)
        k_pe = ops.expand(k_pe, [b, t, h, self.pe])
        # the norm hands back float32; the up-projection's product is in
        # the autocast type, and so is k_pe: concat needs one type
        k = ops.concat([k_nope, ops.cast(k_pe, k_nope.dtype)], axis=-1)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(ops.reshape(out, [b, t, h * self.v_dim]))
