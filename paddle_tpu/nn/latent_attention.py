"""Multi-head latent attention (MLA) in its expanded, training form.

    q = x W_q                              -> heads x (d_nope + d_pe)
    [c, k_pe] = x W_kv_down                -> kv_rank + d_pe
    [k_nope_h, v_h] = RMSNorm(c) W_kv_up   -> heads x (d_nope + d_v)
    k_h = [k_nope_h ; k_pe]                (k_pe shared by every head)
    o_h = softmax_causal(q_h k_h^T / sqrt(d_nope + d_pe)) v_h
    y = [o_h] W_o

Keys and values are low-rank in ``c``; nothing is absorbed into the
projections here (that is a decode-time rewrite). ``rotary`` is refused:
only the NoPE variant, whose ``d_pe`` part is carried unrotated, is
built (``nn.functional.rotary_embedding`` exists; it is not wired here). The value width may differ from the key width; the attention
entry (``ops/pallas/flash_attention.py``) takes that as it is.
"""
from __future__ import annotations

from . import functional as F
from .common import Linear
from .layer import Layer
from .norm import RMSNorm

__all__ = ["MLAttention"]


class MLAttention(Layer):
    def __init__(self, hidden_size, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank, epsilon=1e-5,
                 rotary=False):
        super().__init__()
        if rotary:
            raise NotImplementedError(
                "MLAttention builds the NoPE variant only: its "
                "qk_rope_head_dim part is carried unrotated. The rotary op "
                "is nn.functional.rotary_embedding (used by "
                "nn.GroupedQueryAttention); rotating k_pe and q's pe part "
                "with it is not wired here")
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

        b, t = x.shape[0], x.shape[1]
        h = self.num_heads
        q = ops.reshape(self.q_proj(x), [b, t, h, self.nope + self.pe])
        down = self.kv_down_proj(x)
        latent, k_pe = down[:, :, :self.kv_rank], down[:, :, self.kv_rank:]
        up = ops.reshape(self.kv_up_proj(self.kv_norm(latent)),
                         [b, t, h, self.nope + self.v_dim])
        k_nope, v = up[:, :, :, :self.nope], up[:, :, :, self.nope:]
        k_pe = ops.expand(ops.reshape(k_pe, [b, t, 1, self.pe]),
                          [b, t, h, self.pe])
        # the norm hands back float32; the up-projection's product is in
        # the autocast type, and so is k_pe: concat needs one type
        k = ops.concat([k_nope, ops.cast(k_pe, k_nope.dtype)], axis=-1)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(ops.reshape(out, [b, t, h * self.v_dim]))
