"""Grouped-query attention with rotary positions and an optional sliding
window: the attention layer of the current open decoders.

    q = x W_q -> H x D;   k = x W_k -> Hkv x D;   v = x W_v -> Hkv x D
    q, k = RMSNorm_D(q), RMSNorm_D(k)        per head (``qk_norm``)
    q, k = rope(q, pos), rope(k, pos)        rotate_half convention
    o_h = softmax_j(q_h . k_{h // (H / Hkv)} / sqrt(D) over allowed) v
    allowed(i, j):  j <= i, and with ``window``  i - j < window
    y = [o_h] W_o

No projection has a bias. K and V stay Hkv heads wide from the projection
to the attention kernels (``ops/pallas/flash_attention.py`` reads a
group's keys through its block index maps). ``rope`` is a Hugging Face
``rope_parameters`` entry: ``rope_type`` ``default`` (``rope_theta``) or
``yarn`` (plus ``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``, ``attention_factor``); None leaves q and k
unrotated.
"""
from __future__ import annotations

import math

from . import functional as F
from .common import Linear
from .layer import Layer
from .norm import RMSNorm

__all__ = ["GroupedQueryAttention"]


def rope_tables(head_dim, rope):
    """(inv_freq, scale on cos and sin) of a ``rope_parameters`` entry."""
    kind = rope.get("rope_type", "default")
    theta = rope["rope_theta"]
    if kind == "default":
        return F.rope_inv_freq(head_dim, theta), 1.0
    if kind == "yarn":
        inv_freq, _, _ = F.yarn_inv_freq(
            head_dim, theta, rope["factor"],
            rope["original_max_position_embeddings"],
            rope.get("beta_fast", 32.0), rope.get("beta_slow", 1.0))
        scale = rope.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(rope["factor"]) + 1.0
        return inv_freq, float(scale)
    raise NotImplementedError(
        f"rope_type {kind!r}: nn.functional has rope_inv_freq (default) "
        "and yarn_inv_freq (yarn)")


class GroupedQueryAttention(Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 window=None, rope=None, qk_norm=True, epsilon=1e-6):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads are no multiple of "
                             f"{num_kv_heads} key/value heads")
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.window = None if window is None else int(window)
        self.inv_freq, self.rope_scale = (None, 1.0) if rope is None \
            else rope_tables(self.head_dim, rope)
        self.q_proj = Linear(hidden_size, num_heads * head_dim,
                             bias_attr=False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             bias_attr=False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             bias_attr=False)
        self.q_norm = RMSNorm(head_dim, epsilon=epsilon) if qk_norm else None
        self.k_norm = RMSNorm(head_dim, epsilon=epsilon) if qk_norm else None
        self.o_proj = Linear(num_heads * head_dim, hidden_size,
                             bias_attr=False)

    def forward(self, x):
        from .. import ops

        b, t = x.shape[0], x.shape[1]
        h, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = ops.reshape(self.q_proj(x), [b, t, h, d])
        k = ops.reshape(self.k_proj(x), [b, t, hkv, d])
        v = ops.reshape(self.v_proj(x), [b, t, hkv, d])
        if self.q_norm is not None:
            # the norm's mean of squares in float32, whatever the
            # projections' (autocast) type
            q = self.q_norm(ops.cast(q, "float32"))
            k = self.k_norm(ops.cast(k, "float32"))
        if self.inv_freq is not None:
            q = F.rotary_embedding(q, self.inv_freq, self.rope_scale)
            k = F.rotary_embedding(k, self.inv_freq, self.rope_scale)
        # the attention kernels take one type: the values' (autocast) one
        out = F.scaled_dot_product_attention(
            ops.cast(q, v.dtype), ops.cast(k, v.dtype), v, is_causal=True,
            training=self.training, window=self.window)
        return self.o_proj(ops.reshape(out, [b, t, h * d]))
