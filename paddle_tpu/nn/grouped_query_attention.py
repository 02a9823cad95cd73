"""Grouped-query attention with rotary positions and an optional sliding
window: the attention layer of the current open decoders.

    q = x W_q -> H x D;   k = x W_k -> Hkv x D;   v = x W_v -> Hkv x D
    q, k = RMSNorm_D(q), RMSNorm_D(k)        per head (``qk_norm``)
    q, k = rope(q, pos), rope(k, pos)        rotate_half convention
    o_h = softmax_j(q_h . k_{h // (H / Hkv)} / sqrt(D) over allowed) v
    allowed(i, j):  j <= i, and with ``window``  i - j < window
    y = [o_h] W_o

With ``output_gate`` the query projection is twice as wide, a head's q
then its gate, and ``y = [o_h * sigmoid(gate_h)] W_o`` (the gate in the
values' type; HLO scope ``gated_attn``, counter ``gqa.output_gate`` once
a build). With ``rotary_dim`` R < D only the first R entries of each head
are rotated (rotate_half within those R, the inverse frequencies those of
a head of R) and the other D - R pass through (``gqa.partial_rotary``).
With ``zero_centered_norm`` the per-head norms' scale is ``1 + w``
(``nn.RMSNorm``).

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
                 window=None, rope=None, qk_norm=True, epsilon=1e-6,
                 output_gate=False, rotary_dim=None,
                 zero_centered_norm=False):
        super().__init__()
        from ..ops.pallas.counters import bump

        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads are no multiple of "
                             f"{num_kv_heads} key/value heads")
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.window = None if window is None else int(window)
        self.rotary_dim = self.head_dim if rotary_dim is None \
            else int(rotary_dim)
        if not 0 < self.rotary_dim <= self.head_dim or self.rotary_dim % 2:
            raise ValueError(f"rotary_dim {rotary_dim} of a head of "
                             f"{head_dim}")
        self.inv_freq, self.rope_scale = (None, 1.0) if rope is None \
            else rope_tables(self.rotary_dim, rope)
        self.output_gate = bool(output_gate)
        if self.output_gate:
            bump("gqa", "output_gate")
        if rope is not None and self.rotary_dim < self.head_dim:
            bump("gqa", "partial_rotary")
        self.q_proj = Linear(
            hidden_size, (2 if self.output_gate else 1) * num_heads
            * head_dim, bias_attr=False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             bias_attr=False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             bias_attr=False)
        self.q_norm, self.k_norm = (RMSNorm(
            head_dim, epsilon=epsilon, zero_centered=zero_centered_norm)
            for _ in range(2)) if qk_norm else (None, None)
        self.o_proj = Linear(num_heads * head_dim, hidden_size,
                             bias_attr=False)

    def forward(self, x):
        from .. import ops

        b, t = x.shape[0], x.shape[1]
        h, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q_proj(x)
        if self.output_gate:
            q = ops.reshape(q, [b, t, h, 2 * d])
            q, gate = q[..., :d], ops.reshape(q[..., d:], [b, t, h * d])
        else:
            q = ops.reshape(q, [b, t, h, d])
        k = ops.reshape(self.k_proj(x), [b, t, hkv, d])
        v = ops.reshape(self.v_proj(x), [b, t, hkv, d])
        if self.q_norm is not None:
            # the norm's mean of squares in float32, whatever the
            # projections' (autocast) type
            q = self.q_norm(ops.cast(q, "float32"))
            k = self.k_norm(ops.cast(k, "float32"))
        if self.inv_freq is not None:
            q, k = self._rotate(q), self._rotate(k)
        # the attention kernels take one type: the values' (autocast) one
        out = F.scaled_dot_product_attention(
            ops.cast(q, v.dtype), ops.cast(k, v.dtype), v, is_causal=True,
            training=self.training, window=self.window)
        out = ops.reshape(out, [b, t, h * d])
        if self.output_gate:
            import jax

            with jax.named_scope("gated_attn"):
                out = out * F.sigmoid(ops.cast(gate, out.dtype))
        return self.o_proj(out)

    def _rotate(self, x):
        """The first ``rotary_dim`` entries of each head by their
        positions, the rest as they are."""
        from .. import ops

        r = self.rotary_dim
        if r == self.head_dim:
            return F.rotary_embedding(x, self.inv_freq, self.rope_scale)
        return ops.concat([
            F.rotary_embedding(x[..., :r], self.inv_freq, self.rope_scale),
            x[..., r:]], axis=-1)
