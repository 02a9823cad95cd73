"""Quantized wire codecs shared by the PS data plane and the
parallel/collectives quantized all-reduce (which re-exports them).

stdlib + numpy ONLY — ps/ must stay importable without jax (the PR 9
contract: fault/http_kv/ps serve on boxes that never load XLA). The
jnp trace-time encoders in parallel/collectives.py implement the SAME
layout; ``encoded_nbytes`` is the ONE closed form the cost model, the
wire readers on both ends, and the comm_bytes_saved_pct gauge all
share.

Layouts (all little-endian, deterministic):
  f32   raw float32 payload (codec id 0 — the pre-codec wire bytes)
  bf16  round-to-nearest-even upper 16 bits of each float32 (id 1)
  int8  per-block symmetric scales: ``nblocks`` float32 scales
        (max-abs/127 over each QUANT_BLOCK-element block, final block
        zero-padded) followed by the int8 payload (id 2)
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "QUANT_BLOCK", "CODEC_IDS", "CODEC_NAMES", "codec_name",
    "encoded_nbytes", "ring_nbytes", "np_encode", "np_decode",
    "jnp_encode_kv_rows", "jnp_decode_kv_rows",
]

#: elements covered by one f32 scale in the blocked int8 encoding —
#: 512 keeps scale overhead at 4/(512*4) < 0.2% of the f32 payload
QUANT_BLOCK = 512

#: wire/codec ids (the PS v2 header's codec byte; 0 keeps the
#: pre-codec frames' zero-filled byte meaning "plain f32")
CODEC_IDS = {"f32": 0, "bf16": 1, "int8": 2}
CODEC_NAMES = {v: k for k, v in CODEC_IDS.items()}


def codec_name(codec_id: int) -> str:
    name = CODEC_NAMES.get(int(codec_id))
    if name is None:
        raise ValueError(f"unknown wire codec id {codec_id}")
    return name


def _nblocks(n: int, block: int = QUANT_BLOCK) -> int:
    return -(-int(n) // int(block))


def encoded_nbytes(n_elems: int, codec: str,
                   block: int = QUANT_BLOCK) -> int:
    """Wire bytes of ``n_elems`` f32 values under ``codec`` — payload
    plus per-block scales."""
    n = int(n_elems)
    if codec == "int8":
        return n + 4 * _nblocks(n, block)
    if codec == "bf16":
        return 2 * n
    if codec == "f32":
        return 4 * n
    raise ValueError(f"unknown codec {codec!r}")


def ring_nbytes(n_elems: int, group: int, codec: str,
                block: int = QUANT_BLOCK) -> int:
    """Per-device wire bytes of a ring all-reduce of ``n_elems`` over
    ``group`` devices: reduce-scatter + all-gather each move
    ``(g-1)/g`` of the encoded payload."""
    g = max(1, int(group))
    if g <= 1:
        return 0
    return int(2 * (g - 1) * encoded_nbytes(n_elems, codec, block) // g)


def np_encode(values: np.ndarray, codec: str,
              block: int = QUANT_BLOCK) -> bytes:
    """Encode a float32 array for the wire; byte count is exactly
    ``encoded_nbytes(values.size, codec)``."""
    vals = np.ascontiguousarray(values, np.float32).reshape(-1)
    if codec == "f32":
        return vals.tobytes()
    if codec == "bf16":
        # bf16 = f32's upper 16 bits, round-to-nearest-even (portable,
        # no ml_dtypes dependency on the jax-free PS side)
        u = vals.view(np.uint32)
        rounded = (u.astype(np.uint64) + 0x7FFF + ((u >> 16) & 1)) >> 16
        return rounded.astype(np.uint16).tobytes()
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r}")
    n = vals.size
    nb = _nblocks(n, block)
    padded = np.zeros(nb * block, np.float32)
    padded[:n] = vals
    xb = padded.reshape(nb, block)
    amax = np.max(np.abs(xb), axis=1)
    scale = (amax / 127.0).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0)
    q = np.clip(np.rint(xb / safe[:, None]), -127, 127).astype(np.int8)
    return scale.tobytes() + q.reshape(-1)[:n].tobytes()


def jnp_encode_kv_rows(x):
    """Trace-time int8 encode for KV page writes: one symmetric scale
    per TOKEN ROW — the blocked int8 layout with ``block`` = one row's
    ``H * D`` elements, so ``encoded_nbytes(n, "int8", block=H*D)`` is
    the page's exact byte cost. ``x`` is (..., H, D); returns the int8
    payload (same shape) and the f32 scales (...,). jnp.rint matches
    np_encode's half-even rounding bit for bit.

    Lazy jax import: the module itself stays importable on jax-free PS
    boxes (the PR 9 contract)."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = (amax / 127.0).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint(xf / safe[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def jnp_decode_kv_rows(q, scale):
    """Trace-time dequant twin of :func:`jnp_encode_kv_rows`: int8
    payload (..., H, D) × per-row scales (...,) → f32."""
    import jax.numpy as jnp

    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None,
                                                             None]


def np_decode(raw: bytes, n_elems: int, codec: str,
              block: int = QUANT_BLOCK) -> np.ndarray:
    """Decode ``np_encode`` output back to a 1-D float32 array."""
    n = int(n_elems)
    if codec == "f32":
        return np.frombuffer(raw, np.float32, count=n).copy()
    if codec == "bf16":
        u = np.frombuffer(raw, np.uint16, count=n).astype(np.uint32)
        return (u << 16).view(np.float32).copy()
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r}")
    nb = _nblocks(n, block)
    scale = np.frombuffer(raw, np.float32, count=nb)
    q = np.frombuffer(raw, np.int8, count=n, offset=4 * nb)
    padded = np.zeros(nb * block, np.float32)
    padded[:n] = q.astype(np.float32)
    out = (padded.reshape(nb, block) * scale[:, None]).reshape(-1)
    return out[:n].astype(np.float32)
