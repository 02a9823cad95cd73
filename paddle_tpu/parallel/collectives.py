"""Quantized collectives: dynamic-blocked-quantized ring all-reduce.

EQuARX ("Efficient Quantized AllReduce in XLA", PAPERS.md) inside this
repo's shard_map idiom (ring.py precedent): the DP gradient all-reduce
— the interconnect wall at scale-out — runs as an explicit ring
reduce-scatter + all-gather over `lax.ppermute`, with every hop's
payload encoded (per-block scaled int8, or bf16) and every reduce step
ACCUMULATING IN f32 (the PR 5 accumulator discipline, so the accuracy
gates stay provable). int8 wire bytes are ~1/4 of f32 plus one f32
scale per ``QUANT_BLOCK`` elements — ``encoded_nbytes`` is the closed
form the cost model, the PS wire plane, and the comm gauges all share.

Determinism: encode is pure jnp arithmetic (round-half-to-even via
``jnp.rint``, max-abs block scales), decode is exact multiply — the
round trip is bitwise deterministic, and the all-gather phase forwards
the QUANTIZED payload unchanged, so every device decodes the identical
bytes and ends with bitwise-identical reduced values (what lets the
executor run the optimizer region replicated inside shard_map).

Overlap split: ``allreduce_start`` runs the reduce-scatter phase and
returns a carry; ``allreduce_done`` runs the all-gather and returns the
reduced tensor. The executor issues start(bucket k+1) before
done(bucket k), so the traced program interleaves the buckets' ring
hops — XLA's latency-hiding scheduler is free to run bucket k's
all-gather while bucket k+1's reduce-scatter (and the surrounding
compute) is in flight, instead of one barrier-shaped reduce at the end.

The numpy codecs at the bottom are the PS data plane's wire encodings
(ps/service.py push/pull payloads + the primary→backup replication
stream) — same layout, same closed form, host-side.

Escape: ``PADDLE_QUANT_ALLREDUCE=0`` pins every consumer back to the
XLA f32 path (resolve_comm in static/passes.py returns None; the PS
client drops to codec f32) — the established kernel-pattern escape leg,
bitwise equal to the pre-quantization baseline.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

# the wire codecs + closed forms live in ps/codec.py (stdlib+numpy
# only: the PS plane must import them without loading jax) — this
# module re-exports them as the one collective-side surface
from ..ps.codec import (  # noqa: F401
    CODEC_IDS, CODEC_NAMES, QUANT_BLOCK, codec_name, encoded_nbytes,
    np_decode, np_encode, ring_nbytes,
)

__all__ = [
    "CODEC_IDS", "CODEC_NAMES", "QUANT_BLOCK",
    "encoded_nbytes", "ring_nbytes",
    "reduce_scatter_nbytes", "all_gather_nbytes",
    "quant_encode", "quant_decode",
    "ring_allreduce_local", "allreduce_start", "allreduce_done",
    "reduce_scatter", "all_gather",
    "quantized_allreduce", "bucketed_allreduce", "padded_len",
    "np_encode", "np_decode",
    "quant_allreduce_escaped", "shard_map_nocheck",
]


def reduce_scatter_nbytes(n_elems: int, group: int, codec: str,
                          block: int = QUANT_BLOCK) -> int:
    """Per-device wire bytes of the reduce-scatter half of the ring:
    ``(g-1)/g`` of the encoded payload (one encoded chunk per hop,
    g-1 hops) — half of :func:`ring_nbytes`."""
    g = max(1, int(group))
    if g <= 1:
        return 0
    return ring_nbytes(n_elems, group, codec, block) // 2


def all_gather_nbytes(n_elems: int, group: int, codec: str,
                      block: int = QUANT_BLOCK) -> int:
    """Per-device wire bytes of the all-gather half of the ring — the
    same ``(g-1)/g`` of the encoded payload as the reduce-scatter half
    (the carried chunk circulates g-1 hops); the two halves sum to
    :func:`ring_nbytes` exactly (this side carries the floor
    remainder)."""
    g = max(1, int(group))
    if g <= 1:
        return 0
    full = ring_nbytes(n_elems, group, codec, block)
    return full - full // 2


def quant_allreduce_escaped() -> bool:
    """True when ``PADDLE_QUANT_ALLREDUCE=0`` pins the escape leg."""
    return os.environ.get("PADDLE_QUANT_ALLREDUCE", "").strip() in (
        "0", "off", "false")


# ---------------------------------------------------------------------------
# jnp codecs (trace-time; used inside shard_map / jit)
# ---------------------------------------------------------------------------


def quant_encode(x, codec: str, block: int = QUANT_BLOCK):
    """Encode a flat f32 vector (length divisible by ``block`` for
    int8 — the collective pads). Returns ``(payload, scales)`` with
    ``scales=None`` for bf16/f32. Deterministic: max-abs block scales,
    ``jnp.rint`` (round-half-to-even), symmetric clamp at ±127."""
    import jax.numpy as jnp

    if codec == "f32":
        return x.astype(jnp.float32), None
    if codec == "bf16":
        return x.astype(jnp.bfloat16), None
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r} "
                         f"(expected f32|bf16|int8)")
    xb = x.astype(jnp.float32).reshape(-1, block)
    amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = amax / 127.0
    # zero blocks: scale 0 would divide 0/0 — encode exact zeros
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint(xb / safe), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale.reshape(-1)


def quant_decode(payload, scales, codec: str, block: int = QUANT_BLOCK):
    """Exact inverse transport decode back to f32 (multiply only — the
    lossy step is encode's rounding)."""
    import jax.numpy as jnp

    if codec in ("f32", "bf16"):
        return payload.astype(jnp.float32)
    qb = payload.reshape(-1, block).astype(jnp.float32)
    return (qb * scales.reshape(-1, 1)).reshape(-1)


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """shard_map with vma checking OFF: the quantized ring produces
    outputs that are bitwise-replicated by construction (identical
    decodes of identical forwarded payloads) but not PROVABLY replicated
    to jax's vma type system."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# the quantized ring all-reduce (inside shard_map)
# ---------------------------------------------------------------------------


def _pad_to(x, n: int):
    import jax.numpy as jnp

    flat = x.reshape(-1).astype(jnp.float32)
    if flat.shape[0] == n:
        return flat
    return jnp.concatenate(
        [flat, jnp.zeros((n - flat.shape[0],), jnp.float32)])


def padded_len(n_elems: int, group: int, block: int = QUANT_BLOCK) -> int:
    """Flat length the collective pads a bucket to: divisible by
    ``group * block`` so every ring chunk is whole scale blocks."""
    unit = max(1, int(group)) * int(block)
    return -(-int(n_elems) // unit) * unit


def allreduce_start(x, axis_name: str, *, codec: str = "int8",
                    axis_size: Optional[int] = None,
                    block: int = QUANT_BLOCK):
    """Phase 1 (reduce-scatter) of the quantized ring all-reduce; call
    inside shard_map. ``x`` is this device's local contribution (any
    shape). Returns an opaque carry for :func:`allreduce_done`.

    Ring walk: at step s every device sends the f32 partial sum of
    chunk ``(idx - s) % g`` it has accumulated so far, ENCODED
    (quantize per hop), to its +1 neighbour, decodes what arrives, and
    adds its own contribution in f32 — EQuARX's quantize-per-hop /
    accumulate-wide scheme. After g-1 hops device idx holds the fully
    reduced chunk ``(idx + 1) % g``.
    """
    import jax
    import jax.numpy as jnp

    g = axis_size if axis_size is not None else jax.lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    n = int(np.prod(shape)) if shape else 1
    total = padded_len(n, g, block)
    flat = _pad_to(x, total).reshape(g, total // g)
    if g == 1:
        return ("done1", flat[0], shape, dtype, codec, block, axis_name, g)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % g) for i in range(g)]

    def hop(s, acc):
        j = jnp.mod(idx - s, g)
        part = acc + jnp.take(flat, j, axis=0)
        q, sc = quant_encode(part, codec, block)
        q = jax.lax.ppermute(q, axis_name, perm)
        if sc is not None:
            sc = jax.lax.ppermute(sc, axis_name, perm)
        return quant_decode(q, sc, codec, block)

    acc = jax.lax.fori_loop(0, g - 1, hop,
                            jnp.zeros((total // g,), jnp.float32))
    mine = acc + jnp.take(flat, jnp.mod(idx + 1, g), axis=0)
    return ("rs", mine, shape, dtype, codec, block, axis_name, g)


def allreduce_done(carry, avg: bool = False):
    """Phase 2 (all-gather) completing :func:`allreduce_start`: the
    reduced chunk is encoded ONCE and circulated g-1 hops; every device
    decodes the identical payload (own chunk included — it goes through
    the same encode/decode), so the output is bitwise-replicated.
    ``avg=True`` divides by g after decode (mean-gradient semantics)."""
    import jax
    import jax.numpy as jnp

    tag, mine, shape, dtype, codec, block, axis_name, g = carry
    if tag == "done1":
        out = mine
    else:
        idx = jax.lax.axis_index(axis_name)
        perm = [(i, (i + 1) % g) for i in range(g)]
        q, sc = quant_encode(mine, codec, block)
        own = quant_decode(q, sc, codec, block)
        chunk = own.shape[0]
        out0 = jnp.zeros((g, chunk), jnp.float32)
        out0 = out0.at[jnp.mod(idx + 1, g)].set(own)

        def hop(s, carry2):
            out, q, sc = carry2
            q = jax.lax.ppermute(q, axis_name, perm)
            if sc is not None:
                sc = jax.lax.ppermute(sc, axis_name, perm)
            # after s+1 rotations the payload originated at idx-s-1,
            # whose reduced chunk position is (idx - s) % g
            out = out.at[jnp.mod(idx - s, g)].set(
                quant_decode(q, sc, codec, block))
            return out, q, sc

        if sc is None:
            sc = jnp.zeros((), jnp.float32)  # static carry structure

            def hop_nosc(s, carry2):
                out, q, _ = carry2
                q = jax.lax.ppermute(q, axis_name, perm)
                out = out.at[jnp.mod(idx - s, g)].set(
                    quant_decode(q, None, codec, block))
                return out, q, sc

            out, _, _ = jax.lax.fori_loop(0, g - 1, hop_nosc,
                                          (out0, q, sc))
        else:
            out, _, _ = jax.lax.fori_loop(0, g - 1, hop, (out0, q, sc))
        out = out.reshape(-1)
    if avg:
        out = out / g
    n = int(np.prod(shape)) if shape else 1
    return out[:n].reshape(shape).astype(dtype)


def reduce_scatter(x, axis_name: str, *, codec: str = "int8",
                   axis_size: Optional[int] = None, avg: bool = False,
                   block: int = QUANT_BLOCK):
    """Public reduce-scatter half of the quantized ring; call inside
    shard_map. ``x`` is this device's local contribution (any shape);
    the result is the flat f32 REDUCED chunk this device owns —
    length ``padded_len(x.size, g, block) // g``, f32-accumulated at
    every hop with the wire payloads encoded per ``codec`` (the
    ``np_encode`` block layout).

    Chunk ownership follows the ring convention: device ``idx`` ends
    holding chunk ``(idx + 1) % g`` of the padded flat buffer —
    :func:`all_gather` undoes exactly that placement, so
    ``all_gather(reduce_scatter(x))`` (avg off, same codec) is
    BITWISE ``quantized_allreduce`` of the same contributions. This is
    the ZeRO decomposition: the optimizer consumes the unquantized f32
    chunk, only the wire moves encoded bytes. ``avg=True`` divides the
    reduced chunk by g (mean-gradient semantics, BEFORE any further
    encode)."""
    carry = allreduce_start(x, axis_name, codec=codec,
                            axis_size=axis_size, block=block)
    mine, g = carry[1], carry[7]
    if avg:
        mine = mine / g
    return mine


def all_gather(chunk, axis_name: str, *, codec: str = "f32",
               axis_size: Optional[int] = None,
               block: int = QUANT_BLOCK):
    """Public all-gather half of the ring; call inside shard_map.
    ``chunk`` is this device's flat owned chunk under the ring
    placement (device ``idx`` owns chunk ``(idx + 1) % g`` — what
    :func:`reduce_scatter` returns); the result is the full flat
    ``(g * chunk.size,)`` f32 buffer in ORIGINAL chunk order, bitwise
    identical on every device (the payload is encoded once and every
    device decodes the same bytes). The default ``codec='f32'`` moves
    raw bytes — the ZeRO parameter all-gather leg (sharded-update
    results must come back exact); pass the grad codec to reproduce
    ``quantized_allreduce``'s gather phase."""
    import jax.numpy as jnp

    g = axis_size if axis_size is not None else jax.lax.axis_size(axis_name)
    flat = chunk.reshape(-1).astype(jnp.float32)
    n = flat.shape[0] * g
    tag = "done1" if g == 1 else "rs"
    return allreduce_done(
        (tag, flat, (n,), jnp.float32, codec, block, axis_name, g))


def ring_allreduce_local(x, axis_name: str, *, codec: str = "int8",
                         axis_size: Optional[int] = None,
                         avg: bool = False, block: int = QUANT_BLOCK):
    """Full quantized ring all-reduce (start + done); call inside
    shard_map. ``codec='f32'`` is the exact leg (same ring, no
    rounding)."""
    return allreduce_done(
        allreduce_start(x, axis_name, codec=codec, axis_size=axis_size,
                        block=block), avg=avg)


def quantized_allreduce(x, mesh, axis: str = "dp", *,
                        codec: str = "int8", avg: bool = False,
                        block: int = QUANT_BLOCK):
    """shard_map wrapper over a GLOBAL array: per-device partial
    contributions ride ``axis``'s leading dim — ``x`` has shape
    ``(g, ...)`` (one slice per device) and the result is the reduced
    ``(...)`` value, identical on every device. The direct-call surface
    for tests and the PS-side host tooling; the executor's compiled
    step calls the ``_local`` form inside its own shard_map."""
    from jax.sharding import PartitionSpec as P

    g = mesh.shape[axis]

    def local(xs):
        return ring_allreduce_local(xs[0], axis, codec=codec,
                                    axis_size=g, avg=avg, block=block)

    return shard_map_nocheck(
        local, mesh, (P(axis, *([None] * (x.ndim - 1))),),
        P(*([None] * (x.ndim - 1))))(x)


# ---------------------------------------------------------------------------
# bucketed overlap driver (the executor's per-step gradient reduction)
# ---------------------------------------------------------------------------


def bucketed_allreduce(buckets: Sequence, axis_name: str, *,
                       codec: str = "int8",
                       axis_size: Optional[int] = None,
                       avg: bool = False, block: int = QUANT_BLOCK):
    """Reduce a list of flat f32 buckets with start/done interleaving:
    every bucket's reduce-scatter is ISSUED before any bucket's
    all-gather completes, so in the traced program bucket k's collective
    overlaps bucket k+1's — the latency-hiding emission order the
    comm_bucketing pass sets up (bucket order = backward completion
    order)."""
    starts = [allreduce_start(b, axis_name, codec=codec,
                              axis_size=axis_size, block=block)
              for b in buckets]
    return [allreduce_done(c, avg=avg) for c in starts]


