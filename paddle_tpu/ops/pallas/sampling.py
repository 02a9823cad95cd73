"""Fused token sampling for the decode engine (Pallas kernel + XLA
fallback): temperature scale + top-k mask + Gumbel-max draw in one
VMEM pass over the logits row.

Determinism contract: the Gumbel noise is generated OUTSIDE (the
engine derives it from a seeded host RNG per tick) and passed in, so
the kernel and the XLA fallback are the SAME function of (logits,
noise) — interpret-mode parity is bitwise, and a seeded run replays
token for token. Sampling itself is the Gumbel-max trick:
``argmax(logits/T + g)`` draws from ``softmax(logits/T)``; masking
(top-k / top-p) before the argmax draws from the truncated,
renormalized distribution.

Dispatch follows the established kernel pattern (flash_attention.py /
paged_attention.py): an eligibility gate (``_sample_ok`` — top-p
routes to the XLA path, the sort has no good single-pass kernel
shape), per-decision counters (``fused_sample.pallas`` / ``.xla`` with
a reason), an autotuned choice persisted in the PR 10 disk cache
(autotune.py), and ``PADDLE_FUSED_SAMPLING=0`` as the escape leg that
pins the XLA path.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .counters import kernel_call, nbytes

_NEG_INF = -1e30
_F32 = jnp.float32

__all__ = ["fused_sample"]

#: static top-k ceiling for the kernel: the threshold is found by
#: top_k unrolled max+mask rounds, so large k would bloat the kernel
_KERNEL_TOPK_MAX = 8


# ---------------------------------------------------------------------------
# XLA fallback — the reference path (and the only one for top-p)
# ---------------------------------------------------------------------------
def _xla_sample(logits, noise, temperature, top_k, top_p):
    x = logits.astype(_F32) / temperature
    V = x.shape[-1]
    if top_k and top_k < V:
        kth = jax.lax.top_k(x, int(top_k))[0][..., -1]
        x = jnp.where(x < kth[..., None], _NEG_INF, x)
    if top_p < 1.0:
        srt = jnp.sort(x, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # smallest set whose mass reaches top_p: keep a token while the
        # mass BEFORE it is still short (the head token always stays)
        keep = (csum - probs) < top_p
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
        x = jnp.where(x < thresh[..., None], _NEG_INF, x)
    return jnp.argmax(x + noise.astype(_F32), axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B/8,), one sublane tile of 8 logits rows per step
# ((1, V) row blocks do not lower: Mosaic needs the second-to-last block
# dim % 8), fused scale + top-k threshold + Gumbel add + argmax
# ---------------------------------------------------------------------------
_ROWS = 8
_LANES = 128


def _sample_kernel(l_ref, n_ref, o_ref, *, temperature, top_k):
    x = l_ref[...].astype(_F32) / temperature          # (8, V)
    if top_k:
        # k-th max by top_k unrolled max+mask rounds (k is static and
        # small — the _sample_ok ceiling)
        work = x
        thr = jnp.max(work, axis=1, keepdims=True)
        for _ in range(int(top_k) - 1):
            work = jnp.where(work >= thr, _NEG_INF, work)
            thr = jnp.max(work, axis=1, keepdims=True)
        x = jnp.where(x < thr, _NEG_INF, x)
    y = x + n_ref[...].astype(_F32)
    m = jnp.max(y, axis=1, keepdims=True)
    # first-max index (argmax tie rule) via 2D iota — 1D iota fails on
    # TPU (pallas guide). The min runs in f32: vocab ids are exact there
    # (V <= 2^24 by the _sample_ok ceiling) and float lane reductions
    # lower everywhere integer ones may not.
    idx = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1).astype(_F32)
    first = jnp.min(jnp.where(y >= m, idx, 3e38), axis=1, keepdims=True)
    # lane-dense store: every lane of a row carries that row's token
    o_ref[...] = jnp.broadcast_to(first, o_ref.shape).astype(jnp.int32)


def _fused_sample_pallas(logits, noise, temperature, top_k):
    from jax.experimental import pallas as pl

    B, V = logits.shape
    pad = (-B) % _ROWS
    if pad:   # dead rows: their draws are sliced away below
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
        noise = jnp.pad(noise, ((0, pad), (0, 0)))
    rows = pl.BlockSpec((_ROWS, V), lambda i: (i, 0))
    out = kernel_call(
        "gumbel_sampling",
        functools.partial(_sample_kernel,
                          temperature=float(temperature),
                          top_k=int(top_k)),
        grid=((B + pad) // _ROWS,),
        in_specs=[rows, rows],
        out_specs=pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + pad, _LANES), jnp.int32),
    )(logits, noise)
    return out[:B, 0]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _sample_ok(logits, top_k, top_p) -> bool:
    from ...framework.bringup import pallas_enabled

    if not pallas_enabled():
        return False
    V = logits.shape[-1]
    # top-p needs the sorted-cumsum pass — XLA's sort is the right tool;
    # the lane dim must tile (V % 128) and fit VMEM comfortably
    return (float(top_p) >= 1.0 and 0 <= int(top_k) <= _KERNEL_TOPK_MAX
            and V % 128 == 0 and V <= 16384)


def _escape_pinned() -> bool:
    """PADDLE_FUSED_SAMPLING=0 pins the XLA path — the bitwise escape
    leg (same shape as PADDLE_PAGED_ATTENTION=0)."""
    return os.environ.get("PADDLE_FUSED_SAMPLING", "").strip() == "0"


def fused_sample(logits, noise, temperature, top_k: int = 0,
                 top_p: float = 1.0):
    """Draw one token per row from ``softmax(logits/temperature)``
    truncated by top-k/top-p, using caller-supplied Gumbel ``noise``
    (same shape as ``logits``). ``temperature <= 0`` short-circuits to
    greedy argmax (noise ignored) — the spec-decode-compatible leg.
    Returns int32 token ids (B,)."""
    from .counters import bump

    if float(temperature) <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if _escape_pinned():
        bump("fused_sample", "xla", "PADDLE_FUSED_SAMPLING=0 pin")
        return _xla_sample(logits, noise, temperature, top_k, top_p)
    if _sample_ok(logits, top_k, top_p):
        from .autotune import fused_sample_choice

        choice = fused_sample_choice(logits, top_k)
        if choice == "xla":
            bump("fused_sample", "xla", "autotuned: xla wins this shape")
            return _xla_sample(logits, noise, temperature, top_k, top_p)
        out = _fused_sample_pallas(logits, noise, temperature, top_k)
        # no matmul: logits and noise read once, one token id a row
        bump("fused_sample", "pallas", work={"gumbel_sampling": (
            0.0, nbytes(logits, noise, out))})
        return out
    bump("fused_sample", "xla",
         f"dispatch ineligible (logits {tuple(logits.shape)}, "
         f"top_k={top_k}, top_p={top_p}; gate in _sample_ok)")
    return _xla_sample(logits, noise, temperature, top_k, top_p)
