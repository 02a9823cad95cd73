"""Plain reference for the decode engine's model: the dense causal
forward, float32, one block at a time. Copied from
``paddle_tpu/inference/decode/model.py`` (``dense_forward``) as it stood
at PR 21 so that a later change to the program cannot move the oracle;
imports nothing of the program. The block is OPT's with the departures
the configuration file lists: RMSNorm without bias, no linear biases,
untied head, no position offset.

Parameters are the dict the benchmark makes from the seed
(``drivers/decode_engine.py``), under the engine's parameter names.
``precision`` is the matmul precision of every product: "highest" for
the reference, one step lower for the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * scale / jnp.sqrt(var + 1e-6)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block(n_heads, precision, h, ln1, wq, wk, wv, wo, ln2, w1, w2):
    """One pre-norm block over ``h`` (L, E), causal."""
    length, e = h.shape
    d = e // n_heads
    mm = functools.partial(jnp.matmul, precision=precision)
    x = _rms(h, ln1)
    q = mm(x, wq).reshape(length, n_heads, d)
    k = mm(x, wk).reshape(length, n_heads, d)
    v = mm(x, wv).reshape(length, n_heads, d)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=precision) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=precision)
    h = h + mm(a.reshape(length, e), wo)
    x = _rms(h, ln2)
    return h + mm(jnp.maximum(mm(x, w1), 0.0), w2)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_rows(precision, h, lnf, head, n_rows, start):
    """Logits of ``n_rows`` positions from ``start``."""
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows, 0)
    return jnp.matmul(_rms(rows, lnf), head, precision=precision)


def logits_rows(cfg: dict, params: dict, tokens, start: int, n_rows: int,
                precision: str = "highest"):
    """Logits (n_rows, V) at positions ``start .. start + n_rows`` of the
    causal forward over ``tokens`` (L,) — position i predicts token i+1.
    ``tokens`` may be right-padded: causality keeps padding out of every
    earlier position."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params["tok_emb"][tokens] + params["pos_emb"][:tokens.shape[0]]
    for i in range(cfg["num_hidden_layers"]):
        h = _block(cfg["num_attention_heads"], precision, h,
                   *(params[f"l{i}.{n}"] for n in (
                       "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w2")))
    return _head_rows(precision, h, params["lnf"], params["head"],
                      int(n_rows), jnp.int32(start))
