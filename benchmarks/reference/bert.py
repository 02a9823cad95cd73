"""Plain reference for BERT pretraining: loss, gradients and AdamW steps
in straightforward ``jax.numpy`` float32 at "highest" matmul precision.
No kernels, no autocast, no fused anything; imports nothing of the
program. It follows the published BERT (post-LayerNorm encoder, exact
GELU, tied MLM decoder, NSP head) with the program's departures, which
the configuration file lists under ``departs``: the encoder's LayerNorms
use epsilon 1e-5, the vocabulary is padded, AdamW decays every leaf.

Parameters are a dict under the program's parameter names; the
benchmark makes them from the seed (``drivers/train_step.py``) and hands
the same values to both sides.

``matmuls`` swaps the two matrix products (dense: ``(..., K) @ (K, N)``;
batched: attention's ``(B, H, S, D)`` products) — the reference uses
float32 at "highest"; the control of ``How correct is decided`` uses
:func:`fp8_matmuls`, the precision one step below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def _dense(x, w):
    return jnp.matmul(x, w, precision=_HIGHEST)


def _bmm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


F32_MATMULS = (_dense, _bmm)


def _fake_quant(x, dtype):
    """Per-tensor scaled round trip through an 8-bit float type."""
    top = float(jnp.finfo(dtype).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _low_matmuls(q_fwd, q_bwd):
    """(dense, batched) products whose operands pass through ``q_fwd`` on
    the way forward and whose cotangents pass through ``q_bwd`` on the
    way back; float32 accumulation."""
    @jax.custom_vjp
    def bmm(a, b):
        return _bmm(q_fwd(a), q_fwd(b))

    def fwd(a, b):
        qa, qb = q_fwd(a), q_fwd(b)
        return _bmm(qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        qg = q_bwd(g)
        return (_bmm(qg, jnp.swapaxes(qb, -1, -2)),
                _bmm(jnp.swapaxes(qa, -1, -2), qg))

    bmm.defvjp(fwd, bwd)

    def dense(x, w):
        y = bmm(x.reshape((-1, x.shape[-1])), w)
        return y.reshape(x.shape[:-1] + (w.shape[-1],))

    return dense, bmm


#: the usual fp8 training recipe: e4m3 operands forward, e5m2 cotangents
#: backward, one scale per tensor
fp8_matmuls = _low_matmuls(
    lambda x: _fake_quant(x, jnp.float8_e4m3fn),
    lambda x: _fake_quant(x, jnp.float8_e5m2))


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _xent_sum(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def block_loss(params, cfg, block, n_rows, n_labelled, matmuls=F32_MATMULS):
    """This block of rows' share of the batch loss: summing it over the
    blocks of a batch of ``n_rows`` rows with ``n_labelled`` labelled
    positions in all gives mean MLM cross-entropy + mean NSP
    cross-entropy, and its gradients sum to the batch gradient.

    ``block`` = (ids, segments, label positions, label ids, nsp labels);
    the vocabulary head runs on the labelled positions only, which is the
    same number: an unlabelled position adds nothing to the loss."""
    dense, bmm = matmuls
    ids, segments, where, label_ids, nsp = block
    h_size = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    d = h_size // heads
    eps = cfg["layer_norm_eps"]
    eps_enc = cfg.get("encoder_layer_norm_eps", eps)
    rows, seq = ids.shape
    p = params

    def lin(x, name):
        return dense(x, p[name + ".weight"]) + p[name + ".bias"]

    emb = "bert.embeddings."
    x = p[emb + "word_embeddings.weight"][ids] \
        + p[emb + "position_embeddings.weight"][:seq][None] \
        + p[emb + "token_type_embeddings.weight"][segments]
    x = _layer_norm(x, p[emb + "layer_norm.weight"],
                    p[emb + "layer_norm.bias"], eps)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"bert.encoder.layers.{i}."

        def split(t):
            return t.reshape(rows, seq, heads, d).transpose(0, 2, 1, 3)

        q = split(lin(x, pre + "self_attn.q_proj"))
        k = split(lin(x, pre + "self_attn.k_proj"))
        v = split(lin(x, pre + "self_attn.v_proj"))
        s = bmm(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(d)
        a = bmm(jax.nn.softmax(s, axis=-1), v)
        a = a.transpose(0, 2, 1, 3).reshape(rows, seq, h_size)
        x = _layer_norm(x + lin(a, pre + "self_attn.out_proj"),
                        p[pre + "norm1.weight"], p[pre + "norm1.bias"],
                        eps_enc)
        f = lin(jax.nn.gelu(lin(x, pre + "linear1"), approximate=False),
                pre + "linear2")
        x = _layer_norm(x + f, p[pre + "norm2.weight"],
                        p[pre + "norm2.bias"], eps_enc)
    picked = jnp.take_along_axis(x, where[..., None], axis=1)
    t = _layer_norm(
        jax.nn.gelu(lin(picked, "mlm_transform"), approximate=False),
        p["mlm_norm.weight"], p["mlm_norm.bias"], eps)
    logits = dense(t, p[emb + "word_embeddings.weight"].T) + p["mlm_bias"]
    mlm = _xent_sum(logits, label_ids) / n_labelled
    pooled = jnp.tanh(lin(x[:, 0], "bert.pooler"))
    nsp_loss = _xent_sum(lin(pooled, "nsp"), nsp) / n_rows
    return mlm + nsp_loss


def _blocks(batch, block_rows):
    """Row blocks of one host batch, labelled positions gathered."""
    ids, segments, labels, nsp = (np.asarray(a) for a in batch)
    n_lab = int((labels[0] != -100).sum())
    if not np.all((labels != -100).sum(axis=1) == n_lab):
        raise ValueError("rows differ in their number of labelled positions")
    where = np.argsort(labels == -100, axis=1, kind="stable")[:, :n_lab]
    label_ids = np.take_along_axis(labels, where, axis=1)
    for a in range(0, ids.shape[0], block_rows):
        b = slice(a, a + block_rows)
        yield tuple(jnp.asarray(t) for t in (
            ids[b], segments[b], where[b].astype(np.int32), label_ids[b],
            nsp[b]))


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train(params, cfg, batches, hyper, block_rows=8, matmuls=F32_MATMULS):
    """Follow ``len(batches)`` AdamW steps from ``params``. Returns the
    loss of each step, the per-leaf norm of the first step's gradient and
    the per-leaf norm of the parameters' change after the last step.

    ``hyper``: learning_rate (the peak), warmup_steps (step t runs at
    peak * min(1, t / warmup_steps)), beta1, beta2, epsilon, weight_decay
    — the decoupled decay ``p -= lr * wd * p`` on every leaf, as the
    program's ``optimizer.AdamW`` does it."""
    peak, warmup = hyper["learning_rate"], hyper["warmup_steps"]
    b1, b2 = hyper["beta1"], hyper["beta2"]
    eps, wd = hyper["epsilon"], hyper["weight_decay"]

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def grad_of(p, block, n_rows, n_lab):
        return jax.value_and_grad(block_loss)(p, cfg, block, n_rows, n_lab,
                                              matmuls)

    @jax.jit
    def add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    @jax.jit
    def adamw(p, m, v, g, t, lr):
        def one(p, m, v, g):
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m2 / (1 - b1 ** t)
            vhat = v2 / (1 - b2 ** t)
            p2 = p - lr * mhat / (jnp.sqrt(vhat) + eps)
            return p2 - lr * wd * p, m2, v2
        out = {k: one(p[k], m[k], v[k], g[k]) for k in p}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    @jax.jit
    def change_norms(p, p0):
        return leaf_norms({k: p[k] - p0[k] for k in p})

    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norm = [], None
    for t, batch in enumerate(batches, start=1):
        n_rows = int(np.asarray(batch[0]).shape[0])
        n_lab = n_rows * int((np.asarray(batch[2])[0] != -100).sum())
        loss, grads = 0.0, None
        for block in _blocks(batch, block_rows):
            part, g = grad_of(p, block, n_rows, n_lab)
            loss = loss + part
            grads = g if grads is None else add(grads, g)
        losses.append(float(loss))
        if t == 1:
            grad_norm = {k: float(x) for k, x in
                         jax.jit(leaf_norms)(grads).items()}
        p, m, v = adamw(p, m, v, grads, jnp.float32(t),
                        jnp.float32(peak * min(1.0, t / warmup)))
    delta = {k: float(x) for k, x in change_norms(p, params).items()}
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}
