"""Op-level micro-benchmark harness (reference
operators/benchmark/op_tester.cc + operators/jit/benchmark.cc): times the
hot kernels — matmul, attention (XLA and Pallas flash), layernorm,
embedding lookup, conv — on the current backend and prints one JSON
line per op, so a single kernel can be timed without running a full
model (fused xent's three kernels apart: ``--ops fused_xent``; the
dropless expert layer's rungs at the MoE cells' shapes: ``--ops
expert_ffn``).

Usage:
    python tools/op_bench.py                 # bench all ops, print rows
    python tools/op_bench.py --ops matmul,attention
    python tools/op_bench.py --append bench_ops.jsonl  # history file

Each row: {"op", "shape", "ms", "gflops" (if meaningful), "backend",
"device_kind"}. Smoke shapes via BENCH_SMOKE=1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timeit(fn, *args, iters=20):
    from paddle_tpu.utils.timing import timeit

    return timeit(fn, *args, iters=iters)


def bench_matmul(smoke):
    import jax.numpy as jnp

    n = 512 if smoke else 4096
    key = jax.random.key(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    b = jax.random.normal(key, (n, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    ms = _timeit(f, a, b)
    return {"op": "matmul_bf16", "shape": f"{n}x{n}x{n}", "ms": ms,
            "gflops": 2 * n ** 3 / (ms / 1e3) / 1e9}


def bench_attention(smoke):
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F

    # (B, L, H, D) paddle layout; sdpa dispatches Pallas flash on TPU
    b, h, s, d = (2, 4, 256, 64) if smoke else (8, 12, 512, 64)
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    f = jax.jit(lambda q: F.scaled_dot_product_attention(
        q, q, q, is_causal=True, training=False).value)
    ms = _timeit(f, q)
    flops = 4 * b * h * s * s * d
    return {"op": "attention_causal", "shape": f"b{b}h{h}s{s}d{d}",
            "ms": ms, "gflops": flops / (ms / 1e3) / 1e9}


def bench_flash_attention(smoke):
    import jax.numpy as jnp

    from paddle_tpu.framework.bringup import TPU_PLATFORMS
    from paddle_tpu.ops.pallas.flash_attention import (
        _local_attention, _xla_attention)

    if jax.default_backend() not in TPU_PLATFORMS:
        return {"op": "flash_vs_xla", "skipped": "tpu-only"}
    b, h, s, d = (2, 4, 256, 64) if smoke else (8, 12, 512, 64)
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    flash = jax.jit(lambda q: _local_attention(q, q, q, True))
    xla = jax.jit(lambda q: _xla_attention(q, q, q, None, 0.0, True, None))
    ms_flash = _timeit(flash, q)
    ms_xla = _timeit(xla, q)
    return {"op": "flash_vs_xla", "shape": f"b{b}h{h}s{s}d{d}",
            "ms": ms_flash, "ms_xla": round(ms_xla, 4),
            "speedup": round(ms_xla / ms_flash, 3)}


def bench_flash_short(smoke):
    """Seq-128 dispatch-floor A/B: single-block short kernel vs the
    streaming kernel vs XLA (VERDICT r3 weak #3)."""
    import jax.numpy as jnp

    from paddle_tpu.framework.bringup import TPU_PLATFORMS
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas, _flash_attention_pallas_short,
        _xla_attention)

    if jax.default_backend() not in TPU_PLATFORMS:
        return {"op": "flash_short_vs_xla", "skipped": "tpu-only"}
    b, h, s, d = (2, 4, 128, 64) if smoke else (128, 12, 128, 64)
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    short = jax.jit(lambda q: _flash_attention_pallas_short(
        q, q, q, causal=False))
    stream = jax.jit(lambda q: _flash_attention_pallas(
        q, q, q, causal=False, block_q=128, block_kv=128))
    xla = jax.jit(lambda q: _xla_attention(q, q, q, None, 0.0, False,
                                           None))
    ms_short = _timeit(short, q)
    ms_stream = _timeit(stream, q)
    ms_xla = _timeit(xla, q)
    return {"op": "flash_short_vs_xla", "shape": f"b{b}h{h}s{s}d{d}",
            "ms": ms_short, "ms_stream": round(ms_stream, 4),
            "ms_xla": round(ms_xla, 4),
            "speedup_vs_xla": round(ms_xla / ms_short, 3)}


def bench_layernorm(smoke):
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F

    rows, dim = (1 << 12, 256) if smoke else (1 << 16, 1024)
    key = jax.random.key(0)
    x = jax.random.normal(key, (rows, dim), jnp.float32)
    w = jnp.ones((dim,), jnp.float32)
    bvec = jnp.zeros((dim,), jnp.float32)
    f = jax.jit(lambda x: F.layer_norm(x, (dim,), w, bvec).value)
    ms = _timeit(f, x)
    gbps = x.nbytes * 2 / (ms / 1e3) / 1e9
    return {"op": "layernorm", "shape": f"{rows}x{dim}", "ms": ms,
            "gbps": gbps}


def bench_embedding(smoke):
    import jax.numpy as jnp

    vocab, dim = (10000, 128) if smoke else (100000, 768)
    tokens = 1 << 12 if smoke else 1 << 15
    key = jax.random.key(0)
    table = jax.random.normal(key, (vocab, dim), jnp.float32)
    ids = jax.random.randint(key, (tokens,), 0, vocab)
    f = jax.jit(lambda t, i: t[i])
    ms = _timeit(f, table, ids)
    gbps = tokens * dim * 4 / (ms / 1e3) / 1e9
    return {"op": "embedding", "shape": f"{vocab}x{dim}@{tokens}",
            "ms": ms, "gbps": gbps}


def bench_conv(smoke):
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F

    b, c, hw, k = (4, 32, 32, 64) if smoke else (64, 128, 56, 128)
    key = jax.random.key(0)
    x = jax.random.normal(key, (b, c, hw, hw), jnp.bfloat16)
    w = jax.random.normal(key, (k, c, 3, 3), jnp.bfloat16)
    f = jax.jit(lambda x, w: F.conv2d(x, w, padding=1).value)
    ms = _timeit(f, x, w)
    flops = 2 * b * k * c * 9 * hw * hw
    return {"op": "conv2d_bf16", "shape": f"b{b}c{c}x{hw}->k{k}",
            "ms": ms, "gflops": flops / (ms / 1e3) / 1e9}


def bench_fused_embedding(smoke):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_embedding import \
        fused_embedding_seq_pool

    vocab, dim = (5000, 128) if smoke else (100000, 256)
    b, s = (256, 16) if smoke else (4096, 64)
    key = jax.random.key(0)
    table = jax.random.normal(key, (vocab, dim), jnp.float32)
    ids = jax.random.randint(key, (b, s), 0, vocab)
    f = jax.jit(lambda t, i: fused_embedding_seq_pool(
        t, i, combiner="sum"))
    ms = _timeit(f, table, ids)
    gbps = b * s * dim * 4 / (ms / 1e3) / 1e9
    return {"op": "fused_embedding_bag", "shape": f"{vocab}x{dim}@{b}x{s}",
            "ms": ms, "gbps": gbps}


def bench_softmax_xent(smoke):
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F

    rows, classes = (1 << 10, 1000) if smoke else (1 << 14, 32000)
    key = jax.random.key(0)
    logits = jax.random.normal(key, (rows, classes), jnp.float32)
    labels = jax.random.randint(key, (rows,), 0, classes)

    def step(lg, lb):
        return F.cross_entropy(lg, lb).value

    f = jax.jit(step)
    ms = _timeit(f, logits, labels)
    return {"op": "softmax_xent", "shape": f"{rows}x{classes}", "ms": ms,
            "gbps": logits.nbytes / (ms / 1e3) / 1e9}


def bench_optimizer_update(smoke):
    """AdamW slot update over a flat param bundle (optimizer hot loop)."""
    import jax.numpy as jnp
    import optax

    n = (1 << 20) if smoke else (1 << 24)
    key = jax.random.key(0)
    p = jax.random.normal(key, (n,), jnp.float32)
    g = jax.random.normal(key, (n,), jnp.float32)
    opt = optax.adamw(1e-3)
    state = opt.init(p)

    @jax.jit
    def step(p, g, state):
        up, state = opt.update(g, state, p)
        return optax.apply_updates(p, up), state

    ms = _timeit(step, p, g, state, iters=10)
    return {"op": "adamw_update", "shape": f"{n}", "ms": ms,
            "gbps": p.nbytes * 5 / (ms / 1e3) / 1e9}


def bench_transpose(smoke):
    """HBM bandwidth probe: non-fusible major-axis transpose copy."""
    import jax.numpy as jnp

    n = 1024 if smoke else 8192
    key = jax.random.key(0)
    x = jax.random.normal(key, (n, n), jnp.float32)
    f = jax.jit(lambda x: jnp.swapaxes(x, 0, 1) + 1.0)
    ms = _timeit(f, x)
    return {"op": "transpose_add", "shape": f"{n}x{n}", "ms": ms,
            "gbps": x.nbytes * 2 / (ms / 1e3) / 1e9}


def _xent_kernel_rows(n, hd, v):
    """The three fused-xent kernels apart, at one call's shapes from
    float32 h and table: ms a call and us a grid step beside one logits
    product's us at the bf16 peak (the forward makes one a step, dh and
    dW two each). dh and dW share ``_bwd_call``; fetching one's outputs
    lets XLA drop the other's call."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_xent as fx

    h = jax.random.normal(jax.random.key(0), (n, hd), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (v, hd), jnp.float32) * 0.02
    b = jnp.zeros((v,), jnp.float32)
    lab = jax.random.randint(jax.random.key(2), (n,), 0, v, jnp.int32)
    g = jnp.full((n,), 1.0 / n, jnp.float32)
    bn, bv = fx._blocks(h, w)
    fwd = jax.jit(lambda h, w: fx._fwd_call(h, w, b, lab, bn, bv))
    lse, _ = fwd(h, w)

    def bwd(h, w):
        return fx._bwd_call(h, w, b, lab, lse, g, bn, bv)

    calls = {"fwd": fwd, "dh": jax.jit(lambda h, w: bwd(h, w)[0]),
             "dw": jax.jit(lambda h, w: bwd(h, w)[1:])}
    steps = (n // bn) * (v // bv)
    ms = {k: _timeit(f, h, w) for k, f in calls.items()}
    return {"shape": f"{n}x{hd}x{v}", "blocks": [bn, bv], "grid_steps": steps,
            "ms": {k: round(t, 4) for k, t in ms.items()},
            "us_per_step": {k: round(t * 1e3 / steps, 3)
                            for k, t in ms.items()},
            "product_us_at_peak": round(2e6 * bn * bv * hd / 197e12, 3)}


def bench_fused_xent(smoke):
    """MLM-head A/B (VERDICT r4 #2): fused streamed linear+xent kernel
    vs the materialised-logits XLA path, fwd+bwd at BERT shapes; and
    under ``kernels`` the forward, dh and dW kernels apart at the two
    training cells' calls (BERT's 8,192-row rung, the causal LM's top
    rung at hidden 2,304), so whoever reads ``xent_roofline_pct.train``
    can see which kernel holds it down without a trace."""
    import jax.numpy as jnp

    from paddle_tpu.framework.bringup import TPU_PLATFORMS
    from paddle_tpu.ops.pallas.fused_xent import _fused_xent_core

    if jax.default_backend() not in TPU_PLATFORMS:
        return {"op": "fused_xent_vs_xla", "skipped": "tpu-only"}
    n, hd, v = (512, 128, 1024) if smoke else (4096, 768, 30592)
    key = jax.random.key(0)
    h = jax.random.normal(key, (n, hd), jnp.bfloat16) * 0.2
    w = jax.random.normal(jax.random.key(1), (v, hd), jnp.bfloat16) * 0.2
    b = jnp.zeros((v,), jnp.float32)
    lab = jax.random.randint(jax.random.key(2), (n,), 0, v, jnp.int32)

    fused = jax.jit(jax.grad(
        lambda h_, w_: _fused_xent_core(h_, w_, b, lab, -100),
        argnums=(0, 1)))

    def xla_loss(h_, w_):
        logits = (h_ @ w_.T).astype(jnp.float32) + b
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, lab[:, None], axis=1))

    xla = jax.jit(jax.grad(xla_loss, argnums=(0, 1)))
    ms_fused = _timeit(fused, h, w)
    ms_xla = _timeit(xla, h, w)
    cells = ([(512, 128, 1024)] if smoke
             else [(8192, 768, 30592), (8192, 2304, 20480)])
    return {"op": "fused_xent_vs_xla", "shape": f"{n}x{hd}x{v}",
            "ms": ms_fused, "ms_xla": round(ms_xla, 4),
            "speedup": round(ms_xla / ms_fused, 3),
            "kernels": [_xent_kernel_rows(*c) for c in cells]}


#: the six MoE cells' expert layers: tokens a step, D, F, experts held
#: of how many, picks a token, gated or plain, the router's scores
EXPERT_CELLS = {
    "nemotron": (16384, 2688, 1856, 8, 128, 6, False, "sigmoid"),
    "lfm2": (16384, 2048, 1536, 8, 64, 4, True, "sigmoid"),
    "kanana": (16384, 2048, 768, 8, 128, 6, True, "sigmoid"),
    "mellum": (16384, 2304, 896, 16, 64, 8, True, "softmax"),
    "kimi": (8192, 2304, 1024, 8, 256, 8, True, "sigmoid"),
    "qwen3next": (8192, 2048, 512, 32, 512, 10, True, "softmax"),
}


def bench_expert_ffn(smoke, only=""):
    """The dropless expert layer (``nn.moe.sparse_moe``: router, sort and
    the rung that runs) at each MoE cell's shapes (``only``: some of them,
    ``nemotron+lfm2``), forward + recomputed forward + backward under
    bfloat16 autocast, once a count of pairs: a correction bias on the
    first ``forced`` experts makes them every token's picks, 0 and 1 of
    them for the lowest rung at two counts (a grouped rung's launches
    stop at its last live tile, so its time follows its count:
    ``live_tiles`` of ``tiles`` a run, from the same scores as the
    layer's), ``top_k`` of them for the dense rung.
    ``dense_rows`` is what the lowest rung took in the dense rung's rows
    (the router, the sort and the ways back in both), and ``stated`` what
    ``nn.moe._grouped_cost`` states for it where it is the kernels'."""
    import jax.numpy as jnp

    from paddle_tpu import amp
    from paddle_tpu.nn.moe import SCORE_FUNCS, _grouped_cost, sparse_moe
    from paddle_tpu.ops.pallas import counters
    from paddle_tpu.ops.pallas.grouped_ffn import TILE, padded_rows

    cells = {"smoke": (256, 64, 32, 2, 16, 2, True, "sigmoid")} if smoke \
        else {k: v for k, v in EXPERT_CELLS.items()
              if not only or k in only.split("+")}
    out = []
    for name, (t, d, f, held, experts, top_k, gated, score) in cells.items():
        def layer(x, router, bias, up, down, gate=None):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return sparse_moe.raw_fn(
                    x, router, bias, gate, up, down, top_k=top_k,
                    score_func=score)

        def loss(x, router, up, down, gate, bias):
            routed, routing = jax.checkpoint(layer)(
                x, router, bias, up, down, gate)
            return jnp.sum(routed * routed), routing

        keys = jax.random.split(jax.random.key(0), 5)
        args = [jax.random.normal(keys[0], (t, d), jnp.bfloat16),
                jax.random.normal(keys[1], (d, experts)) * d ** -0.5,
                jax.random.normal(keys[2], (held, d, f)) * d ** -0.5,
                jax.random.normal(keys[3], (held, f, d)) * f ** -0.5,
                jax.random.normal(keys[4], (held, d, f)) * d ** -0.5
                if gated else None]
        step = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3) + ((4,) if gated else ()),
            has_aux=True))

        @jax.jit
        def held_sizes(x, router, bias):
            """Pairs on each held expert, by the layer's own routing."""
            scores = SCORE_FUNCS[score](jnp.matmul(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))
            _, picked = jax.lax.top_k(scores + bias, top_k)
            return jnp.sum(picked.reshape(-1, 1) == jnp.arange(held), axis=0)

        before = counters.snapshot()
        rungs = []
        for forced in (0, 1, top_k):
            bias = jnp.zeros((experts,), jnp.float32).at[:forced].set(10.0)
            (_, routing), _ = step(*args, bias)
            pairs, rows = (int(v) for v in np.asarray(routing))
            ms = _timeit(step, *args, bias, iters=10)
            rungs.append({"forced": forced, "pairs": pairs, "rows": rows,
                          "ms": round(ms, 4),
                          "us_per_row": round(1e3 * ms / rows, 4)})
            if rows < t * held and "sparse_moe.grouped" in counters.delta(
                    before):        # a grouped rung ran, not the dense one
                sizes = np.asarray(held_sizes(args[0], args[1], bias))
                rungs[-1].update(
                    live_tiles=int(np.maximum(1, -(-sizes // TILE)).sum()),
                    tiles=padded_rows(rows, held) // TILE)
        row = {"cell": name, "rungs": rungs}
        # (no ratio where the ladder is one rung: every count ran on it)
        if rungs[0]["rows"] != rungs[-1]["rows"]:
            row["dense_rows"] = round(
                rungs[0]["ms"] / rungs[-1]["ms"] * rungs[-1]["rows"])
            if "sparse_moe.grouped" in counters.delta(before):
                a_row, a_rung = _grouped_cost(t, top_k, held, d, f, gated)
                row["stated"] = round(a_row * rungs[0]["rows"] + a_rung)
        out.append(row)
    return {"op": "expert_ffn", "ms": out[0]["rungs"][0]["ms"],
            "cells": out}


BENCHES = {
    "expert_ffn": bench_expert_ffn,
    "matmul": bench_matmul,
    "attention": bench_attention,
    "flash_attention": bench_flash_attention,
    "flash_short": bench_flash_short,
    "fused_xent": bench_fused_xent,
    "layernorm": bench_layernorm,
    "embedding": bench_embedding,
    "fused_embedding": bench_fused_embedding,
    "conv": bench_conv,
    "softmax_xent": bench_softmax_xent,
    "optimizer_update": bench_optimizer_update,
    "transpose": bench_transpose,
}


def run_benches(ops):
    """Resolve the backend, run the named benches, return the row
    dicts."""
    smoke = os.environ.get("BENCH_SMOKE") == "1"

    global jax
    import jax

    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind
    rows = []
    for name in ops:
        name = name.strip()
        if not name:
            continue
        # (``expert_ffn=nemotron+lfm2``: a bench that takes a choice)
        name, _, choice = name.partition("=")
        try:
            row = BENCHES[name](smoke, *([choice] if choice else []))
        except Exception as e:
            row = {"op": name, "error": f"{type(e).__name__}: {e}"}
        row.update({"backend": backend, "device_kind": kind, "smoke": smoke})
        if "ms" in row:
            row["ms"] = round(row["ms"], 4)
        for k in ("gflops", "gbps"):
            if k in row:
                row[k] = round(row[k], 2)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=",".join(BENCHES))
    ap.add_argument("--append", default=None,
                    help="JSONL history file to append rows to")
    args = ap.parse_args()
    rows = run_benches(args.ops.split(","))
    for row in rows:
        print(json.dumps(row), flush=True)
    if args.append:
        with open(args.append, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")


jax = None  # set in main() after backend resolution

if __name__ == "__main__":
    main()
