"""Flash-attention block-size sweep on a live TPU.

Times the Pallas dropout kernel (the BERT training path: mask=None,
dropout>0) across (block_q, block_kv) candidates at the bench shapes,
plus the XLA reference. Prints one JSON line per timing. Use after
kernel changes to re-pick the default blocks — the defaults encode the
winner at the bench configs (see flash_attention.py's dispatch-floor
comment for measured context).

Usage: python tools/tune_flash.py [--seq 512] [--batch 32] [--steps 30]
"""
import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.framework.bringup import TPU_PLATFORMS  # noqa: E402

import jax  # noqa: E402  (importing jax does not init a backend)
import jax.numpy as jnp  # noqa: E402


def _time(fn, args, steps):
    from paddle_tpu.utils.timing import timeit

    return timeit(fn, *args, iters=steps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--dropout", type=float, default=0.1)
    ap.add_argument("--grad", action="store_true",
                    help="time fwd+bwd (the training path) instead of "
                         "forward only — bwd is ~2/3 of attention time "
                         "and prefers LARGER q blocks (measured: "
                         "bq=512,bkv=512 beats 256,512 by 7% combined "
                         "at seq 512 though it loses the fwd-only race)")
    ns = ap.parse_args()

    backend = jax.default_backend()
    if backend not in TPU_PLATFORMS:
        print(json.dumps({"error": f"needs a TPU backend, got {backend}"}))
        return
    import numpy as np

    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(0)
    shape = (ns.batch, ns.seq, ns.heads, ns.dim)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
               for _ in range(3))
    seed = jnp.zeros((1, 1), jnp.int32)

    base = {"seq": ns.seq, "batch": ns.batch, "heads": ns.heads,
            "dim": ns.dim, "mode": "fwd+bwd" if ns.grad else "fwd"}

    def wrap(fn):
        if not ns.grad:
            return fn
        return jax.jit(jax.grad(
            lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)))

    ms = _time(wrap(jax.jit(functools.partial(
        fa._xla_attention, mask=None, dropout_p=ns.dropout,
        is_causal=False, key_rng=jax.random.key(0)))), (q, k, v), ns.steps)
    print(json.dumps({**base, "kernel": "xla_dropout",
                      "ms": round(ms, 4)}), flush=True)
    cands = [(bq, bkv) for bq in (128, 256, 512) for bkv in (128, 256, 512)
             if ns.seq % bq == 0 and ns.seq % bkv == 0]
    for bq, bkv in cands:
        try:
            pallas = functools.partial(
                fa._flash_attention_pallas_dropout,
                dropout_p=ns.dropout, block_q=bq, block_kv=bkv)
            if ns.grad:
                ms = _time(wrap(lambda q, k, v: pallas(q, k, v, seed)),
                           (q, k, v), ns.steps)
            else:
                ms = _time(pallas, (q, k, v, seed), ns.steps)
        except Exception as e:
            print(json.dumps({**base, "kernel": "pallas_dropout",
                              "bq": bq, "bkv": bkv,
                              "error": f"{type(e).__name__}"}), flush=True)
            continue
        print(json.dumps({**base, "kernel": "pallas_dropout", "bq": bq,
                          "bkv": bkv, "ms": round(ms, 4)}), flush=True)


if __name__ == "__main__":
    main()
