"""Operations and bytes of a Mellum-2 style decoder from its shapes: the
required FLOP per token of a training step (for ``mfu_pct.train``) and
what its attention kernels cannot avoid (for
``gqa_attn_roofline_pct.train``). Beside ``work_kimi_linear.py``. A
multiply-add is two operations, forward + backward is three times the
forward; recomputed activations, the rows of a ladder rung that hold no
pair, the columns of a visited block outside the band and whatever a
program does beyond the algorithm earn no credit.

``cfg`` is the configuration as the model is built from it: the router's
``num_experts`` outputs, ``experts_held`` experts on this chip.
"""
from __future__ import annotations


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs with ``0 <= i - j < window`` among ``length``
    positions: the first W rows' triangle, then W keys a row."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def attended_keys(cfg: dict, kind: str, seq: int) -> float:
    """Keys a query reads on average. Full layers count half the square,
    as ``work_kimi_linear`` and the program's ledger do for a causal
    call; sliding layers count the band's own pairs."""
    if kind == "sliding_attention":
        return band_pairs(seq, cfg["sliding_window"]) / seq
    return seq / 2.0


def attention_matrix_params(cfg: dict) -> int:
    """q, k, v, o: the key and value projections are ``num_key_value_heads``
    wide. The per-head norms are no matrices."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label.

    matrices   6 x the matrix parameters a token meets: q, k, v, o, the
               router, and of the routed experts the expected ``top_k *
               experts_held / num_experts`` a token
    attention  scores and values: 2 x 2 head_dim per key and query head
               (a key head serves its group's query heads; the products
               are per query head), x 3
    head       6 x hidden x vocabulary on the labelled rows
    """
    h, d = cfg["hidden_size"], cfg["head_dim"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    held = cfg.get("experts_held", cfg["num_experts"])
    routed = cfg["num_experts_per_tok"] * held / cfg["num_experts"]
    total = 0.0
    for kind in cfg["layer_types"]:
        total += 6.0 * attention_matrix_params(cfg)
        total += 3.0 * 2.0 * 2.0 * d * cfg["num_attention_heads"] \
            * attended_keys(cfg, kind, seq)
        total += 6.0 * (h * cfg["num_experts"] + routed * expert)
    return total + 6.0 * h * cfg["vocab_size"] * labelled / seq


def gqa_kernel_work(cfg: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the attention kernels in one
    training step, under the roles a device trace shows: the full layers'
    launches are ``flash_attention_grouped``, the sliding layers'
    ``flash_attention_window``, each ONE role for its forward and its
    backward (two calls a layer). Forward: Q K^T and P V, 4 D operations
    a pair and query head; backward: dV, dP, dQ, dK, 8 D (the recomputed
    scores not counted). The compulsory HBM traffic is q, k, v read and
    the output and the float32 logsumexp written forward; the backward
    reads those and the output's cotangent and writes dq, dk, dv. K and V
    are counted ONCE PER KEY HEAD (``num_key_value_heads``), whatever the
    group."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = batch * seq * heads * d * itemsize          # = the output's bytes
    kv = 2 * batch * seq * kv_heads * d * itemsize
    lse = 4 * batch * seq * heads
    out = {}
    for kind, name in (("full_attention", "grouped"),
                       ("sliding_attention", "window")):
        layers = sum(1 for k in cfg["layer_types"] if k == kind)
        if not layers:
            continue
        mm = batch * heads * seq * attended_keys(cfg, kind, seq) * d
        out[f"flash_attention_{name}"] = {
            "calls": 2 * layers, "flops": layers * 12.0 * mm,
            "bytes": layers * ((q + kv + q + lse)
                               + (2 * (q + kv) + 2 * q + lse))}
    return out
