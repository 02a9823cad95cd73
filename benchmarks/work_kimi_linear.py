"""Operations and bytes of a Kimi-Linear style decoder from its shapes:
the required FLOP per token of a training step (for ``mfu_pct.train``)
and what the KDA chunk kernels cannot avoid (for
``kda_roofline_pct.train``). Beside ``flops.py``, which counts BERT and
the decode engine's model. A multiply-add is two operations, forward +
backward is three times the forward; recomputed activations, padded rows
and whatever a program does beyond the algorithm earn no credit.

``cfg`` is the configuration as the model is built from it: the router's
``num_experts`` outputs, ``experts_held`` experts on this chip.
"""
from __future__ import annotations

from benchmarks.reference.kimi_linear import layer_kinds as _kinds


def kda_matrix_params(cfg: dict) -> int:
    """q, k, v, o; the decay's and the gate's low-rank pairs (inner width
    ``head_dim``); beta. The convolutions and norms are no matrices."""
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    width, low = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    return 4 * h * width + 2 * (h * low + low * width) \
        + h * lin["num_heads"]


def mla_matrix_params(cfg: dict) -> int:
    h, a = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return h * a * qk + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + cfg["kv_lora_rank"] * a * (cfg["qk_nope_head_dim"]
                                     + cfg["v_head_dim"]) \
        + a * cfg["v_head_dim"] * h


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label.

    matrices   6 x the matrix parameters a token meets: mixers, dense
               FFN, shared experts, the router, and of the routed experts
               the expected ``top_k * experts_held / num_experts`` a token
    MLA        scores and values: 2 (d_qk + d_v) per key and head, half of
               the keys under the causal mask, x 3
    KDA        the recurrence on a K x V state: 6 K V per head (decay,
               read, rank-one write, query), x 3
    head       6 x hidden x vocabulary on the labelled rows
    """
    h = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    expert = 3 * h * cfg.get("moe_intermediate_size", 0)
    total = 0.0
    for mixer, ffn in _kinds(cfg):
        if mixer == "kda":
            total += 6.0 * kda_matrix_params(cfg)
            total += 3.0 * 6.0 * lin["head_dim"] ** 2 * lin["num_heads"]
        else:
            total += 6.0 * mla_matrix_params(cfg)
            qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            total += 3.0 * 2.0 * (qk + cfg["v_head_dim"]) * (seq / 2.0) \
                * cfg["num_attention_heads"]
        if ffn == "dense":
            total += 6.0 * 3 * h * cfg["intermediate_size"]
        else:
            held = cfg.get("experts_held", cfg["num_experts"])
            routed = cfg["num_experts_per_token"] * held / cfg["num_experts"]
            total += 6.0 * (h * cfg["num_experts"]
                            + (cfg.get("num_shared_experts", 0) + routed)
                            * expert)
    return total + 6.0 * h * cfg["vocab_size"] * labelled / seq


def kda_kernel_work(cfg: dict, batch: int, seq: int) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the KDA chunk kernels in one
    training step, under the roles a device trace shows. Per call the
    recurrence's 6 K V operations a token and head forward and twice that
    backward; the compulsory HBM traffic is q, k, v, g (float32, K or V
    wide) and beta read once and o written once forward, and backward the
    same again plus o's cotangent read and the five cotangents written:
    twice the forward's bytes."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    calls = sum(1 for mixer, _ in _kinds(cfg) if mixer == "kda")
    tokens = batch * seq * heads
    flops = 6.0 * tokens * d * d
    moved = 4.0 * tokens * (5 * d + 1)
    return {
        "kda_chunk_fwd": {"calls": calls, "flops": calls * flops,
                          "bytes": calls * moved},
        "kda_chunk_bwd": {"calls": calls, "flops": calls * 2 * flops,
                          "bytes": calls * 2 * moved}}
