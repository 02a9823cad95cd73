"""Operations, bytes and parameters of a DeepSeek-V3 style decoder
(latent attention with a rotary key in every layer, leading dense gated
FFNs, then routed gated experts beside shared ones) from its shapes: the
required FLOP per token of a training step (for ``mfu_pct.train``), what
the attention kernels cannot avoid at keys ``d_nope + d_pe`` wide and
values ``d_v`` wide (for ``mla_attn_roofline_pct.train``) and the
parameters a chip's share holds. Beside ``work_kimi_linear.py``,
``work_mellum2.py`` and ``work_nemotron_h.py``. A multiply-add is two
operations, forward + backward is three times the forward; recomputed
activations, the rows of the dense rung that hold no pair, the rotation
(no matrix product) and whatever a program does beyond the algorithm
earn no credit.

``cfg`` is the configuration as the model is built from it: the router's
``n_routed_experts`` outputs, ``experts_held`` experts on this chip.
"""
from __future__ import annotations

from benchmarks.reference.deepseek_v3 import layer_kinds
from benchmarks.work_kimi_linear import mla_matrix_params


def _shared_width(cfg: dict) -> int:
    return cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Scores and values, forward, a token and layer: 2 (d_qk + d_v) per
    key and head, half of the keys under the causal mask."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * (qk + cfg["v_head_dim"]) * (seq / 2.0) \
        * cfg["num_attention_heads"]


def ffn_flops_per_token(cfg: dict, kind: str) -> float:
    """Forward FLOP a token of one layer's feed-forward: a gated FFN is
    three matrices; of the routed experts a token meets the expected
    ``top_k * experts_held / n_routed_experts``."""
    h = cfg["hidden_size"]
    if kind == "dense":
        return 2.0 * 3 * h * cfg["intermediate_size"]
    held = cfg.get("experts_held", cfg["n_routed_experts"])
    picks = cfg["num_experts_per_tok"] * held / cfg["n_routed_experts"]
    return 2.0 * (h * cfg["n_routed_experts"] + 3 * h * _shared_width(cfg)
                  + picks * 3 * h * cfg["moe_intermediate_size"])


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label: three times the
    layers' forward (the latent projections 2 x their parameters, the
    scores and values as above, the feed-forward as above) and the
    head's 6 x hidden x vocabulary on the labelled rows."""
    mixer = 2.0 * mla_matrix_params(cfg) + attention_flops_per_token(cfg,
                                                                     seq)
    layers = sum(mixer + ffn_flops_per_token(cfg, kind)
                 for kind in layer_kinds(cfg))
    return 3.0 * layers \
        + 6.0 * cfg["hidden_size"] * cfg["vocab_size"] * labelled / seq


def param_count(cfg: dict) -> dict:
    """Parameters by part: a dense and an expert layer (mixer and norms
    included), embedding + head, and the whole share."""
    h = cfg["hidden_size"]
    held = cfg.get("experts_held", cfg["n_routed_experts"])
    mixer = mla_matrix_params(cfg) + cfg["kv_lora_rank"]
    out = {
        "dense": mixer + 3 * h * cfg["intermediate_size"] + 2 * h,
        "moe": mixer + h * cfg["n_routed_experts"]
        + 3 * h * _shared_width(cfg)
        + held * 3 * h * cfg["moe_intermediate_size"] + 2 * h,
        "embedding_and_head": 2 * h * cfg["vocab_size"]}
    out["total"] = out["embedding_and_head"] + h \
        + sum(out[kind] for kind in layer_kinds(cfg))
    return out


def mla_kernel_work(cfg: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the attention kernels in one
    training step, under the roles a device trace shows (one key head a
    query head, no window: the plain stream kernels). Forward: Q K^T at
    ``d_nope + d_pe`` and P V at ``d_v`` over the causal triangle, 2 (d_qk
    + d_v) operations a pair and head; backward dV, dP, dQ, dK, twice
    that (the recomputed scores not counted). The compulsory HBM traffic
    is q, k, v read and the output and the float32 logsumexp written
    forward; the backward reads those and the output's cotangent and
    writes dq, dk, dv: twice the forward's arrays and the logsumexp once.
    K counts at its full ``d_qk`` a head, as the kernels are handed it."""
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"]
    rows = batch * seq * heads
    flops = rows * (seq / 2.0) * 2.0 * (qk + vd)
    qkv = rows * (2 * qk + vd) * itemsize
    out, lse = rows * vd * itemsize, 4 * rows
    return {
        "flash_attention_stream_fwd": {
            "calls": layers, "flops": layers * flops,
            "bytes": layers * (qkv + out + lse)},
        "flash_attention_stream_bwd": {
            "calls": layers, "flops": layers * 2.0 * flops,
            "bytes": layers * (2 * qkv + 2 * out + lse)}}
