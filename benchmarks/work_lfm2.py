"""Operations and bytes of an LFM2-MoE style decoder from its shapes: the
required FLOP per token of a training step (for ``mfu_pct.train``) and
what its gated-convolution and attention kernels cannot avoid (for
``gated_conv_roofline_pct.train`` and ``gqa_attn_roofline_pct.train``).
Beside ``work_mellum2.py``, whose rules these are: a multiply-add is two
operations, forward + backward is three times the forward; recomputed
activations, the rows of a dense rung that hold no pair and whatever a
program does beyond the algorithm earn no credit.

``cfg`` is the configuration as the model is built from it: the router's
``num_experts`` outputs, ``experts_held`` experts on this chip.
"""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim",
                   cfg["hidden_size"] // cfg["num_attention_heads"])


def mixer_matrix_params(cfg: dict, kind: str) -> int:
    """The mixer's matrices a token meets. ``conv``: the in-projection's
    three groups and the out-projection, 4 hidden^2 (the taps are no
    matrix); attention: q, o and the ``num_key_value_heads`` wide k, v."""
    h = cfg["hidden_size"]
    if kind == "conv":
        return 4 * h * h
    return 2 * h * head_dim(cfg) * (cfg["num_attention_heads"]
                                    + cfg["num_key_value_heads"])


def ffn_matrix_params(cfg: dict, layer: int) -> float:
    """Of layer ``layer`` (from 0): a dense gated FFN in the first
    ``num_dense_layers``; after them the router and the expected ``top_k
    * experts_held / num_experts`` routed experts a token."""
    h = cfg["hidden_size"]
    if layer < cfg["num_dense_layers"]:
        return 3.0 * h * cfg["intermediate_size"]
    held = cfg.get("experts_held", cfg["num_experts"])
    routed = cfg["num_experts_per_tok"] * held / cfg["num_experts"]
    return h * cfg["num_experts"] + routed * 3.0 * h \
        * cfg["moe_intermediate_size"]


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label.

    matrices   6 x the matrix parameters a token meets (above)
    attention  scores and values: 2 x 2 head_dim per key and query head,
               half the square's keys a query, x 3
    head       6 x hidden x vocabulary on the labelled rows (the tied
               embedding's gather is no product)
    """
    total = 0.0
    for n, kind in enumerate(cfg["layer_types"]):
        total += 6.0 * (mixer_matrix_params(cfg, kind)
                        + ffn_matrix_params(cfg, n))
        if kind != "conv":
            total += 3.0 * 2.0 * 2.0 * head_dim(cfg) \
                * cfg["num_attention_heads"] * seq / 2.0
    return total + 6.0 * cfg["hidden_size"] * cfg["vocab_size"] \
        * labelled / seq


def gated_conv_work(cfg: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the gated convolution's
    launches in one training step, ONE role for both directions (two
    calls a ``conv`` layer). No matrix product. The compulsory HBM
    traffic in arrays of ``tokens x hidden`` in the projection's type:
    forward B, C, X read and y written (4); backward those three and y's
    cotangent read, dB, dC, dX written (7). The recomputed forward's
    seconds count, its work does not."""
    layers = sum(1 for kind in cfg["layer_types"] if kind == "conv")
    if not layers:
        return {}
    array = batch * seq * cfg["hidden_size"] * itemsize
    return {"gated_conv": {"calls": 2 * layers, "flops": 0.0,
                           "bytes": layers * 11.0 * array}}


def gqa_kernel_work(cfg: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """The attention layers' launches, as ``work_mellum2.gqa_kernel_work``
    counts a full layer's: 12 D operations a pair and query head over
    half the square, K and V once per key head."""
    layers = sum(1 for kind in cfg["layer_types"] if kind != "conv")
    if not layers:
        return {}
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = head_dim(cfg)
    q = batch * seq * heads * d * itemsize          # = the output's bytes
    kv = 2 * batch * seq * kv_heads * d * itemsize
    lse = 4 * batch * seq * heads
    mm = batch * heads * seq * (seq / 2.0) * d
    return {"flash_attention_grouped": {
        "calls": 2 * layers, "flops": layers * 12.0 * mm,
        "bytes": layers * ((q + kv + q + lse)
                           + (2 * (q + kv) + 2 * q + lse))}}
