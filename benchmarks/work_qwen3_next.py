"""Operations and bytes of a Qwen3-Next style decoder from its shapes: the
required FLOP per token of a training step (for ``mfu_pct.train``) and
what its delta-rule and attention kernels cannot avoid (for
``gdn_roofline_pct.train`` and ``gated_attn_roofline_pct.train``). Beside
``work_kimi_linear.py``, whose rules these are: a multiply-add is two
operations, forward + backward is three times the forward; recomputed
activations, the rows of a rung that hold no pair and whatever a program
does beyond the algorithm earn no credit.

``cfg`` is the configuration as the model is built from it: the router's
``num_experts`` outputs, ``experts_held`` experts on this chip.
"""
from __future__ import annotations

from benchmarks.reference.qwen3_next import layer_kinds as _kinds


def layer_kinds(cfg: dict) -> list:
    """The mixer kind of each layer, as the reference lays them out."""
    return [mixer for mixer, _ffn in _kinds(cfg)]


def mixer_matrix_params(cfg: dict, kind: str) -> int:
    """The mixer's matrices a token meets. ``gdn``: the in-projections of
    q, k, v, z and of b, a, and the out-projection (taps, decays and norms
    are no matrices); ``gqa``: the doubled q (a head's q and its gate),
    o, and the ``num_key_value_heads`` wide k, v."""
    h = cfg["hidden_size"]
    if kind == "gdn":
        keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
        return h * (2 * keys + 2 * values) \
            + h * 2 * cfg["linear_num_value_heads"] + values * h
    d = cfg["head_dim"]
    return h * d * (3 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def ffn_matrix_params(cfg: dict) -> float:
    """An expert layer: the router, the shared expert with its gate, and
    the expected ``top_k * experts_held / num_experts`` routed experts a
    token."""
    h = cfg["hidden_size"]
    held = cfg.get("experts_held", cfg["num_experts"])
    routed = cfg["num_experts_per_tok"] * held / cfg["num_experts"]
    return h * cfg["num_experts"] \
        + 3.0 * h * cfg["shared_expert_intermediate_size"] + h \
        + routed * 3.0 * h * cfg["moe_intermediate_size"]


def recurrence_flops_per_token(cfg: dict) -> float:
    """The delta rule on a K x V state: 6 K V a token and VALUE head
    (decay, read, rank-one write, query), forward."""
    return 6.0 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] \
        * cfg["linear_num_value_heads"]


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label.

    matrices   6 x the matrix parameters a token meets (above)
    GDN        the recurrence, x 3
    attention  scores and values: 2 x 2 head_dim per key and query head,
               half the square's keys a query, x 3
    head       6 x hidden x vocabulary on the labelled rows
    """
    total = 0.0
    for kind in layer_kinds(cfg):
        total += 6.0 * (mixer_matrix_params(cfg, kind)
                        + ffn_matrix_params(cfg))
        if kind == "gdn":
            total += 3.0 * recurrence_flops_per_token(cfg)
        else:
            total += 3.0 * 2.0 * 2.0 * cfg["head_dim"] \
                * cfg["num_attention_heads"] * seq / 2.0
    return total + 6.0 * cfg["hidden_size"] * cfg["vocab_size"] \
        * labelled / seq


def gdn_kernel_work(cfg: dict, batch: int, seq: int) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the delta-rule chunk kernels
    in one training step, under the roles a device trace shows. The
    SCALAR-decay recurrence's work, whatever the launch is handed: per
    call 6 K V operations a token and value head forward and twice that
    backward; the compulsory HBM traffic is q and k once a KEY head, v a
    value head, g and beta ONE float a token and value head read and o
    written once forward (all float32), and backward the same again plus
    o's cotangent read and the cotangents written: twice the forward's
    bytes."""
    calls = sum(1 for kind in layer_kinds(cfg) if kind == "gdn")
    if not calls:
        return {}
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    tokens = batch * seq
    flops = tokens * recurrence_flops_per_token(cfg)
    moved = 4.0 * tokens * (2 * hk * dk + hv + hv * (2 * dv + 1))
    return {
        "kda_chunk_fwd": {"calls": calls, "flops": calls * flops,
                          "bytes": calls * moved},
        "kda_chunk_bwd": {"calls": calls, "flops": calls * 2 * flops,
                          "bytes": calls * 2 * moved}}


def gated_attn_kernel_work(cfg: dict, batch: int, seq: int,
                           itemsize: int = 2) -> dict:
    """The attention layers' launches, as ``work_mellum2.gqa_kernel_work``
    counts a full layer's: 12 D operations a pair and query head over
    half the square, K and V once per key head. The gate is no part of
    the kernel."""
    layers = sum(1 for kind in layer_kinds(cfg) if kind == "gqa")
    if not layers:
        return {}
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = batch * seq * heads * d * itemsize          # = the output's bytes
    kv = 2 * batch * seq * kv_heads * d * itemsize
    lse = 4 * batch * seq * heads
    mm = batch * heads * seq * (seq / 2.0) * d
    return {"flash_attention_grouped": {
        "calls": 2 * layers, "flops": layers * 12.0 * mm,
        "bytes": layers * ((q + kv + q + lse)
                           + (2 * (q + kv) + 2 * q + lse))}}
