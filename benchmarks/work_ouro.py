"""Operations, bytes and parameters of a looped decoder (one stack of
full-attention blocks with gated FFNs applied ``total_ut_steps`` times, a
head and an exit gate read after every pass) from its shapes: the
required FLOP per token of a training step (for ``mfu_pct.train``) and
what the two kernels that the loop multiplies cannot avoid: the stream
attention kernels at keys and values ``head_dim`` wide, once a block
APPLICATION (for ``ut_attn_roofline_pct.train``), and the fused
cross-entropy on the T passes' rows stacked, one loss a row out and one
cotangent a row in (for ``ut_xent_roofline_pct.train``). Beside
``work_deepseek_v3.py`` and the others. A multiply-add is two operations,
forward + backward is three times the forward; recomputed activations,
the rotation, the norms, the gate's (hidden, 1) product and whatever a
program does beyond the algorithm earn no credit.

``cfg`` is the configuration as the model is built from it.
"""
from __future__ import annotations

from benchmarks.work_mellum2 import attention_matrix_params


def block_flops_per_token(cfg: dict, seq: int) -> dict:
    """Forward FLOP a token of ONE application of one block, by part: the
    four projections, the scores and values over the causal triangle
    (2 x 2 head_dim per key and head, half of the keys), the gated FFN's
    three matrices."""
    return {
        "projections": 2.0 * attention_matrix_params(cfg),
        "attention": 2.0 * 2.0 * cfg["head_dim"] * (seq / 2.0)
        * cfg["num_attention_heads"],
        "ffn": 2.0 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]}


def head_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Forward FLOP a token of ONE pass's head, on the labelled rows."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * labelled / seq


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label: three times the
    forward of ``total_ut_steps`` passes, each all the blocks and one
    head."""
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    block = sum(block_flops_per_token(cfg, seq).values())
    return 3.0 * passes * (layers * block
                           + head_flops_per_token(cfg, seq, labelled))


def param_count(cfg: dict) -> dict:
    """Parameters by part: one layer (its four norms included),
    embedding + head, the final norm with the exit gate, and the whole
    share."""
    h = cfg["hidden_size"]
    out = {"layer": attention_matrix_params(cfg)
           + 3 * h * cfg["intermediate_size"] + 4 * h,
           "embedding_and_head": 2 * h * cfg["vocab_size"],
           "final_norm_and_gate": h + h + 1}
    out["total"] = cfg["num_hidden_layers"] * out["layer"] \
        + out["embedding_and_head"] + out["final_norm_and_gate"]
    return out


def attn_kernel_work(cfg: dict, batch: int, seq: int,
                     itemsize: int = 2) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the attention kernels in one
    training step, under the roles a device trace shows (one key head a
    query head, no window: the plain stream kernels), one launch a block
    APPLICATION: ``total_ut_steps`` x layers of them. Forward: Q K^T and
    P V at ``head_dim`` over the causal triangle, 4 D operations a pair
    and head; backward dV, dP, dQ, dK, twice that (the recomputed scores
    not counted). The compulsory HBM traffic is q, k, v read and the
    output and the float32 logsumexp written forward; the backward reads
    those and the output's cotangent and writes dq, dk, dv: twice the
    forward's arrays and the logsumexp once."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    calls = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    rows = batch * seq * heads
    flops = rows * (seq / 2.0) * 4.0 * d
    qkv = rows * 3 * d * itemsize
    out, lse = rows * d * itemsize, 4 * rows
    return {
        "flash_attention_stream_fwd": {
            "calls": calls, "flops": calls * flops,
            "bytes": calls * (qkv + out + lse)},
        "flash_attention_stream_bwd": {
            "calls": calls, "flops": calls * 2.0 * flops,
            "bytes": calls * (2 * qkv + 2 * out + lse)}}


def xent_rows_work(cfg: dict, batch: int, seq: int,
                   h_itemsize: int = 4, w_itemsize: int = 4) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the ONE fused cross-entropy
    call of a training step, on its top rung (every row but a pass's last
    is labelled): K = ``total_ut_steps`` x batch x seq rows against the
    whole table. Forward 2 K H V, backward 4 K H V (dh and dW; the
    recomputed logits not counted). Bytes as ``fused_xent._work``
    declares them: K rows of h and their int32 labels, the table and its
    float32 bias read, the logsumexp and the label's logit written, and a
    float32 loss a row out; the backward reads those with the logsumexp
    and a float32 cotangent a row and writes dh, dW and db."""
    hd, v = cfg["hidden_size"], cfg["vocab_size"]
    k = cfg["total_ut_steps"] * batch * seq
    rows_h = k * hd * h_itemsize
    table = v * hd * w_itemsize + 4 * v
    read = rows_h + 4 * k + table
    return {
        "fused_xent_fwd": {"calls": 1, "flops": 2.0 * k * hd * v,
                           "bytes": read + 8 * k + 4 * k},
        "fused_xent_bwd": {"calls": 1, "flops": 4.0 * k * hd * v,
                           "bytes": read + 8 * k + 4 * k + rows_h + table}}
