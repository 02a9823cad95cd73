"""Operations, bytes and parameters of a Nemotron-H style decoder (blocks
of ONE sublayer: Mamba-2, attention, experts or a plain FFN) from its
shapes: the required FLOP per token of a training step (for
``mfu_pct.train``), what the state-space scan's kernels cannot avoid
(for ``ssd_roofline_pct.train``) and the parameters a chip's share
holds. Beside ``work_kimi_linear.py`` and ``work_mellum2.py``. A
multiply-add is two operations, forward + backward is three times the
forward; recomputed activations, the rows of a ladder rung that hold no
pair and whatever a program does beyond the algorithm earn no credit.

``cfg`` is the configuration as the model is built from it: the router's
``n_routed_experts`` outputs, ``experts_held`` experts on this chip.
"""
from __future__ import annotations

from benchmarks.reference.nemotron_h import layer_kinds

#: the chunk the scan's required work is counted at: the source's
#: ``chunk_size`` where the configuration says one
CHUNK = 128


def _mamba_widths(cfg: dict) -> tuple:
    """(inner, B and C together, heads)."""
    heads = cfg["mamba_num_heads"]
    return (heads * cfg["mamba_head_dim"],
            2 * cfg["n_groups"] * cfg["ssm_state_size"], heads)


def mamba_matrix_params(cfg: dict) -> int:
    """The in-projection ([z | x B C | dt]) and the out-projection; the
    convolution, the norm and the per-head vectors are no matrices."""
    inner, bc, heads = _mamba_widths(cfg)
    return cfg["hidden_size"] * (2 * inner + bc + heads) \
        + inner * cfg["hidden_size"]


def attention_matrix_params(cfg: dict) -> int:
    """q, k, v, o: the key and value projections are
    ``num_key_value_heads`` wide."""
    return 2 * cfg["hidden_size"] * cfg["head_dim"] * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def scan_flops_per_token(cfg: dict, chunk: int = CHUNK) -> float:
    """The chunked scan, forward, a token and layer: ``C B^T`` 2 Q N a
    group, and a head 2 Q P for the chunk's own block + 4 N P for the
    state in and out; whole Q x Q blocks counted."""
    p, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    return cfg["n_groups"] * 2.0 * chunk * n \
        + cfg["mamba_num_heads"] * (2.0 * chunk * p + 4.0 * n * p)


def layer_flops_per_token(cfg: dict, kind: str, seq: int) -> float:
    """Forward FLOP a token of one layer of ``kind``."""
    h = cfg["hidden_size"]
    if kind == "mamba2":
        return 2.0 * mamba_matrix_params(cfg) + scan_flops_per_token(
            cfg, cfg.get("chunk_size", CHUNK))
    if kind == "attention":
        # scores and values: 2 x 2 head_dim per key and query head, half
        # of the keys under the causal mask
        return 2.0 * attention_matrix_params(cfg) + 2.0 * 2.0 \
            * cfg["head_dim"] * cfg["num_attention_heads"] * seq / 2.0
    if kind == "dense":
        return 2.0 * 2 * h * cfg["intermediate_size"]
    held = cfg.get("experts_held", cfg["n_routed_experts"])
    picks = cfg["num_experts_per_tok"] * held / cfg["n_routed_experts"]
    return 2.0 * (h * cfg["n_routed_experts"]
                  + cfg["n_shared_experts"] * 2 * h
                  * cfg["moe_shared_expert_intermediate_size"]
                  + picks * 2 * h * cfg["moe_intermediate_size"])


def train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one training step at ``seq``
    tokens a row, ``labelled`` of them with a label: three times the
    layers' forward (matrices 2 x their parameters a token meets: of the
    routed experts the expected ``top_k * experts_held /
    n_routed_experts``; the scan and the attention scores as above), and
    the head's 6 x hidden x vocabulary on the labelled rows."""
    layers = sum(layer_flops_per_token(cfg, kind, seq)
                 for kind in layer_kinds(cfg))
    return 3.0 * layers \
        + 6.0 * cfg["hidden_size"] * cfg["vocab_size"] * labelled / seq


def param_count(cfg: dict) -> dict:
    """Parameters by part: one layer of each kind (its norm included),
    embedding + head, and the whole share."""
    h = cfg["hidden_size"]
    inner, bc, heads = _mamba_widths(cfg)
    held = cfg.get("experts_held", cfg["n_routed_experts"])
    expert = 2 * h * cfg["moe_intermediate_size"]
    out = {
        "mamba2": mamba_matrix_params(cfg)
        + (cfg["conv_kernel"] + 1) * (inner + bc) + 3 * heads + inner + h,
        "attention": attention_matrix_params(cfg) + h,
        "moe": h * cfg["n_routed_experts"] + cfg["n_shared_experts"] * 2
        * h * cfg["moe_shared_expert_intermediate_size"] + held * expert + h,
        "dense": 2 * h * cfg["intermediate_size"] + h,
        "embedding_and_head": 2 * h * cfg["vocab_size"]}
    out["total"] = out["embedding_and_head"] + h \
        + sum(out[kind] for kind in layer_kinds(cfg))
    return out


def ssd_kernel_work(cfg: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """{role: {"calls", "flops", "bytes"}} of the scan's kernels in one
    training step, under the ONE role a device trace shows for a
    launch's forward and backward (two calls a layer). Forward the work
    of :func:`scan_flops_per_token`, twice that backward; the compulsory
    HBM traffic is u, B, C (``itemsize`` bytes) and delta (float32) read
    and y written once forward, twice that backward. A recomputed forward
    adds seconds and no work."""
    layers = sum(1 for kind in layer_kinds(cfg) if kind == "mamba2")
    inner, bc, heads = _mamba_widths(cfg)
    tokens = batch * seq
    flops = tokens * scan_flops_per_token(cfg, cfg.get("chunk_size", CHUNK))
    moved = tokens * (itemsize * (2 * inner + bc) + 4 * heads)
    return {"ssd_chunk": {"calls": 2 * layers, "flops": layers * 3 * flops,
                          "bytes": layers * 3.0 * moved}}
