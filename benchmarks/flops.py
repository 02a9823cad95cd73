"""Operations and sizes from shapes — the benchmark's own arithmetic.

Every function takes the configuration as the dict of its data file and
counts what the ALGORITHM requires (a multiply-add is two operations;
forward + backward is three times the forward), never what a particular
program happens to execute: recomputed activations, a vocabulary head
applied to unlabelled positions or a padded vocabulary earn no credit.
"""
from __future__ import annotations


def bert_encoder_params(cfg: dict) -> int:
    """Matrix parameters of the encoder stack (what 6 x params counts)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * i)


def bert_train_flops_per_token(cfg: dict, seq: int, labelled: int) -> float:
    """Required FLOP per input token of one BERT pretraining step.

    encoder matmuls   6 x encoder parameters
    attention         forward 2 (QK^T) + 2 (PV) FLOP per key per hidden
                      unit = 4 * seq * hidden per token and layer, x 3
    MLM head          transform (H x H) and tied decoder (H x V, the
                      PUBLISHED vocabulary) on the labelled positions only
    pooler + NSP      one position per sequence
    """
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    dense = 6.0 * bert_encoder_params(cfg)
    attention = 3.0 * layers * 4.0 * seq * h
    head = 6.0 * (h * h + h * v) * labelled / seq
    pooled = 6.0 * (h * h + 2 * h) / seq
    return dense + attention + head + pooled


def decoder_param_count(cfg: dict) -> int:
    """Parameters of the decode engine's model at a configuration's sizes:
    per block q, k, v, o (E x E), two FFN matrices (E x F) and two norm
    scales; token and position embeddings, final norm, untied head."""
    e, f = cfg["hidden_size"], cfg["ffn_dim"]
    v, ctx = cfg["vocab_size"], cfg["max_position_embeddings"]
    block = 4 * e * e + 2 * e * f + 2 * e
    return cfg["num_hidden_layers"] * block + 2 * v * e + ctx * e + e


def decoder_kv_page_bytes(cfg: dict, page_size: int, itemsize: int) -> int:
    """Bytes of one KV page over all layers (K and V)."""
    return (cfg["num_hidden_layers"] * 2 * page_size * cfg["hidden_size"]
            * itemsize)
