"""The generator of causal-LM training feeds (``kind: lm_feed``), beside
``traffic.py``, which generates BERT's feeds and the serving arrivals. A
cell's traffic is still a block of parameters in its data file; this
module only reads them.

``lm_feed``: ``host_batches`` distinct host batches of ``batch`` rows of
``seq`` token ids, one document a row (no boundary inside it). Ids
follow a Zipf law over the vocabulary, ``p(rank r) ~ r ** -zipf_exponent``
— the unigram law of text, so that a few ids are very frequent and
routing by token is as uneven as it is on text — and which id has which
rank is a permutation drawn from ``--seed``. The labels are the next
token of the row; the row's last position has none (-100).
"""
from __future__ import annotations

import numpy as np

from benchmarks.traffic import _rng


def lm_batches(traffic: dict, vocab_size: int, seed: int) -> list:
    """[(ids, labels)] int32 numpy, each (batch, seq)."""
    if traffic["kind"] != "lm_feed":
        raise ValueError(f"lm_traffic generates lm_feed, not "
                         f"{traffic['kind']!r}")
    rng = _rng(seed, 7)
    b, s = int(traffic["batch"]), int(traffic["seq"])
    law = np.arange(1, vocab_size + 1, dtype=np.float64) \
        ** -float(traffic["zipf_exponent"])
    id_of_rank = rng.permutation(vocab_size).astype(np.int32)
    out = []
    for _ in range(int(traffic["host_batches"])):
        ranks = rng.choice(vocab_size, size=(b, s), p=law / law.sum())
        ids = id_of_rank[ranks]
        labels = np.full((b, s), -100, np.int32)
        labels[:, :-1] = ids[:, 1:]
        out.append((ids, labels))
    return out
