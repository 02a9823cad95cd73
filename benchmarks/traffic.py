"""The one traffic generator: every cell's traffic is a block of
parameters in its data file (``benchmarks/workloads/<cell>.json`` ->
``traffic``), read here. A new mix is a new data file, never new code.

Serving (``kind: open_loop``). The SET of request sizes and of
inter-arrival gaps is a function of the parameters alone — the i-th of n
stratified quantiles of each distribution — so every seed offers the
same work at the same mean rate; ``--seed`` only decides the order of
the prompt lengths, of the output lengths and of the gaps (three
independent permutations) and the token ids. Two seeds therefore differ
the way two hours of the same service differ, not the way two services
do. A request's latency is always timed from its DUE time.

Training (``kind: train_feed``): a pool of distinct host batches from
the seed; the step loop cycles them.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

#: draws behind each empirical quantile table (fixed: part of the yardstick)
_TABLE = 200_000


@dataclasses.dataclass
class Request:
    idx: int
    due_s: float                 # offset from the window's start
    prompt: List[int]
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), int(stream)])


def _draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` raw draws from one distribution block."""
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        out = rng.uniform(spec["min"], spec["max"], n)
    elif dist == "lognormal":
        out = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "exponential":
        out = rng.exponential(spec.get("mean", 1.0), n)
    elif dist == "gamma":            # mean 1, coefficient of variation cv
        k = 1.0 / float(spec["cv"]) ** 2
        out = rng.gamma(k, 1.0 / k, n) * spec.get("mean", 1.0)
    elif dist == "mixture":
        parts = spec["parts"]
        shares = np.asarray([p["share"] for p in parts], float)
        which = rng.choice(len(parts), n, p=shares / shares.sum())
        out = np.empty(n)
        for j, part in enumerate(parts):
            sel = which == j
            out[sel] = _draw(part, rng, int(sel.sum()))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec or "max" in spec:
        out = np.clip(out, spec.get("min", -np.inf), spec.get("max", np.inf))
    return out


def stratified(spec: dict, n: int) -> np.ndarray:
    """The (i + 0.5) / n quantiles, i < n, of a distribution block, read
    off a fixed empirical table — the same n values whatever the seed."""
    table = np.sort(_draw(spec, np.random.default_rng(0), _TABLE))
    at = ((np.arange(n) + 0.5) / n * _TABLE).astype(np.int64)
    return table[at]


_ARRIVALS = {"poisson": {"dist": "exponential"},
             "gamma": {"dist": "gamma"}}


def arrival_offsets(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start, ascending) of the
    ``round(rate_rps * seconds)`` requests of one window."""
    n = max(1, int(round(float(traffic["rate_rps"]) * float(seconds))))
    arr = dict(traffic.get("arrivals") or {"process": "poisson"})
    spec = dict(_ARRIVALS[arr.pop("process")], **arr)
    gaps = stratified(spec, n)
    gaps = _rng(seed, 1).permutation(gaps)
    due = np.cumsum(gaps)
    # the n gaps of an exact-rate schedule sum to the window; keep the
    # last request due inside it
    return due * (float(seconds) * n / (n + 1.0) / due[-1])


def open_loop_requests(traffic: dict, vocab_size: int, seconds: float,
                       seed: int) -> List[Request]:
    due = arrival_offsets(traffic, seconds, seed)
    n = len(due)
    prompts = np.rint(stratified(traffic["prompt_tokens"], n)).astype(int)
    outputs = np.rint(stratified(traffic["output_tokens"], n)).astype(int)
    prompts = _rng(seed, 2).permutation(prompts)
    outputs = _rng(seed, 3).permutation(outputs)
    cap = traffic.get("max_total_tokens")
    if cap is not None:
        outputs = np.minimum(outputs, np.maximum(1, int(cap) - prompts))
    ids = _rng(seed, 4)
    prefix = _shared_prefixes(traffic.get("shared_prefix"), vocab_size, seed)
    out = []
    for i in range(n):
        body = ids.integers(0, vocab_size, int(prompts[i])).tolist()
        if prefix is not None and ids.random() < prefix[1]:
            # the prefix takes the place of the prompt's first tokens
            head = prefix[0][int(ids.integers(0, len(prefix[0])))]
            body = head + body[:max(1, int(prompts[i]) - len(head))]
        out.append(Request(i, float(due[i]), body, int(outputs[i])))
    return out


def _shared_prefixes(spec: Optional[dict], vocab_size: int, seed: int):
    """``{"tokens": T, "groups": G, "share": s}``: a share ``s`` of the
    requests start with one of ``G`` fixed ``T``-token prefixes."""
    if not spec:
        return None
    rng = _rng(seed, 5)
    groups = [rng.integers(0, vocab_size, int(spec["tokens"])).tolist()
              for _ in range(int(spec.get("groups", 1)))]
    return groups, float(spec.get("share", 1.0))


def length_histogram(requests: List[Request], edges) -> dict:
    """Prompt-length counts per bucket, for the run's earlier lines."""
    lens = np.asarray([len(r.prompt) for r in requests])
    lo = 0
    out = {}
    for hi in edges:
        out[f"<={hi}"] = int(((lens > lo) & (lens <= hi)).sum())
        lo = hi
    return out


def train_batches(traffic: dict, vocab_size: int, seed: int) -> list:
    """``host_batches`` distinct host batches of one BERT pretraining
    feed: (input ids, segment ids, MLM labels with ``labelled`` positions
    per row and -100 elsewhere, NSP labels), int32 numpy, every row
    different."""
    rng = _rng(seed, 6)
    b, s = int(traffic["batch"]), int(traffic["seq"])
    k = int(traffic["labelled"])
    out = []
    for _ in range(int(traffic["host_batches"])):
        ids = rng.integers(0, vocab_size, (b, s), dtype=np.int32)
        split = rng.integers(s // 4, 3 * s // 4, (b, 1))
        segments = (np.arange(s)[None, :] >= split).astype(np.int32)
        labels = np.full((b, s), -100, np.int32)
        where = np.argsort(rng.random((b, s)), axis=1)[:, :k]
        np.put_along_axis(
            labels, where,
            rng.integers(0, vocab_size, (b, k), dtype=np.int32), axis=1)
        nsp = rng.integers(0, 2, (b,), dtype=np.int32)
        out.append((ids, segments, labels, nsp))
    return out
