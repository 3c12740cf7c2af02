"""The one traffic generator: bursts of requests from a mix file's numbers.

A mix file (``traffic/<name>.json``) gives the distribution of prompt and
output lengths: ``lognormal`` (``median``, ``sigma``) or ``loguniform``,
each clipped to ``[min, max]``, and names the published trace or dataset
statistic its numbers come from (``source``), which of them it takes from
there (``sourced``) and which it assumes (``assumed``).  A burst of ``n`` requests takes its
lengths from the distribution's quantiles at ``(i + 0.5) / n``, so every
burst holds the same set of sizes whatever the seed: the seed changes the
prompts' token ids, not the work.  Prompt and output lengths are paired,
and the burst ordered, by permutations drawn from the mix's own
``order_seed`` and the round's index, so successive rounds differ from each
other and every run sees the same rounds.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np


def quantile(dist: Dict[str, Any], u: float) -> int:
    """The length at quantile ``u`` of ``dist``, rounded and clipped."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        x = float(dist["median"]) * math.exp(
            float(dist["sigma"]) * NormalDist().inv_cdf(u)
        )
    elif kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(int(round(x)), lo), hi)


def burst_lengths(mix: Dict[str, Any], n: int) -> Tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of a burst of ``n``, sorted by
    quantile and unpaired."""
    u = [(i + 0.5) / n for i in range(n)]
    prompts = np.array([quantile(mix["prompt"], q) for q in u], np.int64)
    outputs = np.array([quantile(mix["output"], q) for q in u], np.int64)
    return prompts, outputs


def max_len(mix: Dict[str, Any]) -> int:
    """Longest prompt plus longest output: the engine's ``max_seq``."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def burst(mix: Dict[str, Any], n: int, round_index: int, seed: int,
          vocab: int) -> List[Tuple[np.ndarray, int]]:
    """Round ``round_index``'s burst: ``n`` (prompt token ids, output
    length) pairs.  Sizes and order depend on the mix and the round only;
    the token ids on ``seed`` and the round."""
    prompts, outputs = burst_lengths(mix, n)
    order_rng = np.random.default_rng([int(mix["order_seed"]), round_index])
    outputs = outputs[order_rng.permutation(n)]
    order = order_rng.permutation(n)
    tok_rng = np.random.default_rng([int(seed), round_index])
    out = []
    for i in order:
        ids = tok_rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32)
        out.append((ids, int(outputs[i])))
    return out
