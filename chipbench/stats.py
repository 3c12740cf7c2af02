"""End-to-end metric arithmetic over all the rounds of a window.

A rate is all the work of the window over all of its time; a tail is the
tail of every request of every round, never a statistic of per-round
pieces.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence

import numpy as np


def p95(values: Sequence[float]) -> float:
    if not len(values):
        raise ValueError("a percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), 95.0))


def end_to_end(results: Iterable, window_s: float) -> Dict[str, float]:
    """``output_tok_s``, ``ttft_p95_ms`` and ``tpot_p95_ms`` of the
    completed requests in ``results`` (objects with ``status``,
    ``tokens``, ``ttft_s`` and ``latency_s``), over a window of
    ``window_s`` seconds."""
    done = [r for r in results if r.status == "completed"]
    tokens = sum(len(r.tokens) for r in done)
    ttft = [r.ttft_s * 1e3 for r in done]
    tpot: List[float] = [
        (r.latency_s - r.ttft_s) / (len(r.tokens) - 1) * 1e3
        for r in done if len(r.tokens) >= 2
    ]
    return {
        "output_tok_s": tokens / window_s,
        "ttft_p95_ms": p95(ttft),
        "tpot_p95_ms": p95(tpot),
    }
