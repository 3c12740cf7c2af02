"""Operations and bytes the served requests need, from their lengths.

Counted from the traffic's live lengths and the configuration's shapes,
never from a kernel's grid, so a kernel that skips or pads work cannot
change what it is credited with.  A request with a ``P``-token prompt
and ``n`` served tokens feeds ``P + n - 1`` tokens through the layers
(the last served token is produced, never fed), computes the head for
its ``n`` served tokens only, and the token at position ``t`` attends
``t + 1`` keys.  Work that preemption throws away and recomputes is not
counted.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

from .weights import Dims

BF16 = 2


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Work") -> "Work":
        self.flops += other.flops
        self.bytes += other.bytes
        return self


def layer_matmul_params(d: Dims) -> int:
    """Parameters of one layer's matmuls (attention and gated MLP)."""
    attn = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def _pairs(first: int, last: int) -> int:
    """Sum of ``t + 1`` over positions ``first <= t < last``."""
    return (last * (last + 1) - first * (first + 1)) // 2


def prefill_flops(d: Dims, prompt: int) -> float:
    """Model FLOPs of one prompt: every prompt token through every layer,
    causal attention over the prompt, and the head for its first token."""
    per_layer = 2 * layer_matmul_params(d) * prompt + 4 * d.heads * d.head_dim * _pairs(0, prompt)
    return float(d.layers * per_layer + 2 * d.d_model * d.vocab)


def decode_flops(d: Dims, prompt: int, served: int) -> float:
    """Model FLOPs of the decode steps that produce tokens 2..n."""
    steps = max(served - 1, 0)
    per_layer = (2 * layer_matmul_params(d) * steps
                 + 4 * d.heads * d.head_dim * _pairs(prompt, prompt + steps))
    return float(d.layers * per_layer + 2 * d.d_model * d.vocab * steps)


def model_flops(d: Dims, requests: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """(prefill, decode) model FLOPs of ``(prompt, served)`` requests."""
    pre = dec = 0.0
    for prompt, served in requests:
        pre += prefill_flops(d, prompt)
        dec += decode_flops(d, prompt, served)
    return pre, dec


def paged_attention(d: Dims, prompt: int, served: int) -> Work:
    """Decode attention of one request over its cached keys and values:
    the step at position ``t`` reads ``t + 1`` tokens of K and V in every
    layer and reads ``q``/writes ``o`` for its query heads."""
    steps = max(served - 1, 0)
    keys = _pairs(prompt, prompt + steps)
    flops = 4 * d.heads * d.head_dim * keys
    kv = 2 * d.kv_heads * d.head_dim * BF16 * keys
    qo = 2 * d.heads * d.head_dim * BF16 * steps
    return Work(float(d.layers * flops), float(d.layers * (kv + qo)))


def varlen_prefill(d: Dims, prompt: int) -> Work:
    """Causal prefill attention of one prompt: every query attends its
    prefix; q, k, v are read and o written once per token and layer."""
    flops = 4 * d.heads * d.head_dim * _pairs(0, prompt)
    io = (2 * d.heads + 2 * d.kv_heads) * d.head_dim * BF16 * prompt
    return Work(float(d.layers * flops), float(d.layers * io))


def roofline_s(w: Work, peak_flops: float, peak_bytes: float) -> Tuple[float, str]:
    """Least time the chip needs for ``w``, and which peak bounds it."""
    tf, tb = w.flops / peak_flops, w.bytes / peak_bytes
    return (tf, "compute") if tf >= tb else (tb, "memory")


def served(rounds) -> Iterable[Tuple[int, int]]:
    """(prompt length, served tokens) of every completed request."""
    for rd in rounds:
        for (prompt, _), r in zip(rd.requests, rd.stats.results):
            if r.status == "completed":
                yield len(prompt), len(r.tokens)


def kernel_roofline(run, kernel, match) -> "float | None":
    """Share (%) of its roofline that a kernel reached in the traced round:
    the least time the chip needs for the round's ``kernel`` work over
    the summed device time of the trace's operations that ``match``
    accepts.  None where the trace holds no such operation.

    Over ``tp`` chips each runs its share of the work in about the same
    time ``t``, and the trace sums the time over every chip: the whole
    work over (one chip's peak x ``tp`` x ``t``) is then each chip's share
    of its own roofline, with no count of chips here."""
    if run.trace is None or run.traced_round is None or not run.peak:
        return None
    secs = run.trace.seconds_matching(match)
    if secs <= 0:
        return None
    total = Work()
    for prompt, n in served([run.traced_round]):
        total += kernel(run.dims, prompt, n)
    least, _ = roofline_s(total, run.peak["bf16_flops_per_s"],
                          run.peak["hbm_bytes_per_s"])
    return 100.0 * least / secs
