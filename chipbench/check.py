"""The comparison that decides ``correct``.

After the window, a sample of the requests it finished, drawn from the
seed and always holding the one with the most served tokens, is run
through the float32 reference: each prompt followed by its served tokens.
For every served token the reference gives its logits at the position that
produced it, and the number compared is the widest gap by which a served
token's logit lies below the reference's best there (``served_gap``).
Serving is greedy, so a sound program serves a token the reference ranks
first, or one within rounding of it.

The control puts the reference in the program's place at the precision
below the configuration's (float8) and reads, at the same positions, the
gap of the token the control ranks first.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import reference
from .weights import Dims


@dataclass
class Served:
    prompt: np.ndarray
    tokens: np.ndarray


def sample(served: Sequence[Served], seed: int, count: int) -> List[Served]:
    """The request with the most served tokens (then the longest prompt),
    and ``count - 1`` others drawn from ``seed``."""
    if not served:
        raise ValueError("no finished request to compare")
    longest = max(range(len(served)),
                  key=lambda i: (len(served[i].tokens), len(served[i].prompt)))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(count - 1, len(rest)), replace=False)
    return [served[longest]] + [served[rest[int(i)]] for i in sorted(pick)]


def _inputs(items: Sequence[Served]) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    seqs, rows = [], []
    for it in items:
        p, n = len(it.prompt), len(it.tokens)
        seqs.append(np.concatenate([it.prompt, it.tokens[:-1]]).astype(np.int32))
        rows.append(np.arange(p - 1, p + n - 1))
    return seqs, rows


def gaps(ref_logits, chosen) -> np.ndarray:
    """Per row: the reference's best logit minus that of the chosen token
    (computed where the logits are, fetched as one small array)."""
    import jax.numpy as jnp

    ref_logits = jnp.asarray(ref_logits)
    chosen = jnp.asarray(chosen, jnp.int32)
    best = ref_logits.max(axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def compare(d: Dims, seed: int, items: Sequence[Served],
            control: bool = False) -> Dict[str, float]:
    """``served_gap`` of ``items`` against the reference and, with
    ``control``, ``control_gap`` of the float8 reference at the same
    positions."""
    seqs, rows = _inputs(items)
    ref = reference.logits_at(d, seed, seqs, rows)
    served = np.concatenate([gaps(r, it.tokens) for r, it in zip(ref, items)])
    out = {
        "served_gap": float(np.max(served)) if np.all(np.isfinite(served))
        else float("inf"),
        "tokens": float(len(served)),
    }
    if control:
        low = reference.logits_at(d, seed, seqs, rows, low=True)
        ctrl = np.concatenate([gaps(r, c.argmax(axis=-1))
                               for r, c in zip(ref, low)])
        out["control_gap"] = float(np.max(ctrl))
    return out
