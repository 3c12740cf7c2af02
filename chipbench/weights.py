"""A configuration's sizes, and its random weights made from ``--seed``.

The weights are the benchmark's, not the program's: the same generator
feeds the program (all layers stacked, in one jitted call on the device)
and the reference (one layer at a time, in float32), so the reference
takes nothing the program made.  Every leaf is ``normal * std`` drawn in
float32 from a key folded from the seed, the leaf's name and the layer,
then rounded to the served dtype.  RMSNorm gains are stored as offsets
from 1, the program's convention (``x / rms(x) * (1 + w)``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

LEAF_IDS = {
    "embed": 1, "ln1": 2, "wq": 3, "wk": 4, "wv": 5, "wo": 6, "ln2": 7,
    "w_gate": 8, "w_up": 9, "w_down": 10, "final_norm": 11, "lm_head": 12,
}
STD_IN = 0.02
STD_NORM = 0.1
# the embedding and head are drawn in row blocks, so no float32 copy of a
# whole (vocab, d_model) table is ever live on the device
TABLE_BLOCKS = 16


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    norm_eps: float
    rope_theta: float

    @property
    def std_out(self) -> float:
        return STD_IN / math.sqrt(2 * self.layers)


def dims(config: Dict[str, Any]) -> Dims:
    """Sizes of a configuration file, read through its ``keys`` map (the
    file keeps the source's own key names)."""
    k = config["keys"]
    heads = int(config[k["heads"]])
    d_model = int(config[k["d_model"]])
    head_dim = config.get(k["head_dim"]) if k.get("head_dim") else None
    return Dims(
        layers=int(config[k["layers"]]),
        d_model=d_model,
        d_ff=int(config[k["d_ff"]]),
        heads=heads,
        kv_heads=int(config[k["kv_heads"]]),
        head_dim=int(head_dim) if head_dim else d_model // heads,
        vocab=int(config[k["vocab"]]),
        norm_eps=float(config[k["norm_eps"]]),
        rope_theta=float(config["rope_theta"]),
    )


def root_key(seed: int) -> jax.Array:
    """A key from any whole-number seed, however many bits it has."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(
        jnp.asarray(words, jnp.uint32), impl="threefry2x32"
    )


def _leaf(key, name: str, index, shape, std: float, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def layer(key, l, d: Dims, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Layer ``l``'s weights in the program's tree layout."""
    D, F, H, KV, dh = d.d_model, d.d_ff, d.heads, d.kv_heads, d.head_dim
    so = d.std_out
    return {
        "ln1": _leaf(key, "ln1", l, (D,), STD_NORM, dtype),
        "attn": {
            "wq": _leaf(key, "wq", l, (D, H, dh), STD_IN, dtype),
            "wk": _leaf(key, "wk", l, (D, KV, dh), STD_IN, dtype),
            "wv": _leaf(key, "wv", l, (D, KV, dh), STD_IN, dtype),
            "wo": _leaf(key, "wo", l, (H, dh, D), so, dtype),
        },
        "ln2": _leaf(key, "ln2", l, (D,), STD_NORM, dtype),
        "mlp": {
            "w_gate": _leaf(key, "w_gate", l, (D, F), STD_IN, dtype),
            "w_up": _leaf(key, "w_up", l, (D, F), STD_IN, dtype),
            "w_down": _leaf(key, "w_down", l, (F, D), so, dtype),
        },
    }


def table(key, name: str, rows: int, cols: int, dtype=jnp.bfloat16):
    """A (rows, cols) table drawn in ``TABLE_BLOCKS`` row blocks."""
    if rows % TABLE_BLOCKS:
        raise ValueError(f"{name}: {rows} rows do not split into "
                         f"{TABLE_BLOCKS} blocks")
    blocks = jax.lax.map(
        lambda b: _leaf(key, name, b, (rows // TABLE_BLOCKS, cols), STD_IN,
                        dtype),
        jnp.arange(TABLE_BLOCKS),
    )
    return blocks.reshape(rows, cols)


def final_norm(key, d: Dims, dtype=jnp.bfloat16):
    return _leaf(key, "final_norm", 0, (d.d_model,), STD_NORM, dtype)


def make_params(d: Dims, seed: int, dtype=jnp.bfloat16):
    """Every weight of the model, stacked as the program holds them, made
    on the device by one jitted call."""

    def build(key):
        return {
            "embed": table(key, "embed", d.vocab, d.d_model, dtype),
            "blocks": jax.lax.map(lambda l: layer(key, l, d, dtype),
                                  jnp.arange(d.layers)),
            "final_norm": final_norm(key, d, dtype),
            # stored (vocab, d_model) like the embedding, served transposed
            "lm_head": table(key, "lm_head", d.vocab, d.d_model, dtype).T,
        }

    return jax.jit(build)(root_key(seed))
