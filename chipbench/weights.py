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


def table(key, name: str, rows: int, cols: int, dtype=jnp.bfloat16,
          shards: int = 1):
    """A (rows, cols) table drawn in ``TABLE_BLOCKS`` row blocks.

    The rows lie in ``shards`` equal parts, one a device: each step of
    the loop draws one block of every part side by side, so each device
    draws only blocks of its own rows.  Block ``b`` is the same draw
    whatever ``shards`` is."""
    if rows % TABLE_BLOCKS or TABLE_BLOCKS % shards:
        raise ValueError(f"{name}: {rows} rows do not split into "
                         f"{TABLE_BLOCKS} blocks over {shards} shards")
    shape = (rows // TABLE_BLOCKS, cols)
    per = TABLE_BLOCKS // shards
    # (per, shards, *shape): step j draws blocks i * per + j, i < shards
    steps = jax.lax.map(
        lambda j: jax.vmap(lambda i: _leaf(key, name, i * per + j, shape,
                                           STD_IN, dtype))(jnp.arange(shards)),
        jnp.arange(per),
    )
    return steps.swapaxes(0, 1).reshape(rows, cols)


def final_norm(key, d: Dims, dtype=jnp.bfloat16):
    return _leaf(key, "final_norm", 0, (d.d_model,), STD_NORM, dtype)


def _vocab_shards(sharding, shape, axis: int) -> int:
    """Parts the vocabulary axis ``axis`` of a table lies in."""
    return shape[axis] // sharding.shard_shape(shape)[axis]


def params_fn(d: Dims, dtype=jnp.bfloat16, shardings=None):
    """The jitted call that makes every weight of the model from a root
    key, stacked as the program holds them.

    ``shardings``, a tree of shardings like the weights' (the program's
    tensor-parallel layout), makes every leaf in its sharding: each device
    draws only its share, the float32 draws included, and the values are
    those of the unsharded call (``jax_threefry_partitionable``)."""
    V, D = d.vocab, d.d_model
    embed_shards = head_shards = 1
    if shardings is not None:
        embed_shards = _vocab_shards(shardings["embed"], (V, D), 0)
        head_shards = _vocab_shards(shardings["lm_head"], (D, V), 1)

    def build(key):
        return {
            "embed": table(key, "embed", V, D, dtype, embed_shards),
            "blocks": jax.lax.map(lambda l: layer(key, l, d, dtype),
                                  jnp.arange(d.layers)),
            "final_norm": final_norm(key, d, dtype),
            # stored (vocab, d_model) like the embedding, served transposed
            "lm_head": table(key, "lm_head", V, D, dtype, head_shards).T,
        }

    # None places every leaf as a plain jit does
    return jax.jit(build, out_shardings=shardings)


def make_params(d: Dims, seed: int, dtype=jnp.bfloat16, shardings=None):
    """Every weight of the model made on the device from ``seed`` by one
    jitted call (``params_fn``)."""
    return params_fn(d, dtype, shardings)(root_key(seed))
