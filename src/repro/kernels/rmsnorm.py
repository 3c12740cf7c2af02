"""Pallas TPU fused RMSNorm.

One pass over rows: grid tiles the (flattened) row dimension; each program
normalizes a (block_rows, D) tile in VMEM with fp32 statistics. D sits on
the lane dimension (multiple-of-128 friendly for every assigned arch).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                     # (rows, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    w = 1.0 + w_ref[...].astype(jnp.float32)
    o_ref[...] = (normed * w[None, :]).astype(o_ref.dtype)


def rmsnorm(
    x: jnp.ndarray,
    weight: jnp.ndarray,       # (D,)
    eps: float = 1e-6,
    *,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    orig_shape = x.shape
    D = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, D)
    block_rows = max(min(block_rows, rows), 1)
    nr = pl.cdiv(rows, block_rows)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda ri: (ri, 0)),
            pl.BlockSpec((D,), lambda ri: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda ri: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, weight)
    return out.reshape(orig_shape)
