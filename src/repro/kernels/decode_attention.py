"""Pallas TPU decode attention: one new token vs a (ring-buffered) KV cache.

Grid = (batch, kv_blocks); the kv dimension is innermost and sequential so
the online-softmax state persists in VMEM scratch (flash-decode structure —
on TPU the kv blocks stream HBM→VMEM at full bandwidth, which is the
roofline of decode).  Each kv block carries every kv head, ``(block_s, kvh,
d)``, and the query arrives grouped as ``(kvh, rep, d)``: both end in whole
array dims, as the TPU compiler requires of blocks narrower than (8, 128),
and a static loop over the kv heads runs one ``(rep, d) x (d, block_s)``
matmul per group, so the cache streams once per request, not once per
query head. Per-batch ``lengths`` arrive as a
scalar-prefetch operand so the mask needs no HBM traffic; an optional
window re-creates the ring-cache semantics of long-context serving.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(
    lens_ref,                  # scalar prefetch: (b,) int32 valid lengths
    w_ref,                     # scalar prefetch: (1,) int32 window (0 = none)
    q_ref,                     # (1, kvh, rep, d)
    k_ref, v_ref,              # (1, block_s, kvh, d)
    o_ref,                     # (1, kvh, rep, d)
    m_ref, l_ref, acc_ref,     # VMEM scratch
    *,
    softcap: float,
    block_s: int,
    S: int,
    scale: float,
):
    bi = pl.program_id(0)
    sj = pl.program_id(1)
    ns = pl.num_programs(1)
    _, kvh, rep, d = q_ref.shape

    @pl.when(sj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[bi]
    w = w_ref[0]

    def live(pos):
        # (w <= 0) | ...: Mosaic cannot select between boolean vectors
        return (pos < length) & (pos < S) & ((w <= 0) | (pos >= length - w))

    valid = live(sj * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (rep, block_s), 1
    ))
    row_valid = live(sj * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (block_s, d), 0
    ))
    for g in range(kvh):                      # static: one MXU pass per group
        q = q_ref[0, g]                                     # (rep, d)
        k = k_ref[0, :, g, :]                               # (bs, d)
        v = v_ref[0, :, g, :]
        v = jnp.where(row_valid, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (rep, bs)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[g]                                   # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[g] = m_new
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(sj == ns - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,            # (b, 1, h, d)
    k_cache: jnp.ndarray,      # (b, S, kvh, d)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,      # (b,) int32
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    block_s: int = 512,
    kv_bound: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``kv_bound``: static upper bound on ``lengths`` (host-known).  The kv
    grid covers only ``ceil(kv_bound/block_s)`` blocks instead of the padded
    ``S``, so short-context decodes stop streaming fully-masked blocks."""
    b, _, h, d = q.shape
    S, kvh = k_cache.shape[1], k_cache.shape[2]
    rep = h // kvh
    scale = scale if scale is not None else d ** -0.5
    s_eff = S if kv_bound is None else max(min(S, int(kv_bound)), 1)
    # shrink the block to the bound too: a 16-token live context must not
    # stream a full 512-token block just because the grid has one step
    block_s = min(block_s, s_eff)
    ns = pl.cdiv(s_eff, block_s)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wval = jnp.asarray([0], jnp.int32) if window is None else jnp.asarray(
        [window], jnp.int32
    ).reshape((1,))

    kernel = functools.partial(
        _kernel, softcap=float(softcap), block_s=block_s, S=S, scale=float(scale)
    )
    group_spec = pl.BlockSpec(
        (1, kvh, rep, d), lambda bi, sj, lens, w: (bi, 0, 0, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, block_s, kvh, d), lambda bi, sj, lens, w: (bi, sj, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, ns),
        in_specs=[group_spec, kv_spec, kv_spec],
        out_specs=group_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(
        jnp.asarray(lengths, jnp.int32), wval,
        # q heads are kv-group-major (head = g*rep + r): grouping is a reshape
        q.reshape(b, kvh, rep, d), k_cache, v_cache,
    )
    return out.reshape(b, 1, h, d)
